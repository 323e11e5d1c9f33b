"""The gated delta rule (``ops/deltanet.py``), the Gated DeltaNet mixer
and the pieces Qwen3-Next added to shared operators, on the CPU at small
sizes: the chunked rule against the recurrence it stands for, forward
and every gradient, at two chunk sizes and a length that is no multiple
of either; the mixer against plain expressions; the gate-norm's norm
before the gate and the convolution without a bias, in ``jnp`` and in
the Pallas passes (the interpreter); partial rotary; the attention's
output gate; the shared expert's gate."""
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import deltanet, pallas_kernels, ssm
from mxnet_tpu.ops.contrib import rope
from mxnet_tpu.ops.deltanet import gated_delta_rule, gdn_mixer

HK, HV, DK, DV = 2, 4, 8, 6


def recurrence(q, k, v, g, beta):
    """One position at a time: the definition.  Value head h reads key
    head h // (H // Hk)."""
    b, L, Hk, dk = q.shape
    H = v.shape[2]
    q, k = (jnp.repeat(a, H // Hk, axis=2) for a in (q, k))

    def step(S, at):
        qt, kt, vt, gt, bt = at
        S = jnp.exp(gt)[..., None, None] * S
        predicted = jnp.einsum("bhkv,bhk->bhv", S, kt)
        S = S + jnp.einsum("bhk,bhv->bhkv", kt,
                           bt[..., None] * (vt - predicted))
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((b, H, dk, v.shape[-1])),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _operands(L, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(k[0], (b, L, HK, DK))),
            unit(jax.random.normal(k[1], (b, L, HK, DK))),
            jax.random.normal(k[2], (b, L, HV, DV)),
            -0.5 * jnp.abs(jax.random.normal(k[3], (b, L, HV))),
            jax.nn.sigmoid(jax.random.normal(k[4], (b, L, HV))))


def _gap(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("L", [64, 75], ids=["whole_chunks", "ragged"])
def test_chunked_rule_is_the_recurrence(L, chunk):
    """The value and the gradient in each of the five operands."""
    ops = _operands(L)
    w = jax.random.normal(jax.random.PRNGKey(7), ops[2].shape)
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(
            lambda *a: (recurrence(*a) * w).sum(), argnums=range(5))(*ops)
        got = jax.value_and_grad(
            lambda *a: (gated_delta_rule(*a, chunk=chunk) * w).sum(),
            argnums=range(5))(*ops)
        o = gated_delta_rule(*ops, chunk=chunk)
        assert _gap(o, recurrence(*ops)) < 1e-6
    assert o.shape == ops[2].shape
    assert abs(got[0] - want[0]) <= 1e-5 * abs(want[0])
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got[1], want[1]):
        assert a.shape == b.shape, name
        assert _gap(a, b) < 1e-5, name


def test_two_chunk_sizes_agree_and_the_decay_and_the_step_matter():
    ops = _operands(96, seed=3)
    with jax.default_matmul_precision("highest"):
        a = gated_delta_rule(*ops, chunk=16)
        b = gated_delta_rule(*ops, chunk=64)
        kept = gated_delta_rule(*ops[:3], jnp.zeros_like(ops[3]), ops[4],
                                chunk=16)
        whole = gated_delta_rule(*ops[:4], jnp.ones_like(ops[4]), chunk=16)
    assert _gap(a, b) < 1e-6
    assert _gap(kept, a) > 0.05 and _gap(whole, a) > 0.05


def test_the_rule_keeps_its_operands_and_nothing_else():
    """What the ``custom_vjp`` carries from its forward to its backward
    pass is its five operands: no state of any chunk."""
    ops = [a.reshape(a.shape[:2] + (HK, -1) + a.shape[3:])
           if i in (2, 3, 4) else a for i, a in enumerate(_operands(64))]
    _, kept = deltanet._rule_fwd(*ops, 16)
    assert len(kept) == 5 and all(a is b for a, b in zip(kept, ops))
    _, vjp = jax.vjp(lambda *a: gated_delta_rule(*a, chunk=16),
                     *_operands(64))
    held = sorted(a.size for a in jax.tree_util.tree_leaves(vjp)
                  if hasattr(a, "shape") and a.size > 1)
    assert max(held) <= 2 * 64 * HV * DV


def test_the_inverse_is_the_triangular_solve():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (3, 16, 16)), -1)
    A = 0.3 * A
    inv = deltanet._inverse(A)
    eye = jnp.broadcast_to(jnp.eye(16), A.shape)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray((eye + A) @ inv),
                                   np.asarray(eye), atol=1e-5)


# ----------------------------------------------------------------- the mixer
def _plain_mixer(qkvz, ba, conv_w, A_log, dt_bias, gamma, chunk):
    """The mixer from its equations: every piece written out."""
    b, L, _ = qkvz.shape
    width = 2 * HK * DK + HV * DV
    K = conv_w.shape[1]
    x = jnp.pad(qkvz[..., :width], ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(conv_w[:, j] * x[:, j:j + L] for j in range(K)))
    q = qkv[..., :HK * DK].reshape(b, L, HK, DK)
    k = qkv[..., HK * DK:2 * HK * DK].reshape(b, L, HK, DK)
    v = qkv[..., 2 * HK * DK:].reshape(b, L, HV, DV)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(DK)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(ba[..., :HV])
    g = -jnp.exp(A_log) * jax.nn.softplus(ba[..., HV:] + dt_bias)
    o = recurrence(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * gamma
    z = qkvz[..., width:].reshape(b, L, HV, DV)
    return (o * jax.nn.silu(z)).reshape(b, L, HV * DV)


def test_mixer_is_its_equations():
    L = 40
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    width = 2 * HK * DK + HV * DV
    operands = (jax.random.normal(ks[0], (2, L, width + HV * DV)),
                jax.random.normal(ks[1], (2, L, 2 * HV)),
                jax.random.uniform(ks[2], (width, 4), minval=-0.5,
                                   maxval=0.5),
                jnp.log(jax.random.uniform(ks[3], (HV,), minval=0.1,
                                           maxval=16.0)),
                jnp.ones((HV,)),
                1.0 + 0.1 * jax.random.normal(ks[4], (DV,)))
    w = jax.random.normal(ks[5], (2, L, HV * DV))
    kw = dict(key_heads=HK, value_heads=HV, key_dim=DK, value_dim=DV,
              chunk=16)
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(lambda *a: (_plain_mixer(*a, 16) * w).sum(),
                                  argnums=range(6))(*operands)
        got = jax.value_and_grad(lambda *a: (gdn_mixer(*a, **kw) * w).sum(),
                                 argnums=range(6))(*operands)
    assert abs(got[0] - want[0]) <= 1e-5 * abs(want[0])
    for i, (a, b) in enumerate(zip(got[1], want[1])):
        assert _gap(a, b) < 1e-5, i


# ----------------------------------------- shared operators: the new forms
def _value_and_grads(fn, operands, cotangent):
    out, vjp = jax.vjp(fn, *operands)
    return (out,) + vjp(cotangent.astype(out.dtype))


def _jnp_form(fn, *operands):
    with mock.patch.object(ssm, "ssm_conv_tiles", lambda *a: False), \
            mock.patch.object(ssm, "ssm_norm_tiles", lambda *a: False):
        return fn(*operands)


def _plain_norm_before_gate(y, src, gamma, groups, lo):
    C = y.shape[-1]
    z = src[..., lo:lo + C]
    yg = y.reshape(y.shape[:-1] + (groups, C // groups))
    yn = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True) + 1e-6)
    return yn.reshape(y.shape) * gamma * jax.nn.silu(z)


@pytest.mark.parametrize("form", ["jnp", "pallas"])
@pytest.mark.parametrize("L,C,groups,lo", [(384, 512, 4, 256),
                                           (48, 256, 2, 0)],
                         ids=["gate_behind_others", "two_groups"])
def test_norm_before_gate_is_its_expression(form, L, C, groups, lo):
    """``rmsnorm(y) * gain * silu(z)`` a group, the gate read as columns
    of a wider array, its gradient zeros beside them."""
    k = jax.random.split(jax.random.PRNGKey(L + C), 4)
    y = jax.random.normal(k[0], (2, L, C))
    src = jax.random.normal(k[1], (2, L, lo + C + 128))
    gamma = 1.0 + 0.1 * jax.random.normal(k[2], (C,))
    w = jax.random.normal(k[3], (2, L, C))

    def run(*a):
        return _value_and_grads(
            lambda *b: ssm._gate_norm(*b, groups, 1e-6, lo, True), a, w)

    assert pallas_kernels.ssm_norm_tiles(L, C, groups, lo)
    got = run(y, src, gamma) if form == "pallas" else _jnp_form(
        run, y, src, gamma)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(
            lambda *b: _plain_norm_before_gate(*b, groups, lo),
            (y, src, gamma), w)
    for name, a, b in zip(("value", "y", "src", "gain"), got, want):
        assert a.shape == b.shape, name
        assert _gap(a, b) < 1e-5, name
    d_src = np.asarray(got[2])
    assert not d_src[..., :lo].any() and not d_src[..., lo + C:].any()
    # the gate after the norm is another function
    after = ssm._gate_norm(y, src, gamma, groups, 1e-6, lo)
    assert _gap(after, got[0]) > 0.05


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_convolution_without_a_bias_is_its_expression(form):
    L, C, K, lo = 64, 256, 4, 128
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    src = jax.random.normal(k[0], (2, L, lo + C + 128))
    taps = jax.random.uniform(k[1], (C, K), minval=-0.5, maxval=0.5)
    w = jax.random.normal(k[2], (2, L, C))

    def plain(src, taps):
        x = jnp.pad(src[..., lo:lo + C], ((0, 0), (K - 1, 0), (0, 0)))
        return jax.nn.silu(sum(taps[:, j] * x[:, j:j + L] for j in range(K)))

    def run(*a):
        return _value_and_grads(lambda s, t: ssm._conv_silu(s, t, None, lo),
                                a, w)

    assert pallas_kernels.ssm_conv_tiles(L, C, K, lo)
    got = run(src, taps) if form == "pallas" else _jnp_form(run, src, taps)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(plain, (src, taps), w)
    for name, a, b in zip(("value", "src", "taps"), got, want):
        assert a.shape == b.shape, name
        assert _gap(a, b) < 1e-5, name
    # a zero bias is no bias
    zero = ssm._conv_silu(src, taps, jnp.zeros((C,)), lo)
    assert _gap(zero, got[0]) < 1e-7


# -------------------------------------------------------- partial rotary
def test_partial_rotary_turns_the_first_channels_as_a_head_of_their_own():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 3, 32))
    got = rope(x, theta=1e7, rotary_dim=8)
    np.testing.assert_allclose(np.asarray(got[..., :8]),
                               np.asarray(rope(x[..., :8], theta=1e7)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    # all of D is the old rope, and 0 means all of D
    np.testing.assert_array_equal(np.asarray(rope(x, rotary_dim=32)),
                                  np.asarray(rope(x)))
    assert _gap(rope(x, theta=1e7), got) > 0.05
