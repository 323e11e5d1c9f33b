"""Kimi Linear's pieces on the CPU at small sizes: the delta rule with a
decay a key channel (``ops/deltanet.py``) against the recurrence it
stands for, forward and every gradient, with decays of -20 a step among
them; its agreement with the scalar path where every channel decays
alike; the flash kernels (the interpreter) at a value width that
differs from the query and key width, against plain attention; and the
gate-norm's sigmoid gate in ``jnp`` and in the Pallas passes (the
interpreter).  The mixer and the latent attention block against the
plain reference are ``tests/perfbench_checks/test_kimi_linear_cell.py``'s.

Tolerances: the chunked rule and the recurrence compute the same float32
sums in another order (sub-chunk products, the triangular inverse's
powers, the state carried chunk to chunk), which differ by a few 1e-7
relative; the gradients sum that over the positions, so 1e-5 relative.
Decays of -20 a step leave a state that forgets nearly all at once: the
values are then near the last write alone and the recurrence's and the
chunks' roundings differ a little more, 1e-4."""
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import deltanet, pallas_kernels, ssm
from mxnet_tpu.ops.deltanet import gated_delta_rule, kda_mixer

H, DK, DV = 3, 8, 6


def recurrence(q, k, v, g, beta):
    """One position at a time: each key channel of the state forgets by
    its own decay, then the delta rule's write."""
    b, L, _, dk = q.shape

    def step(S, at):
        qt, kt, vt, gt, bt = at
        S = jnp.exp(gt)[..., None] * S
        predicted = jnp.einsum("bhkv,bhk->bhv", S, kt)
        S = S + jnp.einsum("bhk,bhv->bhkv", kt,
                           bt[..., None] * (vt - predicted))
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((b, H, dk, v.shape[-1])),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _operands(L, scale, b=2, seed=0):
    """q, k unit rows; g uniform(-scale, 0) a key channel."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(k[0], (b, L, H, DK))),
            unit(jax.random.normal(k[1], (b, L, H, DK))),
            jax.random.normal(k[2], (b, L, H, DV)),
            -scale * jax.random.uniform(k[3], (b, L, H, DK)),
            jax.nn.sigmoid(jax.random.normal(k[4], (b, L, H))))


def _gap(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def _run(q, k, v, g, beta, chunk, sub, span):
    """The rule through ``_run`` with explicit sub-chunks and spans."""
    b, L = q.shape[:2]
    x = jnp.concatenate([a.reshape(b, L, -1) for a in (q, k, v)], 2)
    return deltanet._run(x, g, beta, (H, DK, DV), chunk, False, sub=sub,
                         span=span).reshape(b, L, H, DV)


@pytest.mark.parametrize("scale,tol", [(0.5, 1e-5), (20.0, 1e-4)],
                         ids=["mild", "minus_20_a_step"])
@pytest.mark.parametrize("L,chunk,sub,span", [
    (64, 16, 4, 2), (75, 16, 8, 16), (96, 32, 16, 1)],
    ids=["four_spans", "ragged_one_span", "span_a_chunk"])
def test_per_channel_rule_is_the_recurrence(L, chunk, sub, span, scale, tol):
    """The value and the gradient in each of the five operands, finite
    where a chunk's decays sum far past float32's range."""
    ops = _operands(L, scale)
    w = jax.random.normal(jax.random.PRNGKey(7), ops[2].shape)
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(
            lambda *a: (recurrence(*a) * w).sum(), argnums=range(5))(*ops)
        got = jax.value_and_grad(
            lambda *a: (_run(*a, chunk, sub, span) * w).sum(),
            argnums=range(5))(*ops)
    assert abs(got[0] - want[0]) <= tol * abs(want[0])
    for i, (a, b) in enumerate(zip(got[1], want[1])):
        assert bool(jnp.isfinite(a).all()), i
        assert _gap(a, b) < tol, i
    if scale > 1:
        # a chunk's decays pass -88: e^{-Gamma} alone
        # would have overflowed
        assert float(jnp.cumsum(ops[3][:, :chunk], 1).min()) < -88


def test_the_op_takes_a_decay_a_channel_and_the_decay_matters():
    q, k, v, g, beta = _operands(48, 1.0)
    with jax.default_matmul_precision("highest"):
        o = gated_delta_rule(q, k, v, g, beta, chunk=16)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(recurrence(q, k, v, g, beta)),
                                   rtol=1e-4, atol=1e-5)
        # the channels' mean as one decay a head is another rule
        mean = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        assert _gap(gated_delta_rule(q, k, v, mean, beta, chunk=16), o) > 0.02


def test_equal_channels_are_the_scalar_path():
    """Where every key channel decays alike the per-channel form is the
    scalar rule's, value and gradients (the scalar g's gradient is the
    sum of the channels')."""
    q, k, v, g, beta = _operands(64, 1.0)
    scalar = g[..., 0]
    w = jax.random.normal(jax.random.PRNGKey(3), v.shape)
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(
            lambda q, k, v, s, b: (gated_delta_rule(q, k, v, s, b,
                                                    chunk=16) * w).sum(),
            argnums=range(5))(q, k, v, scalar, beta)
        got = jax.value_and_grad(
            lambda q, k, v, s, b: (gated_delta_rule(
                q, k, v, jnp.broadcast_to(s[..., None], g.shape), b,
                chunk=16) * w).sum(), argnums=range(5))(q, k, v, scalar, beta)
    assert abs(got[0] - want[0]) <= 1e-5 * abs(want[0])
    for i, (a, b) in enumerate(zip(got[1], want[1])):
        assert _gap(a, b) < 1e-5, i


def test_a_scalar_decay_takes_the_path_it_took():
    """A decay a head never reaches the per-channel code."""
    q, k, v, g, beta = _operands(32, 1.0)
    with mock.patch.object(deltanet, "_rule_channels",
                           side_effect=AssertionError("per-channel")):
        gated_delta_rule(q, k, v, g[..., 0], beta, chunk=16)
    with mock.patch.object(deltanet, "_rule",
                           side_effect=AssertionError("scalar")):
        gated_delta_rule(q, k, v, g, beta, chunk=16)


def test_the_per_channel_rule_keeps_its_operands_and_nothing_else():
    """What its ``custom_vjp`` carries from forward to backward is x =
    [q | k | v], g and beta: no state and no chunk's matrix."""
    q, k, v, g, beta = _operands(64, 1.0)
    b, L = q.shape[:2]
    x = jnp.concatenate([a.reshape(b, L, -1) for a in (q, k, v)], 2)
    _, kept = deltanet._rule_channels_fwd(x, g, beta, 16, (H, DK, DV),
                                          False, 8, 2)
    assert len(kept) == 3 and all(a is c for a, c in zip(kept, (x, g, beta)))
    _, vjp = jax.vjp(lambda *a: gated_delta_rule(*a, chunk=16),
                     q, k, v, g, beta)
    held = sorted(a.size for a in jax.tree_util.tree_leaves(vjp)
                  if hasattr(a, "shape") and a.size > 1)
    assert max(held) <= b * L * H * (2 * DK + DV)                 # x


def test_no_loop_in_the_per_channel_rule():
    """The chunks and the spans are written out: a ``while`` would count
    its body twice in the device account."""
    q, k, v, g, beta = _operands(64, 1.0)
    text = jax.jit(jax.grad(lambda *a: gated_delta_rule(
        *a, chunk=16).sum(), argnums=range(5))).lower(
        q, k, v, g, beta).as_text()
    assert "stablehlo.while" not in text


# ------------------------------------------- flash at a value width of its own
def _plain_attention(q, k, v):
    """q (BH, L, D), k (BHkv, L, D), v (BHkv, L, Dv), causal."""
    group = q.shape[0] // k.shape[0]
    k, v = (jnp.repeat(a, group, axis=0) for a in (k, v))
    L = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("heads,kv_heads,L,D,Dv", [
    (4, 4, 64, 24, 16), (4, 2, 80, 12, 8), (2, 1, 48, 16, 24)],
    ids=["heads_alike", "grouped_ragged", "v_wider"])
def test_flash_at_another_value_width_is_plain_attention(heads, kv_heads, L,
                                                         D, Dv):
    """Output and the three gradients, float32 in the interpreter with
    blocks of 16 (a band edge crossing blocks, a ragged last block): the
    same sums in another order, 1e-5."""
    ks = jax.random.split(jax.random.PRNGKey(L), 4)
    q = jax.random.normal(ks[0], (heads, L, D))
    k = jax.random.normal(ks[1], (kv_heads, L, D))
    v = jax.random.normal(ks[2], (kv_heads, L, Dv))
    w = jax.random.normal(ks[3], (heads, L, Dv))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: (pallas_kernels.flash_attention(
            *a, causal=True, block_q=16, block_k=16) * w).sum(),
            argnums=range(3))(q, k, v)
        want = jax.value_and_grad(lambda *a: (_plain_attention(*a) * w).sum(),
                                  argnums=range(3))(q, k, v)
    assert abs(got[0] - want[0]) <= 1e-5 * abs(want[0])
    for i, (a, b) in enumerate(zip(got[1], want[1])):
        assert a.shape == b.shape, i
        assert _gap(a, b) < 1e-5, i


def test_flash_plans_at_equal_widths_are_unchanged():
    """Where v is as wide as q and k the kernels' block specs are the
    ones they were: a query head's rows of v's width are q's spec, a
    key head's are k's."""
    plan = pallas_kernels.flash_tile_plan(256, 256, True)
    for order in ("qk", "kq"):
        q, k, _row, o, v = pallas_kernels._specs(plan, 128, order, 128)
        assert (o.block_shape, v.block_shape) == (q.block_shape,
                                                  k.block_shape)
        q, k, _row, o, v = pallas_kernels._specs(plan, 192, order, 128)
        assert (q.block_shape[-1], k.block_shape[-1], o.block_shape[-1],
                v.block_shape[-1]) == (192, 192, 128, 128)


# ------------------------------------------------------------ sigmoid gate
def _value_and_grads(fn, operands, cotangent):
    out, vjp = jax.vjp(fn, *operands)
    return (out,) + vjp(cotangent.astype(out.dtype))


def _plain_gate_norm(y, src, gamma, groups, lo, before, act):
    C = y.shape[-1]
    z = act(src[..., lo:lo + C])

    def norm(a):
        ag = a.reshape(a.shape[:-1] + (groups, C // groups))
        return (ag / jnp.sqrt(jnp.mean(ag * ag, -1, keepdims=True) + 1e-5)
                ).reshape(a.shape)

    return (norm(y) * z if before else norm(y * z)) * gamma


@pytest.mark.parametrize("form", ["jnp", "pallas"])
@pytest.mark.parametrize("before", [True, False], ids=["norm_first",
                                                       "gate_first"])
def test_sigmoid_gate_is_its_expression(form, before):
    """``rmsnorm(y) * gain * sigmoid(z)`` a group (and the gate before
    the norm), the gate read as columns of a wider array; float32 sums
    in another order, 1e-5."""
    L, C, groups, lo = 48, 512, 4, 128
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    y = jax.random.normal(k[0], (2, L, C))
    src = jax.random.normal(k[1], (2, L, lo + C + 128))
    gamma = 1.0 + 0.1 * jax.random.normal(k[2], (C,))
    w = jax.random.normal(k[3], (2, L, C))

    def run(*a):
        return _value_and_grads(lambda *b: ssm._gate_norm(
            *b, groups, 1e-5, lo, before, "sigmoid"), a, w)

    assert pallas_kernels.ssm_norm_tiles(L, C, groups, lo)
    if form == "pallas":
        got = run(y, src, gamma)
    else:
        with mock.patch.object(ssm, "ssm_norm_tiles", lambda *a: False):
            got = run(y, src, gamma)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(lambda *b: _plain_gate_norm(
            *b, groups, lo, before, jax.nn.sigmoid), (y, src, gamma), w)
    for name, a, b in zip(("value", "y", "src", "gain"), got, want):
        assert a.shape == b.shape, name
        assert _gap(a, b) < 1e-5, name
    d_src = np.asarray(got[2])
    assert not d_src[..., :lo].any() and not d_src[..., lo + C:].any()
    # silu, the default, is another function
    silu = ssm._gate_norm(y, src, gamma, groups, 1e-5, lo, before)
    assert _gap(silu, got[0]) > 0.05


def test_silu_stays_the_default_and_its_program():
    """The gate-norm's silu form lowers to the program it lowered to
    before the sigmoid was added: the default is the one form, not a
    second expression of it."""
    y = jnp.ones((1, 16, 256))
    src = jnp.ones((1, 16, 256))
    gamma = jnp.ones((256,))
    default = jax.jit(lambda *a: ssm._gate_norm(*a, 2, 1e-5, 0, True)).lower(
        y, src, gamma).as_text()
    named = jax.jit(lambda *a: ssm._gate_norm(
        *a, 2, 1e-5, 0, True, "silu")).lower(y, src, gamma).as_text()
    assert default == named


def test_kda_mixer_runs_its_pieces_under_their_scopes():
    """The projections, the convolution, the rule with its decays, the
    gated norm and the out-projection each under the leaf the device
    metrics read, forward and in the backward's recomputation, which
    keeps the mixer's input and nothing of its inside."""
    Hh, d, C, L = 2, 8, 12, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 9)
    shapes = [(1, L, C), (3 * Hh * d, C), (d, C), (Hh * d, d), (Hh, C),
              (d, C), (Hh * d, d)]
    ops = [0.3 * jax.random.normal(k, s) for k, s in zip(ks, shapes)] + [
        jax.random.uniform(ks[7], (3 * Hh * d, 4), minval=-0.5, maxval=0.5),
        jnp.log(jnp.array([1.0, 8.0])), jnp.zeros((Hh * d,)),
        jnp.ones((d,)), 0.3 * jax.random.normal(ks[8], (C, Hh * d))]

    def fn(*a):
        return kda_mixer(*a, heads=Hh, head_dim=d, chunk=16)

    text = str(jax.make_jaxpr(jax.grad(lambda *a: fn(*a).sum(),
                                       argnums=range(12)))(*ops).pretty_print(
        name_stack=True))
    for scope in ("mx.kda.in_proj", "mx.kda.conv", "mx.kda.core",
                  "mx.kda.gate_norm", "mx.kda.out_proj"):
        assert scope in text, scope
    _, vjp = jax.vjp(fn, *ops)
    held = [a.shape for a in jax.tree_util.tree_leaves(vjp)
            if hasattr(a, "shape") and a.ndim == 3]
    assert held == [(1, L, C)], held
