"""The Pallas kernels of the delta rule with a decay a key channel
(``ops/kda_kernels.py``), in the interpreter on the CPU at one shape
they tile (two heads of 128, chunks of 64 in sub-chunks of 16, 256
positions): the forward kernel, the sweep and the backward walk against
the ``jnp`` form (``deltanet._channels``) and its autodiff, with every
product at float32 accuracy, 1e-5 (the same float32 sums in another
order); with the bfloat16 operands the chip's products take, 1e-2; the
sweep's states against the recurrence and its inverses against a
float64 inverse; and the predicate that sends the rule to them.

Each case goes through one ``jax.jit`` a set of static arguments, so
that the cases of a set share one compile of the interpreted kernels."""
from functools import partial
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import deltanet, kda_kernels
from mxnet_tpu.ops.deltanet import gated_delta_rule

H, D, Q, SUB, L = 2, 128, 64, 16, 256      # a shape the kernels tile
LAYOUT = (H, D, D)


def _operands(seed, g_scale=0.5, beta_shift=0.0, steep=False):
    """x = [q | k | v] (1, L, .) with q and k raw (the kernels norm
    them), g (1, L, H, D) uniform(-g_scale, 0) a key channel (with
    ``steep`` every third channel -20 a position), beta (1, L, H) and a
    cotangent of o."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (1, L, 3 * H * D))
    g = -g_scale * jax.random.uniform(ks[1], (1, L, H, D))
    if steep:
        g = jnp.where(jnp.arange(D) % 3 == 0, -20.0, g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (1, L, H)) + beta_shift)
    w = jax.random.normal(ks[3], (1, L, H, D))
    return x, g, beta, w


def _unit_columns(x, g):
    """x with q and k as the rule reads them (unit rows, q over
    sqrt(D)): the operand of the rule without ``normed``."""
    return jnp.concatenate([a.reshape(1, L, -1) for a in
                            deltanet._channel_operands(x, g, LAYOUT, True)],
                           axis=2)


def _gap(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


@partial(jax.jit, static_argnums=(4,))
def _kernels(x, g, beta, w, normed):
    o = kda_kernels.kda_chunks(x, g, beta, Q, SUB, LAYOUT, normed)
    return o, kda_kernels.kda_chunks_grads(x, g, beta, w, Q, SUB, LAYOUT,
                                           normed)


@partial(jax.jit, static_argnums=(4,))
def _jnp_form(x, g, beta, w, normed):
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(lambda *a: deltanet._channels(
            *a, Q, LAYOUT, normed, SUB, deltanet._SPAN), x, g, beta)
        return o, vjp(w)


def _float32_products():
    """The kernels with every product at float32 accuracy: their
    mathematics, apart from the bfloat16 operands XLA's default gives
    the ``jnp`` form on the chip."""
    return mock.patch.object(kda_kernels, "_default", kda_kernels._exact)


@partial(jax.jit, static_argnums=(4,))
def _float32_kernels(x, g, beta, w, normed):
    """``_kernels`` traced with every product at float32 accuracy."""
    with _float32_products():
        return _kernels.__wrapped__(x, g, beta, w, normed)


@pytest.mark.parametrize("normed,kw", [
    (True, dict(g_scale=1e-3)), (True, dict(steep=True)),
    (True, dict(beta_shift=-6.0)), (True, dict(beta_shift=6.0)),
    (False, {})],
    ids=["g_near_0", "g_minus_20_on_some_channels", "beta_near_0",
         "beta_near_1", "given_q_k"])
def test_kernels_are_the_chunked_rule(normed, kw):
    """o and the gradients in x, g and beta: the forward kernel, the
    sweep and the backward walk against autodiff of the ``jnp`` form."""
    x, g, beta, w = _operands(1, **kw)
    if not normed:
        x = _unit_columns(x, g)
    assert deltanet._channel_kernels(g, Q, LAYOUT, SUB)
    o, grads = _float32_kernels(x, g, beta, w, normed)
    want, want_grads = _jnp_form(x, g, beta, w, normed)
    assert o.shape == want.shape and _gap(o, want) < 1e-5
    for name, a, b in zip(("x", "g", "beta"), grads, want_grads):
        assert a.shape == b.shape, name
        assert bool(jnp.isfinite(a).all()), name
        assert _gap(a, b) < 1e-5, name
    if kw.get("steep"):
        # a chunk's decays pass -88 on those channels: e^{-Gamma} alone
        # would have overflowed
        assert float(jnp.cumsum(g[:, :Q], 1).min()) < -88


def test_kernels_with_bfloat16_operands_stay_near_the_rule():
    """As the chip runs them: a product's operands rounded to bfloat16
    (the inverse's and its gradient's excepted)."""
    x, g, beta, w = _operands(2)
    o, grads = _kernels(x, g, beta, w, True)
    want, want_grads = _jnp_form(x, g, beta, w, True)
    assert 1e-5 < _gap(o, want) < 1e-2
    for name, a, b in zip(("x", "g", "beta"), grads, want_grads):
        assert _gap(a, b) < 1e-2, name


def _recurrence_states(q, k, v, g, beta):
    """The states (1, L, H, D, D) after each position, one at a time."""
    def step(S, at):
        kt, vt, gt, bt = at
        S = jnp.exp(gt)[..., None] * S
        S = S + jnp.einsum("bhk,bhv->bhkv", kt, bt[..., None] * (
            vt - jnp.einsum("bhkv,bhk->bhv", S, kt)))
        return S, S

    _, after = jax.lax.scan(step, jnp.zeros((1, H, D, D)), tuple(
        jnp.moveaxis(a, 1, 0) for a in (k, v, g, beta)))
    return after


def test_the_sweep_carries_the_states_and_the_chunks_inverses():
    """The (transposed) states entering the chunks are the recurrence's
    at the chunks' starts; each chunk's T is ``(I + A)^-1`` to float32
    accuracy with the inverse the chip takes (a first guess in
    bfloat16, two Newton steps), T^T its transpose."""
    x, g, beta, _ = _operands(3, g_scale=2.0)
    with _float32_products():
        states, inverses, transposed = jax.jit(
            lambda *a: kda_kernels._fwd(*a, Q, SUB, LAYOUT, True, True,
                                        True))(x, g, beta)
    q, k, v = deltanet._channel_operands(x, g, LAYOUT, True)
    with jax.default_matmul_precision("highest"):
        after = _recurrence_states(q, k, v, g, beta)
    entering = jnp.concatenate([jnp.zeros_like(after[:1]),
                                after[Q - 1:-1:Q]])       # (nc, 1, H, ..)
    want = jnp.moveaxis(entering, 0, 1)                    # (1, nc, H, ..)
    assert _gap(states, jnp.swapaxes(jnp.swapaxes(want, 1, 2), -1, -2)) < 1e-5
    np.testing.assert_array_equal(np.asarray(transposed),
                                  np.asarray(jnp.swapaxes(inverses, -1, -2)))
    # A from the rule's own definition, in float64
    kn = np.asarray(k, np.float64)[0].reshape(L // Q, Q, H, D)
    gam = np.cumsum(np.asarray(g, np.float64)[0].reshape(L // Q, Q, H, D), 1)
    bt = np.asarray(beta, np.float64)[0].reshape(L // Q, Q, H)
    T = np.asarray(inverses, np.float64)[0]                # (H, nc, Q, Q)
    below = np.tri(Q, k=-1, dtype=bool)[..., None]
    worst = 0.0
    for h in range(H):
        for c in range(L // Q):
            kc, gc = kn[c, :, h], gam[c, :, h]
            diff = np.where(below, gc[:, None] - gc[None], -np.inf)
            a = bt[c, :, h, None] * np.sum(kc[:, None] * kc[None]
                                           * np.exp(diff), -1)
            want = np.linalg.inv(np.eye(Q) + a)
            worst = max(worst, np.linalg.norm(T[h, c] - want)
                        / np.linalg.norm(want))
    assert worst < 1e-6


def test_the_predicate_takes_the_cells_shape_and_not_the_tests_models():
    tiles = kda_kernels.kda_chunk_tiles
    assert tiles(64, 16, 128, 128, 32)      # Kimi Linear: chunks of 64
    assert tiles(Q, SUB, D, D, H)
    assert not tiles(16, 16, 8, 8, 3)       # the tests' models: heads of 8
    assert not tiles(16, 16, 8, 8, 2)       # chunks of 16 over d 8
    assert not tiles(60, 4, 128, 128, 32)   # chunks no multiple of 8
    assert not tiles(64, 16, 128, 96, 32)   # values no multiple of 128
    assert not tiles(64, 24, 128, 128, 32)  # sub-chunks that do not divide
    calls = []

    def record(x, g, beta, Q, sub, layout, normed):
        calls.append((Q, sub, layout))
        return jnp.zeros(beta.shape + (layout[2],))

    ops = _operands(4)
    q, k, v = (a.reshape(1, L, H, D) for a in
               deltanet._channel_operands(ops[0], ops[1], LAYOUT, True))
    with mock.patch.object(deltanet, "kda_chunks", record):
        gated_delta_rule(q, k, v, ops[1], ops[2], chunk=Q)
    assert calls == [(Q, SUB, LAYOUT)]
    # the tests' models' shapes, and one key head for two value heads
    assert not deltanet._channel_kernels(jnp.zeros((1, 32, 3, 8)), 16,
                                         (3, 8, 8), 16)
    assert not deltanet._channel_kernels(jnp.zeros((1, L, 2 * H, D)), Q,
                                         LAYOUT, SUB)
