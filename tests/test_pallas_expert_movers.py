"""ops/pallas_kernels: the expert layer's movers and activation (the
Pallas kernels in the interpreter here) against the ``jnp`` expressions
they replaced in ops/moe.py: ``x[order // k]``, the ``where`` and
``einsum`` of combine, ``act(gate) * up``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.pallas_kernels import (expert_activation, rows_of_tokens,
                                          tokens_of_rows)

S, K, C, G = 512, 4, 128, 3             # two token tiles, two row tiles
M = S * K
BLOCK = pk._GROUPED_ROW_BLOCK

# the share of the pairs each group draws, and the share held nowhere
# here (the last): what the cases exercise
ROUTINGS = {
    "uneven": [0.2, 0.05, 0.1, 0.65],
    "even_a_quarter_held": [0.08, 0.08, 0.09, 0.75],
    "no_held_pair_at_all": [0, 0, 0, 1],
    "every_pair_held": [0.3, 0.5, 0.2, 0],
    "an_empty_group": [0.3, 0, 0.2, 0.5],
    "all_in_one_group": [0, 1, 0, 0],
}


def _routing(case):
    """(group (S, K): each pair's group, G for a pair in none; order,
    inverse, sizes, live) as ops/moe.py's dispatch makes them."""
    rng = np.random.RandomState(sorted(ROUTINGS).index(case))
    group = jnp.asarray(rng.choice(G + 1, size=(S, K), p=ROUTINGS[case]),
                        jnp.int32)
    order = jnp.argsort(group.reshape(-1), stable=True)
    inverse = jnp.argsort(order).astype(jnp.int32).reshape(S, K)
    sizes = jnp.sum(group.reshape(-1, 1) == jnp.arange(G), axis=0,
                    dtype=jnp.int32)
    return group, order, inverse, sizes, int(sizes.sum())


def _poisoned(a, live):
    """What a kernel leaves past the last held pair: anything."""
    return a.at[live:].set(jnp.nan)


def test_the_cases_are_what_they_say():
    lives = {case: _routing(case)[-1] for case in ROUTINGS}
    assert lives["no_held_pair_at_all"] == 0
    assert lives["every_pair_held"] == M
    assert 0 < lives["even_a_quarter_held"] < M // 3
    # a group boundary inside a 128-row block, a last row inside an
    # 8-row slab
    sizes = np.asarray(_routing("uneven")[3])
    assert (np.cumsum(sizes)[:-1] % BLOCK).all() and sizes.sum() % 8


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_pair_side_mover_is_the_gather_bit_for_bit(case, dtype):
    _group, order, _inverse, _sizes, live = _routing(case)
    x = jnp.asarray(np.random.RandomState(1).randn(S, C), jnp.float32)
    tok = (order // K).astype(jnp.int32)
    got = jax.jit(lambda x, tok, live: rows_of_tokens(
        x, tok, live, dtype=dtype))(x, tok, jnp.int32(live))
    assert got.shape == (M, C) and got.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got[:live].astype(jnp.float32)),
        np.asarray(x[order // K][:live].astype(dtype).astype(jnp.float32)))


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_pair_side_mover_scales_and_takes_the_weights_gradient(case):
    """``scale[i] * x[tok[i]]`` and ``<dot[i], x[tok[i]]>`` for the live
    rows; ``dot`` is not read past them."""
    _group, order, _inverse, _sizes, live = _routing(case)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(S, C), jnp.float32)
    scale = jnp.asarray(rng.rand(M), jnp.float32)
    dot = _poisoned(jnp.asarray(rng.randn(M, C), jnp.float32), live)
    tok = (order // K).astype(jnp.int32)
    rows, dots = jax.jit(lambda *a: rows_of_tokens(
        a[0], a[1], a[2], scale=a[3], dot=a[4], dtype=jnp.bfloat16))(
            x, tok, jnp.int32(live), scale, dot)
    assert rows.dtype == jnp.bfloat16 and dots.shape == (M,)
    want = (x[tok] * scale[:, None]).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(rows[:live].astype(jnp.float32)),
        np.asarray(want[:live].astype(jnp.float32)))
    np.testing.assert_allclose(
        np.asarray(dots[:live]), np.asarray((x[tok] * dot).sum(1)[:live]),
        rtol=1e-5, atol=1e-5)


def _weighted_sum(z, w, inverse, group):
    """combine as ops/moe.py had it: gather, select, sum over k."""
    held = group < G
    pairs = jnp.where(held[..., None], z[inverse], 0)
    return jnp.einsum("skc,sk->sc", pairs, jnp.where(held, w, 0),
                      precision="highest")


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_token_side_mover_is_the_weighted_sum_of_the_held_pairs(case):
    group, _order, inverse, sizes, live = _routing(case)
    rng = np.random.RandomState(3)
    z = jnp.asarray(rng.randn(M, C), jnp.float32)
    w = jnp.asarray(rng.rand(S, K), jnp.float32)
    got = jax.jit(tokens_of_rows)(_poisoned(z, live), w, inverse, group,
                                  sizes)
    assert got.shape == (S, C) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_weighted_sum(z, w, inverse, group)),
        rtol=1e-6, atol=1e-6)
    # a token none of whose pairs is held gets zero, not what was there
    nobody = np.asarray((group == G).all(axis=1))
    assert not np.asarray(got)[nobody].any()


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_movers_are_each_others_transpose(case):
    """With unit weights the token-side mover is the gradient of the
    gather; with scale and dot the pair-side mover gives both gradients
    of the weighted sum (the weights' on the pair side, one row-wise
    product, then back by ``inverse``)."""
    group, order, inverse, sizes, live = _routing(case)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(S, C), jnp.float32)
    z = jnp.asarray(rng.randn(M, C), jnp.float32)
    w = jnp.asarray(rng.rand(S, K), jnp.float32)
    tok = (order // K).astype(jnp.int32)
    is_live = (jnp.arange(M) < live)[:, None]

    want = jax.vjp(lambda x: jnp.where(is_live, x[tok], 0), x)[1](z)[0]
    got = tokens_of_rows(_poisoned(z, live), jnp.ones_like(w), inverse,
                         group, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)

    d_z, d_w = jax.vjp(lambda z, w: _weighted_sum(z, w, inverse, group),
                       z, w)[1](x)
    rows, dots = rows_of_tokens(x, tok, jnp.int32(live),
                                scale=w.reshape(-1)[order],
                                dot=_poisoned(z, live))
    np.testing.assert_allclose(np.asarray(rows[:live]),
                               np.asarray(d_z[:live]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jnp.where(group < G, dots[inverse], 0)), np.asarray(d_w),
        rtol=1e-5, atol=1e-4)


ACTS = {"silu_gated": (jax.nn.silu, True), "relu": (jax.nn.relu, False),
        "relu_gated": (jax.nn.relu, True)}


@pytest.mark.parametrize("case", ["uneven", "no_held_pair_at_all",
                                  "every_pair_held"])
@pytest.mark.parametrize("name", sorted(ACTS))
def test_the_activation_and_its_gradient_over_the_live_rows(name, case):
    act, gated = ACTS[name]
    live = _routing(case)[-1]
    H = 128
    rng = np.random.RandomState(5)
    h = _poisoned(jnp.asarray(rng.randn(M, 2 * H if gated else H),
                              jnp.float32), live)
    g = _poisoned(jnp.asarray(rng.randn(M, H), jnp.float32), live)

    def fn(h):
        return act(h[:, :H]) * h[:, H:] if gated else act(h)
    got = jax.jit(lambda h, live: expert_activation(
        h, live, act, gated, dtype=jnp.bfloat16))(h, jnp.int32(live))
    assert got.shape == (M, H) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got[:live].astype(jnp.float32)),
        np.asarray(fn(h).astype(jnp.bfloat16)[:live].astype(jnp.float32)))
    got = jax.jit(lambda h, g, live: expert_activation(
        h, live, act, gated, g=g))(h, g, jnp.int32(live))
    assert got.shape == h.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got[:live]), np.asarray(jax.vjp(fn, h)[1](g)[0][:live]),
        rtol=1e-5, atol=1e-5)


def _takes_the_kernel(fn, *avals):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*avals))


A = jax.ShapeDtypeStruct


@pytest.mark.parametrize("s,k,c,kernel", [
    (S, K, C, True),
    (8192, 8, 256, True),
    (S, K, 100, False),                 # C does not fill the 128 lanes
    (S - 8, K, C, False),               # S*k is no multiple of the row tile
    (48, 3, 8, False),                  # the unit tests' and the dry run's
])
def test_the_shapes_choose_the_movers_kernels(s, k, c, kernel):
    """The part of the grouped products' predicate that the movers'
    operands show: rows a multiple of the row tile, 128-lane columns."""
    m = s * k
    f32, i32 = jnp.float32, jnp.int32
    assert _takes_the_kernel(
        lambda x, tok, live: rows_of_tokens(x, tok, live),
        A((s, c), f32), A((m,), i32), A((), i32)) == kernel
    assert _takes_the_kernel(
        tokens_of_rows, A((m, c), f32), A((s, k), f32), A((s, k), i32),
        A((s, k), i32), A((G,), i32)) == kernel
    assert _takes_the_kernel(
        lambda h, live: expert_activation(h, live, jax.nn.silu, True),
        A((m, 2 * c), f32), A((), i32)) == kernel
    assert pk.grouped_tiles(m, c, 2 * c) == kernel


def test_the_exact_gelu_stays_in_jnp():
    """Mosaic has no erfc: the layer's products and movers are kernels,
    its activation a ``jnp`` pass over all rows."""
    from mxnet_tpu.ops.moe import _ACTIVATIONS
    assert not _takes_the_kernel(
        lambda h, live: expert_activation(h, live, _ACTIVATIONS["gelu"],
                                          False),
        A((M, C), jnp.float32), A((), jnp.int32))
    assert _takes_the_kernel(
        lambda h, live: expert_activation(h, live, _ACTIVATIONS["silu"],
                                          False),
        A((M, C), jnp.float32), A((), jnp.int32))


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_a_tiles_runs_cover_its_held_pairs(case):
    """The token-side mover's table: every held pair's row lies in one
    of the 8-row slabs fetched for its tile, at the buffer row the table
    says; no tile fetches more slabs than its buffer holds."""
    group, _order, inverse, sizes, live = _routing(case)
    ts, cap = pk._token_tile(S, K, G, C)
    (slabs, n, tail, _live), vrow = pk._token_runs(inverse, group, sizes, ts,
                                                   cap)
    slabs = np.asarray(slabs).reshape(S // ts, cap)
    n, tail, vrow = np.asarray(n), np.asarray(tail), np.asarray(vrow)
    assert (n <= cap).all()
    inv, grp = np.asarray(inverse), np.asarray(group)
    for s in range(S):
        for j in range(K):
            if grp[s, j] == G:
                assert vrow[s, j] == -1
                continue
            q, r = divmod(vrow[s, j], 8)
            assert q < n[s // ts]
            assert slabs[s // ts, q] * 8 + r == inv[s, j]
    for t in range(S // ts):
        ends_here = [q for q in range(n[t])
                     if slabs[t, q] * 8 < live < slabs[t, q] * 8 + 8]
        assert ([tail[t]] if tail[t] >= 0 else []) == ends_here[-1:]
