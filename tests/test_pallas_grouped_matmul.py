"""ops/pallas_kernels.grouped_matmul: the expert layer's grouped product
(the Pallas kernels in the interpreter here) against a per-group
``jnp.dot`` in float32 of bfloat16-rounded operands."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.pallas_kernels import grouped_matmul

TM = pk._GROUPED_ROW_TILE
M, K, N, G = 4 * TM, 256, 128, 5

# what the sizes exercise, each over four row tiles
GROUPS = {
    "uneven": [TM + 37, 300, TM - 111, 64, 200],
    "an_empty_group": [300, 0, TM + 100, 0, 90],
    "a_boundary_inside_a_row_tile": [TM // 2, TM // 4, TM, TM // 4 + 7, 1],
    "all_rows_in_one_group": [0, 0, M, 0, 0],
    "fewer_rows_than_the_buffer": [100, 200, 50, 0, 3],
    "boundaries_on_the_tiles": [TM, 0, 2 * TM, TM, 0],
    "no_rows": [0, 0, 0, 0, 0],
}


def _operands(seed):
    rng = np.random.RandomState(seed)

    def rounded(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16).astype(
            jnp.float32)
    return rounded(M, K), rounded(G, K, N), rounded(M, N)


def reference(lhs, rhs, sizes):
    """Row i times its group's matrix, float32 at "highest"; zero past
    the last group."""
    ends = np.cumsum(sizes)
    row = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g, (size, end) in enumerate(zip(sizes, ends)):
        out = out + jnp.where(
            (row >= end - size) & (row < end),
            jnp.dot(lhs, rhs[g], precision="highest"), 0)
    return out


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_forward_is_each_groups_product(case):
    sizes = GROUPS[case]
    lhs, rhs, _ = _operands(1)
    got = jax.jit(grouped_matmul)(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    assert got.dtype == jnp.float32 and got.shape == (M, N)
    live = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:live]),
                               np.asarray(reference(lhs, rhs, sizes)[:live]),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_gradients_are_those_of_the_reference(case):
    sizes = GROUPS[case]
    lhs, rhs, proj = _operands(2)
    live = (jnp.arange(M) < sum(sizes))[:, None]

    def loss(fn):
        # the rows past the last group belong to nobody: select them away
        return lambda lhs, rhs: (jnp.where(live, fn(lhs, rhs), 0)
                                 * proj).sum()
    got = jax.jit(jax.grad(loss(lambda a, b: grouped_matmul(
        a, b, jnp.asarray(sizes, jnp.int32))), argnums=(0, 1)))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: reference(a, b, sizes)),
                    argnums=(0, 1))(lhs, rhs)
    assert got[0].dtype == got[1].dtype == jnp.float32
    n = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[0][:n]),
                               np.asarray(want[0][:n]), rtol=1e-5, atol=1e-3)
    # a group with no rows gets a zero gradient, not what was in memory
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-5, atol=2e-3)
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(got[1][g]).any()


def test_the_weights_gradient_never_reads_past_the_last_group():
    sizes = GROUPS["fewer_rows_than_the_buffer"]
    lhs, rhs, g = _operands(3)
    n = sum(sizes)
    s = jnp.asarray(sizes, jnp.int32)
    clean = pk._tgmm(lhs.astype(jnp.bfloat16), g.astype(jnp.bfloat16), s,
                     True)
    dirty = pk._tgmm(lhs.at[n:].set(jnp.nan).astype(jnp.bfloat16),
                     g.at[n:].set(jnp.inf).astype(jnp.bfloat16), s, True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_operands_enter_as_bfloat16():
    """float32 operands are rounded to bfloat16 on the way in; the sum
    is float32."""
    rng = np.random.RandomState(4)
    lhs = jnp.asarray(rng.randn(M, K), jnp.float32)
    rhs = jnp.asarray(rng.randn(G, K, N), jnp.float32)
    sizes = GROUPS["uneven"]
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    n = sum(sizes)
    np.testing.assert_allclose(
        np.asarray(got[:n]), np.asarray(reference(r(lhs), r(rhs), sizes)[:n]),
        rtol=1e-5, atol=1e-4)


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, found)
    return found


@pytest.mark.parametrize("shape,kernel", [
    ((M, K, N), True),
    ((M, 128, 128), True),
    ((M, K, 100), False),           # N does not fill the 128 lanes
    ((M, 16, N), False),            # nor K
    ((M - 8, K, N), False),         # M is no multiple of the row tile
    ((48 * 8, 8, 32), False),       # the unit tests' and the dry run's
])
def test_the_shapes_choose_the_kernel(shape, kernel):
    m, k, n = shape
    S = jax.ShapeDtypeStruct

    def both(lhs, rhs, sizes):
        return jax.grad(lambda a, b: grouped_matmul(a, b, sizes).sum(),
                        argnums=(0, 1))(lhs, rhs)
    found = _primitives(jax.make_jaxpr(both)(
        S((m, k), jnp.float32), S((3, k, n), jnp.float32),
        S((3,), jnp.int32)).jaxpr)
    assert ("pallas_call" in found) == kernel
    assert ("ragged_dot_general" in found) == (not kernel)


def test_shapes_that_do_not_tile_are_ragged_dot():
    rng = np.random.RandomState(5)
    lhs = jnp.asarray(rng.randn(96, 24), jnp.float32)
    rhs = jnp.asarray(rng.randn(3, 24, 40), jnp.float32)
    sizes = jnp.asarray([40, 0, 30], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(grouped_matmul(lhs, rhs, sizes)),
        np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes)))


@pytest.mark.parametrize("visit_empty", [False, True])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_the_schedule_follows_the_groups(case, visit_empty):
    """Every group's rows are covered by the tiles visited for it, in
    order, a tile's visits are consecutive, and no tile past the last
    group is visited."""
    sizes = np.asarray(GROUPS[case])
    tm = TM if visit_empty else pk._GROUPED_GMM_ROW_TILE
    (offs, gids, tids), steps = pk._group_tiles(
        jnp.asarray(sizes, jnp.int32), M, tm, visit_empty)
    offs, gids, tids, steps = (np.asarray(a) for a in
                               (offs, gids, tids, steps))
    assert offs.tolist() == [0] + np.cumsum(sizes).tolist()
    gids, tids = gids[:steps], tids[:steps]
    want = []
    for g, size in enumerate(sizes):
        if size:
            want += [(g, t) for t in range(offs[g] // tm,
                                           -(-offs[g + 1] // tm))]
        elif visit_empty:
            want.append((g, min(offs[g] // tm, M // tm - 1)))
    assert list(zip(gids.tolist(), tids.tolist())) == want
    assert (np.diff(gids) >= 0).all()
    if not visit_empty:
        assert (np.diff(tids) >= 0).all()
