"""Pallas TPU kernels: fused flash attention (forward + backward), the
paged decode kernels, and the expert layer's grouped matmul.

The reference's attention kernels (``src/operator/contrib/transformer.cc``,
``_contrib_interleaved_matmul_selfatt_*``) materialize the (L, L) score
matrix — O(L^2) HBM traffic.  This module supplies the TPU-native
replacement (SURVEY.md §5.7 flash/splash mandate): an online-softmax
flash-attention kernel that keeps scores in VMEM tiles, with the standard
FlashAttention-2 backward (recompute P blockwise from the saved
logsumexp).

Design notes:
- the work follows the mask (``flash_tile_plan``): a kernel's grid is
  (head, step), the steps walking only the (query block, key block)
  pairs the causal band shows something of, from tables in the scalar
  prefetch; the running max / denominator / output accumulator live in
  VMEM scratch and carry across a query block's steps (canonical TPU
  flash pattern).  A block on the band's edge is cut into sub-tiles,
  those the mask hides whole left out and the rest computed a strip at
  a time; only a strip the edge crosses pays for the iota comparison.
- per-row key-length masking (padding masks) rides a scalar-prefetch
  lengths vector and is compiled in only where lengths are given.
- matmuls request float32 accumulation (``preferred_element_type``) so
  bf16 inputs hit the MXU without losing the softmax statistics.
- On CPU backends the kernels run in the Pallas interpreter, so the same
  code path is exercised by the virtual-mesh test suite.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register

__all__ = ["flash_attention", "flash_tile_plan", "grouped_matmul",
           "grouped_matmul_grads", "grouped_tiles", "rows_of_tokens",
           "tokens_of_rows", "expert_activation", "ssm_scan_chunks", "ssm_scan_tiles",
           "ssm_conv_tiles", "ssm_conv_pass", "ssm_conv_pass_grads",
           "ssm_norm_tiles", "ssm_norm_pass", "ssm_norm_pass_grads",
           "ragged_paged_attention", "ragged_paged_attention_reference",
           "ragged_paged_verify", "ragged_paged_verify_reference"]

_NEG_INF = -1e30


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# the tile plan: which tiles of the (query, key) square a call computes
# ---------------------------------------------------------------------------
_FIRST, _LAST, _KIND_SHIFT = 1, 2, 2     # a walk step's flags: two bits,
                                         # then the block's kind


def _cols_of_rows(q_lo, q_hi, n, sk, window):
    """[first, one past the last) of ``n`` sub-columns of ``sk`` keys,
    from key 0 on, that queries ``q_lo .. q_hi`` see some key of under
    the causal band."""
    end = min(max(q_hi + sk, 0) // sk, n)
    first = max(q_lo - (window - 1), 0) // sk if window > 0 else 0
    return first, end


def _crossed(q_lo, q_hi, k_lo, k_hi, window):
    """Whether the band's edge crosses the tile (it hides some pair of
    it): a tile it does not cross needs no mask arithmetic."""
    return k_hi > q_lo or (window > 0 and k_lo < q_hi - (window - 1))


@dataclasses.dataclass(frozen=True)
class FlashTilePlan:
    """What ``flash_attention``'s three kernels compute for one call,
    from its shapes and flags alone (``flash_tile_plan``).

    The (Lq, Lk) square is cut into ``block_q x block_k`` grid blocks.
    A kernel's innermost grid axis walks only the blocks the causal band
    (``k <= q``, and ``k > q - window`` under a window) shows a pair of.
    What is computed of a block depends on where the band lies in it,
    which is a matter of ``first query - first key`` alone, so the
    blocks are of a few ``kinds``: the band hides no pair (the block is
    one tile, no mask arithmetic), or its edge crosses the block, which
    is then ``sub_q x sub_k`` sub-tiles, those hidden whole left out,
    the others computed a strip at a time (``_strips``) and a strip
    masked only if the edge crosses it.  Every kind is static: a
    kernel's body holds one straight run of tiles a kind.  A call that
    is not causal has no band: every block, whole.  Four integers a
    query head say how far the call is from its mask: ``steps`` (grid
    steps of the forward or the dQ kernel; the dK/dV kernel makes as
    many a query head), ``computing_steps`` (those that reach a visible
    pair: the others only write a block of zeros that nothing else
    would), ``pairs_computed`` (the computed tiles' area) and
    ``pairs_visible`` (the mask's)."""
    Lq: int
    Lk: int
    group: int                  # query heads a key/value head
    causal: bool
    window: int                 # -1: none
    check_len: bool             # ``lengths`` given, or Lk padded
    block_q: int
    block_k: int
    sub_q: int
    sub_k: int
    steps: int = 0
    computing_steps: int = 0
    pairs_computed: int = 0
    pairs_visible: int = 0

    @property
    def nq(self):
        return -(-self.Lq // self.block_q)

    @property
    def nk(self):
        return -(-self.Lk // self.block_k)

    def block_tiles(self, i, j):
        """The tiles computed of grid block (query block i, key block
        j), each (first query, first key, queries, keys, whether the
        band's edge crosses it) from the block's corner; none where the
        band hides the block whole."""
        return self._tiles_at(i * self.block_q - j * self.block_k)

    @functools.lru_cache(maxsize=None)
    def _tiles_at(self, d):
        # d: the block's first query less its first key
        bq, bk, sq, sk, w = (self.block_q, self.block_k, self.sub_q,
                             self.sub_k, self.window)
        if not self.causal or not _crossed(d, d + bq - 1, 0, bk - 1, w):
            return ((0, 0, bq, bk, False),)
        return tuple(
            (a * sq, b * sk, sq, sk, _crossed(
                d + a * sq, d + a * sq + sq - 1, b * sk, b * sk + sk - 1, w))
            for a in range(bq // sq)
            for b in range(*_cols_of_rows(d + a * sq, d + a * sq + sq - 1,
                                          bk // sk, sk, w)))

    @functools.cached_property
    def kinds(self):
        """The distinct ``block_tiles`` of the call; last the empty one,
        of a step that only writes zeros."""
        found = dict.fromkeys(self.block_tiles(i, j) for i in range(self.nq)
                              for j in range(self.nk))
        return tuple(tiles for tiles in found if tiles) + ((),)

    @functools.lru_cache(maxsize=None)
    def walk(self, order):
        """The grid steps of a kernel, in order, as three int32 arrays:
        the block index of the operand that stays (``order`` "qk": the
        query block, for the forward and dQ kernels; "kq": the key
        block, for dK/dV), of the operand that is walked, and the
        step's flags (``_FIRST`` / ``_LAST`` of its staying block, then
        its kind).  A staying block the band shows nothing of gets one
        step all the same, of the empty kind: its output is still to be
        written."""
        n_stay, n_walk = ((self.nq, self.nk) if order == "qk"
                          else (self.nk, self.nq))
        kind_of = {tiles: n for n, tiles in enumerate(self.kinds)}
        stay, walked, flags = [], [], []
        for o in range(n_stay):
            kinds = [(w, self.block_tiles(*((o, w) if order == "qk"
                                            else (w, o))))
                     for w in range(n_walk)]
            live = [(w, t) for w, t in kinds if t] or [(n_walk - 1, ())]
            for n, (w, tiles) in enumerate(live):
                stay.append(o)
                walked.append(w)
                flags.append((kind_of[tiles] << _KIND_SHIFT)
                             | (_FIRST if n == 0 else 0)
                             | (_LAST if n == len(live) - 1 else 0))
        return tuple(np.asarray(a, np.int32) for a in (stay, walked, flags))

    def tiles(self):
        """Every tile a query head's kernels compute, as (first query,
        first key, queries, keys, whether the band's edge crosses it)."""
        return [(i * self.block_q + r, j * self.block_k + c, rows, cols, x)
                for i in range(self.nq) for j in range(self.nk)
                for r, c, rows, cols, x in self.block_tiles(i, j)]


def _ceil_to(x, m):
    return (x + m - 1) // m * m


# Timed on a v5e (PERF.md, PR 40): what a block on the band's edge is
# walked in.
_SUB_TILE = 256


def _sub_tile(block):
    """The sub-tile edge for a block edge: ``_SUB_TILE`` where it
    divides the block; half of an explicit block too small for the chip
    (the interpreter's tests: 16 gives 8); else the block whole."""
    if block % _SUB_TILE == 0:
        return _SUB_TILE
    return block // 2 if block < 128 and block % 16 == 0 else block


@functools.lru_cache(maxsize=None)
def flash_tile_plan(Lq, Lk, causal=False, window=None, group=1,
                    lengths=False, block_q=None, block_k=None,
                    sub_q=None, sub_k=None):
    """The ``FlashTilePlan`` of a ``flash_attention`` call over Lq
    queries and Lk keys (of any width: heads of 64 and of 128 want the
    same tiles), ``group`` query heads a key/value head, ``lengths``
    saying whether key lengths are given.  Explicit block
    and sub-tile shapes win; the defaults were timed on a v5e (PERF.md,
    PR 40).  ``plan.pairs_computed / plan.pairs_visible`` is how far the
    call is from its mask, for any shape, with no device."""
    window = -1 if window is None else int(window)
    if block_q is None or block_k is None:
        default = 128 if Lk <= 128 else 512 if Lk <= 1024 else 1024
        block_q, block_k = block_q or default, block_k or default
    block_q = min(block_q, _ceil_to(Lq, 8))
    block_k = min(block_k, _ceil_to(Lk, 8))
    sub_q = sub_q or _sub_tile(block_q)
    sub_k = sub_k or _sub_tile(block_k)
    if block_q % sub_q or block_k % sub_k:
        from ..base import MXNetError
        raise MXNetError(
            f"flash_tile_plan: sub-tiles of {sub_q} x {sub_k} do not "
            f"divide blocks of {block_q} x {block_k}")
    plan = FlashTilePlan(
        Lq, Lk, group, bool(causal), window,
        bool(lengths) or Lk % block_k != 0, block_q, block_k, sub_q, sub_k)
    tiles = plan.tiles()
    q = np.arange(Lq, dtype=np.int64)
    hi = np.minimum(q, Lk - 1) if causal else np.full_like(q, Lk - 1)
    lo = np.maximum(q - (window - 1), 0) if window > 0 else 0
    return dataclasses.replace(
        plan, steps=len(plan.walk("qk")[0]),
        computing_steps=len({(t[0] // block_q, t[1] // block_k)
                             for t in tiles}),
        pairs_computed=sum(t[2] * t[3] for t in tiles),
        pairs_visible=int(np.maximum(hi - lo + 1, 0).sum()))


# ---------------------------------------------------------------------------
# the kernels' common parts
# ---------------------------------------------------------------------------
def _masked(s, q0, k0, kv_len, band, window):
    """The score tile ``s`` (first query ``q0``, first key ``k0``) with
    its hidden pairs at -1e30: keys from ``kv_len`` on where a length is
    given, and where ``band`` the pairs outside the causal band."""
    if kv_len is None and not band:
        return s
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = None if kv_len is None else col < kv_len - k0
    if band:
        ahead = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) - col
        seen = ahead >= k0 - q0                 # k <= q
        if window > 0:
            seen = jnp.logical_and(seen, ahead <= k0 - q0 + (window - 1))
        mask = seen if mask is None else jnp.logical_and(mask, seen)
    return jnp.where(mask, s, _NEG_INF)


def _strips(tiles, axis):
    """``tiles`` with every run of neighbours along ``axis`` (0:
    queries, 1: keys) joined into one tile, masked if any of the run is:
    what a kernel computes of a block at a time.  A sub-tile by itself
    costs a quarter of a microsecond more than its share of a whole
    block (PERF.md, PR 40), and the forward kernel pays for every
    visit of a row; a strip pays once."""
    out = []
    for t in sorted(tiles, key=lambda t: (t[1 - axis], t[axis])):
        u = out[-1] if out else None
        if (u and u[1 - axis] == t[1 - axis] and u[3 - axis] == t[3 - axis]
                and u[axis] + u[2 + axis] == t[axis]):
            u = list(u)
            u[2 + axis] += t[2 + axis]
            u[4] = u[4] or t[4]
            out[-1] = tuple(u)
        else:
            out.append(t)
    return out


def _visit(plan, tile, flags, q0, k0, kv_len, axis):
    """Call ``tile(rows, cols, first query, first key, band)`` for what
    the plan computes of the grid block at (q0, k0), ``band`` saying
    whether the band's edge crosses the tile: one static run of tiles
    for each kind of block, the step's flags choosing, a kind's
    sub-tiles joined into strips along ``axis`` (the one the kernel
    does not accumulate over)."""
    live = True if kv_len is None else k0 < kv_len
    for n, tiles in enumerate(plan.kinds):
        if not tiles:
            continue

        @pl.when(jnp.logical_and(live, (flags >> _KIND_SHIFT) == n))
        def _tiles(tiles=_strips(tiles, axis)):
            for r, c, rows, cols, band in tiles:
                tile(pl.ds(r, rows), pl.ds(c, cols), q0 + r, k0 + c, band)


def _step(plan, lens_ref, q_blk, k_blk, flags_ref, batch):
    """(the step's flags, first query, first key, key length or None) of
    a kernel's grid step ``t = program_id(1)``, from the scalar prefetch
    (lengths, the walk's query blocks, key blocks and flags)."""
    t = pl.program_id(1)
    kv_len = lens_ref[batch] if plan.check_len else None
    return (flags_ref[t], q_blk[t] * plan.block_q, k_blk[t] * plan.block_k,
            kv_len)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(lens_ref, q_blk, k_blk, flags_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, sm_scale, plan):
    flags, q0, k0, kv_len = _step(plan, lens_ref, q_blk, k_blk, flags_ref,
                                  pl.program_id(0))

    @pl.when((flags & _FIRST) != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(rows, cols, r0, c0, band):
        # q/k/v stay in their storage dtype (bf16 on the training path):
        # bf16xbf16->fp32 is the MXU fast path — upcasting inputs first
        # would halve matmul throughput.  Softmax statistics are fp32.
        q = q_ref[0, rows, :]
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _masked(s, r0, c0, kv_len, band, plan.window)
        m_prev = m_scr[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[rows, :] = l_scr[rows, :] * corr + jnp.sum(
            p, axis=1, keepdims=True)
        acc_scr[rows, :] = acc_scr[rows, :] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[rows, :] = m_new

    _visit(plan, tile, flags, q0, k0, kv_len, 1)

    @pl.when((flags & _LAST) != 0)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, _NEG_INF,
                               m_scr[:] + jnp.log(safe_l))


# ---------------------------------------------------------------------------
# backward (FlashAttention-2: dQ pass + dK/dV pass, P recomputed)
# ---------------------------------------------------------------------------
def _bwd_tile(refs, rows, cols, r0, c0, band, kv_len, sm_scale, window):
    """(q, k, do, P, dS) of one tile, P recomputed from the saved
    logsumexp."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs
    q = q_ref[0, rows, :]
    k = k_ref[0, cols, :]
    do = do_ref[0, rows, :]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    s = _masked(s, r0, c0, kv_len, band, window)
    p = jnp.exp(s - lse_ref[0, rows, :])
    dp = jax.lax.dot_general(
        do, v_ref[0, cols, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, rows, :]) * sm_scale
    return q, k, do, p, ds


def _bwd_dq_kernel(lens_ref, q_blk, k_blk, flags_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, sm_scale,
                   plan):
    flags, q0, k0, kv_len = _step(plan, lens_ref, q_blk, k_blk, flags_ref,
                                  pl.program_id(0))

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(rows, cols, r0, c0, band):
        _q, k, _do, _p, ds = _bwd_tile(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rows, cols,
            r0, c0, band, kv_len, sm_scale, plan.window)
        dq_scr[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _visit(plan, tile, flags, q0, k0, kv_len, 1)

    @pl.when((flags & _LAST) != 0)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(lens_ref, k_blk, q_blk, flags_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
                    dv_scr, *, sm_scale, plan):
    # one key/value head a grid row; for each of its key blocks the walk
    # visits the band's query blocks, and the innermost axis the
    # ``group`` query heads that read it, so dK and dV are summed over
    # the group in the scratch accumulators
    head = pl.program_id(2)
    flags, q0, k0, kv_len = _step(plan, lens_ref, q_blk, k_blk, flags_ref,
                                  pl.program_id(0) * plan.group)

    @pl.when(jnp.logical_and((flags & _FIRST) != 0, head == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(rows, cols, r0, c0, band):
        q, _k, do, p, ds = _bwd_tile(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rows, cols,
            r0, c0, band, kv_len, sm_scale, plan.window)
        dv_scr[cols, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[cols, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _visit(plan, tile, flags, q0, k0, kv_len, 0)

    @pl.when(jnp.logical_and((flags & _LAST) != 0,
                             head == plan.group - 1))
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------
def _specs(plan, D, order, Dv):
    """BlockSpecs for (q, k, row statistics, a query head's rows of v's
    width Dv: the output and its cotangent, v) of a kernel that walks
    ``plan.walk(order)``: "qk" on the grid (query head, step), "kq" on
    (key/value head, step, query head of the group).  The step's blocks
    come from the walk's tables in the scalar prefetch.  Query head
    ``h`` reads key/value head ``h // group``: the index map does the
    grouping, so K and V are never repeated in HBM.  Where Dv is D the
    last two are the first two."""
    group = plan.group
    if order == "qk":
        qi = lambda b, t, lens, stay, walked, flags: (      # noqa: E731
            b, stay[t], 0)
        ki = lambda b, t, lens, stay, walked, flags: (      # noqa: E731
            b // group, walked[t], 0)
    else:
        qi = lambda b, t, h, lens, stay, walked, flags: (   # noqa: E731
            b * group + h, walked[t], 0)
        ki = lambda b, t, h, lens, stay, walked, flags: (   # noqa: E731
            b, stay[t], 0)
    return (pl.BlockSpec((1, plan.block_q, D), qi),
            pl.BlockSpec((1, plan.block_k, D), ki),
            pl.BlockSpec((1, plan.block_q, 1), qi),
            pl.BlockSpec((1, plan.block_q, Dv), qi),
            pl.BlockSpec((1, plan.block_k, Dv), ki))


def _run(kernel, plan, order, heads, in_specs, out_shape, out_specs, scratch,
         lens, inputs, interpret):
    walk = plan.walk(order)
    grid = (heads, len(walk[0])) + ((plan.group,) if order == "kq" else ())
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
    )(lens, *walk, *inputs)


def _pad_rows(x, L):
    return x if x.shape[1] == L else jnp.pad(
        x, ((0, 0), (0, L - x.shape[1]), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, lens, plan, sm_scale, interpret):
    return _flash_fwd(q, k, v, lens, plan, sm_scale, interpret)[0]


def _flash_fwd(q, k, v, lens, plan, sm_scale, interpret):
    BH, Lq, D = q.shape
    Dv = v.shape[2]
    q_spec, k_spec, row_spec, o_spec, v_spec = _specs(plan, D, "qk", Dv)
    bq = plan.block_q
    out, lse = _run(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, plan=plan),
        plan, "qk", BH, [q_spec, k_spec, v_spec],
        (jax.ShapeDtypeStruct((BH, Lq, Dv), q.dtype),
         jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32)),
        (o_spec, row_spec),
        [_scratch((bq, 1), jnp.float32), _scratch((bq, 1), jnp.float32),
         _scratch((bq, Dv), jnp.float32)],
        lens, (q, k, v), interpret)
    return out, (q, k, v, lens, out, lse)


def _flash_dq(plan, sm_scale, interpret, lens, inputs):
    q, v = inputs[0], inputs[2]
    q_spec, k_spec, row_spec, o_spec, v_spec = _specs(
        plan, q.shape[2], "qk", v.shape[2])
    return _run(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, plan=plan),
        plan, "qk", q.shape[0],
        [q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
        jax.ShapeDtypeStruct(q.shape, q.dtype), q_spec,
        [_scratch((plan.block_q, q.shape[2]), jnp.float32)],
        lens, inputs, interpret)


def _flash_dkv(plan, sm_scale, interpret, lens, inputs):
    k, v = inputs[1:3]
    q_spec, k_spec, row_spec, o_spec, v_spec = _specs(
        plan, k.shape[2], "kq", v.shape[2])
    return _run(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, plan=plan),
        plan, "kq", k.shape[0],
        [q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
        (jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)),
        (k_spec, v_spec),
        [_scratch((plan.block_k, k.shape[2]), jnp.float32),
         _scratch((plan.block_k, v.shape[2]), jnp.float32)],
        lens, inputs, interpret)


def _flash_bwd(plan, sm_scale, interpret, res, dout):
    q, k, v, lens, out, lse = res
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # (BH, Lq, 1)
    inputs = (q, k, v, dout, lse, delta)
    dq = _flash_dq(plan, sm_scale, interpret, lens, inputs)
    dk, dv = _flash_dkv(plan, sm_scale, interpret, lens, inputs)
    return dq, dk, dv, np.zeros(lens.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, lengths=None, causal=False, sm_scale=None,
                    block_q=None, block_k=None, interpret=None,
                    window=None):
    """Fused attention over (B*H, L, D) tensors.

    ``k`` and ``v`` may have fewer heads, (B*Hkv, L, D) with Hkv
    dividing H: query head ``h`` then reads key/value head
    ``h // (H // Hkv)`` (grouped-query attention) through the kernels'
    block index maps, dK and dV summed over each group's query heads.
    ``v`` may be of another width than q and k, (B*Hkv, L, Dv) (latent
    attention's heads of 192 over values of 128): the output, its
    cotangent and dV are Dv wide, and ``sm_scale`` defaults to D's.
    ``lengths``: optional int32 (B*H,) valid key lengths (padding mask).
    ``window``: optional causal sliding-window width — query q attends
    keys in [q-window+1, q] (Mistral/Longformer-style local attention).
    Requires causal=True.  Returns (B*H, Lq, Dv) in the query dtype.

    What is computed follows the mask (``flash_tile_plan``, which takes
    the explicit ``block_q`` / ``block_k`` of a test): the grid walks
    the blocks of the causal band only, so compute scales O(L*window)
    under a window, and inside a block on the band's edge the sub-tiles
    the mask hides whole are skipped.
    """
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    if BH % k.shape[0] or v.shape[:2] != k.shape[:2]:
        from ..base import MXNetError
        raise MXNetError(
            f"flash_attention: {k.shape[0]} key/value heads (k {k.shape}, "
            f"v {v.shape}) do not group {BH} query heads")
    if window is not None:
        from ..base import MXNetError
        if not causal:
            raise MXNetError(
                "flash_attention: window requires causal=True")
        if int(window) < 1:
            raise MXNetError(
                f"flash_attention: window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    plan = flash_tile_plan(Lq, Lk, bool(causal),
                           None if window is None else int(window),
                           BH // k.shape[0], lengths is not None,
                           block_q, block_k)
    lens = (jnp.full((BH,), Lk, jnp.int32) if lengths is None
            else lengths.astype(jnp.int32))
    out = _flash(_pad_rows(q, plan.nq * plan.block_q),
                 _pad_rows(k, plan.nk * plan.block_k),
                 _pad_rows(v, plan.nk * plan.block_k), lens, plan,
                 float(sm_scale), _interpret(interpret))
    return out[:, :Lq]


# ---------------------------------------------------------------------------
# grouped matmul: the expert layer's products (ops/moe.py).  The schedule
# is megablox's (jax.experimental.pallas.ops.tpu.megablox): row tiles
# follow the group sizes through scalar prefetch, so the work follows the
# rows that belong to a group and tiles past the last group are never
# visited.

# Picked on a v5e (PERF.md, PR 34).  A row tile is fetched whole; of its
# 128-row blocks only those that hold a row of the group are multiplied,
# so a group boundary costs 128 rows of work, not a tile.
_GROUPED_ROW_TILE = 1024        # M is a multiple; the weights' gradient's
_GROUPED_GMM_ROW_TILE = 512     # the products'; divides it
_GROUPED_ROW_BLOCK = 128
_GROUPED_WIDTH = 64             # K and N are multiples
_GROUPED_VMEM_BYTES = 100 * 2 ** 20     # of a v5e core's 128 MiB
_GROUPED_BLOCK_BYTES = 75 * 2 ** 20     # of it, the blocks' buffers


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _group_tiles(group_sizes, m, tm, visit_empty):
    """The row tiles to visit, in order: for grid step ``i`` the group
    ``gids[i]`` and the row tile ``tids[i]`` (a tile that holds a group
    boundary is visited once for each group in it, consecutively);
    ``offs[g] .. offs[g + 1]`` are group g's rows.  An empty group gets
    one step if ``visit_empty`` (its output is still to be written).
    Returns ((offs, gids, tids), number of steps).  jitted, like the
    kernels' wrappers, so that a model's many calls share one trace
    (tracing each anew cost the step's first call seconds)."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    first = (ends - group_sizes) // tm
    count = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                      1 if visit_empty else 0).astype(jnp.int32)
    until = jnp.cumsum(count)               # steps before the next group
    step = jnp.arange(m // tm + G - 1, dtype=jnp.int32)     # the most
    gids = jnp.minimum(jnp.sum(step[:, None] >= until[None, :], axis=1,
                               dtype=jnp.int32), G - 1)
    nth = step - (until - count)[gids]
    tids = jnp.clip(first[gids] + nth, 0, m // tm - 1)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offs, gids, tids), until[-1]


def _live_blocks(offs_ref, g, row0, tm):
    """(first, one past the last) of a row tile's 128-row blocks that
    hold a row of group g; ``row0`` is the tile's first row."""
    first = jnp.maximum(offs_ref[g] - row0, 0) // _GROUPED_ROW_BLOCK
    last = pl.cdiv(jnp.minimum(offs_ref[g + 1] - row0, tm),
                   _GROUPED_ROW_BLOCK)
    return first, last


def _block_rows(b):
    return pl.ds(pl.multiple_of(b * _GROUPED_ROW_BLOCK, _GROUPED_ROW_BLOCK),
                 _GROUPED_ROW_BLOCK)


def _in_group(offs_ref, g, first_row, shape):
    """Mask of ``shape``: the rows ``first_row ..`` that are group g's."""
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offs_ref[g]) & (row < offs_ref[g + 1])


def _gmm_kernel(offs_ref, gids_ref, tids_ref, lhs_ref, rhs_ref, out_ref,
                w_ref, *, transpose_rhs):
    i = pl.program_id(1)
    g = gids_ref[i]

    # the group's weights enter the MXU as bfloat16: cast once a group,
    # not once a row tile (the block stays in VMEM while the group lasts)
    @pl.when((i == 0) | (gids_ref[jnp.maximum(i - 1, 0)] != g))
    def _cast():
        w_ref[...] = rhs_ref[...].astype(w_ref.dtype)

    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    tm = lhs_ref.shape[0]
    row0 = tids_ref[i] * tm

    def block(b, _):
        rows = _block_rows(b)
        acc = jax.lax.dot_general(lhs_ref[rows, :], w_ref[...], contract,
                                  preferred_element_type=jnp.float32)
        # a tile on a group boundary is visited for each group in it:
        # keep the other groups' rows (and whatever lies past the last
        # group)
        mine = _in_group(offs_ref, g, row0 + b * _GROUPED_ROW_BLOCK,
                         acc.shape)
        out_ref[rows, :] = jnp.where(mine, acc, out_ref[rows, :])

    jax.lax.fori_loop(*_live_blocks(offs_ref, g, row0, tm), block, None)


def _gmm_columns(K, N, rhs_itemsize):
    """The widest column tile (all of N, or a multiple of 128 dividing
    it: what Mosaic takes as a block's last dimension) whose blocks fit
    the VMEM budget with the whole of K: two buffers each of weights,
    rows and output, and the bfloat16 weights.  None if none fits."""
    tm = _GROUPED_GMM_ROW_TILE
    for tn in range(N, 0, -128):
        need = (K * tn * (2 * rhs_itemsize + 2) + 2 * tm * K * 2
                + 2 * tm * tn * 4)
        if (N % tn == 0 and (tn == N or tn % 128 == 0)
                and need <= _GROUPED_BLOCK_BYTES):
            return tn
    return None


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    """``lhs[rows of g] @ rhs[g]`` (``rhs[g].T`` if ``transpose_rhs``)
    for every group g: lhs (M, K) bfloat16, rhs (G, K, N) or (G, N, K),
    result (M, N) float32, rows past the last group left as they were."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = _GROUPED_GMM_ROW_TILE
    tn = _gmm_columns(K, N, rhs.dtype.itemsize)
    meta, steps = _group_tiles(group_sizes, M, tm, False)
    if transpose_rhs:
        w_block = (None, tn, K)
        w_index = lambda n, i, offs, gids, tids: (gids[i], n, 0)  # noqa: E731
    else:
        w_block = (None, K, tn)
        w_index = lambda n, i, offs, gids, tids: (gids[i], 0, n)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, K),
                             lambda n, i, offs, gids, tids: (tids[i], 0)),
                pl.BlockSpec(w_block, w_index)],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, i, offs, gids, tids: (tids[i], n)),
            scratch_shapes=[_scratch(w_block[1:], jnp.bfloat16)]),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=interpret,
    )(*meta, lhs, rhs)


def _tgmm_kernel(offs_ref, gids_ref, tids_ref, lhs_ref, rhs_ref, out_ref):
    i = pl.program_id(0)
    g = gids_ref[i]

    @pl.when((i == 0) | (gids_ref[jnp.maximum(i - 1, 0)] != g))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    tm = lhs_ref.shape[0]
    row0 = tids_ref[i] * tm
    contract = (((0,), (0,)), ((), ()))

    def block(b, _):
        rows = _block_rows(b)
        r0 = row0 + b * _GROUPED_ROW_BLOCK
        inside = ((r0 >= offs_ref[g])
                  & (r0 + _GROUPED_ROW_BLOCK <= offs_ref[g + 1]))

        @pl.when(inside)
        def _whole():
            out_ref[...] += jax.lax.dot_general(
                lhs_ref[rows, :], rhs_ref[rows, :], contract,
                preferred_element_type=jnp.float32)

        # rows on a group boundary: the other groups' rows, and whatever
        # lies past the last group (NaN included), are selected away
        # from both operands
        @pl.when(jnp.logical_not(inside))
        def _boundary():
            def mine(ref):
                x = ref[rows, :]
                return jnp.where(_in_group(offs_ref, g, r0, x.shape),
                                 x.astype(jnp.float32), 0).astype(x.dtype)
            out_ref[...] += jax.lax.dot_general(
                mine(lhs_ref), mine(rhs_ref), contract,
                preferred_element_type=jnp.float32)

    jax.lax.fori_loop(*_live_blocks(offs_ref, g, row0, tm), block, None)


def _tgmm_fits(K, N):
    """The weights' gradient keeps a group's whole (K, N) result in
    VMEM, two buffers of it, beside two of each operand's row tile."""
    return (2 * K * N * 4 + 2 * _GROUPED_ROW_TILE * (K + N) * 2
            <= _GROUPED_BLOCK_BYTES)


@functools.partial(jax.jit, static_argnums=(3,))
def _tgmm(lhs, rhs, group_sizes, interpret):
    """``lhs[rows of g].T @ rhs[rows of g]`` for every group g: lhs
    (M, K), rhs (M, N) bfloat16, result (G, K, N) float32, zero for a
    group with no rows.  Rows past the last group are never read."""
    (M, K), N = lhs.shape, rhs.shape[1]
    G = group_sizes.shape[0]
    tm = _GROUPED_ROW_TILE
    meta, steps = _group_tiles(group_sizes, M, tm, True)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((tm, K),
                             lambda i, offs, gids, tids: (tids[i], 0)),
                pl.BlockSpec((tm, N),
                             lambda i, offs, gids, tids: (tids[i], 0))],
            out_specs=pl.BlockSpec(
                (None, K, N), lambda i, offs, gids, tids: (gids[i], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((G, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=interpret,
    )(*meta, lhs, rhs)


def _interpret(interpret):
    """The kernels run in the interpreter where there is only a CPU."""
    return (jax.default_backend() == "cpu" if interpret is None
            else bool(interpret))


def grouped_tiles(M, K, N, itemsize=4):
    """Whether an (M, K) x (G, K, N) grouped product takes the Pallas
    kernels: K and N multiples of 64 (Mosaic compiles and the chip
    multiplies a width of 1856 = 14.5 x 128 whole, as a block's full
    dimension: PERF.md, PR 37; below 64 nothing was tried), M of the row
    tile, and weights of ``itemsize`` bytes an element that leave room
    in VMEM.  The one predicate of the expert layer (ops/moe.py): its
    movers and its activation follow what their own operands show of
    it."""
    return not (M % _GROUPED_ROW_TILE or K % _GROUPED_WIDTH
                or N % _GROUPED_WIDTH
                or _gmm_columns(K, N, itemsize) is None
                or _gmm_columns(N, K, itemsize) is None
                or not _tgmm_fits(K, N))


def grouped_matmul_grads(lhs, rhs, group_sizes, g, interpret=None):
    """The two gradients of ``grouped_matmul(lhs, rhs, group_sizes)``
    for the result's cotangent ``g``: (the rows' (M, K) float32, the
    weights' in ``rhs``'s dtype, zero for a group with no rows).  Where
    the shapes tile, ``lhs`` and ``g`` enter the kernels as bfloat16
    (hand them over as bfloat16 and nothing is cast) and the rows past
    the last group are neither read nor written."""
    (M, K), N = lhs.shape, rhs.shape[2]
    if not grouped_tiles(M, K, N, rhs.dtype.itemsize):
        _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
            a, b, group_sizes, preferred_element_type=jnp.float32),
            lhs, rhs)
        d_lhs, d_rhs = vjp(g.astype(jnp.float32))
        return d_lhs.astype(jnp.float32), d_rhs
    interpret = _interpret(interpret)
    group_sizes = group_sizes.astype(jnp.int32)
    g = g.astype(jnp.bfloat16)
    # mxlint: disable=recompile-churn (interpret is a bool)
    d_lhs = _gmm(g, rhs, group_sizes, True, interpret)
    # mxlint: disable=recompile-churn (interpret is a bool)
    d_rhs = _tgmm(lhs.astype(jnp.bfloat16), g, group_sizes, interpret)
    return d_lhs, d_rhs.astype(rhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(lhs, rhs, group_sizes, lhs_dtype, interpret):
    return _grouped_fwd(lhs, rhs, group_sizes, lhs_dtype, interpret)[0]


def _grouped_fwd(lhs, rhs, group_sizes, lhs_dtype, interpret):
    lhs = lhs.astype(jnp.bfloat16)
    # mxlint: disable=recompile-churn (interpret is a bool)
    return (_gmm(lhs, rhs, group_sizes, False, interpret),
            (lhs, rhs, group_sizes))


def _grouped_bwd(lhs_dtype, interpret, res, g):
    lhs, rhs, group_sizes = res
    d_lhs, d_rhs = grouped_matmul_grads(lhs, rhs, group_sizes, g, interpret)
    return d_lhs.astype(lhs_dtype), d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None):
    """``lax.ragged_dot`` for the expert layer: ``lhs`` (M, K) rows
    sorted by group, ``rhs`` (G, K, N), ``group_sizes`` (G,) int32 whose
    sum is at most M; row i of the (M, N) float32 result is
    ``lhs[i] @ rhs[g]`` for the group g that holds row i.

    Where K and N are multiples of 64 and M of 1024 (``grouped_tiles``)
    this is a Pallas grouped matmul: operands enter the MXU as bfloat16
    (the weights cast a group at a time in VMEM), accumulation and
    result float32, and so the two gradients (the rows' in ``lhs``'s
    dtype, the weights' zero for a group with no rows).  **The rows past
    the last group are not written**: they hold whatever was there, NaN
    included, and a reader must select, never multiply by a mask.  Other
    shapes (and weights that leave no room in VMEM) take
    ``lax.ragged_dot`` on the operands as given, which zeroes those rows.
    """
    (M, K), N = lhs.shape, rhs.shape[2]
    if not grouped_tiles(M, K, N, rhs.dtype.itemsize):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32)
    return _grouped(lhs, rhs, group_sizes.astype(jnp.int32),
                    jnp.dtype(lhs.dtype), _interpret(interpret))


# ---------------------------------------------------------------------------
# the expert layer's movers and activation (ops/moe.py): everything
# around the grouped products, over the rows below the last held pair
# only.  XLA's gather costs its output rows, and the pair buffer has the
# worst case of them; these follow the group sizes, as the products do.
# Mosaic slices an array in HBM by whole (8, 128) tiles only, so no DMA
# can fetch one row of 2304 floats: the pair-side mover keeps the token
# rows in VMEM and copies rows there, and the token-side mover fetches
# runs of whole 8-row slabs (a token tile's pairs in one group are
# consecutive pair rows, because the sort is stable).

_MOVER_ROWS = 128               # pair rows, or tokens, a grid step moves
_ACT_ROWS = 512                 # pair rows a step of the activation
_MOVER_SOURCE_BYTES = 84 * 2 ** 20      # the token rows, whole in VMEM
_MOVER_BUFFER_BYTES = 40 * 2 ** 20      # the slabs' two landing buffers
_SLAB = 8                       # rows of a float32 tile
def relu2(x):
    """``relu(x)^2`` (So et al. 2021, Primer)."""
    return jnp.square(jax.nn.relu(x))


# Mosaic lowers these (no erfc there: the exact gelu stays in jnp)
_KERNEL_ACTIVATIONS = (jax.nn.relu, relu2, jax.nn.silu)


def _tile_index(i, *_prefetched):
    """Block i of the rows, all of the columns."""
    return i, 0


def _rows_kernel(tok_ref, x_ref, *refs, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    z_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, = refs
    tm = out_ref.shape[0]
    row0 = pl.program_id(0) * tm

    def slab(b, _):
        for r in range(_SLAB):          # unrolled: the loop is all scalar
            r = b * _SLAB + r
            buf[pl.ds(r, 1), :] = x_ref[pl.ds(tok_ref[row0 + r], 1), :]
    jax.lax.fori_loop(0, tm // _SLAB, slab, None)
    rows = buf[...]
    if dotted:
        dot_ref[...] = jnp.sum(rows * z_ref[...], axis=1, keepdims=True)
    if scaled:
        rows = rows * scale_ref[...]
    out_ref[...] = rows.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _rows(x, tok, live, scale, dot, dtype, interpret):
    C, M, tm = x.shape[1], tok.shape[0], _MOVER_ROWS
    index = _tile_index
    operands = [x] + [a for a in (scale, dot) if a is not None]
    in_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)]
    out_specs = [pl.BlockSpec((tm, C), index)]
    out_shape = [jax.ShapeDtypeStruct((M, C), dtype)]
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), index))
    if dot is not None:
        in_specs.append(pl.BlockSpec((tm, C), index))
        out_specs.append(pl.BlockSpec((tm, 1), index))
        out_shape.append(jax.ShapeDtypeStruct((M, 1), jnp.float32))
    return pl.pallas_call(
        functools.partial(_rows_kernel, scaled=scale is not None,
                          dotted=dot is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(live, tm),),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[_scratch((tm, C), x.dtype)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=interpret,
    )(tok, *operands)


def rows_of_tokens(x, tok, live, scale=None, dot=None, dtype=None,
                   interpret=None):
    """The pair-side mover: row i of the (M, C) result is ``x[tok[i]]``
    (times ``scale[i]`` if given) as ``dtype`` (default ``x``'s), for
    the ``live`` first pairs.  ``x`` (S, C) float32, ``tok`` (M,) int32,
    ``live`` an int32 scalar, ``scale`` (M,) float32.  With ``dot``
    (M, C) also returns (M,) float32, ``<dot[i], x[tok[i]]>``: a
    weight's gradient, on the pair side.

    Where C is a multiple of 128, M of the grouped products' row tile
    and ``x`` fits in VMEM this is a Pallas kernel that copies rows out
    of VMEM and stops at the last live tile: **the rows past it are not
    written** (the products' contract) and ``dot`` is not read there.
    Other shapes take the ``jnp`` gather over all M rows."""
    dtype = jnp.dtype(dtype or x.dtype)
    M, (S, C) = tok.shape[0], x.shape
    if (M % _GROUPED_ROW_TILE or C % 128 or S % _SLAB
            or x.dtype != jnp.float32
            or S * C * 4 > _MOVER_SOURCE_BYTES):
        rows = x[tok]
        dots = None if dot is None else jnp.sum(rows * dot, axis=1)
        if scale is not None:
            rows = rows * scale[:, None]
        return rows.astype(dtype) if dot is None else (
            rows.astype(dtype), dots)
    # mxlint: disable=recompile-churn (a dtype and a bool)
    out = _rows(x, tok.astype(jnp.int32), live.astype(jnp.int32),
                None if scale is None else scale.reshape(M, 1), dot, dtype,
                _interpret(interpret))
    return out[0] if dot is None else (out[0], out[1].reshape(M))


def _token_tile(S, k, G, C):
    """(tokens a grid step sums: the largest of 128, 64 .. 8 that
    divides S; the most 8-row slabs its pairs can lie in: a run in each
    group, each run with a ragged slab at either end), or None where
    two landing buffers of that many slabs do not fit."""
    for ts in (128, 64, 32, 16, 8):
        cap = _ceil_to(ts * k // _SLAB + 2 * G,
                       _GROUPED_ROW_BLOCK // _SLAB)
        if S % ts == 0 and 2 * cap * _SLAB * C * 4 <= _MOVER_BUFFER_BYTES:
            return ts, cap
    return None


def _sum_before(a, axis):
    """The exclusive prefix sum along a short axis as one masked
    reduction (``jnp.cumsum`` is a scan of many small steps on the
    TPU, and these tables are a few hundred numbers)."""
    n = a.shape[axis]
    a = jnp.moveaxis(a, axis, -1)
    before = jnp.arange(n)[:, None] < jnp.arange(n)[None, :]
    out = jnp.sum(jnp.where(before, a[..., :, None], 0), axis=-2)
    return jnp.moveaxis(out, -1, axis)


def _token_runs(inverse, group, group_sizes, ts, cap):
    """Where a tile of ``ts`` tokens finds its pairs' rows: pairs are
    sorted by group and, inside a group, by token, so the tile's pairs
    in group g are one run of rows.  Returns ((the slabs to fetch, tile
    by tile, ``cap`` a tile; how many of them; the one among them that
    the last row of all ends inside, -1 if none does; the number of
    rows in a group), for each pair the row of its tile's landing buffer
    that will hold it, -1 for a pair in no group)."""
    S, k = inverse.shape
    G = group_sizes.shape[0]
    tiles = S // ts
    i32 = jnp.int32
    # groups lead: the long axis stays minor, where the lanes are
    of_group = (group.reshape(1, tiles, ts * k)
                == jnp.arange(G, dtype=i32).reshape(G, 1, 1))
    count = jnp.sum(of_group, axis=2, dtype=i32)            # (G, tiles)
    first = (_sum_before(group_sizes, 0)[:, None]
             + _sum_before(count, 1))
    slab0 = first // _SLAB
    n = jnp.where(count > 0, (first + count - 1) // _SLAB - slab0 + 1, 0)
    base = _sum_before(n, 0)                                # (G, tiles)
    until = base + n
    q = jnp.arange(cap, dtype=i32)
    g_of_q = jnp.minimum(jnp.sum(q >= until[:, :, None], axis=0, dtype=i32),
                         G - 1)                             # (tiles, cap)
    mine = g_of_q[None] == jnp.arange(G, dtype=i32).reshape(G, 1, 1)
    slabs = q + jnp.sum(jnp.where(mine, (slab0 - base)[:, :, None], 0),
                        axis=0)
    live = jnp.sum(group_sizes)
    ragged = (n > 0) & (first + count == live) & (live % _SLAB > 0)
    tail = jnp.max(jnp.where(ragged, until - 1, -1), axis=0)
    shift = (base - slab0) * _SLAB
    vrow = inverse.reshape(tiles, ts * k) + jnp.sum(
        jnp.where(of_group, shift[:, :, None], 0), axis=0)
    vrow = jnp.where(group.reshape(tiles, ts * k) < G, vrow, -1)
    return ((slabs.reshape(-1), until[-1], tail, live.reshape(1)),
            vrow.reshape(S, k))


def _tokens_kernel(slab_ref, n_ref, tail_ref, live_ref, z_hbm, vrow_ref,
                   w_ref, out_ref, buf, sem, *, cap):
    ts, k = w_ref.shape
    i = pl.program_id(0)
    block = _GROUPED_ROW_BLOCK

    def slab(step, q):
        at = lambda a: pl.ds(pl.multiple_of(a * _SLAB, _SLAB), _SLAB)  # noqa: E731,E501
        return pltpu.make_async_copy(
            z_hbm.at[at(slab_ref[step * cap + q])],
            buf.at[step % 2, at(q)], sem.at[step % 2])

    def fetch(step):
        jax.lax.fori_loop(0, n_ref[step],
                          lambda q, _: slab(step, q).start(), None)

    # two landing buffers: the next tile's slabs are on their way while
    # this one is summed.  Zero them first: a block of 128 rows is
    # multiplied whole, and what no slab has landed on must be finite.
    @pl.when(i == 0)
    def _first():
        buf[...] = jnp.zeros_like(buf)
        fetch(i)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        fetch(i + 1)

    jax.lax.fori_loop(0, n_ref[i], lambda q, _: slab(i, q).wait(), None)
    slot = i % 2

    # so must the rows past the last of all be, in the slab it ends in
    @pl.when(tail_ref[i] >= 0)
    def _tail():
        rows = pl.ds(pl.multiple_of(tail_ref[i] * _SLAB, _SLAB), _SLAB)
        x = buf[slot, rows, :]
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        buf[slot, rows, :] = jnp.where(row < live_ref[0] % _SLAB, x, 0)

    out_ref[...] = jnp.zeros_like(out_ref)
    vrow, w = vrow_ref[...], w_ref[...]

    def rows(b, _):
        # the MXU moves the rows: (tokens x rows) weights, one a pair,
        # times the rows, float32 throughout
        col = b * block + jax.lax.broadcasted_iota(jnp.int32, (ts, block), 1)
        weights = jnp.zeros((ts, block), jnp.float32)
        for j in range(k):
            weights = weights + jnp.where(vrow[:, j:j + 1] == col,
                                          w[:, j:j + 1], 0.0)
        out_ref[...] += jax.lax.dot_general(
            weights, buf[slot, _block_rows(b), :], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    jax.lax.fori_loop(0, pl.cdiv(n_ref[i] * _SLAB, block), rows, None)


@functools.partial(jax.jit, static_argnums=(5,))
def _tokens(z, w, inverse, group, group_sizes, interpret):
    (S, k), C = inverse.shape, z.shape[1]
    ts, cap = _token_tile(S, k, group_sizes.shape[0], C)
    index = _tile_index
    meta, vrow = _token_runs(inverse, group, group_sizes, ts, cap)
    return pl.pallas_call(
        functools.partial(_tokens_kernel, cap=cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S // ts,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((ts, k), index),
                      pl.BlockSpec((ts, k), index)],
            out_specs=pl.BlockSpec((ts, C), index),
            scratch_shapes=[_scratch((2, cap * _SLAB, C), z.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((S, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=interpret,
    )(*meta, z, vrow, w)


def tokens_of_rows(z, w, inverse, group, group_sizes, interpret=None):
    """The token-side mover, ``rows_of_tokens``' transpose: token s of
    the (S, C) float32 result is the float32 sum over its k pairs j of
    ``w[s, j] * z[inverse[s, j]]`` for the pairs that are in a group,
    and of nothing for the others (whatever ``z`` holds there).
    ``z`` (M, C) float32, the pairs' rows sorted by group and inside a
    group by pair (s, j); ``w`` (S, k) float32; ``inverse`` (S, k) int32
    the row of each pair; ``group`` (S, k) int32 its group, G for a pair
    in none; ``group_sizes`` (G,).

    Where the shapes tile (``rows_of_tokens``) a Pallas kernel fetches
    only the 8-row slabs that hold a pair in a group and the MXU sums
    those pairs' rows at float32 precision (in its own order, not slot
    order); other shapes gather all M rows, select and sum in ``jnp``."""
    (S, k), C = inverse.shape, z.shape[1]
    G = group_sizes.shape[0]
    if ((S * k) % _GROUPED_ROW_TILE or C % 128 or z.dtype != jnp.float32
            or _token_tile(S, k, G, C) is None):
        held = group < G
        pairs = jnp.where(held[..., None], z[inverse], 0)
        return jnp.einsum("skc,sk->sc", pairs,
                          jnp.where(held, w, 0).astype(pairs.dtype))
    # mxlint: disable=recompile-churn (interpret is a bool)
    return _tokens(z, w.astype(jnp.float32), inverse.astype(jnp.int32),
                   group.astype(jnp.int32), group_sizes.astype(jnp.int32),
                   _interpret(interpret))


def _halves(h, gated):
    H = h.shape[1] // 2
    return (h[:, :H], h[:, H:]) if gated else (h, None)


def _activated(h, act, gated):
    gate, up = _halves(h, gated)
    return act(gate) * up if gated else act(gate)


def _act_kernel(h_ref, out_ref, *, act, gated):
    out_ref[...] = _activated(h_ref[...], act, gated).astype(out_ref.dtype)


def _act_grad_kernel(h_ref, g_ref, out_ref, *, act, gated):
    gate, up = _halves(h_ref[...], gated)
    g = g_ref[...]
    a, vjp = jax.vjp(act, gate)
    H = gate.shape[1]
    out_ref[:, :H] = vjp(g * up if gated else g)[0].astype(out_ref.dtype)
    if gated:
        out_ref[:, H:] = (g * a).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _act(h, g, live, act, gated, dtype, interpret):
    M, N = h.shape
    H = N // 2 if gated else N
    ta = _ACT_ROWS
    index = _tile_index
    kernel, operands, width = _act_kernel, [h], H
    in_specs = [pl.BlockSpec((ta, N), index)]
    if g is not None:
        kernel, operands, width = _act_grad_kernel, [h, g], N
        in_specs.append(pl.BlockSpec((ta, H), index))
    return pl.pallas_call(
        functools.partial(kernel, act=act, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(pl.cdiv(live, ta),),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((ta, width), index)),
        out_shape=jax.ShapeDtypeStruct((M, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=interpret,
    )(*operands)


def expert_activation(h, live, act, gated, g=None, dtype=None,
                      interpret=None):
    """The expert FFN's activation over the ``live`` first rows of the
    first product ``h`` (M, N) float32: ``act(h)``, or ``act(gate) * up``
    of ``h = [gate | up]`` if ``gated``, as ``dtype`` (default ``h``'s).
    With ``g``, the cotangent of that result, its gradient in ``h``
    instead.

    Where the halves are multiples of 128 columns and M of the grouped
    products' row tile a Pallas kernel stops at the last live tile and
    **the rows past it are not written**; other shapes, and the exact
    gelu, compute all M rows in ``jnp``."""
    dtype = jnp.dtype(dtype or h.dtype)
    M, N = h.shape
    # (a width of 14.5 x 128 compiles, and on the chip the kernel then
    # takes 1.8 ms over 3,072 live rows where XLA takes 0.8 ms over all
    # 49,152: PERF.md, PR 37; so such a width stays in jnp)
    if (M % _GROUPED_ROW_TILE or (N // 2 if gated else N) % 128
            or act not in _KERNEL_ACTIVATIONS):
        fn = functools.partial(_activated, act=act, gated=gated)
        if g is None:
            return fn(h).astype(dtype)
        return jax.vjp(fn, h)[1](g.astype(h.dtype))[0].astype(dtype)
    # mxlint: disable=recompile-churn (one of _KERNEL_ACTIVATIONS, bools, a
    # dtype)
    return _act(h, g, live.astype(jnp.int32), act, bool(gated),
                dtype, _interpret(interpret))


# ---------------------------------------------------------------------------
# the selective scan of a Mamba-2 mixer (ops/ssm.py), chunk by chunk.  A
# grid step is one (row, group of B and C, chunk); the chunk axis is last
# and sequential, and the group's state, (N, R x P) float32 for its R
# heads of P channels, stays in VMEM scratch from one chunk to the next.
# x, B and C are read where the mixer's convolution left them, side by
# side in one (L, H x P + 2 G x N) array: x in column blocks of R x P a
# group, B and C in blocks of N behind them, nothing sliced out or laid
# out anew in HBM; y is written as (L, H x P) the same way;
# a chunk's (Q, Q) decay matrix a head is made, multiplied into the
# group's C B^T and consumed by the MXU without leaving VMEM.  The MXU's
# operands are bfloat16 exactly where XLA's default precision rounds the
# operands of ops/ssm._chunked_scan's einsums; decays, sums and the state
# are float32.
#
# The per-head vectors come from jnp (they are 1/P of x): ``cols``
# (b, G, L, 4 R) holds, a position down a column, [delta | a | exp(a) |
# exp(a_last - a)] with ``a`` the running sum of ``delta A`` inside the
# chunk, and ``a_rows`` (b, G, R, L) holds ``a`` a position along a row: a
# decay matrix needs both.

_SSM_LANES = 128                # Q, N and R x P are multiples
_SSM_VMEM_BYTES = 64 * 2 ** 20
_SSM_BLOCK_BYTES = 40 * 2 ** 20         # of it, blocks and temporaries


def ssm_scan_tiles(Q, G, R, P, N):
    """Whether a scan with chunks of Q positions and G groups of R heads
    of P channels over a state of N takes the Pallas kernels: Q, N and
    R x P multiples of 128, P a multiple of 128 or a power of two below
    it (a 128-lane block then holds whole heads), at least two heads a
    group (with one, Mosaic is asked to broadcast one element along
    both axes and does not), x's columns a whole number of blocks of N
    (B's and C's lie behind them in one array), and a chunk's blocks
    and temporaries inside the VMEM budget."""
    W = R * P
    lanes = _SSM_LANES
    heads_fit = R > 1 and (P % lanes == 0 or (P >= 8 and lanes % P == 0))
    # the backward kernel: some twenty (Q, W) and six (N, W) float32
    # arrays, blocks twice, and a head's (Q, Q) matrices
    need = 4 * (20 * Q * W + 6 * N * W + 8 * Q * Q + 8 * Q * N)
    return not (Q % lanes or N % lanes or W % lanes or G * W % N
                or not heads_fit or need > _SSM_BLOCK_BYTES)


def _ssm_col(cols_ref, k, r):
    """Vector k (0 delta, 1 a, 2 exp(a), 3 exp(a_last - a)) of head r:
    (Q, 1)."""
    i = k * (cols_ref.shape[1] // 4) + r
    return cols_ref[:, i:i + 1]


def _ssm_widen(piece, R, P):
    """(rows, R x P) from ``piece(r)`` (rows, 1), head r's value on each
    of its P columns."""
    rows = piece(0).shape[0]
    lanes = _SSM_LANES
    if P % lanes == 0:
        return jnp.concatenate(
            [jnp.broadcast_to(piece(r), (rows, P)) for r in range(R)], axis=1)
    per = lanes // P
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    blocks = []
    for first in range(0, R, per):
        block = jnp.broadcast_to(piece(first), (rows, lanes))
        for k in range(1, per):
            block = jnp.where(
                lane >= k * P,
                jnp.broadcast_to(piece(first + k), (rows, lanes)), block)
        blocks.append(block)
    return jnp.concatenate(blocks, axis=1)


def _ssm_head(v, r, P):
    """Head r's columns of ``v`` (rows, R x P) on whole 128-lane blocks:
    (the columns themselves, or their block with its other heads zeroed;
    the slice of columns that stands for)."""
    lanes = _SSM_LANES
    if P % lanes == 0:
        cols = slice(r * P, (r + 1) * P)
        return v[:, cols], cols
    first = r * P // lanes * lanes
    cols = slice(first, first + lanes)
    block = v[:, cols]
    lane = first + jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    mine = (lane >= r * P) & (lane < (r + 1) * P)
    return jnp.where(mine, block, jnp.zeros_like(block)), cols


def _ssm_head_sum(v, r, P):
    """The sum over head r's columns of ``v`` (rows, R x P) float32:
    (rows, 1)."""
    return jnp.sum(_ssm_head(v, r, P)[0], axis=1, keepdims=True)


def _ssm_decay(cols_ref, a_rows_ref, r, seen):
    """Head r's (Q, Q) decay: position t reads s <= t through
    ``exp(a_t - a_s)``, a difference of running sums and never a
    quotient of two exponentials."""
    return jnp.exp(jnp.where(
        seen, _ssm_col(cols_ref, 1, r) - a_rows_ref[r:r + 1, :], -jnp.inf))


def _ssm_seen(Q):
    return (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def _mxu(a, b, contract=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, contract,
                               preferred_element_type=jnp.float32)


def _ssm_fwd_kernel(x_ref, b_ref, *refs, P, states):
    """One chunk of one group: y, or with ``states`` (the backward
    pass's sweep: no C, no D, no y) the state that enters the chunk."""
    if states:
        cols_ref, out_ref, h_ref = refs
    else:
        c_ref, cols_ref, a_rows_ref, d_ref, out_ref, h_ref = refs
    Q, R = cols_ref.shape[0], cols_ref.shape[1] // 4
    bf = jnp.bfloat16

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    def wide(k):
        return _ssm_widen(lambda r: _ssm_col(cols_ref, k, r), R, P)

    x = x_ref[...]
    xd = x * wide(0)
    B = b_ref[...].astype(bf)
    h, exp_a = h_ref[...], wide(2)
    if states:
        out_ref[...] = h
    else:
        C, xd_b = c_ref[...].astype(bf), xd.astype(bf)
        scores = _mxu(C, B, _NT)
        # what the state carried in gives, and D x
        out_ref[...] = _mxu(C, h.astype(bf)) * exp_a + d_ref[...] * x
        seen = _ssm_seen(Q)
        for r in range(R):
            masked = (scores * _ssm_decay(cols_ref, a_rows_ref, r, seen)
                      ).astype(bf)
            mine, cols = _ssm_head(xd_b, r, P)
            out_ref[:, cols] += _mxu(masked, mine)
    # exp(a) of the chunk's last position is what the chunk leaves of the
    # state that entered it; then what it adds by its end
    h_ref[...] = (h * exp_a[Q - 1:Q]
                  + _mxu(B, (xd * wide(3)).astype(bf), _TN))


def _ssm_bwd_kernel(x_ref, b_ref, c_ref, cols_ref, a_rows_ref, d_ref, h_ref,
                    dy_ref, dx_ref, db_ref, dc_ref, dcols_ref, da_rows_ref,
                    dd_ref, dh_ref, *, P):
    """The same chunk transposed; the chunks come last to first, and
    ``dh_ref`` carries the gradient of the state that LEAVES the chunk.
    ``h_ref`` is the state that entered it (the forward sweep's)."""
    R, Q = a_rows_ref.shape
    bf = jnp.bfloat16

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def wide(k):
        return _ssm_widen(lambda r: _ssm_col(cols_ref, k, r), R, P)

    x, dy, h, dh = x_ref[...], dy_ref[...], h_ref[...], dh_ref[...]
    delta, exp_a, to_end = wide(0), wide(2), wide(3)
    xd = x * delta
    B, C = b_ref[...].astype(bf), c_ref[...].astype(bf)
    xd_b, dy_b, h_b, dh_b = (v.astype(bf) for v in (xd, dy, h, dh))
    dy_decayed = (dy * exp_a).astype(bf)
    d_added = _mxu(B, dh_b)                 # of (xd * to_end), (Q, W)
    d_exp_a = dy * _mxu(C, h_b)             # summed over a head's columns
    d_to_end = d_added * xd                 # the same
    dd_ref[...] += jnp.sum(dy * x, axis=0, keepdims=True)
    dx_ref[...] = d_added * to_end          # d xd, the heads' parts to come
    scores = _mxu(C, B, _NT)
    seen = _ssm_seen(Q)
    d_scores = jnp.zeros((Q, Q), jnp.float32)
    for r in range(R):
        decay = _ssm_decay(cols_ref, a_rows_ref, r, seen)
        masked = scores * decay
        mine, cols = _ssm_head(dy_b, r, P)
        d_masked = _mxu(mine, xd_b[:, cols], _NT)
        dx_ref[:, cols] += _mxu(masked.astype(bf), mine, _TN)
        d_scores += d_masked * decay
        d_log = d_masked * masked           # of a_t - a_s
        dcols_ref[:, R + r:R + r + 1] = jnp.sum(d_log, axis=1, keepdims=True)
        da_rows_ref[r:r + 1, :] = -jnp.sum(d_log, axis=0, keepdims=True)
    d_scores = d_scores.astype(bf)
    dc_ref[...] = _mxu(d_scores, B) + _mxu(dy_decayed, h_b, _NT)
    db_ref[...] = (_mxu(d_scores, C, _TN)
                   + _mxu((xd * to_end).astype(bf), dh_b, _NT))
    d_xd = dx_ref[...]
    dx_ref[...] = d_xd * delta + dy * d_ref[...]
    d_delta = d_xd * x
    # of exp(a) at the chunk's last position, through the state
    d_whole = jnp.sum(dh * h, axis=0, keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    for r in range(R):
        dcols_ref[:, r:r + 1] = _ssm_head_sum(d_delta, r, P)
        dcols_ref[:, 2 * R + r:2 * R + r + 1] = (
            _ssm_head_sum(d_exp_a, r, P)
            + jnp.where(last, _ssm_head_sum(d_whole, r, P), 0.0))
        dcols_ref[:, 3 * R + r:3 * R + r + 1] = _ssm_head_sum(d_to_end, r, P)
    dh_ref[...] = dh * exp_a[Q - 1:Q] + _mxu(C, dy_decayed, _TN)


def _ssm_specs(Q, G, R, P, N, nc, reverse):
    """Block specs on the grid (row, group, chunk), by what they fetch:
    x's, B's and C's columns of ``xbc`` (b, L, [x | B | C]) (``x`` also
    y's, dy's and dx's of a (b, L, G R P) array), ``cols``, ``a_rows``,
    D, and the states (b, G, chunks, N, R P); ``reverse`` walks the
    chunks last to first."""
    chunk = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    W = R * P
    first_b = G * W // N        # B's first block of N columns, then C's

    def columns(width, first):
        return pl.BlockSpec((None, Q, width),
                            lambda i, g, c: (i, chunk(c), first + g))

    return dict(
        x=columns(W, 0), b=columns(N, first_b), c=columns(N, first_b + G),
        cols=pl.BlockSpec((None, None, Q, 4 * R),
                          lambda i, g, c: (i, g, chunk(c), 0)),
        a_rows=pl.BlockSpec((None, None, R, Q),
                            lambda i, g, c: (i, g, 0, chunk(c))),
        d=pl.BlockSpec((1, W), lambda i, g, c: (0, g)),
        states=pl.BlockSpec((None, None, None, N, W),
                            lambda i, g, c: (i, g, chunk(c), 0, 0)))


_SSM_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_SSM_VMEM_BYTES))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _ssm_fwd(xbc, cols, a_rows, D, N, Q, states, interpret):
    """y (b, L, G R P), or with ``states`` the state entering each chunk
    (b, G, L // Q, N, R P); float32."""
    b, L, _ = xbc.shape
    G, R = a_rows.shape[1:3]
    W, nc = D.shape[1] // G, L // Q
    at = _ssm_specs(Q, G, R, W // R, N, nc, False)
    if states:
        operands = dict(x=xbc, b=xbc, cols=cols)
        out, out_shape = "states", (b, G, nc, N, W)
    else:
        operands = dict(x=xbc, b=xbc, c=xbc, cols=cols, a_rows=a_rows, d=D)
        out, out_shape = "x", (b, L, G * W)
    return pl.pallas_call(
        functools.partial(_ssm_fwd_kernel, P=W // R, states=states),
        grid=(b, G, nc), in_specs=[at[k] for k in operands],
        out_specs=at[out],
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[_scratch((N, W), jnp.float32)],
        interpret=interpret, **_SSM_PARAMS)(*operands.values())


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _ssm_bwd(xbc, cols, a_rows, D, entering, dy, N, Q, interpret):
    """The gradients of ``_ssm_fwd``'s y in ``xbc``, ``cols``,
    ``a_rows`` and D (a row of the batch each: (b, 1, G R P))."""
    b, L, _ = xbc.shape
    G, R = a_rows.shape[1:3]
    W, nc = D.shape[1] // G, L // Q
    at = _ssm_specs(Q, G, R, W // R, N, nc, True)
    f32 = jnp.float32
    # dx is written into an array of xbc's shape, where x's columns lie;
    # dB and dC are arrays of their own, (b, L, G N), and take their
    # places in it afterwards (in place: a third of a concatenate's bytes)
    narrow = pl.BlockSpec((None, Q, N), at["x"].index_map)
    d_xbc, dB, dC, *rest = pl.pallas_call(
        functools.partial(_ssm_bwd_kernel, P=W // R),
        grid=(b, G, nc),
        in_specs=[at[k] for k in ("x", "b", "c", "cols", "a_rows", "d",
                                  "states", "x")],
        out_specs=[at["x"], narrow, narrow, at["cols"], at["a_rows"],
                   pl.BlockSpec((None, 1, W), lambda i, g, c: (i, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(shape, f32) for shape in (
            xbc.shape, (b, L, G * N), (b, L, G * N), cols.shape,
            a_rows.shape, (b, 1, G * W))],
        scratch_shapes=[_scratch((N, W), f32)],
        interpret=interpret, **_SSM_PARAMS)(
            xbc, xbc, xbc, cols, a_rows, D, entering, dy)
    d_xbc = jax.lax.dynamic_update_slice(d_xbc, dB, (0, 0, G * W))
    d_xbc = jax.lax.dynamic_update_slice(d_xbc, dC, (0, 0, G * W + G * N))
    return (d_xbc, *rest)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ssm_chunks(xbc, cols, a_rows, D, N, Q, interpret):
    return _ssm_chunks_fwd(xbc, cols, a_rows, D, N, Q, interpret)[0]


def _ssm_chunks_fwd(xbc, cols, a_rows, D, N, Q, interpret):
    operands = (xbc, cols, a_rows, D)
    # mxlint: disable=recompile-churn (two sizes and bools)
    return _ssm_fwd(*operands, N, Q, False, interpret), operands


def _ssm_chunks_bwd(N, Q, interpret, operands, dy):
    # the backward's operations carry the scope's name too (a transposed
    # custom_vjp opens none): train.ssm_device_ms reads them by it
    with jax.named_scope("mx.ssm.scan"):
        # the states entering the chunks live inside this layer's
        # backward only: a sweep of the state alone writes them
        # mxlint: disable=recompile-churn (two sizes and bools)
        entering = _ssm_fwd(*operands, N, Q, True, interpret)
        # mxlint: disable=recompile-churn (two sizes and a bool)
        *grads, dD = _ssm_bwd(*operands, entering, dy.astype(jnp.float32),
                              N, Q, interpret)
        return (*grads, jnp.sum(dD, axis=0))


_ssm_chunks.defvjp(_ssm_chunks_fwd, _ssm_chunks_bwd)


def ssm_scan_chunks(xbc, delta, A, D, N, Q, interpret=None):
    """The chunked selective scan as Pallas kernels, for shapes that
    ``ssm_scan_tiles`` takes.  ``xbc`` (b, L, G R P + 2 G N) is
    [x | B | C] side by side as a Mamba-2 mixer's convolution leaves
    them (the kernels read their columns where they lie; nothing is
    sliced out), delta (b, G, R, L) (positions last: the per-head
    vectors stay dense that way), A and D (G, R); float32, L a multiple
    of Q.  Returns ``y + D x`` (b, L, G R P) float32, what
    ``ops/ssm._chunked_scan`` returns plus the ``D x`` term; its
    gradients come from a second kernel that walks the chunks backward
    (ops/ssm.py has the mathematics)."""
    b, G, R, L = delta.shape
    P = (xbc.shape[2] - 2 * G * N) // (G * R)
    # a: the running sum of delta A inside the chunk, a position's log
    # decay since the chunk began
    a = jnp.cumsum((delta * A[..., None]).reshape(b, G, R, L // Q, Q),
                   axis=-1)
    vectors = (delta, a, jnp.exp(a), jnp.exp(a[..., -1:] - a))
    cols = jnp.concatenate([v.reshape(b, G, R, L) for v in vectors], axis=2)
    return _ssm_chunks(xbc, cols.transpose(0, 1, 3, 2),
                       a.reshape(b, G, R, L),
                       jnp.repeat(D.reshape(1, G * R), P, axis=1), N, Q,
                       _interpret(interpret))


# ---------------------------------------------------------------------------
# The Mamba-2 mixer's two elementwise operators, a pass forward and a
# pass backward each (ops/ssm.py has the mathematics and the ``jnp``
# forms that every other shape runs)
# ---------------------------------------------------------------------------
# Both read float32 blocks of (rows, 128 k) columns where their
# neighbours left them: the convolution its columns of the
# in-projection's one (L, [z | x B C | dt]) result and the group norm
# its gate's (``lo`` is the first column, a whole number of blocks in),
# so nothing is sliced out in HBM.  The convolution needs the K - 1
# positions before a block: walking a row's blocks first to last the
# forward kernel keeps the last eight rows in VMEM; the backward kernel
# walks them last to first with the first rows of the later block's
# pre-activation gradient in VMEM (the transpose reads K - 1 positions
# AFTER a block) and fetches the eight rows before its block through a
# second block spec.  The taps', bias's and gain's gradients are summed
# in an output block that stays in VMEM along the walk.

_SSM_SLAB = 8           # rows before a block: a float32 tile's sublanes
_SSM_PASS_BYTES = 2 ** 20               # a block of a pass, at most


def _pass_rows(L, width):
    """Rows a block: the largest power of two up to 512 that divides L
    and keeps a (rows, width) float32 block inside a MiB; 0 where that
    is under a slab's eight."""
    rows = 512
    while rows >= _SSM_SLAB and (L % rows
                                 or 4 * rows * width > _SSM_PASS_BYTES):
        rows //= 2
    return rows if rows >= _SSM_SLAB else 0


def _conv_cols(C, lo):
    for cols in (512, 256, 128):
        if C % cols == 0 and lo % cols == 0:
            return cols
    return 0


def ssm_conv_tiles(L, C, K, lo=0):
    """Whether ``ssm_conv`` over L positions of C channels with K taps,
    its channels ``lo`` columns into the array it reads, takes the
    Pallas passes: channels and ``lo`` whole 128-lane blocks, L whole
    blocks of eight rows or more, and the taps inside one slab."""
    cols = _conv_cols(C, lo)
    return bool(cols and _pass_rows(L, cols) and 1 <= K <= _SSM_SLAB + 1)


def ssm_norm_tiles(L, C, groups, lo=0):
    """Whether ``ssm_gate_norm`` over L positions of C channels in
    ``groups`` groups, the gate ``lo`` columns into its array, takes the
    Pallas passes: a group a whole number of 128-lane blocks, ``lo`` a
    whole number of groups, L whole blocks of eight rows or more."""
    n = C // groups if groups and C % groups == 0 else 0
    return bool(n and n % _SSM_LANES == 0 and lo % n == 0
                and _pass_rows(L, n))


def _taps(ext_ref, w_ref, rows, ahead=False):
    """``sum_j w[j] * shifted_j``: tap j reads K - 1 - j rows back in
    ``ext_ref`` (a slab, then the block), or with ``ahead`` that many
    rows on (the block, then a slab): the transpose."""
    K = w_ref.shape[0]
    first = 0 if ahead else _SSM_SLAB - (K - 1)
    return sum(w_ref[j:j + 1, :]
               * ext_ref[pl.ds(first + (K - 1 - j if ahead else j), rows), :]
               for j in range(K))


def _ssm_conv_fwd_kernel(x_ref, w_ref, b_ref, o_ref, ext_ref):
    rows = x_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ext_ref[0:_SSM_SLAB, :] = jnp.zeros((_SSM_SLAB, ext_ref.shape[1]),
                                            jnp.float32)

    ext_ref[_SSM_SLAB:, :] = x_ref[...]
    pre = b_ref[...] + _taps(ext_ref, w_ref, rows)
    o_ref[...] = pre * jax.nn.sigmoid(pre)
    ext_ref[0:_SSM_SLAB, :] = ext_ref[rows:, :]


def _ssm_conv_bwd_kernel(x_ref, before_ref, g_ref, w_ref, b_ref, dx_ref,
                         sums_ref, ext_ref, dext_ref):
    rows, K = x_ref.shape[0], w_ref.shape[0]
    step, steps = pl.program_id(2), pl.num_programs(2)
    zeros = jnp.zeros((_SSM_SLAB, ext_ref.shape[1]), jnp.float32)

    @pl.when(step == 0)                 # the row's last block
    def _():
        dext_ref[rows:, :] = zeros
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)

    # the row's first block has nothing before it
    ext_ref[0:_SSM_SLAB, :] = jnp.where(step == steps - 1, zeros,
                                        before_ref[...])
    ext_ref[_SSM_SLAB:, :] = x_ref[...]
    pre = b_ref[...] + _taps(ext_ref, w_ref, rows)
    s = jax.nn.sigmoid(pre)
    dpre = g_ref[...] * (s * (1.0 + pre * (1.0 - s)))
    dext_ref[0:rows, :] = dpre
    dx_ref[...] = _taps(dext_ref, w_ref, rows, ahead=True)
    dext_ref[rows:, :] = dext_ref[0:_SSM_SLAB, :]
    for j in range(K):
        sums_ref[j:j + 1, :] += jnp.sum(
            dpre * ext_ref[pl.ds(_SSM_SLAB - (K - 1) + j, rows), :],
            axis=0, keepdims=True)
    sums_ref[K:K + 1, :] += jnp.sum(dpre, axis=0, keepdims=True)


_SSM_PASS_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_SSM_VMEM_BYTES))


@functools.partial(jax.jit, static_argnums=(3, 4))
def ssm_conv_pass(src, taps, bias, lo, interpret):
    """``silu(bias + causal depthwise conv)`` of columns ``lo`` on of
    ``src`` (b, L, .): ``taps`` (K, C), ``bias`` (1, C), float32;
    (b, L, C)."""
    b, L, _ = src.shape
    K, C = taps.shape
    cols = _conv_cols(C, lo)
    rows = _pass_rows(L, cols)
    block = pl.BlockSpec((None, rows, cols),
                         lambda i, j, c: (i, c, lo // cols + j))
    return pl.pallas_call(
        _ssm_conv_fwd_kernel, grid=(b, C // cols, L // rows),
        in_specs=[block, pl.BlockSpec((K, cols), lambda i, j, c: (0, j)),
                  pl.BlockSpec((1, cols), lambda i, j, c: (0, j))],
        out_specs=pl.BlockSpec((None, rows, cols),
                               lambda i, j, c: (i, c, j)),
        out_shape=jax.ShapeDtypeStruct((b, L, C), jnp.float32),
        scratch_shapes=[_scratch((_SSM_SLAB + rows, cols), jnp.float32)],
        interpret=interpret, **_SSM_PASS_PARAMS)(src, taps, bias)


@functools.partial(jax.jit, static_argnums=(4, 5))
def ssm_conv_pass_grads(src, taps, bias, g, lo, interpret):
    """``ssm_conv_pass``'s gradients from the cotangent ``g`` (b, L, C):
    in its C columns of ``src`` (b, L, C), in the taps (K, C) and in the
    bias (C,)."""
    b, L, _ = src.shape
    K, C = taps.shape
    cols = _conv_cols(C, lo)
    rows = _pass_rows(L, cols)
    nc, slabs = L // rows, rows // _SSM_SLAB

    def back(c):                        # a row's blocks last to first
        return nc - 1 - c

    wide = pl.BlockSpec((None, rows, cols),
                        lambda i, j, c: (i, back(c), lo // cols + j))
    before = pl.BlockSpec(
        (None, _SSM_SLAB, cols),
        lambda i, j, c: (i, jnp.maximum(back(c) * slabs - 1, 0),
                         lo // cols + j))
    own = pl.BlockSpec((None, rows, cols), lambda i, j, c: (i, back(c), j))
    dx, sums = pl.pallas_call(
        _ssm_conv_bwd_kernel, grid=(b, C // cols, nc),
        in_specs=[wide, before, own,
                  pl.BlockSpec((K, cols), lambda i, j, c: (0, j)),
                  pl.BlockSpec((1, cols), lambda i, j, c: (0, j))],
        out_specs=[own, pl.BlockSpec((None, K + 1, cols),
                                     lambda i, j, c: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, L, C), jnp.float32),
                   jax.ShapeDtypeStruct((b, K + 1, C), jnp.float32)],
        scratch_shapes=[_scratch((_SSM_SLAB + rows, cols), jnp.float32)] * 2,
        interpret=interpret, **_SSM_PASS_PARAMS)(src, src, g, taps, bias)
    sums = jnp.sum(sums, axis=0)
    return dx, sums[:K], sums[K]


def _gate_value(z, s, gate):
    """The gate at z, whose sigmoid is s: ``silu`` or ``sigmoid``."""
    return z * s if gate == "silu" else s


def _gate_slope(z, s, gate):
    """The gate's derivative at z."""
    return s * (1.0 + z * (1.0 - s)) if gate == "silu" else s * (1.0 - s)


def _gated_normed(y_ref, z_ref, eps, before, gate):
    """v = y act(z), the reciprocal root of the mean square a row of v
    (of y where the norm comes ``before`` the gate), and what the gate's
    gradient needs: the block's group is its columns."""
    y, z = y_ref[...], z_ref[...]
    s = jax.nn.sigmoid(z)
    v = y * z * s if gate == "silu" else y * s
    normed = y if before else v
    r = jax.lax.rsqrt(jnp.mean(normed * normed, axis=-1, keepdims=True)
                      + eps)
    return y, z, s, v, r


def _ssm_norm_fwd_kernel(y_ref, z_ref, gain_ref, o_ref, *, eps, before,
                         gate):
    *_, v, r = _gated_normed(y_ref, z_ref, eps, before, gate)
    o_ref[...] = v * r * gain_ref[...]


def _ssm_norm_bwd_kernel(y_ref, z_ref, gain_ref, g_ref, dy_ref, dz_ref,
                         dgain_ref, *, eps, before, gate):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dgain_ref[...] = jnp.zeros(dgain_ref.shape, jnp.float32)

    y, z, s, v, r = _gated_normed(y_ref, z_ref, eps, before, gate)
    g = g_ref[...]
    scaled = g * gain_ref[...]
    if before:
        a = scaled * _gate_value(z, s, gate)
        back = jnp.mean(a * y, axis=-1, keepdims=True) * (r * r * r)
        dy_ref[...] = a * r - y * back
        dz_ref[...] = scaled * y * r * _gate_slope(z, s, gate)
    else:
        back = jnp.mean(scaled * v, axis=-1, keepdims=True) * (r * r * r)
        dv = scaled * r - v * back
        dy_ref[...] = dv * _gate_value(z, s, gate)
        dz_ref[...] = dv * y * _gate_slope(z, s, gate)
    dgain_ref[...] += jnp.sum(g * v * r, axis=0, keepdims=True)


def _norm_specs(L, C, groups, lo):
    n = C // groups
    rows = _pass_rows(L, n)
    return dict(
        rows=rows,
        own=pl.BlockSpec((None, rows, n), lambda i, j, c: (i, c, j)),
        gate=pl.BlockSpec((None, rows, n),
                          lambda i, j, c: (i, c, lo // n + j)),
        gain=pl.BlockSpec((1, n), lambda i, j, c: (0, j)))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def ssm_norm_pass(y, gate_src, gain, groups, eps, lo, interpret,
                  before=False, gate="silu"):
    """``y * silu(gate)`` RMS-normed over each of ``groups`` groups of
    its C channels, times ``gain`` (1, C); ``before``: y normed, then
    times the gain and ``silu(gate)``; ``gate`` "sigmoid": ``sigmoid``
    in the place of ``silu``.  The gate is columns ``lo`` on of
    ``gate_src`` (b, L, .); float32; (b, L, C)."""
    b, L, C = y.shape
    at = _norm_specs(L, C, groups, lo)
    return pl.pallas_call(
        functools.partial(_ssm_norm_fwd_kernel, eps=eps, before=before,
                          gate=gate),
        grid=(b, groups, L // at["rows"]),
        in_specs=[at["own"], at["gate"], at["gain"]], out_specs=at["own"],
        out_shape=jax.ShapeDtypeStruct((b, L, C), jnp.float32),
        interpret=interpret, **_SSM_PASS_PARAMS)(y, gate_src, gain)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def ssm_norm_pass_grads(y, gate_src, gain, g, groups, eps, lo, interpret,
                        before=False, gate="silu"):
    """``ssm_norm_pass``'s gradients from the cotangent ``g``: in y, in
    the gate's C columns (b, L, C) and in the gain (C,)."""
    b, L, C = y.shape
    at = _norm_specs(L, C, groups, lo)
    dy, dz, dgain = pl.pallas_call(
        functools.partial(_ssm_norm_bwd_kernel, eps=eps, before=before,
                          gate=gate),
        grid=(b, groups, L // at["rows"]),
        in_specs=[at["own"], at["gate"], at["gain"], at["own"]],
        out_specs=[at["own"], at["own"],
                   pl.BlockSpec((None, 1, C // groups),
                                lambda i, j, c: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, L, C), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((b, 1, C), jnp.float32)],
        interpret=interpret, **_SSM_PASS_PARAMS)(y, gate_src, gain, g)
    return dy, dz, jnp.sum(dgain, axis=(0, 1))


# ---------------------------------------------------------------------------
# op-registry frontends (layout contract of the interleaved MHA ops:
# qkv (L, B, H*3*D) -> out (L, B, H*D); reference transformer.cc)
# ---------------------------------------------------------------------------
@register("_contrib_flash_selfatt", num_inputs=2,
          aliases=["flash_selfatt"])
def flash_selfatt(queries_keys_values, valid_length, *, heads: int = 1,
                  causal: bool = False, window: int = -1):
    """Flash-attention drop-in for the interleaved selfatt qk->softmax->
    valatt chain.  ``valid_length``: (B,) float/int valid KEY lengths.
    ``window > 0``: causal sliding-window attention of that width.
    """
    L, B, H3D = queries_keys_values.shape
    D = H3D // (heads * 3)
    x = queries_keys_values.reshape(L, B, heads, 3, D)
    # (L, B, H, D) -> (B*H, L, D)
    q, k, v = (x[:, :, :, i, :].transpose(1, 2, 0, 3)
               .reshape(B * heads, L, D) for i in range(3))
    lens = jnp.repeat(valid_length.astype(jnp.int32), heads)
    out = flash_attention(q, k, v, lengths=lens, causal=causal,
                          window=None if window <= 0 else window)
    return out.reshape(B, heads, L, D).transpose(2, 0, 1, 3).reshape(
        L, B, heads * D)


@register("_contrib_flash_attention", num_inputs=3,
          aliases=["flash_attention"])
def flash_attention_heads(q, k, v, *, causal: bool = False,
                          window: int = -1):
    """Flash attention over separate projections: q (B, L, H, D), k
    (B, L, Hkv, D) and v (B, L, Hkv, Dv) with Hkv dividing H (query head
    ``h`` reads key/value head ``h // (H // Hkv)``; Dv is D but in
    latent attention).  ``window > 0``: causal sliding window of that
    width.  Returns (B, L, H, Dv) in q's dtype.
    """
    B, L, H, D = q.shape
    Hkv, Dv = v.shape[2:]
    # the scope the device metrics find the kernels by, forward and
    # (as transpose(jvp(...))) backward
    with jax.named_scope("mx.attn.window" if window > 0
                         else "mx.attn.full"):
        out = flash_attention(
            q.transpose(0, 2, 1, 3).reshape(B * H, L, D),
            k.transpose(0, 2, 1, 3).reshape(B * Hkv, k.shape[1], D),
            v.transpose(0, 2, 1, 3).reshape(B * Hkv, v.shape[1], Dv),
            causal=causal, window=None if window <= 0 else window)
        return out.reshape(B, H, L, Dv).transpose(0, 2, 1, 3)


@register("_contrib_flash_selfatt_nomask", num_inputs=1,
          aliases=["flash_selfatt_nomask"])
def flash_selfatt_nomask(queries_keys_values, *, heads: int = 1,
                         causal: bool = False, window: int = -1):
    """flash_selfatt without a padding mask (full key length)."""
    L, B, H3D = queries_keys_values.shape
    D = H3D // (heads * 3)
    x = queries_keys_values.reshape(L, B, heads, 3, D)
    q, k, v = (x[:, :, :, i, :].transpose(1, 2, 0, 3)
               .reshape(B * heads, L, D) for i in range(3))
    out = flash_attention(q, k, v, causal=causal,
                          window=None if window <= 0 else window)
    return out.reshape(B, heads, L, D).transpose(2, 0, 1, 3).reshape(
        L, B, heads * D)


# ---------------------------------------------------------------------------
# ragged paged attention (LLM decode: one query token per sequence, K/V
# read through per-sequence block tables out of a fixed-page pool —
# "Ragged Paged Attention" kernel design, PAPERS.md)
# ---------------------------------------------------------------------------
def _paged_fwd_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, sm_scale, page_size,
                      n_pages):
    """One (sequence, page) grid step of decode attention, all heads.

    The page axis is innermost and sequential, so the online-softmax
    statistics (m/l/acc scratch, one row per head) carry across the
    pages of one sequence exactly like the flash kernel's k axis.  Which
    physical page backs grid step (b, p) is decided by the BlockSpec
    index map reading the scalar-prefetched block table — the kernel
    body never sees a page id, only its (page_size, H, D) tile.

    One query row per head leaves the MXU nothing to do, so the scores
    are a VPU multiply + lane reduce in float32 with the head axis kept
    on sublanes throughout (``keepdims``): no relayout and no per-head
    slicing.
    """
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx = len_ref[b]
    start = p * page_size

    # skip pages entirely past the sequence's context (and everything
    # for an inactive slot, ctx == 0: output falls out as zeros)
    @pl.when(start < ctx)
    def _step():
        q = q_ref[0].astype(jnp.float32)        # (H, D)
        k = k_ref[0].astype(jnp.float32)        # (page_size, H, D)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.sum(q[None] * k, axis=-1,
                    keepdims=True) * sm_scale   # (ps, H, 1)
        idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(idx < ctx, s, _NEG_INF)
        m_prev = m_scr[:]                       # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        corr = jnp.exp(m_prev - m_new)
        p_ = jnp.exp(s - m_new[None])           # (ps, H, 1)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p_, axis=0)
        acc_scr[:] = acc_scr[:] * corr + jnp.sum(p_ * v, axis=0)
        m_scr[:] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, block_tables,
                           context_lens, sm_scale=None, interpret=None):
    """Decode attention over a paged KV cache (Pallas TPU kernel).

    - ``q``: (B, H, D) — ONE query token per sequence slot (the ragged
      decode batch; inactive slots carry ``context_lens == 0``).
    - ``k_pages`` / ``v_pages``: (num_pages, page_size, H, D) — the
      preallocated device pool (``serving.kv_cache``).
    - ``block_tables``: (B, pages_per_seq) int32 — physical page of each
      logical page of each sequence; entries past the sequence's length
      must point at a valid (e.g. the null) page.
    - ``context_lens``: (B,) int32 — tokens of valid context per slot,
      INCLUDING the token whose K/V was just written; 0 = inactive slot
      (output row is zeros).

    The grid is (B, pages_per_seq) with pages innermost-sequential;
    every block takes ALL heads of one sequence or one page, so its last
    two dimensions are the arrays' own (H, D) — the shape rule Mosaic
    holds TPU blocks to.  The block table rides scalar prefetch so the
    page indirection is an index-map lookup, not in-kernel pointer
    math.  Returns (B, H, D) in the query dtype.  Pure-jax twin:
    :func:`ragged_paged_attention_reference` (CPU serving path + test
    oracle).
    """
    B, H, D = q.shape
    n_pool, page_size, HK, DK = k_pages.shape
    if (HK, DK) != (H, D) or v_pages.shape != k_pages.shape:
        from ..base import MXNetError
        raise MXNetError(
            f"ragged_paged_attention: q (B,H,D)={q.shape} inconsistent "
            f"with k_pages {k_pages.shape} / v_pages {v_pages.shape} "
            f"(want (num_pages, page_size, {H}, {D}))")
    n_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_tables = block_tables.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)

    q_spec = pl.BlockSpec((1, H, D), lambda b, p, bt, ln: (b, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, page_size, H, D),
        lambda b, p, bt, ln: (bt[b, p], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[_scratch((H, 1), jnp.float32),
                        _scratch((H, 1), jnp.float32),
                        _scratch((H, D), jnp.float32)],
    )
    kernel = functools.partial(_paged_fwd_kernel,
                               sm_scale=float(sm_scale),
                               page_size=page_size, n_pages=n_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=bool(interpret),
    )(block_tables, context_lens, q, k_pages, v_pages)


def ragged_paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     context_lens, sm_scale=None):
    """Pure-jax twin of :func:`ragged_paged_attention` — same signature
    and semantics (inactive ``context_lens == 0`` slots yield zeros),
    used as the CPU serving path and the kernel-parity oracle.  Gathers
    each sequence's pages into a contiguous (pages*page_size) context
    and runs masked softmax attention."""
    B, H, D = q.shape
    page_size = k_pages.shape[1]
    n_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    block_tables = block_tables.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    # (B, n_pages, page_size, H, D) -> (B, T, H, D), T = n_pages * ps
    k = k_pages[block_tables].reshape(B, n_pages * page_size, H, D)
    v = v_pages[block_tables].reshape(B, n_pages * page_size, H, D)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    valid = (jnp.arange(n_pages * page_size)[None, :]
             < context_lens[:, None])                       # (B, T)
    s = jnp.where(valid[:, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m) * valid[:, None, :]
    l = jnp.sum(e, axis=-1, keepdims=True)                  # (B, H, 1)
    out = jnp.einsum("bht,bthd->bhd", e, v.astype(jnp.float32))
    return (out / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


# ---------------------------------------------------------------------------
# ragged paged verify (multi-token window over a paged context: the
# speculative-decoding verification shape — k+1 query tokens per
# sequence, each attending causally over the full paged prefix — and
# the tail prefill of a prefix-cache hit; docs/serving.md §9)
# ---------------------------------------------------------------------------
def _paged_verify_kernel(bt_ref, start_ref, len_ref, q_ref, k_ref,
                         v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         sm_scale, page_size, n_pages, block_w, heads):
    """One (sequence, window tile, page) grid step of windowed verify
    attention, all heads.  Identical page-innermost online-softmax
    structure to :func:`_paged_fwd_kernel`, but the query block is a
    (block_w, D) tile per head (an MXU matmul against the head's
    (page_size, D) slice of the page, a static loop over heads) and the
    causal mask is per ROW: window row ``w`` (global position
    ``start + w``) sees key ``j`` iff ``j <= start + w``.  Page 0 always
    holds valid keys for every valid row (all rows attend from position
    0), so a valid row's softmax statistics are finite from its first
    processed block; rows past ``length`` accumulate garbage the wrapper
    zeroes."""
    b = pl.program_id(0)
    w_start = pl.program_id(1) * block_w
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = start_ref[b]
    n_valid = len_ref[b]
    page_start = p * page_size
    # rows of this tile that are valid end at tile_end (exclusive)
    tile_end = jnp.minimum(n_valid, w_start + block_w)

    # skip tiles past the valid rows (an inactive slot, n_valid == 0,
    # skips all) and pages past the tile's last row's causal horizon
    @pl.when(jnp.logical_and(w_start < n_valid,
                             page_start < start + tile_end))
    def _step():
        idx = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_w, page_size), 1)
        row = w_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_w, page_size), 0)
        mask = jnp.logical_and(idx <= start + row, row < n_valid)
        for h in range(heads):
            q = q_ref[0, h]                     # (block_w, D)
            k = k_ref[0, :, h, :]               # (page_size, D)
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(mask, s, _NEG_INF)    # (block_w, ps)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p_ = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p_, axis=1,
                                                 keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p_.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


# window rows per verify grid step: at (H=12, D=64) float32 a 256-row
# tile keeps q + out (double-buffered) + the accumulator near 4 MB of
# VMEM; an untiled 512-row window overflows the 16 MB scoped limit
_VERIFY_BLOCK_W = 256


def ragged_paged_verify(q, k_pages, v_pages, block_tables, starts,
                        lengths, sm_scale=None, interpret=None):
    """Multi-token verify attention over a paged KV cache (Pallas TPU
    kernel).

    - ``q``: (B, W, H, D) — a W-token window per sequence slot (the
      speculative k+1 verification window, or a prefix-cache tail).
    - ``k_pages`` / ``v_pages``: (num_pages, page_size, H, D) pool.
    - ``block_tables``: (B, pages_per_seq) int32 — as in
      :func:`ragged_paged_attention`.
    - ``starts``: (B,) int32 — global position of each slot's window
      row 0; K/V of positions below it are read from the cache pages,
      and the window's own K/V must already be written THROUGH the same
      block table (the verify forward writes before it attends).
    - ``lengths``: (B,) int32 — valid rows per window (0 = inactive
      slot).  Rows past ``lengths`` come back as zeros.

    Window row ``w`` attends causally over positions
    ``0 .. starts[b] + w`` — exactly prefill semantics when
    ``starts == 0`` and decode semantics when ``W == 1``.  The grid is
    (B, window tiles, pages_per_seq); the window is tiled head-major
    (the wrapper transposes q and the output, both small next to the
    pool) so each head's query tile and each page block end in the
    arrays' own last two dimensions.  Returns (B, W, H, D) in the query
    dtype; pure-jax twin: :func:`ragged_paged_verify_reference`.
    """
    B, W, H, D = q.shape
    n_pool, page_size, HK, DK = k_pages.shape
    if (HK, DK) != (H, D) or v_pages.shape != k_pages.shape:
        from ..base import MXNetError
        raise MXNetError(
            f"ragged_paged_verify: q (B,W,H,D)={q.shape} inconsistent "
            f"with k_pages {k_pages.shape} / v_pages {v_pages.shape} "
            f"(want (num_pages, page_size, {H}, {D}))")
    n_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    block_w = min(W, _VERIFY_BLOCK_W)
    W_p = _ceil_to(W, block_w)
    qt = q.transpose(0, 2, 1, 3)                            # (B, H, W, D)
    if W_p != W:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, W_p - W), (0, 0)))
    q_spec = pl.BlockSpec((1, H, block_w, D),
                          lambda b, w, p, bt, st, ln: (b, 0, w, 0))
    kv_spec = pl.BlockSpec(
        (1, page_size, H, D),
        lambda b, w, p, bt, st, ln: (bt[b, p], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, W_p // block_w, n_pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[_scratch((H, block_w, 1), jnp.float32),
                        _scratch((H, block_w, 1), jnp.float32),
                        _scratch((H, block_w, D), jnp.float32)],
    )
    kernel = functools.partial(_paged_verify_kernel,
                               sm_scale=float(sm_scale),
                               page_size=page_size, n_pages=n_pages,
                               block_w=block_w, heads=H)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, W_p, D), q.dtype),
        interpret=bool(interpret),
    )(block_tables, starts, lengths, qt, k_pages, v_pages)
    out = out[:, :, :W].transpose(0, 2, 1, 3)               # (B, W, H, D)
    # defined semantics for padded rows (they accumulate garbage in the
    # kernel — their every score is masked, so the online max never
    # leaves the -inf floor and exp(s - m) degenerates to 1)
    valid = jnp.arange(W)[None, :] < lengths[:, None]       # (B, W)
    return jnp.where(valid[:, :, None, None], out,
                     jnp.zeros((), out.dtype))


def ragged_paged_verify_reference(q, k_pages, v_pages, block_tables,
                                  starts, lengths, sm_scale=None):
    """Pure-jax twin of :func:`ragged_paged_verify` — same signature
    and semantics (rows past ``lengths`` yield zeros), used as the CPU
    serving path and the kernel-parity oracle."""
    B, W, H, D = q.shape
    page_size = k_pages.shape[1]
    n_pages = block_tables.shape[1]
    T = n_pages * page_size
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    block_tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    k = k_pages[block_tables].reshape(B, T, H, D)
    v = v_pages[block_tables].reshape(B, T, H, D)
    s = jnp.einsum("bwhd,bthd->bhwt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    row_pos = starts[:, None] + jnp.arange(W)[None, :]      # (B, W)
    mask = (jnp.arange(T)[None, None, :] <= row_pos[:, :, None]) \
        & (jnp.arange(W)[None, :, None] < lengths[:, None, None])
    s = jnp.where(mask[:, None], s, _NEG_INF)               # (B,H,W,T)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m) * mask[:, None]
    l = jnp.sum(e, axis=-1)                                 # (B, H, W)
    out = jnp.einsum("bhwt,bthd->bwhd", e, v.astype(jnp.float32))
    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)  # (B, W, H)
    return (out / denom[:, :, :, None]).astype(q.dtype)


@register("_contrib_ragged_paged_attention", num_inputs=5,
          differentiable=False, aliases=["ragged_paged_attention_op"])
def ragged_paged_attention_auto(q, k_pages, v_pages, block_tables,
                                context_lens):
    """Registry frontend for decode-time paged attention: the Pallas
    kernel on TPU backends, the pure-jax reference elsewhere (the same
    dispatch the serving decode engine uses).  Block tables and context
    lengths accept any numeric dtype (cast to int32)."""
    bt = block_tables.astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    if jax.default_backend() == "tpu":
        return ragged_paged_attention(q, k_pages, v_pages, bt, lens)
    return ragged_paged_attention_reference(q, k_pages, v_pages, bt, lens)
