"""Pallas TPU kernels for the chunked gated delta rule: one call
forward, and two backward, a sweep of the states that enter the chunks
followed by one walk from the last chunk to the first
(``ops/deltanet.py`` has the mathematics and the ``jnp`` form that every
other shape runs).

The grid is (row, key heads, chunks), the chunk axis walked in order (or
last to first) with the key heads' (R, dk, dv) float32 states in VMEM
from one step to the next.  A step takes two key heads of two chunks
where they divide (``_step_shape``): one chunk's products are a chain
that waits on the MXU's latency, and four independent chains
interleave.  It reads q, k and v as column blocks of x (b, L, [q | k |
v]), the array a Gated DeltaNet's convolution leaves (with ``normed``
q and k are its raw columns and are L2-normed here), and the per-head
vectors positions-last: ``cols`` (b, Hk, L, 2 R) holds, a position down
a column, [gamma | beta] with ``gamma`` the running sum of g inside the
chunk, and ``rows`` (b, Hk, L / Q, R, Q) holds gamma a position along
a row (a decay matrix needs both).  Every intra-chunk (Q, Q) matrix (K
K^T, Q K^T, the decays, A, its inverse T, their gradients) is made and
used in VMEM; only the sweep writes the chunks' T (and its transpose:
a product with a transposed left operand waits on the XLU) beside the
states, for the backward walk, inside the backward pass.

The inverse ``T = (I + A)^-1`` is float32-accurate (``_inverses``: a
product form in bfloat16, then two Newton steps whose residual is a
product at "highest"), and so is its gradient's product pair; every
other product rounds its operands to bfloat16 and accumulates in
float32, which is what XLA's default does to the ``jnp`` form's
einsums.  Decays, sums, norms and the state are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _scratch

__all__ = ["gdn_chunk_tiles", "gdn_chunks", "gdn_chunks_grads"]

L2_EPS = 1e-6                   # in q's and k's norms
_LANES = 128                    # dk and dv are multiples
_SUBLANES = 8                   # Q is a multiple
_VMEM_BYTES = 64 * 2 ** 20
_BLOCK_BYTES = 40 * 2 ** 20     # of it, blocks and temporaries


def gdn_chunk_tiles(Q, dk, dv, R, Hk):
    """Whether the rule in chunks of Q positions, Hk key heads of dk and
    R value heads of dv a key head, takes the Pallas kernels: Q a
    multiple of 8 and dk, dv multiples of 128 (the blocks tile), a key
    head's v columns a whole number of its blocks into x (b, L, [q | k |
    v]), and a grid step's blocks and temporaries inside the VMEM
    budget."""
    # the backward kernel at two key heads of two chunks a step: blocks
    # twice over (q, k, their gradients, v, dO, dv, the entering states
    # and T), the carried dS, some twenty (Q, Q) and ten (Q, dk | dv)
    # temporaries a value head and chunk
    units = 2 * 2 * R
    need = 4 * (2 * 4 * (4 * Q * dk + 3 * R * Q * dv + R * (dk * dv + Q * Q))
                + 2 * R * dk * dv + units * (20 * Q * Q + 10 * Q * max(dk, dv)))
    return not (Q % _SUBLANES or dk % _LANES or dv % _LANES
                or 2 * Hk * dk % (R * dv) or need > _BLOCK_BYTES)


# The products are written here and not imported: a Mosaic payload carries
# the source lines of what its kernel traces, and these kernels' should
# not move with another module's.
_NN = (((1,), (0,)), ((), ()))          # a @ b
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def _mxu(a, b, contract=_NN):
    return lax.dot_general(a, b, contract,
                           preferred_element_type=jnp.float32)


def _exact(a, b, contract=_NN):
    """A product of float32 operands at float32 accuracy."""
    return lax.dot_general(a, b, contract, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _default(a, b, contract=_NN):
    """A product at the backend's default: bfloat16 operands, float32
    accumulation."""
    bf = jnp.bfloat16
    return _mxu(a.astype(bf), b.astype(bf), contract)


def _masks(Q):
    t = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return t > j, t >= j, (t == j).astype(jnp.float32), t <= j


def _three_pass(a, b, contract=_NN):
    """A product of float32 operands as bfloat16 pairs ``hi + lo``:
    three products (``lo lo`` left out), 2^-16 of the operands'
    magnitudes."""
    bf, f32 = jnp.bfloat16, jnp.float32
    a_hi, b_hi = a.astype(bf), b.astype(bf)
    a_lo = (a - a_hi.astype(f32)).astype(bf)
    b_lo = (b - b_hi.astype(f32)).astype(bf)
    return (_mxu(a_hi, b_hi, contract)
            + (_mxu(a_hi, b_lo, contract) + _mxu(a_lo, b_hi, contract)))


def _inverses(As, eye):
    """``(I + A)^-1`` of strictly lower (Q, Q) matrices, to float32
    accuracy.  A first guess X from the product form ``(I + N)(I +
    N^2)(I + N^4)...`` (N = -A, up to N^(Q-1)) in bfloat16 products,
    then two Newton steps ``X + X (I - (I + A) X)``, the residual's
    product at float32 accuracy and the correction's, a product with a
    residual of 1e-3 or less, in three passes.  The matrices go in lock
    step, so that one's products wait on the MXU behind another's."""
    powers = [-A for A in As]
    invs = [eye + p for p in powers]
    span = 2
    while span < eye.shape[0]:
        powers = [_default(p, p) for p in powers]
        invs = [x + _default(x, p) for x, p in zip(invs, powers)]
        span *= 2
    for _ in range(2):
        residuals = [eye - x - _exact(A, x) for A, x in zip(As, invs)]
        invs = [x + _three_pass(x, e) for x, e in zip(invs, residuals)]
    return invs


class _Chunk:
    """What one chunk of one value head computes before its state: the
    decays and A = diag(beta) (K K^T * decay); then, given T = (I +
    A)^-1, the writes' two parts W = T diag(beta e^gamma) K and Un = T
    diag(beta) V."""

    def __init__(self, kk, k, v, gam, beta, gam_row, masks):
        below, self.seen, _, self.upper = masks
        Q = k.shape[0]
        self.k, self.v, self.beta = k, v, beta
        self.gam, self.gam_row = gam, gam_row
        self.diff = gam - gam_row                   # gamma_t - gamma_j
        self.e = jnp.exp(gam)
        last = gam[Q - 1:Q]                         # (1, 1)
        # e^gamma_Q as a row, to scale a state: Mosaic broadcasts a (1, 1)
        # along sublanes or along lanes, not both at once
        self.e_last = jnp.exp(jnp.broadcast_to(last, (1, v.shape[1])))
        self.f = jnp.exp(last - gam)                # to the chunk's end
        self.strict = jnp.exp(jnp.where(below, self.diff, -jnp.inf))
        self.M = kk * self.strict
        self.A = beta * self.M

    def solve(self, T, T_t=None):
        self.T, self.T_t = T, T_t
        self.kb = (self.beta * self.e) * self.k
        self.vb = self.beta * self.v
        self.W = _default(T, self.kb)
        self.Un = _default(T, self.vb)

    def causal(self, transposed=False):
        """The masked product's decay: t reads j <= t (``transposed``:
        indexed [j, t])."""
        if transposed:
            return jnp.exp(jnp.where(self.upper, self.gam_row - self.gam,
                                     -jnp.inf))
        return jnp.exp(jnp.where(self.seen, self.diff, -jnp.inf))

    def writes(self, S):
        """U = Un - W S."""
        return self.Un - _default(self.W, S)


class _Step:
    """A grid step's blocks: ``heads`` key heads of ``n`` chunks each,
    R value heads a key head.  Addresses a chunk's pieces by (key head
    h, chunk s, value head r).  With ``normed`` the blocks of q and k
    are the raw columns, and q and k are L2-normed a head here (q then
    over sqrt(dk))."""

    def __init__(self, q_ref, k_ref, v_ref, cols_ref, rows_ref, normed):
        self.heads, self.n, self.R, self.Q = rows_ref.shape
        self.dk = q_ref.shape[1] // self.heads
        self.dv = v_ref.shape[1] // (self.heads * self.R)
        self.refs = q_ref, k_ref, v_ref, cols_ref, rows_ref
        self.normed = normed
        self.masks = _masks(self.Q)

    def at(self, s):
        return slice(s * self.Q, (s + 1) * self.Q)

    def key_cols(self, h):
        return slice(h * self.dk, (h + 1) * self.dk)

    def value_cols(self, h, r):
        i = h * self.R + r
        return slice(i * self.dv, (i + 1) * self.dv)

    def units(self):
        return [(h, r) for h in range(self.heads) for r in range(self.R)]

    def raw(self, h, s):
        """q's and k's columns of (h, s) as read, and the reciprocal
        norm of each row where ``normed`` (else 1)."""
        q_ref, k_ref = self.refs[:2]
        at, cols = self.at(s), self.key_cols(h)
        q, k = q_ref[at, cols], k_ref[at, cols]
        if not self.normed:
            return (q, 1.0), (k, 1.0)
        return tuple((x, lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True)
                                   + L2_EPS)) for x in (q, k))

    def chunks(self, t_ref=None, tt_ref=None):
        """Every chunk's q, k (as the rule reads them) and K K^T by (h,
        s), and its _Chunk by (h, s, r); T and its transpose from
        ``t_ref`` and ``tt_ref`` where given, else T computed, every
        chunk and head in lock step."""
        v_ref, cols_ref, rows_ref = self.refs[2:]
        R = self.R
        keys, chunks, self.raws = {}, {}, {}
        for h in range(self.heads):
            for s in range(self.n):
                (q, rq), (k, rk) = self.raws[h, s] = self.raw(h, s)
                if self.normed:
                    q, k = q * (rq * self.dk ** -0.5), k * rk
                kk = _default(k, k, _NT)
                keys[h, s] = q, k, kk
                at = self.at(s)
                for r in range(R):
                    chunks[h, s, r] = _Chunk(
                        kk, k, v_ref[at, self.value_cols(h, r)],
                        cols_ref[h, at, r:r + 1],
                        cols_ref[h, at, R + r:R + r + 1],
                        rows_ref[h, s, r:r + 1, :], self.masks)
        order = list(chunks)
        if t_ref is None:
            for i, T in zip(order, _inverses([chunks[i].A for i in order],
                                             self.masks[2])):
                chunks[i].solve(T)
        else:
            for i in order:
                chunks[i].solve(t_ref[i], tt_ref[i])
        return keys, chunks

    def unnormed(self, h, s, dq, dk):
        """The gradients in q's and k's columns as read, from those in q
        and k as the rule read them: through ``y = c r x``, r the
        reciprocal norm, ``dx = c r (dy - x^ (x^ . dy))`` with x^ = r x."""
        if not self.normed:
            return dq, dk
        out = []
        for (x, r), dy, c in zip(self.raws[h, s], (dq, dk),
                                 (self.dk ** -0.5, 1.0)):
            unit = x * r
            out.append((c * r) * (dy - unit * jnp.sum(unit * dy, axis=1,
                                                      keepdims=True)))
        return out


def _fwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, *refs, states,
                normed):
    """The grid step's chunks of its key heads, each head's in order: o,
    or with ``states`` (the backward pass's sweep) the states that enter
    the chunks, the chunks' T and its transpose."""
    if states:
        out_ref, t_ref, tt_ref, s_ref = refs
    else:
        out_ref, s_ref = refs
    step = _Step(q_ref, k_ref, v_ref, cols_ref, rows_ref, normed)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    keys, chunks = step.chunks()
    for s in range(step.n):
        at = step.at(s)
        qk = {h: _default(*keys[h, s][:2], _NT)
              for h in range(step.heads) if not states}
        for h, r in step.units():
            q, k, _ = keys[h, s]
            c = chunks[h, s, r]
            S = s_ref[h, r]
            U = c.writes(S)
            if states:
                out_ref[h, s, r] = S
                t_ref[h, s, r] = c.T
                tt_ref[h, s, r] = c.T.T
            else:
                out_ref[at, step.value_cols(h, r)] = (
                    _default(c.e * q, S) + _default(qk[h] * c.causal(), U))
            s_ref[h, r] = c.e_last * S + _default(c.f * k, U, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, h_ref, t_ref,
                tt_ref, do_ref, dq_ref, dk_ref, dv_ref, dcols_ref, drows_ref,
                ds_ref, *, normed):
    """The same chunks transposed, each head's last to first.  ``h_ref``
    holds the states that entered them, ``t_ref`` their T and ``tt_ref``
    its transpose (the sweep's); ``ds_ref`` carries the gradient of the
    state that LEAVES the chunk.  A product with a transposed left
    operand waits on the XLU's transpose, so each such operand is made
    the other way round where it is made (P^T, dP^T, T^T)."""
    step = _Step(q_ref, k_ref, v_ref, cols_ref, rows_ref, normed)
    R, Q = step.R, step.Q
    below = step.masks[0]
    last = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def colsum(x):
        return jnp.sum(x, axis=0, keepdims=True)

    keys, chunks = step.chunks(t_ref, tt_ref)
    for s in reversed(range(step.n)):
        at = step.at(s)
        grads = {h: [jnp.zeros((Q, step.dk), f32) for _ in range(2)]
                 + [jnp.zeros((Q, Q), f32) for _ in range(3)]
                 for h in range(step.heads)}    # dq, dk, dQK, dQK^T, dKK
        parts = {}
        qk = {h: (_default(q, k, _NT), _default(k, q, _NT))   # Q K^T, K Q^T
              for h, (q, k, _) in ((h, keys[h, s]) for h in range(step.heads))}
        for h, r in step.units():
            q, k, _ = keys[h, s]
            c = chunks[h, s, r]
            g = grads[h]
            cols = step.value_cols(h, r)
            S, dS = h_ref[h, s, r], ds_ref[h, r]
            U = c.writes(S)
            causal, causal_t = c.causal(), c.causal(True)
            P = qk[h][0] * causal
            do = do_ref[at, cols]
            # o = diag(e) Q S + P U and S' = e_Q S + (diag(f) K)^T U
            dU = _default(qk[h][1] * causal_t, do) + _default(c.f * k, dS)
            dP = _default(do, U, _NT)
            x = dP * P
            d_gam, d_row = rowsum(x), -colsum(x)
            g[2] = g[2] + dP * causal
            g[3] = g[3] + _default(U, do, _NT) * causal_t
            d_eq = _default(do, S, _NT)             # of diag(e) Q
            g[0] = g[0] + c.e * d_eq
            d_gam = d_gam + c.e * rowsum(d_eq * q)
            d_kf = _default(U, dS, _NT)             # of diag(f) K
            g[1] = g[1] + c.f * d_kf
            d_f = c.f * rowsum(d_kf * k)
            d_last = (colsum(d_f)
                      + colsum(c.e_last * rowsum(dS * S))[:, :1])
            d_gam = d_gam - d_f + jnp.where(last, d_last, 0.0)
            ds_ref[h, r] = (c.e_last * dS + _default(c.e * q, do, _TN)
                            - _default(c.W, dU, _TN))
            # U = Un - W S, W = T Kb, Un = T Vb
            dW = -_default(dU, S, _NT)
            dT = _default(dW, c.kb, _NT) + _default(dU, c.vb, _NT)
            parts[h, r] = (dT, _default(c.T_t, dW), _default(c.T_t, dU),
                           d_gam, d_row)
        # T = (I + A)^-1: dA = -T^T dT T^T on the strictly lower part, the
        # chunks in lock step
        inner = {i: _exact(parts[i][0], chunks[i[0], s, i[1]].T_t)
                 for i in parts}
        for (h, r), (_, d_kb, d_vb, d_gam, d_row) in parts.items():
            k = keys[h, s][1]
            c = chunks[h, s, r]
            g = grads[h]
            dA = jnp.where(below, -_exact(c.T_t, inner[h, r]), 0.0)
            # A = diag(beta) (K K^T * decay)
            dM = c.beta * dA
            y = dM * c.M
            d_gam = d_gam + rowsum(y)
            g[4] = g[4] + dM * c.strict
            # Kb = diag(beta e) K and Vb = diag(beta) V
            z = rowsum(d_kb * k)
            g[1] = g[1] + (c.beta * c.e) * d_kb
            dv_ref[at, step.value_cols(h, r)] = c.beta * d_vb
            dcols_ref[h, at, r:r + 1] = d_gam + (c.beta * c.e) * z
            dcols_ref[h, at, R + r:R + r + 1] = (
                rowsum(dA * c.M) + c.e * z + rowsum(d_vb * c.v))
            drows_ref[h, s, r:r + 1, :] = d_row - colsum(y)
        for h in range(step.heads):
            q, k, _ = keys[h, s]
            dq, dk, d_qk, d_qk_t, d_kk = grads[h]
            dq, dk = step.unnormed(
                h, s, dq + _default(d_qk, k),
                dk + _default(d_qk_t, q) + _default(d_kk, k)
                + _default(d_kk, k, _TN))
            cols = step.key_cols(h)
            dq_ref[at, cols] = dq
            dk_ref[at, cols] = dk


def _specs(Q, n, heads, R, dk, dv, chunks, firsts, reverse):
    """Block specs on the grid (row, key heads, step), a step ``heads``
    key heads of ``n`` chunks each, by what they fetch: q's, k's and v's
    columns of x (b, L, [q | k | v]) (``firsts``: the first block of
    each), the q-wide and v-wide columns of arrays of their own (dq, dk;
    o, dO, dv), ``cols``, ``rows``, the states (b, Hk, L / Q, R, dk, dv)
    and the chunks' T (b, Hk, L / Q, R, Q, Q); ``reverse`` walks the
    steps last to first."""
    step = (lambda c: chunks // n - 1 - c) if reverse else (lambda c: c)

    def columns(width, first=0):
        return pl.BlockSpec((None, n * Q, width),
                            lambda i, g, c: (i, step(c), first + g))

    def vectors(*shape):
        return pl.BlockSpec((None, heads, n) + shape,
                            lambda i, g, c: (i, g, step(c)) + (0,) * len(shape))

    key, value = heads * dk, heads * R * dv
    return dict(
        q=columns(key, firsts[0]), k=columns(key, firsts[1]),
        v=columns(value, firsts[2]), key=columns(key), value=columns(value),
        cols=pl.BlockSpec((None, heads, n * Q, 2 * R),
                          lambda i, g, c: (i, g, step(c), 0)),
        rows=vectors(R, Q), states=vectors(R, dk, dv),
        inverses=vectors(R, Q, Q))


_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES))


def _step_shape(Hk, dk, dv, R, chunks):
    """(key heads, chunks) a grid step: two of each where they divide,
    and two key heads' v columns start a whole block into x.  One
    chunk's products are a chain that waits on the MXU's latency;
    independent ones interleave (at Qwen3-Next's layer, forward and
    backward on a v5e: 15.5 ms one by one, 11.8 two heads, 12.3 two
    chunks, 10.9 two by two or four heads, 48.7 for the ``jnp``
    form)."""
    heads = 2 if Hk % 2 == 0 and 2 * Hk * dk % (2 * R * dv) == 0 else 1
    return heads, 2 if chunks % 2 == 0 else 1


def _vectors(g, beta, Q):
    """``cols`` (b, Hk, L, 2 R) and ``rows`` (b, Hk, L / Q, R, Q) from
    g and beta (b, L, Hk, R)."""
    b, L, G, R = g.shape
    gam = jnp.cumsum(g.reshape(b, L // Q, Q, G, R), axis=2)
    cols = jnp.concatenate([gam.reshape(b, L, G, R), beta], axis=3)
    return cols.transpose(0, 2, 1, 3), gam.transpose(0, 3, 1, 4, 2)


def _grid(x, rows, Q, layout, reverse):
    Hk, dk, dv = layout
    b, G, nc, R = rows.shape[:4]
    heads, n = _step_shape(Hk, dk, dv, R, nc)
    firsts = (0, Hk // heads, 2 * Hk * dk // (heads * R * dv))
    return ((b, G // heads, nc // n),
            _specs(Q, n, heads, R, dk, dv, nc, firsts, reverse), heads)


def _fwd(x, cols, rows, Q, layout, normed, states, interpret):
    """o (b, L, H dv), or with ``states`` the state entering each chunk
    (b, Hk, L / Q, R, dk, dv), each chunk's T (b, Hk, L / Q, R, Q, Q)
    and T's transpose; float32."""
    grid, at, heads = _grid(x, rows, Q, layout, False)
    Hk, dk, dv = layout
    b, _, nc, R = rows.shape[:4]
    f32 = jnp.float32
    if states:
        out = [at["states"], at["inverses"], at["inverses"]]
        out_shape = [jax.ShapeDtypeStruct((b, Hk, nc, R, dk, dv), f32)] + [
            jax.ShapeDtypeStruct((b, Hk, nc, R, Q, Q), f32)] * 2
    else:
        out = at["value"]
        out_shape = jax.ShapeDtypeStruct((b, x.shape[1], Hk * R * dv), f32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, states=states, normed=normed),
        grid=grid,
        in_specs=[at[key] for key in ("q", "k", "v", "cols", "rows")],
        out_specs=out, out_shape=out_shape,
        scratch_shapes=[_scratch((heads, R, dk, dv), f32)],
        interpret=interpret, **_PARAMS)(x, x, x, cols, rows)


def _bwd(x, cols, rows, entering, inverses, transposed, do, Q, layout,
         normed, interpret):
    """The gradients of ``_fwd``'s o in q's, k's and v's columns of x
    (three arrays), ``cols`` and ``rows``."""
    grid, at, heads = _grid(x, rows, Q, layout, True)
    Hk, dk, dv = layout
    b, L = x.shape[:2]
    R = rows.shape[3]
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, normed=normed),
        grid=grid,
        in_specs=[at[key] for key in ("q", "k", "v", "cols", "rows",
                                      "states", "inverses", "inverses",
                                      "value")],
        out_specs=[at[key] for key in ("key", "key", "value", "cols",
                                       "rows")],
        out_shape=[jax.ShapeDtypeStruct(shape, f32) for shape in (
            (b, L, Hk * dk), (b, L, Hk * dk), (b, L, Hk * R * dv),
            cols.shape, rows.shape)],
        scratch_shapes=[_scratch((heads, R, dk, dv), f32)],
        interpret=interpret, **_PARAMS)(x, x, x, cols, rows, entering,
                                        inverses, transposed, do)


def gdn_chunks(x, g, beta, Q, layout, normed, interpret=None):
    """The chunked rule as the Pallas forward kernel, for shapes that
    ``gdn_chunk_tiles`` takes.  x (b, L, [q | k | v]) holds q, k (Hk dk
    columns each) and v (H dv) side by side, as a Gated DeltaNet's
    convolution leaves them (the kernels read their columns where they
    lie; with ``normed`` q and k are the raw columns, L2-normed a head
    in the kernels, q then over sqrt(dk)); ``layout`` (Hk, dk, dv);
    g, beta (b, L, Hk, R); float32, L a multiple of Q.  Returns o
    (b, L, Hk, R, dv): what ``ops/deltanet._chunked`` returns."""
    Hk, _, dv = layout
    b, L, _, R = g.shape
    cols, rows = _vectors(g, beta, Q)
    o = _fwd(x, cols, rows, Q, layout, normed, False,
             _interpret(interpret))
    return o.reshape(b, L, Hk, R, dv)


def gdn_chunks_grads(x, g, beta, do, Q, layout, normed, interpret=None):
    """The gradients of ``gdn_chunks``'s o in x, g and beta, given its
    cotangent ``do``: a sweep of the states that enter the chunks (and
    of the chunks' T), then one kernel that walks the chunks last to
    first."""
    b, L, G, R = g.shape
    interpret = _interpret(interpret)
    cols, rows = _vectors(g, beta, Q)
    # the states entering the chunks, and the chunks' T, live inside this
    # backward only
    entering, inverses, transposed = _fwd(x, cols, rows, Q, layout, normed,
                                          True, interpret)
    dq, dk, dv, dcols, drows = _bwd(x, cols, rows, entering, inverses,
                                    transposed, do.reshape(b, L, -1), Q,
                                    layout, normed, interpret)
    # gamma's gradient, down the columns and along the rows; g's is its
    # reverse running sum inside the chunk
    d_gam = (dcols[..., :R].reshape(b, G, L // Q, Q, R)
             + drows.transpose(0, 1, 2, 4, 3))
    dg = jnp.flip(jnp.cumsum(jnp.flip(d_gam, 3), axis=3), 3)
    dg = dg.reshape(b, G, L, R).transpose(0, 2, 1, 3)
    d_beta = dcols[..., R:].transpose(0, 2, 1, 3)
    return jnp.concatenate([dq, dk, dv], axis=2), dg, d_beta
