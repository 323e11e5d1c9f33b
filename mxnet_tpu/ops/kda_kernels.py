"""Pallas TPU kernels for the chunked delta rule with a decay a key
channel (Kimi Delta Attention): one call forward, and two backward, a
sweep of the states that enter the chunks followed by one walk from the
last chunk to the first (``ops/deltanet.py`` has the mathematics and
``_channel_span``, the ``jnp`` form that every other shape runs).

The grid is (row, heads, chunks), the chunk axis walked in order (or
last to first) with each head's state in VMEM from one step to the
next, held transposed, (dv, dk), so that a key channel's decay scales a
lane.  A step takes two heads of two chunks where they divide
(``_step_shape``): one chunk's products are a chain that waits on the
MXU's latency, and four independent chains interleave.  It reads q, k
and v as column blocks of x (b, L, [q | k | v]), the array the mixer's
convolution leaves (with ``normed`` q and k are its raw columns and are
L2-normed here), the raw log decays g as column blocks of (b, L, H dk),
and beta a position down a column.

Every chunk's decays are made in VMEM: the running sum Gamma (a product
with a triangle of ones, g split into three bfloat16 parts: all 24 bits
of float32's), the chunk cut into sub-chunks of ``sub`` positions, each
referred to the position before it, and ``_channel_span``'s factors,
every exponent at most 0.  The products between sub-chunks go to the
MXU; the diagonal blocks, inside a sub-chunk, are summed elementwise
over the key channels, one diagonal of the blocks at a time (k and Gamma
rolled down the sublanes by its offset; in sub-chunks of 16 a diagonal
past the eighth on the second eight rows alone, where its pairs lie),
with ``e^{Gamma_t - Gamma_j}`` exact.  Every intra-chunk (Q, Q) matrix
(the two decayed products, A, its inverse T, their gradients) is made
and used in VMEM; only the sweep writes the chunks' T (and its
transpose: a product with a transposed left operand waits on the XLU)
beside the states, for the backward walk, inside the backward pass.

Precision is the ``jnp`` form's: the inverse ``T = (I + A)^-1`` and its
gradient's product pair are float32-accurate (``deltanet_kernels.
_inverses``: a bfloat16 product form refined by two Newton steps), the
running sums, the diagonal blocks, decays and norms are float32, and
every other product rounds its operands to bfloat16 and accumulates in
float32, which is what XLA's default does to the ``jnp`` form's einsums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .deltanet_kernels import (_BLOCK_BYTES, _LANES, _NN, _NT, _SUBLANES,
                               _TN, _VMEM_BYTES, L2_EPS, _exact, _inverses,
                               _mxu)
from .pallas_kernels import _interpret, _scratch

__all__ = ["kda_chunk_tiles", "kda_chunks", "kda_chunks_grads"]


def kda_chunk_tiles(Q, sub, dk, dv, H):
    """Whether the per-channel rule in chunks of Q positions cut into
    sub-chunks of ``sub``, over H heads of dk keys and dv values (a key
    head a value head), takes the Pallas kernels: Q a multiple of 8 and
    of ``sub``, dk and dv multiples of 128 (the blocks tile), v's
    columns a whole number of blocks into x (b, L, [q | k | v]), and a
    grid step's blocks and temporaries inside the VMEM budget."""
    # the backward walk at two heads of two chunks a step: blocks twice
    # over (q, k, g, their gradients; v, dO, dv; the entering state, T
    # and its transpose), the carried dS, some thirty (Q, Q) and forty
    # (Q, dk | dv) temporaries a head and chunk
    units = 2 * 2
    need = 4 * (2 * units * (6 * Q * dk + 3 * Q * dv + dk * dv + 2 * Q * Q)
                + 2 * dk * dv
                + units * (30 * Q * Q + 40 * Q * max(dk, dv)))
    return not (Q % _SUBLANES or Q % sub or dk % _LANES or dv % _LANES
                or 2 * H * dk % dv or need > _BLOCK_BYTES)


# a product is written here and not imported: the tests take every
# product of these kernels to float32 accuracy through this name
def _default(a, b, contract=_NN):
    """A product at the backend's default: bfloat16 operands, float32
    accumulation."""
    bf = jnp.bfloat16
    return _mxu(a.astype(bf), b.astype(bf), contract)


def _running(ones, x):
    """``ones @ x`` for a 0/1 matrix, to float32 accuracy: x as three
    bfloat16 parts (which hold its 24 bits), the ones exact in
    bfloat16."""
    bf, f32 = jnp.bfloat16, jnp.float32
    ones = ones.astype(bf)
    total = None
    for _ in range(3):
        part = x.astype(bf)
        term = _mxu(ones, part)
        total = term if total is None else total + term
        x = x - part.astype(f32)
    return total


def _halves(x):
    """The rows of x (Q, n) in the first and in the second half of each
    16-row sub-chunk, each (Q / 2, n)."""
    Q, n = x.shape
    y = x.reshape(Q // 16, 2, 8, n)
    return y[:, 0].reshape(Q // 2, n), y[:, 1].reshape(Q // 2, n)


def _merged(lo, hi):
    """``_halves``' inverse."""
    Q, n = 2 * lo.shape[0], lo.shape[1]
    return jnp.stack([lo.reshape(Q // 16, 8, n), hi.reshape(Q // 16, 8, n)],
                     axis=1).reshape(Q, n)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _colsum(x):
    return jnp.sum(x, axis=0, keepdims=True)


class _Masks:
    """A chunk's index masks, made once a grid step: by row of a (Q, dk)
    array its sub-chunk (``part``) and place in it (``pos``); by (t, j)
    of a (Q, Q) one t's sub-chunk, the strictly lower part, the
    diagonals ``j = t - d`` inside a sub-chunk (``band``), and the
    triangles of ones of the running sums forward and back."""

    def __init__(self, Q, dk, sub):
        self.Q, self.sub, self.m = Q, sub, Q // sub
        row = lax.broadcasted_iota(jnp.int32, (Q, dk), 0)
        self.row = row
        self.part = sum(((row >= a * sub).astype(jnp.int32)
                         for a in range(1, self.m)), jnp.zeros_like(row))
        self.pos = row - sub * self.part
        t = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        self.t_part = sum(((t >= a * sub).astype(jnp.int32)
                           for a in range(1, self.m)), jnp.zeros_like(t))
        self.below = t > j
        self.band = [j == t - d for d in range(sub)]
        # in sub-chunks of 16 a diagonal d >= 8 has its pairs in the
        # second half's rows alone: those diagonals take those rows
        self.split = 8 if sub == 16 else sub
        if self.split < sub:
            self.pos_hi = _halves(self.pos)[1]
            t_hi, j_hi = _halves(t)[1], _halves(j)[1]
            self.band_hi = [j_hi == t_hi - d for d in range(8, sub)]
        self.eye = (t == j).astype(jnp.float32)
        self.lower = (t >= j).astype(jnp.float32)     # Gamma = lower @ g
        self.upper = (t <= j).astype(jnp.float32)     # dg = upper @ dGamma


class _Chunk:
    """What one chunk of one head computes before its state: Gamma, the
    running sum of its decays (Q, dk), and every decay's factors, ``e``
    = e^Gamma, ``f`` = e^{Gamma_Q - Gamma}, ``left`` = e^{Gamma -
    Gamma_r} (r the position before a row's sub-chunk) and, for each
    sub-chunk a after the first, ``rights[a - 1]`` = k e^{Gamma_r -
    Gamma} on the rows before it; M = ``sum_d k_td k_jd e^{Gamma_td -
    Gamma_jd}`` for j < t and A = diag(beta) M; with ``q`` the masked
    product P, the same with q_t for j <= t.  Then, given T = (I + A)^-1,
    the writes' two parts W = T diag(beta) (K * e) and Un = T diag(beta)
    V."""

    def __init__(self, q, k, v, g, beta, masks):
        mk = masks
        Q, sub = mk.Q, mk.sub
        self.q, self.k, self.v, self.beta, self.mk = q, k, v, beta, mk
        gam = _running(mk.lower, g)
        self.gam = gam
        self.e = jnp.exp(gam)
        last = gam[Q - 1:Q]                             # (1, dk)
        self.e_last = jnp.exp(last)
        self.f = jnp.exp(last - gam)
        refs = [gam[a * sub - 1:a * sub] for a in range(1, mk.m)]
        ref_rows = jnp.zeros_like(gam)
        for a, ref in enumerate(refs, 1):
            ref_rows = jnp.where(mk.part == a, ref, ref_rows)
        self.left = jnp.exp(gam - ref_rows)
        # e^{Gamma_r - Gamma_j} for j before sub-chunk a's first row
        self.decays = [jnp.exp(jnp.where(mk.row < a * sub, ref - gam,
                                         -jnp.inf))
                       for a, ref in enumerate(refs, 1)]
        self.rights = [k * d for d in self.decays]
        with_p = q is not None
        diagonal = self.diagonal([k, q] if with_p else [k])
        self.M = jnp.where(mk.below, self.off(k * self.left) + diagonal[0],
                           0.0)
        self.A = beta * self.M
        if with_p:
            self.P = self.off(q * self.left) + diagonal[1]

    def off(self, xl):
        """Sub-chunk a's rows of ``xl`` against every earlier sub-chunk's
        columns, on the MXU: (Q, Q)."""
        out = jnp.zeros((self.mk.Q, self.mk.Q), jnp.float32)
        for a, right in enumerate(self.rights, 1):
            out = out + _default(jnp.where(self.mk.part == a, xl, 0.0),
                                 right, _NT)
        return out

    def rolled(self, d):
        """k_{t-d} and e^{Gamma_t - Gamma_{t-d}} where t - d lies in t's
        sub-chunk (else 0), by row t."""
        k, gam = self.k, self.gam
        if d:
            k, prev = pltpu.roll(k, d, 0), pltpu.roll(gam, d, 0)
        else:
            prev = gam
        return k, jnp.exp(jnp.where(self.mk.pos >= d, gam - prev, -jnp.inf))

    def rolled_hi(self, d):
        """``rolled(d)`` for d >= 8 on the second halves' rows, whose
        t - d lie in the first halves."""
        mk = self.mk
        (k, _), (prev, gam) = _halves(self.k), _halves(self.gam)
        if d > 8:
            k, prev = pltpu.roll(k, d - 8, 0), pltpu.roll(prev, d - 8, 0)
        return k, jnp.exp(jnp.where(mk.pos_hi >= d, gam - prev, -jnp.inf))

    def diagonal(self, xs):
        """``sum_d x_td k_jd e^{Gamma_td - Gamma_jd}`` for j <= t inside
        t's sub-chunk, for each x (Q, dk) in ``xs``, elementwise and
        exact: one diagonal j = t - d of the blocks at a time."""
        mk = self.mk
        out = _diagonal_sums(xs, ((*self.rolled(d), mk.band[d])
                                  for d in range(mk.split)))
        if mk.split == mk.sub:
            return out
        hi = _diagonal_sums([_halves(x)[1] for x in xs],
                            ((*self.rolled_hi(d), mk.band_hi[d - mk.split])
                             for d in range(mk.split, mk.sub)))
        return [o + _merged(jnp.zeros_like(h), h) for o, h in zip(out, hi)]

    def solve(self, T, T_t=None):
        self.T, self.T_t = T, T_t
        self.kb = (self.beta * self.e) * self.k
        self.vb = self.beta * self.v
        self.W = _default(T, self.kb)
        self.Un = _default(T, self.vb)

    def writes(self, St):
        """U = Un - W S, given S's transpose."""
        return self.Un - _default(self.W, St, _NT)

    def leaving(self, St, U):
        """The transposed state leaving the chunk: S' = Diag(e^{Gamma_Q})
        S + (K * f)^T U."""
        return self.e_last * St + _default(U, self.f * self.k, _TN)


class _Step:
    """A grid step's blocks: ``heads`` heads of ``n`` chunks each,
    addressed by (head i, chunk s).  With ``normed`` the blocks of q
    and k are the raw columns, L2-normed a head here (q then over
    sqrt(dk))."""

    def __init__(self, q_ref, k_ref, v_ref, g_ref, beta_ref, Q, sub, normed):
        self.Q = Q
        self.n = k_ref.shape[0] // Q
        self.heads = beta_ref.shape[1]
        self.dk = k_ref.shape[1] // self.heads
        self.dv = v_ref.shape[1] // self.heads
        self.refs = q_ref, k_ref, v_ref, g_ref, beta_ref
        self.normed = normed
        self.masks = _Masks(Q, self.dk, sub)

    def at(self, s):
        return slice(s * self.Q, (s + 1) * self.Q)

    def key_cols(self, i):
        return slice(i * self.dk, (i + 1) * self.dk)

    def value_cols(self, i):
        return slice(i * self.dv, (i + 1) * self.dv)

    def units(self):
        return [(i, s) for s in range(self.n) for i in range(self.heads)]

    def raw(self, ref, i, s):
        """A block's columns of (i, s) as read, and the reciprocal norm
        of each row where ``normed`` (else 1)."""
        x = ref[self.at(s), self.key_cols(i)]
        if not self.normed:
            return x, 1.0
        return x, lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)

    def chunks(self, t_ref=None, tt_ref=None, with_q=True):
        """Every chunk's _Chunk by (i, s); T and its transpose from
        ``t_ref`` and ``tt_ref`` where given, else T computed, every
        chunk in lock step."""
        q_ref, k_ref, v_ref, g_ref, beta_ref = self.refs
        chunks, self.raws = {}, {}
        for i, s in self.units():
            at = self.at(s)
            k, rk = self.raw(k_ref, i, s)
            q, rq = self.raw(q_ref, i, s) if with_q else (None, None)
            self.raws[i, s] = (q, rq), (k, rk)
            if self.normed:
                k = k * rk
                q = None if q is None else q * (rq * self.dk ** -0.5)
            chunks[i, s] = _Chunk(
                q, k, v_ref[at, self.value_cols(i)],
                g_ref[at, self.key_cols(i)], beta_ref[at, i:i + 1],
                self.masks)
        order = list(chunks)
        if t_ref is None:
            for u, T in zip(order, _inverses([chunks[u].A for u in order],
                                             self.masks.eye)):
                chunks[u].solve(T)
        else:
            for i, s in order:
                chunks[i, s].solve(t_ref[i, s], tt_ref[i, s])
        return chunks

    def unnormed(self, i, s, dq, dk):
        """The gradients in q's and k's columns as read, from those in q
        and k as the rule read them: through ``y = c r x``, r the
        reciprocal norm, ``dx = c r (dy - x^ (x^ . dy))`` with x^ = r x."""
        if not self.normed:
            return dq, dk
        out = []
        for (x, r), dy, c in zip(self.raws[i, s], (dq, dk),
                                 (self.dk ** -0.5, 1.0)):
            unit = x * r
            out.append((c * r) * (dy - unit * jnp.sum(unit * dy, axis=1,
                                                      keepdims=True)))
        return out


def _fwd_kernel(*refs, Q, sub, states, normed):
    """The grid step's chunks of its heads, each head's in order: o, or
    with ``states`` (the backward pass's sweep, which reads no q) the
    transposed states that enter the chunks, the chunks' T and its
    transpose."""
    if states:
        k_ref, v_ref, g_ref, beta_ref, out_ref, t_ref, tt_ref, s_ref = refs
        q_ref = None
    else:
        q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, s_ref = refs
    step = _Step(q_ref, k_ref, v_ref, g_ref, beta_ref, Q, sub, normed)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    chunks = step.chunks(with_q=not states)
    for i, s in step.units():
        c = chunks[i, s]
        St = s_ref[i]
        U = c.writes(St)
        if states:
            out_ref[i, s] = St
            t_ref[i, s] = c.T
            tt_ref[i, s] = c.T.T
        else:
            out_ref[step.at(s), step.value_cols(i)] = (
                _default(c.e * c.q, St, _NT) + _default(c.P, U))
        s_ref[i] = c.leaving(St, U)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h_ref, t_ref, tt_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref, *,
                Q, sub, normed):
    """The same chunks transposed, each head's last to first.  ``h_ref``
    holds the transposed states that entered them, ``t_ref`` their T and
    ``tt_ref`` its transpose (the sweep's); ``ds_ref`` carries the
    gradient of the (transposed) state that LEAVES the chunk.  Writes
    the gradients in q's, k's and v's columns as read, in g (Gamma's
    reverse running sum inside the chunk) and in beta."""
    step = _Step(q_ref, k_ref, v_ref, g_ref, beta_ref, Q, sub, normed)
    mk = step.masks

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    chunks = step.chunks(t_ref, tt_ref)
    for s in reversed(range(step.n)):
        at = step.at(s)
        parts = {}
        for i in range(step.heads):
            c = chunks[i, s]
            St, dSt = h_ref[i, s], ds_ref[i]
            U = c.writes(St)
            do = do_ref[at, step.value_cols(i)]
            qe, kd = c.e * c.q, c.f * c.k
            # o = (Q * e) S + P U and S' = Diag(e_Q) S + (K * f)^T U
            dU = _default(c.P, do, _TN) + _default(kd, dSt, _NT)
            dP = _default(do, U, _NT)
            d_qe = _default(do, St)
            d_kd = _default(U, dSt)
            d_last = (_colsum(dSt * St) * c.e_last + _colsum(d_kd * kd))
            ds_ref[i] = (c.e_last * dSt + _default(do, qe, _TN)
                         - _default(dU, c.W, _TN))
            # U = Un - W S, W = T Kb, Un = T Vb
            dW = -_default(dU, St)
            dT = _default(dW, c.kb, _NT) + _default(dU, c.vb, _NT)
            parts[i] = (dT, dP, d_qe, d_kd, d_last, _default(c.T_t, dW),
                        _default(c.T_t, dU))
        # T = (I + A)^-1: dA = -T^T dT T^T on the strictly lower part,
        # the chunks in lock step
        inner = {i: _exact(parts[i][0], chunks[i, s].T_t) for i in parts}
        for i, (_, dP, d_qe, d_kd, d_last, d_kb, d_vb) in parts.items():
            c = chunks[i, s]
            dA = jnp.where(mk.below, -_exact(c.T_t, inner[i]), 0.0)
            dM = c.beta * dA
            # Kb = diag(beta) (K * e), Vb = diag(beta) V
            dv_ref[at, step.value_cols(i)] = c.beta * d_vb
            dbeta_ref[at, i:i + 1] = (_rowsum(dA * c.M)
                                      + _rowsum(d_kb * c.k * c.e)
                                      + _rowsum(d_vb * c.v))
            dq = d_qe * c.e
            dk = d_kb * (c.beta * c.e) + d_kd * c.f
            dgam = d_qe * (c.e * c.q) + d_kb * c.kb - d_kd * (c.f * c.k)
            dgam = dgam + jnp.where(mk.row == Q - 1, d_last, 0.0)
            dq, dk, dgam = _through_off(c, dM, dP, dq, dk, dgam)
            dq, dk, dgam = _through_diagonal(c, dM, dP, dq, dk, dgam)
            dq, dk = step.unnormed(i, s, dq, dk)
            cols = step.key_cols(i)
            dq_ref[at, cols] = dq
            dk_ref[at, cols] = dk
            # g's gradient: Gamma's reverse running sum inside the chunk
            dg_ref[at, cols] = _running(mk.upper, dgam)


def _through_off(c, dM, dP, dq, dk, dgam):
    """M's and P's gradients (dM, dP) through their products between
    sub-chunks: ``x * left`` on sub-chunk a's rows against ``rights[a -
    1]``, whose exponents are Gamma - Gamma_r and Gamma_r - Gamma with r
    the row before sub-chunk a."""
    mk = c.mk
    kl, ql = c.k * c.left, c.q * c.left
    d_kl = jnp.zeros_like(kl)
    d_ql = jnp.zeros_like(ql)
    d_refs = []
    for a, (right, decay) in enumerate(zip(c.rights, c.decays), 1):
        dMa = jnp.where(mk.t_part == a, dM, 0.0)
        dPa = jnp.where(mk.t_part == a, dP, 0.0)
        d_kl = d_kl + _default(dMa, right)
        d_ql = d_ql + _default(dPa, right)
        d_right = _default(dMa, kl, _TN) + _default(dPa, ql, _TN)
        dk = dk + d_right * decay
        y = d_right * right
        dgam = dgam - y
        d_refs.append(_colsum(y))
    z = (d_kl * c.k + d_ql * c.q) * c.left
    dgam = dgam + z
    for a, d_ref in enumerate(d_refs, 1):
        d_ref = d_ref - _colsum(jnp.where(mk.part == a, z, 0.0))
        dgam = dgam + jnp.where(mk.row == a * mk.sub - 1, d_ref, 0.0)
    return dq + d_ql * c.left, dk + d_kl * c.left, dgam


def _diagonal_sums(xs, diagonals):
    """For each x in ``xs``, the (rows, Q) matrix that holds on each
    diagonal ``band`` of ``diagonals`` (k_{t-d}, e^{Gamma_t -
    Gamma_{t-d}}, band) the sum over the channels of x_t k_{t-d} e^{..}."""
    out = None
    for kd, E, band in diagonals:
        ke = kd * E
        if out is None:
            out = [jnp.zeros(band.shape, jnp.float32) for _ in xs]
        out = [jnp.where(band, _rowsum(x * ke), o) for x, o in zip(xs, out)]
    return out


def _diagonal_grads(k, q, dM, dP, diagonals):
    """The gradients through ``_diagonal_sums`` of k and of q, given dM
    and dP, over ``diagonals`` (k_{t-d}, e^{Gamma_t - Gamma_{t-d}}, band,
    d): by row t, ``c_t e^{Gamma_t - Gamma_j} k_j`` into x_t's gradient
    (dq, dk) and Gamma_t's (dg), and ``c_t x_t e^{Gamma_t - Gamma_j}``
    into k_j's, j = t - d, rolled back up by d (``back``)."""
    n = k.shape[0]
    dq = dk = dg = back = jnp.zeros_like(k)
    for kd, E, band, d in diagonals:
        ck = _rowsum(jnp.where(band, dM, 0.0))
        cq = _rowsum(jnp.where(band, dP, 0.0))
        rk, rq = ck * (kd * E), cq * (kd * E)
        dk = dk + rk
        dq = dq + rq
        dg = dg + k * rk + q * rq
        y = (ck * k + cq * q) * E
        back = back + (pltpu.roll(y, n - d, 0) if d else y)
    return dq, dk, dg, back


def _through_diagonal(c, dM, dP, dq, dk, dgam):
    """M's and P's gradients through the diagonal blocks; with
    sub-chunks of 16 the diagonals d >= 8 on the second halves' rows
    alone, whose j = t - d lie in the first halves.  Gamma_j's gradient
    is ``-k_j`` times k_j's from the diagonals."""
    mk = c.mk
    d_q, d_k, d_g, back = _diagonal_grads(
        c.k, c.q, dM, dP,
        ((*c.rolled(d), mk.band[d], d) for d in range(mk.split)))
    dq, dk, dgam = dq + d_q, dk + d_k + back, dgam + d_g - c.k * back
    if mk.split == mk.sub:
        return dq, dk, dgam
    (k_lo, k), (_, q) = _halves(c.k), _halves(c.q)
    d_q, d_k, d_g, back = _diagonal_grads(
        k, q, _halves(dM)[1], _halves(dP)[1],
        ((*c.rolled_hi(d), mk.band_hi[d - mk.split], d - mk.split)
         for d in range(mk.split, mk.sub)))
    zeros = jnp.zeros_like(k)
    return (dq + _merged(zeros, d_q), dk + _merged(back, d_k),
            dgam + _merged(-k_lo * back, d_g))


def _specs(Q, n, heads, dk, dv, chunks, firsts, reverse):
    """Block specs on the grid (row, heads, step), a step ``heads``
    heads of ``n`` chunks each, by what they fetch: q's, k's and v's
    columns of x (b, L, [q | k | v]) (``firsts``: the first block of
    each), the key-wide and value-wide columns of arrays of their own
    (g, dq, dk, dg; o, dO, dv), ``beta`` (b, H / heads, L, heads), the
    transposed states (b, H, L / Q, dv, dk) and the chunks' T (b, H,
    L / Q, Q, Q); ``reverse`` walks the steps last to first."""
    step = (lambda c: chunks // n - 1 - c) if reverse else (lambda c: c)

    def columns(width, first=0):
        return pl.BlockSpec((None, n * Q, width),
                            lambda i, h, c: (i, step(c), first + h))

    def per_chunk(*shape):
        return pl.BlockSpec((None, heads, n) + shape,
                            lambda i, h, c: (i, h, step(c), 0, 0))

    key, value = heads * dk, heads * dv
    return dict(
        q=columns(key, firsts[0]), k=columns(key, firsts[1]),
        v=columns(value, firsts[2]), key=columns(key), value=columns(value),
        beta=pl.BlockSpec((None, None, n * Q, heads),
                          lambda i, h, c: (i, h, step(c), 0)),
        states=per_chunk(dv, dk), inverses=per_chunk(Q, Q))


_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES))


def _step_shape(H, dk, dv, chunks):
    """(heads, chunks) a grid step: two of each where they divide, and
    two heads' v columns start a whole block into x.  One chunk's
    products are a chain that waits on the MXU's latency; independent
    ones interleave (at Kimi Linear's layer, bundles a head and chunk in
    the TPU compiler's schedules, forward / sweep / walk: 1,905 / 1,621
    / 2,729 one by one, 1,568 / 1,313 / 2,501 two heads, 1,351 / 1,135 /
    2,432 four heads, 1,379 / 1,119 / 2,403 two by two)."""
    heads = 2 if H % 2 == 0 and 2 * H * dk % (2 * dv) == 0 else 1
    return heads, 2 if chunks % 2 == 0 else 1


def _grid(x, Q, layout, reverse):
    H, dk, dv = layout
    b, L = x.shape[:2]
    nc = L // Q
    heads, n = _step_shape(H, dk, dv, nc)
    firsts = (0, H // heads, 2 * H * dk // (heads * dv))
    return ((b, H // heads, nc // n),
            _specs(Q, n, heads, dk, dv, nc, firsts, reverse), heads)


def _operands(g, beta, heads):
    """g (b, L, H, dk) as (b, L, H dk) and beta (b, L, H) as (b, H /
    heads, L, heads)."""
    b, L, H, dk = g.shape
    return (g.reshape(b, L, H * dk),
            beta.reshape(b, L, H // heads, heads).transpose(0, 2, 1, 3))


def _fwd(x, g, beta, Q, sub, layout, normed, states, interpret):
    """o (b, L, H dv), or with ``states`` the transposed state entering
    each chunk (b, H, L / Q, dv, dk), each chunk's T (b, H, L / Q, Q, Q)
    and T's transpose; float32."""
    grid, at, heads = _grid(x, Q, layout, False)
    H, dk, dv = layout
    b, L = x.shape[:2]
    nc = L // Q
    f32 = jnp.float32
    if states:
        ins = ("k", "v", "key", "beta")
        out = [at["states"], at["inverses"], at["inverses"]]
        out_shape = [jax.ShapeDtypeStruct((b, H, nc, dv, dk), f32)] + [
            jax.ShapeDtypeStruct((b, H, nc, Q, Q), f32)] * 2
    else:
        ins = ("q", "k", "v", "key", "beta")
        out = at["value"]
        out_shape = jax.ShapeDtypeStruct((b, L, H * dv), f32)
    operands = (x,) * (len(ins) - 2) + _operands(g, beta, heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, Q=Q, sub=sub, states=states,
                          normed=normed),
        grid=grid, in_specs=[at[key] for key in ins],
        out_specs=out, out_shape=out_shape,
        scratch_shapes=[_scratch((heads, dv, dk), f32)],
        name="kda_sweep" if states else "kda_fwd",
        interpret=interpret, **_PARAMS)(*operands)


def _bwd(x, g, beta, entering, inverses, transposed, do, Q, sub, layout,
         normed, interpret):
    """The gradients of ``_fwd``'s o in q's, k's and v's columns of x
    (three arrays), in g (b, L, H dk) and in beta (b, H / heads, L,
    heads)."""
    grid, at, heads = _grid(x, Q, layout, True)
    H, dk, dv = layout
    b, L = x.shape[:2]
    f32 = jnp.float32
    g, beta = _operands(g, beta, heads)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, Q=Q, sub=sub, normed=normed),
        grid=grid,
        in_specs=[at[key] for key in ("q", "k", "v", "key", "beta", "states",
                                      "inverses", "inverses", "value")],
        out_specs=[at[key] for key in ("key", "key", "value", "key",
                                       "beta")],
        out_shape=[jax.ShapeDtypeStruct(shape, f32) for shape in (
            (b, L, H * dk), (b, L, H * dk), (b, L, H * dv), (b, L, H * dk),
            beta.shape)],
        scratch_shapes=[_scratch((heads, dv, dk), f32)],
        name="kda_walk",
        interpret=interpret, **_PARAMS)(x, x, x, g, beta, entering,
                                        inverses, transposed, do)


def kda_chunks(x, g, beta, Q, sub, layout, normed, interpret=None):
    """The chunked per-channel rule as the Pallas forward kernel, for
    shapes that ``kda_chunk_tiles`` takes.  x (b, L, [q | k | v]) holds
    q, k (H dk columns each) and v (H dv) side by side, as the mixer's
    convolution leaves them (with ``normed`` q and k are the raw
    columns, L2-normed a head in the kernels, q then over sqrt(dk));
    ``layout`` (H, dk, dv), one key head a value head; g (b, L, H, dk)
    the log decays and beta (b, L, H); float32, L a multiple of Q.
    Returns o (b, L, H, dv): what ``ops/deltanet._channels`` returns."""
    H, _, dv = layout
    b, L = x.shape[:2]
    o = _fwd(x, g, beta, Q, sub, layout, normed, False,
             _interpret(interpret))
    return o.reshape(b, L, H, dv)


def kda_chunks_grads(x, g, beta, do, Q, sub, layout, normed,
                     interpret=None):
    """The gradients of ``kda_chunks``'s o in x, g and beta, given its
    cotangent ``do`` (b, L, H, dv): a sweep of the states that enter the
    chunks (and of the chunks' T), then one kernel that walks the chunks
    last to first."""
    b, L, H = beta.shape
    interpret = _interpret(interpret)
    # the states entering the chunks, and the chunks' T, live inside this
    # backward only
    entering, inverses, transposed = _fwd(x, g, beta, Q, sub, layout, normed,
                                          True, interpret)
    dq, dk, dv, dg, d_beta = _bwd(x, g, beta, entering, inverses, transposed,
                                  do.reshape(b, L, -1), Q, sub, layout,
                                  normed, interpret)
    d_beta = d_beta.transpose(0, 2, 1, 3).reshape(b, L, H)
    return (jnp.concatenate([dq, dk, dv], axis=2), dg.reshape(g.shape),
            d_beta)
