"""Linear attention by the gated delta rule: what a Gated DeltaNet mixer
(Yang, Kautz and Hatamizadeh 2024, "Gated Delta Networks"; Qwen3-Next's
``linear_attention`` layers) computes between its projections.

A value head's state ``S`` (keys x values) follows, from ``S_0 = 0``,

    S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - e^{g_t} S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

with ``g_t <= 0`` the log decay and ``beta_t`` in (0, 1) the step: the
state forgets, then writes the part of ``v_t`` that it does not already
predict for ``k_t`` (the delta rule).  Mamba-2's scan adds ``x B^T`` to
its state; here the write depends on the state, so inside a chunk of Q
positions the writes ``u`` solve a triangular system (the WY form):

    (I + A) U = diag(beta) V - diag(beta e^gamma) K S_0,
    A[t, j] = beta_t e^{gamma_t - gamma_j} k_t . k_j   (j < t)

with ``gamma`` the chunk's running sum of g.  ``(I + A)^-1`` of a
strictly lower A is ``(I - A)(I + A^2)(I + A^4)...``: log2(Q) batched
products on the MXU, at "highest" precision (a power of A carries the
rounding of every factor), where a triangular solve would walk Q rows one
at a time.  Then, per chunk,

    O   = diag(e^gamma) Q S_0 + (Q K^T * D) U,    D[t, j] = e^{gamma_t - gamma_j}, j <= t
    S_Q = e^{gamma_Q} S_0 + (diag(e^{gamma_Q - gamma}) K)^T U

and the chunks are walked in order with the state carried.  Decays are
``exp`` of differences of the running sum (no quotient of two
exponentials, which underflows); decays, state and sums are float32, the
other products take the backend's default precision.

The rule is one ``custom_vjp`` (``_rule``) over x (b, L, [q | k | v]),
g and beta that keeps those three operands between the passes and
nothing else.  Its backward, a chunk at a time from the last, with ``S``
the state entering the chunk, ``dS'`` the gradient of the one leaving
it, ``f = e^{gamma_Q - gamma}``, ``P = Q K^T * D``, ``T = (I + A)^-1``,
``W = T diag(beta e^gamma) K``, ``Un = T diag(beta) V`` and ``U = Un -
W S``:

    dU = P^T dO + diag(f) K dS'
    dS = e^{gamma_Q} dS' + (diag(e^gamma) Q)^T dO - W^T dU
    dT = -dU S^T (diag(beta e^gamma) K)^T + dU (diag(beta) V)^T
    dA = -T^T dT T^T on the strictly lower part (one product pair)
    dQ = diag(e^gamma) dO S^T + (dO U^T * D) K
    dK = (dO U^T * D)^T Q + diag(f) U dS'^T
         + diag(beta e^gamma) T^T dW + (dM + dM^T) K,  dW = -dU S^T,
         dM = diag(beta) dA * D_<  (D_< the strictly lower decays)
    dV = diag(beta) T^T dU

and beta's and gamma's gradients the row (and, for gamma_j, column)
sums of the same elementwise products; g's is gamma's reverse running
sum inside the chunk.

Shapes that ``deltanet_kernels.gdn_chunk_tiles`` takes (chunks a multiple
of 8, keys and values a multiple of 128 wide: Qwen3-Next's chunks of 64
over 16 key heads of 128 and two value heads of 128 each) run that
backward as Pallas kernels, and the forward too, the state in VMEM from
chunk to chunk and every (Q, Q) matrix made and used there; the
inverse there is a bfloat16 product form refined by two Newton steps
to float32 accuracy, and q and k are read as x's raw columns and
L2-normed in the kernels.  Every other shape (the tests' models, chunks
of 16 and heads of 8) runs ``_chunked`` in ``jnp`` inside the same
``custom_vjp``: the backward pass computes the chunks again (behind an
``optimization_barrier``, so that XLA does not merge them with the
forward's and keep the forward's states alive) and differentiates that.
Either way one layer's per-chunk states exist only inside its own
backward pass.

**A decay per key channel** (Kimi Delta Attention, Kimi Linear report,
arXiv:2510.26692): ``g_t`` is a vector over the dk key channels and the
state forgets row by row,

    S_t = (I - beta_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + beta_t k_t v_t^T

which is the rule above with the scalar ``e^{g_t}`` replaced by
``Diag(e^{g_t})``.  The decay no longer comes out of a chunk's products
as ``e^{gamma_t - gamma_j}``: ``A[t, j] = beta_t sum_d k_td k_jd
e^{Gamma_td - Gamma_jd}`` is a contraction with an exponential inside
it, and ``e^{-Gamma_j}`` over a whole chunk overflows float32 once its
decays pass about 88.  So a chunk is cut into sub-chunks of ``_SUB``
positions with a reference position r before each: where t's sub-chunk
follows j's, ``(k_t e^{Gamma_t - Gamma_r}) . (k_j e^{Gamma_r -
Gamma_j})`` is a product of factors of at most 1 (r is the position
before t's sub-chunk, so Gamma_t <= Gamma_r <= Gamma_j); inside a
sub-chunk the pair's sum is taken elementwise with ``e^{Gamma_t -
Gamma_j}`` exact.  The rest is the scalar rule's with the same
replacement: ``W = T diag(beta) (K * e^Gamma)``, ``O = (Q * e^Gamma)
S_0 + P U``, ``S_Q = Diag(e^{Gamma_Q}) S_0 + (K * e^{Gamma_Q -
Gamma})^T U``, every exponent at most 0.

That form runs in ``jnp`` (``_channel_span``) behind its own
``custom_vjp`` (``_rule_channels``), which keeps x, g and beta and
nothing else.  The chunks are taken ``_SPAN`` at a time, the state
carried from chunk to chunk by a written-out sequence (no ``while``:
a loop's event would count its body twice in the device account), so
no (chunks, Q, Q, dk) array exists over a whole row.  Its backward sweeps
the states entering the spans, then walks the spans last to first,
each one's forward computed again and differentiated by ``jax.vjp``
with the state's gradient carried: one span's per-chunk arrays exist at
a time.  A scalar g takes the path above, unchanged.

``gdn_mixer`` is the mixer between its projections, from the
in-projection's [q | k | v | z] result and the (b | a) one: the causal
depthwise convolution without a bias and its silu over [q | k | v]
(``ssm._conv_silu``, reading its columns in place), beta and g, the
rule over the convolution's result as it lies (q and k L2-normed inside
it), then the norm of each value head's output times one gain shared by
the heads, times ``silu(z)`` (``ssm._gate_norm`` with the norm BEFORE
the gate, reading z in place).

``kda_mixer`` is Kimi Linear's delta-rule mixer with its projections:
the convolution and silu over [q | k | v] as ``gdn_mixer``'s, the decay
``g = -exp(A_log) softplus(f + dt_bias)`` a key channel, the rule with
q and k L2-normed inside it, and ``rmsnorm(o) * gain * sigmoid(z)`` a
head (``ssm._gate_norm`` with the norm before a sigmoid gate); it keeps
its input alone and computes the rest again in the backward pass.

Ops:
  ``gated_delta_rule`` — q, k (b, L, Hk, dk), v (b, L, H, dv), beta
                         (b, L, H), g (b, L, H) or (b, L, H, dk)
                         -> o (b, L, H, dv)
  ``gdn_mixer``        — the mixer between its projections
  ``kda_mixer``        — Kimi Linear's, with its projections
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .deltanet_kernels import (L2_EPS, gdn_chunk_tiles, gdn_chunks,
                               gdn_chunks_grads)
from .registry import register
from .ssm import _conv_silu, _gate_norm, _whole_chunks

__all__ = ["gated_delta_rule", "gdn_mixer", "kda_mixer"]

_HIGHEST = lax.Precision.HIGHEST
_SUB = 16       # positions of a sub-chunk of the per-channel rule
_SPAN = 16      # chunks of the per-channel rule whose arrays exist at once


def _inverse(A):
    """``(I + A)^-1`` of strictly lower (..., Q, Q) matrices: with N = -A,
    ``(I + N)(I + N^2)(I + N^4)...`` up to N^(Q-1)."""
    Q = A.shape[-1]
    eye = jnp.eye(Q, dtype=A.dtype)
    power = -A
    inv = eye + power
    span = 2
    while span < Q:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = inv + jnp.matmul(inv, power, precision=_HIGHEST)
        span *= 2
    return inv


def _chunked(q, k, v, g, beta, Q):
    """The rule over whole chunks, float32.  q, k (b, L, G, dk); v
    (b, L, G, R, dv) (head ``g * R + r`` reads key head g); g, beta
    (b, L, G, R); L a multiple of Q.  Returns o, v's shape."""
    b, L, G, R, dv = v.shape
    dk = q.shape[-1]
    nc = L // Q
    q, k, v, g, beta = (a.reshape((b, nc, Q) + a.shape[2:])
                        for a in (q, k, v, g, beta))
    # positions last for the per-head vectors: (b, nc, G, R, Q)
    cum = jnp.cumsum(jnp.moveaxis(g, 2, -1), axis=-1)
    bt = jnp.moveaxis(beta, 2, -1)
    below = jnp.tril(jnp.ones((Q, Q), bool), -1)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    diff = cum[..., :, None] - cum[..., None, :]               # (..., Q, Q)
    kk = jnp.einsum("bctgd,bcsgd->bcgts", k, k)[:, :, :, None]
    A = bt[..., :, None] * kk * jnp.exp(jnp.where(below, diff, -jnp.inf))
    T = _inverse(A)                                            # (b,nc,G,R,Q,Q)
    # the writes' two parts: U = Un - W S_0
    kg = jnp.moveaxis(k, 2, 3)[:, :, :, None]                  # (b,nc,G,1,Q,dk)
    W = jnp.einsum("bcgrts,bcgrsd->bcgrtd", T,
                   (bt * jnp.exp(cum))[..., None] * kg)
    Un = jnp.einsum("bcgrts,bcsgre->bcgrte", T,
                    jnp.moveaxis(bt, -1, 2)[..., None] * v)
    qk = jnp.einsum("bctgd,bcsgd->bcgts", q, k)[:, :, :, None]
    P = qk * jnp.exp(jnp.where(seen, diff, -jnp.inf))          # (b,nc,G,R,Q,Q)
    qg = jnp.moveaxis(q, 2, 3)[:, :, :, None] * jnp.exp(cum)[..., None]
    kd = kg * jnp.exp(cum[..., -1:] - cum)[..., None]          # (b,nc,G,R,Q,dk)
    whole = jnp.exp(cum[..., -1])                              # (b,nc,G,R)

    def chunk(S, part):
        Wc, Unc, Pc, qgc, kdc, keep = part
        U = Unc - jnp.einsum("bgrtd,bgrde->bgrte", Wc, S)
        o = (jnp.einsum("bgrtd,bgrde->bgrte", qgc, S)
             + jnp.einsum("bgrts,bgrse->bgrte", Pc, U))
        S = keep[..., None, None] * S + jnp.einsum("bgrtd,bgrte->bgrde",
                                                   kdc, U)
        return S, o

    _, o = lax.scan(chunk, jnp.zeros((b, G, R, dk, dv), jnp.float32),
                    tuple(jnp.moveaxis(a, 1, 0)
                          for a in (W, Un, P, qg, kd, whole)))
    # (nc, b, G, R, Q, dv) -> (b, L, G, R, dv)
    return jnp.moveaxis(o, (0, 4), (1, 2)).reshape(b, L, G, R, dv)


def _split(x, g, layout, normed):
    """q, k (b, L, Hk, dk) and v (b, L, Hk, R, dv) from x (b, L, [q | k
    | v]); with ``normed`` q and k L2-normed a head, q over sqrt(dk)."""
    Hk, dk, dv = layout
    b, L, _ = x.shape
    q = x[..., :Hk * dk].reshape(b, L, Hk, dk)
    k = x[..., Hk * dk:2 * Hk * dk].reshape(b, L, Hk, dk)
    v = x[..., 2 * Hk * dk:].reshape(b, L, Hk, g.shape[-1], dv)
    if normed:
        q, k = _l2_normed(q) * dk ** -0.5, _l2_normed(k)
    return q, k, v


def _kernels(g, Q, layout):
    """Whether the Pallas kernels take the rule in chunks of Q, g
    (b, L, Hk, R), ``layout`` (Hk, dk, dv)."""
    Hk, dk, dv = layout
    return gdn_chunk_tiles(Q, dk, dv, g.shape[-1], Hk)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rule(x, g, beta, Q, layout, normed):
    return _rule_fwd(x, g, beta, Q, layout, normed)[0]


def _rule_fwd(x, g, beta, Q, layout, normed):
    if _kernels(g, Q, layout):
        o = gdn_chunks(x, g, beta, Q, layout, normed)
    else:
        o = _chunked(*_split(x, g, layout, normed), g, beta, Q)
    return o, (x, g, beta)


def _rule_bwd(Q, layout, normed, res, do):
    if _kernels(res[1], Q, layout):
        # the backward's operations carry the scope's name too (a
        # transposed custom_vjp opens none)
        with jax.named_scope("mx.gdn.core"):
            return gdn_chunks_grads(*res, do, Q, layout, normed)
    res = lax.optimization_barrier(res)
    return jax.vjp(lambda x, g, beta: _chunked(
        *_split(x, g, layout, normed), g, beta, Q), *res)[1](do)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _channel_span(q, k, v, g, beta, S, sub):
    """The per-channel rule over a span of whole chunks, float32, with
    the state S (b, H, dk, dv) entering it.  q, k, g (b, n, H, Q, dk);
    v (b, n, H, Q, dv); beta (b, n, H, Q).  Returns (o, v's shape; the
    state leaving the span)."""
    Q, dk = q.shape[-2:]
    m = Q // sub
    cum = jnp.cumsum(g, axis=-2)                       # Gamma, (.., Q, dk)
    # the reference of each sub-chunk: Gamma before its first position
    ref = jnp.concatenate([jnp.zeros_like(cum[..., :1, :]),
                           cum[..., sub - 1:Q - 1:sub, :]], axis=-2)
    left = jnp.exp(cum - jnp.repeat(ref, sub, axis=-2))           # <= 1
    before = (jnp.arange(Q)[None, :]
              < sub * jnp.arange(m)[:, None])[..., None]          # (m, Q, 1)
    # k_j e^{Gamma_r - Gamma_j} for j before sub-chunk a's start:
    # (.., m, Q, dk)
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        before, ref[..., :, None, :] - cum[..., None, :, :], -jnp.inf))

    def off(x):
        # sub-chunk a's rows against every earlier sub-chunk's columns
        rows = (x * left).reshape(x.shape[:-2] + (m, sub, dk))
        return jnp.einsum("...asd,...ajd->...asj", rows, right).reshape(
            x.shape[:-2] + (Q, Q))

    # inside a sub-chunk, elementwise: (.., m, sub, sub, dk)
    cs = cum.reshape(cum.shape[:-2] + (m, sub, dk))
    seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    inner = jnp.exp(jnp.where(seen, cs[..., :, None, :] - cs[..., None, :, :],
                              -jnp.inf))
    ks = k.reshape(cs.shape)

    def diagonal(x):
        xs = x.reshape(cs.shape)
        d = jnp.sum(xs[..., :, None, :] * ks[..., None, :, :] * inner, -1)
        # (.., m, sub, sub) placed on the block diagonal of (.., Q, Q)
        eye = jnp.eye(m, dtype=d.dtype)[:, None, :, None]
        return (d[..., :, :, None, :] * eye).reshape(d.shape[:-3] + (Q, Q))

    below = jnp.tril(jnp.ones((Q, Q), bool), -1)
    A = beta[..., :, None] * jnp.where(below, off(k) + diagonal(k), 0.0)
    T = _inverse(A)                                    # (b, n, H, Q, Q)
    P = off(q) + diagonal(q)
    decay = jnp.exp(cum)
    W = T @ (beta[..., None] * k * decay)              # (b, n, H, Q, dk)
    Un = T @ (beta[..., None] * v)                     # (b, n, H, Q, dv)
    kd = k * jnp.exp(cum[..., -1:, :] - cum)
    whole = jnp.exp(cum[..., -1, :])                   # (b, n, H, dk)
    entering, writes = [], []
    for c in range(q.shape[1]):
        entering.append(S)
        U = Un[:, c] - W[:, c] @ S
        writes.append(U)
        S = whole[:, c, ..., None] * S + jnp.swapaxes(kd[:, c], -1, -2) @ U
    S0, U = jnp.stack(entering, 1), jnp.stack(writes, 1)
    return (q * decay) @ S0 + P @ U, S


def _pieces(a, Q, span):
    """``a`` (b, L, H, ...) as the spans' pieces, heads before
    positions: a list of (b, n, H, Q, ...), n chunks of Q a piece and
    ``span`` at most."""
    b, L = a.shape[:2]
    nc = L // Q
    return [jnp.moveaxis(a[:, lo * Q:(lo + n) * Q].reshape(
        (b, n, Q) + a.shape[2:]), 2, 3)
        for lo, n in ((lo, min(span, nc - lo)) for lo in range(0, nc, span))]


def _joined(pieces):
    """``_pieces``' inverse."""
    return jnp.concatenate([jnp.moveaxis(p, 3, 2).reshape(
        (p.shape[0], -1) + p.shape[2:3] + p.shape[4:]) for p in pieces], 1)


def _channel_operands(x, g, layout, normed):
    """q, k (b, L, H, dk), one key head a value head, and v (b, L, H,
    dv) of x (b, L, [q | k | v]); g (b, L, H, dk)."""
    Hk, dk, dv = layout
    b, L, H, _ = g.shape
    q, k, v = _split(x, g[..., 0].reshape(b, L, Hk, H // Hk), layout, normed)
    q, k = (jnp.broadcast_to(a[:, :, :, None], (b, L, Hk, H // Hk, dk))
            .reshape(b, L, H, dk) for a in (q, k))
    return q, k, v.reshape(b, L, H, dv)


def _span_operands(x, g, beta, Q, layout, normed, span):
    """The spans' operands: a list of (q, k, v, g, beta) pieces."""
    ops = _channel_operands(x, g, layout, normed) + (g, beta)
    return list(zip(*(_pieces(a, Q, span) for a in ops)))


def _channels(x, g, beta, Q, layout, normed, sub, span):
    """o (b, L, H, dv) of the per-channel rule, span after span."""
    _, dk, dv = layout
    b, _, H = beta.shape
    S = jnp.zeros((b, H, dk, dv), jnp.float32)
    out = []
    for part in _span_operands(x, g, beta, Q, layout, normed, span):
        o, S = _channel_span(*part, S, sub)
        out.append(o)
    return _joined(out)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _rule_channels(x, g, beta, Q, layout, normed, sub, span):
    return _channels(x, g, beta, Q, layout, normed, sub, span)


def _rule_channels_fwd(x, g, beta, Q, layout, normed, sub, span):
    return (_channels(x, g, beta, Q, layout, normed, sub, span),
            (x, g, beta))


def _rule_channels_bwd(Q, layout, normed, sub, span, res, do):
    x, g, beta = res
    _, dk, dv = layout
    b, _, H = beta.shape
    (q, k, v), operands = jax.vjp(
        lambda x: _channel_operands(x, g, layout, normed), x)
    parts = list(zip(*(_pieces(a, Q, span) for a in (q, k, v, g, beta))))
    # the states entering the spans (the sweep's outputs are not used)
    states = [jnp.zeros((b, H, dk, dv), jnp.float32)]
    for part in parts[:-1]:
        states.append(_channel_span(*part, states[-1], sub)[1])
    dS = jnp.zeros_like(states[0])
    grads = []
    for part, S, d_o in reversed(list(zip(parts, states,
                                          _pieces(do, Q, span)))):
        # a barrier, so that XLA does not merge this span's forward with
        # the sweep's and keep the sweep's arrays alive until here
        part, S = lax.optimization_barrier((part, S))
        _, back = jax.vjp(partial(_channel_span, sub=sub), *part, S)
        *grad, dS = back((d_o, dS))
        grads.append(grad)
    dq, dk_, dv_, dg, dbeta = (_joined(p[::-1]) for p in zip(*grads))
    return operands((dq, dk_, dv_))[0], dg, dbeta


_rule_channels.defvjp(_rule_channels_fwd, _rule_channels_bwd)


def _run(x, g, beta, layout, chunk, normed, sub=_SUB, span=_SPAN):
    """The rule over x (b, L, [q | k | v]), beta (b, L, H) and g
    (b, L, H), or (b, L, H, dk) for a decay a key channel, in float32 in
    chunks of ``chunk``, the last one filled with positions that neither
    decay nor write.  Returns o (b, L, H dv) float32."""
    Hk, _, dv = layout
    b, L, H = beta.shape
    Q = int(chunk)
    if g.ndim == 4:
        o = _rule_channels(*(_whole_chunks(a.astype(jnp.float32), Q)
                             for a in (x, g, beta)),
                           Q, layout, normed, math.gcd(Q, sub), int(span))
        return o[:, :L].reshape(b, L, H * dv)
    o = _rule(*(_whole_chunks(a.astype(jnp.float32), Q) for a in (
        x, g.reshape(b, L, Hk, H // Hk), beta.reshape(b, L, Hk, H // Hk))),
        Q, layout, normed)
    return o[:, :L].reshape(b, L, H * dv)


@register("_contrib_gated_delta_rule", num_inputs=5,
          aliases=["gated_delta_rule"])
def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """The gated delta rule (the module's docstring has the recurrence).
    q, k (b, L, Hk, dk); v (b, L, H, dv) with Hk dividing H (value head
    ``h`` reads key head ``h // (H // Hk)``); g (log decay, <= 0) and
    beta (b, L, H).  Returns o (b, L, H, dv) in v's dtype, computed in
    float32 in chunks of ``chunk`` positions; any L: the last chunk is
    filled with positions that neither decay nor write."""
    b, L, Hk, dk = q.shape
    H, dv = v.shape[2:]
    if H % Hk:
        from ..base import MXNetError
        raise MXNetError(f"gated_delta_rule: {Hk} key heads do not divide "
                         f"{H} value heads")
    f32 = jnp.float32
    x = jnp.concatenate([a.astype(f32).reshape(b, L, -1) for a in (q, k, v)],
                        axis=2)
    o = _run(x, g, beta, (Hk, dk, dv), chunk, False)
    return o.reshape(b, L, H, dv).astype(v.dtype)


def _l2_normed(x, eps=L2_EPS):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@register("_contrib_gdn_mixer", num_inputs=6, aliases=["gdn_mixer"])
def gdn_mixer(qkvz, ba, conv_weight, A_log, dt_bias, gamma, *,
              key_heads: int, value_heads: int, key_dim: int,
              value_dim: int, chunk: int = 64, eps: float = 1e-6):
    """A Gated DeltaNet mixer between its projections.  ``qkvz``
    (b, L, .) is [q | k | v | z] of widths ``Hk dk | Hk dk | H dv |
    H dv``; ``ba`` (b, L, 2H) is [b | a]; ``conv_weight`` (2 Hk dk + H dv,
    K), no bias; ``A_log``, ``dt_bias`` (H,); ``gamma`` (dv,), the gain
    every value head's norm shares.  ``[q | k | v] = silu(conv([q | k |
    v]))``; q and k L2-normed a head (q then over sqrt(dk));
    ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
    the rule; ``rmsnorm(o) * gamma * silu(z)`` a head.  Returns
    (b, L, H dv) for the out-projection."""
    Hk, H, dk, dv = (int(key_heads), int(value_heads), int(key_dim),
                     int(value_dim))
    b, L, _ = qkvz.shape
    width = 2 * Hk * dk + H * dv
    f32 = jnp.float32
    with jax.named_scope("mx.gdn.conv"):
        qkv = _conv_silu(qkvz, conv_weight, None, 0)
    with jax.named_scope("mx.gdn.core"):
        ba = ba.astype(f32)
        beta = jax.nn.sigmoid(ba[..., :H])
        g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(
            ba[..., H:] + dt_bias.astype(f32))
        # q, k and v are read where the convolution left them, q and k
        # L2-normed inside the rule
        o = _run(qkv, g, beta, (Hk, dk, dv), chunk, True).astype(qkv.dtype)
    with jax.named_scope("mx.gdn.gate_norm"):
        return _gate_norm(o, qkvz,
                          jnp.tile(gamma, H), H, float(eps), width, True)


@register("_contrib_kda_mixer", num_inputs=12, aliases=["kda_mixer"])
def kda_mixer(x, qkv_weight, f_a_weight, f_b_weight, b_weight, g_a_weight,
              g_b_weight, conv_weight, A_log, dt_bias, gamma, out_weight, *,
              heads: int, head_dim: int, chunk: int = 64,
              eps: float = 1e-5):
    """Kimi Delta Attention over x (b, L, C), its projections included
    (weights (out, in), as ``Dense`` holds them), ``heads`` H of
    ``head_dim`` d.  ``[q | k | v] = silu(conv(x W_qkv))``, the causal
    depthwise convolution (``conv_weight`` (3 H d, K), no bias); q and k
    L2-normed a head (q then over sqrt(d)); ``beta = sigmoid(x W_b)``
    a head; ``g = -exp(A_log) softplus((x W_fa) W_fb + dt_bias)`` a key
    channel (``A_log`` (H,), ``dt_bias`` (H d,)); the rule;
    ``rmsnorm(o) * gamma * sigmoid((x W_ga) W_gb)`` a head (``gamma``
    (d,), the gain every head's norm shares); ``W_out``.  Returns
    (b, L, C).

    The mixer keeps its input alone between the passes: every array in
    it, the projections' results among them, is computed again in the
    backward pass (``jax.checkpoint``).  Kept, a layer's would hold 1.4
    GB at Kimi Linear's widths over 8192 positions, more than the chip
    has room for beside four such layers."""
    H, d = int(heads), int(head_dim)
    bs, L, _ = x.shape
    f32 = jnp.float32

    def mixer(x, qkv_weight, f_a_weight, f_b_weight, b_weight, g_a_weight,
              g_b_weight, conv_weight, A_log, dt_bias, gamma, out_weight):
        with jax.named_scope("mx.kda.in_proj"):
            qkv = jnp.matmul(x, qkv_weight.T)
            f = jnp.matmul(jnp.matmul(x, f_a_weight.T), f_b_weight.T)
            b = jnp.matmul(x, b_weight.T)
            z = jnp.matmul(jnp.matmul(x, g_a_weight.T), g_b_weight.T)
        with jax.named_scope("mx.kda.conv"):
            u = _conv_silu(qkv, conv_weight, None, 0)
        with jax.named_scope("mx.kda.core"):
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(jnp.repeat(A_log.astype(f32), d)) * jax.nn.softplus(
                f.astype(f32) + dt_bias.astype(f32))
            o = _run(u, g.reshape(bs, L, H, d), beta, (H, d, d), chunk,
                     True).astype(u.dtype)
        with jax.named_scope("mx.kda.gate_norm"):
            y = _gate_norm(o, z, jnp.tile(gamma, H), H, float(eps), 0, True,
                           "sigmoid")
        with jax.named_scope("mx.kda.out_proj"):
            return jnp.matmul(y, out_weight.T)

    return jax.checkpoint(
        mixer, policy=jax.checkpoint_policies.nothing_saveable)(
        x, qkv_weight, f_a_weight, f_b_weight, b_weight, g_a_weight,
        g_b_weight, conv_weight, A_log, dt_bias, gamma, out_weight)
