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

``gated_delta_rule`` is one ``custom_vjp`` that keeps its five operands
between the passes and nothing else: the backward pass computes the
chunks again (behind an ``optimization_barrier``, so that XLA does not
merge them with the forward's and keep the forward's states alive) and
differentiates that.  So one layer's per-chunk states exist only inside
its own backward pass.

``gdn_mixer`` is the mixer between its projections, from the
in-projection's [q | k | v | z] result and the (b | a) one: the causal
depthwise convolution without a bias and its silu over [q | k | v]
(``ssm._conv_silu``, reading its columns in place), the L2 norms, beta
and g, the rule, then the norm of each value head's output times one
gain shared by the heads, times ``silu(z)`` (``ssm._gate_norm`` with the
norm BEFORE the gate, reading z in place).

Ops:
  ``gated_delta_rule`` — q, k (b, L, Hk, dk), v (b, L, H, dv), g, beta
                         (b, L, H) -> o (b, L, H, dv)
  ``gdn_mixer``        — the mixer between its projections
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from .ssm import _conv_silu, _gate_norm, _whole_chunks

__all__ = ["gated_delta_rule", "gdn_mixer"]

_HIGHEST = lax.Precision.HIGHEST


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


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, Q):
    return _chunked(q, k, v, g, beta, Q)


def _rule_fwd(q, k, v, g, beta, Q):
    return _chunked(q, k, v, g, beta, Q), (q, k, v, g, beta)


def _rule_bwd(Q, res, do):
    res = lax.optimization_barrier(res)
    return jax.vjp(lambda *a: _chunked(*a, Q), *res)[1](do)


_rule.defvjp(_rule_fwd, _rule_bwd)


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
    R, Q = H // Hk, int(chunk)
    f32 = jnp.float32
    o = _rule(*(_whole_chunks(a.astype(f32), Q) for a in (
        q, k, v.reshape(b, L, Hk, R, dv), g.reshape(b, L, Hk, R),
        beta.reshape(b, L, Hk, R))), Q)
    return o[:, :L].reshape(b, L, H, dv).astype(v.dtype)


def _l2_normed(x, eps=1e-6):
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
        q = _l2_normed(qkv[..., :Hk * dk].astype(f32).reshape(b, L, Hk, dk))
        k = _l2_normed(qkv[..., Hk * dk:2 * Hk * dk].astype(f32)
                       .reshape(b, L, Hk, dk))
        v = qkv[..., 2 * Hk * dk:].reshape(b, L, H, dv)
        ba = ba.astype(f32)
        beta = jax.nn.sigmoid(ba[..., :H])
        g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(
            ba[..., H:] + dt_bias.astype(f32))
        o = gated_delta_rule(q * dk ** -0.5, k, v, g, beta, chunk=chunk)
    with jax.named_scope("mx.gdn.gate_norm"):
        return _gate_norm(o.reshape(b, L, H * dv), qkvz,
                          jnp.tile(gamma, H), H, float(eps), width, True)
