"""State-space operators: the pieces of a Mamba-2 mixer (Dao and Gu
2024, "Transformers are SSMs") between its two projections.

- ``mx.ssm.conv``: the causal depthwise convolution over the sequence
  and its silu;
- ``mx.ssm.scan``: the selective scan.  A head's state ``h`` (P, N)
  follows ``h_t = exp(delta_t A) h_{t-1} + delta_t x_t B_t^T`` from
  ``h_0 = 0`` and gives ``y_t = h_t C_t + D x_t``, with
  ``delta = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head and
  ``B``, ``C`` shared by the heads of a group.  Computed in chunks of
  ``chunk`` positions: inside a chunk one masked product
  ``(C B^T * decay) (delta x)``, between chunks the carried state, so
  nothing of size L x L or L x heads x P x N exists;
- ``mx.ssm.gate_norm``: ``y * silu(z)``, then RMS norm over each group
  of channels, times a gain.

Decays are ``exp`` of differences of a cumulative sum of ``delta A``
(never a quotient of two exponentials, which underflows to 0/0 where a
chunk's decay passes float32's range); decays, state and sums are
float32, the products take the backend's default precision.

Which code runs the scan is decided from the shapes alone
(``pallas_kernels.ssm_scan_tiles``).  Where chunk and state size are
multiples of 128, a group's heads fill whole 128-lane blocks (R x P a
multiple of 128 with P a power of two up to 128 or a multiple of it,
two heads a group or more), x's width is a multiple of the state size
and a chunk's blocks fit VMEM, it is a pair
of Pallas kernels behind one ``custom_vjp``
(``pallas_kernels.ssm_scan_chunks``): a grid over (row, group, chunk)
with the group's state in VMEM from chunk to chunk, x, B and C read
where the mixer's convolution left them (their columns of its one
(L, H P + 2 G N) result: ``ssm_mixer`` slices nothing out, ``ssm_scan``,
which is handed the three apart, packs them first) and y written as
(L, H P), a head's (chunk, chunk) decay matrix made and consumed in
VMEM, the MXU's operands rounded to bfloat16 where the TPU's default
precision rounds the einsums' below.  Between forward and
backward it keeps its inputs only: the backward pass first sweeps the
states that enter the chunks (state only, no y) and then walks the
chunks last to first with the state's gradient in VMEM, giving the
gradients in x, B, C and the per-head vectors (delta, the running log
decay and its two exponentials); ``softplus``, the running sum, ``A_log``,
``dt_bias`` and ``D`` are ``jnp`` around it, and autodiff's.  Every other
shape runs ``_chunked_scan`` below, in ``jnp``, with autodiff's backward
pass under ``jax.checkpoint``: its inputs are saved, the (chunks, heads,
chunk, chunk) decay matrices are not.

Ops:
  ``ssm_conv``      — (B, L, C), weight (C, K), bias (C,) -> (B, L, C)
  ``ssm_scan``      — x, dt, A_log, B, C, D, dt_bias -> y
  ``ssm_gate_norm`` — y, z, gain -> (..., C)
  ``ssm_mixer``     — the three in a row, from the in-projection's
                      [z | x B C | dt] to what the out-projection reads
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import ssm_scan_chunks, ssm_scan_tiles
from .registry import register

__all__ = ["ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_mixer"]


@register("_contrib_ssm_conv", num_inputs=3, aliases=["ssm_conv"])
def ssm_conv(data, weight, bias):
    """Causal depthwise convolution over axis 1 of (B, L, C), then silu:
    ``out[t, c] = silu(bias[c] + sum_j weight[c, j] *
    data[t - (K - 1) + j, c])`` with zeros before the row's start."""
    with jax.named_scope("mx.ssm.conv"):
        K, L = weight.shape[1], data.shape[1]
        padded = jnp.pad(data, ((0, 0), (K - 1, 0), (0, 0)))
        out = bias + sum(padded[:, j:j + L] * weight[:, j] for j in range(K))
        return jax.nn.silu(out).astype(data.dtype)


def _chunked_scan(x, delta, A, B, C, Q):
    """The scan over whole chunks, float32.  x (b, L, G, R, P) (head
    ``g * R + r`` reads group g), delta (b, L, G, R), A (G, R), B and C
    (b, L, G, N); L a multiple of Q.  Returns y without the ``D x``
    term, x's shape."""
    b, L, G, R, P = x.shape
    nc = L // Q
    x, delta, B, C = (a.reshape((b, nc, Q) + a.shape[2:])
                      for a in (x, delta, B, C))
    # log decay of each position, its running sum inside the chunk
    cum = jnp.cumsum(jnp.moveaxis(delta * A, 2, -1), axis=-1)  # (b,nc,G,R,Q)
    xd = x * delta[..., None]                                # (b,nc,Q,G,R,P)
    # inside a chunk: position t reads s <= t through exp(cum_t - cum_s)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                     # (b,nc,G,R,Q,Q)
    scores = jnp.einsum("bcqgn,bcsgn->bcgqs", C, B)
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", scores[:, :, :, None] * decay,
                   xd)
    # what a chunk adds to the state by its end, and the state carried in
    to_end = jnp.exp(cum[..., -1:] - cum)                    # (b,nc,G,R,Q)
    added = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                       xd * jnp.moveaxis(to_end, -1, 2)[..., None], B)
    whole = jnp.exp(cum[..., -1])                            # (b,nc,G,R)

    def carry(h, chunk):
        keep, new = chunk
        return keep[..., None, None] * h + new, h

    _, entering = lax.scan(
        carry, jnp.zeros_like(added[:, 0]),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                  # (b,nc,G,R,P,N)
    y = y + (jnp.einsum("bcqgn,bcgrpn->bcqgrp", C, entering)
             * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None])
    return y.reshape(b, L, G, R, P)


def _heads_a_group(H, G):
    if H % G:
        from ..base import MXNetError
        raise MXNetError(f"ssm_scan: {G} groups of B and C do not "
                         f"divide {H} heads")
    return H // G


def _whole_chunks(a, Q, axis=1):
    """``a`` with ``axis`` filled with zeros up to a multiple of Q."""
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, -a.shape[axis] % Q)
    return jnp.pad(a, widths) if widths[axis][1] else a


def _kernel_scan(xbc, dt, A_log, D, dt_bias, H, G, N, Q):
    """``ssm_scan`` through the Pallas kernels, for shapes that
    ``ssm_scan_tiles`` takes: ``xbc`` (b, L, .) is [x | B | C] side by
    side, as the mixer's convolution leaves them.  Returns y
    (b, L, H P) float32."""
    with jax.named_scope("mx.ssm.scan"):
        f32 = jnp.float32
        b, L, _ = xbc.shape
        # positions last, so that the per-head vectors stay dense
        # ((L, H) arrays pad every row of 64 to 128 lanes, (L, G, R)
        # ones every 8)
        delta = jax.nn.softplus(jnp.swapaxes(dt.astype(f32), 1, 2)
                                + dt_bias.astype(f32)[:, None])
        return ssm_scan_chunks(
            _whole_chunks(xbc.astype(f32), Q),
            _whole_chunks(delta.reshape(b, G, H // G, L), Q, 3),
            -jnp.exp(A_log.astype(f32)).reshape(G, H // G),
            D.astype(f32).reshape(G, H // G), N, Q)[:, :L]


@register("_contrib_ssm_scan", num_inputs=7, aliases=["ssm_scan"])
def ssm_scan(x, dt, A_log, B, C, D, dt_bias, *, chunk: int = 128):
    """The selective scan of a Mamba-2 mixer.  x (b, L, H, P), dt
    (b, L, H), A_log, D and dt_bias (H,), B and C (b, L, G, N) with G
    dividing H (head ``h`` reads group ``h // (H // G)``).  Returns y
    (b, L, H, P) in x's dtype; the module's docstring has the
    recurrence.  Any L: the last chunk is filled with positions that
    neither decay nor add to the state."""
    b, L, H, P = x.shape
    G, N = B.shape[2:]
    R, Q = _heads_a_group(H, G), int(chunk)
    f32 = jnp.float32
    if ssm_scan_tiles(Q, G, R, P, N):
        xbc = jnp.concatenate([a.astype(f32).reshape(b, L, -1)
                               for a in (x, B, C)], axis=-1)
        y = _kernel_scan(xbc, dt, A_log, D, dt_bias, H, G, N, Q)
        return y.reshape(b, L, H, P).astype(x.dtype)
    with jax.named_scope("mx.ssm.scan"):
        xf = x.astype(f32).reshape(b, L, G, R, P)
        delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        A = -jnp.exp(A_log.astype(f32)).reshape(G, R)
        xp, delta, Bp, Cp = (_whole_chunks(a, Q) for a in (
            xf, delta.reshape(b, L, G, R), B.astype(f32), C.astype(f32)))
        y = jax.checkpoint(_chunked_scan, static_argnums=(5,))(
            xp, delta, A, Bp, Cp, Q)
        y = y[:, :L] + D.astype(f32).reshape(G, R, 1) * xf
        return y.reshape(b, L, H, P).astype(x.dtype)


@register("_contrib_ssm_gate_norm", num_inputs=3,
          aliases=["ssm_gate_norm"])
def ssm_gate_norm(data, gate, gamma, *, groups: int = 1, eps: float = 1e-5):
    """``data * silu(gate)``, then RMS norm over each of ``groups``
    equal groups of the last axis (the gate comes BEFORE the norm),
    times ``gamma``; the statistics in float32."""
    with jax.named_scope("mx.ssm.gate_norm"):
        v = data.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        g = v.reshape(v.shape[:-1] + (int(groups), -1))
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return (g.reshape(v.shape) * gamma.astype(jnp.float32)).astype(
            data.dtype)


@register("_contrib_ssm_mixer", num_inputs=7, aliases=["ssm_mixer"])
def ssm_mixer(data, conv_weight, conv_bias, dt_bias, A_log, D, gamma, *,
              num_heads: int, head_dim: int, n_groups: int,
              state_size: int, chunk: int = 128, eps: float = 1e-5):
    """A Mamba-2 mixer between its projections.  ``data`` (b, L, .) is
    [z | x B C | dt] of widths ``inner | inner + 2 * n_groups *
    state_size | num_heads``, ``inner = num_heads * head_dim``: x, B and
    C pass ``ssm_conv`` (weight (., K), bias) and silu, then
    ``ssm_scan``, then ``ssm_gate_norm`` with z over ``n_groups``
    groups.  Returns (b, L, inner)."""
    H, P, G, N = int(num_heads), int(head_dim), int(n_groups), int(state_size)
    inner = H * P
    b, L, _ = data.shape
    z, xbc, dt = jnp.split(data, (inner, data.shape[-1] - H), axis=-1)
    xbc = ssm_conv(xbc, conv_weight, conv_bias)
    if ssm_scan_tiles(int(chunk), G, _heads_a_group(H, G), P, N):
        # the kernels read x, B and C where the convolution left them
        y = _kernel_scan(xbc, dt, A_log, D, dt_bias, H, G, N,
                         int(chunk)).astype(xbc.dtype)
    else:
        x, B, C = jnp.split(xbc, (inner, inner + G * N), axis=-1)
        y = ssm_scan(x.reshape(b, L, H, P), dt, A_log,
                     B.reshape(b, L, G, N), C.reshape(b, L, G, N), D,
                     dt_bias, chunk=chunk).reshape(b, L, inner)
    return ssm_gate_norm(y, z, gamma, groups=G, eps=eps)
