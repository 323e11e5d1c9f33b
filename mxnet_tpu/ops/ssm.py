"""State-space operators: the pieces of a Mamba-2 mixer (Dao and Gu
2024, "Transformers are SSMs") between its two projections.

- ``mx.ssm.conv``: the causal depthwise convolution over the sequence
  and its silu, ``silu(bias + sum_j w[:, j] * x[t - (K - 1) + j])``;
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

The convolution and the gate-and-norm are elementwise but for K shifted
reads and a sum a group, so bytes bound them.  Each is one
``custom_vjp`` that keeps its operands between the passes and nothing
else (no pre-activation, no gated product, no statistic: the backward
pass computes them again from the operands) and writes the backward
out, the taps', bias's and gain's sums in float32:

    dpre = g * silu'(pre)         d_x[t] = sum_j w[:, j] * dpre[t + (K - 1) - j]
    d_w[:, j] = sum_t dpre[t] * x[t - (K - 1) + j]       d_bias = sum_t dpre

    v = y * silu(z), r = rsqrt(mean_group(v^2) + eps), out = v * r * gain
    dv = r * (g * gain) - v * r^3 * mean_group(g * gain * v)
    dy = dv * silu(z)    dz = dv * y * silu'(z)    d_gain = sum_rows g * v * r

Two variants serve Qwen3-Next's Gated DeltaNet (``ops/deltanet.py``),
which calls the two ``custom_vjp``s itself under its own scopes: the
convolution without a bias (``bias`` None), and the norm BEFORE the gate,
``out = norm(y) * gain * silu(z)`` (``before``), whose backward is the
same with ``a = g * gain * silu(z)`` in the place of ``g * gain`` and y
in the place of v:

    r = rsqrt(mean_group(y^2) + eps)
    dy = r * a - y * r^3 * mean_group(a * y)    dz = g * gain * y * r * silu'(z)

Kimi Linear's delta-rule mixer gates its normed output by ``sigmoid(z)``
(``gate="sigmoid"``): the same passes with ``sigmoid`` and ``sigmoid' =
s (1 - s)`` in the place of ``silu`` and ``silu'``.

Both take their operand as columns ``lo`` on of a wider array: the mixer
hands them the in-projection's one [z | x B C | dt] result and no slice
of it (a slice handed to an operator is written out in HBM; the
gradient comes back as the operator's columns with zeros beside them,
which XLA reads into the in-projection's gradient matmuls as it read
the split's).  Which code runs a pass is decided from the shapes alone
(``pallas_kernels.ssm_conv_tiles``, ``ssm_norm_tiles``): float32 blocks
of (rows, 128 k) columns where L is whole blocks of eight rows, the
channels (a group's, for the norm) and ``lo`` whole 128-lane blocks and
the taps at most nine are ONE Pallas pass forward and ONE backward
(``pallas_kernels.ssm_conv_pass``, ``ssm_norm_pass`` and their
``_grads``; XLA cannot keep a row's group statistic and its use, or a
block's pre-activation gradient and its transpose, in one pass).  Every
other shape runs the same expressions in ``jnp`` inside the same
``custom_vjp``: the shifts one ``lax.pad`` each with a negative edge
(``shortconv._shifted``), the group statistic by static column slices
and spread over its channels by selects on the channel's index (a
reshape to (groups, C / groups) retiles the array on the TPU, a repeat
or a concatenate of broadcasts is written out).

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

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import (
    _gate_slope, _gate_value, _interpret, ssm_conv_pass, ssm_conv_pass_grads,
    ssm_conv_tiles, ssm_norm_pass, ssm_norm_pass_grads, ssm_norm_tiles,
    ssm_scan_chunks, ssm_scan_tiles)
from .registry import register
from .shortconv import _shifted

__all__ = ["ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_mixer"]


def _columns(src, lo, width):
    """Columns ``lo`` to ``lo + width`` of ``src``'s last axis."""
    return lax.slice_in_dim(src, lo, lo + width, axis=-1)


def _in_columns(d, src, lo):
    """``_columns``'s transpose: ``d`` at columns ``lo`` on of an array
    like ``src``, zeros beside it."""
    after = src.shape[-1] - lo - d.shape[-1]
    return lax.pad(d.astype(src.dtype), jnp.zeros((), src.dtype),
                   [(0, 0, 0)] * (d.ndim - 1) + [(lo, after, 0)])


def _taps(x, weight):
    """Tap j reads K - 1 - j positions back."""
    K = weight.shape[1]
    return sum(_shifted(x, K - 1 - j) * weight[:, j] for j in range(K))


def _conv_kernels(src, weight, lo):
    return src.ndim == 3 and ssm_conv_tiles(src.shape[1], *weight.shape, lo)


def _bias_or_zeros(bias, C):
    """The bias in float32, zeros where the convolution has none."""
    return (jnp.zeros((C,), jnp.float32) if bias is None
            else bias.astype(jnp.float32))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(src, weight, bias, lo):
    """``silu(bias + conv)`` of the ``weight.shape[0]`` channels that
    start at column ``lo`` of ``src``; ``bias`` None: no bias."""
    f32 = jnp.float32
    C = weight.shape[0]
    if _conv_kernels(src, weight, lo):
        # mxlint: disable=recompile-churn (a column and a bool)
        out = ssm_conv_pass(src.astype(f32), weight.astype(f32).T,
                            _bias_or_zeros(bias, C)[None], lo,
                            _interpret(None))
    else:
        pre = _taps(_columns(src, lo, C).astype(f32), weight.astype(f32))
        if bias is not None:
            pre = bias.astype(f32) + pre
        out = jax.nn.silu(pre)
    return out.astype(src.dtype)


def _conv_silu_fwd(src, weight, bias, lo):
    return _conv_silu(src, weight, bias, lo), (src, weight, bias)


def _conv_silu_bwd(lo, res, g):
    src, weight, bias = res
    f32 = jnp.float32
    C, K = weight.shape
    w, g = weight.astype(f32), g.astype(f32)
    if _conv_kernels(src, weight, lo):
        # mxlint: disable=recompile-churn (a column and a bool)
        d_x, d_weight, d_bias = ssm_conv_pass_grads(
            src.astype(f32), w.T, _bias_or_zeros(bias, C)[None], g, lo,
            _interpret(None))
        d_weight = d_weight.T
    else:
        x = _columns(src, lo, C).astype(f32)
        pre = _taps(x, w)
        if bias is not None:
            pre = bias.astype(f32) + pre
        s = jax.nn.sigmoid(pre)
        dpre = g * (s * (1.0 + pre * (1.0 - s)))
        # the convolution's transpose: tap j of position t + (K - 1 - j)
        d_x = sum(_shifted(dpre, j - (K - 1)) * w[:, j] for j in range(K))
        d_weight = jnp.stack(
            [jnp.sum(dpre * _shifted(x, K - 1 - j), axis=(0, 1))
             for j in range(K)], axis=1)
        d_bias = jnp.sum(dpre, axis=(0, 1))
    return (_in_columns(d_x, src, lo), d_weight.astype(weight.dtype),
            None if bias is None else d_bias.astype(bias.dtype))


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@register("_contrib_ssm_conv", num_inputs=3, aliases=["ssm_conv"])
def ssm_conv(data, weight, bias):
    """Causal depthwise convolution over axis 1 of (B, L, C), then silu:
    ``out[t, c] = silu(bias[c] + sum_j weight[c, j] *
    data[t - (K - 1) + j, c])`` with zeros before the row's start,
    computed in float32.  One ``custom_vjp``: its three operands are all
    it keeps for the backward pass, which computes the pre-activation
    again (the module's docstring has the gradients)."""
    with jax.named_scope("mx.ssm.conv"):
        return _conv_silu(data, weight, bias, 0)


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


def _group_sums(a, groups):
    """The sums of ``a`` (..., C) over each of ``groups`` equal runs of
    the last axis: a list of ``groups`` arrays (..., 1)."""
    n = a.shape[-1] // groups
    return [jnp.sum(a[..., i * n:(i + 1) * n], axis=-1, keepdims=True)
            for i in range(groups)]


def _each_channel(stats, channels):
    """A group's number, one (..., 1) array a group, at every channel
    of the group: (..., channels).  A chain of selects on the channel's
    index: elementwise, so it fuses into whatever reads it (a repeat or
    a concatenate of broadcasts XLA writes out)."""
    n = channels // len(stats)
    col = lax.broadcasted_iota(jnp.int32, (channels,), 0)
    out = stats[-1]
    for i in range(len(stats) - 2, -1, -1):
        out = jnp.where(col < (i + 1) * n, stats[i], out)
    return jnp.broadcast_to(out, stats[0].shape[:-1] + (channels,))


def _gated(y, gate, groups, eps, before=False, act="silu"):
    """The gate in float32, its sigmoid, ``v = y * act(gate)`` and the
    reciprocal root of the mean square a group (a list, a group each) of
    v, or of y itself where the norm comes ``before`` the gate."""
    z = gate.astype(jnp.float32)
    s = jax.nn.sigmoid(z)
    v = y * z * s if act == "silu" else y * s
    n = v.shape[-1] // groups
    normed = y if before else v
    return z, s, v, [lax.rsqrt(a / n + eps)
                     for a in _group_sums(normed * normed, groups)]


def _norm_kernels(data, groups, lo):
    return data.ndim == 3 and ssm_norm_tiles(*data.shape[1:], groups, lo)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _gate_norm(data, gate_src, gamma, groups, eps, lo, before=False,
               gate="silu"):
    """The gate is the ``data.shape[-1]`` channels that start at column
    ``lo`` of ``gate_src``; ``before``: the norm comes before the gate,
    ``norm(data) * gamma * act(gate)``; ``gate``: ``act``, "silu" or
    "sigmoid"."""
    f32 = jnp.float32
    C = data.shape[-1]
    if _norm_kernels(data, groups, lo):
        # mxlint: disable=recompile-churn (sizes, a bool and a name)
        out = ssm_norm_pass(data.astype(f32), gate_src.astype(f32),
                            gamma.astype(f32)[None], groups, eps, lo,
                            _interpret(None), before, gate)
    else:
        _, _, v, r = _gated(data.astype(f32), _columns(gate_src, lo, C),
                            groups, eps, before, gate)
        out = v * _each_channel(r, C) * gamma.astype(f32)
    return out.astype(data.dtype)


def _gate_norm_fwd(data, gate_src, gamma, groups, eps, lo, before=False,
                   gate="silu"):
    return (_gate_norm(data, gate_src, gamma, groups, eps, lo, before, gate),
            (data, gate_src, gamma))


def _gate_norm_bwd(groups, eps, lo, before, gate, res, g):
    data, gate_src, gamma = res
    f32 = jnp.float32
    C = data.shape[-1]
    y, g = data.astype(f32), g.astype(f32)
    if _norm_kernels(data, groups, lo):
        # mxlint: disable=recompile-churn (sizes, a bool and a name)
        dy, dz, d_gamma = ssm_norm_pass_grads(
            y, gate_src.astype(f32), gamma.astype(f32)[None], g, groups, eps,
            lo, _interpret(None), before, gate)
    else:
        z, s, v, r = _gated(y, _columns(gate_src, lo, C), groups, eps,
                            before, gate)
        n = C // groups
        scaled = g * gamma.astype(f32)
        if before:
            # out = y r gamma act(z): the norm's cotangent is scaled act(z)
            a = scaled * _gate_value(z, s, gate)
            back = [t / n * ri * ri * ri
                    for t, ri in zip(_group_sums(a * y, groups), r)]
            r = _each_channel(r, C)
            dy = a * r - y * _each_channel(back, C)
            dz = scaled * y * r * _gate_slope(z, s, gate)
        else:
            back = [t / n * ri * ri * ri
                    for t, ri in zip(_group_sums(scaled * v, groups), r)]
            r = _each_channel(r, C)
            dv = scaled * r - v * _each_channel(back, C)
            dy = dv * _gate_value(z, s, gate)
            dz = dv * y * _gate_slope(z, s, gate)
        d_gamma = jnp.sum(g * v * r, axis=tuple(range(g.ndim - 1)))
    return (dy.astype(data.dtype), _in_columns(dz, gate_src, lo),
            d_gamma.astype(gamma.dtype))


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


@register("_contrib_ssm_gate_norm", num_inputs=3,
          aliases=["ssm_gate_norm"])
def ssm_gate_norm(data, gate, gamma, *, groups: int = 1, eps: float = 1e-5):
    """``data * silu(gate)``, then RMS norm over each of ``groups``
    equal groups of the last axis (the gate comes BEFORE the norm),
    times ``gamma``; the statistics in float32, a group's taken over its
    own columns where they lie (no reshape to (groups, C / groups)).
    One ``custom_vjp``: its three operands are all it keeps for the
    backward pass, which computes the gated product and the statistic
    again (the module's docstring has the gradients)."""
    with jax.named_scope("mx.ssm.gate_norm"):
        return _gate_norm(data, gate, gamma, int(groups), float(eps), 0)


@register("_contrib_ssm_mixer", num_inputs=7, aliases=["ssm_mixer"])
def ssm_mixer(data, conv_weight, conv_bias, dt_bias, A_log, D, gamma, *,
              num_heads: int, head_dim: int, n_groups: int,
              state_size: int, chunk: int = 128, eps: float = 1e-5):
    """A Mamba-2 mixer between its projections.  ``data`` (b, L, .) is
    [z | x B C | dt] of widths ``inner | inner + 2 * n_groups *
    state_size | num_heads``, ``inner = num_heads * head_dim``: x, B and
    C pass ``ssm_conv`` (weight (., K), bias) and silu, then
    ``ssm_scan``, then ``ssm_gate_norm`` with z over ``n_groups``
    groups.  Returns (b, L, inner).  The convolution and the
    gate-and-norm read their columns of ``data`` in place and keep
    ``data`` itself for their backward passes (with the convolution's
    result, which the scan keeps, and the scan's)."""
    H, P, G, N = int(num_heads), int(head_dim), int(n_groups), int(state_size)
    inner = H * P
    b, L, _ = data.shape
    dt = data[..., data.shape[-1] - H:]
    # each operator reads its columns of the in-projection's result where
    # they lie: a slice handed to it would be written out
    with jax.named_scope("mx.ssm.conv"):
        xbc = _conv_silu(data, conv_weight, conv_bias, inner)
    if ssm_scan_tiles(int(chunk), G, _heads_a_group(H, G), P, N):
        # the kernels read x, B and C where the convolution left them
        y = _kernel_scan(xbc, dt, A_log, D, dt_bias, H, G, N,
                         int(chunk)).astype(xbc.dtype)
    else:
        x, B, C = jnp.split(xbc, (inner, inner + G * N), axis=-1)
        y = ssm_scan(x.reshape(b, L, H, P), dt, A_log,
                     B.reshape(b, L, G, N), C.reshape(b, L, G, N), D,
                     dt_bias, chunk=chunk).reshape(b, L, inner)
    with jax.named_scope("mx.ssm.gate_norm"):
        return _gate_norm(y, data, gamma, G, float(eps), 0)
