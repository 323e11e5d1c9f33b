"""The gated short convolution: what an LFM2 "conv" operator computes
between its two projections (Liquid AI's LFM2 family, ``lfm2`` /
``lfm2_moe`` in the ``transformers`` library).

The in-projection gives three gates' worth of channels, ``[B | C | u]``.
``B`` gates the input, a causal depthwise convolution of a few taps
mixes neighbouring positions, ``C`` gates the output:

    v = B * u
    c[t, ch] = sum_j w[ch, j] * v[t - (K - 1) + j, ch]     (zeros before 0)
    out = C * c

No bias and no activation (``ops.ssm.ssm_conv``, the Mamba-2 mixer's
convolution, has both and no gates).  Everything is elementwise but the
K shifted reads, so bytes bound it: one ``custom_vjp`` keeps only the
in-projection's result between the passes and writes the backward out,
so that XLA sees one expression a pass (forward: read ``data``, write
``out``; backward: read ``data`` and the cotangent, write the gradient
in ``data`` in its three parts' places, and reduce the taps' gradient).

Ops:
  ``gated_short_conv`` — (b, L, 3C) [B | C | u], taps (C, K) -> (b, L, C)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

__all__ = ["gated_short_conv"]


def _shifted(a, by):
    """``a`` (b, L, C) moved ``by`` positions later along axis 1 (earlier
    if negative), zeros where nothing arrives.  One ``lax.pad`` with a
    negative edge: XLA fuses it into its reader (a concatenate with a
    block of zeros, or a roll under a mask, it writes out: tried in the
    TPU compiler)."""
    by = max(-a.shape[1], min(by, a.shape[1]))
    return lax.pad(a, jnp.zeros((), a.dtype),
                   ((0, 0, 0), (by, -by, 0), (0, 0, 0)))


def _parts(data):
    C = data.shape[-1] // 3
    return data[..., :C], data[..., C:2 * C], data[..., 2 * C:]


def _conv(v, weight):
    """Tap j reads K - 1 - j positions back."""
    K = weight.shape[1]
    return sum(_shifted(v, K - 1 - j) * weight[:, j] for j in range(K))


@jax.custom_vjp
def _gated_conv(data, weight):
    B, C, u = _parts(data)
    return C * _conv(B * u, weight)


def _gated_conv_fwd(data, weight):
    return _gated_conv(data, weight), (data, weight)


def _gated_conv_bwd(res, g):
    data, weight = res
    K = weight.shape[1]
    B, C, u = _parts(data)
    v = B * u
    dc = g * C
    # the convolution's transpose: tap j of position t + (K - 1 - j)
    dv = sum(_shifted(dc, j - (K - 1)) * weight[:, j] for j in range(K))
    d_data = jnp.concatenate([dv * u, g * _conv(v, weight), dv * B], -1)
    d_weight = jnp.stack(
        [jnp.sum(dc * _shifted(v, K - 1 - j), axis=(0, 1),
                 dtype=jnp.float32) for j in range(K)], axis=1)
    return d_data, d_weight.astype(weight.dtype)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register("_contrib_gated_short_conv", num_inputs=2,
          aliases=["gated_short_conv"])
def gated_short_conv(data, weight):
    """``data`` (b, L, 3C) holds [B | C | u], ``weight`` (C, K) the
    depthwise taps.  Returns ``C * conv(B * u)`` (b, L, C) with
    ``conv(v)[t, c] = sum_j weight[c, j] * v[t - (K - 1) + j, c]``, zeros
    before the row's start; no bias, no activation."""
    if data.shape[-1] != 3 * weight.shape[0]:
        from ..base import MXNetError
        raise MXNetError(
            f"gated_short_conv: data's last axis {data.shape[-1]} is not "
            f"[B | C | u] of {weight.shape[0]} channels each")
    with jax.named_scope("mx.sconv.conv"):
        return _gated_conv(data, weight.astype(data.dtype))
