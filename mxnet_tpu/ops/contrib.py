"""Contrib operators — notably the transformer MultiHeadAttention kernels.

Reference: ``src/operator/contrib/transformer.cc``
(``_contrib_interleaved_matmul_selfatt_qk`` etc. — the MHA kernels named in
the north star), plus ROIAlign, AdaptiveAvgPooling2D, BilinearResize2D,
index ops (SURVEY.md 2.1).

TPU-native: the interleaved-matmul ops are thin einsum reshapes that XLA
maps onto batched MXU GEMMs; a fused Pallas flash-attention path backs the
same API for long sequences (ops/pallas_kernels.py supplies it and
gluon.contrib MultiHeadAttention selects it) — the reference's O(L^2)
materialized-scores semantics are preserved here for parity and for short L.

Layout contract (matches the reference ops):
  self-attention : qkv interleaved (L, B, H*3*D) — per head [q | k | v]
  enc-dec        : q (L_q, B, H*D), kv interleaved (L_kv, B, H*2*D)
  attention maps : (B*H, L_q, L_kv)
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


@register("_contrib_div_sqrt_dim", aliases=["div_sqrt_dim"])
def div_sqrt_dim(data):
    """data / sqrt(last_dim) (reference: transformer.cc DivSqrtDim)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], dtype=data.dtype))


def _split_interleaved(qkv, heads, n):
    """(L, B, H*n*D) -> n tensors of (B*H, L, D)."""
    L, B, HnD = qkv.shape
    D = HnD // (heads * n)
    x = qkv.reshape(L, B, heads, n, D)
    parts = [x[:, :, :, i, :] for i in range(n)]
    # (L, B, H, D) -> (B*H, L, D)
    return [p.transpose(1, 2, 0, 3).reshape(B * heads, L, D) for p in parts]


@register("_contrib_interleaved_matmul_selfatt_qk",
          aliases=["interleaved_matmul_selfatt_qk"])
def interleaved_matmul_selfatt_qk(queries_keys_values, *, heads: int = 1):
    """scores = (Q/sqrt(D)) @ K^T from interleaved qkv
    (reference: transformer.cc InterleavedMatMulSelfAttQK)."""
    q, k, _ = _split_interleaved(queries_keys_values, heads, 3)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], dtype=q.dtype))
    return jnp.einsum("bqd,bkd->bqk", q * scale, k)


@register("_contrib_interleaved_matmul_selfatt_valatt", num_inputs=2,
          aliases=["interleaved_matmul_selfatt_valatt"])
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, *,
                                      heads: int = 1):
    """out = att @ V, back to (L, B, H*D) (reference:
    InterleavedMatMulSelfAttValAtt)."""
    L, B, _ = queries_keys_values.shape
    _, _, v = _split_interleaved(queries_keys_values, heads, 3)
    out = jnp.einsum("bqk,bkd->bqd", attention, v)    # (B*H, L, D)
    D = v.shape[-1]
    return out.reshape(B, heads, L, D).transpose(2, 0, 1, 3).reshape(
        L, B, heads * D)


@register("_contrib_interleaved_matmul_encdec_qk", num_inputs=2,
          aliases=["interleaved_matmul_encdec_qk"])
def interleaved_matmul_encdec_qk(queries, keys_values, *, heads: int = 1):
    Lq, B, HD = queries.shape
    D = HD // heads
    q = queries.reshape(Lq, B, heads, D).transpose(1, 2, 0, 3).reshape(
        B * heads, Lq, D)
    k, _ = _split_interleaved(keys_values, heads, 2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, dtype=q.dtype))
    return jnp.einsum("bqd,bkd->bqk", q * scale, k)


@register("_contrib_interleaved_matmul_encdec_valatt", num_inputs=2,
          aliases=["interleaved_matmul_encdec_valatt"])
def interleaved_matmul_encdec_valatt(keys_values, attention, *,
                                     heads: int = 1):
    Lkv, B, _ = keys_values.shape
    _, v = _split_interleaved(keys_values, heads, 2)
    out = jnp.einsum("bqk,bkd->bqd", attention, v)
    D = v.shape[-1]
    Lq = out.shape[1]
    return out.reshape(B, heads, Lq, D).transpose(2, 0, 1, 3).reshape(
        Lq, B, heads * D)


@register("_contrib_AdaptiveAvgPooling2D",
          aliases=["AdaptiveAvgPooling2D"])
def adaptive_avg_pooling2d(data, *, output_size=()):
    """reference: contrib/adaptive_avg_pooling.cc."""
    if not output_size:
        oh = ow = 1
    elif isinstance(output_size, int):
        oh = ow = output_size
    else:
        oh, ow = (output_size[0], output_size[-1])
    n, c, h, w = data.shape
    if h % oh == 0 and w % ow == 0:
        x = data.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    return jax.image.resize(data, (n, c, oh, ow), method="linear")


@register("_contrib_BilinearResize2D", aliases=["BilinearResize2D"])
def bilinear_resize2d(data, *, height: int = 1, width: int = 1,
                      scale_height=None, scale_width=None,
                      mode: str = "size", align_corners: bool = True):
    """reference: contrib/bilinear_resize.cc.  The reference default is
    align_corners=True (source/dest corners map exactly); jax.image's
    "linear" is half-pixel (align_corners=False), so the True path is an
    explicit gather-lerp."""
    n, c, h, w = data.shape
    if scale_height is not None:
        height = int(h * scale_height)
        width = int(w * scale_width)
    if not align_corners:
        return jax.image.resize(data, (n, c, height, width),
                                method="linear")
    # align-corners mapping degenerates per-axis at size 1 (0/0): that
    # axis samples its center, the other keeps corner alignment
    ys = (jnp.linspace(0.0, h - 1.0, height) if height > 1
          else jnp.full((1,), (h - 1) / 2.0))
    xs = (jnp.linspace(0.0, w - 1.0, width) if width > 1
          else jnp.full((1,), (w - 1) / 2.0))
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0).astype(data.dtype)[None, None, :, None]
    wx = (xs - x0).astype(data.dtype)
    rows = data[:, :, y0, :] * (1 - wy) + data[:, :, y1, :] * wy
    return rows[:, :, :, x0] * (1 - wx) + rows[:, :, :, x1] * wx


@register("_contrib_ROIAlign", num_inputs=2, aliases=["ROIAlign"])
def roi_align(data, rois, *, pooled_size=(), spatial_scale: float = 1.0,
              sample_ratio: int = -1, position_sensitive: bool = False,
              aligned: bool = False):
    """ROIAlign (reference: contrib/roi_align.cc).  Bilinear sampling on a
    regular grid inside each ROI; rois = (R, 5) [batch_idx, x1, y1, x2, y2]."""
    ph, pw = pooled_size
    n, c, h, w = data.shape
    R = rois.shape[0]
    offset = 0.5 if aligned else 0.0
    batch_idx = rois[:, 0].astype(jnp.int32)
    x1 = rois[:, 1] * spatial_scale - offset
    y1 = rois[:, 2] * spatial_scale - offset
    x2 = rois[:, 3] * spatial_scale - offset
    y2 = rois[:, 4] * spatial_scale - offset
    roi_w = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
    roi_h = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
    s = sample_ratio if sample_ratio > 0 else 2
    # sample grid: (R, ph*s, pw*s)
    ys = y1[:, None] + roi_h[:, None] * (
        (jnp.arange(ph * s) + 0.5) / (ph * s))[None, :]
    xs = x1[:, None] + roi_w[:, None] * (
        (jnp.arange(pw * s) + 0.5) / (pw * s))[None, :]

    def bilinear(img, yy, xx):
        y0 = jnp.clip(jnp.floor(yy).astype(jnp.int32), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xx).astype(jnp.int32), 0, w - 1)
        y1_, x1_ = jnp.clip(y0 + 1, 0, h - 1), jnp.clip(x0 + 1, 0, w - 1)
        wy, wx = yy - y0, xx - x0
        v = (img[:, y0[:, None], x0[None, :]] * ((1 - wy)[:, None] * (1 - wx)[None, :])
             + img[:, y0[:, None], x1_[None, :]] * ((1 - wy)[:, None] * wx[None, :])
             + img[:, y1_[:, None], x0[None, :]] * (wy[:, None] * (1 - wx)[None, :])
             + img[:, y1_[:, None], x1_[None, :]] * (wy[:, None] * wx[None, :]))
        return v  # (c, ph*s, pw*s)

    def per_roi(r):
        img = data[batch_idx[r]]
        v = bilinear(img, ys[r], xs[r])
        v = v.reshape(c, ph, s, pw, s).mean(axis=(2, 4))
        return v

    return jax.vmap(per_roi)(jnp.arange(R))


@register("_contrib_index_copy", num_inputs=3, aliases=["index_copy"])
def index_copy(old, index, new):
    return old.at[index.astype(jnp.int32)].set(new)


@register("_contrib_index_array", aliases=["index_array"])
def index_array(data, *, axes=None):
    shape = data.shape
    if axes is None:
        axes = tuple(range(len(shape)))
    else:
        axes = tuple(axes)
    grids = jnp.meshgrid(*[jnp.arange(shape[a]) for a in axes], indexing="ij")
    full = jnp.stack(jnp.meshgrid(
        *[jnp.arange(s) for s in shape], indexing="ij"), axis=-1)
    return full[..., list(axes)].astype(jnp.int64)


_SQRT_HALF = math.sqrt(0.5)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


@jax.custom_vjp
def _gelu_erf(x):
    return jax.nn.gelu(x, approximate=False)


def _gelu_erf_fwd(x):
    # Under autodiff alone XLA keeps only x and expands erfc again in
    # every fusion that reads gelu(x) or its derivative: in a
    # transformer FFN three times a layer, twice on a matmul's operand
    # side (PERF.md, PR 32).  Here the expansion runs once, where x is
    # produced; the barrier keeps its two results one producer's, and
    # the backward's float32 read of y keeps XLA from narrowing y for
    # the matmuls that follow, which would split that producer again.
    # The density is written from erfc's own argument, so that it
    # shares the exponential of erfc's expansion.
    z = -x * _SQRT_HALF
    cdf = 0.5 * lax.erfc(z)
    y = x * cdf
    slope = cdf + x * (jnp.exp(-(z * z)) * _INV_SQRT_2PI)
    y, rest = lax.optimization_barrier((y, slope - y))
    return y, (y, rest)


def _gelu_erf_bwd(res, g):
    y, rest = res
    return (g * (y + rest),)


_gelu_erf.defvjp(_gelu_erf_fwd, _gelu_erf_bwd)


@register("_contrib_gelu_erf", aliases=["gelu"])
def gelu_erf(data):
    """Exact GELU, ``x * Phi(x)``.  Not differentiated it is
    ``jax.nn.gelu(x, approximate=False)``; differentiated, ``erfc`` is
    evaluated once an element and ``dy/dx = Phi(x) + x * phi(x)`` is
    saved, not recomputed."""
    return _gelu_erf(data)


@register("_contrib_gelu_tanh", aliases=["gelu_tanh"])
def gelu_tanh(data):
    return jax.nn.gelu(data, approximate=True)


@register("smooth_l1")
def smooth_l1(data, *, scalar: float = 1.0):
    """reference: tensor/elemwise_binary_scalar_op_extended.cc smooth_l1."""
    s2 = scalar * scalar
    absd = jnp.abs(data)
    return jnp.where(absd < 1.0 / s2, 0.5 * s2 * jnp.square(data),
                     absd - 0.5 / s2)


# ---------------------------------------------------------------------------
# the present-day decoder block's pieces: RMSNorm and rotary positions
# ---------------------------------------------------------------------------
@register("_contrib_rms_norm", num_inputs=2, aliases=["rms_norm"])
def rms_norm(data, gamma, *, eps: float = 1e-6):
    """``x * rsqrt(mean(x^2, -1) + eps) * gamma``, the statistics in
    float32."""
    x = data.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


def rope_inv_freq(head_dim, theta, yarn_factor=0.0, yarn_original_max=0,
                  yarn_beta_fast=32.0, yarn_beta_slow=1.0):
    """The rotary frequencies ``theta^(-2i/head_dim)``, i < head_dim/2
    (float64).  With ``yarn_factor`` the static YaRN blend (Peng et al.
    2023, as the ``transformers`` library computes it): each frequency
    is interpolated (divided by the factor) or kept, by a linear ramp
    between the dimensions that turn ``yarn_beta_fast`` and
    ``yarn_beta_slow`` times over ``yarn_original_max`` positions."""
    half = head_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    if not yarn_factor:
        return inv

    def turns_at(rotations):
        return (head_dim * math.log(yarn_original_max
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(yarn_beta_fast)), 0)
    high = min(math.ceil(turns_at(yarn_beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inv / yarn_factor * ramp + inv * (1.0 - ramp)


@register("_contrib_rope", num_inputs=1, aliases=["rope"])
def rope(data, *, theta: float = 10000.0, yarn_factor: float = 0.0,
         yarn_original_max: int = 0, yarn_beta_fast: float = 32.0,
         yarn_beta_slow: float = 1.0, attention_factor: float = 1.0):
    """Rotary positions over the whole head dimension of (B, L, H, D):
    position ``t`` of axis 1 rotates the pairs (i, i + D/2) by
    ``t * inv_freq_i`` (the half-split layout of ``transformers``);
    ``attention_factor`` scales cos and sin (YaRN)."""
    with jax.named_scope("mx.rope"):
        D = data.shape[-1]
        inv = jnp.asarray(rope_inv_freq(
            D, theta, yarn_factor, yarn_original_max, yarn_beta_fast,
            yarn_beta_slow), jnp.float32)
        angle = jnp.arange(data.shape[1], dtype=jnp.float32)[:, None] * inv
        cos = (jnp.cos(angle) * attention_factor)[None, :, None, :]
        sin = (jnp.sin(angle) * attention_factor)[None, :, None, :]
        x = data.astype(jnp.float32)
        a, b = x[..., :D // 2], x[..., D // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
        return out.astype(data.dtype)
