"""Shared transformer building blocks (GluonNLP-parity layers).

The reference core ships only the fused attention matmul kernels
(``src/operator/contrib/transformer.cc``); the model-level blocks lived in
GluonNLP.  Here both live in-tree: these HybridBlocks call the same
``_contrib_interleaved_matmul_*`` ops, so the attention math hits batched
MXU GEMMs, and under ``hybridize()``/pjit the whole cell fuses into one
XLA program.  For long sequences the same API can route to the Pallas
flash-attention kernel (ops/pallas_kernels.py) via ``use_flash``.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock

__all__ = ["PositionwiseFFN", "MultiHeadSelfAttention",
           "MultiHeadAttention", "TransformerEncoderCell",
           "TransformerDecoderCell", "TransformerDecoderLM",
           "RMSNorm", "GatedFFN", "RotaryGroupedAttention", "Mamba2Mixer",
           "ShortConvMixer", "GatedDeltaNetMixer", "KimiDeltaMixer",
           "LatentAttention", "DecoderCell",
           "paged_lm_params", "paged_prefill", "paged_decode_step",
           "paged_verify", "paged_verify_batch"]


class PositionwiseFFN(HybridBlock):
    """FFN(x) = W2 act(W1 x) with residual+LN (GluonNLP BERT layout)."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 layer_norm_eps=1e-5, pre_norm=False, **kwargs):
        super().__init__(**kwargs)
        self._pre_norm = pre_norm
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, in_units=units,
                                  flatten=False, prefix="ffn_1_")
            self.ffn_2 = nn.Dense(units, in_units=hidden_size,
                                  flatten=False, prefix="ffn_2_")
            self.layer_norm = nn.LayerNorm(in_channels=units,
                                           epsilon=layer_norm_eps)
            self.dropout_layer = nn.Dropout(dropout)
        self._activation = activation

    def _act(self, F, x):
        if self._activation == "gelu":
            return F._contrib_gelu_erf(x)
        if self._activation == "gelu_tanh":
            return F._contrib_gelu_tanh(x)
        return F.Activation(x, act_type=self._activation)

    def hybrid_forward(self, F, x):
        residual = x
        if self._pre_norm:
            with jax.named_scope("mx.norm"):
                x = self.layer_norm(x)
        with jax.named_scope("mx.ffn.dense"):
            out = self.ffn_1(x)
            out = self._act(F, out)
            out = self.ffn_2(out)
        with jax.named_scope("mx.norm"):
            out = self.dropout_layer(out)
            out = out + residual
            if not self._pre_norm:
                out = self.layer_norm(out)
        return out


class MultiHeadSelfAttention(HybridBlock):
    """Self-attention over (L, B, C) via the interleaved qkv kernels
    (reference op: _contrib_interleaved_matmul_selfatt_qk/valatt).

    ``use_flash=True`` routes the qk→softmax→valatt chain to the fused
    Pallas flash-attention kernel (ops/pallas_kernels.py) whenever the
    mask is expressible as key valid-lengths (+ optional causal), i.e.
    ``mask is None``; an explicit additive ``mask`` falls back to the
    dense path.  The flash path has no attention-prob dropout (the score
    matrix never materializes); dropout is applied to the attention
    output instead.

    When to flip it (measured, BERT-large on one v5e chip, r3 kernel —
    bf16 MXU dots + tuned 512-wide blocks): at L=512 flash now edges out
    XLA's fused dense attention on step time (fwd+bwd ~6.4ms vs ~7.1ms
    per layer at B=8) and wins decisively at L=2048 (~6.8ms vs ~11.5ms
    at the same token count).  Flash also keeps its MEMORY advantage:
    at L=2048 the dense path OOMs a 16GB chip even at batch 1 (O(L^2)
    fp32 scores) while flash trains fine.  Default remains dense for
    L<=128-style short sequences; set use_flash=True from L~512 up,
    optionally combined with ring-attention context parallelism
    (parallel/ring_attention.py) beyond a single chip's length budget.
    """

    def __init__(self, units, num_heads, dropout=0.0, use_flash=False,
                 causal=False, window=None, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        if causal and not use_flash:
            raise MXNetError(
                "causal=True requires use_flash=True; on the dense path "
                "pass an explicit additive causal mask instead")
        if window is not None:
            if not (use_flash and causal):
                raise MXNetError(
                    "window (sliding-window attention) requires "
                    "use_flash=True and causal=True")
            if int(window) < 1:
                raise MXNetError(f"window must be >= 1, got {window}")
        self._units = units
        self._heads = num_heads
        self._use_flash = use_flash
        self._causal = causal
        self._window = -1 if window is None else int(window)
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, in_units=units, flatten=False,
                                prefix="qkv_")
            self.out_proj = nn.Dense(units, in_units=units, flatten=False,
                                     prefix="out_proj_")
            self.dropout_layer = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        # x: (L, B, C). qkv: (L, B, 3C) interleaved per head [q|k|v]
        with jax.named_scope("mx.attn.proj"):
            qkv = self.qkv(x)
        if self._use_flash and mask is None:
            if valid_length is None:
                out = F.flash_selfatt_nomask(qkv, heads=self._heads,
                                             causal=self._causal,
                                             window=self._window)
            else:
                out = F.flash_selfatt(qkv, valid_length,
                                      heads=self._heads,
                                      causal=self._causal,
                                      window=self._window)
            with jax.named_scope("mx.attn.proj"):
                return self.out_proj(self.dropout_layer(out))
        if self._window > 0:
            raise MXNetError(
                "window (sliding-window attention) is only honored on "
                "the flash path (mask=None); passing an explicit mask "
                "would silently drop the window — fold the window into "
                "the mask instead")
        if valid_length is not None:
            raise MXNetError(
                "valid_length is only consumed by the flash path "
                "(use_flash=True, mask=None); the dense path needs an "
                "explicit additive mask — it would otherwise be silently "
                "ignored")
        with jax.named_scope("mx.attn.dense"):
            scores = F._contrib_interleaved_matmul_selfatt_qk(
                qkv, heads=self._heads)            # (B*H, L, L)
            if mask is not None:
                scores = scores + mask
            att = F.softmax(scores, axis=-1)
            att = self.dropout_layer(att)
            out = F._contrib_interleaved_matmul_selfatt_valatt(
                qkv, att, heads=self._heads)       # (L, B, C)
        with jax.named_scope("mx.attn.proj"):
            return self.out_proj(out)


class MultiHeadAttention(HybridBlock):
    """Cross-attention: q from decoder (L_q,B,C), kv from memory
    (L_kv,B,C) via the encdec interleaved kernels."""

    def __init__(self, units, num_heads, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._heads = num_heads
        with self.name_scope():
            self.q_proj = nn.Dense(units, in_units=units, flatten=False,
                                   prefix="q_proj_")
            self.kv_proj = nn.Dense(2 * units, in_units=units,
                                    flatten=False, prefix="kv_proj_")
            self.out_proj = nn.Dense(units, in_units=units, flatten=False,
                                     prefix="out_proj_")
            self.dropout_layer = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mem, mask=None):
        q = self.q_proj(x)
        kv = self.kv_proj(mem)
        scores = F._contrib_interleaved_matmul_encdec_qk(
            q, kv, heads=self._heads)          # (B*H, L_q, L_kv)
        if mask is not None:
            scores = scores + mask
        att = F.softmax(scores, axis=-1)
        att = self.dropout_layer(att)
        out = F._contrib_interleaved_matmul_encdec_valatt(
            kv, att, heads=self._heads)
        return self.out_proj(out)


class TransformerEncoderCell(HybridBlock):
    """Post-norm transformer encoder layer (BERT layout)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="gelu", layer_norm_eps=1e-5, pre_norm=False,
                 use_flash=False, **kwargs):
        super().__init__(**kwargs)
        self._pre_norm = pre_norm
        with self.name_scope():
            self.attention = MultiHeadSelfAttention(units, num_heads,
                                                    dropout,
                                                    use_flash=use_flash)
            self.attn_norm = nn.LayerNorm(in_channels=units,
                                          epsilon=layer_norm_eps)
            self.dropout_layer = nn.Dropout(dropout)
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       activation, layer_norm_eps,
                                       pre_norm)

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        residual = x
        h = x
        if self._pre_norm:
            with jax.named_scope("mx.norm"):
                h = self.attn_norm(x)
        h = self.attention(h, mask, valid_length)
        with jax.named_scope("mx.norm"):
            h = self.dropout_layer(h)
            h = h + residual
            if not self._pre_norm:
                h = self.attn_norm(h)
        return self.ffn(h)


class TransformerDecoderCell(HybridBlock):
    """Decoder layer: masked self-att, cross-att, FFN."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="relu", layer_norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.self_attention = MultiHeadSelfAttention(units, num_heads,
                                                         dropout)
            self.self_norm = nn.LayerNorm(in_channels=units,
                                          epsilon=layer_norm_eps)
            self.cross_attention = MultiHeadAttention(units, num_heads,
                                                      dropout)
            self.cross_norm = nn.LayerNorm(in_channels=units,
                                           epsilon=layer_norm_eps)
            self.dropout_layer = nn.Dropout(dropout)
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       activation, layer_norm_eps)

    def hybrid_forward(self, F, x, mem, self_mask=None, mem_mask=None):
        h = self.self_attention(x, self_mask)
        h = self.self_norm(x + self.dropout_layer(h))
        c = self.cross_attention(h, mem, mem_mask)
        c = self.cross_norm(h + self.dropout_layer(c))
        return self.ffn(c)


# ---------------------------------------------------------------------------
# decoder-only LM + paged decode-mode forward (serving decode engine)
# ---------------------------------------------------------------------------
class RMSNorm(HybridBlock):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis."""

    def __init__(self, in_channels, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.rms_norm(x, gamma, eps=self._eps)


class GatedFFN(HybridBlock):
    """The bias-free gated feed-forward, ``W2 (act(Wg x) * (Wu x))``
    (SwiGLU with ``activation="silu"``); ``ffn_1`` holds [gate | up]."""

    def __init__(self, units, hidden_size, activation="silu", **kwargs):
        super().__init__(**kwargs)
        self._hidden, self._activation = hidden_size, activation
        with self.name_scope():
            self.ffn_1 = nn.Dense(2 * hidden_size, in_units=units,
                                  use_bias=False, flatten=False,
                                  prefix="ffn_1_")
            self.ffn_2 = nn.Dense(units, in_units=hidden_size,
                                  use_bias=False, flatten=False,
                                  prefix="ffn_2_")

    def hybrid_forward(self, F, x):
        with jax.named_scope("mx.ffn.dense"):
            h = self.ffn_1(x)
            gate = F.slice_axis(h, axis=-1, begin=0, end=self._hidden)
            up = F.slice_axis(h, axis=-1, begin=self._hidden, end=None)
            if self._activation == "silu":
                gate = gate * F.sigmoid(gate)
            else:
                gate = F.Activation(gate, act_type=self._activation)
            return self.ffn_2(gate * up)


class RotaryGroupedAttention(HybridBlock):
    """Causal self-attention over (B, L, C) with rotary positions and
    ``num_kv_heads`` key/value heads shared by groups of query heads
    (query head ``h`` reads key/value head ``h // (H // Hkv)``), no
    bias, through the Pallas flash kernels (the interpreter on CPU).

    ``window``: key ``s`` is visible to query ``t`` iff
    ``t - window < s <= t``; None is plain causal.  ``rope``: keyword
    arguments of the ``rope`` op (theta, the YaRN parameters; an empty
    dict rotates at the op's defaults); None leaves q and k as they are
    (a model whose other layers carry position).
    ``kv_proj`` holds [k | v].  ``compute_dtype``: what q, k and v are
    rounded to for the kernels (the MXU's fast path; the default
    matmuls round float32 operands to it too); the output returns to
    x's type.  ``qk_norm_eps``: with a value, every head of q and of k
    passes an RMS norm over its ``head_dim`` channels before the
    rotation, q's and k's each with a gain (head_dim,) of its own,
    shared by the heads; None: no such norm and no such gains.
    ``out_gate``: each query head's projection carries [q | gate] and
    the attention's output is multiplied by ``sigmoid(gate)`` before
    ``out_proj``.  ``rotary_dim``: rotary over the first channels of a
    head only (the ``rope`` op's ``rotary_dim``).
    """

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 window=None, rope=None, compute_dtype="bfloat16",
                 qk_norm_eps=None, out_gate=False, rotary_dim=0, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_kv_heads} key/value heads do not "
                             f"group {num_heads} query heads")
        if window is not None and int(window) < 1:
            raise MXNetError(f"window must be >= 1, got {window}")
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head_dim = head_dim
        self._window = -1 if window is None else int(window)
        self._rope = None if rope is None else dict(rope)
        if self._rope is not None and rotary_dim:
            self._rope["rotary_dim"] = int(rotary_dim)
        self._compute_dtype = compute_dtype
        self._qk_norm_eps = qk_norm_eps
        self._out_gate = bool(out_gate)
        with self.name_scope():
            if qk_norm_eps is not None:
                self.q_norm = RMSNorm(head_dim, qk_norm_eps,
                                      prefix="q_norm_")
                self.k_norm = RMSNorm(head_dim, qk_norm_eps,
                                      prefix="k_norm_")
            self.q_proj = nn.Dense((1 + self._out_gate) * num_heads
                                   * head_dim, in_units=units,
                                   use_bias=False, flatten=False,
                                   prefix="q_proj_")
            self.kv_proj = nn.Dense(2 * num_kv_heads * head_dim,
                                    in_units=units, use_bias=False,
                                    flatten=False, prefix="kv_proj_")
            self.out_proj = nn.Dense(units, in_units=num_heads * head_dim,
                                     use_bias=False, flatten=False,
                                     prefix="out_proj_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, Hkv, D = self._heads, self._kv_heads, self._head_dim
        with jax.named_scope("mx.attn.proj"):
            q = self.q_proj(x)
            if self._out_gate:
                q = q.reshape((B, L, H, 2 * D))
                gate = F.slice_axis(q, axis=-1, begin=D, end=None)
                q = F.slice_axis(q, axis=-1, begin=0, end=D)
            q = q.reshape((B, L, H, D))
            kv = self.kv_proj(x)
            k = F.slice_axis(kv, axis=-1, begin=0, end=Hkv * D)
            v = F.slice_axis(kv, axis=-1, begin=Hkv * D, end=None)
            k = k.reshape((B, L, Hkv, D))
        if self._qk_norm_eps is not None:
            with jax.named_scope("mx.attn.qk_norm"):
                q, k = self.q_norm(q), self.k_norm(k)
        if self._rope is not None:
            q, k = F.rope(q, **self._rope), F.rope(k, **self._rope)
        with jax.named_scope("mx.attn.proj"):
            v = v.reshape((B, L, Hkv, D))
            q, k, v = (F.cast(a, dtype=self._compute_dtype)
                       for a in (q, k, v))
        out = F.flash_attention(q, k, v, causal=True, window=self._window)
        with jax.named_scope("mx.attn.proj"):
            out = F.cast(out, dtype=str(x.dtype))
        if self._out_gate:
            with jax.named_scope("mx.attn.out_gate"):
                out = out * F.sigmoid(gate)
        with jax.named_scope("mx.attn.proj"):
            return self.out_proj(out.reshape((B, L, H * D)))


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer over (B, L, C) (Dao and Gu 2024; ``ops/ssm.py``
    has the equations): ``in_proj`` gives [z | x B C | dt] of widths
    ``inner | inner + 2 * n_groups * state_size | num_heads`` with
    ``inner = num_heads * head_dim``; x, B and C pass the causal
    depthwise convolution of ``conv_kernel`` taps and silu; the
    selective scan runs in chunks of ``chunk``; its result is gated by
    ``silu(z)``, RMS-normed over each of ``n_groups`` groups of
    channels, and ``out_proj`` returns to C.  No bias but the
    convolution's.
    """

    def __init__(self, units, num_heads, head_dim, state_size, n_groups,
                 conv_kernel=4, chunk=128, norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if num_heads % n_groups:
            raise MXNetError(f"{n_groups} groups of B and C do not divide "
                             f"{num_heads} heads")
        inner = num_heads * head_dim
        conv_dim = inner + 2 * n_groups * state_size
        self._sizes = (num_heads, head_dim, n_groups, state_size)
        self._chunk, self._eps = int(chunk), norm_eps
        with self.name_scope():
            self.in_proj = nn.Dense(inner + conv_dim + num_heads,
                                    in_units=units, use_bias=False,
                                    flatten=False, prefix="in_proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_dim, conv_kernel))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(conv_dim,), init="zeros")
            self.dt_bias = self.params.get(
                "dt_bias", shape=(num_heads,), init="zeros")
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init="zeros")
            self.D = self.params.get("D", shape=(num_heads,), init="ones")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(inner,), init="ones")
            self.out_proj = nn.Dense(units, in_units=inner, use_bias=False,
                                     flatten=False, prefix="out_proj_")

    def hybrid_forward(self, F, x, conv_weight, conv_bias, dt_bias, A_log,
                       D, norm_gamma):
        H, P, G, N = self._sizes
        with jax.named_scope("mx.ssm.in_proj"):
            h = self.in_proj(x)
        y = F.ssm_mixer(h, conv_weight, conv_bias, dt_bias, A_log, D,
                        norm_gamma, num_heads=H, head_dim=P, n_groups=G,
                        state_size=N, chunk=self._chunk, eps=self._eps)
        with jax.named_scope("mx.ssm.out_proj"):
            return self.out_proj(y)


class ShortConvMixer(HybridBlock):
    """The gated short convolution over (B, L, C) (the LFM2 family's
    "conv" operator; ``ops/shortconv.py`` has the equations):
    ``in_proj`` gives [B | C | u] of ``units`` channels each, ``B * u``
    passes a causal depthwise convolution of ``kernel`` taps, ``C``
    gates the result and ``out_proj`` returns it.  No bias, no
    activation.
    """

    def __init__(self, units, kernel=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = nn.Dense(3 * units, in_units=units,
                                    use_bias=False, flatten=False,
                                    prefix="in_proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(units, int(kernel)))
            self.out_proj = nn.Dense(units, in_units=units, use_bias=False,
                                     flatten=False, prefix="out_proj_")

    def hybrid_forward(self, F, x, conv_weight):
        with jax.named_scope("mx.sconv.in_proj"):
            h = self.in_proj(x)
        y = F.gated_short_conv(h, conv_weight)
        with jax.named_scope("mx.sconv.out_proj"):
            return self.out_proj(y)


class GatedDeltaNetMixer(HybridBlock):
    """The Gated DeltaNet mixer over (B, L, C) (Qwen3-Next's
    ``linear_attention``; ``ops/deltanet.py`` has the equations):
    ``in_proj`` gives [q | k | v | z] of widths ``key_heads * key_dim``
    twice and ``value_heads * value_dim`` twice, ``ba_proj`` [b | a] of
    ``value_heads`` each; q, k and v pass a causal depthwise convolution
    of ``conv_kernel`` taps without a bias and silu, the gated delta rule
    runs in chunks of ``chunk``, each value head's output is RMS-normed
    (one gain of ``value_dim`` shared by the heads) and gated by
    ``silu(z)``, and ``out_proj`` returns to C.  No bias.
    """

    def __init__(self, units, key_heads, value_heads, key_dim, value_dim,
                 conv_kernel=4, chunk=64, norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if value_heads % key_heads:
            raise MXNetError(f"{key_heads} key heads do not divide "
                             f"{value_heads} value heads")
        conv_dim = 2 * key_heads * key_dim + value_heads * value_dim
        self._sizes = dict(key_heads=key_heads, value_heads=value_heads,
                           key_dim=key_dim, value_dim=value_dim,
                           chunk=int(chunk), eps=norm_eps)
        with self.name_scope():
            self.in_proj = nn.Dense(conv_dim + value_heads * value_dim,
                                    in_units=units, use_bias=False,
                                    flatten=False, prefix="in_proj_")
            self.ba_proj = nn.Dense(2 * value_heads, in_units=units,
                                    use_bias=False, flatten=False,
                                    prefix="ba_proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_dim, conv_kernel))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(value_heads,), init="ones")
            self.A_log = self.params.get(
                "A_log", shape=(value_heads,), init="zeros")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(value_dim,), init="ones")
            self.out_proj = nn.Dense(units, in_units=value_heads * value_dim,
                                     use_bias=False, flatten=False,
                                     prefix="out_proj_")

    def hybrid_forward(self, F, x, conv_weight, dt_bias, A_log, norm_gamma):
        with jax.named_scope("mx.gdn.in_proj"):
            qkvz, ba = self.in_proj(x), self.ba_proj(x)
        y = F.gdn_mixer(qkvz, ba, conv_weight, A_log, dt_bias, norm_gamma,
                        **self._sizes)
        with jax.named_scope("mx.gdn.out_proj"):
            return self.out_proj(y)


class KimiDeltaMixer(HybridBlock):
    """Kimi Delta Attention over (B, L, C) (Kimi Linear's linear
    attention; ``ops/deltanet.py`` has the equations): ``qkv_proj``
    gives [q | k | v] of ``heads * head_dim`` each, which pass a causal
    depthwise convolution of ``conv_kernel`` taps without a bias and
    silu; the decay a key channel comes from ``f_b_proj(f_a_proj(x))``
    (rank ``head_dim``), beta from ``b_proj``, and the delta rule runs
    in chunks of ``chunk``; each head's output is RMS-normed (one gain
    of ``head_dim`` shared by the heads) and gated by ``sigmoid`` of
    ``g_b_proj(g_a_proj(x))``, and ``out_proj`` returns to C.  No bias.
    The projections' weights are ``(out, in)`` leaves of this block
    (``Dense``'s layout and names): the ``kda_mixer`` op takes them, so
    that it can compute the whole mixer again in the backward pass.
    """

    _PROJECTIONS = ("qkv_proj", "f_a_proj", "f_b_proj", "b_proj",
                    "g_a_proj", "g_b_proj")

    def __init__(self, units, heads, head_dim, conv_kernel=4, chunk=64,
                 norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        width = heads * head_dim
        self._sizes = dict(heads=heads, head_dim=head_dim, chunk=int(chunk),
                           eps=norm_eps)
        shapes = dict(zip(self._PROJECTIONS, (
            (3 * width, units), (head_dim, units), (width, head_dim),
            (heads, units), (head_dim, units), (width, head_dim))))
        with self.name_scope():
            for name, shape in shapes.items():
                setattr(self, name, self.params.get(name + "_weight",
                                                    shape=shape))
            self.conv_weight = self.params.get(
                "conv_weight", shape=(3 * width, conv_kernel))
            self.A_log = self.params.get("A_log", shape=(heads,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(width,),
                                           init="zeros")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(head_dim,), init="ones")
            self.out_proj = self.params.get("out_proj_weight",
                                            shape=(units, width))

    def hybrid_forward(self, F, x, qkv_proj, f_a_proj, f_b_proj, b_proj,
                       g_a_proj, g_b_proj, conv_weight, A_log, dt_bias,
                       norm_gamma, out_proj):
        return F.kda_mixer(x, qkv_proj, f_a_proj, f_b_proj, b_proj,
                           g_a_proj, g_b_proj, conv_weight, A_log, dt_bias,
                           norm_gamma, out_proj, **self._sizes)


class LatentAttention(HybridBlock):
    """Multi-head latent attention over (B, L, C) without rotary (Kimi
    Linear's global layers; DeepSeek-V2's MLA with ``q_lora_rank`` none
    and no position): ``q_proj`` gives each of ``num_heads`` heads
    [q_nope | q_pe] of ``qk_nope_dim + qk_rope_dim``; ``kv_a_proj`` the
    latent c (``kv_lora_rank``) and one ``k_pe`` (``qk_rope_dim``)
    shared by the heads; ``kv_b_proj`` of ``RMSNorm(c)`` gives each head
    [k_nope | v] of ``qk_nope_dim + v_head_dim``; head h attends with
    ``k_h = [k_nope_h | k_pe]`` causally, scale ``(qk_nope_dim +
    qk_rope_dim)^-1/2``, through the flash kernels at a v width that
    differs from q's and k's; ``out_proj`` returns to C.  No bias.
    ``compute_dtype``: what q, k and v are rounded to for the kernels.
    """

    def __init__(self, units, num_heads, qk_nope_dim, qk_rope_dim,
                 v_head_dim, kv_lora_rank, latent_eps=1e-5,
                 compute_dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self._sizes = (num_heads, qk_nope_dim, qk_rope_dim, v_head_dim,
                       kv_lora_rank)
        self._compute_dtype = compute_dtype

        def dense(n, fan_in, prefix):
            return nn.Dense(n, in_units=fan_in, use_bias=False,
                            flatten=False, prefix=prefix)

        with self.name_scope():
            self.q_proj = dense(num_heads * (qk_nope_dim + qk_rope_dim),
                                units, "q_proj_")
            self.kv_a_proj = dense(kv_lora_rank + qk_rope_dim, units,
                                   "kv_a_proj_")
            self.kv_norm = RMSNorm(kv_lora_rank, latent_eps,
                                   prefix="kv_norm_")
            self.kv_b_proj = dense(num_heads * (qk_nope_dim + v_head_dim),
                                   kv_lora_rank, "kv_b_proj_")
            self.out_proj = dense(units, num_heads * v_head_dim,
                                  "out_proj_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, nope, rope, dv, rank = self._sizes
        cast = self._compute_dtype
        with jax.named_scope("mx.attn.proj"):
            q = self.q_proj(x).reshape((B, L, H, nope + rope))
            ckv = self.kv_a_proj(x)
        with jax.named_scope("mx.mla.latent"):
            c = self.kv_norm(F.slice_axis(ckv, axis=-1, begin=0, end=rank))
            k_pe = F.slice_axis(ckv, axis=-1, begin=rank, end=None)
        with jax.named_scope("mx.attn.proj"):
            kv = self.kv_b_proj(c).reshape((B, L, H, nope + dv))
        with jax.named_scope("mx.mla.latent"):
            v = F.slice_axis(kv, axis=-1, begin=nope, end=None)
            k = F.concat(
                F.slice_axis(kv, axis=-1, begin=0, end=nope),
                F.broadcast_to(k_pe.reshape((B, L, 1, rope)),
                               shape=(B, L, H, rope)), dim=-1)
            q, k, v = (F.cast(a, dtype=cast) for a in (q, k, v))
        out = F.flash_attention(q, k, v, causal=True)
        with jax.named_scope("mx.attn.proj"):
            out = F.cast(out, dtype=str(x.dtype))
            return self.out_proj(out.reshape((B, L, H * dv)))


class DecoderCell(HybridBlock):
    """The present-day pre-norm decoder block over (B, L, C), RMSNorm
    throughout.  With ``ffn``: ``h = x + mixer(norm(x))``,
    ``y = h + ffn(norm(h))`` (``mixer`` an attention block or a gated
    short convolution).  Without:
    one mixer alone under one norm, ``y = x + mixer(norm(x))`` (a hybrid
    model's layer: a state-space mixer, an attention block or an expert
    layer).  What kind of layer this is (window or full attention,
    Mamba-2, dense or sparse feed-forward) is how the blocks handed in
    were configured, not another class."""

    def __init__(self, units, mixer, ffn=None, rms_norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            if ffn is None:
                self.norm = RMSNorm(units, rms_norm_eps, prefix="norm_")
                self.mixer = mixer
            else:
                self.attn_norm = RMSNorm(units, rms_norm_eps,
                                         prefix="attn_norm_")
                self.attention = mixer
                self.ffn_norm = RMSNorm(units, rms_norm_eps,
                                        prefix="ffn_norm_")
            self.ffn = ffn

    def hybrid_forward(self, F, x):
        if self.ffn is None:
            with jax.named_scope("mx.norm"):
                h = self.norm(x)
            h = self.mixer(h)
            with jax.named_scope("mx.norm"):
                return x + h
        with jax.named_scope("mx.norm"):
            h = self.attn_norm(x)
        h = self.attention(h)
        with jax.named_scope("mx.norm"):
            h = x + h
            y = self.ffn_norm(h)
        y = self.ffn(y)
        with jax.named_scope("mx.norm"):
            return h + y


def _sinusoid_table(max_len, units):
    """Shared sinusoidal position table (also consumed by
    models/transformer.py — ONE copy of the formula)."""
    pos = np.arange(max_len)[:, None]
    dim = np.arange(units)[None, :]
    angle = pos / np.power(10000, (2 * (dim // 2)) / units)
    table = np.zeros((max_len, units), dtype=np.float32)
    table[:, 0::2] = np.sin(angle[:, 0::2])
    table[:, 1::2] = np.cos(angle[:, 1::2])
    return table


NEG_INF = -1e9


class TransformerDecoderLM(HybridBlock):
    """Decoder-only causal LM (GPT layout): embedding + sinusoid
    positions, pre-norm self-attention cells, final LayerNorm, vocab
    projection.

    Two forwards share the SAME parameters:

    - the hybridizable training/teacher-forcing forward here —
      ``lm(tokens (B, L)) -> logits (B, L, V)`` with an additive causal
      mask on the dense attention path;
    - the serving *decode-mode* forward — the pure-jax
      :func:`paged_prefill` / :func:`paged_decode_step` pair below,
      which threads K/V through the paged cache pool
      (``serving.kv_cache``) instead of rematerializing the whole
      prefix each step.  ``paged_lm_params(lm)`` snapshots the
      parameter arrays into the dict those functions consume.
    """

    def __init__(self, vocab_size, units=64, hidden_size=128,
                 num_layers=2, num_heads=2, max_length=128, dropout=0.0,
                 activation="relu", layer_norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        self.vocab_size = int(vocab_size)
        self.units = int(units)
        self.num_heads = int(num_heads)
        self.num_layers = int(num_layers)
        self.head_dim = self.units // self.num_heads
        self.max_context = int(max_length)
        self._activation = activation
        self._eps = layer_norm_eps
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.pos_embed = self.params.get_constant(
                "pos_embed", _sinusoid_table(max_length, units))
            self.dropout_layer = nn.Dropout(dropout)
            self.cells = nn.HybridSequential()
            for _ in range(num_layers):
                self.cells.add(TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout,
                    activation=activation, layer_norm_eps=layer_norm_eps,
                    pre_norm=True))
            self.final_norm = nn.LayerNorm(in_channels=units,
                                           epsilon=layer_norm_eps)
            self.proj = nn.Dense(vocab_size, in_units=units,
                                 flatten=False)

    def hybrid_forward(self, F, tokens, pos_embed=None):
        # tokens: (B, L) int ids -> logits (B, L, V)
        from .. import ndarray as nd
        B, L = tokens.shape
        x = self.embed(tokens) * math.sqrt(self.units)      # (B, L, C)
        x = F.transpose(x, axes=(1, 0, 2))                  # (L, B, C)
        x = x + pos_embed.slice_axis(axis=0, begin=0,
                                     end=L).expand_dims(1)
        x = self.dropout_layer(x)
        steps = nd.arange(L)
        ok = F.broadcast_lesser_equal(steps.reshape((1, L)),
                                      steps.reshape((L, 1)))
        mask = (1.0 - ok) * NEG_INF                         # (L, L) causal
        for cell in self.cells:
            x = cell(x, mask)
        x = self.final_norm(x)
        logits = self.proj(x)                               # (L, B, V)
        return F.transpose(logits, axes=(1, 0, 2))

    def decode_meta(self, eos_id=None, draft=None, spec_k=None):
        """The decode-capable metadata block a serving/deploy manifest
        carries (``deploy.export_stablehlo(decode=...)``): everything an
        external runtime needs to size the paged KV cache and drive the
        step loop.

        ``draft`` (another :class:`TransformerDecoderLM`, or a plain
        dims dict) ships the speculative-decoding draft model's cache
        sizing next to the target's, and ``spec_k`` the proposal depth
        the deployment was tuned for (docs/serving.md §9) — so an
        external runtime can pre-size BOTH pools and the verify-program
        width before loading weights."""
        meta = {"vocab_size": self.vocab_size,
                "num_layers": self.num_layers,
                "num_heads": self.num_heads,
                "head_dim": self.head_dim,
                "max_context": self.max_context}
        if eos_id is not None:
            meta["eos_id"] = int(eos_id)
        if draft is not None:
            meta["draft"] = dict(draft) if isinstance(draft, dict) \
                else draft.decode_meta()
        if spec_k is not None:
            meta["spec_k"] = int(spec_k)
        return meta


def paged_lm_params(lm):
    """Snapshot a :class:`TransformerDecoderLM`'s parameters into the
    flat jnp dict :func:`paged_prefill` / :func:`paged_decode_step`
    consume.  Arrays are snapshots: later training does not mutate a
    served copy (re-snapshot to publish new weights), and weights enter
    compiled programs as INPUTS, so a refresh never retraces."""
    import jax.numpy as jnp

    def g(p):
        return p.data()._data.astype(jnp.float32)

    cells = []
    for cell in lm.cells:
        att, ffn = cell.attention, cell.ffn
        cells.append(dict(
            n1_g=g(cell.attn_norm.gamma), n1_b=g(cell.attn_norm.beta),
            qkv_w=g(att.qkv.weight), qkv_b=g(att.qkv.bias),
            o_w=g(att.out_proj.weight), o_b=g(att.out_proj.bias),
            n2_g=g(ffn.layer_norm.gamma), n2_b=g(ffn.layer_norm.beta),
            f1_w=g(ffn.ffn_1.weight), f1_b=g(ffn.ffn_1.bias),
            f2_w=g(ffn.ffn_2.weight), f2_b=g(ffn.ffn_2.bias),
        ))
    return {
        "embed": g(lm.embed.weight),
        "pos": lm.pos_embed.data()._data.astype(jnp.float32),
        "fn_g": g(lm.final_norm.gamma), "fn_b": g(lm.final_norm.beta),
        "proj_w": g(lm.proj.weight), "proj_b": g(lm.proj.bias),
        "cells": cells,
    }


def _f_ln(x, gamma, beta, eps=1e-5):
    import jax.numpy as jnp
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


def _f_act(x, activation):
    import jax
    if activation == "relu":
        return jax.nn.relu(x)
    if activation in ("gelu", "gelu_erf"):
        return jax.nn.gelu(x, approximate=False)
    if activation == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise MXNetError(f"paged decode forward: unsupported activation "
                     f"{activation!r}")


def _f_ffn(x, cp, activation):
    h = _f_act(x @ cp["f1_w"].T + cp["f1_b"], activation)
    return h @ cp["f2_w"].T + cp["f2_b"]


def paged_prefill(params, tokens, length, block_table, k_pages, v_pages,
                  *, num_heads, page_size, activation="relu",
                  layer_norm_eps=1e-5):
    """Prefill ONE sequence and write its K/V into cache pages.

    ``tokens``: (1, L_bucket) int32, padded past ``length`` (a scalar);
    ``block_table``: (pages_per_seq,) int32 physical pages (null page 0
    in unused slots); ``k_pages``/``v_pages``: the full
    (layers, pool_pages, page_size, heads, head_dim) pools.  Attention
    over the fresh prompt is plain causal+padding-masked softmax (the
    prefix IS the whole context — no cache read yet); K/V of positions
    past ``length`` are routed to the null page.  Returns
    ``(last-token logits (V,), k_pages, v_pages)``.
    """
    import jax.numpy as jnp
    H = num_heads
    L = tokens.shape[1]
    C = params["embed"].shape[1]
    D = C // H
    x = params["embed"][tokens[0]] * math.sqrt(C) \
        + params["pos"][:L]                                 # (L, C)
    pos_idx = jnp.arange(L)
    valid = pos_idx < length                                # (L,)
    page_idx = jnp.where(valid, block_table[pos_idx // page_size], 0)
    slot_idx = pos_idx % page_size
    # causal + padding: key j visible to query i iff j <= i and j valid
    mask = (pos_idx[None, :] <= pos_idx[:, None]) \
        & valid[None, :]                                    # (L, L)
    for li, cp in enumerate(params["cells"]):
        h = _f_ln(x, cp["n1_g"], cp["n1_b"], layer_norm_eps)
        qkv = (h @ cp["qkv_w"].T + cp["qkv_b"]).reshape(L, H, 3, D)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        k_pages = k_pages.at[li, page_idx, slot_idx].set(
            k.astype(k_pages.dtype))
        v_pages = v_pages.at[li, page_idx, slot_idx].set(
            v.astype(v_pages.dtype))
        s = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(D)
        s = jnp.where(mask[None], s, NEG_INF)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        o = jnp.einsum("hij,jhd->ihd", p, v).reshape(L, C)
        x = x + (o @ cp["o_w"].T + cp["o_b"])
        x = x + _f_ffn(_f_ln(x, cp["n2_g"], cp["n2_b"], layer_norm_eps),
                       cp, activation)
    x_last = x[length - 1]                                  # (C,)
    x_last = _f_ln(x_last, params["fn_g"], params["fn_b"],
                   layer_norm_eps)
    return (x_last @ params["proj_w"].T + params["proj_b"],
            k_pages, v_pages)


def paged_decode_step(params, tokens, positions, block_tables, k_pages,
                      v_pages, *, num_heads, page_size,
                      activation="relu", layer_norm_eps=1e-5,
                      attention_impl="jax"):
    """One decode step for the whole (fixed-size) decode batch.

    ``tokens``: (B,) int32 current token per slot; ``positions``: (B,)
    int32 write position (== context length so far); ``block_tables``:
    (B, pages_per_seq) int32.  Inactive slots carry token 0, position
    0, and an all-null block table — their K/V writes land in the null
    page and their logits are garbage the engine never reads.  Each
    layer writes the new token's K/V through the block table, then
    attends over the ragged paged context with the Pallas kernel
    (``attention_impl="pallas"``, TPU) or the pure-jax reference
    (``"jax"``, the CPU serving path).  Returns
    ``(logits (B, V), k_pages, v_pages)``.
    """
    import jax.numpy as jnp

    from ..ops import pallas_kernels as pk
    H = num_heads
    B = tokens.shape[0]
    C = params["embed"].shape[1]
    D = C // H
    x = params["embed"][tokens] * math.sqrt(C) \
        + params["pos"][positions]                          # (B, C)
    page = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    slot = positions % page_size
    ctx = positions + 1                                     # incl. new tok
    for li, cp in enumerate(params["cells"]):
        h = _f_ln(x, cp["n1_g"], cp["n1_b"], layer_norm_eps)
        qkv = (h @ cp["qkv_w"].T + cp["qkv_b"]).reshape(B, H, 3, D)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        k_pages = k_pages.at[li, page, slot].set(k.astype(k_pages.dtype))
        v_pages = v_pages.at[li, page, slot].set(v.astype(v_pages.dtype))
        if attention_impl == "pallas":
            o = pk.ragged_paged_attention(
                q, k_pages[li], v_pages[li], block_tables, ctx)
        else:
            o = pk.ragged_paged_attention_reference(
                q, k_pages[li], v_pages[li], block_tables, ctx)
        x = x + (o.reshape(B, C) @ cp["o_w"].T + cp["o_b"])
        x = x + _f_ffn(_f_ln(x, cp["n2_g"], cp["n2_b"], layer_norm_eps),
                       cp, activation)
    x = _f_ln(x, params["fn_g"], params["fn_b"], layer_norm_eps)
    return x @ params["proj_w"].T + params["proj_b"], k_pages, v_pages


def paged_verify(params, tokens, start, length, block_table, k_pages,
                 v_pages, *, num_heads, page_size, activation="relu",
                 layer_norm_eps=1e-5, attention_impl="jax"):
    """Multi-token window forward over a paged context: the ragged
    verification shape of speculative decoding, and the tail prefill of
    a prefix-cache hit (docs/serving.md §9).

    ``tokens``: (1, W_bucket) int32 window, padded past ``length``;
    ``start``: scalar global position of ``tokens[0, 0]`` (K/V of
    positions ``< start`` already sit in cache pages); ``block_table``:
    (pages_per_seq,) int32.  Writes K/V for the ``length`` valid window
    positions through the block table (padded positions route to the
    null page) and attends each window token causally over the FULL
    paged context up to itself — the prefill/multi-token path of
    ``ragged_paged_attention`` ("Ragged Paged Attention", PAPERS.md).
    Returns ``(logits (W_bucket, V), k_pages, v_pages)``; rows past
    ``length`` are zeros-in/garbage-out and must not be read.

    Equivalences the decode engine leans on: with ``start == 0`` and
    ``length == L`` this is :func:`paged_prefill` over a paged read
    path; with ``W == 1`` it recovers the last-token logits of an
    already-cached prefix; with the speculation window
    ``[last_sampled, draft_1..draft_k]`` it verifies all k+1 positions
    in ONE program call.
    """
    import jax.numpy as jnp

    from ..ops import pallas_kernels as pk
    H = num_heads
    W = tokens.shape[1]
    C = params["embed"].shape[1]
    D = C // H
    P = block_table.shape[0]
    offs = jnp.arange(W)
    pos = start + offs
    valid = offs < length                                   # (W,)
    max_pos = params["pos"].shape[0]
    x = params["embed"][tokens[0]] * math.sqrt(C) \
        + params["pos"][jnp.minimum(pos, max_pos - 1)]      # (W, C)
    page_idx = jnp.where(
        valid, block_table[jnp.minimum(pos // page_size, P - 1)], 0)
    slot_idx = pos % page_size
    starts = jnp.reshape(start, (1,)).astype(jnp.int32)
    lengths = jnp.reshape(length, (1,)).astype(jnp.int32)
    for li, cp in enumerate(params["cells"]):
        h = _f_ln(x, cp["n1_g"], cp["n1_b"], layer_norm_eps)
        qkv = (h @ cp["qkv_w"].T + cp["qkv_b"]).reshape(W, H, 3, D)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        k_pages = k_pages.at[li, page_idx, slot_idx].set(
            k.astype(k_pages.dtype))
        v_pages = v_pages.at[li, page_idx, slot_idx].set(
            v.astype(v_pages.dtype))
        if attention_impl == "pallas":
            o = pk.ragged_paged_verify(
                q[None], k_pages[li], v_pages[li], block_table[None],
                starts, lengths)[0]
        else:
            o = pk.ragged_paged_verify_reference(
                q[None], k_pages[li], v_pages[li], block_table[None],
                starts, lengths)[0]
        x = x + (o.reshape(W, C) @ cp["o_w"].T + cp["o_b"])
        x = x + _f_ffn(_f_ln(x, cp["n2_g"], cp["n2_b"], layer_norm_eps),
                       cp, activation)
    x = _f_ln(x, params["fn_g"], params["fn_b"], layer_norm_eps)
    return x @ params["proj_w"].T + params["proj_b"], k_pages, v_pages


def paged_verify_batch(params, tokens, starts, lengths, block_tables,
                       k_pages, v_pages, *, num_heads, page_size,
                       activation="relu", layer_norm_eps=1e-5,
                       attention_impl="jax"):
    """Batched :func:`paged_verify`: one fixed-shape program verifies
    every running sequence's speculation window in ONE device call —
    the ragged multi-token decode shape (docs/serving.md §9).

    ``tokens``: (B, W) int32 windows; ``starts``/``lengths``: (B,)
    int32 per-slot window origin and valid width (0 = inactive slot:
    null writes, zero rows); ``block_tables``: (B, pages_per_seq).
    Returns ``(logits (B, W, V), k_pages, v_pages)``; rows past a
    slot's ``lengths`` are garbage the engine never reads.
    """
    import jax.numpy as jnp

    from ..ops import pallas_kernels as pk
    H = num_heads
    B, W = tokens.shape
    C = params["embed"].shape[1]
    D = C // H
    P = block_tables.shape[1]
    offs = jnp.arange(W)[None, :]
    pos = starts[:, None] + offs                            # (B, W)
    valid = offs < lengths[:, None]                         # (B, W)
    max_pos = params["pos"].shape[0]
    x = params["embed"][tokens] * math.sqrt(C) \
        + params["pos"][jnp.minimum(pos, max_pos - 1)]      # (B, W, C)
    page_idx = jnp.where(
        valid,
        jnp.take_along_axis(block_tables,
                            jnp.minimum(pos // page_size, P - 1),
                            axis=1), 0)                     # (B, W)
    slot_idx = pos % page_size
    for li, cp in enumerate(params["cells"]):
        h = _f_ln(x, cp["n1_g"], cp["n1_b"], layer_norm_eps)
        qkv = (h @ cp["qkv_w"].T + cp["qkv_b"]).reshape(B, W, H, 3, D)
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        k_pages = k_pages.at[li, page_idx, slot_idx].set(
            k.astype(k_pages.dtype))
        v_pages = v_pages.at[li, page_idx, slot_idx].set(
            v.astype(v_pages.dtype))
        if attention_impl == "pallas":
            o = pk.ragged_paged_verify(
                q, k_pages[li], v_pages[li], block_tables, starts,
                lengths)
        else:
            o = pk.ragged_paged_verify_reference(
                q, k_pages[li], v_pages[li], block_tables, starts,
                lengths)
        x = x + (o.reshape(B, W, C) @ cp["o_w"].T + cp["o_b"])
        x = x + _f_ffn(_f_ln(x, cp["n2_g"], cp["n2_b"], layer_norm_eps),
                       cp, activation)
    x = _f_ln(x, params["fn_g"], params["fn_b"], layer_norm_eps)
    return x @ params["proj_w"].T + params["proj_b"], k_pages, v_pages
