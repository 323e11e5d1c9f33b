"""Present-day decoder-only language models for training: RMSNorm,
rotary positions, grouped key/value heads, window and full causal
layers through the flash kernels or gated short convolutions, each
before a dense or sparse feed-forward, and hybrid models whose layers
are one mixer each (Mamba-2, attention without rotary, an expert layer
with a shared expert).
One ``DecoderCell`` class; ``layer_types`` says what each layer is.

The serving side (the paged forwards of ``transformer_blocks``) has
none of this yet: ``TransformerDecoderLM`` stays what is served.
"""
from __future__ import annotations

import jax

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.contrib.moe import MoEFFN
from .transformer_blocks import (DecoderCell, GatedFFN, Mamba2Mixer,
                                 RMSNorm, RotaryGroupedAttention,
                                 ShortConvMixer)

__all__ = ["DecoderLM", "get_decoder_lm"]


class DecoderLM(HybridBlock):
    """``lm(tokens (B, L)) -> logits (B, L, vocab_size)``.

    ``layer_types``, a layer each.  "sliding_attention" /
    "full_attention" / "conv": attention, or a gated short convolution
    of ``conv_kernel`` taps (``ShortConvMixer``), then a feed-forward,
    each under its norm; ``rope``: the ``rope`` op's keyword arguments
    for each of the two attentions; ``qk_norm_eps``:
    ``RotaryGroupedAttention``'s.  "mamba2" / "attention" / "moe": ONE
    mixer under one norm, a
    hybrid model's layer: a Mamba-2 mixer of the sizes in ``mamba``
    (``Mamba2Mixer``'s keyword arguments), causal attention (rotated
    only if ``rope`` has an entry "attention": such a model's Mamba
    layers carry position), or the expert layer.

    ``num_experts`` > 0 makes every feed-forward but those of the first
    ``dense_ffn_layers`` layers (``GatedFFN(hidden_size)``, a model's
    leading dense layers), and every "moe" layer, sparse: ``experts_per_token`` of ``num_experts`` experts of
    width ``expert_hidden_size`` (``expert_activation``, gated or not
    by ``expert_gated``), of which this model holds ``experts_held``
    from ``first_expert`` on (all by default; one chip's share under
    expert parallelism); ``router``: ``MoEFFN``'s ``scoring``,
    ``route_scale`` and ``route_eps``; ``shared_expert_hidden_size`` > 0
    gives each expert layer a shared expert.  ``vocab_size`` is the
    number of rows of the embedding and the head held here;
    ``tie_embeddings``: the head reads the embedding's rows, one
    parameter whose gradient is the sum of both uses.
    ``recompute_experts``:
    see ``ops.moe.moe_ffn``; ``train_router``: see ``MoEFFN``; ``attention_dtype``: what the
    flash kernels compute in (``RotaryGroupedAttention``).
    """

    def __init__(self, vocab_size, units, layer_types, num_heads,
                 num_kv_heads, head_dim, window=None, rope=None,
                 hidden_size=0, num_experts=0, experts_per_token=1,
                 expert_hidden_size=0, experts_held=None, first_expert=0,
                 rms_norm_eps=1e-6, recompute_experts=False,
                 train_router=True, attention_dtype="bfloat16",
                 expert_activation="silu", expert_gated=True, router=None,
                 shared_expert_hidden_size=0, mamba=None, conv_kernel=3,
                 qk_norm_eps=None, dense_ffn_layers=0, tie_embeddings=False,
                 **kwargs):
        super().__init__(**kwargs)
        rope = rope or {}
        self.vocab_size, self.units = int(vocab_size), int(units)

        def attention(kind, prefix):
            # Mellum's two kinds always rotate (an absent entry: the
            # op's defaults); the hybrid's rotates only if told how
            return RotaryGroupedAttention(
                units, num_heads, num_kv_heads, head_dim,
                window=window if kind == "sliding_attention" else None,
                rope=rope.get(kind, None if kind == "attention" else {}),
                compute_dtype=attention_dtype, qk_norm_eps=qk_norm_eps,
                prefix=prefix + "attention_")

        def experts(prefix):
            return MoEFFN(
                units, expert_hidden_size, num_experts,
                experts_per_token=experts_per_token,
                experts_held=experts_held, first_expert=first_expert,
                activation=expert_activation, gated=expert_gated,
                recompute=recompute_experts, train_router=train_router,
                shared_hidden_size=shared_expert_hidden_size,
                prefix=prefix + "moe_", **(router or {}))

        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.cells = nn.HybridSequential(prefix="")
            for i, kind in enumerate(layer_types):
                with self.cells.name_scope():
                    prefix = f"layer{i}_"
                    if kind in ("sliding_attention", "full_attention",
                                "conv"):
                        sparse = num_experts and i >= dense_ffn_layers
                        blocks = (ShortConvMixer(units, conv_kernel,
                                                 prefix=prefix + "conv_")
                                  if kind == "conv" else
                                  attention(kind, prefix),
                                  experts(prefix) if sparse else
                                  GatedFFN(units, hidden_size,
                                           prefix=prefix + "ffn_"))
                    elif kind == "attention":
                        blocks = (attention(kind, prefix),)
                    elif kind == "moe":
                        blocks = (experts(prefix),)
                    elif kind == "mamba2":
                        blocks = (Mamba2Mixer(
                            units, prefix=prefix + "mamba_", **mamba),)
                    else:
                        raise MXNetError(f"layer_types[{i}]: {kind!r}")
                    self.cells.add(DecoderCell(
                        units, *blocks, rms_norm_eps=rms_norm_eps,
                        prefix=prefix))
            self.final_norm = RMSNorm(units, rms_norm_eps,
                                      prefix="final_norm_")
            # tied: the head's Dense finds the embedding's "weight" in
            # the dictionary it is handed, the same (vocab, units) leaf
            self.lm_head = nn.Dense(
                vocab_size, in_units=units, use_bias=False, flatten=False,
                **(dict(params=self.word_embed.params) if tie_embeddings
                   else dict(prefix="lm_head_")))

    def hybrid_forward(self, F, tokens):
        with jax.named_scope("mx.embed"):
            x = self.word_embed(tokens)                     # (B, L, C)
        for cell in self.cells:
            x = cell(x)
        with jax.named_scope("mx.norm"):
            x = self.final_norm(x)
        with jax.named_scope("mx.head"):
            return self.lm_head(x)


_SLIDING3_FULL1 = ("sliding_attention",) * 3 + ("full_attention",)

_DECODER_CONFIGS = {
    # JetBrains Mellum2-12B-A2.5B-Instruct, config.json
    "mellum2_12b_a2.5b": dict(
        vocab_size=98304, units=2304, layer_types=_SLIDING3_FULL1 * 7,
        num_heads=32, num_kv_heads=4, head_dim=128, window=1024,
        num_experts=64, experts_per_token=8, expert_hidden_size=896,
        rms_norm_eps=1e-6,
        rope={"sliding_attention": dict(theta=500000.0),
              "full_attention": dict(
                  theta=500000.0, yarn_factor=16.0, yarn_original_max=8192,
                  yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                  attention_factor=1.2772588722239782)}),
    # NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json (model_type
    # nemotron_h): a layer is one mixer, its kind a letter of
    # hybrid_override_pattern
    "nemotron_3_nano_30b_a3b": dict(
        vocab_size=131072, units=2688,
        layer_types=tuple(
            {"M": "mamba2", "E": "moe", "*": "attention"}[c] for c in
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
        num_heads=32, num_kv_heads=2, head_dim=128, rms_norm_eps=1e-5,
        mamba=dict(num_heads=64, head_dim=64, state_size=128, n_groups=8,
                   conv_kernel=4, chunk=128, norm_eps=1e-5),
        num_experts=128, experts_per_token=6, expert_hidden_size=1856,
        expert_activation="relu2", expert_gated=False,
        router=dict(scoring="sigmoid", route_scale=2.5),
        shared_expert_hidden_size=3712),
    # LiquidAI LFM2-24B-A2B, config.json (model_type lfm2_moe): a gated
    # short convolution or attention (at layers 2, 6, .. 38), then a
    # dense SwiGLU in the first two layers and experts after
    "lfm2_24b_a2b": dict(
        vocab_size=65536, units=2048,
        layer_types=tuple("full_attention" if i % 4 == 2 else "conv"
                          for i in range(40)),
        num_heads=32, num_kv_heads=8, head_dim=64, rms_norm_eps=1e-5,
        qk_norm_eps=1e-5, rope={"full_attention": dict(theta=1000000.0)},
        conv_kernel=3, hidden_size=11776, dense_ffn_layers=2,
        num_experts=64, experts_per_token=4, expert_hidden_size=1536,
        router=dict(scoring="sigmoid", route_scale=1.0, route_eps=1e-6),
        tie_embeddings=True),
}


def get_decoder_lm(model_name, num_layers=None, **kwargs):
    """A :class:`DecoderLM` by name.  ``num_layers`` keeps the first
    layers of the published pattern; other keyword arguments override
    the published sizes (a test's small ones, a chip's share)."""
    if model_name not in _DECODER_CONFIGS:
        raise MXNetError(f"unknown decoder config {model_name!r}; "
                         f"known: {sorted(_DECODER_CONFIGS)}")
    cfg = dict(_DECODER_CONFIGS[model_name])
    cfg.update(kwargs)
    if num_layers is not None:
        cfg["layer_types"] = tuple(cfg["layer_types"])[:num_layers]
    return DecoderLM(**cfg)
