"""Present-day decoder-only language models for training: RMSNorm,
rotary positions, grouped key/value heads, window and full causal
layers through the flash kernels, dense or sparse gated feed-forwards.
One ``DecoderCell`` class; ``layer_types`` says what each layer is.

The serving side (the paged forwards of ``transformer_blocks``) has
none of this yet: ``TransformerDecoderLM`` stays what is served.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.contrib.moe import MoEFFN
from .transformer_blocks import (DecoderCell, GatedFFN, RMSNorm,
                                 RotaryGroupedAttention)

__all__ = ["DecoderLM", "get_decoder_lm"]


class DecoderLM(HybridBlock):
    """``lm(tokens (B, L)) -> logits (B, L, vocab_size)``.

    ``layer_types``: one of "sliding_attention" / "full_attention" a
    layer; ``rope``: the ``rope`` op's keyword arguments for each of the
    two.  ``num_experts`` > 0 makes every feed-forward sparse:
    ``experts_per_token`` of ``num_experts`` experts of width
    ``expert_hidden_size``, of which this model holds ``experts_held``
    from ``first_expert`` on (all by default; one chip's share under
    expert parallelism).  ``vocab_size`` is the number of rows of the
    embedding and the head held here.  ``recompute_experts``: see
    ``ops.moe.moe_ffn``; ``train_router``: see ``MoEFFN``;
    ``attention_dtype``: what the flash kernels
    compute in (``RotaryGroupedAttention``).
    """

    def __init__(self, vocab_size, units, layer_types, num_heads,
                 num_kv_heads, head_dim, window=None, rope=None,
                 hidden_size=0, num_experts=0, experts_per_token=1,
                 expert_hidden_size=0, experts_held=None, first_expert=0,
                 rms_norm_eps=1e-6, recompute_experts=False,
                 train_router=True, attention_dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        rope = rope or {}
        self.vocab_size, self.units = int(vocab_size), int(units)
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.cells = nn.HybridSequential(prefix="")
            for i, kind in enumerate(layer_types):
                if kind not in ("sliding_attention", "full_attention"):
                    raise MXNetError(f"layer_types[{i}]: {kind!r}")
                sliding = kind == "sliding_attention"
                with self.cells.name_scope():
                    prefix = f"layer{i}_"
                    attention = RotaryGroupedAttention(
                        units, num_heads, num_kv_heads, head_dim,
                        window=window if sliding else None,
                        rope=rope.get(kind), compute_dtype=attention_dtype,
                        prefix=prefix + "attention_")
                    if num_experts:
                        ffn = MoEFFN(
                            units, expert_hidden_size, num_experts,
                            experts_per_token=experts_per_token,
                            experts_held=experts_held,
                            first_expert=first_expert, activation="silu",
                            gated=True, recompute=recompute_experts,
                            train_router=train_router,
                            prefix=prefix + "moe_")
                    else:
                        ffn = GatedFFN(units, hidden_size,
                                       prefix=prefix + "ffn_")
                    self.cells.add(DecoderCell(units, attention, ffn,
                                               rms_norm_eps, prefix=prefix))
            self.final_norm = RMSNorm(units, rms_norm_eps,
                                      prefix="final_norm_")
            self.lm_head = nn.Dense(vocab_size, in_units=units,
                                    use_bias=False, flatten=False,
                                    prefix="lm_head_")

    def hybrid_forward(self, F, tokens):
        x = self.word_embed(tokens)                         # (B, L, C)
        for cell in self.cells:
            x = cell(x)
        return self.lm_head(self.final_norm(x))


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
