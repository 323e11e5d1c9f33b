"""The work of the ``kimi-linear-48b-a3b`` cells, counted from shapes:
the model's FLOPs a step, the chunked Kimi Delta Attention rule's
products and bytes, the latent attention's flash kernels over the
visible (query, key) pairs.  The same work whatever implements it;
recomputation is never counted.  The configuration and the metric
files name these functions as ``module:function``."""
import math

from .work_mellum import visible_pairs

ITEMSIZE = 4            # float32 activations and weights
KERNEL_ITEMSIZE = 2     # the flash kernels' q, k, v and outputs: bfloat16


def kinds(dims, kind):
    return sum(k == kind for k in dims["layer_types"])


def kda_rule_flops(dims, seqlen):
    """Forward FLOPs of one row's chunked delta rule in one layer, chunks
    of Q positions, a head's key and value width d: per head and chunk
    ``K K^T`` and ``Q K^T`` (2 Q^2 d each, the decays inside the
    contraction not counted), the triangular inverse's 2 (log2 Q - 1)
    products of Q^3 multiply-adds, ``W`` and the writes' first part
    (2 Q^2 d each), the state's three products with the chunk (``W S``,
    ``Q S``, ``K^T U``: 2 Q d^2 each) and the masked product with the
    writes (2 Q^2 d).  The last chunk is counted whole."""
    Q, H, d = dims["chunk"], dims["kda_heads"], dims["kda_head_dim"]
    chunks = -(-seqlen // Q)
    per_head = (2 * 2 * Q * Q * d
                + 2 * max(int(math.log2(Q)) - 1, 0) * 2 * Q ** 3
                + 2 * 2 * Q * Q * d + 3 * 2 * Q * d * d + 2 * Q * Q * d)
    return chunks * H * per_head


def kda_projection_flops(dims):
    """A token's products in one KDA mixer outside the rule: [q | k | v],
    the decay's and the output gate's low-rank pairs, beta, out."""
    C, H, d = dims["units"], dims["kda_heads"], dims["kda_head_dim"]
    return 2 * C * (3 * H * d + 2 * d + H + H * d) + 2 * 2 * d * H * d


def mla_projection_flops(dims):
    """A token's products in the latent attention outside its core: q,
    the latent and k_pe, the latent's expansion to K and V, out."""
    C, H = dims["units"], dims["num_heads"]
    nope, rope = dims["qk_nope_dim"], dims["qk_rope_dim"]
    dv, r = dims["v_head_dim"], dims["kv_lora_rank"]
    return 2 * (C * H * (nope + rope) + C * (r + rope) + r * H * (nope + dv)
                + H * dv * C)


def forward_flops(dims, batch, seqlen):
    """Every matrix product of one forward pass (2 FLOPs a
    multiply-add): a KDA mixer's projections and its chunked rule; the
    latent attention's projections and its scores (192 wide) and their
    product with V (128 wide) over the visible pairs; the dense SwiGLU;
    an expert layer's router, the held experts by the expected share of
    a token's chosen experts that is held here, and the shared expert;
    the head over the positions that have a next token.  No
    convolution, no embedding lookup, no recomputation."""
    C, H = dims["units"], dims["num_heads"]
    tokens = batch * seqlen
    kda = (tokens * kda_projection_flops(dims)
           + batch * kda_rule_flops(dims, seqlen))
    mla = (tokens * mla_projection_flops(dims)
           + 2 * batch * visible_pairs(seqlen) * H
           * (dims["qk_nope_dim"] + dims["qk_rope_dim"] + dims["v_head_dim"]))
    dense = tokens * 6 * C * dims["hidden_size"]
    held = (dims["experts_per_token"] * dims["experts_held"]
            / dims["num_experts"])
    moe = tokens * (2 * C * dims["num_experts"]
                    + (held * dims["expert_hidden_size"]
                       + dims["shared_expert_hidden_size"]) * 6 * C)
    head = 2 * batch * (seqlen - 1) * C * dims["vocab_size"]
    experts = dims["num_layers"] - dims["dense_ffn_layers"]
    return (kinds(dims, "kda") * kda + kinds(dims, "mla") * mla
            + dims["dense_ffn_layers"] * dense + experts * moe + head)


def train_flops(ctx):
    """The window's steps, three forwards' worth each (the backward
    pass multiplies each product's operands twice more)."""
    tr = ctx.facts["traffic"]
    return ctx.facts["steps"] * 3 * forward_flops(
        ctx.dims, tr["batch"], tr["seqlen"])


def kda_rule_bytes(dims, seqlen):
    """One row's float32 bytes of one layer's rule, forward and
    backward: q, k, v, the decay a key channel and beta read and o
    written forward; those five and o's cotangent read and their five
    gradients written backward."""
    H, d = dims["kda_heads"], dims["kda_head_dim"]
    operands = 4 * H * d + H
    return ITEMSIZE * seqlen * ((operands + H * d)
                                + (2 * operands + H * d))


def kda_chunk(ctx):
    """(operations, bytes) of the KDA layers' chunked delta rule over
    the window's steps: its products once forward and twice
    backward."""
    tr, d = ctx.facts["traffic"], ctx.dims
    rows = ctx.facts["steps"] * tr["batch"] * kinds(d, "kda")
    return (rows * 3 * kda_rule_flops(d, tr["seqlen"]),
            rows * kda_rule_bytes(d, tr["seqlen"]))


def flash_training(ctx):
    """(operations, bytes) of the latent attention's flash kernels over
    the window's steps: the visible pairs only, scores and dQ, dK at
    q's width (192), the product with V, dP and dV at v's (128), no
    recomputation of the scores; q, o, dO and dQ of every query head,
    K, V, dK and dV of every head (each head its own K and V)."""
    tr, d = ctx.facts["traffic"], ctx.dims
    B, L, H = tr["batch"], tr["seqlen"], d["num_heads"]
    Dqk = d["qk_nope_dim"] + d["qk_rope_dim"]
    widths = 3 * (Dqk + d["v_head_dim"])
    layers = ctx.facts["steps"] * kinds(d, "mla")
    return (layers * 2 * B * visible_pairs(L) * H * widths,
            layers * KERNEL_ITEMSIZE * B * L * widths * 2 * H)
