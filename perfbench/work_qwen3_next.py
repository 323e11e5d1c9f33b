"""The work of the ``qwen3-next-80b-a3b`` cells, counted from shapes and
from what the run recorded: the model's FLOPs a step, the chunked gated
delta rule's products and bytes, the flash kernels' visible (query, key)
pairs, the expert products' routed rows.  The same work whatever
implements it; recomputation is never counted.  The configuration and
the metric files name these functions as ``module:function``."""
import math

from .work_mellum import visible_pairs

ITEMSIZE = 4            # float32 activations and weights
KERNEL_ITEMSIZE = 2     # the flash kernels' q, k, v and outputs: bfloat16


def kinds(dims, kind):
    return sum(k == kind for k in dims["layer_types"])


def expert_row_flops(dims):
    """One routed row through one SwiGLU expert, forward: gate, up and
    down products."""
    return 6 * dims["units"] * dims["expert_hidden_size"]


def delta_rule_flops(dims, seqlen):
    """Forward FLOPs of one row's chunked gated delta rule in one layer,
    as ``ops.deltanet`` computes it, chunks of Q positions: per key head
    and chunk ``K K^T`` and ``Q K^T`` (2 Q^2 dk each); per value head and
    chunk the triangular inverse's 2 (log2 Q - 1) products of Q^3
    multiply-adds, ``W`` and the writes' first part (2 Q^2 dk, 2 Q^2 dv),
    the state's three products with the chunk (``W S``, ``Q S``,
    ``K^T U``: 2 Q dk dv each) and the masked product with the writes
    (2 Q^2 dv).  The last chunk is counted whole."""
    Q = dims["chunk"]
    Hk, H = dims["linear_key_heads"], dims["linear_value_heads"]
    dk, dv = dims["linear_key_dim"], dims["linear_value_dim"]
    chunks = -(-seqlen // Q)
    key = 2 * 2 * Q * Q * dk
    value = (2 * max(int(math.log2(Q)) - 1, 0) * 2 * Q ** 3
             + 2 * Q * Q * (dk + dv) + 3 * 2 * Q * dk * dv + 2 * Q * Q * dv)
    return chunks * (Hk * key + H * value)


def forward_flops(dims, batch, seqlen):
    """Every matrix product of one forward pass (2 FLOPs a
    multiply-add): a DeltaNet mixer's projections and its chunked rule;
    attention's projections (q and its gate, k and v, out) and its
    scores and their product with V over the visible pairs; an expert
    layer's router, the held experts by the expected share of a token's
    chosen experts that is held here, the shared expert and its gate;
    the head over the positions that have a next token.  No
    convolution, no embedding lookup, no recomputation."""
    C, D = dims["units"], dims["head_dim"]
    H, Hkv = dims["num_heads"], dims["num_kv_heads"]
    Hk, Hv = dims["linear_key_heads"], dims["linear_value_heads"]
    dk, dv = dims["linear_key_dim"], dims["linear_value_dim"]
    tokens = batch * seqlen
    gdn = (tokens * 2 * C * (2 * Hk * dk + 2 * Hv * dv + 2 * Hv + Hv * dv)
           + batch * delta_rule_flops(dims, seqlen))
    attn = (2 * tokens * C * (2 * H * D + 2 * Hkv * D + H * D)
            + 2 * 2 * batch * visible_pairs(seqlen) * H * D)
    held = (dims["experts_per_token"] * dims["experts_held"]
            / dims["num_experts"])
    Hs = dims["shared_expert_hidden_size"]
    moe = tokens * (2 * C * dims["num_experts"]
                    + held * expert_row_flops(dims) + 6 * C * Hs + 2 * C)
    head = 2 * batch * (seqlen - 1) * C * dims["vocab_size"]
    return (kinds(dims, "linear_attention") * gdn
            + kinds(dims, "full_attention") * attn
            + dims["num_layers"] * moe + head)


def train_flops(ctx):
    """The window's steps, three forwards' worth each (the backward
    pass multiplies each product's operands twice more)."""
    tr = ctx.facts["traffic"]
    return ctx.facts["steps"] * 3 * forward_flops(
        ctx.dims, tr["batch"], tr["seqlen"])


def delta_rule_bytes(dims, seqlen):
    """One row's float32 bytes of one layer's rule, forward and
    backward: q, k, v, g and beta read and o written forward; those five
    and o's cotangent read and their five gradients written backward."""
    Hk, H = dims["linear_key_heads"], dims["linear_value_heads"]
    dk, dv = dims["linear_key_dim"], dims["linear_value_dim"]
    operands = 2 * Hk * dk + H * dv + 2 * H
    return ITEMSIZE * seqlen * ((operands + H * dv)
                                + (2 * operands + H * dv))


def gdn_chunk(ctx):
    """(operations, bytes) of the DeltaNet layers' chunked delta rule
    over the window's steps: its products once forward and twice
    backward."""
    tr, d = ctx.facts["traffic"], ctx.dims
    rows = ctx.facts["steps"] * tr["batch"] * kinds(d, "linear_attention")
    return (rows * 3 * delta_rule_flops(d, tr["seqlen"]),
            rows * delta_rule_bytes(d, tr["seqlen"]))


def flash_training(ctx):
    """(operations, bytes) of the attention layer's flash kernels over
    the window's steps: the visible pairs only, 2 products forward and 4
    backward, no recomputation of the scores; q, o, dO and dQ of every
    query head, K, V, dK and dV once a group (8 query heads of 256 a
    key/value head)."""
    tr, d = ctx.facts["traffic"], ctx.dims
    B, L, D = tr["batch"], tr["seqlen"], d["head_dim"]
    H, Hkv = d["num_heads"], d["num_kv_heads"]
    layers = ctx.facts["steps"] * kinds(d, "full_attention")
    return (layers * 2 * B * visible_pairs(L) * H * D * (2 + 4),
            layers * KERNEL_ITEMSIZE * B * L * D * (2 + 4) * (H + Hkv))


def expert_products(ctx):
    """(operations, bytes) of the grouped expert products over the
    window's steps, from the rows the program's device-side counter
    counted there: a row's three products forward and twice that
    backward; the held experts' weights once a pass (forward, the rows'
    gradient, the weights' gradient), the rows in and out of each.
    Nothing where the counter was not read around this window."""
    from .adapters import qwen3_next
    window = qwen3_next.WINDOW
    if not window or window["steps"] != ctx.facts["steps"]:
        return None
    d = ctx.dims
    rows = float(window["rows"].sum())
    C = d["units"]
    ops = 3 * rows * expert_row_flops(d)
    weights = (d["num_layers"] * d["experts_held"] * 3 * C
               * d["expert_hidden_size"])
    nbytes = ITEMSIZE * 3 * (window["steps"] * weights + rows * 2 * C)
    return ops, nbytes
