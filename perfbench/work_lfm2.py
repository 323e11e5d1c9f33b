"""The work of the ``lfm2-24b-a2b`` cells, counted from shapes and from
what the run recorded: the model's FLOPs a step, the gated short
convolution's bytes, the flash kernels' visible (query, key) pairs, the
expert products' routed rows.  The same work whatever implements it;
recomputation is never counted.  The configuration and the metric files
name these functions as ``module:function``."""
from .work_mellum import visible_pairs

ITEMSIZE = 4            # float32 activations and weights
KERNEL_ITEMSIZE = 2     # the flash kernels' q, k, v and outputs: bfloat16


def kinds(dims, kind):
    return sum(k == kind for k in dims["layer_types"])


def expert_layers(dims):
    return dims["num_layers"] - dims["dense_ffn_layers"]


def expert_row_flops(dims):
    """One routed row through one gated expert, forward: gate, up and
    down products."""
    return 6 * dims["units"] * dims["expert_hidden_size"]


def forward_flops(dims, batch, seqlen):
    """Every matrix product of one forward pass (2 FLOPs a
    multiply-add): a gated short convolution's two projections;
    attention's projections and its scores and their product with V
    over the visible pairs; a dense feed-forward's three products; an
    expert layer's router and the held experts by the expected share of
    a token's chosen experts that is held here; the (tied) head over the
    positions that have a next token.  No convolution, no embedding
    lookup, no recomputation."""
    C, D = dims["units"], dims["head_dim"]
    H, Hkv = dims["num_heads"], dims["num_kv_heads"]
    tokens = batch * seqlen
    conv = tokens * (2 * C * 3 * C + 2 * C * C)
    attn = (2 * tokens * C * (2 * H * D + 2 * Hkv * D)
            + 2 * 2 * batch * visible_pairs(seqlen) * H * D)
    dense = tokens * 6 * C * dims["hidden_size"]
    held = (dims["experts_per_token"] * dims["experts_held"]
            / dims["num_experts"])
    moe = tokens * (2 * C * dims["num_experts"]
                    + held * expert_row_flops(dims))
    head = 2 * batch * (seqlen - 1) * C * dims["vocab_size"]
    return (kinds(dims, "conv") * conv
            + kinds(dims, "full_attention") * attn
            + dims["dense_ffn_layers"] * dense + expert_layers(dims) * moe
            + head)


def train_flops(ctx):
    """The window's steps, three forwards' worth each (the backward
    pass multiplies each product's operands twice more)."""
    tr = ctx.facts["traffic"]
    return ctx.facts["steps"] * 3 * forward_flops(
        ctx.dims, tr["batch"], tr["seqlen"])


def gated_conv_token(dims):
    """(operations, bytes) of one token through one layer's gated short
    convolution, forward and backward.  Forward: ``B * u``, K
    multiplies and K - 1 adds, ``C *`` a channel; the (3C) input read
    and the (C) result written, once.  Backward: twice the operations;
    the input and the cotangent read, the input's gradient written,
    once (the taps' gradient is K numbers a channel for the whole row).
    Bytes bound it."""
    C, K = dims["units"], dims["conv_kernel"]
    return 3 * (2 * K + 1) * C, ITEMSIZE * C * ((3 + 1) + (3 + 1 + 3))


def gated_conv(ctx):
    """(operations, bytes) of the gated short convolutions (what lies
    between the operators' two projections) over the window's steps."""
    tr, d = ctx.facts["traffic"], ctx.dims
    tokens = (ctx.facts["steps"] * tr["batch"] * tr["seqlen"]
              * kinds(d, "conv"))
    ops, nbytes = gated_conv_token(d)
    return tokens * ops, tokens * nbytes


def flash_training(ctx):
    """(operations, bytes) of the attention layers' flash kernels over
    the window's steps: the visible pairs only, 2 products forward and 4
    backward, no recomputation of the scores; q, o, dO and dQ of every
    query head, K, V, dK and dV once a group (4 query heads of 64 a
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
    from .adapters import lfm2_moe
    window = lfm2_moe.WINDOW
    if not window or window["steps"] != ctx.facts["steps"]:
        return None
    d = ctx.dims
    rows = float(window["rows"].sum())
    C = d["units"]
    ops = 3 * rows * expert_row_flops(d)
    weights = (expert_layers(d) * d["experts_held"] * 3 * C
               * d["expert_hidden_size"])
    nbytes = ITEMSIZE * 3 * (window["steps"] * weights + rows * 2 * C)
    return ops, nbytes
