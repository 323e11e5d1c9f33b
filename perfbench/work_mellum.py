"""The work of the ``mellum2-12b-a2.5b`` cells, counted from shapes and
from what the run recorded: the model's FLOPs a step, the flash
kernels' visible (query, key) pairs, the expert products' routed rows.
The same work whatever implements it; recomputation is never counted.
Configurations and metric files name these functions as
``module:function``."""
KERNEL_ITEMSIZE = 2         # q, k, v and the kernels' outputs: bfloat16
WEIGHT_ITEMSIZE = 4


def visible_pairs(seqlen, window=None):
    """(query, key) pairs under the causal mask, key s visible to query
    t iff s <= t and, with a window, t - window < s."""
    if window is None or window >= seqlen:
        return seqlen * (seqlen + 1) // 2
    return window * (window + 1) // 2 + (seqlen - window) * window


def layer_pairs(dims, seqlen):
    """Visible pairs of each layer, by its kind."""
    return [visible_pairs(seqlen, dims["window"]
                          if kind == "sliding_attention" else None)
            for kind in dims["layer_types"]]


def forward_flops(dims, batch, seqlen):
    """Every matrix product of one forward pass (2 FLOPs a
    multiply-add): projections, router, the scores and their product
    with V over the visible pairs, the held experts by the expected
    share of a token's chosen experts that is held here, the head over
    the positions that have a next token."""
    C, D = dims["units"], dims["head_dim"]
    H, Hkv = dims["num_heads"], dims["num_kv_heads"]
    tokens = batch * seqlen
    proj = 2 * tokens * C * (2 * H * D + 2 * Hkv * D)     # q, o; k, v
    router = 2 * tokens * C * dims["num_experts"]
    held = (dims["experts_per_token"] * dims["experts_held"]
            / dims["num_experts"])
    experts = tokens * held * expert_row_flops(dims)
    attn = sum(2 * 2 * batch * pairs * H * D
               for pairs in layer_pairs(dims, seqlen))
    head = 2 * batch * (seqlen - 1) * C * dims["vocab_size"]
    return dims["num_layers"] * (proj + router + experts) + attn + head


def expert_row_flops(dims):
    """One routed row through one gated expert, forward: gate, up and
    down products."""
    return 6 * dims["units"] * dims["expert_hidden_size"]


def train_flops(ctx):
    """The window's steps, three forwards' worth each (the backward
    pass multiplies each product's operands twice more)."""
    tr = ctx.facts["traffic"]
    return ctx.facts["steps"] * 3 * forward_flops(
        ctx.dims, tr["batch"], tr["seqlen"])


def flash_training(ctx):
    """(operations, bytes) of the flash kernels over the window's
    steps: the visible pairs only, 2 products forward and 4 backward,
    no recomputation of the scores; q, o, dO and dQ of every query head,
    K, V, dK and dV once a group."""
    tr, d = ctx.facts["traffic"], ctx.dims
    B, L, D = tr["batch"], tr["seqlen"], d["head_dim"]
    H, Hkv = d["num_heads"], d["num_kv_heads"]
    ops = sum(2 * B * pairs * H * D * (2 + 4)
              for pairs in layer_pairs(d, L))
    per_layer = KERNEL_ITEMSIZE * B * L * D * ((2 + 4) * H + (2 + 4) * Hkv)
    steps = ctx.facts["steps"]
    return steps * ops, steps * d["num_layers"] * per_layer


def expert_products(ctx):
    """(operations, bytes) of the grouped expert products over the
    window's steps, from the rows the program's device-side counter
    counted there: a row's three products forward and twice that
    backward; the held experts' weights once a pass (forward, the rows'
    gradient, the weights' gradient), the rows in and out of each.
    Nothing where the counter was not read around this window."""
    from .adapters import mellum_moe
    window = mellum_moe.WINDOW
    if not window or window["steps"] != ctx.facts["steps"]:
        return None
    d = ctx.dims
    rows = float(window["rows"].sum())
    C = d["units"]
    ops = 3 * rows * expert_row_flops(d)
    weights = (d["num_layers"] * d["experts_held"] * 3 * C
               * d["expert_hidden_size"])
    nbytes = WEIGHT_ITEMSIZE * 3 * (window["steps"] * weights + rows * 2 * C)
    return ops, nbytes

