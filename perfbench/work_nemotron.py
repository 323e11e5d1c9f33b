"""The work of the ``nemotron-3-nano-30b-a3b`` cells, counted from
shapes and from what the run recorded: the model's FLOPs a step, the
selective scan's products and bytes, the flash kernels' visible (query,
key) pairs, the expert products' routed rows.
The same work whatever implements it (the scan's count is the chunked
form's at the configuration's chunk, the least the published algorithm
does; a recurrence one position at a time would do more); recomputation
is never counted.  The configuration and the metric files name these
functions as ``module:function``."""
from .work_mellum import visible_pairs

ITEMSIZE = 4            # float32 activations and weights
KERNEL_ITEMSIZE = 2     # the flash kernels' q, k, v and outputs: bfloat16


def kinds(dims, kind):
    return sum(k == kind for k in dims["layer_types"])


def scan_token_flops(dims):
    """One token through one layer's scan, forward: ``C B^T`` over the
    chunk a group, the masked product with the chunk's x a head, the
    chunk's contribution to the state and the reading of the state
    carried in, a head each."""
    Q, N, P = dims["chunk_size"], dims["ssm_state_size"], \
        dims["mamba_head_dim"]
    H, G = dims["mamba_num_heads"], dims["n_groups"]
    return 2 * Q * N * G + 2 * Q * P * H + 2 * 2 * N * P * H


def scan_token_bytes(dims):
    """x, B, C, dt read and y written, once, float32."""
    H, G = dims["mamba_num_heads"], dims["n_groups"]
    inner = H * dims["mamba_head_dim"]
    return ITEMSIZE * (2 * inner + 2 * G * dims["ssm_state_size"] + H)


def expert_row_flops(dims):
    """One routed row through one plain (not gated) expert, forward: the
    up and the down product."""
    return 4 * dims["units"] * dims["expert_hidden_size"]


def forward_flops(dims, batch, seqlen):
    """Every matrix product of one forward pass (2 FLOPs a
    multiply-add): a Mamba-2 mixer's two projections and its scan's
    products; attention's projections and its scores and their product
    with V over the visible pairs; an expert layer's router, its shared
    expert and the held routed experts by the expected share of a
    token's chosen experts that is held here; the head over the
    positions that have a next token.  No convolution, no embedding
    lookup, no recomputation."""
    C, D = dims["units"], dims["head_dim"]
    H, Hkv = dims["num_heads"], dims["num_kv_heads"]
    mh, mp = dims["mamba_num_heads"], dims["mamba_head_dim"]
    inner = mh * mp
    tokens = batch * seqlen
    in_width = 2 * inner + 2 * dims["n_groups"] * dims["ssm_state_size"] + mh
    mamba = tokens * (2 * C * in_width + 2 * inner * C
                      + scan_token_flops(dims))
    attn = (2 * tokens * C * (2 * H * D + 2 * Hkv * D)
            + 2 * 2 * batch * visible_pairs(seqlen) * H * D)
    held = (dims["experts_per_token"] * dims["experts_held"]
            / dims["num_experts"])
    moe = tokens * (2 * C * dims["num_experts"]
                    + 4 * C * dims["shared_expert_hidden_size"]
                    + held * expert_row_flops(dims))
    head = 2 * batch * (seqlen - 1) * C * dims["vocab_size"]
    return (kinds(dims, "mamba2") * mamba + kinds(dims, "attention") * attn
            + kinds(dims, "moe") * moe + head)


def train_flops(ctx):
    """The window's steps, three forwards' worth each (the backward
    pass multiplies each product's operands twice more)."""
    tr = ctx.facts["traffic"]
    return ctx.facts["steps"] * 3 * forward_flops(
        ctx.dims, tr["batch"], tr["seqlen"])


def ssm_scan(ctx):
    """(operations, bytes) of the selective scans over the window's
    steps: the forward products and twice that backward; x, B, C, dt and
    y once forward, they and their gradients backward."""
    tr, d = ctx.facts["traffic"], ctx.dims
    tokens = (ctx.facts["steps"] * tr["batch"] * tr["seqlen"]
              * kinds(d, "mamba2"))
    return (3 * tokens * scan_token_flops(d),
            3 * tokens * scan_token_bytes(d))


def flash_training(ctx):
    """(operations, bytes) of the attention layers' flash kernels over
    the window's steps, counted as ``work_mellum.flash_training`` counts
    Mellum's: the visible pairs only, 2 products forward and 4 backward,
    no recomputation of the scores; q, o, dO and dQ of every query head,
    K, V, dK and dV once a group (here 16 query heads a group)."""
    tr, d = ctx.facts["traffic"], ctx.dims
    B, L, D = tr["batch"], tr["seqlen"], d["head_dim"]
    H, Hkv = d["num_heads"], d["num_kv_heads"]
    layers = ctx.facts["steps"] * kinds(d, "attention")
    return (layers * 2 * B * visible_pairs(L) * H * D * (2 + 4),
            layers * KERNEL_ITEMSIZE * B * L * D * (2 + 4) * (H + Hkv))


def expert_products(ctx):
    """(operations, bytes) of the grouped expert products over the
    window's steps, from the rows the program's device-side counter
    counted there: a row's two products forward and twice that backward;
    the held experts' weights once a pass (forward, the rows' gradient,
    the weights' gradient), the rows in and out of each.  Nothing where
    the counter was not read around this window."""
    from .adapters import nemotron_h
    window = nemotron_h.WINDOW
    if not window or window["steps"] != ctx.facts["steps"]:
        return None
    d = ctx.dims
    rows = float(window["rows"].sum())
    C = d["units"]
    ops = 3 * rows * expert_row_flops(d)
    weights = (kinds(d, "moe") * d["experts_held"] * 2 * C
               * d["expert_hidden_size"])
    nbytes = ITEMSIZE * 3 * (window["steps"] * weights + rows * 2 * C)
    return ops, nbytes
