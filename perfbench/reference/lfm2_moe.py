"""LiquidAI LFM2-24B-A2B's layers (config.json, model_type lfm2_moe),
plain float32.  Every layer is an operator and a feed-forward, each
under its RMSNorm with a residual: ``h = x + operator(norm(x))``,
``y = h + ffn(norm(h))``; one RMSNorm after the last layer; the head
reads the embedding's rows (tied).

- operator "conv", the gated short convolution: ``[B | C | u] = m W_in``;
  ``v = B * u``; ``c[t] = sum_j w[:, j] * v[t - (K - 1) + j]`` with zeros
  before the row's start, written as its K-term sum over a row padded
  with K - 1 zeros, no bias and no activation; ``(C * c) W_out``;
- operator "full_attention": 32 query heads over 8 key/value heads of
  64, an RMS norm over the 64 channels of every head of q and of k
  (a gain each, shared by the heads) BEFORE the rotation, rotary
  positions in the rotate-half layout over the whole head (theta
  1,000,000), dense scores under the causal mask, scale 64^-0.5;
- feed-forward of the first ``dense_ffn_layers`` layers: dense SwiGLU,
  ``(silu(m W_gate) * (m W_up)) W_down``; of the others: sigmoid scores
  over all 64 experts in float32, the 4 largest of score + bias chosen
  (ties to the lower id; the bias chooses and does not weigh), weighted
  by the scores over (their sum + 1e-6), times the scaling factor; SwiGLU
  experts, a loop over the held experts with a mask; no shared expert.

This is one chip's share of a layer divided over several chips: the
experts ``first_expert .. first_expert + experts_held - 1`` and a slice
of the vocabulary.  The router keeps its whole width and the chosen
weights are normalised over all chosen experts, held here or not; what
the absent experts would have added is left out, and that partial
result goes on to the next layer.  With every expert held it is the
whole layer.  Operators, the dense feed-forward and the norms are every
chip's.

The canonical weight tree is a flat dict, per-layer leaves
``l<i>.<leaf>``.  ``in_w`` holds [B | C | u] rows, ``kv_w`` [k | v]
rows, ``ffn_w1`` [gate | up] rows, ``w1`` [gate | up] columns.  Nothing
here is another family's: the norm, the rotation, AdamW and the step
loop are written out.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

OPERATOR_LEAVES = {
    "conv": ("op_norm_g", "in_w", "conv_w", "out_w"),
    "full_attention": ("op_norm_g", "q_w", "kv_w", "o_w", "q_norm_g",
                       "k_norm_g"),
}
FFN_LEAVES = {
    "dense": ("ffn_norm_g", "ffn_w1", "ffn_w2"),
    "moe": ("ffn_norm_g", "router_w", "router_bias", "w1", "w2"),
}
FAULTS = (None, "two_taps", "no_out_gate", "no_qk_norm", "top3", "no_bias")
FROZEN = ("router_w", "router_bias")
Q_BLOCK = 1024          # rows of the score matrix, and of the logits, that
                        # exist at once
CONV_TAP_RANGE = 0.5    # the convolution's taps, uniform(-0.5, 0.5)
ROUTER_BIAS_STD = 0.01


def ffn_kind(dims, i):
    return "dense" if i < dims["dense_ffn_layers"] else "moe"


def layer_leaves(dims, i):
    return (OPERATOR_LEAVES[dims["layer_types"][i]]
            + FFN_LEAVES[ffn_kind(dims, i)])


def weight_shapes(dims):
    C, V = dims["units"], dims["vocab_size"]
    H, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    n, Hd, E = (dims["experts_held"], dims["expert_hidden_size"],
                dims["num_experts"])
    I = dims["hidden_size"]
    per_layer = {
        "op_norm_g": (C,), "in_w": (3 * C, C),
        "conv_w": (C, dims["conv_kernel"]), "out_w": (C, C),
        "q_w": (H * D, C), "kv_w": (2 * Hkv * D, C), "o_w": (C, H * D),
        "q_norm_g": (D,), "k_norm_g": (D,),
        "ffn_norm_g": (C,), "ffn_w1": (2 * I, C), "ffn_w2": (C, I),
        "router_w": (C, E), "router_bias": (E,), "w1": (n, C, 2 * Hd),
        "w2": (n, Hd, C)}
    shapes = {"embed": (V, C)}
    for i in range(dims["num_layers"]):
        for leaf in layer_leaves(dims, i):
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    shapes["final_norm_g"] = (C,)
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    """Every leaf from the seed (the configuration's ``assumed``):
    matrices normal(0, 0.02), the embedding's rows among them (they are
    the tied head's too: rows of 1, the other decoders' choice, make
    the head score a position's own token at 2048 over the stream's
    RMS, the softmax one-hot and the first loss 1,280: PERF.md section
    6, PR 39), gains 1 + normal(0, 0.02), the convolution's taps
    uniform(-0.5, 0.5), the router's choice bias normal(0, 0.01)."""
    w = common.init_from_shapes(weight_shapes(dims), seed, jnp.float32)

    def special(key):
        out = {}
        for i, name in enumerate(sorted(w)):
            k = jax.random.fold_in(key, i)
            if name.endswith(".conv_w"):
                out[name] = jax.random.uniform(
                    k, w[name].shape, minval=-CONV_TAP_RANGE,
                    maxval=CONV_TAP_RANGE)
            elif name.endswith(".router_bias"):
                out[name] = ROUTER_BIAS_STD * jax.random.normal(
                    k, w[name].shape)
        return out

    w.update(jax.jit(special)(jax.random.fold_in(common.seed_key(seed), 39)))
    return {n: a.astype(dtype) for n, a in w.items()}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


# ---------------------------------------------------- the short convolution
def short_conv(v, w, taps=None):
    """``c[t, ch] = sum_j w[ch, j] v[t - (K - 1) + j, ch]``, zeros before
    the row's start: the K-term sum over a row padded with K - 1 zeros.
    ``taps``: the taps that count (a planted fault leaves one out)."""
    K, L = w.shape[1], v.shape[1]
    vp = jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(v)
    for j in (range(K) if taps is None else taps):
        out = out + w[:, j] * vp[:, j:j + L]
    return out


def conv_operator(g, m, fault=None):
    """m (b, L, C) -> (b, L, C)."""
    C = m.shape[-1]
    bcu = m @ g["in_w"].T
    B, Cg, u = bcu[..., :C], bcu[..., C:2 * C], bcu[..., 2 * C:]
    K = g["conv_w"].shape[1]
    c = short_conv(B * u, g["conv_w"],
                   range(1, K) if fault == "two_taps" else None)
    return (c if fault == "no_out_gate" else Cg * c) @ g["out_w"].T


# ---------------------------------------------------------------- attention
def rope_tables(dims, length):
    """(cos, sin), each (length, head_dim / 2): ``rope_type`` default."""
    D = dims["head_dim"]
    inv = dims["rope_theta"] ** (-np.arange(D // 2, dtype=np.float64)
                                 * 2.0 / D)
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32))
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, cos, sin):
    """x (B, L, heads, D): the pairs (i, i + D/2) turned by the angle of
    their position (the rotate-half layout)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(g, x, cos, sin, dims, fault=None):
    """Dense causal attention, the scores taken ``Q_BLOCK`` queries at a
    time; each head of q and k normed, then rotated."""
    B, L, _ = x.shape
    H, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    G, eps = H // Hkv, dims["rms_norm_eps"]
    q = (x @ g["q_w"].T).reshape(B, L, H, D)
    kv = x @ g["kv_w"].T
    k = kv[..., :Hkv * D].reshape(B, L, Hkv, D)
    v = kv[..., Hkv * D:].reshape(B, L, Hkv, D)
    if fault != "no_qk_norm":
        q = rms_norm(q, g["q_norm_g"], eps)
        k = rms_norm(k, g["k_norm_g"], eps)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    q = q.reshape(B, L, Hkv, G, D)          # query head j reads j // G
    qb = min(L, Q_BLOCK)
    s_pos = jnp.arange(L)

    def rows(start):
        t_pos = start + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("btkgd,bskd->bkgts", qs, k) / math.sqrt(D)
        seen = s_pos[None, :] <= t_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", p, v)

    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, L, qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * D)
    return o @ g["o_w"].T


# ------------------------------------------------------------ feed-forwards
def dense_ffn(g, m):
    gate, up = jnp.split(m @ g["ffn_w1"].T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ g["ffn_w2"].T


def route(g, m, dims, fault=None):
    """(ids (S, k), weights (S, k)): sigmoid scores over all experts;
    the k largest of score + bias (ties to the lower id); the scores at
    those ids over (their sum + ``route_eps``), times the scaling
    factor."""
    k = dims["experts_per_token"] - (fault == "top3")
    s = jax.nn.sigmoid((m @ g["router_w"]).astype(jnp.float32))
    choice = s if fault == "no_bias" else s + g["router_bias"].astype(
        jnp.float32)
    ids = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + dims["route_eps"])
    return ids, w * dims["routed_scaling_factor"]


def experts(g, m, ids, w, dims):
    """The held experts' part: a loop over them, each on every token,
    weighted; zero where the expert was not among the token's chosen."""
    S = m.shape[0]
    lo, n = dims["first_expert"], dims["experts_held"]
    full = jnp.zeros((S, dims["num_experts"]), w.dtype).at[
        jnp.arange(S)[:, None], ids].set(w)

    def one(expert):
        w1, w2, weight = expert             # (C, 2H), (H, C), (S,)
        gate, up = jnp.split(m @ w1, 2, axis=-1)
        return (jax.nn.silu(gate) * up * weight[:, None]) @ w2

    return jax.lax.map(jax.checkpoint(one), (
        g["w1"], g["w2"], full[:, lo:lo + n].T.astype(m.dtype))).sum(0)


def moe(g, m, dims, fault=None):
    """m (S, C) -> (the held experts' part, the chosen experts (S, k))."""
    ids, w = route(g, m, dims, fault)
    return experts(g, m, ids, w.astype(m.dtype), dims), ids


# ------------------------------------------------------------------ the model
def hidden(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (the last layer's output under the final
    norm (B, L, C), the chosen experts of every expert layer (expert
    layers, B * L, k))."""
    B, L = tokens.shape
    dtype = w["embed"].dtype
    eps = dims["rms_norm_eps"]
    cos, sin = (t.astype(dtype) for t in rope_tables(dims, L))

    def block(x, g, kind, ffn):
        m = rms_norm(x, g["op_norm_g"], eps)
        if kind == "conv":
            h = x + conv_operator(g, m, fault)
        else:
            h = x + attention(g, m, cos, sin, dims, fault)
        m = rms_norm(h, g["ffn_norm_g"], eps)
        if ffn == "dense":
            return h + dense_ffn(g, m), None
        y, ids = moe(g, m.reshape(B * L, -1), dims, fault)
        return h + y.reshape(B, L, -1), ids

    # each layer is computed again in the backward pass, so that a
    # layer's scores and feed-forward activations exist once; the layers
    # are written out, not scanned: they differ in kind
    x, chosen = w["embed"][tokens], []
    for i, kind in enumerate(dims["layer_types"]):
        x, ids = jax.checkpoint(block, static_argnums=(2, 3))(
            x, {leaf: w[f"l{i}.{leaf}"] for leaf in layer_leaves(dims, i)},
            kind, ffn_kind(dims, i))
        if ids is not None:
            chosen.append(ids)
    return rms_norm(x, w["final_norm_g"], eps), jnp.stack(chosen)


def forward(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (logits (B, L, V), the chosen experts)."""
    x, ids = hidden(w, dims, tokens, fault)
    return x @ w["embed"].T, ids


def loss_fn(w, dims, tokens, fault=None):
    """Mean next-token cross-entropy over positions 0 .. L-2, the
    logits (against the embedding's rows) taken ``Q_BLOCK`` positions at
    a time."""
    x, _ = hidden(w, dims, tokens, fault)
    B, L = tokens.shape
    qb = min(L, Q_BLOCK)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    counted = jnp.arange(L) < L - 1             # the last has no next token

    def some(start):
        xs = jax.lax.dynamic_slice_in_dim(x, start, qb, axis=1)
        ys = jax.lax.dynamic_slice_in_dim(labels, start, qb, axis=1)
        logp = jax.nn.log_softmax(
            (xs @ w["embed"].T).astype(jnp.float32), -1)
        picked = jnp.take_along_axis(logp, ys[..., None], -1)[..., 0]
        return -(picked * jax.lax.dynamic_slice_in_dim(counted, start, qb)
                 ).sum()

    total = jax.lax.map(jax.checkpoint(some), jnp.arange(0, L, qb)).sum()
    return total / (B * (L - 1))


def adamw(w, g, m, v, step, opt, frozen=()):
    """AdamW as the configuration states it (decay on every leaf);
    leaves whose name ends in one of ``frozen`` keep their value."""
    b1, b2 = opt["beta1"], opt["beta2"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m = {n: b1 * m[n] + (1 - b1) * g[n] for n in w}
    v = {n: b2 * v[n] + (1 - b2) * g[n] ** 2 for n in w}
    w = {n: w[n] if n.endswith(tuple(frozen)) else
         w[n] - opt["learning_rate"] * (
             (m[n] / c1) / (jnp.sqrt(v[n] / c2) + opt["eps"])
             + opt["weight_decay"] * w[n]) for n in w}
    return w, m, v


# ------------------------------------------------------------ the comparison
def views(name, a, dims):
    """The leaves as the comparison sees them: the in-projection's B, C
    and u parts, the fused projection's key and value parts and the
    dense feed-forward's gate and up parts are leaves of their own, so
    that a fault in one part is not averaged away over the whole.  A
    layer's held experts stay ONE leaf a matrix: an expert here sees 512
    of a step's rows, the few tokens whose fourth and fifth experts are
    nearly tied choose otherwise in the program, and one expert's
    gradient norm swings with them where the layer's does not
    (``reference/nemotron_h.py`` found it; PERF.md section 2)."""
    if name.endswith(".in_w"):
        C = a.shape[0] // 3
        return {f"{name}.B": a[:C], f"{name}.C": a[C:2 * C],
                f"{name}.u": a[2 * C:]}
    if name.endswith(".kv_w"):
        half = a.shape[0] // 2
        return {f"{name}.k": a[:half], f"{name}.v": a[half:]}
    if name.endswith(".ffn_w1"):
        half = a.shape[0] // 2
        return {f"{name}.gate": a[:half], f"{name}.up": a[half:]}
    return {name: a}


def leaf_sizes(dims):
    """Elements of each leaf as :func:`views` splits them."""
    return {n: int(v.size) for name, shape in weight_shapes(dims).items()
            for n, v in views(name, np.empty(shape, np.bool_), dims).items()}


def leaf_norms(tree, dims):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for name, a in tree.items()
            for n, v in views(name, a, dims).items()}


@functools.lru_cache(maxsize=None)
def _programs(dims_json, opt_items, dtype, fault):
    """The reference's jitted pieces for one size, optimizer, type and
    planted fault (cached, so that a process that follows many seeds
    traces once)."""
    dims, opt = json.loads(dims_json), dict(opt_items)
    store = jnp.float32 if dtype is None else dtype
    grad = jax.jit(jax.value_and_grad(
        lambda w, tokens: loss_fn(w, dims, tokens, fault)
        .astype(jnp.float32)))
    add = jax.jit(lambda acc, g, scale: {
        n: acc[n] + scale * g[n].astype(jnp.float32) for n in acc},
        donate_argnums=(0,))
    update = jax.jit(
        lambda w, g, m, v, step: jax.tree_util.tree_map(
            lambda a: a.astype(store),      # the control stays in its type
            adamw(w, {n: g[n].astype(store) for n in g}, m, v, step, opt,
                  () if dims["train_router"] else FROZEN)),
        donate_argnums=(0, 2, 3))
    delta = jax.jit(lambda w, w0: leaf_norms(
        {n: w[n].astype(jnp.float32) - w0[n].astype(jnp.float32)
         for n in w}, dims))
    norms = jax.jit(lambda t: leaf_norms(t, dims))
    return grad, add, update, delta, norms


def chosen_experts(dims, seed, tokens):
    """The chosen experts of every expert layer, (expert layers,
    rows * L, k), in the first step's forward pass from the seed's
    weights."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, t: forward(w, dims, t)[1])(
            init_weights(dims, seed), jnp.asarray(tokens))


def train_steps(dims, opt, seed, batches, rows_per_block, dtype=None,
                keep_rows=None, fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights;
    a batch's first array holds the rows of tokens, and a row's labels
    are the row shifted by one.  Gradients are taken over blocks of
    ``rows_per_block`` rows and averaged.  ``dtype`` (the control)
    stores weights and state and computes in that type instead of
    float32; ``keep_rows`` (the harness's planted fault) takes the mean
    over the first rows only; ``fault`` plants one of :data:`FAULTS` in
    the layers.  Returns the losses, the leaf norms of the first
    gradient and of the parameters' change."""
    store = jnp.float32 if dtype is None else dtype
    numbers = {k: v for k, v in opt.items() if not isinstance(v, str)}
    grad, add, update, delta, norms = _programs(
        json.dumps(dims, sort_keys=True), tuple(sorted(numbers.items())),
        dtype, fault)

    def zeros(w):
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), w)

    with jax.default_matmul_precision(
            "highest" if dtype is None else "default"):
        w = init_weights(dims, seed, store)
        m = jax.tree_util.tree_map(jnp.zeros_like, w)
        v = jax.tree_util.tree_map(jnp.zeros_like, w)
        losses, grad_norms = [], None
        for step, batch in enumerate(batches, 1):
            rows = batch[0].shape[0] if keep_rows is None else keep_rows
            n_blocks = rows // rows_per_block
            acc, loss = None, 0.0
            for b in range(n_blocks):
                sl = slice(b * rows_per_block, (b + 1) * rows_per_block)
                lb, gb = grad(w, jnp.asarray(batch[0][sl]))
                # one block: its gradient is the mean, and no second
                # tree of the parameters' size is held beside it
                acc = gb if n_blocks == 1 else add(
                    zeros(w) if acc is None else acc, gb, 1.0 / n_blocks)
                loss += float(lb) / n_blocks
                del gb
            if acc is None:             # no row kept: no gradient
                acc = zeros(w)
            losses.append(loss)
            if step == 1:
                grad_norms = jax.device_get(norms(acc))
            w, m, v = update(w, acc, m, v, jnp.float32(step))
            del acc
        change = jax.device_get(delta(w, init_weights(dims, seed, store)))
    return {"losses": losses,
            "grad_norms": {n: float(x) for n, x in grad_norms.items()},
            "change_norms": {n: float(x) for n, x in change.items()}}
