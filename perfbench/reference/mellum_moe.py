"""Mellum2-12B-A2.5B's layers (JetBrains, config.json), plain float32:
pre-norm decoder blocks with RMSNorm, rotary positions (plain on the
sliding layers, static YaRN on the full ones, as the ``transformers``
library computes it), 32 query heads over 4 key/value heads, dense
scores under an explicit mask, a top-8 router over all 64 experts and
SwiGLU experts, next-token cross-entropy, and AdamW.  No kernel, no
sort: every held expert is applied to every token and weighted, zero
where it was not chosen.

This is one chip's share of a layer divided over several chips: the
experts ``first_expert .. first_expert + experts_held - 1`` and a slice
of the vocabulary.  The router keeps its whole width and the chosen
weights are normalised over all chosen experts, held here or not; what
the absent experts would have added is left out, and that partial
result goes on to the next layer.  With every expert held it is the
whole layer.

The canonical weight tree is a flat dict; per-layer leaves are named
``l<i>.<leaf>``.  ``kv_w`` holds [k | v] rows, ``w1`` [gate | up]
columns.  Departures and assumptions: the configuration file lists
them.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

LAYER_LEAVES = ("attn_norm_g", "q_w", "kv_w", "o_w", "ffn_norm_g",
                "router_w", "w1", "w2")
FAULTS = (None, "no_window", "top7", "no_renorm")
Q_BLOCK = 1024          # rows of the score matrix, and of the logits, that
                        # exist at once
EXPERT_BLOCK = 4        # experts whose activations exist at once
EMBED_STD = 1.0         # the embedding's rows (torch.nn.Embedding's default)


def weight_shapes(dims):
    C, V = dims["units"], dims["vocab_size"]
    H, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    n, Hd = dims["experts_held"], dims["expert_hidden_size"]
    shapes = {"embed": (V, C)}
    per_layer = {"attn_norm_g": (C,), "q_w": (H * D, C),
                 "kv_w": (2 * Hkv * D, C), "o_w": (C, H * D),
                 "ffn_norm_g": (C,), "router_w": (C, dims["num_experts"]),
                 "w1": (n, C, 2 * Hd), "w2": (n, Hd, C)}
    for i in range(dims["num_layers"]):
        for leaf in LAYER_LEAVES:
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    shapes.update({"final_norm_g": (C,), "head_w": (V, C)})
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    """Every leaf from the seed: normal(0, 0.02), gains 1 + normal(0,
    0.02), and the embedding's rows normal(0, 1).  With rows of 0.02 the
    residual stream of an untrained model is the attention's running
    mean over the keys: every token shows the router nearly the same
    vector and the experts' load collapses (the largest expert's 2 to
    6.5 times the mean: PERF.md), which no trained model's router does.
    With rows of 1 a token's own embedding leads its vector, and the
    router, still computed from the data, spreads the tokens."""
    w = common.init_from_shapes(weight_shapes(dims), seed, jnp.float32)
    w["embed"] = w["embed"] * (EMBED_STD / common.INIT_STD)
    return {n: a.astype(dtype) for n, a in w.items()}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def inv_freq(head_dim, rope):
    """``theta^(-2i/head_dim)``; with ``factor`` the static YaRN blend
    of it and itself over the factor, by the linear ramp between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings`` positions."""
    theta = rope["rope_theta"]
    half = head_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return inv

    def dim_of(rotations):
        return (head_dim * math.log(rope["original_max_position_embeddings"]
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return inv / rope["factor"] * ramp + inv * (1.0 - ramp)


def rope_tables(dims, kind, length):
    """(cos, sin), each (length, head_dim / 2), of one layer kind."""
    rope = dims["rope_parameters"][kind]
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq(dims["head_dim"], rope), jnp.float32))
    scale = rope.get("attention_factor", 1.0)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotate(x, cos, sin):
    """x (B, L, heads, D): the pairs (i, i + D/2) turned by the angle of
    their position."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(g, x, cos, sin, windowed, dims):
    """Dense causal attention, the scores taken ``Q_BLOCK`` queries at a
    time.  ``windowed``: key s is visible to query t iff
    t - window < s <= t; else iff s <= t."""
    B, L, _ = x.shape
    H, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    G = H // Hkv
    q = rotate((x @ g["q_w"].T).reshape(B, L, H, D), cos, sin)
    kv = x @ g["kv_w"].T
    k = rotate(kv[..., :Hkv * D].reshape(B, L, Hkv, D), cos, sin)
    v = kv[..., Hkv * D:].reshape(B, L, Hkv, D)
    q = q.reshape(B, L, Hkv, G, D)          # query head j reads j // G
    qb = min(L, Q_BLOCK)
    s_pos = jnp.arange(L)

    def rows(start):
        t_pos = start + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("btkgd,bskd->bkgts", qs, k) / math.sqrt(D)
        seen = s_pos[None, :] <= t_pos[:, None]
        if windowed:
            seen &= s_pos[None, :] > t_pos[:, None] - dims["window"]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", p, v)

    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, L, qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * D)
    return o @ g["o_w"].T


def route(g, m, dims, fault=None):
    """(ids (S, k), weights (S, k)): the k largest of the softmax over
    all experts (ties to the lower id), normalised over the chosen."""
    k = dims["experts_per_token"] - (fault == "top7")
    p = jax.nn.softmax((m @ g["router_w"]).astype(jnp.float32), axis=-1)
    ids = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(p, ids, axis=-1)
    if fault != "no_renorm":
        w = w / w.sum(-1, keepdims=True)
    return ids, w


def experts(g, m, ids, w, dims):
    """Every held expert on every token, weighted; zero where the
    expert was not among the token's chosen.  ``EXPERT_BLOCK`` experts
    at a time, so that sixteen experts' activations of 8192 tokens never
    exist at once."""
    S = m.shape[0]
    lo, n = dims["first_expert"], dims["experts_held"]
    full = jnp.zeros((S, dims["num_experts"]), w.dtype).at[
        jnp.arange(S)[:, None], ids].set(w)
    eb = math.gcd(n, EXPERT_BLOCK)

    def some(block):
        w1, w2, weight = block          # (eb, C, 2H), (eb, H, C), (eb, S)
        gate, up = jnp.split(jnp.einsum("sc,ech->seh", m, w1), 2, axis=-1)
        a = jax.nn.silu(gate) * up * weight.T[:, :, None]
        return jnp.einsum("seh,ehc->sc", a, w2)

    blocks = (g["w1"].reshape((n // eb, eb) + g["w1"].shape[1:]),
              g["w2"].reshape((n // eb, eb) + g["w2"].shape[1:]),
              full[:, lo:lo + n].T.astype(m.dtype).reshape(n // eb, eb, S))
    return jax.lax.map(jax.checkpoint(some), blocks).sum(0)


def hidden(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (the last layer's output under the final
    norm (B, L, C), the chosen experts of every layer (layers, B * L,
    k))."""
    B, L = tokens.shape
    dtype = w["embed"].dtype
    eps = dims["rms_norm_eps"]
    tables = {kind: tuple(t.astype(dtype) for t in rope_tables(dims, kind, L))
              for kind in set(dims["layer_types"])}

    def block(x, g, cos, sin, windowed):
        h = x + attention(g, rms_norm(x, g["attn_norm_g"], eps), cos, sin,
                          windowed, dims)
        m = rms_norm(h, g["ffn_norm_g"], eps).reshape(B * L, -1)
        ids, wts = route(g, m, dims, fault)
        y = experts(g, m, ids, wts.astype(dtype), dims)
        return h + y.reshape(B, L, -1), ids

    # each layer is computed again in the backward pass, so that a
    # layer's scores and expert activations exist once; the layers are
    # written out (the period is four), not scanned: stacking the
    # leaves would copy every layer's weights, and their gradients
    x, ids = w["embed"][tokens], []
    for i, kind in enumerate(dims["layer_types"]):
        x, chosen = jax.checkpoint(block, static_argnums=(4,))(
            x, {leaf: w[f"l{i}.{leaf}"] for leaf in LAYER_LEAVES},
            *tables[kind],
            kind == "sliding_attention" and fault != "no_window")
        ids.append(chosen)
    ids = jnp.stack(ids)
    return rms_norm(x, w["final_norm_g"], eps), ids


def forward(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (logits (B, L, V), the chosen experts)."""
    x, ids = hidden(w, dims, tokens, fault)
    return x @ w["head_w"].T, ids


def loss_fn(w, dims, tokens, fault=None):
    """Mean next-token cross-entropy over positions 0 .. L-2, the
    logits taken ``Q_BLOCK`` positions at a time."""
    x, _ = hidden(w, dims, tokens, fault)
    B, L = tokens.shape
    qb = min(L, Q_BLOCK)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    counted = jnp.arange(L) < L - 1             # the last has no next token

    def some(start):
        xs = jax.lax.dynamic_slice_in_dim(x, start, qb, axis=1)
        ys = jax.lax.dynamic_slice_in_dim(labels, start, qb, axis=1)
        logp = jax.nn.log_softmax(
            (xs @ w["head_w"].T).astype(jnp.float32), -1)
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


def views(name, a, dims):
    """The leaves as the comparison sees them: the fused projection's
    key and value parts, and every expert's gate, up and down matrix,
    are leaves of their own, so that a fault in one expert's rows is not
    averaged away over sixteen."""
    if name.endswith("kv_w"):
        half = a.shape[0] // 2
        return {f"{name}.k": a[:half], f"{name}.v": a[half:]}
    if name.endswith(".w1"):
        half = a.shape[-1] // 2
        return {f"{name}.e{e}.{part}": a[e, :, sl]
                for e in range(a.shape[0])
                for part, sl in (("gate", slice(0, half)),
                                 ("up", slice(half, None)))}
    if name.endswith(".w2"):
        return {f"{name}.e{e}": a[e] for e in range(a.shape[0])}
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
                  () if dims["train_router"] else ("router_w",))),
        donate_argnums=(0, 2, 3))
    delta = jax.jit(lambda w, w0: leaf_norms(
        {n: w[n].astype(jnp.float32) - w0[n].astype(jnp.float32)
         for n in w}, dims))
    norms = jax.jit(lambda t: leaf_norms(t, dims))
    return grad, add, update, delta, norms


def chosen_experts(dims, seed, tokens):
    """The chosen experts of every layer, (layers, rows * L, k), in the
    first step's forward pass from the seed's weights."""
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
    float32; ``keep_rows`` (a planted fault) takes the mean over the
    first rows only; ``fault`` plants one of :data:`FAULTS` in the
    layers.  Returns the losses, the leaf norms of the first gradient
    and of the parameters' change."""
    store = jnp.float32 if dtype is None else dtype
    numbers = {k: v for k, v in opt.items() if not isinstance(v, str)}
    grad, add, update, delta, norms = _programs(
        json.dumps(dims, sort_keys=True), tuple(sorted(numbers.items())),
        dtype, fault)
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
                # tree of 595 M floats is held beside it
                acc = gb if n_blocks == 1 else add(
                    acc if acc is not None else jax.tree_util.tree_map(
                        lambda a: jnp.zeros(a.shape, jnp.float32), w),
                    gb, 1.0 / n_blocks)
                loss += float(lb) / n_blocks
                del gb
            if acc is None:             # no row kept: no gradient
                acc = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), w)
            losses.append(loss)
            if step == 1:
                grad_norms = jax.device_get(norms(acc))
            w, m, v = update(w, acc, m, v, jnp.float32(step))
            del acc
        change = jax.device_get(delta(w, init_weights(dims, seed, store)))
    return {"losses": losses,
            "grad_norms": {n: float(x) for n, x in grad_norms.items()},
            "change_norms": {n: float(x) for n, x in change.items()}}
