"""Kimi-Linear-48B-A3B's layers (config.json, model_type kimi_linear),
plain float32.  Every layer is a mixer and a feed-forward, each under its
RMSNorm with a residual: ``h = x + mixer(norm(x))``,
``y = h + ffn(norm(h))``; one RMSNorm after the last layer; an untied
head.  Every RMSNorm is ``x / rms(x) * w`` (eps 1e-5), the gain stored
as it is.

- mixer "kda", Kimi Delta Attention: ``q~, k~, v~ = m W_q, m W_k, m
  W_v`` (the leaf ``qkv_w`` holds their rows one after the other);
  ``[q | k | v] = silu(conv([q~ | k~ | v~]))``, the causal depthwise
  convolution of 4 taps, no bias, written as its K-term sum over a row
  padded with K - 1 zeros; per head ``q / sqrt(|q|^2 + 1e-6) /
  sqrt(d)``, ``k / sqrt(|k|^2 + 1e-6)``; the decay **a key channel**
  ``g = -exp(A_log)_h softplus((m W_fa) W_fb + dt_bias)``; ``beta =
  sigmoid(m W_b)`` a head; **the recurrence itself, one position at a
  time**: ``S_t = (I - beta_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + beta_t
  k_t v_t^T``, ``o_t = S_t^T q_t`` from ``S_0 = 0`` (never the chunked
  form the program computes); ``rmsnorm(o) * gain * sigmoid((m W_ga)
  W_gb)`` a head (the norm BEFORE the gate, one gain of d shared by the
  heads); ``W_out``;
- mixer "mla", latent attention without rotary: ``q = m W_q``, each
  head [q_nope (128) | q_pe (64)]; ``[c | k_pe] = m W_kva``; each
  head's ``[k_nope | v] = rmsnorm(c) W_kvb``; ``k_h = [k_nope_h |
  k_pe]`` with the one ``k_pe`` shared by the heads, no rotary
  anywhere; dense causal scores over sqrt(192); ``W_o``;
- feed-forward of the first ``dense_ffn_layers`` layers: a SwiGLU of
  ``hidden_size``; of the others: sigmoid scores over all 256 experts in
  float32, the 8 largest of score + bias chosen (ties to the lower id;
  the bias chooses and does not weigh), their scores over their sum
  (plus 1e-20), times 2.446; SwiGLU experts, a loop over the held
  experts with a mask; plus the plain SwiGLU shared expert.

This is one chip's share of a layer divided over several chips: the
experts ``first_expert .. first_expert + experts_held - 1`` and a slice
of the vocabulary (``reference/mellum_moe.py`` says what that means);
the shared expert, the router, both mixers, the dense layer and the
norms are every chip's.

The canonical weight tree is a flat dict, per-layer leaves
``l<i>.<leaf>``.  ``qkv_w`` holds [q | k | v] rows, ``q_w`` each head's
[nope | pe] rows, ``kva_w`` [c | k_pe] rows, ``kvb_w`` each head's
[k_nope | v] rows, ``ffn_w1`` [gate | up] rows, ``w1`` and
``shared_w1`` [gate | up] columns.  Nothing here is another family's:
the norms, AdamW and the step loop are written out.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

MIXER_LEAVES = {
    "kda": ("op_norm_g", "qkv_w", "fa_w", "fb_w", "b_w", "ga_w", "gb_w",
            "conv_w", "A_log", "dt_bias", "gate_norm_g", "out_w"),
    "mla": ("op_norm_g", "q_w", "kva_w", "kv_norm_g", "kvb_w", "o_w"),
}
FFN_LEAVES = {
    "dense": ("ffn_norm_g", "ffn_w1", "ffn_w2"),
    "moe": ("ffn_norm_g", "router_w", "router_bias", "w1", "w2",
            "shared_w1", "shared_w2"),
}
FAULTS = (None, "scalar_decay", "silu_gate", "norm_after_gate", "rotary",
          "k_pe_per_head", "no_latent_norm", "no_scale")
FROZEN = ("router_w", "router_bias")
Q_BLOCK = 1024          # rows of the score matrix, and of the logits, that
                        # exist at once
SEGMENT = 128           # positions of the recurrence whose states exist at
                        # once in its backward pass
A_RANGE = (1.0, 16.0)   # A = uniform(1, 16), A_log = log(A)
CONV_TAP_RANGE = 0.5    # the convolution's taps, uniform(-0.5, 0.5)
ROUTER_BIAS_STD = 0.01
ROUTE_EPS = 1e-20
L2_EPS = 1e-6


def ffn_kind(dims, i):
    return "dense" if i < dims["dense_ffn_layers"] else "moe"


def layer_leaves(dims, i):
    return (MIXER_LEAVES[dims["layer_types"][i]]
            + FFN_LEAVES[ffn_kind(dims, i)])


def weight_shapes(dims):
    C, V = dims["units"], dims["vocab_size"]
    H, d = dims["kda_heads"], dims["kda_head_dim"]
    Hq = dims["num_heads"]
    nope, rope = dims["qk_nope_dim"], dims["qk_rope_dim"]
    dv, r = dims["v_head_dim"], dims["kv_lora_rank"]
    n, Hd, E = (dims["experts_held"], dims["expert_hidden_size"],
                dims["num_experts"])
    I, Hs = dims["hidden_size"], dims["shared_expert_hidden_size"]
    per_layer = {
        "op_norm_g": (C,), "qkv_w": (3 * H * d, C), "fa_w": (d, C),
        "fb_w": (H * d, d), "b_w": (H, C), "ga_w": (d, C),
        "gb_w": (H * d, d), "conv_w": (3 * H * d, dims["conv_kernel"]),
        "A_log": (H,), "dt_bias": (H * d,), "gate_norm_g": (d,),
        "out_w": (C, H * d),
        "q_w": (Hq * (nope + rope), C), "kva_w": (r + rope, C),
        "kv_norm_g": (r,), "kvb_w": (Hq * (nope + dv), r),
        "o_w": (C, Hq * dv),
        "ffn_norm_g": (C,), "ffn_w1": (2 * I, C), "ffn_w2": (C, I),
        "router_w": (C, E), "router_bias": (E,), "w1": (n, C, 2 * Hd),
        "w2": (n, Hd, C), "shared_w1": (C, 2 * Hs), "shared_w2": (Hs, C)}
    shapes = {"embed": (V, C)}
    for i in range(dims["num_layers"]):
        for leaf in layer_leaves(dims, i):
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    shapes.update({"final_norm_g": (C,), "head_w": (V, C)})
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    """Every leaf from the seed (the configuration's ``assumed``):
    matrices normal(0, 0.02), gains 1 + normal(0, 0.02), embedding rows
    normal(0, ``embed_std``); ``A_log = log(uniform(1, 16))``,
    ``dt_bias = 0``, the convolution's taps uniform(-0.5, 0.5), the
    router's choice bias normal(0, 0.01)."""
    w = common.init_from_shapes(weight_shapes(dims), seed, jnp.float32)
    w["embed"] = w["embed"] * (dims["embed_std"] / common.INIT_STD)

    def special(key):
        out = {}
        for i, name in enumerate(sorted(w)):
            k = jax.random.fold_in(key, i)
            shape = w[name].shape
            if name.endswith(".A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, minval=A_RANGE[0], maxval=A_RANGE[1]))
            elif name.endswith(".dt_bias"):
                out[name] = jnp.zeros(shape)
            elif name.endswith(".conv_w"):
                out[name] = jax.random.uniform(
                    k, shape, minval=-CONV_TAP_RANGE, maxval=CONV_TAP_RANGE)
            elif name.endswith(".router_bias"):
                out[name] = ROUTER_BIAS_STD * jax.random.normal(k, shape)
        return out

    w.update(jax.jit(special)(jax.random.fold_in(common.seed_key(seed), 47)))
    return {n: a.astype(dtype) for n, a in w.items()}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


# ------------------------------------------------------ Kimi Delta Attention
def conv(x, w):
    """``out[t, c] = sum_j w[c, j] x[t - (K - 1) + j, c]``, zeros before
    the row's start: the K-term sum, written out; no bias."""
    K, L = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(K):
        out = out + w[:, j] * xp[:, j:j + L]
    return out


def recurrence(q, k, v, g, beta):
    """One position at a time.  q, k, g (b, L, H, dk), v (b, L, H, dv),
    beta (b, L, H): the state's rows (key channels) forget by their own
    decay, then the write.  ``SEGMENT`` positions at a time are computed
    again in the backward pass, so that only the states at the segments'
    starts are kept."""
    b, L, H, dk = q.shape
    dv = v.shape[-1]
    seg = math.gcd(L, SEGMENT)

    def position(S, at):
        qt, kt, vt, gt, bt = at
        S = jnp.exp(gt)[..., None] * S
        predicted = jnp.einsum("bhkv,bhk->bhv", S, kt)
        S = S + jnp.einsum("bhk,bhv->bhkv", kt,
                           bt[..., None] * (vt - predicted))
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    def segment(S, part):
        return jax.lax.scan(position, S, part)

    parts = tuple(jnp.moveaxis(a, 1, 0).reshape((L // seg, seg) + a.shape[:1]
                                                + a.shape[2:])
                  for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(jax.checkpoint(segment),
                        jnp.zeros((b, H, dk, dv), q.dtype), parts)
    return jnp.moveaxis(o.reshape((L,) + o.shape[2:]), 0, 1)


def l2_normed(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda(g, m, dims, fault=None):
    """m (b, L, C) -> (b, L, C)."""
    H, d = dims["kda_heads"], dims["kda_head_dim"]
    b, L, _ = m.shape
    qkv = jax.nn.silu(conv(m @ g["qkv_w"].T, g["conv_w"]))
    q, k, v = (a.reshape(b, L, H, d) for a in jnp.split(qkv, 3, axis=-1))
    q, k = l2_normed(q) / math.sqrt(d), l2_normed(k)
    f = (m @ g["fa_w"].T) @ g["fb_w"].T + g["dt_bias"]
    decay = (-jnp.exp(g["A_log"])[:, None]
             * jax.nn.softplus(f.reshape(b, L, H, d)))
    if fault == "scalar_decay":
        decay = jnp.broadcast_to(decay.mean(-1, keepdims=True), decay.shape)
    beta = jax.nn.sigmoid(m @ g["b_w"].T)
    o = recurrence(q, k, v, decay, beta)
    z = ((m @ g["ga_w"].T) @ g["gb_w"].T).reshape(b, L, H, d)
    gate = jax.nn.silu(z) if fault == "silu_gate" else jax.nn.sigmoid(z)
    eps = dims["rms_norm_eps"]
    if fault == "norm_after_gate":
        y = rms_norm(o * gate, g["gate_norm_g"], eps)
    else:
        y = rms_norm(o, g["gate_norm_g"], eps) * gate
    return y.reshape(b, L, H * d) @ g["out_w"].T


# --------------------------------------------------------- latent attention
def rotate(x, theta):
    """x (B, L, heads, D) turned in pairs (i, i + D/2) by the angle of
    its position (the rotate-half layout): the planted fault's."""
    B, L, _, D = x.shape
    inv = theta ** (-np.arange(D // 2, dtype=np.float64) * 2.0 / D)
    angle = (jnp.arange(L, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32))
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla(g, x, dims, fault=None):
    """Dense causal attention, the scores taken ``Q_BLOCK`` queries at a
    time, over heads of [nope | pe] against [k_nope | shared k_pe]."""
    B, L, _ = x.shape
    H, nope, rope = dims["num_heads"], dims["qk_nope_dim"], dims["qk_rope_dim"]
    dv, r = dims["v_head_dim"], dims["kv_lora_rank"]
    D = nope + rope
    q = (x @ g["q_w"].T).reshape(B, L, H, D)
    ckv = x @ g["kva_w"].T
    c, k_pe = ckv[..., :r], ckv[..., r:]
    if fault != "no_latent_norm":
        c = rms_norm(c, g["kv_norm_g"], dims["rms_norm_eps"])
    kv = (c @ g["kvb_w"].T).reshape(B, L, H, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if fault == "k_pe_per_head":
        pe = k_nope[..., :rope]         # each head its own, from its k_nope
    else:
        pe = jnp.broadcast_to(k_pe[:, :, None], (B, L, H, rope))
    if fault == "rotary":
        q = jnp.concatenate([q[..., :nope],
                             rotate(q[..., nope:], dims["rope_theta"])], -1)
        pe = rotate(pe, dims["rope_theta"])
    k = jnp.concatenate([k_nope, pe], -1)
    qb = min(L, Q_BLOCK)
    s_pos = jnp.arange(L)

    def rows(start):
        t_pos = start + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("bthd,bshd->bhts", qs, k) / math.sqrt(D)
        seen = s_pos[None, :] <= t_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, v)

    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, L, qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * dv)
    return o @ g["o_w"].T


# --------------------------------------------------------- feed-forwards
def dense_ffn(g, m):
    gate, up = jnp.split(m @ g["ffn_w1"].T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ g["ffn_w2"].T


def route(g, m, dims, fault=None):
    """(ids (S, k), weights (S, k)): sigmoid scores over all experts;
    the k largest of score + bias (ties to the lower id); the scores at
    those ids over (their sum + 1e-20), times the scaling factor."""
    k = dims["experts_per_token"]
    s = jax.nn.sigmoid((m @ g["router_w"]).astype(jnp.float32))
    choice = s + g["router_bias"].astype(jnp.float32)
    ids = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + ROUTE_EPS)
    return ids, w * (1.0 if fault == "no_scale"
                     else dims["routed_scaling_factor"])


def swiglu_columns(m, w1, w2):
    gate, up = jnp.split(m @ w1, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w2


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
    """m (S, C) -> (the held experts' part plus the shared expert's,
    the chosen experts (S, k))."""
    ids, w = route(g, m, dims, fault)
    y = experts(g, m, ids, w.astype(m.dtype), dims)
    return y + swiglu_columns(m, g["shared_w1"], g["shared_w2"]), ids


# ------------------------------------------------------------------ the model
def hidden(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (the last layer's output under the final
    norm (B, L, C), the chosen experts of every expert layer (expert
    layers, B * L, k))."""
    B, L = tokens.shape
    eps = dims["rms_norm_eps"]

    def block(x, g, kind, ffn):
        m = rms_norm(x, g["op_norm_g"], eps)
        h = x + (kda(g, m, dims, fault) if kind == "kda"
                 else mla(g, m, dims, fault))
        m = rms_norm(h, g["ffn_norm_g"], eps)
        if ffn == "dense":
            return h + dense_ffn(g, m), None
        y, ids = moe(g, m.reshape(B * L, -1), dims, fault)
        return h + y.reshape(B, L, -1), ids

    # each layer is computed again in the backward pass; the layers are
    # written out, not scanned: they differ in kind
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


# ------------------------------------------------------------ the comparison
def views(name, a, dims):
    """The leaves as the comparison sees them: the parts of a fused
    projection are leaves of their own (q, k and v; q's nope and pe
    columns; the latent and k_pe; k_nope and v; a SwiGLU's gate and
    up), so that a fault in one part is not averaged away over the
    whole.  A layer's held experts stay ONE leaf a matrix
    (``reference/nemotron_h.py`` says why)."""
    if name.endswith(".qkv_w"):
        return {f"{name}.{part}": x
                for part, x in zip("qkv", jnp.split(a, 3, axis=0))}
    if name.endswith(".q_w") or name.endswith(".kvb_w"):
        first = dims["qk_nope_dim"]
        heads = a.reshape(dims["num_heads"], -1, a.shape[-1])
        second = "pe" if name.endswith(".q_w") else "v"
        return {f"{name}.nope": heads[:, :first],
                f"{name}.{second}": heads[:, first:]}
    if name.endswith(".kva_w"):
        r = dims["kv_lora_rank"]
        return {f"{name}.c": a[:r], f"{name}.pe": a[r:]}
    if name.endswith(".ffn_w1"):
        half = a.shape[0] // 2
        return {f"{name}.gate": a[:half], f"{name}.up": a[half:]}
    if name.endswith(".shared_w1"):
        half = a.shape[1] // 2
        return {f"{name}.gate": a[:, :half], f"{name}.up": a[:, half:]}
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
