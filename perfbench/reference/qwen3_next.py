"""Qwen3-Next-80B-A3B's layers (config.json, model_type qwen3_next),
plain float32.  Every layer is a mixer and a feed-forward, each under its
RMSNorm with a residual: ``h = x + mixer(norm(x))``,
``y = h + moe(norm(h))``; one RMSNorm after the last layer; an untied
head.  Every RMSNorm of the family is ``x / rms(x) * (1 + w)``: the
leaves ``*_g`` here ARE ``1 + w`` (the program stores the same), eps
1e-6.

- mixer "linear_attention", the Gated DeltaNet: ``[q | k | v | z] =
  m W_qkvz``, ``[b | a] = m W_ba``; ``[q | k | v] = silu(conv([q | k |
  v]))``, the causal depthwise convolution of 4 taps, no bias, written
  as its K-term sum over a row padded with K - 1 zeros; per head
  ``q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k / sqrt(|k|^2 + 1e-6)``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` a
  value head; **the recurrence itself, one position at a time**:
  ``S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - e^{g_t} S_{t-1}^T
  k_t))^T``, ``o_t = S_t^T q_t`` from ``S_0 = 0`` (value head h reads key
  head h // 2; never the chunked form the program computes);
  ``rmsnorm(o) * gain * silu(z)`` a head (the norm BEFORE the gate, one
  gain of dv shared by the heads); ``W_out``;
- mixer "full_attention": 16 query heads over 2 key/value heads of 256;
  each query head's rows of ``q_w`` are [q | gate]; q and k RMS-normed a
  head, then rotary (rotate-half, theta 1e7) over the FIRST 64 channels
  of the head, pairs (i, i + 32), the other 192 passed through; dense
  causal scores over sqrt(256); the output times ``sigmoid(gate)``;
  ``W_o``;
- feed-forward: softmax over all 512 experts in float32, the 10 largest
  chosen (ties to the lower id), divided by their sum; SwiGLU experts,
  a loop over the held experts with a mask; plus the shared SwiGLU
  expert times ``sigmoid(m w_sg)`` a token.

This is one chip's share of a layer divided over several chips: the
experts ``first_expert .. first_expert + experts_held - 1`` and a slice
of the vocabulary (``reference/mellum_moe.py`` says what that means);
the shared expert, both mixers and the norms are every chip's.

The canonical weight tree is a flat dict, per-layer leaves
``l<i>.<leaf>``.  ``qkvz_w`` holds [q | k | v | z] rows, ``ba_w`` [b |
a], ``kv_w`` [k | v], ``w1`` and ``shared_w1`` [gate | up] columns.
Nothing here is another family's: the norms, the rotation, AdamW and
the step loop are written out.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

MIXER_LEAVES = {
    "linear_attention": ("op_norm_g", "qkvz_w", "ba_w", "conv_w", "dt_bias",
                         "A_log", "gate_norm_g", "out_w"),
    "full_attention": ("op_norm_g", "q_w", "kv_w", "o_w", "q_norm_g",
                       "k_norm_g"),
}
FFN_LEAVES = ("ffn_norm_g", "router_w", "w1", "w2", "shared_w1", "shared_w2",
              "shared_gate_w")
FAULTS = (None, "no_l2norm", "beta_one", "no_decay", "no_out_gate",
          "no_shared_gate", "full_rotary", "top9", "no_renorm")
FROZEN = ("router_w",)
Q_BLOCK = 1024          # rows of the score matrix, and of the logits, that
                        # exist at once
SEGMENT = 128           # positions of the recurrence whose states exist at
                        # once in its backward pass
A_RANGE = 16.0          # A = uniform(0, 16), A_log = log(A)


def linear_sizes(dims):
    """(key heads, value heads, key dim, value dim, convolved width)."""
    Hk, H = dims["linear_key_heads"], dims["linear_value_heads"]
    dk, dv = dims["linear_key_dim"], dims["linear_value_dim"]
    return Hk, H, dk, dv, 2 * Hk * dk + H * dv


def layer_leaves(dims, i):
    return MIXER_LEAVES[dims["layer_types"][i]] + FFN_LEAVES


def weight_shapes(dims):
    C, V = dims["units"], dims["vocab_size"]
    Hq, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    Hk, H, dk, dv, conv = linear_sizes(dims)
    n, Hd = dims["experts_held"], dims["expert_hidden_size"]
    Hs, E = dims["shared_expert_hidden_size"], dims["num_experts"]
    per_layer = {
        "op_norm_g": (C,), "qkvz_w": (conv + H * dv, C), "ba_w": (2 * H, C),
        "conv_w": (conv, dims["conv_kernel"]), "dt_bias": (H,),
        "A_log": (H,), "gate_norm_g": (dv,), "out_w": (C, H * dv),
        "q_w": (2 * Hq * D, C), "kv_w": (2 * Hkv * D, C), "o_w": (C, Hq * D),
        "q_norm_g": (D,), "k_norm_g": (D,),
        "ffn_norm_g": (C,), "router_w": (C, E), "w1": (n, C, 2 * Hd),
        "w2": (n, Hd, C), "shared_w1": (C, 2 * Hs), "shared_w2": (Hs, C),
        "shared_gate_w": (C, 1)}
    shapes = {"embed": (V, C)}
    for i in range(dims["num_layers"]):
        for leaf in layer_leaves(dims, i):
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    shapes.update({"final_norm_g": (C,), "head_w": (V, C)})
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    """Every leaf from the seed (the configuration's ``assumed``):
    matrices normal(0, 0.02), the gains ``1 + w`` with w normal(0, 0.02),
    embedding rows normal(0, ``embed_std``); ``A_log = log(uniform(0,
    16))``, ``dt_bias = 1`` (the family's initialisation), the
    convolution's taps uniform(-0.5, 0.5)."""
    w = common.init_from_shapes(weight_shapes(dims), seed, jnp.float32)
    w["embed"] = w["embed"] * (dims["embed_std"] / common.INIT_STD)

    def special(key):
        out = {}
        for i, name in enumerate(sorted(w)):
            k = jax.random.fold_in(key, i)
            shape = w[name].shape
            if name.endswith(".A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, minval=0.0, maxval=A_RANGE))
            elif name.endswith(".dt_bias"):
                out[name] = jnp.ones(shape)
            elif name.endswith(".conv_w"):
                out[name] = jax.random.uniform(k, shape, minval=-0.5,
                                               maxval=0.5)
        return out

    w.update(jax.jit(special)(jax.random.fold_in(common.seed_key(seed), 45)))
    return {n: a.astype(dtype) for n, a in w.items()}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


# ----------------------------------------------------------- gated DeltaNet
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
    """One position at a time.  q, k (b, L, H, dk) (each value head's key
    head's), v (b, L, H, dv), g and beta (b, L, H).  ``SEGMENT``
    positions at a time are computed again in the backward pass, so that
    only the states at the segments' starts are kept."""
    b, L, H, dk = q.shape
    dv = v.shape[-1]
    seg = math.gcd(L, SEGMENT)

    def position(S, at):
        qt, kt, vt, gt, bt = at
        S = jnp.exp(gt)[..., None, None] * S
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
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_deltanet(g, m, dims, fault=None):
    """m (b, L, C) -> (b, L, C)."""
    Hk, H, dk, dv, width = linear_sizes(dims)
    b, L, _ = m.shape
    qkvz = m @ g["qkvz_w"].T
    z = qkvz[..., width:].reshape(b, L, H, dv)
    qkv = jax.nn.silu(conv(qkvz[..., :width], g["conv_w"]))
    q = qkv[..., :Hk * dk].reshape(b, L, Hk, dk)
    k = qkv[..., Hk * dk:2 * Hk * dk].reshape(b, L, Hk, dk)
    v = qkv[..., 2 * Hk * dk:].reshape(b, L, H, dv)
    if fault != "no_l2norm":
        q, k = l2_normed(q), l2_normed(k)
    q = q / math.sqrt(dk)
    # value head h reads key head h // (H // Hk)
    q, k = (jnp.repeat(a, H // Hk, axis=2) for a in (q, k))
    ba = m @ g["ba_w"].T
    beta = jax.nn.sigmoid(ba[..., :H])
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    decay = -jnp.exp(g["A_log"]) * jax.nn.softplus(ba[..., H:] + g["dt_bias"])
    if fault == "no_decay":
        decay = jnp.zeros_like(decay)
    o = recurrence(q, k, v, decay, beta)
    y = rms_norm(o, g["gate_norm_g"], dims["rms_norm_eps"]) * jax.nn.silu(z)
    return y.reshape(b, L, H * dv) @ g["out_w"].T


# ---------------------------------------------------------------- attention
def rope_tables(dims, length, rotary):
    """(cos, sin), each (length, rotary / 2): rotary over ``rotary``
    channels, their own frequencies."""
    inv = dims["rope_theta"] ** (-np.arange(rotary // 2, dtype=np.float64)
                                 * 2.0 / rotary)
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32))
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, cos, sin):
    """x (B, L, heads, D): its first ``2 * cos.shape[-1]`` channels
    turned in pairs (i, i + half) by the angle of their position (the
    rotate-half layout), the rest as they are."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(g, x, dims, fault=None):
    """Dense causal attention, the scores taken ``Q_BLOCK`` queries at a
    time; each head of q and k normed, then rotated; the output gated."""
    B, L, _ = x.shape
    H, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    G, eps = H // Hkv, dims["rms_norm_eps"]
    qg = (x @ g["q_w"].T).reshape(B, L, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    kv = x @ g["kv_w"].T
    k = kv[..., :Hkv * D].reshape(B, L, Hkv, D)
    v = kv[..., Hkv * D:].reshape(B, L, Hkv, D)
    q = rms_norm(q, g["q_norm_g"], eps)
    k = rms_norm(k, g["k_norm_g"], eps)
    cos, sin = (t.astype(x.dtype) for t in rope_tables(
        dims, L, D if fault == "full_rotary" else dims["rotary_dim"]))
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
    o = jnp.moveaxis(o, 0, 1).reshape(B, L, H, D)
    if fault != "no_out_gate":
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(B, L, H * D) @ g["o_w"].T


# ------------------------------------------------------------ expert layer
def route(g, m, dims, fault=None):
    """(ids (S, k), weights (S, k)): softmax over all experts in float32,
    the k largest (ties to the lower id), divided by their sum."""
    k = dims["experts_per_token"] - (fault == "top9")
    p = jax.nn.softmax((m @ g["router_w"]).astype(jnp.float32), -1)
    ids = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(p, ids, axis=-1)
    if fault != "no_renorm":
        w = w / w.sum(-1, keepdims=True)
    return ids, w


def swiglu(m, w1, w2):
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
    """m (S, C) -> (the held experts' part plus the gated shared
    expert's, the chosen experts (S, k))."""
    ids, w = route(g, m, dims, fault)
    y = experts(g, m, ids, w.astype(m.dtype), dims)
    shared = swiglu(m, g["shared_w1"], g["shared_w2"])
    if fault != "no_shared_gate":
        shared = shared * jax.nn.sigmoid(m @ g["shared_gate_w"])
    return y + shared, ids


# ------------------------------------------------------------------ the model
def hidden(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (the last layer's output under the final
    norm (B, L, C), the chosen experts of every layer (layers, B * L,
    k))."""
    B, L = tokens.shape
    eps = dims["rms_norm_eps"]

    def block(x, g, kind):
        m = rms_norm(x, g["op_norm_g"], eps)
        if kind == "linear_attention":
            h = x + gated_deltanet(g, m, dims, fault)
        else:
            h = x + attention(g, m, dims, fault)
        m = rms_norm(h, g["ffn_norm_g"], eps)
        y, ids = moe(g, m.reshape(B * L, -1), dims, fault)
        return h + y.reshape(B, L, -1), ids

    # each layer is computed again in the backward pass; the layers are
    # written out, not scanned: they differ in kind
    x, chosen = w["embed"][tokens], []
    for i, kind in enumerate(dims["layer_types"]):
        x, ids = jax.checkpoint(block, static_argnums=(2,))(
            x, {leaf: w[f"l{i}.{leaf}"] for leaf in layer_leaves(dims, i)},
            kind)
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
    """The leaves as the comparison sees them: the in-projection's q, k,
    v and z parts, b and a, the attention's q and gate rows and the
    fused projection's key and value parts are leaves of their own, so
    that a fault in one part is not averaged away over the whole.  A
    layer's held experts stay ONE leaf a matrix (``reference/nemotron_h.
    py`` says why)."""
    if name.endswith(".qkvz_w"):
        Hk, H, dk, dv, _w = linear_sizes(dims)
        cuts = np.cumsum([Hk * dk, Hk * dk, H * dv])
        return {f"{name}.{part}": x for part, x in
                zip("qkvz", jnp.split(a, cuts, axis=0))}
    if name.endswith(".ba_w"):
        half = a.shape[0] // 2
        return {f"{name}.b": a[:half], f"{name}.a": a[half:]}
    if name.endswith(".q_w"):
        D = dims["head_dim"]
        heads = a.reshape(-1, 2 * D, a.shape[-1])
        return {f"{name}.q": heads[:, :D], f"{name}.gate": heads[:, D:]}
    if name.endswith(".kv_w"):
        half = a.shape[0] // 2
        return {f"{name}.k": a[:half], f"{name}.v": a[half:]}
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
