"""NVIDIA-Nemotron-3-Nano-30B-A3B's layers (config.json, model_type
nemotron_h), plain float32.  A block is ONE mixer under one RMSNorm with
a residual, ``x + mixer(norm(x))``, of three kinds:

- "mamba2": ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))``, the
  causal depthwise convolution written as its four-term sum; x, B, C
  split off; **the recurrence itself, one position at a time**:
  ``h_t = exp(delta_t A) h_{t-1} + delta_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t`` with ``delta = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``, ``h_0 = 0`` (never the chunked form the program
  computes); ``y * silu(z)``, THEN the RMS norm over each group of
  channels, times a gain; ``W_out``;
- "attention": 32 query heads over 2 key/value heads, dense scores under
  the causal mask, **no rotary and no other position signal**;
- "moe": sigmoid scores over all 128 experts, the 6 largest of score +
  bias chosen (ties to the lower id), weighted by the scores (without
  the bias) over (their sum + 1e-20), times 2.5; experts
  ``relu(x W_up)^2 W_down``, a loop over the held experts with a mask;
  plus the shared expert of every token.

This is one chip's share of a layer divided over several chips: the
experts ``first_expert .. first_expert + experts_held - 1`` and a slice
of the vocabulary (``reference/mellum_moe.py`` says what that means);
the shared expert, the Mamba and attention layers are every chip's.

The canonical weight tree is a flat dict, per-layer leaves
``l<i>.<leaf>``.  ``in_w`` holds [z | xBC | dt] rows, ``kv_w`` [k | v]
rows.  Loss, AdamW and the norm are Mellum's reference's; what the two
would share beyond that (the step loop) is written out here, because
this PR edits no file the benchmark already has.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .mellum_moe import EMBED_STD, Q_BLOCK, adamw, rms_norm

LEAVES = {
    "mamba2": ("norm_g", "in_w", "conv_w", "conv_b", "dt_bias", "A_log", "D",
               "gate_norm_g", "out_w"),
    "attention": ("norm_g", "q_w", "kv_w", "o_w"),
    "moe": ("norm_g", "router_w", "router_bias", "w1", "w2", "shared_w1",
            "shared_w2"),
}
FAULTS = (None, "no_carry", "norm_before_gate", "top5", "no_scale",
          "no_bias")
FROZEN = ("router_w", "router_bias")
RESIDUAL_WRITERS = (".out_w", ".o_w", ".w2", ".shared_w2")
SEGMENT = 128           # positions of the recurrence whose states exist
                        # at once in its backward pass
ROUTER_BIAS_STD = 0.01


def mamba_sizes(dims):
    """(heads, head size, groups, state size, inner width, convolved
    width)."""
    H, P = dims["mamba_num_heads"], dims["mamba_head_dim"]
    G, N = dims["n_groups"], dims["ssm_state_size"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def weight_shapes(dims):
    C, V = dims["units"], dims["vocab_size"]
    Hq, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    H, _P, _G, _N, inner, conv = mamba_sizes(dims)
    n, Hd = dims["experts_held"], dims["expert_hidden_size"]
    Hs, E = dims["shared_expert_hidden_size"], dims["num_experts"]
    per_layer = {
        "norm_g": (C,), "in_w": (inner + conv + H, C),
        "conv_w": (conv, dims["conv_kernel"]), "conv_b": (conv,),
        "dt_bias": (H,), "A_log": (H,), "D": (H,), "gate_norm_g": (inner,),
        "out_w": (C, inner),
        "q_w": (Hq * D, C), "kv_w": (2 * Hkv * D, C), "o_w": (C, Hq * D),
        "router_w": (C, E), "router_bias": (E,), "w1": (n, C, Hd),
        "w2": (n, Hd, C), "shared_w1": (C, Hs), "shared_w2": (Hs, C)}
    shapes = {"embed": (V, C)}
    for i, kind in enumerate(dims["layer_types"]):
        for leaf in LEAVES[kind]:
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    shapes.update({"final_norm_g": (C,), "head_w": (V, C)})
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    """Every leaf from the seed (the configuration's ``assumed``):
    matrices normal(0, 0.02), those that write into the residual stream
    (:data:`RESIDUAL_WRITERS`) divided by the square root of the
    published depth (``rescale_prenorm_residual``: without it the
    positive ``relu^2`` activations leave every token the same offset,
    which grows block by block until the router sends all tokens the
    same way: PERF.md section 6, PR 37), gains 1 + normal(0, 0.02),
    embedding rows normal(0, 1); ``A_log = log(uniform(1, 16))``, ``dt_bias`` the
    inverse softplus of ``exp(uniform(log 0.001, log 0.1))`` clipped at
    1e-4, ``D = 1``, the convolution's weight and bias uniform(+-0.5);
    the router's choice bias normal(0, 0.01)."""
    w = common.init_from_shapes(weight_shapes(dims), seed, jnp.float32)
    w["embed"] = w["embed"] * (EMBED_STD / common.INIT_STD)
    depth = 1.0 / math.sqrt(dims["published_num_layers"])
    for name in w:
        if name.endswith(RESIDUAL_WRITERS):
            w[name] = w[name] * depth
    lo, hi, floor = (dims["time_step_min"], dims["time_step_max"],
                     dims["time_step_floor"])

    def special(key):
        out = {}
        for i, name in enumerate(sorted(w)):
            k = jax.random.fold_in(key, i)
            shape = w[name].shape
            if name.endswith(".A_log"):
                out[name] = jnp.log(jax.random.uniform(k, shape, minval=1.0,
                                                       maxval=16.0))
            elif name.endswith(".dt_bias"):
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, minval=math.log(lo), maxval=math.log(hi))),
                    floor)
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif name.endswith(".D"):
                out[name] = jnp.ones(shape)
            elif name.endswith((".conv_w", ".conv_b")):
                out[name] = jax.random.uniform(k, shape, minval=-0.5,
                                               maxval=0.5)
            elif name.endswith(".router_bias"):
                out[name] = ROUTER_BIAS_STD * jax.random.normal(k, shape)
        return out

    w.update(jax.jit(special)(jax.random.fold_in(common.seed_key(seed), 77)))
    return {n: a.astype(dtype) for n, a in w.items()}


# ------------------------------------------------------------------ mamba-2
def conv(x, w, b):
    """``out[t, c] = b[c] + sum_j w[c, j] x[t - (K - 1) + j, c]``, zeros
    before the row's start: the K-term sum, written out."""
    K, L = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = b
    for j in range(K):
        out = out + w[:, j] * xp[:, j:j + L]
    return out


def recurrence(x, delta, A, B, C, reset_every=None):
    """One position at a time.  x (b, L, H, P), delta (b, L, H), A (H,),
    B and C (b, L, H, N) (each head's group's).  ``SEGMENT`` positions
    at a time are computed again in the backward pass, so that only the
    states at the segments' starts are kept.  ``reset_every`` (a planted
    fault) zeroes the state at every multiple of it."""
    b, L, H, P = x.shape
    N = B.shape[-1]
    seg = math.gcd(L, SEGMENT)

    def position(h, at):
        t, xt, dl, Bt, Ct = at
        if reset_every:
            h = jnp.where(t % reset_every == 0, jnp.zeros_like(h), h)
        h = (jnp.exp(dl * A)[..., None, None] * h
             + (dl[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, Ct)

    def segment(h, part):
        return jax.lax.scan(position, h, part)

    parts = tuple(jnp.moveaxis(a, 1, 0).reshape((L // seg, seg) + a.shape[:1]
                                                + a.shape[2:])
                  for a in (x, delta, B, C))
    t = jnp.arange(L).reshape(L // seg, seg)
    _, y = jax.lax.scan(jax.checkpoint(segment),
                        jnp.zeros((b, H, P, N), x.dtype), (t,) + parts)
    return jnp.moveaxis(y.reshape((L,) + y.shape[2:]), 0, 1)


def mamba(g, u, dims, fault=None):
    """u (b, L, C) -> (b, L, C)."""
    H, P, G, N, inner, cw = mamba_sizes(dims)
    b, L, _ = u.shape
    zxd = u @ g["in_w"].T
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + cw], \
        zxd[..., inner + cw:]
    xbc = jax.nn.silu(conv(xbc, g["conv_w"], g["conv_b"]))
    x = xbc[..., :inner].reshape(b, L, H, P)
    # head h reads group h // (H // G)
    B, C = (jnp.repeat(part.reshape(b, L, G, N), H // G, axis=2)
            for part in (xbc[..., inner:inner + G * N],
                         xbc[..., inner + G * N:]))
    delta = jax.nn.softplus(dt + g["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(g["A_log"]), B, C,
                   dims["chunk_size"] if fault == "no_carry" else None)
    y = (y + g["D"][:, None] * x).reshape(b, L, inner)
    eps = dims["rms_norm_eps"]

    def group_norm(v):
        v = v.reshape(b, L, G, inner // G)
        return rms_norm(v, 1.0, eps).reshape(b, L, inner) * g["gate_norm_g"]

    if fault == "norm_before_gate":
        y = group_norm(y) * jax.nn.silu(z)
    else:
        y = group_norm(y * jax.nn.silu(z))
    return y @ g["out_w"].T


# ---------------------------------------------------------------- attention
def attention(g, x, dims):
    """Dense causal attention without positions, the scores taken
    ``Q_BLOCK`` queries at a time."""
    B, L, _ = x.shape
    H, Hkv, D = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    G = H // Hkv
    q = (x @ g["q_w"].T).reshape(B, L, Hkv, G, D)   # query head j reads j // G
    kv = x @ g["kv_w"].T
    k = kv[..., :Hkv * D].reshape(B, L, Hkv, D)
    v = kv[..., Hkv * D:].reshape(B, L, Hkv, D)
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


# ------------------------------------------------------------ expert layer
def route(g, m, dims, fault=None):
    """(ids (S, k), weights (S, k)): sigmoid scores over all experts;
    the k largest of score + bias (ties to the lower id); the scores at
    those ids over (their sum + 1e-20), times the scaling factor."""
    k = dims["experts_per_token"] - (fault == "top5")
    s = jax.nn.sigmoid((m @ g["router_w"]).astype(jnp.float32))
    choice = s if fault == "no_bias" else s + g["router_bias"].astype(
        jnp.float32)
    ids = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        w = w * dims["routed_scaling_factor"]
    return ids, w


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def experts(g, m, ids, w, dims):
    """The held experts' part: a loop over them, each on every token,
    weighted; zero where the expert was not among the token's chosen."""
    S = m.shape[0]
    lo, n = dims["first_expert"], dims["experts_held"]
    full = jnp.zeros((S, dims["num_experts"]), w.dtype).at[
        jnp.arange(S)[:, None], ids].set(w)

    def one(expert):
        w1, w2, weight = expert             # (C, H), (H, C), (S,)
        return (relu2(m @ w1) * weight[:, None]) @ w2

    return jax.lax.map(jax.checkpoint(one), (
        g["w1"], g["w2"], full[:, lo:lo + n].T.astype(m.dtype))).sum(0)


def moe(g, m, dims, fault=None):
    """m (S, C) -> (the held experts' part plus the shared expert's,
    the chosen experts (S, k))."""
    ids, w = route(g, m, dims, fault)
    y = experts(g, m, ids, w.astype(m.dtype), dims)
    return y + relu2(m @ g["shared_w1"]) @ g["shared_w2"], ids


# ------------------------------------------------------------------ the model
def hidden(w, dims, tokens, fault=None):
    """tokens (B, L) int32 -> (the last block's output under the final
    norm (B, L, C), the chosen experts of every expert layer (expert
    layers, B * L, k))."""
    B, L = tokens.shape
    eps = dims["rms_norm_eps"]

    def block(x, g, kind):
        m = rms_norm(x, g["norm_g"], eps)
        if kind == "mamba2":
            return x + mamba(g, m, dims, fault), None
        if kind == "attention":
            return x + attention(g, m, dims), None
        y, ids = moe(g, m.reshape(B * L, -1), dims, fault)
        return x + y.reshape(B, L, -1), ids

    # each block is computed again in the backward pass (Mellum's
    # reference says why the layers are written out and not scanned)
    x, chosen = w["embed"][tokens], []
    for i, kind in enumerate(dims["layer_types"]):
        x, ids = jax.checkpoint(block, static_argnums=(2,))(
            x, {leaf: w[f"l{i}.{leaf}"] for leaf in LEAVES[kind]}, kind)
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


# ------------------------------------------------------------ the comparison
def views(name, a, dims):
    """The leaves as the comparison sees them: the in-projection's z,
    xBC and dt parts and the fused projection's key and value parts are
    leaves of their own, so that a fault in one part is not averaged
    away over the whole.  A layer's held experts stay ONE leaf a matrix
    (Mellum's reference splits them): an expert here sees 384 of a
    step's rows, the few tokens whose sixth and seventh experts are
    nearly tied choose otherwise in the program, and one expert's
    gradient norm then reads up to 0.0077 off where a layer's reads
    0.0014 and every other leaf 0.0021, which left the smallest planted
    fault (0.0147) no room (PERF.md section 2, PR 37)."""
    if name.endswith(".in_w"):
        _H, _P, _G, _N, inner, cw = mamba_sizes(dims)
        return {f"{name}.z": a[:inner], f"{name}.xbc": a[inner:inner + cw],
                f"{name}.dt": a[inner + cw:]}
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
