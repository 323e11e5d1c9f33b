"""BERT pretraining (Devlin et al. 2018), plain float32: embeddings,
post-norm encoder blocks, pooler, masked-LM and next-sentence heads, the
summed loss, and AdamW.  Departures from the paper, which the
configuration file states too: the masked-LM decoder has a weight of
its own (not tied to the word embedding), q, k and v come from one
projection whose output is laid out per head as [q | k | v], and weight
decay acts on every parameter.

The canonical weight tree is a flat dict; per-layer leaves are named
``l<i>.<leaf>``.
"""
import functools
import math

import jax
import jax.numpy as jnp

from . import common
from .common import gelu, init_from_shapes

LAYER_LEAVES = ("qkv_w", "qkv_b", "o_w", "o_b", "ln1_g", "ln1_b",
                "f1_w", "f1_b", "f2_w", "f2_b", "ln2_g", "ln2_b")
LN_EPS = 1e-12


def weight_shapes(dims):
    C, Hd, V = dims["units"], dims["hidden_size"], dims["vocab_size"]
    shapes = {"word": (V, C), "type": (2, C),
              "pos": (dims["max_length"], C),
              "emb_ln_g": (C,), "emb_ln_b": (C,)}
    per_layer = {"qkv_w": (3 * C, C), "qkv_b": (3 * C,), "o_w": (C, C),
                 "o_b": (C,), "ln1_g": (C,), "ln1_b": (C,),
                 "f1_w": (Hd, C), "f1_b": (Hd,), "f2_w": (C, Hd),
                 "f2_b": (C,), "ln2_g": (C,), "ln2_b": (C,)}
    for i in range(dims["num_layers"]):
        for leaf in LAYER_LEAVES:
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    shapes.update({"pool_w": (C, C), "pool_b": (C,),
                   "mlm_w": (C, C), "mlm_b": (C,),
                   "mlm_ln_g": (C,), "mlm_ln_b": (C,),
                   "dec_w": (V, C), "dec_b": (V,),
                   "nsp_w": (2, C), "nsp_b": (2,)})
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    return init_from_shapes(weight_shapes(dims), seed, dtype)


def layer_norm(x, g, b):
    return common.layer_norm(x, g, b, LN_EPS)


def forward(w, dims, tokens, types, valid, masked):
    """tokens, types (B, L) int32; valid (B,) key lengths; masked (B, M)
    positions.  Returns (mlm scores (B, M, V), nsp scores (B, 2))."""
    H = dims["num_heads"]
    B, L = tokens.shape
    C = w["word"].shape[1]
    D = C // H
    x = w["word"][tokens] + w["type"][types] + w["pos"][:L]
    x = layer_norm(x, w["emb_ln_g"], w["emb_ln_b"])
    key_ok = jnp.arange(L)[None, :] < valid[:, None]          # (B, L)
    bias = jnp.where(key_ok, 0.0, -1e9)[:, None, None, :]
    def block(x, g):
        qkv = (x @ g["qkv_w"].T + g["qkv_b"]).reshape(B, L, H, 3, D)
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D) + bias
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, L, C)
        x = layer_norm(x + o @ g["o_w"].T + g["o_b"], g["ln1_g"], g["ln1_b"])
        h = gelu(x @ g["f1_w"].T + g["f1_b"]) @ g["f2_w"].T + g["f2_b"]
        return layer_norm(x + h, g["ln2_g"], g["ln2_b"]), None

    # one traced block, scanned over the layers' stacked leaves: the
    # same equations 24 times, in a program a twenty-fourth the size
    x, _ = jax.lax.scan(block, x, common.stacked_layers(
        w, LAYER_LEAVES, dims["num_layers"]))
    pooled = jnp.tanh(x[:, 0] @ w["pool_w"].T + w["pool_b"])
    picked = jnp.take_along_axis(x, masked[:, :, None], axis=1)
    h = layer_norm(gelu(picked @ w["mlm_w"].T + w["mlm_b"]),
                   w["mlm_ln_g"], w["mlm_ln_b"])
    return h @ w["dec_w"].T + w["dec_b"], pooled @ w["nsp_w"].T + w["nsp_b"]


def loss_fn(w, dims, batch):
    tokens, types, valid, masked, mlm_y, nsp_y = batch
    mlm, nsp = forward(w, dims, tokens, types, valid, masked)
    mlm_lp = jax.nn.log_softmax(mlm, -1)
    nsp_lp = jax.nn.log_softmax(nsp, -1)
    return (-jnp.take_along_axis(mlm_lp, mlm_y[..., None], -1).mean()
            - jnp.take_along_axis(nsp_lp, nsp_y[:, None], -1).mean())


def adamw(w, g, m, v, step, opt):
    """AdamW as the configuration states it (decay on every leaf)."""
    b1, b2 = opt["beta1"], opt["beta2"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m = {n: b1 * m[n] + (1 - b1) * g[n] for n in w}
    v = {n: b2 * v[n] + (1 - b2) * g[n] ** 2 for n in w}
    w = {n: w[n] - opt["learning_rate"] * (
        (m[n] / c1) / (jnp.sqrt(v[n] / c2) + opt["eps"])
        + opt["weight_decay"] * w[n]) for n in w}
    return w, m, v


def views(name, a, num_heads):
    """The leaves as the comparison sees them.  The fused projection's
    q, k and v parts are leaves of their own: the key's bias has no
    gradient under softmax and Adam moves it by round-off alone, which
    the rule on the reference's gradient (``check.still_leaves``) can
    only see where that bias is a leaf."""
    if name.endswith(("qkv_w", "qkv_b")):
        parts = a.reshape((num_heads, 3, -1) + a.shape[1:])
        return {f"{name}.{part}": parts[:, i] for i, part in enumerate("qkv")}
    return {name: a}


def leaf_sizes(dims):
    """Elements of each leaf as :func:`views` splits them."""
    out = {}
    for name, shape in weight_shapes(dims).items():
        n = math.prod(shape)
        if name.endswith(("qkv_w", "qkv_b")):
            out.update({f"{name}.{part}": n // 3 for part in "qkv"})
        else:
            out[name] = n
    return out


def leaf_norms(tree, num_heads):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for name, a in tree.items()
            for n, v in views(name, a, num_heads).items()}


@functools.lru_cache(maxsize=None)
def _programs(dims_items, opt_items, dtype):
    """The reference's jitted pieces for one size, optimizer and type
    (cached, so that a process that follows many seeds traces once)."""
    dims, opt = dict(dims_items), dict(opt_items)
    store = jnp.float32 if dtype is None else dtype
    H = dims["num_heads"]
    grad_block = jax.jit(jax.value_and_grad(
        lambda w, b: loss_fn(w, dims, b).astype(jnp.float32)))
    add = jax.jit(lambda acc, g, scale: {
        n: acc[n] + scale * g[n].astype(jnp.float32) for n in acc},
        donate_argnums=(0,))
    update = jax.jit(
        lambda w, g, m, v, step: jax.tree_util.tree_map(
            lambda a: a.astype(store),      # the control stays in its type
            adamw(w, {n: g[n].astype(store) for n in g}, m, v, step, opt)),
        donate_argnums=(0, 2, 3))
    delta = jax.jit(lambda w, w0: leaf_norms(
        {n: w[n].astype(jnp.float32) - w0[n].astype(jnp.float32)
         for n in w}, H))
    norms = jax.jit(lambda t: leaf_norms(t, H))
    return grad_block, add, update, delta, norms


def train_steps(dims, opt, seed, batches, rows_per_block, dtype=None,
                keep_rows=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.
    Gradients are taken over blocks of ``rows_per_block`` rows and
    averaged, so that the float32 activations fit beside the state.
    ``dtype`` (the control) stores weights and state and computes in that
    type instead of float32; ``keep_rows`` (a planted fault) takes the
    mean over the first rows only.  Returns the losses, the leaf norms
    of the first gradient and of the parameters' change."""
    store = jnp.float32 if dtype is None else dtype
    numbers = {k: v for k, v in opt.items() if not isinstance(v, str)}
    grad_block, add, update, delta, norms = _programs(
        tuple(sorted(dims.items())), tuple(sorted(numbers.items())), dtype)
    with jax.default_matmul_precision(
            "highest" if dtype is None else "default"):
        w = init_weights(dims, seed, store)
        m = jax.tree_util.tree_map(jnp.zeros_like, w)
        v = jax.tree_util.tree_map(jnp.zeros_like, w)
        losses, grad_norms = [], None
        for step, batch in enumerate(batches, 1):
            rows = batch[0].shape[0] if keep_rows is None else keep_rows
            n_blocks = rows // rows_per_block
            acc = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), w)
            loss = 0.0
            for b in range(n_blocks):
                sl = slice(b * rows_per_block, (b + 1) * rows_per_block)
                lb, gb = grad_block(w, tuple(jnp.asarray(a[sl])
                                             for a in batch))
                acc = add(acc, gb, 1.0 / n_blocks)
                loss += float(lb) / n_blocks
                del gb
            losses.append(loss)
            if step == 1:
                grad_norms = jax.device_get(norms(acc))
            w, m, v = update(w, acc, m, v, jnp.float32(step))
            del acc
        change = jax.device_get(delta(w, init_weights(dims, seed, store)))
    return {"losses": losses,
            "grad_norms": {n: float(x) for n, x in grad_norms.items()},
            "change_norms": {n: float(x) for n, x in change.items()}}
