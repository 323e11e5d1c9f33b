"""Decoder-only causal language model (GPT-2 sizes, Radford et al.
2019), plain float32: token embedding scaled by sqrt(units) plus
sinusoid positions (Vaswani et al. 2017), pre-norm blocks with one
[q | k | v]-per-head projection, final LayerNorm, and an output
projection with a weight of its own.  Sinusoid positions and the untied
projection are the served class's departures from GPT-2, stated in the
configuration file.

``gaps`` is what ``correct`` reads: for each served token, how far its
logit lies below the reference's best at that position.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .common import gelu, init_from_shapes

LN_EPS = 1e-5
LAYER_LEAVES = ("n1_g", "n1_b", "qkv_w", "qkv_b", "o_w", "o_b",
                "n2_g", "n2_b", "f1_w", "f1_b", "f2_w", "f2_b")


def weight_shapes(dims):
    C, Hd, V = dims["units"], dims["hidden_size"], dims["vocab_size"]
    per_layer = {"n1_g": (C,), "n1_b": (C,), "qkv_w": (3 * C, C),
                 "qkv_b": (3 * C,), "o_w": (C, C), "o_b": (C,),
                 "n2_g": (C,), "n2_b": (C,), "f1_w": (Hd, C),
                 "f1_b": (Hd,), "f2_w": (C, Hd), "f2_b": (C,)}
    shapes = {"embed": (V, C), "fn_g": (C,), "fn_b": (C,),
              "proj_w": (V, C), "proj_b": (V,)}
    for i in range(dims["num_layers"]):
        for leaf in LAYER_LEAVES:
            shapes[f"l{i}.{leaf}"] = per_layer[leaf]
    return shapes


def init_weights(dims, seed, dtype=jnp.float32):
    return init_from_shapes(weight_shapes(dims), seed, dtype)


def sinusoid_positions(length, units):
    pos = np.arange(length, dtype=np.float64)[:, None]
    i = np.arange(units)[None, :]
    angle = pos / np.power(10000.0, 2 * (i // 2) / units)
    return np.where(i % 2 == 0, np.sin(angle), np.cos(angle)).astype(
        np.float32)


def layer_norm(x, g, b):
    return common.layer_norm(x, g, b, LN_EPS)


def forward(w, dims, tokens, rows):
    """tokens (T,) int32, padded with anything past the sequence's end
    (causal attention keeps padding from reaching earlier positions);
    ``rows`` (R,) the positions whose next-token logits are wanted.
    Returns (R, V) logits, in the weights' dtype."""
    H = dims["num_heads"]
    T = tokens.shape[0]
    C = w["embed"].shape[1]
    D = C // H
    dt = w["embed"].dtype
    x = w["embed"][tokens] * jnp.asarray(math.sqrt(C), dt) \
        + jnp.asarray(sinusoid_positions(T, C), dt)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def block(x, g):
        h = layer_norm(x, g["n1_g"], g["n1_b"])
        qkv = (h @ g["qkv_w"].T + g["qkv_b"]).reshape(T, H, 3, D)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal[None], s, -1e9), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p.astype(dt), v).reshape(T, C)
        x = x + o @ g["o_w"].T + g["o_b"]
        h = layer_norm(x, g["n2_g"], g["n2_b"])
        return (x + gelu(h @ g["f1_w"].T + g["f1_b"]) @ g["f2_w"].T
                + g["f2_b"]), None

    x, _ = jax.lax.scan(block, x, common.stacked_layers(
        w, LAYER_LEAVES, dims["num_layers"]))
    x = layer_norm(x[rows], w["fn_g"], w["fn_b"])
    return x @ w["proj_w"].T + w["proj_b"]


def make_scorer(dims, seed, max_len, max_new, dtype=jnp.float32):
    """``score(prompt, served) -> (R, V) logits`` for the positions that
    predicted each served token, one compiled program for every
    request (padded to ``max_len`` tokens and ``max_new`` rows).  The
    control passes ``dtype=bfloat16``: weights and activations held in
    bfloat16, multiplied at the default precision."""
    precision = "highest" if dtype == jnp.float32 else "default"
    w = init_weights(dims, seed, dtype)
    with jax.default_matmul_precision(precision):
        fn = jax.jit(lambda w, t, r: forward(w, dims, t, r)
                     .astype(jnp.float32))

    def score(prompt, served):
        n, m = len(prompt), len(served)
        tokens = np.zeros((max_len,), np.int32)
        tokens[:n] = prompt
        tokens[n:n + m - 1] = served[:-1]
        rows = np.minimum(n - 1 + np.arange(max_new), max_len - 1)
        with jax.default_matmul_precision(precision):
            return np.asarray(fn(w, tokens, rows.astype(np.int32)))[:m]

    return score


def gaps(logits, served):
    """For each served token: the reference's best logit at that
    position minus the served token's logit (0 where they agree)."""
    logits = np.asarray(logits, np.float64)
    served = np.asarray(served)
    return logits.max(-1) - logits[np.arange(len(served)), served]
