"""Mixture-of-experts operators: top-k routing over all experts,
dropless grouped expert FFNs over the experts held here.

New TPU-first capability — the reference has no MoE (SURVEY.md §2.4:
EP is ABSENT upstream).  The layer is told which experts it holds (a
contiguous range of the router's width: all of them on one chip, one
chip's share under expert parallelism), routes every token over ALL
experts, and computes its own experts' part of the result:

- ``mx.moe.route``: scores over all experts in float32 at "highest"
  precision (a bfloat16 router flips near-ties), the k largest,
  renormalised over all k chosen, held here or not.  The scores are
  what the layer is told: the softmax, or sigmoids chosen by score plus
  a bias that is no part of the weights, and scaled;
- ``mx.moe.dispatch``: the (token, expert) pairs sorted by held expert
  (pairs whose expert is held elsewhere sort to the tail) and each held
  pair's token row written into that order
  (``pallas_kernels.rows_of_tokens``, the pair-side mover);
- ``mx.moe.experts``: two grouped products over the held experts
  (``pallas_kernels.grouped_matmul``) with the activation between them
  (``pallas_kernels.expert_activation``);
- ``mx.moe.combine``: each token's held pairs' rows, weighted, summed
  in float32 (``pallas_kernels.tokens_of_rows``, the token-side mover).
  A token none of whose experts is held gets zero;
- ``mx.moe.shared``: a shared expert, where the layer has one: the same
  feed-forward for every token, dense, scaled by a sigmoid gate a token
  where the layer has one, added after combine (every chip computes it
  alike; a sum over the shares counts it once).

No capacity and no dropped token: the pair buffers have S*k rows, the
worst case, and **every pass visits only the rows below the last held
pair** (``sum(sizes)``; a quarter of the buffer where a chip holds 16
experts of 64).  Where the shapes tile (``pallas_kernels.grouped_tiles``,
the products' predicate: C and H multiples of 64, S*k of the row tile)
each pass is a Pallas kernel whose grid ends at the last live tile, and
**the rows past it are undefined** in every pair buffer, forward and
backward: the gathered rows, both products, the activation, and the
gradients of all four hold whatever was in memory there, NaN included.
A reader fetches held pairs' rows by index or stops at the last live
tile; none multiplies by a mask.  Rows and activations enter the MXU as
bfloat16, written so by the pass before; what leaves a product is
float32, and so is the sum over a token's pairs.  Other shapes (the
unit tests' widths of 8-16) take ``lax.ragged_dot`` and the ``jnp``
gathers the movers replaced, inside the same functions, over all S*k
rows, in the operands' dtype.

The backward pass is written out (``_backward``), mover for mover the
forward's transpose: the token-side mover with unit weights is the
gather's gradient, the pair-side mover with a scale is the weighted
sum's, and a weight's gradient is one row-wise product on the pair
side.  No exchange: on one chip there is none, and nothing stands in
for the absent chips.

Ops:
  ``moe_topk_route`` — router: tokens x router weight -> (weights, ids)
  ``moe_ffn``        — the whole layer; also counts the rows it routed
                       to each held expert
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import (expert_activation, grouped_matmul,
                             grouped_matmul_grads, grouped_tiles, relu2,
                             rows_of_tokens, tokens_of_rows)
from .registry import register

__all__ = ["moe_topk_route", "moe_ffn"]

_ACTIVATIONS = {"relu": jax.nn.relu, "relu2": relu2, "silu": jax.nn.silu,
                "gelu": functools.partial(jax.nn.gelu, approximate=False)}


@register("_contrib_moe_topk_route",
          num_inputs=lambda kw: 3 if kw.get("scoring") == "sigmoid" else 2,
          num_outputs=2, aliases=["moe_topk_route"])
def moe_topk_route(x, gate_weight, choice_bias=None, *,
                   experts_per_token: int = 1, scoring: str = "softmax",
                   scale: float = 1.0, route_eps: float = 1e-20):
    """Top-k router.  ``x`` (S, C), ``gate_weight`` (C, E).

    Returns (weights (S, k) float32, ids (S, k) int32).  ``scoring``
    "softmax": the k largest of ``softmax(x @ gate_weight)`` a token,
    largest first (ties to the lower id), divided by their sum.
    "sigmoid" (DeepSeek-V3's router, Nemotron-3's): scores
    ``sigmoid(x @ gate_weight)``; the k largest of ``score +
    choice_bias`` ((E,), the load-balancing bias: it chooses and does
    not weigh) are chosen; the weights are the scores at those ids,
    divided by (their sum + ``route_eps``: 1e-20 in Nemotron-3's
    published code, 1e-6 in LFM2's), times ``scale``.
    """
    with jax.named_scope("mx.moe.route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         gate_weight.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        k = int(experts_per_token)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            weights, ids = lax.top_k(probs, k)
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            choice = scores if choice_bias is None else (
                scores + choice_bias.astype(jnp.float32))
            ids = lax.top_k(choice, k)[1]
            weights = jnp.take_along_axis(scores, ids, axis=-1)
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + route_eps)
        else:
            from ..base import MXNetError
            raise MXNetError(f"moe_topk_route: unsupported scoring "
                             f"{scoring!r} (supported: 'softmax', 'sigmoid')")
        if scale != 1.0:
            weights = weights * scale
    return weights, ids.astype(jnp.int32)


def _forward(xs, weights, ids, w1, w2, first_expert, activation, gated):
    """((the held experts' part of the layer's output (S, C), rows routed
    to each held expert (n_held,) float32), what the backward pass
    needs)."""
    S, C = xs.shape
    k = ids.shape[1]
    n_held = w1.shape[0]
    # what a kernel reads is written as bfloat16 by the pass before it
    to_w1 = _operand_dtype(xs, S * k, w1)
    to_w2 = _operand_dtype(xs, S * k, w2)
    with jax.named_scope("mx.moe.dispatch"):
        local = ids.reshape(-1) - first_expert                 # (S*k,)
        group = jnp.where((local >= 0) & (local < n_held), local, n_held)
        # a sort carries what a gather of S*k numbers would fetch, at a
        # tenth of its cost: each pair's weight goes along
        pair = lax.iota(jnp.int32, S * k)
        _, order, scale = lax.sort((group, pair, weights.reshape(-1)),
                                   num_keys=1, is_stable=True)
        inverse = lax.sort((order, pair), num_keys=1)[1]
        sizes = jnp.sum(group[None, :] == jnp.arange(n_held)[:, None],
                        axis=1, dtype=jnp.int32)               # (n_held,)
        live = jnp.sum(sizes)
        tok = order // k
        rows = rows_of_tokens(xs, tok, live, dtype=to_w1)      # (S*k, C)
    with jax.named_scope("mx.moe.experts"):
        h = grouped_matmul(rows, w1, sizes)
        a = expert_activation(h, live, _ACTIVATIONS[activation], gated,
                              dtype=to_w2)
        y = grouped_matmul(a, w2, sizes)                       # (S*k, C)
    with jax.named_scope("mx.moe.combine"):
        pairs = (inverse.reshape(S, k), group.reshape(S, k), sizes)
        out = tokens_of_rows(y, weights, *pairs)
    return ((out.astype(xs.dtype), sizes.astype(jnp.float32)),
            (scale, w1, w2, tok, order, pairs, rows, h, a, y))


def _operand_dtype(xs, M, w):
    """bfloat16 for the rows of a grouped product that takes the kernels
    (they enter the MXU so), ``xs``'s for ``lax.ragged_dot``."""
    tiles = grouped_tiles(M, w.shape[1], w.shape[2], w.dtype.itemsize)
    return jnp.dtype(jnp.bfloat16 if tiles else xs.dtype)


def _backward(first_expert, activation, gated, res, cotangents):
    """The forward pass transposed, mover for mover: each pair buffer is
    written once, in the dtype its reader wants, below the last held
    pair."""
    scale, w1, w2, tok, order, pairs, rows, h, a, y = res
    inverse, group, sizes = pairs
    g = cotangents[0]
    live = jnp.sum(sizes)
    with jax.named_scope("mx.moe.combine"):
        dy, dscale = rows_of_tokens(g.astype(jnp.float32), tok, live,
                                    scale=scale, dot=y, dtype=a.dtype)
        # back in pair order; past the last held pair it holds anything
        d_weights = lax.sort((order, dscale), num_keys=1)[1].reshape(
            group.shape)
        d_weights = jnp.where(group < sizes.shape[0], d_weights,
                              0).astype(scale.dtype)
    with jax.named_scope("mx.moe.experts"):
        da, d_w2 = grouped_matmul_grads(a, w2, sizes, dy)
        dh = expert_activation(h, live, _ACTIVATIONS[activation], gated,
                               g=da, dtype=rows.dtype)
        drows, d_w1 = grouped_matmul_grads(rows, w1, sizes, dh)
    with jax.named_scope("mx.moe.dispatch"):
        d_xs = tokens_of_rows(drows, jnp.ones(group.shape, scale.dtype),
                              *pairs)
    return d_xs.astype(g.dtype), d_weights, None, d_w1, d_w2


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _experts_part(xs, weights, ids, w1, w2, first_expert, activation,
                  gated):
    """(the held experts' part of the layer's output (S, C), rows routed
    to each held expert (n_held,) float32).  One forward and one
    backward body, written out: the pair buffers change dtype between a
    pass and its transpose (bfloat16 into a kernel, float32 out of it),
    which autodiff's one dtype a value cannot say."""
    return _forward(xs, weights, ids, w1, w2, first_expert, activation,
                    gated)[0]


_experts_part.defvjp(_forward, _backward)


def _n_inputs(kw):
    return (4 + (kw.get("scoring") == "sigmoid")
            + 2 * bool(kw.get("shared_expert"))
            + bool(kw.get("shared_gate")))


@register("_contrib_moe_ffn", num_inputs=_n_inputs, num_outputs=2,
          aliases=["moe_ffn"])
def moe_ffn(x, wg, w1, w2, *more, experts_per_token: int = 1,
            first_expert: int = 0, activation: str = "gelu",
            gated: bool = False, recompute: bool = False,
            scoring: str = "softmax", route_scale: float = 1.0,
            route_eps: float = 1e-20, shared_expert: bool = False,
            shared_gate: bool = False):
    """The expert layer: route over all experts, compute the held
    experts' part.

    x (B, L, C) or (S, C); wg (C, E), E the router's width; w1
    (n_held, C, H), or (n_held, C, 2H) laid out [gate | up] when
    ``gated``; w2 (n_held, H, C).  The layer holds experts
    ``first_expert .. first_expert + n_held - 1``.  ``scoring`` and
    ``route_scale``: ``moe_topk_route``'s ``scoring`` and ``scale``;
    with "sigmoid" the next input is the router's choice bias (E,).
    With ``shared_expert`` the last two inputs are its matrices (C, Hs)
    (or (C, 2Hs), [gate | up]) and (Hs, C): one more expert of the same
    kind that every token passes, added to the held experts' part.
    With ``shared_gate`` one more input (C, 1) follows: the shared
    expert's output is scaled by ``sigmoid(x @ it)`` a token.
    Returns (out with x's shape, rows (n_held,) float32: the (token,
    expert) pairs this call routed to each held expert; over S*k, the
    share of the pair buffers that the layer's passes visit).  Where
    the shapes tile (``pallas_kernels.grouped_tiles``) every pass is a
    Pallas kernel over the held pairs' rows only (the module's
    docstring says which rows are undefined where); other shapes
    compute all S*k rows in ``jnp``.
    ``recompute`` saves nothing of dispatch, experts and combine for
    the backward pass and computes them again there: their S*k-row
    buffers are most of a long sequence's saved activations.
    """
    if activation not in _ACTIVATIONS:
        from ..base import MXNetError
        raise MXNetError(
            f"moe_ffn: unsupported activation {activation!r} "
            f"(supported: {sorted(_ACTIVATIONS)})")
    n_held, E = w1.shape[0], wg.shape[1]
    if not 0 <= first_expert <= E - n_held:
        from ..base import MXNetError
        raise MXNetError(
            f"moe_ffn: experts {first_expert}..{first_expert + n_held - 1} "
            f"held, the router has {E}")
    more = list(more)
    choice_bias = more.pop(0) if scoring == "sigmoid" else None
    xs = x.reshape(-1, x.shape[-1])
    weights, ids = moe_topk_route(xs, wg, choice_bias=choice_bias,
                                  experts_per_token=experts_per_token,
                                  scoring=scoring, scale=route_scale,
                                  route_eps=route_eps)
    def part(xs, weights, ids, w1, w2):
        return _experts_part(xs, weights, ids, w1, w2, int(first_expert),
                             activation, bool(gated))
    if recompute:
        part = jax.checkpoint(part)
    out, rows = part(xs, weights.astype(xs.dtype), ids, w1, w2)
    if shared_expert:
        with jax.named_scope("mx.moe.shared"):
            s1, s2 = more[:2]
            h = jnp.dot(xs, s1.astype(xs.dtype))
            if gated:
                gate, up = jnp.split(h, 2, axis=-1)
                h = _ACTIVATIONS[activation](gate) * up
            else:
                h = _ACTIVATIONS[activation](h)
            shared = jnp.dot(h, s2.astype(xs.dtype))
            if shared_gate:
                shared = shared * jax.nn.sigmoid(
                    jnp.dot(xs, more[2].astype(xs.dtype)))
            out = out + shared
    return out.reshape(x.shape), rows
