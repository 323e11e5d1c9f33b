"""Mixture-of-experts operators: top-k routing over all experts,
dropless grouped expert FFNs over the experts held here.

New TPU-first capability — the reference has no MoE (SURVEY.md §2.4:
EP is ABSENT upstream).  The layer is told which experts it holds (a
contiguous range of the router's width: all of them on one chip, one
chip's share under expert parallelism), routes every token over ALL
experts, and computes its own experts' part of the result:

- ``mx.moe.route``: softmax over all experts in float32 at "highest"
  precision (a bfloat16 router flips near-ties), the k largest,
  renormalised over all k chosen, held here or not;
- ``mx.moe.dispatch``: the (token, expert) pairs sorted by held expert
  (pairs whose expert is held elsewhere sort to the tail) and each
  pair's token row gathered into that order;
- ``mx.moe.experts``: two grouped products over the held experts
  (``pallas_kernels.grouped_matmul``: where the shapes tile, a Pallas
  grouped matmul whose row tiles follow the group sizes, so the work
  follows the rows that are routed here, not the worst case of k rows a
  token; rows and weights enter the MXU as bfloat16, results and
  gradients are float32; other shapes take ``jax.lax.ragged_dot``);
- ``mx.moe.combine``: rows back in pair order, weighted, summed over
  each token's k pairs.  A token none of whose experts is held gets
  zero.

No capacity and no dropped token: the pair buffer has S*k rows, the
worst case.  **The rows past the last held pair are undefined**: the
kernels never visit them, so after the first product they hold whatever
was in memory, NaN included, and the activation runs over that.  Every
reader of those rows selects (``jnp.where(held, ...)``), never
multiplies by a mask.  No exchange: on one chip there is none, and
nothing stands in for the absent chips.

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

from .pallas_kernels import grouped_matmul
from .registry import register

__all__ = ["moe_topk_route", "moe_ffn"]

_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
                "gelu": functools.partial(jax.nn.gelu, approximate=False)}


@register("_contrib_moe_topk_route", num_inputs=2, num_outputs=2,
          aliases=["moe_topk_route"])
def moe_topk_route(x, gate_weight, *, experts_per_token: int = 1):
    """Top-k router.  ``x`` (S, C), ``gate_weight`` (C, E).

    Returns (weights (S, k) float32, ids (S, k) int32): the k largest
    of ``softmax(x @ gate_weight)`` a token, largest first (ties to the
    lower id), divided by their sum.
    """
    with jax.named_scope("mx.moe.route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         gate_weight.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = lax.top_k(probs, int(experts_per_token))
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_of_tokens(x, order, inverse, held, k):
    """Row ``order[i] // k`` of ``x`` for every sorted pair ``i``.  The
    backward is a gather too (``order`` is a permutation of the pairs):
    XLA's own transpose would be a scatter-add of S*k rows."""
    return x[order // k]


def _rows_of_tokens_fwd(x, order, inverse, held, k):
    return x[order // k], (inverse, held)


def _rows_of_tokens_bwd(k, res, g):
    inverse, held = res
    # the rows of pairs held elsewhere were never computed
    pairs = jnp.where(held[:, None], g[inverse], 0)
    return pairs.reshape(-1, k, g.shape[-1]).sum(1), None, None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inverse):
    """``y[perm]`` for a permutation whose inverse is known: the
    backward is ``g[inverse]``, a gather, not a scatter."""
    return y[perm]


_permute_rows.defvjp(lambda y, perm, inverse: (y[perm], (inverse,)),
                     lambda res, g: (g[res[0]], None, None))


def _experts_part(xs, weights, ids, w1, w2, first_expert, activation,
                  gated):
    """(the held experts' part of the layer's output (S, C), rows routed
    to each held expert (n_held,) float32)."""
    S, C = xs.shape
    k = ids.shape[1]
    n_held = w1.shape[0]
    act = _ACTIVATIONS[activation]
    with jax.named_scope("mx.moe.dispatch"):
        local = ids.reshape(-1) - first_expert                 # (S*k,)
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                        axis=0, dtype=jnp.int32)               # (n_held,)
        rows = _rows_of_tokens(xs, order, inverse, held, k)    # (S*k, C)
    with jax.named_scope("mx.moe.experts"):
        h = grouped_matmul(rows, w1, sizes)
        if gated:
            gate, up = jnp.split(h, 2, axis=-1)
            h = act(gate) * up
        else:
            h = act(h)
        y = grouped_matmul(h, w2, sizes)                       # (S*k, C)
    with jax.named_scope("mx.moe.combine"):
        pairs = _permute_rows(y, inverse, order).reshape(S, k, C)
        held = held.reshape(S, k)
        # the tail of ``y`` belongs to no group: whatever it holds, a
        # pair held elsewhere adds nothing
        pairs = jnp.where(held[..., None], pairs, 0)
        out = jnp.einsum("skc,sk->sc", pairs,
                         jnp.where(held, weights, 0).astype(pairs.dtype))
    return out, sizes.astype(jnp.float32)


@register("_contrib_moe_ffn", num_inputs=4, num_outputs=2,
          aliases=["moe_ffn"])
def moe_ffn(x, wg, w1, w2, *, experts_per_token: int = 1,
            first_expert: int = 0, activation: str = "gelu",
            gated: bool = False, recompute: bool = False):
    """The expert layer: route over all experts, compute the held
    experts' part.

    x (B, L, C) or (S, C); wg (C, E), E the router's width; w1
    (n_held, C, H), or (n_held, C, 2H) laid out [gate | up] when
    ``gated``; w2 (n_held, H, C).  The layer holds experts
    ``first_expert .. first_expert + n_held - 1``.  Returns (out with
    x's shape, rows (n_held,) float32: the (token, expert) pairs this
    call routed to each held expert).  ``recompute`` saves nothing of
    dispatch, experts and combine for the backward pass and computes
    them again there: their S*k-row buffers are most of a long
    sequence's saved activations.
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
    xs = x.reshape(-1, x.shape[-1])
    weights, ids = moe_topk_route(xs, wg,
                                  experts_per_token=experts_per_token)
    part = functools.partial(_experts_part, first_expert=int(first_expert),
                             activation=activation, gated=bool(gated))
    if recompute:
        part = jax.checkpoint(part)
    out, rows = part(xs, weights.astype(xs.dtype), ids, w1, w2)
    return out.reshape(x.shape), rows
