"""Mixture-of-Experts Gluon layer (expert-parallel on the ``ep`` mesh
axis).

New TPU-first capability — upstream MXNet has no MoE (SURVEY.md §2.4:
EP absent; flagged as new capability).  Wraps ``ops/moe.py``'s dropless
top-k layer.  The expert leaves carry the expert dimension first and
are named so that ``parallel.MEGATRON_RULES`` shards it over ``ep``.

    layer = MoEFFN(units=512, hidden_size=2048, num_experts=8,
                   experts_per_token=2)
    out = layer(x)

A layer that holds one chip's share of the experts is told so: with
``experts_held=16, first_expert=32`` of ``num_experts=64`` it routes
over all 64, computes experts 32..47's part and leaves the rest out.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock, update_aux_state

__all__ = ["MoEFFN"]


class MoEFFN(HybridBlock):
    """Top-k dropless MoE feed-forward block (k=1 is Switch routing
    without its capacity).

    Inputs (..., units); returns the output (..., units): for each token
    the sum over its ``experts_per_token`` chosen experts that are held
    here of ``w_e * expert_e(x)``, the weights renormalised over all
    chosen.  ``gated`` experts are
    ``w2 (act(w1_gate x) * (w1_up x))`` (SwiGLU with ``activation=
    "silu"``), plain ones ``w2 act(w1 x)``; none has a bias.

    ``scoring``: how the router scores (``ops.moe.moe_topk_route``):
    "softmax", or "sigmoid" with ``route_bias`` (num_experts,), added
    to the scores to choose and not to weigh, ``route_eps`` beside the
    chosen scores' sum, and ``route_scale`` on the renormalised
    weights.  The bias is no weight: no optimizer moves it
    (its published update rule, from the experts' load, is a training
    loop's, not this layer's).  ``shared_hidden_size`` > 0 adds one
    shared expert of that width and of the routed experts' kind, which
    every token passes.

    ``rows_routed`` (n_held,) is a cumulative count, kept on the device
    as float32 (exact to 2**24 a expert; read differences), of the
    (token, expert) pairs routed to each held expert: auxiliary state
    like BatchNorm's moving statistics, so a compiled train step
    carries it and nothing reads it inside the step.

    ``train_router=False`` freezes the router's weight (its gradient is
    still taken; no optimizer moves it).  A layer that holds a share of
    the experts and is trained alone needs it: only the held experts'
    output reaches the loss, so a trained router learns to send every
    token to them, which the whole layer's router, whose gradient sums
    over all the shares, does not.
    """

    def __init__(self, units, hidden_size, num_experts,
                 experts_per_token=1, experts_held=None, first_expert=0,
                 activation="gelu", gated=False, recompute=False, train_router=True,
                 scoring="softmax", route_scale=1.0, route_eps=1e-20,
                 shared_hidden_size=0,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        held = num_experts if experts_held is None else int(experts_held)
        if num_experts < 1 or not 1 <= experts_per_token <= num_experts:
            raise MXNetError(
                f"MoEFFN needs 1 <= experts_per_token <= num_experts, got "
                f"{experts_per_token} of {num_experts}")
        if held < 1 or first_expert < 0 or first_expert + held > num_experts:
            raise MXNetError(
                f"MoEFFN: experts {first_expert}..{first_expert + held - 1} "
                f"held of {num_experts}")
        if activation not in ("relu", "relu2", "gelu", "silu"):
            raise MXNetError(
                f"MoEFFN: unsupported activation {activation!r} "
                f"(supported: 'relu', 'relu2', 'gelu', 'silu')")
        if scoring not in ("softmax", "sigmoid"):
            raise MXNetError(
                f"MoEFFN: unsupported scoring {scoring!r} "
                f"(supported: 'softmax', 'sigmoid')")
        self._kw = dict(experts_per_token=int(experts_per_token),
                        first_expert=int(first_expert),
                        activation=activation, gated=bool(gated),
                        recompute=bool(recompute), scoring=scoring,
                        route_scale=float(route_scale),
                        route_eps=float(route_eps),
                        shared_expert=bool(shared_hidden_size))
        fan = 2 if gated else 1
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(units, num_experts),
                init=weight_initializer,
                grad_req="write" if train_router else "null")
            self.expert_w1 = self.params.get(
                "expert_w1",
                shape=(held, units, fan * hidden_size),
                init=weight_initializer)
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(held, hidden_size, units),
                init=weight_initializer)
            self.rows_routed = self.params.get(
                "rows_routed", shape=(held,), init="zeros",
                grad_req="null")
            if scoring == "sigmoid":
                self.route_bias = self.params.get(
                    "route_bias", shape=(num_experts,), init="zeros",
                    grad_req="null")
            if shared_hidden_size:
                self.shared_w1 = self.params.get(
                    "shared_w1", shape=(units, fan * shared_hidden_size),
                    init=weight_initializer)
                self.shared_w2 = self.params.get(
                    "shared_w2", shape=(shared_hidden_size, units),
                    init=weight_initializer)

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_w2,
                       rows_routed, route_bias=None, shared_w1=None,
                       shared_w2=None):
        more = [a for a in (route_bias, shared_w1, shared_w2)
                if a is not None]
        out, rows = F.moe_ffn(x, gate_weight, expert_w1, expert_w2, *more,
                              **self._kw)
        update_aux_state(self.rows_routed, rows_routed + rows)
        return out
