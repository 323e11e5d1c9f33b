"""Sharding rules: parameter-name regex -> PartitionSpec.

Supersedes the reference's manual model parallelism (``ctx_group`` +
``Bind(group2ctx=...)``, SURVEY.md §2.4 P7): instead of placing subgraphs
on devices by hand, parameters carry PartitionSpecs and GSPMD inserts the
collectives.  MEGATRON_RULES cover the in-tree transformer blocks
(column-parallel qkv/ffn_1, row-parallel out_proj/ffn_2).
"""
from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "MEGATRON_RULES", "partition_params",
           "global_device_put"]


def global_device_put(value, sharding):
    """``jax.device_put`` that also works when ``sharding`` spans
    devices this process cannot address (a multi-process global mesh).

    Plain ``device_put`` of a host value onto a non-addressable
    sharding lowers to cross-host transfer collectives, which the gloo
    CPU transport aborts with a mismatched-size ``EnforceNotMet``
    (the tests/test_dist two-process SPMD failure).  In the SPMD
    program model every process already holds the same host value, so
    the local shards can be sliced out directly and assembled with
    ``make_array_from_callback`` — zero wire traffic, and the only
    path jax guarantees for building global arrays from host data.
    """
    if getattr(value, "sharding", None) == sharding:
        return value
    devices = getattr(sharding, "device_set", None)
    if devices is None \
            or all(d.process_index == jax.process_index()
                   for d in devices):
        return jax.device_put(value, sharding)
    import numpy as np
    host = np.asarray(value)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


class ShardingRules:
    """Ordered (regex, PartitionSpec) table; first match wins."""

    def __init__(self, rules, default=P()):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]
        self._default = default

    def spec_for(self, name, shape=None):
        for prog, spec in self._rules:
            if prog.search(name):
                if shape is not None and spec != P():
                    # drop specs that don't divide the dims (tiny configs)
                    return spec
                return spec
        return self._default

    def shardings(self, mesh: Mesh, params: dict):
        return {n: NamedSharding(mesh, self._safe_spec(mesh, n, a.shape))
                for n, a in params.items()}

    def _safe_spec(self, mesh, name, shape):
        spec = self.spec_for(name, shape)
        out = []
        for i, axis in enumerate(spec):
            if axis is None or i >= len(shape):
                out.append(None)
                continue
            # axes the mesh doesn't have (e.g. 'ep' on a 3-axis mesh)
            # degrade to replication, same as non-dividing dims
            size = mesh.shape.get(axis, 0) if isinstance(axis, str) else 1
            out.append(axis if size and shape[i] % size == 0 else None)
        return P(*out)


# Megatron-style tensor parallelism for the in-tree transformer layers.
# Dense weights are (out_units, in_units): column-parallel shards dim 0,
# row-parallel shards dim 1.
MEGATRON_RULES = ShardingRules([
    (r"qkv_weight$", P("tp", None)),
    (r"qkv_bias$", P("tp")),
    (r"(q|kv)_proj_weight$", P("tp", None)),
    (r"(q|kv)_proj_bias$", P("tp")),
    (r"out_proj_weight$", P(None, "tp")),
    (r"ffn_1_weight$", P("tp", None)),
    (r"ffn_1_bias$", P("tp")),
    (r"ffn_2_weight$", P(None, "tp")),
    (r"(word_embed|tgt_embed|src_embed).*weight$", P(None, "tp")),
    (r"mlm_decoder_weight$", P("tp", None)),
    (r"mlm_decoder_bias$", P("tp")),
    (r"lm_head_weight$", P("tp", None)),
    # MoE experts: dim 0 is the expert dim, sharded over the ep axis;
    # the hidden dim additionally takes tp (GShard layout).  The count
    # of rows routed to each held expert lies with its expert; the
    # router (gate_weight) and the norms' gains are replicated
    (r"expert_w1$", P("ep", None, "tp")),
    (r"expert_w2$", P("ep", "tp", None)),
    (r"rows_routed$", P("ep")),
], default=P())


def partition_params(params, mesh, rules=MEGATRON_RULES):
    """Device-put a params dict with rule-derived NamedShardings."""
    shardings = rules.shardings(mesh, params)
    return {n: global_device_put(a, shardings[n])
            for n, a in params.items()}, shardings
