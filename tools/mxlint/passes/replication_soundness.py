"""replication-soundness: P() out_specs must be provably uniform.

An ``out_specs`` entry of ``P()`` promises that every device returns
the *same* value — JAX's shard_map enforces it with a runtime
replication check, and the compressed trainer deliberately turns
that check off (``check_vma=False``) because the
quantized-allreduce bodies confuse it.  That makes a wrong ``P()``
claim the worst bug shape in the parallel layer: no error, each device
silently keeps its own shard and downstream math diverges per host.

This pass is the static twin of the disabled check: a may-carry-shard
walk (:func:`..mxshard.body_return_state`) over the body — params seed
tainted (they ARE the per-device shards by construction), only the
uniform collectives (psum/pmean/pmax/pmin/all_gather) wash, shuffling
collectives (ppermute/all_to_all/psum_scatter) and ``axis_index``
re-taint, and project helpers are walked interprocedurally so
``quantize.allreduce_mean`` comes back as ``(uniform, per-device)``
per element.  A ``P()`` (or all-``None``) out_spec positionally
aligned with a return element that may still carry a shard flags.

The walk is deliberately one-sided: ``False`` means *provably uniform
or unknown* (stay quiet), so an opaque call keeps the join of its
operands and an un-analyzable body never flags.
"""
from __future__ import annotations

import ast

from ..callgraph import CallGraph, module_of
from ..core import LintPass, dotted_name, register_pass
from .. import mxshard


@register_pass
class ReplicationSoundnessPass(LintPass):
    id = "replication-soundness"
    doc = ("a shard_map out_spec claiming replication (P()) on a "
           "return value that may still carry a per-device shard (no "
           "psum/pmean/all_gather on the path) — the silent "
           "wrong-answer shape check_vma=False stops checking "
           "at runtime")

    def check_file(self, src):
        return ()

    def finalize(self):
        graph = self.project.callgraph()
        for fn in graph.functions.values():
            for call in self._local_calls(fn):
                if mxshard.is_shard_map(call):
                    yield from self._check_site(fn.src, call, fn,
                                                graph, None)
        for src in self.project.files:
            module = module_of(src.path)
            for call in mxshard.module_calls(src):
                if mxshard.is_shard_map(call):
                    yield from self._check_site(src, call, None,
                                                graph, module)

    # ------------------------------------------------------------- check
    def _check_site(self, src, call, within, graph, module):
        out_expr = call.args[3] if len(call.args) >= 4 else None
        for kw in call.keywords:
            if kw.arg == "out_specs":
                out_expr = kw.value
        if out_expr is None:
            return
        specs = mxshard.spec_tuple(out_expr, within, graph)
        if not specs or not any(s is not None and s.replicated()
                                for s in specs):
            return
        target, bound_args, bound_kws = mxshard.body_target(call)
        if isinstance(target, ast.Lambda):
            if within is None:
                return
            state = mxshard.lambda_return_state(target, within, graph)
            body_name = "the lambda body"
        else:
            if within is not None:
                body, bound = mxshard.body_fn(call, within, graph)
            else:
                body, bound = mxshard.body_fn_module(call, module,
                                                     graph)
            if body is None:
                return
            state = mxshard.body_return_state(body, graph, bound)
            body_name = f"{body.node.name} ({body.src.path}:" \
                        f"{body.node.lineno})"
        states = state if isinstance(state, list) else [state]
        if len(specs) == 1 and len(states) > 1:
            specs = specs * len(states)     # jax broadcasts a single
            # out_spec over the output pytree: every leaf claims it
        if len(specs) != len(states):
            return      # structure mismatch: stay quiet, rank/shape
            # errors are trace-time loud already
        for i, (spec, st) in enumerate(zip(specs, states)):
            if spec is None or not spec.replicated():
                continue
            if mxshard.any_shard(st):
                yield self.issue(
                    src, call,
                    f"out_specs[{i}] claims a replicated output (P()) "
                    f"but return value #{i} of {body_name} may still "
                    f"be a per-device shard — no "
                    f"psum/pmean/all_gather reduces it on every path. "
                    f"check_vma=False disables JAX's replication "
                    f"check, so each device would silently keep its "
                    f"own different value; reduce the value, shard "
                    f"the out_spec, or suppress with the contract "
                    f"spelled out")

    @staticmethod
    def _local_calls(fn):
        for node in CallGraph._local_nodes(fn.node):
            if isinstance(node, ast.Call):
                yield node
