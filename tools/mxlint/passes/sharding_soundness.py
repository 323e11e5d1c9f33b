"""sharding-soundness: static validation of SPMD partition specs.

Every ``PartitionSpec`` in the tree is a promise about a mesh and an
array that nothing checked before ISSUE-19: a mistyped axis name fails
at trace time (at best), an axis used twice is rejected by XLA at
lowering, a spec longer than the array's rank is a trace error, and —
the silent one — a dim sharded over an axis whose extent does not
divide it either errors at dispatch or pads per-device shards
depending on the API.  All four are decidable from the AST here:

- the **mesh** resolves through :mod:`..mxshard`'s extended walk
  (``Mesh(...)`` literals, ``make_mesh``-style helpers, and
  ``placement.replica_mesh`` sub-meshes via constant-propagated
  axis-name params), giving axis names *and* static extents where the
  device operand is literal enough (``.reshape(1, 8)``,
  ``devices[:4]``);
- the **spec** resolves through tuple literals, concatenation, local
  names and helper returns (``via helper (file:line)`` chains);
- the **array** rank/dims come from one muted mxshape interpretation
  of the enclosing function (:func:`..shapes.observe_calls`), so the
  symbolic Dim lattice decides divisibility: ``H`` over extent-8 is
  unknown (quiet), ``12`` over extent-8 is provably wrong (flagged),
  ``16`` over extent-8 is provably fine.

Checked sites: ``shard_map``/``shmap``
in_specs+out_specs (and the arrays at the site's application calls),
``NamedSharding(mesh, spec)``, and ``with_sharding_constraint(x,
spec)``.  When the mesh is a runtime value, axis names are checked
against the project-wide axis universe instead (same convention as
collective-soundness).
"""
from __future__ import annotations

import ast

from ..callgraph import CallGraph, module_of
from ..core import LintPass, dotted_name, register_pass
from .. import mxshard
from ..shapes import rules, _as_arr, observe_calls


@register_pass
class ShardingSoundnessPass(LintPass):
    id = "sharding-soundness"
    doc = ("PartitionSpec/NamedSharding/with_sharding_constraint/"
           "shard_map specs: axis names must exist on the resolved "
           "mesh, no axis twice in one spec, spec rank must fit the "
           "array, and sharded dims must be divisible by the axis "
           "extent under the symbolic Dim lattice")

    def check_file(self, src):
        return ()

    def finalize(self):
        graph = self.project.callgraph()
        universe = mxshard.axis_universe(self.project)
        self._obs_cache = {}
        self._emitted = set()       # (path, line, message) dedup: one
        # spec object reachable from two operands reports once
        for fn in graph.functions.values():
            for call in self._local_calls(fn):
                name = dotted_name(call.func)
                term = name.rsplit(".", 1)[-1]
                if mxshard.is_shard_map(call):
                    yield from self._check_shard_map(fn, call, graph,
                                                     universe)
                elif term == "NamedSharding" and len(call.args) >= 2:
                    mesh = mxshard.mesh_info_of(call.args[0], fn, graph)
                    yield from self._check_specs(
                        fn.src, call, call.args[1], fn, graph, mesh,
                        universe)
                elif term == "with_sharding_constraint" \
                        and len(call.args) >= 2:
                    yield from self._check_wsc(fn, call, graph,
                                               universe)
        # module-scope sites (`apply = shard_map(body, MESH, ...)` at
        # top level) belong to no FunctionInfo
        for src in self.project.files:
            module = module_of(src.path)
            for call in mxshard.module_calls(src):
                if not mxshard.is_shard_map(call):
                    continue
                mesh = mxshard.mesh_info_of_module(
                    mxshard.mesh_expr(call), src, module, graph)
                for operand in self._spec_operands(call):
                    yield from self._check_specs(
                        src, call, operand, None, graph, mesh, universe)

    # ------------------------------------------------------------- sites
    @staticmethod
    def _spec_operands(call):
        """in_specs / out_specs expressions at a shard_map site."""
        ops = {}
        if len(call.args) >= 3:
            ops["in_specs"] = call.args[2]
        if len(call.args) >= 4:
            ops["out_specs"] = call.args[3]
        for kw in call.keywords:
            if kw.arg in ("in_specs", "out_specs"):
                ops[kw.arg] = kw.value
        return list(ops.values())

    def _check_shard_map(self, fn, call, graph, universe):
        mesh = mxshard.mesh_info_at_site(call, fn, graph)
        for operand in self._spec_operands(call):
            yield from self._check_specs(fn.src, call, operand, fn,
                                         graph, mesh, universe)
        # positional alignment: arrays handed to the site's
        # applications vs the in_specs tuple
        in_expr = None
        if len(call.args) >= 3:
            in_expr = call.args[2]
        for kw in call.keywords:
            if kw.arg == "in_specs":
                in_expr = kw.value
        aligned = mxshard.spec_tuple(in_expr, fn, graph) \
            if in_expr is not None else None
        if not aligned:
            return
        for app in self._applications(fn, call):
            if any(isinstance(a, ast.Starred) for a in app.args):
                continue
            avs = self._observed(fn).get(id(app))
            if avs is None:
                continue
            specs = aligned
            if len(specs) == 1 and len(app.args) > 1:
                specs = aligned * len(app.args)   # jax broadcasts a
                # single spec over the argument pytree
            for spec, av in zip(specs, avs):
                yield from self._check_spec_vs_arr(
                    fn.src, call, spec, _as_arr(av), mesh)

    def _check_wsc(self, fn, call, graph, universe):
        spec_op = call.args[1]
        mesh = None
        if isinstance(spec_op, ast.Call) and dotted_name(
                spec_op.func).rsplit(".", 1)[-1] == "NamedSharding" \
                and len(spec_op.args) >= 2:
            # axis checks belong to the NamedSharding visit — here we
            # only add the array-vs-spec checks
            mesh = mxshard.mesh_info_of(spec_op.args[0], fn, graph)
        else:
            yield from self._check_specs(fn.src, call, spec_op, fn,
                                         graph, mesh, universe)
        avs = self._observed(fn).get(id(call))
        arr = _as_arr(avs[0]) if avs else None
        spec = mxshard.single_spec(spec_op, fn, graph)
        if spec is not None:
            yield from self._check_spec_vs_arr(fn.src, call, spec, arr,
                                               mesh)

    # ------------------------------------------------------------ checks
    def _check_specs(self, src, site, operand, within, graph, mesh,
                     universe):
        """Axis-name existence + duplicate-axis checks over every spec
        reachable from ``operand``."""
        for spec in mxshard.spec_exprs(operand, within, graph):
            prefix = mxshard.chain_text(spec.hops)
            names = spec.axis_names()
            for n in sorted({x for x in names if names.count(x) > 1}):
                yield self._emit(
                    src, site,
                    f"{prefix}PartitionSpec uses mesh axis {n!r} for "
                    f"more than one dim — an axis can shard at most "
                    f"one dim of a value; XLA rejects the spec at "
                    f"lowering")
            if mesh is not None:
                where = (f"the resolved mesh axes "
                         f"{sorted(mesh.order)}")
                allowed = mesh.names
            elif universe:
                where = (f"any mesh constructed in this project "
                         f"{sorted(universe)}")
                allowed = universe
            else:
                continue
            for n in sorted(set(names)):
                if n not in allowed:
                    yield self._emit(
                        src, site,
                        f"{prefix}PartitionSpec names mesh axis {n!r}, "
                        f"which is not among {where} — a mistyped axis "
                        f"fails at trace time or shards over the wrong "
                        f"device group")

    def _check_spec_vs_arr(self, src, site, spec, arr, mesh):
        """Rank + symbolic-divisibility checks of one spec against one
        inferred array value."""
        if spec is None or spec.open or arr is None or arr.shape is None:
            return
        R = rules()
        prefix = mxshard.chain_text(spec.hops)
        rank = len(arr.shape)
        if len(spec.entries) > rank:
            yield self._emit(
                src, site,
                f"{prefix}PartitionSpec has {len(spec.entries)} dims "
                f"but the array it shards has rank {rank} "
                f"({R.fmt_shape(arr.shape)}) — jax rejects a spec "
                f"longer than the value's rank at trace time")
            return
        if mesh is None:
            return
        for i, entry in enumerate(spec.entries):
            if not entry or i >= rank:
                continue
            extents = [mesh.extents.get(n) for n in entry]
            if any(e is None for e in extents):
                continue        # unknown extent: undecidable, quiet
            total = 1
            for e in extents:
                total *= e
            if total <= 1:
                continue
            dim = arr.shape[i]
            ratio = R.dim_div(dim, R.lit(total))
            # den == 1 -> provably divisible; symbols present ->
            # unknown under the lattice -> quiet; a symbol-free
            # fractional ratio is a proof of non-divisibility
            if ratio is not None and not ratio.syms and ratio.den != 1:
                axis = "*".join(entry)
                yield self._emit(
                    src, site,
                    f"{prefix}dim {i} of the sharded array "
                    f"({R.fmt_dim(dim)}) is not divisible by the "
                    f"extent {total} of mesh axis {axis!r} — each "
                    f"device would need {R.fmt_dim(ratio)} rows; pad "
                    f"the dim or pick a divisible sharding")

    # ----------------------------------------------------------- helpers
    def _emit(self, src, node, message):
        key = (src.path, node.lineno, message)
        if key in self._emitted:
            return None
        self._emitted.add(key)
        return self.issue(src, node, message)

    def _observed(self, fn):
        """Muted-interpretation call observations for ``fn``, cached —
        shard_map application + with_sharding_constraint arrays."""
        obs = self._obs_cache.get(fn.qname)
        if obs is None:
            obs = observe_calls(self.project, fn.src, fn)
            self._obs_cache[fn.qname] = obs
        return obs

    def _applications(self, fn, site):
        """Calls applying the shard_map site's result: direct
        ``shard_map(...)(args)`` and ``f = shard_map(...); f(args)``."""
        bound = None
        for stmt in CallGraph._local_nodes(fn.node):
            if isinstance(stmt, ast.Assign) and stmt.value is site \
                    and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                bound = stmt.targets[0].id
        for node in self._local_calls(fn):
            if node.func is site:
                yield node
            elif bound is not None and isinstance(node.func, ast.Name) \
                    and node.func.id == bound:
                yield node

    @staticmethod
    def _local_calls(fn):
        for node in CallGraph._local_nodes(fn.node):
            if isinstance(node, ast.Call):
                yield node
