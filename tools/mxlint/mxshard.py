"""mxshard: the static SPMD partition model shared by passes 17-19.

The sharding-annotated surface of this repo — ``PartitionSpec`` /
``NamedSharding`` / ``with_sharding_constraint`` / ``shard_map``
in/out specs / buffer donation — is exactly the surface no pass
validated before ISSUE-19, and the compressed trainer's
``check_vma=False`` deliberately turns off the one *runtime* guard
(JAX's static replication check).  This module composes the PR-4 call graph with the
PR-5 symbolic Dim algebra into three reusable analyses:

- **mesh resolution with extents** (:class:`MeshInfo`): the
  collective-soundness mesh walk, extended to record each axis's
  *extent* when the device operand makes it statically knowable
  (``np.array(g).reshape(1, len(g))`` -> ``(1, None)``,
  ``devices[:4]`` -> ``(4,)``) and to resolve helper-built meshes such
  as ``placement.replica_mesh`` by constant-propagating call-site
  string args onto the maker's params (so ``axis_names=("dp",
  axis_name)`` resolves through the ``axis_name="tp"`` default).
- **spec resolution** (:class:`SpecInfo`): every ``P(...)`` /
  ``PartitionSpec(...)`` reachable from a spec operand — through tuple
  literals, tuple concatenation/repetition, local names, and project
  helpers that *return* specs (with a ``via helper (file:line)`` hop
  chain for the finding message).
- **per-device uniformity** (:func:`body_return_state`): a may-carry-
  shard walk over a shard_map body, tuple-aware and interprocedural
  (``qz.allreduce_mean`` returns ``(uniform, per-device)``), washing
  only at the uniform collectives (psum/pmean/pmax/pmin/all_gather) —
  the static twin of the replication check ``check_vma=False``
  disables.

A ``check_vma=False`` site is a shard_map site like any other: that
is the whole point — the sites that opted out of the runtime check
are the ones that need the static one most.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from .callgraph import CallGraph, FunctionInfo
from .core import dotted_name
from .dataflow import COLLECTIVES, UNIFORM_COLLECTIVES

__all__ = [
    "MeshInfo", "SpecInfo", "is_shard_map", "mesh_expr",
    "literal_axis_names", "const_str", "mesh_info_of",
    "mesh_info_of_module", "mesh_info_at_site", "axis_universe",
    "body_target", "bound_uniform", "body_fn", "body_fn_module",
    "module_stmts", "module_calls", "spec_exprs", "spec_tuple",
    "single_spec", "body_return_state", "lambda_return_state",
    "any_shard", "chain_text",
]

SHARD_MAP_NAMES = {"shard_map", "shmap"}
_SPEC_NAMES = {"P", "PartitionSpec"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}
_MAX_DEPTH = 4


def is_shard_map(call: ast.Call) -> bool:
    return dotted_name(call.func).rsplit(".", 1)[-1] in SHARD_MAP_NAMES


def chain_text(hops) -> str:
    if not hops:
        return ""
    return "via " + " -> ".join(f"{n} ({p}:{ln})"
                                for n, p, ln in hops) + ": "


# ---------------------------------------------------------- const strings
def const_str(expr, fn_info, overrides=None):
    """Constant-propagate a string: literal, an ``overrides`` entry
    (call-site value for a helper param), or a Name resolvable to a
    parameter default / simple local assignment in the lexical scope
    chain.  None when unknown."""
    if isinstance(expr, ast.Constant):
        return expr.value if isinstance(expr.value, str) else None
    if not isinstance(expr, ast.Name):
        return None
    if overrides and expr.id in overrides:
        return overrides[expr.id]
    scope = fn_info
    while scope is not None:
        node = scope.node
        args = node.args
        pos = list(args.posonlyargs) + list(args.args)
        for p, d in zip(pos[len(pos) - len(args.defaults):],
                        args.defaults):
            if p.arg == expr.id and isinstance(d, ast.Constant) \
                    and isinstance(d.value, str):
                return d.value
        for p, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None and p.arg == expr.id \
                    and isinstance(d, ast.Constant) \
                    and isinstance(d.value, str):
                return d.value
        all_params = pos + list(args.kwonlyargs) \
            + [p for p in (args.vararg, args.kwarg) if p is not None]
        if any(p.arg == expr.id for p in all_params):
            # a parameter without a constant default is a runtime
            # value — it shadows any outer binding, stay quiet
            return None
        # this scope's own statements only: a same-named local in a
        # nested sibling def must not constant-propagate out of it
        for stmt in CallGraph._local_nodes(node):
            if isinstance(stmt, ast.Assign) \
                    and isinstance(stmt.value, ast.Constant) \
                    and isinstance(stmt.value.value, str) \
                    and any(isinstance(t, ast.Name) and t.id == expr.id
                            for t in stmt.targets):
                return stmt.value.value
        scope = scope.parent
    return None


# -------------------------------------------------------- mesh resolution
class MeshInfo:
    """A statically resolved mesh: axis names in mesh order, per-axis
    extent (int or None when unknowable), and the helper hop chain for
    meshes built by a maker function."""

    __slots__ = ("order", "extents", "hops")

    def __init__(self, order, extents, hops=()):
        self.order: Tuple[str, ...] = tuple(order)
        self.extents: Dict[str, Optional[int]] = dict(extents)
        self.hops = tuple(hops)

    @property
    def names(self):
        return set(self.order)

    def __repr__(self):
        return f"MeshInfo({self.order}, {self.extents})"


def mesh_expr(call: ast.Call):
    """The mesh operand of a shard_map-family site (positional arg 1 or
    ``mesh=``)."""
    mesh = None
    if len(call.args) >= 2:
        mesh = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mesh":
            mesh = kw.value
    return mesh


def literal_axis_names(call: ast.Call):
    """axis_names from a ``Mesh(devices, axis_names=("dp", ...))`` call
    (positional arg 1 or keyword) when every element is a string
    literal, or None."""
    if dotted_name(call.func).rsplit(".", 1)[-1] != "Mesh":
        return None
    cand = _axis_names_operand(call)
    if isinstance(cand, (ast.Tuple, ast.List)) and cand.elts and all(
            isinstance(e, ast.Constant) and isinstance(e.value, str)
            for e in cand.elts):
        return {e.value for e in cand.elts}
    if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
        return {cand.value}
    return None


def _axis_names_operand(call: ast.Call):
    cand = None
    if len(call.args) >= 2:
        cand = call.args[1]
    for kw in call.keywords:
        if kw.arg == "axis_names":
            cand = kw.value
    return cand


def _int_const(expr) -> Optional[int]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int) \
            and not isinstance(expr.value, bool):
        return expr.value
    return None


def _device_extents(dev, n_axes: int):
    """Best-effort per-axis extents from a Mesh devices operand."""
    if dev is None:
        return (None,) * n_axes
    # np.array(g).reshape(1, len(g)) / arr.reshape((a, b))
    if isinstance(dev, ast.Call) and isinstance(dev.func, ast.Attribute) \
            and dev.func.attr == "reshape":
        args = list(dev.args)
        if len(args) == 1 and isinstance(args[0], (ast.Tuple, ast.List)):
            args = list(args[0].elts)
        if len(args) == n_axes:
            return tuple(_int_const(a) for a in args)
    # mesh_utils.create_device_mesh((4, 2))
    if isinstance(dev, ast.Call) and dotted_name(dev.func).rsplit(
            ".", 1)[-1] == "create_device_mesh" and dev.args \
            and isinstance(dev.args[0], (ast.Tuple, ast.List)) \
            and len(dev.args[0].elts) == n_axes:
        return tuple(_int_const(a) for a in dev.args[0].elts)
    # 1-D: np.array(devices[:4]) -> extent 4
    if n_axes == 1:
        inner = dev
        if isinstance(inner, ast.Call) and dotted_name(
                inner.func).rsplit(".", 1)[-1] in ("array", "asarray") \
                and inner.args:
            inner = inner.args[0]
        if isinstance(inner, ast.Subscript) \
                and isinstance(inner.slice, ast.Slice) \
                and inner.slice.lower is None \
                and inner.slice.step is None:
            return (_int_const(inner.slice.upper),)
    return (None,) * n_axes


def mesh_ctor_info(call: ast.Call, fn_info,
                   overrides=None) -> Optional[MeshInfo]:
    """MeshInfo from a direct ``Mesh(...)`` constructor; axis-name
    elements constant-propagate through ``fn_info``'s scope chain (and
    ``overrides``, for helper-call argument binding)."""
    if dotted_name(call.func).rsplit(".", 1)[-1] != "Mesh":
        return None
    cand = _axis_names_operand(call)
    if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
        names: Optional[Tuple[str, ...]] = (cand.value,)
    elif isinstance(cand, (ast.Tuple, ast.List)) and cand.elts:
        out = []
        for e in cand.elts:
            v = const_str(e, fn_info, overrides)
            if v is None:
                return None
            out.append(v)
        names = tuple(out)
    else:
        return None
    if len(set(names)) != len(names):
        return None
    dev = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg == "devices":
            dev = kw.value
    ext = _device_extents(dev, len(names))
    return MeshInfo(names, dict(zip(names, ext)))


def _info_in_maker(maker: Optional[FunctionInfo], call: ast.Call,
                   caller_path: str) -> Optional[MeshInfo]:
    """Mesh ctor inside a make_mesh-style helper, with the call's
    literal string args const-propagated onto the maker's params so
    ``replica_mesh(group, axis_name="model")`` resolves to the right
    axis set."""
    if maker is None:
        return None
    overrides = {}
    for i, arg in CallGraph.arg_map(call, maker).items():
        if i < len(maker.params) and isinstance(arg, ast.Constant) \
                and isinstance(arg.value, str):
            overrides[maker.params[i]] = arg.value
    for node in ast.walk(maker.node):
        if isinstance(node, ast.Call):
            info = mesh_ctor_info(node, maker, overrides)
            if info is not None:
                return MeshInfo(
                    info.order, info.extents,
                    ((maker.node.name, caller_path, call.lineno),))
    return None


def mesh_info_of(expr, within: Optional[FunctionInfo],
                 graph) -> Optional[MeshInfo]:
    """Resolve a mesh expression inside ``within`` to a MeshInfo: a
    direct ctor / maker call, or a Name bound by a ctor assignment in
    the lexical scope chain (params shadow — a runtime mesh stays
    unresolved)."""
    if within is None:
        return None
    if isinstance(expr, ast.Call):
        return _info_of_ctor(expr, within, graph)
    if isinstance(expr, ast.Name):
        scope = within
        while scope is not None:
            args = scope.node.args
            params = set(scope.params) | {
                p.arg for p in (args.vararg, args.kwarg)
                if p is not None}
            if expr.id in params:
                return None
            for stmt in CallGraph._local_nodes(scope.node):
                if isinstance(stmt, ast.Assign) \
                        and isinstance(stmt.value, ast.Call) \
                        and any(isinstance(t, ast.Name)
                                and t.id == expr.id
                                for t in stmt.targets):
                    return _info_of_ctor(stmt.value, scope, graph)
            scope = scope.parent
    return None


def _info_of_ctor(call, within, graph) -> Optional[MeshInfo]:
    info = mesh_ctor_info(call, within, None)
    if info is not None:
        return info
    maker = graph.resolve_call(call, within)
    return _info_in_maker(maker, call, within.src.path)


def mesh_info_of_module(expr, src, module, graph) -> Optional[MeshInfo]:
    """Module-scope variant of :func:`mesh_info_of`: names resolve
    through module-level assignments only."""
    if isinstance(expr, ast.Call):
        return _info_of_ctor_module(expr, src, module, graph)
    if isinstance(expr, ast.Name):
        for stmt in module_stmts(src):
            if isinstance(stmt, ast.Assign) \
                    and isinstance(stmt.value, ast.Call) \
                    and any(isinstance(t, ast.Name) and t.id == expr.id
                            for t in stmt.targets):
                return _info_of_ctor_module(stmt.value, src, module,
                                            graph)
    return None


def _info_of_ctor_module(call, src, module, graph) -> Optional[MeshInfo]:
    info = mesh_ctor_info(call, None, None)
    if info is not None:
        return info
    q = graph._lookup(dotted_name(call.func), module)
    maker = graph.functions.get(q) if q else None
    return _info_in_maker(maker, call, src.path)


def mesh_info_at_site(call: ast.Call, within, graph) -> Optional[MeshInfo]:
    return mesh_info_of(mesh_expr(call), within, graph)


def axis_universe(project) -> set:
    """Every literal mesh axis name in the scanned tree — the fallback
    axis set when a site's mesh is a runtime value."""
    names = set()
    for src in project.files:
        for node in src.nodes():
            if isinstance(node, ast.Call):
                axes = literal_axis_names(node)
                if axes:
                    names |= axes
    return names


# ---------------------------------------------------- shard_map site model
def body_target(call: ast.Call):
    """The body expression at a shard_map site, with any
    ``partial(body, ...)`` wrapper peeled off: returns
    ``(target, bound_args, bound_kws)``."""
    target = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg in ("f", "fun"):
            target = kw.value
    bound_args, bound_kws = (), ()
    if isinstance(target, ast.Call) and dotted_name(
            target.func).rsplit(".", 1)[-1] == "partial" \
            and target.args:
        bound_args = target.args[1:]
        bound_kws = target.keywords
        target = target.args[0]
    return target, bound_args, bound_kws


def bound_uniform(body: FunctionInfo, bound_args, bound_kws) -> frozenset:
    """Params pre-bound by ``partial`` to a literal constant —
    identical on every device (config flags), so they must not seed
    divergence/shard taint; the remaining params receive the shards."""
    bound = set()
    for i, a in enumerate(bound_args):
        if isinstance(a, ast.Constant) and i < len(body.params):
            bound.add(body.params[i])
    for kw in bound_kws:
        if kw.arg is not None and isinstance(kw.value, ast.Constant) \
                and kw.arg in body.params:
            bound.add(kw.arg)
    return frozenset(bound)


def body_fn(call, within, graph):
    """Resolve a shard_map site's body function; returns
    ``(FunctionInfo, bound_uniform_params)``."""
    target, bound_args, bound_kws = body_target(call)
    if target is None:
        return None, frozenset()
    body = graph.resolve_ref(target, within)
    if body is None:
        return None, frozenset()
    return body, bound_uniform(body, bound_args, bound_kws)


def body_fn_module(call, module, graph):
    """Module-scope variant: the body name resolves through the module
    namespace instead of a lexical scope chain."""
    target, bound_args, bound_kws = body_target(call)
    if target is None:
        return None, frozenset()
    q = graph._lookup(dotted_name(target), module)
    body = graph.functions.get(q) if q else None
    if body is None:
        return None, frozenset()
    return body, bound_uniform(body, bound_args, bound_kws)


def module_stmts(src):
    """Module-scope statements/expressions only (function and class
    bodies excluded)."""
    stack = list(ast.iter_child_nodes(src.tree))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def module_calls(src):
    for n in module_stmts(src):
        if isinstance(n, ast.Call):
            yield n


# --------------------------------------------------------- spec resolution
class SpecInfo:
    """One resolved ``P(...)``: ``entries`` is one tuple of axis names
    per array dim (``()`` = replicated dim, ``None`` = unresolvable
    element), ``open`` marks dynamically built specs (``P(*names)``)
    whose rank is unknowable, ``node`` anchors at the ``P`` call,
    ``hops`` is the helper chain the spec was resolved through."""

    __slots__ = ("node", "entries", "open", "hops")

    def __init__(self, node, entries, open_, hops=()):
        self.node = node
        self.entries = tuple(entries)
        self.open = open_
        self.hops = tuple(hops)

    def replicated(self) -> bool:
        """Does this spec claim a fully replicated value?  True for
        ``P()`` and all-None specs; never for open specs."""
        return not self.open and all(e == () for e in self.entries)

    def axis_names(self) -> List[str]:
        out = []
        for e in self.entries:
            if e:
                out.extend(e)
        return out


def _is_spec_call(expr) -> bool:
    return isinstance(expr, ast.Call) and dotted_name(
        expr.func).rsplit(".", 1)[-1] in _SPEC_NAMES


def _spec_entry(expr, fn_info):
    if isinstance(expr, ast.Constant):
        if expr.value is None:
            return ()
        if isinstance(expr.value, str):
            return (expr.value,)
        return None
    if isinstance(expr, (ast.Tuple, ast.List)):
        names = []
        for e in expr.elts:
            v = const_str(e, fn_info)
            if v is None:
                return None
            names.append(v)
        return tuple(names)
    v = const_str(expr, fn_info)
    return (v,) if v is not None else None


def _spec_call_info(call, fn_info, hops) -> SpecInfo:
    entries, open_ = [], False
    for a in call.args:
        if isinstance(a, ast.Starred):
            open_ = True
            continue
        entries.append(_spec_entry(a, fn_info))
    return SpecInfo(call, entries, open_, hops)


def _local_value(name: str, fn_info):
    """``(value_expr, scope)`` of the unique local assignment binding
    ``name`` in the lexical scope chain, or None (params shadow; more
    than one assignment is ambiguous — stay quiet)."""
    scope = fn_info
    while scope is not None:
        args = scope.node.args
        params = set(scope.params) | {
            p.arg for p in (args.vararg, args.kwarg) if p is not None}
        if name in params:
            return None
        hits = [stmt.value for stmt in CallGraph._local_nodes(scope.node)
                if isinstance(stmt, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == name
                        for t in stmt.targets)]
        if len(hits) == 1:
            return hits[0], scope
        if hits:
            return None
        scope = scope.parent
    return None


def _return_exprs(fn: FunctionInfo):
    for n in CallGraph._local_nodes(fn.node):
        if isinstance(n, ast.Return) and n.value is not None:
            yield n.value


def spec_exprs(expr, within, graph, hops=(), depth=0):
    """Yield a SpecInfo for every PartitionSpec reachable from a spec
    operand: through tuple literals, tuple concatenation/repetition
    (``(P(),) + (P("dp"),) * n``), local names, ``NamedSharding``
    wrappers, and project helpers that return specs (adding a ``via
    helper (file:line)`` hop)."""
    if expr is None or depth > _MAX_DEPTH:
        return
    if _is_spec_call(expr):
        yield _spec_call_info(expr, within, hops)
        return
    if isinstance(expr, ast.Call):
        term = dotted_name(expr.func).rsplit(".", 1)[-1]
        if term == "NamedSharding" and len(expr.args) >= 2:
            yield from spec_exprs(expr.args[1], within, graph, hops,
                                  depth + 1)
            return
        if within is not None and graph is not None:
            callee = graph.resolve_call(expr, within)
            if callee is not None and callee.node.name != "__init__":
                nxt = hops + ((callee.node.name, within.src.path,
                               expr.lineno),)
                for ret in _return_exprs(callee):
                    yield from spec_exprs(ret, callee, graph, nxt,
                                          depth + 1)
        return
    if isinstance(expr, (ast.Tuple, ast.List)):
        for e in expr.elts:
            yield from spec_exprs(e, within, graph, hops, depth + 1)
        return
    if isinstance(expr, ast.BinOp):
        yield from spec_exprs(expr.left, within, graph, hops, depth + 1)
        yield from spec_exprs(expr.right, within, graph, hops, depth + 1)
        return
    if isinstance(expr, ast.IfExp):
        yield from spec_exprs(expr.body, within, graph, hops, depth + 1)
        yield from spec_exprs(expr.orelse, within, graph, hops,
                              depth + 1)
        return
    if isinstance(expr, ast.Starred):
        yield from spec_exprs(expr.value, within, graph, hops, depth + 1)
        return
    if isinstance(expr, ast.Name) and within is not None:
        bound = _local_value(expr.id, within)
        if bound is not None:
            value, scope = bound
            yield from spec_exprs(value, scope, graph, hops, depth + 1)


def single_spec(expr, within, graph, hops=(),
                depth=0) -> Optional[SpecInfo]:
    """Resolve an expression expected to be ONE spec (an in_specs /
    out_specs tuple element), or None."""
    if expr is None or depth > _MAX_DEPTH:
        return None
    if _is_spec_call(expr):
        return _spec_call_info(expr, within, hops)
    if isinstance(expr, ast.Call):
        term = dotted_name(expr.func).rsplit(".", 1)[-1]
        if term == "NamedSharding" and len(expr.args) >= 2:
            return single_spec(expr.args[1], within, graph, hops,
                               depth + 1)
        return None
    if isinstance(expr, ast.Name) and within is not None:
        bound = _local_value(expr.id, within)
        if bound is not None:
            value, scope = bound
            return single_spec(value, scope, graph, hops, depth + 1)
    return None


def spec_tuple(expr, within, graph, depth=0):
    """Positionally aligned spec list from a *plain tuple literal*
    operand (each element a SpecInfo or None); None when the operand's
    structure is not statically alignable (concatenation, repetition,
    a runtime value) — axis checks then ride :func:`spec_exprs` and
    positional checks stay quiet."""
    if expr is None or depth > _MAX_DEPTH:
        return None
    if isinstance(expr, (ast.Tuple, ast.List)):
        if any(isinstance(e, ast.Starred) for e in expr.elts):
            return None
        return [single_spec(e, within, graph, depth=depth + 1)
                for e in expr.elts]
    if _is_spec_call(expr):
        return [_spec_call_info(expr, within, ())]
    if isinstance(expr, ast.Name) and within is not None:
        bound = _local_value(expr.id, within)
        if bound is not None:
            value, scope = bound
            return spec_tuple(value, scope, graph, depth + 1)
    return None


# --------------------------------------------- per-device uniformity walk
# State domain: False = provably uniform-or-unknown (never flag),
# True = may still carry a per-device shard, list = tuple of states.
def any_shard(state) -> bool:
    if isinstance(state, list):
        return any(any_shard(s) for s in state)
    return bool(state)


def _u_join(a, b):
    if isinstance(a, list) and isinstance(b, list) and len(a) == len(b):
        return [_u_join(x, y) for x, y in zip(a, b)]
    return any_shard(a) or any_shard(b)


class _ShardWalk:
    """One may-carry-shard walk over one function body.  Params seed
    tainted (they ARE the shards by shard_map construction); the
    uniform collectives wash; resolvable project helpers are walked
    recursively with the caller's argument states so
    ``allreduce_mean`` comes back ``[uniform, per-device]``."""

    def __init__(self, fn: FunctionInfo, graph,
                 stack=frozenset(), depth=0):
        self.fn = fn
        self.graph = graph
        self.stack = stack
        self.depth = depth
        self.returns: List[object] = []

    def run(self, env):
        # two passes: the second resolves forward references and
        # loop-carried states, the same discipline as the dataflow walk
        for _ in range(2):
            self.returns = []
            self._block(self.fn.node.body, env)
        out = None
        for r in self.returns:
            out = r if out is None else _u_join(out, r)
        return False if out is None else out

    # ------------------------------------------------------- statements
    def _block(self, stmts, env):
        for s in stmts:
            self._stmt(s, env)

    def _stmt(self, stmt, env):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return
        if isinstance(stmt, ast.Assign):
            st = self._eval(stmt.value, env)
            for t in stmt.targets:
                self._bind(t, st, env)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._bind(stmt.target, self._eval(stmt.value, env), env)
        elif isinstance(stmt, ast.AugAssign):
            st = self._eval(stmt.value, env)
            if isinstance(stmt.target, ast.Name):
                env[stmt.target.id] = _u_join(
                    env.get(stmt.target.id, False), st)
        elif isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self.returns.append(self._eval(stmt.value, env))
            else:
                self.returns.append(False)
        elif isinstance(stmt, ast.If):
            e1, e2 = dict(env), dict(env)
            self._block(stmt.body, e1)
            self._block(stmt.orelse, e2)
            for k in set(e1) | set(e2):
                env[k] = _u_join(e1.get(k, False), e2.get(k, False))
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._bind(stmt.target,
                       any_shard(self._eval(stmt.iter, env)), env)
            for _ in range(2):
                self._block(stmt.body, env)
            self._block(stmt.orelse, env)
        elif isinstance(stmt, ast.While):
            for _ in range(2):
                self._block(stmt.body, env)
            self._block(stmt.orelse, env)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                if item.optional_vars is not None:
                    self._bind(item.optional_vars,
                               self._eval(item.context_expr, env), env)
            self._block(stmt.body, env)
        elif isinstance(stmt, ast.Try):
            self._block(stmt.body, env)
            for h in stmt.handlers:
                self._block(h.body, env)
            self._block(stmt.orelse, env)
            self._block(stmt.finalbody, env)

    def _bind(self, target, state, env):
        if isinstance(target, ast.Name):
            env[target.id] = state
        elif isinstance(target, (ast.Tuple, ast.List)):
            elts = target.elts
            if isinstance(state, list) and len(state) == len(elts) \
                    and not any(isinstance(e, ast.Starred)
                                for e in elts):
                for e, s in zip(elts, state):
                    self._bind(e, s, env)
            else:
                flat = any_shard(state)
                for e in elts:
                    self._bind(e.value if isinstance(e, ast.Starred)
                               else e, flat, env)
        elif isinstance(target, ast.Subscript) \
                and isinstance(target.value, ast.Name):
            # container store: the container may now carry the shard
            base = target.value.id
            env[base] = _u_join(env.get(base, False), state)
        # attribute targets: untracked

    # ------------------------------------------------------ expressions
    def _eval(self, expr, env):
        if isinstance(expr, ast.Constant):
            return False
        if isinstance(expr, ast.Name):
            return env.get(expr.id, False)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return [self._eval(e, env) for e in expr.elts]
        if isinstance(expr, ast.Dict):
            return any(any_shard(self._eval(v, env))
                       for v in expr.values if v is not None)
        if isinstance(expr, ast.Call):
            return self._call(expr, env)
        if isinstance(expr, ast.Attribute):
            if expr.attr in _STATIC_ATTRS:
                return False
            return any_shard(self._eval(expr.value, env))
        if isinstance(expr, ast.Subscript):
            base = self._eval(expr.value, env)
            if isinstance(base, list):
                i = _int_const(expr.slice)
                if i is not None and -len(base) <= i < len(base):
                    return base[i]
            return any_shard(base)
        if isinstance(expr, ast.IfExp):
            return _u_join(self._eval(expr.body, env),
                           self._eval(expr.orelse, env))
        if isinstance(expr, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp, ast.DictComp)):
            inner = dict(env)
            for gen in expr.generators:
                self._bind(gen.target,
                           any_shard(self._eval(gen.iter, inner)),
                           inner)
            if isinstance(expr, ast.DictComp):
                return any_shard(self._eval(expr.value, inner))
            return any_shard(self._eval(expr.elt, inner))
        if isinstance(expr, ast.Starred):
            return self._eval(expr.value, env)
        if isinstance(expr, ast.Lambda):
            return False
        out = False
        for child in ast.iter_child_nodes(expr):
            if isinstance(child, ast.expr):
                out = _u_join(out, self._eval(child, env))
        return out

    def _call(self, call, env):
        name = dotted_name(call.func)
        term = name.rsplit(".", 1)[-1]
        if "." in name and term in UNIFORM_COLLECTIVES:
            return False        # psum-family: identical on every device
        if "." in name and term in COLLECTIVES - UNIFORM_COLLECTIVES:
            return True     # ppermute/all_to_all/... and axis_index:
            # each device holds a DIFFERENT value
        arg_states = [self._eval(a, env) for a in call.args]
        kw_states = [self._eval(kw.value, env) for kw in call.keywords]
        callee = self.graph.resolve_call(call, self.fn) \
            if self.graph is not None else None
        if callee is not None and callee.node.name != "__init__" \
                and self.depth < _MAX_DEPTH \
                and callee.qname not in self.stack:
            amap = CallGraph.arg_map(call, callee)
            seed = {}
            for i, p in enumerate(callee.params):
                node = amap.get(i)
                seed[p] = self._eval(node, env) if node is not None \
                    else False
            a = callee.node.args
            if a.vararg is not None:
                extra = arg_states[callee.n_positional
                                   - (1 if callee.is_method else 0):]
                seed[a.vararg.arg] = any(any_shard(s) for s in extra)
            if a.kwarg is not None:
                seed[a.kwarg.arg] = any(any_shard(s)
                                        for s in kw_states)
            sub = _ShardWalk(callee, self.graph,
                             self.stack | {callee.qname},
                             self.depth + 1)
            return sub.run(seed)
        # opaque call (jnp ops, unresolvable helpers): elementwise /
        # reductions preserve shard-ness — join of the operands
        out = False
        for s in arg_states + kw_states:
            out = _u_join(out, s)
        if isinstance(call.func, ast.Attribute):
            out = _u_join(out, self._eval(call.func.value, env))
        return out


def body_return_state(body: FunctionInfo, graph,
                      uniform=frozenset()):
    """Joined per-element may-carry-shard state of a shard_map body's
    return value (list for tuple returns).  ``uniform`` params (bound
    by ``partial`` to literals) seed clean."""
    env = {}
    for p in body.params:
        env[p] = p not in uniform and p not in ("self", "cls")
    a = body.node.args
    if a.vararg is not None:
        env[a.vararg.arg] = True
    if a.kwarg is not None:
        env[a.kwarg.arg] = True
    return _ShardWalk(body, graph).run(env)


def lambda_return_state(lam: ast.Lambda, within: FunctionInfo, graph):
    """May-carry-shard state of a ``lambda`` shard_map body."""
    a = lam.args
    env = {p.arg: True
           for p in list(a.posonlyargs) + list(a.args)
           + list(a.kwonlyargs)}
    if a.vararg is not None:
        env[a.vararg.arg] = True
    if a.kwarg is not None:
        env[a.kwarg.arg] = True
    walk = _ShardWalk(within, graph)
    return walk._eval(lam.body, env)
