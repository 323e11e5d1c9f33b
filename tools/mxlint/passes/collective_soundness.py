"""collective-soundness: static deadlock/axis checks for shard_map bodies.

On TPU, a collective with a wrong axis name fails at trace time at
best; a collective that only *some* devices reach deadlocks the whole
slice with no traceback — the most expensive bug class the parallel
layer can ship (cf. EQuARX on XLA collective pitfalls, PAPERS.md).
Three checks over every function reachable from a ``shard_map`` body
(nested defs included — loop bodies handed to ``lax.scan`` /
``fori_loop`` count):

1. **axis-name**: the axis of ``lax.psum`` / ``ppermute`` /
   ``all_gather`` / ... must be drawn from the mesh axes of the
   enclosing ``shard_map`` site when the mesh is statically resolvable
   (a ``Mesh(..., axis_names=(...))`` literal, or a helper like
   ``make_mesh`` that constructs one), else from the project-wide axis
   universe (every ``axis_names`` literal in the tree).  Axis variables
   are constant-propagated through enclosing-scope parameter defaults;
   an unresolvable axis stays quiet.
2. **ppermute totality**: a ``perm`` whose source set differs from its
   destination set is not a permutation of the axis — some device
   sends and never receives (or vice versa), which zero-fills or
   deadlocks depending on the lowering.  Literal pair lists are checked
   exactly; ``[(j, (j + c) % N) for j in range(N)]`` rings are
   recognized as total; a shifted comprehension without the wrapping
   modulo (``range(N - 1)``-style fill-drain hand-offs) is flagged —
   when the drop is deliberate, say so in a suppression.
3. **divergence**: a collective under control flow that branches on a
   per-device value (a shard of a body argument, ``lax.axis_index``) —
   python ``if``, ``lax.cond`` / ``lax.while_loop`` / ``lax.switch``
   branches — is the static deadlock shape: devices disagree on whether
   the collective runs.  Collective *results* (``psum`` of a shard) are
   uniform across the axis and do not taint.
"""
from __future__ import annotations

import ast

from ..callgraph import CallGraph, module_of
from ..core import LintPass, dotted_name, register_pass
from ..dataflow import (COLLECTIVES, COMM_COLLECTIVES,
                        UNIFORM_COLLECTIVES)
from .. import mxshard

# collectives whose arg 1 (or axis_name=) names the axis; axis_index
# takes it at position 0
_AXIS_ARG = {c: (0 if c == "axis_index" else 1) for c in COLLECTIVES}
_CTRL = {"cond", "while_loop", "switch"}

# shared with the SPMD passes (ISSUE-19): a check_vma=False site is a
# shard_map site too — it is exactly the variant whose bodies need the
# static checks most, since the runtime replication check is off there
_is_shard_map = mxshard.is_shard_map
_const_str = mxshard.const_str


def _axis_names_of(expr, fn_info):
    """Resolve an axis operand to a set of names ({} = unresolvable)."""
    if isinstance(expr, (ast.Tuple, ast.List)):
        out = set()
        for e in expr.elts:
            v = _const_str(e, fn_info)
            if v is not None:
                out.add(v)
        return out
    v = _const_str(expr, fn_info)
    return {v} if v is not None else set()


class _PermCheck:
    """Static totality analysis of a ppermute ``perm`` operand."""

    @staticmethod
    def verdict(perm):
        """'total', 'non-total', or None (unrecognized shape)."""
        if isinstance(perm, (ast.List, ast.Tuple)):
            return _PermCheck._literal(perm.elts)
        if isinstance(perm, ast.ListComp) and len(perm.generators) == 1:
            return _PermCheck._comprehension(perm)
        return None

    @staticmethod
    def _literal(elts):
        pairs = []
        for e in elts:
            if not (isinstance(e, (ast.Tuple, ast.List))
                    and len(e.elts) == 2
                    and all(isinstance(x, ast.Constant)
                            and isinstance(x.value, int)
                            for x in e.elts)):
                return None
            pairs.append((e.elts[0].value, e.elts[1].value))
        if not pairs:
            return None
        srcs = [a for a, _ in pairs]
        dsts = [b for _, b in pairs]
        if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
            return "non-total"          # duplicate sender/receiver
        return "total" if set(srcs) == set(dsts) else "non-total"

    @staticmethod
    def _comprehension(comp):
        gen = comp.generators[0]
        if gen.ifs or not isinstance(gen.target, ast.Name):
            return None
        it = gen.iter
        if not (isinstance(it, ast.Call)
                and dotted_name(it.func) == "range"
                and len(it.args) == 1):
            return None
        rng = it.args[0]
        elt = comp.elt
        if not (isinstance(elt, (ast.Tuple, ast.List))
                and len(elt.elts) == 2):
            return None
        var = gen.target.id

        def is_var(e):
            return isinstance(e, ast.Name) and e.id == var

        def shift_mod(e):
            """(var +/- c) % M -> M expression; plain var -> 'ident'."""
            if is_var(e):
                return "ident"
            if isinstance(e, ast.BinOp) and isinstance(e.op, ast.Mod) \
                    and isinstance(e.left, ast.BinOp) \
                    and isinstance(e.left.op, (ast.Add, ast.Sub)) \
                    and (is_var(e.left.left) or is_var(e.left.right)):
                return e.right
            if isinstance(e, ast.BinOp) \
                    and isinstance(e.op, (ast.Add, ast.Sub)) \
                    and (is_var(e.left) or is_var(e.right)):
                if isinstance(e.op, ast.Sub) and is_var(e.right):
                    # c - var is a reflection ((j, N-1-j) is a total
                    # involution), not a shift — stay quiet
                    return None
                return "shift-no-mod"
            return None

        a, b = shift_mod(elt.elts[0]), shift_mod(elt.elts[1])
        if a is None or b is None:
            return None
        if "shift-no-mod" in (a, b):
            # (i, i+1) over range(N-1): shifted without the wrapping
            # modulo — sources and destinations cannot coincide
            return "non-total"
        for side in (a, b):
            if side != "ident" \
                    and ast.dump(side) != ast.dump(rng):
                return None             # modulo base != range bound
        return "total"


@register_pass
class CollectiveSoundnessPass(LintPass):
    id = "collective-soundness"
    doc = ("shard_map-body collectives: axis names must come from the "
           "enclosing mesh, ppermute perms must be total permutations, "
           "and no collective may sit under per-device control flow "
           "(the static deadlock shape)")

    def check_file(self, src):
        return ()

    def finalize(self):
        graph = self.project.callgraph()
        summaries = self.project.summaries()
        universe = self._axis_universe()
        contexts = self._collect_contexts(graph)    # qname -> axes|None
        uniform = self._uniform_params(graph, contexts)
        for qname, axes in sorted(contexts.items()):
            fn = graph.functions.get(qname)
            if fn is None:
                continue
            allowed = axes if axes else universe
            yield from self._check_body(
                fn, graph, summaries, allowed, strict=bool(axes),
                uniform=uniform.get(qname, frozenset())
                | self._root_bound.get(qname, frozenset()))

    # ------------------------------------------------------------- harvest
    def _axis_universe(self):
        return mxshard.axis_universe(self.project)

    def _collect_contexts(self, graph):
        """Map every function reachable from a shard_map body to the
        union of mesh axes of the sites that reach it (empty set when
        any reaching site's mesh is unresolvable)."""
        contexts = {}
        roots = []
        self._root_bound = {}

        def add_root(body, bound, axes):
            roots.append((body.qname, axes))
            # two sites binding different params: only params bound
            # to a constant at EVERY reaching site stay uniform
            prev = self._root_bound.get(body.qname)
            self._root_bound[body.qname] = bound if prev is None \
                else prev & bound

        for fn in graph.functions.values():
            for call in self._local_calls(fn):
                if not _is_shard_map(call):
                    continue
                body, bound = self._body_fn(call, fn, graph)
                if body is None:
                    continue
                add_root(body, bound, self._site_axes(call, fn, graph))
        # module-scope sites (`apply = shard_map(body, mesh, ...)` at
        # top level — a common JAX idiom) belong to no FunctionInfo,
        # so the walk above cannot see them
        for src in self.project.files:
            module = module_of(src.path)
            for call in self._module_calls(src):
                if not _is_shard_map(call):
                    continue
                body, bound = self._body_fn_module(call, module, graph)
                if body is None:
                    continue
                add_root(body, bound, self._site_axes_module(
                    call, src, module, graph))
        self._root_qnames = {q for q, _ in roots}
        # closure: called functions + lexically nested defs
        kids = {}
        for q, f in graph.functions.items():
            if f.parent is not None:
                kids.setdefault(f.parent.qname, []).append(q)
        pending = list(roots)
        while pending:
            q, axes = pending.pop()
            prev = contexts.get(q)
            if prev is not None:
                merged = (prev or set()) | (axes or set()) \
                    if prev and axes else set()
                if merged == prev:
                    continue
                contexts[q] = merged
            else:
                contexts[q] = axes or set()
            fn = graph.functions.get(q)
            if fn is None:
                continue
            nxt = contexts[q]
            for site in graph.calls.get(q, ()):
                pending.append((site.callee.qname, nxt))
            for sub_q in kids.get(q, ()):
                pending.append((sub_q, nxt))
        return contexts

    def _uniform_params(self, graph, contexts):
        """Params of closure helpers that are uniform by construction:
        every reaching call site passes a value that is not shard-
        derived there (a literal like ``helper(x, True)``, or a host
        config scalar like a closure ``n_stages``) — identical on all
        devices, so it must not seed a divergence.  Any site passing a
        tainted value, or any unmapped param, keeps the conservative
        per-device default.  Two rounds so a uniform param forwarded
        one more hop stays uniform."""
        out = {}
        for _ in range(2):
            nxt = {}
            for q in contexts:
                caller = graph.functions.get(q)
                if caller is None:
                    continue
                tmap = dict(self._device_tainted(
                    caller, out.get(q, frozenset())
                    | self._root_bound.get(q, frozenset())))
                anc = caller.parent     # closure vars taint from the
                while anc is not None:  # lexically enclosing scopes —
                    if anc.qname in contexts:   # only those that are
                        # themselves per-device: a host-side wrapper's
                        # params (n_stages, devices) are uniform
                        for n, b in self._device_tainted(anc).items():
                            tmap.setdefault(n, b)
                    anc = anc.parent
                for site in graph.calls.get(q, ()):
                    cq = site.callee.qname
                    if cq not in contexts:
                        continue
                    params = site.callee.params
                    uni = frozenset(
                        params[i] for i, a in site.arg_map.items()
                        if i < len(params) and not self._expr_tainted(
                            a, tmap, site.node.lineno))
                    nxt[cq] = uni if cq not in nxt else nxt[cq] & uni
            # a shard_map body's params are shards by construction,
            # even if the function is also called directly somewhere
            for q in getattr(self, "_root_qnames", ()):
                nxt.pop(q, None)
            out = nxt
        return out

    # the site/body model lives in mxshard (shared with the SPMD
    # passes, ISSUE-19); mesh resolution there is a strict superset of
    # the pre-split walk — it also constant-propagates axis-name
    # variables through helper params (placement.replica_mesh)
    def _body_fn(self, call, within, graph):
        return mxshard.body_fn(call, within, graph)

    def _body_fn_module(self, call, module, graph):
        return mxshard.body_fn_module(call, module, graph)

    @staticmethod
    def _module_calls(src):
        return mxshard.module_calls(src)

    @staticmethod
    def _module_stmts(src):
        return mxshard.module_stmts(src)

    def _site_axes(self, call, within, graph):
        """Mesh axes at a shard_map site, or None when unresolvable."""
        info = mxshard.mesh_info_at_site(call, within, graph)
        return set(info.order) if info is not None else None

    def _site_axes_module(self, call, src, module, graph):
        info = mxshard.mesh_info_of_module(
            mxshard.mesh_expr(call), src, module, graph)
        return set(info.order) if info is not None else None

    # ------------------------------------------------------------- checks
    def _check_body(self, fn, graph, summaries, allowed, strict,
                    uniform=frozenset()):
        src = fn.src
        tainted = self._device_tainted(fn, uniform)
        for call in self._local_calls(fn):
            name = dotted_name(call.func)
            term = name.rsplit(".", 1)[-1]
            if term in COLLECTIVES and "." in name:
                yield from self._check_axis(src, fn, call, term, allowed,
                                            strict)
                if term == "ppermute":
                    yield from self._check_perm(src, call)
            if term in _CTRL and "." in name:
                yield from self._check_ctrl(src, fn, call, term, tainted,
                                            graph, summaries)
        yield from self._check_if_divergence(fn, graph, summaries,
                                             tainted)

    def _check_axis(self, src, fn, call, term, allowed, strict):
        idx = _AXIS_ARG[term]
        axis = call.args[idx] if len(call.args) > idx else None
        for kw in call.keywords:
            if kw.arg == "axis_name":
                axis = kw.value
        if axis is None:
            return
        names = _axis_names_of(axis, fn)
        for nm in sorted(names):
            if allowed and nm not in allowed:
                where = "the enclosing shard_map mesh axes" if strict \
                    else "any mesh constructed in this project"
                yield self.issue(
                    src, call,
                    f"lax.{term} over axis {nm!r}, which is not among "
                    f"{where} {sorted(allowed)} — a mistyped axis name "
                    f"fails at trace time or reduces over the wrong "
                    f"device group")

    def _check_perm(self, src, call):
        perm = call.args[2] if len(call.args) > 2 else None
        for kw in call.keywords:
            if kw.arg == "perm":
                perm = kw.value
        if perm is None:
            return
        if _PermCheck.verdict(perm) == "non-total":
            yield self.issue(
                src, call,
                "ppermute perm is not a total permutation of the axis: "
                "it repeats or omits devices, so some device sends "
                "twice, receives twice, sends without receiving "
                "(zero-fill), or receives from nobody — if the drop is "
                "deliberate (fill/drain schedules), document it with a "
                "suppression")

    # ---------------------------------------------------- divergence check
    def _device_tainted(self, fn, uniform=frozenset()):
        """Names carrying per-device values, as ``{name: boundary}``:
        the name is per-device at uses BEFORE line ``boundary`` (inf =
        throughout).  Seeds: body params and axis_index results, spread
        through assignments with the suite's static-metadata exemption
        (``x.shape``-derived predicates are identical on every device).
        A value whose RHS *is* a uniform reduction (``psum``-family /
        ``all_gather`` — NOT ``ppermute``/``all_to_all``-style shuffles,
        whose results differ per device) is uniform across the axis and
        washes the taint out — but only the exact call
        (``lax.psum(x, a) + x`` still carries the raw shard), only at a
        straight-line rebind (a branch-nested rebind leaves the else
        path holding the raw shard), and only for uses AFTER the rebind
        line (a predicate above it read the raw shard); a later
        re-taint cancels the wash."""
        from ..dataflow import taint_of
        env = {p: {0} for p in fn.params if p not in uniform}
        env.pop("self", None)
        env.pop("cls", None)
        last_taint = {n: fn.node.lineno for n in env}
        washes = {}
        nested = set()

        def mark(node, under):
            for ch in ast.iter_child_nodes(node):
                if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                    continue
                if under and isinstance(ch, ast.Assign):
                    nested.add(id(ch))
                mark(ch, under or isinstance(
                    ch, (ast.If, ast.For, ast.AsyncFor, ast.While)))

        mark(fn.node, False)
        assigns = sorted(
            (n for n in self._local_nodes(fn)
             if isinstance(n, ast.Assign)),
            key=lambda n: (n.lineno, n.col_offset))
        for _ in range(2):      # one re-pass for forward references
            for node in assigns:
                value = node.value
                rhs_name = dotted_name(value.func) \
                    if isinstance(value, ast.Call) else ""
                # dotted receiver required: a bare project helper
                # merely NAMED psum must not wash the per-device taint
                rhs_is_collective = "." in rhs_name \
                    and rhs_name.rsplit(".", 1)[-1] in UNIFORM_COLLECTIVES
                hit = bool(taint_of(value, env)) or any(
                    isinstance(sub, ast.Call)
                    and dotted_name(sub.func).rsplit(".", 1)[-1]
                    == "axis_index"
                    for sub in ast.walk(value))
                for t in node.targets:
                    for leaf in self._written_names(t):
                        if rhs_is_collective:
                            if id(node) not in nested:
                                washes[leaf.id] = node.lineno
                                env.pop(leaf.id, None)
                        elif hit:
                            env[leaf.id] = {0}
                            last_taint[leaf.id] = max(
                                last_taint.get(leaf.id, 0), node.lineno)
        out = {}
        for n in last_taint:
            w = washes.get(n)
            out[n] = float("inf") if w is None or last_taint[n] > w \
                else w
        return out

    @classmethod
    def _written_names(cls, target):
        """Names an assignment target WRITES: the base of a subscript
        store (``synced[n] = m`` writes ``synced``) — never the index
        (``n`` is read, and tainting it made every ``if n in ...:``
        look per-device, a false positive surfaced when
        check_vma=False bodies joined the analysis)."""
        if isinstance(target, ast.Name):
            yield target
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                yield from cls._written_names(e)
        elif isinstance(target, ast.Starred):
            yield from cls._written_names(target.value)
        elif isinstance(target, (ast.Subscript, ast.Attribute)):
            yield from cls._written_names(target.value)

    def _check_ctrl(self, src, fn, call, term, tainted, graph, summaries):
        """lax.cond/while_loop/switch with a per-device predicate whose
        branches reach a collective."""
        if term == "while_loop":
            # while_loop(cond_fn, body_fn, init): the predicate is
            # cond_fn applied to the carry — the carry is per-device
            # exactly when the init operand is (positional or
            # init_val=), so taint-check init and treat both functions
            # as branches
            inits = list(call.args[2:]) + [
                kw.value for kw in call.keywords
                if kw.arg == "init_val"]
            if not any(self._expr_tainted(a, tainted, call.lineno)
                       for a in inits):
                return
            branches = list(call.args[0:2]) + [
                kw.value for kw in call.keywords
                if kw.arg in ("cond_fun", "body_fun")]
        else:
            pred = call.args[0] if call.args else None
            for kw in call.keywords:
                if kw.arg in ("pred", "index"):
                    pred = kw.value
            if pred is None or not self._expr_tainted(pred, tainted,
                                                      call.lineno):
                return
            # cond(pred, true_fun, false_fun, *ops): branches args[1:3]
            # or true_fun=/false_fun=; switch(index, branches, *ops):
            # only args[1] (or branches=) is the branch sequence —
            # args[2:] are data operands, not callables
            branches = list(call.args[1:3]) if term == "cond" \
                else list(call.args[1:2])
            branch_kws = ("true_fun", "false_fun") if term == "cond" \
                else ("branches",)
            branches += [kw.value for kw in call.keywords
                         if kw.arg in branch_kws]
        flat = []
        for br in branches:
            # lax.switch takes its branches as a sequence literal
            flat.extend(br.elts if isinstance(br, (ast.List, ast.Tuple))
                        else [br])
        for br in flat:
            witness = self._branch_collective(br, fn, graph, summaries)
            if witness:
                yield self.issue(
                    src, call,
                    f"lax.{term} branches on a per-device value and its "
                    f"branch reaches a collective ({witness}) — devices "
                    f"that disagree on the predicate skip the collective "
                    f"and the axis deadlocks; hoist the collective out "
                    f"of the branch or make the predicate uniform")
                return

    def _check_if_divergence(self, fn, graph, summaries, tainted):
        reported = set()        # anchor ids: nested tainted ifs share
        # innermost-first (an inner If starts strictly later), so each
        # If anchors at its own collective and an outer If with a
        # second deadlock site still reports it
        ifs = sorted((n for n in self._local_nodes(fn)
                      if isinstance(n, ast.If)),
                     key=lambda n: -n.lineno)
        for node in ifs:
            if not self._expr_tainted(node.test, tainted, node.lineno):
                continue
            # skip nested defs: merely DEFINING a function under the if
            # executes nothing — its body is covered by its own context
            subs = []
            for s in node.body + node.orelse:
                stack = [s]
                while stack:
                    n = stack.pop()
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                        continue
                    subs.append(n)
                    stack.extend(ast.iter_child_nodes(n))
            for sub in subs:
                if isinstance(sub, ast.Call):
                    witness = None
                    cname = dotted_name(sub.func)
                    term = cname.rsplit(".", 1)[-1]
                    # dotted receiver required (same convention as the
                    # summary walk): a bare project helper that happens
                    # to be NAMED psum is not a lax collective — its
                    # summary speaks for what it reaches
                    if term in COMM_COLLECTIVES and "." in cname:
                        witness = f"lax.{term} at line {sub.lineno}"
                    else:
                        witness = self._callee_collective(
                            sub, fn, graph, summaries)
                    if witness and id(sub) in reported:
                        # another If already owns this anchor — keep
                        # scanning for a distinct deadlock site
                        continue
                    if witness:
                        # anchor to the collective (or the call reaching
                        # it), not the whole If: a suppression of some
                        # OTHER finding inside the body must not swallow
                        # this one; if this anchor line is itself
                        # suppressed, keep scanning for another
                        reported.add(id(sub))
                        iss = self.issue(
                            fn.src, sub,
                            f"collective under an `if` (line "
                            f"{node.lineno}) that branches on a "
                            f"per-device value ({witness}) — devices "
                            f"taking different branches deadlock the "
                            f"axis; use a data-level select (jnp.where) "
                            f"or a uniform predicate")
                        if iss is not None:
                            yield iss
                            break

    def _branch_collective(self, branch, fn, graph, summaries):
        """Does a cond/while branch operand reach a collective?"""
        if isinstance(branch, ast.Lambda):
            for sub in ast.walk(branch.body):
                if isinstance(sub, ast.Call):
                    cname = dotted_name(sub.func)
                    term = cname.rsplit(".", 1)[-1]
                    if term in COMM_COLLECTIVES and "." in cname:
                        return f"lax.{term} in the lambda"
                    w = self._callee_collective(sub, fn, graph,
                                                summaries)
                    if w:
                        return w
            return None
        if isinstance(branch, ast.Name):
            callee = graph.resolve_ref(branch, fn)
            if callee is not None:
                summ = summaries.get(callee.qname)
                if summ is not None and summ.calls_collective:
                    return summ.calls_collective.describe()
        return None

    def _callee_collective(self, call, fn, graph, summaries):
        callee = graph.resolve_call(call, fn)
        if callee is None:
            return None
        summ = summaries.get(callee.qname)
        if summ is not None and summ.calls_collective:
            return summ.calls_collective.describe()
        return None

    @staticmethod
    def _expr_tainted(expr, tainted, line):
        """Is this expression per-device at a use on ``line``?  Names
        washed by an earlier straight-line uniform rebind stop counting
        at the rebind line."""
        from ..dataflow import taint_of
        env = {n: {0} for n, bound in tainted.items() if line < bound}
        if taint_of(expr, env):
            return True
        return any(isinstance(sub, ast.Call) and dotted_name(
                       sub.func).rsplit(".", 1)[-1] == "axis_index"
                   for sub in ast.walk(expr))

    # ------------------------------------------------------------- helpers
    @staticmethod
    def _local_nodes(fn):
        yield from CallGraph._local_nodes(fn.node)

    def _local_calls(self, fn):
        for node in self._local_nodes(fn):
            if isinstance(node, ast.Call):
                yield node
