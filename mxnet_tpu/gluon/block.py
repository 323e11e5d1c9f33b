"""Block / HybridBlock: the Gluon imperative NN API.

Reference: ``python/mxnet/gluon/block.py`` (Block, HybridBlock — whose
``hybridize()`` swaps the python forward for a CachedOp; SURVEY.md §2.2,
§3.3) and ``src/imperative/cached_op.cc`` (the CachedOp backend).

TPU-native redesign of CachedOp: instead of capturing an nnvm graph and
replaying node-by-node through the engine, ``hybridize()`` traces the
block's forward into ONE pure JAX function of (params..., inputs...) and
compiles it with ``jax.jit``, cached by input shape/dtype/train-mode
signature — trace once → XLA executable → replay (SURVEY.md §3.3: "the
single most important path to replicate").  Autograd sees the whole
compiled program as a single tape node, so backward is one XLA program too.
Mutable aux state (BatchNorm running stats) is captured at trace time and
returned as extra outputs (purity restored; XLA donates buffers).
"""
from __future__ import annotations

import contextvars
import functools
import re
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..context import Context, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nb_cached_programs"]


class _BlockScope(threading.local):
    """Name manager (reference: _BlockScope + NameManager)."""

    def __init__(self):
        self._current = None
        self._counters = {}

    def create(self, prefix, params, hint):
        current = self._current
        if current is None:
            if prefix is None:
                count = self._counters.get(hint, 0)
                self._counters[hint] = count + 1
                prefix = f"{hint}{count}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._block._scope_counters.get(hint, 0)
            current._block._scope_counters[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params


_SCOPE = _BlockScope()


class _NameScope:
    def __init__(self, block):
        self._block = block
        self._old = None

    def __enter__(self):
        self._old = _SCOPE._current
        _SCOPE._current = self
        return self

    def __exit__(self, *exc):
        _SCOPE._current = self._old
        return False


# Aux-state capture for hybrid tracing: while set, Parameter aux updates
# (BatchNorm running stats) are recorded instead of written (they are
# tracers); CachedOp returns them as extra outputs and writes real values.
_AUX_CAPTURE: contextvars.ContextVar = contextvars.ContextVar(
    "mx_aux_capture", default=None)

# True while a CachedOp trace is running: hybridized blocks encountered
# inside the trace run imperatively (they are being inlined into the outer
# compiled program instead of dispatching their own CachedOp).
_TRACING: contextvars.ContextVar = contextvars.ContextVar(
    "mx_hybrid_tracing", default=False)


def update_aux_state(param: Parameter, new_value, ctx=None):
    """Write an auxiliary (non-differentiable) state parameter, routing
    through the hybrid-trace capture when active."""
    cap = _AUX_CAPTURE.get()
    data = new_value._data if isinstance(new_value, NDArray) else new_value
    if cap is not None:
        cap[param] = data
        return
    from .. import autograd
    with autograd.pause():
        for c, arr in param._data.items():
            if ctx is None or c == ctx:
                arr._set_data(data.astype(arr._data.dtype))


class Block:
    """Base class for all neural network layers and models
    (reference: gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _SCOPE.create(prefix, params,
                                                   self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _NameScope(self)
        self._scope_counters = {}
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    # ----------------------------------------------------------- attributes
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return self._scope

    def __repr__(self):
        mods = "\n".join(f"  ({k}): {_indent(repr(v))}"
                         for k, v in self._children.items())
        return f"{self.__class__.__name__}(\n{mods}\n)"

    # ------------------------------------------------------------ parameters
    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pat = re.compile(select)
            ret.update({n: p for n, p in self.params.items()
                        if pat.match(n)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as init_mod
        if init is None:
            init = init_mod.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    # ------------------------------------------------------------- save/load
    def save_parameters(self, filename, deduplicate=False):
        """Reference: Block.save_parameters — name-keyed params file."""
        params = self._collect_params_with_prefix()
        arrays = {name: p._reduce() for name, p in params.items()}
        nd.save(filename, arrays)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(
                        f"Parameter {name!r} missing in {filename!r}")
        for name, value in loaded.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise MXNetError(
                    f"Parameter {name!r} in file not found in Block "
                    f"(use ignore_extra=True)")
            p = params[name]
            if p.shape is None or not all(
                    s and s > 0 for s in (p.shape or (0,))):
                p.shape = tuple(value.shape)
            if not p._data:
                p.initialize(ctx=ctx or [current_context()])
            p.set_data(value)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + n: p for n, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # --------------------------------------------------------------- forward
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if _TRACING.get():
            # inside a compiled program's trace the block's name is a
            # scope: its ops read .../<block>/... in the HLO's op_name
            # and in the profiler's trace of the device
            with jax.named_scope(self._name):
                out = self.forward(*args, **kwargs)
        else:
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a per-layer summary table (reference: Block.summary)."""
        rows = []

        def _hook(block, inp, out):
            o = out[0] if isinstance(out, (list, tuple)) else out
            n_params = sum(
                int(_prod(p.shape)) for p in block._reg_params.values()
                if p.shape)
            rows.append((block.name, type(block).__name__,
                         tuple(getattr(o, "shape", ())), n_params))

        handles = []
        for blk in self._iter_blocks():
            blk._forward_hooks.append(_hook)
            handles.append(blk)
        try:
            self(*inputs)
        finally:
            for blk in handles:
                blk._forward_hooks.remove(_hook)
        lines = [f"{'Layer':<30}{'Type':<20}{'Output':<24}{'Params':<12}"]
        total = 0
        for name, typ, shape, npar in rows:
            total += npar
            lines.append(f"{name:<30}{typ:<20}{str(shape):<24}{npar:<12}")
        lines.append(f"Total params: {total}")
        print("\n".join(lines))

    def _iter_blocks(self):
        yield self
        for c in self._children.values():
            yield from c._iter_blocks()


def _indent(s, n=2):
    return s.replace("\n", "\n" + " " * n)


def _prod(t):
    out = 1
    for x in t:
        out *= x
    return out


# ---------------------------------------------------------------------------
# CachedOp: the hybridize() backend (reference: src/imperative/cached_op.cc)
# ---------------------------------------------------------------------------

_N_CACHED_PROGRAMS = 0


def nb_cached_programs():
    """Number of XLA programs compiled by CachedOps (introspection aid)."""
    return _N_CACHED_PROGRAMS


class CachedOp:
    """Trace-compile cache over a HybridBlock's forward.

    Keyed by (input shapes/dtypes, train-mode) — the reference keys its
    per-shape-signature graph passes the same way (cached_op.cc).
    ``static_alloc`` maps to XLA buffer donation (memory reuse); XLA's
    buffer assignment replaces PlanMemory wholesale.
    """

    def __init__(self, block, static_alloc=False, static_shape=False,
                 cache_size=None, bucket_shapes=None):
        from ..base import get_env
        self._block = block
        self._static_alloc = static_alloc
        self._cache = OrderedDict()        # LRU over shape signatures
        if cache_size is None:
            cache_size = int(get_env("MXNET_CACHED_OP_CACHE_SIZE", "16"))
        self._cache_size = max(1, int(cache_size))
        self._n_evictions = 0
        if bucket_shapes is not None:
            bucket_shapes = {int(ax): sorted(int(s) for s in sizes)
                             for ax, sizes in dict(bucket_shapes).items()}
        self._bucket_shapes = bucket_shapes

    def _bucketize(self, inputs):
        """Pad each input's bucketed axes up to the next declared bucket
        size (zeros), collapsing ragged shapes onto a fixed program set.

        Contract (documented at ``hybridize(bucket_shapes=...)``): the
        model must be padding-safe on those axes — mask via
        valid_length/attention masks; outputs keep the padded size.
        """
        from ..ops.registry import LightOpDef, invoke
        out = []
        for x in inputs:
            pads = [(0, 0)] * x.ndim
            changed = False
            for ax, sizes in self._bucket_shapes.items():
                if ax >= x.ndim:
                    continue
                cur = x.shape[ax]
                fit = [s for s in sizes if s >= cur]
                if not fit:
                    raise MXNetError(
                        f"CachedOp bucket_shapes: input axis {ax} has "
                        f"size {cur}, larger than the largest declared "
                        f"bucket {sizes[-1]}")
                if fit[0] != cur:
                    pads[ax] = (0, fit[0] - cur)
                    changed = True
            if changed:
                # pad through the op dispatcher so a TapeNode attaches:
                # input gradients must flow through bucketing (the vjp of
                # pad is slice — padding rows receive no cotangent)
                opdef = LightOpDef(
                    "bucket_pad",
                    functools.partial(jnp.pad, pad_width=tuple(pads)),
                    1, 1)
                x = invoke(opdef, [x], {})
            out.append(x)
        return out

    def __call__(self, inputs, param_list, ctx):
        from .. import autograd
        from ..ops.registry import LightOpDef, invoke

        # probe params before anything else (deferred init must surface
        # before signatures or RNG are touched)
        for _n, p in param_list:
            p.data(ctx)
        if self._bucket_shapes:
            inputs = self._bucketize(inputs)
        sig = (tuple((tuple(x.shape), str(x._data.dtype)) for x in inputs),
               tuple((tuple(p.shape), str(p.dtype)) for _n, p in param_list),
               autograd.is_training())
        entry = self._cache.get(sig)
        if entry is None:
            entry = self._build(inputs, param_list, sig, ctx)
        else:
            self._cache.move_to_end(sig)
        jitted, meta = entry

        from .. import random as mxrand
        # fetch params FIRST: DeferredInitializationError must propagate
        # before any RNG is consumed (keeps the eager/hybrid param-init
        # streams identical)
        param_arrays = [p.data(ctx) for _n, p in param_list]
        # fresh PRNG key each call: random ops inside the trace draw from
        # fold_in(key, counter) so dropout masks differ across steps
        key = NDArray(mxrand.next_key())
        all_in = [key] + list(inputs) + param_arrays
        n_out = meta["n_flat_out"] + len(meta["aux_params"])
        recording = autograd.is_recording()
        if recording:
            outs = self._call_recorded(meta, all_in, n_out, ctx)
        else:
            fn = jitted if n_out > 1 else meta["unwrap1"]
            opdef = LightOpDef(f"cached_op_{self._block.name}", fn,
                               len(all_in), n_out)
            outs = invoke(opdef, all_in, {})
            if n_out == 1:
                outs = [outs]
        flat_outputs = outs[:meta["n_flat_out"]]
        aux_values = outs[meta["n_flat_out"]:]
        for p, v in zip(meta["aux_params"], aux_values):
            if v._lazy_cb is None:      # deferred forward writes aux at
                update_aux_state(p, v, ctx=None)   # materialization/step
        return _unflatten(flat_outputs, meta["tree"])

    def _call_recorded(self, meta, all_in, n_out, ctx):
        """Training-mode dispatch: one forward program that also emits the
        vjp residuals, so backward is one cached program with NO forward
        recompute (reference: CachedOp caches fwd and bwd graphs and keeps
        the saved-tensor buffers between them).

        Deferred-forward mode (after the first recorded call per
        signature): the forward is NOT dispatched here — outputs are
        lazy NDArrays and ``Trainer.step`` compiles
        forward+backward+optimizer into ONE donated program (the
        residuals never round-trip HBM between programs).  Any read of
        an output before step materializes the standalone forward and
        everything degrades to exactly the eager-forward behavior."""
        from .. import autograd
        from ..base import get_env
        from ..engine import engine, is_naive
        for a in all_in:
            if a._lazy_cb is not None:
                a._lazy_materialize()
            a._var.check()
        out_ctx = all_in[1].context if len(all_in) > 1 else None
        consumed = [False]
        res_holder = [None]
        fwd_pending = [False]

        defer = (meta.get("out_avals") is not None
                 and not is_naive()
                 and get_env("MXNET_FUSED_HYBRID_STEP", "1") != "0"
                 and get_env("MXNET_DEFERRED_HYBRID_FWD", "1") != "0")
        if defer:
            fwd_pending[0] = True
            raw_in = [a._data for a in all_in]
            outs = [NDArray._deferred(av, None, ctx=out_ctx)
                    for av in meta["out_avals"]]

            def materialize_fwd(_meta=meta, _raw_in=raw_in):
                """Idempotent standalone-forward fallback (any read
                before step, or a step that can't fuse)."""
                if not fwd_pending[0]:
                    return
                fwd_pending[0] = False
                raw = _meta["fwd_rec"](*_raw_in)
                res_holder[0] = raw[n_out:]
                for o, v in zip(outs, raw[:n_out]):
                    o._lazy_cb = None
                    o._set_data(v)
                for p, v in zip(_meta["aux_params"],
                                raw[_meta["n_flat_out"]:n_out]):
                    update_aux_state(p, NDArray(v), ctx=None)

            for o in outs:
                o._lazy_cb = materialize_fwd
        else:
            raw_in = None
            raw = meta["fwd_rec"](*[a._data for a in all_in])
            vis = raw[:n_out]
            res_holder[0] = raw[n_out:]
            if meta.get("out_avals") is None:
                # unlock deferral from the next recorded call on: the
                # first call runs eagerly so build errors surface here
                meta["out_avals"] = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                                     for v in vis]
            outs = [NDArray(o, ctx=out_ctx) for o in vis]

            def materialize_fwd():
                return None

        def custom_backward(out_grads, in_primals, _meta=meta):
            materialize_fwd()             # deferred fwd: run it standalone
            if consumed[0]:
                raise MXNetError(
                    "backward through this hybridized graph a second "
                    "time: the saved buffers were freed after the first "
                    "pass — call every earlier backward with "
                    "retain_graph=True")
            _res = res_holder[0]
            if autograd.in_retain_backward():
                grads = _meta["bwd_res_retain"](_res, tuple(out_grads))
            else:
                consumed[0] = True        # donating replay frees residuals
                import warnings
                with warnings.catch_warnings():
                    # residuals are donated to be FREED early (they never
                    # alias the grad outputs); the "not usable" warning
                    # is the expected cost of that, not a donation miss
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    grads = _meta["bwd_res"](_res, tuple(out_grads))
            return (None,) + tuple(grads)

        node = autograd.record_custom_node(
            all_in, outs, custom_backward,
            name=f"cached_op_{self._block.name}")
        # fusion hook: Trainer.step may compile this backward (and, when
        # the forward is still pending, the forward too) together with
        # the optimizer update into one donated program (see
        # autograd.backward deferral / Trainer._try_fused_hybrid_step)
        node.fused_info = {"bwd_impl": meta["bwd_impl"],
                           "res_holder": res_holder,
                           "consumed": consumed,
                           "fwd_pending": fwd_pending,
                           "materialize_fwd": materialize_fwd,
                           "fwd_bwd_impl": meta.get("fwd_bwd_impl"),
                           "fwd_bwd_factory": meta.get("fwd_bwd_factory"),
                           "raw_in": raw_in,
                           "outs": outs,
                           "aux_params": meta["aux_params"],
                           "n_flat_out": meta["n_flat_out"]}
        eng = engine()
        if is_naive():
            for o in outs:
                o.wait_to_read()
        for o in outs:
            eng.track(o)
        return outs

    def _build(self, inputs, param_list, sig, ctx):
        global _N_CACHED_PROGRAMS
        from .. import autograd
        from .parameter import _PARAM_OVERRIDE
        block = self._block
        n_in = len(inputs)
        params = [p for _n, p in param_list]
        training = autograd.is_training()
        meta = {"aux_params": [], "n_flat_out": None, "tree": None}

        from .. import random as mxrand

        def pure(key, *arrays):
            xs = [NDArray(a) for a in arrays[:n_in]]
            override = {p: NDArray(a)
                        for p, a in zip(params, arrays[n_in:])}
            tok_t = _TRACING.set(True)
            tok_p = _PARAM_OVERRIDE.set(override)
            tok_a = _AUX_CAPTURE.set(OrderedDict())
            try:
                with mxrand.trace_key_scope(key):
                    with autograd.pause(train_mode=training):
                        out = block.forward(*xs)
                cap = _AUX_CAPTURE.get()
            finally:
                _AUX_CAPTURE.reset(tok_a)
                _PARAM_OVERRIDE.reset(tok_p)
                _TRACING.reset(tok_t)
            flat, tree = _flatten(out)
            meta["aux_params"] = list(cap.keys())
            meta["n_flat_out"] = len(flat)
            meta["tree"] = tree
            return tuple(x._data for x in flat) + tuple(cap.values())

        # Trace eagerly once via eval_shape so meta is filled determinately
        # before the jitted callable is used (jit traces lazily).  The key
        # here is a constant dummy (eval_shape executes nothing): the
        # global RNG stream must not advance during meta-tracing.
        jax.eval_shape(pure, jax.random.PRNGKey(0),
                       *[x._data for x in inputs],
                       *[p.data(ctx)._data for p in params])
        jitted = jax.jit(pure)
        meta["unwrap1"] = lambda *arrays: jitted(*arrays)[0]

        # Training path: forward and backward as one cached program pair
        # sharing saved residuals (reference: CachedOp caches the fwd and
        # bwd graphs; saved tensors live between them).  The vjp closure is
        # flattened into plain arrays to cross the jit boundary; its static
        # treedef is captured as a trace-time side effect.  Replaying
        # backward through this program costs zero recompute and exactly
        # one dispatch.
        # What the training forward saves for backward is a memory/compute
        # dial (reference: MXNET_BACKWARD_DO_MIRROR memory mirroring):
        #   all            — save every intermediate (vjp default; hostile
        #                    to HBM at BERT-large scale: fp32 attention
        #                    probs alone are GBs)
        #   dots (default) — save matmul/conv outputs, recompute elementwise
        #                    (XLA refuses nothing the MXU already paid for)
        #   dots_no_batch  — save only weight-side matmuls; activation
        #                    matmuls (attention) recompute
        #   none           — full rematerialization, minimal memory
        from ..base import get_env
        policy_name = get_env("MXNET_CACHED_OP_SAVE_POLICY")
        policies = {
            "all": None,
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "none": jax.checkpoint_policies.nothing_saveable,
        }
        policy = policies.get(str(policy_name), policies["dots_no_batch"])

        @jax.jit
        def fwd_rec(key, *arrays):
            fn = lambda *arr: pure(key, *arr)      # noqa: E731
            if policy is not None:
                fn = jax.checkpoint(fn, policy=policy)
            outs, vjp_fn = jax.vjp(fn, *arrays)
            flat, tree = jax.tree_util.tree_flatten(vjp_fn)
            meta["res_tree"] = tree
            return tuple(outs) + tuple(flat)

        def bwd_impl(res, cots):
            vjp_fn = jax.tree_util.tree_unflatten(meta["res_tree"],
                                                  list(res))
            # key is closed over in fwd_rec's lambda: grads cover
            # inputs+params only; _call_recorded prepends None for the key
            return vjp_fn(tuple(cots))

        def _make_fwd_bwd_impl(p):
            def fwd_bwd_impl(key, arrays, cots):
                """Whole fwd+bwd as one traceable body (un-jitted): the
                deferred-forward step fusion embeds this next to the
                optimizer update so residuals stay program-internal."""
                fn = lambda *arr: pure(key, *arr)      # noqa: E731
                if p is not None:
                    fn = jax.checkpoint(fn, policy=p)
                outs, vjp_fn = jax.vjp(fn, *arrays)
                grads = vjp_fn(tuple(cots))
                return outs, grads
            return fwd_bwd_impl

        # the ONE-program step can afford a more generous save policy
        # than the two-program path (residuals are program-internal,
        # freed as consumed, not materialized program outputs) — the
        # factory lets Trainer pick per MXNET_FUSED_STEP_SAVE_POLICY,
        # including the memory-probed 'auto' mode
        fwd_bwd_impl = _make_fwd_bwd_impl(policy)

        meta["fwd_rec"] = fwd_rec
        meta["fwd_bwd_impl"] = fwd_bwd_impl
        meta["fwd_bwd_factory"] = \
            lambda name: _make_fwd_bwd_impl(policies.get(str(name), policy))
        meta["bwd_impl"] = bwd_impl        # un-jitted: Trainer step fusion
        # residuals are dead after one replay: donating them lets XLA free
        # each saved tensor as soon as its consuming bwd op runs (the
        # reference frees saved tensors the same way).  retain_graph=True
        # backward uses the non-donating twin so a second replay works.
        meta["bwd_res"] = jax.jit(bwd_impl, donate_argnums=(0,))
        meta["bwd_res_retain"] = jax.jit(bwd_impl)
        _N_CACHED_PROGRAMS += 1
        entry = (jitted, dict(meta))
        self._cache[sig] = entry
        while len(self._cache) > self._cache_size:
            self._cache.popitem(last=False)       # evict LRU program
            self._n_evictions += 1
            if self._n_evictions in (1, 10, 100, 1000):
                import warnings
                warnings.warn(
                    f"CachedOp for {self._block.name!r}: "
                    f"{self._n_evictions} compiled-program eviction(s) — "
                    f"ragged input shapes are forcing recompiles.  "
                    f"Declare hybridize(bucket_shapes={{axis: [sizes]}}) "
                    f"to pad onto a fixed bucket set, or raise "
                    f"MXNET_CACHED_OP_CACHE_SIZE "
                    f"(now {self._cache_size}).", stacklevel=3)
        return entry


def _flatten(out):
    if isinstance(out, NDArray):
        return [out], None
    if isinstance(out, (list, tuple)):
        flat, tree = [], []
        for o in out:
            f, t = _flatten(o)
            flat.extend(f)
            tree.append((len(f), t))
        return flat, tree
    raise MXNetError(f"hybrid_forward returned unsupported type {type(out)}")


def _unflatten(flat, tree):
    if tree is None:
        return flat[0]
    out, i = [], 0
    for n, sub in tree:
        chunk = flat[i:i + n]
        out.append(_unflatten(chunk, sub))
        i += n
    return tuple(out)


class HybridBlock(Block):
    """A Block that can be traced and compiled (reference: HybridBlock).

    Subclasses implement ``hybrid_forward(self, F, x, *args, **params)``
    where registered parameters arrive as keyword NDArrays.  Before
    ``hybridize()`` it runs imperatively (op-by-op, full python
    debuggability); after, the whole forward is one compiled XLA program.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  cache_size=None, bucket_shapes=None, **kwargs):
        """Swap the python forward for a compiled CachedOp.

        ``cache_size``: bound on compiled programs kept per CachedOp
        (default env ``MXNET_CACHED_OP_CACHE_SIZE``, 16); LRU-evicted
        beyond that, with a churn warning.  ``bucket_shapes``: optional
        ``{axis: [sizes]}`` — inputs are zero-padded up along those axes
        to the next declared size so ragged shapes share programs
        (BucketingModule's policy for the Gluon layer).  The model must
        be padding-safe on bucketed axes (mask via valid_length etc.);
        outputs keep the padded size.
        """
        self._active = active
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape,
                       "cache_size": cache_size,
                       "bucket_shapes": bucket_shapes}
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def infer_shape(self, *args):
        """Override in layers that support deferred parameter init."""
        raise DeferredInitializationError(
            f"{type(self).__name__} cannot infer parameter shapes; "
            f"provide explicit in_units/in_channels or run a forward pass")

    def _get_ctx(self, args):
        for a in args:
            if isinstance(a, NDArray):
                return a.context
        return current_context()

    def _param_items(self):
        # ALL descendant params are inputs of the compiled program (child
        # blocks resolve theirs through the trace-time override).
        return list(self.collect_params().items())

    def forward(self, x, *args, **kwargs):
        if not isinstance(x, NDArray):
            # symbolic composition path: build a Symbol graph
            from ..symbol import Symbol
            if isinstance(x, Symbol):
                from .. import symbol as sym_mod
                pvars = {n: p.var() for n, p in self._reg_params.items()}
                return self.hybrid_forward(sym_mod, x, *args, **pvars,
                                           **kwargs)
            raise MXNetError(
                f"forward expects NDArray or Symbol, got {type(x)}")
        ctx = self._get_ctx((x,) + args)
        try:
            pdata = {n: p.data(ctx) for n, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._finish_deferred(x, *args)
            pdata = {n: p.data(ctx) for n, p in self._reg_params.items()}

        if self._active and not _TRACING.get() and not kwargs \
                and all(isinstance(a, NDArray) for a in args):
            if self._cached_op is None:
                self._cached_op = CachedOp(self, **self._flags)
            try:
                return self._cached_op([x] + list(args),
                                       self._param_items(), ctx)
            except DeferredInitializationError:
                # child params deferred: run ONE imperative pass to infer
                # shapes; suppress child CachedOps during it (they would
                # compile throwaway programs)
                tok = _TRACING.set(True)
                try:
                    return self.hybrid_forward(nd, x, *args, **pdata,
                                               **kwargs)
                finally:
                    _TRACING.reset(tok)
        return self.hybrid_forward(nd, x, *args, **pdata, **kwargs)

    def _finish_deferred(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init is not None:
                p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def optimize_for(self, x, *args, backend=None, **backend_opts):
        """Trace this block to a Symbol graph, run the registered
        subgraph-backend pass over it, and return a ``SymbolBlock``
        sharing this block's parameters (reference:
        HybridBlock.optimize_for).

        Upstream rewrites the cached graph in place; here the compiled
        path is an XLA trace (which already fuses), so the pass runs on
        the exported Symbol DAG and the optimized graph comes back as a
        new block — same parameters, rewritten topology.
        """
        from .. import symbol as sym_mod
        if backend is None:
            raise MXNetError("optimize_for requires backend=<name>")
        n_in = 1 + len(args)
        data_syms = [sym_mod.var("data")] if n_in == 1 else \
            [sym_mod.var(f"data{i}") for i in range(n_in)]
        out = self(*data_syms)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        opt = out.optimize_for(backend, **backend_opts)
        blk = SymbolBlock(opt, data_syms, params=self.collect_params())
        # example data validates the rewritten graph end-to-end
        blk(x, *args)
        return blk

    # ------------------------------------------------------------ export
    def export(self, path, epoch=0):
        """Serialize to symbol-json + params (reference: HybridBlock.export).

        Builds the symbolic graph by running hybrid_forward with Symbol
        inputs (reference: _build_cache's symbol trace)."""
        from .. import symbol as sym_mod
        data = sym_mod.var("data")
        out = self(data)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        sym_file = f"{path}-symbol.json"
        out.save(sym_file)
        params = {}
        for name, p in self.collect_params().items():
            params[name] = p._reduce()
        nd.save(f"{path}-{epoch:04d}.params", params)
        return sym_file

    def export_stablehlo(self, *example_inputs, path, emit_text=False,
                         dynamic_batch=False, version=None,
                         precompile=(), quantize=None):
        """Export this block's inference forward as a self-contained
        StableHLO artifact (``deploy.export_stablehlo``): weights baked
        in, ``path.json`` serving-signature manifest alongside.  Pass
        ``dynamic_batch=True`` to leave the batch dimension symbolic so
        ``mxnet_tpu.serving`` can shape-bucket request batches over one
        artifact; ``version`` tags the manifest for repository
        hot-swap; ``precompile`` (bucket list, or True for the serving
        defaults) ships AOT-compiled executables next to the manifest
        so a matching-topology server starts with zero XLA compiles;
        ``quantize='int8'|'fp8'`` ships the quantized serving shape
        (weights packed to 1 byte with per-tensor scales in the
        manifest v4 ``quantization`` block, example inputs doubling as
        the calibration batch — docs/serving.md §7)."""
        from .. import deploy
        return deploy.export_stablehlo(
            self, *example_inputs, path=path, emit_text=emit_text,
            dynamic_batch=dynamic_batch, version=version,
            precompile=precompile, quantize=quantize)


class SymbolBlock(HybridBlock):
    """Wrap a Symbol graph as a Block (reference: gluon.SymbolBlock)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from .. import symbol as sym_mod
        from ..symbol import Symbol
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._out_sym = outputs
        self._in_names = [s.name for s in inputs]
        in_set = set(self._in_names)
        for arg in outputs.list_arguments():
            if arg in in_set:
                continue
            # graph argument names are raw Parameter names; adopt a
            # matching shared parameter directly rather than minting a
            # fresh (prefixless) one through get()'s prefixed lookup
            if params is not None and arg in params:
                self._params._params[arg] = params[arg]
            else:
                self._params.get(arg, shape=None, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        out = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        blk = SymbolBlock(out, inputs)
        if param_file is not None:
            loaded = nd.load(param_file)
            for name, value in loaded.items():
                if name in blk._params:
                    p = blk._params[name]
                    p.shape = tuple(value.shape)
                    p.initialize(ctx=ctx or [current_context()])
                    p.set_data(value)
        return blk

    def forward(self, *args):
        ctx = self._get_ctx(args)
        bindings = dict(zip(self._in_names, args))
        for name, p in self._params.items():
            if name not in bindings:
                bindings[name] = p.data(ctx)
        outs = self._out_sym.eval(**bindings)
        return outs[0] if len(outs) == 1 else list(outs)
