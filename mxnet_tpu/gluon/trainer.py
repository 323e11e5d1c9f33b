"""Trainer: optimizer + kvstore orchestration
(reference: python/mxnet/gluon/trainer.py; SURVEY.md §3.4).

Gradient flow per step: backward fills per-ctx grads → `_allreduce_grads`
sums them across devices through the kvstore (on TPU: one fused XLA
collective for the 'xla' tier) → the optimizer updates each ctx copy.
With a single device the reduce is a no-op and no kvstore is created.

One Optimizer instance is shared by every per-device updater; per-device
update counts are kept separate via ``Optimizer._set_current_context`` so
hyperparameter changes (set_learning_rate, rescale_grad) reach all device
copies while Adam-style step counters do not double-advance.
"""
from __future__ import annotations

import time

from ..base import MXNetError
from .. import optimizer as opt
from .. import runtime_metrics as _rm
from .. import tracing as _tr
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict


# optimizer-state pytree helpers, shared with contrib.fused.FusedTrainStep
def _state_raw(s):
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_state_raw(x) for x in s)
    return s._data


def _state_sig(s):
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_state_sig(x) for x in s)
    return (tuple(s.shape), str(s.dtype))


def _state_write_back(dst, new):
    if dst is None:
        return
    if isinstance(dst, (tuple, list)):
        for d, n in zip(dst, new):
            _state_write_back(d, n)
        return
    dst._set_data(new)


def _fused_hyper_refresh(entry, o, params_ordered):
    """Per-step ts/lr/wd/rescale upload with staleness guards — shared
    by the one-program and two-program fused step paths (any divergence
    here silently desynchronizes optimizer schedules between them)."""
    import jax.numpy as jnp
    counts = [o._index_update_count[i] for i, _p in params_ordered]
    if entry.get("ts") is None or entry.get("counts") != counts:
        entry["ts"] = jnp.asarray([float(c) for c in counts], jnp.float32)
    entry["counts"] = [c + 1 for c in counts]
    lrs_py = tuple(float(o._get_lr(i)) for i, _p in params_ordered)
    wds_py = tuple(float(o._get_wd(i)) for i, _p in params_ordered)
    rs_py = float(o.rescale_grad)
    if entry.get("hyper") != (lrs_py, wds_py, rs_py):
        entry["lrs"] = jnp.asarray(lrs_py, jnp.float32)
        entry["wds"] = jnp.asarray(wds_py, jnp.float32)
        entry["rescale"] = jnp.float32(rs_py)
        entry["hyper"] = (lrs_py, wds_py, rs_py)
    return counts


def _fused_rollback(o, params_ordered, prev_num_update, entry, counts):
    """A failed fused step never applied: rewind per-index counts AND
    num_update (advanced via max() in _update_count) so lr schedules
    don't run one step ahead."""
    for i, _p in params_ordered:
        o._index_update_count[i] -= 1
    o.num_update = prev_num_update
    entry["counts"] = counts
    entry["ts"] = None


def _device_capacity_bytes(dev):
    """Usable device memory as the runtime reports it
    (``memory_stats()["bytes_limit"]``).  None = unknown (a CPU device
    reports nothing): callers must then choose the memory-safe path."""
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        return float(stats["bytes_limit"])
    return None


__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a dict or list of Parameters")
        self._params = []
        for p in params:
            if not isinstance(p, Parameter):
                raise MXNetError(f"invalid parameter {p!r}")
            self._params.append(p)
        self._compression_params = compression_params
        self._scale = 1.0
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore = None
        self._kv_initialized = False
        self._kvstore_arg = kvstore
        self._update_on_kvstore = update_on_kvstore

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise MXNetError(
                    "optimizer_params must be empty when optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        # one Updater (state set) per device copy, all driving the SAME
        # optimizer instance (reference: Trainer._init_optimizer)
        self._updater = opt.get_updater(self._optimizer)
        self._dev_updaters = {0: self._updater}

    def _num_ctx(self):
        for p in self._params:
            if p.grad_req != "null":
                return len(p.list_ctx())
        return 1

    def _init_kvstore(self):
        arg = self._kvstore_arg
        multi_ctx = self._num_ctx() > 1
        if arg is None or not multi_ctx:
            # single-device (or explicitly disabled): grads are already the
            # full-batch grads, no cross-device reduce exists
            self._kvstore = None
            if self._update_on_kvstore:
                raise MXNetError(
                    "update_on_kvstore=True requires a kvstore")
            self._update_on_kvstore = False
        else:
            from .. import kvstore as kvs
            store = kvs.create(arg) if isinstance(arg, str) else arg
            if self._compression_params is not None:
                store.set_gradient_compression(self._compression_params)
            update_on_kvstore = self._update_on_kvstore
            if update_on_kvstore is None:
                update_on_kvstore = False
            if update_on_kvstore and not store.is_capable(
                    kvs.KVStoreBase.OPTIMIZER):
                raise MXNetError(
                    f"kvstore type {store.type!r} cannot run the optimizer "
                    f"(update_on_kvstore)")
            self._update_on_kvstore = update_on_kvstore
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    store.init(str(i), p.data())
            if update_on_kvstore:
                store.set_optimizer(self._optimizer)
            self._kvstore = store
        self._kv_initialized = True

    # ---------------------------------------------------------------- props
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # ---------------------------------------------------------------- steps
    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce grads → rescale 1/batch_size → optimizer update
        (reference: Trainer.step).

        When the preceding ``loss.backward()`` deferred a single-CachedOp
        tape (see autograd.backward), the whole backward+update runs as
        ONE donated XLA program here — the three-call recipe at fused-step
        cost."""
        if not _rm._ENABLED:
            self._step_impl(batch_size, ignore_stale_grad)
        else:
            t0 = time.perf_counter()
            try:
                self._step_impl(batch_size, ignore_stale_grad)
            finally:
                # exemplar: a slow step resolves to its trace when the
                # loop runs inside a traced span (serving parity —
                # exemplar_for_quantile(0.99) returns the trace id)
                ctx = _tr.current_context()
                _rm.TRAINER_STEP_SECONDS.observe(
                    time.perf_counter() - t0,
                    exemplar=ctx.trace_id if ctx is not None else None)
            if _rm.grad_norm_enabled():
                self._publish_grad_norm()
        from .. import profiler as _prof
        if _prof._ACTIVE and _prof._state["profile_memory"]:
            _prof.sample_memory()   # per-step live-bytes counter event

    def _step_impl(self, batch_size, ignore_stale_grad):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._kvstore is None and self._try_fused_hybrid_step():
            return
        from .. import autograd
        autograd.flush_pending()
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def _publish_grad_norm(self):
        _rm.publish_grad_norm(p.list_grad()[0] for p in self._params
                              if p.grad_req != "null")

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "allreduce_grads() is meaningless with "
                "update_on_kvstore=True")
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        keys, grads = [], []
        for i, p in enumerate(self._params):
            if p.grad_req != "null":
                keys.append(str(i))
                grads.append(p.list_grad())
        if not keys:
            return
        if self._update_on_kvstore:
            # optimizer runs on the store's master copy: push grads, the
            # updated weights come back in _update via pull
            self._kvstore.push(keys, grads)
        else:
            # one batched call so the 'xla' tier can bucket-fuse collectives
            self._kvstore.pushpull(keys, grads, out=grads)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "update() cannot be called when update_on_kvstore=True; "
                "use step()")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._update_on_kvstore:
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    self._kvstore.pull(str(i), out=p.list_data())
            return
        if self._fused_update():
            return
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            sparse_grad = getattr(p, "_grad_stype", "default") == \
                "row_sparse"
            for j, (w, g) in enumerate(zip(p.list_data(), p.list_grad())):
                if j not in self._dev_updaters:
                    self._dev_updaters[j] = opt.get_updater(self._optimizer)
                self._optimizer._set_current_context(j)
                if sparse_grad:
                    # compress to stored-rows form: the optimizer then
                    # touches only rows this batch actually used
                    g = g.tostype("row_sparse")
                self._dev_updaters[j](i, g, w)
        self._optimizer._set_current_context(0)

    # ------------------------------------------- fused backward+update step
    def _try_fused_hybrid_step(self):
        """Fuse a deferred CachedOp backward with the optimizer update
        into one donated XLA program (VERDICT r2 item 3: the user-facing
        three-call recipe should cost what ShardedTrainer costs).

        Semantics preserved vs the eager path: ``.grad`` buffers are
        still written (as program outputs), update counts advance the
        same way, and any non-parameter leaf (e.g. an attach_grad input)
        gets its grad too.  Falls back to flush+eager on any mismatch.
        """
        from .. import autograd
        pending = autograd.peek_pending()
        if pending is None or not self._fused_eligible():
            return False
        import jax
        import jax.numpy as jnp

        node = pending["node"]
        info = node.fused_info
        items = [(i, p) for i, p in enumerate(self._params)
                 if p.grad_req != "null"]
        if not items:
            return False
        param_by_arr = {}
        for i, p in items:
            try:
                param_by_arr[id(p.data())] = (i, p)
            except Exception:           # noqa: BLE001 — uninitialized etc.
                return False
        # entries: [rng_key] + inputs + params; bwd_impl grads align with
        # entries[1:].  All must be leaves (pure three-call shape).
        entries = node.input_entries
        param_slots, other_slots = {}, []
        for ei, (prod, _oidx, arr) in enumerate(entries):
            if ei == 0:
                continue                # the PRNG key input
            if prod is not None:
                return False
            hit = param_by_arr.get(id(arr))
            if hit is not None:
                param_slots[ei] = hit
            elif arr._grad is not None and arr._grad_req != "null":
                other_slots.append(ei)
        if len(param_slots) != len(items):
            return False                # stale/uncovered params: eager path

        o = self._optimizer
        upd = self._updater
        for i, p in items:
            if i not in upd.states:
                upd.states[i] = o.create_state_multi_precision(i, p.data())

        order = sorted(param_slots)                 # entry index order
        params_ordered = [param_slots[ei] for ei in order]
        weights = [p.data()._data for _i, p in params_ordered]
        states = [_state_raw(upd.states[i]) for i, _p in params_ordered]
        from ..autograd import _node_out_avals
        avals = _node_out_avals(node)
        cots = [g if g is not None else jnp.zeros(a.shape, a.dtype)
                for g, a in zip(node.out_grads, avals)]

        # deferred forward still pending: try the ONE-program path
        # (forward+backward+optimizer; residuals never leave the program)
        if (info.get("fwd_pending") or [False])[0] \
                and info.get("fwd_bwd_impl") is not None:
            handled = self._try_full_fused_step(
                node, info, params_ordered, order, other_slots,
                weights, states, cots)
            if handled:
                return True
            # clean bail: run the standalone forward, then fall through
            # to the two-program backward+optimizer fusion below

        info["materialize_fwd"]()
        res = info["res_holder"][0]

        # cheap cache key: jax.jit re-traces on any aval change, so the
        # per-param shape/dtype signature would only duplicate that at
        # ~10ms host time per step
        key = (id(info["bwd_impl"]), type(o), o._fused_key(),
               tuple(order), tuple(other_slots))
        from collections import OrderedDict
        cache = getattr(self, "_fused_step_progs", None)
        if cache is None:
            cache = self._fused_step_progs = OrderedDict()
        entry = cache.get(key)
        if entry is not None:
            cache.move_to_end(key)      # broken entries too: stay resident
            if entry.get("broken"):
                return False            # negative-cached failing build
        # update counts advance only once fusion is committed (the eager
        # fallback advances its own) — after the broken-entry early out
        prev_num_update = o.num_update
        for i, _p in items:
            o._update_count(i)
        if entry is None:
            bwd_impl = info["bwd_impl"]
            n_entries = len(entries)
            # grad-buffer dtypes baked in: cast INSIDE the program (an
            # eager convert per parameter per step otherwise)
            g_dtypes = tuple(p.data()._grad._data.dtype
                             for _i, p in params_ordered)
            og_dtypes = tuple(entries[ei][2]._grad._data.dtype
                              for ei in other_slots)

            def body(res, cots, weights, states, ts, lrs, wds, rescale):
                grads_all = bwd_impl(list(res), tuple(cots))
                new_w, new_s, pgrads = [], [], []
                for k, ei in enumerate(order):
                    g = grads_all[ei - 1]
                    nw, ns = o._fused_one(weights[k], g, states[k], ts[k],
                                          lrs[k], wds[k], rescale)
                    new_w.append(nw)
                    new_s.append(ns)
                    pgrads.append(g.astype(g_dtypes[k])
                                  if g.dtype != g_dtypes[k] else g)
                ograds = [grads_all[ei - 1].astype(og_dtypes[k])
                          if grads_all[ei - 1].dtype != og_dtypes[k]
                          else grads_all[ei - 1]
                          for k, ei in enumerate(other_slots)]
                return new_w, new_s, ts + 1.0, pgrads, ograds

            # donate residuals (dead after this), weights, states, ts:
            # params update in place at the memory level
            entry = {"prog": jax.jit(body, donate_argnums=(0, 2, 3, 4)),
                     "keepalive": bwd_impl, "n_entries": n_entries}
            cache[key] = entry
            # LRU bound: ragged shapes must not pin evicted CachedOps'
            # backward closures (and their compiled programs) forever
            while len(cache) > 8:
                cache.popitem(last=False)

        counts = _fused_hyper_refresh(entry, o, params_ordered)

        try:
            import warnings
            with warnings.catch_warnings():
                # residuals are donated to be FREED early (they can never
                # alias the outputs); the "not usable" warning is the
                # expected cost of that, not a miss
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                new_w, new_s, new_ts, pgrads, ograds = entry["prog"](
                    list(res), cots, weights, states, entry["ts"],
                    entry["lrs"], entry["wds"], entry["rescale"])
        except BaseException as e:
            # the failed step never applied: never advance schedules
            _fused_rollback(o, params_ordered, prev_num_update,
                            entry, counts)
            entry["ts"] = None
            consumed = any(
                getattr(a, "is_deleted", lambda: False)()
                for a in jax.tree_util.tree_leaves(
                    (res, weights, states)))
            if not consumed and isinstance(e, Exception):
                # pre-donation failure: the deferred tape is untouched —
                # fall back to eager.  Negative-cache ONLY never-succeeded
                # entries (a genuine trace/compile failure); a transient
                # runtime error on a proven program keeps the fused path.
                if not entry.get("succeeded"):
                    entry["broken"] = True
                    warnings.warn(
                        f"fused hybrid step disabled for this signature "
                        f"(falling back to separate backward+update): "
                        f"{e!r}", stacklevel=2)
                return False
            autograd.clear_pending()    # residuals are gone: no replay
            info["consumed"][0] = True
            if isinstance(e, Exception):
                raise MXNetError(
                    "fused hybrid step failed after dispatch; weight, "
                    "optimizer-state and residual buffers were donated "
                    "to the failed program and may be deleted.  Reload "
                    "parameters before continuing.  Cause: "
                    f"{e!r}") from e
            raise   # KeyboardInterrupt/SystemExit propagate as-is
        entry["ts"] = new_ts
        entry["succeeded"] = True
        autograd.clear_pending()
        info["consumed"][0] = True      # residuals donated: no replay
        for (i, p), nw, ns, g in zip(params_ordered, new_w, new_s, pgrads):
            pd = p.data()
            pd._set_data(nw)
            _state_write_back(upd.states[i], ns)
            gb = pd._grad
            gb._set_data(g if g.dtype == gb._data.dtype
                         else jnp.asarray(g, dtype=gb._data.dtype))
        for ei, g in zip(other_slots, ograds):
            gb = entries[ei][2]._grad
            gb._set_data(g if g.dtype == gb._data.dtype
                         else jnp.asarray(g, dtype=gb._data.dtype))
        return True

    def _pick_fused_program(self, info, fpol, make_body, key_arr,
                            nonparams, cots, weights, states):
        """Resolve the save policy for the one-program step and return
        (fwd_bwd_impl, callable program).

        'auto' (the default) AOT-compiles the save-everything variant
        and checks its fitted peak memory against the device capacity:
        save-all reclaims the checkpoint recompute tax (measured +10-15%
        MFU on BERT-large) but would OOM AFTER donation on memory-tight
        models, so it is only chosen when the compiler-reported peak
        fits with margin.  Any probe failure falls back to the
        CachedOp's (memory-safe) policy."""
        import jax
        import jax.numpy as jnp

        factory = info.get("fwd_bwd_factory")
        safe_impl = info["fwd_bwd_impl"]
        if factory is None or fpol == "inherit":
            return safe_impl, jax.jit(make_body(safe_impl),
                                      donate_argnums=(3, 4, 5))
        if fpol != "auto":
            impl = factory(fpol)
            return impl, jax.jit(make_body(impl), donate_argnums=(3, 4, 5))

        try:
            # capacity first: with no capacity estimate the probe result
            # is unusable and the AOT compile (minutes at BERT-large
            # scale) would be pure waste
            cap = _device_capacity_bytes(jax.devices()[0])
            if cap is None:
                return safe_impl, jax.jit(make_body(safe_impl),
                                          donate_argnums=(3, 4, 5))
            impl_all = factory("all")
            jitted = jax.jit(make_body(impl_all), donate_argnums=(3, 4, 5))
            aval = jax.ShapeDtypeStruct
            n = len(weights)
            lowered = jitted.lower(
                key_arr, nonparams, cots, weights, states,
                aval((n,), jnp.float32), aval((n,), jnp.float32),
                aval((n,), jnp.float32), aval((), jnp.float32))
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            if peak <= 0.9 * cap:
                # AOT executables are shape-monomorphic, which is fine:
                # a shape change means a new CachedOp signature and
                # therefore a new entry
                return impl_all, compiled
        except Exception:       # noqa: BLE001 — any probe failure: safe
            pass
        return safe_impl, jax.jit(make_body(safe_impl),
                                  donate_argnums=(3, 4, 5))

    def _try_full_fused_step(self, node, info, params_ordered, order,
                             other_slots, weights, states, cots):
        """Deferred-forward fusion: forward+backward+optimizer compiled
        as ONE donated program — the three-call recipe at ShardedTrainer
        shape (no residual HBM round trip between programs).

        Returns True on success.  Returns None to fall back cleanly: the
        forward has NOT run and no state was touched, so the caller's
        two-program (or eager) path proceeds normally.  Raises MXNetError
        only when the program failed after buffer donation."""
        import warnings

        import jax
        import jax.numpy as jnp

        from .. import autograd
        from .block import update_aux_state

        o = self._optimizer
        upd = self._updater
        entries = node.input_entries
        n_entries = len(entries)
        pset = set(order)
        nonparam_slots = [ei for ei in range(1, n_entries)
                          if ei not in pset]
        # the record-time snapshot, NOT live buffers: an input (or param)
        # mutated in place between record() and step() must not change
        # what this step computes — eager and the materialize_fwd
        # fallback both use the recorded values
        raw_in = info["raw_in"]
        key_arr = raw_in[0]
        nonparams = [raw_in[ei] for ei in nonparam_slots]
        weights = [raw_in[ei] for ei in order]

        from ..base import get_env
        fpol = str(get_env("MXNET_FUSED_STEP_SAVE_POLICY", "auto"))
        # cheap cache key: jax.jit itself re-traces on any aval change,
        # so per-param shape/dtype signatures here would only duplicate
        # that at ~10ms of host time per step (the fused path is
        # host-latency sensitive — one python step per ~20ms of chip)
        key = ("full", id(info["fwd_bwd_impl"]), fpol, type(o),
               o._fused_key(), tuple(order), tuple(other_slots),
               tuple(nonparam_slots))
        from collections import OrderedDict
        cache = getattr(self, "_fused_step_progs", None)
        if cache is None:
            cache = self._fused_step_progs = OrderedDict()
        entry = cache.get(key)
        if entry is not None:
            cache.move_to_end(key)
            if entry.get("broken"):
                return None                 # negative-cached failing build
        prev_num_update = o.num_update
        for i, _p in params_ordered:
            o._update_count(i)
        if entry is None:
            ne = n_entries
            p_slots = tuple(order)
            np_slots = tuple(nonparam_slots)
            # grad-buffer dtypes baked in: casting INSIDE the program
            # replaces one eager convert dispatch per parameter per step
            # (~400 host round trips at BERT-large scale)
            g_dtypes = tuple(p.data()._grad._data.dtype
                             for _i, p in params_ordered)
            og_dtypes = tuple(entries[ei][2]._grad._data.dtype
                              for ei in other_slots)

            def make_body(fwd_bwd):
                def body(key, nonparams, cots, weights, states, ts, lrs,
                         wds, rescale):
                    arrays = [None] * (ne - 1)
                    for k, ei in enumerate(p_slots):
                        arrays[ei - 1] = weights[k]
                    for k, ei in enumerate(np_slots):
                        arrays[ei - 1] = nonparams[k]
                    outs, grads_all = fwd_bwd(key, arrays, tuple(cots))
                    new_w, new_s, pgrads = [], [], []
                    for k, ei in enumerate(p_slots):
                        g = grads_all[ei - 1]
                        nw, ns = o._fused_one(weights[k], g, states[k],
                                              ts[k], lrs[k], wds[k],
                                              rescale)
                        new_w.append(nw)
                        new_s.append(ns)
                        pgrads.append(g.astype(g_dtypes[k])
                                      if g.dtype != g_dtypes[k] else g)
                    ograds = [grads_all[ei - 1].astype(og_dtypes[k])
                              if grads_all[ei - 1].dtype != og_dtypes[k]
                              else grads_all[ei - 1]
                              for k, ei in enumerate(other_slots)]
                    return (list(outs), new_w, new_s, ts + 1.0, pgrads,
                            ograds)
                return body

            # donate weights/states/ts: params update in place at the
            # memory level.  Inputs and cotangents are NOT donated (user
            # arrays may be reused across steps).
            fwd_bwd, prog = self._pick_fused_program(
                info, fpol, make_body, key_arr, nonparams, cots,
                weights, states)
            # pin BOTH impls: the cache key uses id(info["fwd_bwd_impl"])
            # and a recycled id after CachedOp-LRU eviction would hit a
            # stale shape-monomorphic entry
            entry = {"prog": prog,
                     "keepalive": (fwd_bwd, info["fwd_bwd_impl"])}
            cache[key] = entry
            while len(cache) > 8:
                cache.popitem(last=False)

        counts = _fused_hyper_refresh(entry, o, params_ordered)

        try:
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                new_outs, new_w, new_s, new_ts, pgrads, ograds = \
                    entry["prog"](key_arr, nonparams, cots, weights,
                                  states, entry["ts"], entry["lrs"],
                                  entry["wds"], entry["rescale"])
        except BaseException as e:
            # the failed step never applied: never advance schedules
            _fused_rollback(o, params_ordered, prev_num_update,
                            entry, counts)
            consumed_bufs = any(
                getattr(a, "is_deleted", lambda: False)()
                for a in jax.tree_util.tree_leaves((weights, states)))
            if not consumed_bufs and isinstance(e, Exception):
                # pre-donation failure (trace/compile): nothing ran, the
                # deferred forward is untouched — negative-cache a
                # never-succeeded build and fall back
                if not entry.get("succeeded"):
                    entry["broken"] = True
                    warnings.warn(
                        f"one-program hybrid step disabled for this "
                        f"signature (falling back to the two-program "
                        f"path): {e!r}", stacklevel=2)
                return None
            # donation happened: weights/states are gone and the deferred
            # outputs can never materialize.  Store the error on each
            # output's var (reference: exception-on-var) — direct reads
            # raise it, while the waitall sweep skips these husks (the
            # failure below is already raised synchronously here)
            autograd.clear_pending()
            info["consumed"][0] = True
            info["fwd_pending"][0] = False
            for out in info.get("outs") or []:
                if out._lazy_cb is not None:
                    out._lazy_cb = None
                    out._var.set_exception(MXNetError(
                        "this output's producing fused step failed after "
                        f"donation; reload parameters.  Cause: {e!r}"))
            if isinstance(e, Exception):
                raise MXNetError(
                    "fused hybrid step failed after dispatch; weight and "
                    "optimizer-state buffers were donated to the failed "
                    "program and may be deleted.  Reload parameters "
                    f"before continuing.  Cause: {e!r}") from e
            raise   # KeyboardInterrupt/SystemExit propagate as-is

        entry["ts"] = new_ts
        entry["succeeded"] = True
        autograd.clear_pending()
        info["consumed"][0] = True
        info["fwd_pending"][0] = False
        outs_nd = info.get("outs") or []
        for out, v in zip(outs_nd, new_outs):
            out._lazy_cb = None
            out._set_data(v)
        n_flat = info["n_flat_out"]
        for p, v in zip(info["aux_params"], new_outs[n_flat:]):
            update_aux_state(p, v, ctx=None)
        for (i, p), nw, ns, g in zip(params_ordered, new_w, new_s,
                                     pgrads):
            pd = p.data()
            pd._set_data(nw)
            _state_write_back(upd.states[i], ns)
            gb = pd._grad
            gb._set_data(g if g.dtype == gb._data.dtype
                         else jnp.asarray(g, dtype=gb._data.dtype))
        for ei, g in zip(other_slots, ograds):
            gb = entries[ei][2]._grad
            gb._set_data(g if g.dtype == gb._data.dtype
                         else jnp.asarray(g, dtype=gb._data.dtype))
        return True

    # ------------------------------------------------------- fused update
    # One XLA program updates every parameter (reference: the multi-tensor
    # update ops + Trainer aggregation).  Eager per-param dispatch costs
    # ~ms of launch latency each on TPU; at hundreds of parameters that
    # dwarfs the update math.  State buffers are donated — the program
    # updates moments in place at the memory level.
    def _fused_eligible(self):
        o = self._optimizer
        if not getattr(o, "fused", False):
            return False
        if self._num_ctx() > 1:
            return False
        for p in self._params:
            if p.grad_req == "null":
                continue
            if getattr(p, "_grad_stype", "default") != "default":
                return False
            if p.grad_req != "write":
                # 'add' grads accumulate across steps; keep the reference
                # per-param path for that rarity
                return False
        return True

    def _fused_update(self):
        if not self._fused_eligible():
            return False
        import jax
        import jax.numpy as jnp
        o = self._optimizer
        upd = self._updater
        items = [(i, p) for i, p in enumerate(self._params)
                 if p.grad_req != "null"]
        if not items:
            return True
        for i, p in items:
            if i not in upd.states:
                upd.states[i] = o.create_state_multi_precision(i, p.data())
            o._update_count(i)

        as_raw, state_sig, write_back = (_state_raw, _state_sig,
                                         _state_write_back)

        weights = [p.data()._data for _, p in items]
        grads = [p.grad()._data for _, p in items]
        states = [as_raw(upd.states[i]) for i, _ in items]

        key = (type(o), o._fused_key(),
               tuple((tuple(w.shape), str(w.dtype), state_sig(upd.states[i]))
                     for (i, _), w in zip(items, weights)))
        cache = getattr(self, "_fused_progs", None)
        if cache is None:
            cache = self._fused_progs = {}
        entry = cache.get(key)
        if entry is None:
            def body(weights, grads, states, ts, lrs, wds, rescale):
                new_w, new_s = [], []
                for k, (w, g, s) in enumerate(zip(weights, grads, states)):
                    nw, ns = o._fused_one(w, g, s, ts[k], lrs[k], wds[k],
                                          rescale)
                    new_w.append(nw)
                    new_s.append(ns)
                # t advances on device: no per-step host->device upload
                return new_w, new_s, ts + 1.0
            # weights, states and ts are donated: the program updates them
            # in place at the memory level (static-alloc semantics); grads
            # are NOT donated — p.grad() stays readable after step()
            entry = {"prog": jax.jit(body, donate_argnums=(0, 2, 3))}
            cache[key] = entry

        # step-varying scalars stay device-resident: re-upload only when
        # the python-side values change (each small upload pays a full
        # host->device round trip, which at TPU dispatch latency would
        # rival the update program itself)
        counts = [o._index_update_count[i] for i, _ in items]
        if entry.get("ts") is None or entry.get("counts") != counts:
            entry["ts"] = jnp.asarray([float(c) for c in counts],
                                      jnp.float32)
        # after the program runs, the donated+incremented device ts equals
        # counts+1 — which is what the python counts will read next step
        entry["counts"] = [c + 1 for c in counts]
        lrs_py = tuple(float(o._get_lr(i)) for i, _ in items)
        wds_py = tuple(float(o._get_wd(i)) for i, _ in items)
        rs_py = float(o.rescale_grad)
        if entry.get("hyper") != (lrs_py, wds_py, rs_py):
            entry["lrs"] = jnp.asarray(lrs_py, jnp.float32)
            entry["wds"] = jnp.asarray(wds_py, jnp.float32)
            entry["rescale"] = jnp.float32(rs_py)
            entry["hyper"] = (lrs_py, wds_py, rs_py)

        new_w, new_s, new_ts = entry["prog"](
            weights, grads, states, entry["ts"], entry["lrs"],
            entry["wds"], entry["rescale"])
        entry["ts"] = new_ts
        for (i, p), nw, ns in zip(items, new_w, new_s):
            p.data()._set_data(nw)
            write_back(upd.states[i], ns)
        return True

    # ---------------------------------------------------------- persistence
    def save_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
            return
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer=False))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, "rb") as f:
            payload = f.read()
        # restore into EVERY device updater — including ones that have not
        # been lazily created yet (fresh-Trainer resume on multi-ctx params)
        for j in range(self._num_ctx()):
            if j not in self._dev_updaters:
                self._dev_updaters[j] = opt.get_updater(self._optimizer)
        for updater in self._dev_updaters.values():
            updater.set_states(payload)
            updater.optimizer = self._optimizer
