"""FusedTrainStep: the whole training step as ONE compiled XLA program.

New TPU-first capability (no direct upstream equivalent — the closest
reference surface is the fused multi-tensor optimizer ops plus engine
bulk-exec, SURVEY.md §3.3/§7.3, which batch work but still dispatch
forward, backward and update separately).  The classic Gluon recipe

    with autograd.record():
        loss = block(*inputs)
    loss.backward()
    trainer.step(batch_size)

dispatches three XLA programs; gradients make a full HBM round trip
between backward and update, and each dispatch pays the launch
latency.  ``FusedTrainStep`` compiles forward+backward+optimizer into a
single donated program while the weights keep living in the Block's
``Parameter`` objects — ``save_parameters``, ``set_learning_rate``,
``export`` all keep working:

    step = FusedTrainStep(loss_block, trainer)
    for batch in loader:
        loss = step(*batch)                    # one XLA dispatch

Semantic differences from the three-call recipe (documented contract):
- parameter ``.grad`` buffers are NOT written (gradients exist only
  inside the compiled program); ``grad_req='add'`` accumulation is
  unsupported and raises.
- the autograd tape is bypassed — do not wrap calls in
  ``autograd.record()``.
- a step that fails AFTER dispatch consumes the donated weight and
  optimizer-state buffers (unlike the three-call recipe, which leaves
  weights intact).  Errors surfacing at dispatch poison the instance
  with a reload-and-``reset()`` message; with fully asynchronous
  dispatch an execution error can instead surface at a later sync point
  as a raw XLA error, and the next ``__call__`` detects the deleted
  buffers and raises the same guidance.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp

from ...base import MXNetError, get_env
from ...ndarray import NDArray

__all__ = ["FusedTrainStep"]


from ..trainer import _state_raw as _as_raw           # noqa: E402
from ..trainer import _state_write_back as _write_back  # noqa: E402


class FusedTrainStep:
    """Compile ``block``'s loss forward + backward + ``trainer``'s
    optimizer into one donated XLA program (see module docstring).

    ``block`` must return the loss (any shape; it is summed for the
    backward seed, exactly like ``loss.backward()``'s default ones
    cotangent).  ``trainer`` must be single-context with a fused-capable
    optimizer and no kvstore.
    """

    def __init__(self, block, trainer):
        self._block = block
        self._trainer = trainer
        self._cache = {}
        self._poisoned = None
        o = trainer._optimizer
        if not getattr(o, "fused", False):
            raise MXNetError(
                f"FusedTrainStep: optimizer {type(o).__name__} has no "
                f"fused kernel")
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if trainer._kvstore is not None or trainer._update_on_kvstore:
            raise MXNetError(
                "FusedTrainStep is single-context; use "
                "parallel.ShardedTrainer (or kvstore-backed Trainer.step) "
                "for multi-device training")
        for p in trainer._params:
            if p.grad_req == "add":
                raise MXNetError(
                    "FusedTrainStep cannot honor grad_req='add' "
                    "(gradients never materialize); use the "
                    "record/backward/step recipe for accumulation")
            if getattr(p, "_grad_stype", "default") != "default":
                raise MXNetError(
                    f"FusedTrainStep computes dense gradients; parameter "
                    f"{p.name!r} requests grad_stype="
                    f"{p._grad_stype!r} lazy sparse updates — use the "
                    f"record/backward/step recipe")

    def reset(self):
        """Clear the poisoned flag after parameters (and optimizer state)
        have been reloaded following a failed donated step.

        Optimizer states the user restored (``trainer.load_states``) are
        kept; only states still pointing at buffers deleted by the failed
        donation are dropped (they are recreated from scratch on the next
        step)."""
        self._poisoned = None
        upd = self._trainer._updater
        for i in list(upd.states):
            leaves = jax.tree_util.tree_leaves(_as_raw(upd.states[i]))
            if any(getattr(a, "is_deleted", lambda: False)()
                   for a in leaves):
                del upd.states[i]
        for entry in self._cache.values():
            entry["ts"] = None      # ts was donated with weights/states

    # ---------------------------------------------------------------- build
    def _build(self, sig, inputs):
        from ...gluon.block import _AUX_CAPTURE, _TRACING, _flatten
        from ...gluon.parameter import _PARAM_OVERRIDE
        from ... import autograd, random as mxrand

        trainer = self._trainer
        o = trainer._optimizer
        block = self._block

        params = OrderedDict(block.collect_params().items())
        trainable, frozen = [], []
        t_index = {id(p): i for i, p in enumerate(trainer._params)}
        for name, p in params.items():
            if p.grad_req == "null":
                frozen.append((name, p))
            elif id(p) in t_index:
                trainable.append((t_index[id(p)], name, p))
            else:
                # a second Trainer managing this param would read .grad
                # buffers this step never writes: refuse loudly
                raise MXNetError(
                    f"FusedTrainStep: parameter {name!r} has "
                    f"grad_req={p.grad_req!r} but is not managed by the "
                    f"given trainer; multi-trainer setups need the "
                    f"record/backward/step recipe (or grad_req='null' "
                    f"to freeze it)")
        if not trainable:
            raise MXNetError("FusedTrainStep: no trainable parameters")

        n_in = len(inputs)
        t_names = [n for _i, n, _p in trainable]
        f_names = [n for n, _p in frozen]
        aux_order = []                      # Parameter objs, fixed at trace

        def forward(key, input_arrays, weight_arrays, frozen_arrays):
            xs = [NDArray(a) for a in input_arrays]
            override = {params[n]: NDArray(a)
                        for n, a in zip(t_names, weight_arrays)}
            override.update({params[n]: NDArray(a)
                             for n, a in zip(f_names, frozen_arrays)})
            tok_t = _TRACING.set(True)
            tok_p = _PARAM_OVERRIDE.set(override)
            tok_a = _AUX_CAPTURE.set(OrderedDict())
            try:
                with mxrand.trace_key_scope(key):
                    with autograd.pause(train_mode=True):
                        out = block.forward(*xs)
                cap = _AUX_CAPTURE.get()
            finally:
                _AUX_CAPTURE.reset(tok_a)
                _PARAM_OVERRIDE.reset(tok_p)
                _TRACING.reset(tok_t)
            flat, _tree = _flatten(out)
            if not aux_order:
                aux_order.extend(cap.keys())
            return flat[0]._data, tuple(cap.values())

        policy_name = get_env("MXNET_CACHED_OP_SAVE_POLICY")
        policies = {
            "all": None,
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "none": jax.checkpoint_policies.nothing_saveable,
        }
        policy = policies.get(str(policy_name), policies["dots_no_batch"])

        def prog(key, ts, lrs, wds, rescale, input_arrays, weights,
                 frozen_arrays, states):
            def loss_fn(ws):
                loss, aux = forward(key, input_arrays, ws, frozen_arrays)
                return loss.astype(jnp.float32).sum(), (loss, aux)

            fn = loss_fn if policy is None else \
                jax.checkpoint(loss_fn, policy=policy)
            (_total, (loss, aux)), grads = \
                jax.value_and_grad(fn, has_aux=True)(list(weights))
            new_w, new_s = [], []
            for k, (w, g, s) in enumerate(zip(weights, grads, states)):
                nw, ns = o._fused_one(w, g, s, ts[k], lrs[k], wds[k],
                                      rescale)
                new_w.append(nw)
                new_s.append(ns)
            return loss, aux, new_w, new_s, ts + 1.0

        # weights, states and ts are donated: in-place update at the
        # memory level (the static-alloc contract)
        jitted = jax.jit(prog, donate_argnums=(1, 6, 8))
        entry = {"prog": jitted, "trainable": trainable, "frozen": frozen,
                 "aux_order": aux_order, "ts": None, "counts": None,
                 "hyper": None}
        self._cache[sig] = entry
        return entry

    # ----------------------------------------------------------------- call
    def __call__(self, *inputs, batch_size=None):
        from ... import random as mxrand
        from ...gluon.block import update_aux_state

        from ... import autograd

        if self._poisoned is not None:
            raise MXNetError(
                "FusedTrainStep: a previous donated step failed after "
                "dispatch; the block's weight and optimizer-state buffers "
                "were consumed and are gone.  Reload parameters "
                "(load_parameters / initialize(force_reinit=True)), then "
                "call .reset() on this FusedTrainStep (or construct a new "
                "one) before training again.  Original failure: "
                f"{self._poisoned!r}") from self._poisoned

        trainer = self._trainer
        o = trainer._optimizer
        upd = trainer._updater
        if batch_size is None:
            batch_size = inputs[0].shape[0]
        o.rescale_grad = trainer._scale / batch_size

        ctx = inputs[0].context
        block_params = self._block.collect_params()
        if any(p._deferred_init is not None or not p._data
               for p in block_params.values()):
            # one predict-mode pass resolves deferred shapes (same
            # mechanism as parallel.functionalize)
            with autograd.pause(train_mode=False):
                self._block(*inputs)
        sig = (tuple((tuple(x.shape), str(x._data.dtype)) for x in inputs),
               tuple((n, tuple(p.shape), str(p.dtype))
                     for n, p in block_params.items()),
               type(o), o._fused_key())
        entry = self._cache.get(sig)
        if entry is None:
            entry = self._build(sig, inputs)
        trainable, frozen = entry["trainable"], entry["frozen"]

        # detect an asynchronously-surfaced donation failure BEFORE the
        # bookkeeping below advances update counts (a failed/aborted step
        # must never advance schedules)
        stale = [a for _i, _n, p in trainable
                 for a in (p.data(ctx)._data,)] + [
            a for i, _n, _p in trainable if i in upd.states
            for a in jax.tree_util.tree_leaves(_as_raw(upd.states[i]))]
        if any(getattr(a, "is_deleted", lambda: False)() for a in stale):
            raise MXNetError(
                "FusedTrainStep: weight/optimizer-state buffers were "
                "deleted by a previously failed donated step (the failure "
                "surfaced asynchronously).  Reload parameters, then call "
                ".reset() (or construct a new FusedTrainStep).")

        # same per-step bookkeeping as Trainer._fused_update: ensure
        # states, advance the python-side update counts, keep ts on device
        prev_num_update = o.num_update
        for i, _n, p in trainable:
            if i not in upd.states:
                upd.states[i] = o.create_state_multi_precision(i, p.data())
            o._update_count(i)
        counts = [o._index_update_count[i] for i, _n, _p in trainable]
        if entry["ts"] is None or entry["counts"] != counts:
            entry["ts"] = jnp.asarray([float(c) for c in counts],
                                      jnp.float32)
        entry["counts"] = [c + 1 for c in counts]
        lrs_py = tuple(float(o._get_lr(i)) for i, _n, _p in trainable)
        wds_py = tuple(float(o._get_wd(i)) for i, _n, _p in trainable)
        rs_py = float(o.rescale_grad)
        if entry["hyper"] != (lrs_py, wds_py, rs_py):
            entry["lrs"] = jnp.asarray(lrs_py, jnp.float32)
            entry["wds"] = jnp.asarray(wds_py, jnp.float32)
            entry["rescale"] = jnp.float32(rs_py)
            entry["hyper"] = (lrs_py, wds_py, rs_py)

        weights = [p.data(ctx)._data for _i, _n, p in trainable]
        frozen_arrays = [p.data(ctx)._data for _n, p in frozen]
        states = [_as_raw(upd.states[i]) for i, _n, _p in trainable]
        key = mxrand.next_key()

        try:
            loss, aux, new_w, new_s, new_ts = entry["prog"](
                key, entry["ts"], entry["lrs"], entry["wds"],
                entry["rescale"], [x._data for x in inputs], weights,
                frozen_arrays, states)
        except BaseException as e:
            # the program donated weights/states: a failure after dispatch
            # (async XLA error, OOM, interrupt — incl. KeyboardInterrupt,
            # hence BaseException) consumes them without the write-back
            # below ever running — unlike the three-call recipe a failed
            # fused step does NOT leave weights intact.  Trace/compile
            # failures happen BEFORE donation though, so only poison when
            # a donated buffer was actually deleted.
            consumed = any(
                getattr(a, "is_deleted", lambda: False)()
                for a in jax.tree_util.tree_leaves((weights, states)))
            # the failed step never applied: roll back the update counts
            # advanced above so lr schedules / bias correction don't drift
            # (num_update advanced via max(); restore it alongside)
            for i, _n, _p in trainable:
                o._index_update_count[i] -= 1
            o.num_update = prev_num_update
            entry["counts"] = counts
            if not consumed:
                raise
            self._poisoned = e
            entry["ts"] = None          # donated alongside weights/states
            if isinstance(e, Exception):
                raise MXNetError(
                    "FusedTrainStep failed after dispatch; weight and "
                    "optimizer-state buffers were donated to the failed "
                    "program and may be deleted.  Reload parameters, then "
                    "call .reset() (or construct a new FusedTrainStep). "
                    f"Cause: {e!r}") from e
            raise   # KeyboardInterrupt/SystemExit must propagate as-is
        entry["ts"] = new_ts
        for (i, _n, p), nw, ns in zip(trainable, new_w, new_s):
            p.data(ctx)._set_data(nw)
            _write_back(upd.states[i], ns)
        for p, v in zip(entry["aux_order"], aux):
            update_aux_state(p, v, ctx=None)
        out = NDArray(loss)
        from ...engine import engine, is_naive
        if is_naive():
            out.wait_to_read()
        engine().track(out)
        return out
