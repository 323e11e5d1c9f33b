"""ShardedTrainer: ONE compiled train step over a device mesh.

Replaces Trainer+kvstore at pod scale (SURVEY.md §3.4 TPU mapping): the
entire fwd+bwd+optimizer+allreduce is a single pjit program; XLA lowers
the gradient reductions to ICI/DCN collectives from the shardings alone.

With ``compression=`` (int8/fp8, ``mxnet_tpu.quantize``) the
data-parallel gradient mean runs as an EXPLICIT quantized collective
instead: the step computes per-device gradients under ``shard_map``
over the ``dp`` axis, error-feedback-quantizes each device's
contribution, all-gathers only the compressed payload + per-block f32
scales, and dequant-accumulates in f32 — still ONE compiled program
(quant/dequant fuse into the collective), but the bytes crossing chips
shrink ~4x (EQuARX, PAPERS.md).  The per-device rounding-error
residuals ride the donated step state like the optimizer state does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compile_cache as _cc
from .. import faults as _faults
from .. import quantize as qz
from .. import runtime_metrics as _rm
from .. import tracing as _tr
from ..base import MXNetError
from . import optim as _optim
from .functional import functionalize
from .sharding import MEGATRON_RULES, global_device_put, partition_params
from .supervisor import StepWatchdog

__all__ = ["ShardedTrainer"]


def _loss_and_grads(apply_fn, loss_fn, params, inputs, labels):
    """``((loss, aux), grads)`` of one forward and backward pass.  The
    scopes here (an op's op_name reads .../mx.fwd/<block>/..., its
    backward's transpose(jvp(mx.fwd))) are what the device metrics find
    the step's parts by, so both step programs take them from this one
    place.  jax leaves scope names out of the persistent cache's key: a
    program that differs from a cached one in scopes alone is served
    the cached executable without them (PERF.md section 7)."""
    def loss_of(p):
        with jax.named_scope("mx.fwd"):
            out, aux = apply_fn(p, *inputs)
        with jax.named_scope("mx.loss"):
            return loss_fn(out, *labels), aux

    return jax.value_and_grad(loss_of, has_aux=True)(params)


def _keep_frozen(params, new_params, aux, trainable):
    """Frozen params pass through untouched; aux states take the
    forward-captured update (BatchNorm moving stats), exactly like the
    eager/CachedOp paths."""
    new_params = {n: (v if n in trainable else params[n])
                  for n, v in new_params.items()}
    for n, v in aux.items():
        if n in new_params:
            new_params[n] = v.astype(new_params[n].dtype)
    return new_params


def _sgd_shardings(ps, repl):
    return {"mom": dict(ps)}


def _adam_shardings(ps, repl):
    return {"mean": dict(ps), "var": dict(ps), "step": repl}


_OPTIMS = {
    "sgd": (_optim.sgd_init, _optim.sgd_update, _sgd_shardings),
    "adamw": (_optim.adamw_init, _optim.adamw_update, _adam_shardings),
    "lamb": (_optim.lamb_init, _optim.lamb_update, _adam_shardings),
}


class ShardedTrainer:
    """Compile a data+tensor-parallel training step for a Gluon block.

    loss_fn(outputs, *labels) -> scalar, written in jnp over raw arrays.
    Batch dims of inputs/labels are sharded over "dp"; params follow
    ``rules`` (default Megatron TP).  Donation gives in-place updates.
    ``take_block_params=True`` frees the block's own parameter buffers
    once copied, and its gradient buffers (which the step never writes:
    as large as the parameters again), so nothing is held twice; the
    block is unusable until ``write_back()``.
    """

    def __init__(self, block, loss_fn, mesh: Mesh, optimizer="adamw",
                 optimizer_params=None, rules=MEGATRON_RULES,
                 example_inputs=(), n_labels=1, dtype=None,
                 compression=None, step_timeout_ms=None,
                 slow_step_factor=None, take_block_params=False):
        if optimizer not in _OPTIMS:
            raise MXNetError(f"unknown optimizer {optimizer!r}; "
                             f"known: {sorted(_OPTIMS)}")
        self.mesh = mesh
        self.block = block
        # step deadline + straggler detection (defaults from
        # MXNET_TRAIN_STEP_TIMEOUT_MS / MXNET_TRAIN_SLOW_STEP_FACTOR;
        # both off = step() dispatches directly, zero wrapper cost)
        self.watchdog = StepWatchdog(timeout_ms=step_timeout_ms,
                                     slow_factor=slow_step_factor)
        # the tag the mx.train.* phases of one step share; the compile
        # count's listener starts before the step program is built
        self._step_no = 0
        _cc.backend_compiles()
        self.compression = qz.CompressionSpec.parse(compression)
        if self.compression is not None:
            if "dp" not in mesh.shape:
                raise MXNetError(
                    "ShardedTrainer(compression=...): mesh has no 'dp' "
                    "axis to compress gradients over")
            sharded_axes = [a for a, s in mesh.shape.items()
                            if a != "dp" and s > 1]
            if sharded_axes:
                raise MXNetError(
                    f"ShardedTrainer(compression=...) needs a pure "
                    f"data-parallel mesh: axes {sharded_axes} have size "
                    f"> 1, and quantized sync of tensor/pipeline-"
                    f"sharded gradients is not supported — drop "
                    f"compression or reshape the mesh to dp-only")
        opt_init, opt_update, opt_shard = _OPTIMS[optimizer]
        opt_kw = dict(optimizer_params or {})
        if "learning_rate" in opt_kw:
            opt_kw["lr"] = opt_kw.pop("learning_rate")
        if "weight_decay" in opt_kw:            # Gluon naming → optim's
            opt_kw["wd"] = opt_kw.pop("weight_decay")

        apply_fn, params = functionalize(block, *example_inputs,
                                         train_mode=True)
        # device_put below may ALIAS the Block's live buffers on
        # same-backend transfers; the step donates params, and donating
        # an aliased buffer deletes the imperative API's view (a later
        # wait_to_read/waitall then fails with "deleted or donated
        # buffer").  astype is a no-op alias when the dtype already
        # matches, so copy unconditionally in BOTH branches.
        def _own(a):
            if dtype is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return jnp.array(a, dtype=dtype, copy=True)
            return jnp.array(a, copy=True)

        # ``take_block_params``: each of the block's buffers is freed as
        # soon as the trainer has its copy, so that no parameter is held
        # twice (a model near the device's memory cannot afford the
        # block's dead copy).  The block's parameters are then invalid
        # until ``write_back()``.
        for n in list(params):
            theirs = params[n]
            params[n] = _own(theirs)
            if take_block_params:
                theirs.delete()
        if take_block_params:
            for p in block.collect_params().values():
                for g in p._grad.values():
                    g._data.delete()
        self.params, self.param_shardings = partition_params(
            params, mesh, rules)
        self.opt_state = opt_init(self.params)
        self._n_inputs = len(example_inputs)
        self._n_labels = int(n_labels)
        # aux/frozen params (grad_req='null': BatchNorm running stats,
        # positional constants) must NOT receive optimizer updates — with
        # zero grads the weight-decay term would silently erode them
        trainable = frozenset(
            n for n, p in block.collect_params().items()
            if p.grad_req != "null" and n in params)

        batch_spec = NamedSharding(mesh, P("dp"))
        repl = NamedSharding(mesh, P())
        # pin optimizer-state shardings: without this the first step's
        # outputs carry compiler-chosen shardings, every subsequent call
        # misses the jit cache and RECOMPILES the whole step
        opt_shardings = opt_shard(self.param_shardings, repl)
        self.opt_state = jax.tree_util.tree_map(
            global_device_put, self.opt_state, opt_shardings)

        # the program's name (XLA Modules reads jit_mx_train_step) is
        # what the device metrics find the step by
        if self.compression is None:
            def mx_train_step(params, opt_state, *batch):
                (loss, aux), grads = _loss_and_grads(
                    apply_fn, loss_fn, params,
                    batch[:self._n_inputs], batch[self._n_inputs:])
                with jax.named_scope("mx.optim"):
                    new_params, new_state = opt_update(
                        params, grads, opt_state, **opt_kw)
                return (_keep_frozen(params, new_params, aux, trainable),
                        new_state, loss)

            self._step = jax.jit(
                mx_train_step,
                donate_argnums=(0, 1),
                out_shardings=(self.param_shardings, opt_shardings,
                               repl))
        else:
            self._build_compressed_step(
                apply_fn, loss_fn, opt_update, opt_kw, trainable,
                opt_shardings, repl)
        self._batch_spec = batch_spec

    def _build_compressed_step(self, apply_fn, loss_fn, opt_update,
                               opt_kw, trainable, opt_shardings, repl):
        """The quantized-allreduce variant of the train step: local
        grads under ``shard_map`` over dp, EF-quantized mean, optimizer
        outside the manual region.  Per-device residuals are state —
        donated and re-emitted every step like ``opt_state``."""
        mesh, spec = self.mesh, self.compression
        ndp = mesh.shape["dp"]
        comp_names = tuple(
            n for n in self.params
            if n in trainable
            and jnp.issubdtype(self.params[n].dtype, jnp.floating))
        comp_set = frozenset(comp_names)
        comp_index = {n: i for i, n in enumerate(comp_names)}
        res_sharding = NamedSharding(mesh, P("dp"))
        # residual leading axis = dp (each device's rounding error);
        # f32 regardless of param dtype (the EF accumulate-wide rule)
        self.residuals = {
            n: global_device_put(
                jnp.zeros((ndp,) + tuple(self.params[n].shape),
                          jnp.float32), res_sharding)
            for n in comp_names}
        res_shardings = {n: res_sharding for n in comp_names}
        n_inputs = self._n_inputs
        self._quant_step = 0

        def local_sync(p, res, key, *b):
            (loss, aux), grads = _loss_and_grads(
                apply_fn, loss_fn, p, b[:n_inputs], b[n_inputs:])
            dkey = None
            if spec.stochastic:
                dkey = jax.random.fold_in(key, lax.axis_index("dp"))
            synced, new_res = {}, {}
            with jax.named_scope("mx.collective"):
                for n, g in grads.items():
                    if n in comp_set:
                        pkey = None if dkey is None else \
                            jax.random.fold_in(dkey, comp_index[n])
                        m, r = qz.allreduce_mean(g, res[n][0], spec,
                                                 "dp", key=pkey)
                        synced[n] = m
                        new_res[n] = r[None]
                    else:
                        synced[n] = lax.pmean(g, "dp")
                loss = lax.pmean(loss, "dp")
            # out_specs claims aux replicated (P()): every branch must
            # reduce, or each device keeps its own value silently
            # (check_vma=False turns the runtime check off).  pmax
            # is dtype-preserving for the non-float stats — identity
            # when devices already agree, deterministic otherwise.
            aux = {n: (lax.pmean(v, "dp")
                       if jnp.issubdtype(v.dtype, jnp.floating)
                       else lax.pmax(v, "dp"))
                   for n, v in aux.items()}
            return synced, new_res, loss, aux

        # check_vma=False: the quantized-collective bodies produce
        # replicated outputs via a symmetric all_gather + local reduce,
        # which shard_map's static checker cannot prove replicated
        # (only psum-family results are)
        sync = shard_map(
            local_sync, mesh=mesh,
            in_specs=(P(), P("dp"), P())
            + (P("dp"),) * (n_inputs + self._n_labels),
            out_specs=(P(), P("dp"), P(), P()), check_vma=False)

        def mx_train_step(params, opt_state, residuals, key, *batch):
            synced, new_res, loss, aux = sync(params, residuals, key,
                                              *batch)
            with jax.named_scope("mx.optim"):
                new_params, new_state = opt_update(params, synced,
                                                   opt_state, **opt_kw)
            return (_keep_frozen(params, new_params, aux, trainable),
                    new_state, new_res, loss)

        self._step = jax.jit(
            mx_train_step,
            donate_argnums=(0, 1, 2),
            out_shardings=(self.param_shardings, opt_shardings,
                           res_shardings, repl))
        # wire accounting, computed once: each of the dp devices
        # transmits its compressed contribution per step (vs the f32
        # payload the uncompressed allreduce would move)
        sizes = [int(self.params[n].size) for n in comp_names]
        self.wire_bytes_per_step = ndp * sum(
            qz.wire_bytes(s, spec) for s in sizes)
        self.logical_bytes_per_step = ndp * sum(
            qz.logical_bytes(s, self.params[n].dtype)
            for s, n in zip(sizes, comp_names))

    def shard_batch(self, *arrays):
        """Place host arrays batch-sharded over dp."""
        out = []
        with _tr.phase("train.h2d", step=self._step_no):
            for a in arrays:
                spec = P(*(["dp"] + [None] * (a.ndim - 1)))
                out.append(global_device_put(
                    a, NamedSharding(self.mesh, spec)))
        return tuple(out)

    def step(self, *batch):
        """One compiled step; returns the (replicated) scalar loss.

        Under an active watchdog (``MXNET_TRAIN_STEP_TIMEOUT_MS`` /
        ``MXNET_TRAIN_SLOW_STEP_FACTOR``) the dispatch runs to DEVICE
        COMPLETION on a deadline thread: a wedged collective raises
        :class:`~.supervisor.TrainStepTimeoutError` inside the
        configured deadline instead of hanging the loop, and stragglers
        fire ``train.slow_steps``.  ``faults.inject("train.step")`` is
        the chaos hook for the whole step.

        The step is a ``mx.train.step`` phase in the JAX profiler's
        trace (:func:`~mxnet_tpu.tracing.phase`; a no-op outside a
        profiler session, and nothing blocks for it), with
        ``mx.train.h2d`` / ``mx.train.dispatch`` nested, and
        ``mx.train.sync`` under a watchdog deadline only; ``compiles``
        is the process's count of backend compiles so far, so two
        steps' tags tell whether one compiled."""
        self._step_no += 1
        with _tr.phase("train.step", step=self._step_no,
                       compiles=_cc.backend_compiles()):
            batch = self.shard_batch(
                *[getattr(b, "_data", b) for b in batch])
            if self.watchdog.active:
                out = self.watchdog.watch(
                    lambda: self._dispatch_step(batch, sync=True))
            else:
                out = self._dispatch_step(batch, sync=False)
            # commit on the CALLING thread only: after a watchdog
            # timeout the abandoned worker may eventually finish, and
            # its output must never clobber state the supervisor has
            # since restored from a checkpoint (run_with_deadline
            # discards it instead)
            self.params, self.opt_state, residuals, quant_step, loss = out
            if residuals is not None:
                self.residuals = residuals
            if quant_step is not None:
                self._quant_step = quant_step
                if _rm._ENABLED:
                    _rm.KV_WIRE_BYTES.inc(self.wire_bytes_per_step)
            return loss

    def _dispatch_step(self, batch, sync):
        """Pure with respect to trainer attributes — runs on the
        watchdog worker thread when a deadline is set, so it must only
        COMPUTE the new state and return it; ``step()`` commits."""
        # the fault site lives inside the watched call: a ``stall``
        # here is the wedged-collective shape the deadline must bound
        _faults.inject("train.step")
        step = self._step_no
        if self.compression is None:
            with _tr.phase("train.dispatch", step=step):
                params, opt_state, loss = self._step(
                    self.params, self.opt_state, *batch)
            residuals = quant_step = None
        else:
            quant_step = self._quant_step + 1
            key = jax.random.PRNGKey(quant_step)
            with _tr.phase("train.dispatch", step=step):
                params, opt_state, residuals, loss = \
                    self._step(self.params, self.opt_state,
                               self.residuals, key, *batch)
        if sync:
            # the deadline must cover execution, not just dispatch —
            # async dispatch would "beat" any timeout while the wedged
            # collective hangs the NEXT host sync instead
            with _tr.phase("train.sync", step=step):
                jax.block_until_ready(loss)
        return params, opt_state, residuals, quant_step, loss

    def extra_state(self):
        """Non-array step state for checkpoint ``extra`` payloads —
        the quantized-collective step counter seeds each step's
        stochastic-rounding key, so bit-exact resume must restore it."""
        if self.compression is not None:
            return {"quant_step": int(self._quant_step)}
        return {}

    def set_extra_state(self, state):
        if self.compression is not None and state \
                and "quant_step" in state:
            self._quant_step = int(state["quant_step"])

    def write_back(self):
        """Copy trained params back into the Block's Parameters."""
        for name, p in self.block.collect_params().items():
            if name in self.params:
                arr = p.data()
                arr._set_data(jax.device_put(
                    self.params[name],
                    arr._data.sharding if hasattr(arr._data, "sharding")
                    else None).astype(arr._data.dtype))
                if any(g._data.is_deleted() for g in p._grad.values()):
                    p._init_grad()      # taken with the parameters
