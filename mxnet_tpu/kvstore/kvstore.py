"""KVStore: multi-device gradient aggregation & weight sync.

Reference surface: ``python/mxnet/kvstore/kvstore.py`` + ``src/kvstore/``
(`KVStoreLocal`, `CommDevice`, `KVStoreNCCL`) — SURVEY.md §2.1 KVStore row,
§2.4 P1/P2/P5/P6, §5.8.

TPU-native redesign (not a translation):

- ``'local'`` / ``'device'``: single-process reduce across per-context
  copies.  The reference reduces on CPU ('local') or via GPU P2P
  ('device'); here both are one ``jax.device_put`` + add chain differing
  only in where the reduction lands.
- ``'xla'``: the NCCL/dist tier replacement — push/pull/pushpull lower to
  ONE compiled XLA collective program (``shard_map`` + ``lax.psum``) over a
  1-d device mesh, so on real hardware the reduce rides ICI without host
  round-trips.  Small keys are fused into buckets (reference:
  ``MXNET_KVSTORE_BIGARRAY_BOUND`` fusion in KVStoreNCCL).
- 2-bit gradient compression with error-feedback residual (reference:
  ``src/kvstore/gradient_compression.cc``) applies to every tier's push.
- int8/fp8 blockwise gradient compression (``mxnet_tpu.quantize``;
  EQuARX, PAPERS.md): on the ``'xla'`` tier quant/dequant runs INSIDE
  the jitted collective — each device quantizes its shard (+ the
  error-feedback residual), all-gathers only the 1-byte payload and
  per-block f32 scales, and accumulates in f32 — so compressed bytes
  are what actually crosses chips.  Enable per store via
  ``set_gradient_compression({'type': 'int8', ...})`` or process-wide
  via ``MXNET_KVSTORE_GRAD_COMPRESSION``.  ``kvstore.wire.bytes``
  counts interconnect traffic next to the logical
  ``kvstore.push.bytes``; their ratio is the live compression factor.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError, get_env
from ..context import cpu
from .. import faults as _faults
from ..ndarray import NDArray
from .. import optimizer as opt
from .. import quantize as qz
from .. import runtime_metrics as _rm
from .base import KVStoreBase

__all__ = ["KVStore", "create"]


from ..util import as_list as _as_list


def _nd_bytes(vals) -> int:
    """LOGICAL payload size of a list of NDArrays (shape x itemsize;
    sparse and exotic values count 0 rather than densifying just to be
    measured).  This is the application-level gradient volume feeding
    ``kvstore.push.bytes`` / ``kvstore.pull.bytes`` — NOT wire traffic:
    under gradient compression the interconnect moves the (smaller)
    compressed representation, counted by ``kvstore.wire.bytes``
    (docs/observability.md)."""
    total = 0
    for v in vals:
        try:
            n = 1
            for s in v.shape:
                n *= int(s)
            total += n * np.dtype(v.dtype).itemsize
        except Exception:   # noqa: BLE001
            pass
    return total


def _normalize(key, value):
    """-> list of (str_key, [NDArray per device]) pairs."""
    keys = _as_list(key)
    if len(keys) == 1 and not (isinstance(value, (list, tuple))
                               and value and isinstance(value[0],
                                                        (list, tuple))):
        vals = [_as_list(value)]
    else:
        vals = [_as_list(v) for v in value]
    if len(keys) != len(vals):
        raise MXNetError(
            f"kvstore: {len(keys)} keys but {len(vals)} value lists")
    return [(str(k), list(v)) for k, v in zip(keys, vals)]


class _TwoBitCompressor:
    """2-bit sign compression with error feedback
    (reference: gradient_compression.cc)."""

    def __init__(self, threshold=0.5):
        self.threshold = float(threshold)
        self._residual = {}

    def compress(self, key, idx, grad_data):
        thr = self.threshold
        res = self._residual.get((key, idx))
        if res is None:
            res = jnp.zeros_like(grad_data)
        g = grad_data + res
        q = jnp.where(g >= thr, thr, 0.0) + jnp.where(g <= -thr, -thr, 0.0)
        q = q.astype(grad_data.dtype)
        self._residual[(key, idx)] = g - q
        return q

    def wire_bytes(self, vals) -> int:
        # host-side sign simulation: nothing compressed actually
        # crosses a wire here, so account the logical volume
        return _nd_bytes(vals)


class _QuantCompressor:
    """int8/fp8 blockwise compression (``mxnet_tpu.quantize``) for the
    per-key host tiers: a quantize -> dequantize round trip with an
    error-feedback residual per (key, device copy) — the value-level
    twin of the fused in-collective path the ``'xla'`` tier runs."""

    def __init__(self, spec: qz.CompressionSpec):
        self.spec = spec
        self._residual = {}
        self._step = 0          # stochastic-rounding key stream

    def compress(self, key, idx, grad_data):
        spec = self.spec
        res = self._residual.get((key, idx))
        if res is None or res.shape != grad_data.shape:
            res = jnp.zeros(grad_data.shape, jnp.float32)
        rkey = None
        if spec.stochastic:
            self._step += 1
            rkey = jax.random.fold_in(
                jax.random.PRNGKey(self._step), idx)
        payload, scales, new_res = qz.quantize_with_feedback(
            grad_data, res, spec, key=rkey)
        self._residual[(key, idx)] = new_res
        return qz.dequantize(payload, scales, grad_data.shape,
                             grad_data.dtype)

    def wire_bytes(self, vals) -> int:
        total = 0
        for v in vals:
            n = 1
            for s in v.shape:
                n *= int(s)
            total += qz.wire_bytes(n, self.spec)
        return total


class KVStore(KVStoreBase):
    """Classic imperative API: init / push / pull / pushpull.

    Subclasses supply ``_reduce`` (aggregate per-device copies) — everything
    else (storage, updater, compression, broadcast) is shared.
    """

    CAPABILITIES = (KVStoreBase.OPTIMIZER,)

    def __init__(self):
        self._store: "OrderedDict[str, NDArray]" = OrderedDict()
        self._updater = None
        self._optimizer = None
        self._compressor = None

    # ------------------------------------------------------------ identity
    @property
    def type(self):
        return self._TYPE

    # ---------------------------------------------------------------- init
    def init(self, key, value):
        for k, vals in _normalize(key, value):
            if k in self._store:
                raise MXNetError(f"kvstore: key {k!r} already initialized")
            self._store[k] = self._pin(vals[0])

    def _pin(self, value: NDArray) -> NDArray:
        """Where the master copy of a key lives ('local': host cpu).

        Always a fresh NDArray wrapper: ``as_in_context`` returns ``self``
        for a same-context value, and aliasing the caller's array would let
        pushes overwrite live weights.
        """
        return value.as_in_context(cpu(0)).copy()

    # ---------------------------------------------------------------- push
    def push(self, key, value, priority=0):
        _faults.inject("kvstore.push")
        for k, vals in _normalize(key, value):
            if _rm._ENABLED:
                _rm.KV_PUSH.inc()
                _rm.KV_PUSH_BYTES.inc(_nd_bytes(vals))
                self._count_wire(vals)
            self._push_one(k, vals)

    def _count_wire(self, vals):
        """Wire-traffic accounting for one push: logical bytes when
        uncompressed, the compressed representation's size under
        gradient compression.  The 'xla' tier overrides this — its
        fused collective accounts per bucket instead."""
        if self._compressor is not None:
            _rm.KV_WIRE_BYTES.inc(self._compressor.wire_bytes(vals))
        else:
            _rm.KV_WIRE_BYTES.inc(_nd_bytes(vals))

    def _push_one(self, k, vals):
        if k not in self._store:
            raise MXNetError(f"kvstore: push to uninitialized key {k!r}")
        vals = self._maybe_compress(k, vals)
        merged = self._reduce(k, vals)
        stored = self._store[k]
        if self._updater is not None:
            self._updater(int(k) if k.isdigit() else k,
                          merged.as_in_context(stored.context), stored)
        else:
            stored._set_data(merged.as_in_context(stored.context)._data
                             .astype(stored._data.dtype))

    def _maybe_compress(self, k, vals):
        if self._compressor is None:
            return vals
        return [NDArray(self._compressor.compress(k, i, v._data),
                        ctx=v.context) for i, v in enumerate(vals)]

    # ---------------------------------------------------------------- pull
    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        _faults.inject("kvstore.pull")
        if out is None:
            raise MXNetError("kvstore.pull requires out=")
        for k, outs in _normalize(key, out):
            if k not in self._store:
                raise MXNetError(f"kvstore: pull of uninitialized key {k!r}")
            stored = self._store[k]
            if _rm._ENABLED:
                _rm.KV_PULL.inc()
                _rm.KV_PULL_BYTES.inc(_nd_bytes(outs))
            for o in outs:
                stored.copyto(o)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        # dense framework storage: row_ids select rows of the dense value
        if out is None or row_ids is None:
            raise MXNetError("row_sparse_pull requires out= and row_ids=")
        for (k, outs), rids in zip(_normalize(key, out),
                                   _normalize(key, row_ids)):
            stored = self._store[k]
            for o, r in zip(outs, rids):
                rows = jnp.take(stored._data, r._data.astype(jnp.int32),
                                axis=0)
                o._set_data(jax.device_put(
                    rows.astype(o._data.dtype),
                    o.context.jax_device()))

    # ------------------------------------------------------------ pushpull
    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out=out, priority=priority)

    def broadcast(self, key, value, out, priority=0):
        self.init(key, value)
        self.pull(key, out=out, priority=priority)

    # ------------------------------------------------------------ optimizer
    def set_optimizer(self, optimizer):
        if not self.is_capable(KVStoreBase.OPTIMIZER):
            raise MXNetError(
                f"kvstore type {self.type!r} cannot run the optimizer "
                f"(update_on_kvstore unsupported)")
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """Enable gradient compression on every subsequent push.

        - ``{'type': '2bit', 'threshold': t}`` — reference sign
          compression with error feedback (host-side simulation);
        - ``{'type': 'int8'|'fp8', 'block': ..., 'stochastic': ...,
          'error_feedback': ...}`` — blockwise quantization
          (``mxnet_tpu.quantize.CompressionSpec``); also accepted as a
          spec string (``'int8:block=64'``) or a ``CompressionSpec``.
          On the ``'xla'`` tier quant/dequant runs inside the jitted
          collective, so only compressed payloads cross chips.
        """
        if compression_params is None:
            self._compressor = None         # disable (e.g. override an
            return                          # env-default compression)
        if isinstance(compression_params, qz.CompressionSpec):
            self._compressor = _QuantCompressor(compression_params)
            return
        if isinstance(compression_params, str):
            spec = qz.CompressionSpec.parse(compression_params)
            self._compressor = None if spec is None \
                else _QuantCompressor(spec)
            return
        params = dict(compression_params)
        ctype = params.pop("type", "2bit")
        if ctype == "2bit":
            self._compressor = _TwoBitCompressor(
                params.pop("threshold", 0.5))
            if params:
                raise MXNetError(f"unknown compression params {params}")
            return
        if ctype in ("int8", "fp8"):
            self._compressor = _QuantCompressor(
                qz.CompressionSpec.parse(dict(params, type=ctype)))
            return
        raise MXNetError(f"unsupported compression type {ctype!r}")

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    # ------------------------------------------------------------- reduce
    def _reduce(self, k, vals) -> NDArray:
        raise NotImplementedError


@KVStoreBase.register
class Local(KVStore):
    """Reduce on host CPU (reference: KVStoreLocal / CommCPU)."""

    _TYPE = "local"

    def _reduce(self, k, vals):
        dev = cpu(0).jax_device()
        acc = jax.device_put(vals[0]._data, dev)
        for v in vals[1:]:
            acc = acc + jax.device_put(v._data, dev)
        return NDArray(acc, ctx=cpu(0))


@KVStoreBase.register
class Device(KVStore):
    """Reduce on the first value's device (reference: CommDevice P2P)."""

    _TYPE = "device"

    def _pin(self, value):
        return value.copy()

    def _reduce(self, k, vals):
        dev = vals[0]._data.device
        acc = vals[0]._data
        for v in vals[1:]:
            acc = acc + jax.device_put(v._data, dev)
        return NDArray(acc, ctx=vals[0].context)


@KVStoreBase.register
class XLA(KVStore):
    """Allreduce as one compiled XLA collective over the device mesh.

    The north-star ``kvstore('xla')`` tier (SURVEY §5.8): per-device copies
    are assembled into a sharded global array (zero copies — shards stay on
    their devices), a cached ``jit(shard_map(psum))`` program reduces over
    the 'dev' axis on ICI, and the replicated result is read back from
    per-device shards.  Keys smaller than MXNET_KVSTORE_BIGARRAY_BOUND are
    fused into one bucket per dtype (reference: NCCL small-grad fusion).
    """

    _TYPE = "xla"
    CAPABILITIES = ()

    def __init__(self):
        super().__init__()
        self._fn_cache = {}
        self._mesh_cache = {}
        # error-feedback residuals of the quantized fused collective,
        # keyed by (dtype, bucket key tuple, total): one per-device
        # rounding-error vector per bucket, sharded over the mesh
        self._ef_residuals = {}
        self._quant_step = 0
        self.bigarray_bound = int(get_env("MXNET_KVSTORE_BIGARRAY_BOUND",
                                          1 << 19))

    def _pin(self, value):
        return value.copy()

    def _count_wire(self, vals):
        # the fused collective accounts wire bytes per bucket (it knows
        # what actually crosses); counting here too would double it
        pass

    def _maybe_compress(self, k, vals):
        # int8/fp8 compression happens INSIDE the fused collective —
        # the host-side value round trip would quantize twice.  Every
        # multi-copy reduce on this tier (classic push/_push_one
        # included) lands in _fused_allreduce, which applies the quant
        # spec there; a single-copy key skips both, correctly — it has
        # no interconnect hop to compress (and set_optimizer is
        # rejected on this tier, so the updater fallback is
        # unreachable).
        if isinstance(self._compressor, _QuantCompressor):
            return vals
        return super()._maybe_compress(k, vals)

    # single-key reduce (used by push when called per key)
    def _reduce(self, k, vals):
        if len(vals) == 1:
            return vals[0]
        reduced = self._fused_allreduce([(k, vals)])
        return reduced[k][0]

    def pushpull(self, key, value, out=None, priority=0):
        """Batched fused path: aggregates ALL keys in as few collective
        launches as possible, then writes results straight into ``out``
        shards (no master-copy round trip)."""
        pairs = _normalize(key, value)
        for k, _ in pairs:
            if k not in self._store:
                raise MXNetError(
                    f"kvstore: push to uninitialized key {k!r}")
        if any(len(v) == 1 for _, v in pairs) or self._updater is not None \
                or isinstance(self._compressor, _TwoBitCompressor):
            # degenerate / host-compressed path: classic push+pull via
            # the store (which carries its own push/pull accounting
            # and fault sites); int8/fp8 quantization stays ON the
            # fused path below — it runs inside the jitted collective
            return super().pushpull(key, value, out, priority)
        # the fused XLA collective call site: a chaos plan kills or
        # stalls the whole bucketed allreduce launch here
        _faults.inject("kvstore.pushpull")
        if _rm._ENABLED:
            for _k, vals in pairs:
                _rm.KV_PUSH.inc()
                _rm.KV_PUSH_BYTES.inc(_nd_bytes(vals))
        reduced = self._fused_allreduce(pairs)
        for k, _ in pairs:
            per_dev = reduced[k]
            self._store[k]._set_data(
                per_dev[0]._data.astype(self._store[k]._data.dtype))
        if out is not None:
            for k, outs in _normalize(key, out):
                per_dev = reduced[k]
                if _rm._ENABLED:
                    _rm.KV_PULL.inc()
                    _rm.KV_PULL_BYTES.inc(_nd_bytes(outs))
                for o, r in zip(outs, per_dev):
                    o._set_data(r._data.astype(o._data.dtype))

    # ------------------------------------------------------------ internals
    def _sharding(self, devices):
        """Cached (mesh, input sharding) per device tuple — Mesh
        construction is host-side work that must stay off the step path."""
        cached = self._mesh_cache.get(devices)
        if cached is None:
            mesh = Mesh(np.array(devices), ("dev",))
            cached = (mesh, NamedSharding(mesh, P("dev")))
            self._mesh_cache[devices] = cached
        return cached

    def _allreduce_fn(self, devices, size, dtype):
        cache_key = (devices, size, dtype)
        fn = self._fn_cache.get(cache_key)
        if fn is None:
            mesh, _ = self._sharding(devices)
            body = shard_map(lambda x: lax.psum(x, "dev"), mesh=mesh,
                             in_specs=P("dev"), out_specs=P())
            fn = jax.jit(body,
                         out_shardings=NamedSharding(mesh, P()))
            self._fn_cache[cache_key] = fn
        return fn

    def _quant_allreduce_fn(self, devices, size, dtype, spec):
        """ONE compiled program per (topology, bucket, dtype, spec):
        error-feedback quantize + all-gather of the compressed payload
        + f32 dequant-accumulate, all inside the jitted shard_map body
        so XLA fuses quant/dequant into the collective and only
        compressed bytes cross chips."""
        cache_key = ("quant", devices, size, dtype, spec.key())
        fn = self._fn_cache.get(cache_key)
        if fn is None:
            mesh, _ = self._sharding(devices)
            if spec.stochastic:
                def body(x, res, k):
                    rkey = jax.random.fold_in(k, lax.axis_index("dev"))
                    return qz.allreduce_sum(x, res, spec, "dev",
                                            key=rkey)
                in_specs = (P("dev"), P("dev"), P())
            else:
                def body(x, res):
                    return qz.allreduce_sum(x, res, spec, "dev")
                in_specs = (P("dev"), P("dev"))
            # out_specs P("dev") for the sum too: every device returns
            # its own (identical, via the symmetric all_gather) copy,
            # which sidesteps shard_map's static replication check and
            # hands back exactly the per-device layout the shard
            # splitter reads (addressable_shards[d] = full sum)
            sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=(P("dev"), P("dev")))
            fn = jax.jit(sm, out_shardings=(
                NamedSharding(mesh, P("dev")),
                NamedSharding(mesh, P("dev"))))
            self._fn_cache[cache_key] = fn
        return fn

    def _fused_allreduce(self, pairs):
        """pairs: [(key, [NDArray per device])] -> {key: [NDArray per dev]}.

        Groups keys by dtype, packs small ones into shared buckets, runs
        one psum per bucket, and splits results back out of the replicated
        per-device shards.
        """
        ndev = len(pairs[0][1])
        devices = tuple(v._data.device for v in pairs[0][1])
        if len(set(devices)) != ndev:
            raise MXNetError(
                "kvstore('xla'): per-key copies must live on distinct "
                f"devices, got {devices}")
        by_dtype = OrderedDict()
        for k, vals in pairs:
            if len(vals) != ndev:
                raise MXNetError(
                    f"kvstore('xla'): key {k!r} has {len(vals)} copies, "
                    f"expected {ndev}")
            by_dtype.setdefault(str(vals[0]._data.dtype), []).append(
                (k, vals))

        results = {}
        for dtype, group in by_dtype.items():
            buckets, cur, cur_elems = [], [], 0
            for k, vals in group:
                n = int(np.prod(vals[0].shape)) if vals[0].shape else 1
                if n >= self.bigarray_bound:
                    buckets.append([(k, vals, n)])
                    continue
                cur.append((k, vals, n))
                cur_elems += n
                if cur_elems >= self.bigarray_bound:
                    buckets.append(cur)
                    cur, cur_elems = [], 0
            if cur:
                buckets.append(cur)
            quant_spec = self._compressor.spec \
                if isinstance(self._compressor, _QuantCompressor) \
                and jnp.issubdtype(jnp.dtype(dtype), jnp.floating) \
                else None
            for bucket in buckets:
                total = sum(n for _, _, n in bucket)
                shards = []
                for d in range(ndev):
                    flats = [vals[d]._data.reshape(-1)
                             for _, vals, _ in bucket]
                    shards.append(flats[0] if len(flats) == 1
                                  else jnp.concatenate(flats))
                _, in_sharding = self._sharding(devices)
                mesh_arr = jax.make_array_from_single_device_arrays(
                    (ndev * total,), in_sharding, shards)
                if quant_spec is not None:
                    res_key = (dtype, tuple(k for k, _, _ in bucket),
                               total)
                    res = self._ef_residuals.get(res_key)
                    if res is None:
                        res = jax.device_put(
                            jnp.zeros((ndev * total,), jnp.float32),
                            in_sharding)
                    fn = self._quant_allreduce_fn(
                        devices, total, dtype, quant_spec)
                    # bucket totals are NOT request-scoped: they derive
                    # from the training job's fixed key set (one
                    # program per (topology, bucket, dtype, spec),
                    # cached in _fn_cache — same contract as the
                    # uncompressed _allreduce_fn path)
                    if quant_spec.stochastic:
                        self._quant_step += 1
                        # mxlint: disable=recompile-churn
                        out, new_res = fn(
                            mesh_arr, res,
                            jax.random.PRNGKey(self._quant_step))
                    else:
                        # mxlint: disable=recompile-churn
                        out, new_res = fn(mesh_arr, res)
                    self._ef_residuals[res_key] = new_res
                    if _rm._ENABLED:
                        _rm.KV_WIRE_BYTES.inc(
                            ndev * qz.wire_bytes(total, quant_spec))
                else:
                    out = self._allreduce_fn(devices, total,
                                             dtype)(mesh_arr)
                    if _rm._ENABLED:
                        _rm.KV_WIRE_BYTES.inc(
                            ndev * total * jnp.dtype(dtype).itemsize)
                per_dev_full = [s.data for s in out.addressable_shards]
                # addressable_shards order follows device order in mesh
                offset = 0
                for k, vals, n in bucket:
                    outs = []
                    for d in range(ndev):
                        seg = lax.dynamic_slice_in_dim(
                            per_dev_full[d], offset, n)
                        outs.append(NDArray(
                            seg.reshape(vals[d].shape),
                            ctx=vals[d].context))
                    results[k] = outs
                    offset += n
        return results


# 'nccl' scripts get the ICI tier transparently (reference: KVStoreNCCL)
KVStoreBase.register_alias("nccl", XLA)


@KVStoreBase.register
class DistSync(KVStore):
    """Multi-process synchronous tier (reference: KVStoreDist dist_sync).

    The reference runs a parameter-server control plane over DCN; here the
    process group is bootstrapped by ``parallel.dist.initialize`` (env
    protocol from tools/launch.py) and a push reduces first locally across
    this process's device copies, then across processes.  Rank/num_workers
    mirror the reference worker identity API.
    """

    _TYPE = "dist_sync"

    def __init__(self):
        super().__init__()
        from ..parallel import dist
        self._dist = dist
        dist.initialize()   # no-op when standalone / already joined

    def init(self, key, value):
        # rank 0's value is authoritative (reference: KVStoreDist —
        # server stores rank-0 init), else workers whose initial weights
        # differ would train on divergent parameters forever
        super().init(key, value)
        if self._dist.is_initialized():
            for k, _vals in _normalize(key, value):
                stored = self._store[k]
                stored._set_data(
                    self._dist.broadcast_host(stored, root=0)._data)

    @property
    def rank(self):
        return self._dist.rank() if self._dist.is_initialized() else 0

    @property
    def num_workers(self):
        return self._dist.size() if self._dist.is_initialized() else 1

    def _reduce(self, k, vals):
        # intra-process reduce (device copies) ...
        dev = cpu(0).jax_device()
        acc = jax.device_put(vals[0]._data, dev)
        for v in vals[1:]:
            acc = acc + jax.device_put(v._data, dev)
        # ... then inter-process reduce over the group
        return self._dist.allreduce_host(NDArray(acc, ctx=cpu(0)))


KVStoreBase.register_alias("dist_sync", DistSync)
KVStoreBase.register_alias("dist", DistSync)
KVStoreBase.register_alias("dist_device_sync", DistSync)


def create(name="local") -> KVStore:
    """Factory (reference: kvstore.create / KVStoreBase registry).

    ``dist_async`` (reference: KVStoreDist async push + server-side
    optimizer) is **documented-unsupported** on TPU by design, not an
    omission: asynchronous, per-key eventually-consistent updates assume
    a parameter-server topology with CPU-side optimizers.  On a TPU pod
    the same scale point is served by the synchronous ``'xla'``/
    ``'dist_sync'`` tiers, whose allreduce rides ICI/DCN collectives
    inside the compiled step — faster than a PS round trip, with none of
    the staleness.  Use ``'dist_sync'`` (or raw
    ``parallel.ShardedTrainer`` over a multi-host mesh).
    """
    if not isinstance(name, str):
        raise MXNetError("kvstore name must be a string")
    if name.lower() in ("dist_async", "dist_device_async"):
        raise MXNetError(
            f"kvstore type {name!r} is intentionally unsupported on this "
            f"framework: asynchronous parameter-server SGD assumes "
            f"CPU-side per-key optimizers and tolerates gradient "
            f"staleness; on TPU the synchronous 'xla'/'dist_sync' tiers "
            f"(ICI/DCN allreduce compiled into the step) cover the same "
            f"scale without staleness.  Use 'dist_sync' instead.  See "
            f"kvstore.create.__doc__.")
    klass = KVStoreBase.kv_registry.get(name.lower())
    if klass is None:
        raise MXNetError(
            f"unknown kvstore type {name!r}; registered: "
            f"{sorted(KVStoreBase.kv_registry)}")
    store = klass()
    # process-wide default gradient compression: every created store
    # starts compressed (set_gradient_compression still overrides)
    env_spec = qz.CompressionSpec.from_env()
    if env_spec is not None:
        store.set_gradient_compression(env_spec)
    return store
