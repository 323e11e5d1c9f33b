"""Foundation utilities: errors, registries, environment knobs.

TPU-native re-design of the roles played by ``dmlc-core`` in the reference
(``3rdparty/dmlc-core`` -> ``dmlc::Registry``, ``dmlc::GetEnv``, ``LOG/CHECK``)
and ``python/mxnet/base.py`` (error marshalling).  There is no C ABI boundary
for Python-level errors here -- exceptions propagate natively -- but the
public surface (``MXNetError``, registries, env-var config) matches the
reference semantics.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "MXNetError",
    "NotImplementedForSymbol",
    "Registry",
    "declare_deterministic",
    "entropy_rng",
    "get_env",
    "env_truthy",
    "list_deterministic",
    "string_types",
    "numeric_types",
    "integer_types",
]

logging.basicConfig()
_LOGGER = logging.getLogger("mxnet_tpu")

string_types = (str,)
numeric_types = (float, int)
integer_types = (int,)


class MXNetError(RuntimeError):
    """Default error type raised by the framework.

    Mirrors ``mxnet.base.MXNetError`` (reference: python/mxnet/base.py).
    In the reference this wraps errors marshalled across the C ABI via
    ``MXGetLastError``; here it is raised directly.
    """


class NotImplementedForSymbol(MXNetError):
    """Raised when an NDArray-only operation is attempted on a Symbol."""

    def __init__(self, function, alias=None, *args):
        super().__init__()
        self.function = function.__name__ if callable(function) else str(function)
        self.alias = alias
        self.args_ = [str(type(a)) for a in args]

    def __str__(self):
        msg = f"Function {self.function}"
        if self.alias:
            msg += f" (alias {self.alias})"
        if self.args_:
            msg += " with arguments (" + ",".join(self.args_) + ")"
        msg += " is not supported for Symbol and only available in NDArray."
        return msg


class Registry:
    """Generic name -> object registry.

    TPU-native equivalent of ``dmlc::Registry<T>`` (reference:
    3rdparty/dmlc-core/include/dmlc/registry.h), which backs the op registry,
    data-iterator registry, kvstore registry, etc. in the reference.
    """

    _registries: Dict[str, "Registry"] = {}

    def __init__(self, name: str):
        self.name = name
        self._entries: Dict[str, Any] = {}
        self._lock = threading.Lock()
        Registry._registries[name] = self

    @classmethod
    def get(cls, name: str) -> "Registry":
        if name not in cls._registries:
            Registry(name)
        return cls._registries[name]

    def register(self, name: str, obj: Any = None, override: bool = False):
        """Register ``obj`` under ``name``; usable as a decorator."""
        if obj is None:
            def _decorator(fn):
                self.register(name, fn, override=override)
                return fn
            return _decorator
        with self._lock:
            if name in self._entries and not override:
                raise MXNetError(
                    f"'{name}' already registered in registry '{self.name}'")
            self._entries[name] = obj
        return obj

    def find(self, name: str) -> Optional[Any]:
        return self._entries.get(name)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __getitem__(self, name: str) -> Any:
        if name not in self._entries:
            raise MXNetError(
                f"'{name}' is not registered in registry '{self.name}'. "
                f"Known: {sorted(self._entries)[:20]}...")
        return self._entries[name]

    def list_names(self) -> List[str]:
        return sorted(self._entries)

    def items(self):
        return self._entries.items()


# ---------------------------------------------------------------------------
# Environment knob registry.
#
# The reference scatters ~100 `dmlc::GetEnv` calls across use sites (SURVEY.md
# 5.6); here every knob is declared once so `mxnet_tpu.util.list_env_vars()`
# can document them all.
# ---------------------------------------------------------------------------
_ENV_REGISTRY: Dict[str, tuple] = {}


def declare_env(name: str, default, doc: str = ""):
    _ENV_REGISTRY[name] = (default, doc)
    return name


def list_env_vars() -> Dict[str, tuple]:
    return dict(_ENV_REGISTRY)


def get_env(name: str, default=None, typ: Callable = None):
    """Read an environment knob (equivalent of ``dmlc::GetEnv``)."""
    if name in _ENV_REGISTRY and default is None:
        default = _ENV_REGISTRY[name][0]
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is None and default is not None:
        typ = type(default)
    if typ is bool:
        return raw not in ("0", "false", "False", "")
    return typ(raw) if typ else raw


def env_truthy(name: str, default: bool = False) -> bool:
    return get_env(name, default, bool)


# ---------------------------------------------------------------------------
# Deterministic-surface registry.
#
# Every headline guarantee this repro ships is a determinism contract:
# byte-identical trace generation/replay summaries, bit-exact
# checkpoint resume, seeded fault plans, unbiased-but-seeded stochastic
# quantization.  Each such surface is declared ONCE here (pure strings
# — zero runtime coupling to the modules they name) and mxlint's
# determinism-soundness pass statically verifies that no unseeded or
# ambient entropy source (global `random` state, module-level
# `np.random` draws, wall-clock-seeded RNGs, uuid4, os.urandom,
# builtin hash() on strings, unordered set iteration) is reachable
# from any declared surface over the call graph.
# ---------------------------------------------------------------------------
_DETERMINISTIC_REGISTRY: Dict[str, str] = {}


def declare_deterministic(name: str, note: str = ""):
    """Declare ``name`` (a fully-qualified function or class path, e.g.
    ``mxnet_tpu.serving.traffic.generate_trace``; a class covers every
    method) a deterministic surface: equal inputs must yield identical
    outputs across runs.  Enforced statically by mxlint's
    determinism-soundness pass (docs/static_analysis.md §14)."""
    _DETERMINISTIC_REGISTRY[name] = note
    return name


def list_deterministic() -> Dict[str, str]:
    """{declared surface: contract note} (tools/diagnose.py reports the
    count; the mxlint pass harvests the declarations statically)."""
    return dict(_DETERMINISTIC_REGISTRY)


def entropy_rng():
    """The ONE sanctioned source of deliberate nondeterminism: a
    ``random.Random`` seeded from OS entropy.  Retry/backoff jitter
    MUST be nondeterministic (replicas retrying in lockstep re-collide
    forever), but an anonymous ``random.Random()`` at the use site is
    indistinguishable from a forgotten seed — routing through this
    helper marks the intent, and the determinism-soundness pass exempts
    exactly this function while flagging ad-hoc unseeded RNGs."""
    import random as _random
    return _random.Random(os.urandom(16))


# The contract surfaces (mxlint resolves these against the call graph;
# a name with no matching definition is simply inert, so declarations
# may precede the code they cover).
declare_deterministic(
    "mxnet_tpu.serving.traffic.generate_trace",
    "equal TraceConfigs yield byte-identical JSONL traces — one "
    "RandomState(seed) drives every draw in arrival order")
declare_deterministic(
    "mxnet_tpu.serving.traffic.replay_trace",
    "per-client backoff jitter is seeded (jitter_seed), so identical "
    "twins replaying one trace make identical retry decisions")
declare_deterministic(
    "mxnet_tpu.serving.traffic.Trace",
    "save/load round-trips bit-exact JSONL (fixed field order)")
declare_deterministic(
    "mxnet_tpu.serving.traffic.predict_payload",
    "trace rows rebuild the same payload on every replay")
declare_deterministic(
    "mxnet_tpu.serving.traffic.prompt_tokens",
    "trace rows rebuild the same prompt on every replay")
declare_deterministic(
    "mxnet_tpu.parallel.checkpoint.CheckpointManager.save",
    "bit-exact resume: what save writes, restore rebuilds")
declare_deterministic(
    "mxnet_tpu.parallel.checkpoint.CheckpointManager.restore",
    "bit-exact resume (training_resilience.md §3)")
declare_deterministic(
    "mxnet_tpu.parallel.checkpoint.save_checkpoint",
    "module-level save wrapper — same contract as CheckpointManager")
declare_deterministic(
    "mxnet_tpu.parallel.checkpoint.load_checkpoint",
    "module-level restore wrapper")
declare_deterministic(
    "mxnet_tpu.parallel.trainer.ShardedTrainer.extra_state",
    "checkpointed alongside params/opt_state; must serialize "
    "identically for identical training state")
declare_deterministic(
    "mxnet_tpu.parallel.trainer.ShardedTrainer.set_extra_state",
    "restore-side twin of extra_state")
declare_deterministic(
    "mxnet_tpu.faults.FaultPlan",
    "chaos is repeatable: per-rule RNGs are seeded from "
    "(plan seed, pattern, mode)")
declare_deterministic(
    "mxnet_tpu.quantize.quantize",
    "stochastic rounding draws from an explicit jax PRNG key — "
    "quantized parity is byte-identical given the key")
declare_deterministic(
    "mxnet_tpu.quantize.quantize_with_feedback",
    "error-feedback quantization — same key contract")
declare_deterministic(
    "mxnet_tpu.quantize.allreduce_sum",
    "quantized collective: deterministic given keys and inputs")
declare_deterministic(
    "mxnet_tpu.quantize.allreduce_mean",
    "quantized collective: deterministic given keys and inputs")
declare_deterministic(
    "benchmark.bench_traffic._run_one",
    "the frozen/scaled twins must differ ONLY in autoscaler budget — "
    "ambient entropy in the twin path voids the comparison")


# Core knobs (kept name-compatible with the reference where one exists).
declare_env("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice",
            "Execution engine: 'NaiveEngine' forces synchronous op execution "
            "(debug/bisection mode); default is async (XLA/PJRT async dispatch).")
declare_env("MXNET_SEED", None, "Global RNG seed fixed at import if set.")
declare_env("MXNET_EXEC_BULK_EXEC_TRAIN", "1",
            "Bulk-exec mode: compile the whole eager backward tape into one "
            "cached XLA program (autograd bulk replay). Set 0 to disable.")
declare_env("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", 15,
            "engine.bulk_size default when bulk-exec is on; bulk backward "
            "runs when bulk_size > 1.")
declare_env("MXNET_CACHED_OP_CACHE_SIZE", 16,
            "Max compiled programs kept per CachedOp (LRU-evicted beyond, "
            "with a churn warning); override per block via "
            "hybridize(cache_size=...).")
declare_env("MXNET_FUSED_HYBRID_STEP", "1",
            "Fuse a deferred single-CachedOp backward with the optimizer "
            "update into one donated program in Trainer.step "
            "(record/backward/step at fused-step cost); 0 = always eager.")
declare_env("MXNET_DEFERRED_HYBRID_FWD", "1",
            "Defer a hybridized training forward so Trainer.step can "
            "compile forward+backward+optimizer into ONE donated program "
            "(any output read before step materializes the standalone "
            "forward); 0 = always dispatch the forward eagerly.")
declare_env("MXNET_CACHED_OP_SAVE_POLICY", "dots_no_batch",
            "What the hybridized training forward saves for backward: "
            "all / dots / dots_no_batch / none (memory/recompute dial).")
declare_env("MXNET_FUSED_STEP_SAVE_POLICY", "auto",
            "Save policy INSIDE the one-program fused step: 'auto' "
            "(default) AOT-probes the save-everything variant's peak "
            "memory and uses it when it fits (reclaims the checkpoint "
            "recompute tax), else falls back to the CachedOp policy; "
            "or force all / dots / dots_no_batch / none / inherit.")
declare_env("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000,
            "Arrays above this many elements get their own allreduce bucket.")
declare_env("MXNET_KVSTORE_GRAD_COMPRESSION", None,
            "Process-wide default gradient compression for every created "
            "kvstore: a CompressionSpec string — 'int8' or 'fp8', "
            "optionally with options ('int8:block=64,stochastic=1,"
            "error_feedback=0').  On the 'xla' tier quant/dequant runs "
            "inside the jitted collective (only compressed payloads "
            "cross chips; kvstore.wire.bytes vs kvstore.push.bytes is "
            "the live ratio).  Unset (default) = uncompressed; "
            "set_gradient_compression() overrides per store.")
declare_env("MXNET_PROFILER_AUTOSTART", 0, "Start profiler at import.")
declare_env("MXNET_EXCEPTION_VERBOSE", 0, "Verbose async error traces.")
declare_env("MXNET_DEFAULT_DTYPE", "float32", "Default dtype for new arrays.")
declare_env("MXNET_TPU_DISABLE_NATIVE", "0",
            "1 = skip building/loading the native C++ IO library and use "
            "the pure-python RecordIO tier.")
declare_env("MXNET_ENGINE_SANITIZE", "0",
            "1 = concurrency sanitizer: engine/serving locks record "
            "per-thread acquisition order and raise MXNetError on a "
            "cross-thread lock-order inversion (potential deadlock), "
            "in-place NDArray writes assert the array is engine-tracked, "
            "and framework threads (engine.make_thread) are registered "
            "with owner+creation site so engine.check_thread_leaks() "
            "raises on any thread surviving its owner's stop (asserted "
            "at test teardown). Debug/CI knob (sanity_lint re-runs the "
            "serving+engine tests under it); off by default, zero cost "
            "when off.")
declare_env("MXNET_TEST_CTX", "cpu",
            "Context for test_utils.default_context (the reference's "
            "GPU-suite switch): 'cpu', 'tpu', ... — any mxnet_tpu.context "
            "constructor name.")
declare_env("MXNET_TEST_PJRT_PLUGIN", None,
            "Path to a PJRT plugin .so for the framework-free StableHLO "
            "runner (tools/shlo_run.py, tests/test_shlo_runner.py); the "
            "end-to-end artifact tests only run when set.")
declare_env("MXNET_RUNTIME_METRICS", "0",
            "1 = enable the process-wide runtime metrics registry "
            "(mxnet_tpu.runtime_metrics): op dispatch counters/latency, "
            "engine/io/kvstore/trainer instrumentation, Prometheus + "
            "chrome-trace + TensorBoard exporters. Off by default; the "
            "disabled path is a single flag check per site.")
declare_env("MXNET_RUNTIME_METRICS_GRAD_NORM", "0",
            "1 = also sample the global L2 gradient norm into the "
            "trainer.grad_norm gauge after each step (forces a device "
            "sync per step to read gradients; NaN/blowup debugging aid).")
declare_env("MXNET_TRACE", "0",
            "1 = enable the request span tracer (mxnet_tpu.tracing): "
            "every serving request gets a trace-id/span-id timeline "
            "(admission, queue wait, batch assembly, execute, prefill, "
            "decode steps, eviction) exportable as chrome-trace/JSONL, "
            "with histogram exemplars linking Prometheus quantiles to "
            "traces and the flight recorder dumping recent traces on "
            "overload incidents. Off by default; the disabled path is "
            "a single flag check per site and compiles zero additional "
            "XLA programs.")
declare_env("MXNET_TRACE_SAMPLE", 1.0,
            "Head-based trace sampling rate in [0, 1]: the keep/drop "
            "decision is made once per request at root-span start "
            "(deterministic stride, so 0.25 keeps exactly every 4th "
            "trace). 1.0 = trace everything (default).")
declare_env("MXNET_TRACE_RING", 64,
            "Completed traces retained by the flight-recorder ring "
            "(mxnet_tpu.tracing) — always the most recent N; older "
            "traces are evicted in completion order.")
declare_env("MXNET_SERVING_MAX_BATCH", 8,
            "Serving: max rows coalesced into one dispatched batch "
            "(mxnet_tpu.serving.DynamicBatcher); shape buckets are "
            "powers of two up to this cap, so at most "
            "ceil(log2(max_batch))+1 programs compile per model "
            "signature.")
declare_env("MXNET_SERVING_MAX_LATENCY_US", 2000,
            "Serving: how long the batcher holds the FIRST request of a "
            "forming batch waiting for more work before dispatching a "
            "partial batch (microseconds; the latency half of the "
            "batching policy).")
declare_env("MXNET_SERVING_QUEUE_DEPTH", 128,
            "Serving: bound on total outstanding work per ModelServer "
            "(queued + dispatched-but-unfinished requests); admission "
            "sheds at it even below the queue-only shed watermark.")
declare_env("MXNET_SERVING_SHED_WATERMARK", None,
            "Serving: queue depth at/above which new requests are shed "
            "with ServerOverloadedError(retry_after_ms) instead of "
            "queued (load-shedding watermark; default: the full queue "
            "capacity MXNET_SERVING_QUEUE_DEPTH).")
declare_env("MXNET_SERVING_WORKERS", 1,
            "Serving: dispatch worker threads per ModelServer (each "
            "forms and executes whole batches; >1 overlaps host "
            "pre/post-processing with device execution).")
declare_env("MXNET_SERVING_RETRY_AFTER_MS", 50,
            "Serving: retry-after hint (milliseconds) attached to "
            "ServerOverloadedError when a request is shed.")
declare_env("MXNET_SERVING_DECODE_PAGE_SIZE", 16,
            "Decode engine: tokens per KV-cache page "
            "(mxnet_tpu.serving.kv_cache). Smaller pages waste less "
            "HBM on short sequences but deepen the per-sequence block "
            "table; the ragged-paged-attention kernel reads one page "
            "per grid step.")
declare_env("MXNET_SERVING_DECODE_POOL_PAGES", 64,
            "Decode engine: TOTAL pages preallocated in the device KV "
            "pool, including the reserved null page 0 (usable pages = "
            "pool - 1). Pool bytes = 2 * layers * pages * page_size * "
            "heads * head_dim * dtype_size.")
declare_env("MXNET_SERVING_DECODE_MAX_BATCH", 4,
            "Decode engine: sequence slots in the fixed-shape decode "
            "step (token-level continuous batching admits/evicts into "
            "these slots every step). ONE decode program compiles for "
            "this batch size regardless of traffic mix.")
declare_env("MXNET_SERVING_DECODE_MAX_NEW_TOKENS", 32,
            "Decode engine: default cap on generated tokens per "
            "request (generate(max_new_tokens=...) overrides, bounded "
            "by the model's max_context).")
declare_env("MXNET_SERVING_PREFIX_CACHE", "0",
            "Decode engine: enable copy-on-write prefix caching "
            "(docs/serving.md §9) — full prompt pages are "
            "content-addressed in a radix tree, a request whose prefix "
            "is cached aliases the shared (refcounted) KV pages and "
            "skips that prefill; the one page it appends into is "
            "copy-on-write duplicated.  Lookup failures degrade to a "
            "plain prefill.")
declare_env("MXNET_SERVING_PREFIX_CACHE_PAGES", 0,
            "Decode engine: cap on KV pages the prefix cache may hold "
            "(refcount-aware LRU evicts beyond it; cache-only pages "
            "are also evicted on demand when admission needs the free "
            "list).  0 (default) = bounded by the pool alone.")
declare_env("MXNET_SERVING_SPEC_K", 0,
            "Decode engine: speculative-decoding proposal depth — the "
            "draft model proposes up to k tokens per sequence per "
            "round and the target verifies all k+1 positions in ONE "
            "program call (greedy acceptance is exact, so outputs are "
            "byte-identical with speculation on or off).  0 (default) "
            "disables; requires a draft model "
            "(add_decoder(draft=...) or MXNET_SERVING_SPEC_DRAFT).")
declare_env("MXNET_SERVING_SPEC_DRAFT", None,
            "Decode engine: repository model name whose decode model "
            "serves as the DEFAULT speculative-decoding draft for "
            "decoder entries registered without an explicit "
            "add_decoder(draft=...).  The named entry must be "
            "registered before the first generate() call resolves it.")
declare_env("MXNET_SERVING_DEADLINE_DEFAULT", None,
            "Serving: default end-to-end deadline (seconds, float) for "
            "predict()/generate() calls that pass no timeout.  The "
            "timeout is an absolute deadline carried through admission "
            "-> queue -> batch assembly -> execute: expired requests "
            "are cancelled BEFORE consuming a batch slot and fail with "
            "DeadlineExceededError.  Unset (default) = no deadline.")
declare_env("MXNET_SERVING_RETRY_MAX", 2,
            "Serving: max re-executions of a TRANSIENT failure "
            "(exc.transient truthy, e.g. an injected execute fault) "
            "per coalesced batch / decode model call, with jittered "
            "exponential backoff.  0 disables retries.")
declare_env("MXNET_SERVING_RETRY_BACKOFF_MS", 10,
            "Serving: base of the jittered exponential retry backoff "
            "(sleep ~ backoff * 2^attempt * U[0.5,1.0) milliseconds "
            "between transient-failure retries).")
declare_env("MXNET_SERVING_CIRCUIT_WINDOW", 20,
            "Serving circuit breaker: sliding window of the last N "
            "execute outcomes per model version; the breaker can only "
            "trip once the window is full (doubling as the min-samples "
            "guard).  0 disables the breaker.")
declare_env("MXNET_SERVING_CIRCUIT_THRESHOLD", 0.5,
            "Serving circuit breaker: error rate over the full sliding "
            "window at/above which the circuit OPENs (admissions shed "
            "instantly with CircuitOpenError + retry-after until the "
            "cooldown's half-open probe).")
declare_env("MXNET_SERVING_CIRCUIT_COOLDOWN_MS", 1000,
            "Serving circuit breaker: how long an OPEN circuit sheds "
            "before admitting ONE half-open probe request (probe "
            "success re-closes, failure re-opens).")
declare_env("MXNET_SERVING_REPLICAS", 1,
            "Serving: number of replicas per model version "
            "(mxnet_tpu.serving.replica, docs/serving.md §10).  With "
            "N > 1 the server builds a ReplicaSet — N data-parallel "
            "replicas on disjoint device groups of the mesh, each with "
            "its own program cache / decode engine / KV pool — and "
            "routes least-loaded among HEALTHY replicas; a failed "
            "replica's requests fail over to siblings under their "
            "original deadlines.  1 (default) = the single-replica "
            "path, byte-identical to pre-replica behavior.")
declare_env("MXNET_SERVING_REPLICA_HEARTBEAT_MS", 50,
            "Serving replicas: heartbeat interval per replica worker "
            "(milliseconds).  Each replica's heartbeat thread beats, "
            "then sweeps the whole set for stale siblings, so a "
            "stalled replica is detected by its peers even with zero "
            "traffic.")
declare_env("MXNET_SERVING_REPLICA_HEARTBEAT_WINDOW_MS", 500,
            "Serving replicas: a replica whose last heartbeat is older "
            "than this window is marked UNHEALTHY (unroutable) until "
            "beats resume AND it re-passes prewarm (the rolling-"
            "recovery gate: a rejoining replica never serves a cold "
            "program).")
declare_env("MXNET_SERVING_REPLICA_FAILURE_THRESHOLD", 3,
            "Serving replicas: consecutive typed execute failures that "
            "trip one replica's circuit breaker (UNHEALTHY, sheds to "
            "siblings) without waiting for the sliding error-rate "
            "window to fill — the dead-replica fast path.  After "
            "MXNET_SERVING_CIRCUIT_COOLDOWN_MS one probe request may "
            "re-close it.  0 = windowed error rate only.")
declare_env("MXNET_SERVING_TENANT_TIERS", None,
            "Tiered admission (mxnet_tpu.serving.admission, "
            "docs/serving.md §11): 'name=priority[/quota_rps[/burst]]' "
            "comma-separated, e.g. 'gold=100,silver=10/20,free=1/5'. "
            "Higher priority survives overload longer (low tiers "
            "priority-shed first); quota_rps meters each tenant "
            "through a token bucket of capacity burst.  Unset "
            "(default) = admission gate off (every request rides the "
            "watermark shed alone).")
declare_env("MXNET_SERVING_ADMISSION_SHED_START", 0.5,
            "Overload pressure (0..1 — the serving queue fraction, "
            "max'd with the autoscaler's published SLO pressure) at "
            "which the LOWEST tenant tier starts shedding; tiers "
            "above it shed at evenly spaced higher thresholds and the "
            "top tier only at full pressure.")
declare_env("MXNET_SERVING_AUTOSCALE_MIN", 1,
            "Autoscaler floor on replicas per model "
            "(mxnet_tpu.serving.autoscaler, docs/serving.md §11); "
            "scale-down never drains below it.")
declare_env("MXNET_SERVING_AUTOSCALE_MAX", 4,
            "Autoscaler ceiling on replicas per model (the "
            "max-replica budget) — a sustained breach at the ceiling "
            "is counted as a 'blocked' decision, not actuated.")
declare_env("MXNET_SERVING_AUTOSCALE_INTERVAL_MS", 200,
            "Autoscaler control period: one sense -> decide -> "
            "actuate tick per interval (milliseconds).")
declare_env("MXNET_SERVING_AUTOSCALE_BREACH_TICKS", 3,
            "Scale-up hysteresis: consecutive SLO-breach ticks before "
            "adding a replica, MINUS the ticks the measured prewarm "
            "time will consume (prewarm-aware lead — capacity must "
            "start building before the window ends; floor 1).")
declare_env("MXNET_SERVING_AUTOSCALE_IDLE_TICKS", 10,
            "Scale-down hysteresis: consecutive idle ticks (queue "
            "under the low band AND latencies under the scale-down "
            "margin of their SLOs) before draining a replica.")
declare_env("MXNET_SERVING_AUTOSCALE_COOLDOWN_UP_MS", 1000,
            "Refractory period after a scale-up (or a failed "
            "actuation) before the next scale-up — one burst must not "
            "staircase the fleet to the ceiling.")
declare_env("MXNET_SERVING_AUTOSCALE_COOLDOWN_DOWN_MS", 5000,
            "Refractory period after ANY replica-count change before "
            "a scale-down — capacity just added (or a just-survived "
            "burst) must prove itself idle first.")
declare_env("MXNET_SERVING_AUTOSCALE_PREWARM_LEAD_MS", 0,
            "Initial estimate of one add_replica prewarm "
            "(milliseconds) for the prewarm-aware scale-up lead; "
            "refined at runtime by an EWMA of measured prewarms.  "
            "0 (default) = no lead until the first measured add.")
declare_env("MXNET_SERVING_AUTOSCALE_SLO_TTFT_P99_MS", None,
            "Declared SLO target: windowed p99 time-to-first-token "
            "(serving.decode.ttft.seconds) above this breaches and "
            "counts toward scale-up.  Unset (default) = TTFT not "
            "targeted.")
declare_env("MXNET_SERVING_AUTOSCALE_SLO_LATENCY_P99_MS", None,
            "Declared SLO target: windowed p99 end-to-end predict "
            "latency (serving.request.seconds) above this breaches "
            "and counts toward scale-up.  Unset (default) = latency "
            "not targeted.")
declare_env("MXNET_SERVING_AUTOSCALE_QUEUE_HIGH", None,
            "Declared SLO target: serving.queue.depth at/above this "
            "breaches (saturation shows in the queue before the "
            "latency histograms move); the scale-down band defaults "
            "to a quarter of it.  Unset (default) = queue not "
            "targeted.")
declare_env("MXNET_SERVING_TRACE_SEED", 0,
            "Workload-trace generator seed "
            "(mxnet_tpu.serving.traffic.TraceConfig): one RandomState "
            "drives every draw, so equal configs yield byte-identical "
            "JSONL traces.")
declare_env("MXNET_SERVING_TRACE_RATE", 20.0,
            "Workload-trace base arrival rate (requests/s) before the "
            "diurnal ramp and burst multipliers.")
declare_env("MXNET_SERVING_TRACE_SPEED", 1.0,
            "Trace-replay time compression "
            "(serving.traffic.replay_trace): 2.0 plays an 8s trace in "
            "4s wall time; the recorded timeline itself is unchanged.")
declare_env("MXNET_FAULTS", None,
            "Deterministic fault-injection plan for chaos testing "
            "(mxnet_tpu.faults): 'site=mode[,k=v...][;...]' with mode "
            "in fail|delay|corrupt|stall and keys p/after/times/ms/"
            "seed, e.g. 'serving.execute=fail,p=0.05,seed=7'.  Sites "
            "thread through deploy, compile_cache, the serving "
            "batcher, the decode engine, the KV page allocator, and "
            "the replica layer (replica.<rid>.{execute,heartbeat,"
            "decode.*} — kill/stall one replica by id, or every "
            "replica via the replica.* glob).  Training-plane sites: "
            "train.step, train.data.next, kvstore.push, kvstore.pull, "
            "kvstore.pushpull (the fused XLA collective), "
            "checkpoint.save (corrupt = bit-flip a saved payload), "
            "checkpoint.restore.  Unset (default) = "
            "injection off at zero cost.")
declare_env("MXNET_TRAIN_STEP_TIMEOUT_MS", 0,
            "Deadline on one ShardedTrainer.step(): the compiled step "
            "(dispatch + completion) runs on a watchdog thread and a "
            "wedged collective raises TrainStepTimeoutError instead "
            "of hanging the train loop (docs/training_resilience.md). "
            "0 (default) = no deadline, direct in-thread dispatch.")
declare_env("MXNET_TRAIN_SLOW_STEP_FACTOR", 0.0,
            "Straggler detection: a step slower than this multiple of "
            "the rolling median step time increments "
            "train.slow_steps and dumps a flight-recorder incident. "
            "0 (default) = off.")
declare_env("MXNET_TRAIN_MAX_RESTARTS", 5,
            "TrainingSupervisor crash-loop breaker: more than this "
            "many CONSECUTIVE restore+restart cycles without a "
            "completed step raises CrashLoopError instead of "
            "retrying forever (progress resets the run).")
declare_env("MXNET_TRAIN_RESTART_BACKOFF_MS", 100,
            "Base of the TrainingSupervisor's jittered exponential "
            "restart backoff (doubles per consecutive failure, "
            "jitter U[0.5, 1.0)).")
declare_env("MXNET_TRAIN_RESTART_BACKOFF_MAX_MS", 5000,
            "Cap on one TrainingSupervisor restart backoff sleep.")
declare_env("MXNET_SERVING_QUANT_REQUIRE_DIGEST", "1",
            "Serving admission of quantized artifacts "
            "(ModelRepository.load_artifact): 1 (default) rejects a "
            "manifest v4 quantization block that ships without its "
            "scale digest — undetectable scale tampering/corruption — "
            "with a clear MXNetError; 0 admits unprotected scales "
            "(dev/test only).  A PRESENT digest is always verified "
            "regardless of this knob.")
declare_env("MXNET_SERVING_QUANT_MAX_REL_ERR", None,
            "Serving admission bound on a quantized artifact's "
            "recorded calibration error: reject at "
            "ModelRepository.load_artifact when the manifest's "
            "quantization.calibration.max_rel_err exceeds this float "
            "(quality gate on what a replica will serve).  Unset "
            "(default) = no bound.")
declare_env("MXNET_COMPILE_CACHE_DIR", None,
            "Persistent AOT compiled-executable cache directory "
            "(mxnet_tpu.compile_cache): serving bucket programs are "
            "content-addressed on (StableHLO hash, shape bucket, "
            "dtypes, device topology, jax version) and reloaded via "
            "PJRT executable deserialization instead of recompiling — "
            "a warm server restart compiles ZERO new XLA programs. "
            "Unset (default) = disabled.")
declare_env("MXNET_COMPILE_CACHE_MAX_BYTES", 1073741824,
            "Size bound on the compile-cache directory; least-recently-"
            "used entries are evicted beyond it (hits refresh recency). "
            "0 = unbounded.")
