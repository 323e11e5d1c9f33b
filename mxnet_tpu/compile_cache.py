"""Persistent AOT compiled-executable cache (docs/serving.md §5).

Every server start and every bench round used to retrace and recompile
every shape bucket from scratch — minutes of dead time at production
replica counts and a p99 cliff on every hot-swap.  The "Automatic Full
Compilation … to Cloud TPUs" line (PAPERS.md) is the ahead-of-time
grounding: compile once, serialize the executable, reuse it everywhere
the (program, shape bucket, dtypes, device topology, jax version) key
matches.

Two tiers share this module:

- **Serving executables** (:class:`CompileCache`): content-addressed
  blobs of ``jax.experimental.serialize_executable`` payloads under
  ``MXNET_COMPILE_CACHE_DIR``.  Writes are atomic (tmp + rename), loads
  are corruption-tolerant (a bad blob is a miss that falls back to a
  fresh compile — never an error), and the directory is LRU-bounded by
  ``MXNET_COMPILE_CACHE_MAX_BYTES`` (eviction by least-recent use;
  hits refresh recency).  Consumers: ``deploy.StableHLOModel.
  aot_program`` / ``serving.ModelRepository`` bucket programs.
- **Training-side jit programs**: :func:`enable_jax_persistent_cache`
  turns on jax's OWN persistent compilation cache — where
  ``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path inside the
  checkout — and counts its hit/miss monitoring events; the
  benchmark (``perfbench``) and ``chip_smoke.py`` call it first thing
  so a second run on the same machine stops paying the full compile
  bill.

Payload format: ``b"MXAOT1" + sha256(body) + body`` where ``body`` is
the pickled ``(blob, in_tree, out_tree)`` triple from
``serialize_executable.serialize`` — the checksum is what makes a
truncated or bit-flipped entry a detectable miss instead of an opaque
deserialization crash.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
import time

from . import engine, faults as _faults, runtime_metrics as _rm
from .base import MXNetError, get_env

__all__ = ["CompileCache", "cache_key", "topology_fingerprint",
           "aot_program", "get_default", "enable_jax_persistent_cache",
           "backend_compiles"]

_LOG = logging.getLogger("mxnet_tpu")

_MAGIC = b"MXAOT1"
_DIGEST_BYTES = 32          # sha256
_SUFFIX = ".bin"


# --------------------------------------------------------------------- keys
def topology_fingerprint():
    """Device-topology + runtime-version component of every cache key: a
    serialized executable only reloads onto the platform/device-kind/
    count/process layout and jax/jaxlib pair it was compiled for, and
    only onto the very device it was compiled for (``dev=``: the
    serialized form names its device by id)."""
    try:
        import jax
        import jaxlib

        from .context import default_jax_device
        devs = jax.devices()
        kinds = ",".join(sorted({f"{d.platform}:{d.device_kind}"
                                 for d in devs}))
        return (f"{kinds}|n={len(devs)}|procs={jax.process_count()}"
                f"|dev={default_jax_device().id}"
                f"|jax={jax.__version__}|jaxlib={jaxlib.__version__}")
    except Exception:       # noqa: BLE001 — keyable even without a backend
        return "no-backend"


def cache_key(program_hash, bucket_rows, dtypes, topology=None):
    """Content address of one compiled executable:
    (program identity, shape bucket, input dtypes, device topology +
    jax/PJRT version) -> hex digest.  ``program_hash`` is the sha256 of
    the serialized StableHLO module (or any stable program fingerprint).
    """
    if topology is None:
        topology = topology_fingerprint()
    parts = "\x1f".join([str(program_hash), f"rows={bucket_rows}",
                         ",".join(str(d) for d in dtypes), topology])
    return hashlib.sha256(parts.encode()).hexdigest()


# ----------------------------------------------------------------- payloads
def _wrap_payload(body: bytes) -> bytes:
    return _MAGIC + hashlib.sha256(body).digest() + body


def _unwrap_payload(raw: bytes):
    """Checksum-verified body, or None for a corrupt/foreign blob."""
    if len(raw) < len(_MAGIC) + _DIGEST_BYTES \
            or not raw.startswith(_MAGIC):
        return None
    digest = raw[len(_MAGIC):len(_MAGIC) + _DIGEST_BYTES]
    body = raw[len(_MAGIC) + _DIGEST_BYTES:]
    if hashlib.sha256(body).digest() != digest:
        return None
    return body


def _serialize_compiled(compiled) -> bytes:
    """Compiled jax executable -> self-contained payload body."""
    from jax.experimental.serialize_executable import serialize
    return pickle.dumps(serialize(compiled))


def _deserialize_compiled(body: bytes):
    """Payload body -> loaded executable callable, on the one device it
    was compiled for (the cache key's ``dev=``).  Left to its default,
    ``deserialize_and_load`` spreads the executable over EVERY device
    of the backend, and a one-device program then refuses its
    arguments wherever the process sees more than one device."""
    from jax.experimental.serialize_executable import deserialize_and_load

    from .context import default_jax_device
    blob, in_tree, out_tree = pickle.loads(body)
    return deserialize_and_load(blob, in_tree, out_tree,
                                execution_devices=[default_jax_device()])


def load_payload_file(path):
    """Read + checksum-verify one cache/shipped payload file.  Returns
    the body bytes, or None when missing/corrupt (never raises on bad
    data — a broken blob must degrade to a fresh compile)."""
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    return _unwrap_payload(raw)


def load_executable_file(path):
    """Payload file -> loaded executable callable (flagged with
    ``_mx_from_disk_cache=True``), or None on missing/corrupt/
    undeserializable content.  The no-cache-dir path for executables
    shipped inside an artifact (``export_stablehlo(precompile=...)``);
    observes the deserialize histogram like a cache hit."""
    body = load_payload_file(path)
    if body is None:
        return None
    t0 = time.perf_counter()
    try:
        loaded = _deserialize_compiled(body)
    except Exception:   # noqa: BLE001 — stale blob degrades to compile
        return None
    if _rm._ENABLED:
        _rm.COMPILE_CACHE_DESERIALIZE_SECONDS.observe(
            time.perf_counter() - t0)

    def prog(*xs):
        return loaded(*xs)
    prog._mx_from_disk_cache = True
    return prog


def write_payload_file(path, body):
    """Atomically write one payload file (tmp in the same dir +
    ``os.replace``), so a concurrent reader never sees a half-written
    blob and a crash never leaves a truncated entry under the real name.
    """
    d = os.path.dirname(path) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_wrap_payload(body))
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# -------------------------------------------------------------------- cache
class CompileCache:
    """Content-addressed on-disk store of serialized executables.

    ``cache_dir=None`` (and ``MXNET_COMPILE_CACHE_DIR`` unset) disables
    the cache: every lookup misses cheaply and nothing touches disk.
    All byte-level operations are corruption-tolerant; counters
    (``hits``/``misses``/``corrupt``/``stores``/``evictions``) are
    always on (plain ints) and mirrored into ``runtime_metrics`` as
    ``compile.cache{event=...}`` when the registry is enabled.
    """

    def __init__(self, cache_dir=None, max_bytes=None):
        if cache_dir is None:
            cache_dir = get_env("MXNET_COMPILE_CACHE_DIR", typ=str)
        if max_bytes is None:
            max_bytes = get_env("MXNET_COMPILE_CACHE_MAX_BYTES", typ=int)
        self.cache_dir = cache_dir
        self._requested_dir = cache_dir     # identity even when the dir
        self.max_bytes = int(max_bytes) if max_bytes else 0  # is unusable
        self._lock = engine.make_lock("compile_cache.CompileCache._lock")
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.stores = 0
        self.evictions = 0
        if self.cache_dir:
            # an uncreatable dir (permission-denied parent, read-only
            # fs) degrades to cache-off with a warning — never an error
            # on the serving path, and diagnose must stay runnable to
            # report exactly this misconfiguration
            try:
                os.makedirs(self.cache_dir, exist_ok=True)
            except OSError as e:
                _LOG.warning("compile cache: cannot create %s (%s); "
                             "cache disabled", self.cache_dir, e)
                self.cache_dir = None
            else:
                self._sweep_orphan_tmp()

    def _sweep_orphan_tmp(self):
        """Unlink ``*.tmp`` litter left by writers killed between
        mkstemp and the atomic rename (the kill-and-restart lifecycle
        is this cache's whole point).  Age-gated to one minute so a
        concurrent replica's in-flight write is never yanked — real
        writes complete in milliseconds."""
        try:
            names = os.listdir(self.cache_dir)
        except OSError:
            return
        cutoff = time.time() - 60
        for name in names:
            if not name.endswith(".tmp"):
                continue
            path = os.path.join(self.cache_dir, name)
            try:
                if os.stat(path).st_mtime < cutoff:
                    os.unlink(path)
            except OSError:
                continue

    @property
    def enabled(self):
        return bool(self.cache_dir)

    def _path(self, key):
        return os.path.join(self.cache_dir, key + _SUFFIX)

    def _count(self, event):
        # callers hold no lock; counter writes take the instance lock so
        # concurrent workers don't lose increments
        with self._lock:
            setattr(self, _EVENT_ATTR[event],
                    getattr(self, _EVENT_ATTR[event]) + 1)
        if _rm._ENABLED:
            _rm.COMPILE_CACHE.inc(event=event)

    # ------------------------------------------------------------- bytes
    def contains(self, key):
        """Whether an entry exists on disk (no counters, no read)."""
        return self.enabled and os.path.exists(self._path(key))

    def _read_verified(self, key):
        """Checksum-verified body or None.  Counts ``corrupt`` (and
        unlinks the rot) but NOT hit/miss — callers count those once
        they know whether the payload was actually usable."""
        if not self.enabled:
            return None
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        try:
            # chaos site: blob rot (corrupt flips a byte -> the
            # checksum below turns it into a counted miss) or a slow/
            # failing cache volume — ALL modes degrade to a miss, the
            # cache's never-raise contract
            raw = _faults.inject("compile_cache.load", value=raw)
        except MXNetError:
            return None
        body = _unwrap_payload(raw)
        if body is None:
            self._discard_corrupt(path)
            return None
        try:
            os.utime(path, None)        # LRU recency
        except OSError:
            pass
        return body

    def get(self, key):
        """Checksum-verified payload body for ``key`` or None.  A hit
        refreshes the entry's recency (LRU); a corrupt blob is unlinked
        and counted both ``corrupt`` and ``miss`` — the miss counter's
        contract is "lookups that did NOT yield a usable payload", so
        it stays equal to the compiles that follow."""
        body = self._read_verified(key)
        self._count("hit" if body is not None else "miss")
        return body

    def put(self, key, body):
        """Atomically persist ``body`` under ``key`` and enforce the LRU
        size bound.  Best-effort: an unwritable cache dir logs once and
        degrades to cache-off behavior instead of failing the compile
        that produced the executable."""
        if not self.enabled:
            return False
        try:
            write_payload_file(self._path(key), body)
        except OSError as e:
            _LOG.warning("compile cache: cannot write %s: %s",
                         self.cache_dir, e)
            return False
        self._count("store")
        self._enforce_limit()
        return True

    def ingest(self, key, path):
        """Seed the cache from a shipped payload file (an
        ``export_stablehlo(precompile=...)`` artifact).  Returns True
        when the entry is (now) present and valid.  An existing entry
        is checksum-verified, not trusted: a bit-flipped cache blob
        must not shadow a pristine shipped one."""
        if not self.enabled:
            return False
        if self.contains(key) \
                and load_payload_file(self._path(key)) is not None:
            return True
        body = load_payload_file(path)
        if body is None:
            return False
        return self.put(key, body)

    def _discard_corrupt(self, path):
        try:
            os.unlink(path)
        except OSError:
            pass
        self._count("corrupt")

    def _entries(self):
        out = []
        try:
            names = os.listdir(self.cache_dir)
        except OSError:
            return out
        for name in names:
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.cache_dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            out.append((path, st.st_mtime, st.st_size))
        return out

    def _enforce_limit(self):
        """Evict least-recently-used entries until the directory fits
        ``max_bytes`` (0 = unbounded).  The newest entry always stays,
        so one oversized executable degrades to a single-entry cache
        instead of evicting itself forever."""
        if not self.enabled or self.max_bytes <= 0:
            return
        entries = sorted(self._entries(), key=lambda e: e[1])
        total = sum(size for _p, _m, size in entries)
        while total > self.max_bytes and len(entries) > 1:
            path, _mtime, size = entries.pop(0)     # oldest first
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            self._count("evict")

    # ------------------------------------------------------- executables
    def load_executable(self, key):
        """Deserialize + load the executable stored under ``key`` onto
        the current devices.  Returns a callable flagged with
        ``_mx_from_disk_cache=True`` (the serving batcher reads the flag
        to label disk hits), or None on miss/corruption.

        Counting happens HERE, after deserialization: a blob that reads
        and checksums fine but no longer loads (stale PJRT plugin under
        an unchanged jax version) is a ``corrupt`` + ``miss``, never a
        hit — so ``misses`` stays equal to the XLA compiles that
        actually happen, which is what the CI round-trip asserts."""
        body = self._read_verified(key)
        if body is None:
            self._count("miss")
            return None
        t0 = time.perf_counter()
        try:
            loaded = _deserialize_compiled(body)
        except Exception:   # noqa: BLE001 — stale PJRT blob
            self._discard_corrupt(self._path(key))
            self._count("miss")
            return None
        self._count("hit")
        if _rm._ENABLED:
            _rm.COMPILE_CACHE_DESERIALIZE_SECONDS.observe(
                time.perf_counter() - t0)

        def prog(*xs):
            return loaded(*xs)
        prog._mx_from_disk_cache = True
        return prog

    def store_executable(self, key, compiled):
        """Serialize a freshly compiled executable under ``key``.
        Returns False (cache stays consistent, compile result unharmed)
        when the backend does not support executable serialization."""
        try:
            body = _serialize_compiled(compiled)
        except Exception as e:  # noqa: BLE001 — backend w/o serialization
            _LOG.debug("compile cache: executable not serializable: %s", e)
            return False
        return self.put(key, body)

    # ------------------------------------------------------------- stats
    def stats(self):
        """Plain-dict snapshot for diagnose/bench JSON: dir, entry
        count, total bytes, and this process's counters."""
        entries = self._entries() if self.enabled else []
        with self._lock:
            out = {"enabled": self.enabled, "dir": self.cache_dir,
                   "max_bytes": self.max_bytes,
                   "entries": len(entries),
                   "bytes": sum(s for _p, _m, s in entries),
                   "hits": self.hits, "misses": self.misses,
                   "corrupt": self.corrupt, "stores": self.stores,
                   "evictions": self.evictions}
        return out


_EVENT_ATTR = {"hit": "hits", "miss": "misses", "corrupt": "corrupt",
               "store": "stores", "evict": "evictions"}

# process-default instance, rebuilt whenever the env knobs change (so a
# test monkeypatching MXNET_COMPILE_CACHE_DIR gets a fresh cache without
# reaching into module state)
_DEFAULT = None
_DEFAULT_LOCK = engine.make_lock("compile_cache._DEFAULT_LOCK")


def get_default():
    """The env-configured process-wide cache (``MXNET_COMPILE_CACHE_DIR``
    / ``MXNET_COMPILE_CACHE_MAX_BYTES``); disabled when the dir is
    unset."""
    global _DEFAULT
    cache_dir = get_env("MXNET_COMPILE_CACHE_DIR", typ=str)
    max_bytes = get_env("MXNET_COMPILE_CACHE_MAX_BYTES", typ=int)
    with _DEFAULT_LOCK:
        if _DEFAULT is None or _DEFAULT._requested_dir != cache_dir \
                or _DEFAULT.max_bytes != (max_bytes or 0):
            _DEFAULT = CompileCache(cache_dir, max_bytes)
        return _DEFAULT


# ------------------------------------------------------------- AOT compile
def aot_program(fn, avals, key, cache=None, shipped_path=None):
    """Cache-through ahead-of-time compile: returns ``(prog, source)``
    where ``source`` is ``"disk"`` (deserialized from the cache or from
    ``shipped_path`` — zero XLA compiles) or ``"compile"`` (lowered +
    compiled now, and stored for the next process).  ``prog`` takes raw
    arrays matching ``avals`` exactly (the serving batcher pads every
    batch to its bucket, so the shapes always match).  ``shipped_path``
    is the last resort before compiling — it covers a disabled or
    unwritable cache AND a corrupt cache entry shadowing a pristine
    shipped executable."""
    import jax

    cache = get_default() if cache is None else cache
    if cache.enabled:
        prog = cache.load_executable(key)
        if prog is not None:
            return prog, "disk"
    if shipped_path is not None:
        prog = load_executable_file(shipped_path)
        if prog is not None:
            return prog, "disk"
    # an already-jitted fn lowers as itself: wrapping it in a second
    # jit would drop its donate_argnums (the decode programs donate the
    # KV pools)
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    try:
        compiled = lower(*avals).compile()
    except Exception as e:
        raise MXNetError(f"aot_program: compile failed for key "
                         f"{key[:12]}…: {e}") from e
    if cache.enabled:
        cache.store_executable(key, compiled)

    def prog(*xs):
        return compiled(*xs)
    prog._mx_from_disk_cache = False
    return prog, "compile"


# ----------------------------------------------- training-side (jax) cache
# the one in-code home of jax's persistent cache: a fixed path inside
# the checkout (the path is part of what a lookup keys on, so a
# directory that moves between runs never hits)
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_jax_persistent_cache():
    """Turn on jax's OWN persistent compilation cache (the
    training-side ``jax.jit`` path — distinct from the serving
    executable store above), with the size/time admission thresholds
    zeroed so every program persists.  Where the cache lives is decided
    outside the program: when ``JAX_COMPILATION_CACHE_DIR`` is set, jax
    has already read it and no directory is set here; otherwise it is
    :data:`JAX_CACHE_DIR`.  Returns a live ``{"hits": n, "misses": n}``
    dict updated from jax's compilation-cache monitoring events."""
    import jax
    from jax import monitoring

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(JAX_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    stats = {"hits": 0, "misses": 0}

    def _listener(event, **_kw):
        # the counts double as runtime metrics when the registry is on
        if event == "/jax/compilation_cache/cache_hits":
            stats["hits"] += 1
            if _rm._ENABLED:
                _rm.COMPILE_CACHE.inc(event="jax_hit")
        elif event == "/jax/compilation_cache/cache_misses":
            stats["misses"] += 1
            if _rm._ENABLED:
                _rm.COMPILE_CACHE.inc(event="jax_miss")

    monitoring.register_event_listener(_listener)
    return stats


_BACKEND_COMPILES = {"n": 0, "listening": False}
_BACKEND_COMPILES_LOCK = engine.make_lock(
    "compile_cache._BACKEND_COMPILES_LOCK")


def _on_compile_duration(event, _secs, **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        with _BACKEND_COMPILES_LOCK:
            _BACKEND_COMPILES["n"] += 1


def backend_compiles():
    """How many programs this process has handed to the backend so far
    (compiled, or loaded from jax's persistent cache: either way a
    program the caller did not have), counted from jax's
    ``backend_compile_duration`` monitoring event.  The listener is
    registered by the first call, so the count starts there: callers
    compare two readings (``mx.train.step``'s ``compiles`` tag)."""
    if not _BACKEND_COMPILES["listening"]:
        with _BACKEND_COMPILES_LOCK:
            if not _BACKEND_COMPILES["listening"]:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(
                    _on_compile_duration)
                _BACKEND_COMPILES["listening"] = True
    return _BACKEND_COMPILES["n"]
