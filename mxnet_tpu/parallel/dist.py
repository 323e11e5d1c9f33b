"""Multi-process runtime: process-group bootstrap + DCN-tier collectives.

Reference surface: the dmlc tracker (``tools/launch.py``,
``dmlc_tracker/local.py``) + ``KVStoreDist``'s worker bootstrap
(``DMLC_PS_ROOT_URI``/``DMLC_NUM_WORKER`` env protocol) — SURVEY.md §2.4
P3, §4 "multi-node testing".

TPU-native redesign: the parameter-server control plane is replaced by
JAX's coordination service — ``jax.distributed.initialize`` elects process
0 as coordinator, after which *all* collectives (ICI within a slice, DCN
across slices/hosts) are XLA collectives over the global device set; there
is no separate server role.  On CPU test rigs the same code path runs over
gloo TCP collectives, which is how the multi-process tests execute without
TPU hardware (conftest philosophy: real runtime, fake scale).

Env protocol (reference-compatible names accepted):
  MXNET_TPU_COORDINATOR | DMLC_PS_ROOT_URI[:DMLC_PS_ROOT_PORT]
  MXNET_TPU_NUM_PROCS   | DMLC_NUM_WORKER
  MXNET_TPU_PROC_ID     | DMLC_WORKER_ID
"""
from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Optional

from ..base import MXNetError
from .. import engine as _engine

__all__ = ["initialize", "finalize", "is_initialized", "rank", "size",
           "barrier", "allreduce_host", "broadcast_host", "Watchdog"]

# _state is threading-reachable (atexit finalize vs. watchdog vs. user
# threads); mutate only under _STATE_LOCK.  "finalizing" claims the
# teardown without dropping "initialized" early: is_initialized() stays
# true (and re-initialize stays a no-op) until the shutdown completes.
_state = {"initialized": False, "finalizing": False}
_STATE_LOCK = threading.Lock()


def _env(*names, default=None):
    for n in names:
        v = os.environ.get(n)
        if v is not None:
            return v
    return default


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               timeout_s: int = 60):
    """Join the process group (reference: KVStoreDist worker bootstrap).

    With no arguments, configuration is read from the env protocol above —
    what ``tools/launch.py`` sets for each spawned worker.  Single-process
    use (no env, no args) is a no-op so scripts run unchanged standalone.

    No devices are assigned here: each process takes whatever its
    environment lets JAX see.  A chip belongs to one process at a time,
    so several processes on ONE host are the CPU/gloo test path; on a
    TPU host one process drives all of the host's chips, and this
    bootstrap joins one such process per host.
    """
    import jax
    # whole check-and-init under the lock: two racing initialize()
    # calls must not both reach jax.distributed.initialize (the second
    # raises on double client init); the loser blocks, then no-ops
    with _STATE_LOCK:
        did_init = _initialize_locked(jax, coordinator_address,
                                      num_processes, process_id,
                                      timeout_s)
    if did_init:
        atexit.register(finalize)


def _initialize_locked(jax, coordinator_address, num_processes,
                       process_id, timeout_s):
    if _state["initialized"] or _state["finalizing"]:
        return False
    coordinator_address = coordinator_address or _env(
        "MXNET_TPU_COORDINATOR")
    if coordinator_address is None:
        uri = _env("DMLC_PS_ROOT_URI")
        if uri is not None:
            coordinator_address = \
                f"{uri}:{_env('DMLC_PS_ROOT_PORT', default='9091')}"
    if num_processes is None:
        v = _env("MXNET_TPU_NUM_PROCS", "DMLC_NUM_WORKER")
        num_processes = int(v) if v is not None else None
    if process_id is None:
        v = _env("MXNET_TPU_PROC_ID", "DMLC_WORKER_ID")
        process_id = int(v) if v is not None else None
    if coordinator_address is None and num_processes is None:
        return False  # standalone run
    if None in (coordinator_address, num_processes, process_id):
        raise MXNetError(
            "dist.initialize: coordinator_address, num_processes and "
            "process_id must all be provided (or none, for standalone)")
    # DCN-tier collectives over gloo TCP when the CPU client is used
    # (test rigs).  Must not probe the backend here — that would
    # initialize XLA before jax.distributed.initialize.  Harmless on TPU:
    # the flag only affects CPU-client creation.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # shutdown_timeout_seconds: a crashing worker must EXIT, not block
    # in the shutdown barrier — the launcher's failure detection relies
    # on seeing the exit code promptly (§5.3 clean abort)
    jax.distributed.initialize(coordinator_address,
                               num_processes=int(num_processes),
                               process_id=int(process_id),
                               initialization_timeout=timeout_s,
                               shutdown_timeout_seconds=15)
    # mxlint: disable=lock-discipline (contract: sole caller is
    # initialize(), which holds _STATE_LOCK around this helper)
    _state["initialized"] = True
    return True


def finalize():
    # atomically claim the teardown: a concurrent finalize (atexit vs.
    # user thread) sees finalizing=True and returns; initialized is NOT
    # dropped yet — a concurrent initialize() mid-teardown must no-op,
    # not re-create the jax client while shutdown is in flight
    with _STATE_LOCK:
        if not _state["initialized"] or _state["finalizing"]:
            return
        _state["finalizing"] = True
    import jax
    # The shutdown barrier can block forever when a peer is gone (the
    # crash path this atexit hook runs on).  Newer jax clients bound it
    # via shutdown_timeout_seconds at initialize(); older ones lack the
    # knob, so enforce the same 15s clean-abort budget here: run the
    # barrier in a daemon thread and abandon it on timeout.  The process
    # then exits with its ORIGINAL code (a crashed worker's rc reaches
    # the launcher's failure detection, §5.3; a healthy-but-slow
    # shutdown is abandoned, not turned into a failure).

    def _shutdown():
        try:
            jax.distributed.shutdown()
        except Exception:   # noqa: BLE001 — peers may already be gone
            pass

    t = _engine.make_thread(_shutdown, name="mxnet-dist-shutdown",
                            owner="dist.finalize")
    t.start()
    t.join(15)
    if t.is_alive():
        # a peer that never answers wedges jax.distributed.shutdown();
        # the launcher owns the process from here
        _engine.forget_thread(t, "jax.distributed.shutdown() wedged >15s")
    with _STATE_LOCK:
        _state["initialized"] = False
        _state["finalizing"] = False


def is_initialized() -> bool:
    return _state["initialized"]


def rank() -> int:
    import jax
    return jax.process_index()


def size() -> int:
    import jax
    return jax.process_count()


def barrier(name: str = "barrier", timeout_s: int = 120):
    """Cross-process sync point (reference: ps Barrier)."""
    if not _state["initialized"]:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)


def allreduce_host(arr):
    """Sum an array across processes (DCN tier; host-mediated).

    For hot-loop gradients use the sharded-mesh path (parallel/trainer,
    kvstore 'dist_sync') — this helper is for control-plane values
    (metrics, loss scalars, early-stop votes)."""
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from ..ndarray import NDArray
    x = arr._data if isinstance(arr, NDArray) else jnp.asarray(arr)
    if not _state["initialized"]:
        return NDArray(x)
    gathered = multihost_utils.process_allgather(x)
    return NDArray(jnp.sum(gathered, axis=0))


def broadcast_host(arr, root: int = 0):
    """Broadcast from `root` to every process (control-plane values)."""
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from ..ndarray import NDArray
    x = arr._data if isinstance(arr, NDArray) else jnp.asarray(arr)
    if not _state["initialized"]:
        return NDArray(x)
    gathered = multihost_utils.process_allgather(x)
    return NDArray(gathered[root])


class Watchdog:
    """Hang detector: clean abort when a step stops making progress.

    Reference behavior being re-created (SURVEY.md §5.3): the reference's
    ps-lite heartbeats let the tracker detect dead workers and abort the
    job instead of hanging in a collective forever.  Here each process
    runs a watchdog thread; if ``kick()`` is not called within
    ``timeout_s`` the process logs state and hard-exits non-zero, which
    the launcher (tools/launch.py) observes to tear down the whole job.

    Use::

        wd = dist.Watchdog(timeout_s=300); wd.start()
        for batch in data:
            train_step(batch)
            wd.kick()
        wd.stop()
    """

    def __init__(self, timeout_s: float = 300.0, name: str = "step"):
        self.timeout_s = float(timeout_s)
        self.name = name
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = None

    def kick(self):
        self._last = time.monotonic()

    def start(self):
        if self._thread is not None:
            return self

        def watch():
            while not self._stop.wait(min(self.timeout_s / 4, 10.0)):
                stalled = time.monotonic() - self._last
                if stalled > self.timeout_s:
                    import logging
                    logging.error(
                        "Watchdog %r: no progress for %.0fs (limit %.0fs) "
                        "— aborting process %d so the launcher can tear "
                        "down the job", self.name, stalled, self.timeout_s,
                        rank() if _state["initialized"] else 0)
                    os._exit(42)

        self._thread = _engine.make_thread(
            watch, name=f"watchdog-{self.name}",
            owner=f"dist.Watchdog({self.name})")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
