"""Per-step training performance accounting (docs/perf_playbook.md
"Reading a step breakdown"; docs/observability.md training taxonomy).

The serving plane debugs its tail span-by-span (``mxnet_tpu.tracing``);
the training plane had only aggregates — a slow ``trainer.step.seconds``
p99 was compatible with a starved input pipeline, a slow host→device
stage, or a congested gradient collective, and the MFU math lived in
``bench.py`` where no running job could read it.  This module is the
training half of that observability contract:

- **Step attribution** (:class:`StepAttribution`): each attributed
  trainer step roots a ``train.step`` trace decomposed into
  ``train.data.wait`` (iterator next + host staging — noted by the io
  layer via :func:`note_data_wait` and back-dated into the step that
  consumes the batch), ``train.h2d`` (``global_device_put`` staging),
  ``train.compute`` (dispatch → device completion of the compiled
  fwd+bwd program), and zero-length ``train.collective`` /
  ``train.optimizer`` markers (both run fused *inside* the one
  compiled program; the collective marker carries the wire-vs-logical
  byte accounting).  Same head sampling, ring, and chrome-trace export
  as serving — a training timeline opens in Perfetto next to a
  serving one.
- **Runtime MFU** (:func:`step_flops` / :func:`mfu`, promoted from
  ``bench.py``): exact per-step FLOPs from XLA's ``cost_analysis`` of
  the compiled step, divided by measured step time and the per-chip
  peak (``MXNET_PEAK_TFLOPS`` or the :data:`PEAK_TFLOPS` entry of the
  device kind), published as the ``train.mfu`` gauge.  Backends
  without cost analysis degrade to a NaN-safe 0 with one warning; a
  CPU has no peak and publishes no ``train.mfu``.
- **Bottleneck verdict**: over a rolling window of steps, the largest
  non-compute phase names the bottleneck — ``input_bound``
  (data wait + h2d), ``comm_bound`` (collective), else
  ``compute_bound`` — published as the ``train.bottleneck`` gauge,
  tagged on incident dumps, printed by ``tools/diagnose.py`` and the
  ``Speedometer`` log line.

Overhead contract (mirrors ``tracing``/``runtime_metrics``): with both
``MXNET_TRACE`` and ``MXNET_RUNTIME_METRICS`` off, :meth:`step_start`
returns one shared inert handle — an attribute load + branch per step —
and no XLA program is ever added in either switch position (FLOPs
accounting is metrics-gated and AOT, outside the jit cache).
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque

from . import runtime_metrics as _rm
from . import tracing as _tr
from .base import MXNetError, get_env

__all__ = [
    "PHASES", "VERDICTS", "StepAttribution",
    "mfu", "step_flops", "detect_peak_tflops",
    "note_data_wait", "take_data_wait",
    "current_verdict", "current_mfu", "reset",
]

_LOG = logging.getLogger("mxnet_tpu")

# breakdown phases (the `phase` label of train.step.breakdown.seconds);
# every attributed step observes all five so the per-phase histograms
# stay directly comparable and the phases tile the train.step interval
PHASES = ("data_wait", "h2d", "compute", "collective", "optimizer")

# span leaf per phase (span name = f"train.{leaf}")
_SPAN_LEAF = {"data_wait": "data.wait"}

# verdict encoding of the train.bottleneck gauge (index = gauge value)
VERDICTS = ("compute_bound", "input_bound", "comm_bound")
_VERDICT_CODE = {v: i for i, v in enumerate(VERDICTS)}

# which verdict a non-compute phase votes for; compute + the fused
# optimizer marker count as compute time
_PHASE_VERDICT = {"data_wait": "input_bound", "h2d": "input_bound",
                  "collective": "comm_bound"}


# ---------------------------------------------------------------------------
# FLOPs / MFU accounting (promoted from bench.py — one source of truth)
# ---------------------------------------------------------------------------

def mfu(n_params, B, L, dt, peak_tflops):
    """The 6NBL transformer rule: 6 * params * tokens FLOPs per step,
    over measured step seconds and the per-chip peak."""
    return 6.0 * n_params * B * L / dt / (peak_tflops * 1e12)


def step_flops(trainer, batch):
    """Exact per-step model FLOPs from XLA's cost analysis of the
    compiled train step (fwd+bwd+optimizer as one program).  The 6NBL
    transformer rule undercounts conv nets badly, so conv workloads
    need the compiler's own count.  Returns None when the backend's
    PJRT executable doesn't expose cost analysis (callers fall back to
    an analytic estimate, or report MFU 0)."""
    import jax
    try:
        shardb = trainer.shard_batch(
            *[getattr(b, "_data", b) for b in batch])
        args = (trainer.params, trainer.opt_state)
        if getattr(trainer, "compression", None) is not None:
            args = args + (trainer.residuals, jax.random.PRNGKey(0))
        compiled = trainer._step.lower(*args, *shardb).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:                            # noqa: BLE001
        return None


# Per-chip dense bf16 peak, TFLOP/s, keyed by the ``device_kind`` string
# jax reports.  A kind enters this table when a chip run has reported it.
PEAK_TFLOPS = {
    # TPU v5e: 197 TFLOP/s bf16 (Google Cloud documentation, "TPU v5e");
    # device_kind as printed by chip_smoke.py on the v5e (PR 21)
    "TPU v5 lite": 197.0,
}


def detect_peak_tflops(devices=None):
    """Per-chip bf16 peak TFLOP/s for MFU: ``MXNET_PEAK_TFLOPS`` when
    set (> 0), else the :data:`PEAK_TFLOPS` entry of the first device's
    ``device_kind``.  A CPU has no peak (``None``: no MFU is computed
    or published there); an accelerator the table does not know is an
    error, never a default."""
    override = float(get_env("MXNET_PEAK_TFLOPS", typ=float) or 0.0)
    if override > 0:
        return override
    if devices is None:
        import jax
        devices = jax.devices()
    dev = devices[0]
    if dev.platform == "cpu":
        return None
    try:
        return PEAK_TFLOPS[dev.device_kind]
    except KeyError:
        raise MXNetError(
            f"perf_account: no peak TFLOP/s on record for device_kind "
            f"{dev.device_kind!r} (platform {dev.platform!r}); set "
            f"MXNET_PEAK_TFLOPS or add the kind, with its source, to "
            f"perf_account.PEAK_TFLOPS") from None


# ---------------------------------------------------------------------------
# Data-wait handoff (io layer -> the step that consumes the batch)
# ---------------------------------------------------------------------------

# thread-local: the iterator runs on the train-loop thread right before
# step(); a PrefetchingIter's producer thread notes into its own slot,
# which is never consumed — only the consumer-visible wait counts
_TLS = threading.local()


def note_data_wait(t0, t1):
    """Record the host interval one ``DataIter.next()`` took (iterator
    wait + host staging); the next :meth:`StepAttribution.step_start`
    on this thread consumes it as the step's ``train.data.wait``."""
    _TLS.data_wait = (t0, t1)


def take_data_wait():
    """Pop the pending data-wait interval, or None."""
    iv = getattr(_TLS, "data_wait", None)
    if iv is not None:
        _TLS.data_wait = None
    return iv


# ---------------------------------------------------------------------------
# Last-published snapshot (Speedometer / diagnose read these without a
# trainer handle; single-writer per publish, torn reads are benign)
# ---------------------------------------------------------------------------

_LAST = {"verdict": None, "mfu": 0.0}


def current_verdict():
    """The verdict of the most recent attributed step in this process
    (any trainer), or None before the first one."""
    return _LAST["verdict"]


def current_mfu():
    """MFU over the attribution window of the most recent attributed
    step (0.0 when FLOPs are unknown)."""
    return _LAST["mfu"]


def reset():
    """Clear process-level attribution state (tests)."""
    _LAST["verdict"] = None
    _LAST["mfu"] = 0.0
    _TLS.data_wait = None


# ---------------------------------------------------------------------------
# Step handles
# ---------------------------------------------------------------------------

class _InertPhase:
    """No-op phase context (the off path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_INERT_PHASE = _InertPhase()


class _InertHandle:
    """Shared do-nothing step handle: what :meth:`step_start` returns
    when both tracing and metrics are off.  One global instance; every
    method is a constant-time no-op."""

    __slots__ = ()
    active = False
    root = None

    def phase(self, name, **tags):
        return _INERT_PHASE

    def record(self, name, t0, t1, **tags):
        return None

    def mark(self, name, **tags):
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_INERT = _InertHandle()


class _PhaseTimer:
    """``with h.phase("h2d"):`` — times the block and records it."""

    __slots__ = ("_h", "_name", "_tags", "_t0")

    def __init__(self, h, name, tags):
        self._h = h
        self._name = name
        self._tags = tags
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if exc_type is not None:
            self._tags["error"] = exc_type.__name__
        self._h.record(self._name, self._t0, t1, **self._tags)
        return False


class _StepHandle:
    """One attributed step: phase accumulator + the ``train.step`` root
    span.  Enter it (``with h:``) around the step body so thread-local
    ``tracing.tag()`` calls (watchdog straggler/timeout events) land on
    the root; exiting ends the root and publishes the breakdown."""

    __slots__ = ("att", "root", "seconds", "t_begin", "t_end")

    def __init__(self, att, root, t_begin):
        self.att = att
        self.root = root
        self.seconds = {}
        self.t_begin = t_begin
        self.t_end = None

    active = True

    def phase(self, name, **tags):
        """Context manager timing one phase of this step."""
        return _PhaseTimer(self, name, tags)

    def record(self, name, t0, t1, **tags):
        """Add an already-timed interval to phase ``name`` and record
        the matching ``train.*`` span (no-op span when unsampled)."""
        self.seconds[name] = self.seconds.get(name, 0.0) + (t1 - t0)
        leaf = _SPAN_LEAF.get(name, name)
        _tr.record_span(f"train.{leaf}", self.root, t0, t1,
                        tags or None)

    def mark(self, name, **tags):
        """Zero-length phase marker: the phase runs fused inside
        another interval (the one-program step executes collective +
        optimizer inside ``train.compute``), so it contributes 0s to
        the breakdown while its tags carry the accounting."""
        t = time.perf_counter()
        self.record(name, t, t, **tags)

    def __enter__(self):
        if self.root.sampled:
            self.root.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t_end = time.perf_counter()
        if self.root.sampled:
            self.root.__exit__(exc_type, exc, tb)
        self.att._publish(self)
        return False


class StepAttribution:
    """Per-trainer step-time attribution, windowed MFU, and the
    bottleneck verdict.

    Owned by one train-loop thread (no internal locking), mirroring
    :class:`~.parallel.supervisor.StepWatchdog`.  ``ShardedTrainer``
    drives it from ``step()``; fake/numpy trainers (tests, the
    diagnose trace smoke) drive the same handle API directly::

        att = StepAttribution()
        h = att.step_start()
        with h:                      # roots the train.step span
            with h.phase("data_wait"):
                batch = it.next()
            with h.phase("h2d"):
                dev_batch = stage(batch)
            with h.phase("compute"):
                loss = run(dev_batch)
            h.mark("collective", fused=True)
            h.mark("optimizer", fused=True)
        # exit published breakdown histograms, MFU, and the verdict

    ``threshold`` is the window fraction the largest non-compute phase
    must reach before the verdict leaves ``compute_bound``.
    """

    def __init__(self, window=32, threshold=0.25, peak_tflops=None):
        self._window = deque(maxlen=int(window))
        self.threshold = float(threshold)
        self.peak_tflops = (detect_peak_tflops()
                            if peak_tflops is None else
                            float(peak_tflops))
        self.flops_per_step = None      # unknown until note_flops
        self._flops_warned = False
        self._verdict = None
        self._mfu = 0.0
        self._steps = 0

    @property
    def active(self):
        """True when either observability switch is on — the gate the
        instrumented trainer checks before paying any per-step cost."""
        return _rm._ENABLED or _tr._ENABLED

    # ------------------------------------------------------------ flops
    def note_flops(self, flops):
        """Install the per-step FLOP count (from :func:`step_flops` or
        an analytic estimate).  None/0 — no cost analysis on this
        backend — degrades to MFU 0 with one warning, never NaN."""
        if flops:
            self.flops_per_step = float(flops)
        else:
            self.flops_per_step = 0.0
            if not self._flops_warned:
                self._flops_warned = True
                _LOG.warning(
                    "perf_account: step FLOPs unavailable (backend "
                    "exposes no cost_analysis) — train.mfu reports 0")

    # ------------------------------------------------------------- steps
    def step_start(self, **tags):
        """Begin one attributed step.  Returns the step handle — the
        shared inert one when tracing and metrics are both off.  A
        pending data-wait interval (:func:`note_data_wait`) is consumed
        here: the root span is back-dated to its start so the phase
        spans tile the ``train.step`` interval."""
        if not (_rm._ENABLED or _tr._ENABLED):
            return _INERT
        pending = take_data_wait()
        root = _tr.trace("train.step", **tags)
        h = _StepHandle(self, root, time.perf_counter())
        if pending is not None:
            t0, t1 = pending
            if root.sampled:
                root.t0 = min(root.t0, t0)
            h.t_begin = min(h.t_begin, t0)
            h.record("data_wait", t0, t1)
        return h

    # ----------------------------------------------------------- publish
    def _publish(self, h):
        dt = max(h.t_end - h.t_begin, 0.0)
        self._window.append((dt, h.seconds))
        self._steps += 1
        self._verdict = self._compute_verdict()
        self._mfu = self._compute_mfu()
        _LAST["verdict"] = self._verdict
        _LAST["mfu"] = self._mfu
        if _rm._ENABLED:
            for p in PHASES:
                _rm.TRAIN_STEP_BREAKDOWN_SECONDS.observe(
                    h.seconds.get(p, 0.0), phase=p)
            tid = h.root.trace_id if h.root.sampled else None
            _rm.TRAINER_STEP_SECONDS.observe(dt, exemplar=tid)
            if self.peak_tflops:        # a CPU has no peak: no train.mfu
                _rm.TRAIN_MFU.set(self._mfu)
            _rm.TRAIN_BOTTLENECK.set(_VERDICT_CODE[self._verdict])

    def _compute_verdict(self):
        wall = sum(dt for dt, _ in self._window)
        if wall <= 0:
            return "compute_bound"
        votes = {"input_bound": 0.0, "comm_bound": 0.0}
        for _, secs in self._window:
            for p, v in _PHASE_VERDICT.items():
                votes[v] += secs.get(p, 0.0)
        top = max(votes, key=votes.get)
        if votes[top] / wall >= self.threshold:
            return top
        return "compute_bound"

    def _compute_mfu(self):
        if not self.flops_per_step or not self.peak_tflops:
            return 0.0
        wall = sum(dt for dt, _ in self._window)
        if wall <= 0:
            return 0.0
        return (self.flops_per_step * len(self._window)
                / wall / (self.peak_tflops * 1e12))

    # ------------------------------------------------------------ readers
    def verdict(self):
        """Current windowed verdict, or None before the first step."""
        return self._verdict

    def mfu_value(self):
        """MFU over the current window (0.0 while FLOPs unknown)."""
        return self._mfu

    def phase_means(self):
        """Mean seconds per phase over the window."""
        n = len(self._window)
        if not n:
            return {p: 0.0 for p in PHASES}
        return {p: sum(secs.get(p, 0.0)
                       for _, secs in self._window) / n
                for p in PHASES}

    def summary(self):
        """One JSON-ready block: window means, fractions of step time,
        verdict, MFU (the BENCH ``attribution`` payload)."""
        means = self.phase_means()
        wall = sum(dt for dt, _ in self._window)
        n = len(self._window)
        step_mean = wall / n if n else 0.0
        frac = {p: (means[p] / step_mean if step_mean > 0 else 0.0)
                for p in PHASES}
        return {"steps": self._steps,
                "step_seconds_mean": round(step_mean, 6),
                "phase_seconds_mean":
                    {p: round(means[p], 6) for p in PHASES},
                "phase_fraction":
                    {p: round(frac[p], 4) for p in PHASES},
                "verdict": self._verdict,
                "mfu": (round(self._mfu, 4) if self.peak_tflops
                        else None)}

    def debug_state(self):
        """Incident-dump payload (rides supervisor/flight dumps)."""
        out = self.summary()
        out["flops_per_step"] = self.flops_per_step
        out["peak_tflops"] = self.peak_tflops
        return out
