"""Training step attribution, runtime MFU, bottleneck verdicts
(mxnet_tpu.perf_account — ISSUE-16).

Covers: the promoted MFU/FLOPs math, peak detection, the thread-local
data-wait channel, the fake-trainer span chain (tiling, verdicts,
breakdown histograms, exemplar link — zero compiles), the NaN-safe
cost-analysis fallback, the off-path inert contract, a real traced
ShardedTrainer step with the jit cache unchanged, and the Speedometer
log line.  Everything except the one real-trainer test is numpy/sleep
only, so the suite stays cheap under the tier-1 budget.
"""
import logging
import threading
import time
import types

import numpy as np
import pytest

from mxnet_tpu import perf_account as pa
from mxnet_tpu.base import MXNetError
from mxnet_tpu import runtime_metrics as rm
from mxnet_tpu import tracing as tr


@pytest.fixture(autouse=True)
def _clean():
    """Fresh tracer + attribution state per test; off defaults after."""
    tr.reset()
    tr.enable(sample=1.0)
    pa.reset()
    yield
    tr.disable()
    tr.reset()
    tr.TRACER.set_sample(1.0)
    pa.reset()


@pytest.fixture
def metrics():
    rm.reset()
    rm.enable()
    yield rm
    rm.disable()
    rm.reset()


def _assert_links(trace):
    ids = {s["span_id"] for s in trace["spans"]}
    for s in trace["spans"]:
        assert s["trace_id"] == trace["trace_id"], s
        assert s["parent_id"] is None or s["parent_id"] in ids, s


TRAIN_CHAIN = {"train.step", "train.data.wait", "train.h2d",
               "train.compute", "train.collective", "train.optimizer"}


def _fake_steps(att, n=4, data_wait=0.012, h2d=0.002, compute=0.006):
    """Drive the handle API the way ShardedTrainer does, with sleeps
    standing in for the real phases (default shape: the resnet50
    input-bound case — data wait dominates)."""
    for _ in range(n):
        t0 = time.perf_counter()
        if data_wait:
            time.sleep(data_wait)
        pa.note_data_wait(t0, time.perf_counter())
        h = att.step_start()
        with h:
            with h.phase("h2d"):
                time.sleep(h2d)
            with h.phase("compute"):
                time.sleep(compute)
            h.mark("collective", fused=True)
            h.mark("optimizer", fused=True)


# ------------------------------------------------------------- math
def test_mfu_formula():
    # 6NBL over dt * peak: 6 * 1e9 * 32 * 128 / 1.0 / (100e12)
    assert pa.mfu(1e9, 32, 128, 1.0, 100.0) == pytest.approx(
        6e9 * 32 * 128 / 100e12)


def test_detect_peak_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "123.5")
    assert pa.detect_peak_tflops() == 123.5
    monkeypatch.delenv("MXNET_PEAK_TFLOPS")
    fake_cpu = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    assert pa.detect_peak_tflops(fake_cpu) is None   # a CPU has no peak
    v5e = [types.SimpleNamespace(platform="tpu",
                                 device_kind="TPU v5 lite")]
    assert pa.detect_peak_tflops(v5e) == 197.0
    # an accelerator the table does not know is an error, not a default
    unknown = [types.SimpleNamespace(platform="tpu",
                                     device_kind="TPU v99")]
    with pytest.raises(MXNetError, match="TPU v99"):
        pa.detect_peak_tflops(unknown)
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "10")
    assert pa.detect_peak_tflops(unknown) == 10.0


def test_step_flops_unavailable_returns_none():
    class Broken:
        compression = None

        def shard_batch(self, *a):
            raise RuntimeError("no backend")

    assert pa.step_flops(Broken(), (np.ones((2, 2)),)) is None


# ------------------------------------------------- data-wait channel
def test_data_wait_channel_consumed_once():
    pa.note_data_wait(1.0, 2.0)
    assert pa.take_data_wait() == (1.0, 2.0)
    assert pa.take_data_wait() is None


def test_data_wait_channel_is_thread_local():
    seen = {}

    def other():
        pa.note_data_wait(5.0, 6.0)
        seen["own"] = pa.take_data_wait()

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen["own"] == (5.0, 6.0)
    assert pa.take_data_wait() is None      # never crossed threads


# ------------------------------------------- fake-trainer span chain
def test_fake_trainer_chain_tiles_and_is_input_bound(metrics):
    att = pa.StepAttribution(peak_tflops=1.0)
    att.note_flops(1e9)
    _fake_steps(att)

    trace = tr.TRACER.last(root="train.step")
    assert trace is not None, tr.TRACER.stats()
    names = {s["name"] for s in trace["spans"]}
    assert TRAIN_CHAIN <= names, sorted(names)
    _assert_links(trace)
    root = next(s for s in trace["spans"] if s["name"] == "train.step")
    for s in trace["spans"]:
        if s["name"] != "train.step":
            assert s["parent_id"] == root["span_id"], s

    # acceptance: phase spans sum to within 10% of the root interval
    dur = root["t1"] - root["t0"]
    span_sum = sum(s["t1"] - s["t0"] for s in trace["spans"]
                   if s["name"] != "train.step")
    assert abs(span_sum - dur) <= 0.10 * dur, (span_sum, dur)

    # resnet50-shaped case (data wait dominates) -> input_bound
    assert att.verdict() == "input_bound"
    assert pa.current_verdict() == "input_bound"
    assert rm.TRAIN_BOTTLENECK.value() == 1.0
    # every phase observed every step, fused markers at 0
    for phase in pa.PHASES:
        assert rm.TRAIN_STEP_BREAKDOWN_SECONDS.count(phase=phase) == 4
    assert rm.TRAIN_STEP_BREAKDOWN_SECONDS.quantile(
        0.5, phase="collective") < 1e-4
    assert att.mfu_value() > 0
    assert rm.TRAIN_MFU.value() == pytest.approx(att.mfu_value())


def test_root_backdated_to_cover_data_wait():
    att = pa.StepAttribution(peak_tflops=1.0)
    _fake_steps(att, n=1)
    trace = tr.TRACER.last(root="train.step")
    root = next(s for s in trace["spans"] if s["name"] == "train.step")
    dw = next(s for s in trace["spans"]
              if s["name"] == "train.data.wait")
    assert root["t0"] <= dw["t0"]
    assert root["t1"] >= dw["t1"]


def test_comm_and_compute_bound_verdicts(metrics):
    att = pa.StepAttribution(peak_tflops=1.0)
    # collective recorded as a real interval (the explicit-pushpull
    # shape) dominating the step -> comm_bound
    h = att.step_start()
    with h:
        t = time.perf_counter()
        h.record("compute", t, t + 0.001)
        h.record("collective", t, t + 0.009)
        time.sleep(0.01)
    assert att.verdict() == "comm_bound"
    assert rm.TRAIN_BOTTLENECK.value() == 2.0

    att2 = pa.StepAttribution(peak_tflops=1.0)
    h = att2.step_start()
    with h:
        with h.phase("compute"):
            time.sleep(0.008)
        h.mark("collective", fused=True)
        h.mark("optimizer", fused=True)
    assert att2.verdict() == "compute_bound"
    assert rm.TRAIN_BOTTLENECK.value() == 0.0


def test_exemplar_links_p99_to_trace(metrics):
    att = pa.StepAttribution(peak_tflops=1.0)
    _fake_steps(att, n=3)
    tid = rm.TRAINER_STEP_SECONDS.exemplar_for_quantile(0.99)
    assert tid is not None
    assert tr.TRACER.find(tid) is not None


def test_mfu_nan_safe_with_one_warning(metrics, caplog):
    att = pa.StepAttribution(peak_tflops=1.0)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu"):
        att.note_flops(None)          # cost_analysis unavailable
        att.note_flops(0)             # repeated: no second warning
    warnings = [r for r in caplog.records
                if "cost_analysis" in r.getMessage()]
    assert len(warnings) == 1
    _fake_steps(att, n=2)
    assert att.mfu_value() == 0.0
    assert rm.TRAIN_MFU.value() == 0.0
    assert not np.isnan(rm.TRAIN_MFU.value())


def test_metrics_only_mode_publishes_without_tracing(metrics):
    tr.disable()
    att = pa.StepAttribution(peak_tflops=1.0)
    assert att.active          # metrics alone keep attribution on
    _fake_steps(att, n=2)
    assert rm.TRAIN_STEP_BREAKDOWN_SECONDS.count(phase="compute") == 2
    assert pa.current_verdict() is not None
    assert tr.TRACER.stats()["completed"] == 0


# --------------------------------------------------------- off path
def test_off_path_is_inert():
    tr.disable()
    assert not rm.enabled()
    att = pa.StepAttribution(peak_tflops=1.0)
    assert not att.active
    h = att.step_start()
    assert h is pa._INERT                  # shared no-op handle
    with h:
        with h.phase("compute"):
            pass
        h.mark("collective", fused=True)
    assert att.verdict() is None
    assert pa.current_verdict() is None
    assert len(att._window) == 0
    assert tr.TRACER.stats()["completed"] == 0


def test_summary_shape():
    att = pa.StepAttribution(peak_tflops=1.0)
    att.note_flops(1e6)
    _fake_steps(att, n=2)
    s = att.summary()
    assert s["steps"] == 2
    assert set(s["phase_seconds_mean"]) == set(pa.PHASES)
    assert set(s["phase_fraction"]) == set(pa.PHASES)
    assert s["verdict"] == "input_bound"
    # tiled phases: fractions of the step add up to ~1
    assert sum(s["phase_fraction"].values()) == pytest.approx(1.0,
                                                              abs=0.1)
    d = att.debug_state()
    assert d["flops_per_step"] == 1e6
    assert d["peak_tflops"] == 1.0


# ------------------------------------------------- real ShardedTrainer
def test_real_trainer_traced_step_adds_no_programs(metrics):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import io, nd, parallel
    from mxnet_tpu.gluon import nn

    tr.disable()                 # warmup compiles untraced
    mx.random.seed(0)
    net = nn.Dense(1, in_units=8, prefix="pa_net_")
    net.initialize(mx.init.Xavier())
    rs = np.random.RandomState(7)
    x = rs.randn(32, 8).astype(np.float32)
    y = (x @ rs.randn(8).astype(np.float32))[:, None]
    it = io.NDArrayIter(x, y, batch_size=8, shuffle=False)
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1,
                              devices=jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        net, lambda out, lab: ((out - lab) ** 2).mean(), mesh,
        optimizer="sgd", optimizer_params={"learning_rate": 1e-2},
        example_inputs=(nd.array(x[:8]),), n_labels=1)
    b = it.next()
    float(jax.device_get(trainer.step(*b.data, *b.label)))
    baseline = trainer._step._cache_size()
    rm.reset()          # drop the warmup step's metrics-only publish
    rm.enable()

    tr.enable(sample=1.0)
    for _ in range(3):
        b = it.next()
        trainer.step(*b.data, *b.label)
    assert trainer._step._cache_size() == baseline

    trace = tr.TRACER.last(root="train.step")
    assert trace is not None
    names = {s["name"] for s in trace["spans"]}
    assert TRAIN_CHAIN <= names, sorted(names)
    _assert_links(trace)
    coll = next(s for s in trace["spans"]
                if s["name"] == "train.collective")
    assert coll["tags"].get("fused") is True
    assert coll["t0"] == coll["t1"]            # zero-length marker
    assert pa.current_verdict() in pa.VERDICTS
    for phase in pa.PHASES:
        assert rm.TRAIN_STEP_BREAKDOWN_SECONDS.count(phase=phase) == 3

    # off path byte-identical contract: with both switches off the
    # trainer takes the original async-dispatch branch again
    tr.disable()
    rm.disable()
    try:
        assert not trainer.perf.active
        it.reset()
        b = it.next()
        float(jax.device_get(trainer.step(*b.data, *b.label)))
        assert trainer._step._cache_size() == baseline
    finally:
        rm.enable()


# ------------------------------------------------------- Speedometer
def test_speedometer_surfaces_mfu_and_verdict(metrics, caplog):
    from mxnet_tpu.callback import Speedometer

    att = pa.StepAttribution(peak_tflops=1.0)
    att.note_flops(1e9)
    _fake_steps(att, n=2)
    assert pa.current_verdict() == "input_bound"

    sm = Speedometer(batch_size=4, frequent=1)
    param = types.SimpleNamespace(nbatch=0, epoch=0, eval_metric=None)
    with caplog.at_level(logging.INFO):
        sm(param)                       # arms the timer
        param = types.SimpleNamespace(nbatch=1, epoch=0,
                                      eval_metric=None)
        sm(param)                       # logs
    msg = "\n".join(r.getMessage() for r in caplog.records)
    assert "verdict=input_bound" in msg
    assert "mfu=" in msg
