"""Runtime metrics registry: primitives, exporters, and the
instrumented hot layers (op dispatch, engine, io, kvstore, trainer)."""
import json
import re
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler, runtime_metrics as rm


@pytest.fixture(autouse=True)
def _metrics_on():
    """Enable + zero the registry per test, restore the off default."""
    rm.reset()
    rm.enable()
    yield
    rm.disable()
    rm.reset()


class TestPrimitives:
    def test_disabled_path_is_noop(self):
        rm.disable()
        c = rm.counter("t.disabled.counter")
        g = rm.gauge("t.disabled.gauge")
        h = rm.histogram("t.disabled.hist")
        c.inc(5)
        g.set(3.0)
        h.observe(0.1)
        assert c.value() == 0
        assert g.value() == 0
        assert h.count() == 0

    def test_counter_concurrent_increments(self):
        c = rm.counter("t.concurrent", labelnames=("who",))
        n_threads, n_incs = 8, 500

        def worker(i):
            for _ in range(n_incs):
                c.inc(who=str(i % 2))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.total() == n_threads * n_incs
        assert c.value(who="0") == n_threads * n_incs / 2

    def test_counter_rejects_negative(self):
        c = rm.counter("t.neg")
        with pytest.raises(mx.MXNetError):
            c.inc(-1)
        # validation is independent of the registry switch — a bad call
        # site must not run clean in metrics-off environments
        rm.disable()
        with pytest.raises(mx.MXNetError):
            c.inc(-1)

    def test_histogram_bucket_conflict_rejected(self):
        rm.histogram("t.bucket.conflict", buckets=(1.0, 2.0))
        with pytest.raises(mx.MXNetError, match="buckets"):
            rm.histogram("t.bucket.conflict", buckets=(5.0,))
        # same buckets (any order) re-resolve fine
        rm.histogram("t.bucket.conflict", buckets=(2.0, 1.0))
        # omitting buckets returns the existing metric unchecked
        assert rm.histogram("t.bucket.conflict").buckets == (1.0, 2.0)

    def test_gauge_set_max_and_incdec(self):
        g = rm.gauge("t.gauge")
        g.set(5)
        g.set_max(3)
        assert g.value() == 5
        g.set_max(9)
        assert g.value() == 9
        g.inc(1)
        g.dec(4)
        assert g.value() == 6

    def test_histogram_quantiles(self):
        h = rm.histogram("t.hist", buckets=(1, 2, 4, 8))
        for v in (0.5, 1.5, 1.5, 3, 6):
            h.observe(v)
        assert h.count() == 5
        assert h.sum() == pytest.approx(12.5)
        q50 = h.quantile(0.5)
        assert 1.0 <= q50 <= 2.0       # median lands in the (1, 2] bucket
        assert h.quantile(1.0) <= 8.0
        assert h.quantile(0.0) <= 1.0
        # overflow values clamp to the last finite bound
        h.observe(100.0)
        assert h.quantile(1.0) == 8.0

    def test_registry_type_and_label_conflicts(self):
        rm.counter("t.conflict")
        with pytest.raises(mx.MXNetError):
            rm.gauge("t.conflict")
        rm.counter("t.labeled", labelnames=("a",))
        with pytest.raises(mx.MXNetError):
            rm.counter("t.labeled", labelnames=("b",))
        # get-or-create returns the same object
        assert rm.counter("t.conflict") is rm.counter("t.conflict")


class TestExporters:
    def test_prometheus_text_format(self):
        c = rm.counter("t.prom.ops", "op calls", labelnames=("op",))
        c.inc(3, op="dot")
        g = rm.gauge("t.prom.depth")
        g.set(2)
        h = rm.histogram("t.prom.lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        txt = rm.dump_prometheus()
        assert 't_prom_ops_total{op="dot"} 3' in txt
        assert "# TYPE t_prom_ops_total counter" in txt
        assert "t_prom_depth 2" in txt
        assert 't_prom_lat_bucket{le="0.1"} 1' in txt
        assert 't_prom_lat_bucket{le="+Inf"} 2' in txt
        assert "t_prom_lat_count 2" in txt

    def test_chrome_counter_events_merge_into_profiler_dump(self):
        profiler.set_config(filename="/tmp/_rm_merge.json")
        profiler.start()
        (nd.ones((4, 4)) * 2).wait_to_read()
        profiler.stop()
        trace = json.loads(profiler.dumps())
        counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
        names = {e["name"] for e in counters}
        assert "op.invoke" in names
        ev = next(e for e in counters if e["name"] == "op.invoke")
        assert sum(ev["args"].values()) >= 1

    def test_tensorboard_export_roundtrip(self, tmp_path):
        from mxnet_tpu.contrib.tensorboard import read_events
        rm.counter("t.tb.c").inc(7)
        rm.gauge("t.tb.g").set(1.5)
        rm.histogram("t.tb.h").observe(2.0)
        rm.dump_tensorboard(logdir=str(tmp_path), step=3)
        files = list(tmp_path.iterdir())
        assert len(files) == 1
        tags = {}
        for ev in read_events(str(files[0])):
            tags.update(ev["values"])
        assert tags["t.tb.c"] == pytest.approx(7.0)
        assert tags["t.tb.g"] == pytest.approx(1.5)
        assert tags["t.tb.h.count"] == pytest.approx(1.0)
        assert tags["t.tb.h.mean"] == pytest.approx(2.0)

    def test_snapshot_plain_dict(self):
        rm.counter("t.snap").inc(2)
        snap = rm.snapshot()
        assert snap["t.snap"]["type"] == "counter"
        assert snap["t.snap"]["values"][""] == 2


    def test_prometheus_label_value_escaping(self):
        """Label values are arbitrary user strings (model names): quote,
        backslash, and newline must be escaped per the exposition format
        or a scraper rejects the whole scrape."""
        c = rm.counter("t.prom.esc", labelnames=("model",))
        c.inc(model='net"v2\\x\n')
        txt = rm.dump_prometheus()
        assert 't_prom_esc_total{model="net\\"v2\\\\x\\n"} 1' in txt

    def test_tracked_gauge_resampled_at_export(self):
        """engine.tracked_arrays re-samples the weak dict at scrape time
        — after arrays die it must not keep reporting the stale high
        value set at the last track()."""
        import gc
        arrays = [nd.ones((2,)) for _ in range(50)]
        mx.waitall()
        assert rm.ENGINE_TRACKED.value() >= 50
        del arrays
        gc.collect()
        rm.dump_prometheus()                # runs collect hooks
        from mxnet_tpu.engine import Engine
        assert rm.ENGINE_TRACKED.value() == len(Engine.get()._live)
        assert rm.ENGINE_TRACKED.value() < 50


class TestInstrumentation:
    def test_op_invoke_counter_and_latency(self):
        a = nd.ones((8, 8))
        b = nd.ones((8, 8))
        nd.dot(a, b).wait_to_read()
        assert rm.OP_INVOKE.value(op="dot") >= 1
        assert rm.OP_DISPATCH_SECONDS.count(op="dot") >= 1
        assert "op_invoke_total" in rm.dump_prometheus()

    def test_engine_waitall_and_watermark(self):
        nd.ones((4,))
        mx.waitall()
        assert rm.ENGINE_WAITALL.value() >= 1
        assert rm.ENGINE_WAITALL_SECONDS.count() >= 1
        assert rm.ENGINE_TRACKED_PEAK.value() >= 1

    def test_io_batches_counter(self):
        data = np.random.rand(10, 3).astype(np.float32)
        it = mx.io.NDArrayIter(data, np.zeros(10, np.float32),
                               batch_size=5)
        n = sum(1 for _ in it)
        assert n == 2
        assert rm.IO_BATCHES.value() == 2
        assert "io_batches_total 2" in rm.dump_prometheus()

    def test_kvstore_push_pull_bytes(self):
        kv = mx.kv.create("local")
        v = nd.ones((16,))          # 64 bytes float32
        kv.init("w", v)
        kv.push("w", nd.ones((16,)))
        out = nd.zeros((16,))
        kv.pull("w", out=out)
        assert rm.KV_PUSH.value() == 1
        assert rm.KV_PUSH_BYTES.value() == 64
        assert rm.KV_PULL.value() == 1
        assert rm.KV_PULL_BYTES.value() == 64
        assert "kvstore_push_bytes_total 64" in rm.dump_prometheus()

    def test_trainer_step_histogram(self):
        from mxnet_tpu import autograd, gluon
        net = gluon.nn.Dense(2)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        x = nd.ones((4, 3))
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(4)
        assert rm.TRAINER_STEP_SECONDS.count() == 1
        assert "trainer_step_seconds_bucket" in rm.dump_prometheus()

    def test_trainer_grad_norm_gauge_gated(self, monkeypatch):
        from mxnet_tpu import autograd, gluon
        monkeypatch.setattr(rm, "_GRAD_NORM", True)
        net = gluon.nn.Dense(2)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        x = nd.ones((4, 3))
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(4)
        assert rm.TRAINER_GRAD_NORM.value() > 0

    def test_speedometer_publishes_samples_per_sec(self):
        import time as _time
        from mxnet_tpu.callback import Speedometer

        class _Param:
            epoch, nbatch, eval_metric = 0, 0, None

        sp = Speedometer(batch_size=32, frequent=1)
        p = _Param()
        sp(p)                       # initializes the timer
        _time.sleep(0.01)
        p.nbatch = 1
        sp(p)                       # publishes the gauge
        assert rm.TRAINER_SAMPLES_PER_SEC.value() > 0
        assert "trainer_samples_per_sec" in rm.dump_prometheus()

    def test_speedometer_line_is_upstreams(self, caplog):
        """The log line is the reference Speedometer's, with and
        without an ``eval_metric``: throughput, then the metric's
        values, and nothing after them."""
        import logging
        from mxnet_tpu.callback import Speedometer

        class _Metric:
            def get(self):
                return ["acc", "ce"], [0.5, 1.25]

            def reset(self):
                pass

        class _Param:
            epoch, nbatch, eval_metric = 3, 0, None

        lines = []
        for metric in (None, _Metric()):
            sp = Speedometer(batch_size=32, frequent=1)
            p = _Param()
            p.eval_metric = metric
            sp(p)                   # initializes the timer
            p.nbatch = 7
            caplog.clear()
            with caplog.at_level(logging.INFO):
                sp(p)
            (record,) = caplog.records
            lines.append(record.getMessage())
        speed = r"Epoch\[3\] Batch \[7\] Speed: \d+\.\d\d samples/sec"
        assert re.fullmatch(speed, lines[0]), lines[0]
        assert re.fullmatch(speed + " acc=0.500000 ce=1.250000",
                            lines[1]), lines[1]

    def test_after_train_step_all_acceptance_metrics_present(self):
        """ISSUE acceptance: one train step + one io batch yields
        non-zero op_invoke_total, io_batches_total and
        trainer_step_seconds lines in the Prometheus dump."""
        from mxnet_tpu import autograd, gluon
        data = np.random.rand(8, 3).astype(np.float32)
        it = mx.io.NDArrayIter(data, np.zeros(8, np.float32),
                               batch_size=8)
        batch = next(it)
        net = gluon.nn.Dense(2)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        with autograd.record():
            loss = net(batch.data[0]).sum()
        loss.backward()
        trainer.step(8)
        txt = rm.dump_prometheus()
        assert rm.OP_INVOKE.total() > 0 and "op_invoke_total" in txt
        assert "io_batches_total 1" in txt
        assert "trainer_step_seconds_count 1" in txt
