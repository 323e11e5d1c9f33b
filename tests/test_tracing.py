"""Request tracing + flight recorder (mxnet_tpu.tracing — ISSUE-8).

Covers: tracer core (ids/links/tags/no-op path/sampling/ring), span
concurrency across the batcher worker pool and the decode-engine step
loop (also under MXNET_ENGINE_SANITIZE), histogram exemplars + the
label-cardinality guard, the traced serving round trip (predict +
generate span chains, exemplar link, zero-new-programs criterion), and
the flight recorder (debug_state, incident dumps, exporters).

All serving models here are numpy fakes or tiny jit programs — the
suite must stay cheap under the tier-1 budget.
"""
import json
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, runtime_metrics as rm, serving
from mxnet_tpu import tracing as tr
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving.decode import DecodeEngine


@pytest.fixture(autouse=True)
def _tracing_on():
    """Enable + zero the tracer per test, restore the off default."""
    tr.reset()
    tr.enable(sample=1.0)
    yield
    tr.disable()
    tr.reset()
    tr.TRACER.set_sample(1.0)


@pytest.fixture
def metrics():
    rm.reset()
    rm.enable()
    yield rm
    rm.disable()
    rm.reset()


def _span_index(trace):
    return {s["name"]: s for s in trace["spans"]}


def _assert_links(trace):
    """Every span belongs to the trace and parents resolve inside it
    (the root's parent is None)."""
    ids = {s["span_id"] for s in trace["spans"]}
    for s in trace["spans"]:
        assert s["trace_id"] == trace["trace_id"], s
        assert s["parent_id"] is None or s["parent_id"] in ids, s


class FakeLM:
    """Decode-model protocol in pure numpy (zero compiles): prefill
    emits one-hot of (length % vocab), decode emits (token+1) % vocab."""

    vocab_size = 8
    max_context = 16

    def prefill(self, tokens, length, block_table):
        return np.eye(self.vocab_size,
                      dtype=np.float32)[int(length) % self.vocab_size]

    def decode_step(self, tokens, positions, block_tables):
        out = np.zeros((tokens.shape[0], self.vocab_size), np.float32)
        out[np.arange(tokens.shape[0]),
            (tokens + 1) % self.vocab_size] = 1.0
        return out


def _decode_cfg(**kw):
    base = dict(decode_page_size=4, decode_pool_pages=16,
                decode_max_batch=2, decode_max_new_tokens=4)
    base.update(kw)
    return serving.ServingConfig(**base)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------
class TestTracerCore:
    def test_root_child_links_and_tags(self):
        root = tr.trace("req", model="m")
        assert root.sampled
        with root:
            with tr.span("child", rows=3) as c:
                c.set_tag("extra", "x")
                # thread-local nesting: grandchild parents to child
                with tr.span("grandchild"):
                    pass
        t = tr.TRACER.last(root="req")
        assert t is not None
        _assert_links(t)
        idx = _span_index(t)
        assert idx["req"]["parent_id"] is None
        assert idx["child"]["parent_id"] == idx["req"]["span_id"]
        assert idx["grandchild"]["parent_id"] == idx["child"]["span_id"]
        assert idx["child"]["tags"] == {"rows": 3, "extra": "x"}
        assert t["duration"] >= 0

    def test_disabled_path_is_noop(self):
        """Mirror of the metrics-disabled test: with the switch off,
        every entry point returns the shared no-op singleton and
        records nothing."""
        tr.disable()
        assert tr.trace("x") is tr._NOOP
        assert tr.span("x") is tr._NOOP
        assert tr.record_span("x", None, 0.0, 1.0) is None
        assert tr.current_span() is None
        assert tr.current_context() is None
        tr.tag("k", "v")                       # no current span: no-op
        with tr.trace("x") as s:
            assert s is tr._NOOP
            s.set_tag("a", 1)
            s.end()
        st = tr.TRACER.stats()
        assert st["traces_started"] == 0
        assert st["spans"] == 0
        assert not st["enabled"]

    def test_noop_overhead_is_flat(self):
        """The off path must not allocate per call — same object every
        time, and a tight loop stays in the same cost class as the
        metrics-disabled path (no growth assertions on wall time; CI
        machines throttle)."""
        tr.disable()
        spans = {id(tr.span("x")) for _ in range(1000)}
        assert spans == {id(tr._NOOP)}

    def test_span_without_parent_is_noop(self):
        """span() never roots a trace — only trace() does, so helper
        code deep in the stack cannot create orphan traces."""
        assert tr.span("orphan") is tr._NOOP
        assert tr.TRACER.stats()["traces_started"] == 0

    def test_cross_thread_start_end(self):
        root = tr.trace("req")
        ctx = root.context
        q = tr.span("queue_wait", parent=ctx)

        def worker():
            e = tr.span("execute", parent=ctx)
            q.end(slot=0)
            e.end()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        root.end()
        trace = tr.TRACER.last(root="req")
        idx = _span_index(trace)
        assert set(idx) == {"req", "queue_wait", "execute"}
        assert idx["queue_wait"]["tags"] == {"slot": 0}
        # the span remembers the thread it was STARTED on
        assert idx["queue_wait"]["thread"] != idx["execute"]["thread"]
        _assert_links(trace)

    def test_end_is_idempotent(self):
        root = tr.trace("req")
        c = tr.span("c", parent=root.context)
        c.end()
        t1 = c.t1
        c.end(late="tag")
        assert c.t1 == t1
        root.end()
        spans = tr.TRACER.last(root="req")["spans"]
        assert [s["name"] for s in spans].count("c") == 1
        # the second end() returned before tagging: "late" never lands
        c_dict = [s for s in spans if s["name"] == "c"][0]
        assert "late" not in c_dict["tags"]

    def test_late_span_after_completion_dropped(self):
        root = tr.trace("req")
        ctx = root.context
        root.end()                              # trace completes
        tr.span("straggler", parent=ctx).end()
        t = tr.TRACER.last(root="req")
        assert [s["name"] for s in t["spans"]] == ["req"]
        assert tr.TRACER.stats()["spans_dropped"] == 1

    def test_record_span_explicit_interval(self):
        root = tr.trace("req")
        tr.record_span("step", root.context, 10.0, 10.5,
                       {"step": 1})
        root.end()
        idx = _span_index(tr.TRACER.last(root="req"))
        assert idx["step"]["t0"] == 10.0 and idx["step"]["t1"] == 10.5

    def test_error_tag_on_exception(self):
        with pytest.raises(ValueError):
            with tr.trace("req"):
                with tr.span("inner"):
                    raise ValueError("boom")
        idx = _span_index(tr.TRACER.last(root="req"))
        assert idx["inner"]["tags"]["error"] == "ValueError"
        assert idx["req"]["tags"]["error"] == "ValueError"

    def test_sampling_stride_deterministic(self):
        tr.TRACER.set_sample(0.25)
        kept = [tr.trace("t").sampled for _ in range(16)]
        assert sum(kept) == 4
        st = tr.TRACER.stats()
        assert st["traces_unsampled"] == 12
        # unsampled roots are the no-op span: no context to propagate
        tr.TRACER.set_sample(0.0)
        s = tr.trace("never")
        assert s is tr._NOOP and s.context is None

    def test_sample_rate_validated(self):
        with pytest.raises(MXNetError):
            tr.TRACER.set_sample(1.5)

    def test_ring_eviction_order(self):
        t2 = tr.Tracer(ring=3, sample=1.0)
        for i in range(5):
            t2.start_trace(f"r{i}").end()
        assert [x["root"] for x in t2.traces()] == ["r2", "r3", "r4"]
        st = t2.stats()
        assert st["traces_evicted"] == 2
        assert st["traces_completed"] == 5
        assert t2.find("nope") is None

    def test_span_cap_per_trace(self, monkeypatch):
        monkeypatch.setattr(tr, "_MAX_SPANS_PER_TRACE", 4)
        root = tr.trace("req")
        for i in range(10):
            tr.span(f"s{i}", parent=root.context).end()
        root.end()
        t = tr.TRACER.last(root="req")
        # 4 kept (incl. root's own slot usage: 4 children, root dropped
        # past the cap but still completes the trace)
        assert len(t["spans"]) == 4
        assert t["dropped_spans"] == 7

    def test_active_trace_bound(self, monkeypatch):
        monkeypatch.setattr(tr, "_MAX_ACTIVE_TRACES", 3)
        roots = [tr.trace(f"r{i}") for i in range(5)]
        st = tr.TRACER.stats()
        assert st["active"] == 3
        assert st["traces_aborted"] == 2
        # the aborted (oldest) roots end into the void, not a crash
        for r in roots:
            r.end()
        assert tr.TRACER.stats()["completed"] == 3

    def test_concurrent_span_stress(self):
        """Many threads opening/closing spans on a shared trace: every
        finished span lands exactly once, counters stay consistent."""
        root = tr.trace("req")
        ctx = root.context
        n_threads, n_spans = 8, 50

        def worker(k):
            for i in range(n_spans):
                s = tr.span(f"w{k}.{i}", parent=ctx)
                s.end()

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        root.end()
        t = tr.TRACER.last(root="req")
        assert len(t["spans"]) == n_threads * n_spans + 1
        _assert_links(t)


class TestExporters:
    def _one_trace(self):
        root = tr.trace("req", model="m")
        with root:
            with tr.span("child", rows=2):
                pass
        return tr.TRACER.last(root="req")

    def test_chrome_trace_valid(self, tmp_path):
        t = self._one_trace()
        ct = tr.to_chrome_trace(t)
        json.dumps(ct)                          # serializable
        events = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
        assert len(events) == 2
        for e in events:
            assert e["dur"] >= 0 and "trace_id" in e["args"]
        path = tr.dump_chrome_trace(str(tmp_path / "t.json"), t)
        assert json.load(open(path))["traceEvents"]

    def test_jsonl(self, tmp_path):
        t = self._one_trace()
        text = tr.dump_jsonl(str(tmp_path / "t.jsonl"), t)
        lines = [json.loads(l) for l in text.splitlines()]
        assert {l["name"] for l in lines} == {"req", "child"}
        assert all(l["root"] == "req" for l in lines)
        assert open(str(tmp_path / "t.jsonl")).read() == text


# ---------------------------------------------------------------------------
# exemplars + cardinality guard (runtime_metrics)
# ---------------------------------------------------------------------------
class TestExemplars:
    def test_exemplar_per_bucket_latest_wins(self, metrics):
        h = rm.histogram("t.tr.ex", labelnames=("m",),
                         buckets=(0.1, 1.0))
        h.observe(0.05, exemplar="a", m="x")
        h.observe(0.06, exemplar="b", m="x")   # same bucket: b wins
        h.observe(0.5, exemplar="c", m="x")
        h.observe(5.0, exemplar="d", m="x")
        ex = h.exemplars(m="x")
        assert ex[0] == ("b", 0.06)
        assert ex[1] == ("c", 0.5)
        assert ex[2] == ("d", 5.0)

    def test_exemplar_for_quantile_nearest(self, metrics):
        h = rm.histogram("t.tr.q", buckets=(0.1, 1.0))
        for _ in range(99):
            h.observe(0.05, exemplar="fast")
        h.observe(5.0, exemplar="slow")
        assert h.exemplar_for_quantile(0.99, ) in ("fast", "slow")
        assert h.exemplar_for_quantile(1.0) == "slow"
        assert h.exemplar_for_quantile(0.5) == "fast"
        # no data -> None; exemplar-less observations -> nearest search
        h2 = rm.histogram("t.tr.q2", buckets=(0.1,))
        assert h2.exemplar_for_quantile(0.99) is None
        h2.observe(0.05)
        assert h2.exemplar_for_quantile(0.99) is None
        with pytest.raises(MXNetError):
            h.exemplar_for_quantile(1.5)

    def test_exemplar_disabled_noop(self, metrics):
        rm.disable()
        h = rm.histogram("t.tr.exoff", buckets=(1.0,))
        h.observe(0.5, exemplar="a")
        assert h.count() == 0
        assert h.exemplar_for_quantile(0.99) is None

    def test_prometheus_renders_exemplar(self, metrics):
        h = rm.histogram("t.tr.prom", buckets=(1.0,))
        h.observe(0.5, exemplar="tid123")
        txt = rm.dump_prometheus()
        line = [l for l in txt.splitlines()
                if l.startswith("t_tr_prom_bucket")][0]
        assert '# {trace_id="tid123"} 0.5' in line


class TestCardinalityGuard:
    def test_counter_clamps_and_warns_once(self, metrics, caplog):
        c = rm.counter("t.tr.card", labelnames=("who",))
        c.max_label_sets = 4
        import logging
        with caplog.at_level(logging.WARNING, logger="mxnet_tpu"):
            for i in range(12):
                c.inc(who=f"u{i}")
        warns = [r for r in caplog.records
                 if "t.tr.card" in r.getMessage()]
        assert len(warns) == 1                  # warn once
        snap = c._snapshot()
        assert len(snap) == 5                   # bound + overflow
        assert snap[(rm._OVERFLOW_LABEL,)] == 8
        assert c.total() == 12                  # aggregate intact

    def test_existing_series_keep_updating_past_bound(self, metrics):
        c = rm.counter("t.tr.card2", labelnames=("who",))
        c.max_label_sets = 2
        c.inc(who="a")
        c.inc(who="b")
        c.inc(who="c")                          # clamped
        c.inc(who="a")                          # existing: not clamped
        assert c.value(who="a") == 2
        assert c.value(who="c") == 0            # folded into overflow

    def test_gauge_and_histogram_guard(self, metrics):
        g = rm.gauge("t.tr.cardg", labelnames=("w",))
        g.max_label_sets = 2
        for i in range(5):
            g.set(i, w=f"u{i}")
            g.set_max(i, w=f"u{i}")
            g.inc(w=f"u{i}")
        assert len(g._snapshot()) == 3
        h = rm.histogram("t.tr.cardh", labelnames=("w",),
                         buckets=(1.0,))
        h.max_label_sets = 2
        for i in range(5):
            h.observe(0.5, w=f"u{i}")
        assert len(h._snapshot()) == 3

    def test_unlabeled_metrics_unbounded_by_guard(self, metrics):
        c = rm.counter("t.tr.nolabel")
        c.max_label_sets = 0
        c.inc()
        assert c.value() == 1


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------
def _function_server(**cfg_kw):
    repo = serving.ModelRepository()
    repo.add_function("echo", lambda x: x * 2.0,
                      [{"shape": [None, 3], "dtype": "float32"}])
    cfg = serving.ServingConfig(**cfg_kw) if cfg_kw \
        else serving.ServingConfig()
    return serving.ModelServer(repo, cfg), repo


class TestServingTracing:
    def test_predict_span_chain_and_exemplar(self, metrics):
        srv, repo = _function_server()
        try:
            out = srv.predict("echo", np.ones((2, 3), np.float32),
                              timeout=60)
            np.testing.assert_allclose(out, 2.0)
        finally:
            srv.stop()
        t = tr.TRACER.last(root="serving.predict")
        assert t is not None
        _assert_links(t)
        idx = _span_index(t)
        assert {"serving.predict", "serving.admit",
                "serving.queue_wait", "serving.batch",
                "serving.execute"} <= set(idx)
        b = idx["serving.batch"]
        assert b["tags"]["bucket_outcome"] in ("miss", "mem_hit",
                                               "disk_hit")
        assert b["tags"]["bucket"] == 2 and b["tags"]["rows"] == 2
        assert idx["serving.execute"]["parent_id"] == b["span_id"]
        # exemplar: the p99 resolves to this trace
        ex = rm.SERVING_REQUEST_SECONDS.exemplar_for_quantile(
            0.99, model="echo")
        assert ex == t["trace_id"]

    def test_coalesced_requests_share_batch_span(self, metrics):
        """Two coalesced requests: each trace gets the batch-assembly
        span (one live, one copied with shared_with), both with the
        same interval."""
        srv, repo = _function_server(max_batch_size=8,
                                     max_latency_us=200000,
                                     num_workers=1)
        try:
            results = [None, None]

            def call(i):
                results[i] = srv.predict(
                    "echo", np.ones((1, 3), np.float32), timeout=60)

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            srv.stop()
        traces = [t for t in tr.TRACER.traces()
                  if t["root"] == "serving.predict"]
        assert len(traces) == 2
        batch_spans = []
        for t in traces:
            _assert_links(t)
            idx = _span_index(t)
            assert "serving.batch" in idx
            batch_spans.append(idx["serving.batch"])
        # coalesced into ONE dispatch: the shared copy names its home
        if any(b["tags"].get("requests") == 2 for b in batch_spans):
            shared = [b for b in batch_spans
                      if "shared_with" in b["tags"]]
            live = [b for b in batch_spans
                    if "shared_with" not in b["tags"]]
            assert len(shared) == 1 and len(live) == 1
            assert shared[0]["tags"]["shared_with"] \
                == live[0]["trace_id"]
            assert shared[0]["t0"] == live[0]["t0"]

    def test_shed_incident_dump(self, metrics, tmp_path, monkeypatch):
        """Load shedding writes ONE debounced flight-recorder dump with
        the server's debug state inside."""
        # isolate incident bookkeeping for this test
        monkeypatch.setitem(tr._INCIDENTS, "last", 0.0)
        monkeypatch.setitem(tr._INCIDENTS, "count", 0)
        monkeypatch.setattr(
            tr, "_INCIDENTS",
            dict(tr._INCIDENTS, paths=type(tr._INCIDENTS["paths"])()))
        gate = threading.Event()
        entered = threading.Event()

        def gated(a):
            entered.set()
            assert gate.wait(60)
            return a

        repo = serving.ModelRepository()
        repo.add_function("gated", gated,
                          [{"shape": [None, 1], "dtype": "float32"}])
        cfg = serving.ServingConfig(max_batch_size=1, max_latency_us=1,
                                    queue_depth=2, shed_watermark=1,
                                    num_workers=1)
        srv = serving.ModelServer(repo, cfg)
        payload = np.ones((1, 1), np.float32)
        threads = [threading.Thread(
            target=lambda: srv.predict("gated", payload, timeout=60))]
        threads[0].start()
        assert entered.wait(60)
        deadline = time.monotonic() + 60
        while srv.stats()["queue_depth"] > 0:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        threads.append(threading.Thread(
            target=lambda: srv.predict("gated", payload, timeout=60)))
        threads[1].start()
        while srv.stats()["queue_depth"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        sheds = 0
        for _ in range(3):
            with pytest.raises(serving.ServerOverloadedError):
                srv.predict("gated", payload, timeout=60)
            sheds += 1
        gate.set()
        for t in threads:
            t.join(60)
        srv.stop()
        paths = tr.incident_paths()
        assert len(paths) == 1, paths           # 3 sheds, 1 dump
        rec = json.load(open(paths[0]))
        assert rec["reason"] == "serving.shed"
        assert rec["state"]["stats"]["shed"] >= 1
        assert rec["state"]["queues"], rec["state"]
        import os
        os.unlink(paths[0])

    def test_debug_state_shape(self, metrics):
        srv, repo = _function_server()
        repo.add_decoder("lm", FakeLM())
        try:
            srv.predict("echo", np.ones((1, 3), np.float32), timeout=60)
            srv.generate("lm", [1, 2], max_new_tokens=2, timeout=60)
            state = srv.debug_state()
        finally:
            srv.stop()
        json.dumps(state, default=str)          # serializable
        assert state["server"] == srv.name
        assert state["stats"]["completed"] >= 1
        assert state["repository"]["echo"]["current"] == 1
        assert state["repository"]["lm"]["versions"][0]["kind"] \
            == "decoder"
        (eng_state,) = state["decoders"].values()
        assert eng_state["model"] == "lm"
        assert eng_state["free_slots"] == eng_state["max_batch"]
        assert "allocator" in eng_state
        assert state["tracer"]["enabled"]

    def test_untraced_run_records_nothing(self, metrics):
        tr.disable()
        srv, repo = _function_server()
        repo.add_decoder("lm", FakeLM())
        try:
            srv.predict("echo", np.ones((1, 3), np.float32), timeout=60)
            srv.generate("lm", [1], max_new_tokens=2, timeout=60)
        finally:
            srv.stop()
        st = tr.TRACER.stats()
        assert st["traces_started"] == 0 and st["spans"] == 0

    def test_traced_request_compiles_nothing_new(self, metrics):
        """ISSUE-8 acceptance: tracing on/off does not change the jit
        program count — one tiny compiled program serves traced and
        untraced requests alike."""
        import jax
        f = jax.jit(lambda x: x * 2.0)
        repo = serving.ModelRepository()
        repo.add_function("jit", lambda x: f(x),
                          [{"shape": [None, 3], "dtype": "float32"}])
        srv = serving.ModelServer(repo)
        try:
            srv.predict("jit", np.ones((2, 3), np.float32), timeout=60)
            baseline = f._cache_size()
            assert baseline >= 1
            srv.predict("jit", np.ones((2, 3), np.float32), timeout=60)
            tr.disable()
            srv.predict("jit", np.ones((2, 3), np.float32), timeout=60)
            assert f._cache_size() == baseline
        finally:
            srv.stop()


class TestDecodeTracing:
    def test_generate_span_chain(self, metrics):
        srv, repo = _function_server()
        repo.add_decoder("lm", FakeLM())
        try:
            toks = srv.generate("lm", [1, 2, 3], max_new_tokens=3,
                                timeout=60)
            assert len(toks) == 3
        finally:
            srv.stop()
        t = tr.TRACER.last(root="serving.generate")
        assert t is not None
        _assert_links(t)
        idx = _span_index(t)
        need = {"serving.generate", "decode.admission",
                "decode.queue_wait", "decode.prefill", "decode.step",
                "decode.evict"}
        assert need <= set(idx), sorted(idx)
        assert idx["decode.admission"]["tags"]["prompt_tokens"] == 3
        assert idx["decode.queue_wait"]["tags"]["slot"] is not None
        assert idx["decode.prefill"]["tags"]["kv_pages"] >= 1
        assert idx["decode.step"]["tags"]["context_len"] >= 3
        ev = idx["decode.evict"]["tags"]
        assert ev["reason"] == "length"
        assert ev["pages_released"] >= 1
        assert ev["generated_tokens"] == 3
        # exemplar on TTFT
        ex = rm.SERVING_DECODE_TTFT_SECONDS.exemplar_for_quantile(
            0.99, model="lm")
        assert ex == t["trace_id"]

    def test_sampled_out_generate_stays_off_path(self, metrics):
        """Review regression: a sampled-out ModelServer.generate() must
        NOT re-enter head sampling in DecodeEngine.submit and root a
        fragment decode.request trace — one request, one decision."""
        tr.TRACER.set_sample(0.0)
        srv, repo = _function_server()
        repo.add_decoder("lm", FakeLM())
        try:
            srv.generate("lm", [1, 2], max_new_tokens=2, timeout=60)
        finally:
            srv.stop()
        st = tr.TRACER.stats()
        assert st["traces_started"] == 0, st
        assert st["spans"] == 0, st
        # exactly ONE sampling decision was consumed for the request
        assert st["traces_unsampled"] == 1, st

    def test_shed_trace_keeps_admission_span(self):
        """Review regression: on an engine-rooted shed the admission
        span (carrying the shed tag) must land BEFORE the root
        completes the trace — not be dropped as a straggler."""
        eng = DecodeEngine(FakeLM(), _decode_cfg(queue_depth=1),
                           model_name="d", autostart=False)
        eng._started = True
        eng.submit([1], max_new_tokens=2)       # fills the line
        from mxnet_tpu.serving.server import ServerOverloadedError
        with pytest.raises(ServerOverloadedError):
            eng.submit([2], max_new_tokens=2)
        t = tr.TRACER.last(root="decode.request")
        assert t is not None
        idx = _span_index(t)
        assert idx["decode.request"]["tags"]["error"] \
            == "ServerOverloadedError"
        assert idx["decode.admission"]["tags"]["shed"] is True
        assert tr.TRACER.stats()["spans_dropped"] == 0

    def test_failed_batch_trace_keeps_error_batch_span(self, metrics):
        """Review regression: a failing batch still lands its
        error-tagged serving.batch span in the request trace."""
        repo = serving.ModelRepository()

        def broken(x):
            raise RuntimeError("kaboom")

        repo.add_function("broken", broken,
                          [{"shape": [None, 1], "dtype": "float32"}])
        srv = serving.ModelServer(repo)
        try:
            with pytest.raises(RuntimeError, match="kaboom"):
                srv.predict("broken", np.ones((1, 1), np.float32),
                            timeout=60)
        finally:
            srv.stop()
        t = tr.TRACER.last(root="serving.predict")
        idx = _span_index(t)
        assert idx["serving.batch"]["tags"]["error"] == "RuntimeError"
        assert idx["serving.predict"]["tags"]["error"] == "RuntimeError"

    def test_direct_engine_roots_its_own_trace(self):
        """A DecodeEngine driven without a ModelServer still yields a
        complete trace (engine-owned root, closed at eviction)."""
        eng = DecodeEngine(FakeLM(), _decode_cfg(), model_name="d")
        eng.start()
        try:
            out = eng.generate([1, 2], max_new_tokens=2, timeout=60)
            assert len(out) == 2
        finally:
            assert eng.stop(timeout=60)
        t = tr.TRACER.last(root="decode.request")
        assert t is not None
        _assert_links(t)
        names = set(_span_index(t))
        assert {"decode.request", "decode.admission",
                "decode.queue_wait", "decode.prefill",
                "decode.step", "decode.evict"} <= names

    def test_step_span_stride(self, metrics):
        """decode.step spans record the first step then every Nth."""
        eng = DecodeEngine(FakeLM(), _decode_cfg(decode_page_size=2,
                                                 decode_pool_pages=16),
                           model_name="d")
        eng.start()
        try:
            eng.generate([1], max_new_tokens=12, timeout=60)
        finally:
            assert eng.stop(timeout=60)
        t = tr.TRACER.last(root="decode.request")
        steps = [s["tags"]["step"] for s in t["spans"]
                 if s["name"] == "decode.step"]
        from mxnet_tpu.serving import decode as _dec
        expect = [n for n in range(1, 12)
                  if n == 1 or n % _dec._STEP_SPAN_EVERY == 0]
        assert steps == expect, steps

    def test_spans_across_engine_thread_under_sanitizer(self,
                                                        monkeypatch):
        """Tracer + serving locks under MXNET_ENGINE_SANITIZE: spans
        opened in the submitter thread and closed in the step loop must
        not create a lock-order inversion."""
        monkeypatch.setattr(engine, "_SANITIZE", True)
        engine._LOCK_ORDERS.reset()
        try:
            # fresh sanitized tracer so Tracer._lock participates in
            # the order graph alongside the engine's _SanCondition
            monkeypatch.setattr(tr, "TRACER",
                                tr.Tracer(ring=16, sample=1.0))
            eng = DecodeEngine(FakeLM(), _decode_cfg(), model_name="d")
            eng.start()
            try:
                outs = []
                threads = [threading.Thread(
                    target=lambda: outs.append(eng.generate(
                        [1, 2], max_new_tokens=3, timeout=60)))
                    for _ in range(4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(60)
                assert len(outs) == 4
            finally:
                assert eng.stop(timeout=60)
            t = tr.TRACER.last(root="decode.request")
            assert t is not None
            _assert_links(t)
        finally:
            engine._LOCK_ORDERS.reset()

    def test_cancelled_before_admission_evicts_with_trace(self):
        """A request cancelled while WAITING still completes its trace
        (queue-wait error-tagged, evict span with zero pages)."""
        eng = DecodeEngine(FakeLM(), _decode_cfg(), model_name="d",
                           autostart=False)
        eng._started = True                     # accept submits
        seq = eng.submit([1, 2], max_new_tokens=2)
        seq.cancelled = True
        eng._admit()
        with pytest.raises(MXNetError, match="cancelled"):
            eng.result(seq, timeout=5)
        t = tr.TRACER.last(root="decode.request")
        assert t is not None
        idx = _span_index(t)
        assert idx["decode.evict"]["tags"]["reason"] == "cancelled"
        assert idx["decode.evict"]["tags"]["pages_released"] == 0
        assert idx["decode.queue_wait"]["tags"]["error"] == "cancelled"


class TestFlightRecorder:
    def test_flight_record_shape(self):
        root = tr.trace("req")
        root.end()
        rec = tr.flight_record(state={"k": 1})
        assert rec["tracer"]["completed"] == 1
        assert rec["traces"][0]["root"] == "req"
        assert rec["state"] == {"k": 1}

    def test_record_incident_debounce_and_callable_state(self,
                                                         tmp_path,
                                                         monkeypatch):
        monkeypatch.setattr(
            tr, "_INCIDENTS",
            {"last": 0.0, "count": 0,
             "paths": type(tr._INCIDENTS["paths"])()})
        calls = []

        def state():
            calls.append(1)
            return {"depth": 3}

        p1 = tr.record_incident("test", state,
                                path=str(tmp_path / "f1.json"))
        assert p1 is not None
        assert tr.record_incident("test", state,
                                  path=str(tmp_path / "f2.json")) \
            is None                             # debounced
        p3 = tr.record_incident("test", state,
                                path=str(tmp_path / "f3.json"),
                                min_interval=0.0)
        assert p3 is not None
        assert len(calls) == 2                  # debounce skips state()
        rec = json.load(open(p1))
        assert rec["reason"] == "test" and rec["state"] == {"depth": 3}
        assert tr.incident_paths() == [p1, p3]

    def test_record_incident_disabled_noop(self, tmp_path):
        tr.disable()
        assert tr.record_incident("x", {},
                                  path=str(tmp_path / "x.json")) is None

    def test_incident_survives_failing_state_fn(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setattr(
            tr, "_INCIDENTS",
            {"last": 0.0, "count": 0,
             "paths": type(tr._INCIDENTS["paths"])()})

        def bad_state():
            raise RuntimeError("broken")

        p = tr.record_incident("x", bad_state,
                               path=str(tmp_path / "x.json"))
        rec = json.load(open(p))
        assert "debug_state failed" in rec["state"]["error"]


# ---------------------------------------------------------------------------
# phases on the profiler's clock (tracing.phase; ISSUE 31)
# ---------------------------------------------------------------------------
import contextlib
import glob
import os

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _session(trace_dir):
    """One JAX profiler session, host annotations only."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _mx_events(trace_dir):
    """(the session's ``mx.`` events by start, each with its tags and
    the line it ran on; the session's start in epoch nanoseconds)."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events, began = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            began = int(dict(plane.stats)["profile_start_time"])
        for n, line in enumerate(plane.lines):
            events += [{"name": e.name, "t0": e.start_ns, "t1": e.end_ns,
                        "tags": dict(e.stats), "line": (plane.name, n)}
                       for e in line.events if e.name.startswith("mx.")]
    return sorted(events, key=lambda e: e["t0"]), began


def _within(inner, outer):
    """By time only: a watched call is on another thread's line."""
    return outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


def _inside(inner, outer):
    return _within(inner, outer) and inner["line"] == outer["line"]


class TestPhase:
    def test_silent_without_a_session_and_blind_to_the_switch(
            self, tmp_path):
        with tr.phase("test.before", n=1) as ph:    # no session: no-op
            ph.set_metadata(m=2)
        tr.disable()            # MXNET_TRACE is not phase()'s switch
        with _session(tmp_path):
            with tr.phase("test.inside", n=2):
                pass
        events, _began = _mx_events(tmp_path)
        assert [e["name"] for e in events] == ["mx.test.inside"]
        assert tr.TRACER.stats()["spans"] == 0      # no request span either

    def test_on_the_profilers_epoch_clock_with_tags(self, tmp_path):
        """ISSUE 31's premise: an event's ``start_ns`` counts from the
        session's start, which the ``Task Environment`` plane gives in
        epoch nanoseconds on ``time.time_ns()``'s clock; keyword tags
        and ``set_metadata`` come back as the event's stats."""
        with _session(tmp_path):
            before = time.time_ns()
            with tr.phase("train.step", step=7, compiles=3) as ph:
                entered = time.time_ns()
                ph.set_metadata(admitted=2)
        (event,), began = _mx_events(tmp_path)
        assert event["name"] == "mx.train.step"
        assert event["tags"] == {"step": 7, "compiles": 3, "admitted": 2}
        slack = 2_000_000       # the two clocks are read apart: 2 ms
        assert before - slack <= began + event["t0"] <= entered + slack

    @pytest.mark.parametrize("exporter", ["chrome", "jsonl"])
    def test_request_spans_export_on_the_epoch_clock(self, exporter):
        before = time.time()
        with tr.trace("req"):
            pass
        after = time.time()
        t = tr.TRACER.last(root="req")
        if exporter == "chrome":
            (ev,) = [e for e in tr.to_chrome_trace(t)["traceEvents"]
                     if e.get("ph") == "X"]
            t0, t1 = ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6
        else:
            (rec,) = [json.loads(l) for l in tr.dump_jsonl(traces=t)
                      .splitlines()]
            t0, t1 = rec["t0"], rec["t1"]
        # CLOCK_ANCHOR pairs the two clocks once; they drift by less
        # than this over a test run
        assert before - 0.05 <= t0 <= t1 <= after + 0.05


def _toy_trainer(**kw):
    from mxnet_tpu import gluon, parallel
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
            gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    x = np.random.randn(8, 8).astype("float32")
    y = np.random.randn(8, 4).astype("float32")
    trainer = parallel.ShardedTrainer(
        net, lambda out, y: jnp.mean((out - y) ** 2),
        parallel.make_mesh(dp=kw.pop("dp", 1)), optimizer="adamw",
        optimizer_params={"learning_rate": 1e-3},
        example_inputs=(mx.nd.array(x),), n_labels=1, **kw)
    return trainer, x, y


@contextlib.contextmanager
def _switches(trace, metrics):
    """``MXNET_TRACE`` / ``MXNET_RUNTIME_METRICS`` as given, both off
    again afterwards."""
    tr.disable()
    if trace:
        tr.enable(sample=1.0)
    if metrics:
        rm.enable()
    try:
        yield
    finally:
        tr.disable()
        rm.disable()
        rm.reset()


SWITCHES = {"trace": (True, False), "metrics": (False, True),
            "both": (True, True)}


def _traced_steps(trainer, x, y, trace_dir):
    """The ``mx.`` events of four traced steps after two of warm-up."""
    for _ in range(2):
        trainer.step(x, y).block_until_ready()
    with _session(trace_dir):
        losses = [trainer.step(x, y) for _ in range(4)]
        jax.block_until_ready(losses)
    return _mx_events(trace_dir)[0]


@pytest.fixture(scope="module", params=["plain", "observed"])
def train_trace(request, tmp_path_factory):
    """Four traced steps of a toy ``ShardedTrainer`` with both switches
    off, and with ``MXNET_TRACE`` and ``MXNET_RUNTIME_METRICS`` on:
    ``step()`` has one body and the switches change nothing in it."""
    on = request.param == "observed"
    with _switches(trace=on, metrics=on):
        trainer, x, y = _toy_trainer()
        return _traced_steps(
            trainer, x, y,
            tmp_path_factory.mktemp("train_" + request.param))


class TestTrainPhases:
    def test_h2d_and_dispatch_nest_in_their_step(self, train_trace):
        events = train_trace
        steps = [e for e in events if e["name"] == "mx.train.step"]
        assert len(steps) == 4
        for step in steps:
            for name in ("mx.train.h2d", "mx.train.dispatch"):
                (inner,) = [e for e in events if e["name"] == name
                            and e["tags"]["step"] == step["tags"]["step"]]
                assert _inside(inner, step), (name, inner, step)
        # no deadline: no step waits for the device, observed or not
        assert not [e for e in events if e["name"] == "mx.train.sync"]

    def test_step_tag_rises_by_one(self, train_trace):
        events = train_trace
        tags = [e["tags"]["step"] for e in events
                if e["name"] == "mx.train.step"]
        assert tags == list(range(tags[0], tags[0] + 4)) and tags[0] == 3

    def test_compiles_tag_constant_after_warm_up(self, train_trace):
        events = train_trace
        compiles = {e["tags"]["compiles"] for e in events
                    if e["name"] == "mx.train.step"}
        assert len(compiles) == 1 and compiles.pop() >= 1


@pytest.fixture(scope="module")
def deadline_trace(tmp_path_factory):
    """Four traced steps of a toy trainer under a watchdog deadline:
    the watched call runs to device completion on the deadline's
    thread."""
    tr.disable()
    trainer, x, y = _toy_trainer(step_timeout_ms=60000)
    return _traced_steps(trainer, x, y,
                         tmp_path_factory.mktemp("train_deadline"))


class TestTrainPhasesUnderDeadline:
    def test_one_sync_a_step(self, deadline_trace):
        steps = [e["tags"]["step"] for e in deadline_trace
                 if e["name"] == "mx.train.step"]
        syncs = [e["tags"]["step"] for e in deadline_trace
                 if e["name"] == "mx.train.sync"]
        assert len(steps) == 4 and syncs == steps

    def test_sync_and_dispatch_fall_inside_their_step_by_time(
            self, deadline_trace):
        by_step = {e["tags"]["step"]: e for e in deadline_trace
                   if e["name"] == "mx.train.step"}
        inner = [e for e in deadline_trace
                 if e["name"] in ("mx.train.sync", "mx.train.dispatch")]
        assert len(inner) == 8
        for e in inner:
            step = by_step[e["tags"]["step"]]
            assert _within(e, step), (e, step)
            assert e["line"] != step["line"]    # the deadline's thread
        for step_no in by_step:
            dispatch, sync = [e for e in inner
                              if e["tags"]["step"] == step_no]
            assert (dispatch["name"], sync["name"]) == \
                ("mx.train.dispatch", "mx.train.sync")
            assert dispatch["t1"] <= sync["t0"]

    def test_h2d_stays_on_the_callers_line(self, deadline_trace):
        for step in (e for e in deadline_trace
                     if e["name"] == "mx.train.step"):
            (h2d,) = [e for e in deadline_trace
                      if e["name"] == "mx.train.h2d"
                      and e["tags"]["step"] == step["tags"]["step"]]
            assert _inside(h2d, step), (h2d, step)


@contextlib.contextmanager
def _counting_block_until_ready(monkeypatch):
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda a: calls.append(1) or real(a))
    try:
        yield calls
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("sync", [False, True])
def test_dispatch_blocks_only_when_asked(sync, monkeypatch):
    """The phases add no wait: ``_dispatch_step(sync=False)``, which
    is what ``step()`` calls without a deadline, never calls
    ``block_until_ready``."""
    tr.disable()
    trainer, x, y = _toy_trainer()
    batch = trainer.shard_batch(x, y)
    with _counting_block_until_ready(monkeypatch) as calls:
        out = trainer._dispatch_step(batch, sync=sync)
    jax.block_until_ready(out[-1])
    assert len(calls) == (1 if sync else 0)


@pytest.mark.parametrize("switches", SWITCHES)
def test_step_never_waits_for_observation(switches, monkeypatch):
    """``MXNET_TRACE`` / ``MXNET_RUNTIME_METRICS`` do not change how a
    step is dispatched: ``step()`` returns with the step enqueued.
    Under a deadline it waits exactly once, whatever the switches."""
    with _switches(*SWITCHES[switches]):
        trainer, x, y = _toy_trainer()
        watched, _, _ = _toy_trainer(step_timeout_ms=60000)
        with _counting_block_until_ready(monkeypatch) as calls:
            losses = [trainer.step(x, y) for _ in range(3)]
            assert calls == []
            watched.step(x, y)
            assert calls == [1]
        jax.block_until_ready(losses)


@pytest.mark.parametrize("switches", SWITCHES)
def test_observed_steps_add_no_programs(switches):
    """Observation compiles nothing: one entry in the step's jit cache
    and a constant count of backend compiles over four observed steps
    (an ahead-of-time compile, to ask XLA for the step's FLOPs, would
    count)."""
    from mxnet_tpu import compile_cache
    tr.disable()
    trainer, x, y = _toy_trainer()
    trainer.step(x, y).block_until_ready()      # the one compile
    with _switches(*SWITCHES[switches]):
        before = compile_cache.backend_compiles()
        for _ in range(4):
            trainer.step(x, y).block_until_ready()
        assert compile_cache.backend_compiles() == before
    assert trainer._step._cache_size() == 1


class _CountingIter(mx.io.DataIter):
    """The base class's own ``next()``: three batches of ones."""

    def __init__(self):
        super().__init__(batch_size=4)
        self._left = 3

    def iter_next(self):
        self._left -= 1
        return self._left >= 0

    def getdata(self):
        return [mx.nd.ones((4, 2))]

    def getlabel(self):
        return [mx.nd.zeros((4,))]

    def getpad(self):
        return 0

    def getindex(self):
        return None


def _make_iter(kind, tmp_path):
    """An iterator of three batches of four rows."""
    data = np.arange(24, dtype=np.float32).reshape(12, 2)
    if kind == "DataIter":
        return _CountingIter()
    if kind == "NDArrayIter":
        return mx.io.NDArrayIter(data, np.zeros(12, np.float32),
                                 batch_size=4)
    if kind == "PrefetchingIter":
        return mx.io.PrefetchingIter(mx.io.NDArrayIter(
            data, np.zeros(12, np.float32), batch_size=4))
    from test_io import _write_image_rec
    prefix, _labels = _write_image_rec(tmp_path)
    return mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
        data_shape=(3, 32, 32), batch_size=4, shuffle=False)


@pytest.mark.parametrize("kind", ["DataIter", "NDArrayIter",
                                  "PrefetchingIter", "ImageRecordIter"])
def test_data_wait_phase(kind, tmp_path, monkeypatch, metrics):
    """What the train loop waits for its batch is the
    ``mx.train.data_wait`` phase: one event a ``next()`` on the thread
    that called it inside a profiler session; outside one no clock is
    read, whatever ``MXNET_TRACE`` / ``MXNET_RUNTIME_METRICS`` say
    (both are on here)."""
    it = _make_iter(kind, tmp_path)
    me, reads = threading.get_ident(), []
    real = time.perf_counter
    monkeypatch.setattr(
        time, "perf_counter",
        lambda: (reads.append(1) if threading.get_ident() == me
                 else None) or real())
    first = it.next()
    monkeypatch.undo()
    assert reads == [] and first.data[0].shape[0] == 4
    with _session(tmp_path / "trace"):
        with tr.phase("test.caller"):
            got = [it.next(), it.next()]
    events, _began = _mx_events(tmp_path / "trace")
    (caller,) = [e for e in events if e["name"] == "mx.test.caller"]
    waits = [e for e in events if e["name"] == "mx.train.data_wait"
             and e["line"] == caller["line"]]
    assert len(waits) == len(got) == 2
    assert all(_inside(w, caller) for w in waits)
    assert waits[0]["t1"] <= waits[1]["t0"]


class ChainLM:
    """Decode-model protocol in numpy whose every family agrees: the
    next token is (last + 1) mod vocab in prefill, decode step and
    verify window alike, so a draft of the same class is always
    accepted."""

    vocab_size = 32
    max_context = 64

    def _rows(self, tokens):
        rows = np.zeros((len(tokens), self.vocab_size), np.float32)
        rows[np.arange(len(tokens)),
             (np.asarray(tokens) + 1) % self.vocab_size] = 1.0
        return rows

    def prefill(self, tokens, length, block_table):
        return self._rows(tokens[0, int(length) - 1:int(length)])[0]

    def decode_step(self, tokens, positions, block_tables):
        return self._rows(tokens)

    def verify(self, tokens, start, length, block_table):
        return self._rows(tokens[0])


@pytest.fixture(scope="module", params=["plain", "speculative"])
def serve_trace(request, tmp_path_factory):
    """A toy engine on its own thread, three requests over two slots,
    traced from before the first submit to after the last result."""
    tr.disable()
    spec = request.param == "speculative"
    # five tokens each: under speculation the first comes from the
    # prefill, three from one round, and the last, one from the cap,
    # from a plain decode step
    cfg = _decode_cfg(decode_max_new_tokens=5, decode_pool_pages=33,
                      **({"spec_k": 2} if spec else {}))
    eng = DecodeEngine(ChainLM(), cfg, model_name="toy",
                       draft=ChainLM() if spec else None, autostart=True)
    trace_dir = tmp_path_factory.mktemp("serve_" + request.param)
    try:
        with _session(trace_dir):
            seqs = [eng.submit([1, 2, 3 + i], max_new_tokens=5)
                    for i in range(3)]
            for s in seqs:
                assert len(eng.result(s, timeout=60)) == 5
    finally:
        eng.stop()
    return request.param, _mx_events(trace_dir)[0]


class TestServePhases:
    def test_phases_and_their_counts(self, serve_trace):
        mode, events = serve_trace
        by = {}
        for e in events:
            by.setdefault(e["name"], []).append(e)
        want = {"mx.serve.admit", "mx.serve.prefill", "mx.serve.emit",
                "mx.serve.decode_step"}
        if mode == "speculative":
            want |= {"mx.serve.draft", "mx.serve.verify"}
        assert want <= set(by), sorted(by)
        assert len({e["line"] for e in events}) == 1    # the engine's thread
        assert sum(e["tags"]["admitted"] for e in by["mx.serve.admit"]) == 3
        assert all(e["tags"]["queue_wait_us_max"]
                   <= e["tags"]["queue_wait_us_sum"]
                   for e in by["mx.serve.admit"])
        assert [e["tags"]["tokens"] for e in by["mx.serve.prefill"]] \
            == [3, 3, 3]
        assert all(e["tags"]["prefix_hit_tokens"] == 0
                   and e["tags"]["bucket"] >= 3
                   for e in by["mx.serve.prefill"])
        for e in by["mx.serve.decode_step"]:
            t = e["tags"]
            assert 1 <= t["active"] <= t["slots"] == 2, t
            assert 1 <= t["kv_pages_in_use"] <= t["kv_pages_total"] == 32, t
        assert sum(e["tags"]["tokens"] for e in by["mx.serve.emit"]) == 15
        for e in by.get("mx.serve.verify", ()):
            assert 0 <= e["tags"]["accepted"] <= e["tags"]["proposed"]

    def test_one_steps_phases_share_engine_step_and_do_not_overlap(
            self, serve_trace):
        _mode, events = serve_trace
        top = [e for e in events
               if not any(_inside(e, o) for o in events if o is not e)]
        steps = [e["tags"]["engine_step"] for e in top]
        assert steps == sorted(steps)
        for a, b in zip(top, top[1:]):
            assert a["t1"] <= b["t0"], (a, b)
        nested = [e for e in events if e not in top]
        assert {e["name"] for e in nested} <= {"mx.serve.emit"}
        for e in nested:        # the first token's emit, inside its prefill
            (outer,) = [o for o in top if _inside(e, o)]
            assert outer["name"] == "mx.serve.prefill"
            assert outer["tags"]["engine_step"] == e["tags"]["engine_step"]


def _serve_programs():
    """{name: (jitted program, example arguments)} of a tiny paged LM,
    as ``PagedLMAdapter``'s protocol methods call them."""
    from mxnet_tpu.models.transformer_blocks import TransformerDecoderLM
    from mxnet_tpu.serving.decode import PagedLMAdapter
    from mxnet_tpu.serving.kv_cache import PageGeometry
    lm = TransformerDecoderLM(13, units=8, hidden_size=16, num_layers=1,
                              num_heads=2, max_length=16)
    lm.initialize(mx.init.Xavier())
    ad = PagedLMAdapter(lm)
    ad.setup(PageGeometry(page_size=4, pool_pages=9, max_context=16,
                          num_layers=1, num_heads=2, head_dim=4))
    B, P, W = 2, 4, 4
    i32 = lambda *shape: np.zeros(shape, np.int32)      # noqa: E731
    pool = (ad.pool.k_pages, ad.pool.v_pages)
    return {
        "prefill": (ad._prefill_jit,
                    (ad.params, i32(1, 8), np.int32(3), i32(P)) + pool),
        "decode_step": (ad._decode_jit,
                        (ad.params, i32(B), i32(B), i32(B, P)) + pool),
        "verify": (ad._verify_jit, (ad.params, i32(1, W), np.int32(0),
                                    np.int32(2), i32(P)) + pool),
        "verify_batch": (ad._verify_batch_jit,
                         (ad.params, i32(B, W), i32(B), i32(B),
                          i32(B, P)) + pool),
        "copy_pages": (ad._copy_jit, pool + (np.int32(1), np.int32(2))),
    }


@pytest.mark.parametrize("program", [
    "train_step", "train_step_compressed", "prefill", "decode_step",
    "verify", "verify_batch", "copy_pages"])
def test_every_program_lowers_under_its_own_mx_name(program):
    """What a trace's ``XLA Modules`` line reads is ``jit_<name>``: the
    trainer's step and each of the engine's programs have a name of
    their own, so a reader finds them by it (a ``functools.partial``
    lowers as ``jit__unknown``)."""
    if program.startswith("train_step"):
        compressed = program.endswith("compressed")
        trainer, x, y = _toy_trainer(
            **({"compression": "int8", "dp": 8} if compressed else {}))
        args = (trainer.params, trainer.opt_state)
        if compressed:
            args += (trainer.residuals, jax.random.PRNGKey(1))
        fn, args = trainer._step, args + trainer.shard_batch(x, y)
        want = "jit_mx_train_step"
    else:
        fn, args = _serve_programs()[program]
        want = "jit_mx_serve_" + program
    text = fn.lower(*args).as_text()
    assert f"module @{want} " in text, text[:200]
    if program == "train_step":
        # the scopes the device metrics read, the block's name under them
        debug = fn.lower(*args).as_text(debug_info=True)
        for scope in ("jvp(mx.fwd)/dense", "transpose(jvp(mx.fwd))/dense",
                      "jvp(mx.loss)", "mx.optim/"):
            assert f"jit(mx_train_step)/{scope}" in debug, scope
    if program == "train_step_compressed":
        assert "mx.collective" in fn.lower(*args).as_text(debug_info=True)
