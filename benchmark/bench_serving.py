"""Serving benchmark / smoke harness: export LeNet -> serve under
concurrent load -> emit one JSON result.

Prints ONE JSON line (the last stdout line is the authoritative
result) with throughput, p50/p99 latency, batch occupancy,
compiled-program count, cold-start-to-first-response, persistent
compile-cache hit/miss counts, and shed count:

  {"metric": "serving.throughput", "value": ..., "unit": "req/s",
   "p50_ms": ..., "p99_ms": ..., "batch_occupancy_mean": ...,
   "programs": ..., "program_bound": ..., "requests": ...,
   "batches": ..., "shed": ..., "cold_start_ms": ...,
   "compile_cache_hits": ..., "compile_cache_misses": ..., ...}

``--smoke`` (the CI tier, ci/runtime_functions.sh serving_smoke) also
asserts the ISSUE-2 acceptance criteria: 32+ concurrent requests of >=3
distinct batch sizes, at most ceil(log2(max_batch))+1 compiled programs
(via the bucket-cache counter), p99 recorded in the latency histogram,
and load shedding triggering on a saturated bounded queue.

``--cache-roundtrip`` (also run by serving_smoke) is the ISSUE-6
acceptance criterion: it runs the serve loop twice in fresh
subprocesses sharing one compile-cache dir — start server, kill the
process, restart against the same cache — and asserts the warm restart
compiles ZERO new XLA programs (miss counter stays 0) while reporting
cold-start-to-first-response before/after.

``--decode`` (ISSUE-7) drives the autoregressive decode engine
(docs/serving.md §6) under Poisson arrivals of mixed-length requests
and reports tokens/sec, p50/p99 time-to-first-token, p50/p99 per-token
latency, and KV-pool occupancy; with ``--smoke`` it also asserts the
acceptance criteria — continuous batching demonstrably interleaves (a
short request admitted mid-flight finishes before a long one admitted
earlier) and total compiled programs stay <= prefill buckets + 1
across the mixed-length run.

``--decode --shared-prefix [P]`` (ISSUE-12) replays a production-shaped
shared-prompt mix (fraction P of prompts share one long prefix, default
0.8) through the decode engine TWICE — prefix cache off, then on — and
reports TTFT p50/p99 and tokens/sec side by side with the hit ratio
and tokens saved; ``--smoke`` asserts byte-identical outputs, a
hit-ratio that matches the mix, leak-free shared pages, and the
headline criterion: cached TTFT p50 at least 2x better.

``--decode --speculative`` (ISSUE-12) drives speculative decoding on a
deterministic fake pair whose per-call cost is real numpy matmul work
(target heavy, draft ~5%% of it, ~90%% token agreement by
construction) so the tokens/sec win comes from what speculation
actually changes — fewer target calls per emitted token; reports
accept rate and tokens/sec speculative vs plain (``--smoke`` asserts
byte-identical outputs and >= 1.3x tokens/sec).

``--quantized`` (ISSUE-10, also run by serving_smoke) exports the SAME
model as an f32 and an int8 artifact (docs/serving.md §7), serves both
versions of one model through the bucket machinery, and reports req/s
side by side plus ``wire_bytes_*`` / ``compression_ratio`` (the
artifact bytes every replica pulls).  With ``--smoke`` it asserts a
tampered-scale manifest is rejected at load, quantized outputs stay
within the recorded calibration error, and the quantized version adds
zero programs beyond the per-version bucket bound.

Env knobs: BENCH_SERVING_REQUESTS (default 48), BENCH_SERVING_THREADS
(16), BENCH_SERVING_MAX_BATCH (8), BENCH_SERVING_LATENCY_US (2000),
BENCH_SERVING_CACHE_DIR (persistent compile-cache dir; unset = cache
off for the main run — the roundtrip manages its own),
BENCH_DECODE_REQUESTS (20), BENCH_DECODE_RATE (arrivals/sec, 25).
"""
import argparse
import json
import math
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu import compile_cache                       # noqa: E402
from mxnet_tpu import nd, runtime_metrics as rm, serving  # noqa: E402
from mxnet_tpu import tracing                             # noqa: E402
from mxnet_tpu.serving import traffic                     # noqa: E402
from mxnet_tpu.gluon import nn                            # noqa: E402


def build_lenet():
    """The reference LeNet (examples/mnist_gluon.py), NCHW 28x28."""
    net = nn.HybridSequential()
    net.add(nn.Conv2D(20, 5, activation="relu"), nn.MaxPool2D(2, 2),
            nn.Conv2D(50, 5, activation="relu"), nn.MaxPool2D(2, 2),
            nn.Dense(500, activation="relu"), nn.Dense(10))
    return net


def run(requests, threads, max_batch, latency_us, workdir, smoke,
        cache_dir=None, shed_phase=True, trace_out=None):
    if cache_dir:
        os.environ["MXNET_COMPILE_CACHE_DIR"] = cache_dir
    mx.random.seed(42)
    rm.enable()
    # the bench runs fully traced: every request gets a span timeline,
    # and the p99's exemplar trace is dumped (chrome-trace) next to the
    # BENCH json so a tail regression ships with its own evidence
    tracing.enable(sample=1.0)
    net = build_lenet()
    net.initialize(mx.init.Xavier())
    net.hybridize(static_alloc=True)
    x0 = nd.random.uniform(shape=(4, 1, 28, 28))
    net(x0)                                 # materialize params

    artifact = os.path.join(workdir, "lenet") + ".shlo"
    if not os.path.exists(artifact):
        # the cache round-trip re-runs this harness against an existing
        # workdir: reuse the artifact so its content hash (the cache
        # key's program identity) is byte-identical across restarts
        artifact = net.export_stablehlo(
            x0, path=os.path.join(workdir, "lenet"), dynamic_batch=True,
            version=1)

    # cold start to first response: repository load + server start +
    # prewarm of EVERY bucket + one served request — the window a
    # production replica is registered but cannot take traffic.  With a
    # warm compile cache the prewarm deserializes instead of compiling.
    cache0 = compile_cache.get_default().stats()
    t_cold = time.perf_counter()
    repo = serving.ModelRepository()
    repo.load_artifact("lenet", artifact)
    cfg = serving.ServingConfig(max_batch_size=max_batch,
                                max_latency_us=latency_us,
                                queue_depth=max(64, requests))
    srv = serving.ModelServer(repo, cfg)
    prewarmed = srv.prewarm("lenet")

    sizes = (1, 2, 3)                       # >= 3 distinct batch sizes
    rng = np.random.RandomState(0)
    payloads = {n: rng.randn(n, 1, 28, 28).astype(np.float32)
                for n in sizes}

    srv.predict("lenet", payloads[1], timeout=300)
    cold_start_ms = (time.perf_counter() - t_cold) * 1e3
    cache1 = compile_cache.get_default().stats()

    refs = {n: net(nd.NDArray(payloads[n])).asnumpy() for n in sizes}
    # correctness probe outside the timed window (every bucket is
    # already prewarmed, so these are mem hits); zero the metric samples
    # and snapshot server counters afterwards so the reported
    # p50/p99/occupancy/batches cover ONLY the timed load
    for n in sizes:
        np.testing.assert_allclose(
            srv.predict("lenet", payloads[n], timeout=300), refs[n],
            rtol=1e-4, atol=1e-4)
    rm.reset()
    warm = srv.stats()

    errors = []
    barrier = threading.Barrier(threads + 1)
    per_thread = max(1, requests // threads)

    def worker(tid):
        try:
            barrier.wait(60)
            for i in range(per_thread):
                n = sizes[(tid + i) % len(sizes)]
                got = srv.predict("lenet", payloads[n], timeout=300)
                np.testing.assert_allclose(got, refs[n], rtol=1e-4,
                                           atol=1e-4)
        except Exception as e:              # noqa: BLE001
            errors.append(e)

    pool = [threading.Thread(target=worker, args=(t,))
            for t in range(threads)]
    for t in pool:
        t.start()
    barrier.wait(60)
    t0 = time.perf_counter()
    for t in pool:
        t.join(600)
    wall = time.perf_counter() - t0
    stats = srv.stats()
    # snapshot the (unlabeled) occupancy histogram BEFORE the synthetic
    # shed phase below dispatches its own batches into it
    occ_n = rm.SERVING_BATCH_OCCUPANCY.count()
    occ_mean = (rm.SERVING_BATCH_OCCUPANCY.sum() / occ_n) if occ_n \
        else float("nan")

    sheds = 0
    if shed_phase:
        # --- saturate a tiny bounded queue to demonstrate shedding ---
        shed_cfg = serving.ServingConfig(
            max_batch_size=1, max_latency_us=1, queue_depth=2,
            shed_watermark=1, num_workers=1)
        gate = threading.Event()
        entered = threading.Event()

        def gated(a):
            entered.set()
            assert gate.wait(300), "bench never released the gate"
            return a

        shed_repo = serving.ModelRepository()
        shed_repo.add_function(
            "gated", gated, [{"shape": [None, 1], "dtype": "float32"}])
        shed_srv = serving.ModelServer(shed_repo, shed_cfg)

        def _shed_call():
            shed_srv.predict("gated", np.ones((1, 1), np.float32),
                             timeout=300)

        # deterministic saturation (no race with the worker pop): admit
        # one request and wait until the worker holds it INSIDE the
        # gated model and the queue is empty again, THEN queue a second
        # up to the watermark
        shed_threads = [threading.Thread(target=_shed_call)]
        shed_threads[0].start()
        assert entered.wait(120), \
            "serving worker never picked up a request"
        deadline = time.monotonic() + 120
        while shed_srv.stats()["queue_depth"] > 0:
            assert time.monotonic() < deadline, \
                "first request never popped"
            time.sleep(0.01)
        shed_threads.append(threading.Thread(target=_shed_call))
        shed_threads[1].start()
        deadline = time.monotonic() + 120
        while shed_srv.stats()["queue_depth"] < shed_cfg.shed_watermark:
            assert time.monotonic() < deadline, "queue never saturated"
            time.sleep(0.01)
        for _ in range(4):
            try:
                shed_srv.predict("gated", np.ones((1, 1), np.float32),
                                 timeout=300)
            except serving.ServerOverloadedError:
                sheds += 1
        gate.set()
        for t in shed_threads:
            t.join(300)
        shed_srv.stop()
    srv.stop()

    done = per_thread * threads
    p50 = rm.SERVING_REQUEST_SECONDS.quantile(0.50, model="lenet")
    p99 = rm.SERVING_REQUEST_SECONDS.quantile(0.99, model="lenet")
    # exemplar workflow (docs/observability.md): p99 -> trace id ->
    # chrome-trace file next to the BENCH json
    p99_trace_id = rm.SERVING_REQUEST_SECONDS.exemplar_for_quantile(
        0.99, model="lenet")
    p99_trace = tracing.TRACER.find(p99_trace_id) \
        if p99_trace_id else None
    trace_dump = None
    if p99_trace is not None:
        trace_dump = trace_out or os.path.join(workdir,
                                               "serving_p99_trace.json")
        tracing.dump_chrome_trace(trace_dump, p99_trace)
    bound = int(math.ceil(math.log2(max_batch))) + 1
    result = {
        "metric": "serving.throughput",
        "value": round(done / wall, 2),
        "unit": "req/s",
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3),
        "batch_occupancy_mean": round(occ_mean, 4),
        "requests": done,
        "batches": stats["batches"] - warm["batches"],
        "programs": stats["programs"],
        "program_bound": bound,
        "bucket_hits": stats["bucket_hits"] - warm["bucket_hits"],
        "bucket_disk_hits": stats["bucket_disk_hits"]
        - warm["bucket_disk_hits"],
        "bucket_misses": stats["bucket_misses"] - warm["bucket_misses"],
        "shed": sheds,
        "max_batch": max_batch,
        "threads": threads,
        "errors": len(errors),
        # cold start + persistent-cache accounting (ISSUE-6): the
        # cold_start window covers load + start + all-bucket prewarm +
        # first response; cache hits/misses are the compile-cache delta
        # inside that window (misses == XLA programs compiled at start)
        "cold_start_ms": round(cold_start_ms, 1),
        "prewarm_buckets": len(prewarmed["buckets"]),
        "prewarm_compiled": prewarmed["compiled"],
        "prewarm_disk_hits": prewarmed["disk_hits"],
        "compile_cache_hits": cache1["hits"] - cache0["hits"],
        "compile_cache_misses": cache1["misses"] - cache0["misses"],
        "compile_cache_dir": cache_dir,
        # the trace behind the reported p99 (exemplar workflow)
        "p99_exemplar_trace": p99_trace_id,
        "p99_trace_dump": trace_dump,
    }
    if smoke:
        assert not errors, errors[:3]
        assert done >= 32, f"smoke needs >= 32 requests, ran {done}"
        assert stats["programs"] <= bound, \
            (stats["programs"], bound)
        assert rm.SERVING_REQUEST_SECONDS.count(model="lenet") >= done
        assert np.isfinite(p99) and p99 > 0, "p99 not recorded"
        assert sheds > 0, "load shedding never triggered"
        assert "serving_request_seconds" in rm.dump_prometheus()
        # exemplar workflow end to end: the p99 resolves to a trace
        # that is still in the flight-recorder ring, and its
        # chrome-trace dump parses with the request span chain inside
        assert p99_trace_id, "p99 exemplar not recorded"
        assert p99_trace is not None, \
            f"p99 exemplar trace {p99_trace_id} evicted from the ring"
        names = {s["name"] for s in p99_trace["spans"]}
        assert {"serving.predict", "serving.queue_wait",
                "serving.batch"} <= names, names
        with open(trace_dump) as f:
            events = json.load(f)["traceEvents"]
        assert any(e.get("ph") == "X" for e in events), trace_dump
    return result


def run_decode(args):
    """ISSUE-7 decode tier: Poisson arrivals of mixed-length generate()
    requests through the continuous-batching engine; one BENCH JSON
    line with tokens/sec, TTFT/per-token percentiles, and KV-pool
    occupancy."""
    mx.random.seed(7)
    rm.enable()
    tracing.enable(sample=1.0)
    from mxnet_tpu.models.transformer_blocks import TransformerDecoderLM
    lm = TransformerDecoderLM(32, units=16, hidden_size=32, num_layers=2,
                              num_heads=2, max_length=32)
    lm.initialize(mx.init.Xavier())
    repo = serving.ModelRepository()
    repo.add_decoder("lm", lm)
    cfg = serving.ServingConfig(
        decode_page_size=4, decode_pool_pages=65, decode_max_batch=4,
        decode_max_new_tokens=16)
    srv = serving.ModelServer(repo, cfg)

    n_req = args.decode_requests
    rate = args.decode_rate
    # deterministic mixed-length plan: request 0 is LONG; later shorts
    # must overtake it (the continuous-batching interleave criterion)
    plan = []
    for i in range(n_req):
        prompt = list(range(1, 2 + i % 6))          # lens 1..6
        # the long request must stay mid-flight while Poisson shorts
        # arrive — 24 tokens keeps its window open on fast machines
        # (12 was finishing before the first short landed)
        max_new = 24 if i == 0 else 2 + i % 4
        plan.append((prompt, max_new))

    # warm the program families outside the timed window: prefill
    # buckets for lens 1..6 ({1, 2, 4, 8}) + the one decode program
    # (max_new_tokens=2 so at least one decode step actually runs —
    # a 1-token request finishes at prefill)
    for L in (1, 2, 3, 5):
        srv.generate("lm", list(range(1, L + 1)), max_new_tokens=2,
                     timeout=600)
    warm_programs = srv.decode_stats("lm")["programs"]
    rm.reset()

    records = [{"submit": None, "tokens": [], "done": None}
               for _ in range(n_req)]
    errors = []

    def worker(i):
        rec = records[i]
        prompt, max_new = plan[i]
        rec["submit"] = time.perf_counter()
        try:
            out = srv.generate(
                "lm", prompt, max_new_tokens=max_new,
                on_token=lambda t: rec["tokens"].append(
                    time.perf_counter()),
                timeout=600)
            rec["done"] = time.perf_counter()
            rec["n"] = len(out)
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    rng = np.random.RandomState(0)
    pool = [threading.Thread(target=worker, args=(i,))
            for i in range(n_req)]
    t0 = time.perf_counter()
    # the long request goes first; the rest arrive Poisson once it is
    # demonstrably mid-flight (first token streamed), so the interleave
    # criterion is deterministic, not a race against a fast tiny model
    pool[0].start()
    deadline = time.monotonic() + 120
    while not records[0]["tokens"] and time.monotonic() < deadline:
        time.sleep(0.001)
    for i, t in enumerate(pool[1:], start=1):
        t.start()
        if i + 1 < n_req:
            # the ONE Poisson-gap primitive (serving.traffic) — same
            # rng call as before the dedupe, so the seeded draw
            # sequence (and this bench's arrival schedule) is unchanged
            time.sleep(traffic.exponential_gap(rng, rate))
    for t in pool:
        t.join(600)
    wall = time.perf_counter() - t0

    assert not errors, errors[:3]
    total_tokens = sum(r["n"] for r in records)
    ttft_ms = [1e3 * (r["tokens"][0] - r["submit"]) for r in records]
    gaps_ms = [1e3 * (b - a) for r in records
               for a, b in zip(r["tokens"], r["tokens"][1:])]
    stats = srv.decode_stats("lm")
    srv.stop()

    pct = lambda xs, q: float(np.percentile(xs, q)) if xs \
        else float("nan")                           # noqa: E731
    result = {
        "metric": "serving.decode.throughput",
        "value": round(total_tokens / wall, 2),
        "unit": "tokens/s",
        "requests": n_req,
        "generated_tokens": total_tokens,
        "ttft_p50_ms": round(pct(ttft_ms, 50), 3),
        "ttft_p99_ms": round(pct(ttft_ms, 99), 3),
        "token_p50_ms": round(pct(gaps_ms, 50), 3),
        "token_p99_ms": round(pct(gaps_ms, 99), 3),
        "decode_steps": stats["steps"],
        "peak_running": stats["peak_running"],
        "kv_pool_peak_occupancy": round(
            stats["peak_used_pages"]
            / max(1, cfg.decode_pool_pages - 1), 4),
        "kv_pool_pages": cfg.decode_pool_pages,
        "page_size": cfg.decode_page_size,
        "decode_max_batch": cfg.decode_max_batch,
        "programs": stats["programs"],
        "program_bound": stats["program_bound"],
        "arrival_rate_per_s": rate,
        "errors": len(errors),
    }
    if args.smoke:
        assert n_req >= 20, f"decode smoke wants >= 20 requests, {n_req}"
        # O(log) program families: <= prefill buckets + 1 decode, and
        # the timed run compiled NOTHING new after warm-up
        assert stats["programs"] <= stats["program_bound"], stats
        assert stats["programs"] == warm_programs, \
            (stats["programs"], warm_programs)
        # continuous batching interleaves: at least one short request
        # submitted AFTER the long request 0 finished BEFORE it
        long_rec = records[0]
        overtook = [i for i in range(1, n_req)
                    if records[i]["submit"] > long_rec["submit"]
                    and records[i]["done"] < long_rec["done"]]
        assert overtook, "no short request overtook the long one"
        assert stats["peak_running"] >= 2, stats
        assert np.isfinite(result["ttft_p99_ms"])
        assert rm.SERVING_DECODE_TTFT_SECONDS.count(model="lm") == n_req
        assert "serving_decode_tokens" in rm.dump_prometheus()
        # ISSUE-8: a traced generate() must contain a coherent
        # prefill -> decode-step span chain (same trace, parent links
        # resolving inside it)
        chained = None
        for tr in tracing.TRACER.traces():
            names = {s["name"] for s in tr["spans"]}
            if {"decode.prefill", "decode.step"} <= names:
                chained = tr
                break
        assert chained is not None, \
            "no trace holds a prefill -> decode-step span chain"
        ids = {s["span_id"] for s in chained["spans"]}
        for s in chained["spans"]:
            assert s["trace_id"] == chained["trace_id"], s
            assert s["parent_id"] is None or s["parent_id"] in ids, s
    return result


def run_prefix(args):
    """ISSUE-12 shared-prefix tier: the SAME seeded shared-prompt
    workload (fraction ``--shared-prefix`` of requests share one long
    system-prompt-style prefix) served twice — prefix cache OFF then
    ON — one BENCH JSON line with TTFT p50/p99 and tokens/sec side by
    side, the hit ratio, and prefill tokens saved."""
    mx.random.seed(7)
    rm.enable()
    from mxnet_tpu.models.transformer_blocks import TransformerDecoderLM
    share = args.shared_prefix
    n_req = args.decode_requests
    lm = TransformerDecoderLM(64, units=64, hidden_size=128,
                              num_layers=3, num_heads=4, max_length=64)
    lm.initialize(mx.init.Xavier())

    # workload: shared requests = 48-token common prefix + 1-2 private
    # suffix tokens; the rest are distinct random prompts of the same
    # length band (both runs pay identical non-prefix work)
    rng = np.random.RandomState(0)
    prefix = list(rng.randint(1, 64, size=48))
    plan = []
    for i in range(n_req):
        if rng.rand() < share:
            plan.append(prefix + list(rng.randint(1, 64,
                                                  size=1 + i % 2)))
        else:
            plan.append(list(rng.randint(1, 64, size=48 + 1 + i % 2)))

    def serve_round(prefix_cache):
        repo = serving.ModelRepository()
        repo.add_decoder("lm", lm)
        cfg = serving.ServingConfig(
            decode_page_size=4, decode_pool_pages=257,
            decode_max_batch=4, decode_max_new_tokens=8,
            prefix_cache=prefix_cache, queue_depth=max(64, n_req))
        srv = serving.ModelServer(repo, cfg)
        # warm every program family outside the timed window — misses
        # measure the CACHE, not compile time.  The cache-on round also
        # warms the HIT path (the width-1/2 verify programs the shared
        # tails ride), which seeds the prefix tree as a side effect
        srv.generate("lm", plan[0], max_new_tokens=2, timeout=600)
        srv.generate("lm", plan[-1], max_new_tokens=2, timeout=600)
        if prefix_cache:
            srv.generate("lm", prefix + [63], max_new_tokens=2,
                         timeout=600)           # seed/tail-1 verify
            srv.generate("lm", prefix + [63, 62], max_new_tokens=2,
                         timeout=600)           # tail-2 verify
        outs, ttfts = [], []
        t0 = time.perf_counter()
        total = 0
        for prompt in plan:
            first = []
            t_sub = time.perf_counter()
            out = srv.generate(
                "lm", prompt, max_new_tokens=4,
                on_token=lambda t: first.append(time.perf_counter()),
                timeout=600)
            ttfts.append(1e3 * (first[0] - t_sub))
            outs.append(out.tolist())
            total += len(out)
        wall = time.perf_counter() - t0
        stats = srv.decode_stats("lm")
        eng = list(srv._decoders.values())[0]
        eng.allocator.check_leaks()     # exact under shared pages
        srv.stop()
        return outs, ttfts, total / wall, stats

    outs_off, ttft_off, tps_off, st_off = serve_round(False)
    outs_on, ttft_on, tps_on, st_on = serve_round(True)

    pct = lambda xs, q: float(np.percentile(xs, q))     # noqa: E731
    hits = st_on["prefix_hits"]
    misses = st_on["prefix_misses"]
    result = {
        "metric": "serving.decode.prefix",
        "value": round(pct(ttft_off, 50) / max(1e-9, pct(ttft_on, 50)),
                       3),
        "unit": "ttft_p50_speedup_x",
        "requests": n_req,
        "shared_prefix_mix": share,
        "ttft_p50_ms_off": round(pct(ttft_off, 50), 3),
        "ttft_p50_ms_on": round(pct(ttft_on, 50), 3),
        "ttft_p99_ms_off": round(pct(ttft_off, 99), 3),
        "ttft_p99_ms_on": round(pct(ttft_on, 99), 3),
        "tokens_per_s_off": round(tps_off, 2),
        "tokens_per_s_on": round(tps_on, 2),
        "prefix_hits": hits,
        "prefix_misses": misses,
        "prefix_hit_ratio": round(hits / max(1, hits + misses), 4),
        "prefix_tokens_saved": st_on["prefix_tokens_saved"],
        "kv_shared_pages_final": st_on["shared_pages"],
        "cached_pages": st_on["cached_pages"],
        "programs": st_on["programs"],
        "program_bound": st_on["program_bound"],
    }
    if args.smoke:
        # byte-identical outputs cache on vs off — the cache may only
        # move work, never tokens
        assert outs_on == outs_off, "prefix cache changed outputs"
        # the hit-ratio counter proves prefill was skipped: every
        # shared request after the seeding miss hits
        expected_hits = sum(p[:48] == prefix for p in plan) - 1
        assert hits >= max(1, expected_hits), (hits, expected_hits)
        assert result["prefix_tokens_saved"] >= 48 * hits, result
        # the ISSUE-12 headline: TTFT p50 at least 2x better
        assert result["value"] >= 2.0, result
        assert st_on["programs"] <= st_on["program_bound"], st_on
    return result


class _HeavyPair:
    """Deterministic target/draft fakes whose cost is REAL numpy matmul
    work: the target burns ``work`` 192x192 GEMMs per call (verify ~a
    third more), the draft ~1/20 of that, and the draft agrees with
    the target's next-token rule except every 10th token value — so
    the speculative tokens/sec win measured below comes exclusively
    from what speculation changes: target calls per emitted token."""

    vocab_size = 64
    max_context = 96

    def __init__(self, work=4, draft=False):
        self.work = work
        self.draft = draft
        rs = np.random.RandomState(5)
        self._a = rs.randn(192, 192).astype(np.float32)
        self.calls = {"prefill": 0, "step": 0, "verify": 0}

    def _burn(self, reps):
        a = self._a
        for _ in range(max(1, reps)):
            # keep activations O(1): a decaying scale would drift into
            # denormals and make later reps pathologically slow, which
            # would skew the verify-vs-step cost ratio this fake exists
            # to model
            a = np.tanh(a @ self._a * 0.1)
        return float(a[0, 0])

    def _next(self, t):
        t = int(t)
        nxt = (t * 7 + 3) % self.vocab_size
        if self.draft and t % 10 == 0:
            nxt = (nxt + 1) % self.vocab_size   # deliberate disagreement
        return nxt

    def _rows(self, tokens):
        logits = np.zeros((len(tokens), self.vocab_size), np.float32)
        for i, t in enumerate(tokens):
            logits[i, self._next(t)] = 1.0
        return logits

    def prefill(self, tokens, length, block_table):
        self.calls["prefill"] += 1
        self._burn(self.work // (20 if self.draft else 1))
        return self._rows([tokens[0, int(length) - 1]])[0]

    def decode_step(self, tokens, positions, block_tables):
        self.calls["step"] += 1
        self._burn(self.work // (20 if self.draft else 1))
        return self._rows(list(tokens))

    def verify(self, tokens, start, length, block_table):
        self.calls["verify"] += 1
        self._burn(self.work + self.work // 3)
        return self._rows(list(tokens[0]))

    def verify_batch(self, tokens, starts, lengths, block_tables):
        # ONE device call judges every window — the shape the batched
        # verify program has on the real adapter
        self.calls["verify"] += 1
        self._burn(self.work + self.work // 3)
        return np.stack([self._rows(list(row)) for row in tokens])

    def copy_page(self, src, dst):
        pass


def run_speculative(args):
    """ISSUE-12 speculative tier: the same seeded workload decoded
    plainly and speculatively (k=3, ~90%-agreeing cheap draft) over
    cost-realistic fakes; one BENCH JSON line with tokens/sec side by
    side and the draft acceptance rate."""
    rm.enable()
    n_req = args.decode_requests

    rng = np.random.RandomState(2)
    plan = [list(rng.randint(1, 64, size=2 + i % 5))
            for i in range(n_req)]

    def serve_round(spec_k):
        repo = serving.ModelRepository()
        target = _HeavyPair(work=16)
        draft = _HeavyPair(work=16, draft=True)
        repo.add_decoder("lm", target,
                         draft=draft if spec_k else None)
        cfg = serving.ServingConfig(
            decode_page_size=4, decode_pool_pages=257,
            decode_max_batch=4, decode_max_new_tokens=24,
            spec_k=spec_k, queue_depth=max(64, n_req))
        srv = serving.ModelServer(repo, cfg)
        outs, errors = {}, []

        def worker(i):
            try:
                outs[i] = srv.generate("lm", plan[i],
                                       max_new_tokens=24,
                                       timeout=600).tolist()
            except Exception as e:          # noqa: BLE001
                errors.append(e)

        pool = [threading.Thread(target=worker, args=(i,))
                for i in range(n_req)]
        t0 = time.perf_counter()
        for t in pool:
            t.start()
        for t in pool:
            t.join(600)
        wall = time.perf_counter() - t0
        assert not errors, errors[:3]
        total = sum(len(v) for v in outs.values())
        stats = srv.decode_stats("lm")
        eng = list(srv._decoders.values())[0]
        eng.allocator.check_leaks()
        srv.stop()
        return [outs[i] for i in range(n_req)], total / wall, stats

    outs_plain, tps_plain, _ = serve_round(0)
    outs_spec, tps_spec, st = serve_round(3)

    accept = st["spec_accepted"] / max(1, st["spec_proposed"])
    result = {
        "metric": "serving.decode.speculative",
        "value": round(tps_spec / max(1e-9, tps_plain), 3),
        "unit": "tokens_per_s_speedup_x",
        "requests": n_req,
        "spec_k": 3,
        "tokens_per_s_plain": round(tps_plain, 2),
        "tokens_per_s_spec": round(tps_spec, 2),
        "spec_proposed": st["spec_proposed"],
        "spec_accepted": st["spec_accepted"],
        "accept_rate": round(accept, 4),
        "spec_rounds": st["spec_rounds"],
        "spec_fallbacks": st["spec_fallbacks"],
    }
    if args.smoke:
        # rejection sampling in greedy mode is exact: byte-identical
        # outputs with speculation on vs off
        assert outs_spec == outs_plain, \
            "speculation changed greedy outputs"
        assert accept >= 0.5, result
        # the ISSUE-12 headline: >= 1.3x tokens/sec on the smoke config
        assert result["value"] >= 1.3, result
    return result


def run_quantized(args):
    """ISSUE-10 quantized-serving tier: export LeNet as BOTH the f32
    and the int8 artifact, register them as two versions of one model,
    and serve each under the same concurrent load — one BENCH JSON line
    with quantized-vs-f32 req/s side by side, artifact wire bytes and
    compression ratio, and the per-version compiled-program bound.

    With ``--smoke`` (the CI serving_smoke tier) it also asserts the
    acceptance criteria: a tampered-scale manifest is rejected at load
    with ``MXNetError``, quantized predictions stay within the
    manifest's recorded calibration error of the f32 references, and
    the quantized version compiles ZERO programs beyond the same
    per-version bucket bound the f32 version gets."""
    import shutil

    from mxnet_tpu.base import MXNetError
    mx.random.seed(42)
    rm.enable()
    net = build_lenet()
    net.initialize(mx.init.Xavier())
    net.hybridize(static_alloc=True)
    x0 = nd.random.uniform(shape=(4, 1, 28, 28))
    net(x0)

    with tempfile.TemporaryDirectory() as workdir:
        p_f32 = net.export_stablehlo(
            x0, path=os.path.join(workdir, "lenet_f32"),
            dynamic_batch=True, version=1)
        p_int8 = net.export_stablehlo(
            x0, path=os.path.join(workdir, "lenet_int8"),
            dynamic_batch=True, version=2, quantize="int8")
        bytes_f32 = os.path.getsize(p_f32)
        bytes_int8 = os.path.getsize(p_int8)
        manifest = json.load(open(os.path.join(workdir,
                                               "lenet_int8.json")))
        calib = manifest["quantization"]["calibration"]

        # tampered-scale manifest must be rejected at load, BEFORE any
        # serving admission (digest check in deploy.validate_manifest)
        tampered = os.path.join(workdir, "tampered")
        shutil.copyfile(p_int8, tampered + ".shlo")
        bad = json.loads(json.dumps(manifest))
        bad["quantization"]["weights"][0]["scale"] *= 1.25
        json.dump(bad, open(tampered + ".json", "w"))
        tamper_rejected = False
        try:
            serving.ModelRepository().load_artifact("evil",
                                                    tampered + ".shlo")
        except MXNetError:
            tamper_rejected = True

        repo = serving.ModelRepository()
        repo.load_artifact("lenet", p_f32)              # v1 (current)
        repo.load_artifact("lenet", p_int8, activate=False)  # stage v2
        cfg = serving.ServingConfig(max_batch_size=args.max_batch,
                                    max_latency_us=args.latency_us,
                                    queue_depth=max(64, args.requests))
        srv = serving.ModelServer(repo, cfg)

        sizes = (1, 2, 3)
        rng = np.random.RandomState(0)
        payloads = {n: rng.randn(n, 1, 28, 28).astype(np.float32)
                    for n in sizes}
        refs = {n: net(nd.NDArray(payloads[n])).asnumpy()
                for n in sizes}

        def drive(version_label):
            srv.prewarm("lenet")
            errors = []
            threads = args.threads
            per_thread = max(1, args.requests // threads)

            def worker(tid):
                try:
                    for i in range(per_thread):
                        n = sizes[(tid + i) % len(sizes)]
                        got = srv.predict("lenet", payloads[n],
                                          timeout=300)
                        # quantized outputs match within the recorded
                        # calibration error (plus float slack)
                        tol = 1e-4 + 2.0 * calib["max_abs_err"]
                        if np.abs(got - refs[n]).max() > tol:
                            raise AssertionError(
                                f"{version_label}: output error "
                                f"{np.abs(got - refs[n]).max()} > {tol}")
                except Exception as e:          # noqa: BLE001
                    errors.append(e)

            pool = [threading.Thread(target=worker, args=(t,))
                    for t in range(threads)]
            t0 = time.perf_counter()
            for t in pool:
                t.start()
            for t in pool:
                t.join(600)
            wall = time.perf_counter() - t0
            assert not errors, errors[:3]
            return per_thread * threads / wall

        def cache_misses():
            # bucket-cache misses == freshly COMPILED XLA programs
            # (the batcher invariant) — the acceptance criterion's
            # counter of record
            return int(rm.SERVING_BUCKET_CACHE.value(event="miss"))

        stats0, miss0 = srv.stats(), cache_misses()
        req_s_f32 = drive("f32")
        progs_f32 = srv.stats()["programs"] - stats0["programs"]
        miss_f32 = cache_misses() - miss0
        repo.swap("lenet", 2)                   # cutover to int8
        stats1, miss1 = srv.stats(), cache_misses()
        req_s_int8 = drive("int8")
        progs_int8 = srv.stats()["programs"] - stats1["programs"]
        miss_int8 = cache_misses() - miss1
        srv.stop()

    bound = int(math.ceil(math.log2(args.max_batch))) + 1
    result = {
        "metric": "serving.quantized.throughput",
        "value": round(req_s_int8, 2),
        "unit": "req/s",
        "req_s_f32": round(req_s_f32, 2),
        "req_s_int8": round(req_s_int8, 2),
        # artifact wire cost: what every replica pulls at deploy time
        "wire_bytes_f32": bytes_f32,
        "wire_bytes_int8": bytes_int8,
        "compression_ratio": round(bytes_f32 / bytes_int8, 3),
        "calib_max_abs_err": calib["max_abs_err"],
        "calib_max_rel_err": calib["max_rel_err"],
        "programs_f32": progs_f32,
        "programs_int8": progs_int8,
        "bucket_misses_f32": miss_f32,
        "bucket_misses_int8": miss_int8,
        "program_bound": bound,
        "tamper_rejected": tamper_rejected,
        "requests_per_version": args.requests,
        "max_batch": args.max_batch,
    }
    if args.smoke:
        assert tamper_rejected, \
            "tampered-scale manifest was NOT rejected at load"
        # zero extra programs vs the f32 bucket bound: the quantized
        # version rides the same bucket machinery under the same bound,
        # verified through the serving.bucket.cache counter (misses ==
        # freshly compiled programs) AND the batcher's program count
        assert progs_f32 <= bound, (progs_f32, bound)
        assert progs_int8 <= bound, (progs_int8, bound)
        assert miss_f32 == progs_f32, (miss_f32, progs_f32)
        assert miss_int8 == progs_int8, (miss_int8, progs_int8)
        assert bytes_f32 / bytes_int8 > 2.0, (bytes_f32, bytes_int8)
        assert calib["max_rel_err"] < 0.05, calib
    return result


def run_faults(args):
    """Chaos smoke (docs/serving.md §8): one seeded MXNET_FAULTS-style
    plan drives execute faults, compile-cache corruption, and a decode
    poison through the whole resilience layer — ZERO real compiles
    (numpy function/decoder entries), so it is cheap enough for every
    CI run.  Asserts the chaos acceptance criteria: every request
    resolves (completed or TYPED failure — no hung futures), p99 stays
    bounded, retried outputs byte-match a fault-free run with zero
    extra programs, quarantined sequences release all KV pages, and
    the circuit breaker opens and re-closes."""
    from mxnet_tpu import faults
    from mxnet_tpu.serving.resilience import CircuitOpenError

    rm.enable()
    sizes = (1, 2, 3)
    rng = np.random.RandomState(0)
    payloads = {n: rng.randn(n, 2).astype(np.float32) for n in sizes}
    sig = [{"shape": [None, 2], "dtype": "float32"}]
    n_req, threads = 64, 8

    def serve_round(label, plan_spec):
        """One full concurrent round; returns (results, stats)."""
        repo = serving.ModelRepository()
        repo.add_function("m", lambda a: a * 2.0 + 1.0, sig)
        cfg = serving.ServingConfig(
            max_batch_size=4, max_latency_us=500, queue_depth=128,
            retry_backoff_ms=1, num_workers=2)
        results, errors = [], []

        def worker(tid):
            for i in range(n_req // threads):
                n = sizes[(tid + i) % len(sizes)]
                try:
                    results.append(
                        (n, srv.predict("m", payloads[n], timeout=30)))
                except Exception as e:          # noqa: BLE001
                    errors.append(e)

        fired = {}
        with serving.ModelServer(repo, cfg) as srv:
            ctx = faults.plan(plan_spec) if plan_spec else None
            plan_obj = ctx.__enter__() if ctx else None
            try:
                pool = [threading.Thread(target=worker, args=(t,))
                        for t in range(threads)]
                t0 = time.perf_counter()
                for t in pool:
                    t.start()
                for t in pool:
                    t.join(120)
                wall = time.perf_counter() - t0
            finally:
                if ctx:
                    fired = plan_obj.counters()
                    ctx.__exit__(None, None, None)
            stats = srv.stats()
        # zero hung futures: every request resolved one way or the other
        assert len(results) + len(errors) == n_req, \
            (label, len(results), len(errors))
        # typed failures only
        from mxnet_tpu.base import MXNetError
        assert all(isinstance(e, MXNetError) for e in errors), errors[:3]
        # correct results on every success
        for n, got in results:
            np.testing.assert_array_equal(got, payloads[n] * 2.0 + 1.0)
        return results, errors, stats, wall, fired

    # --- phase 1: 5% seeded execute faults, retries absorb them -------
    ok0, err0, stats0, _, _ = serve_round("fault-free", None)
    ok1, err1, stats1, wall1, fired = serve_round(
        "chaos", "serving.execute=fail,p=0.05,seed=11")
    p99 = rm.SERVING_REQUEST_SECONDS.quantile(0.99, model="m")
    assert not err0 and stats0["errors"] == 0, (err0[:3], stats0)
    assert stats0["retries"] == 0
    assert stats1["retries"] > 0, "5% fault plan never fired"
    # same program set either way (no chaos-path compiles/buckets)
    assert stats1["programs"] == stats0["programs"], (stats0, stats1)
    assert np.isfinite(p99) and p99 < 30, p99

    # --- phase 2: circuit opens under a dead version, then recovers ---
    repo = serving.ModelRepository()
    repo.add_function("m", lambda a: a, sig)
    cfg = serving.ServingConfig(
        max_batch_size=1, max_latency_us=1, retry_max=0,
        circuit_window=4, circuit_threshold=0.5, circuit_cooldown_ms=100)
    opened = recovered = False
    with serving.ModelServer(repo, cfg) as srv:
        with faults.plan("serving.execute=fail,times=4"):
            for _ in range(4):
                try:
                    srv.predict("m", payloads[1], timeout=30)
                except faults.InjectedFault:
                    pass
            try:
                srv.predict("m", payloads[1], timeout=30)
            except CircuitOpenError:
                opened = True
        time.sleep(0.12)                    # cooldown -> half-open probe
        out = srv.predict("m", payloads[1], timeout=30)
        np.testing.assert_array_equal(out, payloads[1])
        state = [c["state"]
                 for c in srv.debug_state()["circuits"].values()]
        recovered = state == ["closed"]
    assert opened, "circuit never opened under 100% execute faults"
    assert recovered, "circuit did not re-close after the probe"

    # --- phase 3: decode poison -> quarantine, leak-free --------------
    class PoisonLM:
        vocab_size, max_context = 16, 32

        def prefill(self, tokens, length, block_table):
            logits = np.zeros((self.vocab_size,), np.float32)
            logits[int(tokens[0, int(length) - 1]) % self.vocab_size] = 1
            return logits

        def decode_step(self, tokens, positions, block_tables):
            if np.any(tokens == 13):
                raise ValueError("poisoned decode token")
            logits = np.zeros((tokens.shape[0], self.vocab_size),
                              np.float32)
            logits[np.arange(tokens.shape[0]),
                   (tokens + 1) % self.vocab_size] = 1.0
            return logits

    repo = serving.ModelRepository()
    repo.add_decoder("lm", PoisonLM())
    cfg = serving.ServingConfig(
        decode_page_size=4, decode_pool_pages=17, decode_max_batch=4,
        decode_max_new_tokens=8, retry_backoff_ms=1)
    quarantined = 0
    with serving.ModelServer(repo, cfg) as srv:
        outs, errs = {}, {}

        def gen(i, prompt):
            try:
                outs[i] = srv.generate("lm", prompt, max_new_tokens=4,
                                       timeout=60)
            except Exception as e:          # noqa: BLE001
                errs[i] = e

        prompts = [[3], [12], [5], [1]]     # [12] decodes into 13: poison
        pool = [threading.Thread(target=gen, args=(i, p))
                for i, p in enumerate(prompts)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(120)
        dstats = srv.decode_stats("lm")
        quarantined = dstats["quarantined"]
        entry = repo.get("lm")
        eng = srv._decoders[entry.uid]
        eng.allocator.check_leaks()         # all pages accounted for
        assert dstats["used_pages"] == 0, dstats
    assert set(outs) == {0, 2, 3}, (outs.keys(), errs)
    assert outs[0].tolist() == [3, 4, 5, 6]
    assert isinstance(errs[1], ValueError), errs
    assert quarantined == 1, quarantined

    # --- phase 4: compile-cache blob rot degrades to a counted miss ---
    import tempfile as _tf
    with _tf.TemporaryDirectory() as d:
        cache = compile_cache.CompileCache(cache_dir=d)
        cache.put("k" * 64, b"payload-bytes")
        with faults.plan("compile_cache.load=corrupt,times=1"):
            assert cache.get("k" * 64) is None      # rot -> typed miss
        assert cache.corrupt == 1 and cache.misses == 1
        cache.put("k" * 64, b"payload-bytes")       # re-store heals
        assert cache.get("k" * 64) == b"payload-bytes"

    result = {
        "metric": "serving.chaos",
        "value": round(n_req / wall1, 2),
        "unit": "req/s_under_5pct_execute_faults",
        "requests": n_req,
        "completed_chaos": len(ok1),
        "typed_failures_chaos": len(err1),
        "hung": 0,
        "p99_ms": round(p99 * 1e3, 3),
        "retries": stats1["retries"],
        "programs_fault_free": stats0["programs"],
        "programs_chaos": stats1["programs"],
        "circuit_opened": opened,
        "circuit_recovered": recovered,
        "decode_quarantined": quarantined,
        "faults_fired": fired,
    }
    return result


def run_replicas(args):
    """Replica tier (docs/serving.md §10): N replicas behind one
    ModelServer, driven by closed-loop clients that HONOR the server's
    retry-after hints with jitter (resilience.honor_retry_after — shed
    storms must not come back as one synchronized wave).  With
    ``--faults`` the full failover ladder runs deterministically:
    kill one replica's executes (seeded plan) -> consecutive-failure
    trip -> reroute under the original deadlines -> probe recovery;
    then stall its heartbeat -> sibling detection -> dark window served
    by the others -> prewarm-gated rejoin.  Asserts the ISSUE-13
    acceptance: zero hung requests, typed failures only, outputs
    byte-identical to a fault-free single-replica twin, bounded
    latency, failovers fully accounted by metric AND trace tags, and
    zero extra programs per replica beyond the per-replica bucket
    bound.  Numpy function entries: zero XLA compiles."""
    from mxnet_tpu import faults
    from mxnet_tpu.serving.batcher import bucket_set
    from mxnet_tpu.serving.resilience import Deadline, honor_retry_after

    rm.enable()
    tracing.enable(sample=1.0)
    n_rep = args.replicas
    sizes = (1, 2, 3)
    rng = np.random.RandomState(0)
    payloads = {n: rng.randn(n, 2).astype(np.float32) for n in sizes}
    sig = [{"shape": [None, 2], "dtype": "float32"}]
    fn = lambda a: a * 3.0 - 1.0                    # noqa: E731
    n_req, threads, timeout_s = args.requests, 8, 30.0
    plan_sizes = [sizes[i % len(sizes)] for i in range(n_req)]
    max_batch = 4

    def make_server(replicas):
        repo = serving.ModelRepository()
        repo.add_function("m", fn, sig)
        cfg = serving.ServingConfig(
            max_batch_size=max_batch, max_latency_us=500,
            queue_depth=256, num_workers=2, retry_backoff_ms=1,
            retry_max=2, replicas=replicas, replica_heartbeat_ms=20,
            replica_heartbeat_window_ms=250, circuit_cooldown_ms=100)
        return repo, serving.ModelServer(repo, cfg)

    def drive(srv, monitor=None):
        """One closed-loop round: every client honors retry-after with
        per-client seeded jitter.  Returns (outs, errors, durs, wall).
        """
        import random as _random
        outs = [None] * n_req
        durs = [None] * n_req
        errors = []

        def worker(tid):
            jrng = _random.Random(1000 + tid)
            for i in range(tid, n_req, threads):
                n = plan_sizes[i]
                t0 = time.perf_counter()
                try:
                    outs[i] = honor_retry_after(
                        lambda: srv.predict("m", payloads[n],
                                            timeout=timeout_s),
                        attempts=6, rng=jrng,
                        deadline=Deadline.start(timeout_s))
                except Exception as e:          # noqa: BLE001
                    errors.append(e)
                durs[i] = time.perf_counter() - t0
                if monitor is not None:
                    monitor()

        pool = [threading.Thread(target=worker, args=(t,))
                for t in range(threads)]
        t0 = time.perf_counter()
        for t in pool:
            t.start()
        for t in pool:
            t.join(120)
        wall = time.perf_counter() - t0
        # zero hung requests: every slot resolved or typed error
        done = sum(1 for o in outs if o is not None)
        assert done + len(errors) == n_req, (done, len(errors))
        from mxnet_tpu.base import MXNetError
        assert all(isinstance(e, MXNetError) for e in errors), errors[:3]
        # failed-over requests respect their ORIGINAL deadlines
        assert max(d for d in durs if d is not None) < timeout_s, durs
        return outs, errors, wall

    def check_bytes(outs, refs):
        for i, out in enumerate(outs):
            if out is not None:
                np.testing.assert_array_equal(out, refs[i])

    # --- fault-free single-replica twin: the byte-identity oracle -----
    _, twin = make_server(1)
    with twin:
        refs, twin_err, twin_wall = drive(twin)
    assert not twin_err, twin_err[:3]

    repo, srv = make_server(n_rep)
    entry = repo.get("m")
    result = {"metric": "serving.replicas", "replicas": n_rep,
              "requests_per_phase": n_req,
              "unit": "req/s_during_replica_kill"}
    with srv:
        # --- phase A: healthy load balance --------------------------
        outs, errs, wall_a = drive(srv)
        assert not errs, errs[:3]
        check_bytes(outs, refs)
        rset = srv._replica_sets[entry.uid]
        st = rset.stats()
        per_replica = {r: v["requests"] for r, v in
                       st["replicas"].items()}
        assert all(v > 0 for v in per_replica.values()), \
            f"idle replica under load: {per_replica}"
        # zero extra programs per replica beyond the per-replica bound
        progs = {r: v["programs"]
                 for r, v in rset.debug_state()["replicas"].items()}
        bound = len(bucket_set(max_batch))
        assert all(p <= bound for p in progs.values()), (progs, bound)
        assert len(set(progs.values())) == 1, progs
        result.update(healthy_req_s=round(n_req / wall_a, 2),
                      healthy_load=per_replica,
                      programs_per_replica=progs,
                      program_bound_per_replica=bound)

        if args.faults:
            victim = sorted(per_replica)[1]     # a known, living rid
            # --- phase B: execute-kill -> trip -> failover -> probe --
            tracing.reset()
            fo0 = rset.stats()["failovers"]
            seen_unhealthy = []

            def monitor():
                if rset.replicas().get(victim) == "unhealthy" \
                        and not seen_unhealthy:
                    seen_unhealthy.append(time.perf_counter())

            with faults.plan(
                    f"replica.{victim}.execute=fail,times=18,seed=3"):
                t_kill = time.perf_counter()
                outs, errs, wall_b = drive(srv, monitor=monitor)
                check_bytes(outs, refs)
                assert not errs, errs[:3]       # failover absorbed all
                assert seen_unhealthy, \
                    f"{victim} was never detected unhealthy"
                fo1 = rset.stats()["failovers"]
                assert fo1 > fo0, "no failovers recorded"
                # every rerouted request is accounted: the shared batch
                # span's failover_from tag is copied into each
                # coalesced member's trace, so tagged TRACES count
                # rerouted REQUESTS — at least one per failover of a
                # dispatch group (the counter's unit)
                tagged = sum(
                    1 for tr in tracing.TRACER.traces()
                    if any((s.get("tags") or {}).get("failover_from")
                           for s in tr["spans"]))
                assert tagged >= fo1 - fo0 > 0, (tagged, fo1 - fo0)
                # drained: nothing stuck in flight on the dead replica
                assert rset.replica(victim).inflight == 0
                # bounded goodput dip: the kill phase still completed
                # every request in comparable wall time
                assert wall_b < max(20 * wall_a, 10.0), (wall_a, wall_b)
                # recovery: once the fail budget exhausts, the breaker
                # probe re-closes the replica
                deadline = time.monotonic() + 20
                while rset.replicas()[victim] != "healthy":
                    assert time.monotonic() < deadline, \
                        rset.debug_state()
                    honor_retry_after(
                        lambda: srv.predict(
                            "m", payloads[1], timeout=timeout_s),
                        attempts=6)
                    time.sleep(0.01)
            result.update(
                chaos_req_s=round(n_req / wall_b, 2),
                value=round(n_req / wall_b, 2),
                detect_ms=round(
                    1e3 * (seen_unhealthy[0] - t_kill), 1),
                failovers=fo1 - fo0,
                failover_trace_tags=tagged)

            # --- phase C: heartbeat stall -> dark -> prewarm rejoin --
            p0 = rset.replica(victim).prewarms
            r0 = rset.replica(victim).requests
            with faults.plan(
                    f"replica.{victim}.heartbeat=stall,ms=1500,times=1"):
                deadline = time.monotonic() + 10
                while rset.replicas()[victim] != "unhealthy":
                    assert time.monotonic() < deadline, \
                        rset.debug_state()
                    srv.predict("m", payloads[1], timeout=timeout_s)
                    time.sleep(0.005)
                # dark window: the set keeps serving byte-identical
                outs, errs, _ = drive(srv)
                assert not errs, errs[:3]
                check_bytes(outs, refs)
            # rejoin ONLY after a fresh prewarm pass
            deadline = time.monotonic() + 20
            while rset.replicas()[victim] != "healthy":
                assert time.monotonic() < deadline, rset.debug_state()
                time.sleep(0.02)
            rep = rset.replica(victim)
            assert rep.prewarms == p0 + 1, (p0, rep.prewarms)
            # recovered: the rejoined replica takes traffic again
            deadline = time.monotonic() + 20
            while rset.replica(victim).requests <= r0:
                assert time.monotonic() < deadline, rset.stats()
                drive(srv)
            result.update(
                rejoin_prewarms=rep.prewarms,
                heartbeat_detected=True,
                recovered_requests=rset.replica(victim).requests - r0)
        final = rset.stats()
        result["final_states"] = {r: v["state"]
                                  for r, v in final["replicas"].items()}
    result.setdefault("value", result["healthy_req_s"])
    return result


def cache_roundtrip(args):
    """ISSUE-6 CI criterion: serve -> kill the process -> restart on
    the same cache dir -> the warm restart compiles ZERO new XLA
    programs (miss counter stays 0).  Runs the serve loop twice in
    fresh subprocesses sharing one compile-cache dir + workdir, and
    prints a summary JSON with cold-start before/after."""
    def child(tmp):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--roundtrip-child",
               "--cache-dir", os.path.join(tmp, "cache"),
               "--workdir", os.path.join(tmp, "work"),
               "--requests", "8", "--threads", "4",
               "--max-batch", str(args.max_batch),
               "--latency-us", str(args.latency_us)]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.splitlines() if l.strip()]
        return json.loads(lines[-1])

    with tempfile.TemporaryDirectory() as tmp:
        os.makedirs(os.path.join(tmp, "work"), exist_ok=True)
        cold = child(tmp)       # first start: compiles + populates
        warm = child(tmp)       # restart on the same cache dir
    assert cold["compile_cache_misses"] > 0, cold
    assert cold["errors"] == 0 and warm["errors"] == 0, (cold, warm)
    # the acceptance criterion: a warm-cache restart compiles zero new
    # XLA programs — every bucket deserializes from the persistent cache
    assert warm["compile_cache_misses"] == 0, \
        f"warm restart recompiled: {warm}"
    assert warm["compile_cache_hits"] >= cold["compile_cache_misses"], \
        (warm, cold)
    assert warm["prewarm_compiled"] == 0, warm
    assert warm["prewarm_disk_hits"] == warm["prewarm_buckets"], warm
    summary = {
        "metric": "serving.cache_roundtrip",
        "value": warm["cold_start_ms"],
        "unit": "ms_cold_start_warm_cache",
        "cold_start_ms_cold_cache": cold["cold_start_ms"],
        "cold_start_ms_warm_cache": warm["cold_start_ms"],
        "first_run_compiles": cold["compile_cache_misses"],
        "warm_run_compiles": warm["compile_cache_misses"],
        "warm_run_disk_hits": warm["prewarm_disk_hits"],
    }
    print(json.dumps(summary))
    print("serving cache roundtrip ok (zero recompiles on warm "
          "restart)", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI tier: assert the serving acceptance "
                         "criteria, not just measure")
    ap.add_argument("--cache-roundtrip", action="store_true",
                    help="CI tier: start -> kill -> restart on one "
                         "compile-cache dir; assert zero recompiles")
    ap.add_argument("--decode", action="store_true",
                    help="autoregressive decode tier: Poisson arrivals "
                         "through the continuous-batching engine; "
                         "tokens/sec + TTFT/per-token percentiles "
                         "(--smoke asserts the ISSUE-7 criteria)")
    ap.add_argument("--quantized", action="store_true",
                    help="quantized-artifact tier: export f32 + int8, "
                         "serve both versions under load; req/s side "
                         "by side, artifact compression ratio "
                         "(--smoke asserts tamper rejection + the "
                         "program bound)")
    ap.add_argument("--faults", action="store_true",
                    help="chaos tier: a seeded 5%% execute-fault plan "
                         "plus decode poison + cache rot through the "
                         "resilience layer — asserts zero hung "
                         "requests, typed failures, bounded p99, "
                         "leak-free quarantine, and circuit "
                         "open->probe->close (docs/serving.md §8); "
                         "numpy fakes only, zero XLA compiles")
    ap.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="replica tier (docs/serving.md §10): serve "
                         "through N replicas with health-checked "
                         "least-loaded routing; closed-loop clients "
                         "honor retry-after hints with jitter.  With "
                         "--faults, runs the deterministic failover "
                         "ladder (kill -> detect -> reroute -> probe "
                         "recovery -> heartbeat stall -> prewarm-gated "
                         "rejoin) and asserts the ISSUE-13 criteria; "
                         "numpy fakes, zero XLA compiles")
    ap.add_argument("--shared-prefix", type=float, nargs="?",
                    const=0.8, default=None, metavar="P",
                    help="with --decode: shared-prefix traffic tier — "
                         "fraction P of prompts share one long prefix "
                         "(default 0.8); serves the mix with the "
                         "prefix cache off then on and reports TTFT "
                         "p50/p99 + hit ratio side by side (--smoke "
                         "asserts byte-identical outputs and >= 2x "
                         "TTFT p50)")
    ap.add_argument("--speculative", action="store_true",
                    help="with --decode: speculative-decoding tier — "
                         "plain vs spec_k=3 over a cost-realistic "
                         "fake target/draft pair; tokens/sec side by "
                         "side + acceptance rate (--smoke asserts "
                         "byte-identical outputs and >= 1.3x "
                         "tokens/sec)")
    ap.add_argument("--decode-requests", type=int,
                    default=int(os.environ.get(
                        "BENCH_DECODE_REQUESTS", 20)))
    ap.add_argument("--decode-rate", type=float,
                    default=float(os.environ.get(
                        "BENCH_DECODE_RATE", 25)))
    ap.add_argument("--roundtrip-child", action="store_true",
                    help=argparse.SUPPRESS)       # internal
    ap.add_argument("--cache-dir",
                    default=os.environ.get("BENCH_SERVING_CACHE_DIR"))
    ap.add_argument("--workdir", default=None,
                    help="artifact dir (reused when it already holds "
                         "the export — the roundtrip's restart path)")
    ap.add_argument("--requests", type=int,
                    default=int(os.environ.get(
                        "BENCH_SERVING_REQUESTS", 48)))
    ap.add_argument("--threads", type=int,
                    default=int(os.environ.get(
                        "BENCH_SERVING_THREADS", 16)))
    ap.add_argument("--max-batch", type=int,
                    default=int(os.environ.get(
                        "BENCH_SERVING_MAX_BATCH", 8)))
    ap.add_argument("--latency-us", type=int,
                    default=int(os.environ.get(
                        "BENCH_SERVING_LATENCY_US", 2000)))
    ap.add_argument("--trace-out",
                    default=os.environ.get("BENCH_SERVING_TRACE_OUT"),
                    help="where to write the p99 exemplar's "
                         "chrome-trace (default: next to the bench "
                         "workdir artifacts; set this to place it "
                         "next to the BENCH json)")
    args = ap.parse_args()

    if args.cache_roundtrip:
        cache_roundtrip(args)
        return

    if args.replicas:
        print(json.dumps(run_replicas(args)))
        print("serving replica smoke ok (failover ladder green)"
              if args.faults else "serving replica smoke ok",
              file=sys.stderr)
        return

    if args.faults:
        print(json.dumps(run_faults(args)))
        print("serving chaos smoke ok (no hung requests, circuit "
              "recovered)", file=sys.stderr)
        return

    if args.decode and args.shared_prefix is not None:
        print(json.dumps(run_prefix(args)))
        if args.smoke:
            print("serving shared-prefix smoke ok", file=sys.stderr)
        return

    if args.decode and args.speculative:
        print(json.dumps(run_speculative(args)))
        if args.smoke:
            print("serving speculative smoke ok", file=sys.stderr)
        return

    if args.decode:
        print(json.dumps(run_decode(args)))
        if args.smoke:
            print("serving decode smoke ok", file=sys.stderr)
        return

    if args.quantized:
        print(json.dumps(run_quantized(args)))
        if args.smoke:
            print("serving quantized smoke ok", file=sys.stderr)
        return

    def _run(workdir):
        return run(args.requests, args.threads, args.max_batch,
                   args.latency_us, workdir, args.smoke,
                   cache_dir=args.cache_dir,
                   shed_phase=not args.roundtrip_child,
                   trace_out=args.trace_out)

    if args.workdir is not None:
        os.makedirs(args.workdir, exist_ok=True)
        result = _run(args.workdir)
    else:
        with tempfile.TemporaryDirectory() as workdir:
            result = _run(workdir)
    print(json.dumps(result))
    if args.smoke:
        print("serving smoke ok", file=sys.stderr)


if __name__ == "__main__":
    main()
