"""Diagnose the runtime environment (reference parity:
``tools/diagnose.py`` upstream, which prints platform/pip/hardware
info for bug reports).

Prints: platform + Python, jax/jaxlib/numpy versions, the JAX backend
and device list, every ``MXNET_*`` env knob (registry defaults plus
anything set in the environment), native-library availability, the
persistent compile-cache state (dir, entry count, bytes, hit ratio —
so a mis-set MXNET_COMPILE_CACHE_DIR is diagnosable in one command),
the request-tracer / flight-recorder state, and a runtime-metrics
snapshot.  With ``--metrics-smoke`` it also enables the metrics
registry, dispatches one op, and verifies the pipeline end to end
(used as a CI smoke step by ci/runtime_functions.sh).

``--trace-smoke`` runs a traced serving round trip IN PROCESS — one
``predict()`` and one ``generate()`` through a ModelServer over
fake (numpy, zero-compile) models with ``MXNET_TRACE`` forced on —
then asserts the span chains (admission -> queue wait -> batch/execute
on the predict side; admission -> queue wait -> prefill -> decode
steps -> evict on the generate side), the p99 exemplar link, and that
the flight-recorder dump is non-empty and parses as chrome trace.
This is the CI gate for docs/observability.md's tracing section
(ci/runtime_functions.sh serving_smoke).

``--flight-dump [PATH]`` writes the in-process flight-recorder
snapshot (tracer stats + completed-trace ring) as JSON to PATH
(default ./flight_record.json) — the on-demand half of the flight
recorder.

Usage: python tools/diagnose.py [--metrics-smoke] [--trace-smoke]
                                [--flight-dump [PATH]]
"""
import json
import os
import platform
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _section(title):
    print(f"\n----------{title}----------")


def diagnose(metrics_smoke=False):
    _section("Platform Info")
    print(f"Platform     : {platform.platform()}")
    print(f"system       : {platform.system()}")
    print(f"node         : {platform.node()}")
    print(f"release      : {platform.release()}")
    print(f"version      : {platform.version()}")

    _section("Python Info")
    print(f"version      : {platform.python_version()}")
    print(f"compiler     : {platform.python_compiler()}")
    print(f"implementation: {platform.python_implementation()}")

    _section("Framework Info")
    import numpy as np
    import jax
    import mxnet_tpu as mx
    print(f"mxnet_tpu    : {mx.__version__}")
    print(f"numpy        : {np.__version__}")
    print(f"jax          : {jax.__version__}")
    try:
        import jaxlib
        print(f"jaxlib       : {jaxlib.__version__}")
    except Exception:                       # noqa: BLE001
        pass
    print(f"backend      : {jax.default_backend()}")
    print(f"device_count : {jax.device_count()}")
    for d in jax.devices():
        print(f"  device     : {d} ({d.device_kind})")
    from mxnet_tpu.lib import nativelib
    print(f"native io lib: {'available' if nativelib.available() else 'absent'}")

    _section("Environment")
    for name, (default, _doc) in sorted(mx.base.list_env_vars().items()):
        cur = os.environ.get(name)
        mark = f"{cur}  (set)" if cur is not None else f"{default}  (default)"
        print(f"{name}={mark}")
    extra = sorted(k for k in os.environ
                   if k.startswith(("MXNET_", "DMLC_", "JAX_", "XLA_"))
                   and k not in mx.base.list_env_vars())
    for k in extra:
        print(f"{k}={os.environ[k]}  (set, unregistered)")

    _section("Compile Cache")
    from mxnet_tpu import compile_cache
    st = compile_cache.get_default().stats()
    if not st["enabled"]:
        print("dir          : (disabled — set MXNET_COMPILE_CACHE_DIR "
              "for zero-cold-start serving; docs/serving.md §5)")
    else:
        total = st["hits"] + st["misses"]
        ratio = f"{st['hits'] / total:.2f}" if total else "n/a"
        print(f"dir          : {st['dir']}")
        print(f"entries      : {st['entries']}")
        print(f"bytes        : {st['bytes']} "
              f"(bound {st['max_bytes'] or 'unbounded'})")
        print(f"hit ratio    : {ratio}  (this process: {st['hits']} hit / "
              f"{st['misses']} miss / {st['corrupt']} corrupt / "
              f"{st['evictions']} evicted)")
        print(f"topology key : {compile_cache.topology_fingerprint()}")

    _section("Concurrency Sanitizer")
    from mxnet_tpu import engine
    print(f"active       : {engine.sanitizer_active()}  "
          f"(MXNET_ENGINE_SANITIZE=1 to enable lock-order recording + "
          f"tracked-array assertions; docs/static_analysis.md)")

    _section("Threads")
    from mxnet_tpu import base as _base
    rows = engine.thread_registry()
    if not engine.sanitizer_active():
        print("registry     : (off — MXNET_ENGINE_SANITIZE=1 records "
              "every engine.make_thread with owner + spawn site, and "
              "check_thread_leaks() fails tests whose threads outlive "
              "their owner's stop)")
    elif not rows:
        print("registry     : 0 framework thread(s) registered")
    else:
        print(f"registry     : {len(rows)} framework thread(s)")
        for r in rows:
            flags = ["daemon" if r["daemon"] else "non-daemon"]
            if r["abandoned"]:
                flags.append(f"abandoned: {r['abandoned']}")
            print(f"  {r['name']:<28s} owner={r['owner']} "
                  f"site={r['site']} age={r['age_s']:.1f}s "
                  f"({', '.join(flags)})")
    print(f"deterministic: {len(_base.list_deterministic())} declared "
          f"surface(s) (base.declare_deterministic; ambient entropy on "
          f"them is a lint error — mxlint determinism-soundness)")

    _section("Fault Injection")
    from mxnet_tpu import faults
    sites = faults.declared_sites()
    print(f"declared     : {len(sites)} sites "
          f"(faults.declared_sites(); tables in docs/serving.md §8 + "
          f"docs/training_resilience.md §2)")
    plan = faults.active()
    if plan is None:
        print("plan         : (off — set MXNET_FAULTS to chaos-test "
              "the serving resilience layer; docs/serving.md §8)")
    else:
        print(f"plan         : {plan.spec}")
        for rule in plan.rules:
            if not faults.pattern_matches_declared(rule.pattern):
                print(f"  DEAD RULE  : {rule.spec()} matches no "
                      f"declared site — it can never fire")
            elif not faults.pattern_matches_declared(rule.pattern,
                                                     mode=rule.mode):
                print(f"  DEAD RULE  : {rule.spec()}: no site matching "
                      f"{rule.pattern!r} honors mode {rule.mode!r} — "
                      f"it can never fire")
        for key, fired in sorted(plan.counters().items()):
            print(f"  fired      : {key} x{fired}")

    _section("Training Resilience")
    from mxnet_tpu.base import get_env
    timeout_ms = get_env("MXNET_TRAIN_STEP_TIMEOUT_MS", typ=float)
    slow = get_env("MXNET_TRAIN_SLOW_STEP_FACTOR", typ=float)
    print(f"step deadline: "
          + (f"{timeout_ms:g}ms (TrainStepTimeoutError past it)"
             if timeout_ms else
             "(off — set MXNET_TRAIN_STEP_TIMEOUT_MS to bound a "
             "wedged collective; docs/training_resilience.md §3)"))
    print(f"straggler    : "
          + (f"step > {slow:g}x rolling median -> train.slow_steps + "
             f"incident dump" if slow else
             "(off — set MXNET_TRAIN_SLOW_STEP_FACTOR)"))
    print(f"supervisor   : crash-loop breaker after "
          f"{get_env('MXNET_TRAIN_MAX_RESTARTS', typ=int)} consecutive "
          f"restarts; backoff "
          f"{get_env('MXNET_TRAIN_RESTART_BACKOFF_MS', typ=float):g}ms "
          f"doubling, cap "
          f"{get_env('MXNET_TRAIN_RESTART_BACKOFF_MAX_MS', typ=float):g}"
          f"ms (jitter U[0.5, 1.0))")
    from mxnet_tpu import runtime_metrics as _trm
    if _trm.enabled():
        print(f"restarts     : {_trm.TRAIN_RESTARTS.value():g} "
              f"(+ {_trm.TRAIN_STEP_TIMEOUTS.value():g} step "
              f"timeout(s), {_trm.TRAIN_SLOW_STEPS.value():g} slow "
              f"step(s) this process)")

    _section("Replica Serving")
    n_rep = get_env("MXNET_SERVING_REPLICAS", typ=int)
    print(f"replicas     : {n_rep}  (MXNET_SERVING_REPLICAS; > 1 "
          f"serves every model through a health-checked ReplicaSet; "
          f"docs/serving.md §10)")
    print(f"heartbeat    : every "
          f"{get_env('MXNET_SERVING_REPLICA_HEARTBEAT_MS', typ=float)}"
          f"ms, stale past "
          f"{get_env('MXNET_SERVING_REPLICA_HEARTBEAT_WINDOW_MS', typ=float)}"
          f"ms -> UNHEALTHY")
    print(f"failure trip : "
          f"{get_env('MXNET_SERVING_REPLICA_FAILURE_THRESHOLD', typ=int)}"
          f" consecutive typed failures -> UNHEALTHY (probe after "
          f"cooldown)")
    try:
        import jax
        n_dev = len(jax.devices())
        from mxnet_tpu.parallel.placement import replica_groups
        groups = replica_groups(max(1, n_rep), oversubscribe=None)
        print(f"placement    : {n_dev} device(s) -> "
              f"{len(groups)} group(s)"
              + ("  (oversubscribed: logical replicas)"
                 if n_dev < max(1, n_rep) else ""))
    except Exception as e:      # noqa: BLE001 — diagnostics best-effort
        print(f"placement    : unavailable ({e})")

    _section("Traffic / Autoscaling / Admission")
    tiers = get_env("MXNET_SERVING_TENANT_TIERS", typ=str)
    if tiers:
        from mxnet_tpu.serving.admission import parse_tier_spec
        try:
            parsed = parse_tier_spec(tiers)
            print(f"tiers        : {len(parsed)} "
                  f"({', '.join(parsed)})  "
                  f"(MXNET_SERVING_TENANT_TIERS; docs/serving.md §11)")
            print(f"shed start   : pressure >= "
                  f"{get_env('MXNET_SERVING_ADMISSION_SHED_START', typ=float):g}"
                  f" sheds the lowest tier first (gold-class tiers "
                  f"hold to 1.0)")
        except Exception as e:  # noqa: BLE001 — diagnostics best-effort
            print(f"tiers        : INVALID spec ({e})")
    else:
        print("tiers        : (off — set MXNET_SERVING_TENANT_TIERS "
              "for per-tenant quota buckets + priority shedding; "
              "docs/serving.md §11)")
    slo_ttft = get_env("MXNET_SERVING_AUTOSCALE_SLO_TTFT_P99_MS",
                       typ=float)
    slo_lat = get_env("MXNET_SERVING_AUTOSCALE_SLO_LATENCY_P99_MS",
                      typ=float)
    q_high = get_env("MXNET_SERVING_AUTOSCALE_QUEUE_HIGH", typ=int)
    targets = [s for s in (
        f"ttft p99 {slo_ttft:g}ms" if slo_ttft else None,
        f"latency p99 {slo_lat:g}ms" if slo_lat else None,
        f"queue >= {q_high}" if q_high else None) if s]
    print(f"autoscaler   : "
          f"{get_env('MXNET_SERVING_AUTOSCALE_MIN', typ=int)}"
          f"-{get_env('MXNET_SERVING_AUTOSCALE_MAX', typ=int)} "
          f"replicas, tick "
          f"{get_env('MXNET_SERVING_AUTOSCALE_INTERVAL_MS', typ=float):g}"
          f"ms, up after "
          f"{get_env('MXNET_SERVING_AUTOSCALE_BREACH_TICKS', typ=int)} "
          f"breach tick(s), down after "
          f"{get_env('MXNET_SERVING_AUTOSCALE_IDLE_TICKS', typ=int)} "
          f"idle tick(s)")
    print(f"slo targets  : "
          + (", ".join(targets) if targets else
             "(none — pass SLOTargets(...) or set "
             "MXNET_SERVING_AUTOSCALE_SLO_*)"))
    if _trm.enabled():
        dec = _trm.SERVING_AUTOSCALE_DECISIONS
        models = dec.label_values("model")
        acts = {a: int(sum(dec.value(model=m, action=a)
                           for m in models))
                for a in ("up", "down", "blocked", "error")}
        if any(acts.values()):
            print(f"decisions    : " + ", ".join(
                f"{v} {k}" for k, v in acts.items() if v)
                + "  (serving.autoscale.decisions this process)")
        sheds = _trm.SERVING_TENANT_SHED.total()
        if sheds:
            print(f"tenant sheds : {sheds:g}  (serving.tenant.shed "
                  f"this process)")

    _section("Tracing / Flight Recorder")
    from mxnet_tpu import tracing
    st = tracing.TRACER.stats()
    if not st["enabled"]:
        print("enabled      : False  (MXNET_TRACE=1 for per-request "
              "span timelines + the flight recorder; "
              "docs/observability.md)")
    else:
        print(f"enabled      : True  (sample={st['sample']}, "
              f"ring={st['ring']})")
        print(f"traces       : {st['completed']} completed in ring / "
              f"{st['active']} active / {st['traces_started']} started "
              f"/ {st['traces_unsampled']} sampled out / "
              f"{st['traces_evicted']} evicted")
        print(f"spans        : {st['spans']} recorded / "
              f"{st['spans_dropped']} dropped")
    incidents = tracing.incident_paths()
    print(f"incidents    : {len(incidents)}"
          + ("".join(f"\n  dump       : {p}" for p in incidents)))

    _section("Runtime Metrics")
    from mxnet_tpu import runtime_metrics as rm
    print(f"enabled      : {rm.enabled()}")
    if metrics_smoke:
        rm.enable()
        a = mx.nd.ones((8, 8))
        mx.nd.dot(a, a).wait_to_read()
        mx.waitall()
        assert rm.OP_INVOKE.value(op="dot") >= 1, "metrics pipeline broken"
        mem = rm.sample_memory()
        print(f"memory sample: {mem}")
    snap = rm.snapshot()
    if not snap:
        print("(no metrics recorded)")
    for name, m in sorted(snap.items()):
        if not m["values"]:
            continue
        print(f"{name} [{m['type']}]: {m['values']}")
    if metrics_smoke:
        print("\nmetrics smoke: OK")


class _FakeLM:
    """Zero-compile decode model (numpy only) so the trace smoke runs
    in CI time: deterministic logits, no jax programs."""

    vocab_size = 8
    max_context = 16

    def prefill(self, tokens, length, block_table):
        import numpy as np
        return np.eye(self.vocab_size,
                      dtype=np.float32)[int(length) % self.vocab_size]

    def decode_step(self, tokens, positions, block_tables):
        import numpy as np
        out = np.zeros((tokens.shape[0], self.vocab_size), np.float32)
        out[np.arange(tokens.shape[0]),
            (tokens + 1) % self.vocab_size] = 1.0
        return out


def trace_smoke():
    """Traced predict + generate round trip; asserts the span chains,
    the exemplar link, and a non-empty, parsable flight-recorder dump.
    The serving_smoke CI job runs this."""
    import tempfile

    import numpy as np

    from mxnet_tpu import runtime_metrics as rm, serving, tracing
    tracing.enable(sample=1.0)
    tracing.reset()
    rm.enable()

    repo = serving.ModelRepository()
    repo.add_function("echo", lambda x: x * 2.0,
                      [{"shape": [None, 3], "dtype": "float32"}])
    repo.add_decoder("lm", _FakeLM())
    cfg = serving.ServingConfig(decode_page_size=4, decode_pool_pages=16,
                                decode_max_batch=2,
                                decode_max_new_tokens=4)
    srv = serving.ModelServer(repo, cfg)
    try:
        out = srv.predict("echo", np.ones((2, 3), np.float32),
                          timeout=120)
        np.testing.assert_allclose(out, 2.0)
        toks = srv.generate("lm", [1, 2, 3], max_new_tokens=3,
                            timeout=120)
        assert len(toks) == 3, toks
        state = srv.debug_state()
    finally:
        srv.stop()

    # span chains: one trace each, every span parent-linked inside it
    pt = tracing.TRACER.last(root="serving.predict")
    gt = tracing.TRACER.last(root="serving.generate")
    assert pt is not None and gt is not None, tracing.TRACER.stats()
    for tr, need in (
            (pt, {"serving.predict", "serving.admit",
                  "serving.queue_wait", "serving.batch",
                  "serving.execute"}),
            (gt, {"serving.generate", "decode.admission",
                  "decode.queue_wait", "decode.prefill", "decode.step",
                  "decode.evict"})):
        names = {s["name"] for s in tr["spans"]}
        assert need <= names, (sorted(need - names), sorted(names))
        ids = {s["span_id"] for s in tr["spans"]}
        for s in tr["spans"]:
            assert s["trace_id"] == tr["trace_id"], s
            assert s["parent_id"] is None or s["parent_id"] in ids, s

    # exemplar link: the p99 resolves to the predict trace
    ex = rm.SERVING_REQUEST_SECONDS.exemplar_for_quantile(
        0.99, model="echo")
    assert ex == pt["trace_id"], (ex, pt["trace_id"])

    # flight-recorder dump: non-empty and parsable (the CI criterion)
    with tempfile.TemporaryDirectory() as tmp:
        fpath = os.path.join(tmp, "flight.json")
        with open(fpath, "w") as f:
            json.dump(tracing.flight_record(state=state), f,
                      default=str)
        with open(fpath) as f:
            rec = json.load(f)
        assert rec["traces"], "flight-recorder dump is empty"
        assert rec["state"]["repository"]["lm"]["current"] == 1
        cpath = tracing.dump_chrome_trace(
            os.path.join(tmp, "trace.json"), [pt, gt])
        with open(cpath) as f:
            events = json.load(f)["traceEvents"]
        assert len(events) > 8, "chrome-trace dump is empty"

    print(f"trace smoke: OK ({len(pt['spans'])} predict span(s), "
          f"{len(gt['spans'])} generate span(s), flight recorder "
          f"parsed)")


def main(argv):
    if "--trace-smoke" in argv:
        trace_smoke()
        return 0
    if "--flight-dump" in argv:
        from mxnet_tpu import tracing
        i = argv.index("--flight-dump")
        path = argv[i + 1] if i + 1 < len(argv) \
            and not argv[i + 1].startswith("-") else "flight_record.json"
        with open(path, "w") as f:
            json.dump(tracing.flight_record(), f, default=str)
        print(f"flight record written to {path}")
        return 0
    diagnose(metrics_smoke="--metrics-smoke" in argv)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
