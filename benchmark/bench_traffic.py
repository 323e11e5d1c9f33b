"""Traffic-plane benchmark / smoke harness (docs/serving.md §11).

One seed-deterministic multi-tenant trace — heavy-tailed arrivals, a
10x step burst mid-trace, shared-prefix clusters, tiered tenants — is
recorded to JSONL, loaded back (the replay consumes the FILE, proving
record/replay end to end), and replayed through closed-loop
retry-after-honoring clients against two identical multi-replica decode
servers:

  frozen — the autoscaler runs with its budget pinned to the seed
           replica count (it senses, publishes admission pressure, and
           logs ``blocked`` decisions, but cannot add capacity);
  scaled — the same controller with headroom (``max_replicas`` > seed).

Both runs suffer the SAME chaos: one replica's heartbeat is stalled as
the burst lands, so the set is down a replica exactly when it can least
afford it.  The last stdout line is one JSON result reporting SLO
attainment, goodput, TTFT percentiles, the typed shed taxonomy per
tier, and the autoscaler decision ledger side by side.

``--smoke`` (the CI tier, ci/runtime_functions.sh traffic_smoke)
asserts the ISSUE-17 acceptance criteria:

- the autoscaler added >= 1 replica under the burst;
- SLO attainment AND goodput improve over the frozen twin;
- p99 TTFT stays bounded (< the request deadline; no silent hangs —
  ``replay_trace`` raising on an unresolved record proves zero hung
  requests structurally);
- every non-ok outcome is a TYPED status (shed/deadline/error), and
  sheds are tier-ordered: the free tier's shed rate >= gold's.

Env knobs: BENCH_TRAFFIC_SEED (0), BENCH_TRAFFIC_DURATION (6.0 s),
BENCH_TRAFFIC_RATE (14 req/s), BENCH_TRAFFIC_STEP_MS (25.0 ms of
decode work per engine step — sized so two replicas saturate under the
burst), BENCH_TRAFFIC_TIMEOUT (6.0 s per-request deadline).
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from mxnet_tpu import faults, runtime_metrics as rm, serving  # noqa: E402
from mxnet_tpu.serving import traffic                         # noqa: E402
from mxnet_tpu.serving.autoscaler import (AutoscalerConfig,   # noqa: E402
                                          SLOTargets)

# gold is quota-exempt; silver and free carry quotas WELL below their
# burst-window demand (zipf makes t1/t2 the heavy silver/free tenants),
# so the tier-ordered part of the shed taxonomy is exercised by quota
# enforcement, not just by full-pressure saturation sheds
TIERS = "gold=100,silver=10/8/12,free=1/2/4"
SLO_TTFT_MS = 400.0


class PacedLM:
    """ChainModel-protocol decode fake whose steps cost real wall time
    (``step_ms`` of sleep), so capacity is finite and a burst actually
    queues: next token = (last + 1) mod vocab."""

    vocab_size = 32
    max_context = 64

    def __init__(self, step_ms):
        self.step_ms = float(step_ms)

    def _row(self, t):
        row = np.zeros((self.vocab_size,), np.float32)
        row[(int(t) + 1) % self.vocab_size] = 1.0
        return row

    def prefill(self, tokens, length, block_table):
        time.sleep(1.5 * self.step_ms / 1e3)
        return self._row(tokens[0, int(length) - 1])

    def decode_step(self, tokens, positions, block_tables):
        time.sleep(self.step_ms / 1e3)
        return np.stack([self._row(t) for t in tokens])


def _build_server(step_ms, replicas):
    repo = serving.ModelRepository()
    repo.add_decoder("lm", PacedLM(step_ms),
                     model_factory=lambda: PacedLM(step_ms))
    cfg = serving.ServingConfig(
        replicas=replicas, tenant_tiers=TIERS,
        decode_page_size=4, decode_pool_pages=129, decode_max_batch=4,
        decode_max_new_tokens=16, replica_heartbeat_ms=25,
        replica_heartbeat_window_ms=200)
    srv = serving.ModelServer(repo, cfg)
    srv.replica_set("lm")           # build + prewarm before traffic
    return srv


def _make_call(srv, timeout_s):
    def call(req):
        t0 = time.monotonic()
        first = []

        def on_token(_tok):
            if not first:
                first.append(time.monotonic())

        srv.generate("lm", traffic.prompt_tokens(req),
                     max_new_tokens=req.max_new_tokens,
                     on_token=on_token, timeout=timeout_s,
                     tenant=f"{req.tenant}:{req.tier}")
        return {"ttft_s": first[0] - t0 if first else None}
    return call


def _run_one(label, trace, *, step_ms, replicas, max_replicas,
             timeout_s, burst_wall_s):
    """Replay ``trace`` against a fresh server with the autoscaler's
    budget capped at ``max_replicas``; stall one replica's heartbeat as
    the burst lands (both twins get identical chaos)."""
    rm.reset()
    rm.enable()
    srv = _build_server(step_ms, replicas)
    rset = srv.replica_set("lm")
    asc = serving.Autoscaler(
        rset,
        SLOTargets(ttft_p99_ms=SLO_TTFT_MS),
        AutoscalerConfig(
            min_replicas=replicas, max_replicas=max_replicas,
            interval_s=0.1, breach_ticks=2, idle_ticks=50,
            cooldown_up_s=0.8, cooldown_down_s=60.0,
            drain_timeout_s=5.0),
        admission=srv.admission_controller(), server_name=srv.name)

    def chaos():
        # one replica goes dark exactly as the burst lands: its
        # heartbeat stalls past the staleness window, the router must
        # fail its in-flight sequences over, and (scaled twin only)
        # the autoscaler must rebuild capacity around the hole
        time.sleep(burst_wall_s)
        with faults.plan("replica.r0.heartbeat=stall,ms=1200,times=1"):
            time.sleep(1.6)

    killer = threading.Thread(target=chaos, daemon=True)
    try:
        asc.start()
        killer.start()
        records, wall_s = traffic.replay_trace(
            trace, _make_call(srv, timeout_s), clients=16, speed=1.0,
            timeout_s=timeout_s)
    finally:
        asc.stop()
        killer.join(5.0)
        srv.stop()
    summary = traffic.summarize(records, wall_s=wall_s,
                                ttft_slo_s=SLO_TTFT_MS / 1e3,
                                latency_slo_s=timeout_s)
    ast = asc.stats()
    out = {
        "label": label,
        "replicas_start": replicas,
        "replicas_max": max_replicas,
        "replicas_added": ast["up"],
        "replicas_final": len(rset.replicas()),
        "autoscale": {k: ast[k] for k in
                      ("ticks", "up", "down", "hold", "blocked",
                       "error")},
        "decisions": [
            {k: d[k] for k in ("t", "action", "reason", "replicas",
                               "target")}
            for d in asc.last_actuations(8)],
        "admission": srv.stats().get("admission", {}),
    }
    for k in ("requests", "ok", "shed", "deadline", "error", "slo_ok",
              "attainment", "goodput_rps", "ttft_p50_s", "ttft_p99_s",
              "latency_p99_s", "wall_s", "by_tier"):
        out[k] = summary[k]
    return out


def _shed_rate(run, tier):
    t = run["by_tier"].get(tier)
    return t["shed"] / t["requests"] if t and t["requests"] else 0.0


def run(args):
    duration = float(os.environ.get("BENCH_TRAFFIC_DURATION", 6.0))
    rate = float(os.environ.get("BENCH_TRAFFIC_RATE", 14.0))
    seed = int(os.environ.get("BENCH_TRAFFIC_SEED", 0))
    step_ms = float(os.environ.get("BENCH_TRAFFIC_STEP_MS", 25.0))
    timeout_s = float(os.environ.get("BENCH_TRAFFIC_TIMEOUT", 6.0))

    cfg = traffic.TraceConfig(
        seed=seed, duration_s=duration, base_rate=rate,
        process="lognormal", models=("lm",), generate_fraction=1.0,
        tenants=6, burst_at=0.45, burst_x=10.0,
        burst_duration_s=duration * 0.25, prompt_max=16, output_max=10,
        output_mean=5.0)
    workdir = args.workdir or tempfile.mkdtemp(prefix="bench_traffic_")
    trace_path = os.path.join(workdir, "trace.jsonl")
    traffic.generate_trace(cfg).save(trace_path)
    trace = traffic.Trace.load(trace_path)   # replay the FILE
    burst_wall_s = cfg.burst_at * duration

    common = dict(step_ms=step_ms, replicas=2, timeout_s=timeout_s,
                  burst_wall_s=burst_wall_s)
    frozen = _run_one("frozen", trace, max_replicas=2, **common)
    scaled = _run_one("scaled", trace, max_replicas=4, **common)

    result = {
        "metric": "serving.traffic.slo_attainment",
        "value": round(scaled["attainment"], 4),
        "unit": "fraction",
        "trace": {"path": trace_path, "requests": len(trace),
                  "duration_s": duration, "base_rate": rate,
                  "burst_x": cfg.burst_x, "seed": seed,
                  "tenants": cfg.tenants, "tiers": TIERS},
        "slo": {"ttft_p99_ms": SLO_TTFT_MS,
                "deadline_s": timeout_s},
        "frozen": frozen,
        "scaled": scaled,
        "attainment_gain": round(
            scaled["attainment"] - frozen["attainment"], 4),
        "goodput_gain_rps": round(
            scaled["goodput_rps"] - frozen["goodput_rps"], 3),
    }

    if args.smoke:
        # ISSUE-17 acceptance: capacity was actually added under the
        # burst, and it bought real attainment + goodput
        assert scaled["replicas_added"] >= 1, scaled["autoscale"]
        assert scaled["attainment"] > frozen["attainment"], \
            (scaled["attainment"], frozen["attainment"])
        assert scaled["goodput_rps"] > frozen["goodput_rps"], \
            (scaled["goodput_rps"], frozen["goodput_rps"])
        # bounded tail: the p99 TTFT of completed requests stays under
        # the request deadline (replay_trace returning at all already
        # proved zero HUNG requests — an unresolved record raises)
        assert scaled["ttft_p99_s"] < timeout_s, scaled["ttft_p99_s"]
        # every non-ok outcome is typed, and sheds are tier-ordered:
        # the free tier pays before gold does
        for run_ in (frozen, scaled):
            assert run_["requests"] == run_["ok"] + run_["shed"] \
                + run_["deadline"] + run_["error"], run_
        if scaled["shed"]:
            assert _shed_rate(scaled, "free") >= \
                _shed_rate(scaled, "gold"), scaled["by_tier"]
        print("traffic smoke ok: scaled "
              f"{scaled['attainment']:.3f} vs frozen "
              f"{frozen['attainment']:.3f} attainment, "
              f"+{scaled['replicas_added']} replica(s) under burst",
              file=sys.stderr)

    print(json.dumps(result))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI tier: assert the traffic-plane acceptance "
                         "criteria, not just measure")
    ap.add_argument("--workdir", default=None,
                    help="where the recorded trace JSONL lands "
                         "(default: fresh temp dir)")
    run(ap.parse_args())


if __name__ == "__main__":
    main()
