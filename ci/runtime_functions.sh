#!/usr/bin/env bash
# CI entry points (reference: ci/docker/runtime_functions.sh — SURVEY.md
# §2.3 CI row).  Each function is one CI job; run as
#   ci/runtime_functions.sh <function>
set -euo pipefail
cd "$(dirname "$0")/.."

# The virtual 8-device CPU mesh: "real runtime, fake scale" (same env the
# driver's multichip dry-run uses).
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"

unittest_cpu() {
    python -m pytest tests/ -x -q
}

sanity_imports() {
    # every public subpackage imports; runtime feature report prints
    python -c "
import mxnet_tpu as mx
import mxnet_tpu.gluon, mxnet_tpu.kvstore, mxnet_tpu.io, mxnet_tpu.image
import mxnet_tpu.module, mxnet_tpu.executor, mxnet_tpu.contrib
import mxnet_tpu.parallel, mxnet_tpu.models, mxnet_tpu.np
import mxnet_tpu.runtime_metrics, mxnet_tpu.monitor
print(mx.runtime.Features())"
    # environment/metrics doctor: end-to-end smoke of the metrics
    # registry (enable -> dispatch -> assert counters)
    python tools/diagnose.py --metrics-smoke
}

diagnose() {
    # standalone doctor job (reference: tools/diagnose.py parity)
    python tools/diagnose.py --metrics-smoke
}

sanity_lint() {
    # codebase-specific static analysis must be clean
    # (docs/static_analysis.md; suppressions carry their justification
    # inline, so "clean" means every finding was fixed or argued).
    # --format json: one finding object per line so CI can annotate the
    # offending lines; any finding fails the job (exit 1).  tools/ is
    # linted too — the linter holds itself to its own rules.
    # --baseline is the ratchet: committed findings don't fail, NEW
    # ones do, so a strict new pass can land before a full-tree sweep.
    python -m tools.mxlint --format json \
        --baseline ci/mxlint_baseline.json mxnet_tpu/ tools/
    # the pre-commit loop must stay usable: a --changed run against
    # HEAD (no diff in CI -> reports nothing) exercises the
    # changed-file filter + the .mxlint_cache fallback path and bounds
    # its latency — the full run above just warmed the cache, so this
    # must return in seconds (docs/static_analysis.md "result cache")
    timeout 30 python -m tools.mxlint mxnet_tpu/ tools/ --changed HEAD
    # baseline drift check: re-record and require the committed file
    # byte-identical — a fixed finding whose entry lingered (or a new
    # one argued into the baseline but not committed) fails the job
    python -m tools.mxlint --format json \
        --baseline ci/mxlint_baseline.json --update-baseline \
        mxnet_tpu/ tools/
    git diff --exit-code -- ci/mxlint_baseline.json
    # chaos specs live in tests/benches too: a typo'd MXNET_FAULTS
    # pattern there is a chaos test that tests nothing — hold them to
    # the declared fault-site registry (most passes stay scoped to the
    # product tree)
    python -m tools.mxlint --format json --select fault-site-soundness \
        tests/ benchmark/
    # tests/benches also construct meshes, shard_maps, and donating
    # jits of their own (sharded-trainer suites, serving benches) — a
    # bad spec or use-after-donate there wedges or corrupts the very
    # run that was supposed to catch regressions.  Hold them to the
    # mxshard partition passes (docs/static_analysis.md, passes 17-19)
    python -m tools.mxlint --format json \
        --select sharding-soundness,replication-soundness,donation-soundness \
        tests/ benchmark/
    # the race trio (thread-role x lockset, docs/static_analysis.md
    # ISSUE-20) runs over tests/benches too: suites and benches spawn
    # their own worker/client threads against the serving objects, and
    # an unlocked compound write there is the same lost update the
    # product tree is held to
    python -m tools.mxlint --format json \
        --select shared-state-race,atomicity,condition-discipline \
        tests/ benchmark/
    # the fault-site tables in docs/serving.md §8 and
    # docs/training_resilience.md §2 are generated from the registry —
    # stale tables fail the job (same discipline as env_vars.md)
    python tools/gen_fault_docs.py --check
    # the pass-scope table in docs/static_analysis.md is generated from
    # tools/mxlint/scopes.py — the single source the passes themselves
    # import, so the docs cannot drift from the predicates
    python tools/gen_lint_docs.py --check
    # then the dynamic half: engine+serving tests double as race tests
    # under the concurrency sanitizer (lock-order recording + tracked-
    # array assertions + the thread registry: every test asserts
    # check_thread_leaks() at teardown via tests/conftest.py)
    MXNET_ENGINE_SANITIZE=1 python -m pytest tests/test_sanitizer.py \
        tests/test_serving.py tests/test_ndarray.py -x -q
    # the thread-heaviest suites (replay client pools, autoscaler +
    # heartbeat loops, replica failover) exercise the leak check and
    # the Eraser-style lockset race detector (engine.watch_races —
    # auto-armed on the serving classes) hardest — the runtime twins
    # of the thread-lifecycle and shared-state-race lint passes
    MXNET_ENGINE_SANITIZE=1 python -m pytest tests/test_traffic.py \
        tests/test_autoscale_admission.py tests/test_serving_replica.py \
        -x -q
}

multichip_dryrun() {
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('multichip ok')"
}

compile_entry() {
    python -c "
import __graft_entry__ as g, jax
fn, args = g.entry()
print(jax.jit(fn).lower(*args).compile() and 'entry compiles')"
}

native_build() {
    # rebuild the C++ IO library and run its tests
    g++ -O2 -shared -fPIC -o mxnet_tpu/lib/libmxnet_tpu_native.so \
        mxnet_tpu/lib/src/nativelib.cc
    python -m pytest tests/test_native.py -x -q
    # the framework-free PJRT consumer of exported StableHLO artifacts
    # (docs/frontends.md §2); header from the bundled XLA includes
    PJRT_INC=$(python - <<'PY'
import os, tensorflow
print(os.path.join(os.path.dirname(tensorflow.__file__), "include"))
PY
)
    g++ -O2 -std=c++17 -I"$PJRT_INC" -o mxnet_tpu/lib/shlo_runner \
        mxnet_tpu/lib/src/shlo_runner.cc -ldl
    # end-to-end artifact run needs a PJRT plugin; opt-in via env
    if [ -n "${MXNET_TEST_PJRT_PLUGIN:-}" ]; then
        python -m pytest tests/test_shlo_runner.py -x -q
    fi
}

examples_smoke() {
    python examples/mnist_gluon.py --epochs 1
    python examples/word_language_model.py --epochs 1
    python examples/ssd_detection.py --iters 40
    python examples/nmt_transformer.py --epochs 1 --min-match 0
    python examples/train_imagenet.py --iters 10 --model resnet18_v1
    python examples/bert_squad.py --steps 20 --batch 8
    # two-stage detector: smoke tier (the convergence gate needs ~120
    # iters; tests/test_detection_contrib.py carries the training
    # assertions, and the full-gate run is
    # `python examples/faster_rcnn.py --iters 120`)
    python examples/faster_rcnn.py --iters 8 --batch-size 4 \
        --min-recall 0
}

serving_smoke() {
    # export LeNet -> serve 48 concurrent requests of 3 batch sizes ->
    # assert the O(log N) program bound via the bucket-cache counter,
    # a recorded p99, and load shedding on a saturated bounded queue
    # (docs/serving.md; ISSUE-2 acceptance criteria)
    python benchmark/bench_serving.py --smoke
    # persistent-compile-cache round trip (ISSUE-6 acceptance): start a
    # server, kill the process, restart against the SAME cache dir —
    # the warm restart must compile ZERO new XLA programs (asserted via
    # the compile-cache miss counter; every bucket deserializes)
    python benchmark/bench_serving.py --cache-roundtrip
    # decode tier (ISSUE-7 acceptance): end-to-end generate round trip
    # (prefill -> N decode steps -> eviction) under Poisson arrivals —
    # asserts continuous batching interleaves (a short request admitted
    # mid-flight beats a long one admitted earlier) and that compiled
    # programs stay <= prefill buckets + 1 across a 20-request
    # mixed-length run
    python benchmark/bench_serving.py --decode --smoke
    # shared-prefix tier (ISSUE-12 acceptance): the 80%-shared-prefix
    # mix served with the prefix cache off then on — byte-identical
    # outputs, hit-ratio counter proves skipped prefill, TTFT p50 at
    # least 2x better with the cache, leak-free shared pages
    python benchmark/bench_serving.py --decode --shared-prefix --smoke
    # speculative tier (ISSUE-12 acceptance): plain vs spec_k=3 over a
    # cost-realistic fake target/draft pair — byte-identical greedy
    # outputs (exact rejection sampling) and >= 1.3x tokens/sec, with
    # the draft acceptance rate reported
    python benchmark/bench_serving.py --decode --speculative --smoke
    # quantized round trip (ISSUE-10 acceptance): export int8 ->
    # tampered-scale manifest rejected at load -> predict through the
    # quantized version under load, with zero XLA programs beyond the
    # same per-version bucket bound the f32 version gets, and the
    # artifact compression ratio reported next to req/s
    python benchmark/bench_serving.py --quantized --smoke
    # traced request round trip (ISSUE-8 acceptance): one predict +
    # one generate with MXNET_TRACE on — asserts the span chains
    # (admission -> queue wait -> batch/execute; admission -> queue
    # wait -> prefill -> decode step -> evict), the p99 exemplar link,
    # and that the flight-recorder dump is non-empty and parsable
    python tools/diagnose.py --trace-smoke
    # chaos tier (ISSUE-11 acceptance): a seeded fault plan (5%
    # execute faults + decode poison + compile-cache rot) through the
    # resilience layer — zero hung requests, typed failures only, p99
    # bounded, quarantine leak-free, circuit opens AND re-closes, and
    # the fault-free twin workload byte-matches with zero extra
    # programs.  Numpy fakes: no XLA compiles in this tier.
    python benchmark/bench_serving.py --faults
    # replica tier (ISSUE-13 acceptance): 3 replicas under load with a
    # seeded kill-a-replica plan — consecutive-failure trip, failover
    # under original deadlines (byte-identical to the fault-free
    # single-replica twin), heartbeat-stall detection by siblings, and
    # prewarm-gated rejoin; zero hung requests, typed failures only,
    # failovers accounted by metric AND trace tags, zero extra
    # programs per replica beyond the per-replica bucket bound.
    # Closed-loop clients honor retry-after with jitter.  Numpy fakes:
    # no XLA compiles in this tier.
    python benchmark/bench_serving.py --replicas 3 --faults
    # the decode scheduler + paged-attention kernel + tracer tests
    # double as race tests under the concurrency sanitizer, and the
    # fault/resilience/replica tests join them (deadline/retry/
    # bisection/failover paths cross the same locks)
    MXNET_ENGINE_SANITIZE=1 python -m pytest tests/test_serving_decode.py \
        tests/test_pallas_paged.py tests/test_tracing.py \
        tests/test_faults.py tests/test_serving_replica.py -x -q
}

training_smoke() {
    # training-plane chaos tier (ISSUE-14 acceptance;
    # docs/training_resilience.md §6): a supervised ShardedTrainer run
    # under a seeded fault plan (1 mid-step kill + 1 corrupted
    # checkpoint payload at the newest VERIFIED step) against a
    # fault-free twin — bit-identical loss trajectory, restarts ==
    # injected kills, the corrupt payload detected by the integrity
    # manifest and never restored (verified-step fallback), and a
    # wedged fake collective raising TrainStepTimeoutError within the
    # configured deadline instead of hanging the job.
    python benchmark/bench_train_resilience.py --smoke
    # the watchdog/supervisor/checkpoint suites double as race tests:
    # the deadline worker thread, the fault plan's trigger state, and
    # the incident dumps cross the same locks the sanitizer guards
    MXNET_ENGINE_SANITIZE=1 python -m pytest \
        tests/test_faults_train.py tests/test_faults.py \
        tests/test_checkpoint_sharded.py -x -q
}

traffic_smoke() {
    # traffic-plane tier (ISSUE-17 acceptance; docs/serving.md §11): a
    # seed-0 recorded trace (heavy-tailed multi-tenant arrivals, 10x
    # mid-trace burst, tiered tenants) is saved to JSONL, loaded back,
    # and replayed by closed-loop retry-after-honoring clients against
    # a frozen twin (autoscaler budget pinned) and a scaled twin (real
    # headroom), both losing a replica to a heartbeat stall exactly as
    # the burst lands — asserts the autoscaler added capacity, SLO
    # attainment AND goodput beat the frozen twin, p99 TTFT stays
    # bounded, zero hung requests, and every non-ok outcome is a typed
    # tier-ordered shed.  Numpy fakes: no XLA compiles in this tier.
    python benchmark/bench_traffic.py --smoke
    # the trace replay harness, admission buckets, and autoscale
    # control loop cross the server's locks from extra threads — run
    # their suites under the concurrency sanitizer too
    MXNET_ENGINE_SANITIZE=1 python -m pytest tests/test_traffic.py \
        tests/test_autoscale_admission.py -x -q
}

bench_cpu() {
    # the benchmark's control flow end to end at toy sizes (no TPU
    # required; prints no result: a CPU run measures nothing)
    python3 -m perfbench.run --workload bert-large.pretrain_b32_l128 \
        --seed 1 --seconds 4 --rehearsal --trace 0
}

if [ $# -lt 1 ] || ! declare -F "$1" > /dev/null; then
    echo "usage: ci/runtime_functions.sh <job>" >&2
    echo "jobs: $(declare -F | awk '{print $3}' | tr '\n' ' ')" >&2
    exit 2
fi
"$@"
