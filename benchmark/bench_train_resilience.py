"""Training-plane chaos smoke (docs/training_resilience.md §6).

The serving plane's chaos tier (bench_serving.py --faults) proves the
request path absorbs injected failures; this is the training-plane
twin, end to end on REAL machinery — a compiled ShardedTrainer step,
Orbax sharded checkpoints, the step watchdog, and TrainingSupervisor —
under a seeded fault plan:

1. **watchdog**: a wedged fake collective (the compiled step replaced
   by an Event.wait) raises TrainStepTimeoutError within the
   configured deadline instead of hanging the run.
2. **chaos vs twin**: a supervised run under ``1 mid-step kill + 1
   corrupted checkpoint payload`` (the corruption hits the newest
   VERIFIED step, so restore must detect it via the integrity
   manifest and fall back one checkpoint further — never a torn
   restore) is compared against a fault-free twin: the loss
   trajectory must be IDENTICAL step for step, restarts must equal
   injected kills, and exactly one fallback warning must fire.

CI: ci/runtime_functions.sh training_smoke.  CPU-only, one tiny XLA
compile (~seconds); deterministic via seeded data/shuffle/fault plan.

Usage: python benchmark/bench_train_resilience.py [--smoke]
"""
import logging
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np                                        # noqa: E402

NUM_STEPS = 24
SAVE_EVERY = 6
BATCH = 8
# kill the 15th step; corrupt the 3rd durability barrier (= step 12,
# after the anchor-0 and step-6 barriers) so the marker step is rot
# and restore must fall back to step 6
CHAOS_PLAN = ("train.step=fail,after=14,times=1;"
              "checkpoint.save=corrupt,after=2,times=1")


class _LogCounter(logging.Handler):
    def __init__(self, needle):
        super().__init__()
        self.needle = needle
        self.hits = 0

    def emit(self, record):
        if self.needle in record.getMessage():
            self.hits += 1


def _build(ckpt_dir):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import io, nd, parallel
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.Dense(1, in_units=8, prefix="chaos_net_")
    net.initialize(mx.init.Xavier())
    rs = np.random.RandomState(2)
    x = rs.randn(48, 8).astype(np.float32)
    y = (x @ rs.randn(8).astype(np.float32))[:, None]
    it = io.NDArrayIter(x, y, batch_size=BATCH, shuffle=True, seed=13)
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1,
                              devices=jax.devices()[:1])
    example = nd.array(x[:BATCH])
    trainer = parallel.ShardedTrainer(
        net, lambda out, lab: ((out - lab) ** 2).mean(), mesh,
        optimizer="adamw", optimizer_params={"learning_rate": 1e-2},
        example_inputs=(example,), n_labels=1)
    manager = parallel.CheckpointManager(ckpt_dir, max_to_keep=3,
                                         async_write=False)
    supervisor = parallel.TrainingSupervisor(
        trainer, manager, it, save_every=SAVE_EVERY,
        backoff_ms=5, backoff_max_ms=20)
    return trainer, manager, supervisor


def watchdog_phase():
    """Wedged compiled step -> typed timeout within the deadline."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.Dense(1, in_units=8, prefix="wd_net_")
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1,
                              devices=jax.devices()[:1])
    x = nd.array(np.ones((BATCH, 8), np.float32))
    y = nd.array(np.ones((BATCH, 1), np.float32))
    trainer = parallel.ShardedTrainer(
        net, lambda out, lab: ((out - lab) ** 2).mean(), mesh,
        optimizer="sgd", example_inputs=(x,), n_labels=1,
        step_timeout_ms=500)
    float(jax.device_get(trainer.step(x, y)))   # healthy step first
    release = threading.Event()
    trainer._step = lambda *a, **k: (release.wait(60), None)
    t0 = time.monotonic()
    try:
        trainer.step(x, y)
    except parallel.TrainStepTimeoutError as e:
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0, f"deadline not enforced: {elapsed:.1f}s"
        print(f"watchdog: wedged collective -> {type(e).__name__} in "
              f"{elapsed * 1e3:.0f}ms (deadline 500ms)  OK")
        return
    finally:
        release.set()
    raise AssertionError("wedged step did not raise "
                         "TrainStepTimeoutError")


def _run(ckpt_dir, spec):
    from mxnet_tpu import faults
    trainer, manager, supervisor = _build(ckpt_dir)
    if spec:
        faults.install(spec)
    try:
        losses = supervisor.run(NUM_STEPS)
    finally:
        plan = faults.active()
        faults.clear()
        manager.close()
    return losses, supervisor, plan.counters() if plan else {}


def chaos_phase():
    logger = logging.getLogger("mxnet_tpu")
    fallback = _LogCounter("falling back")
    logger.addHandler(fallback)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            t0 = time.monotonic()
            twin, _sup, _ = _run(os.path.join(tmp, "twin"), None)
            twin_s = time.monotonic() - t0
            t0 = time.monotonic()
            chaos, sup, fired = _run(os.path.join(tmp, "chaos"),
                                     CHAOS_PLAN)
            chaos_s = time.monotonic() - t0
            # read while the checkpoint dir (and its marker) exists
            state = sup.debug_state()
    finally:
        logger.removeHandler(fallback)

    kills = fired.get("train.step:fail", 0)
    corruptions = fired.get("checkpoint.save:corrupt", 0)
    assert kills == 1 and corruptions == 1, fired
    assert sup.restarts == kills, (sup.restarts, kills)
    assert len(chaos) == len(twin) == NUM_STEPS
    diverged = [i for i, (a, b) in enumerate(zip(twin, chaos))
                if a != b]
    assert not diverged, f"trajectory diverged at steps {diverged[:5]}"
    # the corrupted marker step was never restored: exactly one
    # verified-fallback warning, and the run still finished verified
    assert fallback.hits == 1, fallback.hits
    assert state["latest_verified_step"] == NUM_STEPS, state
    assert state["crash_loop_tripped"] is False
    print(f"chaos: {NUM_STEPS} steps, 1 mid-step kill + 1 corrupted "
          f"checkpoint payload -> bit-identical trajectory "
          f"(final loss {chaos[-1]:.6f} == twin {twin[-1]:.6f}), "
          f"restarts == kills == {kills}, verified fallback x1, "
          f"recovery {state['recovery_seconds_total'] * 1e3:.0f}ms  OK")
    print(f"timing: twin {twin_s:.1f}s, chaos {chaos_s:.1f}s")


def main(argv):
    logging.basicConfig(level=logging.WARNING)
    watchdog_phase()
    chaos_phase()
    print("training resilience smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
