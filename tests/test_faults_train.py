"""Training-plane resilience ladder (docs/training_resilience.md):
fault sites in trainer/io/kvstore/checkpoint, the step watchdog,
checkpoint integrity + corrupt-payload fallback, iterator-cursor and
RNG checkpointing, and TrainingSupervisor's bounded-restart bit-exact
resume — all on numpy fakes; the one real ShardedTrainer test reuses a
single tiny compile.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, io, runtime_metrics as rm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import (CheckpointManager, CrashLoopError,
                                StepWatchdog, TrainingSupervisor,
                                TrainStepTimeoutError, run_with_deadline)


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    faults.clear()
    yield
    faults.clear()


class NumpyTrainer:
    """Deterministic toy trainer on numpy (zero compiles): momentum
    SGD on least squares plus one eager-RNG draw per step, so resume
    is bit-exact only if params, opt state, data cursor AND the RNG
    stream are all restored."""

    def __init__(self, n_features=4, lr=0.05):
        rs = np.random.RandomState(0)
        self.params = {"w": rs.randn(n_features).astype(np.float32)}
        self.opt_state = {"m": np.zeros(n_features, np.float32)}
        self.lr = lr
        self.batches_seen = []          # (global caller tag, checksum)

    def step(self, data, label):
        faults.inject("train.step")     # same site ShardedTrainer has
        w = np.asarray(self.params["w"])
        m = np.asarray(self.opt_state["m"])
        x = np.asarray(getattr(data, "asnumpy", lambda: data)())
        y = np.asarray(getattr(label, "asnumpy", lambda: label)())
        noise = mx.random.uniform(shape=w.shape).asnumpy() * 1e-3
        pred = x @ w
        grad = 2 * x.T @ (pred - y) / len(y) + noise
        m = 0.9 * m + grad
        w = w - self.lr * m
        self.params = {"w": w.astype(np.float32)}
        self.opt_state = {"m": m.astype(np.float32)}
        return float(np.mean((pred - y) ** 2))


def _dataset(n=30, n_features=4):
    rs = np.random.RandomState(1)
    x = rs.randn(n, n_features).astype(np.float32)
    y = (x @ np.arange(1, n_features + 1).astype(np.float32)) \
        .astype(np.float32)
    return x, y


def _supervised_run(ckpt_dir, spec=None, num_steps=12, save_every=3,
                    batch_size=6, record=None, **sup_kw):
    """One supervised training run; returns (losses, supervisor,
    fired-fault counters)."""
    mx.random.seed(7)
    x, y = _dataset()
    it = io.NDArrayIter(x, y, batch_size=batch_size, shuffle=True,
                        seed=11)
    trainer = NumpyTrainer()
    manager = CheckpointManager(ckpt_dir, max_to_keep=4,
                                async_write=False)

    def step_fn(tr, batch):
        if record is not None:
            record.append((supervisor._step,
                           float(batch.data[0].asnumpy().sum())))
        return tr.step(batch.data[0], batch.label[0])

    supervisor = TrainingSupervisor(
        trainer, manager, it, step_fn=step_fn, save_every=save_every,
        backoff_ms=sup_kw.pop("backoff_ms", 1),
        backoff_max_ms=sup_kw.pop("backoff_max_ms", 2), **sup_kw)
    if spec:
        faults.install(spec)
    try:
        losses = supervisor.run(num_steps)
    finally:
        plan = faults.active()
        faults.clear()
        manager.close()
    return losses, supervisor, plan.counters() if plan else {}


# ---------------------------------------------------------------------------
# fault sites
# ---------------------------------------------------------------------------
class TestTrainingFaultSites:
    def test_data_next_site(self):
        x, y = _dataset(12)
        it = io.NDArrayIter(x, y, batch_size=4)
        with faults.plan("train.data.next=fail,times=1"):
            with pytest.raises(faults.InjectedFault) as err:
                it.next()
            assert err.value.site == "train.data.next"
            assert err.value.transient
            # the failed call did not consume the batch
            assert it.next().data[0].shape[0] == 4

    def test_kvstore_push_pull_sites(self):
        kv = mx.kv.create("local")
        kv.init("w", mx.nd.zeros((2,)))
        out = mx.nd.zeros((2,))
        with faults.plan("kvstore.push=fail,times=1;"
                         "kvstore.pull=fail,times=1"):
            with pytest.raises(faults.InjectedFault):
                kv.push("w", mx.nd.ones((2,)))
            with pytest.raises(faults.InjectedFault):
                kv.pull("w", out=out)
        kv.push("w", mx.nd.ones((2,)))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0)

    def test_fake_trainer_step_site(self):
        tr = NumpyTrainer()
        x, y = _dataset(6)
        with faults.plan("train.step=fail,times=1"):
            with pytest.raises(faults.InjectedFault):
                tr.step(x, y)
            assert tr.step(x, y) > 0

    def test_train_glob_matches_all_training_sites(self):
        plan = faults.FaultPlan.parse("train.*=fail")
        assert plan.rules[0].matches("train.step")
        assert plan.rules[0].matches("train.data.next")
        assert not plan.rules[0].matches("kvstore.push")


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
class TestStepWatchdog:
    def test_wedged_step_typed_timeout_no_leaked_thread(self):
        release = threading.Event()
        before = {t.name for t in threading.enumerate()}
        t0 = time.monotonic()
        with pytest.raises(TrainStepTimeoutError) as err:
            run_with_deadline(lambda: release.wait(30), 150,
                              site="train.step")
        elapsed = time.monotonic() - t0
        assert elapsed < 5, elapsed          # deadline, not the wedge
        assert err.value.transient
        assert "150" in str(err.value)
        # unwedge the fake collective: the abandoned worker must exit
        release.set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            leaked = {t.name for t in threading.enumerate()} - before
            if not any(n.startswith("mxnet-watchdog") for n in leaked):
                break
            time.sleep(0.01)
        leaked = {t.name for t in threading.enumerate()} - before
        assert not any(n.startswith("mxnet-watchdog") for n in leaked)

    def test_zero_timeout_runs_in_caller_thread(self):
        seen = []
        run_with_deadline(lambda: seen.append(
            threading.current_thread().name), 0)
        assert seen == [threading.current_thread().name]

    def test_result_and_exception_propagate(self):
        assert run_with_deadline(lambda: 41 + 1, 1000) == 42
        with pytest.raises(ZeroDivisionError):
            run_with_deadline(lambda: 1 // 0, 1000)

    def test_straggler_detection(self):
        wd = StepWatchdog(timeout_ms=0, slow_factor=3.0)
        assert wd.active
        for _ in range(6):
            wd.watch(lambda: time.sleep(0.002))
        assert wd.slow_steps == 0
        wd.watch(lambda: time.sleep(0.05))
        assert wd.slow_steps == 1
        state = wd.debug_state()
        assert state["slow_steps"] == 1 and state["observed"] == 7

    def test_inactive_by_default(self, monkeypatch):
        monkeypatch.delenv("MXNET_TRAIN_STEP_TIMEOUT_MS",
                           raising=False)
        monkeypatch.delenv("MXNET_TRAIN_SLOW_STEP_FACTOR",
                           raising=False)
        assert not StepWatchdog().active

    def test_stall_fault_is_bounded_by_the_deadline(self):
        """train.step ``stall`` (the wedged-collective chaos shape)
        fires INSIDE the watched call, so the deadline bounds it
        instead of the sleep hanging the train-loop thread."""
        wd = StepWatchdog(timeout_ms=150, slow_factor=0)

        def body():
            faults.inject("train.step")
            return 1.0

        with faults.plan("train.step=stall,ms=60000,times=1"):
            t0 = time.monotonic()
            with pytest.raises(TrainStepTimeoutError):
                wd.watch(body)
            assert time.monotonic() - t0 < 5

    def test_abandoned_worker_cannot_clobber_restored_state(self):
        """After a timeout the worker's eventual result is discarded:
        a late-finishing wedged step must never overwrite trainer
        state the supervisor has since restored (run_with_deadline
        returns via the caller, and only the caller commits)."""
        release = threading.Event()
        finished = threading.Event()

        def wedged():
            release.wait(30)
            finished.set()
            return "poisoned result"

        with pytest.raises(TrainStepTimeoutError):
            run_with_deadline(wedged, 100)
        release.set()
        assert finished.wait(5)
        # the poisoned result was dropped on the floor — nothing to
        # assert beyond "no exception, no value escaped": the caller
        # got the typed timeout, not "poisoned result"

    def test_sharded_trainer_wedged_step(self):
        """The real step() wiring: a wedged compiled step raises the
        typed timeout within the deadline instead of hanging."""
        import jax
        from mxnet_tpu import nd, parallel
        from mxnet_tpu.gluon import nn
        net = nn.Dense(4, in_units=4, prefix="wdg_")
        net.initialize()
        mesh = parallel.make_mesh(dp=1, tp=1, sp=1,
                                  devices=jax.devices()[:1])
        x = nd.array(np.ones((2, 4), np.float32))
        y = nd.array(np.ones((2, 4), np.float32))
        trainer = parallel.ShardedTrainer(
            net, lambda out, lab: ((out - lab) ** 2).mean(), mesh,
            optimizer="sgd", example_inputs=(x,), n_labels=1,
            step_timeout_ms=2000)
        # (the first step compiles inside the deadline: 300 ms was too
        # little on a machine busy with the suite's other workers)
        assert float(jax.device_get(trainer.step(x, y))) >= 0
        release = threading.Event()
        wedged = lambda *a, **k: (release.wait(30), None)  # noqa: E731
        trainer._step = wedged
        t0 = time.monotonic()
        with pytest.raises(TrainStepTimeoutError):
            trainer.step(x, y)
        assert time.monotonic() - t0 < 5
        release.set()


# ---------------------------------------------------------------------------
# iterator cursor + RNG state
# ---------------------------------------------------------------------------
class TestCheckpointableIterator:
    @pytest.mark.parametrize("handle", ["pad", "discard", "roll_over"])
    def test_cursor_roundtrip_mid_epochs(self, handle):
        x, y = _dataset(20)
        make = lambda: io.NDArrayIter(  # noqa: E731
            x, y, batch_size=3, shuffle=True,
            last_batch_handle=handle, seed=5)

        def drive(it, n):
            out = []
            for _ in range(n):
                try:
                    b = it.next()
                except StopIteration:
                    it.reset()
                    b = it.next()
                out.append(b.data[0].asnumpy().copy())
            return out

        it = make()
        drive(it, 9)                    # into the second epoch
        cursor = it.get_cursor()
        want = drive(it, 8)
        it2 = make()
        it2.set_cursor(cursor)
        got = drive(it2, 8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_unseeded_shuffle_not_checkpointable(self):
        x, y = _dataset(9)
        it = io.NDArrayIter(x, y, batch_size=3, shuffle=True)
        with pytest.raises(MXNetError, match="seed"):
            it.get_cursor()
        # unshuffled iterators are checkpointable without a seed
        it = io.NDArrayIter(x, y, batch_size=3)
        assert it.get_cursor()["epoch"] == 0

    def test_cursor_config_mismatch_refused(self):
        x, y = _dataset(12)
        it = io.NDArrayIter(x, y, batch_size=3, seed=1)
        cursor = it.get_cursor()
        other = io.NDArrayIter(x, y, batch_size=4, seed=1)
        with pytest.raises(MXNetError, match="batch_size"):
            other.set_cursor(cursor)
        other = io.NDArrayIter(x[:9], y[:9], batch_size=3, seed=1)
        with pytest.raises(MXNetError, match="num_data"):
            other.set_cursor(cursor)
        # a different shuffle setting yields different batches from
        # identical (seed, epoch, position) — must be refused too
        shuffled = io.NDArrayIter(x, y, batch_size=3, shuffle=True,
                                  seed=1)
        with pytest.raises(MXNetError, match="shuffle"):
            shuffled.set_cursor(cursor)

    def test_seeded_epochs_are_reproducible(self):
        x, y = _dataset(12)
        orders = []
        for _ in range(2):
            it = io.NDArrayIter(x, y, batch_size=4, shuffle=True,
                                seed=9)
            epoch = [it.next().data[0].asnumpy().copy()
                     for _ in range(3)]
            orders.append(np.concatenate(epoch))
        np.testing.assert_array_equal(orders[0], orders[1])


class TestRNGStateCheckpoint:
    def test_roundtrip_bit_exact(self):
        mx.random.seed(3)
        mx.random.uniform(shape=(4,)).asnumpy()     # advance stream
        state = mx.random.get_state()
        want = [mx.random.uniform(shape=(3,)).asnumpy()
                for _ in range(3)]
        mx.random.set_state(state)
        got = [mx.random.uniform(shape=(3,)).asnumpy()
               for _ in range(3)]
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_state_is_json_serializable(self):
        import json
        state = mx.random.get_state()
        assert json.loads(json.dumps(state)) == state


# ---------------------------------------------------------------------------
# checkpoint integrity + fallback
# ---------------------------------------------------------------------------
class _TinyState:
    def __init__(self, value=0.0):
        self.params = {"w": np.full(4, value, np.float32)}
        self.opt_state = {"m": np.zeros(4, np.float32)}


class TestCorruptPayloadFallback:
    def _manager_with_steps(self, tmp_path, steps=(1, 2)):
        mngr = CheckpointManager(tmp_path / "ckpt", max_to_keep=4,
                                 async_write=False)
        holder = _TinyState()
        for step in steps:
            holder.params["w"] = np.full(4, float(step), np.float32)
            mngr.save(step, holder, extra={"step": step})
            mngr.wait()
        return mngr

    def test_bit_flipped_blob_falls_back_with_warning(self, tmp_path,
                                                      caplog):
        from mxnet_tpu.parallel.checkpoint import _flip_payload_byte
        mngr = self._manager_with_steps(tmp_path)
        assert mngr.latest_verified_step() == 2
        flipped = _flip_payload_byte(mngr._step_dir(2))
        assert flipped is not None
        target = _TinyState()
        with caplog.at_level("WARNING", logger="mxnet_tpu"):
            step = mngr.restore(target)
        assert step == 1
        np.testing.assert_allclose(np.asarray(target.params["w"]), 1.0)
        assert any("falling back" in r.message for r in caplog.records)
        mngr.close()

    def test_explicit_step_still_raises_on_corruption(self, tmp_path):
        from mxnet_tpu.parallel.checkpoint import _flip_payload_byte
        mngr = self._manager_with_steps(tmp_path)
        _flip_payload_byte(mngr._step_dir(2))
        with pytest.raises(Exception):
            mngr.restore(_TinyState(), step=2)
        mngr.close()

    def test_injected_save_corruption_detected(self, tmp_path):
        mngr = CheckpointManager(tmp_path / "c", async_write=False)
        holder = _TinyState()
        holder.params["w"] = np.full(4, 1.0, np.float32)
        mngr.save(1, holder)
        mngr.wait()
        with faults.plan("checkpoint.save=corrupt,times=1"):
            holder.params["w"] = np.full(4, 2.0, np.float32)
            mngr.save(2, holder)
            mngr.wait()                 # barrier fires the bit flip
        target = _TinyState()
        assert mngr.restore(target) == 1
        np.testing.assert_allclose(np.asarray(target.params["w"]), 1.0)
        mngr.close()

    def test_restore_fail_site_raises_typed(self, tmp_path):
        mngr = self._manager_with_steps(tmp_path, steps=(1,))
        with faults.plan("checkpoint.restore=fail,times=1"):
            with pytest.raises(faults.InjectedFault):
                mngr.restore(_TinyState())
        assert mngr.restore(_TinyState()) == 1
        mngr.close()

    def test_residuals_ride_the_checkpoint_tree(self, tmp_path):
        """Quantized-collective error-feedback residuals are step
        state: they round-trip next to params/opt_state so a
        compressed-sync resume stays on the uninterrupted
        trajectory."""
        holder = _TinyState()
        holder.residuals = {"w": np.full(4, 0.25, np.float32)}
        mngr = CheckpointManager(tmp_path / "c", async_write=False)
        mngr.save(1, holder)
        mngr.wait()
        target = _TinyState()
        target.residuals = {"w": np.zeros(4, np.float32)}
        assert mngr.restore(target) == 1
        np.testing.assert_allclose(np.asarray(target.residuals["w"]),
                                   0.25)
        mngr.close()

    def test_unbarriered_newer_step_never_auto_restored(self,
                                                        tmp_path):
        """A step saved but killed before its barrier (no manifest,
        NEWER than the marker) is torn by definition: when the marker
        step rots, fallback must go OLDER — restoring the unverified
        step would also skip its extra payload (RNG/cursor) and break
        bit-exact resume."""
        from mxnet_tpu.parallel.checkpoint import _flip_payload_byte
        mngr = self._manager_with_steps(tmp_path, steps=(1, 2))
        holder = _TinyState(3.0)
        mngr.save(3, holder)            # kill before wait(): no
        mngr._pending = []              # manifest, marker stays at 2
        assert mngr.latest_verified_step() == 2
        _flip_payload_byte(mngr._step_dir(2))
        target = _TinyState()
        assert mngr.restore(target) == 1
        np.testing.assert_allclose(np.asarray(target.params["w"]), 1.0)
        mngr.close()

    def test_extra_payload_roundtrip_and_gc(self, tmp_path):
        mngr = CheckpointManager(tmp_path / "c", max_to_keep=2,
                                 async_write=False)
        holder = _TinyState()
        for step in (1, 2, 3, 4):
            mngr.save(step, holder, extra={"losses": [0.1] * step})
            mngr.wait()
        assert mngr.load_extra(4) == {"losses": [0.1] * 4}
        # retention GC'd steps 1/2: their sidecars must be gone too
        assert mngr.load_extra(1) is None
        assert not (tmp_path / "c" / "VERIFY-1.json").exists()
        mngr.close()


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------
class TestTrainingSupervisor:
    def test_bit_exact_resume_after_midstep_kill(self, tmp_path):
        ref, _sup, _ = _supervised_run(tmp_path / "ref")
        chaos, sup, fired = _supervised_run(
            tmp_path / "chaos",
            spec="train.step=fail,after=5,times=1")
        assert fired["train.step:fail"] == 1
        assert sup.restarts == 1
        assert chaos == ref             # bit-exact trajectory
        assert sup.debug_state()["latest_verified_step"] == 12

    def test_resume_sees_exactly_batch_k_plus_1(self, tmp_path):
        ref_batches, chaos_batches = [], []
        _supervised_run(tmp_path / "r", record=ref_batches)
        _supervised_run(tmp_path / "c", record=chaos_batches,
                        spec="train.step=fail,after=7,times=1")
        ref_by_step = dict(ref_batches)
        for step, checksum in chaos_batches:
            assert checksum == ref_by_step[step], step
        # the killed step (and the steps replayed from the restore
        # point) were re-attempted — always with the SAME batch, so
        # every unique step saw exactly one batch and none was skipped
        steps = [s for s, _ in chaos_batches]
        assert sorted(set(steps)) == list(range(12))
        assert len(steps) > 12          # the kill forced replays

    def test_kill_during_checkpoint_save(self, tmp_path):
        ref, _s, _ = _supervised_run(tmp_path / "ref")
        chaos, sup, fired = _supervised_run(
            tmp_path / "chaos",
            spec="checkpoint.save=fail,after=1,times=1")
        assert fired["checkpoint.save:fail"] == 1
        assert sup.restarts == 1
        assert chaos == ref

    def test_corrupt_marker_checkpoint_plus_kill(self, tmp_path):
        """The acceptance ladder: corrupt the newest verified payload,
        then kill — restore falls back one checkpoint further and the
        trajectory still matches the twin."""
        ref, _s, _ = _supervised_run(tmp_path / "ref")
        chaos, sup, fired = _supervised_run(
            tmp_path / "chaos",
            spec="train.step=fail,after=7,times=1;"
                 "checkpoint.save=corrupt,after=2,times=1")
        assert fired == {"train.step:fail": 1,
                         "checkpoint.save:corrupt": 1}
        assert sup.restarts == 1
        assert chaos == ref

    def test_transient_restore_failure_stays_supervised(self,
                                                        tmp_path):
        """A transient blip DURING recovery (the checkpoint.restore
        fault site) re-enters the restart policy — bounded by the
        breaker — instead of escaping run()."""
        ref, _s, _ = _supervised_run(tmp_path / "ref")
        chaos, sup, fired = _supervised_run(
            tmp_path / "chaos",
            spec="train.step=fail,after=5,times=1;"
                 "checkpoint.restore=fail,times=1")
        assert fired == {"train.step:fail": 1,
                         "checkpoint.restore:fail": 1}
        assert sup.restarts == 2        # the kill + the restore blip
        assert chaos == ref

    def test_transient_restore_failures_trip_the_breaker(self,
                                                         tmp_path):
        with pytest.raises(CrashLoopError):
            _supervised_run(tmp_path / "c",
                            spec="train.step=fail,after=5,times=1;"
                                 "checkpoint.restore=fail",
                            max_restarts=3)

    def test_unseeded_shuffle_iter_degrades_to_warning(self, tmp_path,
                                                       caplog):
        """An uncheckpointable iterator (shuffle without seed=) must
        not fail the save — the supervisor warns once and runs
        without the bit-exact cursor."""
        x, y = _dataset(18)
        it = io.NDArrayIter(x, y, batch_size=6, shuffle=True)
        mngr = CheckpointManager(tmp_path / "c", async_write=False)
        sup = TrainingSupervisor(
            NumpyTrainer(), mngr, it, save_every=2, backoff_ms=1,
            step_fn=lambda t, b: t.step(b.data[0], b.label[0]))
        with caplog.at_level("WARNING", logger="mxnet_tpu"):
            losses = sup.run(4)
        assert len(losses) == 4
        assert sum("cursor unavailable" in r.message
                   for r in caplog.records) == 1
        assert mngr.load_extra(4)["cursor"] is None
        mngr.close()

    def test_explicit_step_corrupt_injection_applies(self, tmp_path):
        """checkpoint.restore=corrupt on an explicit step= really
        flips the payload (the fired counter must match an observed
        effect, not a no-op)."""
        mngr = CheckpointManager(tmp_path / "c", async_write=False)
        holder = _TinyState(1.0)
        mngr.save(1, holder)
        mngr.wait()
        assert mngr._verify_step(1) == (True, "verified")
        with faults.plan("checkpoint.restore=corrupt,times=1") as plan:
            try:
                mngr.restore(_TinyState(), step=1)
            except Exception:   # noqa: BLE001 — backend may reject rot
                pass
            assert plan.counters()["checkpoint.restore:corrupt"] == 1
        # the fired counter corresponds to a REAL on-disk effect
        ok, why = mngr._verify_step(1)
        assert not ok and "mismatch" in why
        mngr.close()

    def test_deterministic_failure_reraises(self, tmp_path):
        boom = ValueError("shape mismatch")

        def bad_step(_trainer, _batch):
            raise boom

        x, y = _dataset(12)
        it = io.NDArrayIter(x, y, batch_size=4, seed=1)
        mngr = CheckpointManager(tmp_path / "c", async_write=False)
        sup = TrainingSupervisor(NumpyTrainer(), mngr, it,
                                 step_fn=bad_step, backoff_ms=1)
        with pytest.raises(ValueError):
            sup.run(4)
        assert sup.restarts == 0
        mngr.close()

    def test_crash_loop_breaker_trips(self, tmp_path):
        with pytest.raises(CrashLoopError) as err:
            _supervised_run(tmp_path / "c", spec="train.step=fail",
                            max_restarts=2)
        assert err.value.restarts == 2
        assert isinstance(err.value.last_error, faults.InjectedFault)

    def test_backoff_is_jittered_exponential_and_bounded(self,
                                                         tmp_path,
                                                         monkeypatch):
        sleeps = []
        import mxnet_tpu.parallel.supervisor as sup_mod
        monkeypatch.setattr(sup_mod.time, "sleep",
                            lambda s: sleeps.append(s))
        _losses, sup, _ = _supervised_run(
            tmp_path / "c", spec="train.step=fail,after=2,times=3",
            backoff_ms=8, backoff_max_ms=20)
        assert sup.restarts == 3
        lo, hi = 8 / 1e3, 20 / 1e3
        assert len(sleeps) == 3
        assert lo * 0.5 <= sleeps[0] <= lo          # 8ms * U[.5,1)
        assert lo <= sleeps[1] <= 2 * lo            # 16ms * U[.5,1)
        assert hi * 0.5 <= sleeps[2] <= hi          # capped at 20ms

    def test_progress_resets_the_breaker(self, tmp_path):
        """Two kills spread across the run with max_restarts=2: each
        restart makes progress before the next kill, so consecutive
        failures reset and the breaker never trips."""
        chaos, sup, fired = _supervised_run(
            tmp_path / "c",
            spec="train.step=fail,after=3,times=1;"
                 "train.step=fail,after=8,times=1",
            max_restarts=2, num_steps=10)
        assert sup.restarts == 2
        assert fired["train.step:fail"] == 2    # aggregated rules
        assert len(chaos) == 10
        assert sup.debug_state()["consecutive_failures"] == 0

    def test_step_timeout_is_supervised(self, tmp_path):
        """A wedged step -> typed timeout -> supervised restore ->
        completion; the wedge releases at teardown."""
        release = threading.Event()
        wedge = {"armed": True}
        watchdog = StepWatchdog(timeout_ms=200, slow_factor=0)

        def step_fn(trainer, batch):
            def body():
                if wedge.pop("armed", None):
                    release.wait(30)    # the wedged collective
                return trainer.step(batch.data[0], batch.label[0])
            return watchdog.watch(body)

        try:
            x, y = _dataset()
            it = io.NDArrayIter(x, y, batch_size=6, seed=1)
            mngr = CheckpointManager(tmp_path / "c", async_write=False)
            sup = TrainingSupervisor(NumpyTrainer(), mngr, it,
                                     step_fn=step_fn, save_every=3,
                                     backoff_ms=1, backoff_max_ms=2)
            losses = sup.run(6)
            assert len(losses) == 6
            assert sup.restarts == 1
            assert watchdog.timeouts == 1
            mngr.close()
        finally:
            release.set()

    def test_cross_process_resume_from_anchor(self, tmp_path):
        """A NEW supervisor over the same checkpoint dir auto-resumes:
        same losses as one uninterrupted run (the preemption story)."""
        ref, _s, _ = _supervised_run(tmp_path / "ref", num_steps=12)
        first, _s2, _ = _supervised_run(tmp_path / "c", num_steps=6)
        resumed, sup, _ = _supervised_run(tmp_path / "c", num_steps=12)
        assert resumed == ref
        assert first == ref[:6]

    def test_restart_metrics_published(self, tmp_path):
        rm.enable()
        rm.reset()
        try:
            _losses, sup, _ = _supervised_run(
                tmp_path / "c", spec="train.step=fail,after=4,times=1")
            assert rm.TRAIN_RESTARTS.value() == 1
            snap = rm.snapshot()
            recovery = snap["train.recovery.seconds"]["values"][""]
            assert recovery["count"] == 1
        finally:
            rm.disable()
            rm.reset()

    def test_debug_state_shape(self, tmp_path):
        _losses, sup, _ = _supervised_run(tmp_path / "c")
        state = sup.debug_state()
        assert state["step"] == 12
        assert state["restarts"] == 0
        assert state["crash_loop_tripped"] is False
        assert state["latest_verified_step"] == 12
