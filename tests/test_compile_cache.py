"""Persistent AOT compile cache (mxnet_tpu/compile_cache.py,
docs/serving.md §5): content-addressed keys, atomic corruption-tolerant
storage, LRU bound, executable round-trip, manifest-v3 precompiled
artifacts, and the zero-compile warm restart.

Byte-level behavior (keys, atomicity, corruption, LRU) is tested with
fake payloads — no XLA compile anywhere near those tests; the
executable round-trip tests use one tiny program each (tier-1 budget
discipline: the 870s budget truncates the suite tail if tests get
expensive).
"""
import json
import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import deploy, nd, runtime_metrics as rm, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _metrics_on():
    rm.reset()
    rm.enable()
    yield
    rm.disable()
    rm.reset()


@pytest.fixture()
def cache(tmp_path):
    return cc.CompileCache(str(tmp_path / "cache"), max_bytes=0)


class TestCacheKey:
    def test_deterministic(self):
        a = cc.cache_key("abc", 4, ["float32"], topology="t")
        b = cc.cache_key("abc", 4, ["float32"], topology="t")
        assert a == b and len(a) == 64

    def test_sensitive_to_every_component(self):
        base = cc.cache_key("abc", 4, ["float32"], topology="t")
        assert cc.cache_key("abd", 4, ["float32"], topology="t") != base
        assert cc.cache_key("abc", 8, ["float32"], topology="t") != base
        assert cc.cache_key("abc", 4, ["float16"], topology="t") != base
        assert cc.cache_key("abc", 4, ["float32"], topology="u") != base

    def test_default_topology_carries_versions(self):
        import jax
        fp = cc.topology_fingerprint()
        assert jax.__version__ in fp
        # the default key uses the live topology
        assert cc.cache_key("x", 1, []) == cc.cache_key(
            "x", 1, [], topology=fp)


class TestBytesTier:
    def test_put_get_roundtrip_and_counters(self, cache):
        key = "k" * 64
        assert cache.get(key) is None
        assert cache.misses == 1
        assert cache.put(key, b"payload")
        assert cache.get(key) == b"payload"
        assert cache.hits == 1 and cache.stores == 1
        assert rm.COMPILE_CACHE.value(event="hit") == 1
        assert rm.COMPILE_CACHE.value(event="miss") == 1
        assert rm.COMPILE_CACHE.value(event="store") == 1

    def test_atomic_write_leaves_no_temp_files(self, cache):
        for i in range(4):
            cache.put(f"{i:064d}", b"x" * 100)
        names = os.listdir(cache.cache_dir)
        assert len(names) == 4
        assert all(n.endswith(".bin") for n in names)

    def test_uncreatable_dir_degrades_to_cache_off(self, tmp_path,
                                                   monkeypatch):
        """A mis-set MXNET_COMPILE_CACHE_DIR must never raise on the
        serving path — it disables the cache with a warning (and
        diagnose stays runnable to report it)."""
        blocker = tmp_path / "file"             # a FILE as parent dir
        blocker.write_text("x")
        bad = str(blocker / "cache")
        c = cc.CompileCache(bad)
        assert not c.enabled
        assert c.get("k" * 64) is None          # inert, no error
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", bad)
        d1 = cc.get_default()
        assert not d1.enabled
        assert cc.get_default() is d1           # no rebuild-warn loop

    def test_disabled_cache_is_inert(self, monkeypatch):
        monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
        c = cc.CompileCache(None)
        assert not c.enabled
        assert not c.put("k" * 64, b"data")
        assert c.get("k" * 64) is None
        assert c.stats()["entries"] == 0

    def test_bitflip_is_a_counted_corrupt_miss(self, cache):
        key = "a" * 64
        cache.put(key, b"hello world payload")
        path = cache._path(key)
        raw = bytearray(open(path, "rb").read())
        raw[-1] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(raw))
        assert cache.get(key) is None           # never an error
        assert cache.corrupt == 1
        assert not os.path.exists(path)         # rot is cleared
        assert rm.COMPILE_CACHE.value(event="corrupt") == 1
        # the slot is reusable afterwards
        cache.put(key, b"fresh")
        assert cache.get(key) == b"fresh"

    def test_truncated_and_foreign_blobs_are_corrupt(self, cache):
        for i, raw in enumerate([b"", b"MXAOT1short", b"not-our-format"]):
            key = f"{i:064d}"
            with open(cache._path(key), "wb") as f:
                f.write(raw)
            assert cache.get(key) is None
        assert cache.corrupt == 3

    def test_lru_eviction_oldest_first(self, tmp_path):
        c = cc.CompileCache(str(tmp_path / "c"), max_bytes=3000)
        body = b"x" * 900                       # ~938B per entry on disk
        now = 1_700_000_000
        for i in range(3):
            c.put(f"{i:064d}", body)
            os.utime(c._path(f"{i:064d}"), (now + i, now + i))
        # a hit refreshes entry 0's recency, so entry 1 is now oldest
        os.utime(c._path("0" * 64), (now + 10, now + 10))
        c.put(f"{3:064d}", body)                # overflows the bound
        assert c.evictions >= 1
        assert c.get(f"{1:064d}") is None       # oldest evicted
        assert c.get("0" * 64) == body          # refreshed one survives

    def test_single_oversized_entry_survives(self, tmp_path):
        c = cc.CompileCache(str(tmp_path / "c"), max_bytes=10)
        c.put("f" * 64, b"y" * 1000)
        assert c.get("f" * 64) is not None      # never evicts itself

    def test_ingest_seeds_from_shipped_file(self, cache, tmp_path):
        shipped = tmp_path / "shipped.bin"
        cc.write_payload_file(str(shipped), b"exported-executable")
        key = "e" * 64
        assert cache.ingest(key, str(shipped))
        assert cache.get(key) == b"exported-executable"
        # corrupt shipped file refuses to seed
        with open(shipped, "wb") as f:
            f.write(b"garbage")
        assert not cache.ingest("d" * 64, str(shipped))

    def test_orphan_tmp_swept_at_construction(self, cache):
        """A writer SIGKILLed between mkstemp and rename leaves *.tmp
        litter; the next cache over the dir sweeps stale ones (age-
        gated, so a concurrent writer's fresh tmp survives)."""
        old = os.path.join(cache.cache_dir, "dead1234.tmp")
        fresh = os.path.join(cache.cache_dir, "live5678.tmp")
        for p in (old, fresh):
            with open(p, "wb") as f:
                f.write(b"partial write")
        os.utime(old, (1, 1))                   # ancient
        cc.CompileCache(cache.cache_dir, max_bytes=0)
        assert not os.path.exists(old)
        assert os.path.exists(fresh)

    def test_stats_shape(self, cache):
        cache.put("a" * 64, b"12345")
        st = cache.stats()
        assert st["enabled"] and st["entries"] == 1
        assert st["bytes"] > 5                  # header + body
        assert st["dir"] == cache.cache_dir


class TestDefaultInstance:
    def test_env_driven_rebuild(self, tmp_path, monkeypatch):
        monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
        assert not cc.get_default().enabled
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR",
                           str(tmp_path / "d1"))
        c1 = cc.get_default()
        assert c1.enabled and c1.cache_dir == str(tmp_path / "d1")
        assert cc.get_default() is c1           # stable while env stable
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR",
                           str(tmp_path / "d2"))
        assert cc.get_default() is not c1


class TestExecutableTier:
    def test_fake_executable_roundtrip_no_xla(self, cache, monkeypatch):
        """The executable layer over fake (de)serializers: flags, the
        deserialize histogram, and deserialize-failure => corrupt —
        zero XLA involvement."""
        monkeypatch.setattr(cc, "_serialize_compiled",
                            lambda compiled: pickle.dumps(compiled))
        monkeypatch.setattr(cc, "_deserialize_compiled",
                            lambda body: pickle.loads(body))
        key = "b" * 64
        assert cache.load_executable(key) is None
        assert cache.store_executable(key, {"fake": "executable"})
        prog = cache.load_executable(key)
        assert prog._mx_from_disk_cache is True
        assert rm.COMPILE_CACHE_DESERIALIZE_SECONDS.count() == 1

    def test_undeserializable_blob_degrades_to_miss(self, cache,
                                                    monkeypatch):
        key = "c" * 64
        cache.put(key, b"valid checksum, not an executable")

        def boom(body):
            raise ValueError("stale PJRT blob")
        monkeypatch.setattr(cc, "_deserialize_compiled", boom)
        assert cache.load_executable(key) is None
        # a checksum-valid but unloadable blob is corrupt + MISS, never
        # a hit — the miss counter must equal the compiles that follow
        # (the CI round-trip's zero-recompile assertion rides on it)
        assert cache.corrupt == 1
        assert cache.misses == 1 and cache.hits == 0
        assert not os.path.exists(cache._path(key))

    def test_unserializable_backend_keeps_compile_result(self, cache,
                                                         monkeypatch):
        def boom(compiled):
            raise RuntimeError("backend without serialization")
        monkeypatch.setattr(cc, "_serialize_compiled", boom)
        assert not cache.store_executable("a" * 64, object())
        assert cache.stats()["entries"] == 0

    def test_aot_program_compile_then_disk(self, cache):
        """One real tiny compile: first call compiles + stores, a fresh
        cache instance over the same dir deserializes (source='disk')
        and computes the same answer."""
        import jax

        aval = jax.ShapeDtypeStruct((2, 3), np.float32)
        key = cc.cache_key("prog", 2, ["float32"], topology="t")
        prog1, src1 = cc.aot_program(lambda x: x * 2 + 1, (aval,), key,
                                     cache)
        assert src1 == "compile"
        x = np.ones((2, 3), np.float32)
        np.testing.assert_allclose(np.asarray(prog1(x)), x * 2 + 1)
        fresh = cc.CompileCache(cache.cache_dir, max_bytes=0)
        prog2, src2 = cc.aot_program(
            lambda x: (_ for _ in ()).throw(AssertionError("compiled!")),
            (aval,), key, fresh)
        assert src2 == "disk" and prog2._mx_from_disk_cache
        np.testing.assert_allclose(np.asarray(prog2(x)), x * 2 + 1)


    def test_aot_program_keeps_a_jitted_fns_donation(self, cache):
        """A fn that is already jitted lowers as itself: a second jit
        around it would drop its donate_argnums, and the decode
        programs' KV pools would be copied on every step."""
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x: x + 1, donate_argnums=0)
        aval = jax.ShapeDtypeStruct((4, 4), np.float32)
        key = cc.cache_key("donating", 4, ["float32"], topology="t")
        prog, src = cc.aot_program(fn, (aval,), key, cache)
        assert src == "compile"
        x = jnp.ones((4, 4), np.float32)
        np.testing.assert_allclose(np.asarray(prog(x)), 2.0)
        assert x.is_deleted()


class TestJaxPersistentCache:
    """enable_jax_persistent_cache: where jax's own cache lives is
    decided outside the program (JAX_COMPILATION_CACHE_DIR), else it is
    ONE fixed path inside the checkout."""

    @pytest.fixture()
    def config_updates(self, monkeypatch):
        """Record jax.config.update calls WITHOUT applying them (this
        process must not start persisting the suite's compiles)."""
        import jax
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_dir_is_left_alone(self, monkeypatch, tmp_path,
                                   config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        stats = cc.enable_jax_persistent_cache()
        assert "jax_compilation_cache_dir" not in config_updates
        assert stats == {"hits": 0, "misses": 0}

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch,
                                                   config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        cc.enable_jax_persistent_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert config_updates["jax_compilation_cache_dir"] \
            == cc.JAX_CACHE_DIR == os.path.join(repo, ".jax_cache")
        # every program persists, however small or quick to compile
        assert config_updates[
            "jax_persistent_cache_min_compile_time_secs"] == 0
        assert config_updates[
            "jax_persistent_cache_min_entry_size_bytes"] == 0


def _mlp(seed=7):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


class TestManifestV3:
    def _manifest(self, **extra):
        m = {"dynamic_batch": True,
             "inputs": [{"shape": [None, 8], "dtype": "float32"}],
             "outputs": [{"shape": [None, 4], "dtype": "float32"}]}
        m.update(extra)
        return m

    def test_valid_precompiled_accepted(self):
        deploy.validate_manifest(self._manifest(
            manifest_version=3,
            precompiled=[{"bucket": 2, "file": "m.aot/abc.bin",
                          "key": "abc"}]))

    def test_malformed_precompiled_rejected(self):
        for bad in ([{"bucket": 0, "file": "f", "key": "k"}],
                    [{"bucket": 2, "file": "/abs/path", "key": "k"}],
                    [{"bucket": 2, "file": "../escape", "key": "k"}],
                    [{"bucket": 2, "file": "f"}],
                    ["not-a-dict"],
                    "not-a-list"):
            with pytest.raises(MXNetError):
                deploy.validate_manifest(
                    self._manifest(precompiled=bad))

    def test_unsupported_manifest_version_rejected(self):
        with pytest.raises(MXNetError, match="manifest_version"):
            deploy.validate_manifest(self._manifest(manifest_version=9))
        deploy.validate_manifest(self._manifest(manifest_version=2))

    def test_export_ships_loadable_aot_blobs(self, tmp_path):
        """export_stablehlo(precompile=...) writes manifest-v3 entries
        whose files exist and pass the payload checksum."""
        net = _mlp()
        x = nd.random.uniform(shape=(2, 8))
        art = net.export_stablehlo(x, path=str(tmp_path / "m"),
                                   dynamic_batch=True, precompile=(1, 2))
        with open(str(tmp_path / "m.json")) as f:
            man = json.load(f)
        assert man["manifest_version"] == 3
        assert [e["bucket"] for e in man["precompiled"]] == [1, 2]
        for e in man["precompiled"]:
            path = os.path.join(str(tmp_path), e["file"])
            assert cc.load_payload_file(path) is not None
        # and the serving loader consumes them with zero compiles even
        # with NO cache dir configured
        repo = serving.ModelRepository()
        repo.load_artifact("m", art)
        srv = serving.ModelServer(repo, serving.ServingConfig(
            max_batch_size=2, max_latency_us=1000))
        try:
            srv.prewarm("m")
            got = srv.predict("m", x.asnumpy(), timeout=60)
            np.testing.assert_allclose(got, net(x).asnumpy(),
                                       rtol=1e-5, atol=1e-5)
        finally:
            srv.stop()
        stats = srv.stats()
        assert stats["bucket_misses"] == 0
        assert stats["bucket_disk_hits"] == 2

    def test_corrupt_cache_entry_does_not_shadow_shipped_blob(
            self, tmp_path, monkeypatch):
        """A bit-flipped cache entry must not beat a pristine shipped
        executable into a recompile: ingest verifies before trusting,
        and aot_program falls back to the shipped file."""
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR",
                           str(tmp_path / "cache"))
        net = _mlp()
        x = nd.random.uniform(shape=(1, 8))
        art = net.export_stablehlo(x, path=str(tmp_path / "m"),
                                   dynamic_batch=True, precompile=(1,))
        model = deploy.load_stablehlo(art)
        assert model.aot_program(rows=1)._mx_from_disk_cache
        cache = cc.get_default()
        for name in os.listdir(cache.cache_dir):    # rot the cache copy
            with open(os.path.join(cache.cache_dir, name), "wb") as f:
                f.write(b"bit-flipped")
        prog = model.aot_program(rows=1)            # re-ingests shipped
        assert prog._mx_from_disk_cache, \
            "shipped blob should have served; a compile happened"

    def test_reexport_sweeps_stale_aot_blobs(self, tmp_path):
        """Re-exporting to the same path (new weights => new keys) must
        not accumulate orphaned executables in path.aot/."""
        x = nd.random.uniform(shape=(1, 8))
        deploy.export_stablehlo(_mlp(1), x, path=str(tmp_path / "m"),
                                dynamic_batch=True, precompile=(1,))
        first = set(os.listdir(str(tmp_path / "m.aot")))
        deploy.export_stablehlo(_mlp(2), x, path=str(tmp_path / "m"),
                                dynamic_batch=True, precompile=(1,))
        second = set(os.listdir(str(tmp_path / "m.aot")))
        assert len(second) == 1
        assert not (first & second)         # old key swept, not kept

    def test_static_export_precompile_bucket_rules(self, tmp_path):
        net = _mlp()
        x = nd.random.uniform(shape=(3, 8))
        with pytest.raises(MXNetError, match="static export"):
            deploy.export_stablehlo(net, x, path=str(tmp_path / "s"),
                                    precompile=(1, 2))
        art = deploy.export_stablehlo(net, x, path=str(tmp_path / "s"),
                                      precompile=True)
        with open(str(tmp_path / "s.json")) as f:
            man = json.load(f)
        assert [e["bucket"] for e in man["precompiled"]] == [3]
        assert deploy.load_stablehlo(art).manifest is not None


class TestWarmRestart:
    def test_server_restart_compiles_zero_new_programs(
            self, tmp_path, monkeypatch):
        """The acceptance criterion, in-process: two fresh
        repository+server generations over one cache dir — the second
        deserializes every bucket (miss counter stays 0) and serves
        bit-correct results."""
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR",
                           str(tmp_path / "cache"))
        net = _mlp()
        x = nd.random.uniform(shape=(2, 8))
        art = net.export_stablehlo(x, path=str(tmp_path / "m"),
                                   dynamic_batch=True, version=1)
        want = net(x).asnumpy()
        cfg_kw = dict(max_batch_size=2, max_latency_us=1000)

        def serve_once():
            repo = serving.ModelRepository()
            repo.load_artifact("m", art)
            srv = serving.ModelServer(
                repo, serving.ServingConfig(**cfg_kw))
            try:
                srv.prewarm("m")
                np.testing.assert_allclose(
                    srv.predict("m", x.asnumpy(), timeout=60), want,
                    rtol=1e-5, atol=1e-5)
            finally:
                srv.stop()
            return srv.stats()

        cold = serve_once()
        assert cold["bucket_misses"] == 2       # buckets 1, 2 compiled
        assert cc.get_default().stats()["stores"] == 2
        warm = serve_once()
        assert warm["bucket_misses"] == 0, \
            f"warm restart recompiled: {warm}"
        assert warm["bucket_disk_hits"] == 2
        assert warm["programs"] == 2

    def test_corrupt_cache_entry_falls_back_to_compile(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR",
                           str(tmp_path / "cache"))
        net = _mlp()
        x = nd.random.uniform(shape=(2, 8))
        art = net.export_stablehlo(x, path=str(tmp_path / "m"),
                                   dynamic_batch=True, version=1)
        model = deploy.load_stablehlo(art)
        prog = model.aot_program(rows=2)
        assert not prog._mx_from_disk_cache
        # rot every stored entry on disk
        cache = cc.get_default()
        for name in os.listdir(cache.cache_dir):
            with open(os.path.join(cache.cache_dir, name), "wb") as f:
                f.write(b"rotten")
        prog2 = model.aot_program(rows=2)       # corrupt -> fresh compile
        assert not prog2._mx_from_disk_cache
        out = prog2(x.asnumpy())
        out = out[0] if isinstance(out, tuple) else out
        np.testing.assert_allclose(np.asarray(out), net(x).asnumpy(),
                                   rtol=1e-5, atol=1e-5)
        assert cache.corrupt >= 1
