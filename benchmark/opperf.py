"""Per-operator performance harness.

Reference surface: ``benchmark/opperf/opperf.py`` — time individual
operators over representative shapes to localize regressions.  Timing
rule on TPU: async dispatch means wall-time must bracket a
``jax.device_get`` sync (block_until_ready is a no-op over some remote
backends), and the first call is excluded as compile time.

CLI:
  python benchmark/opperf.py                 # default op set
  python benchmark/opperf.py --ops dot,relu  --runs 50
  python benchmark/opperf.py --categories nn,reduce

One JSON line per op:
  {"op": "dot", "shape": "...", "avg_ms": .., "p50_ms": .., "compile_ms": ..}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _shapes(large):
    b = 4 if not large else 32
    return {
        "elemwise": [(b, 1024, 1024)],
        "broadcast": [((b, 1024, 1024), (1, 1024, 1))],
        "reduce": [(b, 1024, 1024)],
        "gemm": [((1024, 1024), (1024, 1024))],
        "conv": [(b, 64, 56, 56)],
        "nn": [(b, 1024)],
        "optimizer": [(1024, 1024)],
    }


def _op_specs(large=False):
    """op name -> (category, build_args_fn) where build_args_fn(nd, rng)
    returns (args, kwargs)."""
    S = _shapes(large)

    def t(shape):
        def mk(nd, rng):
            return ([nd.array(rng.rand(*shape).astype(np.float32))], {})
        return mk

    def t2(shapes):
        def mk(nd, rng):
            return ([nd.array(rng.rand(*s).astype(np.float32))
                     for s in shapes], {})
        return mk

    e = S["elemwise"][0]
    bl, br = S["broadcast"][0]
    g = S["gemm"][0]
    c = S["conv"][0]
    n = S["nn"][0]
    o = S["optimizer"][0]
    specs = {
        # elemwise / broadcast (VPU + HBM bandwidth bound)
        "relu": ("elemwise", t(e)),
        "sigmoid": ("elemwise", t(e)),
        "exp": ("elemwise", t(e)),
        "sqrt": ("elemwise", t(e)),
        "elemwise_add": ("elemwise", t2([e, e])),
        "elemwise_mul": ("elemwise", t2([e, e])),
        "broadcast_add": ("broadcast", t2([bl, br])),
        "broadcast_mul": ("broadcast", t2([bl, br])),
        # reductions
        "sum": ("reduce", t(S["reduce"][0])),
        "mean": ("reduce", t(S["reduce"][0])),
        "max": ("reduce", t(S["reduce"][0])),
        "argmax": ("reduce", lambda nd, rng: (
            [nd.array(rng.rand(*S["reduce"][0]).astype(np.float32))],
            {"axis": -1})),
        # MXU
        "dot": ("gemm", t2([g[0], g[1]])),
        "batch_dot": ("gemm", lambda nd, rng: (
            [nd.array(rng.rand(8, 512, 512).astype(np.float32)),
             nd.array(rng.rand(8, 512, 512).astype(np.float32))], {})),
        "FullyConnected": ("nn", lambda nd, rng: (
            [nd.array(rng.rand(*n).astype(np.float32)),
             nd.array(rng.rand(4096, n[1]).astype(np.float32)),
             nd.array(rng.rand(4096).astype(np.float32))],
            {"num_hidden": 4096})),
        "Convolution": ("conv", lambda nd, rng: (
            [nd.array(rng.rand(*c).astype(np.float32)),
             nd.array(rng.rand(128, c[1], 3, 3).astype(np.float32)),
             nd.array(rng.rand(128).astype(np.float32))],
            {"kernel": (3, 3), "pad": (1, 1), "num_filter": 128})),
        "Pooling": ("conv", lambda nd, rng: (
            [nd.array(rng.rand(*c).astype(np.float32))],
            {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"})),
        "softmax": ("nn", lambda nd, rng: (
            [nd.array(rng.rand(*n).astype(np.float32))], {"axis": -1})),
        "LayerNorm": ("nn", lambda nd, rng: (
            [nd.array(rng.rand(*n).astype(np.float32)),
             nd.array(np.ones(n[1], np.float32)),
             nd.array(np.zeros(n[1], np.float32))], {})),
        # optimizer updates
        "sgd_mom_update": ("optimizer", lambda nd, rng: (
            [nd.array(rng.rand(*o).astype(np.float32)) for _ in range(3)],
            {"lr": 0.1})),
        "adam_update": ("optimizer", lambda nd, rng: (
            [nd.array(rng.rand(*o).astype(np.float32)) for _ in range(4)],
            {"lr": 0.001})),
        # int8 MXU path
        "quantized_fully_connected": ("nn", lambda nd, rng: (
            lambda q=nd.quantize_v2(
                nd.array(rng.rand(*n).astype(np.float32))),
                w=nd.quantize_v2(
                    nd.array(rng.rand(4096, n[1]).astype(np.float32))):
            ([q[0], w[0], None, q[1], q[2], w[1], w[2], None, None],
             {"num_hidden": 4096, "no_bias": True}))()),
        # attention (interleaved layout: (L, B, H*3*D))
        "_contrib_interleaved_matmul_selfatt_qk": ("attention",
            lambda nd, rng: (
                [nd.array(rng.rand(128, 8, 16 * 3 * 64)
                          .astype(np.float32))], {"heads": 16})),
        "flash_selfatt_nomask": ("attention", lambda nd, rng: (
            [nd.array(rng.rand(512, 4, 16 * 3 * 64).astype(np.float32))],
            {"heads": 16})),
        # detection
        "MultiBoxPrior": ("detection", lambda nd, rng: (
            [nd.zeros((4, 64, 32, 32))],
            {"sizes": (0.3, 0.5), "ratios": (1.0, 2.0, 0.5)})),
        "MultiBoxDetection": ("detection", lambda nd, rng: (
            [nd.array(rng.rand(4, 3, 4096).astype(np.float32)),
             nd.array(rng.randn(4, 4096 * 4).astype(np.float32) * 0.1),
             nd.array(rng.rand(1, 4096, 4).astype(np.float32))], {})),
        # MoE (top-1 dropless, every expert held; no biases since PR 33)
        "moe_ffn": ("moe", lambda nd, rng: (
            [nd.array(rng.rand(8, 128, 512).astype(np.float32)),
             nd.array(rng.randn(512, 8).astype(np.float32)),
             nd.array(rng.randn(8, 512, 1024).astype(np.float32) * 0.05),
             nd.array(rng.randn(8, 1024, 512).astype(np.float32) * 0.05)],
            {})),
        # the router told to score by sigmoid, choose by score + bias,
        # renormalise and scale (Nemotron-3's: 6 of 128)
        "moe_topk_route": ("moe", lambda nd, rng: (
            [nd.array(rng.rand(8 * 128, 512).astype(np.float32)),
             nd.array(rng.randn(512, 128).astype(np.float32)),
             nd.array(rng.randn(128).astype(np.float32) * 0.01)],
            {"experts_per_token": 6, "scoring": "sigmoid", "scale": 2.5})),
        # state-space (Mamba-2: 16 heads of 64 over 2 groups, state 128)
        "ssm_conv": ("ssm", lambda nd, rng: (
            [nd.array(rng.randn(2, 1024, 1536).astype(np.float32)),
             nd.array(rng.rand(1536, 4).astype(np.float32) - 0.5),
             nd.array(rng.rand(1536).astype(np.float32) - 0.5)], {})),
        # the gated short convolution (LFM2: [B | C | u] of 512, 3 taps)
        "gated_short_conv": ("ssm", lambda nd, rng: (
            [nd.array(rng.randn(2, 1024, 1536).astype(np.float32)),
             nd.array(rng.rand(512, 3).astype(np.float32) - 0.5)], {})),
        "ssm_scan": ("ssm", lambda nd, rng: (
            [nd.array(rng.randn(2, 1024, 16, 64).astype(np.float32)),
             nd.array(rng.randn(2, 1024, 16).astype(np.float32)),
             nd.array(np.log(rng.uniform(1, 16, 16)).astype(np.float32)),
             nd.array(rng.randn(2, 1024, 2, 128).astype(np.float32)),
             nd.array(rng.randn(2, 1024, 2, 128).astype(np.float32)),
             nd.ones((16,)), nd.zeros((16,))], {"chunk": 128})),
        "ssm_gate_norm": ("ssm", lambda nd, rng: (
            [nd.array(rng.randn(2, 1024, 1024).astype(np.float32)),
             nd.array(rng.randn(2, 1024, 1024).astype(np.float32)),
             nd.ones((1024,))], {"groups": 2})),
    }
    return specs


def time_op(name, build, warmup=2, runs=10):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rng = np.random.RandomState(0)
    args, kwargs = build(nd, rng)
    fn = getattr(nd, name)

    import jax.numpy as jnp

    def once(reps=1):
        # reps async dispatches then ONE 1-element sync: amortizes the
        # dispatch/sync round-trip latency and avoids timing the
        # full-output host transfer
        for _ in range(reps):
            out = fn(*args, **kwargs)
            if isinstance(out, (list, tuple)):
                out = out[0]
        jax.device_get(jnp.ravel(out._data)[:1])

    t0 = time.perf_counter()
    once()
    compile_ms = (time.perf_counter() - t0) * 1e3
    reps = 10
    for _ in range(warmup):
        once(reps)
    samples = []
    for _ in range(runs):
        t0 = time.perf_counter()
        once(reps)
        samples.append((time.perf_counter() - t0) * 1e3 / reps)
    shape = "x".join(str(s) for s in args[0].shape) if args else ""
    return {"op": name, "shape": shape,
            "avg_ms": round(float(np.mean(samples)), 4),
            "p50_ms": round(float(np.median(samples)), 4),
            "min_ms": round(float(np.min(samples)), 4),
            "compile_ms": round(compile_ms, 2)}


def time_beam_decode(large=False, warmup=1, runs=5):
    """Decode throughput of the compiled batched beam search
    (models/decoding.py) — tokens/sec on a transformer (Sockeye-facing
    surface: decode is a perf path, not just a correctness path)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, models

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    if large:
        B, Ls, Lt, K = 16, 32, 32, 5
        m = models.transformer_base(src_vocab_size=32000)
    else:
        B, Ls, Lt, K = 8, 12, 12, 4
        m = models.transformer_base(src_vocab_size=128, units=64,
                                    hidden_size=128, num_layers=2,
                                    num_heads=4, max_length=64)
    m.initialize(mx.init.Xavier())
    m.hybridize()          # eager per-op dispatch would dominate decode
    src = nd.array(rng.randint(4, 100, (B, Ls)).astype(np.int32),
                   dtype="int32")
    sv = nd.array(np.full((B,), Ls, np.float32))

    def once():
        out = m.beam_search(src, sv, beam_size=K, max_decode_len=Lt)
        jax.device_get(out._data[:1, :1])

    t0 = time.perf_counter()
    once()
    compile_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(warmup):
        once()
    samples = []
    for _ in range(runs):
        t0 = time.perf_counter()
        once()
        samples.append(time.perf_counter() - t0)
    dt = float(np.median(samples))
    return {"op": "beam_search", "shape": f"B{B}xK{K}xL{Lt}",
            "avg_ms": round(float(np.mean(samples)) * 1e3, 2),
            "p50_ms": round(dt * 1e3, 2),
            "tokens_per_sec": round(B * Lt / dt, 1),
            "compile_ms": round(compile_ms, 2)}


def time_input_pipeline(large=False, threads=None):
    """ImageRecordIter end-to-end throughput (RecordIO read → JPEG decode
    → augment → batch at 224²) vs the resnet-50 training step's
    consumption rate (SURVEY §7.3 M4 'measure early'; reference:
    src/io/iter_image_recordio_2.cc).  The pipeline must sustain
    >= 1.2x the step rate or training is input-bound."""
    import shutil
    import tempfile

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, recordio, gluon, parallel
    import mxnet_tpu.io as mxio

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    n_rec = 768 if large else 64
    B = 64 if large else 16
    # large: raw-photo-sized sources (the DCT-reduced decode fast path
    # engages at >= 2x the resize target); small: pre-resized-style shard
    src_hw = (540, 720) if large else (360, 480)
    tmp = tempfile.mkdtemp(prefix="opperf_rec_")
    try:
        rec_path = os.path.join(tmp, "synth.rec")
        w = recordio.MXIndexedRecordIO(rec_path + ".idx", rec_path, "w")
        for i in range(n_rec):
            img = rng.randint(0, 255, src_hw + (3,), dtype=np.uint8)
            w.write_idx(i, recordio.pack_img(
                recordio.IRHeader(0, float(i % 10), i, 0), img,
                quality=90))
        w.close()

        threads = threads or max(1, (os.cpu_count() or 4) - 1)
        it = mxio.ImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, 224, 224), batch_size=B,
            shuffle=True, rand_crop=True, rand_mirror=True, resize=256,
            preprocess_threads=threads, prefetch_buffer=4)

        def epoch():
            n = 0
            it.reset()
            while True:
                try:
                    batch = it.next()
                except StopIteration:
                    break
                n += batch.data[0].shape[0]
            return n

        epoch()                                   # warm: file cache, pool
        t0 = time.perf_counter()
        n = epoch() + epoch()
        imgs_per_sec = n / (time.perf_counter() - t0)

        # consumption side: resnet-50 on the accelerator; a tiny
        # resnet-18 proxy when only the CPU is available (a large CPU
        # step would take minutes and the comparison is not meaningful)
        on_tpu = any(d.platform != "cpu" for d in jax.devices())
        model_name = "resnet50_v1" if (large and on_tpu) else "resnet18_v1"
        Bs = B if (large and on_tpu) else 2
        net = gluon.model_zoo.vision.get_model(model_name, classes=10)
        net.initialize(mx.init.Xavier())
        import jax.numpy as jnp

        def loss_fn(outputs, y):
            logits = outputs[0] if isinstance(outputs, (list, tuple)) \
                else outputs
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            return -jnp.take_along_axis(
                logp, y[:, None].astype(jnp.int32), axis=-1).mean()

        x_np = rng.randn(Bs, 3, 224, 224).astype(np.float32)
        # functionalize's eager pass runs against fp32 params, so the
        # example input stays fp32; the stepped input is bf16 to match
        # the trainer's bf16-cast params on TPU
        x32 = nd.array(x_np)
        x = nd.array(x_np, dtype="bfloat16") if on_tpu else x32
        y = nd.array(rng.randint(0, 10, (Bs,)).astype(np.int32),
                     dtype="int32")
        mesh = parallel.make_mesh(dp=1, tp=1, sp=1,
                                  devices=jax.devices()[:1])
        tr = parallel.ShardedTrainer(
            net, loss_fn, mesh, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01},
            example_inputs=(x32,), n_labels=1,
            dtype=jnp.bfloat16 if on_tpu else None)
        for _ in range(3):
            jax.device_get(tr.step(x, y))
        steps = 8 if large else 3
        t0 = time.perf_counter()
        for _ in range(steps):
            out = tr.step(x, y)
        jax.device_get(out)
        step_sps = Bs * steps / (time.perf_counter() - t0)
        return {"op": "input_pipeline", "imgs_per_sec":
                round(imgs_per_sec, 1), "threads": threads,
                "batch": B, "records": n_rec, "src_hw": list(src_hw),
                "step_model": model_name,
                "step_samples_per_sec": round(step_sps, 1),
                "pipeline_vs_step": round(imgs_per_sec / step_sps, 2)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_performance_test(ops=None, categories=None, warmup=2, runs=10,
                         large=False):
    """Programmatic entry (reference: opperf.run_performance_test)."""
    specs = _op_specs(large)
    results = []
    for name, (cat, build) in specs.items():
        if ops and name not in ops:
            continue
        if categories and cat not in categories:
            continue
        try:
            results.append(time_op(name, build, warmup, runs))
        except Exception as e:                        # noqa: BLE001
            results.append({"op": name, "error": str(e)[:120]})
    if (not ops or "beam_search" in ops) and \
            (not categories or "decode" in categories):
        try:
            results.append(time_beam_decode(large))
        except Exception as e:                        # noqa: BLE001
            results.append({"op": "beam_search", "error": str(e)[:120]})
    if (not ops or "input_pipeline" in ops) and \
            (not categories or "pipeline" in categories):
        try:
            results.append(time_input_pipeline(large))
        except Exception as e:                        # noqa: BLE001
            results.append({"op": "input_pipeline",
                            "error": str(e)[:120]})
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names (default: all)")
    ap.add_argument("--categories", default=None,
                    help="comma-separated: elemwise,broadcast,reduce,"
                         "gemm,conv,nn,optimizer,attention,detection,"
                         "moe,decode,pipeline")
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--large", action="store_true",
                    help="TPU-scale shapes (default: CI-friendly)")
    args = ap.parse_args()
    ops = set(args.ops.split(",")) if args.ops else None
    cats = set(args.categories.split(",")) if args.categories else None
    for row in run_performance_test(ops, cats, args.warmup, args.runs,
                                    args.large):
        print(json.dumps(row))


if __name__ == "__main__":
    main()
