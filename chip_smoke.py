#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that mxnet_tpu's main path starts
on the chip.

One process, which holds the chip from start to end and starts no child.
It drives the two halves of the main path through the entry points a
user calls, each at the full published width of one model the repo
supports (depth as published too; weights random, from a seed):

- device gate: ``jax.devices()`` must be TPU devices, else exit 2 with
  one line naming what was found (``JAX_PLATFORMS=cpu`` included);
- train leg: BERT-large (``bert_24_1024_16``) masked-LM pretraining
  step, ``parallel.ShardedTrainer`` adamw/bf16, B=32 L=128, 3 + 8 steps
  on one batch;
- flash leg: the same trainer over the Pallas flash-attention path
  (L=512, B=8, 2 steps) plus one ``jax.grad`` of ``flash_attention`` at
  (16, 2048, 64) bf16 against dense attention;
- serve leg: GPT-2-small width ``TransformerDecoderLM`` through
  ``ModelRepository.add_decoder`` -> ``ModelServer.generate`` (paged-KV
  ``DecodeEngine``, prefix cache on): eight streamed requests from four
  threads, three passes; then the paged kernels against their pure-jax
  references at the served shapes, and the paged prefill's logits
  against the model's own dense forward;
- four-chip leg (only where ``len(jax.devices()) >= 4``): the train leg
  on ``make_mesh(dp=4)`` and ``make_mesh(dp=2, tp=2)``.

Any failed check or exception in any leg makes the exit code non-zero;
nothing is retried, skipped or downgraded.  Once the legs have run, the
last stdout line is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
(``"ok": false`` when a leg failed); what the legs measured is on the
``detail:`` line before it.  Without a TPU, or outside a checkout, no such
line is printed.

``--rehearsal`` runs the same control flow at toy sizes on whatever
backend JAX has (the CPU, with the Pallas interpreter) to debug the
script itself: it says REHEARSAL, checks no chip-only property, prints
no rate and no result line.  It never sets ``JAX_PLATFORMS``.
"""
import argparse
import gc
import json
import sys
import threading
import time
import traceback
from types import SimpleNamespace

import numpy as np

# tolerances, stated once (each is printed next to the error it bounds)
FLASH_GRAD_TOL = 3e-2     # bf16 flash grads vs f32 dense, max|err| / max|ref|
PAGED_TOL = 1e-4          # f32 paged kernels vs references, "highest" matmuls
PREFILL_TOL = 5e-2        # paged prefill logits vs dense forward, / max|ref|
MULTICHIP_LOSS_TOL = 5e-2  # first-step loss, n-chip mesh vs one chip (bf16)

FULL = SimpleNamespace(
    bert=dict(model_name="bert_24_1024_16", vocab_size=30522),
    train_batch=32, train_len=128, warmup=3, steps=8,
    flash_len=512, flash_batch=8, flash_steps=2,
    flash_grad_shape=(16, 2048, 64),
    lm=dict(vocab_size=50257, units=768, hidden_size=3072, num_layers=12,
            num_heads=12, max_length=1024, activation="gelu"),
    page_size=16, pool_pages=513, max_batch=8,
    prompt_lens=(5, 40, 130, 300), shared_prefix=64, new_tokens=(16, 32),
    verify_widths=(1, 256))
TOY = SimpleNamespace(
    bert=dict(model_name="bert_12_768_12", vocab_size=1024, units=128,
              hidden_size=512, num_layers=2, num_heads=8),
    train_batch=4, train_len=128, warmup=3, steps=3,
    flash_len=256, flash_batch=2, flash_steps=2,
    flash_grad_shape=(2, 256, 64),
    lm=dict(vocab_size=512, units=64, hidden_size=128, num_layers=2,
            num_heads=2, max_length=256, activation="gelu"),
    page_size=16, pool_pages=65, max_batch=4,
    prompt_lens=(5, 20, 40, 70), shared_prefix=32, new_tokens=(4, 8),
    verify_widths=(1, 8))


class SmokeFailure(Exception):
    pass


def result_line(ok, device):
    """The last stdout line: exactly the keys ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), which is what the driver parses.
    Everything else the run learned goes on the ``detail:`` line."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# ------------------------------------------------------------------ train
def mlm_batch(nd, rng, vocab, B, L):
    """Masked-LM inputs (tokens, token types, valid length, masked
    positions) + labels (mlm ids, nsp class)."""
    n_mask = max(1, int(0.15 * L))
    feats = (nd.array(rng.randint(0, vocab, (B, L)), dtype="int32"),
             nd.zeros((B, L), dtype="int32"),
             nd.array(np.full((B,), L, np.float32)),
             nd.array(rng.randint(0, L, (B, n_mask)), dtype="int32"))
    labels = (nd.array(rng.randint(0, vocab, (B, n_mask)), dtype="int32"),
              nd.array(rng.randint(0, 2, (B,)), dtype="int32"))
    return feats, labels


def pretrain_loss(outputs, mlm_y, nsp_y):
    import jax
    import jax.numpy as jnp
    mlm_scores, nsp_scores = outputs
    mlm_logp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
    nsp_logp = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
    return (-jnp.take_along_axis(mlm_logp, mlm_y[..., None], -1).mean()
            - jnp.take_along_axis(nsp_logp, nsp_y[:, None], -1).mean())


def run_trainer(ctx, mesh, B, L, warmup, steps, **bert_extra):
    """Build BERT + pretrain heads from seed 0, take warmup + steps
    ShardedTrainer steps on ONE batch.  Returns (trainer, batch, losses,
    seconds per timed step).  With warm-up steps the run is long enough
    to ask that the loss fell and that nothing compiled after them; a
    two-step run (the flash leg) only has to stay finite — adamw at
    1e-4 with no learning-rate warm-up overshoots on its first update
    of a 24-layer post-norm BERT, with or without the flash kernel."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd, parallel

    mx.random.seed(0)
    cfg = dict(ctx.size.bert, max_length=max(L, 128), **bert_extra)
    model = models.get_bert_model(dropout=0.0, **cfg)
    model.initialize()
    head = models.BERTForPretrain(model, vocab_size=cfg["vocab_size"])
    head.initialize()
    feats, labels = mlm_batch(nd, np.random.RandomState(0),
                              cfg["vocab_size"], B, L)
    trainer = parallel.ShardedTrainer(
        head, pretrain_loss, mesh, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-4},
        example_inputs=feats, n_labels=2, dtype=jnp.bfloat16)
    batch = feats + labels
    losses = [float(trainer.step(*batch).block_until_ready())
              for _ in range(warmup)]
    programs = trainer._step._cache_size()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(*batch)
        losses.append(loss)
    loss.block_until_ready()
    dt = (time.perf_counter() - t0) / steps
    losses = [float(x) for x in losses]
    check(all(np.isfinite(losses)),
          "losses finite: " + " ".join(f"{x:.3f}" for x in losses))
    if warmup:
        check(losses[-1] < losses[0],
              "loss lower at the end than at the start")
        check(trainer._step._cache_size() == programs,
              f"no step program compiled after warm-up ({programs} in "
              f"cache)")
    if ctx.on_chip:
        check(all(d.platform == "tpu" for a in trainer.params.values()
                  for d in a.devices()),
              "every entry of trainer.params lives on a TPU device")
    return trainer, batch, losses, dt


def step_lowering(trainer, batch):
    shardb = trainer.shard_batch(*[b._data for b in batch])
    return trainer._step.lower(trainer.params, trainer.opt_state,
                               *shardb).as_text()


def leg_train(ctx):
    from mxnet_tpu import parallel
    s = ctx.size
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1, devices=[ctx.device])
    trainer, _batch, losses, dt = run_trainer(
        ctx, mesh, s.train_batch, s.train_len, s.warmup, s.steps)
    out = {"batch": s.train_batch, "seqlen": s.train_len,
           "params": sum(int(np.prod(a.shape))
                         for a in trainer.params.values()),
           "first_loss": losses[0], "last_loss": losses[-1]}
    if ctx.on_chip:
        stats = ctx.device.memory_stats() or {}
        out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        out["step_ms"] = round(1e3 * dt, 2)
        out["samples_per_sec"] = round(s.train_batch / dt, 1)
    ctx.first_loss = losses[0]
    del trainer
    return out


# ------------------------------------------------------------------ flash
def leg_flash(ctx):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    s = ctx.size
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1, devices=[ctx.device])
    # no warm-up: the two steps include the compile, so no step time
    trainer, batch, losses, _dt = run_trainer(
        ctx, mesh, s.flash_batch, s.flash_len, 0, s.flash_steps,
        use_flash=True)
    if ctx.on_chip:
        check("tpu_custom_call" in step_lowering(trainer, batch),
              "the flash step's lowering holds the Mosaic tpu_custom_call")
    out = {"batch": s.flash_batch, "seqlen": s.flash_len,
           "last_loss": losses[-1]}
    del trainer, batch
    gc.collect()

    BH, L, D = s.flash_grad_shape
    rng = np.random.RandomState(1)
    q, k, v, w = (jnp.asarray(rng.randn(BH, L, D), jnp.bfloat16)
                  for _ in range(4))

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v).astype(jnp.float32)
                * w.astype(jnp.float32)).sum()

    def dense_loss(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        p = jax.nn.softmax(
            jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(D), axis=-1)
        return (jnp.einsum("bqk,bkd->bqd", p, v)
                * w.astype(jnp.float32)).sum()

    got = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    errs = []
    for name, g, r in zip("qkv", got, want):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        check(np.isfinite(g).all(), f"flash d{name} finite")
        errs.append(float(np.abs(g - r).max() / np.abs(r).max()))
    check(max(errs) <= FLASH_GRAD_TOL,
          f"flash_attention grads at {(BH, L, D)} bf16 match dense "
          f"attention: max|err|/max|ref| = {max(errs):.2e} "
          f"<= {FLASH_GRAD_TOL}")
    out["grad_rel_err"] = max(errs)
    return out


# ------------------------------------------------------------------ serve
def make_prompts(s):
    rng = np.random.RandomState(2)
    vocab = s.lm["vocab_size"]
    prompts = [rng.randint(1, vocab, (n,)).astype(np.int32)
               for n in s.prompt_lens]
    # the two longest share a prefix, so one of them is served as a
    # prefix-cache hit + paged_verify tail whichever arrives first
    prompts[-1][:s.shared_prefix] = prompts[-2][:s.shared_prefix]
    return prompts


def serve_pass(srv, name, prompts, s):
    """Eight generate() calls from four threads (thread i serves prompt
    i at both lengths), streamed.  Returns the generated-token total."""
    vocab = s.lm["vocab_size"]
    failures, totals = [], []

    def client(prompt):
        try:
            for n_new in s.new_tokens:
                streamed = []
                out = srv.generate(name, prompt, max_new_tokens=n_new,
                                   on_token=streamed.append, timeout=900)
                out = np.asarray(out)
                if streamed != out.tolist():
                    raise SmokeFailure("streamed tokens != returned array")
                if len(out) != n_new or out.min() < 0 or out.max() >= vocab:
                    raise SmokeFailure(f"bad tokens for a {len(prompt)}-"
                                       f"token prompt: {out.tolist()}")
                totals.append(len(out))
        except Exception as e:      # noqa: BLE001 — re-raised by the caller
            failures.append(e)

    threads = [threading.Thread(target=client, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1000)
    if any(t.is_alive() for t in threads):
        raise SmokeFailure("a generate() client did not finish")
    if failures:
        raise failures[0]
    return sum(totals)


def leg_serve(ctx):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving
    from mxnet_tpu.models import TransformerDecoderLM
    s = ctx.size

    mx.random.seed(0)
    lm = TransformerDecoderLM(**s.lm)
    lm.initialize()
    repo = serving.ModelRepository()
    repo.add_decoder("lm", lm)
    adapter = repo.get("lm").decode_model
    srv = serving.ModelServer(repo, serving.ServingConfig(
        decode_page_size=s.page_size, decode_pool_pages=s.pool_pages,
        decode_max_batch=s.max_batch, prefix_cache=True))
    prompts = make_prompts(s)
    out = {}
    try:
        if ctx.on_chip:
            check(adapter.attention_impl == "pallas",
                  "PagedLMAdapter chose attention_impl='pallas'")
        # passes 1 and 2 are the warm-up requests: 1 fills the prefix
        # cache in arrival order, 2 serves every prompt as a cache hit;
        # pass 3 repeats 2's traffic and must compile nothing
        serve_pass(srv, "lm", prompts, s)
        serve_pass(srv, "lm", prompts, s)
        programs = adapter.programs()
        events = dict(ctx.cache_stats)
        t0 = time.perf_counter()
        n_tokens = serve_pass(srv, "lm", prompts, s)
        dt = time.perf_counter() - t0
        stats = srv.decode_stats("lm")
        check(adapter.programs() == programs
              and dict(ctx.cache_stats) == events,
              f"no program compiled after the warm-up requests "
              f"({programs} programs)")
        check(stats["programs"] <= stats["program_bound"],
              f"programs {stats['programs']} <= program_bound "
              f"{stats['program_bound']}")
        check(stats["prefix_hits"] > 0 and stats["running"] == 0
              and stats["waiting"] == 0,
              f"drained; {stats['prefix_hits']} prefix-cache hits")
        check(stats["used_pages"] == stats["prefix_pages"],
              f"after drain used_pages == prefix_pages "
              f"== {stats['used_pages']}")
        engine = next(iter(srv._decoders.values()))
        engine.allocator.check_leaks()
        check(True, "PageAllocator.check_leaks() passes")
        out.update(programs=stats["programs"],
                   program_bound=stats["program_bound"],
                   prefix_hits=stats["prefix_hits"],
                   generated_tokens=stats["generated_tokens"])
        if ctx.on_chip:
            out["pass3_tokens_per_sec"] = round(n_tokens / dt, 1)
            pool = adapter.pool
            check(all(d.platform == "tpu" for a in (pool.k_pages,
                                                    pool.v_pages)
                      for d in a.devices()), "the KV pools live on the TPU")
            for kind, text in decode_lowerings(adapter, s):
                check("tpu_custom_call" in text,
                      f"the {kind} step's lowering holds the Mosaic "
                      f"tpu_custom_call")
        # the paged prefill against the model's own dense forward, on a
        # small input.  All-null block table: the K/V land in the null
        # page, which nothing attends to.  Run after the program counts
        # are taken (this call is outside the engine).
        n = s.prompt_lens[0]
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :n] = prompts[0]
        table = np.zeros((engine.geometry.pages_per_seq,), np.int32)
        paged = np.asarray(adapter.prefill(tokens, np.int32(n), table))
        dense = lm(nd.array(tokens[:, :n], dtype="int32")).asnumpy()[0, -1]
        check(np.isfinite(paged).all(), "paged prefill logits finite")
        err = float(np.abs(paged - dense).max() / np.abs(dense).max())
        check(err <= PREFILL_TOL,
              f"paged prefill logits match the dense forward: "
              f"max|err|/max|ref| = {err:.2e} <= {PREFILL_TOL}")
        out["prefill_rel_err"] = err
    finally:
        srv.stop()
    del srv, repo, adapter, lm
    gc.collect()
    out["paged_kernel_err"] = paged_kernel_parity(ctx)
    return out


def decode_lowerings(adapter, s):
    """StableHLO text of the decode and verify programs at the served
    shapes (lowering only: nothing runs, the pools are not donated)."""
    g = adapter.geometry
    B, P = s.max_batch, g.pages_per_seq
    pool = adapter.pool
    yield "decode", adapter._decode_jit.lower(
        adapter.params, np.zeros((B,), np.int32), np.zeros((B,), np.int32),
        np.zeros((B, P), np.int32), pool.k_pages, pool.v_pages).as_text()
    yield "verify", adapter._verify_jit.lower(
        adapter.params, np.zeros((1, s.verify_widths[-1]), np.int32),
        np.int32(0), np.int32(1), np.zeros((P,), np.int32),
        pool.k_pages, pool.v_pages).as_text()


def paged_kernel_parity(ctx):
    """Both paged kernels against their pure-jax references at the
    serve leg's shapes, float32, matmuls at "highest" precision."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    s = ctx.size
    H, D = s.lm["num_heads"], s.lm["units"] // s.lm["num_heads"]
    B, ps, n_pool = s.max_batch, s.page_size, s.pool_pages
    P = s.lm["max_length"] // ps
    rng = np.random.RandomState(3)
    k_pages, v_pages = (jnp.asarray(rng.randn(n_pool, ps, H, D),
                                    jnp.float32) for _ in range(2))
    tables = jnp.asarray(rng.randint(1, n_pool, (B, P)), jnp.int32)
    # ragged contexts: an inactive slot, one token, page edges, full
    lens = np.resize([0, 1, ps, ps + 1, 5 * ps - 1, P * ps // 2,
                      P * ps - 1, P * ps], B).astype(np.int32)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        got = jax.jit(pk.ragged_paged_attention)(q, k_pages, v_pages,
                                                 tables, lens)
        want = pk.ragged_paged_attention_reference(q, k_pages, v_pages,
                                                   tables, lens)
        worst = max(worst, float(jnp.abs(got - want).max()))
        for W in s.verify_widths:
            q = jnp.asarray(rng.randn(B, W, H, D), jnp.float32)
            starts = np.minimum(lens, P * ps - W).astype(np.int32)
            valid = np.resize([0, 1, W, max(1, W // 2)], B).astype(np.int32)
            got = jax.jit(pk.ragged_paged_verify)(
                q, k_pages, v_pages, tables, starts, valid)
            want = pk.ragged_paged_verify_reference(
                q, k_pages, v_pages, tables, starts, valid)
            worst = max(worst, float(jnp.abs(got - want).max()))
    check(np.isfinite(worst) and worst <= PAGED_TOL,
          f"ragged_paged_attention / ragged_paged_verify (W="
          f"{s.verify_widths}) match their references at H={H} D={D} "
          f"page={ps}: max|err| = {worst:.2e} <= {PAGED_TOL}")
    return worst


# -------------------------------------------------------------- four chips
def leg_four_chip(ctx):
    from mxnet_tpu import parallel
    s = ctx.size
    out = {}
    for dp, tp in ((4, 1), (2, 2)):
        mesh = parallel.make_mesh(dp=dp, tp=tp, devices=ctx.devices[:4])
        trainer, batch, losses, dt = run_trainer(
            ctx, mesh, s.train_batch, s.train_len, s.warmup, s.steps)
        check(abs(losses[0] - ctx.first_loss)
              <= MULTICHIP_LOSS_TOL * abs(ctx.first_loss),
              f"dp={dp} tp={tp} first-step loss {losses[0]:.4f} matches "
              f"the one-chip leg's {ctx.first_loss:.4f}")
        sharded = trainer.shard_batch(batch[0]._data)[0]
        check({sh.data.shape[0] for sh in sharded.addressable_shards}
              == {s.train_batch // dp},
              f"the batch is split over dp: {s.train_batch // dp} rows "
              f"per shard")
        if tp > 1:
            name = next(n for n, sh in trainer.param_shardings.items()
                        if "tp" in sh.spec)
            shards = trainer.params[name].addressable_shards
            check(len({sh.device for sh in shards}) == 4
                  and len({str(sh.index) for sh in shards}) == tp,
                  f"{name} is split {tp} ways over tp, its shards on "
                  f"distinct devices")
        out[f"dp{dp}_tp{tp}"] = {"first_loss": losses[0]}
        if ctx.on_chip:
            out[f"dp{dp}_tp{tp}"]["step_ms"] = round(1e3 * dt, 2)
        del trainer, batch
        gc.collect()
    return out


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on whatever backend JAX has; debugs "
                         "this script, proves nothing about the chip")
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    import jax
    import jaxlib
    from importlib import metadata
    try:
        devices = jax.devices()
    except RuntimeError as e:       # a platform was named and is absent
        print(f"chip_smoke: no TPU: JAX found no device ({e})", flush=True)
        return 2
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    print(f"device: {json.dumps(device)}  versions: "
          f"{json.dumps(versions)}", flush=True)
    on_chip = all(d.platform == "tpu" for d in devices)
    if not on_chip and not args.rehearsal:
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind!r} x {len(devices)}); this check runs on "
              f"the chip only", flush=True)
        return 2
    if args.rehearsal:
        print("REHEARSAL: toy sizes; no chip-only check, no rate, no "
              "result", flush=True)

    try:
        from mxnet_tpu import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import mxnet_tpu ({e}); run from the "
              f"root of a checkout", flush=True)
        return 2
    ctx = SimpleNamespace(
        on_chip=on_chip and not args.rehearsal,
        size=TOY if args.rehearsal else FULL,
        device=dev, devices=devices, first_loss=None,
        cache_stats=compile_cache.enable_jax_persistent_cache())

    legs = [("train", leg_train), ("flash", leg_flash),
            ("serve", leg_serve)]
    if len(devices) >= 4:
        legs.append(("four_chip", leg_four_chip))
    results, failed = {}, []
    for name, leg in legs:
        print(f"[{name}] start at {time.monotonic() - t_start:.0f}s",
              flush=True)
        t0 = time.monotonic()
        try:
            results[name] = leg(ctx)
        except Exception:           # noqa: BLE001 — reported, run fails
            traceback.print_exc()
            sys.stderr.flush()
            failed.append(name)
            print(f"[{name}] FAILED after {time.monotonic() - t0:.0f}s",
                  flush=True)
        else:
            results[name]["seconds"] = round(time.monotonic() - t0, 1)
            print(f"[{name}] passed: {json.dumps(results[name])}",
                  flush=True)
        gc.collect()

    wall = round(time.monotonic() - t_start, 1)
    print(f"jax compile cache ({jax.config.jax_compilation_cache_dir}): "
          f"{json.dumps(ctx.cache_stats)}; wall {wall}s", flush=True)
    if args.rehearsal:
        verdict = "FAILED legs: " + ", ".join(failed) if failed else "passed"
        print(f"REHEARSAL {verdict} (no result: this was not the chip)",
              flush=True)
        return 1 if failed else 0
    print("detail: " + json.dumps(
        {"versions": versions, "legs": results, "failed": failed,
         "wall_seconds": wall, "jax_cache": dict(ctx.cache_stats)}),
        flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {', '.join(failed)}", flush=True)
    sys.stderr.flush()
    print(result_line(not failed, device), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
