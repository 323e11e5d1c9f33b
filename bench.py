"""Headline benchmark: BERT-large pretraining-style training step.

Prints a merged JSON line {"metric", "value", "unit", "vs_baseline", ...}
after every completed phase; the LAST stdout line is the authoritative
(most complete) result.
Metric is model FLOPs utilization (MFU) of a BERT-large (bert_24_1024_16)
masked-LM training step at seq 128 on the available accelerator —
the BASELINE.json north-star metric (target >= 35% MFU).  Extra keys
document the user-facing Gluon hybridize()+Trainer path (now fused
backward+optimizer), the FusedTrainStep path, and the seq-512 Pallas
flash-attention path.

One process for each chip: every phase runs in its OWN subprocess, one
at a time, and that child is the only process that imports JAX — a
parent that had touched JAX would hold the chip and its children would
fail or hang.  A fresh process per phase also gives each phase a clean
HBM arena.  Every result line names the device it ran on (`platform`,
`device_kind`, `device_count`).

The merged JSON is re-printed after EVERY phase, so the last stdout
line is always the best-so-far result even if the driver kills the run
mid-phase, and a total-run deadline (BENCH_TOTAL_BUDGET) skips
remaining phases instead of dying inside a retry ladder.  A phase that
fails every attempt makes the run exit non-zero after printing what it
has.

Phases (each in its own subprocess): headline BERT-large MFU, resnet
(ResNet-50 MFU + imgs/sec — BASELINE's second primary metric), hybrid
(Gluon ergonomic path), samebatch (sharded step re-run at the hybrid
batch when the two diverged, so hybrid_vs_sharded is like-for-like),
fused, flash seq-512, flash seq-2048, nmt (config-4 transformer-big
training tokens/sec + MFU over bucketed lengths), pipeline (input
pipeline imgs/sec vs step consumption).

Env knobs: BENCH_BATCH (default 32 on TPU / 4 on CPU), BENCH_SEQLEN (128),
BENCH_STEPS (8), BENCH_PEAK_TFLOPS (per-chip peak for MFU; default
perf_account.detect_peak_tflops: the device_kind table, an unknown
accelerator raises, and a CPU has no peak so its run prints no MFU),
BENCH_RESNET / BENCH_HYBRID / BENCH_SAMEBATCH / BENCH_FUSED /
BENCH_FLASH / BENCH_FLASH2048 / BENCH_NMT / BENCH_PIPELINE ("0"
disables the phase), BENCH_RESNET_BATCH (512), BENCH_NMT_BATCH (32),
BENCH_FLASH_BATCH (default 8), BENCH_PHASE_TIMEOUT (seconds, 600),
BENCH_TOTAL_BUDGET (seconds, 3000 — hard deadline for the whole run).
jax's persistent compilation cache is shared by every phase subprocess
(compile_cache.enable_jax_persistent_cache: where
JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache).  Each
phase reports compile_cache_hits/misses from jax's cache events; the
orchestrator sums them across phases into the merged JSON.
"""
import json
import os
import sys
import time

import numpy as np

PHASES = ("headline", "resnet", "hybrid", "samebatch", "nmt", "flash",
          "flash2048", "pipeline", "fused")
# budget-priority order: the r5 metrics (resnet, samebatch ratio, nmt,
# pipeline) come before the r4-repeat phases so a budget exhaustion
# drops the least-new information (fused is the hybrid path's explicit
# twin and goes last)


def _mlm_batch(nd, rng, vocab_size, B, L):
    """Masked-LM inputs: (inputs, token_types, valid_length, masked_pos)
    + labels (mlm_y, nsp_y)."""
    n_mask = max(1, int(0.15 * L))
    inputs = nd.array(rng.randint(0, vocab_size, (B, L)), dtype="int32")
    token_types = nd.zeros((B, L), dtype="int32")
    valid_length = nd.array(np.full((B,), L, np.float32))
    masked_pos = nd.array(rng.randint(0, L, (B, n_mask)), dtype="int32")
    mlm_y = nd.array(rng.randint(0, vocab_size, (B, n_mask))
                     .astype(np.int32), dtype="int32")
    nsp_y = nd.array(rng.randint(0, 2, (B,)).astype(np.int32),
                     dtype="int32")
    return (inputs, token_types, valid_length, masked_pos), (mlm_y, nsp_y)


def _time_steps(run_step, steps):
    """Mean step time.  run_step() returns a jax array; the timed
    region ends in block_until_ready (dispatch is asynchronous)."""
    for _ in range(3):                 # first calls compile / re-donate
        run_step().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = run_step()
    out.block_until_ready()
    return (time.perf_counter() - t0) / steps


def _step_flops(trainer, batch):
    """XLA cost-analysis FLOPs of the compiled step — delegates to
    ``mxnet_tpu.perf_account.step_flops`` (promoted; the conv phases
    need the compiler's count because 6NBL undercounts convs badly).
    Returns None when the backend exposes no cost analysis (callers
    fall back to an analytic estimate)."""
    from mxnet_tpu import perf_account
    return perf_account.step_flops(trainer, batch)


def _attribution(env, trainer, batch, flops, steps=2):
    """Per-phase step breakdown for the BENCH JSON: run a few EXTRA
    attributed steps after the timed loop with tracing toggled on
    (attribution syncs every step, which would perturb the headline
    numbers if it ran inside the timed loop).  FLOPs/peak are seeded so
    no extra program is compiled for the MFU."""
    from mxnet_tpu import tracing
    trainer.perf.peak_tflops = env.peak_tflops
    trainer.perf.note_flops(flops)
    trainer._flops_noted = True
    tracing.enable(sample=1.0)
    try:
        for _ in range(steps):
            env.jax.device_get(trainer.step(*batch))
    finally:
        tracing.disable()
    return trainer.perf.summary()


class _Env:
    """Shared per-phase setup (model config, loss, mesh)."""

    def __init__(self):
        # jax is imported HERE, in the phase child, and nowhere in the
        # orchestrating parent (module docstring: one process per chip)
        import jax
        # persistent compilation cache BEFORE any jit compiles: every
        # phase subprocess (and every bench round on the same machine)
        # reuses the same dir, so only the first-ever visit of a
        # program pays the compile
        from mxnet_tpu import compile_cache
        self.cache_stats = compile_cache.enable_jax_persistent_cache()
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import nd, models, parallel

        self.jax, self.jnp = jax, jnp
        self.mx, self.nd = mx, nd
        self.models, self.parallel = models, parallel
        mx.random.seed(0)
        self.rng = np.random.RandomState(0)

        # the one device every phase runs on, named in every result line
        self.device = jax.devices()[0]
        self.on_tpu = on_tpu = self.device.platform != "cpu"
        self.B = int(os.environ.get("BENCH_BATCH", 32 if on_tpu else 4))
        self.L = int(os.environ.get("BENCH_SEQLEN", 128))
        self.steps = int(os.environ.get("BENCH_STEPS", 8))
        # per-chip bf16 peak for MFU: BENCH_PEAK_TFLOPS wins, else the
        # framework's detection (MXNET_PEAK_TFLOPS or the device_kind
        # table; an unknown accelerator raises).  None on a CPU, which
        # has no peak: a CPU run reports rates and counts, never an MFU
        from mxnet_tpu import perf_account
        peak = os.environ.get("BENCH_PEAK_TFLOPS")
        self.peak_tflops = float(peak) if peak else \
            perf_account.detect_peak_tflops([self.device])

        if on_tpu:
            self.cfg = dict(model_name="bert_24_1024_16",
                            vocab_size=30522, max_length=max(self.L, 128))
        else:
            # CI/CPU fallback: tiny config so the harness runs end-to-end
            self.cfg = dict(model_name="bert_12_768_12", vocab_size=1024,
                            units=128, hidden_size=512, num_layers=2,
                            num_heads=8, max_length=max(self.L, 128))
        self.mesh = parallel.make_mesh(dp=1, tp=1, sp=1,
                                       devices=[self.device])

    def device_info(self):
        return {"platform": self.device.platform,
                "device_kind": self.device.device_kind,
                "device_count": len(self.jax.devices())}

    def mfu(self, flops_per_step, dt):
        """Achieved model FLOP/s over the chip's peak, or None where the
        device has no peak on record (a CPU)."""
        if self.peak_tflops is None:
            return None
        return round(flops_per_step / dt / (self.peak_tflops * 1e12), 4)

    def build_pretrain(self, **extra):
        model = self.models.get_bert_model(dropout=0.0,
                                           **dict(self.cfg, **extra))
        model.initialize()
        head = self.models.BERTForPretrain(
            model, vocab_size=self.cfg["vocab_size"])
        head.initialize()
        return model, head

    def loss_fn(self, outputs, mlm_y, nsp_y):
        jax, jnp = self.jax, self.jnp
        mlm_scores, nsp_scores = outputs
        mlm_logp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
        mlm_loss = -jnp.take_along_axis(
            mlm_logp, mlm_y[..., None], axis=-1).mean()
        nsp_logp = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
        nsp_loss = -jnp.take_along_axis(
            nsp_logp, nsp_y[:, None], axis=-1).mean()
        return mlm_loss + nsp_loss

    def n_params_of(self, trainer):
        return sum(int(np.prod(a.shape))
                   for a in trainer.params.values())

    def sharded_phase(self, head, B, L):
        """ShardedTrainer MFU for `head` at (B, L)."""
        jax, jnp = self.jax, self.jnp
        feats, labels = _mlm_batch(self.nd, self.rng,
                                   self.cfg["vocab_size"], B, L)
        trainer = self.parallel.ShardedTrainer(
            head, self.loss_fn, self.mesh, optimizer="adamw",
            optimizer_params={"learning_rate": 1e-4},
            example_inputs=feats, n_labels=2,
            dtype=jnp.bfloat16 if self.on_tpu else None)
        batch = feats + labels
        self._last_batch = batch      # phases reuse it for attribution
        dt = _time_steps(lambda: trainer.step(*batch), self.steps)
        n_params = self.n_params_of(trainer)
        loss_val = float(jax.device_get(trainer.step(*batch)))
        # 6NBL: parameter FLOPs of one fwd+bwd step
        return (self.mfu(6.0 * n_params * B * L, dt), B / dt,
                loss_val, n_params, trainer)


# --------------------------------------------------------------- phases
def phase_headline(env):
    _model, head = env.build_pretrain()
    mfu, sps, loss_val, n_params, trainer = env.sharded_phase(
        head, env.B, env.L)
    return {
        "metric": "bert_large_pretrain_mfu" if env.on_tpu
                  else "bert_tiny_pretrain_mfu_cpu",
        "value": mfu,
        "unit": "mfu_fraction",
        "samples_per_sec": round(sps, 2),
        "batch": env.B, "seqlen": env.L, "params": n_params,
        "loss": loss_val,
        # 6NBL is exact enough for the transformer; avoids an AOT
        # cost-analysis compile just for the breakdown's MFU
        "attribution": _attribution(
            env, trainer, env._last_batch,
            flops=6.0 * n_params * env.B * env.L),
    }


def phase_resnet(env):
    """BASELINE's second named primary metric: ResNet-50 MFU (config 2,
    conv/BN roofline).  bf16 ShardedTrainer step on synthetic NCHW
    batches — the input pipeline is measured separately in the
    `pipeline` phase, so this isolates compute.  MFU uses XLA's own
    FLOP count of the compiled fwd+bwd+SGD program: the 6NBL
    transformer rule badly undercounts convs (a 25.6M-param resnet50
    does ~8.2 GFLOPs/img forward, 60x what 2N would say)."""
    from mxnet_tpu.gluon.model_zoo import vision
    jax, jnp = env.jax, env.jnp
    B = int(os.environ.get("BENCH_RESNET_BATCH", 512 if env.on_tpu else 2))
    S = 224 if env.on_tpu else 32
    classes = 1000 if env.on_tpu else 10
    net = vision.resnet50_v1(classes=classes)
    net.initialize(env.mx.init.Xavier())
    x_np = env.rng.rand(B, 3, S, S).astype(np.float32)
    x32 = env.nd.array(x_np)
    x = env.nd.array(x_np, dtype="bfloat16") if env.on_tpu else x32
    y = env.nd.array(env.rng.randint(0, classes, (B,)).astype(np.int32),
                     dtype="int32")

    def loss_fn(outputs, labels):
        logits = outputs[0] if isinstance(outputs, (list, tuple)) \
            else outputs
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(
            logp, labels[:, None].astype(jnp.int32), axis=-1).mean()

    trainer = env.parallel.ShardedTrainer(
        net, loss_fn, env.mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                          "weight_decay": 1e-4},
        example_inputs=(x32,), n_labels=1,
        dtype=jnp.bfloat16 if env.on_tpu else None)
    batch = (x, y)
    flops = _step_flops(trainer, batch)
    dt = _time_steps(lambda: trainer.step(*batch), env.steps)
    if flops is None:
        # analytic fallback: resnet50@224 fwd ~= 4.09 GMAC/img = 8.18
        # GFLOP; bwd ~= 2x fwd (scaled quadratically for the CPU-CI
        # 32px image)
        flops = 3 * 8.18e9 * B * (S / 224.0) ** 2
    return {"resnet50_mfu": env.mfu(flops, dt),
            "resnet50_imgs_per_sec": round(B / dt, 2),
            "resnet50_batch": B,
            "resnet50_step_gflops": round(flops / 1e9, 1),
            "attribution": _attribution(env, trainer, batch, flops)}


def phase_samebatch(env):
    """Headline ShardedTrainer re-measured at the batch the hybrid
    phase actually survived at, so _finalize can emit hybrid_vs_sharded
    from a like-for-like pair (r4's artifact had hybrid at B=24 vs
    headline at B=32 and rightly refused the ratio).  The orchestrator
    only schedules this when the batches diverged, passing the hybrid
    batch via BENCH_BATCH."""
    _model, head = env.build_pretrain()
    mfu, _sps, _loss, _n, _tr = env.sharded_phase(head, env.B, env.L)
    return {"sharded_mfu_at_hybrid_batch": mfu,
            "samebatch_batch": env.B}


def phase_nmt(env):
    """Config-4 training throughput: transformer-big (Sockeye WMT14
    En-De scale: 1024 units, 4096 hidden, 6+6 layers) training step,
    label-smoothed CE, bucketed (src, tgt) lengths.  Reports
    tokens/sec + MFU (XLA FLOP count, summed across buckets) and
    verifies the compile cache holds exactly one program per bucket —
    the BucketingModule contract (SURVEY §2.4 P8) at the sharded-step
    tier."""
    jax, jnp = env.jax, env.jnp
    B = int(os.environ.get("BENCH_NMT_BATCH", 32 if env.on_tpu else 2))
    vocab = 32768 if env.on_tpu else 64
    if env.on_tpu:
        model = env.models.transformer_big(
            src_vocab_size=vocab, dropout=0.0, max_length=320)
        buckets = [(96, 96), (160, 160), (256, 256)]
    else:
        model = env.models.transformer_base(
            src_vocab_size=vocab, units=64, hidden_size=128,
            num_layers=2, num_heads=4, dropout=0.0, max_length=64)
        buckets = [(8, 8), (16, 16)]
    model.initialize(env.mx.init.Xavier())

    def loss_fn(logits, tgt_out, tgt_valid):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(
            logp, tgt_out[..., None].astype(jnp.int32), -1)[..., 0]
        smooth = 0.1
        per_tok = (1.0 - smooth) * nll + smooth * (-logp.mean(-1))
        mask = (jnp.arange(per_tok.shape[1])[None, :]
                < tgt_valid[:, None]).astype(jnp.float32)
        return (per_tok * mask).sum() / mask.sum()

    def batch_for(Ls, Lt):
        src = env.nd.array(env.rng.randint(4, vocab, (B, Ls)),
                           dtype="int32")
        tgt_in = env.nd.array(env.rng.randint(4, vocab, (B, Lt)),
                              dtype="int32")
        tgt_out = env.nd.array(env.rng.randint(4, vocab, (B, Lt)),
                               dtype="int32")
        sv = env.nd.array(np.full((B,), Ls, np.float32))
        tv = env.nd.array(np.full((B,), Lt, np.float32))
        return (src, tgt_in, sv, tv), (tgt_out, tv)

    feats0, labels0 = batch_for(*buckets[0])
    trainer = env.parallel.ShardedTrainer(
        model, loss_fn, env.mesh, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-4},
        example_inputs=feats0, n_labels=2,
        dtype=jnp.bfloat16 if env.on_tpu else None)

    tok_total, time_total, flops_total = 0, 0.0, 0.0
    steps = max(2, env.steps // 2)
    batches = []
    for (Ls, Lt) in buckets:
        feats, labels = batch_for(Ls, Lt)
        batch = feats + labels
        batches.append(batch)
        dt = _time_steps(lambda: trainer.step(*batch), steps)
        tok_total += B * (Ls + Lt)
        time_total += dt
    # FLOPs via AOT cost analysis after the timed loops (lower/compile
    # does not disturb the dispatch cache)
    for batch in batches:
        flops = _step_flops(trainer, batch)
        if flops is not None:
            flops_total += flops
    n_params = env.n_params_of(trainer)
    if flops_total <= 0:
        # analytic fallback: encoder params touch only the B*Ls source
        # tokens and decoder params only the B*Lt target tokens, so with
        # a roughly even split the 6NBL count uses the MEAN of the two
        # lengths — 6*N*B*(Ls+Lt) would double-count (~2x at Ls==Lt)
        flops_total = sum(6.0 * n_params * B * (Ls + Lt) / 2.0
                          for Ls, Lt in buckets)
    out = {"nmt_train_tokens_per_sec": round(tok_total / time_total, 1),
           "nmt_train_mfu": env.mfu(flops_total, time_total),
           "nmt_batch": B, "nmt_buckets": len(buckets),
           "nmt_params": n_params}
    # bounded-compile-cache contract (SURVEY §2.4 P8): revisiting every
    # bucket must not grow the cache — the BucketingModule guarantee.
    # (The steady-state count can exceed len(buckets) by the first
    # call's layout-settling recompile; stability is the invariant.)
    before = trainer._step._cache_size()
    for batch in batches:
        trainer.step(*batch).block_until_ready()
    out["nmt_compiled_programs"] = trainer._step._cache_size()
    out["nmt_cache_stable"] = bool(
        trainer._step._cache_size() == before)
    return out


def phase_pipeline(env):
    """Input-pipeline feed ratio, in the artifact instead of only the
    playbook (r4 weak item): ImageRecordIter end-to-end imgs/sec on
    this host vs the resnet-50 training step's consumption rate."""
    from benchmark.opperf import time_input_pipeline
    res = time_input_pipeline(large=env.on_tpu)
    return {"pipeline_imgs_per_sec": res["imgs_per_sec"],
            "pipeline_vs_step": res["pipeline_vs_step"],
            "pipeline_threads": res["threads"],
            "pipeline_step_imgs_per_sec": res["step_samples_per_sec"]}


def phase_hybrid(env):
    """The user-facing Gluon path: hybridize + record/backward/step.
    backward+optimizer now fuse into one donated program
    (Trainer._try_fused_hybrid_step)."""
    from mxnet_tpu import gluon, autograd
    _model, head = env.build_pretrain()
    if env.on_tpu:
        head.cast("bfloat16")
    step_blk = env.models.BERTPretrainLoss(head)
    step_blk.hybridize(static_alloc=True)
    # pure-bf16 recipe (no fp32 masters), matching what the fused and
    # sharded phases run: in the ONE-program step the fp32
    # master+moment traffic costs ~16B/param of HBM per step — the
    # dominant tax once the residual round trip is gone
    gtrainer = gluon.Trainer(
        head.collect_params(), "adamw",
        {"learning_rate": 1e-4, "multi_precision": False})
    feats, labels = _mlm_batch(env.nd, env.rng, env.cfg["vocab_size"],
                               env.B, env.L)
    n_params = sum(int(np.prod(p.shape))
                   for p in head.collect_params().values()
                   if p.grad_req != "null")

    def hybrid_step():
        with autograd.record():
            l = step_blk(*feats, *labels)
        l.backward()
        gtrainer.step(env.B)
        return l._data

    hdt = _time_steps(hybrid_step, env.steps)
    return {"hybrid_mfu": env.mfu(6.0 * n_params * env.B * env.L, hdt),
            "_phase_batch": env.B}


def phase_fused(env):
    """gluon.contrib.FusedTrainStep: explicit one-program training.
    multi_precision=False: fp32 master + fp32 moments do not fit next
    to a BERT-large donation transition on a 16GB chip."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib import FusedTrainStep
    _model, head = env.build_pretrain()
    if env.on_tpu:
        head.cast("bfloat16")
    step_blk = env.models.BERTPretrainLoss(head)
    tr = gluon.Trainer(head.collect_params(), "adamw",
                       {"learning_rate": 1e-4, "multi_precision": False})
    fused = FusedTrainStep(step_blk, tr)
    feats, labels = _mlm_batch(env.nd, env.rng, env.cfg["vocab_size"],
                               env.B, env.L)
    n_params = sum(int(np.prod(p.shape))
                   for p in head.collect_params().values()
                   if p.grad_req != "null")
    fdt = _time_steps(
        lambda: fused(*feats, *labels, batch_size=env.B)._data,
        env.steps)
    return {"fused_step_mfu": env.mfu(6.0 * n_params * env.B * env.L,
                                      fdt),
            "_phase_batch": env.B}


def phase_flash(env):
    """Long-sequence Pallas flash-attention path at seq 512."""
    if not env.on_tpu:
        return {}
    Lf = int(os.environ.get("BENCH_FLASH_SEQLEN", 512))
    Bf = int(os.environ.get("BENCH_FLASH_BATCH", 8))
    _model, head = env.build_pretrain(use_flash=True, max_length=Lf)
    mfu, sps, _loss, _n, _tr = env.sharded_phase(head, Bf, Lf)
    return {"flash512_mfu": mfu,
            "flash512_samples_per_sec": round(sps, 2),
            "flash512_batch": Bf}


def phase_flash2048(env):
    """Long-context stretch: seq-2048 flash-attention pretrain step.
    The dense path cannot run this at all on one 16GB chip (O(L^2) fp32
    scores); flash trains it.  Token count B*L matches the headline's
    (2*2048 vs 32*128) so MFU is comparable.

    flash2048_mfu keeps the 6NBL numerator for r1-r4 comparability, but
    6NBL counts only parameter FLOPs; at L=2048 the O(L^2) attention
    matmuls the chip also executes are ~27% extra (per layer fwd
    4BL^2d + bwd 8BL^2d), so flash2048_attn_incl_mfu reports
    utilization against the full model-FLOP count (r4 verdict item 7:
    XLA's cost analysis can't see inside the Pallas custom-call, so the
    attention term is analytic)."""
    if not env.on_tpu:
        return {}
    Lf = 2048
    Bf = int(os.environ.get("BENCH_FLASH2048_BATCH", 2))
    _model, head = env.build_pretrain(use_flash=True, max_length=Lf)
    mfu, sps, _loss, n_params, _tr = env.sharded_phase(head, Bf, Lf)
    # depth/width from the shared config ("bert_<L>_<H>_<A>"), so a
    # config change can't silently skew the attention-FLOP term
    name_parts = env.cfg["model_name"].split("_")
    layers = int(env.cfg.get("num_layers", name_parts[1]))
    d_model = int(env.cfg.get("units", name_parts[2]))
    attn_flops = layers * 12.0 * Bf * Lf * Lf * d_model
    param_flops = 6.0 * n_params * Bf * Lf
    attn_incl = mfu * (param_flops + attn_flops) / param_flops
    return {"flash2048_mfu": mfu,
            "flash2048_attn_incl_mfu": round(attn_incl, 4),
            "flash2048_samples_per_sec": round(sps, 2),
            "flash2048_batch": Bf}


def run_phase(name):
    env = _Env()
    out = {"headline": phase_headline, "resnet": phase_resnet,
           "hybrid": phase_hybrid, "samebatch": phase_samebatch,
           "fused": phase_fused, "flash": phase_flash,
           "flash2048": phase_flash2048, "nmt": phase_nmt,
           "pipeline": phase_pipeline}[name](env)
    # per-phase persistent-cache accounting; the orchestrator SUMS
    # these across phases (they are deltas, not totals)
    out["compile_cache_hits"] = env.cache_stats["hits"]
    out["compile_cache_misses"] = env.cache_stats["misses"]
    out.update(env.device_info())
    print(json.dumps(out))


# ---------------------------------------------------------- orchestrator
def _run_child(phase, overrides, timeout):
    """Run one phase in its own process group, hard-killed on timeout.

    subprocess.run(timeout=...) is not enough here: on TimeoutExpired it
    kills only the direct child and then blocks until pipe EOF, and any
    helper the child spawned (data-loader workers) inherits the pipes —
    a wedged grandchild would hold stderr open and stall the
    orchestrator past its total budget.  killpg() the whole session
    instead."""
    import signal
    import subprocess
    env = dict(os.environ, BENCH_CHILD="1", BENCH_PHASE=phase, **overrides)
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True)
    except Exception as e:                       # noqa: BLE001
        return None, f"{phase}: {e!r}"
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            stdout, stderr = proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            stdout, stderr = "", ""
            try:                                 # reap; don't leave a zombie
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
        return None, (stderr or "") + f"\n{phase}: timed out after {timeout}s"
    lines = [l for l in (stdout or "").splitlines() if l.strip()]
    if proc.returncode == 0 and lines:
        try:
            return json.loads(lines[-1]), stderr
        except ValueError:
            pass
    return None, stderr


def _finalize(merged):
    """Derived keys + stable ordering for one merged snapshot."""
    out_src = dict(merged)
    if out_src.get("value") is not None:
        out_src["vs_baseline"] = round(out_src["value"] / 0.35, 4)  # north star
    if out_src.get("hybrid_mfu") is not None:
        if "hybrid_batch" not in out_src \
                and out_src.get("value") is not None:
            # hybrid survived at the headline batch: direct ratio
            out_src["hybrid_vs_sharded"] = round(
                out_src["hybrid_mfu"] / out_src["value"], 4)
        elif (out_src.get("samebatch_batch") is not None
              and out_src.get("samebatch_batch")
              == out_src.get("hybrid_batch")):
            # batches diverged; the samebatch phase re-ran the sharded
            # step at the hybrid batch so the ratio is like-for-like
            out_src["hybrid_vs_sharded"] = round(
                out_src["hybrid_mfu"]
                / out_src["sharded_mfu_at_hybrid_batch"], 4)
    order = ["metric", "value", "unit", "vs_baseline", "samples_per_sec",
             "batch", "seqlen", "params", "loss",
             "resnet50_mfu", "resnet50_imgs_per_sec", "resnet50_batch",
             "resnet50_step_gflops", "hybrid_mfu",
             "hybrid_vs_sharded", "sharded_mfu_at_hybrid_batch",
             "samebatch_batch", "fused_step_mfu", "flash512_mfu",
             "flash512_samples_per_sec", "flash512_batch",
             "flash2048_mfu", "flash2048_attn_incl_mfu",
             "flash2048_samples_per_sec",
             "flash2048_batch", "nmt_train_tokens_per_sec",
             "nmt_train_mfu", "nmt_batch", "nmt_buckets",
             "nmt_compiled_programs", "nmt_params",
             "pipeline_imgs_per_sec", "pipeline_vs_step",
             "pipeline_threads", "pipeline_step_imgs_per_sec",
             "attribution",
             "compile_cache_hits", "compile_cache_misses",
             "platform", "device_kind", "device_count"]
    out = {k: out_src[k] for k in order if k in out_src}
    out.update({k: v for k, v in out_src.items() if k not in out})
    return out


def _orchestrate():
    """Per-phase subprocess isolation with retries, under a hard deadline.

    This parent never imports jax (one process per chip: the phase child
    holds it).  Each full-batch config gets ONE attempt before dropping
    to the smaller 24/16 rungs.  The merged JSON is re-printed (flushed)
    after every phase so the last stdout line is always the best-so-far
    result, and a total-run deadline skips remaining phases rather than
    dying mid-retry.  Returns non-zero when any enabled phase failed on
    every attempt."""
    timeout = int(os.environ.get("BENCH_PHASE_TIMEOUT", 600))
    budget = float(os.environ.get("BENCH_TOTAL_BUDGET", 3000))
    deadline = time.monotonic() + budget
    attempts = {
        "headline": [{}, {"BENCH_BATCH": "24"}, {"BENCH_BATCH": "16"}],
        "resnet": [{}, {"BENCH_RESNET_BATCH": "256"},
                   {"BENCH_RESNET_BATCH": "128"}],
        "hybrid": [{}, {"BENCH_BATCH": "24"}, {"BENCH_BATCH": "16"}],
        "samebatch": [{}, {}],         # batch injected from hybrid result
        "fused": [{}, {"BENCH_BATCH": "24"}, {"BENCH_BATCH": "16"}],
        "flash": [{}, {}, {"BENCH_FLASH_BATCH": "4"}],
        "flash2048": [{}, {"BENCH_FLASH2048_BATCH": "1"}],
        "nmt": [{}, {"BENCH_NMT_BATCH": "16"}],
        "pipeline": [{}],
    }
    enabled = {
        "headline": True,
        "resnet": os.environ.get("BENCH_RESNET", "1") != "0",
        "hybrid": os.environ.get("BENCH_HYBRID", "1") != "0",
        "samebatch": os.environ.get("BENCH_SAMEBATCH", "1") != "0",
        "fused": os.environ.get("BENCH_FUSED", "1") != "0",
        "flash": os.environ.get("BENCH_FLASH", "1") != "0",
        "flash2048": os.environ.get("BENCH_FLASH2048", "1") != "0",
        "nmt": os.environ.get("BENCH_NMT", "1") != "0",
        "pipeline": os.environ.get("BENCH_PIPELINE", "1") != "0",
    }
    merged = {}
    failed = []

    def emit():
        if merged:
            print(json.dumps(_finalize(merged)), flush=True)

    for phase in PHASES:
        if not enabled[phase]:
            continue
        if phase == "samebatch":
            # only needed when hybrid survived at a DIFFERENT batch than
            # the headline; its job is the like-for-like denominator for
            # hybrid_vs_sharded
            hb = merged.get("hybrid_batch")
            if "hybrid_mfu" not in merged or hb is None:
                continue
            attempts["samebatch"] = [{"BENCH_BATCH": str(hb)}] * 2
        remaining = deadline - time.monotonic()
        if remaining < 90 and phase != "headline":
            print(f"bench: total budget exhausted before {phase}; "
                  f"skipping remaining phases", file=sys.stderr)
            break
        got = None
        for i, overrides in enumerate(attempts[phase]):
            remaining = deadline - time.monotonic()
            # headline's first attempt always runs — an artifact with a
            # headline number is the one non-negotiable output
            if remaining < 60 and not (phase == "headline" and i == 0):
                print(f"bench: total budget exhausted mid-{phase}; "
                      f"abandoning its remaining attempts", file=sys.stderr)
                break
            got, err = _run_child(phase, overrides,
                                  min(timeout, max(60, remaining)))
            if got is not None:
                if err:
                    sys.stderr.write(err[-1500:])
                break
            print(f"bench: phase {phase} attempt failed; retrying "
                  f"({err.strip()[-300:] if err else 'no output'})",
                  file=sys.stderr)
        if got is None:
            print(f"bench: phase {phase} failed on all attempts; "
                  f"continuing without it (the run will exit non-zero)",
                  file=sys.stderr)
            failed.append(phase)
            continue
        # a phase that only survived at a reduced batch must say so —
        # its MFU is not comparable to the headline batch's otherwise
        # (annotate on an explicit batch override too, so the flag
        # survives even when headline itself failed)
        pb = got.pop("_phase_batch", None)
        if pb is not None and ("batch" not in merged
                               or merged["batch"] != pb):
            got[f"{phase}_batch"] = pb
        # per-phase cache counts are deltas: sum across phases
        for k in ("compile_cache_hits", "compile_cache_misses"):
            if k in got:
                got[k] = merged.get(k, 0) + got[k]
        # step-breakdown blocks nest per phase instead of clobbering
        attr = got.pop("attribution", None)
        if attr is not None:
            merged.setdefault("attribution", {})[phase] = attr
        merged.update(got)
        emit()

    if failed:
        print(f"bench: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    if os.environ.get("BENCH_CHILD"):
        run_phase(os.environ.get("BENCH_PHASE", "headline"))
    else:
        sys.exit(_orchestrate())
