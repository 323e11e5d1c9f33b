"""Checks of the ``mellum2-12b-a2.5b`` configuration and its cell on the
CPU at a small size: the program (``models.get_decoder_lm`` through
``ShardedTrainer``, as the cell's adapter builds it) against the plain
reference, the shares of the experts against the uncut layer, the work
functions by hand, the new reader, and (``slow``) a rehearsal of the
cell, its control and its planted faults."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from perfbench import check, harness, traffic, work_mellum   # noqa: E402
from perfbench.adapters import mellum_moe as adapter         # noqa: E402
from perfbench.reference import mellum_moe as ref            # noqa: E402

CELL = "mellum2-12b-a2.5b.causal_b1_l8192"
WORK, CFG, MIX = harness.load_cell(CELL)
TOY, DIMS = CFG["toy"], CFG["dims"]
SEED = 2 ** 31 + 1234


def _program(attention="float32", dims=TOY):
    """The cell's program at the toy size; float32 attention where the
    comparison is to be tight (the cell's own runs in bfloat16)."""
    cfg = dict(CFG, use_flash=True,
               precision=dict(CFG["precision"], attention=attention))
    batches = traffic.mlm_batches(MIX["toy"], dims["vocab_size"], SEED)
    program = adapter.build(cfg, dims, batches[0], jax.devices()[:1], {})
    program.load_weights(ref.init_weights(dims, SEED))
    return program, batches


@pytest.fixture(scope="module")
def followed():
    """Three steps of the program and of the reference from one seed."""
    from perfbench.runners import train as runner
    program, batches = _program()
    with jax.default_matmul_precision("highest"):
        got = runner.first_steps(program, ref, TOY, SEED, batches)
    want = ref.train_steps(TOY, CFG["optimizer"], SEED, batches[:3], 1)
    low = ref.train_steps(TOY, CFG["optimizer"], SEED, batches[:3], 1,
                          dtype=jnp.bfloat16)
    return got, want, low, ref.leaf_sizes(TOY)


# Tolerances, with their reasons.  Program and reference compute the
# same float32 equations in another order (flash blocks against dense
# rows, sorted grouped products against a weighted sum over all
# experts): sums of a few hundred float32 terms differ by a few 1e-7
# relative, and three adamw steps at 1e-4 amplify a gradient's relative
# error into the change (the first step moves every weight by 1e-4
# whatever the gradient's size, so a leaf's change is insensitive; the
# second and third are not).  bfloat16 arithmetic (2**-9) misses each by
# two orders of magnitude.
LOSS_TOL, GRAD_TOL, CHANGE_TOL = 2e-6, 2e-4, 2e-3


def test_logits_match_the_reference():
    program, batches = _program()
    from mxnet_tpu.parallel.functional import functionalize
    tokens = jnp.asarray(batches[0][0])
    t = program.trainer
    with jax.default_matmul_precision("highest"):
        want, _ids = ref.forward(ref.init_weights(TOY, SEED), TOY, tokens)
        apply_fn, _p = functionalize(t.block, tokens)
        got, _aux = jax.jit(apply_fn)(t.params, tokens)
    # logits are sums of 64 products of O(1) by O(0.02) terms: 1e-5 of
    # their size (0.1) is float32 rounding
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-6)
    assert not np.allclose(
        np.asarray(got), np.asarray(ref.forward(
            ref.init_weights(TOY, SEED, jnp.bfloat16), TOY, tokens)[0],
            np.float32), rtol=1e-4, atol=2e-6)


def test_losses_match_the_reference(followed):
    # (a loss of ln(512) moves by 1e-5 of itself under bfloat16: the
    # logits, the gradient and the change tell the precisions apart)
    got, want, _low, _sizes = followed
    for k in range(3):
        assert abs(got["losses"][k] - want["losses"][k]) \
            <= LOSS_TOL * want["losses"][k]


def test_first_gradient_matches_the_reference_leaf_by_leaf(followed):
    got, want, low, sizes = followed
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(sizes)
    gap, leaf = check.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    assert gap <= GRAD_TOL, (gap, leaf)
    assert check.worst_leaf_gap(low["grad_norms"],
                                want["grad_norms"])[0] > GRAD_TOL


def test_parameters_after_three_steps_match_the_reference(followed):
    got, want, low, _sizes = followed
    gap, leaf = check.worst_leaf_gap(got["change_norms"],
                                     want["change_norms"])
    assert gap <= CHANGE_TOL, (gap, leaf)
    assert check.worst_leaf_gap(low["change_norms"],
                                want["change_norms"])[0] > CHANGE_TOL


def test_the_router_is_differentiated_and_not_moved(followed):
    """The configuration trains a share of the experts alone, so its
    router's weight is frozen; the gradient is still taken and
    compared."""
    got, want, _low, _sizes = followed
    assert TOY["train_router"] is False
    for side in (got, want):
        assert side["change_norms"]["l2.router_w"] == 0.0
        assert side["grad_norms"]["l2.router_w"] > 0.0
        assert side["change_norms"]["l2.w2.e1"] > 0.0


def test_the_cells_own_precision_stays_inside_the_toy_limits(followed):
    """bfloat16 attention, as the cell runs it."""
    from perfbench.runners import train as runner
    _got, want, _low, sizes = followed
    program, batches = _program(attention=CFG["precision"]["attention"])
    got = runner.first_steps(program, ref, TOY, SEED, batches)
    numbers, _where = check.train_numbers(got, want, sizes)
    ok, table = check.verdict(numbers, {k: v for k, v in
                                        WORK["toy_limits"].items()
                                        if k.endswith("_gap")})
    assert ok, table


# --------------------------------------------------- the shares add up
SHARE = dict(TOY, num_experts=16, experts_per_token=4)


def _layer(first, held, seed=7):
    """(the reference's weights of one expert layer with experts
    first..first+held-1 of 16, the tokens' vectors)."""
    dims = dict(SHARE, experts_held=16, first_expert=0, num_layers=1)
    w = ref.init_weights(dims, seed)
    g = {leaf: w[f"l0.{leaf}"] for leaf in ("router_w", "w1", "w2")}
    # weights of a size at which the experts' part is not lost in the
    # residual, and a router that tells the experts apart
    g["w1"], g["w2"] = (20 * g[k][first:first + held] for k in ("w1", "w2"))
    g["router_w"] = 50 * g["router_w"]
    m = jax.random.normal(jax.random.PRNGKey(seed), (96, dims["units"]))
    return g, m, dict(dims, experts_held=held, first_expert=first)


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_a_share_of_the_program_is_that_share_of_the_reference(first):
    from mxnet_tpu.ops.moe import moe_ffn
    g, m, dims = _layer(first, 4)
    with jax.default_matmul_precision("highest"):
        ids, w = ref.route(g, m, dims)
        want = ref.experts(g, m, ids, w, dims)
        got, rows = moe_ffn(m, g["router_w"], g["w1"], g["w2"],
                            experts_per_token=4, first_expert=first,
                            activation="silu", gated=True)
    assert float(jnp.abs(want).max()) > 0.01
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    held = (np.asarray(ids) >= first) & (np.asarray(ids) < first + 4)
    assert float(rows.sum()) == held.sum()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """h + sum over the four chips of their experts' part = the uncut
    reference's layer output: the residual is counted once, the router
    and the normalisation over all chosen experts are every chip's."""
    from mxnet_tpu.ops.moe import moe_ffn
    g, m, dims = _layer(0, 16)
    with jax.default_matmul_precision("highest"):
        ids, w = ref.route(g, m, dims)
        whole = m + ref.experts(g, m, ids, w, dims)
        parts = [moe_ffn(m, g["router_w"], g["w1"][lo:lo + 4],
                         g["w2"][lo:lo + 4], experts_per_token=4,
                         first_expert=lo, activation="silu", gated=True)
                 for lo in (0, 4, 8, 12)]
    np.testing.assert_allclose(np.asarray(m + sum(p[0] for p in parts)),
                               np.asarray(whole), rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(whole).max()))
    # every routed pair was computed by exactly one chip
    assert sum(float(p[1].sum()) for p in parts) == 96 * 4


@pytest.mark.parametrize("fault", ["no_window", "top7", "no_renorm"])
def test_planted_faults_change_the_reference(fault):
    tokens = jnp.asarray(traffic.mlm_batches(MIX["toy"], TOY["vocab_size"],
                                             SEED)[0][0])
    w = ref.init_weights(TOY, SEED)
    with jax.default_matmul_precision("highest"):
        sound, ids = ref.hidden(w, TOY, tokens)
        broken, bad_ids = ref.hidden(w, TOY, tokens, fault)
    assert ids.shape[-1] - bad_ids.shape[-1] == (fault == "top7")
    gap = float(jnp.abs(sound - broken).max() / jnp.abs(sound).max())
    assert gap > 1e-4, gap          # float32 rounding is 1e-7


# --------------------------------------------------- the data and the work
def test_configuration_file_states_the_cut():
    from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
    pub = _DECODER_CONFIGS["mellum2_12b_a2.5b"]
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (4, 16, 24576)
    assert CFG["published"]["num_experts"] == 64 == DIMS["num_experts"]
    assert "4 chips share each layer" in CFG["deployment"]
    # every width as published
    for ours, theirs in (("hidden_size", "units"), ("head_dim", "head_dim"),
                         ("num_attention_heads", "num_heads"),
                         ("num_key_value_heads", "num_kv_heads"),
                         ("moe_intermediate_size", "expert_hidden_size"),
                         ("num_experts_per_tok", "experts_per_token"),
                         ("sliding_window", "window")):
        assert CFG[ours] == pub[theirs] == DIMS[theirs], ours
    assert DIMS["layer_types"] == CFG["layer_types"][:4] \
        == list(pub["layer_types"][:4])
    assert (DIMS["experts_held"], DIMS["vocab_size"],
            DIMS["num_layers"]) == (16, 24576, 4)
    for kind, kw in pub["rope"].items():
        assert adapter.rope_kwargs(DIMS["rope_parameters"][kind]) == kw
    sizes = ref.leaf_sizes(DIMS)
    assert round(sum(sizes.values()) / 1e6, 1) == 595.2
    assert MIX == dict(MIX, batch=1, seqlen=8192, masked=0, host_batches=16,
                       warmup_steps=5, reference_rows=1, use_flash=True)


@pytest.mark.parametrize("fn,args,want", [
    (work_mellum.visible_pairs, (4,), 10),
    (work_mellum.visible_pairs, (4, 2), 7),       # 1 + 2 + 2 + 2
    (work_mellum.visible_pairs, (8192, 1024), 1024 * 1025 // 2
     + 7168 * 1024),
    (work_mellum.visible_pairs, (8, 16), 36),
    (work_mellum.expert_row_flops, (DIMS,), 6 * 2304 * 896),
])
def test_work_counts_by_hand(fn, args, want):
    assert fn(*args) == want


def test_forward_flops_by_hand():
    """498 MFLOP a token: projections 34%, scores 23%, head 23%, the
    held experts 20% (2 of a token's 8 on average)."""
    proj = 4 * 2 * 2304 * (2 * 4096 + 2 * 512)
    router = 4 * 2 * 2304 * 64
    experts = 4 * 2 * 6 * 2304 * 896
    attn = 4 * 32 * 128 * (3 * (1024 * 1025 // 2 + 7168 * 1024)
                           + 8192 * 8193 // 2) / 8192
    head = 2 * 2304 * 24576 * 8191 / 8192
    total = work_mellum.forward_flops(DIMS, 1, 8192) / 8192
    assert total == pytest.approx(proj + router + experts + attn + head)
    assert round(total / 1e6) == 498
    assert [round(100 * x / total) for x in (proj, attn, head, experts)] \
        == [34, 23, 23, 20]


def _ctx(steps):
    from types import SimpleNamespace
    return SimpleNamespace(dims=DIMS, cfg=CFG, facts={
        "steps": steps, "traffic": {"batch": 1, "seqlen": 8192}})


def test_kernel_work_by_hand():
    ops, nbytes = work_mellum.flash_training(_ctx(2))
    pairs = 3 * (1024 * 1025 // 2 + 7168 * 1024) + 8192 * 8193 // 2
    assert ops == 2 * 2 * pairs * 32 * 128 * 6
    assert nbytes == 2 * 4 * 2 * 8192 * 128 * 6 * (32 + 4)
    adapter.WINDOW.clear()
    assert work_mellum.expert_products(_ctx(2)) is None
    adapter.WINDOW.update(steps=2, rows=np.full((4, 16), 2048.0))
    ops, nbytes = work_mellum.expert_products(_ctx(2))
    rows = 4 * 16 * 2048
    assert ops == 3 * rows * 6 * 2304 * 896
    assert nbytes == 4 * 3 * (2 * 4 * 16 * 3 * 2304 * 896
                              + rows * 2 * 2304)
    assert work_mellum.expert_products(_ctx(3)) is None   # another window
    adapter.WINDOW.clear()
    assert work_mellum.train_flops(_ctx(2)) \
        == 2 * 3 * work_mellum.forward_flops(DIMS, 1, 8192)


def test_cell_reads_the_trainers_metrics_and_its_own():
    from perfbench.runners import train as runner
    names = {m["name"] for m in harness.cell_metrics(CELL,
                                                     runner.END_TO_END)}
    # at least these: a later PR may add a metric every training cell reads
    assert names >= {
        "train.step_mfu_pct", "train.device_idle_pct", "train.dispatch_ms",
        "train.h2d_ms", "train.compiles_in_window", "train.optim_device_ms",
        "train.fwd_bwd_device_ms", "train.moe_device_ms",
        "train.attn_device_ms", "moe_experts_roofline",
        "flash_attn_train_roofline", "train.step_device_ms",
        "train.attn_proj_device_ms", "train.head_loss_device_ms",
        "train.norm_embed_device_ms", "train.unnamed_device_ms",
        "train.unscoped_device_ms"}
    # no dense feed-forward and no dense attention core in this model
    assert not names & {"train.ffn_device_ms", "train.attn_dense_device_ms"}
    other = {m["name"] for m in harness.cell_metrics(
        "bert-large.pretrain_b32_l128", runner.END_TO_END)}
    assert not other & {"train.moe_device_ms", "train.attn_device_ms",
                        "moe_experts_roofline", "flash_attn_train_roofline"}


@pytest.mark.parametrize("case", ["share", "also", "pattern_matches_nothing",
                                  "no_work", "no_program", "no_peak"])
def test_scope_roofline_on_the_recorded_trace(case):
    """The recorded slice of a BERT step (``fixtures/train_slice``):
    its operations lie under ``mx.fwd`` and ``mx.optim``."""
    from types import SimpleNamespace
    from perfbench import span_reduce, trace_reduce
    from perfbench.readers import scope_roofline
    path = os.path.join(REPO, "perfbench", "fixtures",
                        "train_slice.xspace.txt")
    data, raw = span_reduce._data(path)
    ctx = SimpleNamespace(
        trace=trace_reduce.load(path), mx=(None, span_reduce.op_names(raw)),
        peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        facts={"steps": 10}, note=lambda text: None)
    runs = len(ctx.trace.modules)
    seconds, n, other = span_reduce.scope_seconds(
        ctx.trace, ctx.mx[1], "mx_train_step", ["mx.fwd"])
    assert n == runs and seconds > 0 and other > 0
    scope_roofline.work = SimpleNamespace(
        resolve=lambda spec: (lambda c: None if case == "no_work"
                              else (1e9 * 10, 1e6 * 10)))
    params = {"program": "mx_train_step", "scopes": ["mx.fwd"], "work": "x:y"}
    if case == "pattern_matches_nothing":
        params["pattern"] = "tpu_custom_call"
    elif case == "also":            # every other operation of the program
        params["also"] = "."
        seconds += other
    elif case == "no_program":
        params["program"] = "mx_other_step"
    elif case == "no_peak":
        ctx.peak = None
    got = scope_roofline.read({"name": "x_roofline", "params": params}, ctx)
    if case in ("share", "also"):
        # 1e9 operations a step at 1e12 a second: 1 ms a step at the least
        assert got == pytest.approx(100.0 * 1e-3 / seconds)
    else:
        assert got is None


# ------------------------------------------------------------- rehearsals
def _child(code, timeout=900):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_rehearsal_of_the_cell_is_correct():
    r = _child("import sys\nfrom perfbench import run\n"
               f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', "
               f"'{SEED}', '--seconds', '2', '--trace', '1', "
               "'--rehearsal']))\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "" and "REHEARSAL" in r.stderr
    assert "rows routed to held experts a step" in r.stderr
    assert any(ln.startswith("correct: true  ")
               for ln in r.stderr.strip().splitlines()[-3:])


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["control", "no_window", "top7",
                                   "no_renorm"])
def test_the_control_and_each_planted_fault_are_not_correct(fault):
    """At the toy size and the toy limits: the reference in bfloat16,
    and the reference with one fault planted in its layers, put in the
    program's place (the chip's readings at the cell's size: PERF.md)."""
    batches = traffic.mlm_batches(MIX["toy"], TOY["vocab_size"], SEED)[:3]
    want = ref.train_steps(TOY, CFG["optimizer"], SEED, batches, 1)
    kw = {"dtype": jnp.bfloat16} if fault == "control" else {"fault": fault}
    got = ref.train_steps(TOY, CFG["optimizer"], SEED, batches, 1, **kw)
    numbers, _where = check.train_numbers(got, want, ref.leaf_sizes(TOY))
    limits = {k: v for k, v in WORK["toy_limits"].items()
              if k.endswith("_gap")}
    assert not check.verdict(numbers, limits)[0], numbers
