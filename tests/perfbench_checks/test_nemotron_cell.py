"""Checks of the ``nemotron-3-nano-30b-a3b`` configuration and its cell
on the CPU at a small size: the program (``models.get_decoder_lm``
through ``ShardedTrainer``, as the cell's adapter builds it) against the
plain reference, whose Mamba-2 is the recurrence one position at a time;
the sixteen shares of the experts against the uncut layer; the work
functions by hand; and (``slow``) a rehearsal of the cell, its control
and its planted faults."""
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

from perfbench import check, harness, traffic, work_nemotron   # noqa: E402
from perfbench.adapters import nemotron_h as adapter           # noqa: E402
from perfbench.reference import nemotron_h as ref              # noqa: E402

CELL = "nemotron-3-nano-30b-a3b.causal_b1_l8192"
WORK, CFG, MIX = harness.load_cell(CELL)
TOY, DIMS = CFG["toy"], CFG["dims"]
SEED = 2 ** 31 + 4321
FAULTS = list(ref.FAULTS[1:])


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


# Tolerances, with their reasons: Mellum's cell's, for its reasons (the
# same float32 equations in another order: here also the chunked scan
# against the recurrence, whose sums of up to 128 float32 terms differ
# by a few 1e-7 relative).  bfloat16 arithmetic misses each by two
# orders of magnitude.
LOSS_TOL, GRAD_TOL, CHANGE_TOL = 2e-6, 2e-4, 2e-3


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "mxnet_tpu" not in text
    # and its Mamba-2 is a scan over positions, not over chunks
    assert "jax.lax.scan(position, h, part)" in text


def test_logits_match_the_reference():
    program, batches = _program()
    from mxnet_tpu.parallel.functional import functionalize
    tokens = jnp.asarray(batches[0][0])
    t = program.trainer
    with jax.default_matmul_precision("highest"):
        want, ids = ref.forward(ref.init_weights(TOY, SEED), TOY, tokens)
        apply_fn, _p = functionalize(t.block, tokens)
        got, aux = jax.jit(apply_fn)(t.params, tokens)
    assert ids.shape == (4, tokens.size, TOY["experts_per_token"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-6)
    # the device-side counter counted the reference's held pairs
    held = np.asarray((ids >= TOY["first_expert"])
                      & (ids < TOY["first_expert"] + TOY["experts_held"]))
    counted = [float(aux[n].sum()) for n in program.counters]
    assert counted == held.sum((1, 2)).tolist()
    assert not np.allclose(
        np.asarray(got), np.asarray(ref.forward(
            ref.init_weights(TOY, SEED, jnp.bfloat16), TOY, tokens)[0],
            np.float32), rtol=1e-4, atol=2e-6)


def test_losses_match_the_reference(followed):
    got, want, _low, _sizes = followed
    for k in range(3):
        assert abs(got["losses"][k] - want["losses"][k]) \
            <= LOSS_TOL * want["losses"][k]


def test_first_gradient_matches_the_reference_leaf_by_leaf(followed):
    got, want, low, sizes = followed
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(sizes)
    # every kind of leaf is there, fused ones as their parts
    for leaf in ("l0.in_w.z", "l0.in_w.xbc", "l0.in_w.dt", "l0.conv_w",
                 "l0.A_log", "l0.dt_bias", "l0.D", "l0.gate_norm_g",
                 "l5.kv_w.k", "l5.kv_w.v", "l1.w1", "l1.w2",
                 "l1.shared_w1", "l1.router_w", "l1.router_bias"):
        assert leaf in sizes, leaf
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
    router's weight is frozen (the gradient is still taken and
    compared); the choice bias is no weight at all: it only chooses, so
    its gradient is nought, and no optimizer touches it."""
    got, want, _low, _sizes = followed
    assert TOY["train_router"] is False
    for side in (got, want):
        assert side["change_norms"]["l3.router_w"] == 0.0
        assert side["grad_norms"]["l3.router_w"] > 0.0
        assert side["change_norms"]["l3.router_bias"] == 0.0
        assert side["grad_norms"]["l3.router_bias"] == 0.0
        for leaf in ("l3.w2", "l3.shared_w2", "l2.A_log", "l2.conv_b",
                     "l5.kv_w.k"):
            assert side["change_norms"][leaf] > 0.0, leaf


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
SHARE = dict(TOY, num_experts=16, experts_per_token=3)


def _layer(first, held, seed=7):
    """(the reference's weights of one expert layer with experts
    first..first+held-1 of 16, the tokens' vectors, its dims)."""
    dims = dict(SHARE, experts_held=16, first_expert=0, num_layers=1,
                layer_types=["moe"])
    w = ref.init_weights(dims, seed)
    g = {leaf: w[f"l0.{leaf}"] for leaf in ref.LEAVES["moe"]}
    # weights of a size at which the experts' part is not lost in the
    # residual, a router that tells the experts apart, and a bias that
    # changes what is chosen
    g["w1"], g["w2"] = (20 * g[k][first:first + held] for k in ("w1", "w2"))
    g["shared_w1"], g["shared_w2"] = 20 * g["shared_w1"], 20 * g["shared_w2"]
    g["router_w"], g["router_bias"] = 50 * g["router_w"], 30 * g["router_bias"]
    m = jax.random.normal(jax.random.PRNGKey(seed), (96, dims["units"]))
    return g, m, dict(dims, experts_held=held, first_expert=first)


def _moe_ffn(g, m, first, shared=True):
    from mxnet_tpu.ops.moe import moe_ffn
    more = (g["shared_w1"], g["shared_w2"]) if shared else ()
    return moe_ffn(m, g["router_w"], g["w1"], g["w2"], g["router_bias"],
                   *more, experts_per_token=3, first_expert=first,
                   activation="relu2", gated=False, scoring="sigmoid",
                   route_scale=SHARE["routed_scaling_factor"],
                   shared_expert=shared)


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_a_share_of_the_program_is_that_share_of_the_reference(first):
    g, m, dims = _layer(first, 4)
    with jax.default_matmul_precision("highest"):
        want, ids = ref.moe(g, m, dims)
        got, rows = _moe_ffn(g, m, first)
    assert float(jnp.abs(want).max()) > 0.01
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    held = (np.asarray(ids) >= first) & (np.asarray(ids) < first + 4)
    assert float(rows.sum()) == held.sum()


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """x + the sum over the sixteen chips of their one expert's part +
    the shared expert ONCE = the uncut reference's layer output: the
    residual and the shared expert are every chip's and count once, the
    router and the normalisation over all chosen experts are every
    chip's."""
    g, m, dims = _layer(0, 16)
    one = lambda lo: {**g, "w1": g["w1"][lo:lo + 1],         # noqa: E731
                      "w2": g["w2"][lo:lo + 1]}
    with jax.default_matmul_precision("highest"):
        whole = m + ref.moe(g, m, dims)[0]
        shared = ref.relu2(m @ g["shared_w1"]) @ g["shared_w2"]
        parts = [_moe_ffn(one(lo), m, lo, shared=False) for lo in range(16)]
        # a chip's own output holds the shared expert too
        with_shared = _moe_ffn(one(5), m, 5)[0]
    total = m + shared + sum(p[0] for p in parts)
    scale = float(jnp.abs(whole).max())
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(with_shared - parts[5][0]),
                               np.asarray(shared), rtol=1e-4,
                               atol=1e-5 * scale)
    assert float(jnp.abs(shared).max()) > 0.01 * scale
    # every routed pair was computed by exactly one chip
    assert sum(float(p[1].sum()) for p in parts) == 96 * 3


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_change_the_reference(fault):
    tokens = jnp.asarray(traffic.mlm_batches(MIX["toy"], TOY["vocab_size"],
                                             SEED)[0][0])
    w = ref.init_weights(TOY, SEED)
    with jax.default_matmul_precision("highest"):
        sound, ids = ref.hidden(w, TOY, tokens)
        broken, bad_ids = ref.hidden(w, TOY, tokens, fault)
    assert ids.shape[-1] - bad_ids.shape[-1] == (fault == "top5")
    gap = float(jnp.abs(sound - broken).max() / jnp.abs(sound).max())
    assert gap > 1e-4, gap          # float32 rounding is 1e-7
    if fault == "no_bias":          # the bias changes what is chosen
        assert bool((np.sort(ids, -1) != np.sort(bad_ids, -1)).any())


def test_the_seeds_special_leaves_are_as_assumed():
    w = ref.init_weights(TOY, SEED)
    A, dt_bias = np.asarray(w["l0.A_log"]), np.asarray(w["l0.dt_bias"])
    assert (np.exp(A) >= 1).all() and (np.exp(A) <= 16).all()
    step = np.log1p(np.exp(dt_bias))                    # softplus
    assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()
    assert (np.asarray(w["l0.D"]) == 1).all()
    assert np.abs(np.asarray(w["l0.conv_w"])).max() <= 0.5
    assert 0.2 < np.asarray(w["l0.conv_w"]).std() < 0.35   # uniform(+-0.5)
    assert 0.8 < np.asarray(w["embed"]).std() < 1.2
    # what writes into the residual stream is divided by sqrt(52)
    for leaf in ("l0.out_w", "l5.o_w", "l1.w2", "l1.shared_w2"):
        assert np.asarray(w[leaf]).std() == pytest.approx(
            0.02 / 52 ** 0.5, rel=0.1), leaf
    for leaf in ("l0.in_w", "l5.q_w", "l1.w1", "l1.shared_w1", "head_w"):
        assert np.asarray(w[leaf]).std() == pytest.approx(0.02, rel=0.1), leaf
    bias = np.asarray(w["l1.router_bias"])
    assert 0 < np.abs(bias).max() < 0.05
    assert not (np.asarray(w["l1.router_bias"])
                == np.asarray(w["l3.router_bias"])).all()
    big = ref.init_weights(TOY, 2 ** 31 + 7)        # seeds pass 2**31
    assert not np.allclose(np.asarray(big["l0.A_log"]), A)


# --------------------------------------------------- the data and the work
def test_configuration_file_states_the_cut():
    from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
    pub = _DECODER_CONFIGS["nemotron_3_nano_30b_a3b"]
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (9, 8, 16384)
    assert CFG["published"] == dict(
        CFG["published"], num_hidden_layers=52, n_routed_experts=128,
        vocab_size=131072)
    assert "16 chips share each layer" in CFG["deployment"]
    # the catalog row's numbers, key for key, but the three reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CFG["source"])
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # every width as published: the file, the model zoo and the run agree
    m = pub["mamba"]
    for ours, zoo, dim in (
            ("hidden_size", pub["units"], "units"),
            ("head_dim", pub["head_dim"], "head_dim"),
            ("num_attention_heads", pub["num_heads"], "num_heads"),
            ("num_key_value_heads", pub["num_kv_heads"], "num_kv_heads"),
            ("mamba_num_heads", m["num_heads"], "mamba_num_heads"),
            ("mamba_head_dim", m["head_dim"], "mamba_head_dim"),
            ("ssm_state_size", m["state_size"], "ssm_state_size"),
            ("n_groups", m["n_groups"], "n_groups"),
            ("conv_kernel", m["conv_kernel"], "conv_kernel"),
            ("chunk_size", m["chunk"], "chunk_size"),
            ("moe_intermediate_size", pub["expert_hidden_size"],
             "expert_hidden_size"),
            ("moe_shared_expert_intermediate_size",
             pub["shared_expert_hidden_size"], "shared_expert_hidden_size"),
            ("num_experts_per_tok", pub["experts_per_token"],
             "experts_per_token"),
            ("routed_scaling_factor", pub["router"]["route_scale"],
             "routed_scaling_factor"),
            ("norm_eps", pub["rms_norm_eps"], "rms_norm_eps")):
        assert CFG[ours] == zoo == DIMS[dim], ours
    # nemotron_h's inner width is heads x head size (expand is not read)
    assert DIMS["mamba_num_heads"] * DIMS["mamba_head_dim"] == 4096
    # the router keeps 128 outputs and 6 a token; 8 experts are held
    assert (DIMS["num_experts"], pub["num_experts"],
            DIMS["experts_per_token"], DIMS["experts_held"]) == (128, 128, 6, 8)
    letters = {"mamba2": "M", "moe": "E", "attention": "*"}
    assert "".join(letters[k] for k in DIMS["layer_types"]) \
        == CFG["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert list(pub["layer_types"][:9]) == DIMS["layer_types"] \
        == TOY["layer_types"]
    assert (DIMS["vocab_size"], DIMS["num_layers"]) == (16384, 9)
    sizes = ref.leaf_sizes(DIMS)
    assert round(sum(sizes.values()) / 1e6) == 667
    assert round(12 * sum(sizes.values()) / 1e9, 1) == 8.0
    assert CFG["optimizer"] == harness.load_json(
        "configs", "mellum2-12b-a2.5b.json")["optimizer"]
    assert MIX == dict(MIX, batch=1, seqlen=8192, masked=0, host_batches=16,
                       warmup_steps=5, reference_rows=1, use_flash=True)
    assert len(WORK["why"]) <= 200


@pytest.mark.parametrize("fn,args,want", [
    (work_nemotron.kinds, (DIMS, "mamba2"), 4),
    (work_nemotron.kinds, (DIMS, "moe"), 4),
    (work_nemotron.kinds, (DIMS, "attention"), 1),
    (work_nemotron.expert_row_flops, (DIMS,), 4 * 2688 * 1856),
    (work_nemotron.scan_token_flops, (DIMS,),
     8 * 2 * 128 * 128 + 64 * 2 * 128 * 64 * 3),
    (work_nemotron.scan_token_bytes, (DIMS,),
     4 * (4096 + 1024 + 1024 + 64 + 4096)),
])
def test_work_counts_by_hand(fn, args, want):
    assert fn(*args) == want


def test_forward_flops_by_hand():
    """718 MFLOP a token: the four Mamba-2 mixers 45%, the four expert
    layers 27% (their shared experts 22%, the held routed experts 4%),
    attention 16%, the head 12%."""
    mamba = 2 * 2688 * 10304 + 2 * 4096 * 2688 + (
        8 * 2 * 128 * 128 + 3 * 64 * 2 * 128 * 64)
    attn = 2 * 2688 * (2 * 4096 + 2 * 256) \
        + 2 * 2 * 32 * 128 * (8192 * 8193 // 2) / 8192
    shared, routed = 4 * 2688 * 3712, 6 * 8 / 128 * 4 * 2688 * 1856
    moe = 2 * 2688 * 128 + shared + routed
    head = 2 * 2688 * 16384 * 8191 / 8192
    total = work_nemotron.forward_flops(DIMS, 1, 8192) / 8192
    assert total == pytest.approx(4 * mamba + attn + 4 * moe + head)
    assert round(mamba / 1e6) == 81 and round(total / 1e6) == 718
    assert [round(100 * x / total) for x in
            (4 * mamba, 4 * moe, 4 * shared, 4 * routed, attn, head)] \
        == [45, 27, 22, 4, 16, 12]
    assert round(3 * total * 8192 / 1e12, 1) == 17.6    # TFLOP a step


def _ctx(steps):
    from types import SimpleNamespace
    return SimpleNamespace(dims=DIMS, cfg=CFG, facts={
        "steps": steps, "traffic": {"batch": 1, "seqlen": 8192}})


def test_kernel_work_by_hand():
    ops, nbytes = work_nemotron.ssm_scan(_ctx(2))
    tokens = 2 * 8192 * 4
    assert ops == 3 * tokens * (8 * 2 * 128 * 128 + 3 * 64 * 2 * 128 * 64)
    assert nbytes == 3 * tokens * 4 * (2 * 4096 + 2 * 1024 + 64)
    # memory-bound on a v5e: 197 TFLOP/s, 819 GB/s
    assert nbytes / 819e9 > 2 * ops / 197e12
    # the one attention layer's flash kernels: 32 query heads of 128
    # over 2 key/value heads, 8192 * 8193 / 2 visible pairs
    ops, nbytes = work_nemotron.flash_training(_ctx(2))
    assert ops == 2 * 2 * (8192 * 8193 // 2) * 32 * 128 * 6
    assert nbytes == 2 * 2 * 8192 * 128 * 6 * (32 + 2)
    assert ops / 197e12 > nbytes / 819e9                  # compute-bound
    adapter.WINDOW.clear()
    assert work_nemotron.expert_products(_ctx(2)) is None
    adapter.WINDOW.update(steps=2, rows=np.full((4, 8), 768.0))
    ops, nbytes = work_nemotron.expert_products(_ctx(2))
    rows = 4 * 8 * 768
    assert ops == 3 * rows * 4 * 2688 * 1856
    assert nbytes == 4 * 3 * (2 * 4 * 8 * 2 * 2688 * 1856
                              + rows * 2 * 2688)
    assert work_nemotron.expert_products(_ctx(3)) is None   # another window
    adapter.WINDOW.clear()
    assert work_nemotron.train_flops(_ctx(2)) \
        == 2 * 3 * work_nemotron.forward_flops(DIMS, 1, 8192)


def test_host_clock_names_the_late_call():
    """Ten calls 100 ms apart but the sixth, which starts 900 ms late
    while the device goes on: the two after it follow at once.  The
    second thread ticked all the while (the process did not stand
    still) and saw the awaited loss ready 800 ms before the call."""
    from types import SimpleNamespace
    starts = [0.1 * k for k in range(5)] + [1.3, 1.303, 1.306, 1.4, 1.5]
    me = SimpleNamespace(
        _calls=[(-1.0, -0.9)] + [(t, t + 0.002) for t in starts],
        _ticks=[0.02 * k for k in range(100)], _ready_at={3: 0.5})
    said = adapter.Program._host_clock(me, 10)
    assert "every 100.0 ms (median), 2.00 ms inside" in said
    late = eval(said.split("the one before: ")[1])
    assert late == [{
        "call": 5, "ms_after_the_one_before": [900.0, 3.0, 3.0],
        "ms_inside": 2.0, "second_thread_longest_silence_ms": 20.0,
        "awaited_loss_seen_ready_ms_before_the_call": 800.0}]


def test_cell_reads_the_trainers_metrics_and_its_own():
    from perfbench.runners import train as runner
    mine = {"train.ssm_device_ms", "ssm_scan_roofline",
            "train.moe_with_shared_device_ms", "moe_relu2_experts_roofline",
            "train.attn_g16_device_ms", "flash_attn_g16_train_roofline"}
    names = {m["name"] for m in harness.cell_metrics(CELL,
                                                     runner.END_TO_END)}
    # at least these: a later PR may add a metric every training cell reads
    assert names >= mine | {
        "train.step_mfu_pct", "train.device_idle_pct", "train.dispatch_ms",
        "train.h2d_ms", "train.compiles_in_window", "train.optim_device_ms",
        "train.fwd_bwd_device_ms", "train.step_device_ms",
        "train.attn_proj_device_ms", "train.head_loss_device_ms",
        "train.norm_embed_device_ms", "train.unnamed_device_ms",
        "train.unscoped_device_ms"}
    # no dense feed-forward and no dense attention core in this model
    assert not names & {"train.ffn_device_ms", "train.attn_dense_device_ms"}
    for other in ("bert-large.pretrain_b32_l128",
                  "mellum2-12b-a2.5b.causal_b1_l8192"):
        assert not mine & {m["name"] for m in harness.cell_metrics(
            other, runner.END_TO_END)}
    scopes = {s for name in mine for s in harness.load_json(
        "metrics", name + ".json")["params"]["scopes"]}
    assert scopes == {"mx.ssm.in_proj", "mx.ssm.conv", "mx.ssm.scan",
                      "mx.ssm.gate_norm", "mx.ssm.out_proj", "mx.moe.route",
                      "mx.moe.dispatch", "mx.moe.experts", "mx.moe.combine",
                      "mx.moe.shared", "mx.attn.full"}


def test_the_step_carries_every_scope_the_metrics_read():
    """The jaxpr of the toy step names each ``mx.ssm.*`` scope and
    ``mx.moe.shared``, forward and transposed."""
    program, batches = _program()
    t = program.trainer
    tokens = batches[0][0]
    text = str(jax.make_jaxpr(t._step.__wrapped__)(
        t.params, t.opt_state, tokens, tokens[:, 1:]).pretty_print(
            name_stack=True))
    for scope in ("mx.ssm.in_proj", "mx.ssm.conv", "mx.ssm.scan",
                  "mx.ssm.gate_norm", "mx.ssm.out_proj", "mx.moe.shared",
                  "mx.moe.route", "mx.attn.full"):
        assert scope in text, scope
    assert "transpose(jvp(mx.fwd))" in text and "mx.rope" not in text


# ------------------------------------------------------------- rehearsals
def _child(code, timeout=1500):
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
@pytest.mark.parametrize("fault", ["control"] + FAULTS)
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
