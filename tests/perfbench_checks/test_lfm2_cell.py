"""Checks of the ``lfm2-24b-a2b`` configuration and its cell on the CPU
at a small size: the program (``models.get_decoder_lm`` through
``ShardedTrainer``, as the cell's adapter builds it) against the plain
reference; the eight shares of the experts against the uncut layer; the
work functions by hand; and (``slow``) a rehearsal of the cell, its
control and its planted faults."""
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

from perfbench import check, harness, traffic, work_lfm2       # noqa: E402
from perfbench.adapters import lfm2_moe as adapter             # noqa: E402
from perfbench.reference import lfm2_moe as ref                # noqa: E402

CELL = "lfm2-24b-a2b.causal_b1_l8192"
WORK, CFG, MIX = harness.load_cell(CELL)
TOY, DIMS = CFG["toy"], CFG["dims"]
SEED = 2 ** 31 + 3939
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


# Tolerances, with their reasons: the same float32 equations in another
# order (the flash kernels' online softmax against the dense one, the
# sorted grouped products against the loop over experts, one fused
# [gate | up] product against two), whose sums of some hundred float32
# terms differ by a few 1e-7 relative; the loss carries that directly,
# a gradient leaf sums it over 256 positions, and AdamW's
# division by sqrt(v) at step 1 turns a leaf's relative error into a
# change of sign only where the gradient is nought to rounding
# (``check.still_leaves`` leaves those out).  bfloat16 arithmetic misses
# each by an order of magnitude or more.
LOSS_TOL, GRAD_TOL, CHANGE_TOL = 2e-6, 2e-4, 2e-3


def test_the_reference_imports_nothing_of_the_program_or_other_families():
    with open(ref.__file__) as f:
        text = f.read()
    assert "mxnet_tpu" not in text
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert [ln for ln in imports if ln.startswith("from .")] \
        == ["from . import common"]
    # its convolution is the K-term sum over a padded row, no op of jax's
    assert "conv_general" not in text and "jnp.convolve" not in text


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
                               rtol=1e-4, atol=2e-5)
    # the device-side counter counted the reference's held pairs
    held = np.asarray((ids >= TOY["first_expert"])
                      & (ids < TOY["first_expert"] + TOY["experts_held"]))
    counted = [float(aux[n].sum()) for n in program.counters]
    assert counted == held.sum((1, 2)).tolist()
    assert not np.allclose(
        np.asarray(got), np.asarray(ref.forward(
            ref.init_weights(TOY, SEED, jnp.bfloat16), TOY, tokens)[0],
            np.float32), rtol=1e-4, atol=2e-5)


def test_losses_match_the_reference(followed):
    got, want, _low, _sizes = followed
    for k in range(3):
        assert abs(got["losses"][k] - want["losses"][k]) \
            <= LOSS_TOL * want["losses"][k]


def test_first_gradient_matches_the_reference_leaf_by_leaf(followed):
    got, want, low, sizes = followed
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(sizes)
    # every kind of leaf is there, fused ones as their parts; the head
    # has none (it is the embedding's)
    for leaf in ("embed", "l0.in_w.B", "l0.in_w.C", "l0.in_w.u", "l0.conv_w",
                 "l0.out_w", "l0.ffn_w1.gate", "l0.ffn_w1.up", "l0.ffn_w2",
                 "l1.q_norm_g", "l1.k_norm_g", "l1.kv_w.k", "l1.kv_w.v",
                 "l1.w1", "l1.w2", "l1.router_w", "l1.router_bias",
                 "l4.conv_w", "l4.w2"):
        assert leaf in sizes, leaf
    assert not [leaf for leaf in sizes if "head" in leaf]
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
        for leaf in ("embed", "l0.conv_w", "l0.ffn_w2", "l1.q_norm_g",
                     "l1.kv_w.k", "l3.w2"):
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
    """(the reference's weights of one expert layer's feed-forward with
    experts first..first+held-1 of 16, the tokens' vectors, its dims)."""
    dims = dict(SHARE, experts_held=16, first_expert=0, num_layers=1,
                layer_types=["conv"], dense_ffn_layers=0)
    w = ref.init_weights(dims, seed)
    g = {leaf: w[f"l0.{leaf}"] for leaf in ref.FFN_LEAVES["moe"]}
    # weights of a size at which the experts' part is not lost in the
    # residual, a router that tells the experts apart, and a bias that
    # changes what is chosen
    g["w1"], g["w2"] = (20 * g[k][first:first + held] for k in ("w1", "w2"))
    g["router_w"], g["router_bias"] = 50 * g["router_w"], 30 * g["router_bias"]
    m = jax.random.normal(jax.random.PRNGKey(seed), (96, dims["units"]))
    return g, m, dict(dims, experts_held=held, first_expert=first)


def _moe_ffn(g, m, first):
    from mxnet_tpu.ops.moe import moe_ffn
    return moe_ffn(m, g["router_w"], g["w1"], g["w2"], g["router_bias"],
                   experts_per_token=3, first_expert=first,
                   activation="silu", gated=True, scoring="sigmoid",
                   route_scale=SHARE["routed_scaling_factor"],
                   route_eps=SHARE["route_eps"])


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


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """x + the sum over the eight chips of their two experts' parts =
    the uncut reference's feed-forward half of a layer: the residual is
    every chip's and counts once, the router and the normalisation over
    all chosen experts are every chip's, and there is no shared expert
    to count."""
    g, m, dims = _layer(0, 16)
    two = lambda lo: {**g, "w1": g["w1"][lo:lo + 2],         # noqa: E731
                      "w2": g["w2"][lo:lo + 2]}
    with jax.default_matmul_precision("highest"):
        whole = m + ref.moe(g, m, dims)[0]
        parts = [_moe_ffn(two(lo), m, lo) for lo in range(0, 16, 2)]
        # and the reference's own shares
        ref_parts = [ref.moe(two(lo), m, dict(dims, experts_held=2,
                                              first_expert=lo))[0]
                     for lo in range(0, 16, 2)]
    assert len(parts) == 8
    scale = float(jnp.abs(whole).max())
    for total in (m + sum(p[0] for p in parts), m + sum(ref_parts)):
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   rtol=1e-4, atol=1e-5 * scale)
    assert float(jnp.abs(whole - m).max()) > 0.01 * scale
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
    assert ids.shape[-1] - bad_ids.shape[-1] == (fault == "top3")
    gap = float(jnp.abs(sound - broken).max() / jnp.abs(sound).max())
    assert gap > 1e-4, gap          # float32 rounding is 1e-7
    if fault == "no_bias":          # the bias changes what is chosen
        assert bool((np.sort(ids, -1) != np.sort(bad_ids, -1)).any())


def test_the_reference_convolution_one_position_at_a_time():
    """The reference's K-term sum against a loop over positions and
    taps; the fault leaves the oldest tap out."""
    r = np.random.RandomState(0)
    v, w = r.randn(1, 7, 3), r.uniform(-0.5, 0.5, (3, 3))
    want = np.zeros_like(v)
    for t in range(7):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += w[:, j] * v[:, t - 2 + j]
    got = ref.short_conv(jnp.asarray(v, jnp.float32),
                         jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    two = ref.short_conv(jnp.asarray(v, jnp.float32),
                         jnp.asarray(w, jnp.float32), range(1, 3))
    oldest = np.concatenate([np.zeros_like(v[:, :2]), v[:, :-2]], 1) * w[:, 0]
    np.testing.assert_allclose(np.asarray(two), want - oldest, rtol=1e-5,
                               atol=1e-6)


def test_the_seeds_special_leaves_are_as_assumed():
    w = ref.init_weights(TOY, SEED)
    taps = np.asarray(w["l0.conv_w"])
    assert np.abs(taps).max() <= 0.5 and 0.2 < taps.std() < 0.35
    assert "head_w" not in w                        # tied
    for leaf in ("embed", "l0.in_w", "l0.out_w", "l0.ffn_w1", "l0.ffn_w2",
                 "l1.q_w", "l1.o_w", "l1.w1", "l1.w2", "l1.router_w"):
        assert np.asarray(w[leaf]).std() == pytest.approx(0.02, rel=0.1), leaf
    for leaf in ("l1.q_norm_g", "l1.k_norm_g", "l0.op_norm_g",
                 "final_norm_g"):
        assert np.asarray(w[leaf]).mean() == pytest.approx(1.0, abs=0.02)
    bias = np.asarray(w["l1.router_bias"])
    assert 0 < np.abs(bias).max() < 0.05
    assert not (bias == np.asarray(w["l3.router_bias"])).all()
    big = ref.init_weights(TOY, 2 ** 31 + 7)        # seeds pass 2**31
    assert not np.allclose(np.asarray(big["l0.conv_w"]), taps)


# --------------------------------------------------- the data and the work
def test_configuration_file_states_the_cut():
    from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
    pub = _DECODER_CONFIGS["lfm2_24b_a2b"]
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (5, 8, 8192)
    assert CFG["published"] == dict(
        CFG["published"], num_hidden_layers=40, num_experts=64,
        vocab_size=65536, num_dense_layers=2)
    for said in ("8 chips share each layer", "8 a chip", "8,192 of 65,536",
                 "layers 0, 2, 3, 4, 5"):
        assert said in CFG["deployment"], said
    # the catalog row's config, key for key, but the three reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CFG["source"])
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # every width as published: the file, the model zoo and the run agree
    for ours, zoo, dim in (
            ("hidden_size", pub["units"], "units"),
            ("num_attention_heads", pub["num_heads"], "num_heads"),
            ("num_key_value_heads", pub["num_kv_heads"], "num_kv_heads"),
            ("intermediate_size", pub["hidden_size"], "hidden_size"),
            ("moe_intermediate_size", pub["expert_hidden_size"],
             "expert_hidden_size"),
            ("num_experts_per_tok", pub["experts_per_token"],
             "experts_per_token"),
            ("conv_L_cache", pub["conv_kernel"], "conv_kernel"),
            ("routed_scaling_factor", pub["router"]["route_scale"],
             "routed_scaling_factor"),
            ("norm_eps", pub["rms_norm_eps"], "rms_norm_eps")):
        assert CFG[ours] == zoo == DIMS[dim], ours
    assert DIMS["head_dim"] == pub["head_dim"] == 2048 // 32 == 64
    assert DIMS["rope_theta"] == CFG["rope_parameters"]["rope_theta"] \
        == pub["rope"]["full_attention"]["theta"]
    assert DIMS["route_eps"] == pub["router"]["route_eps"] == 1e-6
    assert pub["qk_norm_eps"] == DIMS["rms_norm_eps"]
    # the adapter leaves the model's constants to the zoo, so the toy's
    # copies (the reference reads them) have to be the zoo's too
    for key in ("conv_kernel", "routed_scaling_factor", "route_eps",
                "rms_norm_eps", "rope_theta"):
        assert TOY[key] == DIMS[key], key
    assert pub["tie_embeddings"] and pub["router"]["scoring"] == "sigmoid"
    # the router keeps 64 outputs and 4 a token; 8 experts are held
    assert (DIMS["num_experts"], pub["num_experts"],
            DIMS["experts_per_token"], DIMS["experts_held"]) == (64, 64, 4, 8)
    # the held layers are published layers 0, 2, 3, 4, 5: the zoo's and
    # the file's published pattern agree, and one dense layer leads
    assert list(pub["layer_types"]) == CFG["layer_types"]
    assert DIMS["published_layers"] == [0, 2, 3, 4, 5]
    assert [CFG["layer_types"][i] for i in DIMS["published_layers"]] \
        == DIMS["layer_types"] == TOY["layer_types"] \
        == ["conv", "full_attention", "conv", "conv", "conv"]
    assert DIMS["layer_types"][1:] == CFG["layer_types"][2:6]  # one period
    assert (CFG["num_dense_layers"], pub["dense_ffn_layers"],
            DIMS["dense_ffn_layers"]) == (2, 2, 1)
    assert (DIMS["vocab_size"], DIMS["num_layers"]) == (8192, 5)
    assert DIMS["vocab_size"] * 8 == 65536 and DIMS["experts_held"] * 8 == 64
    sizes = ref.leaf_sizes(DIMS)
    assert round(sum(sizes.values()) / 1e6) == 469
    assert round(12 * sum(sizes.values()) / 1e9, 2) == 5.63
    for said in ("tie_word_embeddings", "conv", "attention", "router",
                 "weights"):
        assert said in CFG["assumed"], said
    assert CFG["optimizer"] == harness.load_json(
        "configs", "mellum2-12b-a2.5b.json")["optimizer"]
    assert MIX == dict(MIX, batch=1, seqlen=8192, masked=0, host_batches=16,
                       warmup_steps=5, reference_rows=1, use_flash=True)
    assert len(WORK["why"]) <= 200


@pytest.mark.parametrize("fn,args,want", [
    (work_lfm2.kinds, (DIMS, "conv"), 4),
    (work_lfm2.kinds, (DIMS, "full_attention"), 1),
    (work_lfm2.expert_layers, (DIMS,), 4),
    (work_lfm2.expert_row_flops, (DIMS,), 6 * 2048 * 1536),
    (work_lfm2.visible_pairs, (8192,), 8192 * 8193 // 2),
    (work_lfm2.gated_conv_token, (DIMS,),
     (3 * 7 * 2048, 4 * 2048 * (4 + 7))),
])
def test_work_counts_by_hand(fn, args, want):
    assert fn(*args) == want


def test_forward_flops_by_hand():
    """406 MFLOP a token: the four conv operators' projections 33%, the
    dense feed-forward 36%, attention 13%, the four expert layers 10%
    (half an expert a token held), the head 8%."""
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048
    attn = 2 * 2048 * (2 * 2048 + 2 * 512) \
        + 2 * 2 * 32 * 64 * (8192 * 8193 // 2) / 8192
    dense = 6 * 2048 * 11776
    routed = 4 * 8 / 64 * 6 * 2048 * 1536
    moe = 2 * 2048 * 64 + routed
    head = 2 * 2048 * 8192 * 8191 / 8192
    total = work_lfm2.forward_flops(DIMS, 1, 8192) / 8192
    assert total == pytest.approx(4 * conv + attn + dense + 4 * moe + head)
    assert [round(x / 1e6) for x in (4 * conv, attn, dense, 4 * moe, head,
                                     total)] == [134, 55, 145, 39, 34, 406]
    assert [round(100 * x / total) for x in
            (4 * conv, dense, attn, 4 * moe, head)] == [33, 36, 13, 10, 8]
    assert round(3 * total * 8192 / 1e12, 1) == 10.0    # TFLOP a step


def _ctx(steps):
    from types import SimpleNamespace
    return SimpleNamespace(dims=DIMS, cfg=CFG, facts={
        "steps": steps, "traffic": {"batch": 1, "seqlen": 8192}})


def test_kernel_work_by_hand():
    ops, nbytes = work_lfm2.gated_conv(_ctx(2))
    tokens = 2 * 8192 * 4
    assert ops == tokens * 3 * 7 * 2048
    assert nbytes == tokens * 4 * 2048 * 11
    # memory-bound on a v5e: 197 TFLOP/s, 819 GB/s
    assert nbytes / 819e9 > 50 * ops / 197e12
    # the one attention layer's flash kernels: 32 query heads of 64 over
    # 8 key/value heads, 8192 * 8193 / 2 visible pairs
    ops, nbytes = work_lfm2.flash_training(_ctx(2))
    assert ops == 2 * 2 * (8192 * 8193 // 2) * 32 * 64 * 6
    assert nbytes == 2 * 2 * 8192 * 64 * 6 * (32 + 8)
    assert ops / 197e12 > nbytes / 819e9                  # compute-bound
    adapter.WINDOW.clear()
    assert work_lfm2.expert_products(_ctx(2)) is None
    adapter.WINDOW.update(steps=2, rows=np.full((4, 8), 1024.0))
    ops, nbytes = work_lfm2.expert_products(_ctx(2))
    rows = 4 * 8 * 1024
    assert ops == 3 * rows * 6 * 2048 * 1536
    assert nbytes == 4 * 3 * (2 * 4 * 8 * 3 * 2048 * 1536
                              + rows * 2 * 2048)
    assert nbytes / 819e9 > ops / 197e12            # the weights bound it
    assert work_lfm2.expert_products(_ctx(3)) is None   # another window
    adapter.WINDOW.clear()
    assert work_lfm2.train_flops(_ctx(2)) \
        == 2 * 3 * work_lfm2.forward_flops(DIMS, 1, 8192)


def test_host_clock_names_the_late_call():
    """Ten calls 100 ms apart but the sixth, which starts 900 ms late."""
    from types import SimpleNamespace
    starts = [0.1 * k for k in range(5)] + [1.3, 1.4, 1.5, 1.6, 1.7]
    said = adapter.Program._host_clock(
        SimpleNamespace(_calls=[-1.0] + starts), 10)
    assert "every 100.0 ms (median, longest 900.0)" in said
    assert said.endswith("call: ms: {5: 900.0}")


def test_cell_reads_the_trainers_metrics_and_its_own():
    from perfbench.runners import train as runner
    mine = {"train.sconv_device_ms", "sconv_gate_roofline",
            "train.dense_ffn_device_ms", "train.moe_top4_device_ms",
            "moe_swiglu1536_experts_roofline", "train.attn_d64_device_ms",
            "flash_attn_d64_train_roofline"}
    names = {m["name"] for m in harness.cell_metrics(CELL,
                                                     runner.END_TO_END)}
    # at least these: a later PR may add a metric every training cell reads
    assert names >= mine | {
        "train.step_mfu_pct", "train.device_idle_pct", "train.dispatch_ms",
        "train.h2d_ms", "train.compiles_in_window", "train.optim_device_ms",
        "train.fwd_bwd_device_ms", "train.step_device_ms",
        "train.attn_proj_device_ms", "train.head_loss_device_ms",
        "train.norm_embed_device_ms", "train.ffn_device_ms",
        "train.unnamed_device_ms", "train.unscoped_device_ms"}
    assert "train.attn_dense_device_ms" not in names    # no dense core here
    for other in ("bert-large.pretrain_b32_l128",
                  "mellum2-12b-a2.5b.causal_b1_l8192",
                  "nemotron-3-nano-30b-a3b.causal_b1_l8192"):
        assert not mine & {m["name"] for m in harness.cell_metrics(
            other, runner.END_TO_END)}
    scopes = {s for name in mine for s in harness.load_json(
        "metrics", name + ".json")["params"]["scopes"]}
    assert scopes == {"mx.sconv.in_proj", "mx.sconv.conv",
                      "mx.sconv.out_proj", "mx.ffn.dense", "mx.moe.route",
                      "mx.moe.dispatch", "mx.moe.experts", "mx.moe.combine",
                      "mx.attn.full", "mx.attn.qk_norm", "mx.rope"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name, not by place: later PRs append entries
    assert CFG["name"] in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"] if m["name"] in mine] == [
        "train.sconv_device_ms", "sconv_gate_roofline",
        "train.dense_ffn_device_ms", "train.moe_top4_device_ms",
        "moe_swiglu1536_experts_roofline", "train.attn_d64_device_ms",
        "flash_attn_d64_train_roofline"]


def test_the_step_carries_every_scope_the_metrics_read():
    """The jaxpr of the toy step names each scope, forward and
    transposed, and carries the embedding's leaf once."""
    program, batches = _program()
    t = program.trainer
    tokens = batches[0][0]
    text = str(jax.make_jaxpr(t._step.__wrapped__)(
        t.params, t.opt_state, tokens, tokens[:, 1:]).pretty_print(
            name_stack=True))
    for scope in ("mx.sconv.in_proj", "mx.sconv.conv", "mx.sconv.out_proj",
                  "mx.ffn.dense", "mx.attn.qk_norm", "mx.rope",
                  "mx.attn.full", "mx.moe.route", "mx.moe.dispatch",
                  "mx.moe.experts", "mx.moe.combine"):
        assert scope in text, scope
    assert "transpose(jvp(mx.fwd))" in text
    assert "mx.attn.window" not in text and "mx.moe.shared" not in text
    assert not [n for n in t.params if "lm_head" in n]
    assert set(program.names) == set(ref.weight_shapes(TOY))


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
    assert "host clock: a call of step() every" in r.stderr
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
