"""Checks of the ``qwen3-next-80b-a3b`` configuration and its cell on the
CPU at a small size: the program (``models.get_decoder_lm`` through
``ShardedTrainer``, as the cell's adapter builds it) against the plain
reference; every planted fault away from it; the four shares of the
experts, the gated shared expert counted once, against the uncut layer;
the work functions by hand; and (``slow``) a rehearsal of the cell, its
control and its planted faults."""
import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from perfbench import check, harness, traffic, work_qwen3_next  # noqa: E402
from perfbench.adapters import qwen3_next as adapter           # noqa: E402
from perfbench.reference import qwen3_next as ref              # noqa: E402

CELL = "qwen3-next-80b-a3b.causal_b1_l8192"
WORK, CFG, MIX = harness.load_cell(CELL)
TOY, DIMS = CFG["toy"], CFG["dims"]
SEED = 2 ** 31 + 4545
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
# order (the chunked delta rule's WY products and triangular inverse
# against the recurrence one position at a time, the flash kernels'
# online softmax against the dense one, the sorted grouped products
# against the loop over experts), whose sums differ by a few 1e-7
# relative; a gradient leaf sums that over 256 positions, and AdamW's
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
    # the delta rule is the recurrence, not the chunked form
    assert "chunk" not in ref.recurrence.__code__.co_varnames
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
    # every kind of leaf is there, fused ones as their parts
    for leaf in ("embed", "head_w", "l0.qkvz_w.q", "l0.qkvz_w.k",
                 "l0.qkvz_w.v", "l0.qkvz_w.z", "l0.ba_w.b", "l0.ba_w.a",
                 "l0.conv_w", "l0.dt_bias", "l0.A_log", "l0.gate_norm_g",
                 "l0.out_w", "l3.q_w.q", "l3.q_w.gate", "l3.kv_w.k",
                 "l3.kv_w.v", "l3.q_norm_g", "l3.k_norm_g", "l3.w1",
                 "l3.shared_w1", "l3.shared_gate_w", "l2.router_w"):
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
    compared)."""
    got, want, _low, _sizes = followed
    assert TOY["train_router"] is False
    for side in (got, want):
        assert side["change_norms"]["l3.router_w"] == 0.0
        assert side["grad_norms"]["l3.router_w"] > 0.0
        for leaf in ("embed", "l0.conv_w", "l0.A_log", "l0.dt_bias",
                     "l0.gate_norm_g", "l3.q_w.gate", "l3.shared_gate_w",
                     "l3.w2"):
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


@pytest.mark.parametrize("fault", FAULTS)
def test_every_planted_fault_is_out_of_tolerance(followed, fault):
    """Each fault, planted in the reference and put in the program's
    place, misses the reference by more than the program is allowed to
    (the tolerances above), by the first gradient or by the change."""
    _got, want, _low, sizes = followed
    batches = traffic.mlm_batches(MIX["toy"], TOY["vocab_size"], SEED)[:3]
    bad = ref.train_steps(TOY, CFG["optimizer"], SEED, batches, 1,
                          fault=fault)
    numbers, _where = check.train_numbers(bad, want, sizes)
    assert numbers["grad_gap"] > 50 * GRAD_TOL \
        or numbers["change_gap"] > 50 * CHANGE_TOL, numbers


# --------------------------------------------------- the shares add up
SHARE = dict(TOY, num_experts=16, experts_per_token=3)


def _layer(first, held, seed=7):
    """(the reference's weights of one expert layer's feed-forward with
    experts first..first+held-1 of 16, the tokens' vectors, its dims)."""
    dims = dict(SHARE, experts_held=16, first_expert=0, num_layers=1,
                layer_types=["full_attention"])
    w = ref.init_weights(dims, seed)
    g = {leaf: w[f"l0.{leaf}"] for leaf in ref.FFN_LEAVES}
    # weights of a size at which the experts' part is not lost beside the
    # shared expert, and a router that tells the experts apart
    g["w1"], g["w2"] = (20 * g[k][first:first + held] for k in ("w1", "w2"))
    g["shared_w1"], g["shared_w2"] = (20 * g[k] for k in ("shared_w1",
                                                          "shared_w2"))
    g["router_w"] = 50 * g["router_w"]
    g["shared_gate_w"] = 30 * g["shared_gate_w"]
    m = jax.random.normal(jax.random.PRNGKey(seed), (96, dims["units"]))
    return g, m, dict(dims, experts_held=held, first_expert=first)


def _moe_ffn(g, m, first):
    from mxnet_tpu.ops.moe import moe_ffn
    return moe_ffn(m, g["router_w"], g["w1"], g["w2"], g["shared_w1"],
                   g["shared_w2"], g["shared_gate_w"], experts_per_token=3,
                   first_expert=first, activation="silu", gated=True,
                   shared_expert=True, shared_gate=True)


def _shared(g, m):
    gate, up = jnp.split(m @ g["shared_w1"], 2, axis=-1)
    return ((jax.nn.silu(gate) * up) @ g["shared_w2"]
            * jax.nn.sigmoid(m @ g["shared_gate_w"]))


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


def test_the_four_shares_add_up_to_the_uncut_layer():
    """x + the sum over the four chips of their four experts' parts + the
    gated shared expert once = the uncut reference's feed-forward half of
    a layer: the residual and the shared expert are every chip's and
    count once, the router and the normalisation over all chosen experts
    are every chip's."""
    g, m, dims = _layer(0, 16)
    four = lambda lo: {**g, "w1": g["w1"][lo:lo + 4],        # noqa: E731
                       "w2": g["w2"][lo:lo + 4]}
    with jax.default_matmul_precision("highest"):
        whole = m + ref.moe(g, m, dims)[0]
        shared = _shared(g, m)
        parts = [_moe_ffn(four(lo), m, lo) for lo in range(0, 16, 4)]
        ref_parts = [ref.moe(four(lo), m, dict(dims, experts_held=4,
                                               first_expert=lo))[0]
                     for lo in range(0, 16, 4)]
    assert len(parts) == 4
    scale = float(jnp.abs(whole).max())
    for total in (m + sum(p[0] - shared for p in parts) + shared,
                  m + sum(p - shared for p in ref_parts) + shared):
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   rtol=1e-4, atol=1e-5 * scale)
    # the routed part and the gated shared part are each a large share
    routed = whole - m - shared
    assert float(jnp.abs(routed).max()) > 0.05 * scale
    assert float(jnp.abs(shared).max()) > 0.05 * scale
    # the gate matters: ungated, the shared part is another
    gate, up = jnp.split(m @ g["shared_w1"], 2, axis=-1)
    ungated = (jax.nn.silu(gate) * up) @ g["shared_w2"]
    assert float(jnp.abs(ungated - shared).max()) > 0.05 * scale
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
    assert ids.shape[-1] - bad_ids.shape[-1] == (fault == "top9")
    gap = float(jnp.abs(sound - broken).max() / jnp.abs(sound).max())
    assert gap > 1e-4, gap          # float32 rounding is 1e-7


def test_the_reference_recurrence_one_position_at_a_time():
    """The reference's scan against a loop over positions in numpy: the
    state forgets, then writes what it does not already predict."""
    r = np.random.RandomState(0)
    b, L, H, dk, dv = 1, 9, 2, 3, 4
    q, k = r.randn(b, L, H, dk), r.randn(b, L, H, dk)
    v, g = r.randn(b, L, H, dv), -np.abs(r.randn(b, L, H))
    beta = 1 / (1 + np.exp(-r.randn(b, L, H)))
    S, want = np.zeros((b, H, dk, dv)), np.zeros((b, L, H, dv))
    for t in range(L):
        for h in range(H):
            s = np.exp(g[0, t, h]) * S[0, h]
            s = s + np.outer(k[0, t, h], beta[0, t, h]
                             * (v[0, t, h] - s.T @ k[0, t, h]))
            S[0, h] = s
            want[0, t, h] = s.T @ q[0, t, h]
    got = ref.recurrence(*(jnp.asarray(a, jnp.float32)
                           for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_the_reference_rotates_the_first_channels_only():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 16))
    cos, sin = ref.rope_tables(dict(rope_theta=1e7), 5, 4)
    got = ref.rotate(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(got[..., 4:]),
                                  np.asarray(x[..., 4:]))
    a, b = x[..., :2], x[..., 2:4]
    c, s = cos[None, :, None], sin[None, :, None]
    np.testing.assert_allclose(np.asarray(got[..., :4]), np.asarray(
        jnp.concatenate([a * c - b * s, b * c + a * s], -1)), rtol=1e-6)


def test_the_seeds_special_leaves_are_as_assumed():
    w = ref.init_weights(TOY, SEED)
    A = np.exp(np.asarray(w["l0.A_log"]))
    assert 0 < A.min() and A.max() < 16
    assert (np.asarray(w["l1.dt_bias"]) == 1).all()
    taps = np.asarray(w["l0.conv_w"])
    assert np.abs(taps).max() <= 0.5 and 0.2 < taps.std() < 0.35
    for leaf in ("head_w", "l0.qkvz_w", "l0.ba_w", "l0.out_w", "l3.q_w",
                 "l3.o_w", "l3.w1", "l3.w2", "l3.router_w", "l3.shared_w1"):
        assert np.asarray(w[leaf]).std() == pytest.approx(0.02, rel=0.1), leaf
    assert np.asarray(w["embed"]).std() == pytest.approx(
        TOY["embed_std"], rel=0.1)
    for leaf in ("l3.q_norm_g", "l3.k_norm_g", "l0.op_norm_g",
                 "l0.gate_norm_g", "final_norm_g"):
        assert np.asarray(w[leaf]).mean() == pytest.approx(1.0, abs=0.02)
    big = ref.init_weights(TOY, 2 ** 31 + 7)        # seeds pass 2**31
    assert not np.allclose(np.asarray(big["l0.conv_w"]), taps)


# --------------------------------------------------- the data and the work
def test_configuration_file_states_the_cut():
    from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
    pub = _DECODER_CONFIGS["qwen3_next_80b_a3b"]
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (4, 16, 18992)
    assert CFG["published"] == dict(
        CFG["published"], num_hidden_layers=48, num_experts=512,
        vocab_size=151936)
    for said in ("32 chips share each layer", "16 a chip",
                 "18,992 of 151,936", "layers 0, 1, 2, 3", "18.03 GiB"):
        assert said in CFG["deployment"], said
    # the catalog row's config, key for key, but the three reduced; the
    # catalog (architectures.jsonl) is read where MODEL_CATALOG points
    catalog = os.environ.get("MODEL_CATALOG")
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == CFG["source"])
        for key, value in row["config"].items():
            if key not in CFG["reduced"]:
                assert CFG[key] == value, key
    # every width as published: the file, the model zoo and the run agree
    gdn = pub["gdn"]
    for ours, zoo, dim in (
            ("hidden_size", pub["units"], "units"),
            ("num_attention_heads", pub["num_heads"], "num_heads"),
            ("num_key_value_heads", pub["num_kv_heads"], "num_kv_heads"),
            ("head_dim", pub["head_dim"], "head_dim"),
            ("moe_intermediate_size", pub["expert_hidden_size"],
             "expert_hidden_size"),
            ("shared_expert_intermediate_size",
             pub["shared_expert_hidden_size"], "shared_expert_hidden_size"),
            ("num_experts_per_tok", pub["experts_per_token"],
             "experts_per_token"),
            ("linear_num_key_heads", gdn["key_heads"], "linear_key_heads"),
            ("linear_num_value_heads", gdn["value_heads"],
             "linear_value_heads"),
            ("linear_key_head_dim", gdn["key_dim"], "linear_key_dim"),
            ("linear_value_head_dim", gdn["value_dim"], "linear_value_dim"),
            ("linear_conv_kernel_dim", gdn["conv_kernel"], "conv_kernel"),
            ("rms_norm_eps", pub["rms_norm_eps"], "rms_norm_eps"),
            ("rope_theta", pub["rope"]["full_attention"]["theta"],
             "rope_theta")):
        assert CFG[ours] == zoo == DIMS[dim], ours
    assert DIMS["rotary_dim"] == pub["rotary_dim"] \
        == CFG["head_dim"] * CFG["partial_rotary_factor"] == 64
    assert pub["qk_norm_eps"] == gdn["norm_eps"] == DIMS["rms_norm_eps"]
    assert pub["attention_out_gate"] and pub["shared_expert_gate"]
    assert not pub.get("tie_embeddings") and not CFG["tie_word_embeddings"]
    assert gdn["chunk"] == DIMS["chunk"]
    # the adapter leaves the model's constants to the zoo, so the toy's
    # copies (the reference reads them) have to be the zoo's too
    for key in ("rms_norm_eps", "rope_theta", "conv_kernel", "embed_std"):
        assert TOY[key] == DIMS[key], key
    assert TOY["rotary_dim"] * 4 == TOY["head_dim"]
    # the router keeps 512 outputs and 10 a token; 16 experts are held
    assert (DIMS["num_experts"], pub["num_experts"],
            DIMS["experts_per_token"], DIMS["experts_held"]) \
        == (512, 512, 10, 16)
    # the held layers are published layers 0-3, one whole period
    interval = CFG["full_attention_interval"]
    published = ["full_attention" if (i + 1) % interval == 0
                 else "linear_attention" for i in range(48)]
    assert list(pub["layer_types"]) == published
    assert DIMS["published_layers"] == [0, 1, 2, 3]
    assert [published[i] for i in DIMS["published_layers"]] \
        == DIMS["layer_types"] == TOY["layer_types"] \
        == ["linear_attention"] * 3 + ["full_attention"]
    assert DIMS["vocab_size"] * 8 == 151936 and DIMS["experts_held"] * 32 \
        == 512
    sizes = ref.leaf_sizes(DIMS)
    assert round(sum(sizes.values()) / 1e6, 1) == 424.3
    assert round(12 * sum(sizes.values()) / 1e9, 2) == 5.09
    for said in ("norms", "gated_deltanet", "attention", "router",
                 "experts", "weights"):
        assert said in CFG["assumed"], said
    assert "1 + w" in CFG["assumed"]["norms"]
    assert any("multi-token" in d for d in CFG["departures"])
    assert any("[q | k | v | z]" in d for d in CFG["departures"])
    assert CFG["optimizer"] == harness.load_json(
        "configs", "mellum2-12b-a2.5b.json")["optimizer"]
    assert MIX == dict(MIX, batch=1, seqlen=8192, masked=0, host_batches=16,
                       warmup_steps=5, reference_rows=1, use_flash=True)
    assert len(WORK["why"]) <= 200


@pytest.mark.parametrize("fn,args,want", [
    (work_qwen3_next.kinds, (DIMS, "linear_attention"), 3),
    (work_qwen3_next.kinds, (DIMS, "full_attention"), 1),
    (work_qwen3_next.expert_row_flops, (DIMS,), 6 * 2048 * 512),
    (work_qwen3_next.visible_pairs, (8192,), 8192 * 8193 // 2),
])
def test_work_counts_by_hand(fn, args, want):
    assert fn(*args) == want


def test_delta_rule_flops_by_hand():
    """A chunk of 64: per key head K K^T and Q K^T; per value head the
    inverse's 10 products of 64^3, W and the writes, the state's three
    products and the masked one; 128 chunks a row."""
    Q, dk, dv = 64, 128, 128
    key = 2 * 2 * Q * Q * dk
    value = (2 * (int(math.log2(Q)) - 1) * 2 * Q ** 3 + 2 * Q * Q * (dk + dv)
             + 3 * 2 * Q * dk * dv + 2 * Q * Q * dv)
    assert work_qwen3_next.delta_rule_flops(DIMS, 8192) \
        == 128 * (16 * key + 32 * value)
    # a ragged last chunk is counted whole
    assert work_qwen3_next.delta_rule_flops(DIMS, 8193) \
        == 129 * (16 * key + 32 * value)


def test_forward_flops_by_hand():
    """467 MFLOP a token: the DeltaNet mixers' projections 43%, attention
    26%, the head 17%, the four expert layers 9% (the shared expert and
    a third of an expert a token held), the chunked rule 5%."""
    C = 2048
    proj = 2 * C * (2 * 2048 + 2 * 4096 + 64 + 4096)
    rule = work_qwen3_next.delta_rule_flops(DIMS, 8192) / 8192
    attn = (2 * C * (2 * 4096 + 2 * 512 + 4096)
            + 2 * 2 * 16 * 256 * (8192 * 8193 // 2) / 8192)
    moe = 2 * C * 512 + 10 * 16 / 512 * 6 * C * 512 + 6 * C * 512 + 2 * C
    head = 2 * C * 18992 * 8191 / 8192
    total = work_qwen3_next.forward_flops(DIMS, 1, 8192) / 8192
    assert total == pytest.approx(3 * (proj + rule) + attn + 4 * moe + head)
    assert [round(x / 1e6, 1) for x in (3 * proj, 3 * rule, attn, 4 * moe,
                                        head, total)] \
        == [202.1, 23.6, 121.6, 41.4, 77.8, 466.6]
    assert round(3 * total * 8192 / 1e12, 2) == 11.47    # TFLOP a step


def _ctx(steps):
    from types import SimpleNamespace
    return SimpleNamespace(dims=DIMS, cfg=CFG, facts={
        "steps": steps, "traffic": {"batch": 1, "seqlen": 8192}})


def test_kernel_work_by_hand():
    ops, nbytes = work_qwen3_next.gdn_chunk(_ctx(2))
    rows = 2 * 3
    assert ops == rows * 3 * work_qwen3_next.delta_rule_flops(DIMS, 8192)
    operands = 2 * 16 * 128 + 32 * 128 + 2 * 32
    assert nbytes == rows * 4 * 8192 * (3 * operands + 2 * 32 * 128)
    # bytes bound it on a v5e at the peak (197 TFLOP/s, 819 GB/s): five
    # float32 operands in, o out, and their gradients back
    assert nbytes / 819e9 > ops / 197e12 > 0.5 * nbytes / 819e9
    # the one attention layer's flash kernels: 16 query heads of 256 over
    # 2 key/value heads, 8192 * 8193 / 2 visible pairs
    ops, nbytes = work_qwen3_next.flash_training(_ctx(2))
    assert ops == 2 * 2 * (8192 * 8193 // 2) * 16 * 256 * 6
    assert nbytes == 2 * 2 * 8192 * 256 * 6 * (16 + 2)
    assert ops / 197e12 > nbytes / 819e9                  # compute-bound
    adapter.WINDOW.clear()
    assert work_qwen3_next.expert_products(_ctx(2)) is None
    adapter.WINDOW.update(steps=2, rows=np.full((4, 16), 160.0))
    ops, nbytes = work_qwen3_next.expert_products(_ctx(2))
    rows = 4 * 16 * 160
    assert ops == 3 * rows * 6 * 2048 * 512
    assert nbytes == 4 * 3 * (2 * 4 * 16 * 3 * 2048 * 512 + rows * 2 * 2048)
    assert nbytes / 819e9 > ops / 197e12            # the weights bound it
    assert work_qwen3_next.expert_products(_ctx(3)) is None   # another window
    adapter.WINDOW.clear()
    assert work_qwen3_next.train_flops(_ctx(2)) \
        == 2 * 3 * work_qwen3_next.forward_flops(DIMS, 1, 8192)


def test_cell_reads_the_trainers_metrics_and_its_own():
    from perfbench.runners import train as runner
    mine = {"train.gdn_device_ms", "gdn_chunk_roofline",
            "train.attn_gated_d256_device_ms",
            "flash_attn_d256_train_roofline", "train.moe_top10_device_ms",
            "moe_swiglu512_experts_roofline"}
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
    # no dense feed-forward and no dense attention core here
    assert not {"train.ffn_device_ms", "train.attn_dense_device_ms"} & names
    for other in ("bert-large.pretrain_b32_l128",
                  "mellum2-12b-a2.5b.causal_b1_l8192",
                  "nemotron-3-nano-30b-a3b.causal_b1_l8192",
                  "lfm2-24b-a2b.causal_b1_l8192"):
        assert not mine & {m["name"] for m in harness.cell_metrics(
            other, runner.END_TO_END)}
    scopes = {s for name in mine for s in harness.load_json(
        "metrics", name + ".json")["params"]["scopes"]}
    assert scopes == {"mx.gdn.in_proj", "mx.gdn.conv", "mx.gdn.core",
                      "mx.gdn.gate_norm", "mx.gdn.out_proj", "mx.attn.full",
                      "mx.attn.qk_norm", "mx.rope", "mx.attn.out_gate",
                      "mx.moe.route", "mx.moe.dispatch", "mx.moe.experts",
                      "mx.moe.combine", "mx.moe.shared"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name, not by place: later PRs append entries
    assert CFG["name"] in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in mine:
        assert entries[name]["workloads"] == [CELL], name
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "train_tokens_per_s")
    assert CELL in tokens["workloads"]


def test_the_step_carries_every_scope_the_metrics_read():
    """The jaxpr of the toy step names each scope, forward and
    transposed."""
    program, batches = _program()
    t = program.trainer
    tokens = batches[0][0]
    text = str(jax.make_jaxpr(t._step.__wrapped__)(
        t.params, t.opt_state, tokens, tokens[:, 1:]).pretty_print(
            name_stack=True))
    for scope in ("mx.gdn.in_proj", "mx.gdn.conv", "mx.gdn.core",
                  "mx.gdn.gate_norm", "mx.gdn.out_proj", "mx.attn.qk_norm",
                  "mx.rope", "mx.attn.full", "mx.attn.out_gate",
                  "mx.moe.route", "mx.moe.dispatch", "mx.moe.experts",
                  "mx.moe.combine", "mx.moe.shared"):
        assert scope in text, scope
    assert "transpose(jvp(mx.fwd))" in text
    assert "mx.attn.window" not in text and "mx.ffn.dense" not in text
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
    assert "qwen3_next: " in r.stderr and "rows routed to held" in r.stderr
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
