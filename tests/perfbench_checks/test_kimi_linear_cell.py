"""Checks of the ``kimi-linear-48b-a3b`` configuration and its cell on the
CPU at a small size: the program (``models.get_decoder_lm`` through
``ShardedTrainer``, as the cell's adapter builds it) against the plain
reference; the KDA mixer and the latent attention block against the
reference's layers; every planted fault away from the reference; the 32
shares of the experts, the shared expert counted once, against the
uncut layer; the work functions by hand; and (``slow``) a rehearsal of
the cell, its control and its planted faults."""
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

from perfbench import check, harness, traffic, work_kimi_linear  # noqa: E402
from perfbench.adapters import kimi_linear as adapter            # noqa: E402
from perfbench.reference import kimi_linear as ref               # noqa: E402

CELL = "kimi-linear-48b-a3b.causal_b1_l8192"
WORK, CFG, MIX = harness.load_cell(CELL)
TOY, DIMS = CFG["toy"], CFG["dims"]
SEED = 2 ** 31 + 4747
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
# order (the chunked per-channel rule's sub-chunk products and triangular
# inverse against the recurrence one position at a time, the flash
# kernels' online softmax against the dense one, the sorted grouped
# products against the loop over experts), whose sums differ by a few
# 1e-7 relative; a gradient leaf sums that over 256 positions, and
# AdamW's division by sqrt(v) at step 1 turns a leaf's relative error
# into a change of sign only where the gradient is nought to rounding
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


def _layer_input(seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, 96, TOY["units"]))


def test_the_kda_mixer_is_the_reference_layer():
    """``ops.deltanet.kda_mixer`` with the reference's weights of layer 0
    against ``reference.kda`` (the recurrence one position at a time),
    value and the gradient in the input and every weight; chunks of 32
    over 96 positions, sub-chunks of 16.  The decays' own leaves (A_log,
    dt_bias and the low-rank pair before them) within 2e-3: the seed's
    decays are -0.7 to -11 a step, so a chunk's running sum reaches
    several hundred and the differences of two such sums carry an
    absolute rounding of about 1e-5, which the recurrence, taking each
    decay as it comes, does not (against float64 the program's A_log
    gradient is 1e-4 off, the recurrence's 4e-6); every other leaf is
    the same float32 sums in another order, 2e-5."""
    from mxnet_tpu.ops.deltanet import kda_mixer
    w = ref.init_weights(TOY, SEED)
    g = {leaf: w[f"l0.{leaf}"] for leaf in ref.MIXER_LEAVES["kda"]}
    names = ("qkv_w", "fa_w", "fb_w", "b_w", "ga_w", "gb_w", "conv_w",
             "A_log", "dt_bias", "gate_norm_g", "out_w")
    x = _layer_input()
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def program(x, *leaves):
        return kda_mixer(x, *leaves, heads=TOY["kda_heads"],
                         head_dim=TOY["kda_head_dim"], chunk=TOY["chunk"],
                         eps=TOY["rms_norm_eps"])

    def plain(x, *leaves):
        return ref.kda(dict(zip(names, leaves)), x, TOY)

    leaves = [g[n] for n in names]
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: (program(*a) * cot).sum(),
                                 argnums=range(12))(x, *leaves)
        want = jax.value_and_grad(lambda *a: (plain(*a) * cot).sum(),
                                  argnums=range(12))(x, *leaves)
    assert abs(got[0] - want[0]) <= 1e-5 * abs(want[0])
    decays = ("fa_w", "fb_w", "A_log", "dt_bias")
    for name, a, b in zip(("x",) + names, got[1], want[1]):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < (2e-3 if name in decays else 2e-5), (name, gap)
    # the decay a key channel is what the layer reads: GDN's scalar, the
    # mean over the channels, is another layer (at the seed's weights a
    # head's channels decay within a few per cent of each other: 1e-4)
    with jax.default_matmul_precision("highest"):
        sound = plain(x, *leaves)
        scalar = ref.kda(dict(zip(names, leaves)), x, TOY, "scalar_decay")
    assert float(jnp.abs(scalar - sound).max()) \
        > 3e-5 * float(jnp.abs(sound).max())


def test_the_latent_attention_block_is_the_reference_layer():
    """``LatentAttention`` at q/k heads of 12 and v heads of 8 (the toy's
    192 and 128) through the flash kernels in float32, with the
    reference's weights of layer 3, against ``reference.mla``."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.transformer_blocks import LatentAttention
    w = ref.init_weights(TOY, SEED)
    block = LatentAttention(
        TOY["units"], TOY["num_heads"], TOY["qk_nope_dim"],
        TOY["qk_rope_dim"], TOY["v_head_dim"], TOY["kv_lora_rank"],
        latent_eps=TOY["rms_norm_eps"], compute_dtype="float32")
    block.initialize(mx.init.Zero())
    leaf = {"q_proj": "q_w", "kv_a_proj": "kva_w", "kv_norm": "kv_norm_g",
            "kv_b_proj": "kvb_w", "out_proj": "o_w"}
    for name, p in block.collect_params().items():
        part = next(k for k in leaf if f"_{k}_" in name)
        p.set_data(nd.array(np.asarray(w[f"l3.{leaf[part]}"])))
    x = _layer_input()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(block(nd.array(np.asarray(x))).asnumpy())
        want = np.asarray(ref.mla({v: w[f"l3.{v}"] for v in leaf.values()},
                                  x, TOY))
        for fault in ("rotary", "k_pe_per_head", "no_latent_norm"):
            bad = np.asarray(ref.mla({v: w[f"l3.{v}"]
                                      for v in leaf.values()}, x, TOY, fault))
            assert np.abs(bad - want).max() > 1e-3 * np.abs(want).max(), fault
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())


def test_losses_match_the_reference(followed):
    got, want, _low, _sizes = followed
    for k in range(3):
        assert abs(got["losses"][k] - want["losses"][k]) \
            <= LOSS_TOL * want["losses"][k]


def test_first_gradient_matches_the_reference_leaf_by_leaf(followed):
    got, want, low, sizes = followed
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(sizes)
    # every kind of leaf is there, fused ones as their parts
    for leaf in ("embed", "head_w", "l0.qkv_w.q", "l0.qkv_w.k", "l0.qkv_w.v",
                 "l0.fa_w", "l0.fb_w", "l0.b_w", "l0.ga_w", "l0.gb_w",
                 "l0.conv_w", "l0.A_log", "l0.dt_bias", "l0.gate_norm_g",
                 "l0.out_w", "l0.ffn_w1.gate", "l0.ffn_w2", "l3.q_w.nope",
                 "l3.q_w.pe", "l3.kva_w.c", "l3.kva_w.pe", "l3.kv_norm_g",
                 "l3.kvb_w.nope", "l3.kvb_w.v", "l3.o_w", "l3.w1",
                 "l3.shared_w1.gate", "l2.router_w", "l2.router_bias"):
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
    compared), and the choice bias is no weight."""
    got, want, _low, _sizes = followed
    assert TOY["train_router"] is False
    for side in (got, want):
        assert side["change_norms"]["l3.router_w"] == 0.0
        assert side["change_norms"]["l3.router_bias"] == 0.0
        assert side["grad_norms"]["l3.router_w"] > 0.0
        for leaf in ("embed", "l0.conv_w", "l0.A_log", "l0.dt_bias",
                     "l0.gate_norm_g", "l0.fb_w", "l3.kv_norm_g",
                     "l3.kvb_w.v", "l3.shared_w2", "l3.w2", "l0.ffn_w2"):
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
    place, misses the reference by ten times what the program is allowed
    to (the tolerances above) or more, by the first gradient or by the
    change.  (At the toy's width of 64 the scores are nearly flat, so
    rotary over the 4 pe channels moves the first gradient 12 times the
    tolerance and the change 19 times: the weakest fault here.)"""
    _got, want, _low, sizes = followed
    batches = traffic.mlm_batches(MIX["toy"], TOY["vocab_size"], SEED)[:3]
    bad = ref.train_steps(TOY, CFG["optimizer"], SEED, batches, 1,
                          fault=fault)
    numbers, _where = check.train_numbers(bad, want, sizes)
    assert numbers["grad_gap"] > 10 * GRAD_TOL \
        or numbers["change_gap"] > 10 * CHANGE_TOL, numbers


# --------------------------------------------------- the shares add up
SHARES, EXPERTS = 32, 256           # the deployment's: 8 experts a chip


def _layer(seed=7):
    """(the reference's weights of one expert layer's feed-forward with
    all 256 experts, the tokens' vectors, its dims)."""
    dims = dict(TOY, num_experts=EXPERTS, experts_per_token=8,
                experts_held=EXPERTS, first_expert=0, num_layers=2,
                layer_types=["kda", "kda"])
    w = ref.init_weights(dims, seed)
    g = {leaf: w[f"l1.{leaf}"] for leaf in ref.FFN_LEAVES["moe"]}
    # weights of a size at which the experts' part is not lost beside the
    # shared expert, and a router that tells the experts apart
    for k in ("w1", "w2", "shared_w1", "shared_w2"):
        g[k] = 20 * g[k]
    g["router_w"] = 50 * g["router_w"]
    m = jax.random.normal(jax.random.PRNGKey(seed), (48, dims["units"]))
    return g, m, dims


def _moe_ffn(g, m, first, n):
    from mxnet_tpu.ops.moe import moe_ffn
    return moe_ffn(m, g["router_w"], g["w1"][first:first + n],
                   g["w2"][first:first + n], g["router_bias"],
                   g["shared_w1"], g["shared_w2"], experts_per_token=8,
                   first_expert=first, activation="silu", gated=True,
                   scoring="sigmoid", route_scale=TOY["routed_scaling_factor"],
                   shared_expert=True)


def test_the_32_shares_add_up_to_the_uncut_layer():
    """x + the sum over the 32 chips of their 8 experts' parts + the
    shared expert once = the uncut reference's feed-forward half of a
    layer: the residual and the shared expert are every chip's and count
    once, the router, its choice bias and the normalisation over all 8
    chosen experts are every chip's.  The program's shares and the
    reference's share of chip 5 agree too."""
    g, m, dims = _layer()
    n = EXPERTS // SHARES
    with jax.default_matmul_precision("highest"):
        whole = m + ref.moe(g, m, dims)[0]
        shared = ref.swiglu_columns(m, g["shared_w1"], g["shared_w2"])
        parts = [_moe_ffn(g, m, lo, n) for lo in range(0, EXPERTS, n)]
        share = ref.moe({**g, "w1": g["w1"][5 * n:6 * n],
                         "w2": g["w2"][5 * n:6 * n]}, m,
                        dict(dims, experts_held=n, first_expert=5 * n))[0]
    assert len(parts) == SHARES
    scale = float(jnp.abs(whole).max())
    total = m + sum(p[0] - shared for p in parts) + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(parts[5][0]), np.asarray(share),
                               rtol=1e-4, atol=1e-5 * scale)
    # the routed part and the shared part are each a large share
    routed = whole - m - shared
    assert float(jnp.abs(routed).max()) > 0.05 * scale
    assert float(jnp.abs(shared).max()) > 0.05 * scale
    # every routed pair was computed by exactly one chip
    assert sum(float(p[1].sum()) for p in parts) == 48 * 8
    # the scale matters: without it the routed part is another
    unscaled = ref.moe(g, m, dims, "no_scale")[0] - shared
    assert float(jnp.abs(unscaled - routed).max()) > 0.05 * scale


_FAULT_LAYER = {"scalar_decay": (0, "kda"), "silu_gate": (0, "kda"),
                "norm_after_gate": (0, "kda"), "rotary": (3, "mla"),
                "k_pe_per_head": (3, "mla"), "no_latent_norm": (3, "mla"),
                "no_scale": (1, "moe")}


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_change_the_reference(fault):
    """The layer a fault is planted in gives another output, by 300 times
    float32's rounding (1e-7) or more: a layer's part of the residual
    stream, not the stream (the embedding's rows of size 1 dwarf it at
    the toy's width).  The weakest is the decay averaged over a head's
    channels, which at the seed's weights differ by a few per cent
    (1.2e-4)."""
    assert set(_FAULT_LAYER) == set(FAULTS)
    i, kind = _FAULT_LAYER[fault]
    w = ref.init_weights(TOY, SEED)
    g = {leaf: w[f"l{i}.{leaf}"] for leaf in ref.layer_leaves(TOY, i)}
    x = _layer_input()
    with jax.default_matmul_precision("highest"):
        if kind == "moe":
            layer = lambda f: ref.moe(                      # noqa: E731
                g, x.reshape(-1, TOY["units"]), TOY, f)[0]
        else:
            layer = lambda f: getattr(ref, kind)(g, x, TOY, f)  # noqa: E731
        sound, broken = layer(None), layer(fault)
    gap = float(jnp.abs(sound - broken).max() / jnp.abs(sound).max())
    assert gap > 3e-5, gap


def test_the_reference_recurrence_one_position_at_a_time():
    """The reference's scan against a loop over positions in numpy: each
    key channel forgets by its own decay, then the delta rule writes."""
    r = np.random.RandomState(0)
    b, L, H, dk, dv = 1, 9, 2, 3, 4
    q, k = r.randn(b, L, H, dk), r.randn(b, L, H, dk)
    v, g = r.randn(b, L, H, dv), -np.abs(r.randn(b, L, H, dk))
    beta = 1 / (1 + np.exp(-r.randn(b, L, H)))
    S, want = np.zeros((b, H, dk, dv)), np.zeros((b, L, H, dv))
    for t in range(L):
        for h in range(H):
            s = np.diag(np.exp(g[0, t, h])) @ S[0, h]
            s = s + np.outer(k[0, t, h], beta[0, t, h]
                             * (v[0, t, h] - s.T @ k[0, t, h]))
            S[0, h] = s
            want[0, t, h] = s.T @ q[0, t, h]
    got = ref.recurrence(*(jnp.asarray(a, jnp.float32)
                           for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_the_seeds_special_leaves_are_as_assumed():
    w = ref.init_weights(TOY, SEED)
    A = np.exp(np.asarray(w["l0.A_log"]))
    assert 1 <= A.min() and A.max() <= 16
    assert (np.asarray(w["l1.dt_bias"]) == 0).all()
    taps = np.asarray(w["l0.conv_w"])
    assert np.abs(taps).max() <= 0.5 and 0.2 < taps.std() < 0.35
    assert np.asarray(w["l1.router_bias"]).std() == pytest.approx(0.01,
                                                                  rel=0.3)
    for leaf in ("head_w", "l0.qkv_w", "l0.fa_w", "l0.out_w", "l3.q_w",
                 "l3.kvb_w", "l3.o_w", "l3.w1", "l3.w2", "l3.router_w",
                 "l3.shared_w1", "l0.ffn_w1"):
        assert np.asarray(w[leaf]).std() == pytest.approx(0.02, rel=0.1), leaf
    assert np.asarray(w["embed"]).std() == pytest.approx(
        TOY["embed_std"], rel=0.1)
    for leaf in ("l3.kv_norm_g", "l0.op_norm_g", "l0.gate_norm_g",
                 "final_norm_g"):
        assert np.asarray(w[leaf]).mean() == pytest.approx(1.0, abs=0.02)
    big = ref.init_weights(TOY, 2 ** 31 + 7)        # seeds pass 2**31
    assert not np.allclose(np.asarray(big["l0.conv_w"]), taps)


# --------------------------------------------------- the data and the work
def test_configuration_file_states_the_cut():
    from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
    pub = _DECODER_CONFIGS["kimi_linear_48b_a3b"]
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (5, 8, 20480)
    assert CFG["published"] == dict(
        CFG["published"], num_hidden_layers=27, num_experts=256,
        vocab_size=163840)
    for said in ("32 chips share each layer", "8 a chip",
                 "20,480 of 163,840", "layers 1-5", "602.4 M",
                 "7.23 GB"):
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
    kda, mla = pub["kda"], pub["mla"]
    lin = CFG["linear_attn_config"]
    for ours, zoo, dim in (
            ("hidden_size", pub["units"], "units"),
            ("num_attention_heads", pub["num_heads"], "num_heads"),
            ("qk_nope_head_dim", mla["qk_nope_dim"], "qk_nope_dim"),
            ("qk_rope_head_dim", mla["qk_rope_dim"], "qk_rope_dim"),
            ("v_head_dim", mla["v_head_dim"], "v_head_dim"),
            ("kv_lora_rank", mla["kv_lora_rank"], "kv_lora_rank"),
            ("intermediate_size", pub["hidden_size"], "hidden_size"),
            ("first_k_dense_replace", pub["dense_ffn_layers"],
             "dense_ffn_layers"),
            ("moe_intermediate_size", pub["expert_hidden_size"],
             "expert_hidden_size"),
            ("moe_intermediate_size", pub["shared_expert_hidden_size"],
             "shared_expert_hidden_size"),
            ("num_experts_per_token", pub["experts_per_token"],
             "experts_per_token"),
            ("routed_scaling_factor", pub["router"]["route_scale"],
             "routed_scaling_factor"),
            ("rms_norm_eps", pub["rms_norm_eps"], "rms_norm_eps")):
        assert CFG[ours] == zoo == DIMS[dim], ours
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) \
        == (kda["heads"], kda["head_dim"], kda["conv_kernel"]) \
        == (DIMS["kda_heads"], DIMS["kda_head_dim"], DIMS["conv_kernel"])
    assert kda["norm_eps"] == mla["latent_eps"] == DIMS["rms_norm_eps"]
    assert pub["router"]["scoring"] == "sigmoid" \
        and CFG["moe_router_activation_func"] == "sigmoid"
    assert CFG["moe_renormalize"] and CFG["mla_use_nope"]
    assert CFG["num_shared_experts"] == 1 and CFG["num_expert_group"] == 1
    assert not pub.get("tie_embeddings") and not CFG["tie_word_embeddings"]
    assert kda["chunk"] == DIMS["chunk"]
    # the adapter leaves the model's constants to the zoo, so the toy's
    # copies (the reference reads them) have to be the zoo's too
    for key in ("rms_norm_eps", "conv_kernel", "embed_std",
                "routed_scaling_factor", "dense_ffn_layers"):
        assert TOY[key] == DIMS[key], key
    assert TOY["qk_nope_dim"] + TOY["qk_rope_dim"] != TOY["v_head_dim"]
    # the router keeps 256 outputs and 8 a token; 8 experts are held
    assert (DIMS["num_experts"], pub["num_experts"],
            DIMS["experts_per_token"], DIMS["experts_held"]) \
        == (256, 256, 8, 8)
    # the held layers are published layers 1-5, the leading dense one and
    # one whole period of expert layers
    published = ["mla" if i + 1 in lin["full_attn_layers"] else "kda"
                 for i in range(27)]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) \
        == list(range(1, 28))
    assert list(pub["layer_types"]) == published
    assert DIMS["published_layers"] == [0, 1, 2, 3, 4]
    assert [published[i] for i in DIMS["published_layers"]] \
        == DIMS["layer_types"] == TOY["layer_types"] \
        == ["kda"] * 3 + ["mla", "kda"]
    assert DIMS["vocab_size"] * 8 == 163840 and DIMS["vocab_size"] % 128 == 0
    assert DIMS["experts_held"] * 32 == 256
    sizes = ref.leaf_sizes(DIMS)
    assert round(sum(sizes.values()) / 1e6, 1) == 602.4
    assert round(12 * sum(sizes.values()) / 1e9, 2) == 7.23
    for said in ("norms", "kda", "mla", "router", "experts", "weights"):
        assert said in CFG["assumed"], said
    assert "no bias" in CFG["assumed"]["kda"]
    assert "no rotary" in CFG["assumed"]["mla"]
    assert CFG["optimizer"] == harness.load_json(
        "configs", "mellum2-12b-a2.5b.json")["optimizer"]
    assert MIX == dict(MIX, batch=1, seqlen=8192, masked=0, host_batches=16,
                       warmup_steps=5, reference_rows=1, use_flash=True)
    assert len(WORK["why"]) <= 200


@pytest.mark.parametrize("fn,args,want", [
    (work_kimi_linear.kinds, (DIMS, "kda"), 4),
    (work_kimi_linear.kinds, (DIMS, "mla"), 1),
    (work_kimi_linear.visible_pairs, (8192,), 8192 * 8193 // 2),
    (work_kimi_linear.kda_projection_flops, (DIMS,),
     2 * 2304 * (3 * 4096 + 2 * 128 + 32 + 4096) + 2 * 2 * 128 * 4096),
    (work_kimi_linear.mla_projection_flops, (DIMS,),
     2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304)),
])
def test_work_counts_by_hand(fn, args, want):
    assert fn(*args) == want


def test_kda_rule_flops_by_hand():
    """A chunk of 64, a head of 128: K K^T and Q K^T, the inverse's 10
    products of 64^3, W and the writes, the state's three products and
    the masked one; 32 heads, 128 chunks a row."""
    Q, d = 64, 128
    head = (2 * 2 * Q * Q * d + 2 * (int(math.log2(Q)) - 1) * 2 * Q ** 3
            + 2 * 2 * Q * Q * d + 3 * 2 * Q * d * d + 2 * Q * Q * d)
    assert work_kimi_linear.kda_rule_flops(DIMS, 8192) == 128 * 32 * head
    # a ragged last chunk is counted whole
    assert work_kimi_linear.kda_rule_flops(DIMS, 8193) == 129 * 32 * head


def test_forward_flops_by_hand():
    """About 789 MFLOP a token: the KDA mixers' projections 40%, the
    latent attention 18% (its core at q/k 192 and v 128 11%), the dense
    layer 16%, the head 12%, the four expert layers 10%, the chunked
    rule 4%."""
    C = 2304
    kda_proj = work_kimi_linear.kda_projection_flops(DIMS)
    rule = work_kimi_linear.kda_rule_flops(DIMS, 8192) / 8192
    mla = (work_kimi_linear.mla_projection_flops(DIMS)
           + 2 * 32 * (192 + 128) * (8192 * 8193 // 2) / 8192)
    dense = 6 * C * 9216
    moe = 2 * C * 256 + (8 * 8 / 256 * 1024 + 1024) * 6 * C
    head = 2 * C * 20480 * 8191 / 8192
    total = work_kimi_linear.forward_flops(DIMS, 1, 8192) / 8192
    assert total == pytest.approx(4 * (kda_proj + rule) + mla + dense
                                  + 4 * moe + head)
    assert [round(x / 1e6, 1) for x in (4 * kda_proj, 4 * rule, mla, dense,
                                        4 * moe, head, total)] \
        == [315.7, 33.6, 142.1, 127.4, 75.5, 94.4, 788.6]
    assert round(3 * total * 8192 / 1e12, 2) == 19.38   # TFLOP a step


def _ctx(steps):
    from types import SimpleNamespace
    return SimpleNamespace(dims=DIMS, cfg=CFG, facts={
        "steps": steps, "traffic": {"batch": 1, "seqlen": 8192}})


def test_kernel_work_by_hand():
    ops, nbytes = work_kimi_linear.kda_chunk(_ctx(2))
    rows = 2 * 4
    assert ops == rows * 3 * work_kimi_linear.kda_rule_flops(DIMS, 8192)
    operands = 4 * 32 * 128 + 32
    assert nbytes == rows * 4 * 8192 * (3 * operands + 2 * 32 * 128)
    # bytes bound it on a v5e at the peak (197 TFLOP/s, 819 GB/s)
    assert nbytes / 819e9 > ops / 197e12
    # the one latent attention layer's flash kernels: 32 heads, scores
    # and their gradients at 192, values at 128
    ops, nbytes = work_kimi_linear.flash_training(_ctx(2))
    assert ops == 2 * 2 * (8192 * 8193 // 2) * 32 * 3 * (192 + 128)
    assert nbytes == 2 * 2 * 8192 * 3 * (192 + 128) * 2 * 32
    assert ops / 197e12 > nbytes / 819e9                  # compute-bound
    assert work_kimi_linear.train_flops(_ctx(2)) \
        == 2 * 3 * work_kimi_linear.forward_flops(DIMS, 1, 8192)


def test_cell_reads_the_trainers_metrics_and_its_own():
    from perfbench.runners import train as runner
    mine = {"train.kda_device_ms", "kda_chunk_roofline",
            "train.attn_mla_device_ms", "flash_attn_mla_train_roofline"}
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
    for other in ("bert-large.pretrain_b32_l128",
                  "mellum2-12b-a2.5b.causal_b1_l8192",
                  "nemotron-3-nano-30b-a3b.causal_b1_l8192",
                  "lfm2-24b-a2b.causal_b1_l8192",
                  "qwen3-next-80b-a3b.causal_b1_l8192"):
        assert not mine & {m["name"] for m in harness.cell_metrics(
            other, runner.END_TO_END)}
    scopes = {s for name in mine for s in harness.load_json(
        "metrics", name + ".json")["params"]["scopes"]}
    assert scopes == {"mx.kda.in_proj", "mx.kda.conv", "mx.kda.core",
                      "mx.kda.gate_norm", "mx.kda.out_proj", "mx.attn.full",
                      "mx.mla.latent"}
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
    for scope in ("mx.kda.in_proj", "mx.kda.conv", "mx.kda.core",
                  "mx.kda.gate_norm", "mx.kda.out_proj", "mx.mla.latent",
                  "mx.attn.proj", "mx.attn.full", "mx.ffn.dense",
                  "mx.moe.route", "mx.moe.dispatch", "mx.moe.experts",
                  "mx.moe.combine", "mx.moe.shared"):
        assert scope in text, scope
    assert "transpose(jvp(mx.fwd))" in text
    assert "mx.attn.window" not in text and "mx.rope" not in text
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
    assert "kimi_linear: " in r.stderr and "rows routed to held" in r.stderr
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
