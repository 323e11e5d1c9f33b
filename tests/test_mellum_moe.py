"""The present-day decoder's pieces and ``models.get_decoder_lm``:
RMSNorm, both rotary flavours, grouped key/value heads through the flash
kernels (window and full causal), the gated feed-forward, the model's
published sizes, and ``ShardedTrainer(take_block_params=True)``.  What
compares the program with ``perfbench``'s plain reference is in
``tests/perfbench_checks/test_mellum_cell.py``."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, nd, parallel
from mxnet_tpu.models import transformer_blocks as tb
from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
from mxnet_tpu.ops.contrib import rope, rope_inv_freq, rms_norm
from mxnet_tpu.ops.pallas_kernels import flash_attention

MELLUM = _DECODER_CONFIGS["mellum2_12b_a2.5b"]
SMALL = dict(vocab_size=96, units=32, num_heads=4, num_kv_heads=2,
             head_dim=8, window=16, num_experts=8, experts_per_token=2,
             expert_hidden_size=16)


def test_rms_norm():
    x = np.random.RandomState(0).randn(3, 5, 16).astype(np.float32)
    g = 1 + 0.1 * np.random.RandomState(1).randn(16).astype(np.float32)
    want = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                       + 1e-6) * g
    np.testing.assert_allclose(np.asarray(rms_norm(x, g)), want, rtol=1e-5)


def test_plain_rotary_frequencies_by_hand():
    # head_dim 128, theta 500000: inv_freq_i = theta^(-2i/128)
    inv = rope_inv_freq(128, 500000.0)
    assert inv.shape == (64,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[1], math.exp(-math.log(500000.0) / 64),
                               rtol=1e-12)
    np.testing.assert_allclose(inv[63], 500000.0 ** (-126 / 128), rtol=1e-12)


def test_yarn_rotary_frequencies_by_hand():
    """Mellum2's full layers: factor 16 over 8192, beta_fast 32,
    beta_slow 1.  The dimension that turns r times over 8192 positions
    is 128 ln(8192 / (2 pi r)) / (2 ln 500000): 18.08 for r = 32, 34.99
    for r = 1, so the ramp runs from 18 to 35: frequencies 0..18 are
    kept, 35..63 divided by 16, and those between blended linearly."""
    kw = MELLUM["rope"]["full_attention"]
    assert kw["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
    plain = rope_inv_freq(128, 500000.0)
    inv = rope_inv_freq(128, kw["theta"], kw["yarn_factor"],
                        kw["yarn_original_max"], kw["yarn_beta_fast"],
                        kw["yarn_beta_slow"])
    low = 128 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(5e5))
    high = 128 * math.log(8192 / (2 * math.pi * 1)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (18, 35)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-12)
    for i in (19, 27, 34):
        ramp = (i - 18) / (35 - 18)
        np.testing.assert_allclose(
            inv[i], plain[i] * (1 - ramp) + plain[i] / 16 * ramp, rtol=1e-12)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rotary_op_turns_the_half_split_pairs(kind):
    kw = MELLUM["rope"][kind]
    x = np.random.RandomState(2).randn(1, 40, 2, 128).astype(np.float32)
    out = np.asarray(rope(jnp.asarray(x), **kw))
    inv = rope_inv_freq(128, kw["theta"], kw.get("yarn_factor", 0.0),
                        kw.get("yarn_original_max", 0))
    scale = kw.get("attention_factor", 1.0)
    for t, i in ((0, 0), (7, 3), (39, 20), (39, 63)):
        c, s = math.cos(t * inv[i]) * scale, math.sin(t * inv[i]) * scale
        a, b = x[0, t, :, i], x[0, t, :, i + 64]
        np.testing.assert_allclose(out[0, t, :, i], a * c - b * s,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(out[0, t, :, i + 64], b * c + a * s,
                                   rtol=1e-4, atol=1e-5)
    # position 0 is only scaled; the scores of rotated q and k depend on
    # the distance alone
    np.testing.assert_allclose(out[:, 0], x[:, 0] * scale, rtol=1e-6)
    q = np.tile(x[:, :1], (1, 40, 1, 1))
    r = np.asarray(rope(jnp.asarray(q), **kw)).astype(np.float64)
    np.testing.assert_allclose((r[0, 5, 0] * r[0, 9, 0]).sum(),
                               (r[0, 25, 0] * r[0, 29, 0]).sum(), rtol=1e-4)


def _dense_attention(q, k, v, window):
    group = q.shape[0] // k.shape[0]
    L, D = q.shape[1:]
    kk, vv = jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)
    s = jnp.einsum("hqd,hkd->hqk", q, kk) / math.sqrt(D)
    t = jnp.arange(L)
    seen = t[None, :] <= t[:, None]
    if window:
        seen &= t[None, :] > t[:, None] - window
    return jnp.einsum("hqk,hkd->hqd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), vv)


@pytest.mark.parametrize("window", [None, 48], ids=["full", "window48"])
@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_flash_kernels_with_eight_query_heads_a_key_value_head(window, what):
    """32 query heads' worth of grouping at a small size: 16 query
    heads over 2 key/value heads (8 a group), against the dense mask;
    blocks smaller than the window and than the sequence, so that block
    skipping and the clamped index maps are exercised."""
    rng = np.random.RandomState(3)
    L, D = 192, 16
    q = jnp.asarray(rng.randn(16, L, D), jnp.float32)
    k = jnp.asarray(rng.randn(2, L, D), jnp.float32)
    v = jnp.asarray(rng.randn(2, L, D), jnp.float32)
    flash = lambda q, k, v: flash_attention(          # noqa: E731
        q, k, v, causal=True, window=window, block_q=64, block_k=32)
    with jax.default_matmul_precision("highest"):
        if what == "forward":
            np.testing.assert_allclose(
                np.asarray(flash(q, k, v)),
                np.asarray(_dense_attention(q, k, v, window)),
                rtol=1e-5, atol=1e-5)
            return
        proj = jnp.asarray(rng.randn(16, L, D), jnp.float32)
        got = jax.grad(lambda *a: (flash(*a) * proj).sum(),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (_dense_attention(*a, window)
                                    * proj).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape        # dK, dV: one a key/value head
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4, err_msg=f"d{name}")


def test_flash_attention_refuses_heads_that_do_not_group():
    q = jnp.zeros((6, 16, 8))
    with pytest.raises(mx.base.MXNetError):
        flash_attention(q, jnp.zeros((4, 16, 8)), jnp.zeros((4, 16, 8)))


def test_gated_ffn_is_swiglu():
    mx.random.seed(0)
    ffn = tb.GatedFFN(8, 12)
    ffn.initialize(mx.init.Normal(0.5))
    x = np.random.RandomState(4).randn(2, 5, 8).astype(np.float32)
    w1 = ffn.ffn_1.weight.data().asnumpy()
    w2 = ffn.ffn_2.weight.data().asnumpy()
    gate, up = np.split(x @ w1.T, 2, -1)
    want = (gate / (1 + np.exp(-gate)) * up) @ w2.T
    np.testing.assert_allclose(ffn(nd.array(x)).asnumpy(), want, rtol=1e-4,
                               atol=1e-5)


def test_mellum2_published_sizes():
    """config.json of JetBrains/Mellum2-12B-A2.5B-Instruct: 21.4 M
    parameters a layer outside the experts, 396 M inside, 12.1 B in
    all."""
    c = MELLUM
    assert (c["units"], c["num_heads"], c["num_kv_heads"], c["head_dim"],
            c["window"]) == (2304, 32, 4, 128, 1024)
    assert (c["num_experts"], c["experts_per_token"],
            c["expert_hidden_size"], c["vocab_size"]) == (64, 8, 896, 98304)
    assert len(c["layer_types"]) == 28
    assert c["layer_types"][:4] == ("sliding_attention",) * 3 \
        + ("full_attention",)
    C, HD, KV = c["units"], 32 * 128, 4 * 128
    outside = 2 * C * HD + 2 * C * KV + 2 * C + C * 64
    inside = 64 * 3 * C * 896
    total = 28 * (outside + inside) + 2 * c["vocab_size"] * C + C
    assert round(outside / 1e6, 1) == 21.4 and round(inside / 1e6) == 396
    assert round(total / 1e9, 1) == 12.1


def test_decoder_lm_layers_follow_layer_types():
    lm = models.get_decoder_lm("mellum2_12b_a2.5b", num_layers=5,
                               experts_held=4, first_expert=4, **SMALL)
    assert [c.attention._window for c in lm.cells] == [16, 16, 16, -1, 16]
    assert {type(c) for c in lm.cells} == {tb.DecoderCell}
    assert lm.cells[3].attention._rope["yarn_factor"] == 16.0
    assert "yarn_factor" not in lm.cells[0].attention._rope
    shapes = {n.split("_", 1)[1]: p.shape
              for n, p in lm.collect_params().items()}
    assert shapes["layer0_moe_gate_weight"] == (32, 8)        # all experts
    assert shapes["layer0_moe_expert_w1"] == (4, 32, 32)      # the share
    assert shapes["layer0_attention_kv_proj_weight"] == (2 * 2 * 8, 32)
    with pytest.raises(mx.base.MXNetError):
        models.get_decoder_lm("mellum3")


def test_decoder_lm_is_causal_and_windowed():
    """Changing token t changes the logits from t on; with every layer
    sliding (window 16) and 2 layers, not beyond t + 2 * 15."""
    mx.random.seed(1)
    kw = dict(SMALL, num_experts=0, hidden_size=24, attention_dtype="float32")
    lm = models.get_decoder_lm(
        "mellum2_12b_a2.5b", layer_types=("sliding_attention",) * 2, **kw)
    lm.initialize(mx.init.Normal(0.3))
    tokens = np.random.RandomState(5).randint(0, 96, (1, 64)).astype(np.int32)
    other = tokens.copy()
    other[0, 20] = (other[0, 20] + 1) % 96
    a, b = lm(nd.array(tokens)).asnumpy(), lm(nd.array(other)).asnumpy()
    moved = np.abs(a - b).max(-1)[0] > 1e-6
    assert not moved[:20].any() and moved[20]
    assert not moved[20 + 2 * 15 + 1:].any() and moved[21:40].all()


def test_trainer_takes_the_blocks_parameters():
    mx.random.seed(2)
    lm = models.get_decoder_lm("mellum2_12b_a2.5b", num_layers=1, **SMALL)
    lm.initialize(mx.init.Normal(0.02))
    tokens = np.random.RandomState(6).randint(0, 96, (2, 32)).astype(np.int32)
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1, ep=1,
                              devices=jax.devices()[:1])
    loss = lambda logits, y: -jnp.take_along_axis(      # noqa: E731
        jax.nn.log_softmax(logits[:, :-1], -1), y[..., None], -1).mean()
    trainer = parallel.ShardedTrainer(
        lm, loss, mesh, optimizer="adamw", example_inputs=(nd.array(tokens),),
        n_labels=1, take_block_params=True)
    theirs = [p.data()._data for p in lm.collect_params().values()]
    assert all(a.is_deleted() for a in theirs)       # nothing held twice
    grads = [g._data for p in lm.collect_params().values()
             for g in p._grad.values()]
    assert grads and all(g.is_deleted() for g in grads)   # nor its gradient
    first = float(trainer.step(tokens, tokens[:, 1:]))
    for _ in range(3):
        last = float(trainer.step(tokens, tokens[:, 1:]))
    assert np.isfinite(last) and last < first
    trainer.write_back()                             # the block lives again
    assert np.isfinite(lm(nd.array(tokens)).asnumpy()).all()
    with mx.autograd.record():
        out = lm(nd.array(tokens)).sum()
    out.backward()
    assert float(np.abs(lm.lm_head.weight.grad().asnumpy()).max()) > 0
    counters = [n for n in trainer.params if n.endswith("rows_routed")]
    assert float(trainer.params[counters[0]].sum()) == 4 * 64 * 2
