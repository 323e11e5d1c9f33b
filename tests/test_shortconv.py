"""The gated short convolution (``ops/shortconv.py``), its mixer block,
and what ``DecoderLM`` gained with it: per-head q/k norms, leading dense
feed-forwards before sparse ones, a head tied to the embedding, and the
sigmoid router's epsilon as a keyword."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, nd, parallel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer_blocks as tb
from mxnet_tpu.ops.moe import moe_topk_route
from mxnet_tpu.ops.shortconv import gated_short_conv
from mxnet_tpu.parallel.functional import functionalize


def _loop(data, w):
    """``C * conv(B * u)`` one position and one tap at a time."""
    b, L, C3 = data.shape
    C, K = C3 // 3, w.shape[1]
    B, Cg, u = data[..., :C], data[..., C:2 * C], data[..., 2 * C:]
    v = B * u
    out = np.zeros((b, L, C), np.float64)
    for t in range(L):
        for j in range(K):
            s = t - (K - 1) + j
            if s >= 0:
                out[:, t] += w[:, j] * v[:, s]
    return Cg * out


def _operands(L, C, K, seed=0):
    r = np.random.RandomState(seed)
    return (r.randn(2, L, 3 * C).astype(np.float32),
            r.uniform(-0.5, 0.5, (C, K)).astype(np.float32))


# L a multiple of nothing; K = 3 (LFM2's) and others, one of them longer
# than the row
@pytest.mark.parametrize("L,C,K", [(7, 5, 3), (13, 4, 4), (11, 3, 1),
                                   (2, 3, 5)])
def test_op_matches_a_loop_over_positions(L, C, K):
    data, w = _operands(L, C, K)
    got = np.asarray(gated_short_conv(data, w))
    np.testing.assert_allclose(got, _loop(data.astype(np.float64), w),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("L,C,K", [(7, 5, 3), (13, 4, 4), (2, 3, 5)])
def test_gradients_match_autodiff_of_the_plain_expression(L, C, K):
    """The backward pass is written out (a ``custom_vjp``): against
    jax's own gradient of the K-term sum over a padded row."""
    data, w = _operands(L, C, K, seed=1)
    g = np.random.RandomState(2).randn(2, L, C).astype(np.float32)

    def plain(data, w):
        B, Cg, u = data[..., :C], data[..., C:2 * C], data[..., 2 * C:]
        vp = jnp.pad(B * u, ((0, 0), (K - 1, 0), (0, 0)))
        return Cg * sum(w[:, j] * vp[:, j:j + L] for j in range(K))

    want = jax.vjp(plain, data, w)[1](g)
    got = jax.vjp(gated_short_conv, data, w)[1](g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_op_is_causal_and_has_no_bias_and_no_activation():
    data, w = _operands(9, 4, 3, seed=3)
    out = np.asarray(gated_short_conv(data, w))
    later = data.copy()
    later[:, 5:] += 1.0
    np.testing.assert_array_equal(
        np.asarray(gated_short_conv(later, w))[:, :5], out[:, :5])
    # linear in u (no activation), and zero in, zero out (no bias)
    twice = data.copy()
    twice[..., 8:] *= 2.0
    np.testing.assert_allclose(np.asarray(gated_short_conv(twice, w)),
                               2.0 * out, rtol=1e-6)
    assert not np.asarray(gated_short_conv(np.zeros_like(data), w)).any()


def test_op_refuses_a_width_that_is_not_three_parts():
    with pytest.raises(MXNetError, match=r"\[B \| C \| u\]"):
        gated_short_conv(np.zeros((1, 4, 10), np.float32),
                         np.zeros((3, 3), np.float32))


def test_op_is_registered_with_its_scope():
    x, w = _operands(6, 4, 3)
    out = nd.gated_short_conv(nd.array(x), nd.array(w))
    assert out.shape == (2, 6, 4)
    text = str(jax.make_jaxpr(gated_short_conv)(x, w).pretty_print(
        name_stack=True))
    assert "mx.sconv.conv" in text


def _init(block, seed=0):
    mx.random.seed(seed)
    block.initialize(mx.init.Normal(0.3))
    return block


def test_short_conv_mixer_is_in_proj_conv_out_proj():
    mixer = _init(tb.ShortConvMixer(8, kernel=3))
    assert {n.split("_", 1)[1]: p.shape
            for n, p in mixer.collect_params().items()} == {
        "in_proj_weight": (24, 8), "conv_weight": (8, 3),
        "out_proj_weight": (8, 8)}
    x = np.random.RandomState(0).randn(2, 7, 8).astype(np.float32)
    p = {n.split("_", 1)[1]: np.asarray(v.data()._data, np.float64)
         for n, v in mixer.collect_params().items()}
    want = _loop(x @ p["in_proj_weight"].T, p["conv_weight"]) \
        @ p["out_proj_weight"].T
    np.testing.assert_allclose(mixer(nd.array(x)).asnumpy(), want,
                               rtol=1e-4, atol=1e-5)
    apply_fn, params = functionalize(mixer, x)
    text = str(jax.make_jaxpr(lambda p, x: apply_fn(p, x)[0])(
        params, x).pretty_print(name_stack=True))
    for scope in ("mx.sconv.in_proj", "mx.sconv.conv", "mx.sconv.out_proj"):
        assert scope in text, scope


def _attention(qk_norm_eps):
    return _init(tb.RotaryGroupedAttention(
        16, 4, 2, 4, rope={"theta": 100.0}, compute_dtype="float32",
        qk_norm_eps=qk_norm_eps, prefix="att_"), seed=1)


def test_qk_norm_off_by_default_and_on_with_two_gains():
    plain, normed = _attention(None), _attention(1e-5)
    names = set(normed.collect_params()) - set(plain.collect_params())
    assert names == {"att_q_norm_gamma", "att_k_norm_gamma"}
    assert normed.q_norm.gamma.shape == normed.k_norm.gamma.shape == (4,)
    x = nd.array(np.random.RandomState(0).randn(1, 6, 16).astype(np.float32))
    assert not np.allclose(plain(x).asnumpy(), normed(x).asnumpy(),
                           atol=1e-3)
    apply_fn, params = functionalize(normed, x._data)
    text = str(jax.make_jaxpr(lambda p, x: apply_fn(p, x)[0])(
        params, x._data).pretty_print(name_stack=True))
    assert "mx.attn.qk_norm" in text
    apply_fn, params = functionalize(plain, x._data)
    assert "qk_norm" not in str(jax.make_jaxpr(
        lambda p, x: apply_fn(p, x)[0])(params, x._data).pretty_print(
            name_stack=True))


def test_qk_norm_is_an_rms_norm_of_every_head_before_the_rotation():
    """Against the block's own pieces put together by hand: scaling q's
    projection by 7 changes nothing under the norm (and does without)."""
    x = nd.array(np.random.RandomState(0).randn(1, 6, 16).astype(np.float32))
    outs = []
    for eps in (1e-12, None):
        att = _attention(eps)
        before = att(x).asnumpy()
        w = att.q_proj.weight
        w.set_data(w.data() * 7.0)
        outs.append((before, att(x).asnumpy()))
    np.testing.assert_allclose(outs[0][0], outs[0][1], rtol=1e-4, atol=1e-5)
    assert not np.allclose(outs[1][0], outs[1][1], atol=1e-3)


_SMALL = dict(vocab_size=32, units=16, num_heads=4, num_kv_heads=2,
              head_dim=4, hidden_size=24, num_experts=4, experts_per_token=2,
              expert_hidden_size=8)


def test_conv_is_a_two_part_layer_and_dense_ffn_layers_lead():
    lm = models.get_decoder_lm(
        "lfm2_24b_a2b", layer_types=("conv", "conv", "full_attention"),
        dense_ffn_layers=2, **_SMALL)
    kinds = [(type(c.attention).__name__, type(c.ffn).__name__)
             for c in lm.cells]
    assert kinds == [("ShortConvMixer", "GatedFFN"),
                     ("ShortConvMixer", "GatedFFN"),
                     ("RotaryGroupedAttention", "MoEFFN")]
    assert lm.cells[0].ffn.ffn_1.weight.shape == (48, 16)
    # none by default: every feed-forward sparse, as Mellum's
    lm = models.get_decoder_lm(
        "lfm2_24b_a2b", layer_types=("conv", "full_attention"),
        dense_ffn_layers=0, **_SMALL)
    assert [type(c.ffn).__name__ for c in lm.cells] == ["MoEFFN"] * 2
    with pytest.raises(MXNetError, match="layer_types"):
        models.get_decoder_lm("lfm2_24b_a2b", layer_types=("convolution",),
                              **_SMALL)


def test_the_published_configuration_is_the_catalogs():
    from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
    pub = _DECODER_CONFIGS["lfm2_24b_a2b"]
    kinds = pub["layer_types"]
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] \
        == list(range(2, 40, 4))
    assert (pub["units"], pub["num_heads"], pub["num_kv_heads"],
            pub["head_dim"], pub["hidden_size"], pub["dense_ffn_layers"],
            pub["num_experts"], pub["experts_per_token"],
            pub["expert_hidden_size"], pub["vocab_size"], pub["conv_kernel"],
            pub["tie_embeddings"]) == (2048, 32, 8, 64, 11776, 2, 64, 4,
                                       1536, 65536, 3, True)
    assert pub["router"] == dict(scoring="sigmoid", route_scale=1.0,
                                 route_eps=1e-6)
    assert pub["rope"] == {"full_attention": dict(theta=1000000.0)}
    assert pub["qk_norm_eps"] == pub["rms_norm_eps"] == 1e-5


def _lm(tie):
    lm = models.get_decoder_lm(
        "lfm2_24b_a2b", layer_types=("conv", "full_attention"),
        dense_ffn_layers=1, tie_embeddings=tie, attention_dtype="float32",
        prefix="lm_", **_SMALL)
    mx.random.seed(3)
    lm.initialize(mx.init.Normal(0.2))
    return lm


def _loss(logits, labels):
    lg = logits[:, :-1].astype(jnp.float32)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()


def test_tied_heads_gradient_is_the_sum_of_an_untied_pairs():
    """One leaf in place of two; with the untied head set to the
    embedding's rows, the same logits, and the tied leaf's gradient the
    embedding's plus the head's."""
    tied, untied = _lm(True), _lm(False)
    assert set(untied.collect_params()) - set(tied.collect_params()) \
        == {"lm_lm_head_weight"}
    assert tied.lm_head.weight is tied.word_embed.weight
    tokens = np.random.RandomState(0).randint(0, 32, (2, 9)).astype(np.int32)
    f_tied, p_tied = functionalize(tied, tokens)
    f_untied, p_untied = functionalize(untied, tokens)
    p_untied = dict(p_tied, lm_lm_head_weight=p_tied["lm_word_embed_weight"])

    def loss_of(f):
        return lambda p: _loss(f(p, tokens)[0], tokens[:, 1:])

    np.testing.assert_allclose(np.asarray(f_tied(p_tied, tokens)[0]),
                               np.asarray(f_untied(p_untied, tokens)[0]),
                               rtol=1e-6, atol=1e-6)
    g_tied = jax.grad(loss_of(f_tied))(p_tied)
    g_untied = jax.grad(loss_of(f_untied))(p_untied)
    assert float(jnp.abs(g_untied["lm_lm_head_weight"]).max()) > 0
    assert float(jnp.abs(g_untied["lm_word_embed_weight"]).max()) > 0
    np.testing.assert_allclose(
        np.asarray(g_tied["lm_word_embed_weight"]),
        np.asarray(g_untied["lm_word_embed_weight"]
                   + g_untied["lm_lm_head_weight"]), rtol=1e-5, atol=1e-7)


def test_sharded_trainer_carries_the_tied_leaf_once():
    lm = _lm(True)
    tokens = np.random.RandomState(0).randint(0, 32, (2, 9)).astype(np.int32)
    mesh = parallel.make_mesh(dp=1, tp=1, sp=1, ep=1,
                              devices=[jax.devices()[0]])
    t = parallel.ShardedTrainer(
        lm, _loss, mesh, optimizer="adamw",
        optimizer_params=dict(learning_rate=1e-2),
        example_inputs=(nd.array(tokens),), n_labels=1,
        take_block_params=True)
    assert [n for n in t.params if "embed" in n or "head" in n] \
        == ["lm_word_embed_weight"]
    before = np.asarray(t.params["lm_word_embed_weight"])
    losses = [float(t.step(tokens, tokens[:, 1:])) for _ in range(3)]
    assert losses[2] < losses[0]
    assert not np.allclose(before,
                           np.asarray(t.params["lm_word_embed_weight"]))
    t.write_back()
    assert lm(nd.array(tokens)).shape == (2, 9, 32)


def test_route_eps_default_leaves_the_router_bit_for_bit():
    r = np.random.RandomState(0)
    x, wg, bias = (r.randn(64, 8).astype(np.float32),
                   r.randn(8, 16).astype(np.float32),
                   (0.01 * r.randn(16)).astype(np.float32))
    kw = dict(experts_per_token=3, scoring="sigmoid", scale=2.5)
    w0, i0 = moe_topk_route(x, wg, bias, **kw)
    w1, i1 = moe_topk_route(x, wg, bias, route_eps=1e-20, **kw)
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    # the published expression, by hand
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wg)))
    picked = np.take_along_axis(s, np.asarray(i0), -1)
    np.testing.assert_allclose(
        np.asarray(w0), 2.5 * picked / (picked.sum(-1, keepdims=True)
                                        + 1e-20), rtol=1e-5)
    # and the keyword weighs: scores of about 8e-7, which feel 1e-6
    flat = np.full((4, 8), 0.125, np.float32)
    kw = dict(experts_per_token=3, scoring="sigmoid")
    small = moe_topk_route(flat, wg - 14.0, bias, route_eps=1e-6, **kw)[0]
    tiny = moe_topk_route(flat, wg - 14.0, bias, **kw)[0]
    assert float(jnp.abs(tiny.sum(-1) - 1.0).max()) < 1e-5
    assert 0.5 < float(small.sum(-1).max()) < 0.9
