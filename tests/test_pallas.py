"""Flash-attention Pallas kernel tests (CPU interpreter mode; same code
compiles on TPU).  Oracle = dense softmax attention, the reference's
_contrib_interleaved_matmul_* chain semantics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.pallas_kernels import flash_attention


def _dense_ref(q, k, v, lens=None, causal=False, window=None):
    D = q.shape[-1]
    group = q.shape[0] // k.shape[0]
    k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(D)
    Lq, Lk = q.shape[1], k.shape[1]
    mask = jnp.ones((q.shape[0], Lq, Lk), bool)
    ahead = jnp.arange(Lq)[None, :, None] - jnp.arange(Lk)[None, None, :]
    if lens is not None:
        mask &= (jnp.arange(Lk)[None, None, :] < lens[:, None, None])
    if causal:
        mask &= ahead >= 0
    if window is not None:
        mask &= ahead < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _rand_qkv(BH=4, L=48, D=16, seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(BH, L, D), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_lens", [False, True])
def test_flash_forward_matches_dense(causal, with_lens):
    q, k, v = _rand_qkv()
    lens = jnp.asarray([48, 17, 32, 5], jnp.int32) if with_lens else None
    out = flash_attention(q, k, v, lengths=lens, causal=causal,
                          block_q=16, block_k=16)
    ref = _dense_ref(q, k, v, lens, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_flash_nondivisible_seq_padding():
    """Lq=37 not a multiple of any block size: wrapper pads + slices."""
    q, k, v = _rand_qkv(BH=2, L=37, D=8, seed=3)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = _dense_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


# Forward and the three gradients against the dense reference.  The first
# case is the old one (four heads, a length each, blocks of 16).  The
# others: blocks of 32 cut into sub-tiles of 16 over L = 96 (and 80:
# padded), so the diagonal crosses every third block and a window of 40 =
# 2.5 sub-tiles crosses blocks and sub-tiles off their edges; 1, 4 and 16
# query heads a key/value head; with lengths (one a key/value head, as
# the ops hand them over) every row keeps a key in its window (a row
# with none is garbage in, garbage out, as before).  Heads of 256 at 8
# query heads a key/value head are Qwen3-Next's gated attention's.
@pytest.mark.parametrize("causal,window,group,D,lens,L,block", [
    (True, None, 1, 16, (48, 20, 48, 9), 48, 16),
    (True, None, 1, 64, None, 96, 32),
    (True, None, 4, 128, (89, 96), 96, 32),
    (True, None, 16, 64, (73,), 80, 32),
    (True, None, 16, 128, None, 96, 32),
    (True, None, 8, 256, None, 96, 32),
    (True, 40, 1, 128, None, 96, 32),
    (True, 40, 4, 64, (89, 96), 96, 32),
    (True, 40, 16, 128, None, 80, 32),
    (True, 33, 4, 128, (89, 96), 96, 32),
    (True, 200, 4, 64, None, 96, 32),
    (False, None, 1, 64, (89, 96), 96, 32),
    (False, None, 4, 128, None, 80, 32),
])
def test_flash_grads_match_dense(causal, window, group, D, lens, L, block):
    rs = np.random.RandomState(group * D + L)
    heads_kv = len(lens) if lens else 2
    q = jnp.asarray(rs.randn(heads_kv * group, L, D), jnp.float32)
    k, v = (jnp.asarray(rs.randn(heads_kv, L, D), jnp.float32)
            for _ in range(2))
    cot = jnp.asarray(rs.randn(*q.shape), jnp.float32)
    if lens is not None:
        lens = jnp.repeat(jnp.asarray(lens, jnp.int32), group)

    def flash(q, k, v):
        return flash_attention(q, k, v, lengths=lens, causal=causal,
                               window=window, block_q=block, block_k=block)

    def dense(q, k, v):
        return _dense_ref(q, k, v, lens, causal, window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5)
    gf = jax.grad(lambda *a: (flash(*a) * cot).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gd = jax.grad(lambda *a: (dense(*a) * cot).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_selfatt_op_matches_interleaved_chain():
    """F.flash_selfatt == interleaved qk -> masked softmax -> valatt."""
    L, B, H, D = 24, 3, 2, 8
    rs = np.random.RandomState(0)
    qkv = nd.array(rs.randn(L, B, H * 3 * D).astype(np.float32))
    valid = nd.array(np.array([24, 10, 17], np.float32))

    flash = nd.flash_selfatt(qkv, valid, heads=H)

    scores = nd.interleaved_matmul_selfatt_qk(qkv, heads=H)  # (B*H, L, L)
    neg = np.full((B, 1, 1, L), 0.0, np.float32)
    steps = np.arange(L)
    for b in range(B):
        neg[b, 0, 0, steps >= int(valid.asnumpy()[b])] = -1e30
    mask = nd.array(np.broadcast_to(neg, (B, H, L, L))
                    .reshape(B * H, L, L).copy())
    att = nd.softmax(scores + mask, axis=-1)
    dense = nd.interleaved_matmul_selfatt_valatt(qkv, att, heads=H)
    np.testing.assert_allclose(flash.asnumpy(), dense.asnumpy(),
                               atol=1e-4)


def test_bert_use_flash_matches_dense():
    """BERT with use_flash=True == dense-mask BERT, same params."""
    from mxnet_tpu import models
    kwargs = dict(vocab_size=64, units=32, hidden_size=64, num_layers=2,
                  num_heads=4, max_length=32, dropout=0.0)
    mx.random.seed(0)
    dense_model = models.get_bert_model("bert_12_768_12", **kwargs)
    dense_model.initialize()
    flash_model = models.get_bert_model("bert_12_768_12", use_flash=True,
                                        **kwargs)
    flash_model.initialize()
    # copy params dense -> flash (names differ only by block prefix)
    src = {k.split("bertmodel", 1)[-1].split("_", 1)[-1]: v
           for k, v in dense_model.collect_params().items()}
    for name, p in flash_model.collect_params().items():
        key = name.split("bertmodel", 1)[-1].split("_", 1)[-1]
        p.set_data(src[key].data())

    rs = np.random.RandomState(1)
    B, L = 2, 24
    inputs = nd.array(rs.randint(0, 64, (B, L)), dtype="int32")
    tok = nd.zeros((B, L), dtype="int32")
    valid = nd.array(np.array([24, 11], np.float32))
    seq_d, pool_d = dense_model(inputs, tok, valid)
    seq_f, pool_f = flash_model(inputs, tok, valid)
    # padded positions attend to garbage by design; compare valid rows
    for b, vl in enumerate([24, 11]):
        np.testing.assert_allclose(seq_f.asnumpy()[b, :vl],
                                   seq_d.asnumpy()[b, :vl], atol=2e-4)
    np.testing.assert_allclose(pool_f.asnumpy(), pool_d.asnumpy(),
                               atol=2e-4)


def test_runtime_reports_pallas_honestly():
    feats = mx.runtime.Features()
    assert feats.is_enabled("PALLAS")  # interpret mode counts as available

def test_flash_bf16_inputs_close_to_fp32_dense():
    """The r3 kernel keeps q/k/v in bf16 for the MXU dots (fp32 softmax
    stats): outputs must stay within bf16-grade tolerance of the fp32
    dense oracle."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    rng = np.random.RandomState(0)
    BH, L, D = 4, 64, 16
    qf = rng.randn(BH, L, D).astype(np.float32)
    kf = rng.randn(BH, L, D).astype(np.float32)
    vf = rng.randn(BH, L, D).astype(np.float32)
    out = np.asarray(flash_attention(
        jnp.asarray(qf, jnp.bfloat16), jnp.asarray(kf, jnp.bfloat16),
        jnp.asarray(vf, jnp.bfloat16), causal=True)).astype(np.float32)
    s = np.einsum("bqd,bkd->bqk", qf, kf) / np.sqrt(D)
    s[:, np.triu(np.ones((L, L), bool), k=1)] = -1e30
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bqk,bkd->bqd", p, vf)
    assert np.abs(out - ref).max() < 0.06, np.abs(out - ref).max()


def test_flash_block_defaults_table():
    """What ``flash_tile_plan`` derives at the cells' shapes, with no
    device: a call that is not causal plans the dense grid the kernels
    always had; under the causal band no grid step idles and the tiles
    computed lie close to the mask."""
    from mxnet_tpu.ops.pallas_kernels import flash_tile_plan
    for L, block in ((128, 128), (512, 512), (2048, 1024), (8192, 1024)):
        plan = flash_tile_plan(L, L, lengths=True)
        assert (plan.block_q, plan.block_k) == (block, block)
        assert plan.steps == plan.computing_steps == (L // block) ** 2
        assert plan.pairs_computed == plan.pairs_visible == L * L
    assert flash_tile_plan(200, 300).block_q == 200     # one block
    explicit = flash_tile_plan(512, 512, block_q=64, block_k=32)
    assert (explicit.block_q, explicit.block_k) == (64, 32)
    L = 8192
    for group in (8, 16, 4):
        full = flash_tile_plan(L, L, causal=True, group=group)
        assert full.pairs_visible == L * (L + 1) // 2
        assert full.steps == full.computing_steps
        assert full.pairs_computed <= 1.06 * full.pairs_visible
    band = flash_tile_plan(L, L, causal=True, window=1024, group=8)
    assert band.pairs_visible == 1024 * 1025 // 2 + (L - 1024) * 1024
    assert band.steps == band.computing_steps       # no idle step
    assert band.pairs_computed <= 1.4 * band.pairs_visible
    # today's 1024 x 1024 blocks whole: twice the visible pairs
    whole = flash_tile_plan(L, L, causal=True, window=1024,
                            sub_q=1024, sub_k=1024)
    assert whole.pairs_computed == 15 * 1024 * 1024


# (Lq, Lk, causal, window, block_q, block_k, sub_q, sub_k, heads a group,
# lengths given): a window that is no multiple of a tile, one longer than
# L, L no multiple of the tile, Lq and Lk apart either way (query blocks
# past every key and key blocks past every query: steps that only write
# zeros), sub-tiles that are not square, no band at all
@pytest.mark.parametrize("Lq,Lk,causal,window,bq,bk,sq,sk,group,lens", [
    (64, 64, True, None, 16, 16, 8, 8, 1, False),
    (64, 64, True, 24, 16, 16, 8, 8, 4, False),
    (64, 64, True, 13, 16, 16, 8, 8, 1, False),
    (64, 64, True, 200, 16, 16, 8, 8, 16, False),
    (50, 50, True, 12, 16, 16, 8, 8, 1, False),
    (37, 61, True, None, 16, 32, 8, 16, 2, True),
    (96, 32, True, 8, 16, 16, 8, 8, 1, False),
    (32, 96, True, None, 16, 16, 8, 4, 1, False),
    (128, 128, True, 40, 64, 32, 16, 32, 4, True),
    (128, 128, True, 1, 32, 32, 8, 8, 1, False),
    (48, 48, False, None, 16, 16, 8, 8, 1, True),
    (37, 50, False, None, 16, 16, 16, 16, 4, False),
])
def test_flash_tile_plan_counts(Lq, Lk, causal, window, bq, bk, sq, sk,
                                group, lens):
    from mxnet_tpu.ops.pallas_kernels import flash_tile_plan
    plan = flash_tile_plan(Lq, Lk, causal, window, group, lens,
                           bq, bk, sq, sk)
    Lq_p, Lk_p = plan.nq * bq, plan.nk * bk
    ahead = np.arange(Lq_p)[:, None] - np.arange(Lk_p)[None, :]
    band = np.ones((Lq_p, Lk_p), bool)
    if causal:
        band &= ahead >= 0
    if window is not None:
        band &= ahead < window
    visible = band[:Lq, :Lk]
    covered = np.zeros_like(band)
    area = 0
    for q0, k0, rows, cols, crossed in plan.tiles():
        assert not covered[q0:q0 + rows, k0:k0 + cols].any()    # once
        covered[q0:q0 + rows, k0:k0 + cols] = True
        area += rows * cols
        # the mask is applied exactly where the band hides something
        assert crossed == (not band[q0:q0 + rows, k0:k0 + cols].all())
        assert band[q0:q0 + rows, k0:k0 + cols].any()
    assert not (band & ~covered).any()      # every visible pair in a tile
    blocks = band.reshape(plan.nq, bq, plan.nk, bk).any(axis=(1, 3))
    assert (plan.steps, plan.computing_steps, plan.pairs_computed,
            plan.pairs_visible) == (
        int(np.maximum(blocks.sum(axis=1), 1).sum()), int(blocks.sum()),
        area, int(visible.sum()))
    # both walks visit the blocks that hold a visible pair, each staying
    # block's steps together, first and last flagged
    for order in ("qk", "kq"):
        stay, walked, flags = plan.walk(order)
        at = (stay, walked) if order == "qk" else (walked, stay)
        assert sorted(zip(*at)) == sorted(set(zip(*at)))
        seen = {(i, j) for i, j, f in zip(*at, flags) if plan.kinds[f >> 2]}
        assert seen == set(zip(*np.nonzero(blocks)))
        assert list(stay) == sorted(stay)
        first = np.r_[True, stay[1:] != stay[:-1]]
        last = np.r_[stay[1:] != stay[:-1], True]
        assert ((flags & 1 > 0) == first).all()
        assert ((flags & 2 > 0) == last).all()

def test_flash_sliding_window_matches_dense():
    """Causal sliding-window attention (window w: keys in [q-w+1, q])
    matches the dense masked oracle, forward and grads."""
    q, k, v = _rand_qkv(BH=2, L=48, D=8, seed=11)
    w = 12

    def dense_win(q, k, v):
        return _dense_ref(q, k, v, causal=True, window=w)

    out = flash_attention(q, k, v, causal=True, window=w,
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_win(q, k, v)), atol=1e-5)

    cot = jnp.asarray(np.random.RandomState(12).randn(*q.shape),
                      jnp.float32)
    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, causal=True, window=w, block_q=16, block_k=16)
        * cot).sum(), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: (dense_win(q, k, v) * cot).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def test_flash_window_requires_causal():
    import pytest
    from mxnet_tpu.base import MXNetError
    q, k, v = _rand_qkv(BH=1, L=16, D=8)
    with pytest.raises(MXNetError, match="causal"):
        flash_attention(q, k, v, causal=False, window=4)
