"""The Pallas kernels lower for the TPU — checked on the CPU, without
compiling anything.

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas -> Mosaic
lowering (``interpret=False``), which is where block shapes the TPU
cannot tile are refused: the last two dimensions of every block must be
divisible by (8, 128) or equal the array's own.  The interpreter the
rest of the suite runs the kernels in never applies that rule, which is
how the paged kernels shipped with blocks no TPU would take.  Whether
Mosaic then COMPILES the module only a chip (``chip_smoke.py``) can say.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.pallas_kernels import (expert_activation,
                                          flash_attention, grouped_matmul,
                                          ragged_paged_attention,
                                          ragged_paged_verify,
                                          rows_of_tokens, ssm_conv_pass,
                                          ssm_conv_pass_grads,
                                          ssm_norm_pass, ssm_norm_pass_grads,
                                          ssm_scan_chunks, tokens_of_rows)

S = jax.ShapeDtypeStruct


def _tpu_module_text(fn, *avals):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()


@pytest.mark.parametrize("L", [128, 512, 2048])
@pytest.mark.parametrize("mode", ["fwd", "bwd", "windowed"])
def test_flash_attention_lowers_for_tpu(L, mode):
    a = S((16, L, 64), jnp.bfloat16)
    kw = dict(causal=True, window=128) if mode == "windowed" else {}

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False, **kw)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    assert "tpu_custom_call" in _tpu_module_text(fn, a, a, a)


# The flash kernels at the benchmark's four shapes, one 8192-token row in
# bfloat16 with the plan's own tiles (``flash_tile_plan``): Mellum's
# window and full layers (32 query heads over 4 key/value heads of 128),
# Nemotron's (32 over 2 of 128), LFM2's (32 over 8 of 64).  The walk's
# tables ride the scalar prefetch and an edge block's sub-tiles are
# dynamic slices of its refs: what Mosaic's lowering refuses of either
# shows here.
@pytest.mark.parametrize("hkv,d,window", [(4, 128, 1024), (4, 128, None),
                                          (2, 128, None), (8, 64, None)])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_flash_kernels_lower_at_the_cells_shapes(hkv, d, window, mode):
    q, kv = S((32, 8192, d), jnp.bfloat16), S((hkv, 8192, d), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _tpu_module_text(fn, q, kv, kv)
    assert text.count("tpu_custom_call") == (1 if mode == "fwd" else 3)


# Qwen3-Next's gated attention: 16 query heads of 256 over 2 key/value
# heads, 8 a group
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_flash_kernels_lower_at_heads_of_256(mode):
    q, kv = S((16, 8192, 256), jnp.bfloat16), S((2, 8192, 256), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _tpu_module_text(fn, q, kv, kv)
    assert text.count("tpu_custom_call") == (1 if mode == "fwd" else 3)


# Kimi Linear's latent attention: 32 heads of q and k 192 wide over 32
# heads of v 128 wide, the output, its cotangent, dV and the scratch at
# v's width
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_flash_kernels_lower_at_q_192_and_v_128(mode):
    qk, v = S((32, 8192, 192), jnp.bfloat16), S((32, 8192, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _tpu_module_text(fn, qk, qk, v)
    assert text.count("tpu_custom_call") == (1 if mode == "fwd" else 3)


# the shapes chip_smoke.py's serve leg runs: GPT-2-small heads over a
# 513-page pool of 16-token pages, 8 slots, 64 pages per sequence; and
# the wider H=16, D=128 head shape
@pytest.mark.parametrize("H,D", [(12, 64), (16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernels_lower_for_tpu(H, D, dtype):
    B, P, ps, n_pool = 8, 64, 16, 513
    pool = S((n_pool, ps, H, D), dtype)
    i32 = jnp.int32
    text = _tpu_module_text(
        functools.partial(ragged_paged_attention, interpret=False),
        S((B, H, D), dtype), pool, pool, S((B, P), i32), S((B,), i32))
    assert "tpu_custom_call" in text
    # widths: one row, a speculation window, one full tile, several tiles
    for W in (1, 8, 256, 1024):
        text = _tpu_module_text(
            functools.partial(ragged_paged_verify, interpret=False),
            S((B, W, H, D), dtype), pool, pool, S((B, P), i32),
            S((B,), i32), S((B,), i32))
        assert "tpu_custom_call" in text, W


# Mellum's two expert products (16 held experts, S*k = 65,536 pair rows)
# and a small shape: the product, and with its gradients the rows' (the
# weights transposed in the kernel) and the weights' (the rows
# transposed in the kernel)
# (and Nemotron's two: 8 held experts, 49,152 pair rows, a width of
# 1856 = 14.5 x 128 as K and as N)
@pytest.mark.parametrize("M,K,N,G", [(65536, 2304, 1792, 16),
                                     (65536, 896, 2304, 16),
                                     (49152, 2688, 1856, 8),
                                     (49152, 1856, 2688, 8),
                                     (2048, 128, 256, 3)])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_grouped_matmul_lowers_for_tpu(M, K, N, G, mode):
    def fwd(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, sizes, interpret=False)

    def loss(lhs, rhs, sizes):
        return (fwd(lhs, rhs, sizes) ** 2).sum()

    fn = fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1))
    text = _tpu_module_text(fn, S((M, K), jnp.float32),
                            S((G, K, N), jnp.float32), S((G,), jnp.int32))
    assert text.count("tpu_custom_call") >= (1 if mode == "fwd" else 3)
    assert "ragged_dot" not in text


# the expert layer's movers and activation at Mellum's shapes (8192
# tokens of 2304, 8 pairs a token, 16 groups, first product 1792 wide),
# at Nemotron's (8192 tokens of 2688: 84 MiB of token rows, whole in
# VMEM; 6 pairs a token, 8 groups, plain experts 1856 wide) and a small
# one, each in the forms the forward and the backward pass call: the
# interpreter never applies Mosaic's block-shape rule.  (That Mosaic
# slices an array in HBM by whole (8, 128) tiles only, which shaped both
# movers, shows at its compile, not here: PERF.md section 7, recipe 2.)
# The activation kernel wants halves of 128 columns: at 1856 = 14.5 x
# 128 it is its own jnp expression, by the kernel's own predicate.
@pytest.mark.parametrize("s,k,c,h,g,gated", [(8192, 8, 2304, 896, 16, True),
                                             (8192, 6, 2688, 1856, 8, False),
                                             (512, 4, 128, 128, 3, True)])
@pytest.mark.parametrize("mover", ["rows", "rows_scaled_and_dotted",
                                   "tokens", "activation",
                                   "activation_gradient"])
def test_expert_movers_lower_for_tpu(mover, s, k, c, h, g, gated):
    m = s * k
    wide = 2 * h if gated else h
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    fn, avals = {
        "rows": (
            lambda x, tok, live: rows_of_tokens(
                x, tok, live, dtype=bf16, interpret=False),
            (S((s, c), f32), S((m,), i32), S((), i32))),
        "rows_scaled_and_dotted": (
            lambda x, tok, live, scale, dot: rows_of_tokens(
                x, tok, live, scale=scale, dot=dot, dtype=bf16,
                interpret=False),
            (S((s, c), f32), S((m,), i32), S((), i32), S((m,), f32),
             S((m, c), f32))),
        "tokens": (
            functools.partial(tokens_of_rows, interpret=False),
            (S((m, c), f32), S((s, k), f32), S((s, k), i32), S((s, k), i32),
             S((g,), i32))),
        "activation": (
            lambda hh, live: expert_activation(
                hh, live, jax.nn.silu, gated, dtype=bf16, interpret=False),
            (S((m, wide), f32), S((), i32))),
        "activation_gradient": (
            lambda hh, da, live: expert_activation(
                hh, live, jax.nn.silu, gated, g=da, dtype=bf16,
                interpret=False),
            (S((m, wide), f32), S((m, h), f32), S((), i32))),
    }[mover]
    text = _tpu_module_text(fn, *avals)
    kernel = not (mover.startswith("activation") and h % 128)
    assert text.count("tpu_custom_call") == int(kernel)
    assert "gather" not in text.replace("all-gather", "")


# The selective scan at Nemotron-3-Nano's mixer (one 8192-token row, 8
# groups of 8 heads of 64, state 128, chunks of 128) and at the other
# head widths ``ssm_scan_tiles`` takes: the forward kernel; with the
# gradients also the sweep of the entering states and the backward
# kernel.  (That Mosaic compiles them shows at its compile: PERF.md
# section 7, recipe 2.)
@pytest.mark.parametrize("b,L,G,R,P,N,Q", [(1, 8192, 8, 8, 64, 128, 128),
                                           (2, 512, 2, 4, 32, 128, 128),
                                           (2, 512, 2, 2, 128, 256, 256)])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_selective_scan_lowers_for_tpu(b, L, G, R, P, N, Q, mode):
    f32 = jnp.float32
    avals = (S((b, L, G * R * P + 2 * G * N), f32), S((b, G, R, L), f32),
             S((G, R), f32), S((G, R), f32))

    def fwd(*a):
        return ssm_scan_chunks(*a, N, Q, interpret=False)

    fn = fwd if mode == "fwd" else jax.value_and_grad(
        lambda *a: fwd(*a).sum(), argnums=tuple(range(4)))
    text = _tpu_module_text(fn, *avals)
    assert text.count("tpu_custom_call") == (1 if mode == "fwd" else 3)


# The mixer's two elementwise operators at Nemotron-3-Nano's widths (one
# 8192-token row; the convolution's 6144 channels 4096 columns into the
# in-projection's 10304, four taps; the group norm's 4096 channels in 8
# groups, the gate at the array's start) and at a small shape with
# other blocks: a pass forward, a pass backward.  The convolution's
# taps are sublane-offset reads of a VMEM scratch, its backward fetches
# the slab before a block through a second block spec.  Qwen3-Next's
# Gated DeltaNet: the convolution's 8192 channels at the start of the
# in-projection's 12288, no bias; the norm before the gate over 32
# groups of 128, the gate 8192 columns in.
@pytest.mark.parametrize("b,L,C,K,lo,wide", [(1, 8192, 6144, 4, 4096, 10304),
                                             (2, 384, 256, 9, 0, 256),
                                             (1, 8192, 8192, 4, 0, 12288)])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_conv_pass_lowers_for_tpu(b, L, C, K, lo, wide, mode):
    f32 = jnp.float32
    avals = (S((b, L, wide), f32), S((K, C), f32), S((1, C), f32))
    if mode == "fwd":
        def fn(*a):
            return ssm_conv_pass(*a, lo, False)
    else:
        avals += (S((b, L, C), f32),)

        def fn(*a):
            return ssm_conv_pass_grads(*a, lo, False)
    assert _tpu_module_text(fn, *avals).count("tpu_custom_call") == 1


@pytest.mark.parametrize("b,L,C,groups,lo,wide,before", [
    (1, 8192, 4096, 8, 0, 10304, False), (2, 384, 256, 1, 256, 640, False),
    (1, 8192, 4096, 32, 8192, 12288, True)])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_norm_pass_lowers_for_tpu(b, L, C, groups, lo, wide, before, mode):
    f32 = jnp.float32
    avals = (S((b, L, C), f32), S((b, L, wide), f32), S((1, C), f32))
    if mode == "fwd":
        def fn(*a):
            return ssm_norm_pass(*a, groups, 1e-5, lo, False, before)
    else:
        avals += (S((b, L, C), f32),)

        def fn(*a):
            return ssm_norm_pass_grads(*a, groups, 1e-5, lo, False, before)
    assert _tpu_module_text(fn, *avals).count("tpu_custom_call") == 1


# Kimi Delta Attention's gate: the norm before a sigmoid gate, 32 heads of
# 128 over the out-projection's input, z an array of its own
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_norm_pass_with_a_sigmoid_gate_lowers_for_tpu(mode):
    f32 = jnp.float32
    b, L, C, groups = 1, 8192, 4096, 32
    avals = (S((b, L, C), f32), S((b, L, C), f32), S((1, C), f32))
    if mode == "fwd":
        def fn(*a):
            return ssm_norm_pass(*a, groups, 1e-5, 0, False, True, "sigmoid")
    else:
        avals += (S((b, L, C), f32),)

        def fn(*a):
            return ssm_norm_pass_grads(*a, groups, 1e-5, 0, False, True,
                                       "sigmoid")
    assert _tpu_module_text(fn, *avals).count("tpu_custom_call") == 1
