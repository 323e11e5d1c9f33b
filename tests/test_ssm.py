"""The state-space operators (``ops/ssm.py``), the Mamba-2 mixer block
and the hybrid layer kinds of ``DecoderLM`` on the CPU at small sizes:
the chunked scan against the recurrence it stands for, its gradient by
finite differences, the Pallas scan (in the interpreter) against both,
the convolution's first positions, the gate before the norm, attention
without rotary, and the published sizes."""
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, nd
from mxnet_tpu.models import transformer_blocks as tb
from mxnet_tpu.models.decoder_lm import _DECODER_CONFIGS
from mxnet_tpu.ops import pallas_kernels, ssm
from mxnet_tpu.ops.ssm import ssm_conv, ssm_gate_norm, ssm_mixer, ssm_scan

H, P, G, N = 4, 8, 2, 16
NAMES = ("x", "dt", "A_log", "B", "C", "D", "dt_bias")


def _operands(L, seed=0, b=2, H=H, P=P, G=G, N=N):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (b, L, H, P)),
            jax.random.normal(k[1], (b, L, H)),
            jnp.log(jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0)),
            jax.random.normal(k[3], (b, L, G, N)),
            jax.random.normal(k[4], (b, L, G, N)),
            1.0 + 0.1 * jax.random.normal(k[5], (H,)),
            jax.random.normal(k[6], (H,)))


def recurrence(x, dt, A_log, B, C, D, dt_bias):
    """One position at a time: the definition."""
    R = x.shape[2] // B.shape[2]
    delta, A = jax.nn.softplus(dt + dt_bias), -jnp.exp(A_log)
    Bh, Ch = jnp.repeat(B, R, axis=2), jnp.repeat(C, R, axis=2)

    def step(h, at):
        xt, dl, Bt, Ct = at
        h = (jnp.exp(dl * A)[..., None, None] * h
             + (dl[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, Ct)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:])
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (x, delta, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


@pytest.mark.parametrize("L,chunk", [(75, 16), (10, 16), (64, 16), (128, 128),
                                     (33, 1)],
                         ids=["ragged_last_chunk", "below_one_chunk",
                              "whole_chunks", "one_chunk", "chunk_of_one"])
def test_chunked_scan_is_the_recurrence(L, chunk):
    ops = _operands(L)
    with jax.default_matmul_precision("highest"):
        got, want = ssm_scan(*ops, chunk=chunk), recurrence(*ops)
    assert got.shape == want.shape == (2, L, H, P)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5 * float(
                                   jnp.abs(want).max()))


def test_scan_survives_decays_that_underflow_a_quotient():
    """delta A of about -1,000 a position: exp of a chunk's running sum
    is 0 in float32, a quotient of two such is 0/0; differences of the
    sum are exact."""
    x, dt, A_log, B, C, D, dt_bias = _operands(48)
    ops = (x, dt + 8.0, A_log + 3.0, B, C, D, dt_bias)
    cum = jnp.cumsum(jax.nn.softplus(ops[1] + dt_bias) * -jnp.exp(ops[2]), 1)
    assert float(jnp.exp(cum[:, 15]).max()) == 0.0      # a chunk of 16
    with jax.default_matmul_precision("highest"):
        got, want = ssm_scan(*ops, chunk=16), recurrence(*ops)
        grads = jax.grad(lambda *a: ssm_scan(*a, chunk=16).sum(),
                         argnums=tuple(range(7)))(*ops)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@pytest.mark.parametrize("leaf", NAMES)
def test_scan_gradient_by_finite_differences(leaf):
    """One leaf of each kind: the directional derivative along a random
    direction, central differences in float64 against autodiff through
    the chunked form."""
    jax.config.update("jax_enable_x64", True)
    try:
        ops = [a.astype(jnp.float64) for a in _operands(40, seed=3, b=1)]
        i = NAMES.index(leaf)
        u = jax.random.normal(jax.random.PRNGKey(9), ops[i].shape,
                              jnp.float64)
        w = jax.random.normal(jax.random.PRNGKey(10), (1, 40, H, P),
                              jnp.float64)

        def f(a):
            return (ssm_scan(*ops[:i], a, *ops[i + 1:], chunk=16)
                    .astype(jnp.float64) * w).sum()

        with jax.default_matmul_precision("highest"):
            auto = float((jax.grad(f)(ops[i]) * u).sum())
            eps = 1e-3
            numeric = float(f(ops[i] + eps * u) - f(ops[i] - eps * u)) \
                / (2 * eps)
    finally:
        jax.config.update("jax_enable_x64", False)
    # the scan itself computes in float32: 1e-3 of the derivative is its
    # rounding under a step of 1e-3
    assert abs(auto - numeric) <= 2e-3 * max(abs(numeric), 1.0), (auto,
                                                                  numeric)


# ---- the Pallas scan (ops/pallas_kernels.ssm_scan_chunks), which
# ``ssm_scan`` takes where chunk and state are multiples of 128 and a
# group's heads fill whole 128-lane blocks; in the interpreter here.
# Two groups of two heads of 64 over a state of 128, chunks of 128.
KERNEL = dict(H=4, P=64, G=2, N=128)
# The kernels round what enters the MXU to bfloat16 (8 bits of mantissa,
# 2^-9 of an operand at most, 2^-8 of a product of two) where the CPU's
# einsums of ``_chunked_scan`` and the recurrence keep float32.  A value
# passes three such products in a row (C B^T, the masked product or the
# state's, and C against the state), a gradient up to five, and a
# head's leaf (A_log, dt_bias) is a sum over every position in which
# terms of both signs cancel, so its norm is small beside its terms'
# errors.  2^-6 of the NORM of what is compared bounds them with room:
# the values read 0.0033, the gradients 0.0008 to 0.0064 (dt_bias).
KERNEL_TOL = 2.0 ** -6


def _kernel_operands(L, seed=0, b=2):
    """delta of about 0.007 against A of -1 to -16 and D of 0.1: the
    state outlives a chunk of 128 for the slower heads, as a trained
    model's does, and ``D x`` does not drown what it gives, so the carry
    weighs in every comparison."""
    x, dt, A_log, B, C, D, dt_bias = _operands(L, seed=seed, b=b, **KERNEL)
    return x, 0.5 * dt - 5.0, A_log, 0.3 * B, 0.3 * C, 0.1 * D, 0.3 * dt_bias


def _chunked(*ops, chunk=128):
    """``ssm_scan`` on its ``jnp`` path whatever the shape."""
    with mock.patch.object(ssm, "ssm_scan_tiles", lambda *shape: False):
        return ssm_scan(*ops, chunk=chunk)


def _gap(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def _has_kernels(fn, *ops):
    return str(jax.make_jaxpr(fn)(*ops)).count("pallas_call")


@pytest.mark.parametrize("oracle", ["recurrence", "chunked_scan"])
@pytest.mark.parametrize("L", [384, 300], ids=["whole_chunks", "ragged"])
def test_kernel_scan_values(L, oracle):
    ops = _kernel_operands(L)
    assert _has_kernels(ssm_scan, *ops) == 1
    got = ssm_scan(*ops)
    with jax.default_matmul_precision("highest"):
        want = (recurrence if oracle == "recurrence" else _chunked)(*ops)
    assert got.shape == want.shape == (2, L, 4, 64)
    assert _gap(got, want) < KERNEL_TOL
    # ``D x`` is most of y: what the state and the chunk give, alone
    skip = ops[5][:, None] * ops[0]
    assert _gap(got - skip, want - skip) < KERNEL_TOL


@pytest.fixture(scope="module")
def kernel_gradients():
    """Every leaf's gradient of one weighted sum of y, L = 300 (two
    whole chunks and a ragged one): through the kernels, through
    autodiff of ``_chunked_scan`` and of the recurrence."""
    ops = _kernel_operands(300, seed=2)
    w = jax.random.normal(jax.random.PRNGKey(7), ops[0].shape)

    def grads(fn):
        return jax.grad(lambda *a: (fn(*a) * w).sum(),
                        argnums=tuple(range(7)))

    assert _has_kernels(grads(ssm_scan), *ops) == 3
    got = grads(ssm_scan)(*ops)
    with jax.default_matmul_precision("highest"):
        return got, {"recurrence": grads(recurrence)(*ops),
                     "chunked_scan": grads(_chunked)(*ops)}


@pytest.mark.parametrize("oracle", ["recurrence", "chunked_scan"])
@pytest.mark.parametrize("leaf", NAMES)
def test_kernel_scan_gradient(kernel_gradients, leaf, oracle):
    got, want = kernel_gradients
    i = NAMES.index(leaf)
    assert got[i].shape == want[oracle][i].shape
    assert _gap(got[i], want[oracle][i]) < KERNEL_TOL, leaf


def _loses_the_carry(kernel):
    """The kernel with its carried state (its last operand, the VMEM
    scratch) emptied before every chunk."""
    def faulty(*refs, **kw):
        refs[-1][...] = jnp.zeros_like(refs[-1])
        return kernel(*refs, **kw)
    return faulty


@pytest.mark.parametrize("which", ["_ssm_fwd_kernel", "_ssm_bwd_kernel"])
def test_kernel_that_loses_the_carry_fails_the_comparison(which):
    """The fault the kernels could hide: a chunk that starts from an
    empty state (forward, and the sweep that recomputes the states for
    the backward pass), or a chunk whose state's gradient never reaches
    the chunk before (backward).  The comparison of the tests above must
    read it at 8 times its limit or more (it reads 0.16 to 0.25, sound
    kernels 0.0064 at most), in y and in every leaf the state reaches
    the loss through (forward: C reads the state; backward: B and delta
    write it)."""
    ops = _kernel_operands(384, seed=4, b=1)
    w = jax.random.normal(jax.random.PRNGKey(8), ops[0].shape)
    skip = ops[5][:, None] * ops[0]

    def run(fn):
        y, vjp = jax.vjp(fn, *ops)
        return dict(zip(("y",) + NAMES, (y - skip,) + vjp(w)))

    def gaps(got):
        return {name: _gap(got[name], want[name]) for name in got}

    with jax.default_matmul_precision("highest"):
        want = run(_chunked)
    jitted = (pallas_kernels._ssm_fwd, pallas_kernels._ssm_bwd)
    try:
        for f in jitted:
            f.clear_cache()
        with mock.patch.object(pallas_kernels, which, _loses_the_carry(
                getattr(pallas_kernels, which))):
            faulty = gaps(run(ssm_scan))
    finally:
        for f in jitted:        # the faulty traces must not outlive this
            f.clear_cache()
    sound = gaps(run(ssm_scan))
    assert max(sound.values()) < KERNEL_TOL, sound
    read_by = (("y", "C", "A_log", "dt_bias") if which == "_ssm_fwd_kernel"
               else ("B", "dt", "dt_bias"))
    for name in read_by:
        assert faulty[name] > 8 * KERNEL_TOL, faulty
    if which == "_ssm_bwd_kernel":
        assert faulty["y"] == sound["y"]        # the forward pass is whole


def test_kernel_scan_survives_decays_that_underflow_a_quotient():
    """``test_scan_survives_decays_that_underflow_a_quotient`` at a
    shape the kernels take: exp of the running sum is 0 long before a
    chunk of 128 ends."""
    x, dt, A_log, B, C, D, dt_bias = _operands(300, seed=5, **KERNEL)
    ops = (x, dt + 8.0, A_log + 3.0, 0.3 * B, 0.3 * C, D, dt_bias)
    cum = jnp.cumsum(jax.nn.softplus(ops[1] + dt_bias) * -jnp.exp(ops[2]), 1)
    assert float(jnp.exp(cum[:, 15]).max()) == 0.0
    assert _has_kernels(ssm_scan, *ops) == 1
    got = ssm_scan(*ops)
    grads = jax.grad(lambda *a: ssm_scan(*a).sum(),
                     argnums=tuple(range(7)))(*ops)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*ops)
    assert bool(jnp.isfinite(got).all())
    assert _gap(got, want) < KERNEL_TOL
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


# Nemotron-3-Nano's mixer (the benchmark's cell): 64 heads of 64 in 8
# groups over a state of 128, chunks of 128; and shapes that stay on
# ``_chunked_scan``: this file's small ones, a chunk that is no
# multiple of 128, a state of 64, heads of 48, one head a group
@pytest.mark.parametrize("H,P,G,N,chunk,kernels", [
    (64, 64, 8, 128, 128, True), (4, 64, 2, 128, 256, True),
    (8, 32, 2, 128, 128, True), (4, 128, 2, 256, 128, True),
    (4, 8, 2, 16, 16, False), (4, 8, 2, 16, 128, False),
    (4, 64, 2, 128, 64, False), (4, 64, 2, 64, 128, False),
    (8, 48, 1, 128, 128, False), (2, 128, 2, 128, 128, False)],
    ids=["cell_widths", "chunk_256", "heads_of_32", "heads_of_128",
         "small", "small_one_chunk", "chunk_64", "state_64", "heads_of_48",
         "one_head_a_group"])
def test_which_shapes_take_the_kernels(H, P, G, N, chunk, kernels):
    """The choice is static, made from shapes at trace time: read it
    from the jaxpr.  One call forward; forward, the sweep of the states
    and the backward kernel under ``grad``."""
    ops = [jax.ShapeDtypeStruct(a.shape, a.dtype)
           for a in _operands(300, b=1, H=H, P=P, G=G, N=N)]

    def fwd(*a):
        return ssm_scan(*a, chunk=chunk)

    def bwd(*a):
        return jax.grad(lambda *b: fwd(*b).sum(), argnums=(0, 1, 2))(*a)

    assert _has_kernels(fwd, *ops) == (1 if kernels else 0)
    assert _has_kernels(bwd, *ops) == (3 if kernels else 0)
    assert pallas_kernels.ssm_scan_tiles(chunk, G, H // G, P, N) is kernels


def test_scan_refuses_groups_that_do_not_divide_the_heads():
    x, dt, A_log, B, C, D, dt_bias = _operands(8)
    with pytest.raises(mx.base.MXNetError, match="3 groups"):
        ssm_scan(x, dt, A_log, jnp.zeros((2, 8, 3, N)),
                 jnp.zeros((2, 8, 3, N)), D, dt_bias)


def test_convolution_first_positions_by_hand():
    """``out[t] = silu(b + sum_j w[j] x[t - 3 + j])``, zeros before the
    row: position 0 sees only its own input through the LAST tap."""
    x = jnp.arange(1.0, 11.0).reshape(1, 5, 2)        # x[t] = (2t+1, 2t+2)
    w = jnp.asarray([[1.0, 10.0, 100.0, 1000.0], [2.0, 0.0, 0.0, -1.0]])
    b = jnp.asarray([0.5, -0.5])
    sums = np.asarray([
        [0.5 + 1000 * 1, -0.5 - 2],
        [0.5 + 100 * 1 + 1000 * 3, -0.5 - 4],
        [0.5 + 10 * 1 + 100 * 3 + 1000 * 5, -0.5 - 6],
        [0.5 + 1 + 30 + 500 + 7000, -0.5 + 2 * 2 - 8]])
    out = np.asarray(ssm_conv(x, w, b))[0]
    np.testing.assert_allclose(out[:4], sums / (1 + np.exp(-sums)),
                               rtol=1e-6)


def test_gate_comes_before_the_group_norm():
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    gamma = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    v = (y * jax.nn.silu(z)).reshape(2, 5, 4, 8)
    want = (v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 32) * gamma
    got = ssm_gate_norm(y, z, gamma, groups=4, eps=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    w = y.reshape(2, 5, 4, 8)
    other = (w / jnp.sqrt(jnp.mean(w * w, -1, keepdims=True) + 1e-5)
             ).reshape(2, 5, 32) * gamma * jax.nn.silu(z)
    assert float(jnp.abs(got - other).max()) > 0.1


# ---- ``ssm_conv`` and ``ssm_gate_norm`` are one ``custom_vjp`` each
# with its backward pass written out (and, where the shapes tile, one
# Pallas pass each way: in the interpreter here).  The oracle is what
# the two operators were before: plain expressions under autodiff.
def conv_oracle(data, weight, bias):
    K, L = weight.shape[1], data.shape[1]
    padded = jnp.pad(data, ((0, 0), (K - 1, 0), (0, 0)))
    out = bias + sum(padded[:, j:j + L] * weight[:, j] for j in range(K))
    return jax.nn.silu(out).astype(data.dtype)


def gate_norm_oracle(data, gate, gamma, groups=1, eps=1e-5):
    v = data.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    g = v.reshape(v.shape[:-1] + (int(groups), -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(v.shape) * gamma.astype(jnp.float32)).astype(
        data.dtype)


def _value_and_grads(fn, operands, cotangent):
    out, vjp = jax.vjp(fn, *operands)
    return (out,) + vjp(cotangent.astype(out.dtype))


def _held_to_the_oracle(op, oracle, operands, dtype, names):
    """``op`` on ``operands`` rounded to ``dtype`` against ``oracle`` on
    the same rounded numbers in float32: the value and every gradient,
    by the norm.  float32 differs by the order of its sums; a bfloat16
    result is rounded once more, 2^-9 an element."""
    rounded = [a.astype(dtype) for a in operands]
    exact = [a.astype(jnp.float32) for a in rounded]
    w = jax.random.normal(jax.random.PRNGKey(21), rounded[0].shape[:-1]
                          + (oracle(*exact).shape[-1],))
    got = _value_and_grads(op, rounded, w)
    want = _value_and_grads(oracle, exact, w)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for name, a, b in zip(("value",) + names, got, want):
        assert a.shape == b.shape and a.dtype == dtype, name
        assert _gap(a.astype(jnp.float32), b) < tol, name


def _conv_operands(L, C, K, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (b, L, C)),
            jax.random.uniform(k[1], (C, K), minval=-0.5, maxval=0.5),
            0.1 * jax.random.normal(k[2], (C,)))


def _norm_operands(L, C, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (b, L, C)),
            jax.random.normal(k[1], (b, L, C)),
            1.0 + 0.1 * jax.random.normal(k[2], (C,)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("C", [48, 256])
@pytest.mark.parametrize("K", [1, 4, 7], ids=["one_tap", "four_taps",
                                              "seven_taps"])
@pytest.mark.parametrize("L", [1, 3, 75, 384])
def test_conv_is_the_autodiff_expression(L, K, C, dtype):
    """K of 4 and 7 are longer than the rows of 1 and 3; 384 positions
    of 256 channels take the Pallas passes (three blocks of rows), the
    other shapes the ``jnp`` form."""
    assert pallas_kernels.ssm_conv_tiles(L, C, K) is (L == 384 and C == 256)
    _held_to_the_oracle(ssm_conv, conv_oracle, _conv_operands(L, C, K),
                        dtype, ("data", "taps", "bias"))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("C,groups", [(48, 1), (48, 3), (48, 8), (256, 1),
                                      (256, 2), (256, 8)])
@pytest.mark.parametrize("L", [1, 3, 75, 384])
def test_gate_norm_is_the_autodiff_expression(L, C, groups, dtype):
    """384 positions of 256 channels in one or two groups take the
    Pallas passes; groups of 32 or 6 channels and the other lengths the
    ``jnp`` form."""
    assert pallas_kernels.ssm_norm_tiles(L, C, groups) is (
        L == 384 and C == 256 and groups < 8)
    _held_to_the_oracle(
        lambda *a: ssm_gate_norm(*a, groups=groups),
        lambda *a: gate_norm_oracle(*a, groups=groups),
        _norm_operands(L, C), dtype, ("y", "z", "gain"))


@pytest.mark.parametrize("leaf", ["taps", "bias", "gain"])
@pytest.mark.parametrize("form", ["jnp", "kernels"])
def test_conv_and_norm_leaf_gradients_by_finite_differences(leaf, form):
    """The three small leaves' gradients are sums over every position:
    the directional derivative along a random direction, central
    differences of a float64 sum of the operator's float32 result."""
    L = 75 if form == "jnp" else 128
    if leaf == "gain":
        ops, i = _norm_operands(L, 256, seed=5), 2
        fn = lambda *a: ssm_gate_norm(*a, groups=2)         # noqa: E731
        assert pallas_kernels.ssm_norm_tiles(L, 256, 2) is (form != "jnp")
    else:
        ops, i = _conv_operands(L, 256, 4, seed=5), ("taps", "bias").index(
            leaf) + 1
        fn = ssm_conv
        assert pallas_kernels.ssm_conv_tiles(L, 256, 4) is (form != "jnp")
    u = jax.random.normal(jax.random.PRNGKey(9), ops[i].shape)
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(10), ops[0].shape),
                   np.float64)

    def f(a):
        out = fn(*ops[:i], a, *ops[i + 1:])
        return float((np.asarray(out, np.float64) * w).sum())

    auto = float((jax.grad(lambda a: (fn(*ops[:i], a, *ops[i + 1:])
                                      * w.astype(np.float32)).sum())(ops[i])
                  * u).sum())
    eps = 1e-2
    numeric = (f(ops[i] + eps * u) - f(ops[i] - eps * u)) / (2 * eps)
    assert abs(auto - numeric) <= 2e-3 * max(abs(numeric), 1.0), (auto,
                                                                  numeric)


def test_conv_and_norm_keep_their_inputs_and_nothing_else():
    """What each ``custom_vjp`` carries from its forward to its backward
    pass is its operands, the very arrays: no pre-activation, no gated
    product, no statistic."""
    data, taps, bias = _conv_operands(24, 48, 4)
    out, kept = ssm._conv_silu_fwd(data, taps, bias, 0)
    assert out.shape == data.shape
    assert len(kept) == 3 and all(a is b for a, b in zip(
        kept, (data, taps, bias)))
    y, z, gain = _norm_operands(24, 48)
    out, kept = ssm._gate_norm_fwd(y, z, gain, 3, 1e-5, 0)
    assert out.shape == y.shape
    assert len(kept) == 3 and all(a is b for a, b in zip(kept, (y, z, gain)))
    # and what autodiff keeps of a call is what the rule returned
    for fn, operands in ((ssm_conv, (data, taps, bias)),
                         (lambda *a: ssm_gate_norm(*a, groups=3),
                          (y, z, gain))):
        _, vjp = jax.vjp(fn, *operands)
        held = [a for a in jax.tree_util.tree_leaves(vjp)
                if hasattr(a, "shape") and a.size > 1]
        assert sorted(a.shape for a in held) == sorted(
            a.shape for a in operands)


@pytest.mark.parametrize("L,C,K,lo,takes", [
    (8192, 6144, 4, 4096, True), (384, 256, 4, 0, True),
    (16, 128, 9, 128, True), (8, 128, 1, 0, True),
    (300, 256, 4, 0, False), (4, 256, 4, 0, False), (384, 192, 4, 0, False),
    (384, 256, 4, 64, False), (384, 256, 10, 0, False)],
    ids=["cell_widths", "three_blocks", "nine_taps", "one_slab", "ragged_rows",
         "below_a_slab", "channels_of_192", "gate_64_columns_in",
         "ten_taps"])
def test_which_shapes_take_the_conv_passes(L, C, K, lo, takes):
    assert pallas_kernels.ssm_conv_tiles(L, C, K, lo) is takes
    src = jax.ShapeDtypeStruct((1, L, lo + C + 64), jnp.float32)
    taps = jax.ShapeDtypeStruct((C, K), jnp.float32)
    bias = jax.ShapeDtypeStruct((C,), jnp.float32)

    def fwd(*a):
        return ssm._conv_silu(*a, lo)

    def bwd(*a):
        return jax.grad(lambda *b: fwd(*b).sum(), argnums=(0, 1, 2))(*a)

    assert _has_kernels(fwd, src, taps, bias) == int(takes)
    assert _has_kernels(bwd, src, taps, bias) == 2 * int(takes)


@pytest.mark.parametrize("L,C,groups,lo,takes", [
    (8192, 4096, 8, 0, True), (384, 256, 2, 0, True), (64, 512, 1, 512, True),
    (8, 4096, 1, 0, True), (300, 256, 2, 0, False), (384, 256, 8, 0, False),
    (384, 256, 3, 0, False), (384, 256, 2, 64, False)],
    ids=["cell_widths", "three_blocks", "one_group_behind_another",
         "one_slab", "ragged_rows", "groups_of_32", "groups_do_not_divide",
         "gate_64_columns_in"])
def test_which_shapes_take_the_norm_passes(L, C, groups, lo, takes):
    assert pallas_kernels.ssm_norm_tiles(L, C, groups, lo) is takes
    if C % groups:
        return
    y = jax.ShapeDtypeStruct((1, L, C), jnp.float32)
    src = jax.ShapeDtypeStruct((1, L, lo + C + 64), jnp.float32)
    gain = jax.ShapeDtypeStruct((C,), jnp.float32)

    def fwd(*a):
        return ssm._gate_norm(*a, groups, 1e-5, lo)

    def bwd(*a):
        return jax.grad(lambda *b: fwd(*b).sum(), argnums=(0, 1, 2))(*a)

    assert _has_kernels(fwd, y, src, gain) == int(takes)
    assert _has_kernels(bwd, y, src, gain) == 2 * int(takes)


def _jnp_form(fn, *operands):
    """``fn`` with both operators on their ``jnp`` expressions whatever
    the shape."""
    with mock.patch.object(ssm, "ssm_conv_tiles", lambda *a: False), \
            mock.patch.object(ssm, "ssm_norm_tiles", lambda *a: False):
        return fn(*operands)


@pytest.mark.parametrize("L,C,K,lo", [(384, 256, 4, 0), (1024, 512, 4, 512),
                                      (64, 128, 9, 128), (16, 128, 1, 0)],
                         ids=["three_blocks", "columns_behind_others",
                              "nine_taps", "one_tap"])
def test_conv_passes_are_the_jnp_form(L, C, K, lo):
    """The kernels read their columns of a wider array (the mixer's
    in-projection result), carry the rows before a block forward and the
    rows after it backward, and give the gradient in the wide array's
    shape, zeros beside their columns."""
    _, taps, bias = _conv_operands(L, C, K, seed=3)
    src = jax.random.normal(jax.random.PRNGKey(4), (2, L, lo + C + 128))
    w = jax.random.normal(jax.random.PRNGKey(5), (2, L, C))

    def run(*a):
        return _value_and_grads(lambda *b: ssm._conv_silu(*b, lo), a, w)

    assert pallas_kernels.ssm_conv_tiles(L, C, K, lo)
    got, want = run(src, taps, bias), _jnp_form(run, src, taps, bias)
    for name, a, b in zip(("value", "src", "taps", "bias"), got, want):
        assert a.shape == b.shape, name
        assert _gap(a, b) < 1e-6, name
    d_src = np.asarray(got[1])
    assert not d_src[..., :lo].any() and not d_src[..., lo + C:].any()


@pytest.mark.parametrize("L,C,groups,lo", [(384, 256, 2, 0),
                                           (1024, 1024, 8, 1024),
                                           (64, 512, 1, 0)],
                         ids=["three_blocks", "gate_behind_others",
                              "one_group"])
def test_norm_passes_are_the_jnp_form(L, C, groups, lo):
    y, _, gain = _norm_operands(L, C, seed=3)
    src = jax.random.normal(jax.random.PRNGKey(4), (2, L, lo + C + 128))
    w = jax.random.normal(jax.random.PRNGKey(5), (2, L, C))

    def run(*a):
        return _value_and_grads(
            lambda *b: ssm._gate_norm(*b, groups, 1e-5, lo), a, w)

    assert pallas_kernels.ssm_norm_tiles(L, C, groups, lo)
    got, want = run(y, src, gain), _jnp_form(run, y, src, gain)
    for name, a, b in zip(("value", "y", "src", "gain"), got, want):
        assert a.shape == b.shape, name
        assert _gap(a, b) < 1e-6, name
    d_src = np.asarray(got[2])
    assert not d_src[..., :lo].any() and not d_src[..., lo + C:].any()


def test_mixer_hands_both_operators_the_in_projections_result_whole():
    """At widths all the kernels take (two groups of two heads of 64,
    state 128, 256 positions), ``ssm_mixer`` is five Pallas calls
    forward and backward each way of three and slices neither the gate
    nor x, B, C out of its operand: the only slice left is dt's 4
    columns; and it gives what the three operators give apart."""
    H_, P_, G_, N_ = KERNEL["H"], KERNEL["P"], KERNEL["G"], KERNEL["N"]
    inner, cw = H_ * P_, H_ * P_ + 2 * G_ * N_
    k = jax.random.split(jax.random.PRNGKey(12), 4)
    data = jax.random.normal(k[0], (1, 256, inner + cw + H_))
    leaves = (0.5 * jax.random.normal(k[1], (cw, 4)),
              0.1 * jax.random.normal(k[2], (cw,)), jnp.zeros((H_,)) - 4.0,
              jnp.zeros((H_,)), jnp.ones((H_,)),
              1.0 + 0.1 * jax.random.normal(k[3], (inner,)))
    kw = dict(num_heads=H_, head_dim=P_, n_groups=G_, state_size=N_)

    def mixer(*a):
        return ssm_mixer(*a, **kw)

    def by_hand(data, cw_, cb, dtb, A_log, D, gamma):
        z, xbc, dt = jnp.split(data, (inner, inner + cw), axis=-1)
        y = ssm._kernel_scan(ssm_conv(xbc, cw_, cb), dt, A_log, D, dtb, H_,
                             G_, N_, 128)
        return ssm_gate_norm(y, z, gamma, groups=G_)

    jaxpr = str(jax.make_jaxpr(mixer)(data, *leaves))
    assert jaxpr.count("pallas_call") == 3
    sliced = [line.split("=")[0].split(":")[1].strip()
              for line in jaxpr.splitlines()
              if " slice[" in line and ":f32[1,256," in line]
    assert sliced == [f"f32[1,256,{H_}]"]

    def both(fn):
        return jax.value_and_grad(lambda *a: fn(*a).sum(),
                                  argnums=(0, 1, 2, 6))(data, *leaves)

    (got, got_g), (want, want_g) = both(mixer), both(by_hand)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_g, want_g):
        assert _gap(a, b) < 1e-6


def test_mixer_op_is_its_three_parts():
    inner, cw = H * P, H * P + 2 * G * N
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    data = jax.random.normal(k[0], (2, 24, inner + cw + H))
    leaves = (0.5 * jax.random.normal(k[1], (cw, 4)),
              0.1 * jax.random.normal(k[2], (cw,)), jnp.zeros((H,)),
              jnp.zeros((H,)), jnp.ones((H,)),
              1.0 + 0.1 * jax.random.normal(k[3], (inner,)))
    kw = dict(num_heads=H, head_dim=P, n_groups=G, state_size=N, chunk=8)

    def by_hand(data, cw_, cb, dtb, A_log, D, gamma):
        z, xbc, dt = (data[..., :inner], data[..., inner:inner + cw],
                      data[..., inner + cw:])
        xbc = ssm_conv(xbc, cw_, cb)
        x, B, C = (xbc[..., :inner], xbc[..., inner:inner + G * N],
                   xbc[..., inner + G * N:])
        y = recurrence(x.reshape(2, 24, H, P), dt, A_log,
                       B.reshape(2, 24, G, N), C.reshape(2, 24, G, N), D,
                       dtb)
        return ssm_gate_norm(y.reshape(2, 24, inner), z, gamma, groups=G)

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda *a: ssm_mixer(*a, **kw).sum(),
            argnums=(0, 1, 5))(data, *leaves)
        want, want_g = jax.value_and_grad(
            lambda *a: by_hand(*a).sum(), argnums=(0, 1, 5))(data, *leaves)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()))


def test_mixer_hands_the_kernels_the_convolutions_output_whole():
    """At widths the kernels take, ``ssm_mixer`` slices no x, B or C out
    of what the convolution wrote (the kernels fetch their columns of
    it), and gives what its three parts give by hand, bit for bit."""
    H_, P_, G_, N_ = KERNEL["H"], KERNEL["P"], KERNEL["G"], KERNEL["N"]
    inner, cw = H_ * P_, H_ * P_ + 2 * G_ * N_
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    data = jax.random.normal(k[0], (2, 300, inner + cw + H_))
    leaves = (0.5 * jax.random.normal(k[1], (cw, 4)),
              0.1 * jax.random.normal(k[2], (cw,)), jnp.zeros((H_,)) - 4.0,
              jnp.zeros((H_,)), jnp.ones((H_,)),
              1.0 + 0.1 * jax.random.normal(k[3], (inner,)))
    kw = dict(num_heads=H_, head_dim=P_, n_groups=G_, state_size=N_)

    def by_hand(data, cw_, cb, dtb, A_log, D, gamma):
        z, xbc, dt = jnp.split(data, (inner, inner + cw), axis=-1)
        x, B, C = jnp.split(ssm_conv(xbc, cw_, cb), (inner, inner + G_ * N_),
                            axis=-1)
        y = ssm_scan(x.reshape(2, 300, H_, P_), dt, A_log,
                     B.reshape(2, 300, G_, N_), C.reshape(2, 300, G_, N_),
                     D, dtb)
        return ssm_gate_norm(y.reshape(2, 300, inner), z, gamma, groups=G_)

    def both(fn):
        return jax.value_and_grad(lambda *a: fn(*a).sum(),
                                  argnums=(0, 1, 3, 4))(data, *leaves)

    jaxpr = str(jax.make_jaxpr(lambda *a: ssm_mixer(*a, **kw))(data, *leaves))
    assert jaxpr.count("pallas_call") == 1
    # one concatenate, of the per-head vectors; by hand a second one
    # packs x, B and C again
    assert jaxpr.count("concatenate") == 1
    assert str(jax.make_jaxpr(by_hand)(data, *leaves)).count(
        "concatenate") == 2
    (got, got_g), (want, want_g) = both(lambda *a: ssm_mixer(*a, **kw)), \
        both(by_hand)
    assert float(got) == float(want)
    for a, b in zip(got_g, want_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mamba2_mixer_block_shapes_and_causality():
    mx.random.seed(3)
    mixer = tb.Mamba2Mixer(32, num_heads=8, head_dim=8, state_size=16,
                           n_groups=2, conv_kernel=4, chunk=8)
    mixer.initialize(mx.init.Normal(0.3))
    shapes = {n.split("_", 1)[1]: p.shape
              for n, p in mixer.collect_params().items()}
    assert shapes == {
        "in_proj_weight": (64 + 64 + 2 * 2 * 16 + 8, 32),
        "conv_weight": (128, 4), "conv_bias": (128,), "dt_bias": (8,),
        "A_log": (8,), "D": (8,), "norm_gamma": (64,),
        "out_proj_weight": (32, 64)}
    x = np.random.RandomState(0).randn(1, 40, 32).astype(np.float32)
    other = x.copy()
    other[0, 17] += 1.0
    a, b = mixer(nd.array(x)).asnumpy(), mixer(nd.array(other)).asnumpy()
    moved = np.abs(a - b).max(-1)[0] > 1e-7
    assert a.shape == (1, 40, 32)
    assert not moved[:17].any() and moved[17:30].all()   # the state carries
    with pytest.raises(mx.base.MXNetError):
        tb.Mamba2Mixer(32, num_heads=8, head_dim=8, state_size=16, n_groups=3)


def test_attention_without_rope_leaves_q_and_k_bit_for_bit():
    """``rope=None``: the flash kernel gets the projections themselves;
    an empty dict still rotates at the op's defaults (Mellum's two kinds
    pass theirs explicitly)."""
    mx.random.seed(4)
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=8,
              compute_dtype="float32")
    plain = tb.RotaryGroupedAttention(32, rope=None, **kw)
    plain.initialize(mx.init.Normal(0.3))
    x = np.random.RandomState(1).randn(2, 16, 32).astype(np.float32)
    xs = nd.array(x)
    q = plain.q_proj(xs).reshape((2, 16, 4, 8))
    kv = plain.kv_proj(xs)
    k = nd.slice_axis(kv, axis=-1, begin=0, end=16).reshape((2, 16, 2, 8))
    v = nd.slice_axis(kv, axis=-1, begin=16, end=None).reshape((2, 16, 2, 8))
    want = plain.out_proj(nd.flash_attention(q, k, v, causal=True, window=-1)
                          .reshape((2, 16, 32))).asnumpy()
    got = plain(xs).asnumpy()
    np.testing.assert_array_equal(got, want)
    # no position signal at all: the last position's output does not
    # change when two earlier positions swap places
    swapped = x.copy()
    swapped[:, [2, 9]] = swapped[:, [9, 2]]
    np.testing.assert_allclose(plain(nd.array(swapped)).asnumpy()[:, -1],
                               got[:, -1], rtol=1e-5, atol=1e-6)
    plain._rope = {}            # the same weights, rotated at the defaults
    assert np.abs(plain(xs).asnumpy() - got).max() > 1e-3


def test_nemotron3_nano_published_sizes():
    c = _DECODER_CONFIGS["nemotron_3_nano_30b_a3b"]
    kinds = c["layer_types"]
    assert len(kinds) == 52
    assert [kinds.count(k) for k in ("mamba2", "moe", "attention")] \
        == [23, 23, 6]
    assert "".join({"mamba2": "M", "moe": "E", "attention": "*"}[k]
                   for k in kinds[:9]) == "MEMEM*EME"
    C, m = c["units"], c["mamba"]
    inner = m["num_heads"] * m["head_dim"]
    conv = inner + 2 * m["n_groups"] * m["state_size"]
    assert (C, inner, conv, inner + conv + m["num_heads"]) \
        == (2688, 4096, 6144, 10304)
    mamba = (C * (inner + conv + m["num_heads"]) + inner * C + conv * 5
             + inner + 3 * m["num_heads"])
    attn = C * 2 * (32 * 128 + 2 * 128)
    expert = 2 * C * c["expert_hidden_size"]
    moe = (C * 128 + 128 + 2 * C * c["shared_expert_hidden_size"]
           + 128 * expert)
    assert [round(x / 1e6, 2) for x in (mamba, attn, expert)] \
        == [38.74, 23.4, 9.98]
    total = (23 * mamba + 6 * attn + 23 * moe + 53 * C
             + 2 * c["vocab_size"] * C)
    assert round(total / 1e9, 1) == 31.6
    active = total - 23 * (128 - 6) * expert
    assert round(active / 1e9, 1) == 3.6       # with both vocabulary tables
    assert c["router"] == dict(scoring="sigmoid", route_scale=2.5)
    assert (c["expert_activation"], c["expert_gated"]) == ("relu2", False)
    assert "rope" not in c


SMALL = dict(vocab_size=96, units=32, num_heads=4, num_kv_heads=2, head_dim=8,
             mamba=dict(num_heads=8, head_dim=8, state_size=16, n_groups=2,
                        conv_kernel=4, chunk=8),
             num_experts=8, experts_per_token=2, expert_hidden_size=16,
             shared_expert_hidden_size=24, attention_dtype="float32")


def test_hybrid_layers_are_one_mixer_each_of_the_one_cell_class():
    lm = models.get_decoder_lm("nemotron_3_nano_30b_a3b", num_layers=9,
                               experts_held=4, first_expert=4, **SMALL)
    assert {type(c) for c in lm.cells} == {tb.DecoderCell}
    assert [type(c.mixer).__name__ for c in lm.cells] == [
        "Mamba2Mixer", "MoEFFN", "Mamba2Mixer", "MoEFFN", "Mamba2Mixer",
        "RotaryGroupedAttention", "MoEFFN", "Mamba2Mixer", "MoEFFN"]
    assert all(c.ffn is None for c in lm.cells)
    assert lm.cells[5].mixer._rope is None and lm.cells[5].mixer._window == -1
    shapes = {n.split("_", 1)[1]: (p.shape, p.grad_req)
              for n, p in lm.collect_params().items()}
    assert shapes["layer1_moe_gate_weight"] == ((32, 8), "write")
    assert shapes["layer1_moe_route_bias"] == ((8,), "null")
    assert shapes["layer1_moe_expert_w1"] == ((4, 32, 16), "write")  # not gated
    assert shapes["layer1_moe_shared_w1"] == ((32, 24), "write")
    assert shapes["layer1_moe_shared_w2"] == ((24, 32), "write")
    assert shapes["layer0_norm_gamma"] == ((32,), "write")
    assert not any("attn_norm" in n or "ffn_norm" in n for n in shapes)
    with pytest.raises(mx.base.MXNetError, match="layer_types"):
        models.get_decoder_lm("nemotron_3_nano_30b_a3b",
                              layer_types=("mamba",), **SMALL)


def test_hybrid_lm_is_causal_and_its_state_reaches_the_rows_end():
    """Changing token t changes the logits from t on, and (through the
    Mamba layers' state and the full attention) all the way."""
    mx.random.seed(6)
    lm = models.get_decoder_lm("nemotron_3_nano_30b_a3b", num_layers=9,
                               **SMALL)
    lm.initialize(mx.init.Normal(0.3))
    tokens = np.random.RandomState(5).randint(0, 96, (1, 48)).astype(np.int32)
    other = tokens.copy()
    other[0, 20] = (other[0, 20] + 1) % 96
    a, b = lm(nd.array(tokens)).asnumpy(), lm(nd.array(other)).asnumpy()
    moved = np.abs(a - b).max(-1)[0] > 1e-6
    assert a.shape == (1, 48, 96)
    assert not moved[:20].any() and moved[20:].all()
