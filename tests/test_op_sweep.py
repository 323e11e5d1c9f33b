"""Registry-wide operator correctness sweep (VERDICT r4 item 4).

Reference pattern: ``tests/python/unittest/test_operator.py`` — the
biggest single test file upstream, where (nearly) every registered op is
forward-checked against a NumPy oracle and numeric-gradient-checked
(SURVEY.md §4 row 1, ``check_numeric_gradient``).  Here the whole
``list_ops()`` registry is enumerated so a newly registered op is swept
automatically; an op may opt out only via the explicit skip tables below,
each entry with a one-line reason.

Three layers per op:
  1. forward smoke — the generated frontend runs on canonical small
     inputs; outputs are finite (float) and well-formed;
  2. NumPy oracle — where a clean numpy equivalent exists, outputs match;
  3. finite-difference gradient — every differentiable op's autograd
     gradient (the tape path) matches central differences, with
     integer/index inputs held fixed (``wrt``).
"""
import math
import zlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
import mxnet_tpu.ndarray.op as opmod
from mxnet_tpu.ops.registry import OP_REGISTRY, list_ops

# --------------------------------------------------------------- enumeration
_seen = {}
for _n in list_ops():
    _od = OP_REGISTRY[_n]
    _seen.setdefault(id(_od), _n)          # first registration = primary name
CANONICAL = sorted(_seen.values())


def _rng(name):
    # crc32, not hash(): str hashes are salted per interpreter run and
    # would make per-op inputs (and any failure) non-reproducible
    return np.random.RandomState(zlib.crc32(name.encode()) % (2 ** 31))


def _f32(rng, *shape):
    return rng.randn(*shape).astype(np.float32)


def _pos(rng, *shape):
    return (np.abs(rng.randn(*shape)) + 0.3).astype(np.float32)


def _idx(rng, n, *shape):
    """index-like float input: x.5 values so ±eps FD perturbation never
    crosses an integer boundary (the op casts to int internally)."""
    return (rng.randint(0, n, shape) + 0.5).astype(np.float32)


def _spd(rng, n, batch=()):
    m = rng.randn(*batch, n, n)
    a = m @ np.swapaxes(m, -1, -2) + n * np.eye(n)
    return a.astype(np.float32)


# --------------------------------------------------------------------- skips
# Ops the sweep does not run AT ALL (each covered elsewhere or not
# meaningfully invokable standalone).  Budget: < 10% of the registry.
FWD_SKIP = {
    "Custom": "python CustomOp trampoline; needs a registered user op "
              "(covered by tests/test_operator_custom.py)",
}

# Differentiable ops whose FD gradient check is skipped (forward still
# swept).  Each reason is a property of the op, not a TODO.
GRAD_SKIP = {
    "BlockGrad": "gradient is zero BY CONTRACT (identity forward); FD "
                 "sees the identity — asserted separately below",
    "Softmax": "SoftmaxOutput's training gradient is (p - one_hot) by "
               "contract, not d(forward)/dx (covered by test_loss)",
    "MakeLoss": "custom grad_scale gradient by contract, not "
                "d(forward)/dx (reference MakeLoss semantics)",
    "_linalg_syevd": "eigenvector gradient is ill-conditioned under FD "
                     "(sign/ordering flips at crossings)",
    "_linalg_gelqf": "LQ factor gradients are sign-ambiguous under FD",
    "RNN": "fused multi-layer kernel; 100+-element parameter vector "
           "makes FD impractical (gradients covered by test_gluon_rnn "
           "training-convergence tests)",
    "Dropout": "rng op: each FD evaluation draws a fresh mask "
               "(p=0 forward identity is asserted in the oracle)",
    "ceil": "piecewise-constant: gradient is zero a.e. and FD at a step "
            "is undefined",
    "floor": "piecewise-constant (as ceil)",
    "rint": "piecewise-constant (as ceil)",
    "round": "piecewise-constant (as ceil)",
    "trunc": "piecewise-constant (as ceil)",
    "sign": "piecewise-constant (as ceil)",
    "_shuffle": "rng op: each FD evaluation permutes differently",
    "_sample_multinomial": "rng sampler (forward distribution checked "
                           "in test_ndarray random tests)",
}

# ------------------------------------------------------------------- domains
# unary float ops needing a restricted input domain for a well-defined,
# smooth forward (name -> generator(rng) for the single input)
_DOMAIN = {
    "arccos": lambda r: (r.uniform(-0.8, 0.8, (2, 3))).astype(np.float32),
    "arcsin": lambda r: (r.uniform(-0.8, 0.8, (2, 3))).astype(np.float32),
    "arctanh": lambda r: (r.uniform(-0.8, 0.8, (2, 3))).astype(np.float32),
    "erfinv": lambda r: (r.uniform(-0.8, 0.8, (2, 3))).astype(np.float32),
    "arccosh": lambda r: (1.5 + np.abs(r.randn(2, 3))).astype(np.float32),
    "log": lambda r: _pos(r, 2, 3),
    "log2": lambda r: _pos(r, 2, 3),
    "log10": lambda r: _pos(r, 2, 3),
    "log1p": lambda r: _pos(r, 2, 3),
    "sqrt": lambda r: _pos(r, 2, 3),
    "rsqrt": lambda r: _pos(r, 2, 3),
    "cbrt": lambda r: _pos(r, 2, 3),
    "rcbrt": lambda r: _pos(r, 2, 3),
    "reciprocal": lambda r: _pos(r, 2, 3),
    "gamma": lambda r: _pos(r, 2, 3),
    "gammaln": lambda r: _pos(r, 2, 3),
    "digamma": lambda r: (1.0 + _pos(r, 2, 3)).astype(np.float32),
    # keep FD away from the |x|=1 kink / integer steps
    "abs": lambda r: (np.sign(r.randn(2, 3)) *
                      (0.3 + np.abs(r.randn(2, 3)))).astype(np.float32),
}

# ------------------------------------------------------------------- oracles
_ERF = np.vectorize(math.erf)
_GAMMA = np.vectorize(math.gamma)
_LGAMMA = np.vectorize(math.lgamma)


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# name -> callable(*np_inputs, **kwargs) returning the expected FIRST
# output as a numpy array.  Only ops with a clean numpy equivalent.
ORACLES = {
    # elementwise unary
    "abs": np.abs, "arccos": np.arccos, "arccosh": np.arccosh,
    "arcsin": np.arcsin, "arcsinh": np.arcsinh, "arctan": np.arctan,
    "arctanh": np.arctanh, "cbrt": np.cbrt, "ceil": np.ceil,
    "cos": np.cos, "cosh": np.cosh, "degrees": np.degrees,
    "erf": _ERF, "erfc": lambda x: 1.0 - _ERF(x),
    "exp": np.exp, "expm1": np.expm1, "floor": np.floor,
    "gamma": _GAMMA, "gammaln": _LGAMMA,
    "log": np.log, "log10": np.log10, "log1p": np.log1p, "log2": np.log2,
    "logical_not": lambda x: (x == 0).astype(np.float32),
    "negative": np.negative, "radians": np.radians,
    "rcbrt": lambda x: 1.0 / np.cbrt(x),
    "reciprocal": lambda x: 1.0 / x,
    "relu": lambda x: np.maximum(x, 0),
    "rint": np.rint,
    "rsqrt": lambda x: 1.0 / np.sqrt(x),
    "sigmoid": lambda x: 1.0 / (1.0 + np.exp(-x)),
    "sign": np.sign, "sin": np.sin, "sinh": np.sinh,
    "softsign": lambda x: x / (1.0 + np.abs(x)),
    "sqrt": np.sqrt, "square": np.square, "tan": np.tan, "tanh": np.tanh,
    "trunc": np.trunc,
    "hard_sigmoid": lambda x, alpha=0.2, beta=0.5:
        np.clip(alpha * x + beta, 0, 1),
    "smooth_l1": lambda x, scalar=1.0: np.where(
        np.abs(x) < 1.0 / scalar ** 2, 0.5 * (scalar * x) ** 2,
        np.abs(x) - 0.5 / scalar ** 2),
    "_copy": lambda x: x, "BlockGrad": lambda x: x, "Flatten":
        lambda x: x.reshape(x.shape[0], -1),
    "_contrib_div_sqrt_dim": lambda x: x / np.sqrt(x.shape[-1]),
    "_contrib_gelu_erf": lambda x: 0.5 * x * (1 + _ERF(x / np.sqrt(2))),
    "zeros_like": np.zeros_like, "ones_like": np.ones_like,
    "full_like": lambda x, fill_value=0.0: np.full_like(x, fill_value),
    "shape_array": lambda x: np.array(x.shape, np.int64),
    "size_array": lambda x: np.array([x.size], np.int64),
    # binary / broadcast
    "_add": np.add, "_minus": np.subtract, "_mul": np.multiply,
    "_div": np.divide, "_power": np.power,
    "broadcast_add": np.add, "broadcast_minus": np.subtract,
    "broadcast_mul": np.multiply, "broadcast_div": np.divide,
    "broadcast_maximum": np.maximum, "broadcast_minimum": np.minimum,
    "broadcast_hypot": np.hypot, "broadcast_arctan2": np.arctan2,
    "broadcast_mod": np.mod,
    "broadcast_equal": lambda a, b: (a == b).astype(np.float32),
    "broadcast_not_equal": lambda a, b: (a != b).astype(np.float32),
    "broadcast_greater": lambda a, b: (a > b).astype(np.float32),
    "broadcast_greater_equal": lambda a, b: (a >= b).astype(np.float32),
    "broadcast_lesser": lambda a, b: (a < b).astype(np.float32),
    "broadcast_lesser_equal": lambda a, b: (a <= b).astype(np.float32),
    "broadcast_logical_and": lambda a, b:
        np.logical_and(a, b).astype(np.float32),
    "broadcast_logical_or": lambda a, b:
        np.logical_or(a, b).astype(np.float32),
    "broadcast_logical_xor": lambda a, b:
        np.logical_xor(a, b).astype(np.float32),
    # scalar ops
    "_plus_scalar": lambda x, scalar=0.0: x + scalar,
    "_minus_scalar": lambda x, scalar=0.0: x - scalar,
    "_rminus_scalar": lambda x, scalar=0.0: scalar - x,
    "_mul_scalar": lambda x, scalar=1.0: x * scalar,
    "_div_scalar": lambda x, scalar=1.0: x / scalar,
    "_rdiv_scalar": lambda x, scalar=1.0: scalar / x,
    "_mod_scalar": lambda x, scalar=1.0: np.mod(x, scalar),
    "_rmod_scalar": lambda x, scalar=1.0: np.mod(scalar, x),
    "_power_scalar": lambda x, scalar=1.0: np.power(x, scalar),
    "_rpower_scalar": lambda x, scalar=1.0: np.power(scalar, x),
    "_hypot_scalar": lambda x, scalar=0.0: np.hypot(x, scalar),
    "_maximum_scalar": lambda x, scalar=0.0: np.maximum(x, scalar),
    "_minimum_scalar": lambda x, scalar=0.0: np.minimum(x, scalar),
    "_equal_scalar": lambda x, scalar=0.0: (x == scalar).astype(np.float32),
    "_not_equal_scalar": lambda x, scalar=0.0:
        (x != scalar).astype(np.float32),
    "_greater_scalar": lambda x, scalar=0.0:
        (x > scalar).astype(np.float32),
    "_greater_equal_scalar": lambda x, scalar=0.0:
        (x >= scalar).astype(np.float32),
    "_greater_scalar_rev": lambda x, scalar=0.0:
        (scalar > x).astype(np.float32),
    "_lesser_scalar": lambda x, scalar=0.0:
        (x < scalar).astype(np.float32),
    "_lesser_equal_scalar": lambda x, scalar=0.0:
        (x <= scalar).astype(np.float32),
    # reductions
    "sum": lambda x, **k: np.sum(x, axis=k.get("axis")),
    "mean": lambda x, **k: np.mean(x, axis=k.get("axis")),
    "max": lambda x, **k: np.max(x, axis=k.get("axis")),
    "min": lambda x, **k: np.min(x, axis=k.get("axis")),
    "prod": lambda x, **k: np.prod(x, axis=k.get("axis")),
    "nansum": lambda x, **k: np.nansum(x, axis=k.get("axis")),
    "nanprod": lambda x, **k: np.nanprod(x, axis=k.get("axis")),
    "norm": lambda x, **k: np.sqrt(np.sum(np.square(x))),
    "_np_cumsum": lambda x, axis=None, dtype=None: np.cumsum(x, axis=axis),
    "cumprod": lambda x, axis=None, dtype=None: np.cumprod(x, axis=axis),
    "argmax": lambda x, axis=None, keepdims=False:
        np.argmax(x, axis=axis).astype(np.float32),
    "argmin": lambda x, axis=None, keepdims=False:
        np.argmin(x, axis=axis).astype(np.float32),
    "argmax_channel": lambda x: np.argmax(x, axis=1).astype(np.float32),
    # shape / indexing
    "transpose": lambda x, axes=(): np.transpose(
        x, axes if axes else None),
    "expand_dims": lambda x, axis=0: np.expand_dims(x, axis),
    "squeeze": lambda x, axis=None: np.squeeze(x, axis),
    "flip": lambda x, axis=0: np.flip(x, axis),
    "tile": lambda x, reps=(): np.tile(x, reps),
    "repeat": lambda x, repeats=1, axis=None: np.repeat(x, repeats, axis),
    "SwapAxis": lambda x, dim1=0, dim2=0: np.swapaxes(x, dim1, dim2),
    "Reshape": lambda x, shape=(), reverse=False: x.reshape(shape),
    "broadcast_to": lambda x, shape=(): np.broadcast_to(x, shape),
    "clip": lambda x, a_min=None, a_max=None: np.clip(x, a_min, a_max),
    "diag": lambda x, k=0, axis1=0, axis2=1: np.diag(x, k),
    "sort": lambda x, axis=-1, is_ascend=True: np.sort(x, axis),
    "argsort": lambda x, axis=-1, is_ascend=True, dtype=None:
        np.argsort(x, axis, kind="stable").astype(np.float32),
    "one_hot": lambda i, depth=0, on_value=1.0, off_value=0.0, dtype=None:
        np.where(np.eye(depth)[i.astype(np.int64)] > 0, on_value,
                 off_value).astype(np.float32),
    "where": lambda c, x, y: np.where(c != 0, x, y),
    "slice_axis": lambda x, axis=0, begin=0, end=None:
        np.take(x, np.arange(begin, end if end is not None
                             else x.shape[axis]), axis=axis),
    "space_to_depth": lambda x, block_size=1: x.reshape(
        x.shape[0], x.shape[1], x.shape[2] // block_size, block_size,
        x.shape[3] // block_size, block_size).transpose(
            0, 3, 5, 1, 2, 4).reshape(
            x.shape[0], x.shape[1] * block_size ** 2,
            x.shape[2] // block_size, x.shape[3] // block_size),
    # linear algebra
    "dot": lambda a, b, transpose_a=False, transpose_b=False: np.dot(
        a.T if transpose_a else a, b.T if transpose_b else b),
    "batch_dot": lambda a, b, transpose_a=False, transpose_b=False:
        np.matmul(np.swapaxes(a, -1, -2) if transpose_a else a,
                  np.swapaxes(b, -1, -2) if transpose_b else b),
    "FullyConnected": lambda x, w, b, num_hidden=0, no_bias=False,
        flatten=True: x.reshape(x.shape[0], -1) @ w.T + b,
    "_linalg_det": lambda a: np.linalg.det(a).astype(np.float32),
    "_linalg_inverse": np.linalg.inv,
    "_linalg_potrf": np.linalg.cholesky,
    "_linalg_sumlogdiag": lambda a: np.log(np.diagonal(
        a, axis1=-2, axis2=-1)).sum(-1).astype(np.float32),
    "_linalg_extractdiag": lambda a, offset=0: np.diagonal(
        a, offset, -2, -1),
    "_linalg_makediag": lambda a, offset=0: np.apply_along_axis(
        lambda v: np.diag(v, offset), -1, a),
    "khatri_rao": lambda a, b: np.vstack(
        [np.kron(a[:, j], b[:, j]).reshape(-1, 1)
         for j in range(a.shape[1])]).reshape(a.shape[1], -1).T,
    # softmax family
    "softmax": lambda x, axis=-1, **k: _np_softmax(x, axis),
    "softmin": lambda x, axis=-1, **k: _np_softmax(-x, axis),
    "log_softmax": lambda x, axis=-1, **k: np.log(_np_softmax(x, axis)),
    "SoftmaxActivation": lambda x, mode="instance": _np_softmax(x, -1),
    "L2Normalization": lambda x, eps=1e-10, mode="instance":
        x / np.sqrt((x.reshape(x.shape[0], -1) ** 2).sum(-1)
                    + eps).reshape(-1, *([1] * (x.ndim - 1))),
    "_contrib_gelu_tanh": lambda x: 0.5 * x * (1 + np.tanh(
        np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3))),
    # fills
    "_zeros": lambda shape=(), dtype=None, ctx=None: np.zeros(shape),
    "_ones": lambda shape=(), dtype=None, ctx=None: np.ones(shape),
    "_full": lambda shape=(), value=0.0, dtype=None, ctx=None:
        np.full(shape, value),
    "_eye": lambda N=0, M=0, k=0, dtype=None, ctx=None:
        np.eye(N, M or None, k),
    "_arange": lambda start=0, stop=None, step=1.0, repeat=1, dtype=None,
        ctx=None, infer_range=False: np.arange(start, stop, step),
    "_linspace": lambda start=0, stop=1, num=50, endpoint=True,
        dtype=None, ctx=None: np.linspace(start, stop, num, endpoint),
    "Concat": lambda a, b, dim=1, num_args=0: np.concatenate([a, b], dim),
    "stack": lambda a, b, axis=0: np.stack([a, b], axis),
    "Pad": lambda x, mode="constant", pad_width=(), constant_value=0.0:
        np.pad(x, [(pad_width[2 * i], pad_width[2 * i + 1])
                   for i in range(x.ndim)], mode="constant",
               constant_values=constant_value),
    "Cast": lambda x, dtype="float32": x.astype(dtype),
    "amp_cast": lambda x, dtype="float32": x.astype(dtype),
    "Dropout": lambda x, p=0.5, **k: x,              # spec pins p=0.0
    "take": lambda a, i, axis=0, mode="clip": np.take(
        a, i.astype(np.int64), axis=axis),
    "pick": lambda x, i, axis=-1, keepdims=False, mode="clip":
        np.take_along_axis(x, i.astype(np.int64)[..., None],
                           axis=-1).squeeze(-1),
    "gather_nd": lambda d, i: d[tuple(i.astype(np.int64))],
    "unravel_index": lambda x, shape=(): np.stack(
        np.unravel_index(x.astype(np.int64), shape)),
    "_contrib_arange_like": lambda x, start=0.0, step=1.0, repeat=1,
        axis=None: np.arange(start, start + x.size * step,
                             step).reshape(x.shape),
}


# ------------------------------------------------- r5: NN-core oracles
# Independent NumPy forward implementations of the reference semantics
# (VERDICT r4 item 6: FD checks prove gradient/forward CONSISTENCY, not
# forward correctness — a conv with flipped padding passes FD).  These
# are written from the reference op contracts (src/operator/nn/*.cc),
# not transcribed from the jnp bodies.
def _np_sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _np_conv2d(x, w, b=None, kernel=(), stride=(), dilate=(), pad=(),
               num_filter=0, num_group=1, no_bias=False, **_):
    sh, sw = tuple(stride) or (1, 1)
    ph, pw = tuple(pad) or (0, 0)
    dh, dw = tuple(dilate) or (1, 1)
    n, c, H, W = x.shape
    o, cg, kh, kw = w.shape
    xp = np.pad(x.astype(np.float64),
                ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    eh, ew = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    oh = (H + 2 * ph - eh) // sh + 1
    ow = (W + 2 * pw - ew) // sw + 1
    out = np.zeros((n, o, oh, ow), np.float64)
    og = o // num_group
    for g in range(num_group):
        xs = xp[:, g * cg:(g + 1) * cg]
        ws = w[g * og:(g + 1) * og].astype(np.float64)
        for i in range(oh):
            for j in range(ow):
                patch = xs[:, :, i * sh:i * sh + eh:dh,
                           j * sw:j * sw + ew:dw]
                out[:, g * og:(g + 1) * og, i, j] = np.einsum(
                    "nchw,ochw->no", patch, ws)
    if b is not None and not no_bias:
        out = out + b.reshape(1, -1, 1, 1)
    return out


def _np_deconv2d(x, w, b=None, kernel=(), stride=(), dilate=(), pad=(),
                 adj=(), num_filter=0, num_group=1, no_bias=True,
                 target_shape=(), **_):
    sh, sw = tuple(stride) or (1, 1)
    ph, pw = tuple(pad) or (0, 0)
    ah, aw = tuple(adj) or (0, 0)
    n, ci, H, W = x.shape
    _, og, kh, kw = w.shape
    OH, OW = (H - 1) * sh + kh, (W - 1) * sw + kw
    out = np.zeros((n, og, OH, OW), np.float64)
    for i in range(H):
        for j in range(W):
            out[:, :, i * sh:i * sh + kh, j * sw:j * sw + kw] += np.einsum(
                "nc,cokl->nokl", x[:, :, i, j].astype(np.float64),
                w.astype(np.float64))
    out = out[:, :, ph:OH - ph + ah, pw:OW - pw + aw]
    if b is not None and not no_bias:
        out = out + b.reshape(1, -1, 1, 1)
    return out


def _np_pool2d(x, kernel=(), pool_type="max", stride=(), pad=(),
               global_pool=False, count_include_pad=True,
               pooling_convention="valid", **_):
    if global_pool:
        red = tuple(range(2, x.ndim))
        f = {"max": np.max, "avg": np.mean, "sum": np.sum}[pool_type]
        return f(x, axis=red, keepdims=True)
    kh, kw = kernel
    sh, sw = tuple(stride) or (1, 1)
    ph, pw = tuple(pad) or (0, 0)
    n, c, H, W = x.shape
    fill = -np.inf if pool_type == "max" else 0.0
    xp = np.pad(x.astype(np.float64),
                ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                constant_values=fill)
    oh = (H + 2 * ph - kh) // sh + 1
    ow = (W + 2 * pw - kw) // sw + 1
    out = np.zeros((n, c, oh, ow), np.float64)
    for i in range(oh):
        for j in range(ow):
            win = xp[:, :, i * sh:i * sh + kh, j * sw:j * sw + kw]
            if pool_type == "max":
                out[:, :, i, j] = win.max((2, 3))
            elif pool_type == "sum":
                out[:, :, i, j] = win.sum((2, 3))
            elif count_include_pad:
                out[:, :, i, j] = win.mean((2, 3))
            else:
                iy = max(i * sh, ph), min(i * sh + kh, H + ph)
                ix = max(j * sw, pw), min(j * sw + kw, W + pw)
                cnt = (iy[1] - iy[0]) * (ix[1] - ix[0])
                out[:, :, i, j] = win.sum((2, 3)) / cnt
    return out


def _np_im2col(x, kernel=(), stride=(1, 1), dilate=(1, 1), pad=(0, 0),
               **_):
    kh, kw = kernel
    sh, sw = tuple(stride) or (1, 1)
    dh, dw = tuple(dilate) or (1, 1)
    ph, pw = tuple(pad) or (0, 0)
    n, c, H, W = x.shape
    xp = np.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    eh, ew = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    oh = (H + 2 * ph - eh) // sh + 1
    ow = (W + 2 * pw - ew) // sw + 1
    cols = np.zeros((n, c * kh * kw, oh * ow), x.dtype)
    L = 0
    for i in range(oh):
        for j in range(ow):
            cols[:, :, L] = xp[:, :, i * sh:i * sh + eh:dh,
                               j * sw:j * sw + ew:dw].reshape(n, -1)
            L += 1
    return cols


def _np_col2im(cols, output_size=(), kernel=(), stride=(1, 1),
               dilate=(1, 1), pad=(0, 0), **_):
    H, W = output_size
    kh, kw = kernel
    sh, sw = tuple(stride) or (1, 1)
    dh, dw = tuple(dilate) or (1, 1)
    ph, pw = tuple(pad) or (0, 0)
    n, ckk, _L = cols.shape
    c = ckk // (kh * kw)
    eh, ew = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    oh = (H + 2 * ph - eh) // sh + 1
    ow = (W + 2 * pw - ew) // sw + 1
    img = np.zeros((n, c, H + 2 * ph, W + 2 * pw), np.float64)
    c6 = cols.reshape(n, c, kh, kw, oh, ow)
    for i in range(oh):
        for j in range(ow):
            img[:, :, i * sh:i * sh + eh:dh,
                j * sw:j * sw + ew:dw] += c6[:, :, :, :, i, j]
    return img[:, :, ph:H + ph, pw:W + pw]


def _np_lstm(data, params, state, state_cell, state_size=0, num_layers=1,
             mode="lstm", **_):
    """Single-layer LSTM with the cudnn packed layout (all weights, then
    all biases) and i,f,g,o gate order — reference rnn-inl.h."""
    T, N, I = data.shape
    H = state_size
    o = 0
    Wx = params[o:o + 4 * H * I].reshape(4 * H, I); o += 4 * H * I
    Wh = params[o:o + 4 * H * H].reshape(4 * H, H); o += 4 * H * H
    bx = params[o:o + 4 * H]; o += 4 * H
    bh = params[o:o + 4 * H]
    h, c = state[0].astype(np.float64), state_cell[0].astype(np.float64)
    outs = []
    for t in range(T):
        g = data[t] @ Wx.T + bx + h @ Wh.T + bh
        i_g, f_g, g_g, o_g = np.split(g, 4, axis=-1)
        c = _np_sigmoid(f_g) * c + _np_sigmoid(i_g) * np.tanh(g_g)
        h = _np_sigmoid(o_g) * np.tanh(c)
        outs.append(h)
    return np.stack(outs)


def _np_bilinear_resize(x, height=1, width=1, scale_height=None,
                        scale_width=None, mode="size",
                        align_corners=True, **_):
    n, c, h, w = x.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    ys = (np.linspace(0, h - 1, height) if align_corners and height > 1
          else (np.arange(height) + 0.5) * h / height - 0.5)
    xs = (np.linspace(0, w - 1, width) if align_corners and width > 1
          else (np.arange(width) + 0.5) * w / width - 0.5)
    ys, xs = np.clip(ys, 0, h - 1), np.clip(xs, 0, w - 1)
    y0 = np.clip(np.floor(ys).astype(int), 0, h - 1)
    x0 = np.clip(np.floor(xs).astype(int), 0, w - 1)
    y1, x1 = np.minimum(y0 + 1, h - 1), np.minimum(x0 + 1, w - 1)
    wy, wx = ys - y0, xs - x0
    rows = (x[:, :, y0, :] * (1 - wy)[None, None, :, None]
            + x[:, :, y1, :] * wy[None, None, :, None])
    return (rows[:, :, :, x0] * (1 - wx) + rows[:, :, :, x1] * wx)


def _np_groupnorm(x, gamma, beta, num_groups=1, eps=1e-5, **_):
    n, c = x.shape[:2]
    xg = x.reshape((n, num_groups, c // num_groups) + x.shape[2:])
    red = tuple(range(2, xg.ndim))
    mean = xg.mean(red, keepdims=True)
    var = xg.var(red, keepdims=True)
    out = ((xg - mean) / np.sqrt(var + eps)).reshape(x.shape)
    shp = (1, -1) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(shp) + beta.reshape(shp)


def _np_lrn(x, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **_):
    sq = np.square(x)
    half = nsize // 2
    p = np.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2))
    windows = sum(p[:, i:i + x.shape[1]] for i in range(nsize))
    return x / np.power(knorm + alpha * windows / nsize, beta)


_SCIPY = __import__("scipy.special", fromlist=["special"])

ORACLES.update({
    # activations / softmax family
    "Activation": lambda x, act_type="relu": {
        "relu": lambda v: np.maximum(v, 0),
        "sigmoid": _np_sigmoid, "tanh": np.tanh,
        "softrelu": lambda v: np.log1p(np.exp(v)),
        "softsign": lambda v: v / (1 + np.abs(v))}[act_type](x),
    "LeakyReLU": lambda x, act_type="leaky", slope=0.25, **k:
        np.where(x >= 0, x, slope * x),
    "Softmax": lambda x, label, **k: _np_softmax(x, -1),
    "MakeLoss": lambda x, **k: x,
    "softmax_cross_entropy": lambda x, label: np.array(
        -np.take_along_axis(
            np.log(_np_softmax(x, -1)),
            label.astype(np.int64)[:, None], 1).sum(), np.float32),
    # normalization (test_forward runs OUTSIDE train_mode: BatchNorm is
    # inference-mode, fix_gamma=True means gamma is forced to 1)
    "BatchNorm": lambda x, gamma, beta, mm, mv, eps=1e-3, axis=1, **k:
        (x - mm.reshape(1, -1, 1, 1)) / np.sqrt(
            mv.reshape(1, -1, 1, 1) + eps) + beta.reshape(1, -1, 1, 1),
    "LayerNorm": lambda x, gamma, beta, axis=-1, eps=1e-5, **k:
        (x - x.mean(axis, keepdims=True)) / np.sqrt(
            x.var(axis, keepdims=True) + eps) * gamma + beta,
    "InstanceNorm": lambda x, gamma, beta, eps=1e-3, **k:
        (x - x.mean((2, 3), keepdims=True)) / np.sqrt(
            x.var((2, 3), keepdims=True) + eps)
        * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1),
    "GroupNorm": _np_groupnorm,
    "LRN": _np_lrn,
    # NN layers
    "Convolution": _np_conv2d,
    "Deconvolution": _np_deconv2d,
    "Pooling": _np_pool2d,
    "im2col": _np_im2col,
    "col2im": _np_col2im,
    "RNN": _np_lstm,
    "Embedding": lambda idx, w, **k: w[np.clip(
        idx.astype(np.int64), 0, w.shape[0] - 1)],
    "UpSampling": lambda x, scale=1, sample_type="nearest", **k:
        np.repeat(np.repeat(x, scale, 2), scale, 3),
    "AdaptiveAvgPooling2D": lambda x, output_size=(): x.reshape(
        x.shape[0], x.shape[1], output_size[0],
        x.shape[2] // output_size[0], output_size[-1],
        x.shape[3] // output_size[-1]).mean((3, 5)),
    "BilinearResize2D": _np_bilinear_resize,
    "Crop": lambda x, offset=(0, 0), h_w=(0, 0), center_crop=False,
        num_args=1: x[:, :, offset[0]:offset[0] + h_w[0],
                      offset[1]:offset[1] + h_w[1]],
    # sequence ops (time-major; the 2-input frontends consume the
    # lengths — use_sequence_length defaults True here)
    "SequenceLast": lambda x, lens, **k: np.stack(
        [x[int(lens[b]) - 1, b] for b in range(x.shape[1])]),
    "SequenceMask": lambda x, lens, value=0.0, **k: np.where(
        (np.arange(x.shape[0])[:, None]
         < lens.astype(np.int64)[None, :]).reshape(
            (x.shape[0], x.shape[1]) + (1,) * (x.ndim - 2)), x, value),
    "SequenceReverse": lambda x, lens, **k: np.stack(
        [np.concatenate([x[:int(lens[b]), b][::-1], x[int(lens[b]):, b]])
         for b in range(x.shape[1])], axis=1),
    "SliceChannel": lambda x, num_outputs=1, axis=1, **k:
        np.split(x, num_outputs, axis)[0],
    # shape / indexing
    "topk": lambda x, axis=-1, k=1, ret_typ="indices", is_ascend=False,
        dtype="float32": np.argsort(
            x if is_ascend else -x, axis=-1, kind="stable")
        .take(range(k), -1).astype(np.float32),
    "split_v2": lambda x, indices_or_sections=1, axis=0, squeeze_axis=False:
        np.split(x, indices_or_sections, axis)[0],
    "crop": lambda x, begin=(), end=(), step=():
        x[tuple(slice(b, e) for b, e in zip(begin, end))],
    "depth_to_space": lambda x, block_size=1: x.reshape(
        x.shape[0], block_size, block_size,
        x.shape[1] // block_size ** 2, x.shape[2], x.shape[3]).transpose(
        0, 3, 4, 1, 5, 2).reshape(
        x.shape[0], x.shape[1] // block_size ** 2,
        x.shape[2] * block_size, x.shape[3] * block_size),
    "slice_like": lambda a, b, axes=(): a[tuple(
        slice(0, b.shape[i]) if (not axes or i in tuple(axes)) else
        slice(None) for i in range(a.ndim))],
    "broadcast_like": lambda a, b, **k: np.broadcast_to(a, b.shape),
    "broadcast_axes": lambda x, axis=(), size=(): np.broadcast_to(
        x, tuple(size[list(axis).index(i)] if i in tuple(axis) else s
                 for i, s in enumerate(x.shape))),
    "scatter_nd": lambda data, idx, shape=(): (
        lambda out: (np.add.at(out, tuple(idx.astype(np.int64)), data),
                     out)[1])(np.zeros(shape, data.dtype)),
    "all_finite": lambda *data, **k: np.array(
        [float(all(np.isfinite(d).all() for d in data))], np.float32),
    "amp_multicast": lambda *data, **k: data[0],
    "round": np.round,
    "digamma": lambda x: _SCIPY.digamma(x),
    "erfinv": lambda x: _SCIPY.erfinv(x),
    # linalg (spec feeds SPD or tril matrices)
    "_linalg_gemm": lambda A, B, C, transpose_a=False, transpose_b=False,
        alpha=1.0, beta=1.0, axis=-2: alpha * (
            (A.T if transpose_a else A) @ (B.T if transpose_b else B))
        + beta * C,
    "_linalg_gemm2": lambda A, B, transpose_a=False, transpose_b=False,
        alpha=1.0, axis=-2: alpha * (
            (A.T if transpose_a else A) @ (B.T if transpose_b else B)),
    "_linalg_potri": lambda A: np.linalg.inv(np.tril(A) @ np.tril(A).T),
    "_linalg_trmm": lambda A, B, transpose=False, rightside=False,
        lower=True, alpha=1.0: alpha * (np.tril(A) @ B),
    "_linalg_trsm": lambda A, B, transpose=False, rightside=False,
        lower=True, alpha=1.0: np.linalg.solve(np.tril(A), alpha * B),
    "_linalg_syrk": lambda A, transpose=False, alpha=1.0:
        alpha * (A.T @ A if transpose else A @ A.T),
    "_linalg_slogdet": lambda A: np.linalg.slogdet(A)[0],
    # optimizer update ops (reference: optimizer_op.cc formulas; first
    # output = new weight; spec passes no kwargs so defaults apply)
    "sgd_update": lambda w, g, lr=0.01, wd=0.0, **k:
        w - lr * (g + wd * w),
    "sgd_mom_update": lambda w, g, m, lr=0.01, momentum=0.0, wd=0.0, **k:
        w + momentum * m - lr * (g + wd * w),
    "nag_mom_update": lambda w, g, m, lr=0.01, momentum=0.0, wd=0.0, **k:
        w - lr * ((g + wd * w) + momentum
                  * (momentum * m + (g + wd * w))),
    "signsgd_update": lambda w, g, lr=0.01, wd=0.0, **k:
        w - lr * np.sign(g + wd * w),
    "signum_update": lambda w, g, m, lr=0.01, momentum=0.0, wd=0.0,
        wd_lh=0.0, **k: (1 - lr * wd_lh) * w + lr * np.sign(
            momentum * m - (1 - momentum) * (g + wd * w)),
    "rmsprop_update": lambda w, g, n, lr=0.001, gamma1=0.95,
        epsilon=1e-8, wd=0.0, **k: w - lr * (g + wd * w) / np.sqrt(
            gamma1 * n + (1 - gamma1) * np.square(g + wd * w) + epsilon),
    "adam_update": lambda w, g, m, v, lr=0.001, beta1=0.9, beta2=0.999,
        epsilon=1e-8, wd=0.0, **k: w - lr * (
            beta1 * m + (1 - beta1) * (g + wd * w)) / (np.sqrt(
                beta2 * v + (1 - beta2) * np.square(g + wd * w))
                + epsilon),
    "_adamw_update": lambda w, g, m, v, rescale, lr=0.001, beta1=0.9,
        beta2=0.999, epsilon=1e-8, wd=0.0, eta=1.0, **k: w - eta * (
            lr * (beta1 * m + (1 - beta1) * g * rescale) / (np.sqrt(
                beta2 * v + (1 - beta2) * np.square(g * rescale))
                + epsilon) + wd * w),
    "mp_sgd_update": lambda w, g, w32, lr=0.01, wd=0.0, **k:
        w32 - lr * (g + wd * w32),
    "mp_sgd_mom_update": lambda w, g, m, w32, lr=0.01, momentum=0.0,
        wd=0.0, **k: w32 + momentum * m - lr * (g + wd * w32),
    "ftrl_update": lambda w, g, z, n, lr=0.1, lamda1=0.01, beta=1.0,
        wd=0.0, **k: (lambda nn, zn: np.where(
            np.abs(zn) > lamda1,
            -(zn - np.sign(zn) * lamda1)
            / ((beta + np.sqrt(nn)) / lr + wd), 0.0))(
            n + g * g, z + g - (np.sqrt(n + g * g) - np.sqrt(n)) / lr * w),
    "rmspropalex_update": lambda w, g, n, ga, d, lr=0.001, gamma1=0.95,
        gamma2=0.9, epsilon=1e-8, wd=0.0, **k: (lambda nn, gn:
            w + gamma2 * d - lr * (g + wd * w) / np.sqrt(
                np.maximum(nn - gn * gn, 0.0) + epsilon))(
            gamma1 * n + (1 - gamma1) * (g + wd * w) ** 2,
            gamma1 * ga + (1 - gamma1) * (g + wd * w)),
    "lamb_update_phase1": lambda w, g, m, v, beta1=0.9, beta2=0.999,
        epsilon=1e-6, t=1, bias_correction=True, wd=0.0, **k:
        (beta1 * m + (1 - beta1) * g) / (1 - beta1 ** t)
        / (np.sqrt((beta2 * v + (1 - beta2) * g * g)
                   / (1 - beta2 ** t)) + epsilon) + wd * w,
    "lamb_update_phase2": lambda w, g, r1, r2, lr=0.01, lower_bound=-1.0,
        upper_bound=-1.0: w - lr * np.where(
            (r1 > 0) & (r2 > 0), r1 / r2, 1.0) * g,
    "lamb_update_states": lambda w, g, m, v, beta1=0.9, beta2=0.999,
        **k: beta1 * m + (1 - beta1) * g,
    # interleaved-matmul MHA family (reference transformer.cc layout:
    # self-att qkv (L, B, H*3*D) with per-head [q|k|v]; maps (B*H, Lq, Lk))
    "_contrib_interleaved_matmul_selfatt_qk": lambda qkv, heads=1:
        (lambda q, k: np.einsum("bqd,bkd->bqk",
                                q / np.sqrt(q.shape[-1]), k))(
            *_np_split_ileaved(qkv, heads, 3)[:2]),
    "_contrib_interleaved_matmul_selfatt_valatt": lambda qkv, att,
        heads=1: _np_heads_merge(np.einsum(
            "bqk,bkd->bqd", att, _np_split_ileaved(qkv, heads, 3)[2]),
            qkv.shape[1], heads),
    "_contrib_interleaved_matmul_encdec_qk": lambda q, kv, heads=1:
        (lambda qh, kh: np.einsum("bqd,bkd->bqk",
                                  qh / np.sqrt(qh.shape[-1]), kh))(
            _np_q_heads(q, heads), _np_split_ileaved(kv, heads, 2)[0]),
    "_contrib_interleaved_matmul_encdec_valatt": lambda kv, att, heads=1:
        _np_heads_merge(np.einsum(
            "bqk,bkd->bqd", att, _np_split_ileaved(kv, heads, 2)[1]),
            kv.shape[1], heads),
    # misc contrib
    "_contrib_boolean_mask": lambda data, index, axis=0:
        data[np.asarray(index) != 0],
    "_contrib_index_copy": lambda old, idx, new:
        (lambda o: (o.__setitem__(idx.astype(np.int64), new), o)[1])(
            old.copy()),
    "_contrib_index_array": lambda data, axes=None: np.stack(
        np.meshgrid(*[np.arange(s) for s in data.shape], indexing="ij"),
        axis=-1).astype(np.int64),
    "GridGenerator": lambda theta, transform_type="affine",
        target_shape=(): (lambda h, w: np.einsum(
            "nij,jk->nik", theta.reshape(-1, 2, 3), np.stack(
                [np.tile(np.linspace(-1, 1, w), h),
                 np.repeat(np.linspace(-1, 1, h), w),
                 np.ones(h * w)])).reshape(-1, 2, h, w))(*target_shape),
    "MultiBoxPrior": lambda *a, **k: _np_multibox_prior(*a, **k),
    # flash attention vs a dense numpy oracle — the strongest check in
    # the sweep: the Pallas online-softmax kernel against materialized
    # softmax(QK^T)V with the key-padding mask
    "_contrib_flash_selfatt": lambda qkv, vlen, heads=1, **k:
        _np_dense_selfatt(qkv, heads, vlen),
    "_contrib_flash_selfatt_nomask": lambda qkv, heads=1, **k:
        _np_dense_selfatt(qkv, heads, None),
    "_contrib_flash_attention": lambda q, k, v, causal=False, window=-1:
        _np_grouped_attention(q, k, v, causal, window),
    "_contrib_rms_norm": lambda x, g, eps=1e-6:
        x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g,
    "_contrib_rope": lambda x, **k: _np_rope(x, **k),
    # the Mamba-2 mixer's pieces: the convolution's K-term sum, the
    # recurrence one position at a time, the gate before the group norm
    "_contrib_ssm_conv": lambda x, w, b: _np_ssm_conv(x, w, b),
    "_contrib_ssm_scan": lambda *a, chunk=128: _np_ssm_scan(*a),
    "_contrib_ssm_gate_norm": lambda y, z, g, groups=1, eps=1e-5:
        _np_ssm_gate_norm(y, z, g, groups, eps),
    "_contrib_ssm_mixer": lambda *a, **k: _np_ssm_mixer(*a, **k),
    # the gated short convolution: [B | C | u] in, C * conv(B * u) out,
    # the K-term sum over a padded row; no bias, no activation
    "_contrib_gated_short_conv": lambda x, w: _np_gated_short_conv(x, w),
    # decode-path paged attention vs a per-sequence gather + dense
    # softmax (block-table indirection materialized in numpy)
    "_contrib_ragged_paged_attention": lambda q, kp, vp, bt, lens:
        _np_paged_attention(q, kp, vp, bt, lens),
    # int8 quantization formulas (reference quantize.cc symmetric scale)
    "_contrib_quantize": lambda x, mn, mx, out_type="int8":
        np.clip(np.round(x / (max(abs(mn[0]), abs(mx[0])) / 127.0)),
                -127, 127).astype(np.int8),
    "_contrib_quantize_v2": lambda x, **k: np.clip(
        np.round(x / (max(abs(x.min()), abs(x.max())) / 127.0)),
        -127, 127).astype(np.int8),
    "_contrib_dequantize": lambda q, mn, mx, out_type="float32":
        q.astype(np.float32) * (max(abs(mn[0]), abs(mx[0])) / 127.0),
    "BilinearSampler": lambda data, grid, **k:
        _np_bilinear_sampler(data, grid),
    "SpatialTransformer": lambda data, loc, target_shape=(),
        transform_type="affine", sampler_type="bilinear", **k:
        _np_bilinear_sampler(data, ORACLES["GridGenerator"](
            loc, target_shape=target_shape)),
    "CTCLoss": lambda data, label, *a, **k: _np_ctc(data, label),
    "ROIPooling": lambda data, rois, pooled_size=(), spatial_scale=1.0:
        _np_roipool(data, rois, pooled_size, spatial_scale),
    "ROIAlign": lambda data, rois, pooled_size=(), spatial_scale=1.0,
        sample_ratio=-1, **k: _np_roialign(
            data, rois, pooled_size, spatial_scale,
            sample_ratio if sample_ratio > 0 else 2),
    "_contrib_multi_lars": lambda lrs, wss, gss, wds, eta=0.001,
        eps=1e-8, rescale_grad=1.0: lrs * np.where(
            (np.sqrt(wss) > 0) & (np.sqrt(gss) * rescale_grad > 0),
            eta * np.sqrt(wss)
            / (np.sqrt(gss) * rescale_grad + wds * np.sqrt(wss) + eps),
            1.0),
    "_contrib_requantize": lambda q, mn, mx, **k: (lambda real:
        np.clip(np.round(real / (max(abs(real.min()), abs(real.max()))
                                 / 127.0)), -127, 127).astype(np.int8))(
        q.astype(np.float64) * (max(abs(mn[0]), abs(mx[0]))
                                / float(2 ** 31 - 1))),
    "_contrib_quantized_flatten": lambda x, mn, mx:
        x.reshape(x.shape[0], -1),
})


def _np_ctc(data, label):
    """Log-space alpha recursion (Graves 2006), blank = channel 0."""
    T, N, _C = data.shape
    x = data - data.max(-1, keepdims=True)
    logp = x - np.log(np.exp(x).sum(-1, keepdims=True))
    out = np.zeros(N, np.float32)
    for n in range(N):
        ext = [0]
        for v in label[n]:
            if v > 0:
                ext += [int(v), 0]
        S = len(ext)
        alpha = np.full(S, -1e30)
        alpha[0] = logp[0, n, 0]
        if S > 1:
            alpha[1] = logp[0, n, ext[1]]
        for t in range(1, T):
            new = np.full(S, -1e30)
            for s in range(S):
                best = alpha[s]
                if s >= 1:
                    best = np.logaddexp(best, alpha[s - 1])
                if s >= 2 and ext[s] != 0 and ext[s] != ext[s - 2]:
                    best = np.logaddexp(best, alpha[s - 2])
                new[s] = best + logp[t, n, ext[s]]
            alpha = new
        tot = np.logaddexp(alpha[-1], alpha[-2]) if S > 1 else alpha[-1]
        out[n] = -tot
    return out


def _np_roipool(data, rois, pooled_size, spatial_scale):
    """Reference roi_pooling.cc semantics: integer-quantized corners,
    floor/ceil bin boundaries, max over the exact pixels."""
    ph, pw = pooled_size
    _n, c, h, w = data.shape
    out = np.zeros((rois.shape[0], c, ph, pw), np.float32)
    for r, roi in enumerate(rois):
        b = int(roi[0])
        x1, y1 = round(roi[1] * spatial_scale), round(roi[2] * spatial_scale)
        x2, y2 = round(roi[3] * spatial_scale), round(roi[4] * spatial_scale)
        bh = max(y2 - y1 + 1, 1) / ph
        bw = max(x2 - x1 + 1, 1) / pw
        for i in range(ph):
            hs = min(max(int(np.floor(i * bh)) + int(y1), 0), h)
            he = min(max(int(np.ceil((i + 1) * bh)) + int(y1), 0), h)
            for j in range(pw):
                ws = min(max(int(np.floor(j * bw)) + int(x1), 0), w)
                we = min(max(int(np.ceil((j + 1) * bw)) + int(x1), 0), w)
                if he > hs and we > ws:
                    out[r, :, i, j] = data[b, :, hs:he, ws:we].max((1, 2))
    return out


def _np_roialign(data, rois, pooled_size, spatial_scale, s):
    """Bilinear sample grid of (ph*s, pw*s), mean per bin (reference:
    contrib/roi_align.cc, edge-clamped sampling)."""
    ph, pw = pooled_size
    _n, c, h, w = data.shape
    out = np.zeros((rois.shape[0], c, ph, pw), np.float64)
    for r, roi in enumerate(rois):
        b = int(roi[0])
        x1, y1 = roi[1] * spatial_scale, roi[2] * spatial_scale
        x2, y2 = roi[3] * spatial_scale, roi[4] * spatial_scale
        rw, rh = max(x2 - x1, 1.0), max(y2 - y1, 1.0)
        ys = y1 + rh * (np.arange(ph * s) + 0.5) / (ph * s)
        xs = x1 + rw * (np.arange(pw * s) + 0.5) / (pw * s)
        y0 = np.clip(np.floor(ys).astype(int), 0, h - 1)
        x0 = np.clip(np.floor(xs).astype(int), 0, w - 1)
        y1i, x1i = np.clip(y0 + 1, 0, h - 1), np.clip(x0 + 1, 0, w - 1)
        wy, wx = ys - y0, xs - x0
        img = data[b].astype(np.float64)
        v = (img[:, y0][:, :, x0] * ((1 - wy)[:, None] * (1 - wx)[None, :])
             + img[:, y0][:, :, x1i] * ((1 - wy)[:, None] * wx[None, :])
             + img[:, y1i][:, :, x0] * (wy[:, None] * (1 - wx)[None, :])
             + img[:, y1i][:, :, x1i] * (wy[:, None] * wx[None, :]))
        out[r] = v.reshape(c, ph, s, pw, s).mean((2, 4))
    return out


def _np_dense_selfatt(qkv, heads, vlen):
    L, B, H3D = qkv.shape
    D = H3D // (heads * 3)
    x = qkv.reshape(L, B, heads, 3, D)
    q, k, v = (x[:, :, :, i, :].transpose(1, 2, 0, 3)
               .reshape(B * heads, L, D) for i in range(3))
    s = np.einsum("bqd,bkd->bqk", q, k) / np.sqrt(D)
    if vlen is not None:
        lens = np.repeat(vlen.astype(np.int64), heads)
        mask = np.arange(L)[None, None, :] >= lens[:, None, None]
        s = np.where(mask, -np.inf, s)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    out = np.einsum("bqk,bkd->bqd", p, v)
    return out.reshape(B, heads, L, D).transpose(2, 0, 1, 3).reshape(
        L, B, heads * D)


def _np_paged_attention(q, k_pages, v_pages, block_tables, lens):
    """Gather each sequence's pages through its block table, then dense
    masked softmax attention (the ragged-paged-attention contract:
    context_lens == 0 slots yield zeros)."""
    B, H, D = q.shape
    bt = block_tables.astype(np.int64)
    out = np.zeros_like(q)
    for b in range(B):
        L = int(lens[b])
        if L == 0:
            continue
        k = k_pages[bt[b]].reshape(-1, H, D)[:L]
        v = v_pages[bt[b]].reshape(-1, H, D)[:L]
        s = np.einsum("hd,thd->ht", q[b], k) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out[b] = np.einsum("ht,thd->hd", p, v)
    return out


def _np_bilinear_sampler(data, grid):
    """grid in [-1,1], (B, 2, Ho, Wo) [x; y] -> gather-lerp from
    (B, C, H, W) with edge clamp (reference bilinear_sampler.cc)."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1) * (h - 1) / 2.0
    x0 = np.floor(gx).astype(int)
    y0 = np.floor(gy).astype(int)
    wx, wy = gx - x0, gy - y0
    out = np.zeros((n, c) + gx.shape[1:], np.float64)
    for (dy, dx, wgt) in ((0, 0, (1 - wx) * (1 - wy)),
                          (0, 1, wx * (1 - wy)),
                          (1, 0, (1 - wx) * wy), (1, 1, wx * wy)):
        yy = np.clip(y0 + dy, 0, h - 1)
        xx = np.clip(x0 + dx, 0, w - 1)
        for b in range(n):
            out[b] += data[b][:, yy[b], xx[b]] * wgt[b][None]
    return out


def _np_split_ileaved(x, heads, n):
    """(L, B, H*n*D) -> n arrays of (B*H, L, D) (transformer.cc
    interleaved layout)."""
    L, B, HnD = x.shape
    D = HnD // (heads * n)
    parts = x.reshape(L, B, heads, n, D)
    return [parts[:, :, :, i, :].transpose(1, 2, 0, 3)
            .reshape(B * heads, L, D) for i in range(n)]


def _np_q_heads(q, heads):
    Lq, B, HD = q.shape
    D = HD // heads
    return q.reshape(Lq, B, heads, D).transpose(1, 2, 0, 3).reshape(
        B * heads, Lq, D)


def _np_heads_merge(out, B, heads):
    BH, Lq, D = out.shape
    return out.reshape(B, heads, Lq, D).transpose(2, 0, 1, 3).reshape(
        Lq, B, heads * D)


def _np_multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                       steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    """Reference multibox_prior.cc enumeration: per cell, one box per
    size plus one per extra ratio at sizes[0]; w carries the in_h/in_w
    aspect factor so ratio-1 boxes are square in image space."""
    _b, _c, H, W = data.shape
    out = []
    for i in range(H):
        cy = (i + offsets[1]) / H
        for j in range(W):
            cx = (j + offsets[0]) / W
            for s in sizes:
                w = s * H / W / 2
                h = s / 2
                out.append([cx - w, cy - h, cx + w, cy + h])
            for r in ratios[1:]:
                w = sizes[0] * np.sqrt(r) * H / W / 2
                h = sizes[0] / np.sqrt(r) / 2
                out.append([cx - w, cy - h, cx + w, cy + h])
    arr = np.array(out, np.float32)
    if clip:
        arr = np.clip(arr, 0.0, 1.0)
    return arr[None]


# -------------------------------------------------------------------- specs
# Per-op canonical inputs.  An entry is dict(inputs=callable(rng) ->
# [np arrays], kwargs={}, wrt=[indices FD-checked]); ops absent from
# SPECS get arity-default float inputs (with _DOMAIN overrides).
def _i8(rng, *shape):
    return np.clip(rng.randn(*shape) * 50, -127, 127).astype(np.int8)


_MINMAX = lambda: [np.array([-1.0], np.float32), np.array([1.0], np.float32)]

def _np_grouped_attention(q, k, v, causal, window):
    """Dense softmax(QK^T)V with query head h reading key/value head
    h // group, under the causal (and window) mask."""
    B, L, H, D = q.shape
    group = H // k.shape[2]
    t = np.arange(L)
    seen = np.ones((L, L), bool)
    if causal:
        seen &= t[None, :] <= t[:, None]
    if window > 0:
        seen &= t[None, :] > t[:, None] - window
    out = np.zeros(q.shape, np.float64)
    for h in range(H):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // group]) \
            / np.sqrt(D)
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, h] = np.einsum("bqk,bkd->bqd",
                                 p / p.sum(-1, keepdims=True),
                                 v[:, :, h // group])
    return out


def _np_silu(x):
    return x / (1.0 + np.exp(-x))


def _np_ssm_conv(x, w, b):
    K, L = w.shape[1], x.shape[1]
    xp = np.concatenate([np.zeros_like(x[:, :K - 1]), x], axis=1)
    return _np_silu(b + sum(w[:, j] * xp[:, j:j + L] for j in range(K)))


def _np_gated_short_conv(x, w):
    C, K, L = w.shape[0], w.shape[1], x.shape[1]
    v = x[..., :C] * x[..., 2 * C:]
    vp = np.concatenate([np.zeros_like(v[:, :K - 1]), v], axis=1)
    return x[..., C:2 * C] * sum(w[:, j] * vp[:, j:j + L] for j in range(K))


def _np_ssm_scan(x, dt, A_log, B, C, D, dt_bias):
    """h_t = exp(delta_t A) h_{t-1} + delta_t x_t B_t^T, y_t = h_t C_t +
    D x_t, one position at a time."""
    b, L, H, P = x.shape
    R = H // B.shape[2]
    delta = np.log1p(np.exp(dt + dt_bias))
    A = -np.exp(A_log)
    Bh, Ch = np.repeat(B, R, axis=2), np.repeat(C, R, axis=2)
    h = np.zeros((b, H, P, B.shape[-1]))
    y = np.zeros(x.shape)
    for t in range(L):
        h = (np.exp(delta[:, t] * A)[..., None, None] * h
             + (delta[:, t, :, None] * x[:, t])[..., None]
             * Bh[:, t, :, None, :])
        y[:, t] = np.einsum("bhpn,bhn->bhp", h, Ch[:, t])
    return y + D[:, None] * x


def _np_ssm_gate_norm(y, z, g, groups=1, eps=1e-5):
    v = (y * _np_silu(z)).reshape(y.shape[:-1] + (groups, -1))
    v = v / np.sqrt((v * v).mean(-1, keepdims=True) + eps)
    return v.reshape(y.shape) * g


def _np_ssm_mixer(data, cw, cb, dtb, A_log, D, g, num_heads, head_dim,
                  n_groups, state_size, chunk=128, eps=1e-5):
    H, P, G, N = num_heads, head_dim, n_groups, state_size
    inner = H * P
    b, L, W = data.shape
    z, xbc, dt = data[..., :inner], data[..., inner:W - H], data[..., W - H:]
    xbc = _np_ssm_conv(xbc, cw, cb)
    y = _np_ssm_scan(xbc[..., :inner].reshape(b, L, H, P), dt, A_log,
                     xbc[..., inner:inner + G * N].reshape(b, L, G, N),
                     xbc[..., inner + G * N:].reshape(b, L, G, N), D, dtb)
    return _np_ssm_gate_norm(y.reshape(b, L, inner), z, g, G, eps)


def _np_rope(x, theta=10000.0, yarn_factor=0.0, yarn_original_max=0,
             yarn_beta_fast=32.0, yarn_beta_slow=1.0, attention_factor=1.0):
    """Rotary positions written out pair by pair (the YaRN blend as the
    ``transformers`` library's ``_compute_yarn_parameters``)."""
    import math
    D = x.shape[-1]
    out = np.zeros(x.shape, np.float64)
    for i in range(D // 2):
        f = theta ** (-2.0 * i / D)
        if yarn_factor:
            dim = lambda rot: D * math.log(        # noqa: E731
                yarn_original_max / (rot * 2 * math.pi)) \
                / (2 * math.log(theta))
            low = max(math.floor(dim(yarn_beta_fast)), 0)
            high = min(math.ceil(dim(yarn_beta_slow)), D - 1)
            high += 0.001 * (low == high)
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            f = f / yarn_factor * ramp + f * (1.0 - ramp)
        for t in range(x.shape[1]):
            c = math.cos(t * f) * attention_factor
            sn = math.sin(t * f) * attention_factor
            a, b = x[:, t, :, i], x[:, t, :, i + D // 2]
            out[:, t, :, i] = a * c - b * sn
            out[:, t, :, i + D // 2] = b * c + a * sn
    return out


SPECS = {
    # ---------------- NN layers
    "Activation": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "AdaptiveAvgPooling2D": dict(inputs=lambda r: [_f32(r, 1, 2, 6, 6)],
                                 kwargs=dict(output_size=(2, 2))),
    "BatchNorm": dict(
        inputs=lambda r: [_f32(r, 2, 3, 4, 4), _pos(r, 3), _f32(r, 3),
                          _f32(r, 3), _pos(r, 3)],
        wrt=[0, 2]),   # batch stats: moving_* unused in train fwd
    "BilinearResize2D": dict(inputs=lambda r: [_f32(r, 1, 2, 4, 4)],
                             kwargs=dict(height=6, width=6)),
    "BilinearSampler": dict(
        inputs=lambda r: [_f32(r, 1, 2, 4, 4),
                          np.clip(r.randn(1, 2, 3, 3), -0.9,
                                  0.9).astype(np.float32)]),
    "CTCLoss": dict(
        inputs=lambda r: [_f32(r, 4, 2, 5),
                          np.array([[1, 2], [2, 1]], np.float32)],
        wrt=[0]),
    "Concat": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 2, 3)],
                   kwargs=dict(dim=1, num_args=2)),
    "Convolution": dict(
        inputs=lambda r: [_f32(r, 1, 2, 5, 5), _f32(r, 3, 2, 3, 3),
                          _f32(r, 3)],
        kwargs=dict(kernel=(3, 3), num_filter=3)),
    "Correlation": dict(
        inputs=lambda r: [_f32(r, 1, 2, 4, 4), _f32(r, 1, 2, 4, 4)]),
    "Crop": dict(inputs=lambda r: [_f32(r, 1, 2, 6, 6)],
                 kwargs=dict(h_w=(4, 4), num_args=1)),
    "Deconvolution": dict(
        inputs=lambda r: [_f32(r, 1, 3, 4, 4), _f32(r, 3, 2, 3, 3)],
        kwargs=dict(kernel=(3, 3), num_filter=2)),
    "Dropout": dict(inputs=lambda r: [_f32(r, 2, 3)],
                    kwargs=dict(p=0.0)),
    "Embedding": dict(
        inputs=lambda r: [_idx(r, 5, 2, 3), _f32(r, 5, 4)],
        kwargs=dict(input_dim=5, output_dim=4), wrt=[1]),
    "FullyConnected": dict(
        inputs=lambda r: [_f32(r, 2, 3), _f32(r, 4, 3), _f32(r, 4)],
        kwargs=dict(num_hidden=4)),
    "GridGenerator": dict(inputs=lambda r: [_f32(r, 1, 6)],
                          kwargs=dict(target_shape=(3, 3))),
    "GroupNorm": dict(
        inputs=lambda r: [_f32(r, 2, 4, 3, 3), _pos(r, 4), _f32(r, 4)],
        kwargs=dict(num_groups=2)),
    "InstanceNorm": dict(
        inputs=lambda r: [_f32(r, 2, 3, 4, 4), _pos(r, 3), _f32(r, 3)]),
    "L2Normalization": dict(inputs=lambda r: [_f32(r, 2, 3, 4)]),
    "LRN": dict(inputs=lambda r: [_f32(r, 1, 3, 4, 4)],
                kwargs=dict(nsize=3)),
    "LayerNorm": dict(
        inputs=lambda r: [_f32(r, 2, 3, 4), _pos(r, 4), _f32(r, 4)]),
    "LeakyReLU": dict(inputs=lambda r: [
        (np.sign(r.randn(2, 3)) * (0.3 + np.abs(r.randn(2, 3))))
        .astype(np.float32)]),
    "Pad": dict(inputs=lambda r: [_f32(r, 1, 2, 3, 3)],
                kwargs=dict(mode="constant",
                            pad_width=(0, 0, 0, 0, 1, 1, 1, 1))),
    "Pooling": dict(inputs=lambda r: [_f32(r, 1, 2, 4, 4)],
                    kwargs=dict(kernel=(2, 2), pool_type="avg")),
    "RNN": dict(
        inputs=lambda r: [_f32(r, 3, 2, 4), _f32(r, 108) * 0.1,
                          _f32(r, 1, 2, 3), _f32(r, 1, 2, 3)],
        kwargs=dict(state_size=3, num_layers=1, mode="lstm")),
    "ROIAlign": dict(
        inputs=lambda r: [_f32(r, 1, 2, 6, 6),
                          np.array([[0, 0.5, 0.5, 3.5, 3.5],
                                    [0, 1.0, 1.0, 4.0, 4.0]],
                                   np.float32)],
        kwargs=dict(pooled_size=(2, 2)), wrt=[0]),
    "ROIPooling": dict(
        inputs=lambda r: [_f32(r, 1, 2, 6, 6),
                          np.array([[0, 0, 0, 3, 3]], np.float32)],
        kwargs=dict(pooled_size=(2, 2)), wrt=[0]),
    "Reshape": dict(inputs=lambda r: [_f32(r, 2, 3)],
                    kwargs=dict(shape=(3, 2))),
    "SequenceLast": dict(
        inputs=lambda r: [_f32(r, 3, 2, 4),
                          np.array([1.5, 2.5], np.float32)], wrt=[0]),
    "SequenceMask": dict(
        inputs=lambda r: [_f32(r, 3, 2, 4),
                          np.array([1.5, 2.5], np.float32)], wrt=[0]),
    "SequenceReverse": dict(
        inputs=lambda r: [_f32(r, 3, 2, 4),
                          np.array([1.5, 2.5], np.float32)], wrt=[0]),
    "SliceChannel": dict(inputs=lambda r: [_f32(r, 2, 4, 3)],
                         kwargs=dict(num_outputs=2, axis=1)),
    "Softmax": dict(
        inputs=lambda r: [_f32(r, 4, 5), _idx(r, 5, 4)], wrt=[0]),
    "SoftmaxActivation": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "SpatialTransformer": dict(
        inputs=lambda r: [_f32(r, 1, 2, 5, 5),
                          np.array([[1.0, 0.1, 0.0, -0.1, 1.0, 0.0]],
                                   np.float32)],
        kwargs=dict(target_shape=(4, 4))),
    "SwapAxis": dict(inputs=lambda r: [_f32(r, 2, 3)],
                     kwargs=dict(dim1=0, dim2=1)),
    "UpSampling": dict(inputs=lambda r: [_f32(r, 1, 2, 3, 3)],
                       kwargs=dict(scale=2, sample_type="nearest",
                                   num_args=1)),
    # ---------------- detection (forward-only; diff=False)
    "MultiBoxPrior": dict(inputs=lambda r: [_f32(r, 1, 3, 4, 4)],
                          kwargs=dict(sizes=(0.5,), ratios=(1.0, 2.0))),
    "MultiBoxDetection": dict(
        inputs=lambda r: [np.abs(r.rand(1, 2, 4)).astype(np.float32),
                          _f32(r, 1, 16),
                          np.abs(r.rand(1, 4, 4)).astype(np.float32)]),
    "MultiBoxTarget": dict(
        inputs=lambda r: [np.abs(r.rand(1, 4, 4)).astype(np.float32),
                          np.array([[[1, 0.1, 0.1, 0.4, 0.4, 0]]],
                                   np.float32),
                          np.abs(r.rand(1, 2, 4)).astype(np.float32)]),
    # ---------------- contrib
    "_contrib_boolean_mask": dict(
        inputs=lambda r: [_f32(r, 4, 3),
                          np.array([1, 0, 1, 1], np.float32)]),
    "_contrib_index_array": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "_contrib_index_copy": dict(
        inputs=lambda r: [_f32(r, 4, 3), np.array([1.5, 2.5], np.float32),
                          _f32(r, 2, 3)], wrt=[0, 2]),
    "_contrib_flash_selfatt": dict(
        inputs=lambda r: [_f32(r, 4, 2, 12),
                          np.array([3.5, 4.0], np.float32)],
        kwargs=dict(heads=2), wrt=[0], rtol=3e-2, atol=3e-3),
    # q (B,H,D); K/V page pools (pages, page_size, H, D); block tables
    # (B, pages_per_seq) and context lens as x.5 floats (cast to int32
    # inside); forward-only (decode-path op, differentiable=False)
    "_contrib_ragged_paged_attention": dict(
        inputs=lambda r: [_f32(r, 2, 2, 4), _f32(r, 5, 2, 2, 4),
                          _f32(r, 5, 2, 2, 4), _idx(r, 5, 2, 3),
                          np.array([4.5, 1.5], np.float32)]),
    "_contrib_flash_selfatt_nomask": dict(
        inputs=lambda r: [_f32(r, 4, 2, 12)], kwargs=dict(heads=2),
        rtol=3e-2, atol=3e-3),
    "_contrib_interleaved_matmul_selfatt_qk": dict(
        inputs=lambda r: [_f32(r, 4, 2, 12)], kwargs=dict(heads=2)),
    "_contrib_interleaved_matmul_selfatt_valatt": dict(
        inputs=lambda r: [_f32(r, 4, 2, 12), _pos(r, 4, 4, 4)],
        kwargs=dict(heads=2)),
    "_contrib_interleaved_matmul_encdec_qk": dict(
        inputs=lambda r: [_f32(r, 3, 2, 8), _f32(r, 4, 2, 16)],
        kwargs=dict(heads=2)),
    "_contrib_interleaved_matmul_encdec_valatt": dict(
        inputs=lambda r: [_f32(r, 4, 2, 16), _pos(r, 4, 3, 4)],
        kwargs=dict(heads=2)),
    # x, router (C, E), w1 (held, C, 2H) [gate | up], w2 (held, H, C):
    # experts 1..2 of 3 held, 2 a token
    "_contrib_moe_ffn": dict(
        inputs=lambda r: [_f32(r, 4, 3), _f32(r, 3, 3), _f32(r, 2, 3, 10),
                          _f32(r, 2, 5, 3)],
        kwargs=dict(experts_per_token=2, first_expert=1, activation="silu",
                    gated=True),
        rtol=3e-2, atol=3e-3),
    # x, router (C, E), the choice bias (E,): sigmoid scores, 2 of 5
    # chosen by score + bias, weighted by score, renormalised, scaled
    "_contrib_moe_topk_route": dict(
        inputs=lambda r: [_f32(r, 4, 3), _f32(r, 3, 5), _f32(r, 5)],
        kwargs=dict(experts_per_token=2, scoring="sigmoid", scale=2.5)),
    # (b, L, C), taps (C, 4), bias (C,)
    "_contrib_ssm_conv": dict(
        inputs=lambda r: [_f32(r, 2, 6, 5), _f32(r, 5, 4), _f32(r, 5)]),
    # (b, L, 3C) [B | C | u], taps (C, 3)
    "_contrib_gated_short_conv": dict(
        inputs=lambda r: [_f32(r, 2, 7, 15), _f32(r, 5, 3)]),
    # x (b, L, H, P), dt (b, L, H), A_log (H,), B and C (b, L, G, N),
    # D and dt_bias (H,): 4 heads over 2 groups, 7 positions in chunks
    # of 3
    "_contrib_ssm_scan": dict(
        inputs=lambda r: [_f32(r, 1, 7, 4, 3), _f32(r, 1, 7, 4), _f32(r, 4),
                          _f32(r, 1, 7, 2, 5), _f32(r, 1, 7, 2, 5),
                          _f32(r, 4), _f32(r, 4)],
        kwargs=dict(chunk=3)),
    "_contrib_ssm_gate_norm": dict(
        inputs=lambda r: [_f32(r, 2, 3, 8), _f32(r, 2, 3, 8), _pos(r, 8)],
        kwargs=dict(groups=2)),
    # [z | x B C | dt] of widths 6 | 6 + 2 * 4 | 2: 2 heads of 3, one
    # group, state 4
    "_contrib_ssm_mixer": dict(
        inputs=lambda r: [_f32(r, 1, 7, 22), _f32(r, 14, 4), _f32(r, 14),
                          _f32(r, 2), _f32(r, 2), _f32(r, 2), _pos(r, 6)],
        kwargs=dict(num_heads=2, head_dim=3, n_groups=1, state_size=4,
                    chunk=3)),
    "_contrib_rms_norm": dict(inputs=lambda r: [_f32(r, 2, 3, 8),
                                                _pos(r, 8)]),
    "_contrib_rope": dict(
        inputs=lambda r: [_f32(r, 1, 5, 2, 8)],
        kwargs=dict(theta=100.0, yarn_factor=4.0, yarn_original_max=64,
                    attention_factor=1.1)),
    # q (B, L, H, D), k and v (B, L, Hkv, D): 2 query heads a key/value
    # head, window 3
    "_contrib_flash_attention": dict(
        inputs=lambda r: [_f32(r, 1, 8, 4, 8), _f32(r, 1, 8, 2, 8),
                          _f32(r, 1, 8, 2, 8)],
        kwargs=dict(causal=True, window=3), rtol=3e-2, atol=3e-3),
    "_contrib_multi_lars": dict(
        inputs=lambda r: [_pos(r, 3), _pos(r, 3), _pos(r, 3),
                          _pos(r, 3)]),
    "_contrib_arange_like": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    # ---------------- quantization (int8; forward-only, diff=False)
    "_contrib_quantize": dict(
        inputs=lambda r: [_f32(r, 2, 3)] + _MINMAX()),
    "_contrib_quantize_v2": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "_contrib_dequantize": dict(
        inputs=lambda r: [_i8(r, 2, 3)] + _MINMAX()),
    "_contrib_requantize": dict(
        inputs=lambda r: [(r.randn(2, 3) * 1000).astype(np.int32)]
        + _MINMAX()),
    "_contrib_quantized_flatten": dict(
        inputs=lambda r: [_i8(r, 1, 2, 3)] + _MINMAX()),
    "_contrib_quantized_act": dict(
        inputs=lambda r: [_i8(r, 2, 3)] + _MINMAX()),
    "_contrib_quantized_pooling": dict(
        inputs=lambda r: [_i8(r, 1, 2, 4, 4)] + _MINMAX(),
        kwargs=dict(kernel=(2, 2))),
    "_contrib_quantized_conv": dict(
        inputs=lambda r: [_i8(r, 1, 2, 4, 4), _i8(r, 3, 2, 3, 3),
                          (r.randn(3) * 10).astype(np.int32)]
        + _MINMAX() * 3,
        kwargs=dict(kernel=(3, 3), num_filter=3)),
    "_contrib_quantized_fully_connected": dict(
        inputs=lambda r: [_i8(r, 2, 6), _i8(r, 4, 6),
                          (r.randn(4) * 10).astype(np.int32)]
        + _MINMAX() * 3,
        kwargs=dict(num_hidden=4)),
    # ---------------- linalg
    "_linalg_det": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_slogdet": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_inverse": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_potrf": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_potri": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_sumlogdiag": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_syevd": dict(inputs=lambda r: [_spd(r, 3)]),
    "_linalg_gelqf": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "_linalg_syrk": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "_linalg_extractdiag": dict(inputs=lambda r: [_f32(r, 3, 3)]),
    "_linalg_makediag": dict(inputs=lambda r: [_f32(r, 3)]),
    "_linalg_gemm": dict(
        inputs=lambda r: [_f32(r, 2, 3), _f32(r, 3, 4), _f32(r, 2, 4)]),
    "_linalg_gemm2": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 3, 4)]),
    "_linalg_trmm": dict(
        inputs=lambda r: [np.tril(_spd(r, 3)), _f32(r, 3, 3)]),
    "_linalg_trsm": dict(
        inputs=lambda r: [np.tril(_spd(r, 3)), _f32(r, 3, 3)]),
    # ---------------- optimizer update ops (first output = new weight)
    "sgd_update": dict(inputs=lambda r: [_f32(r, 4), _f32(r, 4)]),
    "signsgd_update": dict(inputs=lambda r: [_f32(r, 4), _f32(r, 4)],
                           grad=False,
                           grad_reason="sign() of grad: piecewise-const"),
    "sgd_mom_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4)]),
    "mp_sgd_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4)]),
    "mp_sgd_mom_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4),
                          _f32(r, 4)]),
    "nag_mom_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4)]),
    "signum_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4)],
        grad=False, grad_reason="sign() of momentum: piecewise-const"),
    "adam_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4), _pos(r, 4)]),
    "_adamw_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4), _pos(r, 4),
                          np.array([1.0], np.float32)]),
    "ftrl_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4), _pos(r, 4)]),
    "rmsprop_update": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _pos(r, 4)]),
    "rmspropalex_update": dict(
        # consistent running stats: n >= g_acc^2 (true for states evolved
        # from zero; keeps the Graves-RMSProp radicand positive so the
        # FD check probes the smooth region)
        inputs=lambda r: (lambda ga: [_f32(r, 4), _f32(r, 4),
                                      ga ** 2 + _pos(r, 4), ga,
                                      _f32(r, 4)])(_f32(r, 4))),
    "lamb_update_phase1": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4), _pos(r, 4)]),
    "lamb_update_phase2": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4),
                          np.array([1.3], np.float32),
                          np.array([0.7], np.float32)]),
    "lamb_update_states": dict(
        inputs=lambda r: [_f32(r, 4), _f32(r, 4), _f32(r, 4), _pos(r, 4)]),
    # ---------------- indexing / misc
    "dot": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 3, 4)]),
    "batch_dot": dict(inputs=lambda r: [_f32(r, 2, 2, 3),
                                        _f32(r, 2, 3, 4)]),
    "_power": dict(inputs=lambda r: [_pos(r, 2, 3), _f32(r, 2, 3)]),
    "_rmod_scalar": dict(
        inputs=lambda r: [(1.2 + np.abs(r.randn(2, 3)) % 1.5)
                          .astype(np.float32)]),
    "broadcast_mod": dict(
        inputs=lambda r: [_f32(r, 2, 3) * 2.0,
                          (1.5 + np.abs(r.randn(1, 3)) % 1.4)
                          .astype(np.float32)]),
    "take": dict(inputs=lambda r: [_f32(r, 4, 3), _idx(r, 4, 5)],
                 wrt=[0]),
    "pick": dict(inputs=lambda r: [_f32(r, 3, 4), _idx(r, 4, 3)],
                 wrt=[0]),
    "gather_nd": dict(
        inputs=lambda r: [_f32(r, 3, 4),
                          np.array([[0.5, 1.5], [1.5, 2.5]], np.float32)],
        wrt=[0]),
    "scatter_nd": dict(
        inputs=lambda r: [_f32(r, 2, 3),
                          np.array([[0.5, 1.5]], np.float32)],
        kwargs=dict(shape=(2, 3)), wrt=[0]),
    "_contrib_index_array_2": None,      # placeholder never hit
    "one_hot": dict(inputs=lambda r: [_idx(r, 4, 3)],
                    kwargs=dict(depth=4)),
    "where": dict(
        inputs=lambda r: [(r.rand(2, 3) > 0.5).astype(np.float32),
                          _f32(r, 2, 3), _f32(r, 2, 3)], wrt=[1, 2]),
    "softmax_cross_entropy": dict(
        inputs=lambda r: [_f32(r, 3, 4), _idx(r, 4, 3)], wrt=[0]),
    "broadcast_like": dict(inputs=lambda r: [_f32(r, 1, 3), _f32(r, 2, 3)],
                           wrt=[0]),
    "slice_like": dict(inputs=lambda r: [_f32(r, 4, 5), _f32(r, 2, 3)],
                       wrt=[0]),
    "broadcast_axes": dict(inputs=lambda r: [_f32(r, 1, 3)],
                           kwargs=dict(axis=(0,), size=(4,))),
    "broadcast_to": dict(inputs=lambda r: [_f32(r, 1, 3)],
                         kwargs=dict(shape=(2, 3))),
    "crop": dict(inputs=lambda r: [_f32(r, 4, 5)],
                 kwargs=dict(begin=(1, 1), end=(3, 4))),
    "clip": dict(inputs=lambda r: [_f32(r, 2, 3)],
                 kwargs=dict(a_min=-0.4, a_max=0.4)),
    "depth_to_space": dict(inputs=lambda r: [_f32(r, 1, 4, 2, 2)],
                           kwargs=dict(block_size=2)),
    "space_to_depth": dict(inputs=lambda r: [_f32(r, 1, 1, 4, 4)],
                           kwargs=dict(block_size=2)),
    "im2col": dict(inputs=lambda r: [_f32(r, 1, 2, 4, 4)],
                   kwargs=dict(kernel=(2, 2))),
    "col2im": dict(inputs=lambda r: [_f32(r, 1, 8, 4)],
                   kwargs=dict(output_size=(3, 3), kernel=(2, 2))),
    "unravel_index": dict(
        inputs=lambda r: [np.array([1, 3, 5], np.float32)],
        kwargs=dict(shape=(2, 3))),
    "khatri_rao": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 4, 3)]),
    "stack": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 2, 3)]),
    "all_finite": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 3)]),
    "amp_multicast": dict(inputs=lambda r: [_f32(r, 2, 3), _f32(r, 3)],
                          kwargs=dict(num_outputs=2)),
    "topk": dict(inputs=lambda r: [_f32(r, 3, 5)], kwargs=dict(k=2)),
    "split_v2": dict(inputs=lambda r: [_f32(r, 4, 3)],
                     kwargs=dict(indices_or_sections=2)),
    "diag": dict(inputs=lambda r: [_f32(r, 3, 3)]),
    "tile": dict(inputs=lambda r: [_f32(r, 2, 3)], kwargs=dict(reps=(2, 1))),
    "repeat": dict(inputs=lambda r: [_f32(r, 2, 3)],
                   kwargs=dict(repeats=2, axis=1)),
    "slice_axis": dict(inputs=lambda r: [_f32(r, 4, 5)],
                       kwargs=dict(axis=1, begin=1, end=4)),
    "norm": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "squeeze": dict(inputs=lambda r: [_f32(r, 2, 1, 3)]),
    "flip": dict(inputs=lambda r: [_f32(r, 2, 3)], kwargs=dict(axis=1)),
    "transpose": dict(inputs=lambda r: [_f32(r, 2, 3)]),
    "expand_dims": dict(inputs=lambda r: [_f32(r, 2, 3)],
                        kwargs=dict(axis=1)),
    "sort": dict(inputs=lambda r: [_f32(r, 2, 5)]),
    "argsort": dict(inputs=lambda r: [_f32(r, 2, 5)]),
    "smooth_l1": dict(inputs=lambda r: [
        (np.sign(r.randn(2, 3)) * (0.3 + np.abs(r.randn(2, 3)) % 0.5))
        .astype(np.float32)]),
    "_sample_multinomial": dict(
        inputs=lambda r: [np.abs(r.rand(2, 4)).astype(np.float32) + 0.1]),
    "sample_normal": dict(
        inputs=lambda r: [_f32(r, 3), _pos(r, 3)]),
    "sample_uniform": dict(
        inputs=lambda r: [_f32(r, 3), _f32(r, 3) ** 2 + 1.0]),
    "_shuffle": dict(inputs=lambda r: [_f32(r, 6)]),
    "_sample_unique_zipfian": dict(inputs=lambda r: [],
                                   kwargs=dict(range_max=20, shape=(2, 5))),
    # fills: no inputs, kwargs drive
    "_zeros": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 3))),
    "_ones": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 3))),
    "_full": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 2),
                                                   value=1.5)),
    "_eye": dict(inputs=lambda r: [], kwargs=dict(N=3)),
    "_arange": dict(inputs=lambda r: [], kwargs=dict(start=0, stop=5)),
    "_linspace": dict(inputs=lambda r: [], kwargs=dict(num=7)),
    "_random_exponential": dict(inputs=lambda r: [],
                                kwargs=dict(shape=(2, 3))),
    "_random_gamma": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 3))),
    "_random_negative_binomial": dict(inputs=lambda r: [],
                                      kwargs=dict(k=3, p=0.5,
                                                  shape=(2, 3))),
    "_random_normal": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 3))),
    "_random_poisson": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 3))),
    "_random_randint": dict(inputs=lambda r: [],
                            kwargs=dict(low=0, high=10, shape=(2, 3))),
    "_random_uniform": dict(inputs=lambda r: [], kwargs=dict(shape=(2, 3))),
}


def _default_inputs(name, od, rng):
    if name in _DOMAIN:
        return [_DOMAIN[name](rng)]
    ni = od.num_inputs
    if ni is None:                      # variadic without a spec: 2 inputs
        return [_f32(rng, 2, 3), _f32(rng, 2, 3)]
    if callable(ni):
        raise AssertionError(
            f"op {name} has callable num_inputs and no SPECS entry — "
            f"add one")
    return [_f32(rng, 2, 3) for _ in range(ni)]


def _get_spec(name, od):
    spec = SPECS.get(name)
    rng = _rng(name)
    if spec is None:
        return _default_inputs(name, od, rng), {}, None, None, 1e-2, 1e-3
    return (spec["inputs"](rng), dict(spec.get("kwargs", {})),
            spec.get("wrt"), spec.get("grad_reason"),
            spec.get("rtol", 1e-2), spec.get("atol", 1e-3))


def _to_nd(x):
    return nd.array(x, dtype=str(x.dtype))


def _first(outs):
    return outs[0] if isinstance(outs, (list, tuple)) else outs


def _run(name, np_inputs, kwargs):
    frontend = getattr(opmod, name)
    return frontend(*[_to_nd(x) for x in np_inputs], **kwargs)


# --------------------------------------------------------------------- tests
@pytest.mark.parametrize("name", CANONICAL)
def test_forward(name):
    if name in FWD_SKIP:
        pytest.skip(FWD_SKIP[name])
    od = OP_REGISTRY[name]
    np_inputs, kwargs, _wrt, _gr, rtol, atol = _get_spec(name, od)
    outs = _run(name, np_inputs, kwargs)
    for o in (outs if isinstance(outs, (list, tuple)) else [outs]):
        a = o.asnumpy()
        assert a.size > 0 or name in ("_contrib_boolean_mask",), name
        if np.issubdtype(a.dtype, np.floating):
            assert np.isfinite(a).all(), f"{name}: non-finite output"
    oracle = ORACLES.get(name)
    if oracle is not None:
        got = _first(outs).asnumpy()
        want = np.asarray(oracle(*np_inputs, **kwargs))
        assert got.shape == tuple(want.shape), \
            f"{name}: shape {got.shape} vs oracle {want.shape}"
        np.testing.assert_allclose(got.astype(np.float64),
                                   want.astype(np.float64),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


# FD gradient checks whose cost dominates the whole sweep (ISSUE-15
# tier-1 relief: the two flash kernels finite-difference a fused
# attention at ~65s each, CTCLoss ~12s — together 2/3 of this file's
# runtime).  They run in the slow tier; tier-1 keeps their forward
# sweep here plus the cheap analytic gradient parity in
# tests/test_pallas.py::test_flash_grads_match_dense.
SLOW_GRAD = {"_contrib_flash_selfatt", "_contrib_flash_selfatt_nomask",
             "_contrib_flash_attention", "CTCLoss"}

DIFF = [pytest.param(n, marks=pytest.mark.slow) if n in SLOW_GRAD else n
        for n in CANONICAL
        if OP_REGISTRY[n].differentiable and n not in FWD_SKIP]


@pytest.mark.parametrize("name", DIFF)
def test_gradient(name):
    od = OP_REGISTRY[name]
    np_inputs, kwargs, wrt, grad_reason, rtol, atol = _get_spec(name, od)
    spec = SPECS.get(name, {})
    if name in GRAD_SKIP:
        pytest.skip(GRAD_SKIP[name])
    if spec and spec.get("grad") is False:
        pytest.skip(spec["grad_reason"])
    if not np_inputs:
        pytest.skip("no array inputs (fill op)")
    if wrt is None:
        wrt = [i for i, x in enumerate(np_inputs)
               if np.issubdtype(x.dtype, np.floating)]
    if not wrt:
        pytest.skip("no float inputs to differentiate")

    from mxnet_tpu import autograd
    from mxnet_tpu.test_utils import numeric_grad, assert_almost_equal

    # fixed random projection of the first output: a plain .sum() is
    # structurally zero-gradient for normalization ops (the normalized
    # values sum to a constant) and would only compare FD noise
    with autograd.train_mode():
        out0 = _first(_run(name, np_inputs, kwargs)).asnumpy()
    proj = np.asarray(_rng(name + "/proj").randn(*out0.shape),
                      np.float32)

    def scalar_f(wrt_vals):
        full = list(np_inputs)
        for i, v in zip(wrt, wrt_vals):
            full[i] = v.astype(np.float32)
        # train_mode: mode-dependent ops (BatchNorm) must linearize the
        # same branch the recorded forward below uses
        with autograd.train_mode():
            out = _first(_run(name, full, kwargs))
        return float((out.asnumpy().astype(np.float64) * proj).sum())

    expected = numeric_grad(
        scalar_f, [np_inputs[i].astype(np.float64) for i in wrt],
        eps=1e-3)

    nd_inputs = [_to_nd(x) for x in np_inputs]
    for i in wrt:
        nd_inputs[i].attach_grad()
    with autograd.record():
        out = _first(getattr(opmod, name)(*nd_inputs, **kwargs))
        loss = (out * _to_nd(proj)).sum()
    loss.backward()
    for i, exp in zip(wrt, expected):
        assert_almost_equal(
            nd_inputs[i].grad.asnumpy(), exp.astype(np.float32),
            rtol=rtol, atol=atol,
            names=(f"{name}.grad[{i}]", f"{name}.fd[{i}]"))


def test_blockgrad_gradient_is_zero():
    """BlockGrad: identity forward, zero gradient BY CONTRACT (why it is
    excluded from the FD sweep)."""
    from mxnet_tpu import autograd
    x = _to_nd(np.ones((2, 3), np.float32))
    x.attach_grad()
    with autograd.record():
        y = (opmod.BlockGrad(x) * 3.0).sum()
    y.backward()
    assert float(np.abs(x.grad.asnumpy()).sum()) == 0.0


def test_sweep_budget():
    """The skip lists stay small and every skipped name really is a
    registered op (a rename must not silently disable its coverage)."""
    for k in list(FWD_SKIP) + list(GRAD_SKIP):
        assert k in CANONICAL, f"skip-list entry {k} not in registry"
    assert len(FWD_SKIP) <= 0.02 * len(CANONICAL)
    n_grad_skips = len(GRAD_SKIP) + sum(
        1 for s in SPECS.values()
        if isinstance(s, dict) and s.get("grad") is False)
    assert n_grad_skips <= 0.1 * len(CANONICAL), n_grad_skips
    # tier-2 oracle-coverage floor (r5): most of the registry must have
    # an independent NumPy forward reference, not just smoke+FD — and
    # the floor is asserted so coverage can only ratchet up
    n_oracle = sum(1 for n in CANONICAL if n in ORACLES)
    assert n_oracle >= 240, n_oracle
    assert n_oracle >= 0.9 * len(CANONICAL), (n_oracle, len(CANONICAL))
    # every oracle-less canonical op is one of the legitimate classes:
    # rng samplers (distribution tests live in test_ndarray/test_text),
    # sign-ambiguous decompositions, or complex ops with dedicated
    # oracle tests elsewhere (quantized conv/fc, MultiBox target/
    # detection, MoE) — list pinned so a new op can't silently join it
    allowed_no_oracle = {
        "BilinearResize2D", "Correlation", "MultiBoxDetection",
        "MultiBoxTarget", "_contrib_moe_ffn",
        "_contrib_moe_topk_route", "_contrib_quantized_act",
        "_contrib_quantized_conv", "_contrib_quantized_fully_connected",
        "_contrib_quantized_pooling", "_linalg_gelqf", "_linalg_syevd",
        "_random_exponential", "_random_gamma",
        "_random_negative_binomial", "_random_normal",
        "_random_poisson", "_random_randint", "_random_uniform",
        "_sample_multinomial", "_sample_unique_zipfian", "_shuffle",
        "sample_normal", "sample_uniform", "Custom"}
    missing = {n for n in CANONICAL if n not in ORACLES}
    assert missing <= allowed_no_oracle, missing - allowed_no_oracle


# ------------------------------------------------- declarative shape rules
# ISSUE-5: ops with a rule in ops/shape_rules.py answer "what comes
# out?" without tracing (OpDef.infer_signature) — the same algebra the
# mxlint abstract interpreter and deploy manifest checks consume.  The
# sweep holds every rule to the real forward pass: a concrete predicted
# dim must match the actual output.
RULED = [n for n in CANONICAL
         if OP_REGISTRY[n].shape_rule is not None and n not in FWD_SKIP]


def test_shape_rules_cover_the_juggling_core():
    # the reshape/transpose/reduce/matmul family the serving and lint
    # layers reason about must stay covered as the registry grows
    assert {"Reshape", "transpose", "expand_dims", "dot", "batch_dot",
            "sum", "Concat"} <= set(RULED)


@pytest.mark.parametrize("name", RULED)
def test_infer_signature_agrees_with_forward(name):
    od = OP_REGISTRY[name]
    np_inputs, kwargs, _wrt, _gr, _rtol, _atol = _get_spec(name, od)
    out = _first(_run(name, np_inputs, kwargs))
    sig = od.infer_signature(
        [(x.shape, str(x.dtype)) for x in np_inputs], kwargs)
    assert sig is not None
    shape, dtype = sig
    actual = out.asnumpy()
    if shape is not None:
        assert len(shape) == actual.ndim, \
            f"{name}: predicted rank {len(shape)} vs {actual.ndim}"
        for i, d in enumerate(shape):
            if d is not None and d.concrete is not None:
                assert d.concrete == actual.shape[i], \
                    f"{name}: axis {i} predicted {d.concrete}, " \
                    f"got {actual.shape[i]}"
    if dtype is not None:
        assert dtype == str(actual.dtype), \
            f"{name}: predicted dtype {dtype}, got {actual.dtype}"


def test_infer_signature_symbolic_and_infeasible():
    """The registry rule answers symbolic queries (serving's dynamic
    batch dim) and raises MXNetError on provable infeasibility before
    any tracing happens."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops import shape_rules as SR

    od = OP_REGISTRY["reshape"]
    B = SR.sym("B")
    shape, dtype = od.infer_signature([((B, 8), "float32")],
                                      {"shape": (-1, 4)})
    assert SR.dim_eq(shape[0], SR.dim_mul(SR.lit(2), B)) is True
    assert SR.dim_eq(shape[1], SR.lit(4)) is True
    assert dtype == "float32"
    with pytest.raises(MXNetError, match="infeasible"):
        od.infer_signature([((3, 4), "float32")], {"shape": (5, 2)})
    # int dims in the query are lifted to Dim literals
    shape, _ = od.infer_signature([((6, 4), "float32")],
                                  {"shape": (3, -1)})
    assert shape == (SR.lit(3), SR.lit(8))
    # an op without a rule degrades to None, never to a guess
    no_rule = next(n for n in CANONICAL
                   if OP_REGISTRY[n].shape_rule is None)
    assert OP_REGISTRY[no_rule].infer_signature(
        [((2, 2), "float32")], {}) is None
