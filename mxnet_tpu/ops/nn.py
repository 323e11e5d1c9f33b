"""Neural-network operators.

Reference: ``src/operator/nn/`` (Convolution, BatchNorm, FullyConnected,
Pooling, Activation, Dropout, LayerNorm, Softmax, Embedding — SURVEY.md 2.1)
plus ``src/operator/{rnn.cc,lrn.cc,l2_normalization.cc}``.

TPU-native notes:
- Conv/FC lower straight to ``lax.conv_general_dilated`` / ``dot_general``
  → MXU.  No cuDNN/oneDNN dispatch layer exists: XLA owns kernel selection,
  and Pallas alternatives (ops/pallas_kernels.py) override via the same
  registry when profitable.
- Layouts follow the reference default (NCHW / NCW / NCDHW, TNC for RNN) at
  the API level; XLA relayouts internally for the hardware, so API-level
  layout costs nothing at steady state.
- Dropout/random ops draw from mxnet_tpu.random, which yields *traced* keys
  inside a hybridize trace (counter-based fold_in) and a global key in eager
  mode — keeping op signatures reference-compatible while staying pure
  under jit.
- Training-vs-inference branches (BatchNorm, Dropout) read
  ``autograd.is_training()`` at *trace/call* time — static per compiled
  program, matching how the reference dispatches on ``ctx.is_train``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .contrib import gelu_erf
from .registry import register
from .. import autograd


def _act(data, act_type):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise ValueError(f"unknown act_type {act_type!r}")


@register("Activation", aliases=["activation"])
def Activation(data, *, act_type: str = "relu"):
    """Elementwise activation (reference: nn/activation.cc)."""
    return _act(data, act_type)


def _leaky_nin(kwargs):
    return 2 if kwargs.get("act_type", "leaky") == "prelu" else 1


@register("LeakyReLU", num_inputs=_leaky_nin)
def LeakyReLU(data, gamma=None, *, act_type: str = "leaky",
              slope: float = 0.25, lower_bound: float = 0.125,
              upper_bound: float = 0.334):
    """Leaky-family activations incl. prelu/elu/selu/gelu
    (reference: src/operator/leaky_relu.cc)."""
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) \
            if gamma.ndim == 1 and data.ndim > 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return gelu_erf(data)
    if act_type == "rrelu":
        # inference behavior (fixed mean slope), like reference in test mode
        return jnp.where(data >= 0, data,
                         data * (lower_bound + upper_bound) / 2)
    raise ValueError(f"unknown act_type {act_type!r}")


@register("softmax")
def softmax(data, *, axis: int = -1, temperature=None, dtype=None,
            use_length: bool = False):
    """reference: nn/softmax.cc."""
    x = data / temperature if temperature else data
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("log_softmax")
def log_softmax(data, *, axis: int = -1, temperature=None, dtype=None):
    x = data / temperature if temperature else data
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("softmin")
def softmin(data, *, axis: int = -1, temperature=None, dtype=None):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def SoftmaxActivation(data, *, mode: str = "instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(data, label, grad_scale, ignore_label,
                        use_ignore, multi_output, normalization,
                        smooth_alpha):
    axis = 1 if multi_output else -1
    return jax.nn.softmax(data, axis=axis)


@jax.custom_vjp
def _softmax_output_core(data, label):
    return jax.nn.softmax(data, axis=-1)


def _soc_fwd(data, label):
    out = jax.nn.softmax(data, axis=-1)
    return out, (out, label)


def _soc_bwd(res, g):
    out, label = res
    oh = jax.nn.one_hot(label.astype(jnp.int32), out.shape[-1],
                        dtype=out.dtype)
    oh = oh.reshape(out.shape)
    # Loss-layer semantics: incoming cotangent ignored (reference:
    # softmax_output.cc backward writes (p - onehot) regardless).
    return (out - oh, jnp.zeros_like(label))


_softmax_output_core.defvjp(_soc_fwd, _soc_bwd)


@register("SoftmaxOutput", num_inputs=2, aliases=["Softmax"])
def SoftmaxOutput(data, label, *, grad_scale: float = 1.0,
                  ignore_label: float = -1.0, multi_output: bool = False,
                  use_ignore: bool = False, preserve_shape: bool = False,
                  normalization: str = "null", out_grad: bool = False,
                  smooth_alpha: float = 0.0):
    """Softmax forward + cross-entropy-style gradient (reference:
    src/operator/softmax_output.cc).  The backward writes
    ``(softmax - onehot(label)) * grad_scale`` into data's grad and ignores
    the incoming cotangent, exactly like the reference loss layer."""
    if multi_output:
        # (N, C, ...) softmax over C with per-position labels
        x = jnp.moveaxis(data, 1, -1)
        out = _softmax_output_core(x, label.reshape(x.shape[:-1]))
        return jnp.moveaxis(out, -1, 1) * 1.0
    return _softmax_output_core(data, label) * 1.0


@register("softmax_cross_entropy", num_inputs=2)
def softmax_cross_entropy(data, label):
    """reference: src/operator/loss_binary_op.cc — scalar summed CE."""
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1],
                        dtype=data.dtype)
    return -jnp.sum(oh * logp)


@register("FullyConnected", num_inputs=lambda kw: 2 if kw.get("no_bias") else 3)
def FullyConnected(data, weight, bias=None, *, num_hidden: int = 0,
                   no_bias: bool = False, flatten: bool = True):
    """y = x W^T + b (reference: nn/fully_connected.cc).  dot_general on the
    MXU; weight layout (num_hidden, input_dim) matches the reference."""
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    y = jnp.matmul(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


def _conv_dims(kernel_len):
    # (lhs spec, rhs spec, out spec) for NC* layouts
    spatial = "DHW"[3 - kernel_len:]
    return ("NC" + spatial, "OI" + spatial, "NC" + spatial)


@register("Convolution",
          num_inputs=lambda kw: 2 if kw.get("no_bias") else 3)
def Convolution(data, weight, bias=None, *, kernel=(), stride=(), dilate=(),
                pad=(), num_filter: int = 0, num_group: int = 1,
                no_bias: bool = False, layout=None, cudnn_off: bool = False,
                cudnn_tune=None, workspace: int = 1024):
    """N-d convolution, NC* layout, weight (O, I/g, *k)
    (reference: nn/convolution.cc).  Lowers to conv_general_dilated → MXU."""
    k = len(kernel)
    stride = tuple(stride) or (1,) * k
    dilate = tuple(dilate) or (1,) * k
    pad = tuple(pad) or (0,) * k
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _conv_dims(k))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group,
        preferred_element_type=None)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * k)
    return out


@register("Deconvolution",
          num_inputs=lambda kw: 2 if kw.get("no_bias", True) else 3)
def Deconvolution(data, weight, bias=None, *, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), num_filter: int = 0, num_group: int = 1,
                  no_bias: bool = True, target_shape=(), layout=None,
                  cudnn_off: bool = False, cudnn_tune=None,
                  workspace: int = 512):
    """Transposed convolution (reference: nn/deconvolution.cc); weight
    layout (I, O/g, *k) like the reference."""
    k = len(kernel)
    stride = tuple(stride) or (1,) * k
    pad = tuple(pad) or (0,) * k
    adj = tuple(adj) or (0,) * k
    dn = lax.conv_dimension_numbers(
        data.shape, (weight.shape[1] * num_group, weight.shape[0] // num_group)
        + tuple(weight.shape[2:]), _conv_dims(k))
    # grad-of-conv formulation: transpose via lhs dilation
    w = weight
    if num_group > 1:
        w = w.reshape((num_group, w.shape[0] // num_group) + w.shape[1:])
        w = jnp.concatenate([w[g] for g in range(num_group)], axis=1)
    w_t = jnp.swapaxes(w, 0, 1)  # (O/g*g? , I, *k) -> use flipped kernel
    w_t = jnp.flip(w_t, axis=tuple(range(2, 2 + k)))
    pads = [(kernel[i] - 1 - pad[i], kernel[i] - 1 - pad[i] + adj[i])
            for i in range(k)]
    out = lax.conv_general_dilated(
        data, w_t, window_strides=(1,) * k, padding=pads,
        lhs_dilation=stride, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * k)
    return out


@register("Pooling", aliases=["pooling"])
def Pooling(data, *, kernel=(), pool_type: str = "max", stride=(), pad=(),
            global_pool: bool = False, cudnn_off: bool = False,
            pooling_convention: str = "valid", count_include_pad: bool = True,
            layout=None):
    """Max/avg/sum/lp pooling (reference: nn/pooling.cc)."""
    nsp = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "lp"):
            return jnp.mean(data, axis=axes, keepdims=True)
        return jnp.sum(data, axis=axes, keepdims=True)
    k = tuple(kernel)
    stride = tuple(stride) or (1,) * nsp
    pad = tuple(pad) or (0,) * nsp
    window = (1, 1) + k
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil division semantics: pad on the high side as needed
        pads = [(0, 0), (0, 0)]
        for i in range(nsp):
            in_sz = data.shape[2 + i] + 2 * pad[i]
            out_sz = -(-(in_sz - k[i]) // stride[i]) + 1
            need = max(0, (out_sz - 1) * stride[i] + k[i] - in_sz)
            pads.append((pad[i], pad[i] + need))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pool_type == "max":
        init = -jnp.inf
        out = lax.reduce_window(data, init, lax.max, window, strides, pads)
        return out.astype(data.dtype)
    if pool_type == "sum":
        return lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
    # avg
    summed = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
    if count_include_pad:
        denom = float(np.prod(k))
        return summed / denom
    ones = jnp.ones_like(data)
    counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
    return summed / counts


def _bn_nout(kwargs):
    return 3 if kwargs.get("output_mean_var") else 1


@register("BatchNorm", num_inputs=5, num_outputs=_bn_nout,
          aliases=["batch_norm"])
def BatchNorm(data, gamma, beta, moving_mean, moving_var, *,
              eps: float = 1e-3, momentum: float = 0.9,
              fix_gamma: bool = True, use_global_stats: bool = False,
              output_mean_var: bool = False, axis: int = 1,
              cudnn_off: bool = False):
    """Batch normalization (reference: nn/batch_norm.cc).

    Training mode (autograd.is_training() and not use_global_stats) uses
    batch statistics; inference uses the moving stats.  With
    ``output_mean_var`` the batch mean and inverse-std are returned so the
    Gluon layer can update its running stats functionally (the reference
    mutates aux states inside the op; here state threading is explicit —
    see gluon/nn/basic_layers.py BatchNorm)."""
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    training = autograd.is_training() and not use_global_stats
    if training:
        # stats accumulate AND flow onward in fp32 regardless of
        # activation dtype: a bf16 sum over B*H*W (≈1e5-1e6) elements
        # loses ~3 decimal digits, which corrupts the moving-stat EMA
        # over a long schedule.  Only the normalize expression casts
        # back to the activation dtype, so XLA still fuses it into the
        # producing conv with no extra HBM traffic and the
        # output_mean_var / aux-update consumers see full precision.
        red = tuple(i for i in range(data.ndim) if i != axis)
        data32 = data.astype(jnp.float32)
        mean = jnp.mean(data32, axis=red)
        var = jnp.var(data32, axis=red)
    else:
        mean, var = moving_mean, moving_var
    inv_std = lax.rsqrt(var + eps)
    out = (data - mean.astype(data.dtype).reshape(shape)) \
        * inv_std.astype(data.dtype).reshape(shape) \
        * gamma.reshape(shape) + beta.reshape(shape)
    if output_mean_var:
        return out, mean, inv_std
    return out


def _batchnorm_aux_update(args, kwargs):
    """OpDef.aux_update hook: training-time moving-stat transition
    (reference: batch_norm.cc mutates moving_mean/var in Forward; here the
    executor applies the returned update functionally)."""
    if kwargs.get("use_global_stats") or kwargs.get("output_mean_var"):
        return None
    out, mean, inv_std = BatchNorm(*args,
                                   **dict(kwargs, output_mean_var=True))
    eps = float(kwargs.get("eps", 1e-3))
    mom = float(kwargs.get("momentum", 0.9))
    var = 1.0 / (inv_std * inv_std) - eps
    return (out,), {
        3: mom * args[3] + (1.0 - mom) * mean.astype(args[3].dtype),
        4: mom * args[4] + (1.0 - mom) * var.astype(args[4].dtype),
    }


from .registry import get_op as _get_op  # noqa: E402
_get_op("BatchNorm").aux_update = _batchnorm_aux_update


@register("LayerNorm", num_inputs=3, num_outputs=_bn_nout,
          aliases=["layer_norm"])
def LayerNorm(data, gamma, beta, *, axis: int = -1, eps: float = 1e-5,
              output_mean_var: bool = False):
    """reference: nn/layer_norm.cc."""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    inv_std = lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = (data - mean) * inv_std * gamma.reshape(shape) + beta.reshape(shape)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(inv_std, axis)
    return out


@register("InstanceNorm", num_inputs=3)
def InstanceNorm(data, gamma, beta, *, eps: float = 1e-3):
    """reference: src/operator/instance_norm.cc (NC+ layout)."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) \
        + beta.reshape(shape)


@register("GroupNorm", num_inputs=3)
def GroupNorm(data, gamma, beta, *, num_groups: int = 1, eps: float = 1e-5):
    """reference: nn/group_norm.cc."""
    n, c = data.shape[:2]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization")
def L2Normalization(data, *, eps: float = 1e-10, mode: str = "instance"):
    """reference: src/operator/l2_normalization.cc."""
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        keep = True
    elif mode == "channel":
        red, keep = (1,), True
    else:  # spatial
        red, keep = tuple(range(2, data.ndim)), True
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=keep) + eps)
    return data / norm


@register("LRN")
def LRN(data, *, alpha: float = 1e-4, beta: float = 0.75, knorm: float = 2.0,
        nsize: int = 5):
    """Local response norm across channels (reference: src/operator/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    windows = sum(padded[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + alpha * windows / nsize, beta)


@register("Dropout", mutates_rng=True)
def Dropout(data, *, p: float = 0.5, mode: str = "training", axes=(),
            cudnn_off: bool = False):
    """Dropout (reference: nn/dropout.cc).  Scales by 1/(1-p) at train time.
    Key comes from mxnet_tpu.random (traced key under hybridize)."""
    if not autograd.is_training() and mode != "always":
        return data
    if p <= 0:
        return data
    from .. import random as mxrand
    key = mxrand.next_key()
    if axes:
        shape = tuple(1 if i in tuple(axes) else s
                      for i, s in enumerate(data.shape))
    else:
        shape = data.shape
    keep = jax.random.bernoulli(key, 1.0 - p, shape=shape)
    return jnp.where(keep, data / (1.0 - p), 0.0).astype(data.dtype)


@register("Embedding", num_inputs=2)
def Embedding(data, weight, *, input_dim: int = 0, output_dim: int = 0,
              dtype: str = "float32", sparse_grad: bool = False):
    """Lookup table (reference: indexing_op.cc EmbeddingOp); gather on
    data indices into weight rows."""
    idx = jnp.clip(data.astype(jnp.int32), 0, weight.shape[0] - 1)
    return jnp.take(weight, idx, axis=0)


@register("UpSampling", num_inputs=None)
def UpSampling(*data, scale: int = 1, sample_type: str = "nearest",
               num_args: int = 1, num_filter: int = 0,
               multi_input_mode: str = "concat", workspace: int = 512):
    """reference: src/operator/upsampling.cc (nearest mode)."""
    outs = []
    for d in data:
        n, c, h, w = d.shape
        if sample_type == "nearest":
            o = jnp.repeat(jnp.repeat(d, scale, axis=2), scale, axis=3)
        else:
            o = jax.image.resize(d, (n, c, h * scale, w * scale), "bilinear")
        outs.append(o)
    if len(outs) == 1:
        return outs[0]
    return jnp.concatenate(outs, axis=1)


@register("BilinearSampler", num_inputs=2)
def BilinearSampler(data, grid, *, cudnn_off: bool = False):
    """reference: src/operator/bilinear_sampler.cc; grid in [-1, 1]."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2
    gy = (grid[:, 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx, wy = gx - x0, gy - y0

    def gather(yy, xx):
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        flat = data.reshape(n, c, h * w)
        lin = (yy * w + xx).reshape(n, -1)
        out = jnp.take_along_axis(flat, lin[:, None, :], axis=2)
        return out.reshape(n, c, *gx.shape[1:])

    val = (gather(y0, x0) * ((1 - wx) * (1 - wy))[:, None]
           + gather(y0, x1) * (wx * (1 - wy))[:, None]
           + gather(y1, x0) * ((1 - wx) * wy)[:, None]
           + gather(y1, x1) * (wx * wy)[:, None])
    return val


# ---------------------------------------------------------------------------
# Fused RNN (reference: src/operator/rnn.cc + rnn-inl.h; cuDNN packed-weight
# layout).  TPU-native: lax.scan over time — compiles to one fused loop, the
# idiomatic XLA recurrence (no per-step dispatch).
# ---------------------------------------------------------------------------

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def _rnn_nout(kwargs):
    if not kwargs.get("state_outputs", False):
        return 1
    return 3 if kwargs.get("mode", "lstm") == "lstm" else 2


def _unpack_rnn_params(params, mode, num_layers, input_size, H, D):
    """Split the flat cudnn-style parameter vector: all i2h/h2h weights
    (layer-major, direction-minor), then all biases — the layout the
    reference documents for rnn.cc."""
    G = _GATES[mode]
    ws, bs = [], []
    offset = 0
    for layer in range(num_layers):
        for d in range(D):
            in_sz = input_size if layer == 0 else H * D
            w_i2h = (G * H, in_sz)
            w_h2h = (G * H, H)
            ws.append((w_i2h, w_h2h))
    weights = []
    for (s1, s2) in ws:
        n1 = s1[0] * s1[1]
        weights.append(params[offset:offset + n1].reshape(s1))
        offset += n1
        n2 = s2[0] * s2[1]
        weights.append(params[offset:offset + n2].reshape(s2))
        offset += n2
    biases = []
    for layer in range(num_layers):
        for d in range(D):
            biases.append(params[offset:offset + G * H])
            offset += G * H
            biases.append(params[offset:offset + G * H])
            offset += G * H
    return weights, biases


def _cell_step(mode, H):
    if mode == "lstm":
        def step(carry, gates):
            h, c = carry
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c_new = f * c + i * g
            h_new = o * jnp.tanh(c_new)
            return (h_new, c_new)
        return step
    if mode == "gru":
        def step(carry, pair):
            h = carry[0]
            gi, gh = pair
            ir, iz, inn = jnp.split(gi, 3, axis=-1)
            hr, hz, hn = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(ir + hr)
            z = jax.nn.sigmoid(iz + hz)
            n = jnp.tanh(inn + r * hn)
            h_new = (1 - z) * n + z * h
            return (h_new,)
        return step
    act = jnp.tanh if mode == "rnn_tanh" else (lambda x: jnp.maximum(x, 0))

    def step(carry, gates):
        return (act(gates),)
    return step


def _run_layer(x, mode, w_i2h, w_h2h, b_i2h, b_h2h, h0, c0, reverse):
    """x: (T, N, I). Returns (T, N, H), h_T, c_T."""
    H = w_h2h.shape[1]
    cell = _cell_step(mode, H)
    xin = jnp.flip(x, axis=0) if reverse else x
    gates_i = jnp.einsum("tni,gi->tng", xin, w_i2h) + b_i2h

    def scan_fn(carry, g_i):
        h = carry[0]
        g_h = jnp.matmul(h, w_h2h.T) + b_h2h
        if mode == "gru":
            new = cell(carry, (g_i, g_h))
        else:
            new = cell(carry, g_i + g_h)
        return new, new[0]

    init = (h0, c0) if mode == "lstm" else (h0,)
    carry, ys = lax.scan(scan_fn, init, gates_i)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    h_T = carry[0]
    c_T = carry[1] if mode == "lstm" else None
    return ys, h_T, c_T


@register("RNN", num_inputs=lambda kw: 4 if kw.get("mode") == "lstm" else 3,
          num_outputs=_rnn_nout, mutates_rng=True)
def RNN(data, parameters, state, state_cell=None, *, state_size: int = 0,
        num_layers: int = 1, mode: str = "lstm", bidirectional: bool = False,
        p: float = 0.0, state_outputs: bool = False,
        projection_size=None, use_sequence_length: bool = False,
        lstm_state_clip_min=None, lstm_state_clip_max=None,
        lstm_state_clip_nan: bool = False):
    """Fused multi-layer (bi)RNN/LSTM/GRU over TNC input (reference:
    src/operator/rnn.cc).  lax.scan recurrence; packed cudnn-layout params."""
    T, N, I = data.shape
    H = state_size
    D = 2 if bidirectional else 1
    weights, biases = _unpack_rnn_params(parameters, mode, num_layers, I, H, D)
    x = data
    h_states, c_states = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(D):
            li = layer * D + d
            w_i2h, w_h2h = weights[2 * li], weights[2 * li + 1]
            b_i2h, b_h2h = biases[2 * li], biases[2 * li + 1]
            h0 = state[li]
            c0 = state_cell[li] if mode == "lstm" else None
            ys, h_T, c_T = _run_layer(x, mode, w_i2h, w_h2h, b_i2h, b_h2h,
                                      h0, c0, reverse=(d == 1))
            outs.append(ys)
            h_states.append(h_T)
            if mode == "lstm":
                c_states.append(c_T)
        x = outs[0] if D == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0 and layer < num_layers - 1 and autograd.is_training():
            from .. import random as mxrand
            keep = jax.random.bernoulli(mxrand.next_key(), 1.0 - p, x.shape)
            x = jnp.where(keep, x / (1.0 - p), 0.0)
    if not state_outputs:
        return x
    h_out = jnp.stack(h_states, axis=0)
    if mode == "lstm":
        return x, h_out, jnp.stack(c_states, axis=0)
    return x, h_out


@register("Correlation", num_inputs=2)
def Correlation(data1, data2, *, kernel_size: int = 1,
                max_displacement: int = 1, stride1: int = 1, stride2: int = 1,
                pad_size: int = 0, is_multiply: bool = True):
    """FlowNet cost volume (reference: src/operator/correlation.cc).

    One output channel per displacement in the stride2 grid; each is a
    channel-summed, kernel-window-summed patch product (or abs-difference),
    normalized by kernel_size^2 * C.  The displacement grid is static, so
    the whole volume lowers to a fused stack of shifted multiplies + a
    reduce_window — no gather, MXU/VPU friendly.
    """
    N, C, H, W = data1.shape
    kr = (kernel_size - 1) // 2
    border = max_displacement + kr
    pH, pW = H + 2 * pad_size, W + 2 * pad_size
    if pH - 2 * border < 1 or pW - 2 * border < 1:
        raise ValueError(
            f"Correlation: displacement border {border} "
            f"(max_displacement + kernel radius) leaves no valid output "
            f"for padded input {pH}x{pW}; increase pad_size or shrink "
            f"max_displacement/kernel_size")
    top_h = int(-(-(pH - 2 * border) // stride1))
    top_w = int(-(-(pW - 2 * border) // stride1))
    grid_r = max_displacement // stride2
    sumelems = float(kernel_size * kernel_size * C)
    pad = ((0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size))
    p1 = jnp.pad(data1, pad)
    p2 = jnp.pad(data2, pad)
    start = border - kr
    planes = []
    for dy in range(-grid_r * stride2, grid_r * stride2 + 1, stride2):
        for dx in range(-grid_r * stride2, grid_r * stride2 + 1, stride2):
            shifted = jnp.roll(p2, (-dy, -dx), axis=(2, 3))
            prod = p1 * shifted if is_multiply else jnp.abs(p1 - shifted)
            s = prod.sum(axis=1)
            if kernel_size > 1:
                s = lax.reduce_window(s, 0.0, lax.add,
                                      (1, kernel_size, kernel_size),
                                      (1, 1, 1), "VALID")
            sub = lax.slice(s, (0, start, start),
                            (N, start + (top_h - 1) * stride1 + 1,
                             start + (top_w - 1) * stride1 + 1),
                            (1, stride1, stride1))
            planes.append(sub / sumelems)
    return jnp.stack(planes, axis=1)


@register("GridGenerator")
def GridGenerator(data, *, transform_type: str = "affine", target_shape=()):
    h, w = target_shape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gx, gy = jnp.meshgrid(xs, ys)
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()], axis=0)
    theta = data.reshape(-1, 2, 3)
    out = jnp.einsum("nij,jk->nik", theta, base)
    return out.reshape(-1, 2, h, w)


# ---------------------------------------------------------------------------
# CTC loss (reference: src/operator/nn/ctc_loss.cc / mx.nd.CTCLoss).
# Log-domain forward algorithm via lax.scan (TPU-friendly: static shapes,
# no data-dependent python control flow); vmapped over the batch.
# Convention (blank_label='first'): channel 0 is blank, labels are 1..C-1,
# label padding value is 0.
# ---------------------------------------------------------------------------

def _ctc_forward_single(logprobs, label, t_len, l_len):
    """logprobs (T, C) log-softmax; label (L,) ints; returns -log p(label)."""
    T, C = logprobs.shape
    L = label.shape[0]
    S = 2 * L + 1
    neg_inf = jnp.float32(-1e30)
    # extended label sequence: blank, l1, blank, l2, ..., blank
    z = jnp.zeros((S,), dtype=label.dtype)
    z = z.at[1::2].set(label)
    s_idx = jnp.arange(S)
    # transitions: from s, s-1 always; from s-2 iff z[s] != z[s-2] and odd s
    z_prev2 = jnp.concatenate([jnp.zeros((2,), z.dtype), z[:-2]])
    can_skip = (s_idx % 2 == 1) & (z != z_prev2)

    alpha0 = jnp.full((S,), neg_inf)
    alpha0 = alpha0.at[0].set(logprobs[0, 0])
    alpha0 = alpha0.at[1].set(
        jnp.where(l_len > 0, logprobs[0, z[1]], neg_inf))

    def step(alpha, t):
        a_prev1 = jnp.concatenate([jnp.array([neg_inf]), alpha[:-1]])
        a_prev2 = jnp.concatenate([jnp.full((2,), neg_inf), alpha[:-2]])
        a_prev2 = jnp.where(can_skip, a_prev2, neg_inf)
        stacked = jnp.stack([alpha, a_prev1, a_prev2])
        merged = jax.scipy.special.logsumexp(stacked, axis=0)
        new_alpha = merged + logprobs[t, z]
        # freeze the recursion past this sample's length
        new_alpha = jnp.where(t < t_len, new_alpha, alpha)
        return new_alpha, None

    alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
    end1 = alpha[2 * l_len]        # final blank
    end2 = jnp.where(l_len > 0, alpha[2 * l_len - 1], neg_inf)
    logp = jnp.logaddexp(end1, end2)
    return -logp


@register("CTCLoss", num_inputs=4, aliases=["ctc_loss", "_contrib_CTCLoss",
                                            "_contrib_ctc_loss"])
def CTCLoss(data, label, data_lengths=None, label_lengths=None, *,
            use_data_lengths: bool = False, use_label_lengths: bool = False,
            blank_label: str = "first"):
    """data (T, N, C) unnormalized activations; label (N, L)."""
    T, N, C = data.shape
    logprobs = jax.nn.log_softmax(data, axis=-1)  # (T, N, C)
    label = label.astype(jnp.int32)
    if blank_label == "last":
        # rotate so blank becomes channel 0 (internal convention)
        logprobs = jnp.concatenate(
            [logprobs[..., -1:], logprobs[..., :-1]], axis=-1)
        label = label + 1
    if data_lengths is None or not use_data_lengths:
        t_lens = jnp.full((N,), T, dtype=jnp.int32)
    else:
        t_lens = data_lengths.astype(jnp.int32)
    if label_lengths is None or not use_label_lengths:
        l_lens = jnp.sum(label > 0, axis=1).astype(jnp.int32)
    else:
        l_lens = label_lengths.astype(jnp.int32)
    per_n = jax.vmap(_ctc_forward_single, in_axes=(1, 0, 0, 0))(
        logprobs, label, t_lens, l_lens)
    return per_n.astype(data.dtype)


@register("hard_sigmoid")
def hard_sigmoid(data, *, alpha: float = 0.2, beta: float = 0.5):
    """Piecewise-linear sigmoid (reference: mshadow_op hard_sigmoid)."""
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register("im2col")
def im2col(data, *, kernel=(), stride=(1, 1), dilate=(1, 1),
           pad=(0, 0)):
    """Sliding-window patch extraction, NCHW -> (N, C*kh*kw, L)
    (reference: src/operator/nn/im2col.h).  XLA's dilated-patch
    primitive keeps it one fused op."""
    kh, kw = kernel
    patches = jax.lax.conv_general_dilated_patches(
        data, (kh, kw), tuple(stride),
        [(pad[0], pad[0]), (pad[1], pad[1])],
        rhs_dilation=tuple(dilate),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, ckk, oh, ow = patches.shape
    return patches.reshape(n, ckk, oh * ow)


@register("col2im")
def col2im(data, *, output_size=(), kernel=(), stride=(1, 1),
           dilate=(1, 1), pad=(0, 0)):
    """Inverse of im2col: scatter-add patches back to NCHW (reference:
    src/operator/nn/im2col.h col2im).  Implemented as the linear
    transpose of im2col — exact adjoint by construction."""
    H, W = output_size
    n, ckk, _L = data.shape
    kh, kw = kernel
    c = ckk // (kh * kw)

    def fwd(img):
        return im2col(img, kernel=kernel, stride=stride, dilate=dilate,
                      pad=pad)

    img_shape = jax.ShapeDtypeStruct((n, c, H, W), data.dtype)
    (out,) = jax.linear_transpose(fwd, img_shape)(data)
    return out


@register("SpatialTransformer", num_inputs=2)
def SpatialTransformer(data, loc, *, target_shape=(),
                       transform_type: str = "affine",
                       sampler_type: str = "bilinear",
                       cudnn_off: bool = False):
    """Affine spatial transformer network: GridGenerator +
    BilinearSampler composed (reference:
    src/operator/spatial_transformer.cc)."""
    grid = GridGenerator(loc, transform_type=transform_type,
                         target_shape=target_shape)
    return BilinearSampler(data, grid)


@register("ROIPooling", num_inputs=2)
def ROIPooling(data, rois, *, pooled_size=(), spatial_scale: float = 1.0):
    """Max pooling over ROI bins (reference: src/operator/roi_pooling.cc).

    TPU-native deviation: the reference max-pools over the exact integer
    pixels of each quantized bin (data-dependent bin sizes); here each
    bin is sampled on a static sub-grid DENSE ENOUGH that consecutive
    samples are <= 1 pixel apart for any ROI in the feature map
    (sg = ceil(H/ph) per side), so the nearest-pixel gather + max sees
    every pixel of every bin — equal to the reference max up to corner
    quantization.  Prefer ROIAlign for new models."""
    ph, pw = pooled_size
    n, c, h, w = data.shape
    batch_idx = rois[:, 0].astype(jnp.int32)
    # quantize roi corners like the reference (round to pixels)
    x1 = jnp.round(rois[:, 1] * spatial_scale)
    y1 = jnp.round(rois[:, 2] * spatial_scale)
    x2 = jnp.round(rois[:, 3] * spatial_scale)
    y2 = jnp.round(rois[:, 4] * spatial_scale)
    bin_h = jnp.maximum(y2 - y1 + 1, 1.0) / ph
    bin_w = jnp.maximum(x2 - x1 + 1, 1.0) / pw
    # sub-samples per bin side: max bin size is H/ph (W/pw) pixels, so
    # this guarantees <=1px sample spacing for any ROI
    sgy = max(2, -(-h // ph))
    sgx = max(2, -(-w // pw))
    iy = (jnp.arange(ph * sgy) + 0.5) / sgy    # (ph*sgy,) in bin units
    ix = (jnp.arange(pw * sgx) + 0.5) / sgx
    ys = y1[:, None] + iy[None, :] * bin_h[:, None]     # (R, ph*sgy)
    xs = x1[:, None] + ix[None, :] * bin_w[:, None]     # (R, pw*sgx)
    yi = jnp.clip(jnp.floor(ys), 0, h - 1).astype(jnp.int32)
    xi = jnp.clip(jnp.floor(xs), 0, w - 1).astype(jnp.int32)
    imgs = data[batch_idx]                     # (R, C, H, W)
    rows = jnp.take_along_axis(
        imgs, yi[:, None, :, None], axis=2)    # (R, C, ph*sgy, W)
    vals = jnp.take_along_axis(
        rows, xi[:, None, None, :], axis=3)    # (R, C, ph*sgy, pw*sgx)
    R = vals.shape[0]
    vals = vals.reshape(R, c, ph, sgy, pw, sgx)
    return vals.max(axis=(3, 5))
