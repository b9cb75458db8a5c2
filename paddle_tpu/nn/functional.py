"""paddle.nn.functional parity surface.

Reference: ``python/paddle/nn/functional/`` (activation.py, common.py, conv.py,
loss.py, norm.py, pooling.py) over PHI kernels. Here every functional is a pure
JAX composite registered on the eager tape; XLA fuses the elementwise chains and
lowers conv/matmul to the MXU. Flash attention routes to the Pallas kernel on
TPU (ops/pallas/) with a reference jnp path elsewhere.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import generator as _gen
from paddle_tpu.core.autograd import apply_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops import OPS

__all__ = [
    # activations
    "relu", "relu6", "gelu", "silu", "swish", "sigmoid", "tanh", "softmax",
    "log_softmax", "leaky_relu", "elu", "selu", "celu", "hardswish",
    "hardsigmoid", "hardtanh", "hardshrink", "softshrink", "tanhshrink",
    "softplus", "softsign", "mish", "prelu", "rrelu", "glu", "maxout",
    "log_sigmoid", "thresholded_relu", "swiglu",
    # linear/embedding/common
    "linear", "embedding", "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    "one_hot", "label_smooth", "bilinear", "interpolate", "upsample",
    "pixel_shuffle", "pixel_unshuffle", "channel_shuffle", "unfold", "fold",
    # norm
    "layer_norm", "rms_norm", "batch_norm", "instance_norm", "group_norm",
    "local_response_norm", "normalize",
    # conv/pool
    "conv1d", "conv2d", "conv3d", "conv1d_transpose", "conv2d_transpose",
    "conv3d_transpose", "max_pool1d", "max_pool2d", "max_pool3d",
    "avg_pool1d", "avg_pool2d", "avg_pool3d", "adaptive_avg_pool1d",
    "adaptive_avg_pool2d", "adaptive_avg_pool3d", "adaptive_max_pool1d",
    "adaptive_max_pool2d", "adaptive_max_pool3d",
    # attention
    "scaled_dot_product_attention", "flash_attention",
    # losses
    "cross_entropy", "softmax_with_cross_entropy", "mse_loss", "l1_loss",
    "nll_loss", "binary_cross_entropy", "binary_cross_entropy_with_logits",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "cosine_similarity",
    "hinge_embedding_loss", "square_error_cost", "log_loss", "ctc_loss",
    "triplet_margin_loss", "cosine_embedding_loss", "pairwise_distance",
    "sequence_mask", "temporal_shift",
]


# =========================== activations =====================================
def _unary(name, fn):
    def wrapper(x, *args, **kwargs):
        return apply_op(fn, x, op_name=name, **kwargs)
    wrapper.__name__ = name
    return wrapper


relu = _unary("relu", lambda x: jax.nn.relu(x))
relu6 = _unary("relu6", lambda x: jax.nn.relu6(x))
silu = _unary("silu", lambda x: jax.nn.silu(x))
swish = silu
sigmoid = OPS["sigmoid"]
tanh = OPS["tanh"]
log_sigmoid = _unary("log_sigmoid", lambda x: jax.nn.log_sigmoid(x))
softsign = _unary("softsign", lambda x: jax.nn.soft_sign(x))
mish = _unary("mish", lambda x: x * jnp.tanh(jax.nn.softplus(x)))


def gelu(x, approximate=False):
    return apply_op(
        lambda v: jax.nn.gelu(v, approximate=approximate), x, op_name="gelu")


def softmax(x, axis=-1, dtype=None):
    def f(v):
        if dtype is not None:
            from paddle_tpu.core.dtype import convert_dtype
            v = v.astype(convert_dtype(dtype).np_dtype)
        return jax.nn.softmax(v, axis=int(axis))
    return apply_op(f, x, op_name="softmax")


def log_softmax(x, axis=-1):
    return apply_op(lambda v: jax.nn.log_softmax(v, axis=int(axis)), x,
                    op_name="log_softmax")


def leaky_relu(x, negative_slope=0.01):
    return apply_op(lambda v: jax.nn.leaky_relu(v, negative_slope), x,
                    op_name="leaky_relu")


def elu(x, alpha=1.0):
    return apply_op(lambda v: jax.nn.elu(v, alpha), x, op_name="elu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return apply_op(
        lambda v: scale * jnp.where(v > 0, v, alpha * jnp.expm1(v)), x,
        op_name="selu")


def celu(x, alpha=1.0):
    return apply_op(lambda v: jax.nn.celu(v, alpha), x, op_name="celu")


def hardswish(x):
    return apply_op(lambda v: v * jnp.clip(v + 3, 0, 6) / 6, x,
                    op_name="hardswish")


def hardsigmoid(x, slope=1.0 / 6, offset=0.5):
    return apply_op(lambda v: jnp.clip(v * slope + offset, 0, 1), x,
                    op_name="hardsigmoid")


def hardtanh(x, min=-1.0, max=1.0):
    return apply_op(lambda v: jnp.clip(v, min, max), x, op_name="hardtanh")


def hardshrink(x, threshold=0.5):
    return apply_op(
        lambda v: jnp.where(jnp.abs(v) > threshold, v, 0.0), x,
        op_name="hardshrink")


def softshrink(x, threshold=0.5):
    return apply_op(
        lambda v: jnp.where(v > threshold, v - threshold,
                            jnp.where(v < -threshold, v + threshold, 0.0)),
        x, op_name="softshrink")


def tanhshrink(x):
    return apply_op(lambda v: v - jnp.tanh(v), x, op_name="tanhshrink")


def softplus(x, beta=1.0, threshold=20.0):
    return apply_op(
        lambda v: jnp.where(v * beta > threshold, v,
                            jnp.log1p(jnp.exp(beta * v)) / beta),
        x, op_name="softplus")


def thresholded_relu(x, threshold=1.0):
    return apply_op(lambda v: jnp.where(v > threshold, v, 0.0), x,
                    op_name="thresholded_relu")


def prelu(x, weight):
    return apply_op(
        lambda v, w: jnp.where(v >= 0, v, _reshape_prelu(w, v) * v),
        x, weight, op_name="prelu")


def _reshape_prelu(w, v):
    if w.size == 1:
        return w
    shape = [1] * v.ndim
    shape[1] = w.size
    return jnp.reshape(w, shape)


def rrelu(x, lower=1.0 / 8, upper=1.0 / 3, training=True):
    if training:
        key = _gen.next_key()

        def f(v):
            a = jax.random.uniform(key, v.shape, v.dtype, lower, upper)
            return jnp.where(v >= 0, v, a * v)
        return apply_op(f, x, op_name="rrelu")
    mid = (lower + upper) / 2
    return apply_op(lambda v: jnp.where(v >= 0, v, mid * v), x,
                    op_name="rrelu")


def glu(x, axis=-1):
    def f(v):
        a, b = jnp.split(v, 2, axis=axis)
        return a * jax.nn.sigmoid(b)
    return apply_op(f, x, op_name="glu")


def swiglu(x, y=None):
    """SwiGLU (used by Llama FFN): silu(x) * y; single-arg splits in half."""
    if y is None:
        return apply_op(
            lambda v: (lambda a, b: jax.nn.silu(a) * b)(
                *jnp.split(v, 2, axis=-1)), x, op_name="swiglu")
    return apply_op(lambda a, b: jax.nn.silu(a) * b, x, y, op_name="swiglu")


def maxout(x, groups, axis=1):
    def f(v):
        ax = axis % v.ndim
        c = v.shape[ax]
        new = v.shape[:ax] + (c // groups, groups) + v.shape[ax + 1:]
        return jnp.max(jnp.reshape(v, new), axis=ax + 1)
    return apply_op(f, x, op_name="maxout")


# =========================== common ==========================================
def linear(x, weight, bias=None):
    """y = x @ W + b with paddle's [in, out] weight layout
    (reference: phi matmul + elementwise_add, nn/functional/common.py)."""
    if bias is None:
        return apply_op(lambda a, w: jnp.matmul(a, w), x, weight,
                        op_name="linear")
    return apply_op(lambda a, w, b: jnp.matmul(a, w) + b, x, weight, bias,
                    op_name="linear")


def embedding(x, weight, padding_idx=None, sparse=False):
    def f(ids, w):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return apply_op(f, x, weight, op_name="embedding")


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train"):
    if not training:
        # downscale_in_infer scales by (1-p) at inference; upscale_in_train
        # is identity at eval (python/paddle/nn/functional/common.py dropout).
        if mode == "downscale_in_infer" and p > 0.0:
            return apply_op(lambda v: v * (1.0 - p), x, op_name="dropout")
        return x if isinstance(x, Tensor) else Tensor(x)
    if p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    key = _gen.next_key()

    def f(v):
        shape = list(v.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0)
        return jnp.where(keep, v, 0.0)
    return apply_op(f, x, op_name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    ax = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    ax = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    key = _gen.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(v):
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        a = (1.0 / math.sqrt((1 - p) * (1 + p * alpha_p ** 2))) \
            if p < 1 else 0.0
        b = -a * alpha_p * p
        return a * jnp.where(keep, v, alpha_p) + b
    return apply_op(f, x, op_name="alpha_dropout")


def one_hot(x, num_classes):
    return OPS["one_hot"](x, num_classes)


def label_smooth(label, prior_dist=None, epsilon=0.1):
    def f(l):
        n = l.shape[-1]
        if prior_dist is not None:
            pd = prior_dist.data if isinstance(prior_dist, Tensor) \
                else jnp.asarray(prior_dist)
            return (1 - epsilon) * l + epsilon * pd
        return (1 - epsilon) * l + epsilon / n
    return apply_op(f, label, op_name="label_smooth")


def bilinear(x1, x2, weight, bias=None):
    def f(a, b, w, *bb):
        # w: [out, in1, in2]
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bb:
            out = out + bb[0]
        return out
    args = (x1, x2, weight) + ((bias,) if bias is not None else ())
    return apply_op(f, *args, op_name="bilinear")


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def f(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)
    return apply_op(f, x1, x2, op_name="cosine_similarity")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    def f(a, b):
        d = a - b + epsilon
        return jnp.linalg.norm(d, ord=p, axis=-1, keepdims=keepdim)
    return apply_op(f, x, y, op_name="pairwise_distance")


def normalize(x, p=2, axis=1, epsilon=1e-12):
    def f(v):
        n = jnp.linalg.norm(v, ord=p, axis=axis, keepdims=True)
        return v / jnp.maximum(n, epsilon)
    return apply_op(f, x, op_name="normalize")


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    from paddle_tpu.core.dtype import convert_dtype
    import jax.dtypes as jdt

    def f(l):
        m = int(maxlen) if maxlen is not None else int(jnp.max(l))
        rng = jnp.arange(m)
        return (rng[None, :] < l[..., None]).astype(
            jdt.canonicalize_dtype(convert_dtype(dtype).np_dtype))
    return apply_op(f, lengths, op_name="sequence_mask")


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    def f(v):
        n, c, h, w = v.shape
        b = n // seg_num
        v5 = jnp.reshape(v, (b, seg_num, c, h, w))
        fold = int(c * shift_ratio)
        left = jnp.concatenate(
            [v5[:, 1:, :fold], jnp.zeros_like(v5[:, :1, :fold])], axis=1)
        right = jnp.concatenate(
            [jnp.zeros_like(v5[:, :1, fold:2 * fold]),
             v5[:, :-1, fold:2 * fold]], axis=1)
        rest = v5[:, :, 2 * fold:]
        return jnp.reshape(jnp.concatenate([left, right, rest], axis=2),
                           (n, c, h, w))
    return apply_op(f, x, op_name="temporal_shift")


# =========================== norms ===========================================
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    ns = ([normalized_shape] if isinstance(normalized_shape, int)
          else list(normalized_shape))
    n_axes = len(ns)

    def f(v, *wb):
        axes = tuple(range(v.ndim - n_axes, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i]; i += 1
        if bias is not None:
            out = out + wb[i]
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, op_name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6):
    """RMSNorm (Llama-style). Computed in f32 for bf16 inputs, TPU-friendly.
    Reference analog: fused rms_norm in paddle/phi/kernels (fusion); greenfield
    here since the reference snapshot lacks a standalone rms_norm op."""
    def f(v, *w):
        dt = v.dtype
        v32 = v.astype(jnp.float32)
        ms = jnp.mean(jnp.square(v32), axis=-1, keepdims=True)
        out = v32 * jax.lax.rsqrt(ms + epsilon)
        out = out.astype(dt)
        if w:
            out = out * w[0]
        return out
    args = [x] + ([weight] if weight is not None else [])
    return apply_op(f, *args, op_name="rms_norm")


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None):
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    use_batch_stats = training and not (use_global_stats is True)

    def f(v, rm, rv, *wb):
        shape = [1] * v.ndim
        shape[ch_axis] = v.shape[ch_axis]
        if use_batch_stats:
            axes = tuple(i for i in range(v.ndim) if i != ch_axis)
            mean = jnp.mean(v, axis=axes)
            var = jnp.var(v, axis=axes)
        else:
            mean, var = rm, rv
        out = (v - jnp.reshape(mean, shape)) * jax.lax.rsqrt(
            jnp.reshape(var, shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * jnp.reshape(wb[i], shape); i += 1
        if bias is not None:
            out = out + jnp.reshape(wb[i], shape)
        return out

    args = [x, running_mean, running_var]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    out = apply_op(f, *args, op_name="batch_norm")

    if use_batch_stats and isinstance(running_mean, Tensor):
        # update running stats eagerly (leaf storage replacement)
        v = x.data if isinstance(x, Tensor) else x
        axes = tuple(i for i in range(v.ndim) if i != ch_axis)
        bm = jnp.mean(v, axis=axes)
        bv = jnp.var(v, axis=axes)
        running_mean._data = momentum * running_mean.data + (1 - momentum) * bm
        running_var._data = momentum * running_var.data + (1 - momentum) * bv
    return out


def instance_norm(x, weight=None, bias=None, epsilon=1e-5,
                  data_format="NCHW"):
    def f(v, *wb):
        axes = tuple(range(2, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + epsilon)
        shape = [1, v.shape[1]] + [1] * (v.ndim - 2)
        i = 0
        if weight is not None:
            out = out * jnp.reshape(wb[i], shape); i += 1
        if bias is not None:
            out = out + jnp.reshape(wb[i], shape)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, op_name="instance_norm")


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    def f(v, *wb):
        n, c = v.shape[0], v.shape[1]
        rest = v.shape[2:]
        g = num_groups
        vg = jnp.reshape(v, (n, g, c // g) + rest)
        axes = tuple(range(2, vg.ndim))
        mean = jnp.mean(vg, axis=axes, keepdims=True)
        var = jnp.var(vg, axis=axes, keepdims=True)
        out = jnp.reshape((vg - mean) * jax.lax.rsqrt(var + epsilon),
                          v.shape)
        shape = [1, c] + [1] * (v.ndim - 2)
        i = 0
        if weight is not None:
            out = out * jnp.reshape(wb[i], shape); i += 1
        if bias is not None:
            out = out + jnp.reshape(wb[i], shape)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, op_name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW"):
    def f(v):
        sq = jnp.square(v)
        half = size // 2
        c = v.shape[1]
        pads = [(0, 0)] * v.ndim
        pads[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        acc = jnp.zeros_like(v)
        for i in range(size):
            acc = acc + jax.lax.slice_in_dim(padded, i, i + c, axis=1)
        return v / jnp.power(k + alpha * acc, beta)
    return apply_op(f, x, op_name="local_response_norm")


# =========================== conv / pool =====================================
def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(i) for i in v)


def _conv_nd(x, weight, bias, stride, padding, dilation, groups,
             data_format, nd, transpose=False, output_padding=0):
    stride = _norm_tuple(stride, nd)
    dilation = _norm_tuple(dilation, nd)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if channel_last:
        lhs_spec = "N" + "DHW"[3 - nd:] + "C"
    else:
        lhs_spec = "NC" + "DHW"[3 - nd:]
    # paddle weight layout: [out_c, in_c/groups, *k] (conv) or
    # [in_c, out_c/groups, *k] (conv_transpose)
    rhs_spec = "OI" + "DHW"[3 - nd:]
    out_spec = lhs_spec
    k_spatial = tuple(int(s) for s in weight.shape[2:])

    if isinstance(padding, str):
        p_str = padding.upper()  # "SAME" / "VALID"
        if transpose:
            # explicit pads: VALID = 0; SAME makes output = input * stride
            if p_str == "VALID":
                pad = [(0, 0)] * nd
            else:
                pad = []
                for i in range(nd):
                    tot = max(dilation[i] * (k_spatial[i] - 1) + 1 - stride[i],
                              0)
                    pad.append((tot // 2, tot - tot // 2))
        else:
            pad = p_str
    else:
        p = _norm_tuple(padding, nd) if not (
            isinstance(padding, (list, tuple)) and len(padding) == 2 * nd) \
            else tuple(padding)
        if len(p) == nd:
            pad = [(int(i), int(i)) for i in p]
        else:
            pad = [(int(p[2 * i]), int(p[2 * i + 1])) for i in range(nd)]

    def f(v, w, *b):
        if transpose:
            # Gradient-of-conv semantics (paddle conv_transpose): output size
            # (in-1)*s - p_lo - p_hi + d*(k-1) + 1 + output_padding. Lower as
            # an input-dilated conv with the spatially-flipped, OI-swapped
            # kernel: lax pads on the dilated input are d*(k-1) - p, and
            # output_padding extends the high side.
            in_c = w.shape[0]
            ocg = w.shape[1]
            w2 = jnp.reshape(w, (groups, in_c // groups, ocg) + k_spatial)
            w2 = jnp.swapaxes(w2, 1, 2)
            w2 = jnp.reshape(w2, (groups * ocg, in_c // groups) + k_spatial)
            w2 = jnp.flip(w2, axis=tuple(range(2, 2 + nd)))
            opad = _norm_tuple(output_padding, nd)
            adj = [(dilation[i] * (k_spatial[i] - 1) - pad[i][0],
                    dilation[i] * (k_spatial[i] - 1) - pad[i][1] + opad[i])
                   for i in range(nd)]
            dn_t = jax.lax.conv_dimension_numbers(
                tuple(v.shape), tuple(w2.shape),
                (lhs_spec, rhs_spec, out_spec))
            out = jax.lax.conv_general_dilated(
                v, w2, (1,) * nd, adj, lhs_dilation=stride,
                rhs_dilation=dilation, dimension_numbers=dn_t,
                feature_group_count=groups)
        else:
            dn = jax.lax.conv_dimension_numbers(
                tuple(v.shape), tuple(w.shape),
                (lhs_spec, rhs_spec, out_spec))
            out = jax.lax.conv_general_dilated(
                v, w, stride, pad, rhs_dilation=dilation,
                dimension_numbers=dn, feature_group_count=groups)
        if b:
            shape = [1] * out.ndim
            ch_axis = out.ndim - 1 if channel_last else 1
            shape[ch_axis] = b[0].shape[0]
            out = out + jnp.reshape(b[0], shape)
        return out

    args = [x, weight] + ([bias] if bias is not None else [])
    return apply_op(f, *args, op_name="conv%dd%s" %
                    (nd, "_transpose" if transpose else ""))


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 1)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 2)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 3)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCL"):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 1, transpose=True,
                    output_padding=output_padding)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW"):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 2, transpose=True,
                    output_padding=output_padding)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW"):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 3, transpose=True,
                    output_padding=output_padding)


def _pool_nd(x, kernel_size, stride, padding, nd, reducer, init, data_format,
             ceil_mode=False, exclusive=True):
    ks = _norm_tuple(kernel_size, nd)
    st = _norm_tuple(stride if stride is not None else kernel_size, nd)
    pd = _norm_tuple(padding, nd)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    spatial0 = 1 if channel_last else 2

    def f(v):
        # ceil_mode: extend the high-side pad so the last partial window is
        # kept; the extension is treated as padding (excluded from avg counts
        # when exclusive), matching paddle's pool2d ceil semantics.
        extra = [0] * nd
        if ceil_mode:
            for i in range(nd):
                in_sz = v.shape[spatial0 + i]
                span = in_sz + 2 * pd[i] - ks[i]
                out_ceil = -(-span // st[i]) + 1
                # torch/paddle clamp: the last window must start inside
                # input + pad_lo, else it is dropped (no phantom all-pad
                # window)
                if (out_ceil - 1) * st[i] >= in_sz + pd[i]:
                    out_ceil -= 1
                extra[i] = max(
                    (out_ceil - 1) * st[i] + ks[i] - (in_sz + 2 * pd[i]), 0)
        sp_pads = tuple((pd[i], pd[i] + extra[i]) for i in range(nd))
        if channel_last:
            window = (1,) + ks + (1,)
            strides = (1,) + st + (1,)
            pads = ((0, 0),) + sp_pads + ((0, 0),)
        else:
            window = (1, 1) + ks
            strides = (1, 1) + st
            pads = ((0, 0), (0, 0)) + sp_pads
        if reducer == "max":
            return jax.lax.reduce_window(v, -jnp.inf, jax.lax.max, window,
                                         strides, pads)
        s = jax.lax.reduce_window(v, 0.0, jax.lax.add, window, strides, pads)
        if exclusive and (any(p > 0 for p in pd) or any(e > 0 for e in extra)):
            ones = jnp.ones_like(v)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides, pads)
            return s / cnt
        return s / float(np.prod(ks))
    return apply_op(f, x, op_name=f"{reducer}_pool{nd}d")


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCL"):
    return _pool_nd(x, kernel_size, stride, padding, 1, "max", -jnp.inf,
                    data_format, ceil_mode)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW"):
    return _pool_nd(x, kernel_size, stride, padding, 2, "max", -jnp.inf,
                    data_format, ceil_mode)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCDHW"):
    return _pool_nd(x, kernel_size, stride, padding, 3, "max", -jnp.inf,
                    data_format, ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCL"):
    return _pool_nd(x, kernel_size, stride, padding, 1, "avg", 0.0,
                    data_format, ceil_mode, exclusive)


def avg_pool2d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCHW"):
    return _pool_nd(x, kernel_size, stride, padding, 2, "avg", 0.0,
                    data_format, ceil_mode, exclusive)


def avg_pool3d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCDHW"):
    return _pool_nd(x, kernel_size, stride, padding, 3, "avg", 0.0,
                    data_format, ceil_mode, exclusive)


def _adaptive_pool(x, output_size, nd, mode, data_format):
    out_sz = _norm_tuple(output_size, nd)

    def f(v):
        spatial_start = 2 if not data_format.endswith("C") else 1
        out = v
        for i, o in enumerate(out_sz):
            ax = spatial_start + i
            in_sz = out.shape[ax]
            if in_sz % o == 0:
                # uniform windows: reshape + reduce (maps to one XLA reduce)
                k = in_sz // o
                new_shape = out.shape[:ax] + (o, k) + out.shape[ax + 1:]
                r = jnp.reshape(out, new_shape)
                out = jnp.max(r, axis=ax + 1) if mode == "max" \
                    else jnp.mean(r, axis=ax + 1)
            else:
                # non-uniform windows (torch/paddle rule: window i spans
                # [floor(i*in/o), ceil((i+1)*in/o))): contract along the axis
                # with a per-output-row membership mask — small dense [o, in]
                # matmul, MXU-friendly, static shapes
                starts = (np.arange(o) * in_sz) // o
                ends = -((-(np.arange(o) + 1) * in_sz) // o)
                idx = np.arange(in_sz)
                member = (idx[None, :] >= starts[:, None]) & \
                         (idx[None, :] < ends[:, None])
                moved = jnp.moveaxis(out, ax, -1)
                if mode == "max":
                    masked = jnp.where(
                        jnp.asarray(member), moved[..., None, :],
                        jnp.asarray(-jnp.inf, moved.dtype))
                    red = jnp.max(masked, axis=-1)
                else:
                    w = member / member.sum(axis=1, keepdims=True)
                    red = moved @ jnp.asarray(w, moved.dtype).T
                out = jnp.moveaxis(red, -1, ax)
        return out
    return apply_op(f, x, op_name=f"adaptive_{mode}_pool{nd}d")


def adaptive_avg_pool1d(x, output_size, data_format="NCL"):
    return _adaptive_pool(x, output_size, 1, "avg", data_format)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    return _adaptive_pool(x, output_size, 2, "avg", data_format)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    return _adaptive_pool(x, output_size, 3, "avg", data_format)


def _no_mask(return_mask):
    if return_mask:
        raise NotImplementedError(
            "return_mask=True (argmax indices) is not implemented; "
            "silently dropping it would corrupt tuple-unpacking callers")


def adaptive_max_pool1d(x, output_size, return_mask=False, data_format="NCL"):
    _no_mask(return_mask)
    return _adaptive_pool(x, output_size, 1, "max", data_format)


def adaptive_max_pool2d(x, output_size, return_mask=False, data_format="NCHW"):
    _no_mask(return_mask)
    return _adaptive_pool(x, output_size, 2, "max", data_format)


def adaptive_max_pool3d(x, output_size, return_mask=False,
                        data_format="NCDHW"):
    _no_mask(return_mask)
    return _adaptive_pool(x, output_size, 3, "max", data_format)


# =========================== resize / shuffle ================================
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    def f(v):
        channel_last = data_format.endswith("C")
        spatial_axes = list(range(1, v.ndim - 1)) if channel_last \
            else list(range(2, v.ndim))
        in_sizes = [v.shape[a] for a in spatial_axes]
        if size is not None:
            out_sizes = _norm_tuple(size, len(spatial_axes))
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor] * len(spatial_axes)
            out_sizes = [int(s * f_) for s, f_ in zip(in_sizes, sf)]
        shape = list(v.shape)
        for a, o in zip(spatial_axes, out_sizes):
            shape[a] = o
        m = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
        return jax.image.resize(v, shape, method=m)
    return apply_op(f, x, op_name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor

    def f(v):
        n, c, h, w = v.shape
        v6 = jnp.reshape(v, (n, c // (r * r), r, r, h, w))
        v6 = jnp.transpose(v6, (0, 1, 4, 2, 5, 3))
        return jnp.reshape(v6, (n, c // (r * r), h * r, w * r))
    return apply_op(f, x, op_name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor

    def f(v):
        n, c, h, w = v.shape
        v6 = jnp.reshape(v, (n, c, h // r, r, w // r, r))
        v6 = jnp.transpose(v6, (0, 1, 3, 5, 2, 4))
        return jnp.reshape(v6, (n, c * r * r, h // r, w // r))
    return apply_op(f, x, op_name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW"):
    def f(v):
        n, c, h, w = v.shape
        vg = jnp.reshape(v, (n, groups, c // groups, h, w))
        return jnp.reshape(jnp.swapaxes(vg, 1, 2), (n, c, h, w))
    return apply_op(f, x, op_name="channel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    ks = _norm_tuple(kernel_sizes, 2)
    st = _norm_tuple(strides, 2)
    pd = _norm_tuple(paddings, 2)
    dl = _norm_tuple(dilations, 2)

    def f(v):
        n, c = v.shape[0], v.shape[1]
        patches = jax.lax.conv_general_dilated_patches(
            v, ks, st, [(pd[0], pd[0]), (pd[1], pd[1])], rhs_dilation=dl,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return jnp.reshape(patches, (n, c * ks[0] * ks[1], -1))
    return apply_op(f, x, op_name="unfold")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    os_ = _norm_tuple(output_sizes, 2)
    ks = _norm_tuple(kernel_sizes, 2)
    st = _norm_tuple(strides, 2)
    pd = _norm_tuple(paddings, 2)

    def f(v):
        n, ckk, L = v.shape
        c = ckk // (ks[0] * ks[1])
        oh = (os_[0] + 2 * pd[0] - ks[0]) // st[0] + 1
        ow = (os_[1] + 2 * pd[1] - ks[1]) // st[1] + 1
        out = jnp.zeros((n, c, os_[0] + 2 * pd[0], os_[1] + 2 * pd[1]),
                        v.dtype)
        v6 = jnp.reshape(v, (n, c, ks[0], ks[1], oh, ow))
        for i in range(ks[0]):
            for j in range(ks[1]):
                patch = v6[:, :, i, j]
                out = out.at[:, :,
                             i:i + oh * st[0]:st[0],
                             j:j + ow * st[1]:st[1]].add(patch)
        return out[:, :, pd[0]:os_[0] + pd[0], pd[1]:os_[1] + pd[1]]
    return apply_op(f, x, op_name="fold")


# =========================== attention =======================================
def _unwrap(x):
    """Tensor → raw jnp array (attention masks/ids are constants, not
    taped)."""
    return x.data if hasattr(x, "data") else jnp.asarray(x)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, q_segment_ids=None,
                                 kv_segment_ids=None):
    """SDPA with [batch, seq, heads, head_dim] layout (paddle convention,
    reference: python/paddle/nn/functional/flash_attention.py). Routes to
    the Pallas flash kernel on TPU when enabled, else a jnp composite.

    ``key``/``value`` may carry fewer heads than ``query`` (GQA/MQA) — the
    Pallas kernel serves them natively (no KV replication in HBM); the
    composite broadcasts. ``attn_mask`` of any float/bool shape
    broadcastable to [B, H, Sq, Sk] is streamed through the kernel as an
    additive bias tile-by-tile (reference's fused_attention_op.cc arbitrary
    -mask seam). Masks produced by
    ``Transformer.generate_square_subsequent_mask`` are *recognized* (a
    ``_causal_diag`` tag) and served by the kernel's causal block-skip path
    without ever materializing or reading the S×S mask. Segment ids map the
    reference's varlen/unpadded flash variant.

    A non-trainable ``attn_mask`` is a *constant* on every route; a
    trainable float mask (``stop_gradient=False`` — a learned additive
    bias) takes the differentiable composite path and receives a gradient,
    matching the reference's logits-add / grad_bias behavior."""
    from paddle_tpu.core.flags import flag
    use_pallas = flag("use_pallas_kernels")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError(
            "q_segment_ids and kv_segment_ids must be passed together; for "
            "pure key padding use all-ones q_segment_ids")
    # trainable float masks (learned relative-position biases through
    # MultiHeadAttention / memory_efficient_attention) must RECEIVE a
    # gradient — the reference's composite adds the mask to the logits and
    # its fused kernel emits grad_bias. Route them to the differentiable
    # composite (the Pallas kernel streams the bias as a constant).
    mask_trainable = attn_mask is not None and \
        getattr(attn_mask, "stop_gradient", True) is False
    if mask_trainable and attn_mask.dtype == jnp.bool_:
        # a bool mask enters as a where() selector — structurally zero
        # grad on every route; the caller asked for one, so fail loudly
        raise ValueError(
            "a boolean attn_mask cannot receive a gradient (it selects, "
            "it is not added to the logits); pass a float additive mask "
            "or set attn_mask.stop_gradient = True")
    s_q, s_k = query.shape[1], key.shape[1]
    causal_tagged = (
        attn_mask is not None
        and getattr(attn_mask, "_causal_diag", False)
        and s_q == s_k and tuple(attn_mask.shape)[-2:] == (s_q, s_k))
    # off-TPU, and for lengths the kernel cannot tile, the composite
    # below is the path — chosen from the backend and the shapes. On TPU
    # a kernel error raises: it must never turn into the S×S composite
    use_flash = use_pallas and not mask_trainable \
        and jax.default_backend() == "tpu"
    if use_flash:
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bshd, flash_tileable)
        full_bias = attn_mask is not None and not causal_tagged \
            and tuple(attn_mask.shape)[-2] != 1
        use_flash = flash_tileable(s_q, s_k, full_bias)
    if use_flash:
        drop = float(dropout_p) if training else 0.0
        seed = None
        if drop > 0.0:
            # in-kernel position-hashed dropout; fresh seed per call from
            # the generator stream (a DIFFERENT pattern than the
            # composite's bernoulli — dropout RNG is backend-specific by
            # contract)
            seed = jax.random.randint(_gen.next_key(), (1,),
                                      minval=-2**31, maxval=2**31 - 1,
                                      dtype=jnp.int32)
        if attn_mask is None or causal_tagged:
            return flash_attention_bshd(
                query, key, value,
                causal=is_causal or causal_tagged,
                q_segment_ids=q_segment_ids,
                kv_segment_ids=kv_segment_ids,
                dropout_p=drop, dropout_seed=seed)
        return flash_attention_bshd(
            query, key, value, causal=is_causal,
            bias=_additive_mask(attn_mask),
            q_segment_ids=q_segment_ids,
            kv_segment_ids=kv_segment_ids,
            dropout_p=drop, dropout_seed=seed)

    drop_key = _gen.next_key() if (dropout_p > 0 and training) else None
    seg_mask = _segment_mask(q_segment_ids, kv_segment_ids)
    # a non-trainable attn_mask is a constant — closed over, NOT taped, so
    # the Pallas route (zero bias grad) and this composite agree; a
    # trainable one is passed as a taped operand instead (grad flows)
    mask_arr = None if attn_mask is None else _unwrap(attn_mask)

    def f(q, k, v, *taped_mask):
        scale = 1.0 / math.sqrt(q.shape[-1])
        # [B,S,H,D] -> [B,H,S,D]
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        if kt.shape[1] != qt.shape[1]:  # GQA: broadcast KV heads
            rep = qt.shape[1] // kt.shape[1]
            kt = jnp.repeat(kt, rep, axis=1)
            vt = jnp.repeat(vt, rep, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
        if is_causal:
            sq_, sk_ = logits.shape[-2], logits.shape[-1]
            causal = jnp.tril(jnp.ones((sq_, sk_), bool), sk_ - sq_)
            logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
        if seg_mask is not None:
            logits = jnp.where(seg_mask[:, None],
                               logits, jnp.finfo(logits.dtype).min)
        if taped_mask or mask_arr is not None:
            m = taped_mask[0] if taped_mask \
                else jax.lax.stop_gradient(mask_arr)
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, jnp.finfo(logits.dtype).min)
            else:
                logits = logits + m
        w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        if seg_mask is not None:  # zero fully-masked rows (pure padding)
            rowlive = jnp.any(seg_mask[:, None], axis=-1, keepdims=True)
            w = jnp.where(rowlive, w, 0.0)
        if drop_key is not None:
            keep = jax.random.bernoulli(drop_key, 1 - dropout_p, w.shape)
            w = jnp.where(keep, w / (1 - dropout_p), 0)
        out = jnp.einsum("bhqk,bhkd->bhqd", w, vt)
        return jnp.swapaxes(out, 1, 2)

    if mask_trainable:
        return apply_op(f, query, key, value, attn_mask,
                        op_name="scaled_dot_product_attention")
    return apply_op(f, query, key, value,
                    op_name="scaled_dot_product_attention")


def _additive_mask(mask):
    """bool (True = attend) → additive f32; float passes through raw."""
    m = _unwrap(mask)
    if m.dtype == jnp.bool_:
        return jnp.where(m, 0.0, jnp.float32(jnp.finfo(jnp.float32).min))
    return m


def _segment_mask(q_seg, kv_seg):
    """[B, Sq] x [B, Sk] ids → bool [B, Sq, Sk] (True = attend)."""
    if q_seg is None:
        return None
    qs = _unwrap(q_seg)
    ks = _unwrap(kv_seg)
    return qs[:, :, None] == ks[:, None, :]


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    training=True, q_segment_ids=None, kv_segment_ids=None):
    """Reference: python/paddle/nn/functional/flash_attention.py
    ``flash_attention`` / ``flash_attn_unpadded`` (segment ids are the
    TPU-idiomatic varlen form). GQA key/value head counts pass through."""
    return scaled_dot_product_attention(
        query, key, value, None, dropout, causal, training,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids)


# =========================== losses ==========================================
def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0):
    """Reference: python/paddle/nn/functional/loss.py cross_entropy →
    c_softmax_with_cross_entropy for the TP case (we get that via GSPMD when
    logits are vocab-sharded)."""
    def f(logits, lbl, *w):
        lp = jax.nn.log_softmax(logits, axis=axis) if use_softmax \
            else jnp.log(jnp.maximum(logits, 1e-30))
        if soft_label:
            tgt = lbl
            if label_smoothing > 0:
                n = lp.shape[axis]
                tgt = (1 - label_smoothing) * tgt + label_smoothing / n
            loss = -jnp.sum(tgt * lp, axis=axis)
        else:
            lbl_ = lbl.astype(jnp.int32)
            if lbl_.ndim == lp.ndim:
                lbl_ = jnp.squeeze(lbl_, axis)
            valid = lbl_ != ignore_index
            safe = jnp.where(valid, lbl_, 0)
            picked = jnp.take_along_axis(
                lp, safe[..., None], axis=-1)[..., 0] if axis in (-1, lp.ndim - 1) \
                else jnp.take_along_axis(lp, safe[..., None], axis=axis)
            if label_smoothing > 0:
                n = lp.shape[axis]
                smooth = jnp.mean(lp, axis=axis)
                picked = (1 - label_smoothing) * picked \
                    + label_smoothing * smooth
            loss = -jnp.where(valid, picked, 0.0)
            if w:
                tw = jnp.take(w[0], safe)
                loss = loss * tw
                if reduction == "mean":
                    # reference mean: sum / sum-of-weights over valid
                    # tokens; reduce in f32 so bf16/f16 losses never round
                    # the denominator (integer counts are exact only to
                    # 256 in bf16)
                    wt = jnp.sum(jnp.where(valid, tw, 0),
                                 dtype=jnp.float32)
                    return (jnp.sum(loss, dtype=jnp.float32) /
                            jnp.maximum(wt, 1e-12)).astype(loss.dtype)
            if reduction == "mean":
                # reference mean divides by the count of NON-ignored tokens
                # (including at the default ignore_index=-100); with no
                # ignored labels this equals loss.size, so always mask-mean.
                # f32 accumulation: see weighted branch.
                denom = jnp.maximum(jnp.sum(valid, dtype=jnp.float32), 1.0)
                return (jnp.sum(loss, dtype=jnp.float32) /
                        denom).astype(loss.dtype)
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply_op(f, *args, op_name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False, axis=-1,
                               ignore_index=-100, return_softmax=False):
    loss = cross_entropy(logits, label, reduction="none",
                         soft_label=soft_label, axis=axis,
                         ignore_index=ignore_index)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def mse_loss(input, label, reduction="mean"):
    return apply_op(lambda a, b: _reduce(jnp.square(a - b), reduction),
                    input, label, op_name="mse_loss")


def l1_loss(input, label, reduction="mean"):
    return apply_op(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                    input, label, op_name="l1_loss")


def square_error_cost(input, label):
    return apply_op(lambda a, b: jnp.square(a - b), input, label,
                    op_name="square_error_cost")


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    def f(lp, lbl, *w):
        lbl_ = lbl.astype(jnp.int32)
        valid = lbl_ != ignore_index
        safe = jnp.where(valid, lbl_, 0)
        picked = jnp.take_along_axis(lp, safe[..., None], axis=-1)[..., 0]
        loss = -jnp.where(valid, picked, 0.0)
        if w:
            wt = jnp.take(w[0], safe) * valid.astype(lp.dtype)
            loss = loss * jnp.take(w[0], safe)
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(wt), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(valid.astype(lp.dtype)), 1.0)
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply_op(f, *args, op_name="nll_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    def f(p, t, *w):
        p_ = jnp.clip(p, 1e-12, 1 - 1e-12)
        loss = -(t * jnp.log(p_) + (1 - t) * jnp.log1p(-p_))
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply_op(f, *args, op_name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    def f(z, t, *extra):
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = extra[i]; i += 1
        if pos_weight is not None:
            pw = extra[i]
        neg_abs = -jnp.abs(z)
        if pw is not None:
            log_w = (pw - 1) * t + 1
            loss = (1 - t) * z + log_w * (jnp.log1p(jnp.exp(neg_abs))
                                          + jnp.maximum(-z, 0))
        else:
            loss = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(neg_abs))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)
    args = [logit, label]
    if weight is not None:
        args.append(weight)
    if pos_weight is not None:
        args.append(pos_weight)
    return apply_op(f, *args, op_name="binary_cross_entropy_with_logits")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    def f(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce(loss, reduction)
    return apply_op(f, input, label, op_name="smooth_l1_loss")


def kl_div(input, label, reduction="mean"):
    def f(lp, t):
        loss = t * (jnp.log(jnp.maximum(t, 1e-30)) - lp)
        if reduction == "batchmean":
            return jnp.sum(loss) / lp.shape[0]
        return _reduce(loss, reduction)
    return apply_op(f, input, label, op_name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    def f(a, b, t):
        return _reduce(jnp.maximum(-t * (a - b) + margin, 0.0), reduction)
    return apply_op(f, input, other, label, op_name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    def f(a, t):
        loss = jnp.where(t == 1, a, jnp.maximum(margin - a, 0.0))
        return _reduce(loss, reduction)
    return apply_op(f, input, label, op_name="hinge_embedding_loss")


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean"):
    def f(a, b, t):
        cos = jnp.sum(a * b, -1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        loss = jnp.where(t == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
        return _reduce(loss, reduction)
    return apply_op(f, input1, input2, label,
                    op_name="cosine_embedding_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def f(a, pos, neg):
        dp = jnp.linalg.norm(a - pos + epsilon, ord=p, axis=-1)
        dn = jnp.linalg.norm(a - neg + epsilon, ord=p, axis=-1)
        if swap:
            # paddle/torch swap: also consider positive-negative distance
            dpn = jnp.linalg.norm(pos - neg + epsilon, ord=p, axis=-1)
            dn = jnp.minimum(dn, dpn)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)
    return apply_op(f, input, positive, negative,
                    op_name="triplet_margin_loss")


def log_loss(input, label, epsilon=1e-4):
    def f(p, t):
        return -t * jnp.log(p + epsilon) - (1 - t) * jnp.log(1 - p + epsilon)
    return apply_op(f, input, label, op_name="log_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean"):
    """CTC via the standard forward algorithm in log space (lax.scan over
    time). Reference: warpctc-backed ctc_loss (paddle/phi/kernels/gpu/
    warpctc_kernel.cu); here it is a pure XLA scan — no external lib."""
    def f(lp, lbl, in_len, lbl_len):
        # lp: [T, B, C] log-probs; lbl: [B, L]
        T, B, C = lp.shape
        L = lbl.shape[1]
        S = 2 * L + 1
        # extended label sequence with blanks
        ext = jnp.full((B, S), blank, lbl.dtype)
        ext = ext.at[:, 1::2].set(lbl)
        neg_inf = jnp.array(-1e30, lp.dtype)
        alpha0 = jnp.full((B, S), neg_inf)
        alpha0 = alpha0.at[:, 0].set(lp[0, :, blank])
        first_lbl = jnp.take_along_axis(lp[0], ext[:, 1:2], axis=-1)[:, 0]
        alpha0 = alpha0.at[:, 1].set(first_lbl)

        same_as_prev2 = jnp.concatenate(
            [jnp.zeros((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, lp_t):
            a_shift1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            a_shift2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            a_shift2 = jnp.where(same_as_prev2, neg_inf, a_shift2)
            m = jnp.maximum(jnp.maximum(alpha, a_shift1), a_shift2)
            s = (jnp.exp(alpha - m) + jnp.exp(a_shift1 - m)
                 + jnp.exp(a_shift2 - m))
            new = m + jnp.log(jnp.maximum(s, 1e-30))
            emit = jnp.take_along_axis(lp_t, ext, axis=-1)
            new = new + emit
            return new, new

        _, alphas = jax.lax.scan(step, alpha0, lp[1:],
                                 unroll=min(int(lp.shape[0] - 1), 8))
        # [T, B, S] alpha per timestep; read each sample's alpha at its own
        # final frame t = input_lengths[b] - 1 (padded frames past the true
        # length must not contribute — warpctc honors per-sample lengths).
        all_alphas = jnp.concatenate([alpha0[None], alphas], axis=0)
        t_idx = jnp.clip(in_len.astype(jnp.int32) - 1, 0, T - 1)
        aT = jnp.take_along_axis(
            all_alphas, t_idx[None, :, None].astype(jnp.int32),
            axis=0)[0]  # [B, S]
        # gather final two states at position 2*label_len-1 and 2*label_len
        idx_last = 2 * lbl_len
        idx_prev = jnp.maximum(idx_last - 1, 0)
        a_last = jnp.take_along_axis(aT, idx_last[:, None], axis=1)[:, 0]
        a_prev = jnp.take_along_axis(aT, idx_prev[:, None], axis=1)[:, 0]
        m = jnp.maximum(a_last, a_prev)
        ll = m + jnp.log(jnp.exp(a_last - m) + jnp.exp(a_prev - m))
        loss = -ll
        return _reduce(loss, reduction)
    return apply_op(f, log_probs, labels, input_lengths, label_lengths,
                    op_name="ctc_loss")

from paddle_tpu.nn import functional_extras as _fx  # noqa: E402
from paddle_tpu.nn.functional_extras import *  # noqa: F401,F403,E402
__all__ = list(__all__) + list(_fx.__all__)
