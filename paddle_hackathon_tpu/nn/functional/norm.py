"""Normalization functionals (ref ``python/paddle/nn/functional/norm.py``;
kernels ref ``paddle/phi/kernels/gpu/batch_norm_kernel.cu``,
``layer_norm_kernel.cu``).

These are the reference's fused norm kernels expressed as jnp compositions —
XLA fuses the mean/var/normalize chain into one or two HBM passes; the Pallas
fused layernorm+residual+dropout (incubate/) covers the transformer hot path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.autograd import apply_op
from ...core.tensor import Tensor


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """BatchNorm. In training mode the running stats are updated in place on
    the stats tensors (matching the reference's in-place mean/variance
    outputs, ``batch_norm_kernel``)."""
    channel_axis = 1 if data_format.startswith("NC") else -1
    use_batch_stats = training and not use_global_stats

    x = _t(x)
    v = x._value
    axes = tuple(i for i in range(v.ndim)
                 if i != (channel_axis % v.ndim))

    if use_batch_stats:
        # compute batch stats eagerly (also used to update running stats)
        mean = jnp.mean(v, axis=axes)
        var = jnp.var(v, axis=axes)
        if running_mean is not None:
            running_mean._set_value(
                momentum * running_mean._value + (1 - momentum) * mean)
        if running_var is not None:
            n = v.size / mean.size
            unbiased = var * n / max(n - 1, 1)
            running_var._set_value(
                momentum * running_var._value + (1 - momentum) * unbiased)
        mean_t, var_t = Tensor(mean), Tensor(var)
    else:
        mean_t, var_t = _t(running_mean), _t(running_var)

    # the closure must capture only the None-ness of weight/bias, not the
    # Tensor objects (identity-keyed mutable cells defeat the dispatch
    # cache — core/autograd._freeze); the values flow through args
    has_w, has_b = weight is not None, bias is not None

    def fn(v, m, s, *rest):
        shape = [1] * v.ndim
        shape[channel_axis % v.ndim] = m.shape[0]
        out = (v - m.reshape(shape)) / jnp.sqrt(s.reshape(shape) + epsilon)
        i = 0
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        return out

    args = [x, mean_t, var_t]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply_op("batch_norm", fn, args)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))

    has_w, has_b = weight is not None, bias is not None

    def fn(v, *rest):
        axes = tuple(range(v.ndim - n_axes, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) / jnp.sqrt(var + epsilon)
        i = 0
        if has_w:
            out = out * rest[i]
            i += 1
        if has_b:
            out = out + rest[i]
        return out

    args = [_t(x)]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply_op("layer_norm", fn, args)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = not data_format.startswith("NC")

    has_w, has_b = weight is not None, bias is not None

    def fn(v, *rest):
        if channel_last:
            v = jnp.moveaxis(v, -1, 1)
        n, c = v.shape[0], v.shape[1]
        g = num_groups
        grouped = v.reshape((n, g, c // g) + v.shape[2:])
        axes = tuple(range(2, grouped.ndim))
        mean = jnp.mean(grouped, axis=axes, keepdims=True)
        var = jnp.var(grouped, axis=axes, keepdims=True)
        out = ((grouped - mean) / jnp.sqrt(var + epsilon)).reshape(v.shape)
        shape = [1, c] + [1] * (v.ndim - 2)
        i = 0
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [_t(x)]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply_op("group_norm", fn, args)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    has_w, has_b = weight is not None, bias is not None

    def fn(v, *rest):
        axes = tuple(range(2, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) / jnp.sqrt(var + eps)
        shape = [1, v.shape[1]] + [1] * (v.ndim - 2)
        i = 0
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        return out

    args = [_t(x)]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply_op("instance_norm", fn, args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def fn(v):
        sq = jnp.square(v)
        half = size // 2
        ch_axis = 1 if data_format.startswith("NC") else v.ndim - 1
        c = v.shape[ch_axis]
        pads = [(0, 0)] * v.ndim
        pads[ch_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        acc = jnp.zeros_like(v)
        for i in range(size):
            sl = [slice(None)] * v.ndim
            sl[ch_axis] = slice(i, i + c)
            acc = acc + padded[tuple(sl)]
        return v / jnp.power(k + alpha * acc, beta)
    return apply_op("local_response_norm", fn, [_t(x)])


def rms_norm_f32(v, weight=None, epsilon=1e-6):
    """RMSNorm of an array over its last axis, in float32 whatever the
    input's dtype (a bfloat16 mean of squares is 8 bits of a sum), and
    left in float32 for the caller to round once."""
    v32 = v.astype(jnp.float32)
    out = v32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(v32), axis=-1, keepdims=True) + epsilon)
    return out if weight is None else out * weight.astype(jnp.float32)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm — not in the reference (2022-era) but required by modern LM
    parity; the Pallas fused version lives in incubate/."""
    def fn(v, *rest):
        return rms_norm_f32(v, *rest, epsilon=epsilon).astype(v.dtype)
    args = [_t(x)]
    if weight is not None:
        args.append(_t(weight))
    return apply_op("rms_norm", fn, args)
