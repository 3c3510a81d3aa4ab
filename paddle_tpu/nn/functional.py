"""paddle.nn.functional — TPU-native functional ops.

Upstream: python/paddle/nn/functional/*.py (activation.py, common.py,
conv.py, loss.py, norm.py, pooling.py). All ops are pure jax under the
hood (XLA fuses elementwise chains into surrounding matmuls/convs); they
flow through the autograd tape via apply_op, and trace cleanly under jit.
Convolutions use lax.conv_general_dilated in NCHW/NCL layouts; pooling uses
lax.reduce_window — both map directly onto TPU MXU/VPU tiling.
"""
from __future__ import annotations

import math as _math
import numbers
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import framework
from ..dtype import convert_dtype
from ..ops._helpers import defop
from ..tensor import Tensor, apply_op, to_jax

# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def relu(x, name=None):
    return defop(jax.nn.relu, name='relu')(x)


def relu_(x):
    return x._rebind(relu(x))


def relu6(x, name=None):
    return defop(lambda v: jnp.clip(v, 0, 6), name='relu6')(x)


def gelu(x, approximate=False, name=None):
    return defop(lambda v: jax.nn.gelu(v, approximate=bool(approximate)),
                 name='gelu')(x)


def silu(x, name=None):
    return defop(jax.nn.silu, name='silu')(x)


swish = silu


def sigmoid(x, name=None):
    return defop(jax.nn.sigmoid, name='sigmoid')(x)


def tanh(x, name=None):
    return defop(jnp.tanh, name='tanh')(x)


def leaky_relu(x, negative_slope=0.01, name=None):
    return defop(lambda v: jnp.where(v >= 0, v, negative_slope * v),
                 name='leaky_relu')(x)


def elu(x, alpha=1.0, name=None):
    return defop(lambda v: jax.nn.elu(v, alpha), name='elu')(x)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return defop(lambda v: scale * jnp.where(v > 0, v, alpha * jnp.expm1(v)),
                 name='selu')(x)


def celu(x, alpha=1.0, name=None):
    return defop(lambda v: jax.nn.celu(v, alpha), name='celu')(x)


def hardswish(x, name=None):
    return defop(lambda v: v * jnp.clip(v + 3, 0, 6) / 6, name='hardswish')(x)


def hardsigmoid(x, slope=1 / 6, offset=0.5, name=None):
    return defop(lambda v: jnp.clip(slope * v + offset, 0, 1),
                 name='hardsigmoid')(x)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return defop(lambda v: jnp.clip(v, min, max), name='hardtanh')(x)


def hardshrink(x, threshold=0.5, name=None):
    return defop(lambda v: jnp.where(jnp.abs(v) > threshold, v, 0),
                 name='hardshrink')(x)


def softshrink(x, threshold=0.5, name=None):
    return defop(
        lambda v: jnp.where(v > threshold, v - threshold,
                            jnp.where(v < -threshold, v + threshold, 0)),
        name='softshrink')(x)


def tanhshrink(x, name=None):
    return defop(lambda v: v - jnp.tanh(v), name='tanhshrink')(x)


def mish(x, name=None):
    return defop(lambda v: v * jnp.tanh(jax.nn.softplus(v)), name='mish')(x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return defop(
        lambda v: jnp.where(beta * v > threshold, v,
                            jnp.log1p(jnp.exp(beta * v)) / beta),
        name='softplus')(x)


def softsign(x, name=None):
    return defop(lambda v: v / (1 + jnp.abs(v)), name='softsign')(x)


def logsigmoid(x, name=None):
    return defop(jax.nn.log_sigmoid, name='log_sigmoid')(x)


def glu(x, axis=-1, name=None):
    def f(v):
        a, b = jnp.split(v, 2, axis=axis)
        return a * jax.nn.sigmoid(b)
    return defop(f, name='glu')(x)


def prelu(x, weight, data_format='NCHW', name=None):
    def f(v, w):
        if w.size == 1:
            wb = w.reshape(())
        else:
            ch_axis = 1 if data_format[1] == 'C' else v.ndim - 1
            shape = [1] * v.ndim
            shape[ch_axis] = w.size
            wb = w.reshape(shape)
        return jnp.where(v >= 0, v, wb * v)
    return defop(f, name='prelu')(x, weight)


def softmax(x, axis=-1, dtype=None, name=None):
    def f(v):
        if dtype is not None:
            v = v.astype(convert_dtype(dtype))
        return jax.nn.softmax(v, axis=axis)
    return defop(f, name='softmax')(x)


def log_softmax(x, axis=-1, dtype=None, name=None):
    def f(v):
        if dtype is not None:
            v = v.astype(convert_dtype(dtype))
        return jax.nn.log_softmax(v, axis=axis)
    return defop(f, name='log_softmax')(x)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    key = framework.next_rng_key()

    def f(v):
        g = jax.random.gumbel(key, v.shape, v.dtype)
        y = jax.nn.softmax((v + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis)
            onehot = jax.nn.one_hot(idx, y.shape[axis], dtype=y.dtype,
                                    axis=axis)
            y = jax.lax.stop_gradient(onehot - y) + y
        return y
    return defop(f, name='gumbel_softmax')(x)


# ---------------------------------------------------------------------------
# linear / embedding / common
# ---------------------------------------------------------------------------


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b, W shape [in, out] (paddle convention)."""
    if bias is None:
        return defop(lambda v, w: v @ w, name='linear')(x, weight)
    return defop(lambda v, w, b: v @ w + b, name='linear')(x, weight, bias)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    def f(ids, w):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None:
            pi = padding_idx if padding_idx >= 0 else w.shape[0] + padding_idx
            mask = (ids == pi)[..., None]
            out = jnp.where(mask, jnp.zeros((), out.dtype), out)
        return out
    return defop(f, name='embedding')(x, weight)


def one_hot(x, num_classes, name=None):
    from ..ops import creation
    return creation.one_hot(x, num_classes)


def dropout(x, p=0.5, axis=None, training=True, mode='upscale_in_train',
            name=None):
    if not training or p == 0:
        return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
    if p == 1:
        return defop(lambda v: jnp.zeros_like(v), name='dropout')(x)
    key = framework.next_rng_key()

    def f(v):
        shape = list(v.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == 'upscale_in_train':
            return jnp.where(keep, v / (1.0 - p), jnp.zeros((), v.dtype))
        return jnp.where(keep, v, jnp.zeros((), v.dtype))
    # cacheable=False: f closes over a fresh PRNG key array every call
    return defop(f, name='dropout', cacheable=False)(x)


def dropout2d(x, p=0.5, training=True, data_format='NCHW', name=None):
    ax = [0, 1] if data_format == 'NCHW' else [0, 3]
    return dropout(x, p=p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format='NCDHW', name=None):
    ax = [0, 1] if data_format == 'NCDHW' else [0, 4]
    return dropout(x, p=p, axis=ax, training=training)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(v):
        n = jnp.linalg.norm(v, ord=p, axis=axis, keepdims=True)
        return v / jnp.maximum(n, epsilon)
    return defop(f, name='normalize')(x)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(l, *pd):
        k = l.shape[-1]
        smooth = pd[0] if pd else jnp.full((k,), 1.0 / k, l.dtype)
        return (1 - epsilon) * l + epsilon * smooth
    args = (label,) if prior_dist is None else (label, prior_dist)
    return defop(f, name='label_smooth')(*args)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def f(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.linalg.norm(a, axis=axis)
        nb = jnp.linalg.norm(b, axis=axis)
        return dot / jnp.maximum(na * nb, eps)
    return defop(f, name='cosine_similarity')(x1, x2)


def sequence_mask(x, maxlen=None, dtype='int64', name=None):
    def f(v):
        m = int(maxlen) if maxlen is not None else int(np.asarray(to_jax(x)).max())
        rng = jnp.arange(m)
        return (rng[None, :] < v[..., None]).astype(convert_dtype(dtype))
    return defop(f, name='sequence_mask')(x)


def bilinear(x1, x2, weight, bias=None, name=None):
    def f(a, b, w, *bb):
        out = jnp.einsum('bi,oij,bj->bo', a, w, b)
        if bb:
            out = out + bb[0]
        return out
    args = (x1, x2, weight) if bias is None else (x1, x2, weight, bias)
    return defop(f, name='bilinear')(*args)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, numbers.Integral):
        normalized_shape = (int(normalized_shape),)
    n_axes = len(tuple(normalized_shape))

    def f(v, *wb):
        axes = tuple(range(v.ndim - n_axes, v.ndim))
        mu = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(v - mu), axis=axes, keepdims=True)
        out = (v - mu) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i]; i += 1
        if bias is not None:
            out = out + wb[i]
        return out
    args = [x] + [t for t in (weight, bias) if t is not None]
    return defop(f, name='layer_norm')(*args)


def rms_norm(x, weight=None, bias=None, epsilon=1e-6, axis=-1, name=None):
    """Root-mean-square norm (Llama-style; fused by XLA, pallas kernel on TPU)."""
    from ..ops import pallas as _pallas

    def f(v, *wb):
        out = _pallas.rms_norm(v, epsilon=epsilon, axis=axis)
        i = 0
        if weight is not None:
            out = out * wb[i]; i += 1
        if bias is not None:
            out = out + wb[i]
        return out
    args = [x] + [t for t in (weight, bias) if t is not None]
    return defop(f, name='rms_norm')(*args)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format='NCHW', use_global_stats=None, name=None):
    """BN over the channel axis. In training mode the running stats tensors
    are updated in place (matching the reference's mutable-state semantics);
    under jit the updated values flow out via functional_state buffers."""
    ch_axis = 1 if data_format.startswith('NC') and to_jax(x).ndim > 1 else -1
    use_batch = training and not use_global_stats

    def stats_f(v):
        axes = tuple(i for i in range(v.ndim) if i != ch_axis % v.ndim)
        mu = jnp.mean(v, axis=axes)
        var = jnp.mean(jnp.square(v), axis=axes) - jnp.square(mu)
        return mu, var

    if use_batch:
        mu_t, var_t = apply_op(stats_f, x, _name='bn_stats')
        n = to_jax(x).size // to_jax(x).shape[ch_axis]
        unbiased = var_t * (n / max(n - 1, 1))
        running_mean._data = (momentum * to_jax(running_mean)
                              + (1 - momentum) * to_jax(mu_t))
        running_var._data = (momentum * to_jax(running_var)
                             + (1 - momentum) * to_jax(unbiased))
        mean_arg, var_arg = mu_t, var_t
    else:
        mean_arg, var_arg = running_mean, running_var

    def f(v, mu, var, *wb):
        shape = [1] * v.ndim
        shape[ch_axis] = v.shape[ch_axis]
        out = (v - mu.reshape(shape)) * jax.lax.rsqrt(
            var.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape); i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out
    args = [x, mean_arg, var_arg] + [t for t in (weight, bias) if t is not None]
    return defop(f, name='batch_norm')(*args)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format='NCHW', name=None):
    def f(v, *wb):
        if data_format != 'NCHW' and not data_format.startswith('NC'):
            v = jnp.moveaxis(v, -1, 1)
        n, c = v.shape[0], v.shape[1]
        g = int(num_groups)
        vv = v.reshape((n, g, c // g) + v.shape[2:])
        axes = tuple(range(2, vv.ndim))
        mu = jnp.mean(vv, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(vv - mu), axis=axes, keepdims=True)
        out = ((vv - mu) * jax.lax.rsqrt(var + epsilon)).reshape(v.shape)
        shape = [1] * v.ndim
        shape[1] = c
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape); i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        if data_format != 'NCHW' and not data_format.startswith('NC'):
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = [x] + [t for t in (weight, bias) if t is not None]
    return defop(f, name='group_norm')(*args)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format='NCHW', name=None):
    def f(v, *wb):
        axes = tuple(range(2, v.ndim))
        mu = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(v - mu), axis=axes, keepdims=True)
        out = (v - mu) * jax.lax.rsqrt(var + eps)
        shape = [1, v.shape[1]] + [1] * (v.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape); i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out
    args = [x] + [t for t in (weight, bias) if t is not None]
    return defop(f, name='instance_norm')(*args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format='NCHW', name=None):
    def f(v):
        sq = jnp.square(v)
        half = size // 2
        pad = [(0, 0)] * v.ndim
        pad[1] = (half, size - half - 1)
        sq = jnp.pad(sq, pad)
        acc = sum(jax.lax.slice_in_dim(sq, i, i + v.shape[1], axis=1)
                  for i in range(size))
        return v / jnp.power(k + alpha * acc / size, beta)
    return defop(f, name='local_response_norm')(x)


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------


def _tuplize(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    v = tuple(int(i) for i in v)
    return v if len(v) == n else tuple(v) * (n // len(v))


def _conv_padding(padding, n, stride, dilation, ksize):
    """Normalize paddle padding spec → lax padding list of (lo, hi)."""
    if isinstance(padding, str):
        return padding.upper()  # 'SAME' / 'VALID'
    if isinstance(padding, (int, np.integer)):
        return [(int(padding), int(padding))] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, (int, np.integer)) for p in padding):
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)]
    # [[0,0],[0,0],[lo,hi],...] form
    flat = [p for p in padding if isinstance(p, (list, tuple))]
    if flat:
        return [(int(p[0]), int(p[1])) for p in flat[-n:]]
    raise ValueError(f'bad padding {padding!r}')


def _conv_nd(x, weight, bias, stride, padding, dilation, groups, n,
             channel_last=False, name='conv'):
    stride_t = _tuplize(stride, n)
    dil_t = _tuplize(dilation, n)

    def f(v, w, *b):
        pad = _conv_padding(padding, n, stride_t, dil_t, w.shape[2:])
        if channel_last:
            v = jnp.moveaxis(v, -1, 1)
        spatial = ''.join('DHW'[3 - n:][i] for i in range(n))
        dn = jax.lax.conv_dimension_numbers(
            v.shape, w.shape,
            ('NC' + spatial, 'OI' + spatial, 'NC' + spatial))
        out = jax.lax.conv_general_dilated(
            v, w, window_strides=stride_t, padding=pad,
            rhs_dilation=dil_t, dimension_numbers=dn,
            feature_group_count=groups,
            preferred_element_type=None)
        if b:
            out = out + b[0].reshape((1, -1) + (1,) * n)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = (x, weight) if bias is None else (x, weight, bias)
    return defop(f, name=name)(*args)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format='NCL', name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 1,
                    channel_last=(data_format == 'NLC'), name='conv1d')


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format='NCHW', name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 2,
                    channel_last=(data_format == 'NHWC'), name='conv2d')


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format='NCDHW', name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 3,
                    channel_last=(data_format == 'NDHWC'), name='conv3d')


def _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                       dilation, groups, n, channel_last, name):
    stride_t = _tuplize(stride, n)
    dil_t = _tuplize(dilation, n)
    opad_t = _tuplize(output_padding, n)

    def f(v, w, *b):
        if channel_last:
            v = jnp.moveaxis(v, -1, 1)
        pad = _conv_padding(padding, n, stride_t, dil_t, w.shape[2:])
        if isinstance(pad, str):
            pads = [(0, 0)] * n if pad == 'VALID' else None
            if pads is None:
                raise ValueError('SAME padding unsupported for conv_transpose')
            pad = pads
        # gradient-of-conv formulation: lhs-dilate the input by stride
        k = [(w.shape[2 + i] - 1) * dil_t[i] + 1 for i in range(n)]
        tpad = [(k[i] - 1 - pad[i][0], k[i] - 1 - pad[i][1] + opad_t[i])
                for i in range(n)]
        spatial = ''.join('DHW'[3 - n:][i] for i in range(n))
        # weight layout is [in, out//groups, *k] for paddle conv_transpose
        w_t = jnp.flip(w, axis=tuple(range(2, 2 + n)))
        if groups > 1:
            gi = w.shape[0] // groups
            w_t = w_t.reshape((groups, gi) + w_t.shape[1:])
            w_t = jnp.moveaxis(w_t, 2, 1).reshape(
                (groups * w.shape[1], gi) + w.shape[2:])
        else:
            w_t = jnp.swapaxes(w_t, 0, 1)
        dn = jax.lax.conv_dimension_numbers(
            v.shape, w_t.shape,
            ('NC' + spatial, 'OI' + spatial, 'NC' + spatial))
        out = jax.lax.conv_general_dilated(
            v, w_t, window_strides=(1,) * n, padding=tpad,
            lhs_dilation=stride_t, rhs_dilation=dil_t,
            dimension_numbers=dn, feature_group_count=groups)
        if b:
            out = out + b[0].reshape((1, -1) + (1,) * n)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = (x, weight) if bias is None else (x, weight, bias)
    return defop(f, name=name)(*args)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format='NCL', name=None):
    return _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                              dilation, groups, 1, data_format == 'NLC',
                              'conv1d_transpose')


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format='NCHW', output_size=None, name=None):
    return _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                              dilation, groups, 2, data_format == 'NHWC',
                              'conv2d_transpose')


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format='NCDHW', output_size=None, name=None):
    return _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                              dilation, groups, 3, data_format == 'NDHWC',
                              'conv3d_transpose')


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


def _pool_nd(x, kernel, stride, padding, n, reducer, init, ceil_mode=False,
             count_include_pad=True, average=False, name='pool'):
    k_t = _tuplize(kernel, n)
    s_t = _tuplize(stride if stride is not None else kernel, n)

    def f(v):
        pad = _conv_padding(padding, n, s_t, (1,) * n, k_t)
        if isinstance(pad, str):
            raise ValueError('str padding unsupported in pool')
        pad = list(pad)
        # ceil_mode: allow a final partial window, realized as extra
        # high-side padding — but only if that window starts inside the
        # input-or-left-padding extent (torch/paddle rule)
        extra = _ceil_mode_extra(v.shape[2:], k_t, s_t, pad) if ceil_mode \
            else (0,) * n
        window = (1, 1) + k_t
        strides = (1, 1) + s_t
        pads = [(0, 0), (0, 0)] + [(lo, hi + e)
                                   for (lo, hi), e in zip(pad, extra)]
        out = jax.lax.reduce_window(v, init, reducer, window, strides, pads)
        if average:
            if count_include_pad and not any(extra):
                out = out / float(np.prod(k_t))
            elif count_include_pad:
                # regular padding counts toward the divisor; the ceil-mode
                # extra cells never do
                ones = jnp.pad(jnp.ones(v.shape, v.dtype),
                               [(0, 0), (0, 0)] + pad, constant_values=1)
                cnt = jax.lax.reduce_window(
                    ones, 0.0, jax.lax.add, window, strides,
                    [(0, 0), (0, 0)] + [(0, e) for e in extra])
                out = out / cnt
            else:
                ones = jnp.ones(v.shape, v.dtype)
                cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                            strides, pads)
                out = out / cnt
        return out
    return defop(f, name=name)(x)


def _ceil_mode_extra(spatial, k_t, s_t, pad):
    """Per-dim extra high-side padding a ceil-mode pool needs so the last
    (partial) window exists; 0 where floor and ceil outputs coincide."""
    extra = []
    for i, h in enumerate(spatial):
        lo, hi = pad[i]
        eff = h + lo + hi - k_t[i]
        out = -(-eff // s_t[i]) + 1  # ceil division
        if (out - 1) * s_t[i] >= h + lo:
            out -= 1
        extra.append(max(0, (out - 1) * s_t[i] + k_t[i] - (h + lo + hi)))
    return tuple(extra)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    return _pool_nd(x, kernel_size, stride, padding, 1, jax.lax.max,
                    -jnp.inf, ceil_mode, name='max_pool1d')


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format='NCHW', name=None):
    if return_mask:
        return max_pool2d_with_index(x, kernel_size, stride, padding,
                                     ceil_mode)
    return _pool_nd(x, kernel_size, stride, padding, 2, jax.lax.max,
                    -jnp.inf, ceil_mode, name='max_pool2d')


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format='NCDHW', name=None):
    return _pool_nd(x, kernel_size, stride, padding, 3, jax.lax.max,
                    -jnp.inf, ceil_mode, name='max_pool3d')


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    return _pool_nd(x, kernel_size, stride, padding, 1, jax.lax.add, 0.0,
                    ceil_mode, count_include_pad=not exclusive, average=True,
                    name='avg_pool1d')


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format='NCHW',
               name=None):
    return _pool_nd(x, kernel_size, stride, padding, 2, jax.lax.add, 0.0,
                    ceil_mode, count_include_pad=not exclusive, average=True,
                    name='avg_pool2d')


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format='NCDHW',
               name=None):
    return _pool_nd(x, kernel_size, stride, padding, 3, jax.lax.add, 0.0,
                    ceil_mode, count_include_pad=not exclusive, average=True,
                    name='avg_pool3d')


def _adaptive_pool(x, output_size, n, maximum, name):
    def f(v):
        out_sz = _tuplize(output_size, n)
        spatial = v.shape[-n:]
        # integer bucketing identical to the reference's adaptive pooling
        res = v
        for d in range(n):
            in_d = spatial[d]
            out_d = out_sz[d]
            axis = v.ndim - n + d
            starts = [int(_math.floor(i * in_d / out_d)) for i in range(out_d)]
            ends = [int(_math.ceil((i + 1) * in_d / out_d)) for i in range(out_d)]
            pieces = []
            for s, e in zip(starts, ends):
                seg = jax.lax.slice_in_dim(res, s, e, axis=axis)
                red = (jnp.max if maximum else jnp.mean)(seg, axis=axis,
                                                         keepdims=True)
                pieces.append(red)
            res = jnp.concatenate(pieces, axis=axis)
        return res
    return defop(f, name=name)(x)


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_pool(x, output_size, 1, False, 'adaptive_avg_pool1d')


def adaptive_avg_pool2d(x, output_size, data_format='NCHW', name=None):
    return _adaptive_pool(x, output_size, 2, False, 'adaptive_avg_pool2d')


def adaptive_avg_pool3d(x, output_size, data_format='NCDHW', name=None):
    return _adaptive_pool(x, output_size, 3, False, 'adaptive_avg_pool3d')


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    return _adaptive_pool(x, output_size, 1, True, 'adaptive_max_pool1d')


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive_pool(x, output_size, 2, True, 'adaptive_max_pool2d')


# ---------------------------------------------------------------------------
# shape ops
# ---------------------------------------------------------------------------


def pad(x, pad, mode='constant', value=0.0, data_format='NCHW', name=None):
    """Pad the last len(pad)//2 dims, innermost-first (reference layout)."""
    pad_l = [int(p) for p in (pad.tolist() if hasattr(pad, 'tolist') else pad)]

    def f(v):
        if len(pad_l) == 2 * v.ndim:
            cfg = [(pad_l[2 * i], pad_l[2 * i + 1]) for i in range(v.ndim)]
        else:
            # innermost-dim-first pairs, padding the last k dims
            k = len(pad_l) // 2
            cfg = [(0, 0)] * (v.ndim - k) + [
                (pad_l[2 * (k - 1 - i)], pad_l[2 * (k - 1 - i) + 1])
                for i in range(k)]
        jmode = {'constant': 'constant', 'reflect': 'reflect',
                 'replicate': 'edge', 'circular': 'wrap'}[mode]
        if jmode == 'constant':
            return jnp.pad(v, cfg, mode=jmode,
                           constant_values=np.asarray(value, v.dtype))
        return jnp.pad(v, cfg, mode=jmode)
    return defop(f, name='pad')(x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (NCHW → [N, C*kh*kw, L]) via conv_general_dilated_patches."""
    k = _tuplize(kernel_sizes, 2)
    s = _tuplize(strides, 2)
    d = _tuplize(dilations, 2)

    def f(v):
        pd = _conv_padding(paddings, 2, s, d, k)
        patches = jax.lax.conv_general_dilated_patches(
            v, filter_shape=k, window_strides=s, padding=pd,
            rhs_dilation=d, dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
        n = v.shape[0]
        return patches.reshape(n, patches.shape[1], -1)
    return defop(f, name='unfold')(x)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0,
         dilations=1, name=None):
    """col2im — inverse of unfold: [N, C*kh*kw, L] -> NCHW with
    overlapping patches summed. TPU-native formulation: one
    scatter-add over the same patch index map unfold reads from."""
    oh, ow = _tuplize(output_sizes, 2)
    kh, kw = _tuplize(kernel_sizes, 2)
    sh, sw = _tuplize(strides, 2)
    dh, dw = _tuplize(dilations, 2)
    p = _tuplize(paddings, 2) if not isinstance(paddings, int) \
        else (paddings, paddings)

    def f(v):
        n, ckk, L = v.shape
        c = ckk // (kh * kw)
        hp, wp = oh + 2 * p[0], ow + 2 * p[1]
        nh = (hp - (dh * (kh - 1) + 1)) // sh + 1
        nw = (wp - (dw * (kw - 1) + 1)) // sw + 1
        cols = v.reshape(n, c, kh, kw, nh, nw)
        # destination row/col per (kernel tap, patch) pair
        ys = (jnp.arange(kh) * dh)[:, None, None, None] \
            + (jnp.arange(nh) * sh)[None, None, :, None]
        xs = (jnp.arange(kw) * dw)[None, :, None, None] \
            + (jnp.arange(nw) * sw)[None, None, None, :]
        flat_idx = (ys * wp + xs).reshape(-1)
        out = jnp.zeros((n, c, hp * wp), v.dtype)
        out = out.at[:, :, flat_idx].add(cols.reshape(n, c, -1))
        out = out.reshape(n, c, hp, wp)
        return out[:, :, p[0]:p[0] + oh, p[1]:p[1] + ow]
    return defop(f, name='fold')(x)


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """[N,2,3] affine matrices -> [N,H,W,2] sampling grid in [-1, 1]
    coords (paddle.nn.functional.affine_grid)."""
    def f(th):
        n, h, w = th.shape[0], int(out_shape[2]), int(out_shape[3])
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) + 0.5) * 2 / h - 1
            xs = (jnp.arange(w) + 0.5) * 2 / w - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing='ij')
        base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [H,W,3]
        return jnp.einsum('hwk,nok->nhwo', base, th.astype(jnp.float32))
    return defop(f, name='affine_grid')(theta)


def grid_sample(x, grid, mode='bilinear', padding_mode='zeros',
                align_corners=True, name=None):
    """Sample NCHW `x` at [N,H',W',2] normalized grid locations
    (paddle.nn.functional.grid_sample) — gather + fused bilinear
    arithmetic, the XLA-native replacement for the CUDA sampler.
    padding_mode zeros/border/reflection match upstream: zeros blends
    per-corner (a partially out-of-bounds bilinear sample still gets
    mass from its in-bounds corners)."""
    if padding_mode not in ('zeros', 'border', 'reflection'):
        raise ValueError(f'unsupported padding_mode {padding_mode!r}')

    def f(xv, gv):
        n, c, h, w = xv.shape
        gx, gy = gv[..., 0], gv[..., 1]
        if align_corners:
            fx = (gx + 1) * 0.5 * (w - 1)
            fy = (gy + 1) * 0.5 * (h - 1)
        else:
            fx = ((gx + 1) * w - 1) * 0.5
            fy = ((gy + 1) * h - 1) * 0.5

        def reflect(v, size):
            # reflect across cell borders onto [0, size-1]
            span = 2 * (size - 1) if align_corners else 2 * size
            if span == 0:
                return jnp.zeros_like(v)
            v = jnp.abs(v) if align_corners else jnp.abs(v + 0.5) - 0.5
            v = v % span
            return jnp.where(v > span / 2, span - v, v) \
                if align_corners else \
                jnp.clip(jnp.where(v > span / 2 - 0.5, span - 1 - v, v),
                         0, size - 1)

        if padding_mode == 'border':
            fx = jnp.clip(fx, 0, w - 1)
            fy = jnp.clip(fy, 0, h - 1)
        elif padding_mode == 'reflection':
            fx = jnp.clip(reflect(fx, w), 0, w - 1)
            fy = jnp.clip(reflect(fy, h), 0, h - 1)

        def inb(yy, xx):
            return ((yy >= 0) & (yy <= h - 1)
                    & (xx >= 0) & (xx <= w - 1))

        if mode == 'nearest':
            xi = jnp.round(fx)
            yi = jnp.round(fy)
            out = jax.vmap(lambda img, yy, xx: img[:, yy, xx])(
                xv, jnp.clip(yi, 0, h - 1).astype(jnp.int32),
                jnp.clip(xi, 0, w - 1).astype(jnp.int32))
            if padding_mode == 'zeros':
                out = jnp.where(inb(yi, xi)[:, None], out, 0.0)
            return out.astype(xv.dtype)

        x0 = jnp.floor(fx)
        y0 = jnp.floor(fy)
        wx = (fx - x0)[:, None]
        wy = (fy - y0)[:, None]

        def gather(img, yy, xx):
            yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
            xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
            return img[:, yc, xc]

        def corners(img, yy0, xx0):
            return (gather(img, yy0, xx0), gather(img, yy0, xx0 + 1),
                    gather(img, yy0 + 1, xx0),
                    gather(img, yy0 + 1, xx0 + 1))

        v00, v01, v10, v11 = jax.vmap(corners)(xv, y0, x0)
        if padding_mode == 'zeros':
            # per-corner zeroing: out-of-bounds corners contribute 0,
            # in-bounds corners keep their bilinear mass (upstream)
            v00 = v00 * inb(y0, x0)[:, None]
            v01 = v01 * inb(y0, x0 + 1)[:, None]
            v10 = v10 * inb(y0 + 1, x0)[:, None]
            v11 = v11 * inb(y0 + 1, x0 + 1)[:, None]
        out = (v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx
               + v10 * wy * (1 - wx) + v11 * wy * wx)
        return out.astype(xv.dtype)
    return defop(f, name='grid_sample')(x, grid)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format='NCHW',
                   name=None):
    """TSM temporal shift: shift 1/ratio of channels one step along the
    segment axis ([N*T, C, H, W] with T=seg_num; NHWC supported via
    transpose)."""
    if data_format not in ('NCHW', 'NHWC'):
        raise ValueError(f'unsupported data_format {data_format!r}')

    def f(v):
        if data_format == 'NHWC':
            v = jnp.transpose(v, (0, 3, 1, 2))
        nt, c, h, w = v.shape
        n = nt // seg_num
        v = v.reshape(n, seg_num, c, h, w)
        fold_c = int(c * shift_ratio)
        left = jnp.concatenate(
            [v[:, 1:, :fold_c], jnp.zeros_like(v[:, :1, :fold_c])], axis=1)
        right = jnp.concatenate(
            [jnp.zeros_like(v[:, :1, fold_c:2 * fold_c]),
             v[:, :-1, fold_c:2 * fold_c]], axis=1)
        out = jnp.concatenate([left, right, v[:, :, 2 * fold_c:]], axis=2)
        out = out.reshape(nt, c, h, w)
        if data_format == 'NHWC':
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out
    return defop(f, name='temporal_shift')(x)


def pixel_shuffle(x, upscale_factor, data_format='NCHW', name=None):
    r = int(upscale_factor)

    def f(v):
        n, c, h, w = v.shape
        v = v.reshape(n, c // (r * r), r, r, h, w)
        v = v.transpose(0, 1, 4, 2, 5, 3)
        return v.reshape(n, c // (r * r), h * r, w * r)
    return defop(f, name='pixel_shuffle')(x)


def pixel_unshuffle(x, downscale_factor, data_format='NCHW', name=None):
    r = int(downscale_factor)

    def f(v):
        n, c, h, w = v.shape
        v = v.reshape(n, c, h // r, r, w // r, r)
        v = v.transpose(0, 1, 3, 5, 2, 4)
        return v.reshape(n, c * r * r, h // r, w // r)
    return defop(f, name='pixel_unshuffle')(x)


def interpolate(x, size=None, scale_factor=None, mode='nearest',
                align_corners=False, align_mode=0, data_format='NCHW',
                name=None):
    def f(v):
        spatial_in = v.shape[2:]
        if size is not None:
            out_sz = _tuplize(size, len(spatial_in))
        else:
            sf = scale_factor
            if isinstance(sf, (int, float)):
                sf = [sf] * len(spatial_in)
            out_sz = tuple(int(s * f_) for s, f_ in zip(spatial_in, sf))
        if mode == 'nearest':
            return jax.image.resize(v, v.shape[:2] + out_sz, method='nearest')
        if mode in ('bilinear', 'linear', 'trilinear', 'bicubic'):
            if not align_corners:
                meth = 'cubic' if mode == 'bicubic' else 'linear'
                return jax.image.resize(v, v.shape[:2] + out_sz, method=meth)
            # align_corners=True: explicit gather-based linear interp
            out = v
            for d, o in enumerate(out_sz):
                axis = 2 + d
                in_d = out.shape[axis]
                if o == 1 or in_d == 1:
                    idx = jnp.zeros((o,), jnp.float32)
                else:
                    idx = jnp.arange(o) * ((in_d - 1) / (o - 1))
                lo = jnp.floor(idx).astype(jnp.int32)
                hi = jnp.minimum(lo + 1, in_d - 1)
                w_hi = (idx - lo).astype(v.dtype)
                a = jnp.take(out, lo, axis=axis)
                b_ = jnp.take(out, hi, axis=axis)
                shape = [1] * out.ndim
                shape[axis] = o
                w_hi = w_hi.reshape(shape)
                out = a * (1 - w_hi) + b_ * w_hi
            return out
        raise ValueError(f'unsupported interpolate mode {mode!r}')
    return defop(f, name='interpolate')(x)


def upsample(x, size=None, scale_factor=None, mode='nearest',
             align_corners=False, align_mode=0, data_format='NCHW', name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def _reduce(v, reduction):
    if reduction == 'mean':
        return jnp.mean(v)
    if reduction == 'sum':
        return jnp.sum(v)
    return v


def _fused_softmax_ce(logits2d, safe_labels, valid):
    """Per-row softmax CE that never materializes fp32 logits or log-probs:
    forward saves only (low-precision logits, fp32 lse); backward is a
    single fused elementwise pass (softmax minus iota-one-hot). This is
    what makes large-vocab LM training fit in HBM (a [B*S, V] fp32 copy
    at GPT vocab sizes is ~2GB per buffer).

    On TPU with a wide vocab the pallas online-softmax kernel takes over:
    its forward reads the logits from HBM once (XLA's lowering reads
    twice — max pass then exp-sum pass), which matters exactly when the
    [B*S, V] logits dominate HBM traffic."""
    from ..ops import pallas as _pallas
    if (_pallas.pallas_ce_enabled() and logits2d.shape[-1] >= 8192
            and logits2d.shape[-1] % 128 == 0):
        # the shape conditions above are the whole selection: a kernel
        # error on this side propagates (ops/pallas.py says why)
        from jax.sharding import PartitionSpec as P
        from ..ops import pallas_kernels as _pk

        def specs(mesh):
            # rows are batch-major, so the dp batch split is a row split;
            # the vocab dim stays whole (one lse per row)
            rows = _pallas.mesh_axis(mesh, 'dp', logits2d.shape[0])
            return (P(rows, None), P(rows)), P(rows)

        per = _pallas.on_mesh(_pk.softmax_cross_entropy,
                              (logits2d, safe_labels), specs)
        return jnp.where(valid, per, 0.0)
    return _fused_softmax_ce_xla(logits2d, safe_labels, valid)


# labels/valid are explicit non-differentiated args and ride the
# RESIDUALS, never a closure: a closure would capture trace-local
# tracers, which breaks any caller that jits the vjp-forward and
# invokes the pullback outside the trace (the eager dispatch cache's
# reusable-VJP split does exactly that). Module-level so the
# custom_vjp object is created ONCE — a per-call `@jax.custom_vjp`
# inside the wrapper gave every call a fresh fn identity, defeating
# identity-keyed tracing caches.
@jax.custom_vjp
def _ce_xla(x, safe_labels, valid):
    return _ce_xla_fwd(x, safe_labels, valid)[0]


def _ce_xla_fwd(x, safe_labels, valid):
    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(xf - m[:, None]), axis=-1))
    tgt = jnp.take_along_axis(xf, safe_labels[:, None], 1)[:, 0]
    return jnp.where(valid, lse - tgt, 0.0), (x, lse, safe_labels, valid)


def _ce_xla_bwd(res, g):
    x, lse, labels_r, valid_r = res
    xf = x.astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    p = jnp.exp(xf - lse[:, None])
    onehot = (cols == labels_r[:, None]).astype(jnp.float32)
    dx = (p - onehot) * jnp.where(valid_r, g, 0.0)[:, None]
    return (dx.astype(x.dtype), None, None)


_ce_xla.defvjp(_ce_xla_fwd, _ce_xla_bwd)


def _fused_softmax_ce_xla(logits2d, safe_labels, valid):
    """The XLA custom_vjp arm of _fused_softmax_ce (importable on its
    own so the bench races the pallas kernel against the ACTUAL
    fallback implementation, not a strawman)."""
    return _ce_xla(logits2d, safe_labels, valid)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction='mean', soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    def f(logits, lab, *w):
        # fused memory-light path for the common LM-loss shape
        if (use_softmax and not soft_label and not w and not label_smoothing
                and axis in (-1, logits.ndim - 1) and logits.ndim == 2
                and not jnp.issubdtype(jnp.asarray(lab).dtype, jnp.floating)):
            if lab.ndim == logits.ndim:   # trailing [N, 1] label layout
                lab = jnp.squeeze(lab, axis=-1)
            lab_i = lab.astype(jnp.int32)
            valid = lab_i != ignore_index
            per = _fused_softmax_ce(logits, jnp.where(valid, lab_i, 0),
                                    valid)
            if reduction == 'mean':
                denom = jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0)
                return jnp.sum(per) / denom
            return _reduce(per, reduction)
        logp = jax.nn.log_softmax(logits, axis=axis) if use_softmax \
            else jnp.log(jnp.maximum(logits, 1e-30))
        nclass = logits.shape[axis]
        if soft_label:
            soft = lab
            if label_smoothing:
                soft = soft * (1 - label_smoothing) + label_smoothing / nclass
            per = -jnp.sum(soft * logp, axis=axis)
            return _reduce(per, reduction)
        lab_i = lab.astype(jnp.int32)
        if lab_i.ndim == logp.ndim:  # trailing [..., 1] label layout
            lab_i = jnp.squeeze(lab_i, axis=axis)
        valid = lab_i != ignore_index
        safe = jnp.where(valid, lab_i, 0)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(safe, axis), axis=axis)
        per = -jnp.squeeze(picked, axis)
        if label_smoothing:
            smooth = -jnp.mean(logp, axis=axis)
            per = (1 - label_smoothing) * per + label_smoothing * smooth
        if w:
            cw = jnp.take(w[0], safe)
            per = per * cw
            per = jnp.where(valid, per, 0.0)
            if reduction == 'mean':
                return jnp.sum(per) / jnp.maximum(
                    jnp.sum(jnp.where(valid, cw, 0.0)), 1e-12)
            return _reduce(per, reduction)
        per = jnp.where(valid, per, 0.0)
        if reduction == 'mean':
            denom = jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0)
            return jnp.sum(per) / denom
        return _reduce(per, reduction)
    args = (input, label) if weight is None else (input, label, weight)
    with jax.named_scope('loss'):
        return defop(f, name='cross_entropy')(*args)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False, name=None):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction='none', axis=axis)
    loss = loss.unsqueeze(axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction='mean',
             name=None):
    return _nll(input, label, weight, ignore_index, reduction)


def _nll(input, label, weight, ignore_index, reduction):
    def f(logp, lab, *w):
        lab_i = lab.astype(jnp.int32)
        valid = lab_i != ignore_index
        safe = jnp.where(valid, lab_i, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, 1), axis=1)
        per = -jnp.squeeze(picked, 1)
        if w:
            cw = jnp.take(w[0], safe)
            per = per * cw
            per = jnp.where(valid, per, 0.0)
            if reduction == 'mean':
                return jnp.sum(per) / jnp.maximum(
                    jnp.sum(jnp.where(valid, cw, 0.0)), 1e-12)
            return _reduce(per, reduction)
        per = jnp.where(valid, per, 0.0)
        if reduction == 'mean':
            denom = jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0)
            return jnp.sum(per) / denom
        return _reduce(per, reduction)
    args = (input, label) if weight is None else (input, label, weight)
    return defop(f, name='nll_loss')(*args)


def binary_cross_entropy(input, label, weight=None, reduction='mean',
                         name=None):
    def f(p, y, *w):
        eps = 1e-12
        per = -(y * jnp.log(jnp.maximum(p, eps))
                + (1 - y) * jnp.log(jnp.maximum(1 - p, eps)))
        if w:
            per = per * w[0]
        return _reduce(per, reduction)
    args = (input, label) if weight is None else (input, label, weight)
    return defop(f, name='binary_cross_entropy')(*args)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction='mean', pos_weight=None,
                                     name=None):
    def f(z, y, *extra):
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = extra[i]; i += 1
        if pos_weight is not None:
            pw = extra[i]
        # numerically stable: max(z,0) - z*y + log(1+exp(-|z|))
        base = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if pw is not None:
            logsig = -jax.nn.log_sigmoid(z)       # -log σ(z)
            logsig_neg = -jax.nn.log_sigmoid(-z)  # -log(1-σ(z))
            base = y * pw * logsig + (1 - y) * logsig_neg
        if w is not None:
            base = base * w
        return _reduce(base, reduction)
    args = [logit, label]
    if weight is not None:
        args.append(weight)
    if pos_weight is not None:
        args.append(pos_weight)
    return defop(f, name='bce_with_logits')(*args)


def mse_loss(input, label, reduction='mean', name=None):
    return defop(lambda a, b: _reduce(jnp.square(a - b), reduction),
                 name='mse_loss')(input, label)


def l1_loss(input, label, reduction='mean', name=None):
    return defop(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                 name='l1_loss')(input, label)


def smooth_l1_loss(input, label, reduction='mean', delta=1.0, name=None):
    def f(a, b):
        d = jnp.abs(a - b)
        per = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        # reference multiplies by delta (huber): loss = delta * huber_delta
        per = per * delta
        return _reduce(per, reduction)
    return defop(f, name='smooth_l1_loss')(input, label)


def kl_div(input, label, reduction='mean', log_target=False, name=None):
    def f(logp, q):
        tgt = jnp.exp(q) if log_target else q
        logt = q if log_target else jnp.log(jnp.maximum(q, 1e-12))
        per = tgt * (logt - logp)
        if reduction == 'batchmean':
            return jnp.sum(per) / logp.shape[0]
        return _reduce(per, reduction)
    return defop(f, name='kl_div')(input, label)


def margin_ranking_loss(input, other, label, margin=0.0, reduction='mean',
                        name=None):
    def f(a, b, y):
        per = jnp.maximum(0.0, -y * (a - b) + margin)
        return _reduce(per, reduction)
    return defop(f, name='margin_ranking_loss')(input, other, label)


def hinge_embedding_loss(input, label, margin=1.0, reduction='mean', name=None):
    def f(a, y):
        per = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(per, reduction)
    return defop(f, name='hinge_embedding_loss')(input, label)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction='sum', name=None):
    def f(z, y, *nrm):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        per = a_t * jnp.power(1 - p_t, gamma) * ce
        if nrm:
            per = per / nrm[0]
        return _reduce(per, reduction)
    args = (logit, label) if normalizer is None else (logit, label, normalizer)
    return defop(f, name='sigmoid_focal_loss')(*args)


def square_error_cost(input, label, name=None):
    return defop(lambda a, b: jnp.square(a - b), name='square_error_cost')(
        input, label)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Fused attention. Layout [batch, seq, heads, head_dim] (reference
    paddle.nn.functional.scaled_dot_product_attention). On TPU this lowers
    to the pallas flash-attention kernel; elsewhere to an XLA softmax chain.
    """
    from ..ops import pallas as _pallas
    drop_key = framework.next_rng_key() if (dropout_p and training) else None

    def f(q, k, v, *m):
        mask = m[0] if m else None
        return _pallas.flash_attention(
            q, k, v, mask=mask, causal=is_causal,
            dropout_p=dropout_p if training else 0.0, dropout_key=drop_key)
    args = (query, key, value) if attn_mask is None else (
        query, key, value, attn_mask)
    return defop(f, name='scaled_dot_product_attention')(*args)


# aliases the reference exposes
def alltoall(*a, **k):  # placed in distributed; import-compat shim
    from .. import distributed
    return distributed.alltoall(*a, **k)


def gather_tree(ids, parents, name=None):
    """Trace beam-search parent pointers back from the last step
    (upstream: paddle.nn.functional.gather_tree; [T, B, K] layout)."""
    def f(idv, par):
        t = idv.shape[0]

        def body(carry, xs):
            beams = carry  # [B, K] beam index selected at step t+1
            step_ids, step_par = xs
            toks = jnp.take_along_axis(step_ids, beams, axis=1)
            prev = jnp.take_along_axis(step_par, beams, axis=1)
            return prev, toks

        init = jnp.broadcast_to(jnp.arange(idv.shape[2])[None, :],
                                idv.shape[1:])
        _, toks = jax.lax.scan(body, init, (idv[::-1], par[::-1]))
        return toks[::-1]
    return defop(f, name='gather_tree')(ids, parents)


# ---------------------------------------------------------------------------
# round-4 wideners (upstream: python/paddle/nn/functional/{activation,common,
# loss,pooling,distance}.py)
# ---------------------------------------------------------------------------


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    return defop(lambda v: jnp.where(v > threshold, v, value),
                 name='thresholded_relu')(x)


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, name=None):
    """Randomized leaky relu: random negative slope in [lower, upper] during
    training, the mean slope at eval (upstream F.rrelu)."""
    if not training:
        mid = (lower + upper) / 2.0
        return defop(lambda v: jnp.where(v >= 0, v, v * mid), name='rrelu')(x)
    key = framework.next_rng_key()

    def f(v):
        a = jax.random.uniform(key, v.shape, jnp.float32, lower, upper)
        return jnp.where(v >= 0, v, v * a.astype(v.dtype))
    return defop(f, name='rrelu')(x)


def maxout(x, groups, axis=1, name=None):
    """Max over `groups` consecutive channels (upstream F.maxout)."""
    def f(v):
        ax = int(axis) % v.ndim
        c = v.shape[ax]
        shape = (v.shape[:ax] + (c // groups, groups) + v.shape[ax + 1:])
        return jnp.max(v.reshape(shape), axis=ax + 1)
    return defop(f, name='maxout')(x)


def alpha_dropout(x, p=0.5, training=True, name=None):
    """SELU-preserving dropout (upstream F.alpha_dropout): dropped units are
    set to alpha', then the output is affinely rescaled to keep mean/var."""
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(to_jax(x))
    if p == 1.0:
        return defop(lambda v: jnp.zeros_like(v), name='alpha_dropout')(x)
    key = framework.next_rng_key()
    alpha = 1.6732632423543772 * 1.0507009873554805  # selu alpha * scale

    def f(v):
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        a = jnp.asarray(-alpha, v.dtype)
        scale = (1.0 - p + p * alpha ** 2 * (1.0 - p)) ** -0.5
        bias = -scale * p * (-alpha)
        out = jnp.where(keep, v, a)
        return out * scale + bias
    return defop(f, name='alpha_dropout')(x)


def channel_shuffle(x, groups, data_format='NCHW', name=None):
    def f(v):
        if data_format == 'NCHW':
            n, c, h, w = v.shape
            return v.reshape(n, groups, c // groups, h, w) \
                .swapaxes(1, 2).reshape(n, c, h, w)
        n, h, w, c = v.shape
        return v.reshape(n, h, w, groups, c // groups) \
            .swapaxes(3, 4).reshape(n, h, w, c)
    return defop(f, name='channel_shuffle')(x)


def zeropad2d(x, padding, data_format='NCHW', name=None):
    p = _tuplize(padding, 4)  # [left, right, top, bottom]

    def f(v):
        if data_format == 'NCHW':
            cfg = [(0, 0), (0, 0), (p[2], p[3]), (p[0], p[1])]
        else:
            cfg = [(0, 0), (p[2], p[3]), (p[0], p[1]), (0, 0)]
        return jnp.pad(v, cfg)
    return defop(f, name='zeropad2d')(x)


def max_pool2d_with_index(x, kernel_size, stride=None, padding=0,
                          ceil_mode=False, name=None):
    """(out, flat-indices-into-H*W) pair — the mask max_unpool2d consumes
    (upstream returns this from max_pool2d(return_mask=True))."""
    k = _tuplize(kernel_size, 2)
    s = _tuplize(stride if stride is not None else kernel_size, 2)
    p = _conv_padding(padding, 2, s, (1, 1), k)

    def f(v):
        n, c, h, w = v.shape
        extra = _ceil_mode_extra((h, w), k, s, list(p)) if ceil_mode \
            else (0, 0)
        vp = jnp.pad(v, [(0, 0), (0, 0),
                         (p[0][0], p[0][1] + extra[0]),
                         (p[1][0], p[1][1] + extra[1])],
                     constant_values=-jnp.inf)
        hp, wp = vp.shape[-2:]
        ho = (hp - k[0]) // s[0] + 1
        wo = (wp - k[1]) // s[1] + 1
        # window gather: [N, C, Ho, Wo, kh*kw]
        oy = (jnp.arange(ho) * s[0])[:, None, None, None]
        ox = (jnp.arange(wo) * s[1])[None, :, None, None]
        dy = jnp.arange(k[0])[None, None, :, None]
        dx = jnp.arange(k[1])[None, None, None, :]
        yy, xx = jnp.broadcast_arrays(oy + dy, ox + dx)  # [Ho, Wo, kh, kw]
        patches = vp[:, :, yy, xx].reshape(n, c, ho, wo, -1)
        out = jnp.max(patches, axis=-1)
        arg = jnp.argmax(patches, axis=-1)  # in-window index
        # back to unpadded flat H*W coordinates
        win_y = yy.reshape(ho, wo, -1) - p[0][0]
        win_x = xx.reshape(ho, wo, -1) - p[1][0]
        flat = win_y * w + win_x  # [Ho, Wo, kh*kw]
        idx = jnp.take_along_axis(
            jnp.broadcast_to(flat, (n, c) + flat.shape),
            arg[..., None], axis=-1)[..., 0]
        return out, idx.astype(jnp.int32)
    return defop(f, name='max_pool2d_with_index')(x)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 output_size=None, data_format='NCHW', name=None):
    """Scatter pooled values back to their argmax positions (upstream
    F.max_unpool2d; `indices` are flat H*W positions of the input that
    was pooled)."""
    if data_format != 'NCHW':
        raise NotImplementedError('max_unpool2d supports NCHW')
    k = _tuplize(kernel_size, 2)
    s = _tuplize(stride if stride is not None else kernel_size, 2)
    p = _tuplize(padding, 2)

    def f(v, idx):
        n, c, ho, wo = v.shape
        if output_size is not None:
            out_h, out_w = [int(o) for o in output_size[-2:]]
        else:
            out_h = (ho - 1) * s[0] - 2 * p[0] + k[0]
            out_w = (wo - 1) * s[1] - 2 * p[1] + k[1]
        flat = jnp.zeros((n, c, out_h * out_w), v.dtype)
        flat = flat.at[
            jnp.arange(n)[:, None, None],
            jnp.arange(c)[None, :, None],
            idx.reshape(n, c, -1)].set(v.reshape(n, c, -1))
        return flat.reshape(n, c, out_h, out_w)
    return defop(f, name='max_unpool2d')(x, indices)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    def f(a, b):
        d = a - b + epsilon
        return jnp.linalg.norm(d, ord=p, axis=-1, keepdims=keepdim)
    return defop(f, name='pairwise_distance')(x, y)


def pdist(x, p=2.0, name=None):
    """Condensed pairwise distances of rows -> [N*(N-1)/2] (upstream
    paddle.pdist / F.pdist)."""
    def f(v):
        n = v.shape[0]
        iu, ju = jnp.triu_indices(n, k=1)
        diff = v[iu] - v[ju]
        if p == 2.0:
            return jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, -1), 0.0))
        if p == float('inf'):
            return jnp.max(jnp.abs(diff), -1)
        return jnp.power(jnp.sum(jnp.power(jnp.abs(diff), p), -1), 1.0 / p)
    return defop(f, name='pdist')(x)


# -- losses ------------------------------------------------------------------

def soft_margin_loss(input, label, reduction='mean', name=None):
    def f(x, y):
        return _reduce(jnp.log1p(jnp.exp(-y * x)), reduction)
    return defop(f, name='soft_margin_loss')(input, label)


def multi_label_soft_margin_loss(input, label, weight=None, reduction='mean',
                                 name=None):
    def f(x, y, *w):
        loss = -(y * jax.nn.log_sigmoid(x)
                 + (1 - y) * jax.nn.log_sigmoid(-x))
        if w:
            loss = loss * w[0]
        return _reduce(jnp.mean(loss, axis=-1), reduction)
    args = (input, label) if weight is None else (input, label, weight)
    return defop(f, name='multi_label_soft_margin_loss')(*args)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction='mean',
                        name=None):
    def f(a, pos, neg):
        def dist(u, v):
            return jnp.linalg.norm(u - v + epsilon, ord=p, axis=-1)
        d_pos = dist(a, pos)
        d_neg = dist(a, neg)
        if swap:
            d_neg = jnp.minimum(d_neg, dist(pos, neg))
        return _reduce(jnp.maximum(d_pos - d_neg + margin, 0.0), reduction)
    return defop(f, name='triplet_margin_loss')(input, positive, negative)


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction='mean',
                                      name=None):
    if distance_function is None:
        return triplet_margin_loss(input, positive, negative, margin=margin,
                                   swap=swap, reduction=reduction)
    d_pos = distance_function(input, positive)
    d_neg = distance_function(input, negative)
    if swap:
        d_swap = distance_function(positive, negative)
        d_neg = minimum_t(d_neg, d_swap)
    return defop(lambda dp, dn: _reduce(jnp.maximum(dp - dn + margin, 0.0),
                                        reduction),
                 name='triplet_margin_with_distance_loss')(d_pos, d_neg)


def minimum_t(a, b):
    return defop(lambda x, y: jnp.minimum(x, y), name='minimum')(a, b)


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction='mean', name=None):
    def f(mu, y, var):
        var = jnp.maximum(var, epsilon)
        loss = 0.5 * (jnp.log(var) + (y - mu) ** 2 / var)
        if full:
            loss = loss + 0.5 * _math.log(2 * _math.pi)
        return _reduce(loss, reduction)
    return defop(f, name='gaussian_nll_loss')(input, label, variance)


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction='mean', name=None):
    def f(x, y):
        if log_input:
            loss = jnp.exp(x) - y * x
        else:
            loss = x - y * jnp.log(x + epsilon)
        if full:
            # Stirling approximation for y! when y > 1
            stirling = y * jnp.log(y) - y + 0.5 * jnp.log(2 * _math.pi * y)
            loss = loss + jnp.where(y > 1, stirling, 0.0)
        return _reduce(loss, reduction)
    return defop(f, name='poisson_nll_loss')(input, label)


def dice_loss(input, label, epsilon=1e-5, name=None):
    """1 - dice coefficient over one-hot labels (upstream F.dice_loss:
    input [N, ..., C] probabilities, label [N, ..., 1] int)."""
    def f(x, y):
        num_classes = x.shape[-1]
        oh = jax.nn.one_hot(y[..., 0], num_classes, dtype=x.dtype)
        red = tuple(range(1, x.ndim))
        inter = jnp.sum(x * oh, axis=red)
        denom = jnp.sum(x, axis=red) + jnp.sum(oh, axis=red)
        return jnp.mean(1.0 - 2.0 * inter / (denom + epsilon))
    return defop(f, name='dice_loss')(input, label)


def npair_loss(anchor, positive, labels, l2_reg=0.002, name=None):
    """N-pair loss (upstream F.npair_loss): softmax CE over the
    anchor-positive similarity matrix + L2 on the embeddings."""
    def f(a, pos, y):
        reg = jnp.mean(jnp.sum(a * a, -1)) + jnp.mean(jnp.sum(pos * pos, -1))
        reg = reg * 0.25 * l2_reg
        sim = a @ pos.T  # [N, N]
        same = (y[:, None] == y[None, :]).astype(a.dtype)
        tgt = same / jnp.sum(same, axis=1, keepdims=True)
        ce = jnp.mean(jnp.sum(
            -tgt * jax.nn.log_softmax(sim, axis=1), axis=1))
        return ce + reg
    return defop(f, name='npair_loss')(anchor, positive, labels)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction='mean', norm_by_times=False, name=None):
    """CTC loss (upstream F.ctc_loss / warpctc).

    log_probs: [T, B, C] logits (softmax applied internally, matching
    warpctc); labels: [B, L] padded with anything past label_lengths.
    TPU-native: the alpha recursion over 2L+1 states is a `lax.scan` in
    log space — each step is a vectorized [B, S] update, no per-sample
    host loop.
    """
    def f(logits, lab, in_len, lab_len):
        T, B, C = logits.shape
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        L = lab.shape[1]
        S = 2 * L + 1
        neg_inf = jnp.asarray(-1e30, jnp.float32)
        # extended label sequence: blank, l1, blank, l2, ... blank
        ext = jnp.full((B, S), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
        # allowed skip: ext[s] != ext[s-2] (and s odd — label positions)
        skip_ok = jnp.concatenate(
            [jnp.zeros((B, 2), bool), ext[:, 2:] != ext[:, :-2]], axis=1)
        pos = jnp.arange(S)[None, :]
        valid_state = pos < (2 * lab_len[:, None] + 1)

        emit0 = jnp.take_along_axis(lp[0], ext, axis=1)  # [B, S]
        alpha0 = jnp.where(pos < 2, emit0, neg_inf)
        alpha0 = jnp.where(valid_state, alpha0, neg_inf)

        def step(alpha, t):
            prev1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            prev2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            prev2 = jnp.where(skip_ok, prev2, neg_inf)
            tot = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2)
            emit = jnp.take_along_axis(lp[t], ext, axis=1)
            new = tot + emit
            new = jnp.where(valid_state, new, neg_inf)
            # frames past a sample's input length leave alpha frozen
            active = (t < in_len)[:, None]
            return jnp.where(active, new, alpha), None

        alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
        # final: logaddexp of the last two valid states
        last = 2 * lab_len[:, None]  # blank after final label
        a_last = jnp.take_along_axis(alpha, last, axis=1)[:, 0]
        a_prev = jnp.take_along_axis(
            alpha, jnp.maximum(last - 1, 0), axis=1)[:, 0]
        a_prev = jnp.where(lab_len > 0, a_prev, neg_inf)
        nll = -jnp.logaddexp(a_last, a_prev)
        if norm_by_times:
            nll = nll / in_len.astype(nll.dtype)
        if reduction == 'mean':
            # upstream mean: per-sample loss / label_length, then batch mean
            return jnp.mean(nll / jnp.maximum(lab_len, 1).astype(nll.dtype))
        return _reduce(nll, reduction)
    return defop(f, name='ctc_loss')(log_probs, labels, input_lengths,
                                     label_lengths)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction='mean', name=None):
    """1 − cos(x1,x2) for label=1, max(0, cos − margin) for label=−1
    (reference paddle.nn.functional.cosine_embedding_loss)."""
    def f(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1),
            1e-12)
        loss = jnp.where(y > 0, 1.0 - cos,
                         jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)
    return defop(f, name='cosine_embedding_loss')(input1, input2, label)


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction='mean', name=None):
    """Multi-class margin loss mean_j max(0, margin − x_y + x_j)^p
    (reference paddle.nn.functional.multi_margin_loss)."""
    def f(x, y, *w):
        n, c = x.shape
        y = y.astype(jnp.int32)
        xy = jnp.take_along_axis(x, y[:, None], axis=1)
        m = jnp.maximum(0.0, margin - xy + x) ** p
        if w:
            m = m * jnp.take(w[0], y)[:, None]
        # the true-class column contributes margin^p — mask it out
        cols = jnp.arange(c)[None, :]
        m = jnp.where(cols == y[:, None], 0.0, m)
        return _reduce(jnp.sum(m, axis=1) / c, reduction)
    args = (input, label) if weight is None else (input, label, weight)
    return defop(f, name='multi_margin_loss')(*args)


def log_loss(input, label, epsilon=1e-4, name=None):
    """Elementwise negative log likelihood of probabilities (reference
    paddle.nn.functional.log_loss; no reduction, matching upstream)."""
    def f(x, y):
        return -(y * jnp.log(x + epsilon)
                 + (1.0 - y) * jnp.log1p(-x + epsilon))
    return defop(f, name='log_loss')(input, label)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference
    paddle.nn.functional.hsigmoid_loss): classify by walking a binary
    tree of `num_classes - 1` internal nodes, paying a binary logistic
    loss at each step. Default tree is the complete binary tree in heap
    layout (root 0, children 2i+1/2i+2, leaf c at node c + C - 1); a
    custom Huffman-style tree comes in via path_table/path_code
    ([N, L], -1-padded). The walk is a fixed log2(C)-step masked loop —
    no data-dependent shapes, so it jits."""
    def f(x, lab, w, *rest):
        i = 0
        b = rest[i] if bias is not None else None
        if bias is not None:
            i += 1
        if path_table is not None:
            pt = rest[i].astype(jnp.int32)
            pc = rest[i + 1].astype(jnp.float32)
            valid = (pt >= 0)
            nodes = jnp.maximum(pt, 0)
            codes = pc
        else:
            C = int(num_classes)
            depth = max(1, int(np.ceil(np.log2(max(C, 2)))) + 1)
            node = lab.astype(jnp.int32) + (C - 1)  # leaf id (heap)
            nodes_l, codes_l, valid_l = [], [], []
            for _ in range(depth):
                parent = (node - 1) // 2
                is_right = (node == 2 * parent + 2)
                alive = node > 0
                nodes_l.append(jnp.where(alive, parent, 0))
                codes_l.append(is_right.astype(jnp.float32))
                valid_l.append(alive)
                node = jnp.where(alive, parent, 0)
            nodes = jnp.stack(nodes_l, axis=-1)   # [N, D] internal ids
            codes = jnp.stack(codes_l, axis=-1)   # [N, D] 0/1
            valid = jnp.stack(valid_l, axis=-1)
        wn = w[nodes]                              # [N, D, F]
        z = jnp.einsum('nf,ndf->nd', x.astype(jnp.float32),
                       wn.astype(jnp.float32))
        if b is not None:
            z = z + b[nodes].astype(jnp.float32)
        # BCE-with-logits at each step, target = code
        step_loss = jax.nn.softplus(z) - codes * z
        per = jnp.sum(jnp.where(valid, step_loss, 0.0), axis=-1)
        return per[:, None]  # upstream returns per-sample [N, 1]
    args = [input, label, weight]
    if bias is not None:
        args.append(bias)
    if path_table is not None:
        args += [path_table, path_code]
    return defop(f, name='hsigmoid_loss')(*args)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction='mean',
                         name=None):
    """Combined-margin softmax CE over cosine logits (reference
    paddle.nn.functional.margin_cross_entropy; ArcFace family): the
    target-class logit cosθ becomes cos(m1·θ + m2) − m3 before scaling.
    m1/m2/m3 = (1, 0.5, 0) is ArcFace, (1, 0, 0.35) CosFace."""
    if group is not None:
        raise NotImplementedError(
            'class-sharded margin_cross_entropy: shard the classifier '
            'with distributed.ParallelCrossEntropy/ColumnParallelLinear '
            'over the mesh instead of a process group')

    def f(x, y):
        y = y.astype(jnp.int32)
        # arccos only the gathered target column; eps-clip keeps the
        # boundary gradient finite (d/dx arccos -> -inf at |x|=1)
        eps = 1e-6
        tcos = jnp.take_along_axis(x, y[:, None], axis=1)[:, 0]
        theta = jnp.arccos(jnp.clip(tcos, -1.0 + eps, 1.0 - eps))
        mod = jnp.cos(margin1 * theta + margin2) - margin3
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        adjusted = jnp.where(cols == y[:, None], mod[:, None], x)
        z = adjusted * scale
        lse = jax.scipy.special.logsumexp(z, axis=1)
        per = lse - mod * scale
        loss = _reduce(per, reduction)
        if return_softmax:
            return loss, jnp.exp(z - lse[:, None])
        return loss
    return defop(f, name='margin_cross_entropy')(logits, label)


def adaptive_log_softmax_with_loss(input, label, head_weight, tail_weights,
                                   cutoffs, head_bias=None, name=None):
    """Adaptive softmax (reference
    paddle.nn.functional.adaptive_log_softmax_with_loss; Grave et al.
    2017): frequent classes live in the head, rare classes in projected
    tail clusters. Returns (per-sample log-prob output, mean nll loss),
    matching upstream's (output, loss) pair."""
    n_clusters = len(cutoffs)  # cutoffs excludes the final vocab size

    def f(x, y, hw, *rest):
        i = 0
        hb = None
        if head_bias is not None:
            hb = rest[i]; i += 1
        tails = []
        while i < len(rest):
            tails.append((rest[i], rest[i + 1]))
            i += 2
        y = y.astype(jnp.int32)
        head = x @ hw  # [N, cutoffs[0] + n_clusters]
        if hb is not None:
            head = head + hb
        head_lp = jax.nn.log_softmax(head, axis=-1)
        # head classes: direct log-prob; tail c: cluster-prob + within
        out = jnp.where(y < cutoffs[0],
                        jnp.take_along_axis(
                            head_lp, jnp.minimum(y, cutoffs[0] - 1)[:, None],
                            axis=1)[:, 0],
                        0.0)
        lows = [0] + list(cutoffs)
        for c, (w1, w2) in enumerate(tails):
            lo, hi = lows[c + 1], lows[c + 2] if c + 2 < len(lows) else None
            in_c = (y >= lo) & ((y < hi) if hi is not None else True)
            rel = jnp.clip(y - lo, 0, w2.shape[1] - 1)
            tl = jax.nn.log_softmax((x @ w1) @ w2, axis=-1)
            cluster_lp = head_lp[:, cutoffs[0] + c]
            within = jnp.take_along_axis(tl, rel[:, None], axis=1)[:, 0]
            out = jnp.where(in_c, cluster_lp + within, out)
        return out, -jnp.mean(out)
    args = [input, label, head_weight]
    if head_bias is not None:
        args.append(head_bias)
    for w1, w2 in tail_weights:
        args += [w1, w2]
    return defop(f, name='adaptive_log_softmax_with_loss')(*args)
