"""Neural-network ops — parity with ``src/operator/nn/`` (SURVEY.md §2.2).

Design notes vs the reference:

* Convolution/Pooling lower to ``lax.conv_general_dilated`` / ``lax.reduce_window`` —
  XLA tiles these onto the MXU/VPU directly; there is no im2col, no cuDNN algo
  selection, no autotune cache (that whole subsystem disappears, SURVEY.md §2.7).
* Layout is NCHW by default for API parity with the reference. XLA's layout assignment
  re-tiles internally, so NCHW at the API boundary costs nothing after compilation.
* Loss-fused heads (``SoftmaxOutput``, ``make_loss``) carry the reference's *custom
  backward* semantics via ``jax.custom_vjp`` — their gradient is NOT the vjp of their
  forward (softmax output's grad is ``p - onehot(label)``, src/operator/softmax_output-inl.h).
* Stochastic ops (Dropout) draw keys from ``mxtpu.rng`` (trace-aware, see rng.py).
"""

from __future__ import annotations

import math

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import rng
from .registry import register, alias

# ---------------------------------------------------------------------------
# dense / conv / pooling
# ---------------------------------------------------------------------------

# Low-precision execution hooks (mxtpu.quant.train.quant_scope): when set,
# these replace the fp32 matmul/conv contraction — bias add, flattening and
# layout handling stay here so the quant layer only sees the contraction.
_QUANT_DENSE = None   # (x, weight) -> x @ weight.T in the active quant mode
_QUANT_CONV = None    # (data, weight, **conv_kw) -> conv in the active mode


@register("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, bias=None, num_hidden: int = 0,
                     no_bias: bool = False, flatten: bool = True):
    """src/operator/nn/fully_connected.cc:231: y = x·Wᵀ + b (weight stored [out,in])."""
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    y = _QUANT_DENSE(x, weight) if _QUANT_DENSE is not None \
        else jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias
    return y


_CONV_LAYOUTS = {
    1: ("NCW", "OIW", "NCW"),
    2: ("NCHW", "OIHW", "NCHW"),
    3: ("NCDHW", "OIDHW", "NCDHW"),
}


def _tup(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(int(x) for x in v)
    return t if t else (1,) * n


@register("Convolution", aliases=("convolution",))
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                 num_filter: int = 0, num_group: int = 1, no_bias: bool = False,
                 layout: Optional[str] = None):
    """src/operator/nn/convolution.cc — N-D conv with groups/dilation/stride/pad.

    Direct ``lax.conv_general_dilated``; grouped conv via ``feature_group_count``
    (depthwise = num_group == in_channels), which XLA maps to MXU batch tiles without
    the reference's separate depthwise kernel (depthwise_convolution-inl.h).
    """
    n = len(kernel) if kernel else data.ndim - 2
    stride, dilate = _tup(stride, n), _tup(dilate, n)
    pad = _tup(pad, n) if pad else (0,) * n
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_LAYOUTS[n])
    conv_kw = dict(window_strides=stride, padding=[(p, p) for p in pad],
                   rhs_dilation=dilate, dimension_numbers=dn,
                   feature_group_count=num_group)
    out = _QUANT_CONV(data, weight, **conv_kw) if _QUANT_CONV is not None \
        else lax.conv_general_dilated(data, weight, **conv_kw)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


@register("Deconvolution", aliases=("deconvolution",))
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                   adj=(), target_shape=(), num_filter: int = 0, num_group: int = 1,
                   no_bias: bool = True, layout: Optional[str] = None):
    """src/operator/nn/deconvolution.cc — transposed conv (gradient of Convolution).

    Implemented as ``lax.conv_transpose``-equivalent via input dilation so the same MXU
    path serves forward and transposed convs. Weight layout matches the reference:
    [in, out/group, *kernel].
    """
    n = len(kernel) if kernel else data.ndim - 2
    stride, dilate = _tup(stride, n), _tup(dilate, n)
    pad = _tup(pad, n) if pad else (0,) * n
    adj = _tup(adj, n) if adj else (0,) * n
    k = tuple(weight.shape[2:])
    # conv_transpose padding: for each dim, (k-1)*d - p on both sides, + adj on high side
    pads = [((k[i] - 1) * dilate[i] - pad[i], (k[i] - 1) * dilate[i] - pad[i] + adj[i])
            for i in range(n)]
    # weight [in, out/g, *k] → flip spatial, swap to [out, in/g, *k] per group
    w = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    if num_group > 1:
        ci, cog = w.shape[0], w.shape[1]
        w = w.reshape((num_group, ci // num_group, cog) + k)
        w = jnp.swapaxes(w, 1, 2).reshape((num_group * cog, ci // num_group) + k)
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _CONV_LAYOUTS[n])
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * n, padding=pads, lhs_dilation=stride,
        dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


@register("Pooling", aliases=("pooling",))
def _pooling(data, kernel=(), pool_type: str = "max", global_pool: bool = False,
             stride=(), pad=(), pooling_convention: str = "valid",
             p_value: int = 2, count_include_pad: bool = True):
    """src/operator/nn/pooling.cc — max/avg/sum/lp pooling via lax.reduce_window."""
    n = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            red = jnp.sum(data, axis=axes, keepdims=True)
            return red / jnp.prod(jnp.asarray(data.shape[2:])) if pool_type == "avg" else red
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value), axis=axes,
                                     keepdims=True), 1.0 / p_value)
    kernel = _tup(kernel, n)
    stride = _tup(stride, n)
    pad = _tup(pad, n) if pad else (0,) * n
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad high edge enough that the last window fits
        extra = []
        for i in range(n):
            size = data.shape[2 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra.append((stride[i] - rem) % stride[i] if size >= kernel[i] else 0)
        pads = [(0, 0), (0, 0)] + [(pad[i], pad[i] + extra[i]) for i in range(n)]
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            # static python product: a jnp op here would stage a tracer
            # under an outer jit, breaking float()
            return s / float(math.prod(kernel))
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.power(jnp.abs(data), p_value), 0.0, lax.add,
                              window, strides, pads)
        return jnp.power(s, 1.0 / p_value)
    raise ValueError(f"unknown pool_type {pool_type!r}")


@register("UpSampling", aliases=("upsampling",))
def _upsampling(data, scale: int = 1, sample_type: str = "nearest", num_args: int = 1):
    """src/operator/upsampling.cc nearest-neighbour path (bilinear via contrib resize)."""
    n, c, h, w = data.shape
    out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return out


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


@register("BatchNorm", aliases=("batch_norm",))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps: float = 1e-3,
                momentum: float = 0.9, fix_gamma: bool = True,
                use_global_stats: bool = False, axis: int = 1,
                cudnn_off: bool = False):
    """Inference-mode BatchNorm using running stats (src/operator/nn/batch_norm.cc).

    Training mode (batch stats + moving-stat update) is ``batch_norm_train`` — the
    functional split keeps this op pure; the Gluon layer owns the aux-state update,
    where the reference mutates aux arrays inside the op.
    """
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    mm, mv = moving_mean.reshape(shape), moving_var.reshape(shape)
    return (data - mm) * lax.rsqrt(mv + eps) * g.reshape(shape) + beta.reshape(shape)


@register("batch_norm_train", num_outputs=3)
def _batch_norm_train(data, gamma, beta, eps: float = 1e-3, fix_gamma: bool = True,
                      axis: int = 1):
    """Training-mode BN: returns (out, batch_mean, batch_var) for moving-stat update."""
    axes = tuple(i for i in range(data.ndim) if i != axis)
    mean = jnp.mean(data, axis=axes)
    var = jnp.var(data, axis=axes)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = (data - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + eps)
    out = out * g.reshape(shape) + beta.reshape(shape)
    return out, mean, var


@register("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis: int = -1, eps: float = 1e-5):
    """src/operator/nn/layer_norm.cc — normalize over one axis, affine per that axis."""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("rms_norm", namespace="contrib")
def rms_norm(x, gamma, eps: float = 1e-5):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, worked out
    in float32 and returned in ``x``'s type."""
    wide = x.astype(jnp.float32)
    wide = wide * lax.rsqrt(jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
                            + eps)
    return (wide * gamma.astype(jnp.float32)).astype(x.dtype)


@register("InstanceNorm", aliases=("instance_norm",))
def _instance_norm(data, gamma, beta, eps: float = 1e-3):
    """src/operator/instance_norm-inl.h — per-(sample,channel) normalization (NC+)."""
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN", aliases=("lrn",))
def _lrn(data, nsize: int = 5, alpha: float = 1e-4, beta: float = 0.75, knorm: float = 2.0):
    """src/operator/nn/lrn.cc — local response norm across channels (NCHW)."""
    sq = jnp.square(data)
    half = nsize // 2
    pad = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    windows = sum(pad[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + alpha * windows / nsize, beta)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": lambda x: x / (1 + jnp.abs(x)),
    "silu": jax.nn.silu,
}


@register("Activation", aliases=("activation",))
def _activation(data, act_type: str = "relu"):
    return _ACTS[act_type](data)


@register("LeakyReLU", aliases=("leaky_relu",))
def _leaky_relu(data, gamma=None, act_type: str = "leaky", slope: float = 0.25,
                lower_bound: float = 0.125, upper_bound: float = 0.334):
    """src/operator/leaky_relu.cc family: leaky/prelu/elu/selu/gelu/rrelu."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data > 0, data, a * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        # eval-mode rrelu = mean-slope leaky (training draws uniform slope)
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError(f"unknown LeakyReLU act_type {act_type!r}")


@register("softmax")
def _softmax(data, axis: int = -1, temperature: Optional[float] = None,
             length=None, use_length: bool = False):
    x = data / temperature if temperature else data
    if use_length and length is not None:
        mask = jnp.arange(data.shape[axis]) < length[..., None]
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def _log_softmax(data, axis: int = -1, temperature: Optional[float] = None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def _softmin(data, axis: int = -1):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation", aliases=("softmax_activation",))
def _softmax_activation(data, mode: str = "instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------


def _dropout_resolve(kwargs):
    """Resolve training flag + RNG key at invoke time so the tape closure replays
    bit-identically under jax.vjp (forward and backward masks must match)."""
    from .. import autograd
    if kwargs.get("_training") is None:
        kwargs["_training"] = autograd.is_training()
    active = kwargs.get("p", 0.5) > 0 and (
        kwargs["_training"] or kwargs.get("mode", "training") == "always")
    if kwargs.get("key") is None and active:
        kwargs["key"] = rng.next_key()
    return kwargs


@register("Dropout", aliases=("dropout",), resolve_kwargs=_dropout_resolve)
def _dropout(data, p: float = 0.5, mode: str = "training", axes=(), key=None,
             _training: Optional[bool] = None):
    """src/operator/nn/dropout.cc — inverted dropout; ``axes`` gives broadcast noise.

    Key sourcing is trace-aware (mxtpu.rng): imperative calls split the global key,
    hybridized traces receive fresh keys per step. ``mode='always'`` applies dropout in
    inference too.
    """
    from .. import autograd
    training = _training if _training is not None else autograd.is_training()
    if p <= 0 or (not training and mode != "always"):
        return data
    if key is None:
        key = rng.next_key()
    shape = list(data.shape)
    for a in axes or ():
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# ---------------------------------------------------------------------------
# loss-fused heads (custom backward semantics via jax.custom_vjp)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                         multi_output, normalization):
    return jax.nn.softmax(data, axis=-1 if not multi_output else 1)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization):
    out = _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                               multi_output, normalization)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, multi_output,
                        normalization, res, g):
    out, label = res
    axis = 1 if multi_output else -1
    onehot = jax.nn.one_hot(label.astype(jnp.int32), out.shape[axis],
                            axis=axis, dtype=out.dtype)
    grad = out - onehot
    if use_ignore:
        keep = (label != ignore_label).astype(out.dtype)
        grad = grad * jnp.expand_dims(keep, axis)
    scale = grad_scale
    if normalization == "batch":
        scale = scale / out.shape[0]
    elif normalization == "valid" and use_ignore:
        valid = jnp.maximum(jnp.sum(label != ignore_label), 1).astype(out.dtype)
        grad = grad / valid
    grad = grad * scale
    return grad, jnp.zeros_like(label)


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=("softmax_output", "Softmax"))
def _softmax_output(data, label, grad_scale: float = 1.0, ignore_label: float = -1.0,
                    use_ignore: bool = False, multi_output: bool = False,
                    normalization: str = "null", **_ignored):
    """src/operator/softmax_output-inl.h — forward=softmax, backward=p−onehot(label).

    The defining legacy loss-head: its gradient ignores the incoming cotangent shape
    and injects the cross-entropy gradient directly, which custom_vjp reproduces.
    """
    return _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                                multi_output, normalization)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _make_loss_core(data, grad_scale):
    return data


def _make_loss_fwd(data, grad_scale):
    return data, None


def _make_loss_bwd(grad_scale, res, g):
    return (jnp.full_like(g, grad_scale),)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("make_loss", aliases=("MakeLoss",))
def _make_loss(data, grad_scale: float = 1.0, valid_thresh: float = 0.0,
               normalization: str = "null"):
    """src/operator/make_loss-inl.h — identity forward, grad_scale gradient injected
    by the custom vjp (the incoming cotangent is ignored, matching the reference)."""
    return _make_loss_core(data, grad_scale)


@register("LinearRegressionOutput", aliases=("linear_regression_output",))
def _linreg_output(data, label, grad_scale: float = 1.0):
    """src/operator/regression_output-inl.h — forward=identity, backward=(pred−label)/n."""
    return _regression_core(data, label, grad_scale, "linear")


@register("MAERegressionOutput", aliases=("mae_regression_output",))
def _maereg_output(data, label, grad_scale: float = 1.0):
    return _regression_core(data, label, grad_scale, "mae")


@register("LogisticRegressionOutput", aliases=("logistic_regression_output",))
def _logreg_output(data, label, grad_scale: float = 1.0):
    return _regression_core(data, label, grad_scale, "logistic")


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _regression_core(data, label, grad_scale, kind):
    if kind == "logistic":
        return jax.nn.sigmoid(data)
    return data


def _regression_fwd(data, label, grad_scale, kind):
    out = _regression_core(data, label, grad_scale, kind)
    return out, (out, label)


def _regression_bwd(grad_scale, kind, res, g):
    out, label = res
    # reference normalizes by per-sample output size: num_output = Size()/shape[0]
    # (src/operator/regression_output-inl.h)
    n = int(out.size // out.shape[0]) if out.ndim > 1 else 1
    if kind == "mae":
        grad = jnp.sign(out - label)
    else:  # linear & logistic share (pred - label)
        grad = out - label
    return grad * grad_scale / n, jnp.zeros_like(label)


_regression_core.defvjp(_regression_fwd, _regression_bwd)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    """src/operator/loss_binary_op.cc — scalar summed CE with integer labels."""
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked)


# ---------------------------------------------------------------------------
# transformer helpers (contrib parity: src/operator/contrib/transformer.cc)
# ---------------------------------------------------------------------------


@register("div_sqrt_dim", namespace="contrib")
def _div_sqrt_dim(data):
    """contrib._contrib_div_sqrt_dim (transformer.cc:33): x / sqrt(d_last)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], dtype=data.dtype))


# ---------------------------------------------------------------------------
# IdentityAttachKLSparseReg (src/operator/identity_attach_KL_sparse_reg.cc)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _kl_sparse_reg(data, sparseness_target, penalty):
    return data


def _kl_sparse_reg_fwd(data, sparseness_target, penalty):
    return data, data


def _kl_sparse_reg_bwd(sparseness_target, penalty, data, dy):
    # reference backward (identity_attach_KL_sparse_reg-inl.h:91): the KL
    # penalty gradient vs the mean activation rho_hat is ADDED to the incoming
    # gradient. The reference keeps rho_hat as a momentum-smoothed aux buffer;
    # stateless here, rho_hat is the current batch mean (declared deviation —
    # the momentum kwarg is accepted and ignored at the op layer).
    rho_hat = jnp.mean(data, axis=0, keepdims=True)
    reg = penalty * (-sparseness_target / rho_hat
                     + (1.0 - sparseness_target) / (1.0 - rho_hat))
    return (dy + jnp.broadcast_to(reg, dy.shape),)


_kl_sparse_reg.defvjp(_kl_sparse_reg_fwd, _kl_sparse_reg_bwd)


@register("IdentityAttachKLSparseReg",
          aliases=("identity_attach_kl_sparse_reg",))
def _identity_attach_kl_sparse_reg(data, sparseness_target: float = 0.1,
                                   penalty: float = 0.001,
                                   momentum: float = 0.9):
    """Identity forward; backward attaches the KL sparseness penalty gradient
    for sigmoid activations (src/operator/identity_attach_KL_sparse_reg.cc;
    Hinton's guideTR P11). Pair only with sigmoid outputs (rho in (0,1))."""
    return _kl_sparse_reg(data, float(sparseness_target), float(penalty))


# ---------------------------------------------------------------------------
# SVMOutput (src/operator/svm_output.cc — hinge-loss head)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_output_core(data, label, margin, reg_coef, use_linear):
    return data


def _svm_output_fwd(data, label, margin, reg_coef, use_linear):
    return data, (data, label)


def _svm_output_bwd(margin, reg_coef, use_linear, res, g):
    # svm_output.cc L1_SVM :31 / L2_SVM :50 — the injected hinge gradient
    # (incoming cotangent ignored, like every legacy loss head)
    out, label = res
    k = jax.nn.one_hot(label.astype(jnp.int32), out.shape[-1],
                       dtype=out.dtype)
    if use_linear:   # L1-SVM: ±reg_coef where the margin is violated
        grad_k = -(margin > out).astype(out.dtype) * reg_coef
        grad_o = (margin > -out).astype(out.dtype) * reg_coef
    else:            # L2-SVM: linear in the violation
        grad_k = -jnp.where(margin > out, 2.0 * (margin - out), 0.0) * reg_coef
        grad_o = jnp.where(margin > -out, 2.0 * (margin + out), 0.0) * reg_coef
    grad = k * grad_k + (1.0 - k) * grad_o
    return grad, jnp.zeros_like(label)


_svm_output_core.defvjp(_svm_output_fwd, _svm_output_bwd)


@register("SVMOutput", aliases=("svm_output",))
def _svm_output(data, label, margin: float = 1.0,
                regularization_coefficient: float = 1.0,
                use_linear: bool = False):
    """Hinge-loss head (svm_output-inl.h): forward identity, backward the
    L1/L2-SVM margin gradient per class."""
    return _svm_output_core(data, label, float(margin),
                            float(regularization_coefficient),
                            bool(use_linear))


# v1-legacy / cuDNN op-name aliases (reference registers *_v1 and
# CuDNNBatchNorm as distinct legacy entry points over the same math)
alias("BatchNorm", "BatchNorm_v1", "CuDNNBatchNorm")
alias("Convolution", "Convolution_v1")
alias("Pooling", "Pooling_v1")


@register("SyncBatchNorm", namespace="contrib",
          aliases=("_contrib_SyncBatchNorm",))
def _sync_batch_norm_op(data, gamma, beta, moving_mean, moving_var,
                        eps: float = 1e-3, momentum: float = 0.9,
                        fix_gamma: bool = True, use_global_stats: bool = False,
                        ndev: int = 1, key: str = "", axis: int = 1,
                        cudnn_off: bool = False):
    """contrib SyncBatchNorm op name (src/operator/contrib/sync_batch_norm.cc).

    Inference form = plain BatchNorm over running stats; the cross-device
    TRAINING sync lives in ``gluon.contrib.nn.SyncBatchNorm`` (under a
    dp-sharded input XLA computes global-batch statistics, which IS the sync
    semantic — pmean only matters inside explicit shard_map regions). ndev/
    key are the reference's comm-handshake knobs — accepted, nothing to
    coordinate here."""
    return _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=eps,
                       momentum=momentum, fix_gamma=fix_gamma,
                       use_global_stats=use_global_stats, axis=axis)
