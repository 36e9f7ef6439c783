"""Neural-network layer ops.

Covers the reference's legacy layer-op tier (src/operator/*-inl.h):
FullyConnected, Convolution, Deconvolution, Pooling, BatchNorm, Dropout,
Activation, LeakyReLU, LRN, InstanceNorm, L2Normalization, softmax family,
loss/output ops, sequence ops. Design notes:

- Convs/matmuls lower to XLA `conv_general_dilated` / `dot_general`, the
  MXU path — no im2col (reference src/operator/nn/im2col.h) and no cuDNN
  algo registry (cudnn_algoreg-inl.h); XLA autotunes.
- Stateful aux (BatchNorm moving stats, reference batch_norm-inl.h) is
  functional: aux arrays in, updated aux out (see ops/registry.py).
- Output/loss ops (SoftmaxOutput, *RegressionOutput, MakeLoss) reproduce
  the reference's *custom backward semantics* — they ignore or replace the
  incoming head gradient — via jax.custom_vjp, so `Executor.backward()`
  with default head grads matches the reference bit-for-bit in structure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from ..base import MXNetError, coerce_bool, coerce_float, coerce_int, coerce_tuple

# ------------------------------------------------------------ activation


@register(
    "Activation",
    arg_names=["data"],
    defaults={"act_type": "relu"},
    aliases=("activation",),
)
def activation(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    raise MXNetError(f"unknown act_type {act_type!r}")


@register(
    "LeakyReLU",
    arg_names=["data", "gamma"],
    defaults={"act_type": "leaky", "slope": 0.25,
              "lower_bound": 0.125, "upper_bound": 0.334},
    coerce={"slope": coerce_float, "lower_bound": coerce_float,
            "upper_bound": coerce_float},
    needs_rng=True,
    needs_mode=True,
)
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, rng=None,
               is_train=False):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        if is_train:
            s = jax.random.uniform(
                rng, data.shape, data.dtype, lower_bound, upper_bound
            )
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise MXNetError(f"unknown act_type {act_type!r}")


# PReLU variant takes gamma as a learned input; expose it through the same
# registered op — Symbol-level composition passes gamma when act_type=prelu.


# -------------------------------------------------------- fully connected


@register(
    "FullyConnected",
    arg_names=["data", "weight", "bias"],
    coerce={"num_hidden": coerce_int, "no_bias": coerce_bool,
            "flatten": coerce_bool},
    defaults={"no_bias": False, "flatten": True},
    aliases=("fully_connected",),
)
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True):
    if flatten:
        x = data.reshape((data.shape[0], -1))
    else:
        x = data
    out = jnp.matmul(x, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ------------------------------------------------------------ convolution


def _conv_dims(kernel):
    return len(kernel)


def _norm_layout(layout, nd):
    """Resolve a reference-style layout string ('NCHW', 'NHWC', 'NCDHW',
    'NDHWC', 'NCW', 'NWC'); default channel-first like the reference."""
    if not layout or layout in ("None",):
        return "NC" + "DHW"[3 - nd:]
    layout = str(layout).upper()
    if len(layout) != nd + 2 or "N" not in layout or "C" not in layout:
        raise MXNetError(f"bad conv layout {layout!r} for {nd}d")
    return layout


def _spatial_tuple(v, nd, default):
    t = coerce_tuple(v) if v not in (None, "", ()) else ()
    if not t:
        t = (default,) * nd
    if len(t) != nd:
        t = (t[0],) * nd
    return t


@register(
    "Convolution",
    arg_names=["data", "weight", "bias"],
    coerce={
        "kernel": coerce_tuple,
        "stride": coerce_tuple,
        "dilate": coerce_tuple,
        "pad": coerce_tuple,
        "num_filter": coerce_int,
        "num_group": coerce_int,
        "no_bias": coerce_bool,
        "workspace": coerce_int,
    },
    defaults={"num_group": 1, "no_bias": False},
    aliases=("convolution",),
)
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                workspace=1024, cudnn_tune=None, cudnn_off=False,
                layout=None):
    """Convolution (reference src/operator/convolution-inl.h), any
    reference layout: NCHW (default) or channels-last NHWC/NDHWC/NWC.

    The reference lowers to im2col+GEMM (nn/im2col.h) or cuDNN; here a
    single lax.conv_general_dilated lowers straight onto the MXU. On TPU
    channels-last is the native orientation (C maps onto the 128-wide
    lane dimension), so NHWC graphs skip XLA's NCHW->NHWC relayout.
    Weight layout follows the reference convention: data layout with
    N->O, C->I (NCHW weights are OIHW, NHWC weights are OHWI).
    """
    nd = _conv_dims(kernel)
    stride = _spatial_tuple(stride, nd, 1)
    dilate = _spatial_tuple(dilate, nd, 1)
    pad = _spatial_tuple(pad, nd, 0)
    lay = _norm_layout(layout, nd)
    dn = lax.conv_dimension_numbers(
        data.shape,
        weight.shape,
        (lay, lay.replace("N", "O").replace("C", "I"), lay),
    )
    out = lax.conv_general_dilated(
        data,
        weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias and bias is not None:
        c_ax = lay.index("C")
        out = out + bias.reshape(
            tuple(-1 if i == c_ax else 1 for i in range(nd + 2))
        )
    return out


@register(
    "Deconvolution",
    arg_names=["data", "weight", "bias"],
    coerce={
        "kernel": coerce_tuple,
        "stride": coerce_tuple,
        "dilate": coerce_tuple,
        "pad": coerce_tuple,
        "adj": coerce_tuple,
        "target_shape": coerce_tuple,
        "num_filter": coerce_int,
        "num_group": coerce_int,
        "no_bias": coerce_bool,
    },
    defaults={"num_group": 1, "no_bias": True},
)
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), target_shape=(), num_filter=0,
                  num_group=1, no_bias=True, workspace=512, cudnn_tune=None,
                  cudnn_off=False, layout=None):
    """Transposed convolution (reference src/operator/deconvolution-inl.h):
    the gradient of Convolution w.r.t. its input, expressed directly via
    lax.conv_transpose."""
    nd = _conv_dims(kernel)
    stride = _spatial_tuple(stride, nd, 1)
    dilate = _spatial_tuple(dilate, nd, 1)
    pad = _spatial_tuple(pad, nd, 0)
    adj = _spatial_tuple(adj, nd, 0) if adj else (0,) * nd
    spatial = "DHW"[3 - nd :]
    dn = lax.conv_dimension_numbers(
        data.shape,
        weight.shape,
        ("NC" + spatial, "IO" + spatial, "NC" + spatial),
    )
    # explicit padding matching the reference output formula:
    # out = (in-1)*stride - 2*pad + dilate*(kernel-1) + adj + 1
    out = lax.conv_transpose(
        data,
        weight,
        strides=stride,
        padding=[
            (d * (k - 1) - p, d * (k - 1) - p + a)
            for k, p, a, d in zip(kernel, pad, adj, dilate)
        ],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        transpose_kernel=False,
    )
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# --------------------------------------------------------------- pooling


@register(
    "Pooling",
    arg_names=["data"],
    coerce={
        "kernel": coerce_tuple,
        "stride": coerce_tuple,
        "pad": coerce_tuple,
        "global_pool": coerce_bool,
    },
    defaults={"pool_type": "max", "global_pool": False,
              "pooling_convention": "valid"},
    aliases=("pooling",),
)
def pooling(data, kernel=(), pool_type="max", global_pool=False,
            pooling_convention="valid", stride=(), pad=(), cudnn_off=False,
            layout=None):
    nd = data.ndim - 2
    lay = _norm_layout(layout, nd)
    sp_axes = [i for i, ch in enumerate(lay) if ch not in "NC"]
    if global_pool:
        kernel = tuple(data.shape[a] for a in sp_axes)
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = _spatial_tuple(kernel, nd, 1)
        stride = _spatial_tuple(stride, nd, 1)
        pad = _spatial_tuple(pad, nd, 0)

    window = [1] * (nd + 2)
    strides = [1] * (nd + 2)
    base_pad = [(0, 0)] * (nd + 2)
    for i, ax in enumerate(sp_axes):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        base_pad[ax] = (pad[i], pad[i])
    if pooling_convention == "full" and not global_pool:
        # ceil output convention (pooling-inl.h): pad extra on the right
        # so that ceil((in + 2p - k)/s) + 1 windows fit.
        import math

        for i, ax in enumerate(sp_axes):
            in_ = data.shape[ax]
            out_ = int(
                math.ceil((in_ + 2 * pad[i] - kernel[i]) / stride[i])
            ) + 1
            needed = (out_ - 1) * stride[i] + kernel[i] - in_ - pad[i]
            base_pad[ax] = (pad[i], max(needed, pad[i]))
    window = tuple(window)
    strides = tuple(strides)

    if pool_type == "max":
        init = -jnp.inf
        out = lax.reduce_window(
            data, init, lax.max, window, strides, base_pad
        )
        return out
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(
            data, 0.0, lax.add, window, strides, base_pad
        )
        if pool_type == "sum":
            return summed
        # reference avg-pool divides by the full kernel size, padding
        # included (pooling-inl.h pool_enum::kAvgPooling)
        denom = 1.0
        for k in kernel:
            denom *= k
        return summed / denom
    raise MXNetError(f"unknown pool_type {pool_type!r}")


# ------------------------------------------------------------- batchnorm


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_core(x, gamma, beta, axis, eps):
    """Training-mode BN with one-pass sufficient statistics and a
    hand-written backward. The HBM traffic budget is the whole game on
    TPU (the profile shows ResNet-50 is BN/elementwise-bound, not
    MXU-bound): forward reads x once for the fused (sum, sum-of-squares)
    sibling reduction and once for the normalize pass; backward reads
    (dy, x) once for the fused (sum dy, sum dy*xhat) pair and once for
    the dx pass — the minimum for a non-materializing BN. Stats
    accumulate in f32 regardless of the compute dtype.

    Returns (out, mean, var) with mean/var in f32.
    """
    (out, mean, var), _ = _bn_core_fwd(x, gamma, beta, axis, eps)
    return out, mean, var


def _bn_stats(x, axis):
    axes = tuple(i for i in range(x.ndim) if i != axis)
    n = x.size // x.shape[axis]
    # f32 ACCUMULATION of low-precision elements via the reduce dtype —
    # never a materialized f32 cast of x (a cast the fusion planner may
    # schedule as its own full HBM pass)
    s1 = jnp.sum(x, axis=axes, dtype=jnp.float32)
    s2 = jnp.sum(x * x, axis=axes, dtype=jnp.float32)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    return mean, var, n


def _bn_core_fwd(x, gamma, beta, axis, eps):
    mean, var, _ = _bn_stats(x, axis)
    inv = lax.rsqrt(var + eps)
    bshape = tuple(
        x.shape[i] if i == axis else 1 for i in range(x.ndim))
    gf = gamma.astype(jnp.float32)
    # per-channel coefficients in f32 (C-sized, cheap); the big
    # elementwise pass stays in x.dtype end to end
    scale = (gf * inv).astype(x.dtype).reshape(bshape)
    shift = (beta.astype(jnp.float32) - mean * gf * inv).astype(
        x.dtype).reshape(bshape)
    out = x * scale + shift
    return (out, mean, var), (x, gamma, mean, inv)


def _bn_core_bwd(axis, eps, res, cts):
    dy, dmean_ct, dvar_ct = cts
    x, gamma, mean, inv = res
    axes = tuple(i for i in range(x.ndim) if i != axis)
    n = x.size // x.shape[axis]
    bshape = tuple(
        x.shape[i] if i == axis else 1 for i in range(x.ndim))
    dt = x.dtype
    mean_b = mean.astype(dt).reshape(bshape)
    inv_b = inv.astype(dt).reshape(bshape)
    xhat = (x - mean_b) * inv_b
    sum_dy = jnp.sum(dy, axis=axes, dtype=jnp.float32)
    sum_dy_xhat = jnp.sum(dy * xhat, axis=axes, dtype=jnp.float32)
    gf = gamma.astype(jnp.float32)
    c1 = (gf * inv).astype(dt).reshape(bshape)
    c2 = (sum_dy / n).astype(dt).reshape(bshape)
    c3 = (sum_dy_xhat / n).astype(dt).reshape(bshape)
    dx = c1 * (dy.astype(dt) - c2 - xhat * c3)
    # stat-output cotangents: literal zeros when the stats only feed the
    # (non-differentiated) moving-average update, so XLA folds these away
    dx = dx + (dmean_ct / n).astype(dt).reshape(bshape) \
        + (x - mean_b) * ((2.0 / n) * dvar_ct).astype(dt).reshape(bshape)
    return (dx, sum_dy_xhat.astype(gamma.dtype),
            sum_dy.astype(gamma.dtype))


_bn_core.defvjp(_bn_core_fwd, _bn_core_bwd)



def _bn_num_outputs(params):
    return 3 if coerce_bool(params.get("output_mean_var", False)) else 1


@register(
    "BatchNorm",
    arg_names=["data", "gamma", "beta"],
    aux_names=("moving_mean", "moving_var"),
    coerce={
        "eps": coerce_float,
        "momentum": coerce_float,
        "fix_gamma": coerce_bool,
        "use_global_stats": coerce_bool,
        "output_mean_var": coerce_bool,
        "axis": coerce_int,
    },
    defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
              "use_global_stats": False, "output_mean_var": False,
              "axis": 1},
    needs_mode=True,
    num_outputs_fn=_bn_num_outputs,
    aliases=("batch_norm",),
)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               is_train=False):
    """Reference src/operator/batch_norm-inl.h. Channel axis default 1
    (NCHW). Functional aux: returns updated moving stats in train mode."""
    axis = axis % data.ndim
    bshape = tuple(
        data.shape[i] if i == axis else 1 for i in range(data.ndim)
    )
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    g = lax.stop_gradient(g) if fix_gamma else g

    if is_train and not use_global_stats:
        out, mean, var = _bn_core(data, g, beta, axis, eps)
        new_mean = moving_mean * momentum + mean.astype(
            moving_mean.dtype) * (1 - momentum)
        new_var = moving_var * momentum + var.astype(
            moving_var.dtype) * (1 - momentum)
    else:
        mean = lax.stop_gradient(moving_mean)
        var = lax.stop_gradient(moving_var)
        inv = lax.rsqrt(var + eps)
        out = (data - mean.reshape(bshape)) * inv.reshape(
            bshape) * g.reshape(bshape) + beta.reshape(bshape)

    outs = (out,)
    if output_mean_var:
        # visible stat outputs keep the declared dtype contract
        # (infer_type reports the data dtype for every BN output); the
        # f32 copies still feed the moving-average update below
        outs = (out, mean.astype(data.dtype), var.astype(data.dtype))
    if is_train:
        return outs + (new_mean, new_var) if not use_global_stats else outs + (moving_mean, moving_var)
    return outs if len(outs) > 1 else out


@register(
    "InstanceNorm",
    arg_names=["data", "gamma", "beta"],
    coerce={"eps": coerce_float},
    defaults={"eps": 1e-3},
)
def instance_norm(data, gamma, beta, eps=1e-3):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(
        bshape
    ) + beta.reshape(bshape)


@register(
    "L2Normalization",
    arg_names=["data"],
    coerce={"eps": coerce_float},
    defaults={"eps": 1e-10, "mode": "instance"},
)
def l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise MXNetError(f"unknown mode {mode!r}")
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


@register(
    "LRN",
    arg_names=["data"],
    coerce={"alpha": coerce_float, "beta": coerce_float,
            "knorm": coerce_float, "nsize": coerce_int},
    defaults={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0},
)
def lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    """Local response norm across channels (src/operator/lrn-inl.h)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    windows = sum(
        padded[:, i : i + data.shape[1]] for i in range(nsize)
    )
    return data / jnp.power(knorm + alpha / nsize * windows, beta)


# --------------------------------------------------------------- dropout


@register(
    "Dropout",
    arg_names=["data"],
    coerce={"p": coerce_float},
    defaults={"p": 0.5, "mode": "training"},
    needs_rng=True,
    needs_mode=True,
    aliases=("dropout",),
)
def dropout(data, p=0.5, mode="training", rng=None, is_train=False):
    if not is_train and mode != "always":
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, jnp.zeros_like(data))


# ---------------------------------------------------------- softmax family


def _softmax_axis(v):
    return coerce_int(v)


@register(
    "softmax",
    arg_names=["data"],
    coerce={"axis": _softmax_axis, "temperature": coerce_float},
    defaults={"axis": -1, "temperature": 1.0},
)
def softmax(data, axis=-1, temperature=1.0):
    return jax.nn.softmax(data / temperature, axis=axis)


@register(
    "log_softmax",
    arg_names=["data"],
    coerce={"axis": _softmax_axis, "temperature": coerce_float},
    defaults={"axis": -1, "temperature": 1.0},
)
def log_softmax(data, axis=-1, temperature=1.0):
    return jax.nn.log_softmax(data / temperature, axis=axis)


@register(
    "SoftmaxActivation",
    arg_names=["data"],
    defaults={"mode": "instance"},
)
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(
        data.shape
    )


# ---------------------------------------------------- output (loss) ops
#
# These reproduce the reference's "output op" pattern: forward is identity
# or softmax; backward REPLACES the incoming gradient with the loss
# gradient. Implemented with custom_vjp so jax.vjp-driven executors get
# reference semantics with ones as head gradient.


def _softmax_output_impl(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, preserve_shape, normalization,
                         smooth_alpha, out_grad):
    del out_grad
    if multi_output:
        prob = jax.nn.softmax(data, axis=1)
    elif preserve_shape:
        prob = jax.nn.softmax(data, axis=-1)
    else:
        prob = jax.nn.softmax(
            data.reshape(data.shape[0], -1), axis=-1
        ).reshape(data.shape)
    return prob


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False,
                    preserve_shape=False, normalization="null",
                    smooth_alpha=0.0, out_grad=False):
    return _softmax_output_impl(
        data, label, grad_scale, ignore_label, multi_output, use_ignore,
        preserve_shape, normalization, smooth_alpha, out_grad
    )


def _softmax_output_fwd(data, label, *nd):
    prob = _softmax_output(data, label, *nd)
    return prob, (prob, label)


def _softmax_output_bwd(grad_scale, ignore_label, multi_output, use_ignore,
                        preserve_shape, normalization, smooth_alpha,
                        out_grad, res, g):
    prob, label = res
    if multi_output:
        # data (N, C, d...), label (N, d...): softmax over axis 1
        nclass = prob.shape[1]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, nclass, axis=1, dtype=prob.dtype)
        grad = prob - onehot
        if use_ignore:
            valid = (label != ignore_label).astype(prob.dtype)
            grad = grad * jnp.expand_dims(valid, 1)
    elif label.shape == prob.shape:
        # soft labels
        grad = prob - label
        valid = None
    else:
        nclass = prob.shape[-1]
        lab = label.astype(jnp.int32).reshape(prob.shape[:-1])
        onehot = jax.nn.one_hot(lab, nclass, dtype=prob.dtype)
        if smooth_alpha > 0:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (
                nclass - 1
            ) * (1 - onehot)
        grad = prob - onehot
        if use_ignore:
            valid = (lab != int(ignore_label)).astype(prob.dtype)
            grad = grad * valid[..., None]

    scale = grad_scale
    if normalization == "batch":
        grad = grad / prob.shape[0]
    elif normalization == "valid":
        if use_ignore:
            if multi_output:
                cnt = jnp.sum((label != ignore_label).astype(prob.dtype))
            else:
                cnt = jnp.sum(
                    (label.astype(jnp.int32) != int(ignore_label)).astype(
                        prob.dtype
                    )
                )
            grad = grad / jnp.maximum(cnt, 1.0)
        else:
            grad = grad / prob.shape[0]
    grad = grad * scale
    if out_grad:
        grad = grad * g
    return grad, jnp.zeros_like(label)


_softmax_output.defvjp(_softmax_output_fwd, _softmax_output_bwd)


_NORM_MAP = {0: "null", 1: "batch", 2: "valid",
             "null": "null", "batch": "batch", "valid": "valid"}


@register(
    "SoftmaxOutput",
    arg_names=["data", "label"],
    coerce={
        "grad_scale": coerce_float,
        "ignore_label": coerce_float,
        "multi_output": coerce_bool,
        "use_ignore": coerce_bool,
        "preserve_shape": coerce_bool,
        "normalization": lambda v: _NORM_MAP[v],
        "smooth_alpha": coerce_float,
        "out_grad": coerce_bool,
    },
    defaults={"grad_scale": 1.0, "ignore_label": -1.0,
              "multi_output": False, "use_ignore": False,
              "preserve_shape": False, "normalization": "null",
              "smooth_alpha": 0.0, "out_grad": False},
    no_grad_inputs=("label",),
    aliases=("Softmax",),
)
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False,
                   preserve_shape=False, normalization="null",
                   smooth_alpha=0.0, out_grad=False):
    return _softmax_output(
        data, label, grad_scale, ignore_label, multi_output, use_ignore,
        preserve_shape, normalization, smooth_alpha, out_grad
    )


def _regression_output(name, fwd, bwd, aliases=()):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def _core(data, label, grad_scale=1.0):
        return fwd(data)

    def _core_fwd(data, label, grad_scale):
        out = fwd(data)
        return out, (out, label)

    def _core_bwd(grad_scale, res, g):
        out, label = res
        num_output = 1
        for s in label.shape[1:]:
            num_output *= s
        grad = grad_scale / num_output * bwd(out, label.reshape(out.shape))
        # the label keeps its own dtype under mixed-precision compute;
        # the cotangent must come back in the data's
        return grad.astype(out.dtype), jnp.zeros_like(label)

    _core.defvjp(_core_fwd, _core_bwd)

    @register(
        name,
        arg_names=["data", "label"],
        coerce={"grad_scale": coerce_float},
        defaults={"grad_scale": 1.0},
        no_grad_inputs=("label",),
        aliases=aliases,
    )
    def _op(data, label, grad_scale=1.0):
        return _core(data, label, grad_scale)

    return _op


_regression_output(
    "LinearRegressionOutput", lambda x: x, lambda o, l: o - l
)
_regression_output(
    "MAERegressionOutput", lambda x: x, lambda o, l: jnp.sign(o - l)
)
_regression_output(
    "LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _make_loss(data, grad_scale=1.0, normalization="null"):
    return data


def _make_loss_fwd(data, grad_scale, normalization):
    return data, data.shape


def _make_loss_bwd(grad_scale, normalization, shape, g):
    grad = jnp.full(shape, grad_scale)
    if normalization == "batch":
        grad = grad / shape[0]
    return (grad,)


_make_loss.defvjp(_make_loss_fwd, _make_loss_bwd)


@register(
    "MakeLoss",
    arg_names=["data"],
    coerce={"grad_scale": coerce_float,
            "normalization": lambda v: _NORM_MAP.get(v, v)},
    defaults={"grad_scale": 1.0, "normalization": "null"},
    aliases=("make_loss",),
)
def make_loss(data, grad_scale=1.0, normalization="null", valid_thresh=0.0):
    return _make_loss(data, grad_scale, normalization)


def _ctc_arg_names(params):
    names = ["data", "label"]
    if coerce_bool(params.get("use_data_lengths", False)):
        names.append("data_lengths")
    if coerce_bool(params.get("use_label_lengths", False)):
        names.append("label_lengths")
    return names


@register(
    "CTCLoss",
    arg_names_fn=_ctc_arg_names,
    coerce={"use_data_lengths": coerce_bool,
            "use_label_lengths": coerce_bool,
            "blank_label": lambda v: str(v)},
    defaults={"use_data_lengths": False, "use_label_lengths": False,
              "blank_label": "first"},
    no_grad_inputs=("label", "data_lengths", "label_lengths"),
    aliases=("ctc_loss", "WarpCTC"),
)
def ctc_loss(*inputs, use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """Connectionist Temporal Classification loss (reference
    plugin/warpctc + contrib ctc_loss). data is (T, N, C) activations
    (softmax applied internally, the warpctc convention); label is
    (N, L). With blank_label='first' (default) the blank is id 0,
    classes are 1..C-1, and label padding is 0; with 'last' the blank
    is C-1 and label padding is any NEGATIVE id (the reference's -1
    convention). `use_data_lengths`/`use_label_lengths` add the
    corresponding (N,) length inputs, masking padded frames/labels.
    Returns per-example costs (N,); gradients flow to data via jax
    autodiff of the log-alpha recursion (optax's CTC).
    """
    try:
        import optax
    except ImportError as exc:  # pragma: no cover - env without optax
        raise MXNetError(
            "CTCLoss needs the optax package for its CTC core "
            "(pip install optax)") from exc

    if blank_label not in ("first", "last"):
        raise MXNetError(
            f"CTCLoss: blank_label must be 'first' or 'last', got "
            f"{blank_label!r}")
    # positional inputs follow _ctc_arg_names' order (the lengths are
    # present exactly when the corresponding use_* flag is set)
    want = 2 + int(use_data_lengths) + int(use_label_lengths)
    if len(inputs) != want:
        raise MXNetError(
            f"CTCLoss: expected {want} inputs "
            f"({', '.join(_ctc_arg_names({'use_data_lengths': use_data_lengths, 'use_label_lengths': use_label_lengths}))}), "
            f"got {len(inputs)}")
    data, label = inputs[0], inputs[1]
    idx = 2
    data_lengths = label_lengths = None
    if use_data_lengths:
        data_lengths = inputs[idx]
        idx += 1
    if use_label_lengths:
        label_lengths = inputs[idx]

    T, N, C = data.shape
    logits = jnp.transpose(data, (1, 0, 2))  # (N, T, C)
    if use_data_lengths:
        t_idx = jnp.arange(T, dtype=jnp.float32)[None, :]
        logit_pads = (t_idx >= data_lengths.astype(
            jnp.float32).reshape(-1, 1)).astype(logits.dtype)
    else:
        logit_pads = jnp.zeros((N, T), dtype=logits.dtype)
    lab = label.astype(jnp.int32)
    if blank_label == "first":
        blank_id = 0
        pads = (lab <= 0)
    else:
        blank_id = C - 1
        pads = (lab < 0)
    if use_label_lengths:
        l_idx = jnp.arange(lab.shape[1], dtype=jnp.int32)[None, :]
        pads = pads | (l_idx >= label_lengths.astype(
            jnp.int32).reshape(-1, 1))
    # padded slots must hold a safe id for the gather inside optax
    lab = jnp.where(pads, blank_id, lab)
    return optax.ctc_loss(logits, logit_pads, lab,
                          pads.astype(logits.dtype),
                          blank_id=blank_id)


@register(
    "softmax_cross_entropy",
    arg_names=["data", "label"],
    no_grad_inputs=("label",),
)
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return -jnp.sum(picked).reshape((1,))


@register(
    "SVMOutput",
    arg_names=["data", "label"],
    coerce={"margin": coerce_float, "regularization_coefficient": coerce_float,
            "use_linear": coerce_bool},
    defaults={"margin": 1.0, "regularization_coefficient": 1.0,
              "use_linear": False},
    no_grad_inputs=("label",),
)
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    return _svm_output(data, label, margin, regularization_coefficient,
                       use_linear)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_output(data, label, margin, reg_coef, use_linear):
    return data


def _svm_fwd(data, label, margin, reg_coef, use_linear):
    return data, (data, label)


def _svm_bwd(margin, reg_coef, use_linear, res, g):
    data, label = res
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, data.shape[1], dtype=data.dtype)
    # hinge loss gradient (svm_output-inl.h): L1 or squared hinge
    signed = jnp.where(onehot > 0, -data, data)
    viol = (margin + signed) > 0
    if use_linear:
        grad = jnp.where(viol, jnp.where(onehot > 0, -1.0, 1.0), 0.0)
    else:
        grad = jnp.where(
            viol,
            2.0 * (margin + signed) * jnp.where(onehot > 0, -1.0, 1.0),
            0.0,
        )
    return grad * reg_coef, jnp.zeros_like(label)


_svm_output.defvjp(_svm_fwd, _svm_bwd)


# ------------------------------------------------------------- sequence ops


def _seq_mask_from_length(length, maxlen, batch, dtype):
    steps = jnp.arange(maxlen, dtype=jnp.float32)[:, None]
    return (steps < length.astype(jnp.float32)[None, :]).astype(dtype)


@register(
    "SequenceMask",
    arg_names=["data", "sequence_length"],
    coerce={"use_sequence_length": coerce_bool, "value": coerce_float},
    defaults={"use_sequence_length": False, "value": 0.0},
    no_grad_inputs=("sequence_length",),
)
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0):
    """(T, N, ...) masking (src/operator/sequence_mask-inl.h)."""
    if not use_sequence_length or sequence_length is None:
        return data
    mask = _seq_mask_from_length(
        sequence_length, data.shape[0], data.shape[1], data.dtype
    )
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return data * mask + value * (1 - mask)


@register(
    "SequenceLast",
    arg_names=["data", "sequence_length"],
    coerce={"use_sequence_length": coerce_bool},
    defaults={"use_sequence_length": False},
    no_grad_inputs=("sequence_length",),
)
def sequence_last(data, sequence_length=None, use_sequence_length=False):
    if not use_sequence_length or sequence_length is None:
        return data[-1]
    idx = (sequence_length.astype(jnp.int32) - 1).clip(0)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
    )[0]


@register(
    "SequenceReverse",
    arg_names=["data", "sequence_length"],
    coerce={"use_sequence_length": coerce_bool},
    defaults={"use_sequence_length": False},
    no_grad_inputs=("sequence_length",),
)
def sequence_reverse(data, sequence_length=None, use_sequence_length=False):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T)
    lens = sequence_length.astype(jnp.int32)  # (N,)
    # index steps: for t < len: len-1-t else t
    idx = jnp.where(
        steps[:, None] < lens[None, :],
        lens[None, :] - 1 - steps[:, None],
        steps[:, None],
    )
    return jnp.take_along_axis(
        data, idx.reshape(idx.shape + (1,) * (data.ndim - 2)), axis=0
    )


# ------------------------------------------------------------ misc layers


@register(
    "UpSampling",
    coerce={"scale": coerce_int, "num_filter": coerce_int,
            "num_args": coerce_int},
    defaults={"sample_type": "nearest"},
)
def upsampling(*args, scale=2, sample_type="nearest", num_filter=0,
               num_args=None, multi_input_mode="concat", workspace=512):
    data = args[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        if len(args) > 1:
            outs = [out]
            for extra in args[1:]:
                s = out.shape[2] // extra.shape[2]
                outs.append(
                    jnp.repeat(jnp.repeat(extra, s, axis=2), s, axis=3)
                )
            return jnp.concatenate(outs, axis=1)
        return out
    if sample_type == "bilinear":
        weight = args[1]
        dn = lax.conv_dimension_numbers(
            data.shape, weight.shape, ("NCHW", "IOHW", "NCHW")
        )
        k = 2 * scale - scale % 2
        p = (k - scale) // 2  # matches DeconvolutionParam in upsampling
        return lax.conv_transpose(
            data, weight, strides=(scale, scale),
            padding=[(k - 1 - p, k - 1 - p)] * 2,
            dimension_numbers=dn,
        )
    raise MXNetError(f"unknown sample_type {sample_type!r}")


@register(
    "IdentityAttachKLSparseReg",
    arg_names=["data"],
    coerce={"sparseness_target": coerce_float, "penalty": coerce_float,
            "momentum": coerce_float},
    defaults={"sparseness_target": 0.1, "penalty": 0.001, "momentum": 0.9},
)
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1,
                                  penalty=0.001, momentum=0.9):
    return data
