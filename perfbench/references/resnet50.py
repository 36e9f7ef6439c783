"""Plain reference for the `resnet50` configuration: pre-activation
ResNet (He et al. 2016) forward, cross-entropy loss, gradients and
SGD-with-momentum, in straightforward jax.numpy. It imports nothing of
mxnet_tpu and makes its own weights and batches from the seed; the
harness hands the same arrays to the program.

Precision. `compute` is the type activations and the weights' working
copies are held in between operations (the configuration states
bfloat16; master weights, BatchNorm statistics, the loss and the update
are float32). `quant="fp8"` is the control: every convolution's and the
classifier's inputs rounded to float8_e4m3 with a per-tensor scale
(straight-through gradient), the step a later PR would be tempted by.

Departures from the program, each of no consequence to the function
computed: the stem is the plain 7x7 stride-2 convolution (the program
runs its space-to-depth rewrite of the same weights); BatchNorm is
computed in float32 from the upcast input and rounded once.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 2e-5


def _arch(cfg):
    """(units per stage, filters per stage, bottleneck) as the
    configuration's file states them."""
    return cfg["units"], cfg["filters"], bool(cfg["bottleneck"])


def param_shapes(cfg):
    """{name: shape} of every trainable array, named as the Symbol
    names them; convolution weights are (out, kh, kw, in) (NHWC)."""
    units, filters, bottle = _arch(cfg)
    s = {"bn_data_gamma": (3,), "bn_data_beta": (3,),
         "conv0_weight": (filters[0], 7, 7, 3),
         "bn0_gamma": (filters[0],), "bn0_beta": (filters[0],)}
    c = filters[0]
    for i, n_units in enumerate(units):
        f = filters[i + 1]
        for j in range(n_units):
            u = f"stage{i + 1}_unit{j + 1}"
            s[u + "_bn1_gamma"] = (c,)
            s[u + "_bn1_beta"] = (c,)
            if bottle:
                m = f // 4
                s[u + "_conv1_weight"] = (m, 1, 1, c)
                s[u + "_bn2_gamma"] = (m,)
                s[u + "_bn2_beta"] = (m,)
                s[u + "_conv2_weight"] = (m, 3, 3, m)
                s[u + "_bn3_gamma"] = (m,)
                s[u + "_bn3_beta"] = (m,)
                s[u + "_conv3_weight"] = (f, 1, 1, m)
            else:
                s[u + "_conv1_weight"] = (f, 3, 3, c)
                s[u + "_bn2_gamma"] = (f,)
                s[u + "_bn2_beta"] = (f,)
                s[u + "_conv2_weight"] = (f, 3, 3, f)
            if j == 0:
                s[u + "_sc_weight"] = (f, 1, 1, c)
            c = f
    s["bn1_gamma"] = (c,)
    s["bn1_beta"] = (c,)
    s["fc1_weight"] = (int(cfg["num_classes"]), c)
    s["fc1_bias"] = (int(cfg["num_classes"]),)
    return s


def aux_shapes(cfg):
    """{name: shape} of the BatchNorm moving statistics."""
    out = {}
    for name, shape in param_shapes(cfg).items():
        if name.endswith("_gamma"):
            stem = name[:-len("_gamma")]
            out[stem + "_moving_mean"] = shape
            out[stem + "_moving_var"] = shape
    return out


def make_params(seed, cfg):
    """He-normal weights (fan-in), gamma 1, beta and bias 0, float32, on
    the default device, in one jitted call from the seed."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def build(key):
        out = {}
        for i, n in enumerate(names):
            shp = shapes[n]
            if n.endswith("_weight"):
                fan_in = int(np.prod(shp[1:]))
                out[n] = jax.random.normal(
                    jax.random.fold_in(key, i), shp, jnp.float32) \
                    * np.float32(np.sqrt(2.0 / fan_in))
            elif n.endswith("_gamma"):
                out[n] = jnp.ones(shp, jnp.float32)
            else:
                out[n] = jnp.zeros(shp, jnp.float32)
        return out

    return build(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def make_batches(seed, cfg, batch, count, sharding=None):
    """`count` batches whose rows all differ: images uniform in [-1, 1)
    (batch, H, W, 3) float32 and labels as float32 class ids, made on
    the device in one jitted call (sharded over the batch where a
    sharding is given)."""
    side, classes = int(cfg["image_size"]), int(cfg["num_classes"])

    def build(key):
        xs, ys = [], []
        for i in range(count):
            kx, ky = jax.random.split(jax.random.fold_in(key, i))
            xs.append(jax.random.uniform(
                kx, (batch, side, side, 3), jnp.float32, -1.0, 1.0))
            ys.append(jax.random.randint(
                ky, (batch,), 0, classes).astype(jnp.float32))
        return xs, ys

    fn = jax.jit(build) if sharding is None else jax.jit(
        build, out_shardings=([sharding] * count, [sharding] * count))
    xs, ys = fn(jax.random.PRNGKey((seed + 7919) % (2 ** 31 - 1)))
    return list(zip(xs, ys))


@jax.custom_vjp
def _fp8(x):
    """Round to float8_e4m3 with a per-tensor scale; identity gradient."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-30) / 448.0
    q = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * s).astype(x.dtype)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def _acc(x):
    """The type sums are taken in: float32, or wider if x is."""
    return jnp.promote_types(jnp.float32, x.dtype)


def _conv(x, w, stride, pad, quant):
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    # sums are taken in float32 by the hardware and rounded once to the
    # compute type (a preferred_element_type wider than the inputs does
    # not transpose under autodiff)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"))


def _bn(x, gamma, beta, fix_gamma=False, style="scale_shift"):
    """Training-mode BatchNorm: batch mean and biased variance in
    float32 (two passes). `style="scale_shift"` is BatchNorm as
    mixed-precision training computes it: the per-channel scale and
    shift worked out in float32, rounded to the compute type, and
    applied there as x * scale + shift. `style="float32"` normalises the
    upcast input in float32 and rounds once at the end."""
    xf = x.astype(_acc(x))
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(xf - mean), axis=(0, 1, 2))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = jax.lax.rsqrt(var + BN_EPS)
    if style == "float32":
        return ((xf - mean) * inv * g + beta).astype(x.dtype)
    scale = (g * inv).astype(x.dtype)
    shift = (beta - mean * g * inv).astype(x.dtype)
    return x * scale + shift


def _maxpool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])


def forward(params, x, cfg, compute=jnp.bfloat16, quant=None):
    """Images (N, H, W, 3) -> class logits (N, classes) float32."""
    units, filters, bottle = _arch(cfg)
    style = cfg.get("reference_bn", "scale_shift")
    _bn = functools.partial(globals()["_bn"], style=style)
    p = {k: v.astype(compute) if k.endswith("_weight") else v
         for k, v in params.items()}
    relu = jax.nn.relu
    x = _bn(x.astype(compute), p["bn_data_gamma"], p["bn_data_beta"],
            fix_gamma=True)
    x = _conv(x, p["conv0_weight"], 2, 3, quant)
    x = relu(_bn(x, p["bn0_gamma"], p["bn0_beta"]))
    x = _maxpool(x)
    for i, n_units in enumerate(units):
        for j in range(n_units):
            u = f"stage{i + 1}_unit{j + 1}"
            stride = 2 if (i > 0 and j == 0) else 1
            a1 = relu(_bn(x, p[u + "_bn1_gamma"], p[u + "_bn1_beta"]))
            if bottle:
                y = _conv(a1, p[u + "_conv1_weight"], 1, 0, quant)
                y = relu(_bn(y, p[u + "_bn2_gamma"], p[u + "_bn2_beta"]))
                y = _conv(y, p[u + "_conv2_weight"], stride, 1, quant)
                y = relu(_bn(y, p[u + "_bn3_gamma"], p[u + "_bn3_beta"]))
                y = _conv(y, p[u + "_conv3_weight"], 1, 0, quant)
            else:
                y = _conv(a1, p[u + "_conv1_weight"], stride, 1, quant)
                y = relu(_bn(y, p[u + "_bn2_gamma"], p[u + "_bn2_beta"]))
                y = _conv(y, p[u + "_conv2_weight"], 1, 1, quant)
            sc = x if j > 0 else _conv(a1, p[u + "_sc_weight"], stride, 0,
                                       quant)
            x = y + sc
    x = relu(_bn(x, p["bn1_gamma"], p["bn1_beta"]))
    x = jnp.mean(x.astype(_acc(x)), axis=(1, 2)).astype(compute)
    w = p["fc1_weight"]
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    logits = jnp.dot(x, w.T).astype(_acc(x))
    return logits + p["fc1_bias"].astype(_acc(x))


def loss_sum(params, x, y, cfg, compute, quant):
    """Sum over rows of the cross-entropy (SoftmaxOutput's gradient is
    that of the sum; the optimizer rescales by 1/batch), and the mean of
    -log(p + 1e-8) that is reported as the loss."""
    logits = forward(params, x, cfg, compute, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=1)[:, 0]
    shown = -jnp.mean(jnp.log(jnp.exp(picked) + 1e-8))
    return -jnp.sum(picked), (shown, jnp.exp(logp))


def _decays(name):
    return name.endswith("_weight") or name.endswith("_gamma")


@functools.partial(jax.jit, static_argnames=("cfg_key", "compute", "quant",
                                             "rows", "freeze"))
def _step(params, moms, x, y, cfg_key, compute, quant, rows, freeze,
          lr, momentum, wd):
    cfg = dict(cfg_key)
    n = x.shape[0]
    if rows is not None:
        # a planted fault: only the first `rows` rows take part, the
        # mean taken over them (half of the batch left out; or, with a
        # quarter, one chip's shard with the exchange left out)
        x, y, n = x[:rows], y[:rows], rows
    (_, (shown, probs)), grads = jax.value_and_grad(
        loss_sum, has_aux=True)(params, x, y, cfg, compute, quant)
    new_p, new_m, got = {}, {}, {}
    for k, w in params.items():
        g = grads[k] * np.float32(1.0 / n)
        got[k] = g
        m = momentum * moms[k] - lr * (g + (wd if _decays(k) else 0.0) * w)
        new_m[k] = moms[k] if freeze else m
        new_p[k] = w if freeze else w + m
    return new_p, new_m, shown, got, probs


def train_steps(params, batches, cfg, hyper, compute=jnp.bfloat16,
                quant=None, rows=None, freeze=False, steps=3):
    """Follow the first `steps` steps. Returns per-step losses, the
    first step's gradient as the optimizer gets it (already divided by
    the batch), the first step's class probabilities (rows, classes; rows
    kept by a planted fault only) and the parameters after the last step,
    as host arrays.
    `freeze` plants the fault of a step that returns its state
    unchanged."""
    cfg_key = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, list))))
    moms = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, first = [], None
    p = params
    for i in range(steps):
        x, y = batches[i]
        p, moms, shown, got, probs = _step(
            p, moms, x, y, cfg_key, compute, quant, rows, freeze,
            np.float32(hyper["learning_rate"]),
            np.float32(hyper["momentum"]), np.float32(hyper["wd"]))
        losses.append(float(shown))
        if i == 0:
            first = {k: np.asarray(v) for k, v in got.items()}
            probs1 = np.asarray(probs)
        del got, probs
    return {"losses": losses, "grad1": first, "probs1": probs1,
            "params": {k: np.asarray(v) for k, v in p.items()}}
