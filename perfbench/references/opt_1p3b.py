"""Plain reference for the `opt_1p3b` configuration: the dense causal
forward of a decoder-only transformer with learned positions, ReLU MLP
and tied output, in straightforward jax.numpy, float32 at `highest`
matmul precision. It imports nothing of mxnet_tpu; it makes the weights
from the seed, under the names `init_decoder_params` uses, and the
harness hands the same arrays to the program.

The block is the decode tier's, whose departures from OPT's own are
listed in the configuration's `assumed`: RMS norm with a gain and no
bias where OPT has LayerNorm, no linear biases, positions from 0.

`quant="fp8"` is the control: both operands of every matrix product
(projections, attention's score and value products, MLP, output) rounded
to float8_e4m3 with a per-tensor scale, the step below the bfloat16 the
configuration states.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg):
    return (int(cfg["vocab_size"]), int(cfg["hidden_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
            int(cfg["ffn_dim"]), int(cfg["max_position_embeddings"]))


def param_shapes(cfg):
    v, d, n_layers, _h, ff, max_len = dims(cfg)
    s = {"embed": (v, d), "pos": (max_len, d), "ln_f": (d,)}
    for i in range(n_layers):
        s[f"l{i}.ln1"] = (d,)
        s[f"l{i}.ln2"] = (d,)
        for nm in ("wq", "wk", "wv", "wo"):
            s[f"l{i}.{nm}"] = (d, d)
        s[f"l{i}.w1"] = (d, ff)
        s[f"l{i}.w2"] = (ff, d)
    return s


def make_params(seed, cfg, dtype=jnp.bfloat16):
    """Uniform(-1/sqrt(fan_in), 1/sqrt(fan_in)) matrices (positions a
    tenth of that), gains 1, in the type they are served in, on the
    device in one jitted call from the seed."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def build(key):
        out = {}
        for i, n in enumerate(names):
            shp = shapes[n]
            if len(shp) == 1:
                out[n] = jnp.ones(shp, dtype)
                continue
            scale = 1.0 / math.sqrt(shp[0])
            if n == "pos":
                scale *= 0.1
            out[n] = jax.random.uniform(
                jax.random.fold_in(key, i), shp, jnp.float32,
                -scale, scale).astype(dtype)
        return out

    return build(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def _fp8(w):
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(x, quant):
    return _fp8(x) if quant == "fp8" else x


def _rms(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * g


def logits(params, tokens, cfg, quant=None):
    """tokens (T,) int32 -> logits (T, V) float32, causal."""
    _v, d, n_layers, h, _ff, _max_len = dims(cfg)
    dh = d // h
    t = tokens.shape[0]
    hi = jax.lax.Precision.HIGHEST

    def mm(a, name):
        w = params[name].astype(jnp.float32)
        return jnp.dot(_q(a, quant), _q(w, quant), precision=hi)

    embed = params["embed"].astype(jnp.float32)
    x = embed[tokens] + params["pos"].astype(jnp.float32)[:t]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    for i in range(n_layers):
        h1 = _rms(x, params[f"l{i}.ln1"].astype(jnp.float32))
        q = mm(h1, f"l{i}.wq").reshape(t, h, dh)
        k = mm(h1, f"l{i}.wk").reshape(t, h, dh)
        v = mm(h1, f"l{i}.wv").reshape(t, h, dh)
        s = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant),
                       precision=hi) / math.sqrt(dh)
        s = jnp.where(causal[None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _q(w, quant), _q(v, quant),
                       precision=hi).reshape(t, d)
        x = x + mm(o, f"l{i}.wo")
        h2 = _rms(x, params[f"l{i}.ln2"].astype(jnp.float32))
        x = x + mm(jax.nn.relu(mm(h2, f"l{i}.w1")), f"l{i}.w2")
    x = _rms(x, params["ln_f"].astype(jnp.float32))
    return jnp.dot(_q(x, quant), _q(embed, quant).T, precision=hi)


def _gaps_impl(params, tokens, n_prompt, cfg_key, with_control):
    cfg = dict(cfg_key)
    lg = logits(params, tokens, cfg)
    # position p's logits choose token p+1: served token j (absolute
    # position n_prompt + j) is chosen by the logits at n_prompt + j - 1
    best = jnp.max(lg[:-1], axis=-1)
    served = jnp.take_along_axis(lg[:-1], tokens[1:, None], axis=1)[:, 0]
    pos = jnp.arange(tokens.shape[0] - 1)
    mask = pos >= n_prompt - 1
    gap = jnp.where(mask, best - served, 0.0)
    if not with_control:
        return gap, gap
    low = jnp.argmax(logits(params, tokens, cfg, quant="fp8")[:-1], axis=-1)
    low_logit = jnp.take_along_axis(lg[:-1], low[:, None], axis=1)[:, 0]
    return gap, jnp.where(mask, best - low_logit, 0.0)


_gaps = jax.jit(_gaps_impl, static_argnames=("cfg_key", "with_control"))


def served_gaps(params, prompt, served, cfg, pad_to=256, control=False):
    """For one request: by how much each served token's reference logit
    lies below the reference's best at its position (0 where the served
    token IS the best). One dense pass over prompt + served tokens,
    padded to a multiple of `pad_to` (causal: the padding cannot reach
    the positions read). With `control`, also the same gap for the token
    that the fp8 forward puts first at each of those positions."""
    toks = list(prompt) + list(served)
    n = len(toks)
    width = -(-n // pad_to) * pad_to
    buf = np.zeros((width,), np.int32)
    buf[:n] = toks
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    gap, low = _gaps(params, jnp.asarray(buf), len(prompt), cfg_key,
                     control)
    lo, hi = len(prompt) - 1, n - 1
    return (np.asarray(gap)[lo:hi].astype(np.float64),
            np.asarray(low)[lo:hi].astype(np.float64))
