"""Plain reference for the `mimo_v25_ep16` configuration: the forward of
a decoder that mixes sliding-window and full attention layers (each
kind with its own KV head count, grouped queries, partial rotary with a
base of its own, a learned sink in the window layers), one leading dense
gated feed-forward and expert layers (sigmoid router with a choosing
bias, a plain top-k, no shared expert), in straightforward jax.numpy,
float32 at `highest` matmul precision, no cache and no batching. It
imports nothing of mxnet_tpu. It makes the weights from the seed under
the names the program uses, and the harness hands the same arrays to
the program.

The layer, as the configuration's keys are read (each departure is in
the file's `assumed`), with d = hidden_size, RMS norm with a learned
gain, no bias anywhere:

  x = x + Attn_i(RMS(x));  x = x + FFN_i(RMS(x));  final RMS, untied head
  [q | k | v] = h W_qkv (attention_projection_layout fused_qkv): H
        heads of head_dim, n_kv heads of head_dim, n_kv heads of
        v_head_dim, v scaled by attention_value_scale
  rotary on dimensions 0..R-1 of every q and k head, R =
        int(head_dim * partial_rotary_factor), pairs (d, d + R/2); the
        rest untouched
  query head j reads KV head j // (H / n_kv); scores q.k / sqrt(head_dim)
  full layer (hybrid_layer_pattern[i] == 0): n_kv = num_key_value_heads,
        base rope_theta, every position <= the query's, no sink
  window layer (== 1): n_kv = swa_num_key_value_heads, base
        swa_rope_theta, positions p - sliding_window + 1 .. p, and a
        learned scalar sink[head] in the softmax's denominator:
        P = exp(s - m) / (sum_j exp(s_j - m) + exp(sink - m))
  o = concat_heads(P v) W_o
  dense layer (moe_layer_freq[i] == 0): W_2(silu(W_1 h) * W_3 h)
  expert layer: s = sigmoid(h W_r) (float32); the num_experts_per_tok
        largest s + e_score_correction_bias chosen (ties to the lower
        index); weights their s / their sum (norm_topk_prob), scale 1;
        output sum_e w_e E_e(h), no shared expert.

The share. `n_routed_experts_held` experts from `experts_held_first` on,
and `vocab_size` rows of the vocabulary, are what one chip of the stated
deployment holds: the router is over all `n_routed_experts`, the terms
of the experts not held are left out (another chip's), and that partial
sum goes on to the next layer. The program is given the same share. Of
the two layer patterns the first `num_hidden_layers` entries are the
layers this file computes.

Controls: `fp8` rounds both operands of every matrix product to
float8_e4m3 with a per-tensor scale (the step below the bfloat16 the
configuration states); `no_sink` leaves the sink out of the window
layers' softmax; `full_window` lets the window layers attend their
whole causal context.

The pass is made of small jitted pieces driven from Python, in blocks of
queries, heads and rows, so that a 20k-token request fits beside the
resident weights.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512      # queries scored against their reach at once
HEAD_BLOCK = 16        # query heads scored at once (whole KV groups)
ROW_BLOCK = 4096       # rows through a feed-forward at once
REACH_STEP = 4096      # a query block's keys are cut to a multiple of this


def _key(cfg):
    """The configuration's numbers and patterns as a hashable (a jitted
    piece's static argument)."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, list)) and not isinstance(v, bool)))


class Dims:
    """The configuration's sizes by short names (from the file's dict
    or its `_key`)."""

    def __init__(self, cfg):
        cfg = cfg if isinstance(cfg, dict) else dict(cfg)
        g = lambda k: int(cfg[k])  # noqa: E731
        self.vocab, self.d = g("vocab_size"), g("hidden_size")
        self.layers = g("num_hidden_layers")
        self.h, self.dk, self.dv = g("num_attention_heads"), \
            g("head_dim"), g("v_head_dim")
        self.kv, self.kv_win = g("num_key_value_heads"), \
            g("swa_num_key_value_heads")
        self.window = g("sliding_window")
        self.windowed = [int(x) for x in
                         cfg["hybrid_layer_pattern"]][:self.layers]
        self.routed = [int(x) for x in cfg["moe_layer_freq"]][:self.layers]
        self.rot = int(self.dk * float(cfg["partial_rotary_factor"]))
        self.theta = float(cfg["rope_theta"])
        self.theta_win = float(cfg["swa_rope_theta"])
        self.v_scale = float(cfg["attention_value_scale"])
        self.ff, self.eff = g("intermediate_size"), \
            g("moe_intermediate_size")
        self.experts = g("n_routed_experts")
        self.held = g("n_routed_experts_held")
        self.first = int(cfg.get("experts_held_first", 0))
        self.k = g("num_experts_per_tok")
        self.eps = float(cfg["layernorm_epsilon"])

    def kv_of(self, i):
        return self.kv_win if self.windowed[i] else self.kv


def param_shapes(cfg):
    m = Dims(cfg)
    s = {"embed": (m.vocab, m.d), "head": (m.d, m.vocab), "norm_f": (m.d,)}
    for i in range(m.layers):
        p, kv = f"l{i}.", m.kv_of(i)
        s.update({
            p + "attn_norm": (m.d,), p + "ffn_norm": (m.d,),
            p + "wqkv": (m.d, (m.h + kv) * m.dk + kv * m.dv),
            p + "wo": (m.h * m.dv, m.d)})
        if m.windowed[i]:
            s[p + "sink"] = (m.h,)
        if m.routed[i]:
            s.update({
                p + "gate": (m.d, m.experts),
                p + "gate_bias": (m.experts,),
                p + "experts_w1": (m.held, m.d, m.eff),
                p + "experts_w3": (m.held, m.d, m.eff),
                p + "experts_w2": (m.held, m.eff, m.d)})
        else:
            s.update({p + "w1": (m.d, m.ff), p + "w3": (m.d, m.ff),
                      p + "w2": (m.ff, m.d)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi", "dtype"))
def _uniform(key, shape, lo, hi, dtype):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


def make_params(seed, cfg, dtype=jnp.bfloat16):
    """Matrices uniform(+-1/sqrt(fan_in)), gains 1, the router's choosing
    bias uniform(+-0.01), the sinks uniform(log(window) - 2, log(window))
    (both float32): a sink then takes between an eighth and a half of a
    head's mass against a window of near-equal scores, so that one left
    out shows. In the type they are served in, made on the device one
    array at a time (the largest is an expert layer's held experts)."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    top = math.log(Dims(cfg).window)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("gate_bias"):
            out[name] = _uniform(k, shape, -0.01, 0.01, jnp.float32)
        elif name.endswith("sink"):
            out[name] = _uniform(k, shape, top - 2.0, top, jnp.float32)
        elif len(shape) == 1:
            out[name] = jnp.ones(shape, dtype)
        else:
            scale = 1.0 / math.sqrt(shape[-2])
            out[name] = _uniform(k, shape, -scale, scale, dtype)
    return out


# ------------------------------------------------------------- arithmetic
def _fp8(w):
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(x, quant):
    return _fp8(x) if quant == "fp8" else x


def _f32(w):
    return w.astype(jnp.float32)


def _mm(a, w, quant):
    return jnp.dot(_q(a, quant), _q(_f32(w), quant), precision=HI)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(g)


def rotary_freqs(cfg, windowed):
    m = Dims(cfg)
    theta = m.theta_win if windowed else m.theta
    return (1.0 / theta ** (np.arange(0, m.rot, 2, dtype=np.float64)
                            / m.rot)).astype(np.float32)


def rotate(x, pos, freqs):
    """x (T, heads, D) at positions pos (T,): rotary on dimensions
    0..R-1, R = 2 * len(freqs), pairs (d, d + R/2); the rest as is."""
    half = freqs.shape[0]
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., 2 * half:]], axis=-1)


# ----------------------------------------------------- pieces of a layer
@functools.partial(jax.jit, static_argnames=("ck", "kv", "quant"))
def _qkv(x, norm, wqkv, freqs, ck, kv, quant):
    """q (T, H, dk), k (T, kv, dk) rotated, v (T, kv, dv) scaled, for
    every position."""
    m = Dims(ck)
    t = x.shape[0]
    pos = jnp.arange(t)
    y = _mm(_rms(x, norm, m.eps), wqkv, quant)
    q = rotate(y[:, :m.h * m.dk].reshape(t, m.h, m.dk), pos, freqs)
    k = rotate(y[:, m.h * m.dk:(m.h + kv) * m.dk].reshape(t, kv, m.dk),
               pos, freqs)
    v = y[:, (m.h + kv) * m.dk:].reshape(t, kv, m.dv) * m.v_scale
    return q, k, v


@functools.partial(jax.jit, static_argnames=("ck", "quant", "window"))
def _attend(q, k, v, sink, row0, lo, wo_h, ck, quant, window):
    """One block of query heads (whole KV groups) for one block of
    queries (rows row0..) over the keys given, which are positions lo..
    (all that the block can reach), through these heads' rows of W_o —
    (block, D), to be summed over head blocks. `window` None: every
    position <= the query's; `sink` None: none."""
    m = Dims(ck)
    n, hb, _ = q.shape
    g = k.shape[1]
    qpos = row0 + jnp.arange(n)
    kpos = lo + jnp.arange(k.shape[0])
    s = jnp.einsum("ngqd,sgd->gqns",
                   _q(q.reshape(n, g, hb // g, m.dk), quant), _q(k, quant),
                   precision=HI) / math.sqrt(m.dk)
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    s = jnp.where(mask[None, None], s, -1e30)
    mx = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.reshape(g, hb // g, 1, 1)
        mx = jnp.maximum(mx, sk)
    e = jnp.exp(s - mx)
    den = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - mx)
    o = jnp.einsum("gqns,sgd->ngqd", _q(e / den, quant), _q(v, quant),
                   precision=HI)
    return _mm(o.reshape(n, hb * m.dv), wo_h, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_ffn(x, norm, w1, w3, w2, eps, quant):
    xh = _rms(x, norm, eps)
    return _mm(jax.nn.silu(_mm(xh, w1, quant)) * _mm(xh, w3, quant), w2,
               quant)


@functools.partial(jax.jit, static_argnames=("ck",))
def _route(x, norm, gate, bias, ck):
    """(chosen (N, k) int32, weights (N, k)) over ALL experts. The
    router is never rounded by a control: its choice is discrete."""
    m = Dims(ck)
    xh = _rms(x, norm, m.eps)
    s = jax.nn.sigmoid(jnp.dot(xh, _f32(gate), precision=HI))
    chosen = jax.lax.top_k(s + _f32(bias), m.k)[1]
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _expert(x, norm, weight, w1, w3, w2, eps, quant):
    """weight (N,) * E(x^): one expert for every row (0 where the row
    did not choose it)."""
    xh = _rms(x, norm, eps)
    y = _mm(jax.nn.silu(_mm(xh, w1, quant)) * _mm(xh, w3, quant), w2, quant)
    return y * weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm, head, eps, quant):
    return _mm(_rms(x, norm, eps), head, quant)


def _blocks(n, size):
    return [(a, min(size, n - a)) for a in range(0, n, size)]


def expert_layer(params, i, x, cfg, quant=None, share=None):
    """An expert layer's feed-forward for rows x (N, D), residual not
    added: the held experts' terms (`share` = (first, count) overrides
    the configuration's). There is no shared expert."""
    m = Dims(cfg)
    p = f"l{i}."
    first, held = share if share is not None else (m.first, m.held)
    chosen, weights = _route(x, params[p + "ffn_norm"], params[p + "gate"],
                             params[p + "gate_bias"], _key(cfg))
    y = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=1)
        y = y + _expert(x, params[p + "ffn_norm"], w_e,
                        params[p + "experts_w1"][e],
                        params[p + "experts_w3"][e],
                        params[p + "experts_w2"][e], m.eps, quant)
    return y


def _add_rows_impl(x, rows, at):
    return jax.lax.dynamic_update_slice_in_dim(
        x, jax.lax.dynamic_slice_in_dim(x, at, rows.shape[0]) + rows, at, 0)


@functools.lru_cache(maxsize=None)
def _add_rows_jit(backend):
    return jax.jit(_add_rows_impl,
                   donate_argnums=() if backend == "cpu" else (0,))


def _add_rows(x, rows, at):
    """x[at:at+len(rows)] += rows, in place on an accelerator."""
    return _add_rows_jit(jax.default_backend())(x, rows, at)


def forward(params, tokens, cfg, quant=None, no_sink=False,
            full_window=False, rows=None):
    """tokens (T,) int32 -> (logits (R, V) float32 of rows [r0, r0+R),
    r0). `rows` = (r0, R) is the window the LAST layer is computed for
    (every earlier layer needs every position); None: all."""
    m = Dims(cfg)
    ck = _key(cfg)
    t = int(tokens.shape[0])
    r0, nr = rows if rows is not None else (0, t)
    x = _f32(params["embed"])[jnp.asarray(tokens)]
    for i in range(m.layers):
        p = f"l{i}."
        lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
        kv = m.kv_of(i)
        windowed = bool(m.windowed[i])
        window = m.window if windowed and not full_window else None
        sink = lp["sink"] if windowed and not no_sink else None
        last = i == m.layers - 1
        lo, n = (r0, nr) if last else (0, t)
        q, k, v = _qkv(x, lp["attn_norm"], lp["wqkv"],
                       jnp.asarray(rotary_freqs(cfg, windowed)), ck, kv,
                       quant)
        if last:
            x = x[lo:lo + n]
        group = m.h // kv
        hb = min(m.h, group * max(1, HEAD_BLOCK // group))
        for h0 in range(0, m.h, hb):
            g0, g1 = h0 // group, (h0 + hb) // group
            wo_h = lp["wo"][h0 * m.dv:(h0 + hb) * m.dv]
            for a, b in _blocks(n, QUERY_BLOCK):
                # the keys the block can reach, cut to few distinct
                # sizes (each is a compiled shape)
                end = lo + a + b
                if window is None:
                    k_lo, k_hi = 0, -(-end // REACH_STEP) * REACH_STEP
                else:
                    k_hi = -(-end // QUERY_BLOCK) * QUERY_BLOCK
                    k_lo = max(0, k_hi - 2 * QUERY_BLOCK) \
                        if window <= QUERY_BLOCK else 0
                k_hi = min(t, k_hi)
                x = _add_rows(x, _attend(
                    q[lo + a:lo + a + b, h0:h0 + hb], k[k_lo:k_hi, g0:g1],
                    v[k_lo:k_hi, g0:g1],
                    None if sink is None else _f32(sink[h0:h0 + hb]),
                    lo + a, k_lo, wo_h, ck, quant, window), a)
        del q, k, v
        for a, b in _blocks(n, ROW_BLOCK):
            xb = x[a:a + b]
            if m.routed[i]:
                y = expert_layer(params, i, xb, cfg, quant)
            else:
                y = _dense_ffn(xb, lp["ffn_norm"], lp["w1"], lp["w3"],
                               lp["w2"], m.eps, quant)
            x = _add_rows(x, y, a)
    return _head(x, params["norm_f"], params["head"], m.eps, quant), r0


def served_gaps(params, prompt, served, cfg, pad_to=512, control=False):
    """For one request: by how much each served token's reference logit
    lies below the reference's best at its position (0 where the served
    token IS the best). One pass over prompt + served tokens, padded to
    a multiple of `pad_to` (causal: the padding cannot reach the
    positions read); the last layer is computed for the rows read
    alone. With `control` ("fp8", or True for it; "no_sink";
    "full_window"; or a tuple, answered as a dict), also the same gap
    for the token that the control's forward puts first at each of
    those positions."""
    with jax.default_matmul_precision("highest"):
        toks = list(prompt) + list(served)
        n = len(toks)
        width = -(-n // pad_to) * pad_to
        buf = np.zeros((width,), np.int32)
        buf[:n] = toks
        lo, hi = len(prompt) - 1, n - 1
        nr = min(width, -(-(hi - lo) // QUERY_BLOCK) * QUERY_BLOCK)
        r0 = max(0, min(lo, width - nr))
        lg, _ = forward(params, buf, cfg, rows=(r0, nr))
        lg = lg[lo - r0:hi - r0]
        want = jnp.asarray(buf[lo + 1:hi + 1])
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, want[:, None], axis=1)[:, 0]
        gap = np.asarray(best - got, np.float64)
        if not control:
            return gap, gap
        kinds = ("fp8",) if control is True else \
            (control,) if isinstance(control, str) else tuple(control)
        lows = {}
        for kind in kinds:
            low_lg, _ = forward(params, buf, cfg,
                                quant="fp8" if kind == "fp8" else None,
                                no_sink=kind == "no_sink",
                                full_window=kind == "full_window",
                                rows=(r0, nr))
            low = jnp.argmax(low_lg[lo - r0:hi - r0], axis=-1)
            low_logit = jnp.take_along_axis(lg, low[:, None], axis=1)[:, 0]
            lows[kind] = np.asarray(best - low_logit, np.float64)
        return gap, lows[kinds[0]] if len(kinds) == 1 else lows
