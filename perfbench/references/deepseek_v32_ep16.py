"""Plain reference for the `deepseek_v32_ep16` configuration: the forward
of a decoder with multi-head latent attention (MLA), a learned sparse
selection of what each query attends (the indexer, exact top-k), rotary
positions with YaRN, one leading dense SwiGLU layer and expert layers
(sigmoid router with a choosing bias, group-limited top-k, a shared
expert), in straightforward jax.numpy, float32 at `highest` matmul
precision. It imports nothing of mxnet_tpu. It makes the weights from the
seed under the names the program uses, and the harness hands the same
arrays to the program.

The layer follows the published description and inference code of
DeepSeek-V3.2 (deepseek-ai/DeepSeek-V3.2, `inference/model.py`):

  x^ = RMS(x)
  c_q = RMS(x^ W_qa);  q = c_q W_qb -> per head [q_nope, q_pe], q_pe
        rotated (interleaved pairs)
  [c_kv, k_pe] = x^ W_kva;  c = RMS(c_kv), k_pe rotated (one for all
        heads);  per head k = [c W_uk, k_pe], v = c W_uv  (W_kvb)
  indexer: q^I = c_q W^I_qb (J heads; first rope dims rotated, first half
        with second half), k^I = LayerNorm(x^ W^I_k) (same rotation),
        w = x^ W^I_w / sqrt(J) / sqrt(D^I);
        I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]),  s <= t
        S_t = the index_topk positions of largest I[t, .] (all while
        t < index_topk)
  attention: UNABSORBED, dense scores q . k over every position with the
        mask s in S_t, softmax in float32, times v, then W_o
  dense layer: x + W_2(silu(W_1 x^) * W_3 x^)
  expert layer: s = sigmoid(x^ W_g); for choosing only s' = s + b; groups
        scored by their two largest s', the best topk_group groups kept,
        the num_experts_per_tok largest s' among them chosen; weights
        the chosen s / their sum * routed_scaling_factor; output
        sum_e w_e E_e(x^) + E_shared(x^)
  head: RMS, then the vocabulary rows held.

The share. `n_routed_experts_held` experts from `experts_held_first` on,
and `vocab_size` rows of the vocabulary, are what one chip of the stated
deployment holds: the router, its groups and its normalisation are over
all `n_routed_experts`, the terms of the experts not held are left out
(they are another chip's), and that partial sum goes on to the next
layer. The program is given the same share.

Departures from the published layer (the configuration's `assumed`):
q^I and k^I stay in the weights' precision where deployments round them
to FP8 after a Hadamard rotation (orthogonal and on both sides: without
the rounding it changes no product, so it is left out); the
multi-token-prediction module is not loaded.

Controls: `fp8` rounds both operands of every matrix product to
float8_e4m3 with a per-tensor scale (the step below the bfloat16 the
configuration states); `dense` leaves the index mask out (every query
attends its whole causal context).

The pass is made of small jitted pieces driven from Python, in blocks of
queries, heads and rows, so that a 33k-token request fits beside the
resident weights: the residual stream is the only array of the prompt's
length times the model's width that lives through a layer.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512      # queries scored against the whole context at once
HEAD_BLOCK = 8         # heads whose keys and values exist at once
ROW_BLOCK = 4096       # rows through a feed-forward at once
WINDOW = 640           # rows of the last layer that are computed
REACH_STEP = 16896     # a query block's keys are cut to a multiple of this

# what the newest served_gaps call selected for the request's last served
# token: {"selected": (layers, index_topk) positions, -1 past the reach}
LAST = {}


def _key(cfg):
    """The configuration's numbers as a hashable (a jitted piece's
    static argument); `_thaw` undoes it."""
    def freeze(v):
        return tuple(sorted((k, freeze(x)) for k, x in v.items())) \
            if isinstance(v, dict) else v
    return tuple(sorted(
        (k, freeze(v)) for k, v in cfg.items()
        if k == "rope_scaling" or isinstance(v, (int, float))))


def _thaw(cfg):
    if isinstance(cfg, dict):
        return cfg
    return {k: (dict(v) if isinstance(v, tuple) else v) for k, v in cfg}


class Dims:
    """The configuration's sizes by short names (from the file's dict
    or its `_key`)."""

    def __init__(self, cfg):
        cfg = _thaw(cfg)
        g = lambda k: int(cfg[k])  # noqa: E731
        self.vocab, self.d = g("vocab_size"), g("hidden_size")
        self.layers, self.dense = g("num_hidden_layers"), \
            g("first_k_dense_replace")
        self.h = g("num_attention_heads")
        self.qr, self.kvr = g("q_lora_rank"), g("kv_lora_rank")
        self.dn, self.dr = g("qk_nope_head_dim"), g("qk_rope_head_dim")
        self.dv = g("v_head_dim")
        self.j, self.di = g("index_n_heads"), g("index_head_dim")
        self.topk = g("index_topk")
        self.ff, self.eff = g("intermediate_size"), \
            g("moe_intermediate_size")
        self.experts = g("n_routed_experts")
        self.held = g("n_routed_experts_held")
        self.first = int(cfg.get("experts_held_first", 0))
        self.k = g("num_experts_per_tok")
        self.groups, self.keep = g("n_group"), g("topk_group")
        self.route_scale = float(cfg["routed_scaling_factor"])
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.max_len = g("max_position_embeddings")
        self.rope = dict(cfg["rope_scaling"])


def param_shapes(cfg):
    m = Dims(cfg)
    s = {"embed": (m.vocab, m.d), "head": (m.d, m.vocab), "norm_f": (m.d,)}
    for i in range(m.layers):
        p = f"l{i}."
        s.update({
            p + "attn_norm": (m.d,), p + "ffn_norm": (m.d,),
            p + "wq_a": (m.d, m.qr), p + "q_norm": (m.qr,),
            p + "wq_b": (m.qr, m.h * (m.dn + m.dr)),
            p + "wkv_a": (m.d, m.kvr + m.dr), p + "kv_norm": (m.kvr,),
            p + "wkv_b": (m.kvr, m.h * (m.dn + m.dv)),
            p + "wo": (m.h * m.dv, m.d),
            p + "idx_wq_b": (m.qr, m.j * m.di),
            p + "idx_wk": (m.d, m.di),
            p + "idx_k_norm_g": (m.di,), p + "idx_k_norm_b": (m.di,),
            p + "idx_w": (m.d, m.j)})
        if i < m.dense:
            s.update({p + "w1": (m.d, m.ff), p + "w3": (m.d, m.ff),
                      p + "w2": (m.ff, m.d)})
        else:
            s.update({
                p + "gate": (m.d, m.experts),
                p + "gate_bias": (m.experts,),
                p + "experts_w1": (m.held, m.d, m.eff),
                p + "experts_w3": (m.held, m.d, m.eff),
                p + "experts_w2": (m.held, m.eff, m.d),
                p + "shared_w1": (m.d, m.eff),
                p + "shared_w3": (m.d, m.eff),
                p + "shared_w2": (m.eff, m.d)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _uniform(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -scale,
                              scale).astype(dtype)


def make_params(seed, cfg, dtype=jnp.bfloat16):
    """Matrices uniform(+-1/sqrt(fan_in)), gains 1, the router's choosing
    bias (float32) and the index key's LayerNorm bias uniform(+-0.01), in
    the type they are served in, made on the device one array at a time
    (the largest is an expert layer's held experts)."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("gate_bias"):
            out[name] = _uniform(k, shape, 0.01, jnp.float32)
        elif name.endswith("idx_k_norm_b"):
            out[name] = _uniform(k, shape, 0.01, dtype)
        elif len(shape) == 1:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = _uniform(k, shape, 1.0 / math.sqrt(shape[-2]),
                                 dtype)
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


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(g) + _f32(b)


def yarn_freqs(cfg):
    """The published `precompute_freqs_cis`: base frequencies over the
    rope dims, each blended between f and f/factor by the linear ramp
    between the correction dims of beta_fast and beta_slow."""
    m = Dims(cfg)
    dim, r = m.dr, m.rope
    freqs = 1.0 / (m.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    orig = int(r["original_max_position_embeddings"])
    if m.max_len > orig:
        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) \
                / (2 * math.log(m.theta))

        low = max(math.floor(correction_dim(float(r["beta_fast"]))), 0)
        high = min(math.ceil(correction_dim(float(r["beta_slow"]))),
                   dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        smooth = 1.0 - ramp
        freqs = freqs / float(r["factor"]) * (1 - smooth) + freqs * smooth
    return freqs.astype(np.float32)


def softmax_scale(cfg):
    m = Dims(cfg)
    scale = (m.dn + m.dr) ** -0.5
    if m.max_len > int(m.rope["original_max_position_embeddings"]):
        ms = 0.1 * float(m.rope["mscale"]) \
            * math.log(float(m.rope["factor"])) + 1.0
        scale *= ms * ms
    return scale


def rotate(x, pos, freqs, interleaved):
    """x (T, ..., R) at positions pos (T,): the pairs are (x0,x1),(x2,x3)
    when `interleaved`, else (x0,x[R/2]),(x1,x[R/2+1])."""
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2)
                      + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
    else:
        a, b = x[..., :half], x[..., half:]
    ra, rb = a * cos - b * sin, a * sin + b * cos
    if interleaved:
        return jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    return jnp.concatenate([ra, rb], axis=-1)


# ----------------------------------------------------- pieces of a layer
@functools.partial(jax.jit, static_argnames=("ck", "quant"))
def _latents(x, lp, freqs, ck, quant):
    """Everything of a layer's attention that is small per token: the
    query latent, the cached latent and rope key, the index key, the
    index-head weights — for every position."""
    m = Dims(ck)
    pos = jnp.arange(x.shape[0])
    xh = _rms(x, lp["attn_norm"], m.eps)
    c_q = _rms(_mm(xh, lp["wq_a"], quant), lp["q_norm"], m.eps)
    kv = _mm(xh, lp["wkv_a"], quant)
    c = _rms(kv[:, :m.kvr], lp["kv_norm"], m.eps)
    k_pe = rotate(kv[:, m.kvr:], pos, freqs, True)
    k_idx = _layer_norm(_mm(xh, lp["idx_wk"], quant), lp["idx_k_norm_g"],
                        lp["idx_k_norm_b"], m.eps)
    k_idx = jnp.concatenate([rotate(k_idx[:, :m.dr], pos, freqs, False),
                             k_idx[:, m.dr:]], axis=-1)
    w_idx = _mm(xh, lp["idx_w"], quant) * m.j ** -0.5 * m.di ** -0.5
    return c_q, c, k_pe, k_idx, w_idx


@functools.partial(jax.jit,
                   static_argnames=("ck", "quant", "dense", "width",
                                    "reach"))
def _select(c_q, w_idx, k_idx, row0, wq, freqs, ck, quant, dense, width,
            reach):
    """The selected positions of a block of queries (rows row0..) over
    the positions given (all that the block's last query can reach):
    (block, width) int32, -1 where fewer than index_topk are in reach
    (or `dense`: all -1, nothing is masked by the index)."""
    m = Dims(ck)
    k_idx = k_idx[:reach]
    n, t = c_q.shape[0], reach
    k = min(m.topk, t)
    if dense:
        return jnp.full((n, width), -1, jnp.int32)
    pos = row0 + jnp.arange(n)
    q = _mm(c_q, wq, quant).reshape(n, m.j, m.di)
    q = jnp.concatenate([rotate(q[..., :m.dr], pos, freqs, False),
                         q[..., m.dr:]], axis=-1)

    hb = min(HEAD_BLOCK, m.j)

    def heads(acc, jb):
        qb = jax.lax.dynamic_slice_in_dim(q, jb * hb, hb, 1)
        wb = jax.lax.dynamic_slice_in_dim(w_idx, jb * hb, hb, 1)
        s = jnp.einsum("njd,sd->njs", _q(qb, quant), _q(k_idx, quant),
                       precision=HI)
        return acc + jnp.sum(jax.nn.relu(s) * wb[..., None], axis=1), None

    assert m.j % hb == 0
    score, _ = jax.lax.scan(heads, jnp.zeros((n, t), jnp.float32),
                            jnp.arange(m.j // hb))
    reach = jnp.arange(t)[None, :] <= pos[:, None]
    score = jnp.where(reach, score, -jnp.inf)
    sel = jax.lax.top_k(score, k)[1].astype(jnp.int32)
    sel = jnp.where(sel <= pos[:, None], sel, -1)
    return jnp.pad(sel, ((0, 0), (0, width - k)), constant_values=-1)


@functools.partial(jax.jit, static_argnames=("ck", "quant"))
def _expand(c, k_pe, wkv_h, ck, quant):
    """One block of heads' keys and values for every position, from the
    cached latent: k = [c W_uk, k_pe] (T, heads, 192), v = c W_uv."""
    m = Dims(ck)
    t = c.shape[0]
    hb = wkv_h.shape[1] // (m.dn + m.dv)
    kv = _mm(c, wkv_h, quant).reshape(t, hb, m.dn + m.dv)
    k = jnp.concatenate(
        [kv[..., :m.dn], jnp.broadcast_to(k_pe[:, None], (t, hb, m.dr))],
        axis=-1)
    return k, kv[..., m.dn:]


@functools.partial(jax.jit,
                   static_argnames=("ck", "quant", "dense", "reach"))
def _attend(c_q, sel, k, v, row0, wq_h, wo_h, freqs, ck, quant, dense,
            reach):
    """One block of heads for one block of queries: the unabsorbed
    attention over the positions given (all that the block's last query
    can reach) with the index mask, through these heads' rows of W_o —
    (block, D), to be summed over head blocks."""
    m = Dims(ck)
    k, v = k[:reach], v[:reach]
    n, t = c_q.shape[0], reach
    hb = k.shape[1]
    pos = row0 + jnp.arange(n)
    q = _mm(c_q, wq_h, quant).reshape(n, hb, m.dn + m.dr)
    q = jnp.concatenate([q[..., :m.dn],
                         rotate(q[..., m.dn:], pos, freqs, True)], axis=-1)
    s = jnp.einsum("nhd,shd->hns", _q(q, quant), _q(k, quant),
                   precision=HI) * softmax_scale(ck)
    mask = jnp.arange(t)[None, :] <= pos[:, None]
    if not dense:
        picked = jnp.zeros((n, t + 1), bool).at[
            jnp.arange(n)[:, None],
            jnp.where((sel < 0) | (sel >= t), t, sel)].set(True)
        mask = mask & picked[:, :t]
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hns,shd->nhd", _q(p, quant), _q(v, quant),
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
    biased = s + _f32(bias)
    n = x.shape[0]
    groups = biased.reshape(n, m.groups, -1)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
    keep = jax.lax.top_k(group_score, m.keep)[1]
    kept = jnp.any(keep[..., None] == jnp.arange(m.groups), axis=1)
    biased = jnp.where(jnp.repeat(kept, groups.shape[-1], axis=1), biased,
                       -jnp.inf)
    chosen = jax.lax.top_k(biased, m.k)[1]
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen.astype(jnp.int32), \
        w / jnp.sum(w, axis=-1, keepdims=True) * m.route_scale


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


def _reach(end, t):
    """Positions a block of queries ending at `end` can reach, rounded
    up to few distinct sizes (each is a compiled shape): causal, so the
    rest of the padded sequence is never scored."""
    return min(t, -(-end // REACH_STEP) * REACH_STEP)


def _blocks(n, size):
    return [(a, min(size, n - a)) for a in range(0, n, size)]


def expert_layer(params, i, x, cfg, quant=None, share=None):
    """An expert layer's feed-forward for rows x (N, D), residual not
    added: the held experts' terms (`share` = (first, count) overrides
    the configuration's) plus the shared expert."""
    m = Dims(cfg)
    ck = _key(cfg)
    p = f"l{i}."
    first, held = share if share is not None else (m.first, m.held)
    chosen, weights = _route(x, params[p + "ffn_norm"], params[p + "gate"],
                             params[p + "gate_bias"], ck)
    y = _dense_ffn(x, params[p + "ffn_norm"], params[p + "shared_w1"],
                   params[p + "shared_w3"], params[p + "shared_w2"],
                   m.eps, quant)
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
    """x[at:at+len(rows)] += rows, in place on an accelerator (the
    residual stream is the one array of its size that is kept)."""
    return _add_rows_jit(jax.default_backend())(x, rows, at)


def forward(params, tokens, cfg, quant=None, dense=False, rows=None):
    """tokens (T,) int32 -> (logits (R, V) float32 of rows [r0, r0+R),
    r0, selected (layers, R, k)). `rows` = (r0, R) is the window the
    LAST layer is computed for (every earlier layer needs every
    position); None: all. T must divide into QUERY_BLOCK-sized blocks or
    be smaller than one."""
    m = Dims(cfg)
    ck = _key(cfg)
    freqs = jnp.asarray(yarn_freqs(cfg))
    t = int(tokens.shape[0])
    r0, nr = rows if rows is not None else (0, t)
    x = _f32(params["embed"])[jnp.asarray(tokens)]
    hb = min(HEAD_BLOCK, m.h)
    picks = []
    for i in range(m.layers):
        p = f"l{i}."
        lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
        last = i == m.layers - 1
        lo, n = (r0, nr) if last else (0, t)
        c_q, c, k_pe, k_idx, w_idx = _latents(x, lp, freqs, ck, quant)
        sel = jnp.concatenate([
            _select(c_q[lo + a:lo + a + b], w_idx[lo + a:lo + a + b],
                    k_idx, lo + a, lp["idx_wq_b"], freqs, ck, quant, dense,
                    width=min(m.topk, t), reach=_reach(lo + a + b, t))
            for a, b in _blocks(n, QUERY_BLOCK)])
        picks.append(sel if last else sel[r0:r0 + nr])
        wkv = lp["wkv_b"].reshape(m.kvr, m.h, m.dn + m.dv)
        wq = lp["wq_b"].reshape(m.qr, m.h, m.dn + m.dr)
        if last:
            x = x[lo:lo + n]
        for h0 in range(0, m.h, hb):
            k, v = _expand(c, k_pe, wkv[:, h0:h0 + hb].reshape(m.kvr, -1),
                           ck, quant)
            wq_h = wq[:, h0:h0 + hb].reshape(m.qr, -1)
            wo_h = lp["wo"][h0 * m.dv:(h0 + hb) * m.dv]
            for a, b in _blocks(n, QUERY_BLOCK):
                x = _add_rows(x, _attend(
                    c_q[lo + a:lo + a + b], sel[a:a + b], k, v, lo + a,
                    wq_h, wo_h, freqs, ck, quant, dense,
                    reach=_reach(lo + a + b, t)), a)
            del k, v
        del c_q, c, k_pe, k_idx, w_idx, sel
        for a, b in _blocks(n, ROW_BLOCK):
            xb = x[a:a + b]
            if i < m.dense:
                y = _dense_ffn(xb, lp["ffn_norm"], lp["w1"], lp["w3"],
                               lp["w2"], m.eps, quant)
            else:
                y = expert_layer(params, i, xb, cfg, quant)
            x = _add_rows(x, y, a)
    logits = _head(x, params["norm_f"], params["head"], m.eps, quant)
    return logits, r0, jnp.stack(picks)


def served_gaps(params, prompt, served, cfg, pad_to=256, control=False):
    """For one request: by how much each served token's reference logit
    lies below the reference's best at its position (0 where the served
    token IS the best). One pass over prompt + served tokens, padded to
    a multiple of `pad_to` (causal: the padding cannot reach the
    positions read). With `control` ("fp8", or True for it; "dense"; or
    a tuple of both, answered as a dict), also the same gap for the
    token that the control's forward puts first at each of those
    positions. Leaves in LAST what the reference
    selected for the query that chose the last served token."""
    with jax.default_matmul_precision("highest"):
        toks = list(prompt) + list(served)
        n = len(toks)
        width = -(-n // pad_to) * pad_to
        buf = np.zeros((width,), np.int32)
        buf[:n] = toks
        lo, hi = len(prompt) - 1, n - 1
        nr = min(width, WINDOW)
        r0 = max(0, min(lo, width - nr))
        if hi - r0 > nr:
            raise ValueError(f"{hi - lo} served tokens do not fit the "
                             f"reference's window of {nr} rows")
        lg, _, picks = forward(params, buf, cfg, rows=(r0, nr))
        lg = lg[lo - r0:hi - r0]
        want = jnp.asarray(buf[lo + 1:hi + 1])
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, want[:, None], axis=1)[:, 0]
        gap = np.asarray(best - got, np.float64)
        LAST["selected"] = np.asarray(picks[:, hi - 1 - r0])
        if not control:
            return gap, gap
        kinds = ("fp8",) if control is True else \
            (control,) if isinstance(control, str) else tuple(control)
        lows = {}
        for kind in kinds:
            low_lg, _, _ = forward(params, buf, cfg,
                                   quant="fp8" if kind == "fp8" else None,
                                   dense=kind == "dense", rows=(r0, nr))
            low = jnp.argmax(low_lg[lo - r0:hi - r0], axis=-1)
            low_logit = jnp.take_along_axis(lg, low[:, None], axis=1)[:, 0]
            lows[kind] = np.asarray(best - low_logit, np.float64)
        return gap, lows[kinds[0]] if len(kinds) == 1 else lows
