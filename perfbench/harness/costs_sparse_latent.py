"""Operations and bytes of the sparse latent decoder from its shapes,
and the device time of its named scopes: what `serve_mfu.sparse_latent`,
`dsa_index_roofline`, `sparse_attn_roofline` and `moe_experts_roofline`
are computed from. Everything is per chip: the experts and the
vocabulary are the share the configuration holds."""
import bisect

from perfbench.harness import scopes


def _i(cfg, k):
    return int(cfg[k])


def expert_params(cfg):
    """One routed (or the shared) expert: three matrices."""
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "moe_intermediate_size")


def layer_matmul_params(cfg):
    """Matrix parameters every token passes in ONE layer outside the
    feed-forward: the latent projections, the indexer's, the output."""
    d, h = _i(cfg, "hidden_size"), _i(cfg, "num_attention_heads")
    qr, kvr = _i(cfg, "q_lora_rank"), _i(cfg, "kv_lora_rank")
    dn, dr = _i(cfg, "qk_nope_head_dim"), _i(cfg, "qk_rope_head_dim")
    dv = _i(cfg, "v_head_dim")
    attn = d * qr + qr * h * (dn + dr) + d * (kvr + dr) \
        + kvr * h * (dn + dv) + h * dv * d
    index = qr * _i(cfg, "index_n_heads") * _i(cfg, "index_head_dim") \
        + d * _i(cfg, "index_head_dim") + d * _i(cfg, "index_n_heads")
    return attn + index


def trunk_matmul_params(cfg):
    """Matrix parameters a token passes on this chip whatever it is
    routed to: every layer's projections, the dense layers' feed-forward,
    the expert layers' router and shared expert. The routed experts are
    counted from the counter, the head for a sampled row only."""
    d = _i(cfg, "hidden_size")
    n, dense = _i(cfg, "num_hidden_layers"), _i(cfg, "first_k_dense_replace")
    return n * layer_matmul_params(cfg) \
        + dense * 3 * d * _i(cfg, "intermediate_size") \
        + (n - dense) * (d * _i(cfg, "n_routed_experts")
                         + expert_params(cfg))


def head_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "vocab_size")


def step_flops(cfg, tokens, sampled_rows, expert_rows):
    """2 FLOPs a multiply-add: `tokens` through the trunk, `sampled_rows`
    through the head, `expert_rows` assignments through a held expert.
    Attention's own score and value products are left out, as in
    `costs.decoder_flops_per_token`."""
    return 2.0 * (trunk_matmul_params(cfg) * tokens
                  + head_params(cfg) * sampled_rows
                  + expert_params(cfg) * expert_rows)


def _pool_itemsize(cfg):
    return {"bf16": 2, "bfloat16": 2, "float32": 4}[str(cfg["kv_dtype"])]


def index_floor_s(cfg, peaks, ctx_tokens):
    """Least seconds to score `ctx_tokens` context positions in every
    layer: their index keys read once, or the index products computed."""
    layers = _i(cfg, "num_hidden_layers")
    width, heads = _i(cfg, "index_head_dim"), _i(cfg, "index_n_heads")
    nbytes = ctx_tokens * width * _pool_itemsize(cfg) * layers
    flops = 2.0 * heads * width * ctx_tokens * layers
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def attn_floor_s(cfg, peaks, selected_tokens):
    """Least seconds to attend `selected_tokens` (query, selected row)
    pairs in every layer: the latent rows read once each, or the score
    over the whole row and the value over its latent part, all heads."""
    layers = _i(cfg, "num_hidden_layers")
    row = _i(cfg, "kv_lora_rank") + _i(cfg, "qk_rope_head_dim")
    nbytes = selected_tokens * row * _pool_itemsize(cfg) * layers
    flops = 2.0 * _i(cfg, "num_attention_heads") \
        * (row + _i(cfg, "kv_lora_rank")) * selected_tokens * layers
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def experts_floor_s(cfg, peaks, experts_hit, expert_rows):
    """Least seconds for the routed experts of a step, all expert layers
    (both counters are sums over them): each expert that was hit read
    once, or the assignments' products."""
    itemsize = {"bfloat16": 2, "float32": 4}[str(cfg["weights_dtype"])]
    nbytes = experts_hit * expert_params(cfg) * itemsize
    flops = 2.0 * expert_params(cfg) * expert_rows
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def scope_seconds_per_step(facts, name):
    """[(device seconds under the scope `name`, the step's attrs)] for
    every `decoding.step` span of the traced window: operations of the
    step's own program whose scope path has `name` as a segment
    ('l3/index/...'). None where the program keeps no scope maps, names
    no such scope in any step, or its steps carry no counters."""
    ops, steps = scopes.scoped_ops(facts), scopes.decode_steps(facts)
    if not ops or not steps:
        return None
    ops = sorted(ops, key=lambda o: o[1])
    starts = [o[1] for o in ops]
    out, named = [], False
    for a, b, attrs in steps:
        total = 0.0
        for scope, t0, t1, module in ops[bisect.bisect_left(starts, a):
                                         bisect.bisect_left(starts, b)]:
            if module != attrs["program"] or not scope:
                continue
            if name in scope.split("/"):
                total += min(t1, b) - t0
                named = True
        out.append((total, attrs))
    return out if named else None


def roofline(facts, scope, floor_of):
    """100 x sum of floors over sum of the scope's device time, over the
    traced steps whose attrs give `floor_of` something to count (it
    returns None where a counter is missing or zero)."""
    per_step = scope_seconds_per_step(facts, scope)
    if not per_step:
        return None
    floor = measured = 0.0
    for seconds, attrs in per_step:
        f = floor_of(attrs)
        if f is None or seconds <= 0.0:
            continue
        floor += f
        measured += seconds
    return 100.0 * floor / measured if measured > 0.0 else None
