"""Operations and bytes of the window-mixed decoder from its shapes:
what `serve_mfu.window_mixed`, `gqa_attn_roofline`,
`window_attn_roofline` and `kv_pool_bytes_per_ctx_token` are computed
from. Everything is per chip: the experts and the vocabulary are the
share the configuration holds; the layers are the first
`num_hidden_layers` entries of the two patterns."""
from perfbench.harness import costs_sparse_latent

# the block's scopes that the reader of PR 25 puts under `other`
OWN_SCOPES = ("attn_window", "router", "experts")


def _i(cfg, k):
    return int(cfg[k])


def layer_kinds(cfg):
    """[(windowed, routed)] of the layers that are served."""
    n = _i(cfg, "num_hidden_layers")
    return list(zip((int(x) for x in cfg["hybrid_layer_pattern"][:n]),
                    (int(x) for x in cfg["moe_layer_freq"][:n])))


def layers_of(cfg, windowed):
    """How many of the served layers are of a kind."""
    return sum(1 for w, _ in layer_kinds(cfg) if bool(w) == bool(windowed))


def kv_heads(cfg, windowed):
    return _i(cfg, "swa_num_key_value_heads" if windowed
              else "num_key_value_heads")


def expert_params(cfg):
    """One routed expert: three matrices."""
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "moe_intermediate_size")


def attn_params(cfg, windowed):
    """The fused projection and the output matrix of one layer."""
    d, h = _i(cfg, "hidden_size"), _i(cfg, "num_attention_heads")
    dk, dv = _i(cfg, "head_dim"), _i(cfg, "v_head_dim")
    kv = kv_heads(cfg, windowed)
    return d * ((h + kv) * dk + kv * dv) + h * dv * d


def trunk_matmul_params(cfg):
    """Matrix parameters a token passes on this chip whatever it is
    routed to: every layer's projections, the dense layers'
    feed-forward, the expert layers' router. The routed experts are
    counted from the counter, the head for a sampled row only."""
    d = _i(cfg, "hidden_size")
    total = 0
    for windowed, routed in layer_kinds(cfg):
        total += attn_params(cfg, windowed)
        total += d * _i(cfg, "n_routed_experts") if routed \
            else 3 * d * _i(cfg, "intermediate_size")
    return total


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


def kv_bytes_per_token_layer(cfg, windowed):
    """Bytes of K and V one position holds in one layer of a kind."""
    return kv_heads(cfg, windowed) * (
        _i(cfg, "head_dim") + _i(cfg, "v_head_dim")) * _pool_itemsize(cfg)


def group_bytes_per_token(cfg, windowed):
    """Bytes one position holds over all layers of a kind: what a slot
    of a page of that kind's page group stores."""
    return layers_of(cfg, windowed) * kv_bytes_per_token_layer(cfg, windowed)


def attn_floor_s(cfg, peaks, tokens, windowed):
    """Least seconds to attend `tokens` (query row, position in reach)
    pairs in every layer of a kind: their K and V rows read once, or
    the score and value products of all query heads if that is more."""
    layers = layers_of(cfg, windowed)
    nbytes = tokens * layers * kv_bytes_per_token_layer(cfg, windowed)
    flops = 2.0 * _i(cfg, "num_attention_heads") * (
        _i(cfg, "head_dim") + _i(cfg, "v_head_dim")) * tokens * layers
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def note_own_scopes(facts):
    """Leave in the run's log the device milliseconds a decode step
    spends under each scope of `OWN_SCOPES`: `decode_step_parts_ms`
    gives them as one `other`."""
    ms = {}
    for name in OWN_SCOPES:
        per_step = costs_sparse_latent.scope_seconds_per_step(facts, name)
        if per_step:
            ms[name] = round(sum(sec for sec, _ in per_step)
                             / len(per_step) * 1e3, 4)
    if ms:
        facts.setdefault("notes", {})["window_mixed_other_ms"] = str(ms)
