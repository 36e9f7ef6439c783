"""The readers of the sparse latent block's scopes and counters on the
small hand-made trace with planted scope maps and counters: each returns
the number worked out by hand, and None (never 0) where its scope is
missing or its counter is zero or absent, as on a program that has
neither. The costs at the published sizes are the issue's arithmetic."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common  # noqa: E402
from perfbench.harness import costs_sparse_latent as costs  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402

READERS = ("serve_mfu.sparse_latent", "dsa_index_roofline",
           "sparse_attn_roofline", "moe_experts_roofline",
           "moe_expert_rows_max_over_mean")
# every step of the trace: fusion.1 10 ms, copy.1 20, fusion.2 20,
# fusion.3 20, an unplaced custom call 10
SCOPES = {"jit_decode_p4": {"fusion.1": "l0/index/top_k",
                            "copy.1": "l0/kv_write",
                            "fusion.2": "l1/attn/bthw,btkw->bthk",
                            "fusion.3": "l1/experts"},
          "jit_prefill_t16": {"fusion.1": "l0/attn"}}
CONFIG = {"hidden_size": 64, "num_attention_heads": 4,
          "num_hidden_layers": 3, "first_k_dense_replace": 1,
          "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 8,
          "index_head_dim": 16, "intermediate_size": 128,
          "moe_intermediate_size": 32, "n_routed_experts": 16,
          "vocab_size": 96, "kv_dtype": "bf16", "weights_dtype": "bfloat16"}
COUNTERS = {"selected_tokens": 64, "expert_rows": 6, "experts_hit": 3,
            "expert_rows_max": 4}


def _reader(name):
    return common.load_py(
        os.path.join(ROOT, "perfbench/metrics", name + ".py"),
        "selfcheck_metric_" + name.replace(".", "_"))


def _facts(scope_maps=SCOPES, counters=COUNTERS, more_spans=()):
    with open(os.path.join(ROOT, "perfbench/selfcheck/data",
                           "small_decode_trace.json")) as f:
        rec = json.load(f)
    raw = {"devices": [{"name": d["name"],
                        "ops": [tuple(o) for o in d["ops"]],
                        "modules": [tuple(m) for m in d["modules"]]}
                       for d in rec["devices"]],
           "host": [tuple(h) for h in rec["host"]]}
    host_spans = []
    for name, t0, t1, attrs in rec["host_spans"]:
        if name == "decoding.step":
            attrs = dict(attrs, **counters)
        host_spans.append((name, t0, t1, attrs))
    host_spans += list(more_spans)
    red = tr.Reduced(raw, host_spans, rec["t_open_host"],
                     rec["t_close_host"])
    assert red.ok
    return {"config": CONFIG, "peaks": rec["peaks"], "chips": 1,
            "trace": red, "spans": host_spans, "tokens": 12,
            "window_host": (rec["t_open_host"], rec["t_close_host"]),
            "scope_maps": (scope_maps or {}).get if scope_maps != "none"
            else (lambda module: None)}


def test_rooflines_by_hand():
    facts = _facts()
    bw, peak = 819e9, 197e12
    # index: (1000 + 2000 + 3000) context tokens x 16 x 2 B x 3 layers
    # over the bandwidth (its FLOPs, 2 x 8 x 16 a token a layer, are
    # less), against 3 x 10 ms
    want = 100.0 * (6000 * 16 * 2 * 3 / bw) / 0.030
    assert _reader("dsa_index_roofline").read(facts) == pytest.approx(want)
    # attn: 3 x 64 selected tokens x (16 + 8) x 2 B x 3 layers, or 2 x 4
    # heads x (24 + 16) FLOPs each if larger, against 3 x 20 ms
    floor = max(64 * 24 * 2 * 3 / bw, 2 * 4 * 40 * 64 * 3 / peak)
    assert _reader("sparse_attn_roofline").read(facts) \
        == pytest.approx(100.0 * 3 * floor / 0.060)
    # experts: 3 experts hit x 3 x 64 x 32 x 2 B against 20 ms a step
    one = 3 * 64 * 32
    assert costs.expert_params(CONFIG) == one
    assert _reader("moe_experts_roofline").read(facts) \
        == pytest.approx(100.0 * (3 * one * 2 / bw) / 0.020)
    # the busiest expert's 4 rows over 6 / 3 a hit expert
    assert _reader("moe_expert_rows_max_over_mean").read(facts) \
        == pytest.approx(2.0)


def test_whole_step_share_counts_trunk_head_and_routed_experts():
    fill = [("decoding.prefill", 10.094, 10.107,
             {"tokens": 12, "cached_tokens": 2, "expert_rows": 5})]
    facts = _facts(more_spans=fill)
    # 10 prompt tokens + 12 delivered through the trunk, 12 + 1 sampled
    # rows through the head, 3 steps x 6 + 5 assignments through experts
    flops = 2.0 * (costs.trunk_matmul_params(CONFIG) * 22
                   + costs.head_params(CONFIG) * 13
                   + costs.expert_params(CONFIG) * 23)
    assert costs.step_flops(CONFIG, 22, 13, 23) == flops
    got = _reader("serve_mfu.sparse_latent").read(facts)
    assert got == pytest.approx(
        100.0 * flops / facts["trace"].window_s / 197e12)
    assert 0 < got < 100


@pytest.mark.parametrize("name", READERS)
def test_no_counter_reads_nothing(name):
    """A program whose spans carry no such counter (the parent commit, a
    dense decoder): every reader leaves its metric out."""
    assert _reader(name).read(_facts(counters={})) is None


@pytest.mark.parametrize("name,zeroed", [
    ("dsa_index_roofline", "selected_tokens"),
    ("sparse_attn_roofline", "selected_tokens"),
    ("moe_experts_roofline", "experts_hit"),
    ("moe_experts_roofline", "expert_rows"),
    ("moe_expert_rows_max_over_mean", "expert_rows_max"),
    ("moe_expert_rows_max_over_mean", "experts_hit")])
def test_a_zero_counter_reads_nothing(name, zeroed):
    facts = _facts(counters=dict(COUNTERS, **{zeroed: 0}))
    assert _reader(name).read(facts) is None


@pytest.mark.parametrize("name,scope", [
    ("dsa_index_roofline", "fusion.1"),
    ("sparse_attn_roofline", "fusion.2"),
    ("moe_experts_roofline", "fusion.3")])
def test_a_missing_scope_reads_nothing(name, scope):
    maps = {"jit_decode_p4": {k: v for k, v in
                              SCOPES["jit_decode_p4"].items()
                              if k != scope}}
    assert _reader(name).read(_facts(scope_maps=maps)) is None
    assert _reader(name).read(_facts(scope_maps="none")) is None


def test_costs_at_the_published_sizes():
    cfg = common.load_json(os.path.join(
        ROOT, "perfbench/configs/deepseek_v32_ep16.json"))
    assert costs.expert_params(cfg) == 3 * 7168 * 2048      # 44.04M
    assert round(costs.layer_matmul_params(cfg) / 1e6, 2) \
        == round(187.105280 + 13.959168, 2)
    # per expert layer outside the routed experts: 246.9M
    per_layer = costs.layer_matmul_params(cfg) + 7168 * 256 \
        + costs.expert_params(cfg)
    assert round(per_layer / 1e6, 1) == 246.9
    assert costs.trunk_matmul_params(cfg) == 5 * costs.layer_matmul_params(
        cfg) + 3 * 7168 * 18432 + 4 * (7168 * 256 + 3 * 7168 * 2048)
    assert costs.head_params(cfg) == 7168 * 16160
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # one context token: 256 B of index key a layer against 16384 FLOPs
    assert costs.index_floor_s(cfg, peaks, 1) == pytest.approx(
        max(5 * 256 / 819e9, 5 * 2 * 64 * 128 / 197e12))
    # one selected token: 1152 B a layer against 2 x 128 x 1088 FLOPs
    assert costs.attn_floor_s(cfg, peaks, 1) == pytest.approx(
        max(5 * 1152 / 819e9, 5 * 2 * 128 * 1088 / 197e12))
    assert costs.experts_floor_s(cfg, peaks, 1, 1) == pytest.approx(
        88080384 / 819e9)
