"""The readers of the window-mixed block's scopes, attrs and counters on
the small hand-made trace with planted scope maps and attrs: each
returns the number worked out by hand, and None (never 0) where its
scope is missing or its attr is zero or absent, as on a program that
has neither (the parent commit, the other decoders). The costs at the
published sizes are the issue's arithmetic."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common  # noqa: E402
from perfbench.harness import costs_window_mixed as costs  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402

READERS = ("serve_mfu.window_mixed", "gqa_attn_roofline",
           "window_attn_roofline", "kv_pool_bytes_per_ctx_token")
# every step of the trace: fusion.1 10 ms, copy.1 20, fusion.2 20,
# fusion.3 20, an unplaced custom call 10
SCOPES = {"jit_decode_p4": {"fusion.1": "l0/attn/paged_attention",
                            "copy.1": "l0/kv_write",
                            "fusion.2": "l1/attn_window/paged_attention",
                            "fusion.3": "l1/experts"}}
CONFIG = common.load_json(os.path.join(
    ROOT, "perfbench/selfcheck/tiny/mimo_v25_ep16.json"))
# what a step's span carries beside `ctx_tokens` (1000, 2000, 3000)
ATTRS = {"window_tokens": 32, "pages_held": [300, 12], "expert_rows": 6,
         "experts_hit": 3, "expert_rows_max": 4}


def _reader(name):
    return common.load_py(
        os.path.join(ROOT, "perfbench/metrics", name + ".py"),
        "selfcheck_metric_" + name.replace(".", "_"))


def _facts(scope_maps=SCOPES, attrs=ATTRS, more_spans=()):
    with open(os.path.join(ROOT, "perfbench/selfcheck/data",
                           "small_decode_trace.json")) as f:
        rec = json.load(f)
    raw = {"devices": [{"name": d["name"],
                        "ops": [tuple(o) for o in d["ops"]],
                        "modules": [tuple(m) for m in d["modules"]]}
                       for d in rec["devices"]],
           "host": [tuple(h) for h in rec["host"]]}
    host_spans = [(n, t0, t1, dict(a, **attrs) if n == "decoding.step"
                   else a) for n, t0, t1, a in rec["host_spans"]]
    host_spans += list(more_spans)
    red = tr.Reduced(raw, host_spans, rec["t_open_host"],
                     rec["t_close_host"])
    assert red.ok
    return {"config": CONFIG, "peaks": rec["peaks"], "chips": 1,
            "trace": red, "spans": host_spans, "tokens": 12,
            "window_host": (rec["t_open_host"], rec["t_close_host"]),
            "scope_maps": (scope_maps or {}).get if scope_maps != "none"
            else (lambda module: None)}


def test_rooflines_and_pool_bytes_by_hand():
    facts = _facts()
    bw, peak = 819e9, 197e12
    # the toy layers: 2 full (2 KV heads of 12 + 8, float32) and 5 window
    # (4 KV heads): 160 B and 320 B a token a layer
    assert costs.group_bytes_per_token(CONFIG, False) == 2 * 2 * 20 * 4
    assert costs.group_bytes_per_token(CONFIG, True) == 5 * 4 * 20 * 4
    # full: (1000 + 2000 + 3000) context tokens x 320 B, or 2 x 16
    # heads x 20 FLOPs x 2 layers a token if larger, against 3 x 10 ms
    per_tok = max(320 / bw, 2 * 16 * 20 * 2 / peak)
    assert _reader("gqa_attn_roofline").read(facts) == pytest.approx(
        100.0 * 6000 * per_tok / 0.030)
    # window: 3 x 32 tokens in reach x 1600 B against 3 x 20 ms
    per_tok = max(1600 / bw, 2 * 16 * 20 * 5 / peak)
    assert _reader("window_attn_roofline").read(facts) == pytest.approx(
        100.0 * 96 * per_tok / 0.060)
    # pool: (300 x 320 + 12 x 1600) B x 4 slots a page over the step's
    # context tokens, mean over the three steps
    held = (300 * 320 + 12 * 1600) * 4
    assert _reader("kv_pool_bytes_per_ctx_token").read(facts) \
        == pytest.approx((held / 1000 + held / 2000 + held / 3000) / 3)


def test_whole_step_share_counts_trunk_head_and_routed_experts():
    fill = [("decoding.prefill", 10.094, 10.107,
             {"tokens": 12, "cached_tokens": 0, "expert_rows": 5})]
    facts = _facts(more_spans=fill)
    # 12 prompt tokens + 12 delivered through the trunk, 12 + 1 sampled
    # rows through the head, 3 steps x 6 + 5 assignments through experts
    flops = 2.0 * (costs.trunk_matmul_params(CONFIG) * 24
                   + costs.head_params(CONFIG) * 13
                   + costs.expert_params(CONFIG) * 23)
    got = _reader("serve_mfu.window_mixed").read(facts)
    assert got == pytest.approx(
        100.0 * flops / facts["trace"].window_s / 197e12)
    assert 0 < got < 100
    # and says what the reader of PR 25 lumps under `other`
    assert facts["notes"]["window_mixed_other_ms"] == str(
        {"attn_window": 20.0, "experts": 20.0})


@pytest.mark.parametrize("name", READERS)
def test_no_attr_reads_nothing(name):
    """A program whose spans carry none of the attrs (the parent commit;
    a dense decoder has `ctx_tokens` and an `attn` scope, but no reader
    of this block is listed for its cells)."""
    facts = _facts(attrs={}, scope_maps={"jit_decode_p4": {
        "copy.1": "l0/kv_write"}})
    assert _reader(name).read(facts) is None


@pytest.mark.parametrize("name,zeroed", [
    ("window_attn_roofline", {"window_tokens": 0}),
    ("kv_pool_bytes_per_ctx_token", {"pages_held": [300]}),
    ("serve_mfu.window_mixed", None)])
def test_a_missing_or_zero_attr_reads_nothing(name, zeroed):
    attrs = dict(ATTRS, **zeroed) if zeroed else {
        k: v for k, v in ATTRS.items() if k != "expert_rows"}
    assert _reader(name).read(_facts(attrs=attrs)) is None


@pytest.mark.parametrize("name,scope", [
    ("gqa_attn_roofline", "fusion.1"),
    ("window_attn_roofline", "fusion.2")])
def test_a_missing_scope_reads_nothing(name, scope):
    maps = {"jit_decode_p4": {k: v for k, v in
                              SCOPES["jit_decode_p4"].items()
                              if k != scope}}
    assert _reader(name).read(_facts(scope_maps=maps)) is None
    assert _reader(name).read(_facts(scope_maps="none")) is None


def test_the_window_scope_is_not_the_full_layers_scope():
    """`attn_window` is a scope of its own: the full layers' reader does
    not count the window kernel's time, nor the other way round."""
    only_window = {"jit_decode_p4": {
        "fusion.2": "l1/attn_window/paged_attention"}}
    assert _reader("gqa_attn_roofline").read(
        _facts(scope_maps=only_window)) is None
    assert _reader("window_attn_roofline").read(
        _facts(scope_maps=only_window)) is not None


def test_costs_at_the_published_sizes():
    cfg = common.load_json(os.path.join(
        ROOT, "perfbench/configs/mimo_v25_ep16.json"))
    assert costs.layer_kinds(cfg) == [
        (0, 0), (1, 1), (1, 1), (1, 1), (1, 1), (0, 1), (1, 1)]
    assert costs.expert_params(cfg) == 3 * 4096 * 2048      # 25.17M
    assert round(costs.attn_params(cfg, True) / 1e6, 2) == 94.37
    assert round(costs.attn_params(cfg, False) / 1e6, 2) == 89.13
    assert costs.trunk_matmul_params(cfg) == (
        5 * costs.attn_params(cfg, True) + 2 * costs.attn_params(cfg, False)
        + 3 * 4096 * 16384 + 6 * 4096 * 256)
    assert costs.head_params(cfg) == 4096 * 19072
    # the chip's share: 290.46 + 5 x 498.07 + 492.83 + 156.24 = 3430M
    held = costs.trunk_matmul_params(cfg) + 6 * 16 * costs.expert_params(
        cfg) + 2 * costs.head_params(cfg)
    assert round(held / 1e6) == 3430
    assert costs.kv_bytes_per_token_layer(cfg, False) == 2560
    assert costs.kv_bytes_per_token_layer(cfg, True) == 5120
    assert costs.group_bytes_per_token(cfg, False) == 5120
    assert costs.group_bytes_per_token(cfg, True) == 25600
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # one context token: 5120 B over the full layers against 2 x 64 x
    # 320 FLOPs a layer: the bytes bound
    assert costs.attn_floor_s(cfg, peaks, 1, False) == pytest.approx(
        5120 / 819e9)
    assert costs.attn_floor_s(cfg, peaks, 1, True) == pytest.approx(
        25600 / 819e9)
