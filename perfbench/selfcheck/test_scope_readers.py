"""The readers of the program's named scopes and leaf spans (PR 25) on a
small hand-made trace with planted scope maps: each returns the number
worked out by hand, and None (never 0) where its scope or span is
missing, as on a program that has neither."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common, scopes  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402


def _reader(name):
    return common.load_py(
        os.path.join(ROOT, "perfbench/metrics", name + ".py"),
        "selfcheck_metric_" + name.replace(".", "_"))


def _facts(scope_maps="planted", spans=True, more_spans=()):
    with open(os.path.join(ROOT, "perfbench/selfcheck/data",
                           "small_decode_trace.json")) as f:
        rec = json.load(f)
    raw = {"devices": [{"name": d["name"],
                        "ops": [tuple(o) for o in d["ops"]],
                        "modules": [tuple(m) for m in d["modules"]]}
                       for d in rec["devices"]],
           "host": [tuple(h) for h in rec["host"]]}
    host_spans = [tuple(s) for s in rec["host_spans"]] if spans else []
    host_spans += list(more_spans)
    red = tr.Reduced(raw, host_spans, rec["t_open_host"],
                     rec["t_close_host"])
    assert red.ok
    facts = {"config": rec["config"], "peaks": rec["peaks"], "chips": 1,
             "trace": red, "spans": host_spans,
             "window_host": (rec["t_open_host"], rec["t_close_host"])}
    if scope_maps == "planted":
        facts["scope_maps"] = rec["scope_maps"].get
    elif scope_maps == "none":           # the module has no map
        facts["scope_maps"] = lambda module: None
    return facts


def test_an_operation_takes_the_scope_of_the_launch_that_covers_it():
    ops = scopes.scoped_ops(_facts())
    # `fusion.1` is l0/qkv in the decode program and l0/attn in the
    # prefill program: the covering launch decides
    by_start = {round(t0, 3): (scope, module) for scope, t0, _, module in ops}
    assert by_start[1.010] == ("l0/qkv", "jit_decode_p4")
    assert by_start[1.095] == ("l0/attn", "jit_prefill_t16")
    assert by_start[1.080] == ("unscoped", "jit_decode_p4")
    assert scopes.part_of("l3/attn/bhd,bthd->bht") == "attn"
    assert scopes.part_of("draft0/l1/kv_write") == "kv_write"
    assert scopes.part_of("copy_page") is None


def test_device_time_by_part():
    facts = _facts()
    # every step: qkv 10, kv_write 20 (a copy the map places), attn 20,
    # mlp 20, one unplaced custom call 10 = 80 ms
    assert _reader("kv_write_device_ms_per_step").read(facts) \
        == pytest.approx(20.0)
    assert _reader("decode_unscoped_device_share").read(facts) \
        == pytest.approx(12.5)
    assert "sum 80.0000 over 3 steps" in \
        facts["notes"]["decode_step_parts_ms"]
    # floors: (1000 + 2000 + 3000) context tokens x 2 layers x K and V x
    # 64 x 2 B = 512 B a token over 819 GB/s, against 3 x 20 ms of attn
    want = 100.0 * (6000 * 512 / 819e9) / 0.060
    assert _reader("paged_attn_roofline").read(facts) \
        == pytest.approx(want)
    assert facts["notes"]["paged_attn_roofline"] == "memory-bound"


def test_int8_pool_counts_its_scale_planes():
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_hidden_layers": 2, "kv_dtype": "int8"}
    assert scopes.kv_bytes_per_context_token(cfg) == 2 * 2 * (64 + 4 * 4)
    assert scopes.attn_flops_per_context_token(cfg) == 4 * 64 * 2


def test_program_temporaries_come_from_the_record_table(monkeypatch):
    from mxnet_tpu import profiling

    monkeypatch.setattr(
        profiling, "records_for",
        lambda **kw: [{"module": "jit_prefill_t16", "temp_bytes": 9 << 30},
                      {"module": "jit_decode_p4", "temp_bytes": 3 << 30}])
    assert _reader("decode_program_temp_gib").read(_facts()) == 3.0
    monkeypatch.setattr(profiling, "records_for", lambda **kw: [])
    assert _reader("decode_program_temp_gib").read(_facts()) is None


def test_host_spans_outside_and_inside_the_capture():
    facts = _facts()
    # outside: the turns that ended a second before the first device
    # event, the first left out: launch 5 + fetch 80.5 less 80 busy;
    # inside: four launches of 7 ms that hold 2 ms of device work each
    # and three fetches of 81 ms that hold 78 (the window's close cuts
    # the fourth fetch, so the two kinds are averaged apart)
    assert _reader("engine_host_ms_per_step").read(facts) \
        == pytest.approx(5.5)
    assert "inside it 8.0000 ms" in facts["notes"]["engine_host_ms_per_step"]
    assert _reader("emit_host_ms_per_step").read(facts) \
        == pytest.approx(4.0)
    assert "inside it 6.0000 ms" in facts["notes"]["emit_host_ms_per_step"]


def test_fit_host_work_is_dispatch_less_its_wait():
    facts = _facts()
    # outside (first left out): (10 - 7) and (12 - 6) ms; inside: (10 - 7)
    # ms of one step and (20 - 10) ms of a dispatch of two steps
    assert _reader("fit_host_work_ms_per_step").read(facts) \
        == pytest.approx(4.5)
    assert "inside it 4.3333 ms" in \
        facts["notes"]["fit_host_work_ms_per_step"]
    assert "mean 60.0 over 5 waits" in \
        facts["notes"]["fit_window_wait_fetch_us"]


@pytest.mark.parametrize("name", [
    "paged_attn_roofline", "kv_write_device_ms_per_step",
    "decode_unscoped_device_share"])
def test_no_scope_map_reads_nothing(name):
    assert _reader(name).read(_facts(scope_maps="none")) is None


@pytest.mark.parametrize("name", [
    "paged_attn_roofline", "kv_write_device_ms_per_step",
    "decode_unscoped_device_share", "decode_program_temp_gib",
    "engine_host_ms_per_step", "emit_host_ms_per_step",
    "fit_host_work_ms_per_step"])
def test_no_span_reads_nothing(name):
    assert _reader(name).read(_facts(spans=False)) is None


def test_a_scope_the_steps_never_name_reads_nothing():
    facts = _facts()
    facts["scope_maps"] = lambda module: {"fusion.1": "l0/qkv"}
    assert _reader("kv_write_device_ms_per_step").read(facts) is None
    assert _reader("paged_attn_roofline").read(facts) is None


def test_prefill_device_time_per_thousand_prompt_tokens():
    # the trace's one prefill launch (10 ms of device time) under a
    # `decoding.prefill` span of 13 ms that prefilled 12 tokens, 2 of
    # them from cached pages; a span the window's close cuts is left out
    fill = [("decoding.prefill", 10.094, 10.107,
             {"tokens": 12, "cached_tokens": 2}),
            ("decoding.prefill", 10.296, 10.31, {"tokens": 500})]
    facts = _facts(more_spans=fill)
    assert _reader("prefill_device_ms_per_ktok").read(facts) \
        == pytest.approx(10.0 / 10 * 1000)
    assert _reader("prefill_device_ms_per_ktok").read(_facts()) is None


def test_first_token_time_is_the_clients_median():
    facts = dict(_facts(), ttft=[0.1, 0.3, 0.2])
    assert _reader("ttft_p50_ms.serve").read(facts) == pytest.approx(200.0)
    assert _reader("ttft_p50_ms.serve").read(dict(facts, ttft=[])) is None
