"""The trace reduction on a small recorded trace: busy union, idle gaps
named by host span, per-node kernel time, exposed collective time, and
the unwrapping of autodiff wrappers around a named_scope."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import trace_reduce as tr  # noqa: E402


def _reduced(name="small_trace.json"):
    with open(os.path.join(ROOT, "perfbench/selfcheck/data", name)) as f:
        rec = json.load(f)
    raw = {"devices": [{"name": d["name"],
                        "ops": [tuple(o) for o in d["ops"]],
                        "modules": [tuple(m) for m in d["modules"]]}
                       for d in rec["devices"]],
           "host": [tuple(h) for h in rec["host"]]}
    spans = [tuple(s) for s in rec["host_spans"]]
    return tr.Reduced(raw, spans, rec["t_open_host"], rec["t_close_host"])


def test_busy_union_and_window():
    red = _reduced()
    assert red.ok
    assert abs(red.window_s - 0.036) < 1e-9
    # three steps of 10 ms on each chip; overlapping ops counted once
    assert abs(red.busy[0] - 0.030) < 1e-9
    assert abs(red.busy_s - 0.030) < 1e-9
    assert abs(red.step_count() - 3.0) < 1e-9


def test_idle_gaps_named_by_host_span():
    red = _reduced()
    named, total, n = red.gaps_named()
    assert n == 3 and abs(total - 0.006) < 1e-9
    assert [g[0] for g in named] == ["fit.dispatch"] * 3
    assert all(abs(g[1] - 0.002) < 1e-9 for g in named)
    assert tr.name_gap((5.0, 5.1), [("x", 1.0, 2.0)]) == "none"


def test_per_node_kernel_time_is_the_mean_over_chips():
    red = _reduced()
    lab = red.labels()
    # chip 0: 3 x (4 + 3.4) ms; chip 1: 3 x 9 ms
    assert abs(lab["stage1_unit1_conv1"] - (0.0222 + 0.027) / 2) < 1e-9
    assert abs(lab["bn0"] - 0.006 / 2) < 1e-9
    assert red.top_ops(1)[0][0] == "stage1_unit1_conv1"


def test_exposed_collective_time():
    red = _reduced()
    assert red.saw_collective()
    # chip 0: 1 ms a step of which 0.4 ms lie under a convolution;
    # chip 1: 1 ms a step, all exposed
    assert abs(red.exposed_collective_seconds()
               - (3 * 0.0006 + 3 * 0.001) / 2) < 1e-9


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (1, 2), (1.5, 3.5)]) == [(1, 4)]
    assert tr.idle_gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]


def test_node_label_unwraps_autodiff():
    path = "jit(step)/jit(main)/transpose(jvp(stage2_unit1_conv1))/conv"
    assert tr.label_from_path(path) == "stage2_unit1_conv1"
    assert tr.label_from_path("jit(step)/bn0/mul") == "bn0"
    text = ('%fusion.7 = bf16[8,8]{1,0} fusion(%p0), kind=kOutput, '
            'metadata={op_name="' + path + '" source_file="x.py"}')
    assert tr.node_label(text) == "stage2_unit1_conv1"
    hlo = "HloModule m\n  ROOT " + text + "\n  %copy.1 = f32[2] copy(%p1)\n"
    assert tr.labels_from_hlo(hlo) == {"fusion.7": "stage2_unit1_conv1"}
    assert tr.node_label("%fusion.7 = bf16[8,8] fusion(...)",
                         {"fusion.7": "bn0"}) == "bn0"
    assert tr.node_label("%copy.1 = f32[2] copy(%p1)") == "copy.1"


def test_a_trace_without_marks_reads_nothing():
    red = tr.Reduced({"devices": [], "host": []}, [], 0.0, 1.0)
    assert not red.ok


def _gaps_named_every_gap(red, dev=0, top=10):
    """The routine as it stood before PR 28, kept as the reference: every
    gap named against every span, then the longest taken."""
    spans3 = [(n, a, b) for n, a, b, _ in red.spans]
    gaps = tr.idle_gaps(red.intervals(dev), red.lo, red.hi)
    named = [(tr.name_gap(g, spans3), g[1] - g[0]) for g in gaps]
    named.sort(key=lambda x: -x[1])
    return named[:top], sum(b - a for a, b in gaps), len(gaps)


def _busy_inside_every_interval(red, span_name, dev=0):
    iv = tr.merge(tr.clip(red.intervals(dev), red.lo, red.hi))
    return sum(tr.busy_seconds(iv, a, b) for n, a, b, _ in red.spans
               if n == span_name and a >= red.lo and b <= red.hi)


@pytest.mark.parametrize("name", ["small_trace.json",
                                  "small_decode_trace.json"])
def test_naming_only_the_longest_gaps_changes_nothing(name):
    red = _reduced(name)
    for top in (1, 3, 10):
        assert red.gaps_named(top=top) == _gaps_named_every_gap(red, top=top)
    assert red.breakdown()["idle_gaps"] \
        == [[k, v] for k, v in _gaps_named_every_gap(red)[0]]
    for span_name in {s[0] for s in red.spans}:
        busy, _spans = red.busy_inside(span_name)
        assert busy == _busy_inside_every_interval(red, span_name)


def test_the_reduction_does_not_grow_with_the_square_of_the_steps():
    """20,000 device operations with a gap after each and 20,000 host
    spans, as a window of short steps holds them: the breakdown's gaps
    and the busy time inside the spans come in seconds (every gap
    against every span took minutes)."""
    n = 20000
    ops = [("op", "op", i * 1e-3, i * 1e-3 + 0.6e-3 + (i % 7) * 1e-5)
           for i in range(n)]
    raw = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                        "modules": []}],
           "host": [(tr.SYNC_A, 0.0, 0.0), (tr.SYNC_B, n * 1e-3, n * 1e-3)]}
    spans = [("decoding.step" if i % 2 else "decoding.admit",
              i * 1e-3 + 0.5e-3, (i + 1) * 1e-3 + 0.1e-3, None)
             for i in range(n)]
    red = tr.Reduced(raw, spans, 0.0, n * 1e-3)
    t = time.perf_counter()
    named, total, count = red.gaps_named()
    busy, inside = red.busy_inside("decoding.step")
    took = time.perf_counter() - t
    assert count == n and len(named) == 10 and len(inside) == n // 2 - 1
    # the longest gaps follow the shortest operations (i % 7 == 0), and
    # most of such a gap lies under the span that began in that operation
    assert all(abs(g - 0.4e-3) < 1e-9 for _name, g in named)
    assert {name for name, _g in named} == {"decoding.admit",
                                            "decoding.step"}
    assert abs(total - sum(0.4e-3 - (i % 7) * 1e-5 for i in range(n))) < 1e-6
    assert busy > 0
    assert took < 2.0, took
