"""The trace reduction on a small recorded trace: busy union, idle gaps
named by host span, per-node kernel time, exposed collective time, and
the unwrapping of autodiff wrappers around a named_scope."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import trace_reduce as tr  # noqa: E402


def _reduced():
    with open(os.path.join(ROOT, "perfbench/selfcheck/data",
                           "small_trace.json")) as f:
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
