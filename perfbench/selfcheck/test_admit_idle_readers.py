"""The readers of the admission's parts (`harness/loop_phases.py` and the
four metrics on it) on a hand-made window whose idle split is worked out
by hand: every idle millisecond under exactly one phase of the loop, a
gap that straddles an admission and the relaunch after it, spans that
stick out of the window left out of the per-span metrics, and None on
spans without the attributes (a program older than them)."""
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common, loop_phases  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402

NAMES = ("admit_idle_ms_per_prefill", "relaunch_idle_ms_per_turn",
         "admit_queue_ms_p50", "prefill_behind_ms_p50")


def _reader(name):
    return common.load_py(
        os.path.join(ROOT, "perfbench/metrics", name + ".py"),
        "selfcheck_metric_" + name)


def ms(*ts):
    return tuple(t * 1e-3 for t in ts)


def _facts(busy, spans):
    """A window of [0, 100] ms on one chip with these busy intervals and
    host spans, both in ms; the host's clock is the trace's."""
    raw = {"devices": [{"name": "/device:TPU:0", "modules": [],
                        "ops": [("op", "op") + ms(a, b) for a, b in busy]}],
           "host": [(tr.SYNC_A, 0.0, 0.0), (tr.SYNC_B, 0.1, 0.1)]}
    host = [(n,) + ms(a, b) + (attrs,) for n, a, b, attrs in spans]
    red = tr.Reduced(raw, host, 0.0, 0.1)
    assert red.ok
    return {"trace": red, "spans": host}


# idle gaps: [10,12] [16,20] [30,35] [38,43] [58,60] [70,72] [82,84]: 22 ms
BUSY = [(0, 10), (12, 16), (20, 30), (35, 38), (43, 58), (60, 70),
        (72, 82), (84, 100)]


def _fill(t0, t1, queued, launch, behind, readmission=False):
    return ("decoding.prefill", t0, t1,
            {"tokens": 64, "readmission": readmission,
             "queued_us": queued, "launch_us": launch,
             "behind_us": behind})


SPANS = [
    ("decoding.step", 0, 11, {"queued": 2, "in_flight": 1}),
    # an admission with two prefills: the first dispatched at 11-14 with
    # steps in flight, taken out at 14-20 (a retire inside), its span
    # 20-28; the second with nothing in flight, dispatched at 31-33.5
    # inside its span 31-38
    ("decoding.admit", 11, 40, {"prefills": 2, "drained": 1}),
    ("decoding.step", 14, 19, {"in_flight": 0}),
    ("decoding.emit", 17, 19, {"tokens": 4}),
    _fill(20, 28, 3000, 3000, 6000),
    _fill(31, 38, 9000, 2500, 0),
    # the relaunch from an empty queue, then a turn with one queued
    ("decoding.step", 40, 62, {"queued": 0, "in_flight": 1}),
    ("decoding.step", 62, 80, {"queued": 1, "in_flight": 1}),
    ("decoding.pack", 62, 63, None),
    # 80-85 under no loop span; a settle's retire launches nothing
    ("decoding.step", 85, 100, {"in_flight": 0}),
    # a relaunch that sticks out of the window: no idle, not counted
    ("decoding.step", 99, 104, {"queued": 0}),
    # another thread's span is not the loop's
    ("decoding.submit", 50, 90, None),
]

WANT = {"admit.launch": 3.5, "admit.behind": 4.0, "admit.prefill": 1.5,
        "admit.rest": 3.0, "relaunch": 5.0, "steady": 3.0, "none": 2.0}


def test_every_idle_millisecond_lies_under_one_phase():
    facts = _facts(BUSY, SPANS)
    res = loop_phases.split(facts)
    got = {k: round(v * 1e3, 9) for k, v in res["phases"].items()}
    assert got == WANT
    # the phases sum to the trace's own idle
    red = facts["trace"]
    assert abs(sum(res["phases"].values())
               - (red.window_s - red.busy_s)) < 1e-12
    assert loop_phases.split(facts) is res      # cached


def test_the_readers_by_hand():
    facts = _facts(BUSY, SPANS)
    got = {n: _reader(n).read(facts) for n in NAMES}
    # 12 ms of idle inside the admission over its two prefills
    assert got["admit_idle_ms_per_prefill"] == pytest.approx(6.0)
    # 5 ms inside the one relaunch whole in the window
    assert got["relaunch_idle_ms_per_turn"] == pytest.approx(5.0)
    # nearest rank of two: the lower
    assert got["admit_queue_ms_p50"] == pytest.approx(3.0)
    assert got["prefill_behind_ms_p50"] == pytest.approx(0.0)
    line = facts["notes"]["idle_by_loop_phase"]
    assert line.startswith("admit 0.0120 s (12.00%)")
    assert "relaunch 0.0050 s (5.00%)" in line
    assert "under no loop span 0.0020 s (2.00%)" in line
    assert "sum 0.0220 s (22.00%) against the window's idle 0.0220 s" \
        in line
    # [38,43]: relaunch 3 of its 5; [30,35]: launch 2.5 of its 5, the
    # rest 1, the prefill 1.5; [16,20] behind
    assert line.endswith("longest gaps 5.00 ms relaunch, "
                         "5.00 ms admit.launch, 4.00 ms admit.behind")
    assert facts["notes"]["first_token_parts"].startswith("2 prefills")


def test_medians_leave_out_readmissions_and_spans_outside_the_window():
    spans = [_fill(10, 12, 3000, 100, 0), _fill(20, 22, 5000, 100, 7000),
             _fill(30, 32, 9000, 100, 2000),
             _fill(40, 42, 90000, 100, 90000, readmission=True),
             _fill(99, 101, 1, 100, 1)]
    facts = _facts(BUSY, spans)
    assert _reader("admit_queue_ms_p50").read(facts) == pytest.approx(5.0)
    assert _reader("prefill_behind_ms_p50").read(facts) \
        == pytest.approx(2.0)
    assert "= 9.000 ms" in facts["notes"]["first_token_parts"]


def _parent(spans):
    """The spans as a program older than the attributes records them."""
    drop = {"queued", "prefills", "drained", "queued_us", "launch_us",
            "behind_us"}
    return [(n, a, b, {k: v for k, v in attrs.items() if k not in drop}
             if attrs else attrs) for n, a, b, attrs in spans]


@pytest.mark.parametrize("name", NAMES)
def test_the_parent_reads_nothing(name):
    facts = _facts(BUSY, _parent(SPANS))
    assert _reader(name).read(facts) is None
    assert "idle_by_loop_phase" not in facts.get("notes", {})


@pytest.mark.parametrize("name", NAMES)
def test_no_span_reads_nothing(name):
    assert _reader(name).read(_facts(BUSY, [])) is None


def test_the_sweep_agrees_with_every_gap_against_every_span():
    """Random turns and busy intervals: the linear sweep puts each top
    span's idle where the quadratic count does, and loses nothing."""
    rng = random.Random(39)
    for _ in range(20):
        t, spans = 0.0, []
        while t < 95:
            a, t = t, t + rng.uniform(0.5, 6)
            kind = rng.choice(["admit", "step0", "step1", "gap"])
            if kind == "admit":
                spans.append(("decoding.admit", a, t, {"prefills": 0}))
            elif kind != "gap":
                spans.append(("decoding.step", a, t,
                              {"queued": int(kind[-1])}))
        busy, t = [], 0.0
        while t < 100:
            a, t = t + rng.uniform(0, 2), t + rng.uniform(0.2, 8)
            busy.append((a, min(t, 100)))
        facts = _facts(busy, spans)
        res = loop_phases.split(facts)
        gaps = tr.idle_gaps(facts["trace"].intervals(0), 0.0, 0.1)
        for (_n, a, b, _), idle in zip(res["tops"], res["idle"]):
            want = sum(max(0.0, min(b, g1) - max(a, g0))
                       for g0, g1 in gaps)
            assert abs(idle - want) < 1e-12
        assert abs(sum(res["phases"].values())
                   - sum(b - a for a, b in gaps)) < 1e-12
