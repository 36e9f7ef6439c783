"""`decode_steps_in_flight` over `decoding.step` spans: the mean of their
`in_flight`, and None where no span carries the attr, as on a program
that does not report it (one older than the attr)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common  # noqa: E402

READER = common.load_py(
    os.path.join(ROOT, "perfbench/metrics/decode_steps_in_flight.py"),
    "selfcheck_metric_decode_steps_in_flight")


class _Trace:
    """What the reader asks of a reduced trace: the step spans."""

    def __init__(self, attrs):
        self.attrs = attrs

    def busy_inside(self, name):
        assert name == "decoding.step"
        return 0.0, [(i, i + 1, a) for i, a in enumerate(self.attrs)]


@pytest.mark.parametrize("attrs,want", [
    ([{"in_flight": 0}] * 4, 0.0),       # the waited-for turn
    ([{"in_flight": 2}, {"in_flight": 2}, {"in_flight": 1},
      {"in_flight": 0}, {"in_flight": 2}], 1.4),
    ([{"live": 4}] * 3, None),           # the parent: no such attr
    ([None, {}], None),
    ([], None),
])
def test_decode_steps_in_flight(attrs, want):
    assert READER.read({"trace": _Trace(attrs)}) == want
