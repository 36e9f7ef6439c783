"""`greedy_step_share` over `decoding.step` spans: the share of them with
`sampled_rows` 0, and None where no span carries the attr, as on a
program whose sampler has no argmax branch (the parent of PR 35)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common  # noqa: E402

READER = common.load_py(
    os.path.join(ROOT, "perfbench/metrics/greedy_step_share.py"),
    "selfcheck_metric_greedy_step_share")


class _Trace:
    """What the reader asks of a reduced trace: the step spans."""

    def __init__(self, attrs):
        self.attrs = attrs

    def busy_inside(self, name):
        assert name == "decoding.step"
        return 0.0, [(i, i + 1, a) for i, a in enumerate(self.attrs)]


@pytest.mark.parametrize("attrs,want", [
    ([{"sampled_rows": 0}] * 4, 1.0),
    ([{"sampled_rows": 0}, {"sampled_rows": 1}, {"sampled_rows": 0},
      {"sampled_rows": 3}], 0.5),
    ([{"live": 4}] * 3, None),           # the parent: no such attr
    ([None, {}], None),
    ([], None),
])
def test_greedy_step_share(attrs, want):
    assert READER.read({"trace": _Trace(attrs)}) == want
