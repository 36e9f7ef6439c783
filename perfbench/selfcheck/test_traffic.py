"""The closed-loop generator deals once: a client keeps its (prompt
length, answer length) for the whole run, so the live set is the mix's
own pairs at every instant, whatever the seed; the seed moves only who
holds which pair and the token ids. (ISSUE 28 asked for these under
tests/; a benchmark PR adds files only under the benchmark's paths.)"""
import collections
import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import traffic  # noqa: E402

VOCAB = 50272
SEEDS = (1, 2147483999, 2800000101, 2 ** 31 + 12345)
MIXES = sorted(
    os.path.relpath(p, ROOT) for p in
    glob.glob(os.path.join(ROOT, "perfbench/traffic/*.json"))
    + glob.glob(os.path.join(ROOT, "perfbench/selfcheck/tiny/*.json"))
    if json.load(open(p)).get("kind") == "serve_closed")


def _mix(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _sizes(plan, rnd):
    out = []
    for c in range(plan.clients):
        prompt, n_out = plan.request(c, rnd)
        out.append((len(prompt), n_out))
    return out


def test_every_serving_mix_is_found():
    assert "perfbench/traffic/chat_closed.json" in MIXES
    assert "perfbench/traffic/docqa_closed.json" in MIXES
    assert len(MIXES) >= 4


@pytest.mark.parametrize("path", MIXES)
def test_a_client_keeps_its_pair_and_draws_new_tokens_every_round(path):
    plan = traffic.ClosedLoopPlan(_mix(path), SEEDS[1], VOCAB)
    first = _sizes(plan, 0)
    for rnd in (1, 2, 7, 40):
        assert _sizes(plan, rnd) == first
    for c in range(plan.clients):
        prompts = [tuple(plan.request(c, r)[0]) for r in range(6)]
        assert len(set(prompts)) == 6
        assert plan.request(c, 3) == plan.request(c, 3)   # from the seed
    assert not hasattr(plan, "_perm") and not hasattr(plan, "_perms")


@pytest.mark.parametrize("path", MIXES)
def test_seeds_deal_the_same_pairs_to_different_clients(path):
    mix = _mix(path)
    want = collections.Counter(traffic.size_pairs(mix))
    deals = []
    for seed in SEEDS:
        plan = traffic.ClosedLoopPlan(mix, seed, VOCAB)
        deal = _sizes(plan, 0)
        assert collections.Counter(deal) == want
        assert collections.Counter(_sizes(plan, 5)) == want
        deals.append(tuple(deal))
    if len(want) > 2:       # two toy clients can be dealt alike
        assert len(set(deals)) > 1
    a = traffic.ClosedLoopPlan(mix, SEEDS[0], VOCAB).request(0, 0)[0]
    b = traffic.ClosedLoopPlan(mix, SEEDS[2], VOCAB).request(0, 0)[0]
    assert a != b


@pytest.mark.parametrize("path,pages", [
    ("perfbench/traffic/chat_closed.json", 825),
    ("perfbench/traffic/docqa_closed.json", 1388)])
def test_the_pages_at_the_longest_are_the_mix_s_own(path, pages):
    """Every client at the end of its answer at once: the most the live
    set can ever hold, the same for every seed and round, and inside the
    pool the mix asks for (so nothing is ever preempted)."""
    mix = _mix(path)
    page = 16       # configs/opt_1p3b.json page_size
    for seed in SEEDS:
        plan = traffic.ClosedLoopPlan(mix, seed, VOCAB)
        for rnd in (0, 1, 9):
            need = [-(-(p + n) // page) for p, n in _sizes(plan, rnd)]
            assert sum(need) == pages
            assert max(need) <= max(mix["engine"]["page_buckets"])
    assert pages < int(mix["engine"]["num_pages"])
    assert int(mix["engine"]["max_batch"]) == int(mix["clients"])


def test_a_shared_prefix_still_begins_every_prompt():
    mix = dict(_mix("perfbench/traffic/chat_closed.json"),
               shared_prefix_tokens=24)
    plan = traffic.ClosedLoopPlan(mix, SEEDS[2], VOCAB)
    assert len(plan.prefix) == 24
    for c in range(plan.clients):
        for rnd in (0, 3):
            prompt, _n = plan.request(c, rnd)
            assert prompt[:24] == plan.prefix
            assert len(prompt) == plan.pairs[c][0] >= 32
    other = traffic.ClosedLoopPlan(mix, SEEDS[3], VOCAB)
    assert other.prefix != plan.prefix
    assert collections.Counter(other.pairs) == collections.Counter(plan.pairs)


def test_no_traffic_file_chooses_a_deal():
    for path in MIXES:
        keys = set(_mix(path))
        assert not {k for k in keys if "deal" in k or "perm" in k
                    or "shuffle" in k}, path
