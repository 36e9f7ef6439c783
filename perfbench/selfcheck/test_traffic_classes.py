"""The class plan deals once: every seed deals the same multiset of
(class, prompt, answer) sizes (the file's `sizes_seed` draws them, class
after class) and moves only who holds which pair and the token ids; a
caller keeps its pair, and so its class, over all rounds. The served
mix's pools hold every dealt pair at its full length. The new cell has
its toy files for the rehearsal."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import traffic_classes as tc  # noqa: E402

SEEDS = (1, 2147483999, 2800000101, 2 ** 31 + 12345)
MIXES = ("perfbench/traffic/longshort_closed.json",
         "perfbench/selfcheck/tiny/longshort_closed.json")


def _mix(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES)
def test_a_caller_keeps_its_pair_and_class(path):
    mix = _mix(path)
    vocab = 19072 if "tiny" not in path else 96
    plan = tc.ClassPlan(mix, SEEDS[1], vocab)
    by_name = {c["name"]: c for c in mix["classes"]}
    assert plan.clients == mix["clients"] == sum(
        c["clients"] for c in mix["classes"])
    for c in range(plan.clients):
        spec = by_name[plan.klass(c)]
        prompts = []
        for rnd in (0, 1, 7):
            prompt, n_out = plan.request(c, rnd)
            assert spec["prompt_tokens"]["min"] <= len(prompt) \
                <= spec["prompt_tokens"]["max"]
            assert spec["output_tokens"]["min"] <= n_out \
                <= spec["output_tokens"]["max"]
            assert all(2 <= t < vocab for t in prompt)
            prompts.append(tuple(prompt))
        assert len({len(p) for p in prompts}) == 1      # its pair's length
        assert len(set(prompts)) == 3                   # new ids a round
        assert plan.request(c, 3) == plan.request(c, 3)  # from the seed
    assert collections.Counter(plan.klass(c) for c in range(
        plan.clients)) == {c["name"]: c["clients"] for c in mix["classes"]}


@pytest.mark.parametrize("path", MIXES)
def test_every_seed_deals_the_same_sizes(path):
    mix = _mix(path)
    dealt = [sorted(tc.ClassPlan(mix, s, 96).pairs) for s in SEEDS]
    assert all(d == dealt[0] for d in dealt)
    assert dealt[0] == sorted(tc.class_pairs(mix))
    holders = [tuple(tc.ClassPlan(mix, s, 96).pairs) for s in SEEDS]
    assert len(set(holders)) > 1            # the seed moves who holds what
    other = dict(mix, sizes_seed=int(mix["sizes_seed"]) + 1)
    assert sorted(tc.class_pairs(other)) != dealt[0]


def test_the_served_mix_is_the_issues():
    mix = _mix(MIXES[0])
    pairs = tc.class_pairs(mix)
    long_ = [(p, a) for c, p, a in pairs if c == "long"]
    short = [(p, a) for c, p, a in pairs if c == "short"]
    assert len(long_) == len(short) == 32 and mix["clients"] == 64
    assert all(8192 <= p <= 16384 and a == 12288 for p, a in long_)
    assert all(32 <= p <= 1024 and 64 <= a <= 512 for p, a in short)
    assert mix["sizes_seed"] == 20261005
    assert sum(p for p, _ in long_) == 350005      # the file's `why`
    eng = mix["engine"]
    # the full group: every dealt pair at its full length at once, plus
    # 5% and the scratch page; the longest row fills the one bucket
    pages = tc.pages_for(mix, 64)
    assert pages == 11935
    assert eng["num_pages"][0] == int(pages * 1.05) + 1 + 1
    assert tc.ClassPlan(mix, 1, 19072).longest() == 448 * 64
    assert eng["page_buckets"] == [448]
    # the window group: 3 pages a decoding row and a prefill chunk's 11
    row = -(-128 // 64) + 1
    chunk = -(-(512 + 127) // 64) + 1
    assert (row, chunk) == (3, 11)
    assert eng["num_pages"][1] == int((64 * row + chunk) * 1.05 + 1) + 1
    assert eng["max_batch"] == 64 and eng["run_ahead"] == 8
    assert eng["chunk_buckets"] == [128, 512]
    assert eng["prefix_cache"] is False
    assert mix["check_requests"] == 2 and mix["trace_seconds"] == 10.0


def test_the_toy_mix_fits_its_toy_engine():
    mix = _mix(MIXES[1])
    eng = mix["engine"]
    assert tc.ClassPlan(mix, 1, 96).longest() <= eng["page_buckets"][-1] * 4
    assert tc.pages_for(mix, 4) < eng["num_pages"][0]
    assert eng["num_pages"][1] > mix["clients"] * 3 + 5
