"""The session plan deals once: a caller keeps its document over all
rounds and asks a new question in each; every seed deals the same
multiset of sizes (the file's `sizes_seed` draws them), and moves only
who holds which slot and the token ids. The new cell has its toy files
for the rehearsal."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import traffic_sessions as ts  # noqa: E402

SEEDS = (1, 2147483999, 2800000101, 2 ** 31 + 12345)
MIXES = ("perfbench/traffic/docsessions_closed.json",
         "perfbench/selfcheck/tiny/docsessions_closed.json")


def _mix(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES)
def test_a_caller_keeps_its_document_and_asks_a_new_question(path):
    mix = _mix(path)
    vocab = 16160 if "tiny" not in path else 96
    plan = ts.SessionPlan(mix, SEEDS[1], vocab)
    for c in range(plan.clients):
        doc = plan.document(c)
        n_doc = plan.slots[c][0]
        assert len(doc) == n_doc
        assert mix["document_tokens"]["min"] <= n_doc \
            <= mix["document_tokens"]["max"]
        questions = []
        for rnd in (0, 1, 2, 9):
            prompt, n_out = plan.request(c, rnd)
            assert prompt[:n_doc] == doc
            q = prompt[n_doc:]
            assert mix["question_tokens"]["min"] <= len(q) \
                <= mix["question_tokens"]["max"]
            assert mix["output_tokens"]["min"] <= n_out \
                <= mix["output_tokens"]["max"]
            assert all(2 <= t < vocab for t in q)
            questions.append(tuple(q))
        assert len(set(questions)) == len(questions)
        assert plan.request(c, 3) == plan.request(c, 3)   # from the seed
        # the dealt rounds repeat
        rounds = int(mix["rounds_dealt"])
        assert [len(plan.request(c, r)[0]) for r in (0, 1)] == \
            [len(plan.request(c, r + rounds)[0]) for r in (0, 1)]
    assert len({tuple(plan.document(c)[:16])
                for c in range(plan.clients)}) == plan.clients


@pytest.mark.parametrize("path", MIXES)
def test_every_seed_deals_the_same_multiset(path):
    mix = _mix(path)
    want = collections.Counter(
        (d, tuple(r)) for d, r in ts.session_sizes(mix))
    held = []
    for seed in SEEDS:
        plan = ts.SessionPlan(mix, seed, 16160)
        assert collections.Counter(
            (d, tuple(r)) for d, r in plan.slots) == want
        held.append(tuple(d for d, _ in plan.slots))
        assert plan.document(0) != ts.SessionPlan(
            mix, seed + 1, 16160).document(0)[:len(plan.document(0))]
    if "tiny" not in path:
        assert len(set(held)) > 1       # the seed moves who holds which


def test_the_cell_fits_its_pool_and_its_bucket():
    mix = _mix(MIXES[0])
    cfg = _mix("perfbench/configs/deepseek_v32_ep16.json")
    page = cfg["page_size"]
    sizes = ts.session_sizes(mix)
    plan = ts.SessionPlan(mix, 7, cfg["vocab_size"])
    assert plan.longest() <= mix["engine"]["page_buckets"][-1] * page
    worst_pages = sum(-(-(d + max(q + a for q, a in r)) // page)
                      for d, r in sizes)
    # every caller's longest request at once, with room for the radix
    # cache's old questions
    assert worst_pages < 0.95 * (mix["engine"]["num_pages"] - 1)
    assert 330_000 < sum(d for d, _ in sizes) < 350_000
    assert mix["engine"]["chunk_buckets"][-1] == cfg["prefill_chunk"]
    assert mix["engine"]["context_buckets"][-1] \
        == mix["engine"]["page_buckets"][-1]


def test_the_new_cell_has_its_toy_files():
    bench = _mix("BENCHMARK.json")
    w = next(w for w in bench["workloads"]
             if w["name"] == "dsv32_docsessions_closed")
    for name in (w["config"], w["traffic"]):
        assert os.path.exists(os.path.join(
            ROOT, "perfbench/selfcheck/tiny", name + ".json")), name
