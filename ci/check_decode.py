"""Decode-tier runtime gates (ci/check_decode.sh drives this; tier-1
safe: CPU backend, tiny model, a few min).

Six gates over live continuous-batching runs:

  (i)   ZERO retraces across a >= 64-step continuous decode with
        mid-stream admissions, evictions, AND preemptions — the
        fixed-shape decode grid absorbs every batch composition the
        scheduler can produce (prefix cache ON: tail prefills and
        cache evictions included);
  (ii)  greedy decode output is TOKEN-IDENTICAL to an unbatched
        single-request reference loop, for every request, including
        preempted-and-readmitted ones;
  (iii) page-pool exhaustion triggers preemption (and later
        readmission), never an OOM/crash: every future resolves, the
        scheduler thread survives, and the allocator ends clean after
        a cache flush;
  (iv)  a shared-prefix workload reuses >= 50% of its prompt pages
        through the prefix cache and ALLOCATES strictly fewer pages
        than the identical cache-off run (the work-avoided proof,
        not just a hit-rate claim);
  (v)   speculative decoding with a K=4 self-draft emits tokens
        IDENTICAL to target-only greedy while averaging > 1.5
        accepted draft tokens per target step;
  (vi)  sampled decoding (temperature/top-k/top-p in-program) is
        bit-identical between a big-pool run and a tiny-pool run with
        forced preemption churn — the (seed, position) streams make
        preemption invisible to sampled output.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from mxnet_tpu import decoding as dec  # noqa: E402

CFG = dec.DecoderConfig(vocab=64, d_model=32, n_layers=2,
                        n_heads=2, d_ff=64, max_len=128)
PARAMS = dec.init_decoder_params(CFG, seed=0)


def ref_greedy(prompt, n):
    import jax.numpy as jnp
    toks, out = list(prompt), []
    for _ in range(n):
        lg = dec.reference_logits(
            PARAMS, np.asarray([toks], np.int32), CFG)
        nxt = int(jnp.argmax(lg[0, -1]))
        if nxt == CFG.eos_id:
            break
        out.append(nxt)
        toks.append(nxt)
    return out


def gate_churn():
    """(i) + (ii) + (iii): the original three gates, cache on."""
    # pool deliberately too small for the offered load: 12 allocatable
    # pages vs 4 rows x up to 8 pages each forces preemption churn
    model = dec.DecodedModel(
        "gate", 1, PARAMS, CFG, max_batch=4, page_size=4,
        num_pages=13, page_buckets=(1, 2, 4, 8), queue_cap=256,
        max_tokens=16)
    floor = model.engine.traces()

    rs = np.random.RandomState(7)
    jobs = [(rs.randint(2, CFG.vocab,
                        size=int(rs.randint(2, 14))).tolist(),
             int(rs.randint(6, 15))) for _ in range(28)]
    # staggered submission = mid-stream admissions while earlier
    # sequences are decoding (and being evicted/preempted)
    futs = []
    for i, (p, n) in enumerate(jobs):
        futs.append(model.submit(p, max_new_tokens=n,
                                 priority=i % 3))
    outs = [f.result(600) for f in futs]
    snap = model.stats.snapshot()
    retraces = model.engine.traces() - floor
    # cached pages are held deliberately; a flush must drain the pool
    model.scheduler.cache.release_all()
    alloc_stats = model.engine.allocator.stats()
    model.engine.allocator.check()
    model.close()

    assert snap["steps"] >= 64, (
        f"gate needs >= 64 continuous decode steps, ran {snap['steps']}")
    assert retraces == 0, (
        f"gate (i) FAILED: {retraces} retraces after warmup "
        f"({model.engine.trace_counts()})")
    assert snap["traces_since_warmup"] == 0, snap

    bad = [i for i, ((p, n), o) in enumerate(zip(jobs, outs))
           if o != ref_greedy(p, n)]
    assert not bad, f"gate (ii) FAILED: requests {bad} diverge from " \
                    "the unbatched reference"

    assert snap["preemptions"] > 0, (
        "gate (iii) FAILED: pool pressure produced no preemptions "
        f"(low watermark {snap['free_low_watermark']})")
    assert snap["readmissions"] == snap["preemptions"], snap
    assert snap["completed"] == len(jobs), snap
    assert alloc_stats["pages_in_use"] == 0, alloc_stats
    print(f"decode-check (i-iii) OK: {snap['steps']} steps, "
          f"{len(jobs)} requests token-identical to reference, "
          f"{snap['preemptions']} preemptions survived, 0 retraces "
          f"(decode {snap['decode_tokens_per_s']} tok/s, "
          f"prefill {snap['prefill_tokens_per_s']} tok/s)")


def gate_prefix():
    """(iv): shared-prefix page reuse with a falling allocation
    count vs the cache-off twin."""
    prefix = list(range(2, 26))            # 24 tokens = 6 full pages
    jobs = [prefix + [30 + i, 31 + i] for i in range(8)]

    def run(cache_on):
        m = dec.DecodedModel(
            "gate-prefix", 1, PARAMS, CFG, max_batch=4, page_size=4,
            num_pages=64, page_buckets=(1, 2, 4, 8), max_tokens=8,
            prefix_cache=cache_on)
        floor = m.engine.traces()
        try:
            outs = [m.generate(p, max_new_tokens=6, timeout=120)
                    for p in jobs]
            snap = m.stats.snapshot()
            assert m.engine.traces() == floor, "prefix arm retraced"
            return outs, snap
        finally:
            m.close()

    outs_off, snap_off = run(False)
    outs_on, snap_on = run(True)
    assert outs_on == outs_off, (
        "gate (iv) FAILED: cache-on output diverges from cache-off")
    prompt_pages = sum(len(p) // 4 for p in jobs)
    reused = snap_on["prefix_pages_reused"]
    assert reused >= prompt_pages * 0.5, (
        f"gate (iv) FAILED: only {reused}/{prompt_pages} prompt pages "
        "reused (< 50%)")
    assert snap_on["pages_allocated"] < snap_off["pages_allocated"], (
        f"gate (iv) FAILED: cache did not reduce page allocations "
        f"({snap_on['pages_allocated']} vs "
        f"{snap_off['pages_allocated']})")
    print(f"decode-check (iv) OK: {reused}/{prompt_pages} prompt "
          f"pages reused (hit rate {snap_on['prefix_hit_rate']}), "
          f"pages allocated {snap_off['pages_allocated']} -> "
          f"{snap_on['pages_allocated']}")


def gate_speculative():
    """(v): K=4 self-draft speculative greedy == target-only greedy,
    > 1.5 accepted tokens per target step."""
    m = dec.DecodedModel(
        "gate-spec", 1, PARAMS, CFG, max_batch=4, page_size=4,
        num_pages=64, page_buckets=(1, 2, 4, 8), max_tokens=16,
        draft="self", spec_k=4, prefix_cache=False)
    floor = m.engine.traces()
    try:
        rs = np.random.RandomState(11)
        jobs = [(rs.randint(2, CFG.vocab,
                            size=int(rs.randint(2, 12))).tolist(),
                 int(rs.randint(8, 15))) for _ in range(10)]
        futs = [m.submit(p, max_new_tokens=n) for p, n in jobs]
        outs = [f.result(600) for f in futs]
        snap = m.stats.snapshot()
        assert m.engine.traces() == floor, "speculative arm retraced"
    finally:
        m.close()
    bad = [i for i, ((p, n), o) in enumerate(zip(jobs, outs))
           if o != ref_greedy(p, n)]
    assert not bad, (
        f"gate (v) FAILED: speculative requests {bad} diverge from "
        "target-only greedy")
    acc_per_step = snap["spec_accepted"] / max(1, snap["steps"])
    assert acc_per_step > 1.5, (
        f"gate (v) FAILED: {acc_per_step:.2f} accepted tokens per "
        f"target step (need > 1.5; acceptance "
        f"{snap['spec_acceptance_rate']})")
    print(f"decode-check (v) OK: speculative K=4 token-identical, "
          f"{acc_per_step:.2f} accepted tokens/target step "
          f"({snap['tokens_per_target_step']} emitted/step, "
          f"acceptance {snap['spec_acceptance_rate']})")


def gate_sampled_replay():
    """(vi): sampled output is bit-identical across preemption."""
    sps = [dec.SamplingParams(temperature=0.8, top_k=12, top_p=0.9,
                              seed=100 + i) for i in range(8)]
    rs = np.random.RandomState(5)
    prompts = [rs.randint(2, CFG.vocab,
                          size=int(rs.randint(2, 10))).tolist()
               for _ in range(8)]

    big = dec.DecodedModel(
        "gate-samp-big", 1, PARAMS, CFG, max_batch=4, page_size=4,
        num_pages=64, page_buckets=(1, 2, 4, 8), max_tokens=12)
    try:
        want = [big.generate(p, max_new_tokens=10, timeout=120,
                             sampling=s)
                for p, s in zip(prompts, sps)]
    finally:
        big.close()

    small = dec.DecodedModel(
        "gate-samp-small", 1, PARAMS, CFG, max_batch=4, page_size=4,
        num_pages=11, page_buckets=(1, 2, 4), max_tokens=12,
        queue_cap=64)
    floor = small.engine.traces()
    try:
        futs = [small.submit(p, max_new_tokens=10, sampling=s,
                             priority=i % 2)
                for i, (p, s) in enumerate(zip(prompts, sps))]
        got = [f.result(600) for f in futs]
        snap = small.stats.snapshot()
        assert small.engine.traces() == floor, "sampled arm retraced"
    finally:
        small.close()
    assert snap["preemptions"] > 0, (
        "gate (vi) vacuous: tiny pool produced no preemptions")
    bad = [i for i, (w, g) in enumerate(zip(want, got)) if w != g]
    assert not bad, (
        f"gate (vi) FAILED: sampled requests {bad} not bit-identical "
        "across preempt/readmit")
    print(f"decode-check (vi) OK: 8 sampled requests bit-identical "
          f"across {snap['preemptions']} preemptions")


def main():
    gate_churn()
    gate_prefix()
    gate_speculative()
    gate_sampled_replay()
    return 0


if __name__ == "__main__":
    sys.exit(main())
