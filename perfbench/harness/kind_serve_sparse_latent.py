"""Runner for configurations of kind `serve_sparse_latent`: a decoder
with a latent page pool and learned sparse attention, holding one
chip's share of its experts and vocabulary, served through the SAME
`ModelServer.load_decoder` / `submit_decode`, clients, window and sample
as `kind_serve_decoder` (imported from it). Of its own it has the
configuration object, the session plan, what the run prints beside the
decoder kind's counts (the radix cache's evictions, the documents that
had to be prefilled again, the routing counters, `selection_overlap`),
and the two numbers of its check. `served_logit_gap` is the decoder
kind's: the widest gap over the checked served tokens. With routed
experts and a learned selection it is set by a rare discrete flip (a
near-tie at an expert's eighth place or at the 2048th selected token
falls otherwise in bfloat16 than in float32, and the token's logits move
by tenths), in the program and in a float8 control alike, so it tells a
gross fault and little else; `served_logit_gap_p90`, the 90th percentile
over the same tokens, is what rounding moves in proportion, and is the
number that the float8 control fails.
"""
import os
import time

import numpy as np

from perfbench.harness import check, common, trace_reduce, traffic_sessions
from perfbench.harness.kind_serve_decoder import (
    MODEL, now, pick_sample, serve_window, window_account, window_numbers)


def config_object(cfg):
    """The program's configuration object from the file's published
    keys: nothing here but the renaming."""
    from mxnet_tpu import decoding as dec

    r = cfg["rope_scaling"]
    return dec.SparseLatentConfig(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_dense_layers=int(cfg["first_k_dense_replace"]),
        n_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        index_n_heads=int(cfg["index_n_heads"]),
        index_head_dim=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        d_ff=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_experts=int(cfg["n_routed_experts"]),
        experts_held=(int(cfg["experts_held_first"]),
                      int(cfg["n_routed_experts_held"])),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(r["factor"]),
        rope_original_max_len=int(r["original_max_position_embeddings"]),
        rope_beta_fast=float(r["beta_fast"]),
        rope_beta_slow=float(r["beta_slow"]),
        rope_mscale=float(r["mscale"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        max_len=int(cfg["max_position_embeddings"]), eos_id=-1,
        prefill_chunk=int(cfg["prefill_chunk"]))


def load_server(ctx, params, hooks=None):
    from mxnet_tpu import serving

    cfg, eng = ctx.config, ctx.traffic["engine"]
    server = serving.ModelServer()
    model = server.load_decoder(
        MODEL, params, config_object(cfg), max_batch=int(eng["max_batch"]),
        page_size=int(cfg["page_size"]), num_pages=int(eng["num_pages"]),
        page_buckets=tuple(eng["page_buckets"]),
        chunk_buckets=tuple(eng["chunk_buckets"]),
        context_buckets=tuple(eng["context_buckets"]),
        prefix_cache=bool(eng["prefix_cache"]), kv_dtype=cfg["kv_dtype"],
        max_tokens=int(ctx.traffic["output_tokens"]["max"]),
        # steps the scheduler keeps in flight: with them the device goes
        # from step to step without the host, and a host stall shorter
        # than they last costs the window nothing
        run_ahead=int(eng.get("run_ahead", 0)))
    if hooks and "after_load" in hooks:
        hooks["after_load"](model)
    return server, model


def probe_selection(model, tokens):
    """What the PROGRAM selects, in every layer, for the query that
    chose the last of `tokens` (the request's prompt and served tokens):
    the context goes into the pages through the normal prefill (the
    document's pages from the prefix cache where they still are) and one
    probed decode step reads the selection. The scheduler must be idle.
    Returns (layers, k) positions, -1 past the reach."""
    from mxnet_tpu.decoding import pages_needed

    eng, cache = model.engine, model.scheduler.cache
    ctx_tokens = list(tokens[:-2])       # positions the last query sees
    need = pages_needed(len(tokens) - 1, eng.page_size)
    matched, start = ([], 0) if cache is None else cache.match(
        ctx_tokens, (len(ctx_tokens) - 1) // eng.page_size)
    while eng.allocator.free_pages() < need - len(matched):
        if cache is None or not cache.evict_lru():
            raise RuntimeError("no pages for the selection probe")
    table = matched + eng.allocator.alloc(need - len(matched))
    try:
        eng.prefill(ctx_tokens, table, start=start)
        tbl = np.zeros((1, eng.page_buckets[-1]), np.int32)
        tbl[0, :len(table)] = table
        _, picked = eng.probe_selected(
            [tokens[-2]], tbl, [len(tokens) - 2], [True])
    finally:
        eng.allocator.free(table)
    return picked[:, 0]


def overlap(program, reference):
    """Share of the program's selected positions that the reference
    selects too: (over all layers, [layer by layer])."""
    hits, totals = [], []
    for p, r in zip(program, reference):
        mine = {int(x) for x in p if x >= 0}
        totals.append(len(mine))
        hits.append(len(mine & {int(x) for x in r if x >= 0}))
    if not sum(totals):
        return float("nan"), []
    return sum(hits) / sum(totals), [
        round(h / t, 4) if t else None for h, t in zip(hits, totals)]


def gap_numbers(gaps):
    """The check's numbers from the gaps of all checked served tokens."""
    return {"served_logit_gap": float(np.max(gaps)),
            "served_logit_gap_p90": float(np.quantile(gaps, 0.9))}


def score_requests(ctx, ref, params, sample, t1, controls=(),
                   control_requests=None):
    """The reference's gaps of every checked request's served tokens,
    pooled: (the program's numbers, {control: its numbers}, served tokens
    checked, [what the reference selected for each request's last
    served token]). The controls (two more passes each) are computed
    for the first `control_requests` of the sample, the longest first."""
    pad = int(ctx.traffic["engine"]["page_buckets"][-1]) \
        * int(ctx.config["page_size"])
    gaps, lows, selected = [], {c: [] for c in controls}, []
    for i, r in enumerate(sample):
        served = [t for t, s in zip(r.tokens, r.stamps) if s <= t1] \
            or r.tokens
        want = tuple(controls) if control_requests is None \
            or i < control_requests else ()
        gap, low = ref.served_gaps(params, r.prompt, served, ctx.config,
                                   pad_to=pad, control=want or False)
        selected.append(ref.LAST["selected"])
        gaps.append(gap)
        for c in want:
            lows[c].append(low[c] if isinstance(low, dict) else low)
        qs = (0.5, 0.9, 0.99, 1.0)
        ctx.log("gaps of %d served tokens after %d (median, 90th, 99th, "
                "max): program %s%s" % (
                    len(served), len(r.prompt), _quantiles(gap, qs),
                    "".join(f"; {c} {_quantiles(lows[c][-1], qs)}"
                            for c in want)))
    pooled = np.concatenate(gaps) if gaps else np.zeros((0,))
    return (gap_numbers(pooled) if len(pooled) else {},
            {c: gap_numbers(np.concatenate(v)) for c, v in lows.items()},
            len(pooled), selected)


def _quantiles(values, qs):
    return [round(float(x), 4) for x in np.quantile(values, qs)]


def run(ctx, hooks=None):
    import jax.numpy as jnp

    ref = ctx.reference()
    cfg = ctx.config
    config_object(cfg)      # a program without the block fails here, at once
    params = ref.make_params(ctx.seed, cfg, jnp.dtype(cfg["weights_dtype"]))
    plan = traffic_sessions.SessionPlan(ctx.traffic, ctx.seed,
                                        cfg["vocab_size"])
    server, model = load_server(ctx, params, hooks)
    ctx.log("decoder loaded and warm")
    picked = {}
    try:
        clients, w = serve_window(ctx, server, model, plan)
        t0, t1 = w["t0"], w["t1"]
        tokens, gaps, ttft, touched, failed = window_numbers(clients, t0, t1)
        sample = pick_sample(ctx, touched, t1)
        pool = model.stats.snapshot()
        # the scheduler is idle once every client's request is resolved
        t_probe = now()
        while model.scheduler.depth() != (0, 0) and now() - t_probe < 30:
            time.sleep(0.02)
        for r in sample:
            served = [t for t, s in zip(r.tokens, r.stamps) if s <= t1] \
                or r.tokens
            if len(served) >= 2:
                picked[id(r)] = probe_selection(
                    model, list(r.prompt) + list(served))
        ctx.log(f"selection probe: {now() - t_probe:.1f}s over "
                f"{len(picked)} requests")
    finally:
        server.unload(MODEL)      # stops the scheduler, drops its stats
        server.stop(drain=False)
    rate = tokens / (t1 - t0)
    peak = common.peak_bytes(ctx.devices)
    ctx.log(f"memory_stats: {ctx.devices[0].memory_stats()}")
    names = ("steps", "decode_tokens", "prefill_tokens", "prefills",
             "preemptions", "completed", "prefill_chunks",
             "selected_tokens", "expert_rows", "experts_hit")
    d = {k: w["stats1"].get(k, 0) - w["stats0"].get(k, 0) for k in names}
    steps = sorted((s[2] - s[1]) * 1e3 for s in w["spans"]
                   if s[0] == "decoding.step" and s[1] >= t0 and s[2] <= t1)
    page = int(cfg["page_size"])
    tails = [a["tokens"] - a["cached_tokens"] for n, a0, a1, a in w["spans"]
             if n == "decoding.prefill" and a0 >= t0 and a1 <= t1]
    longest_tail = page - 1 + int(ctx.traffic["question_tokens"]["max"])
    # where the asked seconds ended between two admissions: the window
    # closes at the first pause in deliveries after that instant, so a
    # prefill that begins within a stall's length of it can fall on
    # either side of the close from run to run
    mark = t0 + ctx.seconds
    fills = [a0 for n, a0, _a1, _a in w["spans"] if n == "decoding.prefill"]
    mark_to_prefill = [
        round(min((mark - a for a in fills if a <= mark), default=-1.0), 3),
        round(min((a - mark for a in fills if a > mark), default=-1.0), 3)]
    counts = {
        "requests_in_window": len(touched),
        "requests_finished": sum(1 for r in touched if r.done),
        "tokens": tokens, "token_gaps": len(gaps),
        "fenced_seconds": t1 - t0, "seconds_asked": ctx.seconds,
        "compilations_in_window": w["built"],
        "traces_since_warmup": w["stats1"].get("traces_since_warmup"),
        "peak_bytes": peak, "engine_steps": d["steps"],
        "decode_tokens": d["decode_tokens"],
        "prefill_tokens": d["prefill_tokens"], "prefills": d["prefills"],
        "prefill_chunks": d["prefill_chunks"],
        "preemptions": d["preemptions"],
        "preemptions_since_load": w["stats1"].get("preemptions"),
        # the radix cache may drop old questions (its least recently
        # used leaves); a document prefilled again shows as a tail
        # longer than a question and a partial page
        "prefix_evicted_pages": pool.get("prefix_evictions"),
        "documents_prefilled_again": sum(1 for t in tails
                                         if t > longest_tail),
        "prefill_tail_tokens_max": max(tails) if tails else None,
        "pages_free_low_watermark": pool.get("free_low_watermark"),
        "selected_tokens": d["selected_tokens"],
        "expert_rows": d["expert_rows"], "experts_hit": d["experts_hit"],
        "expert_rows_max": w["stats1"].get("expert_rows_max"),
        "mark_to_prefill_s": mark_to_prefill,
        "step_ms_median": steps[len(steps) // 2] if steps else None,
        "step_ms_longest": [round(x, 2) for x in steps[-5:][::-1]],
        "generate_throughput": rate,
        # not an end-to-end metric of this kind's cells (one turn in
        # twenty holds a question's prefill, so the 95th percentile sits
        # on the edge between a bare step and a step with a prefill)
        "tpot_p95_ms": common.quantile(gaps, 0.95) * 1e3 if gaps else None,
        "tpot_p50_ms": common.quantile(gaps, 0.5) * 1e3 if gaps else None,
        "setup_s": t0 - common.T_PROCESS_START,
    }
    counts.update(w["gc"])
    counts.update(window_account(w["spans"], t0, t1))
    ctx.log("window: " + str(counts))
    # free the program's state before the reference runs
    del model, server
    for c in clients:
        c.server = c.current = None
    common.free_device_memory()
    t_ref = now()
    controls = (hooks or {}).get("control") or ()
    controls = ("fp8",) if controls is True else \
        (controls,) if isinstance(controls, str) else tuple(controls)
    numbers, low, n_tok, selected = score_requests(
        ctx, ref, params, sample, t1, controls,
        int((hooks or {}).get("control_requests", len(sample))))
    overlaps = []
    for r, sel in zip(sample, selected):
        if id(r) in picked:
            share, by_layer = overlap(picked[id(r)], sel)
            overlaps.append(share)
            ctx.log(f"selection overlap by layer, request of "
                    f"{len(r.prompt)} prompt tokens: {by_layer}")
    ctx.log(f"reference: {now() - t_ref:.1f}s over {len(sample)} requests, "
            f"{n_tok} served tokens")
    selection_overlap = min(overlaps) if overlaps else None
    ctx.log(f"selection_overlap (program's selected positions that the "
            f"reference selects, last served token, worst request): "
            f"{selection_overlap} of {overlaps}")
    if not numbers:
        numbers = {k: float("nan") for k in cfg["check"]["limits"]}
    checks, ok = check.judge(numbers, cfg["check"]["limits"])
    ok = ok and bool(sample) and failed == 0 and w["built"] == 0
    e2e = {"generate_throughput": rate, "setup_s": counts["setup_s"]}
    if gaps:
        e2e["tpot_p95_ms"] = common.quantile(gaps, 0.95) * 1e3
    counts["selection_overlap"] = selection_overlap
    res = {"correct": ok, "attempted": len(touched), "failed": failed,
           "counts": counts, "checks": checks, "end_to_end": e2e,
           "control_gap": low or None, "served_tokens_checked": n_tok,
           "selection_overlap": selection_overlap,
           "device": dict(ctx.device, memory_peak_bytes=peak)}
    if ctx.trace:
        t_red = now()
        raw = trace_reduce.load_xplane(os.path.join(ctx.out_dir, "trace"))
        red = trace_reduce.Reduced(raw, w["spans"], w["t_open"],
                                   w["t_close"])
        if not red.ok:
            raise RuntimeError("the traced window holds no device "
                               "operation: nothing to reduce")
        lo, hi = w["t_open"], w["t_close"]
        facts = {"config": cfg, "chips": ctx.chips, "peaks": ctx.peaks,
                 "trace": red, "spans": w["spans"], "window_host": (lo, hi),
                 "counters": d, "ttft": ttft, "all_gaps": gaps,
                 "tokens": window_numbers(clients, lo, hi)[0]}
        t_read = now()
        res["per_layer"] = common.read_per_layer(ctx, facts)
        for k, v in facts.get("notes", {}).items():
            ctx.log(f"{k}: {v}")
        t_brk = now()
        res["breakdown"] = red.breakdown()
        res["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        ctx.log(f"trace: window {red.window_s:.3f}s busy {red.busy_s:.3f}s "
                f"clock drift {red.drift * 1e3:.3f}ms; reading it took "
                f"{t_read - t_red:.1f}s, the readers {t_brk - t_read:.1f}s, "
                f"the breakdown {now() - t_brk:.1f}s")
    return res
