"""Runner for configurations of kind `serve_decoder`: a decoder served
through `ModelServer.load_decoder` / `submit_decode` under a closed
loop of clients.

Set-up makes the weights on the device from the seed, loads and warms
the decoder, and starts the clients. THE WINDOW opens once every
client's first request has been prefilled and its first token is out,
at the instant the next step's tokens have all been delivered, and
closes at the first such instant after `--seconds` (see `settle`). `generate_throughput` = every output
token delivered to a client between those two instants over that time;
token gaps are stamped at the client's side of the stream. After the
close the clients stop, the server stops, the peak is read, the
program's state is freed, and the reference scores a seeded sample of
the window's requests (the longest among them).
"""
import os
import threading
import time

import numpy as np

from perfbench.harness import check, common, trace_reduce, traffic

now = time.perf_counter
MODEL = "lm"


class Request:
    __slots__ = ("client", "rnd", "prompt", "n_out", "t_submit", "stamps",
                 "tokens", "done", "error")

    def __init__(self, client, rnd, prompt, n_out):
        self.client, self.rnd = client, rnd
        self.prompt, self.n_out = prompt, n_out
        self.t_submit = None
        self.stamps, self.tokens = [], []
        self.done, self.error = False, None


class Client(threading.Thread):
    """One caller that waits for each reply before it sends the next."""

    def __init__(self, idx, server, plan, stop, first_token, tick):
        super().__init__(name=f"client-{idx}", daemon=True)
        self.idx, self.server, self.plan = idx, server, plan
        self.stop_flag, self.first_token = stop, first_token
        self.tick = tick
        self.requests = []
        self.current = None

    def run(self):
        rnd = 0
        while not self.stop_flag.is_set():
            prompt, n_out = self.plan.request(self.idx, rnd)
            req = Request(self.idx, rnd, prompt, n_out)
            self.requests.append(req)
            try:
                req.t_submit = now()
                fut = self.server.submit_decode(MODEL, prompt,
                                                max_new_tokens=n_out)
                self.current = fut
                for tok in fut.stream(timeout=600):
                    req.stamps.append(now())
                    req.tokens.append(int(tok))
                    self.tick.set()
                    if len(req.tokens) == 1:
                        self.first_token.set()
                req.done = fut.finish_reason in ("max_tokens", "length",
                                                 "eos")
            except Exception as exc:  # a failed request is counted, not fatal
                if not self.stop_flag.is_set():
                    req.error = repr(exc)
            rnd += 1


def load_server(ctx, params, hooks=None):
    from mxnet_tpu import decoding as dec
    from mxnet_tpu import serving

    cfg, eng = ctx.config, ctx.traffic["engine"]
    dcfg = dec.DecoderConfig(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]), d_ff=int(cfg["ffn_dim"]),
        max_len=int(cfg["max_position_embeddings"]), eos_id=-1)
    server = serving.ModelServer()
    model = server.load_decoder(
        MODEL, params, dcfg, max_batch=int(eng["max_batch"]),
        page_size=int(cfg["page_size"]), num_pages=int(eng["num_pages"]),
        page_buckets=tuple(eng["page_buckets"]),
        prefix_cache=bool(eng["prefix_cache"]), kv_dtype=cfg["kv_dtype"],
        max_tokens=int(ctx.traffic["output_tokens"]["max"]))
    if hooks and "after_load" in hooks:
        hooks["after_load"](model)
    return server, model


def settle(tick, first_wait=10.0, quiet=0.03, cap=0.5):
    """Wait for the next delivery of tokens and until it has gone quiet
    (the engine hands a step's tokens to all its rows within some
    milliseconds): the instant just after a whole step's tokens are out.
    Both ends of the window are taken so, so that the window holds whole
    steps' deliveries and a step more or less cannot move the rate; where
    deliveries never pause, the instant comes `cap` seconds later."""
    tick.clear()
    tick.wait(first_wait)
    end = now() + cap
    while now() < end:
        tick.clear()
        if not tick.wait(quiet):
            break
    return now()


def serve_window(ctx, server, model, plan):
    """Start the clients, open and close the window; returns the
    clients' records and the window's facts."""
    import jax

    from mxnet_tpu.telemetry import trace as spans

    stop, tick = threading.Event(), threading.Event()
    clients = []
    for i in range(plan.clients):
        c = Client(i, server, plan, stop, threading.Event(), tick)
        clients.append(c)
        c.start()
    for c in clients:
        if not c.first_token.wait(600):
            raise RuntimeError(f"client {c.idx} got no first token")
    gcw = common.GcWatch()
    gcw.open()
    t0 = settle(tick)
    spans.clear()
    built0 = ctx.compiles.built()
    stats0 = model.stats.snapshot()
    trace_s = float(ctx.traffic.get("trace_seconds", 10.0))
    t_open = t_close = None
    if ctx.trace:
        time.sleep(max(0.0, ctx.seconds - trace_s - 2.0))
        common.start_trace(ctx)
        time.sleep(1.0)
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_A):
            t_open = now()
    time.sleep(max(0.0, t0 + ctx.seconds - now()))
    if ctx.trace:
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_B):
            t_close = now()
    t1 = settle(tick)
    gc_counts = gcw.close()
    stats1 = model.stats.snapshot()
    built1 = ctx.compiles.built()
    if ctx.trace:
        jax.profiler.stop_trace()
    stop.set()
    for c in clients:
        if c.current is not None:
            c.current.cancel()
    for c in clients:
        c.join(60)
    host_spans = [(s.name, s.t0, s.t1, s.attrs)
                  for s in spans.recent_spans()]
    return clients, {"t0": t0, "t1": t1, "t_open": t_open,
                     "t_close": t_close, "stats0": stats0,
                     "stats1": stats1, "built": built1 - built0,
                     "gc": gc_counts,
                     "spans": host_spans}


def window_numbers(clients, t0, t1):
    """Tokens, gaps and first-token times of the window, from the
    clients' own stamps."""
    tokens, gaps, ttft, touched, failed = 0, [], [], [], 0
    for c in clients:
        for r in c.requests:
            inside = [s for s in r.stamps if t0 <= s <= t1]
            if r.error is not None:
                failed += 1
            if not inside and r.error is None:
                continue
            touched.append(r)
            tokens += len(inside)
            for a, b in zip(r.stamps, r.stamps[1:]):
                if a >= t0 and b <= t1:
                    gaps.append(b - a)
            if r.t_submit is not None and r.t_submit >= t0 and r.stamps \
                    and r.stamps[0] <= t1:
                ttft.append(r.stamps[0] - r.t_submit)
    return tokens, gaps, ttft, touched, failed


def window_account(spans, t0, t1):
    """What the window's turns were made of, for the counts line: the
    share of steps and the median step per decode program, the prefills,
    and the turns (one `decoding.step` start to the next) that ran long.
    A turn's excess is its length less its prefills less the median of
    that: a host stall, whatever its cause."""
    steps = sorted((s[1], s[2], (s[3] or {}).get("program", "?"))
                   for s in spans
                   if s[0] == "decoding.step" and s[1] >= t0 and s[2] <= t1)
    fills = sorted((s[1], s[2]) for s in spans
                   if s[0] == "decoding.prefill" and s[1] >= t0
                   and s[2] <= t1)
    by_prog = {}
    for a, b, prog in steps:
        by_prog.setdefault(prog, []).append((b - a) * 1e3)
    programs = {
        prog: {"steps": len(ms),
               "share_pct": round(100.0 * len(ms) / len(steps), 2),
               "ms_median": round(common.quantile(ms, 0.5), 3)}
        for prog, ms in sorted(by_prog.items())}
    bare, i = [], 0
    for (a, _, _), (b, _, _) in zip(steps, steps[1:]):
        inside = 0.0
        while i < len(fills) and fills[i][0] < b:
            if fills[i][0] >= a:
                inside += fills[i][1] - fills[i][0]
            i += 1
        bare.append((b - a - inside) * 1e3)
    out = {"programs": programs, "prefills_in_window": len(fills),
           "prefill_ms_total": round(sum(b - a for a, b in fills) * 1e3, 1)}
    if bare:
        med = common.quantile(bare, 0.5)
        excess = sorted((x - med for x in bare), reverse=True)
        out.update(
            turn_ms_median=round(med, 3),
            turn_excess_ms_longest=[round(x, 1) for x in excess[:5]],
            turn_excess_ms_total=round(
                sum(x for x in excess if x > 0.1 * med), 1))
    return out


def pick_sample(ctx, touched, t1):
    """A seeded sample of the window's requests, finished ones first,
    the longest always in it; each cut to the tokens delivered by the
    close."""
    rs = np.random.RandomState((ctx.seed * 31 + 17) % (2 ** 32))
    cand = [r for r in touched if r.tokens and r.error is None]
    if not cand:
        return []
    fin = [r for r in cand if r.done and r.stamps[-1] <= t1] or cand
    longest = max(fin, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in fin if r is not longest]
    order = rs.permutation(len(rest))
    n = int(ctx.traffic["check_requests"])
    return [longest] + [rest[i] for i in order[:max(0, n - 1)]]


def score_sample(ctx, ref, params, sample, t1, control=False):
    pad = int(ctx.traffic["engine"]["page_buckets"][-1]) \
        * int(ctx.config["page_size"])
    worst, worst_low, n_tok = 0.0, None, 0
    for r in sample:
        served = [t for t, s in zip(r.tokens, r.stamps) if s <= t1] \
            or r.tokens
        gap, low = ref.served_gaps(params, r.prompt, served, ctx.config,
                                   pad_to=pad, control=control)
        n_tok += len(served)
        worst = max(worst, float(gap.max()))
        if control:
            worst_low = max(worst_low or 0.0, float(low.max()))
    return worst, worst_low, n_tok


def run(ctx, hooks=None):
    import jax
    import jax.numpy as jnp

    ref = ctx.reference()
    cfg = ctx.config
    params = ref.make_params(ctx.seed, cfg, jnp.dtype(cfg["weights_dtype"]))
    plan = traffic.ClosedLoopPlan(ctx.traffic, ctx.seed, cfg["vocab_size"])
    server, model = load_server(ctx, params, hooks)
    ctx.log("decoder loaded and warm")
    try:
        clients, w = serve_window(ctx, server, model, plan)
    finally:
        server.unload(MODEL)      # stops the scheduler, drops its stats
        server.stop(drain=False)
    t0, t1 = w["t0"], w["t1"]
    tokens, gaps, ttft, touched, failed = window_numbers(clients, t0, t1)
    rate = tokens / (t1 - t0)
    peak = common.peak_bytes(ctx.devices)
    ctx.log(f"memory_stats: {ctx.devices[0].memory_stats()}")
    d = {k: w["stats1"][k] - w["stats0"][k]
         for k in ("steps", "decode_tokens", "prefill_tokens", "prefills",
                   "preemptions", "completed")}
    steps = sorted((s[2] - s[1]) * 1e3 for s in w["spans"]
                   if s[0] == "decoding.step" and s[1] >= t0 and s[2] <= t1)
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
        "preemptions": d["preemptions"],
        "step_ms_median": steps[len(steps) // 2] if steps else None,
        "step_ms_longest": [round(x, 2) for x in steps[-5:][::-1]],
        "generate_throughput": rate,
        "setup_s": t0 - common.T_PROCESS_START,
    }
    counts.update(w["gc"])
    counts.update(window_account(w["spans"], t0, t1))
    ctx.log("window: " + str(counts))
    ctx.log("share of steps per decode program: " + ", ".join(
        f"{p} {v['share_pct']}% ({v['steps']} steps, median "
        f"{v['ms_median']} ms)" for p, v in counts["programs"].items()))
    sample = pick_sample(ctx, touched, t1)
    # free the program's state before the reference runs
    del model, server
    for c in clients:
        c.server = c.current = None
    common.free_device_memory()
    t_ref = now()
    want_control = bool(hooks and hooks.get("control"))
    worst, low, n_tok = score_sample(ctx, ref, params, sample, t1,
                                     control=want_control)
    ctx.log(f"reference: {now() - t_ref:.1f}s over {len(sample)} requests, "
            f"{n_tok} served tokens")
    numbers = {"served_logit_gap": worst if sample else float("nan")}
    checks, ok = check.judge(numbers, cfg["check"]["limits"])
    ok = ok and bool(sample) and failed == 0 and w["built"] == 0
    e2e = {"generate_throughput": rate, "setup_s": counts["setup_s"]}
    if gaps:
        e2e["tpot_p95_ms"] = common.quantile(gaps, 0.95) * 1e3
    res = {"correct": ok, "attempted": len(touched), "failed": failed,
           "counts": counts, "checks": checks, "end_to_end": e2e,
           "control_gap": low, "served_tokens_checked": n_tok,
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
