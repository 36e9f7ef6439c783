"""Read, on the chip and at the cell's own size, what a serving cell's
limit is set from, for any serving kind (the runner is found from the
configuration's `kind`, as perfbench/run.py finds it): one whole run of
the cell per seed, each in this one process, printing the run's own two
lines (counts, result) and after them a `reading` line with the
program's numbers of the check, the controls' and, where the runner
reports it, `selection_overlap`. The runner takes `hooks["control"]` as
a tuple of control names and answers `control_gap` as {control:
numbers} (kind `serve_sparse_latent` does).

    python3 perfbench/selfcheck/readings_serve.py --workload <cell> \\
        --seeds 11,12,... [--controls fp8,dense] [--control-seeds 4] \\
        [--control-requests 1] [--seconds 30]

The first `--control-seeds` seeds also compute the controls (the
reference in float8; the reference with the index mask left out): each
has to come out as not correct by one of the cell's limits; they are
computed for the first `--control-requests` checked requests (the
longest first), two more reference passes each. Nothing here is run by the
benchmark's own runs; the result lines are the same as perfbench/run.py
prints, so a set of them can be handed to spread.py.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import common  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--control-requests", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = tuple(c for c in args.controls.split(",") if c)
    common.place_caches(ROOT)
    jax = common.configure_jax()
    compiles = common.CompileCounter()
    rows = []
    for i, seed in enumerate(seeds):
        ctx = common.build_context(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"], ROOT)
        ctx.compiles = compiles
        ctx.device, ctx.devices = common.device_record(jax, ctx.chips)
        ctx.peaks = common.peaks_for(ctx.device["kind"], ROOT)
        kind = ctx.config["kind"]
        runner = common.load_py(
            os.path.join(ROOT, "perfbench/harness", f"kind_{kind}.py"),
            f"perfbench_kind_{kind}")
        want = controls if i < args.control_seeds else ()
        hook = want if len(want) > 1 else (want[0] if want else False)
        res = runner.run(ctx, hooks={
            "control": hook, "control_requests": args.control_requests})
        common.emit_result(ctx, res)
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: v[0] for k, v in res["checks"].items()},
               "limits": {k: v[1] for k, v in res["checks"].items()},
               "controls": res.get("control_gap") or {},
               "selection_overlap": res.get("selection_overlap"),
               "served_tokens_checked": res["served_tokens_checked"],
               "generate_throughput":
                   res["end_to_end"]["generate_throughput"]}
        rows.append(row)
        print(json.dumps({"reading": row}), flush=True)
        common.free_device_memory()
    summary = {}
    for arm, pick in [("program", lambda r: r["program"])] + [
            (c, lambda r, c=c: r["controls"].get(c)) for c in controls]:
        have = [pick(r) for r in rows if pick(r)]
        if have:
            summary[arm] = {n: {"min": min(h[n] for h in have),
                                "max": max(h[n] for h in have),
                                "seeds": len(have)} for n in have[0]}
    over = [r["selection_overlap"] for r in rows
            if r["selection_overlap"] is not None]
    if over:
        summary["selection_overlap"] = {"min": min(over), "max": max(over)}
    print(json.dumps({"summary": summary,
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
