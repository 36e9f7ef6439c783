"""Read, on the chip and at the cell's own size, what the limits are set
from (steps 3-5 of "How `correct` is decided"): over many seeds in one
process, the program's numbers against the reference (the lower
reading), the control's (the reference in float8 put in the program's
place) and, for training cells, the planted faults' (the reference with
half of the batch left out; with all but one chip's shard left out).
Nothing here is run by the benchmark's own runs.

    python3 perfbench/selfcheck/readings.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 3] [--seconds 20]

Prints one JSON line per seed and a summary; run it through the chip
tool and copy the summary into PERF.md.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import check, common  # noqa: E402


def train_readings(ctx, n_control, i_seed=0):
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx

    k = common.load_py(os.path.join(ROOT, "perfbench/harness",
                                    "kind_train_symbol.py"), "k_train")
    ref = ctx.reference()
    st = k.build_and_fit(ctx, mx, ref)
    got = st["got"]
    st["mod"] = st["it"].mod = None
    st["it"] = None
    st["pool"] = st["pool"][:k.CHECK_STEPS]
    common.free_device_memory()
    w0 = {n: np.asarray(v) for n, v in st["w0"].items()}
    hyper = ctx.config["optimizer"]
    out = {"seed": ctx.seed}
    styles = os.environ.get("READINGS_BN_STYLES", "").split(",")
    for style in [s for s in styles if s] or [None]:
        tag = f"@{style}" if style else ""
        if style:
            ctx.config["reference_bn"] = style
        want = k.run_reference(ctx, ref, st)
        out["program" + tag], where = check.training_numbers(
            got, want, w0, hyper, k._decays)
        out["where" + tag] = {a: b for a, b in where.items()
                              if a != "still_leaves"}
        if not n_control:
            continue
        batch = st["batch"]
        arms = {"control_fp8": {"quant": "fp8"},
                "fault_half_batch": {"rows": batch // 2}}
        if ctx.chips > 1:
            arms["fault_no_exchange"] = {"rows": batch // ctx.chips}
        if i_seed == 0:
            # a second witness for leaves whose norms differ: the same
            # reference in float32 compute against itself in bfloat16
            arms["witness_ref_float32"] = {"compute": jnp.float32}
        for name, kw in arms.items():
            alt = k.run_reference(ctx, ref, st, **kw)
            out[name + tag], _ = check.training_numbers(
                {"losses": alt["losses"], "grad1": alt["grad1"],
                 "w3": alt["params"], "probs1": alt["probs1"]}, want, w0,
                hyper, k._decays)
    return out


def serve_readings(ctx, n_control, i_seed=0):
    k = common.load_py(os.path.join(ROOT, "perfbench/harness",
                                    "kind_serve_decoder.py"), "k_serve")
    res = k.run(ctx, hooks={"control": bool(n_control)})
    out = {"seed": ctx.seed, "correct": res["correct"],
           "program": {"served_logit_gap":
                       res["checks"]["served_logit_gap"][0]},
           "served_tokens_checked": res["served_tokens_checked"],
           "generate_throughput": res["end_to_end"]["generate_throughput"]}
    if n_control:
        out["control_fp8"] = {"served_logit_gap": res["control_gap"]}
    return out


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
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
        fn = train_readings if ctx.config["kind"] == "train_symbol" \
            else serve_readings
        row = fn(ctx, i < args.control_seeds, i)
        rows.append(row)
        print(json.dumps(row), flush=True)
        common.free_device_memory()
    summary = {}
    arms = sorted({a for r in rows for a in r
                   if isinstance(r[a], dict) and not a.startswith("where")
                   and a != "losses"})
    for arm in arms:
        have = [r[arm] for r in rows if arm in r]
        summary[arm] = {n: {"min": min(h[n] for h in have),
                            "max": max(h[n] for h in have),
                            "seeds": len(have)} for n in have[0]}
    print(json.dumps({"summary": summary, "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
