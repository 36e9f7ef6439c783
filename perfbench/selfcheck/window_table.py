"""One row per run from the logs of a set of serving runs: the end-to-end
metrics beside what the window was made of (share of steps per decode
program, prefills, host stalls), as PERF.md's tables have them.

    python3 perfbench/selfcheck/window_table.py <stdout file of a run> [...]

Each file holds one run's stdout (its counts line and its result line).
"""
import json
import sys


def read_run(path):
    counts = line = None
    with open(path) as f:
        for text in f:
            text = text.strip()
            if text.startswith('{"counts"'):
                counts = json.loads(text)["counts"]
            elif text.startswith("{") and '"metrics"' in text:
                line = json.loads(text)
    return counts, line


def row(counts, line):
    progs = counts.get("programs", {})
    share = " ".join(f"{p.replace('jit_decode_', '')} {v['share_pct']}"
                     for p, v in progs.items())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    gap = (line.get("checks", {}).get("served_logit_gap") or [None])[0]
    return [counts["seed"], f"{counts['generate_throughput']:.2f}",
            f"{m['tpot_p95_ms']:.2f}" if "tpot_p95_ms" in m else "-",
            f"{counts['setup_s']:.1f}", share, counts["engine_steps"],
            counts.get("prefills_in_window", counts["prefills"]),
            counts.get("prefill_ms_total"), counts["requests_finished"],
            counts["tokens"], counts.get("turn_ms_median"),
            (counts.get("turn_excess_ms_longest") or [None])[0],
            counts.get("turn_excess_ms_total"), counts["preemptions"],
            counts["compilations_in_window"],
            None if gap is None else round(gap, 4), line["correct"]]


HEAD = ["seed", "tokens/s", "tpot_p95_ms", "setup_s", "steps by program %",
        "steps", "prefills", "prefill ms", "finished", "tokens",
        "turn ms", "longest stall ms", "stalls ms", "preempt", "compiles",
        "logit gap", "correct"]


def main(argv):
    print("| " + " | ".join(HEAD) + " |")
    print("|" + " --- |" * len(HEAD))
    for path in argv:
        counts, line = read_run(path)
        if counts is None or line is None:
            print(f"| {path}: no result |")
            continue
        print("| " + " | ".join(str(x) for x in row(counts, line)) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
