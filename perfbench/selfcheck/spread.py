"""Spreads of a set of runs, as the bounds are set from them: for each
metric the distance between the first and third quartile
(statistics.quantiles(values, n=4)) as a share of the median.

    python3 perfbench/selfcheck/spread.py <file of result lines> [...]

Each file holds the last stdout lines of the runs of ONE set of one cell
(one JSON object a line, as perfbench/run.py prints them).
"""
import json
import statistics
import sys


def read_set(path):
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"metrics"' in line:
                rows.append(json.loads(line))
    return rows


def spreads(rows):
    out = {}
    names = sorted({n for r in rows for n in r["metrics"]})
    for n in names:
        vals = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
        if len(vals) < 2:
            continue
        q = statistics.quantiles(vals, n=4)
        med = statistics.median(vals)
        out[n] = {"n": len(vals), "median": med, "iqr": q[2] - q[0],
                  "spread": (q[2] - q[0]) / med, "min": min(vals),
                  "max": max(vals)}
    return out


def main(argv):
    for path in argv:
        rows = read_set(path)
        print(path, f"{len(rows)} runs, correct:",
              [r["correct"] for r in rows])
        for n, s in spreads(rows).items():
            print(f"  {n:22s} median {s['median']:.6g}  iqr {s['iqr']:.4g}"
                  f"  spread {100 * s['spread']:.3f}%  "
                  f"[{s['min']:.6g} .. {s['max']:.6g}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
