"""perfbench/run.py — the one command behind BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about one cell is data found by name: the cell's entry in
BENCHMARK.json names its configuration (a file under configs/, its
plain reference beside it under references/) and its traffic mix (a
file under traffic/); the configuration's "kind" names the runner under
harness/ (kind_<kind>.py); each per-layer metric is a reader under
metrics/<name>.py. A later PR adds cells and metrics by adding files
and entries, and edits nothing here.

The run refuses to start without a TPU (exit 3, no result line). Its
last stdout line is the result object; the line before it holds the
run's counts (steps or requests, fenced seconds, compilations and
epoch ends inside the window, peak bytes).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import common  # noqa: E402  (needs ROOT on the path)

if __name__ == "__main__":
    sys.exit(common.main(sys.argv[1:], root=ROOT))
