"""The tiny-size rehearsal of a cell on the CPU: the same runner, entry
and check as on the chip, with the configuration and the traffic swapped
for the toy files under selfcheck/tiny/ and the look for a chip skipped.
Its last line is well formed and holds no device metric.

    python3 perfbench/selfcheck/rehearse.py <cell> [chips] [seconds]
"""
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def pin_cpu(devices=4):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={devices}")


def rehearse(cell_name, seconds=1.5, seed=2147483999, hooks=None,
             limits=None):
    """Run one cell at toy size; returns (result line as a dict, all of
    stdout as text)."""
    pin_cpu()
    from perfbench.harness import common

    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    tiny = os.path.join(ROOT, "perfbench/selfcheck/tiny")
    cfg = common.load_json(os.path.join(tiny, cell["config"] + ".json"))
    if limits:
        cfg["check"]["limits"].update(limits)
    tname = "synth_resident" if cfg["kind"] == "train_symbol" \
        else cell["traffic"]
    tr = common.load_json(os.path.join(tiny, tname + ".json"))
    common.place_caches(ROOT)
    ctx = common.Context(ROOT, bench, cell, seed, seconds, 0,
                         rehearsal=True, config=cfg, traffic=tr)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        common.run_cell(ctx, hooks)
    text = buf.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text


if __name__ == "__main__":
    name = sys.argv[1]
    secs = float(sys.argv[2]) if len(sys.argv) > 2 else 1.5
    line, text = rehearse(name, secs)
    sys.stdout.write(text)
