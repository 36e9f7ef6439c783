"""A tiny-size rehearsal of every cell's command on the CPU ends with a
well-formed last line and reports no device metric; the command itself
refuses to run off the chip."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.selfcheck import rehearse as rh  # noqa: E402

rh.pin_cpu()
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TINY_CELLS = ["resnet50_b256_synth", "resnet50_dp4_b1024_synth",
              "opt1p3b_chat_closed", "opt1p3b_docqa_closed"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_ends_well_formed(cell):
    line, text = rh.rehearse(cell)
    assert KEYS <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}          # never a device metric off the chip
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    counts = json.loads(text.strip().splitlines()[-2])["counts"]
    assert counts["compilations_in_window"] == 0
    assert counts["fenced_seconds"] > 0
    if "programs" in counts:    # serving: the share of steps per program
        assert abs(sum(v["share_pct"] for v in counts["programs"].values())
                   - 100.0) < 0.1
        assert counts["prefills_in_window"] <= counts["prefills"]
    if "epoch_ends_in_window" in counts:
        assert counts["epoch_ends_in_window"] == 0
        assert counts["steps"] == line["attempted"]


def test_every_cell_of_the_benchmark_is_rehearsed():
    assert sorted(w["name"] for w in BENCH["workloads"]) \
        == sorted(c for c in TINY_CELLS
                  if c in {w["name"] for w in BENCH["workloads"]})


def test_the_command_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        BENCH["command"] + ["--workload", cell, "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs" in p.stderr and "TPU" in p.stderr
