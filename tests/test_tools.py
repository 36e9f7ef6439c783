"""Aux tooling tier (reference tools/: parse_log, bandwidth; round-2
verdict missing #9 / weak #10): log parsing correctness + the two
benchmark tools run and emit parseable JSON."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


def test_parse_log_summarizes_epochs(tmp_path):
    import parse_log

    log = """\
INFO Epoch[0] Batch [20]\tSpeed: 100.00 samples/sec\tTrain-accuracy=0.1
INFO Epoch[0] Batch [40]\tSpeed: 300.00 samples/sec\tTrain-accuracy=0.2
INFO Epoch[0] Train-accuracy=0.250000
INFO Epoch[0] Time cost=12.500
INFO Epoch[0] Validation-accuracy=0.300000
INFO Epoch[1] Train-accuracy=0.500000
INFO Epoch[1] Time cost=11.000
INFO Epoch[1] Validation-accuracy=0.550000
"""
    rows, cols = parse_log.parse(log.splitlines())
    assert [r["epoch"] for r in rows] == [0, 1]
    assert rows[0]["train-accuracy"] == 0.25
    assert rows[0]["val-accuracy"] == 0.3
    assert rows[0]["time"] == 12.5
    assert rows[0]["speed"] == 200.0  # mean of the two speedometer lines
    assert rows[1]["val-accuracy"] == 0.55
    md = parse_log.render(rows, cols, "markdown")
    assert "epoch" in md and "0.55" in md
    csv = parse_log.render(rows, cols, "csv")
    assert csv.splitlines()[0].startswith("epoch,")


def _run_tool(name, args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", name)] + args,
        env=env, capture_output=True, text=True, timeout=540,
        cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]


def test_bandwidth_tool_emits_json():
    recs = _run_tool("bandwidth.py", ["--size-mb", "4", "--iters", "2"])
    metrics = {r["metric"] for r in recs}
    assert {"host_to_device", "device_to_host",
            "kvstore_push_pull"} <= metrics
    assert all(r["value"] > 0 for r in recs)


def test_io_bench_tool_emits_json():
    recs = _run_tool("io_bench.py", ["--num-images", "32", "--side",
                                     "64", "--threads", "1,2",
                                     "--batch-size", "16"])
    assert len(recs) == 2
    assert all(r["metric"] == "image_record_decode" and r["value"] > 0
               for r in recs)


# ---------------------------------------------------------- launchers


class _LaunchArgs:
    num_workers = 3
    env = ["FOO=bar baz"]
    command = ["python", "train.py", "--lr", "0.1"]
    port = 12345
    hostfile = None


def test_sge_script_shape():
    import launch

    script = launch._sge_script(_LaunchArgs(), 12345, "/shared/rdv")
    assert "#$ -t 1-3" in script
    assert "WID=$((SGE_TASK_ID-1))" in script
    assert 'MXNET_TPU_COORDINATOR="$(cat /shared/rdv):12345"' in script
    assert "export MXNET_TPU_NUM_WORKERS=3" in script
    assert "export FOO='bar baz'" in script
    assert script.rstrip().endswith("exec python train.py --lr 0.1")


def test_yarn_command_quoting():
    import shlex

    import launch

    cmd = launch._yarn_command(_LaunchArgs(), 12345, "/shared/rdv")
    assert cmd[:2] == ["yarn", "jar"]
    assert "$HADOOP_HOME" not in cmd[2]  # env expanded, not literal
    assert cmd[cmd.index("-num_containers") + 1] == "3"
    shell = cmd[cmd.index("-shell_command") + 1]
    assert shell.startswith("bash -c ")
    # the script must survive one level of shell evaluation intact:
    # after the container shell splits `bash -c <quoted>`, the payload
    # still contains the UNEXPANDED claim loop and rendezvous read
    payload = shlex.split(shell[len("bash -c "):])[0] if shell[
        len("bash -c ")] in "'\"" else shell[len("bash -c "):]
    inner = shlex.split("bash -c " + shlex.quote(payload))
    assert "mkdir /shared/rdv.claim.$i" in payload
    assert '$(cat /shared/rdv):12345' in payload
    assert inner  # quoting round-trips


def test_kill_mxnet_dry_run():
    import subprocess as sp
    import time

    marker = "kmx_sentinel_sleep"
    victim = sp.Popen([sys.executable, "-c",
                       f"import time  # {marker}\ntime.sleep(60)"])
    try:
        time.sleep(0.5)
        proc = sp.run(
            [sys.executable, os.path.join(ROOT, "tools/kill_mxnet.py"),
             "-p", marker, "--dry-run"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert f"would kill {victim.pid}" in proc.stdout
        assert victim.poll() is None  # dry run left it alive

        proc = sp.run(
            [sys.executable, os.path.join(ROOT, "tools/kill_mxnet.py"),
             "-p", marker],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        victim.wait(timeout=10)  # killed for real
    finally:
        if victim.poll() is None:
            victim.kill()
