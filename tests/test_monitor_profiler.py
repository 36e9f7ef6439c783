"""Monitor / profiler / visualization tests (reference
tests/python/unittest/test_profiler.py, test_monitor idioms,
test_viz.py)."""
import json
import os

import numpy as np

import mxnet_tpu as mx


def _net():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    act = mx.sym.Activation(fc, act_type="relu", name="relu")
    return act


def test_monitor_collects_stats():
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
    mon = mx.Monitor(interval=1, pattern=".*")
    mon.install(ex)
    ex.arg_dict["fc_weight"][:] = np.ones((4, 3), np.float32)
    mon.tic()
    ex.forward(data=np.ones((2, 3), np.float32))
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert any("fc_output" in n for n in names)
    assert any("relu_output" in n for n in names)
    assert "fc_weight" in names


def test_monitor_pattern_filter():
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
    mon = mx.Monitor(interval=1, pattern=".*relu.*")
    mon.install(ex)
    mon.tic()
    ex.forward(data=np.ones((2, 3), np.float32))
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert names and all("relu" in n for n in names)


def test_profiler_chrome_trace(tmp_path):
    fn = str(tmp_path / "profile.json")
    mx.profiler.profiler_set_config(mode="symbolic", filename=fn)
    mx.profiler.profiler_set_state("run")
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
    ex.forward(data=np.ones((2, 3), np.float32))
    mx.profiler.profiler_set_state("stop")
    assert os.path.exists(fn)
    with open(fn) as f:
        trace = json.load(f)
    assert "traceEvents" in trace
    names = {e["name"] for e in trace["traceEvents"]}
    assert any("executor_forward" in n for n in names)


def _capture(name, ops, modules):
    """One device of a capture in the plain-list form
    profiling.timeline.read_xplane returns (seconds)."""
    return {"name": name,
            "ops": [(n, n, t0, t1) for n, t0, t1 in ops],
            "modules": [(n, n, t0, t1) for n, t0, t1 in modules]}


def test_collect_device_events_rebase(tmp_path, monkeypatch):
    """_collect_device_events on a synthetic capture: every device
    gets a process lane of its own (pid 1001.. next to the host's
    pid 0) and every ts is re-based by trace_t0_us onto the host
    timeline — proven here without a real device capture, at the
    plain-list seam (a CPU capture has no device plane)."""
    from mxnet_tpu import profiler
    from mxnet_tpu.profiling import timeline

    raw = {"devices": [
        _capture("/device:TPU:0", [("fusion", 10e-6, 15e-6)],
                 [("jit_a(1)", 0.0, 1.0)]),
        _capture("/device:TPU:1", [("copy", 20.5e-6, 21.5e-6)],
                 [("jit_a(1)", 0.0, 1.0)])], "host": []}
    monkeypatch.setattr(timeline, "read_xplane", lambda d: raw)
    monkeypatch.setitem(profiler._state, "trace_t0_us", 1000.0)
    out = profiler._collect_device_events(str(tmp_path))

    by_name = {e["name"]: e for e in out}
    assert by_name["fusion"]["pid"] == 1001
    assert by_name["copy"]["pid"] == 1002
    assert abs(by_name["fusion"]["ts"] - 1010.0) < 1e-6  # 10 + t0
    assert abs(by_name["copy"]["ts"] - 1020.5) < 1e-6
    assert abs(by_name["fusion"]["dur"] - 5.0) < 1e-6
    # the launch that covers an operation is named; without a scope
    # map for that module the operation is unscoped
    assert by_name["fusion"]["args"] == {"module": "jit_a",
                                         "scope": "unscoped"}


def test_collect_device_events_newest_capture(tmp_path):
    """Of several captures under one directory only the NEWEST is
    read (real `.xplane.pb` files of two CPU captures; their host
    plane holds the telemetry spans open during each)."""
    import os as _os
    import time as _time

    import jax

    from mxnet_tpu import telemetry
    from mxnet_tpu.profiling import timeline

    for name in ("monitor.first_capture", "monitor.second_capture"):
        jax.profiler.start_trace(str(tmp_path))
        with telemetry.span(name):
            pass
        jax.profiler.stop_trace()
        _time.sleep(1.1)   # run directories are named by the second
    paths = sorted(
        _os.path.join(r, f) for r, _, fs in _os.walk(str(tmp_path))
        for f in fs if f.endswith(".xplane.pb"))
    assert len(paths) == 2
    _os.utime(paths[0], (1, 1))
    raw = timeline.read_xplane(str(tmp_path))
    assert raw["path"] == paths[1]
    names = {n for n, _, _ in raw["host"]}
    assert "monitor.second_capture" in names
    assert "monitor.first_capture" not in names
    # a CPU capture has no device plane: nothing to merge
    assert raw["devices"] == []
    assert timeline.device_slices(raw) == []


def test_collect_device_events_empty_dir(tmp_path):
    from mxnet_tpu import profiler

    assert profiler._collect_device_events(str(tmp_path)) == []


def test_dump_profile_keeps_events_on_write_failure(tmp_path):
    """A failed dump must neither drop the buffered events nor leave a
    torn file: the write goes through tmp + os.replace and the buffer
    is cleared only after the rename succeeded."""
    ok = str(tmp_path / "ok.json")
    mx.profiler.profiler_set_config(filename=ok)
    mx.profiler.profiler_set_state("run")
    with mx.profiler.scope("durable-region"):
        pass
    mx.profiler._state["running"] = False  # no auto-dump via stop
    bad_dir = str(tmp_path / "missing-dir" / "x.json")
    mx.profiler.profiler_set_config(filename=bad_dir)
    try:
        mx.profiler.dump_profile()
        raise AssertionError("dump into a missing dir must raise")
    except OSError:
        pass
    # no tmp litter from the failed attempt
    assert not list((tmp_path / "missing-dir").parent.glob("*.tmp.*"))
    mx.profiler.profiler_set_config(filename=ok)
    mx.profiler.dump_profile()
    with open(ok) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert "durable-region" in names


def test_scope_latches_record_decision(tmp_path):
    """A region that began while the profiler was running is recorded
    even when collection stops before __exit__ (the old behavior
    silently dropped it); symmetrically a region opened before 'run'
    stays out of the profile."""
    fn = str(tmp_path / "latch.json")
    mx.profiler.profiler_set_config(filename=fn)

    # opened before run -> stays out even though running at exit
    pre = mx.profiler.scope("born-too-early")
    pre.__enter__()
    mx.profiler.profiler_set_state("run")
    pre.__exit__(None, None, None)

    # opened during run, profiler stopped mid-region -> recorded
    mid = mx.profiler.scope("born-during-run")
    mid.__enter__()
    mx.profiler._state["running"] = False
    mid.__exit__(None, None, None)

    mx.profiler._state["running"] = True
    mx.profiler.profiler_set_state("stop")
    with open(fn) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert "born-during-run" in names
    assert "born-too-early" not in names


def test_stop_without_run_is_noop(tmp_path, monkeypatch):
    """profiler_set_state('stop') in a process where collection never
    ran must not write a profile file (defensive stop() calls were
    polluting the cwd with empty profile.json)."""
    import subprocess
    import sys

    code = (
        "import mxnet_tpu as mx\n"
        "out = mx.profiler.profiler_set_state('stop')\n"
        "assert out is None, out\n"
        "import os\n"
        "assert not os.path.exists('profile.json')\n"
    )
    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(mx.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr


def test_profiler_merges_device_trace(tmp_path, monkeypatch):
    """With a device capture enabled, the dumped Chrome trace must be
    ONE file holding both host events (pid 0) and the capture's
    device timeline (pids 1001..) — reference emits a single unified
    trace (src/engine/profiler.cc:134); round-2 flagged the split
    artifact."""
    from mxnet_tpu.profiling import timeline

    fn = str(tmp_path / "merged.json")
    trace_dir = str(tmp_path / "xla")
    monkeypatch.setenv("MXNET_TPU_XLA_TRACE_DIR", trace_dir)
    real_read = timeline.read_xplane

    def read_with_a_device(d):
        # the real capture is read for real; a CPU capture has no
        # device plane, so one operation 1 ms into the capture stands
        # in for it at the plain-list seam
        raw = real_read(d)
        assert raw is not None and raw["devices"] == []
        raw["devices"] = [_capture("/device:TPU:0",
                                   [("fusion.1", 1e-3, 2e-3)],
                                   [("jit_fwd(1)", 0.0, 1.0)])]
        return raw

    monkeypatch.setattr(timeline, "read_xplane", read_with_a_device)
    mx.profiler.profiler_set_config(mode="symbolic", filename=fn)
    mx.profiler.profiler_set_state("run")
    net = _net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
    ex.forward(data=np.ones((2, 3), np.float32))
    mx.profiler.profiler_set_state("stop")
    with open(fn) as f:
        trace = json.load(f)
    pids = {e.get("pid") for e in trace["traceEvents"]}
    assert 0 in pids  # host events
    # a device capture produced SOMETHING under the trace dir
    assert os.path.isdir(trace_dir) and os.listdir(trace_dir)
    device_pids = {p for p in pids if isinstance(p, int) and p >= 1000}
    assert device_pids, (
        "device timeline not merged into the host trace")
    # one clock: device events must be re-based onto the host timeline
    # (overlapping the host events' window, not at capture-relative 0)
    host_ts = [e["ts"] for e in trace["traceEvents"]
               if e.get("pid") == 0]
    dev_ts = [e["ts"] for e in trace["traceEvents"]
              if isinstance(e.get("pid"), int) and e["pid"] >= 1000
              and isinstance(e.get("ts"), (int, float))]
    if dev_ts:
        # all device work happened after profiling started
        assert min(dev_ts) >= min(host_ts) - 1e6


def test_print_summary(capsys):
    net = mx.sym.SoftmaxOutput(_net(), name="sm")
    total = mx.visualization.print_summary(
        net, shape={"data": (2, 3)}
    )
    out = capsys.readouterr().out
    assert "fc(FullyConnected)" in out
    # fc: 4*3 weight + 4 bias = 16 params
    assert total == 16
