"""Test configuration: run the whole suite on a virtual 8-device CPU mesh
so multi-chip sharding semantics are exercised without TPU hardware
(analog of the reference testing multi-device semantics with
mx.cpu(0)/mx.cpu(1), tests/python/unittest/test_model_parallel.py).
Must set flags before jax is imported anywhere.
"""
import os

# The suite — including every subprocess tests spawn (tools, examples,
# launch.py workers), which inherit this through os.environ — runs on
# the CPU backend. That also makes it a CPU-PINNED process, the one
# place where mx.tpu(n) degrades to CPU device n (context.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Pre-bind graph verification is always on under test: every
# Executor._build in the suite runs mxnet_tpu.analysis.verify_graph
# (shape/dtype contradictions, duplicate args, donation aliasing)
# before tracing. Subprocesses inherit it through os.environ.
os.environ.setdefault("MXNET_GRAPH_VERIFY", "1")
# Calibration harvests (serving/decode warmups, Module.fit) persist
# measured timings to MXNET_CALIBRATION_CACHE; point the suite at a
# throwaway path so tests neither read the developer's ~/.cache table
# nor leave their toy-graph timings behind for real runs.
import tempfile as _tempfile  # noqa: E402

os.environ.setdefault(
    "MXNET_CALIBRATION_CACHE",
    os.path.join(_tempfile.mkdtemp(prefix="mx_test_calib_"),
                 "calibration.json"))
# The exec-cache disk tier (MXNET_EXEC_CACHE_DIR) must be per-run
# under test — UNCONDITIONAL assignment, not setdefault: a developer's
# ambient cache dir would let one run's serialized executables leak
# into the next and skew the exact trace/compile counts many tests
# pin. Within one run the same dir is shared (subprocess round-trip
# tests rely on inheriting it), and the in-process self-written skip
# keeps same-process counts identical to the no-disk world.
os.environ["MXNET_EXEC_CACHE_DIR"] = _tempfile.mkdtemp(
    prefix="mx_test_exec_cache_")

import pytest  # noqa: E402

# Threaded test modules run under the runtime lock witness in raise
# mode: a genuine lock-order cycle anywhere in serving/decoding/data/
# telemetry surfaces as LockOrderViolation at the acquisition attempt
# that completes it, instead of a rare hang. Witness-owned tests
# (test_concurrency_analysis) manage install/uninstall themselves and
# are excluded; everything else keeps the zero-overhead unpatched
# factories.
_WITNESS_MODULES = {
    "test_serving", "test_decoding", "test_data_pipeline",
    "test_telemetry", "test_fleet",
}


@pytest.fixture(autouse=True)
def _lock_witness(request):
    if request.module.__name__ not in _WITNESS_MODULES:
        yield
        return
    from mxnet_tpu.analysis import lockwitness

    was_installed = lockwitness.is_installed()
    lockwitness.install("raise")
    try:
        yield
    finally:
        if not was_installed:
            lockwitness.uninstall()
