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


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: takes tens of seconds or real replicas; left out of the "
        "tier-1 run (-m 'not slow'), run by the ci/check_*.sh gates")


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


@pytest.fixture(autouse=True)
def _jax_compile_cache_as_found():
    """The exec cache's disk tier is on for the whole run (above), and
    the first Executor bind of a process then turns jax's persistent
    compilation cache on (`exec_cache_disk.configure_jax_cache`):
    process-wide, so every LATER test of that worker compiled through
    it, whichever file it came from. On this CPU backend an executable
    read back from that cache cannot be serialized again: a bundle
    saved from one (tests/test_quant.py's int8 bundles) failed at its
    first call, `NOT_FOUND: Function ... not found`, but only in a
    worker that had bound an Executor before. A test leaves the
    cache's configuration as it found it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    if all(getattr(jax.config, n) == v for n, v in was.items()):
        return
    from mxnet_tpu import exec_cache_disk

    for n, v in was.items():
        jax.config.update(n, v)
    exec_cache_disk._jax_cache_configured_for = None
    compilation_cache.reset_cache()
