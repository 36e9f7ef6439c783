"""What keeps a CPU or interpreter run from passing for a chip run.

Two halves:

  * every Pallas kernel on the train/decode paths compiled — by the
    TPU's own compiler, for a DESCRIBED v5e chip, no chip attached
    (jax.experimental.topologies) — at the widths chip_smoke.py uses.
    Interpret mode proves a kernel's arithmetic, not that Mosaic takes
    it: these are the cases a later PR breaks without noticing.
    Skipped (not failed) where the topology cannot be described.
  * the rules that stop a missing TPU from degrading quietly: context
    resolution, Module placement, the compile-cache helper, bench.py
    and chip_smoke.py without a chip.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import exec_cache_disk, passes, utils
from mxnet_tpu.context import resolve_device
from mxnet_tpu.decoding import attention as paged
from mxnet_tpu.decoding import quant
from mxnet_tpu.parallel.attention import attention
from mxnet_tpu.passes import pallas_codegen as pc
from mxnet_tpu.passes.ir import Graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

FULL = chip_smoke.SIZES["full"]


# ------------------------------------------------- compiled for a v5e
@pytest.fixture(scope="module")
def v5e():
    """SingleDeviceSharding on one chip of a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compile-only use of libtpu: parallel test workers may each load it
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {exc}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(v5e, monkeypatch):
    """compile(fn, *shape_structs) -> compiled text, with every Pallas
    call built for the chip (the code under test asks
    utils.pallas_interpret, which here would see the CPU) and jax's
    persistent cache off: an executable compiled for a described chip
    is written to it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(utils, "pallas_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args):
        placed = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            args)
        return jax.jit(fn).lower(*placed).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_flash_forward_compiles(compile_for_chip):
    q = _s(FULL["flash"], jnp.bfloat16)
    text = compile_for_chip(
        lambda q, k, v: attention(q, k, v, causal=True, impl="flash"),
        q, q, q)
    assert "tpu_custom_call" in text


def _paged_args(kv_dtype, rows, bucket):
    dcfg, spec = FULL["decoder"], FULL["serve"]
    h = dcfg["n_heads"]
    d = dcfg["d_model"] // h
    n, p = spec["num_pages"], spec["page_size"]
    if kv_dtype == "int8":
        pool = quant.KVPool(_s((n, p, h, d), jnp.int8),
                            _s((n, p, h), jnp.float32))
    else:
        pool = quant.KVPool(_s((n, p, h, d), quant.storage_dtype(kv_dtype)),
                            None)
    return (_s((rows, h, d), jnp.float32), pool, pool,
            _s((rows, bucket), jnp.int32), _s((rows,), jnp.int32))


@pytest.mark.parametrize("kv_dtype", ["float32", "bf16", "int8"])
@pytest.mark.parametrize("bucket", FULL["serve"]["page_buckets"])
def test_paged_attention_compiles(compile_for_chip, kv_dtype, bucket):
    args = _paged_args(kv_dtype, FULL["serve"]["max_batch"], bucket)
    text = compile_for_chip(paged.paged_attention_pallas, *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_ragged_paged_attention_compiles(compile_for_chip, kv_dtype):
    """The merged step's shape: max_batch decode rows plus a page of
    tail-prefill rows through the ragged entry."""
    spec = FULL["serve"]
    args = _paged_args(kv_dtype, spec["max_batch"] + spec["page_size"],
                       spec["page_buckets"][-1])
    text = compile_for_chip(paged.get_ragged_kernel("pallas"), *args)
    assert "tpu_custom_call" in text


def _group_spec(net):
    graph = Graph.from_symbol(passes.optimize(net, collect_stats=False))
    (members,) = pc._groups_in(graph.nodes).values()
    return pc._group_spec(graph.nodes, sorted(members))


@pytest.mark.parametrize("template,dtype", [
    ("elementwise", "float32"), ("elementwise", "bfloat16"),
    ("scale_bias_act", "float32"), ("reduction", "float32"),
])
def test_codegen_template_compiles(compile_for_chip, template, dtype):
    """Each surviving template's generated kernel, the smoke's graph
    and shape, through the emitter the codegen stage calls."""
    spec, ext = _group_spec(chip_smoke._codegen_nets()[template])
    assert pc._template_of(spec) == template
    avals = [(FULL["codegen_shape"], jnp.dtype(dtype))] * len(ext)
    structs = [_s(s, d) for s, d in avals]
    out_aval = jax.eval_shape(pc.group_lax_fn(spec), *structs)
    kernel = pc._EMITTERS[template](spec, avals, out_aval,
                                    utils.pallas_interpret())
    assert "tpu_custom_call" in compile_for_chip(kernel, *structs)


def test_codegen_blocks_fill_vmem_not_one_tile():
    """A ResNet-sized activation is a few hundred grid steps, not one
    (8, 128) register tile per step."""
    r, c = pc._norm2d((256, 56, 56, 256))
    block, grid = pc._tiling(r, c, np.float32, False, n_operands=2)
    assert block[0] % 8 == 0 and block[1] % 128 == 0
    assert r % block[0] == 0 and c % block[1] == 0
    assert grid[0] * grid[1] < 1000
    assert block[0] * block[1] * 4 * 2 <= pc._BLOCK_BYTES
    with pytest.raises(pc._Unsupported, match="irregular_shapes"):
        pc._tiling(5, 7, np.float32, False, n_operands=2)


def test_rtc_pallas_kernel_compiles(compile_for_chip):
    def double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    fn = mx.rtc.PallasKernel("double", double_kernel).compiled([(8,)])
    assert "tpu_custom_call" in compile_for_chip(
        fn, _s((8,), jnp.float32))


def test_refused_kernel_is_counted_compile_refused(monkeypatch):
    """A compiler refusal at build time is caught there, under its own
    reason — not inside the step's compile, not as irregular_shapes."""
    monkeypatch.setenv("MXNET_FUSION_INTERPRET", "1")

    def refusing(spec, ext_avals, out_aval, interpret):
        def kernel(*vals):
            raise RuntimeError("Mosaic says no")
        return kernel

    monkeypatch.setitem(pc._EMITTERS, "elementwise", refusing)
    passes.reset_fusion_stats()
    passes.clear_memo()
    mx.exec_cache.clear()
    net = chip_smoke._codegen_nets()["elementwise"]
    exe = net.simple_bind(mx.cpu(), x=(8, 128), y=(8, 128))
    assert passes.fusion_stats()["fallback_reasons"] == \
        {"compile_refused": 1}
    # the group still runs, through its lax twin
    exe.forward(is_train=False, x=mx.nd.ones((8, 128)),
                y=mx.nd.ones((8, 128)))
    assert float(exe.outputs[0].asnumpy().max()) == 0.0
    passes.reset_fusion_stats()
    passes.clear_memo()
    mx.exec_cache.clear()


# ------------------------------------------- no fallback hides the device
class _Dev:
    def __init__(self, platform, i):
        self.platform, self.id = platform, i


CPUS = [_Dev("cpu", i) for i in range(8)]
ONE_TPU = [_Dev("tpu", 0)]


def test_pallas_interprets_only_off_the_tpu(monkeypatch):
    assert utils.pallas_interpret()          # this process: CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not utils.pallas_interpret()
    monkeypatch.setenv("MXNET_FUSION_INTERPRET", "1")
    assert utils.pallas_interpret()


@pytest.mark.parametrize("device_type,device_id,devices", [
    ("tpu", 0, []),           # no TPU in an unpinned process
    ("tpu", 0, CPUS),         # ... and CPUs are not one
    ("tpu", 1, ONE_TPU),      # tpu(1) on a one-chip host
    ("gpu", 0, ONE_TPU),      # a TPU is not a GPU either
])
def test_unpinned_context_without_its_device_raises(device_type,
                                                    device_id, devices):
    with pytest.raises(mx.MXNetError):
        resolve_device(device_type, device_id, devices,
                       pinned_to_cpu=False)


def test_context_resolution_rules():
    assert resolve_device("tpu", 0, ONE_TPU, False) is ONE_TPU[0]
    # host contexts are nominal: ids wrap, pinned or not
    assert resolve_device("cpu", 9, CPUS, False) is CPUS[1]
    # the test tier (JAX_PLATFORMS=cpu): accelerator contexts degrade
    # to the virtual CPU mesh
    assert resolve_device("tpu", 3, CPUS, True) is CPUS[3]
    assert resolve_device("tpu", 11, CPUS, True) is CPUS[3]
    # ... which is what this process is
    assert mx.tpu(2).jax_device().platform == "cpu"


def _two_context_module(contexts, **kw):
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=contexts, **kw)
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    return mod


def test_module_contexts_on_one_device_raise():
    n = len(jax.devices())
    mod = _two_context_module([mx.tpu(0), mx.tpu(n)])  # wraps onto 0
    with pytest.raises(mx.MXNetError, match="distinct device"):
        mod.init_optimizer(kvstore="tpu")
    # distinct devices still build the fused mesh step
    mod = _two_context_module([mx.tpu(0), mx.tpu(1)])
    mod.init_optimizer(kvstore="tpu")
    assert mod._fused_step._mesh.devices.size == 2


def test_module_mesh_it_cannot_build_raises():
    n = len(jax.devices())
    mod = _two_context_module(mx.cpu(), mesh_shape={"data": 2 * n})
    with pytest.raises(mx.MXNetError, match="mesh_shape"):
        mod.init_optimizer(kvstore="tpu")


# -------------------------------------------------- compile-cache helper
@pytest.fixture
def restore_jax_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_honours_environment(monkeypatch, tmp_path,
                                          restore_jax_cache_dir):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert exec_cache_disk.place_jax_cache() == str(tmp_path / "c")
    assert exec_cache_disk.place_jax_cache(
        default=str(tmp_path / "other")) == str(tmp_path / "c")
    # nothing was set in code: jax keeps what it read at start-up
    assert jax.config.jax_compilation_cache_dir == was
    assert not (tmp_path / "other").exists()


def test_cache_helper_default_is_fixed_in_checkout(monkeypatch, tmp_path,
                                                   restore_jax_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert exec_cache_disk.DEFAULT_JAX_CACHE_DIR == \
        os.path.join(REPO, ".jax_cache")
    got = exec_cache_disk.place_jax_cache(default=str(tmp_path / "d"))
    assert got == str(tmp_path / "d") and os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got


# ------------------------------------------- entry points without a chip
def _run(script, *args, **env):
    child_env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        env=child_env, capture_output=True, text=True, timeout=300)


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    proc = _run("chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "needs a TPU" in proc.stderr


def test_bench_without_a_tpu_exits_nonzero():
    proc = _run("bench.py", BENCH_PLATFORM="")
    assert proc.returncode != 0
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "bench_error" and "TPU" in rec["error"]


def test_bench_unknown_device_kind_is_an_error():
    import bench

    class Dev:
        platform, device_kind = "tpu", "TPU v99 mega"

    with pytest.raises(RuntimeError, match="no peak-FLOP/s row"):
        bench._detect_peak_flops(Dev())
    Dev.device_kind = "TPU v5 lite"
    assert bench._detect_peak_flops(Dev()) == 197e12
