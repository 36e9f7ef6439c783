"""What keeps a CPU or interpreter run from passing for a chip run.

Two halves:

  * every Pallas kernel on the train/decode paths compiled — by the
    TPU's own compiler, for a DESCRIBED v5e chip, no chip attached
    (jax.experimental.topologies) — at the widths chip_smoke.py uses.
    Interpret mode proves a kernel's arithmetic, not that Mosaic takes
    it: these are the cases a later PR breaks without noticing.
    Skipped (not failed) where the topology cannot be described.
  * the rules that stop a missing TPU from degrading quietly: context
    resolution, Module placement, the compile-cache helper, and
    chip_smoke.py without a chip.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import exec_cache_disk, utils
from mxnet_tpu.context import resolve_device
from mxnet_tpu.decoding import attention as paged
from mxnet_tpu.decoding import quant
from mxnet_tpu.parallel.attention import attention

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


def _pool_struct(shape, kv_dtype):
    """quant.make_pool's result as shapes only: nothing is allocated."""
    return jax.eval_shape(lambda: quant.make_pool(shape, kv_dtype))


def _paged_args(kv_dtype, rows, bucket):
    dcfg, spec = FULL["decoder"], FULL["serve"]
    h = dcfg["n_heads"]
    d = dcfg["d_model"] // h
    pool = _pool_struct((2, spec["num_pages"], spec["page_size"], h, d),
                        kv_dtype)
    return (_s((rows, h, d), jnp.float32), pool, pool,
            _s((rows, bucket), jnp.int32), _s((rows,), jnp.int32))


def _on_layer(kernel, layer=1):
    """The kernel as a decode program calls it: on one layer of whole
    pools, read by index."""
    return lambda q, k, v, table, lengths: kernel(
        q, k.layer(layer), v.layer(layer), table, lengths)


def _entry_instructions(text):
    """(name, result type, op, line) of each instruction of the compiled
    ENTRY computation: what the program keeps in device memory (what a
    fusion computes inside itself is not listed there). Parameters,
    tuples and views of a buffer are left out."""
    entry = text[text.index("ENTRY "):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(",
                     line)
        if m and m.group(3) not in ("parameter", "tuple",
                                    "get-tuple-element", "bitcast"):
            yield (*m.groups(), line)


def _pool_sized_ops(text, pool_dims, carried=()):
    """Instructions of the compiled ENTRY computation whose result has
    the pool's or one layer's shape and is not an in-place scatter (a
    fusion of a `scatter` whose result aliases its operand): `copy`,
    `fusion` (a relayout or a slice made whole) and the like. The ops
    named in `carried` do not count (a `while` whose loop state holds
    the pool by reference: a copy inside it would show in the
    program's temporaries)."""
    shapes = [",".join(map(str, pool_dims)), ",".join(map(str, pool_dims[1:]))]
    found = []
    for name, result, op, line in _entry_instructions(text):
        if op in carried:
            continue
        if not any(f"[{dims}]" in result for dims in shapes):
            continue
        if op == "fusion" and "/scatter" in line \
                and '"aliasing_operands"' in line:
            continue    # written in place: the result IS operand 0
        found.append(f"{name} = {result} {op}")
    return found


def _context_sized_arrays(text, elements, pool_elements):
    """(name, type, op) of every ENTRY instruction whose result holds
    an array of at least `elements` elements and less than a pool's
    (the donated pools are larger than a context)."""
    found = []
    for name, result, op, _ in _entry_instructions(text):
        for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]", result):
            n = int(np.prod([int(x) for x in dims.split(",")]))
            if elements <= n < pool_elements:
                found.append((name, f"{dtype}[{dims}]", op))
    return found


@pytest.mark.parametrize("kv_dtype", ["float32", "bf16", "int8"])
@pytest.mark.parametrize("bucket", FULL["serve"]["page_buckets"])
def test_paged_attention_compiles(compile_for_chip, kv_dtype, bucket):
    args = _paged_args(kv_dtype, FULL["serve"]["max_batch"], bucket)
    text = compile_for_chip(_on_layer(paged.paged_attention_pallas), *args)
    assert "tpu_custom_call" in text
    # the kernel reads pages from the pool as stored: no relayout of it
    assert not _pool_sized_ops(text, args[1].data.shape)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_attention_compiles_under_a_global_precision(
        compile_for_chip, kv_dtype):
    """bf16 activations on bf16 and int8 pools (the served case), with
    the caller's default matmul precision at `highest` as
    `chip_smoke.py`'s serve phase sets it: the kernel's one-pass
    products name their precision, so Mosaic is not asked for float32
    passes over bf16 operands (which it refuses: found on the chip in
    PR 32, where the float32-query cases above had passed)."""
    q, *rest = _paged_args(kv_dtype, FULL["serve"]["max_batch"],
                           FULL["serve"]["page_buckets"][-1])
    with jax.default_matmul_precision("highest"):
        text = compile_for_chip(_on_layer(paged.paged_attention_pallas),
                                _s(q.shape, jnp.bfloat16), *rest)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_ragged_paged_attention_compiles(compile_for_chip, kv_dtype):
    """The merged step's shape: max_batch decode rows plus a page of
    tail-prefill rows through the ragged entry."""
    spec = FULL["serve"]
    args = _paged_args(kv_dtype, spec["max_batch"] + spec["page_size"],
                       spec["page_buckets"][-1])
    text = compile_for_chip(_on_layer(paged.get_ragged_kernel("pallas")),
                            *args)
    assert "tpu_custom_call" in text


# the chat cell's pool and step (perfbench/traffic/chat_closed.json) at
# OPT-1.3B's head widths; two layers and a small vocabulary, which the
# pool's handling does not depend on
CELL = dict(pages=1152, page_size=16, heads=32, head_dim=64, rows=48,
            bucket=48, layers=4)
SPEC_K = 4


def _cell_engine(kernel="lax", **kw):
    from mxnet_tpu import decoding as dec

    c = CELL
    cfg = dec.DecoderConfig(
        vocab=512, d_model=c["heads"] * c["head_dim"],
        n_layers=c["layers"], n_heads=c["heads"], d_ff=512, max_len=2048)
    if kw.pop("draft", False):
        kw.update(draft_params={}, draft_cfg=cfg, spec_k=SPEC_K)
    eng = dec.DecodeEngine(
        {}, cfg, max_batch=c["rows"], page_size=c["page_size"],
        num_pages=c["bucket"] + 1, page_buckets=(c["bucket"],),
        kernel=kernel, prefix_cache=True, **kw)
    eng._donate = True
    return eng


@pytest.fixture(scope="module")
def cell_engine():
    """A DecodeEngine whose program builders the tests below lower over
    DESCRIBED pools: its own pool is small and its weights are absent
    (a builder closes over neither). Donation is on, as on the chip."""
    return _cell_engine(merged_step=False, draft=True)


def _decoder_param_structs(cfg):
    dm, ff = cfg.d_model, cfg.d_ff
    shapes = {"embed": (cfg.vocab, dm), "pos": (cfg.max_len, dm),
              "ln_f": (dm,)}
    for i in range(cfg.n_layers):
        shapes.update({f"l{i}.ln1": (dm,), f"l{i}.ln2": (dm,),
                       f"l{i}.w1": (dm, ff), f"l{i}.w2": (ff, dm)})
        shapes.update({f"l{i}.{nm}": (dm, dm)
                       for nm in ("wq", "wk", "wv", "wo")})
    return {k: _s(v, jnp.bfloat16) for k, v in shapes.items()}


def _cell_program(eng, program, kv_dtype, pages):
    """(jitted program, argument structs, one pool's struct) of one
    engine program at the cell's shapes over pools of `pages` pages."""
    c = CELL
    pool = _pool_struct((c["layers"], pages, c["page_size"], c["heads"],
                         c["head_dim"]), kv_dtype)
    params = _decoder_param_structs(eng.cfg)
    scalar = [_s((), jnp.uint32), _s((), jnp.float32), _s((), jnp.int32),
              _s((), jnp.float32)]
    r = eng.step_rows
    row = [_s((r,), jnp.uint32), _s((r,), jnp.float32), _s((r,), jnp.int32),
           _s((r,), jnp.float32)]
    rows_in = (_s((r, c["bucket"]), jnp.int32), _s((r,), jnp.int32),
               _s((r,), jnp.bool_))        # page table, lengths, active
    tokens = c["bucket"] * c["page_size"]
    prompt = _s((1, tokens), jnp.int32)
    ids = _s((c["bucket"],), jnp.int32)
    i32 = _s((), jnp.int32)
    if program == "decode":
        fn = eng._build_decode_fn(c["bucket"])
        args = (params, _s((r,), jnp.int32), (pool, pool), *rows_in, *row)
    elif program == "draft":
        fn = eng._build_propose_fn(c["bucket"])
        args = (params, _s((r,), jnp.int32), pool, pool, *rows_in, *row)
    elif program == "verify":
        fn = eng._build_verify_fn(c["bucket"])
        args = (params, _s((r,), jnp.int32), _s((r, SPEC_K), jnp.int32),
                _s((r, SPEC_K, eng.cfg.vocab), jnp.float32), pool, pool,
                *rows_in, _s((r,), jnp.bool_), *row)
    elif program == "prefill":
        fn = eng._build_prefill_fn(tokens)
        args = (params, prompt, i32, (pool, pool), ids, *scalar)
    elif program == "prefill_tail":
        fn = eng._build_tail_fn(tokens)
        args = (params, prompt, i32, i32, (pool, pool), ids, *scalar)
    else:
        fn = eng._build_copy_fn()
        args = (pool, i32, i32)
    return getattr(fn, "fn", fn), args, pool


def _compile_cell_program(v5e, eng, program, kv_dtype, pages):
    """(compiled program, one pool's struct): `_cell_program` compiled
    for the described chip."""
    fn, args, pool = _cell_program(eng, program, kv_dtype, pages)
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        args)
    return fn.lower(*placed).compile(), pool


@pytest.mark.parametrize("program,kv_dtype", [
    (program, kv_dtype)
    for program in ("decode", "prefill", "prefill_tail")
    for kv_dtype in ("bf16", "float32", "int8")
] + [("draft", "bf16"), ("verify", "bf16"), ("copy_page", "int8")])
def test_engine_program_holds_no_pool_sized_copy(v5e, cell_engine, program,
                                                 kv_dtype):
    """A token's write is in place and a read gathers from the pool:
    the compiled program holds nothing of the pool's or a layer's size
    but the scatters on the donated buffers, and its temporaries do not
    grow with the pool."""
    temps = []
    for pages in (CELL["pages"], 2 * CELL["pages"]):
        compiled, pool = _compile_cell_program(v5e, cell_engine, program,
                                               kv_dtype, pages)
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        if pages == CELL["pages"]:
            text = compiled.as_text()
            assert f"jit_{program}" in text.split("\n", 1)[0]
            assert not _pool_sized_ops(text, pool.data.shape)
    # twice the pool: what is left (the gathered contexts) depends on
    # rows and bucket only
    assert temps[1] - temps[0] <= 0.05 * temps[0], temps


@pytest.mark.parametrize("program,kv_dtype", [
    ("decode", "bf16"), ("decode", "float32"), ("decode", "int8"),
    ("draft", "bf16"), ("merged", "bf16")])
def test_single_query_program_attends_the_rows_as_stored(
        v5e, cell_engine, program, kv_dtype):
    """The single-query programs never split a gathered context into
    heads: nothing of the context's size (rows x bucket x page_size x
    heads x head_dim elements) is float32 unless the pool is, nothing
    of that size is a `reshape`, `copy` or `transpose`, and the bf16
    program's temporaries are the gathered rows alone. `merged` is the
    ragged step's decode program: a page of tail rows beside the
    decode rows, each ONE query over its own context."""
    c = CELL
    eng = cell_engine
    if program == "merged":
        eng, program = _cell_engine(merged_step=True), "decode"
    context = (eng.step_rows * c["bucket"] * c["page_size"]
               * c["heads"] * c["head_dim"])
    compiled, pool = _compile_cell_program(v5e, eng, program,
                                           kv_dtype, c["pages"])
    arrays = _context_sized_arrays(compiled.as_text(), context,
                                   pool.data.size)
    assert arrays, "the gathered rows themselves should be listed"
    if kv_dtype != "float32":
        assert not [a for a in arrays if a[1].startswith("f32")], arrays
    assert not [a for a in arrays
                if a[2] in ("reshape", "copy", "transpose")], arrays
    if kv_dtype == "bf16":
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= 0.25 * 2 ** 30, temp


@pytest.mark.parametrize("program,kv_dtype", [
    ("decode", "bf16"), ("decode", "int8"), ("merged", "bf16")])
def test_kernel_program_holds_no_gathered_context(
        v5e, compile_for_chip, program, kv_dtype):
    """With the in-place kernel (the tier's default on a TPU) a
    single-query program holds one `tpu_custom_call` a layer and NO
    array of a gathered context's size in any type (`[2304,16,2048]`
    at the cell's decode step): the pages are read where they lie. Its
    temporaries are under the lax form's, which holds one gathered
    context at a time. (`compile_for_chip` is here for what it patches:
    the kernel compiled, not interpreted.)"""
    c = CELL
    merged = program == "merged"
    program = "decode" if merged else program
    temps = {}
    for kernel in ("pallas", "lax"):
        eng = _cell_engine(kernel, merged_step=merged)
        compiled, pool = _compile_cell_program(v5e, eng, program,
                                               kv_dtype, c["pages"])
        temps[kernel] = compiled.memory_analysis().temp_size_in_bytes
        if kernel == "lax":
            continue
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= c["layers"]
        context = (eng.step_rows * c["bucket"] * c["page_size"]
                   * c["heads"] * c["head_dim"])
        assert not _context_sized_arrays(text, context, pool.data.size)
        assert not _pool_sized_ops(text, pool.data.shape)
    assert temps["pallas"] < temps["lax"], temps


# the sparse latent block at its published attention widths (a 576-wide
# latent row, a 128-wide index key, 64 index heads, top-2048): one dense
# and one expert layer, a small vocabulary and few experts, which the
# pool's handling does not depend on
SPARSE = dict(rows=16, bucket=64, page_size=64, chunk=128)


@pytest.fixture(scope="module")
def sparse_engine():
    from mxnet_tpu import decoding as dec

    c = SPARSE
    cfg = dec.SparseLatentConfig(
        vocab=1024, d_model=7168, n_layers=2, n_dense_layers=1,
        n_heads=128, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        index_n_heads=64, index_head_dim=128, index_topk=2048, d_ff=2048,
        d_expert=2048, n_experts=256, experts_held=(0, 2),
        experts_per_token=8, n_group=8, topk_group=4, eos_id=-1,
        prefill_chunk=c["chunk"])
    eng = dec.DecodeEngine(
        {}, cfg, max_batch=c["rows"], page_size=c["page_size"],
        num_pages=c["bucket"] + 1, page_buckets=(c["bucket"],),
        kernel="lax", prefix_cache=True, kv_dtype="bf16")
    eng._donate = True
    return eng


def _sparse_program(eng, program, pages):
    from mxnet_tpu.decoding import sparse_latent

    c, cfg = SPARSE, eng.cfg
    params = {n: _s(shape, jnp.float32 if n.endswith("gate_bias")
                    else jnp.bfloat16)
              for n, shape in sparse_latent.param_shapes(cfg).items()}
    pools = tuple(jax.eval_shape(
        lambda pl=pl: quant.make_plane(cfg.n_layers, pages, c["page_size"],
                                       pl, "bf16")) for pl in cfg.planes)
    r, i32 = c["rows"], _s((), jnp.int32)
    if program == "decode":
        fn = eng._build_decode_fn(c["bucket"])
        args = (params, _s((r,), jnp.int32), pools,
                _s((r, c["bucket"]), jnp.int32), _s((r,), jnp.int32),
                _s((r,), jnp.bool_), _s((r,), jnp.uint32),
                _s((r,), jnp.float32), _s((r,), jnp.int32),
                _s((r,), jnp.float32))
    else:
        fn = eng._build_chunk_fn(c["chunk"], c["bucket"])
        args = (params, _s((1, c["chunk"]), jnp.int32), i32, i32, pools,
                _s((c["bucket"],), jnp.int32), _s((), jnp.uint32),
                _s((), jnp.float32), i32, _s((), jnp.float32))
    return getattr(fn, "fn", fn), args, pools


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_sparse_latent_program_holds_no_pool_sized_copy(v5e, sparse_engine,
                                                        program):
    """PR 26's property for the second configuration: both planes are
    written in place (the 576-wide latent row is stored 640 wide, so
    the chip keeps the pool's own order), every read gathers from the
    pool, and the temporaries do not grow with the pool."""
    temps = []
    # pool sizes that no gathered context (rows x bucket pages) equals
    for pages in (1536, 3072):
        fn, args, pools = _sparse_program(sparse_engine, program, pages)
        placed = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            args)
        compiled = fn.lower(*placed).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        if pages == 1536:
            text = compiled.as_text()
            assert f"jit_sparse_latent_{program}" in text.split("\n", 1)[0]
            assert pools[0].data.shape[-1] == 640
            for pool in pools:
                # a chunk's query blocks are a loop that carries the pools
                assert not _pool_sized_ops(text, pool.data.shape,
                                           carried=("while",))
    assert temps[1] - temps[0] <= 0.05 * temps[0], temps


# the third block at its published attention widths (MiMo-V2.5: 64 heads
# of 192/128 against 4 and 8 KV heads, window 128), two experts held
WINDOW_MIXED = {"rows": 8, "page_size": 64, "bucket": 48, "chunk": 128}


@pytest.fixture(scope="module")
def window_mixed_engine():
    from mxnet_tpu import decoding as dec

    c = WINDOW_MIXED
    cfg = dec.WindowMixedConfig(
        vocab=1024, d_model=4096, n_heads=64, head_dim=192, v_head_dim=128,
        kv_heads=4, window_kv_heads=8, window=128,
        layer_pattern=(0, 1, 1), expert_layers=(0, 1, 1), rotary_dim=64,
        d_ff=2048, d_expert=2048, n_experts=256, experts_held=(0, 2),
        experts_per_token=8, eos_id=-1, prefill_chunk=c["chunk"])
    eng = dec.DecodeEngine(
        {}, cfg, max_batch=c["rows"], page_size=c["page_size"],
        num_pages=(c["bucket"] + 1, 16), page_buckets=(c["bucket"],),
        kernel="pallas", kv_dtype="bf16")
    eng._donate = True
    return eng


def _window_mixed_program(eng, program, pages):
    from mxnet_tpu.decoding import window_mixed

    c, cfg = WINDOW_MIXED, eng.cfg
    params = {n: _s(shape, jnp.float32 if n.endswith(("gate_bias", "sink"))
                    else jnp.bfloat16)
              for n, shape in window_mixed.param_shapes(cfg).items()}
    pools = tuple(jax.eval_shape(
        lambda pl=pl, gi=gi: quant.make_plane(
            pl.layers, pages[gi], c["page_size"], pl, "bf16"))
        for pl, gi in zip(cfg.planes, eng._plane_group))
    r, i32 = c["rows"], _s((), jnp.int32)
    if program == "decode":
        fn = eng._build_decode_fn(c["bucket"])
        args = (params, _s((r,), jnp.int32), pools,
                _s((2, r, c["bucket"]), jnp.int32), _s((r,), jnp.int32),
                _s((r,), jnp.bool_), _s((r,), jnp.uint32),
                _s((r,), jnp.float32), _s((r,), jnp.int32),
                _s((r,), jnp.float32))
    else:
        fn = eng._build_chunk_fn(c["chunk"], c["bucket"])
        args = (params, _s((1, c["chunk"]), jnp.int32), i32, i32, pools,
                _s((2, c["bucket"]), jnp.int32), _s((), jnp.uint32),
                _s((), jnp.float32), i32, _s((), jnp.float32))
    return getattr(fn, "fn", fn), args, pools


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_window_mixed_program_holds_no_pool_sized_copy(
        v5e, window_mixed_engine, program, monkeypatch):
    """PR 26's property for the third configuration: the four planes of
    both page groups are written in place (all four widths are
    multiples of 128 lanes), every read comes from the pool (a decode
    step's through the in-place kernel, one `tpu_custom_call` a layer
    with grouped queries, key 192 beside value 128, the window and the
    sink: Mosaic takes them at the published widths), and the
    temporaries do not grow with either pool."""
    monkeypatch.setattr(utils, "pallas_interpret", lambda: False)
    temps = []
    # pool sizes that no gathered context (rows x bucket pages) equals
    for pages in ((1536, 512), (3072, 1024)):
        fn, args, pools = _window_mixed_program(window_mixed_engine,
                                                program, pages)
        placed = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            args)
        compiled = fn.lower(*placed).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        if pages[0] == 1536:
            text = compiled.as_text()
            assert f"jit_window_mixed_{program}" in text.split("\n", 1)[0]
            assert [p.data.shape[-1] for p in pools] == [
                768, 512, 1536, 1024]
            if program == "decode":
                assert text.count("tpu_custom_call") >= 3
            for pool in pools:
                # a chunk's key blocks are a loop that carries the pools
                assert not _pool_sized_ops(text, pool.data.shape,
                                           carried=("while",))
    assert temps[1] - temps[0] <= 0.05 * temps[0], temps


def test_rtc_pallas_kernel_compiles(compile_for_chip):
    def double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    fn = mx.rtc.PallasKernel("double", double_kernel).compiled([(8,)])
    assert "tpu_custom_call" in compile_for_chip(
        fn, _s((8,), jnp.float32))


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


@pytest.mark.parametrize("block", ["dense", "sparse_latent", "window_mixed"])
def test_greedy_batch_runs_no_sort_of_the_vocabulary(
        v5e, block, cell_engine, sparse_engine, window_mixed_engine,
        monkeypatch):
    """Compiled for the chip, each block's decode program keeps the
    sampler's sort in the conditional's branch that a batch with a
    sampled row takes: a greedy batch runs none (what the chat cell's
    `sort.5` was). The sparse block's selection keeps its own."""
    from test_decoding import assert_sampler_sorts_in_branch

    monkeypatch.setattr(utils, "pallas_interpret", lambda: False)
    if block == "dense":
        fn, args, _ = _cell_program(cell_engine, "decode", "bf16",
                                    CELL["pages"])
    elif block == "sparse_latent":
        fn, args, _ = _sparse_program(sparse_engine, "decode", 1536)
    else:
        fn, args, _ = _window_mixed_program(window_mixed_engine, "decode",
                                            (1536, 512))
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        args)
    always = assert_sampler_sorts_in_branch(
        fn.lower(*placed).compile().as_text())
    # what the program always sorts: the selection's and the router's
    # top-k, by the scope that holds each
    assert {s.split("/")[-2] for s in always} == {
        "dense": set(), "sparse_latent": {"index", "router"},
        "window_mixed": {"router"}}[block], always
