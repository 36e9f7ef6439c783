"""The sparse latent block through the decode tier against its plain
reference (perfbench/references/deepseek_v32_ep16.py), at a tiny preset
on the CPU: float32 weights, contexts to 64 tokens with top-k 8 so that
the selection bites, pages of 4 so that every path crosses pages."""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import decoding as dec
from mxnet_tpu import serving
from mxnet_tpu.decoding import model as dmodel
from mxnet_tpu.decoding import sparse_latent as sl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference's configuration (the published keys) at the tiny preset
TINY = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 8,
    "index_head_dim": 16, "index_topk": 8, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "n_routed_experts_held": 16, "experts_held_first": 0,
    "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "max_position_embeddings": 512,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
}
PAGE = 4


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "ref_deepseek_v32_ep16", os.path.join(
            ROOT, "perfbench/references/deepseek_v32_ep16.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_object(cfg, **over):
    r = cfg["rope_scaling"]
    return dec.SparseLatentConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        experts_held=(cfg["experts_held_first"],
                      cfg["n_routed_experts_held"]),
        experts_per_token=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scale=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], rope_factor=r["factor"],
        rope_original_max_len=r["original_max_position_embeddings"],
        rope_beta_fast=r["beta_fast"], rope_beta_slow=r["beta_slow"],
        rope_mscale=r["mscale"], rms_eps=cfg["rms_norm_eps"],
        max_len=cfg["max_position_embeddings"], eos_id=-1,
        **{"prefill_chunk": 8, **over})


@pytest.fixture(scope="module")
def params(ref):
    with jax.default_matmul_precision("highest"):
        return ref.make_params(7, TINY, jnp.float32)


def _engine(params, cfg=None, **kw):
    kw = {"max_batch": 3, "page_size": PAGE, "num_pages": 256,
          "page_buckets": (16,), "kernel": "lax", "prefix_cache": True,
          "chunk_buckets": (8,), "context_buckets": (8, 16), **kw}
    return dec.DecodeEngine(params, cfg or config_object(TINY), **kw).warmup()


@pytest.fixture(scope="module")
def eng(params):
    """One warmed engine for the module (each test takes pages of its
    own): one chunk bucket over two context buckets. Its chunks of 8
    queries are attended in two blocks of 4, as a served chunk of 512
    is in blocks of 32 (`lax.map` over query blocks)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sl, "QUERY_BLOCK", 4)
        return _engine(params)


def _table(eng, rows, tables):
    tbl = np.zeros((rows, eng.page_buckets[-1]), np.int32)
    for r, t in enumerate(tables):
        tbl[r, :len(t)] = t
    return tbl


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(2, TINY["vocab_size"],
                                               n).tolist()


# (a) + (b): prefill in chunks, then decode through the pool, against the
# reference's full forward — logits, and the selected sets exactly
def test_chunked_prefill_then_decode_matches_reference(ref, params, eng):
    """Tolerance 2e-4 on logits of magnitude ~1: both sides are float32
    (the CPU multiplies float32 exactly as `highest` asks), and differ
    by the order of their sums — the program scores in latent space
    (q W_uk . c), the reference per head (q . c W_uk), and sums experts
    in one product where the reference adds them one by one. A wrong
    rotation, norm, mask, bias or scale moves logits by 1e-2 or more."""
    toks = _tokens(0, 56)
    n_prompt = 37               # chunks of 8 8 8 8 5: partial page, bucket 8
    table = eng.allocator.alloc(dec.pages_needed(len(toks), PAGE))
    first = eng.prefill(toks[:n_prompt], table)
    assert eng.last_prefill["chunks"] == 5
    lg_ref, _, sel_ref = ref.forward(params, np.asarray(toks, np.int32),
                                     TINY)
    lg_ref, sel_ref = np.asarray(lg_ref), np.asarray(sel_ref)
    assert first == int(np.argmax(lg_ref[n_prompt - 1]))
    for length in range(n_prompt, len(toks)):
        tbl = _table(eng, 1, [table])
        lg, sel = eng.probe_selected([toks[length]], tbl, [length], [True])
        np.testing.assert_allclose(lg[0], lg_ref[length], atol=2e-4, rtol=0)
        for layer in range(TINY["num_hidden_layers"]):
            assert set(sel[layer, 0].tolist()) == set(
                sel_ref[layer, length].tolist()), (layer, length)
        assert len(set(sel[0, 0].tolist())) == TINY["index_topk"]
        out = eng.step([toks[length]], _table(eng, 3, [table]), [length],
                       [True])
        assert out[0] == int(np.argmax(lg_ref[length]))
        assert eng.last_step_counters["selected_tokens"] == min(
            length + 1, TINY["index_topk"])


def test_selection_is_all_tokens_while_context_is_short(eng):
    toks = _tokens(1, 5)
    table = eng.allocator.alloc(2)
    eng.prefill(toks[:4], table)
    _, sel = eng.probe_selected([toks[4]], _table(eng, 1, [table]), [4],
                                [True])
    got = sel[:, 0]
    assert all(sorted(x for x in row if x >= 0) == [0, 1, 2, 3, 4]
               for row in got.tolist())
    assert (got < 0).sum() == got.shape[0] * (TINY["index_topk"] - 5)


# (c) continuous batching: a row among others equals the row alone
def test_row_among_others_equals_row_alone(eng):
    prompts = [_tokens(10 + i, n) for i, n in enumerate((23, 9, 41))]
    tables = [eng.allocator.alloc(dec.pages_needed(len(p) + 6, PAGE))
              for p in prompts]
    firsts = [eng.prefill(p, t) for p, t in zip(prompts, tables)]
    lengths = [len(p) for p in prompts]
    tbl = _table(eng, 3, tables)
    together, _ = eng.probe_selected(firsts, tbl, lengths, [True] * 3)
    for r in range(3):
        alone, _ = eng.probe_selected(
            [firsts[r], 0, 0], _table(eng, 3, [tables[r]]),
            [lengths[r], 0, 0], [True, False, False])
        np.testing.assert_array_equal(together[r], alone[0])
    toks = firsts
    for _ in range(5):
        toks = eng.step(toks, tbl, lengths, [True] * 3).tolist()
        lengths = [n + 1 for n in lengths]
    # row 1 again, alone, from its prefill into pages of its own
    t2 = eng.allocator.alloc(len(tables[1]))
    tok = eng.prefill(prompts[1], t2)
    assert tok == firsts[1]
    for k in range(5):
        tok = int(eng.step([tok], _table(eng, 3, [t2]),
                           [len(prompts[1]) + k], [True])[0])
    assert tok == toks[1]


# (d) a prefix-cache hit (same document, a new question) gives what a
# cold prefill gives; the counters reach the spans and the stats
def test_prefix_hit_equals_cold_prefill_and_counters_are_kept(params, eng,
                                                              tmp_path):
    cfg = config_object(TINY)
    doc, q1, q2 = _tokens(20, 30), _tokens(21, 7), _tokens(22, 9)
    server = serving.ModelServer()
    warm = server.load_decoder(
        "warm", params, cfg, prefix_cache=True, max_batch=2, page_size=PAGE,
        num_pages=96, page_buckets=(16,), chunk_buckets=(8,),
        context_buckets=(16,), kernel="lax", max_tokens=6)
    try:
        server.submit_decode("warm", doc + q1).result(60)
        hit = server.submit_decode("warm", doc + q2).result(60)
        snap = warm.stats.snapshot()
        # an AOT bundle's manifest is the dense block's: refused, not
        # written wrong
        with pytest.raises(serving.bundle.BundleError):
            serving.bundle.save_bundle(warm, str(tmp_path / "bundle"))
    finally:
        server.stop(drain=False)
    # cold: the whole prompt through the module's engine, greedy
    table = eng.allocator.alloc(dec.pages_needed(len(doc + q2) + 6, PAGE))
    tok = eng.prefill(doc + q2, table)
    cold = [tok]
    for k in range(5):
        tok = int(eng.step([tok], _table(eng, 3, [table]),
                           [len(doc + q2) + k], [True])[0])
        cold.append(tok)
    assert hit == cold
    # the tail alone was prefilled: the document's 7 full pages hit
    fills = [s for s in _spans("decoding.prefill")
             if s.attrs.get("model") == warm.key]
    assert fills[-1].attrs["cached_tokens"] == 28
    assert fills[-1].attrs["chunks"] == 2           # 11 tokens: 8 + 3
    assert fills[-1].attrs["expert_rows"] == 11 * 2 * 2
    steps = [s for s in _spans("decoding.step")
             if s.attrs.get("model") == warm.key]
    assert steps and all(
        set(cfg.step_counters) <= set(s.attrs) for s in steps)
    assert steps[0].attrs["selected_tokens"] == 8
    assert steps[0].attrs["expert_rows"] == 2 * 2   # 1 row, 2 layers
    assert snap["prefill_chunks"] == 5 + 2
    assert snap["expert_rows"] == (37 + 11 + 5 + 5) * 2 * 2
    assert snap["expert_rows_max"] >= 1
    assert snap["traces_since_warmup"] == 0
    assert snap["preemptions"] == 0


# steps kept in flight (ContinuousScheduler.run_ahead): the streams and
# the counters of the loop that waits for every step
def test_run_ahead_gives_the_same_streams_and_counters(params):
    cfg = config_object(TINY)
    doc = _tokens(30, 30)
    jobs = [(doc + _tokens(31 + i, 5 + i), 4 + i) for i in range(4)]
    outs, snaps = [], []
    for depth in (0, 3):
        server = serving.ModelServer()
        model = server.load_decoder(
            f"ahead{depth}", params, cfg, prefix_cache=True, max_batch=2,
            page_size=PAGE, num_pages=96, page_buckets=(16,),
            chunk_buckets=(8,), context_buckets=(16,), kernel="lax",
            max_tokens=8, run_ahead=depth)
        try:
            futs = [server.submit_decode(f"ahead{depth}", p,
                                         max_new_tokens=n)
                    for p, n in jobs]
            outs.append([f.result(120) for f in futs])
            snaps.append(model.stats.snapshot())
        finally:
            server.stop(drain=False)
        steps = [s for s in _spans("decoding.step")
                 if s.attrs.get("model") == model.key]
        assert len(steps) == snaps[-1]["steps"]
        assert all(set(cfg.step_counters) <= set(s.attrs) for s in steps)
    assert outs[0] == outs[1]
    assert [len(o) for o in outs[1]] == [n for _, n in jobs]
    # sums over rows are the same whatever rows share a step (the
    # distinct experts a step touches are not)
    for key in ("decode_tokens", "prefill_tokens", "prefills",
                "selected_tokens", "expert_rows", "traces_since_warmup",
                "preemptions"):
        assert snaps[0][key] == snaps[1][key], key


def _spans(name):
    from mxnet_tpu.telemetry import trace

    return [s for s in trace.recent_spans() if s.name == name]


# (e) the share ties to the model: 16 shares of one expert each, what
# every chip computes alike (a shared expert, where the block has one)
# counted once, sum to the uncut layer: for both blocks with experts
def _expert_blocks(ref, params):
    import test_window_mixed as twm

    spec = importlib.util.spec_from_file_location(
        "ref_mimo_v25_ep16", os.path.join(
            ROOT, "perfbench/references/mimo_v25_ep16.py"))
    wm_ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wm_ref)
    with jax.default_matmul_precision("highest"):
        wm_params = wm_ref.make_params(7, twm.TINY, jnp.float32)
    return {"sparse_latent": (ref, params, TINY, config_object(TINY), True),
            "window_mixed": (wm_ref, wm_params, twm.TINY,
                             twm.config_object(twm.TINY), False)}


@pytest.mark.parametrize("block", ["sparse_latent", "window_mixed"])
def test_expert_shares_sum_to_uncut_layer(ref, params, block):
    ref, params, tiny, cfg, has_shared = _expert_blocks(ref, params)[block]
    x = jnp.asarray(np.random.RandomState(3).randn(24, 64), jnp.float32)
    layer = 1
    whole = np.asarray(ref.expert_layer(params, layer, x, tiny))
    xh = sl._rms(x, params[f"l{layer}.ffn_norm"], cfg.rms_eps)
    shared = np.asarray(sl._swiglu(
        xh, params[f"l{layer}.shared_w1"], params[f"l{layer}.shared_w3"],
        params[f"l{layer}.shared_w2"])) if has_shared \
        else np.zeros((24, 64), np.float32)
    chosen, weights = sl.route(params, layer, xh, cfg)
    total, rows = shared.copy(), 0
    for share in range(16):
        one = type(cfg)(**{**cfg.__dict__, "experts_held": (share, 1)})
        held = {k: (v[share:share + 1] if "experts_" in k else v)
                for k, v in params.items()}
        part, stats = sl.held_experts(held, layer, xh, chosen, weights,
                                      jnp.ones((24,), bool), one)
        total += np.asarray(part)
        rows += int(stats[0])
        # the reference, given the same share, gives the same part
        np.testing.assert_allclose(
            np.asarray(part) + shared,
            np.asarray(ref.expert_layer(held, layer, x, tiny,
                                        share=(share, 1))), atol=2e-5)
    assert rows == 24 * tiny["num_experts_per_tok"]
    np.testing.assert_allclose(total, whole, atol=5e-5)


# (f) a token that chooses no held expert gets the shared expert alone
def test_token_with_no_held_expert_gets_shared_alone(params):
    cfg = config_object(TINY)
    x = jnp.asarray(np.random.RandomState(4).randn(40, 64), jnp.float32)
    xh = sl._rms(x, params["l1.ffn_norm"], cfg.rms_eps)
    chosen, weights = sl.route(params, 1, xh, cfg)
    one = type(cfg)(**{**cfg.__dict__, "experts_held": (5, 2)})
    held = {k: (v[5:7] if "experts_" in k else v) for k, v in params.items()}
    part, stats = sl.held_experts(held, 1, xh, chosen, weights,
                                  jnp.ones((40,), bool), one)
    none = ~np.isin(np.asarray(chosen), (5, 6)).any(axis=1)
    assert none.any() and not none.all()
    assert np.all(np.asarray(part)[none] == 0.0)
    assert np.all(np.abs(np.asarray(part)[~none]).sum(axis=1) > 0)
    assert int(stats[0]) == int(np.isin(np.asarray(chosen), (5, 6)).sum())


def test_router_uses_bias_for_choice_only_and_keeps_groups(params):
    cfg = config_object(TINY)
    x = jnp.asarray(np.random.RandomState(5).randn(64, 64), jnp.float32)
    chosen, weights = sl.route(params, 1, x, cfg)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(params["l1.gate"]))))
    np.testing.assert_allclose(weights.sum(axis=1), 2.5, rtol=1e-5)
    picked = np.take_along_axis(s, chosen, axis=1)
    np.testing.assert_allclose(weights, picked / picked.sum(1, keepdims=True)
                               * 2.5, rtol=1e-4)
    biased = s + np.asarray(params["l1.gate_bias"])
    group = np.sort(biased.reshape(64, 4, 4), axis=-1)[..., -2:].sum(-1)
    best_two = np.argsort(-group, axis=1)[:, :2]
    assert all(set(c // 4) <= set(b) for c, b in zip(chosen, best_two))


# (g) YaRN frequencies and both rotary layouts against hand-computed
# values (the published sizes: 64 rope dims, base 10000, factor 40,
# original length 4096, beta 32 and 1)
def test_yarn_frequencies_by_hand():
    f = sl.yarn_freqs(dec.SparseLatentConfig(qk_rope_head_dim=64))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 ->
    # 10; 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(
        f[16], base[16] / 40 * ramp + base[16] * (1 - ramp), rtol=1e-6)
    cfg = dec.SparseLatentConfig(qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    short = dec.SparseLatentConfig(max_len=4096)
    np.testing.assert_allclose(
        sl.yarn_freqs(short), 10000.0 ** (-np.arange(0, 8, 2) / 8),
        rtol=1e-6)


def test_rotary_layouts_by_hand():
    freqs = jnp.asarray([0.5, 0.25], jnp.float32)
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    pos = jnp.asarray([2])
    c, s = np.cos([1.0, 0.5]), np.sin([1.0, 0.5])
    # interleaved: (1,2) by angle 1.0, (3,4) by angle 0.5
    np.testing.assert_allclose(
        np.asarray(sl.rotate(x, pos, freqs, True))[0],
        [1 * c[0] - 2 * s[0], 1 * s[0] + 2 * c[0],
         3 * c[1] - 4 * s[1], 3 * s[1] + 4 * c[1]], rtol=1e-6)
    # halves: (1,3) by angle 1.0, (2,4) by angle 0.5
    np.testing.assert_allclose(
        np.asarray(sl.rotate(x, pos, freqs, False))[0],
        [1 * c[0] - 3 * s[0], 2 * c[1] - 4 * s[1],
         1 * s[0] + 3 * c[0], 2 * s[1] + 4 * c[1]], rtol=1e-6)


def _instructions_only(text):
    """A compiled program's instructions alone: the tables of files
    and stack frames and each instruction's metadata (where in the
    Python source it was traced) left out."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return "\n".join(
        ln for ln in text.splitlines()
        if ln.strip() and not re.match(r"\s*\d+ ", ln)
        and ln.strip() not in ("FileNames", "FunctionNames",
                               "FileLocations", "StackFrames"))


# (h) the dense block's decode program is what it was before the engine
# took a model contract: the same text as the block's own forward jitted
# with K and V as two arguments, names aside
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_dense_decode_program_text_is_unchanged(kv_dtype):
    cfg = dec.DecoderConfig(vocab=64, d_model=32, n_layers=2, n_heads=2,
                            d_ff=64, max_len=256)
    eng = dec.DecodeEngine(
        dec.init_decoder_params(cfg, 0), cfg, max_batch=4, page_size=4,
        num_pages=32, page_buckets=(8,), kernel="lax", prefix_cache=False,
        kv_dtype=kv_dtype).warmup()
    attn = dec.get_kernel("lax")

    def decode_p8(params, tokens, k_pages, v_pages, page_table, lengths,
                  active, seeds, temps, top_ks, top_ps):
        return dmodel.decode_forward(
            params, tokens, k_pages, v_pages, page_table, lengths, active,
            seeds, temps, top_ks, top_ps, cfg=cfg, attn=attn)

    args = eng._masked_step_args(8)
    was = jax.jit(decode_p8).lower(
        *args[:2], *args[2], *args[3:]).compile().as_text()

    def plain(text):
        text = re.sub(r"jit_?\w*decode_p8", "M", text)
        text = text.replace("pools_0__", "k_pages_").replace(
            "pools_1__", "v_pages_")
        return _instructions_only(text)

    assert plain(eng.decode_program_text(8)) == plain(was)
    assert eng.step_program(8) == "jit_decode_p8"


# (i) no choice of the single-query kernel reaches this block's programs:
# it attends through `sparse_latent_attention` and `quant.gather_rows`
@pytest.mark.parametrize("named", ["lax", "pallas", None])
def test_kernel_choice_does_not_reach_the_sparse_programs(
        monkeypatch, params, named):
    """The sparse latent decode and chunk programs are the same text
    under MXNET_DECODE_KERNEL=lax, =pallas and unset on a TPU backend
    (where the tier's default is the in-place kernel), names aside, and
    neither form of the single-query paged attention is ever called
    while they are traced: `dsv32_docsessions_closed` runs the program
    it ran before the default moved."""
    from mxnet_tpu.decoding import attention

    def text_of(eng):
        decode = eng._build_decode_fn(16).lower(
            *eng._masked_step_args(16)).compile().as_text()
        chunk = eng._build_chunk_fn(8, 16).lower(
            eng._params, np.zeros((1, 8), np.int32), jnp.int32(0),
            jnp.int32(0), eng._pools, np.zeros((16,), np.int32),
            *eng._samp_scalars()).compile().as_text()
        return _instructions_only(decode + chunk)

    def build():
        eng = dec.DecodeEngine(
            params, config_object(TINY), max_batch=3, page_size=PAGE,
            num_pages=64, page_buckets=(16,), prefix_cache=True,
            chunk_buckets=(8,), context_buckets=(16,))
        eng._donate = False     # the backend's, not the kernel's, to say
        return eng

    monkeypatch.setenv("MXNET_DECODE_KERNEL", "lax")
    want = text_of(build())

    def never(*_a, **_k):
        raise AssertionError("single-query paged attention called")

    for table in (attention._KERNELS, attention._RAGGED_KERNELS):
        for name in table:
            monkeypatch.setitem(table, name, never)
    if named is None:
        monkeypatch.delenv("MXNET_DECODE_KERNEL")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    else:
        monkeypatch.setenv("MXNET_DECODE_KERNEL", named)
    eng = build()
    assert eng.kernel_name == (named or "pallas")
    got = text_of(eng)
    assert got == want
    assert "paged_attention" not in got


def test_planes_of_the_three_configurations():
    import test_window_mixed as twm

    dense = dec.DecoderConfig(d_model=32, n_heads=2)
    assert [(p.name, p.width, p.groups) for p in dense.planes] == [
        ("k", 32, 2), ("v", 32, 2)]
    sparse = config_object(TINY)
    assert [(p.name, p.width, p.groups) for p in sparse.planes] == [
        ("latent", 24, 1), ("index_key", 16, 1)]
    # one page group over every layer, for both
    for cfg in (dense, sparse):
        assert cfg.page_groups == (dec.PageGroup(),)
        assert all((p.layers, p.group) == (0, "") for p in cfg.planes)
    # the third: a k/v pair for each kind of layer, at that kind's KV
    # heads and layer count, in two page groups of which one has a window
    mixed = twm.config_object(twm.TINY)
    assert list(mixed.planes) == [
        dec.Plane("k", 24, 2, 2, "full"), dec.Plane("v", 16, 2, 2, "full"),
        dec.Plane("k_win", 48, 4, 5, "window"),
        dec.Plane("v_win", 32, 4, 5, "window")]
    assert mixed.page_groups == (dec.PageGroup("full"),
                                 dec.PageGroup("window", 8))
    weng = dec.DecodeEngine({}, mixed, max_batch=2, page_size=PAGE,
                            num_pages=(32, 12), page_buckets=(16,),
                            kernel="lax")
    assert [p.data.shape for p in weng._pools] == [
        (2, 32, PAGE, 24), (2, 32, PAGE, 16), (5, 12, PAGE, 48),
        (5, 12, PAGE, 32)]
    assert [a.capacity() for a in weng.allocators] == [31, 11]
    assert weng.pool_stats()["kv_bytes_per_token"] == (
        2 * (24 + 16) + 5 * (48 + 32)) * 4
    assert weng.pool_stats()["window_pages_total"] == 11
    assert weng.step_program(16) == "jit_window_mixed_decode_p16"
    assert weng.prefill_pages(100) == [25, 5]   # 8 + 7 positions + 1
    eng = _engine_shapes(sparse)
    assert quant_plane_widths(eng) == [24, 16]
    # a 576-wide row is stored in whole 128-lane tiles, a toy row as is
    assert dec.Plane("latent", 576).stored_width == 640
    assert dec.Plane("k", 2048, 32).stored_width == 2048
    assert eng.pool_stats()["kv_bytes_per_token"] == 3 * (24 + 16) * 4
    assert eng.step_program(16) == "jit_sparse_latent_decode_p16"
    assert not eng.merged_step_enabled


def quant_plane_widths(eng):
    return [p.data.shape[-1] for p in eng._pools]


def _engine_shapes(cfg):
    return dec.DecodeEngine({}, cfg, max_batch=2, page_size=PAGE,
                            num_pages=32, page_buckets=(16,), kernel="lax",
                            prefix_cache=True)


def test_copy_page_covers_every_plane(eng):
    table = eng.allocator.alloc(3)
    eng.prefill(_tokens(30, 10), table)
    spare = eng.allocator.alloc(1)[0]
    eng.copy_page(table[1], spare)
    for layer in range(3):
        for a, b in zip(eng.read_page(layer, table[1]),
                        eng.read_page(layer, spare)):
            np.testing.assert_array_equal(a, b)
            assert np.abs(a).sum() > 0


# (j) a greedy batch runs no sort of the vocabulary: the sampler's sort
# lies in the branch a sampled row takes, the selection's top-k sorts
# where they were
def test_sampler_sorts_only_in_the_sampled_branch(eng):
    from test_decoding import assert_sampler_sorts_in_branch, \
        chunk_program_text

    assert_sampler_sorts_in_branch(eng.decode_program_text(16))
    assert_sampler_sorts_in_branch(chunk_program_text(eng, 8, 16))
