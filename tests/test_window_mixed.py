"""The window-mixed block through the decode tier against its plain
reference (perfbench/references/mimo_v25_ep16.py), at a tiny preset on
the CPU that keeps every ratio of the served one: 7 layers in the
pattern 0,1,1,1,1,0,1 (the first dense, the rest experts), 16 query
heads against 2 KV heads in full layers and 4 in window layers (groups
of 8 and 4), key width 12 beside value width 8, rotary on the first 4
dimensions with a base a kind, a window of 8 = 2 pages of 4, 16 experts
(4 held in the served preset), a float32 pool."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import decoding as dec
from mxnet_tpu import serving
from mxnet_tpu.decoding import attention
from mxnet_tpu.decoding import layers
from mxnet_tpu.decoding import window_mixed as wm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 7,
    "num_attention_heads": 16, "head_dim": 12, "v_head_dim": 8,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "sliding_window": 8, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "attention_value_scale": 0.707,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1, 1],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 16, "n_routed_experts_held": 16,
    "experts_held_first": 0, "num_experts_per_tok": 2,
    "layernorm_epsilon": 1e-5, "max_position_embeddings": 512,
}
PAGE = 4
WINDOW = TINY["sliding_window"]
# a decoding row's window lies in at most this many pages
ROW_PAGES = -(-WINDOW // PAGE) + 1


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "ref_mimo_v25_ep16", os.path.join(
            ROOT, "perfbench/references/mimo_v25_ep16.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_object(cfg, **over):
    n = cfg["num_hidden_layers"]
    return dec.WindowMixedConfig(**{**dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_heads=cfg["num_key_value_heads"],
        window_kv_heads=cfg["swa_num_key_value_heads"],
        window=cfg["sliding_window"],
        layer_pattern=tuple(cfg["hybrid_layer_pattern"][:n]),
        expert_layers=tuple(cfg["moe_layer_freq"][:n]),
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        window_rope_theta=float(cfg["swa_rope_theta"]),
        value_scale=cfg["attention_value_scale"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        experts_held=(cfg["experts_held_first"],
                      cfg["n_routed_experts_held"]),
        experts_per_token=cfg["num_experts_per_tok"],
        rms_eps=cfg["layernorm_epsilon"],
        max_len=cfg["max_position_embeddings"], eos_id=-1,
        prefill_chunk=8), **over})


@pytest.fixture(scope="module")
def params(ref):
    with jax.default_matmul_precision("highest"):
        return ref.make_params(7, TINY, jnp.float32)


ENGINE = {"max_batch": 3, "page_size": PAGE, "num_pages": (96, 24),
          "page_buckets": (16,), "chunk_buckets": (4, 8)}


def _engine(params, cfg=None, **kw):
    return dec.DecodeEngine(params, cfg or config_object(TINY),
                            **{"kernel": "lax", **ENGINE, **kw}).warmup()


@pytest.fixture(scope="module")
def eng(params):
    """One warmed engine for the module: chunks of 8 queries over keys
    walked 8 at a time (`attention.KEY_BLOCK`), so that a full layer's
    chunk crosses several key blocks as a served one does."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "KEY_BLOCK", 8)
        return _engine(params)


def _tables(eng, n_tokens):
    """A row's tables for `n_tokens` positions: the full group's, and an
    empty one for the prefill to cover."""
    return [eng.allocators[0].alloc(dec.pages_needed(n_tokens, PAGE)), []]


def _free(eng, tables):
    for a, t in zip(eng.allocators, tables):
        a.free(t)


def _grow_window(eng, tables, position):
    """What the scheduler does before the step that writes `position`."""
    g, a = eng.groups[1], eng.allocators[1]
    dec.blocks.release_behind(a, tables[1], g.first_page(position, PAGE))
    dec.blocks.cover(a, tables[1], position + 1,
                     g.first_page(position, PAGE))


def _stacked(eng, rows, tables):
    tbl = np.zeros((2, rows, eng.page_buckets[-1]), np.int32)
    for r, pair in enumerate(tables):
        for g, t in enumerate(pair):
            tbl[g, r, :len(t)] = t
    return tbl


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(2, TINY["vocab_size"],
                                               n).tolist()


# (a) chunked prefill, then decode through both groups' pages, against
# the reference's full forward over a context of many windows
def test_chunked_prefill_then_decode_matches_reference(ref, params, eng):
    """Tolerance 1e-4 on logits of magnitude ~1: both sides are float32
    (the CPU multiplies float32 exactly as `highest` asks) and differ by
    the order of their sums: the program's softmax is online over key
    blocks and its experts are one grouped product where the reference
    adds them one by one. A wrong rotation, base, mask, sink, scale or
    KV head moves logits by 1e-2 or more."""
    toks = _tokens(0, 60)
    n_prompt = 37               # chunks of 8 8 8 8 5: partial page, bucket 8
    tables = _tables(eng, len(toks))
    first = eng.prefill(toks[:n_prompt], tables)
    assert eng.last_prefill["chunks"] == 5
    # the window group kept the pages of the last 8 positions alone
    held = [p for p in tables[1] if p != dec.SCRATCH_PAGE]
    assert len(held) <= ROW_PAGES and len(tables[1]) == 10
    lg_ref, _ = ref.forward(params, np.asarray(toks, np.int32), TINY)
    lg_ref = np.asarray(lg_ref)
    assert first == int(np.argmax(lg_ref[n_prompt - 1]))
    for length in range(n_prompt, len(toks)):
        _grow_window(eng, tables, length)
        assert sum(p != dec.SCRATCH_PAGE for p in tables[1]) <= ROW_PAGES
        tbl = _stacked(eng, 1, [tables])
        lg = eng.probe_logits([toks[length]], tbl, [length], [True])
        np.testing.assert_allclose(lg[0], lg_ref[length], atol=1e-4, rtol=0)
        eng.step([toks[length]], tbl, [length], [True])
    _free(eng, tables)
    eng.allocator.check()
    assert [a.pages_in_use() for a in eng.allocators] == [0, 0]


# (b) the in-place kernel (interpreted) inside the block's decode step
# gives the lax form's logits
def test_decode_step_through_the_kernel_equals_lax(params, eng):
    kern = _engine(params, kernel="pallas")
    toks = _tokens(1, 30)
    got = []
    for e in (eng, kern):
        tables = _tables(e, len(toks))
        e.prefill(toks[:21], tables)
        rows = []
        for length in range(21, 30):
            _grow_window(e, tables, length)
            tbl = _stacked(e, 1, [tables])
            rows.append(e.probe_logits([toks[length]], tbl, [length],
                                       [True])[0])
            e.step([toks[length]], tbl, [length], [True])
        got.append(np.stack(rows))
        _free(e, tables)
    np.testing.assert_allclose(got[1], got[0], atol=1e-5, rtol=0)


# (c) continuous batching: a row among others equals the row alone
def test_row_among_others_equals_row_alone(eng):
    prompts = [_tokens(10 + i, n) for i, n in enumerate((23, 9, 41))]
    tables = [_tables(eng, len(p) + 6) for p in prompts]
    firsts = [eng.prefill(p, t) for p, t in zip(prompts, tables)]
    lengths = [len(p) for p in prompts]
    for t, n in zip(tables, lengths):
        _grow_window(eng, t, n)
    tbl = _stacked(eng, 3, tables)
    together = eng.probe_logits(firsts, tbl, lengths, [True] * 3)
    for r in range(3):
        alone = eng.probe_logits(
            [firsts[r], 0, 0], _stacked(eng, 3, [tables[r]]),
            [lengths[r], 0, 0], [True, False, False])
        np.testing.assert_array_equal(together[r], alone[0])
    toks = firsts
    for _ in range(5):
        for t, n in zip(tables, lengths):
            _grow_window(eng, t, n)
        toks = eng.step(toks, _stacked(eng, 3, tables), lengths,
                        [True] * 3).tolist()
        lengths = [n + 1 for n in lengths]
    # row 1 again, alone, from its prefill into pages of its own
    t2 = _tables(eng, len(prompts[1]) + 6)
    tok = eng.prefill(prompts[1], t2)
    assert tok == firsts[1]
    for k in range(5):
        _grow_window(eng, t2, len(prompts[1]) + k)
        tok = int(eng.step([tok], _stacked(eng, 3, [t2]),
                           [len(prompts[1]) + k], [True])[0])
    assert tok == toks[1]
    for t in tables + [t2]:
        _free(eng, t)
    eng.allocator.check()


def _spans(name):
    from mxnet_tpu.telemetry import trace

    return [s for s in trace.recent_spans() if s.name == name]


class _WatchedScheduler:
    """Counts, at every step the scheduler launches, the window pages of
    its decoding rows."""

    def __init__(self, model):
        self.most = 0
        sched = model.scheduler
        pack = sched._pack

        def watched(live):
            for _, s in live:
                held = sum(p != dec.SCRATCH_PAGE for p in s.side[0])
                self.most = max(self.most, held)
            return pack(live)

        sched._pack = watched


# (d) steps kept in flight give the streams and counters of the loop
# that waits for every step; a decoding row holds ceil(window/page)+1
# window pages at most; both allocators are clean after the churn
def test_run_ahead_gives_the_same_streams_and_counters(params):
    cfg = config_object(TINY)
    jobs = [(_tokens(31 + i, 19 + 5 * i), 9 + 3 * i) for i in range(5)]
    outs, snaps = [], []
    for depth in (0, 3):
        server = serving.ModelServer()
        model = server.load_decoder(
            f"wm{depth}", params, cfg, kernel="lax", max_tokens=32,
            run_ahead=depth, **{**ENGINE, "max_batch": 2})
        watch = _WatchedScheduler(model)
        try:
            futs = [server.submit_decode(f"wm{depth}", p, max_new_tokens=n)
                    for p, n in jobs]
            outs.append([f.result(120) for f in futs])
            snaps.append(model.stats.snapshot())
            model.engine.allocator.check()
            assert [a.pages_in_use()
                    for a in model.engine.allocators] == [0, 0]
        finally:
            server.stop(drain=False)
        assert 0 < watch.most <= ROW_PAGES
        steps = [s for s in _spans("decoding.step")
                 if s.attrs.get("model") == model.key]
        assert len(steps) == snaps[-1]["steps"]
        for s in steps:
            assert set(cfg.step_counters) <= set(s.attrs)
            assert len(s.attrs["pages_held"]) == 2
            assert 0 < s.attrs["window_tokens"] <= s.attrs["ctx_tokens"]
            assert s.attrs["window_tokens"] <= WINDOW * s.attrs["live"]
    assert outs[0] == outs[1]
    assert [len(o) for o in outs[1]] == [n for _, n in jobs]
    for key in ("decode_tokens", "prefill_tokens", "prefills",
                "expert_rows", "ctx_tokens", "window_tokens",
                "window_pages_released", "traces_since_warmup",
                "preemptions"):
        assert snaps[0][key] == snaps[1][key], key
    assert snaps[0]["window_pages_released"] > 0
    assert snaps[0]["preemptions"] == 0
    # every context page of the window group but a row's last went back
    assert snaps[0]["pages_held"][1] <= ROW_PAGES * 2 * snaps[0]["steps"]


# (e) a pool too small for every row: preemption and readmission move
# both groups' pages, the streams are those of a roomy pool
def test_preempt_and_readmit_keep_streams_and_pages(params):
    cfg = config_object(TINY)
    jobs = [(_tokens(50 + i, 30), 20) for i in range(3)]
    outs = []
    for pages in ((96, 24), (22, 24)):
        server = serving.ModelServer()
        model = server.load_decoder(
            f"wmp{pages[0]}", params, cfg, kernel="lax", max_tokens=32,
            **{**ENGINE, "num_pages": pages})
        try:
            futs = [server.submit_decode(model.name, p, max_new_tokens=n)
                    for p, n in jobs]
            outs.append([f.result(180) for f in futs])
            snap = model.stats.snapshot()
            model.engine.allocator.check()
            assert [a.pages_in_use()
                    for a in model.engine.allocators] == [0, 0]
        finally:
            server.stop(drain=False)
    assert snap["preemptions"] > 0 and snap["readmissions"] > 0
    assert outs[0] == outs[1]


# (f) the sink joins the softmax: it moves the logits, and the reference
# without it is another model
def test_sink_changes_the_logits_and_matches_the_reference(ref, params,
                                                          eng):
    toks = _tokens(3, 24)
    lg_ref, _ = ref.forward(params, np.asarray(toks, np.int32), TINY)
    lg_none, _ = ref.forward(params, np.asarray(toks, np.int32), TINY,
                             no_sink=True)
    lg_all, _ = ref.forward(params, np.asarray(toks, np.int32), TINY,
                            full_window=True)
    tables = _tables(eng, len(toks))
    eng.prefill(toks[:23], tables)
    _grow_window(eng, tables, 23)
    lg = eng.probe_logits([toks[23]], _stacked(eng, 1, [tables]), [23],
                          [True])[0]
    _free(eng, tables)
    np.testing.assert_allclose(lg, np.asarray(lg_ref)[23], atol=1e-4)
    assert np.abs(np.asarray(lg_none)[23] - lg).max() > 1e-2
    assert np.abs(np.asarray(lg_all)[23] - lg).max() > 1e-2


def test_rotary_bases_differ_by_layer_kind(ref):
    cfg = config_object(TINY)
    full, win = wm.rotary_freqs(cfg, 0), wm.rotary_freqs(cfg, 1)
    assert full.shape == win.shape == (2,)          # int(12 * 0.334) = 4
    np.testing.assert_allclose(full, [1.0, 1e7 ** -0.5], rtol=1e-6)
    np.testing.assert_allclose(win, [1.0, 1e4 ** -0.5], rtol=1e-6)
    np.testing.assert_array_equal(full, ref.rotary_freqs(TINY, False))
    np.testing.assert_array_equal(win, ref.rotary_freqs(TINY, True))
    # dimensions past the rotary ones are untouched; pairs are (d, d+2)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 3, 2, 12),
                    jnp.float32)
    pos = jnp.asarray([[0, 5, 9]])
    y = np.asarray(wm._rotary(x, pos, jnp.asarray(win)))
    np.testing.assert_array_equal(y[..., 4:], np.asarray(x)[..., 4:])
    np.testing.assert_array_equal(y[0, 0], np.asarray(x)[0, 0])
    a, b = np.asarray(x)[0, 1, 0, 1], np.asarray(x)[0, 1, 0, 3]
    ang = 5 * win[1]
    np.testing.assert_allclose(
        y[0, 1, 0, [1, 3]],
        [a * np.cos(ang) - b * np.sin(ang),
         a * np.sin(ang) + b * np.cos(ang)], rtol=1e-5)


# (g) what cannot resume a row from pages it has released is refused,
# with the reason
@pytest.mark.parametrize("kw", [{"prefix_cache": True},
                                {"draft_params": {}, "spec_k": 2}])
def test_engine_refuses_prefix_cache_and_draft_for_a_windowed_group(kw):
    with pytest.raises(dec.PageError, match="released"):
        dec.DecodeEngine({}, config_object(TINY), kernel="lax",
                         **{**ENGINE, **kw})
    # unasked, the cache is off whatever the environment's default
    eng = dec.DecodeEngine({}, config_object(TINY), kernel="lax", **ENGINE)
    assert not eng.prefix_cache_enabled and not eng.merged_step_enabled
    with pytest.raises(dec.PageError, match="2 page groups"):
        dec.DecodeEngine({}, config_object(TINY), kernel="lax",
                         **{**ENGINE, "num_pages": (96,)})


def test_copy_page_moves_one_group(params, eng):
    tables = _tables(eng, 12)
    eng.prefill(_tokens(60, 10), tables)
    for group, layers in ((0, 2), (1, 5)):
        src = [p for p in tables[group] if p != dec.SCRATCH_PAGE][-1]
        spare = eng.allocators[group].alloc(1)[0]
        eng.copy_page(src, spare, group=group)
        for layer in range(layers):
            planes = eng.read_page(layer, src)[2 * group:2 * group + 2]
            copies = eng.read_page(layer, spare)[2 * group:2 * group + 2]
            for a, b in zip(planes, copies):
                np.testing.assert_array_equal(a, b)
                assert np.abs(a).sum() > 0
        eng.allocators[group].free([spare])
    _free(eng, tables)


def test_sampler_sorts_only_in_the_sampled_branch(eng):
    """A greedy batch runs no sort of the vocabulary: in the decode and
    the chunk programs the sampler's sort lies in the branch a sampled
    row takes."""
    from test_decoding import assert_sampler_sorts_in_branch, \
        chunk_program_text

    assert_sampler_sorts_in_branch(eng.decode_program_text(16))
    assert_sampler_sorts_in_branch(chunk_program_text(eng, 8, 16))
