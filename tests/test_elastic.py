"""Elastic training control-plane tests (PR 19): placement math,
mid-epoch sampler re-keys, slice-decomposable updates, the wire codec,
the elasticStats surface, and in-process end-to-end membership
transitions. The heavyweight SIGKILL soak lives in ci/check_elastic.py;
these tests pin the invariants it relies on."""
import threading

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.data.sampler import remainder_stream, ShardedSampler
from mxnet_tpu.elastic import (
    codec, reshard, ElasticCoordinator, ElasticWorker, JobSpec,
    load_entry,
)
from mxnet_tpu.elastic import stats as estats
from mxnet_tpu.elastic.trainer import combine_grads, ElasticSGD

ENTRY = "mxnet_tpu.elastic.ci_job:build"


# ------------------------------------------------------ sampler re-key
def test_world1_stream_is_the_remainder_stream():
    """A single rank owning every logical shard must emit the
    membership-independent ground-truth stream element for element."""
    s = ShardedSampler(240, 8, seed=3, shard_id=0, num_shards=4)
    s.set_epoch(1)
    s.set_membership(0, 1, consumed=0)
    ref = remainder_stream(3, 1, 240, 4, 8)
    assert np.array_equal(s.epoch_indices(), ref)
    # and from any mid-epoch position
    s.set_membership(0, 1, consumed=5)
    ref5 = remainder_stream(3, 1, 240, 4, 8, consumed=5)
    assert np.array_equal(s.epoch_indices(), ref5)


def test_rekey_2_to_1_union_equals_uninterrupted_remainder():
    """The ISSUE acceptance identity: after a 2->1 shrink at consumed
    k, the survivor's re-keyed stream IS the uninterrupted remainder —
    bitwise, not just as a set."""
    seed, epoch, n, S, bs = 11, 0, 256, 2, 8
    k = 7  # steps already applied when the membership changed
    survivor = ShardedSampler(n, bs, seed=seed, shard_id=0,
                              num_shards=S)
    survivor.set_epoch(epoch)
    # consume k steps under the old world=2 membership
    consumed_before = [survivor.shard_batch(0, p) for p in range(k)]
    survivor.set_membership(0, 1, consumed=k)
    stream = survivor.epoch_indices()
    assert np.array_equal(stream,
                          remainder_stream(seed, epoch, n, S, bs,
                                           consumed=k))
    # exactly-once over the whole epoch: consumed + dead rank's share
    dead_share = [
        ShardedSampler(n, bs, seed=seed, shard_id=1,
                       num_shards=S).shard_batch(1, p)
        for p in range(k)]
    union = np.concatenate(consumed_before + dead_share + [stream])
    assert sorted(union.tolist()) == list(range(n))


def test_rekey_3_to_2_union_disjoint_and_complete():
    seed, epoch, n, S, bs, k = 5, 2, 360, 3, 6, 4
    streams = []
    for rank in range(2):
        s = ShardedSampler(n, bs, seed=seed, shard_id=0, num_shards=S)
        s.set_epoch(epoch)
        s.set_membership(rank, 2, consumed=k)
        streams.append(s.epoch_indices())
    ref = remainder_stream(seed, epoch, n, S, bs, consumed=k)
    union = np.concatenate(streams)
    assert len(union) == len(ref)
    assert sorted(union.tolist()) == sorted(ref.tolist())
    assert not set(streams[0].tolist()) & set(streams[1].tolist())


def test_default_membership_contract_unchanged():
    """Pre-elastic behaviour (one contiguous shard per process) is the
    default membership — batch k is the k-th slice of the shard."""
    s = ShardedSampler(128, 8, seed=1, shard_id=1, num_shards=2)
    shard = s.epoch_indices()
    assert len(shard) == 64
    for k in range(s.batches_per_epoch):
        assert np.array_equal(s.batch_indices(k),
                              shard[k * 8:(k + 1) * 8])
    assert len(s) == s.batches_per_epoch


def test_set_membership_validation():
    s = ShardedSampler(128, 8, seed=1, shard_id=0, num_shards=2)
    with pytest.raises(MXNetError):
        s.set_membership(2, 2)
    with pytest.raises(MXNetError):
        s.set_membership(0, 3)   # world > logical shards
    with pytest.raises(MXNetError):
        s.set_membership(0, 1, consumed=99)


def test_refresh_membership_rereads_process_world():
    """The historical bug: the (process_index, process_count) pair was
    captured once at construction. refresh_membership re-reads it —
    under the single-process test runner that is rank 0 of world 1,
    which makes a 2-shard sampler own BOTH logical shards."""
    s = ShardedSampler(128, 8, seed=1, shard_id=1, num_shards=2)
    assert s.owned_shards == (1,)
    s.refresh_membership(consumed=3)
    assert (s.rank, s.world) == (0, 1)
    assert s.owned_shards == (0, 1)
    assert s.consumed == 3


# --------------------------------------------------------- reshard math
def _mlp_shapes():
    spec = load_entry(ENTRY)({})
    return spec.param_shapes()


def test_placement_world1_replicates_everything():
    shapes = _mlp_shapes()
    bounds, specs = reshard.placement(shapes, 1)
    for n, shape in shapes.items():
        assert bounds[n] == ((0, shape[0]),)
        assert specs[n].split(",")[0] == "None"


def test_placement_world2_shards_dim0_evenly():
    shapes = _mlp_shapes()
    bounds, specs = reshard.placement(shapes, 2)
    for n, shape in shapes.items():
        half = shape[0] // 2
        assert bounds[n] == ((0, half), (half, shape[0]))
        assert reshard.WORLD_AXIS in specs[n].split(",")[0]


def test_owner_bounds_replicated_and_nondividing():
    assert reshard.owner_bounds("None,None", (7, 3), 2) == \
        ((0, 7), (0, 0))
    with pytest.raises(MXNetError):
        reshard.owner_bounds("fsdp,None", (7, 3), 2)


def test_interval_sub():
    assert reshard.interval_sub((0, 10), (0, 10)) == []
    assert reshard.interval_sub((0, 10), (20, 30)) == [(0, 10)]
    assert reshard.interval_sub((0, 10), (3, 7)) == [(0, 3), (7, 10)]
    assert reshard.interval_sub((0, 10), (0, 4)) == [(4, 10)]
    assert reshard.interval_sub((0, 10), (6, 12)) == [(0, 6)]


def test_member_moves_only_deltas():
    old = {"w": {"a": (0, 8), "b": (8, 16)}}
    new = {"w": {"a": (0, 16)}}          # b died; a absorbs its rows
    moves = reshard.member_moves(old, new)
    assert moves == {"a": [("w", 8, 16)]}
    # unchanged ownership moves nothing
    assert reshard.member_moves(new, new) == {}
    # a joiner (absent from old) receives its full share
    grown = {"w": {"a": (0, 8), "c": (8, 16)}}
    moves = reshard.member_moves(new, grown)
    assert moves == {"c": [("w", 8, 16)]}


def test_moves_bytes_counts_rows():
    shapes = {"w": (16, 4)}
    moves = {"a": [("w", 8, 16)]}
    assert reshard.moves_bytes(moves, shapes) == 8 * 4 * 4
    assert reshard.state_bytes(shapes) == 16 * 4 * 4
    assert reshard.state_bytes(shapes, copies=3) == 3 * 16 * 4 * 4


# ---------------------------------------------------- update arithmetic
def test_sgd_update_is_slice_decomposable():
    """The property owner-sharded steps and resharding both lean on:
    updating dim-0 slices independently equals the full-tensor update
    bit for bit."""
    rs = np.random.RandomState(0)
    p = rs.randn(12, 5).astype(np.float32)
    g = rs.randn(12, 5).astype(np.float32)
    m = rs.randn(12, 5).astype(np.float32)
    sgd = ElasticSGD(lr=0.05, momentum=0.9)
    pf, mf = p.copy(), m.copy()
    sgd.update(pf, g, mf)
    ps, ms = p.copy(), m.copy()
    for lo, hi in ((0, 7), (7, 12)):
        prow, mrow = ps[lo:hi], ms[lo:hi]
        sgd.update(prow, g[lo:hi], mrow)
    assert np.array_equal(pf, ps) and np.array_equal(mf, ms)


def test_combine_grads_fixed_order_and_missing():
    rs = np.random.RandomState(1)
    gs = {s: {"w": rs.randn(4, 3).astype(np.float32)} for s in range(3)}
    out = combine_grads(gs, 3)
    ref = gs[0]["w"].astype(np.float32, copy=True)
    ref += gs[1]["w"]
    ref += gs[2]["w"]
    ref *= np.float32(1.0 / 3)
    assert np.array_equal(out["w"], ref)
    with pytest.raises(MXNetError):
        combine_grads({0: gs[0]}, 3)


def test_jobspec_initial_params_deterministic():
    spec_a = load_entry(ENTRY)({})
    spec_b = load_entry(ENTRY)({})
    shapes = spec_a.param_shapes()
    assert shapes == spec_b.param_shapes()
    pa = spec_a.initial_params(shapes)
    pb = spec_b.initial_params(shapes)
    assert sorted(pa) == sorted(shapes)
    for n in pa:
        assert pa[n].dtype == np.float32
        assert np.array_equal(pa[n], pb[n])


# ---------------------------------------------------------------- codec
def test_codec_roundtrip_exact():
    rs = np.random.RandomState(2)
    tree = {"a": rs.randn(5, 3).astype(np.float32),
            "b": np.arange(4, dtype=np.int64)}
    back = codec.decode_tree(codec.encode_tree(tree))
    for n in tree:
        assert back[n].dtype == tree[n].dtype
        assert np.array_equal(back[n], tree[n])
    enc = codec.encode(tree["a"])
    assert codec.payload_bytes(enc) == tree["a"].nbytes
    d1 = codec.digest(tree)
    tree["a"][0, 0] += np.float32(1e-7)
    assert codec.digest(tree) != d1


# ------------------------------------------------------- stats surface
def test_elastic_stats_view_shape_pinned():
    """The elasticStats snapshot key set is a published surface
    (dashboards, /metrics) — additions need a deliberate pin bump."""
    st = estats.ElasticStats("pinjob")
    estats._register("pinjob", st)
    try:
        st.note_membership(2, 1)
        st.note_step(3)
        st.note_transition("shrink", 1.5, 100, 400, 64)
        snap = estats.elastic_stats()["pinjob"]
        assert sorted(snap) == sorted((
            "world", "generation", "steps_completed", "transitions",
            "transitions_shrink", "transitions_grow",
            "quiesce_wall_ms_last", "quiesce_wall_ms_total",
            "reshard_bytes_moved", "reshard_bytes_full_restore",
            "examples_rekeyed", "digest_mismatches", "workers"))
        assert snap["world"] == 2 and snap["steps_completed"] == 3
        assert snap["transitions"] == 1
        assert snap["transitions_shrink"] == 1
        assert snap["reshard_bytes_moved"] == 100
        assert snap["reshard_bytes_full_restore"] == 400
        assert snap["examples_rekeyed"] == 64
    finally:
        estats._unregister("pinjob")


def test_elastic_view_omitted_when_empty():
    """No live coordinator -> the view vanishes from dumps entirely,
    keeping pre-elastic profiler output byte-identical."""
    from mxnet_tpu.telemetry import view_items
    assert "elasticStats" not in [k for k, _ in view_items()]


# ----------------------------------------------------------- end-to-end
def _spawn_worker(port, name, **kwargs):
    w = ElasticWorker(f"127.0.0.1:{port}", ENTRY, {}, name=name,
                      **kwargs)

    def run():
        try:
            w.run(rejoin_ms=0)
        except MXNetError:
            pass   # a close()d victim exhausts its rejoin budget

    threading.Thread(target=run, daemon=True).start()
    return w


def _run_uninterrupted(world, name):
    c = ElasticCoordinator(ENTRY, {}, name=name,
                           initial_world=world).start()
    try:
        for i in range(world):
            _spawn_worker(c.port, f"{name}-w{i}")
        assert c.wait(120), c.status()
        return c.final_params()
    finally:
        c.stop()


def test_single_worker_job_completes():
    c = ElasticCoordinator(ENTRY, {}, name="t_solo",
                           initial_world=1).start()
    try:
        w = _spawn_worker(c.port, "solo-w0")
        assert c.wait(120), c.status()
        coord_params = c.final_params()
        # the worker's committed state is the coordinator mirror
        deadline = 50
        while w.completed_steps < 32 and deadline:
            threading.Event().wait(0.1)
            deadline -= 1
        wp = w.params()
        for n in coord_params:
            assert np.array_equal(coord_params[n], wp[n])
        snap = estats.elastic_stats()["t_solo"]
        assert snap["steps_completed"] == 32
        assert snap["transitions"] == 0
        assert snap["digest_mismatches"] == 0
    finally:
        c.stop()


@pytest.mark.slow
def test_shrink_and_grow_bitwise_identical():
    """The tentpole claim end-to-end, in process: a mid-run shrink
    (worker vanishes) and a mid-run grow (worker joins) both finish
    with final params bitwise equal to the uninterrupted run."""
    ref = _run_uninterrupted(1, "t_ref")

    c = ElasticCoordinator(ENTRY, {}, name="t_shrink",
                           initial_world=2).start()
    try:
        _spawn_worker(c.port, "shr-w0")
        victim = _spawn_worker(c.port, "shr-w1")
        while victim.completed_steps < 5 and not c.wait(0.05):
            pass
        victim.close()
        assert c.wait(120), c.status()
        got = c.final_params()
        snap = estats.elastic_stats()["t_shrink"]
    finally:
        c.stop()
    for n in ref:
        assert np.array_equal(ref[n], got[n])
    assert snap["transitions"] == snap["transitions_shrink"] == 1
    # the placement delta beats restoring everyone, and is not empty
    assert 0 < snap["reshard_bytes_moved"] < \
        snap["reshard_bytes_full_restore"]
    assert snap["digest_mismatches"] == 0

    c = ElasticCoordinator(ENTRY, {}, name="t_grow",
                           initial_world=1).start()
    try:
        w0 = _spawn_worker(c.port, "gro-w0")
        while w0.completed_steps < 5 and not c.wait(0.05):
            pass
        _spawn_worker(c.port, "gro-w1")
        assert c.wait(120), c.status()
        got = c.final_params()
        snap = estats.elastic_stats()["t_grow"]
    finally:
        c.stop()
    for n in ref:
        assert np.array_equal(ref[n], got[n])
    assert snap["transitions"] == snap["transitions_grow"] == 1
    assert 0 < snap["reshard_bytes_moved"] < \
        snap["reshard_bytes_full_restore"]
    assert snap["digest_mismatches"] == 0


def test_model_fit_elastic_entrypoint():
    """mx.model.fit_elastic is the library-level worker entry: it
    joins a coordinator and trains to completion."""
    import mxnet_tpu as mx

    c = ElasticCoordinator(ENTRY, {}, name="t_fit",
                           initial_world=1).start()
    try:
        out = {}

        def run():
            out["r"] = mx.model.fit_elastic(
                f"127.0.0.1:{c.port}", ENTRY, {}, num_retries=0)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert c.wait(120), c.status()
        t.join(30)
        assert not t.is_alive()
        reason, params = out["r"]
        assert reason == "complete"
        ref = c.final_params()
        for n in ref:
            assert np.array_equal(ref[n], params[n])
    finally:
        c.stop()
