"""mxnet_tpu.sharding: rule table, plan resolution, pre-trace
verification, and end-to-end parity of plan-driven training.

Parity tests use EXACT float32 arithmetic (dyadic-rational data and
weights, power-of-two lr/batch, one no-bias FC) so reduction order is
irrelevant and `np.array_equal` across shardings is a real invariant,
not a tolerance."""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.sharding import (DEFAULT_LAYOUT, ShardingPlan,
                                device_param_bytes,
                                parameter_spec_from_name, rules_digest,
                                spec_to_str)

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


# ------------------------------------------------------------ rule layer
def _spec(name, overrides=None, ndim=None):
    return parameter_spec_from_name(
        name, DEFAULT_LAYOUT, overrides, ndim=ndim)


def test_default_rule_table():
    spec, explicit = _spec("tok_embed_weight")
    assert spec == P(("fsdp", "tp"), None) and not explicit
    spec, _ = _spec("l0_qkv_weight")
    assert spec == P("tp", "fsdp")
    spec, _ = _spec("l0_attn_out_weight")
    assert spec == P("tp", "fsdp")
    spec, _ = _spec("ffn_up_weight")
    assert spec == P("tp", "fsdp")
    spec, _ = _spec("ffn_down_weight")
    assert spec == P("fsdp", None)
    spec, _ = _spec("bn_gamma")
    assert spec == P("fsdp")
    spec, _ = _spec("fc1_bias")
    assert spec == P("fsdp")
    # fallback: dim 0 over fsdp, scalars replicated
    spec, explicit = _spec("something_else", ndim=2)
    assert spec == P("fsdp", None) and not explicit
    spec, _ = _spec("scalar_thing", ndim=0)
    assert spec == P()


def test_override_precedence():
    overrides = {
        "*_weight": P("tp", None),         # glob, first
        "fc9_weight": P(None, "tp"),       # exact name outranks glob
        "*9_weight": P("fsdp", None),      # later glob never reached
    }
    spec, explicit = _spec("fc1_weight", overrides)
    assert spec == P("tp", None) and explicit
    spec, explicit = _spec("fc9_weight", overrides)
    assert spec == P(None, "tp") and explicit
    # no override hit -> default rules still apply, not explicit
    spec, explicit = _spec("bn_gamma", overrides)
    assert spec == P("fsdp") and not explicit


def test_override_string_roundtrip():
    # the parse_partition_spec string syntax round-trips via spec_to_str
    plan = ShardingPlan({"data": 2, "tp": 2, "fsdp": 2},
                        overrides={"w": "tp,fsdp",
                                   "e": "fsdp+tp,None"})
    spec, explicit = plan.spec_for("w", ndim=2)
    assert explicit and spec == P("tp", "fsdp")
    spec, _ = plan.spec_for("e", ndim=2)
    assert spec == P(("fsdp", "tp"), None)
    assert spec_to_str(spec) == "fsdp+tp,None"
    assert spec_to_str(P()) == "None"  # parses back to P()


def test_rules_digest_stability():
    a = rules_digest(DEFAULT_LAYOUT, {"x": P("tp")})
    # dict insertion order must not matter (digest sorts)
    b = rules_digest(DEFAULT_LAYOUT, dict([("x", P("tp"))]))
    assert a == b
    assert a != rules_digest(DEFAULT_LAYOUT, {"x": P("fsdp")})
    assert a != rules_digest(DEFAULT_LAYOUT, None)


def test_plan_digest():
    mk = lambda: ShardingPlan({"data": 2, "tp": 4},
                              overrides={"w": P("tp", None)})
    assert mk().digest() == mk().digest()
    assert mk().digest() != ShardingPlan({"data": 8}).digest()
    assert mk().digest() != ShardingPlan(
        {"data": 2, "tp": 4}, overrides={"w": P("tp", None)},
        constrain_compute=False).digest()


# ------------------------------------------------------- plan resolution
def test_resolve_advisory_downgrade():
    plan = ShardingPlan({"data": 4})  # no tp/fsdp axes in the mesh
    specs = plan.resolve({"l0_qkv_weight": (8, 8), "fc_bias": (3,)})
    # every advisory axis dropped -> replicated
    assert specs["l0_qkv_weight"] == P()
    assert specs["fc_bias"] == P()
    assert plan.explicit_names == set()


def test_resolve_divisibility_downgrade():
    plan = ShardingPlan({"fsdp": 2, "tp": 2})
    specs = plan.resolve({"ffn_down_weight": (7, 4),  # 7 % 2 != 0
                          "ffn_up_weight": (8, 6)})
    assert specs["ffn_down_weight"] == P()
    assert specs["ffn_up_weight"] == P("tp", "fsdp")


def test_fsdp_min_size(monkeypatch):
    monkeypatch.setenv("MXNET_SHARD_FSDP_MIN_SIZE", "100")
    plan = ShardingPlan({"fsdp": 2, "tp": 2})
    specs = plan.resolve({"small_gamma": (8,),        # 8 < 100
                          "big_down_weight": (64, 4)})
    assert specs["small_gamma"] == P()
    assert specs["big_down_weight"] == P("fsdp")  # trailing None trimmed
    # explicit overrides are never downgraded
    plan = ShardingPlan({"fsdp": 2, "tp": 2},
                        overrides={"small_gamma": P("fsdp")})
    assert plan.resolve({"small_gamma": (8,)})["small_gamma"] \
        == P("fsdp")


def test_compute_spec_drops_fsdp():
    plan = ShardingPlan({"data": 2, "fsdp": 2, "tp": 2})
    assert plan.compute_spec(P("tp", "fsdp")) == P("tp")
    assert plan.compute_spec(P(("fsdp", "tp"), None)) == P("tp")
    assert plan.compute_spec(P("fsdp")) == P()
    assert plan.uses_fsdp()
    assert not ShardingPlan({"data": 8}).uses_fsdp()


def test_input_spec_batch_axes():
    plan = ShardingPlan({"data": 2, "fsdp": 2, "tp": 2})
    assert plan.batch_axes() == ("data", "fsdp")
    assert plan.input_spec("data", ndim=3) \
        == P(("data", "fsdp"), None, None)
    assert ShardingPlan({"data": 8}).input_spec("data", ndim=2) \
        == P("data", None)


# ------------------------------------------------- pre-trace verification
def test_verify_sharding_rejects_bad_explicit():
    from mxnet_tpu.analysis import GraphVerifyError, verify_sharding

    plan = ShardingPlan({"tp": 2}, overrides={"w": P(None, "tp")})
    with pytest.raises(GraphVerifyError) as ei:
        verify_sharding(plan, {"w": (8, 7)})  # 7 % 2 != 0
    msg = str(ei.value)
    assert "w" in msg and "tp" in msg and "7" in msg and "2" in msg
    # axis not in the mesh is also named
    plan = ShardingPlan({"data": 2}, overrides={"w": P("tp", None)})
    with pytest.raises(GraphVerifyError, match="tp"):
        verify_sharding(plan, {"w": (8, 8)})
    # advisory specs never raise (they downgrade instead)
    verify_sharding(ShardingPlan({"tp": 2}), {"l0_qkv_weight": (7, 7)})


# -------------------------------------------------- exact-parity helpers
def _toy_sym():
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data, name="out_head", num_hidden=8,
                                  no_bias=True)
    return mx.symbol.LinearRegressionOutput(fc, name="lro")


def _toy_iter():
    rng = np.random.RandomState(0)
    X = rng.randint(-1, 2, size=(8, 4)).astype(np.float32) / 2.0
    Y = rng.randint(-1, 2, size=(8, 8)).astype(np.float32) / 2.0
    return mx.io.NDArrayIter(X, Y, batch_size=8, label_name="lro_label")


def _toy_epochs(mod, n):
    it = _toy_iter()
    for _ in range(n):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()


def _toy_fit(plan=None, mesh_shape=None, n_steps=3):
    """3 SGD steps on one no-bias FC with dyadic-rational data: every
    intermediate stays exactly representable in f32, so the final
    params are bitwise-identical under ANY sharding."""
    it = _toy_iter()
    mod = mx.mod.Module(_toy_sym(), data_names=("data",),
                        label_names=("lro_label",),
                        sharding=plan, mesh_shape=mesh_shape)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    w0 = np.random.RandomState(7).randint(
        -1, 2, size=(8, 4)).astype(np.float32) / 2.0
    mod.init_params(arg_params={"out_head_weight": mx.nd.array(w0)},
                    aux_params={}, force_init=True)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    _toy_epochs(mod, n_steps)
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in params.items()}


# --------------------------------------------------------- module wiring
@needs8
def test_module_bind_rejects_bad_plan_before_trace():
    from mxnet_tpu.analysis import GraphVerifyError
    from mxnet_tpu import exec_cache

    plan = ShardingPlan({"data": 2, "tp": 2},
                        overrides={"out_head_weight": P(None, "tp")})
    mod = mx.mod.Module(_toy_sym(), data_names=("data",),
                        label_names=("lro_label",), sharding=plan)
    before = exec_cache.cache_stats()["traces"]
    with pytest.raises(GraphVerifyError, match="out_head_weight"):
        # (8, 5): 5 % tp=2 != 0 on the explicit override's dim 1
        mod.bind(data_shapes=[("data", (8, 5))],
                 label_shapes=[("lro_label", (8, 8))])
    assert exec_cache.cache_stats()["traces"] == before  # pre-trace


@needs8
def test_dp_plan_matches_mesh_shape_exactly():
    """Satellite 2: dp-only ShardingPlan == the FusedTrainStep
    mesh_shape path, param for param, bit for bit."""
    _, via_plan = _toy_fit(plan=ShardingPlan({"data": 8}))
    _, via_mesh = _toy_fit(mesh_shape={"data": 8})
    for name in via_mesh:
        assert np.array_equal(via_plan[name], via_mesh[name])


@needs8
def test_dp_tp_fsdp_parity_and_storage():
    """Tentpole acceptance: 2x2x2 plan training == unsharded training
    bitwise; param storage actually shards (tp x fsdp = 1/4 bytes)."""
    _, base = _toy_fit()  # no plan, no mesh
    mod, full = _toy_fit(
        plan=ShardingPlan({"data": 2, "fsdp": 2, "tp": 2}))
    for name in base:
        assert np.array_equal(base[name], full[name])
    fs = mod._fused_step
    assert fs is not None and fs._mesh is not None
    w = fs.params["out_head_weight"]
    assert w.sharding.spec == P("tp", "fsdp")
    replicated = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                     for v in fs.params.values())
    assert device_param_bytes(fs.params) * 2 <= replicated
    # gather-before-use was wired (storage != compute for the weight)
    assert "out_head_weight" in fs._gather_sh


@needs8
@pytest.mark.parametrize("mesh", [{"data": 8},
                                  {"data": 2, "fsdp": 2, "tp": 2}])
def test_sharded_steady_state_adds_no_trace(mesh):
    """After its first steps a plan-driven Module traces nothing more:
    no executor-cache trace and no sharded jit build in further
    epochs, replicated or partitioned."""
    from mxnet_tpu import exec_cache
    from mxnet_tpu.sharding import lower_stats

    mod, _ = _toy_fit(plan=ShardingPlan(mesh))
    before = (exec_cache.cache_stats()["traces"],
              lower_stats()["jit_builds"])
    _toy_epochs(mod, 3)
    mod.sync()
    assert (exec_cache.cache_stats()["traces"],
            lower_stats()["jit_builds"]) == before


@needs8
def test_plan_digest_joins_exec_cache_key():
    """Satellite 4 (cache half): same plan -> same exec-cache key;
    different plan -> different key; no plan -> a third key."""
    sym = _toy_sym()
    shapes = {"data": (8, 4), "lro_label": (8, 8)}
    p1 = ShardingPlan({"data": 8})
    p2 = ShardingPlan({"data": 2, "fsdp": 2, "tp": 2})
    e1 = sym.simple_bind(ctx=mx.cpu(), sharding=p1, **shapes)
    e1b = sym.simple_bind(ctx=mx.cpu(), sharding=ShardingPlan(
        {"data": 8}), **shapes)
    e2 = sym.simple_bind(ctx=mx.cpu(), sharding=p2, **shapes)
    e3 = sym.simple_bind(ctx=mx.cpu(), **shapes)
    assert e1._cache_key == e1b._cache_key
    assert e1._cache_key != e2._cache_key
    assert e1._cache_key != e3._cache_key and \
        e2._cache_key != e3._cache_key


# ------------------------------------------------------------ kvstore tpu
@needs8
def test_kv_barrier_mesh_path():
    """Satellite 3: the barrier runs as a mesh jit (no pmap) on the
    default path; force=True exercises it single-process."""
    from mxnet_tpu.parallel import kvstore_tpu as kvt
    from mxnet_tpu.sharding import lower_stats

    kv = mx.kv.create("tpu")
    before = lower_stats()["jit_builds"]
    kv._barrier(force=True)
    assert kvt._BARRIER_MESH is not None  # mesh program built
    assert lower_stats()["jit_builds"] >= before
    kv._barrier(force=True)  # second call reuses the cached program
    # legacy fallback still selectable
    import os
    old = os.environ.get("MXNET_SHARD_KV_MESH")
    os.environ["MXNET_SHARD_KV_MESH"] = "0"
    try:
        kv._barrier(force=False)  # single-process: early return
    finally:
        if old is None:
            os.environ.pop("MXNET_SHARD_KV_MESH", None)
        else:
            os.environ["MXNET_SHARD_KV_MESH"] = old


@needs8
def test_kv_attach_plan_pins_replicated():
    kv = mx.kv.create("tpu")
    plan = ShardingPlan({"data": 8})
    kv.attach_plan(plan)
    v = mx.nd.array(np.arange(16, dtype=np.float32).reshape(4, 4))
    kv.init(3, v)
    kv.push(3, [mx.nd.ones((4, 4)), mx.nd.ones((4, 4))])
    out = mx.nd.zeros((4, 4))
    kv.pull(3, out=out)
    # no updater: push stores the device-summed value; pull reads it
    assert np.array_equal(out.asnumpy(), 2 * np.ones((4, 4)))
    # the stored value now lives pinned to the plan's mesh
    stored = kv._store[3]._data
    assert getattr(stored.sharding, "mesh", None) is plan.mesh
    assert stored.sharding.is_fully_replicated
