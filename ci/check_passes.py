#!/usr/bin/env python
"""CI gate: the graph-pass pipeline must actually shrink the executed
graph, bit-for-bit-close parity included.

Runtime A/B over a seeded redundant net (dead branch + const subgraph +
CSE duplicate + identity op): binds it with MXNET_GRAPH_PASSES=0 and
=1 and asserts

  1. the optimized bind executes strictly fewer graph nodes,
  2. forward AND backward outputs agree to 1e-6 relative,
  3. steady-state re-binds with passes ON stay trace-free (the memoized
     pipeline + canonical cache key add zero retraces), and
  4. two differently-built isomorphic symbols converge on ONE compiled
     program (canonical_collisions goes live).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import exec_cache, passes  # noqa: E402

RTOL = 1e-6


def _redundant_net(noise=0):
    for _ in range(noise):              # vary auto-name numbering
        _ = mx.sym.exp(mx.sym.Variable("x"))
    x = mx.sym.Variable("x")
    w = mx.sym.Variable("w")
    a = x * w
    b = x * w                           # CSE duplicate
    c = mx.sym.zeros((4, 8)) + 3.0      # const-foldable subgraph
    d = (a + b) * 1.0                   # identity (non-head)
    return mx.sym.broadcast_add(d, c)


def _run(spec, noise=0):
    os.environ["MXNET_GRAPH_PASSES"] = spec
    exec_cache.clear()
    exec_cache.reset_stats()
    passes.clear_memo()
    net = _redundant_net(noise)
    exe = net.simple_bind(mx.cpu(), x=(4, 8), w=(4, 8))
    rs = np.random.RandomState(0)
    vals = {k: rs.rand(4, 8).astype("float32") for k in ("x", "w")}
    exe.forward(is_train=True,
                **{k: mx.nd.array(v) for k, v in vals.items()})
    out = exe.outputs[0].asnumpy()
    exe.backward()
    grads = {k: g.asnumpy() for k, g in exe.grad_dict.items()
             if g is not None}
    n_exec = len(exe._compiled.plan)
    return net, exe, out, grads, n_exec


def main():
    net_raw, _, out_raw, g_raw, n_raw = _run("0")
    net_opt, exe_opt, out_opt, g_opt, n_opt = _run("1")

    # 1. strictly fewer executed nodes
    assert n_opt < n_raw, (
        f"pipeline did not shrink the executed graph: {n_raw} -> {n_opt}")

    # 2. numerical parity, forward and backward
    np.testing.assert_allclose(out_raw, out_opt, rtol=RTOL, atol=1e-6)
    assert set(g_raw) == set(g_opt)
    for k in g_raw:
        np.testing.assert_allclose(g_raw[k], g_opt[k], rtol=RTOL,
                                   atol=1e-6, err_msg=f"grad {k}")

    # 3. steady-state re-binds with passes on: zero retraces
    before = exec_cache.cache_stats()["traces"]
    for _ in range(3):
        _redundant_net().simple_bind(mx.cpu(), x=(4, 8), w=(4, 8))
    stats = exec_cache.cache_stats()
    assert stats["traces"] == before, (
        f"re-binds retraced: {before} -> {stats['traces']}")

    # 4. isomorphic build orders share one program
    _redundant_net(noise=5).simple_bind(mx.cpu(), x=(4, 8), w=(4, 8))
    stats = exec_cache.cache_stats()
    assert stats["traces"] == before, stats
    assert stats["canonical_collisions"] >= 1, stats

    pst = passes.graph_pass_stats()
    print(f"passes gate OK: executed nodes {n_raw} -> {n_opt}, "
          f"parity rtol={RTOL}, steady-state traces={stats['traces']}, "
          f"canonical_collisions={stats['canonical_collisions']}, "
          f"folds={pst['folds']} cse_hits={pst['cse_hits']} "
          f"eliminated={pst['nodes_eliminated']}")


if __name__ == "__main__":
    main()
