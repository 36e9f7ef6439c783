"""mxnet_tpu.analysis: mxlint rules MX001-MX005 (trigger + suppress),
the effects pass MX010-MX012 and protocol-drift pass MX013 (trigger +
suppress + baseline on synthetic trees), jit-entry reachability on a
synthetic module, the result cache, engine mechanics (suppression
forms, baseline multiset), and the pre-bind graph verifier
(shape/dtype contradictions, duplicate args, dead nodes, donation
aliasing) on hand-built Symbols."""
import ast
import json
import os
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.analysis import (
    GraphVerifyError,
    callgraph,
    effects,
    lint,
    rules,
    verify_graph,
)


def _lint_src(src, relpath, registered_envs=(), tmp_path=None,
              select=None):
    """Run the real engine over one synthetic file."""
    path = tmp_path / os.path.basename(relpath)
    path.write_text(textwrap.dedent(src))
    return lint.lint_file(str(path), relpath, set(registered_envs),
                          select=select)


# ===================================================================
# MX001 — host sync on a declared hot path
# ===================================================================
HOT = "mxnet_tpu/serving/batcher.py"  # manifest says "*": every def is hot


def test_mx001_flags_sync_calls_on_hot_path(tmp_path):
    src = """
    import numpy as np

    def flush(batch):
        a = batch.out.asnumpy()
        batch.out.wait_to_read()
        s = batch.loss.item()
        h = np.array(batch.dev_arr)
        return a, s, h
    """
    found = _lint_src(src, HOT, tmp_path=tmp_path, select={"MX001"})
    assert [f.rule for f in found] == ["MX001"] * 4
    assert "asnumpy" in found[0].message
    assert "hot-path" in found[0].message


def test_mx001_quiet_off_manifest_and_suppressible(tmp_path):
    src = """
    def flush(batch):
        return batch.out.asnumpy()
    """
    # same code, not a manifest file -> clean
    assert not _lint_src(src, "mxnet_tpu/model.py", tmp_path=tmp_path,
                         select={"MX001"})
    sup = """
    def flush(batch):
        return batch.out.asnumpy()  # mxlint: disable=MX001
    """
    assert not _lint_src(sup, HOT, tmp_path=tmp_path, select={"MX001"})


def test_mx001_item_with_args_is_not_a_sync(tmp_path):
    # dict.item-like calls with arguments are not the 0-arg scalar fetch
    src = """
    def flush(d):
        return d.item("k")
    """
    assert not _lint_src(src, HOT, tmp_path=tmp_path, select={"MX001"})


# ===================================================================
# MX002 — retrace hazards
# ===================================================================
def test_mx002_jit_in_loop_and_immediate_invoke(tmp_path):
    src = """
    import jax

    def train(fn, xs):
        for x in xs:
            step = jax.jit(lambda v: v + 1)
            x = step(x)
        return jax.jit(fn)(xs[0])
    """
    found = _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                      select={"MX002"})
    assert [f.rule for f in found] == ["MX002", "MX002"]
    msgs = " ".join(f.message for f in found)
    assert "inside a loop" in msgs and "immediately invoked" in msgs


def test_mx002_hoisted_jit_is_clean(tmp_path):
    src = """
    import jax

    _step = jax.jit(lambda v: v + 1)

    def train(xs):
        for x in xs:
            x = _step(x)
        return x
    """
    assert not _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX002"})


def test_mx002_suppress_next_line(tmp_path):
    src = """
    import jax

    def once(fn, x):
        # retrace accepted: one-shot probe
        # mxlint: disable-next-line=MX002
        return jax.jit(fn)(x)
    """
    assert not _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX002"})


# ===================================================================
# MX003 — unregistered MXNET_* env reads
# ===================================================================
def test_mx003_unregistered_reads_flagged(tmp_path):
    src = """
    import os

    a = os.environ.get("MXNET_BOGUS_KNOB", "0")
    b = os.getenv("MXNET_OTHER_KNOB")
    c = os.environ["MXNET_THIRD_KNOB"]
    d = os.environ.get("NOT_OURS")            # non-MXNET: ignored
    e = os.environ.get("MXNET_KNOWN_KNOB")    # registered: ignored
    """
    found = _lint_src(src, "mxnet_tpu/foo.py",
                      registered_envs={"MXNET_KNOWN_KNOB"},
                      tmp_path=tmp_path, select={"MX003"})
    names = sorted(f.message.split("'")[1] for f in found)
    assert names == ["MXNET_BOGUS_KNOB", "MXNET_OTHER_KNOB",
                     "MXNET_THIRD_KNOB"]


def test_mx003_suppressed_inline(tmp_path):
    src = """
    import os

    a = os.environ.get("MXNET_SCRATCH")  # mxlint: disable=MX003
    """
    assert not _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX003"})


def test_registry_collection_sees_register_env_calls(tmp_path):
    mod = tmp_path / "reg.py"
    mod.write_text(
        'register_env("MXNET_FROM_SCAN", int, 1, "doc")\n'
        'utils.register_env("MXNET_VIA_ATTR", str, "", "doc")\n')
    got = rules.collect_registered_envs([str(tmp_path)])
    assert got == {"MXNET_FROM_SCAN", "MXNET_VIA_ATTR"}


# ===================================================================
# MX004 — concurrency hygiene
# ===================================================================
def test_mx004_bare_except_thread_acquire(tmp_path):
    src = """
    import threading

    def go(q, lock):
        t = threading.Thread(target=q.get)
        t.start()
        lock.acquire()
        try:
            pass
        except:
            pass
    """
    found = _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                      select={"MX004"})
    msgs = " ".join(f.message for f in found)
    assert len(found) == 3
    assert "daemon" in msgs and "acquire" in msgs and "bare" in msgs


def test_mx004_clean_forms(tmp_path):
    src = """
    import threading

    def go(q, lock):
        t = threading.Thread(target=q.get, daemon=True)
        t.start()
        with lock:
            pass
        try:
            pass
        except Exception:
            pass
    """
    assert not _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX004"})


# ===================================================================
# MX005 — nondeterminism
# ===================================================================
def test_mx005_global_rng_and_wallclock_key(tmp_path):
    src = """
    import random
    import time
    import numpy as np

    def augment(img):
        if random.random() < 0.5:
            return img + np.random.normal(0, 1, img.shape)
        return img

    def cache_key(sym):
        return (sym.name, time.time())
    """
    found = _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                      select={"MX005"})
    msgs = " ".join(f.message for f in found)
    assert len(found) == 3
    assert "py_rng" in msgs and "np_rng" in msgs and "wall-clock" in msgs


def test_mx005_library_only_and_owned_generators_ok(tmp_path):
    src = """
    import random
    import numpy as np

    r = random.random()
    """
    # user-side code (tools/, examples/) is out of contract
    assert not _lint_src(src, "tools/sample.py", tmp_path=tmp_path,
                         select={"MX005"})
    owned = """
    import numpy as np

    def sample(seed, shape):
        rng = np.random.RandomState(seed)   # owned stream: fine
        return rng.uniform(size=shape)
    """
    assert not _lint_src(owned, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX005"})


def test_mx005_disable_file(tmp_path):
    src = """
    # mxlint: disable-file=MX005
    import random

    x = random.random()
    y = random.random()
    """
    assert not _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX005"})


def test_mx005_wallclock_outside_key_fn_is_fine(tmp_path):
    src = """
    import time

    def speedometer(t0):
        return time.time() - t0
    """
    assert not _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                         select={"MX005"})


# ===================================================================
# engine mechanics
# ===================================================================
def test_syntax_error_is_reported_not_raised(tmp_path):
    found = _lint_src("def broken(:\n", "mxnet_tpu/foo.py",
                      tmp_path=tmp_path)
    assert [f.rule for f in found] == ["MXSYN"]


def test_baseline_multiset_consumption(tmp_path):
    src = """
    import os

    a = os.environ.get("MXNET_AAA")
    b = os.environ.get("MXNET_AAA")
    """
    found = _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                      select={"MX003"})
    assert len(found) == 2
    bl = tmp_path / "baseline.json"
    # baseline only ONE of the two identical findings: the second must
    # still be reported (multiset consume, not set membership)
    lint.write_baseline(found[:1], str(bl))
    new, kept = lint.apply_baseline(found, lint.load_baseline(str(bl)))
    assert len(new) == 1 and len(kept) == 1 and kept[0].baselined
    # baselining both silences both, and the exit code goes green
    lint.write_baseline(found, str(bl))
    relint = _lint_src(src, "mxnet_tpu/foo.py", tmp_path=tmp_path,
                       select={"MX003"})
    new, kept = lint.apply_baseline(relint, lint.load_baseline(str(bl)))
    assert not new and len(kept) == 2


def test_render_json_shape(tmp_path):
    found = _lint_src("import os\nx = os.environ.get('MXNET_ZZZ')\n",
                      "mxnet_tpu/foo.py", tmp_path=tmp_path)
    data = json.loads(lint.render_json(found, []))
    assert data["counts"] == {"new": 1, "baselined": 0}
    f = data["findings"][0]
    assert f["rule"] == "MX003" and f["path"] == "mxnet_tpu/foo.py"


def test_self_scan_analysis_package_is_clean():
    """mxlint self-hosts: the analyzer's own sources lint clean."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = lint.lint_paths(
        [os.path.join(root, "mxnet_tpu", "analysis")], root=root,
        extra_registry_paths=(
            os.path.join(root, "mxnet_tpu", "utils", "__init__.py"),))
    assert not found, [f.format_text() for f in found]


# ===================================================================
# MX010-MX013 — effects + protocol passes (project scope)
# ===================================================================
def _lint_tree(files, tmp_path, select=None):
    """Write {relpath: src} under tmp_path and run the full engine —
    per-file rules AND the project-scope passes — over the tree."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint.lint_paths([str(tmp_path)], root=str(tmp_path),
                           select=select)


MX010_TRIGGER = """
    import jax

    LOG = []

    def helper(x):
        LOG.append(x)
        return x

    def step(x):
        print(x)
        return helper(x) + 1

    run = jax.jit(step)
    """


def test_mx010_impure_jitted_function(tmp_path):
    found = _lint_tree({"mod.py": MX010_TRIGGER}, tmp_path,
                       select={"MX010"})
    assert [f.rule for f in found] == ["MX010", "MX010"]
    srcs = {f.source for f in found}
    assert srcs == {"LOG.append(x)", "print(x)"}
    msgs = " ".join(f.message for f in found)
    assert "jit entry" in msgs


def test_mx010_unreached_effect_and_suppression(tmp_path):
    # same effects with no jit entry anywhere: out of scope
    cold = """
    LOG = []

    def helper(x):
        LOG.append(x)
        return x
    """
    assert not _lint_tree({"mod.py": cold}, tmp_path,
                          select={"MX010"})
    sup = """
    import jax

    LOG = []

    def step(x):
        LOG.append(x)  # mxlint: disable=MX010
        return x

    run = jax.jit(step)
    """
    assert not _lint_tree({"mod.py": sup}, tmp_path,
                          select={"MX010"})


def test_jit_reachability_on_synthetic_module():
    src = textwrap.dedent("""
    import jax

    def leaf(x):
        return x + 1

    def mid(x):
        return leaf(x)

    def top(x):
        return mid(x)

    def cold(x):
        return x

    entry = jax.jit(top)
    """)
    files = [("mod.py", ast.parse(src))]
    graph = callgraph.CallGraph(files)
    entries = effects.jit_entries(graph, files)
    assert ("mod.py", "top") in entries
    reach = effects.reachable_from(graph, entries)
    names = {qn for (_rel, qn) in reach}
    assert {"top", "mid", "leaf"} <= names
    assert "cold" not in names
    # hop counts: entry itself 0, transitive callee 2
    assert reach[("mod.py", "top")][1] == 0
    assert reach[("mod.py", "leaf")][1] == 2


MX011_TRIGGER = """
    import jax

    def _run(params, x):
        return params, x

    step = jax.jit(_run, donate_argnums=(0,))

    def go(params, x):
        out = step(params, x)
        return params
    """


def test_mx011_use_after_donate(tmp_path):
    found = _lint_tree({"mod.py": MX011_TRIGGER}, tmp_path,
                       select={"MX011"})
    assert [f.rule for f in found] == ["MX011"]
    assert found[0].source == "return params"
    assert "donated" in found[0].message


def test_mx011_rebind_kills_and_suppression(tmp_path):
    rebound = """
    import jax

    def _run(params, x):
        return params, x

    step = jax.jit(_run, donate_argnums=(0,))

    def go(params, x):
        params, aux = step(params, x)
        return params
    """
    assert not _lint_tree({"mod.py": rebound}, tmp_path,
                          select={"MX011"})
    sup = MX011_TRIGGER.replace(
        "return params",
        "return params  # mxlint: disable=MX011")
    assert not _lint_tree({"mod.py": sup}, tmp_path,
                          select={"MX011"})


MX012_TRIGGER = """
    import json

    MXLINT_DIGEST_PATH = "*"

    def digest(tree, f):
        out = []
        for k in tree.values():
            out.append(k)
        json.dump(out, f)
        return out
    """


def test_mx012_unordered_iteration_on_digest_path(tmp_path):
    found = _lint_tree({"mod.py": MX012_TRIGGER}, tmp_path,
                       select={"MX012"})
    assert [f.rule for f in found] == ["MX012", "MX012"]
    msgs = " ".join(f.message for f in found)
    assert "sort" in msgs


def test_mx012_sorted_and_optout_are_clean(tmp_path):
    clean = """
    import json

    MXLINT_DIGEST_PATH = "*"

    def digest(tree, f):
        out = []
        for k, v in sorted(tree.items()):
            out.append((k, v))
        json.dump(out, f, sort_keys=True)
        return out
    """
    assert not _lint_tree({"mod.py": clean}, tmp_path,
                          select={"MX012"})
    # tuple form covers only the named qualnames
    scoped = """
    MXLINT_DIGEST_PATH = ("digest",)

    def digest(tree):
        return [k for k in sorted(tree.values())]

    def display(tree):
        return [k for k in tree.values()]  # not a digest fn: fine
    """
    assert not _lint_tree({"mod.py": scoped}, tmp_path,
                          select={"MX012"})


MX013_DRIFT = {
    "sender.py": """
    MXLINT_PROTOCOL = "tproto"

    def run(sock):
        sock.send({"op": "ping", "seq": 1})
        sock.send({"op": "orphan"})
    """,
    "handler.py": """
    MXLINT_PROTOCOL = "tproto"

    def on_message(sock, msg):
        op = msg.get("op")
        if op == "ping":
            return msg["seq"]
        if op == "stale":
            return None
    """,
}


def test_mx013_orphaned_op_and_dead_handler(tmp_path):
    found = _lint_tree(dict(MX013_DRIFT), tmp_path, select={"MX013"})
    assert [f.rule for f in found] == ["MX013", "MX013"]
    by_path = {f.path: f.message for f in found}
    assert "orphan" in by_path["sender.py"]      # sent, never handled
    assert "stale" in by_path["handler.py"]      # handled, never sent
    # the matched op/field pair raises nothing
    assert not any("seq" in m for m in by_path.values())


def test_mx013_missing_required_field(tmp_path):
    files = dict(MX013_DRIFT)
    files["handler.py"] = files["handler.py"].replace(
        'return msg["seq"]', 'return msg["seq"] + msg["nonce"]')
    found = _lint_tree(files, tmp_path, select={"MX013"})
    missing = [f for f in found if "nonce" in f.message]
    assert len(missing) == 1
    assert "no sender" in missing[0].message


def test_mx013_suppression(tmp_path):
    files = {
        "sender.py": MX013_DRIFT["sender.py"].replace(
            'sock.send({"op": "orphan"})',
            'sock.send({"op": "orphan"})  # mxlint: disable=MX013'),
        "handler.py": MX013_DRIFT["handler.py"].replace(
            'if op == "stale":',
            '# mxlint: disable-next-line=MX013\n'
            '    if op == "stale":'),
    }
    assert not _lint_tree(files, tmp_path, select={"MX013"})


def test_effects_and_protocol_findings_are_baselinable(tmp_path):
    """Every MX010-MX013 finding routes through the same baseline
    multiset as the per-file rules."""
    files = dict(MX013_DRIFT)
    files["impure.py"] = MX010_TRIGGER
    files["donate.py"] = MX011_TRIGGER
    files["digest.py"] = MX012_TRIGGER
    select = {"MX010", "MX011", "MX012", "MX013"}
    found = _lint_tree(files, tmp_path, select=select)
    assert sorted({f.rule for f in found}) == [
        "MX010", "MX011", "MX012", "MX013"]
    bl = tmp_path / "baseline.json"
    lint.write_baseline(found, str(bl))
    relint = _lint_tree(files, tmp_path, select=select)
    new, kept = lint.apply_baseline(relint, lint.load_baseline(str(bl)))
    assert not new and len(kept) == len(found)


# ===================================================================
# result cache + parallel analysis
# ===================================================================
CACHED_SRC = 'import os\nx = os.environ.get("MXNET_CACHED_KNOB")\n'


def test_cache_roundtrip_and_invalidation(tmp_path):
    d = tmp_path / "tree"
    d.mkdir()
    (d / "mod.py").write_text(CACHED_SRC)
    cache = str(tmp_path / "cache.json")
    cold = lint.lint_paths([str(d)], root=str(d), cache_path=cache)
    assert os.path.exists(cache)
    assert [f.rule for f in cold] == ["MX003"]
    warm = lint.lint_paths([str(d)], root=str(d), cache_path=cache)
    assert [f.__dict__ for f in warm] == [f.__dict__ for f in cold]
    # a content edit invalidates exactly that file's entry
    (d / "mod.py").write_text(
        CACHED_SRC.replace("MXNET_CACHED_KNOB", "MXNET_OTHER_KNOB"))
    edited = lint.lint_paths([str(d)], root=str(d), cache_path=cache)
    assert "MXNET_OTHER_KNOB" in edited[0].message


def test_cache_stores_full_findings_select_filters(tmp_path):
    """A select run against a cache written by a full run (and the
    reverse) must agree with uncached results."""
    d = tmp_path / "tree"
    d.mkdir()
    (d / "mod.py").write_text(CACHED_SRC)
    cache = str(tmp_path / "cache.json")
    # warm the cache with a SELECT run; a later full run still sees
    # everything (entries always hold the unfiltered finding set)
    sel = lint.lint_paths([str(d)], root=str(d), cache_path=cache,
                          select={"MX001"})
    assert sel == []
    full = lint.lint_paths([str(d)], root=str(d), cache_path=cache)
    assert [f.rule for f in full] == ["MX003"]
    sel2 = lint.lint_paths([str(d)], root=str(d), cache_path=cache,
                           select={"MX003"})
    assert [f.rule for f in sel2] == ["MX003"]


def test_parallel_jobs_match_serial(tmp_path):
    d = tmp_path / "tree"
    d.mkdir()
    (d / "a.py").write_text(CACHED_SRC)
    (d / "b.py").write_text(
        CACHED_SRC.replace("MXNET_CACHED_KNOB", "MXNET_B_KNOB"))
    (d / "c.py").write_text("x = 1\n")
    serial = lint.lint_paths([str(d)], root=str(d))
    para = lint.lint_paths([str(d)], root=str(d), jobs=2)
    assert [f.__dict__ for f in para] == [f.__dict__ for f in serial]


def test_engine_version_pins_the_cache(tmp_path):
    """A cache written under a different engine hash is discarded."""
    d = tmp_path / "tree"
    d.mkdir()
    (d / "mod.py").write_text(CACHED_SRC)
    cache = tmp_path / "cache.json"
    lint.lint_paths([str(d)], root=str(d), cache_path=str(cache))
    data = json.loads(cache.read_text())
    assert data["engine"] == lint.engine_version()
    data["engine"] = "stale"
    # poison every cached finding: if the stale cache were trusted,
    # the bogus rule would surface
    for ent in data["files"].values():
        for f in ent["findings"]:
            f["rule"] = "MX999"
    cache.write_text(json.dumps(data))
    fresh = lint.lint_paths([str(d)], root=str(d),
                            cache_path=str(cache))
    assert [f.rule for f in fresh] == ["MX003"]


# ===================================================================
# graph verifier
# ===================================================================
def test_verify_clean_graph_passes():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    out = mx.sym.SoftmaxOutput(fc, name="softmax")
    assert verify_graph(out, data=(4, 16)) == []


def test_verify_declared_vs_bound_shape_contradiction():
    v = mx.sym.Variable("x", shape=(3, 4))
    s = mx.sym.identity(v, name="id")
    with pytest.raises(GraphVerifyError) as ei:
        verify_graph(s, x=(5, 6))
    (issue,) = ei.value.issues
    assert issue.kind == "shape_contradiction"
    assert "(3, 4)" in issue.message and "(5, 6)" in issue.message


def test_verify_op_shape_contradiction_names_the_op():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    d = mx.sym.dot(a, b, name="mm")
    with pytest.raises(GraphVerifyError) as ei:
        verify_graph(d, a=(2, 3), b=(4, 5))
    (issue,) = ei.value.issues
    assert issue.kind == "shape_contradiction"
    assert "'mm'" in issue.message          # offending op is named
    assert "(2, 3)" in issue.message and "(4, 5)" in issue.message


def test_verify_dtype_contradiction_at_elemwise():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    s = mx.sym.elemwise_add(a, b, name="add")
    issues = verify_graph(
        s, raise_on_issue=False,
        dtypes={"a": np.float32, "b": np.float16},
        a=(2, 2), b=(2, 2))
    assert any(i.kind == "dtype_contradiction" and "'add'" in i.message
               for i in issues)


def test_verify_duplicate_name():
    x = mx.sym.Variable("dup")
    y = mx.sym.identity(x, name="dup")
    with pytest.raises(GraphVerifyError) as ei:
        verify_graph(y)
    assert ei.value.issues[0].kind == "duplicate_arg"


def test_verify_donation_alias_through_reshape():
    w = mx.sym.Variable("w")
    r = mx.sym.Reshape(w, shape=(4,), name="rs")
    with pytest.raises(GraphVerifyError) as ei:
        verify_graph(r, grad_names=["w"], w=(2, 2))
    (issue,) = ei.value.issues
    assert issue.kind == "donation_alias"
    assert "'w'" in issue.message
    # same head with no grad on w: not a hazard
    assert verify_graph(r, grad_names=[], w=(2, 2)) == []


def test_verify_dead_node_in_json():
    live = mx.sym.identity(mx.sym.Variable("p"), name="live")
    g = json.loads(live.tojson())
    g["nodes"].append(
        {"op": "identity", "name": "orphan", "inputs": [[0, 0]]})
    issues = verify_graph(g, raise_on_issue=False)
    assert [(i.kind, i.node) for i in issues] == [("dead_node", "orphan")]
    # the checked JSON string form works too
    issues = verify_graph(json.dumps(g), raise_on_issue=False)
    assert issues and issues[0].kind == "dead_node"


def test_verify_json_bad_input_index():
    g = {"nodes": [{"op": "null", "name": "x", "inputs": [[7, 0]]}],
         "heads": [[0, 0]]}
    issues = verify_graph(g, raise_on_issue=False)
    assert any("nonexistent" in i.message for i in issues)


def test_executor_build_runs_verifier(monkeypatch):
    """Under MXNET_GRAPH_VERIFY=1 a contradicted bind fails at _build
    with the op named — before any jit tracing."""
    monkeypatch.setenv("MXNET_GRAPH_VERIFY", "1")
    v = mx.sym.Variable("x", shape=(2, 2))
    s = mx.sym.identity(v, name="id")
    arr = mx.nd.array(np.zeros((3, 3), dtype=np.float32))
    with pytest.raises(GraphVerifyError):
        s.bind(ctx=mx.cpu(), args={"x": arr}, grad_req="null")
    # flag off: the same bind is allowed through to (working) execution
    monkeypatch.setenv("MXNET_GRAPH_VERIFY", "0")
    ex = s.bind(ctx=mx.cpu(), args={"x": arr}, grad_req="null")
    assert ex.forward()[0].shape == (3, 3)
