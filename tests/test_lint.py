"""Lint gate (the reference gated `make lint` in CI). Two layers:
bytecode compilation + repo hygiene (merge markers, tabs), and the
framework-native analyzer — `tools/mxlint.py` over the whole tree must
report zero non-baselined findings (rules MX001-MX005, docs/analysis.md).
"""
import compileall
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_all_sources_compile():
    for pkg in ("mxnet_tpu", "tools", "examples", "tests"):
        path = os.path.join(ROOT, pkg)
        # compile_dir returns True for a MISSING dir — guard first
        assert os.path.isdir(path), path
        assert compileall.compile_dir(path, quiet=2, force=True), pkg


def test_no_merge_markers_or_tabs_in_python():
    bad = []
    for base in ("mxnet_tpu", "tools", "examples"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in dirpath:
                continue
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                if re.search(r"^(<{7}|>{7}|={7})( |$)", text, re.M):
                    bad.append((path, "merge marker"))
                if "\t" in text:
                    bad.append((path, "tab indentation"))
    assert not bad, bad


def test_mxlint_tree_is_clean():
    """The shipped tree passes the framework analyzer: zero findings
    beyond the checked-in baseline. The CLI is stdlib-only (never
    imports jax), so this runs as a plain subprocess."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
         "mxnet_tpu", "tools", "examples", "--format", "json"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["counts"]["new"] == 0, data["findings"]


def test_mxlint_exits_nonzero_on_violation(tmp_path):
    """The gate actually gates: a seeded violation fails the run."""
    bad = tmp_path / "mxnet_tpu" / "seeded.py"
    bad.parent.mkdir()
    bad.write_text("import os\n"
                   "x = os.environ.get('MXNET_NOT_A_REAL_KNOB')\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
         str(bad.parent), "--no-baseline"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "MX003" in proc.stdout


def test_mx009_pallas_call_containment():
    """MX009 keeps pl.pallas_call behind the kernel entry points: a
    raw call anywhere else is flagged, and even the allowlisted kernel
    modules must carry a visible lax/reference twin."""
    import ast

    from mxnet_tpu.analysis.rules import FileContext, check_mx009

    raw_kernel = ("from jax.experimental import pallas as pl\n"
                  "fn = pl.pallas_call(lambda i_ref, o_ref: None,\n"
                  "                    out_shape=None)\n")

    def findings(relpath, src):
        ctx = FileContext(relpath=relpath, tree=ast.parse(src),
                          lines=src.splitlines())
        return check_mx009(ctx)

    # outside the allowlist: flagged no matter what else the file has
    found = findings("mxnet_tpu/my_kernel.py", raw_kernel)
    assert len(found) == 1 and found[0].rule == "MX009"
    assert "outside the kernel entry points" in found[0].message

    # allowlisted module WITHOUT a lax twin: still flagged
    found = findings("mxnet_tpu/decoding/attention.py", raw_kernel)
    assert len(found) == 1 and "fallback" in found[0].message

    # allowlisted module WITH a module-level lax twin: clean; a
    # kernel-registry dict with a "lax" entry also counts
    twin = "def attention_lax(q, k, v):\n    return q\n\n"
    assert findings("mxnet_tpu/decoding/attention.py",
                    twin + raw_kernel) == []
    registry = 'KERNELS = {"lax": None}\n'
    assert findings("mxnet_tpu/parallel/attention.py",
                    registry + raw_kernel) == []

    # no pallas_call at all: nothing to say
    assert findings("mxnet_tpu/anything.py", "x = 1\n") == []
