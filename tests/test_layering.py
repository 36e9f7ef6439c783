"""The arrows between the program's packages, held by a test.

PERF.md section 3 names two paths from the entry point down to the
device (training: module -> executor / exec_cache / parallel -> ops;
serving: serving -> decoding). LAYERS below puts their packages, and
the ones both use, in ONE order, lowest first: a package may
import what stands before it and nothing that stands after it. The
files are parsed with `ast` (imports inside functions count: a lazy
import is still a dependency); nothing of the program is imported.

The upward edges the tree still has are listed in KNOWN_UPWARD_EDGES,
each with the ROADMAP.md debt (Queue C) that removes it. A NEW upward
edge fails its package's case, and so does a listed edge that is no
longer there: the list can only shrink.
"""
import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "mxnet_tpu")

#: lowest first; a layer is a package directory or one or two modules.
#: The decode tier stands UNDER the Symbol graph's tier (passes,
#: executor, module): it runs no graph pass and binds no Executor, so
#: it may import none of them; `serving` loads both kinds of model and
#: stands over both.
LAYERS = {
    "telemetry": ("telemetry",),
    "utils": ("utils",),
    "exec_cache_disk": ("exec_cache_disk.py",),
    "numerics": ("numerics",),
    "ops": ("ops",),
    "analysis": ("analysis",),
    "profiling": ("profiling",),
    "sharding": ("sharding",),
    "parallel": ("parallel",),
    "decoding": ("decoding",),
    "passes": ("passes",),
    "executor": ("executor.py", "exec_cache.py"),
    "module": ("module",),
    "serving": ("serving",),
    "fleet": ("fleet",),
    "elastic": ("elastic",),
}
ORDER = list(LAYERS)
REST = "rest of mxnet_tpu"

#: (importing file, layer it reaches up to) -> the debt that removes it
KNOWN_UPWARD_EDGES = {
    ("mxnet_tpu/decoding/config.py", "serving"): "C18",
    ("mxnet_tpu/decoding/engine.py", "serving"): "C18",
    ("mxnet_tpu/decoding/sampling.py", "serving"): "C18",
    ("mxnet_tpu/decoding/scheduler.py", "serving"): "C18",
    ("mxnet_tpu/decoding/stats.py", "serving"): "C18",
    ("mxnet_tpu/profiling/preflight.py", "passes"): "C19",
    ("mxnet_tpu/sharding/plan.py", "parallel"): "C20",
    ("mxnet_tpu/sharding/spec.py", "parallel"): "C20",
    ("mxnet_tpu/ops/parallel_ops.py", "parallel"): "C20",
}


def _outside_names():
    """What no file of the program may import: the benchmark, and the
    scripts and directories beside the package."""
    names = set()
    for entry in os.listdir(REPO):
        path = os.path.join(REPO, entry)
        if entry.endswith(".py"):
            names.add(entry[:-3])
        elif (os.path.isdir(path) and entry != "mxnet_tpu"
              and not entry.startswith(".")):
            names.add(entry)
    return names


def _layer_files(layer):
    for part in LAYERS[layer]:
        path = os.path.join(PKG, part)
        if part.endswith(".py"):
            yield path
            continue
        for root, _, files in os.walk(path):
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(root, name)


def _rest_files():
    claimed = {f for layer in LAYERS for f in _layer_files(layer)}
    for root, _, files in os.walk(PKG):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and path not in claimed:
                yield path


def _imported_modules(path):
    """Absolute dotted names of everything `path` imports, relative
    imports resolved against the file's own place in the package."""
    rel = os.path.relpath(path, REPO)[:-3].split(os.sep)
    here = rel[:-1]                    # the package the file lives in
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                up = node.level - 1
                base = here[:len(here) - up] if up else here
                assert base, (path, node.lineno)
                mod = ".".join(base + ([node.module]
                                       if node.module else []))
            else:
                mod = node.module
            yield mod
            # `from .. import passes` names a module as an alias
            for alias in node.names:
                yield f"{mod}.{alias.name}"


def _layer_of(module):
    parts = module.split(".")
    if parts[0] != "mxnet_tpu" or len(parts) < 2:
        return None
    for layer, members in LAYERS.items():
        for part in members:
            if parts[1] == (part[:-3] if part.endswith(".py") else part):
                return layer
    return None


@pytest.mark.parametrize("layer", ORDER + [REST])
def test_imports_point_down(layer):
    outside = _outside_names()
    files = _rest_files() if layer == REST else _layer_files(layer)
    own, upward, leaks = set(), set(), set()
    for path in files:
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        own.add(rel)
        for module in _imported_modules(path):
            if module.split(".")[0] in outside:
                leaks.add((rel, module))
            target = None if layer == REST else _layer_of(module)
            if (target is not None
                    and ORDER.index(target) > ORDER.index(layer)):
                upward.add((rel, target))
    assert not leaks, (
        f"the program imports the benchmark or a script: {sorted(leaks)}")
    if layer == REST:
        return
    known = {edge for edge in KNOWN_UPWARD_EDGES if edge[0] in own}
    new = sorted(upward - known)
    assert not new, (
        f"`{layer}` imports a layer above it ({' < '.join(ORDER)}): "
        f"{new}. Move what both need to where both may import it.")
    gone = sorted(known - upward)
    assert not gone, (
        f"these upward edges are gone: {gone}. Take them out of "
        "KNOWN_UPWARD_EDGES and strike their debt in ROADMAP.md.")
