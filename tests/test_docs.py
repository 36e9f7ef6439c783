"""Docs stay true: env_vars.md is generated (must match the registry),
and code snippets' API references must exist."""
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


def test_env_vars_doc_in_sync():
    import gen_env_docs

    with open(os.path.join(ROOT, "docs/env_vars.md")) as f:
        on_disk = f.read()
    assert on_disk == gen_env_docs.render(), (
        "docs/env_vars.md is stale — run python tools/gen_env_docs.py")


def test_every_registered_env_documented():
    from mxnet_tpu import utils

    with open(os.path.join(ROOT, "docs/env_vars.md")) as f:
        doc = f.read()
    for name in utils._ENV_REGISTRY:
        assert f"`{name}`" in doc, name


def test_every_registered_env_is_read_by_the_program():
    """A registered variable that no file of the package reads is a
    switch wired to nothing: the registry (and docs/env_vars.md) then
    promises what the program does not do."""
    from mxnet_tpu import utils

    registry = os.path.join(ROOT, "mxnet_tpu", "utils", "__init__.py")
    sources = []
    for root, _, files in os.walk(os.path.join(ROOT, "mxnet_tpu")):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and path != registry:
                with open(path) as f:
                    sources.append(f.read())
    text = "\n".join(sources)
    unread = [name for name in utils._ENV_REGISTRY
              if not re.search(rf"""["']{name}["']""", text)]
    assert not unread, unread


def test_doc_api_references_exist():
    import mxnet_tpu as mx

    # the load-bearing names the guides lean on
    for path in ("sym.RingAttention", "sym.MoEFFN",
                 "mod.PipelineModule", "mod.BucketingModule",
                 "set_memory_fraction", "rtc.PallasKernel",
                 "callback.Speedometer", "model.load_checkpoint",
                 "autograd.train_section"):
        obj = mx
        for part in path.split("."):
            obj = getattr(obj, part)


def test_doc_file_references_exist():
    """Every `path`-style reference to a repo file in docs/ resolves."""
    pat = re.compile(r"`((?:tools|docs|examples|tests|native|mxnet_tpu|"
                     r"cpp-package)/[\w./-]+)`")
    for fn in os.listdir(os.path.join(ROOT, "docs")):
        with open(os.path.join(ROOT, "docs", fn)) as f:
            text = f.read()
        for ref in pat.findall(text):
            assert os.path.exists(os.path.join(ROOT, ref)), (fn, ref)


def test_api_doc_in_sync():
    import gen_api_docs

    with open(os.path.join(ROOT, "docs/api.md")) as f:
        on_disk = f.read()
    assert on_disk == gen_api_docs.render(), (
        "docs/api.md is stale — run python tools/gen_api_docs.py")
