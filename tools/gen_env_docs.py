#!/usr/bin/env python
"""Generate docs/env_vars.md from the typed env registry
(mxnet_tpu/utils — the analog of the reference docs/how_to/env_var.md,
which was hand-maintained; here the doc is derived from the single
source of truth so it cannot drift). tests/test_docs.py asserts the
checked-in file matches this generator's output."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def render():
    from mxnet_tpu import utils

    lines = [
        "# Environment variables",
        "",
        "Typed runtime knobs, read through the registry in",
        "`mxnet_tpu/utils` (the reference read ~25 `MXNET_*` vars via",
        "`dmlc::GetEnv` at point of use, documented by hand in its",
        "docs/how_to/env_var.md; this file is GENERATED — run",
        "`python tools/gen_env_docs.py` after registering a new var).",
        "",
        "| variable | type | default | effect |",
        "|---|---|---|---|",
    ]
    for name, ev in sorted(utils._ENV_REGISTRY.items()):
        default = repr(ev.default)
        help_ = " ".join(str(ev.help).split())
        lines.append(
            f"| `{name}` | {ev.type.__name__} | `{default}` | {help_} |")
    lines += [
        "",
        "Additional process-level knobs outside the registry:",
        "",
        "- `JAX_PLATFORMS=cpu` + `XLA_FLAGS=--xla_force_host_platform_"
        "device_count=N` — N-device virtual CPU mesh for testing "
        "sharded code without hardware (tests/conftest.py does this).",
        "- `XLA_PYTHON_CLIENT_MEM_FRACTION` / `_PREALLOCATE` — set via "
        "`mx.set_memory_fraction()`; see docs/perf.md.",
        "- `JAX_COMPILATION_CACHE_DIR` — where jax's persistent compile "
        "cache lives. When set, no code moves it; unset, "
        "`chip_smoke.py` uses `<checkout>/.jax_cache` and the exec-cache "
        "disk tier uses `<MXNET_EXEC_CACHE_DIR>/xla` "
        "(`exec_cache_disk.place_jax_cache`).",
        "",
    ]
    return "\n".join(lines)


if __name__ == "__main__":
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "env_vars.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(render())
    print(f"wrote {out}")
