#!/usr/bin/env python
"""mxlint — the framework-native static analyzer (docs/analysis.md).

    python tools/mxlint.py mxnet_tpu tools examples
    python tools/mxlint.py mxnet_tpu --format json
    python tools/mxlint.py mxnet_tpu --write-baseline

Exit code 1 iff any non-baselined finding exists. The engine and
rules load standalone (stdlib-only) so the CI gate never imports jax
or the framework package.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# load the engine without importing mxnet_tpu/__init__ (which pulls
# jax: the linter stays stdlib-only and starts fast)
sys.path.insert(0, os.path.join(ROOT, "mxnet_tpu", "analysis"))
import lint  # noqa: E402
import rules  # noqa: E402  (re-exported for introspection/tests)

DEFAULT_BASELINE = os.path.join(ROOT, "ci", "mxlint_baseline.json")
DEFAULT_CACHE = os.path.join(ROOT, ".mxlint_cache.json")
# MX003 needs the full env registry even when linting a subset of the
# tree; the canonical declarations live in mxnet_tpu/utils.
REGISTRY_PATH = os.path.join(ROOT, "mxnet_tpu", "utils", "__init__.py")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="+",
                    help="files or directories to lint")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--select", default="",
                    help="comma-separated rule codes to run "
                         "(default: all)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default ci/mxlint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline file entirely")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write ALL current findings to the baseline "
                         "file and exit 0")
    ap.add_argument("--show-baselined", action="store_true",
                    help="also print baselined findings (text format)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--no-concurrency", action="store_true",
                    help="skip the project-scope passes (MX006-MX008, "
                         "MX010-MX013 — they build a call graph over "
                         "every scanned file; opt out in "
                         "speed-sensitive hooks)")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore and do not write .mxlint_cache.json")
    ap.add_argument("--cache", default=DEFAULT_CACHE,
                    help="result-cache path "
                         "(default <repo>/.mxlint_cache.json)")
    ap.add_argument("--jobs", type=int, default=0, metavar="N",
                    help="analyze cache-miss files in N worker "
                         "processes (default: in-process)")
    args = ap.parse_args(argv)
    cache_path = None if args.no_cache else args.cache

    if args.list_rules:
        for code, (_fn, summary) in sorted(rules.ALL_RULES.items()):
            print(f"{code}  {summary}")
        for code, summary in sorted(rules.PROJECT_RULES.items()):
            print(f"{code}  {summary} [project-scope]")
        return 0

    select = {s.strip() for s in args.select.split(",") if s.strip()} \
        or None
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"mxlint: no such path(s): {missing}", file=sys.stderr)
        return 2

    if args.write_baseline:
        findings = lint.lint_paths(
            args.paths, root=ROOT,
            select=select, extra_registry_paths=(REGISTRY_PATH,),
            concurrency=not args.no_concurrency,
            cache_path=cache_path, jobs=args.jobs)
        lint.write_baseline(findings, args.baseline)
        print(f"mxlint: wrote {len(findings)} finding(s) to "
              f"{args.baseline}")
        return 0

    code, report = lint.run(
        args.paths, root=ROOT,
        baseline_path=None if args.no_baseline else args.baseline,
        fmt=args.format, select=select,
        show_baselined=args.show_baselined,
        extra_registry_paths=(REGISTRY_PATH,),
        concurrency=not args.no_concurrency,
        cache_path=cache_path, jobs=args.jobs)
    print(report)
    return code


if __name__ == "__main__":
    sys.exit(main())
