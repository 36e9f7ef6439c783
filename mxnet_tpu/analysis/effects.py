"""Effects analysis: jit-purity, donation discipline, digest determinism.

Three project-scope rules over the whole parsed file set (like the
concurrency pass, they need more than one file at a time):

  MX010  impure jitted function — a function reachable from a jit
         entry point writes `self.*`/globals/nonlocals, mutates a
         closed-over container, does I/O, reads the environment or
         the wall clock, or bumps a telemetry instrument. A traced
         side effect runs ONCE (at trace time) and then silently
         never again — the classic "my counter stopped at 1" bug.
         Jit entry points are auto-detected (`jax.jit(f)`,
         `jax.pmap(f)`, `jit_sharded(f)` where `f` resolves
         statically) plus the declared JIT_ENTRY_MANIFEST; the
         reachable set is closed over the interprocedural call graph
         (callgraph.py).
  MX011  use-after-donate — a name is read after it flowed into a
         donated argnum position of a known donating call. Donated
         buffers are invalidated at dispatch; touching one afterwards
         is undefined (on TPU: garbage or a crash; on CPU jax it
         often silently *works*, which is why a static rule exists).
         Donating callables are detected in-file (`jax.jit(...,
         donate_argnums=...)` bound to a local or `self.*` name) plus
         the declared DONATING_CALLS manifest. A re-assignment of the
         name kills the taint; the analysis is intraprocedural and
         statement-ordered.
  MX012  unordered iteration on a digest path — inside a function on
         the declared digest-path manifest (canonical signatures,
         page digests, elastic combine, checkpoint/bundle meta
         writers), iterating a `set(...)`/`.items()`/`.values()`/
         `.keys()` without `sorted(...)`, or `json.dump(s)` without
         `sort_keys=True`, makes the output depend on insertion/hash
         order — bit-identity across processes and hosts is the whole
         point of these paths.

Files can extend the digest manifest locally with a module-level
`MXLINT_DIGEST_PATH = "*"` (or a tuple of qualnames) — used by tests
and the CI seeded-violation gate, and the sanctioned way for a new
subsystem to opt its digest writers in without touching this file.

Stdlib-only, like the rest of the analyzer.
"""
from __future__ import annotations

import ast

try:  # normal package import
    from . import callgraph as _cg
    from .rules import RawFinding
except ImportError:  # loaded standalone (tools/mxlint.py)
    import callgraph as _cg
    from rules import RawFinding

#: walk depth for the jit-reachability closure (entry -> callee -> ...)
MAX_REACH_DEPTH = 8

# --------------------------------------------------------------------------
# MX010 manifest: traced functions the auto-detector cannot see (the
# callable is passed across files, built dynamically, or — for the
# elastic update/combine — required pure for bit-identity even though
# it runs eagerly in numpy). Values are qualnames, or "*".
# --------------------------------------------------------------------------
JIT_ENTRY_MANIFEST = {
    # membership-invariant arithmetic: not jax-traced, but the elastic
    # bit-identity contract needs the same purity discipline — a side
    # effect or ambient read here varies across workers
    "mxnet_tpu/elastic/trainer.py": ("ElasticSGD.update",
                                     "combine_grads"),
}

#: sanctioned trace-time effects: functions whose ONLY job is a
#: trace-time side effect (trace counters). Suppressing at the call
#: graph level keeps every call site clean without inline noise.
TRACE_EFFECT_ALLOWED = {
    ("mxnet_tpu/decoding/engine.py", "DecodeEngine._note_trace"),
}

# --------------------------------------------------------------------------
# MX011 manifest: donating callables whose construction the in-file
# detector cannot see (the jit is built in another method/file and
# stored on the instance). Keyed by relpath; each entry maps a
# normalized receiver pattern (subscripts collapse to "[...]") to the
# donated argnum positions of the call.
# --------------------------------------------------------------------------
DONATING_CALLS = {
    "mxnet_tpu/decoding/engine.py": {
        "self._copy_fn": (0,),
        "self._prefill_fns[...]": (3, 4),
        "self._draft_prefill_fns[...]": (3, 4),
        "self._tail_fns[...]": (4, 5),
        "self._draft_tail_fns[...]": (4, 5),
        "self._decode_fns[...]": (2, 3),
        "self._propose_fns[...]": (2, 3),
        "self._verify_fns[...]": (4, 5),
    },
}

# --------------------------------------------------------------------------
# MX012 manifest: the digest paths. Every function here feeds a value
# that must agree bit-for-bit across processes/hosts/restarts.
# --------------------------------------------------------------------------
DIGEST_PATH_MANIFEST = {
    "mxnet_tpu/symbol.py": ("Symbol.structure_key",
                            "Symbol.canonical_signature"),
    "mxnet_tpu/exec_cache.py": ("_CacheKey", "make_key",
                                "CompiledGraph._input_sig"),
    "mxnet_tpu/passes/__init__.py": ("canonical_digest",),
    "mxnet_tpu/passes/transforms.py": ("canonicalize",),
    "mxnet_tpu/sharding/plan.py": ("ShardingPlan.digest",),
    "mxnet_tpu/decoding/prefix.py": (
        "page_digests", "_chain", "_chain_seed",
        "PrefixCache.cache_digest", "PrefixCache.cached_prefixes",
    ),
    "mxnet_tpu/decoding/sampling.py": ("stream_key",),
    "mxnet_tpu/elastic/codec.py": "*",
    "mxnet_tpu/elastic/trainer.py": ("combine_grads",
                                     "JobSpec.initial_params"),
    "mxnet_tpu/elastic/coordinator.py": ("ElasticCoordinator._on_grads",
                                         "ElasticCoordinator._on_slices"),
    "mxnet_tpu/checkpoint_sharded.py": ("save_sharded", "spec_strings",
                                        "_spec_meta"),
    "mxnet_tpu/serving/bundle.py": ("param_content_hash",),
    "mxnet_tpu/utils/persist.py": ("atomic_write_json",),
    "mxnet_tpu/profiling/calibration.py": ("CalibrationStore._key",),
}

#: container/instance mutators (MX010): calling one of these on a
#: non-local receiver inside traced code is a write that happens once
_MUTATOR_METHODS = {
    "append", "extend", "insert", "remove", "clear", "pop", "popleft",
    "appendleft", "add", "discard", "update", "setdefault", "sort",
    "reverse", "write", "writelines", "put", "put_nowait",
    "__setitem__",
}
#: telemetry-instrument mutators flagged textually (receiver must be a
#: plain name / self-attribute chain — jax's `.at[i].set(v)` has a
#: subscript receiver and never matches)
_INSTRUMENT_METHODS = {"inc", "dec", "observe"}

#: ambient reads that become trace-time constants (value baked at
#: trace, never refreshed) — plus plain I/O
_AMBIENT_CALLS = {
    "os.getenv": "environment read",
    "time.time": "wall-clock read",
    "time.time_ns": "wall-clock read",
    "time.perf_counter": "wall-clock read",
    "time.monotonic": "wall-clock read",
    "datetime.datetime.now": "wall-clock read",
}
_IO_NAME_CALLS = {"print", "open", "input"}

_JIT_WRAPPERS = ("jax.jit", "jax.pmap")


def _leaf(dotted):
    return dotted.rsplit(".", 1)[-1] if dotted else None


def _is_jit_wrapper(dotted):
    """jax.jit / jax.pmap / sharding.lower.jit_sharded by any import
    alias (the import map already resolved the module half)."""
    if dotted is None:
        return False
    return dotted in _JIT_WRAPPERS or _leaf(dotted) == "jit_sharded"


def _first_fn_arg(call):
    """The expression holding the traced callable: first positional
    arg, unwrapping one functools.partial layer."""
    if not call.args:
        return None
    arg = call.args[0]
    if (isinstance(arg, ast.Call)
            and _leaf(_cg.dotted_name(arg.func, {})) == "partial"
            and arg.args):
        return arg.args[0]
    return arg


def file_manifest_extra(tree, name="MXLINT_DIGEST_PATH"):
    """Module-level `MXLINT_DIGEST_PATH = "*" | ("qn", ...)` — the
    in-file opt-in used by tests and new subsystems."""
    for node in tree.body if hasattr(tree, "body") else ():
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) and tgt.id == name:
                v = node.value
                if isinstance(v, ast.Constant) and v.value == "*":
                    return "*"
                if isinstance(v, (ast.Tuple, ast.List)):
                    vals = tuple(e.value for e in v.elts
                                 if isinstance(e, ast.Constant)
                                 and isinstance(e.value, str))
                    if vals:
                        return vals
    return None


# ==========================================================================
# MX010 — jit purity
# ==========================================================================
def jit_entries(graph, files):
    """{function key -> entry label} for every statically-resolvable
    traced callable: jax.jit/jax.pmap/jit_sharded first args, plus the
    declared manifest. `files` is [(relpath, tree)]."""
    entries = {}

    def note(key, label):
        entries.setdefault(key, label)

    for relpath, tree in files:
        imports = graph.imports.get(relpath, {})

        # enclosing-scope walk so a Name first-arg can resolve to a
        # nested def (`def impl(...)` inside the builder method)
        def walk(node, prefix, cls):
            for child in ast.iter_child_nodes(node):
                nprefix, ncls = prefix, cls
                if isinstance(child,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                    nprefix = f"{prefix}{child.name}."
                elif isinstance(child, ast.ClassDef):
                    nprefix, ncls = f"{prefix}{child.name}.", child.name
                if isinstance(child, ast.Call) and _is_jit_wrapper(
                        _cg.dotted_name(child.func, imports)):
                    key = _resolve_traced(
                        graph, relpath, prefix, cls,
                        _first_fn_arg(child))
                    if key is not None:
                        note(key, f"{relpath}:{child.lineno}")
                walk(child, nprefix, ncls)

        walk(tree, "", None)

    for relpath, names in JIT_ENTRY_MANIFEST.items():
        for key, info in graph.functions.items():
            if key[0] != relpath:
                continue
            if names == "*" or info.qualname in names:
                note(key, f"{relpath} (manifest)")
    return entries


def _resolve_traced(graph, relpath, prefix, cls, arg):
    """Function key of a jit first-arg expression, or None."""
    if arg is None or isinstance(arg, ast.Lambda):
        return None
    if isinstance(arg, ast.Name):
        # innermost enclosing scope first: `jax.jit(impl)` where impl
        # is a nested def of the current function
        parts = prefix.rstrip(".").split(".") if prefix else []
        for i in range(len(parts), -1, -1):
            qn = ".".join(parts[:i] + [arg.id])
            if (relpath, qn) in graph.functions:
                return (relpath, qn)
        r = graph.resolve_dotted(
            graph.imports.get(relpath, {}).get(arg.id, arg.id), relpath)
        return r[1] if r and r[0] == "func" else None
    if isinstance(arg, ast.Attribute):
        ch = _cg.attr_chain(arg)
        if ch and ch[0] == "self" and cls is not None:
            owner = (graph.chain_type((relpath, cls), ch[1][:-1])
                     if len(ch[1]) > 1 else (relpath, cls))
            if owner:
                fi = graph.method(owner, ch[1][-1])
                if fi is not None:
                    return fi.key
            return None
        dn = _cg.dotted_name(arg, graph.imports.get(relpath, {}))
        r = graph.resolve_dotted(dn, relpath) if dn else None
        return r[1] if r and r[0] == "func" else None
    return None


def reachable_from(graph, entries):
    """{function key -> (entry label, hop count)} closure of the call
    graph from the entry set, nested defs included (a nested def of a
    traced function executes inside the trace when called)."""
    out = {}
    frontier = [(k, lbl, 0) for k, lbl in entries.items()]
    while frontier:
        key, label, depth = frontier.pop()
        if key in out or depth > MAX_REACH_DEPTH:
            continue
        out[key] = (label, depth)
        for callee, _line in graph.callees(key):
            if callee not in out:
                frontier.append((callee, label, depth + 1))
        relpath, qn = key
        prefix = qn + "."
        for (rp, q2) in graph.functions:
            if rp == relpath and q2.startswith(prefix) \
                    and (rp, q2) not in out:
                frontier.append(((rp, q2), label, depth + 1))
    return out


def _local_names(fn_node):
    """Names bound in this function's own scope: params, assignment /
    loop / with / walrus targets, comprehension variables."""
    names = set()
    a = fn_node.args if hasattr(fn_node, "args") else None
    if a is not None:
        for grp in (a.posonlyargs, a.args, a.kwonlyargs):
            names.update(x.arg for x in grp)
        if a.vararg:
            names.add(a.vararg.arg)
        if a.kwarg:
            names.add(a.kwarg.arg)

    def targets(t):
        if isinstance(t, ast.Name):
            names.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                targets(e)
        elif isinstance(t, ast.Starred):
            targets(t.value)

    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                targets(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                               ast.For, ast.AsyncFor)):
            targets(node.target)
        elif isinstance(node, ast.NamedExpr):
            targets(node.target)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    targets(item.optional_vars)
        elif isinstance(node, ast.comprehension):
            targets(node.target)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            names.add(node.name)
    return names


def _own_body(fn_node):
    """Walk the function's own statements, skipping nested defs /
    lambdas / classes (separate scopes, reached on their own)."""
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def check_purity(graph, files):
    """MX010 findings: [(relpath, RawFinding)]."""
    entries = jit_entries(graph, files)
    reach = reachable_from(graph, entries)
    findings = []
    for key, (entry_label, _depth) in sorted(reach.items()):
        if key in TRACE_EFFECT_ALLOWED:
            continue
        info = graph.functions.get(key)
        if info is None:
            continue
        relpath, qn = key
        via = (f"traced function `{qn}` (reachable from jit entry at "
               f"{entry_label})")
        local = _local_names(info.node)
        imports = graph.imports.get(relpath, {})

        def flag(node, what):
            findings.append((relpath, RawFinding(
                "MX010", node.lineno, node.col_offset,
                f"{via}: {what} — a traced side effect runs once at "
                "trace time and never again per step; return the "
                "value out of the jit (or suppress if the effect is "
                "deliberately trace-time-only)")))

        for node in _own_body(info.node):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                kind = ("global" if isinstance(node, ast.Global)
                        else "nonlocal")
                flag(node, f"declares `{kind} "
                           f"{', '.join(node.names)}` for writing")
            elif isinstance(node, (ast.Assign, ast.AugAssign,
                                   ast.AnnAssign)):
                tgts = (node.targets if isinstance(node, ast.Assign)
                        else [node.target])
                for t in _flat_targets(tgts):
                    ch = _cg.attr_chain(t) if isinstance(
                        t, ast.Attribute) else None
                    if ch and ch[0] == "self":
                        flag(t, f"writes `self.{'.'.join(ch[1])}`")
                    elif isinstance(t, ast.Subscript):
                        root = _sub_root(t)
                        if root == "self":
                            flag(t, "writes a subscript of a `self` "
                                    "attribute")
                        elif root is not None and root not in local:
                            flag(t, f"writes `{root}[...]` where "
                                    f"`{root}` is closed-over/global")
            elif isinstance(node, ast.Call):
                f = node.func
                dn = _cg.dotted_name(f, imports)
                if isinstance(f, ast.Name) and f.id in _IO_NAME_CALLS \
                        and f.id not in local:
                    flag(node, f"calls `{f.id}(...)` (I/O)")
                elif dn in _AMBIENT_CALLS:
                    flag(node, f"calls `{dn}` ({_AMBIENT_CALLS[dn]})")
                elif dn is not None and (dn.startswith("os.environ")
                                         or dn.startswith("logging.")):
                    flag(node, f"calls `{dn}`")
                elif isinstance(f, ast.Attribute):
                    ch = _cg.attr_chain(f)
                    root = ch[0] if ch else None
                    # a call on an imported MODULE (`jnp.sort(x)`,
                    # `np.add(a, b)`) is a function call, never a
                    # container mutation
                    is_module = root in imports and root != "self"
                    meth = f.attr
                    if root in ("logger", "log", "logging") and \
                            root not in local:
                        flag(node, f"logs via `{root}.{meth}`")
                    elif meth in _INSTRUMENT_METHODS and ch \
                            and not is_module and (
                                root == "self" or root not in local):
                        flag(node, f"bumps instrument "
                                   f"`{'.'.join([root] + ch[1][:-1])}"
                                   f".{meth}()`")
                    elif meth in _MUTATOR_METHODS and ch \
                            and not is_module:
                        if root == "self":
                            flag(node, f"mutates `self."
                                       f"{'.'.join(ch[1][:-1])}"
                                       f".{meth}(...)`")
                        elif root not in local and len(ch[1]) >= 1:
                            flag(node, f"mutates closed-over/global "
                                       f"`{root}` via `.{meth}(...)`")
    return findings


def _flat_targets(targets):
    out = []
    stack = list(targets)
    while stack:
        t = stack.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
        elif isinstance(t, ast.Starred):
            stack.append(t.value)
        else:
            out.append(t)
    return out


def _sub_root(node):
    """Root name of a Subscript target chain, or None."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


# ==========================================================================
# MX011 — use-after-donate
# ==========================================================================
def _donate_argnums_of(call, imports):
    """Donated positions of a jax.jit/jit_sharded construction, or
    None if this call is not one / donates nothing. A non-literal
    donate_argnums (a variable) yields () — unknowable, stay quiet."""
    dn = _cg.dotted_name(call.func, imports)
    if not _is_jit_wrapper(dn):
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        return _tuple_ints(kw.value)
    return ()


def _tuple_ints(node):
    """Literal tuple/list of ints; IfExp takes the union of both arms;
    anything else -> () (unknown, conservative)."""
    if isinstance(node, ast.IfExp):
        return tuple(sorted(set(_tuple_ints(node.body))
                            | set(_tuple_ints(node.orelse))))
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return ()
        return tuple(out)
    return ()


def _recv_pattern(func):
    """Normalized receiver text of a call: `self._fns[bucket](...)`
    -> "self._fns[...]"; `fn(...)` -> "fn"; None if unsupported."""
    parts = []
    node = func
    while True:
        if isinstance(node, ast.Subscript):
            parts.append("[...]")
            node = node.value
        elif isinstance(node, ast.Attribute):
            parts.append("." + node.attr)
            node = node.value
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            return "".join(reversed(parts))
        else:
            return None


def _taint_expr(node):
    """Taint identity of an argument expression: a bare Name or a
    self-attribute chain; None for anything else (a computed value
    that is donated has no name to misuse afterwards)."""
    if isinstance(node, ast.Name):
        return node.id
    ch = _cg.attr_chain(node)
    if ch and ch[0] == "self":
        return "self." + ".".join(ch[1])
    return None


class _DonateScan(ast.NodeVisitor):
    """Statement-ordered scan of ONE function: donating calls taint
    their donated args; later loads flag; assignments kill."""

    def __init__(self, donating, findings, relpath):
        self.donating = donating      # pattern -> argnums
        self.findings = findings
        self.relpath = relpath
        self.tainted = {}             # taint name -> (line, callee)
        self._skip = set()            # ids of nodes not to treat as reads

    def _kill(self, target):
        for t in _flat_targets([target]):
            name = _taint_expr(t)
            if name is not None:
                self.tainted.pop(name, None)
            elif isinstance(t, ast.Subscript):
                # writing x[i] neither reads the stale buffer nor
                # revives it; treat as a kill of nothing
                pass

    def _check_reads(self, nodes):
        if not self.tainted:
            return
        for sub in nodes:
            if id(sub) in self._skip:
                continue
            name = None
            if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, ast.Load):
                name = sub.id
            elif isinstance(sub, ast.Attribute) and isinstance(
                    sub.ctx, ast.Load):
                name = _taint_expr(sub)
            if name in self.tainted:
                line, callee = self.tainted[name]
                self.findings.append((self.relpath, RawFinding(
                    "MX011", sub.lineno, sub.col_offset,
                    f"`{name}` is read after being donated to "
                    f"`{callee}` (line {line}): donated buffers are "
                    "invalidated at dispatch — rebind the name from "
                    "the call's outputs before any further use")))
                # one report per taint: further reads of the same name
                # are the same bug
                self.tainted.pop(name, None)

    def _process_call(self, call):
        pat = _recv_pattern(call.func)
        argnums = self.donating.get(pat) if pat else None
        if not argnums:
            return
        for pos in argnums:
            if pos < len(call.args):
                name = _taint_expr(call.args[pos])
                if name is not None:
                    self.tainted[name] = (call.lineno, pat)

    def scan(self, stmts):
        for stmt in stmts:
            # nested defs/classes: separate scope, scanned on their own
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            # only the statement's OWN expressions at this level — a
            # compound statement's nested blocks are scanned (in
            # source order) by the recursion below, so taints/kills
            # inside them stay properly ordered
            header = _header_nodes(stmt)
            # 1) reads in this statement flag against PRIOR taints;
            #    a donating call's own argument expressions are reads
            #    of the still-valid buffer, so exempt exactly those
            calls = [n for n in header if isinstance(n, ast.Call)]
            for call in calls:
                pat = _recv_pattern(call.func)
                if pat and self.donating.get(pat):
                    for n in call.args:
                        for s in ast.walk(n):
                            self._skip.add(id(s))
            self._check_reads(header)
            # 2) taints from donating calls in this statement
            for call in calls:
                self._process_call(call)
            # 3) kills from assignments in this statement
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    self._kill(t)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                self._kill(stmt.target)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._kill(stmt.target)
            # recurse into compound statements in source order
            for body in _sub_blocks(stmt):
                self.scan(body)


_BLOCK_FIELDS = ("body", "orelse", "finalbody", "handlers")


def _header_nodes(stmt):
    """Every AST node in the statement's non-block fields: the whole
    statement for simple statements; test/iter/items/targets only for
    compound ones (their blocks are separate scan steps)."""
    out = []
    for fname, value in ast.iter_fields(stmt):
        if fname in _BLOCK_FIELDS:
            continue
        vals = value if isinstance(value, list) else [value]
        for v in vals:
            if isinstance(v, ast.AST):
                out.extend(ast.walk(v))
    return out


def _sub_blocks(stmt):
    for attr in ("body", "orelse", "finalbody"):
        blk = getattr(stmt, attr, None)
        if blk:
            yield blk
    for h in getattr(stmt, "handlers", ()) or ():
        yield h.body


def check_donation(files):
    """MX011 findings: [(relpath, RawFinding)]. Intraprocedural; the
    donating-callable map is (file-detected jits) + DONATING_CALLS."""
    findings = []
    for relpath, tree in files:
        imports = _file_imports(relpath, tree)
        manifest = dict(DONATING_CALLS.get(relpath, {}))
        # file-wide detection: `<name-or-self.attr> = jax.jit(...,
        # donate_argnums=(...))` anywhere (class attrs persist across
        # methods; locals are per-function but a global map is a safe
        # over-approximation only if names don't collide — donation
        # patterns are distinctive, so accept it)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            argnums = _donate_argnums_of(node.value, imports)
            if not argnums:
                continue
            for t in node.targets:
                pat = (_taint_expr(t) if not isinstance(t, ast.Subscript)
                       else _recv_pattern_target(t))
                if pat:
                    manifest[pat] = argnums
        if not manifest:
            continue
        for fn_node, _qn in _all_defs(tree):
            scan = _DonateScan(manifest, findings, relpath)
            scan.scan(fn_node.body)
    return findings


def _recv_pattern_target(t):
    """Assignment target `self._fns[bucket]` -> "self._fns[...]"."""
    if isinstance(t, ast.Subscript):
        inner = _taint_expr(t.value)
        return f"{inner}[...]" if inner else None
    return None


def _file_imports(relpath, tree):
    return _cg.imports_for(relpath, tree)


def _all_defs(tree):
    out = []

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append((child, f"{prefix}{child.name}"))
                walk(child, f"{prefix}{child.name}.")
            elif isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.")
            else:
                walk(child, prefix)

    walk(tree, "")
    return out


# ==========================================================================
# MX012 — digest-path determinism
# ==========================================================================
_UNORDERED_METHODS = {"items", "values", "keys"}


def _digest_functions(relpath, tree):
    manifest = DIGEST_PATH_MANIFEST.get(relpath)
    extra = file_manifest_extra(tree)
    if manifest is None and extra is None:
        return []
    covered = []
    for fn_node, qn in _all_defs(tree):
        for m in (manifest, extra):
            if m is None:
                continue
            if m == "*" or qn in m or any(
                    qn.startswith(x + ".") for x in m):
                covered.append((fn_node, qn))
                break
    return covered


def check_digest_paths(files):
    """MX012 findings: [(relpath, RawFinding)]."""
    findings = []
    for relpath, tree in files:
        covered = _digest_functions(relpath, tree)
        if not covered:
            continue
        imports = _file_imports(relpath, tree)
        seen = set()
        for fn_node, qn in covered:
            # every node lexically under a sorted(...) call: an
            # iteration found there is ordered by construction
            # (`sorted(x for x in d.items())` visits the genexp node
            # on its own, so the wrapper must be tracked here)
            sorted_ids = set()
            for node in ast.walk(fn_node):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "sorted"):
                    for sub in ast.walk(node):
                        if sub is not node:
                            sorted_ids.add(id(sub))
            for node in _own_body(fn_node):
                if id(node) in seen:
                    continue
                seen.add(id(node))
                findings.extend(
                    (relpath, f) for f in _digest_node(
                        node, qn, imports, sorted_ids))
    return findings


def _digest_node(node, qn, imports, sorted_ids=frozenset()):
    out = []
    iters = []
    if isinstance(node, (ast.For, ast.AsyncFor)):
        iters.append(node.iter)
    elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                           ast.GeneratorExp)):
        iters.extend(g.iter for g in node.generators)
    for it in iters:
        for bad, what in _unordered_in(it):
            if id(bad) in sorted_ids:
                continue
            out.append(RawFinding(
                "MX012", bad.lineno, bad.col_offset,
                f"digest-path function `{qn}` iterates {what} without "
                "`sorted(...)`: insertion/hash order leaks into a "
                "value that must be bit-identical across processes — "
                "wrap the iterable in sorted()"))
    if isinstance(node, ast.Call):
        dn = _cg.dotted_name(node.func, imports)
        if dn in ("json.dumps", "json.dump"):
            # a MISSING sort_keys (or a literal False) is the bug; an
            # explicit passthrough (`sort_keys=sort_keys`) means the
            # author decided — leave it alone
            kw = next((k for k in node.keywords
                       if k.arg == "sort_keys"), None)
            bad = kw is None or (isinstance(kw.value, ast.Constant)
                                 and kw.value.value is not True)
            if bad:
                out.append(RawFinding(
                    "MX012", node.lineno, node.col_offset,
                    f"digest-path function `{qn}` serializes with "
                    f"`{dn}` without sort_keys=True: dict insertion "
                    "order leaks into the serialized bytes — pass "
                    "sort_keys=True"))
    return out


def _unordered_in(expr, in_sorted=False):
    """(node, description) for unordered iterables inside one iterable
    expression; anything lexically under a sorted(...) call is fine."""
    out = []
    if isinstance(expr, ast.Call):
        f = expr.func
        if isinstance(f, ast.Name) and f.id in ("sorted", "min", "max",
                                                "sum", "frozenset"):
            in_sorted = in_sorted or f.id == "sorted"
            for a in expr.args:
                out.extend(_unordered_in(a, in_sorted))
            return out
        if (isinstance(f, ast.Attribute)
                and f.attr in _UNORDERED_METHODS and not expr.args
                and not in_sorted):
            out.append((expr, f"`.{f.attr}()` of a dict"))
            return out
        if isinstance(f, ast.Name) and f.id == "set" and not in_sorted:
            out.append((expr, "a `set(...)`"))
            return out
    elif isinstance(expr, ast.Set) and not in_sorted:
        out.append((expr, "a set literal"))
        return out
    for child in ast.iter_child_nodes(expr):
        out.extend(_unordered_in(child, in_sorted))
    return out


# ==========================================================================
# entry point for the engine
# ==========================================================================
def check_project(files, graph=None):
    """All MX010/MX011/MX012 findings over the parsed file set:
    [(relpath, RawFinding)], engine-ready (lint._project_findings
    routes them through suppressions + baseline). Pass a prebuilt
    CallGraph to share the index with the concurrency pass."""
    if graph is None:
        graph = _cg.CallGraph(files)
    out = []
    out.extend(check_purity(graph, files))
    out.extend(check_donation(files))
    out.extend(check_digest_paths(files))
    return out
