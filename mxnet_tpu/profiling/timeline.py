"""Op-level device timelines: a profiler capture's device operations →
named scopes → per-scope totals.

A `jax.profiler` capture on this JAX is one `.xplane.pb`: per device
an `XLA Modules` line (one event per program launch, named after the
compiled module) and an `XLA Ops` line (one event per executed
instruction, named by the instruction's text). Events carry no
metadata of their own, so attribution goes through what the program
kept at compile time: `device_stats.record_executable` parses every
compiled program's text into a SCOPE MAP, {instruction name: scope
path}, keyed by the module's name (`device_stats.scope_map`). The scope path is the
`jax.named_scope` nesting the instruction was traced under — the
executor wraps every graph op in `jax.named_scope(node_name)`, the
decode tier names layers and their parts (`l3/attn`) — with jit and
autodiff wrappers unwrapped (`transpose(jvp(conv0))` → `conv0`).

Instruction names (`fusion.67`) repeat across programs; module names do
not. So an operation event belongs to the module launch that covers it
in time, and takes its scope from that module's map.

Two seams, both over plain lists so that tests need no device capture
(a CPU capture has no device plane):

  read_xplane(trace_dir)       newest `.xplane.pb` → {"devices":
                               [{"name", "ops": [(instr, label, t0,
                               t1)], "modules": [(name, name, t0,
                               t1)]}], "host": [(name, t0, t1)]},
                               seconds on the capture's clock
  device_slices(raw, ...)      that form → Chrome slices with
                               `args.scope` / `args.module`; what
                               `dump_profile` merges and
                               `ingest_device_events` folds into the
                               `deviceTimelineStats` view
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import threading

from ..telemetry import register_view as _register_view

_lock = threading.Lock()
# scope label -> {"count", "total_us", "max_us"}
_ops: "dict[str, dict]" = {}
_totals = {"events": 0, "captures": 0, "device_pids": set()}

_DEFAULT_TOPK = 20

UNSCOPED = "unscoped"

# name-stack wrappers jax puts around a scope's name; a segment whose
# wrappers include a jit is a FUNCTION's name, not a scope
_WRAPPERS = ("jit", "pjit", "jvp", "vjp", "transpose", "checkpoint",
             "remat", "custom_jvp", "custom_vjp", "vmap", "while", "cond",
             "named", "shard_map", "xla_call", "core_call")
_JITS = ("jit", "pjit", "xla_call", "core_call")
_WRAPPED = re.compile(r"^([A-Za-z_]+)\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# control flow under an instruction: the computations a device steps
# into (`body=%b`, `condition=%c`, `branch_computations={%x, %y}`,
# `true_computation=`/`false_computation=`), and a call's `to_apply=`
_CONTROL = re.compile(
    r"(?:body|condition|true_computation|false_computation|"
    r"branch_computations)=(\{[^}]*\}|%[\w.\-]+)")
_TO_APPLY = re.compile(r"to_apply=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*[({]")
_ANNOTATION = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _topk():
    try:
        return max(1, int(os.environ.get("MXNET_PROFILING_TOPK",
                                         _DEFAULT_TOPK)))
    except ValueError:
        return _DEFAULT_TOPK


# ------------------------------------------------------- the scope map
def scope_path(op_name):
    """'jit(step)/jit(main)/transpose(jvp(conv0))/conv_general_dilated'
    -> 'conv0'; 'jit(decode_p8)/l3/attn/dot_general' -> 'l3/attn';
    None where no scope encloses the operation. The last segment is
    the primitive and never a scope; of the several paths the compiler
    joins with ';' when it merges instructions, the first counts."""
    segs = []
    for seg in op_name.split(";", 1)[0].split("/")[:-1]:
        seg = seg.strip()
        is_fn = False
        while True:
            m = _WRAPPED.match(seg)
            if not m or m.group(1) not in _WRAPPERS:
                break
            is_fn = is_fn or m.group(1) in _JITS
            seg = m.group(2)
        if seg and not is_fn:
            segs.append(seg)
    return "/".join(segs) or None


def module_name(hlo_text):
    """The compiled module's name (`jit_decode_p8`), as a capture's
    `XLA Modules` line has it; None for a text without a header."""
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else None


def _computations(hlo_text):
    """(entry name, {computation name: its instruction lines})."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if " = " in line:
            if cur is not None:
                cur.append(line)
            continue
        head = _COMPUTATION.match(line)
        if head and line.rstrip().endswith("{"):
            cur = comps[head.group(1)] = []
            if line.startswith("ENTRY"):
                entry = head.group(1)
    return entry, comps


def parse_scope_map(hlo_text):
    """{instruction name: scope path} for every instruction a device
    can report as an operation of its own: those of the entry
    computation and of the control flow under it (while bodies and
    conditions, branches, calls). Fused bodies and the reducers and
    comparators handed to `to_apply` are left out: a fusion, a reduce
    or a sort runs as ONE operation.

    An instruction's own `op_name` metadata decides where it has one.
    The compiler's own instructions have none (layout copies, the
    `fusion.N.remat_uncompressed` it clones): such an instruction takes
    the scope of the first instruction that uses it (through further
    unnamed users, if need be), else of the one that produces its
    first operand (likewise), else stays `unscoped` — a copy made FOR
    a scope's kernel is that scope's cost, and a copy of a scope's
    result on its way out of the program (its only user the unnamed
    root tuple) is the producer's."""
    entry, comps = _computations(hlo_text)
    todo, reached = [entry], []
    while todo:
        name = todo.pop()
        if name in reached or name not in comps:
            continue
        reached.append(name)
        for line in comps[name]:
            if "_computation" in line or "body=" in line:
                for group in _CONTROL.findall(line):
                    todo.extend(_OPERAND.findall(group))
            if " call(" in line:
                todo.extend(_TO_APPLY.findall(line))
    order, scope, operands = [], {}, {}
    for comp in reached:
        for line in comps[comp]:
            m = _INSTR.match(line)
            if not m:
                continue
            name = m.group(1)
            body = line[m.end():]
            meta = body.find("metadata={")
            path = None
            if meta >= 0:
                p = _OP_NAME.search(body, meta)
                if p:
                    path = scope_path(p.group(1))
                body = body[:meta]
            order.append(name)
            scope[name] = path
            operands[name] = _OPERAND.findall(body)
    first_user = {}
    for name in order:
        for op in operands[name]:
            first_user.setdefault(op, name)

    def follow(name, step):
        """The first scope along a chain of unnamed instructions."""
        seen = set()
        while name in scope and name not in seen:
            if scope[name] is not None:
                return scope[name]
            seen.add(name)
            name = step(name)
        return None

    def producer(name):
        return operands[name][0] if operands[name] else None

    return {name: scope[name] or follow(name, first_user.get)
            or follow(name, producer) or UNSCOPED for name in order}


# -------------------------------------------------- reading a capture
def read_xplane(trace_dir):
    """The newest `.xplane.pb` under `trace_dir` as plain lists (see
    the module docstring); None where there is no capture. Host events
    are the process's own annotations only (telemetry spans and the
    like: names with a dot), not the runtime's thousands."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        return None
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith(("/device:TPU:", "/device:GPU:")):
            dev = {"name": pname, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        # an event's name is the instruction's text
                        m = _EVENT_INSTR.match(ev.name)
                        instr = m.group(1) if m else ev.name
                        t0 = ev.start_ns * 1e-9
                        dev["ops"].append(
                            (instr, instr, t0,
                             t0 + ev.duration_ns * 1e-9))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        t0 = ev.start_ns * 1e-9
                        dev["modules"].append(
                            (ev.name, ev.name, t0,
                             t0 + ev.duration_ns * 1e-9))
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif pname.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if _ANNOTATION.match(ev.name):
                        t0 = ev.start_ns * 1e-9
                        host.append((ev.name, t0,
                                     t0 + ev.duration_ns * 1e-9))
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host, "path": paths[-1]}


def module_of_launch(name):
    """'jit_decode_p8(1234567)' -> 'jit_decode_p8': a launch event's
    name is the module's, with the program's id in brackets."""
    cut = name.find("(")
    return name[:cut] if cut > 0 else name


def device_slices(raw, scope_maps=None, base_us=0.0):
    """Chrome trace slices (ph 'X', microseconds) of every device
    operation of a capture in the plain-list form, one process lane
    per device (pid 1001, 1002, ...; the host timeline is pid 0).
    Each carries `args.module` — the launch that covers it in time —
    and `args.scope`, from that module's scope map (`scope_maps`:
    module name -> map or None, by default the record table's
    `device_stats.scope_map`); an operation no map places is
    `unscoped`, outside every launch it keeps its instruction's name
    alone. `base_us` shifts the capture's clock onto the caller's."""
    if scope_maps is None:
        from .device_stats import scope_map as scope_maps
    out = []
    for idx, dev in enumerate((raw or {}).get("devices", ())):
        pid = 1001 + idx
        launches = sorted((t0, t1, module_of_launch(n))
                          for n, _l, t0, t1 in dev["modules"])
        starts = [la[0] for la in launches]
        maps = {}
        for instr, _label, t0, t1 in dev["ops"]:
            args = {}
            i = bisect.bisect_right(starts, t0) - 1
            if i >= 0 and t0 < launches[i][1]:
                module = launches[i][2]
                if module not in maps:
                    maps[module] = scope_maps(module) or {}
                args["module"] = module
                args["scope"] = maps[module].get(instr, UNSCOPED)
            out.append({"name": instr, "ph": "X", "pid": pid, "tid": 0,
                        "ts": t0 * 1e6 + base_us,
                        "dur": (t1 - t0) * 1e6, "args": args})
    return out


# ------------------------------------------------------ the aggregator
def aggregate_device_events(events):
    """Fold device slices (`device_slices`) into {label: {count,
    total_us, max_us}}; the label is the slice's scope, or its own
    name where it has none. Only complete slices (ph=='X' with a dur)
    carry device time."""
    out = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        dur = ev.get("dur")
        if not isinstance(dur, (int, float)) or dur < 0:
            continue
        label = (ev.get("args") or {}).get("scope") or ev.get("name")
        if not label:
            continue
        rec = out.get(label)
        if rec is None:
            rec = out[label] = {"count": 0, "total_us": 0.0,
                                "max_us": 0.0}
        rec["count"] += 1
        rec["total_us"] += float(dur)
        if dur > rec["max_us"]:
            rec["max_us"] = float(dur)
    return out


def ingest_device_events(events):
    """Merge one capture's slices into the process-wide table (the
    profiler calls this from dump_profile, so the view snapshot in the
    same dump already includes the capture being written)."""
    agg = aggregate_device_events(events)
    pids = {ev.get("pid") for ev in events
            if isinstance(ev.get("pid"), int)}
    with _lock:
        for label, rec in agg.items():
            cur = _ops.get(label)
            if cur is None:
                _ops[label] = dict(rec)
            else:
                cur["count"] += rec["count"]
                cur["total_us"] += rec["total_us"]
                if rec["max_us"] > cur["max_us"]:
                    cur["max_us"] = rec["max_us"]
        _totals["events"] += sum(r["count"] for r in agg.values())
        _totals["captures"] += 1 if events else 0
        _totals["device_pids"] |= pids
    return agg


def timeline_stats():
    """`deviceTimelineStats` view: top-K scopes by total device time.
    {"ops": {label: {count, total_us, max_us, mean_us}}, "totals":
    {...}}; empty until a capture was ingested."""
    with _lock:
        if not _ops:
            return {}
        items = sorted(_ops.items(), key=lambda kv: -kv[1]["total_us"])
        k = _topk()
        ops = {}
        for label, rec in items[:k]:
            ops[label] = {
                "count": rec["count"],
                "total_us": round(rec["total_us"], 3),
                "max_us": round(rec["max_us"], 3),
                "mean_us": round(rec["total_us"] / rec["count"], 3),
            }
        return {
            "ops": ops,
            "totals": {
                "distinct_ops": len(_ops),
                "shown": len(ops),
                "events": _totals["events"],
                "captures": _totals["captures"],
                "devices": len(_totals["device_pids"]),
                "device_time_us": round(
                    sum(r["total_us"] for r in _ops.values()), 3),
            },
        }


def reset_timeline():
    with _lock:
        _ops.clear()
        _totals["events"] = 0
        _totals["captures"] = 0
        _totals["device_pids"] = set()


_register_view("deviceTimelineStats", timeline_stats,
               prom_prefix="device_timeline", omit_empty=True)
