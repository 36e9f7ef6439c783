"""What the readers of the program's own names share: device
operations placed in named scopes through the program's scope maps,
the decode steps of a traced window, and host spans taken from outside
the traced sub-window (where the profiler does not slow the host).

Everything here reads what PR 25 added to the program (`jax.named_scope`
in the decode tier, module names, `mxnet_tpu.profiling.scope_map`, the
leaf spans of a scheduler turn and of `fit.dispatch`). On a program
that lacks them every function returns None or an empty list and raises
nothing, so the readers built on it leave their metric out of the line.
"""
import bisect

UNSCOPED = "unscoped"
PARTS = ("embed", "qkv", "kv_write", "attn", "out", "mlp", "logits",
         "sample")
# seconds before the trace's first device event that the host already
# ran under the profiler (start_trace itself takes a while)
CAPTURE_MARGIN_S = 1.0


def _scope_map_fn():
    try:
        from mxnet_tpu import profiling
    except ImportError:
        return None
    return getattr(profiling, "scope_map", None)


def module_of_launch(name):
    """'jit_decode_p48(1523...)' -> 'jit_decode_p48'."""
    cut = name.find("(")
    return name[:cut] if cut > 0 else name


def scoped_ops(facts, dev=0):
    """[(scope path, t0, t1, module)] of every device operation of chip
    `dev`, on the trace's clock: an operation belongs to the module
    launch that covers its start, and takes its scope from that
    module's map (instruction names repeat across programs; module
    names do not): `unscoped` where the map does not place it or no
    launch covers it, None where the module has no map. The whole is
    None where the program keeps no scope maps. `facts["scope_maps"]`
    (module name -> map) stands in for the program's table in the
    self-check. Cached in `facts`."""
    key = ("_scoped_ops", dev)
    if key in facts:
        return facts[key]
    lookup = facts.get("scope_maps") or _scope_map_fn()
    red = facts["trace"]
    out = None
    if lookup is not None and red.devices:
        d = red.devices[dev]
        launches = sorted((t0, t1, module_of_launch(n))
                          for n, _l, t0, t1 in d["modules"])
        starts = [la[0] for la in launches]
        maps, out = {}, []
        for instr, _label, t0, t1 in d["ops"]:
            i = bisect.bisect_right(starts, t0) - 1
            module, scope = None, UNSCOPED
            if i >= 0 and t0 < launches[i][1]:
                module = launches[i][2]
                if module not in maps:
                    maps[module] = lookup(module)
                scope = maps[module].get(instr, UNSCOPED) \
                    if maps[module] is not None else None
            out.append((scope, t0, t1, module))
    facts[key] = out
    return out


def decode_steps(facts):
    """[(t0, t1, attrs)] of the `decoding.step` spans that lie whole
    inside the traced window and name their program, on the trace's
    clock. Empty on a program whose step spans carry no `program`."""
    _busy, spans = facts["trace"].busy_inside("decoding.step")
    return [(a, b, attrs) for a, b, attrs in spans
            if attrs and attrs.get("program")]


def part_of(scope):
    """The part of a decode step a scope path lies in ('l3/attn/...' ->
    'attn', 'draft0/logits' -> 'logits'): its first segment that is one
    of PARTS; `unscoped`; or None for a scope outside the parts."""
    if scope == UNSCOPED:
        return UNSCOPED
    for seg in scope.split("/"):
        if seg in PARTS:
            return seg
    return None


def step_part_seconds(facts):
    """Per decode step of the traced window, the device seconds of each
    part: [({part: seconds}, attrs)]. Only operations of the step's own
    program count (`attrs['program']`); a part the step's operations
    never name is absent; a step whose program has no scope map is left
    out. None where scopes or steps are missing."""
    if "_step_parts" in facts:
        return facts["_step_parts"]
    ops, steps = scoped_ops(facts), decode_steps(facts)
    out = None
    if ops and steps:
        ops = sorted(ops, key=lambda o: o[1])
        starts = [o[1] for o in ops]
        out = []
        for a, b, attrs in steps:
            parts = {}
            for scope, t0, t1, module in ops[bisect.bisect_left(starts, a):
                                             bisect.bisect_left(starts, b)]:
                if module != attrs["program"]:
                    continue
                if scope is None:
                    parts = None
                    break
                part = part_of(scope) or "other"
                parts[part] = parts.get(part, 0.0) + (min(t1, b) - t0)
            if parts:
                out.append((parts, attrs))
        out = out or None
    facts["_step_parts"] = out
    return out


def part_ms_per_step(facts, part):
    """Mean device milliseconds a decode step spends in one part; None
    where no step's operations name it."""
    steps = step_part_seconds(facts)
    if not steps or not any(part in p for p, _ in steps):
        return None
    return sum(p.get(part, 0.0) for p, _ in steps) / len(steps) * 1e3


def capture_start_host(facts):
    """The host-clock instant from which the host ran under the
    profiler: the trace's first device event, less a margin for
    start_trace itself."""
    red = facts["trace"]
    first = min((iv[0] for d in range(len(red.devices))
                 for iv in red.intervals(d)), default=red.lo)
    return min(first, red.lo) - red.offset - CAPTURE_MARGIN_S


def spans_in_and_out(facts, name):
    """Host spans of one name as (outside, inside), each [(t0, t1,
    attrs)] on the host's clock: `inside` lie whole in the traced
    window, `outside` ended before the capture began (the ring holds
    the timed window from its opening; its first span is left out, a
    turn the window's opening cut). Outside the host runs without the
    profiler's hooks, so the two means differ by what tracing costs."""
    lo, hi = facts["window_host"]
    before = capture_start_host(facts)
    inside, outside = [], []
    for n, t0, t1, attrs in facts["spans"]:
        if n != name:
            continue
        if t0 >= lo and t1 <= hi:
            inside.append((t0, t1, attrs))
        elif t1 <= before:
            outside.append((t0, t1, attrs))
    return outside[1:], inside


def mean_ms(spans):
    return sum(t1 - t0 for t0, t1, _ in spans) / len(spans) * 1e3 \
        if spans else None


def children_seconds(facts, parents, child_name):
    """For each parent span (t0, t1, attrs) the seconds of the spans
    named `child_name` that lie inside it, in order."""
    kids = sorted((t0, t1) for n, t0, t1, _ in facts["spans"]
                  if n == child_name)
    starts = [k[0] for k in kids]
    out = []
    for a, b, _ in parents:
        i = bisect.bisect_left(starts, a)
        total = 0.0
        while i < len(kids) and kids[i][0] < b:
            if kids[i][1] <= b:
                total += kids[i][1] - kids[i][0]
            i += 1
        out.append(total)
    return out


def note_in_out(facts, metric, outside_ms, inside_ms):
    """Leave both readings in the run's log: their difference is the
    profiler's own cost on that piece of host work."""
    fmt = lambda v: "none" if v is None else f"{v:.4f}"  # noqa: E731
    facts.setdefault("notes", {})[metric] = (
        f"outside the traced sub-window {fmt(outside_ms)} ms, inside it "
        f"{fmt(inside_ms)} ms")


# ------------------------------------------------ the attention's floor
def kv_bytes_per_context_token(config):
    """Bytes of K and V one context position holds over all layers, at
    the pool's stored width: bf16/f32 values, or int8 values with one
    float32 scale per (position, head) beside them."""
    hidden = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    layers = int(config["num_hidden_layers"])
    kv = str(config["kv_dtype"])
    if kv == "int8":
        per_plane = hidden * 1 + heads * 4
    else:
        per_plane = hidden * {"bf16": 2, "bfloat16": 2, "float16": 2,
                              "float32": 4}[kv]
    return 2 * layers * per_plane


def attn_flops_per_context_token(config):
    """One query row against one context position, all layers: the
    score's multiply-add and the value's (2 FLOPs each) per hidden
    element."""
    return 4 * int(config["hidden_size"]) * int(config["num_hidden_layers"])
