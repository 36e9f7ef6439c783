"""From the profiler's trace to numbers: busy union, idle gaps named by
the host span that covers them, per-node kernel time, exposed
collective time. Pure functions over plain lists so that the self-check
can feed them a small recorded trace; `load_xplane` is the only part
that touches jax."""
import bisect
import glob
import os
import re

SYNC_A = "perfbench.window_open"
SYNC_B = "perfbench.window_close"
_WRAPPERS = ("jit", "pjit", "jvp", "vjp", "transpose", "checkpoint",
             "remat", "custom_jvp", "custom_vjp", "vmap", "while", "cond",
             "named", "shard_map", "xla_call", "core_call")
_JITS = ("jit", "pjit", "xla_call", "core_call")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")


def _unwrap(seg):
    """'transpose(jvp(stage1_unit1_conv1))' -> ('stage1_unit1_conv1',
    was_jit). The executor's named_scope survives autodiff wrapped so."""
    was_jit = False
    first = True
    while True:
        m = re.match(r"^([A-Za-z_]+)\((.*)\)$", seg)
        if not m or m.group(1) not in _WRAPPERS:
            return seg, was_jit
        if first and m.group(1) in _JITS:
            was_jit = True
        first = False
        seg = m.group(2)


_OP_NAME = re.compile(r'op_name="([^"]+)"')
_INSTR = re.compile(r"^%?([\w.\-]+)")


def label_from_path(path):
    """Graph-node label from an op_name path: its first segment that is
    neither a jit wrapper nor empty, autodiff wrappers unwrapped so that
    a backward kernel lands on its node (copied from
    mxnet_tpu/profiling/timeline.py:attribute_event)."""
    for seg in path.split("/"):
        seg = seg.strip()
        if not seg:
            continue
        inner, was_jit = _unwrap(seg)
        if was_jit or not inner or inner == "main":
            continue
        return inner
    return None


def labels_from_hlo(text):
    """{instruction name: node label} from a compiled program's text:
    every instruction's metadata carries the op_name path that the
    executor's jax.named_scope(node) wrote."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if " = " not in line or "op_name=" not in line:
            continue
        m, p = _INSTR.match(line.replace("ROOT ", "", 1)), \
            _OP_NAME.search(line)
        if m and p:
            label = label_from_path(p.group(1))
            if label:
                out.setdefault(m.group(1), label)
    return out


def node_label(name, hlo_labels=None):
    """Label of one device op event. The event's name is the HLO
    instruction (often its whole text): take the op_name path from the
    text where it is there, else from the compiled program's own text
    (`hlo_labels`), else the instruction's name."""
    p = _OP_NAME.search(name)
    if p:
        label = label_from_path(p.group(1))
        if label:
            return label
    m = _INSTR.match(name)
    instr = m.group(1) if m else name
    if hlo_labels and instr in hlo_labels:
        return hlo_labels[instr]
    return instr


def load_xplane(trace_dir, hlo_labels=None):
    """Read the newest .xplane.pb under `trace_dir` into plain lists:
    {"devices": [{"ops": [(name, label, t0, t1)], "modules": [...]}],
     "host": [(name, t0, t1)]} with times in seconds on the trace's
    clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        return None
    pd = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:TPU:") or pname.startswith(
                "/device:GPU:"):
            dev = {"name": pname, "ops": [], "modules": []}
            for line in plane.lines:
                lname = line.name
                if lname == "XLA Ops":
                    memo = {}
                    for ev in line.events:
                        name = ev.name
                        if name not in memo:
                            m = _INSTR.match(name)
                            memo[name] = (m.group(1) if m else name,
                                          node_label(name, hlo_labels))
                        t0 = ev.start_ns * 1e-9
                        dev["ops"].append(
                            memo[name] + (t0, t0 + ev.duration_ns * 1e-9))
                elif lname == "XLA Modules":
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
                    if ev.name.startswith("perfbench."):
                        t0 = ev.start_ns * 1e-9
                        host.append((ev.name, t0,
                                     t0 + ev.duration_ns * 1e-9))
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host, "path": paths[-1]}


def clip(intervals, lo, hi):
    out = []
    for t0, t1 in intervals:
        a, b = max(t0, lo), min(t1, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals):
    """Union of intervals as a sorted list of disjoint ones."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def busy_seconds(intervals, lo, hi):
    return sum(b - a for a, b in merge(clip(intervals, lo, hi)))


def idle_gaps(intervals, lo, hi):
    """The gaps of the union inside [lo, hi], as (t0, t1)."""
    gaps, cur = [], lo
    for a, b in merge(clip(intervals, lo, hi)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def name_gap(gap, spans):
    """The host span (name, t0, t1) that covers most of the gap; 'none'
    where no span touches it."""
    best, best_ov = "none", 0.0
    for name, t0, t1 in spans:
        ov = min(gap[1], t1) - max(gap[0], t0)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def subtract(intervals, others):
    """Parts of `intervals` (merged) not covered by `others`."""
    out = []
    others = merge(others)
    for a, b in merge(intervals):
        cur = a
        for oa, ob in others:
            if ob <= cur or oa >= b:
                continue
            if oa > cur:
                out.append((cur, oa))
            cur = max(cur, ob)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def is_collective(op_name):
    return op_name.startswith(_COLLECTIVES)


def label_seconds(ops, lo, hi):
    """{label: seconds} of device ops inside [lo, hi] (clipped)."""
    out = {}
    for _name, label, t0, t1 in ops:
        a, b = max(t0, lo), min(t1, hi)
        if b > a:
            out[label] = out.get(label, 0.0) + (b - a)
    return out


class Reduced:
    """One traced window reduced: what the per-layer readers read."""

    def __init__(self, raw, host_spans, t_open_host, t_close_host):
        """`raw` from load_xplane; `host_spans` [(name, t0, t1, attrs)]
        on the host's perf_counter clock; the window's two instants on
        that clock, each stamped inside a TraceAnnotation so that the
        trace holds the same instants on its own clock."""
        self.ok = False
        self._merged = {}
        self.devices = raw["devices"] if raw else []
        marks = {n: t0 for n, t0, _t1 in (raw["host"] if raw else [])}
        if not self.devices or SYNC_A not in marks or SYNC_B not in marks:
            return
        self.offset = marks[SYNC_A] - t_open_host   # host -> trace clock
        self.drift = (marks[SYNC_B] - t_close_host) - self.offset
        self.lo, self.hi = marks[SYNC_A], marks[SYNC_B]
        self.window_s = self.hi - self.lo
        self.spans = [(n, t0 + self.offset, t1 + self.offset, attrs)
                      for n, t0, t1, attrs in host_spans]
        self.busy = []
        for dev in self.devices:
            iv = [(t0, t1) for _n, _l, t0, t1 in
                  (dev["ops"] or dev["modules"])]
            self.busy.append(busy_seconds(iv, self.lo, self.hi))
        self.busy_s = sum(self.busy) / len(self.busy)
        self.ok = self.busy_s > 0 and self.window_s > 0

    def intervals(self, dev=0):
        d = self.devices[dev]
        return [(t0, t1) for _n, _l, t0, t1 in (d["ops"] or d["modules"])]

    def _busy(self, dev):
        """The chip's busy intervals inside the window, merged, and
        their ends (to bisect)."""
        if dev not in self._merged:
            iv = merge(clip(self.intervals(dev), self.lo, self.hi))
            self._merged[dev] = (iv, [b for _a, b in iv])
        return self._merged[dev]

    def gaps_named(self, dev=0, top=10):
        """(the `top` longest idle gaps, each named by the host span that
        covers most of it; the idle seconds; the count of gaps). Only
        the gaps returned are named: naming looks at every span, and
        gaps and spans both grow with the steps in the window."""
        spans3 = [(n, a, b) for n, a, b, _ in self.spans]
        gaps = idle_gaps(self._busy(dev)[0], self.lo, self.hi)
        longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        named = [(name_gap(g, spans3), g[1] - g[0]) for g in longest]
        return named, sum(b - a for a, b in gaps), len(gaps)

    def top_ops(self, top=10):
        return sorted(self.labels().items(), key=lambda x: -x[1])[:top]

    def labels(self):
        tot = {}
        for dev in self.devices:
            for k, v in label_seconds(dev["ops"], self.lo, self.hi).items():
                tot[k] = tot.get(k, 0.0) + v / len(self.devices)
        return tot

    def busy_inside(self, span_name, dev=0):
        """(device busy seconds inside host spans of that name, the
        spans that lie whole inside the window)."""
        iv, ends = self._busy(dev)
        total, spans = 0.0, []
        for n, a, b, attrs in self.spans:
            if n != span_name or a < self.lo or b > self.hi:
                continue
            i = bisect.bisect_right(ends, a)
            while i < len(iv) and iv[i][0] < b:
                total += min(iv[i][1], b) - max(iv[i][0], a)
                i += 1
            spans.append((a, b, attrs))
        return total, spans

    def exposed_collective_seconds(self):
        """Mean over chips of the time a collective op runs while no
        other op does."""
        per = []
        for dev in self.devices:
            coll = [(t0, t1) for n, _l, t0, t1 in dev["ops"]
                    if is_collective(n)]
            rest = [(t0, t1) for n, _l, t0, t1 in dev["ops"]
                    if not is_collective(n)]
            exposed = subtract(clip(coll, self.lo, self.hi), rest)
            per.append(sum(b - a for a, b in exposed))
        return sum(per) / len(per) if per else None

    def step_count(self, dev=0, fallback=None):
        """Launches of the program that takes most device time, counted
        by the share of each that lies inside the window."""
        mods = self.devices[dev]["modules"]
        if not mods:
            return fallback
        tot = {}
        for n, _l, t0, t1 in mods:
            tot[n] = tot.get(n, 0.0) + (t1 - t0)
        top = max(tot, key=tot.get)
        count = 0.0
        for n, _l, t0, t1 in mods:
            if n == top and t1 > t0:
                a, b = max(t0, self.lo), min(t1, self.hi)
                if b > a:
                    count += (b - a) / (t1 - t0)
        return count or fallback

    def saw_collective(self):
        return any(is_collective(n) for dev in self.devices
                   for n, _l, _a, _b in dev["ops"])

    def breakdown(self):
        gaps, _tot, _n = self.gaps_named()
        return {"device_ops": [[k, v] for k, v in self.top_ops()],
                "idle_gaps": [[k, v] for k, v in gaps]}
