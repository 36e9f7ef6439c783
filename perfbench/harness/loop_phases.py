"""The serving loop's phases under the device's idle time: every idle
instant of the traced window put down to the outermost span of the
scheduler's loop that covers it, and an admission's idle time split
further by the parts its `decoding.prefill` spans state.

Phases:
  admit.launch, admit.behind, admit.prefill, admit.rest
      inside a `decoding.admit` span: a prefill's dispatch
      (`launch_us`), its wait behind the steps in flight (`behind_us`,
      between the dispatch and the span), the `decoding.prefill` span
      less its dispatch, and the rest of the admission (deadlines,
      pages, the host's work between one prefill's token and the next
      dispatch, page growth);
  relaunch
      a `decoding.step` span whose `queued` is 0: the device waits for
      the turn's pack and launch (and the waited-for turn's `pack` and
      `emit`, which lie outside its step span);
  steady
      a `decoding.step` span with steps already in flight, or one that
      launches nothing (`queued` absent: a settle's retire);
  none
      under no span of the loop.

On a program whose loop spans carry neither `queued` nor `prefills`
(one older than them) `split` returns None and raises nothing. The idle
gaps and the spans are swept once in time order: linear in gaps plus
spans.
"""
from perfbench.harness import trace_reduce

LOOP = ("decoding.admit", "decoding.step", "decoding.pack",
        "decoding.emit")
ADMIT_PARTS = ("admit.launch", "admit.behind", "admit.prefill",
               "admit.rest")
PHASES = ADMIT_PARTS + ("relaunch", "steady", "none")


def top_level(spans):
    """The loop's outermost spans [(name, t0, t1, attrs)] in time order:
    the loop's spans come from its one thread, so one that starts
    inside the span before it is nested in it."""
    out = []
    for s in sorted((s for s in spans if s[0] in LOOP),
                    key=lambda s: (s[1], -s[2])):
        if out and s[1] < out[-1][2]:
            continue
        out.append((s[0], s[1], s[2], s[3] or {}))
    return out


def _prefill_pieces(fill):
    """(label, t0, t1) of one `decoding.prefill` span's parts: with
    steps in flight (`behind_us` > 0) the dispatch and the wait behind
    them precede the span; without them the dispatch opens it."""
    _n, p0, p1, a = fill
    launch, behind = a["launch_us"] * 1e-6, a["behind_us"] * 1e-6
    if behind > 0:
        return [("admit.launch", p0 - behind - launch, p0 - behind),
                ("admit.behind", p0 - behind, p0),
                ("admit.prefill", p0, p1)]
    return [("admit.launch", p0, p0 + launch),
            ("admit.prefill", p0 + launch, p1)]


def _admit_pieces(a0, a1, fills):
    """An admission's span [a0, a1] as disjoint labelled pieces in time
    order, its prefills' parts first and `admit.rest` between them."""
    out, cur = [], a0
    for fill in fills:
        for label, t0, t1 in _prefill_pieces(fill):
            t0, t1 = max(t0, cur), min(t1, a1)
            if t1 <= t0:
                continue
            if t0 > cur:
                out.append(("admit.rest", cur, t0))
            out.append((label, t0, t1))
            cur = t1
    if a1 > cur:
        out.append(("admit.rest", cur, a1))
    return out


def _phase(name, attrs):
    if name == "decoding.step":
        return "relaunch" if attrs.get("queued") == 0 else "steady"
    return "relaunch"   # the waited-for turn's pack and emit


def labelled(spans):
    """The loop's outermost spans cut into disjoint labelled pieces:
    ([(t0, t1, label, owner)], the outermost spans), `owner` the index
    of the outermost span a piece belongs to."""
    tops = top_level(spans)
    fills = sorted((s for s in spans if s[0] == "decoding.prefill"
                    and s[3] and "launch_us" in s[3]),
                   key=lambda s: s[1])
    out, j = [], 0
    for i, (name, a, b, attrs) in enumerate(tops):
        if name != "decoding.admit":
            out.append((a, b, _phase(name, attrs), i))
            continue
        while j < len(fills) and fills[j][1] < a:
            j += 1
        inside = []
        while j < len(fills) and fills[j][1] < b:
            inside.append(fills[j])
            j += 1
        out.extend((t0, t1, label, i)
                   for label, t0, t1 in _admit_pieces(a, b, inside))
    return out, tops


def split(facts):
    """{"phases": {phase: idle seconds}, "tops": the outermost spans,
    "idle": [idle seconds inside each], "window": (lo, hi), "longest":
    the three longest gaps as (seconds, the phase holding most of
    each)}; None where no span carries `queued` or `prefills` (a
    program older than them). Cached in `facts`."""
    if "_loop_phases" in facts:
        return facts["_loop_phases"]
    red = facts["trace"]
    out = None
    if any(s[3] and ("queued" in s[3] or "prefills" in s[3])
           for s in red.spans if s[0] in LOOP):
        gaps = trace_reduce.idle_gaps(red.intervals(0), red.lo, red.hi)
        pieces, tops = labelled(red.spans)
        phases = dict.fromkeys(PHASES, 0.0)
        idle = [0.0] * len(tops)
        named, j = [], 0
        for g0, g1 in gaps:
            while j < len(pieces) and pieces[j][1] <= g0:
                j += 1
            covered, k, most = 0.0, j, (0.0, "none")
            while k < len(pieces) and pieces[k][0] < g1:
                t0, t1, label, owner = pieces[k]
                ov = min(t1, g1) - max(t0, g0)
                if ov > 0:
                    phases[label] += ov
                    idle[owner] += ov
                    covered += ov
                    if ov > most[0]:
                        most = (ov, label)
                k += 1
            phases["none"] += (g1 - g0) - covered
            if g1 - g0 - covered > most[0]:
                most = (g1 - g0 - covered, "none")
            named.append((g1 - g0, most[1]))
        out = {"phases": phases, "tops": tops, "idle": idle,
               "window": (red.lo, red.hi),
               "longest": sorted(named, reverse=True)[:3]}
    facts["_loop_phases"] = out
    return out


def whole_in_window(res):
    """(outermost span, its idle seconds) for the spans that lie whole
    inside the traced window."""
    lo, hi = res["window"]
    return [(t, s) for t, s in zip(res["tops"], res["idle"])
            if t[1] >= lo and t[2] <= hi]


def first_fills(facts):
    """[(t0, t1, attrs)] of the `decoding.prefill` spans that lie whole in
    the traced window, carry the admission's parts (`queued_us`,
    `launch_us`, `behind_us`) and are not readmissions."""
    red = facts["trace"]
    return [(a, b, attrs) for n, a, b, attrs in red.spans
            if n == "decoding.prefill" and a >= red.lo and b <= red.hi
            and attrs and "queued_us" in attrs
            and not attrs.get("readmission")]


def note(facts, res):
    """One line in the run's log: the window's idle seconds put down to
    the phases, their sum against the trace's own idle."""
    red = facts["trace"]
    ph, win = res["phases"], red.window_s
    pct = lambda v: f"{v:.4f} s ({100.0 * v / win:.2f}%)"  # noqa: E731
    admit = sum(ph[p] for p in ADMIT_PARTS)
    facts.setdefault("notes", {})["idle_by_loop_phase"] = (
        f"admit {pct(admit)} = launch {pct(ph['admit.launch'])} + behind "
        f"{pct(ph['admit.behind'])} + prefill {pct(ph['admit.prefill'])} "
        f"+ rest {pct(ph['admit.rest'])}; relaunch {pct(ph['relaunch'])}; "
        f"steady {pct(ph['steady'])}; under no loop span "
        f"{pct(ph['none'])}; sum {pct(sum(ph.values()))} against the "
        f"window's idle {pct(win - red.busy_s)} of {win:.4f} s; longest "
        "gaps " + ", ".join(f"{g * 1e3:.2f} ms {label}"
                            for g, label in res["longest"]))
