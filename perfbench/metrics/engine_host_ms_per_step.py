"""decoding.engine: host time of one engine step that is not the device's:
the `engine.launch` and `engine.fetch` spans (row padding, transfers in,
the program call, the copy back) less the device busy time inside them.

The spans are taken from the timed window OUTSIDE the traced sub-window,
where the profiler does not slow the host; the device time a step needs
is the traced steps' (the device does the same work either way). The
same number from inside the traced sub-window goes to facts["notes"]."""
from perfbench.harness import scopes


def _per_step(launches, fetches):
    if not launches or not fetches:
        return None
    return scopes.mean_ms(launches) + scopes.mean_ms(fetches)


def read(facts):
    out_l, in_l = scopes.spans_in_and_out(facts, "engine.launch")
    out_f, in_f = scopes.spans_in_and_out(facts, "engine.fetch")
    if not in_l or not in_f:
        return None
    busy = 0.0      # device ms inside one launch and one fetch span
    for name in ("engine.launch", "engine.fetch"):
        seconds, spans = facts["trace"].busy_inside(name)
        if not spans:
            return None
        busy += seconds / len(spans) * 1e3
    inside = _per_step(in_l, in_f) - busy
    outside = _per_step(out_l, out_f)
    outside = outside - busy if outside is not None else None
    scopes.note_in_out(facts, "engine_host_ms_per_step", outside, inside)
    return outside if outside is not None else inside
