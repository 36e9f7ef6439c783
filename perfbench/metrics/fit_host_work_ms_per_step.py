"""module fit loop: what the host itself needs per step: a `fit.dispatch`
span less the `fit.window_wait` span inside it (the wait for step n-2
and the scalar fetch after it), per step of the dispatch. Mean over the
timed window OUTSIDE the traced sub-window; the same from inside it,
and the mean `fetch_us` of the waits, go to facts["notes"]. None on a
program whose dispatch has no `fit.window_wait` child."""
from perfbench.harness import scopes


def _work_ms(facts, dispatches):
    if not dispatches:
        return None
    waits = scopes.children_seconds(facts, dispatches, "fit.window_wait")
    steps = sum((a or {}).get("steps", 1) for _, _, a in dispatches)
    work = sum((t1 - t0) - w for (t0, t1, _), w in zip(dispatches, waits))
    return work / steps * 1e3


def read(facts):
    waits = [a for n, _, _, a in facts["spans"] if n == "fit.window_wait"]
    if not waits:
        return None
    outside, inside = scopes.spans_in_and_out(facts, "fit.dispatch")
    out_ms, in_ms = _work_ms(facts, outside), _work_ms(facts, inside)
    if out_ms is None and in_ms is None:
        return None
    scopes.note_in_out(facts, "fit_host_work_ms_per_step", out_ms, in_ms)
    fetch = [a["fetch_us"] for a in waits if a and "fetch_us" in a]
    if fetch:
        facts["notes"]["fit_window_wait_fetch_us"] = (
            f"mean {sum(fetch) / len(fetch):.1f} over {len(fetch)} waits")
    return out_ms if out_ms is not None else in_ms
