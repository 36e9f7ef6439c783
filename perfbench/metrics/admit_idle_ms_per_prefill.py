"""decoding.scheduler: device idle time inside the `decoding.admit` spans
that lie whole in the traced window, over the prefills they launched
(`prefills` on the span): the host's share of an admission that the
device waits for. Also logs the window's idle seconds put down to the
loop's phases (`harness/loop_phases.py`). None where no span carries
`prefills` or `queued` (a program older than them), or where the window
admitted nothing."""
from perfbench.harness import loop_phases


def read(facts):
    res = loop_phases.split(facts)
    if res is None:
        return None
    loop_phases.note(facts, res)
    idle, prefills = 0.0, 0
    for (name, _a, _b, attrs), seconds in loop_phases.whole_in_window(res):
        if name == "decoding.admit" and "prefills" in attrs:
            idle += seconds
            prefills += attrs["prefills"]
    return idle / prefills * 1e3 if prefills else None
