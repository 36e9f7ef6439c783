"""decoding.scheduler: device idle time inside the `decoding.step` spans
whose `queued` is 0, the turns that launch onto an empty queue (after an
admission's drain, or every turn of the loop that waits for each step),
over their count, in the traced window. None where no span carries
`queued` (a program older than it)."""
from perfbench.harness import loop_phases


def read(facts):
    res = loop_phases.split(facts)
    if res is None:
        return None
    idle = [seconds for (name, _a, _b, attrs), seconds
            in loop_phases.whole_in_window(res)
            if name == "decoding.step" and attrs.get("queued") == 0]
    return sum(idle) / len(idle) * 1e3 if idle else None
