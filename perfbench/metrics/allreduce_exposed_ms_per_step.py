"""kvstore_tpu collective: time per step in which a collective operation
runs on a chip while no other operation does (mean over chips), from
the device trace. Only where the cell spans chips."""


def read(facts):
    if facts["chips"] < 2:
        return None
    red = facts["trace"]
    steps = red.step_count(fallback=facts.get("window_steps"))
    sec = red.exposed_collective_seconds()
    if not steps or sec is None or not red.saw_collective():
        return None
    return sec / steps * 1e3
