"""module fit loop: device idle time between operations, per step, from
the device trace (chip 0). The breakdown names each long gap by the host
span that covers it."""


def read(facts):
    red = facts["trace"]
    steps = red.step_count(fallback=facts.get("window_steps"))
    if not steps:
        return None
    return (red.window_s - red.busy[0]) / steps * 1e3
