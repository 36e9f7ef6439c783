"""fused step program: device busy time per step, from the device trace
(mean over chips)."""


def read(facts):
    red = facts["trace"]
    steps = red.step_count(fallback=facts.get("window_steps"))
    return red.busy_s / steps * 1e3 if steps else None
