"""device: share of the traced window in which no operation ran on the
device (mean over chips)."""


def read(facts):
    red = facts["trace"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
