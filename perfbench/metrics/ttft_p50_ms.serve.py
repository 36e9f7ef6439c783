"""serving.server, client side: median time from submit to first token of
the requests submitted inside the window (closed loop: each waits for a
free row and its prefill). Nothing where none was submitted."""
from perfbench.harness import common


def read(facts):
    t = facts["ttft"]
    return common.quantile(t, 0.5) * 1e3 if t else None
