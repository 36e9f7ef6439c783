"""serving.server, client side: median gap between successive output
tokens of a request, all requests of the window, stamped by the clients."""
from perfbench.harness import common


def read(facts):
    g = facts["all_gaps"]
    return common.quantile(g, 0.5) * 1e3 if g else None
