"""decoding.model (routed experts): the least time this chip could take
for the routed experts of each decode step, over the device time of the
operations the program names `experts`.

Per `decoding.step` span inside the traced window: the weights of the
span's `experts_hit` (distinct held experts touched, summed over expert
layers) over the chip's memory bandwidth, or 2 x one expert's parameters
x `expert_rows` over its peak if that is larger
(harness/costs_sparse_latent.py); sum of floors over sum of times. The
work is the traffic's, whatever computes it: a grouped computation that
reads every held expert reads low when few are hit."""
from perfbench.harness import costs_sparse_latent as costs


def read(facts):
    cfg, peaks = facts["config"], facts["peaks"]
    return costs.roofline(
        facts, "experts",
        lambda a: costs.experts_floor_s(cfg, peaks, a["experts_hit"],
                                        a["expert_rows"])
        if a.get("experts_hit") and a.get("expert_rows") else None)
