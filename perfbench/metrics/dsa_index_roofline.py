"""decoding.attention (sparse selection): the least time this chip could
take to score the live context of each decode step, over the device time
of the operations the program names `index` (the index keys' gather, the
index scores, the exact top-k).

Per `decoding.step` span inside the traced window: the index-key bytes
of the span's `ctx_tokens` over all layers at the pool's stored width
over the chip's memory bandwidth, or the index products' FLOPs over its
peak if that is larger (harness/costs_sparse_latent.py); sum of floors
over sum of times. The bytes are the live context's: a selection that
scores padding, or sorts where a partial selection would do, reads low."""
from perfbench.harness import costs_sparse_latent as costs


def read(facts):
    cfg, peaks = facts["config"], facts["peaks"]
    return costs.roofline(
        facts, "index",
        lambda a: costs.index_floor_s(cfg, peaks, a["ctx_tokens"])
        if a.get("ctx_tokens") and a.get("selected_tokens") else None)
