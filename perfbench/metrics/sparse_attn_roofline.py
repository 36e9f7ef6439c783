"""decoding.attention (sparse attention): the least time this chip could
take to attend the rows each decode step selected, over the device time
of the operations the program names `attn` (the token-granular gather of
the selected latent rows, the scores and values in latent space, the
softmax).

Per `decoding.step` span inside the traced window: the latent-row bytes
of the span's `selected_tokens` (the program's counter: sum over live
rows of min(context, top-k)) over all layers over the chip's memory
bandwidth, or the score and value FLOPs over its peak if that is larger
(harness/costs_sparse_latent.py); sum of floors over sum of times."""
from perfbench.harness import costs_sparse_latent as costs


def read(facts):
    cfg, peaks = facts["config"], facts["peaks"]
    return costs.roofline(
        facts, "attn",
        lambda a: costs.attn_floor_s(cfg, peaks, a["selected_tokens"])
        if a.get("selected_tokens") else None)
