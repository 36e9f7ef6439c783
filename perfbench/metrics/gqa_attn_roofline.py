"""decoding.attention (full layers, grouped queries): the least time this
chip could take to attend the live context of each decode step in the
full-attention layers, over the device time of the operations the
program names `attn` (the in-place kernel of those layers with the
query's spread over its KV head's lanes).

Per `decoding.step` span inside the traced window: the K and V bytes of
the span's `ctx_tokens` in the full layers at the pool's stored width
over the chip's memory bandwidth, or the score and value FLOPs of all
query heads over its peak if that is larger
(harness/costs_window_mixed.py); sum of floors over sum of times."""
from perfbench.harness import costs_sparse_latent, costs_window_mixed


def read(facts):
    cfg, peaks = facts["config"], facts["peaks"]
    return costs_sparse_latent.roofline(
        facts, "attn",
        lambda a: costs_window_mixed.attn_floor_s(
            cfg, peaks, a["ctx_tokens"], False)
        if a.get("ctx_tokens") else None)
