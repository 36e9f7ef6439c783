"""decoding.attention (window layers): the least time this chip could
take to attend, in the sliding-window layers, what each decode step's
rows have in reach, over the device time of the operations the program
names `attn_window` (the in-place kernel with a lower position bound
and the sink).

Per `decoding.step` span inside the traced window: the K and V bytes of
the span's `window_tokens` (the program's attr: sum over live rows of
min(context, window)) in the window layers at the pool's stored width
over the chip's memory bandwidth, or the score and value FLOPs over its
peak if that is larger (harness/costs_window_mixed.py); sum of floors
over sum of times. Nothing where the spans carry no `window_tokens`."""
from perfbench.harness import costs_sparse_latent, costs_window_mixed


def read(facts):
    cfg, peaks = facts["config"], facts["peaks"]
    return costs_sparse_latent.roofline(
        facts, "attn_window",
        lambda a: costs_window_mixed.attn_floor_s(
            cfg, peaks, a["window_tokens"], True)
        if a.get("window_tokens") else None)
