"""decoding.attention: the least time this chip could take to attend the
live context of each decode step, over the device time of the
operations the program names `attn` (the attention kernel with its
context gather; layout copies the compiler makes for it count with it).

Per `decoding.step` span inside the traced window: the K and V bytes of
the span's `ctx_tokens` over all layers at the pool's stored width
(scale planes included) over the chip's memory bandwidth, or the
attention's FLOPs over its peak if that is larger (harness/scopes.py);
sum of floors over sum of times. The bytes are the live context's,
whatever kernel reads them: a kernel that reads padding or whole pools
reads low. Leaves which side bounds in facts["notes"]."""
from perfbench.harness import scopes


def read(facts):
    steps = scopes.step_part_seconds(facts)
    if not steps:
        return None
    cfg, peaks = facts["config"], facts["peaks"]
    per_tok_s = (scopes.kv_bytes_per_context_token(cfg)
                 / peaks["hbm_bytes_per_s"],
                 scopes.attn_flops_per_context_token(cfg)
                 / peaks["bf16_flops_per_s"])
    floor, measured = 0.0, 0.0
    for parts, attrs in steps:
        ctx = attrs.get("ctx_tokens")
        if not ctx or "attn" not in parts:
            continue
        floor += ctx * max(per_tok_s)
        measured += parts["attn"]
    if measured <= 0.0:
        return None
    facts.setdefault("notes", {})["paged_attn_roofline"] = (
        "memory-bound" if per_tok_s[0] >= per_tok_s[1] else "compute-bound")
    return 100.0 * floor / measured
