"""whole step: the matrix FLOPs of the traced window over the chip's bf16
peak (harness/costs_window_mixed.py): 2 x the matrix parameters every
token passes on this chip (the fused projections and output matrices,
the dense feed-forward, the routers) x (prompt tokens computed + output
tokens delivered), the head for each sampled row, and 2 x one expert's
parameters x `expert_rows`, the assignments to held experts that the
program's counter reports on its `decoding.step` and `decoding.prefill`
spans. Cannot pass 100. Nothing where the spans carry no such counter.
Leaves in the log what a step spends under the block's own scopes."""
from perfbench.harness import costs_window_mixed as costs


def read(facts):
    red = facts["trace"]
    _, fills = red.busy_inside("decoding.prefill")
    _, steps = red.busy_inside("decoding.step")
    spans = [a or {} for _, _, a in fills + steps]
    if not spans or not any("expert_rows" in a for a in spans):
        return None
    prompt = sum((a or {}).get("tokens", 0) - (a or {}).get("cached_tokens", 0)
                 for _, _, a in fills)
    toks = prompt + facts["tokens"]
    if not toks:
        return None
    costs.note_own_scopes(facts)
    flops = costs.step_flops(
        facts["config"], toks, facts["tokens"] + len(fills),
        sum(a.get("expert_rows", 0) for a in spans))
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops / red.window_s / peak
