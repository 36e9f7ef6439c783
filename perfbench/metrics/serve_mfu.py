"""whole step: 2 x matmul parameters (harness/costs.py) x (prompt tokens
prefilled + output tokens delivered) a second of the traced window, over
the chip's bf16 peak. Cannot pass 100."""
from perfbench.harness import costs


def read(facts):
    red = facts["trace"]
    _, fills = red.busy_inside("decoding.prefill")
    prompt = sum((a or {}).get("tokens", 0) - (a or {}).get("cached_tokens", 0)
                 for _, _, a in fills)
    toks = prompt + facts["tokens"]
    if not toks:
        return None
    flops = costs.decoder_flops_per_token(facts["config"]) * toks
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops / red.window_s / peak
