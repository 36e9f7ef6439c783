"""decoding.engine (KV pool): bytes of the pages both page groups have
handed out over the live context tokens, mean over the `decoding.step`
spans of the traced window: the span's `pages_held` (pages out of the
full and of the window group's allocator) x page size x what a position
stores over that group's layers (harness/costs_window_mixed.py), over
its `ctx_tokens`. The full layers' 5120 B a token plus the window
layers' share where pages behind a row's window are released; 30720 B
where they are kept. The program's attrs, nothing from the device;
nothing where the spans carry no `pages_held` of two groups."""
from perfbench.harness import costs_window_mixed as costs


def read(facts):
    cfg = facts["config"]
    _, steps = facts["trace"].busy_inside("decoding.step")
    page = int(cfg["page_size"])
    per_slot = [costs.group_bytes_per_token(cfg, False),
                costs.group_bytes_per_token(cfg, True)]
    ratios = []
    for _, _, a in steps:
        a = a or {}
        held = a.get("pages_held")
        if not held or len(held) != 2 or not a.get("ctx_tokens"):
            continue
        ratios.append(sum(n * page * b for n, b in zip(held, per_slot))
                      / a["ctx_tokens"])
    return sum(ratios) / len(ratios) if ratios else None
