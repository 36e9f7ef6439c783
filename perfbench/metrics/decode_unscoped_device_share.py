"""decoding.engine: share of the decode program's device time, inside the
`decoding.step` spans of the traced window, that lands in no named
scope: how far the per-part numbers can be trusted. Leaves every part's
milliseconds a step in facts["notes"], beside their sum."""
from perfbench.harness import scopes


def read(facts):
    steps = scopes.step_part_seconds(facts)
    if not steps:
        return None
    total = {}
    for parts, _ in steps:
        for k, v in parts.items():
            total[k] = total.get(k, 0.0) + v
    whole = sum(total.values())
    if whole <= 0.0:
        return None
    ms = {k: round(v / len(steps) * 1e3, 4) for k, v in sorted(
        total.items(), key=lambda kv: -kv[1])}
    facts.setdefault("notes", {})["decode_step_parts_ms"] = (
        f"{ms} sum {whole / len(steps) * 1e3:.4f} over {len(steps)} steps")
    return 100.0 * total.get(scopes.UNSCOPED, 0.0) / whole
