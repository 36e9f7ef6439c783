"""decoding.scheduler: median time a request waited for a row, from its
submit to the admission taking it from the queue (`queued_us` on the
`decoding.prefill` spans of the traced window that are not
readmissions). Also logs the median prefill span and the sum of the
three medians that make up a first token. None where the spans carry no
`queued_us` (a program older than it)."""
from perfbench.harness import common, loop_phases


def read(facts):
    fills = loop_phases.first_fills(facts)
    if not fills:
        return None
    p50 = lambda k: common.quantile(  # noqa: E731
        [attrs[k] for _a, _b, attrs in fills], 0.5) / 1e3
    queued, behind = p50("queued_us"), p50("behind_us")
    span = common.quantile([b - a for a, b, _ in fills], 0.5) * 1e3
    facts.setdefault("notes", {})["first_token_parts"] = (
        f"{len(fills)} prefills: queued p50 {queued:.3f} ms + behind p50 "
        f"{behind:.3f} ms + prefill span p50 {span:.3f} ms = "
        f"{queued + behind + span:.3f} ms")
    return queued
