"""decoding.scheduler: host time between the end of one `decoding.step`
span and the start of the next, less the `decoding.prefill` spans that
lie between them (admission, page growth, row packing, stream emits)."""


def read(facts):
    lo, hi = facts["window_host"]
    steps = sorted((t0, t1) for n, t0, t1, _ in facts["spans"]
                   if n == "decoding.step" and t0 >= lo and t1 <= hi)
    fills = [(t0, t1) for n, t0, t1, _ in facts["spans"]
             if n == "decoding.prefill"]
    if len(steps) < 2:
        return None
    total = 0.0
    for (_, a1), (b0, _) in zip(steps, steps[1:]):
        gap = b0 - a1
        gap -= sum(t1 - t0 for t0, t1 in fills if t0 >= a1 and t1 <= b0)
        total += max(gap, 0.0)
    return total / (len(steps) - 1) * 1e3
