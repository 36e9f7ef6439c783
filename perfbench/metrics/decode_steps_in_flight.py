"""decoding.scheduler: the decode steps still launched as a step's tokens
were taken out (`in_flight` on the `decoding.step` span), mean over the
spans whole inside the traced window. 0 where the loop waits for each
step; up to the scheduler's `run_ahead` where it keeps steps in flight,
and lower the more often a turn takes out everything launched (an
admission, a decision). None where the spans carry no `in_flight`: a
program that does not report it (one older than the attr)."""


def read(facts):
    _, steps = facts["trace"].busy_inside("decoding.step")
    counts = [a["in_flight"] for _, _, a in steps
              if a and "in_flight" in a]
    return sum(counts) / len(counts) if counts else None
