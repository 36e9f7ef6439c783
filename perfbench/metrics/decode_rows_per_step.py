"""decoding.scheduler: output tokens per engine step over the window,
from DecodeStats (`decode_tokens` / `steps`)."""


def read(facts):
    c = facts["counters"]
    return c["decode_tokens"] / c["steps"] if c["steps"] else None
