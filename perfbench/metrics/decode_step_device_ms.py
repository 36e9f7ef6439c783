"""decoding.engine: device busy time inside one `decoding.step` span (the
engine blocks on each step's tokens, so a launch's device time lies
inside its host span), mean over the spans whole inside the traced
window."""


def read(facts):
    busy, spans = facts["trace"].busy_inside("decoding.step")
    return busy / len(spans) * 1e3 if spans else None
