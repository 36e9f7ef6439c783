"""decoding.engine: device busy time inside `decoding.prefill` spans per
1000 prompt tokens prefilled, in the traced window."""


def read(facts):
    busy, spans = facts["trace"].busy_inside("decoding.prefill")
    toks = sum((a or {}).get("tokens", 0) - (a or {}).get("cached_tokens", 0)
               for _, _, a in spans)
    return busy / toks * 1e6 if toks else None
