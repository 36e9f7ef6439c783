"""decoding.engine: the share of the decode steps whose sampler took its
argmax branch, with no row above temperature 0 (`sampled_rows` 0 on the
`decoding.step` span), over the spans whole inside the traced window.
None where the spans carry no `sampled_rows`: a program whose sampler
has no such branch (the parent of PR 35)."""


def read(facts):
    _, steps = facts["trace"].busy_inside("decoding.step")
    rows = [a["sampled_rows"] for _, _, a in steps
            if a and "sampled_rows" in a]
    return sum(n == 0 for n in rows) / len(rows) if rows else None
