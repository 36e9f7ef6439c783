"""decoding.model (routed experts): how uneven the routing is over the
held experts: the busiest expert's rows in a step (`expert_rows_max`,
largest over the expert layers) over the mean rows of an expert that
was hit (`expert_rows` / `experts_hit`, both summed over the expert
layers), mean over the `decoding.step` spans of the traced window. 1 is
even; the program's counters, nothing from the device."""


def read(facts):
    _, steps = facts["trace"].busy_inside("decoding.step")
    ratios = []
    for _, _, a in steps:
        a = a or {}
        if a.get("experts_hit") and a.get("expert_rows") \
                and a.get("expert_rows_max"):
            ratios.append(a["expert_rows_max"]
                          / (a["expert_rows"] / a["experts_hit"]))
    return sum(ratios) / len(ratios) if ratios else None
