"""decoding.scheduler: median time a prefill waited behind the decode
steps in flight, from its dispatch returning to the last of them taken
out (`behind_us` on the traced window's `decoding.prefill` spans that
are not readmissions; 0 where nothing was in flight). None where the
spans carry no `behind_us` (a program older than it)."""
from perfbench.harness import common, loop_phases


def read(facts):
    fills = loop_phases.first_fills(facts)
    return common.quantile([attrs["behind_us"] for _a, _b, attrs in fills],
                           0.5) / 1e3 if fills else None
