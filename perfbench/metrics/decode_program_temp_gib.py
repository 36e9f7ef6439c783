"""decoding.engine: temporaries of the decode program the window ran, in
GiB: `temp_bytes` (the compiled program's `memory_analysis()`) of the
`deviceStats` record whose module the window's `decoding.step` spans
name. `peak_bytes_in_use` does not hold it; it is what the pool's size
has to leave room for. The record table outlives the engine."""
from perfbench.harness import scopes


def read(facts):
    steps = scopes.decode_steps(facts)
    if not steps:
        return None
    names = [attrs["program"] for _, _, attrs in steps]
    program = max(set(names), key=names.count)
    try:
        from mxnet_tpu import profiling
    except ImportError:
        return None
    recs = [r for r in profiling.records_for()
            if r.get("module") == program and r.get("temp_bytes")]
    if not recs:
        return None
    return max(r["temp_bytes"] for r in recs) / 2 ** 30
