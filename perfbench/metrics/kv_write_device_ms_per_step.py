"""decoding.engine (KV pool): device time a decode step spends under the
scope `kv_write` (both `kv_scatter` calls of every layer), mean over the
`decoding.step` spans of the traced window. A layout copy the compiler
makes of the pool has no name of its own and counts with the scope of
the operation that first uses it (the program's scope map)."""
from perfbench.harness import scopes


def read(facts):
    return scopes.part_ms_per_step(facts, "kv_write")
