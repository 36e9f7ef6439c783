"""serving.server client side: host time of one `decoding.emit` span (a
step's tokens handed to their streams, finished requests resolved, the
step's stats), mean over the timed window OUTSIDE the traced
sub-window; the same from inside it goes to facts["notes"]."""
from perfbench.harness import scopes


def read(facts):
    outside, inside = scopes.spans_in_and_out(facts, "decoding.emit")
    out_ms, in_ms = scopes.mean_ms(outside), scopes.mean_ms(inside)
    if out_ms is None and in_ms is None:
        return None
    scopes.note_in_out(facts, "emit_host_ms_per_step", out_ms, in_ms)
    return out_ms if out_ms is not None else in_ms
