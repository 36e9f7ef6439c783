"""module fit loop: mean host time of one `fit.dispatch` span (stage the
batch, launch the step, update the metric, admit to the dispatch window;
the wait for step n-2 is inside it), inside the traced window."""


def read(facts):
    lo, hi = facts["window_host"]
    d = [t1 - t0 for n, t0, t1, _ in facts["spans"]
         if n == "fit.dispatch" and t0 >= lo and t1 <= hi]
    return sum(d) / len(d) * 1e3 if d else None
