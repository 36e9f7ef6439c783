"""io iterator: mean host time `Module.fit` was blocked in the iterator's
next(), from the program's `fit.data_wait` spans inside the traced window."""


def read(facts):
    lo, hi = facts["window_host"]
    d = [t1 - t0 for n, t0, t1, _ in facts["spans"]
         if n == "fit.data_wait" and t0 >= lo and t1 <= hi]
    return sum(d) / len(d) * 1e3 if d else None
