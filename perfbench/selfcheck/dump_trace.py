"""Look at one trace by hand: planes, lines, and a sample of events with
their stats, of the newest .xplane.pb under a directory (default: the
last traced run's, .perfbench_out/trace).

    python3 perfbench/selfcheck/dump_trace.py [dir] [events per line]
"""
import glob
import os
import sys


def main(argv):
    from jax.profiler import ProfileData

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    d = argv[0] if argv else os.path.join(root, ".perfbench_out", "trace")
    per_line = int(argv[1]) if len(argv) > 1 else 6
    paths = sorted(glob.glob(os.path.join(
        d, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    if not paths:
        print("no trace under", d)
        return 1
    print(paths[-1], os.path.getsize(paths[-1]), "bytes")
    pd = ProfileData.from_file(paths[-1])
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            span = (evs[-1].start_ns + evs[-1].duration_ns
                    - evs[0].start_ns) * 1e-9
            print(f"  LINE {line.name!r}: {len(evs)} events over "
                  f"{span:.3f}s")
            names = {}
            for e in evs:
                rec = names.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns * 1e-9
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:per_line]
            print(f"    FULL NAME OF ONE EVENT: {evs[len(evs) // 2].name[:3000]!r}")
            for n, (c, s) in top:
                ev = next(e for e in evs if e.name == n)
                print(f"    {n[:90]!r} x{c} {s:.4f}s stats="
                      f"{ {k: str(v)[:160] for k, v in dict(ev.stats).items()} }")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
