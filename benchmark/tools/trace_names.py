"""Look at one trace by hand: which planes and lines it has, and what the
events are called. Prints a summary of an .xplane.pb.

    python3 benchmark/tools/trace_names.py <dir or file> [--top 40]
"""
import argparse
import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--fixture", help="also write the first "
                    "--fixture-seconds of the window as a small json.gz")
    ap.add_argument("--fixture-seconds", type=float, default=0.3)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    from benchmark import tracing

    path = args.path if os.path.isfile(args.path) \
        else tracing.newest_xplane(args.path)
    if args.fixture:
        import gzip
        import json

        from benchmark import reduce

        tr = tracing.load(path, args.devices)
        win = (tr.window[0], tr.window[0] + args.fixture_seconds)
        with gzip.open(args.fixture, "wt") as f:
            json.dump({"window": win,
                       "devices": [reduce.clip_events(d, win)
                                   for d in tr.devices],
                       "host": reduce.clip_events(tr.host, win)}, f)
    for plane, lines in tracing.planes(path):
        print(f"PLANE {plane!r}: {len(lines)} lines")
        for name, events in lines:
            if not events:
                continue
            total = collections.Counter()
            count = collections.Counter()
            for ev, _, dur in events:
                total[ev] += dur
                count[ev] += 1
            span = (min(s for _, s, _ in events),
                    max(s + d for _, s, d in events))
            print(f"  LINE {name!r}: {len(events)} events, "
                  f"{(span[1] - span[0]) * 1e-9:.4f} s from "
                  f"{span[0] * 1e-9:.4f}")
            for ev, ns in total.most_common(args.top):
                print(f"    {ns * 1e-9:10.6f} s  x{count[ev]:<6} {ev[:140]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
