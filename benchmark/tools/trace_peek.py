#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, and the first events of
each line with their stats, as ``jax.profiler.ProfileData`` shows them.

    python3 benchmark/tools/trace_peek.py <file.xplane.pb> [--events 8]

``benchmark/run.py --trace 1 --dump-dir <dir>`` keeps the run's xplane file.
What PR 22 saw on a v5e is written at the top of
``benchmark/lib/trace_reduce.py``.
"""

import argparse


def main(argv=None):
    import jax
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--events", type=int, default=8)
    ap.add_argument("--width", type=int, default=160)
    args = ap.parse_args(argv)
    data = jax.profiler.ProfileData.from_file(args.path)
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name}: {len(events)} events")
            for ev in events[:args.events]:
                stats = {k: v for k, v in ev.stats
                         if isinstance(v, (int, float, str))}
                print(f"    {ev.start_ns:.0f} +{ev.duration_ns:.0f} ns  "
                      f"{ev.name[:args.width]}  {stats}")


if __name__ == "__main__":
    main()
