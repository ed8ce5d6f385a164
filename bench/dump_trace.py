"""Print what a profiler trace holds, to read it by hand before writing a
reducer against it.

    python bench/dump_trace.py bench/.trace/<cell>/plugins/profile/*/*.xplane.pb

For each plane and line: the number of events, and the event names that
took most time, with their counts and the stats of the first of each.
"""
from __future__ import annotations

import gzip
import sys
from pathlib import Path


def main(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    prof = ProfileData.from_serialized_xspace(data)
    for plane in prof.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            tot: dict[str, list] = {}
            for e in evs:
                t = tot.setdefault(e.name, [0.0, 0, e])
                t[0] += e.duration_ns
                t[1] += 1
            print(f"  line {line.name!r}: {len(evs)} events"
                  + (f", {evs[0].start_ns:.0f}..{evs[-1].end_ns:.0f} ns" if evs else ""))
            for name, (ns, n, first) in sorted(tot.items(), key=lambda kv: -kv[1][0])[:top]:
                stats = {k: (v if len(str(v)) < 120 else str(v)[:120] + "...")
                         for k, v in first.stats}
                print(f"    {ns / 1e6:12.3f} ms {n:7d}x  {name}  {stats}")


if __name__ == "__main__":
    main(sys.argv[1])
