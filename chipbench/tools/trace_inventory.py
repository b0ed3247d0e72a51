#!/usr/bin/env python3
"""What a captured trace holds, for reading by hand before the reduction is
trusted: planes, their lines, event counts, sample names and stats, and the
ops that took most device time.

    python chipbench/tools/trace_inventory.py <trace dir> [<out file>]
"""

from __future__ import annotations

import collections
import glob
import os
import sys


def main(logdir, out=None):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    lines_out = [f"trace files: {paths}"]
    for path in paths:
        lines_out.append(f"size: {os.path.getsize(path)} bytes")
        for plane in ProfileData.from_file(path).planes:
            lines_out.append(f"PLANE {plane.name!r}")
            for line in plane.lines:
                events = list(line.events)
                lines_out.append(f"  LINE {line.name!r}: {len(events)} events")
                if not events:
                    continue
                t0 = min(e.start_ns for e in events)
                t1 = max(e.start_ns + e.duration_ns for e in events)
                lines_out.append(f"    span {t0:.0f} .. {t1:.0f} ns ({(t1 - t0) / 1e6:.3f} ms)")
                for e in events[:3]:
                    stats = {k: (v if not isinstance(v, str) else v[:160]) for k, v in e.stats}
                    lines_out.append(
                        f"    e.g. {e.name[:120]!r} start {e.start_ns:.0f} dur {e.duration_ns:.0f} stats {stats}")
                total = collections.Counter()
                count = collections.Counter()
                for e in events:
                    total[e.name] += e.duration_ns
                    count[e.name] += 1
                for name, ns in total.most_common(25):
                    lines_out.append(f"    top {ns / 1e6:10.3f} ms x{count[name]:<6} {name[:140]}")
                for e in events:
                    text = e.name + " " + " ".join(str(v) for _, v in e.stats)
                    if "pool_bwd" in text or "custom" in e.name:
                        stats = {k: (v if not isinstance(v, str) else v[:300]) for k, v in e.stats}
                        lines_out.append(f"    custom/pool: {e.name!r} dur {e.duration_ns:.0f} stats {stats}")
                        break
    text = "\n".join(lines_out)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            f.write(text + "\n")
    print(text[-6000:])


if __name__ == "__main__":
    main(*sys.argv[1:3])
