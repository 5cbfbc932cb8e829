#!/usr/bin/env python3
"""How ``testdata/`` is cut from a traced run, and what a trace holds.

    python3 perfbench/cut_testdata.py <cell> <out.json> [start_ms] [length_ms]

reads the ``.xplane.pb`` the last ``--trace 1`` run of ``<cell>`` left under
``.perfbench_out/trace/<cell>/``, prints each plane and line with its
longest-running event names and the statistics one event of each carries (look
at them by hand before writing a regex against them), and writes the events
that start in [start_ms, start_ms + length_ms) after the first device event,
as the plain structure ``trace_reduce.reduce`` works on.
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from perfbench import harness, trace_reduce  # noqa: E402


def event_stats(path: str, per_line: int = 12) -> list[dict]:
    """For each device line, the statistics of one event of each of its
    longest-running names."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name) is None:
            continue
        for line in plane.lines:
            total: dict[str, float] = {}
            sample: dict[str, dict] = {}
            for e in line.events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
                if e.name not in sample:
                    sample[e.name] = {str(k): str(v)[:200] for k, v in e.stats}
            for name in sorted(total, key=lambda n: -total[n])[:per_line]:
                out.append({"plane": plane.name, "line": line.name, "event": name,
                            "seconds": total[name] / 1e9, "stats": sample[name]})
        break  # one device is enough to read names from
    return out


def main(argv: list[str]) -> int:
    cell, out_path = argv[0], argv[1]
    start_ms = float(argv[2]) if len(argv) > 2 else 0.0
    length_ms = float(argv[3]) if len(argv) > 3 else 40.0
    path = harness.Tracer(cell).xplane_path()
    for row in event_stats(path):
        print(json.dumps(row))
    trace = trace_reduce.load_xplane(path, keep_host_events=(harness.SYNC_EVENT,))
    first = min(
        e[1] for p in trace["planes"] if trace_reduce.DEVICE_PLANE.match(p["name"])
        for line in p["lines"] for e in line["events"]
    )
    lo = first + start_ms * 1e6
    cut = trace_reduce.cut(trace, lo, lo + length_ms * 1e6, (harness.SYNC_EVENT,))
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(cut, f, separators=(",", ":"))
    print(json.dumps({"wrote": out_path, "bytes": os.path.getsize(out_path)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
