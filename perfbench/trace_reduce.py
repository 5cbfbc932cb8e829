"""From a profiler trace to busy and idle share, time per operation, and idle
gaps named by what the host was doing.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes (with nothing
but ``jax.profiler.ProfileData``) into a plain structure, device event names
shortened by ``op_name``::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

``reduce`` works on that structure alone, so it is checked against a small
recorded trace kept as JSON (``testdata/``) and computes the same number in
the same way on every PR.

Definitions. On a device plane the operations are the events of the line named
``XLA Ops``. They nest (a ``while`` holds its body's operations), so an
operation's time is its SELF time (its span less its children's), and the
device is BUSY during the union of the events that have no children. Idle
share is 1 - busy / window. A GAP is a maximal stretch of the window with no
such event; it is named by the innermost host span (the program's
``telemetry`` spans and the harness's own, on the wall clock) that covers its
middle. The trace's clock starts at the profiler's start; the harness's sync
annotation, whose wall time it noted, ties the two clocks.
"""

from __future__ import annotations

import re
from typing import Any, Iterable, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NO_SPAN = "(no host span)"
SHORT_GAPS = "(gaps under {us} us)"
#: a gap shorter than this is the device's own launch-to-launch turnaround; all
#: such gaps are summed under one name instead of being attributed one by one
MIN_GAP_NS = 5_000


_LAYOUT = re.compile(r"\{[^{}]*\}")
_NUMBER_SUFFIX = re.compile(r"\.\d+$")
NAME_LIMIT = 96


def op_name(text: str) -> str:
    """A device event's name, short enough to read and to group by. The v5e
    trace names an operation by its whole HLO instruction
    (``%fusion.12 = f32[64,8]{1,0:T(8,128)} fusion(...), kind=...``); kept are
    the instruction's name without its number and the type and shape of what
    it returns without the layout: ``%fusion f32[64,8]``. So the fourteen
    layers' calls of one kernel are one operation, and two fusions that
    return different shapes are two."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text[:NAME_LIMIT]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        returned = rest[: i + 1]
    else:
        returned = rest.split(" ", 1)[0]
    returned = _LAYOUT.sub("", returned)
    return f"{_NUMBER_SUFFIX.sub('', head)} {returned}"[:NAME_LIMIT]


def load_xplane(path: str, *, keep_host_events: Sequence[str] = ()) -> dict[str, Any]:
    """The device planes of ``path``, whole, and of the host plane only the
    events whose name is in ``keep_host_events`` (the sync annotation)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    keep = set(keep_host_events)
    planes = []
    for plane in data.planes:
        is_device = DEVICE_PLANE.match(plane.name) is not None
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device:
                events = [[op_name(e.name), e.start_ns, e.duration_ns]
                          for e in line.events]
            else:
                events = [
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name in keep
                ]
                if not events:
                    continue
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict[str, Any], top: int = 8) -> list[dict[str, Any]]:
    """Planes, lines, event counts and each line's longest-running names: what
    to look at by hand before trusting a regex."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            by_name: dict[str, float] = {}
            for name, _, dur in line["events"]:
                by_name[name] = by_name.get(name, 0.0) + dur
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
            out.append({
                "plane": plane["name"], "line": line["name"],
                "events": len(line["events"]),
                "top": [[n, round(d / 1e9, 6)] for n, d in ranked],
            })
    return out


def sync_offset_ns(trace: dict[str, Any], sync_event: str, sync_wall_ns: int) -> int:
    """wall_ns - trace_ns, from the sync annotation on the host plane."""
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name == sync_event:
                    return int(sync_wall_ns - start)
    raise LookupError(f"no {sync_event!r} annotation on {HOST_PLANE}")


def _self_times_and_leaves(starts, ends):
    """For events sorted by (start, -end): each one's self time (its span less
    the spans of its direct children) and whether it has no child."""
    starts, ends = starts.tolist(), ends.tolist()  # plain floats: a million events loop here
    self_ns = [e - s for s, e in zip(starts, ends)]
    leaf = [True] * len(starts)
    stack: list[int] = []
    for i, (s, e) in enumerate(zip(starts, ends)):
        while stack and ends[stack[-1]] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            leaf[parent] = False
            self_ns[parent] -= min(e, ends[parent]) - s
        stack.append(i)
    return np.maximum(np.array(self_ns, np.float64), 0.0), np.array(leaf, bool)


def _union(starts, ends):
    """Merged intervals of (starts, ends), both sorted by start: arrays (lo, hi)."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    reach = np.maximum.accumulate(ends)
    new = np.ones(len(starts), bool)
    new[1:] = starts[1:] > reach[:-1]
    lo = starts[new]
    last = np.flatnonzero(new)
    hi = reach[np.append(last[1:] - 1, len(starts) - 1)]
    return lo, hi


def _name_gap(mid_wall_ns: float, spans) -> str:
    best, best_len = NO_SPAN, None
    for name, t0, t1 in spans:
        if t0 <= mid_wall_ns <= t1 and (best_len is None or t1 - t0 < best_len):
            best, best_len = name, t1 - t0
    return best


def reduce_device(events: Iterable[Sequence], window_ns: tuple[float, float],
                  host_spans=(), offset_ns: int = 0) -> dict[str, Any]:
    """One device plane's operations over ``window_ns`` (trace clock):
    busy seconds, self seconds per operation name, gap seconds per host span."""
    lo_w, hi_w = window_ns
    rows = [(n, s, s + d) for n, s, d in events if s + d > lo_w and s < hi_w and d > 0]
    rows.sort(key=lambda r: (r[1], -r[2]))
    names = [r[0] for r in rows]
    starts = np.array([max(r[1], lo_w) for r in rows], np.float64)
    ends = np.array([min(r[2], hi_w) for r in rows], np.float64)
    self_ns, leaf = _self_times_and_leaves(starts, ends)
    ops: dict[str, float] = {}
    for name, t in zip(names, self_ns):
        ops[name] = ops.get(name, 0.0) + t
    lo, hi = _union(starts[leaf], ends[leaf])
    busy_ns = float((hi - lo).sum())
    # the gaps: before the first busy stretch, between stretches, after the last
    gap_lo = np.append(lo_w, hi)
    gap_hi = np.append(lo, hi_w)
    gaps: dict[str, float] = {}
    longest: list[tuple[float, str, float]] = []
    short_name = SHORT_GAPS.format(us=MIN_GAP_NS // 1000)
    for g0, g1 in zip(gap_lo, gap_hi):
        length = g1 - g0
        if length <= 0:
            continue
        if length < MIN_GAP_NS:
            gaps[short_name] = gaps.get(short_name, 0.0) + length
            continue
        name = _name_gap((g0 + g1) / 2.0 + offset_ns, host_spans)
        gaps[name] = gaps.get(name, 0.0) + length
        longest.append((length, name, g0 - lo_w))
    longest.sort(reverse=True)
    return {
        "events": len(rows), "busy_s": busy_ns / 1e9,
        "ops_s": {n: t / 1e9 for n, t in ops.items()},
        "gaps_s": {n: t / 1e9 for n, t in gaps.items()},
        "longest_gaps": [
            {"seconds": l / 1e9, "host": n, "at_s": at / 1e9}
            for l, n, at in longest[:10]
        ],
    }


def reduce(trace: dict[str, Any], *, window_wall_ns: tuple[int, int] | None = None,
           host_spans=(), offset_ns: int = 0, op_line: str = OP_LINE,
           ) -> dict[str, Any]:
    """Every device plane of ``trace`` over the traced window, and their mean.

    ``window_wall_ns`` is the window on the wall clock (None: from the first
    operation's start to the last one's end over all devices). Returns
    ``window_s``, per-device ``busy_s`` and ``idle_share``, their means, and
    ``ops_s`` / ``gaps_s`` (name -> seconds, mean over devices, so that busy
    plus gaps is the window on the average device)."""
    per_plane = []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m is None:
            continue
        events = [e for line in plane["lines"] if line["name"] == op_line
                  for e in line["events"]]
        per_plane.append((int(m.group(1)), plane["name"], events))
    per_plane.sort()
    if not any(ev for _, _, ev in per_plane):
        return {"devices": [], "window_s": 0.0}
    if window_wall_ns is not None:
        window = (window_wall_ns[0] - offset_ns, window_wall_ns[1] - offset_ns)
    else:
        window = (
            min(e[1] for _, _, ev in per_plane for e in ev),
            max(e[1] + e[2] for _, _, ev in per_plane for e in ev),
        )
    window_s = (window[1] - window[0]) / 1e9
    devices, ops, gaps = [], {}, {}
    for _, name, events in per_plane:
        r = reduce_device(events, window, host_spans, offset_ns)
        devices.append({
            "plane": name, "events": r["events"], "busy_s": r["busy_s"],
            "idle_share": 1.0 - r["busy_s"] / window_s,
            "longest_gaps": r["longest_gaps"],
        })
        for table, part in ((ops, r["ops_s"]), (gaps, r["gaps_s"])):
            for n, t in part.items():
                table[n] = table.get(n, 0.0) + t / len(per_plane)
    busy = float(np.mean([d["busy_s"] for d in devices]))
    return {
        "window_s": window_s, "devices": devices, "busy_s": busy,
        "idle_share": 1.0 - busy / window_s, "ops_s": ops, "gaps_s": gaps,
    }


def ranked(table: dict[str, float], top: int = 10) -> list[list]:
    """``[[name, seconds], ...]``, the ``top`` largest first."""
    return [[n, t] for n, t in sorted(table.items(), key=lambda kv: -kv[1])[:top]]


def cut(trace: dict[str, Any], lo_ns: float, hi_ns: float,
        keep_host_events: Sequence[str] = ()) -> dict[str, Any]:
    """The events of ``trace`` that start in [lo_ns, hi_ns), plus the named
    host events wherever they are: how ``testdata/`` was made from a run."""
    keep = set(keep_host_events)
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [e for e in line["events"]
                      if lo_ns <= e[1] < hi_ns or e[0] in keep]
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}
