"""Device time by the program's own scope names, from the traced ``.xplane.pb``.

``trace_reduce`` ranks operations by HLO name and returned shape, which a change
to a layer renames. The program carries ``jax.named_scope`` names instead
(``distrl_llm_tpu/telemetry.py``, ``SCOPE_NAMES``), and this module sums device
time under them. Which names count is data: the VOCABULARY of a run is what
``spec.load_scope_names`` finds in the ``scopes/*.json`` files under the
benchmark's ``paths`` (the yardstick's own copy, so that it also runs over a
program that has none; a PR whose program carries a new name adds a file), and
every function here that needs it takes it as a tuple of names.

Where the scope is. On a v5e trace (found with one ``--trace 1`` run, PR 24) an
``XLA Ops`` event carries only ``device_offset_ps`` / ``device_duration_ps`` of
its own. The scope path is a stat of the event's METADATA (``XEventMetadata``,
shared by all events of one HLO instruction), named **``tf_op``**, and reads
``jit(step)/while/body/closed_call/transpose(jvp(learner/loss))/.../mul:``
(JAX's ``op_name`` and a colon). ``jax.profiler.ProfileData`` does not expose
metadata stats, so ``load`` decodes the few protobuf fields it needs itself
(``_fields``: the wire format, no dependency), keeping for each event of the
``XLA Ops`` lines its metadata's name and ``tf_op``. Beside ``tf_op`` the
metadata holds ``hlo_category``, ``flops``, ``bytes_accessed``, ``source``,
``program_id``; asynchronous starts and dones, and the copies the compiler
inserts, have no ``tf_op`` at all.

A fused operation carries its ROOT's path: this is an attribution by fusion
root, not by instruction. A fusion that the compiler built across two scopes
counts under the scope of the instruction that became its root.

``table`` gives every LEAF event (one with no child event: what
``trace_reduce`` counts as busy) its duration, under a row named by the
innermost vocabulary name on its path, or ``unscoped``. Under ``learner/loss``
the row is prefixed by the phase JAX wrote into the path: ``.forward`` (neither
transposed nor rematerialised), ``.recompute`` (``rematted_computation``),
``.backward`` (``transpose(``), e.g. ``learner/loss.backward model/mlp``. The
rows sum to the device's busy time, to rounding. A trace in which no event
carries a vocabulary name (executables loaded from a cache that a program
without scopes filled) has no table: ``table`` says so and returns None.
"""

from __future__ import annotations

import functools
import os
import re
import sys
from typing import Any

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # run as a script, as cut_testdata.py is
    sys.path.insert(0, _ROOT)

from perfbench import spec, trace_reduce  # noqa: E402

UNSCOPED = "unscoped"
LOSS = "learner/loss"
SCOPE_STAT = "tf_op"


@functools.lru_cache(maxsize=None)
def _names(vocabulary: tuple[str, ...]) -> re.Pattern:
    """The regex that finds ``vocabulary``'s names on a path, built once per
    vocabulary. A name counts only as a whole path component or run of
    components: ``jvp(learner/loss)`` and ``.../model/mlp/dot_general`` match,
    ``my_model/mlp`` does not."""
    return re.compile(
        r"(?<![A-Za-z0-9_])("
        + "|".join(re.escape(n) for n in sorted(vocabulary, key=len, reverse=True))
        + r")(?![A-Za-z0-9_])"
    )


def classify(path: str | None, vocabulary: tuple[str, ...]) -> str:
    """The row an operation's time goes to: the innermost name of
    ``vocabulary`` on ``path`` (the last to start; the regex prefers the longer
    of two that start together), prefixed by the learner's phase where
    ``learner/loss`` is on it."""
    if not path or not vocabulary:
        return UNSCOPED
    found = [m.group(1) for m in _names(vocabulary).finditer(path)]
    if not found:
        return UNSCOPED
    inner = found[-1]
    if not any(n == LOSS or n.startswith(LOSS + "/") for n in found):
        return inner
    if "rematted_computation" in path:
        phase = "recompute"
    elif "transpose(" in path:
        phase = "backward"
    else:
        phase = "forward"
    return f"{LOSS}.{phase}" if inner == LOSS else f"{LOSS}.{phase} {inner}"


# ------------------------------------------------------- the xplane, decoded


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of each field of one protobuf
    message; a length-delimited value is a slice of ``buf``, left undecoded."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, wire, value


def _map_values(buf):
    """The values of a ``map<int64, Message>`` entry list's one entry."""
    for number, _, value in _fields(buf):
        if number == 2:
            return value
    return None


def _plane_metadata(plane) -> dict[int, tuple[str, str | None]]:
    """``metadata id -> (name, scope path or None)`` of one XPlane."""
    stat_ids = set()
    for number, _, value in _fields(plane):
        if number == 5:  # stat_metadata
            entry = _map_values(value)
            sid = sname = None
            for n2, _, v2 in _fields(entry):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = bytes(v2).decode()
            if sname == SCOPE_STAT:
                stat_ids.add(sid)
    out = {}
    for number, _, value in _fields(plane):
        if number != 4:  # event_metadata
            continue
        mid = name = path = None
        for n2, _, v2 in _fields(_map_values(value)):
            if n2 == 1:
                mid = v2
            elif n2 == 2:
                name = bytes(v2).decode(errors="replace")
            elif n2 == 5:  # XStat
                sid = text = None
                for n3, _, v3 in _fields(v2):
                    if n3 == 1:
                        sid = v3
                    elif n3 == 5:
                        text = v3
                if sid in stat_ids and text is not None:
                    path = bytes(text).decode(errors="replace")
        out[mid] = (name or "", path)
    return out


def _line_events(line) -> tuple[str, list[tuple[int, int, int]]]:
    """A line's name and its events as ``(metadata id, start ns, duration ns)``."""
    name, t0_ns, raw = "", 0, []
    for number, _, value in _fields(line):
        if number == 2:
            name = bytes(value).decode()
        elif number == 3:
            t0_ns = value
        elif number == 4:
            raw.append(value)
    if name != trace_reduce.OP_LINE:
        return name, []
    events = []
    for ev in raw:
        mid = offset_ps = duration_ps = 0
        for n2, wire, v2 in _fields(ev):
            if wire:
                continue
            if n2 == 1:
                mid = v2
            elif n2 == 2:
                offset_ps = v2
            elif n2 == 3:
                duration_ps = v2
        events.append((mid, t0_ns + offset_ps // 1000, duration_ps // 1000))
    return name, events


def load(path: str) -> dict[str, Any]:
    """The ``XLA Ops`` events of every device plane of ``path``, each with its
    scope path: ``{"planes": [{"name", "events": [[op, start_ns, dur_ns, path]]}]}``
    (``op`` shortened by ``trace_reduce.op_name``: the structure ``cut`` and the
    recorded pieces in ``testdata/`` use)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name = ""
        for n2, _, v2 in _fields(plane):
            if n2 == 2:
                name = bytes(v2).decode()
                break
        if trace_reduce.DEVICE_PLANE.match(name) is None:
            continue
        metadata = _plane_metadata(plane)
        short: dict[int, tuple[str, str | None]] = {}
        events = []
        for n2, _, v2 in _fields(plane):
            if n2 != 3:
                continue
            for mid, start, dur in _line_events(v2)[1]:
                if mid not in short:
                    full, scope = metadata.get(mid, ("?", None))
                    short[mid] = (trace_reduce.op_name(full), scope)
                op, scope = short[mid]
                events.append([op, start, dur, scope])
        planes.append({"name": name, "events": events})
    return {"planes": planes}


def cut(trace: dict[str, Any], lo_ns: float, hi_ns: float) -> dict[str, Any]:
    """The events that start in [lo_ns, hi_ns): how ``testdata/`` is made."""
    return {"planes": [
        {"name": p["name"],
         "events": [e for e in p["events"] if lo_ns <= e[1] < hi_ns]}
        for p in trace["planes"]
    ]}


# ----------------------------------------------------------------- the table


def table(trace: dict[str, Any], vocabulary: tuple[str, ...],
          window_ns: tuple[float, float] | None = None,
          top: int = 8) -> dict[str, Any] | None:
    """Busy seconds of the average device under each row (see the module's
    docstring), over ``window_ns`` on the trace's clock (None: everything).
    None where no event carries a name of ``vocabulary`` or no device was
    traced."""
    per_device = []
    for plane in trace["planes"]:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]) is None:
            continue
        lo_w, hi_w = window_ns if window_ns is not None else (-np.inf, np.inf)
        rows = [e for e in plane["events"]
                if e[1] + e[2] > lo_w and e[1] < hi_w and e[2] > 0]
        if not rows:
            continue
        rows.sort(key=lambda e: (e[1], -(e[1] + e[2])))
        starts = np.array([max(e[1], lo_w) for e in rows], np.float64)
        ends = np.array([min(e[1] + e[2], hi_w) for e in rows], np.float64)
        _, leaf = trace_reduce._self_times_and_leaves(starts, ends)
        seconds: dict[str, float] = {}
        unscoped: dict[str, float] = {}
        labels: dict[str | None, str] = {}
        scoped_events = 0
        for (op, _, _, path), s, e, is_leaf in zip(rows, starts, ends, leaf):
            if not is_leaf:
                continue
            if path not in labels:
                labels[path] = classify(path, vocabulary)
            label = labels[path]
            seconds[label] = seconds.get(label, 0.0) + (e - s) / 1e9
            if label == UNSCOPED:
                unscoped[op] = unscoped.get(op, 0.0) + (e - s) / 1e9
            else:
                scoped_events += 1
        per_device.append((seconds, unscoped, scoped_events))
    if not per_device or not any(n for _, _, n in per_device):
        return None
    rows_s: dict[str, float] = {}
    unscoped_s: dict[str, float] = {}
    for seconds, unscoped, _ in per_device:
        for into, part in ((rows_s, seconds), (unscoped_s, unscoped)):
            for name, t in part.items():
                into[name] = into.get(name, 0.0) + t / len(per_device)
    return {
        "busy_s": sum(rows_s.values()),
        "rows_s": dict(sorted(rows_s.items(), key=lambda kv: -kv[1])),
        "unscoped_top": trace_reduce.ranked(unscoped_s, top),
        "scoped_events": sum(n for _, _, n in per_device),
    }


def seconds_under(tab: dict[str, Any], scope: str) -> float:
    """Seconds of the rows whose name ``scope`` (a regex) matches; ``unscoped``
    names that one row."""
    if scope == UNSCOPED:
        return tab["rows_s"].get(UNSCOPED, 0.0)
    pattern = re.compile(scope)
    return sum(t for name, t in tab["rows_s"].items() if pattern.search(name))


# ------------------------------------------------------ one table per run


_RUNS: dict[str, dict[str, Any] | None] = {}
_LOADED: dict[str, dict[str, Any] | None] = {}


def _traced_file(ctx) -> str | None:
    """The traced run's ``.xplane.pb``; None for a call without a run, an
    untraced run and a run whose profiler wrote no file."""
    tracer = getattr(ctx, "tracer", None)
    if tracer is None or tracer.window_wall_ns is None:
        return None
    try:
        return tracer.xplane_path()
    except FileNotFoundError:
        return None


def loaded_for(ctx) -> dict[str, Any] | None:
    """The traced run's file, parsed once a run and kept for every reader that
    cuts it: ``{"trace": load(path), "host": the host plane with the sync
    annotation and the spans' own, "offset": wall clock less profiler clock in
    ns, or None where the sync annotation is missing, "spans": the tables
    ``seconds_in_spans`` has cut}``. None for a call
    without a run, an untraced run and a trace with no device event (the CPU)."""
    from perfbench import harness

    path = _traced_file(ctx)
    if path is None:
        return None
    if path not in _LOADED:
        tracer = ctx.tracer
        trace = load(path)
        held = None
        if any(p["events"] for p in trace["planes"]):
            names = {name for name, _, _ in tracer.host_spans}
            host = trace_reduce.load_xplane(
                path, keep_host_events=(harness.SYNC_EVENT, *names)
            )
            try:
                offset = trace_reduce.sync_offset_ns(
                    host, harness.SYNC_EVENT, tracer.sync_wall_ns
                )
            except LookupError:
                offset = None
            held = {"trace": trace, "host": host, "offset": offset, "spans": {}}
        _LOADED[path] = held
    return _LOADED[path]


def seconds_in_spans(ctx, scope: str, span: str) -> float | None:
    """Device seconds under the scope rows ``scope`` names (a regex), inside
    the traced run's host spans called ``span`` (``engine/decode``: a round's
    decode loop, which starts once its prefill has finished); None where there
    is no such span, no trace, no clock offset or no second under the scope.
    The trace is the one ``loaded_for`` parsed, and a span's tables are cut
    once for every metric that reads them."""
    held = loaded_for(ctx)
    if held is None or held["offset"] is None:
        return None
    if span not in held["spans"]:
        vocabulary = spec.load_scope_names(ctx.cell.paths)
        cut = (table(held["trace"], vocabulary, (t0 - held["offset"], t1 - held["offset"]))
               for name, t0, t1 in ctx.tracer.host_spans if name == span)
        held["spans"][span] = [tab for tab in cut if tab is not None]
    seconds = sum(seconds_under(tab, scope) for tab in held["spans"][span])
    return seconds if seconds > 0 else None


def table_for(ctx) -> dict[str, Any] | None:
    """The traced run's table, loaded and reduced once and kept: every metric of
    the reader reads this one. Prints the whole table as a ``note`` line, or
    why there is none. None for a call without a run, an untraced run, a trace
    with no device plane (the CPU) or a trace that carries no scope."""
    from perfbench import harness

    path = _traced_file(ctx)
    if path is None:
        return None
    if path in _RUNS:
        return _RUNS[path]
    held, tracer = loaded_for(ctx), ctx.tracer
    tab = None
    if held is not None:
        offset = held["offset"]
        window = None if offset is None else (
            tracer.window_wall_ns[0] - offset, tracer.window_wall_ns[1] - offset
        )
        tab = table(held["trace"], spec.load_scope_names(ctx.cell.paths), window)
        if tab is None:
            harness.emit(
                "trace_scopes", problem=(
                    "no operation carries a name of the vocabulary: the "
                    "executables came from a compilation cache that a program "
                    "without scopes filled (metadata is not in the cache's key), "
                    "or the program has no scopes; the scope metrics are left out"
                ),
            )
        else:
            harness.emit("trace_scopes", **tab)
            clock = span_clock(held["host"], tracer.host_spans, offset)
            if clock is not None:
                harness.emit("span_clock", **clock)
    _RUNS[path] = tab
    return tab


def span_clock(host: dict[str, Any], host_spans,
               offset_ns: int | None) -> dict[str, Any] | None:
    """How far the program's spans on the wall clock (less the sync offset) lie
    from their own ``TraceAnnotation`` on the profiler's clock. ``host`` is
    ``trace_reduce.load_xplane`` with the spans' names kept; per name the
    starts are paired in order where the counts agree. None where the program
    annotates nothing (the parent of PR 24)."""
    if offset_ns is None:
        return None
    names = {name for name, _, _ in host_spans}
    annotated: dict[str, list[int]] = {}
    for plane in host["planes"]:
        if plane["name"] != trace_reduce.HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name in names:
                    annotated.setdefault(name, []).append(start)
    differences = []
    for name in names:
        wall = sorted(t0 - offset_ns for n, t0, _ in host_spans if n == name)
        prof = sorted(annotated.get(name, []))
        if prof and len(prof) == len(wall):
            differences += [w - p for w, p in zip(wall, prof)]
    if not differences:
        return None
    arr = np.abs(np.array(differences, np.float64)) / 1e3
    return {"pairs": len(differences), "max_abs_us": float(arr.max()),
            "median_abs_us": float(np.median(arr))}


def main(argv: list[str]) -> int:
    """``python3 perfbench/trace_scopes.py <file.xplane.pb> [out.json start_ms
    length_ms ...]``: prints the table of the whole trace under the vocabulary
    of the checkout's ``BENCHMARK.json``, and writes the events that start in
    each [start_ms, start_ms + length_ms) after the first device event, scope
    kept, as ``testdata/`` holds them."""
    import json

    trace = load(argv[0])
    vocabulary = spec.load_scope_names(spec.load_benchmark()["paths"])
    print(json.dumps(table(trace, vocabulary)
                     or {"problem": "no operation carries a scope"}))
    if len(argv) > 1:
        first = min(e[1] for p in trace["planes"] for e in p["events"])
        planes = [{"name": p["name"], "events": []} for p in trace["planes"]]
        for start_ms, length_ms in zip(argv[2::2], argv[3::2]):
            lo = first + float(start_ms) * 1e6
            piece = cut(trace, lo, lo + float(length_ms) * 1e6)
            for into, part in zip(planes, piece["planes"]):
                into["events"] += part["events"]
        with open(argv[1], "w", encoding="utf-8") as f:
            json.dump({"planes": planes}, f, separators=(",", ":"))
        print(json.dumps({"wrote": argv[1],
                          "events": sum(len(p["events"]) for p in planes)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
