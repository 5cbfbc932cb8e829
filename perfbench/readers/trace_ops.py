"""Reader ``trace_ops``: from the reduced device trace (``trace_reduce.reduce``).

``args["what"]``:

* ``idle_share``: 1 - busy / window, mean over the cell's chips, in %;
* ``busy_share_of``: self time of the operations whose name matches
  ``args["regex"]`` over the device's busy time, in %. The regex lives in the
  metric's file: it names the kernel as the trace shows it today.
"""

from __future__ import annotations

import re


def matching_seconds(trace, regex: str) -> float:
    pattern = re.compile(regex)
    return sum(t for name, t in trace["ops_s"].items() if pattern.search(name))


def read(observed, args, ctx):
    trace = observed.get("trace")
    if not trace or not trace.get("devices"):
        return None
    what = args["what"]
    if what == "idle_share":
        return 100.0 * trace["idle_share"]
    if what == "busy_share_of":
        seconds = matching_seconds(trace, args["regex"])
        return 100.0 * seconds / trace["busy_s"] if seconds > 0 else None
    raise ValueError(f"trace_ops cannot read {what!r}")
