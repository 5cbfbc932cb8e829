"""Reader ``host_spans``: the program's own ``telemetry.span`` records over the
traced units (``ctx.tracer.host_spans``: name, start and end on the wall clock,
collected by the harness while the profile ran).

``args``: ``name`` (the span's name, letter for letter); ``stat``:
``sum_per_unit`` (all such spans' seconds over the number of traced units: a
round, an update, a step) or ``median`` (of the spans' seconds, for a span that
occurs once a unit); ``scale`` (1000 for milliseconds).

A program that emits no such span (the parent of the PR that added it), an
untraced run and a call without a run all return None: the metric is left out.
"""

from __future__ import annotations

import statistics


def read(observed, args, ctx):
    tracer = getattr(ctx, "tracer", None)
    if tracer is None:
        return None
    seconds = [(t1 - t0) / 1e9 for name, t0, t1 in tracer.host_spans
               if name == args["name"]]
    if not seconds:
        return None
    stat = args.get("stat", "median")
    if stat == "sum_per_unit":
        units = len(observed.get("traced_units", []))
        if not units:
            return None
        value = sum(seconds) / units
    elif stat == "median":
        value = statistics.median(seconds)
    else:
        raise ValueError(f"host_spans cannot compute {stat!r}")
    return value * args.get("scale", 1.0)
