"""Reader ``span_self``: a span's SELF time, the span's seconds less what the
spans inside it cover (``ctx.tracer.host_spans``: name, start and end on the
wall clock, collected by the harness while the profile ran).

``args``: ``names`` (the parents, letter for letter: every span of one of
these names is one); ``scale`` (1000 for milliseconds). A parent's children are
the other spans that begin inside it, whatever their names, cut to its end; the
time they cover is their UNION, so a child's own children count once. The value
is the parents' self seconds, summed, over the number of traced units (a round):
what the loop between its named parts costs (its own Python, the launches it
does not name, table uploads).

A program that emits no such span, an untraced run and a call without a run all
return None: the metric is left out.
"""

from __future__ import annotations


def covered_ns(parent: tuple[int, int], spans) -> int:
    """Nanoseconds of ``parent`` (start, end) under the union of ``spans``
    ((start, end) each) that begin inside it, each cut to its end."""
    lo, hi = parent
    total, reach = 0, lo
    for t0, t1 in sorted(s for s in spans if lo <= s[0] < hi):
        t1 = min(t1, hi)
        if t1 > reach:
            total += t1 - max(t0, reach)
            reach = t1
    return total


def read(observed, args, ctx):
    tracer = getattr(ctx, "tracer", None)
    if tracer is None:
        return None
    parents = [i for i, (name, _, _) in enumerate(tracer.host_spans)
               if name in args["names"]]
    units = len(observed.get("traced_units", []))
    if not parents or not units:
        return None
    spans = [(t0, t1) for _, t0, t1 in tracer.host_spans]
    self_ns = 0
    for i in parents:
        t0, t1 = spans[i]
        self_ns += (t1 - t0) - covered_ns(spans[i], spans[:i] + spans[i + 1:])
    return self_ns / 1e9 / units * args.get("scale", 1.0)
