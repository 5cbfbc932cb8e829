"""Reader ``program_gauge``: one gauge of the program's own registry
(``telemetry.observe_snapshot()["gauges"]``), as it stands when the run ends.

A gauge holds its LAST value: for a gauge an engine files a round, the last
round of the run, which is a measured one (the traced round in a traced run),
never the warm-up with its compiles. ``args``: ``name`` (the gauge's name,
letter for letter); ``scale``.

A program with no such gauge (the parent of the PR that added it), or no such
registry, and a call without a run give None: the metric is left out.
"""

from __future__ import annotations


def read(observed, args, ctx):
    if ctx is None:
        return None
    try:
        from distrl_llm_tpu import telemetry

        gauges = telemetry.observe_snapshot()["gauges"]
    except (ImportError, AttributeError, KeyError):  # no such registry: no gauge
        return None
    value = gauges.get(args["name"])
    if value is None:
        return None
    return value * args.get("scale", 1.0)
