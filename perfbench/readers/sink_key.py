"""Reader ``sink_key``: a statistic of one key over the run's unit records.

The records are what each unit of work left behind: for ``rl_step`` cells the
trainer's own per-step sink record (``timing/generation_duration``,
``timing/update_duration``, ``loss`` ...) with the harness's ``step_s`` beside
it. ``args``: ``key``; ``minus`` (keys subtracted record by record);
``stat`` (``median``, ``mean`` or ``sum``); ``scale``.
"""

from __future__ import annotations

import statistics


def read(observed, args, ctx):
    values = []
    for record in observed.get("units", []):
        keys = [args["key"], *args.get("minus", [])]
        if any(record.get(k) is None for k in keys):
            continue
        values.append(record[args["key"]] - sum(record[k] for k in args.get("minus", [])))
    if not values:
        return None
    stat = {"median": statistics.median, "mean": statistics.fmean, "sum": sum}[
        args.get("stat", "median")
    ]
    return stat(values) * args.get("scale", 1.0)
