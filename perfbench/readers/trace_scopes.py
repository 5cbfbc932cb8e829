"""Reader ``trace_scopes``: device time under the program's scope names
(``perfbench/trace_scopes.py``: one table per traced run, loaded once).

``args``: ``scope``: a regex over the table's row names (``^model/mlp$``,
``^learner/loss\\.backward( |$)``, ``^learner/optimizer``), or ``unscoped`` for
the time under no name of the vocabulary; ``of``: ``busy`` (the share, in %, of
the device's busy time: the only denominator there is).

None, and the metric is left out, for a call without a run, an untraced run, a
trace without a device plane, and a trace in which no operation carries a scope
(the parent of the PR that added them, or executables loaded from a cache that
such a program filled: the table's ``note`` line says which).
"""

from __future__ import annotations

from perfbench import trace_scopes


def read(observed, args, ctx):
    table = trace_scopes.table_for(ctx)
    if table is None or table["busy_s"] <= 0:
        return None
    if args.get("of", "busy") != "busy":
        raise ValueError(f"trace_scopes cannot take a share of {args['of']!r}")
    seconds = trace_scopes.seconds_under(table, args["scope"])
    if seconds <= 0 and args["scope"] != trace_scopes.UNSCOPED:
        return None  # a scope that names nothing in this trace is no reading of 0%
    return 100.0 * seconds / table["busy_s"]
