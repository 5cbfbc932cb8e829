"""Reader ``round_ledger``: the program's own record of every round
(``telemetry.round_records()``: the ring the engines file one record into at
the end of each ``generate``, tracing on or off), as it stands when the run
ends. It needs no trace.

A record with ``programs_built > 0`` is left out (the warm-up round with its
compiles or cache loads): what is left are the MEASURED rounds of the run, the
untraced ones and the traced one alike. ``args``: ``what``:

* ``boundary_median_ms``: the median interval between two returns from the
  snapshot wait, over all those rounds' boundaries pooled;
* ``worst_boundary_ms``, ``worst_boundary_host_ms``, ``worst_boundary_cpu_ms``:
  the longest interval of any of those rounds, the part of it before its wait
  began, and the CPU milliseconds of all the process's threads inside it;
* ``stalled_boundaries``: boundaries the program itself called stalled (no
  admission, grant or preemption pass in them, and longer than 1.5 of their
  round's median), summed over those rounds: a count, 0 in a sound run;
* ``stall_recovered_ms``: ``recovered_s`` of the round that holds the worst
  boundary (what the boundaries after its longest stalled one came back under
  the median: how far the device had run ahead through the stall); 0 where
  that round stalled nowhere.

A program without the ledger (the parent of the PR that added it), a ledger
with no measured round and a call without a run give None: the metric is left
out. So is each of the five in milliseconds where no measured round kept a
boundary (a round of one or two snapshots has no interval between two returns);
the count reads 0 there.
"""

from __future__ import annotations

import statistics


def measured(records) -> list:
    """The records of rounds that built no program."""
    return [r for r in records if not r.get("programs_built")]


def read(observed, args, ctx):
    if ctx is None:
        return None
    try:
        from distrl_llm_tpu import telemetry

        rounds = measured(telemetry.round_records())
    except (ImportError, AttributeError):  # no such ledger
        return None
    if not rounds:
        return None
    what = args["what"]
    if what == "stalled_boundaries":
        return sum(len(r["stalled"]) for r in rounds)
    rounds = [r for r in rounds if r["boundaries"]]
    if not rounds:
        return None
    if what == "boundary_median_ms":
        return 1e3 * statistics.median(b[0] for r in rounds for b in r["boundaries"])
    # the round that holds the longest interval, and that boundary
    worst_round = max(rounds, key=lambda r: max(b[0] for b in r["boundaries"]))
    interval_s, host_s, cpu_s = max(worst_round["boundaries"], key=lambda b: b[0])[:3]
    if what == "worst_boundary_ms":
        return 1e3 * interval_s
    if what == "worst_boundary_host_ms":
        return 1e3 * host_s
    if what == "worst_boundary_cpu_ms":
        return 1e3 * cpu_s
    if what == "stall_recovered_ms":
        return 1e3 * worst_round["recovered_s"]
    raise ValueError(f"round_ledger cannot read {what!r}")
