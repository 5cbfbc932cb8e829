"""Reader ``window_moe_work``: what the sliding-window layers of an
``exaone_moe`` cell attended, by the program's own counters.

``args["what"]``:

* ``window_attended_share``: the keys the window layers' decode steps attended
  over the keys a full-attention layer would have (``args["attended"]`` /
  ``args["visible"]``, in units of 128 keys), in %, over everything the process
  ran. 100 below 128 tokens of context, and the day a window layer silently
  attends everything.

A program without these counters (the parent of the PR that added them) and a
call without a run give None.
"""

from __future__ import annotations


def read(observed, args, ctx):
    if ctx is None:
        return None
    if args["what"] != "window_attended_share":
        raise ValueError(f"window_moe_work cannot read {args['what']!r}")
    try:
        from distrl_llm_tpu import telemetry

        counters = telemetry.observe_snapshot()["counters"]
    except (ImportError, AttributeError, KeyError):  # no such registry: no counter
        return None
    attended, visible = counters.get(args["attended"]), counters.get(args["visible"])
    if not attended or not visible:
        return None
    return 100.0 * attended / visible
