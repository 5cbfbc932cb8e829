"""Reader ``engine_result``: what ``GenerationResult`` counted, over the
untraced rounds of a ``rollout`` cell. ``args["what"]``:

* ``decode_step_ms``: round wall seconds (prefill and admissions included)
  over the decode step programs dispatched, in milliseconds;
* ``slot_occupancy``: live slot-steps over dispatched steps x slots, in %.
"""

from __future__ import annotations


def read(observed, args, ctx):
    rounds = [u for u in observed.get("units", []) if u.get("steps_dispatched")]
    if not rounds:
        return None
    steps = sum(u["steps_dispatched"] for u in rounds)
    what = args["what"]
    if what == "decode_step_ms":
        return 1e3 * sum(u["t1"] - u["t0"] for u in rounds) / steps
    if what == "slot_occupancy":
        if any(u.get("alive_slot_steps") is None for u in rounds):
            return None
        alive = sum(u["alive_slot_steps"] for u in rounds)
        return 100.0 * alive / sum(u["steps_dispatched"] * u["slots"] for u in rounds)
    raise ValueError(f"engine_result cannot read {what!r}")
