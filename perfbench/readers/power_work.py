"""Reader ``power_work``: what the power-retention layers of a ``brumby`` cell
did, against what they had to (``perfbench/power_counts.py``, or whatever
module the cell's configuration names under ``counts``).

``args["what"]``:

* ``power_step_roofline``: the float32 state bytes the traced rounds' DECODE
  steps must read and write (``power_state_bytes``: S and z a KV head a layer,
  packed) / peak HBM bandwidth / the device time under ``args["scope"]`` inside
  the rounds' decode spans (``args["span"]``), in %. Bound: memory.
* ``power_chunk_roofline``: the operations the chunked form needs for the
  traced rounds' PROMPTS (``power_chunk_flops``; a prompt is prefilled once for
  its group of candidates) / peak bf16 FLOP/s / the device time under
  ``args["scope"]`` inside the rounds' prefill spans, in %. Bound: compute;
  float32 products at full precision are several bf16 passes each, so this
  reads low by construction.

A program without this scope or these spans (the parent of the PR that added
them), an untraced run, a configuration whose ``counts`` has no such functions
and a call without a run all give None.
"""

from __future__ import annotations

from perfbench import spec, trace_scopes
from perfbench.readers.required_work import cache_bytes


def read(observed, args, ctx):
    if ctx is None:
        return None
    peaks, model = observed.get("peaks"), observed.get("model")
    layout, units = observed.get("rollout"), observed.get("traced_units")
    if peaks is None or model is None or not layout or not units:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", "roofline"))
    if not hasattr(counts, "power_state_bytes"):
        return None  # another family's counts: it has no such layers
    what = args["what"]
    if what == "power_step_roofline":
        needed = sum(cache_bytes(counts.power_state_bytes, model, u,
                                 kv_bytes=layout["kv_bytes"]) for u in units)
        peak = peaks["hbm_bytes_per_s"]
    elif what == "power_chunk_roofline":
        # consecutive rows of a group share a prompt, prefilled once
        needed = sum(
            counts.power_chunk_flops(model, u["prompt_lens"][:: u.get("group_size") or 1])
            for u in units)
        peak = peaks["bf16_flops_per_s"]
    else:
        raise ValueError(f"power_work cannot read {what!r}")
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peak / seconds
