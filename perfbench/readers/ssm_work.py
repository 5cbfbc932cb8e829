"""Reader ``ssm_work``: what the state-space layers of a ``jamba`` cell did,
against what they had to (``perfbench/ssm_counts.py``, or whatever module the
cell's configuration names under ``counts``).

``args["what"]``:

* ``ssm_step_roofline``: the float32 state bytes the traced rounds' DECODE
  steps must read and write (``ssm_state_bytes``: each Mamba layer's state once
  in and once out a decoded token) / peak HBM bandwidth / the device time under
  ``args["scope"]`` inside the rounds' decode spans (``args["span"]``), in %.
  Bound: memory.
* ``ssm_scan_roofline``: the bytes the scan over the traced rounds' PROMPTS
  must move (``ssm_scan_bytes``: a token's inputs and output, the carried state
  once a segment; a prompt is prefilled once for its group of candidates) /
  peak HBM bandwidth / the device time under ``args["scope"]`` inside the
  rounds' prefill spans, in %. Bound: memory: the scan multiplies nothing on
  the matrix unit. A program that moves a state a token, or writes a chunk's
  states out, reads low.

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
    if not hasattr(counts, "ssm_state_bytes"):
        return None  # another family's counts: it has no such layers
    what = args["what"]
    if what == "ssm_step_roofline":
        needed = sum(cache_bytes(counts.ssm_state_bytes, model, u,
                                 kv_bytes=layout["kv_bytes"]) for u in units)
    elif what == "ssm_scan_roofline":
        # consecutive rows of a group share a prompt, prefilled once
        needed = sum(
            counts.ssm_scan_bytes(model, u["prompt_lens"][:: u.get("group_size") or 1],
                                  act_bytes=layout["weight_bytes"])
            for u in units)
    else:
        raise ValueError(f"ssm_work cannot read {what!r}")
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
