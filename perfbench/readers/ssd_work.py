"""Reader ``ssd_work``: what the chunked form of a ``nemotron_h`` cell's Mamba-2
layers did in prefill, against what it had to (``perfbench/ssd_moe_counts.py``,
or whatever module the cell's configuration names under ``counts``).

``args["what"]``:

* ``ssd_chunk_roofline``: what the chunked form needs over the traced rounds'
  PROMPTS (a prompt is prefilled once for its group of candidates) at the
  chip's peak, over the device time under ``args["scope"]`` inside the rounds'
  prefill spans (``args["span"]``), in %. The need is the LARGER of two times
  by the counts module's own arithmetic: ``ssd_chunk_flops`` at the matrix
  unit's bf16 peak (each product counted once, whatever precision the program
  multiplies in) and ``ssd_chunk_bytes`` at the peak HBM bandwidth.

(The one-token step's share is ``ssm_work``'s ``ssm_step_roofline``, which reads
this counts module's ``ssm_state_bytes`` as it reads a ``jamba`` cell's.)

A program without this scope or these spans (the parent of the PR that added
them), an untraced run, a configuration whose ``counts`` has no such functions
and a call without a run all give None.
"""

from __future__ import annotations

from perfbench import spec, trace_scopes


def read(observed, args, ctx):
    if ctx is None:
        return None
    peaks, model = observed.get("peaks"), observed.get("model")
    layout, units = observed.get("rollout"), observed.get("traced_units")
    if peaks is None or model is None or not layout or not units:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", "roofline"))
    if not hasattr(counts, "ssd_chunk_bytes"):
        return None  # another family's counts: it has no such layers
    if args["what"] != "ssd_chunk_roofline":
        raise ValueError(f"ssd_work cannot read {args['what']!r}")
    # consecutive rows of a group share a prompt, prefilled once
    prompts = [u["prompt_lens"][:: u.get("group_size") or 1] for u in units]
    needed = max(
        sum(counts.ssd_chunk_flops(model, p) for p in prompts) / peaks["bf16_flops_per_s"],
        sum(counts.ssd_chunk_bytes(model, p, act_bytes=layout["weight_bytes"])
            for p in prompts) / peaks["hbm_bytes_per_s"])
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / seconds
