"""Reader ``delta_moe_work``: what the delta-rule layers, the softmax layers'
paged kernel and the expert share of a ``solar_open2`` cell did, against what
they had to (``perfbench/delta_moe_counts.py``, or whatever module the cell's
configuration names under ``counts``).

``args["what"]``:

* ``delta_step_roofline``: the float32 state bytes the traced rounds' DECODE
  steps must read and write (``delta_state_bytes``) / peak HBM bandwidth / the
  device time under ``args["scope"]`` inside the rounds' decode spans
  (``args["span"]``), in %. Bound: memory.
* ``delta_chunk_roofline``: the operations the chunked rule needs for the
  traced rounds' PROMPTS (``delta_chunk_flops``; a prompt is prefilled once for
  its group of candidates) / peak bf16 FLOP/s / the device time under
  ``args["scope"]`` inside the rounds' prefill spans, in %. Bound: compute;
  float32 products at full precision are several bf16 passes each, so this
  reads low by construction.
* ``softmax_paged_roofline``: the K/V bytes the softmax layers' decode must
  read (``softmax_kv_bytes``) / peak HBM bandwidth / the device time of the
  paged kernel's events (operations matching ``args["regex"]``), in %. Bound:
  memory.
* ``expert_held_share``: the program's own counters, the token-expert pairs of
  experts HELD here over the pairs the router chose over all its experts, in %
  (12.5 for one chip of eight under an even router), over everything the
  process ran.

A program without these scopes, spans or counters (the parent of the PR that
added them), an untraced run, a configuration whose ``counts`` has no such
functions and a call without a run all give None.
"""

from __future__ import annotations

from perfbench import spec, trace_scopes
from perfbench.readers.required_work import cache_bytes
from perfbench.readers.trace_ops import matching_seconds


def read(observed, args, ctx):
    if ctx is None:
        return None
    what = args["what"]
    if what == "expert_held_share":
        try:
            from distrl_llm_tpu import telemetry

            counters = telemetry.observe_snapshot()["counters"]
        except (ImportError, AttributeError, KeyError):  # no such registry: no counter
            return None
        held, routed = counters.get(args["held"]), counters.get(args["routed"])
        if not held or not routed:
            return None
        return 100.0 * held / routed
    peaks, model = observed.get("peaks"), observed.get("model")
    layout, units = observed.get("rollout"), observed.get("traced_units")
    if peaks is None or model is None or not layout or not units:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", "roofline"))
    if not hasattr(counts, "delta_state_bytes"):
        return None  # another family's counts: it has no such layers
    if what == "softmax_paged_roofline":
        trace = observed.get("trace")
        if not trace or not trace.get("devices"):
            return None
        seconds = matching_seconds(trace, args["regex"])
        if seconds <= 0:
            return None
        needed = sum(cache_bytes(counts.softmax_kv_bytes, model, u,
                                 kv_bytes=layout["kv_bytes"]) for u in units)
        return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
    if what == "delta_step_roofline":
        needed = sum(cache_bytes(counts.delta_state_bytes, model, u,
                                 kv_bytes=layout["kv_bytes"]) for u in units)
        peak = peaks["hbm_bytes_per_s"]
    elif what == "delta_chunk_roofline":
        # consecutive rows of a group share a prompt, prefilled once
        needed = sum(
            counts.delta_chunk_flops(model, u["prompt_lens"][:: u.get("group_size") or 1])
            for u in units)
        peak = peaks["bf16_flops_per_s"]
    else:
        raise ValueError(f"delta_moe_work cannot read {what!r}")
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peak / seconds
