"""Reader ``latent_moe_work``: what the routed experts and latent attention of
a ``deepseek_v3`` cell did in decode, against what they had to
(``perfbench/latent_moe_counts.py``, or whatever module the cell's
configuration names under ``counts``).

``args["what"]``:

* ``moe_experts_roofline`` / ``latent_attn_roofline``: the bytes the traced
  rounds' DECODE steps must read there (every expert held, once a step; the
  latent rows each decoded token attends over, a shared prompt's once a
  group: ``required_work.cache_bytes``) / peak HBM bandwidth / the
  device time under ``args["scope"]`` inside the rounds' decode spans
  (``args["span"]``), in %. Bound: memory. The window's cut is
  ``trace_scopes.seconds_in_spans``.
* ``expert_load_imbalance``: the program's own counters, the fullest expert's
  pairs over the mean expert's (``max_load * experts / assignments``), over
  everything the process ran.

A program without these scopes, spans or counters (the parent of the PR that
added them), an untraced run, a configuration whose ``counts`` has no such
functions and a call without a run all give None.
"""

from __future__ import annotations

from perfbench import spec, trace_scopes
from perfbench.readers.required_work import cache_bytes


def read(observed, args, ctx):
    if ctx is None:
        return None
    what = args["what"]
    model = observed.get("model")
    if what == "expert_load_imbalance":
        try:
            from distrl_llm_tpu import telemetry

            counters = telemetry.observe_snapshot()["counters"]
        except (ImportError, AttributeError, KeyError):  # no such registry: no counter
            return None
        pairs, fullest = counters.get(args["assignments"]), counters.get(args["max_load"])
        if not pairs or not fullest or not model or not model.get("n_routed_experts"):
            return None
        return fullest * model["n_routed_experts"] / pairs
    peaks, layout, units = (
        observed.get("peaks"), observed.get("rollout"), observed.get("traced_units"))
    if peaks is None or model is None or not layout or not units:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", "roofline"))
    if not hasattr(counts, "expert_bytes_per_step"):
        return None  # another family's counts: it has no such layers
    if what == "moe_experts_roofline":
        needed = sum(u["steps_dispatched"] for u in units) * counts.expert_bytes_per_step(
            model, weight_bytes=layout["weight_bytes"])
    elif what == "latent_attn_roofline":
        needed = sum(
            cache_bytes(counts.latent_attn_bytes, model, u, kv_bytes=layout["kv_bytes"])
            for u in units)
    else:
        raise ValueError(f"latent_moe_work cannot read {what!r}")
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
