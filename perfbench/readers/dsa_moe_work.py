"""Reader ``dsa_moe_work``: what the learned index of a ``glm_moe_dsa`` cell
and the attention behind it did in decode, against what they had to
(``perfbench/dsa_moe_counts.py``, or whatever module the cell's configuration
names under ``counts``).

``args["what"]``:

* ``index_score_roofline`` / ``indexed_attn_roofline``: the bytes the traced
  rounds' DECODE steps must read there (the index keys of every visible token,
  a shared prompt's once a group of candidates: ``index_key_bytes``; the
  latent rows of the tokens each row chose, a row: ``indexed_attn_bytes``) /
  peak HBM bandwidth / the device time under ``args["scope"]`` inside the
  rounds' decode spans (``args["span"]``), in %. Bound: memory. The window's
  cut is ``trace_scopes.seconds_in_spans``.
* ``index_attended_share``: the program's own counters, the tokens the decode
  steps attended over the tokens they saw (``args["attended"]`` /
  ``args["visible"]``, in units of 128 tokens), in %, over everything the
  process ran. 100 below ``index_topk`` tokens of context, and the day a layer
  silently attends everything.

A program without these scopes, spans or counters (the parent of the PR that
added them), an untraced run, a configuration whose ``counts`` has no such
functions and a call without a run all give None.
"""

from __future__ import annotations

from perfbench import spec, trace_scopes
from perfbench.readers.required_work import cache_bytes

#: ``what`` -> the counts module's function of the bytes
BYTES = {"index_score_roofline": "index_key_bytes",
         "indexed_attn_roofline": "indexed_attn_bytes"}


def read(observed, args, ctx):
    if ctx is None:
        return None
    what = args["what"]
    if what == "index_attended_share":
        try:
            from distrl_llm_tpu import telemetry

            counters = telemetry.observe_snapshot()["counters"]
        except (ImportError, AttributeError, KeyError):  # no such registry: no counter
            return None
        attended, visible = counters.get(args["attended"]), counters.get(args["visible"])
        if not attended or not visible:
            return None
        return 100.0 * attended / visible
    if what not in BYTES:
        raise ValueError(f"dsa_moe_work cannot read {what!r}")
    peaks, model = observed.get("peaks"), observed.get("model")
    layout, units = observed.get("rollout"), observed.get("traced_units")
    if peaks is None or model is None or not layout or not units:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", "roofline"))
    count = getattr(counts, BYTES[what], None)
    if count is None:
        return None  # another family's counts: it has no index
    needed = sum(cache_bytes(count, model, u, kv_bytes=layout["kv_bytes"]) for u in units)
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
