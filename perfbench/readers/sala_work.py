"""Reader ``sala_work``: what the block-sparse and lightning layers of a
MiniCPM-SALA cell did, against what they had to (``perfbench/sala_counts.py``,
or whatever module the cell's configuration names under ``counts``).

``args["what"]``:

* ``linear_attn_roofline`` / ``sparse_attn_roofline``: the bytes the traced
  rounds' DECODE steps must move in those layers (the state read and written;
  the chosen blocks' K/V and the pooled keys) / peak HBM bandwidth / the
  device time under ``args["scope"]`` (a regex over the scope rows) inside the
  rounds' decode spans (``args["span"]``: the program's host span round a
  round's decode loop, which starts after the prefill has finished), in %.
  Bound: memory. Prefill runs the same scopes and is left out by the window.
* ``sparse_attended_share``: the program's own counters, blocks attended over
  blocks visible, in %, over everything the process ran (each round counts
  the same work, so the share is a round's).

A program without these scopes, spans or counters (the parent of the PR that
added them), an untraced run, and a call without a run all give None.

The decode window's cut is ``trace_scopes.seconds_in_spans``.
"""

from __future__ import annotations

from perfbench import spec, trace_scopes


def read(observed, args, ctx):
    if ctx is None:
        return None
    what = args["what"]
    if what == "sparse_attended_share":
        try:
            from distrl_llm_tpu import telemetry

            counters = telemetry.observe_snapshot()["counters"]
        except (ImportError, AttributeError, KeyError):  # no such registry: no counter
            return None
        attended, visible = counters.get(args["attended"]), counters.get(args["visible"])
        if not attended or not visible:
            return None
        return 100.0 * attended / visible
    peaks, model = observed.get("peaks"), observed.get("model")
    layout, units = observed.get("rollout"), observed.get("traced_units")
    if peaks is None or model is None or not layout or not units:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", "roofline"))
    if not hasattr(counts, "linear_attn_bytes"):
        return None  # another family's counts: it has no such layers
    if what == "linear_attn_roofline":
        needed = sum(
            counts.linear_attn_bytes(model, u["prompt_lens"], u["gen_lens"])
            for u in units)
    elif what == "sparse_attn_roofline":
        needed = sum(
            counts.sparse_attn_bytes(model, u["prompt_lens"], u["gen_lens"],
                                     kv_bytes=layout["kv_bytes"])
            for u in units)
    else:
        raise ValueError(f"sala_work cannot read {what!r}")
    seconds = trace_scopes.seconds_in_spans(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
