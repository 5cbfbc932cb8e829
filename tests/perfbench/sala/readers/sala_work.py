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

It lives beside the rehearsal's files, with the six metrics that name it
(``tests/perfbench/sala_spec.py`` says why), until a ``benchmark`` PR can
declare them in ``BENCHMARK.json``; then it moves to ``perfbench/readers/``.
"""

from __future__ import annotations

from perfbench import spec, trace_reduce, trace_scopes

_LOADED: dict[str, dict] = {}


def _decode_scope_seconds(ctx, scope: str, span: str) -> float | None:
    from perfbench import harness

    tracer = getattr(ctx, "tracer", None)
    if tracer is None or tracer.window_wall_ns is None:
        return None
    spans = [(t0, t1) for name, t0, t1 in tracer.host_spans if name == span]
    if not spans:
        return None
    try:
        path = tracer.xplane_path()
    except FileNotFoundError:
        return None
    if path not in _LOADED:
        trace = trace_scopes.load(path)
        host = trace_reduce.load_xplane(path, keep_host_events=(harness.SYNC_EVENT,))
        try:
            offset = trace_reduce.sync_offset_ns(
                host, harness.SYNC_EVENT, tracer.sync_wall_ns)
        except LookupError:
            offset = None
        _LOADED[path] = {"trace": trace, "offset": offset}
    held = _LOADED[path]
    if held["offset"] is None or not any(p["events"] for p in held["trace"]["planes"]):
        return None
    vocabulary = spec.load_scope_names(ctx.cell.paths)
    seconds = 0.0
    for t0, t1 in spans:
        tab = trace_scopes.table(
            held["trace"], vocabulary, (t0 - held["offset"], t1 - held["offset"]))
        if tab is not None:
            seconds += trace_scopes.seconds_under(tab, scope)
    return seconds if seconds > 0 else None


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
    seconds = _decode_scope_seconds(ctx, args["scope"], args["span"])
    if seconds is None:
        return None
    return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
