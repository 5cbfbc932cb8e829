#!/usr/bin/env python3
"""One cell of ``BENCHMARK.json``, once, on the TPU this process is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` in a traced run), then ``check``: each number ``correct`` was
decided by beside its limit, which are also the last lines of standard error. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics. Everything else worth keeping is
on earlier lines, each a JSON object with a ``note``.

There is no CPU fallback. With no TPU, or fewer chips than the cell asks for,
the process exits non-zero and prints no result. ``JAX_PLATFORMS=cpu`` is
accepted only for a configuration whose file says ``"rehearsal": true`` (the
tiny one the tests bring), and then reports counts and no time, rate,
utilization or share.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()  # as near to the process's start as Python gets

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from perfbench import correct, harness, roofline, spec, trace_reduce  # noqa: E402

#: units a CPU rehearsal may report: what the program counts, never a device number
COUNT_UNITS = ("count",)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (the tests' tiny one); "
                         "default: the one at the root of the checkout")
    return ap.parse_args(argv)


def _enable_compile_cache(jax, on_tpu: bool) -> str | None:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says if it is set (JAX reads it itself), else ``<checkout>/.jax_cache``, a
    fixed path (the path is part of the cache's key). Every program is kept,
    however quickly it compiled: a cell builds some 150 sub-second programs,
    which JAX's default floor of one second would compile again in every run."""
    if not on_tpu:
        return None  # a CPU rehearsal compiles in seconds and keeps nothing
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(spec.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def _devices(jax, cell: spec.Cell):
    """The cell's chips, or no run at all."""
    devices = jax.devices()
    platform = devices[0].platform
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform != "tpu" and not (
        platform == "cpu" and asked_cpu and cell.config.get("rehearsal")
    ):
        harness.fail(
            f"no TPU: JAX found {len(devices)} {platform} device(s). The benchmark "
            "measures the accelerator and does not fall back."
        )
    if len(devices) < cell.chips:
        harness.fail(
            f"cell {cell.name!r} needs {cell.chips} chip(s); JAX found {len(devices)}"
        )
    return devices, devices[: cell.chips]


def _layer_metrics(cell: spec.Cell, observed, ctx) -> dict:
    """Each per-layer metric of the cell through its own reader. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in cell.per_layer:
        metric = spec.load_layer_metric(cell.paths, entry["name"])
        if not ctx.on_tpu and metric["unit"] not in COUNT_UNITS:
            continue
        reader = spec.load_module(cell.paths, "readers", metric["reader"])
        value = reader.read(observed, metric.get("args", {}), ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def _unit_seconds(units) -> list:
    """Wall seconds of each unit: a step record carries ``step_s``, any other
    unit its own start and end."""
    return [u["step_s"] if "step_s" in u else u["t1"] - u["t0"] for u in units]


def _reduce_trace(ctx: harness.RunContext) -> dict | None:
    """The traced sub-window, reduced; None where no device plane was traced."""
    tracer = ctx.tracer
    t0 = time.perf_counter()
    trace = trace_reduce.load_xplane(
        tracer.xplane_path(), keep_host_events=(harness.SYNC_EVENT,)
    )
    for line in trace_reduce.describe(trace):
        harness.emit("trace_line", **line)
    try:
        offset = trace_reduce.sync_offset_ns(
            trace, harness.SYNC_EVENT, tracer.sync_wall_ns
        )
    except LookupError as e:
        harness.emit("trace", problem=str(e))
        return None
    reduced = trace_reduce.reduce(
        trace, window_wall_ns=tracer.window_wall_ns, host_spans=tracer.host_spans,
        offset_ns=offset,
    )
    harness.emit(
        "trace", window_s=reduced.get("window_s"), devices=reduced.get("devices"),
        host_spans=len(tracer.host_spans), reduce_s=time.perf_counter() - t0,
    )
    return reduced if reduced.get("devices") else None


def run(args, t0: float, scrubbed: list[str]) -> dict:
    """The run, up to the result object. Raises ``SystemExit`` to refuse.
    ``t0`` is the process's start on ``perf_counter``; ``scrubbed`` names the
    DISTRL_* switches that were unset for the run."""
    bench = spec.load_benchmark(args.benchmark)
    cell = spec.load_cell(bench, args.workload)

    import jax

    devices, chips = _devices(jax, cell)
    on_tpu = chips[0].platform == "tpu"
    cache_dir = _enable_compile_cache(jax, on_tpu)
    # the peaks come from the benchmark's own table; a kind it lacks is an error
    peaks = roofline.peaks_for_kind(chips[0].device_kind) if on_tpu else None
    compiles = harness.CompileLog()
    ctx = harness.RunContext(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=chips, t0=t0,
    )
    harness.emit(
        "run", workload=cell.name, config=cell.config_name, traffic=cell.traffic_name,
        kind=cell.traffic["kind"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, jax=jax.__version__, compile_cache_dir=cache_dir,
        distrl_switches_unset=scrubbed,
        devices=[str(d) for d in chips],
    )
    try:
        driver = spec.load_module(cell.paths, "drivers", cell.traffic["kind"])
        result = driver.run(ctx)
        if ctx.window_end is None:  # a driver that left the window open
            ctx.end_window()
    finally:
        compiles.close()

    from perfbench import assembly

    observed = result.observed
    observed["compiles"] = {
        "setup": compiles.between(t0, ctx.window_start),
        "window": compiles.between(ctx.window_start, ctx.window_end),
        "cache_hits": compiles.hits, "cache_misses": compiles.misses,
    }
    observed["memory_peaks"] = ctx.memory_peaks()
    observed["peaks"] = peaks
    observed["chips"] = cell.chips
    observed["model"] = assembly.model_sizes(assembly.model_config(cell.config))
    harness.emit(
        "compiles", setup=observed["compiles"]["setup"]["programs"],
        setup_seconds=observed["compiles"]["setup"]["seconds"],
        window=observed["compiles"]["window"],
        cache_hits=compiles.hits, cache_misses=compiles.misses,
    )
    units = observed.get("units", [])
    harness.emit(
        "window", units=len(units), traced_units=len(observed.get("traced_units", [])),
        unit_seconds=_unit_seconds(units)[:32], setup_s=ctx.setup_s,
        memory_peaks=observed["memory_peaks"],
    )

    device = {
        "platform": chips[0].platform, "kind": chips[0].device_kind,
        "count": len(devices),
    }
    known = [p for p in observed["memory_peaks"] if p is not None]
    if known:
        device["memory_peak_bytes"] = max(known)
    line = {
        "correct": bool(result.correct) and observed["compiles"]["window"]["programs"] == 0,
        "attempted": int(result.attempted), "failed": int(result.failed),
    }
    if not args.trace:
        metrics = {"setup_s": ctx.setup_s, **result.end_to_end}
        units_of = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {
            name: {"value": float(value), "unit": units_of[name]}
            for name, value in metrics.items() if name in units_of and on_tpu
        }
    else:
        reduced = _reduce_trace(ctx)
        observed["trace"] = reduced
        line["metrics"] = _layer_metrics(cell, observed, ctx)
        if reduced is not None and on_tpu:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {
                "device_ops": trace_reduce.ranked(reduced["ops_s"]),
                "idle_gaps": trace_reduce.ranked(reduced["gaps_s"]),
            }
            traced = observed.get("traced_units", [])
            harness.emit(
                "tracing_overhead",
                untraced_unit_s=_unit_seconds(units)[:32],
                traced_unit_s=_unit_seconds(traced),
                end_to_end_in_this_run=result.end_to_end,
            )
    line["device"] = device
    # last, each number that decided ``correct`` beside its limit
    line["check"] = correct.compared(
        result.check, observed["compiles"]["window"]["programs"])
    return line


def main(argv=None, t0: float | None = None) -> int:
    args = _parse(argv)
    # the program is measured as it ships: every DISTRL_* switch unset
    scrubbed = {k: os.environ.pop(k) for k in list(os.environ) if k.startswith("DISTRL_")}
    try:
        line = run(args, _PROCESS_START if t0 is None else t0, sorted(scrubbed))
    finally:
        os.environ.update(scrubbed)
    for name, held in line["check"].items():  # the last lines of standard error
        said = (f"{held['value']!r} (at {held['at']} {held['limit']!r})"
                if isinstance(held, dict) else held)
        print(f"perfbench: check {name} {said}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
