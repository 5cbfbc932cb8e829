"""What every driver shares: the run's context, the compile log, the measured
window, the traced sub-window and the device's memory peak.

A driver (``drivers/<kind>.py``) exports ``run(ctx) -> RunResult``. It builds
the system under test from the cell's configuration and traffic, warms up the
shapes the cell uses, checks the outputs against the reference, calls
``ctx.begin_window()`` and then measures whole units of work until
``ctx.seconds`` have passed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, NoReturn

from perfbench import spec

SYNC_EVENT = "perfbench.sync"  # host annotation that ties the trace's clock to the wall clock


def emit(note: str, /, **fields) -> None:
    """One earlier line of output: a JSON object that is NOT the result."""
    print(json.dumps({"note": note, **fields}, default=str), flush=True)


class CompileLog:
    """Every program JAX builds from now on, compiled or loaded from the
    persistent cache: (name, seconds, when it finished), off JAX's own
    monitoring events. Copy of ``chip_smoke.CompileLog``, with the
    persistent cache's hit and miss events counted beside it."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.events: list[tuple[str, float, float]] = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.BUILD:
            self.events.append((
                str(kw.get("fun_name", "?")), float(duration), time.perf_counter(),
            ))

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, t0: float, t1: float) -> dict[str, Any]:
        """Programs whose build finished in [t0, t1) of ``perf_counter``."""
        new = [(n, s) for n, s, done in self.events if t0 <= done < t1]
        return {
            "programs": len(new),
            "seconds": sum(s for _, s in new),
            "names": sorted({n for n, _ in new}),
        }


@dataclasses.dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict[str, float]  # name -> value, as measured
    #: what the readers take per-layer metrics from (see readers/*.py)
    observed: dict[str, Any]
    #: why ``correct`` is what it is: the comparison's numbers, printed earlier
    check: dict[str, Any] = dataclasses.field(default_factory=dict)


class RunContext:
    """One run of one cell. ``t0`` is the process's start on ``perf_counter``."""

    def __init__(self, cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
                 devices: list, t0: float):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices  # the chips this cell runs on
        self.on_tpu = devices[0].platform == "tpu"
        self.t0 = t0
        self.window_start: float | None = None
        self.window_end: float | None = None
        self.tracer = Tracer(cell.name) if trace else None

    # ------------------------------------------------------------- the window

    def begin_window(self) -> float:
        """End of set-up: everything before this is ``setup_s``."""
        self.window_start = time.perf_counter()
        return self.window_start

    def end_window(self) -> None:
        """The last unit has ended: what is built after this (the driver's own
        end-of-run checks) is not a compile inside the window."""
        self.window_end = time.perf_counter()

    @property
    def setup_s(self) -> float:
        assert self.window_start is not None, "begin_window() was never called"
        return self.window_start - self.t0

    @property
    def untraced_seconds(self) -> float:
        """How long the untraced part of the window lasts. A traced run
        measures half the window untraced (the per-layer numbers that need no
        trace come from there, undisturbed) and then traces whole units."""
        return self.seconds / 2.0 if self.trace else self.seconds

    def measure_units(self, unit: Callable[[int], dict[str, Any]],
                      seconds: float) -> list[dict[str, Any]]:
        """Whole units of work, back to back, until ``seconds`` have passed:
        a unit that started inside the window runs to its end. ``unit(i)``
        ends in a host fetch and returns what it counted."""
        units: list[dict[str, Any]] = []
        begin = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            if t0 - begin >= seconds and units:
                return units
            counted = unit(len(units))
            units.append({**counted, "t0": t0, "t1": time.perf_counter()})

    def measure_count(self, unit: Callable[[int], dict[str, Any]],
                      n: int) -> list[dict[str, Any]]:
        """Exactly ``n`` whole units, back to back."""
        units: list[dict[str, Any]] = []
        for i in range(n):
            t0 = time.perf_counter()
            counted = unit(i)
            units.append({**counted, "t0": t0, "t1": time.perf_counter()})
        return units

    def trace_units(self, unit: Callable[[int], dict[str, Any]],
                    n: int) -> list[dict[str, Any]]:
        """``n`` whole units under the profiler (none in an untraced run)."""
        if self.tracer is None:
            return []
        self.tracer.start()
        try:
            return self.measure_count(unit, n)
        finally:
            self.tracer.stop()

    def memory_peaks(self) -> list[int | None]:
        """``peak_bytes_in_use`` of each chip of the cell, as the runtime reports it."""
        out = []
        for d in self.devices:
            stats = d.memory_stats()
            out.append(int(stats["peak_bytes_in_use"]) if stats and
                       "peak_bytes_in_use" in stats else None)
        return out


class Tracer:
    """A ``jax.profiler`` trace of a sub-window, with the program's own host
    spans (``telemetry.span``) turned on for exactly that long, and a sync
    annotation that lets the reduction lay both on one clock."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(spec.ROOT, ".perfbench_out", "trace", cell_name)
        self.window_wall_ns: tuple[int, int] | None = None
        self.sync_wall_ns: int | None = None
        self.host_spans: list[tuple[str, int, int]] = []  # (name, t0, t1) wall ns

    def start(self) -> None:
        import jax

        from distrl_llm_tpu import telemetry

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer slows the host it measures
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.sync_wall_ns = time.time_ns()
        with jax.profiler.TraceAnnotation(SYNC_EVENT):
            pass
        telemetry.configure(enabled=True)
        self.window_wall_ns = (time.time_ns(), 0)

    def stop(self) -> None:
        import jax

        from distrl_llm_tpu import telemetry

        assert self.window_wall_ns is not None
        self.window_wall_ns = (self.window_wall_ns[0], time.time_ns())
        telemetry.configure(enabled=False)
        jax.profiler.stop_trace()
        lo, hi = self.window_wall_ns
        for ev in telemetry.recent_events(1_000_000):
            if ev.get("ph") != "X":
                continue
            t0 = int(ev["ts"]) * 1000
            t1 = t0 + int(ev["dur"]) * 1000
            if t1 >= lo and t0 <= hi:
                self.host_spans.append((str(ev["name"]), t0, t1))

    @contextlib.contextmanager
    def span(self, name: str):
        """The harness's own span round a call into a layer: recorded on the
        wall clock for the gap attribution, and as a ``TraceAnnotation`` so
        it shows in the profile too."""
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.time_ns()
            try:
                yield
            finally:
                self.host_spans.append((name, t0, time.time_ns()))

    def xplane_path(self) -> str:
        found = sorted(glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
        ))
        if not found:
            raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {self.dir}")
        return found[-1]


def layer_span(ctx: RunContext, name: str):
    """``ctx.tracer.span(name)`` in a traced run, nothing otherwise."""
    return ctx.tracer.span(name) if ctx.tracer is not None else contextlib.nullcontext()


def rate(tokens: float, units: list[dict[str, Any]]) -> float:
    """``tokens`` over the wall seconds from the first unit's start to the last
    unit's end: the units run back to back, so nothing between them is lost."""
    return tokens / (units[-1]["t1"] - units[0]["t0"])


def fail(message: str) -> NoReturn:
    """Refuse to run: a message on stderr, no result line, exit code 3."""
    print(f"perfbench: {message}", file=sys.stderr, flush=True)
    raise SystemExit(3)
