"""Continuous observability plane (ISSUE 8): live metrics endpoint, fleet
aggregation, measured resource attribution, and an anomaly sentinel with a
flight recorder.

PR 1's telemetry is post-hoc — spans, counters, and Perfetto traces you read
after the run. A long-running service and a multi-host elastic fleet need
*live*, *attributed* telemetry. This module adds, on top of
``telemetry.py``'s registry:

* **Live export** — :class:`MetricsServer` serves the process's cumulative
  registry (``telemetry.observe_snapshot``) over HTTP as Prometheus text
  (``/metrics``) and a JSON snapshot (``/metrics.json``), from the driver
  and from every ``worker_main --metrics-port`` process.
* **Fleet aggregation** — workers piggyback their registry snapshot on
  control-plane RESULT frames (the same channel PR 1's span blobs ride);
  :class:`FleetAggregator` folds those per-worker snapshots plus the
  DriverClient's health/rejoin state into ``fleet/*`` series: aggregate
  tok/s, per-worker health, rejoin epoch — the fleet-level rows ROADMAP
  item 4 needs.
* **Measured attribution** — per-phase HBM watermarks sampled from
  ``jax.Device.memory_stats()`` at span boundaries (the PhaseSpans hook), a
  compile/retrace tracker keyed by jitted-fn × shape signature (silent
  retrace storms become a counter) — both surfaced on the endpoint.
* **Anomaly sentinel + flight recorder** — a bounded in-memory ring of
  recent step records; deterministic triggers (NaN/Inf loss, reward
  collapse, staleness blowup, tok/s regression vs a running EMA, HBM
  watermark breach) dump the ring + span tail + config/plan snapshot into a
  per-incident directory (and request a guarded ``TraceProfiler`` capture
  window), so the first production incident arrives with its own evidence.

Contract: same as PR 1 — near-zero cost when off. Nothing here runs unless
a flag arms it (``--metrics_port`` / ``--sentinel`` / ``--flight_recorder_
dir`` / worker ``--metrics-port`` / ``DISTRL_OBS=1``), and the only
always-on additions are counter bumps at compile sites (inherently slow
paths) and one counter per generation wave.
"""

from __future__ import annotations

import json
import logging
import math
import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Mapping

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.learn_obs import (
    LEARN_CAP_FRAC,
    LEARN_CLIP_FRAC,
    LEARN_ENTROPY,
    LEARN_GRAD_NORM_TOTAL,
    LEARN_KL,
)
from distrl_llm_tpu.serving_obs import (
    FLEET_SERVING_QUEUE_WAIT_MAX_MS,
    FLEET_SERVING_TTFT_MAX_MS,
    SERVING_QUEUE_WAIT_MS,
    SERVING_TTFT_MS,
    fold_fleet_serving,
)

log = logging.getLogger(__name__)

# ------------------------------------------------------------- series names
# (pinned, with their types, in tests/test_telemetry.py)

OBS_GEN_TOKENS = "obs/gen_tokens"            # counter: engine-accounted tokens
OBS_HBM_LIVE = "obs/hbm_live_bytes"          # gauge: bytes_in_use at sample
OBS_HBM_PEAK = "obs/hbm_peak_bytes"          # gauge: device peak watermark
OBS_COMPILES = "obs/compiles"                # counter: tracked compile events
OBS_RETRACES = "obs/retraces"                # counter: compiles BEYOND the
#                                              first per (fn, signature) key
OBS_LEARNER_IDLE = "obs/learner_idle_frac"   # gauge: blocked-on-data share
OBS_WEIGHT_SYNC_MS = "obs/weight_sync_ms"    # gauge: push_weights latency
OBS_INCIDENTS = "obs/incidents"              # counter: flight-recorder dumps

FLEET_TOK_S = "fleet/tok_s"                  # gauge: aggregate worker tok/s
FLEET_GEN_TOKENS = "fleet/gen_tokens_total"  # gauge: cumulative worker tokens
FLEET_WORKERS_HEALTHY = "fleet/workers_healthy"  # gauge
FLEET_WORKERS_TOTAL = "fleet/workers_total"      # gauge
FLEET_REJOIN_EPOCH = "fleet/rejoin_epoch"        # gauge
# elastic fleet (ISSUE 20): the supervisor/autoscaler publish these — the
# constants live here with the rest of the fleet/* series (single-owner
# registry discipline, GC2xx), imported by distributed/fleet.py
FLEET_TARGET_WORKERS = "fleet/target_workers"    # gauge: autoscaler setpoint
FLEET_SCALE_EVENTS = "fleet/scale_events"        # counter: grow/shrink events

# engine-side LoraMailbox push→swap latency (engine/engine.py observes it)
SWAP_LATENCY_MS = "engine/swap_latency_ms"   # histogram


def _jsonable(v: Any) -> Any:
    try:
        json.dumps(v)
        return v
    except (TypeError, ValueError):
        try:
            return float(v)
        except (TypeError, ValueError):
            return str(v)


# ------------------------------------------------------------ HBM sampling


def hbm_stats(device=None) -> dict[str, float] | None:
    """``memory_stats()`` of ``device`` (the first local device by default),
    or None when the backend exposes none (CPU hosts). ``DISTRL_OBS_FAKE_HBM``
    (a JSON object) substitutes deterministic numbers for tests/smokes."""
    fake = os.environ.get("DISTRL_OBS_FAKE_HBM")
    if fake:
        try:
            stats = json.loads(fake)
            return dict(stats) if isinstance(stats, dict) else None
        except ValueError:
            return None
    try:
        import jax

        stats = (device or jax.local_devices()[0]).memory_stats()
    except Exception:  # noqa: BLE001 — no backend at all
        return None
    if not stats:
        return None
    return {k: float(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


def hbm_free(device=None) -> tuple[int, int, int] | None:
    """``(bytes_limit, bytes_in_use, largest free block)`` of ``device`` (the
    first local device by default) as it stands now, or None where the backend
    reports no limit (the CPU). The one reading behind every "does it still
    fit" decision: the engines' decode view (``engine._view_shortfall``) and
    what the learner's rematerialised scan keeps (``learner/remat.py``). A
    backend that does not report its largest free block gets all that is not
    in use."""
    stats = hbm_stats(device)
    if not stats or not stats.get("bytes_limit"):
        return None
    limit, in_use = int(stats["bytes_limit"]), int(stats.get("bytes_in_use", 0))
    return limit, in_use, int(stats.get("largest_free_block_bytes", limit - in_use))


_phase_mu = threading.Lock()
_phase_hbm: dict[str, dict[str, float]] = {}


def _on_phase(phase: str) -> None:
    """PhaseSpans-exit hook (installed by ObsPlane): sample device memory at
    the span boundary, publish live/peak gauges, and keep the per-phase
    high-watermark table the flight recorder and trace_report read."""
    stats = hbm_stats()
    if not stats:
        return
    live = float(stats.get("bytes_in_use", 0.0))
    peak = float(stats.get("peak_bytes_in_use", live) or live)
    telemetry.gauge_set(OBS_HBM_LIVE, live)
    telemetry.gauge_set(OBS_HBM_PEAK, peak)
    # per-phase series so trace_report can attribute the HBM budget: the
    # name set is bounded by the driver's phase vocabulary (4–5 names)
    telemetry.gauge_set(f"{OBS_HBM_PEAK}/{phase}", peak)
    with _phase_mu:
        w = _phase_hbm.setdefault(
            phase, {"live_max": 0.0, "peak_max": 0.0, "samples": 0}
        )
        w["live_max"] = max(w["live_max"], live)
        w["peak_max"] = max(w["peak_max"], peak)
        w["samples"] += 1


def phase_hbm() -> dict[str, dict[str, float]]:
    """Per-phase HBM high-watermark table accumulated by the phase hook."""
    with _phase_mu:
        return {k: dict(v) for k, v in _phase_hbm.items()}


# -------------------------------------------------- compile / retrace table

_compile_mu = threading.Lock()
_compile_counts: dict[tuple, int] = {}


def note_compile(fn: str, signature: Any = ()) -> None:
    """Record one compile of ``fn`` at ``signature`` (a shape-ish key).
    First compile per key bumps ``obs/compiles``; every later compile of
    the SAME key additionally bumps ``obs/retraces`` — the silent-retrace-
    storm signal. Always on: compiles are inherently seconds-long, so the
    dict write is free by comparison."""
    try:
        key = (fn, signature if isinstance(signature, tuple)
               else tuple(signature) if isinstance(signature, list)
               else (signature,))
        hash(key)
    except TypeError:
        key = (fn, repr(signature))
    with _compile_mu:
        n = _compile_counts.get(key, 0) + 1
        _compile_counts[key] = n
    telemetry.counter_add(OBS_COMPILES)
    if n > 1:
        telemetry.counter_add(OBS_RETRACES)


def compile_counts() -> dict[tuple, int]:
    with _compile_mu:
        return dict(_compile_counts)


def compile_total() -> int:
    with _compile_mu:
        return sum(_compile_counts.values())


def retrace_total() -> int:
    """Compiles beyond the first per (fn, signature) key — 0 in a healthy
    run; anything else is a retrace storm in the making."""
    with _compile_mu:
        return sum(n - 1 for n in _compile_counts.values() if n > 1)


def reset_compile_tracker() -> None:
    """Scope the tracker to a run (tests clear it between cases). Registry
    counters are NOT rewound — they are monotonic by contract."""
    with _compile_mu:
        _compile_counts.clear()
    with _phase_mu:
        _phase_hbm.clear()


# --------------------------------------------------------------- exposition


def _prom_name(name: str) -> str:
    return "distrl_" + re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_num(v: float) -> str:
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    return repr(f)


def prometheus_text(snapshot: Mapping[str, Any] | None = None,
                    fleet: Mapping[str, Any] | None = None) -> str:
    """Prometheus text exposition of the cumulative registry: counters as
    counters, gauges as gauges, histograms as REAL Prometheus histogram
    types — cumulative ``_bucket{le=...}`` lines over the shared
    ``telemetry.HIST_BUCKET_BOUNDS`` ladder plus ``_sum``/``_count``, so
    ``histogram_quantile()`` computes ``serving/ttft_ms`` percentiles from
    a standard scrape (ISSUE 13 satellite; summary-stat-only exposition
    made latency SLOs unscrapable) — plus the ``_max`` gauge the summary
    always carried. A snapshot without bucket data (older worker blobs,
    synthetic test snapshots) degrades to the ``+Inf`` bucket alone.
    Fleet per-worker detail (when provided) rides as labeled
    ``distrl_fleet_worker_*`` series; the fleet SCALARS are already
    registry gauges (FleetAggregator publishes them), so they are not
    duplicated here."""
    snap = snapshot if snapshot is not None else telemetry.observe_snapshot()
    lines: list[str] = []
    for name, v in sorted(snap.get("counters", {}).items()):
        m = _prom_name(name)
        lines.append(f"# TYPE {m} counter")
        lines.append(f"{m} {_prom_num(v)}")
    for name, v in sorted(snap.get("gauges", {}).items()):
        m = _prom_name(name)
        lines.append(f"# TYPE {m} gauge")
        lines.append(f"{m} {_prom_num(v)}")
    for name, h in sorted(snap.get("hists", {}).items()):
        m = _prom_name(name)
        count = h.get("count", 0.0)
        lines.append(f"# TYPE {m} histogram")
        buckets = h.get("buckets") or ()
        cum = 0.0
        for le, c in zip(telemetry.HIST_BUCKET_BOUNDS, buckets):
            cum += c
            lines.append(
                f'{m}_bucket{{le="{_prom_num(le)}"}} {_prom_num(cum)}'
            )
        lines.append(f'{m}_bucket{{le="+Inf"}} {_prom_num(count)}')
        lines.append(f"{m}_count {_prom_num(count)}")
        lines.append(f"{m}_sum {_prom_num(h.get('sum', 0.0))}")
        lines.append(f"# TYPE {m}_max gauge")
        lines.append(f"{m}_max {_prom_num(h.get('max', 0.0))}")
    if fleet:
        lines.append("# TYPE distrl_fleet_worker_healthy gauge")
        for w in fleet.get("workers", ()):
            addr = str(w.get("address", "?")).replace('"', "'")
            lines.append(
                f'distrl_fleet_worker_healthy{{worker="{addr}"}} '
                f"{1 if w.get('healthy') else 0}"
            )
        wm = fleet.get("worker_metrics", {})
        if wm:
            lines.append("# TYPE distrl_fleet_worker_gen_tokens counter")
            for addr, rec in sorted(wm.items()):
                a = str(addr).replace('"', "'")
                lines.append(
                    f'distrl_fleet_worker_gen_tokens{{worker="{a}"}} '
                    f"{_prom_num(rec.get('gen_tokens', 0.0))}"
                )
    return "\n".join(lines) + "\n"


def json_snapshot(fleet: Mapping[str, Any] | None = None) -> dict[str, Any]:
    """The JSON form of one scrape: cumulative registry + compile/HBM
    tables + (driver-side) the fleet view."""
    snap = telemetry.observe_snapshot()
    return {
        "ts": time.time(),
        "counters": snap["counters"],
        "gauges": snap["gauges"],
        "hists": snap["hists"],
        "compiles": {
            "total": compile_total(),
            "retraces": retrace_total(),
            "keys": len(compile_counts()),
        },
        "hbm": hbm_stats(),
        "phase_hbm": phase_hbm(),
        "fleet": dict(fleet) if fleet else None,
    }


class MetricsServer:
    """Threaded HTTP exposition endpoint.

    ``GET /metrics`` → Prometheus text format; ``GET /metrics.json`` (alias
    ``/json``) → the JSON snapshot; ``GET /healthz`` → ``ok``. Binds
    127.0.0.1 by default (an operator fronts it; nothing here needs to be
    internet-facing). ``port=0`` auto-assigns — read ``.port``."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 fleet_provider: Callable[[], Mapping[str, Any]] | None = None):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: ARG002 — quiet
                pass

            def _send(self, code: int, ctype: str, body: bytes) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — http.server contract
                path = self.path.split("?", 1)[0]
                try:
                    if path == "/healthz":
                        self._send(200, "text/plain", b"ok\n")
                    elif path == "/metrics":
                        body = prometheus_text(
                            fleet=server._fleet()
                        ).encode()
                        self._send(
                            200, "text/plain; version=0.0.4", body
                        )
                    elif path in ("/metrics.json", "/json"):
                        body = json.dumps(
                            json_snapshot(fleet=server._fleet()),
                            default=_jsonable,
                        ).encode()
                        self._send(200, "application/json", body)
                    else:
                        self._send(404, "text/plain", b"not found\n")
                except (BrokenPipeError, ConnectionResetError):
                    pass  # scraper went away mid-write
                except Exception as e:  # noqa: BLE001 — a scrape must
                    # never kill the serving thread
                    log.warning("metrics scrape failed: %s", e)
                    try:
                        self._send(500, "text/plain", b"scrape failed\n")
                    except OSError:
                        pass

        self._fleet_provider = fleet_provider
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="obs-metrics", daemon=True
        )
        self._thread.start()

    def _fleet(self) -> Mapping[str, Any] | None:
        if self._fleet_provider is None:
            return None
        try:
            return self._fleet_provider()
        except Exception as e:  # noqa: BLE001 — degrade, don't 500
            log.warning("fleet refresh failed during scrape: %s", e)
            return None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def close(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:  # noqa: BLE001 — idempotent teardown
            pass


# ---------------------------------------------------------- fleet aggregator


class FleetAggregator:
    """Driver-side fold of the per-worker registry snapshots (piggybacked on
    control-plane results — ``telemetry.remote_metrics``) plus the
    DriverClient's health/rejoin state into the ``fleet/*`` series.

    Aggregate tok/s is derived from each worker's monotonic
    ``obs/gen_tokens`` counter between refreshes; a worker restart (counter
    reset, raw count goes BACKWARDS) contributes zero to that window
    instead of a negative rate, and the dead incarnation's count is
    retired into the track's base so the published cumulative totals never
    regress. Refreshes are rate-limited (``min_refresh_s``) so a hot
    scrape loop cannot turn into registry churn."""

    def __init__(self, driver, min_refresh_s: float = 0.5):
        self.driver = driver
        self.min_refresh_s = float(min_refresh_s)
        self._mu = threading.Lock()
        self._last: dict[str, Any] | None = None
        self._last_t = 0.0
        # track -> (snapshot ts, cumulative tokens) at the last refresh
        self._marks: dict[str, tuple[float, float]] = {}
        # track -> tokens finalized by PREVIOUS incarnations of the worker
        # (a restart resets its counter; the dead process's count is final
        # and must stay in the published total — totals never regress)
        self._retired: dict[str, float] = {}
        # track -> last seen worker pid (exported in the snapshot): pid
        # change detects a restart EXACTLY, where counter regression alone
        # misses an incarnation that already out-generated its predecessor
        self._pids: dict[str, Any] = {}
        # tokens finalized by workers the fleet SCALED IN (ISSUE 20): a
        # retired worker's whole cumulative count folds here and its track
        # is dropped from the live table — fleet/gen_tokens_total stays
        # monotone and /metrics.json stops carrying the dead track
        self._scaled_in_tokens = 0.0

    @staticmethod
    def _addr(track: str) -> str:
        # ingest_remote tracks are labeled "worker host:port"
        return track[7:] if track.startswith("worker ") else track

    def refresh(self, force: bool = False) -> dict[str, Any]:
        with self._mu:
            now = time.time()
            if (
                not force and self._last is not None
                and now - self._last_t < self.min_refresh_s
            ):
                return self._last
            workers = (
                self.driver.worker_states()
                if hasattr(self.driver, "worker_states") else []
            )
            epoch = int(getattr(self.driver, "rejoin_epoch", 0))
            remote = telemetry.remote_metrics()
            # elastic scale-in (ISSUE 20): a retired worker is TERMINAL
            # membership state — fold its cumulative count into the fleet
            # base (the restart-retirement logic generalized to a whole
            # track) and drop the track so it never leaks into the live
            # table or /metrics.json again
            retired_addrs = {
                (
                    f"{a[0]}:{a[1]}" if isinstance(a, (tuple, list))
                    else str(a)
                )
                for a in (
                    w.get("address") for w in workers if w.get("retired")
                )
            }
            for track in list(remote):
                if self._addr(track) not in retired_addrs:
                    continue
                snap = remote.pop(track)
                tokens = float(
                    snap.get("counters", {}).get(OBS_GEN_TOKENS, 0.0)
                )
                self._scaled_in_tokens += (
                    self._retired.pop(track, 0.0) + tokens
                )
                self._marks.pop(track, None)
                self._pids.pop(track, None)
                telemetry.drop_remote_track(track)
            total_tokens = self._scaled_in_tokens
            rate = 0.0
            per_worker: dict[str, dict[str, float]] = {}
            for track, snap in remote.items():
                tokens = float(
                    snap.get("counters", {}).get(OBS_GEN_TOKENS, 0.0)
                )
                ts = float(snap.get("_ts", now))
                pid = snap.get("pid")
                last_pid = self._pids.get(track)
                self._pids[track] = pid
                mark = self._marks.get(track)
                restarted = mark is not None and (
                    tokens < mark[1]  # counter went backwards
                    # pid change is the EXACT signal: it also catches an
                    # incarnation that regenerated past its predecessor's
                    # count within one refresh gap
                    or (pid is not None and last_pid is not None
                        and pid != last_pid)
                )
                if restarted:
                    # retire the dead incarnation's count into the track's
                    # base so the published cumulative total never
                    # regresses; this window contributes zero rate (no
                    # honest delta exists across the reset)
                    self._retired[track] = (
                        self._retired.get(track, 0.0) + mark[1]
                    )
                elif mark is not None and ts > mark[0]:
                    rate += (tokens - mark[1]) / (ts - mark[0])
                self._marks[track] = (ts, tokens)
                cumulative = self._retired.get(track, 0.0) + tokens
                total_tokens += cumulative
                per_worker[self._addr(track)] = {
                    "gen_tokens": cumulative, "ts": ts,
                    # incarnation id (ISSUE 14): the cumulative total
                    # NEVER regresses across restarts (by design, above),
                    # so a consumer tracking rates — the worker-health
                    # governor — needs the pid to reset its marks at the
                    # exact restart instead of judging the stall window
                    "pid": pid,
                }
            # fleet-wide serving view (ISSUE 13): fold the workers'
            # serving/* histogram summaries and admission-stall counters
            # into fleet gauges + the endpoint's serving section (None —
            # and no gauges — until some worker served a request)
            serving = fold_fleet_serving(remote)
            fleet = {
                "ts": now,
                "rejoin_epoch": epoch,
                "workers": workers,
                "workers_healthy": sum(
                    1 for w in workers if w.get("healthy")
                ),
                # retired workers left the membership — they are reported
                # in "workers" (terminal state, distinctly) but no longer
                # counted in the pool size
                "workers_total": sum(
                    1 for w in workers if not w.get("retired")
                ),
                "tok_s": round(rate, 3),
                "gen_tokens_total": total_tokens,
                "worker_metrics": per_worker,
                "serving": serving,
            }
            telemetry.gauge_set(FLEET_TOK_S, fleet["tok_s"])
            telemetry.gauge_set(FLEET_GEN_TOKENS, total_tokens)
            telemetry.gauge_set(
                FLEET_WORKERS_HEALTHY, fleet["workers_healthy"]
            )
            telemetry.gauge_set(FLEET_WORKERS_TOTAL, fleet["workers_total"])
            telemetry.gauge_set(FLEET_REJOIN_EPOCH, epoch)
            self._last, self._last_t = fleet, now
            return fleet


# ------------------------------------------------- flight recorder + sentinel


class FlightRecorder:
    """Bounded in-memory ring of recent step records; ``dump`` writes one
    incident directory with the ring, the telemetry span tail, and the
    config/plan snapshot — the evidence bundle a production incident should
    arrive with."""

    def __init__(self, out_dir: str, ring_size: int = 256):
        self.out_dir = out_dir
        self._mu = threading.Lock()
        self.ring: deque = deque(maxlen=max(int(ring_size), 1))
        self.incidents: list[str] = []

    def record(self, kind: str, payload: Mapping[str, Any]) -> None:
        rec = {"ts": time.time(), "kind": kind}
        rec.update({k: _jsonable(v) for k, v in payload.items()})
        with self._mu:
            self.ring.append(rec)

    def dump(self, trigger: str, step: int, *,
             config: Mapping[str, Any] | None = None,
             plan: Mapping[str, Any] | None = None,
             extra: Mapping[str, Any] | None = None) -> str:
        """Write ``<out_dir>/incident_step<N>_<trigger>/`` and return its
        path. The directory name is deterministic (step + trigger) so a
        seeded failure produces a stable bundle; a name collision (two
        dumps at one step, e.g. two distinct triggers share a name only if
        equal — they don't) gets a numeric suffix rather than overwrite."""
        base = os.path.join(
            self.out_dir, f"incident_step{step:06d}_{trigger}"
        )
        path = base
        k = 1
        while os.path.exists(path):
            k += 1
            path = f"{base}_{k}"
        os.makedirs(path)
        with self._mu:
            ring = list(self.ring)
        span_tail = telemetry.recent_events()
        with open(os.path.join(path, "metric_ring.jsonl"), "w") as f:
            for rec in ring:
                f.write(json.dumps(rec, default=_jsonable) + "\n")
        with open(os.path.join(path, "span_tail.json"), "w") as f:
            json.dump(span_tail, f, default=_jsonable)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(
                {
                    "config": dict(config) if config else None,
                    "plan": dict(plan) if plan else None,
                },
                f, default=_jsonable, indent=2,
            )
        manifest = {
            "trigger": trigger,
            "step": int(step),
            "time": time.time(),
            "ring_records": len(ring),
            "span_tail_events": len(span_tail),
            "tracing_enabled": telemetry.enabled(),
            "phase_hbm": phase_hbm(),
            "files": ["metric_ring.jsonl", "span_tail.json", "config.json"],
        }
        if extra:
            manifest.update({k: _jsonable(v) for k, v in extra.items()})
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f, default=_jsonable, indent=2)
        with self._mu:
            self.incidents.append(path)
        telemetry.counter_add(OBS_INCIDENTS)
        log.error(
            "sentinel incident %r at step %d — evidence in %s",
            trigger, step, path,
        )
        return path


class Sentinel:
    """Deterministic anomaly triggers over each step's metrics record.

    Each trigger fires AT MOST ONCE per run (the first incident is the
    evidence; repeats would bury it), dumping the flight recorder and
    requesting a guarded ``TraceProfiler`` capture window when a profiler
    is armed. Triggers:

    * ``nan_loss`` — non-finite ``loss`` / ``grad_norm``.
    * ``reward_collapse`` — ``mean_accuracy_reward`` pinned at ≤ 0 for
      ``collapse_steps`` consecutive steps after having been positive.
    * ``staleness_blowup`` — ``rollout/staleness_max`` above the
      configured bound (the admission layers should make this impossible;
      seeing it means a staleness-control bug).
    * ``tok_s_regression`` — ``engine/decode_tok_s`` below ``tok_drop_frac``
      of its running EMA after ``warmup_steps`` observations.
    * ``hbm_breach`` — device peak bytes above ``hbm_frac`` of
      ``bytes_limit`` (when the backend reports one).
    * ``ttft_blowup`` / ``queue_wait_blowup`` — the step's worst observed
      ``serving/ttft_ms`` / ``serving/queue_wait_ms`` (local registry max,
      or the fleet-folded worker max) above the configured SLO
      (``slo_ttft_ms`` / ``slo_queue_wait_ms``; None = trigger unarmed).
    * ``entropy_collapse`` / ``kl_blowup`` / ``ratio_saturation`` /
      ``grad_spike`` — training-dynamics triggers (ISSUE 16) over the
      device-fused ``learn/*`` bundle the trainer merges into the step
      record: masked answer-token entropy below ``learn_entropy_floor``;
      behavior↔policy KL above ``learn_kl_limit``; the AIPO
      cap-saturation (or PPO clip) fraction above
      ``learn_ratio_sat_frac``; the whole-adapter grad norm above
      ``learn_grad_spike`` × its running EMA after ``warmup_steps``
      observations. None = trigger unarmed.

    ``DISTRL_SENTINEL_INJECT="<trigger>:<step>"`` deterministically
    injects any trigger's precondition at the named step — the seeded
    faults the obs/control smokes and chaos gates build on (ISSUE 14
    closed the parse-time asymmetry that rejected ``reward_collapse``,
    ``staleness_blowup`` and ``hbm_breach``): ``nan_loss`` fakes a NaN
    loss, ``tok_s_regression`` a zero-throughput step,
    ``reward_collapse`` a sustained zero-reward run (from the named step
    until the trigger fires, with the had-been-positive precondition
    seeded), ``staleness_blowup`` a staleness reading past the armed
    bound (async mode only), ``hbm_breach`` a one-step synthetic
    watermark breach (the single-step twin of ``DISTRL_OBS_FAKE_HBM``,
    which fakes *sustained* pressure for the HBM governor), and
    ``ttft_blowup`` / ``queue_wait_blowup`` an SLO breach (legal only
    with the matching SLO armed — injecting an unarmable trigger would
    make a CI gate built on it pass vacuously). The training-dynamics
    triggers inject the same way: a reading past their armed threshold
    at the named step (``grad_spike`` additionally seeds the EMA/warmup
    preconditions so the spike is judgeable) — each legal only with its
    ``learn_*`` threshold armed.
    """

    def __init__(self, recorder: FlightRecorder | None, profiler=None, *,
                 warmup_steps: int = 3, tok_drop_frac: float = 0.5,
                 tok_ema_alpha: float = 0.3, hbm_frac: float = 0.95,
                 collapse_steps: int = 3,
                 staleness_limit: float | None = None,
                 slo_ttft_ms: float | None = None,
                 slo_queue_wait_ms: float | None = None,
                 learn_entropy_floor: float | None = None,
                 learn_kl_limit: float | None = None,
                 learn_ratio_sat_frac: float | None = None,
                 learn_grad_spike: float | None = None,
                 capture_steps: int = 2):
        self.recorder = recorder
        self.profiler = profiler
        self.warmup_steps = warmup_steps
        self.tok_drop_frac = tok_drop_frac
        self.tok_ema_alpha = tok_ema_alpha
        self.hbm_frac = hbm_frac
        self.collapse_steps = collapse_steps
        self.staleness_limit = staleness_limit
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_queue_wait_ms = slo_queue_wait_ms
        self.learn_entropy_floor = learn_entropy_floor
        self.learn_kl_limit = learn_kl_limit
        self.learn_ratio_sat_frac = learn_ratio_sat_frac
        self.learn_grad_spike = learn_grad_spike
        self.capture_steps = capture_steps
        self.fired: set[str] = set()
        # trigger escalation hook (ISSUE 14): the trainer points this at
        # ControlRuntime.on_trigger so a fired trigger can ACT (shrink the
        # admission cap, engage shedding, quarantine, …) instead of only
        # dumping. None — or a runtime with no governor registered for the
        # trigger — preserves the PR 8 dump-only contract exactly.
        self.on_trigger: Callable[[str, int, Mapping[str, Any]], Any] | None = None
        self._tok_ema: float | None = None
        self._tok_obs = 0
        self._grad_ema: float | None = None
        self._grad_obs = 0
        self._seen_reward = False
        self._collapse_run = 0
        self._inject: tuple[str, int] | None = None
        spec = os.environ.get("DISTRL_SENTINEL_INJECT")
        if spec:
            try:
                trig, _, at = spec.partition(":")
                trig = trig.strip()
                # every Sentinel trigger is injectable (ISSUE 14 closed the
                # parse-time asymmetry: reward_collapse / staleness_blowup /
                # hbm_breach were valid triggers but rejected here, making
                # chaos gates for them impossible); the guard now only
                # rejects triggers whose ARMING precondition is absent —
                # accepting those and never firing would make a CI gate
                # built on them pass vacuously
                if trig not in ("nan_loss", "tok_s_regression",
                                "reward_collapse", "staleness_blowup",
                                "hbm_breach",
                                "ttft_blowup", "queue_wait_blowup",
                                "entropy_collapse", "kl_blowup",
                                "ratio_saturation", "grad_spike"):
                    raise ValueError(trig)
                # vacuous-gate guards: without the matching limit there is
                # no threshold to breach
                if trig == "ttft_blowup" and slo_ttft_ms is None:
                    raise ValueError("ttft_blowup needs slo_ttft_ms")
                if (trig == "queue_wait_blowup"
                        and slo_queue_wait_ms is None):
                    raise ValueError(
                        "queue_wait_blowup needs slo_queue_wait_ms"
                    )
                if trig == "staleness_blowup" and staleness_limit is None:
                    raise ValueError(
                        "staleness_blowup needs a staleness limit "
                        "(async mode)"
                    )
                if (trig == "entropy_collapse"
                        and learn_entropy_floor is None):
                    raise ValueError(
                        "entropy_collapse needs learn_entropy_floor"
                    )
                if trig == "kl_blowup" and learn_kl_limit is None:
                    raise ValueError("kl_blowup needs learn_kl_limit")
                if (trig == "ratio_saturation"
                        and learn_ratio_sat_frac is None):
                    raise ValueError(
                        "ratio_saturation needs learn_ratio_sat_frac"
                    )
                if trig == "grad_spike" and learn_grad_spike is None:
                    raise ValueError("grad_spike needs learn_grad_spike")
                self._inject = (trig, int(at))
            except ValueError:
                log.warning(
                    "ignoring DISTRL_SENTINEL_INJECT=%r (expected "
                    "'<trigger>:<step>' where <trigger> is one of "
                    "nan_loss, tok_s_regression, reward_collapse, "
                    "staleness_blowup, hbm_breach, ttft_blowup, "
                    "queue_wait_blowup, entropy_collapse, kl_blowup, "
                    "ratio_saturation or grad_spike; staleness_blowup "
                    "only in async mode, the SLO triggers only with their "
                    "slo_* limit armed, the training-dynamics triggers "
                    "only with their learn_* threshold armed)",
                    spec,
                )

    def _fire(self, trigger: str, step: int, *, config, plan,
              extra: Mapping[str, Any] | None = None) -> bool:
        if trigger in self.fired:
            return False
        self.fired.add(trigger)
        if self.recorder is not None:
            self.recorder.dump(
                trigger, step, config=config, plan=plan, extra=extra
            )
        else:
            telemetry.counter_add(OBS_INCIDENTS)
            log.error(
                "sentinel trigger %r at step %d (no flight_recorder_dir "
                "configured — nothing dumped)", trigger, step,
            )
        if self.profiler is not None and hasattr(
            self.profiler, "request_capture"
        ):
            # guarded: a capture already in flight (the configured step
            # window) makes this a counted no-op, never a second
            # start_trace mid-run
            self.profiler.request_capture(self.capture_steps)
        hook = self.on_trigger
        if hook is not None:
            # trigger → action escalation (ISSUE 14): exactly once per
            # trigger per run (this method's own fire-once contract); a
            # runtime with no governor for the trigger returns without
            # acting — the dump above already happened either way, so an
            # un-armed controller leaves the trigger dump-only
            try:
                hook(trigger, step, dict(extra) if extra else {})
            except Exception:  # noqa: BLE001 — an escalation bug must not
                # suppress the incident path that just produced evidence
                log.warning(
                    "control escalation for trigger %r failed", trigger,
                    exc_info=True,
                )
        return True

    def check(self, step: int, metrics: Mapping[str, Any], *,
              config: Mapping[str, Any] | None = None,
              plan: Mapping[str, Any] | None = None) -> list[str]:
        m = dict(metrics)
        forced_hbm: dict[str, float] | None = None
        if self._inject is not None and self._inject[1] == step:
            trig = self._inject[0]
            if trig == "nan_loss":
                m["loss"] = float("nan")
            elif trig == "tok_s_regression":
                m["engine/decode_tok_s"] = 0.0
            elif trig == "staleness_blowup":
                # parse-time guard ensures staleness_limit is armed
                m["rollout/staleness_max"] = float(self.staleness_limit) + 1.0
            elif trig == "hbm_breach":
                # synthesize a one-step breach for the HBM check below —
                # the single-step twin of the DISTRL_OBS_FAKE_HBM hook
                # (which fakes SUSTAINED pressure for the governor gates;
                # this injection proves the trigger itself fires)
                forced_hbm = {
                    "bytes_limit": 1.0,
                    "peak_bytes_in_use": 1.0,
                    "bytes_in_use": 1.0,
                }
            elif trig == "ttft_blowup":
                # parse-time guard ensures slo_ttft_ms is armed
                m[SERVING_TTFT_MS + "_max"] = 1000.0 * self.slo_ttft_ms
            elif trig == "queue_wait_blowup":
                m[SERVING_QUEUE_WAIT_MS + "_max"] = (
                    1000.0 * self.slo_queue_wait_ms
                )
            elif trig == "entropy_collapse":
                # parse-time guards ensure the learn_* thresholds below
                # are armed
                m[LEARN_ENTROPY] = max(
                    self.learn_entropy_floor - 1.0, 0.0
                )
            elif trig == "kl_blowup":
                m[LEARN_KL] = 10.0 * self.learn_kl_limit + 1.0
            elif trig == "ratio_saturation":
                # synthetic reading; may exceed 1.0 when the threshold sits
                # at the ceiling — the check only compares against it
                m[LEARN_CAP_FRAC] = self.learn_ratio_sat_frac + 0.5
            elif trig == "grad_spike":
                # seed the EMA/warmup preconditions so the check below can
                # judge the spike at exactly the named step
                if self._grad_ema is None:
                    self._grad_ema = 1.0
                self._grad_obs = max(self._grad_obs, self.warmup_steps)
                m[LEARN_GRAD_NORM_TOTAL] = (
                    10.0 * self.learn_grad_spike * self._grad_ema
                )
        if (
            self._inject is not None
            and self._inject[0] == "reward_collapse"
            and step >= self._inject[1]
            and "reward_collapse" not in self.fired
        ):
            # reward collapse is a RUN of zero-reward steps after reward
            # had been positive: inject the whole run (zero reward from
            # the named step until the trigger fires), with the
            # had-been-positive precondition seeded too
            self._seen_reward = True
            m["mean_accuracy_reward"] = 0.0
        fired: list[str] = []

        def fire(trigger: str, **extra) -> None:
            if self._fire(trigger, step, config=config, plan=plan,
                          extra=extra or None):
                fired.append(trigger)

        # --- NaN/Inf in loss or grad norm
        for key in ("loss", "grad_norm"):
            v = m.get(key)
            try:
                bad = v is not None and not math.isfinite(float(v))
            except (TypeError, ValueError):
                bad = False
            if bad:
                fire("nan_loss", metric=key, value=str(v))
                break
        # --- reward collapse
        acc = m.get("mean_accuracy_reward")
        if acc is not None:
            if float(acc) > 0.0:
                self._seen_reward = True
                self._collapse_run = 0
            elif self._seen_reward:
                self._collapse_run += 1
                if self._collapse_run >= self.collapse_steps:
                    fire(
                        "reward_collapse",
                        zero_steps=self._collapse_run,
                    )
        # --- staleness histogram blowup
        if self.staleness_limit is not None:
            mx = m.get("rollout/staleness_max")
            if mx is not None and float(mx) > self.staleness_limit:
                fire(
                    "staleness_blowup",
                    staleness_max=float(mx), limit=self.staleness_limit,
                )
        # --- tok/s regression vs running EMA
        tok = m.get("engine/decode_tok_s")
        if tok is not None:
            tok = float(tok)
            self._tok_obs += 1
            if self._tok_ema is None:
                self._tok_ema = tok
            else:
                if (
                    self._tok_obs > self.warmup_steps
                    and tok < self.tok_drop_frac * self._tok_ema
                ):
                    fire(
                        "tok_s_regression",
                        tok_s=tok, ema=round(self._tok_ema, 1),
                    )
                a = self.tok_ema_alpha
                self._tok_ema = a * tok + (1 - a) * self._tok_ema
        # --- serving SLO breaches (ISSUE 13): the step's worst observed
        # latency — the local registry's per-step hist max (the trainer
        # merges metrics_snapshot into the step record) or the fleet-folded
        # worker max gauge, whichever the run produces
        for trigger, slo, keys in (
            ("ttft_blowup", self.slo_ttft_ms,
             (SERVING_TTFT_MS + "_max", FLEET_SERVING_TTFT_MAX_MS)),
            ("queue_wait_blowup", self.slo_queue_wait_ms,
             (SERVING_QUEUE_WAIT_MS + "_max",
              FLEET_SERVING_QUEUE_WAIT_MAX_MS)),
        ):
            if slo is None:
                continue
            observed = [float(m[k]) for k in keys if m.get(k) is not None]
            if observed and max(observed) > slo:
                fire(
                    trigger,
                    observed_ms=round(max(observed), 3), slo_ms=slo,
                )
        # --- training-dynamics triggers (ISSUE 16): the device-fused
        # learn/* bundle the trainer merges into the step record
        if self.learn_entropy_floor is not None:
            ent = m.get(LEARN_ENTROPY)
            if ent is not None and float(ent) < self.learn_entropy_floor:
                fire(
                    "entropy_collapse",
                    entropy=float(ent), floor=self.learn_entropy_floor,
                )
        if self.learn_kl_limit is not None:
            kl = m.get(LEARN_KL)
            if kl is not None and float(kl) > self.learn_kl_limit:
                fire(
                    "kl_blowup",
                    kl=float(kl), limit=self.learn_kl_limit,
                )
        if self.learn_ratio_sat_frac is not None:
            # AIPO runs report the cap-saturation fraction, PPO-clip runs
            # the clip fraction — one trigger covers whichever the loss
            # computes
            sat = m.get(LEARN_CAP_FRAC)
            if sat is None:
                sat = m.get(LEARN_CLIP_FRAC)
            if sat is not None and float(sat) > self.learn_ratio_sat_frac:
                fire(
                    "ratio_saturation",
                    saturated_frac=float(sat),
                    limit=self.learn_ratio_sat_frac,
                )
        if self.learn_grad_spike is not None:
            g = m.get(LEARN_GRAD_NORM_TOTAL)
            if g is not None:
                g = float(g)
                self._grad_obs += 1
                if self._grad_ema is None:
                    self._grad_ema = g
                else:
                    if (
                        self._grad_obs > self.warmup_steps
                        and g > self.learn_grad_spike * self._grad_ema
                    ):
                        fire(
                            "grad_spike",
                            grad_norm=g, ema=round(self._grad_ema, 6),
                            factor=self.learn_grad_spike,
                        )
                    a = self.tok_ema_alpha
                    self._grad_ema = a * g + (1 - a) * self._grad_ema
        # --- HBM watermark breach
        stats = forced_hbm if forced_hbm is not None else hbm_stats()
        if stats and stats.get("bytes_limit"):
            peak = float(
                stats.get("peak_bytes_in_use")
                or stats.get("bytes_in_use", 0.0)
            )
            if peak > self.hbm_frac * float(stats["bytes_limit"]):
                fire(
                    "hbm_breach",
                    peak_bytes=peak, bytes_limit=stats["bytes_limit"],
                )
        return fired


# ------------------------------------------------------------------- plane


class ObsPlane:
    """One handle bundling the pieces a process arms: the HTTP endpoint,
    the fleet aggregator (driver with remote workers only), the flight
    recorder ring, and the sentinel. The trainer owns one when any obs
    flag is set; ``on_step`` is its single per-step entry point."""

    def __init__(self, *, metrics_port: int | None = None,
                 sentinel: bool = False,
                 flight_recorder_dir: str | None = None,
                 ring_size: int = 256,
                 driver=None, profiler=None,
                 staleness_limit: float | None = None,
                 slo_ttft_ms: float | None = None,
                 slo_queue_wait_ms: float | None = None,
                 learn_entropy_floor: float | None = None,
                 learn_kl_limit: float | None = None,
                 learn_ratio_sat_frac: float | None = None,
                 learn_grad_spike: float | None = None,
                 config_snapshot: Mapping[str, Any] | None = None,
                 plan_provider: Callable[[], Mapping[str, Any] | None] | None = None):
        self.fleet = FleetAggregator(driver) if driver is not None else None
        self.server = (
            MetricsServer(
                metrics_port,
                fleet_provider=self.fleet.refresh if self.fleet else None,
            )
            if metrics_port is not None else None
        )
        self.recorder = (
            FlightRecorder(flight_recorder_dir, ring_size)
            if flight_recorder_dir else None
        )
        self.sentinel = (
            Sentinel(
                self.recorder, profiler, staleness_limit=staleness_limit,
                slo_ttft_ms=slo_ttft_ms,
                slo_queue_wait_ms=slo_queue_wait_ms,
                learn_entropy_floor=learn_entropy_floor,
                learn_kl_limit=learn_kl_limit,
                learn_ratio_sat_frac=learn_ratio_sat_frac,
                learn_grad_spike=learn_grad_spike,
            )
            if sentinel else None
        )
        self._config_snapshot = (
            dict(config_snapshot) if config_snapshot else None
        )
        self._plan_provider = plan_provider
        # HBM sampling at every PhaseSpans boundary while this plane lives
        telemetry.set_phase_hook(_on_phase)
        if self.server is not None:
            log.info("obs endpoint serving on %s/metrics", self.server.url)

    def on_step(self, step: int, metrics: Mapping[str, Any]) -> None:
        if self.recorder is not None:
            self.recorder.record("step", {"step": step, "metrics": {
                k: _jsonable(v) for k, v in metrics.items()
            }})
        if self.fleet is not None:
            # keep the fleet gauges flowing into the sink records too, not
            # just scrapes (rate-limited inside refresh)
            self.fleet.refresh()
        if self.sentinel is not None:
            plan = self._plan_provider() if self._plan_provider else None
            self.sentinel.check(
                step, metrics, config=self._config_snapshot, plan=plan
            )

    def close(self) -> None:
        telemetry.set_phase_hook(None)
        if self.server is not None:
            self.server.close()
