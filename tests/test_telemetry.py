"""Telemetry subsystem tests: span nesting, the disabled no-op fast path,
Chrome-trace schema validity, the counters/gauges/histogram registry, MFU
math against hand-computed FLOP counts, and the worker-blob merge across a
real multi-process control-plane round."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.models.configs import TINY
from distrl_llm_tpu.native.build import native_available


@pytest.fixture(autouse=True)
def clean_state():
    """Telemetry is process-global; every test starts and ends empty."""
    telemetry.reset()
    telemetry.configure(enabled=False)
    yield
    telemetry.reset()
    telemetry.configure(enabled=False)


def events():
    return telemetry._STATE.events


class TestSpans:
    def test_nesting_records_both_and_contains(self):
        telemetry.configure(enabled=True)
        with telemetry.span("outer", phase="gen"):
            with telemetry.span("inner"):
                time.sleep(0.002)
        by_name = {e["name"]: e for e in events()}
        assert set(by_name) == {"outer", "inner"}
        outer, inner = by_name["outer"], by_name["inner"]
        # children exit first (appended first) and nest within the parent
        assert events()[0]["name"] == "inner"
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["tid"] == inner["tid"]
        assert outer["args"] == {"phase": "gen"}

    def test_disabled_is_free(self):
        """span() off the enabled path returns ONE shared no-op object and
        records nothing — the instrumented hot paths cost an attribute
        read, not an allocation."""
        assert telemetry.span("a") is telemetry.span("b", x=1)
        with telemetry.span("a") as sp:
            sp.set(tokens=3)
        assert events() == []

    def test_set_attaches_args_mid_span(self):
        telemetry.configure(enabled=True)
        with telemetry.span("decode", rows=4) as sp:
            sp.set(tokens=17)
        (ev,) = events()
        assert ev["args"] == {"rows": 4, "tokens": 17}

    def test_thread_awareness(self):
        import threading

        telemetry.configure(enabled=True)

        def work():
            with telemetry.span("worker-side"):
                pass

        t = threading.Thread(target=work, name="rollout-0")
        with telemetry.span("main-side"):
            t.start()
            t.join(timeout=30)
        tids = {e["name"]: e["tid"] for e in events()}
        assert tids["worker-side"] != tids["main-side"]
        assert telemetry._STATE.thread_names[tids["worker-side"]] == "rollout-0"


class TestPhaseSpans:
    def test_metric_name_parity_and_span(self):
        """PhaseSpans must keep the reference's exact timing/*_duration
        names (the PhaseTimer contract) while recording driver/* spans."""
        telemetry.configure(enabled=True)
        timer = telemetry.PhaseSpans()
        with timer("generation"):
            time.sleep(0.001)
        with timer("update"):
            pass
        m = timer.metrics()
        assert set(m) == {"timing/generation_duration",
                          "timing/update_duration"}
        assert m["timing/generation_duration"] > 0
        assert timer.get("generation") == m["timing/generation_duration"]
        assert {e["name"] for e in events()} == {"driver/generation",
                                                 "driver/update"}

    def test_works_disabled(self):
        timer = telemetry.PhaseSpans()
        with timer("reward"):
            pass
        assert "timing/reward_duration" in timer.metrics()
        assert events() == []


class TestChromeTraceExport:
    def test_schema_validity(self, tmp_path):
        telemetry.configure(enabled=True)
        with telemetry.span("engine/prefill", tokens=32):
            pass
        telemetry.gauge_set("pool/occupancy", 0.5)
        path = telemetry.export_chrome_trace(
            str(tmp_path / "trace.json"), metadata={"model": "tiny"}
        )
        with open(path) as f:
            doc = json.load(f)
        assert isinstance(doc["traceEvents"], list)
        assert doc["metadata"] == {"model": "tiny"}
        phases = {}
        for ev in doc["traceEvents"]:
            assert {"ph", "name", "pid", "tid"} <= set(ev), ev
            phases.setdefault(ev["ph"], []).append(ev)
        # one complete-span event with µs ts/dur, one counter sample, and
        # process/thread name metadata
        (x,) = phases["X"]
        assert x["name"] == "engine/prefill" and x["dur"] >= 1
        assert isinstance(x["ts"], int)
        (c,) = phases["C"]
        assert c["name"] == "pool/occupancy"
        assert c["args"] == {"occupancy": 0.5}
        meta_names = {e["name"] for e in phases["M"]}
        assert "process_name" in meta_names

    def test_export_clears_by_default(self, tmp_path):
        telemetry.configure(enabled=True)
        with telemetry.span("a"):
            pass
        telemetry.export_chrome_trace(str(tmp_path / "t.json"))
        assert events() == []


class TestRegistry:
    def test_counter_reports_delta_and_resets(self):
        # graftcheck: disable=GC203 -- synthetic series exercising registry mechanics, not a production pin
        telemetry.counter_add("engine/rounds")
        telemetry.counter_add("engine/rounds", 2)
        snap = telemetry.metrics_snapshot()
        assert snap["engine/rounds"] == 3.0
        assert telemetry.metrics_snapshot() == {}  # untouched since

    def test_gauge_keeps_last_value(self):
        telemetry.gauge_set("pool/occupancy", 0.25)
        telemetry.gauge_set("pool/occupancy", 0.75)
        assert telemetry.metrics_snapshot()["pool/occupancy"] == 0.75

    def test_histogram_summary(self):
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):
            telemetry.hist_observe("cp/rpc_dispatch_ms", v)
        snap = telemetry.metrics_snapshot()
        assert snap["cp/rpc_dispatch_ms_count"] == 5
        assert snap["cp/rpc_dispatch_ms_mean"] == pytest.approx(22.0)
        assert snap["cp/rpc_dispatch_ms_p50"] == 3.0
        assert snap["cp/rpc_dispatch_ms_max"] == 100.0

    def test_paged_grid_telemetry(self):
        """Engines surface the grid-overhead bound (ISSUE 3): total grid
        steps = per-call count × op calls/step × layers × decode steps."""
        from distrl_llm_tpu.engine.paged_engine import _record_grid_telemetry

        _record_grid_telemetry(num_layers=24, steps=100, per_call=960)
        snap = telemetry.metrics_snapshot()
        assert snap["ops/paged_grid_steps"] == 960 * 24 * 100
        # speculative verify fans out draft_len+1 op calls per layer/step
        _record_grid_telemetry(
            num_layers=24, steps=100, per_call=960, calls_per_step=5,
        )
        snap = telemetry.metrics_snapshot()
        assert snap["ops/paged_grid_steps"] == 960 * 24 * 100 * 5

    def test_paged_grid_telemetry_reference_path_is_silent(self):
        from distrl_llm_tpu.engine.paged_engine import _record_grid_telemetry

        _record_grid_telemetry(num_layers=24, steps=100, per_call=0)
        snap = telemetry.metrics_snapshot()
        assert "ops/paged_grid_steps" not in snap

    def test_engine_grid_lookup_is_geometry_keyed(self, monkeypatch):
        """The engine derives the count from ITS OWN dispatch-choice record
        (keyed by requested impl + geometry) at the LIVE row count — never
        from another engine's entry or a stale batch (the autotuner's
        candidate sweep runs several engines in one process, and one wave
        engine serves varying row counts without retracing)."""
        import jax.numpy as jnp

        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
        from distrl_llm_tpu.models import TINY
        from distrl_llm_tpu.ops import paged as paged_ops
        from distrl_llm_tpu.ops.paged import dispatch_choice_key

        eng = PagedGenerationEngine(
            TINY, max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
            pad_token_id=0, cache_dtype=jnp.float32, page_size=8,
        )
        pps = eng.prompt_pages + eng.private_pages
        own_key = dispatch_choice_key(
            quantized=False, num_kv_heads=TINY.num_kv_heads,
            num_groups=TINY.num_heads // TINY.num_kv_heads,
            head_dim=TINY.head_dim, page_size=8, pps=pps,
            impl="auto",
        )
        # a same-geometry engine pinned to a DIFFERENT kernel keys apart
        pinned_key = dispatch_choice_key(
            quantized=False, num_kv_heads=TINY.num_kv_heads,
            num_groups=TINY.num_heads // TINY.num_kv_heads,
            head_dim=TINY.head_dim, page_size=8, pps=pps,
            impl="reference",
        )
        assert pinned_key != own_key
        monkeypatch.setattr(
            paged_ops, "dispatch_choices",
            {("stale", "other", "geometry"): "reference",
             pinned_key: "reference",
             own_key: "native"},
        )
        # native at 8 rows: 8 × the blocks a row, the block sized from this
        # engine's own shapes — computed at the live batch, so a later 3-row
        # wave reports 3-row counts, no retrace
        from distrl_llm_tpu.ops.paged_native import native_pages_per_step

        blocks = -(-pps // native_pages_per_step(
            num_kv_heads=TINY.num_kv_heads, head_dim=TINY.head_dim,
            page_size=8, pps=pps, kv_itemsize=4,
        ))
        assert eng._grid_steps_per_call(8) == 8 * blocks
        assert eng._grid_steps_per_call(3) == 3 * blocks
        # no record yet (fresh process) → 0, telemetry stays silent
        monkeypatch.setattr(paged_ops, "dispatch_choices", {})
        assert eng._grid_steps_per_call(8) == 0

    def test_gauge_emits_counter_event_when_tracing(self):
        telemetry.gauge_set("pool/occupancy", 0.5)
        assert events() == []  # disabled: metric only, no trace sample
        telemetry.configure(enabled=True)
        telemetry.gauge_set("pool/occupancy", 0.75)
        (ev,) = events()
        assert ev["ph"] == "C" and ev["args"] == {"occupancy": 0.75}

    def test_hist_trace_sample_emits_counter_event(self):
        """hist_observe(trace_sample=True): sink histogram AND (while
        tracing) a per-observation Chrome counter event — the staleness
        series' contract (rollout/staleness renders as a Perfetto track and
        trace_report summarizes it from the file alone)."""
        telemetry.hist_observe("rollout/staleness", 1.0, trace_sample=True)
        assert events() == []  # disabled: no trace event
        telemetry.configure(enabled=True)
        telemetry.hist_observe("rollout/staleness", 2.0, trace_sample=True)
        (ev,) = events()
        assert ev["ph"] == "C" and ev["args"] == {"staleness": 2.0}
        snap = telemetry.metrics_snapshot()
        assert snap["rollout/staleness_count"] == 2

    def test_rollout_series_schema(self):
        """Schema pin for the async-rollout registry names (ISSUE 4): the
        buffer's occupancy gauge + backpressure/drop counters and the
        policy's staleness histogram land in the MetricsSink snapshot under
        exactly these names."""
        from distrl_llm_tpu.rollout import (
            StalenessPolicy, Trajectory, TrajectoryBuffer,
        )

        def traj(version):
            return Trajectory(
                problem="p", solution="s", answers=["a"], token_lengths=[1],
                produced_version=version,
            )

        buf = TrajectoryBuffer(2, high_watermark=2, low_watermark=1)
        buf.put(traj(0))
        buf.put(traj(0))
        buf.put(traj(5), block=False)  # capacity drop
        buf.evict_stale(learner_version=9, max_staleness=1)  # stale drops
        kept, _ = StalenessPolicy(2).admit([traj(9), traj(1)], 9)
        assert len(kept) == 1
        snap = telemetry.metrics_snapshot()
        assert snap["rollout/buffer_occupancy"] == 0.0
        assert snap["rollout/dropped_capacity"] == 1.0
        assert snap["rollout/dropped_stale"] == 3.0  # 2 evicted + 1 admission
        assert snap["rollout/staleness_count"] == 1.0

    def test_cp_resilience_series_schema(self):
        """Schema pin for the control-plane resilience registry names
        (ISSUE 5): the series the DriverClient emits — and their TYPES —
        land in the MetricsSink snapshot under exactly these names:
        cp/healthy_workers is a GAUGE (last value wins), the rest are
        COUNTERS (report-and-reset deltas)."""
        from distrl_llm_tpu.distributed import resilience as r

        assert r.CP_HEALTHY_GAUGE == "cp/healthy_workers"
        assert r.CP_RECONNECTS == "cp/reconnects"
        assert r.CP_RESUBMITS == "cp/resubmits"
        assert r.CP_RETRIES == "cp/retries"
        assert r.CP_POISON_SHARDS == "cp/poison_shards"
        assert r.CP_DEGRADED_GROUPS == "cp/degraded_groups"
        # intentional scale-in (ISSUE 20): a COUNTER, distinct from the
        # quarantine/reconnect vocabulary — retire is terminal, not a fault
        assert r.CP_RETIRES == "cp/retires"
        telemetry.gauge_set(r.CP_HEALTHY_GAUGE, 4)
        telemetry.gauge_set(r.CP_HEALTHY_GAUGE, 3)  # gauge: last value
        telemetry.counter_add(r.CP_RECONNECTS)
        telemetry.counter_add(r.CP_RESUBMITS, 2)
        telemetry.counter_add(r.CP_RETRIES)
        telemetry.counter_add(r.CP_RETRIES)
        telemetry.counter_add(r.CP_POISON_SHARDS)
        telemetry.counter_add(r.CP_DEGRADED_GROUPS, 4)
        snap = telemetry.metrics_snapshot()
        assert snap["cp/healthy_workers"] == 3.0
        assert snap["cp/reconnects"] == 1.0
        assert snap["cp/resubmits"] == 2.0
        assert snap["cp/retries"] == 2.0
        assert snap["cp/poison_shards"] == 1.0
        assert snap["cp/degraded_groups"] == 4.0
        # counters report-and-reset: untouched series stay out of the next
        # snapshot instead of logging zeros forever
        snap2 = telemetry.metrics_snapshot()
        assert "cp/reconnects" not in snap2

    def test_control_series_schema(self):
        """Schema pin for the self-healing runtime's registry names
        (ISSUE 14) and their TYPES: control/actions,
        control/trigger_escalations, control/cooldown_skips,
        control/budget_exhausted, control/shed_groups and
        control/nan_rollbacks are COUNTERS; control/shed_active and the
        per-actuator control/value/<name> derivations are GAUGES. The
        quarantine counter (cp/quarantines) rides the cp family — the
        DriverClient emits it."""
        from distrl_llm_tpu import control as c
        from distrl_llm_tpu.distributed import resilience as r

        assert c.CONTROL_ACTIONS == "control/actions"
        assert c.CONTROL_TRIGGER_ESCALATIONS == "control/trigger_escalations"
        assert c.CONTROL_COOLDOWN_SKIPS == "control/cooldown_skips"
        assert c.CONTROL_BUDGET_EXHAUSTED == "control/budget_exhausted"
        assert c.CONTROL_SHED_GROUPS == "control/shed_groups"
        assert c.CONTROL_SHED_ACTIVE == "control/shed_active"
        assert c.CONTROL_NAN_ROLLBACKS == "control/nan_rollbacks"
        assert c.CONTROL_VALUE == "control/value"
        assert r.CP_QUARANTINES == "cp/quarantines"
        telemetry.counter_add(c.CONTROL_ACTIONS)
        telemetry.counter_add(c.CONTROL_TRIGGER_ESCALATIONS)
        telemetry.counter_add(c.CONTROL_COOLDOWN_SKIPS, 2)
        telemetry.counter_add(c.CONTROL_BUDGET_EXHAUSTED)
        telemetry.counter_add(c.CONTROL_SHED_GROUPS, 3)
        telemetry.counter_add(c.CONTROL_NAN_ROLLBACKS)
        telemetry.counter_add(r.CP_QUARANTINES)
        telemetry.gauge_set(c.CONTROL_SHED_ACTIVE, 1.0)
        telemetry.gauge_set(f"{c.CONTROL_VALUE}/admission_frac", 0.5)
        snap = telemetry.metrics_snapshot()
        assert snap["control/actions"] == 1.0
        assert snap["control/trigger_escalations"] == 1.0
        assert snap["control/cooldown_skips"] == 2.0
        assert snap["control/budget_exhausted"] == 1.0
        assert snap["control/shed_groups"] == 3.0
        assert snap["control/nan_rollbacks"] == 1.0
        assert snap["cp/quarantines"] == 1.0
        assert snap["control/shed_active"] == 1.0
        assert snap["control/value/admission_frac"] == 0.5
        # shed admission stalls attribute through the serving audit's
        # constant-prefix derivation with the new "shed" reason
        from distrl_llm_tpu.serving_obs import SERVING_ADMISSION_STALLS

        telemetry.counter_add(f"{SERVING_ADMISSION_STALLS}/shed")
        assert telemetry.metrics_snapshot()[
            "serving/admission_stalls/shed"
        ] == 1.0

    def test_weight_bus_series_schema(self):
        """Schema pin for the weight-bus registry names (ISSUE 9): byte
        and push COUNTERS, plus the push→last-ack broadcast latency
        HISTOGRAM (summary-stat keys in the snapshot)."""
        from distrl_llm_tpu.distributed import resilience as r

        assert r.CP_DISPATCH_BYTES == "cp/dispatch_bytes"
        assert r.CP_WEIGHT_BYTES == "cp/weight_bytes_sent"
        assert r.CP_WEIGHT_PUSHES == "cp/weight_pushes"
        assert r.CP_WEIGHT_FULL_SYNCS == "cp/weight_full_syncs"
        assert r.CP_WEIGHT_REREQUESTS == "cp/weight_rerequests"
        assert r.CP_WEIGHT_BROADCAST_MS == "cp/weight_broadcast_ms"
        telemetry.counter_add(r.CP_DISPATCH_BYTES, 1000)
        telemetry.counter_add(r.CP_WEIGHT_BYTES, 2048)
        telemetry.counter_add(r.CP_WEIGHT_PUSHES, 2)
        telemetry.counter_add(r.CP_WEIGHT_FULL_SYNCS)
        telemetry.counter_add(r.CP_WEIGHT_REREQUESTS)
        telemetry.hist_observe(r.CP_WEIGHT_BROADCAST_MS, 5.0)
        telemetry.hist_observe(r.CP_WEIGHT_BROADCAST_MS, 15.0)
        snap = telemetry.metrics_snapshot()
        assert snap["cp/dispatch_bytes"] == 1000.0
        assert snap["cp/weight_bytes_sent"] == 2048.0
        assert snap["cp/weight_pushes"] == 2.0
        assert snap["cp/weight_full_syncs"] == 1.0
        assert snap["cp/weight_rerequests"] == 1.0
        assert snap["cp/weight_broadcast_ms_count"] == 2
        assert snap["cp/weight_broadcast_ms_mean"] == 10.0

    def test_backpressure_counter_schema(self):
        import threading

        from distrl_llm_tpu.rollout import Trajectory, TrajectoryBuffer

        buf = TrajectoryBuffer(1)
        t = Trajectory(problem="p", solution="s", answers=["a"],
                       token_lengths=[1])
        buf.put(t)
        th = threading.Thread(target=lambda: buf.put(t, timeout=0.05))
        th.start()
        th.join(timeout=5)
        snap = telemetry.metrics_snapshot()
        assert snap["rollout/backpressure_waits"] == 1.0

    def test_observe_snapshot_is_cumulative_and_nondestructive(self):
        """The live-endpoint view (ISSUE 8): counters report monotonic
        totals that survive metrics_snapshot's report-and-reset, gauges
        their last value, histograms cumulative count/sum/max — and
        reading it never consumes anything."""
        telemetry.counter_add("obs/gen_tokens", 10)
        telemetry.gauge_set("pool/occupancy", 0.5)
        telemetry.hist_observe("cp/rpc_dispatch_ms", 2.0)
        telemetry.hist_observe("cp/rpc_dispatch_ms", 4.0, count=3)
        snap = telemetry.observe_snapshot()
        assert snap["counters"]["obs/gen_tokens"] == 10.0
        assert snap["gauges"]["pool/occupancy"] == 0.5
        h = snap["hists"]["cp/rpc_dispatch_ms"]
        assert (h["count"], h["sum"], h["max"]) == (4.0, 14.0, 4.0)
        # + the cumulative bucket counts (ISSUE 13) — 2.0 in le=2.5,
        # 4.0×3 in le=5.0
        assert sum(h["buckets"]) == 4.0
        # the sink feed still reports-and-resets its delta…
        assert telemetry.metrics_snapshot()["obs/gen_tokens"] == 10.0
        telemetry.counter_add("obs/gen_tokens", 5)
        assert telemetry.metrics_snapshot()["obs/gen_tokens"] == 5.0
        # …while the cumulative view keeps the running total
        assert telemetry.observe_snapshot()["counters"][
            "obs/gen_tokens"] == 15.0

    def test_obs_series_schema(self):
        """Schema pin for the observability-plane registry names
        (ISSUE 8) and their TYPES: obs/gen_tokens, obs/compiles,
        obs/retraces, obs/incidents are COUNTERS; obs/hbm_live_bytes,
        obs/hbm_peak_bytes, obs/learner_idle_frac, obs/weight_sync_ms are
        GAUGES; engine/swap_latency_ms is a HISTOGRAM."""
        from distrl_llm_tpu import obs

        assert obs.OBS_GEN_TOKENS == "obs/gen_tokens"
        assert obs.OBS_HBM_LIVE == "obs/hbm_live_bytes"
        assert obs.OBS_HBM_PEAK == "obs/hbm_peak_bytes"
        assert obs.OBS_COMPILES == "obs/compiles"
        assert obs.OBS_RETRACES == "obs/retraces"
        assert obs.OBS_LEARNER_IDLE == "obs/learner_idle_frac"
        assert obs.OBS_WEIGHT_SYNC_MS == "obs/weight_sync_ms"
        assert obs.OBS_INCIDENTS == "obs/incidents"
        assert obs.SWAP_LATENCY_MS == "engine/swap_latency_ms"
        telemetry.counter_add(obs.OBS_GEN_TOKENS, 100)
        telemetry.counter_add(obs.OBS_COMPILES)
        telemetry.counter_add(obs.OBS_RETRACES)
        telemetry.counter_add(obs.OBS_INCIDENTS)
        telemetry.gauge_set(obs.OBS_HBM_LIVE, 10.0)
        telemetry.gauge_set(obs.OBS_HBM_PEAK, 20.0)
        telemetry.gauge_set(obs.OBS_LEARNER_IDLE, 0.25)
        telemetry.gauge_set(obs.OBS_LEARNER_IDLE, 0.5)  # gauge: last wins
        telemetry.gauge_set(obs.OBS_WEIGHT_SYNC_MS, 1.5)
        telemetry.hist_observe(obs.SWAP_LATENCY_MS, 3.0)
        snap = telemetry.metrics_snapshot()
        assert snap["obs/gen_tokens"] == 100.0
        assert snap["obs/compiles"] == 1.0
        assert snap["obs/retraces"] == 1.0
        assert snap["obs/incidents"] == 1.0
        assert snap["obs/hbm_live_bytes"] == 10.0
        assert snap["obs/hbm_peak_bytes"] == 20.0
        assert snap["obs/learner_idle_frac"] == 0.5
        assert snap["obs/weight_sync_ms"] == 1.5
        assert snap["engine/swap_latency_ms_count"] == 1.0
        # counters report-and-reset
        assert "obs/gen_tokens" not in telemetry.metrics_snapshot()

    def test_fleet_series_schema(self):
        """Schema pin for the fleet-aggregation names (ISSUE 8): all
        GAUGES (the aggregator republishes the fold on every refresh), plus
        cp/rejoin_epoch, the gauge the control plane bumps per re-admit."""
        from distrl_llm_tpu import obs
        from distrl_llm_tpu.distributed import resilience as r

        assert obs.FLEET_TOK_S == "fleet/tok_s"
        assert obs.FLEET_GEN_TOKENS == "fleet/gen_tokens_total"
        assert obs.FLEET_WORKERS_HEALTHY == "fleet/workers_healthy"
        assert obs.FLEET_WORKERS_TOTAL == "fleet/workers_total"
        assert obs.FLEET_REJOIN_EPOCH == "fleet/rejoin_epoch"
        # elastic-fleet pins (ISSUE 20): the autoscaler's target-size gauge
        # and the scale-event counter-as-gauge the supervisor republishes
        assert obs.FLEET_TARGET_WORKERS == "fleet/target_workers"
        assert obs.FLEET_SCALE_EVENTS == "fleet/scale_events"
        assert r.CP_REJOIN_EPOCH == "cp/rejoin_epoch"
        telemetry.gauge_set(obs.FLEET_TOK_S, 1200.0)
        telemetry.gauge_set(obs.FLEET_GEN_TOKENS, 4000.0)
        telemetry.gauge_set(obs.FLEET_WORKERS_HEALTHY, 2)
        telemetry.gauge_set(obs.FLEET_WORKERS_TOTAL, 2)
        telemetry.gauge_set(obs.FLEET_REJOIN_EPOCH, 1)
        telemetry.gauge_set(r.CP_REJOIN_EPOCH, 1)
        snap = telemetry.metrics_snapshot()
        assert snap["fleet/tok_s"] == 1200.0
        assert snap["fleet/gen_tokens_total"] == 4000.0
        assert snap["fleet/workers_healthy"] == 2.0
        assert snap["fleet/workers_total"] == 2.0
        assert snap["fleet/rejoin_epoch"] == 1.0
        assert snap["cp/rejoin_epoch"] == 1.0

    def test_ingest_remote_stores_metrics_without_tracing(self):
        """The obs piggyback must work on untraced drivers: the snapshot
        lands in the fleet table while the event list stays empty (nothing
        would ever export it)."""
        telemetry.ingest_remote(
            {"events": [{"ph": "X", "name": "worker/echo", "ts": 1,
                         "dur": 1, "tid": 9, "args": {}}],
             "threads": {},
             "metrics": {"counters": {"obs/gen_tokens": 64.0},
                         "gauges": {}, "hists": {}}},
            track="worker 127.0.0.1:7001",
        )
        assert events() == []  # untraced: span events dropped
        table = telemetry.remote_metrics()
        assert table["worker 127.0.0.1:7001"]["counters"][
            "obs/gen_tokens"] == 64.0
        assert "_ts" in table["worker 127.0.0.1:7001"]

    def test_serving_series_schema(self):
        """Schema pin for the serving-observability registry names
        (ISSUE 13) and their TYPES: serving/ttft_ms, serving/tpot_ms,
        serving/queue_wait_ms, serving/e2e_ms are HISTOGRAMS;
        serving/live_slots, serving/queue_depth, serving/free_pages are
        GAUGES (one sample per admission pass, Perfetto counter tracks);
        serving/admission_passes, serving/declined_passes,
        serving/records_closed, serving/ring_evictions and the per-reason
        serving/admission_stalls/<reason> derivations are COUNTERS. The
        fleet fold republishes fleet/serving_* GAUGES."""
        from distrl_llm_tpu import serving_obs as so

        assert so.SERVING_TTFT_MS == "serving/ttft_ms"
        assert so.SERVING_TPOT_MS == "serving/tpot_ms"
        assert so.SERVING_QUEUE_WAIT_MS == "serving/queue_wait_ms"
        assert so.SERVING_E2E_MS == "serving/e2e_ms"
        assert so.SERVING_ADMISSION_STALLS == "serving/admission_stalls"
        assert so.SERVING_DECLINED_PASSES == "serving/declined_passes"
        assert so.SERVING_ADMISSION_PASSES == "serving/admission_passes"
        assert so.SERVING_LIVE_SLOTS == "serving/live_slots"
        assert so.SERVING_QUEUE_DEPTH == "serving/queue_depth"
        assert so.SERVING_FREE_PAGES == "serving/free_pages"
        assert so.SERVING_RECORDS_CLOSED == "serving/records_closed"
        assert so.SERVING_RING_EVICTIONS == "serving/ring_evictions"
        assert so.FLEET_SERVING_TTFT_MEAN_MS == "fleet/serving_ttft_ms_mean"
        assert so.FLEET_SERVING_TTFT_MAX_MS == "fleet/serving_ttft_ms_max"
        assert (so.FLEET_SERVING_QUEUE_WAIT_MEAN_MS
                == "fleet/serving_queue_wait_ms_mean")
        assert (so.FLEET_SERVING_QUEUE_WAIT_MAX_MS
                == "fleet/serving_queue_wait_ms_max")
        assert so.FLEET_SERVING_STALLS == "fleet/serving_admission_stalls"
        # "quota" (ISSUE 19): the gateway's per-tenant token budget joined
        # the decline vocabulary — conservation extends, never breaks
        assert so.STALL_REASONS == (
            "no_slots", "no_pages", "chain_cap", "budget_wedge", "shed",
            "quota",
        )
        # per-class breakdown prefix rides NEXT to the flat counters
        # (separate root so the fleet fold's rsplit can't double-count)
        assert so.SERVING_CLASS_STALLS == "serving/class_stalls"
        for name in (so.SERVING_TTFT_MS, so.SERVING_TPOT_MS,
                     so.SERVING_QUEUE_WAIT_MS, so.SERVING_E2E_MS):
            telemetry.hist_observe(name, 5.0)
        telemetry.gauge_set(so.SERVING_LIVE_SLOTS, 3.0)
        telemetry.gauge_set(so.SERVING_QUEUE_DEPTH, 2.0)
        telemetry.gauge_set(so.SERVING_FREE_PAGES, 7.0)
        telemetry.counter_add(so.SERVING_ADMISSION_PASSES)
        telemetry.counter_add(so.SERVING_DECLINED_PASSES)
        telemetry.counter_add(so.SERVING_RECORDS_CLOSED)
        telemetry.counter_add(so.SERVING_RING_EVICTIONS)
        telemetry.counter_add(f"{so.SERVING_ADMISSION_STALLS}/no_pages")
        snap = telemetry.metrics_snapshot()
        assert snap["serving/ttft_ms_count"] == 1.0
        assert snap["serving/tpot_ms_count"] == 1.0
        assert snap["serving/queue_wait_ms_count"] == 1.0
        assert snap["serving/e2e_ms_count"] == 1.0
        assert snap["serving/live_slots"] == 3.0
        assert snap["serving/queue_depth"] == 2.0
        assert snap["serving/free_pages"] == 7.0
        assert snap["serving/admission_passes"] == 1.0
        assert snap["serving/declined_passes"] == 1.0
        assert snap["serving/records_closed"] == 1.0
        assert snap["serving/ring_evictions"] == 1.0
        assert snap["serving/admission_stalls/no_pages"] == 1.0

    def test_gateway_series_schema(self):
        """Schema pin for the serving-gateway registry names (ISSUE 19)
        and their TYPES: gateway/requests, gateway/rejected,
        gateway/rounds, gateway/streamed_tokens, gateway/quota_denials and
        gateway/aged_promotions are COUNTERS (per-class / per-tenant
        breakdowns derive with the constant-prefix pattern);
        gateway/queue_depth and gateway/quota_reserved are GAUGES."""
        from distrl_llm_tpu.gateway import scheduler as gw

        assert gw.GATEWAY_REQUESTS == "gateway/requests"
        assert gw.GATEWAY_REJECTED == "gateway/rejected"
        assert gw.GATEWAY_QUEUE_DEPTH == "gateway/queue_depth"
        assert gw.GATEWAY_ROUNDS == "gateway/rounds"
        assert gw.GATEWAY_STREAMED_TOKENS == "gateway/streamed_tokens"
        assert gw.GATEWAY_QUOTA_DENIALS == "gateway/quota_denials"
        assert gw.GATEWAY_QUOTA_RESERVED == "gateway/quota_reserved"
        assert gw.GATEWAY_AGED_PROMOTIONS == "gateway/aged_promotions"
        assert gw.PRIORITY_CLASSES == ("interactive", "batch", "scavenger")
        telemetry.counter_add(gw.GATEWAY_REQUESTS)
        telemetry.counter_add(f"{gw.GATEWAY_REQUESTS}/interactive")
        telemetry.counter_add(gw.GATEWAY_REJECTED)
        telemetry.counter_add(gw.GATEWAY_ROUNDS)
        telemetry.counter_add(gw.GATEWAY_STREAMED_TOKENS, 12.0)
        telemetry.counter_add(gw.GATEWAY_QUOTA_DENIALS)
        telemetry.counter_add(f"{gw.GATEWAY_QUOTA_DENIALS}/acme")
        telemetry.counter_add(gw.GATEWAY_AGED_PROMOTIONS)
        telemetry.gauge_set(gw.GATEWAY_QUEUE_DEPTH, 4.0)
        telemetry.gauge_set(gw.GATEWAY_QUOTA_RESERVED, 96.0)
        telemetry.gauge_set(f"{gw.GATEWAY_QUOTA_RESERVED}/acme", 96.0)
        snap = telemetry.metrics_snapshot()
        assert snap["gateway/requests"] == 1.0
        assert snap["gateway/requests/interactive"] == 1.0
        assert snap["gateway/rejected"] == 1.0
        assert snap["gateway/rounds"] == 1.0
        assert snap["gateway/streamed_tokens"] == 12.0
        assert snap["gateway/quota_denials"] == 1.0
        assert snap["gateway/quota_denials/acme"] == 1.0
        assert snap["gateway/aged_promotions"] == 1.0
        assert snap["gateway/queue_depth"] == 4.0
        assert snap["gateway/quota_reserved"] == 96.0
        assert snap["gateway/quota_reserved/acme"] == 96.0

    def test_learn_series_schema(self):
        """Schema pin for the training-dynamics registry names (ISSUE 16)
        and their TYPES: learn/entropy, learn/kl_behavior,
        learn/clip_frac, learn/ratio_cap_frac, learn/adv_mean,
        learn/adv_std, learn/adv_pos_frac, learn/reward_drift and the
        learn/grad_norm/<group> family (total + a0..b3 depth buckets) are
        GAUGES; learn/is_ratio is a HISTOGRAM (device-binned, replayed
        with the weighted count= idiom); learn/steps is a COUNTER."""
        from distrl_llm_tpu import learn_obs as lo

        assert lo.LEARN_ENTROPY == "learn/entropy"
        assert lo.LEARN_KL == "learn/kl_behavior"
        assert lo.LEARN_RATIO == "learn/is_ratio"
        assert lo.LEARN_CLIP_FRAC == "learn/clip_frac"
        assert lo.LEARN_CAP_FRAC == "learn/ratio_cap_frac"
        assert lo.LEARN_ADV_MEAN == "learn/adv_mean"
        assert lo.LEARN_ADV_STD == "learn/adv_std"
        assert lo.LEARN_ADV_POS_FRAC == "learn/adv_pos_frac"
        assert lo.LEARN_GRAD_NORM == "learn/grad_norm"
        assert lo.LEARN_GRAD_NORM_TOTAL == "learn/grad_norm/total"
        assert lo.LEARN_REWARD_DRIFT == "learn/reward_drift"
        assert lo.LEARN_STEPS == "learn/steps"
        for name in (lo.LEARN_ENTROPY, lo.LEARN_KL, lo.LEARN_CLIP_FRAC,
                     lo.LEARN_CAP_FRAC, lo.LEARN_ADV_MEAN,
                     lo.LEARN_ADV_STD, lo.LEARN_ADV_POS_FRAC,
                     lo.LEARN_GRAD_NORM_TOTAL, lo.LEARN_REWARD_DRIFT):
            telemetry.gauge_set(name, 0.5)
        group = "a0"
        telemetry.gauge_set(f"{lo.LEARN_GRAD_NORM}/{group}", 0.25)
        telemetry.hist_observe(lo.LEARN_RATIO, 1.0, count=3)
        telemetry.counter_add(lo.LEARN_STEPS)
        snap = telemetry.metrics_snapshot()
        assert snap["learn/entropy"] == 0.5
        assert snap["learn/kl_behavior"] == 0.5
        assert snap["learn/clip_frac"] == 0.5
        assert snap["learn/ratio_cap_frac"] == 0.5
        assert snap["learn/adv_mean"] == 0.5
        assert snap["learn/adv_std"] == 0.5
        assert snap["learn/adv_pos_frac"] == 0.5
        assert snap["learn/grad_norm/total"] == 0.5
        assert snap["learn/grad_norm/a0"] == 0.25
        assert snap["learn/reward_drift"] == 0.5
        assert snap["learn/is_ratio_count"] == 3.0
        assert snap["learn/steps"] == 1.0

    def test_pool_series_schema(self):
        """Schema pin for the tiered-KV-cache registry names (ISSUE 18)
        and their TYPES, all single-owned by engine/page_pool.py:
        pool/radix_hit_rate is a GAUGE (cumulative hit/lookup token
        ratio); pool/prefill_tok_saved, pool/evictions and
        pool/spilled_pages are COUNTERS; pool/restore_ms is a HISTOGRAM
        (host->device restore batches)."""
        from distrl_llm_tpu.engine import page_pool as pp

        assert pp.POOL_RADIX_HIT_RATE == "pool/radix_hit_rate"
        assert pp.POOL_PREFILL_TOK_SAVED == "pool/prefill_tok_saved"
        assert pp.POOL_EVICTIONS == "pool/evictions"
        assert pp.POOL_SPILLED_PAGES == "pool/spilled_pages"
        assert pp.POOL_RESTORE_MS == "pool/restore_ms"
        telemetry.gauge_set(pp.POOL_RADIX_HIT_RATE, 0.5)
        telemetry.counter_add(pp.POOL_PREFILL_TOK_SAVED, 16.0)
        telemetry.counter_add(pp.POOL_EVICTIONS)
        telemetry.counter_add(pp.POOL_SPILLED_PAGES, 2.0)
        telemetry.hist_observe(pp.POOL_RESTORE_MS, 1.5)
        snap = telemetry.metrics_snapshot()
        assert snap["pool/radix_hit_rate"] == 0.5
        assert snap["pool/prefill_tok_saved"] == 16.0
        assert snap["pool/evictions"] == 1.0
        assert snap["pool/spilled_pages"] == 2.0
        assert snap["pool/restore_ms_count"] == 1.0

    def test_round_ledger_series_schema(self):
        """Schema pin for the names ISSUE 56 added and their TYPES:
        engine/snapshot_launch and host/gc are SPANS, engine/boundary_ms a
        HISTOGRAM (served as Prometheus buckets by the obs endpoint),
        engine/boundary_median_ms a GAUGE, engine/stalled_boundaries and
        host/gc_full_ms COUNTERS, and the ledger a ring of ROUND_RING
        records beside the registry."""
        assert telemetry.ENGINE_SNAPSHOT_LAUNCH == "engine/snapshot_launch"
        assert telemetry.HOST_GC == "host/gc"
        assert telemetry.HOST_GC_FULL_MS == "host/gc_full_ms"
        assert telemetry.ENGINE_BOUNDARY_MS == "engine/boundary_ms"
        assert telemetry.ENGINE_BOUNDARY_MEDIAN_MS == "engine/boundary_median_ms"
        assert telemetry.ENGINE_STALLED_BOUNDARIES == "engine/stalled_boundaries"
        assert telemetry.ROUND_RING == 64
        # host spans are not device scopes: no jitted program carries them
        assert not {telemetry.ENGINE_SNAPSHOT_LAUNCH, telemetry.HOST_GC} & set(
            telemetry.SCOPE_NAMES)
        telemetry.hist_observe(telemetry.ENGINE_BOUNDARY_MS, 212.0)
        telemetry.hist_observe(telemetry.ENGINE_BOUNDARY_MS, 2354.9)
        telemetry.gauge_set(telemetry.ENGINE_BOUNDARY_MEDIAN_MS, 212.0)
        telemetry.counter_add(telemetry.ENGINE_STALLED_BOUNDARIES, 1)
        telemetry.counter_add(telemetry.HOST_GC_FULL_MS, 31.5)
        live = telemetry.observe_snapshot()
        buckets = live["hists"]["engine/boundary_ms"]["buckets"]
        at = telemetry.HIST_BUCKET_BOUNDS.index
        assert buckets[at(250.0)] == 1 and buckets[at(2500.0)] == 1 and sum(buckets) == 2
        snap = telemetry.metrics_snapshot()
        assert snap["engine/boundary_ms_count"] == 2.0
        assert snap["engine/boundary_ms_max"] == 2354.9
        assert snap["engine/boundary_median_ms"] == 212.0
        assert snap["engine/stalled_boundaries"] == 1.0
        assert snap["host/gc_full_ms"] == 31.5
        telemetry.configure(enabled=True)
        with telemetry.span(telemetry.ENGINE_SNAPSHOT_LAUNCH, fused=True):
            pass
        (ev,) = [e for e in events() if e["name"] == "engine/snapshot_launch"]
        assert ev["ph"] == "X" and ev["args"] == {"fused": True}
        assert telemetry.round_records() == []
        assert telemetry.round_filed({"wall_s": 1.0}) == {"wall_s": 1.0, "round": 0}
        assert telemetry.round_records() == [{"wall_s": 1.0, "round": 0}]

    def test_observe_snapshot_carries_hist_buckets(self):
        """Cumulative per-bucket counts ride observe_snapshot (the obs
        endpoint's and the worker blob's feed), aligned to
        HIST_BUCKET_BOUNDS with one trailing overflow slot; the
        metrics_snapshot (report-and-reset sink feed) is untouched."""
        from distrl_llm_tpu.serving_obs import SERVING_QUEUE_WAIT_MS

        telemetry.hist_observe(SERVING_QUEUE_WAIT_MS, 3.0, count=2)
        telemetry.hist_observe(SERVING_QUEUE_WAIT_MS, 99999.0)
        snap = telemetry.observe_snapshot()
        h = snap["hists"][SERVING_QUEUE_WAIT_MS]
        buckets = h["buckets"]
        assert len(buckets) == len(telemetry.HIST_BUCKET_BOUNDS) + 1
        # 3.0 lands in the le=5.0 bucket (index of first bound >= value)
        assert buckets[telemetry.HIST_BUCKET_BOUNDS.index(5.0)] == 2.0
        assert buckets[-1] == 1.0  # overflow slot (> last bound)
        assert sum(buckets) == h["count"] == 3.0
        # sink feed unchanged: summary stats only, then reset
        sink = telemetry.metrics_snapshot()
        assert sink["serving/queue_wait_ms_count"] == 3.0
        assert not any(k.endswith("_buckets") for k in sink)

    def test_hist_observe_count_prebinned(self):
        """hist_observe(count=N) records the observation N times in ONE
        call — the contract the engine's device-side emit histogram
        relies on (one Python call per bucket per round, not one per
        slot-step); count=0 is a no-op that must not touch the series."""
        telemetry.hist_observe("engine/spec_emit_tokens", 3.0, count=4)
        telemetry.hist_observe("engine/spec_emit_tokens", 5.0, count=1)
        telemetry.hist_observe("engine/spec_emit_tokens", 9.0, count=0)
        snap = telemetry.metrics_snapshot()
        assert snap["engine/spec_emit_tokens_count"] == 5
        assert snap["engine/spec_emit_tokens_mean"] == pytest.approx(3.4)
        assert snap["engine/spec_emit_tokens_max"] == 5.0
        assert telemetry.metrics_snapshot() == {}  # 0-count left no trace

    def test_spec_series_schema(self):
        """Schema pin for the speculative-decoding registry names
        (ISSUE 6) and their TYPES: engine/spec_accept_rate is a GAUGE
        (last round wins), engine/spec_emit_tokens a HISTOGRAM (the
        per-step emit distribution, pre-binned device-side), and
        engine/spec_verify_grid_steps + engine/spec_draft_resizes are
        COUNTERS (report-and-reset deltas)."""
        telemetry.gauge_set("engine/spec_accept_rate", 0.5)
        telemetry.gauge_set("engine/spec_accept_rate", 0.8)
        for n, c in enumerate([0, 3, 2, 1, 2]):  # emit 0..4 tokens/step
            telemetry.hist_observe("engine/spec_emit_tokens", float(n),
                                   count=c)
        telemetry.counter_add("engine/spec_verify_grid_steps", 23040)
        telemetry.counter_add("engine/spec_verify_grid_steps", 23040)
        telemetry.counter_add("engine/spec_draft_resizes")
        snap = telemetry.metrics_snapshot()
        assert snap["engine/spec_accept_rate"] == 0.8
        assert snap["engine/spec_emit_tokens_count"] == 8
        assert snap["engine/spec_emit_tokens_mean"] == pytest.approx(2.25)
        assert snap["engine/spec_verify_grid_steps"] == 46080
        assert snap["engine/spec_draft_resizes"] == 1.0
        # counters reset; the gauge persists only until next snapshot too
        assert "engine/spec_verify_grid_steps" not in (
            telemetry.metrics_snapshot()
        )

    def test_spec_round_emits_series_end_to_end(self):
        """The engine actually emits the pinned series: one tiny
        speculative refill round must land engine/spec_accept_rate,
        engine/spec_emit_tokens and engine/spec_verify_grid_steps in the
        snapshot, with the histogram's token count conserving the round's
        generated volume (emitted = generated − admitted first tokens)."""
        import jax
        import jax.numpy as jnp

        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
        from distrl_llm_tpu.models import TINY, init_params

        params = init_params(jax.random.PRNGKey(7), TINY)
        ids = np.random.default_rng(1).integers(
            1, TINY.vocab_size, size=(2, 8)).astype(np.int32)
        mask = np.ones((2, 8), np.int32)
        engine = PagedGenerationEngine(
            TINY, max_prompt_tokens=8, max_new_tokens=8,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32, page_size=8,
            scheduler="refill", max_concurrent_rows=2, spec_draft=2,
            autotune=False,
        )
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=8, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        snap = telemetry.metrics_snapshot()
        assert 0.0 <= snap["engine/spec_accept_rate"] <= 1.0
        # CPU dispatch resolves to the jnp reference (no Pallas grid), so
        # the grid counter stays honestly SILENT — same contract as
        # test_paged_grid_telemetry_reference_path_is_silent; on TPU the
        # engine emits it (asserted in tools/spec bench artifacts)
        assert "engine/spec_verify_grid_steps" not in snap
        emitted = snap["engine/spec_emit_tokens_count"] * snap[
            "engine/spec_emit_tokens_mean"]
        assert emitted == pytest.approx(
            int(res.lengths.sum()) - res.lengths.size)


class TestMfuMath:
    def test_flops_per_token_hand_computed_tiny(self):
        """TINY: hidden 64, inter 128, 2 layers, 4 heads × d16 (q_dim 64),
        2 kv heads (kv_dim 32), vocab 256 — worked by hand:
        per-layer matmul params = 64·64 (q) + 2·64·32 (kv) + 64·64 (o)
        + 3·64·128 (mlp) = 36,864; + lm_head 64·256 = 16,384
        → matmul params 90,112 → 180,224 FLOPs/token at zero context."""
        assert TINY.matmul_param_count == 90_112
        assert TINY.decode_flops_per_token(0) == 180_224.0
        # attention adds 4·L·q_dim·kv = 4·2·64·10 = 5,120 at kv len 10
        assert TINY.decode_flops_per_token(10) == 185_344.0
        # train: 3× forward at mean key length seq/2
        assert TINY.train_flops_per_token(20) == 3.0 * 185_344.0

    def test_mfu_is_achieved_over_peak(self):
        fpt = TINY.decode_flops_per_token(10)
        assert telemetry.mfu(1000.0, fpt, 1e9) == pytest.approx(
            1000.0 * 185_344.0 / 1e9
        )

    def test_peak_flops_env_override(self, monkeypatch):
        monkeypatch.setenv("DISTRL_PEAK_FLOPS", "1.23e14")
        assert telemetry.device_peak_flops() == 1.23e14


class TestRemoteBlobUnit:
    def test_drain_and_ingest_assign_worker_track(self):
        telemetry.configure(enabled=True)
        with telemetry.span("worker/generate", tokens=5):
            pass
        blob = telemetry.drain_remote_blob()
        assert events() == []  # drained
        assert len(blob["events"]) == 1
        telemetry.ingest_remote(blob, track="worker 127.0.0.1:1234")
        telemetry.ingest_remote(
            {"events": [{"ph": "X", "name": "worker/echo", "ts": 1,
                         "dur": 1, "tid": 9, "args": {}}], "threads": {}},
            track="worker 127.0.0.1:9999",
        )
        pids = {e["pid"] for e in events()}
        assert len(pids) == 2  # one track per worker

    def test_empty_drain_is_none(self):
        assert telemetry.drain_remote_blob() is None

    def test_ingest_dropped_when_disabled(self):
        """A traced worker feeding an untraced driver must not grow the
        driver's event list (nothing would ever export it)."""
        telemetry.ingest_remote(
            {"events": [{"ph": "X", "name": "worker/echo", "ts": 1,
                         "dur": 1, "tid": 9, "args": {}}], "threads": {}},
            track="worker 127.0.0.1:1",
        )
        assert events() == []


class TestTrainerIntegration:
    """trace_dir wiring through the Trainer on the FakeEngine: spans record
    under the reference timing names and one Chrome-trace JSON lands in
    trace_dir at shutdown."""

    def _trainer(self, tmp_path, **cfg_kw):
        import jax

        from distrl_llm_tpu.config import TrainConfig
        from distrl_llm_tpu.engine.fake import FakeEngine
        from distrl_llm_tpu.metrics import MemorySink
        from distrl_llm_tpu.models import TINY as MTINY, init_params
        from distrl_llm_tpu.rewards import reward_function
        from distrl_llm_tpu.tokenizer import CharTokenizer
        from distrl_llm_tpu.trainer import Trainer

        config = TrainConfig(
            model="tiny", episodes=1, batch_size=4, num_candidates=4, topk=4,
            train_batch_size=4, max_prompt_tokens=16, max_new_tokens=24,
            number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
            eval_every=0, save_every=0, metrics_backend="null", lr=1e-3,
            max_lora_rank=4, lora_alpha=8, trace_dir=str(tmp_path),
            **cfg_kw,
        )
        tok = CharTokenizer()
        problems = [f"q {c}" for c in "abcdefgh"]
        train = {"problem": problems,
                 "solution": [p.strip()[-1].upper() for p in problems]}
        sink = MemorySink()
        trainer = Trainer(
            train, {k: v[:4] for k, v in train.items()},
            reward_function, config, tokenizer=tok,
            engine=FakeEngine(tok, lambda p, j: "<answer>x</answer>",
                              max_new_tokens=config.max_new_tokens),
            base_params=init_params(jax.random.PRNGKey(0), MTINY),
            model_cfg=MTINY, sink=sink,
        )
        return trainer, sink

    def test_trace_dir_enables_and_exports(self, tmp_path):
        trainer, sink = self._trainer(tmp_path)
        assert telemetry.enabled()  # __init__ armed recording
        trainer.train()
        path = tmp_path / "trace.json"
        assert path.exists()
        doc = json.loads(path.read_text())
        names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert {"driver/generation", "driver/reward",
                "driver/update"} <= names
        assert doc["metadata"]["decode_flops_per_token"] > 0
        # metric-name parity survives the PhaseTimer → spans swap
        steps = [m for _, m in sink.records if "loss" in m]
        assert steps and all(
            "timing/generation_duration" in m
            and "timing/update_duration" in m for m in steps
        )

    def test_trace_steps_window_closes_early(self, tmp_path):
        trainer, _ = self._trainer(tmp_path, trace_steps=1)
        trainer.train()  # 8 problems / batch 4 = 2 steps; window = 1
        assert (tmp_path / "trace.json").exists()
        assert not telemetry.enabled()  # recording stopped at the window


@pytest.mark.distributed
@pytest.mark.skipif(not native_available(), reason="g++ not available")
class TestWorkerBlobMerge:
    """The cross-process acceptance piece: a traced worker subprocess ships
    its spans back in the RPC response and the driver merges them under a
    per-worker track."""

    def test_multiprocess_round_merges_worker_spans(self, tmp_path):
        from distrl_llm_tpu.distributed.control_plane import DriverClient

        telemetry.configure(enabled=True)
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "distrl_llm_tpu.distributed.worker_main", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTRL_TRACE": "1"},
        )
        try:
            line = proc.stdout.readline().strip()
            assert line.startswith("PORT "), line
            driver = DriverClient([("127.0.0.1", int(line.split()[1]))])
            batch = {"answers": [["<answer>4</answer>", "wrong"]],
                     "solution": [["4", "4"]]}
            (rewards,) = driver.dispatch_objects(
                [("rollout_rewards", batch)], timeout_ms=30_000
            )
            # the RPC result itself is unchanged by the piggybacked blob
            assert np.asarray(rewards[0]).shape == (2, 2)
            driver.shutdown()
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)

        # worker spans landed under a per-worker track…
        worker_evs = [e for e in events() if e.get("pid", 0) >= 100]
        assert any(
            e["name"] == "worker/rollout_rewards" for e in worker_evs
        ), events()
        # …the driver recorded its own dispatch span and RPC latency…
        assert any(e["name"] == "cp/dispatch" for e in events())
        snap = telemetry.metrics_snapshot()
        assert snap["cp/rpc_dispatch_ms_count"] >= 1
        # …and the export names the worker track
        path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        track_names = {
            e["args"]["name"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert any(n.startswith("worker 127.0.0.1:") for n in track_names)
        assert "driver" in track_names

    def test_untraced_worker_sends_plain_result(self):
        """Without DISTRL_TRACE the worker must answer with the plain
        MSG_RESULT frame (no envelope) — zero overhead on untraced runs."""
        from distrl_llm_tpu.distributed.control_plane import DriverClient

        proc = subprocess.Popen(
            [sys.executable, "-m",
             "distrl_llm_tpu.distributed.worker_main", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTRL_TRACE": "0"},
        )
        try:
            line = proc.stdout.readline().strip()
            driver = DriverClient([("127.0.0.1", int(line.split()[1]))])
            out = driver.dispatch_objects([("echo", 42)], timeout_ms=10_000)
            assert out == [42]
            driver.shutdown()
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        assert all(e.get("pid", 0) < 100 for e in events())
