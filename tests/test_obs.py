"""Observability-plane tests (ISSUE 8): exposition formats (Prometheus +
JSON), the live endpoint, fleet aggregation math from synthetic worker
snapshots, the flight-recorder ring bound/eviction, sentinel trigger
determinism (seeded NaN → exactly one incident bundle), the TraceProfiler
capture guards, and trace_report's MFU line."""

import json
import os
import urllib.request

import pytest

from distrl_llm_tpu import obs, telemetry


@pytest.fixture(autouse=True)
def clean_state():
    """Telemetry and the obs tables are process-global; every test starts
    and ends empty."""
    telemetry.reset()
    telemetry.configure(enabled=False)
    obs.reset_compile_tracker()
    yield
    telemetry.reset()
    telemetry.configure(enabled=False)
    obs.reset_compile_tracker()


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read()


class TestPrometheusExposition:
    def test_counter_gauge_hist_formatting(self):
        snap = {
            "counters": {"obs/gen_tokens": 128.0},
            "gauges": {"pool/occupancy": 0.5},
            "hists": {"cp/rpc_dispatch_ms": {
                "count": 3.0, "sum": 9.0, "max": 5.0,
            }},
        }
        text = obs.prometheus_text(snap)
        assert "# TYPE distrl_obs_gen_tokens counter" in text
        assert "distrl_obs_gen_tokens 128.0" in text
        assert "# TYPE distrl_pool_occupancy gauge" in text
        assert "distrl_pool_occupancy 0.5" in text
        # histograms are REAL Prometheus histogram types (ISSUE 13): one
        # TYPE line for the family, _bucket/_count/_sum samples, plus the
        # _max gauge the summary always carried. A snapshot without
        # bucket data degrades to the +Inf bucket alone.
        assert "# TYPE distrl_cp_rpc_dispatch_ms histogram" in text
        assert 'distrl_cp_rpc_dispatch_ms_bucket{le="+Inf"} 3.0' in text
        assert "distrl_cp_rpc_dispatch_ms_count 3.0" in text
        assert "distrl_cp_rpc_dispatch_ms_sum 9.0" in text
        assert "# TYPE distrl_cp_rpc_dispatch_ms_max gauge" in text
        assert "distrl_cp_rpc_dispatch_ms_max 5.0" in text
        assert text.endswith("\n")

    def test_histogram_buckets_cumulative_le(self):
        """Real registry observations render as CUMULATIVE bucket counts
        over telemetry.HIST_BUCKET_BOUNDS with inclusive-le semantics —
        the exact exposition histogram_quantile() consumes (ISSUE 13:
        serving/ttft_ms percentiles must be scrapable by standard
        tooling, not summary stats only)."""
        from distrl_llm_tpu.serving_obs import SERVING_TTFT_MS

        for v in (0.5, 3.0, 3.0, 40.0, 99.0, 70000.0):
            telemetry.hist_observe(SERVING_TTFT_MS, v)
        text = obs.prometheus_text()
        # le="0.5" is inclusive: the 0.5 observation lands IN it
        assert 'distrl_serving_ttft_ms_bucket{le="0.5"} 1.0' in text
        assert 'distrl_serving_ttft_ms_bucket{le="5.0"} 3.0' in text
        assert 'distrl_serving_ttft_ms_bucket{le="50.0"} 4.0' in text
        assert 'distrl_serving_ttft_ms_bucket{le="100.0"} 5.0' in text
        # the 70000 observation overflows the ladder: only +Inf holds it
        assert 'distrl_serving_ttft_ms_bucket{le="60000.0"} 5.0' in text
        assert 'distrl_serving_ttft_ms_bucket{le="+Inf"} 6.0' in text
        assert "distrl_serving_ttft_ms_count 6.0" in text

    def test_name_sanitization(self):
        text = obs.prometheus_text({
            "counters": {"obs/hbm_peak_bytes/generation": 1.0},
            "gauges": {}, "hists": {},
        })
        # every exposed name is a legal Prometheus identifier
        for line in text.splitlines():
            if line and not line.startswith("#"):
                name = line.split()[0].split("{")[0]
                assert name.replace("_", "").replace(":", "").isalnum(), line
                assert name.startswith("distrl_")

    def test_fleet_worker_labels(self):
        fleet = {
            "workers": [
                {"address": "10.0.0.1:7001", "healthy": True},
                {"address": "10.0.0.2:7001", "healthy": False},
            ],
            "worker_metrics": {
                "10.0.0.1:7001": {"gen_tokens": 640.0},
            },
        }
        text = obs.prometheus_text(
            {"counters": {}, "gauges": {}, "hists": {}}, fleet=fleet
        )
        assert (
            'distrl_fleet_worker_healthy{worker="10.0.0.1:7001"} 1' in text
        )
        assert (
            'distrl_fleet_worker_healthy{worker="10.0.0.2:7001"} 0' in text
        )
        assert (
            'distrl_fleet_worker_gen_tokens{worker="10.0.0.1:7001"} 640.0'
            in text
        )


class TestMetricsServer:
    def test_scrape_prometheus_and_json(self):
        telemetry.counter_add(obs.OBS_GEN_TOKENS, 42)
        telemetry.gauge_set("pool/occupancy", 0.25)
        server = obs.MetricsServer(0)
        try:
            text = _get(f"{server.url}/metrics").decode()
            assert "distrl_obs_gen_tokens 42.0" in text
            doc = json.loads(_get(f"{server.url}/metrics.json"))
            assert doc["counters"]["obs/gen_tokens"] == 42.0
            assert doc["gauges"]["pool/occupancy"] == 0.25
            assert doc["fleet"] is None  # no fleet provider on this server
            assert "compiles" in doc and "hbm" in doc
            assert _get(f"{server.url}/healthz") == b"ok\n"
        finally:
            server.close()

    def test_unknown_path_404_and_close_idempotent(self):
        server = obs.MetricsServer(0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{server.url}/nope")
            assert ei.value.code == 404
        finally:
            server.close()
            server.close()  # idempotent

    def test_fleet_provider_feeds_scrapes(self):
        fleet = {
            "workers": [{"address": "w:1", "healthy": True}],
            "worker_metrics": {"w:1": {"gen_tokens": 7.0}},
            "tok_s": 3.5,
        }
        server = obs.MetricsServer(0, fleet_provider=lambda: fleet)
        try:
            text = _get(f"{server.url}/metrics").decode()
            assert 'distrl_fleet_worker_healthy{worker="w:1"} 1' in text
            doc = json.loads(_get(f"{server.url}/metrics.json"))
            assert doc["fleet"]["tok_s"] == 3.5
        finally:
            server.close()


class _FakeDriver:
    """The DriverClient surface FleetAggregator consumes."""

    def __init__(self):
        self.rejoin_epoch = 0
        self._states = [
            {"address": "h1:1", "healthy": True, "cold": False},
            {"address": "h2:2", "healthy": True, "cold": False},
        ]

    def worker_states(self):
        return [dict(s) for s in self._states]


def _worker_snapshot(track: str, tokens: float, ts: float,
                     pid: int | None = None) -> None:
    """Synthesize the piggybacked snapshot ingest_remote would store."""
    metrics = {"counters": {obs.OBS_GEN_TOKENS: tokens},
               "gauges": {}, "hists": {}}
    if pid is not None:
        metrics["pid"] = pid
    telemetry.ingest_remote(
        {"events": [], "threads": {}, "metrics": metrics},
        track=track,
    )
    # pin the receive timestamp for deterministic rate math
    with telemetry._STATE.lock:
        telemetry._STATE.remote_metrics[track]["_ts"] = ts


class TestFleetAggregation:
    def test_tok_s_from_counter_deltas(self):
        driver = _FakeDriver()
        agg = obs.FleetAggregator(driver, min_refresh_s=0.0)
        _worker_snapshot("worker h1:1", 100.0, ts=10.0)
        _worker_snapshot("worker h2:2", 50.0, ts=10.0)
        fleet = agg.refresh(force=True)
        assert fleet["tok_s"] == 0.0  # first refresh: no window yet
        assert fleet["gen_tokens_total"] == 150.0
        assert fleet["workers_healthy"] == 2
        # 2 s later: +400 tokens on h1, +100 on h2 → 200 + 50 tok/s
        _worker_snapshot("worker h1:1", 500.0, ts=12.0)
        _worker_snapshot("worker h2:2", 150.0, ts=12.0)
        fleet = agg.refresh(force=True)
        assert fleet["tok_s"] == pytest.approx(250.0)
        assert fleet["gen_tokens_total"] == 650.0
        # per-worker detail keyed by bare address (track prefix stripped)
        assert fleet["worker_metrics"]["h1:1"]["gen_tokens"] == 500.0

    def test_worker_restart_never_negative(self):
        """A restarted worker's counter resets to ~0: its window must
        contribute zero rate (not a negative one), and the dead
        incarnation's count stays in the cumulative totals — a published
        total that regresses breaks every monotonic consumer."""
        agg = obs.FleetAggregator(_FakeDriver(), min_refresh_s=0.0)
        _worker_snapshot("worker h1:1", 1000.0, ts=10.0)
        first = agg.refresh(force=True)
        assert first["gen_tokens_total"] == 1000.0
        _worker_snapshot("worker h1:1", 5.0, ts=12.0)  # restarted
        fleet = agg.refresh(force=True)
        assert fleet["tok_s"] == 0.0
        assert fleet["gen_tokens_total"] == 1005.0  # retired + fresh
        assert fleet["worker_metrics"]["h1:1"]["gen_tokens"] == 1005.0
        # the next post-restart window rates normally again
        _worker_snapshot("worker h1:1", 105.0, ts=13.0)
        fleet = agg.refresh(force=True)
        assert fleet["tok_s"] == pytest.approx(100.0)
        assert fleet["gen_tokens_total"] == 1105.0

    def test_pid_change_detects_fast_restart(self):
        """A restarted worker that already out-generated its predecessor
        within one refresh gap shows NO counter regression — the exported
        pid is the exact restart signal, so the dead incarnation's count
        is still retired into the total and the bogus cross-incarnation
        delta contributes zero rate."""
        agg = obs.FleetAggregator(_FakeDriver(), min_refresh_s=0.0)
        _worker_snapshot("worker h1:1", 100.0, ts=10.0, pid=1111)
        agg.refresh(force=True)
        # new incarnation (pid 2222) already at 150 > 100
        _worker_snapshot("worker h1:1", 150.0, ts=12.0, pid=2222)
        fleet = agg.refresh(force=True)
        assert fleet["tok_s"] == 0.0  # 50-token "delta" spans a restart
        assert fleet["gen_tokens_total"] == 250.0  # 100 retired + 150

    def test_publishes_fleet_gauges_and_health(self):
        driver = _FakeDriver()
        driver.rejoin_epoch = 3
        driver._states[1]["healthy"] = False
        agg = obs.FleetAggregator(driver, min_refresh_s=0.0)
        fleet = agg.refresh(force=True)
        assert fleet["rejoin_epoch"] == 3
        assert fleet["workers_healthy"] == 1
        snap = telemetry.metrics_snapshot()
        assert snap["fleet/rejoin_epoch"] == 3.0
        assert snap["fleet/workers_healthy"] == 1.0
        assert snap["fleet/workers_total"] == 2.0
        assert snap["fleet/tok_s"] == 0.0

    def test_min_refresh_rate_limits(self):
        agg = obs.FleetAggregator(_FakeDriver(), min_refresh_s=3600.0)
        first = agg.refresh()
        _worker_snapshot("worker h1:1", 9.0, ts=99.0)
        assert agg.refresh() is first  # cached within the window
        assert agg.refresh(force=True) is not first

    def test_scale_in_folds_retired_worker_and_drops_track(self):
        """Elastic scale-in (ISSUE 20): a retired worker's cumulative count
        folds into the fleet base (gen_tokens_total stays monotone across
        the event), its track leaves the live table AND the telemetry
        fleet table, and the membership accounting excludes the terminal
        slot while still listing it in the worker states."""
        driver = _FakeDriver()
        agg = obs.FleetAggregator(driver, min_refresh_s=0.0)
        _worker_snapshot("worker h1:1", 300.0, ts=10.0)
        _worker_snapshot("worker h2:2", 200.0, ts=10.0)
        fleet = agg.refresh(force=True)
        assert fleet["gen_tokens_total"] == 500.0
        assert fleet["workers_total"] == 2

        # h2 retires (graceful drain): terminal membership state
        driver._states[1]["healthy"] = False
        driver._states[1]["retired"] = True
        fleet = agg.refresh(force=True)
        assert fleet["gen_tokens_total"] == 500.0  # monotone across fold
        assert "h2:2" not in fleet["worker_metrics"]
        assert "worker h2:2" not in telemetry.remote_metrics()  # no leak
        assert fleet["workers_total"] == 1
        assert fleet["workers_healthy"] == 1
        # the terminal state is still VISIBLE (ledger), just not counted
        assert any(w.get("retired") for w in fleet["workers"])
        snap = telemetry.metrics_snapshot()
        assert snap["fleet/workers_total"] == 1.0
        assert snap["fleet/gen_tokens_total"] == 500.0

        # the survivor keeps rating against the folded base
        _worker_snapshot("worker h1:1", 400.0, ts=12.0)
        fleet = agg.refresh(force=True)
        assert fleet["gen_tokens_total"] == 600.0
        assert fleet["tok_s"] == pytest.approx(50.0)
        assert list(fleet["worker_metrics"]) == ["h1:1"]

    def test_scale_in_fold_includes_restart_retired_base(self):
        """A worker that restarted once (per-track retired base) and THEN
        scaled in must fold base + final count — dropping either would
        regress the published fleet total."""
        driver = _FakeDriver()
        agg = obs.FleetAggregator(driver, min_refresh_s=0.0)
        _worker_snapshot("worker h2:2", 1000.0, ts=10.0, pid=1)
        agg.refresh(force=True)
        _worker_snapshot("worker h2:2", 50.0, ts=11.0, pid=2)  # restarted
        fleet = agg.refresh(force=True)
        assert fleet["gen_tokens_total"] == 1050.0
        driver._states[1]["retired"] = True
        driver._states[1]["healthy"] = False
        fleet = agg.refresh(force=True)
        assert fleet["gen_tokens_total"] == 1050.0  # 1000 base + 50 final
        assert "h2:2" not in fleet["worker_metrics"]


class TestFlightRecorder:
    def test_ring_bound_and_eviction(self):
        rec = obs.FlightRecorder("/tmp/unused", ring_size=3)
        for i in range(10):
            rec.record("step", {"step": i})
        ring = list(rec.ring)
        assert len(ring) == 3
        assert [r["step"] for r in ring] == [7, 8, 9]  # FIFO eviction

    def test_dump_layout_and_manifest(self, tmp_path):
        telemetry.configure(enabled=True)
        with telemetry.span("driver/update"):
            pass
        rec = obs.FlightRecorder(str(tmp_path), ring_size=8)
        rec.record("step", {"step": 1, "metrics": {"loss": 0.5}})
        path = rec.dump(
            "nan_loss", 7,
            config={"model": "tiny"}, plan={"decode_path": "dense"},
        )
        assert os.path.basename(path) == "incident_step000007_nan_loss"
        files = sorted(os.listdir(path))
        assert files == ["config.json", "manifest.json",
                         "metric_ring.jsonl", "span_tail.json"]
        man = json.load(open(os.path.join(path, "manifest.json")))
        assert man["trigger"] == "nan_loss" and man["step"] == 7
        assert man["ring_records"] == 1
        assert man["tracing_enabled"] is True
        rows = [json.loads(l) for l in
                open(os.path.join(path, "metric_ring.jsonl"))]
        assert rows[0]["metrics"]["loss"] == 0.5
        tail = json.load(open(os.path.join(path, "span_tail.json")))
        assert any(e.get("name") == "driver/update" for e in tail)
        cfgdoc = json.load(open(os.path.join(path, "config.json")))
        assert cfgdoc["config"]["model"] == "tiny"
        assert cfgdoc["plan"]["decode_path"] == "dense"
        snap = telemetry.metrics_snapshot()
        assert snap["obs/incidents"] == 1.0

    def test_dump_collision_gets_suffix(self, tmp_path):
        rec = obs.FlightRecorder(str(tmp_path))
        p1 = rec.dump("t", 1)
        p2 = rec.dump("t", 1)
        assert p1 != p2 and os.path.isdir(p1) and os.path.isdir(p2)


def _metrics(step, loss=0.1, acc=0.5, tok=None, stale=None):
    m = {"loss": loss, "mean_accuracy_reward": acc,
         "total_batch_steps": step}
    if tok is not None:
        m["engine/decode_tok_s"] = tok
    if stale is not None:
        m["rollout/staleness_max"] = stale
    return m


class TestSentinel:
    def _sentinel(self, tmp_path, **kw):
        rec = obs.FlightRecorder(str(tmp_path))
        return obs.Sentinel(rec, **kw), rec

    def test_nan_fires_exactly_once(self, tmp_path):
        s, rec = self._sentinel(tmp_path)
        assert s.check(1, _metrics(1)) == []
        assert s.check(2, _metrics(2, loss=float("nan"))) == ["nan_loss"]
        # a second NaN step must NOT produce a second bundle: the first
        # incident is the evidence
        assert s.check(3, _metrics(3, loss=float("inf"))) == []
        assert len(rec.incidents) == 1
        assert os.path.basename(rec.incidents[0]).endswith("_nan_loss")

    def test_seeded_injection_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DISTRL_SENTINEL_INJECT", "nan_loss:3")
        s, rec = self._sentinel(tmp_path)
        for step in range(1, 6):
            s.check(step, _metrics(step))  # all-finite metrics
        assert len(rec.incidents) == 1
        man = json.load(
            open(os.path.join(rec.incidents[0], "manifest.json"))
        )
        assert man["step"] == 3 and man["trigger"] == "nan_loss"

    def test_reward_collapse_needs_consecutive_zeros(self, tmp_path):
        s, rec = self._sentinel(tmp_path, collapse_steps=3)
        s.check(1, _metrics(1, acc=0.4))  # reward was alive
        s.check(2, _metrics(2, acc=0.0))
        s.check(3, _metrics(3, acc=0.0))
        assert not rec.incidents  # only 2 consecutive zeros
        fired = s.check(4, _metrics(4, acc=0.0))
        assert fired == ["reward_collapse"]
        # never-positive runs (cold start) must not fire at all
        s2, rec2 = self._sentinel(tmp_path / "b", collapse_steps=2)
        for step in range(1, 6):
            s2.check(step, _metrics(step, acc=0.0))
        assert not rec2.incidents

    def test_tok_s_regression_vs_ema(self, tmp_path):
        s, rec = self._sentinel(
            tmp_path, warmup_steps=2, tok_drop_frac=0.5
        )
        for step, tok in enumerate([1000.0, 1000.0, 1000.0], 1):
            assert s.check(step, _metrics(step, tok=tok)) == []
        fired = s.check(4, _metrics(4, tok=100.0))  # < 0.5 × EMA
        assert fired == ["tok_s_regression"]

    def test_staleness_blowup(self, tmp_path):
        s, rec = self._sentinel(tmp_path, staleness_limit=2)
        assert s.check(1, _metrics(1, stale=2.0)) == []  # at the bound
        assert s.check(2, _metrics(2, stale=5.0)) == ["staleness_blowup"]

    def test_hbm_breach_from_fake_stats(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "DISTRL_OBS_FAKE_HBM",
            json.dumps({"bytes_in_use": 98.0, "peak_bytes_in_use": 99.0,
                        "bytes_limit": 100.0}),
        )
        s, rec = self._sentinel(tmp_path, hbm_frac=0.95)
        assert s.check(1, _metrics(1)) == ["hbm_breach"]
        assert s.check(2, _metrics(2)) == []  # once

    def test_incident_bundle_carries_ring_and_config(self, tmp_path):
        rec = obs.FlightRecorder(str(tmp_path), ring_size=4)
        s = obs.Sentinel(rec)
        for step in range(1, 4):
            m = _metrics(step)
            rec.record("step", {"step": step, "metrics": m})
            s.check(step, m, config={"model": "tiny"})
        m = _metrics(4, loss=float("nan"))
        rec.record("step", {"step": 4, "metrics": m})
        s.check(4, m, config={"model": "tiny"})
        (path,) = rec.incidents
        rows = [json.loads(l) for l in
                open(os.path.join(path, "metric_ring.jsonl"))]
        assert [r["step"] for r in rows] == [1, 2, 3, 4]
        cfgdoc = json.load(open(os.path.join(path, "config.json")))
        assert cfgdoc["config"]["model"] == "tiny"


class TestHbmSampling:
    def test_phase_hook_records_watermarks(self, monkeypatch):
        monkeypatch.setenv(
            "DISTRL_OBS_FAKE_HBM",
            json.dumps({"bytes_in_use": 10.0, "peak_bytes_in_use": 30.0}),
        )
        obs._on_phase("generation")
        monkeypatch.setenv(
            "DISTRL_OBS_FAKE_HBM",
            json.dumps({"bytes_in_use": 20.0, "peak_bytes_in_use": 25.0}),
        )
        obs._on_phase("generation")
        obs._on_phase("update")
        table = obs.phase_hbm()
        # per-phase HIGH watermark, not last sample
        assert table["generation"]["live_max"] == 20.0
        assert table["generation"]["peak_max"] == 30.0
        assert table["generation"]["samples"] == 2
        assert table["update"]["peak_max"] == 25.0
        snap = telemetry.metrics_snapshot()
        assert snap["obs/hbm_live_bytes"] == 20.0
        assert snap["obs/hbm_peak_bytes"] == 25.0
        assert snap["obs/hbm_peak_bytes/update"] == 25.0

    def test_no_stats_is_silent(self):
        # CPU backend: memory_stats() is None — no gauges, no crash
        obs._on_phase("generation")
        assert "obs/hbm_live_bytes" not in telemetry.metrics_snapshot()


class TestCompileTracker:
    def test_retrace_counts_beyond_first(self):
        obs.note_compile("fn_a", (64,))
        obs.note_compile("fn_a", (128,))  # new shape: compile, not retrace
        obs.note_compile("fn_a", (64,))   # SAME key again: retrace
        obs.note_compile("fn_a", (64,))
        assert obs.compile_total() == 4
        assert obs.retrace_total() == 2
        snap = telemetry.metrics_snapshot()
        assert snap["obs/compiles"] == 4.0
        assert snap["obs/retraces"] == 2.0
        obs.reset_compile_tracker()
        assert obs.compile_total() == 0

    def test_unhashable_signature_degrades(self):
        obs.note_compile("fn_b", [[1, 2], [3]])  # nested list: unhashable
        obs.note_compile("fn_b", [[1, 2], [3]])
        assert obs.retrace_total() == 1


class TestNoCostRecorder:
    def test_no_costs_key_and_no_extra_programs(self, tmp_path, monkeypatch):
        """The step-cost recorder is gone, not merely unread: neither the
        endpoint's snapshot nor an exported trace's metadata has a
        ``costs`` key, and the switch that once made the engines lower
        their step programs a second time builds no program more."""
        import types

        import jax
        import jax.numpy as jnp
        import numpy as np

        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
        from distrl_llm_tpu.models import TINY, init_params
        from distrl_llm_tpu.trainer import Trainer

        assert "costs" not in obs.json_snapshot()

        telemetry.configure(enabled=True)
        Trainer._export_trace(types.SimpleNamespace(
            config=types.SimpleNamespace(
                trace_dir=str(tmp_path), model="tiny",
                max_prompt_tokens=8, max_new_tokens=4,
            ),
            model_cfg=TINY, _peak_flops=None, _rollout_chips=1,
        ))
        telemetry.configure(enabled=False)
        doc = json.loads((tmp_path / "trace.json").read_text())
        assert doc["metadata"]["decode_flops_per_token"] > 0
        assert "costs" not in doc["metadata"]

        params = init_params(jax.random.PRNGKey(7), TINY)
        ids = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
        mask = np.ones((2, 8), np.int32)
        sampling = SamplingConfig(max_tokens=4, temperature=1.0, n=2)
        kw = dict(
            max_prompt_tokens=8, max_new_tokens=4, pad_token_id=0,
            eos_token_ids=[TINY.vocab_size - 1], cache_dtype=jnp.float32,
        )

        def round_of_each():
            for engine in (
                GenerationEngine(TINY, **kw),
                PagedGenerationEngine(TINY, page_size=8, **kw),
                PagedGenerationEngine(
                    TINY, page_size=8, scheduler="refill",
                    max_concurrent_rows=2, **kw
                ),
            ):
                engine.generate(
                    params, None, ids, mask, sampling, jax.random.PRNGKey(0)
                )

        log = telemetry.CompileLog()
        try:
            round_of_each()  # module-level programs are built once, here
            mark = log.mark()
            round_of_each()
            without = log.since(mark)["programs"]
            assert without > 0  # each engine builds its own programs
            # the name is split so that a search of the tree for the switch
            # finds no reader and no writer, this test included
            monkeypatch.setenv("DISTRL_MEASURE" + "_COST", "1")
            mark = log.mark()
            round_of_each()
            assert log.since(mark)["programs"] == without
        finally:
            log.close()


class TestTraceProfilerGuards:
    @pytest.fixture
    def profiler(self, tmp_path, monkeypatch):
        import jax

        calls = {"start": 0, "stop": 0}
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d: calls.__setitem__("start", calls["start"] + 1),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace",
            lambda: calls.__setitem__("stop", calls["stop"] + 1),
        )
        from distrl_llm_tpu.metrics import TraceProfiler

        return TraceProfiler(str(tmp_path), start_step=2, num_steps=2), calls

    def test_configured_window_unchanged(self, profiler):
        prof, calls = profiler
        prof.step_begin(1)
        assert calls["start"] == 0
        prof.step_begin(2)
        assert calls["start"] == 1
        prof.step_begin(3)
        assert calls == {"start": 1, "stop": 0}
        prof.step_begin(4)  # window [2, 4) closed
        assert calls == {"start": 1, "stop": 1}

    def test_stop_and_finish_idempotent(self, profiler):
        prof, calls = profiler
        prof.step_begin(2)
        prof.finish()
        prof.finish()
        prof.stop()
        assert calls == {"start": 1, "stop": 1}

    def test_request_capture_guarded_against_overlap(self, profiler):
        prof, calls = profiler
        prof.step_begin(2)  # configured window active
        assert prof.request_capture(2) is False  # refused, not raised
        assert prof.captures_skipped == 1
        prof.step_begin(3)
        prof.step_begin(4)  # configured window closes
        assert prof.request_capture(2) is True
        assert prof.request_capture(2) is False  # one pending at a time
        prof.step_begin(5)  # requested window starts
        assert calls["start"] == 2
        prof.step_begin(6)
        prof.step_begin(7)  # requested window closes
        assert calls["stop"] == 2
        prof.finish()
        assert calls["stop"] == 2  # nothing left to stop


class TestTraceReport:
    def _events(self):
        return [
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "driver"}},
            {"ph": "X", "name": "driver/generation", "ts": 0,
             "dur": 3_000_000, "pid": 1, "tid": 1, "args": {}},
            {"ph": "X", "name": "driver/update", "ts": 3_000_000,
             "dur": 1_000_000, "pid": 1, "tid": 1, "args": {}},
            {"ph": "X", "name": "engine/decode", "ts": 0, "dur": 2_000_000,
             "pid": 1, "tid": 2, "args": {"tokens": 4000}},
        ]

    def test_mfu_line_from_trace_metadata(self):
        import importlib

        tr = importlib.import_module("tools.trace_report")
        report = tr.build_report(self._events(), {
            "decode_flops_per_token": 1e9, "peak_flops": 1e13, "chips": 1,
        })
        # 4000 tok / 2 s = 2000 tok/s × 1 GF/tok = 2 TF/s of 10 TF peak
        assert "decode  tok/s: 2,000" in report
        assert "decode MFU:    20.00%" in report
        # two chips: the engine's tok/s is divided before the peak
        report = tr.build_report(self._events(), {
            "decode_flops_per_token": 1e9, "peak_flops": 1e13, "chips": 2,
        })
        assert "decode MFU:    10.00%" in report and "2 chips" in report
        # no peak known: the line says what is missing, never a number
        report = tr.build_report(
            self._events(), {"decode_flops_per_token": 1e9}
        )
        assert "decode MFU:    n/a" in report

    def test_truncated_trace_one_line_failure(self, tmp_path, capsys):
        """A still-being-written/truncated trace file must exit 1 with one
        stderr line, never a traceback (the run_all_checks gate)."""
        import importlib

        tr = importlib.import_module("tools.trace_report")
        bad = tmp_path / "trace.json"
        bad.write_text('{"traceEvents": [{"ph": "X", "na')  # truncated
        rc = tr.main([str(bad)])
        assert rc == 1
        err = capsys.readouterr().err
        assert err.count("\n") == 1
        assert "cannot report on" in err
        # events of the wrong TYPE (a malformed writer) degrade the same way
        mangled = tmp_path / "mangled.json"
        mangled.write_text(json.dumps({"traceEvents": [
            {"ph": "X", "name": "a", "ts": "not-an-int", "dur": "x",
             "tid": 0},
        ]}))
        assert tr.main([str(mangled)]) == 1


class TestEngineObsIntegration:
    def test_round_stats_count_gen_tokens(self):
        from distrl_llm_tpu.engine.engine import accumulate_round_stats

        accumulate_round_stats(
            None, prefill_s=0.1, prefill_tokens=64, prompt_rows=4,
            decode_s=0.5, gen_tokens=100, gen_rows=8,
        )
        snap = telemetry.metrics_snapshot()
        assert snap["obs/gen_tokens"] == 100.0

    def test_swap_latency_observed_on_consume(self):
        from distrl_llm_tpu.engine.engine import LoraMailbox

        class Box(LoraMailbox):
            def __init__(self):
                self.last_swap_steps = []
                self.last_swap_versions = []

        box = Box()
        box.push_lora({"w": 1}, version=3)
        cell = [None]
        box._take_pending_lora(cell, dispatched=5)
        assert cell[0] == {"w": 1}
        snap = telemetry.metrics_snapshot()
        assert snap["engine/swap_latency_ms_count"] == 1.0
        assert snap["engine/swap_latency_ms_max"] >= 0.0
