"""Serving-observability tests (ISSUE 13): the ServingLedger lifecycle
state machine, the paged engine's refill/continuous instrumentation
(byte-identity with the ledger armed, complete monotone lifecycles,
admission-stall conservation), the fleet fold, the sentinel SLO triggers,
config/CLI validation, and the serving_report satellite."""

import json
import os

import numpy as np
import pytest

from distrl_llm_tpu import obs, telemetry
from distrl_llm_tpu import serving_obs as so
from distrl_llm_tpu.serving_obs import ServingLedger


@pytest.fixture(autouse=True)
def clean_state():
    telemetry.reset()
    telemetry.configure(enabled=False)
    yield
    telemetry.reset()
    telemetry.configure(enabled=False)


class TestServingLedger:
    def test_lifecycle_derives_latencies(self, tmp_path):
        led = ServingLedger(out_dir=str(tmp_path))
        uid = led.on_enqueue(0, n=2, prompt_tokens=12, ts=100.0)
        led.on_prefill_done(uid, ts=100.2)
        led.on_admit(uid, cand=0, slot=1, shared_pages=2, cow=True,
                     ts=100.5)
        led.on_admit(uid, cand=1, slot=2, backfill=True, ts=101.0)
        led.on_first_token(uid, ts=101.5)
        led.on_first_token(uid, ts=999.0)  # idempotent: first wins
        led.on_finish(uid, 0, ts=102.0)
        led.on_finish(uid, 1, ts=103.0)   # group closes on the LAST cand
        led.note_tokens(uid, 22, ts=103.0)
        led.close()
        docs = [json.loads(l) for l in
                open(tmp_path / "serving.jsonl")]
        (g,) = [d for d in docs if d["kind"] == "group"]
        assert g["queue_wait_ms"] == pytest.approx(500.0)
        assert g["ttft_ms"] == pytest.approx(1500.0)
        assert g["e2e_ms"] == pytest.approx(3000.0)
        # tpot: (finish - first_token) over tokens beyond one per cand
        assert g["tpot_ms"] == pytest.approx(1500.0 / 20)
        assert g["gen_tokens"] == 22 and g["backfilled"] is True
        assert len(g["admits"]) == 2
        assert g["admits"][0]["shared_pages"] == 2
        assert g["admits"][0]["cow"] is True
        # the registry saw one observation per latency histogram
        snap = telemetry.observe_snapshot()
        for name in (so.SERVING_TTFT_MS, so.SERVING_QUEUE_WAIT_MS,
                     so.SERVING_E2E_MS, so.SERVING_TPOT_MS):
            assert snap["hists"][name]["count"] == 1.0

    def test_fast_finish_backfills_first_token(self):
        """A group that finishes before any boundary observed progress
        gets first_token = finish — the lifecycle stays complete and
        monotone (the boundary cadence's tightest honest bound)."""
        led = ServingLedger()
        uid = led.on_enqueue(0, n=1, prompt_tokens=4, ts=10.0)
        led.on_admit(uid, cand=0, slot=0, ts=10.1)
        led.on_finish(uid, 0, ts=10.4)
        rec = led._ring[uid]
        assert rec.first_token_ts == rec.finish_ts == 10.4
        assert rec.ttft_ms == pytest.approx(400.0)

    def test_admit_records_carry_prefix_hit_tokens(self, tmp_path):
        """ISSUE 18: the admit record pins the radix-cache hit the group
        rode in on — prompt tokens that skipped prefill — and defaults to
        0 on cold admissions so cache-off ledgers stay shape-identical."""
        led = ServingLedger(out_dir=str(tmp_path))
        uid = led.on_enqueue(0, n=2, prompt_tokens=24, ts=1.0)
        led.on_admit(uid, cand=0, slot=0, prefix_hit_tokens=16, ts=1.1)
        led.on_admit(uid, cand=1, slot=1, ts=1.2)  # cold twin
        led.on_finish(uid, 0, ts=2.0)
        led.on_finish(uid, 1, ts=2.0)
        led.close()
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        (g,) = [d for d in docs if d["kind"] == "group"]
        assert g["admits"][0]["prefix_hit_tokens"] == 16
        assert g["admits"][1]["prefix_hit_tokens"] == 0

    def test_resumed_admit_keeps_original_queue_wait(self):
        led = ServingLedger()
        uid = led.on_enqueue(0, n=1, prompt_tokens=4, ts=10.0)
        led.on_admit(uid, cand=0, slot=0, ts=11.0)
        led.on_preempt(uid, 0)
        led.on_admit(uid, cand=0, slot=1, resumed=True, ts=15.0)
        rec = led._ring[uid]
        assert rec.queue_wait_ms == pytest.approx(1000.0)  # first admit
        assert rec.preemptions == 1 and rec.resumes == 1

    def test_ring_bound_evicts_counted_and_streamed(self, tmp_path):
        led = ServingLedger(ring_size=2, out_dir=str(tmp_path))
        for g in range(4):
            led.on_enqueue(g, n=1, prompt_tokens=4)
        assert len(led._ring) == 2
        snap = telemetry.observe_snapshot()
        assert snap["counters"][so.SERVING_RING_EVICTIONS] == 2.0
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        # partial lifecycles still landed in the JSONL, never silent
        assert [d["group_index"] for d in docs] == [0, 1]

    def test_boundary_decline_accounting(self):
        led = ServingLedger()
        led.on_boundary(live_slots=4, queue_depth=3, free_pages=2,
                        admitted=0, reason="no_slots")
        led.on_boundary(live_slots=2, queue_depth=3, free_pages=0,
                        admitted=0, reason="no_pages")
        led.on_boundary(live_slots=2, queue_depth=3, free_pages=9,
                        admitted=2)           # admitted: not a decline
        led.on_boundary(live_slots=2, queue_depth=0, free_pages=9,
                        admitted=0)           # nothing waiting: no decline
        assert led.boundary_passes == 4
        assert led.declined_passes == 2
        assert sum(led.stalls.values()) == led.declined_passes
        assert led.stall_frac() == pytest.approx(0.5)
        snap = telemetry.observe_snapshot()
        assert snap["counters"][so.SERVING_DECLINED_PASSES] == 2.0
        assert snap["counters"][
            f"{so.SERVING_ADMISSION_STALLS}/no_slots"] == 1.0
        assert snap["gauges"][so.SERVING_QUEUE_DEPTH] == 0.0  # last pass

    def test_unknown_stall_reason_raises(self):
        led = ServingLedger()
        with pytest.raises(ValueError, match="unknown admission-stall"):
            led.on_boundary(live_slots=0, queue_depth=1, free_pages=0,
                            admitted=0, reason="cosmic_rays")

    def test_trace_context_stamps_dispatch_ids(self):
        """Records carry the SAME (trace_id, dispatch_id) the lineage
        ledger stores — telemetry's trace context, one allocation path —
        so lineage_report --serving joins on dispatch_id."""
        ctx = telemetry.next_dispatch_context()
        telemetry.bind_trace_context(ctx)
        try:
            led = ServingLedger()
            uid = led.on_enqueue(0, n=1, prompt_tokens=4)
            rec = led._ring[uid]
            assert rec.trace_id == ctx["trace_id"]
            assert rec.dispatch_id == ctx["dispatch_id"]
        finally:
            telemetry.unbind_trace_context()
        led2 = ServingLedger()
        uid2 = led2.on_enqueue(0, n=1, prompt_tokens=4)
        assert led2._ring[uid2].dispatch_id is None  # unbound: no ids

    def test_percentile_and_summary(self, tmp_path):
        led = ServingLedger(out_dir=str(tmp_path))
        for i in range(10):
            uid = led.on_enqueue(i, n=1, prompt_tokens=4, ts=0.0)
            led.on_admit(uid, cand=0, slot=0, ts=float(i + 1) / 1000)
            led.on_finish(uid, 0, ts=1.0)
            led.note_tokens(uid, 5)
        assert led.percentile("queue_wait_ms", 50) == pytest.approx(6.0)
        assert led.percentile("tpot_ms", 50) is not None
        led.close()
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        (summ,) = [d for d in docs if d["kind"] == "summary"]
        assert summ["closed_groups"] == 10


class TestClassServing:
    """ISSUE 19: the multi-tenant additions ride NEXT to the single-tenant
    audit — per-class breakdowns never replace the flat counters, and the
    class-less paths keep their exact pre-gateway shape."""

    def test_class_stall_conservation(self):
        led = ServingLedger()
        led.on_boundary(live_slots=2, queue_depth=3, free_pages=0,
                        admitted=0, reason="shed", cls="scavenger")
        led.on_boundary(live_slots=2, queue_depth=3, free_pages=0,
                        admitted=0, reason="shed", cls="scavenger")
        led.on_boundary(live_slots=4, queue_depth=2, free_pages=0,
                        admitted=0, reason="quota", cls="batch")
        # a class-less decline (non-gateway round interleaved): counts in
        # the flat reason, absent from the breakdown
        led.on_boundary(live_slots=4, queue_depth=2, free_pages=0,
                        admitted=0, reason="no_pages")
        stats = led.stats()
        assert sum(stats["stalls"].values()) == stats["declined_passes"]
        assert stats["stalls_by_class"] == {
            "scavenger": {"shed": 2}, "batch": {"quota": 1},
        }
        for cls, reasons in stats["stalls_by_class"].items():
            for reason, count in reasons.items():
                assert count <= stats["stalls"][reason]
        snap = telemetry.observe_snapshot()["counters"]
        assert snap[f"{so.SERVING_CLASS_STALLS}/scavenger/shed"] == 2.0
        assert snap[f"{so.SERVING_CLASS_STALLS}/batch/quota"] == 1.0
        assert snap[f"{so.SERVING_ADMISSION_STALLS}/no_pages"] == 1.0
        assert not any(
            k.startswith(so.SERVING_CLASS_STALLS) and "no_pages" in k
            for k in snap
        )

    def test_records_carry_tenant_and_priority(self, tmp_path):
        led = ServingLedger(out_dir=str(tmp_path))
        uid = led.on_enqueue(0, n=1, prompt_tokens=4, tenant="acme",
                             priority="interactive", ts=1.0)
        led.on_admit(uid, cand=0, slot=0, ts=1.2)
        led.on_finish(uid, 0, ts=2.0)
        led.note_tokens(uid, 3, ts=2.0)  # closes the record
        led.close()
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        (g,) = [d for d in docs if d["kind"] == "group"]
        assert g["tenant"] == "acme" and g["priority"] == "interactive"
        # per-class percentile narrows to this record's class
        assert led.percentile("ttft_ms", 50, cls="interactive") == \
            pytest.approx(1000.0)
        assert led.percentile("ttft_ms", 50, cls="batch") is None
        # the per-class histograms ride NEXT to the flat ones
        snap = telemetry.observe_snapshot()["hists"]
        assert snap[so.SERVING_TTFT_MS]["count"] == 1.0
        assert snap[f"{so.SERVING_TTFT_MS}/interactive"]["count"] == 1.0

    def test_single_tenant_shape_pinned(self, tmp_path):
        """Class-less lifecycles (every pre-gateway caller) write records
        with tenant/priority null, mint NO per-class series, and answer
        class-narrowed percentiles with None — byte-for-byte the ISSUE 13
        shape plus two null fields."""
        led = ServingLedger(out_dir=str(tmp_path))
        uid = led.on_enqueue(0, n=1, prompt_tokens=4, ts=1.0)
        led.on_admit(uid, cand=0, slot=0, ts=1.1)
        led.on_finish(uid, 0, ts=1.5)
        led.note_tokens(uid, 3, ts=1.5)  # closes the record
        led.on_boundary(live_slots=1, queue_depth=1, free_pages=0,
                        admitted=0, reason="no_slots")
        led.close()
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        (g,) = [d for d in docs if d["kind"] == "group"]
        assert g["tenant"] is None and g["priority"] is None
        assert led.percentile("ttft_ms", 50) is not None
        assert led.percentile("ttft_ms", 50, cls="interactive") is None
        assert led.stats()["stalls_by_class"] == {}
        snap = telemetry.observe_snapshot()
        assert not any(
            k.startswith(so.SERVING_CLASS_STALLS)
            for k in snap["counters"]
        )
        assert not any("/" in k[len("serving/"):]
                       for k in snap["hists"] if k.startswith("serving/"))

    def test_gateway_round_attributes_classes_end_to_end(self, tmp_path):
        """A REAL gateway round on the tiny engine: records carry the
        tenant/priority identity from round_meta and the per-class stall
        breakdown stays conservation-consistent."""
        import jax
        import jax.numpy as jnp

        from distrl_llm_tpu.gateway.service import GatewayService
        from distrl_llm_tpu.models import TINY, init_params
        from distrl_llm_tpu.tokenizer import CharTokenizer

        eng = _tiny_engine(continuous_admission=True)
        led = ServingLedger(out_dir=str(tmp_path))
        params = init_params(jax.random.PRNGKey(0), TINY,
                             dtype=jnp.bfloat16)
        svc = GatewayService(
            eng, params, CharTokenizer(TINY.vocab_size),
            serving_ledger=led, max_groups_per_round=4, seed=3,
        ).start()
        try:
            reqs = [
                svc.submit("hello", tenant="acme", cls="interactive"),
                svc.submit("worldly", tenant="globex", cls="batch"),
                svc.submit("byebye", tenant="acme", cls="scavenger"),
            ]
            assert svc.drain(timeout_s=120.0)
        finally:
            svc.close()
        for req in reqs:
            while True:
                kind, payload = req.events.get(timeout=5)
                if kind == "done":
                    break
                assert kind == "tokens", payload
        stats = led.stats()
        assert stats["closed_groups"] == 3
        assert sum(stats["stalls"].values()) == stats["declined_passes"]
        led.close()
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        by_identity = {
            (d["tenant"], d["priority"])
            for d in docs if d["kind"] == "group"
        }
        assert by_identity == {
            ("acme", "interactive"), ("globex", "batch"),
            ("acme", "scavenger"),
        }


def _tiny_engine(**kw):
    import jax.numpy as jnp  # noqa: F401 — backend init
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import TINY

    return PagedGenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
        pad_token_id=0, page_size=8, max_concurrent_rows=2,
        scheduler="refill", decode_chunk=2, autotune=False, **kw,
    )


def _tiny_round(engine, seed: int = 1):
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.models import TINY, init_params

    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    b = 3
    ids = rng.integers(2, TINY.vocab_size, size=(b, 16)).astype(np.int32)
    mask = np.ones((b, 16), np.int32)
    sampling = SamplingConfig(max_tokens=8, temperature=0.0, top_p=1.0, n=2)
    return engine.generate(
        params, None, ids, mask, sampling, jax.random.PRNGKey(seed)
    )


class TestEngineServing:
    def test_continuous_round_records_complete_lifecycles(self, tmp_path):
        golden = _tiny_round(_tiny_engine(continuous_admission=True))
        eng = _tiny_engine(continuous_admission=True)
        led = ServingLedger(out_dir=str(tmp_path))
        eng.serving_ledger = led
        res = _tiny_round(eng)
        # the ledger observes, it never schedules: byte-identical outputs
        assert np.array_equal(res.tokens, golden.tokens)
        assert np.array_equal(res.lengths, golden.lengths)
        led.close()
        docs = [json.loads(l) for l in open(tmp_path / "serving.jsonl")]
        groups = [d for d in docs if d["kind"] == "group"]
        assert len(groups) == 3
        for g in groups:
            assert (g["enqueue_ts"] <= g["admit_ts"]
                    <= g["first_token_ts"] <= g["finish_ts"])
            assert g["enqueue_ts"] <= g["prefill_done_ts"]
            assert g["gen_tokens"] and g["ttft_ms"] is not None
        # 6 candidates over 2 slots: somebody backfilled and waited
        assert any(g["backfilled"] for g in groups)
        (summ,) = [d for d in docs if d["kind"] == "summary"]
        assert sum(summ["stalls"].values()) == summ["declined_passes"]
        assert summ["admission_passes"] > 0

    def test_fixed_refill_round_records_too(self):
        """The plain refill scheduler (no continuous admission) gets the
        same lifecycle coverage — its queue is candidates waiting on
        slots, its prefill the monolithic batched pass."""
        eng = _tiny_engine(prefix_sharing=True)
        led = ServingLedger()
        eng.serving_ledger = led
        _tiny_round(eng)
        assert led.closed_groups == 3
        assert led.boundary_passes > 0
        assert sum(led.stalls.values()) == led.declined_passes

    def test_unarmed_engine_emits_nothing(self):
        _tiny_round(_tiny_engine(continuous_admission=True))
        snap = telemetry.observe_snapshot()
        assert not any(k.startswith("serving/") for k in snap["counters"])
        assert not any(k.startswith("serving/") for k in snap["hists"])


class TestFleetServingFold:
    def test_fold_publishes_gauges(self):
        remote = {
            "worker a:1": {
                "hists": {so.SERVING_TTFT_MS:
                          {"count": 4.0, "sum": 400.0, "max": 200.0}},
                "counters": {
                    f"{so.SERVING_ADMISSION_STALLS}/no_pages": 3.0,
                },
            },
            "worker b:2": {
                "hists": {so.SERVING_TTFT_MS:
                          {"count": 6.0, "sum": 200.0, "max": 90.0}},
                "counters": {
                    f"{so.SERVING_ADMISSION_STALLS}/no_slots": 2.0,
                },
            },
        }
        view = so.fold_fleet_serving(remote)
        assert view["admission_stalls_total"] == 5.0
        assert view["admission_stalls"] == {"no_pages": 3.0,
                                            "no_slots": 2.0}
        h = view["hists"][so.SERVING_TTFT_MS]
        assert h["count"] == 10.0 and h["max"] == 200.0
        assert h["mean"] == pytest.approx(60.0)
        snap = telemetry.observe_snapshot()
        assert snap["gauges"][so.FLEET_SERVING_TTFT_MEAN_MS] == (
            pytest.approx(60.0)
        )
        assert snap["gauges"][so.FLEET_SERVING_TTFT_MAX_MS] == 200.0
        assert snap["gauges"][so.FLEET_SERVING_STALLS] == 5.0

    def test_fold_absent_without_serving_traffic(self):
        view = so.fold_fleet_serving({
            "worker a:1": {"hists": {"cp/rpc_dispatch_ms":
                                     {"count": 1, "sum": 1, "max": 1}},
                           "counters": {"obs/gen_tokens": 5.0}},
        })
        assert view is None
        snap = telemetry.observe_snapshot()
        assert so.FLEET_SERVING_STALLS not in snap["gauges"]


class TestServingSLO:
    def _sentinel(self, tmp_path, **kw):
        return obs.Sentinel(
            obs.FlightRecorder(str(tmp_path)), **kw
        )

    def test_ttft_blowup_fires_once(self, tmp_path):
        s = self._sentinel(tmp_path, slo_ttft_ms=100.0)
        fired = s.check(1, {so.SERVING_TTFT_MS + "_max": 90.0})
        assert fired == []
        fired = s.check(2, {so.SERVING_TTFT_MS + "_max": 150.0})
        assert fired == ["ttft_blowup"]
        fired = s.check(3, {so.SERVING_TTFT_MS + "_max": 900.0})
        assert fired == []  # exactly once per run
        assert os.path.isdir(
            os.path.join(str(tmp_path), "incident_step000002_ttft_blowup")
        )

    def test_queue_wait_blowup_reads_fleet_gauge(self, tmp_path):
        s = self._sentinel(tmp_path, slo_queue_wait_ms=50.0)
        fired = s.check(1, {so.FLEET_SERVING_QUEUE_WAIT_MAX_MS: 80.0})
        assert fired == ["queue_wait_blowup"]

    def test_unarmed_slo_never_fires(self, tmp_path):
        s = self._sentinel(tmp_path)
        assert s.check(1, {so.SERVING_TTFT_MS + "_max": 1e9}) == []

    def test_injection_requires_matching_slo(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DISTRL_SENTINEL_INJECT", "ttft_blowup:2")
        s = self._sentinel(tmp_path)  # slo_ttft_ms unarmed
        assert s._inject is None  # vacuous-gate guard: dropped with warning
        s2 = self._sentinel(tmp_path, slo_ttft_ms=10.0)
        assert s2._inject == ("ttft_blowup", 2)
        assert s2.check(2, {}) == ["ttft_blowup"]


class TestServingConfig:
    def _cfg(self, **kw):
        from distrl_llm_tpu.config import TrainConfig

        base = dict(
            model="tiny", engine_impl="paged", continuous_batching=True,
            max_concurrent_sequences=4,
        )
        base.update(kw)
        return TrainConfig(**base)

    def test_serving_dir_implies_serving_obs(self, tmp_path):
        cfg = self._cfg(serving_dir=str(tmp_path))
        assert cfg.serving_obs is True

    def test_serving_obs_requires_continuous_batching(self):
        from distrl_llm_tpu.config import TrainConfig

        with pytest.raises(ValueError, match="serving_obs"):
            TrainConfig(model="tiny", serving_obs=True)

    def test_serving_obs_rejects_rollout_workers(self):
        with pytest.raises(ValueError, match="WORKER-side"):
            self._cfg(serving_obs=True,
                      rollout_workers=("127.0.0.1:7001",))

    def test_slo_requires_sentinel(self):
        with pytest.raises(ValueError, match="sentinel"):
            self._cfg(slo_ttft_ms=200.0)

    def test_slo_arms_serving_obs_locally(self, tmp_path):
        cfg = self._cfg(
            slo_ttft_ms=200.0, sentinel=True,
            flight_recorder_dir=str(tmp_path),
        )
        assert cfg.serving_obs is True

    def test_bad_ring_and_slo_values(self):
        with pytest.raises(ValueError, match="serving_ring"):
            self._cfg(serving_ring=0)
        with pytest.raises(ValueError, match="slo_ttft_ms"):
            self._cfg(slo_ttft_ms=-1.0, sentinel=True,
                      flight_recorder_dir="/tmp/x")


class TestServingReportTool:
    def _write(self, tmp_path, docs):
        path = tmp_path / "serving.jsonl"
        with open(path, "w") as f:
            for d in docs:
                f.write(json.dumps(d) + "\n")
        return str(path)

    def test_report_renders_sections(self, tmp_path, capsys):
        from tools import serving_report

        led = ServingLedger(out_dir=str(tmp_path))
        for i in range(3):
            uid = led.on_enqueue(i, n=1, prompt_tokens=8, ts=0.0)
            led.on_admit(uid, cand=0, slot=0, shared_pages=1,
                         ts=0.01 * (i + 1))
            led.on_first_token(uid, ts=0.05)
            led.on_finish(uid, 0, ts=0.1)
            led.note_tokens(uid, 8)
        led.on_boundary(live_slots=1, queue_depth=2, free_pages=3,
                        admitted=0, reason="no_pages")
        led.close()
        rc = serving_report.main(
            [str(tmp_path / "serving.jsonl")]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "latency (ms):" in out and "ttft" in out
        assert "admission: 1 declined of 1 passes" in out
        assert "no_pages" in out
        assert "occupancy:" in out

    def test_report_warm_vs_cold_ttft(self, tmp_path, capsys):
        """ISSUE 18: one warm group (an admit with prefix_hit_tokens)
        makes the report render the radix-cache section with warm and
        cold TTFT rows; a hit-free ledger must not grow the section."""
        from tools import serving_report

        path = self._write(tmp_path, [
            {"kind": "group", "group_index": 0, "n": 1, "finish_ts": 1.0,
             "ttft_ms": 3.0,
             "admits": [{"cand": 0, "slot": 0, "prefix_hit_tokens": 16}]},
            {"kind": "group", "group_index": 1, "n": 1, "finish_ts": 1.0,
             "ttft_ms": 9.0,
             "admits": [{"cand": 0, "slot": 1, "prefix_hit_tokens": 0}]},
        ])
        assert serving_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "radix cache: 1 warm group(s) of 2" in out
        assert "16 prompt tokens admitted straight from cache" in out
        assert "warm ttft" in out and "cold ttft" in out

    def test_report_no_radix_section_when_cold(self, tmp_path, capsys):
        from tools import serving_report

        path = self._write(tmp_path, [
            {"kind": "group", "group_index": 0, "n": 1, "finish_ts": 1.0,
             "ttft_ms": 3.0, "admits": [{"cand": 0, "slot": 0}]},
        ])
        assert serving_report.main([path]) == 0
        assert "radix cache" not in capsys.readouterr().out

    def test_no_groups_exits_1(self, tmp_path, capsys):
        from tools import serving_report

        path = self._write(tmp_path, [{"kind": "summary"}])
        assert serving_report.main([path]) == 1
        assert "serving_report: cannot report" in capsys.readouterr().err

    def test_unattributed_decline_warns(self, tmp_path, capsys):
        from tools import serving_report

        path = self._write(tmp_path, [
            {"kind": "group", "group_index": 0, "n": 1, "finish_ts": 1.0,
             "ttft_ms": 5.0, "admits": []},
            {"kind": "summary", "declined_passes": 3,
             "admission_passes": 5, "stalls": {"no_slots": 1}},
        ])
        assert serving_report.main([path]) == 0
        assert "carry no reason" in capsys.readouterr().out


class TestLineageServingJoin:
    def test_step_rows_gain_serving_columns(self, tmp_path, capsys):
        from tools import lineage_report

        lineage = tmp_path / "lineage.jsonl"
        serving = tmp_path / "serving.jsonl"
        with open(lineage, "w") as f:
            f.write(json.dumps({
                "kind": "group", "uid": 1, "episode": 0, "batch_index": 0,
                "worker": "w:1", "dispatch_id": 7, "min_version": 0,
                "max_version": 0, "staleness_lag": 0,
                "verdict": "admitted", "consumed_step": 3,
                "produced_version": 1, "sample_to_learn_ms": 12.0,
            }) + "\n")
        with open(serving, "w") as f:
            f.write(json.dumps({
                "kind": "group", "group_index": 0, "n": 2,
                "dispatch_id": 7, "ttft_ms": 42.0,
                "queue_wait_ms": 11.0,
            }) + "\n")
        rc = lineage_report.main(
            [str(lineage), "--step", "3", "--serving", str(serving)]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "ttft ms" in out and "42.0" in out and "11.0" in out
