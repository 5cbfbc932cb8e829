"""Execution-plan autotuner: PlanStore durability, resolution semantics,
engine integration, and bench-row ingestion (distrl_llm_tpu/autotune).

The two contracts the subsystem exists for, both pinned here:

* with an EMPTY (or absent, or corrupt) plan DB, every engine behaves
  byte-identically to the pre-autotuner hard-coded defaults;
* with a DB populated from the round-5 silicon measurements, the resolved
  plan for the benched dense-bf16 geometry selects scan-chunk OFF — the
  2.5× regression becomes unrepresentable without deleting
  the DB.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.autotune import (
    DEFAULT_PLAN,
    ExecutionPlan,
    PlanStore,
    SCHEMA_VERSION,
    TUNABLE_FIELDS,
    canonical_device_kind,
    current_device_kind,
    model_config_hash,
    plan_key,
    resolve_plan,
    shape_bucket,
)
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine.engine import GenerationEngine, compile_chunk_guarded
from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
from distrl_llm_tpu.models import TINY, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _key(rows=0, cfg=TINY, max_prompt=16, max_new=8, kind=None):
    return plan_key(
        kind or current_device_kind(), model_config_hash(cfg),
        shape_bucket(max_prompt, max_new, rows),
    )


def _write_db(path, entries):
    with open(path, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "entries": entries}, f)


ENGINE_KW = dict(
    max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
    pad_token_id=0, cache_dtype=jnp.float32,
)


class TestPlanStore:
    def test_missing_file_is_empty(self, tmp_path):
        store = PlanStore(str(tmp_path / "nope.json"))
        assert store.entries == {}
        assert store.get("anything") is None

    def test_corrupt_file_retunes_not_crashes(self, tmp_path):
        db = tmp_path / "db.json"
        db.write_text("{this is not json")
        store = PlanStore(str(db))
        assert store.entries == {}
        # the store stays writable: a re-tune overwrites the corpse
        store.put(_key(), ExecutionPlan(scan_chunk=4))
        store.save()
        assert PlanStore(str(db)).get(_key()).scan_chunk == 4

    def test_truncated_file_retunes(self, tmp_path):
        db = tmp_path / "db.json"
        store = PlanStore(str(db))
        store.put(_key(), ExecutionPlan(scan_chunk=4), [{"tok_s": 9.0}])
        store.save()
        blob = db.read_text()
        db.write_text(blob[: len(blob) // 2])
        assert PlanStore(str(db)).entries == {}

    def test_schema_version_mismatch_retunes(self, tmp_path):
        db = tmp_path / "db.json"
        db.write_text(json.dumps({
            "schema_version": SCHEMA_VERSION + 1,
            "entries": {_key(): {"plan": {"scan_chunk": 64}}},
        }))
        assert PlanStore(str(db)).entries == {}

    def test_non_dict_document_retunes(self, tmp_path):
        db = tmp_path / "db.json"
        db.write_text(json.dumps([1, 2, 3]))
        assert PlanStore(str(db)).entries == {}

    def test_invalid_entry_is_absent(self, tmp_path):
        db = tmp_path / "db.json"
        _write_db(db, {_key(): {"plan": {"scan_chunk": -5}}})
        assert PlanStore(str(db)).get(_key()) is None

    def test_roundtrip_and_unknown_keys_tolerated(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        plan = ExecutionPlan(scan_chunk=16, top_p_impl="bisect_mw",
                             prompt_buckets=(8, 16))
        store.put(_key(), plan, [{"tok_s": 100.0}], note="test")
        store.save()
        again = PlanStore(db)
        assert again.get(_key()) == plan
        # a newer writer's extra plan field must not break this reader
        doc = json.loads(open(db).read())
        doc["entries"][_key()]["plan"]["from_the_future"] = 1
        open(db, "w").write(json.dumps(doc))
        assert PlanStore(db).get(_key()) == plan

    def test_report_mentions_entries(self, tmp_path):
        store = PlanStore(str(tmp_path / "db.json"))
        store.put(_key(), ExecutionPlan(scan_chunk=4), [{"tok_s": 55.0}])
        rep = store.report()
        assert "scan_chunk=4" in rep and "55" in rep


class TestResolve:
    RK = dict(model_cfg=TINY, max_prompt_tokens=16, max_new_tokens=8)

    def test_no_db_resolves_defaults(self, tmp_path):
        r = resolve_plan(db_path=str(tmp_path / "absent.json"), **self.RK)
        assert r.plan == DEFAULT_PLAN
        assert r.source == "default"
        assert set(r.sources.values()) == {"default"}

    def test_db_hit_is_deterministic(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=4, top_p_impl="bisect_mw"))
        store.save()
        a = resolve_plan(db_path=db, **self.RK)
        b = resolve_plan(db_path=db, **self.RK)
        assert a.plan == b.plan
        assert a.source == "db"
        assert a.plan.scan_chunk == 4
        assert a.plan.top_p_impl == "bisect_mw"

    def test_explicit_request_beats_db(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=64))
        store.save()
        r = resolve_plan(db_path=db, requested={"scan_chunk": 0}, **self.RK)
        assert r.plan.scan_chunk == 0
        assert r.sources["scan_chunk"] == "user"
        assert r.sources["top_p_impl"] == "db"  # untouched fields still db

    def test_rows_bucket_falls_back_to_any_rows(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(rows=0), ExecutionPlan(scan_chunk=4))
        store.save()
        r = resolve_plan(db_path=db, rows=480, **self.RK)
        assert r.plan.scan_chunk == 4
        assert r.source == "db"

    def test_exact_rows_bucket_preferred(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(rows=0), ExecutionPlan(scan_chunk=4))
        store.put(_key(rows=512), ExecutionPlan(scan_chunk=16))
        store.save()
        # 480 buckets to 512 → the exact-rows entry wins
        assert resolve_plan(db_path=db, rows=480, **self.RK).plan.scan_chunk == 16

    def test_disabled_skips_db(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=64))
        store.save()
        r = resolve_plan(db_path=db, enabled=False, **self.RK)
        assert r.plan == DEFAULT_PLAN and r.source == "disabled"

    def test_env_kill_switch(self, tmp_path, monkeypatch):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=64))
        store.save()
        monkeypatch.setenv("DISTRL_AUTOTUNE", "0")
        assert resolve_plan(db_path=db, **self.RK).plan == DEFAULT_PLAN

    def test_env_db_path(self, tmp_path, monkeypatch):
        db = str(tmp_path / "env_db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=4))
        store.save()
        monkeypatch.setenv("DISTRL_PLAN_DB", db)
        assert resolve_plan(**self.RK).plan.scan_chunk == 4

    def test_decode_path_mismatch_ignores_entry(self, tmp_path):
        """A plan measured on one decode path must not hand its knobs to an
        engine pinned to a different path (its scan_chunk was never
        measured there — the r5 class of unmeasured-lever regression)."""
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(decode_path="paged", scan_chunk=16,
                                        top_p_impl="bisect_mw"))
        store.save()
        r = resolve_plan(
            db_path=db, requested={"decode_path": "dense"}, **self.RK
        )
        assert r.source == "default"
        assert r.plan.scan_chunk == 0 and r.plan.top_p_impl is None
        # an engine of the MATCHING path still adopts it
        e = PagedGenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert e.scan_chunk == 16

    def test_invalid_stored_plan_falls_back(self, tmp_path):
        db = tmp_path / "db.json"
        _write_db(db, {_key(): {"plan": {"decode_path": "quantum"}}})
        r = resolve_plan(db_path=str(db), **self.RK)
        assert r.plan == DEFAULT_PLAN and r.source == "default"

    def test_invalid_user_request_raises(self, tmp_path):
        with pytest.raises(ValueError, match="scan_chunk"):
            resolve_plan(db_path=str(tmp_path / "x.json"),
                         requested={"scan_chunk": -1}, **self.RK)
        with pytest.raises(ValueError, match="unknown plan fields"):
            resolve_plan(db_path=str(tmp_path / "x.json"),
                         requested={"warp_factor": 9}, **self.RK)

    def test_resolution_telemetry_counters(self, tmp_path):
        telemetry.reset()
        resolve_plan(db_path=str(tmp_path / "absent.json"), **self.RK)
        snap = telemetry.metrics_snapshot()
        assert snap.get("autotune/plan_resolved") == 1.0
        assert snap.get("autotune/plan_default") == 1.0
        # disabled resolutions are distinguishable from DB misses
        telemetry.reset()
        resolve_plan(db_path=str(tmp_path / "absent.json"), enabled=False,
                     **self.RK)
        snap = telemetry.metrics_snapshot()
        assert snap.get("autotune/plan_disabled") == 1.0
        assert "autotune/plan_default" not in snap

    def test_stale_store_cache_rereads_changed_file(self, tmp_path):
        db = str(tmp_path / "db.json")
        assert resolve_plan(db_path=db, **self.RK).source == "default"
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=4))
        store.save()
        assert resolve_plan(db_path=db, **self.RK).plan.scan_chunk == 4


class TestEngineIntegration:
    def test_empty_db_matches_legacy_defaults(self, tmp_path):
        e = GenerationEngine(TINY, plan_db=str(tmp_path / "no.json"),
                             **ENGINE_KW)
        assert e.scan_chunk == 0
        assert e.cache_read_formulation == "dot"
        assert e.prompt_buckets == [16]
        assert e.plan_top_p_impl is None
        assert e.resolved_plan.source == "default"

    def test_db_plan_applies_and_formulation_derives(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=4, top_p_impl="bisect_mw",
                                        prompt_buckets=(8,)))
        store.save()
        e = GenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert e.scan_chunk == 4
        assert e.cache_read_formulation == "mulred"  # derived from chunk
        assert e.plan_top_p_impl == "bisect_mw"
        assert e.prompt_buckets == [8, 16]
        assert e.resolved_plan.source == "db"

    def test_explicit_kwargs_beat_db(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(
            scan_chunk=4, cache_read_formulation="mulred",
            prompt_buckets=(8,),
        ))
        store.save()
        e = GenerationEngine(
            TINY, plan_db=db, scan_chunk=0, cache_read_formulation="dot",
            prompt_buckets=(12,), **ENGINE_KW,
        )
        assert e.scan_chunk == 0
        assert e.cache_read_formulation == "dot"
        assert e.prompt_buckets == [12, 16]

    def test_autotune_off_ignores_db(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(scan_chunk=4))
        store.save()
        e = GenerationEngine(TINY, plan_db=db, autotune=False, **ENGINE_KW)
        assert e.scan_chunk == 0
        assert e.resolved_plan.source == "disabled"

    def test_paged_engine_resolves(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(decode_path="paged", scan_chunk=4))
        store.save()
        p = PagedGenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert p.scan_chunk == 4
        assert p.resolved_plan.plan.decode_path == "paged"
        # explicit still wins
        p0 = PagedGenerationEngine(TINY, plan_db=db, scan_chunk=0, **ENGINE_KW)
        assert p0.scan_chunk == 0

    def test_the_paged_launch_is_the_callers_word_alone(self, tmp_path):
        """No plan field names a paged kernel: with an empty database or a
        stored plan the engine's ``paged_impl`` is what the caller said
        ("auto": the launch the backend and the shapes choose)."""
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(decode_path="paged", scan_chunk=4))
        store.save()
        for path in (str(tmp_path / "no.json"), db):
            p = PagedGenerationEngine(TINY, plan_db=path, **ENGINE_KW)
            assert p.paged_impl == "auto"
            r = PagedGenerationEngine(
                TINY, plan_db=path, paged_impl="reference", **ENGINE_KW)
            assert r.paged_impl == "reference"
        assert "paged_impl" not in TUNABLE_FIELDS

    def test_a_database_written_with_the_paged_kernel_fields_is_retuned(
            self, tmp_path, caplog):
        """The version before this one stored ``paged_kernel`` and
        ``pages_per_block``: such a file is a version mismatch (warn, empty,
        static defaults), never a ``TypeError`` at an engine's construction."""
        db = tmp_path / "db.json"
        db.write_text(json.dumps({
            "schema_version": SCHEMA_VERSION - 1,
            "entries": {_key(): {"plan": {
                "decode_path": "paged", "scan_chunk": 4,
                "paged_kernel": "blocked", "pages_per_block": 4}}},
        }))
        assert SCHEMA_VERSION == 2
        with caplog.at_level("WARNING"):
            assert PlanStore(str(db)).entries == {}
        assert "schema_version" in caplog.text and "re-run" in caplog.text
        p = PagedGenerationEngine(TINY, plan_db=str(db), **ENGINE_KW)
        assert p.scan_chunk == 0 and p.resolved_plan.source != "db"

    def test_an_entry_that_still_names_a_paged_kernel_is_read_without_it(
            self, tmp_path):
        """Inside one schema version ``from_dict`` drops a key it does not
        know: the rest of the entry is the plan."""
        db = tmp_path / "db.json"
        _write_db(str(db), {_key(): {"plan": {
            "decode_path": "paged", "scan_chunk": 4,
            "paged_kernel": "blocked", "pages_per_block": 4}}})
        plan = PlanStore(str(db)).get(_key())
        assert plan == ExecutionPlan(decode_path="paged", scan_chunk=4)
        assert ExecutionPlan.from_dict(plan.to_dict()) == plan
        assert not {"paged_kernel", "pages_per_block"} & set(plan.to_dict())

    @pytest.mark.parametrize("gone", ["paged_kernel", "pages_per_block"])
    def test_neither_the_plan_nor_an_engine_takes_a_paged_kernel_field(self, gone):
        value = "blocked" if gone == "paged_kernel" else 4
        assert gone not in TUNABLE_FIELDS
        with pytest.raises(TypeError, match=gone):
            ExecutionPlan(**{gone: value})
        if gone == "pages_per_block":
            with pytest.raises(TypeError, match=gone):
                PagedGenerationEngine(TINY, pages_per_block=4, **ENGINE_KW)

    def test_candidate_plans_enumerate_no_kernel_variant(self):
        from distrl_llm_tpu.autotune import candidate_plans

        with pytest.raises(TypeError, match="paged_kernels"):
            candidate_plans(paged_kernels=(None, "blocked"))
        plans = candidate_plans(
            decode_paths=("dense", "paged"), scan_chunks=(0, 4),
            cb_modes=(None, "continuous"),
        )
        # one candidate a (path, chunk, admission regime) that can run: the
        # dense path has no admission scheduler
        assert [(p.decode_path, p.scan_chunk, p.cb_mode) for p in plans] == [
            ("dense", 0, None), ("dense", 4, None),
            ("paged", 0, None), ("paged", 0, "continuous"),
            ("paged", 4, None), ("paged", 4, "continuous"),
        ]

    def test_generation_identical_with_and_without_empty_db(self, tmp_path):
        """The empty-DB fallback path produces byte-identical output to an
        autotune-disabled engine — the acceptance contract's first half."""
        params = init_params(jax.random.PRNGKey(0), TINY)
        prompts = np.full((2, 16), 3, np.int32)
        mask = np.ones_like(prompts)
        sampling = SamplingConfig(max_tokens=8, temperature=1.0, top_p=0.9, n=2)
        outs = []
        for kw in (
            dict(plan_db=str(tmp_path / "absent.json")),
            dict(autotune=False),
        ):
            e = GenerationEngine(TINY, **ENGINE_KW, **kw)
            res = e.generate(params, None, prompts, mask, sampling,
                             jax.random.PRNGKey(7))
            outs.append(np.asarray(res.tokens))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_unfitting_plan_buckets_degrade_not_crash(self, tmp_path):
        """A stored bucket past this engine's max_prompt_tokens is dropped
        with a warning (never-crash contract); the same bucket passed
        explicitly still raises."""
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(prompt_buckets=(8, 350)))
        store.save()
        e = GenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert e.prompt_buckets == [8, 16]  # 350 dropped, 16 appended
        with pytest.raises(ValueError, match="buckets"):
            GenerationEngine(TINY, prompt_buckets=(350,), **ENGINE_KW)

    def test_worker_engine_honors_autotune_flags(self, tmp_path):
        """Rollout workers resolve against their own host's DB; --autotune
        off / --decode-scan-chunk pins must reach the worker engine."""
        from distrl_llm_tpu.distributed import worker_main

        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(rows=0, max_prompt=32, max_new=16),
                  ExecutionPlan(scan_chunk=4))
        store.save()
        try:
            worker_main._init_engine("tiny", 32, 16, seed=0, plan_db=db)
            assert worker_main._ENGINE_STATE["engine"].scan_chunk == 4
            worker_main._init_engine("tiny", 32, 16, seed=0, plan_db=db,
                                     autotune=False)
            assert worker_main._ENGINE_STATE["engine"].scan_chunk == 0
            worker_main._init_engine("tiny", 32, 16, seed=0, plan_db=db,
                                     scan_chunk=0)
            assert worker_main._ENGINE_STATE["engine"].scan_chunk == 0
        finally:
            worker_main._ENGINE_STATE.clear()

    def test_plan_top_p_priority(self):
        # plan default applies only when the sampling config doesn't pin
        assert SamplingConfig().resolved_top_p_impl("bisect_mw") == "bisect_mw"
        assert SamplingConfig(top_p_impl="bisect").resolved_top_p_impl(
            "bisect_mw") == "bisect"
        assert SamplingConfig(top_p_exact=True).resolved_top_p_impl(
            "bisect_mw") == "exact"
        assert SamplingConfig().resolved_top_p_impl(None) == "bisect"
        # plan values are validated at ExecutionPlan construction — an
        # invalid top_p_impl can never reach resolved_top_p_impl
        with pytest.raises(ValueError, match="top_p_impl"):
            ExecutionPlan(top_p_impl="warp")

    def test_engine_kwargs_from_config_forwarding(self):
        from distrl_llm_tpu.config import TrainConfig
        from distrl_llm_tpu.trainer import engine_kwargs_from_config

        # defaults stay minimal (pinned by test_speculative's equality check)
        assert "autotune" not in engine_kwargs_from_config(TrainConfig())
        kw = engine_kwargs_from_config(
            TrainConfig(autotune=False, plan_db="/tmp/p.json")
        )
        assert kw["autotune"] is False
        assert kw["plan_db"] == "/tmp/p.json"

    def test_explicit_zero_scan_chunk_reaches_engine(self):
        """--decode_scan_chunk 0 is a PIN (chunking off), distinct from the
        unset default (None → plan DB decides): the kwarg must be forwarded
        so a stored plan can never retune an explicit off."""
        from distrl_llm_tpu.config import TrainConfig
        from distrl_llm_tpu.trainer import engine_kwargs_from_config

        assert "scan_chunk" not in engine_kwargs_from_config(TrainConfig())
        kw = engine_kwargs_from_config(TrainConfig(decode_scan_chunk=0))
        assert kw["scan_chunk"] == 0
        assert engine_kwargs_from_config(
            TrainConfig(decode_scan_chunk=16)
        )["scan_chunk"] == 16

    def test_cli_unset_scan_chunk_is_none(self):
        import train_distributed as td

        args = td.build_parser().parse_args([])
        assert td.config_from_args(args).decode_scan_chunk is None
        args0 = td.build_parser().parse_args(["--decode_scan_chunk", "0"])
        assert td.config_from_args(args0).decode_scan_chunk == 0


class TestChunkFallbackTelemetry:
    def test_compile_failure_is_loud(self):
        class Boom:
            def lower(self, *a, **k):
                raise RuntimeError("mosaic says no")

        telemetry.reset()
        assert compile_chunk_guarded(Boom(), 1 << 20, "test-chunk") is None
        snap = telemetry.metrics_snapshot()
        assert snap.get("engine/chunk_fallback") == 1.0

    def test_mulred_broadcast_bytes_math(self):
        from distrl_llm_tpu.ops.attention import mulred_broadcast_bytes

        # [B=480, KH=2, G=7, D=64, S=1550] f32
        assert mulred_broadcast_bytes(480, 2, 7, 64, 1550) == (
            480 * 2 * 7 * 64 * 1550 * 4
        )


def _load_autotune_cli():
    spec = importlib.util.spec_from_file_location(
        "autotune_cli", os.path.join(REPO, "tools", "autotune.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchIngest:
    """tools/autotune.py ingest — the round-5 acceptance scenario."""

    ROW_COMMON = {
        "metric": "rollout_tokens_per_sec_per_chip", "engine": "dense",
        "model": "qwen2.5-0.5b", "backend": "tpu", "peak_tflops": 197.0,
        "completions": 480, "top_p_impl": "bisect_mw", "kv_quant": "int8",
        "unit": "tok/s/chip",
    }

    def _rows(self):
        # the r5 pair: chunk-active 4,150 tok/s vs chunk-fallback 10,405
        slow = dict(self.ROW_COMMON, value=4150.8, scan_chunk=64,
                    scan_chunk_active=True)
        fast = dict(self.ROW_COMMON, value=10404.9, scan_chunk=64,
                    scan_chunk_active=False)
        return [slow, fast]

    def test_r5_regression_unrepresentable(self, tmp_path):
        from distrl_llm_tpu.models import QWEN2_0_5B

        cli = _load_autotune_cli()
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        written = cli.ingest_rows(
            self._rows(), store=store, max_prompt=350, max_new=1200,
        )
        assert written
        store.save()
        r = resolve_plan(
            model_cfg=QWEN2_0_5B, max_prompt_tokens=350, max_new_tokens=1200,
            rows=480, db_path=db, device_kind="tpu_v5e",
        )
        assert r.source == "db"
        assert r.plan.decode_path == "dense"
        # the winner ran with scan-chunk FALLEN BACK → the stored plan turns
        # chunking OFF
        assert r.plan.scan_chunk == 0
        assert r.plan.top_p_impl == "bisect_mw"

    def test_rows_with_recorded_geometry_key_their_own_entries(self, tmp_path):
        """Post-PR rows carry max_prompt/new_tokens; a faster row at a
        DIFFERENT geometry must not win the production geometry's key."""
        from distrl_llm_tpu.models import QWEN2_0_5B

        cli = _load_autotune_cli()
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        short = dict(self.ROW_COMMON, value=50_000.0, scan_chunk=64,
                     scan_chunk_active=True, max_prompt_tokens=64,
                     max_new_tokens=128)
        cli.ingest_rows(
            self._rows() + [short], store=store, max_prompt=350, max_new=1200,
        )
        store.save()
        prod = resolve_plan(
            model_cfg=QWEN2_0_5B, max_prompt_tokens=350, max_new_tokens=1200,
            rows=480, db_path=db, device_kind="tpu_v5e",
        )
        assert prod.plan.scan_chunk == 0  # the 10.4k fallback row still wins
        other = resolve_plan(
            model_cfg=QWEN2_0_5B, max_prompt_tokens=64, max_new_tokens=128,
            db_path=db, device_kind="tpu_v5e",
        )
        assert other.source == "db" and other.plan.scan_chunk == 64

    def test_error_rows_and_foreign_metrics_skipped(self, tmp_path):
        cli = _load_autotune_cli()
        store = PlanStore(str(tmp_path / "db.json"))
        rows = [
            dict(self.ROW_COMMON, value=99999.0, scan_chunk=0,
                 scan_chunk_active=None, error="TPU unavailable"),
            {"metric": "learner_tokens_per_sec_per_chip", "value": 5.0},
        ]
        assert cli.ingest_rows(rows, store=store, max_prompt=350,
                               max_new=1200) == []

    def test_row_recorded_device_kind_wins_over_peak_inference(self, tmp_path):
        """Rows since this PR record device_kind; it must beat the
        peak_tflops heuristic (which would mis-key a v4/v6 row benched with
        the 197 default)."""
        from distrl_llm_tpu.models import QWEN2_0_5B

        cli = _load_autotune_cli()
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        row = dict(self.ROW_COMMON, value=9000.0, scan_chunk=0,
                   scan_chunk_active=None, device_kind="tpu_v4")
        written = cli.ingest_rows([row], store=store, max_prompt=350,
                                  max_new=1200)
        assert written and all(k.startswith("tpu_v4/") for k in written)
        store.save()
        r = resolve_plan(
            model_cfg=QWEN2_0_5B, max_prompt_tokens=350, max_new_tokens=1200,
            rows=480, db_path=db, device_kind="tpu_v4",
        )
        assert r.source == "db"

    def test_plan_rows_aligns_engine_with_exact_rows_entry(self, tmp_path):
        """An engine told the round volume (plan_rows) resolves the same
        exact-rows entry a rows-aware caller (bench) consulted, even when
        the any-rows entry diverges."""
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(rows=0), ExecutionPlan(scan_chunk=2))
        store.put(_key(rows=4), ExecutionPlan(scan_chunk=4))
        store.save()
        e = GenerationEngine(TINY, plan_db=db, plan_rows=4, **ENGINE_KW)
        assert e.scan_chunk == 4
        e0 = GenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert e0.scan_chunk == 2

    def test_unrecognized_tpu_peak_skipped_not_mis_keyed(self, tmp_path):
        """A TPU row whose peak_tflops maps to no known kind must be
        skipped, never filed under the ingesting (CPU) host's kind."""
        cli = _load_autotune_cli()
        store = PlanStore(str(tmp_path / "db.json"))
        weird = dict(self.ROW_COMMON, value=5000.0, scan_chunk=0,
                     scan_chunk_active=None, peak_tflops=394.0)
        assert cli.ingest_rows([weird], store=store, max_prompt=350,
                               max_new=1200) == []
        # --device-kind is the explicit escape hatch
        written = cli.ingest_rows([weird], store=store, max_prompt=350,
                                  max_new=1200, device_kind="tpu_v5e_int8")
        assert written and all(k.startswith("tpu_v5e_int8/") for k in written)

    def test_cli_ingest_real_r5_artifacts(self, tmp_path):
        """End-to-end over the repo's actual round-5 silicon rows."""
        import glob

        from distrl_llm_tpu.models import QWEN2_0_5B

        files = sorted(glob.glob(os.path.join(REPO, "benchmarks/r5/*.json")))
        if not files:
            pytest.skip("no r5 artifacts in tree")
        cli = _load_autotune_cli()
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        cli.ingest_rows(
            cli.iter_bench_rows(files), store=store,
            max_prompt=350, max_new=1200,
        )
        store.save()
        r = resolve_plan(
            model_cfg=QWEN2_0_5B, max_prompt_tokens=350, max_new_tokens=1200,
            rows=480, db_path=db, device_kind="tpu_v5e",
        )
        assert r.source == "db"
        assert r.plan.scan_chunk == 0  # the 10.4k fallback row won


REFILL_KW = dict(scheduler="refill", max_concurrent_rows=4)


class TestSpecPlanFields:
    """Resolution pins for the ISSUE-6 spec plan fields (spec_draft_len /
    spec_ngram_k / spec_drafter / spec_verify): explicit kwargs beat the
    DB, an empty DB is byte-identical to the historical defaults, and a
    stored speculative plan only engages on a refill engine."""

    def test_empty_db_keeps_spec_off(self, tmp_path):
        """Byte-identity pin: a refill engine with no DB entry keeps
        speculation OFF with the historical satellite defaults — exactly
        the pre-ISSUE-6 engine."""
        p = PagedGenerationEngine(
            TINY, plan_db=str(tmp_path / "no.json"), **REFILL_KW,
            **ENGINE_KW,
        )
        assert p.spec_draft == 0
        assert p.spec_ngram == 2
        assert p.spec_drafter == "ngram"
        assert p.spec_verify == "fused"
        assert p.resolved_plan.plan.decode_path == "paged"

    def test_db_spec_plan_applies_on_refill_engine(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(
            decode_path="speculative", spec_draft_len=3, spec_ngram_k=3,
            spec_drafter="self", spec_verify="unrolled",
        ))
        store.save()
        p = PagedGenerationEngine(TINY, plan_db=db, **REFILL_KW, **ENGINE_KW)
        assert p.spec_draft == 3
        assert p.spec_ngram == 3
        assert p.spec_drafter == "self"
        assert p.spec_verify == "unrolled"
        assert p.resolved_plan.sources["spec_draft_len"] == "db"
        assert p.resolved_plan.plan.decode_path == "speculative"

    def test_explicit_spec_kwargs_beat_db(self, tmp_path):
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(
            decode_path="speculative", spec_draft_len=3, spec_ngram_k=3,
            spec_drafter="self", spec_verify="unrolled",
        ))
        store.save()
        # explicit spec_draft=0 pins speculation OFF over a stored
        # speculative plan (the A/B-control contract)
        off = PagedGenerationEngine(
            TINY, plan_db=db, spec_draft=0, **REFILL_KW, **ENGINE_KW,
        )
        assert off.spec_draft == 0
        assert off.resolved_plan.plan.decode_path == "paged"
        # explicit satellites all beat their stored values
        pin = PagedGenerationEngine(
            TINY, plan_db=db, spec_draft=2, spec_ngram=2,
            spec_drafter="ngram", spec_verify="fused",
            **REFILL_KW, **ENGINE_KW,
        )
        assert pin.spec_draft == 2
        assert pin.spec_ngram == 2
        assert pin.spec_drafter == "ngram"
        assert pin.spec_verify == "fused"

    def test_stored_dense_plan_is_miss_on_refill_engine(self, tmp_path):
        """A refill engine with spec unpinned can host 'paged' OR
        'speculative' stored plans — but a DENSE entry's knobs were never
        measured on the paged path, so the whole entry must be a miss
        (review finding: the unpinned-spec constructor used to request no
        decode_path at all, letting a dense plan's scan_chunk/top_p leak
        in field-by-field)."""
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(
            decode_path="dense", scan_chunk=4, top_p_impl="exact",
        ))
        store.save()
        p = PagedGenerationEngine(TINY, plan_db=db, **REFILL_KW, **ENGINE_KW)
        assert p.resolved_plan.source == "default"
        assert p.resolved_plan.plan.decode_path == "paged"
        assert p.scan_chunk == 0
        assert p.plan_top_p_impl is None
        assert p.spec_draft == 0

    def test_config_spec_draft_zero_pins_off(self):
        """TrainConfig.spec_draft follows the decode_scan_chunk convention:
        None (the default) stays out of the engine kwargs — plan-DB-
        resolvable — while an explicit 0 reaches the engine as a pin, so a
        --spec_draft 0 A/B can never be retuned into a speculative run by
        a stored plan (review finding: the trainer used to forward only
        truthy values, making the off-pin unreachable)."""
        from distrl_llm_tpu.config import TrainConfig
        from distrl_llm_tpu.trainer import engine_kwargs_from_config

        base = dict(engine_impl="paged", continuous_batching=True,
                    max_concurrent_sequences=4)
        assert "spec_draft" not in engine_kwargs_from_config(
            TrainConfig(**base)
        )
        kw = engine_kwargs_from_config(TrainConfig(spec_draft=0, **base))
        assert kw["spec_draft"] == 0
        # spec_ngram rides the same convention: unset stays DB-resolvable
        # even when speculation itself came from the DB, explicit pins
        kw = engine_kwargs_from_config(TrainConfig(spec_draft=4, **base))
        assert kw["spec_draft"] == 4 and "spec_ngram" not in kw
        kw = engine_kwargs_from_config(TrainConfig(spec_ngram=4, **base))
        assert kw["spec_ngram"] == 4 and "spec_draft" not in kw

    def test_stored_spec_plan_degrades_on_wave_engine(self, tmp_path):
        """A stored speculative plan must never crash or silently reshape
        a wave-scheduler run: the decode-path mismatch drops the entry
        and the engine stays plain paged."""
        db = str(tmp_path / "db.json")
        store = PlanStore(db)
        store.put(_key(), ExecutionPlan(
            decode_path="speculative", spec_draft_len=4,
        ))
        store.save()
        p = PagedGenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert p.scheduler == "waves"
        assert p.spec_draft == 0
        assert p.resolved_plan.plan.decode_path == "paged"

    def test_candidate_plans_prune_spec_combos(self):
        from distrl_llm_tpu.autotune import candidate_plans

        plans = candidate_plans(
            decode_paths=("paged", "speculative"),
            scan_chunks=(0,),
            spec_draft_lens=(0, 4),
            spec_drafters=(None, "ngram", "self"),
            spec_verifies=(None, "fused"),
        )
        # spec knobs pair only with the speculative path, and the
        # speculative path always carries a draft length (a spec plan
        # with d=0 is just the paged path wearing a costume)
        assert all(
            p.spec_draft_len > 0 for p in plans
            if p.decode_path == "speculative"
        )
        assert all(
            p.spec_draft_len == 0 and p.spec_drafter is None
            and p.spec_verify is None
            for p in plans if p.decode_path == "paged"
        )
        spec = {(p.spec_drafter, p.spec_verify) for p in plans
                if p.decode_path == "speculative"}
        assert spec == {(None, None), (None, "fused"), ("ngram", None),
                        ("ngram", "fused"), ("self", None),
                        ("self", "fused")}

    def test_spec_plan_field_validation(self):
        with pytest.raises(ValueError, match="spec_draft_len"):
            ExecutionPlan(decode_path="speculative", spec_draft_len=17)
        with pytest.raises(ValueError, match="spec_drafter"):
            ExecutionPlan(spec_drafter="oracle")
        with pytest.raises(ValueError, match="spec_verify"):
            ExecutionPlan(spec_verify="maybe")
        with pytest.raises(ValueError, match="spec_ngram_k"):
            ExecutionPlan(spec_ngram_k=-1)


class TestMicrobenchSelfDrafter:
    """The microbench must not score spec_drafter='self' candidates in the
    q == p regime (review finding: with nothing pushed through the mailbox
    the drafter fell back to the target adapter — acceptance ≡ 1.0,
    systematically optimistic vs any real superseded version)."""

    def test_perturbed_drafter_differs_on_every_leaf(self):
        import jax

        from distrl_llm_tpu.autotune.microbench import _perturbed_drafter
        from distrl_llm_tpu.models import init_lora_params

        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        prev = _perturbed_drafter(lora)
        for a, b in zip(
            jax.tree_util.tree_leaves(lora), jax.tree_util.tree_leaves(prev)
        ):
            # zero-init B leaves must be perturbed too — they are exactly
            # the leaves whose production updates make the drafter differ
            assert not jnp.array_equal(a, b)
        # deterministic: same seed, same drafter
        again = _perturbed_drafter(lora)
        for a, b in zip(
            jax.tree_util.tree_leaves(prev), jax.tree_util.tree_leaves(again)
        ):
            assert jnp.array_equal(a, b)

    def test_self_candidate_without_lora_is_infeasible(self):
        import jax

        from distrl_llm_tpu.autotune.microbench import tune_geometry
        from distrl_llm_tpu.autotune.plan import ExecutionPlan

        params = init_params(jax.random.PRNGKey(0), TINY)
        plan = ExecutionPlan(
            decode_path="speculative", spec_draft_len=2, spec_drafter="self",
        )
        results = tune_geometry(
            TINY, params, None, [plan],
            n_prompts=1, n_candidates=1,
            max_prompt_tokens=8, max_new_tokens=4,
        )
        assert len(results) == 1 and not results[0].feasible
        assert "LoRA" in results[0].note

    def test_self_candidate_measures_with_distinct_drafter(self):
        """tune_geometry must seed the superseded-adapter slot with a
        drafter that is NOT the target adapter before timing a 'self'
        candidate (acceptance < 1 becomes reachable)."""
        import jax

        from distrl_llm_tpu.autotune import microbench
        from distrl_llm_tpu.autotune.plan import ExecutionPlan

        params = init_params(jax.random.PRNGKey(0), TINY)
        from distrl_llm_tpu.models import init_lora_params

        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        seeded = {}
        real_build = microbench.build_engine_for_plan

        def spy_build(*a, **kw):
            engine = real_build(*a, **kw)
            seeded["engine"] = engine
            return engine

        plan = ExecutionPlan(
            decode_path="speculative", spec_draft_len=2, spec_drafter="self",
        )
        orig = microbench.build_engine_for_plan
        microbench.build_engine_for_plan = spy_build
        try:
            results = microbench.tune_geometry(
                TINY, params, lora, [plan],
                n_prompts=1, n_candidates=1,
                max_prompt_tokens=8, max_new_tokens=4,
                warmup=0, repeats=1,
            )
        finally:
            microbench.build_engine_for_plan = orig
        assert results[0].feasible, results[0].note
        engine = seeded["engine"]
        assert engine._prev_lora is not None
        leaves_t = jax.tree_util.tree_leaves(lora)
        leaves_d = jax.tree_util.tree_leaves(engine._prev_lora)
        assert any(
            not jnp.array_equal(a, b) for a, b in zip(leaves_t, leaves_d)
        )


class TestKeys:
    def test_canonical_device_kind_aliases(self):
        assert canonical_device_kind("TPU v5e") == "tpu_v5e"
        assert canonical_device_kind("TPU v5 lite") == "tpu_v5e"
        assert canonical_device_kind("tpu v5litepod") == "tpu_v5e"
        assert canonical_device_kind("TPU v6e") == "tpu_v6"
        assert canonical_device_kind("Weird Chip 9") == "weird_chip_9"

    def test_shape_bucket_rows_power_of_two(self):
        assert shape_bucket(350, 1200) == "p350_n1200"
        assert shape_bucket(350, 1200, 480) == "p350_n1200_r512"
        assert shape_bucket(350, 1200, 512) == "p350_n1200_r512"

    def test_model_hash_stable_and_distinct(self):
        from distrl_llm_tpu.models import QWEN2_0_5B

        assert model_config_hash(TINY) == model_config_hash(TINY)
        assert model_config_hash(TINY) != model_config_hash(QWEN2_0_5B)


class TestQuantPlanFields:
    """ISSUE 15: kv_format/base_quant plan fields — validation, candidate
    space, engine resolution, and the explicit-pin convention."""

    def test_field_validation(self):
        ExecutionPlan(kv_format="int8", base_quant="int4")
        ExecutionPlan(kv_format="none", base_quant="none")
        with pytest.raises(ValueError, match="kv_format"):
            ExecutionPlan(kv_format="fp8")
        with pytest.raises(ValueError, match="base_quant"):
            ExecutionPlan(base_quant="int2")

    def test_defaults_stay_none(self):
        # the empty-DB byte-identity contract: DEFAULT_PLAN's new fields
        # are None (engine default), so resolution without a DB entry
        # leaves every engine exactly as before ISSUE 15
        assert DEFAULT_PLAN.kv_format is None
        assert DEFAULT_PLAN.base_quant is None

    def test_candidate_space_enumerates_formats(self):
        from distrl_llm_tpu.autotune import candidate_plans

        plans = candidate_plans(
            scan_chunks=(0,), kv_formats=(None, "int8"),
            base_quants=(None, "int4"),
        )
        combos = {(p.kv_format, p.base_quant) for p in plans}
        assert combos == {
            (None, None), (None, "int4"), ("int8", None), ("int8", "int4"),
        }

    def test_engine_adopts_stored_kv_format(self, tmp_path):
        db = str(tmp_path / "db.json")
        _write_db(db, {
            _key(): {
                "plan": ExecutionPlan(
                    decode_path="dense", kv_format="int8"
                ).to_dict(),
                "measurements": [], "note": "",
            },
        })
        eng = GenerationEngine(TINY, plan_db=db, **ENGINE_KW)
        assert eng.kv_quant == "int8"
        assert eng.cache_dtype == "int8"  # the scale-carrying dense cache

    def test_explicit_none_pins_past_stored_int8(self, tmp_path):
        db = str(tmp_path / "db.json")
        _write_db(db, {
            _key(): {
                "plan": ExecutionPlan(
                    decode_path="dense", kv_format="int8"
                ).to_dict(),
                "measurements": [], "note": "",
            },
        })
        eng = GenerationEngine(TINY, plan_db=db, kv_quant="none", **ENGINE_KW)
        assert eng.kv_quant == "none"

    def test_paged_engine_adopts_and_pins(self, tmp_path):
        db = str(tmp_path / "db.json")
        _write_db(db, {
            _key(): {
                "plan": ExecutionPlan(
                    decode_path="paged", kv_format="int8"
                ).to_dict(),
                "measurements": [], "note": "",
            },
        })
        eng = PagedGenerationEngine(TINY, plan_db=db, page_size=8, **ENGINE_KW)
        assert eng.kv_quant == "int8"
        pinned = PagedGenerationEngine(
            TINY, plan_db=db, page_size=8, kv_quant="none", **ENGINE_KW
        )
        assert pinned.kv_quant == "none"

    def test_empty_db_keeps_historical_default(self, tmp_path):
        eng = GenerationEngine(
            TINY, plan_db=str(tmp_path / "nope.json"), **ENGINE_KW
        )
        assert eng.kv_quant == "none"
        assert eng.cache_dtype == jnp.float32

    def test_ingest_carries_quant_provenance(self):
        from tools.autotune import plan_from_bench_row

        plan = plan_from_bench_row({
            "engine": "dense", "scan_chunk": 0, "scan_chunk_active": None,
            "kv_format": "int8", "base_quant": "int4",
        })
        assert plan.kv_format == "int8"
        assert plan.base_quant == "int4"
        # pre-ISSUE-15 rows: fields absent → None (engine default)
        legacy = plan_from_bench_row({
            "engine": "dense", "scan_chunk": 0, "scan_chunk_active": None,
        })
        assert legacy.kv_format is None
        assert legacy.base_quant is None

    def test_microbench_builds_kv_format_candidate(self):
        from distrl_llm_tpu.autotune.microbench import build_engine_for_plan

        eng = build_engine_for_plan(
            TINY, ExecutionPlan(decode_path="dense", kv_format="int8"),
            max_prompt_tokens=16, max_new_tokens=8, rows=4,
        )
        assert eng.kv_quant == "int8"
