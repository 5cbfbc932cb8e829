"""The contract of bench.py: exactly ONE parseable JSON line on stdout with
the required keys when it runs — plus the honesty field (scan_chunk_active)
a reader needs — and no row at all when there is no TPU and the CPU was not
asked for in so many words.

``TestBenchContract`` runs the real script in a subprocess on the CPU backend
at tiny volume (``JAX_PLATFORMS=cpu``, asked for), so a refactor that breaks
the record shape or the env-var contract fails here. ``TestDeviceHandling``
holds the small functions that keep a measurement on the right device, with
stub devices and no subprocess.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench(extra_env: dict, timeout: int = 600) -> dict:
    # hermetic: strip every BENCH_* var a watcher/driver shell may have
    # exported, and conftest's 8-virtual-device XLA_FLAGS mutation — the
    # record must describe the single-device surface the driver invokes
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_") and k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, (
        f"expected ONE JSON line, got {lines!r}; "
        f"stderr tail: {out.stderr[-800:]}"
    )
    return json.loads(lines[0])


@pytest.mark.slow
class TestBenchContract:
    TINY = {
        "BENCH_MODEL": "tiny", "BENCH_PROMPTS": "4", "BENCH_CANDIDATES": "2",
        "BENCH_MAX_PROMPT": "16", "BENCH_MAX_NEW": "24",
    }

    def test_rollout_record_shape(self):
        rec = run_bench(self.TINY)
        for key in ("metric", "value", "unit", "vs_baseline", "backend",
                    "scan_chunk", "scan_chunk_active", "engine",
                    "paged_attn_impl", "total_tokens",
                    "paged_kernel", "pages_per_block", "grid_steps_estimate",
                    "us_per_grid_step",
                    "plan", "plan_source", "cache_read_formulation",
                    "rollout_mode", "max_staleness", "rollout_dropped_stale",
                    "spec_drafter", "spec_accept_rate",
                    "tokens_per_verify_step", "spec_verify_impl",
                    "hbm_peak_bytes", "recompile_count", "fleet_tok_s",
                    "fleet_workers", "weight_bus", "weight_bytes_per_update",
                    "weight_sync_ms",
                    "cb_mode", "prefill_shared_frac", "pages_shared_frac",
                    "slot_idle_frac",
                    "ttft_p50_ms", "ttft_p99_ms", "queue_wait_p50_ms",
                    "admission_stall_frac",
                    "control_actions", "shed_groups",
                    "kv_format", "kv_quant", "base_quant",
                    "bytes_per_token", "step_bytes_accessed",
                    "sample_kernel", "quant_matmul",
                    "env_name", "turns_mean", "turns_max",
                    "env_step_ms_p50",
                    "prefix_cache", "radix_hit_rate", "prefill_tok_saved",
                    "spill_restore_ms_p50",
                    "gateway_mode", "arrival_rate",
                    "ttft_p99_interactive_ms", "ttft_p99_batch_ms",
                    "shed_frac_by_class"):
            assert key in rec, key
        # quantized-serving fields (ISSUE 15): an unpinned run resolves
        # the KV format from the (empty) plan DB — "none", the historical
        # default; the unquantized base never dispatches a quant matmul
        # (honest null), and the CPU sampler default is the multi-pass
        # path. bytes_per_token is measured cost analysis — the CPU
        # backend provides it, so the contract pins it populated.
        assert rec["kv_format"] == "none"
        assert rec["kv_quant"] == "none"
        assert rec["quant_matmul"] is None
        assert rec["sample_kernel"] == "xla"
        assert rec["bytes_per_token"] and rec["bytes_per_token"] > 0
        assert rec["step_bytes_accessed"] and rec["step_bytes_accessed"] > 0
        # measured-attribution fields (ISSUE 8): CPU has no memory stats
        # (honest null, never a fabricated number), a healthy single-config
        # run retraces nothing, and bench drives the engine directly — no
        # control-plane fleet ever publishes a tok/s gauge here
        assert rec["hbm_peak_bytes"] is None
        assert rec["recompile_count"] == 0
        assert rec["fleet_tok_s"] is None
        # weight-bus fields (ISSUE 9): bench drives a local engine, so the
        # transport provenance reads null — "no weight bus ran", distinct
        # from a fleet row's "dispatch"/"broadcast"
        assert rec["weight_bus"] is None
        assert rec["weight_bytes_per_update"] is None
        assert rec["weight_sync_ms"] is None
        # continuous-batching fields (ISSUE 12): the dense engine has no
        # admission scheduler or shared pool — every slot honestly null
        assert rec["cb_mode"] is None
        assert rec["prefill_shared_frac"] is None
        assert rec["pages_shared_frac"] is None
        assert rec["slot_idle_frac"] is None
        # serving-latency fields (ISSUE 13): no ledger without continuous
        # admission — dense rows read null, never a fabricated latency
        assert rec["ttft_p50_ms"] is None
        assert rec["ttft_p99_ms"] is None
        assert rec["queue_wait_p50_ms"] is None
        assert rec["admission_stall_frac"] is None
        # self-healing-runtime fields (ISSUE 14): controllers off — both
        # null, distinguishing "no controller ran" from "ran, acted 0×"
        assert rec["control_actions"] is None
        assert rec["shed_groups"] is None
        # tiered-KV-cache fields (ISSUE 18): the dense engine has no
        # pool at all — all four honestly null (a cache-off PAGED row
        # reads prefix_cache=False instead; see test_cb_record_fields)
        assert rec["prefix_cache"] is None
        assert rec["radix_hit_rate"] is None
        assert rec["prefill_tok_saved"] is None
        assert rec["spill_restore_ms_p50"] is None
        # serving-gateway fields (ISSUE 19): no gateway drove this row —
        # mode False, arrival/per-class-latency/shed-mix provenance null,
        # so the overload A/B can tell "no gateway" from "gateway, 0 shed"
        assert rec["gateway_mode"] is False
        assert rec["arrival_rate"] is None
        assert rec["ttft_p99_interactive_ms"] is None
        assert rec["ttft_p99_batch_ms"] is None
        assert rec["shed_frac_by_class"] is None
        # multi-turn env fields (ISSUE 17): the single-turn control row
        # never arms a turn hook — all four honestly null, so the A/B
        # artifact can tell "no env ran" from "env ran, 1 turn"
        assert rec["env_name"] is None
        assert rec["turns_mean"] is None
        assert rec["turns_max"] is None
        assert rec["env_step_ms_p50"] is None
        # spec off: the speculative self-description fields read null, so
        # a driver can distinguish "off" from "ran but never accepted"
        assert rec["spec_draft"] == 0
        assert rec["spec_drafter"] is None
        assert rec["spec_accept_rate"] is None
        assert rec["tokens_per_verify_step"] is None
        assert rec["metric"] == "rollout_tokens_per_sec_per_chip"
        assert rec["backend"] == "cpu"
        assert rec["value"] > 0
        assert "error" not in rec
        # rollout-regime fields, schema-shared with the trainer's
        # train-curve JSONL: bench drives the engine synchronously, so the
        # row always reads sync / bound 0 / zero drops
        assert rec["rollout_mode"] == "sync"
        assert rec["max_staleness"] == 0
        assert rec["rollout_dropped_stale"] == 0
        # the resolved execution plan makes the row self-describing: the
        # effective dispatch choices plus where they came from
        assert rec["plan"]["decode_path"] == "dense"
        assert rec["plan_source"] in ("db", "default", "disabled")
        assert rec["scan_chunk"] == rec["plan"]["scan_chunk"]

    def test_fleet_record_fields(self):
        """A BENCH_WORKERS row must populate the reserved fleet slot
        (ISSUE 10 satellite): the same rollout volume through 2 control-
        plane workers yields a FleetAggregator-derived fleet_tok_s, the
        worker count, and the weight-transport provenance — while the
        local-engine introspection fields honestly read null (workers run
        their own engines)."""
        rec = run_bench({**self.TINY, "BENCH_WORKERS": "2"})
        assert "error" not in rec
        assert rec["fleet_workers"] == 2
        # the aggregate derives from the workers' piggybacked monotonic
        # obs/gen_tokens counters over the timed window — a real rate
        assert rec["fleet_tok_s"] is not None and rec["fleet_tok_s"] > 0
        assert rec["weight_bus"] == "dispatch"  # the raw-API default
        assert rec["weight_bytes_per_update"] is None  # dispatch re-ships
        assert rec["weight_sync_ms"] is None
        assert rec["value"] > 0
        assert rec["bucket_used"] is None  # workers bucket their own shards

    def test_spec_record_fields(self):
        """A speculative refill row must self-describe (ISSUE 6): which
        drafter proposed, the realized accept rate, tokens per verify
        step, and which verify sweep ran — the fields the A/B artifact
        and tools/autotune.py ingestion consume."""
        rec = run_bench({
            **self.TINY, "BENCH_ENGINE": "paged",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "8",
            "BENCH_SPEC_DRAFT": "3", "BENCH_SPEC_DRAFTER": "self",
        })
        assert "error" not in rec
        assert rec["spec_draft"] == 3
        assert rec["spec_drafter"] == "self"
        assert 0.0 <= rec["spec_accept_rate"] <= 1.0
        assert rec["tokens_per_verify_step"] >= 1.0
        # CPU resolves the probe-gated fused kernel to its exact
        # unrolled fallback; either spelling is a valid record, null is not
        assert rec["spec_verify_impl"] in ("fused", "unrolled")

    def test_env_record_fields(self):
        """A BENCH_ENV row must self-describe the multi-turn regime
        (ISSUE 17): which env label ran, realized turn counts, and the
        synthetic env-step latency — while the engaged refill mirror
        still reports slot_idle_frac, the stat a multi-turn-vs-control
        A/B compares."""
        rec = run_bench({
            **self.TINY, "BENCH_ENGINE": "paged",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "4",
            "BENCH_ENV": "code", "BENCH_MAX_TURNS": "2",
        })
        assert "error" not in rec
        assert rec["env_name"] == "code"
        # every candidate takes at least its first turn; the hook grants
        # continuation up to BENCH_MAX_TURNS, so the realized mean sits
        # in [1, 2] and the max never exceeds the cap
        assert 1.0 <= rec["turns_mean"] <= 2.0
        assert 1 <= rec["turns_max"] <= 2
        assert rec["env_step_ms_p50"] is not None
        assert rec["env_step_ms_p50"] >= 0
        # turn continuations ride the refill scheduler's resident-KV
        # path, so the engaged mirror (and its idle accounting) is live
        assert rec["slot_idle_frac"] is not None
        assert 0.0 <= rec["slot_idle_frac"] < 1.0
        assert rec["value"] > 0

    def test_cb_record_fields(self):
        """A shared-prefix continuous-admission row must self-describe
        (ISSUE 12): the admission regime that ran, genuinely shared pages
        (the prompt-KV capacity win), shared-prefix admissions, and the
        slot-idle fraction the backfill A/B moves."""
        # prompts must span >= 1 FULL page (max_prompt > the 128-token
        # default page size) or there is no full-prefix chain to alias —
        # only the CoW tail, which every candidate splits
        rec = run_bench({
            **self.TINY, "BENCH_ENGINE": "paged",
            "BENCH_MAX_PROMPT": "256", "BENCH_MAX_NEW": "16",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "4",
            "BENCH_CONT_ADMISSION": "1",
        })
        assert "error" not in rec
        assert rec["cb_mode"] == "continuous"
        assert rec["scheduler"] == "refill"
        assert rec["pages_shared_frac"] > 0
        assert 0.0 < rec["prefill_shared_frac"] <= 1.0
        assert 0.0 <= rec["slot_idle_frac"] < 1.0
        assert rec["plan"]["cb_mode"] == "continuous"
        assert rec["value"] > 0
        # request-level serving latencies (ISSUE 13): a post-warmup
        # ServingLedger records the TIMED rounds, so cb rows carry real
        # percentiles and the attributed stall fraction
        assert rec["ttft_p50_ms"] is not None and rec["ttft_p50_ms"] > 0
        assert rec["ttft_p99_ms"] >= rec["ttft_p50_ms"]
        assert rec["queue_wait_p50_ms"] is not None
        assert rec["queue_wait_p50_ms"] >= 0
        assert 0.0 <= rec["admission_stall_frac"] <= 1.0
        # no ControlLimits attached: control provenance honestly null
        assert rec["control_actions"] is None
        assert rec["shed_groups"] is None
        # tiered cache off (the A/B control row): prefix_cache reads
        # False — "pool ran, cache off" — and the cache measurements null
        assert rec["prefix_cache"] is False
        assert rec["radix_hit_rate"] is None
        assert rec["prefill_tok_saved"] is None
        assert rec["spill_restore_ms_p50"] is None

    def test_radix_cache_record_fields(self):
        """BENCH_PREFIX_CACHE=1 (ISSUE 18): the warm arm's timed round
        re-admits the warmup round's prompts, so the row carries a real
        radix hit rate and saved-prefill count — the fields a
        radix_warm-vs-cb_continuous A/B compares.
        Device page ids are round-scoped, so the cross-round warm hit
        necessarily restored its pages from the host-side park — the
        restore p50 is a real measured latency here, not null."""
        # prompts must span >= 1 FULL page (the 128-token default page
        # size) or nothing is cacheable — only the mutable partial tail
        rec = run_bench({
            **self.TINY, "BENCH_ENGINE": "paged",
            "BENCH_MAX_PROMPT": "256", "BENCH_MAX_NEW": "16",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "4",
            "BENCH_CONT_ADMISSION": "1", "BENCH_PREFIX_CACHE": "1",
        })
        assert "error" not in rec
        assert rec["prefix_cache"] is True
        assert rec["radix_hit_rate"] is not None
        assert 0.0 < rec["radix_hit_rate"] <= 1.0
        assert rec["prefill_tok_saved"] is not None
        assert rec["prefill_tok_saved"] > 0
        assert rec["spill_restore_ms_p50"] is not None
        assert rec["spill_restore_ms_p50"] >= 0
        assert rec["value"] > 0

    def test_cb_control_pinned_fields(self):
        """BENCH_CONTROL_FRAC (ISSUE 14): the static governor-shrunk A/B
        arm records its control provenance — 0 dynamic actions (the pin
        IS the action) and 0 shed groups — while completing the same
        volume under the shrunk chain cap."""
        rec = run_bench({
            **self.TINY, "BENCH_ENGINE": "paged",
            "BENCH_MAX_PROMPT": "256", "BENCH_MAX_NEW": "16",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "4",
            "BENCH_CONT_ADMISSION": "1", "BENCH_CONTROL_FRAC": "0.4",
        })
        assert "error" not in rec
        assert rec["cb_mode"] == "continuous"
        assert rec["control_actions"] == 0
        assert rec["shed_groups"] == 0
        assert rec["value"] > 0

    def test_gateway_record_fields(self):
        """A BENCH_GATEWAY row must self-describe the serving-gateway
        regime (ISSUE 19): open-loop mode on, the offered arrival rate,
        per-class TTFT p99s off the ledger's class-tagged samples —
        the fields a 1x-vs-2x overload A/B and tools/bench_history.py
        compare."""
        # 8 requests: the seeded mix needs >= 5 before an interactive
        # arrival shows up (the weights skew toward batch)
        rec = run_bench({
            **self.TINY, "BENCH_PROMPTS": "8", "BENCH_ENGINE": "paged",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "4",
            "BENCH_CONT_ADMISSION": "1", "BENCH_GATEWAY": "1",
            "BENCH_ARRIVAL_RPS": "16", "BENCH_ARRIVAL_PROCESS": "poisson",
        })
        assert "error" not in rec
        assert rec["gateway_mode"] is True
        assert rec["arrival_rate"] == 16.0
        # the synthesized mix always includes interactive and batch, and
        # every closed request feeds a class-tagged TTFT sample
        assert rec["ttft_p99_interactive_ms"] is not None
        assert rec["ttft_p99_interactive_ms"] > 0
        assert rec["ttft_p99_batch_ms"] is not None
        assert rec["ttft_p99_batch_ms"] > 0
        # the open-loop replay measures wall-clock, not engine steps —
        # step/alive accounting honestly absent, volume still real
        assert rec["value"] > 0
        assert rec["total_tokens"] > 0

    def test_gateway_needs_refill_engine(self):
        """BENCH_GATEWAY on the dense engine is a config error: still
        exactly one JSON line, with the error naming the constraint."""
        rec = run_bench({**self.TINY, "BENCH_GATEWAY": "1"})
        assert "error" in rec
        assert "continuous-admission" in rec["error"]
        assert rec["vs_baseline"] == 0.0

    def test_cb_fixed_control_fields(self):
        """The fixed-batch refill control reads cb_mode='refill' with the
        sharing fields null — distinguishable from a shared row by the
        artifact alone."""
        rec = run_bench({
            **self.TINY, "BENCH_ENGINE": "paged",
            "BENCH_SCHEDULER": "refill", "BENCH_MAX_CONCURRENT": "4",
        })
        assert "error" not in rec
        assert rec["cb_mode"] == "refill"
        assert rec["prefill_shared_frac"] is None
        assert rec["pages_shared_frac"] is None
        assert rec["slot_idle_frac"] is not None
        # fixed-batch control: no continuous admission, no serving ledger
        # — the serving fields read null (the cb A/B distinguishes the
        # arms from the artifact alone)
        assert rec["ttft_p50_ms"] is None
        assert rec["ttft_p99_ms"] is None
        assert rec["queue_wait_p50_ms"] is None
        assert rec["admission_stall_frac"] is None

    def test_quantized_arm_reduces_measured_bytes(self):
        """ISSUE 15 acceptance: the int8-base + int8-KV arm must stream
        fewer MEASURED bytes per token (decode-step cost_analysis) than
        the bf16/f32 control at identical volume — the quantized-serving
        scoreboard the checked-in benchmarks/r15 artifact freezes."""
        common = {**self.TINY, "BENCH_NO_EOS": "1"}
        ctrl = run_bench(common)
        arm = run_bench({
            **common, "BENCH_BASE_QUANT": "int8",
            "BENCH_KV_FORMAT": "int8", "BENCH_PARAMS_CACHE": "",
        })
        assert "error" not in ctrl and "error" not in arm
        assert arm["base_quant"] == "int8"
        assert arm["kv_format"] == "int8"
        assert ctrl["bytes_per_token"] and arm["bytes_per_token"]
        assert arm["bytes_per_token"] < ctrl["bytes_per_token"], (
            arm["bytes_per_token"], ctrl["bytes_per_token"],
        )

    def test_learner_record_shape(self):
        rec = run_bench({
            "BENCH_MODE": "learner", "BENCH_MODEL": "tiny",
            "BENCH_ROWS": "2", "BENCH_MICRO": "1",
            "BENCH_MAX_PROMPT": "16", "BENCH_MAX_NEW": "16",
            "BENCH_STEPS": "1",
        })
        assert rec["metric"] == "learner_tokens_per_sec_per_chip"
        for key in ("step_seconds", "mfu", "attn_impl", "attn_fallback",
                    "base_quant", "loss",
                    "hbm_peak_bytes", "recompile_count"):
            assert key in rec, key
        assert "error" not in rec
        # training-dynamics fields (ISSUE 16): keys always present,
        # honestly null when BENCH_LEARN_OBS did not arm the fused bundle
        for key in ("entropy", "kl_p90", "clip_frac", "ratio_cap_frac"):
            assert key in rec, key
            assert rec[key] is None

    def test_learner_dynamics_fields(self):
        """BENCH_LEARN_OBS=1 (ISSUE 16): the armed learner row carries the
        measured policy-health fields — entropy/kl_p90/clip_frac real
        numbers off the device bundle, ratio_cap_frac still null (the
        bench step runs the PPO-clip objective, not AIPO)."""
        rec = run_bench({
            "BENCH_MODE": "learner", "BENCH_MODEL": "tiny",
            "BENCH_ROWS": "2", "BENCH_MICRO": "1",
            "BENCH_MAX_PROMPT": "16", "BENCH_MAX_NEW": "16",
            "BENCH_STEPS": "1", "BENCH_LEARN_OBS": "1",
        })
        assert "error" not in rec
        assert rec["entropy"] is not None and rec["entropy"] > 0
        assert rec["kl_p90"] is not None and rec["kl_p90"] >= 0
        assert rec["clip_frac"] is not None
        assert 0.0 <= rec["clip_frac"] <= 1.0
        assert rec["ratio_cap_frac"] is None

    def test_learner_quantized_base(self):
        rec = run_bench({
            "BENCH_MODE": "learner", "BENCH_MODEL": "tiny",
            "BENCH_ROWS": "2", "BENCH_MICRO": "1",
            "BENCH_MAX_PROMPT": "16", "BENCH_MAX_NEW": "16",
            "BENCH_STEPS": "1", "BENCH_BASE_QUANT": "int4",
            # no cache dir -> host-quantize in-process
            "BENCH_PARAMS_CACHE": "",
        })
        assert rec["base_quant"] == "int4"
        assert "error" not in rec

    def test_invalid_base_quant_still_one_line(self):
        rec = run_bench({**self.TINY, "BENCH_BASE_QUANT": "fp5"})
        assert "error" in rec
        assert rec["vs_baseline"] == 0.0

    def test_scan_chunk_active_flag(self):
        rec = run_bench({**self.TINY, "BENCH_SCAN_CHUNK": "4"})
        # CPU compiles accept chunk programs (no memory analysis), so the
        # honesty flag must report the chunked program actually ran
        assert rec["scan_chunk"] == 4
        assert rec["scan_chunk_active"] is True


def _dev(platform: str, kind: str):
    import types

    return types.SimpleNamespace(platform=platform, device_kind=kind)


class TestDeviceHandling:
    """bench.py measures the accelerator: it never moves itself to another
    backend, never assumes a peak for a chip it cannot name, and keeps its
    compile cache where the environment says (or in the checkout)."""

    def test_guard_raises_without_tpu_unless_cpu_was_asked_for(self):
        from distrl_llm_tpu.utils.devices import require_tpu

        cpu = [_dev("cpu", "cpu")]
        tpu = [_dev("tpu", "TPU v5 lite")]
        assert require_tpu(tpu) is tpu
        assert require_tpu(tpu, cpu_requested="cpu") is tpu
        assert require_tpu(cpu, cpu_requested="cpu") is cpu
        assert require_tpu(cpu, cpu_requested=" CPU ") is cpu
        for asked in (None, "", "tpu", "tpu,cpu"):
            with pytest.raises(RuntimeError, match="no TPU"):
                require_tpu(cpu, cpu_requested=asked)
        with pytest.raises(RuntimeError, match="no TPU"):
            require_tpu([_dev("gpu", "A100")], cpu_requested="cpu")

    def test_unknown_device_kind_is_an_error_not_a_default_peak(
        self, monkeypatch
    ):
        import jax

        from distrl_llm_tpu import telemetry

        assert telemetry.peak_flops_for_kind("TPU v5 lite") == 197e12
        assert telemetry.peak_flops_for_kind("TPU v5e") == 197e12
        with pytest.raises(ValueError, match="TPU v9x"):
            telemetry.peak_flops_for_kind("TPU v9x")
        monkeypatch.delenv("DISTRL_PEAK_FLOPS", raising=False)
        monkeypatch.setattr(jax, "devices", lambda: [_dev("tpu", "TPU v9x")])
        with pytest.raises(ValueError, match="TPU v9x"):
            telemetry.device_peak_flops()
        # the CPU has no peak, and publishes no utilisation
        monkeypatch.setattr(jax, "devices", lambda: [_dev("cpu", "cpu")])
        assert telemetry.device_peak_flops() is None

    def test_unnamed_tpu_is_an_error_for_plan_keys_too(self, monkeypatch):
        import jax

        from distrl_llm_tpu.autotune import current_device_kind

        monkeypatch.setattr(jax, "devices", lambda: [_dev("tpu", "TPU v5 lite")])
        assert current_device_kind() == "tpu_v5e"
        monkeypatch.setattr(jax, "devices", lambda: [_dev("tpu", "TPU x1")])
        with pytest.raises(ValueError, match="TPU x1"):
            current_device_kind()

        def no_backend():
            raise RuntimeError("Unable to initialize backend")

        monkeypatch.setattr(jax, "devices", no_backend)
        assert current_device_kind() == "unknown"

    def test_tpu_without_bytes_limit_is_an_error_not_16_gib(self):
        from distrl_llm_tpu.engine.budget import (
            DEFAULT_HBM_BYTES, device_hbm_bytes,
        )

        def dev(platform, stats):
            d = _dev(platform, platform)
            d.memory_stats = lambda: stats
            return d

        assert device_hbm_bytes(dev("tpu", {"bytes_limit": 123})) == 123
        for stats in (None, {}, {"bytes_in_use": 1}):
            with pytest.raises(RuntimeError, match="bytes_limit"):
                device_hbm_bytes(dev("tpu", stats))
        assert device_hbm_bytes(dev("cpu", None)) == DEFAULT_HBM_BYTES

    def test_compile_cache_helper(self, monkeypatch, tmp_path):
        import jax

        from distrl_llm_tpu.utils import devices

        # an exported directory is JAX's own business: nothing is set in code
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert devices.enable_compile_cache() == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "c").exists()
        # a CPU rehearsal keeps none
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert devices.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
        # otherwise: the fixed path inside the checkout
        monkeypatch.delenv("JAX_PLATFORMS")
        try:
            got = devices.enable_compile_cache()
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert "/tmp" not in got and str(os.getpid()) not in got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
