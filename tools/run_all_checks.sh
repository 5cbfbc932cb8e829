#!/bin/bash
# Full local validation battery (CPU host): the checks a round should be
# green on before it ends. Each stage prints PASS/FAIL; exits nonzero if any
# stage fails. Suite stages are chunked so each stays under ~10 minutes.
#
# Usage: bash tools/run_all_checks.sh [--quick]
#   --quick: entry points + one representative suite chunk only
cd "$(dirname "$0")/.."
set -u
fails=0

stage() {
  local name="$1"; shift
  echo "=== $name"
  if "$@"; then echo "PASS $name"; else echo "FAIL $name"; fails=$((fails+1)); fi
}

# static-analysis gate (ISSUE 11): project-native AST lint — lock
# discipline, telemetry schema, host-sync, CLI parity, wire protocol —
# blocking, zero unsuppressed findings (suppress inline with
# `# graftcheck: disable=GCxxx -- reason`, or grandfather deliberately via
# `python -m tools.graftcheck --update-baseline`). Runs first: it needs no
# devices and fails in seconds.
stage "graftcheck" timeout 120 python -m tools.graftcheck
stage "dryrun_multichip" timeout 300 python __graft_entry__.py
stage "cli_smoke" env JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  timeout 600 python train_distributed.py --smoke
# telemetry acceptance gate: 2-step traced train + worker round → one
# Chrome-trace JSON that parses and trace_report.py exits 0 on
stage "telemetry_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/telemetry_smoke.py
# autotune acceptance gate: 2-candidate micro-bench → tmpdir plan-DB
# round-trip, deterministic resolve, kwarg override, corrupt-DB fallback
stage "autotune_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/autotune_smoke.py
# async-rollout gate (ISSUE 4): sync/pipelined/async tiny runs through the
# real engine — finite losses, buffer/staleness telemetry in the trace, and
# the trace_report rollout section
stage "rollout_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/rollout_smoke.py
# fault-tolerance gate (ISSUE 5): a multi-worker training run survives a
# seeded kill/restart of a worker mid-run — shards resubmit, the rejoin
# loop recovers capacity, group accounting stays intact, SIGTERM drains
stage "chaos_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/chaos_smoke.py
# speculative-decoding gate (ISSUE 6): greedy bit-identity for both
# drafters (ngram + previous-LoRA self-drafting), chunked dispatch, emit
# accounting, and a traced async train through the spec engine whose
# trace_report shows the speculative section
stage "spec_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/spec_smoke.py
# continuous-batching gate (ISSUE 12): grouped prompts through the
# prefix-sharing and continuous-admission engines — byte-identical greedy
# outputs vs the unshared fixed-batch golden, genuinely shared prompt
# pages (pages_shared_frac > 0), >= 1 mid-round backfill admission,
# once-per-group prefill, budgeted-pool preemption parity, and the
# speculative composition
stage "cb_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/cb_smoke.py
# serving-observability gate (ISSUE 13): a continuous-admission run with
# the serving ledger armed — byte-identical outputs, complete monotone
# per-group lifecycles (enqueue <= admit <= first_token <= finish), >= 1
# backfill with nonzero queue-wait, stall-reason counts summing to the
# declined-admission passes, scrapable Prometheus histogram buckets, and
# a seeded DISTRL_SENTINEL_INJECT=ttft_blowup producing exactly one
# flight-recorder bundle
stage "serving_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/serving_smoke.py
# quantized-serving gate (ISSUE 15): quantized-base greedy decode through
# the fused dequant-matmul kernel bit-identical to the XLA container path
# (int8 + int4, LoRA epilogue), fused sampler greedy bit-identity + a
# seeded sampled-path distribution check, and int8-KV plan resolution
# (stored kv_format adopted, explicit "none" pins, empty DB = historical
# default)
stage "quant_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/quant_smoke.py
# observability gate (ISSUE 8): 2-worker tiny run — scrape both worker
# endpoints and the driver's fleet endpoint mid-run (fleet/* series
# present, per-worker token counters flowing), inject a seeded NaN,
# assert exactly one incident bundle with the expected manifest
stage "obs_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/obs_smoke.py
# self-healing-runtime gate (ISSUE 14): armed-but-quiescent controllers
# byte-identical to controllers-off, seeded nan-loss rollback ends with a
# finite loss + a lineage rollback record, sustained fake HBM pressure
# walks the admission cap to its clamp in exactly the bounded shrink count
# (no oscillation, run completes), and an injected ttft_blowup escalates
# into one shed engage/release with conservation-intact "shed" attribution
stage "control_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/control_smoke.py
# weight-bus gate (ISSUE 9): broadcast-bus tiny train byte-identical to the
# dispatch-transport golden (losses + adapter), per-dispatch payload shed
# >= the serialized adapter, and a seeded mid-run worker kill/rejoin whose
# full-resync converges both version caches bit-identically
stage "weight_bus_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/weight_bus_smoke.py
# lineage gate (ISSUE 10): 2-worker async run over the broadcast bus —
# every trained group's lineage record closes (sampled version <= consumed
# step's version, worker + dispatch provenance), learn-to-act measured for
# >= 1 in-flight swap, every worker span in the merged trace resolves to
# its driver dispatch, and the lag histograms reconcile with the existing
# rollout/staleness + obs/weight_sync_ms series
stage "lineage_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/lineage_smoke.py
# training-dynamics gate (ISSUE 16): armed learn_obs run byte-identical to
# off (losses + adapter checksum), learn/* gauges in the per-step sink
# records + learn.jsonl step/summary stream, a seeded kl_blowup yields
# exactly one incident bundle, and learn_report/lineage_report exit 0 on
# the run's artifacts
stage "learn_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/learn_smoke.py
# pluggable-environment gate (ISSUE 17): the code env's <tool> block runs
# in the sandbox and round-trips loss-masked, both multi-turn envs train
# end-to-end sync+async through the paged refill engine with turn
# continuations resuming resident KV chains (no prefix re-prefill), and
# lineage stamps per-turn provenance the report tool renders
stage "env_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/env_smoke.py
# tiered-KV gate (ISSUE 18): warm-prefix rounds book measured
# prefill_tok_saved, cross-round re-admission restores through the host-
# parked tree, a tight page budget spills tier-2 and restores bit-exact,
# and a multi-turn round's transcript re-admits as the next round's
# prompt with every full history page served from cache — all arms
# byte-identical to the cache-off golden run under greedy decode
stage "radix_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/radix_smoke.py
# serving-gateway gate (ISSUE 19): a multi-tenant three-class replay over
# the streaming HTTP front-end — chunk streams byte-complete, scavenger
# sheds under a pinned floor while interactive never does, the per-class
# admission audit conserves on the ledger AND the registry, a
# quota-impossible request 400s at the door, and greedy outputs are
# byte-identical before the gateway ever attaches and after it closes
stage "gateway_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/gateway_smoke.py
# elastic-fleet gate (ISSUE 20): a supervised pool scales 2→4→2 under fake
# load signals — cooldown-spaced scale-ups admit cold workers that answer
# dispatches, a seeded SIGKILL mid-scale-event converges via the restart
# budget, scale-downs drain gracefully (exactly one drain per retire),
# fleet totals stay monotone across scale-in, and the armed-but-quiescent
# autoscaler is byte-identical to controllers-off
stage "fleet_smoke" env JAX_PLATFORMS=cpu \
  timeout 600 python tools/fleet_smoke.py
if [ "${1:-}" = "--quick" ]; then
  # representative post-tiering mix: budget accounting + config + one
  # engine-parity and one learner-parity anchor from the default tier
  stage "suite_quick" timeout 600 python -m pytest -q \
    tests/test_paged_budget.py tests/test_config.py \
    "tests/test_paged.py::TestPagedEngine::test_greedy_matches_dense_engine" \
    "tests/test_train_step.py::TestDataParallelStep"
  echo "quick done: $fails failure(s)"; exit $((fails > 0))
fi

stage "suite_trainer" timeout 600 python -m pytest -q \
  tests/test_trainer.py tests/test_async_rollout.py tests/test_clip_objective.py \
  tests/test_failure_and_resume.py tests/test_role_separation.py \
  tests/test_rollout_buffer.py tests/test_rollout_modes.py tests/test_env.py
stage "suite_engines_1" timeout 600 python -m pytest -q \
  tests/test_engine.py tests/test_paged.py
stage "suite_engines_2" timeout 600 python -m pytest -q \
  tests/test_speculative.py tests/test_sharded_paged.py
stage "suite_engines_3" timeout 600 python -m pytest -q \
  tests/test_paged_budget.py tests/test_inflight_updates.py \
  tests/test_prefix_sharing.py tests/test_tpu_compile.py
stage "suite_learner" timeout 600 python -m pytest -q \
  tests/test_train_step.py tests/test_losses.py tests/test_model_golden.py \
  tests/test_lora.py tests/test_optim.py tests/test_quant.py tests/test_sharding.py
stage "suite_ops" timeout 600 python -m pytest -q \
  tests/test_flash_attention.py tests/test_splash.py tests/test_ring_attention.py \
  tests/test_ulysses.py tests/test_chunking.py tests/test_sampling.py
stage "suite_misc" timeout 600 python -m pytest -q \
  tests/test_control_plane.py tests/test_data.py tests/test_rewards.py \
  tests/test_shaping.py tests/test_long_context.py tests/test_full_finetune.py \
  tests/test_telemetry.py tests/test_obs.py tests/test_weight_bus.py \
  tests/test_lineage.py tests/test_control.py tests/test_serving_obs.py \
  tests/test_gateway.py
# the hybrid families (PR 62): the cases every family repeats, once
# (tests/test_family_conformance.py over tests/family_suite.py's records), then
# each family's own mechanism
stage "suite_families_shared" timeout 900 python -m pytest -q \
  tests/test_family_conformance.py tests/test_hybrid_prefill_stages.py \
  tests/test_decode_view.py
stage "suite_families_own" timeout 900 python -m pytest -q \
  tests/test_hybrid_model.py tests/test_latent_moe.py tests/test_delta_moe.py \
  tests/test_power_model.py tests/test_jamba_model.py tests/test_window_moe_model.py \
  tests/test_dsa_moe_model.py tests/test_cca_moe.py tests/test_swa_sink_moe_model.py
stage "suite_io" timeout 600 python -m pytest -q \
  tests/test_from_pretrained.py tests/test_remote_engine.py \
  tests/test_native_tokenizer.py tests/test_native_spm.py \
  tests/test_config.py tests/test_cli.py tests/test_real_checkpoint.py \
  tests/test_devices.py tests/test_docs.py
# the slow tier (excluded from the default run by pytest.ini addopts):
# heavyweight fuzz/parity/scale cases. Chunked like the fast stages so one
# stage timeout can't silently drop the back half of the tier.
stage "suite_slow_engines" timeout 1200 python -m pytest -q -m slow \
  tests/test_engine.py tests/test_paged.py tests/test_sharded_paged.py \
  tests/test_inflight_updates.py
# the cells' whole steps and prefills compiled for the described v5e at the
# cells' OWN depth and context, each byte limit with them (14 cases, eight
# minutes on four cores; the default run holds their one-period forms): run it
# before the chip after touching a cell's step, a kernel's launch or the pools
stage "suite_slow_tpu_compile" timeout 1200 python -m pytest -q -m slow \
  tests/test_tpu_compile.py
stage "suite_slow_sched" timeout 1200 python -m pytest -q -m slow \
  tests/test_speculative.py tests/test_paged_budget.py \
  tests/test_prefix_sharing.py
stage "suite_slow_learner" timeout 1200 python -m pytest -q -m slow \
  tests/test_train_step.py tests/test_losses.py tests/test_clip_objective.py \
  tests/test_full_finetune.py tests/test_quant.py tests/test_trainer.py \
  tests/test_async_rollout.py tests/test_failure_and_resume.py \
  tests/test_rollout_buffer.py tests/test_rollout_modes.py
stage "suite_slow_ops" timeout 1200 python -m pytest -q -m slow \
  tests/test_ring_attention.py tests/test_ulysses.py tests/test_sampling.py \
  tests/test_long_context.py \
  tests/test_sharding.py tests/test_role_separation.py
stage "suite_slow_io" timeout 1200 python -m pytest -q -m slow \
  tests/test_from_pretrained.py tests/test_real_checkpoint.py \
  tests/test_remote_engine.py tests/test_control_plane.py \
  tests/test_model_golden.py tests/test_weight_bus.py

echo "done: $fails failure(s)"
exit $((fails > 0))
