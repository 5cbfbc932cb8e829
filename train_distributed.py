#!/usr/bin/env python
"""CLI entry point — flag-for-flag parity with the reference
``train_distributed.py`` (BY571/DistRL-LLM train_distributed.py:10–35), with
TPU-native knobs appended. Pipeline parity (:38–85): load MATH-500, rename
answer→solution, 90/10 split, chat-template with the R1 preprompt, train.

Usage (reference README.md:48–61 contract):
    python train_distributed.py --model Qwen/Qwen2.5-7B-Instruct \
        --number_of_actors 2 --number_of_learners 1 --learner grpo
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from distrl_llm_tpu.config import MeshConfig, TrainConfig
from distrl_llm_tpu.data import prepare_dataset
from distrl_llm_tpu.rewards import reward_function
from distrl_llm_tpu.tokenizer import load_tokenizer
from distrl_llm_tpu.trainer import Trainer
from distrl_llm_tpu.utils.devices import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native distributed RL for LLMs")
    # --- reference flags (train_distributed.py:10–35), names and defaults kept
    p.add_argument("--model", type=str, default="Qwen/Qwen2.5-7B-Instruct")
    p.add_argument("--dataset", type=str, default="HuggingFaceH4/MATH-500")
    p.add_argument("--run_name", type=str, default=None)
    p.add_argument("--project_name", type=str, default="math-reasoning")
    p.add_argument("--lora_save_path", type=str, default="lora_request_math")
    p.add_argument("--lr", type=float, default=2e-5)
    p.add_argument("--max_new_tokens", type=int, default=1200)
    p.add_argument("--max_prompt_tokens", type=int, default=350)
    p.add_argument("--temperature", type=float, default=1.2)
    p.add_argument("--episodes", type=int, default=15)
    p.add_argument("--num_candidates", type=int, default=16)
    p.add_argument("--batch_size", type=int, default=30)
    p.add_argument("--learner_chunk_size", type=int, default=8)
    p.add_argument("--train_batch_size", type=int, default=8)
    p.add_argument("--save_every", type=int, default=100)
    p.add_argument("--eval_every", type=int, default=10)
    p.add_argument("--number_of_actors", type=int, default=2)
    p.add_argument("--number_of_learners", type=int, default=1)
    p.add_argument("--learner", type=str, default="pg", choices=["pg", "grpo"])
    p.add_argument("--max_lora_rank", type=int, default=32)
    # float, matching worker_main --lora-alpha: lora_scale = alpha/rank is
    # float math, and an int-typed driver could not express an alpha the
    # workers accept (graftcheck GC402 caught the divergence)
    p.add_argument("--lora_alpha", type=float, default=16.0)
    p.add_argument("--lora_dropout", type=float, default=0.0)
    p.add_argument("--topk", type=int, default=16)
    p.add_argument("--actor_gpu_usage", type=float, default=0.91)
    p.add_argument("--learner_gpu_usage", type=float, default=0.35)
    # --- TPU-native additions
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel chips per role")
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel chips (ring/ulysses attention)")
    p.add_argument("--fsdp", type=int, default=1, help="learner parameter sharding")
    p.add_argument("--base_quant", type=str, default="none", choices=["none", "int8", "int4"])
    p.add_argument("--quant_group_size", type=int, default=None,
                   help="groupwise-scale width along the input dim for "
                        "--base_quant (must divide the projection input "
                        "dims); unset = per-format default (int8: "
                        "per-column, int4: 64 — bnb's blockwise knob)")
    p.add_argument("--attn_impl", type=str, default="reference",
                   choices=["reference", "flash", "splash", "ring", "ulysses"])
    p.add_argument("--engine_impl", type=str, default="dense",
                   choices=["dense", "paged"],
                   help="rollout engine: dense fixed-shape cache, or paged "
                        "ragged KV (Pallas paged-attention decode)")
    p.add_argument("--max_concurrent_sequences", type=int, default=0,
                   help="cap on concurrent candidate rows (vLLM max_num_seqs"
                        "); rounds beyond the cap run as sequential waves. "
                        "0 = unlimited")
    p.add_argument("--kv_cache_quant", type=str, default=None,
                   choices=["none", "int8"],
                   help="KV cache quantization (int8 halves cache memory "
                        "+ decode bandwidth via the compact-scales "
                        "kernels). Unset = this host's autotune plan DB "
                        "decides (ExecutionPlan.kv_format; empty DB = "
                        "none). An explicit value, including none, always "
                        "wins over any stored plan")
    p.add_argument("--decode_scan_chunk", type=int, default=None,
                   help="decode steps fused per dispatch via lax.scan "
                        "(all engines: dense, paged wave/refill, sharded, "
                        "and speculative) — "
                        "amortizes per-dispatch host overhead "
                        "(tools/dispatch_probe.py measures it); "
                        "auto-falls back if the compiler "
                        "double-buffers the KV cache. 0 = off; unset = "
                        "let the autotune plan DB decide (static "
                        "default: off). An explicit value, including 0, "
                        "always wins over any stored plan")
    p.add_argument("--full_finetune", action="store_true",
                   help="bf16 full-rank fine-tuning (no LoRA): the whole "
                        "param tree trains; requires --base_quant none")
    p.add_argument("--logprob_chunk", type=int, default=128,
                   help="learner fused-CE chunk: lm_head+logsumexp per this "
                        "many answer positions (live logits [B,chunk,V] "
                        "instead of [B,T,V]); 0 = dense")
    p.add_argument("--continuous_batching", action="store_true",
                   help="paged-engine slot refill: keep max_concurrent_"
                        "sequences rows decoding, admit a pending candidate "
                        "whenever a slot's occupant hits EOS (vLLM continuous "
                        "batching) instead of draining whole waves")
    p.add_argument("--prefix_sharing", action="store_true",
                   help="copy-on-write prompt-prefix sharing: a group's N "
                        "rollouts alias ONE refcounted prompt page chain "
                        "(vLLM prefix caching) instead of holding private "
                        "copies — prompt KV is resident ~once per group and "
                        "finished groups' pages recycle into decode "
                        "capacity. Requires --continuous_batching; greedy "
                        "outputs are bit-identical to the unshared engine")
    p.add_argument("--continuous_admission", action="store_true",
                   help="serving-grade admission: replace the fixed-episode-"
                        "batch prefill with a group request queue — each "
                        "prompt prefills lazily into pool-allocated chain "
                        "pages as freed slots and page budget allow, so "
                        "short completions backfill immediately. Implies "
                        "--prefix_sharing; requires --continuous_batching")
    p.add_argument("--prefix_cache", choices=["on", "off"], default=None,
                   help="tiered KV cache tier 1: cross-request radix prefix "
                        "index over the continuous-admission pool — warm "
                        "prompts (multi-turn history, shared preambles) "
                        "alias cached pages and prefill ONLY their "
                        "un-cached suffix, bit-identically to cache-off. "
                        "Requires --continuous_admission and an "
                        "unquantized KV pool. Passing the flag — INCLUDING "
                        "'off' — pins the choice past any stored autotune "
                        "plan; omitting it leaves the plan DB in charge")
    p.add_argument("--kv_spill", action="store_true",
                   help="tiered KV cache tier 2: preempted chains spill "
                        "written KV pages to a host-RAM store and restore "
                        "bit-exactly on resume instead of recomputing. "
                        "Requires --prefix_cache on; incompatible with "
                        "--spec_draft")
    p.add_argument("--kv_spill_host_mb", type=int, default=0,
                   help="host page-store cap in MiB for --kv_spill (0 = "
                        "unbounded); payloads LRU-drop past the cap and "
                        "fall back to the recompute resume")
    p.add_argument("--spec_draft", type=int, default=None,
                   help="speculative decoding: draft this many tokens per "
                        "step and verify in one forward; distribution-"
                        "identical to plain decoding. Requires "
                        "--continuous_batching. Passing the flag — "
                        "INCLUDING 0 (off) — pins the choice past any "
                        "stored autotune plan; omitting it leaves the plan "
                        "DB in charge (default off)")
    p.add_argument("--spec_ngram", type=int, default=None,
                   help="lookup n-gram size for --spec_draft (passing the "
                        "flag pins past any stored autotune plan; unset = "
                        "engine default / plan DB)")
    p.add_argument("--spec_drafter", choices=["ngram", "self"],
                   default=None,
                   help="draft source for --spec_draft: 'ngram' (prompt "
                        "lookup) or 'self' (the policy's own previous LoRA "
                        "version off the weight-update swap log — "
                        "near-on-policy, high acceptance; needs a LoRA run). "
                        "Passing the flag — even 'ngram' — pins the choice "
                        "past any stored autotune plan; omitting it leaves "
                        "the plan DB in charge")
    p.add_argument("--spec_verify", choices=["fused", "unrolled"],
                   default=None,
                   help="verify-attention kernel: 'fused' = the whole draft "
                        "block in ONE blocked Pallas sweep (probe-gated, "
                        "exact unrolled fallback); 'unrolled' = d+1 "
                        "per-position dispatches (A/B control). Passing the "
                        "flag pins past any stored autotune plan")
    p.add_argument("--spec_adapt", action="store_true",
                   help="acceptance-rate-driven draft-length adaptation: "
                        "shrink the effective draft length when the accept-"
                        "rate EMA says drafts are wasted, regrow on recovery")
    p.add_argument("--clip_ratio", type=float, default=0.0,
                   help="PPO-clip epsilon over engine-captured behavior "
                        "logprobs (0 = reference-parity no-clip objective)")
    p.add_argument("--kl_coeff", type=float, default=0.0,
                   help="KL(policy || frozen base) penalty coefficient (the "
                        "GRPO paper's regularizer; LoRA mode only; 0 = "
                        "reference parity)")
    p.add_argument("--rollout_mode", type=str, default="sync",
                   choices=["sync", "pipelined", "async"],
                   help="rollout/learner coupling: 'sync' = reference-parity "
                        "serialized loop; 'pipelined' = one-step overlap "
                        "(batch t+1 generates while batch t updates); "
                        "'async' = fully decoupled RolloutService + bounded "
                        "trajectory buffer with --max_staleness admission "
                        "and truncated-IS correction (requires --clip_ratio "
                        "> 0)")
    p.add_argument("--max_staleness", type=int, default=2,
                   help="async staleness bound K: trajectories whose stalest "
                        "token lags the learner by more than K optimizer "
                        "steps are dropped (or down-weighted, "
                        "--staleness_policy); sync/pipelined derive their "
                        "allowed lag (0/1) from the mode")
    p.add_argument("--staleness_policy", type=str, default="drop",
                   choices=["drop", "downweight"],
                   help="what happens to a pulled trajectory beyond "
                        "--max_staleness: discard it (counted in "
                        "rollout/dropped_stale) or train it down-weighted "
                        "by staleness_downweight^(lag-K)")
    p.add_argument("--rollout_buffer_groups", type=int, default=0,
                   help="trajectory-buffer capacity in task groups for "
                        "--rollout_mode async (0 = auto: 4x batch_size)")
    p.add_argument("--env", type=str, default="math",
                   choices=["code", "math", "verifier"],
                   help="rollout environment: 'math' = the legacy "
                        "single-turn scorer (byte-identical pre-env path); "
                        "'code' = multi-turn sandboxed <tool> execution "
                        "with outputs fed back; 'verifier' = multi-turn "
                        "verifier feedback with per-turn improvement "
                        "rewards. Multi-turn envs need --continuous_batching "
                        "+ --continuous_admission (turn continuations "
                        "resume on resident KV chains, no re-prefill)")
    p.add_argument("--max_turns", type=int, default=1,
                   help="conversation-turn budget per episode for "
                        "multi-turn --env values (env='math' is single-turn "
                        "by construction; >1 there is rejected)")
    p.add_argument("--format_reward", type=str, default="soft",
                   choices=["soft", "strict"],
                   help="format-reward gate: 'soft' = the reference's "
                        "anchored single-line pattern (parity default); "
                        "'strict' = the newline-delimited variant")
    p.add_argument("--async_rollout", action="store_true",
                   help="DEPRECATED alias for --rollout_mode pipelined "
                        "(one-step-off-policy LlamaRL/PipelineRL-style "
                        "overlap)")
    p.add_argument("--workers_capture_logprobs", action="store_true",
                   help="declare that every --rollout_workers process was "
                        "started with worker_main --capture-logprobs, "
                        "enabling --clip_ratio/--rollout_mode async over "
                        "remote workers")
    p.add_argument("--inflight_weight_updates", action="store_true",
                   help="push each optimizer step's adapter into the "
                        "generation round still in flight (PipelineRL-style; "
                        "requires --async_rollout and --clip_ratio > 0 — the "
                        "clip objective consumes the captured per-token "
                        "behavior logprobs)")
    p.add_argument("--rollout_workers", type=str, default="",
                   help="comma-separated control-plane workers "
                        "(host:port,...) to dispatch generation to; start "
                        "them with python -m "
                        "distrl_llm_tpu.distributed.worker_main --serve-model")
    p.add_argument("--weight_bus", type=str, default="broadcast",
                   choices=["broadcast", "dispatch"],
                   help="learner→worker weight transport for "
                        "--rollout_workers: 'broadcast' ships each "
                        "optimizer step's adapter once per version over an "
                        "out-of-band delta-encoded push (dispatches carry "
                        "only a version reference; enables "
                        "--inflight_weight_updates over workers); "
                        "'dispatch' is the legacy full-adapter-per-payload "
                        "fallback")
    p.add_argument("--worker_rejoin", type=str, default="on",
                   choices=["on", "off"],
                   help="background reconnect loop for --rollout_workers: "
                        "unhealthy workers are re-dialed with seeded "
                        "backoff and re-admitted after a PING (capacity "
                        "recovers instead of shrinking monotonically); "
                        "'off' restores the pre-resilience behavior")
    p.add_argument("--rpc_retries", type=int, default=2,
                   help="transient worker-error retries per RPC (MSG_ERROR "
                        "classified by exception type) before the shard is "
                        "requeued to a different worker")
    p.add_argument("--rpc_backoff_s", type=float, default=0.25,
                   help="base delay of the seeded exponential backoff used "
                        "by RPC retries, worker reconnects, and the async "
                        "producer's supervised restarts")
    p.add_argument("--poison_shard_k", type=int, default=3,
                   help="poison-shard quarantine threshold: a shard that "
                        "fails on this many DISTINCT workers raises "
                        "ShardFailedError naming the shard instead of "
                        "grinding every worker to unhealthy")
    p.add_argument("--degrade_on_poison", action="store_true",
                   help="on a quarantined shard, return the surviving "
                        "groups (the trainer drops the lost prompts with "
                        "conservation accounting, cp/degraded_groups) "
                        "instead of failing the round")
    p.add_argument("--producer_restarts", type=int, default=2,
                   help="supervised restart budget for the async "
                        "RolloutService producer: failed produce rounds "
                        "retry in place this many times before the failure "
                        "surfaces")
    p.add_argument("--dtype", type=str, default="bfloat16")
    p.add_argument("--seed", type=int, default=3407)
    p.add_argument("--no_print_samples", dest="print_samples",
                   action="store_false",
                   help="disable the per-update sample dump (reference "
                        "prints one sample per update)")
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--metrics_backend", type=str, default="auto",
                   choices=["auto", "wandb", "jsonl", "null"])
    p.add_argument("--export_hf_snapshots", action="store_true",
                   help="write HF-format merged-model snapshots to "
                        "run_dir/model_{step} (reference save_pretrained "
                        "artifacts)")
    p.add_argument("--write_adapter_file", action="store_true",
                   help="export the reference's per-step adapter artifact")
    p.add_argument("--profile_dir", type=str, default=None)
    p.add_argument("--trace-dir", "--trace_dir", dest="trace_dir",
                   type=str, default=None,
                   help="span-trace capture (telemetry.py): write a Chrome-"
                        "trace/Perfetto JSON of driver/engine/worker spans "
                        "to this directory (trace.json); inspect with "
                        "tools/trace_report.py or ui.perfetto.dev")
    p.add_argument("--trace-steps", "--trace_steps", dest="trace_steps",
                   type=int, default=0,
                   help="trace only the first N train steps, writing the "
                        "file when the window closes (0 = whole run, "
                        "written at shutdown)")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve the live metrics endpoint (Prometheus at "
                        "/metrics, JSON at /metrics.json) on this port; "
                        "with --rollout_workers it also publishes fleet/* "
                        "series aggregated from worker snapshots. 0 = "
                        "auto-assign; omit = off")
    p.add_argument("--sentinel", action="store_true",
                   help="anomaly sentinel: deterministic per-step triggers "
                        "(NaN/Inf loss, reward collapse, staleness blowup, "
                        "tok/s regression vs EMA, HBM watermark breach) "
                        "dump the flight-recorder ring as an incident "
                        "bundle; requires --flight_recorder_dir")
    p.add_argument("--flight_recorder_dir", type=str, default=None,
                   help="keep a bounded ring of recent step records and "
                        "write sentinel incident bundles "
                        "(incident_step<N>_<trigger>/) here")
    p.add_argument("--obs_ring_size", type=int, default=256,
                   help="flight-recorder ring capacity in step records")
    p.add_argument("--lineage", action="store_true",
                   help="trajectory lineage ledger (ISSUE 10): follow every "
                        "sampled group from prompt through the buffer into "
                        "the optimizer step that consumed it and out as a "
                        "broadcast weight version, publishing "
                        "lineage/sample_to_learn_ms, lineage/learn_to_act_ms "
                        "and lineage/policy_lag_ms histograms; requires "
                        "--rollout_mode async")
    p.add_argument("--lineage_dir", type=str, default=None,
                   help="write closed lineage records to "
                        "<dir>/lineage.jsonl as they close (implies "
                        "--lineage); inspect with tools/lineage_report.py")
    p.add_argument("--lineage_ring", type=int, default=1024,
                   help="bounded ring of OPEN lineage records; overflow is "
                        "counted in lineage/ring_evictions, never silent")
    p.add_argument("--serving_obs", action="store_true",
                   help="request-level serving ledger (ISSUE 13): per-group "
                        "lifecycle events from the continuous-batching "
                        "engine (enqueue/admit/first token/finish) yielding "
                        "serving/ttft_ms, serving/tpot_ms, "
                        "serving/queue_wait_ms and serving/e2e_ms "
                        "histograms plus attributed admission stalls; "
                        "requires --engine_impl paged + "
                        "--continuous_batching (workers arm their own via "
                        "worker_main --serving-obs)")
    p.add_argument("--serving_dir", type=str, default=None,
                   help="stream closed serving records to "
                        "<dir>/serving.jsonl (implies --serving_obs); "
                        "inspect with tools/serving_report.py")
    p.add_argument("--serving_ring", type=int, default=1024,
                   help="bounded ring of OPEN serving records; overflow is "
                        "counted in serving/ring_evictions, never silent")
    p.add_argument("--slo_ttft_ms", type=float, default=None,
                   help="time-to-first-token SLO: arms the sentinel's "
                        "ttft_blowup trigger (a step whose worst observed "
                        "TTFT exceeds this dumps a flight-recorder "
                        "bundle); requires --sentinel")
    p.add_argument("--slo_queue_wait_ms", type=float, default=None,
                   help="queue-wait SLO: arms the sentinel's "
                        "queue_wait_blowup trigger; requires --sentinel")
    p.add_argument("--gateway_port", type=int, default=None,
                   help="multi-tenant serving gateway (ISSUE 19): serve "
                        "POST /v1/generate on 127.0.0.1:<port> (0 = auto-"
                        "assign; the bound port prints as 'GATEWAY <n>'), "
                        "streaming tokens per request with tenant + "
                        "priority class (interactive > batch > scavenger) "
                        "from X-Tenant / X-Priority headers; requires "
                        "engine_impl=paged + --continuous_batching + "
                        "--continuous_admission")
    p.add_argument("--gateway_classes", type=str, default=None,
                   help="comma-separated subset of priority classes the "
                        "gateway serves (default: all three); requests "
                        "naming an unserved class get HTTP 400")
    p.add_argument("--tenant_quota", type=str, default=None,
                   help="per-tenant reserved-token quotas "
                        "'tenant=tokens,...' (pseudo-tenant 'default' caps "
                        "unnamed tenants); admission declines on quota are "
                        "the 'quota' stall reason; requires --gateway_port")
    p.add_argument("--learn_obs", action="store_true",
                   help="training-dynamics observability (ISSUE 16): fuse "
                        "the device-computed dynamics bundle (masked policy "
                        "entropy, behavior-policy KL, pre-binned IS-ratio "
                        "histogram, clip/cap-saturation fractions, "
                        "advantage moments, per-layer-group LoRA grad "
                        "norms) into the jitted train step — it rides the "
                        "one host transfer the loss already pays — and "
                        "publish it as learn/* registry series")
    p.add_argument("--learn_dir", type=str, default=None,
                   help="stream one learning-dynamics record per optimizer "
                        "step to <dir>/learn.jsonl (implies --learn_obs); "
                        "inspect with tools/learn_report.py")
    p.add_argument("--learn_drift_window", type=int, default=32,
                   help="reward-drift reference window in steps: "
                        "learn/reward_drift is the z-score of the step's "
                        "reward mean against the trailing window of older "
                        "means")
    p.add_argument("--learn_entropy_floor", type=float, default=None,
                   help="arms the sentinel's entropy_collapse trigger: "
                        "masked answer-token entropy below this floor "
                        "dumps a flight-recorder bundle; requires "
                        "--sentinel (implies --learn_obs)")
    p.add_argument("--learn_kl_limit", type=float, default=None,
                   help="arms the sentinel's kl_blowup trigger: behavior-"
                        "policy KL above this limit dumps a bundle, and "
                        "escalates to the staleness governor when "
                        "--control_staleness is armed; requires --sentinel "
                        "(implies --learn_obs)")
    p.add_argument("--learn_ratio_sat_frac", type=float, default=None,
                   help="arms the sentinel's ratio_saturation trigger: "
                        "fraction of answer tokens whose IS ratio the "
                        "AIPO cap (or PPO clip) truncated above this "
                        "threshold dumps a bundle; requires --sentinel "
                        "(implies --learn_obs)")
    p.add_argument("--learn_grad_spike", type=float, default=None,
                   help="arms the sentinel's grad_spike trigger: whole-"
                        "adapter grad norm above this multiple (> 1) of "
                        "its running EMA dumps a bundle; requires "
                        "--sentinel (implies --learn_obs)")
    p.add_argument("--control", action="store_true",
                   help="self-healing runtime (ISSUE 14): arm every "
                        "closed-loop controller this run's shape supports "
                        "(HBM admission governor, SLO load-shedder, "
                        "staleness governor, worker-health actor, nan-loss "
                        "rollback) — bounded, hysteretic, cooldown-guarded "
                        "actions on the observability plane, all counted "
                        "under control/* and capped by --control_budget")
    p.add_argument("--control_hbm", action="store_true",
                   help="HBM governor only: shrink the continuous-"
                        "admission chain cap under watermark pressure / "
                        "hbm_breach, regrow after a sustained-headroom "
                        "dwell (requires a local paged engine with "
                        "--continuous_admission)")
    p.add_argument("--control_shed", action="store_true",
                   help="SLO load-shedder only: throttle group admission "
                        "(decline reason 'shed') while TTFT/queue-wait "
                        "breach the --slo_* limits (requires "
                        "--continuous_admission and an SLO)")
    p.add_argument("--control_staleness", action="store_true",
                   help="staleness governor only: adapt the EFFECTIVE "
                        "max_staleness and buffer watermark from the live "
                        "lineage/policy_lag_ms distribution (requires "
                        "--lineage; async mode)")
    p.add_argument("--control_worker_health", action="store_true",
                   help="worker-health actor only: quarantine a worker "
                        "whose tok/s regresses against its own EMA and "
                        "let the rejoin loop probe + re-admit it "
                        "(requires --rollout_workers with rejoin on)")
    p.add_argument("--control_nan_rollback", action="store_true",
                   help="nan-loss rollback only: restore the last-good "
                        "(adapter, opt state, version) snapshot and skip "
                        "the poisoned step instead of training on NaNs")
    p.add_argument("--control_budget", type=int, default=64,
                   help="global actuation budget per run; once spent every "
                        "controller knob freezes at its current value")
    p.add_argument("--control_cooldown_steps", type=int, default=2,
                   help="minimum steps between two actions of one governor")
    p.add_argument("--control_dwell_steps", type=int, default=3,
                   help="consecutive healthy observations before a governor "
                        "regrows a shrunk knob")
    p.add_argument("--control_lag_ms", type=float, default=5000.0,
                   help="staleness-governor setpoint: policy-lag p90 above "
                        "this shrinks the effective staleness bound")
    p.add_argument("--control_autoscale", action="store_true",
                   help="autoscaling governor (ISSUE 20): steer the "
                        "supervised worker pool's target size over "
                        "[--fleet_min, --fleet_max] from serving queue "
                        "wait and learner idle (scale-up admits a cold "
                        "worker through the weight-bus resync; scale-down "
                        "drains the least-productive one). Requires "
                        "--rollout_workers with rejoin on and the fleet "
                        "bounds; never armed by the --control master")
    p.add_argument("--fleet_min", type=int, default=0,
                   help="lower bound on the autoscaler's target worker "
                        "count (0 = no elastic fleet)")
    p.add_argument("--fleet_max", type=int, default=0,
                   help="upper bound on the autoscaler's target worker "
                        "count (0 = no elastic fleet)")
    p.add_argument("--prompt_buckets", type=str, default="",
                   help="comma-separated prompt length buckets for the "
                        "rollout engine, e.g. 128,256 (max_prompt_tokens is "
                        "always included)")
    p.add_argument("--learner_len_buckets", type=str, default="",
                   help="comma-separated ANSWER length buckets for the "
                        "learner update step, e.g. 256,512: each update "
                        "runs at the smallest bucket holding the batch's "
                        "longest real answer instead of padding every row "
                        "to max_new_tokens (exact semantics; one compiled "
                        "step per bucket)")
    p.add_argument("--learner_prompt_buckets", type=str, default="",
                   help="comma-separated PROMPT length buckets for the "
                        "learner update step (left-padded side; exact up "
                        "to RoPE float round-off). Separate from "
                        "--prompt_buckets, which only shapes the rollout "
                        "engine")
    p.add_argument("--autotune", type=str, default="on",
                   choices=["on", "off"],
                   help="execution-plan autotuner (distrl_llm_tpu/autotune)"
                        ": engines resolve dispatch choices (scan chunk, "
                        "cache-read formulation, top-p impl, prompt "
                        "buckets) from the persistent plan DB of on-device "
                        "measurements (tools/autotune.py populates it). "
                        "Explicitly-set flags always win; with no DB entry "
                        "behavior is identical to the static defaults. "
                        "'off' pins the static defaults without reading "
                        "any DB")
    p.add_argument("--plan-db", "--plan_db", dest="plan_db",
                   type=str, default=None,
                   help="plan-DB path for --autotune (default: "
                        "$DISTRL_PLAN_DB or "
                        "~/.cache/distrl_llm_tpu/plan_db.json)")
    p.add_argument("--top_p_exact", action="store_true",
                   help="exact sort-based nucleus filter (reference vLLM "
                        "semantics) instead of the fast bisection filter")
    p.add_argument("--generation_timeout_s", type=float, default=0.0,
                   help="hang detector on generation rounds (0 = off; "
                        "reference parity value: 240)")
    p.add_argument("--checkpoint_path", type=str, default=None,
                   help="local HF checkpoint dir (defaults to --model as a path)")
    p.add_argument("--smoke", action="store_true",
                   help="end-to-end smoke: tiny random-init model, inline "
                        "dataset, real engine+learner, 1 episode (SURVEY §4)")
    return p


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    mesh = MeshConfig(
        number_of_actors=args.number_of_actors,
        number_of_learners=args.number_of_learners,
        tp=args.tp, sp=args.sp, fsdp=args.fsdp,
    )
    fields = {
        k: v for k, v in vars(args).items()
        if k in TrainConfig.__dataclass_fields__
    }
    from distrl_llm_tpu.config import parse_buckets

    fields["prompt_buckets"] = parse_buckets(args.prompt_buckets)
    fields["learner_len_buckets"] = parse_buckets(
        args.learner_len_buckets, field="learner_len_buckets"
    )
    fields["learner_prompt_buckets"] = parse_buckets(
        args.learner_prompt_buckets, field="learner_prompt_buckets"
    )
    fields["rollout_workers"] = tuple(
        w.strip() for w in str(args.rollout_workers or "").split(",") if w.strip()
    )
    fields["autotune"] = args.autotune == "on"
    fields["worker_rejoin"] = args.worker_rejoin == "on"
    # tri-state pin (the spec_draft convention): omitted = None = plan-DB-
    # resolvable; an explicit spelling — including "off" — pins the engine
    fields["prefix_cache"] = (
        None if args.prefix_cache is None else args.prefix_cache == "on"
    )
    return TrainConfig(mesh=mesh, **fields)


@dataclasses.dataclass(frozen=True)
class SmokeSizes:
    """What the offline assembly (``run_smoke``) is sized by. The defaults are
    the CPU smoke's; ``chip_smoke.py`` passes the chip's."""

    prompts: int = 4  # prompts per step (batch_size)
    candidates: int = 4  # samples per prompt
    steps: int = 2  # train steps: the one episode holds steps·prompts problems
    max_prompt_tokens: int = 64
    max_new_tokens: int = 32
    micro_batch: int = 4  # learner micro-batch rows (train_batch_size)
    lora_rank: int = 4
    dtype: str = "float32"  # base weights; the chip runs "bfloat16"
    # paged engine only
    page_size: int = 8
    max_concurrent_rows: int = 4
    decode_chunk: int = 4


def smoke_problems(n: int, seed: int, max_chars: int) -> dict[str, list[str]]:
    """``n`` seeded arithmetic problems of varied length (so prompts differ in
    length under a byte tokenizer), each at most ``max_chars`` characters."""
    rng = np.random.default_rng(seed)
    problems, solutions = [], []
    for _ in range(n):
        terms = rng.integers(1, 1000, size=int(rng.integers(2, 12)))
        text = "What is " + " + ".join(str(t) for t in terms) + "?"
        problems.append(text[:max_chars])
        solutions.append(str(int(terms.sum())))
    return {"problem": problems, "solution": solutions}


def dense_smoke_reward(completions, solutions) -> np.ndarray:
    """(N, 2) reward contract with a dense, deterministic accuracy column: a
    hash of the completion's text. A smoke's policy is random weights, whose
    completions the math reward scores 0 throughout — every microbatch is
    then skipped and no update ever happens. This one gives each group
    unequal rewards, so the learner really steps."""
    import zlib

    acc = [(zlib.crc32(c.encode("utf-8")) % 8) / 8.0 for c in completions]
    return np.column_stack((np.zeros(len(acc)), np.asarray(acc)))


def tree_checksum(tree) -> float:
    """Sum of absolute values over a pytree's leaves, on the host."""
    import jax

    return float(sum(
        np.abs(np.asarray(leaf, np.float64)).sum()
        for leaf in jax.tree_util.tree_leaves(tree)
    ))


def run_smoke(
    config: TrainConfig,
    model_cfg=None,
    sizes: SmokeSizes = SmokeSizes(),
    *,
    seed: int = 0,
    reward_fn=reward_function,
    devices: list | None = None,
) -> dict:
    """Reference-recipe-1-shaped integration smoke without downloads: a
    random-init model (``model_cfg``, default TINY) through the REAL engine
    + learner + ``Trainer.train()`` on whatever devices exist (CPU mesh, one
    TPU chip, or a role-split of several). ``--smoke`` on the CPU and
    ``chip_smoke.py`` on the chip are this one function at different
    ``sizes``. Asserts every loss is finite and returns what happened: one
    record per rollout round (the policy version it sampled under, the
    checksum of the adapter it was given), one per train step, the final
    adapter checksum, and the trainer itself for placement checks."""
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.data import process_dataset
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.parallel.mesh import build_role_meshes
    from distrl_llm_tpu.parallel.partition import param_specs, shard_tree
    from distrl_llm_tpu.tokenizer import CharTokenizer

    config = dataclasses.replace(
        config,
        model=config.model if model_cfg is not None else "tiny",
        episodes=1, batch_size=sizes.prompts,
        num_candidates=sizes.candidates, topk=sizes.candidates,
        eval_n=sizes.candidates, train_batch_size=sizes.micro_batch,
        max_prompt_tokens=sizes.max_prompt_tokens,
        max_new_tokens=sizes.max_new_tokens,
        eval_every=0, save_every=0, metrics_backend="null",
        max_lora_rank=sizes.lora_rank, lora_alpha=2 * sizes.lora_rank,
        lr=1e-3,
    )
    model_cfg = model_cfg if model_cfg is not None else TINY
    tokenizer = CharTokenizer(model_cfg.vocab_size)
    # the chat template costs ~75 characters of a byte-tokenized prompt
    train = process_dataset(tokenizer, smoke_problems(
        sizes.steps * sizes.prompts, seed,
        max_chars=max(8, sizes.max_prompt_tokens - 80),
    ))
    test = {k: v[:sizes.prompts] for k, v in train.items()}
    base = init_params(
        jax.random.PRNGKey(seed), model_cfg, dtype=jnp.dtype(sizes.dtype)
    )
    if config.base_quant != "none":
        from distrl_llm_tpu.ops.quant import quant_bits_for, quantize_params

        base = quantize_params(
            base, bits=quant_bits_for(config.base_quant),
            group_size=config.quant_group_size or 16,
        )
    # each role holds the frozen base on its own submesh, as
    # Trainer.from_pretrained places a loaded checkpoint; timeshared roles
    # alias one copy
    meshes = build_role_meshes(config.mesh, devices)
    specs = param_specs(base)
    base_rollout = shard_tree(base, meshes.rollout, specs)
    base_learner = (
        base_rollout if meshes.timeshared
        else shard_tree(base, meshes.learner, specs)
    )
    engine_common = dict(
        max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        pad_token_id=tokenizer.pad_token_id,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        # behavior-logprob capture whenever the objective needs it, so
        # --smoke composes with --clip_ratio / --rollout_mode async
        capture_logprobs=config.clip_ratio > 0.0,
        # honor --autotune/--plan-db in the smoke path too: "--autotune
        # off skips the DB read entirely" must hold for every engine the
        # CLI builds
        autotune=config.autotune,
        plan_db=config.plan_db,
    )
    if config.engine_impl == "paged":
        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

        engine = PagedGenerationEngine(
            model_cfg,
            # multi-turn smoke: half-vocab EOS so the random policy
            # actually ends turns inside the window and the env gets to
            # inject observations; math keeps the real EOS contract
            eos_token_ids=(
                [tokenizer.eos_token_id] if config.env == "math"
                else list(range(2, model_cfg.vocab_size, 2))
            ),
            page_size=sizes.page_size,
            max_concurrent_rows=sizes.max_concurrent_rows,
            scheduler="refill" if config.continuous_batching else "static",
            continuous_admission=config.continuous_admission,
            decode_chunk=sizes.decode_chunk,
            kv_quant=config.kv_cache_quant,
            **engine_common,
        )
    else:
        engine = GenerationEngine(
            model_cfg, eos_token_ids=[tokenizer.eos_token_id],
            **engine_common,
        )
    sink = MemorySink()
    trainer = Trainer(
        train, test, reward_fn, config,
        tokenizer=tokenizer, engine=engine, base_params=base_rollout,
        base_params_learner=base_learner, model_cfg=model_cfg,
        meshes=meshes, sink=sink,
    )
    # observe each rollout round from outside: what version it sampled
    # under and which adapter it was handed (the first round is the initial
    # evaluation, the rest one per train step)
    rounds: list[dict] = []
    generate_round = trainer._generate_round

    def observed_round(batch, sampling):
        entry = {
            "started": time.perf_counter(),
            "policy_version": trainer._rollout_weight_version,
            "adapter_checksum": tree_checksum(trainer._lora_rollout),
        }
        out = generate_round(batch, sampling)
        entry["seconds"] = time.perf_counter() - entry["started"]
        entry["gen_tokens"] = int(sum(
            sum(lens) for c in out for lens in c["token_lengths"]
        ))
        rounds.append(entry)
        return out

    trainer._generate_round = observed_round
    trainer.train()
    steps = [m for _, m in sink.records if "loss" in m]
    assert steps, "no train steps ran"
    assert all(np.isfinite(m["loss"]) for m in steps), "non-finite loss"
    return {
        "steps": steps,
        "rounds": rounds,
        "final_adapter_checksum": tree_checksum(trainer.lora),
        "trainer": trainer,
    }


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    config = config_from_args(args)

    enable_compile_cache()

    if args.smoke:
        import jax

        report = run_smoke(
            # one actor group and one learner group, however many roles the
            # CLI's defaults name; one learner row per hybrid round
            dataclasses.replace(
                config, learner_chunk_size=1,
                number_of_actors=1, number_of_learners=1,
                mesh=dataclasses.replace(
                    config.mesh, number_of_actors=1, number_of_learners=1
                ),
            ),
            sizes=SmokeSizes(
                # multi-turn envs need the answer window to seat a policy
                # turn PLUS the injected observation (CharTokenizer: 1 char
                # ≈ 1 token) or every turn resume is declined for lack of room
                max_new_tokens=32 if config.env == "math" else 96,
            ),
        )
        print(f"SMOKE OK — {len(report['steps'])} train steps on "
              f"{jax.device_count()} {jax.devices()[0].platform} device(s)")
        print(report["steps"][-1])
        return

    tokenizer = load_tokenizer(args.checkpoint_path or config.model)
    train_ds, test_ds = prepare_dataset(
        config.dataset, tokenizer, test_size=0.1, seed=config.seed
    )
    trainer = Trainer.from_pretrained(
        train_ds, test_ds, reward_function, config,
        checkpoint_path=args.checkpoint_path, tokenizer=tokenizer,
    )
    trainer.train()


if __name__ == "__main__":
    main()
