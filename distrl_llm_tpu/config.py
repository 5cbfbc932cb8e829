"""Typed configuration for the TPU-native distributed RL framework.

Replaces the reference's flat argparse→dict config (train_distributed.py:10–35,
:54–81 in BY571/DistRL-LLM). Every reference flag name and default is preserved —
the CLI contract is part of parity — plus TPU-specific knobs (mesh shape, chip
roles, dtype/quantization policy) the reference expressed as GPU-process counts.

One deliberate default divergence: ``model`` defaults to the plain
"Qwen/Qwen2.5-7B-Instruct" checkpoint rather than the reference's GPU-only
"unsloth/Qwen2.5-7B-Instruct-bnb-4bit"; NF4-style base quantization is the
orthogonal ``base_quant`` knob here.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass
class SamplingConfig:
    """Sampling parameters for a generation round.

    Mirrors the reference's vllm.SamplingParams usage: train-time params built
    from the GenerationConfig (distributed_actor.py:43–48), eval-time params
    hardcoded (distributed_trainer.py:53–58).
    """

    max_tokens: int = 1200
    temperature: float = 1.2
    top_p: float = 0.95
    n: int = 16  # candidates per prompt
    # top-p filter implementation: False = sort-free bisection (fast path;
    # kept set is a superset of the exact nucleus by at most the boundary
    # tie mass), True = exact rank-based sort filter matching the reference's
    # vLLM semantics — for eval/reproducibility runs.
    top_p_exact: bool = False
    # explicit impl override (a key of ops.sampling.TOP_P_IMPLS, e.g.
    # "bisect_mw"); None derives from top_p_exact. Engines resolve via
    # resolved_top_p_impl().
    top_p_impl: str | None = None

    def resolved_top_p_impl(self, plan_default: str | None = None) -> str:
        """Effective top-p implementation. Priority: an explicit
        ``top_p_impl`` pin, then ``top_p_exact`` (reference semantics were
        asked for by name), then the engine's autotuned plan default
        (``plan_default`` — ExecutionPlan.top_p_impl), then "bisect"."""
        if self.top_p_impl:  # "" and None both mean "derive"
            from distrl_llm_tpu.ops.sampling import TOP_P_IMPLS

            if self.top_p_impl not in TOP_P_IMPLS:
                raise ValueError(
                    f"top_p_impl must be one of {sorted(TOP_P_IMPLS)}, "
                    f"got {self.top_p_impl!r}"
                )
            return self.top_p_impl
        if self.top_p_exact:
            return "exact"
        if plan_default:
            # already validated: plan_default only ever carries
            # ExecutionPlan.top_p_impl, checked against TOP_P_IMPLS at plan
            # construction (autotune/plan.py)
            return plan_default
        return "bisect"

    def replace(self, **kw) -> "SamplingConfig":
        return dataclasses.replace(self, **kw)


def parse_buckets(
    spec: str | None, field: str = "prompt_buckets"
) -> tuple[int, ...]:
    """Parse a comma-separated bucket list ("128,256") into a tuple; shared
    by every CLI entry so the format cannot drift. ``field`` names the
    flag in the error message."""
    if not spec:
        return ()
    try:
        return tuple(int(x) for x in str(spec).split(",") if x.strip())
    except ValueError as e:
        raise ValueError(
            f"{field} must be comma-separated integers, got {spec!r}"
        ) from e


@dataclass
class MeshConfig:
    """How chips are carved into roles and parallelism axes.

    The reference maps roles to whole GPUs via Ray placement groups
    (distributed_actor.py:517–585). Here roles are partitions of one global
    ``jax.sharding.Mesh``: the first ``number_of_actors`` data-parallel groups
    are rollout chips, the next ``number_of_learners`` groups are learner chips.
    Within a group, ``tp`` shards attention heads / MLP and ``sp`` shards
    sequence for ring attention.
    """

    number_of_actors: int = 2
    number_of_learners: int = 1
    tp: int = 1  # tensor-parallel size within each role group
    sp: int = 1  # sequence-parallel (ring attention) size
    fsdp: int = 1  # parameter sharding of the learner state
    # When there are fewer physical devices than roles (e.g. 1 chip), roles
    # time-share the whole mesh instead of partitioning it; this matches the
    # reference's hybrid learner-generation behavior in spirit.
    allow_timeshare: bool = True

    @property
    def num_roles(self) -> int:
        return self.number_of_actors + self.number_of_learners


@dataclass
class TrainConfig:
    """Full training configuration. Field names follow the reference CLI
    (train_distributed.py:10–35); TPU-specific fields are grouped at the end."""

    # --- reference CLI contract -------------------------------------------
    model: str = "Qwen/Qwen2.5-7B-Instruct"
    dataset: str = "HuggingFaceH4/MATH-500"
    run_name: str | None = None
    project_name: str = "math-reasoning"
    lora_save_path: str = "lora_request_math"
    lr: float = 2e-5
    max_new_tokens: int = 1200
    max_prompt_tokens: int = 350
    temperature: float = 1.2
    episodes: int = 15
    num_candidates: int = 16
    batch_size: int = 30
    learner_chunk_size: int = 8
    train_batch_size: int = 8
    save_every: int = 100
    eval_every: int = 10
    number_of_actors: int = 2
    number_of_learners: int = 1
    learner: str = "pg"  # {"pg", "grpo"}
    max_lora_rank: int = 32
    # float (16 == 16.0 keeps reference-dict parity): lora_scale is
    # alpha/rank float math and worker_main --lora-alpha is float — an
    # int-only driver could not express an alpha the workers accept
    lora_alpha: float = 16.0
    lora_dropout: float = 0.0
    topk: int = 16
    # HBM fraction for weights+KV (vLLM gpu_memory_utilization contract,
    # ref train_distributed.py:34-35): sizes the paged engine's KV page
    # pool (engine/budget.py). actor_gpu_usage applies on disjoint rollout
    # meshes (the reference's actor GPUs); learner_gpu_usage applies when
    # roles timeshare one mesh (the reference's learner GPU, where training
    # state shares the chip with the engine).
    actor_gpu_usage: float = 0.91
    learner_gpu_usage: float = 0.35

    # --- TPU-native additions ---------------------------------------------
    mesh: MeshConfig = field(default_factory=MeshConfig)
    seed: int = 3407  # reference fixes random_state=3407 (helper.py:43)
    dtype: str = "bfloat16"
    # weight-only quantization of the frozen base: {"none","int8","int4"}
    # (reference uses NF4 via bitsandbytes — LOAD_IN_4BIT, distributed_actor.py:17)
    base_quant: str = "none"
    # quantization group size along the input dim for base_quant (ISSUE 15):
    # None = per-format default (int8: per-column scales; int4: 64-wide
    # blocks, bnb's blockwise NF4 knob). Must divide the model's projection
    # input dims; requires base_quant != "none" (dead-flag policy).
    quant_group_size: int | None = None
    # 8-bit blockwise optimizer state (reference: bnb.optim.Adam8bit, :209)
    optimizer_8bit: bool = True
    # Skip semantics for all-zero-reward microbatches. The reference intends
    # "skip if all rewards are zero" but `.all() == 0` skips when ANY reward is
    # zero (distributed_actor.py:367 — SURVEY §3.6.3). We implement the intent.
    skip_all_zero_reward_batches: bool = True
    eval_temperature: float = 0.6
    eval_top_p: float = 0.95
    eval_n: int = 8
    # use the exact sort-based nucleus filter (reference vLLM semantics)
    # instead of the fast bisection filter, for reproducibility runs
    top_p_exact: bool = False
    # chunked fused-cross-entropy logprobs in the learner (unsloth CE-kernel
    # equivalent, SURVEY §2b N3): lm_head + logsumexp run per time-chunk of
    # this many answer positions under scan+checkpoint, shrinking the live
    # logits buffer from [B, T, V] to [B, chunk, V] with bit-identical math.
    # 0 = dense. At the default learner shapes (8×1200×152k vocab, f32)
    # chunk=128 is ~5.8 GB → ~0.6 GB of logits memory.
    logprob_chunk: int = 128
    # bf16 full-rank fine-tuning (reference recipe 3: "bf16 full-rank, no
    # 4-bit"): the WHOLE param tree trains instead of a LoRA adapter; weight
    # sync pushes the full tree to the rollout mesh each step. Requires an
    # unquantized base; LoRA rank/alpha/dropout and the adapter-file writer
    # do not apply.
    full_finetune: bool = False
    # prompt length buckets for the rollout engine (SURVEY §2b N1): each
    # round compiles/runs at the smallest bucket holding its longest real
    # prompt. Empty = single bucket at max_prompt_tokens.
    prompt_buckets: tuple[int, ...] = ()
    # answer length buckets for the LEARNER update step: each update runs at
    # the smallest bucket holding the batch's longest real answer instead of
    # always padding to max_new_tokens (the reference pads every row to the
    # full window, distributed_actor.py:224–229 — ~60% wasted learner FLOPs
    # at its own ~470-token mean). Exact semantics (trailing all-masked
    # columns contribute nothing); one compiled step per bucket. Empty =
    # single width at max_new_tokens.
    learner_len_buckets: tuple[int, ...] = ()
    # the same cut on the learner's LEFT-padded prompt side (leading
    # all-masked columns dropped). Deliberately a SEPARATE flag from the
    # engine's prompt_buckets: the learner slice shifts absolute RoPE
    # positions (exact only up to float round-off — relative distances are
    # unchanged) and multiplies compiled step widths, so it must be an
    # explicit opt-in rather than riding an engine knob.
    learner_prompt_buckets: tuple[int, ...] = ()
    # rollout engine implementation: "dense" (fixed-shape cache), "paged"
    # (packed ragged KV pages + Pallas paged-attention decode — the full N1),
    # or "paged_sharded" (ONE paged engine whose page pool is partitioned
    # over the rollout mesh's dp axis via shard_map — engine/sharded_paged.py;
    # wave scheduler, dp-only meshes)
    engine_impl: str = "dense"
    # KV cache quantization: "none" or "int8" (per-token absmax; the
    # compact-scales Pallas launches keep per-element traffic at
    # ~1 byte — ops/paged_native.py — so int8 KV is a bandwidth AND capacity
    # knob). None (default) = let the autotune plan DB decide per
    # (device, model, geometry) via ExecutionPlan.kv_format — the int8
    # serving default is MEASURED in, not hard-coded; with an empty DB the
    # engines fall back to "none", byte-identical to the historical
    # default. An EXPLICIT value — including "none" — always wins over any
    # stored plan (the decode_scan_chunk convention: default ≠ pin).
    kv_cache_quant: str | None = None
    # K decode steps per dispatch in the dense engine (lax.scan inside one
    # jitted program). Where per-dispatch host overhead bounds decode
    # throughput regardless of chip speed (tools/dispatch_probe.py measures
    # it), chunking divides that overhead by K. The engine compile-checks the chunked program's
    # memory_analysis and falls back to one dispatch per step if the TPU
    # compiler double-buffered the KV cache in the scan carry.
    # None (default) = let the autotune plan DB decide (static default: off);
    # an EXPLICIT value — including 0 — always wins over any stored plan.
    decode_scan_chunk: int | None = None
    # execution-plan autotuner (distrl_llm_tpu/autotune): engines resolve
    # their dispatch choices (scan chunk, cache-read formulation, top-p
    # impl, prompt buckets) from a persistent DB of on-device measurements
    # instead of hard-coded guesses. Explicitly-set flags always win; with
    # no DB entry behavior is byte-identical to the static defaults.
    # autotune=False pins the static defaults without consulting any DB.
    autotune: bool = True
    # plan-DB path (tools/autotune.py writes it). None = $DISTRL_PLAN_DB or
    # ~/.cache/distrl_llm_tpu/plan_db.json
    plan_db: str | None = None
    # control-plane rollout workers ("host:port", ...): when set, generation
    # dispatches to these worker processes (distributed/worker_main.py) over
    # the C++ control plane instead of running on local chips — the
    # multi-host actor fan-out (SURVEY §2b N5). The adapter ships with every
    # round; the local mesh serves the learner only.
    rollout_workers: tuple[str, ...] = ()
    # driver-side declaration that every rollout worker was started with
    # worker_main --capture-logprobs (its engine records behavior logprobs
    # per token). Required for clip_ratio > 0 / rollout_mode="async" over
    # workers — the driver cannot introspect worker engine flags, and a
    # worker round returning no logprobs fails the first training batch.
    workers_capture_logprobs: bool = False
    # learner→worker weight transport for rollout_workers (ISSUE 9):
    # "broadcast" (default) ships each optimizer step's adapter ONCE per
    # version over an out-of-band MSG_WEIGHTS push — delta-encoded against
    # the worker's last acked version, full-tensor on first contact or
    # checksum mismatch — and MSG_DISPATCH payloads carry only a
    # {weight_version} reference resolved from the worker's 2-slot adapter
    # cache. "dispatch" is the legacy fallback: the full LoRA pytree rides
    # in every dispatch payload (N workers × every round). Broadcast is
    # what makes inflight_weight_updates possible over remote workers.
    weight_bus: str = "broadcast"
    # --- control-plane resilience (distributed/resilience.py) -------------
    # background reconnect loop: unhealthy rollout workers are re-dialed
    # with seeded exponential backoff and re-admitted after a PING, so
    # capacity recovers instead of shrinking monotonically to "no healthy
    # workers remain". The first round after a rejoin re-warms (the fresh
    # worker process recompiles, so the cold deadline applies again).
    worker_rejoin: bool = True
    # transient worker-side errors (MSG_ERROR classified by exception type:
    # OSError / Connection* / Timeout flavors) retry on the same worker
    # this many times with seeded exponential backoff (base rpc_backoff_s)
    # before the shard is requeued to a different worker
    rpc_retries: int = 2
    rpc_backoff_s: float = 0.25
    # poison-shard quarantine: a shard that fails on this many DISTINCT
    # workers raises ShardFailedError naming the shard instead of grinding
    # every worker to unhealthy
    poison_shard_k: int = 3
    # degrade instead of raise on a quarantined shard: the round returns
    # the surviving groups (lost prompts are dropped by the trainer with
    # exact conservation accounting, counted in cp/degraded_groups) rather
    # than failing the run
    degrade_on_poison: bool = False
    # supervised restart budget for the async RolloutService producer: a
    # failed produce round retries in place (seeded backoff) this many
    # times across the run before the failure closes the buffer and
    # surfaces (rollout/producer_restarts counts the retries)
    producer_restarts: int = 2
    # cap on concurrent candidate rows in the rollout engine (vLLM
    # max_num_seqs; the reference tunes the same capacity knob — 256
    # concurrent sequences, train_distributed.py:34). 0 = unlimited; rounds
    # beyond the cap run as sequential waves of whole prompt groups.
    max_concurrent_sequences: int = 0
    # continuous batching for the paged engine: keep exactly
    # max_concurrent_sequences candidate rows decoding and admit a pending
    # candidate into every slot whose occupant hit EOS (vLLM's scheduler),
    # instead of draining whole waves. Requires engine_impl="paged" and a
    # max_concurrent_sequences cap.
    continuous_batching: bool = False
    # copy-on-write prompt-prefix sharing (ISSUE 12): a group's N rollouts
    # alias ONE refcounted prompt page chain (vLLM prefix caching) instead
    # of each holding a private copy — the partial tail page splits
    # copy-on-write at first decode write, prompt KV is resident ~once per
    # group, and finished groups' prompt pages recycle into decode
    # capacity. Greedy outputs are bit-identical to the unshared engine
    # (pinned in tests/test_prefix_sharing.py). Requires
    # continuous_batching (the refill scheduler's slot machinery).
    prefix_sharing: bool = False
    # serving-grade continuous admission (ISSUE 12): replace the
    # fixed-episode-batch prefill with a group request queue — each
    # prompt prefills lazily into pool-allocated chain pages as freed
    # slots and page budget allow, so short completions backfill
    # immediately instead of idling until the batch drains. Implies
    # prefix_sharing (chains are pool-allocated); requires
    # continuous_batching. Leaving BOTH flags unset keeps the engine
    # plan-DB-resolvable (a stored cb_mode="continuous" entry may enable
    # it; empty DB = historical fixed batches, byte-identical).
    continuous_admission: bool = False
    # tiered KV cache, tier 1 (ISSUE 18): cross-request radix prefix index
    # over the continuous-admission pool — any prompt sharing a cached
    # prefix (multi-turn history, shared task preambles) aliases those
    # pages and prefills ONLY its un-cached suffix, with unpinned cache
    # nodes LRU-evicted under page pressure. Greedy outputs stay
    # bit-identical to the cache-off engine (the warm suffix prefill runs
    # the same packed attention numerics over the cached pages —
    # tests/test_prefix_sharing.py pins it). None = plan-DB-resolvable
    # (stored prefix_cache="on" enables; empty DB = off, byte-identical);
    # an explicit bool — including False — pins past any stored plan.
    # Requires continuous_admission and an unquantized KV pool.
    prefix_cache: bool | None = None
    # tiered KV cache, tier 2 (ISSUE 18): preempted chains spill their
    # written KV pages to a host-RAM page store on a background thread and
    # restore bit-exactly on resume (no recompute); idle cache nodes spill
    # on eviction and page back in on the next radix hit. Explicit-only
    # (never plan-resolved); requires prefix_cache; incompatible with
    # spec_draft (speculative chains resume by recompute).
    kv_spill: bool = False
    # host page-store byte cap in MiB for kv_spill (0 = unbounded); the
    # store LRU-drops whole payloads past the cap, and a dropped preempt
    # payload falls back to the recompute resume path
    kv_spill_host_mb: int = 0
    # speculative decoding for the paged refill engine: draft spec_draft
    # tokens per step and verify them in one forward (the verify attention
    # runs as ONE fused blocked kernel sweep — spec_verify); rejection
    # sampling keeps the output distribution identical to plain decoding
    # (exact under greedy). Requires continuous_batching. None = engine
    # default (off unless a tuned plan-DB entry for this geometry says
    # otherwise); an EXPLICIT value — INCLUDING 0 — pins the choice past
    # any stored plan (the decode_scan_chunk convention: default ≠ pin),
    # so --spec_draft 0 is always a real A/B control.
    spec_draft: int | None = None
    # lookup n-gram size for the ngram drafter. None = engine default (2)
    # unless a tuned plan-DB entry says otherwise; an explicit value pins
    # past any stored plan (the decode_scan_chunk convention).
    spec_ngram: int | None = None
    # draft source: "ngram" (prompt lookup over the row's own history) or
    # "self" — the policy's own PREVIOUS LoRA version, sourced from the
    # in-flight weight-update swap log (PipelineRL: recent-checkpoint
    # weights stay near-on-policy, so the previous version is a
    # high-acceptance draft model for free). "self" needs a LoRA run (the
    # drafter rides the adapter mailbox; full_finetune has no adapter
    # stream to draft from). None = engine default ("ngram") unless a
    # tuned plan-DB entry says otherwise; an EXPLICIT value — including
    # "ngram" itself — pins the choice past any stored plan (the
    # decode_scan_chunk convention: default ≠ pin).
    spec_drafter: str | None = None
    # verify-attention kernel: "fused" (one blocked Pallas sweep for the
    # whole draft block; probe-gated with an exact unrolled fallback) or
    # "unrolled" (d+1 per-position dispatches — the A/B control). None =
    # engine default ("fused") / plan-DB; explicit value pins.
    spec_verify: str | None = None
    # acceptance-rate-driven draft-length adaptation: shrink the effective
    # draft length (halving, floor 1) when the accept-rate EMA says drafts
    # are being wasted, grow it back when acceptance recovers
    spec_adapt: bool = False
    # Rollout/learner coupling regime (distrl_llm_tpu/rollout):
    #   "sync"      — the reference's strictly synchronous loop: generation
    #                 and learning serialize; byte-identical to the pre-async
    #                 trainer (pinned by tests/test_rollout_modes.py).
    #   "pipelined" — one-step overlap (LlamaRL/PipelineRL-style): batch t+1
    #                 generates WHILE the learner updates on batch t;
    #                 rollouts sample exactly one optimizer step stale.
    #   "async"     — fully decoupled: a RolloutService generates
    #                 continuously into a bounded trajectory buffer and the
    #                 learner pulls batches on its own cadence; staleness is
    #                 bounded by max_staleness and corrected by the
    #                 AIPO/truncated-IS objective over per-token version
    #                 tags (requires clip_ratio > 0 for the engine-captured
    #                 behavior logprobs the correction ratios against).
    rollout_mode: str = "sync"
    # staleness bound for rollout_mode="async": trajectories whose stalest
    # token lags the learner by more than this many optimizer steps are
    # dropped or down-weighted (staleness_policy) and the version-lag mask
    # inside the AIPO objective enforces the same bound token-wise.
    # sync/pipelined derive their allowed lag (0 / 1) from the mode.
    max_staleness: int = 2
    # trajectory-buffer capacity in task GROUPS for rollout_mode="async";
    # 0 = auto (4 × batch_size, floor 2 × batch_size — the learner pulls
    # batch_size groups per update, so the floor keeps a get from
    # deadlocking against producer backpressure)
    rollout_buffer_groups: int = 0
    # what happens to a pulled group beyond max_staleness: "drop" (discard,
    # counted in rollout/dropped_stale) or "downweight" (train with its
    # update coefficients scaled by staleness_downweight^(lag − K))
    staleness_policy: str = "drop"
    staleness_downweight: float = 0.5
    # AIPO truncation cap C for the async objective's per-token importance
    # ratio min(exp(logp_cur − logp_behavior), C)
    rollout_is_cap: float = 2.0
    # DEPRECATED alias for --rollout_mode pipelined (the pre-rollout-service
    # spelling): async_rollout=True with the default rollout_mode selects
    # "pipelined"; after __post_init__ this field always reads as
    # (rollout_mode != "sync") so existing call sites keep working.
    async_rollout: bool = False
    # in-flight weight updates (PipelineRL-style): push each optimizer
    # step's adapter into the generation round still in flight instead of
    # waiting for it to drain — the engines swap at the next decode
    # dispatch, and the PPO-clip objective ratios every token against the
    # captured behavior logprob of the policy that actually sampled it.
    # Requires async_rollout (there must BE an in-flight round), clip_ratio
    # > 0 (the off-policy correction), local LoRA rollout.
    inflight_weight_updates: bool = False
    # PPO-clip surrogate epsilon (0 = reference parity: the no-KL/no-clip
    # single-update objective). With clip_ratio > 0 the learner ratios the
    # current policy against ENGINE-CAPTURED behavior logprobs
    # (GenerationResult.logprobs — the vLLM-logprobs equivalent) and trains
    # on the engine's raw token ids, making updates stable off-policy
    # (async_rollout staleness; the reference's documented long-training
    # instability, README.md:91).
    clip_ratio: float = 0.0
    # KL(π‖π_ref) penalty coefficient (the GRPO paper's regularizer; the
    # reference never loads a reference model — SURVEY §3.6.2). π_ref is the
    # FROZEN BASE, so this is LoRA-mode only (full_finetune would need a
    # second resident tree) and costs one extra no-adapter forward.
    kl_coeff: float = 0.0
    # per-update sample dump (the reference prints a problem/completion/
    # reward sample every update, distributed_trainer.py:297–299)
    print_samples: bool = True
    # write HF-format merged-model snapshots to run_dir/model_{step} at every
    # save_every step and episode end (the reference's save_pretrained
    # artifacts, distributed_trainer.py:372–380). Heavy (full model write);
    # requires run_name and an unquantized base.
    export_hf_snapshots: bool = False
    checkpoint_dir: str | None = None
    resume: bool = False
    metrics_backend: str = "auto"  # {"auto","wandb","jsonl","null"}
    # attention implementation for learner/prefill forwards:
    # "reference" (XLA softmax), "flash" (Pallas blockwise kernel, TPU only,
    # GQA via repeat — ops/flash_attention.py), "splash" (Pallas multi-query
    # kernel, native GQA with no KV repeat — ops/splash.py), "ring"
    # (sequence-parallel by KV rotation — ops/ring_attention.py), or
    # "ulysses" (sequence-parallel by all-to-all head scatter — ops/ulysses.py;
    # needs heads divisible by sp); non-TPU backends fall back to the
    # reference path with a warning
    attn_impl: str = "reference"
    write_adapter_file: bool = False  # artifact-parity adapter writer
    # jax.profiler trace capture (SURVEY §5 tracing): traces the step window
    # [profile_start_step, profile_start_step + profile_num_steps) into
    # profile_dir (TensorBoard format). Step 1 is skipped by default — it is
    # dominated by compilation.
    profile_dir: str | None = None
    profile_start_step: int = 2
    profile_num_steps: int = 3
    # Span-trace capture (telemetry.py): when set, the trainer records
    # driver (generation/reward/update/eval), engine (prefill/decode), and
    # worker spans — workers ship theirs back over the control plane — and
    # writes one Chrome-trace/Perfetto JSON to trace_dir/trace.json.
    # trace_steps > 0 limits recording to the first N train steps (the file
    # is written when the window closes); 0 traces the whole run and writes
    # at shutdown. Orthogonal to profile_dir (device-level XLA traces).
    trace_dir: str | None = None
    trace_steps: int = 0
    # --- continuous observability (distrl_llm_tpu/obs.py, ISSUE 8) --------
    # Live metrics endpoint: serve the cumulative telemetry registry over
    # HTTP (Prometheus text at /metrics, JSON at /metrics.json) from the
    # driver process. With remote rollout workers the endpoint additionally
    # publishes fleet/* series aggregated from the per-worker snapshots
    # piggybacked on control-plane results. None = off; 0 = auto-assign a
    # port (read it from the startup log).
    metrics_port: int | None = None
    # Anomaly sentinel: deterministic triggers per train step (NaN/Inf
    # loss, reward collapse, staleness blowup, tok/s regression vs a
    # running EMA, HBM watermark breach); each fires at most once and dumps
    # the flight-recorder ring into an incident directory. Requires
    # flight_recorder_dir (the evidence has to land somewhere).
    sentinel: bool = False
    # Incident bundle output directory: arming it keeps a bounded
    # in-memory ring of recent step records (obs_ring_size) that sentinel
    # triggers dump as incident_step<N>_<trigger>/ with the metric ring,
    # telemetry span tail, and config/plan snapshot.
    flight_recorder_dir: str | None = None
    obs_ring_size: int = 256
    # --- trajectory lineage ledger (distrl_llm_tpu/lineage.py, ISSUE 10) --
    # Follow every sampled group from prompt through the buffer into the
    # optimizer step that consumed it and out as a broadcast weight version:
    # per-group LineageRecords (sampling worker + causal dispatch_id, weight
    # versions, buffer passage, staleness verdict, consuming step) plus the
    # derived lag histograms (lineage/sample_to_learn_ms,
    # lineage/learn_to_act_ms, lineage/policy_lag_ms) on the registry /
    # metrics endpoint. Async-mode only (the sync loop has no buffer or
    # staleness machinery to trace). One attribute check per hook site when
    # off. lineage_dir set alone implies lineage=True.
    lineage: bool = False
    # per-run JSONL output (lineage_dir/lineage.jsonl, streamed as records
    # close; tools/lineage_report.py reads it). None = ring only.
    lineage_dir: str | None = None
    # bounded ring of OPEN records; overflow is counted
    # (lineage/ring_evictions), never silent
    lineage_ring: int = 1024
    # --- serving observability (distrl_llm_tpu/serving_obs.py, ISSUE 13) --
    # Request-level serving ledger over the continuous-batching engine:
    # per-group lifecycle events (enqueue → admit → prefill done → first
    # token → finish) recorded at the refill loop's host chunk boundaries,
    # yielding serving/ttft_ms, serving/tpot_ms, serving/queue_wait_ms,
    # serving/e2e_ms histograms plus the admission audit
    # (serving/admission_stalls/<reason>). Requires engine_impl='paged' +
    # continuous_batching (the instrumented loops); over rollout_workers
    # the ledger is armed worker-side (worker_main --serving-obs) and the
    # driver folds the fleet view. One attribute check per hook when off.
    serving_obs: bool = False
    # per-run JSONL (serving_dir/serving.jsonl, streamed as records close;
    # tools/serving_report.py reads it). Implies serving_obs.
    serving_dir: str | None = None
    # bounded ring of OPEN serving records; overflow counted
    # (serving/ring_evictions), never silent
    serving_ring: int = 1024
    # SLO gates (ISSUE 13): arm the sentinel's ttft_blowup /
    # queue_wait_blowup triggers — the step's worst observed latency above
    # the limit dumps a flight-recorder bundle. Require --sentinel.
    slo_ttft_ms: float | None = None
    slo_queue_wait_ms: float | None = None
    # --- multi-tenant serving gateway (distrl_llm_tpu/gateway/, ISSUE 19) -
    # Streaming HTTP front-end + priority-class scheduling over the
    # continuous-admission engine: POST /v1/generate streams tokens as the
    # refill loop emits them, requests carry tenant + priority class
    # (interactive > batch > scavenger) from headers, and the gateway's
    # round former drains its open queue class-then-FIFO-with-aging.
    # gateway_port None = gateway off (the default; off is byte-identical
    # to a build without the subsystem). 0 = auto-assign (the bound port
    # is printed as "GATEWAY <n>").
    gateway_port: int | None = None
    # comma-separated subset of priority classes this gateway serves
    # (empty = all three). Requests naming an unserved class are rejected
    # with HTTP 400, never silently reclassified.
    gateway_classes: str | None = None
    # per-tenant reserved-token quotas, "tenant=tokens,..."; the pseudo-
    # tenant "default" caps tenants not named. Admission declines on quota
    # are the ``quota`` stall reason in the serving ledger's conservation
    # sum. Requires the gateway (dead otherwise).
    tenant_quota: str | None = None
    # --- training-dynamics observability (learn_obs.py, ISSUE 16) ---------
    # Device-computed training-dynamics bundle fused into the jitted train
    # step (learner/train_step.py emit_dynamics): masked policy entropy,
    # behavior↔policy KL, pre-binned IS-ratio histogram, clip/cap-saturation
    # fractions, advantage moments, per-layer-group LoRA grad norms — all
    # riding the ONE host transfer the loss already pays. The armed run is
    # byte-identical to off in losses and adapter (pinned,
    # tools/learn_smoke.py). Publishes learn/* registry series + a per-step
    # JSONL (learn_dir/learn.jsonl; tools/learn_report.py reads it).
    # learn_dir set alone implies learn_obs=True.
    learn_obs: bool = False
    learn_dir: str | None = None
    # reward-distribution drift reference window (steps); drift is the
    # z-score of the step's reward mean against the trailing window of
    # older means
    learn_drift_window: int = 32
    # Training-dynamics sentinel triggers (ISSUE 16): each arms one
    # deterministic trigger on the learn/* view; all require --sentinel
    # (the evidence lands in the flight recorder) and auto-arm learn_obs
    # (the signal's producer). Default None = off.
    # entropy_collapse: masked answer-token entropy below this floor
    learn_entropy_floor: float | None = None
    # kl_blowup: behavior↔policy KL above this limit; also an escalation
    # input to the staleness governor when control_staleness is armed
    learn_kl_limit: float | None = None
    # ratio_saturation: AIPO cap-saturation (or PPO clip) fraction above
    # this threshold — fraction of answer tokens whose IS ratio the
    # correction truncated
    learn_ratio_sat_frac: float | None = None
    # grad_spike: whole-adapter grad norm above this multiple of its
    # running EMA (must be > 1)
    learn_grad_spike: float | None = None
    # --- self-healing runtime (distrl_llm_tpu/control/, ISSUE 14) ---------
    # Closed-loop governors that ACT on the observability plane: bounded,
    # hysteretic, cooldown-guarded actuations with a global per-run budget.
    # --control arms every controller the run's shape supports (silently
    # skipping inapplicable ones); the per-controller flags arm exactly one
    # and LOUDLY reject a run shape that cannot host it (dead-flag policy).
    # All default OFF; a run with controllers off is byte-identical to one
    # without the subsystem (pinned).
    control: bool = False
    # HBM governor: shrinks the continuous-admission chain cap under
    # watermark pressure / hbm_breach, regrows after a sustained-headroom
    # dwell. Requires a LOCAL paged engine with continuous_admission
    # (fleet runs arm it worker-side: worker_main --control-hbm).
    control_hbm: bool = False
    # SLO load-shedder: throttles admit_groups (decline reason "shed")
    # while serving TTFT/queue-wait breach the PR 13 SLOs. Requires
    # continuous_admission + at least one slo_* limit; worker-side over
    # rollout_workers (worker_main --control-shed).
    control_shed: bool = False
    # staleness governor: adapts the EFFECTIVE max_staleness and buffer
    # high watermark from the live lineage/policy_lag_ms distribution
    # (async mode only; drop/downweight semantics preserved — only the
    # bound moves, never past the configured max_staleness). Requires
    # lineage (the signal's producer).
    control_staleness: bool = False
    # worker-health actor: converts a per-worker tok/s regression into
    # proactive quarantine + rejoin-probe (the PR 5 machinery). Requires
    # rollout_workers + worker_rejoin.
    control_worker_health: bool = False
    # nan-loss rollback: restore the last-good (adapter, opt state,
    # version) snapshot and skip the poisoned step instead of training on
    # NaNs from there on. Applicable to every run shape.
    control_nan_rollback: bool = False
    # --- elastic fleet (distrl_llm_tpu/distributed/fleet.py, ISSUE 20) ----
    # autoscaling governor: steers a FleetSupervisor-owned worker pool's
    # target size over [fleet_min, fleet_max] — scale-up admits a cold
    # worker through add_worker (full weight-bus resync), scale-down
    # retires the least-productive worker through the graceful-drain path.
    # Requires rollout_workers + worker_rejoin + fleet bounds. NOT armed by
    # the --control master (resizing the pool is a capacity decision, not
    # a self-healing default) — always explicit.
    control_autoscale: bool = False
    # target-pool bounds for the autoscaler / FleetSupervisor; 0 = unset
    # (the fleet stays static at the connect-time worker set)
    fleet_min: int = 0
    fleet_max: int = 0
    # global actuation budget per run: once spent, every knob freezes at
    # its current (clamped) value — a runaway controller is bounded by
    # construction
    control_budget: int = 64
    # minimum steps between two actions of one governor
    control_cooldown_steps: int = 2
    # consecutive healthy observations required before a governor regrows
    # a previously shrunk knob (the sustained-headroom dwell)
    control_dwell_steps: int = 3
    # staleness governor setpoint: policy-lag p90 above this shrinks the
    # effective staleness bound / buffer watermark; sustained p90 under
    # half of it regrows them
    control_lag_ms: float = 5000.0
    # Hang detector on generation rounds — parity with the reference's
    # ray.get(timeout=240) (distributed_trainer.py:200). 0 disables (the
    # default: a first rollout legitimately spends minutes in XLA compilation;
    # production configs should set it once compile times are known). On
    # timeout the trainer checkpoints and raises EngineHangError — restart
    # with resume=True to continue from the last completed step.
    generation_timeout_s: float = 0.0

    # --- Pluggable environments (ISSUE 17) ---------------------------------
    # rollout environment: "math" (the legacy single-turn scorer — the exact
    # pre-env generation/reward path, byte-identical), "code" (multi-turn
    # sandboxed <tool> execution with outputs fed back), or "verifier"
    # (multi-turn verifier-feedback, per-turn improvement reward). Multi-turn
    # envs interleave engine generation with env.step on the local paged
    # refill engine: continuing conversations are re-admitted onto their
    # resident KV chains (no re-prefill) and env-injected observation tokens
    # are loss-masked in the learner.
    env: str = "math"
    # max conversation turns per episode for multi-turn envs. env="math" is
    # single-turn by construction, so >1 there is a dead flag (rejected).
    max_turns: int = 1
    # format-reward gate: "soft" (the reference's anchored single-line
    # pattern — the parity default) or "strict" (the newline-delimited
    # variant, previously dead parity code)
    format_reward: str = "soft"

    def __post_init__(self):
        if self.learner not in ("pg", "grpo"):
            raise ValueError(f"learner must be 'pg' or 'grpo', got {self.learner!r}")
        if self.rollout_mode not in ("sync", "pipelined", "async"):
            raise ValueError(
                f"rollout_mode must be sync/pipelined/async, got "
                f"{self.rollout_mode!r}"
            )
        # --async_rollout is the deprecated spelling of --rollout_mode
        # pipelined; after normalization async_rollout reads as "any
        # overlapped mode" (the trainer's pushed-copy/no-hybrid paths apply
        # to pipelined AND async alike)
        if self.async_rollout and self.rollout_mode == "sync":
            self.rollout_mode = "pipelined"
        self.async_rollout = self.rollout_mode != "sync"
        if self.max_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0, got {self.max_staleness}"
            )
        if self.staleness_policy not in ("drop", "downweight"):
            raise ValueError(
                f"staleness_policy must be drop/downweight, got "
                f"{self.staleness_policy!r}"
            )
        if self.rollout_buffer_groups < 0:
            raise ValueError(
                f"rollout_buffer_groups must be >= 0, got "
                f"{self.rollout_buffer_groups}"
            )
        if self.rollout_mode == "async":
            if self.clip_ratio <= 0:
                raise ValueError(
                    "rollout_mode='async' requires clip_ratio > 0: the "
                    "bounded-staleness regime trains on trajectories up to "
                    "max_staleness optimizer steps old, and the truncated-IS "
                    "correction consumes the engine-captured behavior "
                    "logprobs that clip_ratio enables"
                )
            if self.max_staleness < 1:
                raise ValueError(
                    "rollout_mode='async' requires max_staleness >= 1 (0 "
                    "would drop every trajectory the moment the learner "
                    "steps; use rollout_mode='sync' for strict on-policy)"
                )
        if self.base_quant not in ("none", "int8", "int4"):
            raise ValueError(f"base_quant must be none/int8/int4, got {self.base_quant!r}")
        if self.engine_impl not in ("dense", "paged", "paged_sharded"):
            raise ValueError(
                f"engine_impl must be dense/paged/paged_sharded, got "
                f"{self.engine_impl!r}"
            )
        if self.kv_cache_quant not in (None, "none", "int8"):
            raise ValueError(
                f"kv_cache_quant must be none/int8 (or unset = plan-DB-"
                f"resolved), got {self.kv_cache_quant!r}"
            )
        if self.quant_group_size is not None and self.quant_group_size < 1:
            raise ValueError(
                f"quant_group_size must be >= 1, got {self.quant_group_size}"
            )
        if self.quant_group_size is not None and self.base_quant == "none":
            # dead-flag policy: the group size shapes the base containers,
            # which only exist under base_quant
            raise ValueError(
                "quant_group_size configures base_quant's groupwise scales "
                "— set base_quant int8/int4 (it would be silently ignored)"
            )
        if self.engine_impl == "paged_sharded" and (
            self.continuous_batching or self.spec_draft
        ):
            raise ValueError(
                "paged_sharded runs the wave scheduler only; continuous "
                "batching / speculative decoding are per-replica engine "
                "features (engine/sharded_paged.py)"
            )
        if self.full_finetune and self.base_quant != "none":
            raise ValueError(
                "full_finetune trains the base weights — they cannot be "
                "quantized (base_quant must be 'none')"
            )
        if self.full_finetune and self.write_adapter_file:
            raise ValueError(
                "full_finetune has no LoRA adapter to export; use "
                "export_hf_snapshots for full-model artifacts"
            )
        if self.full_finetune and self.lora_dropout:
            raise ValueError(
                "full_finetune has no adapter for lora_dropout to act on — "
                "set lora_dropout=0"
            )
        if self.full_finetune and self.kl_coeff:
            raise ValueError(
                "kl_coeff uses the frozen base as the reference policy — "
                "full_finetune has no frozen base (keep a LoRA run, or 0)"
            )
        if self.full_finetune and self.rollout_workers:
            # remote workers hold their own frozen base and receive only the
            # adapter; with no adapter the trained weights would never reach
            # them — silently severely-off-policy RL
            raise ValueError(
                "full_finetune cannot ship full weights to rollout_workers "
                "(workers receive adapters only); run local rollout"
            )
        if self.decode_scan_chunk is not None and self.decode_scan_chunk < 0:
            raise ValueError(
                f"decode_scan_chunk must be >= 0, got {self.decode_scan_chunk}"
            )
        if self.trace_steps < 0:
            raise ValueError(
                f"trace_steps must be >= 0, got {self.trace_steps}"
            )
        if self.trace_steps and not self.trace_dir:
            raise ValueError("trace_steps requires trace_dir")
        if self.metrics_port is not None and not (
            0 <= self.metrics_port <= 65535
        ):
            raise ValueError(
                f"metrics_port must be in [0, 65535] (0 = auto-assign), "
                f"got {self.metrics_port}"
            )
        if self.sentinel and not self.flight_recorder_dir:
            raise ValueError(
                "sentinel requires flight_recorder_dir — a trigger's whole "
                "point is the incident bundle it dumps there"
            )
        if self.obs_ring_size < 1:
            raise ValueError(
                f"obs_ring_size must be >= 1, got {self.obs_ring_size}"
            )
        if self.lineage_dir and not self.lineage:
            # an output directory is an unambiguous ask — arm the ledger
            self.lineage = True
        if self.lineage_ring < 1:
            raise ValueError(
                f"lineage_ring must be >= 1, got {self.lineage_ring}"
            )
        if self.lineage and self.rollout_mode != "async":
            raise ValueError(
                "lineage requires rollout_mode='async' — the ledger traces "
                "the buffer passage, staleness verdict, and decoupled "
                "consumption that only exist in the async regime (sync/"
                "pipelined rounds are consumed by construction)"
            )
        if self.serving_dir and not self.serving_obs:
            # an output directory is an unambiguous ask — arm the ledger
            self.serving_obs = True
        if self.serving_ring < 1:
            raise ValueError(
                f"serving_ring must be >= 1, got {self.serving_ring}"
            )
        for slo_name in ("slo_ttft_ms", "slo_queue_wait_ms"):
            slo = getattr(self, slo_name)
            if slo is not None and slo <= 0:
                raise ValueError(f"{slo_name} must be > 0, got {slo}")
        if (
            (self.slo_ttft_ms is not None
             or self.slo_queue_wait_ms is not None)
            and not self.sentinel
        ):
            raise ValueError(
                "slo_ttft_ms/slo_queue_wait_ms arm sentinel triggers "
                "(ttft_blowup / queue_wait_blowup) — set --sentinel (and "
                "--flight_recorder_dir) or drop the SLO flags"
            )
        if (
            (self.slo_ttft_ms is not None
             or self.slo_queue_wait_ms is not None)
            and not self.rollout_workers and not self.serving_obs
        ):
            # a local-engine SLO gate without the ledger could never fire
            # (nothing produces serving/*_max) — an SLO is an unambiguous
            # ask, arm the measurement; fleet runs instead read the
            # worker-fed fleet/serving_* gauges
            self.serving_obs = True
        if self.learn_dir and not self.learn_obs:
            # an output directory is an unambiguous ask — arm the ledger
            self.learn_obs = True
        if self.learn_drift_window < 2:
            raise ValueError(
                f"learn_drift_window must be >= 2 (a one-sample reference "
                f"window has no variance), got {self.learn_drift_window}"
            )
        for learn_name in ("learn_entropy_floor", "learn_kl_limit",
                           "learn_ratio_sat_frac", "learn_grad_spike"):
            limit = getattr(self, learn_name)
            if limit is not None and limit <= 0:
                raise ValueError(f"{learn_name} must be > 0, got {limit}")
        if (
            self.learn_ratio_sat_frac is not None
            and self.learn_ratio_sat_frac > 1.0
        ):
            raise ValueError(
                f"learn_ratio_sat_frac is a token fraction in (0, 1], got "
                f"{self.learn_ratio_sat_frac}"
            )
        if self.learn_grad_spike is not None and self.learn_grad_spike <= 1.0:
            raise ValueError(
                f"learn_grad_spike is a multiple of the grad-norm EMA and "
                f"must be > 1, got {self.learn_grad_spike}"
            )
        _learn_triggers = (
            self.learn_entropy_floor is not None
            or self.learn_kl_limit is not None
            or self.learn_ratio_sat_frac is not None
            or self.learn_grad_spike is not None
        )
        if _learn_triggers and not self.sentinel:
            raise ValueError(
                "learn_entropy_floor/learn_kl_limit/learn_ratio_sat_frac/"
                "learn_grad_spike arm sentinel triggers (entropy_collapse / "
                "kl_blowup / ratio_saturation / grad_spike) — set "
                "--sentinel (and --flight_recorder_dir) or drop them"
            )
        if _learn_triggers and not self.learn_obs:
            # a trigger without the producer could never fire — a threshold
            # is an unambiguous ask, arm the measurement (the SLO precedent)
            self.learn_obs = True
        if self.serving_obs:
            # dead-flag policy (the prefix_sharing precedent): the ledger
            # instruments the refill/continuous loops only
            if self.rollout_workers:
                raise ValueError(
                    "serving_obs over rollout_workers is armed WORKER-side "
                    "(worker_main --serving-obs; the driver folds the "
                    "fleet serving view from the obs blobs) — the driver "
                    "has no local refill engine to instrument"
                )
            if self.engine_impl != "paged" or not self.continuous_batching:
                raise ValueError(
                    "serving_obs instruments the paged engine's refill/"
                    "continuous loops — requires engine_impl='paged' and "
                    "continuous_batching"
                )
        # --- serving gateway validation (ISSUE 19) ------------------------
        if self.gateway_port is not None:
            if not (0 <= self.gateway_port <= 65535):
                raise ValueError(
                    f"gateway_port must be in [0, 65535] (0 = auto-assign), "
                    f"got {self.gateway_port}"
                )
            if (
                self.engine_impl != "paged"
                or not self.continuous_batching
                or not self.continuous_admission
            ):
                raise ValueError(
                    "the serving gateway schedules the continuous-admission "
                    "refill engine — requires engine_impl='paged', "
                    "continuous_batching, and continuous_admission"
                )
            if self.rollout_workers:
                raise ValueError(
                    "the serving gateway fronts a LOCAL engine; over "
                    "rollout_workers arm it worker-side "
                    "(worker_main --gateway-port)"
                )
            # validate eagerly so a bad spec fails at config time, not when
            # the first request arrives
            from distrl_llm_tpu.gateway.scheduler import (
                parse_gateway_classes,
                parse_tenant_quota,
            )
            parse_gateway_classes(self.gateway_classes)
            parse_tenant_quota(self.tenant_quota)
        elif self.gateway_classes or self.tenant_quota:
            # dead-flag policy: class/quota knobs shape the gateway's
            # admission plane only
            raise ValueError(
                "gateway_classes/tenant_quota configure the serving "
                "gateway — set gateway_port (they would be silently "
                "ignored otherwise)"
            )
        # decode_scan_chunk covers every engine_impl and scheduler (dense,
        # paged wave + refill + speculative, paged_sharded)
        if self.continuous_batching and (
            self.engine_impl != "paged" or not self.max_concurrent_sequences
        ):
            raise ValueError(
                "continuous_batching requires engine_impl='paged' and a "
                "max_concurrent_sequences cap (the decode slot count)"
            )
        if self.spec_draft and not self.continuous_batching:
            raise ValueError(
                "spec_draft (speculative decoding) requires "
                "continuous_batching (the refill scheduler hosts it)"
            )
        # dead-flag policy (mirrors the spec satellite knobs): prefix
        # sharing and continuous admission live on the refill scheduler —
        # without continuous_batching they would silently never engage
        if (self.prefix_sharing or self.continuous_admission) and (
            not self.continuous_batching
        ):
            raise ValueError(
                "prefix_sharing/continuous_admission run on the refill "
                "scheduler — set continuous_batching (and a "
                "max_concurrent_sequences cap); they would be silently "
                "ignored otherwise"
            )
        # dead-flag policy for the tiered KV cache (ISSUE 18): tier 1
        # aliases cached chains out of the continuous-admission pool, tier 2
        # spills through tier 1's host store — surface dead wiring here
        # rather than letting the engine raise mid-run
        if self.prefix_cache and not self.continuous_admission:
            raise ValueError(
                "prefix_cache (the radix KV cache) aliases cached prompt "
                "chains out of the continuous-admission pool — set "
                "continuous_admission (it would be a dead flag otherwise)"
            )
        if self.prefix_cache and self.kv_cache_quant == "int8":
            raise ValueError(
                "prefix_cache requires a lossless KV pool: int8 pages "
                "cannot reproduce the cold prefill's attention inputs "
                "bit-exactly — drop kv_cache_quant or prefix_cache"
            )
        if self.kv_spill and not self.prefix_cache:
            raise ValueError(
                "kv_spill parks KV pages through the tiered cache's host "
                "store — it requires prefix_cache"
            )
        if self.kv_spill and self.spec_draft:
            raise ValueError(
                "kv_spill restores raw decode cursors the speculative "
                "scheduler does not expose — preempted speculative chains "
                "already resume by recompute; drop kv_spill or spec_draft"
            )
        if self.kv_spill_host_mb and not self.kv_spill:
            raise ValueError(
                "kv_spill_host_mb caps the kv_spill host store — set "
                "kv_spill (it would be a dead knob otherwise)"
            )
        # Pluggable environments (ISSUE 17). Import here, not at module
        # top: config must stay importable without pulling the env package
        # (worker processes construct configs before JAX spins up).
        from distrl_llm_tpu.env import env_names
        if self.env not in env_names():
            raise ValueError(
                f"env must be one of {', '.join(env_names())}, got "
                f"{self.env!r}"
            )
        if self.max_turns < 1:
            raise ValueError(f"max_turns must be >= 1, got {self.max_turns}")
        if self.format_reward not in ("soft", "strict"):
            raise ValueError(
                f"format_reward must be 'soft' or 'strict', got "
                f"{self.format_reward!r}"
            )
        if self.env == "math" and self.max_turns > 1:
            # dead-flag policy: the math env is single-turn by construction
            raise ValueError(
                "max_turns > 1 is a dead flag with env='math' (single-turn "
                "by construction) — pick env='code' or env='verifier'"
            )
        if self.env != "math":
            # multi-turn envs need the refill scheduler's slot machinery:
            # the engine turn hook re-admits continuing conversations onto
            # their resident KV chains between turns
            if not (self.continuous_batching and self.continuous_admission):
                raise ValueError(
                    f"env={self.env!r} (multi-turn) requires "
                    "continuous_batching + continuous_admission: turn "
                    "continuations re-enter through the refill scheduler's "
                    "admission queue onto resident KV chains"
                )
            if self.engine_impl != "paged":
                raise ValueError(
                    f"env={self.env!r} requires engine_impl='paged' (the "
                    "turn hook lives on the local paged refill engine)"
                )
            if self.spec_draft:
                raise ValueError(
                    f"env={self.env!r} is incompatible with spec_draft: "
                    "the turn hook and the speculative resume path contend "
                    "for the same slot state"
                )
            if self.rollout_workers:
                raise ValueError(
                    f"env={self.env!r} runs driver-local only this "
                    "iteration — rollout_workers have no turn hook"
                )
        if self.spec_draft is not None and not 0 <= self.spec_draft <= 16:
            raise ValueError(
                f"spec_draft must be in [0, 16] (longer draft blocks waste "
                f"verify width faster than they amortize weight reads), got "
                f"{self.spec_draft}"
            )
        if self.spec_ngram is not None and self.spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {self.spec_ngram}")
        if self.spec_drafter not in (None, "ngram", "self"):
            raise ValueError(
                f"spec_drafter must be 'ngram' or 'self', got "
                f"{self.spec_drafter!r}"
            )
        if self.spec_verify not in (None, "fused", "unrolled"):
            raise ValueError(
                f"spec_verify must be 'fused' or 'unrolled', got "
                f"{self.spec_verify!r}"
            )
        # the satellite knobs are dead flags unless speculation can engage:
        # loud errors here keep this entry point consistent with
        # worker_main's parser (which rejects the same combinations)
        # instead of silently running plain decode
        if not self.continuous_batching and (
            self.spec_ngram is not None or self.spec_drafter is not None
            or self.spec_verify is not None or self.spec_adapt
        ):
            raise ValueError(
                "spec_ngram/spec_drafter/spec_verify/spec_adapt configure "
                "speculative decoding, which requires continuous_batching "
                "(the refill scheduler hosts it) — they would be silently "
                "ignored"
            )
        if self.spec_draft == 0 and (
            self.spec_ngram is not None or self.spec_drafter is not None
            or self.spec_verify is not None
        ):
            raise ValueError(
                "spec_ngram/spec_drafter/spec_verify with spec_draft=0: an "
                "explicit 0 pins speculation off, so they would be "
                "silently ignored (leave spec_draft unset to let the plan "
                "DB decide)"
            )
        # spec_draft None counts: a plan-DB entry may enable speculation at
        # engine construction, and full_finetune never grows an adapter
        # stream, so the combination is invalid whenever speculation COULD
        # engage (only an explicit 0 pins it off)
        if self.spec_drafter == "self" and self.spec_draft != 0:
            if self.full_finetune:
                raise ValueError(
                    "spec_drafter='self' drafts with the policy's previous "
                    "LoRA adapter (the weight-update mailbox stream) — "
                    "full_finetune has no adapter stream; use "
                    "spec_drafter='ngram'"
                )
        if self.spec_adapt and self.spec_draft == 0:
            # spec_draft=None stays legal here: a tuned plan-DB entry may
            # enable speculation, and the engine re-validates post-resolution
            raise ValueError(
                "spec_adapt adapts the speculative draft length — set "
                "spec_draft > 0"
            )
        if self.weight_bus not in ("broadcast", "dispatch"):
            raise ValueError(
                f"weight_bus must be 'broadcast' or 'dispatch', got "
                f"{self.weight_bus!r}"
            )
        if self.inflight_weight_updates:
            if not self.async_rollout:
                raise ValueError(
                    "inflight_weight_updates requires async_rollout (there "
                    "must be an in-flight generation round to update)"
                )
            if self.clip_ratio <= 0:
                raise ValueError(
                    "inflight_weight_updates requires clip_ratio > 0: tokens "
                    "sampled pre-swap are off-policy for the update, and the "
                    "clip objective is the correction that consumes their "
                    "captured behavior logprobs"
                )
            if self.full_finetune:
                raise ValueError(
                    "inflight_weight_updates requires a LoRA run "
                    "(full_finetune swaps the whole param tree, not an "
                    "adapter)"
                )
            if self.rollout_workers and self.weight_bus != "broadcast":
                # the silent-no-op fix (ISSUE 9): this combination used to
                # pretend to work while never updating worker weights
                # mid-round — the engine lacked a real push_lora. The
                # broadcast bus provides one; anything else is an error,
                # never a silent regression (the trainer additionally
                # rejects any engine without push_lora at construction).
                raise ValueError(
                    "inflight_weight_updates over rollout_workers requires "
                    "weight_bus='broadcast' (the versioned weight bus is "
                    "what delivers mid-round adapters to workers; "
                    "'dispatch' ships weights only at round entry and "
                    "would silently never swap)"
                )
        if (
            self.clip_ratio > 0 and self.rollout_workers
            and not self.workers_capture_logprobs
        ):
            # clip needs per-token behavior logprobs captured at generation
            # time; by default worker engines are built without
            # capture_logprobs, so a remote-rollout clip run would only fail
            # at the first training batch — reject it up front unless the
            # caller declares the workers were started with
            # --capture-logprobs (worker_main)
            raise ValueError(
                "clip_ratio > 0 with rollout_workers requires workers "
                "started with --capture-logprobs AND "
                "--workers_capture_logprobs on the driver (declares the "
                "worker engines record behavior logprobs)"
            )
        if self.rpc_retries < 0:
            raise ValueError(f"rpc_retries must be >= 0, got {self.rpc_retries}")
        if self.rpc_backoff_s < 0:
            raise ValueError(
                f"rpc_backoff_s must be >= 0, got {self.rpc_backoff_s}"
            )
        if self.poison_shard_k < 1:
            raise ValueError(
                f"poison_shard_k must be >= 1, got {self.poison_shard_k}"
            )
        if self.producer_restarts < 0:
            raise ValueError(
                f"producer_restarts must be >= 0, got {self.producer_restarts}"
            )
        if self.rollout_workers and (
            self.kv_cache_quant not in (None, "none")
            or self.engine_impl != "dense"
        ):
            # remote workers build their own engines (worker_main flags);
            # silently ignoring these knobs would misreport memory behavior
            raise ValueError(
                "engine_impl/kv_cache_quant are local-engine knobs; with "
                "rollout_workers, configure the workers via worker_main flags"
            )
        if self.batch_size <= 0:
            raise ValueError("batch_size must be positive")
        if any(
            b <= 0 or b > self.max_new_tokens for b in self.learner_len_buckets
        ):
            # same contract as the engine's prompt buckets (engine.py raises
            # for out-of-range buckets): a bucket past max_new_tokens would
            # silently clamp into a no-op while logging answer_width as if
            # bucketing were active
            raise ValueError(
                f"learner_len_buckets must be in (0, max_new_tokens="
                f"{self.max_new_tokens}], got {self.learner_len_buckets}"
            )
        if any(
            b <= 0 or b > self.max_prompt_tokens
            for b in self.learner_prompt_buckets
        ):
            raise ValueError(
                f"learner_prompt_buckets must be in (0, max_prompt_tokens="
                f"{self.max_prompt_tokens}], got {self.learner_prompt_buckets}"
            )
        if self.number_of_learners <= 0:
            raise ValueError("need at least one learner")
        if self.number_of_actors < 0:
            raise ValueError("number_of_actors must be >= 0")
        # The flat flags are authoritative for role counts (the reference CLI
        # contract); a custom MeshConfig may only restate them, never override.
        default_mesh = MeshConfig()
        mesh_roles = (self.mesh.number_of_actors, self.mesh.number_of_learners)
        flat_roles = (self.number_of_actors, self.number_of_learners)
        default_roles = (default_mesh.number_of_actors, default_mesh.number_of_learners)
        if mesh_roles != default_roles and mesh_roles != flat_roles:
            raise ValueError(
                f"mesh role counts {mesh_roles} conflict with number_of_actors/"
                f"number_of_learners {flat_roles}; set the flat flags instead"
            )
        self.mesh = dataclasses.replace(
            self.mesh,
            number_of_actors=self.number_of_actors,
            number_of_learners=self.number_of_learners,
        )
        # --- self-healing runtime (ISSUE 14): per-controller dead-flag
        # policy — an EXPLICIT per-controller flag on a run shape that
        # cannot host the controller is a loud error; the --control master
        # arms only the applicable subset (armed_controllers()).
        if self.control_budget < 1:
            raise ValueError(
                f"control_budget must be >= 1, got {self.control_budget}"
            )
        if self.control_cooldown_steps < 0:
            raise ValueError(
                f"control_cooldown_steps must be >= 0, got "
                f"{self.control_cooldown_steps}"
            )
        if self.control_dwell_steps < 1:
            raise ValueError(
                f"control_dwell_steps must be >= 1, got "
                f"{self.control_dwell_steps}"
            )
        if self.control_lag_ms <= 0:
            raise ValueError(
                f"control_lag_ms must be > 0, got {self.control_lag_ms}"
            )
        if self.control_hbm and not self._hbm_controller_applicable():
            raise ValueError(
                "control_hbm shrinks the continuous-admission chain cap — "
                "requires a LOCAL engine_impl='paged' with "
                "continuous_admission (fleet runs arm it worker-side: "
                "worker_main --control-hbm)"
            )
        if self.control_shed and not self._shed_controller_applicable():
            raise ValueError(
                "control_shed throttles continuous admission against an "
                "SLO — requires continuous_admission plus slo_ttft_ms or "
                "slo_queue_wait_ms, on a local engine (fleet runs arm it "
                "worker-side: worker_main --control-shed)"
            )
        if self.control_staleness and not self.lineage:
            raise ValueError(
                "control_staleness steers on the lineage/policy_lag_ms "
                "distribution — requires --lineage (async mode), which "
                "produces that signal"
            )
        if self.control_worker_health and not (
            self.rollout_workers and self.worker_rejoin
        ):
            raise ValueError(
                "control_worker_health quarantines regressing workers and "
                "relies on the rejoin loop to re-admit them — requires "
                "rollout_workers with worker_rejoin"
            )
        # --- elastic fleet (ISSUE 20) ---------------------------------
        if (self.fleet_min or self.fleet_max) and not (
            1 <= self.fleet_min <= self.fleet_max
        ):
            raise ValueError(
                f"fleet bounds need 1 <= fleet_min <= fleet_max, got "
                f"[{self.fleet_min}, {self.fleet_max}]"
            )
        if self.control_autoscale and not self._autoscale_applicable():
            raise ValueError(
                "control_autoscale resizes a dynamic rollout pool — "
                "requires rollout_workers with worker_rejoin (cold joins "
                "ride the rejoin/resync path) and fleet_min/fleet_max "
                "bounds for the target-size actuator"
            )

    def _hbm_controller_applicable(self) -> bool:
        return bool(
            self.engine_impl == "paged"
            and self.continuous_admission
            and not self.rollout_workers
        )

    def _shed_controller_applicable(self) -> bool:
        return bool(
            self._hbm_controller_applicable()
            and (self.slo_ttft_ms is not None
                 or self.slo_queue_wait_ms is not None)
        )

    def _autoscale_applicable(self) -> bool:
        return bool(
            self.rollout_workers and self.worker_rejoin
            and self.fleet_max > 0
        )

    def armed_controllers(self) -> tuple[str, ...]:
        """Which ISSUE 14 controllers this run arms: the explicit
        per-controller flags, plus — under the --control master — every
        controller the run's shape supports. Explicit flags on unsupported
        shapes already raised in __post_init__."""
        armed: list[str] = []
        if self.control_hbm or (
            self.control and self._hbm_controller_applicable()
        ):
            armed.append("hbm")
        if self.control_shed or (
            self.control and self._shed_controller_applicable()
        ):
            armed.append("shed")
        if self.control_staleness or (self.control and self.lineage):
            armed.append("staleness")
        if self.control_worker_health or (
            self.control and self.rollout_workers and self.worker_rejoin
        ):
            armed.append("worker_health")
        if self.control_nan_rollback or self.control:
            armed.append("nan_rollback")
        # explicit-only (never under the --control master): resizing the
        # pool is a capacity decision — __post_init__ already rejected the
        # flag on shapes that cannot host it
        if self.control_autoscale:
            armed.append("autoscale")
        return tuple(armed)

    @property
    def max_seq_length(self) -> int:
        # reference: max_seq_length = prompt + new tokens (distributed_actor.py:25)
        return self.max_prompt_tokens + self.max_new_tokens

    @property
    def allowed_weight_lag(self) -> int:
        """How many optimizer steps the rollout-resident adapter may lag the
        learner before StaleWeightsError fires — derived from the rollout
        regime instead of the old hard-coded ``1 if async_rollout else 0``:
        sync serializes (0), pipelined overlaps exactly one step (1), async
        is bounded by the staleness policy (max_staleness)."""
        if self.rollout_mode == "sync":
            return 0
        if self.rollout_mode == "pipelined":
            return 1
        return max(self.max_staleness, 1)

    @property
    def run_directory(self) -> str:
        return f"run_{self.run_name}"

    def train_sampling(self) -> SamplingConfig:
        return SamplingConfig(
            max_tokens=self.max_new_tokens,
            temperature=self.temperature,
            top_p=0.95,  # reference hardcodes top_p=0.95 (distributed_actor.py:47)
            n=self.num_candidates,
            top_p_exact=self.top_p_exact,
        )

    def eval_sampling(self) -> SamplingConfig:
        # reference eval params at distributed_trainer.py:53–58
        return SamplingConfig(
            max_tokens=self.max_new_tokens,
            temperature=self.eval_temperature,
            top_p=self.eval_top_p,
            n=self.eval_n,
            top_p_exact=self.top_p_exact,
        )

    def to_flat_dict(self) -> dict[str, Any]:
        """The reference-shaped flat config dict (train_distributed.py:54–81),
        used for wandb config logging parity."""
        return {
            "run_name": self.run_name,
            "project_name": self.project_name,
            "lora_save_path": self.lora_save_path,
            "lr": self.lr,
            "max_prompt_tokens": self.max_prompt_tokens,
            "max_new_tokens": self.max_new_tokens,
            "episodes": self.episodes,
            "num_candidates": self.num_candidates,
            "batch_size": self.batch_size,
            "train_batch_size": self.train_batch_size,
            "temperature": self.temperature,
            "save_every": self.save_every,
            "eval_every": self.eval_every,
            "model": self.model,
            "dataset": self.dataset,
            "number_of_actors": self.number_of_actors,
            "number_of_learners": self.number_of_learners,
            "learner": self.learner,
            "use_vllm": False,  # TPU build: jit generation engine, not vLLM
            "max_lora_rank": self.max_lora_rank,
            "topk": self.topk,
            "learner_chunk_size": self.learner_chunk_size,
            "actor_gpu_usage": self.actor_gpu_usage,
            "learner_gpu_usage": self.learner_gpu_usage,
            "lora_alpha": self.lora_alpha,
            "lora_dropout": self.lora_dropout,
        }
