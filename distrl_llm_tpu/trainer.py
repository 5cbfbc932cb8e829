"""The driver: synchronous on-policy RL loop re-hosted on a TPU mesh.

TPU-native replacement for the reference Trainer (distributed_trainer.py:13–416
— SURVEY §3.2). The reference's mechanisms map as follows:

* **Rollout fan-out** (Ray actors + chunk dispatch, :178–200) → ONE sharded
  ``engine.generate`` call: the batch is laid out over the rollout mesh's dp
  axis and GSPMD parallelizes it. ``chunk_sizes`` is still computed for its
  validation/warning semantics (and exercised by the multi-process control
  plane), but on a single host no per-worker RPC exists.
* **Weight sync** (adapter file save/load every step, :346 / distributed_
  actor.py:150) → the learner's LoRA pytree is PASSED to the engine each
  round — device arrays, no filesystem. ``weight_version`` counts updates and
  the engine round records which version it sampled with (the race detector
  the reference lacks, SURVEY §5). ``write_adapter_file=True`` still exports
  the per-step artifact for compatibility.
* **Gradient merge** (CPU dicts through Ray, :308–342) → inside the pjit'd
  train step (learner/train_step.py); nothing to orchestrate here.
* **Metrics / timing**: exact reference names (:348–366, :412–415) through a
  pluggable sink (metrics.py).
* **Checkpointing**: Orbax {lora, opt_state, step, episode} with true resume
  (the reference is save-only, SURVEY §5).
"""

from __future__ import annotations

import concurrent.futures
import logging
import math
import os
import time
from functools import partial
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distrl_llm_tpu import obs as obs_mod, telemetry
from distrl_llm_tpu.checkpoint import CheckpointManager, save_adapter_file
from distrl_llm_tpu.config import SamplingConfig, TrainConfig
from distrl_llm_tpu.data import DictDataset
from distrl_llm_tpu.learner.optim import make_optimizer
from distrl_llm_tpu.learner.train_step import make_train_step, prepare_update_batch
from distrl_llm_tpu.metrics import MetricsSink, make_sink
from distrl_llm_tpu.models.lora import init_lora_params, lora_scale
from distrl_llm_tpu.ops.quant import default_group_size, quant_bits_for, quantize_params
from distrl_llm_tpu.parallel.mesh import RoleMeshes, build_role_meshes
from distrl_llm_tpu.rewards import (
    RewardComputer,
    make_reward_function,
    reward_function as parity_reward_function,
)
from distrl_llm_tpu.shaping import flatten_for_update, shape_rewards, topk_filter
from distrl_llm_tpu.tokenizer import decode_batch, encode_fixed
from distrl_llm_tpu.utils.chunking import chunk_sizes

log = logging.getLogger(__name__)

RewardFn = Callable[[Sequence[str], Sequence[str]], np.ndarray]


def engine_kwargs_from_config(config: TrainConfig) -> dict[str, Any]:
    """Engine-constructor kwargs derived from the config (paged-engine knobs:
    KV quant, continuous batching, speculative decoding, row cap). Module
    level so the config→engine wiring is unit-testable without a checkpoint."""
    kwargs: dict[str, Any] = {"kv_quant": config.kv_cache_quant}
    if config.decode_scan_chunk is not None:
        # every engine_impl hosts the chunked step (dense, paged wave +
        # refill, paged_sharded, and the speculative scheduler via
        # _spec_chunk_fn — chunk counts verify rounds there). An explicit
        # value — INCLUDING 0 — must reach the engine as a pin, so a
        # --decode_scan_chunk 0 A/B can never be retuned by a stored plan
        kwargs["scan_chunk"] = config.decode_scan_chunk
    if config.engine_impl == "paged":
        if config.continuous_batching:
            kwargs["scheduler"] = "refill"
            # prefix sharing / continuous admission (ISSUE 12): forwarded
            # only when set, so an unset config stays plan-DB-resolvable
            # at the engine (continuous_admission None = consult cb_mode)
            # and the empty-DB default remains byte-identical fixed batches
            if config.prefix_sharing:
                kwargs["prefix_sharing"] = True
            if config.continuous_admission:
                kwargs["continuous_admission"] = True
            # None = unpinned (engine default / plan-DB-resolvable); any
            # explicit value — INCLUDING spec_draft=0 and the default
            # spellings 'ngram'/'fused' — reaches the engine as a pin, so
            # a --spec_draft 0 A/B can never be retuned by a stored plan
            # (the decode_scan_chunk convention)
            if config.spec_draft is not None:
                kwargs["spec_draft"] = config.spec_draft
            if config.spec_ngram is not None:
                kwargs["spec_ngram"] = config.spec_ngram
            if config.spec_drafter is not None:
                kwargs["spec_drafter"] = config.spec_drafter
            if config.spec_verify is not None:
                kwargs["spec_verify"] = config.spec_verify
            if config.spec_adapt:
                kwargs["spec_adapt"] = True
            # tiered KV cache (ISSUE 18): None stays plan-DB-resolvable at
            # the engine; an explicit bool — INCLUDING False — pins past
            # any stored plan (the spec_draft convention). kv_spill is
            # explicit-only, never plan-resolved.
            if config.prefix_cache is not None:
                kwargs["prefix_cache"] = config.prefix_cache
            if config.kv_spill:
                kwargs["kv_spill"] = True
                if config.kv_spill_host_mb:
                    kwargs["kv_spill_host_mb"] = config.kv_spill_host_mb
    if config.max_concurrent_sequences and config.engine_impl != "paged_sharded":
        # the sharded engine admits whole dp-sharded waves; a row cap is the
        # per-replica engines' admission knob
        kwargs["max_concurrent_rows"] = config.max_concurrent_sequences
    if config.clip_ratio > 0.0:
        # behavior-logprob capture costs a per-step vocab logsumexp plus the
        # [B, n, T] f32 transport — only pay it when the clip objective needs it
        kwargs["capture_logprobs"] = True
    # autotune plan resolution (distrl_llm_tpu/autotune): only non-default
    # settings are forwarded, so the kwargs stay minimal and an engine built
    # from a default config keeps consulting the default plan-DB path
    if not config.autotune:
        kwargs["autotune"] = False
    if config.plan_db:
        kwargs["plan_db"] = config.plan_db
    return kwargs


def _env_turn_counts(candidates: list[dict]) -> list[int]:
    """Per-EPISODE turn counts from the provenance riding consumed batches.

    ``cand["turns"]`` nests group-major: one entry per trajectory (group),
    each a list over the group's candidate rows, each row the list of that
    episode's turn records — the episode count is the innermost length, NOT
    the row count (len(grp) is just ``num_candidates``)."""
    return [
        len(row or ())
        for c in candidates if "turns" in c
        for grp in c["turns"]
        for row in (grp or ())
    ]


class StaleWeightsError(RuntimeError):
    """The rollout mesh holds an adapter older than the learner's — the race
    the reference structurally prevents with its synchronous barrier and we
    detect with asserted weight-version counters (SURVEY §5 race detection)."""


class EngineHangError(RuntimeError):
    """A generation round exceeded ``generation_timeout_s`` — the hang
    detector matching the reference's ``ray.get(timeout=240)``
    (distributed_trainer.py:200). The trainer checkpoints before raising;
    restart with ``resume=True`` to continue from the last completed step."""


class Trainer:
    """Owns the episode/batch loop. Heavy pieces (tokenizer, base params,
    engine, meshes) are injectable so the loop tests with fakes (SURVEY §4
    "FakeEngine") and assembles itself for real runs via ``from_pretrained``.
    """

    def __init__(
        self,
        train_dataset,
        test_dataset,
        reward_function: RewardFn,
        config: TrainConfig,
        *,
        tokenizer,
        engine,
        base_params,
        model_cfg,
        meshes: RoleMeshes | None = None,
        base_params_learner=None,
        sink: MetricsSink | None = None,
        reward_computer: RewardComputer | None = None,
    ):
        self.config = config
        self.train_dataset = DictDataset.wrap(train_dataset)
        self.test_dataset = DictDataset.wrap(test_dataset)
        self.tokenizer = tokenizer
        self.engine = engine
        self.base_params = base_params
        # the learner's copy of the frozen base: resident on the learner
        # submesh so the train step never touches rollout devices (the
        # reference's per-worker model load, distributed_actor.py:58); with
        # timeshared roles both names alias one tree.
        self.base_params_learner = (
            base_params_learner if base_params_learner is not None else base_params
        )
        self.model_cfg = model_cfg
        self.meshes = meshes
        self.sink = sink
        # format-reward gate (ISSUE 17 satellite): "strict" swaps the
        # previously-dead strict newline-delimited scorer into the (N, 2)
        # contract. Only the parity default is substitutable — a custom fn
        # plus a non-default gate is ambiguous (which one wins?), refuse.
        if config.format_reward != "soft":
            if reward_function is not parity_reward_function:
                raise ValueError(
                    "format_reward != 'soft' with a custom reward_function "
                    "is ambiguous — encode the gate inside the custom fn, "
                    "or drop one of the two"
                )
            reward_function = make_reward_function(config.format_reward)
        # the computer evaluates THIS trainer's reward_function (a custom fn
        # passed positionally — the reference contract — must actually run).
        # An explicit reward_computer carries parallelism config; the fn is
        # passed per call so a computer shared across Trainers is never
        # mutated. A computer EXPLICITLY built with a different fn than the
        # trainer's is ambiguous — refuse.
        if reward_computer is None:
            reward_computer = RewardComputer(reward_fn=reward_function)
        elif (
            reward_computer.fn_explicit
            and reward_computer.reward_fn is not reward_function
        ):
            raise ValueError(
                "reward_computer was built with a different reward_fn than "
                "the one passed to Trainer — pass the fn in exactly one place"
            )
        self.rewards = reward_computer
        self._reward_fn = reward_function

        # pluggable environments (ISSUE 17): a multi-turn env arms the
        # engine's turn hook per round — finished turns step the env and
        # continuing conversations resume on their resident KV chains.
        # env="math" routes the exact legacy single-turn path (no driver,
        # byte-identical losses and checksums).
        self._env_driver: Any = None
        if config.env != "math":
            from distrl_llm_tpu.env import EnvRolloutDriver

            if not hasattr(engine, "turn_hook"):
                raise ValueError(
                    f"env={config.env!r} needs an engine with a turn_hook "
                    "(the local paged refill engine); "
                    f"{type(engine).__name__} has none"
                )
            self._env_driver = EnvRolloutDriver(
                config.env, tokenizer,
                max_turns=config.max_turns,
                max_new_tokens=config.max_new_tokens,
                format_scorer=config.format_reward,
            )

        # multi-tenant serving gateway (ISSUE 19): built lazily at the top
        # of train() — it serves WHILE training runs, and its rounds share
        # the engine with rollout generation through _engine_mutex
        self._gateway_service: Any = None
        self._gateway_server: Any = None
        self._engine_mutex: Any = None
        if config.gateway_port is not None and not getattr(
            engine, "continuous_admission", False
        ):
            raise ValueError(
                "gateway_port needs a local continuous-admission paged "
                f"engine; {type(engine).__name__} has no request-queue "
                "admission plane"
            )

        # the silent-no-op fix (ISSUE 9): inflight_weight_updates with an
        # engine that cannot actually swap mid-round used to pretend to
        # work (the push was a getattr that quietly found nothing). Any
        # engine that still lacks a real push_lora is rejected HERE, so
        # the combination can never silently regress again. Local engines
        # inherit push_lora from LoraMailbox; RemoteEngine advertises
        # supports_inflight_push only in broadcast-bus mode.
        if config.inflight_weight_updates:
            push = getattr(engine, "push_lora", None)
            if not callable(push) or not getattr(
                engine, "supports_inflight_push", callable(push)
            ):
                raise ValueError(
                    "inflight_weight_updates requires an engine with a real "
                    f"push_lora (in-flight weight-update mailbox); "
                    f"{type(engine).__name__} cannot swap a round in flight "
                    "— use a local engine or a RemoteEngine with "
                    "weight_bus='broadcast'"
                )

        # chunk-composition validation parity (distributed_trainer.py:34–36)
        assert config.number_of_learners > 0, "Need at least one learner"
        chunk_sizes(
            config.batch_size,
            config.number_of_actors,
            config.number_of_learners,
            config.learner_chunk_size,
        )

        self.scale = lora_scale(config.max_lora_rank, config.lora_alpha)
        import threading as _threading

        self._rng = jax.random.PRNGKey(config.seed)
        self._rng_mu = _threading.Lock()
        self._rng, lora_key = jax.random.split(self._rng)
        if config.full_finetune:
            # reference recipe 3 (bf16 full-rank, no 4-bit): the WHOLE param
            # tree is the trainable state; there is no adapter. self.lora
            # holds whichever tree trains — the engine call sites and weight
            # push branch on _full below. The trainable copy is kept in f32
            # (master weights): with lr=2e-5 a typical update is below bf16's
            # ~0.4% relative resolution, so bf16 apply_updates would round
            # many steps to no-ops; _push_weights casts back down for rollout.
            from distrl_llm_tpu.ops.quant import is_quantized_tree

            if is_quantized_tree(self.base_params_learner):
                raise ValueError("full_finetune requires an unquantized base")
            self._rollout_dtype = jax.tree_util.tree_leaves(
                self.base_params_learner
            )[0].dtype  # rollout samples at the base's dtype (bf16 on TPU)
            self.lora = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), self.base_params_learner
            )
            # the donating train step deletes self.lora's buffers each step;
            # keeping base_params* pointing anywhere would leave stale
            # references whose reads fail far from the cause — full mode has
            # no frozen base, make that explicit
            self.base_params = None
            self.base_params_learner = None
        else:
            self.lora = init_lora_params(
                lora_key, model_cfg, config.max_lora_rank,
                dtype=jnp.float32,  # adapters train in f32; base stays bf16
            )
        self._full = config.full_finetune
        self.optimizer = make_optimizer(config.lr, use_8bit=config.optimizer_8bit)
        self.opt_state = self.optimizer.init(self.lora)
        if meshes is not None:
            # adapter + optimizer state are LEARNER-mesh residents with
            # explicit shardings (FSDP sharding of learner state, SURVEY §2c)
            from distrl_llm_tpu.parallel.partition import shard_opt_state, shard_tree

            # shard_tree derives the right specs for either tree shape
            # (param_specs handles LoRA and full param trees alike)
            self.lora = shard_tree(self.lora, meshes.learner)
            self.opt_state = shard_opt_state(self.opt_state, meshes.learner)
        self.train_step = make_train_step(
            model_cfg,
            learner_type=config.learner,
            optimizer=self.optimizer,
            lora_scale=self.scale,
            micro_size=config.train_batch_size,
            skip_semantics=(
                "all_zero" if config.skip_all_zero_reward_batches else "any_zero"
            ),
            attn_impl=config.attn_impl,
            attn_mesh=meshes.learner if (
                config.attn_impl in ("ring", "ulysses") and meshes is not None
            ) else None,
            lora_dropout=config.lora_dropout,
            logit_chunk=config.logprob_chunk,
            train_mode="full" if self._full else "lora",
            clip_ratio=config.clip_ratio,
            kl_coeff=config.kl_coeff,
            # async trains on data up to max_staleness steps old — the
            # truncated-IS objective (AIPO) with per-token version-lag
            # masking replaces the near-on-policy 1±ε clip. The mask is
            # DROP-mode semantics (trim the stale tokens of admitted
            # mixed-version groups); under the downweight policy it must
            # stay off (0) — the fade deliberately trains beyond-K tokens
            # at reduced weight, and masking them would silently turn
            # downweight back into drop
            off_policy="aipo" if config.rollout_mode == "async" else "clip",
            is_cap=config.rollout_is_cap,
            max_staleness=(
                config.max_staleness
                if config.staleness_policy == "drop" else 0
            ),
            # training-dynamics bundle (ISSUE 16): computed inside the
            # jitted step and returned through the existing aux pytree —
            # it rides the one host fetch the loss already pays
            emit_dynamics=config.learn_obs,
        )

        self.total_batch_steps = 0
        self.total_samples_processed = 0
        self.episode = 0
        self.batch_in_episode = 0  # mid-episode resume cursor (SURVEY §5)
        self.weight_version = 0  # incremented per optimizer step
        self._rollout_weight_version = -1  # version resident on the rollout mesh
        # (role, bucket, rows, n) executables seen — cold ones are exempt
        # from the generation hang detector (compile is slow, not hung)
        self._warm_engine_keys: set[tuple] = set()

        self._last_hf_export_step = -1
        if config.export_hf_snapshots and not config.run_name:
            log.warning(
                "export_hf_snapshots is set but run_name is not — no "
                "snapshots will be written (run_dir is derived from run_name)"
            )

        self.profiler = None
        if config.profile_dir:
            from distrl_llm_tpu.metrics import TraceProfiler

            self.profiler = TraceProfiler(
                config.profile_dir,
                start_step=config.profile_start_step,
                num_steps=config.profile_num_steps,
            )

        # span tracing (telemetry.py): enabled here so directly-driven
        # rounds (tests, tools) record too, not just train(); the trace is
        # exported when the trace_steps window closes or at shutdown
        self._trace_steps_done = 0
        if config.trace_dir:
            telemetry.configure(enabled=True)
        # MFU denominator: one chip's peak FLOP/s, when the hardware is
        # known (telemetry table / DISTRL_PEAK_FLOPS); None suppresses the
        # engine/mfu series rather than publishing a made-up number.
        # decode_tok_s is WHOLE-ENGINE throughput, so MFU divides it by the
        # rollout chip count first —
        # otherwise an 8-chip mesh reports ~8× the true utilisation
        self._peak_flops = telemetry.device_peak_flops()
        self._rollout_chips = (
            int(meshes.rollout.devices.size) if meshes is not None else 1
        )

        # continuous observability plane (distrl_llm_tpu/obs.py, ISSUE 8):
        # live metrics endpoint + fleet aggregation (remote rollout), HBM
        # sampling at phase boundaries, and the anomaly sentinel / flight
        # recorder. None unless a flag armed it — the step loop then pays
        # exactly one attribute check.
        self.obs: Any = None
        if (
            config.metrics_port is not None
            or config.sentinel
            or config.flight_recorder_dir
        ):
            self.obs = obs_mod.ObsPlane(
                metrics_port=config.metrics_port,
                sentinel=config.sentinel,
                flight_recorder_dir=config.flight_recorder_dir,
                ring_size=config.obs_ring_size,
                # fleet aggregation needs the control plane: local-engine
                # runs expose their own registry, nothing to aggregate
                driver=(
                    getattr(engine, "driver", None)
                    if getattr(engine, "is_remote", False) else None
                ),
                profiler=self.profiler,
                staleness_limit=(
                    config.max_staleness
                    if config.rollout_mode == "async" else None
                ),
                # serving SLO gates (ISSUE 13): arm the ttft_blowup /
                # queue_wait_blowup sentinel triggers
                slo_ttft_ms=config.slo_ttft_ms,
                slo_queue_wait_ms=config.slo_queue_wait_ms,
                # training-dynamics gates (ISSUE 16): arm the
                # entropy_collapse / kl_blowup / ratio_saturation /
                # grad_spike triggers over the learn/* bundle
                learn_entropy_floor=config.learn_entropy_floor,
                learn_kl_limit=config.learn_kl_limit,
                learn_ratio_sat_frac=config.learn_ratio_sat_frac,
                learn_grad_spike=config.learn_grad_spike,
                config_snapshot=config.to_flat_dict(),
                plan_provider=lambda: (
                    self.engine.resolved_plan.plan.to_dict()
                    if getattr(self.engine, "resolved_plan", None) else None
                ),
            )

        # trajectory lineage ledger (distrl_llm_tpu/lineage.py, ISSUE 10):
        # per-group causal records (sampling worker + dispatch_id → buffer
        # → staleness verdict → consuming optimizer step → produced weight
        # version) and the derived policy-lag histograms. None unless
        # --lineage armed it; every hook below is one attribute check.
        self.lineage: Any = None
        if config.lineage:
            from distrl_llm_tpu.lineage import LineageLedger

            self.lineage = LineageLedger(
                ring_size=config.lineage_ring, out_dir=config.lineage_dir
            )
            bus = getattr(engine, "bus", None)
            if bus is not None:
                # the policy-lag loop closes at the LAST WORKER ACK of the
                # produced version (PR 9's broadcast), not the local push
                self.lineage.expect_acks = True
                bus.on_broadcast = self.lineage.on_broadcast_complete

        # training-dynamics ledger (distrl_llm_tpu/learn_obs.py, ISSUE 16):
        # host half of the device-fused bundle the armed train step returns
        # — publishes learn/* registry series, tracks reward drift, streams
        # the per-step JSONL. None unless --learn_obs armed it; the step
        # loop's hook is one attribute check when off.
        self.learn: Any = None
        self._last_dynamics: Any = None
        if config.learn_obs:
            from distrl_llm_tpu.learn_obs import LearnLedger

            self.learn = LearnLedger(
                out_dir=config.learn_dir,
                drift_window=config.learn_drift_window,
            )

        # request-level serving ledger (distrl_llm_tpu/serving_obs.py,
        # ISSUE 13): per-group lifecycle + admission audit recorded by the
        # paged engine's refill/continuous loops. None unless
        # --serving_obs armed it; the engine then pays one attribute
        # check per hook site. Config validation guarantees a local paged
        # continuous-batching engine here (fleet runs arm worker-side).
        self.serving: Any = None
        if config.serving_obs:
            from distrl_llm_tpu.serving_obs import ServingLedger

            self.serving = ServingLedger(
                ring_size=config.serving_ring, out_dir=config.serving_dir
            )
            if hasattr(engine, "serving_ledger"):
                engine.serving_ledger = self.serving
            else:
                log.warning(
                    "serving_obs armed but engine %s has no "
                    "serving_ledger hook — nothing will be recorded "
                    "(remote fleets arm worker_main --serving-obs)",
                    type(engine).__name__,
                )

        # self-healing control runtime (distrl_llm_tpu/control/, ISSUE 14):
        # bounded governors acting on the signals the obs plane measures.
        # None unless a --control flag armed one; a run with controllers
        # off is byte-identical to HEAD (the engine hook is a None check).
        self.control: Any = None
        if config.armed_controllers():
            from distrl_llm_tpu.control import build_runtime, injected_nan_step

            self.control = build_runtime(
                config,
                engine=engine,
                recorder=(
                    self.obs.recorder if self.obs is not None else None
                ),
                driver=(
                    getattr(engine, "driver", None)
                    if getattr(engine, "is_remote", False) else None
                ),
                fleet_provider=(
                    self.obs.fleet.refresh
                    if self.obs is not None and self.obs.fleet is not None
                    else None
                ),
                # elastic fleet (ISSUE 20): the launcher attaches the
                # FleetSupervisor to the remote engine; the autoscaling
                # governor actuates the pool through it
                fleet_supervisor=getattr(engine, "fleet_supervisor", None),
            )
            if (
                self.control is not None and self.obs is not None
                and self.obs.sentinel is not None
            ):
                # trigger → action escalation: a fired sentinel trigger
                # reaches its governor exactly once; triggers without a
                # registered governor stay dump-only (the PR 8 contract)
                self.obs.sentinel.on_trigger = self.control.on_trigger
        # seeded chaos hook for the rollback gate (control_smoke): poison
        # the REALIZED loss at the named step — honored only with the
        # rollback controller armed, so the env can never corrupt a
        # controller-less run
        self._inject_nan_step = (
            injected_nan_step()
            if self.control is not None and self.control.nan is not None
            else None
        )

        self.ckpt: CheckpointManager | None = None
        if config.checkpoint_dir:
            self.ckpt = CheckpointManager(config.checkpoint_dir)
            if config.resume:
                self._try_resume()
        self._push_weights()
        if self.control is not None and self.control.nan is not None:
            # the pre-step state is the first "last good" snapshot: a nan
            # on the very first optimizer step rolls back to initialization
            self.control.nan.note_good(
                self.weight_version, self.lora, self.opt_state
            )

    # ------------------------------------------------------------------ setup

    @classmethod
    def from_pretrained(
        cls,
        train_dataset,
        test_dataset,
        reward_function: RewardFn,
        config: TrainConfig,
        *,
        checkpoint_path: str | None = None,
        tokenizer=None,
        sink: MetricsSink | None = None,
    ) -> "Trainer":
        """Assemble the real thing: tokenizer + HF weights + sharded engine.

        ``checkpoint_path`` is a local HF checkpoint directory; when None the
        model id must resolve to a local path. (The reference's from_pretrained
        pulls from the hub — distributed_actor.py:58; this environment has no
        egress, so weights must be on disk.) Pass ``tokenizer`` if the caller
        already loaded it (the CLI does, for dataset templating).
        """
        from distrl_llm_tpu.engine.engine import GenerationEngine
        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
        from distrl_llm_tpu.models.loading import load_pretrained
        from distrl_llm_tpu.parallel.partition import param_specs, shard_tree
        from distrl_llm_tpu.tokenizer import load_tokenizer

        path = checkpoint_path or config.model
        if tokenizer is None:
            tokenizer = load_tokenizer(path)
        meshes = build_role_meshes(config.mesh)
        params, model_cfg = load_pretrained(path, dtype=np.dtype(config.dtype))
        bits = quant_bits_for(config.base_quant)
        if bits is not None:
            # N4 equivalent of the reference's 4-bit base (LOAD_IN_4BIT,
            # distributed_actor.py:17): quantize the frozen projections before
            # sharding so shards ship at int width
            params = quantize_params(
                params, bits=bits,
                group_size=config.quant_group_size or default_group_size(bits),
            )
        specs = param_specs(params)
        eos = [tokenizer.eos_token_id]
        extra_eos = getattr(tokenizer, "eos_token_ids", None)
        if extra_eos:
            eos = sorted(set(eos) | set(extra_eos))
        if config.rollout_workers:
            # generation runs in worker processes: the local mesh serves the
            # LEARNER only — no rollout-mesh base copy, no per-step adapter
            # push (the adapter ships over the wire instead)
            from distrl_llm_tpu.distributed import connect_remote_engine

            params_learner = shard_tree(params, meshes.learner, specs)
            params_rollout = params_learner
            if config.number_of_actors > 0 and not meshes.timeshared:
                log.warning(
                    "rollout_workers is set but number_of_actors=%d local "
                    "chips are carved for a rollout mesh that never "
                    "generates; consider --number_of_actors 0",
                    config.number_of_actors,
                )
            addresses = []
            for spec in config.rollout_workers:
                host, _, port = spec.rpartition(":")
                addresses.append((host or "127.0.0.1", int(port)))
            from distrl_llm_tpu.distributed.resilience import RetryPolicy

            engine = connect_remote_engine(
                addresses,
                max_prompt_tokens=config.max_prompt_tokens,
                max_new_tokens=config.max_new_tokens,
                # generation_timeout_s <= 0 means "hang detector disabled";
                # the control plane still needs SOME deadline — use a day
                timeout_ms=(
                    int(config.generation_timeout_s * 1000)
                    if config.generation_timeout_s > 0 else 86_400_000
                ),
                lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
                eos_token_ids=eos,
                # control-plane resilience (distributed/resilience.py):
                # seeded per-run so retry/reconnect backoff replays
                retry_policy=RetryPolicy(
                    max_call_retries=config.rpc_retries,
                    base_s=config.rpc_backoff_s,
                    seed=config.seed,
                ),
                poison_threshold=config.poison_shard_k,
                rejoin=config.worker_rejoin,
                degrade_on_shard_failure=config.degrade_on_poison,
                # versioned weight bus (ISSUE 9): broadcast = one delta
                # push per optimizer step, dispatches carry only a
                # version reference; dispatch = legacy weights-in-request
                weight_bus=config.weight_bus,
            )
            if "autoscale" in config.armed_controllers():
                # elastic fleet (ISSUE 20): the supervisor adopts the
                # connected workers (it can drain-retire them but not
                # respawn them) and spawns OWNED workers for any scale-up
                # past this set; the autoscaling governor finds it through
                # engine.fleet_supervisor at build_runtime time
                from distrl_llm_tpu.distributed.fleet import (
                    FleetSupervisor, spec_from_config,
                )

                supervisor = FleetSupervisor(
                    spec_from_config(config),
                    min_workers=config.fleet_min,
                    max_workers=config.fleet_max,
                )
                supervisor.adopt(addresses)
                supervisor.attach(engine)
        else:
            if config.full_finetune and not meshes.timeshared:
                # full mode never reads a frozen base on the rollout mesh —
                # _push_weights places the TRAINED tree there each step, so a
                # resident base copy would just double rollout-mesh HBM in
                # exactly the memory-tight config
                params_learner = shard_tree(params, meshes.learner, specs)
                params_rollout = None
            else:
                params_rollout = shard_tree(params, meshes.rollout, specs)
                # non-timeshared roles each hold the frozen base (the
                # reference loads the model once per worker,
                # distributed_actor.py:58); timeshared roles alias one copy
                params_learner = (
                    params_rollout if meshes.timeshared
                    else shard_tree(params, meshes.learner, specs)
                )
            engine_cls = (
                PagedGenerationEngine if config.engine_impl == "paged"
                else GenerationEngine
            )
            engine_kwargs = engine_kwargs_from_config(config)
            if config.engine_impl == "paged_sharded":
                # one paged engine, page pool partitioned over the rollout
                # mesh's dp axis (engine/sharded_paged.py)
                from distrl_llm_tpu.engine.sharded_paged import ShardedPagedEngine

                engine_cls = partial(ShardedPagedEngine, mesh=meshes.rollout)
            if config.engine_impl == "paged":
                # --actor_gpu_usage → KV page budget (the reference's vLLM
                # gpu_memory_utilization contract, train_distributed.py:34-35)
                from distrl_llm_tpu.engine.budget import kv_pool_pages, tree_bytes
                from distrl_llm_tpu.ops.paged import DEFAULT_PAGE_SIZE

                # timeshared roles = the reference's LEARNER GPU (training
                # state shares the chip with the engine → the 0.35 fraction);
                # disjoint rollout meshes = its ACTOR GPUs (0.91)
                usage = (
                    config.learner_gpu_usage if meshes.timeshared
                    else config.actor_gpu_usage
                )
                engine_kwargs["max_kv_pages"] = kv_pool_pages(
                    model_cfg,
                    gpu_usage=usage,
                    param_bytes=tree_bytes(params),
                    batch_prompts=config.batch_size,
                    max_prompt_tokens=config.max_prompt_tokens,
                    max_new_tokens=config.max_new_tokens,
                    page_size=DEFAULT_PAGE_SIZE,
                    # pool sizing sees only the EXPLICIT format (the
                    # spec_draft convention): a plan-DB entry resolving
                    # int8 KV at engine construction leaves the pool sized
                    # for the larger bf16 pages — slack, never an OOM
                    kv_quant=config.kv_cache_quant or "none",
                    # pool sizing sees only the EXPLICIT draft length; a
                    # plan-DB entry that enables speculation (spec_draft
                    # None) isn't resolved until engine construction, so
                    # its ≤d extra resident tokens/row ride the pool's
                    # refill-admission slack instead
                    spec_draft=(
                        (config.spec_draft or 0)
                        if config.continuous_batching else 0
                    ),
                    # continuous admission allocates prompt chains FROM the
                    # pool (no static region to subtract); only the
                    # EXPLICIT config flag is visible here — a plan-DB
                    # entry resolving continuous at engine construction
                    # surfaces as the engine's pool-floor error, naming
                    # the pin to set
                    continuous=config.continuous_admission,
                    # only the EXPLICIT flag bumps the floor (same rule):
                    # a plan-resolved cache rides the refill slack instead
                    prefix_cache=bool(config.prefix_cache),
                    # a slot's row states come off the budget before pages
                    slots=config.max_concurrent_sequences or 0,
                )
            engine = engine_cls(
                model_cfg,
                max_prompt_tokens=config.max_prompt_tokens,
                max_new_tokens=config.max_new_tokens,
                eos_token_ids=eos,
                pad_token_id=(
                    tokenizer.pad_token_id
                    if tokenizer.pad_token_id is not None
                    else tokenizer.eos_token_id
                ),
                lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
                attn_impl=config.attn_impl,
                prompt_buckets=config.prompt_buckets or None,
                **engine_kwargs,
            )
        return cls(
            train_dataset, test_dataset, reward_function, config,
            tokenizer=tokenizer, engine=engine, base_params=params_rollout,
            base_params_learner=params_learner,
            model_cfg=model_cfg, meshes=meshes, sink=sink,
        )

    # ------------------------------------------------------------- checkpoint

    def _state_tree(self) -> dict:
        return {
            "lora": self.lora,
            "opt_state": self.opt_state,
            "step": jnp.asarray(self.total_batch_steps),
            "episode": jnp.asarray(self.episode),
            "batch_in_episode": jnp.asarray(self.batch_in_episode),
            "samples": jnp.asarray(self.total_samples_processed),
            "rng": self._rng,
        }

    def _try_resume(self) -> None:
        assert self.ckpt is not None
        restored = self.ckpt.restore(self._state_tree())
        if restored is None:
            return
        self.lora = restored["lora"]
        self.opt_state = restored["opt_state"]
        from distrl_llm_tpu.learner.optim import check_state_format

        check_state_format(self.opt_state)
        if self.meshes is not None:
            from distrl_llm_tpu.parallel.partition import shard_opt_state, shard_tree

            self.lora = shard_tree(self.lora, self.meshes.learner)
            self.opt_state = shard_opt_state(self.opt_state, self.meshes.learner)
        self.total_batch_steps = int(restored["step"])
        self.episode = int(restored["episode"])
        self.batch_in_episode = int(restored.get("batch_in_episode", 0))
        self.total_samples_processed = int(restored["samples"])
        self._rng = restored["rng"]
        self.weight_version = self.total_batch_steps
        if self.config.rollout_mode == "async":
            from distrl_llm_tpu.checkpoint import load_rollout_state

            # buffered-but-unconsumed trajectories + producer cursor;
            # absent/corrupt sidecar degrades to a fresh buffer
            self._resume_rollout_state = load_rollout_state(
                self.config.checkpoint_dir, self.total_batch_steps
            )
        log.info(
            "resumed from step %d (episode %d, batch %d)",
            self.total_batch_steps, self.episode, self.batch_in_episode,
        )

    def save_checkpoint(self) -> None:
        if self.ckpt is None:
            return
        self.ckpt.save(self.total_batch_steps, self._state_tree())
        buffer = getattr(self, "_rollout_buffer", None)
        if buffer is not None:
            # async regime: the in-flight state (queued trajectories + the
            # producer's episode/batch cursor) rides as a pickle sidecar
            # keyed by the same step, so resume neither loses nor
            # re-generates buffered data
            from distrl_llm_tpu.checkpoint import save_rollout_state

            service = getattr(self, "_rollout_service", None)
            # cursor BEFORE the buffer snapshot: if the producer lands a
            # round between the two reads, the stale cursor re-produces
            # that batch on resume (benign duplicates); the other order
            # could pair a pre-put snapshot with an advanced cursor and
            # LOSE the round's tail
            cursor = service.cursor if service is not None else None
            policy = getattr(self, "_staleness_policy", None)
            save_rollout_state(
                self.config.checkpoint_dir, self.total_batch_steps, {
                    "buffer": buffer.state_dict(),
                    "cursor": cursor,
                    # admission counters ride along so the cumulative
                    # rollout_dropped_stale series never goes BACKWARDS
                    # across a resume (dashboards join on it)
                    "policy_dropped": policy.dropped if policy else 0,
                    "policy_admitted": policy.admitted if policy else 0,
                },
            )

    def export_hf_snapshot(self) -> None:
        """The reference's ``save_pretrained`` artifact: an HF-format
        checkpoint of the MERGED model at run_dir/model_{step}
        (distributed_trainer.py:372–380). On multi-process runs every process
        joins a ``multihost_utils.process_allgather`` pass (each host's
        shards may be non-addressable elsewhere, so the gather is a
        collective all processes MUST enter), then process 0 alone writes —
        write-race-free and byte-identical to the single-host artifact."""
        if self.total_batch_steps == self._last_hf_export_step:
            return  # episode end landing on a save_every step: already written
        from distrl_llm_tpu.models.loading import save_hf_checkpoint

        trained, base = self.lora, None if self._full else self.base_params_learner
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            def gather(tree):
                return jax.tree_util.tree_map(
                    lambda x: (
                        multihost_utils.process_allgather(x, tiled=True)
                        if isinstance(x, jax.Array) else np.asarray(x)
                    ),
                    tree,
                )

            trained = gather(trained)
            base = gather(base) if base is not None else None
            if jax.process_index() != 0:
                self._last_hf_export_step = self.total_batch_steps
                return
        path = os.path.join(
            self.config.run_directory, f"model_{self.total_batch_steps}"
        )
        try:
            if self._full:
                save_hf_checkpoint(
                    trained, self.model_cfg, path,
                    model_type=self.model_cfg.model_type,
                )
            else:
                save_hf_checkpoint(
                    base, self.model_cfg, path,
                    lora=trained, lora_alpha=self.config.lora_alpha,
                    model_type=self.model_cfg.model_type,
                )
            self._last_hf_export_step = self.total_batch_steps
        except (NotImplementedError, RuntimeError) as e:  # quantized base /
            # non-addressable shards: skip rather than kill the run
            log.warning("HF snapshot skipped: %s", e)

    def save_adapter(self) -> None:
        """The reference's per-step adapter artifact (distributed_trainer.py:346
        → save_lora). Export-only here — weight sync is in-memory."""
        if self._full:
            raise RuntimeError("full_finetune has no LoRA adapter to export")
        save_adapter_file(
            self.lora, self.config.lora_save_path,
            rank=self.config.max_lora_rank, alpha=self.config.lora_alpha,
            model_name=self.config.model,
        )

    # ------------------------------------------------------------ weight sync

    def _push_weights(self) -> None:
        """Learner→rollout weight sync: a device-to-device transfer of the LoRA
        pytree onto the rollout submesh, replacing the reference's adapter-file
        bus (save_lora distributed_actor.py:85 / load_lora :150). Records the
        version now resident on the rollout mesh; ``_generate_round`` asserts
        it before sampling."""
        with telemetry.span(
            telemetry.DRIVER_PUSH, version=self.weight_version
        ) as push_span:
            pushed = self.lora
            if self._full:
                # master weights train in f32; rollout samples at the base dtype
                pushed = jax.tree_util.tree_map(
                    lambda x: x.astype(self._rollout_dtype), pushed
                )
            if self.config.async_rollout:
                # the train step DONATES self.lora's buffers; in the overlap
                # window the next batch's generation still reads the pushed tree,
                # so it must own its buffers (same-device/same-dtype paths would
                # otherwise alias the donated arrays → "buffer deleted" crashes)
                pushed = jax.tree_util.tree_map(jnp.copy, pushed)
            if getattr(self.engine, "is_remote", False):
                # remote rollout: the adapter ships over the wire — either once
                # per version on the broadcast bus (below) or inside each
                # round's dispatch payloads — no local rollout-mesh copy
                self._lora_rollout = pushed
                push_span.set(mode="remote")
                if getattr(self.engine, "bus", None) is not None:
                    # versioned weight bus (ISSUE 9): ONE asynchronous push per
                    # optimizer step; subsequent dispatches reference it as
                    # {weight_version} and mid-round swaps ride the same push
                    # when inflight_weight_updates is on
                    self.engine.push_lora(pushed, version=self.weight_version)
            elif self.meshes is not None and not self.meshes.timeshared:
                from distrl_llm_tpu.parallel.partition import shard_tree

                self._lora_rollout = shard_tree(pushed, self.meshes.rollout)
                push_span.set(mode="split")
            else:
                self._lora_rollout = pushed
                push_span.set(mode="timeshared")
            self._rollout_weight_version = self.weight_version
            if self._gateway_service is not None:
                # the gateway serves the freshest pushed policy: attribute
                # swap only — a round already being formed finishes on the
                # previous tree (one-round staleness, same as rollout)
                gw_params, gw_lora = self._engine_params("rollout")
                self._gateway_service.params = gw_params
                self._gateway_service.lora = gw_lora
            if self.lineage is not None:
                # weight-version lineage: push time opens the learn-to-act
                # window; with a broadcast bus the policy-lag loop stays open
                # until on_broadcast_complete (the bus hook), locally it closes
                # here — the pushed tree IS resident when this returns
                self.lineage.on_push(self.weight_version)

    # ---------------------------------------------------------------- gateway

    def _start_gateway(self) -> None:
        """Serve the rollout engine over HTTP while training runs
        (ISSUE 19). The service forms class-ordered rounds between the
        trainer's own generation rounds — _engine_mutex serializes the
        two owners — and records into the already-attached serving
        ledger/control limits (it only overrides what it was given)."""
        cfg = self.config
        if cfg.gateway_port is None or self._gateway_service is not None:
            return
        import threading as _threading

        from distrl_llm_tpu.gateway.scheduler import (
            parse_gateway_classes,
            parse_tenant_quota,
        )
        from distrl_llm_tpu.gateway.server import GatewayServer
        from distrl_llm_tpu.gateway.service import GatewayService

        self._engine_mutex = _threading.Lock()
        params, lora = self._engine_params("rollout")
        self._gateway_service = GatewayService(
            self.engine, params, self.tokenizer, lora=lora,
            classes=parse_gateway_classes(cfg.gateway_classes),
            quota=parse_tenant_quota(cfg.tenant_quota),
            max_groups_per_round=max(1, cfg.max_concurrent_sequences or 8),
            seed=cfg.seed,
            engine_lock=self._engine_mutex,
        ).start()
        self._gateway_server = GatewayServer(
            self._gateway_service, port=cfg.gateway_port
        )
        log.info(
            "serving gateway listening on 127.0.0.1:%d (classes %s)",
            self._gateway_server.port, self._gateway_service.classes,
        )

    def _close_gateway(self) -> None:
        if self._gateway_server is not None:
            self._gateway_server.close()
            self._gateway_server = None
        if self._gateway_service is not None:
            self._gateway_service.close()
            self._gateway_service = None
        self._engine_mutex = None

    # ---------------------------------------------------------------- rollout

    def _next_rng(self) -> jax.Array:
        # async_rollout draws keys from the generation thread while the main
        # thread draws dropout keys — serialize the split
        with self._rng_mu:
            self._rng, key = jax.random.split(self._rng)
            return key

    def _dispatch_rollout(
        self, prompt_ids, prompt_mask, sampling: SamplingConfig, n_real: int
    ):
        """Run one generation round over every role's chips.

        Hybrid learner-generation (README.md:19; dispatch at
        distributed_trainer.py:194–197): with disjoint role submeshes, the
        batch splits by ``chunk_sizes`` — the actors' share decodes on the
        rollout mesh while the learners' ``learner_chunk_size`` share decodes
        CONCURRENTLY on the otherwise-idle learner mesh (two threads; JAX
        dispatches to disjoint devices in parallel). Timeshared roles, and
        partial batches whose real rows all fit the actor share (the padding
        rows at the tail would be the learners' only work), take the
        single-call path."""
        cfg = self.config
        hybrid = (
            not self.config.async_rollout  # learner mesh is busy updating
            and self.meshes is not None
            and not self.meshes.timeshared
            and cfg.number_of_actors > 0
            and cfg.learner_chunk_size > 0
            # a remote engine already fans out over worker processes; a
            # second local dispatch would double-generate the batch
            and not getattr(self.engine, "is_remote", False)
            # a mesh-bound engine (paged_sharded) compiles against the
            # rollout mesh; the learner share's params live on a different
            # device set — the whole batch decodes on the sharded engine
            and getattr(self.engine, "mesh", None) is None
            # a multi-turn env round must be ONE engine call: the turn
            # hook's candidate ids index the whole round's rows
            and self._env_driver is None
        )
        if hybrid:
            sizes = chunk_sizes(
                prompt_ids.shape[0], cfg.number_of_actors,
                cfg.number_of_learners, cfg.learner_chunk_size,
            )
            actor_rows = sum(sizes[: cfg.number_of_actors])
            if actor_rows >= n_real:
                hybrid = False  # learner share would be padding-only
        if not hybrid:
            return self._call_engine(
                *self._engine_params("rollout"),
                prompt_ids, prompt_mask, sampling, self._next_rng(),
                role="rollout",
            )

        from concurrent.futures import ThreadPoolExecutor

        key_a, key_l = self._next_rng(), self._next_rng()
        pool = ThreadPoolExecutor(max_workers=2)
        try:
            fut_a = pool.submit(
                self._call_engine, *self._engine_params("rollout"),
                prompt_ids[:actor_rows], prompt_mask[:actor_rows], sampling, key_a,
                role="rollout",
            )
            # the learner share samples with the learner-resident adapter —
            # definitionally the current version
            fut_l = pool.submit(
                self._call_engine, *self._engine_params("learner"),
                prompt_ids[actor_rows:], prompt_mask[actor_rows:], sampling, key_l,
                role="learner",
            )
            res_a, res_l = fut_a.result(), fut_l.result()
        finally:
            # never join a possibly-hung sibling here: a raised
            # EngineHangError must reach train()'s checkpoint handler
            pool.shutdown(wait=False)
        from distrl_llm_tpu.engine.engine import GenerationResult

        both_logps = res_a.logprobs is not None and res_l.logprobs is not None
        both_steps = (
            res_a.steps_dispatched is not None
            and res_l.steps_dispatched is not None
        )
        return GenerationResult(
            tokens=np.concatenate([res_a.tokens, res_l.tokens], axis=0),
            lengths=np.concatenate([res_a.lengths, res_l.lengths], axis=0),
            steps_dispatched=(
                res_a.steps_dispatched + res_l.steps_dispatched
                if both_steps else None
            ),
            alive_slot_steps=(
                res_a.alive_slot_steps + res_l.alive_slot_steps
                if res_a.alive_slot_steps is not None
                and res_l.alive_slot_steps is not None
                else None
            ),
            logprobs=(
                np.concatenate([res_a.logprobs, res_l.logprobs], axis=0)
                if both_logps else None
            ),
        )

    def _engine_params(self, role: str) -> tuple:
        """(params, lora) for an engine call. LoRA mode: frozen base + the
        role's adapter copy. Full-finetune mode: the trained tree IS the
        model — rollout uses the pushed copy, the learner its resident one."""
        if self.config.async_rollout:
            # during the pipeline overlap the trainable tree's buffers are
            # being donated by the concurrent train step — every role must
            # sample the pushed copy (one step stale by design)
            role = "rollout"
        if self._full:
            return (
                (self._lora_rollout, None) if role == "rollout"
                else (self.lora, None)
            )
        return (
            (self.base_params, self._lora_rollout) if role == "rollout"
            else (self.base_params_learner, self.lora)
        )

    def _call_engine(self, *args, role: str = "rollout"):
        """Engine call with the configured hang detector: the generation runs
        in a watchdog thread and exceeding ``generation_timeout_s`` raises
        ``EngineHangError`` (the reference's ray.get(timeout=240) equivalent,
        distributed_trainer.py:200). The hung device computation itself cannot
        be interrupted — like the reference, the recovery unit is the process
        (checkpoint + restart with resume=True).

        Cold executables are exempt: XLA specializes per (bucket, batch
        shape, placement), so warmness is tracked per (role, bucket, rows) —
        a first compile minutes long is slow, not hung."""
        timeout = self.config.generation_timeout_s
        warm_key = None
        if timeout > 0:
            ids, mask, sampling = args[2], args[3], args[4]
            bucket = (
                self.engine.bucket_for(mask)
                if hasattr(self.engine, "bucket_for") else 0
            )
            warm_key = (role, bucket, ids.shape[0], sampling.n)
            if warm_key not in self._warm_engine_keys:
                timeout = 0.0
        # an armed serving gateway shares this engine — the mutex
        # serializes trainer rounds against gateway rounds (absent a
        # gateway there is no mutex and nothing changes)
        from contextlib import nullcontext

        mutex = self._engine_mutex or nullcontext()
        if timeout <= 0:
            with mutex:
                result = self.engine.generate(*args)
            if warm_key is not None:
                self._warm_engine_keys.add(warm_key)
            return result

        import threading

        result: dict[str, Any] = {}

        def run() -> None:
            try:
                with mutex:
                    result["value"] = self.engine.generate(*args)
            except BaseException as e:  # noqa: BLE001 — re-raised on the caller
                result["error"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive():
            raise EngineHangError(
                f"generation round exceeded {timeout:.0f}s "
                f"(step {self.total_batch_steps}, weights v{self.weight_version})"
            )
        if "error" in result:
            raise result["error"]
        return result["value"]

    def _generate_round(
        self, batch: Mapping[str, Sequence[str]], sampling: SamplingConfig
    ) -> list[dict[str, Any]]:
        """One rollout round → candidate dicts shaped like the reference's
        ``vllm_generate`` output (distributed_actor.py:147–172): per task group,
        n candidate strings, the prompt/solution tiled ×n, token lengths.

        The whole round is one fixed-shape engine call: prompts padded to
        ``batch_size`` rows (masked rows discarded after) so jit compiles once;
        the batch shards over the rollout mesh's dp axis.
        """
        problems = list(batch["problem"])
        solutions = list(batch["solution"])
        b_real = len(problems)
        b_pad = self.config.batch_size
        prompt_ids, prompt_mask = encode_fixed(
            self.tokenizer, problems + [""] * (b_pad - b_real),
            self.config.max_prompt_tokens, side="left",
        )
        # race detector (SURVEY §5): the engine must only ever sample with the
        # adapter version the learner last published — the check the
        # reference's filesystem bus never had. The allowed lag derives from
        # the rollout regime (config.allowed_weight_lag): sync serializes
        # (0), pipelined deliberately samples one step stale (1), async is
        # bounded by the staleness policy (max_staleness); anything beyond
        # the mode's bound is still a bug.
        allowed_lag = self.config.allowed_weight_lag
        # read order matters on the overlapped modes' rollout thread: the
        # ROLLOUT-resident version is read FIRST, so a learner step landing
        # between the two reads surfaces as a benign positive lag — the
        # other order could read pre-step weight_version with post-push
        # rollout version and compute lag -1, crashing a healthy run
        rollout_version = self._rollout_weight_version
        lag = self.weight_version - rollout_version
        if not 0 <= lag <= allowed_lag:
            # lag < 0 (rollout AHEAD of the learner) is version-bookkeeping
            # corruption — e.g. a resume that restored an older learner state
            raise StaleWeightsError(
                f"rollout mesh holds adapter v{rollout_version} "
                f"but learner is at v{self.weight_version} — rollout_mode="
                f"{self.config.rollout_mode!r} allows lag <= {allowed_lag}; "
                "_push_weights() was not called after the last optimizer "
                "step, or the staleness bound is misconfigured"
            )
        # snapshot the mailbox BEFORE dispatch so this round's in-flight
        # swaps (and the versions pushed with them) can be sliced out after
        swaps_before = len(getattr(self.engine, "last_swap_steps", ()))
        base_version = self._rollout_weight_version
        env_round = None
        if self._env_driver is not None:
            # one env per candidate row (group-major, padding rows get
            # synthetic done episodes); the driver IS the engine turn hook
            # for the duration of this round
            self._env_driver.begin_round(
                problems + [""] * (b_pad - b_real),
                solutions + [""] * (b_pad - b_real),
                sampling.n,
            )
            self.engine.turn_hook = self._env_driver
        try:
            result = self._dispatch_rollout(
                prompt_ids, prompt_mask, sampling, b_real
            )
        finally:
            if self._env_driver is not None:
                self.engine.turn_hook = None
        if self._env_driver is not None:
            # score stragglers the engine finished without consulting the
            # hook (final blocking sweep) and assemble masks/rewards/turns
            width = result.tokens.shape[-1]
            env_round = self._env_driver.finish_round(
                np.asarray(result.tokens).reshape(-1, width),
                np.asarray(result.lengths).reshape(-1),
            )

        # degraded remote rounds (poison-shard quarantine with
        # degrade_on_poison): the engine zero-filled the quarantined
        # shards' rows and recorded them — DROP those prompts from the
        # round instead of training on fabricated zeros, with exact
        # conservation accounting (kept + lost == the real batch)
        lost = {
            int(r) for r in getattr(self.engine, "last_lost_rows", ()) or ()
        }
        kept_idx = [i for i in range(b_real) if i not in lost]
        lost_real = b_real - len(kept_idx)
        if lost_real:
            if not kept_idx:
                raise RuntimeError(
                    "every group in the round was lost to quarantined "
                    "shards — nothing survives to train on"
                )
            assert len(kept_idx) + lost_real == b_real  # conservation
            log.warning(
                "dropping %d/%d group(s) lost to quarantined shards",
                lost_real, b_real,
            )
        n = sampling.n
        answers, token_lengths = [], []
        for i in kept_idx:
            answers.append(decode_batch(self.tokenizer, result.tokens[i], result.lengths[i]))
            token_lengths.append([int(x) for x in result.lengths[i]])
        cand: dict[str, Any] = {
            "answers": answers,
            "problem": [[problems[i]] * n for i in kept_idx],
            "solution": [[solutions[i]] * n for i in kept_idx],
            "token_lengths": token_lengths,
        }
        # raw engine tokens + behavior logprobs (when the engine captures
        # them): the PPO-clip objective trains on THESE ids — retokenizing
        # decoded text (the reference's path) can shift token boundaries and
        # corrupt per-token importance ratios
        if result.logprobs is not None:
            cand["answer_tokens"] = [result.tokens[i] for i in kept_idx]
            cand["behavior_logps"] = [result.logprobs[i] for i in kept_idx]
            cand["gen_lengths"] = [result.lengths[i] for i in kept_idx]
            # per-token policy-version tags (rollout/trajectory.py): which
            # learner weight_version sampled each position. The round opens
            # at the rollout-resident version; every consumed in-flight swap
            # (push_lora) advances the tag from its recorded step on. A
            # swap pushed without a version (legacy callers) is inferred as
            # one optimizer step past its predecessor.
            from distrl_llm_tpu.rollout.trajectory import version_tags_for_round

            steps = list(getattr(self.engine, "last_swap_steps", ()))
            versions = list(getattr(self.engine, "last_swap_versions", ()))
            events: list[tuple[int, int]] = []
            inferred = base_version
            for k, step in enumerate(steps[swaps_before:]):
                v = (
                    versions[swaps_before + k]
                    if swaps_before + k < len(versions) else None
                )
                inferred = int(v) if v is not None else inferred + 1
                events.append((int(step), inferred))
            tags = version_tags_for_round(
                n, result.tokens.shape[2], base_version, events
            )
            cand["version_tags"] = [tags for _ in kept_idx]
            cand["base_version"] = base_version
            cand["swap_events"] = events
            if self.lineage is not None:
                # learn-to-act: this round sampled under its entry version
                # and every in-flight swap it consumed — the first round to
                # do so closes each version's push→act window (measured at
                # round completion: an upper bound, the engines log swap
                # steps, not wall times)
                now = time.time()
                self.lineage.note_first_sample(base_version, now)
                for _step, v in events:
                    self.lineage.note_first_sample(v, now)
        if env_round is not None:
            # env-routed rounds: per-group loss masks (1 on policy spans, 0
            # on injected observations), the env's own (n, 2) rewards (the
            # reward pass must NOT re-score — each turn was consumed live),
            # and per-turn provenance for lineage
            n_ = sampling.n
            cand["loss_mask"] = [
                env_round.loss_mask[i * n_:(i + 1) * n_] for i in kept_idx
            ]
            cand["rewards"] = [env_round.group_rewards[i] for i in kept_idx]
            cand["turns"] = [
                env_round.turn_provenance[i * n_:(i + 1) * n_]
                for i in kept_idx
            ]
            cand["env_name"] = self._env_driver.env_name
            cand["env_stats"] = env_round.stats
        # snapshot pool + round telemetry HERE, on the thread that ran the
        # round: with async_rollout the next round (or an eval) may
        # overwrite the engine's shared attributes before _train_batch
        # logs metrics
        pool = getattr(self.engine, "last_pool_stats", None)
        if pool:
            cand["pool_stats"] = dict(pool)
        rstats = getattr(self.engine, "last_round_stats", None)
        if rstats:
            cand["round_stats"] = dict(rstats)
        if self.lineage is not None:
            # sampling provenance per KEPT group: which worker + causal
            # dispatch_id sampled each prompt row (RemoteEngine records the
            # shard→row map; local engines have no dispatch, meta is None)
            cand["sampled_ts"] = time.time()
            shard_meta = getattr(self.engine, "last_shard_meta", None)
            row_meta: list[dict | None] = []
            for i in kept_idx:
                m = None
                for sm in shard_meta or ():
                    lo, hi = sm["rows"]
                    if lo <= i < hi:
                        m = {"worker": sm["worker"],
                             "dispatch_id": sm["dispatch_id"]}
                        break
                row_meta.append(m)
            cand["row_meta"] = row_meta
        return [cand]

    def _compute_round_rewards(self, candidates: list[dict[str, Any]]) -> None:
        """Per-task-group (n, 2) rewards (distributed_trainer.py:205–219),
        host-parallel via RewardComputer."""
        for cand in candidates:
            if "rewards" in cand:
                # env-scored round (ISSUE 17): each turn was rewarded as it
                # happened — re-scoring the decoded text would double-count
                # and lose the per-turn shaping
                continue
            groups = [
                (cand["answers"][j], cand["solution"][j])
                for j in range(len(cand["answers"]))
            ]
            cand["rewards"] = self.rewards(groups, reward_fn=self._reward_fn)

    def _generate_all_candidates(
        self, batch: Mapping[str, Sequence[str]], sampling: SamplingConfig | None = None
    ) -> list[dict[str, Any]]:
        sampling = sampling or self.config.train_sampling()
        candidates = self._generate_round(batch, sampling)
        self._compute_round_rewards(candidates)
        return candidates

    # ------------------------------------------------------------------ train

    def train(self) -> None:
        cfg = self.config
        if self.sink is None:
            self.sink = make_sink(
                cfg.metrics_backend,
                run_name=cfg.run_name,
                project=cfg.project_name,
                config=cfg.to_flat_dict(),
                run_dir=cfg.run_directory if cfg.run_name else ".",
            )
        if cfg.run_name:
            os.makedirs(cfg.run_directory, exist_ok=True)

        try:
            # serving gateway up BEFORE the first eval: "serve while
            # training" covers the whole loop, evals included
            self._start_gateway()
            # initial eval (distributed_trainer.py:241–242)
            self.evaluate()

            if cfg.rollout_mode == "async":
                # fully decoupled regime: RolloutService + trajectory
                # buffer + bounded-staleness learner loop
                self._train_async()
                return

            # self.episode is the next episode to START (end-of-episode saves
            # store episode+1, so a finished run resumes as a no-op).
            # ``batch_in_episode`` is the mid-episode cursor: the episode
            # shuffle is seeded by (config.seed, episode), so a resumed run
            # re-derives the same batch order and skips the batches already
            # trained instead of re-sampling them (SURVEY §5 checkpoint).
            start_episode = self.episode
            gen_pool = None
            if cfg.rollout_mode == "pipelined":
                from concurrent.futures import ThreadPoolExecutor

                gen_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="rollout"
                )
                self._gen_pool = gen_pool
            for episode in range(start_episode, cfg.episodes):
                self.episode = episode
                skip = self.batch_in_episode if episode == start_episode else 0

                # ONE-batch lookahead iterator, streamed — the sync path must
                # not materialize the episode (reference parity: it iterates),
                # and the async pipeline only ever needs the next batch.
                # Pipelined mode: batch t+1's generation is submitted BEFORE
                # batch t's update (LlamaRL/PipelineRL-style overlap), so it
                # samples with weights one step stale while the learner mesh
                # works; the pipeline stays within the episode (batch order
                # and the resume cursor are unchanged).
                stream = self._episode_batch_stream(episode, skip)
                pending = next(stream, None)
                gen_future = None
                if gen_pool is not None and pending is not None:
                    gen_future = gen_pool.submit(
                        self._generate_round, pending[1], cfg.train_sampling()
                    )
                while pending is not None:
                    bi, batch = pending
                    pending = next(stream, None)
                    if self.profiler is not None:
                        self.profiler.step_begin(self.total_batch_steps + 1)
                    next_future = None
                    if gen_pool is not None and pending is not None:
                        next_future = gen_pool.submit(
                            self._generate_round, pending[1], cfg.train_sampling()
                        )
                    self._train_batch(batch, episode, gen_future=gen_future)
                    gen_future = next_future
                    self.batch_in_episode = bi + 1
                    if cfg.eval_every and self.total_batch_steps % cfg.eval_every == 0:
                        if gen_future is not None:
                            # drain the in-flight next-batch generation first:
                            # running eval concurrently would hold two decode
                            # states/KV caches at once (HBM pressure on tight
                            # configs) and skew the eval timing numbers
                            concurrent.futures.wait([gen_future])
                        self.evaluate()
                    if cfg.save_every and self.total_batch_steps % cfg.save_every == 0:
                        self.save_checkpoint()
                        if cfg.export_hf_snapshots and cfg.run_name:
                            self.export_hf_snapshot()
                self.episode = episode + 1
                self.batch_in_episode = 0
                self.save_checkpoint()
                if cfg.export_hf_snapshots and cfg.run_name:
                    self.export_hf_snapshot()
        except EngineHangError:
            # last-gasp state capture so the documented restart path
            # (resume=True) continues from the final completed step
            log.exception("generation round hung; checkpointing before exit")
            self.save_checkpoint()
            raise
        finally:
            # gateway down first: its rounds must not race the teardown
            # of the ledger/lineage streams below
            self._close_gateway()
            service = getattr(self, "_rollout_service", None)
            if service is not None:
                # closes the buffer and stops after the round in flight;
                # never joins a possibly-hung generation (EngineHangError's
                # documented recovery is process restart)
                service.stop()
                self._rollout_service = None
            pool = getattr(self, "_gen_pool", None)
            if pool is not None:
                # never join a possibly-hung generation thread (a raised
                # EngineHangError's documented recovery is process restart),
                # and cancel any queued next-batch generation — letting it
                # start against a hung engine would wedge interpreter exit
                # (ThreadPoolExecutor threads are joined at atexit)
                pool.shutdown(wait=False, cancel_futures=True)
                self._gen_pool = None
            if self.profiler is not None:
                self.profiler.finish()
            # whole-run tracing (trace_steps=0) exports here; a closed
            # trace_steps window already wrote and disabled — no-op then
            self._export_trace()
            if self.lineage is not None:
                # flush unwritten weight-version lines and close the JSONL
                # stream; the ring (open records) stays queryable
                self.lineage.close()
            if self.serving is not None:
                # stream any open serving records plus the stall/occupancy
                # summary line, so serving.jsonl is report-complete
                self.serving.close()
            if self.learn is not None:
                # append the run-summary line so learn.jsonl is
                # report-complete for tools/learn_report.py
                self.learn.close()
            # the obs plane deliberately OUTLIVES train(): a fleet
            # operator scrapes the endpoint while rejoins/drains settle
            # after the loop ends — close_obs() (or process exit; the
            # server thread is a daemon) tears it down
            self.sink.finish()
            self.rewards.close()

    def close_obs(self) -> None:
        """Tear down the observability plane (endpoint + phase hook).
        Separate from train()'s cleanup: the endpoint stays scrapeable
        after the loop ends so post-run fleet state (late rejoins, drains)
        is observable; callers that own the trainer call this last."""
        if self.obs is not None:
            self.obs.close()
            self.obs = None

    def _episode_batch_stream(self, episode: int, skip: int):
        """One episode's (batch_index, batch) stream — the SINGLE owner of
        the per-episode shuffle seed and resume-skip semantics. Both the
        sync/pipelined loop and the async producer iterate this, so the
        regimes can never disagree on which batches exist or their order."""
        cfg = self.config
        dataset = self.train_dataset.shuffle(seed=cfg.seed + 1000 * episode)
        for bi, b in enumerate(dataset.iter(cfg.batch_size)):
            if bi >= skip:
                yield bi, b

    # ------------------------------------------------------------- async RL

    def _episode_batches(self, start_episode: int, start_batch: int):
        """(episode, batch_index, batch) stream in EXACTLY the sync loop's
        order (shared _episode_batch_stream) — the async regime changes
        when batches train, never which ones."""
        for episode in range(start_episode, self.config.episodes):
            skip = start_batch if episode == start_episode else 0
            for bi, b in self._episode_batch_stream(episode, skip):
                yield episode, bi, b

    def _train_async(self) -> None:
        """The fully decoupled regime (``--rollout_mode async``): a
        RolloutService thread generates continuously into a bounded
        TrajectoryBuffer while this loop pulls ``batch_size`` task groups
        per update on its own cadence (LlamaRL/Laminar decoupling;
        PipelineRL-style ``push_lora`` keeps the stream near-on-policy when
        ``inflight_weight_updates`` is on).

        Staleness control is layered: the buffer evicts queued groups
        already beyond ``max_staleness`` (cheap, before reward/update work),
        the StalenessPolicy drops or down-weights at admission, and the
        AIPO objective masks per-token by version lag. Every drop is
        counted, never silent."""
        cfg = self.config
        from distrl_llm_tpu.rollout import (
            RolloutService, StalenessPolicy, TrajectoryBuffer,
            round_to_trajectories, trajectories_to_candidates,
        )

        # capacity floor 2× the per-update pull: a get_batch(batch_size)
        # must always be satisfiable below the backpressure gate, or the
        # learner and a gated producer would deadlock against each other
        capacity = max(
            cfg.rollout_buffer_groups or 4 * cfg.batch_size,
            2 * cfg.batch_size,
        )
        buffer = TrajectoryBuffer(capacity, ledger=self.lineage)
        policy = StalenessPolicy(
            cfg.max_staleness, mode=cfg.staleness_policy,
            downweight=cfg.staleness_downweight, ledger=self.lineage,
        )
        self._rollout_buffer = buffer
        self._staleness_policy = policy
        self._rollout_dropped_stale = 0
        if self.control is not None:
            # staleness governor (ISSUE 14): its plant — the admission
            # policy and the buffer watermarks — exists only now; no-op
            # unless the controller is armed
            from distrl_llm_tpu.control import attach_staleness

            attach_staleness(self.control, cfg, policy, buffer)

        start_episode, start_batch = self.episode, self.batch_in_episode
        restored = getattr(self, "_resume_rollout_state", None)
        if restored:
            # unconsumed trajectories + the producer cursor from the
            # checkpoint sidecar: the run resumes without losing or
            # re-generating in-flight data
            buffer.load_state(restored.get("buffer", {}))
            cursor = restored.get("cursor")
            if cursor is not None:
                start_episode, start_batch = int(cursor[0]), int(cursor[1])
            policy.dropped = int(restored.get("policy_dropped", 0))
            policy.admitted = int(restored.get("policy_admitted", 0))
            self._rollout_dropped_stale = (
                buffer.dropped_stale + policy.dropped
            )

        def produce(episode: int, bi: int, batch) -> list:
            [cand] = self._generate_round(batch, cfg.train_sampling())
            trajs = round_to_trajectories(
                cand,
                base_version=cand.get(
                    "base_version", self._rollout_weight_version
                ),
                swap_events=cand.get("swap_events", ()),
                episode=episode, batch_index=bi,
            )
            if self.lineage is not None:
                # open one LineageRecord per group: sampling worker +
                # causal dispatch_id (remote rounds), weight-version
                # bounds, and the round-completion timestamp
                row_meta = cand.get("row_meta") or []
                ts = cand.get("sampled_ts")
                for j, traj in enumerate(trajs):
                    m = row_meta[j] if j < len(row_meta) else None
                    self.lineage.on_group_sampled(
                        traj,
                        worker=m.get("worker") if m else None,
                        dispatch_id=m.get("dispatch_id") if m else None,
                        ts=ts,
                    )
                    events = cand.get("swap_events")
                    if events:
                        self.lineage.note_swap_events(traj, events)
            return trajs

        from distrl_llm_tpu.distributed.resilience import RetryPolicy

        service = RolloutService(
            produce, buffer, self._episode_batches(start_episode, start_batch),
            # supervised restart budget (seeded backoff): transient produce
            # failures — a worker pool mid-rejoin, an RPC hiccup — retry in
            # place instead of closing the buffer and killing the regime
            max_restarts=cfg.producer_restarts,
            retry_policy=RetryPolicy(
                base_s=cfg.rpc_backoff_s, seed=cfg.seed
            ),
        )
        self._rollout_service = service
        service.start()
        while True:
            if self.profiler is not None:
                # the async loop gets the same step-window (and sentinel-
                # requested) capture hooks as the sync/pipelined loop
                self.profiler.step_begin(self.total_batch_steps + 1)
            timer = telemetry.PhaseSpans()
            if cfg.staleness_policy == "drop":
                # queued groups already beyond the bound will be rejected
                # at admission anyway — evict them first so the buffer
                # refills with usable data while this update runs. NOT in
                # downweight mode: there admission trains beyond-K groups
                # at reduced weight, so evicting them here would silently
                # turn downweight into drop. The EFFECTIVE bound is the
                # policy's (the staleness governor may have shrunk it —
                # identical to cfg.max_staleness with controllers off)
                buffer.evict_stale(
                    self.weight_version, policy.max_staleness
                )
            with timer("generation"):
                # honest accounting: the learner's BLOCKED time waiting on
                # the buffer (decoupling hides the rest of generation)
                groups = buffer.get_batch(cfg.batch_size)
            service.raise_if_failed()
            if not groups:
                break  # producer done and buffer drained
            kept, weights = policy.admit(groups, self.weight_version)
            self._rollout_dropped_stale = (
                buffer.dropped_stale + policy.dropped
            )
            if not kept:
                continue
            # (occupancy gauge: the buffer maintains rollout/buffer_occupancy
            # itself on every mutation — no second writer here)
            cand = trajectories_to_candidates(kept, weights)
            episode = kept[0].episode
            self.episode = episode
            # conservative resume cursor: re-derived from the producer at
            # save time (save_checkpoint stores the service cursor + buffer
            # snapshot; these counters only feed metrics/logs here)
            self.batch_in_episode = kept[-1].batch_index + 1
            self._update_on_candidates(
                [cand], episode, timer, n_samples=len(kept)
            )
            if self.lineage is not None:
                # the optimizer step that consumed these groups and the
                # weight version it produced (both just advanced inside
                # _update_on_candidates) — closes each record and opens
                # the produced version's policy-lag window
                from distrl_llm_tpu.learn_obs import lineage_dynamics

                self.lineage.on_consumed(
                    kept, step=self.total_batch_steps,
                    produced_version=self.weight_version,
                    # the consuming step's dynamics subset (ISSUE 16) —
                    # None unless learn_obs armed the device bundle
                    dynamics=lineage_dynamics(self._last_dynamics),
                )
            if cfg.eval_every and self.total_batch_steps % cfg.eval_every == 0:
                # evals need exclusive engine access (engines are not
                # re-entrant): pause at the next round boundary, resume after
                service.pause()
                try:
                    self.evaluate()
                finally:
                    service.resume()
            if cfg.save_every and self.total_batch_steps % cfg.save_every == 0:
                self.save_checkpoint()
                if cfg.export_hf_snapshots and cfg.run_name:
                    self.export_hf_snapshot()
        service.raise_if_failed()
        self.episode = cfg.episodes
        self.batch_in_episode = 0
        self.save_checkpoint()
        if cfg.export_hf_snapshots and cfg.run_name:
            self.export_hf_snapshot()

    def _train_batch(self, batch: Mapping[str, Sequence[str]], episode: int,
                     gen_future=None) -> None:
        cfg = self.config
        # spans + the reference's exact timing/*_duration metric names
        # (the PhaseTimer contract, now recorded on the driver trace track)
        timer = telemetry.PhaseSpans()

        with timer("generation"):
            # pipelined rollout hands in a future: timing/generation_duration
            # then honestly records the BLOCKED time (overlap hides the rest)
            if gen_future is not None:
                candidates = gen_future.result()
            else:
                candidates = self._generate_round(batch, cfg.train_sampling())
        self._update_on_candidates(
            candidates, episode, timer, n_samples=len(batch["problem"])
        )

    def _update_on_candidates(
        self, candidates: list[dict[str, Any]], episode: int,
        timer: "telemetry.PhaseSpans", n_samples: int,
    ) -> None:
        """Everything after generation: rewards, shaping, the optimizer
        step, weight push, and the metrics record. Shared verbatim by the
        sync/pipelined batch loop (candidates fresh from the round) and the
        async learner loop (candidates reassembled from buffered
        trajectories — rollout/trajectory.py)."""
        cfg = self.config
        with timer("reward"):
            self._compute_round_rewards(candidates)

        if cfg.print_samples and candidates and candidates[0]["answers"]:
            # sample dump parity (distributed_trainer.py:297–299)
            c = candidates[0]
            log.info("sample problem: %.200s", c["problem"][0][0])
            log.info("sample completion: %.400s", c["answers"][0][0])
            log.info("sample reward: %s", np.asarray(c["rewards"][0])[0])

        # policy-sharpening observability: mean rollout-time logprob of the
        # sampled tokens (only when the engine captures them — clip_ratio
        # runs); a steadily rising value = the policy concentrating
        extra_metrics: dict[str, float] = {}
        if candidates and "behavior_logps" in candidates[0]:
            tot, cnt = 0.0, 0
            for cand in candidates:
                for lp_g, len_g in zip(cand["behavior_logps"], cand["gen_lengths"]):
                    lp = np.asarray(lp_g)
                    ln = np.asarray(len_g)
                    m = np.arange(lp.shape[1])[None, :] < ln[:, None]
                    tot += float(lp[m].sum())
                    cnt += int(m.sum())
            if cnt:
                extra_metrics["mean_behavior_logprob"] = tot / cnt

        # shaping: baselines / GRPO group-norm advantages + metric collection
        # (distributed_trainer.py:262–279), then top-k (:281–294)
        with telemetry.span(telemetry.DRIVER_SHAPING):
            stats = shape_rewards(candidates, cfg.learner)
            if cfg.topk < cfg.num_candidates:
                topk_filter(candidates, cfg.topk)

        with timer("update"):
            batch_span = telemetry.span(telemetry.DRIVER_UPDATE_BATCH)
            batch_span.__enter__()
            problems, answers, coeffs, raw = flatten_for_update(
                candidates, cfg.learner
            )
            if cfg.clip_ratio > 0.0 and raw is None:
                raise RuntimeError(
                    "clip_ratio requires engine-captured behavior logprobs; "
                    "this engine returned none (GenerationResult.logprobs)"
                )
            update = prepare_update_batch(
                self.tokenizer, problems, answers, coeffs,
                max_prompt_tokens=cfg.max_prompt_tokens,
                max_new_tokens=cfg.max_new_tokens,
                micro_size=cfg.train_batch_size,
                mesh=self.meshes.learner if self.meshes is not None else None,
                raw_rollout=raw if cfg.clip_ratio > 0.0 else None,
                answer_buckets=cfg.learner_len_buckets or None,
                prompt_buckets=cfg.learner_prompt_buckets or None,
                # async: per-token version lag (learner version − sampling
                # version tag) feeds the AIPO staleness mask; None keeps
                # the sync/pipelined batch pytree unchanged
                current_version=(
                    self.weight_version
                    if cfg.rollout_mode == "async" else None
                ),
            )
            # visibility: which widths this update compiled/ran at (equal
            # the max_* caps unless the learner buckets cut them)
            answer_width = int(update.answer_ids.shape[1])
            prompt_width = int(update.prompt_ids.shape[1])
            step_args = (
                self.lora, self.opt_state,
                None if self._full else self.base_params_learner, update,
                # adapter-input dropout (helper.py:40) needs a fresh key per
                # update; disabled (None) when the rate is 0
                self._next_rng() if cfg.lora_dropout > 0.0 else None,
            )
            batch_span.__exit__(None, None, None)
            step_span = telemetry.span(telemetry.DRIVER_UPDATE_STEP)
            step_span.__enter__()  # dispatch to the host's fetch of the loss
            if self.learn is not None:
                # training-dynamics bundle (ISSUE 16): the armed step
                # returns it through the aux pytree, and the loss fetch the
                # off path already pays is widened to carry it — still
                # exactly ONE host transfer per optimizer step
                self.lora, self.opt_state, loss_dev, dyn_dev = (
                    self.train_step(*step_args)
                )
                loss_host, self._last_dynamics = jax.device_get(
                    (loss_dev, dyn_dev)
                )
                loss = float(loss_host)
            else:
                self.lora, self.opt_state, loss = self.train_step(*step_args)
                loss = float(loss)
            step_span.__exit__(None, None, None)
        if (
            self._inject_nan_step is not None
            and self.total_batch_steps + 1 == self._inject_nan_step
        ):
            # seeded chaos injection (ISSUE 14): the sentinel's env hook
            # fakes the METRIC; this one poisons the realized loss so the
            # rollback controller exercises its real path end-to-end
            loss = float("nan")
        # nan-loss rollback (ISSUE 14): a non-finite loss means the update
        # that just donated self.lora is poisoned — restore the last-good
        # (adapter, opt state, version) snapshot and skip the push, so the
        # run trains on from the last finite step instead of spreading
        # NaNs. The metrics record keeps the honest nan loss (the sentinel
        # still dumps its once-per-run incident bundle from it).
        rolled_back_to: int | None = None
        if (
            self.control is not None and self.control.nan is not None
            and not math.isfinite(loss)
        ):
            restored = self.control.nan.rollback(
                self.total_batch_steps + 1, self.control,
                bus=getattr(self.engine, "bus", None),
            )
            if restored is not None:
                self.lora, self.opt_state, rolled_back_to = restored
                if self.lineage is not None:
                    self.lineage.on_rollback(
                        step=self.total_batch_steps + 1,
                        restored_version=rolled_back_to,
                    )
        if rolled_back_to is not None:
            # the poisoned update never becomes a version — no bump — but
            # the restored tree must still be RE-PUSHED under the same
            # version: the previously pushed rollout copy can alias
            # buffers the poisoned train step just donated (sync mode
            # pushes self.lora by reference), and the weight bus's
            # idempotent per-(tree, version) push makes the re-broadcast
            # a no-op for workers that already hold it
            t_sync0 = time.perf_counter()
            self._push_weights()
        else:
            self.weight_version += 1
            t_sync0 = time.perf_counter()
            self._push_weights()
        if rolled_back_to is None and cfg.inflight_weight_updates:
            # PipelineRL-style: hand the fresh adapter to the generation
            # round still in flight on the rollout thread — engines swap at
            # their next decode dispatch (push_lora mailbox, or the remote
            # weight bus's MSG_WEIGHTS broadcast); the captured behavior
            # logprobs keep the clip objective honest about which policy
            # sampled each token. The version rides with the adapter so the
            # round in flight can tag every post-swap position with the
            # policy that sampled it (rollout/trajectory.py version tags).
            push = getattr(self.engine, "push_lora", None)
            if push is None:
                # construction-time validation rejects such engines; a
                # swapped-in engine must fail the same way, never no-op
                raise RuntimeError(
                    "inflight_weight_updates is on but the engine has no "
                    "push_lora — mid-round weight updates would silently "
                    "never happen"
                )
            push(self._lora_rollout, version=self.weight_version)
        if self.obs is not None and getattr(self.engine, "bus", None) is None:
            # weight-sync latency (learner→rollout push; the in-engine
            # push→swap half is the engine/swap_latency_ms histogram).
            # Broadcast-bus engines skip this: the bus sets the gauge from
            # push → LAST WORKER ACK, the honest end-to-end number
            telemetry.gauge_set(
                obs_mod.OBS_WEIGHT_SYNC_MS,
                (time.perf_counter() - t_sync0) * 1e3,
            )
        if (
            self.control is not None and self.control.nan is not None
            and rolled_back_to is None and math.isfinite(loss)
        ):
            # this step's state is the new last-good snapshot (taken after
            # the push, so the snapshot version is one every worker is
            # already being broadcast — a rollback never needs a resync)
            self.control.nan.note_good(
                self.weight_version, self.lora, self.opt_state
            )

        if cfg.write_adapter_file:
            self.save_adapter()

        self.total_batch_steps += 1
        self.total_samples_processed += n_samples
        log_span = telemetry.span(telemetry.DRIVER_LOG)
        log_span.__enter__()  # metrics assembly and the sink's write
        metrics = {
            "loss": loss,
            "mean_accuracy_reward": float(np.mean(stats.mean_acc)),
            "min_accuracy_reward": float(np.mean(stats.min_acc)),
            "max_accuracy_reward": float(np.mean(stats.max_acc)),
            "mean_format_reward": float(np.mean(stats.mean_format)),
            "mean_token_length": float(np.mean(stats.mean_token_length)),
            "episode": episode,
            "total_batch_steps": self.total_batch_steps,
            "total_samples_processed": self.total_samples_processed,
            # rollout-regime provenance on every train-curve record
            # (artifacts from different regimes must be distinguishable
            # from the JSONL alone): the mode, the EFFECTIVE staleness
            # bound (0 sync / 1 pipelined / K async), and cumulative stale
            # drops
            "rollout_mode": cfg.rollout_mode,
            "max_staleness": cfg.allowed_weight_lag,
            "rollout_dropped_stale": getattr(
                self, "_rollout_dropped_stale", 0
            ),
        }
        if rolled_back_to is not None:
            # which version the nan-loss rollback restored (the lineage
            # ledger carries the durable record; this is the sink's copy)
            metrics["control/rolled_back_to"] = rolled_back_to
        if cfg.learner_len_buckets:
            metrics["learner/answer_width"] = answer_width
        if cfg.learner_prompt_buckets:
            metrics["learner/prompt_width"] = prompt_width
        # budgeted-pool observability (vLLM's gpu_cache_usage-style
        # telemetry): page pressure + preemption count, snapshotted by
        # _generate_round on the thread that ran THIS round (reading the
        # engine attribute here would race async rollout / eval rounds).
        # A stat the engine didn't produce is SKIPPED, not logged as None
        # (a null metric poisons sink aggregations).
        pool = next(
            (c["pool_stats"] for c in candidates if "pool_stats" in c), None
        )
        if pool:
            for name, key in (
                ("pool/pages", "pool_pages"),
                ("pool/peak_pages_used", "peak_pages_used"),
                ("pool/preemptions", "preemptions"),
            ):
                if pool.get(key) is not None:
                    metrics[name] = pool[key]
        # env-routed rounds (ISSUE 17): per-round turn/tool telemetry the
        # driver assembled at finish_round; absent on the legacy path
        env_stats = next(
            (c["env_stats"] for c in candidates if "env_stats" in c), None
        )
        if env_stats is not None:
            metrics["env/turns_mean"] = env_stats.turns_mean
            metrics["env/turns_max"] = env_stats.turns_max
            metrics["env/step_ms_p50"] = env_stats.env_step_ms_p50
            metrics["env/round_tool_calls"] = env_stats.tool_calls
            metrics["env/round_resume_declined"] = env_stats.resume_declined
        elif any("turns" in c for c in candidates):
            # async-consumed env batches: the round-level stats object
            # stayed with the producer, but turn counts are derivable
            # from the provenance that rode the trajectories
            counts = _env_turn_counts(candidates)
            if counts:
                metrics["env/turns_mean"] = float(np.mean(counts))
                metrics["env/turns_max"] = int(np.max(counts))
        metrics.update(self._engine_metrics(candidates))
        metrics.update(extra_metrics)
        metrics.update(timer.metrics())
        if self.obs is not None:
            # learner idle fraction: the share of this step the learner
            # spent BLOCKED on data (generation phase = wait time in the
            # pipelined/async regimes) — the signal RLAX's fleet loop
            # steers on. Published before the snapshot merge below so it
            # rides the same sink record.
            phase_total = sum(
                timer.get(p) for p in ("generation", "reward", "update")
            )
            if phase_total > 0:
                telemetry.gauge_set(
                    obs_mod.OBS_LEARNER_IDLE,
                    timer.get("generation") / phase_total,
                )
        if self.learn is not None and self._last_dynamics is not None:
            # training-dynamics bundle (ISSUE 16): publish this step's
            # device-computed learn/* gauges + IS-ratio histogram BEFORE
            # the snapshot merge below, so the dynamics ride the same sink
            # record (wandb/jsonl curves) and the sentinel's metrics view
            self.learn.on_step(
                self.total_batch_steps, self._last_dynamics,
                reward_mean=metrics.get("mean_accuracy_reward"),
            )
        # registry series (pool/occupancy gauge, cp/rpc_* histograms, …)
        # ride the same sink record
        metrics.update(telemetry.metrics_snapshot())
        self.sink.log(metrics, step=self.total_batch_steps)
        log_span.__exit__(None, None, None)
        if self.obs is not None:
            # ring record + sentinel pass + fleet refresh — the per-step
            # entry point of the observability plane
            self.obs.on_step(self.total_batch_steps, metrics)
        if self.control is not None:
            # governors read the same metrics record the sentinel just
            # checked (trigger escalations already ran inside on_step
            # above); actions land before the next generation round
            self.control.on_step(self.total_batch_steps, metrics)
        if cfg.trace_dir and telemetry.enabled():
            self._trace_steps_done += 1
            if cfg.trace_steps and self._trace_steps_done >= cfg.trace_steps:
                # window closed: write the trace now (a crashed run past the
                # window still has its file) and stop paying for recording
                self._export_trace()
                telemetry.configure(enabled=False)

    def _engine_metrics(self, candidates) -> dict[str, float]:
        """engine/prefill_tok_s, engine/decode_tok_s, engine/mfu from the
        round stats every engine records (engine.accumulate_round_stats);
        MFU uses the model's FLOPs/token (models/configs.py) at this
        round's realized mean context length."""
        stats = next(
            (c["round_stats"] for c in candidates if "round_stats" in c), None
        )
        if not stats:
            return {}
        out: dict[str, float] = {}
        decode_tok_s = None
        if stats["prefill_s"] > 0 and stats["prefill_tokens"]:
            out["engine/prefill_tok_s"] = (
                stats["prefill_tokens"] / stats["prefill_s"]
            )
        if stats["decode_s"] > 0 and stats["gen_tokens"]:
            decode_tok_s = stats["gen_tokens"] / stats["decode_s"]
            out["engine/decode_tok_s"] = decode_tok_s
        if (
            decode_tok_s is not None and self._peak_flops
            # remote rounds measure N workers' unknown chips against the
            # local peak — no honest per-chip number exists driver-side
            and not getattr(self.engine, "is_remote", False)
            # whole-round stats (sharded engine) fold prefill + compile
            # into decode_s: honest throughput, but not an MFU numerator
            and not stats.get("whole_round")
        ):
            mean_kv = (
                stats["prefill_tokens"] / max(stats["prompt_rows"], 1)
                + stats["gen_tokens"] / max(stats["gen_rows"], 1) / 2
            )
            out["engine/mfu"] = telemetry.mfu(
                decode_tok_s / self._rollout_chips,
                self.model_cfg.decode_flops_per_token(mean_kv),
                self._peak_flops,
            )
        return out

    def _export_trace(self) -> None:
        """Write the Chrome-trace/Perfetto JSON to trace_dir/trace.json with
        the metadata tools/trace_report.py needs for tok/s and MFU."""
        cfg = self.config
        if not cfg.trace_dir or not telemetry.enabled():
            return
        path = telemetry.export_chrome_trace(
            os.path.join(cfg.trace_dir, "trace.json"),
            metadata={
                "model": cfg.model,
                # static context estimate for report-side MFU: full prompt
                # window + half the generation window
                "decode_flops_per_token": self.model_cfg.decode_flops_per_token(
                    cfg.max_prompt_tokens + cfg.max_new_tokens / 2
                ),
                "peak_flops": self._peak_flops,
                # trace_report divides whole-engine tok/s by this before
                # comparing against the single-chip peak
                "chips": self._rollout_chips,
                # the round ledger (the last 64 rounds' boundaries): one line
                # a stalled round in trace_report
                "rounds": telemetry.round_records(),
            },
        )
        log.info("telemetry trace written to %s", path)

    # ------------------------------------------------------------------- eval

    def evaluate(self) -> dict[str, float]:
        """Best-of-n eval (distributed_trainer.py:384–416): pass@1 = mean
        accuracy over candidates, BoN = max; same rollout path with eval
        sampling params."""
        cfg = self.config
        timer = telemetry.PhaseSpans()
        accs, bons, tok_lens = [], [], []
        with timer("eval"):
            for batch in self.test_dataset.iter(cfg.batch_size):
                candidates = self._generate_all_candidates(batch, cfg.eval_sampling())
                for cand in candidates:
                    for rewards, lengths in zip(cand["rewards"], cand["token_lengths"]):
                        acc = np.asarray(rewards)[:, 1]
                        accs.append(float(np.mean(acc)))
                        bons.append(float(np.max(acc)))
                        tok_lens.append(float(np.mean(lengths)))
        n = cfg.eval_n
        metrics = {
            f"eval/pass@1(mean{n})": float(np.mean(accs)),
            f"eval/BoN({n})": float(np.mean(bons)),
            "eval/mean_token_length": float(np.mean(tok_lens)),
            **timer.metrics(),
        }
        if self.sink is not None:
            self.sink.log(metrics, step=self.total_batch_steps)
        return metrics
