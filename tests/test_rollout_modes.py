"""Rollout-regime tests (``--rollout_mode``): the sync determinism pins, the
--async_rollout alias, the config-derived staleness detector, the
fully-decoupled async loop (buffer + staleness telemetry + in-flight swaps),
and buffer-state resume.

What the sync pins protect, on whatever JAX is installed: the sync loop is a
pure function of its seed (two runs in one process give every loss float and
the final adapter checksum bit for bit), an explicit ``env="math"`` is the
default path bit for bit, and the clipped objective's first step — on-policy,
every ratio 1 — is the unclipped run's first step. No float is stored: the
losses are of order 1e-7, the rounding residue of a group-normalised
objective, and belong to whichever JAX computed them.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.config import TrainConfig
from distrl_llm_tpu.engine import GenerationEngine
from distrl_llm_tpu.metrics import MemorySink
from distrl_llm_tpu.models import TINY, init_params
from distrl_llm_tpu.models.lora import lora_scale
from distrl_llm_tpu.tokenizer import CharTokenizer
from distrl_llm_tpu.trainer import StaleWeightsError, Trainer
from tests.test_trainer import make_trainer

def dense_reward(completions, solutions):
    return np.asarray(
        [(0.0, 0.1 + (len(c) % 5) / 10.0) for c in completions],
        np.float32,
    )


def _run_tiny(**cfg_kw):
    """The one tiny configuration every regime here runs; cfg_kw overrides
    select the regime under test."""
    defaults = dict(
        model="tiny", episodes=2, batch_size=4, num_candidates=4, topk=4,
        train_batch_size=4, max_prompt_tokens=16, max_new_tokens=24,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
        max_lora_rank=4, lora_alpha=8, learner="grpo",
    )
    defaults.update(cfg_kw)
    cfg = TrainConfig(**defaults)
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    test = {k: v[:4] for k, v in train.items()}
    params = init_params(jax.random.PRNGKey(0), TINY)
    engine = GenerationEngine(
        TINY, max_prompt_tokens=cfg.max_prompt_tokens,
        max_new_tokens=cfg.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jnp.float32,
        lora_scale=lora_scale(cfg.max_lora_rank, cfg.lora_alpha),
        capture_logprobs=cfg.clip_ratio > 0.0, decode_chunk=4,
    )
    sink = MemorySink()
    trainer = Trainer(
        train, test, dense_reward, cfg,
        tokenizer=tok, engine=engine, base_params=params, model_cfg=TINY,
        sink=sink,
    )
    trainer.train()
    return trainer, sink, engine


def _checksum(tree) -> float:
    return float(sum(
        np.abs(np.asarray(x)).sum() for x in jax.tree_util.tree_leaves(tree)
    ))


def _fresh_adapter():
    """The adapter ``_run_tiny``'s trainer starts from (its seed, its rank)."""
    from distrl_llm_tpu.models import init_lora_params

    _, lora_key = jax.random.split(jax.random.PRNGKey(TrainConfig().seed))
    return init_lora_params(lora_key, TINY, 4, dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _sync_trained(clip: float, repeat: int = 0, env: str | None = None):
    """(trainer, sink, engine) of one sync run, for the cases that only read
    what a run leaves behind. ``repeat`` only keys the cache: another value
    is another, independent run of the same configuration."""
    kw = {} if env is None else {"env": env}
    return _run_tiny(clip_ratio=clip, **kw)


def _sync_run(clip: float, repeat: int = 0, env: str | None = None):
    """(losses, adapter checksum, mean behavior logprobs) of one sync run."""
    trainer, sink, _ = _sync_trained(clip, repeat, env)
    recs = [m for _, m in sink.records if "loss" in m]
    return (
        tuple(m["loss"] for m in recs),
        _checksum(trainer.lora),
        tuple(m.get("mean_behavior_logprob") for m in recs),
    )


class TestSyncByteIdentity:
    """Acceptance pin: ``--rollout_mode sync`` is deterministic to the bit on
    the tiny CPU config — the property every regime comparison rests on."""

    @pytest.mark.parametrize("clip", [0.0, 0.2])
    def test_loss_sequence_and_adapter_identical_to_pre_pr(self, clip):
        losses, checksum, mbl = _sync_run(clip)
        again = _sync_run(clip, repeat=1)
        assert len(losses) == 4 and all(np.isfinite(losses))
        assert again[0] == losses, "sync-mode loss sequence is not reproducible"
        assert again[1] == checksum, "sync-mode final adapter is not reproducible"
        # the run trained: the adapter left its initialisation
        assert checksum != _checksum(_fresh_adapter())
        if clip > 0.0:
            assert again[2] == mbl and all(np.isfinite(mbl))
            # first step is on-policy (every ratio 1): clipping changes
            # nothing yet, so it is the clip-0 run's first step up to the
            # learner's recompute of the engine's logprobs
            assert losses[0] == pytest.approx(_sync_run(0.0)[0][0], abs=1e-5)
            # and from then on the objectives differ
            assert checksum != _sync_run(0.0)[1]

    def test_sync_records_carry_regime_fields(self):
        trainer, sink, _ = _sync_trained(TrainConfig().clip_ratio)
        recs = [m for _, m in sink.records if "loss" in m]
        assert all(m["rollout_mode"] == "sync" for m in recs)
        assert all(m["max_staleness"] == 0 for m in recs)
        assert all(m["rollout_dropped_stale"] == 0 for m in recs)


class TestEnvRouting:
    """``env="math"`` (the default) routes the EXACT legacy path (ISSUE
    17): no env driver is constructed, the engine's turn hook is never
    armed, and the determinism pins above therefore cover the default
    env. An explicit ``env="math"`` must change nothing."""

    @pytest.mark.parametrize("clip", [0.0, 0.2])
    def test_explicit_math_env_is_byte_identical(self, clip):
        explicit = _sync_run(clip, env="math")
        default = _sync_run(clip)
        assert explicit[0] == default[0], (
            "env='math' diverged from the legacy rollout path"
        )
        assert explicit[1] == default[1]

    def test_math_env_never_arms_driver_or_hook(self):
        trainer, _, engine = _sync_trained(TrainConfig().clip_ratio, env="math")
        assert trainer._env_driver is None
        assert getattr(engine, "turn_hook", None) is None

    def test_math_records_carry_no_env_metrics(self):
        _, sink, _ = _sync_trained(TrainConfig().clip_ratio, env="math")
        recs = [m for _, m in sink.records if "loss" in m]
        assert recs and not any(
            k.startswith("env/") for m in recs for k in m
        )


class TestModeAliasing:
    def test_async_rollout_flag_selects_pipelined(self):
        cfg = TrainConfig(model="t", async_rollout=True)
        assert cfg.rollout_mode == "pipelined"
        assert cfg.async_rollout is True
        assert cfg.allowed_weight_lag == 1

    def test_pipelined_reads_back_as_async_rollout(self):
        # existing call sites branch on config.async_rollout — both
        # overlapped modes must satisfy them
        assert TrainConfig(model="t", rollout_mode="pipelined").async_rollout
        assert TrainConfig(
            model="t", rollout_mode="async", clip_ratio=0.2
        ).async_rollout
        assert not TrainConfig(model="t").async_rollout

    def test_async_requires_clip_and_staleness(self):
        with pytest.raises(ValueError, match="clip_ratio"):
            TrainConfig(model="t", rollout_mode="async")
        with pytest.raises(ValueError, match="max_staleness"):
            TrainConfig(model="t", rollout_mode="async", clip_ratio=0.2,
                        max_staleness=0)

    def test_allowed_lag_derivation(self):
        assert TrainConfig(model="t").allowed_weight_lag == 0
        assert TrainConfig(
            model="t", rollout_mode="pipelined"
        ).allowed_weight_lag == 1
        assert TrainConfig(
            model="t", rollout_mode="async", clip_ratio=0.2, max_staleness=5
        ).allowed_weight_lag == 5


class TestStaleDetectorMessage:
    def test_names_mode_and_bound(self):
        trainer = make_trainer()
        trainer.weight_version = 5
        trainer._rollout_weight_version = 4
        with pytest.raises(StaleWeightsError, match="rollout_mode='sync'"):
            trainer._generate_round(
                {"problem": ["q a"], "solution": ["A"]},
                trainer.config.train_sampling(),
            )
        with pytest.raises(StaleWeightsError, match="lag <= 0"):
            trainer._generate_round(
                {"problem": ["q a"], "solution": ["A"]},
                trainer.config.train_sampling(),
            )


class TestAsyncMode:
    @pytest.fixture(autouse=True)
    def clean_telemetry(self):
        telemetry.reset()
        telemetry.configure(enabled=False)
        yield
        telemetry.reset()
        telemetry.configure(enabled=False)

    def test_multi_episode_run_with_inflight_swaps(self):
        """The acceptance run: multi-episode async training completes with
        finite losses, the trajectory stream is version-tagged, buffer and
        staleness telemetry are nonzero, and with inflight pushes enabled
        the engine consumes in-flight swaps whose recorded versions match
        learner weight versions."""
        trainer, sink, engine = _run_tiny(
            episodes=4, num_candidates=2, topk=2,
            rollout_mode="async", max_staleness=3, clip_ratio=0.2,
            inflight_weight_updates=True,
            # capacity floor (2× batch) backpressures the producer after two
            # rounds, forcing rounds to interleave with updates — the regime
            # where in-flight swaps actually happen
            rollout_buffer_groups=1,
        )
        recs = [m for _, m in sink.records if "loss" in m]
        assert recs and all(np.isfinite(m["loss"]) for m in recs)
        assert all(m["rollout_mode"] == "async" for m in recs)
        assert all(m["max_staleness"] == 3 for m in recs)
        stats = trainer._rollout_buffer.stats()
        assert stats["total_put"] >= 8  # 4 episodes × 2 batches
        assert (
            stats["total_put"]
            == stats["total_got"] + stats["dropped_stale"]
            + stats["dropped_capacity"] + stats["occupancy"]
        ), stats
        # staleness histogram reached the sink on at least one step
        assert any(
            k.startswith("rollout/staleness") for m in recs for k in m
        ), "no staleness telemetry in the train records"
        assert any(
            "rollout/buffer_occupancy" in m for m in recs
        ), "no occupancy telemetry in the train records"
        # in-flight swaps: recorded versions are real learner versions
        assert len(engine.last_swap_steps) >= 2, (
            f"expected >=2 in-flight swaps, got {engine.last_swap_steps}"
        )
        assert len(engine.last_swap_versions) == len(engine.last_swap_steps)
        assert all(
            v is not None and 0 < v <= trainer.weight_version
            for v in engine.last_swap_versions
        ), engine.last_swap_versions

    def test_async_processes_same_batch_stream_when_nothing_drops(self):
        """With a staleness bound large enough that nothing drops, async
        consumes exactly the batches sync would have produced."""
        trainer, sink, _ = _run_tiny(
            num_candidates=2, topk=2,
            rollout_mode="async", max_staleness=100, clip_ratio=0.2,
        )
        recs = [m for _, m in sink.records if "loss" in m]
        assert len(recs) == 4  # 2 episodes × (8 problems / batch 4)
        assert trainer._rollout_buffer.stats()["dropped_stale"] == 0
        assert all(m["rollout_dropped_stale"] == 0 for m in recs)

    def test_downweight_policy_trains_stale_groups_instead_of_dropping(self):
        """Regression (review finding): with --staleness_policy downweight
        the trainer must NOT pre-evict beyond-K groups from the buffer —
        eviction would silently turn downweight into drop. Every produced
        group trains (at reduced weight when stale); nothing is dropped."""
        trainer, sink, _ = _run_tiny(
            num_candidates=2, topk=2,
            rollout_mode="async", max_staleness=1, clip_ratio=0.2,
            staleness_policy="downweight",
        )
        recs = [m for _, m in sink.records if "loss" in m]
        assert recs and all(np.isfinite(m["loss"]) for m in recs)
        stats = trainer._rollout_buffer.stats()
        policy = trainer._staleness_policy
        assert stats["dropped_stale"] == 0, stats
        assert policy.dropped == 0
        # every group handed to the learner was admitted (weighted, maybe)
        assert policy.admitted == stats["total_got"]

    def test_version_lag_masking_drops_stale_tokens_from_loss(self):
        """The AIPO objective's version-lag mask: a microbatch whose tokens
        all exceed max_staleness contributes zero gradient signal."""
        from distrl_llm_tpu.learner.losses import grpo_aipo_loss

        logp = jnp.asarray([[-1.0, -1.5], [-2.0, -0.5]])
        behav = jnp.asarray([[-1.2, -1.0], [-1.0, -1.0]])
        mask = jnp.ones((2, 2))
        adv = jnp.asarray([1.0, -1.0])
        fresh = grpo_aipo_loss(logp, behav, mask, adv)
        assert np.isfinite(float(fresh)) and float(fresh) != 0.0
        # all tokens beyond the bound → empty mask → zero loss
        lag = jnp.full((2, 2), 7.0)
        stale = grpo_aipo_loss(
            logp, behav, mask, adv, version_lag=lag, max_staleness=3
        )
        assert float(stale) == 0.0
        # mixed-version trajectory: only the fresh column contributes
        lag2 = jnp.asarray([[0.0, 7.0], [0.0, 7.0]])
        mixed = grpo_aipo_loss(
            logp, behav, mask, adv, version_lag=lag2, max_staleness=3
        )
        fresh_only = grpo_aipo_loss(
            logp[:, :1], behav[:, :1], mask[:, :1], adv
        )
        assert float(mixed) == pytest.approx(float(fresh_only))

    def test_aipo_truncates_ratio(self):
        from distrl_llm_tpu.learner.losses import grpo_aipo_loss

        logp = jnp.asarray([[3.0]])  # exp(3-0)=20 — way past the cap
        behav = jnp.asarray([[0.0]])
        mask = jnp.ones((1, 1))
        adv = jnp.asarray([1.0])
        loss = grpo_aipo_loss(logp, behav, mask, adv, is_cap=2.0)
        assert float(loss) == pytest.approx(-2.0)

    def test_buffer_state_survives_resume(self, tmp_path):
        """The checkpoint sidecar round-trip through the trainer: queued
        trajectories and the producer cursor reload on resume."""
        from distrl_llm_tpu.checkpoint import (
            load_rollout_state, save_rollout_state,
        )
        from distrl_llm_tpu.rollout import Trajectory, TrajectoryBuffer

        trainer, _, _ = _run_tiny(
            num_candidates=2, topk=2,
            rollout_mode="async", max_staleness=100, clip_ratio=0.2,
            checkpoint_dir=str(tmp_path / "ckpt"), save_every=2,
        )
        step = trainer.total_batch_steps
        # simulate a crash that left data in flight: overwrite the final
        # sidecar with a non-empty buffer + mid-episode cursor
        buf = TrajectoryBuffer(8)
        buf.put(Trajectory(
            problem="carried", solution="S", answers=["a", "b"],
            token_lengths=[2, 2], produced_version=step,
        ))
        save_rollout_state(str(tmp_path / "ckpt"), step, {
            "buffer": buf.state_dict(), "cursor": (1, 1),
        })
        assert load_rollout_state(str(tmp_path / "ckpt"), step) is not None

        cfg2 = dict(
            model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
            train_batch_size=4, max_prompt_tokens=16, max_new_tokens=24,
            number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
            eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
            max_lora_rank=4, lora_alpha=8, learner="grpo",
            rollout_mode="async", max_staleness=100, clip_ratio=0.2,
            checkpoint_dir=str(tmp_path / "ckpt"), resume=True,
        )
        cfg2 = TrainConfig(**cfg2)
        tok = CharTokenizer()
        problems = [f"q {c}" for c in "abcdefgh"]
        train = {"problem": problems,
                 "solution": [p.strip()[-1].upper() for p in problems]}
        engine = GenerationEngine(
            TINY, max_prompt_tokens=16, max_new_tokens=24,
            eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
            cache_dtype=jnp.float32, lora_scale=lora_scale(4, 8),
            capture_logprobs=True, decode_chunk=4,
        )
        resumed = Trainer(
            train, {k: v[:4] for k, v in train.items()}, dense_reward, cfg2,
            tokenizer=tok, engine=engine,
            base_params=init_params(jax.random.PRNGKey(0), TINY),
            model_cfg=TINY, sink=MemorySink(),
        )
        assert resumed.total_batch_steps == step
        state = resumed._resume_rollout_state
        assert state is not None
        assert state["cursor"] == (1, 1)
        restored = TrajectoryBuffer(8)
        restored.load_state(state["buffer"])
        [t] = restored.get_batch(1)
        assert t.problem == "carried"

    def test_corrupt_sidecar_degrades_to_fresh(self, tmp_path):
        from distrl_llm_tpu.checkpoint import (
            load_rollout_state, rollout_state_path,
        )

        path = rollout_state_path(str(tmp_path), 3)
        import os

        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(b"not a pickle")
        assert load_rollout_state(str(tmp_path), 3) is None
        assert load_rollout_state(str(tmp_path), 99) is None
