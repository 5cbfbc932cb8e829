"""Failure handling + mid-episode resume + profiler wiring.

Covers SURVEY §5's failure-detection and checkpoint subsystems at the level
the reference has (timeouts as hang detectors, distributed_trainer.py:200)
and beyond it (mid-episode resume; the reference can't resume at all)."""

import os
import time

import numpy as np
import pytest

from distrl_llm_tpu.engine.fake import FakeEngine
from distrl_llm_tpu.metrics import MemorySink
from distrl_llm_tpu.trainer import EngineHangError

from tests.test_trainer import make_config, make_datasets, make_trainer


class HangingEngine(FakeEngine):
    """Sleeps past the watchdog on the first call."""

    def __init__(self, *a, hang_s: float = 10.0, **kw):
        super().__init__(*a, **kw)
        self.hang_s = hang_s

    def generate(self, *args, **kw):
        time.sleep(self.hang_s)
        return super().generate(*args, **kw)


class TestHangDetection:
    @pytest.mark.slow
    def test_hang_raises_and_checkpoints(self, tmp_path):
        cfg = make_config(
            generation_timeout_s=0.3,
            checkpoint_dir=str(tmp_path / "ckpt"),
            eval_every=0,
        )
        trainer = make_trainer(config=cfg)
        trainer.engine = HangingEngine(
            trainer.tokenizer, lambda p, j: "x", hang_s=5.0,
            max_new_tokens=cfg.max_new_tokens,
        )
        with pytest.raises(EngineHangError):
            trainer.train()
        # last-gasp checkpoint for the documented restart path
        assert trainer.ckpt.latest_step() is not None

    def test_timeout_disabled_by_default(self):
        trainer = make_trainer()
        assert trainer.config.generation_timeout_s == 0.0
        # engine errors propagate unchanged through the wrapper
        trainer.config.generation_timeout_s = 5.0

        class Boom(FakeEngine):
            def generate(self, *a, **k):
                raise ValueError("boom")

        trainer.engine = Boom(trainer.tokenizer, lambda p, j: "x")
        with pytest.raises(ValueError, match="boom"):
            trainer._generate_round(
                {"problem": ["q a"], "solution": ["A"]},
                trainer.config.train_sampling(),
            )


class TestMidEpisodeResume:
    def test_resume_skips_seen_batches(self, tmp_path):
        """Kill a run after 1 of 2 batches in an episode; the resumed run
        must train exactly the remaining batch — same shuffle order, no
        re-sampling of the seen batch."""
        cfg = make_config(checkpoint_dir=str(tmp_path / "ckpt"), episodes=1)
        sink = MemorySink()
        trainer = make_trainer(config=cfg, sink=sink)
        # run exactly one batch by hand (8 problems / batch 4 = 2 per episode)
        dataset = trainer.train_dataset.shuffle(seed=cfg.seed)
        first = next(iter(dataset.iter(cfg.batch_size)))
        trainer._train_batch(first, episode=0)
        trainer.batch_in_episode = 1
        trainer.save_checkpoint()
        assert trainer.total_batch_steps == 1

        sink2 = MemorySink()
        cfg2 = make_config(
            checkpoint_dir=str(tmp_path / "ckpt"), episodes=1, resume=True
        )
        resumed = make_trainer(config=cfg2, sink=sink2)
        assert resumed.batch_in_episode == 1
        resumed.train()
        # exactly ONE more train step happened (the unseen batch)
        train_recs = [m for _, m in sink2.records if "loss" in m]
        assert len(train_recs) == 1
        assert resumed.total_batch_steps == 2
        # after the episode the cursor resets and the episode advances
        assert resumed.episode == 1
        assert resumed.batch_in_episode == 0

    def test_shuffle_is_seed_deterministic(self):
        train, _ = make_datasets()
        from distrl_llm_tpu.data import DictDataset

        a = DictDataset(train).shuffle(seed=7)
        b = DictDataset(train).shuffle(seed=7)
        assert a["problem"] == b["problem"]
        c = DictDataset(train).shuffle(seed=8)
        assert a["problem"] != c["problem"]


class TestProfiler:
    @pytest.mark.slow
    def test_trace_dir_produced(self, tmp_path):
        """profile_dir is no longer a dead flag: a smoke run produces a
        TensorBoard trace directory."""
        prof = str(tmp_path / "traces")
        cfg = make_config(profile_dir=prof, profile_start_step=1, profile_num_steps=1)
        trainer = make_trainer(config=cfg)
        trainer.train()
        entries = []
        for root, _, files in os.walk(prof):
            entries += [os.path.join(root, f) for f in files]
        assert entries, f"no trace files under {prof}"
