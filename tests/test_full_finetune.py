"""bf16 full-rank fine-tuning (reference recipe 3: "bf16 full-rank, no
4-bit") — the whole param tree trains instead of a LoRA adapter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.config import TrainConfig
from distrl_llm_tpu.learner.optim import make_optimizer
from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
from distrl_llm_tpu.models import TINY, init_params


def make_batch(rng, n, p_len=6, t_len=8):
    return UpdateBatch(
        prompt_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, p_len)), jnp.int32),
        prompt_mask=jnp.ones((n, p_len), jnp.int32),
        answer_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, t_len)), jnp.int32),
        answer_mask=jnp.ones((n, t_len), jnp.int32),
        coeffs=jnp.asarray(rng.normal(size=n), jnp.float32),
        sample_mask=jnp.ones((n,), jnp.float32),
    )


class TestFullRankTrainStep:
    @pytest.mark.slow
    def test_updates_every_param(self):
        """In full mode ALL leaves move — embed, norms, lm_head included
        (LoRA mode can only touch the adapter)."""
        params = init_params(jax.random.PRNGKey(0), TINY)
        opt = make_optimizer(1e-3, use_8bit=True)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=opt, lora_scale=1.0,
            micro_size=2, donate=False, train_mode="full",
        )
        batch = make_batch(np.random.default_rng(0), 4)
        new_params, _, loss = step(params, opt.init(params), None, batch)
        assert np.isfinite(float(loss))
        moved = [
            float(jnp.abs(a - b).max()) > 0
            for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(new_params),
            )
        ]
        assert all(moved), f"{sum(moved)}/{len(moved)} leaves updated"

    @pytest.mark.slow
    def test_repeated_steps_reduce_pg_loss(self):
        params = init_params(jax.random.PRNGKey(0), TINY)
        opt = make_optimizer(5e-3, use_8bit=True)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=opt, lora_scale=1.0,
            micro_size=2, donate=False, train_mode="full",
        )
        rng = np.random.default_rng(1)
        batch = make_batch(rng, 4)
        batch = batch._replace(coeffs=jnp.ones((4,), jnp.float32))
        opt_state = opt.init(params)
        losses = []
        for _ in range(6):
            params, opt_state, loss = step(params, opt_state, None, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses

    def test_grpo_full_matches_shapes_and_runs_chunked(self):
        params = init_params(jax.random.PRNGKey(2), TINY)
        opt = make_optimizer(1e-3, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="grpo", optimizer=opt, lora_scale=1.0,
            micro_size=2, donate=False, train_mode="full", logit_chunk=4,
        )
        batch = make_batch(np.random.default_rng(3), 4)
        new_params, _, loss = step(params, opt.init(params), None, batch)
        assert np.isfinite(float(loss))
        assert jax.tree_util.tree_structure(new_params) == jax.tree_util.tree_structure(params)


class TestFullFinetuneConfig:
    def test_rejects_quantized_base(self):
        with pytest.raises(ValueError, match="quantized|base_quant"):
            TrainConfig(full_finetune=True, base_quant="int8")

    def test_rejects_adapter_file(self):
        with pytest.raises(ValueError, match="adapter"):
            TrainConfig(full_finetune=True, write_adapter_file=True)

    def test_accepts_plain(self):
        assert TrainConfig(full_finetune=True).full_finetune


class TestFullFinetuneTrainer:
    @pytest.mark.slow
    def test_round_updates_weights_and_engine_sees_them(self):
        """A full trainer batch in full-rank mode: the engine must sample
        from the UPDATED tree on the next round (weight sync pushes the whole
        tree), and there is no adapter to export."""
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.metrics import MemorySink
        from distrl_llm_tpu.tokenizer import CharTokenizer
        from distrl_llm_tpu.trainer import Trainer
        from tests.test_trainer import make_config, make_datasets

        config = make_config(full_finetune=True, lr=1e-2)
        tok = CharTokenizer()
        train, test = make_datasets()
        params = init_params(jax.random.PRNGKey(0), TINY)
        engine = GenerationEngine(
            TINY, max_prompt_tokens=config.max_prompt_tokens,
            max_new_tokens=config.max_new_tokens,
            eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
            cache_dtype=jnp.float32,
        )
        sink = MemorySink()

        def dense_reward(completions, solutions):
            # nonzero, varying coeffs so the zero-reward skip never fires
            return np.asarray(
                [(0.0, 0.1 + (len(c) % 7) / 10.0) for c in completions],
                np.float32,
            )

        trainer = Trainer(
            train, test, dense_reward, config,
            tokenizer=tok, engine=engine, base_params=params, model_cfg=TINY,
            sink=sink,
        )
        before = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), trainer.lora)
        batch = {"problem": train["problem"][:4], "solution": train["solution"][:4]}
        trainer._train_batch(batch, episode=0)
        after = trainer.lora
        deltas = [
            float(jnp.abs(jnp.asarray(a) - b).max())
            for a, b in zip(jax.tree_util.tree_leaves(after), jax.tree_util.tree_leaves(before))
        ]
        assert max(deltas) > 0  # weights moved
        # the pushed rollout copy is the trained tree (full mode has no base)
        p, lo = trainer._engine_params("rollout")
        assert lo is None
        assert p is trainer._lora_rollout
        with pytest.raises(RuntimeError, match="adapter"):
            trainer.save_adapter()
        recs = [m for _, m in sink.records if "loss" in m]
        assert recs and np.isfinite(recs[-1]["loss"])

    def test_bf16_base_trains_in_f32_master_weights(self):
        """Review regression: with a bf16 base, per-step updates (~lr) sit
        below bf16's ~0.4% relative resolution — the trainable copy must be
        f32, and the pushed rollout tree must come back down to bf16."""
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.metrics import MemorySink
        from distrl_llm_tpu.rewards import reward_function
        from distrl_llm_tpu.tokenizer import CharTokenizer
        from distrl_llm_tpu.trainer import Trainer
        from tests.test_trainer import make_config, make_datasets

        config = make_config(full_finetune=True)
        tok = CharTokenizer()
        train, test = make_datasets()
        params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
        engine = GenerationEngine(
            TINY, max_prompt_tokens=config.max_prompt_tokens,
            max_new_tokens=config.max_new_tokens,
            eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        )
        trainer = Trainer(
            train, test, reward_function, config,
            tokenizer=tok, engine=engine, base_params=params, model_cfg=TINY,
            sink=MemorySink(),
        )
        assert all(
            leaf.dtype == jnp.float32
            for leaf in jax.tree_util.tree_leaves(trainer.lora)
        )
        assert trainer.base_params is None and trainer.base_params_learner is None
        trainer._push_weights()
        assert all(
            leaf.dtype == jnp.bfloat16
            for leaf in jax.tree_util.tree_leaves(trainer._lora_rollout)
        )
