"""Long-CoT shapes (reference recipe 4: 4k-token rollouts) on the CPU mesh.

The reference cannot express these at all (sequence hard-fixed at 1,550
tokens, SURVEY §5 long-context); here the learner's 4k-token step runs
sequence-parallel (ring / ulysses) with remat + chunked CE, and the engine
decodes past the reference's 1,200-token ceiling. Tiny model, real shapes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.models import TINY, init_lora_params, init_params


@pytest.mark.slow
class TestLongContextLearner:
    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_4k_token_step_under_sequence_parallelism(self, impl):
        """One GRPO step at prompt 256 + answer 3840 = 4096 tokens, sequence
        sharded over sp=2 with remat and chunked CE — config 4's learner
        shape. Loss must be finite and the adapter must move."""
        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
        from distrl_llm_tpu.parallel.mesh import _make_mesh

        mesh = _make_mesh(jax.devices(), tp=1, sp=2, fsdp=1)
        params = init_params(jax.random.PRNGKey(0), TINY)
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        opt = make_optimizer(1e-3, use_8bit=True)
        rng = np.random.default_rng(0)
        n, p_len, t_len = 2, 256, 3840
        batch = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, p_len)), jnp.int32),
            prompt_mask=jnp.ones((n, p_len), jnp.int32),
            answer_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, t_len)), jnp.int32),
            answer_mask=jnp.ones((n, t_len), jnp.int32),
            coeffs=jnp.asarray([1.0, -0.5], jnp.float32),
            sample_mask=jnp.ones((n,), jnp.float32),
        )
        step = make_train_step(
            TINY, learner_type="grpo", optimizer=opt, lora_scale=0.5,
            micro_size=2, attn_impl=impl, attn_mesh=mesh, donate=False,
            remat=True, logit_chunk=256,
        )
        new_lora, _, loss = step(lora, opt.init(lora), params, batch)
        assert np.isfinite(float(loss))
        moved = any(
            float(jnp.abs(a - b).max()) > 0
            for a, b in zip(
                jax.tree_util.tree_leaves(lora),
                jax.tree_util.tree_leaves(new_lora),
            )
        )
        assert moved


class TestLongDecode:
    @pytest.mark.slow
    def test_paged_decode_past_reference_ceiling(self):
        """The paged engine decodes 2,048 new tokens (refill scheduler) —
        past the reference's hard 1,200 ceiling — with correct lengths."""
        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

        params = init_params(jax.random.PRNGKey(0), TINY)
        # sentinel EOS id no sample can hit: every row must decode the full
        # 2,048 tokens, so the packed page pool genuinely holds sequences
        # past the reference ceiling (a tiny vocab otherwise samples a real
        # EOS within a few hundred steps)
        engine = PagedGenerationEngine(
            TINY, max_prompt_tokens=32, max_new_tokens=2048,
            eos_token_ids=[-1], pad_token_id=0,
            cache_dtype=jnp.float32, page_size=128,
            scheduler="refill", max_concurrent_rows=2,
        )
        rng = np.random.default_rng(0)
        ids = rng.integers(1, TINY.vocab_size - 1, (2, 32)).astype(np.int32)
        mask = np.ones_like(ids)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=2048, temperature=1.0, n=2),
            jax.random.PRNGKey(1),
        )
        assert res.tokens.shape == (2, 2, 2048)
        np.testing.assert_array_equal(res.lengths, 2048)
