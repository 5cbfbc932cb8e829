"""Weight-only quantization (ops/quant.py) — the N4/bitsandbytes equivalent.

Covers: round-trip error bounds, the dequant-fused matmul in ops.linear,
a quantized-base forward against the dense forward, engine generation over a
quantized base, a train step (grads flow only through LoRA), and partition
specs for the container leaves.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.models import TINY, forward, init_lora_params, init_params
from distrl_llm_tpu.ops.linear import linear
from distrl_llm_tpu.ops.quant import (
    QUANT_TARGETS,
    default_group_size,
    dequantize,
    is_quantized,
    quant_bits_for,
    quantize,
    quantize_params,
)


def rand_w(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape) * 0.05, jnp.float32)


class TestRoundTrip:
    def test_int8_per_column_error(self):
        w = rand_w((256, 128))
        deq = dequantize(quantize(w, bits=8), dtype=jnp.float32)
        err = np.abs(np.asarray(deq - w)).max()
        # absmax/127 quantization step bounds the error at scale/2
        step = np.abs(np.asarray(w)).max(axis=0) / 127.0
        assert err <= step.max() * 0.51 + 1e-8

    def test_int4_blockwise_better_than_per_column(self):
        w = rand_w((256, 64), seed=1)
        # plant an outlier so per-column scales suffer
        w = w.at[0, 0].set(2.0)
        err_pc = np.abs(np.asarray(dequantize(quantize(w, bits=4)) - w)).mean()
        err_blk = np.abs(
            np.asarray(dequantize(quantize(w, bits=4, group_size=64)) - w)
        ).mean()
        assert err_blk < err_pc

    def test_stacked_leading_dims(self):
        w = rand_w((3, 128, 64), seed=2)  # [L, in, out]
        qw = quantize(w, bits=8, group_size=32)
        assert qw["q"].shape == (3, 4, 32, 64)
        assert qw["scale"].shape == (3, 4, 1, 64)
        deq = dequantize(qw, dtype=jnp.float32)
        assert deq.shape == w.shape
        np.testing.assert_allclose(np.asarray(deq), np.asarray(w), atol=2e-3)

    def test_zero_weight_column_is_exact(self):
        w = jnp.zeros((64, 8))
        deq = dequantize(quantize(w, bits=8))
        assert np.asarray(deq).sum() == 0.0

    def test_bad_bits_raises(self):
        with pytest.raises(ValueError, match="bits"):
            quantize(rand_w((8, 8)), bits=3)

    def test_bad_group_raises(self):
        with pytest.raises(ValueError, match="group_size"):
            quantize(rand_w((100, 8)), bits=8, group_size=64)


class TestLinearDispatch:
    def test_quantized_matmul_close_to_dense(self):
        w = rand_w((128, 96), seed=3)
        x = rand_w((4, 128), seed=4)
        dense = linear(x, w)
        quant = linear(x, quantize(w, bits=8, group_size=32))
        np.testing.assert_allclose(
            np.asarray(quant), np.asarray(dense), atol=2e-3, rtol=0.05
        )

    def test_bias_applies(self):
        w, b = rand_w((16, 8)), jnp.ones((8,))
        y = linear(jnp.ones((2, 16)), quantize(w, bits=8), b)
        y0 = linear(jnp.ones((2, 16)), quantize(w, bits=8))
        np.testing.assert_allclose(np.asarray(y - y0), 1.0, atol=1e-6)


class TestQuantizedModel:
    def test_quantize_params_targets_only_projections(self):
        params = init_params(jax.random.PRNGKey(0), TINY)
        qp = quantize_params(params, bits=8)
        for name in QUANT_TARGETS:
            assert is_quantized(qp["layers"][name])
        assert not is_quantized(qp["layers"]["attn_norm"])
        assert not isinstance(qp["embed"], dict)
        # biases untouched
        assert qp["layers"]["bq"].dtype == params["layers"]["bq"].dtype

    def test_forward_close_to_dense(self):
        params = init_params(jax.random.PRNGKey(0), TINY)
        qp = quantize_params(params, bits=8, group_size=16)
        ids = jnp.asarray(np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 12)))
        dense, _ = forward(params, TINY, ids)
        quant, _ = forward(qp, TINY, ids)
        # int8 groupwise keeps logits close enough for greedy agreement
        assert (
            np.asarray(dense.argmax(-1)) == np.asarray(quant.argmax(-1))
        ).mean() > 0.9

    @pytest.mark.slow
    def test_forward_with_lora_and_cache(self):
        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine import GenerationEngine

        params = quantize_params(
            init_params(jax.random.PRNGKey(0), TINY), bits=4, group_size=16
        )
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        eng = GenerationEngine(
            TINY, max_prompt_tokens=8, max_new_tokens=8,
            eos_token_ids=[1], pad_token_id=0,
        )
        prompts = np.random.default_rng(0).integers(2, TINY.vocab_size, (2, 8)).astype(np.int32)
        res = eng.generate(
            params, lora, prompts, np.ones_like(prompts),
            SamplingConfig(max_tokens=8, temperature=1.0, top_p=0.95, n=2),
            jax.random.PRNGKey(2),
        )
        assert res.tokens.shape == (2, 2, 8)
        assert np.isfinite(res.lengths).all()

    def test_train_step_over_quantized_base(self):
        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step

        params = quantize_params(
            init_params(jax.random.PRNGKey(0), TINY), bits=8, group_size=16
        )
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        opt = make_optimizer(1e-3, use_8bit=False)
        opt_state = opt.init(lora)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=opt, lora_scale=0.5,
            micro_size=2, donate=False,
        )
        rng = np.random.default_rng(0)
        batch = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(2, TINY.vocab_size, (2, 6)), jnp.int32),
            prompt_mask=jnp.ones((2, 6), jnp.int32),
            answer_ids=jnp.asarray(rng.integers(2, TINY.vocab_size, (2, 4)), jnp.int32),
            answer_mask=jnp.ones((2, 4), jnp.int32),
            coeffs=jnp.asarray([1.0, -0.5], jnp.float32),
            sample_mask=jnp.ones((2,), jnp.float32),
        )
        new_lora, _, loss = step(lora, opt_state, params, batch)
        assert np.isfinite(float(loss))
        changed = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), lora, new_lora
        )
        assert max(jax.tree_util.tree_leaves(changed)) > 0.0


class TestQuantSharding:
    def test_specs_cover_quantized_tree(self):
        from jax.sharding import PartitionSpec as P

        from distrl_llm_tpu.parallel import param_specs

        params = quantize_params(init_params(jax.random.PRNGKey(0), TINY), bits=8)
        specs = param_specs(params)
        leaves_p = jax.tree_util.tree_leaves(params)
        leaves_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_p) == len(leaves_s)
        # spec ndim must match each leaf
        flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
        flat_s = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
        for (kp, leaf), (ks, spec) in zip(flat_p, flat_s):
            assert len(spec) == leaf.ndim, (kp, spec, leaf.shape)

    @pytest.mark.slow
    def test_sharded_quantized_forward_matches(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distrl_llm_tpu.parallel import shard_tree
        from distrl_llm_tpu.parallel.mesh import _make_mesh

        params = quantize_params(
            init_params(jax.random.PRNGKey(0), TINY), bits=8, group_size=16
        )
        ids = np.random.default_rng(0).integers(0, TINY.vocab_size, size=(4, 10))
        expected, _ = forward(params, TINY, jnp.asarray(ids))
        mesh = _make_mesh(jax.devices(), 2, 1, 2)
        sharded = shard_tree(params, mesh)
        ids_s = jax.device_put(jnp.asarray(ids), NamedSharding(mesh, P("dp", None)))

        @jax.jit
        def run(p, i):
            logits, _ = forward(p, TINY, i)
            return logits

        got = run(sharded, ids_s)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), atol=5e-4, rtol=5e-4
        )


class TestConfigMapping:
    def test_bits_mapping(self):
        assert quant_bits_for("none") is None
        assert quant_bits_for("int8") == 8
        assert quant_bits_for("int4") == 4

    def test_default_groups(self):
        assert default_group_size(4) == 64
        assert default_group_size(8) is None


class TestEdgeCases:
    """ISSUE-15 satellite: the container format's sharp edges, pinned."""

    def test_non_divisible_group_tail_raises(self):
        # a group size that leaves a tail is a LOUD error, not a silently
        # mis-scaled last block (the engine would decode garbage): callers
        # pick a divisor or fall back to per-column scales (None)
        for d_in, g in ((100, 64), (96, 36), (64, 48)):
            with pytest.raises(ValueError, match="divide"):
                quantize(rand_w((d_in, 8)), bits=8, group_size=g)

    def test_odd_input_dim_per_column_ok(self):
        # None = one group spanning the whole (odd) input dim — always legal
        w = rand_w((97, 8), seed=5)
        deq = dequantize(quantize(w, bits=8), dtype=jnp.float32)
        assert deq.shape == w.shape

    def test_scales_pinned_f32(self):
        # bf16-rounding the scales stacks ~0.4% error on the quantization
        # error (ops/linear.py) — the container contract stores them f32
        # regardless of the source dtype
        for src in (jnp.float32, jnp.bfloat16):
            qw = quantize(rand_w((64, 8)).astype(src), bits=8, group_size=16)
            assert qw["scale"].dtype == jnp.float32
        qp = quantize_params(
            init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16),
            bits=8, group_size=16,
        )
        for name in QUANT_TARGETS:
            assert qp["layers"][name]["scale"].dtype == jnp.float32

    def test_quantize_params_leaves_lm_head_untouched(self):
        # untied-embedding config: lm_head must stay a dense array (it is
        # not a QUANT_TARGET — mirrors bnb quantizing nn.Linear layers of
        # the decoder blocks only)
        import dataclasses

        from distrl_llm_tpu.models import TINY

        cfg = dataclasses.replace(TINY, tie_word_embeddings=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        qp = quantize_params(params, bits=8, group_size=16)
        assert not isinstance(qp["lm_head"], dict)
        assert qp["lm_head"].dtype == params["lm_head"].dtype
        assert not isinstance(qp["embed"], dict)
        assert not isinstance(qp["final_norm"], dict)
        assert not isinstance(qp["layers"]["attn_norm"], dict)
        assert not isinstance(qp["layers"]["mlp_norm"], dict)
