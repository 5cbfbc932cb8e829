"""The multiply+reduce decode-attention formulation (r5 silicon finding).

Inside a K-steps-per-dispatch scan program, ANY ``dot_general`` over the
carried KV cache makes TPU layout assignment relayout the operand to a
B-minormost layout — one cache-leaf-sized conversion copy per leaf per
iteration, which defeats in-place aliasing and OOMs the chunk program
(the 9-variant formulation matrix in tools/chunk_alias_bisect.py; the dot
path is the r3-proven fast read for SINGLE-step dispatch, so it stays the
default there). ``formulation="mulred"`` reads the cache with fused
multiply+reduce instead; these tests pin it numerically against the dot
path and pin the engine-level wiring.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu.engine.engine import GenerationEngine
from distrl_llm_tpu.models import TINY
from distrl_llm_tpu.ops.attention import (
    attention_cached,
    attention_cached_quant,
    causal_padding_mask,
    quantize_kv_position,
)

def _decode_inputs(seed=0, b=3, h=4, kh=2, d=8, s=12, q_dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), q_dtype)
    k = jax.random.normal(ks[1], (b, kh, d, s), q_dtype)
    v = jax.random.normal(ks[2], (b, kh, d, s), q_dtype)
    valid = (jax.random.uniform(ks[3], (b, s)) > 0.2).astype(jnp.int32)
    valid = valid.at[:, 0].set(1)  # never a fully-masked row
    mask = causal_padding_mask(valid, q_len=1, q_offset=s - 1)
    return q, k, v, mask


class TestMulredOp:
    def test_matches_dot_f32(self):
        q, k, v, mask = _decode_inputs()
        a = attention_cached(q, k, v, mask)
        b = attention_cached(q, k, v, mask, formulation="mulred")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_against_f32_dot(self):
        """CPU's XLA DotThunk can't run the bf16 dot baseline at all
        (bf16 x bf16 = f32 unsupported — the reason the CPU suite uses f32
        caches), so pin bf16 mulred against the f32 dot reference at bf16
        resolution instead."""
        q, k, v, mask = _decode_inputs(q_dtype=jnp.bfloat16)
        ref = attention_cached(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), mask)
        got = jax.jit(partial(attention_cached, formulation="mulred"))(
            q, k, v, mask).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-2, atol=2e-2)

    def test_quant_matches_dot(self):
        q, k, v, mask = _decode_inputs()
        k8, ks_ = quantize_kv_position(k)
        v8, vs_ = quantize_kv_position(v)
        a = attention_cached_quant(q, k8, ks_, v8, vs_, mask)
        b = attention_cached_quant(q, k8, ks_, v8, vs_, mask,
                                   formulation="mulred")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_per_head_mask(self):
        q, k, v, _ = _decode_inputs()
        b, _, h, _ = q.shape
        s = k.shape[-1]
        mask = jax.random.uniform(jax.random.PRNGKey(9), (b, h, 1, s)) > 0.3
        mask = mask.at[..., 0].set(True)
        a = attention_cached(q, k, v, mask)
        m = attention_cached(q, k, v, mask, formulation="mulred")
        np.testing.assert_allclose(np.asarray(a), np.asarray(m),
                                   rtol=1e-5, atol=1e-5)

    def test_prefill_ignores_mulred(self):
        """Sq>1 (prefill through the cached path) must use the dot path —
        mulred is a decode-only formulation."""
        q, k, v, _ = _decode_inputs()
        qp = jnp.concatenate([q, q], axis=1)  # Sq=2
        valid = jnp.ones((q.shape[0], k.shape[-1]), jnp.int32)
        mask = causal_padding_mask(valid, q_len=2, q_offset=k.shape[-1] - 2)
        a = attention_cached(qp, k, v, mask)
        b = attention_cached(qp, k, v, mask, formulation="mulred")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFormulationValidation:
    """An unrecognized formulation string must raise, not
    silently fall back to the dot path (a typo like 'mul_red' inside a scan
    program would reintroduce the relayout/OOM the flag avoids)."""

    def test_typo_raises_on_cached(self):
        q, k, v, mask = _decode_inputs()
        with pytest.raises(ValueError, match="formulation"):
            attention_cached(q, k, v, mask, formulation="mul_red")

    def test_typo_raises_on_cached_quant(self):
        q, k, v, mask = _decode_inputs()
        k8, ks_ = quantize_kv_position(k)
        v8, vs_ = quantize_kv_position(v)
        with pytest.raises(ValueError, match="formulation"):
            attention_cached_quant(q, k8, ks_, v8, vs_, mask,
                                   formulation="dot_general")


class TestEngineWiring:
    def _engine(self, **kw):
        return GenerationEngine(
            TINY, max_prompt_tokens=8, max_new_tokens=4,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0, **kw)

    def test_auto_formulation(self):
        assert self._engine().cache_read_formulation == "dot"
        assert self._engine(scan_chunk=4).cache_read_formulation == "mulred"

    def test_explicit_override(self):
        e = self._engine(cache_read_formulation="mulred")
        assert e.cache_read_formulation == "mulred"

    def test_invalid_rejected(self):
        with pytest.raises(ValueError, match="cache_read_formulation"):
            self._engine(cache_read_formulation="vpu")
