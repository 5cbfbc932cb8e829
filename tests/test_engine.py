"""Generation engine tests: greedy-vs-naive equivalence, EOS early stop,
candidate fan-out, padding discipline (the FakeEngine-free core of SURVEY §4's
integration strategy — the engine itself runs on tiny models in CI)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine import GenerationEngine
from distrl_llm_tpu.models import TINY, forward, init_params


P_LEN = 8


@pytest.fixture(scope="module")
def setup():
    params = init_params(jax.random.PRNGKey(7), TINY)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, TINY.vocab_size, size=(2, P_LEN)).astype(np.int32)
    mask = np.ones((2, P_LEN), np.int32)
    mask[0, :3] = 0  # left padding on row 0
    ids[0, :3] = 0
    return params, ids, mask


def make_engine(max_new=6, eos=(), pad=0):
    return GenerationEngine(
        TINY, max_prompt_tokens=P_LEN, max_new_tokens=max_new,
        eos_token_ids=eos or [TINY.vocab_size - 1], pad_token_id=pad,
        cache_dtype=jnp.float32,
    )


def naive_greedy(params, ids, mask, steps):
    """Reference decode: full forward (no cache) re-run per token."""
    ids = jnp.asarray(ids)
    mask = jnp.asarray(mask)
    out = []
    for _ in range(steps):
        logits, _ = forward(params, TINY, ids, attention_mask=mask)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
        ids = jnp.concatenate([ids, tok[:, None]], axis=1)
        mask = jnp.concatenate([mask, jnp.ones((ids.shape[0], 1), jnp.int32)], axis=1)
    return np.stack(out, axis=1)  # [B, steps]


class TestGreedyEquivalence:
    @pytest.mark.slow
    def test_engine_matches_naive_full_forward(self, setup):
        params, ids, mask = setup
        engine = make_engine(max_new=6)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=6, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        expected = naive_greedy(params, ids, mask, 6)
        np.testing.assert_array_equal(res.tokens[:, 0, :], expected)
        np.testing.assert_array_equal(res.lengths[:, 0], [6, 6])


class TestEosStop:
    def test_row_stops_at_eos_and_pads(self, setup):
        params, ids, mask = setup
        expected = naive_greedy(params, ids, mask, 6)
        # make the token row 0 greedily emits at step 2 the EOS
        eos = int(expected[0, 2])
        engine = make_engine(max_new=6, eos=[eos], pad=0)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=6, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        assert res.lengths[0, 0] == 3  # tokens at steps 0,1,2 incl. EOS
        np.testing.assert_array_equal(res.tokens[0, 0, :3], expected[0, :3])
        np.testing.assert_array_equal(res.tokens[0, 0, 3:], 0)  # pad after EOS
        # row 1 unaffected unless it also hits eos
        if eos not in expected[1]:
            assert res.lengths[1, 0] == 6

    def test_all_rows_done_exits_early(self, setup):
        params, ids, mask = setup
        expected = naive_greedy(params, ids, mask, 1)
        engine = make_engine(max_new=50, eos=[int(expected[0, 0]), int(expected[1, 0])])
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=50, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        np.testing.assert_array_equal(res.lengths[:, 0], [1, 1])


class TestCandidates:
    def test_fanout_shapes_and_grouping(self, setup):
        params, ids, mask = setup
        engine = make_engine(max_new=4)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=4, temperature=1.5, n=5),
            jax.random.PRNGKey(3),
        )
        assert res.tokens.shape == (2, 5, 4)
        assert res.lengths.shape == (2, 5)

    def test_candidates_differ_under_sampling(self, setup):
        params, ids, mask = setup
        engine = make_engine(max_new=8)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=8, temperature=2.0, n=8),
            jax.random.PRNGKey(4),
        )
        unique = {tuple(res.tokens[0, j]) for j in range(8)}
        assert len(unique) > 1

    def test_greedy_candidates_identical(self, setup):
        params, ids, mask = setup
        engine = make_engine(max_new=4)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=4, temperature=0.0, n=3),
            jax.random.PRNGKey(5),
        )
        for j in range(1, 3):
            np.testing.assert_array_equal(res.tokens[:, j], res.tokens[:, 0])


class TestValidation:
    def test_wrong_prompt_pad_raises(self, setup):
        params, ids, mask = setup
        engine = make_engine()
        with pytest.raises(ValueError, match="padded"):
            engine.generate(
                params, None, ids[:, :4], mask[:, :4],
                SamplingConfig(max_tokens=4, n=1), jax.random.PRNGKey(0),
            )


class TestLengthBucketing:
    """SURVEY §2b N1: short batches run at a smaller compiled bucket with
    identical outputs (left-pad columns are fully masked, so dropping them
    cannot change the math)."""

    def make_bucketed(self, buckets, max_new=6):
        return GenerationEngine(
            TINY, max_prompt_tokens=P_LEN, max_new_tokens=max_new,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32, prompt_buckets=buckets,
        )

    @pytest.mark.slow
    def test_short_batch_uses_small_bucket(self, setup):
        params, ids, mask = setup
        # longest real prompt: row 1 with 8 real tokens → full bucket; shrink
        # both rows to ≤4 real tokens to hit the small bucket
        ids2, mask2 = ids.copy(), mask.copy()
        ids2[:, :4] = 0
        mask2[:, :4] = 0
        engine = self.make_bucketed([4])
        res = engine.generate(
            params, None, ids2, mask2,
            SamplingConfig(max_tokens=6, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        assert list(engine._compiled) == [4]
        expected = naive_greedy(params, ids2, mask2, 6)
        np.testing.assert_array_equal(res.tokens[:, 0, :], expected)

    @pytest.mark.slow
    def test_long_batch_uses_full_bucket(self, setup):
        params, ids, mask = setup
        engine = self.make_bucketed([4])
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=6, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        assert list(engine._compiled) == [P_LEN]
        expected = naive_greedy(params, ids, mask, 6)
        np.testing.assert_array_equal(res.tokens[:, 0, :], expected)

    @pytest.mark.slow
    def test_bucket_choice_matches_unbucketed_outputs(self, setup):
        params, ids, mask = setup
        ids2, mask2 = ids.copy(), mask.copy()
        ids2[:, :4] = 0
        mask2[:, :4] = 0
        plain = make_engine(max_new=6).generate(
            params, None, ids2, mask2,
            SamplingConfig(max_tokens=6, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        bucketed = self.make_bucketed([4]).generate(
            params, None, ids2, mask2,
            SamplingConfig(max_tokens=6, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        np.testing.assert_array_equal(plain.tokens, bucketed.tokens)
        np.testing.assert_array_equal(plain.lengths, bucketed.lengths)

    def test_invalid_buckets_raise(self):
        with pytest.raises(ValueError, match="buckets"):
            self.make_bucketed([0])
        with pytest.raises(ValueError, match="buckets"):
            self.make_bucketed([P_LEN + 1])


class TestWaveScheduling:
    """max_concurrent_rows runs rounds as sequential waves (vLLM
    max_num_seqs); greedy results must equal the unlimited path."""

    @pytest.mark.slow
    def test_waves_match_unlimited_greedy(self, setup):
        params, ids, mask = setup
        cfg = SamplingConfig(max_tokens=4, temperature=0.0, n=2)
        want = make_engine(max_new=4).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        waved = GenerationEngine(
            TINY, max_prompt_tokens=P_LEN, max_new_tokens=4,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32, max_concurrent_rows=2,  # 1 prompt/wave
        ).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(waved.tokens, want.tokens)
        np.testing.assert_array_equal(waved.lengths, want.lengths)

    @pytest.mark.slow
    def test_tail_wave_pads_with_dead_rows(self, setup):
        params, ids, mask = setup
        # 3 prompts, 2 per wave → tail wave has 1 real + 1 dead row
        ids3 = np.concatenate([ids, ids[:1]], axis=0)
        mask3 = np.concatenate([mask, mask[:1]], axis=0)
        cfg = SamplingConfig(max_tokens=4, temperature=0.0, n=1)
        want = make_engine(max_new=4).generate(
            params, None, ids3, mask3, cfg, jax.random.PRNGKey(0))
        waved = GenerationEngine(
            TINY, max_prompt_tokens=P_LEN, max_new_tokens=4,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32, max_concurrent_rows=2,
        ).generate(params, None, ids3, mask3, cfg, jax.random.PRNGKey(0))
        assert waved.tokens.shape == want.tokens.shape == (3, 1, 4)
        np.testing.assert_array_equal(waved.tokens, want.tokens)


class TestTopPImplOverride:
    """SamplingConfig.top_p_impl plumbs through to the decode step: the
    multiway filter must produce a working round, and greedy decoding must
    be impl-invariant (temperature 0 bypasses the filter)."""

    @pytest.mark.slow
    def test_multiway_round_and_greedy_invariance(self, setup):
        params, ids, mask = setup
        eng = make_engine(max_new=6)
        outs = {}
        for impl in (None, "bisect_mw", "exact"):
            res = eng.generate(
                params, None, ids, mask,
                SamplingConfig(max_tokens=6, temperature=0.0, n=1,
                               top_p_impl=impl),
                jax.random.PRNGKey(0),
            )
            outs[impl] = np.asarray(res.tokens)
        np.testing.assert_array_equal(outs[None], outs["bisect_mw"])
        np.testing.assert_array_equal(outs[None], outs["exact"])

    def test_multiway_sampling_round_completes(self, setup):
        params, ids, mask = setup
        eng = make_engine(max_new=5)
        res = eng.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=5, temperature=1.2, top_p=0.9, n=2,
                           top_p_impl="bisect_mw"),
            jax.random.PRNGKey(1),
        )
        assert res.tokens.shape == (2, 2, 5)
        assert (np.asarray(res.lengths) >= 0).all()

    def test_invalid_impl_rejected(self):
        with pytest.raises(ValueError, match="top_p_impl"):
            SamplingConfig(top_p_impl="nope").resolved_top_p_impl()


class TestInt8KvCache:
    """Dense-engine int8 KV: fused-dequant attention must track the f32
    cache closely enough that greedy decoding stays coherent end-to-end."""

    def test_generate_runs_and_shapes(self, setup):
        params, ids, mask = setup
        eng = GenerationEngine(
            TINY, max_prompt_tokens=P_LEN, max_new_tokens=6,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            kv_quant="int8",
        )
        res = eng.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=6, temperature=0.0, n=2),
            jax.random.PRNGKey(0),
        )
        assert res.tokens.shape == (2, 2, 6)
        assert np.asarray(res.tokens).max() < TINY.vocab_size

    @pytest.mark.slow
    def test_greedy_mostly_matches_f32_cache(self, setup):
        """int8 quantization perturbs logits by ~1e-3 — on a random-init
        model ties can flip a token, but the sequences should agree at the
        first decoded position for every row (largest logit gap)."""
        params, ids, mask = setup
        kw = dict(max_prompt_tokens=P_LEN, max_new_tokens=4,
                  eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0)
        e_f32 = GenerationEngine(TINY, cache_dtype=jnp.float32, **kw)
        e_i8 = GenerationEngine(TINY, kv_quant="int8", **kw)
        sc = SamplingConfig(max_tokens=4, temperature=0.0, n=1)
        r_f32 = e_f32.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        r_i8 = e_i8.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        t_f32 = np.asarray(r_f32.tokens)[:, 0]
        t_i8 = np.asarray(r_i8.tokens)[:, 0]
        np.testing.assert_array_equal(t_f32[:, 0], t_i8[:, 0])
        # and the overall agreement should be high
        agree = (t_f32 == t_i8).mean()
        assert agree >= 0.5, f"agreement {agree}"

    def test_invalid_kv_quant_rejected(self):
        with pytest.raises(ValueError, match="kv_quant"):
            GenerationEngine(
                TINY, max_prompt_tokens=8, max_new_tokens=4,
                eos_token_ids=[1], pad_token_id=0, kv_quant="int4",
            )


class TestScanChunk:
    """K-steps-per-dispatch decode (``scan_chunk``): the chunked program must
    be bit-identical to the host-dispatched loop — sampling rng depends only
    on the step index (``fold_in(rng, step)``), so any divergence is a bug in
    the chunk body, its overshoot guard, or the done masking."""

    def _pair(self, scan_chunk, max_new=6, capture=False, eos=()):
        kw = dict(max_prompt_tokens=P_LEN, max_new_tokens=max_new,
                  eos_token_ids=eos or [TINY.vocab_size - 1], pad_token_id=0,
                  cache_dtype=jnp.float32, capture_logprobs=capture)
        # chunk engines decode with the mulred cache read (the dot
        # formulation relayout-copies the scanned carry on TPU); pin the
        # host reference to the same math so this class compares DISPATCH
        # modes bit-exactly, not float formulations
        host = GenerationEngine(TINY, cache_read_formulation="mulred", **kw)
        chunked = GenerationEngine(TINY, scan_chunk=scan_chunk, **kw)
        return host, chunked

    def test_greedy_parity_chunk_divides(self, setup):
        params, ids, mask = setup
        host, chunked = self._pair(scan_chunk=3, max_new=6)
        sc = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        a = host.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        b = chunked.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.lengths, b.lengths)

    def test_chunk_matches_default_dot_host_decode(self, setup):
        """TestScanChunk pins its host reference to mulred for
        bit-exact dispatch comparison, which left the DEFAULT dot-formulation
        host path untested against the chunk path at engine level. This is
        the tolerance-based cross-formulation anchor: a default engine (dot
        cache read) and a chunked engine (mulred cache read) greedy-decode
        the same prompts; tokens must agree and the captured behavior
        logprobs must match to float tolerance (the two formulations are the
        same math in a different contraction order — see _gqa_mulred)."""
        params, ids, mask = setup
        kw = dict(max_prompt_tokens=P_LEN, max_new_tokens=6,
                  eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
                  cache_dtype=jnp.float32, capture_logprobs=True)
        host = GenerationEngine(TINY, **kw)  # default path: dot formulation
        assert host.cache_read_formulation == "dot"
        chunked = GenerationEngine(TINY, scan_chunk=3, **kw)
        sc = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        a = host.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        b = chunked.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.lengths, b.lengths)
        np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=1e-4, atol=1e-5)

    def test_structural_swap_rebuilds_chunk_program(self, setup):
        """Regression: an in-flight swap to a STRUCTURALLY
        different adapter (None-adapter round receiving its first adapter)
        lands at a chunk boundary; the chunk program is a compiled
        executable that raises on structure change instead of retracing —
        the swap-aware step must refetch from the signature-keyed cache.
        Pushing before generate makes the boundary deterministic (step 0)."""
        from distrl_llm_tpu.models import init_lora_params

        params, ids, mask = setup
        _, chunked = self._pair(scan_chunk=3, max_new=6)
        adapter = init_lora_params(jax.random.PRNGKey(5), TINY, rank=4)
        chunked.push_lora(adapter)
        sc = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        out = chunked.generate(
            params, None, ids, mask, sc, jax.random.PRNGKey(0)
        )
        assert chunked.last_swap_steps == [0]
        assert chunked.scan_chunk_active
        # the swap really took effect: output matches a round that passed
        # the adapter directly (greedy, same rng)
        direct, _ = self._pair(scan_chunk=3, max_new=6)
        want = direct.generate(
            params, adapter, ids, mask, sc, jax.random.PRNGKey(0)
        )
        np.testing.assert_array_equal(out.tokens, want.tokens)

    def test_pick_chunk_prefers_divisors(self):
        """The host cadence never lets a chunk cross max_steps: pick_chunk
        returns the largest divisor ≤ scan_chunk when that keeps most of
        the amortization, else min(scan_chunk, max_steps) with the
        remainder handled per-step (run_nondivisor_tail)."""
        from distrl_llm_tpu.engine.engine import pick_chunk

        assert pick_chunk(16, 1200) == 16   # divides exactly
        assert pick_chunk(64, 1200) == 60   # divisor 60 beats 64 + 48-tail
        assert pick_chunk(4, 6) == 3        # small-scale divisor
        assert pick_chunk(4, 7) == 4        # prime: keep 4, tail of 3
        assert pick_chunk(8, 4) == 4        # chunk larger than the wave
        assert pick_chunk(2, 1) == 1

    @pytest.mark.slow
    def test_sampled_parity_with_overshoot_and_logprobs(self, setup):
        """scan_chunk=4 over max_new=6 (pick_chunk → 3, two exact chunks):
        tokens, lengths AND captured behavior logprobs must be bit-identical
        to the per-step loop."""
        params, ids, mask = setup
        host, chunked = self._pair(scan_chunk=4, max_new=6, capture=True)
        sc = SamplingConfig(max_tokens=6, temperature=1.1, top_p=0.9, n=2)
        a = host.generate(params, None, ids, mask, sc, jax.random.PRNGKey(3))
        b = chunked.generate(params, None, ids, mask, sc, jax.random.PRNGKey(3))
        assert chunked.scan_chunk_active  # chunked program ran, not a fallback
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.lengths, b.lengths)
        np.testing.assert_array_equal(a.logprobs, b.logprobs)

    def test_nondivisor_tail_parity(self, setup):
        """Prime max_new=7 with scan_chunk=4 forces the per-step tail
        (pick_chunk keeps k=4: one full chunk + 3 tail steps) — the tail
        must produce the same tokens/lengths/logprobs as the host loop,
        and the chunk program must still have run."""
        params, ids, mask = setup
        host, chunked = self._pair(scan_chunk=4, max_new=7, capture=True)
        sc = SamplingConfig(max_tokens=7, temperature=1.1, top_p=0.9, n=2)
        a = host.generate(params, None, ids, mask, sc, jax.random.PRNGKey(3))
        b = chunked.generate(params, None, ids, mask, sc, jax.random.PRNGKey(3))
        assert chunked.scan_chunk_active
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.lengths, b.lengths)
        np.testing.assert_array_equal(a.logprobs, b.logprobs)

    @pytest.mark.slow
    def test_eos_stop_parity(self, setup):
        """Rows that hit EOS mid-chunk must stop, pad, and stop counting
        exactly as in the host loop (the done masking rides inside the
        scanned body)."""
        params, ids, mask = setup
        probe = make_engine(max_new=1).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=1, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        eos = [int(np.asarray(probe.tokens)[0, 0, 0])]  # row 0 stops at step 1
        host, chunked = self._pair(scan_chunk=5, max_new=8, eos=eos)
        sc = SamplingConfig(max_tokens=8, temperature=0.0, n=1)
        a = host.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        b = chunked.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.lengths, b.lengths)

    @pytest.mark.slow
    def test_chunk_larger_than_max_steps(self, setup):
        params, ids, mask = setup
        host, chunked = self._pair(scan_chunk=16, max_new=3)
        sc = SamplingConfig(max_tokens=3, temperature=0.0, n=1)
        a = host.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        b = chunked.generate(params, None, ids, mask, sc, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_negative_scan_chunk_rejected(self):
        with pytest.raises(ValueError, match="scan_chunk"):
            GenerationEngine(
                TINY, max_prompt_tokens=8, max_new_tokens=4,
                eos_token_ids=[1], pad_token_id=0, scan_chunk=-1,
            )

    @pytest.mark.slow
    def test_none_then_adapter_rounds_share_engine(self, setup):
        """Round with lora=None then a round with an adapter (and back):
        a Compiled chunk program raises on a structurally different pytree
        instead of retracing, so the cache must key on the adapter
        signature (round-3 review finding)."""
        from distrl_llm_tpu.models import init_lora_params

        params, ids, mask = setup
        _, chunked = self._pair(scan_chunk=3, max_new=6)
        host, _ = self._pair(scan_chunk=0, max_new=6)
        lora = init_lora_params(jax.random.PRNGKey(5), TINY, rank=4)
        sc = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        for adapter in (None, lora, None):
            a = host.generate(params, adapter, ids, mask, sc, jax.random.PRNGKey(0))
            b = chunked.generate(params, adapter, ids, mask, sc, jax.random.PRNGKey(0))
            np.testing.assert_array_equal(a.tokens, b.tokens)
