"""Paged-KV cache + engine tests (the N1 ragged decode path, ops/paged.py).

The Pallas kernel itself is TPU-only; CI exercises the jnp reference (same
semantics contract) plus full-engine equivalence against the dense engine's
greedy decode — the paged path must produce identical tokens, since packing
is a masked-attention-invariant position shift.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine.engine import GenerationEngine
from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine, _pack_rows
from distrl_llm_tpu.models import TINY, init_params
from distrl_llm_tpu.ops.attention import attention_reference, causal_padding_mask
from distrl_llm_tpu.ops.paged import (
    make_page_table,
    paged_attention_reference,
    pages_per_seq,
    write_prompt_to_pages,
    write_token_to_pages,
)

PS = 8  # tiny page size for tests


class TestPageTable:
    def test_identity_layout(self):
        t = make_page_table(3, 20, page_size=PS)
        assert t.shape == (3, 3)  # ceil(20/8) = 3 pages per row
        np.testing.assert_array_equal(t, [[0, 1, 2], [3, 4, 5], [6, 7, 8]])

    def test_pages_per_seq(self):
        assert pages_per_seq(16, 8) == 2
        assert pages_per_seq(17, 8) == 3


class TestPageWrites:
    def test_prompt_write_roundtrip(self):
        rng = np.random.default_rng(0)
        b, p, kh, hd = 2, 16, 2, 4
        pps = pages_per_seq(p, PS)
        kv = jnp.asarray(rng.normal(size=(b, p, kh, hd)), jnp.float32)
        pages = jnp.zeros((kh, b * pps, PS, hd), jnp.float32)
        table = jnp.asarray(make_page_table(b, p, PS))
        pages = write_prompt_to_pages(pages, kv, table, PS)
        # gather back row 1, position 11 → page 1 of row 1, slot 3
        got = pages[:, table[1, 11 // PS], 11 % PS]  # [K, hd]
        np.testing.assert_allclose(np.asarray(got), np.asarray(kv[1, 11]))

    def test_token_write(self):
        rng = np.random.default_rng(1)
        b, kh, hd = 3, 2, 4
        cap = 24
        pps = pages_per_seq(cap, PS)
        pages = jnp.zeros((kh, b * pps, PS, hd), jnp.float32)
        table = jnp.asarray(make_page_table(b, cap, PS))
        lengths = jnp.asarray([0, 9, 17])
        new = jnp.asarray(rng.normal(size=(b, kh, hd)), jnp.float32)
        pages = write_token_to_pages(pages, new, lengths, table, PS)
        for r, ln in enumerate([0, 9, 17]):
            got = pages[:, table[r, ln // PS], ln % PS]
            np.testing.assert_allclose(np.asarray(got), np.asarray(new[r]))


def _pool(rng, shape, quantized):
    """A pool with something in every slot (so an untouched slot can be told
    from a written one) and the same pool as numpy arrays, one per leaf."""
    from distrl_llm_tpu.ops.paged import quantize_pages

    pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pages = quantize_pages(pages) if quantized else pages.astype(jnp.bfloat16)
    return pages, [np.array(leaf) for leaf in jax.tree_util.tree_leaves(pages)]


def _loop_write(want, tok, lengths, table, ps, valid, quantized):
    """The plain write: row by row, head by head, one slot at a time."""
    from distrl_llm_tpu.ops.paged import quantize_pages

    tok = jnp.asarray(tok)  # the int8 codes and scales are per (row, head) vector
    vals = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(
        quantize_pages(tok) if quantized else tok.astype(jnp.bfloat16))]
    for r in range(tok.shape[0]):
        if not valid[r]:
            continue
        page, slot = table[r, lengths[r] // ps], lengths[r] % ps
        for h in range(tok.shape[1]):
            for leaf, val in zip(want, vals):
                leaf[h, page, slot] = val[r, h]


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("ps", [16, 64, 128])
@pytest.mark.parametrize("kh", [2, 4, 8])
class TestTokenWriteAgainstLoop:
    """``write_token_to_pages`` addresses (KV head, page, slot) point by point
    (ops/paged.py says why); the pools it leaves are those of a numpy loop,
    bit for bit, in both containers."""

    B, HD, PPS = 6, 8, 3

    def table(self, rng):
        # rows own scattered pages, and two pages of the pool belong to no row
        perm = rng.permutation(self.B * self.PPS + 2)[: self.B * self.PPS]
        return perm.reshape(self.B, self.PPS).astype(np.int32)

    def check(self, got, want):
        for g, w in zip(jax.tree_util.tree_leaves(got), want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g), w)

    def test_one_token(self, kh, ps, quantized):
        rng = np.random.default_rng(kh * 1000 + ps)
        shape = (kh, self.B * self.PPS + 2, ps, self.HD)
        pages, want = _pool(rng, shape, quantized)
        table = self.table(rng)
        # a page's last slot, the next page's first, the pool's first and last
        lengths = np.array([ps - 1, ps, 0, 3 * ps - 1, ps + 5, 2 * ps - 1], np.int32)
        tok = rng.normal(size=(self.B, kh, self.HD)).astype(np.float32)
        for valid in (None, np.array([True, False, True, True, False, True])):
            got = write_token_to_pages(
                pages, jnp.asarray(tok), jnp.asarray(lengths), jnp.asarray(table),
                ps, valid=None if valid is None else jnp.asarray(valid),
            )
            ref = [w.copy() for w in want]
            _loop_write(ref, tok, lengths, table, ps,
                        np.ones(self.B, bool) if valid is None else valid, quantized)
            self.check(got, ref)
            assert any((r != w).any() for r, w in zip(ref, want))

    def test_three_tokens_across_a_page_boundary(self, kh, ps, quantized):
        from distrl_llm_tpu.ops.paged import write_tokens_to_pages

        rng = np.random.default_rng(kh * 1000 + ps + 1)
        shape = (kh, self.B * self.PPS + 2, ps, self.HD)
        pages, want = _pool(rng, shape, quantized)
        table = self.table(rng)
        # slots ps-2, ps-1 | 0 for the first row; ps-1 | 0, 1 for the second
        lengths = np.array([ps - 2, ps - 1, 0, 2 * ps - 1, ps, 5], np.int32)
        toks = rng.normal(size=(self.B, 3, kh, self.HD)).astype(np.float32)
        valid = rng.random((self.B, 3)) < 0.7
        valid[0] = valid[1] = True
        got = write_tokens_to_pages(
            pages, jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(table), ps,
            valid=jnp.asarray(valid),
        )
        for i in range(3):
            _loop_write(want, toks[:, i], lengths + i, table, ps, valid[:, i], quantized)
        self.check(got, want)


class TestPagedAttentionReference:
    def test_matches_dense_masked_attention(self):
        """Reference paged attention over packed pages == dense attention over
        the same tokens with a length mask."""
        rng = np.random.default_rng(2)
        b, h, kh, hd = 3, 4, 2, 8
        cap = 24
        pps = pages_per_seq(cap, PS)
        lengths = jnp.asarray([5, 24, 13])
        q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, cap, kh, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, cap, kh, hd)), jnp.float32)

        table = jnp.asarray(make_page_table(b, cap, PS))
        k_pages = write_prompt_to_pages(
            jnp.zeros((kh, b * pps, PS, hd), jnp.float32), k, table, PS)
        v_pages = write_prompt_to_pages(
            jnp.zeros((kh, b * pps, PS, hd), jnp.float32), v, table, PS)
        got = paged_attention_reference(q, k_pages, v_pages, lengths, table)

        valid = (jnp.arange(cap)[None, :] < lengths[:, None]).astype(jnp.int32)
        mask = valid[:, None, None, :].astype(bool)  # [B,1,1,S]
        want = attention_reference(q[:, None], k, v, mask)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


class TestPackRows:
    def test_left_pad_removed(self):
        ids = jnp.asarray([[0, 0, 5, 6], [1, 2, 3, 4]])
        mask = jnp.asarray([[0, 0, 1, 1], [1, 1, 1, 1]])
        packed, pmask, real = _pack_rows(ids, mask)
        np.testing.assert_array_equal(np.asarray(packed), [[5, 6, 0, 0], [1, 2, 3, 4]])
        np.testing.assert_array_equal(np.asarray(pmask), [[1, 1, 0, 0], [1, 1, 1, 1]])
        np.testing.assert_array_equal(np.asarray(real), [2, 4])


P_LEN = 8


@pytest.fixture(scope="module")
def setup():
    params = init_params(jax.random.PRNGKey(7), TINY)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, TINY.vocab_size, size=(2, P_LEN)).astype(np.int32)
    mask = np.ones((2, P_LEN), np.int32)
    mask[0, :3] = 0
    ids[0, :3] = 0
    return params, ids, mask


def make_dense(max_new=6, eos=()):
    return GenerationEngine(
        TINY, max_prompt_tokens=P_LEN, max_new_tokens=max_new,
        eos_token_ids=eos or [TINY.vocab_size - 1], pad_token_id=0,
        cache_dtype=jnp.float32,
    )


def make_paged(max_new=6, eos=(), **kw):
    return PagedGenerationEngine(
        TINY, max_prompt_tokens=P_LEN, max_new_tokens=max_new,
        eos_token_ids=eos or [TINY.vocab_size - 1], pad_token_id=0,
        cache_dtype=jnp.float32, page_size=PS, **kw,
    )


class TestPagedEngine:
    def test_greedy_matches_dense_engine(self, setup):
        """Packing + paged reads are math-invariant: greedy tokens from the
        paged engine equal the dense engine's (which equals the naive full
        forward — test_engine.py)."""
        params, ids, mask = setup
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        dense = make_dense().generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        paged = make_paged().generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(paged.tokens, dense.tokens)
        np.testing.assert_array_equal(paged.lengths, dense.lengths)

    @pytest.mark.slow
    def test_eos_early_exit(self, setup):
        params, ids, mask = setup
        probe = make_paged(max_new=2).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=2, temperature=0.0, n=1), jax.random.PRNGKey(0),
        )
        eos = [int(probe.tokens[0, 0, 0]), int(probe.tokens[1, 0, 0])]
        engine = make_paged(max_new=50, eos=eos)
        res = engine.generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=50, temperature=0.0, n=1), jax.random.PRNGKey(0),
        )
        np.testing.assert_array_equal(res.lengths[:, 0], [1, 1])

    def test_candidate_fanout(self, setup):
        params, ids, mask = setup
        res = make_paged(max_new=4).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=4, temperature=1.5, n=5), jax.random.PRNGKey(3),
        )
        assert res.tokens.shape == (2, 5, 4)
        unique = {tuple(res.tokens[1, j]) for j in range(5)}
        assert len(unique) > 1


class TestPrefixSharing:
    """Candidates of one prompt share its full prompt pages; the KV pool
    shrinks from B·n to ~B prompt copies (vLLM prefix sharing)."""

    def test_candidates_share_full_prompt_pages(self, setup):
        from distrl_llm_tpu.engine.paged_engine import _paged_fanout
        import jax.numpy as jnp
        from functools import partial

        b, n, pp, priv = 2, 3, 2, 2
        kh, hd = 2, 4
        prompt_pages = tuple(
            jnp.arange(kh * b * pp * PS * hd, dtype=jnp.float32).reshape(
                kh, b * pp, PS, hd
            )
            for _ in range(1)
        )
        real_len = jnp.asarray([PS + 3, 5])  # row 0: 1 full page; row 1: none
        state, table = jax.jit(
            partial(_paged_fanout, prompt_pages=pp, private_pages=priv,
                    page_size=PS),
            static_argnames=("n", "b", "max_steps"),
        )(prompt_pages, prompt_pages, jnp.zeros((b, 8)), real_len,
          jnp.ones((b,), bool), n=n, b=b, max_steps=4)
        table = np.asarray(table)
        # prompt 0's three candidates all point column 0 at the SAME shared page
        assert table[0, 0] == table[1, 0] == table[2, 0] == 0
        # their partial/private pages are DISTINCT
        assert len({table[j, 1] for j in range(3)}) == 3
        # prompt 1 (no full pages): column 0 is already private and distinct
        assert len({table[3 + j, 0] for j in range(3)}) == 3
        # pool is shared+private sized, smaller than per-candidate duplication
        total_pages = state.k_pages[0].shape[1]
        assert total_pages == b * pp + b * n * priv
        assert total_pages < b * n * (pp + priv)

    def test_shared_pages_hold_prompt_kv(self, setup):
        """The shared pool region is the prefill pages verbatim, and each
        candidate's private partial page is a copy of its prompt's partial."""
        from distrl_llm_tpu.engine.paged_engine import _paged_fanout
        from functools import partial
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        b, n, pp, priv = 2, 2, 2, 2
        kh, hd = 2, 4
        pages = tuple(
            jnp.asarray(rng.normal(size=(kh, b * pp, PS, hd)), jnp.float32)
            for _ in range(1)
        )
        real_len = jnp.asarray([PS + 1, PS + 2])
        state, table = jax.jit(
            partial(_paged_fanout, prompt_pages=pp, private_pages=priv,
                    page_size=PS),
            static_argnames=("n", "b", "max_steps"),
        )(pages, pages, jnp.zeros((b, 8)), real_len, jnp.ones((b,), bool),
          n=n, b=b, max_steps=4)
        pool = np.asarray(state.k_pages[0])
        src = np.asarray(pages[0])
        np.testing.assert_array_equal(pool[:, : b * pp], src)
        # candidate (b=1, j=1): partial page copy of prompt 1's page index 1·pp+1
        r = 1 * n + 1
        priv0 = int(np.asarray(table)[r, 1])  # column 1 = first private (full=1)
        np.testing.assert_array_equal(pool[:, priv0], src[:, 1 * pp + 1])


class TestKvQuant:
    """int8 KV cache (per-token absmax, the kernel's native quantized mode)."""

    def test_quantized_reference_close_to_float(self):
        from distrl_llm_tpu.ops.paged import quantize_pages

        rng = np.random.default_rng(5)
        b, h, kh, hd = 2, 4, 2, 8
        cap = 16
        pps = pages_per_seq(cap, PS)
        lengths = jnp.asarray([cap, 9])
        q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, cap, kh, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, cap, kh, hd)), jnp.float32)
        table = jnp.asarray(make_page_table(b, cap, PS))

        kf = write_prompt_to_pages(
            jnp.zeros((kh, b * pps, PS, hd), jnp.float32), k, table, PS)
        vf = write_prompt_to_pages(
            jnp.zeros((kh, b * pps, PS, hd), jnp.float32), v, table, PS)
        want = paged_attention_reference(q, kf, vf, lengths, table)
        got = paged_attention_reference(
            q, quantize_pages(kf), quantize_pages(vf), lengths, table)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.05)

    def test_quantized_writes_roundtrip(self):
        from distrl_llm_tpu.ops.paged import dequantize_pages, quantize_pages

        rng = np.random.default_rng(6)
        b, kh, hd = 2, 2, 4
        cap = 16
        pps = pages_per_seq(cap, PS)
        table = jnp.asarray(make_page_table(b, cap, PS))
        pages = quantize_pages(jnp.zeros((kh, b * pps, PS, hd), jnp.float32))
        tok = jnp.asarray(rng.normal(size=(b, kh, hd)), jnp.float32)
        lengths = jnp.asarray([3, 11])
        pages = write_token_to_pages(pages, tok, lengths, table, PS)
        deq = dequantize_pages(pages)
        for r, ln in enumerate([3, 11]):
            got = deq[:, table[r, ln // PS], ln % PS]
            np.testing.assert_allclose(np.asarray(got), np.asarray(tok[r]), atol=0.02)

    @pytest.mark.slow
    def test_engine_with_int8_kv_decodes(self, setup):
        """End-to-end: the paged engine with kv_quant='int8' produces valid
        rollouts close to the float engine's greedy path."""
        params, ids, mask = setup
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        f32 = make_paged().generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        q8 = PagedGenerationEngine(
            TINY, max_prompt_tokens=P_LEN, max_new_tokens=6,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32, page_size=PS, kv_quant="int8",
        ).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        assert q8.tokens.shape == f32.tokens.shape
        # int8 rounding can flip near-tie argmaxes; most tokens must agree
        agree = (q8.tokens == f32.tokens).mean()
        assert agree >= 0.75, agree

    def test_invalid_quant_raises(self):
        with pytest.raises(ValueError, match="kv_quant"):
            PagedGenerationEngine(
                TINY, max_prompt_tokens=P_LEN, max_new_tokens=4,
                eos_token_ids=[1], pad_token_id=0, kv_quant="int4",
            )


class TestComposition:
    @pytest.mark.slow
    def test_quantized_base_with_paged_engine(self, setup):
        """int8 weight-only base (N4) composes with the paged engine (N1):
        linear() handles quantized containers independent of the cache."""
        from distrl_llm_tpu.ops.quant import quantize_params

        params, ids, mask = setup
        qparams = quantize_params(params, bits=8, group_size=16)
        cfg = SamplingConfig(max_tokens=4, temperature=0.0, n=1)
        dense = make_dense(max_new=4).generate(
            qparams, None, ids, mask, cfg, jax.random.PRNGKey(0))
        paged = make_paged(max_new=4).generate(
            qparams, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(paged.tokens, dense.tokens)

    @pytest.mark.slow
    def test_trainer_round_on_paged_engine(self):
        """A full trainer batch with the PAGED engine as the rollout backend
        (interface drift between the engines would surface here)."""
        from distrl_llm_tpu.metrics import MemorySink
        from distrl_llm_tpu.rewards import reward_function
        from distrl_llm_tpu.tokenizer import CharTokenizer
        from distrl_llm_tpu.trainer import Trainer
        from tests.test_trainer import make_config, make_datasets

        cfg = make_config(max_prompt_tokens=16, max_new_tokens=8)
        tok = CharTokenizer()
        train, test = make_datasets()
        params = init_params(jax.random.PRNGKey(0), TINY)
        engine = PagedGenerationEngine(
            TINY, max_prompt_tokens=16, max_new_tokens=8,
            eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
            cache_dtype=jnp.float32, page_size=8,
        )
        sink = MemorySink()
        trainer = Trainer(
            train, test, reward_function, cfg,
            tokenizer=tok, engine=engine, base_params=params, model_cfg=TINY,
            sink=sink,
        )
        batch = {"problem": train["problem"][:4], "solution": train["solution"][:4]}
        trainer._train_batch(batch, episode=0)
        recs = [m for _, m in sink.records if "loss" in m]
        assert recs and np.isfinite(recs[-1]["loss"])


def make_refill(max_new=6, eos=(), slots=2, **kw):
    return PagedGenerationEngine(
        TINY, max_prompt_tokens=P_LEN, max_new_tokens=max_new,
        eos_token_ids=eos or [TINY.vocab_size - 1], pad_token_id=0,
        cache_dtype=jnp.float32, page_size=PS,
        scheduler="refill", max_concurrent_rows=slots, **kw,
    )


@pytest.fixture(scope="module")
def setup4():
    """Four distinct prompts (different greedy streams) with ragged lengths."""
    params = init_params(jax.random.PRNGKey(7), TINY)
    rng = np.random.default_rng(1)
    ids = rng.integers(1, TINY.vocab_size, size=(4, P_LEN)).astype(np.int32)
    mask = np.ones((4, P_LEN), np.int32)
    mask[0, :3] = 0
    ids[0, :3] = 0
    mask[2, :6] = 0
    ids[2, :6] = 0
    return params, ids, mask


class TestRefillScheduler:
    """Continuous batching: per-candidate slot refill (PagedGenerationEngine
    scheduler="refill"). Greedy decode is scheduler-invariant, so wave mode is
    the oracle: every candidate must produce the same stream no matter when
    its slot admits it."""

    def test_greedy_matches_waves_with_refill(self, setup4):
        """4 candidates through 2 slots: candidates 2 and 3 are admitted only
        after earlier occupants finish, mid-decode of the compiled program."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        oracle = make_paged().generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        res = make_refill(slots=2).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(res.tokens, oracle.tokens)
        np.testing.assert_array_equal(res.lengths, oracle.lengths)

    @pytest.mark.slow
    def test_eos_frees_slots_early(self, setup4):
        """Rows hitting EOS at different steps: freed slots admit pending
        candidates; outputs and lengths still match wave mode exactly."""
        params, ids, mask = setup4
        probe = make_paged(max_new=3).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=3, temperature=0.0, n=1), jax.random.PRNGKey(0),
        )
        # rows 0/2 stop at step 1 or 2, rows 1/3 run longer (or also stop)
        eos = sorted({int(probe.tokens[0, 0, 1]), int(probe.tokens[2, 0, 2])})
        cfg = SamplingConfig(max_tokens=10, temperature=0.0, n=1)
        oracle = make_paged(max_new=10, eos=eos).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        res = make_refill(max_new=10, eos=eos, slots=2).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(res.tokens, oracle.tokens)
        np.testing.assert_array_equal(res.lengths, oracle.lengths)

    @pytest.mark.slow
    def test_candidate_granularity_fanout(self, setup4):
        """n=3 candidates per prompt through 4 slots: slots mix candidates of
        different prompts (wave mode admits whole prompt groups — refill is
        strictly finer). Greedy keeps every candidate equal to its prompt's
        stream."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=5, temperature=0.0, n=3)
        oracle = make_paged(max_new=5).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(2))
        res = make_refill(max_new=5, slots=4).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(2))
        np.testing.assert_array_equal(res.tokens, oracle.tokens)
        np.testing.assert_array_equal(res.lengths, oracle.lengths)

    @pytest.mark.slow
    def test_sampling_shapes_and_bounds(self, setup4):
        params, ids, mask = setup4
        res = make_refill(max_new=4, slots=3).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=4, temperature=1.5, n=2), jax.random.PRNGKey(3),
        )
        assert res.tokens.shape == (4, 2, 4)
        assert (res.lengths >= 1).all() and (res.lengths <= 4).all()

    @pytest.mark.slow
    def test_int8_kv_refill_matches_int8_waves(self, setup4):
        """Admit's partial-page recopy must preserve the quantized (weight,
        scales) pair: int8-KV refill ≡ int8-KV waves under greedy."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=5, temperature=0.0, n=1)
        oracle = make_paged(max_new=5, kv_quant="int8").generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        res = make_refill(max_new=5, slots=2, kv_quant="int8").generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(res.tokens, oracle.tokens)
        np.testing.assert_array_equal(res.lengths, oracle.lengths)

    def test_dead_prompt_rows_stay_padded(self, setup4):
        """Batch-padding rows (empty mask) are never admitted: pad tokens,
        zero length — same contract as wave mode's born-done rows."""
        params, ids, mask = setup4
        mask = mask.copy()
        ids = ids.copy()
        mask[3] = 0
        ids[3] = 0
        res = make_refill(max_new=4, slots=2).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=4, temperature=0.0, n=2), jax.random.PRNGKey(0),
        )
        np.testing.assert_array_equal(res.tokens[3], 0)
        np.testing.assert_array_equal(res.lengths[3], 0)

    def test_config_flag_requires_paged_and_cap(self):
        from distrl_llm_tpu.config import TrainConfig

        with pytest.raises(ValueError, match="continuous_batching"):
            TrainConfig(continuous_batching=True)  # dense engine
        with pytest.raises(ValueError, match="continuous_batching"):
            TrainConfig(continuous_batching=True, engine_impl="paged")  # no cap
        cfg = TrainConfig(
            continuous_batching=True, engine_impl="paged",
            max_concurrent_sequences=64,
        )
        assert cfg.continuous_batching

    @pytest.mark.slow
    def test_dead_slots_never_corrupt_shared_pages(self, setup4):
        """Review regression: live candidates < slot count leaves slots
        never-admitted. Their per-step garbage KV writes must land in their
        own private pages — an all-zero init table would alias physical page
        0 (prompt 0's SHARED prefill page) and silently corrupt prompt 0."""
        params, ids, mask = setup4
        mask = mask.copy()
        ids = ids.copy()
        for r in (1, 2, 3):  # only prompt 0 is live
            mask[r] = 0
            ids[r] = 0
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=2)
        oracle = make_paged().generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        # total=8 > slots=4 engages refill; pending holds only 2 live candidates
        res = make_refill(slots=4).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(res.tokens[0], oracle.tokens[0])
        np.testing.assert_array_equal(res.lengths[0], oracle.lengths[0])


class TestPagedEngineTP:
    """The paged engine targets one rollout replica — a single chip or a TP
    group (module docstring). Substantiate the TP-group claim: with base
    params Megatron-sharded over a tp mesh, greedy output must equal the
    unsharded engine's (GSPMD inserts the collectives; the page pools created
    inside the jitted prefill/steps follow the propagated shardings)."""

    @pytest.mark.slow
    @pytest.mark.parametrize("scheduler", ["waves", "refill"])
    def test_tp_sharded_matches_unsharded(self, setup4, scheduler):
        from distrl_llm_tpu.parallel import shard_tree
        from distrl_llm_tpu.parallel.mesh import _make_mesh

        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=5, temperature=0.0, n=2)
        kw = dict(max_concurrent_rows=4, scheduler=scheduler) if scheduler == "refill" else {}
        want = make_paged(max_new=5, **kw).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))

        mesh = _make_mesh(jax.devices()[:2], 2, 1, 1)  # tp=2 (TINY has 2 kv heads)
        sharded = shard_tree(params, mesh)
        got = make_paged(max_new=5, **kw).generate(
            sharded, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(got.tokens, want.tokens)
        np.testing.assert_array_equal(got.lengths, want.lengths)


class TestRefillScanChunk:
    """K-steps-per-dispatch refill decode (``scan_chunk``): chunk size never
    exceeds the host cadence ``check``, so with scan_chunk >= check the host
    acts at exactly the same dispatched-step counts as the per-step loop and
    outputs must be BIT-identical (including rng: the all-done skip branch
    still advances the fold_in index). With a smaller chunk the host cadence
    shifts, which greedy decoding cannot observe (schedule-invariance)."""

    @pytest.mark.slow
    def test_greedy_parity_with_refills(self, setup4):
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        base = make_refill(slots=2).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        eng = make_refill(slots=2, scan_chunk=16)
        chunked = eng.generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        assert eng.scan_chunk_active  # chunked program ran, not a fallback
        np.testing.assert_array_equal(base.tokens, chunked.tokens)
        np.testing.assert_array_equal(base.lengths, chunked.lengths)

    @pytest.mark.slow
    def test_structural_swap_rebuilds_chunk_program(self, setup4):
        """Regression (refill flavor): the None->first-adapter
        in-flight swap lands at a k-aligned dispatch; the compiled chunk
        program must be refetched for the new signature, not crash."""
        from distrl_llm_tpu.models import init_lora_params

        params, ids, mask = setup4
        adapter = init_lora_params(jax.random.PRNGKey(5), TINY, rank=4)
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        eng = make_refill(slots=2, scan_chunk=16)
        eng.push_lora(adapter)
        out = eng.generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        assert eng.last_swap_steps == [0]
        assert eng.scan_chunk_active
        want = make_refill(slots=2, scan_chunk=16).generate(
            params, adapter, ids, mask, cfg, jax.random.PRNGKey(0)
        )
        np.testing.assert_array_equal(out.tokens, want.tokens)

    @pytest.mark.slow
    def test_sampled_parity_with_eos_and_logprobs(self, setup4):
        """EOS mid-round frees slots for refills; sampled tokens, lengths
        and captured behavior logprobs must match the per-step loop."""
        params, ids, mask = setup4
        probe = make_paged(max_new=3).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=3, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        eos = sorted({int(probe.tokens[0, 0, 1]), int(probe.tokens[2, 0, 2])})
        cfg = SamplingConfig(max_tokens=8, temperature=1.3, top_p=0.9, n=2)
        kw = dict(max_new=8, eos=eos, slots=3, capture_logprobs=True)
        base = make_refill(**kw).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(5))
        chunked = make_refill(scan_chunk=16, **kw).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(5))
        np.testing.assert_array_equal(base.tokens, chunked.tokens)
        np.testing.assert_array_equal(base.lengths, chunked.lengths)
        np.testing.assert_array_equal(base.logprobs, chunked.logprobs)

    @pytest.mark.slow
    def test_non_divisor_chunk_rounds_down_and_keeps_parity(self, setup4):
        """scan_chunk=4 with check=6 (max_new=6) rounds down to the divisor
        3 — a non-divisor K would stretch the host cadence past the
        budgeted pool's grant horizon (review finding). With the divisor,
        sampled output stays bit-identical to the per-step loop."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=6, temperature=1.1, top_p=0.9, n=2)
        base = make_refill(slots=2).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(7))
        res = make_refill(slots=2, scan_chunk=4).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(7))
        np.testing.assert_array_equal(res.tokens, base.tokens)
        np.testing.assert_array_equal(res.lengths, base.lengths)

    @pytest.mark.slow
    def test_tight_budget_with_non_divisor_chunk(self, setup4):
        """Budgeted pool + non-divisor scan_chunk: the divisor rounding is
        what keeps grants ahead of the write frontier; outputs must match
        the per-step loop exactly."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        eng = make_refill(slots=2)
        pages = 1 + eng.private_pages + 2
        base = make_refill(slots=2, max_kv_pages=pages).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        res = make_refill(
            slots=2, max_kv_pages=pages, scan_chunk=4
        ).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(res.tokens, base.tokens)
        np.testing.assert_array_equal(res.lengths, base.lengths)

    @pytest.mark.slow
    def test_budgeted_pool_preemption_parity(self, setup4):
        """A pool tight enough to stall admissions (grow-as-you-go grants +
        possible preemption) must not change greedy outputs under chunking."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        eng = make_refill(slots=2)
        pages = 1 + eng.private_pages + 2  # one full region + a little slack
        base = make_refill(slots=2, max_kv_pages=pages).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        chunked = make_refill(
            slots=2, max_kv_pages=pages, scan_chunk=16
        ).generate(params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(base.tokens, chunked.tokens)
        np.testing.assert_array_equal(base.lengths, chunked.lengths)

    @pytest.mark.slow
    def test_spec_budget_chunk_parity(self, setup4):
        """Tight pool + speculative + chunking: the (d+1)-scaled grant
        horizon must stay ahead of the fused steps' write frontier; greedy
        outputs must match the per-step loop exactly."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=6, temperature=0.0, n=1)
        eng = make_refill(slots=2, spec_draft=2)
        pages = 1 + eng.private_pages + 2
        kw = dict(slots=2, spec_draft=2, max_kv_pages=pages)
        base = make_refill(**kw).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        eng = make_refill(scan_chunk=16, **kw)
        res = eng.generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        assert eng.scan_chunk_active  # chunked program ran, not a fallback
        np.testing.assert_array_equal(res.tokens, base.tokens)
        np.testing.assert_array_equal(res.lengths, base.lengths)

    @pytest.mark.slow
    def test_spec_scan_chunk_parity(self, setup4):
        """Speculative scheduler + chunked dispatch: the spec step is fully
        functional (draft/verify/accept all device-side), so K fused steps
        must be bit-identical to the per-step loop — here under sampling
        with EOS mid-round and logprob capture."""
        params, ids, mask = setup4
        probe = make_paged(max_new=3).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=3, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        eos = sorted({int(probe.tokens[0, 0, 1]), int(probe.tokens[2, 0, 2])})
        cfg = SamplingConfig(max_tokens=8, temperature=1.2, top_p=0.9, n=2)
        kw = dict(max_new=8, eos=eos, slots=3, spec_draft=2,
                  capture_logprobs=True)
        base = make_refill(**kw).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(5))
        eng = make_refill(scan_chunk=16, **kw)
        chunked = eng.generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(5))
        assert eng.scan_chunk_active  # chunked program ran, not a fallback
        np.testing.assert_array_equal(base.tokens, chunked.tokens)
        np.testing.assert_array_equal(base.lengths, chunked.lengths)
        np.testing.assert_array_equal(base.logprobs, chunked.logprobs)


class TestWaveScanChunk:
    """Wave-scheduler chunked dispatch: exact mirror of the dense engine's
    scan_chunk (guarded overshoot, bit-parity with the per-step loop)."""

    @pytest.mark.slow
    def test_sampled_parity_with_overshoot_and_logprobs(self, setup4):
        """chunk=5 over max_new=7: the second chunk overshoots by 3 guarded
        steps; sampled tokens/lengths/logprobs must be bit-identical."""
        params, ids, mask = setup4
        cfg = SamplingConfig(max_tokens=7, temperature=1.2, top_p=0.9, n=2)
        kw = dict(max_new=7, capture_logprobs=True)
        base = make_paged(**kw).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(9))
        eng = make_paged(scan_chunk=5, **kw)
        chunked = eng.generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(9))
        assert eng.scan_chunk_active  # chunked program ran, not a fallback
        np.testing.assert_array_equal(base.tokens, chunked.tokens)
        np.testing.assert_array_equal(base.lengths, chunked.lengths)
        np.testing.assert_array_equal(base.logprobs, chunked.logprobs)

    @pytest.mark.slow
    def test_greedy_eos_parity(self, setup4):
        params, ids, mask = setup4
        probe = make_paged(max_new=3).generate(
            params, None, ids, mask,
            SamplingConfig(max_tokens=3, temperature=0.0, n=1),
            jax.random.PRNGKey(0),
        )
        eos = [int(probe.tokens[0, 0, 1])]
        cfg = SamplingConfig(max_tokens=8, temperature=0.0, n=1)
        base = make_paged(max_new=8, eos=eos).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        chunked = make_paged(max_new=8, eos=eos, scan_chunk=3).generate(
            params, None, ids, mask, cfg, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(base.tokens, chunked.tokens)
        np.testing.assert_array_equal(base.lengths, chunked.lengths)
