"""Sampler unit tests: top-p nucleus semantics, greedy, temperature, and
the fused sample-from-logits Pallas kernel (ISSUE 15 — interpreter-mode
pins; tests/test_tpu_compile.py holds the Mosaic lowering)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.sampling import NEG_INF, sample, top_p_filter


def logits_for_probs(probs):
    return jnp.log(jnp.asarray([probs], jnp.float32))


class TestTopPFilter:
    def test_keeps_minimal_prefix_crossing_threshold(self):
        lg = logits_for_probs([0.5, 0.3, 0.15, 0.05])
        out = np.asarray(top_p_filter(lg, 0.7))
        # cum-excluding: 0, 0.5, 0.8, 0.95 → keep tokens 0,1 (0.8 ≥ 0.7 drops #2)
        assert out[0, 0] > NEG_INF and out[0, 1] > NEG_INF
        assert out[0, 2] == NEG_INF and out[0, 3] == NEG_INF

    def test_top_p_1_keeps_everything(self):
        lg = logits_for_probs([0.4, 0.3, 0.2, 0.1])
        out = np.asarray(top_p_filter(lg, 1.0))
        assert (out > NEG_INF).all()

    def test_always_keeps_top_token(self):
        lg = logits_for_probs([0.99, 0.005, 0.005])
        out = np.asarray(top_p_filter(lg, 0.01))
        assert out[0, 0] > NEG_INF
        assert (out[0, 1:] == NEG_INF).all()

    def test_unsorted_input(self):
        lg = logits_for_probs([0.05, 0.5, 0.15, 0.3])
        out = np.asarray(top_p_filter(lg, 0.7))
        assert out[0, 1] > NEG_INF and out[0, 3] > NEG_INF  # 0.5 and 0.3 kept
        assert out[0, 0] == NEG_INF and out[0, 2] == NEG_INF


class TestSample:
    def test_temperature_zero_is_greedy(self):
        lg = jnp.asarray([[1.0, 5.0, 2.0], [9.0, 0.0, 1.0]])
        tok = sample(jax.random.PRNGKey(0), lg, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(tok), [1, 0])

    def test_sampling_respects_top_p_support(self):
        lg = logits_for_probs([0.6, 0.3, 0.05, 0.05])
        toks = [
            int(sample(jax.random.PRNGKey(i), lg, 1.0, 0.8)[0]) for i in range(64)
        ]
        assert set(toks) <= {0, 1}

    @pytest.mark.slow
    def test_high_temperature_flattens(self):
        lg = jnp.asarray([[4.0, 0.0, 0.0, 0.0]])
        toks = [int(sample(jax.random.PRNGKey(i), lg, 50.0, 1.0)[0]) for i in range(200)]
        # at T=50 the distribution is near-uniform: non-argmax tokens dominate
        assert sum(t != 0 for t in toks) > 100

    def test_traced_params_one_compile(self):
        calls = []

        @jax.jit
        def f(rng, lg, t, p):
            calls.append(1)
            return sample(rng, lg, t, p)

        lg = jnp.zeros((2, 8))
        f(jax.random.PRNGKey(0), lg, jnp.float32(1.2), jnp.float32(0.95))
        f(jax.random.PRNGKey(1), lg, jnp.float32(0.6), jnp.float32(0.95))
        assert len(calls) == 1  # no retrace for different sampling params

    def test_ties_at_cutoff_do_not_expand_nucleus(self):
        # uniform 4-way tie, top_p=0.5 → exactly 2 kept (rank-based membership)
        lg = logits_for_probs([0.25, 0.25, 0.25, 0.25])
        out = np.asarray(top_p_filter(lg, 0.5))
        assert (out > NEG_INF).sum() == 2


class TestTopPBisect:
    """The sort-free filter must agree with the exact sort-based filter away
    from exact probability ties at the nucleus boundary."""

    def test_superset_of_sort_filter_with_negligible_extra_mass(self):
        # Guaranteed contract: bisect never drops a token the exact filter
        # keeps (its kept mass is always >= top_p and both sets are prob-rank
        # prefixes); extra tokens sit within the bisection window of the
        # boundary, so their total mass is tiny.
        import numpy as np

        from distrl_llm_tpu.ops.sampling import top_p_filter, top_p_filter_bisect

        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(8, 512)) * 3.0, jnp.float32)
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        for p in (0.1, 0.5, 0.95, 0.999):
            exact = np.asarray(top_p_filter(logits, p)) > -1e29
            bisect = np.asarray(top_p_filter_bisect(logits, p)) > -1e29
            assert (bisect | exact == bisect).all(), "dropped an exact-kept token"
            extra_mass = (probs * (bisect & ~exact)).sum(-1)
            assert (extra_mass < 5e-3).all()

    def test_kept_mass_at_least_top_p(self):
        import numpy as np

        from distrl_llm_tpu.ops.sampling import top_p_filter_bisect

        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(16, 1024)), jnp.float32)
        p = 0.9
        kept = np.asarray(top_p_filter_bisect(logits, p)) > -1e29
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        mass = (probs * kept).sum(-1)
        assert (mass >= p - 1e-6).all()

    def test_top_p_1_keeps_everything(self):
        import numpy as np

        from distrl_llm_tpu.ops.sampling import top_p_filter_bisect

        logits = jnp.asarray([[0.0, 1.0, -2.0, 3.0]], jnp.float32)
        kept = np.asarray(top_p_filter_bisect(logits, 1.0)) > -1e29
        assert kept.all()


class TestTopPBisectMultiway:
    """Multiway bisection must honor the same contracts as binary bisection:
    a superset of the exact filter's kept set, kept mass >= top_p."""

    def test_superset_of_sort_filter(self):
        import numpy as np

        from distrl_llm_tpu.ops.sampling import (
            top_p_filter, top_p_filter_bisect_multiway,
        )

        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(8, 512)) * 3.0, jnp.float32)
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        for p in (0.1, 0.5, 0.95, 0.999):
            exact = np.asarray(top_p_filter(logits, p)) > -1e29
            mw = np.asarray(top_p_filter_bisect_multiway(logits, p)) > -1e29
            assert (mw | exact == mw).all(), "dropped an exact-kept token"
            extra_mass = (probs * (mw & ~exact)).sum(-1)
            assert (extra_mass < 5e-3).all()

    def test_kept_mass_at_least_top_p(self):
        import numpy as np

        from distrl_llm_tpu.ops.sampling import top_p_filter_bisect_multiway

        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.normal(size=(16, 1024)), jnp.float32)
        for p in (0.5, 0.9, 0.99):
            kept = np.asarray(top_p_filter_bisect_multiway(logits, p)) > -1e29
            probs = np.asarray(jax.nn.softmax(logits, axis=-1))
            assert ((probs * kept).sum(-1) >= p - 1e-6).all()

    def test_agrees_with_binary_bisect_resolution(self):
        """Same 2^16 resolution target: the two bisect variants should keep
        nearly identical sets away from threshold-window boundaries."""
        import numpy as np

        from distrl_llm_tpu.ops.sampling import (
            top_p_filter_bisect, top_p_filter_bisect_multiway,
        )

        rng = np.random.default_rng(4)
        logits = jnp.asarray(rng.normal(size=(4, 2048)) * 2.0, jnp.float32)
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        bi = np.asarray(top_p_filter_bisect(logits, 0.95)) > -1e29
        mw = np.asarray(top_p_filter_bisect_multiway(logits, 0.95)) > -1e29
        sym_diff_mass = (probs * (bi ^ mw)).sum(-1)
        assert (sym_diff_mass < 2e-3).all()

    def test_top_p_1_keeps_everything(self):
        import numpy as np

        from distrl_llm_tpu.ops.sampling import top_p_filter_bisect_multiway

        logits = jnp.asarray([[0.0, 1.0, -2.0, 3.0]], jnp.float32)
        kept = np.asarray(top_p_filter_bisect_multiway(logits, 1.0)) > -1e29
        assert kept.all()


class TestFusedSampler:
    """One-pass Pallas sampler (ops/sampling.py::fused_sample): greedy
    bit-identity, raw-basis logprob exactness, nucleus support, seeded
    distribution parity, and the DISTRL_SAMPLE_KERNEL dispatch."""

    def _logits(self, b=8, v=300, seed=0, scale=3.0):
        # non-multiple-of-128 vocab exercises the NEG_INF padding
        return jnp.asarray(
            np.random.default_rng(seed).normal(size=(b, v)) * scale,
            jnp.float32,
        )

    def test_greedy_bit_identity_and_logprob(self):
        from distrl_llm_tpu.ops.sampling import fused_sample, token_logprob

        lg = self._logits()
        tok, logp = fused_sample(
            jax.random.PRNGKey(0), lg, 0.0, 0.95, interpret=True
        )
        ref = sample(jax.random.PRNGKey(0), lg, 0.0, 0.95)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref))
        # the kernel sums a row as an [R, 128] tile, token_logprob as one
        # vector: same math, another reduction order — a last-bit difference
        np.testing.assert_allclose(
            np.asarray(logp), np.asarray(token_logprob(lg, tok)), rtol=2e-6
        )

    def test_sampled_tokens_within_nucleus(self):
        from distrl_llm_tpu.ops.sampling import (
            fused_sample, top_p_filter_bisect,
        )

        lg = self._logits(seed=1)
        t, p = 1.0, 0.7
        kept = np.asarray(top_p_filter_bisect(lg / t, p)) > -1e29
        for i in range(16):
            tok, _ = fused_sample(
                jax.random.PRNGKey(i), lg, t, p, interpret=True
            )
            tk = np.asarray(tok)
            assert kept[np.arange(lg.shape[0]), tk].all()

    def test_sampled_logprob_is_raw_basis(self):
        from distrl_llm_tpu.ops.sampling import fused_sample, token_logprob

        lg = self._logits(seed=2)
        tok, logp = fused_sample(
            jax.random.PRNGKey(3), lg, 1.2, 0.9, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(logp), np.asarray(token_logprob(lg, tok)), atol=1e-6
        )

    @pytest.mark.slow
    def test_distribution_parity_vs_multipass(self):
        """Seeded statistical parity (the spec_accept discipline): fused
        and multi-pass empirical distributions agree within a TV bound
        scaled to sampling noise."""
        from distrl_llm_tpu.ops.sampling import fused_sample

        V, N = 64, 8192
        row = jnp.asarray(
            np.random.default_rng(5).normal(size=(V,)) * 2.0, jnp.float32
        )
        tiled = jnp.tile(row[None, :], (N, 1))
        t, p = 1.2, 0.95
        toks_f = np.asarray(
            fused_sample(jax.random.PRNGKey(6), tiled, t, p,
                         interpret=True)[0]
        )
        toks_m = np.asarray(sample(jax.random.PRNGKey(7), tiled, t, p))
        emp_f = np.bincount(toks_f, minlength=V) / N
        emp_m = np.bincount(toks_m, minlength=V) / N
        tv = 0.5 * np.abs(emp_f - emp_m).sum()
        assert tv < 3.0 * (V / N) ** 0.5, tv

    def test_temperature_zero_rows_vs_sampled(self):
        # traced scalar temperature selects greedy inside the kernel
        from distrl_llm_tpu.ops.sampling import fused_sample

        lg = self._logits(b=4, seed=8)
        tok0, _ = fused_sample(
            jax.random.PRNGKey(9), lg, 0.0, 1.0, interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(tok0), np.asarray(lg.argmax(-1))
        )

    def test_wrapper_dispatch_modes(self):
        from distrl_llm_tpu.ops.sampling import (
            sample_dispatch, sample_impl_mode, sample_with_logprob,
        )

        lg = self._logits(b=2, seed=10)
        tok_x, lp_x = sample_with_logprob(
            jax.random.PRNGKey(0), lg, 0.0, 0.95, capture_logprob=True,
            impl="xla",
        )
        tok_i, lp_i = sample_with_logprob(
            jax.random.PRNGKey(0), lg, 0.0, 0.95, capture_logprob=True,
            impl="interpret",
        )
        np.testing.assert_array_equal(np.asarray(tok_x), np.asarray(tok_i))
        np.testing.assert_allclose(
            np.asarray(lp_x), np.asarray(lp_i), atol=1e-6
        )
        # capture off → no logprob pass at all
        _, lp_none = sample_with_logprob(
            jax.random.PRNGKey(0), lg, 0.0, 0.95, impl="xla"
        )
        assert lp_none is None
        # env validation + the exact-nucleus reproducibility pin
        os.environ["DISTRL_SAMPLE_KERNEL"] = "bogus"
        try:
            with pytest.raises(ValueError, match="DISTRL_SAMPLE_KERNEL"):
                sample_impl_mode()
        finally:
            del os.environ["DISTRL_SAMPLE_KERNEL"]
        use, _ = sample_dispatch("exact")
        assert use is False  # an explicit exact-nucleus ask never fuses
