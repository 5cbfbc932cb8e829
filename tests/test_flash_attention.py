"""Flash-attention wrapper (ops/flash_attention.py).

CPU CI exercises the fallback contract (the Pallas kernel is TPU-only); the
numeric comparison against attention_reference runs when a TPU is attached
(tpu marker — see tests/test_flash_attention_tpu.py's driver usage in
bench/verify flows).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.attention import attention, attention_reference, causal_padding_mask

ON_TPU = jax.default_backend() == "tpu"


def make_qkv(b=2, s=256, h=4, kh=2, d=64, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(r.normal(size=(b, s, kh, d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(b, s, kh, d)), jnp.float32)
    return q, k, v


class TestFallback:
    def test_flash_raises_off_tpu(self):
        if ON_TPU:
            pytest.skip("TPU attached")
        from distrl_llm_tpu.ops.flash_attention import flash_attention

        q, k, v = make_qkv(s=128)
        with pytest.raises(NotImplementedError):
            flash_attention(q, k, v, None)

    def test_attention_impl_flash_falls_back(self):
        # the front door must never hard-fail: off-TPU it warns once and
        # returns the reference result
        q, k, v = make_qkv(s=64)
        mask = causal_padding_mask(jnp.ones((2, 64), jnp.int32), q_len=64)
        out = attention(q, k, v, mask, impl="flash")
        ref = attention_reference(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.skipif(not ON_TPU, reason="requires TPU backend")
class TestFlashNumerics:
    def test_matches_reference_with_padding(self):
        from distrl_llm_tpu.ops.flash_attention import flash_attention

        b, s = 2, 200  # not a block multiple — exercises the pad path
        q, k, v = make_qkv(b=b, s=s)
        am = np.ones((b, s), np.int32)
        am[0, :50] = 0  # left padding
        mask = causal_padding_mask(jnp.asarray(am), q_len=s)
        out = flash_attention(q, k, v, mask)
        ref = attention_reference(q, k, v, mask)
        real = np.asarray(am, bool)
        np.testing.assert_allclose(
            np.asarray(out)[real], np.asarray(ref)[real], atol=2e-2, rtol=2e-2
        )

    def test_gradients_flow(self):
        from distrl_llm_tpu.ops.flash_attention import flash_attention

        q, k, v = make_qkv(s=128)
        mask = causal_padding_mask(jnp.ones((2, 128), jnp.int32), q_len=128)

        def loss(q, impl):
            f = flash_attention if impl == "flash" else attention_reference
            return jnp.sum(f(q, k, v, mask) ** 2)

        gf = jax.grad(lambda q: loss(q, "flash"))(q)
        gr = jax.grad(lambda q: loss(q, "ref"))(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-2, rtol=5e-2)


class TestKernelFailuresSurface:
    """On a TPU backend ``attention(impl="flash"|"splash")`` runs the kernel
    or fails: a lowering rejection or a device error reaches the caller, and
    nothing gives way to the XLA reference. (The backend is steered in the
    test; the kernels themselves are stubbed, so no TPU is needed.)"""

    @pytest.fixture()
    def attn_mod(self, monkeypatch):
        import importlib

        # ops/__init__ re-exports the attention FUNCTION under the name
        mod = importlib.import_module("distrl_llm_tpu.ops.attention")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(mod, "_flash_fallback_warned", False)
        return mod

    def test_lowering_rejection_raises(self, attn_mod, monkeypatch):
        def boom(*a, **k):
            raise ValueError(
                "The Pallas TPU lowering currently requires that the last two "
                "dimensions of your block shape are divisible by 8 and 128"
            )

        import distrl_llm_tpu.ops.flash_attention as fa_mod
        monkeypatch.setattr(fa_mod, "flash_attention", boom)
        q, k, v = make_qkv(s=128)
        with pytest.raises(ValueError, match="block shape"):
            attn_mod.attention(q, k, v, None, impl="flash")
        assert attn_mod._flash_fallback_warned is False

    def test_device_error_raises(self, attn_mod, monkeypatch):
        def flaky(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        import distrl_llm_tpu.ops.splash as splash_mod
        monkeypatch.setattr(splash_mod, "splash_attention", flaky)
        q, k, v = make_qkv(s=128)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            attn_mod.attention(
                q, k, v, None, impl="splash",
                key_valid=jnp.ones((2, 128), jnp.int32),
            )

    def test_kernel_result_is_returned(self, attn_mod, monkeypatch):
        calls = []

        def ok(q, k, v, mask, **kw):
            calls.append(kw)
            return q + 1.0

        import distrl_llm_tpu.ops.flash_attention as fa_mod
        monkeypatch.setattr(fa_mod, "flash_attention", ok)
        q, k, v = make_qkv(s=128)
        out = attn_mod.attention(q, k, v, None, impl="flash")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(q + 1.0))
        assert len(calls) == 1
        # outside the kernel's self-attention contract (a cached decode
        # step: one query against Sk keys) the reference is the path
        out1 = attn_mod.attention(q[:, :1], k, v, None, impl="flash")
        assert len(calls) == 1 and out1.shape == q[:, :1].shape
