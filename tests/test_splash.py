"""Splash attention (native-GQA Pallas kernel) parity tests.

Runs the REAL kernel under the Pallas interpreter on CPU (same code path
Mosaic compiles on TPU) against the XLA reference — forward and gradients
(the kernel carries custom-VJP backward kernels, needed by the learner).
This path replaces flash's GQA repeat_kv (G× KV traffic).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.attention import attention, attention_reference, causal_padding_mask
from distrl_llm_tpu.ops.splash import splash_attention

B, S, H, KH, D = 2, 128, 4, 2, 64


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.float32)
    valid = np.ones((B, S), np.int32)
    valid[0, 100:] = 0  # right padding on row 0 (packed layout)
    return q, k, v, jnp.asarray(valid)


def reference(q, k, v, valid):
    return attention_reference(q, k, v, causal_padding_mask(valid, q_len=S))


class TestForwardParity:
    pytestmark = pytest.mark.pallas_interpret

    def test_matches_reference_with_padding(self, qkv):
        q, k, v, valid = qkv
        got = splash_attention(q, k, v, valid, interpret=True, block=128)
        want = reference(q, k, v, valid)
        err = np.abs(np.asarray(got - want)) * np.asarray(valid)[:, :, None, None]
        assert err.max() < 2e-3, err.max()

    def test_unpadded_no_mask(self, qkv):
        q, k, v, _ = qkv
        got = splash_attention(q, k, v, None, interpret=True, block=128)
        want = reference(q, k, v, jnp.ones((B, S), jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)

    def test_non_multiple_seq_pads(self, qkv):
        q, k, v, valid = qkv
        s2 = 100  # not a multiple of 128 → internal pad path
        got = splash_attention(
            q[:, :s2], k[:, :s2], v[:, :s2], valid[:, :s2],
            interpret=True, block=128,
        )
        want = attention_reference(
            q[:, :s2], k[:, :s2], v[:, :s2],
            causal_padding_mask(valid[:, :s2], q_len=s2),
        )
        err = np.abs(np.asarray(got - want)) * np.asarray(valid[:, :s2])[:, :, None, None]
        assert err.max() < 2e-3, err.max()


    def test_second_trace_of_one_geometry(self, qkv):
        """The kernel object is cached per geometry; no tracer of the first
        trace may ride it into the second (learner bucket, then eval)."""
        q, k, v, valid = qkv

        def fwd(q_, k_, v_):
            return splash_attention(q_, k_, v_, valid, interpret=True, block=128)

        first = jax.jit(fwd)(q, k, v)
        second = jax.jit(lambda *a: fwd(*a) * 1.0)(q, k, v)
        np.testing.assert_allclose(np.asarray(first), np.asarray(second), atol=1e-6)


class TestGradParity:
    pytestmark = pytest.mark.pallas_interpret

    def test_grads_match_reference(self, qkv):
        """The learner differentiates through attention — splash's custom-VJP
        backward kernels must agree with XLA autodiff."""
        q, k, v, valid = qkv
        vmask = valid.astype(jnp.float32)[:, :, None, None]

        def loss_splash(q, k, v):
            out = splash_attention(q, k, v, valid, interpret=True, block=128)
            return ((out * vmask) ** 2).sum()

        def loss_ref(q, k, v):
            out = reference(q, k, v, valid)
            return ((out * vmask) ** 2).sum()

        g_s = jax.grad(loss_splash, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_s, g_r, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-3,
                err_msg=f"grad wrt {name}",
            )


class TestDispatch:
    def test_cpu_dispatch_falls_back_to_reference(self, qkv):
        """attention(impl='splash') off-TPU uses the XLA path (the interpreter
        is test-only), with identical results."""
        q, k, v, valid = qkv
        got = attention(q, k, v, None, impl="splash", key_valid=valid)
        want = reference(q, k, v, valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
