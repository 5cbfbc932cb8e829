"""Fused quantized-matmul Pallas kernel (ops/quant_matmul.py).

Pins the kernel's contract under the Pallas interpreter (the Mosaic
lowering for a v5e is held by tests/test_tpu_compile.py): bit-identity
with the XLA container path at decode-tile sizes, the LoRA epilogue's
exact math order, padding edges, gradients through the custom VJP, the
DISTRL_QUANT_MATMUL dispatch modes, and end-to-end engine greedy
bit-identity (the ISSUE-15 acceptance claim).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.linear import linear, lora_delta
from distrl_llm_tpu.ops.quant import quantize, quantize_params
from distrl_llm_tpu.ops.quant_matmul import (
    MODES,
    quant_matmul,
    quant_matmul_dispatch,
    quant_matmul_mode,
)


def rand(shape, seed=0, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, jnp.float32
    )


def container_ref(x, wq, bias=None, a=None, b=None, scale=1.0):
    """The exact split-path math _proj runs: (x@W + bias) + delta."""
    y = linear(x, wq, bias)
    if a is not None:
        y = y + lora_delta(x, a, b, scale)
    return y


class TestKernelParity:
    @pytest.mark.parametrize(
        "bits,gs,K,N,M",
        [
            (8, None, 64, 96, 4),     # per-column scales, odd N (padding)
            (8, 32, 128, 200, 13),    # grouped, non-multiple M and N
            (4, 16, 64, 96, 8),       # int4 blockwise
        ],
    )
    def test_bit_identity_base_only(self, bits, gs, K, N, M):
        wq = quantize(rand((K, N), 1, 0.05), bits=bits, group_size=gs)
        x = rand((M, K), 2)
        got = quant_matmul(x, wq, interpret=True)
        want = container_ref(x, wq)
        assert (np.asarray(got) == np.asarray(want)).all()

    def test_bit_identity_with_bias_and_lora_epilogue(self):
        wq = quantize(rand((128, 96), 3, 0.05), bits=8, group_size=32)
        x = rand((8, 128), 4)
        bias = rand((96,), 5)
        a, b = rand((128, 8), 6, 0.1), rand((8, 96), 7, 0.1)
        got = quant_matmul(x, wq, bias, a, b, 0.5, interpret=True)
        want = container_ref(x, wq, bias, a, b, 0.5)
        assert (np.asarray(got) == np.asarray(want)).all()

    def test_leading_dims_flattened(self):
        wq = quantize(rand((64, 32), 8, 0.05), bits=8, group_size=16)
        x = rand((2, 5, 64), 9)
        got = quant_matmul(x, wq, interpret=True)
        want = container_ref(x, wq)
        assert got.shape == (2, 5, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    def test_large_m_tile_close(self):
        # M > block_m splits the row tiles; the per-element K reduction
        # stays a single dot, so parity holds to float reorder noise
        wq = quantize(rand((256, 128), 10, 0.05), bits=8)
        x = rand((480, 256), 11)
        got = quant_matmul(x, wq, interpret=True)
        want = container_ref(x, wq)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
        )

    def test_stacked_container_rejected(self):
        wq = quantize(rand((3, 64, 32), 12, 0.05), bits=8)  # [L, G, g, N]
        with pytest.raises(ValueError, match="per-layer"):
            quant_matmul(rand((4, 64), 13), wq, interpret=True)

    def test_mismatched_input_dim_rejected(self):
        wq = quantize(rand((64, 32), 14, 0.05), bits=8)
        with pytest.raises(ValueError, match="input dim"):
            quant_matmul(rand((4, 48), 15), wq, interpret=True)


class TestGradients:
    def test_grads_match_reference(self):
        """The custom VJP backward runs the reference math: grads wrt x
        and the LoRA factors must be bit-equal to differentiating the
        split path (QLoRA trains LoRA only — tests/test_quant.py)."""
        wq = quantize(rand((64, 32), 20, 0.05), bits=8, group_size=16)
        x = rand((4, 64), 21)
        a, b = rand((64, 4), 22, 0.1), rand((4, 32), 23, 0.1)

        def loss_k(x_, a_, b_):
            return quant_matmul(x_, wq, None, a_, b_, 0.5,
                                interpret=True).sum()

        def loss_r(x_, a_, b_):
            return container_ref(x_, wq, None, a_, b_, 0.5).sum()

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(x, a, b)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, a, b)
        for k_, r_ in zip(gk, gr):
            assert (np.asarray(k_) == np.asarray(r_)).all()

    def test_int_payload_gets_no_cotangent(self):
        # differentiating wrt x with an int8 payload in the graph must not
        # raise (float0 cotangents for the int leaves)
        wq = quantize(rand((32, 16), 24, 0.05), bits=8)
        g = jax.grad(
            lambda x_: quant_matmul(x_, wq, interpret=True).sum()
        )(rand((2, 32), 25))
        assert np.isfinite(np.asarray(g)).all()


class TestDispatch:
    def test_mode_validation(self):
        os.environ["DISTRL_QUANT_MATMUL"] = "bogus"
        try:
            with pytest.raises(ValueError, match="DISTRL_QUANT_MATMUL"):
                quant_matmul_mode()
        finally:
            del os.environ["DISTRL_QUANT_MATMUL"]
        assert quant_matmul_mode() in MODES

    def test_auto_is_xla_off_tpu(self):
        # CPU tier-1 default: the container path, byte-identical to the
        # pre-kernel behavior
        use, _ = quant_matmul_dispatch()
        assert use is (jax.default_backend() == "tpu") or use is False

    def test_explicit_modes(self):
        for mode, want_use in (("xla", False), ("interpret", True)):
            os.environ["DISTRL_QUANT_MATMUL"] = mode
            try:
                use, interp = quant_matmul_dispatch()
            finally:
                del os.environ["DISTRL_QUANT_MATMUL"]
            assert use is want_use
            if mode == "interpret":
                assert interp is True


class TestEngineGreedyBitIdentity:
    """The ISSUE-15 acceptance pin: greedy decode with base_quant=int8
    through the fused kernel is bit-identical to the XLA-container path."""

    @pytest.mark.parametrize("bits", [8, 4])
    def test_engine_tokens_identical(self, bits):
        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.models import TINY, init_lora_params, init_params

        params = quantize_params(
            init_params(jax.random.PRNGKey(0), TINY), bits=bits,
            group_size=16,
        )
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        prompts = np.random.default_rng(0).integers(
            2, TINY.vocab_size, (2, 8)
        ).astype(np.int32)
        samp = SamplingConfig(max_tokens=8, temperature=0.0, top_p=1.0, n=2)
        outs = {}
        for mode in ("xla", "interpret"):
            os.environ["DISTRL_QUANT_MATMUL"] = mode
            try:
                eng = GenerationEngine(
                    TINY, max_prompt_tokens=8, max_new_tokens=8,
                    eos_token_ids=[1], pad_token_id=0, autotune=False,
                )
                outs[mode] = eng.generate(
                    params, lora, prompts, np.ones_like(prompts), samp,
                    jax.random.PRNGKey(2),
                ).tokens
            finally:
                del os.environ["DISTRL_QUANT_MATMUL"]
        assert (outs["xla"] == outs["interpret"]).all()
