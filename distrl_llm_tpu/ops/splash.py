"""Splash attention (Pallas) for GQA training forwards — no repeat_kv.

The flash path's GQA handling is wasteful: jaxlib's flash kernel
demands equal head counts, so K/V are ``jnp.repeat``-ed to full heads — the
exact KV traffic multiplication (7× for Qwen2.5-0.5B) the decode path avoids.
The splash kernel is natively multi-query: built per KV head group
(``make_splash_mqa_single_device``) and vmapped over KV heads and batch, K/V
move through the kernel ONCE at their true head count.

Causality + right-padding come from a CausalMask plus SegmentIds (padding
tokens get segment 0, real tokens 1 — cross-segment attention is masked).
``interpret=True`` runs the same kernel under the Pallas interpreter so CPU
CI tests true parity with the XLA reference (tests/test_splash.py).

Selected via ``attn_impl="splash"`` (training/uncached forwards only; decode
uses the paged/cached paths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry


@functools.cache
def _mods():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as mask_lib,
    )

    return kernel, mask_lib


@functools.cache
def _make_kernel(groups: int, seq: int, block: int, interpret: bool):
    kernel, mask_lib = _mods()
    mask = mask_lib.MultiHeadMask(
        [mask_lib.CausalMask((seq, seq)) for _ in range(groups)]
    )
    block_sizes = kernel.BlockSizes(
        block_q=min(block, seq),
        block_kv=min(block, seq),
        block_kv_compute=min(block, seq),
        block_q_dkv=min(block, seq),
        block_kv_dkv=min(block, seq),
        block_kv_dkv_compute=min(block, seq),
        block_q_dq=min(block, seq),
        block_kv_dq=min(block, seq),
    )
    # the kernel object carries its mask-info arrays; built under a trace
    # they would be that trace's tracers, and this cache would hand them to
    # the next one (UnexpectedTracerError on the second jit of one geometry)
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            mask, block_sizes=block_sizes, interpret=interpret
        )


def splash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, K, D]
    v: jax.Array,  # [B, S, K, D]
    key_valid: jax.Array | None,  # [B, S] 1 = real token
    scale: float | None = None,
    block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA self-attention via the splash kernel. Differentiable
    (custom-VJP kernels). Sequence must be a multiple of the kernel's lane
    width; callers' fixed shapes are padded here if needed.

    ``interpret=True`` runs the Pallas interpreter (tests on CPU — orders of
    magnitude slower than the XLA reference; production non-TPU callers
    should fall back via ``attention(..., impl="splash")`` instead)."""
    kernel, _ = _mods()
    b, s, h, d = q.shape
    kh = k.shape[2]
    if h % kh:
        raise ValueError(f"q heads {h} not divisible by kv heads {kh}")
    g = h // kh
    if scale is None:
        scale = d**-0.5

    pad = (-s) % 128  # splash lane granularity
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if key_valid is not None:
            key_valid = jnp.pad(key_valid, ((0, 0), (0, pad)))
    sp = s + pad

    if key_valid is None:
        key_valid = jnp.ones((b, sp), jnp.int32)
    seg = kernel.SegmentIds(
        q=key_valid.astype(jnp.int32), kv=key_valid.astype(jnp.int32)
    )

    splash = _make_kernel(g, sp, block, interpret)
    # [B, S, H, D] → per-KV-head groups [B, K, G, S, D]; K/V [B, K, S, D]
    qg = (q * scale).transpose(0, 2, 1, 3).reshape(b, kh, g, sp, d)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    # vmap over KV heads (shared segment ids), then over batch
    per_head = jax.vmap(splash, in_axes=(0, 0, 0, None))
    per_batch = jax.vmap(per_head, in_axes=(0, 0, 0, 0))
    with jax.named_scope(telemetry.KERNEL_SPLASH):
        out = per_batch(qg, kt, vt, seg)  # [B, K, G, S, D]
    out = out.reshape(b, h, sp, d).transpose(0, 2, 1, 3)
    if pad:
        out = out[:, :s]
    return out.astype(q.dtype)
