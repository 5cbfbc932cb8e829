"""Pallas flash attention for the training/prefill path (N1/N3 equivalent).

Wraps jaxlib's Pallas TPU flash-attention kernel (differentiable: custom-VJP
fwd+bwd kernels) behind the same ``(q, k, v, mask, scale)`` interface as
``attention_reference``, so ``attention(..., impl="flash")`` swaps the O(S²)
XLA softmax for the O(S)-memory blockwise kernel. This is what makes 4k+
long-CoT learner forwards (reference recipe 4) fit: at S=4k the reference path
materializes [B, H, S, S] f32 logits (~1 GB per layer at B=8), flash keeps
only block-sized tiles in VMEM.

Interface contract (checked, falls back to the XLA path via
``NotImplementedError`` otherwise — see ops/attention.py):

* self-attention with ``Sq == Sk`` and a causal+key-padding mask of the form
  produced by ``causal_padding_mask(attention_mask, q_len=S, q_offset=0)`` —
  the key-validity vector is recovered from the mask's last query row;
* TPU backend only (the kernel is Mosaic-compiled).

Sequence lengths are padded up to the kernel's block multiple with
segment-id-0 rows, which the segment mask excludes from every real token's
attention window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry

_BLOCK = 128  # kernel block granularity; seq is padded up to a multiple


@functools.cache
def _kernel():
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    return fa


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, K, D]
    v: jax.Array,  # [B, Sk, K, D]
    mask: jax.Array | None,  # [B, 1, Sq, Sk] from causal_padding_mask
    scale: float | None = None,
    key_valid: jax.Array | None = None,  # [B, Sk]; preferred over mask
) -> jax.Array:
    if jax.default_backend() != "tpu":
        raise NotImplementedError("flash attention requires the TPU backend")
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    if sq != sk:
        raise NotImplementedError("flash path expects self-attention (Sq == Sk)")
    if mask is not None and mask.shape[1] != 1:
        raise NotImplementedError("flash path expects a head-agnostic mask")
    fa = _kernel()
    if scale is None:
        scale = d**-0.5

    # GQA → MHA for the kernel's equal-head contract. The repeat costs G× KV
    # VMEM traffic only inside the (remat'd) training forward — the decode hot
    # loop never takes this path.
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)

    if key_valid is not None:
        # direct [B, Sk] contract — no dense mask was ever materialized
        valid = key_valid.astype(jnp.int32)
    elif mask is not None:
        # legacy contract: key validity from the mask's last query row (with
        # causal ∧ padding and q_offset=0, row S-1 attends exactly the valid keys)
        valid = mask[:, 0, -1, :].astype(jnp.int32)  # [B, Sk]
    else:
        valid = jnp.ones((b, sk), jnp.int32)

    pad = (-sq) % _BLOCK
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    s = sq + pad

    # kernel layout [B, H, S, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    seg = fa.SegmentIds(q=valid, kv=valid)

    block = min(_BLOCK, s)
    sizes = fa.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block,
        block_k_dkv=block, block_q_dkv=block,
        block_k_major_dq=block, block_k_dq=block, block_q_dq=block,
    )
    with jax.named_scope(telemetry.KERNEL_FLASH):
        out = fa.flash_attention(
            qt, kt, vt, segment_ids=seg, causal=True, sm_scale=scale,
            block_sizes=sizes,
        )
    out = out.transpose(0, 2, 1, 3)  # [B, S, H, D]
    if pad:
        out = out[:, :sq]
    return out.astype(q.dtype)
