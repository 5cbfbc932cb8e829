"""Latent attention (MLA, DeepSeek-V2/V3): one cached row a token for all heads.

A token's cache row is ``[c, k_pe]``: the normed latent ``c`` (``kv_lora_rank``
values) and the rotated position key ``k_pe`` (``qk_rope_head_dim`` values,
shared by every head). A head's key is ``[c W_k[h], k_pe]`` and its value
``c W_v[h]`` (``W_kvb = [W_k | W_v]`` a head), so the same function of the cache
has two forms, and which is cheaper depends on queries a cached token:

* **expanded** (many queries: prefill, the learner): rebuild K and V per head
  from the latent, ``nope + rope``-wide scores, ``v``-wide values;
* **absorbed** (one query a row: decode): fold ``W_k`` into the query and
  ``W_v`` into the output, so every head attends over the latent row itself:
  ``scores = (q_nope W_k[h]^T) . c + q_pe . k_pe``, ``o = (sum p c) W_v[h]``.
  The cache is read ``latent_dim`` values a token, whatever the heads. A
  row's pages are gathered and folded in a block of pages at a time
  (``absorbed_attention``'s running softmax): the gathered context of 64 rows
  of 21k tokens, 1.5 GB a layer, never exists whole.

Both are plain XLA here (no Mosaic kernel yet: ROADMAP). The softmax scale is
``(nope + rope)^-0.5`` in both. RoPE pairs are DeepSeek's interleaved ones,
``(x[2i], x[2i+1])``; the output keeps the halves apart (evens first), which a
score cannot tell as long as q and k are rotated alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.attention import NEG_INF


def rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x [B, S, ..., D]`` by ``cos`` / ``sin [B, S, D/2]``, pairing
    ``(x[2i], x[2i+1])``; returns ``[rotated evens | rotated odds]``."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    extra = (None,) * (x.ndim - 3)
    cos = cos[(slice(None), slice(None)) + extra].astype(x.dtype)
    sin = sin[(slice(None), slice(None)) + extra].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def split_kvb(w_kvb: jax.Array, heads: int, nope: int, v_dim: int):
    """``W_kvb [rank, heads * (nope + v)]`` -> ``(W_k [rank, H, nope],
    W_v [rank, H, v])``."""
    w = w_kvb.reshape(w_kvb.shape[0], heads, nope + v_dim)
    return w[..., :nope], w[..., nope:]


def expanded_attention(
    q_nope: jax.Array,  # [B, Sq, H, nope]
    q_pe: jax.Array,  # [B, Sq, H, rope], rotated
    kv: jax.Array,  # [B, Sk, H, nope + v]: the latent through W_kvb
    k_pe: jax.Array,  # [B, Sk, rope], rotated
    mask: jax.Array,  # [B, Sq, Sk] bool; True = attend
    carry=None,
):
    """Attention with K and V rebuilt per head, one block of keys folded into
    a running softmax (flash-style, in XLA). ``carry = (m [B, H, Sq],
    l [B, H, Sq], acc [B, Sq, H, v])`` in float32, ``None`` to start;
    ``expanded_finish`` gives ``[B, Sq, H, v]``. A prefill segment attends over
    the earlier segments' pages block by block, so the scores of a 21k-token
    context never exist at once; the learner's rows are one block. The shared
    ``k_pe`` is contracted on its own: it is never copied a head."""
    b, sq, h, nope = q_nope.shape
    m, l, acc = carry or expanded_start(b, sq, h, kv.shape[-1] - nope)
    scale = (nope + q_pe.shape[-1]) ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q_nope, kv[..., :nope],
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bqhd,bkd->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None], scores * scale, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.where(mask[:, None], jnp.exp(scores - m_new[..., None]), 0.0)
    fix = jnp.exp(m - m_new)
    l = l * fix + p.sum(axis=-1)
    acc = acc * fix.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(kv.dtype), kv[..., nope:],
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def expanded_start(b: int, sq: int, heads: int, v_dim: int):
    """The running softmax before any key."""
    return (
        jnp.full((b, heads, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, heads, sq), jnp.float32),
        jnp.zeros((b, sq, heads, v_dim), jnp.float32),
    )


def expanded_finish(carry, dtype) -> jax.Array:
    """The running softmax's output; a query that saw no key gives zeros."""
    _, l, acc = carry
    return (acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]).astype(dtype)


def absorbed_query(q_nope: jax.Array, q_pe: jax.Array, w_k: jax.Array) -> jax.Array:
    """``[q_nope W_k^T, q_pe]``: one query a (row, head) against the cached row
    itself. ``q_nope [B, H, nope]``, ``q_pe [B, H, rope]`` (rotated),
    ``w_k [rank, H, nope]`` -> ``[B, H, rank + rope]``."""
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_k.astype(q_nope.dtype))
    return jnp.concatenate([q_lat, q_pe], axis=-1)


def absorbed_attention(
    q_row: jax.Array,  # [B, H, rank + rope] from ``absorbed_query``
    latent: jax.Array,  # [B, Sk, rank + rope]: a block of the rows' cached [c, k_pe]
    seen: jax.Array,  # [B, Sk] bool: which cached rows the query may see
    scale: float,  # (nope + rope)^-0.5
    carry=None,
):
    """One block of cached rows folded into the running softmax of a decode
    step. ``carry = (m [B, H], l [B, H], acc [B, H, rank + rope])`` in float32,
    ``None`` to start. The score is one contraction over the row, and the
    values are the same row: the block is read for both."""
    m, l, acc = carry or absorbed_start(*q_row.shape)
    scores = jnp.einsum(
        "bhd,bkd->bhk", q_row.astype(latent.dtype), latent,
        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None], scores * scale, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.where(seen[:, None], jnp.exp(scores - m_new[..., None]), 0.0)
    fix = jnp.exp(m - m_new)
    # over the whole row, cut in ``absorbed_output``: a slice of the block
    # would be a copy of it
    acc = acc * fix[..., None] + jnp.einsum(
        "bhk,bkd->bhd", p.astype(latent.dtype), latent,
        preferred_element_type=jnp.float32)
    return m_new, l * fix + p.sum(axis=-1), acc


def absorbed_start(b: int, heads: int, row: int):
    """A decode step's running softmax before any cached row."""
    return (jnp.full((b, heads), NEG_INF, jnp.float32),
            jnp.zeros((b, heads), jnp.float32),
            jnp.zeros((b, heads, row), jnp.float32))


def absorbed_output(carry, w_v: jax.Array, dtype) -> jax.Array:
    """``(sum p c) W_v`` a head: ``[B, H, v]`` from the running softmax."""
    _, l, acc = carry
    rank = w_v.shape[0]
    o_lat = (acc[..., :rank] / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)
    return jnp.einsum("bhr,rhv->bhv", o_lat, w_v.astype(dtype))
