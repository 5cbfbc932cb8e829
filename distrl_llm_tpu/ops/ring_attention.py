"""Ring attention: causal GQA attention with the sequence sharded over "sp".

The long-context learner path the reference cannot express (SURVEY §2c/§5:
max sequence is hard-fixed at 1,550 tokens — distributed_actor.py:25; scaling
CoT to 4k+ needs sequence parallelism). Design:

* q/k/v are sequence-sharded over the mesh's ``sp`` axis (shard_map); each
  device owns one contiguous chunk of the sequence.
* KV chunks rotate around the ring with ``lax.ppermute`` (ICI
  neighbor-to-neighbor — the cheapest collective there is) while each device
  folds every chunk into an online-softmax accumulator (running max ``m``,
  normalizer ``l``, weighted sum ``o``) — the flash-attention recurrence, so
  no device ever materializes more than [B, c, H, c] logits for chunk c = S/sp.
* causality and key padding are applied per chunk from GLOBAL positions
  (chunk index × chunk length + local offset), so the result matches the
  single-device ``causal_padding_mask`` formulation exactly.
* grouped-query heads contract directly against the K kv heads (same trick
  as ops/attention.py — no repeat_kv materialization).

Gradients flow through shard_map/ppermute, so the same function serves the
learner's forward AND backward; `jax.checkpoint` composes around it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distrl_llm_tpu.ops.attention import NEG_INF

from jax import shard_map as _shard_map


def _chunk_logits(q, k, scale):
    """Grouped-query logits: q [B,c,K,G,D] × k [B,s,K,D] → [B,K,G,c,s] f32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32) * scale


def _ring_local(q, k, v, kv_valid, *, axis_name: str, sp: int, scale: float,
                varying_axes: tuple[str, ...]):
    """Per-shard body. q/k/v: [B, c, H|K, D] local chunks; kv_valid: [B, c]."""
    b, c, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.astype(jnp.float32).reshape(b, c, kh, g, d)
    my = jax.lax.axis_index(axis_name)
    q_pos = my * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)  # [c, 1]

    # online-softmax accumulators — pcast marks the constant inits as
    # varying over the same mesh axes as the sharded inputs so the fori_loop
    # carry type matches the updated values under shard_map's varying-axis
    # typing
    m = jnp.full((b, kh, g, c), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kh, g, c), jnp.float32)
    o = jnp.zeros((b, kh, g, c, d), jnp.float32)
    m, l, o = jax.lax.pcast((m, l, o), varying_axes, to="varying")

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def fold(j, m, l, o, k, v, kv_valid):
        """Fold the chunk currently held (originally from device my − j) into
        the online-softmax accumulators."""
        src = (my - j) % sp
        kv_pos = src * c + jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)  # [1, c]
        allowed = (kv_pos <= q_pos)[None, None, None]  # [1,1,1,c,c] causal
        allowed = allowed & kv_valid[:, None, None, None, :].astype(bool)
        s_blk = _chunk_logits(qg, k.astype(jnp.float32), scale)  # [B,K,G,c,c]
        s_blk = jnp.where(allowed, s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        # guard exp(NEG_INF - NEG_INF) for all-masked rows
        alpha = jnp.exp(jnp.clip(m - m_new, a_min=-80.0, a_max=0.0))
        p = jnp.exp(jnp.clip(s_blk - m_new[..., None], a_min=-80.0, a_max=0.0))
        p = jnp.where(allowed, p, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v.astype(jnp.float32)
        )
        return m_new, l, o

    def step(j, carry):
        m, l, o, k, v, kv_valid = carry
        m, l, o = fold(j, m, l, o, k, v, kv_valid)
        k, v, kv_valid = jax.lax.ppermute((k, v, kv_valid), axis_name, perm)
        return m, l, o, k, v, kv_valid

    # rotate sp−1 times; the last chunk is folded outside the loop so the
    # final (discarded) ppermute never happens
    m, l, o, k, v, kv_valid = jax.lax.fori_loop(
        0, sp - 1, step, (m, l, o, k, v, kv_valid)
    )
    m, l, o = fold(sp - 1, m, l, o, k, v, kv_valid)
    out = jnp.where(l[..., None] > 0, o / jnp.maximum(l, 1e-30)[..., None], 0.0)
    # [B,K,G,c,D] → [B,c,H,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, d).astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, S, H, D] global
    k: jax.Array,  # [B, S, K, D]
    v: jax.Array,  # [B, S, K, D]
    key_valid: jax.Array,  # [B, S] 1 = real token
    *,
    mesh: Mesh,
    scale: float | None = None,
    axis_name: str = "sp",
    batch_axis: str | None = "dp",
) -> jax.Array:
    """Causal self-attention with sequence parallelism over ``axis_name``.

    Semantics match ``attention_reference(q, k, v,
    causal_padding_mask(key_valid, S))`` up to f32 accumulation order; S must
    divide evenly by the sp axis size.

    The batch dim is additionally sharded over ``batch_axis`` when it divides
    evenly (otherwise replicated — correct but redundant across that axis).
    Heads stay unsharded: the learner mesh this serves uses dp×sp(×fsdp for
    params); combine tp with ring only by threading a head spec here first.
    """
    sp = mesh.shape[axis_name]
    s = q.shape[1]
    if s % sp != 0:
        raise ValueError(f"sequence {s} not divisible by sp={sp}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b_ax = batch_axis
    if b_ax is not None and (
        b_ax not in mesh.shape or q.shape[0] % mesh.shape[b_ax] != 0
    ):
        b_ax = None
    varying = (axis_name,) if b_ax is None else (b_ax, axis_name)
    body = partial(
        _ring_local, axis_name=axis_name, sp=sp, scale=scale,
        varying_axes=varying,
    )
    seq_spec = P(b_ax, axis_name, None, None)
    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(b_ax, axis_name)),
        out_specs=seq_spec,
    )(q, k, v, key_valid)
