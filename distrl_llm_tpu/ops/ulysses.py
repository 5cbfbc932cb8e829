"""Ulysses attention: sequence parallelism by all-to-all head scatter.

The second sequence-parallel strategy SURVEY §5 names for long-context
training (alongside ring attention, ops/ring_attention.py): instead of
rotating KV chunks around a ring, one ``all_to_all`` re-shards the activations
from sequence-sharded to HEAD-sharded, each device runs ordinary full-sequence
attention over its H/sp heads, and a second ``all_to_all`` restores sequence
sharding. Communication is two all-to-alls of the activations per layer
(DeepSpeed-Ulysses' cost model) versus ring's sp−1 KV-chunk hops; it wins
when heads ≥ sequence shards and the interconnect favors bulk all-to-all
(TPU ICI does).

Constraints (checked): S, H, and K (kv heads) must all divide by sp. GQA
grouping survives the scatter because contiguous blocks of H/sp query heads
map exactly onto blocks of K/sp kv heads.

Gradients flow through shard_map/all_to_all, so the same function serves the
learner's forward and backward; ``jax.checkpoint`` composes around it.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, PartitionSpec as P

from distrl_llm_tpu.ops.attention import attention

from jax import shard_map as _shard_map


def _ulysses_local(q, k, v, kv_valid, *, axis_name: str, sp: int, scale: float,
                   local_impl: str):
    """Per-shard body. q [B, c, H, D], k/v [B, c, K, D], kv_valid [B, c]
    (c = S/sp) → [B, c, H, D]."""
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # seq-sharded → head-sharded: [B, c, H, D] → [B, S, H/sp, D]
    q = a2a(q, split_axis=2, concat_axis=1)
    k = a2a(k, split_axis=2, concat_axis=1)
    v = a2a(v, split_axis=2, concat_axis=1)
    valid = jax.lax.all_gather(kv_valid, axis_name, axis=1, tiled=True)  # [B, S]
    # the per-device full-sequence attention goes through the dispatching
    # front door so long-context runs use the O(S)-memory Pallas kernels
    # (splash: native GQA) — materializing [*, S, S] logits here would defeat
    # the sequence parallelism exactly at the lengths it exists for; the
    # reference fallback (CPU tests) builds the dense causal mask itself
    out = attention(q, k, v, None, scale=scale, impl=local_impl, key_valid=valid)
    # head-sharded → seq-sharded: [B, S, H/sp, D] → [B, c, H, D]
    return a2a(out, split_axis=1, concat_axis=2)


def ulysses_attention(
    q: jax.Array,  # [B, S, H, D] global
    k: jax.Array,  # [B, S, K, D]
    v: jax.Array,  # [B, S, K, D]
    key_valid: jax.Array,  # [B, S] 1 = real token
    *,
    mesh: Mesh,
    scale: float | None = None,
    axis_name: str = "sp",
    batch_axis: str | None = "dp",
    local_impl: str = "auto",  # per-device attention: auto | splash | flash | reference
) -> jax.Array:
    """Causal GQA self-attention, sequence-parallel via head scatter.

    Semantics match ``attention_reference(q, k, v,
    causal_padding_mask(key_valid, S))`` up to f32 accumulation order.
    """
    if local_impl == "auto":
        # splash (native GQA, O(S) memory) on TPU; the dense reference off it
        local_impl = "splash" if jax.default_backend() == "tpu" else "reference"
    sp = mesh.shape[axis_name]
    b, s, h, _ = q.shape
    kh = k.shape[2]
    if s % sp != 0:
        raise ValueError(f"sequence {s} not divisible by sp={sp}")
    if h % sp != 0 or kh % sp != 0:
        raise ValueError(
            f"heads must divide by sp for ulysses: H={h}, K={kh}, sp={sp} "
            "(use ring attention when they don't)"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b_ax = batch_axis
    if b_ax is not None and (
        b_ax not in mesh.shape or b % mesh.shape[b_ax] != 0
    ):
        b_ax = None
    body = partial(_ulysses_local, axis_name=axis_name, sp=sp, scale=scale,
                   local_impl=local_impl)
    seq_spec = P(b_ax, axis_name, None, None)
    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(b_ax, axis_name)),
        out_specs=seq_spec,
    )(q, k, v, key_valid)
