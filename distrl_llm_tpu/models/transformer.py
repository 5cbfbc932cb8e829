"""Pure-JAX GQA decoder (Qwen2 / Llama-3 family) over param pytrees.

TPU-first design choices, deliberately unlike the reference's torch modules:

* **Stacked layers + lax.scan** — per-layer params are stacked on a leading
  [L, ...] axis and the decoder scans one compiled layer body over them. XLA
  compiles the layer once instead of L times, and the same scan carries the KV
  cache through prefill/decode.
* **Functional everywhere** — params are nested dicts; the forward is a pure
  function of (params, lora, inputs, cache), so jit/pjit/grad/remat compose
  trivially and weight sync is array movement, not module surgery.
* **Fixed shapes** — callers pad to static prompt/answer lengths (the
  reference already does this on the learner side: distributed_actor.py:217–229),
  so every distinct shape compiles exactly once.

LoRA (q/k/v/o/gate/up/down targets — helper.py:29–37) is a separate pytree of
stacked (A, B) factors applied additively inside the layer body; the base tree
is frozen and may hold quantized weight containers (ops/quant.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.models import moe
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.ops.attention import attention, attention_cached, causal_padding_mask
from distrl_llm_tpu.ops.linear import OutIn, linear, lora_delta

Params = dict[str, Any]


def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             offset: bool = False) -> jax.Array:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if offset:  # Gemma stores the norm weight as a delta around 1
        w = w + 1.0
    return (x * w).astype(orig_dtype)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [B, S] → (cos, sin) each [B, S, head_dim/2], f32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [B, S, H, D] by per-position angles (HF rotate-half convention)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# stable per-target stream ids so each projection's dropout mask differs
_TARGET_STREAM = {
    "wq": 0, "wk": 1, "wv": 2, "wo": 3, "w_gate": 4, "w_up": 5, "w_down": 6,
    "wkv_a": 7, "wkv_b": 8,  # latent attention (models/hybrid.py)
    "w_in": 9, "w_out": 10,  # a Mamba layer's two projections
    "wv1": 11, "wv2": 12,  # compressed convolutional attention's two value halves
}


# The products a rematerialised layer scan may be told to keep for the backward
# pass, in the order the train step fills the room it has (learner/remat.py):
# q/k/v, then the MLP's gate, then its up. ``_proj`` names each where it is
# made; nothing else is worth a byte (the attention core's float32 scores are
# 470 MB a layer at [4, 1024]; the backward pass needs neither ``wo``'s nor
# ``w_down``'s output). A name lowers to nothing, so no program without a
# rematerialised scan, the cache-mode programs among them, changes by a
# character.
KEPT_PRODUCT_GROUPS = (("wq", "wk", "wv"), ("w_gate",), ("w_up",))
KEPT_PRODUCTS = frozenset(n for group in KEPT_PRODUCT_GROUPS for n in group)


def _proj(h, p, lora, key, bias_key, lora_scale,
          lora_dropout: float = 0.0, dropout_rng=None):
    """One projection with optional bias and optional LoRA delta, named for
    the rematerialised scan's policy where it is one of ``KEPT_PRODUCTS``."""
    y = _proj_value(h, p, lora, key, bias_key, lora_scale, lora_dropout, dropout_rng)
    return checkpoint_name(y, key) if key in KEPT_PRODUCTS else y


def _proj_value(h, p, lora, key, bias_key, lora_scale, lora_dropout, dropout_rng):
    """``_proj``'s value: base product, bias, and the adapter's delta.

    A quantized base weight with an active adapter (and no LoRA dropout —
    dropout perturbs the adapter INPUT, which the epilogue can't express)
    dispatches to the fused Pallas dequant-matmul with the LoRA delta
    applied in the kernel epilogue (ops/quant_matmul.py): one program, one
    output-tile round-trip, weight streamed at int width. Same math order
    as the split path — (dot + bias) + delta — so greedy decode is
    bit-identical whichever path ran."""
    has_lora = lora is not None and key in lora
    w = p[key]
    if (
        has_lora and isinstance(w, dict) and w["q"].ndim == 3
        and (lora_dropout <= 0.0 or dropout_rng is None)
    ):
        from distrl_llm_tpu.ops.quant_matmul import (
            dispatch_choices, quant_matmul, quant_matmul_dispatch,
        )

        a, b = lora[key]["a"], lora[key]["b"]
        bits = 4 if w["q"].dtype == jnp.int4 else 8
        use, interp = quant_matmul_dispatch()
        dispatch_choices[
            (bits, h.shape[-1], w["q"].shape[-1], a.shape[-1])
        ] = "kernel" if use else "xla"
        if use:
            return quant_matmul(
                h, w, p.get(bias_key), a, b, lora_scale, interpret=interp
            )
    y = linear(h, p[key], p.get(bias_key))
    if has_lora:
        rng = (
            jax.random.fold_in(dropout_rng, _TARGET_STREAM[key])
            if dropout_rng is not None else None
        )
        y = y + lora_delta(
            h, lora[key]["a"], lora[key]["b"], lora_scale,
            dropout_rate=lora_dropout, dropout_rng=rng,
        )
    return y


def _layer(
    x: jax.Array,  # [B, S, D]
    p: Params,  # one layer's params (leading L axis already sliced off)
    lora: Params | None,
    cache_k: jax.Array | None,  # [B, K, hd, Smax] — S minormost (attention_cached)
    cache_v: jax.Array | None,
    *,
    cache_k_scale: jax.Array | None = None,  # f32 [B, K, 1, Smax] — int8 KV
    cache_v_scale: jax.Array | None = None,
    cfg: ModelConfig,
    cos: jax.Array,
    sin: jax.Array,
    mask: jax.Array | None,
    cache_offset: jax.Array | int,
    lora_scale: float,
    attn_impl: str,
    attn_mesh=None,
    key_valid: jax.Array | None = None,  # [B, S] for the ring path
    paged_lengths: jax.Array | None = None,  # [B] — paged-cache mode
    page_indices: jax.Array | None = None,  # [B, pps]
    page_size: int = 0,
    paged_impl: str = "auto",
    paged_verify: bool = False,  # S>1 per-row draft-block decode (spec decode)
    paged_verify_impl: str = "fused",  # "fused" | "unrolled" verify sweep
    paged_chunked: bool = False,  # S>1 continuation (chunked) prefill
    paged_prefix: bool = False,  # S>1 warm (radix-hit) suffix prefill
    lora_dropout: float = 0.0,
    dropout_rng: jax.Array | None = None,  # per-layer key (training only)
    cache_read_formulation: str = "dot",  # "mulred" inside scan-chunk bodies
):
    b, s, _ = x.shape
    proj = partial(_proj, lora_dropout=lora_dropout, dropout_rng=dropout_rng)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps, offset=cfg.rmsnorm_offset)
        q = proj(h, p, lora, "wq", "bq", lora_scale).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = proj(h, p, lora, "wk", "bk", lora_scale).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = proj(h, p, lora, "wv", "bv", lora_scale).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    with jax.named_scope(telemetry.MODEL_ATTN_CORE):
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        att, cache_k, cache_v, cache_k_scale, cache_v_scale = _attend(
            q, k, v, cache_k, cache_v, cache_k_scale, cache_v_scale,
            mask=mask, cache_offset=cache_offset, attn_impl=attn_impl,
            attn_mesh=attn_mesh, key_valid=key_valid,
            paged_lengths=paged_lengths, page_indices=page_indices,
            page_size=page_size, paged_impl=paged_impl,
            paged_verify=paged_verify, paged_verify_impl=paged_verify_impl,
            paged_chunked=paged_chunked, paged_prefix=paged_prefix,
            cache_read_formulation=cache_read_formulation,
        )
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        att = att.reshape(b, s, cfg.q_dim)
        out = proj(att, p, lora, "wo", "bo", lora_scale)
        if "attn_out_norm" in p:  # a sublayer normed on both sides (ouro)
            out = rms_norm(out, p["attn_out_norm"], cfg.rms_norm_eps,
                           offset=cfg.rmsnorm_offset)
        x = x + out

    x = _mlp_half(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale)
    return x, cache_k, cache_v, cache_k_scale, cache_v_scale


def _mlp_half(x, p: Params, lora, *, cfg: ModelConfig, proj, lora_scale,
              residual_scale=None):
    """The second half of a layer of any kind: norm, gated MLP, residual
    (``residual_scale``: muP's factor on what joins the stream, or None). A
    layer that holds no ``w_gate`` has the UNGATED MLP ``W_down relu(W_up h)^2``
    (``nemotron_h``'s shared expert: models/moe.py)."""
    with jax.named_scope(telemetry.MODEL_MLP):
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps, offset=cfg.rmsnorm_offset)
        act = (
            jax.nn.silu if cfg.hidden_act == "silu"
            else partial(jax.nn.gelu, approximate=True)  # Gemma gelu_pytorch_tanh
        )
        if "w_gate" in p:
            gate = act(proj(h, p, lora, "w_gate", "b_gate", lora_scale))
            up = gate * proj(h, p, lora, "w_up", "b_up", lora_scale)
        else:
            up = moe.relu2(proj(h, p, lora, "w_up", "b_up", lora_scale))
        y = proj(up, p, lora, "w_down", "b_down", lora_scale)
        if "mlp_out_norm" in p:  # a sublayer normed on both sides (ouro)
            y = rms_norm(y, p["mlp_out_norm"], cfg.rms_norm_eps,
                         offset=cfg.rmsnorm_offset)
        return x + (y if residual_scale is None else residual_scale * y)


def _attend(
    q: jax.Array,  # [B, S, H, hd], rotated
    k: jax.Array,  # [B, S, K, hd], rotated
    v: jax.Array,
    cache_k, cache_v, cache_k_scale, cache_v_scale,
    *, mask, cache_offset, attn_impl: str, attn_mesh, key_valid,
    paged_lengths, page_indices, page_size: int, paged_impl: str,
    paged_verify: bool, paged_verify_impl: str, paged_chunked: bool,
    paged_prefix: bool, cache_read_formulation: str,
):
    """The layer's KV write (under ``engine/kv_write``) and its attention
    call, whichever implementation: paged, dense cache, ring, ulysses or plain.
    Returns ``(att, cache_k, cache_v, cache_k_scale, cache_v_scale)``."""
    b, s = q.shape[:2]
    if cache_k is not None and page_indices is not None:
        # paged cache (ops/paged.py — the N1 ragged decode path): cache_k/v
        # are page arrays [K, total_pages, ps, hd]; sequences are PACKED, so
        # attention reads each row's true [0, length) prefix only.
        from distrl_llm_tpu.ops.paged import (
            paged_attention_op, write_prompt_to_pages, write_token_to_pages,
            write_tokens_to_pages,
        )

        if s == 1:
            with jax.named_scope(telemetry.ENGINE_KV_WRITE):
                cache_k = write_token_to_pages(
                    cache_k, k[:, 0], paged_lengths, page_indices, page_size)
                cache_v = write_token_to_pages(
                    cache_v, v[:, 0], paged_lengths, page_indices, page_size)
            att = paged_attention_op(
                q[:, 0], cache_k, cache_v, paged_lengths + 1, page_indices,
                impl=paged_impl,
            )[:, None]
        elif paged_chunked:
            # continuation (chunked) prefill: S tokens extend each row's
            # sequence at its own per-row offset (recompute after preemption —
            # vLLM's chunked prefill). KV is written to pages first (padding
            # positions dropped via ``valid``), then attention runs over the
            # row's dense-gathered context with exact per-position causality.
            from distrl_llm_tpu.ops.paged import (
                chunked_context_attention, gather_pages_dense,
            )

            q_valid = key_valid[:, :s] if key_valid is not None else (
                jnp.ones((b, s), jnp.int32)
            )
            with jax.named_scope(telemetry.ENGINE_KV_WRITE):
                cache_k = write_tokens_to_pages(
                    cache_k, k, paged_lengths, page_indices, page_size,
                    valid=q_valid > 0)
                cache_v = write_tokens_to_pages(
                    cache_v, v, paged_lengths, page_indices, page_size,
                    valid=q_valid > 0)
            att = chunked_context_attention(
                q, gather_pages_dense(cache_k, page_indices),
                gather_pages_dense(cache_v, page_indices),
                paged_lengths, q_valid,
            )
        elif paged_prefix:
            # warm (radix-hit) suffix prefill: the row's first
            # ``paged_lengths`` positions are already resident in cached
            # pages; only the suffix re-forwards. Bit-identity with the
            # packed cold prefill demands the SAME attention numerics
            # (``attention_reference`` rounds probs to the value dtype
            # before the PV product; ``chunked_context_attention`` keeps
            # them f32 to match the decode op), so this branch writes the
            # suffix KV to pages and then attends over the row's
            # dense-gathered packed window in COMPUTE dtype through the
            # same ``attention`` front door the cold path uses. Contract:
            # ``page_indices`` carries ONE trailing scratch column (the
            # engine's warm-admission row extension) — the gather drops it
            # so the key window width equals the cold packed width.
            from distrl_llm_tpu.ops.paged import gather_pages_dense

            q_valid = key_valid[:, :s] if key_valid is not None else (
                jnp.ones((b, s), jnp.int32)
            )
            with jax.named_scope(telemetry.ENGINE_KV_WRITE):
                cache_k = write_tokens_to_pages(
                    cache_k, k, paged_lengths, page_indices, page_size,
                    valid=q_valid > 0)
                cache_v = write_tokens_to_pages(
                    cache_v, v, paged_lengths, page_indices, page_size,
                    valid=q_valid > 0)
            ctx_k = gather_pages_dense(
                cache_k, page_indices[:, :-1], dtype=q.dtype)
            ctx_v = gather_pages_dense(
                cache_v, page_indices[:, :-1], dtype=q.dtype)
            # query i sits at global position lengths+i; causality over the
            # packed window reproduces the cold mask rows for real lanes
            # (padding lanes attend garbage, but their outputs land on the
            # scratch page and the logits gather never reads them)
            jpos = jnp.arange(ctx_k.shape[1])[None, None, None, :]
            qpos = (paged_lengths[:, None]
                    + jnp.arange(s, dtype=jnp.int32)[None, :])[:, None, :, None]
            att = attention(q, ctx_k, ctx_v, jpos <= qpos, impl=attn_impl)
        elif paged_verify:
            # speculative-decode verify: S draft tokens extend each row's
            # sequence at its own per-row offset. QKV/MLP batch over the
            # whole block (the weight-bandwidth amortization speculative
            # decoding buys); attention goes through paged_verify_op —
            # draft position i attends over the prefix plus draft tokens
            # ≤ i (lengths + i + 1, exact causality), as ONE fused blocked
            # sweep when the hardware can (ops/paged_native.py
            # paged_attention_native_verify) or unrolled per position
            # (paged_verify_impl="unrolled" / non-TPU backends)
            from distrl_llm_tpu.ops.paged import paged_verify_op

            with jax.named_scope(telemetry.ENGINE_KV_WRITE):
                cache_k = write_tokens_to_pages(
                    cache_k, k, paged_lengths, page_indices, page_size)
                cache_v = write_tokens_to_pages(
                    cache_v, v, paged_lengths, page_indices, page_size)
            att = paged_verify_op(
                q, cache_k, cache_v, paged_lengths, page_indices,
                impl=paged_impl, verify_impl=paged_verify_impl,
            )
        else:
            # packed prefill: write the prompt pages, attend over the input
            with jax.named_scope(telemetry.ENGINE_KV_WRITE):
                cache_k = write_prompt_to_pages(cache_k, k, page_indices, page_size)
                cache_v = write_prompt_to_pages(cache_v, v, page_indices, page_size)
            att = attention(q, k, v, mask, impl=attn_impl, key_valid=key_valid)
    elif cache_k is not None:
        quant = cache_k_scale is not None
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            if quant:
                # int8 KV cache: quantize the new positions per (B, K, position)
                # over head_dim and write values + scales; attention reads the
                # cache at 1 byte/element with dequant folded into the einsums
                from distrl_llm_tpu.ops.attention import quantize_kv_position

                k_t, ks = quantize_kv_position(k.transpose(0, 2, 3, 1))
                v_t, vs = quantize_kv_position(v.transpose(0, 2, 3, 1))
                cache_k_scale = jax.lax.dynamic_update_slice(
                    cache_k_scale, ks, (0, 0, 0, cache_offset))
                cache_v_scale = jax.lax.dynamic_update_slice(
                    cache_v_scale, vs, (0, 0, 0, cache_offset))
            else:
                k_t = k.astype(cache_k.dtype).transpose(0, 2, 3, 1)  # [B, K, hd, S]
                v_t = v.astype(cache_v.dtype).transpose(0, 2, 3, 1)
            cache_k = jax.lax.dynamic_update_slice(cache_k, k_t, (0, 0, 0, cache_offset))
            cache_v = jax.lax.dynamic_update_slice(cache_v, v_t, (0, 0, 0, cache_offset))
        if attn_impl == "flash" and isinstance(cache_offset, int) and cache_offset == 0 and s > 1:
            # prefill: the cache holds nothing beyond the prompt being
            # written, so attention is plain self-attention over the input —
            # run the flash kernel on the fresh k/v and only WRITE the cache
            att = attention(
                q, k, v, mask[..., :s], impl="flash",
                key_valid=key_valid[:, :s] if key_valid is not None else None,
            )
        elif quant:
            from distrl_llm_tpu.ops.attention import attention_cached_quant

            att = attention_cached_quant(
                q, cache_k, cache_k_scale, cache_v, cache_v_scale, mask,
                formulation=cache_read_formulation,
            )
        else:
            att = attention_cached(
                q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask,
                formulation=cache_read_formulation,
            )
    elif attn_impl == "ring" and attn_mesh is not None:
        # sequence-parallel training path: causal+padding semantics come from
        # global positions inside the ring, not from the materialized mask
        from distrl_llm_tpu.ops.ring_attention import ring_attention

        att = ring_attention(q, k, v, key_valid, mesh=attn_mesh)
    elif attn_impl == "ulysses" and attn_mesh is not None:
        # sequence parallelism by head scatter (two all-to-alls per layer);
        # needs H and K divisible by sp — ring covers the rest
        from distrl_llm_tpu.ops.ulysses import ulysses_attention

        att = ulysses_attention(q, k, v, key_valid, mesh=attn_mesh)
    else:
        att = attention(q, k, v, mask, impl=attn_impl, key_valid=key_valid)
    return att, cache_k, cache_v, cache_k_scale, cache_v_scale


_MLP_KEYS = frozenset(
    ("mlp_norm", "mlp_out_norm", "w_gate", "w_up", "w_down", "b_gate", "b_up", "b_down")
)
# an expert layer's own leaves (models/moe.py); any other key is attention's
_SLICE_SCOPES = {
    **dict.fromkeys(_MLP_KEYS, telemetry.MODEL_MLP),
    "router": telemetry.MODEL_MOE_ROUTER,
    "e_score_bias": telemetry.MODEL_MOE_ROUTER,
    **dict.fromkeys(("experts_gate", "experts_up", "experts_down"),
                    telemetry.MODEL_MOE_EXPERTS),
    # a gated delta-rule model's own leaves (models/hybrid.py)
    "conv": telemetry.MODEL_SHORT_CONV,
    **dict.fromkeys(("wf_a", "wf_b", "wb", "A_log", "dt_bias"),
                    telemetry.MODEL_DELTA_ATTN),
    **dict.fromkeys(("wg", "wg_a", "wg_b", "head_norm"), telemetry.MODEL_ATTN_GATE),
    # a state-space model's own leaves
    "b_conv": telemetry.MODEL_SHORT_CONV,
    **dict.fromkeys(("ssm_dt_norm", "ssm_b_norm", "ssm_c_norm", "b_dt", "ssm_a_log",
                     "ssm_d"), telemetry.MODEL_SSM),
}


# The leaves a cache-mode program reads one ``[out, in]`` array a layer
# (``decode_view``), chosen by a census of the decode steps compiled for a v5e
# (tests/test_tpu_compile.py): at a decode step's 32-64 rows the compiler keeps
# each of these resident in the chip's fast memory ahead of its matmul, and
# sliced from a stacked ``[layers, in, out]`` leaf that costs a synchronous
# fusion materialising every layer's slice plus a transpose a layer. The MLP's
# and the experts' matrices are too large to be made resident, and their
# slices fuse into the matmuls; a Mamba layer's ``w_in`` / ``w_out`` show
# neither operation and stay stacked.
DECODE_VIEW_KEYS = frozenset(("wq", "wk", "wv", "wo"))
# The kinds whose census differs: of a latent layer's projections ``wq`` alone
# shows the two operations; its ``wo``, ``wkv_a`` and ``wkv_b`` show neither
# and stay stacked.
_KIND_VIEW_KEYS = {**dict.fromkeys(("latent", "latent_moe", "latent_fork", "latent_join"),
                                   frozenset(("wq",))),
                   # compressed convolutional attention: v is two halves
                   "cca": frozenset(("wq", "wk", "wv1", "wv2", "wo"))}


def _slice_layer(stacked: Params, i: int) -> Params:
    """Layer ``i`` of a stacked tree (base weights or LoRA factors), each
    weight taken under the scope of the block that reads it, so a slice the
    compiler does not fuse into its matmul is still that block's time: the
    MLP's slices fuse (``model/mlp`` read 84.5% of its bytes' roofline in
    ``rollout-lockstep``; ledger, PR 44), the mixer's projections did not
    (``model/attn_proj`` 39%: one fusion a step wrote all 14 slices of wq,
    then a transposed copy a layer). A decode view's tuple of one ``OutIn`` a
    layer is indexed, not sliced. Same leaves in the same order as
    ``tree_map(lambda w: w[i], stacked)``."""
    if not isinstance(stacked, dict):
        return jax.tree_util.tree_map(lambda w: w[i], stacked)
    out = {}
    for key in sorted(stacked):
        with jax.named_scope(_SLICE_SCOPES.get(key, telemetry.MODEL_ATTN_PROJ)):
            out[key] = jax.tree_util.tree_map(
                lambda w: w[i], stacked[key],
                is_leaf=lambda w: isinstance(w, tuple))
    return out


@jax.jit
def _per_layer(w: jax.Array) -> tuple[OutIn, ...]:
    """``[layers, in, out]`` as ``layers`` arrays ``[out, in]``, on the
    devices ``w`` is on: a leaf placed on a mesh keeps its sharding with the
    two axes swapped (the partitioner carries it through the transpose)."""
    return tuple(OutIn(w[i].T) for i in range(w.shape[0]))


def decode_view_leaves(layers: dict, keys=DECODE_VIEW_KEYS, path=()):
    """``(path, leaf)`` of every stacked array of ``params["layers"]`` that a
    decode view holds a layer at a time, and so a second time: a table key's
    leaf (the kind's own table where it has one) that is an array. A quantized
    container under such a key is a dict of leaves named otherwise, so none
    of it is listed."""
    for key, leaf in layers.items():
        if isinstance(leaf, dict):
            yield from decode_view_leaves(
                leaf, _KIND_VIEW_KEYS.get(key, keys), path + (key,))
        elif key in keys and getattr(leaf, "ndim", 0) == 3:
            yield path + (key,), leaf


def decode_view(params: Params) -> Params:
    """``params`` as the cache-mode programs read it: every stacked array of
    ``params["layers"]`` under a ``DECODE_VIEW_KEYS`` key (a kind's stack
    under its own keys in a model with per-layer mixers) becomes a tuple of
    one ``OutIn`` a layer; every other leaf, a quantized container under one
    of those keys too, is shared with ``params`` as it is. The learner's path
    (``kv_cache is None``: a scan over stacked leaves) takes the stacked tree,
    never this."""
    layers = params["layers"]
    for path, leaf in decode_view_leaves(layers):
        layers = _with_leaf(layers, path, _per_layer(leaf))
    return {**params, "layers": layers}


def _with_leaf(tree: dict, path: tuple, leaf) -> dict:
    """``tree`` with ``leaf`` at ``path``; the dicts along it are copied."""
    if len(path) == 1:
        return {**tree, path[0]: leaf}
    return {**tree, path[0]: _with_leaf(tree[path[0]], path[1:], leaf)}


def forward(
    params: Params,
    cfg: ModelConfig,
    input_ids: jax.Array,  # [B, S]
    *,
    attention_mask: jax.Array | None = None,  # [B, Sk]; 1 = attendable key
    positions: jax.Array | None = None,  # [B, S] absolute positions
    lora: Params | None = None,
    lora_scale: float = 1.0,
    kv_cache: Params | None = None,  # {"k","v": L-tuples of [B, K, hd, Smax]}
    cache_offset: jax.Array | int = 0,
    remat=False,  # True, or the checkpoint policy of the no-cache layer scan
    attn_impl: str = "reference",
    attn_mesh=None,  # jax Mesh with an "sp" axis; required for attn_impl="ring"
    logits_slice: tuple[int, int] | None = None,  # (start, length) along seq
    logits_positions: jax.Array | None = None,  # [B] per-row position gather
    page_size: int = 0,  # static; paged-cache mode (ops/paged.py)
    paged_impl: str = "auto",
    paged_verify: bool = False,  # speculative-decode draft-block verify
    paged_verify_impl: str = "fused",  # verify sweep: "fused" | "unrolled"
    paged_chunked: bool = False,  # continuation (chunked) prefill over pages
    paged_prefix: bool = False,  # warm (radix-hit) suffix prefill over pages
    lora_dropout: float = 0.0,  # peft-style adapter-input dropout (training)
    dropout_rng: jax.Array | None = None,
    skip_lm_head: bool = False,  # return final-norm hidden states, not logits
    cache_read_formulation: str = "dot",  # see ops.attention.attention_cached
    exit_gates: list | None = None,  # a looped model's gates [T, B, S] are appended
) -> tuple[jax.Array, Params | None]:
    """Decoder forward. Returns (logits f32 [B, S, V], updated kv_cache).

    A looped model (``cfg.loop_steps`` > 1) walks ``params["layers"]`` that
    many times: the final norm closes every pass and its output feeds the
    next, the exit gate reads each pass's output (``_close_pass``), and with a
    cache pass ``u``'s layer ``l`` reads and writes cache layer
    ``u * num_layers + l`` of the ``cfg.paged_layers`` the cache holds. A
    decode step adds its rows' expected exit pass to ``kv_cache["exit_stats"]``
    where the cache carries it.

    Without a cache this is the training/prefill path (causal over the input);
    with a dense cache (per-layer tuples from init_kv_cache — NOT a stacked
    array; the cached path also always uses attention_cached, ignoring
    ``attn_impl``), queries attend to all cache keys marked valid by
    ``attention_mask`` (length Smax) and new K/V are written at
    ``cache_offset``. Contract: ``cache_offset + S <= Smax`` — the engine sizes
    caches as prompt+max_tokens so this holds by construction; writes past
    capacity would be silently clamped by dynamic_update_slice.

    A PAGED cache (``init_paged_kv_cache`` plus traced "lengths" [B] and
    "page_indices" [B, pps] entries in the dict, with the static
    ``page_size``/``paged_impl`` kwargs) switches to the ragged N1 path:
    sequences are packed, prefill self-attends over the input while writing
    prompt pages, and decode runs paged attention over each row's true
    [0, length+1) prefix.
    """
    if cfg.hybrid:
        # layers of several kinds (MiniCPM-SALA): models/hybrid.py has the
        # layer bodies, the per-kind stacks and the cache of two kinds of state
        from distrl_llm_tpu.models.hybrid import forward_hybrid

        return forward_hybrid(
            params, cfg, input_ids, attention_mask=attention_mask,
            positions=positions, lora=lora, lora_scale=lora_scale,
            kv_cache=kv_cache, remat=remat, attn_impl=attn_impl,
            logits_slice=logits_slice, logits_positions=logits_positions,
            page_size=page_size, lora_dropout=lora_dropout,
            dropout_rng=dropout_rng, skip_lm_head=skip_lm_head,
            attn_mesh=attn_mesh, paged_verify=paged_verify,
            paged_chunked=paged_chunked, paged_prefix=paged_prefix,
            paged_impl=paged_impl,
        )
    b, s = input_ids.shape
    paged = kv_cache is not None and "page_indices" in kv_cache
    if kv_cache is not None and not paged and isinstance(cache_offset, int):
        smax = kv_cache["k"][0].shape[-1]
        if cache_offset + s > smax:
            raise ValueError(
                f"KV cache overflow: offset {cache_offset} + seq {s} > capacity {smax}"
            )
    if positions is None:
        positions = cache_offset + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
    with jax.named_scope(telemetry.MODEL_ATTN_CORE):
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    with jax.named_scope(telemetry.MODEL_EMBED):
        x = jnp.take(params["embed"], input_ids, axis=0)
        if cfg.scale_embeddings:  # Gemma: hidden states enter at sqrt(D) scale
            x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)

    # paged caches attend raggedly by per-row length (decode) or over the
    # packed input only (prefill) — the dense key window is the input itself
    sk = kv_cache["k"][0].shape[-1] if (kv_cache is not None and not paged) else s
    cfg.check_within_window(sk)
    if attention_mask is None:
        attention_mask = jnp.ones((b, sk), dtype=jnp.int32)
    # ring and (uncached) flash consume the [B, S] validity vector directly —
    # building the [B, 1, S, S] mask for them would cost O(S²) memory on
    # exactly the long-context paths those kernels exist to avoid (it is also
    # DCE'd under jit, but eager/non-jit callers would pay it)
    needs_dense_mask = (
        (kv_cache is not None and not paged)
        or (paged and s > 1 and not paged_chunked and not paged_prefix
            and attn_impl not in ("ring", "ulysses", "flash", "splash"))
        or (kv_cache is None and attn_impl not in ("ring", "ulysses", "flash", "splash"))
    )
    with jax.named_scope(telemetry.MODEL_ATTN_CORE):
        mask = (
            causal_padding_mask(
                attention_mask, q_len=s, q_offset=0 if paged else cache_offset
            )
            if needs_dense_mask else None
        )

    layer_fn = partial(
        _layer,
        cfg=cfg,
        cos=cos,
        sin=sin,
        mask=mask,
        cache_offset=cache_offset,
        lora_scale=lora_scale,
        attn_impl=attn_impl,
        attn_mesh=attn_mesh,
        key_valid=attention_mask,
        paged_lengths=kv_cache.get("lengths") if paged else None,
        page_indices=kv_cache.get("page_indices") if paged else None,
        page_size=page_size,
        paged_impl=paged_impl,
        paged_verify=paged_verify,
        paged_verify_impl=paged_verify_impl,
        paged_chunked=paged_chunked,
        paged_prefix=paged_prefix,
        lora_dropout=lora_dropout if dropout_rng is not None else 0.0,
        cache_read_formulation=cache_read_formulation,
    )

    layer_keys = (
        jax.random.split(dropout_rng, cfg.layer_steps)  # one a (pass, layer)
        if (dropout_rng is not None and lora_dropout > 0.0) else None
    )
    gates = None  # a looped model's exit gates, one [B, S] a pass
    xs = (
        params["layers"],
        lora["layers"] if lora is not None else None,
        layer_keys,
    )

    if kv_cache is None:
        def scan_body(x, xs):
            p, lora_p, key = xs
            y = layer_fn(x, p, lora_p, None, None, dropout_rng=key)[0]
            return y, None

        if remat:
            # the backward pass keeps each layer's input and recomputes the
            # layer, but for what a caller's policy keeps: the train step
            # (learner/remat.py) names the weights' products that fit the
            # device's memory; ``True`` keeps nothing, as models/hybrid.py does
            scan_body = jax.checkpoint(scan_body, policy=(
                jax.checkpoint_policies.nothing_saveable if remat is True else remat))
        if cfg.looped:
            # one scan over the layers inside one scan over the passes: the
            # layer body is compiled once, the adapters' gradient is the sum
            # over the passes, and the backward pass finds what each (pass,
            # layer) kept, ``loop_steps`` times a plain model's (learner/remat.py)
            def one_pass(x, pass_keys):
                x, _ = jax.lax.scan(scan_body, x, (*xs[:2], pass_keys))
                return _close_pass(x, params, cfg)

            x, gates = jax.lax.scan(
                one_pass, x,
                None if layer_keys is None else layer_keys.reshape(
                    cfg.loop_steps, cfg.num_layers, *layer_keys.shape[1:]),
                length=cfg.loop_steps)
        else:
            x, _ = jax.lax.scan(scan_body, x, xs)
        new_k = new_v = None
    else:
        # UNROLLED layer loop over PER-LAYER cache buffers. Carrying a stacked
        # [L, ...] cache through a lax.scan (as slice/update on the scan carry)
        # defeats XLA's in-place buffer aliasing: the while-loop ping-pongs the
        # whole cache, costing a full cache-sized HBM temp (~9 GB at the
        # reference rollout volume, measured via compile memory_analysis).
        # Separate per-layer carry leaves alias to zero temp bytes. Of the
        # static weight slices params["layers"][w][i] the MLP's fuse into
        # their matmuls; the mixer's projections do not (0.870 s of the 8.28 s
        # ``rollout-lockstep`` round; ledger, PR 44), so the engines hand these
        # programs a ``decode_view`` that holds them one array a layer.
        kv_quant = "k_scale" in kv_cache  # int8 dense cache carries scales
        new_k, new_v = list(kv_cache["k"]), list(kv_cache["v"])
        new_ks = list(kv_cache["k_scale"]) if kv_quant else [None] * len(new_k)
        new_vs = list(kv_cache["v_scale"]) if kv_quant else [None] * len(new_k)
        gates = [] if cfg.looped else None
        sliced: dict = {}  # a weight layer's slices, shared by a looped model's passes
        for u in range(cfg.loop_steps):
            for l in range(cfg.num_layers):
                if l not in sliced:
                    sliced[l] = (
                        _slice_layer(params["layers"], l),
                        _slice_layer(lora["layers"], l) if lora is not None else None)
                # a looped model's pass reads the keys and values THAT pass wrote
                i = _cache_layer(cfg, u, l)
                x, new_k[i], new_v[i], new_ks[i], new_vs[i] = layer_fn(
                    x, *sliced[l], new_k[i], new_v[i],
                    cache_k_scale=new_ks[i], cache_v_scale=new_vs[i],
                    dropout_rng=(layer_keys[u * cfg.num_layers + l]
                                 if layer_keys is not None else None),
                )
            if cfg.looped:
                x, gate = _close_pass(x, params, cfg)
                gates.append(gate)
        gates = jnp.stack(gates) if cfg.looped else None  # [T, B, S], as the scan's
        new_k, new_v = tuple(new_k), tuple(new_v)
        new_scales = (
            {"k_scale": tuple(new_ks), "v_scale": tuple(new_vs)}
            if kv_quant else {}
        )

    with jax.named_scope(telemetry.MODEL_HEAD):
        logits = _head(x, params, cfg, logits_slice, logits_positions, skip_lm_head)

    if gates is not None and exit_gates is not None:
        exit_gates.append(gates)
    if kv_cache is None:
        new_cache = None
    else:
        new_cache = {**kv_cache, "k": new_k, "v": new_v, **new_scales}
        if gates is not None and s == 1 and "exit_stats" in kv_cache:
            with jax.named_scope(telemetry.MODEL_EXIT_GATE):
                new_cache["exit_stats"] = kv_cache["exit_stats"] + _exit_step_sums(
                    gates[:, :, 0], kv_cache.get("alive"))
    return logits, new_cache


def _cache_layer(cfg: ModelConfig, u: int, l: int) -> int:
    """The cache layer of pass ``u``'s layer ``l``: pass-major, so that a
    model with no loop keeps layer ``l`` at ``l``."""
    return u * cfg.num_layers + l


def _close_pass(x, params: Params, cfg: ModelConfig):
    """What stands between a looped model's passes: the final norm, whose
    output is the pass's ``h_u`` AND the next pass's input, and the exit gate
    ``sigmoid(w . h_u + b)``, one ``Linear(hidden, 1)`` for every pass, in
    float32. Returns ``(h_u, gate [B, S])``."""
    with jax.named_scope(telemetry.MODEL_EXIT_GATE):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                     offset=cfg.rmsnorm_offset)
        gate = params["exit_gate"]
        z = jnp.einsum("bsd,do->bso", x.astype(jnp.float32),
                       gate["w"].astype(jnp.float32))[..., 0]
        return x, jax.nn.sigmoid(z + gate["b"].astype(jnp.float32)[0])


def exit_probabilities(gates: jax.Array) -> jax.Array:
    """The exit distribution of gates ``[T, ...]``: ``p_u = g_u prod_{j<u}
    (1 - g_j)`` for ``u < T - 1`` and the rest of the mass on the last pass."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(gates * before)[:-1], before[-1:]], axis=0)


def _exit_step_sums(gates: jax.Array, alive) -> jax.Array:
    """``[2]`` float32: over the rows of one decode step that are alive, the
    sum of ``sum_u (u + 1) p_u`` (the pass the exit distribution of ``gates``
    ``[T, rows]`` would stop at, in expectation) and the rows counted."""
    steps = jnp.arange(1, gates.shape[0] + 1, dtype=jnp.float32)[:, None]
    expected = (steps * exit_probabilities(gates)).sum(0)
    counted = (jnp.ones_like(expected) if alive is None
               else alive.astype(jnp.float32))
    return jnp.stack([(expected * counted).sum(), counted.sum()])


def exit_distribution(params: Params, cfg: ModelConfig, input_ids: jax.Array,
                      **forward_kwargs) -> jax.Array:
    """``[T, B, S]``: a looped model's exit distribution ``p_u`` a token, by
    the forward as it is served or trained (``forward``'s keywords)."""
    gates: list = []
    forward(params, cfg, input_ids, skip_lm_head=True, exit_gates=gates,
            **forward_kwargs)
    return exit_probabilities(gates[0])


def _head(x, params: Params, cfg: ModelConfig, logits_slice, logits_positions,
          skip_lm_head: bool) -> jax.Array:
    """Final norm, the positions the caller wants, and the output head."""
    if not cfg.looped:  # a looped model's last pass ended with it (_close_pass)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                     offset=cfg.rmsnorm_offset)
    if cfg.logit_scale != 1.0:  # muP: the head reads x / (hidden / dim_model_base)
        x = x * jnp.asarray(cfg.logit_scale, x.dtype)
    if logits_slice is not None:
        # project only the needed positions — the learner's logprob recompute
        # discards all prompt logits, so slicing the hidden states first skips
        # ~P/(P+T) of the lm_head FLOPs and the [B, P, V] buffer
        x = jax.lax.dynamic_slice_in_dim(x, logits_slice[0], logits_slice[1], axis=1)
    elif logits_positions is not None:
        # per-row gather (packed prompts end at different columns): [B, 1, D]
        idx = jnp.broadcast_to(
            logits_positions[:, None, None].astype(jnp.int32),
            (x.shape[0], 1, x.shape[-1]),
        )
        x = jnp.take_along_axis(x, idx, axis=1)
    if skip_lm_head:
        # caller projects to the vocab itself (e.g. the learner's CHUNKED
        # logprob path, which never wants the whole [B, S, V] buffer live)
        logits = x
    else:
        lm_head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
        logits = linear(x, lm_head).astype(jnp.float32)
    return logits


def init_params(
    rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32
) -> Params:
    """Random init with HF-comparable scales (normal 0.02 for projections)."""
    if cfg.hybrid:
        from distrl_llm_tpu.models.hybrid import init_hybrid_params

        return init_hybrid_params(rng, cfg, dtype)
    init = _normal_init(rng, 16, dtype)
    L = cfg.num_layers
    layers = _init_layer_stack(init, cfg, L, cfg.q_dim, cfg.kv_dim, dtype)
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, cfg.q_dim), dtype)
        layers["bk"] = jnp.zeros((L, cfg.kv_dim), dtype)
        layers["bv"] = jnp.zeros((L, cfg.kv_dim), dtype)
    if cfg.sublayer_out_norm:
        layers["attn_out_norm"] = jnp.ones((L, cfg.hidden_size), dtype)
        layers["mlp_out_norm"] = jnp.ones((L, cfg.hidden_size), dtype)
    params = _init_around_layers(init, cfg, layers, dtype)
    if cfg.looped:  # one Linear(hidden, 1) for every pass
        params["exit_gate"] = {"w": init((cfg.hidden_size, 1)),
                               "b": jnp.zeros((1,), dtype)}
    return params


def _normal_init(rng: jax.Array, draws: int, dtype):
    """``init(shape)``: Normal(0, 0.02) from the next of ``draws`` keys."""
    keys = iter(jax.random.split(rng, draws))
    return lambda shape: (0.02 * jax.random.normal(next(keys), shape)).astype(dtype)


def _init_layer_stack(init, cfg: ModelConfig, n: int, q_dim: int, kv_dim: int,
                      dtype) -> Params:
    """``n`` stacked layers' norms, the four mixer projections and the gated
    MLP: what a layer of any kind holds (a kind adds its own leaves)."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    return {
        "attn_norm": jnp.ones((n, D), dtype),
        "mlp_norm": jnp.ones((n, D), dtype),
        "wq": init((n, D, q_dim)),
        "wk": init((n, D, kv_dim)),
        "wv": init((n, D, kv_dim)),
        "wo": init((n, q_dim, D)),
        "w_gate": init((n, D, F)),
        "w_up": init((n, D, F)),
        "w_down": init((n, F, D)),
    }


def _init_around_layers(init, cfg: ModelConfig, layers: Params, dtype) -> Params:
    """The embedding, the final norm and the head round ``layers``."""
    D = cfg.hidden_size
    params: Params = {
        "embed": init((cfg.vocab_size, D)),
        "final_norm": jnp.ones((D,), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init((D, cfg.vocab_size))
    return params


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
) -> Params:
    """Per-CACHE-layer tuples of [B, K, hd, Smax], S minormost: one a layer,
    and one a (pass, layer) of a looped model (``cfg.paged_layers``).

    Two deliberate choices, both required for the decode loop to update the
    cache in place (zero HBM temps, verified with compile memory_analysis):
    separate per-layer buffers (a stacked [L, ...] array carried through a
    scan gets ping-pong-buffered by XLA), and S as the minormost dim (the
    layout XLA assigns the loop carry; any other logical order inserts
    cache-sized layout-conversion copies)."""
    shape = (batch, cfg.num_kv_heads, cfg.head_dim, max_seq)
    return {
        "k": tuple(jnp.zeros(shape, dtype) for _ in range(cfg.paged_layers)),
        "v": tuple(jnp.zeros(shape, dtype) for _ in range(cfg.paged_layers)),
    }


def init_kv_cache_int8(cfg: ModelConfig, batch: int, max_seq: int) -> Params:
    """int8 dense decode cache with per-(B, K, position) f32 scales —
    1 + 4/head_dim bytes per element vs bf16's 2. Same per-layer-tuple /
    S-minormost layout rules as ``init_kv_cache``; the "k_scale"/"v_scale"
    keys switch the dense-cache forward onto the fused-dequant attention
    path (ops/attention.py::attention_cached_quant)."""
    shape = (batch, cfg.num_kv_heads, cfg.head_dim, max_seq)
    sshape = (batch, cfg.num_kv_heads, 1, max_seq)
    return {
        "k": tuple(jnp.zeros(shape, jnp.int8) for _ in range(cfg.paged_layers)),
        "v": tuple(jnp.zeros(shape, jnp.int8) for _ in range(cfg.paged_layers)),
        "k_scale": tuple(jnp.zeros(sshape, jnp.float32) for _ in range(cfg.paged_layers)),
        "v_scale": tuple(jnp.zeros(sshape, jnp.float32) for _ in range(cfg.paged_layers)),
    }
