"""Attention ops: masked GQA attention with a plain-XLA reference path.

This is the N1/N3-equivalent compute core (SURVEY §2b): the reference gets its
attention from vLLM's CUDA kernels (decode) and Triton (train); here the
baseline is a jnp implementation XLA fuses well on the MXU, with Pallas flash
attention layered on top (ops/flash_attention.py) for long sequences, selected
by ``attention(..., impl=...)``.

Shapes follow the TPU-friendly layout [batch, seq, heads, head_dim] — last two
dims map onto (sublane, lane) tiles.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

NEG_INF = -1e30  # large-negative for masked logits; avoids NaNs from true -inf


def repeat_kv(k: jax.Array, num_groups: int) -> jax.Array:
    """[B, S, K, D] → [B, S, K*num_groups, D] by repeating each kv head for its
    query group (GQA)."""
    if num_groups == 1:
        return k
    b, s, kh, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, num_groups, d)).reshape(
        b, s, kh * num_groups, d
    )


def attention_reference(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, K, D]
    v: jax.Array,  # [B, Sk, K, D]
    mask: jax.Array | None,  # [B, 1|H, Sq, Sk]; True = attend
    scale: float | None = None,
    sink: jax.Array | None = None,  # [H]: one learned logit a query head
) -> jax.Array:
    """Plain-XLA masked attention. Softmax in f32 regardless of input dtype.
    ``v`` may be narrower or wider than ``k``: the output has its width.

    GQA contracts the grouped query heads [B, Sq, K, G, D] directly against the
    K kv heads — never materializing ``repeat_kv``, which would multiply KV
    HBM traffic by G (7× for Qwen2.5-0.5B) in the decode hot loop.

    ``sink`` is a column of the softmax whose value is nothing: ``p[t, j] =
    exp(s[t, j]) / (exp(sink_h) + sum_j' exp(s[t, j']))``, joined to the
    running maximum and the denominator in float32."""
    return _gqa_attention(q, k, v, mask, scale, kv_subscript="bskd", kv_heads_axis=2,
                          sink=sink)


def causal_padding_mask(
    attention_mask: jax.Array,  # [B, Sk] 1 = real token
    q_len: int,
    q_offset: jax.Array | int = 0,
    window: int = 0,
) -> jax.Array:
    """[B, 1, Sq, Sk] boolean mask combining causality with key padding.

    ``q_offset`` is the absolute position of the first query row — 0 for a
    training/prefill forward, the current decode length for single-token decode
    steps against a KV cache. ``window`` > 0 makes the mask a BAND: a query
    attends the last ``window`` keys, itself included (sliding-window layers).
    """
    sk = attention_mask.shape[-1]
    q_pos = q_offset + jnp.arange(q_len)[:, None]  # [Sq, 1]
    k_pos = jnp.arange(sk)[None, :]  # [1, Sk]
    causal = k_pos <= q_pos  # [Sq, Sk]
    if window:
        causal = causal & (q_pos - k_pos < window)
    pad = attention_mask[:, None, None, :].astype(bool)  # [B, 1, 1, Sk]
    return causal[None, None, :, :] & pad


def attention_cached(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, K, D, Sk] — decode-cache layout, S minormost
    v: jax.Array,  # [B, K, D, Sk]
    mask: jax.Array | None,  # [B, 1|H, Sq, Sk]; True = attend
    scale: float | None = None,
    formulation: str = "dot",
) -> jax.Array:
    """Masked GQA attention against a [B, K, D, S] KV cache.

    The cache keeps S as its minormost dim — the layout XLA's layout
    assignment picks for the decode while-loop. Storing the cache any other
    way makes XLA insert full-cache conversion copies inside the loop (two
    extra cache-sized HBM temps that break donation aliasing).

    ``formulation="mulred"`` switches the Sq==1 decode read from
    ``dot_general`` to multiply+reduce — required inside K-steps-per-dispatch
    scan programs, where ANY dot over the carried cache makes TPU layout
    assignment relayout the operand to a B-minormost layout with a
    cache-leaf-sized conversion copy per leaf per iteration, defeating
    in-place aliasing and OOMing the program (r5 silicon finding; the
    9-variant ladder in tools/chunk_alias_bisect.py isolates it — operand
    order and which einsum are irrelevant, only mul+reduce keeps the native
    layout). Reduce-of-product fuses into the cache read, so HBM traffic is
    identical; the MXU is ~idle at one query token either way. Sq>1 calls
    (prefill) always use the dot path."""
    return _gqa_attention(q, k, v, mask, scale, kv_subscript="bkds",
                          kv_heads_axis=1, formulation=formulation)


def quantize_kv_position(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(B, K, position) symmetric int8 over head_dim for the decode
    cache: [B, K, hd, S] → (int8 [B, K, hd, S], f32 scales [B, K, 1, S])."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=2, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q8, s


def attention_cached_quant(
    q: jax.Array,  # [B, Sq, H, D]
    k8: jax.Array,  # int8 [B, K, D, Sk] — decode-cache layout
    k_scale: jax.Array,  # f32 [B, K, 1, Sk]
    v8: jax.Array,  # int8 [B, K, D, Sk]
    v_scale: jax.Array,  # f32 [B, K, 1, Sk]
    mask: jax.Array | None,
    scale: float | None = None,
    formulation: str = "dot",
) -> jax.Array:
    """Masked GQA attention against an int8 KV cache with per-position
    scales, dequantization FOLDED into the attention math so the cache is
    read from HBM at 1 byte/element (the paged engine's int8-KV bandwidth
    win, for the dense engine):

    * k: logits[..., s] = (Σ_d q·k8) · k_scale[s] — the scale factors out
      of the contraction over d;
    * v: out[..., d] = Σ_s probs·v8·v_scale[s] — the scale rides the probs
      ([B, K, G, Sq, Sk] f32, already materialized by the softmax).

    XLA is expected to fuse the int8→f32 convert into the dot-operand read
    (no f32 cache-sized temp); a compiled step's ``memory_analysis`` shows
    whether it did.

    ``formulation="mulred"`` — see attention_cached: mandatory for the
    scan-chunk programs, where a dot over the carried int8 cache costs a
    per-leaf relayout copy per iteration."""
    return _gqa_attention(
        q, k8, v8, mask, scale, kv_subscript="bkds", kv_heads_axis=1,
        k_scale=k_scale, v_scale=v_scale, formulation=formulation,
    ).astype(q.dtype)


def _gqa_attention(q, k, v, mask, scale, *, kv_subscript: str,
                   kv_heads_axis: int, k_scale=None, v_scale=None,
                   formulation: str = "dot", sink=None):
    """Shared GQA attention body; only the kv einsum layout differs between
    the training ([B,S,K,D]) and decode-cache ([B,K,D,S]) paths.

    ``k_scale``/``v_scale`` ([B, K, 1, Sk] f32, decode-cache layout only)
    switch on the fused-dequant int8 path: k/v stay int8 in HBM, the k
    scale factors out of the d-contraction onto the logits, the v scale
    rides the (already f32) probs."""
    if formulation not in ("dot", "mulred"):
        # a typo ('mul_red', 'dot_general', …) must not silently take the
        # dot path — inside a scan program that reintroduces the per-leaf
        # relayout copy / OOM the flag exists to avoid
        raise ValueError(
            f"formulation must be 'dot' or 'mulred', got {formulation!r}"
        )
    quant = k_scale is not None
    assert not quant or kv_heads_axis == 1, "scales imply the [B,K,D,S] layout"
    b, sq, h, d = q.shape
    kh = k.shape[kv_heads_axis]
    g = h // kh
    if scale is None:
        scale = d**-0.5
    if formulation == "mulred" and sq == 1 and kv_heads_axis == 1:
        return _gqa_mulred(q, k, v, mask, scale, k_scale=k_scale,
                           v_scale=v_scale)
    qg = q.reshape(b, sq, kh, g, d)
    if quant:
        qg = qg.astype(jnp.float32)
        k = k.astype(jnp.float32)  # fused into the dot-operand read by XLA
    logits = jnp.einsum(
        f"bqkgd,{kv_subscript}->bkgqs", qg, k, preferred_element_type=jnp.float32
    )
    if quant:
        logits = logits * k_scale[:, :, :, None, :]  # [B, K, 1, 1, Sk]
    logits = logits * scale
    if mask is not None:
        if mask.shape[1] == 1:  # head-agnostic mask
            m = mask[:, :, None]  # [B, 1, 1, Sq, Sk]
        else:
            m = mask.reshape(b, kh, g, *mask.shape[2:])
        logits = jnp.where(m, logits, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:  # one more column, of no value: in the maximum and the denominator
        logits = logits.astype(jnp.float32)
        col = sink.astype(jnp.float32).reshape(kh, g)[None, :, :, None, None]
        top = jnp.maximum(logits.max(-1, keepdims=True), col)
        e = jnp.exp(logits - top)
        probs = e / (e.sum(-1, keepdims=True) + jnp.exp(col - top))
    if quant:
        probs = probs * v_scale[:, :, :, None, :]
        v = v.astype(jnp.float32)
    else:
        probs = probs.astype(v.dtype)
    out = jnp.einsum(f"bkgqs,{kv_subscript}->bqkgd", probs, v)
    return out.reshape(b, sq, h, out.shape[-1])


def mulred_broadcast_bytes(batch_rows: int, kv_heads: int, groups: int,
                           head_dim: int, kv_len: int) -> int:
    """Bytes of ONE layer's unfused ``_gqa_mulred`` broadcast product — the
    [B, KH, G, D, S] f32 temp a backend would materialize if it failed to
    fuse reduce-of-product into the cache read. ``compile_chunk_guarded``'s
    ``fusion_bytes`` threshold prices temp bytes against this: a fused
    program's scratch sits far below it, an unfused one lands on it and
    OOMs real geometries."""
    return batch_rows * kv_heads * groups * head_dim * kv_len * 4


def _gqa_mulred(q, k, v, mask, scale, *, k_scale=None, v_scale=None):
    """Sq==1 decode attention as multiply+reduce over the [B, K, D, S]
    cache — no ``dot_general`` touches the cache operands, so TPU layout
    assignment keeps the carry's native S-minormost layout inside scan
    programs instead of inserting cache-sized relayout copies each
    iteration (attention_cached's docstring has the full story). Both
    contractions accumulate in f32 (the dot path's k-side did too via
    preferred_element_type; the v-side rounded at bf16 — mulred is the
    same or slightly better numerically). XLA fuses reduce-of-product
    into the cache read: one pass over K + one over V, the same HBM
    traffic as the dot formulation."""
    quant = k_scale is not None
    b, _, h, d = q.shape
    kh = k.shape[1]
    g = h // kh
    qv = q.reshape(b, kh, g, d).astype(jnp.float32)
    # logits[b,k,g,s] = sum_d q[b,k,g,d] * K[b,k,d,s]
    logits = jnp.sum(qv[..., None] * k.astype(jnp.float32)[:, :, None], axis=-2)
    if quant:
        logits = logits * k_scale[:, :, None, 0, :]  # [B, K, 1, Sk]
    logits = logits * scale
    if mask is not None:  # [B, 1|H, 1, Sk]
        m = (
            mask[:, :, None, 0, :]  # head-agnostic -> [B, 1, 1, Sk]
            if mask.shape[1] == 1
            else mask[:, :, 0, :].reshape(b, kh, g, mask.shape[-1])
        )
        logits = jnp.where(m, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)  # [B, K, G, Sk] f32
    if quant:
        probs = probs * v_scale[:, :, None, 0, :]
    # out[b,k,g,d] = sum_s probs[b,k,g,s] * V[b,k,d,s]
    out = jnp.sum(probs[:, :, :, None, :] * v.astype(jnp.float32)[:, :, None],
                  axis=-1)
    return out.reshape(b, 1, h, d).astype(q.dtype)


#: set once a "flash"/"splash" request ran the XLA reference instead (a
#: backend other than the TPU, or a call outside the kernels'
#: self-attention contract)
_flash_fallback_warned = False


def _note_reference(impl: str, why: str) -> None:
    global _flash_fallback_warned
    if not _flash_fallback_warned:
        _flash_fallback_warned = True
        logger.warning(
            "%s attention runs the XLA reference path here (%s) — "
            "O(Sq*Sk) memory", impl, why,
        )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,
    scale: float | None = None,
    impl: str = "reference",
    key_valid: jax.Array | None = None,
    window: int = 0,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Dispatching front door. ``impl``: "reference" (XLA), "flash" or
    "splash" (Pallas kernels).

    ``window`` > 0 (with ``key_valid`` and no ``mask``) is a sliding-window
    layer's band, in the reference's mask; the kernels take a causal mask only
    and refuse the band by name rather than attend everything. ``sink`` [H] is
    the reference's (``attention_reference``); the kernels have no such column
    and refuse it likewise.

    On a TPU backend a named kernel is what runs: a kernel that fails to
    lower or to run fails the step, it never gives way to the reference
    (tests/test_tpu_compile.py holds the lowerings at training shapes). The
    reference serves a kernel request only where the kernel cannot apply by
    construction: on a backend other than the TPU, and outside the kernels'
    contract (self-attention under a head-agnostic mask — cached decode
    steps and warm-prefix prefill are not).

    ``key_valid`` is the [B, Sk] validity vector; the flash/splash paths
    consume it directly (no [B, 1, Sq, Sk] mask needs to exist). When only
    ``key_valid`` is given and the reference runs, the dense causal mask is
    built here."""
    if window and impl in ("flash", "splash"):
        raise NotImplementedError(
            f"attn_impl={impl!r} masks causally and has no band: a sliding-window "
            f"layer (window {window}) would attend its whole context; use "
            "attn_impl='reference'")
    if sink is not None and impl in ("flash", "splash"):
        raise NotImplementedError(
            f"attn_impl={impl!r} has no sink column in its softmax: a layer with a "
            "learned sink would lose it; use attn_impl='reference'")
    if impl in ("flash", "splash"):
        if jax.default_backend() != "tpu":
            _note_reference(impl, "no TPU backend")
        elif q.shape[1] != k.shape[1] or (
            mask is not None and mask.shape[1] != 1
        ):
            _note_reference(impl, "not a self-attention call")
        elif impl == "splash":
            from distrl_llm_tpu.ops.splash import splash_attention

            return splash_attention(q, k, v, key_valid, scale=scale)
        else:
            from distrl_llm_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v, mask, scale=scale, key_valid=key_valid)
    if mask is None and key_valid is not None:
        mask = causal_padding_mask(key_valid, q_len=q.shape[1], window=window)
    return attention_reference(q, k, v, mask, scale=scale, sink=sink)
