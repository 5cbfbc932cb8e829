"""A decoder whose layers differ in kind (MiniCPM-SALA): block-sparse attention
layers (``minicpm4``, InfLLM-V2) beside lightning linear-attention layers.

``transformer.forward`` / ``init_params`` hand over to this module when
``cfg.mixer_types`` is set; a dense GQA model never reaches it. What is shared
with the dense decoder is imported from it: ``_proj`` (the LoRA delta),
``rms_norm``, RoPE, ``_head``, a layer's MLP half (``_mlp_half``) and the
stack initialisers. This module holds the two mixers and the cache plumbing.

**Parameters.** One stack per layer KIND under ``params["layers"]``:
``{"sparse": {...[n_sparse, ...]}, "lightning": {...[n_lightning, ...]}}``,
each in the order its layers appear in the model. ``cfg.layer_runs`` walks the
published order: runs of like layers are scanned (no cache) or unrolled
(cache), so a run of eight lightning layers compiles one body.

**Equations** (``c = cfg.residual_scale``, ``h = RMSNorm(x)``)::

    x0 = scale_emb * Embed(ids)
    x <- x + c * Mixer(h);   x <- x + c * W_down(silu(W_gate h) * (W_up h))
    logits = W_head(RMSNorm(x) * dim_model_base / hidden)

    lightning: q, k, v = W h  [T, H, D];  q, k <- RMSNorm_D;  q, k <- RoPE
               S_t = lam_h S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t / sqrt(D)
               y = W_o(RMSNorm_{H*D}(o) * sigmoid(W_z h))
    sparse:    q [T, H, D], k, v [T, K, D];  q, k <- RMSNorm_D;  no RoPE
               o = attention over the chosen blocks (ops/sparse_attention.py)
               y = W_o(o * sigmoid(W_z h))

**Three modes**, by the cache handed in:

* no cache: the whole sequence (training, scoring). Rows are packed to the
  left first (the learner left-pads prompts, and a sparse layer's blocks are
  counted from a row's first real token) and unpacked before the head.
* a paged cache and one token a row: a decode step. Sparse layers write K/V to
  pages, complete a pooled key every ``kernel_stride`` tokens and attend over
  the chosen pages; lightning layers step their state.
* a paged cache with ``"segment_start"``: one page-aligned SEGMENT of a prompt
  prefill, every row at the same offset. Sparse layers write the segment's
  pages whole and attend over the row's pages gathered dense; lightning layers
  run the segment chunked from the carried state. A prompt is prefilled
  segment after segment (``engine/paged_engine.py``): a 20k-token prompt at
  once would need the MLP's activations and the attention scores for all of it.

The cache is a dict: ``k``/``v``/``pooled`` (a tuple over SPARSE layers: pages
``[K, pages, block, hd]`` and selector keys ``[B, NP, K, hd]``), ``lin`` (a
tuple over LIGHTNING layers of ``[B, H, D, D]`` float32), ``lengths`` [B],
``page_indices`` [B, W], and optionally ``alive`` [B] and ``sel_stats`` [2]
int32 (blocks attended, blocks visible, summed over sparse layers and alive
rows: a counter the engine carries through a round).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.models.transformer import (
    _head, _init_around_layers, _init_layer_stack, _mlp_half, _normal_init, _proj,
    _slice_layer, apply_rope, rms_norm, rope_cos_sin,
)
from distrl_llm_tpu.ops.attention import attention
from distrl_llm_tpu.ops.linear import linear
from distrl_llm_tpu.ops.linear_attention import lightning_chunked, lightning_step
from distrl_llm_tpu.ops.sparse_attention import (
    pool_keys, pooled_count, sparse_attend, sparse_decode, update_pooled,
)

Params = dict[str, Any]


def init_hybrid_params(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Random init, one stack per layer kind (module docstring)."""
    init = _normal_init(rng, 32, dtype)

    def stack(n: int, q_dim: int, kv_dim: int, head_dim: int, gate: bool,
              out_norm: bool) -> Params:
        p = _init_layer_stack(init, cfg, n, q_dim, kv_dim, dtype)
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((n, head_dim), dtype)
            p["k_norm"] = jnp.ones((n, head_dim), dtype)
        if gate:
            p["wz"] = init((n, cfg.hidden_size, q_dim))
        if out_norm:
            p["o_norm"] = jnp.ones((n, q_dim), dtype)
        return p

    layers: Params = {}
    if cfg.kind_count("sparse"):
        layers["sparse"] = stack(
            cfg.kind_count("sparse"), cfg.q_dim, cfg.kv_dim, cfg.head_dim,
            cfg.attn_output_gate, False,
        )
    if cfg.kind_count("lightning"):
        layers["lightning"] = stack(
            cfg.kind_count("lightning"), cfg.lightning_dim, cfg.lightning_dim,
            cfg.lightning_head_dim, cfg.lightning_output_gate,
            cfg.lightning_output_norm,
        )
    return _init_around_layers(init, cfg, layers, dtype)


def init_mixer_state(cfg: ModelConfig, rows: int, max_tokens: int,
                     cache_dtype=jnp.bfloat16) -> Params:
    """What a slot holds beside its K/V pages: a float32 state per lightning
    layer, the selector's pooled keys per sparse layer, the round's counter."""
    h, d = cfg.lightning_heads, cfg.lightning_head_dim
    pooled = (rows, pooled_count(max_tokens, cfg), cfg.num_kv_heads, cfg.head_dim)
    return {
        "lin": tuple(
            jnp.zeros((rows, h, d, d), jnp.float32)
            for _ in range(cfg.kind_count("lightning"))
        ),
        "pooled": tuple(
            jnp.zeros(pooled, cache_dtype) for _ in range(cfg.kind_count("sparse"))
        ),
        "sel_stats": jnp.zeros((2,), jnp.int32),
    }


def _mode(kv_cache, s: int, flags: dict) -> str:
    if kv_cache is None:
        return "full"
    refused = [name for name, on in flags.items() if on]
    if "page_indices" not in kv_cache or refused:
        raise NotImplementedError(
            "a model with per-layer mixers runs without a cache, one decode "
            "token a row over a paged cache, or a page-aligned prefill segment; "
            f"not {refused or ['a dense K/V cache']}"
        )
    if "segment_start" in kv_cache:
        return "segment"
    if s != 1:
        raise NotImplementedError(
            "several tokens a row over a paged cache need 'segment_start' "
            "(a page-aligned prefill segment)"
        )
    return "decode"


def _sparse_mix(q, k, v, cache, *, cfg, mode, env):
    """The block-sparse layer's attention in each mode. Returns
    (o [B, S, H, hd], the layer's new cache pieces, stats or None)."""
    from distrl_llm_tpu.ops.paged import (
        gather_pages_dense, write_token_to_pages,
    )

    if mode == "full":
        if q.shape[1] <= cfg.sparse_dense_len:  # no query's context is longer
            with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
                o = attention(q, k, v, None, impl=env["attn_impl"],
                              key_valid=env["valid"])
            return o, None, None
        with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
            pooled = pool_keys(k, cfg)
        return sparse_attend(q, k, v, pooled, env["q_pos"], cfg), None, None
    pages_k, pages_v, pooled = cache
    idx, ps = env["page_indices"], env["page_size"]
    if mode == "decode":
        lengths = env["lengths"]
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            pages_k = write_token_to_pages(pages_k, k[:, 0], lengths, idx, ps)
            pages_v = write_token_to_pages(pages_v, v[:, 0], lengths, idx, ps)
        with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
            pooled = update_pooled(pooled, pages_k, lengths + 1, idx, cfg)
        o, stats = sparse_decode(
            q[:, 0], pages_k, pages_v, pooled, lengths, idx, cfg, alive=env["alive"]
        )
        return o[:, None], (pages_k, pages_v, pooled), stats
    # one page-aligned segment of a prefill, every row at offset ``start``
    b, s = q.shape[:2]
    start = env["segment_start"]
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        dest = jax.lax.dynamic_slice_in_dim(idx, start // ps, s // ps, axis=1)

        def write(pages, new):
            tiles = new.reshape(b, s // ps, ps, new.shape[2], new.shape[3])
            tiles = tiles.transpose(3, 0, 1, 2, 4).reshape(
                new.shape[2], b * (s // ps), ps, new.shape[3]
            )
            return pages.at[:, dest.reshape(-1)].set(tiles.astype(pages.dtype))

        pages_k, pages_v = write(pages_k, k), write(pages_v, v)
        ctx_k = gather_pages_dense(pages_k, idx, dtype=q.dtype)
        ctx_v = gather_pages_dense(pages_v, idx, dtype=q.dtype)
    with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
        pooled = pool_keys(ctx_k, cfg, count=pooled.shape[1]).astype(pooled.dtype)
    o = sparse_attend(q, ctx_k, ctx_v, pooled, env["q_pos"], cfg)
    return o, (pages_k, pages_v, pooled), None


def _lightning_mix(q, k, v, state, rate, *, cfg, mode, env):
    with jax.named_scope(telemetry.MODEL_LINEAR_ATTN):
        if cfg.lightning_use_rope:
            q = apply_rope(q, env["cos"], env["sin"])
            k = apply_rope(k, env["cos"], env["sin"])
        if mode == "decode":
            o, state = lightning_step(q[:, 0], k[:, 0], v[:, 0], rate, state)
            return o[:, None], state
        o, state = lightning_chunked(q, k, v, rate, env["valid"], state=state)
        return o, (state if mode == "segment" else None)


def _block(x, p, lora, rate, cache, *, kind: str, cfg: ModelConfig, mode: str,
           env: dict, lora_scale: float, lora_dropout: float, dropout_rng):
    """One layer of either kind: (x, new cache pieces, stats)."""
    b, s, _ = x.shape
    proj = partial(_proj, lora_dropout=lora_dropout, dropout_rng=dropout_rng)
    c = jnp.asarray(cfg.residual_scale, x.dtype)
    sparse = kind == "sparse"
    heads, kv_heads, hd = (
        (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) if sparse
        else (cfg.lightning_heads, cfg.lightning_heads, cfg.lightning_head_dim)
    )
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q = proj(h, p, lora, "wq", "bq", lora_scale).reshape(b, s, heads, hd)
        k = proj(h, p, lora, "wk", "bk", lora_scale).reshape(b, s, kv_heads, hd)
        v = proj(h, p, lora, "wv", "bv", lora_scale).reshape(b, s, kv_heads, hd)
        if "q_norm" in p:
            q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(linear(h, p["wz"])) if "wz" in p else None
    stats = None
    if sparse:
        if cfg.attn_use_rope:
            raise NotImplementedError("sparse layers with RoPE (attn_use_rope)")
        o, cache, stats = _sparse_mix(q, k, v, cache, cfg=cfg, mode=mode, env=env)
    else:
        o, cache = _lightning_mix(q, k, v, cache, rate, cfg=cfg, mode=mode, env=env)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        o = o.reshape(b, s, heads * hd)
        if "o_norm" in p:
            o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
        if gate is not None:
            o = o * gate
        x = x + c * proj(o, p, lora, "wo", "bo", lora_scale)
    x = _mlp_half(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale,
                  residual_scale=c)
    return x, cache, stats


def _pack_left(ids, mask):
    """Rows whose real tokens are contiguous, moved to column 0. Returns
    (ids, valid, the column each packed column came from)."""
    s = ids.shape[1]
    real = mask.sum(axis=-1).astype(jnp.int32)
    shift = jnp.argmax(mask > 0, axis=-1).astype(jnp.int32)
    cols = (jnp.arange(s)[None, :] + shift[:, None]) % s
    valid = (jnp.arange(s)[None, :] < real[:, None]).astype(jnp.int32)
    return jnp.take_along_axis(ids, cols, axis=1) * valid, valid, shift


def forward_hybrid(
    params: Params, cfg: ModelConfig, input_ids: jax.Array, *,
    attention_mask=None, positions=None, lora=None, lora_scale: float = 1.0,
    kv_cache: Params | None = None, remat: bool = False,
    attn_impl: str = "reference", logits_slice=None, logits_positions=None,
    page_size: int = 0, lora_dropout: float = 0.0, dropout_rng=None,
    skip_lm_head: bool = False, **unsupported,
):
    """``transformer.forward`` for a model with per-layer mixers: same
    arguments, same returns. ``unsupported`` holds the dense decoder's other
    switches; one that is on is refused by name."""
    b, s = input_ids.shape
    mode = _mode(kv_cache, s, {
        name: unsupported.get(name) for name in
        ("paged_verify", "paged_chunked", "paged_prefix", "attn_mesh")
    })
    if attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"attn_impl={attn_impl!r} shards dense attention over the sequence; "
            f"{cfg.mixer_names} layers are not dense attention"
        )
    shift = None
    if mode == "full":
        if attention_mask is None:
            attention_mask = jnp.ones((b, s), jnp.int32)
        with jax.named_scope(telemetry.MODEL_EMBED):
            input_ids, valid, shift = _pack_left(input_ids, attention_mask)
        q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        rope_pos = q_pos
        env: dict = {"valid": valid, "q_pos": q_pos, "attn_impl": attn_impl}
    elif mode == "decode":
        lengths = kv_cache["lengths"]
        rope_pos = lengths[:, None] if positions is None else positions
        env = {
            "lengths": lengths, "page_indices": kv_cache["page_indices"],
            "page_size": page_size, "alive": kv_cache.get("alive"),
        }
    else:
        start = kv_cache["segment_start"]
        q_pos = start + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        rope_pos = q_pos
        env = {
            "segment_start": start, "q_pos": q_pos,
            "valid": attention_mask, "page_indices": kv_cache["page_indices"],
            "page_size": page_size,
        }
    with jax.named_scope(telemetry.MODEL_LINEAR_ATTN):
        env["cos"], env["sin"] = rope_cos_sin(
            rope_pos, cfg.lightning_head_dim or cfg.head_dim, cfg.rope_theta)

    with jax.named_scope(telemetry.MODEL_EMBED):
        x = jnp.take(params["embed"], input_ids, axis=0)
        if cfg.scale_emb != 1.0:
            x = x * jnp.asarray(cfg.scale_emb, x.dtype)

    rates = jnp.asarray(cfg.lightning_decay_rates())
    use_dropout = dropout_rng is not None and lora_dropout > 0.0
    layer_keys = jax.random.split(dropout_rng, cfg.num_layers) if use_dropout else None
    block = partial(
        _block, cfg=cfg, mode=mode, env=env, lora_scale=lora_scale,
        lora_dropout=lora_dropout if use_dropout else 0.0,
    )
    stacks = params["layers"]
    lora_stacks = lora["layers"] if lora is not None else {}

    if mode == "full":
        for kind, first, at, count in cfg.layer_runs:
            take = lambda tree: jax.tree_util.tree_map(
                lambda w: w[at: at + count], tree)
            xs = (
                take(stacks[kind]),
                take(lora_stacks[kind]) if kind in lora_stacks else None,
                rates[at: at + count] if kind == "lightning" else None,
                layer_keys[first: first + count] if use_dropout else None,
            )

            def body(x, xs, kind=kind):
                p, lora_p, rate, key = xs
                return block(x, p, lora_p, rate, None, kind=kind,
                             dropout_rng=key)[0], None

            if remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = jax.lax.scan(body, x, xs)
        with jax.named_scope(telemetry.MODEL_HEAD):
            # back to the caller's columns before it slices the positions it wants
            cols = (jnp.arange(s)[None, :] - shift[:, None]) % s
            x = jnp.take_along_axis(x, cols[:, :, None], axis=1)
            return _head(x, params, cfg, logits_slice, logits_positions,
                         skip_lm_head), None

    # cache modes: an unrolled loop over per-layer cache buffers (a stacked
    # cache carried through a scan is ping-ponged whole: transformer.forward)
    new = {name: list(kv_cache[name]) for name in ("k", "v", "pooled", "lin")}
    stats = kv_cache.get("sel_stats")
    at = {"sparse": 0, "lightning": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        j = at[kind]
        at[kind] += 1
        p = _slice_layer(stacks[kind], j)
        lora_p = _slice_layer(lora_stacks[kind], j) if kind in lora_stacks else None
        if kind == "sparse":
            held = (new["k"][j], new["v"][j], new["pooled"][j])
            x, held, layer_stats = block(
                x, p, lora_p, None, held, kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
            new["k"][j], new["v"][j], new["pooled"][j] = held
            if stats is not None and layer_stats is not None:
                stats = stats + layer_stats.astype(stats.dtype)
        else:
            x, new["lin"][j], _ = block(
                x, p, lora_p, rates[j], new["lin"][j], kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
    with jax.named_scope(telemetry.MODEL_HEAD):
        logits = _head(x, params, cfg, logits_slice, logits_positions, skip_lm_head)
    out = {**kv_cache, **{name: tuple(vals) for name, vals in new.items()}}
    if stats is not None:
        out["sel_stats"] = stats
    return logits, out
