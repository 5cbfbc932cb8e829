"""Compile (never execute) the real engines' K-steps-per-dispatch programs
on the TPU compiler and report memory_analysis temp bytes — the gate that
decides whether scan_chunk benches actually run chunked
(`scan_chunk_active`) or silently fall back. Safe to run while a bench
owns the chip: everything here is lower()+compile() on abstract shapes.

Checks the flavors the r5 matrix benches at bench-scale shapes
(480 rows / 128 refill slots, 350+1200): dense bf16, dense int8 KV,
refill, and spec.

Usage: python tools/chunk_compile_check.py [chunk]
"""

import os
import sys
from functools import partial

sys.path.insert(0, ".")

import jax


import jax.numpy as jnp

CHUNK = int(sys.argv[1]) if len(sys.argv) > 1 else 16


def gate(name, fn_jit, alias_bytes, *args, **kwargs):
    from distrl_llm_tpu.engine.engine import compile_chunk_guarded

    compiled = compile_chunk_guarded(fn_jit, alias_bytes, name,
                                     *args, **kwargs)
    if compiled is None:
        print(f"REJECTED {name}")
        return 1
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"ACCEPTED {name}: temp {temp/2**30:.2f} GiB "
          f"vs cache {alias_bytes/2**30:.2f} GiB")
    return 0


def sds_tree(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def main() -> int:
    from distrl_llm_tpu.engine import engine as E
    from distrl_llm_tpu.engine import paged_engine as PE
    from distrl_llm_tpu.models import QWEN2_0_5B, init_params

    cfg = QWEN2_0_5B
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    temperature = jax.ShapeDtypeStruct((), jnp.float32)
    top_p = jax.ShapeDtypeStruct((), jnp.float32)
    eos = jnp.asarray([151645], jnp.int32)
    failures = 0

    P_, T = 350, 1200
    B = 480  # dense rows (30 prompts x 16 candidates, the bench volume)

    # ---- dense engine (bf16 and int8 KV) ------------------------------
    from distrl_llm_tpu.models.transformer import init_kv_cache, init_kv_cache_int8

    for name, kv_quant in [("dense_bf16", None), ("dense_int8", "int8")]:
        cache = jax.eval_shape(lambda q=kv_quant: (
            init_kv_cache_int8(cfg, B, P_ + T) if q == "int8"
            else init_kv_cache(cfg, B, P_ + T, dtype=jnp.bfloat16)))
        state = jax.eval_shape(partial(
            E._decode_init, n=1, max_steps=T, pad_id=0),
            cache,
            jax.ShapeDtypeStruct((B, P_ + T), jnp.int32),
            jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
        )
        fn = jax.jit(
            partial(
                E._decode_chunk, chunk=CHUNK, cfg=cfg, prompt_len=P_,
                pad_id=0, lora_scale=1.0, attn_impl="reference",
                top_p_impl="bisect", capture_logprobs=False,
                cache_read_formulation="mulred",  # what chunk engines use
            ),
            donate_argnames=("state",),
        )
        cache_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(state.cache))
        failures += gate(
            f"{name} scan_chunk={CHUNK}", fn, cache_bytes,
            params, None, state, rng, eos_ids=eos,
            temperature=temperature, top_p=top_p,
        )

    # ---- paged refill + spec ------------------------------------------
    r_slots, total, b = 128, 480, 30
    eng = PE.PagedGenerationEngine(
        cfg, max_prompt_tokens=P_, max_new_tokens=T,
        eos_token_ids=[151645], pad_token_id=0, page_size=128,
        scheduler="refill", max_concurrent_rows=r_slots, scan_chunk=CHUNK,
    )
    pool_s = jax.eval_shape(lambda: tuple(
        jnp.zeros((cfg.num_kv_heads, b * eng.prompt_pages, 128,
                   cfg.head_dim), jnp.bfloat16)
        for _ in range(cfg.num_layers)))
    pool_pages = 1 + r_slots * eng.private_pages
    state = jax.eval_shape(partial(
        PE._refill_init, b=b, r_slots=r_slots, total=total, max_steps=T,
        vocab=cfg.vocab_size, pool_pages=pool_pages,
        prompt_pages=eng.prompt_pages, private_pages=eng.private_pages,
        pad_id=0), pool_s, pool_s)
    fn = jax.jit(
        partial(
            PE._refill_decode_chunk, chunk=CHUNK, cfg=cfg, page_size=128,
            pad_id=0, lora_scale=1.0, paged_impl="auto", max_steps=T,
            top_p_impl="bisect", capture_logprobs=False,
        ),
        donate_argnames=("state",),
    )
    pool_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves((state.k_pages, state.v_pages)))
    failures += gate(
        f"refill scan_chunk={CHUNK}", fn, pool_bytes,
        params, None, state, rng, eos_ids=eos,
        temperature=temperature, top_p=top_p,
    )

    d = 4
    spec_state = jax.eval_shape(partial(
        PE._spec_init, b=b, r_slots=r_slots, total=total, max_steps=T,
        buf_width=P_ + T + d + 1, pool_pages=pool_pages, hist_width=d + 2,
        prompt_pages=eng.prompt_pages, private_pages=eng.private_pages,
        pad_id=0), pool_s, pool_s)
    fn = jax.jit(
        partial(
            PE._spec_decode_chunk, chunk=CHUNK, cfg=cfg, page_size=128,
            pad_id=0, lora_scale=1.0, paged_impl="auto", max_steps=T,
            draft_len=d, ngram_k=3, top_p_impl="bisect",
            capture_logprobs=False,
        ),
        donate_argnames=("state",),
    )
    failures += gate(
        f"spec scan_chunk={CHUNK}", fn, pool_bytes,
        params, None, spec_state, rng, eos_ids=eos,
        temperature=temperature, top_p=top_p,
    )

    print("ALL CHUNKED" if failures == 0 else f"{failures} FELL BACK")
    return failures


if __name__ == "__main__":
    sys.exit(main())
