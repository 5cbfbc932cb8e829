"""Bisect WHICH part of the real decode-step body makes the TPU compiler
double-buffer the scanned KV-cache carry (r5 silicon finding #2).

tools/scan_alias_probe.py proved a MINIMAL dus-write + full-cache-read scan
body aliases to ~0 temp once the lax.cond is gone — yet the REAL
``engine._decode_chunk`` still compiles with one cache-leaf-sized
``copy.N.remat_*`` per K/V leaf (48 x 195 MB at bench scale = compile OOM,
see /tmp/chunk_compile_check.log). Something between the probe's body and
the real body flips XLA copy insertion. This tool compiles (never runs)
the real chunk program at a 4-layer variant of the 0.5B geometry, then a
ladder of hybrids between probe-body and real-body, printing temp bytes
for each — the first rung that double-buffers names the culprit.

Safe to run while a bench owns the chip (lower+compile only).

Usage: python tools/chunk_alias_bisect.py [chunk]
"""

import sys
from dataclasses import replace
from functools import partial

sys.path.insert(0, ".")

import jax


import jax.numpy as jnp

from distrl_llm_tpu.engine import engine as E
from distrl_llm_tpu.models import QWEN2_0_5B, init_params
from distrl_llm_tpu.models.transformer import forward, init_kv_cache
from distrl_llm_tpu.ops.sampling import sample, token_logprob

CHUNK = int(sys.argv[1]) if len(sys.argv) > 1 else 16
P_, T = 350, 1200
B = 480
S = P_ + T

# 4 layers is enough: a double-buffered carry shows as ~8 x 195 MB = 1.5 GiB
# of temp vs ~0 when aliased; compiles stay fast enough to ladder.
CFG = replace(QWEN2_0_5B, num_layers=4)


def sds(x):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)


def report(name, fn, state, *args, static_kwargs=None, donate=("state",)):
    try:
        jfn = jax.jit(fn, donate_argnames=donate)
        compiled = jfn.lower(state, *args, **(static_kwargs or {})).compile()
        t = compiled.memory_analysis().temp_size_in_bytes
        cache_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(
                state.cache if hasattr(state, "cache") else state[0]))
        flag = "DOUBLE-BUFFERED" if t > 0.5 * cache_bytes else "aliased ok"
        print(f"{name}: temp {t/2**30:.3f} GiB (cache {cache_bytes/2**30:.2f})"
              f"  [{flag}]", flush=True)
    except Exception as e:  # noqa: BLE001
        msg = str(e).split("\n")[0][:160]
        print(f"{name}: COMPILE FAILED {type(e).__name__}: {msg}", flush=True)


def make_state(cfg):
    cache = jax.eval_shape(
        lambda: init_kv_cache(cfg, B, S, dtype=jnp.bfloat16))
    return jax.eval_shape(partial(
        E._decode_init, n=1, max_steps=T, pad_id=0),
        cache,
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.bool_),
    )


def main():
    cfg = CFG
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    temperature = jax.ShapeDtypeStruct((), jnp.float32)
    top_p = jax.ShapeDtypeStruct((), jnp.float32)
    eos = jnp.asarray([151645], jnp.int32)
    state = make_state(cfg)

    # rung 0: the real chunk program, 4 layers — expect DOUBLE-BUFFERED
    fn = partial(
        E._decode_chunk, chunk=CHUNK, cfg=cfg, prompt_len=P_, pad_id=0,
        lora_scale=1.0, attn_impl="reference", top_p_impl="bisect",
        capture_logprobs=False,
    )
    report("r0_real_full", lambda state, params, rng, eos, t_, p_:
           fn(params, None, state, rng, eos_ids=eos, temperature=t_, top_p=p_),
           state, params, rng, eos, temperature, top_p)

    # rung 1: real forward() only — fixed token, no sampling / isin / out- or
    # mask-dus; carry = (step, logits, cache). If this double-buffers, the
    # culprit is inside forward(); if it aliases, it's the step scaffolding.
    def chunk_fwd_only(state, params, key_mask):
        def body(c, _):
            step, logits, cache = c
            tok = jnp.full((B, 1), 7, jnp.int32)
            nl, cache = forward(
                params, cfg, tok, attention_mask=key_mask,
                kv_cache=cache, cache_offset=P_ + step,
                attn_impl="reference",
            )
            return (step + 1, nl[:, 0], cache), None
        return jax.lax.scan(
            body, (jnp.zeros((), jnp.int32),
                   jnp.zeros((B, cfg.vocab_size), jnp.float32),
                   state.cache),
            None, length=CHUNK)[0]

    km = jax.ShapeDtypeStruct((B, S), jnp.int32)
    report("r1_forward_only", chunk_fwd_only, state, params, km)

    # rung 2: full step scaffolding (sample + isin + out/lengths/key_mask
    # dus) but forward replaced by probe-style per-layer dus + einsum read +
    # tiny logits head. If this double-buffers, the culprit is scaffolding.
    def fake_forward(cache, tok, key_mask, step):
        x = jnp.zeros((B, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
        new_k, new_v = [], []
        acc = jnp.zeros((B,), jnp.float32)
        for i in range(cfg.num_layers):
            ck = jax.lax.dynamic_update_slice(
                cache["k"][i], x[..., None], (0, 0, 0, P_ + step))
            cv = jax.lax.dynamic_update_slice(
                cache["v"][i], x[..., None], (0, 0, 0, P_ + step))
            sc = jnp.einsum("bkh,bkhs->bks", x.astype(jnp.float32),
                            ck.astype(jnp.float32))
            acc = acc + sc.mean(axis=(1, 2))
            new_k.append(ck)
            new_v.append(cv)
        logits = acc[:, None] * jnp.ones((1, cfg.vocab_size), jnp.float32)
        return logits, {**cache, "k": tuple(new_k), "v": tuple(new_v)}

    def step_scaffold(params, lora, s, rng, *, fwd, eos_ids, temperature,
                      top_p):
        tok = sample(jax.random.fold_in(rng, s.step), s.logits, temperature,
                     top_p, top_p_impl="bisect")
        tok = jnp.where(s.done, 0, tok)
        out = jax.lax.dynamic_update_slice(s.out, tok[:, None], (0, s.step))
        lengths = s.lengths + (~s.done).astype(jnp.int32)
        hit_eos = jnp.isin(tok, eos_ids)
        key_mask = jax.lax.dynamic_update_slice(
            s.key_mask, (~s.done).astype(s.key_mask.dtype)[:, None],
            (0, P_ + s.step))
        done = s.done | hit_eos
        next_logits, cache = fwd(s.cache, tok, key_mask, s.step)
        return E._DecodeState(
            step=s.step + 1, out=out, logps=s.logps, lengths=lengths,
            done=done, key_mask=key_mask, logits=next_logits, cache=cache)

    def chunk_scaffold(state, params, rng, eos, t_, p_, fwd):
        def body(c, _):
            return step_scaffold(params, None, c, rng, fwd=fwd, eos_ids=eos,
                                 temperature=t_, top_p=p_), None
        return jax.lax.scan(body, state, None, length=CHUNK)[0]

    report("r2_scaffold_fakefwd",
           lambda state, params, rng, eos, t_, p_: chunk_scaffold(
               state, params, rng, eos, t_, p_, fake_forward),
           state, params, rng, eos, temperature, top_p)

    # rung 3: scaffolding + REAL forward (the full body, == rung 0 but built
    # here — consistency check that the local scaffold reproduces it)
    def real_fwd(cache, tok, key_mask, step):
        nl, cache = forward(
            None_params[0], cfg, tok[:, None], attention_mask=key_mask,
            kv_cache=cache, cache_offset=P_ + step, attn_impl="reference",
        )
        return nl[:, 0], cache

    None_params = [params]
    report("r3_scaffold_realfwd",
           lambda state, params, rng, eos, t_, p_: chunk_scaffold(
               state, params, rng, eos, t_, p_,
               lambda c, t, m, st: (lambda nl_c: (nl_c[0][:, 0], nl_c[1]))(
                   forward(params, cfg, t[:, None], attention_mask=m,
                           kv_cache=c, cache_offset=P_ + st,
                           attn_impl="reference"))),
           state, params, rng, eos, temperature, top_p)

    # ---- stage 2: ladder INSIDE forward(), forward-only carry ----------
    from distrl_llm_tpu.models.transformer import (
        _proj, apply_rope, rms_norm, rope_cos_sin,
    )
    from distrl_llm_tpu.ops.attention import (
        attention_cached, causal_padding_mask,
    )

    def fwd_ladder(params, cfg, tok, key_mask, cache, step, *, rungs):
        """Partial re-assembly of forward()'s cached decode path; ``rungs``
        switches each real ingredient on."""
        b, s = tok.shape
        cache_offset = P_ + step
        if "embed" in rungs:
            x = jnp.take(params["embed"], tok, axis=0)
        else:
            x = jnp.zeros((b, s, cfg.hidden_size), jnp.bfloat16)
        positions = cache_offset + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        mask = (
            causal_padding_mask(key_mask, q_len=s, q_offset=cache_offset)
            if "mask" in rungs else None
        )
        new_k, new_v = [], []
        for i in range(cfg.num_layers):
            p_i = jax.tree_util.tree_map(lambda w: w[i], params["layers"])
            ck, cv = cache["k"][i], cache["v"][i]
            if "proj" in rungs:
                h = rms_norm(x, p_i["attn_norm"], cfg.rms_norm_eps)
                q = _proj(h, p_i, None, "wq", "bq", 1.0).reshape(
                    b, s, cfg.num_heads, cfg.head_dim)
                k = _proj(h, p_i, None, "wk", "bk", 1.0).reshape(
                    b, s, cfg.num_kv_heads, cfg.head_dim)
                v = _proj(h, p_i, None, "wv", "bv", 1.0).reshape(
                    b, s, cfg.num_kv_heads, cfg.head_dim)
                if "rope" in rungs:
                    q = apply_rope(q, cos, sin)
                    k = apply_rope(k, cos, sin)
            else:
                q = jnp.zeros((b, s, cfg.num_heads, cfg.head_dim),
                              jnp.bfloat16)
                k = jnp.zeros((b, s, cfg.num_kv_heads, cfg.head_dim),
                              jnp.bfloat16)
                v = k
            k_t = k.astype(ck.dtype).transpose(0, 2, 3, 1)
            v_t = v.astype(cv.dtype).transpose(0, 2, 3, 1)
            ck = jax.lax.dynamic_update_slice(ck, k_t, (0, 0, 0, cache_offset))
            cv = jax.lax.dynamic_update_slice(cv, v_t, (0, 0, 0, cache_offset))
            if "attn" in rungs:
                att = attention_cached(
                    q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
                att = att.reshape(b, s, cfg.q_dim)
            else:
                sc = jnp.einsum("bshd,bkds->bsk", q.astype(jnp.float32),
                                ck.astype(jnp.float32))
                att = (sc.mean(-1, keepdims=True)
                       * jnp.ones((1, 1, cfg.q_dim), jnp.float32)
                       ).astype(x.dtype)
            if "resid" in rungs:
                x = x + _proj(att, p_i, None, "wo", "bo", 1.0)
                h2 = rms_norm(x, p_i["mlp_norm"], cfg.rms_norm_eps)
                gate = jax.nn.silu(_proj(h2, p_i, None, "w_gate", "b_gate", 1.0))
                up = _proj(h2, p_i, None, "w_up", "b_up", 1.0)
                x = x + _proj(gate * up, p_i, None, "w_down", "b_down", 1.0)
            else:
                x = x + att.astype(x.dtype) * 0
            new_k.append(ck)
            new_v.append(cv)
        if "head" in rungs:
            xo = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            lm = (params["embed"].T if cfg.tie_word_embeddings
                  else params["lm_head"])
            logits = (xo @ lm).astype(jnp.float32)[:, 0]
        else:
            logits = jnp.zeros((b, cfg.vocab_size), jnp.float32)
        return logits, {**cache, "k": tuple(new_k), "v": tuple(new_v)}

    def chunk_ladder(state, params, key_mask, rungs):
        def body(c, _):
            step, logits, cache = c
            tok = jnp.full((B, 1), 7, jnp.int32)
            nl, cache = fwd_ladder(params, cfg, tok, key_mask, cache, step,
                                   rungs=rungs)
            return (step + 1, nl, cache), None
        return jax.lax.scan(
            body, (jnp.zeros((), jnp.int32),
                   jnp.zeros((B, cfg.vocab_size), jnp.float32),
                   state.cache),
            None, length=CHUNK)[0]

    LADDER = [
        ("s2_dus_only", frozenset()),
        ("s2_mask_attn", frozenset({"mask", "attn"})),
        ("s2_proj_rope", frozenset({"embed", "proj", "rope"})),
        ("s2_proj_attn", frozenset({"embed", "proj", "rope", "mask", "attn"})),
        ("s2_layers_full", frozenset({"embed", "proj", "rope", "mask",
                                      "attn", "resid"})),
        ("s2_everything", frozenset({"embed", "proj", "rope", "mask",
                                     "attn", "resid", "head"})),
    ]
    for name, rungs in LADDER:
        report(name,
               lambda state, params, km, rungs=rungs: chunk_ladder(
                   state, params, km, rungs),
               state, params, km)

    # ---- stage 3: write-value provenance vs read fusion ----------------
    # s2 found: invariant (zeros) writes alias, real computed writes don't.
    # Distinguish (a) ANY loop-variant write value, (b) the matmul/rope
    # provenance chain, (c) the read-after-write fusion with attention.
    def fwd_probe(params, cfg, key_mask, cache, step, *, write, read):
        b, s = B, 1
        cache_offset = P_ + step
        positions = jnp.broadcast_to(
            cache_offset + jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        mask = causal_padding_mask(key_mask, q_len=s, q_offset=cache_offset)
        new_k, new_v = [], []
        acc = jnp.zeros((b,), jnp.float32)
        for i in range(cfg.num_layers):
            p_i = jax.tree_util.tree_map(lambda w: w[i], params["layers"])
            ck, cv = cache["k"][i], cache["v"][i]
            if write == "real":  # embed-of-const -> proj -> rope
                x = jnp.take(params["embed"],
                             jnp.full((b, s), 7, jnp.int32), axis=0)
                h = rms_norm(x, p_i["attn_norm"], cfg.rms_norm_eps)
                q = apply_rope(_proj(h, p_i, None, "wq", "bq", 1.0).reshape(
                    b, s, cfg.num_heads, cfg.head_dim), cos, sin)
                k = apply_rope(_proj(h, p_i, None, "wk", "bk", 1.0).reshape(
                    b, s, cfg.num_kv_heads, cfg.head_dim), cos, sin)
                v = _proj(h, p_i, None, "wv", "bv", 1.0).reshape(
                    b, s, cfg.num_kv_heads, cfg.head_dim)
                k_t = k.astype(ck.dtype).transpose(0, 2, 3, 1)
                v_t = v.astype(cv.dtype).transpose(0, 2, 3, 1)
            elif write == "variant_scalar":  # step-derived, no matmuls
                q = jnp.zeros((b, s, cfg.num_heads, cfg.head_dim),
                              jnp.bfloat16)
                k_t = (jnp.zeros((b, cfg.num_kv_heads, cfg.head_dim, s),
                                 jnp.bfloat16)
                       + step.astype(jnp.bfloat16))
                v_t = k_t
            elif write == "invariant_matmul":  # matmul chain, no step dep
                x = jnp.take(params["embed"],
                             jnp.full((b, s), 7, jnp.int32), axis=0)
                h = rms_norm(x, p_i["attn_norm"], cfg.rms_norm_eps)
                q = _proj(h, p_i, None, "wq", "bq", 1.0).reshape(
                    b, s, cfg.num_heads, cfg.head_dim)
                k = _proj(h, p_i, None, "wk", "bk", 1.0).reshape(
                    b, s, cfg.num_kv_heads, cfg.head_dim)
                k_t = k.astype(ck.dtype).transpose(0, 2, 3, 1)
                v_t = k_t
            ck = jax.lax.dynamic_update_slice(ck, k_t, (0, 0, 0, cache_offset))
            cv = jax.lax.dynamic_update_slice(cv, v_t, (0, 0, 0, cache_offset))
            if read == "attn":
                att = attention_cached(
                    q, ck.astype(jnp.bfloat16), cv.astype(jnp.bfloat16), mask)
                acc = acc + att.reshape(b, -1).astype(jnp.float32).sum(-1)
            elif read == "sum":
                acc = acc + ck.astype(jnp.float32).sum((1, 2, 3))
            # read == "none": don't touch ck/cv again
            new_k.append(ck)
            new_v.append(cv)
        logits = jnp.broadcast_to(acc[:, None], (b, cfg.vocab_size))
        return logits.astype(jnp.float32), {
            **cache, "k": tuple(new_k), "v": tuple(new_v)}

    def chunk_probe(state, params, key_mask, write, read):
        def body(c, _):
            step, logits, cache = c
            nl, cache = fwd_probe(params, cfg, key_mask, cache, step,
                                  write=write, read=read)
            return (step + 1, nl, cache), None
        return jax.lax.scan(
            body, (jnp.zeros((), jnp.int32),
                   jnp.zeros((B, cfg.vocab_size), jnp.float32),
                   state.cache),
            None, length=CHUNK)[0]

    for name, write, read in [
        ("t1_varscalar_attn", "variant_scalar", "attn"),
        ("t2_real_noread", "real", "none"),
        ("t3_real_sumread", "real", "sum"),
        ("t4_invmatmul_attn", "invariant_matmul", "attn"),
    ]:
        report(name,
               lambda state, params, km, w=write, r=read: chunk_probe(
                   state, params, km, w, r),
               state, params, km)


if __name__ == "__main__":
    main()
