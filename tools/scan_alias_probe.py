"""Isolate WHY the K-steps-per-dispatch scan programs double-buffer their
KV-cache-sized carry on the TPU compiler (r5 finding: every scan_chunk
bench row fell back — dense bf16 chunk program crashes remote compile,
int8/refill trip the 0.5x-alias memory guard, so `scan_chunk_active` was
False in all four rows and the dispatch-amortization A/B never ran).

Compiles (never executes) a family of structurally-minimal decode-like
scan bodies at cache scale and prints `memory_analysis().temp_size_in_bytes`
for each variant:

  v1_cond      lax.scan, body wrapped in lax.cond(halt, skip, run)  [today]
  v2_nocond    lax.scan, body runs unconditionally
  v3_where     lax.scan, cond replaced by predicate-masked writes
  v4_fori      fori_loop instead of scan, unconditional
  v5_cond_fori fori_loop with lax.cond body                          [control]

Each body mimics one decode step over a [B, K, hd, S] cache: dus-write one
position at a data-dependent step index, then read-reduce the whole cache
(attention-like), then update small carries. If v1 shows a cache-sized temp
and v2/v3 do not, the cond's select over the carried cache is the
double-buffering culprit and the engines' chunk scaffolding should drop it.

Usage: python tools/scan_alias_probe.py [B] [S] [chunk]
"""

import os
import sys
from functools import partial

import jax


import jax.numpy as jnp

B = int(sys.argv[1]) if len(sys.argv) > 1 else 480
S = int(sys.argv[2]) if len(sys.argv) > 2 else 1550
CHUNK = int(sys.argv[3]) if len(sys.argv) > 3 else 16
KH, HD, LAYERS = 2, 64, 8  # 8 layers is enough to dwarf the guard floor
VOCAB = 1024  # logits scratch is not what we are measuring


def step(s):
    cache, out, step_i, done = s
    # attention-like read of the full cache: q·K over hd, softmax-ish, ·V
    q = jnp.ones((B, KH, HD), jnp.bfloat16)
    new_cache = []
    att_acc = jnp.zeros((B,), jnp.float32)
    for l in range(LAYERS):
        ck = cache[l]
        # write this step's k at position step_i (clamped like dus)
        kt = (q[..., None] * 0.01).astype(ck.dtype)  # [B, K, hd, 1]
        ck = jax.lax.dynamic_update_slice(ck, kt, (0, 0, 0, step_i))
        scores = jnp.einsum("bkh,bkhs->bks", q.astype(jnp.float32),
                            ck.astype(jnp.float32))
        att_acc = att_acc + scores.mean(axis=(1, 2))
        new_cache.append(ck)
    tok = (att_acc * 7).astype(jnp.int32) % VOCAB
    out = out.at[:, step_i].set(jnp.where(done, out[:, step_i], tok))
    done = done | (tok == 0)
    return tuple(new_cache), out, step_i + 1, done


def skip(s):
    cache, out, step_i, done = s
    return cache, out, step_i + 1, done


def halt(s):
    return s[3].all()


def chunk_cond(s):
    def body(c, _):
        return jax.lax.cond(halt(c), skip, step, c), None
    return jax.lax.scan(body, s, None, length=CHUNK)[0]


def chunk_nocond(s):
    def body(c, _):
        return step(c), None
    return jax.lax.scan(body, s, None, length=CHUNK)[0]


def chunk_where(s):
    # predicate folded into the index: halted iterations write off the end
    # (dus clamps; out uses drop-mode scatter) — no select over the cache
    def body(c, _):
        cache, out, step_i, done = c
        n = step((cache, out, step_i, done))
        live = ~halt(c)
        # big buffers: take the stepped version unconditionally (halted
        # bodies only re-write position step_i with identical masking);
        # small carries keep exact skip semantics
        return (n[0], n[1], step_i + 1,
                jnp.where(live, n[3], done)), None
    return jax.lax.scan(body, s, None, length=CHUNK)[0]


def chunk_fori(s):
    return jax.lax.fori_loop(0, CHUNK, lambda i, c: step(c), s)


def chunk_cond_fori(s):
    return jax.lax.fori_loop(
        0, CHUNK, lambda i, c: jax.lax.cond(halt(c), skip, step, c), s)


def main():
    cache = tuple(
        jax.ShapeDtypeStruct((B, KH, HD, S), jnp.bfloat16)
        for _ in range(LAYERS)
    )
    out = jax.ShapeDtypeStruct((B, S), jnp.int32)
    s0 = (cache, out, jnp.asarray(0, jnp.int32),
          jax.ShapeDtypeStruct((B,), jnp.bool_))
    cache_bytes = sum(2 * B * KH * HD * S for _ in range(LAYERS))
    print(f"cache bytes: {cache_bytes/2**30:.2f} GiB  "
          f"(B={B} S={S} chunk={CHUNK} layers={LAYERS})")
    for name, fn in [("v1_cond", chunk_cond), ("v2_nocond", chunk_nocond),
                     ("v3_where", chunk_where), ("v4_fori", chunk_fori),
                     ("v5_cond_fori", chunk_cond_fori)]:
        try:
            c = jax.jit(fn, donate_argnums=(0,)).lower(s0).compile()
            ma = c.memory_analysis()
            t = ma.temp_size_in_bytes
            flag = "DOUBLE-BUFFERED" if t > 0.5 * cache_bytes else "aliased ok"
            print(f"{name}: temp {t/2**30:.2f} GiB  [{flag}]")
        except Exception as e:  # noqa: BLE001
            print(f"{name}: COMPILE FAILED {type(e).__name__}: {str(e)[:200]}")


if __name__ == "__main__":
    main()
