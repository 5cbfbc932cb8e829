"""Decompose the dense decode step's on-chip time: forward+cache-write vs
top-p sampling vs the assembled step, at bench shapes (480 rows, 0.5B).

Answers the r5 roofline question: even with real chunking, where does the
per-step time beyond the ~4-7 ms bandwidth bound go? The three timings
bracket it:

  fwd      one-token forward incl. KV cache dus-write (no sampling)
  sample   top-p sampling alone on a carried [B, V] logits buffer
  step     the engine's full _decode_step (sample + write + forward)

Timing is fetch-based (float() of a chain-dependent scalar): the scalar's
bytes depend on the whole chain, so the clock cannot stop early.
Each timing chains STEPS donated executions, threading the carry so
donated buffers are never reused; divide by STEPS for ms/step.

Usage: python tools/step_anatomy.py [B] [kv_quant] [top_p_impl]
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, ".")

import jax


import jax.numpy as jnp
import numpy as np

B = int(sys.argv[1]) if len(sys.argv) > 1 else 480
KV_QUANT = sys.argv[2] if len(sys.argv) > 2 else "none"
TOP_P_IMPL = sys.argv[3] if len(sys.argv) > 3 else "bisect"
STEPS = 32
P_LEN, T_LEN = 350, 1200
MID = 600  # mid-decode position: cache half full, the representative step


def fetch(carry) -> float:
    """Synchronize on a value that DEPENDS on the whole chain: a scalar
    fetched to the host cannot return early."""
    leaf = jax.tree_util.tree_leaves(carry)[0]
    return float(jnp.asarray(leaf, jnp.float32).ravel()[0])


def timed(label, fn, carry):
    """fn(carry) -> carry, chained STEPS times after one warmup call."""
    carry = fn(carry)  # compile + warm
    fetch(carry)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        carry = fn(carry)
    fetch(carry)
    dt = (time.perf_counter() - t0) / STEPS
    print(f"{label}: {dt*1e3:.2f} ms/step  ({B/dt:,.0f} tok/s at B={B})",
          flush=True)
    return dt, carry


def main() -> int:
    from distrl_llm_tpu.engine import engine as E
    from distrl_llm_tpu.models import QWEN2_0_5B, init_params
    from distrl_llm_tpu.models.transformer import (
        forward, init_kv_cache, init_kv_cache_int8,
    )
    from distrl_llm_tpu.ops.sampling import sample

    cfg = QWEN2_0_5B
    dev = jax.devices()[0]
    print(f"backend={dev.platform} B={B} kv={KV_QUANT} top_p={TOP_P_IMPL}",
          flush=True)
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    total = P_LEN + T_LEN
    cache = (init_kv_cache_int8(cfg, B, total) if KV_QUANT == "int8"
             else init_kv_cache(cfg, B, total, dtype=dtype))
    key_mask = jnp.concatenate([
        jnp.ones((B, P_LEN + MID), jnp.int32),
        jnp.zeros((B, total - P_LEN - MID), jnp.int32)], axis=1)
    tok = jnp.full((B, 1), 17, jnp.int32)
    logits0 = jnp.asarray(
        np.random.default_rng(0).normal(size=(B, cfg.vocab_size)), jnp.float32)
    rng = jax.random.PRNGKey(1)

    # ---- forward + cache write only ----------------------------------
    @partial(jax.jit, donate_argnames=("cache",))
    def fwd(cache, tok):
        logits, cache = forward(
            params, cfg, tok, attention_mask=key_mask, lora=None,
            lora_scale=1.0, kv_cache=cache, cache_offset=P_LEN + MID,
            attn_impl="reference",
        )
        return logits, cache

    dt_fwd, (logits, cache) = timed(
        "fwd+write", lambda c: fwd(c[1], tok), (logits0, cache))

    # ---- sampling only (no donation; rng folds per call) -------------
    @jax.jit
    def samp(logits, rng):
        tok = sample(rng, logits, jnp.float32(1.0), jnp.float32(0.95),
                     top_p_impl=TOP_P_IMPL)
        return tok, jax.random.fold_in(rng, 1)

    dt_s, _ = timed(
        "sample", lambda c: samp(logits0, c[1]), (jnp.zeros(()), rng))

    # ---- the engine's assembled step ---------------------------------
    state = E._decode_init(
        cache, key_mask, logits0, jnp.ones((B,), bool),
        n=1, max_steps=T_LEN, pad_id=0)
    state = state._replace(step=jnp.asarray(MID, jnp.int32))
    step_fn = jax.jit(
        partial(E._decode_step, cfg=cfg, prompt_len=P_LEN, pad_id=0,
                lora_scale=1.0, attn_impl="reference",
                top_p_impl=TOP_P_IMPL, capture_logprobs=False),
        donate_argnames=("state",), static_argnames=("top_p_impl",),
    )

    # hoisted device constants: rebuilding them per call would charge three
    # extra host->device transfers to dt_step but not dt_fwd/dt_s, skewing
    # the residual this tool exists to isolate
    eos_ids = jnp.asarray([151645], jnp.int32)
    temperature = jnp.float32(1.0)
    top_p = jnp.float32(0.95)

    def one(state):
        return step_fn(params, None, state, rng, eos_ids=eos_ids,
                       temperature=temperature, top_p=top_p)

    dt_step, _ = timed("full step", one, state)

    resid = dt_step - dt_fwd - dt_s
    print(f"residual (step - fwd - sample): {resid*1e3:.2f} ms "
          f"(dispatch + out/mask writes + logit copy)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
