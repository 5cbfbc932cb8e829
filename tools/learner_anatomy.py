"""Decompose the on-chip learner step at bench shapes (8 × [350+1200]):

  loss_fwd   value_and_grad's forward alone (loss value, no grads)
  grad       loss + backward (no optimizer)
  update     the engine's full train step (grad accum + int8 Adam)

The r5 learner row measured 2.997 s/step at 0.5B — ~15x the ~0.2 s FLOPs
bound at 197 TFLOP/s — and nothing isolates whether the forward (chunked
CE over the 151,936 vocab), the backward, remat recompute, or the
optimizer owns the gap. Fetch-based timing (the fetched scalar depends on
the whole step).

Usage: python tools/learner_anatomy.py [rows] [micro] [max_new]
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, ".")

import jax


import jax.numpy as jnp
import numpy as np

ROWS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
MICRO = int(sys.argv[2]) if len(sys.argv) > 2 else 8
T_LEN = int(sys.argv[3]) if len(sys.argv) > 3 else 1200
MODEL = sys.argv[4] if len(sys.argv) > 4 else "qwen2.5-0.5b"
P_LEN = 350
STEPS = 3


def timed(label, fn, *args, fetch):
    out = fn(*args)
    fetch(out)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    fetch(out)
    dt = (time.perf_counter() - t0) / STEPS
    toks = ROWS * (P_LEN + T_LEN)
    print(f"{label}: {dt*1e3:.0f} ms  ({toks/dt:,.0f} tok/s)", flush=True)
    return dt


def main() -> int:
    from distrl_llm_tpu.learner.losses import answer_logprobs, grpo_loss
    from distrl_llm_tpu.learner.optim import make_optimizer
    from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
    from distrl_llm_tpu.models import (
        QWEN2_0_5B, TINY, init_lora_params, init_params,
    )
    from distrl_llm_tpu.models.lora import lora_scale

    cfg = {"qwen2.5-0.5b": QWEN2_0_5B, "tiny": TINY}[MODEL]
    dev = jax.devices()[0]
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    print(f"backend={dev.platform} rows={ROWS} micro={MICRO} "
          f"seq={P_LEN}+{T_LEN}", flush=True)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=32)
    scale = lora_scale(32, 16.0)
    rng = np.random.default_rng(0)
    batch = UpdateBatch(
        prompt_ids=jnp.asarray(
            rng.integers(1, cfg.vocab_size, (ROWS, P_LEN)), jnp.int32),
        prompt_mask=jnp.ones((ROWS, P_LEN), jnp.int32),
        answer_ids=jnp.asarray(
            rng.integers(1, cfg.vocab_size, (ROWS, T_LEN)), jnp.int32),
        answer_mask=jnp.ones((ROWS, T_LEN), jnp.int32),
        coeffs=jnp.asarray(rng.normal(size=ROWS), jnp.float32),
        sample_mask=jnp.ones((ROWS,), jnp.float32),
    )

    def loss_fn(lora_p, mb):
        logps = answer_logprobs(
            params, cfg, mb.prompt_ids, mb.prompt_mask,
            mb.answer_ids, mb.answer_mask, lora=lora_p, lora_scale=scale,
            logit_chunk=128,
        )
        return grpo_loss(logps, mb.answer_mask, mb.coeffs, mb.sample_mask)

    # ---- forward only -------------------------------------------------
    fwd = jax.jit(loss_fn)
    timed("loss_fwd", fwd, lora, batch, fetch=lambda o: float(o))

    # ---- forward + backward ------------------------------------------
    grad = jax.jit(jax.value_and_grad(loss_fn))
    timed("grad", grad, lora, batch,
          fetch=lambda o: float(o[0]))

    # ---- the engine's full update (grad accum + int8 Adam) -----------
    optimizer = make_optimizer(2e-5, use_8bit=True)
    opt_state = optimizer.init(lora)
    step = make_train_step(
        cfg, learner_type="grpo", optimizer=optimizer, lora_scale=scale,
        micro_size=MICRO, donate=False, logit_chunk=128,
        attn_impl="reference",
    )
    timed("update", lambda: step(lora, opt_state, params, batch),
          fetch=lambda o: float(o[2]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
