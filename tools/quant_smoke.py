#!/usr/bin/env python
"""Quantized-serving smoke check (wired into tools/run_all_checks.sh).

The ISSUE-15 acceptance contract, end to end on a CPU host:

1. **Kernel-vs-container greedy bit-identity** — a quantized-base (int8
   AND int4, with LoRA) greedy decode through the fused Pallas
   dequant-matmul kernel (interpret mode) must emit byte-identical tokens
   to the XLA container path (the claim ops/quant_matmul.py makes for the
   TPU dispatch).
2. **Fused sampler** — greedy decode through the fused sample-from-logits
   kernel must be bit-identical to the multi-pass sampler at the engine
   level; the SAMPLED path must pass a seeded statistical-parity check
   against the multi-pass reference (distribution-exact, the spec_accept
   discipline — the draw streams differ by construction).
3. **int8-KV plan resolution** — an engine built with kv_quant=None must
   adopt a stored plan's ``kv_format: int8``; an explicit ``"none"`` must
   pin it off past the same plan; an empty DB must keep the historical
   "none" default.

Exits nonzero on any violation.
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _greedy_tokens(params, lora, env_mode: str) -> "object":
    """One greedy TINY decode round under DISTRL_QUANT_MATMUL=env_mode
    (fresh engine per mode: the dispatch decision is made at trace time)."""
    import numpy as np

    import jax

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.models import TINY

    os.environ["DISTRL_QUANT_MATMUL"] = env_mode
    try:
        eng = GenerationEngine(
            TINY, max_prompt_tokens=8, max_new_tokens=12,
            eos_token_ids=[1], pad_token_id=0, autotune=False,
            capture_logprobs=True,
        )
        prompts = np.random.default_rng(0).integers(
            2, TINY.vocab_size, (3, 8)
        ).astype(np.int32)
        res = eng.generate(
            params, lora, prompts, np.ones_like(prompts),
            SamplingConfig(max_tokens=12, temperature=0.0, top_p=1.0, n=2),
            jax.random.PRNGKey(7),
        )
    finally:
        del os.environ["DISTRL_QUANT_MATMUL"]
    return res


def main() -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.models import TINY, init_lora_params, init_params
    from distrl_llm_tpu.ops.quant import quantize_params

    base = init_params(jax.random.PRNGKey(0), TINY)
    lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)

    # ---- 1. kernel-vs-container greedy bit-identity (int8 + int4) -------
    for bits, label in ((8, "int8"), (4, "int4")):
        qp = quantize_params(base, bits=bits, group_size=16)
        ref = _greedy_tokens(qp, lora, "xla")
        got = _greedy_tokens(qp, lora, "interpret")
        assert (ref.tokens == got.tokens).all(), (
            f"{label}: fused-kernel greedy tokens diverged from the "
            f"container path"
        )
        assert np.allclose(ref.logprobs, got.logprobs, atol=1e-6), (
            f"{label}: behavior logprobs diverged"
        )
        print(f"PASS quant_matmul_{label}_greedy_bit_identity "
              f"(tokens {ref.tokens.shape}, kernel==container)")

    # ---- 2a. fused sampler greedy bit-identity (engine level) -----------
    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.engine import GenerationEngine

    prompts = np.random.default_rng(3).integers(
        2, TINY.vocab_size, (3, 8)
    ).astype(np.int32)
    outs = {}
    for mode in ("xla", "interpret"):
        os.environ["DISTRL_SAMPLE_KERNEL"] = mode
        try:
            eng = GenerationEngine(
                TINY, max_prompt_tokens=8, max_new_tokens=12,
                eos_token_ids=[1], pad_token_id=0, autotune=False,
                capture_logprobs=True,
            )
            outs[mode] = eng.generate(
                base, None, prompts, np.ones_like(prompts),
                SamplingConfig(max_tokens=12, temperature=0.0, top_p=0.95,
                               n=2),
                jax.random.PRNGKey(5),
            )
        finally:
            del os.environ["DISTRL_SAMPLE_KERNEL"]
    assert (outs["xla"].tokens == outs["interpret"].tokens).all(), (
        "fused sampler greedy tokens diverged from the multi-pass sampler"
    )
    assert np.allclose(
        outs["xla"].logprobs, outs["interpret"].logprobs, atol=1e-6
    ), "fused sampler greedy logprobs diverged"
    print("PASS fused_sampler_greedy_bit_identity")

    # ---- 2b. fused sampler sampled-path distribution parity -------------
    # N iid draws per call (identical rows, per-row seeds): the fused and
    # multi-pass empirical distributions must both sit within sampling
    # noise of each other — total-variation distance under a seeded bound
    # (~sqrt(V/N) scale; 3x headroom keeps the gate deterministic-stable)
    from distrl_llm_tpu.ops.sampling import fused_sample, sample

    V, N = 64, 8192
    row = jnp.asarray(
        np.random.default_rng(11).normal(size=(V,)) * 2.0, jnp.float32
    )
    tiled = jnp.tile(row[None, :], (N, 1))
    t, p = 1.2, 0.95
    toks_f = np.asarray(
        fused_sample(jax.random.PRNGKey(21), tiled, t, p, interpret=True)[0]
    )
    toks_m = np.asarray(sample(jax.random.PRNGKey(22), tiled, t, p))
    emp_f = np.bincount(toks_f, minlength=V) / N
    emp_m = np.bincount(toks_m, minlength=V) / N
    tv = 0.5 * np.abs(emp_f - emp_m).sum()
    bound = 3.0 * (V / N) ** 0.5
    assert tv < bound, f"sampled-path TV {tv:.4f} >= bound {bound:.4f}"
    print(f"PASS fused_sampler_distribution_parity (TV {tv:.4f} < "
          f"{bound:.4f} at N={N})")

    # ---- 3. int8-KV plan resolution ------------------------------------
    from distrl_llm_tpu.autotune import (
        ExecutionPlan, PlanStore, model_config_hash, plan_key, shape_bucket,
    )
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    tmp = tempfile.mkdtemp(prefix="distrl_quant_smoke_")
    db = os.path.join(tmp, "plan_db.json")
    store = PlanStore(db)
    store.put(
        plan_key("cpu", model_config_hash(TINY), shape_bucket(8, 12, 0)),
        ExecutionPlan(decode_path="paged", kv_format="int8"),
        [{"tok_s": 1.0, "note": "quant_smoke seed"}],
    )
    store.save()
    common = dict(
        max_prompt_tokens=8, max_new_tokens=12, eos_token_ids=[1],
        pad_token_id=0, cache_dtype=jnp.float32, page_size=8,
    )
    eng_db = PagedGenerationEngine(TINY, plan_db=db, **common)
    assert eng_db.kv_quant == "int8", (
        f"kv_quant=None must adopt the stored kv_format, got "
        f"{eng_db.kv_quant!r}"
    )
    eng_pin = PagedGenerationEngine(TINY, plan_db=db, kv_quant="none",
                                    **common)
    assert eng_pin.kv_quant == "none", (
        "explicit kv_quant='none' must pin past the stored int8 plan"
    )
    eng_empty = PagedGenerationEngine(
        TINY, plan_db=os.path.join(tmp, "empty.json"), **common
    )
    assert eng_empty.kv_quant == "none", (
        "empty plan DB must keep the historical 'none' default"
    )
    # and the resolved engine actually decodes over int8 pages
    res = eng_db.generate(
        base, None, prompts, np.ones_like(prompts),
        SamplingConfig(max_tokens=12, temperature=0.0, top_p=1.0, n=2),
        jax.random.PRNGKey(9),
    )
    assert res.tokens.shape == (3, 2, 12)
    print("PASS int8_kv_plan_resolution (db→int8, explicit-none pin, "
          "empty-db default, int8 decode round)")

    print("quant_smoke: ALL PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
