"""On-chip HBM envelope for reference recipe 2 (Qwen2.5-7B, one chip).

The 7B-on-one-chip capacity math from exact accounting: this tool computes
the envelope with ``jax.eval_shape`` (exact per-leaf bytes, nothing allocated)
for the int4-quantized base + LoRA + the paged engine's page pools at the
reference rollout volume (480 candidates, 350+1,200 token budget,
train_distributed.py:17-28), across slot counts and KV-quant modes, and
prints the recommended ``--max_concurrent_sequences`` / page-pool size.

With ``GRAFT_MEMORY_COMPILE=1`` and a live TPU it additionally lowers and
compiles the refill decode step at the recommended config and prints XLA's
``memory_analysis`` (argument/output/temp bytes) — the compile-time ground
truth the table approximates.

Run: ``python tools/memory_envelope.py [--hbm-gib 16] [--usage 0.91]``
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hbm-gib", type=float, default=16.0,
                    help="chip HBM (v5e/v5p: 16)")
    ap.add_argument("--usage", type=float, default=0.91,
                    help="--actor_gpu_usage (reference default)")
    ap.add_argument("--markdown", action="store_true",
                    help="emit the table as markdown rows")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if os.environ.get("GRAFT_MEMORY_COMPILE", "0") != "1":
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from distrl_llm_tpu.engine.budget import ACTIVATION_RESERVE, page_bytes
    from distrl_llm_tpu.models import QWEN2_7B, init_lora_params, init_params
    from distrl_llm_tpu.ops.paged import pages_per_seq
    from distrl_llm_tpu.ops.quant import default_group_size, quantize_params

    cfg = QWEN2_7B
    GIB = 1024**3
    hbm = args.hbm_gib * GIB

    def tree_bytes_abstract(tree) -> int:
        return sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(tree)
            if hasattr(l, "shape")
        )

    # exact per-leaf bytes via eval_shape — nothing is allocated
    base_q = jax.eval_shape(
        lambda k: quantize_params(
            init_params(k, cfg, dtype=jnp.bfloat16),
            bits=4, group_size=default_group_size(4),
        ),
        jax.random.PRNGKey(0),
    )
    lora = jax.eval_shape(
        functools.partial(init_lora_params, cfg=cfg, rank=32, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0),
    )
    w_bytes = tree_bytes_abstract(base_q)
    lora_bytes = tree_bytes_abstract(lora)

    # recipe-2 volume (reference train_distributed.py:17-28)
    B, n = 30, 16
    total = B * n  # 480 candidates
    P_TOK, NEW = 350, 1200
    MEAN_REALIZED = 470  # reference's observed rollout mean
    ps = 128
    prompt_pages = pages_per_seq(P_TOK, ps)
    private = 1 + pages_per_seq(NEW, ps)
    mean_pages = 1 + pages_per_seq(MEAN_REALIZED, ps)

    rows = []
    for kv in ("bf16", "int8"):
        quant = "none" if kv == "bf16" else "int8"
        pb = page_bytes(cfg, ps, quant)
        shared = B * prompt_pages * pb
        # decode-step activations: carried logits [R, V] f32 ×2 (carried +
        # next), sampling temps ≈ another [R, V], hidden states negligible
        for R in (64, 96, 128, 192, 256, 480):
            act = 3 * R * cfg.vocab_size * 4
            worst = (1 + R * private) * pb
            realized = (1 + R * mean_pages) * pb
            budget_pool = int(
                hbm * (args.usage - ACTIVATION_RESERVE)
                - w_bytes - lora_bytes - shared
            ) // pb
            fits_worst = w_bytes + lora_bytes + shared + worst + act <= args.usage * hbm
            fits_real = w_bytes + lora_bytes + shared + realized + act <= args.usage * hbm
            rows.append({
                "kv": kv, "R": R,
                "worst_gib": worst / GIB,
                "realized_gib": realized / GIB,
                "budget_pool_pages": max(budget_pool, 0),
                "act_gib": act / GIB,
                "fits_worst": fits_worst, "fits_realized": fits_real,
            })

    print(f"# Qwen2.5-7B one-chip envelope (config 2): HBM {args.hbm_gib} GiB, "
          f"usage {args.usage}")
    print(f"weights int4(g{default_group_size(4)}): {w_bytes / GIB:.2f} GiB; "
          f"LoRA r32: {lora_bytes / GIB:.3f} GiB; "
          f"volume {B}x{n}={total} cand, {P_TOK}+{NEW} tok, "
          f"mean realized {MEAN_REALIZED}")
    hdr = ("| KV | R (slots) | KV worst-case | KV @realized | budget pool "
           "(pages @0.91) | decode act | fits worst? | fits realized? |")
    print(hdr)
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['kv']} | {r['R']} | {r['worst_gib']:.2f} GiB "
            f"| {r['realized_gib']:.2f} GiB | {r['budget_pool_pages']} "
            f"| {r['act_gib']:.2f} GiB "
            f"| {'yes' if r['fits_worst'] else 'NO'} "
            f"| {'yes' if r['fits_realized'] else 'NO'} |"
        )

    # recommendation: largest R that (a) fits at realized lengths AND
    # (b) keeps mean steady-state occupancy R×mean_pages within the budget
    # pool (so the grow-as-you-go allocator isn't preempting at the MEAN —
    # preemption covers the tail, not the steady state); worst-case
    # provisioning shown for the no-budget configuration
    for kv in ("int8", "bf16"):
        ok = [
            r["R"] for r in rows
            if r["kv"] == kv and r["fits_realized"]
            and r["R"] * mean_pages + 1 <= r["budget_pool_pages"]
        ]
        okw = [r["R"] for r in rows if r["kv"] == kv and r["fits_worst"]]
        print(
            f"recommended max_concurrent_sequences ({kv} KV): "
            f"{max(ok) if ok else 'none'} with the page budget "
            f"(worst-case provisioning: {max(okw) if okw else 'none'})"
        )

    if os.environ.get("GRAFT_MEMORY_COMPILE", "0") == "1":
        _compile_check(cfg)


def _compile_check(cfg) -> None:
    """Ground-truth: lower + compile ONE refill decode step at the
    recommended config (R=128, int8 KV, int4 base, config-2 volume) and
    print XLA's memory analysis. Everything is abstract until the backend
    compile — run on a chip for TPU-accurate numbers."""
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import init_params
    from distrl_llm_tpu.ops.quant import default_group_size, quantize_params

    print("\n# compile-time memory_analysis (refill decode step, R=128, "
          "int8 KV, int4 base)")
    b, n, r_slots, max_steps = 30, 16, 128, 1200
    eng = PagedGenerationEngine(
        cfg, max_prompt_tokens=384, max_new_tokens=max_steps,
        eos_token_ids=[151645], pad_token_id=151643, page_size=128,
        max_concurrent_rows=r_slots, scheduler="refill", kv_quant="int8",
    )
    struct = jax.eval_shape
    params = struct(
        lambda k: quantize_params(
            init_params(k, cfg, dtype=jnp.bfloat16),
            bits=4, group_size=default_group_size(4),
        ),
        jax.random.PRNGKey(0),
    )
    from distrl_llm_tpu.ops.paged import init_quantized_pages

    page_shape = (cfg.num_kv_heads, b * eng.prompt_pages, 128, cfg.head_dim)
    prompt_pages_abs = struct(
        lambda: tuple(init_quantized_pages(page_shape)
                      for _ in range(cfg.num_layers))
    )
    pool_pages = 1 + r_slots * eng.private_pages
    state = struct(
        functools.partial(
            eng._refill_init.__wrapped__,  # noqa: SLF001 — tooling
            b=b, r_slots=r_slots, total=b * n, max_steps=max_steps,
            vocab=cfg.vocab_size, pool_pages=pool_pages,
        ),
        prompt_pages_abs, prompt_pages_abs,
    )
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    eos = jax.ShapeDtypeStruct((2,), jnp.int32)
    lowered = eng._refill_step.lower(
        params, None, state, rng, eos_ids=eos, temperature=scalar,
        top_p=scalar, max_steps=max_steps, top_p_impl="bisect",
    )
    mem = lowered.compile().memory_analysis()
    gib = 1024**3
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            print(f"{k}: {v / gib:.3f} GiB")


if __name__ == "__main__":
    main()
