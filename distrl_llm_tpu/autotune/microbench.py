"""In-process micro-bench harness: score each candidate plan on THIS device.

Three rules:

* **Warmup/steady-state separation.** The first generate() pays XLA
  compilation (minutes, cold); a
  candidate's score is the mean of the post-warmup repeats only, and both
  times are reported so a pathological compile also shows up.
* **Infeasible, not fatal.** Every candidate runs under the existing
  memory-envelope math (engine/budget.py — the ``--actor_gpu_usage``
  contract's single owner) BEFORE an engine is built, and the build+run is
  wrapped: a candidate that would OOM, trip the compiler, or hit a Mosaic
  lowering surprise is scored ``feasible=False`` with the reason, and the
  sweep continues. The engines' own compile-time guards
  (``compile_chunk_guarded``) stay active underneath, so a chunk candidate
  whose program double-buffers is measured as what it actually ran
  (host-dispatched fallback) and flagged via ``scan_chunk_active``.
* **Deterministic volume.** EOS is unreachable, so every candidate decodes
  exactly the same token count and tok/s is comparable across candidates.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, NamedTuple, Sequence

import numpy as np

from distrl_llm_tpu.autotune.plan import ExecutionPlan

log = logging.getLogger(__name__)


class CandidateResult(NamedTuple):
    plan: ExecutionPlan
    feasible: bool
    tok_s: float  # steady-state tokens/sec (0.0 when infeasible)
    warmup_s: float  # compile + first run
    steady_s: float  # mean timed-run seconds
    tokens: int  # tokens generated per timed run
    note: str  # infeasibility reason / honesty flags ("chunk fell back")


def plan_memory_guard(
    model_cfg,
    plan: ExecutionPlan,
    *,
    rows: int,
    max_prompt_tokens: int,
    max_new_tokens: int,
    param_bytes: int,
    kv_quant: str = "none",
    hbm_bytes: int | None = None,
) -> str | None:
    """None when the candidate's resident footprint fits the device, else
    the reason string. Reuses the budget module's page math (the single
    owner of KV bytes) and its activation reserve — the same envelope the
    refill pool is sized with, so "infeasible" here means "the engine's own
    budget would have clamped or OOMed"."""
    from distrl_llm_tpu.engine.budget import (
        ACTIVATION_RESERVE, device_hbm_bytes, page_bytes,
    )

    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    total = max_prompt_tokens + max_new_tokens
    kv = rows * total * page_bytes(model_cfg, 1, kv_quant)
    budget = int(hbm * (1.0 - ACTIVATION_RESERVE))
    need = param_bytes + kv
    if need > budget:
        return (
            f"resident footprint {need / 2**30:.2f} GiB (weights "
            f"{param_bytes / 2**30:.2f} + KV {kv / 2**30:.2f}) exceeds "
            f"{budget / 2**30:.2f} GiB usable HBM"
        )
    return None


def time_candidate(
    run: Callable[[int], int],
    *,
    warmup: int = 1,
    repeats: int = 2,
) -> tuple[float, float, int]:
    """(warmup_s, steady_s_mean, tokens_per_run) for ``run(seed) -> tokens``.
    Warmup runs are timed but excluded from the score."""
    t0 = time.perf_counter()
    tokens = 0
    for i in range(max(warmup, 1)):
        tokens = run(i)
    warmup_s = time.perf_counter() - t0
    times = []
    for i in range(max(repeats, 1)):
        t1 = time.perf_counter()
        tokens = run(100 + i)
        times.append(time.perf_counter() - t1)
    return warmup_s, float(np.mean(times)), tokens


def build_engine_for_plan(
    model_cfg,
    plan: ExecutionPlan,
    *,
    max_prompt_tokens: int,
    max_new_tokens: int,
    rows: int,
    pad_id: int = 0,
    eos_ids: Sequence[int] = (-1,),
    cache_dtype=None,
    kv_quant: str = "none",
    spec_draft: int = 4,
):
    """The engine a candidate plan describes, built with ``autotune=False``
    so the measurement exercises the CANDIDATE, not a previously stored
    plan."""
    import jax.numpy as jnp

    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    if cache_dtype is None:
        import jax

        cache_dtype = (
            jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32
        )
    common = dict(
        max_prompt_tokens=max_prompt_tokens,
        max_new_tokens=max_new_tokens,
        eos_token_ids=list(eos_ids),
        pad_token_id=pad_id,
        cache_dtype=cache_dtype,
        # a kv_format candidate (ISSUE 15) IS the engine's KV format; a
        # None-field candidate falls back to the sweep-level kv_quant
        kv_quant=(
            plan.kv_format if plan.kv_format is not None else kv_quant
        ),
        scan_chunk=plan.scan_chunk,
        autotune=False,
    )
    if plan.decode_path == "dense":
        return GenerationEngine(
            model_cfg,
            cache_read_formulation=plan.cache_read_formulation,
            prompt_buckets=plan.prompt_buckets or None,
            **common,
        )
    paged_kw: dict = {}
    if plan.cb_mode is not None:
        # the admission-regime candidate pins continuous admission on or
        # off ("batch" measures the fixed-batch control); it needs the
        # refill scheduler — the slot machinery that hosts both prefix
        # sharing and the lazy group-admission queue
        paged_kw["continuous_admission"] = plan.cb_mode == "continuous"
    if plan.decode_path == "paged":
        if plan.cb_mode is not None:
            paged_kw.update(
                scheduler="refill",
                max_concurrent_rows=max(min(rows, 64), 1),
            )
        return PagedGenerationEngine(model_cfg, **paged_kw, **common)
    # speculative: refill scheduler hosts it; slots capped at the row
    # count. The plan's spec fields ARE the candidate (draft length,
    # drafter, verify kernel); ``spec_draft`` only backstops pre-spec-field
    # plans (spec_draft_len 0), and 0-valued satellites fall back to the
    # engine defaults via None
    return PagedGenerationEngine(
        model_cfg,
        scheduler="refill",
        max_concurrent_rows=max(min(rows, 64), 1),
        spec_draft=plan.spec_draft_len or spec_draft,
        spec_ngram=plan.spec_ngram_k or None,
        spec_drafter=plan.spec_drafter,
        spec_verify=plan.spec_verify,
        **paged_kw,
        **common,
    )


def _perturbed_drafter(lora, *, rel: float = 0.05, seed: int = 0):
    """A deterministically noise-perturbed copy of ``lora`` to stand in as
    the self-drafter's 'previous version' during a microbench.

    With nothing pushed through the mailbox the self-drafter would fall
    back to the TARGET adapter itself — q == p, acceptance ≡ 1.0, and every
    'self' candidate would be scored at the best case it can ever achieve
    (systematically optimistic vs the production regime, where the drafter
    is a genuinely superseded version). A small relative perturbation
    (``rel`` × per-leaf RMS, seeded) keeps the drafter NEAR-on-policy — the
    regime PipelineRL argues production actually sits in — while pushing
    the measured acceptance off the trivial upper bound. The measurement is
    still a proxy (the real update delta is unknowable offline); bench A/B
    on the live run remains the ground truth for drafter choice."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(lora)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, leaf in enumerate(leaves):
        leaf = jnp.asarray(leaf)
        rms = float(
            jnp.sqrt(jnp.mean(jnp.square(leaf.astype(jnp.float32))))
        )
        if rms == 0.0:
            # zero-init leaves (LoRA B matrices) are exactly the ones whose
            # production updates make the drafter differ — perturb them at
            # the init-scheme's own fan scale instead of not at all
            rms = leaf.shape[-1] ** -0.5
        noise = jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape, jnp.float32
        )
        out.append(
            (leaf.astype(jnp.float32) + rel * rms * noise).astype(leaf.dtype)
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def tune_geometry(
    model_cfg,
    params,
    lora,
    candidates: Sequence[ExecutionPlan],
    *,
    n_prompts: int,
    n_candidates: int,
    max_prompt_tokens: int,
    max_new_tokens: int,
    kv_quant: str = "none",
    warmup: int = 1,
    repeats: int = 2,
    hbm_bytes: int | None = None,
    pad_id: int = 0,
) -> list[CandidateResult]:
    """Measure every candidate at one geometry; returns results in input
    order (``best_result`` picks the winner)."""
    import jax

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.budget import tree_bytes

    rows = n_prompts * n_candidates
    # per-base-format param trees (ISSUE 15), quantized once per format the
    # candidate space names: a base_quant candidate is measured over the
    # int8/int4 containers it describes (the fused dequant-matmul kernel
    # where enabled), and its memory guard sees the SHRUNK resident bytes
    # — the capacity win is part of what makes a quantized plan feasible
    _params_by_quant: dict[str, object] = {"none": params}

    def _params_for(plan: ExecutionPlan):
        bq = plan.base_quant or "none"
        if bq not in _params_by_quant:
            from distrl_llm_tpu.ops.quant import (
                default_group_size, quant_bits_for, quantize_params,
            )

            bits = quant_bits_for(bq)
            _params_by_quant[bq] = quantize_params(
                params, bits=bits, group_size=default_group_size(bits)
            )
        return _params_by_quant[bq]

    rng = np.random.default_rng(0)
    prompts = rng.integers(
        1, min(model_cfg.vocab_size, 50_000),
        size=(n_prompts, max_prompt_tokens),
    ).astype(np.int32)
    pmask = np.ones_like(prompts)

    results: list[CandidateResult] = []
    for plan in candidates:
        cand_params = _params_for(plan)
        cand_kv = plan.kv_format if plan.kv_format is not None else kv_quant
        reason = plan_memory_guard(
            model_cfg, plan, rows=rows, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens,
            param_bytes=tree_bytes(cand_params),
            kv_quant=cand_kv, hbm_bytes=hbm_bytes,
        )
        if reason is not None:
            log.warning("autotune: %s infeasible: %s", plan.to_dict(), reason)
            results.append(CandidateResult(plan, False, 0.0, 0.0, 0.0, 0, reason))
            continue
        if plan.spec_drafter == "self" and lora is None:
            # the self-drafter IS the previous adapter version — with no
            # adapter there is nothing to draft from (mirrors the config
            # validation: spec_drafter='self' requires a LoRA run)
            results.append(CandidateResult(
                plan, False, 0.0, 0.0, 0.0, 0,
                "spec_drafter='self' requires a LoRA adapter to measure",
            ))
            continue
        try:
            engine = build_engine_for_plan(
                model_cfg, plan,
                max_prompt_tokens=max_prompt_tokens,
                max_new_tokens=max_new_tokens, rows=rows,
                pad_id=pad_id, kv_quant=kv_quant,
            )
            if plan.spec_drafter == "self":
                # seed the mailbox's superseded-adapter slot: without this
                # the drafter falls back to the target adapter (q == p,
                # acceptance ≡ 1.0) and 'self' scores its own unreachable
                # best case — see _perturbed_drafter
                engine._prev_lora = _perturbed_drafter(lora)
                engine._prev_lora_version = -1
            sampling = SamplingConfig(
                max_tokens=max_new_tokens, temperature=1.2, top_p=0.95,
                n=n_candidates, top_p_impl=plan.top_p_impl,
            )

            def run(seed: int) -> int:
                res = engine.generate(
                    cand_params, lora, prompts, pmask, sampling,
                    jax.random.PRNGKey(seed),
                )
                return int(res.lengths.sum())

            warmup_s, steady_s, tokens = time_candidate(
                run, warmup=warmup, repeats=repeats,
            )
            note = ""
            if plan.scan_chunk > 1 and engine.scan_chunk_active is False:
                # honesty flag: the measurement is real but it timed the
                # host-dispatched fallback, not the chunked program
                note = "scan_chunk fell back to host dispatch"
            results.append(CandidateResult(
                plan, True, tokens / steady_s if steady_s > 0 else 0.0,
                warmup_s, steady_s, tokens, note,
            ))
        except Exception as e:  # noqa: BLE001 — infeasible, not fatal
            log.warning(
                "autotune: %s failed (%s: %s) — scored infeasible",
                plan.to_dict(), type(e).__name__, e,
            )
            results.append(CandidateResult(
                plan, False, 0.0, 0.0, 0.0, 0, f"{type(e).__name__}: {e}",
            ))
    return results


def best_result(results: Sequence[CandidateResult]) -> CandidateResult | None:
    feasible = [r for r in results if r.feasible and r.tok_s > 0]
    if not feasible:
        return None
    return max(feasible, key=lambda r: r.tok_s)
