"""Traffic kind ``rollout``: the rollout engine alone, round after round.

A unit of work is one whole round, ``engine.generate`` over ``batch_size``
seeded prompts x ``num_candidates`` candidates, which returns host arrays (the
clock stops on a fetch). The learner does nothing. The first round is warm-up
and is what the correctness check reads.

Traffic parameters: ``train_config`` (the ``TrainConfig`` fields that size and
choose the engine), ``prompt_tokens`` [min, max], ``eos`` or ``eos_rate``
(``assembly.eos_ids``), ``check``, ``trace_units``.
"""

from __future__ import annotations

import numpy as np

from perfbench import assembly, correct, harness, spec, weights


def run(ctx: harness.RunContext) -> harness.RunResult:
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.models import init_lora_params
    from distrl_llm_tpu.models.lora import lora_scale

    cell, traffic = ctx.cell, ctx.cell.traffic
    model_cfg = assembly.model_config(cell.config)
    dtype = cell.config["torch_dtype"]
    config = assembly.train_config(traffic, ctx.seed, dtype)
    pad_id = 0
    eos = assembly.eos_ids(traffic, model_cfg.vocab_size, ctx.seed, real_eos=3)
    engine = assembly.build_engine(config, model_cfg, eos=eos, pad_id=pad_id)
    params = weights.make_base_params(
        model_cfg, dtype, ctx.seed, rules=weights.load_rules(cell.paths, cell.config))
    # an adapter as the trainer holds it (float32 factors over the bf16 base),
    # with b drawn from the seed so that the adapter's term is not zero
    lora = weights.randomize_lora_b(
        init_lora_params(jax.random.PRNGKey(ctx.seed + 1), model_cfg,
                         config.max_lora_rank, dtype=jnp.float32),
        ctx.seed,
    )
    scale = lora_scale(config.max_lora_rank, config.lora_alpha)
    sampling = config.train_sampling()
    lo, hi = traffic["prompt_tokens"]
    key = jax.random.PRNGKey(ctx.seed + 2)
    harness.emit(
        "system", engine=type(engine).__name__, scheduler=getattr(engine, "cb_mode", None),
        slots=config.max_concurrent_sequences, rows=config.batch_size * config.num_candidates,
        eos_ids=len(eos), plan_source=engine.resolved_plan.source,
        sampling={"temperature": sampling.temperature, "top_p": sampling.top_p,
                  "n": sampling.n, "max_tokens": sampling.max_tokens},
    )

    def make_round(i: int):
        rng = np.random.default_rng([ctx.seed, i])
        return assembly.seeded_prompts(
            rng, rows=config.batch_size, width=config.max_prompt_tokens,
            min_len=lo, max_len=hi, vocab_size=model_cfg.vocab_size, pad_id=pad_id,
        )

    rounds_done = [0]

    def one_round(_i: int = 0, keep_result: bool = False) -> dict:
        i = rounds_done[0]
        rounds_done[0] += 1
        ids, mask = make_round(i)
        with harness.layer_span(ctx, "engine.generate"):
            result = engine.generate(
                params, lora, ids, mask, sampling, jax.random.fold_in(key, i)
            )
        lengths = np.asarray(result.lengths)
        counted = {
            "tokens": int(lengths.sum()),
            "steps_dispatched": result.steps_dispatched,
            "alive_slot_steps": result.alive_slot_steps,
            # a prompt's rows are consecutive: group_size of them share it
            "prompt_lens": np.repeat(mask.sum(-1), sampling.n).tolist(),
            "group_size": sampling.n,
            "gen_lens": lengths.reshape(-1).tolist(),
            "slots": min(config.max_concurrent_sequences or lengths.size, lengths.size),
        }
        if keep_result:
            counted["_result"] = (ids, mask, result)
        return counted

    # warm-up: one whole round (the round's row counts, caps and slot count
    # are static arguments of its programs, so nothing shorter compiles them)
    warm = one_round(keep_result=True)
    ids, mask, result = warm.pop("_result")
    reference = spec.load_module(cell.paths, "", cell.config["reference"])
    check = correct.rollout_rows_check(
        reference, model_cfg, params, lora, scale, ids, mask, result,
        seed=ctx.seed, width=config.max_prompt_tokens + config.max_new_tokens,
        check=traffic.get("check"),
    )
    harness.emit("check", **check)
    del result

    ctx.begin_window()
    units = ctx.measure_units(one_round, ctx.untraced_seconds)
    traced = ctx.trace_units(one_round, int(traffic.get("trace_units", 1)))

    ctx.end_window()

    every = units + traced
    tokens = sum(u["tokens"] for u in units)
    return harness.RunResult(
        correct=bool(check["ok"]),
        attempted=len(every),
        failed=sum(1 for u in every if u["tokens"] <= 0),
        end_to_end={"rollout_tok_s": harness.rate(tokens, units) / cell.chips},
        observed={
            "units": units, "traced_units": traced, "warmup_unit": warm,
            "rollout": {
                "lora_rank": config.max_lora_rank, "page_size": getattr(engine, "page_size", 0),
                "kv_bytes": 2, "weight_bytes": jnp.dtype(dtype).itemsize,
            },
        },
        check=check,
    )
