"""Traffic kind ``learner``: the train step alone, update after update.

A unit of work is one whole policy update: ``rows`` rows of ``prompt_tokens``
+ ``answer_tokens`` seeded token ids through the train step exactly as a
``Trainer`` built for the cell's ``TrainConfig`` holds it (``trainer.train_step``
and ``trainer.optimizer``, so a changed default reaches the cell), ended by
``float(loss)``. Every token is real and every coefficient is drawn non-zero
from the seed, so no micro-batch is skipped. The rollout engine is never built.

The correctness check is one update of that same train step, on a batch of the
measured shape whose only real rows are ``check_rows`` short ones, against the
reference's loss and gradient on those rows.

Traffic parameters: ``train_config``, ``rows``, ``prompt_tokens``,
``answer_tokens``, ``check_rows`` [rows, prompt tokens, answer tokens],
``distinct_batches``, ``trace_units``, ``check`` (the cell's own tolerances).
"""

from __future__ import annotations

import math

import numpy as np

from perfbench import assembly, correct, harness, spec, weights


def _batch(UpdateBatch, rng, *, rows, prompt_w, answer_w, vocab, real_rows=None,
           real_prompt=None, real_answer=None):
    """A seeded ``UpdateBatch`` of the program's layout: prompts left-padded,
    answers right-padded. By default every row and token is real; otherwise
    only the first ``real_rows`` rows are, with ``real_prompt`` +
    ``real_answer`` real tokens each."""
    import jax.numpy as jnp

    real_rows = rows if real_rows is None else real_rows
    real_prompt = prompt_w if real_prompt is None else real_prompt
    real_answer = answer_w if real_answer is None else real_answer
    prompt_ids = np.zeros((rows, prompt_w), np.int32)
    prompt_mask = np.zeros((rows, prompt_w), np.int32)
    answer_ids = np.zeros((rows, answer_w), np.int32)
    answer_mask = np.zeros((rows, answer_w), np.int32)
    prompt_ids[:real_rows, prompt_w - real_prompt:] = rng.integers(
        0, vocab, size=(real_rows, real_prompt))
    prompt_mask[:real_rows, prompt_w - real_prompt:] = 1
    answer_ids[:real_rows, :real_answer] = rng.integers(
        0, vocab, size=(real_rows, real_answer))
    answer_mask[:real_rows, :real_answer] = 1
    # reward minus baseline: magnitudes in [0.25, 1], either sign, never zero
    coeffs = np.zeros(rows, np.float32)
    coeffs[:real_rows] = rng.uniform(0.25, 1.0, real_rows) * rng.choice(
        [-1.0, 1.0], real_rows)
    sample_mask = np.zeros(rows, np.float32)
    sample_mask[:real_rows] = 1.0
    host = dict(prompt_ids=prompt_ids, prompt_mask=prompt_mask,
                answer_ids=answer_ids, answer_mask=answer_mask, coeffs=coeffs,
                sample_mask=sample_mask)
    return UpdateBatch(**{k: jnp.asarray(v) for k, v in host.items()}), host


def run(ctx: harness.RunContext) -> harness.RunResult:
    import jax

    from distrl_llm_tpu.learner.train_step import UpdateBatch
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    cell, traffic = ctx.cell, ctx.cell.traffic
    model_cfg = assembly.model_config(cell.config)
    dtype = cell.config["torch_dtype"]
    rows, prompt_w, answer_w = (
        int(traffic["rows"]), int(traffic["prompt_tokens"]), int(traffic["answer_tokens"])
    )
    config = assembly.train_config(traffic, ctx.seed, dtype)
    if (config.max_prompt_tokens, config.max_new_tokens) != (prompt_w, answer_w):
        raise spec.SpecError("learner traffic: train_config's caps must equal the row shape")
    params = weights.make_base_params(
        model_cfg, dtype, ctx.seed, rules=weights.load_rules(cell.paths, cell.config))
    # the trainer is built for its train step, optimizer, adapter and optimizer
    # state only: no engine, and its two one-problem datasets are never read
    nothing = {"problem": ["-"], "solution": ["-"]}
    trainer = Trainer(
        nothing, nothing, lambda completions, solutions: np.zeros((len(completions), 2)),
        config, tokenizer=CharTokenizer(model_cfg.vocab_size), engine=None,
        base_params=params, model_cfg=model_cfg,
    )
    train_step = trainer.train_step
    harness.emit(
        "system", learner=config.learner, micro_batch=config.train_batch_size,
        lora_rank=config.max_lora_rank, attn_impl=config.attn_impl,
        logprob_chunk=config.logprob_chunk, optimizer_8bit=config.optimizer_8bit,
        lr=config.lr, rows=rows, row_tokens=prompt_w + answer_w,
    )

    # ---- warm-up and correctness: ONE update of the measured program
    check_rows, check_prompt, check_answer = traffic["check_rows"]
    check_batch, host = _batch(
        UpdateBatch, np.random.default_rng([ctx.seed, 10_000]), rows=rows,
        prompt_w=prompt_w, answer_w=answer_w, vocab=model_cfg.vocab_size,
        real_rows=check_rows, real_prompt=check_prompt, real_answer=check_answer,
    )
    before = weights.randomize_lora_b(trainer.lora, ctx.seed)
    before_host = jax.device_get(before)
    after, _, loss = train_step(
        before, trainer.optimizer.init(before), params, check_batch, None
    )
    loss = float(loss)
    r = slice(0, check_rows)
    ids = np.concatenate([host["prompt_ids"][r, prompt_w - check_prompt:],
                          host["answer_ids"][r, :check_answer]], axis=1)
    answer_cols = np.concatenate([np.zeros((check_rows, check_prompt), np.int32),
                                  np.ones((check_rows, check_answer), np.int32)], axis=1)
    reference = spec.load_module(cell.paths, "", cell.config["reference"])
    check = correct.learner_update_check(
        reference, model_cfg, params, before_host, jax.device_get(after),
        trainer.scale, loss, ids, np.ones_like(ids), answer_cols,
        host["coeffs"][r], check=traffic.get("check"),
    )
    harness.emit("check", **check)
    del after, before, before_host

    n_batches = int(traffic.get("distinct_batches", 4))
    batches = [
        _batch(UpdateBatch, np.random.default_rng([ctx.seed, i]), rows=rows,
               prompt_w=prompt_w, answer_w=answer_w, vocab=model_cfg.vocab_size)[0]
        for i in range(n_batches)
    ]
    state = [trainer.lora, trainer.opt_state]
    done = [0]

    def one_update(_i: int = 0) -> dict:
        batch = batches[done[0] % n_batches]
        done[0] += 1
        with harness.layer_span(ctx, "learner.train_step"):
            state[0], state[1], loss = train_step(state[0], state[1], params, batch, None)
            loss = float(loss)
        return {"tokens": rows * (prompt_w + answer_w), "loss": loss}

    # one more warm-up update, from the trainer's own initial state: arrays the
    # constructors made and arrays a jitted step returned can key two programs
    one_update()
    ctx.begin_window()
    units = ctx.measure_units(one_update, ctx.untraced_seconds)
    traced = ctx.trace_units(one_update, int(traffic.get("trace_units", 1)))
    ctx.end_window()

    every = units + traced
    tokens = sum(u["tokens"] for u in units)
    return harness.RunResult(
        correct=bool(check["ok"]),
        attempted=len(every),
        failed=sum(1 for u in every if not math.isfinite(u["loss"])),
        end_to_end={"learner_tok_s": harness.rate(tokens, units) / cell.chips},
        observed={
            "units": units, "traced_units": traced,
            "learner": {"seq_len": prompt_w + answer_w, "answer_len": answer_w,
                        "lora_rank": config.max_lora_rank},
        },
        check=check,
    )
