"""Traffic kind ``rl_step``: the whole loop, through ``Trainer.train()`` itself.

A unit of work is one RL step: rollout -> reward -> shaping -> update -> weight
push. The trainer is assembled as ``train_distributed.run_smoke`` assembles it
(a copy: seeded arithmetic problems through the chat template, a byte-level
tokenizer over the model's vocabulary, seeded weights placed per role submesh,
the engine ``TrainConfig`` names) and runs its own ``train()``: the initial
evaluation and the first ``warm_steps`` steps are warm-up. The harness's sink
stamps every step record; once ``--seconds`` have passed it raises a private
exception on a step boundary, which ``run`` catches after ``train()``'s own
clean-up. The reward is the smoke's dense hash of the completion: the math
reward scores a random-weight policy 0 and every update would be skipped.

Held beside the logprob check: step k samples under the adapter k-1 updates
produced, every update changes the adapter, every loss is finite; on several
chips the two roles' devices are disjoint, each role's arrays sit on its own
devices, and the pushed adapter equals the learner's value for value.

Traffic parameters: ``train_config``, ``warm_steps``, ``trace_units``.
"""

from __future__ import annotations

import math
import time
import zlib

import numpy as np

from perfbench import assembly, correct, harness, spec, weights

STEPS_PER_EPISODE = 32


class _WindowOver(Exception):
    """Raised by the sink on a step boundary once the window has passed."""


def problems(n: int, seed: int, max_chars: int) -> dict[str, list[str]]:
    """``n`` seeded arithmetic problems of varied length (copy of
    ``train_distributed.smoke_problems``)."""
    rng = np.random.default_rng(seed)
    out, solutions = [], []
    for _ in range(n):
        terms = rng.integers(1, 1000, size=int(rng.integers(2, 12)))
        text = "What is " + " + ".join(str(t) for t in terms) + "?"
        out.append(text[:max_chars])
        solutions.append(str(int(terms.sum())))
    return {"problem": out, "solution": solutions}


def hash_reward(completions, solutions) -> np.ndarray:
    """(N, 2) rewards with a dense, deterministic accuracy column: a hash of
    the completion's text (copy of ``train_distributed.dense_smoke_reward``)."""
    acc = [(zlib.crc32(c.encode("utf-8")) % 8) / 8.0 for c in completions]
    return np.column_stack((np.zeros(len(acc)), np.asarray(acc)))


class StepSink:
    """The trainer's metrics sink, and the run's clock: one record a step,
    stamped on arrival; the window opens after ``warm_steps`` records and the
    run ends, by ``_WindowOver``, on the first step boundary past it."""

    def __init__(self, ctx: harness.RunContext, warm_steps: int, trace_units: int,
                 on_warm):
        self.ctx, self.warm_steps, self.trace_units = ctx, warm_steps, trace_units
        self.on_warm = on_warm
        self.steps: list[dict] = []  # every train step, warm-up included
        self.tracing = False

    def log(self, metrics, step: int) -> None:
        arrived = time.perf_counter()
        if "loss" not in metrics:
            return  # an evaluation record
        record = {k: v for k, v in metrics.items() if isinstance(v, (int, float))}
        # the step ran from the end of the previous record's handling to now
        record["step_s"] = arrived - self.steps[-1]["t_end"] if self.steps else None
        record["traced"] = self.tracing
        self.steps.append(record)
        try:
            self._advance(arrived)
        finally:
            record["t_end"] = time.perf_counter()

    def _advance(self, arrived: float) -> None:
        ctx, n = self.ctx, len(self.steps)
        if n < self.warm_steps:
            return
        if n == self.warm_steps:
            self.on_warm()
            ctx.begin_window()
            return
        if self.tracing:
            traced = sum(1 for s in self.steps if s["traced"])
            if traced >= self.trace_units:
                ctx.tracer.stop()
                raise _WindowOver
            return
        if arrived - ctx.window_start >= ctx.untraced_seconds:
            if ctx.tracer is None:
                raise _WindowOver
            ctx.tracer.start()
            self.tracing = True

    def finish(self) -> None:
        pass

    def measured(self, traced: bool) -> list[dict]:
        return [s for s in self.steps[self.warm_steps:] if s["traced"] == traced]


def _device_ids(tree) -> set[int]:
    import jax

    return {d.id for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()}


def run(ctx: harness.RunContext) -> harness.RunResult:
    import jax

    from distrl_llm_tpu.data import process_dataset
    from distrl_llm_tpu.parallel.mesh import build_role_meshes
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    cell, traffic = ctx.cell, ctx.cell.traffic
    model_cfg = assembly.model_config(cell.config)
    dtype = cell.config["torch_dtype"]
    config = assembly.train_config(traffic, ctx.seed, dtype)
    warm_steps = int(traffic.get("warm_steps", 2))
    tokenizer = CharTokenizer(model_cfg.vocab_size)
    # the chat template costs ~75 characters of a byte-tokenized prompt
    train = process_dataset(tokenizer, problems(
        STEPS_PER_EPISODE * config.batch_size, ctx.seed,
        max_chars=max(8, config.max_prompt_tokens - 80),
    ))
    test = {k: v[: config.batch_size] for k, v in train.items()}
    # each role holds the frozen base on its own submesh, as
    # Trainer.from_pretrained places a checkpoint; timeshared roles alias one copy
    meshes = build_role_meshes(config.mesh, ctx.devices)
    rules = weights.load_rules(cell.paths, cell.config)
    base_rollout = weights.make_base_params(model_cfg, dtype, ctx.seed, meshes.rollout, rules)
    base_learner = (
        base_rollout if meshes.timeshared
        else weights.make_base_params(model_cfg, dtype, ctx.seed, meshes.learner, rules)
    )
    engine = assembly.build_engine(
        config, model_cfg, eos=[tokenizer.eos_token_id], pad_id=tokenizer.pad_token_id
    )
    harness.emit(
        "system", engine=type(engine).__name__, timeshared=meshes.timeshared,
        rollout_devices=[d.id for d in meshes.rollout.devices.flat],
        learner_devices=[d.id for d in meshes.learner.devices.flat],
        rows=config.batch_size * config.num_candidates, learner=config.learner,
        micro_batch=config.train_batch_size, lora_rank=config.max_lora_rank,
        plan_source=engine.resolved_plan.source,
    )

    # ---- every engine call, observed from outside
    rounds: list[dict] = []
    last_round: dict = {}
    checksum = jax.jit(lambda tree: sum(
        abs(x).sum().astype("float32") for x in jax.tree_util.tree_leaves(tree)
    ))
    engine_generate = engine.generate
    state = {"trainer": None, "warm": False}

    def observed_generate(params, lora, prompt_ids, prompt_mask, sampling, rng):
        trainer = state["trainer"]
        entry = {
            "policy_version": trainer._rollout_weight_version,
            "adapter_checksum": checksum(lora),  # fetched after the run
            "t0": time.perf_counter(),
        }
        if not state["warm"]:
            # the adapter this round samples under, before a later update
            # donates its buffers; only warm-up rounds are kept for the check
            last_round.update(lora=jax.device_get(lora), ids=prompt_ids,
                              mask=prompt_mask)
        with harness.layer_span(ctx, "engine.generate"):
            result = engine_generate(params, lora, prompt_ids, prompt_mask, sampling, rng)
        entry["t1"] = time.perf_counter()
        entry["tokens"] = int(np.asarray(result.lengths).sum())
        entry["steps_dispatched"] = result.steps_dispatched
        if not state["warm"]:
            last_round["result"] = result
        rounds.append(entry)
        return result

    engine.generate = observed_generate
    check: dict = {"ok": False, "why": "the warm-up never finished"}

    def on_warm() -> None:
        """End of warm-up, inside the sink: the correctness check, on the last
        warm-up round, then the window opens."""
        state["warm"] = True
        trainer = state["trainer"]
        reference = spec.load_module(cell.paths, "", cell.config["reference"])
        check.clear()
        check.update(correct.rollout_rows_check(
            reference, model_cfg, trainer.base_params, last_round["lora"],
            trainer.scale, last_round["ids"], last_round["mask"],
            last_round["result"], seed=ctx.seed,
            width=config.max_prompt_tokens + config.max_new_tokens,
            check=traffic.get("check"),
        ))
        harness.emit("check", **check)
        last_round.clear()

    sink = StepSink(ctx, warm_steps, int(traffic.get("trace_units", 1)), on_warm)
    trainer = Trainer(
        train, test, hash_reward, config, tokenizer=tokenizer, engine=engine,
        base_params=base_rollout, base_params_learner=base_learner,
        model_cfg=model_cfg, meshes=meshes, sink=sink,
    )
    state["trainer"] = trainer
    try:
        trainer.train()
        raise RuntimeError("train() ran out of episodes before the window passed")
    except _WindowOver:
        ctx.end_window()
    finally:
        if ctx.tracer is not None and sink.tracing and ctx.tracer.window_wall_ns[1] == 0:
            ctx.tracer.stop()  # a step failed under the profiler

    # ---- the loop's invariants (chip_smoke.py's), over every train round
    invariants: dict = {}
    train_rounds = rounds[-len(sink.steps):]  # rounds[0] is the initial evaluation
    versions = [r["policy_version"] for r in train_rounds]
    invariants["versions_in_step"] = versions == list(range(len(versions)))
    sums = [float(r["adapter_checksum"]) for r in train_rounds] + [
        float(checksum(trainer.lora))
    ]
    invariants["every_update_moved_adapter"] = all(a != b for a, b in zip(sums, sums[1:]))
    losses = [s["loss"] for s in sink.steps]
    invariants["losses_finite"] = all(math.isfinite(x) for x in losses)
    if not meshes.timeshared:
        actors = {d.id for d in meshes.rollout.devices.flat}
        learners = {d.id for d in meshes.learner.devices.flat}
        invariants["roles_disjoint"] = not actors & learners
        pool = getattr(engine, "last_pool_stats", None) or {}
        invariants["rollout_arrays_on_actors"] = (
            _device_ids(trainer.base_params) == actors
            and _device_ids(trainer._lora_rollout) == actors
            and set(pool.get("kv_devices", actors)) == actors
        )
        invariants["learner_arrays_on_learners"] = (
            _device_ids(trainer.base_params_learner) == learners
            and _device_ids(trainer.lora) == learners
            and _device_ids(trainer.opt_state) == learners
        )
        pushed, held = jax.device_get(trainer._lora_rollout), jax.device_get(trainer.lora)
        invariants["pushed_adapter_equals_learners"] = all(
            np.array_equal(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(pushed), jax.tree_util.tree_leaves(held))
        ) and trainer._rollout_weight_version == trainer.weight_version
    harness.emit("invariants", **invariants, steps=len(sink.steps),
                 first_losses=losses[:3])

    steps, traced = sink.measured(traced=False), sink.measured(traced=True)
    every = steps + traced
    for s, r in zip(sink.steps, train_rounds):
        s["round_tokens"] = r["tokens"]
        s["round_steps_dispatched"] = r["steps_dispatched"]
    return harness.RunResult(
        correct=bool(check.get("ok")) and all(invariants.values()),
        attempted=len(every),
        failed=sum(1 for s in every if not math.isfinite(s["loss"])),
        end_to_end={"step_s": float(np.median([s["step_s"] for s in steps]))},
        observed={"units": steps, "traced_units": traced},
        check={**check, "invariants": invariants},
    )
