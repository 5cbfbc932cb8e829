#!/usr/bin/env python
"""Rollout-regime smoke check (wired into tools/run_all_checks.sh).

The acceptance contract for the async rollout subsystem
(distrl_llm_tpu/rollout), end to end on a CPU host: the SAME tiny training
problem through all three ``--rollout_mode`` regimes with a real TINY
generation engine —

* ``sync``       — finite losses, zero allowed weight lag;
* ``pipelined``  — finite losses, same step count as sync (the one-step
                   overlap changes when batches generate, never which ones);
* ``async``      — finite losses, nonzero trajectory-buffer telemetry
                   (occupancy gauge samples + staleness histogram in the
                   trace), drop accounting consistent with the buffer
                   counters, and a trace whose ``tools/trace_report.py``
                   report contains the rollout section.

Exits nonzero on any missing piece.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_mode(mode: str, trace_dir: str | None = None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    clip = 0.2 if mode == "async" else 0.0
    config = TrainConfig(
        model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=16, max_new_tokens=12,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null",
        max_lora_rank=4, lora_alpha=8, lr=1e-3,
        rollout_mode=mode, max_staleness=2, clip_ratio=clip,
        trace_dir=trace_dir,
    )
    tok = CharTokenizer(TINY.vocab_size)
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}

    def dense_reward(completions, solutions):
        return np.asarray(
            [(0.0, 0.1 + (len(c) % 5) / 10.0) for c in completions],
            np.float32,
        )

    engine = GenerationEngine(
        TINY, max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jnp.float32,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        capture_logprobs=clip > 0.0,
        autotune=False,  # this gate checks rollout modes, not plans
    )
    sink = MemorySink()
    trainer = Trainer(
        train, {k: v[:4] for k, v in train.items()}, dense_reward, config,
        tokenizer=tok, engine=engine, base_params=init_params(
            jax.random.PRNGKey(0), TINY
        ), model_cfg=TINY, sink=sink,
    )
    trainer.train()
    steps = [m for _, m in sink.records if "loss" in m]
    assert steps, f"{mode}: no train steps ran"
    assert all(np.isfinite(m["loss"]) for m in steps), (
        f"{mode}: non-finite loss"
    )
    assert all(m["rollout_mode"] == mode for m in steps), (
        f"{mode}: train-curve records mislabeled"
    )
    return trainer, steps


def main() -> int:
    _, sync_steps = run_mode("sync")
    _, pipe_steps = run_mode("pipelined")
    assert len(pipe_steps) == len(sync_steps), (
        f"pipelined processed {len(pipe_steps)} batches, sync "
        f"{len(sync_steps)} — the overlap must not change the batch stream"
    )
    assert {m["max_staleness"] for m in sync_steps} == {0}
    assert {m["max_staleness"] for m in pipe_steps} == {1}

    tmp = tempfile.mkdtemp(prefix="distrl_rollout_")
    trainer, async_steps = run_mode("async", trace_dir=tmp)
    assert {m["max_staleness"] for m in async_steps} == {2}
    stats = trainer._rollout_buffer.stats()
    assert stats["total_put"] > 0 and stats["total_got"] > 0, stats
    # drop accounting: everything produced is either consumed, dropped, or
    # still queued — nothing vanishes silently
    policy = trainer._staleness_policy
    assert (
        stats["total_put"]
        == stats["total_got"] + stats["dropped_stale"]
        + stats["dropped_capacity"] + stats["occupancy"]
    ), stats
    assert policy.admitted + policy.dropped == stats["total_got"], (
        policy.admitted, policy.dropped, stats
    )
    assert all("rollout_dropped_stale" in m for m in async_steps)

    path = os.path.join(tmp, "trace.json")
    assert os.path.exists(path), f"no trace written at {path}"
    with open(path) as f:
        doc = json.load(f)
    counters = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "C"}
    assert "rollout/buffer_occupancy" in counters, counters
    assert "rollout/staleness" in counters, counters
    spans = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "rollout/produce" in spans, spans

    report = os.path.join(os.path.dirname(__file__), "trace_report.py")
    out = subprocess.run(
        [sys.executable, report, path], capture_output=True, text=True
    )
    assert out.returncode == 0, f"trace_report.py exited {out.returncode}"
    assert "rollout:" in out.stdout, (
        f"trace_report has no rollout section:\n{out.stdout}"
    )
    assert "buffer occupancy" in out.stdout and "staleness" in out.stdout
    print(f"ROLLOUT SMOKE OK — sync {len(sync_steps)} / pipelined "
          f"{len(pipe_steps)} / async {len(async_steps)} steps; "
          f"buffer {stats}; trace at {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
