#!/usr/bin/env python
"""Telemetry smoke check (wired into tools/run_all_checks.sh).

The acceptance contract for the telemetry subsystem, end to end on a CPU
host: a 2-step train run with tracing on — real TINY generation engine, so
engine prefill/decode spans exist — plus one multi-process control-plane
round against a traced worker subprocess, must produce ONE Chrome-trace
JSON containing:

* driver spans (driver/generation, driver/reward, driver/update),
* engine spans (engine/prefill, engine/decode),
* at least one span on a per-worker track (worker/rollout_rewards shipped
  back over the control plane), when the native transport is available;

and ``tools/trace_report.py`` must exit 0 on that file, printing per-phase
totals and tok/s. Exits nonzero on any missing piece.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_worker_round() -> bool:
    """One control-plane round against a traced worker subprocess; its spans
    merge into this process's (the driver's) tracer. Returns False when the
    native transport isn't available (no g++)."""
    from distrl_llm_tpu.native.build import native_available

    if not native_available():
        return False
    proc = subprocess.Popen(
        [sys.executable, "-m", "distrl_llm_tpu.distributed.worker_main",
         "--port", "0", "--trace"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT "), line
        from distrl_llm_tpu.distributed import DriverClient

        driver = DriverClient([("127.0.0.1", int(line.split()[1]))])
        batch = {"answers": [["<answer>4</answer>", "wrong"]],
                 "solution": [["4", "4"]]}
        driver.dispatch_objects([("rollout_rewards", batch)],
                                timeout_ms=30_000)
        driver.shutdown()
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
    return True


def main() -> int:
    import jax
    import numpy as np

    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.rewards import reward_function
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    tmp = tempfile.mkdtemp(prefix="distrl_trace_")
    config = TrainConfig(
        model="tiny", episodes=1, batch_size=2, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=16, max_new_tokens=12,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null",
        max_lora_rank=4, lora_alpha=8, lr=1e-3,
        trace_dir=tmp,
    )
    tok = CharTokenizer(TINY.vocab_size)
    problems = [f"q {c}" for c in "abcd"]  # batch 2 → exactly 2 train steps
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    engine = GenerationEngine(
        TINY, max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jax.numpy.float32,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        # this gate checks telemetry, not plans: pin the static defaults so
        # a populated user plan DB can't make the CI stage nondeterministic
        autotune=False,
    )
    sink = MemorySink()
    trainer = Trainer(
        train, {k: v[:2] for k, v in train.items()}, reward_function, config,
        tokenizer=tok, engine=engine, base_params=init_params(
            jax.random.PRNGKey(0), TINY
        ), model_cfg=TINY, sink=sink,
    )
    # the worker round runs BEFORE train() so its merged spans land in the
    # trace train() exports at shutdown
    have_worker = run_worker_round()
    trainer.train()

    steps = [m for _, m in sink.records if "loss" in m]
    assert len(steps) == 2, f"expected 2 train steps, got {len(steps)}"
    assert all(np.isfinite(m["loss"]) for m in steps)
    assert all("engine/decode_tok_s" in m for m in steps), (
        "engine round stats did not reach the sink"
    )
    if have_worker:
        assert any(
            k.startswith("cp/rpc_dispatch_ms") for m in steps for k in m
        ), "control-plane RPC histogram did not reach the sink"

    path = os.path.join(tmp, "trace.json")
    assert os.path.exists(path), f"no trace written at {path}"
    with open(path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    for want in ("driver/generation", "driver/reward", "driver/update",
                 "engine/prefill", "engine/decode"):
        assert want in names, f"span {want!r} missing from trace ({names})"
    if have_worker:
        worker_pids = {
            e["pid"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and "worker" in e.get("args", {}).get("name", "")
        }
        assert worker_pids, "no worker track in the merged trace"
        assert any(
            e.get("ph") == "X" and e.get("pid") in worker_pids
            for e in doc["traceEvents"]
        ), "worker track has no spans"

    report = os.path.join(os.path.dirname(__file__), "trace_report.py")
    rc = subprocess.call([sys.executable, report, path])
    assert rc == 0, f"trace_report.py exited {rc}"
    print(f"TELEMETRY SMOKE OK — trace at {path}"
          + ("" if have_worker else " (no g++: worker track skipped)"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
