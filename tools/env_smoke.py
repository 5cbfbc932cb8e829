#!/usr/bin/env python
"""Pluggable-environment smoke check (ISSUE 17; wired into
tools/run_all_checks.sh).

The CI-side acceptance gate for the multi-turn agentic rollout subsystem,
runnable on a CPU host:

1. **Tool round-trip** — the code env's ``<tool>`` block really executes in
   the sandbox and its output round-trips through the driver: tokens →
   decode → sandbox → ``<output>`` observation → tokens, with the
   observation span loss-masked (env tokens never train), the policy spans
   unmasked, and the terminal ``<answer>`` scored for accuracy.
2. **End-to-end training** — both genuinely multi-turn envs (code,
   verifier) train through the REAL trainer + paged refill engine in sync
   AND async mode: finite losses, per-round ``env/*`` metrics on the sink,
   and — the KV-residency claim — the engine's turn-resume counters prove
   continuing conversations re-entered their resident chains
   (``engine/turn_resumes`` > 0) without re-prefilling the prefix
   (``engine/turn_prefill_saved_tokens`` > 0).
3. **Lineage provenance** — a lineage-armed env run stamps per-turn
   provenance (turn index, tool-call id, policy span, sampling version)
   on the consumed group records, and ``tools/lineage_report.py --step``
   renders the per-turn rows and exits 0.

Exits nonzero on any miss.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


FAILURES = 0


def check(name: str, ok: bool, detail: str = "") -> None:
    global FAILURES
    print(f"{'PASS' if ok else 'FAIL'} {name}"
          + (f"  [{detail}]" if detail else ""))
    if not ok:
        FAILURES += 1


# --------------------------------------------------- gate 1: tool round-trip


def gate_tool_round_trip() -> None:
    import numpy as np

    from distrl_llm_tpu.env import EnvRolloutDriver
    from distrl_llm_tpu.models import TINY
    from distrl_llm_tpu.tokenizer import CharTokenizer

    tok = CharTokenizer(TINY.vocab_size)
    width = 96
    driver = EnvRolloutDriver(
        "code", tok, max_turns=3, max_new_tokens=width)
    driver.begin_round(["compute 6*7"], ["42"], 1)

    turn1 = np.asarray(tok.encode("<tool>print(6*7)</tool>"), np.int32)
    obs = driver(0, turn1)
    check("code env returns observation tokens for a <tool> turn",
          obs is not None and obs.size > 0)
    obs_text = tok.decode(obs) if obs is not None else ""
    check("sandbox executed the block and round-tripped its output",
          "<output>" in obs_text and "42" in obs_text, repr(obs_text))

    # second policy turn commits to the answer on the SAME token row —
    # exactly what the engine hands the hook after a turn resume
    turn2 = np.asarray(tok.encode("<answer>42</answer>"), np.int32)
    full = np.concatenate([turn1, obs, turn2]) if obs is not None else turn1
    done = driver(0, full)
    check("terminal <answer> turn ends the episode", done is None)

    tokens = np.zeros((1, width), np.int32)
    tokens[0, :full.size] = full[:width]
    result = driver.finish_round(tokens, np.asarray([full.size]))
    mask = result.loss_mask[0]
    p1 = (0, int(turn1.size))
    env_span = (int(turn1.size), int(turn1.size + obs.size))
    p2 = (env_span[1], int(full.size))
    check("policy spans train (loss_mask == 1)",
          mask[p1[0]:p1[1]].all() and mask[p2[0]:p2[1]].all())
    check("env-injected observation is loss-masked (== 0)",
          not mask[env_span[0]:env_span[1]].any(),
          f"span={env_span}")
    check("terminal accuracy scored from the <answer>",
          result.group_rewards[0][0, 1] == 1.0,
          str(result.group_rewards[0]))
    prov = result.turn_provenance[0]
    check("provenance names the tool call and both policy spans",
          len(prov) == 2 and prov[0]["tool_call_id"] == "tool-1"
          and prov[0]["policy_span"] == [p1[0], p1[1]]
          and prov[1]["policy_span"] == [p2[0], p2[1]],
          str(prov))
    check("round stats count the sandbox execution",
          result.stats.tool_calls == 1 and result.stats.turns_max == 2)


# ------------------------------------------- gate 2: end-to-end train runs


def run_env_train(env_name: str, mode: str, **cfg_kw):
    """One tiny env-routed train run on the paged refill engine; returns
    (trainer, sink step records, telemetry counter totals)."""
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.rewards import reward_function
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    telemetry.reset()
    clip = 0.2 if mode == "async" else 0.0
    defaults = dict(
        model="tiny", episodes=2, batch_size=2, num_candidates=2, topk=2,
        # the answer window must seat a policy turn + a CharTokenizer-
        # encoded observation (~130 tokens for the verifier critique) +
        # the next turn, or every resume is declined for lack of room
        train_batch_size=2, max_prompt_tokens=16, max_new_tokens=192,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null",
        max_lora_rank=4, lora_alpha=8, lr=1e-3,
        rollout_mode=mode, max_staleness=2, clip_ratio=clip,
        autotune=False,
        env=env_name, max_turns=2,
        engine_impl="paged", continuous_batching=True,
        continuous_admission=True, max_concurrent_sequences=4,
    )
    defaults.update(cfg_kw)
    config = TrainConfig(**defaults)
    tok = CharTokenizer(TINY.vocab_size)
    problems = [f"q {c}" for c in "abcd"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    engine = PagedGenerationEngine(
        TINY,
        max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        # half-vocab EOS: the random tiny policy ends turns quickly, so
        # episodes fit several policy turns inside the answer window
        eos_token_ids=list(range(2, TINY.vocab_size, 2)),
        pad_token_id=tok.pad_token_id, cache_dtype=jnp.float32,
        page_size=8, max_concurrent_rows=4, scheduler="refill",
        continuous_admission=True, decode_chunk=4,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        capture_logprobs=clip > 0.0, autotune=False,
    )
    sink = MemorySink()
    trainer = Trainer(
        train, {k: v[:2] for k, v in train.items()}, reward_function,
        config, tokenizer=tok, engine=engine,
        base_params=init_params(jax.random.PRNGKey(0), TINY),
        model_cfg=TINY, sink=sink,
    )
    trainer.train()
    trainer.close_obs()
    steps = [m for _, m in sink.records if "loss" in m]
    counters = telemetry.observe_snapshot()["counters"]
    return trainer, steps, counters


def gate_train_end_to_end() -> None:
    for env_name in ("code", "verifier"):
        for mode in ("sync", "async"):
            tag = f"{env_name}/{mode}"
            trainer, steps, counters = run_env_train(env_name, mode)
            losses = [m["loss"] for m in steps]
            check(f"{tag}: run completed with finite losses",
                  len(losses) >= 2
                  and all(math.isfinite(x) for x in losses),
                  str(losses))
            envd = [m for m in steps if "env/turns_mean" in m]
            check(f"{tag}: sink step records carry env/* metrics",
                  len(envd) == len(steps) and all(
                      m["env/turns_mean"] >= 1.0
                      and m["env/turns_max"] <= 2 for m in envd),
                  f"{len(envd)}/{len(steps)} records")
            check(f"{tag}: episodes genuinely multi-turn",
                  any(m["env/turns_mean"] > 1.0 for m in envd),
                  str([m.get("env/turns_mean") for m in envd]))
            # the KV-residency claim: continuations re-entered resident
            # chains (turn_resumes) and the conversation prefix was NOT
            # re-prefilled (every saved token is a prefix token the
            # legacy restart path would have recomputed)
            check(f"{tag}: turn continuations resumed resident KV chains",
                  counters.get("engine/turn_resumes", 0) > 0,
                  f"turn_resumes={counters.get('engine/turn_resumes')}")
            check(f"{tag}: re-admission skipped prefix re-prefill",
                  counters.get("engine/turn_prefill_saved_tokens", 0) > 0,
                  f"saved={counters.get('engine/turn_prefill_saved_tokens')}")


# ------------------------------------------- gate 3: lineage provenance


def gate_lineage_provenance() -> None:
    import contextlib
    import io

    from tools.lineage_report import main as lineage_main

    lineage_dir = tempfile.mkdtemp(prefix="env_smoke_lin_")
    _, steps, _ = run_env_train(
        "verifier", "async", lineage=True, lineage_dir=lineage_dir)
    path = os.path.join(lineage_dir, "lineage.jsonl")
    groups = [
        doc for doc in (json.loads(l) for l in open(path) if l.strip())
        if doc.get("kind") == "group"
    ]
    turny = [g for g in groups if g.get("turns")]
    check("lineage group records carry per-turn provenance",
          len(turny) > 0, f"{len(turny)}/{len(groups)} records")
    entries = [t for g in turny for t in g["turns"]]
    check("per-turn entries carry span + sampling version",
          all(
              isinstance(t.get("policy_span"), list)
              and len(t["policy_span"]) == 2
              and t.get("version") is not None
              and t.get("turn") is not None
              for t in entries
          ),
          str(entries[:2]))
    check("some turn ended on a verifier tool-call id",
          any(str(t.get("tool_call_id") or "").startswith("verify-")
              for t in entries))

    step_n = next(
        (g["consumed_step"] for g in turny
         if g.get("consumed_step") is not None), None)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = lineage_main([path, "--step", str(step_n)])
    out = buf.getvalue()
    check("lineage_report --step exits 0 and renders per-turn rows",
          rc == 0 and "turn cand=" in out and "turns" in out,
          out.splitlines()[1] if out else "")


def main() -> int:
    gate_tool_round_trip()
    gate_train_end_to_end()
    gate_lineage_provenance()
    print(f"{'OK' if FAILURES == 0 else 'FAILED'} "
          f"env smoke ({FAILURES} failure(s))")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
