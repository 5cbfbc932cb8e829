#!/usr/bin/env python
"""Self-healing-runtime smoke check (ISSUE 14; wired into
tools/run_all_checks.sh).

Four end-to-end gates over the REAL trainer + tiny engines on a CPU host —
the wiring half of the chaos contract (the per-controller closed-loop
convergence gates live in tests/test_control.py with scripted plants):

1. **Quiescent byte-identity** — a run with every applicable controller
   ARMED but unbreached (no fault injected, latency far under its SLO, no
   device memory stats on CPU) produces a loss sequence and final adapter
   checksum byte-identical to the controllers-off run. Armed-but-idle
   governors must be free.
2. **NaN rollback** — a seeded poisoned loss (DISTRL_CONTROL_INJECT_NAN)
   mid-async-run: the run ends with a FINITE loss, exactly one rollback,
   the restored version recorded in the lineage ledger's JSONL, and the
   version stream gapless (poisoned step produced no version).
3. **HBM governor** — sustained fake watermark pressure
   (DISTRL_OBS_FAKE_HBM, the ISSUE 8 hook): the governor walks the
   admission fraction down to its hard clamp in exactly the bounded number
   of cooldown-spaced shrinks, and the run still completes with finite
   losses (bounded degradation, no wedge).
4. **SLO shed** — a seeded ttft_blowup trigger escalates into exactly one
   shed ENGAGE, deferred groups are counted, the admission audit
   attributes the declined passes to "shed" with conservation intact, the
   governor RELEASES after the recovery dwell (real latency is far under
   the SLO), and exactly one incident bundle exists.

Exits nonzero on any missing piece.
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


def run_tiny(mode: str = "sync", *, engine_kind: str = "paged", **cfg_kw):
    """One tiny train run; returns (trainer, step records)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    telemetry.reset()
    clip = 0.2 if mode == "async" else 0.0
    defaults = dict(
        model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=16, max_new_tokens=12,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null",
        max_lora_rank=4, lora_alpha=8, lr=1e-3,
        rollout_mode=mode, max_staleness=2, clip_ratio=clip,
        autotune=False,
    )
    if engine_kind == "paged":
        defaults.update(
            engine_impl="paged", continuous_batching=True,
            prefix_sharing=True, continuous_admission=True,
            max_concurrent_sequences=4,
        )
    defaults.update(cfg_kw)
    config = TrainConfig(**defaults)
    tok = CharTokenizer(TINY.vocab_size)
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}

    def dense_reward(completions, solutions):
        return np.asarray(
            [(0.0, 0.1 + (len(c) % 5) / 10.0) for c in completions],
            np.float32,
        )

    common = dict(
        max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jnp.float32,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        capture_logprobs=clip > 0.0, autotune=False,
    )
    if engine_kind == "paged":
        engine = PagedGenerationEngine(
            TINY, page_size=8, max_concurrent_rows=4, scheduler="refill",
            prefix_sharing=True, continuous_admission=True,
            decode_chunk=4, **common,
        )
    else:
        engine = GenerationEngine(TINY, **common)
    sink = MemorySink()
    trainer = Trainer(
        train, {k: v[:4] for k, v in train.items()}, dense_reward, config,
        tokenizer=tok, engine=engine, base_params=init_params(
            jax.random.PRNGKey(0), TINY
        ), model_cfg=TINY, sink=sink,
    )
    trainer.train()
    trainer.close_obs()
    steps = [m for _, m in sink.records if "loss" in m]
    return trainer, steps


def _checksum(tree) -> float:
    import jax
    import numpy as np

    return float(sum(
        np.abs(np.asarray(x)).sum() for x in jax.tree_util.tree_leaves(tree)
    ))


def gate_quiescent_byte_identity() -> None:
    fr = tempfile.mkdtemp(prefix="ctl_smoke_fr_")
    obs_kw = dict(
        sentinel=True, flight_recorder_dir=fr, slo_ttft_ms=1e9,
    )
    _t0, base = run_tiny(**obs_kw)
    t1, armed = run_tiny(
        control=True, control_cooldown_steps=0, **obs_kw
    )
    check(
        "armed-but-quiescent controllers arm hbm+shed+nan",
        set(t1.config.armed_controllers()) == {"hbm", "shed",
                                               "nan_rollback"},
        str(t1.config.armed_controllers()),
    )
    check(
        "quiescent loss sequence byte-identical to controllers-off",
        [m["loss"] for m in base] == [m["loss"] for m in armed],
    )
    check(
        "quiescent adapter checksum byte-identical",
        _checksum(_t0.lora) == _checksum(t1.lora),
    )
    check("quiescent run took zero control actions",
          t1.control.actions_taken == 0)


def gate_nan_rollback() -> None:
    lineage_dir = tempfile.mkdtemp(prefix="ctl_smoke_lin_")
    os.environ["DISTRL_CONTROL_INJECT_NAN"] = "2"
    try:
        trainer, steps = run_tiny(
            "async", engine_kind="dense",
            control_nan_rollback=True, lineage=True,
            lineage_dir=lineage_dir,
        )
    finally:
        del os.environ["DISTRL_CONTROL_INJECT_NAN"]
    losses = [m["loss"] for m in steps]
    check("nan gate: poisoned step logged honestly",
          any(math.isnan(x) for x in losses))
    check("nan gate: run ends with a finite loss",
          math.isfinite(losses[-1]))
    check("nan gate: exactly one rollback",
          trainer.control.nan.rollbacks == 1)
    check(
        "nan gate: poisoned step produced no version (gapless stream)",
        trainer.weight_version == len(losses) - 1,
        f"version {trainer.weight_version}, steps {len(losses)}",
    )
    path = os.path.join(lineage_dir, "lineage.jsonl")
    rollbacks = [
        json.loads(line) for line in open(path)
        if json.loads(line).get("kind") == "rollback"
    ]
    check("nan gate: rollback recorded in the lineage ledger",
          len(rollbacks) == 1)
    if rollbacks:
        check(
            "nan gate: ledger names the restored adapter version",
            rollbacks[0]["restored_version"]
            == trainer.lineage.rollbacks[0]["restored_version"] >= 1,
            str(rollbacks[0]),
        )


def gate_hbm_governor() -> None:
    os.environ["DISTRL_OBS_FAKE_HBM"] = json.dumps(
        {"bytes_limit": 100.0, "peak_bytes_in_use": 95.0,
         "bytes_in_use": 90.0}
    )
    try:
        trainer, steps = run_tiny(
            control_hbm=True, control_cooldown_steps=0,
        )
    finally:
        del os.environ["DISTRL_OBS_FAKE_HBM"]
    losses = [m["loss"] for m in steps]
    check("hbm gate: run completed with finite losses under pressure",
          len(losses) == 4 and all(math.isfinite(x) for x in losses))
    # sustained breach: 1.0 → 0.5 → 0.25 → 0.125 → clamp 0.1 — exactly
    # four bounded shrinks, then the clamp holds (no further actions)
    check("hbm gate: bounded actuation count (4 shrinks to the clamp)",
          trainer.control.actions_taken == 4,
          f"{trainer.control.actions_taken} actions")
    check("hbm gate: admission fraction at its hard clamp",
          trainer.control.limits.admission_frac == 0.1)
    kinds = [a.kind for a in trainer.control.actions]
    check("hbm gate: no regrow under sustained pressure (no oscillation)",
          kinds == ["shrink"] * len(kinds), str(kinds))


def gate_slo_shed() -> None:
    fr = tempfile.mkdtemp(prefix="ctl_smoke_shed_")
    os.environ["DISTRL_SENTINEL_INJECT"] = "ttft_blowup:1"
    try:
        trainer, steps = run_tiny(
            control=True, sentinel=True, flight_recorder_dir=fr,
            slo_ttft_ms=10000.0, control_cooldown_steps=2,
            control_dwell_steps=2,
        )
    finally:
        del os.environ["DISTRL_SENTINEL_INJECT"]
    from distrl_llm_tpu import telemetry

    bundles = sorted(os.listdir(fr))
    check("shed gate: exactly one ttft_blowup incident bundle",
          len(bundles) == 1 and "ttft_blowup" in bundles[0],
          str(bundles))
    shed_actions = [
        a for a in trainer.control.actions
        if a.controller == "slo_shed"
    ]
    kinds = [a.kind for a in shed_actions]
    check("shed gate: exactly one engage (trigger-escalated) + release",
          kinds == ["engage", "release"], str(kinds))
    if shed_actions:
        check("shed gate: engage names its sentinel trigger",
              shed_actions[0].trigger == "ttft_blowup")
    check("shed gate: shed released by run end",
          not trainer.control.limits.shed_active())
    snap = telemetry.observe_snapshot()["counters"]
    check("shed gate: deferred groups counted",
          snap.get("control/shed_groups", 0) >= 1,
          f"shed_groups={snap.get('control/shed_groups')}")
    sl = trainer.serving
    check(
        "shed gate: admission audit attributes shed declines, "
        "conservation intact",
        sl is not None and sl.stalls.get("shed", 0) >= 1
        and sum(sl.stalls.values()) == sl.declined_passes,
        f"stalls={getattr(sl, 'stalls', None)} "
        f"declined={getattr(sl, 'declined_passes', None)}",
    )
    losses = [m["loss"] for m in steps]
    check("shed gate: run completed with finite losses",
          len(losses) == 4 and all(math.isfinite(x) for x in losses))


def main() -> int:
    gate_quiescent_byte_identity()
    gate_nan_rollback()
    gate_hbm_governor()
    gate_slo_shed()
    print(f"{'OK' if FAILURES == 0 else 'FAILED'} "
          f"control smoke ({FAILURES} failure(s))")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
