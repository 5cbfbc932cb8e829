#!/usr/bin/env python
"""Training-dynamics observability smoke check (ISSUE 16; wired into
tools/run_all_checks.sh).

Three end-to-end gates over the REAL trainer + tiny engines on a CPU host
(the bundle's math and the per-trigger unit gates live in
tests/test_learn_obs.py):

1. **Armed byte-identity** — an async run with ``--learn_obs`` armed
   produces a loss sequence and final adapter checksum byte-identical to
   the off run: the bundle is derived under ``stop_gradient`` from
   intermediates the loss already materializes and rides the step's
   existing single host fetch. The armed run's per-step sink records must
   carry the ``learn/*`` gauges, and ``<learn_dir>/learn.jsonl`` must hold
   one ``step`` line per optimizer step plus the ``summary`` line.
2. **kl_blowup chaos gate** — a seeded ``DISTRL_SENTINEL_INJECT=
   kl_blowup:N`` run yields EXACTLY ONE incident bundle whose manifest
   names the trigger and step.
3. **Report tools** — ``tools/learn_report.py`` (with ``--incidents``)
   and ``tools/lineage_report.py`` both exit 0 on the artifacts the run
   just produced, and the learn report's trigger audit names the seeded
   incident.

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


def run_tiny(mode: str = "async", **cfg_kw):
    """One tiny async train run on the dense engine; returns
    (trainer, step records)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine.engine import GenerationEngine
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

    engine = GenerationEngine(
        TINY,
        max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jnp.float32,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        capture_logprobs=clip > 0.0, autotune=False,
    )
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


def gate_byte_identity() -> str:
    """Armed vs off; returns the armed run's learn_dir for the report
    gate."""
    learn_dir = tempfile.mkdtemp(prefix="learn_smoke_")
    t0, base = run_tiny()
    t1, armed = run_tiny(learn_obs=True, learn_dir=learn_dir)
    check(
        "armed loss sequence byte-identical to off",
        [m["loss"] for m in base] == [m["loss"] for m in armed],
        f"off={[m['loss'] for m in base]} "
        f"armed={[m['loss'] for m in armed]}",
    )
    check(
        "armed adapter checksum byte-identical to off",
        _checksum(t0.lora) == _checksum(t1.lora),
    )
    # satellite 1: the learn/* gauges flow into the per-step sink record
    carried = [m for m in armed if "learn/entropy" in m]
    check(
        "armed step records carry learn/* gauges in the sink",
        len(carried) == len(armed) and all(
            m["learn/entropy"] > 0.0 and "learn/kl_behavior" in m
            for m in carried
        ),
        f"{len(carried)}/{len(armed)} records",
    )
    check("off step records carry no learn/* series",
          not any("learn/entropy" in m for m in base))
    rows = [json.loads(l)
            for l in open(os.path.join(learn_dir, "learn.jsonl"))]
    kinds = [r["kind"] for r in rows]
    check(
        "learn.jsonl: one step line per optimizer step + summary",
        kinds == ["step"] * len(armed) + ["summary"]
        and rows[-1]["steps"] == len(armed),
        str(kinds),
    )
    step_rows = [r for r in rows if r["kind"] == "step"]
    check(
        "learn.jsonl steps carry the async bundle (kl + histogram)",
        all("kl" in r and "ratio_counts" in r and "grad_norm_total" in r
            for r in step_rows),
    )
    return learn_dir


def gate_kl_blowup_chaos() -> tuple[str, str]:
    """Seeded kl_blowup: exactly one incident bundle; returns (fr_dir,
    lineage_dir) for the report gate."""
    fr = tempfile.mkdtemp(prefix="learn_smoke_fr_")
    lineage_dir = tempfile.mkdtemp(prefix="learn_smoke_lin_")
    os.environ["DISTRL_SENTINEL_INJECT"] = "kl_blowup:2"
    try:
        trainer, steps = run_tiny(
            sentinel=True, flight_recorder_dir=fr,
            # far above any real tiny-model KL: only the injection fires
            learn_kl_limit=1e6,
            lineage=True, lineage_dir=lineage_dir,
        )
    finally:
        del os.environ["DISTRL_SENTINEL_INJECT"]
    bundles = sorted(os.listdir(fr))
    check("kl gate: exactly one incident bundle",
          len(bundles) == 1 and "kl_blowup" in bundles[0], str(bundles))
    if bundles:
        man = json.load(
            open(os.path.join(fr, bundles[0], "manifest.json"))
        )
        check(
            "kl gate: manifest names trigger, step, and the reading",
            man["trigger"] == "kl_blowup" and man["step"] == 2
            and man["kl"] > man["limit"],
            str({k: man.get(k) for k in ("trigger", "step", "kl",
                                         "limit")}),
        )
    losses = [m["loss"] for m in steps]
    check("kl gate: run completed with finite losses",
          len(losses) >= 2 and all(math.isfinite(x) for x in losses),
          str(losses))
    check(
        "kl gate: lineage consumed rows carry the dynamics columns",
        any(
            json.loads(l).get("kl") is not None
            for l in open(os.path.join(lineage_dir, "lineage.jsonl"))
            if json.loads(l).get("kind") == "group"
        ),
    )
    return fr, lineage_dir


def gate_reports(learn_dir: str, fr: str, lineage_dir: str) -> None:
    import contextlib
    import io

    from tools.learn_report import main as learn_main
    from tools.lineage_report import main as lineage_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = learn_main([
            os.path.join(learn_dir, "learn.jsonl"), "--incidents", fr,
        ])
    out = buf.getvalue()
    check("learn_report exits 0 on the run's artifacts", rc == 0)
    check("learn_report audits the seeded kl_blowup incident",
          "kl_blowup" in out)
    # (the drift section is empty-when-absent: a 3-step run never fills
    # the reference window, so only the table + distributions render)
    check("learn_report renders the per-step table + distributions",
          "entropy" in out and "steps:" in out)

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = lineage_main([os.path.join(lineage_dir, "lineage.jsonl")])
    check("lineage_report exits 0 on the run's ledger", rc == 0)


def main() -> int:
    learn_dir = gate_byte_identity()
    fr, lineage_dir = gate_kl_blowup_chaos()
    gate_reports(learn_dir, fr, lineage_dir)
    print(f"{'OK' if FAILURES == 0 else 'FAILED'} "
          f"learn smoke ({FAILURES} failure(s))")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
