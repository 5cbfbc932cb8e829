#!/usr/bin/env python
"""Speculative-decoding smoke check (wired into tools/run_all_checks.sh).

The acceptance contract for the system-integrated speculative path
(ISSUE 6), end to end on a CPU host:

* greedy spec decode is BIT-IDENTICAL to plain refill decode for BOTH
  drafters (n-gram prompt lookup and previous-LoRA self-drafting), with
  the fused verify dispatch threaded (on CPU it resolves to the exact
  unrolled reference — the dispatch layer, not the kernel, is what this
  gate exercises; interpreter kernel parity lives in
  tests/test_paged_native.py, the v5e lowering in tests/test_tpu_compile.py);
* chunked dispatch (scan_chunk over the spec scheduler) stays
  bit-identical AND actually runs (scan_chunk_active);
* per-round spec stats populate (accept rate, tokens/verify-step, emit
  histogram conservation);
* a tiny traced ``--rollout_mode async`` training run through the
  speculative refill engine produces finite losses, engine/spec_*
  telemetry in the trace, and a ``speculative:`` section in
  tools/trace_report.py's report.

Exits nonzero on any missing piece.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def engine_checks() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import TINY, init_params

    params = init_params(jax.random.PRNGKey(7), TINY)
    rng = np.random.default_rng(1)
    ids = rng.integers(1, TINY.vocab_size, size=(4, 8)).astype(np.int32)
    mask = np.ones((4, 8), np.int32)
    mask[0, :3] = 0
    ids[0, :3] = 0

    def make(**kw):
        return PagedGenerationEngine(
            TINY, max_prompt_tokens=8, max_new_tokens=12,
            eos_token_ids=[TINY.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32, page_size=8,
            scheduler="refill", max_concurrent_rows=4, autotune=False, **kw,
        )

    cfg = SamplingConfig(max_tokens=12, temperature=0.0, n=2)
    key = jax.random.PRNGKey(0)
    plain = make().generate(params, None, ids, mask, cfg, key)

    for label, kw in (
        ("ngram", dict(spec_draft=3)),
        ("self", dict(spec_draft=3, spec_drafter="self")),
        ("ngram+chunk", dict(spec_draft=3, scan_chunk=4)),
        ("self+chunk", dict(spec_draft=3, spec_drafter="self", scan_chunk=4)),
        ("self+unrolled", dict(spec_draft=3, spec_drafter="self",
                               spec_verify="unrolled")),
    ):
        eng = make(**kw)
        res = eng.generate(params, None, ids, mask, cfg, key)
        np.testing.assert_array_equal(
            res.tokens, plain.tokens,
            err_msg=f"{label}: greedy spec decode diverged from plain",
        )
        if kw.get("scan_chunk"):
            assert eng.scan_chunk_active, (
                f"{label}: chunked spec dispatch silently fell back"
            )
        st = eng.last_spec_stats
        assert st is not None, f"{label}: no spec stats recorded"
        hist = st["emit_hist"]
        emitted = sum(i * c for i, c in enumerate(hist))
        # conservation: every generated token beyond each candidate's
        # admit-sampled first token was emitted by some verify step
        assert emitted == int(res.lengths.sum()) - res.lengths.size, (
            f"{label}: emit histogram does not conserve tokens: {st}"
        )
        assert st["tokens_per_verify_step"] >= 1.0, st
        assert st["drafter"] == kw.get("spec_drafter", "ngram"), st
        print(f"  {label:<14} accept_rate={st['accept_rate']:.3f} "
              f"tokens/verify_step={st['tokens_per_verify_step']:.2f} "
              f"verify={st['verify_impl']}")
    # the self-drafter (q == p before any swap) must accept nearly every
    # draft slot under greedy — that is the whole premise of online
    # self-drafting off the near-on-policy version stream
    eng = make(spec_draft=3, spec_drafter="self")
    eng.generate(params, None, ids, mask, cfg, key)
    assert eng.last_spec_stats["accept_rate"] > 0.5, eng.last_spec_stats


def train_check(trace_dir: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    config = TrainConfig(
        model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=16, max_new_tokens=12,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null",
        max_lora_rank=4, lora_alpha=8, lr=1e-3,
        engine_impl="paged", continuous_batching=True,
        max_concurrent_sequences=6, spec_draft=3, spec_drafter="self",
        rollout_mode="async", max_staleness=2, clip_ratio=0.2,
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

    engine = PagedGenerationEngine(
        TINY, max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jnp.float32, page_size=8,
        scheduler="refill", max_concurrent_rows=6,
        spec_draft=3, spec_drafter="self",
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        capture_logprobs=True, autotune=False,
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
    assert steps, "async spec run: no train steps ran"
    assert all(np.isfinite(m["loss"]) for m in steps), "non-finite loss"
    return steps


def main() -> int:
    print("engine checks (both drafters, chunked, unrolled A/B):")
    engine_checks()

    tmp = tempfile.mkdtemp(prefix="distrl_spec_")
    steps = train_check(tmp)

    path = os.path.join(tmp, "trace.json")
    assert os.path.exists(path), f"no trace written at {path}"
    with open(path) as f:
        doc = json.load(f)
    counters = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "C"}
    assert "engine/spec_accept_rate" in counters, counters
    spans = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e.get("name") == "engine/refill_decode"]
    assert spans, "no refill decode spans in trace"
    assert any("spec_accept_rate" in s.get("args", {}) for s in spans), (
        "refill decode spans carry no spec args"
    )

    report = os.path.join(os.path.dirname(__file__), "trace_report.py")
    out = subprocess.run(
        [sys.executable, report, path], capture_output=True, text=True
    )
    assert out.returncode == 0, f"trace_report.py exited {out.returncode}"
    assert "speculative:" in out.stdout, (
        f"trace_report has no speculative section:\n{out.stdout}"
    )
    assert "tokens/verify step" in out.stdout and "drafter mix" in out.stdout
    print(f"SPEC SMOKE OK — {len(steps)} async train steps through the "
          f"self-drafting speculative engine; trace at {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
