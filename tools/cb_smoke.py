#!/usr/bin/env python
"""Continuous-batching / prefix-sharing smoke check (wired into
tools/run_all_checks.sh).

The CI-side acceptance gate for ISSUE 12's serving-grade scheduler,
runnable on a CPU host:

* grouped prompts (N candidates per prompt) through the prefix-sharing
  refill engine and the continuous-admission engine are BYTE-IDENTICAL
  under greedy decode to the unshared fixed-batch golden run;
* the pool genuinely shared pages (pages_shared_frac > 0 — a group's
  candidates alias one refcounted prompt-prefix chain, with the
  copy-on-write tail splits counted);
* >= 1 candidate was BACKFILLED into a freed slot mid-round (the
  admission the fixed episode batch would have idled away), and the
  continuous engine prefilled once per GROUP, not per slot;
* the per-boundary pool self-check (DISTRL_POOL_CHECK=1) holds at every
  grant/admit/preempt boundary, including a tight budgeted pool that
  forces preemption under sharing;
* speculative decoding composes: the spec refill loop over shared
  prefixes stays bit-identical too.

Exits nonzero on any miss.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


os.environ["DISTRL_POOL_CHECK"] = "1"


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import TINY, init_params

    failures = 0

    def check(name: str, ok: bool, detail: str = "") -> None:
        nonlocal failures
        print(f"{'PASS' if ok else 'FAIL'} {name}" + (f"  [{detail}]" if detail else ""))
        if not ok:
            failures += 1

    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    b, n, rows, page = 5, 2, 4, 8
    ids = rng.integers(2, TINY.vocab_size, size=(b, 16)).astype(np.int32)
    mask = np.ones((b, 16), np.int32)
    for i in range(b):
        pad = int(rng.integers(0, 9))  # rl in [8, 16]: >= 1 full page each
        ids[i, :pad] = 0
        mask[i, :pad] = 0
    sampling = SamplingConfig(max_tokens=16, temperature=0.0, top_p=1.0, n=n)

    def engine(**kw):
        return PagedGenerationEngine(
            TINY, max_prompt_tokens=16, max_new_tokens=16, eos_token_ids=[1],
            pad_token_id=0, page_size=page, max_concurrent_rows=rows,
            scheduler="refill", decode_chunk=4, autotune=False, **kw,
        )

    key = jax.random.PRNGKey(1)
    golden = engine().generate(params, None, ids, mask, sampling, key)

    # --- arm 1: monolithic prefill + CoW prefix sharing -------------------
    sh = engine(prefix_sharing=True)
    res = sh.generate(params, None, ids, mask, sampling, key)
    st = sh.last_pool_stats
    check("prefix_sharing greedy outputs byte-identical",
          np.array_equal(res.tokens, golden.tokens)
          and np.array_equal(res.lengths, golden.lengths))
    check("prefix_sharing shares the full prompt-prefix chain",
          (st["pages_shared_frac"] or 0) > 0,
          f"pages_shared_frac={st['pages_shared_frac']}")
    check("every admission aliased a shared prefix",
          st["prefill_shared_frac"] == 1.0)
    check("copy-on-write tail splits counted", st["cow_splits"] > 0,
          f"cow_splits={st['cow_splits']}")
    check("candidates backfilled into freed slots mid-round",
          st["backfill_admissions"] >= 1,
          f"backfill_admissions={st['backfill_admissions']}")

    # --- arm 2: continuous admission (lazy per-group prefill) -------------
    co = engine(continuous_admission=True)
    res = co.generate(params, None, ids, mask, sampling, key)
    st = co.last_pool_stats
    check("continuous_admission greedy outputs byte-identical",
          np.array_equal(res.tokens, golden.tokens)
          and np.array_equal(res.lengths, golden.lengths))
    check("prefill ran once per GROUP, not per slot",
          st["groups_prefilled"] == b,
          f"groups_prefilled={st['groups_prefilled']} of {b} groups / "
          f"{b * n} candidates")
    check("continuous rounds share pages and backfill",
          (st["pages_shared_frac"] or 0) > 0
          and st["backfill_admissions"] >= 1,
          f"shared={st['pages_shared_frac']} "
          f"backfill={st['backfill_admissions']}")
    check("cb_mode recorded", st["cb_mode"] == "continuous"
          and co.last_cb_mode == "continuous")

    # --- arm 3: tight budgeted pool under sharing (preempt + resume) ------
    bt = engine(continuous_admission=True, max_kv_pages=9)
    res = bt.generate(params, None, ids, mask, sampling, key)
    st = bt.last_pool_stats
    check("budgeted shared pool stays byte-identical",
          np.array_equal(res.tokens, golden.tokens))
    check("budget respected under sharing",
          st["peak_pages_used"] <= 9 - 1,
          f"peak={st['peak_pages_used']} pool=9")

    # --- arm 4: speculative decoding composes -----------------------------
    spec_golden = engine(spec_draft=2).generate(
        params, None, ids, mask, sampling, key)
    sp = engine(spec_draft=2, continuous_admission=True)
    res = sp.generate(params, None, ids, mask, sampling, key)
    check("spec decode over shared prefixes byte-identical",
          np.array_equal(res.tokens, spec_golden.tokens))
    check("spec round shared pages",
          (sp.last_pool_stats["pages_shared_frac"] or 0) > 0)

    print(f"cb_smoke: {failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
