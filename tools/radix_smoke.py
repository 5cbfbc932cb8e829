#!/usr/bin/env python
"""Tiered-KV-cache smoke check (wired into tools/run_all_checks.sh).

The CI-side acceptance gate for ISSUE 18's radix prefix cache + host-RAM
spill, runnable on a CPU host:

* a warm-prefix round through the cache-on engine books MEASURED
  ``prefill_tok_saved > 0`` (cross-group aliasing of a shared prompt
  prefix) and stays BYTE-IDENTICAL under greedy decode to the cache-off
  golden run;
* a second round of the same prompts re-admits through the flushed (host-
  parked) tree — restored pages > 0, still byte-identical;
* a page budget tight enough to preempt forces tier-2 spill→restore
  through the host store and the restored continuations stay
  byte-identical to the unbudgeted cache-off run;
* a multi-turn round's conversation history (prompt + turn 1 + observation
  + turn 2), re-admitted as the next round's prompt, radix-hits at ZERO
  prefill for every full history page — the admission prefills only the
  partial tail;
* the per-boundary pool self-check (DISTRL_POOL_CHECK=1) holds at every
  match/admit/evict/spill/restore boundary throughout.

Exits nonzero on any miss.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


os.environ["DISTRL_POOL_CHECK"] = "1"

PAGE = 8


class _FixedObsHook:
    """Minimal deterministic engine turn hook: every candidate re-enters
    once with the same observation block, so that the smoke's transcripts
    are reproducible inputs for the history re-admission round."""

    def __init__(self, obs):
        self.obs = obs
        self.turns: dict[int, int] = {}
        self.resumed = 0

    def __call__(self, cand_id: int, gen_tokens):
        if self.turns.get(cand_id, 1) >= 2:
            return None
        self.turns[cand_id] = 2
        self.resumed += 1
        return self.obs

    def declined(self, cand_id: int) -> None:
        pass


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

    def engine(cache=False, pool=0, prompt_len=16, eos=(1,), **kw):
        return PagedGenerationEngine(
            TINY, max_prompt_tokens=prompt_len, max_new_tokens=24,
            eos_token_ids=list(eos), pad_token_id=0, page_size=PAGE,
            max_concurrent_rows=4, scheduler="refill", max_kv_pages=pool,
            spec_draft=0, decode_chunk=4, autotune=False,
            continuous_admission=True, prefix_cache=cache, **kw,
        )

    rng = np.random.default_rng(0)
    b = 6
    ids = rng.integers(2, TINY.vocab_size, size=(b, 16)).astype(np.int32)
    ids[:, :PAGE] = ids[0, :PAGE]  # one page-aligned cross-group prefix
    mask = np.ones((b, 16), np.int32)
    samp = SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=2)
    key = jax.random.PRNGKey(7)

    golden = engine().generate(params, None, ids, mask, samp, key)

    # --- gate 1: warm-prefix round, measured savings, bit-identity --------
    eng = engine(cache=True)
    r1 = eng.generate(params, None, ids, mask, samp, key)
    s1 = eng.last_pool_stats
    check("warm round greedy outputs byte-identical to cache-off",
          np.array_equal(r1.tokens, golden.tokens)
          and np.array_equal(r1.lengths, golden.lengths))
    check("warm round booked measured prefill savings",
          (s1["prefill_tok_saved"] or 0) > 0,
          f"prefill_tok_saved={s1['prefill_tok_saved']} "
          f"hit_rate={s1['radix_hit_rate']}")

    # --- gate 2: cross-round flush -> restore re-admission ----------------
    r2 = eng.generate(params, None, ids, mask, samp, key)
    s2 = eng.last_pool_stats
    check("second round re-admits through the host-parked tree",
          (s2["restored_pages"] or 0) > 0
          and (s2["prefill_tok_saved"] or 0) > 0
          and s2["spill_restore_ms_p50"] is not None,
          f"restored={s2['restored_pages']} "
          f"restore_p50={s2['spill_restore_ms_p50']}ms")
    check("restored round stays byte-identical",
          np.array_equal(r2.tokens, golden.tokens)
          and np.array_equal(r2.lengths, golden.lengths))

    # --- gate 3: tier-2 spill under forced page pressure ------------------
    sp = engine(cache=True, pool=12, kv_spill=True)
    r3 = sp.generate(params, None, ids, mask, samp, key)
    s3 = sp.last_pool_stats
    check("budgeted pool actually preempted and spilled",
          s3["preemptions"] > 0 and (s3["spilled_pages"] or 0) > 0
          and (s3["restored_pages"] or 0) > 0,
          f"preempt={s3['preemptions']} spilled={s3['spilled_pages']} "
          f"restored={s3['restored_pages']}")
    check("spill->restore continuation byte-identical",
          np.array_equal(r3.tokens, golden.tokens)
          and np.array_equal(r3.lengths, golden.lengths))

    # --- gate 4: multi-turn history re-admits at zero prefill -------------
    # round 1: a 2-turn episode per candidate (fixed observation block);
    # its transcript (prompt + turn 1 + observation + turn 2) becomes the
    # NEXT round's prompt — the env driver's EnvRoundResult.history
    # contract — and must land almost entirely on cached pages.
    hb = 3
    hids = np.zeros((hb, 64), np.int32)
    hmask = np.zeros((hb, 64), np.int32)
    hids[:, :16] = rng.integers(2, TINY.vocab_size, size=(hb, 16))
    hmask[:, :16] = 1
    hsamp = SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=1)
    obs = rng.integers(2, TINY.vocab_size, size=PAGE).astype(np.int32)
    eos = list(range(2, TINY.vocab_size, 2))  # half-vocab: turns end fast

    def mt_engine(cache):
        return engine(cache=cache, prompt_len=64, eos=eos)

    ref_eng = mt_engine(False)
    ref_eng.turn_hook = _FixedObsHook(obs)
    mt_ref = ref_eng.generate(params, None, hids, hmask, hsamp, key)
    mt = mt_engine(True)
    mt.turn_hook = _FixedObsHook(obs)
    m1 = mt.generate(params, None, hids, hmask, hsamp, key)
    check("multi-turn round resumed in place and stayed byte-identical",
          mt.turn_hook.resumed == hb
          and np.array_equal(m1.tokens, mt_ref.tokens)
          and np.array_equal(m1.lengths, mt_ref.lengths),
          f"resumed={mt.turn_hook.resumed}/{hb}")

    # next-round prompts = full transcripts (EnvRoundResult.history shape)
    h2ids = np.zeros((hb, 64), np.int32)
    h2mask = np.zeros((hb, 64), np.int32)
    for g in range(hb):
        gen = np.asarray(m1.tokens[g, 0, : int(m1.lengths[g, 0])])
        row = np.concatenate([hids[g, :16], gen])[:64].astype(np.int32)
        h2ids[g, : row.size] = row
        h2mask[g, : row.size] = 1
    rl2 = h2mask.sum(axis=-1)
    check("transcripts extend past the first-turn prompt",
          bool((rl2 > 16).all()), f"history lens={rl2.tolist()}")

    mt.turn_hook = None
    ref_eng.turn_hook = None
    h_golden = ref_eng.generate(params, None, h2ids, h2mask, hsamp, key)
    mt.generate(params, None, h2ids, h2mask, hsamp, key)  # caches full history
    m3 = mt.generate(params, None, h2ids, h2mask, hsamp, key)
    sm = mt.last_pool_stats
    # every FULL history page admits straight from cache: the only prefill
    # left is the partial tail + the final token (which must re-run to
    # produce the admission's sampling logits)
    max_cacheable = int(sum(((int(r) - 1) // PAGE) * PAGE for r in rl2))
    check("history re-admission hits every full page (zero prefill)",
          sm["prefill_tok_saved"] == max_cacheable,
          f"saved={sm['prefill_tok_saved']} of max {max_cacheable} "
          f"({int(rl2.sum())} history tokens)")
    check("history re-admission stays byte-identical",
          np.array_equal(m3.tokens, h_golden.tokens)
          and np.array_equal(m3.lengths, h_golden.lengths))

    print(f"radix_smoke: {failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
