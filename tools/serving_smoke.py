#!/usr/bin/env python
"""Serving-observability acceptance gate (ISSUE 13), runnable on a CPU
host and wired into tools/run_all_checks.sh.

What it proves, on a REAL continuous-admission run (grouped prompts
through the prefix-sharing paged engine, queue longer than the slot
count so admission genuinely backfills):

1. the ledger does not perturb the engine: greedy outputs are
   BYTE-IDENTICAL with the ledger armed vs off;
2. every finished group has a COMPLETE MONOTONE lifecycle
   (enqueue <= admit <= first_token <= finish) with realized tokens;
3. >= 1 group was backfilled into a freed slot mid-round AND carries a
   nonzero queue-wait (the request actually waited — the latency the
   fixed episode batch could never show);
4. the admission audit conserves: the per-reason stall counts sum to the
   observed declined-admission passes (an unattributed decline is an
   engine bug), and the registry counters mirror the ledger's totals;
5. tools/serving_report.py renders the percentile table + stall
   breakdown from the streamed JSONL alone and exits 0;
6. the Prometheus exposition carries REAL histogram types — cumulative
   ``_bucket{le=...}`` lines for serving/ttft_ms — so standard tooling
   can scrape percentiles;
7. a seeded ``DISTRL_SENTINEL_INJECT=ttft_blowup:2`` with
   ``slo_ttft_ms`` armed yields EXACTLY ONE flight-recorder bundle.

Exit 0 = the serving observability layer held; nonzero otherwise.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


os.environ["DISTRL_POOL_CHECK"] = "1"
# seeded SLO breach: the sentinel must see an injected TTFT blowup at
# step 2 and produce exactly one incident bundle (set before it builds)
os.environ["DISTRL_SENTINEL_INJECT"] = "ttft_blowup:2"


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu import obs, telemetry
    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.serving_obs import STALL_REASONS, ServingLedger

    t_start = time.time()
    failures = 0

    def check(name: str, ok: bool, detail: str = "") -> None:
        nonlocal failures
        print(
            f"{'PASS' if ok else 'FAIL'} {name}"
            + (f"  [{detail}]" if detail else "")
        )
        if not ok:
            failures += 1

    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    b, n, rows, page = 5, 2, 4, 8
    ids = rng.integers(2, TINY.vocab_size, size=(b, 16)).astype(np.int32)
    mask = np.ones((b, 16), np.int32)
    for i in range(b):
        pad = int(rng.integers(0, 9))  # rl in [8, 16]
        ids[i, :pad] = 0
        mask[i, :pad] = 0
    sampling = SamplingConfig(max_tokens=16, temperature=0.0, top_p=1.0, n=n)

    def engine(**kw):
        return PagedGenerationEngine(
            TINY, max_prompt_tokens=16, max_new_tokens=16, eos_token_ids=[1],
            pad_token_id=0, page_size=page, max_concurrent_rows=rows,
            scheduler="refill", decode_chunk=4, autotune=False,
            continuous_admission=True, **kw,
        )

    key = jax.random.PRNGKey(1)
    golden = engine().generate(params, None, ids, mask, sampling, key)

    serving_dir = tempfile.mkdtemp(prefix="serving_smoke_")
    eng = engine()
    ledger = ServingLedger(out_dir=serving_dir)
    eng.serving_ledger = ledger
    res = eng.generate(params, None, ids, mask, sampling, key)

    # --- 1: the ledger observes, it never schedules -----------------------
    check(
        "ledger-armed outputs byte-identical",
        np.array_equal(res.tokens, golden.tokens)
        and np.array_equal(res.lengths, golden.lengths),
    )

    ledger.close()
    path = os.path.join(serving_dir, "serving.jsonl")
    docs = [json.loads(line) for line in open(path)]
    groups = [d for d in docs if d["kind"] == "group"]
    summaries = [d for d in docs if d["kind"] == "summary"]

    # --- 2: complete monotone lifecycles ---------------------------------
    check("one record per live group", len(groups) == b,
          f"{len(groups)} records / {b} groups")
    monotone = all(
        g["enqueue_ts"] is not None and g["admit_ts"] is not None
        and g["first_token_ts"] is not None and g["finish_ts"] is not None
        and (g["enqueue_ts"] <= g["admit_ts"] <= g["first_token_ts"]
             <= g["finish_ts"])
        for g in groups
    )
    check("every lifecycle complete and monotone "
          "(enqueue <= admit <= first_token <= finish)", monotone)
    check("every group carries realized tokens + latencies",
          all(
              (g["gen_tokens"] or 0) > 0 and g["ttft_ms"] is not None
              and g["queue_wait_ms"] is not None and g["e2e_ms"] is not None
              for g in groups
          ))
    check("prefill-done recorded between enqueue and first token",
          all(
              g["prefill_done_ts"] is not None
              and g["enqueue_ts"] <= g["prefill_done_ts"]
              <= g["first_token_ts"]
              for g in groups
          ))

    # --- 3: backfill with genuine queue-wait -----------------------------
    backfilled = [g for g in groups if g["backfilled"]]
    check(">= 1 group backfilled mid-round with nonzero queue-wait",
          any(g["queue_wait_ms"] > 0 for g in backfilled),
          f"{len(backfilled)} backfilled")
    check("admissions carry chain-alias info",
          any(
              a["shared_pages"] > 0 or a["cow"]
              for g in groups for a in g["admits"]
          ))

    # --- 4: the admission audit conserves --------------------------------
    check("exactly one summary line", len(summaries) == 1)
    summ = summaries[0]
    stall_sum = sum(summ["stalls"].values())
    check("stall-reason counts sum to declined passes",
          stall_sum == summ["declined_passes"]
          and set(summ["stalls"]) == set(STALL_REASONS),
          f"{summ['stalls']} vs declined={summ['declined_passes']}")
    check("declined passes bounded by admission passes",
          0 < summ["declined_passes"] <= summ["admission_passes"],
          f"{summ['declined_passes']}/{summ['admission_passes']}")
    snap = telemetry.observe_snapshot()
    reg_declined = snap["counters"].get("serving/declined_passes", 0)
    reg_stalls = sum(
        v for k, v in snap["counters"].items()
        if k.startswith("serving/admission_stalls/")
    )
    check("registry counters mirror the ledger",
          reg_declined == summ["declined_passes"]
          and reg_stalls == stall_sum,
          f"registry declined={reg_declined} stalls={reg_stalls}")

    # --- 5: serving_report renders from the file alone -------------------
    from tools import serving_report

    rc = serving_report.main([path])
    check("serving_report exits 0 on the streamed JSONL", rc == 0)

    # --- 6: scrapable Prometheus histograms ------------------------------
    text = obs.prometheus_text()
    check("exposition carries cumulative histogram buckets",
          'distrl_serving_ttft_ms_bucket{le="+Inf"} ' in text
          and "# TYPE distrl_serving_ttft_ms histogram" in text)

    # --- 7: seeded SLO breach → exactly one bundle ------------------------
    incident_dir = tempfile.mkdtemp(prefix="serving_smoke_incidents_")
    # SLO far above the run's REAL TTFT so the only breach is the seeded
    # injection (which fires at 1000× the SLO): exactly-one stays exact
    sentinel = obs.Sentinel(
        obs.FlightRecorder(incident_dir), slo_ttft_ms=1e6
    )
    for step in (1, 2, 3, 4):
        sentinel.check(step, dict(telemetry.metrics_snapshot()))
    bundles = sorted(glob.glob(os.path.join(incident_dir, "incident_*")))
    check("injected ttft_blowup yields exactly one bundle",
          len(bundles) == 1
          and bundles[0].endswith("incident_step000002_ttft_blowup"),
          str([os.path.basename(p) for p in bundles]))
    if len(bundles) == 1:
        man = json.load(open(os.path.join(bundles[0], "manifest.json")))
        check("bundle manifest names the trigger",
              man["trigger"] == "ttft_blowup" and man["step"] == 2)

    print(
        f"serving_smoke: {failures} failure(s), "
        f"{len(groups)} lifecycles, stalls {summ['stalls']}, "
        f"{time.time() - t_start:.0f}s total"
    )
    return 1 if failures else 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 — the gate must report, not hang
        import traceback

        traceback.print_exc()
        rc = 1
    sys.exit(rc)
