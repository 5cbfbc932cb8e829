#!/usr/bin/env python
"""Lineage acceptance gate (ISSUE 10): causal tracing + the trajectory
lineage ledger hold end to end on a CPU host.

What it does:

1. launches 2 control-plane workers serving the deterministic TINY model
   with ``--trace`` (spans ship home on RPC responses), behavior-logprob
   capture, and a 2-step decode chunk (so broadcast-bus pushes land
   MID-ROUND, not at boundaries);
2. trains a tiny ``--rollout_mode async`` run through ``RemoteEngine`` over
   the BROADCAST weight bus with in-flight updates, ``--lineage`` armed,
   and span tracing on;
3. asserts afterwards:
   * **lineage closes** — every trained group's record names its consuming
     optimizer step and sampled-version bound ≤ the version that step
     produced, with worker + causal dispatch_id provenance on every record;
   * **learn-to-act measured** — ≥1 weight version has a push→first-sample
     latency, and ≥1 in-flight (mid-round) swap was recorded;
   * **trace links** — in the merged Perfetto trace every worker-side span
     recorded at-or-after the first driver dispatch carries a dispatch_id
     that resolves to a driver ``cp/dispatch``/``cp/weight_push`` span
     (no orphans);
   * **reconciliation** — the lineage histograms' sample counts equal the
     staleness histogram's admitted-group count (same admission events,
     two views), and ``obs/weight_sync_ms`` (push→last-ack, PR 9) is
     consistent with the ledger's per-version broadcast times;
   * **reports** — ``tools/trace_report.py`` prints its ``policy lag:`` /
     ``lineage:`` sections and ``tools/lineage_report.py`` exits 0 on the
     run's JSONL.

Exit 0 = the lineage plane held; nonzero otherwise.
``tools/run_all_checks.sh`` runs this as the lineage stage.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P_LEN, MAX_NEW = 8, 48


def spawn_worker(port: int = 0):
    import subprocess

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "distrl_llm_tpu.distributed.worker_main",
            "--port", str(port), "--serve-model", "tiny",
            "--max-prompt-tokens", str(P_LEN),
            "--max-new-tokens", str(MAX_NEW),
            "--seed", "7", "--lora-rank", "4", "--lora-alpha", "8",
            # mid-round swap machinery: behavior logprobs for the async
            # objective, 2-step dispatch granularity so a broadcast push
            # lands inside a round (~24 mailbox polls per 48-token round)
            "--capture-logprobs", "--decode-chunk", "2",
            "--trace",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTRL_OBS": "1"},
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), f"worker failed to start: {line!r}"
    return proc, int(line.split()[1])


def main() -> int:
    import jax
    import numpy as np

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.distributed import RetryPolicy, connect_remote_engine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.rewards import reward_function
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    t_start = time.time()
    out_dir = tempfile.mkdtemp(prefix="lineage_smoke_")
    procs, ports = [], []
    for _ in range(2):
        proc, port = spawn_worker()
        procs.append(proc)
        ports.append(port)
    print(f"workers up on ports {ports}")

    cfg = TrainConfig(
        model="tiny", episodes=5, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=0,
        eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
        max_lora_rank=4, lora_alpha=8, learner="grpo", eval_n=2,
        rollout_mode="async", clip_ratio=0.2, max_staleness=4,
        inflight_weight_updates=True, workers_capture_logprobs=True,
        lineage=True, lineage_dir=out_dir, trace_dir=out_dir,
    )
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    test = {k: v[:4] for k, v in train.items()}
    engine = connect_remote_engine(
        [("127.0.0.1", p) for p in ports],
        max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        timeout_ms=120_000,
        lora_scale=lora_scale(cfg.max_lora_rank, cfg.lora_alpha),
        retry_policy=RetryPolicy(max_call_retries=2, base_s=0.05, seed=0),
        weight_bus="broadcast",
    )
    sink = MemorySink()
    trainer = Trainer(
        train, test, reward_function, cfg,
        tokenizer=tok, engine=engine,
        base_params=init_params(jax.random.PRNGKey(7), TINY),
        model_cfg=TINY, sink=sink,
    )
    trainer.train()

    losses = [m["loss"] for _, m in sink.records if "loss" in m]
    assert losses and all(np.isfinite(v) for v in losses), losses
    assert engine.last_swap_steps, (
        "no in-flight swap landed mid-round — learn-to-act has nothing "
        "to measure"
    )

    # ---- registry view BEFORE shutdown: the reconciliation inputs --------
    snap = telemetry.observe_snapshot()
    stale_hist = snap["hists"].get("rollout/staleness", {})
    s2l_hist = snap["hists"].get("lineage/sample_to_learn_ms", {})
    l2a_hist = snap["hists"].get("lineage/learn_to_act_ms", {})
    e2e_hist = snap["hists"].get("lineage/policy_lag_ms", {})
    weight_sync_ms = snap["gauges"].get("obs/weight_sync_ms")

    trainer.close_obs()
    engine.driver.shutdown()
    for proc in procs:
        rc = proc.wait(timeout=15)
        assert rc == 0, f"worker shutdown exited {rc}"

    # ---- every trained group's lineage record closes ---------------------
    lineage_path = os.path.join(out_dir, "lineage.jsonl")
    docs = [json.loads(line) for line in open(lineage_path)]
    groups = [d for d in docs if d["kind"] == "group"]
    weights = [d for d in docs if d["kind"] == "weights"]
    consumed = [g for g in groups if g.get("consumed_step") is not None]
    assert consumed, "no consumed group records in the ledger"
    for g in consumed:
        assert g["verdict"] == "admitted", g
        # sampled version <= the version the consuming step produced: the
        # causal arrow points forward (a violation means version
        # bookkeeping corruption somewhere in the loop)
        assert g["max_version"] <= g["produced_version"], g
        assert g["min_version"] <= g["max_version"], g
        # sampling provenance: worker + causal dispatch id on every record
        assert g["worker"] and g["dispatch_id"], g
        assert g["sample_to_learn_ms"] is not None and (
            g["sample_to_learn_ms"] > 0
        ), g
        # buffer passage is fully stamped
        assert g["enqueue_ts"] and g["dequeue_ts"] and g["consumed_ts"], g
        assert g["enqueue_ts"] <= g["dequeue_ts"] <= g["consumed_ts"], g
    # the learner consumed each step's batch_size groups; every consumed
    # group names a real step
    steps = sorted({g["consumed_step"] for g in consumed})
    assert steps == list(range(1, len(steps) + 1)), steps

    # ---- learn-to-act measured for >= 1 in-flight swap -------------------
    lta = [w for w in weights if w.get("learn_to_act_ms") is not None]
    assert lta, "no weight version recorded a learn-to-act latency"
    # at least one MID-ROUND swapped version (the engine's merged worker
    # swap log) closed its push→first-sample window
    swapped = {int(v) for v in engine.last_swap_versions if v is not None}
    assert swapped & {w["version"] for w in lta}, (swapped, lta)
    assert l2a_hist.get("count", 0) >= 1, l2a_hist

    # ---- reconciliation with the existing series -------------------------
    # the staleness histogram observes once per ADMITTED group; so does the
    # ledger's sample→learn histogram (the same admission events, viewed
    # from two planes) — their counts must agree, and the consumed records
    # are exactly those admissions
    assert stale_hist.get("count") == s2l_hist.get("count") == len(consumed), (
        stale_hist, s2l_hist, len(consumed),
    )
    assert e2e_hist.get("count", 0) >= 1, e2e_hist
    # obs/weight_sync_ms is push→LAST-WORKER-ACK (PR 9); the ledger's
    # per-version broadcast time is the same measurement recorded per
    # version — the gauge must match one of them (the most recent)
    assert weight_sync_ms is not None and weight_sync_ms > 0
    bms = [w.get("broadcast_ms") for w in weights
           if w.get("broadcast_ms") is not None]
    assert bms, weights
    assert any(abs(weight_sync_ms - b) < 1e-6 for b in bms), (
        weight_sync_ms, bms,
    )
    # end-to-end >= sample-to-learn on means: the full loop includes the
    # broadcast leg
    if e2e_hist.get("count") and s2l_hist.get("count"):
        e2e_mean = e2e_hist["sum"] / e2e_hist["count"]
        s2l_mean = s2l_hist["sum"] / s2l_hist["count"]
        assert e2e_mean >= s2l_mean * 0.99, (e2e_mean, s2l_mean)

    # ---- merged trace: every worker span links to its driver dispatch ----
    trace_path = os.path.join(out_dir, "trace.json")
    doc = json.load(open(trace_path))
    evs = doc["traceEvents"]
    tracks = {e["pid"]: e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "process_name"}
    worker_pids = {p for p, n in tracks.items() if n.startswith("worker")}
    assert len(worker_pids) == 2, tracks
    driver_ids = {
        e["args"]["dispatch_id"] for e in evs
        if e.get("ph") == "X" and e.get("pid", 1) not in worker_pids
        and e["name"] in ("cp/dispatch", "cp/weight_push")
        and "dispatch_id" in e.get("args", {})
    }
    first_dispatch_ts = min(
        e["ts"] for e in evs
        if e.get("ph") == "X" and e["name"] == "cp/dispatch"
    )
    wspans = [e for e in evs if e.get("ph") == "X"
              and e.get("pid") in worker_pids]
    assert wspans, "no worker spans reached the merged trace"
    linked = [e for e in wspans
              if e.get("args", {}).get("dispatch_id") is not None]
    # every worker span recorded at-or-after the first dispatch carries
    # trace context (pre-dispatch engine-construction spans legitimately
    # have no driver parent)
    for e in wspans:
        if e["ts"] >= first_dispatch_ts:
            assert e.get("args", {}).get("dispatch_id") is not None, e
    # and no carried id is orphaned — each resolves to a driver span
    orphans = {e["args"]["dispatch_id"] for e in linked} - driver_ids
    assert not orphans, f"orphaned dispatch ids: {orphans}"
    # flow arrows rendered: start events on the driver, finish on workers
    assert any(e.get("ph") == "s" for e in evs)
    assert any(e.get("ph") == "f" for e in evs)

    # ---- both report tools run and show the new sections -----------------
    import contextlib
    import io

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lineage_report
    import trace_report

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = trace_report.main([trace_path])
    assert rc == 0, "trace_report failed on the merged trace"
    out = buf.getvalue()
    assert "policy lag:" in out and "lineage:" in out, out[:2000]
    assert "sample→learn:" in out and "learn→act:" in out

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = lineage_report.main([lineage_path])
    assert rc == 0, "lineage_report failed on the ledger"
    out = buf.getvalue()
    assert "consumption:" in out and "weight versions:" in out
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = lineage_report.main([lineage_path, "--step", str(steps[-1])])
    assert rc == 0
    assert f"step {steps[-1]}:" in buf.getvalue()

    print(
        f"LINEAGE OK — {len(consumed)} trained groups closed over "
        f"{len(steps)} steps, {len(lta)} version(s) with learn-to-act, "
        f"{len(linked)}/{len(wspans)} worker spans causally linked "
        f"({len(driver_ids)} driver dispatches, 0 orphans), "
        f"weight_sync reconciled at {weight_sync_ms:.1f} ms, "
        f"{time.time() - t_start:.0f}s total"
    )
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 — the gate must report, not hang
        import traceback

        traceback.print_exc()
        rc = 1
    sys.exit(rc)
