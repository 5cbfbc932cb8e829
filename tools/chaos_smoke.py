#!/usr/bin/env python
"""Chaos acceptance gate (ISSUE 5): a multi-worker training run survives a
seeded kill/restart schedule with intact accounting.

What it does, end to end on a CPU host:

1. launches 2 control-plane workers serving the deterministic TINY model
   (identical seeds — the same twin-worker topology as
   tests/test_remote_engine.py), each exporting its registry snapshot on
   RPC results (``DISTRL_OBS=1``);
2. trains a real 2-episode tiny run through ``RemoteEngine`` — every
   generation round fans out over MSG_DISPATCH/MSG_RESULT frames — with
   the driver's live metrics endpoint, sentinel, and flight recorder
   armed (ISSUE 8), plus a seeded NaN injection at step 3;
3. a chaos thread, on a seeded schedule (``CHAOS_SEED``), SIGKILLs worker 0
   mid-run, waits a seeded delay, and restarts it ON THE SAME PORT —
   scraping the driver's fleet endpoint after the observed death and again
   after the rejoin;
4. asserts: the run completes with finite losses, every group is accounted
   for (sample conservation: no prompt lost to the failure), the driver's
   rejoin loop re-admitted the restarted worker (capacity recovered to
   2/2), the fleet endpoint REFLECTED the kill/restart sequence (healthy
   2→1→2, rejoin epoch 0→≥1), the injected NaN produced exactly one
   incident bundle, and the surviving worker then drains gracefully on
   SIGTERM.

Exit 0 = the fault-tolerant control plane held; nonzero otherwise.
``tools/run_all_checks.sh`` runs this as the resilience stage.
"""

from __future__ import annotations

import glob
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# seeded anomaly for the flight recorder (ISSUE 8): one NaN at step 3 must
# produce exactly one incident bundle (read by the Sentinel at build time)
os.environ["DISTRL_SENTINEL_INJECT"] = "nan_loss:3"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P_LEN, MAX_NEW = 8, 6
CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


def spawn_worker(port: int = 0):
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "distrl_llm_tpu.distributed.worker_main",
            "--port", str(port), "--serve-model", "tiny",
            "--max-prompt-tokens", str(P_LEN),
            "--max-new-tokens", str(MAX_NEW),
            "--seed", "7", "--lora-rank", "4", "--lora-alpha", "8",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        # DISTRL_OBS=1: piggyback the registry snapshot on results so the
        # driver's fleet aggregator sees this worker's token counters
        env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTRL_OBS": "1"},
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), f"worker failed to start: {line!r}"
    return proc, int(line.split()[1])


def main() -> int:
    import jax
    import numpy as np

    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.distributed import RetryPolicy, connect_remote_engine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.rewards import reward_function
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    t_start = time.time()
    procs: list = [None, None]
    ports: list[int] = []
    for k in range(2):
        procs[k], port = spawn_worker()
        ports.append(port)
    print(f"workers up on ports {ports}")

    incident_dir = tempfile.mkdtemp(prefix="chaos_smoke_incidents_")
    cfg = TrainConfig(
        model="tiny", episodes=4, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
        max_lora_rank=4, lora_alpha=8, learner="grpo", eval_n=2,
        # observability plane (ISSUE 8): live fleet endpoint + sentinel +
        # flight recorder, all exercised by the same chaos schedule
        metrics_port=0, sentinel=True, flight_recorder_dir=incident_dir,
    )
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    test = {k: v[:4] for k, v in train.items()}
    base = init_params(jax.random.PRNGKey(7), TINY)  # the workers' twin
    engine = connect_remote_engine(
        [("127.0.0.1", p) for p in ports],
        max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        timeout_ms=120_000,
        lora_scale=lora_scale(cfg.max_lora_rank, cfg.lora_alpha),
        retry_policy=RetryPolicy(
            max_call_retries=2, base_s=0.05, seed=CHAOS_SEED
        ),
        rejoin=True,
    )
    sink = MemorySink()
    trainer = Trainer(
        train, test, reward_function, cfg,
        tokenizer=tok, engine=engine, base_params=base, model_cfg=TINY,
        sink=sink,
    )

    rng = random.Random(CHAOS_SEED)
    chaos_log: list[str] = []
    fleet_views: dict[str, dict] = {}

    driver = engine.driver
    obs_port = trainer.obs.server.port

    def scrape_fleet(label: str) -> dict | None:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{obs_port}/metrics.json", timeout=10
            ) as r:
                fleet = json.load(r).get("fleet")
        except Exception as e:  # noqa: BLE001 — recorded, asserted later
            chaos_log.append(f"fleet scrape {label} failed: {e!r}")
            return None
        if fleet is None:
            # the endpoint degrades a failed fleet refresh to "fleet":
            # null rather than a 500 — record it as a failed scrape, don't
            # let the subscript below kill the chaos thread
            chaos_log.append(f"fleet scrape {label}: endpoint served null")
            return None
        fleet_views[label] = fleet
        chaos_log.append(
            f"fleet[{label}]: healthy {fleet['workers_healthy']}/"
            f"{fleet['workers_total']}, rejoin epoch "
            f"{fleet['rejoin_epoch']}"
        )
        return fleet

    def chaos() -> None:
        # wait for the run to be genuinely mid-flight: at least one train
        # step must have completed (so the kill lands inside the loop, not
        # during worker warmup), then kill IMMEDIATELY — post-compile tiny
        # rounds are milliseconds, so any extra delay closes the window
        deadline = time.time() + 400
        while time.time() < deadline:
            if any("loss" in m for _, m in sink.records):
                break
            time.sleep(0.05)
        else:
            chaos_log.append("timeout waiting for first step")
            return
        scrape_fleet("before_kill")
        chaos_log.append(f"KILL worker0 (port {ports[0]})")
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=10)
        # hold the restart until the DRIVER has observed the death (a later
        # round hit the dead connection and resubmitted its shards) — the
        # rejoin that follows is then provably a recovery, not a no-op
        deadline = time.time() + 120
        while driver.num_healthy == 2 and time.time() < deadline:
            time.sleep(0.02)
        if driver.num_healthy == 2:
            chaos_log.append("driver never observed the death")
            return
        chaos_log.append("death observed by driver")
        # the endpoint must REFLECT the death: re-scrape until the
        # aggregator's refresh window (0.5 s) lapses and the fold shows
        # the demoted worker
        deadline = time.time() + 30
        while time.time() < deadline:
            fleet = scrape_fleet("after_kill")
            if fleet is not None and fleet["workers_healthy"] < 2:
                break
            time.sleep(0.2)
        time.sleep(rng.uniform(0.1, 0.5))
        procs[0] = spawn_worker(port=ports[0])[0]
        chaos_log.append(f"RESTART worker0 on port {ports[0]}")
        deadline = time.time() + 120
        while driver.num_healthy < 2 and time.time() < deadline:
            time.sleep(0.05)
        if driver.num_healthy == 2:
            time.sleep(0.6)  # let the endpoint's refresh window lapse
            scrape_fleet("after_rejoin")

    th = threading.Thread(target=chaos, name="chaos", daemon=True)
    th.start()
    trainer.train()
    th.join(timeout=60)
    for line in chaos_log:
        print(f"chaos: {line}")
    assert any("KILL" in l for l in chaos_log), (
        "the chaos schedule never fired — the run finished before the "
        "first kill; nothing was proven"
    )
    assert any("observed" in l for l in chaos_log), chaos_log
    assert any("RESTART" in l for l in chaos_log), "worker never restarted"

    # --- the run completed, with every group accounted for ----------------
    losses = [m["loss"] for _, m in sink.records if "loss" in m]
    assert len(losses) == 8, f"expected 8 train steps, got {len(losses)}"
    assert all(np.isfinite(l) for l in losses), losses
    # group conservation: 4 episodes × 8 prompts — the worker death lost
    # nothing (resubmission) and dropped nothing (no degrade configured)
    assert trainer.total_samples_processed == 32, (
        trainer.total_samples_processed
    )
    assert not engine.last_lost_rows

    # --- capacity recovered: the restarted worker rejoined ----------------
    deadline = time.time() + 60
    while driver.num_healthy < 2 and time.time() < deadline:
        time.sleep(0.1)
    assert driver.num_healthy == 2, (
        f"capacity never recovered: {driver.num_healthy}/2 healthy"
    )
    assert driver.rejoin_epoch >= 1, "no rejoin recorded"
    assert driver.dispatch_objects([("echo", 1), ("echo", 2)], 30_000) == [1, 2]

    # --- the fleet endpoint reflected the kill/restart sequence -----------
    # (the endpoint outlives train() by design — the chaos thread's
    # after_rejoin scrape may land after the loop ended; wait it out)
    th.join(timeout=150)
    assert not th.is_alive(), "chaos thread never finished"
    assert "before_kill" in fleet_views, chaos_log
    assert "after_kill" in fleet_views, chaos_log
    assert "after_rejoin" in fleet_views, chaos_log
    before, after, rejoined = (
        fleet_views["before_kill"], fleet_views["after_kill"],
        fleet_views["after_rejoin"],
    )
    assert before["workers_total"] == 2
    assert before["workers_healthy"] == 2, before
    assert before["rejoin_epoch"] == 0, before
    assert after["workers_healthy"] < 2, after
    assert rejoined["workers_healthy"] == 2, rejoined
    assert rejoined["rejoin_epoch"] >= 1, rejoined
    # aggregate token accounting flowed from the worker piggybacks
    assert before["gen_tokens_total"] > 0, before
    assert rejoined["gen_tokens_total"] >= before["gen_tokens_total"]

    # --- the seeded NaN produced EXACTLY ONE incident bundle --------------
    # (the kill itself may legitimately trip the tok/s-regression trigger —
    # a slow resubmission round IS an anomaly — so the exactly-one contract
    # is per trigger, on the injected one)
    incidents = sorted(glob.glob(os.path.join(incident_dir, "incident_*")))
    nan_incidents = [p for p in incidents if p.endswith("_nan_loss")]
    assert len(nan_incidents) == 1, incidents
    (incident,) = nan_incidents
    assert os.path.basename(incident) == "incident_step000003_nan_loss"
    files = sorted(os.listdir(incident))
    assert files == ["config.json", "manifest.json", "metric_ring.jsonl",
                     "span_tail.json"], files
    ring = [json.loads(l) for l in
            open(os.path.join(incident, "metric_ring.jsonl"))]
    assert ring, "incident bundle carried an empty metric ring"
    cfg_doc = json.load(open(os.path.join(incident, "config.json")))
    assert cfg_doc["config"]["model"] == "tiny"

    # --- graceful preemption: SIGTERM drains the restarted worker ---------
    procs[0].send_signal(signal.SIGTERM)
    rc = procs[0].wait(timeout=15)
    assert rc == 0, f"SIGTERM drain exited {rc}"
    trainer.close_obs()
    driver.shutdown()
    rc1 = procs[1].wait(timeout=15)
    assert rc1 == 0, f"worker1 shutdown exited {rc1}"

    print(
        f"CHAOS OK — 8 steps / 32 groups conserved, worker killed+rejoined "
        f"(epoch {driver.rejoin_epoch}), fleet endpoint tracked "
        f"2→{after['workers_healthy']}→2 healthy + the rejoin epoch, one "
        f"incident bundle, SIGTERM drain clean, "
        f"{time.time() - t_start:.0f}s total (seed {CHAOS_SEED})"
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
