#!/usr/bin/env python
"""Observability acceptance gate (ISSUE 8): the continuous observability
plane works end to end on a CPU host.

What it does:

1. launches 2 control-plane workers serving the deterministic TINY model,
   each with ``--metrics-port 0`` — a live worker endpoint plus the
   registry-snapshot piggyback on RPC results;
2. trains a tiny 2-episode run through ``RemoteEngine`` with the driver's
   endpoint (``metrics_port=0``), the sentinel, and the flight recorder
   armed, and ``DISTRL_SENTINEL_INJECT=nan_loss:2`` injecting a seeded NaN
   at step 2;
3. DURING the run, scrapes both worker endpoints (Prometheus text must
   carry this worker's registry) and the driver endpoint (the JSON
   snapshot must show fleet/* series: both workers healthy, per-worker
   gen-token counters flowing, aggregate tok/s);
4. asserts afterwards: the scrapes succeeded, the run completed with every
   group accounted for, and the injected NaN produced EXACTLY ONE incident
   bundle containing the metric ring, span tail, and config/plan snapshot.

Exit 0 = the observability plane held; nonzero otherwise.
``tools/run_all_checks.sh`` runs this as the observability stage.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# seeded anomaly: the sentinel must see a NaN loss at train step 2 and
# produce exactly one incident bundle (set before the Trainer builds it)
os.environ["DISTRL_SENTINEL_INJECT"] = "nan_loss:2"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P_LEN, MAX_NEW = 8, 6


def spawn_worker():
    import subprocess

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "distrl_llm_tpu.distributed.worker_main",
            "--port", "0", "--serve-model", "tiny",
            "--max-prompt-tokens", str(P_LEN),
            "--max-new-tokens", str(MAX_NEW),
            "--seed", "7", "--lora-rank", "4", "--lora-alpha", "8",
            "--metrics-port", "0",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), f"worker failed to start: {line!r}"
    port = int(line.split()[1])
    mline = proc.stdout.readline().strip()
    assert mline.startswith("METRICS "), f"no metrics endpoint: {mline!r}"
    return proc, port, int(mline.split()[1])


def scrape(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


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
    incident_dir = tempfile.mkdtemp(prefix="obs_smoke_incidents_")
    procs, ports, mports = [], [], []
    for _ in range(2):
        proc, port, mport = spawn_worker()
        procs.append(proc)
        ports.append(port)
        mports.append(mport)
    print(f"workers up on ports {ports} (metrics {mports})")

    cfg = TrainConfig(
        model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
        max_lora_rank=4, lora_alpha=8, learner="grpo", eval_n=2,
        metrics_port=0, sentinel=True, flight_recorder_dir=incident_dir,
    )
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    test = {k: v[:4] for k, v in train.items()}
    base = init_params(jax.random.PRNGKey(7), TINY)
    engine = connect_remote_engine(
        [("127.0.0.1", p) for p in ports],
        max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        timeout_ms=120_000,
        lora_scale=lora_scale(cfg.max_lora_rank, cfg.lora_alpha),
        retry_policy=RetryPolicy(max_call_retries=2, base_s=0.05, seed=0),
    )
    sink = MemorySink()
    trainer = Trainer(
        train, test, reward_function, cfg,
        tokenizer=tok, engine=engine, base_params=base, model_cfg=TINY,
        sink=sink,
    )
    driver_port = trainer.obs.server.port
    print(f"driver endpoint on port {driver_port}")

    scraped: dict = {}
    errors: list[str] = []

    def watcher() -> None:
        # scrape mid-run, once at least one step's results (and therefore
        # the workers' piggybacked snapshots) exist
        deadline = time.time() + 400
        while time.time() < deadline:
            if any("loss" in m for _, m in sink.records):
                break
            time.sleep(0.05)
        else:
            errors.append("timeout waiting for the first train step")
            return
        try:
            for k, mport in enumerate(mports):
                scraped[f"worker{k}"] = scrape(
                    f"http://127.0.0.1:{mport}/metrics"
                )
            scraped["driver_json"] = json.loads(scrape(
                f"http://127.0.0.1:{driver_port}/metrics.json"
            ))
            scraped["driver_prom"] = scrape(
                f"http://127.0.0.1:{driver_port}/metrics"
            )
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(f"scrape failed: {e!r}")

    th = threading.Thread(target=watcher, name="obs-watcher", daemon=True)
    th.start()
    trainer.train()
    th.join(timeout=60)
    assert not errors, errors

    # --- run completed with intact accounting ----------------------------
    losses = [m["loss"] for _, m in sink.records if "loss" in m]
    assert len(losses) == 4, f"expected 4 train steps, got {len(losses)}"
    assert trainer.total_samples_processed == 16

    # --- worker endpoints served their registries ------------------------
    for k in range(2):
        text = scraped[f"worker{k}"]
        assert "distrl_obs_gen_tokens" in text, (
            f"worker{k} endpoint missing obs/gen_tokens:\n{text[:400]}"
        )
    # --- driver endpoint serves the fleet fold ---------------------------
    fleet = scraped["driver_json"]["fleet"]
    assert fleet is not None, "driver endpoint returned no fleet view"
    assert fleet["workers_total"] == 2
    assert fleet["workers_healthy"] == 2, fleet["workers"]
    assert fleet["gen_tokens_total"] > 0, fleet
    assert len(fleet["worker_metrics"]) == 2, fleet["worker_metrics"]
    assert all(
        w["gen_tokens"] > 0 for w in fleet["worker_metrics"].values()
    ), fleet["worker_metrics"]
    assert "distrl_fleet_worker_healthy" in scraped["driver_prom"]
    assert "distrl_obs_gen_tokens" in scraped["driver_prom"]

    # --- the seeded NaN produced EXACTLY ONE incident bundle -------------
    # (exactly-once is per trigger: a CI scheduling stall can legitimately
    # trip the tok/s-regression trigger too — same filter chaos_smoke uses)
    incidents = sorted(glob.glob(os.path.join(incident_dir, "incident_*")))
    nan_incidents = [p for p in incidents if p.endswith("_nan_loss")]
    assert len(nan_incidents) == 1, incidents
    (incident,) = nan_incidents
    assert os.path.basename(incident) == "incident_step000002_nan_loss"
    files = sorted(os.listdir(incident))
    assert files == ["config.json", "manifest.json", "metric_ring.jsonl",
                     "span_tail.json"], files
    man = json.load(open(os.path.join(incident, "manifest.json")))
    assert man["trigger"] == "nan_loss" and man["step"] == 2
    ring = [json.loads(l) for l in
            open(os.path.join(incident, "metric_ring.jsonl"))]
    assert ring and all("metrics" in r for r in ring)
    assert all(np.isfinite(r["metrics"]["loss"]) for r in ring), (
        "the INJECTED NaN is sentinel-side; the training loop itself "
        "stayed finite"
    )
    cfg_doc = json.load(open(os.path.join(incident, "config.json")))
    assert cfg_doc["config"]["model"] == "tiny"

    # --- clean shutdown ---------------------------------------------------
    trainer.close_obs()
    engine.driver.shutdown()
    for proc in procs:
        rc = proc.wait(timeout=15)
        assert rc == 0, f"worker shutdown exited {rc}"

    print(
        f"OBS OK — 4 steps / 16 groups, 2 worker + 1 driver endpoint "
        f"scraped live, fleet fold {fleet['gen_tokens_total']:.0f} tokens "
        f"over {fleet['workers_healthy']}/2 workers, exactly one incident "
        f"bundle ({os.path.basename(incident)}), "
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
