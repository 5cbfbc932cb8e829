#!/usr/bin/env python
"""Weight-bus acceptance gate (ISSUE 9): the versioned broadcast bus is
byte-exact, survives a seeded worker kill/rejoin, and actually sheds the
per-dispatch adapter payload.

What it does, end to end on a CPU host (2 control-plane workers serving the
deterministic TINY model — the chaos_smoke twin-worker topology):

1. GOLDEN  — a tiny 2-episode sync train with ``weight_bus=dispatch`` (the
   legacy weights-in-every-payload transport): records the loss sequence
   and final-adapter checksum.
2. BROADCAST — the same run with ``weight_bus=broadcast``: losses and the
   trained adapter must be BYTE-IDENTICAL to the golden (the delta codec's
   exactness contract, end to end through real wire frames), per-round
   dispatch bytes must drop by at least the serialized adapter size, and
   every worker must ack the learner's final weight_version.
3. CHAOS  — broadcast again with a seeded mid-run SIGKILL → observed death
   → same-port restart (reusing the chaos_smoke scaffolding): the run
   completes with finite losses and full group conservation, the rejoin
   hook full-resyncs the cold worker BEFORE re-admission, and at the end
   the version caches on BOTH workers converge to the learner's current
   adapter, bit-identical (checksum compare over the weights_debug op).

Exit 0 = the bus held; nonzero otherwise. ``tools/run_all_checks.sh`` runs
this as the weight-bus stage; ``--report-json PATH`` additionally writes the
dispatch-vs-broadcast byte/latency A/B record.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
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
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), f"worker failed to start: {line!r}"
    return proc, int(line.split()[1])


def spawn_fleet(n=2, ports=None):
    procs, out_ports = [], []
    for k in range(n):
        p, port = spawn_worker(port=0 if ports is None else ports[k])
        procs.append(p)
        out_ports.append(port)
    return procs, out_ports


def kill_fleet(procs):
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGKILL)
        p.wait(timeout=10)


def run_train(ports, weight_bus, chaos=False):
    """One tiny sync train over the worker fleet; returns (losses, adapter
    checksum, engine, trainer, byte/latency stats)."""
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

    cfg = TrainConfig(
        model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
        max_lora_rank=4, lora_alpha=8, learner="grpo", eval_n=2,
        weight_bus=weight_bus,
    )
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    test = {k: v[:4] for k, v in train.items()}
    base = init_params(jax.random.PRNGKey(7), TINY)
    engine = connect_remote_engine(
        [("127.0.0.1", p) for p in ports],
        max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW, timeout_ms=120_000,
        lora_scale=lora_scale(cfg.max_lora_rank, cfg.lora_alpha),
        retry_policy=RetryPolicy(max_call_retries=2, base_s=0.05,
                                 seed=CHAOS_SEED),
        rejoin=True, weight_bus=weight_bus,
    )
    sink = MemorySink()
    trainer = Trainer(
        train, test, reward_function, cfg,
        tokenizer=tok, engine=engine, base_params=base, model_cfg=TINY,
        sink=sink,
    )
    telemetry.metrics_snapshot()  # reset counter deltas for this run
    chaos_log: list[str] = []
    th = None
    if chaos:
        driver = engine.driver
        rng = random.Random(CHAOS_SEED)
        procs_ref = chaos  # [procs, ports] mutable holder from the caller

        def chaos_thread():
            deadline = time.time() + 400
            while time.time() < deadline:
                if any("loss" in m for _, m in sink.records):
                    break
                time.sleep(0.05)
            else:
                chaos_log.append("timeout waiting for first step")
                return
            chaos_log.append("KILL worker0")
            procs_ref[0][0].send_signal(signal.SIGKILL)
            procs_ref[0][0].wait(timeout=10)
            deadline = time.time() + 120
            while driver.num_healthy == 2 and time.time() < deadline:
                time.sleep(0.02)
            if driver.num_healthy == 2:
                chaos_log.append("driver never observed the death")
                return
            chaos_log.append("death observed")
            time.sleep(rng.uniform(0.1, 0.5))
            procs_ref[0][0] = spawn_worker(port=procs_ref[1][0])[0]
            chaos_log.append("RESTART worker0")
            deadline = time.time() + 120
            while driver.num_healthy < 2 and time.time() < deadline:
                time.sleep(0.05)
            chaos_log.append(f"healthy {driver.num_healthy}/2")

        th = threading.Thread(target=chaos_thread, name="chaos", daemon=True)
        th.start()
    trainer.train()
    if th is not None:
        th.join(timeout=150)
        for line in chaos_log:
            print(f"chaos: {line}")
        assert any("KILL" in l for l in chaos_log), (
            "chaos never fired — nothing was proven"
        )
        assert any("RESTART" in l for l in chaos_log), chaos_log
    losses = [m["loss"] for _, m in sink.records if "loss" in m]
    checksum = float(sum(
        np.abs(np.asarray(x)).sum()
        for x in jax.tree_util.tree_leaves(trainer.lora)
    ))
    # counters are report-and-reset and the trainer folds each snapshot
    # into its per-step sink record — total = sum over records + the tail
    # still in the registry
    tail = telemetry.metrics_snapshot()

    def total(name: str) -> float:
        return sum(
            m.get(name, 0.0) for _, m in sink.records
        ) + tail.get(name, 0.0)

    stats = {
        "dispatch_bytes": total("cp/dispatch_bytes"),
        "weight_bytes_sent": total("cp/weight_bytes_sent"),
        "weight_pushes": total("cp/weight_pushes"),
        "weight_full_syncs": total("cp/weight_full_syncs"),
        "weight_sync_ms": (
            engine.bus.last_broadcast_ms if engine.bus is not None else None
        ),
    }
    return losses, checksum, engine, trainer, stats


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--report-json", type=str, default=None,
                    help="write the dispatch-vs-broadcast A/B record here "
                         "(one JSON object)")
    args = ap.parse_args()

    import numpy as np

    from distrl_llm_tpu.distributed import weight_bus as wb

    t_start = time.time()

    # --- 1. golden: legacy dispatch transport ----------------------------
    procs, ports = spawn_fleet()
    print(f"golden fleet on ports {ports}")
    g_losses, g_sum, g_engine, _, g_stats = run_train(ports, "dispatch")
    g_engine.driver.shutdown()
    kill_fleet(procs)
    assert len(g_losses) == 4 and all(np.isfinite(l) for l in g_losses)
    print(f"golden: losses {g_losses} checksum {g_sum:.6f} "
          f"dispatch_bytes {g_stats['dispatch_bytes']:.0f}")

    # --- 2. broadcast: byte-identity + payload shed ----------------------
    procs, ports = spawn_fleet()
    print(f"broadcast fleet on ports {ports}")
    b_losses, b_sum, b_engine, b_trainer, b_stats = run_train(
        ports, "broadcast"
    )
    # versions converge: every worker acked the learner's final version
    assert b_engine.bus.flush(timeout_s=60)
    final_v = b_trainer.weight_version
    assert b_engine.bus.last_acked_version == final_v, (
        b_engine.bus.last_acked_version, final_v,
    )
    # losses + adapter byte-identical to the dispatch golden: the delta
    # codec never altered a single sampled token
    assert b_losses == g_losses, (b_losses, g_losses)
    assert b_sum == g_sum, (b_sum, g_sum)
    # the payload win: dispatch bytes dropped by more than the adapter size
    # per round (8 rounds × 2 shards used to carry the full tree)
    adapter_bytes = len(__import__("pickle").dumps(
        __import__("jax").tree_util.tree_map(np.asarray, b_trainer.lora)
    ))
    shed = g_stats["dispatch_bytes"] - b_stats["dispatch_bytes"]
    assert shed >= adapter_bytes, (shed, adapter_bytes)
    print(f"broadcast: byte-identical to golden; dispatch bytes "
          f"{b_stats['dispatch_bytes']:.0f} (-{shed:.0f}, adapter is "
          f"{adapter_bytes}), weight bytes {b_stats['weight_bytes_sent']:.0f}"
          f" over {b_stats['weight_pushes']:.0f} pushes")
    b_engine.driver.shutdown()
    kill_fleet(procs)

    # --- 3. chaos: kill/rejoin with full-resync convergence --------------
    procs, ports = spawn_fleet()
    print(f"chaos fleet on ports {ports}")
    holder = [procs, ports]
    c_losses, _c_sum, c_engine, c_trainer, _ = run_train(
        ports, "broadcast", chaos=holder
    )
    procs = holder[0]
    assert len(c_losses) == 4 and all(np.isfinite(l) for l in c_losses)
    assert c_trainer.total_samples_processed == 16, (
        c_trainer.total_samples_processed
    )
    assert not c_engine.last_lost_rows
    driver = c_engine.driver
    deadline = time.time() + 60
    while driver.num_healthy < 2 and time.time() < deadline:
        time.sleep(0.1)
    assert driver.num_healthy == 2, "capacity never recovered"
    assert driver.rejoin_epoch >= 1, "no rejoin recorded"
    # versions converge across the kill: both workers hold the learner's
    # final adapter, bit-identical to the driver's copy (the rejoin hook's
    # full-tensor resync + subsequent delta pushes)
    assert c_engine.bus.flush(timeout_s=60)
    final_v = c_trainer.weight_version
    want_crc = wb.checksum_tree(c_engine._bus_lora_np)
    for dbg in driver.dispatch_objects(
        [("weights_debug", {}), ("weights_debug", {})], 60_000
    ):
        assert dbg["current"] == final_v, (dbg, final_v)
        assert dbg["checksums"][final_v] == want_crc, dbg
    print(f"chaos: 4 steps / 16 groups conserved, rejoin epoch "
          f"{driver.rejoin_epoch}, both caches at v{final_v} bit-identical")
    # graceful drain
    procs[0].send_signal(signal.SIGTERM)
    assert procs[0].wait(timeout=15) == 0
    driver.shutdown()
    assert procs[1].wait(timeout=15) == 0

    if args.report_json:
        record = {
            "metric": "weight_bus_ab",
            "rounds": len(g_losses) * 2,  # train + eval rounds per run
            "weight_bus_dispatch_bytes": g_stats["dispatch_bytes"],
            "weight_bus_broadcast_bytes": b_stats["dispatch_bytes"],
            "dispatch_bytes_shed": shed,
            "adapter_bytes": adapter_bytes,
            "weight_bytes_per_update": (
                b_stats["weight_bytes_sent"]
                / max(b_trainer.weight_version + 1, 1)
            ),
            "weight_sync_ms": b_stats["weight_sync_ms"],
            "byte_identical_losses": True,
        }
        with open(args.report_json, "w") as f:
            json.dump(record, f)
        print(f"A/B record → {args.report_json}")

    print(
        f"WEIGHT BUS OK — broadcast byte-identical to dispatch golden, "
        f"payload shed {shed:.0f}B (adapter {adapter_bytes}B), chaos "
        f"kill/rejoin converged, {time.time() - t_start:.0f}s total "
        f"(seed {CHAOS_SEED})"
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
