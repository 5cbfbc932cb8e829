#!/usr/bin/env python
"""Elastic-fleet acceptance gate (ISSUE 20): a supervised worker pool
scales 2→4→2 under fake load signals with intact accounting, survives a
seeded SIGKILL mid-scale-event, and the armed-but-quiescent autoscaler is
byte-identical to controllers-off.

Two gates, end to end on a CPU host:

1. **Elastic 2→4→2** — a real FleetSupervisor-owned pool (tiny-model
   workers, obs piggyback armed) behind a real RemoteEngine, steered by a
   real AutoscaleGovernor fed FAKE serving-queue-wait metrics:

   * calm prelude: zero actions, pool holds at 2;
   * breach (queue wait 5x its threshold): exactly one cooldown-spaced
     scale-up per pass until the pool converges to fleet_max=4 — each new
     worker spawned, PING-verified, admitted cold, and answering
     dispatches (group conservation across the scale event);
   * seeded chaos: SIGKILL one owned worker DURING the scale-up — the
     governor's poll pass observes the death, retires the dead port from
     membership (the rejoin loop must never re-dial it), respawns within
     the restart budget, and the pool still converges to 4 with a bounded
     actuation count (no oscillation);
   * deadband (load 0.8x): hysteresis hold, no actions;
   * sustained low throughput (echo-only traffic, per-worker rate under
     tok_s_low for the dwell): one scale-down per cooldown window back to
     fleet_min=2, each retire a graceful drain — EXACTLY one drain per
     retire, zero extra deaths;
   * throughout: fleet/gen_tokens_total is monotone (scaled-in workers'
     counters fold into the fleet base, never vanish), and no dead track
     leaks into the aggregator's worker_metrics table.

2. **Armed-but-quiescent byte-identity** — two twin 2-worker tiny TRAIN
   runs (the chaos_smoke topology): --control_autoscale armed with fleet
   bounds [2, 4] but no load signal breached produces a loss sequence and
   final adapter checksum byte-identical to the controllers-off run, with
   zero control actions taken.

Exit 0 = the elastic fleet held; nonzero otherwise.
``tools/run_all_checks.sh`` runs this as the fleet stage.
"""

from __future__ import annotations

import os
import random
import signal
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P_LEN, MAX_NEW = 8, 6
FLEET_SEED = int(os.environ.get("FLEET_SEED", "0"))

_checks: list[str] = []


def check(name: str, ok: bool, detail: str = "") -> None:
    _checks.append(name)
    status = "ok" if ok else "FAIL"
    print(f"  {status}: {name}" + (f" ({detail})" if detail and not ok else ""))
    assert ok, f"{name}: {detail}"


# --------------------------------------------------------------- gate 1


def gate_elastic() -> None:
    import jax
    import numpy as np

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.control import AutoscaleGovernor, ControlRuntime
    from distrl_llm_tpu.distributed import RetryPolicy, connect_remote_engine
    from distrl_llm_tpu.distributed.fleet import FleetSupervisor, WorkerSpec
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.obs import FleetAggregator
    from distrl_llm_tpu.serving_obs import SERVING_QUEUE_WAIT_MS

    telemetry.reset()
    qw = SERVING_QUEUE_WAIT_MS + "_max"
    rng = random.Random(FLEET_SEED)

    spec = WorkerSpec(
        serve_model="tiny", max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        seed=7, lora_rank=4, lora_alpha=8.0,
        env={"DISTRL_OBS": "1", "JAX_PLATFORMS": "cpu"},
    )
    sup = FleetSupervisor(spec, min_workers=2, max_workers=4,
                          restart_budget=2)
    addrs = sup.start(2)
    print(f"initial pool: {addrs}")
    engine = connect_remote_engine(
        addrs, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        timeout_ms=120_000, lora_scale=lora_scale(4, 8.0),
        retry_policy=RetryPolicy(
            max_call_retries=2, base_s=0.05, seed=FLEET_SEED
        ),
        rejoin=True,
    )
    sup.attach(engine)
    driver = engine.driver
    agg = FleetAggregator(driver)
    provider = lambda: agg.refresh(force=True)  # noqa: E731

    runtime = ControlRuntime(budget=16)
    gov = AutoscaleGovernor(
        sup, provider, min_workers=2, max_workers=4,
        queue_wait_high_ms=100.0, tok_s_low=5.0,
        release_frac=0.7, cooldown_steps=2, dwell_steps=2,
    )
    runtime.register(gov)

    totals: list[float] = []

    def snap_total() -> float:
        t = float(provider()["gen_tokens_total"])
        totals.append(t)
        return t

    def echo_round(n: int = 8) -> None:
        got = driver.dispatch_objects(
            [("echo", i) for i in range(n)], 60_000
        )
        assert got == list(range(n)), got

    ids = np.random.default_rng(0).integers(
        1, 16, size=(8, P_LEN)
    ).astype(np.int32)
    mask = np.ones((8, P_LEN), np.int32)
    sampling = SamplingConfig(max_tokens=MAX_NEW, temperature=0.0, n=1)

    def generate_round(tag: str) -> None:
        out = engine.generate(
            None, None, ids, mask, sampling, jax.random.PRNGKey(0)
        )
        assert out.tokens.shape == (8, 1, MAX_NEW), out.tokens.shape
        # kept + lost == batch, with lost == 0: nothing quarantined or
        # degraded away across the scale event
        assert not engine.last_lost_rows, (tag, engine.last_lost_rows)

    step = 0

    # ---- calm prelude: armed governor, zero actions ----------------------
    for _ in range(3):
        assert gov.step(step, {}, runtime) == []
        step += 1
    check("calm prelude takes zero actions", runtime.actions_taken == 0)
    check("calm prelude holds the pool", sup.pool_size == 2)

    generate_round("prelude")
    time.sleep(0.1)
    snap_total()
    check("worker token counters flow into the fleet total", totals[-1] > 0,
          str(totals))

    # ---- breach: scale up to fleet_max, SIGKILL mid-event ---------------
    high = {qw: 500.0}
    killed = False
    deadline = time.time() + 300
    while sup.pool_size < 4 and time.time() < deadline:
        gov.step(step, high, runtime)
        step += 1
        echo_round()
        if not killed and sup.pool_size >= 3:
            # seeded chaos: kill one OWNED worker while the scale event is
            # still in flight — the next governor pass must observe the
            # death, retire the port, respawn within budget, and still
            # converge to the target
            owned = [
                r for r in list(sup._procs.values()) if r.proc is not None
            ]
            victim = rng.choice(owned)
            print(f"chaos: SIGKILL {victim.address} mid-scale-up")
            victim.proc.send_signal(signal.SIGKILL)
            victim.proc.wait(timeout=10)
            killed = True
            # conservation through the degraded window: the dead conn's
            # shard resubmits to survivors
            echo_round()
    check("chaos arm fired during the scale-up", killed)
    check("pool converged to fleet_max=4", sup.pool_size == 4,
          f"pool={sup.pool_size}")
    # let any straggling admission settle, then confirm capacity
    deadline = time.time() + 60
    while driver.num_healthy < 4 and time.time() < deadline:
        gov.step(step, high, runtime)
        step += 1
        time.sleep(0.1)
    check("driver admits all 4 (healthy)", driver.num_healthy == 4,
          f"healthy={driver.num_healthy}")
    check("exactly one death observed (the SIGKILL)", sup.deaths == 1,
          f"deaths={sup.deaths}")
    check("no drains yet", sup.drains == 0, f"drains={sup.drains}")
    check(
        "bounded actuation: exactly 2 scale-ups, no oscillation",
        runtime.actions_taken == 2, f"actions={runtime.actions_taken}",
    )

    generate_round("scaled-up")
    time.sleep(0.1)
    snap_total()

    # ---- deadband: hysteresis hold --------------------------------------
    acted_before = runtime.actions_taken
    for _ in range(3):
        assert gov.step(step, {qw: 80.0}, runtime) == []
        step += 1
    check("deadband holds (no actions at 0.8x load)",
          runtime.actions_taken == acted_before)

    # ---- sustained low throughput: scale down to fleet_min --------------
    low = {qw: 10.0}
    deadline = time.time() + 300
    while sup.pool_size > 2 and time.time() < deadline:
        echo_round()  # echo-only traffic: fresh obs snapshots, zero tok/s
        gov.step(step, low, runtime)
        step += 1
        snap_total()
    check("pool converged back to fleet_min=2", sup.pool_size == 2,
          f"pool={sup.pool_size}")
    check(
        "exactly one graceful drain per retire",
        sup.drains == 2 and sup.deaths == 1,
        f"drains={sup.drains} deaths={sup.deaths}",
    )
    check(
        "bounded actuation: exactly 2 scale-downs",
        runtime.actions_taken == 4, f"actions={runtime.actions_taken}",
    )

    # min bound holds under continued low signal
    acted_before = runtime.actions_taken
    for _ in range(3):
        echo_round()
        gov.step(step, low, runtime)
        step += 1
    check("fleet_min bound holds (no actions below min)",
          runtime.actions_taken == acted_before)

    # ---- accounting ------------------------------------------------------
    fleet = provider()
    snap_total()
    check(
        "fleet/gen_tokens_total is monotone across scale events",
        all(b >= a for a, b in zip(totals, totals[1:])), str(totals),
    )
    check("workers_total excludes retired members",
          fleet["workers_total"] == 2, str(fleet["workers_total"]))
    check("both survivors healthy", fleet["workers_healthy"] == 2)
    live = {f"{h}:{p}" for h, p in sup.addresses()}
    check(
        "no dead track leaks into worker_metrics",
        set(fleet["worker_metrics"]) <= live and len(
            fleet["worker_metrics"]
        ) == 2,
        f"{set(fleet['worker_metrics'])} vs {live}",
    )
    leaked = {
        t for t in telemetry.remote_metrics()
        if t.removeprefix("worker ") not in live
    }
    check("no dead track leaks into the telemetry registry", not leaked,
          str(leaked))
    snap = telemetry.metrics_snapshot()
    check("fleet/target_workers gauge landed at 2",
          snap.get("fleet/target_workers") == 2.0,
          str(snap.get("fleet/target_workers")))
    check(
        "fleet/scale_events counted every pool change",
        snap.get("fleet/scale_events") == float(sup.scale_events)
        and sup.scale_events == 4,
        f"counter={snap.get('fleet/scale_events')} "
        f"sup={sup.scale_events}",
    )

    generate_round("final")
    driver.shutdown()
    sup.close()


# --------------------------------------------------------------- gate 2


def _spawn_tiny_worker():
    import subprocess

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "distrl_llm_tpu.distributed.worker_main",
            "--port", "0", "--serve-model", "tiny",
            "--max-prompt-tokens", str(P_LEN),
            "--max-new-tokens", str(MAX_NEW),
            "--seed", "7", "--lora-rank", "4", "--lora-alpha", "8",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTRL_OBS": "1"},
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), f"worker failed to start: {line!r}"
    return proc, int(line.split()[1])


def _run_twin(armed: bool):
    import jax

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.distributed import RetryPolicy, connect_remote_engine
    from distrl_llm_tpu.metrics import MemorySink
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.rewards import reward_function
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    telemetry.reset()
    procs, ports = [], []
    for _ in range(2):
        p, port = _spawn_tiny_worker()
        procs.append(p)
        ports.append(port)
    addrs = [("127.0.0.1", p) for p in ports]
    extra = {}
    if armed:
        extra = dict(
            control_autoscale=True, fleet_min=2, fleet_max=4,
            control_cooldown_steps=0,
        )
    cfg = TrainConfig(
        model="tiny", episodes=2, batch_size=4, num_candidates=2, topk=2,
        train_batch_size=4, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        number_of_actors=1, number_of_learners=1, learner_chunk_size=1,
        eval_every=0, save_every=0, metrics_backend="null", lr=1e-2,
        max_lora_rank=4, lora_alpha=8, learner="grpo", eval_n=2,
        # the applicability contract: autoscale needs a dynamic worker
        # pool (rollout_workers + worker_rejoin) and fleet bounds
        rollout_workers=[f"127.0.0.1:{p}" for p in ports],
        worker_rejoin=True,
        **extra,
    )
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems,
             "solution": [p.strip()[-1].upper() for p in problems]}
    test = {k: v[:4] for k, v in train.items()}
    base = init_params(jax.random.PRNGKey(7), TINY)
    engine = connect_remote_engine(
        addrs, max_prompt_tokens=P_LEN, max_new_tokens=MAX_NEW,
        timeout_ms=120_000,
        lora_scale=lora_scale(cfg.max_lora_rank, cfg.lora_alpha),
        retry_policy=RetryPolicy(max_call_retries=2, base_s=0.05, seed=0),
        rejoin=True,
    )
    supervisor = None
    if armed:
        from distrl_llm_tpu.distributed.fleet import (
            FleetSupervisor, WorkerSpec,
        )

        supervisor = FleetSupervisor(
            WorkerSpec(
                serve_model="tiny", max_prompt_tokens=P_LEN,
                max_new_tokens=MAX_NEW, seed=7, lora_rank=4,
                lora_alpha=8.0, env={"DISTRL_OBS": "1"},
            ),
            min_workers=2, max_workers=4,
        )
        supervisor.adopt(addrs)
        supervisor.attach(engine)
    sink = MemorySink()
    trainer = Trainer(
        train, test, reward_function, cfg,
        tokenizer=tok, engine=engine, base_params=base, model_cfg=TINY,
        sink=sink,
    )
    trainer.train()
    trainer.close_obs()
    losses = [m["loss"] for _, m in sink.records if "loss" in m]
    checksum = float(sum(
        abs(float(x.sum())) for x in jax.tree_util.tree_leaves(trainer.lora)
    ))
    actions = (
        trainer.control.actions_taken if trainer.control is not None else 0
    )
    governors = (
        [getattr(g, "name", "?") for g in trainer.control.governors]
        if trainer.control is not None else []
    )
    engine.driver.shutdown()
    for p in procs:
        rc = p.wait(timeout=15)
        assert rc == 0, f"worker exited {rc}"
    if supervisor is not None:
        supervisor.close()
    return losses, checksum, actions, governors


def gate_quiescent() -> None:
    base_losses, base_sum, _, _ = _run_twin(armed=False)
    armed_losses, armed_sum, actions, governors = _run_twin(armed=True)
    check("armed run registered the autoscale governor",
          "autoscale" in governors, str(governors))
    check("armed-but-quiescent run took zero control actions",
          actions == 0, str(actions))
    check(
        "quiescent loss sequence byte-identical to controllers-off",
        base_losses == armed_losses,
        f"{base_losses} vs {armed_losses}",
    )
    check("quiescent adapter checksum byte-identical",
          base_sum == armed_sum, f"{base_sum} vs {armed_sum}")


def main() -> int:
    t0 = time.time()
    print("== gate 1: elastic 2→4→2 with seeded chaos")
    gate_elastic()
    print("== gate 2: armed-but-quiescent byte-identity")
    gate_quiescent()
    print(
        f"FLEET OK — {len(_checks)} checks, "
        f"{time.time() - t0:.0f}s total (seed {FLEET_SEED})"
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
