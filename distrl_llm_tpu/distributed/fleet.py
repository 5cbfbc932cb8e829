"""Elastic fleet supervisor (ISSUE 20): local worker *processes* as a
mutable pool.

The control plane (PR 5/9) already survives workers dying and rejoining,
and `control_plane.DriverClient` now speaks dynamic membership
(``add_worker`` / ``retire_worker``) — but something still has to own the
operating-system side of a scale event: spawn a worker process with the
driver's engine flags, notice that it died (preemption) versus drained
(intentional scale-in), and respawn within a bounded restart budget. That
owner is :class:`FleetSupervisor`.

Division of labor:

* :class:`WorkerSpec` — the argv recipe for one worker. It reuses
  ``worker_main``'s OWN flags (never a parallel spelling), so the GC401/402
  CLI-parity rules keep checking the single source of truth and a spawned
  worker is configured exactly as a hand-started one.
* :class:`FleetSupervisor` — owns the ``Popen`` handles keyed by control
  address. ``scale_to`` is the pool-resize actuator the autoscaling
  governor (control/controllers.py ``AutoscaleGovernor``) steers: grow
  spawns + admits through ``engine.add_worker`` (cold join, full-tensor
  resync via the weight bus); shrink retires through
  ``engine.retire_worker`` (graceful drain — the worker delivers its
  in-flight shard, flushes telemetry, prints ``DRAINED`` and exits 0).
  ``poll`` observes *death* (unexpected exit — the preemption case):
  the dead address is retired from membership (it will never come back on
  that port) and, within ``restart_budget``, a replacement is spawned and
  admitted on a fresh port.

Death vs drain is an exit-status contract, not a guess: a retire the
supervisor initiated that ends in exit 0 (+ the ``DRAINED`` marker) counts
in ``drains``; any other exit of a non-retiring worker counts in
``deaths``. ``tools/fleet_smoke.py`` gates "exactly one drain per retire"
on these counters.

Telemetry: the supervisor publishes ``fleet/target_workers`` (gauge — the
autoscaler setpoint) and ``fleet/scale_events`` (counter — one per
actuation that changed the pool) through the constants owned by ``obs.py``
(single-owner registry discipline; the weight-bus → ``obs/weight_sync_ms``
precedent).
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.obs import FLEET_SCALE_EVENTS, FLEET_TARGET_WORKERS
from distrl_llm_tpu.utils.devices import holds_tpu, worker_env

log = logging.getLogger(__name__)

_HOST = "127.0.0.1"


@dataclass
class WorkerSpec:
    """Argv recipe for one supervised worker process.

    Engine-shaping fields mirror the driver's config (the
    ``connect_remote_engine`` contract: remote engines are configured via
    ``worker_main`` flags); anything beyond the common core rides
    ``extra_args`` verbatim — e.g. ``("--metrics-port", "0")`` or a
    ``--fault-schedule`` for chaos runs. ``env`` overlays the inherited
    environment (``DISTRL_OBS=1`` for fleet-aggregation runs, forced
    ``JAX_PLATFORMS=cpu`` in tests); a worker that is not a CPU worker is
    also given its own TPU chip there (``utils.devices.worker_env``).
    """

    serve_model: str | None = None
    max_prompt_tokens: int = 350
    max_new_tokens: int = 1200
    seed: int = 0
    lora_rank: int = 32
    lora_alpha: float = 16.0
    engine_impl: str = "dense"
    extra_args: tuple[str, ...] = ()
    env: dict[str, str] = field(default_factory=dict)

    def argv(self) -> list[str]:
        argv = [
            sys.executable, "-m",
            "distrl_llm_tpu.distributed.worker_main", "--port", "0",
        ]
        if self.serve_model:
            argv += [
                "--serve-model", self.serve_model,
                "--max-prompt-tokens", str(self.max_prompt_tokens),
                "--max-new-tokens", str(self.max_new_tokens),
                "--seed", str(self.seed),
                "--lora-rank", str(self.lora_rank),
                "--lora-alpha", str(self.lora_alpha),
                "--engine-impl", self.engine_impl,
            ]
        argv += list(self.extra_args)
        return argv


def spec_from_config(config) -> WorkerSpec:
    """Driver TrainConfig → worker argv recipe. Every field maps through
    ``worker_main``'s OWN flags or the documented GC401 alias table
    (``--model``→``--serve-model``, ``--max_lora_rank``→``--lora-rank``,
    ``--workers_capture_logprobs``→``--capture-logprobs``), so a
    supervisor-spawned scale-up worker is configured exactly as the
    hand-started fleet the driver connected to."""
    extra: list[str] = []
    if getattr(config, "workers_capture_logprobs", False):
        extra.append("--capture-logprobs")
    return WorkerSpec(
        serve_model=config.model,
        max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        lora_rank=config.max_lora_rank,
        lora_alpha=config.lora_alpha,
        engine_impl=(
            "paged" if str(config.engine_impl).startswith("paged")
            else "dense"
        ),
        extra_args=tuple(extra),
        # piggyback registry snapshots on RPC results: the fleet
        # aggregator's per-worker rates are the autoscaler's victim marks
        env={"DISTRL_OBS": "1"},
    )


@dataclass
class _Proc:
    # None = an ADOPTED worker: started externally (the --rollout_workers
    # CLI contract), so the supervisor can retire it through the control
    # plane's drain but cannot observe its exit status or respawn it
    proc: subprocess.Popen | None
    address: tuple[str, int]
    chip: int | None = None  # the TPU chip its environment names (None: CPU)
    retiring: bool = False   # supervisor-initiated drain in progress
    drained: bool = False    # exit 0 after a retire (the SIGTERM contract)


class FleetSupervisor:
    """Owns local worker processes and the pool-resize actuator.

    Thread-safety: the autoscaling governor actuates from the trainer's
    control pass while ``poll`` may run from the same loop — one mutex
    guards the process table and counters. Process waits happen OUTSIDE
    the mutex (a draining worker finishing its in-flight shard must not
    stall membership queries).
    """

    def __init__(self, spec: WorkerSpec, *, min_workers: int = 1,
                 max_workers: int = 4, restart_budget: int = 3,
                 spawn_timeout_s: float = 120.0, engine=None) -> None:
        if not (1 <= min_workers <= max_workers):
            raise ValueError(
                f"need 1 <= min_workers <= max_workers, got "
                f"[{min_workers}, {max_workers}]"
            )
        self.spec = spec
        self.min_workers = int(min_workers)
        self.max_workers = int(max_workers)
        self.restart_budget = int(restart_budget)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.engine = engine
        self._mu = threading.Lock()
        self._procs: dict[tuple[str, int], _Proc] = {}
        self._target = 0
        self._restarts_used = 0
        # the death/drain ledger fleet_smoke gates on
        self.drains = 0
        self.deaths = 0
        self.scale_events = 0

    # ------------------------------------------------------------ queries

    def addresses(self) -> list[tuple[str, int]]:
        with self._mu:
            return [r.address for r in self._procs.values() if not r.retiring]

    @property
    def target_workers(self) -> int:
        return self._target

    @property
    def pool_size(self) -> int:
        return len(self.addresses())

    def attach(self, engine) -> None:
        """Bind the remote engine AFTER connect (start() runs pre-connect:
        the initial pool must exist before ``connect_remote_engine`` dials
        it). Also hangs this supervisor off the engine so the trainer's
        control wiring finds it (``engine.fleet_supervisor``)."""
        self.engine = engine
        engine.fleet_supervisor = self

    # ------------------------------------------------------------ spawn

    def _spawn(self) -> _Proc:
        # one process per chip: the worker's chip is named in its
        # environment, and a driver that holds every chip itself is told so
        # here instead of starting a child that hangs
        with self._mu:
            in_use = [
                r.chip for r in self._procs.values()
                if r.chip is not None and r.proc is not None
                and r.proc.poll() is None
            ]
        env, chip = worker_env(
            os.environ, self.spec.env, chips_in_use=in_use,
            parent_holds_tpu=holds_tpu(),
        )
        proc = subprocess.Popen(
            self.spec.argv(), stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, env=env,
        )
        port = None
        deadline = time.monotonic() + self.spawn_timeout_s
        assert proc.stdout is not None
        # worker_main prints "PORT <n>" first; METRICS/GATEWAY lines may
        # follow — stop at PORT, the rest of the pipe stays tiny (DRAINED
        # is the only other line a quiet worker emits)
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
        if port is None:
            proc.kill()
            proc.wait()
            raise RuntimeError(
                f"worker failed to report PORT within {self.spawn_timeout_s}s "
                f"(exit {proc.returncode})"
            )
        return _Proc(proc=proc, address=(_HOST, port), chip=chip)

    def start(self, n: int) -> list[tuple[str, int]]:
        """Spawn the initial pool (pre-connect: no admission — the caller
        hands these addresses to ``connect_remote_engine``)."""
        n = max(self.min_workers, min(int(n), self.max_workers))
        spawned = []
        for _ in range(n):
            rec = self._spawn()
            spawned.append(rec.address)
            with self._mu:
                self._procs[rec.address] = rec
        self._set_target(n)
        return spawned

    def adopt(self, addresses) -> None:
        """Register externally-started workers (the ``--rollout_workers``
        CLI path): the supervisor can retire them through the control
        plane's graceful drain, but without the Popen handle it cannot
        observe their exit or respawn them — scale-up past the adopted set
        still spawns owned workers from ``spec``."""
        for address in addresses:
            addr = self._parse(address)
            with self._mu:
                if addr not in self._procs:
                    self._procs[addr] = _Proc(proc=None, address=addr)
        self._set_target(max(self._target, self.pool_size))

    def _set_target(self, target: int) -> None:
        self._target = int(target)
        telemetry.gauge_set(FLEET_TARGET_WORKERS, float(self._target))

    # ------------------------------------------------------------ resize

    def scale_to(self, target: int, *,
                 victims: tuple | list = ()) -> int:
        """The pool-resize actuator: converge the live pool to ``target``
        (clamped to [min_workers, max_workers]). Grow spawns + admits cold
        through the engine; shrink retires ``victims`` first (the
        autoscaler passes the least-productive workers), then newest-first.
        Returns the new target. One actuation that changes the pool counts
        one ``fleet/scale_events``."""
        target = max(self.min_workers, min(int(target), self.max_workers))
        before = self.pool_size
        while self.pool_size < target:
            if not self._grow_one():
                break
        if self.pool_size > target:
            order = [tuple(self._parse(v)) for v in victims]
            pool = self.addresses()
            # newest-first for the remainder: the coldest workers hold the
            # least warm state (compile caches, KV residency)
            order += [a for a in reversed(pool) if a not in order]
            for addr in order:
                if self.pool_size <= target:
                    break
                self.retire(addr)
        changed = self.pool_size != before or target != self._target
        self._set_target(target)
        if changed:
            self.scale_events += 1
            telemetry.counter_add(FLEET_SCALE_EVENTS)
        return target

    @staticmethod
    def _parse(address) -> tuple[str, int]:
        if isinstance(address, str):
            host, _, port = address.rpartition(":")
            return (host or _HOST, int(port))
        return (address[0], int(address[1]))

    def _grow_one(self) -> bool:
        try:
            rec = self._spawn()
        except RuntimeError:
            log.exception("fleet: spawn failed during scale-up")
            return False
        admitted = True
        if self.engine is not None:
            admitted = bool(self.engine.add_worker(rec.address))
        if not admitted:
            # a worker the driver cannot admit is dead weight — reap it
            rec.proc.kill()
            rec.proc.wait()
            log.warning("fleet: admission failed for %s:%d, reaped",
                        *rec.address)
            return False
        with self._mu:
            self._procs[rec.address] = rec
        log.info("fleet: worker %s:%d joined (pool=%d)",
                 rec.address[0], rec.address[1], self.pool_size)
        return True

    def retire(self, address, *, timeout_s: float = 30.0) -> bool:
        """Intentional scale-in of one worker: retire from membership
        (graceful drain — the control plane's MSG_SHUTDOWN contract), wait
        for the process to exit, and book death-vs-drain by exit status."""
        addr = self._parse(address)
        with self._mu:
            rec = self._procs.get(addr)
            if rec is None or rec.retiring:
                return False
            rec.retiring = True
        drained_cp = None
        if self.engine is not None:
            drained_cp = bool(self.engine.retire_worker(addr, drain=True))
        elif rec.proc is not None and rec.proc.poll() is None:
            # standalone (no engine attached): the SIGTERM half of the
            # same contract — worker_main drains and exits 0
            rec.proc.send_signal(signal.SIGTERM)
        if rec.proc is None:
            # adopted worker: no exit status to observe — trust the
            # control plane's drain handshake (MSG_SHUTDOWN acked)
            rc = 0 if drained_cp else 1
        else:
            try:
                rc = rec.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                rec.proc.kill()
                rc = rec.proc.wait()
        rec.drained = rc == 0
        with self._mu:
            self._procs.pop(addr, None)
            if rec.drained:
                self.drains += 1
            else:
                self.deaths += 1
        log.info("fleet: worker %s:%d retired (%s, pool=%d)",
                 addr[0], addr[1], "drained" if rec.drained else
                 f"exit {rc}", self.pool_size)
        return rec.drained

    # ------------------------------------------------------------ observe

    def poll(self) -> dict:
        """Observe the pool once: unexpected exits (preemption) are
        *deaths* — the dead address is retired from membership (that port
        never comes back) and, within ``restart_budget``, a replacement is
        spawned and admitted on a fresh port. Returns a summary dict the
        autoscaler and fleet_smoke read."""
        dead: list[tuple[str, int]] = []
        with self._mu:
            for addr, rec in list(self._procs.items()):
                if (rec.proc is not None and not rec.retiring
                        and rec.proc.poll() is not None):
                    dead.append(addr)
                    del self._procs[addr]
                    self.deaths += 1
        for addr in dead:
            log.warning("fleet: worker %s:%d died unexpectedly", *addr)
            if self.engine is not None:
                # terminal membership exit: without this the rejoin thread
                # re-dials a port that will never answer again
                self.engine.retire_worker(addr, drain=False)
        respawned = 0
        while (dead and self.pool_size < self._target
               and self._restarts_used < self.restart_budget):
            self._restarts_used += 1
            if self._grow_one():
                respawned += 1
            else:
                break
        return {
            "pool": self.pool_size, "target": self._target,
            "dead": len(dead), "respawned": respawned,
            "restarts_left": self.restart_budget - self._restarts_used,
            "drains": self.drains, "deaths": self.deaths,
            "scale_events": self.scale_events,
        }

    # ------------------------------------------------------------ teardown

    def close(self) -> None:
        """Reap every owned process (tests/smokes; not a graceful drain)."""
        with self._mu:
            recs = list(self._procs.values())
            self._procs.clear()
        for rec in recs:
            if rec.proc is None:
                continue  # adopted — not ours to reap
            if rec.proc.poll() is None:
                rec.proc.kill()
            rec.proc.wait()
