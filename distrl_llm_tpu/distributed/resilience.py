"""Resilience primitives for the distributed rollout path.

The paper's reference stack treats ``ray.get(timeout=240)`` as its only
failure detector — a worker death kills the run (SURVEY §5). Our control
plane already resubmits shards away from a dead worker; this module adds the
remaining failure half (LlamaRL/Laminar-style fault isolation, PAPERS.md):

* :class:`RetryPolicy` — seeded exponential backoff with jitter plus
  per-call and per-round deadline budgets. Seeded, so two policies built
  from the same config produce the same delay sequence (deterministic
  tests AND deterministic chaos runs).
* :class:`WorkerError` / :func:`classify_worker_error` — a worker-side
  exception (MSG_ERROR frame) classified transient-vs-fatal by its
  exception type: transport/timeout flavors are retried under the policy,
  deterministic program errors (ValueError, unknown op, …) propagate
  immediately.
* :class:`ShardFailedError` — the poison-shard quarantine signal: a shard
  that failed on K distinct workers names itself instead of grinding every
  worker to unhealthy.
* :class:`FaultInjector` — wraps :class:`~.control_plane.Connection` to
  deterministically delay, drop, close, or error frames on a scripted
  schedule. Driven by ``DISTRL_FAULT_SCHEDULE`` (env) or ``install()``
  (tests), so worker subprocesses and the driver share one spec string.

Telemetry series contract (names pinned by tests/test_telemetry.py):
``cp/healthy_workers`` (gauge), ``cp/reconnects``, ``cp/resubmits``,
``cp/retries``, ``cp/poison_shards``, ``cp/degraded_groups``,
``cp/retires`` (counters),
plus ``cp/reconnect`` / ``cp/retry`` / ``cp/resubmit`` spans while tracing.
The weight bus (weight_bus.py, ISSUE 9) adds ``cp/dispatch_bytes``,
``cp/weight_bytes_sent``, ``cp/weight_pushes``, ``cp/weight_full_syncs``,
``cp/weight_rerequests`` (counters), ``cp/weight_broadcast_ms`` (histogram:
learner push → last worker ack per version), and ``cp/weight_push`` spans.
"""

from __future__ import annotations

import logging
import os
import random
import re
import threading
import time
from dataclasses import dataclass, field

log = logging.getLogger(__name__)

# -------------------------------------------------------- telemetry contract

CP_HEALTHY_GAUGE = "cp/healthy_workers"
CP_RECONNECTS = "cp/reconnects"
CP_RESUBMITS = "cp/resubmits"
CP_RETRIES = "cp/retries"
CP_POISON_SHARDS = "cp/poison_shards"
CP_DEGRADED_GROUPS = "cp/degraded_groups"
CP_REJOIN_EPOCH = "cp/rejoin_epoch"  # gauge: bumps per re-admit
# proactive health demotions (ISSUE 14 worker-health controller): the
# worker was alive but regressing, so the controller quarantined it and
# left the rejoin loop to probe + re-admit
CP_QUARANTINES = "cp/quarantines"
# intentional scale-in retirements (ISSUE 20 elastic fleet): a retired
# worker is TERMINAL membership state — drained, never re-dialed, and
# never counted against the quarantine/reconnect series
CP_RETIRES = "cp/retires"
# ---- weight bus (weight_bus.py, ISSUE 9) ----
CP_DISPATCH_BYTES = "cp/dispatch_bytes"        # counter: MSG_DISPATCH payload bytes
CP_WEIGHT_BYTES = "cp/weight_bytes_sent"       # counter: MSG_WEIGHTS payload bytes
CP_WEIGHT_PUSHES = "cp/weight_pushes"          # counter: per-worker weight pushes
CP_WEIGHT_FULL_SYNCS = "cp/weight_full_syncs"  # counter: full-tensor (non-delta) sends
CP_WEIGHT_REREQUESTS = "cp/weight_rerequests"  # counter: unknown-version re-pushes
CP_WEIGHT_BROADCAST_MS = "cp/weight_broadcast_ms"  # hist: push → last worker ack
# ---- RPC latency histogram (control_plane.py) ----
CP_RPC_DISPATCH_MS = "cp/rpc_dispatch_ms"  # hist: dispatch → result frame

FAULT_SCHEDULE_ENV = "DISTRL_FAULT_SCHEDULE"


# --------------------------------------------------------------- exceptions


class WorkerError(RuntimeError):
    """A worker-side exception shipped back as an ERROR frame.

    ``transient`` says whether the control plane may retry the call under
    its :class:`RetryPolicy` (transport/timeout flavors) or must propagate
    it (deterministic program errors)."""

    def __init__(self, address: tuple[str, int] | str, traceback_text: str,
                 *, transient: bool):
        super().__init__(f"worker {address} raised:\n{traceback_text}")
        self.address = address
        self.traceback_text = traceback_text
        self.transient = transient


class ShardFailedError(RuntimeError):
    """A shard failed on K distinct workers (or exhausted its attempt cap):
    the poison-shard quarantine signal. Names the shard so the caller can
    drop its groups instead of the run."""

    def __init__(self, shard_index: int, *, workers=(), attempts: int = 0,
                 message: str | None = None):
        self.shard_index = shard_index
        self.workers = tuple(workers)
        self.attempts = attempts
        if message is None:
            message = (
                f"shard {shard_index} quarantined after failing on "
                f"{len(self.workers)} distinct worker(s) "
                f"({', '.join(str(w) for w in self.workers)}; "
                f"{attempts} failed attempt(s))"
            )
        super().__init__(message)


# Exception TYPE names considered transient when they arrive in a worker
# traceback: transport hiccups, timeouts, and resource pressure a retry can
# plausibly outlive. Everything else (ValueError, TypeError, shape errors,
# "unknown op", …) is deterministic and fatal — retrying it would burn the
# whole round's deadline reproducing the same failure.
_TRANSIENT_TYPES = frozenset({
    "OSError", "IOError", "ConnectionError", "ConnectionResetError",
    "ConnectionAbortedError", "ConnectionRefusedError", "BrokenPipeError",
    "TimeoutError", "EOFError", "InterruptedError", "BlockingIOError",
})

_EXC_LINE = re.compile(r"^([A-Za-z_][A-Za-z0-9_.]*)(?::|$)")


def classify_worker_error(traceback_text: str) -> bool:
    """True when a worker traceback's final exception type is transient.

    A handler can also force the transient classification by including the
    literal marker ``[transient]`` in its exception message."""
    if "[transient]" in traceback_text:
        return True
    for line in reversed(traceback_text.strip().splitlines()):
        line = line.strip()
        if not line:
            continue
        m = _EXC_LINE.match(line)
        if m:
            name = m.group(1).rsplit(".", 1)[-1]
            return name in _TRANSIENT_TYPES
        # message-continuation line of a multi-line exception repr: keep
        # scanning upward for the "Type: message" line
    return False


# -------------------------------------------------------------- retry policy


@dataclass
class RetryPolicy:
    """Seeded exponential backoff + deadline budgets for control-plane RPC.

    ``backoff(attempt)`` draws jitter from a private, lock-guarded
    ``random.Random(seed)``: two policies built with the same fields replay
    the same delay sequence for the same CALL ORDER. Single-threaded
    callers (tests, the rejoin loop alone, the chaos harness's assertions)
    therefore replay exactly; when several drain threads share one policy
    the per-draw values are still seed-derived but their interleaving
    follows thread scheduling — only the sequence as a whole, not its
    assignment to threads, is reproducible.
    """

    max_call_retries: int = 2       # transient retries per RPC (after try 1)
    base_s: float = 0.05            # first backoff delay
    multiplier: float = 2.0
    max_backoff_s: float = 2.0      # delay cap
    jitter: float = 0.1             # ± fraction applied to each delay
    seed: int = 0
    call_budget_s: float | None = None   # wall budget across one RPC's retries
    round_budget_s: float | None = None  # wall budget for a dispatch round
    max_shard_attempts: int = 6     # failed dispatches per shard before quarantine

    _rng: random.Random = field(init=False, repr=False, compare=False)
    _rng_mu: threading.Lock = field(init=False, repr=False, compare=False)

    def __post_init__(self):
        if self.max_call_retries < 0:
            raise ValueError(
                f"max_call_retries must be >= 0, got {self.max_call_retries}"
            )
        if self.base_s < 0 or self.max_backoff_s < 0:
            raise ValueError("backoff delays must be >= 0")
        if self.max_shard_attempts < 1:
            raise ValueError(
                f"max_shard_attempts must be >= 1, got {self.max_shard_attempts}"
            )
        self._rng = random.Random(self.seed)
        self._rng_mu = threading.Lock()

    def backoff(self, attempt: int) -> float:
        """Delay before retry ``attempt`` (0-based): exponential with the
        policy's seeded jitter, capped at ``max_backoff_s``. The rng draw
        is lock-guarded — drain threads and the rejoin loop share one
        policy instance."""
        d = min(self.base_s * self.multiplier ** max(attempt, 0),
                self.max_backoff_s)
        if self.jitter:
            with self._rng_mu:
                jitter_draw = self._rng.random()
            d *= 1.0 + self.jitter * (2.0 * jitter_draw - 1.0)
        return max(d, 0.0)


# ------------------------------------------------------------ fault injection


@dataclass
class _Rule:
    op: str                  # "send" | "recv"
    index: int | None        # 1-based call number; None = probabilistic
    action: str              # "delay" | "drop" | "close" | "error"
    arg: float | None = None  # delay seconds
    prob: float | None = None
    # channel selector (ISSUE 14 satellite): None matches every connection
    # (the historical process-global schedule); a named channel matches
    # only connections wrapped with that channel — "weights" targets the
    # weight bus's out-of-band MSG_WEIGHTS connections independently of
    # the "dispatch" control-plane connections, with its own call counter
    channel: str | None = None


def _parse_schedule(spec: str) -> tuple[int, list[_Rule]]:
    """Parse a schedule spec. Grammar (``;``-separated items)::

        seed=SEED
        OP:N=ACTION            # the Nth OP call (1-based) takes ACTION
        OP:*=ACTION@P          # every OP call takes ACTION with prob P
        CHANNEL.OP:N=ACTION    # the Nth OP call ON THAT CHANNEL only
        CHANNEL.OP:*=ACTION@P  # per-channel probabilistic rule

    where OP is ``send``/``recv``, ACTION is ``drop`` | ``close`` |
    ``error`` | ``delay:SECONDS``, and CHANNEL names a connection class —
    ``dispatch`` (control-plane RPC, the default every unprefixed rule
    also matches) or ``weights`` (the weight bus's out-of-band
    MSG_WEIGHTS connections, ISSUE 9). Channel-scoped rules advance a
    per-channel call counter, so a ``weights.send:2=close`` fires on the
    second weight-bus send regardless of how many dispatch frames
    interleave. Example:
    ``"seed=7;recv:3=close;weights.send:2=close;send:*=delay:0.05@0.2"``.
    """
    seed = 0
    rules: list[_Rule] = []
    for item in spec.split(";"):
        item = item.strip()
        if not item:
            continue
        if item.startswith("seed="):
            seed = int(item[len("seed="):])
            continue
        try:
            lhs, rhs = item.split("=", 1)
            op, idx = lhs.split(":", 1)
            op = op.strip()
            channel = None
            if "." in op:
                channel, _, op = op.partition(".")
                channel = channel.strip()
                op = op.strip()
                if not channel:
                    raise ValueError("empty channel selector")
            if op not in ("send", "recv"):
                raise ValueError(f"op must be send/recv, got {op!r}")
            prob = None
            if "@" in rhs:
                rhs, p = rhs.rsplit("@", 1)
                prob = float(p)
                if not 0.0 <= prob <= 1.0:
                    raise ValueError(f"probability must be in [0, 1], got {prob}")
            action, _, argtxt = rhs.partition(":")
            action = action.strip()
            if action not in ("delay", "drop", "close", "error"):
                raise ValueError(f"unknown action {action!r}")
            arg = float(argtxt) if argtxt else None
            if action == "delay" and arg is None:
                raise ValueError("delay needs an argument (delay:SECONDS)")
            index = None if idx.strip() == "*" else int(idx)
            if index is None and prob is None:
                raise ValueError("wildcard rules need a probability (@P)")
            rules.append(_Rule(op, index, action, arg, prob, channel))
        except ValueError as e:
            raise ValueError(
                f"bad fault-schedule item {item!r}: {e}"
            ) from e
    return seed, rules


class FaultInjector:
    """Deterministic frame-level fault injection on a scripted schedule.

    One injector is installed process-wide (``install()`` or the
    ``DISTRL_FAULT_SCHEDULE`` env var) and every control-plane
    :class:`Connection` is wrapped through it. Unprefixed rules advance
    process-global per-op counters (the historical contract: same schedule
    + same RPC sequence → same event sequence); channel-scoped rules
    (``weights.send:2=close``) advance per-channel counters, so a
    weight-bus fault fires on the Nth WEIGHTS frame however many dispatch
    frames interleave (ISSUE 14 satellite — PR 9's out-of-band connections
    previously shared the global counters with no way to target them).
    ``events`` records decisions for assertions: ``(op, n, action)`` for
    global rules, ``("<channel>.<op>", n_channel, action)`` for scoped
    ones."""

    def __init__(self, schedule: str = "", seed: int | None = None):
        sched_seed, self.rules = _parse_schedule(schedule)
        self.schedule = schedule
        self.seed = sched_seed if seed is None else seed
        self._rng = random.Random(self.seed)
        self._counts = {"send": 0, "recv": 0}
        # per-(channel, op) counters for channel-scoped rules
        self._chan_counts: dict[tuple[str, str], int] = {}
        self._mu = threading.Lock()
        # decision-order event log — the determinism contract above
        self.events: list[tuple[str, int, str]] = []

    @classmethod
    def from_env(cls) -> "FaultInjector | None":
        spec = os.environ.get(FAULT_SCHEDULE_ENV, "")
        return cls(spec) if spec else None

    def decide(self, op: str,
               channel: str = "dispatch") -> tuple[str, float | None] | None:
        """Advance the counters and return (action, arg) when a rule
        fires, else None. Probabilistic rules draw from the seeded rng on
        every MATCHING call (fired or not), keeping the stream
        deterministic."""
        with self._mu:
            self._counts[op] += 1
            n = self._counts[op]
            key = (channel, op)
            n_chan = self._chan_counts.get(key, 0) + 1
            self._chan_counts[key] = n_chan
            fired: tuple[str, float | None] | None = None
            fired_scoped = False
            for r in self.rules:
                if r.op != op:
                    continue
                if r.channel is not None and r.channel != channel:
                    continue
                r_n = n if r.channel is None else n_chan
                if r.index is not None:
                    if r.index == r_n and fired is None:
                        fired = (r.action, r.arg)
                        fired_scoped = r.channel is not None
                else:
                    draw = self._rng.random()
                    if draw < r.prob and fired is None:
                        fired = (r.action, r.arg)
                        fired_scoped = r.channel is not None
            if fired is not None:
                self.events.append((
                    f"{channel}.{op}" if fired_scoped else op,
                    n_chan if fired_scoped else n,
                    fired[0],
                ))
            return fired


_installed: FaultInjector | None = None
_env_checked = False


def install(injector: FaultInjector | None) -> None:
    """Install (or clear, with None) the process-wide injector."""
    global _installed, _env_checked
    _installed = injector
    _env_checked = True  # an explicit install wins over the env


def active_injector() -> FaultInjector | None:
    global _installed, _env_checked
    if not _env_checked:
        _env_checked = True
        _installed = FaultInjector.from_env()
    return _installed


class FaultyConnection:
    """Connection proxy applying an injector's schedule to send/recv.

    Fault semantics: ``delay`` sleeps then forwards; ``drop`` discards the
    frame (send: pretend-ok; recv: consume and report a timeout);
    ``close`` closes the underlying socket and raises WorkerDeadError;
    ``error`` raises WorkerDeadError without closing. ``channel`` names
    the connection class for channel-scoped rules ("dispatch" by default;
    the weight bus dials with "weights")."""

    def __init__(self, inner, injector: FaultInjector,
                 channel: str = "dispatch"):
        self._inner = inner
        self._injector = injector
        self.channel = channel

    @property
    def fd(self):
        return self._inner.fd

    def _dead(self, what: str):
        from distrl_llm_tpu.distributed.control_plane import WorkerDeadError

        return WorkerDeadError(f"injected fault: {what}")

    def send(self, msg_type: int, req_id: int, payload: bytes = b"",
             timeout_ms: int = 30_000) -> None:
        fault = self._injector.decide("send", self.channel)
        if fault is not None:
            action, arg = fault
            if action == "delay":
                time.sleep(arg or 0.0)
            elif action == "drop":
                return  # frame silently discarded
            elif action == "close":
                self._inner.close()
                raise self._dead("send close")
            elif action == "error":
                raise self._dead("send error")
        self._inner.send(msg_type, req_id, payload, timeout_ms)

    def recv(self, timeout_ms: int):
        fault = self._injector.decide("recv", self.channel)
        if fault is not None:
            action, arg = fault
            if action == "delay":
                time.sleep(arg or 0.0)
            elif action == "drop":
                # consume the frame if one arrives, then report a timeout —
                # the closest local analogue of an undelivered response
                self._inner.recv(timeout_ms)
                return None
            elif action == "close":
                self._inner.close()
                raise self._dead("recv close")
            elif action == "error":
                raise self._dead("recv error")
        return self._inner.recv(timeout_ms)

    def close(self) -> None:
        self._inner.close()


def wrap_connection(conn, channel: str = "dispatch"):
    """Wrap a Connection with the active injector, if any (no-op otherwise).
    Called at every control-plane connection creation point, driver and
    worker side alike, so a schedule in the environment reaches both.
    ``channel`` tags the connection class for channel-scoped rules: the
    driver's weight bus dials its out-of-band connections with
    ``channel="weights"`` so ``weights.*`` rules can fault MSG_WEIGHTS
    traffic independently of dispatch traffic (worker-side ACCEPTED
    connections serve both frame kinds on one socket and stay on the
    default channel — the selector targets the driver side, where the
    connections are distinct objects)."""
    injector = active_injector()
    if injector is None:
        return conn
    return FaultyConnection(conn, injector, channel)
