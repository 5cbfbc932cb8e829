"""Driver↔worker RPC on the C++ TCP transport (native/csrc/control_plane.cc).

The N5 equivalent of the reference's Ray usage (SURVEY §2b): the reference
dispatches rollout shards to actor processes and collects results through
Ray's object store with ray.get timeouts as its only failure detector
(distributed_trainer.py:190–200, :325–337; ray.get(timeout=240) at :200).
This module provides those semantics natively:

* ``WorkerServer`` — the worker-side serve loop: receives DISPATCH frames,
  runs a handler, replies RESULT (or ERROR with the traceback); answers PING
  with PONG (the health check the reference lacks, SURVEY §5).
* ``DriverClient`` — the driver side: round-robin shard dispatch with
  deadlines, health-checked workers, and **shard resubmission**: a shard whose
  worker times out or dies is re-dispatched to a healthy worker instead of
  killing the run (the reference's worker death kills the run — SURVEY §5
  failure detection).

On top of those semantics sits the resilience layer (resilience.py):

* **Rejoin** — a background reconnect loop re-dials unhealthy workers with
  seeded exponential backoff and re-admits them after a PING, so capacity
  recovers instead of monotonically shrinking to ``WorkerDeadError("no
  healthy workers remain")``. ``rejoin_epoch`` bumps on every re-admit —
  RemoteEngine clears its warm keys off it (the rejoined worker's engine
  process restarted, so its XLA executables are cold again).
* **Bounded retry of worker exceptions** — an ERROR frame is classified
  transient-vs-fatal by exception type; transient ones retry on the same
  worker under the policy before the shard is requeued elsewhere.
* **Poison-shard quarantine** — a shard that fails on K distinct workers
  (or exhausts its attempt cap) raises :class:`ShardFailedError` naming the
  shard instead of grinding every worker to unhealthy; ``allow_partial``
  callers get ``None`` in its slot and degrade instead.
* **Graceful preemption** — ``WorkerServer.request_shutdown()`` (wired to
  SIGTERM by worker_main) drains the dispatch in flight — its result is
  still delivered — and exits the serve loop cleanly.

Payloads are opaque bytes; callers pickle (the reference moves pickled Python
objects through the object store, distributed_actor.py:289–293).
"""

from __future__ import annotations

import ctypes
import logging
import pickle
import threading
import time
import traceback
from dataclasses import dataclass
from typing import Any, Callable, Sequence

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.distributed import resilience
from distrl_llm_tpu.distributed.resilience import (
    RetryPolicy,
    ShardFailedError,
    WorkerError,
    classify_worker_error,
)
from distrl_llm_tpu.native.build import build_library

log = logging.getLogger(__name__)

MSG_DISPATCH = 1
MSG_RESULT = 2
MSG_PING = 3
MSG_PONG = 4
MSG_SHUTDOWN = 5
MSG_ERROR = 6
# RESULT with a telemetry blob piggybacked: payload is
# pickle((blob, result_bytes)). Workers send it when they recorded spans
# (DISTRL_TRACE / --trace) AND/OR have obs export armed (--metrics-port /
# DISTRL_OBS=1 — the blob then carries a "metrics" registry snapshot for
# the driver's fleet aggregator); runs with neither keep the plain
# MSG_RESULT frame and zero overhead.
MSG_RESULT_TLM = 7
# out-of-band weight push (weight_bus.py, ISSUE 9): the driver's WeightBus
# ships one versioned adapter update per frame — delta-encoded against the
# worker's last acked version — on its OWN connection, so the push lands
# (and swaps in-flight via the engine's LoraMailbox) while the worker's
# dispatch thread is deep inside a generation round. The worker replies
# MSG_RESULT with pickle({"version", "checksum"}) as the ack, or MSG_ERROR
# (checksum mismatch / unknown base → the sender falls back to full-tensor).
MSG_WEIGHTS = 8
# DISPATCH with a causal trace context (ISSUE 10): payload is
# pickle((ctx, payload)) where ctx carries (trace_id, dispatch_id) from
# telemetry.next_dispatch_context(). The worker binds it for the handler's
# duration, so every span it records — and ships home via MSG_RESULT_TLM —
# names the driver dispatch that caused it, and the merged Perfetto trace
# renders one causally linked timeline per round. Only sent while the
# driver is TRACING; untraced runs keep the plain MSG_DISPATCH frame.
MSG_DISPATCH_CTX = 9


class WorkerDeadError(RuntimeError):
    """A worker missed its deadline or its connection broke."""


class _Lib:
    _inst = None

    @classmethod
    def get(cls):
        if cls._inst is None:
            lib = ctypes.CDLL(build_library("control_plane.cc"))
            lib.cp_listen.restype = ctypes.c_int64
            lib.cp_listen.argtypes = [ctypes.c_int]
            lib.cp_bound_port.restype = ctypes.c_int
            lib.cp_bound_port.argtypes = [ctypes.c_int64]
            lib.cp_accept.restype = ctypes.c_int64
            lib.cp_accept.argtypes = [ctypes.c_int64, ctypes.c_int]
            lib.cp_connect.restype = ctypes.c_int64
            lib.cp_connect.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
            lib.cp_send.restype = ctypes.c_int
            lib.cp_send.argtypes = [
                ctypes.c_int64, ctypes.c_int, ctypes.c_uint64,
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ]
            lib.cp_recv_header.restype = ctypes.c_int
            lib.cp_recv_header.argtypes = [
                ctypes.c_int64, ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int,
            ]
            lib.cp_recv_payload.restype = ctypes.c_int
            lib.cp_recv_payload.argtypes = [
                ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ]
            lib.cp_close.argtypes = [ctypes.c_int64]
            cls._inst = lib
        return cls._inst


class Connection:
    """One framed TCP connection."""

    def __init__(self, fd: int):
        self._lib = _Lib.get()
        self.fd = fd
        self._send_mu = threading.Lock()

    def send(self, msg_type: int, req_id: int, payload: bytes = b"",
             timeout_ms: int = 30_000) -> None:
        with self._send_mu:
            # the send mutex must span the whole native write: cp_send
            # frames header+payload in one call, and two threads
            # interleaving partial writes on one fd would corrupt the
            # framing (the worker serves dispatch and weight-bus frames
            # from separate threads over separate connections precisely so
            # this lock is uncontended in steady state)
            # graftcheck: disable=GC102 -- frame atomicity: one writer per fd for the whole native send
            rc = self._lib.cp_send(
                self.fd, msg_type, req_id, payload, len(payload), timeout_ms
            )
        if rc != 0:
            raise WorkerDeadError("send failed (peer gone or deadline hit)")

    def recv(self, timeout_ms: int) -> tuple[int, int, bytes] | None:
        """One frame, or None on timeout. Raises WorkerDeadError on close."""
        t = ctypes.c_int()
        rid = ctypes.c_uint64()
        ln = ctypes.c_int64()
        rc = self._lib.cp_recv_header(
            self.fd, ctypes.byref(t), ctypes.byref(rid), ctypes.byref(ln),
            timeout_ms,
        )
        if rc == -1:
            return None
        if rc != 0:
            raise WorkerDeadError("connection closed")
        buf = ctypes.create_string_buffer(ln.value) if ln.value else None
        if ln.value:
            if self._lib.cp_recv_payload(self.fd, buf, ln.value, timeout_ms) != 0:
                raise WorkerDeadError("payload truncated")
        return t.value, rid.value, buf.raw if buf else b""

    def close(self) -> None:
        if self.fd >= 0:
            self._lib.cp_close(self.fd)
            self.fd = -1


class WorkerServer:
    """Worker-side serve loop. ``handler(payload: bytes) -> bytes`` runs per
    DISPATCH; exceptions travel back as ERROR frames with the traceback.

    Connections are served CONCURRENTLY (one thread each): the driver's
    dispatch channel and its out-of-band weight bus (MSG_WEIGHTS →
    ``weights_handler``) coexist, so a weight push lands — and swaps
    in-flight through the engine mailbox — while a generation dispatch is
    still running on the other connection (ISSUE 9)."""

    def __init__(self, port: int = 0):
        self._lib = _Lib.get()
        self._server_fd = self._lib.cp_listen(port)
        if self._server_fd < 0:
            raise OSError(f"cannot listen on port {port}")
        self.port = self._lib.cp_bound_port(self._server_fd)
        self._draining = False
        self._stopped = False
        # MSG_WEIGHTS frames route here (worker_main installs the weight-bus
        # handler when it serves a model); absent → ERROR reply
        self.weights_handler: Callable[[bytes], bytes] | None = None

    def request_shutdown(self) -> None:
        """Graceful preemption (worker_main wires SIGTERM here): finish the
        dispatch in flight — its result is still delivered — then exit the
        serve loop cleanly instead of dying mid-RPC. Signal-safe: only sets
        a flag the serve loop polls at its next frame boundary."""
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def serve_forever(self, handler: Callable[[bytes], bytes],
                      accept_timeout_ms: int = 1000) -> None:
        """Accept driver connections (one thread per connection) and serve
        until SHUTDOWN (or a ``request_shutdown`` drain)."""
        threads: list[threading.Thread] = []
        try:
            while True:
                if self._draining or self._stopped:
                    return
                fd = self._lib.cp_accept(self._server_fd, accept_timeout_ms)
                if fd == -1:
                    continue  # accept timeout; keep listening
                if fd < 0:
                    raise OSError("accept failed")
                conn = resilience.wrap_connection(Connection(fd))
                t = threading.Thread(
                    target=self._conn_loop, args=(conn, handler),
                    name="cp-serve", daemon=True,
                )
                threads.append(t)
                t.start()
                threads = [t for t in threads if t.is_alive()]
        finally:
            self._lib.cp_close(self._server_fd)
            # stop flag BEFORE the joins: on the accept-failure exit path
            # (OSError above) neither drain nor stop is set yet, and
            # without it a healthy connection thread would serve forever —
            # wedging this join and swallowing the exception
            self._stopped = True
            # in-flight frames still deliver their results before the
            # process moves on (the SIGTERM drain contract) — the old
            # single-connection loop blocked in the handler the same way;
            # idle siblings notice the stop flag within one 1s recv timeout
            for t in threads:
                t.join()

    def _conn_loop(self, conn: Connection, handler) -> None:
        try:
            self._serve_conn(conn, handler)
        except WorkerDeadError:
            log.info("driver connection dropped; re-listening")
        finally:
            conn.close()

    def _serve_conn(self, conn: Connection, handler) -> bool:
        while True:
            frame = conn.recv(timeout_ms=1000)
            if frame is None:
                if self._draining or self._stopped:
                    return True  # idle between frames: drain immediately
                continue
            msg_type, req_id, payload = frame
            if msg_type == MSG_PING:
                conn.send(MSG_PONG, req_id)
            elif msg_type == MSG_SHUTDOWN:
                conn.send(MSG_PONG, req_id)
                # stop the accept loop and every sibling connection thread
                # (each notices at its next 1s recv timeout)
                self._stopped = True
                return True
            elif msg_type in (MSG_DISPATCH, MSG_DISPATCH_CTX):
                ctx = None
                try:
                    if msg_type == MSG_DISPATCH_CTX:
                        # causal trace context (ISSUE 10): bound for the
                        # handler's duration so every span it records names
                        # the originating driver dispatch
                        ctx, payload = pickle.loads(payload)
                        telemetry.bind_trace_context(ctx)
                    result = handler(payload)
                    # spans the handler recorded ride home on the response
                    # (the worker has no trace file of its own; the driver
                    # merges them under a per-worker track). With obs
                    # export armed (--metrics-port / DISTRL_OBS=1) the
                    # worker's cumulative registry snapshot rides the same
                    # envelope — the driver's fleet aggregator feeds on it.
                    blob = telemetry.drain_remote_blob()
                    obs_snap = telemetry.export_obs_blob()
                    if obs_snap is not None:
                        blob = dict(blob) if blob else {
                            "events": [], "threads": {},
                        }
                        blob["metrics"] = obs_snap
                    if blob is not None:
                        conn.send(
                            MSG_RESULT_TLM, req_id,
                            pickle.dumps((blob, result)),
                        )
                    else:
                        conn.send(MSG_RESULT, req_id, result)
                except Exception:  # noqa: BLE001 — shipped to the driver
                    conn.send(
                        MSG_ERROR, req_id, traceback.format_exc().encode()
                    )
                finally:
                    if ctx is not None:
                        telemetry.unbind_trace_context()
            elif msg_type == MSG_WEIGHTS:
                # weight-bus push (ISSUE 9): runs on THIS connection's
                # thread, concurrent with any dispatch in flight — the
                # whole point of the out-of-band channel
                try:
                    wh = self.weights_handler
                    if wh is None:
                        raise RuntimeError(
                            "worker has no weight-bus handler (started "
                            "without --serve-model)"
                        )
                    conn.send(MSG_RESULT, req_id, wh(payload))
                except Exception:  # noqa: BLE001 — shipped to the driver
                    conn.send(
                        MSG_ERROR, req_id, traceback.format_exc().encode()
                    )
            else:
                log.warning("unexpected frame type %d", msg_type)
            if self._draining or self._stopped:
                # SIGTERM (or a sibling connection's MSG_SHUTDOWN) landed
                # while this frame was being handled: the in-flight result
                # was just delivered — now drain
                return True


@dataclass
class _Worker:
    address: tuple[str, int]
    conn: Connection | None
    healthy: bool = True
    cold: bool = False  # just rejoined: its engine process recompiles
    # TERMINAL membership state (ISSUE 20 elastic fleet): an intentionally
    # scaled-in worker. Distinct from death — the rejoin loop must never
    # re-dial it, quarantine refuses it, and dispatch never routes to it.
    retired: bool = False


class DriverClient:
    """Driver-side dispatch/collect over N workers with failure handling.

    ``retry_policy`` governs transient-error retries, reconnect backoff,
    and the per-call/per-round deadline budgets; ``poison_threshold`` is K,
    the distinct-worker failure count that quarantines a shard; ``rejoin``
    starts the background reconnect loop that re-admits recovered workers.
    """

    def __init__(self, addresses: Sequence[tuple[str, int]],
                 connect_timeout_ms: int = 10_000, *,
                 retry_policy: RetryPolicy | None = None,
                 poison_threshold: int = 3,
                 rejoin: bool = True,
                 rejoin_poll_s: float = 0.25):
        self._lib = _Lib.get()
        self._workers: list[_Worker] = []
        self._req_id = 0
        self._id_mu = threading.Lock()  # per-worker drain threads share it
        self._workers_mu = threading.Lock()  # health transitions
        self._connect_timeout_ms = connect_timeout_ms
        self.retry = retry_policy or RetryPolicy()
        self.poison_threshold = max(int(poison_threshold), 1)
        # bumps on every successful re-admit; RemoteEngine clears its warm
        # keys when it changes (the rejoined worker compiles from scratch)
        self.rejoin_epoch = 0
        # bumps on every MEMBERSHIP change (add_worker / retire_worker),
        # distinct from rejoin_epoch: a dispatch round spanning a scale
        # event re-snapshots the worker set per iteration, so shards on a
        # retiring worker requeue to survivors and every group is conserved
        self.membership_epoch = 0
        # weight-bus hooks (weight_bus.py, ISSUE 9). rejoin_hook(address)
        # runs after a PING-verified reconnect and BEFORE re-admission —
        # the bus resyncs the cold worker with a full-tensor push; False
        # fails this rejoin attempt (retried under the policy backoff).
        # transient_hook(worker, error) runs before each same-worker retry
        # of a transient MSG_ERROR — the bus re-pushes a version the worker
        # reported unknown (one bounded re-request, not a poisoned shard).
        self.rejoin_hook: Callable[[tuple[str, int]], bool] | None = None
        self.transient_hook: (
            Callable[["_Worker", WorkerError], None] | None
        ) = None
        # shutdown() runs these before closing connections (the weight bus
        # parks its sender thread and channels here)
        self.shutdown_hooks: list[Callable[[], None]] = []
        # per-shard dispatch metadata of the LAST dispatch_round, aligned
        # with its shards ({worker, dispatch_id} per slot; None for a slot
        # that never completed) — RemoteEngine folds it into lineage
        # records (ISSUE 10). Written once per round on the calling thread.
        self.last_dispatch_meta: list[dict | None] = []
        for host, port in addresses:
            fd = self._lib.cp_connect(host.encode(), port, connect_timeout_ms)
            if fd < 0:
                raise OSError(f"cannot connect to worker {host}:{port}")
            self._workers.append(
                _Worker((host, port), resilience.wrap_connection(Connection(fd)))
            )
        telemetry.gauge_set(resilience.CP_HEALTHY_GAUGE, self.num_healthy)
        self._stop_rejoin = threading.Event()
        self._rejoin_thread: threading.Thread | None = None
        if rejoin:
            self._rejoin_poll_s = rejoin_poll_s
            self._rejoin_thread = threading.Thread(
                target=self._rejoin_loop, name="cp-rejoin", daemon=True
            )
            self._rejoin_thread.start()

    @property
    def num_healthy(self) -> int:
        return sum(w.healthy for w in self._workers)

    @property
    def addresses(self) -> list[tuple[str, int]]:
        """Configured worker addresses, in construction order (the weight
        bus dials its out-of-band channels against the same set)."""
        return [w.address for w in self._workers]

    def worker_states(self) -> list[dict]:
        """Point-in-time health view for the observability plane
        (obs.FleetAggregator): one dict per configured worker, under the
        same mutex health transitions take. A retired worker reports
        distinctly (terminal; not merely unhealthy)."""
        with self._workers_mu:
            return [
                {
                    "address": f"{w.address[0]}:{w.address[1]}",
                    "healthy": bool(w.healthy),
                    "cold": bool(w.cold),
                    "retired": bool(w.retired),
                }
                for w in self._workers
            ]

    def _next_id(self) -> int:
        with self._id_mu:
            self._req_id += 1
            return self._req_id

    def _mark_unhealthy(self, w: _Worker, conn: Connection | None = None) -> None:
        """Close + demote a worker. ``conn`` (when given) guards against a
        racing rejoin: only demote if the failed connection is still the
        worker's current one."""
        with self._workers_mu:
            if conn is not None and w.conn is not conn:
                return  # the rejoin loop already replaced it
            w.healthy = False
            if w.conn is not None:
                w.conn.close()
                w.conn = None
        telemetry.gauge_set(resilience.CP_HEALTHY_GAUGE, self.num_healthy)

    # ---------------------------------------------------------------- rejoin

    def _rejoin_loop(self) -> None:
        """Background re-dial of unhealthy workers with the policy's seeded
        backoff; a PING-verified connection re-admits the worker (cold: its
        engine process likely restarted and recompiles everything).

        Backoff state is keyed by ADDRESS, not list index: the worker list
        grows under add_worker, and an index key would alias one worker's
        backoff clock onto another after a scale event. A RETIRED worker is
        terminal — it is never probed, never re-dialed (the ISSUE 20
        rejoin/retire aliasing fix)."""
        backoff: dict[tuple, tuple[int, float]] = {}  # addr -> (attempt, next_t)
        while not self._stop_rejoin.wait(self._rejoin_poll_s):
            with self._workers_mu:
                snapshot = list(self._workers)
            for w in snapshot:
                if self._stop_rejoin.is_set():
                    break
                if w.retired:
                    backoff.pop(w.address, None)
                    continue
                if w.healthy:
                    backoff.pop(w.address, None)
                    continue
                attempt, next_t = backoff.get(w.address, (0, 0.0))
                if time.monotonic() < next_t:
                    continue
                if self._try_rejoin(w):
                    backoff.pop(w.address, None)
                else:
                    backoff[w.address] = (
                        attempt + 1,
                        time.monotonic() + self.retry.backoff(attempt),
                    )

    def _dial_verified(self, address: tuple[str, int]) -> Connection | None:
        """The admission preamble shared by rejoin AND first joins
        (``add_worker``): cp_connect → PING/PONG → weight-bus full resync
        through ``rejoin_hook``. Returns the verified connection, or None
        — the caller owns the admit-under-mutex step."""
        host, port = address
        fd = self._lib.cp_connect(
            host.encode(), port, self._connect_timeout_ms
        )
        if fd < 0:
            return None
        conn = resilience.wrap_connection(Connection(fd))
        rid = self._next_id()
        ok = False
        try:
            conn.send(MSG_PING, rid)
            frame = conn.recv(timeout_ms=5000)
            ok = (
                frame is not None
                and frame[0] == MSG_PONG
                and frame[1] == rid
            )
        except WorkerDeadError:
            ok = False
        if not ok:
            conn.close()
            return None
        hook = self.rejoin_hook
        if hook is not None:
            # weight-bus resync (ISSUE 9): the joining worker's engine
            # process has no adapter cache — push the current version
            # full-tensor BEFORE admission, so the first post-join
            # dispatch never names a version it lacks
            try:
                synced = bool(hook(tuple(address)))
            except Exception:  # noqa: BLE001 — a failed resync fails
                # this attempt; the caller's backoff/retry owns the rest
                log.warning(
                    "join/rejoin hook failed for %s", address, exc_info=True
                )
                synced = False
            if not synced:
                conn.close()
                return None
        return conn

    def _try_rejoin(self, w: _Worker) -> bool:
        host, port = w.address
        with telemetry.span("cp/reconnect", worker=f"{host}:{port}") as sp:
            conn = self._dial_verified(w.address)
            if conn is None:
                sp.set(ok=False)
                return False
            with self._workers_mu:
                if self._stop_rejoin.is_set() or w.retired:
                    # shutdown() (or a racing retire) won: admitting now
                    # would leak the fd and leave a worker process that
                    # never receives MSG_SHUTDOWN
                    conn.close()
                    sp.set(ok=False)
                    return False
                w.conn = conn
                w.cold = True
                w.healthy = True
                self.rejoin_epoch += 1
            sp.set(ok=True)
        telemetry.counter_add(resilience.CP_RECONNECTS)
        telemetry.gauge_set(resilience.CP_HEALTHY_GAUGE, self.num_healthy)
        telemetry.gauge_set(resilience.CP_REJOIN_EPOCH, self.rejoin_epoch)
        log.info("worker %s:%d rejoined (cold)", host, port)
        return True

    # ----------------------------------------------------------- membership

    @staticmethod
    def _parse_address(address) -> tuple[str, int]:
        if isinstance(address, str):
            host, _, port = address.rpartition(":")
            return (host or "127.0.0.1", int(port))
        return (address[0], int(address[1]))

    def add_worker(self, address) -> bool:
        """Admit a NEW worker mid-run (ISSUE 20 elastic fleet): the PR 5
        rejoin path generalized to first joins — dial, PING-verify, full
        weight-bus resync through ``rejoin_hook``, admit COLD (its engine
        compiles from scratch, so the next round gets the cold deadline).
        Re-adding a previously retired address re-activates its slot.

        Returns False when the worker cannot be verified (unreachable,
        no PONG, resync failed) or the address is already an active
        member; the membership set is unchanged on failure."""
        address = self._parse_address(address)
        with self._workers_mu:
            existing = next(
                (w for w in self._workers if w.address == address), None
            )
            if existing is not None and not existing.retired:
                log.warning(
                    "add_worker(%s): already a member (healthy=%s)",
                    address, existing.healthy,
                )
                return False
        conn = self._dial_verified(address)
        if conn is None:
            return False
        with self._workers_mu:
            if self._stop_rejoin.is_set():
                # shutdown in progress: do not admit into a closing plane
                conn.close()
                return False
            target = next(
                (w for w in self._workers if w.address == address), None
            )
            if target is None:
                target = _Worker(address, None, healthy=False)
                self._workers.append(target)
            elif not target.retired:
                conn.close()  # lost an add/add race: already active
                return False
            target.retired = False
            target.conn = conn
            target.cold = True
            target.healthy = True
            self.rejoin_epoch += 1
            self.membership_epoch += 1
        telemetry.gauge_set(resilience.CP_HEALTHY_GAUGE, self.num_healthy)
        telemetry.gauge_set(resilience.CP_REJOIN_EPOCH, self.rejoin_epoch)
        log.info("worker %s:%d added (cold)", *address)
        return True

    def retire_worker(self, address, drain: bool = True,
                      timeout_ms: int = 5000) -> bool:
        """Intentional scale-in (ISSUE 20): transition a worker to the
        TERMINAL ``retired`` state — distinct from death. The rejoin loop
        never re-dials it, dispatch never routes to it, and a shard in
        flight on it requeues to survivors through the standard
        resubmission path (group conservation holds across the event).

        ``drain=True`` sends MSG_SHUTDOWN over a dedicated connection so
        the worker exits its serve loop cleanly (the SIGTERM contract:
        in-flight frames deliver their results before the process moves
        on). Supervised local workers are drained by their FleetSupervisor
        via SIGTERM instead (drain=False here).

        Returns False for an unknown or already-retired address. Bumps
        ``cp/retires`` — never the quarantine/reconnect counters."""
        address = self._parse_address(address)
        with self._workers_mu:
            target = next(
                (w for w in self._workers if w.address == address), None
            )
            if target is None or target.retired:
                return False
            target.retired = True
            target.healthy = False
            conn, target.conn = target.conn, None
            self.membership_epoch += 1
        if conn is not None:
            conn.close()
        if drain:
            # dedicated drain connection: the dispatch conn above may have
            # a drain thread blocked in recv on it — sending SHUTDOWN
            # there would corrupt the request/response pairing
            host, port = address
            fd = self._lib.cp_connect(
                host.encode(), port, self._connect_timeout_ms
            )
            if fd >= 0:
                dconn = resilience.wrap_connection(Connection(fd))
                try:
                    dconn.send(MSG_SHUTDOWN, self._next_id())
                    dconn.recv(timeout_ms)
                except WorkerDeadError:
                    pass  # already gone: retired either way
                finally:
                    dconn.close()
        telemetry.counter_add(resilience.CP_RETIRES)
        telemetry.gauge_set(resilience.CP_HEALTHY_GAUGE, self.num_healthy)
        log.info(
            "worker %s:%d retired (%s)", *address,
            "drained" if drain else "no drain",
        )
        return True

    # ---------------------------------------------------------------- health

    def quarantine_worker(self, address, *, min_healthy: int = 1) -> bool:
        """Proactive demotion (ISSUE 14 worker-health controller): close a
        live-but-regressing worker's connection and mark it unhealthy so
        dispatches route around it; the rejoin loop then PING-probes the
        address with the policy backoff and re-admits it cold — the same
        recovery path a crashed worker takes, entered deliberately.

        Refuses (returns False) when the worker is unknown or already
        unhealthy, when demoting it would leave fewer than ``min_healthy``
        healthy workers (a controller must degrade capacity, never zero
        it), or when no rejoin loop is running (the quarantine would be
        permanent — that is a kill, not a control action)."""
        address = self._parse_address(address)
        if self._rejoin_thread is None:
            log.warning(
                "refusing to quarantine %s: worker_rejoin is off, so the "
                "worker could never be re-admitted", address,
            )
            return False
        with self._workers_mu:
            target = next(
                (w for w in self._workers if w.address == address), None
            )
            if target is None or not target.healthy or target.retired:
                # retired is TERMINAL: quarantining it would re-enter the
                # rejoin loop's probe set and re-dial an intentional exit
                return False
            healthy = sum(w.healthy for w in self._workers)
            if healthy - 1 < max(int(min_healthy), 1):
                log.warning(
                    "refusing to quarantine %s: only %d healthy worker(s) "
                    "remain (min_healthy=%d)", address, healthy, min_healthy,
                )
                return False
            conn = target.conn
        # demote OUTSIDE the mutex via the standard path (it re-takes the
        # lock and applies the conn-identity guard against a racing rejoin)
        self._mark_unhealthy(target, conn)
        telemetry.counter_add(resilience.CP_QUARANTINES)
        log.warning(
            "worker %s:%d quarantined (proactive); rejoin loop will probe "
            "and re-admit", *address,
        )
        return True

    def ping_all(self, timeout_ms: int = 5000) -> list[bool]:
        """Health check every worker — one thread per worker, so a single
        hung worker costs the sweep ONE ``timeout_ms``, not one per victim
        (SURVEY §5: health-checked workers).

        A missed or mismatched PONG closes the connection: the unanswered
        PING would otherwise desync the request/response framing (a late
        PONG surfacing as some future call's reply)."""
        from concurrent.futures import ThreadPoolExecutor

        def ping(w: _Worker) -> bool:
            conn = w.conn
            if conn is None:
                # already unhealthy — the rejoin loop owns it. Demoting here
                # would bypass the conn-identity guard and could close a
                # connection a concurrent rejoin JUST re-admitted.
                return False
            ok = False
            rid = self._next_id()
            try:
                conn.send(MSG_PING, rid)
                frame = conn.recv(timeout_ms)
                ok = (
                    frame is not None
                    and frame[0] == MSG_PONG
                    and frame[1] == rid
                )
            except WorkerDeadError:
                ok = False
            if ok:
                with self._workers_mu:
                    if w.conn is conn:
                        w.healthy = True
            else:
                self._mark_unhealthy(w, conn)
            return ok

        if not self._workers:
            return []
        with ThreadPoolExecutor(
            max_workers=len(self._workers), thread_name_prefix="cp-ping"
        ) as pool:
            out = list(pool.map(ping, self._workers))
        telemetry.gauge_set(resilience.CP_HEALTHY_GAUGE, self.num_healthy)
        return out

    def _call(self, w: _Worker, payload: bytes,
              timeout_ms: int) -> tuple[bytes, dict]:
        """One dispatch RPC. Returns (result bytes, dispatch meta) — the
        meta names the worker and the causal ``dispatch_id`` stamped on the
        frame (telemetry.next_dispatch_context), the handle the lineage
        ledger records per sampled group (ISSUE 10)."""
        rid = self._next_id()
        host, port = w.address
        # ONE snapshot of the connection: retire_worker / _mark_unhealthy
        # null w.conn concurrently, and a torn read here would surface as
        # AttributeError instead of the WorkerDeadError the resubmission
        # path handles (ISSUE 20 mid-round scale events)
        conn = w.conn
        if conn is None:
            raise WorkerDeadError(
                f"worker {w.address} connection closed mid-round "
                "(retired or demoted)"
            )
        # dispatch id: always allocated (a counter bump) so lineage works
        # with tracing off; the ctx ENVELOPE only ships while tracing is on
        ctx = telemetry.next_dispatch_context()
        meta = {"worker": f"{host}:{port}",
                "dispatch_id": ctx["dispatch_id"]}
        with telemetry.span("cp/dispatch", worker=f"{host}:{port}",
                            bytes=len(payload),
                            dispatch_id=ctx["dispatch_id"],
                            trace_id=ctx["trace_id"]):
            t0 = time.perf_counter()
            # frame-size accounting (ISSUE 9): the dispatch-vs-broadcast
            # payload win is asserted from this counter (the inner payload;
            # the ~100-byte traced-run ctx envelope is not dispatch data)
            telemetry.counter_add(resilience.CP_DISPATCH_BYTES, len(payload))
            if telemetry.enabled():
                telemetry.emit_flow_start(ctx["dispatch_id"])
                conn.send(
                    MSG_DISPATCH_CTX, rid, pickle.dumps((ctx, payload))
                )
            else:
                conn.send(MSG_DISPATCH, rid, payload)
            frame = conn.recv(timeout_ms)
        if frame is None:
            raise WorkerDeadError(
                f"worker {w.address} missed the {timeout_ms}ms deadline"
            )
        msg_type, got_rid, body = frame
        if got_rid != rid or msg_type not in (
            MSG_RESULT, MSG_RESULT_TLM, MSG_ERROR
        ):
            raise WorkerDeadError(f"worker {w.address} protocol violation")
        if msg_type == MSG_ERROR:
            # classified transient-vs-fatal so the caller can retry under
            # the policy instead of aborting the round on a hiccup
            tb = body.decode(errors="replace")
            raise WorkerError(
                w.address, tb, transient=classify_worker_error(tb)
            )
        if msg_type == MSG_RESULT_TLM:
            # worker-recorded spans piggybacked on the result: merge them
            # into the driver trace under this worker's track
            blob, body = pickle.loads(body)
            telemetry.ingest_remote(blob, track=f"worker {host}:{port}")
        telemetry.hist_observe(
            resilience.CP_RPC_DISPATCH_MS, (time.perf_counter() - t0) * 1e3
        )
        return body, meta

    def _call_with_retry(self, w: _Worker, payload: bytes,
                         timeout_ms: int) -> tuple[bytes, dict]:
        """``_call`` plus the policy's bounded transient-error retry: a
        worker-side exception classified transient retries on the SAME
        worker (it answered — it is alive) with seeded backoff, within the
        per-call deadline budget. Fatal errors and transport deaths
        propagate to the caller unchanged."""
        host, port = w.address
        attempt = 0
        t0 = time.monotonic()
        while True:
            try:
                return self._call(w, payload, timeout_ms)
            except WorkerError as e:
                if not e.transient or attempt >= self.retry.max_call_retries:
                    raise
                delay = self.retry.backoff(attempt)
                budget = self.retry.call_budget_s
                if budget is not None and (
                    time.monotonic() - t0 + delay > budget
                ):
                    raise
                attempt += 1
                telemetry.counter_add(resilience.CP_RETRIES)
                hook = self.transient_hook
                if hook is not None:
                    # weight-bus re-request (ISSUE 9): an unknown-version
                    # error gets its version re-pushed full-tensor before
                    # the retry, so the bounded retry can actually succeed
                    try:
                        hook(w, e)
                    except Exception:  # noqa: BLE001 — the retry itself
                        # is the recovery path; a hook failure only means
                        # the retry may fail the same way
                        log.warning(
                            "transient-error hook failed for %s", w.address,
                            exc_info=True,
                        )
                with telemetry.span("cp/retry", worker=f"{host}:{port}",
                                    attempt=attempt):
                    log.warning(
                        "transient worker error on %s (retry %d/%d in "
                        "%.3fs): %s", w.address, attempt,
                        self.retry.max_call_retries, delay,
                        e.traceback_text.strip().splitlines()[-1],
                    )
                    time.sleep(delay)

    def dispatch_round(self, shards: Sequence[bytes],
                       timeout_ms: int = 240_000,
                       allow_partial: bool = False) -> list[bytes]:
        """Dispatch shards round-robin over healthy workers, ALL workers
        working concurrently (one thread per worker draining its queue — the
        parallel fan-out that is this plane's whole purpose; a worker's own
        shards run sequentially over its single connection).

        The reference's equivalent is actor.generate.remote per chunk +
        ray.get(timeout=240) (distributed_trainer.py:190–200) — except a
        timeout there kills the run. Here a dead worker is marked unhealthy
        and its shards are RESUBMITTED to the remaining workers; the round
        only fails when no healthy workers remain.

        Poison-shard quarantine: a shard that fails on ``poison_threshold``
        DISTINCT workers (or ``retry.max_shard_attempts`` total attempts)
        raises :class:`ShardFailedError` naming the shard — unless
        ``allow_partial``, in which case its slot holds ``None`` and the
        returned list stays aligned with ``shards`` so the caller can
        degrade with exact accounting."""
        from concurrent.futures import ThreadPoolExecutor

        results: list[bytes | None] = [None] * len(shards)
        # dispatch meta per shard slot (worker + causal dispatch_id of the
        # call that SUCCEEDED), published as last_dispatch_meta at exit
        meta: list[dict | None] = [None] * len(shards)
        # poison tracking: which DISTINCT workers failed each shard, and
        # its total failed attempts (mutated on the main thread only)
        shard_workers: dict[int, set] = {}
        shard_attempts: dict[int, int] = {}
        quarantined: set[int] = set()
        pending = list(range(len(shards)))
        t_round = time.monotonic()
        # the caller chose this round's deadline knowing the rejoin epoch
        # (RemoteEngine re-checks it per round), so workers cold at ENTRY
        # are covered — clear their flags. Workers that rejoin MID-round
        # stay cold and sit the rest of this round out (below): their fresh
        # engine would cold-compile past the warm deadline, read as a
        # second death, and unjustly poison whatever shard it carried.
        with self._workers_mu:
            for w in self._workers:
                w.cold = False
        while pending:
            budget = self.retry.round_budget_s
            if budget is not None and time.monotonic() - t_round > budget:
                raise WorkerDeadError(
                    f"dispatch round exceeded its {budget:.0f}s budget with "
                    f"{len(pending)} shard(s) still pending"
                )
            with self._workers_mu:
                # membership snapshot per iteration: a worker retired (or
                # added) MID-round is respected at the next redistribution,
                # so a round spanning a scale event conserves every group
                avail = [
                    w for w in self._workers
                    if w.healthy and w.conn and not w.retired
                ]
                warm = [w for w in avail if not w.cold]
            # fall back to cold workers only when they are ALL that's left
            # (better a possible compile-time miss than failing the round)
            healthy = warm or avail
            if not healthy:
                raise WorkerDeadError("no healthy workers remain")
            queues: dict[int, list[int]] = {id(w): [] for w in healthy}
            for k, i in enumerate(pending):
                # a requeued shard PREFERS workers it has not yet failed on:
                # plain round-robin would re-land it on the same worker
                # forever, so the K-distinct-workers poison signature could
                # never accumulate and quarantine would only fire via the
                # (much larger) attempt cap
                failed_on = shard_workers.get(i)
                candidates = (
                    [w for w in healthy if w.address not in failed_on]
                    if failed_on else healthy
                ) or healthy
                queues[id(candidates[k % len(candidates)])].append(i)

            def drain(w: _Worker, idxs: list[int]):
                """Returns (requeue, failures): shard indices to redistribute
                and [(shard, kind)] failure records for poison tracking —
                only the shard actually IN FLIGHT at a worker death is
                recorded against it (that is the poison signature); the rest
                of the queue just redistributes."""
                conn = w.conn
                requeue: list[int] = []
                failures: list[tuple[int, str]] = []
                host, port = w.address
                for pos, i in enumerate(idxs):
                    try:
                        results[i], meta[i] = self._call_with_retry(
                            w, shards[i], timeout_ms
                        )
                    except WorkerDeadError as e:
                        log.warning(
                            "worker %s lost; resubmitting %d shard(s): %s",
                            w.address, len(idxs) - pos, e,
                        )
                        self._mark_unhealthy(w, conn)
                        failures.append((i, "dead"))
                        requeue.extend(idxs[pos:])
                        telemetry.counter_add(
                            resilience.CP_RESUBMITS, len(idxs) - pos
                        )
                        with telemetry.span(
                            "cp/resubmit", worker=f"{host}:{port}",
                            count=len(idxs) - pos,
                        ):
                            pass
                        break
                    except WorkerError as e:
                        if not e.transient:
                            raise  # deterministic program error: fail loudly
                        log.warning(
                            "shard %d exhausted transient retries on worker "
                            "%s; requeueing", i, w.address,
                        )
                        failures.append((i, "exhausted"))
                        requeue.append(i)
                        telemetry.counter_add(resilience.CP_RESUBMITS)
                        with telemetry.span(
                            "cp/resubmit", worker=f"{host}:{port}", count=1,
                        ):
                            pass
                return requeue, failures

            pool = ThreadPoolExecutor(
                max_workers=len(healthy), thread_name_prefix="cp-drain"
            )
            outcomes: list[tuple[_Worker, list[int], list[tuple[int, str]]]] = []
            first_exc: BaseException | None = None
            try:
                futs = [
                    (w, pool.submit(drain, w, queues[id(w)]))
                    for w in healthy if queues[id(w)]
                ]
                for w, f in futs:
                    try:
                        requeue, failures = f.result()
                        outcomes.append((w, requeue, failures))
                    except BaseException as e:  # noqa: BLE001 — surfaced below
                        if first_exc is None:
                            first_exc = e
            finally:
                # a fatal error mid-pool must not leak drain threads that
                # keep writing into ``results`` after this frame returns:
                # cancel anything queued and JOIN the running drains before
                # surfacing (the old wait=False teardown leaked them)
                pool.shutdown(wait=True, cancel_futures=True)
            if first_exc is not None:
                raise first_exc
            pending = []
            for w, requeue, failures in outcomes:
                failed_here = set()
                for i, _kind in failures:
                    failed_here.add(i)
                    shard_workers.setdefault(i, set()).add(w.address)
                    shard_attempts[i] = shard_attempts.get(i, 0) + 1
                for i in requeue:
                    if i in failed_here and (
                        len(shard_workers[i]) >= self.poison_threshold
                        or shard_attempts[i] >= self.retry.max_shard_attempts
                    ):
                        telemetry.counter_add(resilience.CP_POISON_SHARDS)
                        err = ShardFailedError(
                            i, workers=sorted(shard_workers[i]),
                            attempts=shard_attempts[i],
                        )
                        if not allow_partial:
                            raise err
                        log.error("degrading: %s", err)
                        quarantined.add(i)
                    else:
                        pending.append(i)
        self.last_dispatch_meta = meta
        if allow_partial:
            return [
                None if i in quarantined else results[i]
                for i in range(len(shards))
            ]
        return [r for r in results if r is not None]

    def dispatch_objects(self, shards: Sequence[Any],
                         timeout_ms: int = 240_000,
                         allow_partial: bool = False) -> list[Any]:
        """pickle-in / pickle-out convenience over ``dispatch_round``."""
        raw = self.dispatch_round(
            [pickle.dumps(s) for s in shards], timeout_ms,
            allow_partial=allow_partial,
        )
        return [pickle.loads(r) if r is not None else None for r in raw]

    def shutdown(self, timeout_ms: int = 5000) -> None:
        for hook in self.shutdown_hooks:
            try:
                hook()
            except Exception:  # noqa: BLE001 — shutdown must proceed
                log.warning("shutdown hook failed", exc_info=True)
        self._stop_rejoin.set()
        if self._rejoin_thread is not None:
            self._rejoin_thread.join(timeout=5)
            self._rejoin_thread = None
        # detach the connections under the mutex, THEN shut them down: a
        # rejoin attempt still in flight after the join timed out either
        # admitted before this block (its conn is in the snapshot and gets
        # MSG_SHUTDOWN) or hits the stop-guard in _try_rejoin and closes
        # its own connection — no fd leaks either way
        with self._workers_mu:
            conns = [w.conn for w in self._workers]
            for w in self._workers:
                w.conn = None
                w.healthy = False
        for conn in conns:
            if conn is not None:
                try:
                    conn.send(MSG_SHUTDOWN, self._next_id())
                    conn.recv(timeout_ms)
                except WorkerDeadError:
                    pass
                conn.close()
