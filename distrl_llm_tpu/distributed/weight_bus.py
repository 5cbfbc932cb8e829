"""Versioned weight-broadcast bus: one-shot delta push replaces per-dispatch
adapter shipping (ISSUE 9).

The control-plane port shipped the full LoRA pytree inside EVERY
``MSG_DISPATCH`` payload, for every worker, every round — the reference's
shared-filesystem adapter bus (distributed_actor.py:150) re-expressed as
weights-in-the-request. LlamaRL makes direct memory weight transfer (DDMA) a
headline result and PipelineRL shows mid-sequence weight updates keep
long-generation RL near on-policy; both demand a *versioned push channel*:

* **Wire codec** — :func:`encode_update` / :func:`decode_update` ship the
  adapter once per learner version, delta-encoded against the worker's last
  ACKED version. Per leaf the encoder tries, in order: a bf16 delta
  (``new − prev``, 2 bytes/elem), an fp32 delta, and the full tensor —
  verifying each candidate's reconstruction bit-exactly BEFORE choosing it,
  so the decoded tree is always byte-identical to the learner's (the sync
  byte-identity golden holds over the bus). A crc32 checksum over the target
  tree rides along; a worker whose decode mismatches (corrupt base, wire
  fault) raises :class:`WeightChecksumError` and the sender falls back to a
  full-tensor push.
* **AdapterCache** — the worker-side versioned 2-slot cache (current +
  superseded — exactly what the speculative self-drafter needs remotely).
  Dispatches carry ``{weight_version: v}`` and resolve against it;
  :meth:`AdapterCache.wait_for` bridges the benign race where a dispatch
  lands before its broadcast (the push is already in flight).
* **WeightBus** — the driver-side broadcaster: a double-buffered single-slot
  mailbox (the ``LoraMailbox`` torn-read discipline — one reference, newest
  push wins) drained by a sender thread, so the learner never blocks on the
  wire; per-version parallel fan-out to every worker with the control
  plane's :class:`~.resilience.RetryPolicy` backoff; per-worker acked
  (version, tree) state feeds the next delta; rejoin and unknown-version
  re-requests resync with a full-tensor push.

Telemetry: ``cp/weight_bytes_sent``, ``cp/weight_pushes``,
``cp/weight_full_syncs``, ``cp/weight_rerequests`` counters,
``cp/weight_broadcast_ms`` histogram (push → last worker ack), and
``cp/weight_push`` spans (worker=, version=, bytes=, mode=) that feed
tools/trace_report.py's "weight bus:" section. ``obs/weight_sync_ms`` is set
from the broadcast completion, so it covers learner-push → last-worker-ack,
not just the local ``_push_weights`` call (ISSUE 8 follow-up).
"""

from __future__ import annotations

import logging
import os
import pickle
import threading
import time
import zlib
from typing import Any, Callable, Sequence

import numpy as np

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.distributed import resilience
from distrl_llm_tpu.distributed.resilience import RetryPolicy

log = logging.getLogger(__name__)

# how long a dispatch naming a not-yet-arrived version waits for the
# broadcast before raising the (transient) WeightVersionError that triggers
# the driver's bounded re-request
WEIGHT_WAIT_ENV = "DISTRL_WEIGHT_WAIT_S"
DEFAULT_WEIGHT_WAIT_S = 30.0

WEIGHT_PUSH_SPAN = "cp/weight_push"


def _bfloat16():
    import ml_dtypes  # jax dependency; always present with jax

    return ml_dtypes.bfloat16


class WeightVersionError(RuntimeError):
    """A worker was asked for an adapter version it does not hold.

    The message carries the literal ``[transient]`` marker so
    :func:`~.resilience.classify_worker_error` retries the dispatch on the
    same worker — the driver's transient hook re-pushes the named version
    full-tensor first (one bounded re-request instead of a poisoned shard).
    """

    def __init__(self, message: str):
        super().__init__(f"[transient] {message}")


class WeightChecksumError(RuntimeError):
    """A decoded adapter's checksum mismatched the sender's.

    Raised worker-side during a bus push (corrupt base slot, wire fault);
    the sender clears its acked state for that worker and falls back to a
    full-tensor push. ``[transient]`` so a dispatch-path surfacing retries.
    """

    def __init__(self, message: str):
        super().__init__(f"[transient] {message}")


# ------------------------------------------------------------------- codec


def _leaves(tree) -> list[np.ndarray]:
    import jax

    return [np.ascontiguousarray(np.asarray(x))
            for x in jax.tree_util.tree_leaves(tree)]


def checksum_tree(tree) -> int:
    """crc32 over the tree's leaves in flatten order (shape/dtype included,
    so a reshaped or recast tree never collides with the original)."""
    crc = 0
    for leaf in _leaves(tree):
        crc = zlib.crc32(
            f"{leaf.dtype.name}{leaf.shape}".encode(), crc
        )
        crc = zlib.crc32(leaf.tobytes(), crc)
    return crc


def _encode_leaf(new: np.ndarray, prev: np.ndarray | None) -> dict:
    """One leaf's wire record: the cheapest encoding whose reconstruction
    is BIT-EXACT, verified here (never trusted): bf16 delta → fp32 delta →
    full tensor. First contact (no prev) and shape/dtype drift are full."""
    new = np.ascontiguousarray(new)
    # dtype by NAME, not .str: extension floats (bfloat16) stringify to a
    # void descriptor ('<V2') that would decode as raw bytes
    rec = {"dtype": new.dtype.name, "shape": tuple(new.shape)}
    if (
        prev is not None
        and prev.shape == new.shape
        and prev.dtype == new.dtype
        and (
            np.issubdtype(new.dtype, np.floating)
            or new.dtype == _bfloat16()
        )
    ):
        prev32 = prev.astype(np.float32)
        delta32 = new.astype(np.float32) - prev32
        d16 = delta32.astype(_bfloat16())
        recon = (prev32 + d16.astype(np.float32)).astype(new.dtype)
        if recon.tobytes() == new.tobytes():
            rec.update(mode="delta_bf16", data=d16.tobytes())
            return rec
        recon = (prev32 + delta32).astype(new.dtype)
        if recon.tobytes() == new.tobytes():
            rec.update(mode="delta_f32", data=delta32.tobytes())
            return rec
    rec.update(mode="full", data=new.tobytes())
    return rec


def _decode_leaf(rec: dict, prev: np.ndarray | None) -> np.ndarray:
    _bfloat16()  # registers the extension dtypes with np.dtype by name
    dtype = np.dtype(rec["dtype"])
    shape = tuple(rec["shape"])
    mode = rec["mode"]
    if mode == "full":
        return np.frombuffer(rec["data"], dtype=dtype).reshape(shape).copy()
    if prev is None:
        raise WeightChecksumError(
            f"delta leaf ({mode}) arrived with no base tensor to apply it to"
        )
    prev32 = np.ascontiguousarray(prev).astype(np.float32)
    if mode == "delta_bf16":
        delta = np.frombuffer(
            rec["data"], dtype=_bfloat16()
        ).reshape(shape).astype(np.float32)
    elif mode == "delta_f32":
        delta = np.frombuffer(rec["data"], dtype=np.float32).reshape(shape)
    else:
        raise ValueError(f"unknown weight-leaf mode {mode!r}")
    return (prev32 + delta).astype(dtype)


def encode_update(
    new_tree, version: int, prev_tree=None, base_version: int | None = None,
) -> dict:
    """One version's wire payload: per-leaf records (delta against
    ``prev_tree`` where bit-exact, full otherwise) + the target checksum.
    ``prev_tree=None`` (first contact / forced resync) encodes full."""
    import jax

    new_leaves, treedef = jax.tree_util.tree_flatten(new_tree)
    if prev_tree is not None:
        prev_leaves, prev_def = jax.tree_util.tree_flatten(prev_tree)
        if prev_def != treedef or len(prev_leaves) != len(new_leaves):
            prev_leaves = [None] * len(new_leaves)  # structure drift → full
    else:
        prev_leaves = [None] * len(new_leaves)
    records = [
        _encode_leaf(np.asarray(n), None if p is None else np.asarray(p))
        for n, p in zip(new_leaves, prev_leaves)
    ]
    modes = {r["mode"] for r in records}
    is_delta = base_version is not None and modes != {"full"}
    payload = {
        "version": int(version),
        "base_version": int(base_version) if is_delta else None,
        "leaves": records,
        "checksum": checksum_tree(new_tree),
        "delta": is_delta,
    }
    if not is_delta:
        # full pushes carry a zero-filled container skeleton so a cold
        # worker (no prior tree) rebuilds the exact pytree structure the
        # engine expects
        skeleton = jax.tree_util.tree_unflatten(
            treedef,
            [np.zeros((), np.asarray(x).dtype) for x in new_leaves],
        )
        payload["tree_pickle"] = pickle.dumps(skeleton)
    return payload


def decode_update(payload: dict, prev_tree=None) -> tuple[int, Any]:
    """Inverse of :func:`encode_update`: (version, np tree) with the
    decoded tree verified against the sender's checksum — a mismatch is
    :class:`WeightChecksumError`, never a silently-wrong adapter."""
    import jax

    records = payload["leaves"]
    if payload.get("base_version") is not None:
        if prev_tree is None:
            raise WeightVersionError(
                f"update v{payload['version']} is a delta against "
                f"v{payload['base_version']}, which this worker does not hold"
            )
        prev_leaves, treedef = jax.tree_util.tree_flatten(prev_tree)
        if len(prev_leaves) != len(records):
            raise WeightChecksumError(
                f"delta v{payload['version']} carries {len(records)} leaves "
                f"but base v{payload['base_version']} has {len(prev_leaves)}"
            )
        leaves = [
            _decode_leaf(r, np.asarray(p))
            for r, p in zip(records, prev_leaves)
        ]
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
    else:
        # full push: the embedded skeleton carries the container structure
        skeleton = pickle.loads(payload["tree_pickle"])
        flat, skel_def = jax.tree_util.tree_flatten(skeleton)
        if len(flat) != len(records):
            raise WeightChecksumError(
                "structure skeleton does not match the leaf records"
            )
        tree = jax.tree_util.tree_unflatten(
            skel_def, [_decode_leaf(r, None) for r in records]
        )
    got = checksum_tree(tree)
    if got != payload["checksum"]:
        raise WeightChecksumError(
            f"decoded adapter v{payload['version']} checksum {got:#x} != "
            f"sender's {payload['checksum']:#x} (base "
            f"v{payload.get('base_version')})"
        )
    return int(payload["version"]), tree


def serialize_update(payload: dict) -> bytes:
    """Frame bytes for one update (the skeleton, when one is needed, was
    embedded by :func:`encode_update`)."""
    return pickle.dumps(payload)


# ------------------------------------------------------ worker-side cache


class AdapterCache:
    """Versioned 2-slot adapter cache (current + superseded).

    ``put`` keeps the inserted version plus the highest other — the
    superseded slot is what the speculative self-drafter reads remotely,
    and an out-of-order resync (a requeued shard naming an old version the
    driver re-pushed) must not evict the version it just delivered."""

    def __init__(self, slots: int = 2):
        self._slots = max(int(slots), 1)
        self._entries: dict[int, Any] = {}
        self._cv = threading.Condition()

    def put(self, version: int, tree) -> None:
        with self._cv:
            self._entries[int(version)] = tree
            while len(self._entries) > self._slots:
                evictable = sorted(
                    v for v in self._entries if v != int(version)
                )
                del self._entries[evictable[0]]
            self._cv.notify_all()

    def get(self, version: int | None):
        if version is None:
            return None
        with self._cv:
            return self._entries.get(int(version))

    def wait_for(self, version: int, timeout_s: float):
        """The resolved tree for ``version``, waiting out the benign
        dispatch-vs-broadcast race; :class:`WeightVersionError` (transient)
        after ``timeout_s`` — the driver's re-request hook takes it from
        there."""
        deadline = time.monotonic() + max(timeout_s, 0.0)
        with self._cv:
            while int(version) not in self._entries:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise WeightVersionError(
                        f"unknown weight version v{version} (cache holds "
                        f"{sorted(self._entries)}) after {timeout_s:.1f}s — "
                        "WeightVersionError: re-push required"
                    )
                self._cv.wait(remaining)
            return self._entries[int(version)]

    def versions(self) -> list[int]:
        with self._cv:
            return sorted(self._entries)

    @property
    def current_version(self) -> int | None:
        with self._cv:
            return max(self._entries) if self._entries else None

    def previous(self) -> tuple[int, Any] | None:
        """The superseded slot (version, tree), if one is held."""
        with self._cv:
            if len(self._entries) < 2:
                return None
            v = sorted(self._entries)[-2]
            return v, self._entries[v]


def resolve_wait_s() -> float:
    try:
        return float(os.environ.get(WEIGHT_WAIT_ENV, DEFAULT_WEIGHT_WAIT_S))
    except ValueError:
        return DEFAULT_WEIGHT_WAIT_S


# ------------------------------------------------------- driver-side bus


class WeightBus:
    """Driver-side versioned broadcaster over out-of-band bus connections.

    One connection per worker, SEPARATE from the dispatch channel, so a
    push lands (and swaps in-flight) while the worker's serve thread is
    deep inside a generation round. ``push`` never blocks on the wire: the
    (tree, version) lands in a single-slot mailbox consumed by the sender
    thread; a newer push supersedes an unsent one (the learner's freshest
    weights are the only ones worth broadcasting).
    """

    def __init__(
        self,
        addresses: Sequence[tuple[str, int]],
        *,
        retry_policy: RetryPolicy | None = None,
        connect_timeout_ms: int = 10_000,
        ack_timeout_ms: int = 120_000,
        connection_factory: Callable | None = None,
    ):
        self._addresses = [tuple(a) for a in addresses]
        # guards MEMBERSHIP mutations (ISSUE 20 add_worker/retire_worker):
        # the sender thread snapshots the target set per broadcast, and a
        # retire mid-broadcast must make the victim's push a skip, never a
        # flush()-wedging straggler
        self._members_mu = threading.Lock()
        self.retry = retry_policy or RetryPolicy()
        self._connect_timeout_ms = connect_timeout_ms
        self._ack_timeout_ms = ack_timeout_ms
        self._connection_factory = connection_factory or self._dial
        self._chan: dict[tuple, Any] = {}
        self._chan_mu: dict[tuple, threading.Lock] = {}
        self._chan_mu_guard = threading.Lock()
        for a in self._addresses:
            self._chan_mu[a] = threading.Lock()
        # per-worker last ACKED (version, np tree): the next delta's base
        self._acked: dict[tuple, tuple[int, Any]] = {}
        self._acked_mu = threading.Lock()
        self._req_id = 0
        self._id_mu = threading.Lock()
        # single-slot pending mailbox (LoraMailbox discipline): one tuple
        # reference, written by push / consumed whole by the sender thread.
        # The swap-out below runs under _pending_mu — an UNLOCKED consume
        # (read slot, store None) would silently drop a push() landing
        # between its read and its store (graftcheck GC103, same fix as
        # LoraMailbox._pending_mu)
        self._pending: tuple | None = None
        self._pending_mu = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._done = threading.Condition()
        self.last_pushed_version: int | None = None
        self.last_acked_version: int | None = None
        # bytes shipped for the most recent completed broadcast (all
        # workers), for the smoke's artifacts
        self.last_broadcast_bytes = 0
        self.last_broadcast_ms: float | None = None
        # per-worker ack latency of the most recent broadcast ("host:port"
        # -> ms, acked workers only) — the lineage ledger's broadcast leg
        self.last_ack_ms: dict[str, float] = {}
        # on_broadcast(version, total_ms, acks_ms, complete) runs after
        # every broadcast attempt on the sender thread (exceptions
        # swallowed), and again — complete=True — when a rejoin/re-request
        # resync finishes a broadcast a death interrupted: the lineage
        # ledger closes its policy-lag loop only on complete=True, so the
        # all-workers-acked metric never lies about a partial push
        self.on_broadcast: (
            Callable[[int, float | None, dict, bool], None] | None
        ) = None
        self._sender = threading.Thread(
            target=self._sender_loop, name="cp-weight-bus", daemon=True
        )
        self._sender.start()

    # ------------------------------------------------------------- plumbing

    def _dial(self, address: tuple[str, int]):
        from distrl_llm_tpu.distributed.control_plane import Connection, _Lib

        host, port = address
        fd = _Lib.get().cp_connect(
            host.encode(), int(port), self._connect_timeout_ms
        )
        if fd < 0:
            raise OSError(f"cannot connect weight bus to {host}:{port}")
        # channel-tagged for fault injection (ISSUE 14 satellite): a
        # "weights.send:2=close" schedule faults the Nth WEIGHTS frame
        # without perturbing the dispatch connections' counters
        return resilience.wrap_connection(Connection(fd), channel="weights")

    def _next_id(self) -> int:
        with self._id_mu:
            self._req_id += 1
            return self._req_id

    def _channel(self, address: tuple):
        conn = self._chan.get(address)
        if conn is None:
            conn = self._connection_factory(address)
            self._chan[address] = conn
        return conn

    def _drop_channel(self, address: tuple) -> None:
        conn = self._chan.pop(address, None)
        if conn is not None:
            try:
                conn.close()
            except Exception:  # noqa: BLE001 — already tearing down
                pass

    # ---------------------------------------------------------- membership

    def member_addresses(self) -> list[tuple]:
        with self._members_mu:
            return list(self._addresses)

    def _is_member(self, address: tuple) -> bool:
        with self._members_mu:
            return tuple(address) in self._addresses

    def add_worker(self, address: tuple) -> bool:
        """Admit a new broadcast target (ISSUE 20 scale-up). Must run
        BEFORE the control plane's admission hook fires — the hook's
        ``sync_worker`` call needs the address to be a member. The new
        worker has no acked base, so its first push is automatically a
        full-tensor sync. Returns False if already a member."""
        address = tuple(address)
        with self._members_mu:
            if address in self._addresses:
                return False
            self._addresses.append(address)
        with self._chan_mu_guard:
            self._chan_mu.setdefault(address, threading.Lock())
        return True

    def retire_worker(self, address: tuple) -> bool:
        """Remove a broadcast target (ISSUE 20 scale-in): drop its channel
        and acked state, and wake any ``flush()`` blocked on its ack — a
        retired worker must complete the drain, never hang it. Returns
        False if not a member."""
        address = tuple(address)
        with self._members_mu:
            if address not in self._addresses:
                return False
            self._addresses.remove(address)
        self._drop_channel(address)
        with self._acked_mu:
            self._acked.pop(address, None)
        # the survivors may ALL have acked already: recompute the
        # watermark and re-evaluate any blocked flush()
        self._refresh_acked()
        with self._done:
            self._done.notify_all()
        return True

    # --------------------------------------------------------------- pushes

    def push(self, tree_np, version: int) -> None:
        """Enqueue (tree, version) for asynchronous broadcast. Non-blocking;
        supersedes any unsent push (double-buffered single slot)."""
        with self._pending_mu:
            self._pending = (tree_np, int(version))
            self.last_pushed_version = int(version)
        self._wake.set()

    def _drained(self) -> bool:
        if self._pending is not None:
            return False
        if self.last_pushed_version is None:
            return True
        targets = self.member_addresses()  # retired workers never block a drain
        with self._acked_mu:
            return all(
                self._acked.get(a, (None, None))[0] == self.last_pushed_version
                for a in targets
            )

    def flush(self, timeout_s: float = 60.0) -> bool:
        """Block until EVERY worker has acked the newest push — whether it
        arrived by broadcast or by a rejoin/re-request resync. True when
        drained within the deadline (False e.g. while a worker is dead; its
        eventual rejoin resync completes the drain)."""
        deadline = time.monotonic() + timeout_s
        with self._done:
            while not self._drained():
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._done.wait(min(remaining, 0.25))
        return True

    def _sender_loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(timeout=0.1)
            if self._stop.is_set():
                return
            with self._pending_mu:
                pending, self._pending = self._pending, None
            self._wake.clear()
            if pending is None:
                continue
            try:
                self._broadcast(*pending)
            except Exception:  # noqa: BLE001 — the sender must survive;
                # the per-worker acked state reflects what actually landed
                log.exception("weight broadcast failed")
            with self._done:
                self._done.notify_all()

    def _broadcast(self, tree_np, version: int) -> None:
        from concurrent.futures import ThreadPoolExecutor

        t0 = time.perf_counter()
        total = 0
        oks: list[bool] = []
        acks: dict[str, float] = {}

        def timed_push(a):
            tw = time.perf_counter()
            ok, nbytes = self._push_worker(a, tree_np, version)
            return a, ok, nbytes, (time.perf_counter() - tw) * 1e3

        # membership snapshot: a worker added mid-broadcast gets its full
        # sync through the admission hook; one retired mid-broadcast turns
        # its in-flight push into a skip (checked per attempt below)
        targets = self.member_addresses()
        with ThreadPoolExecutor(
            max_workers=max(len(targets), 1),
            thread_name_prefix="cp-weight-push",
        ) as pool:
            futs = [pool.submit(timed_push, a) for a in targets]
            for f in futs:
                a, ok, nbytes, ack_ms = f.result()
                oks.append(ok)
                total += nbytes
                if ok:
                    acks[f"{a[0]}:{a[1]}"] = ack_ms
        self.last_broadcast_bytes = total
        self.last_ack_ms = acks
        ms = (time.perf_counter() - t0) * 1e3
        self.last_broadcast_ms = ms
        telemetry.hist_observe(resilience.CP_WEIGHT_BROADCAST_MS, ms)
        # learner-push → last-worker-ack: the honest weight-sync latency
        # (ISSUE 8's obs/weight_sync_ms previously timed only the local
        # _push_weights call)
        from distrl_llm_tpu import obs

        telemetry.gauge_set(obs.OBS_WEIGHT_SYNC_MS, ms)
        if all(oks) and oks:
            self.last_acked_version = int(version)
        else:
            self._refresh_acked()
        self._notify_broadcast(version, ms, acks, bool(oks) and all(oks))

    def _notify_broadcast(self, version: int, ms: float | None,
                          acks: dict, complete: bool) -> None:
        hook = self.on_broadcast
        if hook is not None:
            try:
                hook(int(version), ms, dict(acks), complete)
            except Exception:  # noqa: BLE001 — lineage bookkeeping must
                # never take the sender thread down with it
                log.warning("on_broadcast hook failed", exc_info=True)

    def _push_worker(
        self, address: tuple, tree_np, version: int,
        *, force_full: bool = False,
    ) -> tuple[bool, int]:
        """Push one version to one worker, delta against its acked base,
        with policy-bounded retries; checksum/unknown-base failures fall
        back to a full-tensor send. Returns (acked, bytes_sent)."""
        from distrl_llm_tpu.distributed.control_plane import (
            MSG_ERROR, MSG_RESULT, MSG_WEIGHTS, WorkerDeadError,
        )

        host, port = address
        sent_total = 0
        full = force_full
        with self._chan_mu_guard:
            mu = self._chan_mu.setdefault(tuple(address), threading.Lock())
        with mu:
            for attempt in range(self.retry.max_call_retries + 1):
                if not self._is_member(tuple(address)):
                    # retired mid-broadcast (ISSUE 20): skip, don't retry —
                    # the drain completes on the survivors' acks
                    return False, sent_total
                with self._acked_mu:
                    base = None if full else self._acked.get(tuple(address))
                payload = encode_update(
                    tree_np, version,
                    prev_tree=base[1] if base else None,
                    base_version=base[0] if base else None,
                )
                # causal trace context (ISSUE 10): while tracing, the push
                # frame names the driver span that caused it, so the
                # worker's worker/weights span links back across tracks
                ctx = None
                if telemetry.enabled():
                    ctx = telemetry.next_dispatch_context()
                    payload["trace_ctx"] = ctx
                frame = serialize_update(payload)
                mode = "delta" if payload["base_version"] is not None else "full"
                rid = self._next_id()
                try:
                    with telemetry.span(
                        WEIGHT_PUSH_SPAN, worker=f"{host}:{port}",
                        version=int(version), bytes=len(frame), mode=mode,
                        **({"dispatch_id": ctx["dispatch_id"]} if ctx else {}),
                    ):
                        if ctx is not None:
                            telemetry.emit_flow_start(ctx["dispatch_id"])
                        conn = self._channel(tuple(address))
                        # the per-worker channel lock is MEANT to pin the
                        # wire for the whole push+ack exchange: only the
                        # sender thread and a rejoin/re-request resync ever
                        # contend, and interleaving their frames would
                        # corrupt the request/response pairing
                        # graftcheck: disable=GC102 -- channel serialization: push+ack must be one uninterleaved exchange
                        conn.send(
                            MSG_WEIGHTS, rid, frame,
                            timeout_ms=self._ack_timeout_ms,
                        )
                        sent_total += len(frame)
                        telemetry.counter_add(
                            resilience.CP_WEIGHT_BYTES, len(frame)
                        )
                        telemetry.counter_add(resilience.CP_WEIGHT_PUSHES)
                        if mode == "full":
                            telemetry.counter_add(
                                resilience.CP_WEIGHT_FULL_SYNCS
                            )
                        # graftcheck: disable=GC102 -- same exchange: the ack belongs to the frame just sent on this channel
                        frame_back = conn.recv(self._ack_timeout_ms)
                        if frame_back is None:
                            raise WorkerDeadError(
                                f"weight ack from {host}:{port} missed the "
                                f"{self._ack_timeout_ms}ms deadline"
                            )
                        msg_type, got_rid, body = frame_back
                        if got_rid != rid:
                            raise WorkerDeadError(
                                f"weight bus to {host}:{port}: "
                                "protocol violation"
                            )
                        if msg_type == MSG_ERROR:
                            tb = body.decode(errors="replace")
                            if (
                                "WeightChecksumError" in tb
                                or "WeightVersionError" in tb
                            ):
                                # the worker's base slot is unusable (or
                                # absent): clear acked and resend full
                                log.warning(
                                    "weight push v%d to %s:%d rejected "
                                    "(%s); falling back to full tensor",
                                    version, host, port,
                                    tb.strip().splitlines()[-1],
                                )
                                with self._acked_mu:
                                    self._acked.pop(tuple(address), None)
                                full = True
                                continue
                            raise WorkerDeadError(
                                f"weight push to {host}:{port} failed:\n{tb}"
                            )
                        if msg_type != MSG_RESULT:
                            raise WorkerDeadError(
                                f"weight bus to {host}:{port}: unexpected "
                                f"frame type {msg_type}"
                            )
                        ack = pickle.loads(body)
                        if int(ack.get("version", -1)) != int(version):
                            raise WorkerDeadError(
                                f"weight ack names v{ack.get('version')} "
                                f"!= pushed v{version}"
                            )
                    with self._acked_mu:
                        self._acked[tuple(address)] = (int(version), tree_np)
                    return True, sent_total
                except WorkerDeadError as e:
                    self._drop_channel(tuple(address))
                    if attempt >= self.retry.max_call_retries:
                        log.warning(
                            "weight push v%d to %s:%d exhausted retries: %s",
                            version, host, port, e,
                        )
                        break
                    # backoff INSIDE the channel lock on purpose: a resync
                    # (sync_worker) slipping in mid-retry would race the
                    # re-dial for the same worker's wire; nothing else
                    # contends on this per-address lock
                    # graftcheck: disable=GC102 -- per-worker retry backoff; the lock scope IS the retry exchange
                    time.sleep(self.retry.backoff(attempt))
                except OSError as e:  # connect failure
                    if attempt >= self.retry.max_call_retries:
                        log.warning(
                            "weight bus cannot reach %s:%d: %s",
                            host, port, e,
                        )
                        break
                    # graftcheck: disable=GC102 -- per-worker retry backoff; the lock scope IS the retry exchange
                    time.sleep(self.retry.backoff(attempt))
        # the worker is unreachable: clear acked so the eventual rejoin
        # resync starts from a full tensor
        with self._acked_mu:
            self._acked.pop(tuple(address), None)
        return False, sent_total

    # ------------------------------------------------------------- resyncs

    def sync_worker(
        self, address: tuple, tree_np=None, version: int | None = None,
    ) -> bool:
        """Synchronous FULL-tensor push of one version to one worker — the
        rejoin re-admission hook and the unknown-version re-request path.
        Defaults to the newest pushed tree. True when acked."""
        if tree_np is None or version is None:
            pending = self._pending
            if pending is not None:
                tree_np, version = pending
            else:
                with self._acked_mu:
                    current = [
                        (v, t) for v, t in self._acked.values()
                        if self.last_pushed_version is None
                        or v == self.last_pushed_version
                    ]
                if current:
                    version, tree_np = current[0]
        if tree_np is None or version is None:
            return True  # nothing ever pushed: nothing to resync
        self._drop_channel(tuple(address))
        ok, _ = self._push_worker(
            tuple(address), tree_np, int(version), force_full=True
        )
        if ok:
            self._refresh_acked()
            if self.last_acked_version == int(version):
                # this resync completed a broadcast a death interrupted:
                # EVERY worker now holds the version — tell the ledger so
                # the policy-lag loop closes at the true all-acked time
                self._notify_broadcast(int(version), None, {}, True)
            with self._done:
                self._done.notify_all()
        return ok

    def _refresh_acked(self) -> None:
        """Recompute the all-workers-acked watermark from per-worker state
        (a rejoin resync can complete a broadcast a death interrupted)."""
        if self.last_pushed_version is None:
            return
        targets = self.member_addresses()
        with self._acked_mu:
            if all(
                self._acked.get(a, (None, None))[0] == self.last_pushed_version
                for a in targets
            ):
                self.last_acked_version = self.last_pushed_version

    def acked_version(self, address: tuple) -> int | None:
        with self._acked_mu:
            entry = self._acked.get(tuple(address))
        return entry[0] if entry else None

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        self._sender.join(timeout=5)
        for address in list(self._chan):
            self._drop_channel(address)
