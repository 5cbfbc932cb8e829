"""GatewayService: the engine-facing half of the serving gateway
(ISSUE 19).

The service owns an open request queue fed by :mod:`.server` (or any
in-process producer — the smoke drives it directly) and a
round-forming loop: drain up to ``max_groups_per_round`` requests in
class-then-FIFO-with-aging order, attach the round's tenancy to the
engine (``round_meta`` / ``quota_book`` / ``stream_hook``), run ONE
``engine.generate`` round under the engine lock, and demux streamed
tokens back to each request's subscriber queue.

Rounds stay the engine's batching unit — open-loop realism comes from
the queue (arrivals never wait for completions) and from stamping each
request's true ``arrival_ts`` into the serving ledger, so TTFT /
queue-wait include the open-queue wait, not just the in-round wait.
Each request is one group with ``n=1`` candidates; its ``(trace_id,
dispatch_id)`` lineage context is allocated at arrival via
``telemetry.next_dispatch_context()`` — the SAME allocation path the
trainer's dispatches use, so gateway requests render in Perfetto and
join ``lineage_report`` / ``serving_report`` rows for free."""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Any

import numpy as np

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.gateway.scheduler import (
    CLASS_RANK,
    DEFAULT_CLASS,
    GATEWAY_REJECTED,
    GATEWAY_ROUNDS,
    GATEWAY_STREAMED_TOKENS,
    PRIORITY_CLASSES,
    GatewayRequest,
    RequestQueue,
    TenantQuotaBook,
    sanitize_tenant,
)


class GatewayService:
    """Round-forming loop between the request queue and one paged engine.

    ``engine_lock`` serializes gateway rounds against any other owner of
    the same engine (the worker's ``generate`` op); pass the worker's lock
    when sharing, or let the service own a private one."""

    def __init__(self, engine, params, tokenizer, *, lora=None,
                 classes: tuple[str, ...] = PRIORITY_CLASSES,
                 quota: dict[str, int] | None = None,
                 serving_ledger=None,
                 control_limits=None,
                 max_groups_per_round: int = 8,
                 temperature: float = 0.0,
                 top_p: float = 1.0,
                 default_max_new_tokens: int | None = None,
                 seed: int = 0,
                 engine_lock: threading.Lock | None = None,
                 poll_s: float = 0.005):
        if not getattr(engine, "continuous_admission", False):
            raise ValueError(
                "GatewayService requires a paged engine with "
                "continuous_admission (the request-queue scheduler is the "
                "gateway's admission plane)"
            )
        if getattr(engine, "spec_draft", 0):
            # the turn-hook precedent: the speculative sub-path drives its
            # own admission/stream cadence and does not consult the
            # gateway's round hooks — reject rather than silently lose the
            # class policy and streaming
            raise ValueError(
                "GatewayService does not support speculative decoding "
                "(spec_draft) — the gateway's scheduling and streaming "
                "hooks ride the plain refill boundaries"
            )
        self.engine = engine
        self.params = params
        self.lora = lora
        self.tokenizer = tokenizer
        self.classes = tuple(classes)
        self.quota_book = TenantQuotaBook(quota)
        self.serving_ledger = serving_ledger
        self.control_limits = control_limits
        self.max_groups_per_round = max(1, int(max_groups_per_round))
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.default_max_new_tokens = (
            int(default_max_new_tokens)
            if default_max_new_tokens else int(engine.max_new_tokens)
        )
        self.seed = int(seed)
        self.engine_lock = engine_lock or threading.Lock()
        self.poll_s = float(poll_s)
        self.queue = RequestQueue(self.classes)
        self.rounds = 0
        self.completed = 0
        self.failed = 0
        # run-cumulative per-class shed/preempt group tallies (the engine's
        # last_pool_stats only covers one round); tools/gateway_smoke.py
        # reads this
        self.class_actions: dict[str, dict[str, int]] = {
            "shed": {}, "preempt": {},
        }
        self.completed_by_class: dict[str, int] = {}
        self._rid = 0
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------ producer

    def submit(self, prompt: str | None = None, *,
               prompt_ids=None,
               tenant: str = "anon", cls: str = DEFAULT_CLASS,
               max_new_tokens: int | None = None,
               temperature: float | None = None,
               arrival_ts: float | None = None) -> GatewayRequest:
        """Enqueue one request; returns the request whose ``events`` queue
        streams ``("tokens", [ids])`` chunks then one ``("done", result)``
        or ``("error", message)``. Tokenizes ``prompt`` when ``prompt_ids``
        is not given; prompts longer than the engine window keep their
        TAIL (the recent context)."""
        if cls not in CLASS_RANK:
            telemetry.counter_add(GATEWAY_REJECTED)
            raise ValueError(
                f"unknown priority class {cls!r} "
                f"(expected one of {PRIORITY_CLASSES})"
            )
        if cls not in self.classes:
            telemetry.counter_add(GATEWAY_REJECTED)
            raise ValueError(
                f"priority class {cls!r} is not served by this gateway "
                f"(serving {self.classes})"
            )
        if prompt_ids is None:
            if prompt is None:
                telemetry.counter_add(GATEWAY_REJECTED)
                raise ValueError("request needs prompt or prompt_ids")
            prompt_ids = self.tokenizer.encode(
                str(prompt), add_special_tokens=False
            )
        ids = np.asarray(prompt_ids, np.int32).ravel()
        if ids.size == 0:
            telemetry.counter_add(GATEWAY_REJECTED)
            raise ValueError("empty prompt")
        p_max = int(self.engine.max_prompt_tokens)
        if ids.size > p_max:
            ids = ids[-p_max:]
        window = min(
            int(max_new_tokens or self.default_max_new_tokens),
            int(self.engine.max_new_tokens),
        )
        lim = self.quota_book.limit_for(sanitize_tenant(tenant))
        if lim is not None and int(ids.size) + window > lim:
            # a footprint the tenant's quota can NEVER hold would stall in
            # the engine queue forever — reject at the door instead (the
            # engine charges exactly prompt + window, see try_admit_group)
            telemetry.counter_add(GATEWAY_REJECTED)
            raise ValueError(
                f"request footprint {int(ids.size) + window} tokens "
                f"(prompt {int(ids.size)} + window {window}) exceeds "
                f"tenant {sanitize_tenant(tenant)!r} quota {lim} — it "
                "could never admit; shrink the prompt or max_new_tokens"
            )
        with self._mu:
            self._rid += 1
            rid = self._rid
        req = GatewayRequest(
            rid=rid, tenant=sanitize_tenant(tenant), cls=cls,
            prompt_ids=ids, prompt_len=int(ids.size),
            max_new_tokens=window,
            temperature=(
                self.temperature if temperature is None
                else float(temperature)
            ),
            arrival_ts=time.time() if arrival_ts is None else arrival_ts,
            # lineage stamp at ARRIVAL: the same counter the trainer's
            # dispatches draw from — one allocation path (ISSUE 16)
            trace_ctx=telemetry.next_dispatch_context(),
            events=queue_mod.Queue(),
        )
        self.queue.push(req)
        self._wake.set()
        return req

    # ----------------------------------------------------------- lifecycle

    def start(self) -> "GatewayService":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._loop, name="gateway-service", daemon=True
        )
        self._thread.start()
        return self

    def close(self, timeout_s: float = 10.0) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            self._thread = None

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Block until the open queue and in-flight round are empty (the
        replay harness's end-of-run barrier). False on timeout."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            with self._mu:
                busy = self._rid > self.completed + self.failed
            if not busy and len(self.queue) == 0:
                return True
            time.sleep(self.poll_s)
        return False

    # ---------------------------------------------------------- round loop

    def _loop(self) -> None:
        while not self._stop.is_set():
            batch = self.queue.pop_batch(self.max_groups_per_round)
            if not batch:
                self._wake.wait(timeout=self.poll_s)
                self._wake.clear()
                continue
            try:
                self._run_round(batch)
            except Exception as e:  # noqa: BLE001 — a failed round fails
                # its requests, not the gateway: subscribers get the error
                # and the loop keeps serving
                with self._mu:
                    self.failed += len(batch)
                # reservations charged by the dead round never reach their
                # group-finish credit — reset so the book can't wedge
                self.quota_book.reset()
                for req in batch:
                    req.events.put(("error", f"{type(e).__name__}: {e}"))

    def _run_round(self, batch: list[GatewayRequest]) -> None:
        import jax

        from distrl_llm_tpu.config import SamplingConfig

        engine = self.engine
        b = len(batch)
        p_max = int(engine.max_prompt_tokens)
        pad = int(engine.pad_id)
        prompt_ids = np.full((b, p_max), pad, np.int32)
        prompt_mask = np.zeros((b, p_max), np.int32)
        meta: dict[int, dict[str, Any]] = {}
        for g, req in enumerate(batch):
            ids = req.prompt_ids
            prompt_ids[g, p_max - ids.size:] = ids  # left-pad (trainer contract)
            prompt_mask[g, p_max - ids.size:] = 1
            meta[g] = {
                "tenant": req.tenant, "cls": req.cls,
                "rank": CLASS_RANK[req.cls], "seq": req.seq,
                "arrival_ts": req.arrival_ts,
                "trace_ctx": req.trace_ctx,
                "max_new": req.max_new_tokens,
            }
        round_max = max(req.max_new_tokens for req in batch)
        sampling = SamplingConfig(
            max_tokens=round_max,
            temperature=max(r.temperature for r in batch),
            top_p=self.top_p, n=1,
        )
        streamed: dict[int, int] = {}

        def stream_hook(cand: int, toks: list[int]) -> None:
            req = batch[cand]
            sent = streamed.get(cand, 0)
            room = req.max_new_tokens - sent
            if room <= 0:
                return
            toks = toks[:room]
            streamed[cand] = sent + len(toks)
            telemetry.counter_add(GATEWAY_STREAMED_TOKENS, len(toks))
            req.events.put(("tokens", [int(t) for t in toks]))

        with self.engine_lock:
            self.rounds += 1
            telemetry.counter_add(GATEWAY_ROUNDS)
            engine.round_meta = meta
            engine.quota_book = self.quota_book
            engine.stream_hook = stream_hook
            prev_ledger = engine.serving_ledger
            if self.serving_ledger is not None:
                engine.serving_ledger = self.serving_ledger
            prev_limits = engine.control_limits
            if self.control_limits is not None:
                engine.control_limits = self.control_limits
            try:
                result = engine.generate(
                    self.params, self.lora, prompt_ids, prompt_mask,
                    sampling, jax.random.PRNGKey(self.seed + self.rounds),
                )
            finally:
                # detach-pattern discipline: the engine leaves the round
                # exactly as a non-gateway owner would find it
                engine.round_meta = None
                engine.quota_book = None
                engine.stream_hook = None
                engine.serving_ledger = prev_ledger
                engine.control_limits = prev_limits
        stats = engine.last_pool_stats or {}
        for kind, per_cls in (stats.get("class_actions") or {}).items():
            agg = self.class_actions.setdefault(kind, {})
            for cls_name, cnt in per_cls.items():
                agg[cls_name] = agg.get(cls_name, 0) + int(cnt)
        for g, req in enumerate(batch):
            ln = min(int(result.lengths[g, 0]), req.max_new_tokens)
            toks = [int(t) for t in result.tokens[g, 0, :ln]]
            with self._mu:
                self.completed += 1
                self.completed_by_class[req.cls] = (
                    self.completed_by_class.get(req.cls, 0) + 1
                )
            req.events.put(("done", {
                "rid": req.rid,
                "tenant": req.tenant,
                "cls": req.cls,
                "tokens": toks,
                "text": self._decode(toks),
                "gen_tokens": ln,
                "prompt_tokens": req.prompt_len,
                "trace_id": (req.trace_ctx or {}).get("trace_id"),
                "dispatch_id": (req.trace_ctx or {}).get("dispatch_id"),
                "class_actions": stats.get("class_actions"),
            }))

    def _decode(self, toks: list[int]) -> str:
        try:
            return self.tokenizer.decode(toks, skip_special_tokens=True)
        except TypeError:
            return self.tokenizer.decode(toks)

    def stats(self) -> dict[str, Any]:
        with self._mu:
            return {
                "rounds": self.rounds,
                "submitted": self._rid,
                "completed": self.completed,
                "completed_by_class": dict(self.completed_by_class),
                "failed": self.failed,
                "queue_depth": len(self.queue),
                "class_actions": {
                    k: dict(v) for k, v in self.class_actions.items()
                },
                "quota": self.quota_book.stats(),
            }
