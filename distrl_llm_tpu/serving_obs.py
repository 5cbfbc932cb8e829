"""Request-level serving observability: per-group lifecycle ledger, SLO
latency histograms, and an admission audit for the continuous-batching
engine (ISSUE 13).

PR 12 turned the paged rollout engine into a multi-tenant serving engine
(copy-on-write prefix sharing + lazy group admission) but the observability
plane still saw it as a batch job: round-level tok/s, admission *counters*
(``engine/backfill_admits``), and nothing per request. The operational
signal of an RL serving engine is its latency/lag STRUCTURE — PipelineRL
optimizes lag, Laminar shows heterogeneous trajectory lengths make
per-request distributions (not means) the signal — and ROADMAP item 5's
closed-loop controllers cannot steer on quantities nobody measures. This
module is the measurement layer, one bounded :class:`ServingLedger` per
engine:

* **Per-group lifecycle** — ``enqueue → admit (slot + chain-alias info from
  the page pool) → prefill done → first token → [preempt/resume]* →
  finish``, recorded from the refill/spec/continuous loops at host chunk
  boundaries (timestamps are therefore boundary-granular upper bounds — the
  loop's own observability cadence, no extra device syncs). Derived
  latencies land on the registry as histograms every endpoint scrape and
  trace sees: ``serving/ttft_ms`` (enqueue → first token),
  ``serving/queue_wait_ms`` (enqueue → slot admission), ``serving/tpot_ms``
  (steady-state ms per output token), ``serving/e2e_ms`` (enqueue →
  last candidate finished).
* **Admission audit** — every admission pass that leaves waiting work
  unadmitted is a *declined pass*, attributed to exactly one reason:
  ``no_slots`` (every slot busy), ``no_pages`` (free list can't cover the
  admission), ``chain_cap`` (the live prefix-chain cap), or
  ``budget_wedge`` (the PR 12 wedge detector: all slots dead and the page
  budget cannot make progress). ``serving/admission_stalls/<reason>``
  counters explain the ``slot_idle_frac`` round statistic instead of just
  measuring it; ``tools/serving_smoke.py`` asserts the reason counts sum
  to the declined passes — an unattributed decline is a bug, not a gap.
* **Live occupancy tracks** — per-boundary gauges (``serving/live_slots``,
  ``serving/queue_depth``, ``serving/free_pages``) that render as Perfetto
  counter tracks while tracing, aligned with the decode spans.

Closed records stream to ``<out_dir>/serving.jsonl`` (one JSON object per
line, ``kind: "group"``; ``close()`` appends one ``kind: "summary"`` line
with the stall breakdown and occupancy summary) — ``tools/serving_report.py``
reports from the file alone. Records carry the generate dispatch's
``(trace_id, dispatch_id)`` read from :func:`telemetry.current_trace_context`
— the SAME ids the lineage ledger stores, one allocation path, no second
counter — so ``tools/lineage_report.py --serving`` joins serving latency
onto policy-lag rows.

Cost contract: the ledger exists only when armed (``--serving_obs`` /
worker ``--serving-obs`` / a ledger an owner attaches); every hook site in
the engine is one ``is not None`` attribute check when off, so the
telemetry-off fast path and the sync byte-identity pins are untouched.
The ledger never changes scheduling decisions — byte-identical outputs
with the ledger on or off are pinned in tests/test_serving_obs.py.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict, deque
from dataclasses import asdict, dataclass, field
from typing import Any, Mapping

from distrl_llm_tpu import telemetry

# ------------------------------------------------------------- series names
# (schema-pinned, with types, in tests/test_telemetry.py; graftcheck GC2xx:
# this module is the single owner of every serving/* and fleet/serving_*
# name — consumers reference these constants, never a second literal)

SERVING_TTFT_MS = "serving/ttft_ms"              # hist: enqueue → first token
SERVING_TPOT_MS = "serving/tpot_ms"              # hist: ms per output token
SERVING_QUEUE_WAIT_MS = "serving/queue_wait_ms"  # hist: enqueue → admission
SERVING_E2E_MS = "serving/e2e_ms"                # hist: enqueue → finish
# declined-admission attribution: one counter per reason, derived as
# f"{SERVING_ADMISSION_STALLS}/<reason>" (constant-prefix derivation)
SERVING_ADMISSION_STALLS = "serving/admission_stalls"
SERVING_DECLINED_PASSES = "serving/declined_passes"    # counter
SERVING_ADMISSION_PASSES = "serving/admission_passes"  # counter
SERVING_LIVE_SLOTS = "serving/live_slots"        # gauge (Perfetto track)
SERVING_QUEUE_DEPTH = "serving/queue_depth"      # gauge (Perfetto track)
SERVING_FREE_PAGES = "serving/free_pages"        # gauge (Perfetto track)
SERVING_RECORDS_CLOSED = "serving/records_closed"      # counter
SERVING_RING_EVICTIONS = "serving/ring_evictions"      # counter
# per-class decline attribution (ISSUE 19): gateway rounds carry a
# priority class on the head group; the flat stalls counters above stay
# the conservation ledger while f"{SERVING_CLASS_STALLS}/<class>/<reason>"
# explains WHICH class ate the decline (separate prefix so the fleet fold
# of the flat reasons never double-counts)
SERVING_CLASS_STALLS = "serving/class_stalls"

# fleet-folded serving view (FleetAggregator publishes these from the
# per-worker obs blobs — cumulative hist summaries, so the mean is the
# honest fleet-wide scalar; percentiles stay per-worker on each endpoint)
FLEET_SERVING_TTFT_MEAN_MS = "fleet/serving_ttft_ms_mean"
FLEET_SERVING_TTFT_MAX_MS = "fleet/serving_ttft_ms_max"
FLEET_SERVING_QUEUE_WAIT_MEAN_MS = "fleet/serving_queue_wait_ms_mean"
FLEET_SERVING_QUEUE_WAIT_MAX_MS = "fleet/serving_queue_wait_ms_max"
FLEET_SERVING_STALLS = "fleet/serving_admission_stalls"

# the complete decline-reason vocabulary (the admission audit's contract:
# every declined pass carries exactly one of these). "shed" is the ISSUE 14
# SLO load-shedder's reason: the controller, not the pool, deferred the
# head group; "quota" (ISSUE 19) is the gateway's per-tenant token budget
# declining the head group — the conservation sum(stalls) ==
# declined_passes holds with controllers and gateway on or off
STALL_REASONS = (
    "no_slots", "no_pages", "chain_cap", "budget_wedge", "shed", "quota",
)

# closed-value window per metric for percentile queries (the smoke):
# bounds host memory on a long-running server; counts/sums in the
# registry histograms stay exact regardless
_SAMPLE_WINDOW = 8192


@dataclass
class ServingRecord:
    """One task group's serving lifecycle. Times are wall-clock
    ``time.time()`` seconds observed at host chunk boundaries; ``None``
    means the stage has not happened (yet)."""

    uid: int
    group_index: int           # position within the round's prompt batch
    n: int                     # candidates in the group
    prompt_tokens: int
    # multi-tenant identity (ISSUE 19): None on non-gateway rounds — the
    # single-tenant JSONL shape is pinned unchanged in tests
    tenant: str | None = None
    priority: str | None = None
    # causal ids shared with the lineage ledger (telemetry trace context —
    # one allocation path, no second counter)
    trace_id: str | None = None
    dispatch_id: int | None = None
    # lifecycle timestamps (monotone by construction: enqueue <= admit <=
    # first_token <= finish; prefill_done sits between enqueue and first
    # token on the continuous path)
    enqueue_ts: float | None = None
    admit_ts: float | None = None
    prefill_done_ts: float | None = None
    first_token_ts: float | None = None
    finish_ts: float | None = None
    # admission detail: one entry per slot admission of any candidate —
    # {cand, slot, shared_pages, cow, backfill, resumed, ts}
    admits: list = field(default_factory=list)
    preemptions: int = 0
    resumes: int = 0
    backfilled: bool = False   # any candidate admitted after round start
    gen_tokens: int | None = None
    # derived latencies (ms)
    queue_wait_ms: float | None = None
    ttft_ms: float | None = None
    tpot_ms: float | None = None
    e2e_ms: float | None = None

    def to_dict(self) -> dict[str, Any]:
        d = asdict(self)
        d["kind"] = "group"
        return d


class ServingLedger:
    """Bounded per-group serving-lifecycle ring + admission audit.

    Thread-safe (a worker's dispatch handler and a scraping endpoint can
    overlap); every hook is a cheap dict/deque operation under one lock.
    ``ring_size`` bounds OPEN records — an evicted record is counted
    (``serving/ring_evictions``) and its partial lifecycle still lands in
    the JSONL, never silent."""

    def __init__(self, ring_size: int = 1024, out_dir: str | None = None):
        if ring_size < 1:
            raise ValueError(f"ring_size must be >= 1, got {ring_size}")
        self.ring_size = int(ring_size)
        self.out_dir = out_dir
        self._mu = threading.Lock()
        self._ring: OrderedDict[int, ServingRecord] = OrderedDict()
        self._uid = 0
        self._file = None  # lazily opened <out_dir>/serving.jsonl
        # per-record finished-candidate sets (host bookkeeping, not
        # serialized — the record's finish_ts is the durable fact)
        self._finished: dict[int, set[int]] = {}
        # admission audit totals (the smoke's conservation contract:
        # sum(stalls.values()) == declined_passes)
        self.stalls: dict[str, int] = {r: 0 for r in STALL_REASONS}
        # per-class breakdown (ISSUE 19): {class: {reason: count}} for the
        # declines whose head group carried a priority class. Invariant:
        # sum over classes of stalls_by_class[cls][r] <= stalls[r], equal
        # when every decline is class-attributed (all-gateway traffic)
        self.stalls_by_class: dict[str, dict[str, int]] = {}
        self.declined_passes = 0
        self.boundary_passes = 0
        # bounded occupancy timeline: (ts, live_slots, queue_depth,
        # free_pages) per boundary, for the report's occupancy summary
        self.occupancy: deque = deque(maxlen=4096)
        # closed-record latency samples for percentile queries
        self._samples: dict[str, deque] = {
            "ttft_ms": deque(maxlen=_SAMPLE_WINDOW),
            "queue_wait_ms": deque(maxlen=_SAMPLE_WINDOW),
            "tpot_ms": deque(maxlen=_SAMPLE_WINDOW),
            "e2e_ms": deque(maxlen=_SAMPLE_WINDOW),
        }
        # per-class samples keyed (class, metric), populated only for
        # records that carried a priority class (gateway traffic)
        self._class_samples: dict[tuple[str, str], deque] = {}
        self.closed_groups = 0

    # ------------------------------------------------------------- plumbing

    def _write(self, doc: dict[str, Any]) -> None:
        """Stream one record to the JSONL file (lock held)."""
        if self.out_dir is None:
            return
        if self._file is None:
            os.makedirs(self.out_dir, exist_ok=True)
            self._file = open(
                os.path.join(self.out_dir, "serving.jsonl"), "a"
            )
        self._file.write(json.dumps(doc, default=str) + "\n")
        self._file.flush()

    def _rec(self, uid) -> ServingRecord | None:
        if uid is None:
            return None
        return self._ring.get(uid)

    def _close_locked(self, rec: ServingRecord) -> None:
        self._ring.pop(rec.uid, None)
        self._finished.pop(rec.uid, None)
        self.closed_groups += 1
        telemetry.counter_add(SERVING_RECORDS_CLOSED)
        for key in ("ttft_ms", "queue_wait_ms", "tpot_ms", "e2e_ms"):
            v = getattr(rec, key)
            if v is not None:
                self._samples[key].append(float(v))
                if rec.priority is not None:
                    self._class_samples.setdefault(
                        (rec.priority, key), deque(maxlen=_SAMPLE_WINDOW)
                    ).append(float(v))
        self._write(rec.to_dict())

    # ------------------------------------------------------------ lifecycle

    def on_enqueue(self, group_index: int, *, n: int, prompt_tokens: int,
                   tenant: str | None = None, priority: str | None = None,
                   trace_ctx: Mapping[str, Any] | None = None,
                   ts: float | None = None) -> int:
        """Open one record as the group enters the engine's request queue.
        Stamps the ambient trace context (the worker handler binds the
        driver dispatch's ids for the frame's duration) so serving records
        join onto lineage/policy-lag rows by dispatch_id. Gateway rounds
        pass ``trace_ctx`` explicitly — each HTTP request carries its OWN
        dispatch ids allocated at arrival, not the round's ambient frame —
        plus the tenant/priority identity."""
        ts = time.time() if ts is None else ts
        ctx = (
            trace_ctx if trace_ctx is not None
            else telemetry.current_trace_context()
        )
        with self._mu:
            self._uid += 1
            uid = self._uid
            rec = ServingRecord(
                uid=uid, group_index=int(group_index), n=int(n),
                prompt_tokens=int(prompt_tokens),
                tenant=tenant, priority=priority,
                trace_id=ctx.get("trace_id") if ctx else None,
                dispatch_id=ctx.get("dispatch_id") if ctx else None,
                enqueue_ts=ts,
            )
            self._ring[uid] = rec
            self._finished[uid] = set()
            while len(self._ring) > self.ring_size:
                _, old = self._ring.popitem(last=False)
                self._finished.pop(old.uid, None)
                telemetry.counter_add(SERVING_RING_EVICTIONS)
                self._write(old.to_dict())
        return uid

    def on_admit(self, uid, *, cand: int, slot: int, shared_pages: int = 0,
                 cow: bool = False, backfill: bool = False,
                 resumed: bool = False, prefix_hit_tokens: int = 0,
                 ts: float | None = None) -> None:
        """A candidate of this group was admitted into a decode slot
        (``shared_pages``/``cow`` are the page pool's chain-alias facts for
        the slot: how many prefix pages it aliases and whether the
        copy-on-write tail split rode this admission;
        ``prefix_hit_tokens`` is the radix-cache hit the group's admission
        rode in on — tokens of prompt that skipped prefill entirely, 0 on
        cold admissions and cache-off engines)."""
        ts = time.time() if ts is None else ts
        with self._mu:
            rec = self._rec(uid)
            if rec is None:
                return
            rec.admits.append({
                "cand": int(cand), "slot": int(slot),
                "shared_pages": int(shared_pages), "cow": bool(cow),
                "backfill": bool(backfill), "resumed": bool(resumed),
                "prefix_hit_tokens": int(prefix_hit_tokens),
                "ts": ts,
            })
            if resumed:
                rec.resumes += 1
            if backfill:
                rec.backfilled = True
            if rec.admit_ts is None and not resumed:
                rec.admit_ts = ts
                if rec.enqueue_ts is not None:
                    rec.queue_wait_ms = (ts - rec.enqueue_ts) * 1e3
                    telemetry.hist_observe(
                        SERVING_QUEUE_WAIT_MS, rec.queue_wait_ms,
                        trace_sample=True,
                    )
                    if rec.priority is not None:
                        telemetry.hist_observe(
                            f"{SERVING_QUEUE_WAIT_MS}/{rec.priority}",
                            rec.queue_wait_ms,
                        )

    def on_prefill_done(self, uid, ts: float | None = None) -> None:
        with self._mu:
            rec = self._rec(uid)
            if rec is not None and rec.prefill_done_ts is None:
                rec.prefill_done_ts = time.time() if ts is None else ts

    def on_first_token(self, uid, ts: float | None = None) -> None:
        """First observed generated token of ANY candidate in the group
        (idempotent — boundary snapshots re-report progress every pass)."""
        ts = time.time() if ts is None else ts
        with self._mu:
            rec = self._rec(uid)
            if rec is None or rec.first_token_ts is not None:
                return
            rec.first_token_ts = ts
            if rec.enqueue_ts is not None:
                rec.ttft_ms = (ts - rec.enqueue_ts) * 1e3
                telemetry.hist_observe(
                    SERVING_TTFT_MS, rec.ttft_ms, trace_sample=True
                )
                if rec.priority is not None:
                    telemetry.hist_observe(
                        f"{SERVING_TTFT_MS}/{rec.priority}", rec.ttft_ms
                    )

    def on_preempt(self, uid, cand: int) -> None:  # noqa: ARG002 — the
        # candidate id documents intent at call sites; the record
        # aggregates per group
        with self._mu:
            rec = self._rec(uid)
            if rec is not None:
                rec.preemptions += 1

    def on_finish(self, uid, cand: int, ts: float | None = None) -> None:
        """A candidate finished; the group's lifecycle completes when its
        last candidate does. A group that finished before any boundary
        observed its progress backfills first_token = finish (the tightest
        bound the boundary cadence can state)."""
        ts = time.time() if ts is None else ts
        with self._mu:
            rec = self._rec(uid)
            if rec is None:
                return
            done = self._finished.setdefault(uid, set())
            done.add(int(cand))
            if len(done) < rec.n or rec.finish_ts is not None:
                return
            rec.finish_ts = ts
            if rec.first_token_ts is None:
                rec.first_token_ts = ts
                if rec.enqueue_ts is not None:
                    rec.ttft_ms = (ts - rec.enqueue_ts) * 1e3
                    telemetry.hist_observe(
                        SERVING_TTFT_MS, rec.ttft_ms, trace_sample=True
                    )
                    if rec.priority is not None:
                        telemetry.hist_observe(
                            f"{SERVING_TTFT_MS}/{rec.priority}", rec.ttft_ms
                        )
            if rec.enqueue_ts is not None:
                rec.e2e_ms = (ts - rec.enqueue_ts) * 1e3
                telemetry.hist_observe(
                    SERVING_E2E_MS, rec.e2e_ms, trace_sample=True
                )

    def note_tokens(self, uid, tokens: int, ts: float | None = None) -> None:
        """Round end: the engine read the group's realized token counts —
        derive TPOT (decode interval over emitted tokens beyond the first)
        and CLOSE the record (streams to the JSONL)."""
        with self._mu:
            rec = self._rec(uid)
            if rec is None:
                return
            rec.gen_tokens = int(tokens)
            if rec.finish_ts is None:
                # defensive close (the engine asserts all-finished before
                # reading lengths, so this is unreachable in healthy runs)
                rec.finish_ts = time.time() if ts is None else ts
            if (
                rec.first_token_ts is not None
                and rec.finish_ts is not None and tokens > rec.n
            ):
                # per-token interval over the group's steady-state stretch:
                # the group's candidates emitted `tokens` in total, the
                # first token of each candidate rides TTFT — exclude n
                rec.tpot_ms = (
                    (rec.finish_ts - rec.first_token_ts) * 1e3
                    / max(int(tokens) - rec.n, 1)
                )
                telemetry.hist_observe(
                    SERVING_TPOT_MS, rec.tpot_ms, trace_sample=True
                )
            self._close_locked(rec)

    # ------------------------------------------------------ admission audit

    def on_boundary(self, *, live_slots: int, queue_depth: int,
                    free_pages: int, admitted: int,
                    reason: str | None = None, cls: str | None = None,
                    ts: float | None = None) -> None:
        """One admission pass at a host chunk boundary. ``admitted`` counts
        slot admissions + group prefills this pass; a pass that admitted
        nothing while work waited is a DECLINED pass, attributed to
        ``reason`` (one of :data:`STALL_REASONS`). ``cls`` is the priority
        class of the declined head group when the round carries gateway
        identity — the per-class breakdown rides NEXT to the flat reason
        counters, never instead of them (conservation stays class-blind)."""
        if reason is not None and reason not in STALL_REASONS:
            raise ValueError(
                f"unknown admission-stall reason {reason!r} "
                f"(expected one of {STALL_REASONS})"
            )
        telemetry.gauge_set(SERVING_LIVE_SLOTS, float(live_slots))
        telemetry.gauge_set(SERVING_QUEUE_DEPTH, float(queue_depth))
        telemetry.gauge_set(SERVING_FREE_PAGES, float(free_pages))
        telemetry.counter_add(SERVING_ADMISSION_PASSES)
        with self._mu:
            self.boundary_passes += 1
            self.occupancy.append((
                time.time() if ts is None else ts,
                int(live_slots), int(queue_depth), int(free_pages),
            ))
            declined = queue_depth > 0 and admitted == 0
            if declined:
                self.declined_passes += 1
            if declined and reason is not None:
                self.stalls[reason] += 1
                if cls is not None:
                    by = self.stalls_by_class.setdefault(cls, {})
                    by[reason] = by.get(reason, 0) + 1
        if declined:
            telemetry.counter_add(SERVING_DECLINED_PASSES)
            if reason is not None:
                telemetry.counter_add(f"{SERVING_ADMISSION_STALLS}/{reason}")
                if cls is not None:
                    telemetry.counter_add(
                        f"{SERVING_CLASS_STALLS}/{cls}/{reason}"
                    )

    # --------------------------------------------------------------- export

    def percentile(self, metric: str, q: float,
                   cls: str | None = None) -> float | None:
        """q-th percentile (0..100) of a closed-record latency metric
        ("ttft_ms" | "queue_wait_ms" | "tpot_ms" | "e2e_ms"), or None when
        no record produced it. ``cls`` narrows to one priority class
        (gateway rounds only; None when that class closed no record)."""
        with self._mu:
            # snapshot under the lock: a closing record appends to this
            # deque concurrently (the thread-safety contract above)
            if cls is not None:
                vals = sorted(self._class_samples.get((cls, metric), ()))
            else:
                vals = sorted(self._samples[metric])
        if not vals:
            return None
        idx = min(int(len(vals) * q / 100.0), len(vals) - 1)
        return vals[idx]

    def stall_frac(self) -> float | None:
        """Declined-admission passes over all admission passes (the
        attribution of PR 12's slot_idle_frac), or None before any pass."""
        with self._mu:
            if not self.boundary_passes:
                return None
            return self.declined_passes / self.boundary_passes

    def stats(self) -> dict[str, Any]:
        with self._mu:
            occ = list(self.occupancy)
            stalls = dict(self.stalls)
            by_class = {c: dict(r) for c, r in self.stalls_by_class.items()}
            declined = self.declined_passes
            passes = self.boundary_passes
            closed = self.closed_groups
        return {
            "closed_groups": closed,
            "stalls": stalls,
            "stalls_by_class": by_class,
            "declined_passes": declined,
            "admission_passes": passes,
            "stall_frac": declined / passes if passes else None,
            "occupancy_samples": len(occ),
        }

    def _summary_doc_locked(self) -> dict[str, Any]:
        occ = list(self.occupancy)
        doc: dict[str, Any] = {
            "kind": "summary",
            "closed_groups": self.closed_groups,
            "stalls": dict(self.stalls),
            "declined_passes": self.declined_passes,
            "admission_passes": self.boundary_passes,
        }
        if self.stalls_by_class:
            doc["stalls_by_class"] = {
                c: dict(r) for c, r in self.stalls_by_class.items()
            }
        if occ:
            lives = [o[1] for o in occ]
            queues = [o[2] for o in occ]
            frees = [o[3] for o in occ]
            doc["occupancy"] = {
                "samples": len(occ),
                "span_s": round(occ[-1][0] - occ[0][0], 3),
                "live_slots_mean": round(sum(lives) / len(lives), 3),
                "live_slots_max": max(lives),
                "queue_depth_mean": round(sum(queues) / len(queues), 3),
                "queue_depth_max": max(queues),
                "free_pages_min": min(frees),
            }
        return doc

    def close(self) -> None:
        """Stream any still-open records (partial lifecycles, e.g. a
        crashed round) plus the summary line, and close the file."""
        with self._mu:
            for rec in self._ring.values():
                self._write(rec.to_dict())
            self._ring.clear()
            self._finished.clear()
            self._write(self._summary_doc_locked())
            if self._file is not None:
                self._file.close()
                self._file = None


# -------------------------------------------------------------- fleet fold


def fold_fleet_serving(
    remote: Mapping[str, Mapping[str, Any]],
) -> dict[str, Any] | None:
    """Fold the per-worker registry snapshots (``telemetry.remote_metrics``
    — cumulative, restart-monotone per incarnation) into fleet-wide
    serving gauges. Returns the serving sub-view for the fleet dict, or
    None when no worker has served a request yet (the fleet endpoint then
    omits the section — empty-when-absent)."""
    hists: dict[str, list[float]] = {}  # name -> [count, sum, max]
    stalls_total = 0.0
    stalls_by_reason: dict[str, float] = {}
    seen = False
    for snap in remote.values():
        for name, h in (snap.get("hists") or {}).items():
            if not name.startswith("serving/"):
                continue
            seen = True
            a = hists.setdefault(name, [0.0, 0.0, 0.0])
            a[0] += float(h.get("count", 0.0))
            a[1] += float(h.get("sum", 0.0))
            a[2] = max(a[2], float(h.get("max", 0.0)))
        for name, v in (snap.get("counters") or {}).items():
            if name.startswith(SERVING_ADMISSION_STALLS + "/"):
                seen = True
                reason = name.rsplit("/", 1)[-1]
                stalls_by_reason[reason] = (
                    stalls_by_reason.get(reason, 0.0) + float(v)
                )
                stalls_total += float(v)
    if not seen:
        return None
    for series_mean, series_max, name in (
        (FLEET_SERVING_TTFT_MEAN_MS, FLEET_SERVING_TTFT_MAX_MS,
         SERVING_TTFT_MS),
        (FLEET_SERVING_QUEUE_WAIT_MEAN_MS, FLEET_SERVING_QUEUE_WAIT_MAX_MS,
         SERVING_QUEUE_WAIT_MS),
    ):
        a = hists.get(name)
        if a and a[0] > 0:
            telemetry.gauge_set(series_mean, a[1] / a[0])
            telemetry.gauge_set(series_max, a[2])
    telemetry.gauge_set(FLEET_SERVING_STALLS, stalls_total)
    return {
        "hists": {
            name: {"count": a[0], "sum": a[1], "max": a[2],
                   "mean": a[1] / a[0] if a[0] else None}
            for name, a in sorted(hists.items())
        },
        "admission_stalls": stalls_by_reason,
        "admission_stalls_total": stalls_total,
    }
