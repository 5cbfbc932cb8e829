#!/usr/bin/env python
"""One-command diagnosis of a telemetry trace: per-phase / per-worker time
breakdown with tok/s and MFU.

Reads one run's trace file (written by ``--trace-dir`` — see telemetry.py):

    python tools/trace_report.py run_myrun/trace/trace.json

Prints, per track (driver + one per worker): each span name's call count,
total and mean wall time, and share of the track's traced span time; then
throughput derived from the engine spans' token counts (prefill tok/s,
decode tok/s) and MFU when the trace metadata carries the model's
FLOPs/token and a known peak (``--peak-flops`` overrides, FLOP/s).

Exit status: 0 on a parseable trace with at least one span, 1 otherwise —
tools/run_all_checks.sh uses this as the telemetry smoke gate.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


def load_trace(path: str) -> tuple[list[dict], dict]:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):  # bare event-array form is also legal
        return doc, {}
    return doc.get("traceEvents", []), doc.get("metadata", {}) or {}


def _union_us(intervals: list[tuple[int, int]]) -> int:
    """Total µs covered by a set of [start, end) intervals."""
    total = 0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _intersect_us(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Total µs where the unions of two interval sets overlap."""
    a, b = sorted(a), sorted(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


#: the two kinds of round, and the named parts of the host's side of one
#: (telemetry.py's vocabulary): what is left of the round's span is its self time
_ROUND_KINDS = ("engine/decode", "engine/refill_decode")
_HOST_PARTS = (("launches", "engine/dispatch"), ("waits", "engine/snapshot_wait"),
               ("snapshot_launch", "engine/snapshot_launch"),
               ("admissions", "engine/admit"), ("readback", "engine/readback"))


def _host_account(rounds: list[dict], track: list[dict]) -> str:
    """The host's account of one kind of round, summed over ``rounds`` (its
    spans on one track): seconds inside each named part and the rounds' self
    time, the span less the union of every span of its thread inside it."""
    part_us = dict.fromkeys((label for label, _ in _HOST_PARTS), 0)
    self_us = 0
    for r in rounds:
        lo, hi = r["ts"], r["ts"] + r.get("dur", 0)
        # (name, start, end cut to the round's) of every span that begins inside it
        inside = [(e["name"], e["ts"], min(e["ts"] + e.get("dur", 0), hi))
                  for e in track if e is not r and e.get("tid") == r.get("tid")
                  and lo <= e["ts"] < hi]
        for label, name in _HOST_PARTS:
            part_us[label] += _union_us([(a, b) for n, a, b in inside if n == name])
        self_us += (hi - lo) - _union_us([(a, b) for _, a, b in inside])
    parts = ", ".join(f"{label} {us / 1e6:.3f}" for label, us in part_us.items())
    line = f"    host s: {parts}, self {self_us / 1e6:.3f}"
    # what the slots' row states hold (the gauge engine/slot_state_bytes, the
    # largest round's) and what the decode steps moved of a power-retention
    # model's (the counter engine/power_state_bytes, summed), where a round says
    held = max((r.get("args", {}).get("slot_state_bytes", 0) for r in rounds), default=0)
    moved = sum(r.get("args", {}).get("power_state_bytes", 0) for r in rounds)
    if held:
        line += f"; slot state {held / 1e9:.3f} GB"
    if moved:
        line += f", moved {moved / 1e9:.1f} GB"
    # and the (live row, layer) states a state-space model's steps read and wrote
    stepped = sum(r.get("args", {}).get("ssm_states_stepped", 0) for r in rounds)
    if stepped:
        line += f", {stepped} states stepped"
    # and what a window model's rings attended of what full attention would
    attended, visible = (sum(r.get("args", {}).get(f"window_pages_{k}", 0) for r in rounds)
                         for k in ("attended", "visible"))
    if visible:
        line += f", window {attended} of {visible} x 128 keys"
    # and what a learned index let latent attention attend of what it saw
    attended, visible = (sum(r.get("args", {}).get(f"index_tokens_{k}", 0) for r in rounds)
                         for k in ("attended", "visible"))
    if visible:
        line += f", index {attended} of {visible} x 128 tokens"
    return line


def stalled_rounds_section(metadata: dict) -> list[str]:
    """One line a stalled round of the ledger the trace's metadata carries
    (``rounds``: ``telemetry.round_records()`` as the trainer exported them),
    with the fields of the engine's own warning: the longest stalled boundary's
    interval for the round's median, its host part, the process's CPU in it,
    involuntary switches, what the boundaries after it came back under the
    median, and the round's collections, builds, faults and pressure. Empty
    where no round stalled or the trace has no ledger."""
    lines = []
    for r in metadata.get("rounds") or ():
        stalled, boundaries = r.get("stalled") or (), r.get("boundaries") or ()
        if not stalled:
            continue
        worst = max(stalled, key=lambda i: boundaries[i][0])
        interval_s, host_s, cpu_s, switches = boundaries[worst][:4]
        lines.append(
            f"  round {r.get('round')}: boundary {worst} of {len(boundaries)} stalled: "
            f"{1e3 * interval_s:.1f} ms for a median of {1e3 * r['median_s']:.1f} "
            f"(host part {1e3 * host_s:.1f} ms, process CPU {1e3 * cpu_s:.1f} ms, "
            f"{switches} involuntary switches); {len(stalled)} stalled in the round; "
            f"recovered {1e3 * r.get('recovered_s', 0.0):.1f} ms; full collections "
            f"{1e3 * r.get('gc_full_s', 0.0):.1f} ms, programs built "
            f"{r.get('programs_built')}, major faults {r.get('majflt')}, "
            f"pressure gained (us) {r.get('pressure_us')}")
    return ["stalled rounds:", *lines, ""] if lines else []


def resilience_section(spans: dict[tuple[int, str], list[dict]]) -> list[str]:
    """Per-worker fault-handling summary from the driver's resilience spans
    (control_plane.py): reconnect attempts (``cp/reconnect``, with ok=),
    shard resubmissions (``cp/resubmit``, count=), and transient-error
    retries (``cp/retry``). One line per worker answers "which worker was
    flapping and how much work moved because of it". Empty when the trace
    has no resilience activity (healthy runs)."""
    per: dict[str, dict[str, int]] = defaultdict(
        lambda: {"reconnects": 0, "reconnect_ok": 0, "resubmits": 0,
                 "retries": 0}
    )
    for (_pid, name), evs in spans.items():
        if name not in ("cp/reconnect", "cp/resubmit", "cp/retry"):
            continue
        for e in evs:
            args = e.get("args", {})
            d = per[str(args.get("worker", "?"))]
            if name == "cp/reconnect":
                d["reconnects"] += 1
                d["reconnect_ok"] += 1 if args.get("ok") else 0
            elif name == "cp/resubmit":
                d["resubmits"] += int(args.get("count", 1))
            else:
                d["retries"] += 1
    if not per:
        return []
    lines = ["resilience:"]
    for worker in sorted(per):
        d = per[worker]
        lines.append(
            f"  {worker:<24} reconnects {d['reconnects']} "
            f"({d['reconnect_ok']} ok) / resubmits {d['resubmits']} / "
            f"retries {d['retries']}"
        )
    lines.append("")
    return lines


def weight_bus_section(spans: dict[tuple[int, str], list[dict]]) -> list[str]:
    """Versioned weight-bus summary (ISSUE 9) from the driver's
    ``cp/weight_push`` spans (one per worker per version, args: worker=,
    version=, bytes=, mode=delta|full; dur = push→ack): total bytes and
    bytes/version, the delta-vs-full ratio (how often the codec actually
    saved wire), and per-worker push counts with mean ack latency. Empty
    when the run never broadcast (dispatch-mode or local rollout)."""
    pushes = [
        e for (_pid, name), evs in spans.items()
        if name == "cp/weight_push" for e in evs
    ]
    if not pushes:
        return []
    versions = {int(e.get("args", {}).get("version", -1)) for e in pushes}
    total_bytes = sum(int(e.get("args", {}).get("bytes", 0)) for e in pushes)
    delta = sum(
        1 for e in pushes if e.get("args", {}).get("mode") == "delta"
    )
    full = len(pushes) - delta
    per: dict[str, list[dict]] = defaultdict(list)
    for e in pushes:
        per[str(e.get("args", {}).get("worker", "?"))].append(e)
    lines = ["weight bus:"]
    lines.append(
        f"  versions pushed:    {len(versions)} ({len(pushes)} worker-"
        f"pushes: delta ×{delta} / full ×{full}), "
        f"{total_bytes / 2**20:.2f} MiB total "
        f"({total_bytes / max(len(versions), 1) / 2**20:.2f} MiB/version)"
    )
    for worker in sorted(per):
        evs = per[worker]
        wbytes = sum(int(e.get("args", {}).get("bytes", 0)) for e in evs)
        ack_ms = sum(e.get("dur", 0) for e in evs) / len(evs) / 1e3
        lines.append(
            f"  {worker:<24} pushes {len(evs)} / "
            f"{wbytes / 2**20:.2f} MiB / mean ack {ack_ms:.1f} ms"
        )
    lines.append("")
    return lines


def rollout_section(events: list[dict],
                    spans: dict[tuple[int, str], list[dict]]) -> list[str]:
    """Async-rollout diagnosis from one trace: buffer occupancy over time
    (the ``rollout/buffer_occupancy`` counter track), a staleness-histogram
    summary (per-sample ``rollout/staleness`` counter events), and the
    producer-vs-learner overlap fraction — how much of the learner's update
    time a ``rollout/produce`` span was simultaneously active, the number
    that says whether decoupling actually bought concurrency. Empty when
    the trace has no rollout signals (sync/pipelined runs)."""
    occ: list[float] = []
    stale: list[float] = []
    for ev in events:
        if ev.get("ph") != "C":
            continue
        args = ev.get("args", {})
        if ev.get("name") == "rollout/buffer_occupancy":
            occ.append(float(args.get("buffer_occupancy", 0)))
        elif ev.get("name") == "rollout/staleness":
            # hist_observe(count=) carries the observation weight in the
            # event args; a weighted sample must count that many times or
            # the trace summary disagrees with metrics_snapshot
            stale.extend(
                [float(args.get("staleness", 0))]
                * int(args.get("count", 1))
            )
    produce = [e for (_, n), evs in spans.items() if n == "rollout/produce"
               for e in evs]
    updates = [e for (_, n), evs in spans.items() if n == "driver/update"
               for e in evs]
    if not occ and not stale and not produce:
        return []
    lines = ["rollout:"]
    if occ:
        lines.append(
            f"  buffer occupancy:   min {min(occ):.0f} / mean "
            f"{sum(occ) / len(occ):.1f} / max {max(occ):.0f} groups "
            f"({len(occ)} samples)"
        )
    if stale:
        s = sorted(stale)
        n = len(s)
        lines.append(
            f"  staleness (steps):  mean {sum(s) / n:.2f} / p50 "
            f"{s[n // 2]:.0f} / p90 {s[min(int(n * 0.9), n - 1)]:.0f} / "
            f"max {s[-1]:.0f} ({n} admitted groups)"
        )
    if produce and updates:
        p_iv = [(e["ts"], e["ts"] + e.get("dur", 0)) for e in produce]
        u_iv = [(e["ts"], e["ts"] + e.get("dur", 0)) for e in updates]
        upd_us = _union_us(u_iv)
        overlap = _intersect_us(p_iv, u_iv)
        lines.append(
            f"  producer overlap:   {100 * overlap / max(upd_us, 1):.1f}% "
            f"of learner update time had generation in flight "
            f"({len(produce)} rounds / {len(updates)} updates)"
        )
    elif produce:
        lines.append(
            f"  producer rounds:    {len(produce)} (no driver/update spans "
            "in window)"
        )
    lines.append("")
    return lines


def _dist_lines(label: str, vals: list[float], unit: str = "ms") -> str:
    s = sorted(vals)
    n = len(s)
    return (
        f"  {label:<19} mean {sum(s) / n:,.1f} / p50 {s[n // 2]:,.1f} / "
        f"p90 {s[min(int(n * 0.9), n - 1)]:,.1f} / max {s[-1]:,.1f} {unit} "
        f"({n} samples)"
    )


def policy_lag_section(events: list[dict]) -> list[str]:
    """Policy-lag distributions (ISSUE 10) from the lineage ledger's traced
    histogram samples (``lineage/*`` counter events, one per observation):
    sample→learn (group sampled → optimizer step consumed it), learn→act
    (version pushed → first round sampled under it), and the end-to-end
    loop (group sampled → the version its update produced reached every
    worker). Empty when the run never armed --lineage."""
    series: dict[str, list[float]] = {}
    for ev in events:
        name = ev.get("name", "")
        if ev.get("ph") != "C" or not name.startswith("lineage/"):
            continue
        args = ev.get("args", {})
        key = name.rsplit("/", 1)[-1]
        series.setdefault(name, []).extend(
            [float(args.get(key, 0))] * int(args.get("count", 1))
        )
    if not series:
        return []
    lines = ["policy lag:"]
    for name, label in (
        ("lineage/sample_to_learn_ms", "sample→learn:"),
        ("lineage/learn_to_act_ms", "learn→act:"),
        ("lineage/policy_lag_ms", "end-to-end:"),
    ):
        if series.get(name):
            lines.append(_dist_lines(label, series[name]))
    lines.append("")
    return lines


def serving_section(events: list[dict]) -> list[str]:
    """Request-level serving view (ISSUE 13) from the serving ledger's
    traced samples: latency distributions (``serving/ttft_ms`` /
    ``serving/queue_wait_ms`` / ``serving/tpot_ms`` / ``serving/e2e_ms``
    counter events, one per closed group) and the occupancy tracks
    (``serving/live_slots`` / ``serving/queue_depth`` /
    ``serving/free_pages`` gauges, one sample per admission pass). Empty
    when the run never armed --serving_obs."""
    hists: dict[str, list[float]] = {}
    gauges: dict[str, list[float]] = {}
    for ev in events:
        name = ev.get("name", "")
        if ev.get("ph") != "C" or not name.startswith("serving/"):
            continue
        args = ev.get("args", {})
        key = name.rsplit("/", 1)[-1]
        if name in ("serving/live_slots", "serving/queue_depth",
                    "serving/free_pages"):
            gauges.setdefault(name, []).append(float(args.get(key, 0)))
        else:
            hists.setdefault(name, []).extend(
                [float(args.get(key, 0))] * int(args.get("count", 1))
            )
    if not hists and not gauges:
        return []
    lines = ["serving:"]
    for name, label in (
        ("serving/ttft_ms", "ttft:"),
        ("serving/queue_wait_ms", "queue wait:"),
        ("serving/tpot_ms", "tpot:"),
        ("serving/e2e_ms", "e2e:"),
    ):
        if hists.get(name):
            lines.append(_dist_lines(label, hists[name]))
    live = gauges.get("serving/live_slots")
    if live:
        queue = gauges.get("serving/queue_depth") or [0.0]
        free = gauges.get("serving/free_pages") or [0.0]
        lines.append(
            f"  occupancy:          live slots mean "
            f"{sum(live) / len(live):,.1f} / max {max(live):,.0f}, queue "
            f"depth max {max(queue):,.0f}, free pages min {min(free):,.0f} "
            f"({len(live)} admission passes)"
        )
    lines.append("")
    return lines


def learning_section(events: list[dict]) -> list[str]:
    """Training-dynamics view (ISSUE 16) from the learn ledger's traced
    samples: per-step policy-health gauges published off the device-fused
    bundle (``learn/entropy``, ``learn/kl_behavior``, the clip/cap
    saturation fractions, ``learn/grad_norm/total``,
    ``learn/reward_drift`` counter tracks) and the device-binned IS-ratio
    histogram (``learn/is_ratio`` counter events, weight in count=). Empty
    when the run never armed --learn_obs."""
    gauges: dict[str, list[float]] = {}
    ratios: list[float] = []
    for ev in events:
        name = ev.get("name", "")
        if ev.get("ph") != "C" or not name.startswith("learn/"):
            continue
        args = ev.get("args", {})
        key = name.rsplit("/", 1)[-1]
        if name == "learn/is_ratio":
            ratios.extend(
                [float(args.get(key, 0))] * int(args.get("count", 1))
            )
        else:
            gauges.setdefault(name, []).append(float(args.get(key, 0)))
    if not gauges and not ratios:
        return []
    lines = ["learning:"]
    for name, label in (
        ("learn/entropy", "entropy:"),
        ("learn/kl_behavior", "kl (behavior):"),
        ("learn/clip_frac", "clip frac:"),
        ("learn/ratio_cap_frac", "cap frac:"),
        ("learn/adv_mean", "adv mean:"),
        ("learn/adv_std", "adv std:"),
        ("learn/grad_norm/total", "grad norm:"),
        ("learn/reward_drift", "reward drift:"),
    ):
        vals = gauges.get(name)
        if vals:
            lines.append(
                f"  {label:<19} mean {sum(vals) / len(vals):,.4f} / min "
                f"{min(vals):,.4f} / max {max(vals):,.4f} "
                f"({len(vals)} steps)"
            )
    if ratios:
        lines.append(_dist_lines("is ratio:", ratios, unit=""))
    lines.append("")
    return lines


def control_section(events: list[dict]) -> list[str]:
    """Self-healing-runtime view (ISSUE 14): every governor actuation is
    stamped as a ``control/action`` Perfetto instant with its controller,
    actuator, kind, and old→new values. This section counts actions per
    controller/kind and lists the first few in order — the audit trail of
    what the runtime DID to itself. Empty when no controller ever acted
    (or the run was untraced)."""
    actions = [
        ev.get("args", {}) for ev in events
        if ev.get("ph") == "i" and ev.get("name") == "control/action"
    ]
    if not actions:
        return []
    lines = ["control:"]
    per: dict[tuple[str, str], int] = {}
    for a in actions:
        key = (str(a.get("controller", "?")), str(a.get("kind", "?")))
        per[key] = per.get(key, 0) + 1
    lines.append(
        f"  actions:            {len(actions)} total — " + ", ".join(
            f"{ctrl}/{kind} ×{n}"
            for (ctrl, kind), n in sorted(per.items())
        )
    )
    escalated = sum(1 for a in actions if a.get("trigger"))
    if escalated:
        lines.append(
            f"  trigger-escalated:  {escalated} "
            f"({', '.join(sorted({str(a['trigger']) for a in actions if a.get('trigger')}))})"
        )
    for a in actions[:8]:
        lines.append(
            f"    step {a.get('step', '?'):>4}  "
            f"[{a.get('kind', '?')}] {a.get('controller', '?')}."
            f"{a.get('actuator', '?')} {a.get('old')} -> {a.get('new')}"
            f" ({a.get('reason', '')})"
        )
    if len(actions) > 8:
        lines.append(f"    … and {len(actions) - 8} more")
    lines.append("")
    return lines


def fleet_section(events: list[dict]) -> list[str]:
    """Elastic-fleet view (ISSUE 20): the autoscaler's setpoint trajectory
    (``fleet/target_workers`` gauge), scale events (``fleet/scale_events``
    counter), graceful retirements (``cp/retires``), and every
    ``control/action`` instant stamped by the ``autoscale`` governor with
    its old→new pool target. Empty when the run never scaled and never
    armed --control_autoscale — a static fleet leaves no trace here."""
    targets: list[float] = []
    scale_events = retires = 0.0
    for ev in events:
        if ev.get("ph") != "C":
            continue
        name = ev.get("name", "")
        args = ev.get("args", {})
        key = name.rsplit("/", 1)[-1]
        if name == "fleet/target_workers":
            targets.append(float(args.get(key, 0)))
        elif name == "fleet/scale_events":
            scale_events += float(args.get(key, 0))
        elif name == "cp/retires":
            retires += float(args.get(key, 0))
    actions = [
        ev.get("args", {}) for ev in events
        if ev.get("ph") == "i" and ev.get("name") == "control/action"
        and ev.get("args", {}).get("controller") == "autoscale"
    ]
    if not actions and not scale_events and not retires:
        return []
    lines = ["fleet:"]
    if targets:
        lines.append(
            f"  target pool:        {targets[0]:.0f} -> {targets[-1]:.0f} "
            f"(min {min(targets):.0f} / max {max(targets):.0f} across "
            f"{len(targets)} samples)"
        )
    ups = sum(1 for a in actions if a.get("kind") == "scale_up")
    downs = sum(1 for a in actions if a.get("kind") == "scale_down")
    lines.append(
        f"  scale events:       {scale_events:.0f} applied — "
        f"{ups} up / {downs} down actuations, {retires:.0f} retire(s)"
    )
    for a in actions[:8]:
        lines.append(
            f"    step {a.get('step', '?'):>4}  [{a.get('kind', '?')}] "
            f"pool {a.get('old')} -> {a.get('new')} ({a.get('reason', '')})"
        )
    if len(actions) > 8:
        lines.append(f"    … and {len(actions) - 8} more")
    lines.append("")
    return lines


def lineage_section(events: list[dict],
                    spans: dict[tuple[int, str], list[dict]],
                    tracks: dict[int, str]) -> list[str]:
    """Causal-link audit (ISSUE 10): with trace-context propagation on,
    every worker-side span recorded while handling a driver frame carries
    the originating ``dispatch_id``; this section counts linked vs orphaned
    worker spans (an orphan names a dispatch the driver never recorded —
    a propagation bug) and lists restarted-worker incarnations (distinct
    ``(worker, pid)`` tracks). Empty when no worker span carries trace
    context (local rollout, or workers/driver untraced)."""
    worker_pids = {
        pid for pid, name in tracks.items() if name.startswith("worker")
    }
    driver_ids: set[int] = set()
    for (pid, name), evs in spans.items():
        if pid in worker_pids or name not in (
            "cp/dispatch", "cp/weight_push"
        ):
            continue
        for e in evs:
            did = e.get("args", {}).get("dispatch_id")
            if did is not None:
                driver_ids.add(int(did))
    linked = orphaned = unlinked = 0
    for (pid, _name), evs in spans.items():
        if pid not in worker_pids:
            continue
        for e in evs:
            did = e.get("args", {}).get("dispatch_id")
            if did is None:
                unlinked += 1
            elif int(did) in driver_ids:
                linked += 1
            else:
                orphaned += 1
    if not linked and not orphaned:
        return []
    lines = ["lineage:"]
    lines.append(
        f"  trace links:        {linked} worker spans resolve to "
        f"{len(driver_ids)} driver dispatches / {orphaned} orphaned / "
        f"{unlinked} without context (pre-dispatch startup)"
    )
    # restarted incarnations: two tracks for one worker address ("worker
    # host:port" + "worker host:port (pid N)") mean a kill/restart was
    # correctly split instead of aliased onto one timeline
    by_addr: dict[str, int] = {}
    for name in tracks.values():
        if name.startswith("worker"):
            addr = name.split(" (pid", 1)[0]
            by_addr[addr] = by_addr.get(addr, 0) + 1
    for addr, count in sorted(by_addr.items()):
        if count > 1:
            lines.append(
                f"  incarnations:       {addr} ×{count} tracks "
                "(restart detected)"
            )
    lines.append("")
    return lines


def spec_section(spans: dict[tuple[int, str], list[dict]]) -> list[str]:
    """Speculative-decoding diagnosis from one trace: every spec-mode
    refill round stamps its decode span with ``spec_drafter`` /
    ``spec_accept_rate`` / ``tokens_per_verify_step``, so the report can
    show the realized speculation — the mean accepted
    draft prefix per verify step, tokens emitted per step (the speculation
    multiplier on step rate), and the drafter mix across rounds (a run
    that swaps --spec_drafter mid-experiment shows both). Empty when no
    spec round traced."""
    rounds = [
        e for (_, n), evs in spans.items()
        if n == "engine/refill_decode" for e in evs
        if e.get("args", {}).get("spec_drafter")
    ]
    if not rounds:
        return []
    rates = [float(e["args"].get("spec_accept_rate", 0)) for e in rounds]
    tps = [float(e["args"].get("tokens_per_verify_step", 0)) for e in rounds]
    mix: dict[str, int] = {}
    for e in rounds:
        drafter = str(e["args"]["spec_drafter"])
        mix[drafter] = mix.get(drafter, 0) + 1
    lines = ["speculative:"]
    lines.append(
        f"  accept rate:        mean {sum(rates) / len(rates):.3f} / min "
        f"{min(rates):.3f} / max {max(rates):.3f} ({len(rounds)} rounds)"
    )
    # tokens_per_verify_step is the EMITTED count — EOS/budget truncation
    # can cut an accepted draft run short, so label it as emitted drafts,
    # not "accepted" (the accept-rate line above is the sampler-true
    # acceptance off accept_total)
    lines.append(
        f"  tokens/verify step: mean {sum(tps) / len(tps):.2f} "
        f"(emitted drafts {sum(tps) / len(tps) - 1:.2f} + 1 "
        "resample/bonus; post EOS/budget truncation)"
    )
    lines.append(
        "  drafter mix:        "
        + ", ".join(f"{k} ×{v}" for k, v in sorted(mix.items()))
    )
    lines.append("")
    return lines


def build_report(events: list[dict], metadata: dict,
                 peak_flops: float | None = None) -> str:
    tracks: dict[int, str] = {}
    spans: dict[tuple[int, str], list[dict]] = defaultdict(list)
    for ev in events:
        ph = ev.get("ph")
        pid = ev.get("pid", 0)
        if ph == "M" and ev.get("name") == "process_name":
            tracks[pid] = ev.get("args", {}).get("name", f"pid {pid}")
        elif ph == "X":
            spans[(pid, ev["name"])].append(ev)
    if not spans:
        raise ValueError("trace contains no span events")

    lines: list[str] = []
    by_pid: dict[int, list[tuple[str, list[dict]]]] = defaultdict(list)
    for (pid, name), evs in spans.items():
        by_pid[pid].append((name, evs))
    for pid in sorted(by_pid):
        label = tracks.get(pid, f"pid {pid}")
        rows = []
        for name, evs in by_pid[pid]:
            total_us = sum(e.get("dur", 0) for e in evs)
            rows.append((name, len(evs), total_us))
        # per-track share uses only top-level-ish totals; nested spans
        # double-count by design (each row is that span's own wall time)
        track_us = max(sum(t for _, _, t in rows), 1)
        lines.append(f"track: {label}")
        lines.append(f"  {'span':<28} {'count':>6} {'total s':>10} "
                     f"{'mean ms':>10} {'share':>7}")
        for name, count, total_us in sorted(rows, key=lambda r: -r[2]):
            lines.append(
                f"  {name:<28} {count:>6} {total_us / 1e6:>10.3f} "
                f"{total_us / count / 1e3:>10.2f} "
                f"{100 * total_us / track_us:>6.1f}%"
            )
            if name in _ROUND_KINDS:
                lines.append(_host_account(
                    spans[(pid, name)],
                    [e for _, evs in by_pid[pid] for e in evs]))
        lines.append("")

    # throughput from engine span args (every engine records tokens= on its
    # prefill/decode spans; worker tracks contribute their own)
    def tok_s(span_names: tuple[str, ...]) -> float | None:
        toks = us = 0
        for (pid, name), evs in spans.items():
            if name in span_names:
                for e in evs:
                    toks += e.get("args", {}).get("tokens", 0)
                    us += e.get("dur", 0)
        if toks and us:
            return toks * 1e6 / us
        return None

    lines.extend(stalled_rounds_section(metadata))
    lines.extend(resilience_section(spans))
    lines.extend(weight_bus_section(spans))
    lines.extend(rollout_section(events, spans))
    lines.extend(policy_lag_section(events))
    lines.extend(serving_section(events))
    lines.extend(learning_section(events))
    lines.extend(control_section(events))
    lines.extend(fleet_section(events))
    lines.extend(lineage_section(events, spans, tracks))
    lines.extend(spec_section(spans))

    prefill = tok_s(("engine/prefill",))
    # NOT worker/generate or engine/remote_round: those wrap the engine
    # spans (a traced serving worker ships its engine/decode spans in the
    # same blob), so counting them would double the tokens and mix
    # prefill-inclusive durations into the decode rate
    decode = tok_s(("engine/decode", "engine/refill_decode"))
    lines.append("throughput:")
    lines.append(f"  prefill tok/s: "
                 f"{f'{prefill:,.0f}' if prefill else 'n/a (no token counts)'}")
    lines.append(f"  decode  tok/s: "
                 f"{f'{decode:,.0f}' if decode else 'n/a (no token counts)'}")
    fpt = metadata.get("decode_flops_per_token")
    peak = peak_flops or metadata.get("peak_flops")
    chips = metadata.get("chips", 1) or 1
    if decode and fpt and peak:
        lines.append(
            f"  decode MFU:    {100 * decode / chips * fpt / peak:.2f}%  "
            f"(FLOPs/token {fpt / 1e9:.2f} GF, peak {peak / 1e12:.0f} TF/s"
            f"{f', {chips} chips' if chips > 1 else ''})"
        )
    else:
        lines.append(
            "  decode MFU:    n/a (needs token counts, metadata "
            "decode_flops_per_token, and a known peak — pass --peak-flops)"
        )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="per-phase/per-worker breakdown of a telemetry trace"
    )
    p.add_argument("trace", help="path to a trace.json written by --trace-dir")
    p.add_argument("--peak-flops", type=float, default=None,
                   help="peak FLOP/s of one chip for the MFU line "
                        "(overrides the trace metadata)")
    args = p.parse_args(argv)
    try:
        events, metadata = load_trace(args.trace)
        report = build_report(events, metadata, peak_flops=args.peak_flops)
    except Exception as e:  # noqa: BLE001 — a truncated or still-being-
        # written trace (partial JSON, malformed events, wrong types) must
        # exit 1 with ONE line, never a raw traceback: this script gates
        # run_all_checks and gets pointed at live trace files
        print(
            f"trace_report: cannot report on {args.trace}: "
            f"{type(e).__name__}: {e}",
            file=sys.stderr,
        )
        return 1
    print(report)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
