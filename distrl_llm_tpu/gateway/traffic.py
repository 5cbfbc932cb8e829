"""Open-loop traffic synthesis + replay for the serving gateway
(ISSUE 19).

Production-shaped load is heterogeneous in BOTH dimensions Laminar
measures (PAPERS.md): arrival times (Poisson steady state punctuated by
bursts) and lengths (long-tail — a few huge prompts/outputs dominate
the page pool). This module synthesizes such traces deterministically
from a seed, persists them as JSONL so a measured round and a regression
bisect replay the SAME arrivals, and drives them at the gateway
OPEN-LOOP: each request fires at its scheduled offset whether or not
earlier requests completed — under overload the queue grows, which is
the point (a closed-loop client self-throttles and can never show the
p99 cliff).

Client-side latency is recorded per class alongside the server-side
ledger: TTFT here is "POST sent → first streamed chunk", including HTTP
and queue time the server-side number can't see."""

from __future__ import annotations

import http.client
import json
import random
import threading
import time
from typing import Any
from urllib.parse import urlsplit

# long-tail defaults (lognormal, tokens): most prompts small, a heavy
# tail capped by the caller's engine window
DEFAULT_PROMPT_MU = 2.5      # median ~12 tokens
DEFAULT_PROMPT_SIGMA = 0.8
DEFAULT_OUTPUT_MU = 2.0      # median ~7 tokens
DEFAULT_OUTPUT_SIGMA = 0.7


def synthesize(
    *,
    seed: int,
    n_requests: int,
    rate_rps: float,
    process: str = "poisson",
    burst_every_s: float = 2.0,
    burst_size: int = 8,
    class_mix: dict[str, float] | None = None,
    tenants: tuple[str, ...] = ("acme", "globex"),
    prompt_mu: float = DEFAULT_PROMPT_MU,
    prompt_sigma: float = DEFAULT_PROMPT_SIGMA,
    max_prompt_tokens: int = 64,
    output_mu: float = DEFAULT_OUTPUT_MU,
    output_sigma: float = DEFAULT_OUTPUT_SIGMA,
    max_new_tokens: int = 32,
) -> list[dict[str, Any]]:
    """Deterministic arrival trace: ``n_requests`` dicts with offset ``t``
    (seconds from replay start, nondecreasing), tenant, class, prompt
    length and output budget. ``process``: "poisson" (exponential
    inter-arrivals at ``rate_rps``) or "burst" (the same Poisson base with
    ``burst_size`` extra back-to-back arrivals every ``burst_every_s`` —
    the overload shape the r19 artifact drives)."""
    if process not in ("poisson", "burst"):
        raise ValueError(
            f"unknown arrival process {process!r} (poisson|burst)"
        )
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    mix = dict(class_mix or {
        "interactive": 0.4, "batch": 0.4, "scavenger": 0.2,
    })
    classes = sorted(mix)
    weights = [float(mix[c]) for c in classes]
    rng = random.Random(int(seed))
    out: list[dict[str, Any]] = []
    t = 0.0
    next_burst = burst_every_s
    while len(out) < n_requests:
        t += rng.expovariate(rate_rps)
        burst = 1
        if process == "burst" and t >= next_burst:
            burst += int(burst_size)
            next_burst += burst_every_s
        for _ in range(burst):
            if len(out) >= n_requests:
                break
            cls = rng.choices(classes, weights=weights)[0]
            p_len = max(1, min(
                int(rng.lognormvariate(prompt_mu, prompt_sigma)),
                int(max_prompt_tokens),
            ))
            o_len = max(1, min(
                int(rng.lognormvariate(output_mu, output_sigma)),
                int(max_new_tokens),
            ))
            out.append({
                "t": round(t, 6),
                "tenant": rng.choice(list(tenants)),
                "cls": cls,
                "prompt_len": p_len,
                "max_new_tokens": o_len,
            })
    return out


def save_trace(path: str, arrivals: list[dict[str, Any]]) -> None:
    with open(path, "w") as f:
        for a in arrivals:
            f.write(json.dumps(a) + "\n")


def load_trace(path: str) -> list[dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _percentile(vals: list[float], q: float) -> float | None:
    if not vals:
        return None
    s = sorted(vals)
    idx = min(int(len(s) * q / 100.0), len(s) - 1)
    return s[idx]


class _ClientRecord:
    __slots__ = ("cls", "ttft_ms", "e2e_ms", "gen_tokens", "error",
                 "streamed_ok")

    def __init__(self, cls: str):
        self.cls = cls
        self.ttft_ms: float | None = None
        self.e2e_ms: float | None = None
        self.gen_tokens = 0
        self.error: str | None = None
        self.streamed_ok: bool | None = None


def _one_request(url_parts, arrival: dict[str, Any],
                 rec: _ClientRecord, prompt_char: str,
                 timeout_s: float) -> None:
    t0 = time.time()
    try:
        conn = http.client.HTTPConnection(
            url_parts.hostname, url_parts.port, timeout=timeout_s
        )
        body = json.dumps({
            "prompt": prompt_char * int(arrival["prompt_len"]),
            "max_new_tokens": int(arrival["max_new_tokens"]),
        })
        conn.request(
            "POST", "/v1/generate", body=body,
            headers={
                "Content-Type": "application/json",
                "X-Tenant": str(arrival.get("tenant", "anon")),
                "X-Priority": str(arrival.get("cls", "batch")),
            },
        )
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return
        streamed: list[int] = []
        final: dict | None = None
        # http.client transparently de-chunks; one JSON doc per line
        for raw in resp:
            line = raw.strip()
            if not line:
                continue
            doc = json.loads(line)
            if "error" in doc:
                rec.error = str(doc["error"])
                return
            if doc.get("done"):
                final = doc
                break
            if doc.get("tokens"):
                if rec.ttft_ms is None:
                    rec.ttft_ms = (time.time() - t0) * 1e3
                streamed.extend(int(t) for t in doc["tokens"])
        rec.e2e_ms = (time.time() - t0) * 1e3
        if final is None:
            rec.error = "stream ended without done line"
            return
        if rec.ttft_ms is None:
            # everything arrived in the final flush: TTFT = e2e
            rec.ttft_ms = rec.e2e_ms
        rec.gen_tokens = int(final.get("gen_tokens", 0))
        # byte-complete contract: the streamed chunks, concatenated,
        # ARE the final token list (the smoke asserts all(streamed_ok))
        rec.streamed_ok = streamed == [
            int(t) for t in final.get("tokens", ())
        ]
        conn.close()
    except Exception as e:  # noqa: BLE001 — a failed request is a row,
        # not a harness crash
        rec.error = f"{type(e).__name__}: {e}"


def replay(url: str, arrivals: list[dict[str, Any]], *,
           prompt_char: str = "a", timeout_s: float = 120.0,
           speedup: float = 1.0) -> dict[str, Any]:
    """Drive an arrival trace at the gateway open-loop: each request
    fires on its own thread at ``t / speedup`` seconds after start,
    never waiting for earlier completions. Returns the per-class
    client-side summary (TTFT/e2e p50/p99, errors, stream integrity)."""
    parts = urlsplit(url)
    records = [_ClientRecord(str(a.get("cls", "batch"))) for a in arrivals]
    threads: list[threading.Thread] = []
    t_start = time.time()
    for arrival, rec in zip(arrivals, records):
        delay = float(arrival.get("t", 0.0)) / max(speedup, 1e-9)
        wait = t_start + delay - time.time()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(
            target=_one_request,
            args=(parts, arrival, rec, prompt_char, timeout_s),
            daemon=True,
        )
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=timeout_s)
    wall_s = time.time() - t_start
    by_class: dict[str, dict[str, Any]] = {}
    for rec in records:
        cls = by_class.setdefault(rec.cls, {
            "n": 0, "errors": 0, "ttft_ms": [], "e2e_ms": [],
            "gen_tokens": 0, "stream_incomplete": 0,
        })
        cls["n"] += 1
        if rec.error is not None:
            cls["errors"] += 1
            continue
        cls["gen_tokens"] += rec.gen_tokens
        if rec.ttft_ms is not None:
            cls["ttft_ms"].append(rec.ttft_ms)
        if rec.e2e_ms is not None:
            cls["e2e_ms"].append(rec.e2e_ms)
        if rec.streamed_ok is False:
            cls["stream_incomplete"] += 1
    summary: dict[str, Any] = {
        "requests": len(records),
        "wall_s": round(wall_s, 3),
        "arrival_rate_rps": (
            round(len(records) / wall_s, 3) if wall_s > 0 else None
        ),
        "by_class": {},
    }
    for cls, agg in sorted(by_class.items()):
        summary["by_class"][cls] = {
            "n": agg["n"],
            "errors": agg["errors"],
            "stream_incomplete": agg["stream_incomplete"],
            "gen_tokens": agg["gen_tokens"],
            "ttft_p50_ms": _percentile(agg["ttft_ms"], 50),
            "ttft_p99_ms": _percentile(agg["ttft_ms"], 99),
            "e2e_p50_ms": _percentile(agg["e2e_ms"], 50),
            "e2e_p99_ms": _percentile(agg["e2e_ms"], 99),
        }
    return summary
