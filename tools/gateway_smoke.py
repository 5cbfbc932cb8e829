#!/usr/bin/env python
"""Serving-gateway acceptance gate (ISSUE 19), runnable on a CPU host
and wired into tools/run_all_checks.sh.

What it proves, on a REAL multi-tenant replay (three priority classes,
two tenants, every request fired at t=0 over the streaming HTTP
front-end, queue far longer than the slot count):

1. the gateway does not perturb the engine: greedy outputs are
   BYTE-IDENTICAL before the service ever attaches and after it closed
   (the per-round attach/detach leaves no residue);
2. streaming is byte-complete for every successful request — the
   chunked token deltas, concatenated, ARE the final token list;
3. the class policy holds under a pinned shed floor of 2: scavenger
   groups were shed >= 1 time while interactive was NEVER shed;
4. the admission audit conserves with classes on: per-reason stall
   counts sum to the declined passes, the per-class breakdown never
   exceeds its flat reason counter, and the registry's
   serving/class_stalls/* counters mirror the ledger exactly;
5. tenant quotas reject at the door: a request whose worst-case
   footprint exceeds its tenant's budget gets HTTP 400 (and only that
   request fails), with gateway/rejected counting it.

Exit 0 = the gateway held; nonzero otherwise.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


os.environ["DISTRL_POOL_CHECK"] = "1"


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.control import ControlLimits
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.gateway import traffic
    from distrl_llm_tpu.gateway.scheduler import GATEWAY_REJECTED
    from distrl_llm_tpu.gateway.server import GatewayServer
    from distrl_llm_tpu.gateway.service import GatewayService
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.serving_obs import (
        SERVING_CLASS_STALLS,
        STALL_REASONS,
        ServingLedger,
    )
    from distrl_llm_tpu.tokenizer import CharTokenizer

    t_start = time.time()
    failures = 0

    def check(name: str, ok: bool, detail: str = "") -> None:
        nonlocal failures
        print(
            f"{'PASS' if ok else 'FAIL'} {name}"
            + (f"  [{detail}]" if detail else "")
        )
        if not ok:
            failures += 1

    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    eng = PagedGenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
        pad_token_id=0, page_size=8, max_concurrent_rows=2,
        scheduler="refill", decode_chunk=2, autotune=False,
        continuous_admission=True,
    )

    # --- 1 (first half): a golden greedy round BEFORE any gateway ---------
    rng = np.random.default_rng(0)
    ids = rng.integers(2, TINY.vocab_size, size=(3, 16)).astype(np.int32)
    mask = np.ones((3, 16), np.int32)
    sampling = SamplingConfig(max_tokens=8, temperature=0.0, top_p=1.0, n=2)
    key = jax.random.PRNGKey(1)
    golden = eng.generate(params, None, ids, mask, sampling, key)

    # --- the replay: all three classes, two tenants, everything at t=0 ----
    # (max queue pressure) + one quota-impossible request; shed floor
    # pinned at 2 so only scavenger is below the admission line
    arrivals = []
    for i in range(6):
        arrivals.append({"t": 0.0, "tenant": "acme", "cls": "interactive",
                         "prompt_len": 6 + i % 3, "max_new_tokens": 8})
        arrivals.append({"t": 0.0, "tenant": "globex", "cls": "batch",
                         "prompt_len": 5 + i % 3, "max_new_tokens": 8})
        arrivals.append({"t": 0.0, "tenant": "acme", "cls": "scavenger",
                         "prompt_len": 4 + i % 3, "max_new_tokens": 8})
    # footprint 12 + 8 = 20 > 10: must 400 at the door, never queue
    arrivals.append({"t": 0.0, "tenant": "smalltenant", "cls": "batch",
                     "prompt_len": 12, "max_new_tokens": 8})

    ledger = ServingLedger(ring_size=4096)
    limits = ControlLimits()
    limits.set_shed(True, floor=2)
    service = GatewayService(
        eng, params, CharTokenizer(TINY.vocab_size),
        quota={"smalltenant": 10},
        serving_ledger=ledger, control_limits=limits,
        max_groups_per_round=4, seed=3,
    ).start()
    server = GatewayServer(service, port=0)
    try:
        summary = traffic.replay(server.url, arrivals)
    finally:
        server.close()
        service.close()

    by_class = summary["by_class"]

    # --- 2: streaming byte-complete ---------------------------------------
    check("every class completed its successful requests",
          all(
              c["n"] - c["errors"] > 0 and c["gen_tokens"] > 0
              for c in by_class.values()
          ),
          str({k: (c["n"], c["errors"]) for k, c in by_class.items()}))
    check("streamed chunks byte-complete on every successful request",
          sum(c["stream_incomplete"] for c in by_class.values()) == 0)

    # --- 3: the class policy under the pinned floor -----------------------
    shed = service.class_actions["shed"]
    check("scavenger shed >= 1 under floor=2",
          shed.get("scavenger", 0) >= 1, str(shed))
    check("interactive NEVER shed", shed.get("interactive", 0) == 0,
          str(shed))

    # --- 4: per-class admission audit conserves ---------------------------
    stats = ledger.stats()
    stall_sum = sum(stats["stalls"].values())
    check("stall-reason counts sum to declined passes",
          stall_sum == stats["declined_passes"]
          and set(stats["stalls"]) == set(STALL_REASONS),
          f"{stats['stalls']} vs declined={stats['declined_passes']}")
    by_cls = stats["stalls_by_class"]
    per_reason_cls = {}
    for cls, reasons in by_cls.items():
        for reason, count in reasons.items():
            per_reason_cls[reason] = per_reason_cls.get(reason, 0) + count
    check("per-class breakdown never exceeds its flat reason counter",
          all(
              per_reason_cls[r] <= stats["stalls"][r]
              for r in per_reason_cls
          ),
          f"{per_reason_cls} vs {stats['stalls']}")
    check("the shed stalls carry class attribution",
          by_cls.get("scavenger", {}).get("shed", 0) >= 1, str(by_cls))
    snap = telemetry.observe_snapshot()["counters"]
    reg_cls = {
        k[len(SERVING_CLASS_STALLS) + 1:]: v
        for k, v in snap.items()
        if k.startswith(SERVING_CLASS_STALLS + "/")
    }
    ledger_cls = {
        f"{cls}/{reason}": float(count)
        for cls, reasons in by_cls.items()
        for reason, count in reasons.items()
    }
    check("registry class_stalls counters mirror the ledger",
          reg_cls == ledger_cls, f"registry={reg_cls} ledger={ledger_cls}")
    reg_flat = {
        r: snap.get(f"serving/admission_stalls/{r}", 0.0)
        for r in STALL_REASONS
    }
    check("registry flat stall counters mirror the ledger",
          all(
              reg_flat[r] == float(stats["stalls"][r])
              for r in STALL_REASONS
          ),
          f"registry={reg_flat} ledger={stats['stalls']}")

    # --- 5: quota rejects at the door -------------------------------------
    check("exactly the quota-impossible request failed",
          sum(c["errors"] for c in by_class.values()) == 1
          and by_class.get("batch", {}).get("errors", 0) == 1,
          str({k: c["errors"] for k, c in by_class.items()}))
    check("gateway/rejected counted it",
          snap.get(GATEWAY_REJECTED, 0) >= 1,
          f"rejected={snap.get(GATEWAY_REJECTED, 0)}")

    # --- 1 (second half): gateway-off byte-identity -----------------------
    check("gateway hooks fully detached after close",
          eng.round_meta is None and eng.quota_book is None
          and eng.stream_hook is None)
    eng.serving_ledger = None
    eng.control_limits = None
    after = eng.generate(params, None, ids, mask, sampling, key)
    check("post-gateway greedy outputs byte-identical to pre-gateway",
          np.array_equal(after.tokens, golden.tokens)
          and np.array_equal(after.lengths, golden.lengths))

    print(
        f"gateway_smoke: {failures} failure(s), "
        f"{summary['requests']} requests, shed={shed}, "
        f"stalls={stats['stalls']}, {time.time() - t_start:.0f}s total"
    )
    return 1 if failures else 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 — the gate must report, not hang
        import traceback

        traceback.print_exc()
        rc = 1
    sys.exit(rc)
