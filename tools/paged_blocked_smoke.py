#!/usr/bin/env python
"""Blocked paged-kernel smoke check (wired into tools/run_all_checks.sh).

The CI-side acceptance gate for the grid-collapsed decode kernel (ISSUE 3),
runnable on a CPU host via the Pallas interpreter:

* interpret-mode parity of ``paged_attention_native_blocked`` vs the jnp
  reference at the r5-shaped geometry (GQA 14q/2kv, hd=64), including a
  non-divisor final block, for pages_per_block ∈ {1, 4, 8};
* pages_per_block=1 bit-identical to the one-page folded kernel;
* the analytic grid-step budget at the r5 benched geometry (480×2×13):
  the blocked kernel must count ≥ 8× fewer grid steps than the one-page
  kernel — a grid-count regression (e.g. someone re-splitting the page
  axis) fails CI here without needing silicon.

Exits nonzero on any miss.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops.paged import (
        make_page_table,
        paged_attention_reference,
        paged_grid_steps,
    )
    from distrl_llm_tpu.ops.paged_native import (
        paged_attention_native_blocked,
        paged_attention_native_folded,
    )

    failures = 0
    rng = np.random.default_rng(0)
    b, h, kh, hd, ps, pps = 4, 14, 2, 64, 8, 13  # r5 shape, pool scaled down
    cap = pps * ps
    kp = jnp.asarray(rng.standard_normal((kh, b * pps, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((kh, b * pps, ps, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    table = jnp.asarray(make_page_table(b, cap, ps))
    lengths = jnp.asarray([0, 5, 37, cap], jnp.int32)  # dead, short, mid, full
    want = np.asarray(paged_attention_reference(q, kp, vp, lengths, table))
    live = np.asarray(lengths) > 0

    for ppb in (1, 4, 8):
        got = np.asarray(paged_attention_native_blocked(
            q * hd**-0.5, kp, vp, lengths, table,
            pages_per_block=ppb, interpret=True,
        ))
        err = np.abs(got - want)[live].max()
        ok = err < 2e-5 and np.isfinite(got).all() and (got[~live] == 0).all()
        failures += not ok
        print(f"{'PASS' if ok else 'FAIL'} blocked_parity ppb={ppb} "
              f"pps={pps} max_err={err:.2e}")

    fold = np.asarray(paged_attention_native_folded(
        q * hd**-0.5, kp, vp, lengths, table, interpret=True))
    blk1 = np.asarray(paged_attention_native_blocked(
        q * hd**-0.5, kp, vp, lengths, table,
        pages_per_block=1, interpret=True))
    ok = (fold == blk1).all()
    failures += not ok
    print(f"{'PASS' if ok else 'FAIL'} blocked_ppb1_bit_identical_to_folded")

    r5 = dict(batch=480, num_kv_heads=2, pps=13)
    one_page = 480 * 2 * 13  # the (B, K, pps) grid of a one-page kernel
    blocked = paged_grid_steps("native_blocked", pages_per_block=8, **r5)
    ok = blocked * 8 <= one_page
    failures += not ok
    print(f"{'PASS' if ok else 'FAIL'} blocked_grid_budget "
          f"one_page={one_page} blocked={blocked} "
          f"(x{one_page / max(blocked, 1):.1f}, need >= 8)")

    print("ALL PASS" if failures == 0 else f"{failures} FAILURES")
    return failures


if __name__ == "__main__":
    sys.exit(main())
