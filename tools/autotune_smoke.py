#!/usr/bin/env python
"""Autotune smoke check (wired into tools/run_all_checks.sh).

The acceptance contract for the autotuner subsystem, end to end on a CPU
host: ``tools/autotune.py measure`` over a 2-candidate space at tiny-model
scale must write a schema-valid plan DB into a tmpdir; ``resolve_plan``
must return the stored winner deterministically; an engine built against
that DB must adopt the plan while an explicit kwarg still overrides it;
and a corrupted DB must degrade to the static defaults instead of
crashing. Exits nonzero on any missing piece.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax.numpy as jnp

    import tools.autotune as autotune_cli
    from distrl_llm_tpu.autotune import SCHEMA_VERSION, resolve_plan
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.models import TINY

    tmp = tempfile.mkdtemp(prefix="distrl_autotune_")
    db = os.path.join(tmp, "plan_db.json")

    # 2-candidate space (host loop vs chunk 4) at tiny volume
    rc = autotune_cli.main([
        "measure", "--model", "tiny", "--prompts", "2", "--candidates", "2",
        "--max-prompt", "16", "--max-new", "8", "--scan-chunks", "0,4",
        "--repeats", "1", "--plan-db", db,
    ])
    assert rc == 0, f"autotune measure exited {rc}"
    assert os.path.exists(db), f"no plan DB written at {db}"
    with open(db) as f:
        doc = json.load(f)
    assert doc["schema_version"] == SCHEMA_VERSION, doc
    assert doc["entries"], "DB has no entries"

    kw = dict(
        model_cfg=TINY, max_prompt_tokens=16, max_new_tokens=8,
        rows=4, db_path=db,
    )
    first = resolve_plan(**kw)
    second = resolve_plan(**kw)
    assert first.source == "db", first
    assert first.plan == second.plan, "resolution is not deterministic"
    winner_chunk = first.plan.scan_chunk
    assert winner_chunk in (0, 4), first.plan

    ekw = dict(
        max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
        pad_token_id=0, cache_dtype=jnp.float32, plan_db=db,
    )
    engine = GenerationEngine(TINY, **ekw)
    assert engine.scan_chunk == winner_chunk, (
        f"engine did not adopt the stored plan: {engine.scan_chunk} != "
        f"{winner_chunk}"
    )
    pinned = GenerationEngine(TINY, scan_chunk=2, **ekw)
    assert pinned.scan_chunk == 2, "explicit kwarg must beat the stored plan"

    # corrupt-DB round trip: truncated file degrades to the static defaults
    with open(db, "w") as f:
        f.write(json.dumps(doc)[: len(json.dumps(doc)) // 2])
    broken = resolve_plan(**kw)
    assert broken.source == "default", broken
    assert GenerationEngine(TINY, **ekw).scan_chunk == 0

    print(f"AUTOTUNE SMOKE OK — winner scan_chunk={winner_chunk}, DB at {db}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
