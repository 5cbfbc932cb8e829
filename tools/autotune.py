#!/usr/bin/env python
"""Offline plan-DB populator + report: `python tools/autotune.py <cmd>`.

Three subcommands:

* ``measure`` — run the in-process micro-bench harness
  (distrl_llm_tpu/autotune/microbench.py) over a candidate plan space at one
  geometry on THIS host's device, and write the winner to the plan DB.
  Warmup/steady-state separated; OOM/compile-failing candidates score
  infeasible instead of killing the sweep.

* ``ingest`` — derive plans from the JSON rows the retired benchmark
  script printed (the last of them are under benchmarks/r5/; nothing
  produces such rows now, and the subcommand goes with the plan database):
  group rows by (device, model, geometry), pick the fastest error-free row,
  and store the plan it actually ran — ``scan_chunk_active: false`` rows
  store chunk 0. Geometry is not recorded in those rows, so
  ``--max-prompt/--max-new`` name it (defaults: the reference 350/1200).

* ``report`` — print every stored plan with its best measurement.

The DB location follows the standard override chain: ``--plan-db`` >
``$DISTRL_PLAN_DB`` > ``~/.cache/distrl_llm_tpu/plan_db.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def _peak_to_kind() -> list[tuple[float, str]]:
    """telemetry's peak-TFLOPs table keyed the other way (peak → canonical
    kind), derived at call time so there is exactly ONE table to extend
    when a new TPU generation lands."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.autotune import canonical_device_kind

    return [
        (tflops, canonical_device_kind(sub))
        for sub, tflops in telemetry._PEAK_TFLOPS_BY_KIND
    ]


def _model_cfg(name: str):
    from distrl_llm_tpu.models import QWEN2_0_5B, TINY
    from distrl_llm_tpu.models.configs import QWEN2_7B

    table = {"tiny": TINY, "qwen2.5-0.5b": QWEN2_0_5B, "qwen2.5-7b": QWEN2_7B}
    if name not in table:
        raise SystemExit(
            f"unknown model {name!r} (expected one of {sorted(table)})"
        )
    return table[name]


def _row_device_kind(row: dict, override: str | None) -> str | None:
    """The canonical device kind a row was measured on, or None when it
    cannot be determined — a TPU row with an unrecognized peak_tflops must
    be SKIPPED (with --device-kind as the explicit escape hatch), never
    keyed to the ingesting host's kind: a TPU-tuned plan filed under "cpu"
    would retune every CPU engine sharing the DB."""
    if override:
        return override
    if row.get("device_kind"):  # rows since this PR record it directly
        return str(row["device_kind"])
    backend = row.get("backend", "cpu")
    if backend != "tpu":
        return backend
    peak = float(row.get("peak_tflops") or 0)
    for p, kind in _peak_to_kind():
        if abs(peak - p) < 1.0:
            return kind
    return None


def plan_from_bench_row(row: dict):
    """The ExecutionPlan a bench row ACTUALLY ran: chunk-inactive rows store
    chunk 0 (what executed), honoring the scan_chunk_active honesty flag."""
    from distrl_llm_tpu.autotune import ExecutionPlan

    engine = row.get("engine", "dense")
    path = (
        "speculative" if engine == "paged" and row.get("spec_draft")
        else ("paged" if engine == "paged" else "dense")
    )
    chunk = int(row.get("scan_chunk") or 0)
    if not row.get("scan_chunk_active"):
        chunk = 0
    spec_kw = {}
    if path == "speculative":
        # spec rows carry their whole configuration (ISSUE 6): the draft
        # length, the drafter, and the verify kernel that actually ran —
        # storing them makes the tuned plan reproducible
        spec_kw = {
            "spec_draft_len": int(row.get("spec_draft") or 0),
            "spec_drafter": row.get("spec_drafter"),
            "spec_verify": row.get("spec_verify_impl"),
        }
    return ExecutionPlan(
        decode_path=path,
        scan_chunk=chunk,
        # rows since this PR carry the formulation; older rows derive
        cache_read_formulation=row.get("cache_read_formulation"),
        top_p_impl=row.get("top_p_impl"),
        # quantized-serving provenance (ISSUE 15): what the row MEASURED
        # becomes the stored serving format ("none" included — it is a
        # measured choice, not "unset"); pre-ISSUE-15 rows without the
        # fields leave them None (engine default)
        kv_format=row.get("kv_format") or row.get("kv_quant"),
        base_quant=row.get("base_quant"),
        **spec_kw,
    )


def iter_bench_rows(paths):
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    print(f"skipping unparseable line in {path}", file=sys.stderr)
                    continue
                if isinstance(row, dict):
                    row["_path"] = path
                    yield row


def ingest_rows(rows, *, store, max_prompt: int, max_new: int,
                device_kind: str | None = None) -> list[str]:
    """Group rollout rows by (device, model, geometry), keep each group's
    fastest error-free row, store its plan under the exact-rows AND
    any-rows geometry keys. Returns the keys written.

    Rows since this PR record their own ``max_prompt_tokens`` /
    ``max_new_tokens``; LEGACY rows (the r5 artifacts) don't, and fall back
    to the ``--max-prompt/--max-new`` flags — only feed same-geometry
    legacy artifacts into one ingest run."""
    from distrl_llm_tpu.autotune import (
        model_config_hash, plan_key, shape_bucket,
    )

    groups: dict[tuple, list[dict]] = {}
    for row in rows:
        if row.get("metric") != "rollout_tokens_per_sec_per_chip":
            continue
        if row.get("error") or not row.get("value"):
            continue
        kind = _row_device_kind(row, device_kind)
        if kind is None:
            print(
                f"skipping tpu row with unrecognized peak_tflops="
                f"{row.get('peak_tflops')!r} "
                f"({os.path.basename(row.get('_path', ''))}) — pass "
                "--device-kind to ingest it",
                file=sys.stderr,
            )
            continue
        geo = (
            int(row.get("max_prompt_tokens") or max_prompt),
            int(row.get("max_new_tokens") or max_new),
        )
        groups.setdefault((kind, row.get("model", ""), geo), []).append(row)

    written: list[str] = []
    for (kind, model, (mp, mn)), rws in sorted(groups.items()):
        best = max(rws, key=lambda r: float(r["value"]))
        try:
            cfg = _model_cfg(model)
        except SystemExit:
            print(f"skipping rows for unknown model {model!r}", file=sys.stderr)
            continue
        plan = plan_from_bench_row(best)
        measurements = [
            {
                "tok_s": float(r["value"]),
                "plan": plan_from_bench_row(r).to_dict(),
                "note": os.path.basename(r.get("_path", "")),
            }
            for r in sorted(rws, key=lambda r: -float(r["value"]))
        ]
        rows_count = int(best.get("completions") or 0)
        mhash = model_config_hash(cfg)
        keys = [plan_key(kind, mhash, shape_bucket(mp, mn, 0))]
        if rows_count:
            keys.insert(0, plan_key(
                kind, mhash, shape_bucket(mp, mn, rows_count)
            ))
        for key in keys:
            store.put(
                key, plan, measurements,
                note=f"ingested from {len(rws)} bench row(s) at "
                     f"p{mp}+n{mn}; best {best['value']} tok/s/chip "
                     f"({os.path.basename(best.get('_path', ''))})",
            )
            written.append(key)
    return written


def cmd_ingest(args) -> int:
    from distrl_llm_tpu.autotune import PlanStore

    store = PlanStore(args.plan_db)
    written = ingest_rows(
        iter_bench_rows(args.bench), store=store,
        max_prompt=args.max_prompt, max_new=args.max_new,
        device_kind=args.device_kind,
    )
    if not written:
        print("no usable rollout rows found — DB unchanged", file=sys.stderr)
        return 1
    store.save()
    print(f"wrote {len(written)} plan entr{'y' if len(written) == 1 else 'ies'}"
          f" to {store.path}")
    print(store.report())
    return 0


def cmd_measure(args) -> int:
    import jax

    from distrl_llm_tpu.autotune import (
        PlanStore, candidate_plans, current_device_kind, model_config_hash,
        plan_key, shape_bucket,
    )
    from distrl_llm_tpu.autotune.microbench import best_result, tune_geometry
    from distrl_llm_tpu.models import init_lora_params, init_params

    cfg = _model_cfg(args.model)
    dtype = (
        jax.numpy.bfloat16 if jax.devices()[0].platform == "tpu"
        else jax.numpy.float32
    )
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=8, dtype=dtype)
    candidates = candidate_plans(
        decode_paths=tuple(args.paths.split(",")),
        scan_chunks=tuple(int(x) for x in args.scan_chunks.split(",")),
        top_p_impls=tuple(
            (None if x in ("", "auto") else x)
            for x in args.top_p_impls.split(",")
        ),
        spec_draft_lens=tuple(
            int(x) for x in args.spec_draft_lens.split(",")
        ),
        spec_drafters=tuple(
            (None if x in ("", "auto") else x)
            for x in args.spec_drafters.split(",")
        ),
        spec_verifies=tuple(
            (None if x in ("", "auto") else x)
            for x in args.spec_verifies.split(",")
        ),
        cb_modes=tuple(
            (None if x in ("", "auto") else x)
            for x in args.cb_modes.split(",")
        ),
        kv_formats=tuple(
            (None if x in ("", "auto") else x)
            for x in args.kv_formats.split(",")
        ),
        base_quants=tuple(
            (None if x in ("", "auto") else x)
            for x in args.base_quants.split(",")
        ),
    )
    print(f"measuring {len(candidates)} candidate plan(s) for {args.model} "
          f"p{args.max_prompt}+n{args.max_new} × {args.prompts}·"
          f"{args.candidates} rows on {current_device_kind()}")
    results = tune_geometry(
        cfg, params, lora, candidates,
        n_prompts=args.prompts, n_candidates=args.candidates,
        max_prompt_tokens=args.max_prompt, max_new_tokens=args.max_new,
        warmup=args.warmup, repeats=args.repeats, kv_quant=args.kv_quant,
    )
    for r in results:
        status = f"{r.tok_s:9.1f} tok/s" if r.feasible else "INFEASIBLE"
        note = f"  [{r.note}]" if r.note else ""
        print(f"  {status}  path={r.plan.decode_path} "
              f"chunk={r.plan.scan_chunk} "
              f"top_p={r.plan.top_p_impl or 'auto'}"
              f" (warmup {r.warmup_s:.2f}s, steady {r.steady_s:.3f}s)"
              f"{note}")
    winner = best_result(results)
    if winner is None:
        print("every candidate was infeasible — DB unchanged", file=sys.stderr)
        return 1
    store = PlanStore(args.plan_db)
    mhash = model_config_hash(cfg)
    kind = current_device_kind()
    rows = args.prompts * args.candidates
    measurements = [
        {"tok_s": r.tok_s, "plan": r.plan.to_dict(),
         "feasible": r.feasible, "note": r.note}
        for r in results
    ]
    for rws in {rows, 0}:
        store.put(
            plan_key(kind, mhash, shape_bucket(args.max_prompt, args.max_new, rws)),
            winner.plan, measurements,
            note=f"microbench winner {winner.tok_s:.1f} tok/s "
                 f"({len(results)} candidates)",
        )
    store.save()
    print(f"winner: path={winner.plan.decode_path} "
          f"chunk={winner.plan.scan_chunk} ({winner.tok_s:.1f} tok/s) "
          f"→ {store.path}")
    print(store.report())
    return 0


def cmd_report(args) -> int:
    from distrl_llm_tpu.autotune import PlanStore

    print(PlanStore(args.plan_db).report())
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--plan-db", dest="plan_db", default=None,
                        help="DB path (default: $DISTRL_PLAN_DB or "
                             "~/.cache/distrl_llm_tpu/plan_db.json)")

    m = sub.add_parser("measure", help="micro-bench a candidate space here")
    common(m)
    m.add_argument("--model", default="tiny")
    m.add_argument("--prompts", type=int, default=4)
    m.add_argument("--candidates", type=int, default=2)
    m.add_argument("--max-prompt", dest="max_prompt", type=int, default=64)
    m.add_argument("--max-new", dest="max_new", type=int, default=64)
    m.add_argument("--paths", default="dense",
                   help="comma list from dense,paged,speculative")
    m.add_argument("--scan-chunks", dest="scan_chunks", default="0,16",
                   help="comma list of scan_chunk candidates (0 = host loop)")
    m.add_argument("--top-p-impls", dest="top_p_impls", default="auto",
                   help="comma list of top-p impls ('auto' = derive)")
    m.add_argument("--spec-draft-lens", dest="spec_draft_lens", default="0,4",
                   help="comma list of speculative draft lengths (0 rides "
                        "the non-speculative paths; >0 only pairs with the "
                        "speculative path)")
    m.add_argument("--spec-drafters", dest="spec_drafters", default="auto",
                   help="comma list from auto,ngram,self ('auto' = engine "
                        "default; speculative path only)")
    m.add_argument("--cb-modes", dest="cb_modes", default="auto",
                   help="comma list of continuous-batching admission "
                        "candidates: auto (engine default — fixed "
                        "batches), batch, continuous (prefix-shared "
                        "chains + lazy per-group admission; paged/"
                        "speculative paths only)")
    m.add_argument("--spec-verifies", dest="spec_verifies", default="auto",
                   help="comma list from auto,fused,unrolled ('auto' = "
                        "engine default; speculative path only)")
    m.add_argument("--kv-quant", dest="kv_quant", default="none",
                   choices=["none", "int8"],
                   help="sweep-level KV format for candidates whose "
                        "kv_format field is unset ('auto' in --kv-formats)")
    m.add_argument("--kv-formats", dest="kv_formats", default="auto",
                   help="comma list of KV-format candidates from "
                        "auto,none,int8 (ISSUE 15): 'auto' leaves the "
                        "field unset (engine default / --kv-quant), "
                        "none/int8 store a MEASURED serving format the "
                        "engines resolve when built with kv_quant=None — "
                        "e.g. --kv-formats none,int8 makes int8 KV the "
                        "measured default wherever it wins")
    m.add_argument("--base-quants", dest="base_quants", default="auto",
                   help="comma list of frozen-base weight formats from "
                        "auto,none,int8,int4 (ISSUE 15): each non-auto "
                        "candidate is measured over a base tree quantized "
                        "to that format (fused dequant-matmul kernel "
                        "where enabled) and stored in the winning plan")
    m.add_argument("--warmup", type=int, default=1)
    m.add_argument("--repeats", type=int, default=2)
    m.set_defaults(fn=cmd_measure)

    i = sub.add_parser(
        "ingest",
        help="derive plans from the retired benchmark script's JSON rows "
             "(benchmarks/r5)",
    )
    common(i)
    i.add_argument("bench", nargs="+", help="bench JSON files (one row/line)")
    i.add_argument("--max-prompt", dest="max_prompt", type=int, default=350)
    i.add_argument("--max-new", dest="max_new", type=int, default=1200)
    i.add_argument("--device-kind", dest="device_kind", default=None,
                   help="canonical device kind for tpu rows (default: "
                        "inferred from the row's peak_tflops)")
    i.set_defaults(fn=cmd_ingest)

    r = sub.add_parser("report", help="print the stored plans")
    common(r)
    r.set_defaults(fn=cmd_report)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
