"""A tenth rehearsal benchmark: the ``rollout`` kind over a shortcut-connected expert model (LongCat-Flash's layer: two latent
sublayers and two dense MLPs round one expert block, a softmax router with
zero-compute outputs, its share, at a test size), as new files under
``tests/perfbench/scmoe/`` and none of the other families' edited. The real
benchmark's metrics over one cell.

The two per-layer metrics this family brings (PR 65) lie under
``perfbench/layer_metrics/`` (``engine.zero_expert_share``, read by the accepted
``delta_moe_work`` from the new counter beside ``engine/moe_pairs_routed``;
``model.moe_zero_share``, read by the accepted ``trace_scopes``) and are
declared in the real ``BENCHMARK.json`` for
``longcat-flash-ep32-L4.rollout-reasoning-zero-256``; this benchmark declares
them by name for its own rollout cell and finds the same files over its second
path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

SCMOE_DIR = "tests/perfbench/scmoe"
CELL = "scmoe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("scmoe-rollout", "rollout_tok_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
SCMOE_METRICS = (
    ("engine.zero_expert_share", "%", "program_counter", "engine", "higher"),
    ("model.moe_zero_share", "%", "device_trace", "model forward", "lower"),
)

#: what PR 65 appended its cell's name to: the end-to-end metric and every list
#: Kimi-VL's latent expert cell is in, and the held share of a cell with a share
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "engine.snapshot_wait_ms", "engine.kv_write_share",
          "engine.expert_load_imbalance", "engine.expert_held_share",
          "engine.prefill_real_share", "kernel.sampler_share",
          "kernel.moe_experts_roofline", "kernel.latent_attn_roofline",
          "model.attn_proj_share", "model.attn_core_share", "model.mlp_share",
          "model.head_share", "model.moe_router_share", "model.moe_dispatch_share",
          "model.moe_experts_share", "model.latent_attn_share", "rollout.unscoped_share",
          "engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
          "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
          "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms",
          "engine.boundary_median_ms", "engine.worst_boundary_ms",
          "engine.worst_boundary_host_ms", "engine.worst_boundary_cpu_ms",
          "engine.stalled_boundaries", "engine.stall_recovered_ms",
          "engine.snapshot_launch_ms")
#: what it does not report: a learned index's, the paged launch's (none runs)
NOT_JOINED = ("model.indexed_attn_share", "kernel.indexed_attn_roofline",
              "engine.index_attended_share", "kernel.paged_attn_share",
              "paged_attn_roofline", "engine.admit_host_ms")


def scmoe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in SCMOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{SCMOE_DIR}/configs/scmoe-tiny.json"
    return {
        "command": real["command"],
        "paths": [SCMOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "scmoe-tiny", "source": config, "file": config,
            "reduced": ["n_routed_experts", "vocab_size"],
            "why": "the drivers over two latent sublayers a layer round a softmax-routed expert block with zero-compute experts and a share, on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "scmoe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in SCMOE_METRICS],
    }


def write_scmoe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.scmoe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(scmoe_benchmark(), f)
    return path
