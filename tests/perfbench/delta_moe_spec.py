"""A fourth rehearsal benchmark: the ``rollout`` and ``learner`` kinds over a
gated delta-rule model that holds one chip's share of its routed experts
(Solar-Open2-250B's layer kinds, at a test size), as new files under
``tests/perfbench/delta_moe/`` and none of ``tiny/``, ``sala/`` or
``latent_moe/`` edited. The real benchmark's metrics over two cells.

The six per-layer metrics this family brings (PR 36) lie under
``perfbench/layer_metrics/`` with their reader ``perfbench/readers/delta_moe_work.py``
and are declared in the real ``BENCHMARK.json`` for
``solar-open2-250b-ep8-L4.rollout-reasoning``; this benchmark declares them by
name for its own rollout cell and finds the same files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

DELTA_MOE_DIR = "tests/perfbench/delta_moe"
CELL = "delta-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("delta-moe-rollout", "rollout_tok_s"),
    "delta-moe-tiny.learner": ("delta-moe-learner", "learner_tok_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
DELTA_MOE_METRICS = (
    ("model.delta_attn_share", "%", "device_trace", "model forward", "lower"),
    ("model.short_conv_share", "%", "device_trace", "model forward", "lower"),
    ("kernel.delta_step_roofline", "%", "device_trace", "kernels", "higher"),
    ("kernel.delta_chunk_roofline", "%", "device_trace", "kernels", "higher"),
    ("kernel.softmax_paged_roofline", "%", "device_trace", "kernels", "higher"),
    ("engine.expert_held_share", "%", "program_counter", "engine", "higher"),
)

#: what PR 36 appended its cell's name to: the end-to-end metric, the ten
#: general per-layer lists, the paged kernel's share and the expert layer's five
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "kernel.sampler_share", "model.attn_proj_share",
          "model.mlp_share", "model.head_share", "engine.kv_write_share",
          "rollout.unscoped_share", "engine.snapshot_wait_ms", "kernel.paged_attn_share",
          "model.moe_router_share", "model.moe_dispatch_share", "model.moe_experts_share",
          "kernel.moe_experts_roofline", "engine.expert_load_imbalance")


def delta_moe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in DELTA_MOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{DELTA_MOE_DIR}/configs/delta-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [DELTA_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "delta-moe-tiny", "source": config, "file": config,
            "reduced": ["n_routed_experts", "vocab_size"],
            "why": "the drivers over a gated delta rule and a share of the experts on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "delta-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in DELTA_MOE_METRICS],
    }


def write_delta_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.delta_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(delta_moe_benchmark(), f)
    return path
