"""A third rehearsal benchmark: the ``rollout`` and ``learner`` kinds over a
model with latent attention and routed experts (Kimi-VL-A3B's layer kinds, at a
test size), as new files under ``tests/perfbench/latent_moe/`` and none of
``tiny/`` or ``sala/`` edited. The real benchmark's metrics over two cells: the
rollout engine (segmented prefill over latent pages, fan-out, absorbed decode)
and one learner update against the reference's loss and adapter gradient.

Beside them the seven per-layer metrics that read what these layers add to the
program (four scope shares, two rooflines, the experts' load imbalance). Their
files and their reader lie under ``perfbench/layer_metrics/`` and
``perfbench/readers/`` (PR 35 declared them in the real ``BENCHMARK.json``, for
``kimi-vl-a3b-L7.rollout-longctx-latent``); this benchmark declares them by
name for its own rollout cell and finds the same files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

LATENT_MOE_DIR = "tests/perfbench/latent_moe"
CELL = "latent-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("latent-moe-rollout", "rollout_tok_s"),
    "latent-moe-tiny.learner": ("latent-moe-learner", "learner_tok_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
LATENT_MOE_METRICS = (
    ("model.moe_router_share", "%", "device_trace", "model forward", "lower"),
    ("model.moe_dispatch_share", "%", "device_trace", "model forward", "lower"),
    ("model.moe_experts_share", "%", "device_trace", "model forward", "lower"),
    ("model.latent_attn_share", "%", "device_trace", "model forward", "lower"),
    ("kernel.moe_experts_roofline", "%", "device_trace", "kernels", "higher"),
    ("kernel.latent_attn_roofline", "%", "device_trace", "kernels", "higher"),
    ("engine.expert_load_imbalance", "x", "program_counter", "engine", "lower"),
)


#: what a rollout cell's PR appends its cell's name to (PR 29 did, PR 33 did):
#: the end-to-end metric and the ten accepted per-layer lists
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "kernel.sampler_share", "model.attn_proj_share",
          "model.mlp_share", "model.head_share", "engine.kv_write_share",
          "rollout.unscoped_share", "engine.snapshot_wait_ms")


def latent_moe_benchmark() -> dict:
    real = real_benchmark()

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{LATENT_MOE_DIR}/configs/latent-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [LATENT_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "latent-moe-tiny", "source": config, "file": config, "reduced": [],
            "why": "the drivers over latent attention and routed experts on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "latent-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in {name for name, *_ in LATENT_MOE_METRICS}] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in LATENT_MOE_METRICS],
    }


def write_latent_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.latent_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(latent_moe_benchmark(), f)
    return path
