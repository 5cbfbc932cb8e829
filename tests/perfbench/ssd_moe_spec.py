"""A twelfth rehearsal benchmark: the ``rollout`` kind over a state-space expert
model of one sublayer a layer (NVIDIA-Nemotron-3-Nano's layer kinds: Mamba-2,
attention without a second half, ungated relu^2 experts without a mixer, at a
test size), as new files under ``tests/perfbench/ssd_moe/`` and none of the
other families' edited. The real benchmark's metrics over one cell.

The one per-layer metric this family brings (PR 70) lies under
``perfbench/layer_metrics/`` (``kernel.ssd_chunk_roofline``) with its reader
``perfbench/readers/ssd_work.py`` and is declared in the real ``BENCHMARK.json``
for ``nemotron-3-nano-ep2-L13.rollout-reasoning-ssd``; this benchmark declares
it by name for its own rollout cell and finds the same files over its second
path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

SSD_MOE_DIR = "tests/perfbench/ssd_moe"
CELL = "ssd-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("ssd-moe-rollout", "rollout_tok_s"),
}

#: (name, unit, source, layer, better) of the metric this family brings, moving
#: ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
SSD_MOE_METRICS = (
    ("kernel.ssd_chunk_roofline", "%", "device_trace", "kernels", "higher"),
)

#: what PR 70 appended its cell's name to: the end-to-end metric, every list the
#: state-space family's cell is in but the Mamba-1 scan's, the experts' six, the
#: paged launch's share of its roofline and what a cached token costs
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "kernel.paged_attn_share", "kernel.sampler_share",
          "model.attn_proj_share", "model.mlp_share", "model.head_share",
          "engine.kv_write_share", "rollout.unscoped_share", "engine.snapshot_wait_ms",
          "model.short_conv_share", "engine.slot_state_share", "model.ssm_share",
          "kernel.ssm_step_roofline", "engine.prefill_real_share",
          "engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
          "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
          "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms",
          "engine.boundary_median_ms", "engine.worst_boundary_ms",
          "engine.worst_boundary_host_ms", "engine.worst_boundary_cpu_ms",
          "engine.stalled_boundaries", "engine.stall_recovered_ms",
          "engine.snapshot_launch_ms",
          "model.moe_router_share", "model.moe_dispatch_share", "model.moe_experts_share",
          "kernel.moe_experts_roofline", "engine.expert_load_imbalance",
          "engine.expert_held_share", "kernel.softmax_paged_roofline",
          "engine.cache_token_bytes")
#: what it does not report: the Mamba-1 scan's bytes (this family's segments are
#: matrix products: ``kernel.ssd_chunk_roofline`` reads them); the refill
#: scheduler's admission (one wave); the dense decoder's cache roofline (it would
#: divide the states' bytes by the paged launch's time)
NOT_JOINED = ("kernel.ssm_scan_roofline", "engine.admit_host_ms", "paged_attn_roofline")


def ssd_moe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in SSD_MOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{SSD_MOE_DIR}/configs/ssd-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [SSD_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "ssd-moe-tiny", "source": config, "file": config,
            "reduced": ["n_routed_experts"],
            "why": "the drivers over Mamba-2 heads of state, an attention layer alone and ungated experts alone, one sublayer a layer, on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "ssd-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in SSD_MOE_METRICS],
    }


def write_ssd_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.ssd_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(ssd_moe_benchmark(), f)
    return path
