"""An eighth rehearsal benchmark: the ``rollout`` and ``rl_step`` kinds over the
second window family (MiMo-V2-Flash's layer kinds and its share, at a test
size: KV heads a kind, keys wider than values, a rotated share of a head at a
base a kind, a learned sink in the window layers), as new files under
``tests/perfbench/swa_sink_moe/`` and none of the other families' edited. The
real benchmark's metrics over two cells.

The one per-layer metric this family brings (PR 60),
``engine.cache_token_bytes``, lies under ``perfbench/layer_metrics/`` (its
reader is the accepted ``program_gauge``) and is declared in the real
``BENCHMARK.json`` for ``mimo-v2-flash-ep16-L7.rollout-longctx-sink-128``; this
benchmark declares it by name for its own rollout cell and finds the same file
over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

SWA_SINK_MOE_DIR = "tests/perfbench/swa_sink_moe"
CELL = "swa-sink-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("swa-sink-moe-rollout", "rollout_tok_s"),
    # Trainer.train() with --engine_impl paged: the whole loop over this model
    "swa-sink-moe-tiny.rl-paged": ("swa-sink-moe-rl-paged", "step_s"),
}

#: (name, unit, source, layer, better) of the metric this family brings, moving
#: ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
SWA_SINK_MOE_METRICS = (
    ("engine.cache_token_bytes", "count", "program_counter", "engine", "lower"),
)

#: what PR 60 appended its cell's name to: everything K-EXAONE's cell reports
#: (the rollout cells' common lists, PR 38's host account, PR 56's round ledger,
#: the expert layer's, the full layers' paged launch, the rings' share and the
#: window's two), and the full layers' prefill
JOINED = (
    "rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
    "engine.slot_occupancy", "engine.snapshot_wait_ms", "engine.kv_write_share",
    "engine.slot_state_share", "engine.expert_load_imbalance", "engine.expert_held_share",
    "kernel.sampler_share", "kernel.paged_attn_share", "kernel.moe_experts_roofline",
    "kernel.softmax_paged_roofline", "model.attn_proj_share", "model.mlp_share",
    "model.head_share", "model.moe_router_share", "model.moe_dispatch_share",
    "model.moe_experts_share", "rollout.unscoped_share",
    "engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
    "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
    "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms",
    "engine.boundary_median_ms", "engine.worst_boundary_ms", "engine.worst_boundary_host_ms",
    "engine.worst_boundary_cpu_ms", "engine.stalled_boundaries", "engine.stall_recovered_ms",
    "engine.snapshot_launch_ms", "engine.prefill_real_share",
    "model.window_attn_share", "engine.window_attended_share", "model.attn_core_share",
)
#: what it does not report. ``paged_attn_roofline`` divides the configuration's
#: whole cache bytes, rings included, by the paged kernel's time
NOT_JOINED = ("paged_attn_roofline", "engine.admit_host_ms")


def swa_sink_moe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in SWA_SINK_MOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{SWA_SINK_MOE_DIR}/configs/swa-sink-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [SWA_SINK_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "swa-sink-moe-tiny", "source": config, "file": config,
            "reduced": ["n_routed_experts", "vocab_size"],
            "why": "the drivers over window layers with a sink beside full layers of other KV heads, K wider than V, and a share of the experts on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "swa-sink-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in SWA_SINK_MOE_METRICS],
    }


def write_swa_sink_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.swa_sink_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(swa_sink_moe_benchmark(), f)
    return path
