"""A ninth rehearsal benchmark: the ``rollout``, ``learner`` and ``rl_step`` kinds over
compressed convolutional attention with an MLP router (ZAYA1-8B's layer at a
test size), as new files under ``tests/perfbench/cca_moe/`` and none of the
other families' edited. The real benchmark's metrics over three cells.

The one per-layer metric this family brings (PR 58), ``model.cca_mix_share``,
lies under ``perfbench/layer_metrics/`` (read by the accepted ``trace_scopes``)
and is declared in the real ``BENCHMARK.json`` for
``zaya1-8b-L20.rollout-reasoning-cca``; this benchmark declares it by name for
its own rollout cell and finds the same file over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

CCA_MOE_DIR = "tests/perfbench/cca_moe"
CELL = "cca-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("cca-moe-rollout", "rollout_tok_s"),
    "cca-moe-tiny.learner": ("cca-moe-learner", "learner_tok_s"),
    # Trainer.train() with --engine_impl paged: the whole loop over this model
    "cca-moe-tiny.rl-paged": ("cca-moe-rl-paged", "step_s"),
}

#: (name, unit, source, layer, better) of the metric this family brings, moving
#: ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
CCA_MOE_METRICS = (
    ("model.cca_mix_share", "%", "device_trace", "model forward", "lower"),
)

#: what PR 58 appended its cell's name to: the end-to-end metric, the lists the
#: rollout cells share, the paged kernel's (Qwen's, Solar's, Jamba's, K-EXAONE's),
#: the expert layer's, the slots' state and the round's host account and ledger
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "engine.snapshot_wait_ms", "engine.kv_write_share",
          "engine.expert_load_imbalance", "engine.slot_state_share",
          "engine.prefill_real_share", "kernel.paged_attn_share", "kernel.sampler_share",
          "kernel.softmax_paged_roofline", "kernel.moe_experts_roofline",
          "model.attn_proj_share", "model.attn_core_share", "model.head_share",
          "model.moe_router_share", "model.moe_dispatch_share", "model.moe_experts_share",
          "rollout.unscoped_share", "engine.dispatch_host_ms", "engine.dispatch_median_ms",
          "engine.prefill_ms", "engine.readback_ms", "engine.loop_self_ms",
          "engine.host_busy_share", "engine.slowest_boundary_ms",
          "engine.slowest_boundary_host_ms", "engine.boundary_median_ms",
          "engine.worst_boundary_ms", "engine.worst_boundary_host_ms",
          "engine.worst_boundary_cpu_ms", "engine.stalled_boundaries",
          "engine.stall_recovered_ms", "engine.snapshot_launch_ms")
#: what it does not report: nothing is held elsewhere, no layer has a dense MLP
#: or a shared expert, and the dense decoder's own roofline counts Qwen's heads
NOT_JOINED = ("engine.expert_held_share", "model.mlp_share", "paged_attn_roofline",
              "model.short_conv_share")


def cca_moe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in CCA_MOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{CCA_MOE_DIR}/configs/cca-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [CCA_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "cca-moe-tiny", "source": config, "file": config,
            "reduced": ["num_hidden_layers"],
            "why": "the drivers over compressed convolutional attention with an MLP router on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "cca-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in CCA_MOE_METRICS],
    }


def write_cca_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.cca_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(cca_moe_benchmark(), f)
    return path
