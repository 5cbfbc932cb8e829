"""An eighth rehearsal benchmark: the ``rollout``, ``learner`` and ``rl_step`` kinds over a
latent-attention model behind a learned index over tokens (GLM-5's layer kinds,
its index and its share, at a test size), as new files under
``tests/perfbench/dsa_moe/`` and none of the other families' edited. The real
benchmark's metrics over three cells.

The six per-layer metrics this family brings (PR 54) lie under
``perfbench/layer_metrics/`` (three shares read by the accepted
``trace_scopes``; ``engine.index_attended_share`` and the two rooflines with
their reader ``perfbench/readers/dsa_moe_work.py``) and are declared in the
real ``BENCHMARK.json`` for ``glm-5-ep16-L5.rollout-longctx-indexed``; this
benchmark declares them by name for its own rollout cell and finds the same
files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

DSA_MOE_DIR = "tests/perfbench/dsa_moe"
CELL = "dsa-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("dsa-moe-rollout", "rollout_tok_s"),
    "dsa-moe-tiny.learner": ("dsa-moe-learner", "learner_tok_s"),
    # Trainer.train() with --engine_impl paged: the whole loop over this model
    "dsa-moe-tiny.rl-paged": ("dsa-moe-rl-paged", "step_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
DSA_MOE_METRICS = (
    ("model.index_score_share", "%", "device_trace", "model forward", "lower"),
    ("model.index_select_share", "%", "device_trace", "model forward", "lower"),
    ("model.indexed_attn_share", "%", "device_trace", "model forward", "lower"),
    ("engine.index_attended_share", "%", "program_counter", "engine", "lower"),
    ("kernel.index_score_roofline", "%", "device_trace", "kernels", "higher"),
    ("kernel.indexed_attn_roofline", "%", "device_trace", "kernels", "higher"),
)

#: what PR 54 appended its cell's name to: the end-to-end metric, the lists the
#: rollout cells share, the expert layer's (Kimi-VL's, Solar's, K-EXAONE's) and
#: the round's host account
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "engine.snapshot_wait_ms", "engine.kv_write_share",
          "engine.expert_load_imbalance", "engine.expert_held_share",
          "engine.prefill_real_share", "kernel.sampler_share",
          "kernel.moe_experts_roofline", "model.attn_proj_share", "model.mlp_share",
          "model.head_share", "model.moe_router_share", "model.moe_dispatch_share",
          "model.moe_experts_share", "rollout.unscoped_share",
          "engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
          "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
          "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms")
#: what it does not report: Kimi-VL's dense walk over every latent row (its
#: counts would read this cell's gather wrong) and the paged launch (none runs)
NOT_JOINED = ("model.latent_attn_share", "kernel.latent_attn_roofline",
              "kernel.paged_attn_share", "paged_attn_roofline")


def dsa_moe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in DSA_MOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{DSA_MOE_DIR}/configs/dsa-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [DSA_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "dsa-moe-tiny", "source": config, "file": config,
            "reduced": ["n_routed_experts", "vocab_size"],
            "why": "the drivers over latent attention behind a learned index over tokens and a share of the experts on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "dsa-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in DSA_MOE_METRICS],
    }


def write_dsa_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.dsa_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(dsa_moe_benchmark(), f)
    return path
