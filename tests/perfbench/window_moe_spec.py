"""A seventh rehearsal benchmark: the ``rollout``, ``learner`` and ``rl_step``
kinds over a window model with routed experts (K-EXAONE-236B-A23B's layer
kinds and its share, at a test size), as new files under
``tests/perfbench/window_moe/`` and none of the other families' edited. The
real benchmark's metrics over three cells.

The two per-layer metrics this family brings (PR 49) lie under
``perfbench/layer_metrics/`` (``engine.window_attended_share`` with its reader
``perfbench/readers/window_moe_work.py``; ``model.window_attn_share`` is read
by the accepted ``trace_scopes``) and are declared in the real
``BENCHMARK.json`` for ``k-exaone-236b-ep8-L5.rollout-longctx-window``; this
benchmark declares them by name for its own rollout cell and finds the same
files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

WINDOW_MOE_DIR = "tests/perfbench/window_moe"
CELL = "window-moe-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("window-moe-rollout", "rollout_tok_s"),
    "window-moe-tiny.learner": ("window-moe-learner", "learner_tok_s"),
    # Trainer.train() with --engine_impl paged: the whole loop over this model
    "window-moe-tiny.rl-paged": ("window-moe-rl-paged", "step_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
WINDOW_MOE_METRICS = (
    ("model.window_attn_share", "%", "device_trace", "model forward", "lower"),
    ("engine.window_attended_share", "%", "program_counter", "engine", "lower"),
)

#: what PR 49 appended its cell's name to: the end-to-end metric, the lists the
#: rollout cells share, the expert layer's (Kimi-VL's and Solar's), the full
#: layer's paged launch (Solar's) and the slots' share of the chip (Brumby's)
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "engine.snapshot_wait_ms", "engine.kv_write_share",
          "engine.slot_state_share", "engine.expert_load_imbalance",
          "engine.expert_held_share", "kernel.sampler_share", "kernel.paged_attn_share",
          "kernel.moe_experts_roofline", "kernel.softmax_paged_roofline",
          "model.attn_proj_share", "model.mlp_share", "model.head_share",
          "model.moe_router_share", "model.moe_dispatch_share", "model.moe_experts_share",
          "rollout.unscoped_share")
#: the eight of PR 38 (the round's host account), which the cell joined in
#: PR 53: until then a test of PR 38 held their lists equal to its four cells
JOINED += ("engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
           "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
           "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms")
#: what it does not report. ``paged_attn_roofline`` divides the configuration's
#: whole cache bytes, rings included, by the paged kernel's time
NOT_JOINED = ("paged_attn_roofline",)


def window_moe_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in WINDOW_MOE_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{WINDOW_MOE_DIR}/configs/window-moe-tiny.json"
    return {
        "command": real["command"],
        "paths": [WINDOW_MOE_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "window-moe-tiny", "source": config, "file": config,
            "reduced": ["num_experts", "vocab_size"],
            "why": "the drivers over window layers beside a full-attention layer and a share of the experts on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "window-moe-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in WINDOW_MOE_METRICS],
    }


def write_window_moe_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.window_moe.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(window_moe_benchmark(), f)
    return path
