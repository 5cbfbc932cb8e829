"""An eleventh rehearsal benchmark: the ``rollout`` kind over a looped dense
decoder (Ouro's layer: one stack of weights run several times a token, a cache
layer a (pass, layer), both sides of a sublayer normed, an exit gate a pass, at
a test size), as new files under ``tests/perfbench/looped/`` and none of the
other families' edited. The real benchmark's metrics over one cell.

The two per-layer metrics this family brings (PR 68) lie under
``perfbench/layer_metrics/`` (``model.exit_gate_share``, read by the accepted
``trace_scopes``; ``engine.exit_step_mean``, read by the accepted
``program_gauge``) and are declared in the real ``BENCHMARK.json`` for
``ouro-2.6b-L8.rollout-reasoning-loop4``; this benchmark declares them by name
for its own rollout cell and finds the same files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

LOOPED_DIR = "tests/perfbench/looped"
CELL = "looped-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("looped-rollout", "rollout_tok_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
LOOPED_METRICS = (
    ("model.exit_gate_share", "%", "device_trace", "model forward", "lower"),
    ("engine.exit_step_mean", "count", "program_counter", "engine", "lower"),
)

#: what PR 68 appended its cell's name to: the end-to-end metric, the lists the
#: dense family's rollout cell is in and what a cached token costs
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "kernel.paged_attn_share", "kernel.sampler_share",
          "paged_attn_roofline", "model.attn_proj_share", "model.mlp_share",
          "model.head_share", "engine.kv_write_share", "rollout.unscoped_share",
          "engine.snapshot_wait_ms", "engine.dispatch_host_ms",
          "engine.dispatch_median_ms", "engine.prefill_ms", "engine.readback_ms",
          "engine.loop_self_ms", "engine.host_busy_share", "engine.slowest_boundary_ms",
          "engine.slowest_boundary_host_ms", "engine.boundary_median_ms",
          "engine.worst_boundary_ms", "engine.worst_boundary_host_ms",
          "engine.worst_boundary_cpu_ms", "engine.stalled_boundaries",
          "engine.stall_recovered_ms", "engine.snapshot_launch_ms",
          "engine.cache_token_bytes")
#: what it does not report: the prompts' attention stands with the decode launch
#: under ``model/attn_core`` in ``transformer._layer`` (the dense cell does not
#: list it either); no refill admission runs in one wave; no state beside pages
NOT_JOINED = ("model.attn_core_share", "engine.admit_host_ms",
              "engine.slot_state_share", "engine.prefill_real_share")


def looped_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in LOOPED_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{LOOPED_DIR}/configs/looped-tiny.json"
    return {
        "command": real["command"],
        "paths": [LOOPED_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "looped-tiny", "source": config, "file": config,
            "reduced": [],
            "why": "the drivers over one stack of two layers run three times a token, six cache layers and an exit gate a pass, on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "looped-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in LOOPED_METRICS],
    }


def write_looped_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.looped.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(looped_benchmark(), f)
    return path
