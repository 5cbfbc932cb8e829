"""A fifth rehearsal benchmark: the ``rollout`` and ``learner`` kinds over a
power-retention model (Brumby-14B-Base's layer kind, at a test size), as new
files under ``tests/perfbench/power/`` and none of ``tiny/``, ``sala/``,
``latent_moe/`` or ``delta_moe/`` edited. The real benchmark's metrics over two
cells.

The four per-layer metrics this family brings (PR 40) lie under
``perfbench/layer_metrics/`` with their reader ``perfbench/readers/power_work.py``
(``engine.slot_state_share`` is read by the accepted ``program_gauge``) and are
declared in the real ``BENCHMARK.json`` for
``brumby-14b-L4.rollout-retention-16k``; this benchmark declares them by name
for its own rollout cell and finds the same files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

POWER_DIR = "tests/perfbench/power"
CELL = "power-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("power-rollout", "rollout_tok_s"),
    "power-tiny.learner": ("power-learner", "learner_tok_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
POWER_METRICS = (
    ("model.power_attn_share", "%", "device_trace", "model forward", "lower"),
    ("kernel.power_step_roofline", "%", "device_trace", "kernels", "higher"),
    ("kernel.power_chunk_roofline", "%", "device_trace", "kernels", "higher"),
    ("engine.slot_state_share", "%", "program_counter", "engine", "higher"),
)

#: what PR 40 appended its cell's name to: the end-to-end metric and the general
#: per-layer lists of a rollout cell
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "engine.snapshot_wait_ms", "kernel.sampler_share",
          "model.attn_proj_share", "model.mlp_share", "model.head_share",
          "rollout.unscoped_share")
#: the eight of PR 38 (the round's host account), which the cell joined in
#: PR 53: until then a test of PR 38 held their lists equal to its four cells
JOINED += ("engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
           "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
           "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms")
#: what it does not report. No layer keeps a page: ``engine.kv_write_share``,
#: ``kernel.paged_attn_share``, ``paged_attn_roofline``
NOT_JOINED = ("engine.kv_write_share", "kernel.paged_attn_share", "paged_attn_roofline")


def power_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in POWER_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{POWER_DIR}/configs/power-tiny.json"
    return {
        "command": real["command"],
        "paths": [POWER_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "power-tiny", "source": config, "file": config, "reduced": [],
            "why": "the drivers over power retention (a slot whose whole cache is state) on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "power-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in POWER_METRICS],
    }


def write_power_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.power.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(power_benchmark(), f)
    return path
