"""A sixth rehearsal benchmark: the ``rollout``, ``learner`` and ``rl_step`` kinds over a
state-space model (AI21-Jamba2-3B's layer kinds, at a test size), as new files
under ``tests/perfbench/jamba/`` and none of ``tiny/``, ``sala/``,
``latent_moe/``, ``delta_moe/`` or ``power/`` edited. The real benchmark's
metrics over three cells.

The three per-layer metrics this family brings (PR 44) lie under
``perfbench/layer_metrics/`` with their reader ``perfbench/readers/ssm_work.py``
(``model.ssm_share`` is read by the accepted ``trace_scopes``) and are declared
in the real ``BENCHMARK.json`` for ``jamba2-3b.rollout-wide-480``; this
benchmark declares them by name for its own rollout cell and finds the same
files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

JAMBA_DIR = "tests/perfbench/jamba"
CELL = "jamba-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("jamba-rollout", "rollout_tok_s"),
    "jamba-tiny.learner": ("jamba-learner", "learner_tok_s"),
    # Trainer.train() with --engine_impl paged: the whole loop over this model
    "jamba-tiny.rl-paged": ("jamba-rl-paged", "step_s"),
}

#: (name, unit, source, layer, better) of the metrics this family brings, each
#: moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
JAMBA_METRICS = (
    ("model.ssm_share", "%", "device_trace", "model forward", "lower"),
    ("kernel.ssm_step_roofline", "%", "device_trace", "kernels", "higher"),
    ("kernel.ssm_scan_roofline", "%", "device_trace", "kernels", "higher"),
)

#: what PR 44 appended its cell's name to: the end-to-end metric, the eleven
#: per-layer lists Solar's cell shares with the older cells, the convolution's
#: share (Solar's) and the slots' share of the chip (Brumby's)
JOINED = ("rollout_tok_s", "engine.decode_bandwidth_util", "engine.decode_step_ms",
          "engine.slot_occupancy", "kernel.paged_attn_share", "kernel.sampler_share",
          "model.attn_proj_share", "model.mlp_share", "model.head_share",
          "engine.kv_write_share", "rollout.unscoped_share", "engine.snapshot_wait_ms",
          "model.short_conv_share", "engine.slot_state_share")
#: the eight of PR 38 (the round's host account), which the cell joined in
#: PR 53: until then a test of PR 38 held their lists equal to its four cells
JOINED += ("engine.dispatch_host_ms", "engine.dispatch_median_ms", "engine.prefill_ms",
           "engine.readback_ms", "engine.loop_self_ms", "engine.host_busy_share",
           "engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms")
#: what it does not report. ``paged_attn_roofline`` divides the configuration's
#: whole cache bytes, states included, by the paged kernel's time (over 100%
#: here, as for Solar's)
NOT_JOINED = ("paged_attn_roofline",)


def jamba_benchmark() -> dict:
    real = real_benchmark()
    own = {name for name, *_ in JAMBA_METRICS}

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    config = f"{JAMBA_DIR}/configs/jamba-tiny.json"
    return {
        "command": real["command"],
        "paths": [JAMBA_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "jamba-tiny", "source": config, "file": config, "reduced": [],
            "why": "the drivers over Mamba layers beside an attention layer of one KV head on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "jamba-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in own] + [{
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, unit, source, layer, better in JAMBA_METRICS],
    }


def write_jamba_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.jamba.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(jamba_benchmark(), f)
    return path
