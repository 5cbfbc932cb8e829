"""A second rehearsal benchmark: the ``rollout`` kind over a model whose layers
differ in kind (MiniCPM-SALA's, at a test size), as new files under
``tests/perfbench/sala/`` and none of ``tiny/`` edited. The real benchmark's
metrics over three cells: the rollout engine, one learner update against the
reference's loss and adapter gradient, and ``Trainer.train()`` with the paged
engine.

Beside them the six per-layer metrics that read what these layers add to the
program (three scope shares, two rooflines, the share of visible blocks the
sparse layers attended). Their files and their reader lie under
``perfbench/layer_metrics/`` and ``perfbench/readers/`` (PR 35 declared them in
the real ``BENCHMARK.json``, for ``minicpm-sala-L10.rollout-longctx``); this
benchmark declares them by name for its own rollout cell and finds the same
files over its second path."""

from __future__ import annotations

import json
import os

from tiny_spec import real_benchmark

SALA_DIR = "tests/perfbench/sala"
CELL = "sala-tiny.rollout"
#: cell -> (traffic file, the end-to-end metric the cell's kind reports)
CELLS = {
    CELL: ("sala-rollout", "rollout_tok_s"),
    "sala-tiny.learner": ("sala-learner", "learner_tok_s"),
    "sala-tiny.rl-paged": ("sala-rl-paged", "step_s"),
}


#: (name, source, layer, better) of the metrics this family brings; every one in
#: %, moving ``rollout_tok_s``, as its file under ``perfbench/layer_metrics/`` says
SALA_METRICS = (
    ("model.linear_attn_share", "device_trace", "model forward", "lower"),
    ("model.sparse_select_share", "device_trace", "model forward", "lower"),
    ("model.sparse_attn_share", "device_trace", "model forward", "lower"),
    ("kernel.linear_attn_roofline", "device_trace", "kernels", "higher"),
    ("kernel.sparse_attn_roofline", "device_trace", "kernels", "higher"),
    ("engine.sparse_attended_share", "program_counter", "engine", "lower"),
)


def sala_benchmark() -> dict:
    real = real_benchmark()

    def over(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    return {
        "command": real["command"],
        "paths": [SALA_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": "sala-tiny", "source": f"{SALA_DIR}/configs/sala-tiny.json",
            "file": f"{SALA_DIR}/configs/sala-tiny.json", "reduced": [],
            "why": "the rollout driver over sparse and lightning layers on the CPU",
        }],
        "workloads": [
            {"name": cell, "config": "sala-tiny", "traffic": traffic, "chips": 1,
             "why": "rehearsal"} for cell, (traffic, _) in CELLS.items()
        ],
        "end_to_end": [over(m, "name") for m in real["end_to_end"]],
        "per_layer": [over(m, "moves") for m in real["per_layer"]
                      if m["name"] not in {name for name, *_ in SALA_METRICS}] + [{
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "rollout_tok_s", "workloads": [CELL],
        } for name, source, layer, better in SALA_METRICS],
    }


def write_sala_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.sala.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(sala_benchmark(), f)
    return path
