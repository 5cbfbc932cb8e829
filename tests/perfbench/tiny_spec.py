"""The tests' own benchmark: a tiny configuration, four traffic mixes, a
per-layer metric and its reader, all NEW files under ``tests/perfbench/tiny/``,
and one new ``BENCHMARK.json`` that names them beside every metric of the real
one. No file of ``perfbench/`` is edited to add them: that is the point.

What a second model family brings is rehearsed the same way (PR 27): a scope
name of its own (``scopes/tiny.json``), its own counts (``tiny_counts.py``), a
second configuration that names them (``configs/tiny-counted.json``) and a
learner traffic file that states its own tolerances
(``traffic/tiny-learner-checked.json``), in one more cell.
"""

from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY_DIR = "tests/perfbench/tiny"

#: cell -> (traffic file, chips, the end-to-end metric the cell's kind reports);
#: a cell's configuration is the part of its name before the dot
CELLS = {
    "tiny.rollout": ("tiny-rollout", 1, "rollout_tok_s"),
    "tiny.learner": ("tiny-learner", 1, "learner_tok_s"),
    "tiny.rl-dense": ("tiny-rl-dense", 1, "step_s"),
    "tiny.rl-split4": ("tiny-rl-split4", 4, "step_s"),
    "tiny-counted.learner-checked": ("tiny-learner-checked", 1, "learner_tok_s"),
}


def real_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def tiny_benchmark() -> dict:
    """The real benchmark's metrics over the tiny cells, plus ``tiny.units``."""
    real = real_benchmark()

    def over_tiny(metric: dict, key: str) -> dict:
        metric = dict(metric)
        if "workloads" in metric:
            metric["workloads"] = [c for c, (_, _, e2e) in CELLS.items()
                                   if e2e == metric[key]]
        return metric

    return {
        "command": real["command"],
        "paths": [TINY_DIR, "perfbench"],
        "run_seconds": 1,
        "configs": [{
            "name": config, "source": "distrl_llm_tpu/models/configs.py::TINY",
            "file": f"{TINY_DIR}/configs/{config}.json", "reduced": [],
            "why": "every driver's control flow on the CPU; counts only",
        } for config in sorted({cell.split(".")[0] for cell in CELLS})],
        "workloads": [
            {"name": cell, "config": cell.split(".")[0], "traffic": traffic,
             "chips": chips, "why": "rehearsal"}
            for cell, (traffic, chips, _) in CELLS.items()
        ],
        "end_to_end": [over_tiny(m, "name") for m in real["end_to_end"]],
        "per_layer": [over_tiny(m, "moves") for m in real["per_layer"]] + [{
            "name": "tiny.units", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "harness", "moves": "setup_s",
        }],
    }


def write_tiny_benchmark(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.tiny.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(tiny_benchmark(), f)
    return path
