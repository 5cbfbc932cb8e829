"""``BENCHMARK.json`` and the files it names, found by name and nothing else.

A cell is a configuration (a JSON file of sizes), a traffic mix (a JSON file of
parameters whose ``kind`` names a driver module) and the metrics that list the
cell. Every lookup walks the benchmark's ``paths`` in order, so a later PR adds
a file under one of them and edits none that is there.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Any

#: the checkout: the directory that holds ``BENCHMARK.json`` and ``perfbench/``
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: a scope name: lower-case components joined by single slashes, as
#: ``jax.named_scope`` writes them into an operation's path
SCOPE_NAME = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+)*$")


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict[str, Any]  # the configuration file, whole
    traffic_name: str
    traffic: dict[str, Any]  # the traffic file, whole
    end_to_end: tuple[dict[str, Any], ...]  # the metric entries this cell reports
    per_layer: tuple[dict[str, Any], ...]
    paths: tuple[str, ...]  # the benchmark's directories, relative to ROOT


def load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(path: str | None = None) -> dict[str, Any]:
    path = path or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(path)
    for key in ("paths", "configs", "workloads", "end_to_end", "per_layer"):
        if key not in bench:
            raise SpecError(f"{path} has no {key!r}")
    return bench


def find_file(paths, sub: str, filename: str) -> str:
    """``<ROOT>/<path>/<sub>/<filename>`` in the first of ``paths`` that has it."""
    for p in paths:
        candidate = os.path.join(ROOT, p, sub, filename)
        if os.path.isfile(candidate):
            return candidate
    raise SpecError(
        f"no {sub}/{filename} under any of the benchmark's paths {list(paths)}"
    )


def load_module(paths, sub: str, name: str) -> ModuleType:
    """The Python module ``<path>/<sub>/<name>.py``, found like ``find_file``
    and imported under a name of its own (so a directory that is not a package
    can hold one)."""
    path = find_file(paths, sub, f"{name}.py")
    mod_name = f"perfbench_{sub}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules and getattr(
        sys.modules[mod_name], "__file__", None
    ) == path:
        return sys.modules[mod_name]
    module_spec = importlib.util.spec_from_file_location(mod_name, path)
    if module_spec is None or module_spec.loader is None:
        raise SpecError(f"cannot import {path}")
    module = importlib.util.module_from_spec(module_spec)
    sys.modules[mod_name] = module
    module_spec.loader.exec_module(module)
    return module


def _reported_in(metric: dict[str, Any], cell_name: str) -> bool:
    only = metric.get("workloads")
    return only is None or cell_name in only


def load_cell(bench: dict[str, Any], workload: str) -> Cell:
    paths = tuple(bench["paths"])
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise SpecError(f"no workload {workload!r}; BENCHMARK.json has {known}")
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise SpecError(f"workload {workload!r} names no known config")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(find_file(paths, "traffic", f"{entry['traffic']}.json"))
    if "kind" not in traffic:
        raise SpecError(f"traffic {entry['traffic']!r} has no 'kind'")
    if "check" in traffic and not traffic["check"].get("basis"):
        raise SpecError(
            f"traffic {entry['traffic']!r}: its 'check' sets tolerances and gives "
            "no 'basis' (the runs they were measured from)"
        )
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], config=config,
        traffic_name=entry["traffic"], traffic=traffic,
        end_to_end=tuple(
            m for m in bench["end_to_end"] if _reported_in(m, workload)
        ),
        per_layer=tuple(
            m for m in bench["per_layer"] if _reported_in(m, workload)
        ),
        paths=paths,
    )


def load_layer_metric(paths, name: str) -> dict[str, Any]:
    """``layer_metrics/<name>.json``: ``layer``, ``unit``, ``moves``,
    ``source``, the ``reader`` module that takes the number from what the run
    observed, and the reader's ``args``."""
    metric = load_json(find_file(paths, "layer_metrics", f"{name}.json"))
    for key in ("layer", "unit", "moves", "source", "reader"):
        if key not in metric:
            raise SpecError(f"layer_metrics/{name}.json has no {key!r}")
    return metric


def load_scope_names(paths) -> tuple[str, ...]:
    """The scope names a run's device time is summed under: every
    ``<path>/scopes/*.json`` (``{"names": [...], "for": "..."}``) over
    ``paths`` in order, the files of one directory by name. The union of the
    files, and a name held twice is refused: a PR whose program carries a new
    ``jax.named_scope`` adds a file and edits none."""
    names: dict[str, str] = {}
    for p in paths:
        for path in sorted(glob.glob(os.path.join(ROOT, p, "scopes", "*.json"))):
            held = load_json(path)
            if not isinstance(held, dict) or "names" not in held or "for" not in held:
                raise SpecError(f"{path} must hold 'names' and what they are 'for'")
            for name in held["names"]:
                if not isinstance(name, str) or SCOPE_NAME.match(name) is None:
                    raise SpecError(f"{path}: {name!r} is no plain scope name")
                if name in names:
                    raise SpecError(f"scope {name!r} is in {names[name]} and in {path}")
                names[name] = path
    return tuple(names)
