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


#: the key of ``reduced`` that cuts depth, and the second that may stand beside
#: it: the count of leading dense layers, of which the first stage holds ONE
DEPTH_KEY = "num_hidden_layers"
DENSE_KEY = "first_k_dense_replace"
DEPTH_KEYS = (DEPTH_KEY, DENSE_KEY)
#: the names a published config gives its depth (84 and 4 of the catalog's 88
#: rows), and no pattern: a file's depth key is the ONE of them it holds, and
#: any other spelling (``n_layer``, ``depth``) is refused as a width until a
#: published config bears it out
DEPTH_NAMES = (DEPTH_KEY, "num_layers")
#: the fewest layers that follow the one leading dense layer
MIN_LAYERS_AFTER_DENSE = 4
#: keys of ``reduced`` that COUNT the routed experts this chip holds of a layer
#: (whichever the published config uses), and the fewest it may hold
EXPERT_KEYS = ("n_routed_experts", "num_experts", "num_local_experts")
MIN_EXPERTS_HELD = 8
#: the smallest slice of the published vocabulary a chip may hold: an eighth.
#: So the vocabulary lies in at most 8 slices however many chips share a layer
MIN_VOCAB_SHARE = 8
#: every key that counts what one chip holds of a layer; any other key of
#: ``reduced`` but the depth keys is a width
SHARE_KEYS = (*EXPERT_KEYS, "vocab_size", "num_attention_heads", "num_key_value_heads")


def check_reduced(config_file: dict[str, Any], where: str) -> None:
    """What a configuration file may cut from its source (the guide's section
    4, whole), held for ``load_cell`` (a run) and ``test_config_file`` (the
    tests) alike.

    The deployment the cut stands for: each layer is shared by ``n`` chips,
    its routed experts expert-parallel over the ``n``, the embedding and the
    head in ``min(n, 8)`` slices of the vocabulary (each on ``n / 8`` chips
    where ``n > 8``: a chip holds at least an eighth); the layers left out lie
    on further chips as the stages of a pipeline, and the first stage holds
    ONE of the model's leading dense layers.

    ``reduced`` may hold the depth key and, only beside a ``share`` object,
    keys that count what THIS chip holds of a layer (``SHARE_KEYS``). The
    depth key is the name the published config gives its depth: the one of
    ``DEPTH_NAMES`` the file holds, never both, and ``reduced`` names that one:
    a depth that is cut stands in the file, so a file that holds neither name
    cuts no depth. Its number counts
    the layers the published config counts, whatever a layer holds (two
    attention sublayers, say: what a layer is made of is the program's).
    ``share`` is ``{"chips_per_layer": n, "published": {<key>: <published
    value>}}`` with one ``published`` entry for every such key and none else.
    What is held follows from ``n`` and the published count (one chip's share,
    not a number chosen to fit): for the experts and the heads, held times
    ``n`` is what was published, and at least ``MIN_EXPERTS_HELD`` routed
    experts are held; for ``vocab_size`` the divisor is ``n`` up to 8 chips
    and 8 beyond, where ``n`` is then a multiple of 8.

    ``reduced`` may also hold ``first_k_dense_replace``, a second DEPTH key
    (leading dense layers count once; it needs no ``share``): only beside
    the depth key, only with 1 held, only where the file's ``depth`` object
    ``{"published": {<depth key>: N, "first_k_dense_replace": K}}`` states the
    published counts (``K > 1``) under the file's own names, and only where at
    least ``MIN_LAYERS_AFTER_DENSE`` layers follow the one dense layer.
    ``depth`` stands in a file exactly when that key is reduced.

    Any other key is a width, and a width is never cut. The harness does
    nothing else with the depth key, ``share`` or ``depth``: the whole file
    goes to ``ModelConfig.from_hf_config``."""
    reduced = list(config_file.get("reduced", []))
    share = config_file.get("share")
    depth_key = _depth_key(config_file, reduced, where)
    # what the refusals call it: the file's own name, or both where it holds none
    depth_name = repr(depth_key) if depth_key else " or ".join(map(repr, DEPTH_NAMES))
    share_keys = [k for k in reduced if k not in (depth_key, DENSE_KEY)]
    for key in share_keys:
        if key in DEPTH_NAMES:
            raise SpecError(
                f"{where}: 'reduced' names {key!r}, which the file does not hold: its "
                f"depth key is {depth_key!r}, the name its published config counts its "
                "layers under")
        if key not in SHARE_KEYS:
            raise SpecError(
                f"{where}: 'reduced' names {key!r}: a width is never cut (only "
                f"{depth_name}, beside it {DENSE_KEY!r} held once, and, beside a "
                f"'share', the counts {list(SHARE_KEYS)})")
    _check_dense_once(config_file, reduced, where, depth_key, depth_name)
    if share is None:
        if share_keys:
            raise SpecError(
                f"{where}: 'reduced' names {share_keys}, one chip's share of a layer, "
                "and the file has no 'share' that states the deployment")
        return
    if not share_keys:
        raise SpecError(f"{where}: a 'share' and no key in 'reduced' that it cuts")
    if not isinstance(share, dict) or set(share) != {"chips_per_layer", "published"}:
        raise SpecError(
            f"{where}: 'share' is {{'chips_per_layer': n, 'published': {{key: value}}}}")
    chips, published = share["chips_per_layer"], share["published"]
    if not isinstance(chips, int) or isinstance(chips, bool) or chips < 2:
        raise SpecError(
            f"{where}: 'chips_per_layer' is {chips!r}; a layer is shared by 2 chips or "
            "more (one chip holds it whole and cuts nothing)")
    if not isinstance(published, dict) or set(published) != set(share_keys):
        raise SpecError(
            f"{where}: 'published' has {sorted(published)} and 'reduced' cuts "
            f"{sorted(share_keys)}: one published value for each, and none else")
    for key in share_keys:
        held = config_file.get(key)
        divisor, of_what = chips, "chips"
        if key == "vocab_size" and chips > MIN_VOCAB_SHARE:
            # more chips than slices: 8 slices, each on chips / 8 of the chips
            if chips % MIN_VOCAB_SHARE:
                raise SpecError(
                    f"{where}: vocab_size is cut and {chips} chips share a layer: past "
                    f"{MIN_VOCAB_SHARE} chips the vocabulary lies in {MIN_VOCAB_SHARE} "
                    f"slices, each on chips_per_layer / {MIN_VOCAB_SHARE} chips, so "
                    f"'chips_per_layer' is a multiple of {MIN_VOCAB_SHARE}")
            if _is_count(held) and held * chips == published[key]:
                raise SpecError(
                    f"{where}: vocab_size holds {held} of {published[key]}; a chip holds "
                    f"at least an eighth of the vocabulary")
            divisor, of_what = MIN_VOCAB_SHARE, f"slices over the {chips} chips"
        if not _is_count(held) or held * divisor != published[key]:
            raise SpecError(
                f"{where}: {key} holds {held!r}, and {divisor} {of_what} of that make "
                f"{held * divisor if _is_count(held) else None}, not the "
                f"published {published[key]!r}: the share is what one of the chips holds")
        if key in EXPERT_KEYS and held < MIN_EXPERTS_HELD:
            raise SpecError(
                f"{where}: {key} holds {held} routed experts; a chip holds at least "
                f"{MIN_EXPERTS_HELD}")


def _is_count(value: Any) -> bool:
    return isinstance(value, int) and not isinstance(value, bool) and value >= 0


def _depth_key(config_file: dict[str, Any], reduced: list[str], where: str) -> str | None:
    """The one of ``DEPTH_NAMES`` the file holds, or None where it holds neither
    and ``reduced`` names neither (``check_reduced`` says the rule)."""
    held = [k for k in DEPTH_NAMES if k in config_file]
    if len(held) > 1:
        raise SpecError(
            f"{where}: the file holds {held[0]!r} and {held[1]!r}: a published config "
            "counts its layers under one name, and the file keeps that one")
    if held:
        return held[0]
    for key in reduced:
        if key in DEPTH_NAMES:
            raise SpecError(
                f"{where}: 'reduced' names {key!r} and the file holds no {key!r}: a "
                "depth that is cut stands in the file, the number of layers held "
                "under the name its published config counts them under")
    return None


def _check_dense_once(config_file: dict[str, Any], reduced: list[str], where: str,
                      depth_key: str | None, depth_name: str) -> None:
    """``first_k_dense_replace`` in ``reduced``: leading dense layers count
    once (``check_reduced`` says the rule). ``depth_key`` is the file's own,
    ``depth_name`` what a refusal calls it."""
    depth = config_file.get("depth")
    if DENSE_KEY not in reduced:
        if depth is not None:
            raise SpecError(
                f"{where}: a 'depth' and no {DENSE_KEY!r} in 'reduced': it states the "
                "published counts for that cut alone")
        return
    if depth_key not in reduced:
        raise SpecError(
            f"{where}: 'reduced' names {DENSE_KEY!r} and not {depth_name}: leading "
            "dense layers count once only where depth is cut (a model at its whole "
            "depth keeps them all)")
    wanted = {"published": {depth_key: "N", DENSE_KEY: "K"}}
    stated = depth.get("published") if isinstance(depth, dict) else None
    if isinstance(stated, dict):
        for key in DEPTH_NAMES:
            if key != depth_key and key in stated:
                raise SpecError(
                    f"{where}: 'depth' publishes {key!r} and the file's depth key is "
                    f"{depth_key!r}: the published counts stand under the file's own "
                    f"names, {wanted}")
    if (not isinstance(depth, dict) or set(depth) != {"published"}
            or not isinstance(depth["published"], dict)
            or set(depth["published"]) != {depth_key, DENSE_KEY}):
        raise SpecError(
            f"{where}: 'reduced' names {DENSE_KEY!r} and the file's 'depth' is "
            f"{depth!r}, not {wanted}: the published counts it was cut from")
    published = depth["published"]
    layers, dense = config_file.get(depth_key), config_file.get(DENSE_KEY)
    if (not all(map(_is_count, (layers, dense, *published.values())))
            or published[DENSE_KEY] < 2 or published[depth_key] <= layers):
        raise SpecError(
            f"{where}: {depth_key} {layers!r} and {DENSE_KEY} {dense!r} are held and "
            f"'depth' publishes {published}: {DENSE_KEY!r} is reduced where the model "
            "has 2 leading dense layers or more, and more layers than are held")
    if dense == 0:
        raise SpecError(
            f"{where}: {DENSE_KEY} holds 0 of {published[DENSE_KEY]}: the first stage "
            "holds one of the leading dense layers")
    if dense != 1:
        raise SpecError(
            f"{where}: {DENSE_KEY} holds {dense} of {published[DENSE_KEY]}: leading dense "
            f"layers count once, so 1 is held (or all, with the key left out of 'reduced')")
    if layers < 1 + MIN_LAYERS_AFTER_DENSE:
        raise SpecError(
            f"{where}: {depth_key} holds {layers}: the one dense layer and {layers - 1} "
            f"after it; at least {MIN_LAYERS_AFTER_DENSE} layers follow the leading "
            "dense one")


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
    check_reduced(config, cfg_entry["file"])
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
