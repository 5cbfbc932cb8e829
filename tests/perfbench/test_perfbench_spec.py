"""``BENCHMARK.json`` against the contract it is written to, and every file it
names: what the driver would refuse before a single run, held here for free."""

import glob
import json
import os
import re

import pytest

from tiny_spec import REPO, real_benchmark

BENCH = real_benchmark()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def reported(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", CELLS)]


def under_paths(rel: str) -> bool:
    return any(rel == p or rel.startswith(p + "/") for p in BENCH["paths"])


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PLAIN_PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    # the command names no file of the repo outside the paths
    for arg in BENCH["command"]:
        if os.path.exists(os.path.join(REPO, arg)):
            assert under_paths(arg), arg
    assert 1 <= len(BENCH["configs"]) <= 24 and 2 <= len(CELLS) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128


def test_names_are_plain_and_used_once():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_file_under_the_paths_has_a_plain_name():
    for p in BENCH["paths"]:
        for path in glob.glob(os.path.join(REPO, p, "**"), recursive=True):
            rel = os.path.relpath(path, REPO)
            if "__pycache__" in rel or rel.endswith(".pyc"):
                continue
            assert PLAIN_PATH.match(rel), rel


def test_four_chip_cells_are_at_most_a_quarter_and_at_least_allowed_one():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    assert under_paths(config["file"]) and len(config["why"]) <= 200
    assert config["source"].startswith("https://")
    with open(os.path.join(REPO, config["file"]), encoding="utf-8") as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert held["reduced"] == config["reduced"]
    # depth, or one chip's share of a stated deployment; a width is never cut:
    # the rule is spec.check_reduced's, which a run (spec.load_cell) calls too
    from perfbench import spec

    spec.check_reduced(held, config["file"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    # the plain reference beside it
    assert os.path.isfile(os.path.join(REPO, "perfbench", held["reference"] + ".py"))


#: one of 8 chips that share each layer: 40 of 320 routed experts, an eighth
#: of a vocabulary of 196,608 (the sizes ISSUE 35 works out for its draw)
SHARED = {
    "num_hidden_layers": 4, "n_routed_experts": 40, "vocab_size": 24576,
    "num_experts_per_tok": 8, "hidden_size": 4096, "moe_intermediate_size": 1280,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "share": {"chips_per_layer": 8,
              "published": {"n_routed_experts": 320, "vocab_size": 196608}},
}


def shared(reduced=None, share="keep", **sizes):
    held = {**SHARED, **sizes}
    if reduced is not None:
        held["reduced"] = reduced
    if share is None:
        del held["share"]
    elif share != "keep":
        held["share"] = share
    return held


#: the cut ISSUE 53 works out for its draw, letter for letter: one of 16 chips
#: that share each layer holds 16 of 256 routed experts and an eighth of a
#: vocabulary of 154,880 (8 slices, each on 2 chips), the first pipeline stage
#: one of the 3 leading dense layers and 4 of the 75 layers that follow
CUT_WHOLE = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
    "vocab_size": 19360, "num_experts_per_tok": 8, "hidden_size": 6144,
    "moe_intermediate_size": 2048,
    "reduced": ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"],
    "share": {"chips_per_layer": 16,
              "published": {"n_routed_experts": 256, "vocab_size": 154880}},
    "depth": {"published": {"num_hidden_layers": 78, "first_k_dense_replace": 3}},
}
#: the dense layers counted once under depth alone: no share
DENSE_ONCE = {k: CUT_WHOLE[k] for k in ("num_hidden_layers", "first_k_dense_replace", "depth")}
DENSE_ONCE["reduced"] = ["num_hidden_layers", "first_k_dense_replace"]


def over(chips, experts, vocab=None, held_vocab=None):
    """``experts`` published over ``chips`` chips a layer, and the vocabulary
    ``vocab`` with ``held_vocab`` rows here (uncut where ``vocab`` is None)."""
    published = {"n_routed_experts": experts, **({} if vocab is None else {"vocab_size": vocab})}
    return shared(["num_hidden_layers", *published],
                  {"chips_per_layer": chips, "published": published},
                  n_routed_experts=experts // chips, vocab_size=held_vocab or 154880)


#: the cut ISSUE 64 works out for its draw, letter for letter: a published config
#: that names its depth ``num_layers``; one of 32 chips that share each layer
#: holds 16 of 512 routed experts and an eighth of a vocabulary of 131,072 (8
#: slices, each on 4 chips), 4 of the 28 layers, no leading dense one
NUM_LAYERS_CUT = {
    "num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
    "reduced": ["num_layers", "n_routed_experts", "vocab_size"],
    "share": {"chips_per_layer": 32,
              "published": {"n_routed_experts": 512, "vocab_size": 131072}},
}
#: ``DENSE_ONCE`` as a config that says ``num_layers`` would state it
DENSE_ONCE_NUM_LAYERS = {
    "num_layers": 5, "first_k_dense_replace": 1,
    "reduced": ["num_layers", "first_k_dense_replace"],
    "depth": {"published": {"num_layers": 78, "first_k_dense_replace": 3}},
}


REDUCED_CASES = {
    # accepted
    # (PR 64 gave this one the key it names: a depth that is cut stands in the file)
    "depth-alone": ({"num_hidden_layers": 14, "reduced": ["num_hidden_layers"]}, None),
    "nothing-cut": ({"reduced": []}, None),
    "experts-and-vocabulary-over-8-chips": (SHARED, None),
    "heads-as-a-share": (shared(
        ["num_attention_heads", "num_key_value_heads"],
        {"chips_per_layer": 8,
         "published": {"num_attention_heads": 64, "num_key_value_heads": 8}},
        num_attention_heads=8, num_key_value_heads=1), None),
    "num_experts-is-a-count-too": (shared(
        ["num_experts"], {"chips_per_layer": 4, "published": {"num_experts": 64}},
        num_experts=16), None),
    # refused, each by its own sentence
    "hidden_size": (shared([*SHARED["reduced"], "hidden_size"]), "'hidden_size': a width is never cut"),
    "moe_intermediate_size": (shared(["moe_intermediate_size"], None),
                              "'moe_intermediate_size': a width is never cut"),
    "num_experts_per_tok": (shared([*SHARED["reduced"], "num_experts_per_tok"]),
                            "'num_experts_per_tok': a width is never cut"),
    "head_dim-without-a-share": ({"reduced": ["head_dim"]}, "'head_dim': a width is never cut"),
    "a-share-key-without-share": (shared(share=None), "has no 'share'"),
    "share-without-a-share-key": (shared(["num_hidden_layers"]), "no key in 'reduced' that it cuts"),
    "held-times-chips-is-not-published": (shared(n_routed_experts=48), "not the published 320"),
    "four-experts-held": (shared(
        share={"chips_per_layer": 8, "published": {"n_routed_experts": 32, "vocab_size": 196608}},
        n_routed_experts=4), "holds 4 routed experts"),
    "a-sixteenth-of-the-vocabulary": (shared(
        share={"chips_per_layer": 16,
               "published": {"n_routed_experts": 320, "vocab_size": 196608}},
        n_routed_experts=20, vocab_size=12288), "an eighth of the vocabulary"),
    "one-chip-a-layer": (shared(
        share={"chips_per_layer": 1, "published": {"n_routed_experts": 40, "vocab_size": 24576}}),
        "'chips_per_layer' is 1"),
    "published-for-a-key-not-reduced": (shared(
        share={"chips_per_layer": 8, "published": {
            "n_routed_experts": 320, "vocab_size": 196608, "num_attention_heads": 64}}),
        "one published value for each, and none else"),
    "a-reduced-key-with-nothing-published": (shared(
        share={"chips_per_layer": 8, "published": {"n_routed_experts": 320}}),
        "one published value for each, and none else"),
    "a-share-with-another-key": (shared(
        share={**SHARED["share"], "stands_in_for": "the absent chips"}), "'share' is {"),
    # since PR 53: the vocabulary in at most 8 slices, leading dense layers once
    "cut-whole-16-chips-an-eighth-and-one-dense-layer": (CUT_WHOLE, None),
    "experts-over-16-chips-the-vocabulary-uncut": (over(16, 256), None),
    "experts-over-24-chips-an-eighth-of-the-vocabulary": (over(24, 384, 154880, 19360), None),
    "dense-layers-once-under-depth-alone": (DENSE_ONCE, None),
    "twelve-chips-and-a-vocabulary-cut": (
        over(12, 384, 154880, 19360), "'chips_per_layer' is a multiple of 8"),
    "a-sixteenth-of-the-vocabulary-beside-16-experts": (
        over(16, 256, 154880, 9680), "an eighth of the vocabulary"),
    "an-eighth-of-the-vocabulary-at-4-chips": (
        over(4, 256, 154880, 19360), "4 chips of that make 77440, not the published 154880"),
    "a-quarter-of-the-vocabulary-at-16-chips": (
        over(16, 256, 154880, 38720),
        "8 slices over the 16 chips of that make 309760, not the published 154880"),
    "no-dense-layer-held": ({**DENSE_ONCE, "first_k_dense_replace": 0},
                            "holds one of the leading dense layers"),
    "two-of-three-dense-layers-held": ({**DENSE_ONCE, "first_k_dense_replace": 2},
                                       "leading dense layers count once, so 1 is held"),
    "dense-layers-cut-with-depth-uncut": (
        {**DENSE_ONCE, "reduced": ["first_k_dense_replace"]},
        "count once only where depth is cut"),
    "dense-layers-cut-with-nothing-published": (
        {k: v for k, v in DENSE_ONCE.items() if k != "depth"},
        "the published counts it was cut from"),
    "one-dense-layer-and-three-after-it": ({**DENSE_ONCE, "num_hidden_layers": 4},
                                           "at least 4 layers follow the leading dense one"),
    "one-dense-layer-published": (
        {**DENSE_ONCE, "depth": {"published": {"num_hidden_layers": 78,
                                               "first_k_dense_replace": 1}}},
        "the model has 2 leading dense layers or more"),
    "a-depth-and-no-dense-key-reduced": (
        {**DENSE_ONCE, "reduced": ["num_hidden_layers"]},
        "a 'depth' and no 'first_k_dense_replace' in 'reduced'"),
}
#: since PR 64: the depth key is the ONE of two names the file holds. Cases of
#: the same test, in a dict of their own so that they are held by name
DEPTH_NAME_CASES = {
    "cut-under-num_layers-32-chips-an-eighth": (NUM_LAYERS_CUT, None),
    "depth-alone-under-num_layers": ({"num_layers": 4, "reduced": ["num_layers"]}, None),
    "num_layers-and-one-dense-layer-held": (DENSE_ONCE_NUM_LAYERS, None),
    "both-depth-names-in-one-file": (
        {**NUM_LAYERS_CUT, "num_hidden_layers": 4},
        "the file holds 'num_hidden_layers' and 'num_layers'"),
    "num_layers-reduced-in-a-num_hidden_layers-file": (
        shared(["num_layers", "n_routed_experts", "vocab_size"]),
        "'reduced' names 'num_layers', which the file does not hold"),
    "num_hidden_layers-reduced-in-a-num_layers-file": (
        {**NUM_LAYERS_CUT, "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"]},
        "'reduced' names 'num_hidden_layers', which the file does not hold"),
    "depth-published-under-the-other-name": (
        {**DENSE_ONCE_NUM_LAYERS, "depth": DENSE_ONCE["depth"]},
        "'depth' publishes 'num_hidden_layers' and the file's depth key is 'num_layers'"),
    "depth-reduced-in-a-file-that-holds-none": (
        {"reduced": ["num_hidden_layers"]},
        "'reduced' names 'num_hidden_layers' and the file holds no 'num_hidden_layers'"),
    "num_layers-reduced-in-a-file-that-holds-none": (
        {k: v for k, v in NUM_LAYERS_CUT.items() if k != "num_layers"},
        "'reduced' names 'num_layers' and the file holds no 'num_layers'"),
    "n_layer-is-still-a-width": (
        {"n_layer": 4, "reduced": ["n_layer"]},
        "'n_layer': a width is never cut (only 'num_hidden_layers' or 'num_layers', beside"),
    "a-width-beside-num_layers": (
        {**NUM_LAYERS_CUT, "reduced": [*NUM_LAYERS_CUT["reduced"], "expert_ffn_hidden_size"]},
        "'expert_ffn_hidden_size': a width is never cut (only 'num_layers'"),
    "num_layers-with-three-after-the-dense-one": (
        {**DENSE_ONCE_NUM_LAYERS, "num_layers": 4},
        "num_layers holds 4: the one dense layer and 3 after it"),
    "dense-layers-cut-with-num_layers-uncut": (
        {**DENSE_ONCE_NUM_LAYERS, "reduced": ["first_k_dense_replace"]},
        "'first_k_dense_replace' and not 'num_layers'"),
}
REDUCED_CASES.update(DEPTH_NAME_CASES)


@pytest.mark.parametrize("held, refusal", REDUCED_CASES.values(), ids=REDUCED_CASES)
def test_reduced_names_depth_or_one_chips_share_and_never_a_width(held, refusal):
    """``spec.check_reduced``: what the guide's section 4 lets a configuration
    cut. Every refusal says which rule, by a sentence of its own."""
    from perfbench import spec

    if refusal is None:
        assert spec.check_reduced(held, "a.json") is None
    else:
        with pytest.raises(spec.SpecError, match=re.escape(refusal)) as said:
            spec.check_reduced(held, "a.json")
        assert str(said.value).startswith("a.json: ")


def test_each_refusal_about_the_depths_name_is_met_by_its_own_case_alone():
    """The parent refuses ``NUM_LAYERS_CUT`` ("'num_layers': a width is never
    cut"); what PR 64 refuses instead, it refuses in words no other case meets."""
    from perfbench import spec

    def said(case):
        try:
            spec.check_reduced(REDUCED_CASES[case][0], "a.json")
        except spec.SpecError as e:
            return str(e)
        return ""

    everything = {case: said(case) for case in REDUCED_CASES}
    for case, (_, refusal) in DEPTH_NAME_CASES.items():
        if refusal is not None:
            assert [c for c, text in everything.items() if refusal in text] == [case]
    assert not any("'num_layers': a width" in text for text in everything.values())


@pytest.mark.parametrize("case", ["experts-and-vocabulary-over-8-chips", "hidden_size",
                                  "held-times-chips-is-not-published",
                                  "cut-whole-16-chips-an-eighth-and-one-dense-layer",
                                  "two-of-three-dense-layers-held",
                                  "both-depth-names-in-one-file"])
def test_a_run_refuses_what_the_test_refuses(tmp_path, case):
    """``spec.load_cell`` goes through the same function: a configuration the
    tests would refuse never reaches a driver."""
    from perfbench import spec
    from tiny_spec import tiny_benchmark

    held, refusal = REDUCED_CASES[case]
    bench = tiny_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "tiny")
    with open(os.path.join(REPO, entry["file"]), encoding="utf-8") as f:
        tiny = json.load(f)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps({**tiny, **held}), encoding="utf-8")
    entry["file"] = str(path)
    if refusal is None:
        config = spec.load_cell(bench, "tiny.learner").config
        assert (config["share"], config.get("depth")) == (held["share"], held.get("depth"))
    else:
        with pytest.raises(spec.SpecError, match=re.escape(refusal)):
            spec.load_cell(bench, "tiny.learner")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_names_files_that_exist_and_reports_what_it_must(cell):
    from perfbench import spec

    assert len(cell["why"]) <= 200
    loaded = spec.load_cell(BENCH, cell["name"])
    kind = loaded.traffic["kind"]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "drivers", f"{kind}.py"))
    e2e = [m["name"] for m in reported(BENCH["end_to_end"], cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = reported(BENCH["per_layer"], cell["name"])
    assert layer
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in e2e for m in layer), [
        (m["name"], m["moves"]) for m in layer if m["moves"] not in e2e]


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert metric["source"] in ("host_clock", "device_trace")
    assert metric["better"] in ("lower", "higher")
    assert 0.01 <= metric["bound"] <= 0.1
    if metric["name"] == "setup_s":
        assert metric["bound"] == 0.1 and "workloads" not in metric
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_file_and_its_reader(metric):
    from perfbench import spec

    held = spec.load_layer_metric(BENCH["paths"], metric["name"])
    for key in ("unit", "better", "source", "layer", "moves"):
        assert held[key] == metric[key], (metric["name"], key)
    # one file of that name anywhere under the paths: a metric that moved left no copy
    copies = [path for p in BENCH["paths"] for path in glob.glob(
        os.path.join(REPO, p, "**", "layer_metrics", metric["name"] + ".json"), recursive=True)]
    assert len(set(copies)) == 1, copies
    assert metric["source"] in SOURCES
    assert metric["moves"] in [m["name"] for m in BENCH["end_to_end"]]
    assert "bound" not in metric
    reader = spec.load_module(BENCH["paths"], "readers", held["reader"])
    assert callable(reader.read)
    # a reader that finds nothing to read returns nothing
    assert reader.read({}, held.get("args", {}), None) is None
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    if "regex" in held.get("args", {}):
        re.compile(held["args"]["regex"])


def traffic_files():
    """Every traffic file under the real benchmark's paths (the tiny ones too)."""
    return sorted(
        path for p in BENCH["paths"]
        for path in glob.glob(os.path.join(REPO, p, "**", "traffic", "*.json"), recursive=True)
    )


def held_check(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f).get("check")


@pytest.mark.parametrize("path", traffic_files(), ids=lambda p: os.path.basename(p)[:-5])
def test_a_check_block_carries_the_runs_it_came_from(path):
    """A traffic file's ``check`` sets the tolerances ``correct`` is decided
    by; each is measured, and says from which runs (``basis``)."""
    check = held_check(path)
    if check is None:
        return
    assert isinstance(check.get("basis"), str) and len(check["basis"]) >= 40, path
    known = {"logprob_mean_abs_tol", "logprob_max_abs_tol", "loss_scaled_tol",
             "grad_sign_mass_tol", "basis"}
    assert set(check) <= known, set(check) - known
    assert all(isinstance(check[k], float) for k in set(check) - {"basis"})


def test_checks_are_where_they_are_expected():
    held = {os.path.basename(p)[:-5] for p in traffic_files() if held_check(p)}
    assert {"rollout-lockstep", "tiny-learner-checked"} <= held
    assert "learner-1k" not in held  # it reads correct.py's two constants


@pytest.mark.parametrize("check, refused", [
    ({"loss_scaled_tol": 1e-3}, True), ({"loss_scaled_tol": 1e-3, "basis": ""}, True),
    ({"loss_scaled_tol": 1e-3, "basis": "nine runs, PR n"}, False), (None, False),
])
def test_a_check_without_basis_is_refused_with_the_cell(tmp_path, check, refused):
    from perfbench import spec
    from tiny_spec import tiny_benchmark

    bench = tiny_benchmark()
    traffic = spec.load_cell(bench, "tiny.learner").traffic
    os.makedirs(tmp_path / "traffic")
    with open(tmp_path / "traffic" / "tiny-learner.json", "w", encoding="utf-8") as f:
        json.dump({**traffic, **({} if check is None else {"check": check})}, f)
    bench["paths"] = [str(tmp_path), *bench["paths"]]  # found first
    if refused:
        with pytest.raises(spec.SpecError, match="basis"):
            spec.load_cell(bench, "tiny.learner")
    else:
        assert spec.load_cell(bench, "tiny.learner").traffic.get("check") == check


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configurations_counts_module_is_beside_its_reference(config):
    """``counts`` is optional and names a module under the paths, found as the
    reference is; with none, ``roofline`` (the dense GQA decoder's)."""
    from perfbench import spec

    with open(os.path.join(REPO, config["file"]), encoding="utf-8") as f:
        held = json.load(f)
    counts = spec.load_module(BENCH["paths"], "", held.get("counts", "roofline"))
    for name in ("train_flops_per_token", "decode_weight_bytes", "kv_read_bytes"):
        assert callable(getattr(counts, name)), name


def test_traffic_is_data():
    for path in glob.glob(os.path.join(REPO, "perfbench", "traffic", "*")):
        assert path.endswith((".json", ".jsonl", ".toml", ".txt", ".csv")), path


def test_no_file_waits_for_a_cell():
    """Every traffic mix and per-layer metric under ``perfbench/`` is named by
    ``BENCHMARK.json``: a file for a cell that is not there comes with the cell."""
    def held(sub):
        return {os.path.basename(p)[:-5]
                for p in glob.glob(os.path.join(REPO, "perfbench", sub, "*.json"))}

    assert held("traffic") == {w["traffic"] for w in BENCH["workloads"]}
    assert held("layer_metrics") == {m["name"] for m in BENCH["per_layer"]}
