"""The ``rollout`` and ``learner`` drivers over a power-retention model
(Brumby-14B-Base's layer kind at a test size), end to end on the CPU through
``perfbench/run.py``: new files under ``tests/perfbench/power/`` and
``power_spec.py``, none of ``tiny/``, ``sala/``, ``latent_moe/`` or
``delta_moe/`` edited. The checks there are the real ones: the engine's
captured log-probabilities, and one update of ``trainer.train_step``, against
``perfbench/reference_power_retention.py``.

What PR 40 added to the real benchmark is held here BY NAME, never by position
or by count (``perfbench/README.md``'s rule): the next PR appends after it.
"""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from delta_moe_spec import DELTA_MOE_METRICS
from latent_moe_spec import LATENT_MOE_METRICS
from power_spec import (
    CELL, CELLS, JOINED, NOT_JOINED, POWER_DIR, POWER_METRICS, power_benchmark,
    write_power_benchmark,
)
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from sala_spec import SALA_METRICS
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "brumby-14b-L4"
REAL_CELL = "brumby-14b-L4.rollout-retention-16k"
#: the cells of the four other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_power_benchmark(tmp_path_factory.mktemp("power"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 700 and 1,280 tokens in two segments of 640: the second
    segment reads the (S, z) the first carried; no layer keeps a page."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 2e-6
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1


def test_the_learner_cell_updates_against_the_references_gradient(bench_file):
    """``trainer.train_step`` as the CLI builds it, one traced run."""
    trace = 1
    line, notes = shared_cell(bench_file, "power-tiny.learner", trace)
    assert_contract(line, trace)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


@pytest.mark.parametrize("control", ["no_gate", "z_not_handed", "other_prompts_state"])
def test_a_dropped_mechanism_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell the mechanisms: with the gate dropped, the
    normaliser not handed to the candidates, or each prompt's candidates
    handed the OTHER prompt's state, the same run reports ``correct: false``
    (``tests/test_power_model.py`` holds every mechanism at 2e-5)."""
    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import hybrid

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    if control == "no_gate":
        monkeypatch.setattr(hybrid, "_power_decay", lambda h, p: jnp.zeros(
            h.shape[:-1] + (p["w_decay"].shape[-1],), jnp.float32))
    else:
        prefill = paged_engine._paged_prefill_hybrid
        change = (
            (lambda m: {**m, "power_z": tuple(jnp.zeros_like(x) for x in m["power_z"])})
            if control == "z_not_handed" else
            (lambda m: {**m, "power": tuple(jnp.roll(x, 1, axis=0) for x in m["power"])}))

        def patched(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, change(mixer)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 100 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 32, "kv_cache_quant": "none", "batch_size": 2,
        "num_candidates": 16, "max_prompt_tokens": 16384, "max_new_tokens": 256,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [8192, 16384] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "32 decode slots" in cell.traffic["fixed"] and "one wave" in cell.traffic["fixed"]
    assert [m["name"] for m in cell.end_to_end] == ["rollout_tok_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in POWER_METRICS} <= reported
    # no layer keeps a page: nothing writes K/V, and no paged kernel runs
    assert not set(NOT_JOINED) & reported
    # no other family's mixer, no expert layer, no refill admissions
    assert not {"engine.admit_host_ms", *(name for group in (
        SALA_METRICS, LATENT_MOE_METRICS, DELTA_MOE_METRICS) for name, *_ in group)} & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.1 < check["logprob_max_abs_tol"] < 2
    assert len({w["name"]: w for w in real_benchmark()["workloads"]}[REAL_CELL]["why"]) <= 200


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-retention-16k", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("8k and 16k", "no page", "4 of 40"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in POWER_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    # its own four are read in this cell alone of those that stand today
    for name in own:
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    # and it reads none of what needs a page, or another family's layers
    for name in (*NOT_JOINED, "engine.admit_host_ms", *(n for group in (
            SALA_METRICS, LATENT_MOE_METRICS, DELTA_MOE_METRICS) for n, *_ in group)):
        assert REAL_CELL not in metrics[name]["workloads"], name


@pytest.mark.parametrize("name, unit, source, layer, better", POWER_METRICS,
                         ids=[m[0] for m in POWER_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = power_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == entry
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))


def test_the_new_scope_is_the_programs_and_in_one_file():
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    with open(os.path.join(REPO, "perfbench/scopes/power_retention.json")) as f:
        held = json.load(f)
    assert held["names"] == ["model/power_attn"] == [telemetry.MODEL_POWER_ATTN]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_readers_read_hand_worked_counts_and_nothing_from_a_parent(monkeypatch):
    """The slots' share of the chip from the gauge (the accepted reader, a
    scale of 100 / 16e9); the two rooflines give None where a program has no
    such scope or spans (the parent), where the run was not traced, and for
    another family's counts."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import spec

    bench = power_benchmark()
    cell = spec.load_cell(bench, CELL)
    ctx = SimpleNamespace(cell=cell, tracer=None)
    metric = lambda name: spec.load_layer_metric(bench["paths"], name)
    share = metric("engine.slot_state_share")
    assert share["reader"] == "program_gauge" and share["args"] == {
        "name": "engine/slot_state_bytes", "scale": 6.25e-9}
    gauge = spec.load_module(bench["paths"], "readers", "program_gauge")
    # the cell's 32 slots x 4 layers x 34.08 MB: 27.3% of 16 GB
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"gauges": {
        "engine/slot_state_bytes": 32 * 4 * 34_080_768.0}})
    assert gauge.read({}, share["args"], ctx) == pytest.approx(27.2646144)
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"gauges": {}})
    assert gauge.read({}, share["args"], ctx) is None  # the parent files no such gauge
    reader = spec.load_module(bench["paths"], "readers", "power_work")
    model = dataclasses.asdict(PRESETS["tiny-power"])
    unit = {"steps_dispatched": 24, "prompt_lens": [40, 40], "gen_lens": [24, 24],
            "group_size": 2}
    traced = {"model": model, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              "rollout": {"weight_bytes": 2, "kv_bytes": 2}, "traced_units": [unit]}
    for name in ("kernel.power_step_roofline", "kernel.power_chunk_roofline"):
        args = metric(name)["args"]
        assert reader.read(traced, args, ctx) is None, name  # no trace to divide by
        assert reader.read(traced, args, None) is None
        assert reader.read({**traced, "traced_units": []}, args, ctx) is None
    # with the scope's seconds inside the spans: bytes and operations over time
    from perfbench import power_counts, trace_scopes

    monkeypatch.setattr(trace_scopes, "seconds_in_spans", lambda ctx, scope, span: 1e-3)
    step = reader.read(traced, metric("kernel.power_step_roofline")["args"], ctx)
    assert step == pytest.approx(
        100.0 * power_counts.power_state_bytes(model, [40, 40], [24, 24]) / 819e9 / 1e-3)
    chunk = reader.read(traced, metric("kernel.power_chunk_roofline")["args"], ctx)
    assert chunk == pytest.approx(  # ONE prompt of the group of 2 is prefilled
        100.0 * power_counts.power_chunk_flops(model, [40]) / 197e12 / 1e-3)
    dense = SimpleNamespace(cell=SimpleNamespace(paths=cell.paths, config={}), tracer=None)
    assert reader.read(traced, metric("kernel.power_step_roofline")["args"], dense) is None
    with pytest.raises(ValueError, match="cannot read"):
        reader.read(traced, {"what": "else", "scope": "x", "span": "y"}, ctx)


def test_the_configuration_file_holds_the_catalogs_numbers_and_every_assumption():
    with open(os.path.join(REPO, f"perfbench/configs/{REAL_CONFIG}.json")) as f:
        held = json.load(f)
    assert (held["model_type"], held["torch_dtype"]) == ("brumby", "bfloat16")
    assert held["num_hidden_layers"] == 4 and held["reduced"] == ["num_hidden_layers"]
    assert "share" not in held
    assert (held["hidden_size"], held["num_attention_heads"], held["num_key_value_heads"],
            held["head_dim"], held["intermediate_size"], held["vocab_size"]) == (
        5120, 40, 8, 128, 17408, 151936)
    for key in ("degree", "gate", "normaliser", "scale", "qk_norm", "rope", "state", "block",
                "adapter_targets", "frozen", "weights", "sizes_held"):
        assert held["assumed"][key], key
    assert "degree 2" in held["assumed"]["degree"] and "bias" in held["assumed"]["gate"]
    assert "1e-6" in held["assumed"]["normaliser"] and "INSIDE" in held["assumed"]["scale"]
    assert "float32" in held["assumed"]["state"] and "padded" in held["assumed"]["state"]
    assert "seeded weights only" in held["assumed"]["weights"]
    assert "10 pipeline stages" in held["deployment"] and "5.754 GB" in held["deployment"]
    assert (held["reference"], held["counts"], held["weight_rules"]) == (
        "reference_power_retention", "power_counts", "brumby")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Brumby-14B-Base")
        assert held["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if held.get(k, "absent") != v}
        assert differs == set(held["reduced"])


def test_the_familys_weight_rule_draws_a_state_that_remembers():
    """The rule file draws the leaf it names, and e^g spans about 0.99-0.9999:
    a state that forgets in two tokens would let the check pass a wrong one."""
    import jax
    import numpy as np

    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import weights

    bench = power_benchmark()
    rules = weights.load_rules(bench["paths"], {"weight_rules": "brumby"})
    assert [r["leaf"] for r in rules] == ["^layers/power/b_decay$"]
    params = weights.make_base_params(PRESETS["tiny-power"], "float32", 11, rules=rules)
    bias = np.asarray(params["layers"]["power"]["b_decay"])
    assert 4.6 <= bias.min() and bias.max() <= 9.2
    keep = 1.0 / (1.0 + np.exp(-bias))
    assert 0.989 < keep.min() and keep.max() < 0.99995
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        weights.make_base_params(PRESETS["tiny-power"], "float32", 11))


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = power_benchmark()
    assert bench["paths"][0] == POWER_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, POWER_DIR, "traffic"))
    assert sorted(held) == ["power-learner.json", "power-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, POWER_DIR, sub))
