"""The ``rollout``, ``learner`` and ``rl_step`` drivers over a state-space model
(AI21-Jamba2-3B's layer kinds at a test size), end to end on the CPU through
``perfbench/run.py``: new files under ``tests/perfbench/jamba/`` and
``jamba_spec.py``, none of the other families' edited. The checks there are the
real ones: the engine's captured log-probabilities, and one update of
``trainer.train_step``, against ``perfbench/reference_jamba.py``.

What PR 44 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from delta_moe_spec import DELTA_MOE_METRICS
from jamba_spec import (
    CELL, CELLS, JAMBA_DIR, JAMBA_METRICS, JOINED, NOT_JOINED, jamba_benchmark,
    write_jamba_benchmark,
)
from latent_moe_spec import LATENT_MOE_METRICS
from power_spec import POWER_METRICS
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from sala_spec import SALA_METRICS
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "jamba2-3b"
REAL_CELL = "jamba2-3b.rollout-wide-480"
#: the cells of the five other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k",
)
#: the metrics of the other families' own mixers and experts, which this cell
#: does not report (Solar's convolution and Brumby's slot share it does)
OTHERS_OWN = {name for group in (SALA_METRICS, LATENT_MOE_METRICS, DELTA_MOE_METRICS,
                                 POWER_METRICS) for name, *_ in group} - set(JOINED)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_jamba_benchmark(tmp_path_factory.mktemp("jamba"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 700 and 1,280 tokens in two segments of 640: the second
    segment's scan starts from the carried state, its convolution from the
    carried window, its attention layer reads the first segment's pages."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 5e-4  # bf16 pages and windows
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1
        # every metric the cell declares in a unit a CPU may report is there
        from perfbench import spec

        cell = spec.load_cell(jamba_benchmark(), CELL)
        counted = {m["name"] for m in cell.per_layer if spec.load_layer_metric(
            cell.paths, m["name"])["unit"] == "count"}
        assert counted and counted <= set(line["metrics"])


def test_the_learner_cell_updates_against_the_references_gradient(bench_file):
    """``trainer.train_step`` as the CLI builds it, one traced run."""
    trace = 1
    line, notes = shared_cell(bench_file, "jamba-tiny.learner", trace)
    assert_contract(line, trace)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


def test_trainer_train_steps_with_the_paged_engine(bench_file):
    """``Trainer.train()`` with ``--engine_impl paged`` over this model through
    the ``rl_step`` driver: rollout (segmented prefill, the hand-off, decode),
    rewards, the update, the adapter pushed back to the engine, and the
    engine's log-probabilities under the TRAINED adapter against the reference.
    No flag, environment variable or configuration field chose anything."""
    line, notes = shared_cell(bench_file, "jamba-tiny.rl-paged", 0)
    assert_contract(line, 0)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] > 0
    assert notes["compiles"]["window"]["programs"] == 0


@pytest.mark.parametrize("control", ["no_inner_norms", "window_not_handed",
                                     "other_prompts_state"])
def test_a_dropped_mechanism_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell the mechanisms: with the three inner norms dropped,
    the window not handed to the candidates, or each prompt's candidates
    handed the OTHER prompt's state, the same run reports ``correct: false``
    (``tests/test_jamba_model.py`` holds every mechanism at 2e-5)."""
    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import hybrid

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    if control == "no_inner_norms":
        norm = hybrid.rms_norm
        monkeypatch.setattr(hybrid, "rms_norm", lambda x, w, eps, **kw: (
            x if w.shape[-1] < 32 else norm(x, w, eps, **kw)))
    else:
        prefill = paged_engine._paged_prefill_hybrid
        change = (
            (lambda m: {**m, "conv": tuple(jnp.zeros_like(x) for x in m["conv"])})
            if control == "window_not_handed" else
            (lambda m: {**m, "ssm": tuple(jnp.roll(x, 1, axis=0) for x in m["ssm"])}))

        def patched(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, change(mixer)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 10 * 5e-4 > 10 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 480, "kv_cache_quant": "none", "batch_size": 30,
        "num_candidates": 16, "max_prompt_tokens": 2048, "max_new_tokens": 384,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [512, 2048] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "480 decode slots" in cell.traffic["fixed"] and "one wave" in cell.traffic["fixed"]
    assert "65,536" in cell.traffic["fixed"]
    assert set(cell.traffic["reduced"]) == {"answers", "prompts"}
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in JAMBA_METRICS} <= reported
    assert not set(NOT_JOINED) & reported
    # no other family's mixer, no expert layer, no refill admissions
    assert not ({"engine.admit_host_ms"} | OTHERS_OWN) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.1 < check["logprob_max_abs_tol"] < 2
    for control in ("window not handed", "wrong prompt", "inner norms", "b_conv", "b_dt",
                    "D skip", "gate", "A_log", "RoPE", "10 + 10", "3 mantissa bits"):
        assert control in check["basis"], control


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == []
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-wide-480", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("480 slots", "state-space", "one KV head", "tied head", "full depth"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in JAMBA_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    # its own three are read in this cell alone of those that stand today
    for name in own:
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    # and not what divides the whole cache by the paged kernel's time, nor another family's layers
    for name in (*NOT_JOINED, "engine.admit_host_ms", *OTHERS_OWN):
        assert REAL_CELL not in metrics[name]["workloads"], name
    # the four entry.* hold for every cell: they have no list
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name


@pytest.mark.parametrize("name, unit, source, layer, better", JAMBA_METRICS,
                         ids=[m[0] for m in JAMBA_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = jamba_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == {**entry, "workloads": [CELL]}
    assert "jamba2-3b.rollout-wide-480" in real["workloads"]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))


def test_the_new_scope_is_the_programs_and_in_one_file():
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    with open(os.path.join(REPO, "perfbench/scopes/ssm.json")) as f:
        held = json.load(f)
    assert held["names"] == ["model/ssm"] == [telemetry.MODEL_SSM]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_readers_read_hand_worked_counts_and_nothing_from_a_parent(monkeypatch):
    """The two rooflines give None where a program has no such scope or spans
    (the parent), where the run was not traced, and for another family's
    counts; with the scope's seconds they are the counts' bytes over time."""
    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import spec, ssm_counts, trace_scopes

    bench = jamba_benchmark()
    cell = spec.load_cell(bench, CELL)
    ctx = SimpleNamespace(cell=cell, tracer=None)
    metric = lambda name: spec.load_layer_metric(bench["paths"], name)
    share = metric("model.ssm_share")
    assert share["reader"] == "trace_scopes" and share["args"] == {
        "scope": "^model/ssm$", "of": "busy"}
    reader = spec.load_module(bench["paths"], "readers", "ssm_work")
    model = dataclasses.asdict(PRESETS["tiny-jamba"])
    unit = {"steps_dispatched": 24, "prompt_lens": [40, 40], "gen_lens": [24, 24],
            "group_size": 2}
    traced = {"model": model, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              "rollout": {"weight_bytes": 2, "kv_bytes": 2}, "traced_units": [unit]}
    for name in ("kernel.ssm_step_roofline", "kernel.ssm_scan_roofline"):
        args = metric(name)["args"]
        assert reader.read(traced, args, ctx) is None, name  # no trace to divide by
        assert reader.read(traced, args, None) is None
        assert reader.read({**traced, "traced_units": []}, args, ctx) is None
    monkeypatch.setattr(trace_scopes, "seconds_in_spans", lambda ctx, scope, span: 1e-3)
    step = reader.read(traced, metric("kernel.ssm_step_roofline")["args"], ctx)
    assert step == pytest.approx(
        100.0 * ssm_counts.ssm_state_bytes(model, [40, 40], [24, 24]) / 819e9 / 1e-3)
    scan = reader.read(traced, metric("kernel.ssm_scan_roofline")["args"], ctx)
    assert scan == pytest.approx(  # ONE prompt of the group of 2 is prefilled
        100.0 * ssm_counts.ssm_scan_bytes(model, [40]) / 819e9 / 1e-3)
    dense = SimpleNamespace(cell=SimpleNamespace(paths=cell.paths, config={}), tracer=None)
    assert reader.read(traced, metric("kernel.ssm_step_roofline")["args"], dense) is None
    with pytest.raises(ValueError, match="cannot read"):
        reader.read(traced, {"what": "else", "scope": "x", "span": "y"}, ctx)


def test_the_configuration_file_holds_the_catalogs_numbers_and_every_assumption():
    with open(os.path.join(REPO, f"perfbench/configs/{REAL_CONFIG}.json")) as f:
        held = json.load(f)
    assert (held["model_type"], held["torch_dtype"]) == ("jamba", "bfloat16")
    assert held["num_hidden_layers"] == 28 and held["reduced"] == [] and "share" not in held
    for key in ("layer_order", "head_dim", "inner_norms", "split_orders", "dt", "conv", "state",
                "padding", "adapter_targets", "frozen", "unread_keys", "weights", "sizes_held"):
        assert held["assumed"][key], key
    assert "i % attn_layer_period == attn_layer_offset" in held["assumed"]["layer_order"]
    assert "2,560 / num_attention_heads 20" in held["assumed"]["head_dim"]
    assert "u (what the convolution reads) first" in held["assumed"]["split_orders"]
    assert "before the softplus" in held["assumed"]["dt"]
    for key in ("num_logits_to_keep", "use_mamba_kernels", "sliding_window"):
        assert key in held["assumed"]["unread_keys"], key
    assert "seeded weights only" in held["assumed"]["weights"]
    assert "whole on one chip" in held["deployment"] and "6.06 GB" in held["deployment"]
    assert "3,029,337,472" in held["deployment"]
    assert (held["reference"], held["counts"], held["weight_rules"]) == (
        "reference_jamba", "ssm_counts", "jamba")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
        assert held["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if held.get(k, "absent") != v} == set()


def test_the_familys_weight_rules_draw_states_that_forget_at_many_rates():
    """The rule file draws the leaves it names: steps between 0.001 and 0.1,
    A between -1 and -16, D = 1, a convolution of order 1; drawn by the base
    rule every channel would forget at one rate."""
    import jax
    import numpy as np

    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import weights

    bench = jamba_benchmark()
    rules = weights.load_rules(bench["paths"], {"weight_rules": "jamba"})
    assert {r["leaf"] for r in rules} == {
        "^layers/mamba/ssm_a_log$", "^layers/mamba/ssm_d$", "^layers/mamba/b_dt$",
        "^layers/mamba/conv$"}
    cfg = PRESETS["tiny-jamba"]
    params = weights.make_base_params(cfg, "float32", 11, rules=rules)
    layer = {k: np.asarray(v) for k, v in params["layers"]["mamba"].items()}
    step = np.log1p(np.exp(layer["b_dt"]))
    assert 0.00099 < step.min() and step.max() < 0.1001
    assert np.median(step) < 0.02  # log-uniform, not uniform: half the steps under 0.01
    a = np.exp(layer["ssm_a_log"])
    assert 1.0 <= a.min() and a.max() <= 16.01
    keep = np.exp(-step[:, None, :] * a)  # a state entry's decay a token
    assert keep.min() < 0.5 and keep.max() > 0.998
    assert (layer["ssm_d"] == 1.0).all() and (layer["ssm_dt_norm"] == 1.0).all()
    assert 0.4 < layer["conv"].std() < 0.6 and 0.15 < layer["b_conv"].std() < 0.35
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        weights.make_base_params(cfg, "float32", 11))


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = jamba_benchmark()
    assert bench["paths"][0] == JAMBA_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, JAMBA_DIR, "traffic"))
    assert sorted(held) == ["jamba-learner.json", "jamba-rl-paged.json", "jamba-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, JAMBA_DIR, sub))
