"""The ``rollout`` driver over a looped dense decoder (Ouro's layer at a test
size: one stack of two layers run three times a token, six cache layers, an
exit gate a pass), end to end on the CPU through ``perfbench/run.py``: new files
under ``tests/perfbench/looped/`` and ``looped_spec.py``, none of the other
families' edited. The check there is the real one: the engine's captured
log-probabilities against ``perfbench/reference_looped.py`` (the learner's loss
and gradient against it: ``tests/test_looped_model.py``).

What PR 68 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import json
import os

import pytest

from looped_spec import (
    CELL, JOINED, LOOPED_METRICS, NOT_JOINED, looped_benchmark, write_looped_benchmark,
)
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "ouro-2.6b-L8"
REAL_CELL = "ouro-2.6b-L8.rollout-reasoning-loop4"
DENSE_CELL = "qwen2.5-7b-L14.rollout-lockstep"
#: the cells of the eleven other configurations as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window", "glm-5-ep16-L5.rollout-longctx-indexed",
    "zaya1-8b-L20.rollout-reasoning-cca", "mimo-v2-flash-ep16-L7.rollout-longctx-sink-128",
    "longcat-flash-ep32-L4.rollout-reasoning-zero-256",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_looped_benchmark(tmp_path_factory.mktemp("looped"))


def test_the_rollout_cell_runs_end_to_end_traced(bench_file):
    """Prompts of 140 and 256 tokens (one and two full pages and a partial one)
    through six cache layers, each pool's pages aliased to 4 candidates, then 16
    lockstep decode steps of three passes over two layers."""
    line, notes = shared_cell(bench_file, CELL, 1)
    assert_contract(line, 1)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 16
    assert notes["check"]["mean_abs"] < 5e-4  # bf16 pages on the CPU
    assert notes["compiles"]["window"]["programs"] == 0
    metrics = line["metrics"]
    assert metrics["entry.window_compiles"]["value"] == 0
    assert notes["window"]["traced_units"] == 1
    # K and V of 4 heads x 16 in bf16, in each of 2 x 3 cache layers
    assert metrics["engine.cache_token_bytes"]["value"] == 6 * 2 * 4 * 16 * 2
    assert 1.0 < metrics["engine.exit_step_mean"]["value"] < 3.0
    assert "engine.admit_host_ms" not in metrics  # one wave admits nothing


def test_a_bent_program_is_not_correct(bench_file, monkeypatch):
    """The check can tell what this configuration is: with the attention's
    output norm dropped the same run reports ``correct: false`` (the other bent
    mechanisms are held by ``tests/test_looped_model.py``, through both engines)."""
    from distrl_llm_tpu.models import transformer

    sound = transformer._slice_layer
    monkeypatch.setattr(transformer, "_slice_layer", lambda stacked, i: {
        k: v for k, v in sound(stacked, i).items() if k != "attn_out_norm"})
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 5 * 5e-4


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 64, "kv_cache_quant": "none", "batch_size": 4,
        "num_candidates": 16, "max_prompt_tokens": 2048, "max_new_tokens": 384,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [512, 2048] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "one wave" in cell.traffic["measures"] and "24,576" in cell.traffic["measures"]
    assert "ALL 32 pools" in cell.traffic["measures"]
    assert "refill scheduler" in cell.traffic["bypasses"]
    assert "five further pipeline stages" in cell.traffic["bypasses"]
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in LOOPED_METRICS} <= reported
    assert not set(NOT_JOINED) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < check["logprob_max_abs_tol"] < 4
    for said in ("seeds", "3 mantissa bits", "cache layer of the pass before", "three passes",
                 "output norm dropped", "between passes", "pass 0's pools"):
        assert said in check["basis"].lower(), said
    assert cell.config["reference"] == "reference_looped"
    assert cell.config["counts"] == "looped_counts"
    assert "weight_rules" not in cell.config
    for key in ("out_norms", "final_norm_each_pass", "exit_gate", "exit_rule", "cache_layers",
                "positions", "adapters", "frozen", "unread_keys", "weights"):
        assert key in cell.config["assumed"], key


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-reasoning-loop4", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("64 slots", "one wave", "384 steps", "32 pools", "256 KiB a token"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in LOOPED_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    for name in NOT_JOINED:
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name
    # every list the dense family's rollout cell is in took this cell too, but
    # the refill scheduler's admission, which one wave never runs
    for name, metric in metrics.items():
        if DENSE_CELL in metric.get("workloads", ()) and name != "engine.admit_host_ms":
            assert REAL_CELL in metric["workloads"], name


def test_the_published_config_is_kept_letter_for_letter_but_the_depth():
    """The catalog row's ``config`` keys at their published values, the 48
    ``layer_types`` entries whole; ``num_hidden_layers`` 8."""
    with open(os.path.join(REPO, "perfbench", "configs", f"{REAL_CONFIG}.json")) as f:
        held = json.load(f)
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    cut = {"num_hidden_layers": 8}
    assert held["reduced"] == list(cut)
    for key, value in published.items():
        assert key in held and held[key] == cut.get(key, value), key
    assert "share" not in held and "depth" not in held


@pytest.mark.parametrize("name, unit, source, layer, better", LOOPED_METRICS,
                         ids=[m[0] for m in LOOPED_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = looped_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == {**entry, "workloads": [CELL]}
    assert REAL_CELL in real["workloads"]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))


def test_the_new_scope_is_the_programs_and_in_one_file():
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    with open(os.path.join(REPO, "perfbench/scopes/looped.json")) as f:
        held = json.load(f)
    assert held["names"] == [telemetry.MODEL_EXIT_GATE]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))
