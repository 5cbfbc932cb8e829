"""The ``rollout``, ``learner`` and ``rl_step`` drivers over compressed
convolutional attention with an MLP router (ZAYA1-8B's layer at a test size),
end to end on the CPU through ``perfbench/run.py``: new files under
``tests/perfbench/cca_moe/`` and ``cca_moe_spec.py``, none of the other
families' edited. The checks there are the real ones: the engine's captured
log-probabilities, and one update of ``trainer.train_step``, against
``perfbench/reference_cca_moe.py``.

What PR 58 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import json
import os

import pytest

from cca_moe_spec import (
    CCA_MOE_DIR, CCA_MOE_METRICS, CELL, CELLS, JOINED, NOT_JOINED, cca_moe_benchmark,
    write_cca_moe_benchmark,
)
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "zaya1-8b-L20"
REAL_CELL = "zaya1-8b-L20.rollout-reasoning-cca"
#: the cells of the eight other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window", "glm-5-ep16-L5.rollout-longctx-indexed",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_cca_moe_benchmark(tmp_path_factory.mktemp("cca_moe"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 200 and 384 tokens in one segment of 384: each prompt's page
    chain, its partial last page and its three tails (the shorter row's taken
    184 tokens before the segment's end) handed to 4 candidates, then 24 decode
    steps from the slots' tails over their pages, one expert of 4 a token."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 3e-4  # bf16 pages and tails: rounding alone
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1


def test_the_learner_cell_updates_against_the_references_gradient(bench_file):
    """``trainer.train_step`` as the CLI builds it over rows of 160 tokens: one
    traced run."""
    line, notes = shared_cell(bench_file, "cca-moe-tiny.learner", 1)
    assert_contract(line, 1)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


def test_trainer_train_steps_with_the_paged_engine(bench_file):
    """``Trainer.train()`` with ``--engine_impl paged`` over this model through
    the ``rl_step`` driver: rollout (segmented prefill, pages and tails handed,
    decode), rewards, the update, the adapter pushed back to the engine, and
    the engine's log-probabilities under the TRAINED adapter against the
    reference. No flag, environment variable or configuration field chose
    anything."""
    line, notes = shared_cell(bench_file, "cca-moe-tiny.rl-paged", 0)
    assert_contract(line, 0)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] > 0
    assert notes["compiles"]["window"]["programs"] == 0


@pytest.mark.parametrize("control", ["tail_not_handed", "shift_dropped"])
def test_a_bent_mechanism_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell what this configuration is: with the prompts' tails
    not handed to the candidates, or every value head taken from the token
    itself, the same run reports ``correct: false``."""
    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import hybrid

    if control == "tail_not_handed":
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            *rest, mixer = prefill(*a, **kw)
            return (*rest, {**mixer, "cca_tail": tuple(map(jnp.zeros_like, mixer["cca_tail"]))})
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    else:
        late = hybrid._shifted  # the value's half is 16 wide here, [q~ | k~] 96
        monkeypatch.setattr(hybrid, "_shifted", lambda x, before, valid: (
            (x, late(x, before, valid)[1]) if x.shape[-1] == 16 else late(x, before, valid)))
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    # a tail reaches a candidate's first two tokens of 24: the max tells it
    assert notes["check"]["max_abs"] > 3 * 1.5e-3
    if control == "shift_dropped":
        assert notes["check"]["mean_abs"] > 10 * 3e-4


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 192, "kv_cache_quant": "none", "batch_size": 12,
        "num_candidates": 16, "max_prompt_tokens": 2048, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [512, 2048] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "one wave" in cell.traffic["fixed"] and "12 pairs an expert" in cell.traffic["fixed"]
    assert "98,304 tokens a round" in cell.traffic["measures"]
    assert "second pipeline stage" in cell.traffic["bypasses"]
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in CCA_MOE_METRICS} <= reported
    assert not ({*NOT_JOINED, "engine.admit_host_ms"}) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < check["logprob_max_abs_tol"]
    for said in ("seeds", "3 mantissa bits", "not handed", "segment's end", "shift",
                 "q-k mean", "temperature", "convolution", "not carried", "not multiplied",
                 "before the bias", "not tellable"):
        assert said in check["basis"].lower(), said
    # the configuration is the catalog's row at half its depth, whole otherwise
    spec.check_reduced(cell.config, "perfbench/configs/zaya1-8b-L20.json")
    assert cell.config["reduced"] == ["num_hidden_layers"] and "share" not in cell.config
    assert (cell.config["reference"], cell.config["counts"], cell.config["weight_rules"]) == (
        "reference_cca_moe", "cca_moe_counts", "zaya")


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-reasoning-cca", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("192 slots", "512 steps", "conv tail", "shifted value", "1 of 16"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in CCA_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    for name in (*NOT_JOINED, "engine.admit_host_ms"):
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name
    assert all(w["chips"] == 1 for w in real["workloads"])


@pytest.mark.parametrize("name, unit, source, layer, better", CCA_MOE_METRICS,
                         ids=[m[0] for m in CCA_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = cca_moe_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert held["reader"] == "trace_scopes"
    assert held["args"] == {"scope": "^model/cca_mix$", "of": "busy"}
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

    with open(os.path.join(REPO, "perfbench/scopes/cca_moe.json")) as f:
        held = json.load(f)
    assert held["names"] == [telemetry.MODEL_CCA_MIX] == ["model/cca_mix"]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_tail_is_what_the_slot_state_share_reads():
    """No ``engine.cca_tail_share``: the tails are the model's only row state,
    so the accepted gauge ``engine/slot_state_bytes`` is theirs alone."""
    from perfbench import spec

    held = spec.load_layer_metric(("perfbench",), "engine.slot_state_share")
    assert held["args"]["name"] == "engine/slot_state_bytes"
    assert not os.path.exists(
        os.path.join(REPO, "perfbench", "layer_metrics", "engine.cca_tail_share.json"))


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = cca_moe_benchmark()
    assert bench["paths"][0] == CCA_MOE_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, CCA_MOE_DIR, "traffic"))
    assert sorted(held) == ["cca-moe-learner.json", "cca-moe-rl-paged.json",
                            "cca-moe-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, CCA_MOE_DIR, sub))
