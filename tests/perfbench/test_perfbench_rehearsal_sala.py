"""The ``rollout`` driver over a model whose layers differ in kind
(MiniCPM-SALA's two mixers at a test size), end to end on the CPU through
``perfbench/run.py``: new files under ``tests/perfbench/sala/`` and
``sala_spec.py``, none of ``tiny/`` edited. The check there is the real one,
the engine's captured log-probabilities against ``perfbench/reference_sala.py``.
(The learner's update and ``Trainer.train()``: the two files beside this one.)
"""

import json
import os

import pytest

from rehearsal_helpers import assert_contract, run_cell, shared_cell
from sala_spec import CELL, SALA_DIR, SALA_METRICS, sala_benchmark, write_sala_benchmark
from tiny_spec import REPO, real_benchmark

#: the cells of the two other families as they stand beside the real cell, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "kimi-vl-a3b-L7.rollout-longctx-latent",
)


@pytest.fixture(scope="module")
def sala_file(tmp_path_factory):
    return write_sala_benchmark(tmp_path_factory.mktemp("sala"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(sala_file, trace):
    line, notes = shared_cell(sala_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1


def test_a_wrong_block_choice_is_not_correct(sala_file, monkeypatch):
    """The check can tell the mechanism: with dense attention in place of the
    choice of blocks the same run reports ``correct: false``."""
    from distrl_llm_tpu.ops import sparse_attention

    sound = shared_cell(sala_file, CELL, 0)[1]["check"]["mean_abs"]
    monkeypatch.setattr(
        sparse_attention, "choose_blocks",
        lambda q, pooled, q_pos, cfg, n_blocks: (
            sparse_attention.jnp.arange(n_blocks)
            <= q_pos[:, :, None, None] // cfg.sparse_block_size
        ) & sparse_attention.jnp.ones((1, 1, pooled.shape[2], 1), bool))
    line, notes = run_cell(sala_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 10 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), "minicpm-sala-L10.rollout-longctx")
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 64, "kv_cache_quant": "none", "batch_size": 4,
        "num_candidates": 16, "max_prompt_tokens": 20480, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [10240, 20480]
    assert cell.traffic["eos"] == "never" and cell.traffic["trace_units"] == 1
    assert [m["name"] for m in cell.end_to_end] == ["rollout_tok_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"engine.decode_step_ms", "engine.slot_occupancy",
            "engine.decode_bandwidth_util", "engine.snapshot_wait_ms",
            "kernel.sampler_share", "model.attn_proj_share", "model.mlp_share",
            "model.head_share", "engine.kv_write_share",
            "rollout.unscoped_share"} <= reported
    # the paged-attention kernel does not run in it (the sparse layers gather)
    assert not {"kernel.paged_attn_share", "paged_attn_roofline"} & reported
    # this family's own six are declared for this cell (PR 35), and for none of
    # the cells of another family that stand today, each by name: a later cell
    # that runs the same mixers appends its name after this one
    own = {name for name, *_ in SALA_METRICS}
    assert own <= reported
    for m in cell.per_layer:
        if m["name"] in own:
            assert not set(OTHER_FAMILIES_CELLS) & set(m["workloads"]), m["name"]


@pytest.mark.parametrize("name, source, layer, better", SALA_METRICS,
                         ids=[m[0] for m in SALA_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, source, layer, better):
    """Each of the six resolves from ``perfbench/layer_metrics/`` to a reader
    under ``perfbench/readers/``, agrees with its entry in the rehearsal's
    benchmark and in the real one, and is reported in the rollout cell alone."""
    from perfbench import spec

    bench = sala_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == ("%", "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == entry


def test_this_familys_files_lie_under_perfbench_and_nowhere_else():
    """The six files and the reader moved to ``perfbench/`` whole (PR 35): no
    copy stays beside the rehearsal's files."""
    for sub in ("layer_metrics", "readers"):
        assert not os.path.exists(os.path.join(REPO, SALA_DIR, sub))
    for name, *_ in SALA_METRICS:
        assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))
    assert os.path.isfile(os.path.join(REPO, "perfbench", "readers", "sala_work.py"))


def test_the_configuration_file_holds_the_catalogs_numbers_and_every_assumption():
    with open(os.path.join(REPO, "perfbench/configs/minicpm-sala-L10.json")) as f:
        held = json.load(f)
    assert held["num_hidden_layers"] == 10 and held["reduced"] == ["num_hidden_layers"]
    assert len(held["mixer_types"]) == 32  # the pattern is cut by depth alone
    assert [i for i, m in enumerate(held["mixer_types"]) if m == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert (held["hidden_size"], held["intermediate_size"], held["vocab_size"]) == (
        4096, 16384, 73448)
    assert (held["scale_emb"], held["scale_depth"], held["dim_model_base"]) == (12, 1.4, 256)
    for key in ("residual_scale", "mup_denominator", "lightning_projections", "qk_norm",
                "lightning_decay", "use_output_norm", "output_gate", "sparse_config",
                "window_blocks", "dense_len", "scoring_stages", "ties", "weights"):
        assert held["assumed"][key]
    assert held["reference"] == "reference_sala" and held["counts"] == "sala_counts"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
        assert held["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if held.get(k) != v}
        assert differs == {"num_hidden_layers"}


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = sala_benchmark()
    assert bench["paths"][0] == SALA_DIR and len(bench["workloads"]) == 3
    held = os.listdir(os.path.join(REPO, SALA_DIR, "traffic"))
    assert sorted(held) == ["sala-learner.json", "sala-rl-paged.json", "sala-rollout.json"]
