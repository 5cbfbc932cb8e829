"""The ``rollout`` and ``learner`` drivers over a model with latent attention
and routed experts (Kimi-VL-A3B's layer kinds at a test size), end to end on
the CPU through ``perfbench/run.py``: new files under
``tests/perfbench/latent_moe/`` and ``latent_moe_spec.py``, none of ``tiny/`` or
``sala/`` edited. The checks there are the real ones: the engine's captured
log-probabilities, and one update of ``trainer.train_step``, against
``perfbench/reference_latent_moe.py``.
"""

import json
import os

import pytest

from latent_moe_spec import (
    CELL, CELLS, JOINED, LATENT_MOE_DIR, LATENT_MOE_METRICS, latent_moe_benchmark,
    write_latent_moe_benchmark,
)
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from sala_spec import SALA_METRICS
from tiny_spec import REPO, real_benchmark

REAL_CELL = "kimi-vl-a3b-L7.rollout-longctx-latent"
#: the cells of the two other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_latent_moe_benchmark(tmp_path_factory.mktemp("latent_moe"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 130-256 tokens in pages of 128 (the engine's default): the
    second page's queries attend over the first's latent rows."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1


@pytest.mark.parametrize("trace", [0, 1])
def test_the_learner_cell_updates_against_the_references_gradient(bench_file, trace):
    line, notes = shared_cell(bench_file, "latent-moe-tiny.learner", trace)
    assert_contract(line, trace)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


@pytest.mark.parametrize("control", ["top1", "no_shared"])
def test_a_dropped_mechanism_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell the mechanisms: with one expert a token in place of
    two, or the shared expert left out, the same run reports ``correct: false``."""
    from distrl_llm_tpu.models import hybrid, moe

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    if control == "top1":
        route = moe.route

        def one_expert(h, router, bias, cfg):
            idx, w = route(h, router, bias, cfg)
            return idx, w.at[:, 1:].set(0.0)

        monkeypatch.setattr(moe, "route", one_expert)
    else:
        monkeypatch.setattr(
            hybrid, "_mlp_half",
            lambda x, p, lora, **kw: x if "router" in p else hybrid_mlp(x, p, lora, **kw))
        from distrl_llm_tpu.models.transformer import _mlp_half as hybrid_mlp
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 10 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    # the numbers of rollout-longctx: two configurations run one traffic
    longctx = spec.load_cell(real_benchmark(), "minicpm-sala-L10.rollout-longctx").traffic
    for key in ("train_config", "prompt_tokens", "eos", "trace_units", "kind"):
        assert cell.traffic[key] == longctx[key], key
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 64, "kv_cache_quant": "none", "batch_size": 4,
        "num_candidates": 16, "max_prompt_tokens": 20480, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }
    assert "128" in cell.traffic["fixed"] and "DEFAULT_PAGE_SIZE" in cell.traffic["fixed"]
    assert [m["name"] for m in cell.end_to_end] == ["rollout_tok_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"engine.decode_step_ms", "engine.slot_occupancy",
            "engine.decode_bandwidth_util", "engine.snapshot_wait_ms",
            "kernel.sampler_share", "model.attn_proj_share", "model.mlp_share",
            "model.head_share", "engine.kv_write_share",
            "rollout.unscoped_share"} <= reported
    # absorbed decode is not launched as paged_attention_native
    assert not {"kernel.paged_attn_share", "paged_attn_roofline"} & reported
    # this family's own seven are declared for this cell, and SALA's six are not
    assert {name for name, *_ in LATENT_MOE_METRICS} <= reported
    assert not {name for name, *_ in SALA_METRICS} & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.1 < check["logprob_max_abs_tol"] < 3
    for control in ("3 mantissa bits", "latent pages", "top-5", "shared expert",
                    "routed_scaling_factor", "correction bias", "k_pe", "kv_b_proj"):
        assert control in check["basis"], control


def test_the_benchmark_gained_one_configuration_and_one_cell_at_the_end():
    """What PR 33 added, held by NAME: the next PR appends after it, so no
    position and no count of the real benchmark's lists is held here."""
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}["kimi-vl-a3b-L7"]
    assert config["reduced"] == ["num_hidden_layers"]
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-vl-a3b-L7", "rollout-longctx-latent", 1)
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in LATENT_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    # its own seven (PR 35 declared them) are read in none of the cells of
    # another family that stand today, each by name: a later cell that runs
    # the same expert layers appends its name after this one
    for name in own:
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    # and none of another family's: the paged kernel's, the refill path's, SALA's
    for name in ("kernel.paged_attn_share", "paged_attn_roofline", "engine.admit_host_ms",
                 *(name for name, *_ in SALA_METRICS)):
        assert REAL_CELL not in metrics[name]["workloads"], name


@pytest.mark.parametrize("name, unit, source, layer, better", LATENT_MOE_METRICS,
                         ids=[m[0] for m in LATENT_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    """Each of the seven resolves from ``perfbench/layer_metrics/`` to a reader
    under ``perfbench/readers/``, agrees with its entry in the rehearsal's
    benchmark and in the real one, and is reported in the rollout cell alone."""
    from perfbench import spec

    bench = latent_moe_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == entry


def test_this_familys_files_lie_under_perfbench_and_nowhere_else():
    """The seven files and the reader moved to ``perfbench/`` whole (PR 35):
    no copy stays beside the rehearsal's files."""
    for sub in ("layer_metrics", "readers"):
        assert not os.path.exists(os.path.join(REPO, LATENT_MOE_DIR, sub))
    for name, *_ in LATENT_MOE_METRICS:
        assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))
    assert os.path.isfile(os.path.join(REPO, "perfbench", "readers", "latent_moe_work.py"))


def test_the_reader_reads_hand_worked_counters_and_nothing_from_a_parent(monkeypatch):
    """The imbalance from the two counters (fullest x experts / pairs), None
    where a program has no such counters (the parent), where the run was not
    traced, and for another family's counts."""
    from types import SimpleNamespace

    from distrl_llm_tpu import telemetry
    from perfbench import spec

    bench = latent_moe_benchmark()
    reader = spec.load_module(bench["paths"], "readers", "latent_moe_work")
    cell = spec.load_cell(bench, CELL)
    ctx = SimpleNamespace(cell=cell, tracer=None)
    args = spec.load_layer_metric(bench["paths"], "engine.expert_load_imbalance")["args"]
    import dataclasses

    from distrl_llm_tpu.models.configs import PRESETS

    model = dataclasses.asdict(PRESETS["tiny-latent-moe"])
    observed = {"model": model}
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"counters": {
        "engine/moe_assignments": 768.0, "engine/moe_max_expert_load": 240.0}})
    assert reader.read(observed, args, ctx) == pytest.approx(240 * 8 / 768)
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"counters": {}})
    assert reader.read(observed, args, ctx) is None
    assert reader.read(observed, args, None) is None
    roofline = spec.load_layer_metric(bench["paths"], "kernel.moe_experts_roofline")["args"]
    traced = {"model": model, "peaks": {"hbm_bytes_per_s": 819e9},
              "rollout": {"weight_bytes": 2, "kv_bytes": 2},
              "traced_units": [{"steps_dispatched": 24, "prompt_lens": [40], "gen_lens": [24]}]}
    assert reader.read(traced, roofline, ctx) is None  # no trace: nothing to divide by
    dense = SimpleNamespace(cell=SimpleNamespace(paths=cell.paths, config={}), tracer=None)
    assert reader.read(traced, roofline, dense) is None  # roofline.py has no experts


def test_the_configuration_file_holds_the_catalogs_numbers_and_every_assumption():
    with open(os.path.join(REPO, "perfbench/configs/kimi-vl-a3b-L7.json")) as f:
        held = json.load(f)
    assert held["num_hidden_layers"] == 7 and held["reduced"] == ["num_hidden_layers"]
    assert (held["model_type"], held["torch_dtype"]) == ("deepseek_v3", "bfloat16")
    assert (held["hidden_size"], held["intermediate_size"], held["moe_intermediate_size"],
            held["vocab_size"]) == (2048, 11264, 1408, 163840)
    assert (held["n_routed_experts"], held["num_experts_per_tok"], held["n_shared_experts"],
            held["first_k_dense_replace"]) == (64, 6, 2, 1)
    assert (held["kv_lora_rank"], held["qk_nope_head_dim"], held["qk_rope_head_dim"],
            held["v_head_dim"], held["q_lora_rank"]) == (512, 128, 64, 128, None)
    for key in ("rope_pairs", "softmax_scale", "router_precision", "adapter_targets",
                "frozen", "weight_normalisation", "no_auxiliary_loss", "shared_expert",
                "kv_a_layernorm", "weights", "sizes_held"):
        assert held["assumed"][key]
    assert "1e-20" in held["assumed"]["weight_normalisation"]
    assert "text only" in held["deployment"] or "text" in held["deployment"]
    assert held["reference"] == "reference_latent_moe"
    assert held["counts"] == "latent_moe_counts"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-VL-A3B-Instruct")
        assert held["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if held.get(k, "absent") != v}
        assert differs == {"num_hidden_layers"}


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = latent_moe_benchmark()
    assert bench["paths"][0] == LATENT_MOE_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, LATENT_MOE_DIR, "traffic"))
    assert sorted(held) == ["latent-moe-learner.json", "latent-moe-rollout.json"]
