"""The ``rollout``, ``learner`` and ``rl_step`` drivers over a window model with
routed experts (K-EXAONE-236B-A23B's layer kinds and its share at a test size),
end to end on the CPU through ``perfbench/run.py``: new files under
``tests/perfbench/window_moe/`` and ``window_moe_spec.py``, none of the other
families' edited. The checks there are the real ones: the engine's captured
log-probabilities, and one update of ``trainer.train_step``, against
``perfbench/reference_window_moe.py``.

What PR 49 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import json
import os
from types import SimpleNamespace

import pytest

from delta_moe_spec import DELTA_MOE_METRICS
from jamba_spec import JAMBA_METRICS
from latent_moe_spec import LATENT_MOE_METRICS
from power_spec import POWER_METRICS
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from sala_spec import SALA_METRICS
from tiny_spec import REPO, real_benchmark
from window_moe_spec import (
    CELL, CELLS, JOINED, NOT_JOINED, WINDOW_MOE_DIR, WINDOW_MOE_METRICS,
    window_moe_benchmark, write_window_moe_benchmark,
)

REAL_CONFIG = "k-exaone-236b-ep8-L5"
REAL_CELL = "k-exaone-236b-ep8-L5.rollout-longctx-window"
#: the cells of the six other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
)
#: the metrics of the other families' own mixers, which this cell does not
#: report (the expert layer's, the full layer's launch and the slots' share it does)
OTHERS_OWN = {name for group in (SALA_METRICS, LATENT_MOE_METRICS, DELTA_MOE_METRICS,
                                 POWER_METRICS, JAMBA_METRICS)
              for name, *_ in group} - set(JOINED)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_window_moe_benchmark(tmp_path_factory.mktemp("window_moe"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 700 and 1,280 tokens in two segments of 640 (five windows of
    128 each): the second segment's window layers start from the carried ring,
    its full layer reads the first segment's pages, its 1,280 token-rows go
    through the experts in the grouped form and the decode rows in the dense."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 5e-4  # bf16 pages and rings
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1
        from perfbench import spec

        cell = spec.load_cell(window_moe_benchmark(), CELL)
        counted = {m["name"] for m in cell.per_layer if spec.load_layer_metric(
            cell.paths, m["name"])["unit"] == "count"}
        assert counted and counted <= set(line["metrics"])


def test_the_learner_cell_updates_against_the_references_gradient(bench_file):
    """``trainer.train_step`` as the CLI builds it over rows of 160 tokens, a
    window and a quarter long: one traced run."""
    trace = 1
    line, notes = shared_cell(bench_file, "window-moe-tiny.learner", trace)
    assert_contract(line, trace)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


def test_trainer_train_steps_with_the_paged_engine(bench_file):
    """``Trainer.train()`` with ``--engine_impl paged`` over this model through
    the ``rl_step`` driver: rollout (segmented prefill, the rings handed,
    decode), rewards, the update, the adapter pushed back to the engine, and
    the engine's log-probabilities under the TRAINED adapter against the
    reference. No flag, environment variable or configuration field chose
    anything."""
    line, notes = shared_cell(bench_file, "window-moe-tiny.rl-paged", 0)
    assert_contract(line, 0)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] > 0
    assert notes["compiles"]["window"]["programs"] == 0


@pytest.mark.parametrize("control", ["window_as_full", "ring_not_handed", "no_window_rope"])
def test_a_dropped_mechanism_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell the mechanisms: with the window layers attending
    their whole context, the rings not handed to the candidates, or RoPE
    dropped from the window layers, the same run reports ``correct: false``
    (``tests/test_window_moe_model.py`` holds every mechanism at 2e-5)."""
    import dataclasses

    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import hybrid
    from perfbench import assembly

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    if control == "window_as_full":  # the program alone is told a window of 2,048
        build = assembly.build_engine
        monkeypatch.setattr(assembly, "build_engine", lambda config, cfg, **kw: build(
            config, dataclasses.replace(cfg, sliding_window=2048), **kw))
    elif control == "no_window_rope":
        monkeypatch.setattr(hybrid, "apply_rope", lambda x, cos, sin: x)
    else:
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, {**mixer, **{
                n: tuple(jnp.zeros_like(x) for x in mixer[n]) for n in ("win_k", "win_v")}}
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
        "max_concurrent_sequences": 64, "kv_cache_quant": "none", "batch_size": 4,
        "num_candidates": 16, "max_prompt_tokens": 20480, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }
    longctx = spec.load_json(os.path.join(REPO, "perfbench/traffic/rollout-longctx.json"))
    assert cell.traffic["train_config"] == longctx["train_config"]  # one traffic, three caches
    assert cell.traffic["prompt_tokens"] == [10240, 20480] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "one wave" in cell.traffic["fixed"] and "19,200" in cell.traffic["fixed"]
    assert "an eighth of a deployment's pairs" in cell.traffic["fixed"]
    assert "refill scheduler" in cell.traffic["bypasses"]
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in WINDOW_MOE_METRICS} <= reported
    assert not set(NOT_JOINED) & reported
    assert not ({"engine.admit_host_ms"} | OTHERS_OWN) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.1 < check["logprob_max_abs_tol"] < 3
    for said in ("seeds", "window", "RoPE", "q/k norm", "not handed", "3 mantissa bits",
                 "top-7", "scaling factor", "bias", "shared expert", "NOT tellable"):
        assert said in check["basis"], said


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-longctx-window", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("64 slots", "one wave", "rings", "its own batch", "an eighth"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in WINDOW_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    for name in (*NOT_JOINED, "engine.admit_host_ms", *OTHERS_OWN):
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name
    # the ring's roofline is NOT a metric: its time leaves out the transfer (PERF.md)
    assert "kernel.window_attn_roofline" not in metrics


@pytest.mark.parametrize("name, unit, source, layer, better", WINDOW_MOE_METRICS,
                         ids=[m[0] for m in WINDOW_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = window_moe_benchmark()
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

    with open(os.path.join(REPO, "perfbench/scopes/window_moe.json")) as f:
        held = json.load(f)
    assert held["names"] == ["model/window_attn"] == [telemetry.MODEL_WINDOW_ATTN]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_reader_reads_the_programs_counters_and_nothing_from_a_parent(monkeypatch):
    """``engine.window_attended_share`` is the two counters' quotient; a
    program without them (the parent) and a call without a run give None."""
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    bench = window_moe_benchmark()
    metric = spec.load_layer_metric(bench["paths"], "engine.window_attended_share")
    share = spec.load_layer_metric(bench["paths"], "model.window_attn_share")
    assert share["reader"] == "trace_scopes" and share["args"] == {
        "scope": "^model/window_attn$", "of": "busy"}
    reader = spec.load_module(bench["paths"], "readers", "window_moe_work")
    ctx = SimpleNamespace(cell=spec.load_cell(bench, CELL), tracer=None)
    assert metric["args"]["attended"] == telemetry.ENGINE_WINDOW_PAGES_ATTENDED
    assert metric["args"]["visible"] == telemetry.ENGINE_WINDOW_PAGES_VISIBLE
    said = {"counters": {}}
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: said)
    assert reader.read({}, metric["args"], ctx) is None  # the parent: no such counter
    said["counters"] = {telemetry.ENGINE_WINDOW_PAGES_ATTENDED: 131_072.0,
                        telemetry.ENGINE_WINDOW_PAGES_VISIBLE: 16_187_392.0}
    assert reader.read({}, metric["args"], None) is None
    assert reader.read({}, metric["args"], ctx) == pytest.approx(100.0 * 131_072 / 16_187_392)
    with pytest.raises(ValueError, match="cannot read"):
        reader.read({}, {"what": "else"}, ctx)


def test_the_counts_module_answers_the_joined_readers():
    """``delta_moe_work`` and ``latent_moe_work`` read this cell's counts
    through the functions they ask a counts module for."""
    from perfbench import window_moe_counts as counts

    for name in ("delta_state_bytes", "softmax_kv_bytes", "expert_bytes_per_step",
                 "decode_weight_bytes", "kv_read_bytes", "train_flops_per_token"):
        assert callable(getattr(counts, name)), name


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = window_moe_benchmark()
    assert bench["paths"][0] == WINDOW_MOE_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, WINDOW_MOE_DIR, "traffic"))
    assert sorted(held) == ["window-moe-learner.json", "window-moe-rl-paged.json",
                            "window-moe-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, WINDOW_MOE_DIR, sub))
