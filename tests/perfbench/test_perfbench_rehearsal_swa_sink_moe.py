"""The ``rollout`` and ``rl_step`` drivers over the second window family
(MiMo-V2-Flash's layer kinds and its share at a test size), end to end on the
CPU through ``perfbench/run.py``: new files under
``tests/perfbench/swa_sink_moe/`` and ``swa_sink_moe_spec.py``, none of the
other families' edited. The checks there are the real ones: the engine's
captured log-probabilities, before and after ``Trainer.train()``'s update,
against ``perfbench/reference_swa_sink_moe.py``.

What PR 60 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import os
from types import SimpleNamespace

import pytest

from rehearsal_helpers import assert_contract, run_cell, shared_cell
from swa_sink_moe_spec import (
    CELL, CELLS, JOINED, NOT_JOINED, SWA_SINK_MOE_DIR, SWA_SINK_MOE_METRICS,
    swa_sink_moe_benchmark, write_swa_sink_moe_benchmark,
)
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "mimo-v2-flash-ep16-L7"
REAL_CELL = "mimo-v2-flash-ep16-L7.rollout-longctx-sink-128"
#: the accepted cells as they stand beside it, by name: none reports the gauge yet
OLDER_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window", "glm-5-ep16-L5.rollout-longctx-indexed",
    "zaya1-8b-L20.rollout-reasoning-cca",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_swa_sink_moe_benchmark(tmp_path_factory.mktemp("swa_sink_moe"))


def test_the_rollout_cell_runs_end_to_end(bench_file):
    """Prompts of 700 and 1,280 tokens in two segments of 640 (five windows of
    128 each), one traced run: the second segment's window layers start from
    the carried rings (4 KV heads, K 24 / V 16, a sink a head), its two full
    layers read the first segment's pages (2 KV heads, at a base of their
    own), and the gauge says what one more token costs a slot: two full
    layers' K and V, each at its own width, in bf16."""
    trace = 1
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 5e-4  # bf16 pages and rings
    assert notes["compiles"]["window"]["programs"] == 0
    assert line["metrics"]["entry.window_compiles"]["value"] == 0
    assert notes["window"]["traced_units"] == 1
    assert line["metrics"]["engine.cache_token_bytes"] == {
        "value": 2 * 2 * (24 + 16) * 2, "unit": "count"}


def test_trainer_train_steps_with_the_paged_engine(bench_file):
    """``Trainer.train()`` with ``--engine_impl paged`` over this model through
    the ``rl_step`` driver: rollout (segmented prefill, the rings handed,
    decode), rewards, the update of q, k, v, o in both kinds and layer 0's MLP,
    the adapter pushed back to the engine, and the engine's log-probabilities
    under the TRAINED adapter against the reference. No flag, environment
    variable or configuration field chose anything."""
    line, notes = shared_cell(bench_file, "swa-sink-moe-tiny.rl-paged", 0)
    assert_contract(line, 0)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] > 0
    assert notes["compiles"]["window"]["programs"] == 0


def test_a_dropped_sink_is_not_correct(bench_file, monkeypatch):
    """The check can tell the family's own mechanism through the timed path:
    with the sink dropped from the window layers the same run reports
    ``correct: false`` (``tests/test_swa_sink_moe_model.py`` holds every
    mechanism at 2e-5)."""
    from distrl_llm_tpu.models import hybrid

    sound = shared_cell(bench_file, CELL, 1)[1]["check"]["mean_abs"]
    mix = hybrid._window_mix
    monkeypatch.setattr(hybrid, "_window_mix", lambda x, p, *a, **kw: mix(
        x, {k: v for k, v in p.items() if k != "sink"}, *a, **kw))
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 100 * 5e-4 > 100 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 128, "kv_cache_quant": "none", "batch_size": 8,
        "num_candidates": 16, "max_prompt_tokens": 20480, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [10240, 20480] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "one wave" in cell.traffic["fixed"] and "19,072" in cell.traffic["fixed"]
    assert "a sixteenth of a deployment's pairs" in cell.traffic["fixed"]
    assert "refill scheduler" in cell.traffic["bypasses"]
    assert cell.config["reference"] == "reference_swa_sink_moe"
    assert cell.config["counts"] == "swa_sink_moe_counts"
    assert cell.config["weight_rules"] == "mimo_v2_flash"
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in SWA_SINK_MOE_METRICS} <= reported
    assert not set(NOT_JOINED) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.1 < check["logprob_max_abs_tol"] < 3
    for said in ("seeds", "sink", "3 mantissa bits", "NOT tellable"):
        assert said in check["basis"], said


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-longctx-sink-128", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("128 slots", "one wave", "rings", "sink", "K 192 / V 128", "group 16",
                 "a 16th"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in SWA_SINK_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today (ROADMAP D0b)
        assert not set(OLDER_CELLS) & set(metrics[name]["workloads"]), name
    for name in NOT_JOINED:
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name


@pytest.mark.parametrize("name, unit, source, layer, better", SWA_SINK_MOE_METRICS,
                         ids=[m[0] for m in SWA_SINK_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_an_accepted_reader(name, unit, source, layer,
                                                                 better, monkeypatch):
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    bench = swa_sink_moe_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert held["reader"] == "program_gauge"
    assert held["args"] == {"name": telemetry.ENGINE_CACHE_TOKEN_BYTES}
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == {**entry, "workloads": [CELL]}
    assert REAL_CELL in real["workloads"]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))
    # a program without the gauge (the parent) gives None and the line leaves it out
    reader = spec.load_module(bench["paths"], "readers", held["reader"])
    said = {"gauges": {}, "counters": {}}
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: said)
    ctx = SimpleNamespace(cell=spec.load_cell(bench, CELL), tracer=None)
    assert reader.read({}, held["args"], ctx) is None
    said["gauges"][telemetry.ENGINE_CACHE_TOKEN_BYTES] = 6144.0
    assert reader.read({}, held["args"], ctx) == 6144.0


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = swa_sink_moe_benchmark()
    assert bench["paths"][0] == SWA_SINK_MOE_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, SWA_SINK_MOE_DIR, "traffic"))
    assert sorted(held) == ["swa-sink-moe-rl-paged.json", "swa-sink-moe-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, SWA_SINK_MOE_DIR, sub))
    # no new scope name: the family runs under the window family's and the base's
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    assert set(spec.load_scope_names(("perfbench",))) == set(telemetry.SCOPE_NAMES)
