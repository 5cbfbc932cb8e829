"""The ``rollout``, ``learner`` and ``rl_step`` drivers over latent attention
behind a learned index over tokens (GLM-5's layer kinds, its index and its share
at a test size), end to end on the CPU through ``perfbench/run.py``: new files
under ``tests/perfbench/dsa_moe/`` and ``dsa_moe_spec.py``, none of the other
families' edited. The checks there are the real ones: the engine's captured
log-probabilities, and one update of ``trainer.train_step``, against
``perfbench/reference_dsa_moe.py``.

What PR 54 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import json
import os
from types import SimpleNamespace

import pytest

from dsa_moe_spec import (
    CELL, CELLS, DSA_MOE_DIR, DSA_MOE_METRICS, JOINED, NOT_JOINED, dsa_moe_benchmark,
    write_dsa_moe_benchmark,
)
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "glm-5-ep16-L5"
REAL_CELL = "glm-5-ep16-L5.rollout-longctx-indexed"
#: the cells of the seven other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_dsa_moe_benchmark(tmp_path_factory.mktemp("dsa_moe"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 700 and 1,280 tokens in two segments of 640 under an index of
    256: the second segment's queries choose among the first segment's cached
    index keys and their own, both arrays of pages are aliased to 4 candidates,
    and every decode step scores 700-1,304 keys, chooses 256 and gathers them."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 0.012  # bf16 index keys: the choice's floor
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1


def test_the_learner_cell_updates_against_the_references_gradient(bench_file):
    """``trainer.train_step`` as the CLI builds it over rows of 400 tokens,
    past the index's 256: one traced run."""
    trace = 1
    line, notes = shared_cell(bench_file, "dsa-moe-tiny.learner", trace)
    assert_contract(line, trace)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


def test_trainer_train_steps_with_the_paged_engine(bench_file):
    """``Trainer.train()`` with ``--engine_impl paged`` over this model through
    the ``rl_step`` driver: rollout (segmented prefill, both arrays of pages
    handed, decode), rewards, the update, the adapter pushed back to the
    engine, and the engine's log-probabilities under the TRAINED adapter
    against the reference. No flag, environment variable or configuration
    field chose anything."""
    line, notes = shared_cell(bench_file, "dsa-moe-tiny.rl-paged", 0)
    assert_contract(line, 0)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] > 0
    assert notes["compiles"]["window"]["programs"] == 0


@pytest.mark.parametrize("control", ["newest_tokens", "no_choice", "keys_not_handed"])
def test_a_wrong_choice_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell what this configuration is: with the newest 256
    tokens chosen in place of the index's, with every token attended, or with
    the prompt's index keys not handed to the candidates, the same run reports
    ``correct: false``."""
    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import hybrid
    from distrl_llm_tpu.ops import token_index

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    newest = lambda scores: jnp.broadcast_to(
        jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)
    if control == "newest_tokens":
        monkeypatch.setattr(hybrid, "chosen_mask", lambda scores, visible, k: (
            token_index.chosen_mask(newest(scores), visible, k)))
        monkeypatch.setattr(hybrid, "chosen_tokens", lambda scores, lengths, k: (
            token_index.chosen_tokens(newest(scores), lengths, k)))
    elif control == "no_choice":
        monkeypatch.setattr(hybrid, "chosen_mask", lambda scores, visible, k: visible)
        monkeypatch.setattr(hybrid, "chosen_tokens", lambda scores, lengths, k: (
            token_index.chosen_tokens(scores, lengths, scores.shape[-1])))
    else:
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, *rest = prefill(*a, **kw)
            return (k, tuple(jnp.zeros_like(x) for x in v), *rest)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 5 * 0.012 > 5 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    longctx = spec.load_json(os.path.join(REPO, "perfbench/traffic/rollout-longctx.json"))
    assert cell.traffic["train_config"] == longctx["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 64, "kv_cache_quant": "none", "batch_size": 4,
        "num_candidates": 16, "max_prompt_tokens": 20480, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }  # one traffic, four caches
    assert cell.traffic["prompt_tokens"] == [10240, 20480] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "one wave" in cell.traffic["fixed"] and "19,360" in cell.traffic["fixed"]
    assert "a sixteenth of a deployment's pairs" in cell.traffic["fixed"]
    assert "refill scheduler" in cell.traffic["bypasses"]
    assert "other 15 chips" in cell.traffic["bypasses"]
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in DSA_MOE_METRICS} <= reported
    assert not ({*NOT_JOINED, "engine.admit_host_ms"}) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.3 < check["logprob_max_abs_tol"] < 4
    for said in ("seeds", "no choice", "the newest 2,048 tokens", "1,024", "relu",
                 "head weights", "rope", "not handed", "q_a", "top-7", "3 mantissa bits",
                 "not tellable"):
        assert said in check["basis"].lower(), said


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "n_routed_experts", "vocab_size"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-longctx-indexed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("64 slots", "one wave", "top-2,048", "gather", "its own batch", "a 16th"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in DSA_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    for name in (*NOT_JOINED, "engine.admit_host_ms"):
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name


@pytest.mark.parametrize("name, unit, source, layer, better", DSA_MOE_METRICS,
                         ids=[m[0] for m in DSA_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = dsa_moe_benchmark()
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


def test_the_new_scopes_are_the_programs_and_in_one_file():
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    with open(os.path.join(REPO, "perfbench/scopes/dsa_moe.json")) as f:
        held = json.load(f)
    assert held["names"] == [telemetry.MODEL_INDEX_SCORE, telemetry.MODEL_INDEX_SELECT,
                             telemetry.MODEL_INDEXED_ATTN]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_reader_reads_counters_and_a_tiny_trace_and_nothing_from_a_parent(monkeypatch):
    """``engine.index_attended_share`` is the two counters' quotient; the two
    rooflines are the counts module's bytes at the peak over the scope's
    seconds inside the decode spans; a program without the counters or the
    scopes (the parent), another family's counts and a call without a run
    give None."""
    from distrl_llm_tpu import telemetry
    from perfbench import dsa_moe_counts, spec, trace_scopes

    bench = dsa_moe_benchmark()
    paths = bench["paths"]
    reader = spec.load_module(paths, "readers", "dsa_moe_work")
    cell = spec.load_cell(bench, CELL)
    ctx = SimpleNamespace(cell=cell, tracer=None)
    share = spec.load_layer_metric(paths, "engine.index_attended_share")
    assert share["args"]["attended"] == telemetry.ENGINE_INDEX_TOKENS_ATTENDED
    assert share["args"]["visible"] == telemetry.ENGINE_INDEX_TOKENS_VISIBLE
    said = {"counters": {}}
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: said)
    assert reader.read({}, share["args"], ctx) is None  # the parent: no such counter
    said["counters"] = {telemetry.ENGINE_INDEX_TOKENS_ATTENDED: 2_621_440.0,
                        telemetry.ENGINE_INDEX_TOKENS_VISIBLE: 20_070_400.0}
    assert reader.read({}, share["args"], None) is None
    assert reader.read({}, share["args"], ctx) == pytest.approx(13.0612, abs=1e-3)
    for name in ("model.index_score_share", "model.index_select_share",
                 "model.indexed_attn_share"):
        held = spec.load_layer_metric(paths, name)
        scope = name.split(".")[1].removesuffix("_share")
        assert held["reader"] == "trace_scopes"
        assert held["args"] == {"scope": f"^model/{scope}$", "of": "busy"}
    # the rooflines over a made-up trace: 2 ms and 4 ms inside the decode span
    from distrl_llm_tpu.models import ModelConfig

    model = __import__("dataclasses").asdict(ModelConfig.from_hf_config(
        SimpleNamespace(**cell.config)))
    unit = {"prompt_lens": [700] * 4 + [1280] * 4, "gen_lens": [24] * 8, "group_size": 4}
    observed = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": model,
                "rollout": {"kv_bytes": 2, "weight_bytes": 2}, "traced_units": [unit]}
    seconds = {"^model/index_score$": 0.002, "^model/indexed_attn$": 0.004}
    monkeypatch.setattr(trace_scopes, "seconds_in_spans",
                        lambda ctx, scope, span: seconds.get(scope) if span == "engine/decode" else None)
    for name, count, kw in (
            ("kernel.index_score_roofline", dsa_moe_counts.index_key_bytes, {"group_size": 4}),
            ("kernel.indexed_attn_roofline", dsa_moe_counts.indexed_attn_bytes, {})):
        metric = spec.load_layer_metric(paths, name)
        assert metric["reader"] == "dsa_moe_work" and metric["args"]["span"] == "engine/decode"
        needed = count(model, unit["prompt_lens"], unit["gen_lens"], kv_bytes=2, **kw)
        got = reader.read(observed, metric["args"], ctx)
        assert got == pytest.approx(100.0 * needed / 819e9 / seconds[metric["args"]["scope"]])
        assert 0 < got < 100
        assert reader.read({**observed, "traced_units": []}, metric["args"], ctx) is None
        assert reader.read(observed, {**metric["args"], "scope": "^none$"}, ctx) is None
        other = SimpleNamespace(cell=SimpleNamespace(
            paths=cell.paths, config={"counts": "window_moe_counts"}), tracer=None)
        assert reader.read(observed, metric["args"], other) is None  # no index there
    with pytest.raises(ValueError, match="cannot read"):
        reader.read(observed, {"what": "else"}, ctx)


def test_the_counts_module_answers_the_joined_readers():
    """``required_work`` and ``latent_moe_work`` read this cell's counts through
    the functions they ask a counts module for; the ones they must NOT find
    (Kimi-VL's dense walk, Solar's state) are absent, so those metrics stay
    silent here."""
    import inspect

    from perfbench import dsa_moe_counts as counts

    for name in ("expert_bytes_per_step", "decode_weight_bytes", "kv_read_bytes",
                 "train_flops_per_token", "index_key_bytes", "indexed_attn_bytes"):
        assert callable(getattr(counts, name)), name
    assert "group_size" in inspect.signature(counts.kv_read_bytes).parameters
    assert not hasattr(counts, "latent_attn_bytes") and not hasattr(counts, "delta_state_bytes")


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = dsa_moe_benchmark()
    assert bench["paths"][0] == DSA_MOE_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, DSA_MOE_DIR, "traffic"))
    assert sorted(held) == ["dsa-moe-learner.json", "dsa-moe-rl-paged.json",
                            "dsa-moe-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, DSA_MOE_DIR, sub))
