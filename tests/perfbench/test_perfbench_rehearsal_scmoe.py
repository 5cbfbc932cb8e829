"""The ``rollout`` driver over a shortcut-connected
expert model (LongCat-Flash's layer, its router and its share at a test size),
end to end on the CPU through ``perfbench/run.py``: new files under
``tests/perfbench/scmoe/`` and ``scmoe_spec.py``, none of the other families'
edited. The checks there are the real ones: the engine's captured
log-probabilities against ``perfbench/reference_scmoe.py`` (the learner's loss
and gradient against it: ``tests/test_family_conformance.py``).

What PR 65 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import json
import os

import pytest

from rehearsal_helpers import assert_contract, run_cell, shared_cell
from scmoe_spec import (
    CELL, JOINED, NOT_JOINED, SCMOE_METRICS, scmoe_benchmark, write_scmoe_benchmark,
)
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "longcat-flash-ep32-L4"
REAL_CELL = "longcat-flash-ep32-L4.rollout-reasoning-zero-256"
#: the cells of the nine other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window", "glm-5-ep16-L5.rollout-longctx-indexed",
    "zaya1-8b-L20.rollout-reasoning-cca", "mimo-v2-flash-ep16-L7.rollout-longctx-sink-128",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_scmoe_benchmark(tmp_path_factory.mktemp("scmoe"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 700 and 1,280 tokens in two segments of 640 through four
    sublayers' pools (the experts' grouped form over 8 of 32 held and 16 of
    nothing), each pool's pages aliased to 4 candidates, then 24 decode steps
    of absorbed attention in every sublayer and the dense form of the experts."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 4e-4  # bf16 latent pages on the CPU
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1
        # four pools of 128 lanes in bf16: what a cached token holds
        assert line["metrics"]["engine.cache_token_bytes"]["value"] == 4 * 128 * 2


def test_a_bent_program_is_not_correct(bench_file, monkeypatch):
    """The check can tell what this configuration is: with the zero-compute
    part dropped the same run reports ``correct: false`` (the other bent
    mechanisms are held by ``tests/test_family_conformance.py``, through the
    engine too)."""
    import jax.numpy as jnp

    from distrl_llm_tpu.models import moe

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    monkeypatch.setattr(moe, "zero_part", lambda h, idx, w, first, alive=None: (
        jnp.zeros_like(h), jnp.int32(0)))
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 5 * 4e-4 > 5 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 256, "kv_cache_quant": "none", "batch_size": 16,
        "num_candidates": 16, "max_prompt_tokens": 2048, "max_new_tokens": 512,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [512, 2048] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "one wave" in cell.traffic["fixed"] and "16,384" in cell.traffic["fixed"]
    assert "a 32nd of a deployment's pairs" in cell.traffic["fixed"]
    assert "refill scheduler" in cell.traffic["bypasses"]
    assert "other 31 chips" in cell.traffic["bypasses"]
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in SCMOE_METRICS} <= reported
    assert not set(NOT_JOINED) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.3 < check["logprob_max_abs_tol"] < 4
    for said in ("seeds", "zero-compute part dropped", "renormalised", "sigmoid",
                 "bias in the weights", "top 11", "after sublayer 0", "scale", "shifted by one",
                 "3 mantissa bits", "not tellable"):
        assert said in check["basis"].lower(), said
    assert cell.config["reference"] == "reference_scmoe"
    assert cell.config["counts"] == "scmoe_counts"
    assert cell.config["weight_rules"] == "longcat_flash"
    for key in ("model_type", "norm_topk_prob", "router_bias", "rope_pairs", "hidden_act",
                "tie_word_embeddings", "latent_scales", "torch_dtype"):
        assert key in cell.config["assumed"], key


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-reasoning-zero-256", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("256 slots", "one wave", "8 latent pools", "their batch whole", "a 32nd"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in SCMOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    for name in NOT_JOINED:
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name
    # every list Kimi-VL's latent expert cell is in took this cell too
    for name, metric in metrics.items():
        if "kimi-vl-a3b-L7.rollout-longctx-latent" in metric.get("workloads", ()):
            assert REAL_CELL in metric["workloads"], name


def test_the_published_config_is_kept_letter_for_letter_but_the_three_cuts():
    """The catalog row's ``config`` keys at their published values, except the
    depth, the experts held and the vocabulary slice; the router's 768 outputs
    and the 256 experts of nothing whole."""
    with open(os.path.join(REPO, "perfbench", "configs", f"{REAL_CONFIG}.json")) as f:
        held = json.load(f)
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert held["reduced"] == list(cut)
    for key, value in published.items():
        assert held[key] == cut.get(key, value), key
    assert held["share"] == {"chips_per_layer": 32, "published": {
        "n_routed_experts": 512, "vocab_size": 131072}}
    assert "num_hidden_layers" not in held and held["model_type"] == "longcat_flash"


@pytest.mark.parametrize("name, unit, source, layer, better", SCMOE_METRICS,
                         ids=[m[0] for m in SCMOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = scmoe_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == {**entry, "workloads": [CELL]}
    assert real["workloads"] == [REAL_CELL]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))


def test_the_new_scope_is_the_programs_and_in_one_file():
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    with open(os.path.join(REPO, "perfbench/scopes/scmoe.json")) as f:
        held = json.load(f)
    assert held["names"] == [telemetry.MODEL_MOE_ZERO]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_routers_bias_does_not_fall_to_the_base_rule():
    """Drawn Normal(0, 0.02) the bias would choose the same 12 of 768 softmax
    outputs for every token: the rule draws it Normal(0, 1e-3) and says why."""
    from perfbench import weights

    with open(os.path.join(REPO, "perfbench", "configs", f"{REAL_CONFIG}.json")) as f:
        (rule,) = weights.load_rules(("perfbench",), json.load(f))
    assert rule["leaf"] == "e_score_bias$" and rule["draw"] == "normal"
    assert rule["std"] == 1e-3 and "SAME 12" in rule["why"]
