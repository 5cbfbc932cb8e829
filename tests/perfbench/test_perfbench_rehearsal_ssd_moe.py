"""The ``rollout`` driver over a state-space expert model of one sublayer a
layer (NVIDIA-Nemotron-3-Nano's layer kinds at a test size: three Mamba-2
layers, one attention layer alone, two layers of ungated relu^2 experts alone),
end to end on the CPU through ``perfbench/run.py``: new files under
``tests/perfbench/ssd_moe/`` and ``ssd_moe_spec.py``, none of the other
families' edited. The check there is the real one: the engine's captured
log-probabilities against ``perfbench/reference_ssd_moe.py`` (the learner's
loss and gradient against it: ``tests/test_family_conformance.py``).

What PR 70 added to the real benchmark is held here BY NAME and by membership,
never by position, by count or by the equality of a list
(``perfbench/README.md``'s rule): the next PR appends after it.
"""

import os

import pytest

from rehearsal_helpers import assert_contract, run_cell, shared_cell
from ssd_moe_spec import (
    CELL, JOINED, NOT_JOINED, SSD_MOE_METRICS, ssd_moe_benchmark, write_ssd_moe_benchmark,
)
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "nemotron-3-nano-ep2-L13"
REAL_CELL = "nemotron-3-nano-ep2-L13.rollout-reasoning-ssd"
SSM_CELL = "jamba2-3b.rollout-wide-480"
#: the cells of the twelve other configurations as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window", "glm-5-ep16-L5.rollout-longctx-indexed",
    "zaya1-8b-L20.rollout-reasoning-cca", "mimo-v2-flash-ep16-L7.rollout-longctx-sink-128",
    "longcat-flash-ep32-L4.rollout-reasoning-zero-256", "ouro-2.6b-L8.rollout-reasoning-loop4",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_ssd_moe_benchmark(tmp_path_factory.mktemp("ssd_moe"))


@pytest.mark.parametrize("trace", [1, 0], ids=["traced", "untraced"])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 700 and 1,280 tokens in two segments of 640 (the chunks from
    the carried state, the convolution from the carried tail, the attention
    layer over the first segment's pages), each prompt's three states, three
    tails and page chain handed to 4 candidates, then 24 lockstep decode steps."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["check"]["mean_abs"] < 1e-3  # bf16 pages and tails on the CPU
    assert notes["compiles"]["window"]["programs"] == 0
    metrics = line["metrics"]
    if trace:
        assert metrics["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1
        # K and V of 2 heads x 16 in bf16, in the ONE attention layer
        assert metrics["engine.cache_token_bytes"]["value"] == 2 * 2 * 16 * 2
        assert "engine.admit_host_ms" not in metrics  # one wave admits nothing


def _relu_not_squared(monkeypatch):
    import jax

    from distrl_llm_tpu.models import moe

    monkeypatch.setattr(moe, "relu2", jax.nn.relu)


def _state_not_handed(monkeypatch):
    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine

    prefill = paged_engine._paged_prefill_hybrid

    def zeroed(*a, **kw):
        k, v, logits, real_len, mixer = prefill(*a, **kw)
        return k, v, logits, real_len, {
            **mixer, "ssm": tuple(jnp.zeros_like(x) for x in mixer["ssm"])}
    monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", zeroed)


@pytest.mark.parametrize("bend", [_relu_not_squared, _state_not_handed],
                         ids=["relu_not_squared", "state_not_handed"])
def test_a_bent_program_is_not_correct(bench_file, monkeypatch, bend):
    """The check can tell what this configuration is: with relu in relu^2's
    place, or the prompts' states not handed to their candidates at the
    fan-out, the same run reports ``correct: false`` (the other bent mechanisms
    are held by ``tests/test_family_conformance.py``, through the engine)."""
    bend(monkeypatch)
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 3 * 1e-3


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
    assert "ONE wave" in cell.traffic["measures"] and "131,072" in cell.traffic["measures"]
    assert "12 an expert" in cell.traffic["measures"]
    assert "refill scheduler" in cell.traffic["bypasses"]
    assert "three further pipeline stages" in cell.traffic["bypasses"]
    assert set(cell.traffic["reduced"]) == {"answers", "round"}
    assert {"rollout_tok_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in SSD_MOE_METRICS} <= reported
    assert not set(NOT_JOINED) & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < check["logprob_max_abs_tol"] < 4
    for said in ("seeds", "3 mantissa bits", "decay dropped", "wrong group",
                 "gate after the norm", "not handed", "relu in", "shared expert dropped"):
        assert said in check["basis"].lower(), said
    assert cell.config["reference"] == "reference_ssd_moe"
    assert cell.config["counts"] == "ssd_moe_counts"
    assert cell.config["weight_rules"] == "nemotron_h"
    assert cell.config["share"] == {"chips_per_layer": 2, "published": {
        "n_routed_experts": 128, "vocab_size": 131072}}


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-reasoning-ssd", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("256 slots", "one wave", "512 lockstep steps", "2 MiB", "12 pairs"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in SSD_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    for name in own:  # read in this cell alone of those that stand today
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    for name in NOT_JOINED:
        assert REAL_CELL not in metrics[name]["workloads"], name
    for name in ("entry.cache_misses", "entry.compile_s", "entry.programs_built",
                 "entry.window_compiles"):
        assert "workloads" not in metrics[name], name
    # every list the state-space family's cell is in took this cell too, but the
    # Mamba-1 scan's bytes
    for name, metric in metrics.items():
        if SSM_CELL in metric.get("workloads", ()) and name != "kernel.ssm_scan_roofline":
            assert REAL_CELL in metric["workloads"], name


@pytest.mark.parametrize("name, unit, source, layer, better", SSD_MOE_METRICS,
                         ids=[m[0] for m in SSD_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = ssd_moe_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert held["reader"] == "ssd_work" and held["args"]["scope"] == "^model/ssm$"
    assert held["args"]["span"] == "engine/prefill"
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == {**entry, "workloads": [CELL]}
    assert REAL_CELL in real["workloads"]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))


def test_the_reader_returns_nothing_where_there_is_nothing_to_read():
    """The parent of this PR has no such counts module, an untraced run no
    units: ``None``, never a raise."""
    from perfbench import spec

    read = spec.load_module(("perfbench",), "readers", "ssd_work").read
    args = {"what": "ssd_chunk_roofline", "scope": "^model/ssm$", "span": "engine/prefill"}
    assert read({}, args, None) is None
    assert read({"peaks": {}, "model": {}, "rollout": {}, "traced_units": []}, args,
                object()) is None


def test_the_family_brings_no_scope_name_of_its_own():
    """The recurrence and the gated norm stand under ``model/ssm`` (PR 44's
    file), the convolution under ``model/short_conv``, the experts under the
    three ``model/moe_*``: no new name, so no new file under ``scopes/``."""
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    names = set(spec.load_scope_names(("perfbench",)))
    assert names == set(telemetry.SCOPE_NAMES)
    assert not os.path.exists(os.path.join(REPO, "perfbench/scopes/ssd_moe.json"))
