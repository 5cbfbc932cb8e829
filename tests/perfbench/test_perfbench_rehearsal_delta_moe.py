"""The ``rollout`` and ``learner`` drivers over a gated delta-rule model that
holds one chip's share of its routed experts (Solar-Open2-250B's layer kinds
at a test size), end to end on the CPU through ``perfbench/run.py``: new files
under ``tests/perfbench/delta_moe/`` and ``delta_moe_spec.py``, none of
``tiny/``, ``sala/`` or ``latent_moe/`` edited. The checks there are the real
ones: the engine's captured log-probabilities, and one update of
``trainer.train_step``, against ``perfbench/reference_delta_moe.py``.

What PR 36 added to the real benchmark is held here BY NAME, never by position
or by count (``perfbench/README.md``'s rule): the next PR appends after it.
"""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from delta_moe_spec import (
    CELL, CELLS, DELTA_MOE_DIR, DELTA_MOE_METRICS, JOINED, delta_moe_benchmark,
    write_delta_moe_benchmark,
)
from latent_moe_spec import LATENT_MOE_METRICS
from rehearsal_helpers import assert_contract, run_cell, shared_cell
from sala_spec import SALA_METRICS
from tiny_spec import REPO, real_benchmark

REAL_CONFIG = "solar-open2-250b-ep8-L4"
REAL_CELL = "solar-open2-250b-ep8-L4.rollout-reasoning"
#: the cells of the three other families as they stand beside it, by name
OTHER_FAMILIES_CELLS = (
    "qwen2.5-7b-L14.rollout-lockstep", "qwen2.5-7b-L14.learner-1k",
    "qwen2.5-7b-L14.rl-step-dense", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent",
)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return write_delta_moe_benchmark(tmp_path_factory.mktemp("delta_moe"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rollout_cell_runs_end_to_end(bench_file, trace):
    """Prompts of 130-256 tokens in pages of 128 (the engine's default): the
    second page's tokens continue the first's state and convolution tail, and
    its queries attend over the first's K/V pages."""
    line, notes = shared_cell(bench_file, CELL, trace)
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True and notes["check"]["tokens"] == 4 * 24
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert notes["window"]["traced_units"] == 1


def test_the_learner_cell_updates_against_the_references_gradient(bench_file):
    """``trainer.train_step`` as the CLI builds it, one traced run (a run
    compiles the chunked rule's reverse mode and the reference's: the untraced
    twin would cost as much again and hold nothing more)."""
    trace = 1
    line, notes = shared_cell(bench_file, "delta-moe-tiny.learner", trace)
    assert_contract(line, trace)
    check = notes["check"]
    assert check["ok"] is True and check["elements_moved"] > 0
    assert check["loss_scaled_err"] < 1e-5 and check["grad_sign_mass"] > 0.9999


@pytest.mark.parametrize("control", ["no_shared", "no_decay", "wrong_prompts_state"])
def test_a_dropped_mechanism_is_not_correct(bench_file, control, monkeypatch):
    """The check can tell the mechanisms: with the shared expert left out, the
    decay dropped, or each prompt's candidates handed the OTHER prompt's
    state, the same run reports ``correct: false`` (``tests/test_delta_moe.py``
    holds every mechanism at 2e-5)."""
    import jax.numpy as jnp

    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import hybrid

    sound = shared_cell(bench_file, CELL, 0)[1]["check"]["mean_abs"]
    if control == "no_shared":
        monkeypatch.setattr(hybrid, "_mlp_half", lambda x, *a, **kw: x)
    elif control == "no_decay":
        step, chunked = hybrid.delta_step, hybrid.delta_chunked
        monkeypatch.setattr(hybrid, "delta_step", lambda q, k, v, g, b, s: step(
            q, k, v, g * 0, b, s))
        monkeypatch.setattr(hybrid, "delta_chunked", lambda q, k, v, g, b, ok, state=None: (
            chunked(q, k, v, g * 0, b, ok, state=state)))
    else:
        prefill = paged_engine._paged_prefill_hybrid

        def swapped(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, {**mixer, "delta": tuple(
                jnp.roll(x, 1, axis=0) for x in mixer["delta"])}
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", swapped)
    line, notes = run_cell(bench_file, CELL, 0)
    assert line["correct"] is False
    assert notes["check"]["mean_abs"] > 3 * sound


def test_the_real_cell_is_the_issues_letter_for_letter():
    from perfbench import spec

    cell = spec.load_cell(real_benchmark(), REAL_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "rollout"
    assert cell.traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True,
        "max_concurrent_sequences": 128, "kv_cache_quant": "none", "batch_size": 8,
        "num_candidates": 16, "max_prompt_tokens": 2048, "max_new_tokens": 768,
        "max_lora_rank": 32,
    }
    assert cell.traffic["prompt_tokens"] == [512, 2048] and cell.traffic["eos"] == "never"
    assert cell.traffic["trace_units"] == 1
    assert "128" in cell.traffic["fixed"] and "one wave" in cell.traffic["fixed"]
    assert [m["name"] for m in cell.end_to_end] == ["rollout_tok_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(JOINED) - {"rollout_tok_s"} <= reported
    assert {name for name, *_ in DELTA_MOE_METRICS} <= reported
    # the configuration's kv_read_bytes holds 3.2 GB of state a step beside the
    # K/V: the accepted reader would divide all of it by the paged kernel's time
    assert "paged_attn_roofline" not in reported
    # no latent attention, no lightning or sparse layers, no refill admissions
    assert not {"model.latent_attn_share", "kernel.latent_attn_roofline",
                "engine.admit_host_ms", *(name for name, *_ in SALA_METRICS)} & reported
    check = cell.traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < 0.2 < check["logprob_max_abs_tol"] < 5
    for control in ("beta", "decay", "convolution", "tail", "wrong prompt", "gate",
                    "top-7", "shifted", "shared expert", "RoPE", "bf16 state"):
        assert control in check["basis"], control
    assert len(real_benchmark()["workloads"][-1]["why"]) <= 200


def test_the_benchmark_gained_this_configuration_and_this_cell_by_name():
    real = real_benchmark()
    config = {c["name"]: c for c in real["configs"]}[REAL_CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["file"] == f"perfbench/configs/{REAL_CONFIG}.json"
    assert config["source"].startswith("https://huggingface.co/upstage/Solar-Open2-250B")
    cell = {w["name"]: w for w in real["workloads"]}[REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rollout-reasoning", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("8 chips share a layer", "mixers whole", "4 of 48"):
        assert said in cell["why"], said
    metrics = {m["name"]: m for m in real["per_layer"] + real["end_to_end"]}
    own = [name for name, *_ in DELTA_MOE_METRICS]
    for name in (*JOINED, *own):
        assert REAL_CELL in metrics[name]["workloads"], name
    # its own six are read in none of the other families' cells that stand today
    for name in own:
        assert not set(OTHER_FAMILIES_CELLS) & set(metrics[name]["workloads"]), name
    # and it reads none of what another family alone has
    for name in ("paged_attn_roofline", "engine.admit_host_ms", "model.latent_attn_share",
                 "kernel.latent_attn_roofline", *(name for name, *_ in SALA_METRICS)):
        assert REAL_CELL not in metrics[name]["workloads"], name
    # the expert layer's five are shared with Kimi's cell, which stands before it
    for name, *_ in LATENT_MOE_METRICS:
        assert "kimi-vl-a3b-L7.rollout-longctx-latent" in metrics[name]["workloads"]


@pytest.mark.parametrize("name, unit, source, layer, better", DELTA_MOE_METRICS,
                         ids=[m[0] for m in DELTA_MOE_METRICS])
def test_this_familys_metric_has_its_file_and_its_reader(name, unit, source, layer, better):
    from perfbench import spec

    bench = delta_moe_benchmark()
    held = spec.load_layer_metric(bench["paths"], name)
    assert (held["source"], held["layer"], held["better"]) == (source, layer, better)
    assert (held["unit"], held["moves"]) == (unit, "rollout_tok_s")
    assert callable(spec.load_module(bench["paths"], "readers", held["reader"]).read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in spec.load_cell(bench, CELL).per_layer}
    (real,) = [m for m in real_benchmark()["per_layer"] if m["name"] == name]
    assert {**real, "workloads": [CELL]} == entry
    assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", f"{name}.json"))


def test_the_new_scopes_are_the_programs_and_in_one_file():
    from distrl_llm_tpu import telemetry
    from perfbench import spec

    with open(os.path.join(REPO, "perfbench/scopes/delta_moe.json")) as f:
        held = json.load(f)
    assert held["names"] == ["model/delta_attn", "model/short_conv", "model/attn_gate"]
    assert held["names"] == [telemetry.MODEL_DELTA_ATTN, telemetry.MODEL_SHORT_CONV,
                             telemetry.MODEL_ATTN_GATE]
    assert set(held["names"]) <= set(telemetry.SCOPE_NAMES)
    assert set(held["names"]) <= set(spec.load_scope_names(("perfbench",)))


def test_the_reader_reads_hand_worked_counts_and_nothing_from_a_parent(monkeypatch):
    """The held share from the two counters; the three rooflines give None
    where a program has no such scopes or spans (the parent), where the run
    was not traced, and for another family's counts; the paged kernel's share
    of its roofline from a hand-made reduced trace."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import spec

    bench = delta_moe_benchmark()
    reader = spec.load_module(bench["paths"], "readers", "delta_moe_work")
    cell = spec.load_cell(bench, CELL)
    ctx = SimpleNamespace(cell=cell, tracer=None)
    args = lambda name: spec.load_layer_metric(bench["paths"], name)["args"]
    model = dataclasses.asdict(PRESETS["tiny-delta-moe"])
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"counters": {
        "engine/moe_assignments": 96.0, "engine/moe_pairs_routed": 768.0}})
    assert reader.read({"model": model}, args("engine.expert_held_share"), ctx) == 12.5
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"counters": {}})
    assert reader.read({"model": model}, args("engine.expert_held_share"), ctx) is None
    assert reader.read({"model": model}, args("engine.expert_held_share"), None) is None
    unit = {"steps_dispatched": 24, "prompt_lens": [40, 40], "gen_lens": [24, 24],
            "group_size": 2}
    traced = {"model": model, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              "rollout": {"weight_bytes": 2, "kv_bytes": 2}, "traced_units": [unit]}
    for name in ("kernel.delta_step_roofline", "kernel.delta_chunk_roofline",
                 "kernel.softmax_paged_roofline"):
        assert reader.read(traced, args(name), ctx) is None, name  # no trace to divide by
    # the paged kernel's events in a reduced trace: K/V bytes of the ONE softmax
    # layer, 2 KV heads of 16, bf16, over (24 x 40 + 24 x 25 / 2) x 2 rows tokens
    kernel_s = 1e-6
    with_trace = {**traced, "trace": {"devices": 1, "ops_s": {
        "%paged_attention_native bf16[2,2,2,16]": kernel_s, "%fusion f32[2]": 5.0}}}
    tokens = 2 * (24 * 40 + 24 * 25 // 2)
    want = 100.0 * (1 * 2 * 32 * 2 * tokens) / 819e9 / kernel_s
    got = reader.read(with_trace, args("kernel.softmax_paged_roofline"), ctx)
    assert got == pytest.approx(want)
    dense = SimpleNamespace(cell=SimpleNamespace(paths=cell.paths, config={}), tracer=None)
    assert reader.read(with_trace, args("kernel.softmax_paged_roofline"), dense) is None


def test_the_configuration_file_holds_the_catalogs_numbers_and_every_assumption():
    with open(os.path.join(REPO, f"perfbench/configs/{REAL_CONFIG}.json")) as f:
        held = json.load(f)
    assert (held["model_type"], held["torch_dtype"]) == ("solar_open2", "bfloat16")
    assert (held["num_hidden_layers"], held["n_routed_experts"], held["vocab_size"]) == (
        4, 40, 24576)
    assert held["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert held["share"] == {"chips_per_layer": 8, "published": {
        "n_routed_experts": 320, "vocab_size": 196608}}
    assert (held["hidden_size"], held["num_attention_heads"], held["num_key_value_heads"],
            held["head_dim"], held["moe_intermediate_size"], held["num_experts_per_tok"]) == (
        4096, 64, 8, 128, 1280, 8)
    assert held["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    for key in ("short_conv", "qk_norm", "decay", "beta", "state", "delta_output",
                "softmax_layers", "softmax_gate", "router", "experts", "held_experts",
                "vocabulary", "adapter_targets", "frozen", "no_auxiliary_loss", "weights",
                "sizes_held"):
        assert held["assumed"][key], key
    assert "ids 0-39" in held["assumed"]["held_experts"]
    assert "float32" in held["assumed"]["state"]
    assert "intermediate_size 10240 is used by no layer" in held["assumed"]["experts"]
    assert "seeded weights only" in held["assumed"]["weights"]
    assert "8 chips" in held["deployment"] and "12 pipeline stages" in held["deployment"]
    assert (held["reference"], held["counts"], held["weight_rules"]) == (
        "reference_delta_moe", "delta_moe_counts", "solar_open2")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
        assert held["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if held.get(k, "absent") != v}
        assert differs == set(held["reduced"])


def test_the_familys_weight_rules_draw_a_state_that_remembers():
    """The rule file draws every leaf it names, and a channel's decay spans
    about 0.86-0.999: a state that forgets in ten tokens would let the check
    pass a wrong state."""
    import jax
    import numpy as np

    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import weights

    bench = delta_moe_benchmark()
    rules = weights.load_rules(bench["paths"], {"weight_rules": "solar_open2"})
    assert [r["leaf"] for r in rules][:2] == ["^layers/delta/A_log$", "^layers/delta/dt_bias$"]
    params = weights.make_base_params(PRESETS["tiny-delta-moe"], "float32", 11, rules=rules)
    delta = params["layers"]["delta"]
    a_log, dt = np.asarray(delta["A_log"]), np.asarray(delta["dt_bias"])
    assert -3.0 <= a_log.min() and a_log.max() <= -1.5 and -4.0 <= dt.min() and dt.max() <= 0.0
    decay = np.exp(-np.exp(a_log)[:, :, None] * np.log1p(np.exp(dt)).reshape(3, 4, 16))
    assert 0.85 < decay.min() < 0.95 and 0.995 < decay.max() < 1.0
    assert 0.4 < float(np.asarray(delta["conv"]).std()) < 0.6
    assert float(np.abs(np.asarray(params["layers"]["softmax"]["e_score_bias"])).max()) > 0
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        weights.make_base_params(PRESETS["tiny-delta-moe"], "float32", 11))


def test_the_rehearsal_benchmark_names_only_new_files():
    bench = delta_moe_benchmark()
    assert bench["paths"][0] == DELTA_MOE_DIR and len(bench["workloads"]) == len(CELLS)
    held = os.listdir(os.path.join(REPO, DELTA_MOE_DIR, "traffic"))
    assert sorted(held) == ["delta-moe-learner.json", "delta-moe-rollout.json"]
    for sub in ("layer_metrics", "readers", "scopes", "weight_rules"):
        assert not os.path.exists(os.path.join(REPO, DELTA_MOE_DIR, sub))
