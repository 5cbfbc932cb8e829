"""A power-retention model (Brumby-14B-Base, ``brumby``) against its plain
reference, ``perfbench/reference_power_retention.py`` (the ATTENTION form: no
state, no chunk, no cache), at a small size on the CPU: the ``tiny-power``
preset (hidden 64, three layers, 10 query heads over 2 KV heads of 16, a state
of 136 x 16 a KV head). Float32 throughout, seeded weights with every term
alive.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s. The
rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_power.py``, the ops by
``tests/test_power_retention.py``.
"""

import dataclasses
import functools
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite as fs
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig
from distrl_llm_tpu.models import hybrid
from distrl_llm_tpu.models.configs import PRESETS
from distrl_llm_tpu.ops import power_retention
from perfbench import reference_power_retention as ref

CFG = PRESETS["tiny-power"]
TRAFFIC_FILE = os.path.join(fs.REPO, "perfbench", "traffic", "rollout-retention-16k.json")
#: bytes of one slot's state in one layer: 2 KV heads x (136 x 16 + 136) float32
STATE_BYTES = 2 * (136 * 16 + 136) * 4


def _control(name, monkeypatch):
    """Bend the PROGRAM in one place (never the reference)."""
    if name == "degree_1":
        def phi1(x):
            x = x.astype(jnp.float32)
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [
                (0, power_retention.state_dim(x.shape[-1]) - x.shape[-1])])
        monkeypatch.setattr(power_retention, "phi", phi1)
        monkeypatch.setattr(power_retention, "_weights", lambda s: s)
    elif name == "no_normaliser":
        monkeypatch.setattr(power_retention, "_normalised", lambda num, den, eps: num)
    elif name == "no_gate":
        monkeypatch.setattr(hybrid, "_power_decay", lambda h, p: jnp.zeros(
            h.shape[:-1] + (p["w_decay"].shape[-1],), jnp.float32))
    elif name == "no_gate_bias":
        decay = hybrid._power_decay
        monkeypatch.setattr(hybrid, "_power_decay", lambda h, p: decay(
            h, {**p, "b_decay": jnp.zeros_like(p["b_decay"])}))
    elif name == "no_qk_norm":
        block = hybrid._block
        monkeypatch.setattr(hybrid, "_block", lambda x, p, *a, **kw: block(
            x, {k: v for k, v in p.items() if k not in ("q_norm", "k_norm")}, *a, **kw))
    elif name == "no_rope":
        monkeypatch.setattr(hybrid, "apply_rope", lambda x, cos, sin: x)
    elif name == "neighbour_kv_head":
        def shifted(form):
            def run(q, k, *rest, **kw):
                kv = k.shape[-2]
                split = q.shape[:-2] + (kv, q.shape[-2] // kv, q.shape[-1])
                o, state = form(jnp.roll(q.reshape(split), 1, axis=-3).reshape(q.shape),
                                k, *rest, **kw)
                return jnp.roll(o.reshape(split), -1, axis=-3).reshape(o.shape), state
            return run
        monkeypatch.setattr(hybrid, "power_step", shifted(hybrid.power_step))
        monkeypatch.setattr(hybrid, "power_chunked", shifted(hybrid.power_chunked))
    elif name == "bf16_state":
        step = hybrid.power_step
        monkeypatch.setattr(hybrid, "power_step", lambda q, k, v, g, st, eps=1e-6: step(
            q, k, v, g, (jax.lax.reduce_precision(st[0], 8, 7), st[1]), eps=eps))
    else:
        raise AssertionError(name)


def _round_check(moved, result, engine, scheduler, slots):
    """The counter is the bytes ``power_counts`` says the same rows must move."""
    from perfbench import power_counts

    # 3 layers x 8 rows x 24 steps, each state read once and written once
    assert moved("engine/power_state_bytes") == 3 * 8 * 24 * 2 * STATE_BYTES
    model = dataclasses.asdict(CFG)
    assert moved("engine/power_state_bytes") == power_counts.power_state_bytes(
        model, [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    assert power_counts.slot_state_bytes(model) == 3 * STATE_BYTES
    assert power_retention.dispatch_choices[power_retention.dispatch_key(2, 5, 16, 16)] == "plain"


FORWARD_CONTROLS = ["degree_1", "no_normaliser", "no_gate", "no_gate_bias", "no_qk_norm",
                    "no_rope", "neighbour_kv_head"]

FAMILY = fs.Family(
    name="power", cfg=CFG, ref=ref, config_file="brumby-14b-L4.json",
    # a decay that remembers (e^g about 0.9-0.999) and moves with the token
    seed_rules=((fs.named("b_decay"), fs.uniform(2.0, 7.0)),), weight_scale=3.0,
    # Prefill in segments of 16 tokens (two pages of 8), so that 40-57-token
    # prompts cross every boundary the cell's 16k-token prompts cross: (S, z)
    # carried from segment to segment, a last segment that is part padding.
    engine_pieces=((paged_engine, "HYBRID_PREFILL_SEGMENT", 16),),
    refusals=(
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
        ({"power_degree": 4}, "power_degree"),
        ({"model_type": "brumby2"}, "brumby2")),
    loader_refusal=("brumby.*seeded weights", "brumby.*seeded weights"),
    # one chunk, the attention form inside it; rows longer than a chunk: the
    # chunked form from its own carried (S, z), under remat as the learner runs it
    forward_cases=(
        ("one_chunk", False, ()),
        ("chunks_of_16_remat", True, ((power_retention, "DEFAULT_CHUNK", 16),))),
    forward_full_logits=True,
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # the chunked form's own reverse mode across two chunks; a and b of seven
    # targets, none on the decay
    learner={"answer": 20, "leaves": 2 * 7,
             "pieces": ((power_retention, "DEFAULT_CHUNK", 16),)},
    train_targets={"power": {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}},
    # a model with NO paged layer: 8 rows through 4 slots (a freed slot takes
    # another prompt's state); every candidate admitted at once; lockstep
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)),
    slot_bytes=3 * STATE_BYTES, round_check=_round_check,
    # a state kept in bf16 (the chip's check tells it by 22% only: the traffic
    # file's ``basis``), a normaliser or a state that the candidates are not
    # handed from their own prompt, a query head that reads its neighbour's state
    engine_controls={
        "bf16_state": functools.partial(_control, "bf16_state"),
        "neighbour_kv_head": functools.partial(_control, "neighbour_kv_head"),
        "state_from_other_prompt": fs.handed_each(("power",), lambda x: jnp.roll(x, 1, axis=0)),
        "z_not_handed": fs.handed_each(("power_z",), jnp.zeros_like)},
    # sixteen rows on both sides, so both decode through the same products: every
    # candidate starts from its prompt's state AND its normaliser
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 2e-6, "rows": True},
    state_refusals=fs.NINE_REFUSALS,
    state_refusal_says=("power-retention layers",
                        "a float32 power-retention state and its normaliser a KV head, and no "
                        "K/V at all"),
    span_args={"power_state_bytes": 3 * 8 * 24 * 2 * STATE_BYTES},
    report_tail="; slot state 0.000 GB, moved 0.0 GB",
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


# --------------------------------------------------- what the program is told


def test_every_layer_is_a_retention_layer_and_no_layer_keeps_a_page():
    assert CFG.layer_kinds == ("power",) * 3 and CFG.layer_runs == (("power", 0, 0, 3),)
    assert CFG.hybrid and CFG.power and not CFG.latent and not CFG.delta_moe
    assert CFG.model_type == "brumby" and CFG.paged_layers == 0
    assert CFG.power_state_dim == 136
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    # ONE state a KV head (2), not one a query head (10), float32 whatever the cache's type
    assert [x.shape for x in state["power"]] == [(5, 2, 136, 16)] * 3
    assert [x.shape for x in state["power_z"]] == [(5, 2, 136)] * 3
    assert {x.dtype for x in state["power"] + state["power_z"]} == {jnp.dtype(jnp.float32)}
    assert state["lin"] == () and state["pooled"] == ()
    assert state["power_stats"].shape == (1,)
    assert set(hybrid.ROW_STATES) >= {"power", "power_z", "delta", "conv", "lin", "pooled"}


def test_parameters_and_operations_count_the_state_packed():
    d, f, v = CFG.hidden_size, CFG.intermediate_size, CFG.vocab_size
    layer = 2 * d * 160 + 2 * d * 32 + d * 2 + 3 * d * f  # q, o; k, v; the decay; the MLP
    assert CFG.matmul_param_count == CFG.total_matmul_param_count == 3 * layer + d * v
    # a token costs its state whatever the context: 3 D d a KV head, 2 D d a query head
    state = 3 * 136 * 16 * (3 * 2 + 2 * 10)
    assert CFG.decode_flops_per_token() == CFG.decode_flops_per_token(5000.0) == (
        2.0 * CFG.matmul_param_count + state)


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == ["num_hidden_layers"] and "share" not in file
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    assert cfg.layer_kinds == ("power",) * 4 and cfg.paged_layers == 0
    # every published width, both head counts and the whole vocabulary, unchanged
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (5120, 40, 8, 128)
    assert (cfg.intermediate_size, cfg.vocab_size) == (17408, 151936)
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6 and cfg.qk_norm
    assert not cfg.attention_bias and not cfg.tie_word_embeddings
    assert cfg.sliding_window is None and cfg.max_position_embeddings == 32768
    assert cfg.power_state_dim == 8256
    # the catalog row's keys, as published but for the depth
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: file[k] for k in published if k != "num_hidden_layers"} == {
        k: v for k, v in published.items() if k != "num_hidden_layers"}
    assert file["num_hidden_layers"] == 4
    for key in ("degree", "gate", "normaliser", "scale", "qk_norm", "rope", "state",
                "adapter_targets", "frozen", "weights"):
        assert key in file["assumed"], key
    # the round trip: the config names itself, and the full depth gives 40 such layers
    assert cfg.model_type == "brumby"
    full = ModelConfig.from_hf_config(SimpleNamespace(**{**file, "num_hidden_layers": 40}))
    assert full.kind_count("power") == 40
    # 34.08 MB a layer a slot, 14.77B parameters whole
    assert 8 * (8256 * 128 + 8256) * 4 == 34_080_768
    whole = full.matmul_param_count + full.hidden_size * full.vocab_size
    assert abs(whole - 14.77e9) < 0.01e9


# -------------------------------------------------------------- the engine


@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_cpu_round_counts_no_kernel_steps(weights, small_pieces, scheduler, slots):
    """``ops/power_kernel_steps`` is filed by both schedulers and reads 0 here:
    heads of 16 on a CPU take the plain form, and ``power_step`` says so."""
    from distrl_llm_tpu import telemetry

    before = telemetry.observe_snapshot()["counters"].get(telemetry.OPS_POWER_KERNEL_STEPS, 0)
    fs.generate(FAMILY, fs.engine(FAMILY, scheduler, slots))
    assert power_retention.dispatch_choices[
        power_retention.dispatch_key(2, 5, 16, 16)] == "plain"
    after = telemetry.observe_snapshot()["counters"]
    assert after[telemetry.OPS_POWER_KERNEL_STEPS] == before


@pytest.mark.parametrize("ran,steps,want", [
    ("kernel", 256, 3 * 256), ("plain", 256, 0), (None, 256, 0), ("kernel", 0, None)])
def test_the_counter_is_layers_times_steps_where_the_kernel_ran(monkeypatch, ran, steps, want):
    from distrl_llm_tpu import telemetry

    assert CFG.kind_count("power") == 3
    monkeypatch.setattr(power_retention, "dispatch_choices", {} if ran is None else {
        power_retention.dispatch_key(2, 5, 16, 16): ran})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_power_telemetry(CFG, steps)
    assert filed == ([] if want is None else [("ops/power_kernel_steps", want)])
    # a model without such layers files nothing
    filed.clear()
    paged_engine._record_power_telemetry(PRESETS["tiny"], 256)
    assert filed == []


def test_the_prompts_state_is_the_chunked_forms_after_its_last_real_token(weights,
                                                                          small_pieces):
    """What the prefill returns for the fan-out: S and z a layer a prompt,
    float32, not zero, and no page of K or V at all."""
    params, lora = weights
    ids, mask, (k, v, logits, real_len, mixer) = fs.prefilled(FAMILY, params, lora)
    assert k == () and v == () and list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["power"]] == [(2, 2, 136, 16)] * 3
    assert all(float(jnp.abs(x).max()) > 0 for x in mixer["power"] + mixer["power_z"])
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=fs.LORA_SCALE)[:, -1]
    np.testing.assert_allclose(logits, want, atol=2e-5)


# --------------------------------------------- the budget, adapters and placement


def test_a_page_costs_its_paged_layers_and_a_slot_its_states():
    from distrl_llm_tpu.engine import budget

    # no layer keeps a page: a page costs nothing, and nothing divides by it
    assert budget.page_bytes(CFG, 8) == 0
    assert budget.slot_state_bytes(CFG, 88) == 3 * STATE_BYTES
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**9)
    assert budget.kv_pool_pages(CFG, **common) == 0
    assert budget.kv_pool_pages(CFG, slots=8, **common) == 0
    # the slots a budget allows when a slot's whole cache is state
    slots = budget.state_slots(CFG, gpu_usage=0.9, param_bytes=10**6, max_tokens=88,
                               hbm_bytes=10**9)
    assert slots == int(10**9 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6) // (3 * STATE_BYTES)
    assert budget.state_slots(CFG, gpu_usage=0.1, param_bytes=10**9, max_tokens=88,
                              hbm_bytes=10**9) == 1
    # a dense model pays for every layer and holds no row state
    tiny = PRESETS["tiny"]
    assert budget.slot_state_bytes(tiny, 88) == 0
    assert budget.page_bytes(tiny, 8) == (
        tiny.num_kv_heads * 8 * tiny.head_dim * 2 * 2 * tiny.num_layers)


@pytest.mark.parametrize("preset,paged", [
    ("tiny-delta-moe", 1), ("tiny-latent-moe", 3), ("tiny-power", 0)])
def test_pages_are_counted_over_the_layers_that_keep_them(preset, paged):
    """A model whose layers differ in kind pays for pages in its paged layers
    only (it paid for every layer before), and its slots' row states come off
    the budget before pages: the pool of a hybrid model can only have grown."""
    from distrl_llm_tpu.engine import budget

    cfg = PRESETS[preset]
    assert cfg.paged_layers == paged
    every = dataclasses.replace(cfg, mixer_types=None) if not cfg.latent else cfg
    if paged and not cfg.latent:
        assert budget.page_bytes(cfg, 8) * cfg.num_layers == (
            budget.page_bytes(every, 8) * paged)
    state = budget.slot_state_bytes(cfg, 88)
    assert (state > 0) == (not cfg.latent)
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**8)
    if paged:
        assert budget.kv_pool_pages(cfg, slots=8, **common) == (
            int(10**8 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6
                - 2 * 8 * budget.page_bytes(cfg, 8) - 10 * state)
            // budget.page_bytes(cfg, 8))


def test_adapter_factors_are_the_dense_decoders_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == {"power"}
    stack = lora["layers"]["power"]
    assert set(stack) == set(DEFAULT_TARGETS)  # none on w_decay
    assert stack["wk"]["b"].shape[-1] == 32 and stack["wq"]["b"].shape[-1] == 160
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_new_leaf_has_a_partition_spec(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    specs = param_specs(params)["layers"]["power"]
    for name in ("w_decay", "b_decay", "q_norm", "k_norm"):
        leaf = params["layers"]["power"][name]
        assert specs[name] == P(*([None] * leaf.ndim)), name
    assert specs["wq"] == P(None, "fsdp", "tp") and specs["wo"] == P(None, "tp", "fsdp")
    assert param_specs(lora)["layers"]["power"]["wo"]["a"] == P(None, "tp", None)


def test_the_scope_and_the_names_are_telemetrys():
    from distrl_llm_tpu import telemetry

    assert telemetry.MODEL_POWER_ATTN == "model/power_attn"
    assert telemetry.MODEL_POWER_ATTN in telemetry.SCOPE_NAMES
    assert paged_engine.ENGINE_POWER_STATE_BYTES == "engine/power_state_bytes"
    assert paged_engine.ENGINE_SLOT_STATE_BYTES == "engine/slot_state_bytes"
    # a model without such layers files neither
    filed = []
    orig = telemetry.counter_add
    try:
        telemetry.counter_add = lambda name, value: filed.append(name)
        paged_engine._count_mixer_stats({"lin": (), "pooled": ()})
    finally:
        telemetry.counter_add = orig
    assert filed == [] and paged_engine._file_slot_state(None) == {}


def test_the_traffic_file_is_the_issues_letter_for_letter():
    traffic = json.load(open(TRAFFIC_FILE))
    assert traffic["kind"] == "rollout" and traffic["eos"] == "never"
    assert traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True, "max_concurrent_sequences": 32,
        "kv_cache_quant": "none", "batch_size": 2, "num_candidates": 16,
        "max_prompt_tokens": 16384, "max_new_tokens": 256, "max_lora_rank": 32}
    assert traffic["prompt_tokens"] == [8192, 16384] and traffic["trace_units"] == 1
    check = traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < check["logprob_max_abs_tol"]
    for word in ("degree", "normaliser", "gate", "z not handed", "other prompt",
                 "neighbour", "norm", "RoPE", "3 mantissa bits", "bf16 state",
                 "test_this_files_agreement_can_tell_a_wrong_state"):
        assert word in check["basis"], word
