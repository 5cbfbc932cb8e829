"""A state-space model (AI21-Jamba2-3B, ``jamba``) against its plain reference,
``perfbench/reference_jamba.py`` (a token-by-token scan from a zero state, full
causal attention: no chunk, no window, no cache), at a small size on the CPU:
the ``tiny-jamba`` preset (hidden 32, four layers of one period's kinds with
attention at 1, 4 query heads over ONE KV head of 16, a state of 16 x 64 a
Mamba layer, a tied head). Float32 throughout, seeded weights with every term
alive.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s. The
rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_jamba.py``, the ops by
``tests/test_selective_scan.py``.
"""

import dataclasses
import functools
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import family_suite as fs
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig, init_lora_params, init_params
from distrl_llm_tpu.models import hybrid, transformer
from distrl_llm_tpu.models.configs import PRESETS
from distrl_llm_tpu.ops import selective_scan
from perfbench import reference_jamba as ref

CFG = PRESETS["tiny-jamba"]
#: bytes of one slot's state and window in one Mamba layer (float32 caches here)
STATE_BYTES = 16 * 64 * 4
WINDOW_BYTES = 3 * 64 * 4


def _with_params(monkeypatch, change):
    """``_mamba_mix`` reading a layer whose leaves ``change`` bent."""
    mix = hybrid._mamba_mix
    monkeypatch.setattr(hybrid, "_mamba_mix", lambda x, p, *a, **kw: mix(
        x, {**p, **change(p)}, *a, **kw))


def _control(name, monkeypatch):
    """Bend the PROGRAM in one place (never the reference)."""
    zero = lambda leaf: (lambda p: {leaf: jnp.zeros_like(p[leaf])})
    if name == "no_inner_norms":
        norm = hybrid.rms_norm
        monkeypatch.setattr(hybrid, "rms_norm", lambda x, w, eps, **kw: (
            x if w.shape[-1] < CFG.hidden_size else norm(x, w, eps, **kw)))
    elif name in ("no_b_conv", "no_b_dt", "no_d_skip"):
        _with_params(monkeypatch, zero({"no_b_conv": "b_conv", "no_b_dt": "b_dt",
                                        "no_d_skip": "ssm_d"}[name]))
    elif name == "no_gate":
        monkeypatch.setattr(selective_scan, "gate", lambda y, z: y)
    elif name == "a_log_as_a":  # A = -A_log where it is -exp(A_log)
        _with_params(monkeypatch, lambda p: {
            "ssm_a_log": jnp.log(jnp.maximum(p["ssm_a_log"].astype(jnp.float32), 1e-30))})
    elif name == "u_z_swapped":
        fs.with_proj(monkeypatch, "_mamba_mix", lambda key, y, env, mode: (
            jnp.roll(y, y.shape[-1] // 2, axis=-1) if key == "w_in" else y))
    elif name == "rope_in_attention":
        fs.rope_in_the_softmax_layers(monkeypatch, CFG.head_dim, 10000.0)
    elif name == "group_as_two_halves":  # the one KV head's group read as 2 + 2, swapped
        fs.with_proj(monkeypatch, "_softmax_mix", lambda key, y, env, mode: (
            jnp.roll(y, y.shape[-1] // 2, axis=-1) if key == "wq" else y))
    elif name in ("state_3_bits", "bf16_state"):  # the state rounded before every step
        step, bits = hybrid.ssm_step, 3 if name == "state_3_bits" else 7
        monkeypatch.setattr(hybrid, "ssm_step", lambda *a: step(
            *a[:6], jax.lax.reduce_precision(a[6], 8, bits), *a[7:]))
    else:
        raise AssertionError(name)


def _round_check(moved, result, engine, scheduler, slots):
    """The counter x a state's bytes is what ``ssm_counts`` says the same rows
    must move."""
    from perfbench import ssm_counts

    stepped = moved("engine/ssm_states_stepped")
    assert stepped == 3 * 8 * 24  # Mamba layers x rows x steps
    model = dataclasses.asdict(CFG)
    assert ssm_counts.state_bytes(model) == STATE_BYTES
    assert 2 * stepped * STATE_BYTES == ssm_counts.ssm_state_bytes(
        model, [40] * 4 + [57] * 4, result.lengths.reshape(-1))


FORWARD_CONTROLS = ["no_inner_norms", "no_b_conv", "no_b_dt", "no_d_skip", "no_gate",
                    "a_log_as_a", "u_z_swapped", "rope_in_attention", "group_as_two_halves"]

FAMILY = fs.Family(
    name="jamba", cfg=CFG, ref=ref, config_file="jamba2-3b.json",
    # steps between 0.001 and 0.1 that move with the token, A over -1..-16, a
    # skip off 1, biases that are not zero
    seed_rules=(
        (fs.named("b_dt"), fs.uniform(-6.9, -2.2)),
        (fs.named("ssm_a_log"), fs.uniform(0.0, 2.77)),
        (fs.named("ssm_d"), fs.normal(0.2, 1.0)),
        (fs.named("b_conv"), fs.normal(0.25)),
        (fs.named("conv"), fs.normal(0.5))),
    # Prefill in segments of 16 tokens (two pages of 8) and the attention layer's
    # segment a page of keys at a time, so that 40-57-token prompts cross every
    # boundary the cell's 2k-token prompts cross: the state and the window
    # carried from segment to segment, the attention layer over earlier
    # segments' pages, a last segment that is part padding.
    engine_pieces=((paged_engine, "HYBRID_PREFILL_SEGMENT", 16),),
    refusals=(
        ({"num_experts": 16, "num_experts_per_tok": 2}, "num_experts=16"),
        ({"mamba_n_heads": 128}, "mamba_n_heads"),
        ({"mamba_n_groups": 8}, "mamba_n_groups"),
        ({"mamba_proj_bias": True}, "mamba_proj_bias"),
        ({"mamba_conv_bias": False}, "mamba_conv_bias"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"attn_layer_period": None}, "attn_layer_period"),
        ({"model_type": "jamba2"}, "jamba2")),
    loader_refusal=("jamba.*seeded weights", "jamba.*seeded weights"),
    # one chunk; chunks that carry the state between them under remat as the
    # learner runs it; chunks that do not divide the row
    forward_cases=(
        ("one_chunk", False, ()),
        ("chunks_of_16_remat", True, ((selective_scan, "DEFAULT_CHUNK", 16),)),
        ("chunks_of_7", False, ((selective_scan, "DEFAULT_CHUNK", 7),))),
    forward_full_logits=True,
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # reverse mode through the rematerialised chunk scan across two chunks; a
    # and b: seven targets in attention, five in Mamba
    learner={"answer": 20, "leaves": 2 * (7 + 5),
             "pieces": ((selective_scan, "DEFAULT_CHUNK", 16),)},
    train_targets={"mamba": {"w_in", "w_out", "w_gate", "w_up", "w_down"},
                   "softmax": {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}},
    # 8 rows through 4 slots (a freed slot takes another prompt's state); every
    # candidate admitted at once; prefill, fan-out, lockstep
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)),
    slot_bytes=3 * (STATE_BYTES + WINDOW_BYTES), round_check=_round_check,
    # a state kept in bf16 or at 3 bits of mantissa, a window or a state that the
    # candidates are not handed, a state handed from the other prompt
    engine_controls={
        "bf16_state": functools.partial(_control, "bf16_state"),
        "state_3_bits": functools.partial(_control, "state_3_bits"),
        "state_from_other_prompt": fs.handed_each(("ssm",), lambda x: jnp.roll(x, 1, axis=0)),
        "state_not_handed": fs.handed_each(("ssm",), jnp.zeros_like),
        "window_not_handed": fs.handed_each(("conv",), jnp.zeros_like)},
    # through segments, fan-out and the decode steps (the paged kernel at one KV head)
    engine_mechanisms=("rope_in_attention", "group_as_two_halves", "no_b_conv",
                       "no_inner_norms"),
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 1e-5},
    state_refusals=fs.NINE_REFUSALS,
    state_refusal_says=("attention, mamba layers",
                        "a float32 state-space state and a convolution window",
                        "K/V pages for its softmax layers only"),
    span_args={"ssm_states_stepped": 3 * 8 * 24},
    report_tail="; slot state 0.000 GB, 576 states stepped",
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


# --------------------------------------------------- what the program is told


def test_one_periods_kinds_and_what_a_slot_holds():
    assert CFG.layer_kinds == ("mamba", "softmax", "mamba", "mamba")
    assert CFG.layer_runs == (("mamba", 0, 0, 1), ("softmax", 1, 0, 1), ("mamba", 2, 1, 2))
    assert CFG.hybrid and CFG.mamba and not (CFG.latent or CFG.delta_moe or CFG.power)
    assert CFG.model_type == "jamba" and CFG.paged_layers == 1 and CFG.mamba_inner == 64
    assert CFG.mixer_names == "attention, mamba"
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    # the state float32 whatever the cache's type, channels last; the window the cache's
    assert [x.shape for x in state["ssm"]] == [(5, 16, 64)] * 3
    assert {x.dtype for x in state["ssm"]} == {jnp.dtype(jnp.float32)}
    assert [x.shape for x in state["conv"]] == [(5, 3, 64)] * 3
    assert {x.dtype for x in state["conv"]} == {jnp.dtype(jnp.bfloat16)}
    assert state["lin"] == () and state["pooled"] == () and state["ssm_stats"].shape == (1,)
    assert set(hybrid.ROW_STATES) >= {"ssm", "conv", "power", "power_z", "delta", "lin"}
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert "lm_head" not in params  # the tied head
    assert set(params["layers"]) == {"mamba", "softmax"}
    # an attention layer of this family has the dense MLP and no experts
    assert set(params["layers"]["softmax"]) == {
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert params["layers"]["mamba"]["ssm_a_log"].shape == (3, 16, 64)
    np.testing.assert_allclose(
        jnp.exp(params["layers"]["mamba"]["ssm_a_log"][0, :, 0]), np.arange(1, 17), rtol=1e-6)


def test_parameters_and_operations_count_both_kinds():
    d, f, v, e = CFG.hidden_size, CFG.intermediate_size, CFG.vocab_size, CFG.mamba_inner
    mlp = 3 * d * f
    attention = 2 * d * 64 + 2 * d * 16  # q, o; k, v of ONE head
    mamba = d * 2 * e + e * (8 + 32) + 8 * e + e * d  # W_in, W_x, W_dt, W_out
    assert CFG.matmul_param_count == CFG.total_matmul_param_count == (
        attention + mlp + 3 * (mamba + mlp) + d * v)
    # the attention layer attends over the context; a Mamba layer's token costs its state
    assert CFG.decode_flops_per_token(100.0) == (
        2.0 * CFG.matmul_param_count + 4.0 * 64 * 100.0 + 7.0 * 3 * 64 * 16)
    assert CFG.train_flops_per_token(200) == 3.0 * CFG.decode_flops_per_token(100.0)


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == [] and "share" not in file
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    kinds = cfg.layer_kinds
    assert len(kinds) == 28 and [i for i, k in enumerate(kinds) if k == "softmax"] == [7, 21]
    assert set(kinds) == {"softmax", "mamba"} and cfg.paged_layers == 2
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2560, 20, 1, 128)
    assert (cfg.intermediate_size, cfg.vocab_size) == (8192, 65536)
    assert (cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank) == (
        5120, 16, 4, 160)
    assert cfg.tie_word_embeddings and not cfg.attn_use_rope and not cfg.attention_bias
    assert cfg.rms_norm_eps == 1e-6 and cfg.sliding_window is None
    assert cfg.n_routed_experts == 0 and cfg.model_type == "jamba"
    # the catalog row's keys, every one as published
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
        "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
        "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k: file[k] for k in published} == published
    for key in ("layer_order", "head_dim", "inner_norms", "split_orders", "dt", "conv",
                "state", "padding", "adapter_targets", "frozen", "unread_keys", "weights"):
        assert key in file["assumed"], key
    # "auto" is the family's ceil(hidden / 16)
    auto = ModelConfig.from_hf_config(SimpleNamespace(**{**file, "mamba_dt_rank": "auto"}))
    assert auto.mamba_dt_rank == 160


# -------------------------------------------------------------- the engine


def test_the_prompts_state_is_the_scans_after_its_last_real_token(weights, small_pieces):
    """What the prefill returns for the fan-out: a state and a window a Mamba
    layer a prompt (the state float32, neither zero, the window the prompt's
    last three tokens' u), and pages for the one attention layer only."""
    params, lora = weights
    ids, mask, (k, v, logits, real_len, mixer) = fs.prefilled(FAMILY, params, lora)
    assert len(k) == len(v) == 1 and k[0].shape == (1, 16, 8, 16)
    assert list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["ssm"]] == [(2, 16, 64)] * 3
    assert [x.shape for x in mixer["conv"]] == [(2, 3, 64)] * 3
    assert all(float(jnp.abs(x).max()) > 0 for x in mixer["ssm"] + mixer["conv"])
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=fs.LORA_SCALE)[:, -1]
    np.testing.assert_allclose(logits, want, atol=2e-5)
    # the first Mamba layer's window is W_in's u of the last three real tokens
    layer = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["mamba"])
    x = jnp.take(params["embed"], jnp.asarray(ids[:, -3:]), axis=0)
    h = transformer.rms_norm(x, layer["attn_norm"], CFG.rms_norm_eps)
    ab = jax.tree_util.tree_map(lambda w: w[0], lora["layers"]["mamba"]["w_in"])
    u = (h @ layer["w_in"] + fs.LORA_SCALE * (h @ ab["a"]) @ ab["b"])[..., :64]
    np.testing.assert_allclose(mixer["conv"][0], u, atol=2e-5)


# --------------------------------------------- the budget, adapters and placement


def test_a_page_costs_its_two_paged_layers_and_a_slot_its_states():
    """The pool is sized by what the states leave: a page is K and V of ONE
    head in the attention layers alone, a slot's state and window come off the
    budget first, for the decode slots and for the prompts' own."""
    from distrl_llm_tpu.engine import budget

    assert budget.page_bytes(CFG, 8) == 1 * 8 * 16 * 2 * 2 * 1  # one head, bf16, K and V, 1 layer
    # float32 state and a bf16 window, three Mamba layers
    slot = 3 * (STATE_BYTES + 3 * 64 * 2)
    assert budget.slot_state_bytes(CFG, 88) == slot
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**8)
    assert budget.kv_pool_pages(CFG, slots=8, **common) == (
        int(10**8 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6
            - 2 * 8 * budget.page_bytes(CFG, 8) - 10 * slot) // budget.page_bytes(CFG, 8))
    # the published widths: 9.32 MB a slot, 1 KB of K/V a token
    full = ModelConfig.from_hf_config(SimpleNamespace(**json.load(open(CONFIG_FILE))))
    assert budget.slot_state_bytes(full, 2432) == 26 * (327_680 + 3 * 5120 * 2)
    assert budget.page_bytes(full, 128) == 128 * 1024


def test_adapter_factors_are_each_kinds_own_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS, MAMBA_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == {"softmax", "mamba"}
    assert set(lora["layers"]["softmax"]) == set(DEFAULT_TARGETS)
    stack = lora["layers"]["mamba"]
    assert set(stack) == set(MAMBA_TARGETS)  # none on W_x, W_dt, the convolution, A_log, D
    assert stack["w_in"]["b"].shape == (3, 4, 128) and stack["w_out"]["a"].shape == (3, 64, 4)
    assert lora["layers"]["softmax"]["wk"]["b"].shape[-1] == 16
    # targets named by the caller go to the layers that have them
    named = init_lora_params(jax.random.PRNGKey(0), CFG, 4, targets=("wq", "w_in", "w_up"))
    assert set(named["layers"]["softmax"]) == {"wq", "w_up"}
    assert set(named["layers"]["mamba"]) == {"w_in", "w_up"}
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_new_leaf_has_a_partition_spec(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    specs = param_specs(params)["layers"]["mamba"]
    for name in ("conv", "w_x", "w_dt", "ssm_a_log", "ssm_d", "ssm_dt_norm", "ssm_b_norm",
                 "ssm_c_norm"):
        leaf = params["layers"]["mamba"][name]
        assert specs[name] == P(*([None] * leaf.ndim)), name
    assert specs["w_in"] == P(None, "fsdp", "tp") and specs["w_out"] == P(None, "tp", "fsdp")
    assert param_specs(lora)["layers"]["mamba"]["w_out"]["a"] == P(None, "tp", None)


def test_a_slices_scope_is_the_block_that_reads_it():
    """``_slice_layer`` names a Mamba layer's leaves under the scope that reads
    them, and a delta-rule layer's A_log keeps its own."""
    from distrl_llm_tpu import telemetry

    scopes = transformer._SLICE_SCOPES
    assert scopes["ssm_a_log"] == scopes["ssm_d"] == scopes["b_dt"] == telemetry.MODEL_SSM
    assert scopes["b_conv"] == scopes["conv"] == telemetry.MODEL_SHORT_CONV
    assert scopes["A_log"] == telemetry.MODEL_DELTA_ATTN
    assert telemetry.MODEL_SSM in telemetry.SCOPE_NAMES
