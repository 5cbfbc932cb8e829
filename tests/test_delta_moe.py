"""A gated delta-rule model with routed experts held as one chip's share
(Solar-Open2-250B, ``solar_open2``) against its plain reference,
``perfbench/reference_delta_moe.py``, at a small size on the CPU: the
``tiny-delta-moe`` preset (hidden 64, a period of four: softmax at 0, delta
rule at 1-3; 2 of 16 experts held, 4 a token, 1 shared). Float32 throughout,
seeded weights with every term alive.

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_delta_moe.py``, the ops by
``tests/test_delta_attention.py``.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s.
"""

import dataclasses
import functools
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite as fs
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig
from distrl_llm_tpu.models import hybrid, moe
from distrl_llm_tpu.models.configs import PRESETS
from perfbench import reference_delta_moe as ref

CFG = PRESETS["tiny-delta-moe"]


def _rule(monkeypatch, change_args=None, change_state=None):
    step, chunked = hybrid.delta_step, hybrid.delta_chunked

    def s(q, k, v, g, beta, state):
        args = change_args(q, k, v, g, beta) if change_args else (q, k, v, g, beta)
        o, new = step(*args, state)
        return o, change_state(new) if change_state else new

    def c(q, k, v, g, beta, valid, state=None, **kw):
        args = change_args(q, k, v, g, beta) if change_args else (q, k, v, g, beta)
        o, new = chunked(*args, valid, state=state, **kw)
        return o, change_state(new) if change_state else new

    monkeypatch.setattr(hybrid, "delta_step", s)
    monkeypatch.setattr(hybrid, "delta_chunked", c)


def _control(name, monkeypatch):
    """The chip's controls (the traffic file's ``basis``), made the same way:
    the PROGRAM is patched, never the reference."""
    if name == "beta_not_doubled":
        _rule(monkeypatch, lambda q, k, v, g, b: (q, k, v, g, b / 2))
    elif name == "no_decay":
        _rule(monkeypatch, lambda q, k, v, g, b: (q, k, v, g * 0, b))
    elif name == "scalar_decay":
        _rule(monkeypatch, lambda q, k, v, g, b: (
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), b))
    elif name == "no_conv":
        conv = hybrid.short_conv

        def ident(x, w, valid=None, tail=None):
            return (x if valid is None else x * valid.astype(x.dtype)[..., None],
                    conv(x, w, valid, tail)[1])
        monkeypatch.setattr(hybrid, "short_conv", ident)
    elif name == "no_softmax_gate":
        mix = hybrid._softmax_mix
        monkeypatch.setattr(hybrid, "_softmax_mix", lambda x, p, *a, **kw: mix(
            x, {k: v for k, v in p.items() if k != "wg"}, *a, **kw))
    elif name == "no_delta_gate":
        monkeypatch.setattr(hybrid, "_delta_gate", lambda h, p: jnp.ones((), h.dtype))
    elif name == "one_expert_fewer":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda h, r, b, cfg: route(
            h, r, b, dataclasses.replace(cfg, experts_per_token=cfg.experts_per_token - 1)))
    elif name == "held_shifted":
        half = hybrid.moe_half
        monkeypatch.setattr(hybrid, "moe_half", lambda h, p, cfg, held=None, alive=None: half(
            h, p, cfg, held=tuple(i + 1 for i in held), alive=alive))
    elif name == "no_shared":
        monkeypatch.setattr(hybrid, "_mlp_half", lambda x, *a, **kw: x)
    elif name == "rope_in_softmax":
        fs.rope_in_the_softmax_layers(monkeypatch, CFG.head_dim, CFG.rope_theta)
    elif name == "bf16_state":
        _rule(monkeypatch, change_state=lambda st: jax.lax.reduce_precision(st, 8, 7))
    else:
        raise AssertionError(name)


def _round_check(moved, result, engine, scheduler, slots):
    # 4 expert layers x 8 rows x 24 steps x 4 experts a token, live slots only
    routed = 4 * 8 * 24 * 4
    assert moved("engine/moe_pairs_routed") == routed
    # 2 of 16 experts are held: some of the pairs land here, never all
    assert 0 < moved("engine/moe_assignments") < routed
    assert moved("engine/moe_assignments") / 2 <= moved("engine/moe_max_expert_load") <= (
        moved("engine/moe_assignments"))


FORWARD_CONTROLS = [
    "beta_not_doubled", "no_decay", "scalar_decay", "no_conv", "no_softmax_gate",
    "no_delta_gate", "one_expert_fewer", "held_shifted", "no_shared", "rope_in_softmax",
]

FAMILY = fs.Family(
    name="delta-moe", cfg=CFG, ref=ref, config_file="solar-open2-250b-ep8-L4.json",
    # decays that remember, filters of order 1
    seed_rules=(
        (fs.named("A_log"), fs.uniform(-3.0, 0.5)),
        (fs.named("dt_bias"), fs.normal(1.0)),
        (fs.named("conv"), fs.normal(0.5))),
    weight_scale=3.0,
    # Eight tokens or fewer take the dense form (a decode step of 8 rows), more
    # the grouped one (a prefill segment, the learner's rows).
    pieces=((moe, "expert_form", fs.expert_forms(8)),),
    # Prefill in segments of 16 tokens (two pages of 8, scored a page at a time),
    # so that 40-57-token prompts cross every boundary the cell's 2,048-token
    # prompts cross: the state, the tail and the pages carried from segment to
    # segment, a last segment that is part padding.
    engine_pieces=((paged_engine, "HYBRID_PREFILL_SEGMENT", 16),),
    refusals=(
        ({"use_rope": True}, "use_rope"),
        ({"kda_use_full_proj": True}, "kda_use_full_proj"),
        ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
        ({"n_group": 4}, "n_group"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                                 "num_heads": 64, "num_kv_heads": 8}}, "num_kv_heads"),
        ({"gqa_layers": None}, "gqa_layers"),
        ({"model_type": "solar_open3"}, "solar_open3")),
    loader_refusal=("solar_open2.*seeded weights", "solar_open2"),
    forward_cases=(("plain", False, ()),),
    # the chip's controls (the traffic file's ``basis``), made the same way
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # the chunked rule's own reverse mode and the grouped experts with ``held``.
    # 4e-5 of a leaf's largest entry where the latent family's test holds 2e-5:
    # the gradient crosses three triangular solves in float32 (one element in
    # 5,000 reads 2.2e-5). a and b of seven targets in two kinds.
    learner={"answer": 20, "leaves": 2 * 7 * 2, "atol": 4e-5},
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)), round_check=_round_check,
    # a state kept in bf16 (the chip's check cannot tell it: the traffic file's
    # ``basis``), a tail or a state that the candidates are not handed from
    # their own prompt
    engine_controls={
        "bf16_state": functools.partial(_control, "bf16_state"),
        "state_from_wrong_prompt": fs.handed_each(("delta",), lambda x: jnp.roll(x, 1, axis=0)),
        "tail_not_handed": fs.handed_each(("conv",), jnp.zeros_like)},
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 2e-6},
    # the refusal names the STATE KINDS, whatever the model
    state_refusals=fs.NINE_REFUSALS[:7],
    state_refusal_says=("gqa, kda layers",
                        "a float32 delta-rule state and a convolution tail",
                        "K/V pages for its softmax layers only"),
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


# --------------------------------------------------- what the program is told


def test_the_pattern_the_share_and_the_state_are_the_configs():
    assert CFG.layer_kinds == ("softmax", "delta", "delta", "delta")
    assert CFG.layer_runs == (("softmax", 0, 0, 1), ("delta", 1, 0, 3))
    assert CFG.hybrid and not CFG.latent and CFG.model_type == "solar_open2"
    assert CFG.router_width == 16 and CFG.held_experts == (0, 1)
    assert dataclasses.replace(CFG, expert_shard=7).held_experts == (14, 15)
    whole = dataclasses.replace(CFG, n_routed_experts=16, router_experts=0)
    assert whole.held_experts is None and whole.router_width == 16
    assert CFG.paged_layers == 1 and CFG.page_pool_shape(9, 8) == (2, 9, 8, 16)
    with pytest.raises(ValueError, match="whole number of runs"):
        dataclasses.replace(CFG, n_routed_experts=3)
    with pytest.raises(ValueError, match="whole number of runs"):
        dataclasses.replace(CFG, expert_shard=8)
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    assert [x.shape for x in state["delta"]] == [(5, 4, 16, 16)] * 3
    assert {x.dtype for x in state["delta"]} == {jnp.dtype(jnp.float32)}
    assert [x.shape for x in state["conv"]] == [(5, 3, 3 * 64)] * 3
    assert state["lin"] == () and state["pooled"] == ()
    assert state["moe_stats"].shape == (2,) and state["moe_routed"].shape == (1,)
    assert set(hybrid.ROW_STATES) >= {"delta", "conv", "lin", "pooled"}


def test_bytes_count_the_experts_held_and_operations_the_experts_run():
    d, f = CFG.hidden_size, CFG.moe_intermediate_size
    mixers = (3 * d * 64 + 2 * d * 32) + 3 * (4 * d * 64 + 2 * (d * 16 + 16 * 64) + d * 4)
    around = 4 * (3 * d * f + d * 16) + d * CFG.vocab_size  # shared, router, head
    assert CFG.total_matmul_param_count == mixers + around + 4 * 2 * 3 * d * f
    assert CFG.matmul_param_count == mixers + around + 4 * 4 * 3 * d * f
    # a delta-rule layer's token costs its state whatever the context
    assert CFG.decode_flops_per_token() == 2.0 * CFG.matmul_param_count + 7.0 * 3 * 64 * 16
    assert CFG.decode_flops_per_token(100.0) - CFG.decode_flops_per_token() == 4.0 * 1 * 64 * 100


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert file["share"] == {"chips_per_layer": 8, "published": {
        "n_routed_experts": 320, "vocab_size": 196608}}
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    assert cfg.layer_kinds == ("softmax", "delta", "delta", "delta")
    # every published width, unchanged
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4096, 64, 8, 128)
    assert (cfg.delta_heads, cfg.delta_head_dim, cfg.delta_conv_size) == (64, 128, 4)
    assert (cfg.moe_intermediate_size, cfg.experts_per_token, cfg.n_shared_experts) == (1280, 8, 1)
    assert cfg.delta_low_rank == 128 and cfg.delta_beta_scale == 2.0
    assert not cfg.attn_use_rope and cfg.attn_output_gate and cfg.rms_norm_eps == 1e-5
    # the share: 40 held of a router 320 wide, an eighth of the vocabulary
    assert cfg.n_routed_experts == 40 and cfg.router_width == 320
    assert cfg.held_experts == tuple(range(40)) and cfg.vocab_size == 24576
    assert cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0
    for key in ("short_conv", "qk_norm", "decay", "beta", "state", "delta_output",
                "softmax_layers", "softmax_gate", "router", "experts", "held_experts",
                "vocabulary", "adapter_targets", "frozen", "weights"):
        assert key in file["assumed"], key
    # the full depth gives the published 36 + 12
    full = ModelConfig.from_hf_config(SimpleNamespace(**{**file, "num_hidden_layers": 48}))
    assert full.kind_count("delta") == 36 and full.kind_count("softmax") == 12
    assert [i for i, k in enumerate(full.layer_kinds) if k == "softmax"] == file["gqa_layers"]


# --------------------------------------------------------------- the share


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_the_eight_shares_of_a_layer_sum_to_the_uncut_references(weights, form):
    """One layer's second half run as each of the 8 chips of the deployment
    (its 2 of 16 experts, the router whole): the routed parts summed, with the
    shared expert counted once, are the uncut reference's whole expert layer."""
    from distrl_llm_tpu.models.transformer import _mlp_half, _proj, rms_norm

    whole_cfg = dataclasses.replace(CFG, n_routed_experts=16, router_experts=0)
    params, _ = fs.seeded(FAMILY, whole_cfg)
    p = jax.tree_util.tree_map(lambda w: w[1], params["layers"]["delta"])
    tokens = 6 if form == "dense" else 40
    x = jax.random.normal(jax.random.PRNGKey(7), (2, tokens // 2, CFG.hidden_size))
    shared = _mlp_half(x, p, None, cfg=CFG, proj=_proj, lora_scale=1.0) - x
    def as_chip(shard):
        """(the layer with that chip's 2 experts, its config, its routed part)"""
        cfg = dataclasses.replace(CFG, expert_shard=shard)
        here = {**p, **{name: p[name][2 * shard: 2 * shard + 2]
                        for name in ("experts_gate", "experts_up", "experts_down")}}
        out, stats = hybrid._expert_half(
            x, here, None, cfg=cfg, env={}, proj=_proj, lora_scale=1.0)
        assert 0 <= int(stats[0]) <= tokens * CFG.experts_per_token
        return here, cfg, out - x - shared

    routed = sum(as_chip(shard)[2] for shard in range(8))
    h = np.asarray(rms_norm(x, p["mlp_norm"], CFG.rms_norm_eps)).reshape(-1, CFG.hidden_size)
    want = ref._experts(jnp.asarray(h), p, whole_cfg).reshape(x.shape)
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(routed, want, atol=2e-6)
    # and the model's reference, given one share, adds that share's part alone
    here, cfg3, part = as_chip(3)
    np.testing.assert_allclose(
        part, ref._experts(jnp.asarray(h), here, cfg3).reshape(x.shape), atol=2e-6)


# -------------------------------------------------------------- the engine


def test_the_readers_read_the_share_off_the_counters(weights, small_pieces, monkeypatch):
    """``engine.expert_load_imbalance`` is fullest x HELD / pairs here (the
    field named ``n_routed_experts`` is the experts held, not the router's
    width), ``engine.expert_held_share`` pairs here / pairs routed."""
    from distrl_llm_tpu import telemetry
    from perfbench.readers import delta_moe_work, latent_moe_work

    before = dict(telemetry.observe_snapshot()["counters"])
    fs.generate(FAMILY, fs.engine(FAMILY, "waves", 0))
    after = telemetry.observe_snapshot()["counters"]
    moved = {name: after[name] - before.get(name, 0) for name in after}
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"counters": moved})
    here, fullest, routed = (moved["engine/moe_assignments"],
                             moved["engine/moe_max_expert_load"], moved["engine/moe_pairs_routed"])
    model = dataclasses.asdict(CFG)
    assert model["n_routed_experts"] == 2 and model["router_experts"] == 16
    got = latent_moe_work.read({"model": model}, {
        "what": "expert_load_imbalance", "assignments": "engine/moe_assignments",
        "max_load": "engine/moe_max_expert_load"}, object())
    assert got == pytest.approx(fullest * 2 / here) and 1.0 <= got <= 2.0
    share = delta_moe_work.read({"model": model}, {
        "what": "expert_held_share", "held": "engine/moe_assignments",
        "routed": "engine/moe_pairs_routed"}, object())
    assert share == pytest.approx(100.0 * here / routed) and 0 < share < 100
    # a program without the counter (the parent) gives nothing, and does not raise
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"counters": {
        "engine/moe_assignments": 5.0}})
    assert delta_moe_work.read({"model": model}, {
        "what": "expert_held_share", "held": "engine/moe_assignments",
        "routed": "engine/moe_pairs_routed"}, object()) is None


@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_cpu_round_counts_no_kernel_steps(weights, small_pieces, scheduler, slots):
    """``ops/delta_kernel_steps`` is filed by both schedulers and reads 0 here:
    heads of 16 on a CPU take the plain form, and ``delta_step`` says so."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import delta_attention

    before = telemetry.observe_snapshot()["counters"].get(telemetry.OPS_DELTA_KERNEL_STEPS, 0)
    fs.generate(FAMILY, fs.engine(FAMILY, scheduler, slots))
    head = CFG.delta_head_dim
    assert delta_attention.dispatch_choices[
        delta_attention.dispatch_key(CFG.delta_heads, head, head)] == "plain"
    after = telemetry.observe_snapshot()["counters"]
    assert after[telemetry.OPS_DELTA_KERNEL_STEPS] == before


@pytest.mark.parametrize("ran,steps,want", [
    ("kernel", 768, 3 * 768), ("plain", 768, 0), (None, 768, 0), ("kernel", 0, None)])
def test_the_counter_is_layers_times_steps_where_the_kernel_ran(monkeypatch, ran, steps, want):
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import delta_attention

    assert CFG.kind_count("delta") == 3
    head = CFG.delta_head_dim
    monkeypatch.setattr(delta_attention, "dispatch_choices", {} if ran is None else {
        delta_attention.dispatch_key(CFG.delta_heads, head, head): ran})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_delta_telemetry(CFG, steps)
    assert filed == ([] if want is None else [("ops/delta_kernel_steps", want)])
    # a model without such layers files nothing
    filed.clear()
    paged_engine._record_delta_telemetry(PRESETS["tiny"], 768)
    assert filed == []


# ------------------------------------------- the refusals, adapters and placement


def test_every_models_refusal_names_its_own_state():
    sala = dataclasses.replace(
        CFG, mixer_types=("minicpm4", "lightning-attn"), num_layers=2,
        lightning_heads=4, lightning_head_dim=16)
    with pytest.raises(ValueError, match="a selector cache of pooled keys and a recurrent "
                                         "float32 state"):
        sala.refuse_hybrid("x")
    with pytest.raises(ValueError, match="one latent row a token in place of K and V"):
        PRESETS["tiny-latent-moe"].refuse_hybrid("x")
    PRESETS["tiny"].refuse_hybrid("x")  # a dense decoder is held by everything


def test_adapter_factors_follow_each_kinds_shapes_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == {"softmax", "delta"}
    for kind, kv in (("softmax", 32), ("delta", 64)):
        stack = lora["layers"][kind]
        # no router, no routed expert, no low-rank pair, no beta, no filter
        assert set(stack) == set(DEFAULT_TARGETS)
        assert stack["wk"]["b"].shape[-1] == kv and stack["wq"]["b"].shape[-1] == 64
        assert stack["w_gate"]["b"].shape[-1] == 32  # the shared expert's width
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_new_leaf_has_a_partition_spec(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    specs = param_specs(params)["layers"]
    for name in ("conv", "wf_a", "wf_b", "wb", "wg_a", "wg_b", "A_log", "dt_bias",
                 "head_norm", "router", "experts_gate"):
        leaf = params["layers"]["delta"][name]
        assert specs["delta"][name] == P(*([None] * leaf.ndim)), name
    assert specs["softmax"]["wg"] == P(None, "fsdp", "tp")
    assert specs["delta"]["wq"] == P(None, "fsdp", "tp")
    assert param_specs(lora)["layers"]["delta"]["wo"]["a"] == P(None, "tp", None)
