"""Compressed convolutional attention with an MLP router (ZAYA1-8B, ``zaya``)
against its plain reference, ``perfbench/reference_cca_moe.py`` (full causal
scores over the whole row: no cache, no page, no tail), at a small size on the
CPU: the ``tiny-cca`` preset (hidden 64, three layers, 4 query heads over 2 KV
heads of 16 in the latent, the first 8 of a head rotated, a router of width 16
choosing 1 of 4 experts). Float32 throughout, seeded weights with every term
alive.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s. The
rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_cca_moe.py``.
"""

import dataclasses
import functools
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import family_suite as fs
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig, init_params
from distrl_llm_tpu.models import hybrid, moe
from distrl_llm_tpu.models.configs import PRESETS
from perfbench import cca_moe_counts
from perfbench import reference_cca_moe as ref

CFG = PRESETS["tiny-cca"]
#: bytes of one slot's tail in one layer (float32 caches here)
TAIL_BYTES = (2 * (64 + 32) + 16) * 4

def _bent_layer(monkeypatch, **leaves):
    """``_cca_mix`` reading a layer whose ``leaves`` are bent."""
    mix = hybrid._cca_mix
    monkeypatch.setattr(hybrid, "_cca_mix", lambda x, p, *a, **kw: mix(
        x, {**p, **{k: bend(p[k]) for k, bend in leaves.items()}}, *a, **kw))


def _bent_router(monkeypatch, bend):
    """``route_mlp`` with ``bend(h, carried, p) -> (h, carried, p)`` ahead of it."""
    route = hybrid.route_mlp
    monkeypatch.setattr(hybrid, "route_mlp", lambda h, carried, p, cfg: route(
        *bend(h, carried, p), cfg))


def _control(name, monkeypatch):
    """Bend the PROGRAM in one place (never the reference)."""
    if name == "shift_dropped":  # every value head from this token
        late = hybrid._shifted
        monkeypatch.setattr(hybrid, "_shifted", lambda x, before, valid: (
            (x, late(x, before, valid)[1]) if x.shape[-1] == CFG.kv_dim // 2
            else late(x, before, valid)))
    elif name == "qk_mean_dropped":
        mean = hybrid._qk_mean
        monkeypatch.setattr(hybrid, "_qk_mean", lambda q, k: jax.tree_util.tree_map(
            jnp.zeros_like, mean(q, k)))
    elif name == "temperature_dropped":
        _bent_layer(monkeypatch, k_temp=jnp.ones_like)
    elif name == "first_convolution_dropped":  # c1 = u
        _bent_layer(monkeypatch, conv0=lambda w: jnp.zeros_like(w).at[1].set(1.0),
                    b_conv0=jnp.zeros_like)
    elif name == "second_convolution_dropped":  # c2 = c1
        eye = lambda w: jnp.zeros_like(w).at[1].set(jnp.eye(w.shape[-1], dtype=w.dtype))
        _bent_layer(monkeypatch, conv1=eye, b_conv1=jnp.zeros_like)
    elif name == "r_not_carried":
        _bent_router(monkeypatch, lambda h, carried, p: (h, jnp.zeros_like(carried), p))
    elif name == "p_not_multiplied":
        route = hybrid.route_mlp

        def unweighted(h, carried, p, cfg):
            idx, w, r = route(h, carried, p, cfg)
            return idx, jnp.ones_like(w), r
        monkeypatch.setattr(hybrid, "route_mlp", unweighted)
    elif name == "top_1_before_the_bias":
        _bent_router(monkeypatch, lambda h, carried, p: (
            h, carried, {**p, "e_score_bias": jnp.zeros_like(p["e_score_bias"])}))
    elif name == "residual_unscaled":
        merge = hybrid._merge
        monkeypatch.setattr(hybrid, "_merge", lambda x, y, p, half: merge(x, y, {}, half))
    elif name == "whole_head_rotated":
        return dataclasses.replace(CFG, rotary_dim=CFG.head_dim)
    else:
        raise AssertionError(name)


def _wrong_tail(name, monkeypatch):
    """What only the cache path can get wrong: a tail taken at the SEGMENT's
    last token where the row's prompt ended before it, one kept in bf16."""
    if name == "tail_at_the_segments_end":
        late = hybrid._shifted
        monkeypatch.setattr(hybrid, "_shifted", lambda x, before, valid: late(x, before, None))
    else:
        mix = hybrid._cca_mix
        monkeypatch.setattr(hybrid, "_cca_mix", lambda x, p, lora, cache, **kw: mix(
            x, p, lora, None if cache is None else (
                *cache[:2], jax.lax.reduce_precision(cache[2], 8, 7)), **kw))


def _tail(change):
    return fs.handed(lambda m: {**m, "cca_tail": tuple(map(change, m["cca_tail"]))})


def _round_check(moved, result, engine, scheduler, slots):
    assert moved("engine/moe_assignments") == 3 * 8 * 16  # layers x rows x steps: one expert
    assert cca_moe_counts.slot_state_bytes(dataclasses.asdict(CFG), kv_bytes=4) == (
        3 * TAIL_BYTES)


FORWARD_CONTROLS = ["shift_dropped", "qk_mean_dropped", "temperature_dropped",
                    "first_convolution_dropped", "second_convolution_dropped",
                    "r_not_carried", "p_not_multiplied", "top_1_before_the_bias",
                    "residual_unscaled", "whole_head_rotated"]

FAMILY = fs.Family(
    name="cca-moe", cfg=CFG, ref=ref, config_file="zaya1-8b-L20.json",
    # norms, temperatures, taps and the residual's scales off 1, every bias and
    # shift off 0, a router whose probabilities differ by more than its bias
    seed_rules=(
        (fs.named("k_temp", "attn_res_scale", "mlp_res_scale"), fs.normal(0.3, 1.0)),
        (fs.named("conv0", "router_gamma"), fs.normal(0.3, 0.6)),
        (lambda name: name.startswith("b_") or name.endswith("res_shift"), fs.normal(0.1)),
        (fs.starting("router_w"), fs.times(30.0))),
    # Prefill in segments of 16 tokens (two pages of 8) scored a page of keys at
    # a time, so that prompts of 37 and 57 tokens cross what the cell's
    # 512-2,048-token prompts cross: a segment's first two tokens read the tail
    # the segment before left, a last segment that is part padding ends on NO
    # multiple of the convolutions' reach (37 = 2 x 16 + 5, 57 = 3 x 16 + 9), the
    # shorter row rides two segments past its end, and a prompt's partial last
    # page is copied beside its tail. Decode rows dense, segments grouped.
    engine_pieces=((paged_engine, "HYBRID_PREFILL_SEGMENT", 16),
                   (moe, "expert_form", fs.expert_forms(8))),
    engine_kw={"max_new_tokens": 16}, lengths=(37, 57),
    refusals=(
        ({"sliding_window": 4096}, "sliding_window"),
        ({"layer_types": ["hybrid", "hybrid_sliding"] * 20}, "layer_types"),
        ({"layer_types": None}, "layer_types"),
        ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
        ({"cca_time0": 4}, "cca_time0"),
        ({"cca_time1": 3}, "cca_time1"),
        ({"attention_bias": True}, "attention_bias"),
        ({"lm_head_bias": True}, "lm_head_bias"),
        ({"share": {"chips_per_layer": 2, "published": {"num_experts": 16}}},
         "reads no share yet"),
        ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
        ({"rope_parameters": {"hybrid": {"rope_theta": 5000000, "rope_type": "yarn"}}},
         "rope_parameters"),
        ({"zaya_use_mod": True}, "zaya_use_mod"),
        ({"zaya_use_eda": False}, "zaya_use_eda"),
        ({"scale_residual_merge": False}, "scale_residual_merge"),
        ({"model_type": "zaya1_vl"}, "zaya1_vl")),
    loader_refusal=("zaya.*seeded weights", "zaya.*seeded weights"),
    forward_cases=(("plain", False, ()), ("remat", True, ())), forward_full_logits=True,
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    learner={"answer": 20, "leaves": 2 * 5},  # a and b of q, k, the value's two halves and o
    train_targets={"cca": {"wq", "wk", "wv1", "wv2", "wo"}},
    # 8 rows through 4 slots (a freed slot takes another prompt's tail); prefill,
    # fan-out, lockstep
    rounds=(("refill", 4), ("waves", 0)), slot_bytes=3 * TAIL_BYTES, round_check=_round_check,
    engine_controls={
        "tail_not_handed": _tail(jnp.zeros_like),
        "tail_from_other_prompt": _tail(lambda x: jnp.roll(x, 1, axis=0)),
        "tail_at_the_segments_end": functools.partial(_wrong_tail, "tail_at_the_segments_end"),
        "bf16_tail": functools.partial(_wrong_tail, "bf16_tail")},
    engine_mechanisms=("shift_dropped", "second_convolution_dropped", "r_not_carried"),
    # the nine refusals that name a row state name this one: pages AND a tail
    state_refusals=fs.NINE_REFUSALS,
    state_refusal_says=("hybrid layers",
                        "K/V pages and, beside them in the same layer, a row state",
                        "convolutions' tail"),
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)

# --------------------------------------------------- what the program is told


def test_one_kind_that_keeps_pages_and_a_tail_in_every_layer():
    assert CFG.layer_kinds == ("cca",) * 3 and CFG.layer_runs == (("cca", 0, 0, 3),)
    assert CFG.hybrid and CFG.cca and CFG.layer_ffn("cca") == "experts"
    assert not (CFG.latent or CFG.delta_moe or CFG.power or CFG.mamba or CFG.window_moe)
    assert CFG.model_type == "zaya" and CFG.paged_layers == 3 and CFG.held_experts is None
    assert CFG.cca_tail_dim * 4 == TAIL_BYTES
    assert hybrid._MIXER_CACHE["cca"] == ("k", "v", "cca_tail")
    assert "cca_tail" in hybrid.ROW_STATES
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    assert [x.shape for x in state["cca_tail"]] == [(5, 208)] * 3
    assert {x.dtype for x in state["cca_tail"]} == {jnp.dtype(jnp.bfloat16)}
    assert state["moe_stats"].shape == (2,) and "moe_routed" not in state
    assert "pages and" in CFG.slot_state_names and "tail" in CFG.slot_state_names
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert "lm_head" not in params and set(params["layers"]) == {"cca"}
    stack = params["layers"]["cca"]
    assert stack["wq"].shape == (3, 64, 64) and stack["wk"].shape == (3, 64, 32)
    assert stack["wv1"].shape == stack["wv2"].shape == (3, 64, 16)
    assert stack["conv0"].shape == (3, 2, 96) and stack["conv1"].shape == (3, 2, 6, 16, 16)
    assert stack["router_w3"].shape == (3, 16, 4) and stack["k_temp"].shape == (3, 2)
    assert stack["experts_gate"].shape == (3, 4, 64, 32)
    assert "w_gate" not in stack and "router" not in stack


def test_parameters_and_operations_are_the_programs_tree():
    d, r = 64, 16
    layer = (2 * d * 64 + 2 * d * 32) + 2 * 6 * 16 * 16 + (d * r + 2 * r * r + r * 4)
    assert CFG.total_matmul_param_count == 3 * (layer + 4 * 3 * d * 32) + d * 256
    assert CFG.matmul_param_count == 3 * (layer + 3 * d * 32) + d * 256
    assert CFG.decode_flops_per_token(100.0) == (
        2.0 * CFG.matmul_param_count + 4.0 * 3 * 64 * 100.0)
    held = sum(x.size for x in jax.tree_util.tree_leaves(
        init_params(jax.random.PRNGKey(0), CFG)))
    assert cca_moe_counts.param_count(dataclasses.asdict(CFG)) == held


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == ["num_hidden_layers"] and "share" not in file
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    assert cfg.layer_kinds == ("cca",) * 20 and len(cfg.mixer_types) == 40
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2048, 8, 2, 128)
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.moe_intermediate_size) == (
        16, 1, 2048)
    assert (cfg.router_hidden_size, cfg.cca_time0, cfg.cca_time1) == (256, 2, 2)
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 5e6 and cfg.rms_norm_eps == 1e-5
    assert cfg.vocab_size == 262272 and cfg.tie_word_embeddings and cfg.sliding_window is None
    assert cfg.cca_tail_dim == 2688 and cfg.paged_layers == 20 and cfg.model_type == "zaya"
    for key in ("conv_groups_and_bias", "rope_pairs", "depth_averaging", "router_mlp",
                "balancing_bias", "residual_scaling", "skip_choice_not_modelled",
                "adapter_targets", "frozen", "weights"):
        assert file["assumed"][key], key
    assert "NOT MODELLED" in file["assumed"]["skip_choice_not_modelled"]
    assert "4,689M parameters, 9.38 GB" in file["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
        assert file["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if file.get(k, "absent") != v} == set(
            file["reduced"])
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert cca_moe_counts.param_count(dataclasses.asdict(cfg)) == count
    assert 4_685_000_000 < count < 4_695_000_000  # the issue's 4,689M


# ---------------------------------------------------- the family's own mechanism


def test_the_router_chooses_one_expert_the_lower_index_among_equals(weights, monkeypatch):
    params, _ = weights
    layer = jax.tree_util.tree_map(lambda w: w[1], params["layers"]["cca"])
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    carried = jax.random.normal(jax.random.PRNGKey(8), (24, 16))
    idx, w, r = moe.route_mlp(h, carried, layer, CFG)
    prob, r_want = ref.router(h, carried, layer, CFG)
    np.testing.assert_allclose(r, r_want, atol=2e-6)
    assert idx.shape == w.shape == (24, 1) and idx.dtype == jnp.int32
    want = np.argmax(np.asarray(prob) + np.asarray(layer["e_score_bias"]), -1)
    assert (np.asarray(idx[:, 0]) == want).all() and len(set(want.tolist())) > 1
    np.testing.assert_allclose(w[:, 0], np.asarray(prob)[np.arange(24), want], atol=2e-6)
    # zeros carried are layer 0's: the sum adds nothing
    first = moe.route_mlp(h, jnp.zeros_like(carried), layer, CFG)
    np.testing.assert_allclose(first[2], ref.router(h, None, layer, CFG)[1], atol=2e-6)
    # equal probabilities: the first
    flat = {**layer, "router_w3": jnp.zeros_like(layer["router_w3"]),
            "e_score_bias": jnp.zeros_like(layer["e_score_bias"])}
    assert (np.asarray(moe.route_mlp(h, carried, flat, CFG)[0]) == 0).all()
    # both forms of the experts give the reference's part: 24 tokens of one
    # choice are dense by the rule (as the cell's 192 are), grouped when told
    assert moe.expert_form(24, 1, 16) == moe.expert_form(192, 1, 16) == 0
    for most in (192, 8):
        monkeypatch.setattr(moe, "expert_form", fs.expert_forms(most))
        y, stats = moe.moe_half(h, layer, CFG, choice=(idx, w))
        np.testing.assert_allclose(y, ref.routed_part(h, prob, layer), atol=2e-5)
        assert int(stats[0]) == 24


def test_the_fan_out_hands_the_tail_and_the_pages(weights, small_pieces):
    """Greedy, 8 candidates of one prompt are 8 times the single row; and a
    prompt that ends ON a page (40 = 5 pages of 8) needs no partial page."""
    params, lora = weights
    greedy = dict(temperature=0.0, top_p=1.0, max_tokens=12)
    ids, mask = fs.prompts((45,))
    engine = fs.engine(FAMILY, "waves", 0)
    many = engine.generate(
        params, lora, ids, mask, SamplingConfig(n=8, **greedy), jax.random.PRNGKey(3))
    one = engine.generate(
        params, lora, ids, mask, SamplingConfig(n=1, **greedy), jax.random.PRNGKey(3))
    assert (many.tokens[0] == one.tokens[0, 0]).all()
    np.testing.assert_allclose(many.logprobs[0], np.broadcast_to(
        one.logprobs[0, 0], many.logprobs[0].shape), atol=2e-6)
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, one) < 2e-5
    ids, mask, whole = fs.generate(FAMILY, engine, lengths=(40,), n=8)
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, whole) < 2e-5
