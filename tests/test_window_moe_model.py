"""A window model with routed experts (K-EXAONE-236B-A23B, ``exaone_moe``)
against its plain reference, ``perfbench/reference_window_moe.py`` (full causal
scores with the window written as a mask: no ring, no cache, no segment), at a
small size on the CPU: the ``tiny-exaone-moe`` preset (hidden 64, five layers
of the published pattern: window, window, window, full, window; a dense layer 0
before expert layers; 4 query heads over 2 KV heads of 16; a ring of 8 tokens;
2 of 16 experts held, 4 a token, beside one shared expert). Float32 throughout,
seeded weights with every term alive.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s. The
rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_window_moe.py``.
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
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig, forward, init_params
from distrl_llm_tpu.models import hybrid, moe, transformer
from distrl_llm_tpu.models.configs import PRESETS
from perfbench import reference_window_moe as ref
from perfbench import window_moe_counts

CFG = PRESETS["tiny-exaone-moe"]
#: the same model with the published window: contexts on both sides of 128
WIDE = dataclasses.replace(CFG, sliding_window=128)
#: bytes of one slot's ring in one window layer (float32 caches here): K and V
RING_BYTES = 2 * 2 * 8 * 16 * 4
RINGS = ("win_k", "win_v")


def _without(monkeypatch, name, *leaves):
    """``name`` (a function of ``hybrid`` that takes a layer's ``p`` second)
    reading a layer without ``leaves``."""
    fn = getattr(hybrid, name)
    monkeypatch.setattr(hybrid, name, lambda x, p, *a, **kw: fn(
        x, {k: v for k, v in p.items() if k not in leaves}, *a, **kw))


def _control(name, monkeypatch, cfg=CFG):
    """Bend the PROGRAM in one place (never the reference). Returns the
    configuration the program is then given."""
    if name in ("window_7", "window_9"):
        return dataclasses.replace(cfg, sliding_window=int(name[-1]))
    if name == "no_window_rope":
        monkeypatch.setattr(hybrid, "apply_rope", lambda x, cos, sin: x)
    elif name == "rope_in_full_layer":
        fs.rope_in_the_softmax_layers(monkeypatch, cfg.head_dim, cfg.rope_theta)
    elif name == "no_qk_norm":
        _without(monkeypatch, "_qkv_heads", "q_norm", "k_norm")
    elif name == "no_shared_expert":
        _without(monkeypatch, "_expert_half", "w_gate")
    elif name == "no_bias":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda h, router, bias, c: route(
            h, router, jnp.zeros_like(bias), c))
    elif name == "top_3":
        return dataclasses.replace(cfg, experts_per_token=cfg.experts_per_token - 1)
    elif name == "no_scaling":
        return dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif name == "held_shifted":
        return dataclasses.replace(cfg, expert_shard=1)
    else:
        raise AssertionError(name)
    return cfg


def _ring_precision(bits, monkeypatch):
    """A ring kept in bf16 (7 bits of mantissa) or at 3."""
    mix = hybrid._window_mix
    monkeypatch.setattr(hybrid, "_window_mix", lambda x, p, lora, cache, **kw: mix(
        x, p, lora, None if cache is None else tuple(
            jax.lax.reduce_precision(c, 8, bits) for c in cache), **kw))


def _round_check(moved, result, engine, scheduler, slots):
    """The counters are the counts module's."""
    said = moved("engine/window_pages_attended"), moved("engine/window_pages_visible")
    want = window_moe_counts.window_pages(
        dataclasses.asdict(CFG), [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    assert said == want == (4 * 8 * 24, 4 * 8 * 24)
    # expert layers x choices x rows x steps: not layer 0
    assert moved("engine/moe_pairs_routed") == 4 * 4 * 8 * 24
    assert window_moe_counts.slot_state_bytes(dataclasses.asdict(CFG), kv_bytes=4) == (
        4 * RING_BYTES)


FORWARD_CONTROLS = ["window_7", "window_9", "no_window_rope", "rope_in_full_layer",
                    "no_qk_norm", "no_shared_expert", "no_bias", "top_3", "no_scaling",
                    "held_shifted"]

FAMILY = fs.Family(
    name="window-moe", cfg=CFG, ref=ref, config_file="k-exaone-236b-ep8-L5.json",
    # Prefill in segments of 16 tokens (two pages of 8, two windows of 8) and the
    # full layer's segment a page of keys at a time, so that 40-57-token prompts
    # cross every boundary the cell's 10k-20k-token prompts cross: the ring
    # carried from segment to segment, a window that starts in the segment
    # before, the full layer over earlier segments' pages, a last segment that
    # is part padding. Decode rows dense, segments grouped.
    engine_pieces=((paged_engine, "HYBRID_PREFILL_SEGMENT", 16),
                   (moe, "expert_form", fs.expert_forms(8))),
    refusals=(
        ({"layer_types": ["sliding_attention", "chunked_attention"] * 24}, "layer_types"),
        ({"sliding_windows": [128] * 48}, "sliding_windows"),
        ({"first_k_dense_replace": 3}, "first_k_dense_replace"),
        ({"mlp_layer_types": ["dense"] + ["moe"] * 47}, "mlp_layer_types"),
        ({"mlp_layer_types": None}, "mlp_layer_types"),
        ({"rope_parameters": {"rope_theta": 1000000, "rope_type": "yarn"}}, "rope_parameters"),
        ({"n_group": 4}, "n_group"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"model_type": "exaone4"}, "exaone4")),
    loader_refusal=("exaone_moe.*seeded weights", "exaone_moe.*seeded weights"),
    # the tests' window of 8 (the published 128 is this file's own case)
    forward_cases=(("plain", False, ()), ("remat", True, ())), forward_full_logits=True,
    # a window one token short or long, RoPE dropped where it belongs or added
    # where it does not, the q/k norm, the shared expert, the bias, the count of
    # experts a token runs, the scaling factor, which experts are held
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # rows five windows long; a and b: seven targets in each of three stacks
    learner={"answer": 28, "leaves": 2 * 7 * 3},
    train_targets={kind: {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
                   for kind in ("window_dense", "window", "softmax")},
    # 8 rows through 4 slots (a freed slot takes another prompt's rings); every
    # candidate admitted at once; prefill, fan-out, lockstep
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)),
    slot_bytes=4 * RING_BYTES, round_check=_round_check,
    # a ring kept in bf16 or at 3 bits of mantissa, a ring the candidates are not
    # handed, a ring handed from the other prompt, keys a slot away from their values
    engine_controls={
        "bf16_ring": functools.partial(_ring_precision, 7),
        "ring_3_bits": functools.partial(_ring_precision, 3),
        "ring_from_other_prompt": fs.handed_each(RINGS, lambda x: jnp.roll(x, 1, axis=0)),
        "ring_not_handed": fs.handed_each(RINGS, jnp.zeros_like),
        "ring_rolled_by_one": fs.handed_each(("win_k",), lambda x: jnp.roll(x, 1, axis=2))},
    # the chip's check cannot tell a window of 127 or 129 from 128, this one can
    # tell 7 and 9 from 8
    engine_mechanisms=("window_7", "window_9", "no_window_rope", "rope_in_full_layer",
                       "no_qk_norm", "no_shared_expert"),
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 1e-5},
    state_refusals=fs.NINE_REFUSALS,
    state_refusal_says=("full_attention, sliding_attention layers",
                        "a ring of the last sliding_window tokens' K and V and no page",
                        "K/V pages for its softmax layers only"),
    span_args={"window_pages_attended": 4 * 8 * 24, "window_pages_visible": 4 * 8 * 24},
    report_tail="; slot state 0.000 GB, window 768 of 768 x 128 keys",
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


@functools.cache
def wide_weights():
    return fs.seeded(FAMILY, WIDE)


# --------------------------------------------------- what the program is told


def test_the_published_pattern_and_what_a_slot_holds():
    assert CFG.layer_kinds == ("window_dense", "window", "window", "softmax", "window")
    assert CFG.layer_runs == (("window_dense", 0, 0, 1), ("window", 1, 0, 2),
                              ("softmax", 3, 0, 1), ("window", 4, 2, 1))
    assert CFG.hybrid and CFG.window_moe
    assert not (CFG.latent or CFG.delta_moe or CFG.power or CFG.mamba)
    assert [CFG.layer_ffn(k) for k in CFG.layer_kinds] == ["dense"] + ["experts"] * 4
    assert CFG.mixer_count("window") == 4 and CFG.mixer_count("softmax") == 1
    assert CFG.model_type == "exaone_moe" and CFG.paged_layers == 1
    assert CFG.mixer_names == "full_attention, sliding_attention"
    assert CFG.held_experts == (0, 1) and CFG.router_width == 16
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    # a ring of the window's tokens a window layer, in the cache's type
    for name in ("win_k", "win_v"):
        assert [x.shape for x in state[name]] == [(5, 2, 8, 16)] * 4
        assert {x.dtype for x in state[name]} == {jnp.dtype(jnp.bfloat16)}
    assert state["lin"] == () and state["pooled"] == ()
    assert state["window_stats"].shape == (2,) and state["moe_stats"].shape == (2,)
    assert set(hybrid.ROW_STATES) >= {"win_k", "win_v", "ssm", "conv", "power", "delta"}
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert "lm_head" in params and set(params["layers"]) == {
        "window_dense", "window", "softmax"}
    mixer = {"attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "mlp_norm"}
    mlp = {"w_gate", "w_up", "w_down"}
    experts = {"router", "e_score_bias", "experts_gate", "experts_up", "experts_down"}
    assert set(params["layers"]["window_dense"]) == mixer | mlp
    assert set(params["layers"]["window"]) == set(params["layers"]["softmax"]) == (
        mixer | mlp | experts)
    # layer 0's MLP at intermediate_size, the others' shared expert at the experts'
    assert params["layers"]["window_dense"]["w_gate"].shape == (1, 64, 128)
    assert params["layers"]["window"]["w_gate"].shape == (3, 64, 32)
    assert params["layers"]["window"]["router"].shape == (3, 64, 16)
    assert params["layers"]["window"]["experts_gate"].shape == (3, 2, 64, 32)


def test_parameters_and_operations_count_both_mixers_and_both_second_halves():
    d, v = CFG.hidden_size, CFG.vocab_size
    attention = 2 * d * 64 + 2 * d * 32
    dense = 3 * d * 128

    def experts(n):
        return 3 * d * (n * 32 + 32) + d * 16

    assert CFG.total_matmul_param_count == 5 * attention + dense + 4 * experts(2) + d * v
    assert CFG.matmul_param_count == 5 * attention + dense + 4 * experts(4) + d * v
    # a full layer's token attends its context, a window layer's at most the window
    assert CFG.decode_flops_per_token(100.0) == (
        2.0 * CFG.matmul_param_count + 4.0 * 64 * (100.0 + 4 * 8))
    assert CFG.decode_flops_per_token(5.0) == (
        2.0 * CFG.matmul_param_count + 4.0 * 64 * (5.0 + 4 * 5.0))
    # the counts module's parameters are the program's own tree, to the unit
    model = dataclasses.asdict(CFG)
    held = sum(x.size for x in jax.tree_util.tree_leaves(
        init_params(jax.random.PRNGKey(0), CFG)))
    assert window_moe_counts.param_count(model) == held


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert file["share"] == {"chips_per_layer": 8,
                             "published": {"num_experts": 128, "vocab_size": 153600}}
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    assert cfg.layer_kinds == ("window_dense", "window", "window", "softmax", "window")
    assert len(cfg.mixer_types) == len(cfg.mlp_types) == 48  # the lists stay whole
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (6144, 64, 8, 128)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.vocab_size) == (
        18432, 2048, 19200)
    assert (cfg.n_routed_experts, cfg.router_width, cfg.experts_per_token) == (16, 128, 8)
    assert cfg.held_experts == tuple(range(16)) and cfg.n_shared_experts == 1
    assert cfg.sliding_window == 128 and cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-5
    assert cfg.routed_scaling_factor == 2.5 and cfg.norm_topk_prob and cfg.qk_norm
    assert not cfg.attn_use_rope and not cfg.tie_word_embeddings and not cfg.attention_bias
    assert cfg.paged_layers == 1 and cfg.model_type == "exaone_moe"
    for key in ("qk_norm", "rope_placement", "norm_placement", "window", "correction_bias",
                "shared_expert", "adapter_targets", "mtp_module", "unread_keys", "weights"):
        assert file["assumed"][key], key
    assert "NOT INSTANTIATED" in file["assumed"]["mtp_module"]
    assert "3,712M parameters, 7.42 GB" in file["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every number of the catalog's row, under its key
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "K-EXAONE-236B-A23B")
        assert file["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if file.get(k, "absent") != v} == set(
            file["reduced"])
    # the whole configuration file: 3,712,028,416 parameters, to the unit
    model = dataclasses.asdict(cfg)
    assert window_moe_counts.param_count(model) == 3_712_028_416
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 3_712_028_416


def test_the_window_refuses_nothing_and_a_dense_models_still_does():
    CFG.check_within_window(10**6)  # a ring, not a limit
    with pytest.raises(ValueError, match="sliding_window"):
        PRESETS["mistral-7b"].check_within_window(5000)
    with pytest.raises(ValueError, match="sliding_window"):
        dataclasses.replace(CFG, sliding_window=None)


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("tokens", [100, 128, 129, 3 * 128 + 5])
def test_forward_equals_the_reference_on_both_sides_of_the_window(tokens, side):
    """``full`` mode at the published window of 128: a context inside the
    window, exactly the window, one past it and three windows and five long,
    padded on either side."""
    params, lora = wide_weights()
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(tokens), (2, tokens + 9), 1, 256))
    mask = np.ones_like(ids)
    if side == "left":
        mask[0, :9] = 0
    else:
        mask[0, tokens:] = 0
    both = (mask[:, 1:] * mask[:, :-1]) > 0
    want = fs.reference_logprobs(FAMILY, params, lora, ids, mask, WIDE)
    got = fs.forward_logprobs(FAMILY, params, lora, ids, mask, WIDE)
    assert np.abs(got - want)[both].max() < 2e-5


def test_the_band_is_a_mask_the_kernels_refuse_by_name():
    from distrl_llm_tpu.ops.attention import attention, causal_padding_mask

    band = np.asarray(causal_padding_mask(jnp.ones((1, 6), jnp.int32), 6, window=3))[0, 0]
    want = np.array([[c <= r and r - c < 3 for c in range(6)] for r in range(6)])
    assert (band == want).all()
    q = jnp.ones((1, 6, 2, 8))
    for impl in ("flash", "splash"):
        with pytest.raises(NotImplementedError, match="no band"):
            attention(q, q, q, None, impl=impl, key_valid=jnp.ones((1, 6), jnp.int32), window=3)


# --------------------------------------------------------------- the share


def test_eight_shares_sum_to_the_whole_layer_and_eight_slices_to_the_whole_head():
    """The eight chips' routed parts, with the shared expert and the mixer
    counted once, are what the uncut reference gives for the whole layer; the
    program's part for a share is the reference's; and the eight vocabulary
    slices' logits concatenate to the whole head's."""
    whole, uncut = fs.shares_add_up(FAMILY, "window", 8)
    # the vocabulary: a slice of the head's columns is that slice of the logits
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 32, (1, 20)))
    full = ref.full_logits(whole, uncut, ids, jnp.ones_like(ids))
    pieces = []
    for piece in range(8):
        cols = slice(32 * piece, 32 * piece + 32)
        cut = {**whole, "lm_head": whole["lm_head"][:, cols]}
        pieces.append(ref.full_logits(cut, uncut, ids, jnp.ones_like(ids)))
    np.testing.assert_allclose(jnp.concatenate(pieces, -1), full, atol=1e-6)


# -------------------------------------------------------------- the engine


def test_past_the_window_the_counters_say_what_was_spared(monkeypatch):
    """At the published window, prompts of 150 and 260 tokens in segments of
    64: the rings attend one unit of 128 keys a step where a full layer would
    attend two or three, and the engine still equals the reference."""
    from distrl_llm_tpu import telemetry

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 64)
    params, lora = wide_weights()
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = fs.make_engine(FAMILY, "waves", 0, WIDE, prompt=272, page_size=16)
    ids, mask = fs.prompts((150, 260), 272)
    result = engine.generate(
        params, lora, ids, mask, SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=24),
        jax.random.PRNGKey(3))
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result, WIDE) < 2e-5
    after = telemetry.observe_snapshot()["counters"]
    said = tuple(after[f"engine/window_pages_{k}"] - before.get(f"engine/window_pages_{k}", 0)
                 for k in ("attended", "visible"))
    want = window_moe_counts.window_pages(
        dataclasses.asdict(WIDE), [150, 150, 260, 260], result.lengths.reshape(-1))
    # 150 + j stays under 256: two units; 260 + j is three: 2.5 on average
    assert said == want == (4 * 4 * 24, 4 * 2 * 24 * (2 + 3))
    assert window_moe_counts.window_kv_bytes(
        dataclasses.asdict(WIDE), [150], [24], kv_bytes=4) == 4 * 24 * 128 * 2 * 32 * 4


def test_the_prompts_ring_is_its_last_window_rotated_and_in_place(weights, small_pieces):
    """What the prefill returns for the fan-out: a ring a window layer a prompt
    that holds the prompt's last 8 tokens' K (normed, ROTATED) and V, position
    ``p`` at slot ``p % 8``, and pages for the one full layer only."""
    params, lora = weights
    ids, mask, (k, v, logits, real_len, mixer) = fs.prefilled(FAMILY, params, lora)
    assert len(k) == len(v) == 1 and k[0].shape == (2, 16, 8, 16)
    assert list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["win_k"]] == [(2, 2, 8, 16)] * 4
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=fs.LORA_SCALE)[:, -1]
    np.testing.assert_allclose(logits, want, atol=2e-5)
    # layer 0's ring from its own weights: RMSNorm, W_k with its adapter, the
    # head's norm, RoPE at the token's position
    layer = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["window_dense"])
    ab = jax.tree_util.tree_map(lambda w: w[0], lora["layers"]["window_dense"])
    for row, n in enumerate((40, 57)):
        last = jnp.asarray(ids[row, -8:])
        h = transformer.rms_norm(jnp.take(params["embed"], last, axis=0),
                                 layer["attn_norm"], CFG.rms_norm_eps)
        for name, ring in (("wk", mixer["win_k"][0]), ("wv", mixer["win_v"][0])):
            y = (h @ layer[name] + fs.LORA_SCALE * (h @ ab[name]["a"]) @ ab[name]["b"])
            y = y.reshape(8, 2, 16)
            pos = jnp.arange(n - 8, n)
            if name == "wk":
                y = transformer.rms_norm(y, layer["k_norm"], CFG.rms_norm_eps)
                cos, sin = transformer.rope_cos_sin(pos[None], 16, CFG.rope_theta)
                y = transformer.apply_rope(y[None], cos, sin)[0]
            held = np.asarray(ring[row]).transpose(1, 0, 2)[np.asarray(pos) % 8]
            np.testing.assert_allclose(held, y, atol=2e-5, err_msg=f"{name} row {row}")


def test_a_candidates_ring_is_a_copy_that_no_other_candidate_sees(weights, small_pieces):
    """Two candidates handed one prompt's rings, fed different tokens: each
    writes its own ring at the token's slot and nowhere else, and the prompt's
    ring is as it was."""
    params, lora = weights
    _, _, (k, v, _, real_len, mixer) = fs.prefilled(FAMILY, params, lora)
    blank = hybrid.init_mixer_state(CFG, 2, 88, jnp.float32)
    handed = paged_engine._hand_mixer(
        blank, mixer, jnp.asarray([1, 1]), jnp.asarray([True, True]))
    cache = {**handed, "k": k, "v": v, "lengths": jnp.asarray([57, 57], jnp.int32),
             "page_indices": jnp.tile(jnp.arange(8, 16, dtype=jnp.int32)[None], (2, 1)),
             "alive": jnp.asarray([True, True])}
    # the candidates' next pages would be their own; the decode token lands in
    # the prompt's last page here, which only the full layer reads
    _, out = forward(transformer.decode_view(params), CFG, jnp.asarray([[5], [9]]),
                     lora=lora, lora_scale=fs.LORA_SCALE, kv_cache=cache, page_size=8,
                     paged_impl="reference")
    for name in ("win_k", "win_v"):
        for before, after in zip(mixer[name], out[name]):
            a, b = np.asarray(after[0]), np.asarray(after[1])
            assert np.abs(a[:, 57 % 8] - b[:, 57 % 8]).max() > 1e-3  # each wrote its own
            others = [s for s in range(8) if s != 57 % 8]
            np.testing.assert_array_equal(a[:, others], b[:, others])
            np.testing.assert_array_equal(a[:, others], np.asarray(before[1])[:, others])
    assert list(np.asarray(out["window_stats"])) == [4 * 2, 4 * 2]  # 4 layers x 2 rows, a unit


# --------------------------------------------- the budget, adapters and placement


def test_a_page_costs_its_one_full_layer_and_a_slot_its_rings():
    """A token costs K and V in the full layers alone (a fifth of what a
    full-attention model of this shape pays), a slot's rings come off the
    budget first."""
    from distrl_llm_tpu.engine import budget

    assert budget.page_bytes(CFG, 8) == 1 * 8 * 32 * 2 * 2  # 2 heads of 16, bf16, K and V
    slot = 4 * 2 * 2 * 8 * 16 * 2  # four layers' rings in bf16
    assert budget.slot_state_bytes(CFG, 88) == slot
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**8)
    assert budget.kv_pool_pages(CFG, slots=8, **common) == (
        int(10**8 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6
            - 2 * 8 * budget.page_bytes(CFG, 8) - 10 * slot) // budget.page_bytes(CFG, 8))
    # the published widths: 4 KB of pages a token, 512 KB of ring a layer a slot
    full = ModelConfig.from_hf_config(SimpleNamespace(**json.load(open(CONFIG_FILE))))
    assert budget.page_bytes(full, 128) == 128 * 4096
    assert budget.slot_state_bytes(full, 20992) == 4 * 524_288
    model = dataclasses.asdict(full)
    assert window_moe_counts.kv_token_bytes(model) == 4096
    assert window_moe_counts.ring_bytes(model) == 524_288


def test_adapter_factors_are_each_stacks_own_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == {"window_dense", "window", "softmax"}
    for kind in lora["layers"]:
        assert set(lora["layers"][kind]) == set(DEFAULT_TARGETS)
    # the MLP's three: layer 0's dense MLP, the other layers' shared expert
    assert lora["layers"]["window_dense"]["w_gate"]["b"].shape == (1, 4, 128)
    assert lora["layers"]["window"]["w_gate"]["b"].shape == (3, 4, 32)
    assert lora["layers"]["softmax"]["wk"]["b"].shape == (1, 4, 32)
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_leaf_has_a_partition_spec_and_the_view_holds_the_new_kinds(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.ops.linear import OutIn
    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    for kind, stack in params["layers"].items():
        specs = param_specs(params)["layers"][kind]
        for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
            assert specs[name] == P(None, None), (kind, name)
        assert specs["wq"] == P(None, "fsdp", "tp") and specs["wo"] == P(None, "tp", "fsdp")
        if "router" in stack:
            assert specs["experts_gate"] == P(None, None, None, None)
    assert param_specs(lora)["layers"]["window"]["wo"]["a"] == P(None, "tp", None)
    # the decode view holds q, k, v, o of every new kind a layer at a time
    view = transformer.decode_view(params)
    listed = {path for path, _ in transformer.decode_view_leaves(params["layers"])}
    assert listed == {(kind, key) for kind in params["layers"]
                      for key in ("wq", "wk", "wv", "wo")}
    for kind, stack in view["layers"].items():
        for key in ("wq", "wk", "wv", "wo"):
            assert isinstance(stack[key], tuple) and isinstance(stack[key][0], OutIn)
        assert stack["w_gate"] is params["layers"][kind]["w_gate"]


def test_the_new_scope_and_counters_are_the_programs_constants():
    from distrl_llm_tpu import telemetry

    assert telemetry.MODEL_WINDOW_ATTN == "model/window_attn"
    assert telemetry.MODEL_WINDOW_ATTN in telemetry.SCOPE_NAMES
    assert telemetry.ENGINE_WINDOW_PAGES_ATTENDED == "engine/window_pages_attended"
    assert telemetry.ENGINE_WINDOW_PAGES_VISIBLE == "engine/window_pages_visible"
    assert hybrid.WINDOW_COUNT_UNIT == window_moe_counts.COUNT_UNIT == 128
