"""The second window family (MiMo-V2-Flash, ``mimo_v2_flash``) against its plain
reference, ``perfbench/reference_swa_sink_moe.py`` (causal scores with the band
and the sink written out: no ring, no cache, no segment, no page), at a small
size on the CPU: the ``tiny-swa-sink-moe`` preset (hidden 64, seven layers as
the cut's: layer 0 full and dense, then one whole period of five window layers
and a full one; 8 query heads over 2 KV heads in the full layers and 4 in the
window layers; q and k of 24 over v of 16; the first 8 of a head rotated at a
base a kind; a sink a query head in the window layers; a ring of 8 tokens; 2 of
8 experts held, 3 a token, no shared expert). Float32 throughout, seeded
weights with every term alive.

The rollout through ``perfbench/run.py`` is
held by ``tests/perfbench/test_perfbench_rehearsal_swa_sink_moe.py``.

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
from distrl_llm_tpu.models import ModelConfig, configs, init_params
from distrl_llm_tpu.models import hybrid, moe
from distrl_llm_tpu.models.configs import PRESETS
from perfbench import reference_swa_sink_moe as ref
from perfbench import swa_sink_moe_counts as counts

CFG = PRESETS["tiny-swa-sink-moe"]
#: lanes a cached key's row takes in the engine tests: a "tile" of 16 lanes there
#: (``SMALL_PIECES``), so that a head of 24 is a tile and a half as 192 is of 128
KEY_ROW = 32
#: bytes of one slot's rings in one window layer (float32 caches here): 4 KV
#: heads x 8 tokens x (a key's row + 16 values)
RING_BYTES = 4 * 8 * (KEY_ROW + 16) * 4
#: bytes one token costs a full layer's pages: 2 KV heads x (a key's row + 16 values)
TOKEN_BYTES = 2 * (KEY_ROW + 16) * 4
#: Prefill in segments of 12 tokens (three pages of 4) under a window of 8: a
#: segment does NOT end on the window's edge, so 40- and 57-token prompts cross
#: every boundary the cell's 10k-20k-token prompts cross and one more: the ring
#: carried from segment to segment mid-window, a window that starts in the
#: segment before, the full layers over earlier segments' pages a page of keys
#: at a time, a last segment that is part padding. A "lane tile" of 16: a key of
#: 24 is kept in 32 lanes, zeros after it, in the pages and in the rings, as the
#: chip keeps 192 in 256. Decode rows dense, segments grouped.
SMALL_PIECES = ((configs, "KEY_ROW_LANES", 16), (paged_engine, "HYBRID_PREFILL_SEGMENT", 12),
                (moe, "expert_form", fs.expert_forms(8)))


def _with_attention(monkeypatch, bend):
    """Every softmax of the PROGRAM's three modes (``hybrid.attention``, the
    ring's and the segment's ``hybrid.attention_reference``) called through
    ``bend(fn, q, k, v, mask, kw)``; a window layer's call is told by its
    ``sink`` keyword."""
    for name in ("attention", "attention_reference"):
        fn = getattr(hybrid, name)
        monkeypatch.setattr(
            hybrid, name,
            lambda q, k, v, mask, fn=fn, **kw: bend(fn, q, k, v, mask, kw))


def _control(name, monkeypatch):
    """Bend the PROGRAM in one place (never the reference). Returns the
    configuration the program is then given."""
    for module, attribute, value in SMALL_PIECES:  # this file's controls always ran under them
        monkeypatch.setattr(module, attribute, value)
    cfg, params = CFG, fs.weights(FAMILY)[0]
    if name == "no_sink":
        mix = hybrid._window_mix
        monkeypatch.setattr(hybrid, "_window_mix", lambda x, p, *a, **kw: mix(
            x, {k: v for k, v in p.items() if k != "sink"}, *a, **kw))
    elif name == "sink_in_full_layers":
        fake = params["layers"]["window"]["sink"][0]
        _with_attention(monkeypatch, lambda fn, q, k, v, mask, kw: fn(
            q, k, v, mask, **{**kw, "sink": kw.get("sink", fake)}))
    elif name == "sink_with_a_value":
        # the sink's column given a value row of 0.5: what it takes of the
        # denominator comes back as output (a row of zeros would be right)
        def counted(fn, q, k, v, mask, kw):
            o = fn(q, k, v, mask, **kw)
            if kw.get("sink") is None:
                return o
            kept = fn(q, k, jnp.ones_like(v), mask, **kw)  # sum_j p[t, j] = 1 - p_sink
            return o + 0.5 * (1.0 - kept)
        _with_attention(monkeypatch, counted)
    elif name == "window_grouped_as_full":
        # a query head reads KV head i // (H / 2) in a window layer too: its
        # first 2 KV heads of 4, in groups of 4 for 2
        def grouped(fn, q, k, v, mask, kw):
            if kw.get("sink") is None:
                return fn(q, k, v, mask, **kw)
            return fn(q, k[:, :, :CFG.num_kv_heads], v[:, :, :CFG.num_kv_heads], mask, **kw)
        _with_attention(monkeypatch, grouped)
    elif name == "no_value_scale":
        cfg = dataclasses.replace(cfg, value_scale=1.0)
    elif name == "value_scaled_twice":
        for mix in ("_window_mix", "_softmax_mix"):
            fs.with_proj(monkeypatch, mix, lambda key, y, env, mode: (
                y * CFG.value_scale if key == "wo" else y))
    elif name == "rope_on_all_dims":
        cfg = dataclasses.replace(cfg, rotary_dim=0)
    elif name == "bases_swapped":
        cfg = dataclasses.replace(cfg, rope_theta=cfg.window_rope_theta,
                                  window_rope_theta=cfg.rope_theta)
    elif name == "scores_over_sqrt_v":
        _with_attention(monkeypatch, lambda fn, q, k, v, mask, kw: fn(
            q * (q.shape[-1] / v.shape[-1]) ** 0.5, k, v, mask, **kw))
    elif name in ("window_7", "window_9"):
        cfg = dataclasses.replace(cfg, sliding_window=int(name[-1]))
    elif name == "no_bias":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda h, router, bias, c: route(
            h, router, jnp.zeros_like(bias), c))
    elif name == "top_2":
        cfg = dataclasses.replace(cfg, experts_per_token=2)
    else:
        raise AssertionError(name)
    return cfg


def _round_check(moved, result, engine, scheduler, slots):
    """20 tokens a row, past the window, over the slots' rings with their sink
    and the pages of two widths: the two gauges are what the pools' and the
    rings' shapes say."""
    from distrl_llm_tpu import telemetry

    said = moved("engine/window_pages_attended"), moved("engine/window_pages_visible")
    model = dataclasses.asdict(CFG)
    assert said == counts.window_pages(
        model, [40] * 4 + [57] * 4, result.lengths.reshape(-1)) == (5 * 8 * 20,) * 2
    # expert layers x choices x rows x steps: not layer 0
    assert moved("engine/moe_pairs_routed") == 6 * 3 * 8 * 20
    # one more token of context: two full layers' K (its row) and V (its width)
    assert telemetry.observe_snapshot()["gauges"]["engine/cache_token_bytes"] == 2 * TOKEN_BYTES
    # the counts module says what the ALGORITHM moves: a key's own 24 values
    assert counts.slot_state_bytes(model, kv_bytes=4) == 5 * 4 * 8 * (24 + 16) * 4
    assert counts.cache_token_bytes(model, kv_bytes=4) == 2 * 2 * (24 + 16) * 4


#: each bends what ``full`` mode runs; the last two are the router's
FORWARD_CONTROLS = ["no_sink", "sink_in_full_layers", "sink_with_a_value",
                    "window_grouped_as_full", "no_value_scale", "value_scaled_twice",
                    "rope_on_all_dims", "bases_swapped", "scores_over_sqrt_v",
                    "window_7", "window_9", "no_bias", "top_2"]

FAMILY = fs.Family(
    name="swa-sink-moe", cfg=CFG, ref=ref, config_file="mimo-v2-flash-ep16-L7.json",
    seed_rules=((fs.named("sink"), fs.normal(1.0)),),  # sinks Normal(0, 1)
    engine_pieces=SMALL_PIECES,
    engine_kw={"prompt": 60, "max_new_tokens": 20, "page_size": 4},
    refusals=(
        ({"swa_num_attention_heads": 32}, "swa_num_attention_heads"),
        ({"swa_head_dim": 128}, "swa_head_dim"),
        ({"swa_v_head_dim": 64}, "swa_v_head_dim"),
        ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"),
        ({"sliding_window_size": 256}, "sliding_window_size"),
        ({"attention_chunk_size": 64}, "attention_chunk_size"),
        ({"n_shared_experts": 1}, "n_shared_experts"),
        ({"n_group": 8}, "n_group"),
        ({"topk_group": 4}, "topk_group"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"attention_bias": True}, "attention_bias"),
        ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"hybrid_layer_pattern": [0, 2] * 24}, "hybrid_layer_pattern"),
        ({"moe_layer_freq": [0] * 7}, "moe_layer_freq")),
    # the band and the sink's column in a mask, K and V of two widths in two
    # einsums, over rows padded on either side and past the window
    forward_cases=(("plain", False, ()), ("remat", True, ())),
    # the sink dropped, added to the full layers, or counted with a value; the
    # window layers grouped as the full layers are; the value's scale dropped or
    # applied twice; RoPE on every dim; the two bases swapped; scores over
    # sqrt(16); a window one token short or long; the router's bias and count
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # rows five windows long; a and b: q, k, v, o in each of three stacks, and
    # layer 0's dense MLP's three
    learner={"answer": 28, "leaves": 2 * (4 * 3 + 3)},
    train_targets={"window": {"wq", "wk", "wv", "wo"}, "softmax": {"wq", "wk", "wv", "wo"},
                   "softmax_dense": {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}},
    # 5 window layers of 4 KV heads and 2 full layers of 2: 8 rows through 4 slots
    # (a freed slot takes another prompt's rings); prefill, fan-out, lockstep
    rounds=(("refill", 4), ("waves", 0)), slot_bytes=5 * RING_BYTES, round_check=_round_check,
    # through the engine (segments, fan-out, decode over rings and pages): a ring
    # not handed at the fan-out, a ring's V read at K's width (slot w starts
    # w x 32 values in, not w x 16), and three of the forward's bends again,
    # where the ring's softmax and the segment's run
    engine_controls={
        "ring_not_handed": fs.handed_each(("win_k", "win_v"), jnp.zeros_like),
        "ring_v_read_at_k_width": fs.handed_each(
            ("win_v",),
            lambda x: jnp.pad(x.reshape(*x.shape[:2], -1), ((0, 0), (0, 0), (0, 8 * 16)))
            .reshape(*x.shape[:2], 8, KEY_ROW)[..., :16])},
    engine_limit=2e-3,
    engine_mechanisms=("no_sink", "window_9", "scores_over_sqrt_v"),
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


# --------------------------------------------------- what the program is told


def test_the_two_kinds_state_their_own_heads_widths_bases_and_sink():
    assert CFG.layer_kinds == ("softmax_dense", "window", "window", "window", "window",
                               "softmax", "window")
    assert CFG.hybrid and CFG.window_moe and CFG.model_type == "mimo_v2_flash"
    assert PRESETS["tiny-exaone-moe"].model_type == "exaone_moe"
    assert [CFG.layer_ffn(k) for k in CFG.layer_kinds] == ["dense"] + ["experts"] * 6
    assert CFG.mixer_count("window") == 5 and CFG.paged_layers == 2
    assert (CFG.kv_heads_of("softmax"), CFG.kv_heads_of("window")) == (2, 4)
    assert (CFG.head_dim, CFG.value_head_dim, CFG.o_dim, CFG.rotary_dim) == (24, 16, 128, 8)
    assert CFG.held_experts == (0, 1) and CFG.router_width == 8
    # the pool's K and V at their own widths under one table, the rings likewise
    assert CFG.page_pool_shape(6, 8) == (2, 6, 8, 24)
    assert CFG.second_pool_shape(6, 8) == (2, 6, 8, 16)
    assert CFG.ring_shapes(5) == ((5, 4, 8, 24), (5, 4, 8, 16))
    # a key wider than one lane tile and no multiple of it takes whole tiles
    assert CFG.key_row == 24 and dataclasses.replace(CFG, head_dim=192).key_row == 256
    assert [dataclasses.replace(CFG, head_dim=d).key_row for d in (64, 128, 256)] == [
        64, 128, 256]
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    assert [x.shape for x in state["win_k"]] == [(5, 4, 8, 24)] * 5
    assert [x.shape for x in state["win_v"]] == [(5, 4, 8, 16)] * 5
    params = init_params(jax.random.PRNGKey(0), CFG)
    mixer = {"attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"}  # no q/k norm
    experts = {"router", "e_score_bias", "experts_gate", "experts_up", "experts_down"}
    assert set(params["layers"]["softmax_dense"]) == mixer | {"w_gate", "w_up", "w_down"}
    assert set(params["layers"]["softmax"]) == mixer | experts  # no shared expert
    assert set(params["layers"]["window"]) == mixer | experts | {"sink"}
    shapes = {k: params["layers"]["window"][k].shape[1:] for k in ("wq", "wk", "wv", "wo", "sink")}
    assert shapes == {"wq": (64, 192), "wk": (64, 96), "wv": (64, 64), "wo": (128, 64),
                      "sink": (8,)}
    assert params["layers"]["softmax"]["wk"].shape == (1, 64, 48)
    assert params["layers"]["softmax"]["wv"].shape == (1, 64, 32)
    # the other window family's stacks are what they were
    old = init_params(jax.random.PRNGKey(0), PRESETS["tiny-exaone-moe"])
    assert "q_norm" in old["layers"]["window"] and "sink" not in old["layers"]["window"]


def test_parameters_operations_and_the_counts_module_agree_with_the_tree():
    params = init_params(jax.random.PRNGKey(0), CFG)
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    model = dataclasses.asdict(CFG)
    assert counts.param_count(model) == held
    matmul = held - 256 * 64 - sum(  # less the embedding, the norms, sinks and biases
        x.size for path, x in jax.tree_util.tree_leaves_with_path(params)
        if str(path[-1].key).endswith("norm") or str(path[-1].key) in ("sink", "e_score_bias"))
    assert CFG.total_matmul_param_count == matmul
    # a token's operations: its own three experts of eight, both widths in a key
    per_key = 2.0 * 8 * (24 + 16)
    assert CFG.decode_flops_per_token(100.0) == 2.0 * CFG.matmul_param_count + per_key * (
        2 * 100.0 + 5 * 8)


def test_from_hf_config_reads_the_benchmarks_file():
    file = json.load(open(CONFIG_FILE))
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.window_kv_heads) == (
        4096, 64, 4, 8)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.sliding_window, cfg.rotary_dim) == (
        192, 128, 128, 64)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.value_scale) == (5e6, 1e4, 0.707)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (16384, 2048)
    assert (cfg.router_width, cfg.experts_per_token, cfg.n_routed_experts) == (256, 8, 16)
    assert cfg.window_sink and cfg.attn_use_rope and not cfg.qk_norm
    assert cfg.n_shared_experts == 0 and cfg.routed_scaling_factor == 1.0
    assert cfg.rms_norm_eps == 1e-5 and cfg.vocab_size == 19072 and cfg.num_layers == 7
    assert cfg.layer_kinds == CFG.layer_kinds and cfg.model_type == "mimo_v2_flash"
    assert cfg.total_matmul_param_count + 19072 * 4096 == 3_429_892_096  # 3,430M: 6.86 GB
    from distrl_llm_tpu.engine import budget

    model = dataclasses.asdict(cfg)
    # what the algorithm moves: 2,560 B a token a full layer, 655,360 B of ring a
    # window layer; what the program holds: a key's 192 values in 256 lanes
    assert counts.cache_token_bytes(model) == 2 * 2560 == 2 * counts.kv_token_bytes(model)
    assert counts.slot_state_bytes(model) == 5 * 655_360 == 5 * counts.ring_bytes(model)
    assert cfg.key_row == 256 and cfg.page_pool_shape(7, 128) == (4, 7, 128, 256)
    assert cfg.second_pool_shape(7, 128) == (4, 7, 128, 128)
    assert budget.page_bytes(cfg, 128) == 128 * 2 * 4 * (256 + 128) * 2 == 128 * 6144
    assert budget.slot_state_bytes(cfg, 20992) == 5 * 8 * 128 * (256 + 128) * 2
    assert counts.param_count(model) == 3_429_955_392
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for reading in ("norm_placement", "value_scale", "qk_norm", "rope", "score_scale",
                    "grouping", "window", "sink", "router", "routed_scaling_factor",
                    "mtp_layers", "towers", "unread_keys", "adapter_targets", "frozen"):
        assert file["assumed"][reading], reading


def test_the_loader_refuses_a_checkpoint_by_name(weights):
    from distrl_llm_tpu.models import loading

    with pytest.raises(NotImplementedError, match="mimo_v2_flash"):
        loading._refuse_unnamed(CFG)
    with pytest.raises(NotImplementedError, match="exaone_moe"):
        loading._refuse_unnamed(PRESETS["tiny-exaone-moe"])


# ------------------------------------------------------------- the forward


def test_the_sink_is_a_column_the_kernels_refuse_by_name():
    from distrl_llm_tpu.ops.attention import attention, attention_reference

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 2, 5))  # V narrower than K
    sink = jnp.asarray([0.3, -1.0, 2.0, 0.0])
    mask = jnp.tril(jnp.ones((6, 6), bool))[None, None]
    got = attention_reference(q, k, v, mask, sink=sink)
    # a zero value row added to V beside a key whose score is the sink: the same
    scores = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, 2, 2)) / jnp.sqrt(8.0)
    scores = jnp.where(mask, scores, -jnp.inf)
    wide = jnp.concatenate(
        [scores, jnp.broadcast_to(sink[None, :, None, None], (1, 4, 6, 1))], -1)
    p = jax.nn.softmax(wide, -1)[..., :-1]
    want = jnp.einsum("bhqs,bshd->bqhd", p, jnp.repeat(v, 2, 2))
    assert got.shape == (1, 6, 4, 5)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert float(jnp.abs(got - attention_reference(q, k, v, mask)).max()) > 1e-2
    for impl in ("flash", "splash"):
        with pytest.raises(NotImplementedError, match="no sink column"):
            attention(q, k, v, None, impl=impl, key_valid=jnp.ones((1, 6), jnp.int32),
                      sink=sink)


# --------------------------------------------------------------- the share


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4 at the tiny size: the four chips' parts of an
    expert layer's result (``expert_shard`` 0..3, two of eight experts each) add
    up to what the uncut reference gives for the whole layer, and the program's
    part for a share is the reference's."""
    fs.shares_add_up(FAMILY, "window", 4)


# -------------------------------------------------------------- the engine


_ENGINES: dict = {}


# -------------------------------------------------------------- the engine


@pytest.mark.parametrize("form,folds", [("xla", 0), ("kernel", 2 * 15)])
def test_a_round_files_the_full_layers_folds_that_ran_as_the_kernel(
        weights, small_pieces, monkeypatch, form, folds):
    """``ops/softmax_kernel_folds`` beside the other kernels' counters: 0 on the
    CPU's own path (float32 heads of 24 in 32 lanes take the XLA form, and
    ``expanded_segment`` says so under the full layers' geometry), and with the
    dispatch answered for and the kernel interpreted, 2 full layers x the
    1 + 2 + 3 + 4 + 5 folds of a 57-token prompt in segments of 12, the
    captured log-probabilities still the reference's."""
    import functools

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    params, lora = weights
    if form == "kernel":  # a program of its own, traced under the answer
        monkeypatch.setattr(la, "expanded_segment_impl", lambda q_nope, v_dim: "kernel")
        monkeypatch.setattr(la, "expanded_fold_kernel", functools.partial(
            la.expanded_fold_kernel, interpret=True))
        monkeypatch.setattr(la, "dispatch_choices", {})
    name = telemetry.OPS_SOFTMAX_KERNEL_FOLDS
    before = telemetry.observe_snapshot()["counters"].get(name, 0)
    engine = fs.make_engine(FAMILY, "waves", 0) if form == "kernel" else fs.engine(
        FAMILY, "waves", 0)
    ids, mask, result = fs.generate(FAMILY, engine)
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result) < 2e-5
    assert la.dispatch_choices[la.dispatch_key(8, KEY_ROW, 0, 16, 12, jnp.float32)] == form
    assert telemetry.observe_snapshot()["counters"][name] - before == folds  # filed, even 0


@pytest.mark.parametrize("preset,longest,want", [
    ("tiny-swa-sink-moe", None, 2 * 15), ("tiny-swa-sink-moe", 3, 2 * 6),
    ("tiny-cca", None, 3 * 15), ("tiny-jamba", 1, 1), ("tiny", None, None)])
def test_the_counter_is_the_paged_softmax_layers_times_the_folds(
        monkeypatch, preset, longest, want):
    """"softmax" and "cca" layers x the folds the stages ran (to the longest
    row's segments), under the layers' own key: query heads, the key's row, no
    rope part, the value's width. A dense model files nothing (a latent
    model's counter is its own: tests/test_latent_moe.py)."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    cfg = PRESETS[preset]
    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 12)
    monkeypatch.setattr(la, "dispatch_choices", {la.dispatch_key(
        cfg.num_heads, cfg.key_row, 0, cfg.value_head_dim, 12, jnp.float32): "kernel"})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_fold_telemetry(cfg, 15, 4, jnp.float32, longest)
    assert filed == ([] if want is None else [("ops/softmax_kernel_folds", want)])
    filed.clear()
    la.dispatch_choices.clear()  # the XLA form, or a prefill never traced
    paged_engine._record_fold_telemetry(cfg, 15, 4, jnp.float32, longest)
    assert filed == ([] if want is None else [("ops/softmax_kernel_folds", 0)])


def test_every_paged_family_files_what_a_token_costs():
    """The gauge is read off the pools' own shapes, K's and V's apart, so a V
    array allocated at K's width shows in it; a dense model files its own."""
    from distrl_llm_tpu.engine.paged_engine import _cache_token_bytes

    k, v = CFG.page_pool_shape(6, 4), CFG.second_pool_shape(6, 4)
    pools = lambda shape: tuple(jnp.zeros(shape, jnp.bfloat16) for _ in range(2))
    assert _cache_token_bytes(pools(k), pools(v)) == 2 * 2 * (24 + 16) * 2
    assert _cache_token_bytes(pools(k), pools(k)) == 2 * 2 * (24 + 24) * 2
    dense = PRESETS["tiny"]  # 2 layers of 2 KV heads of 16
    shape = (dense.num_kv_heads, 0, 8, dense.head_dim)
    assert _cache_token_bytes(pools(shape), pools(shape)) == 2 * 2 * 2 * 16 * 2
    assert _cache_token_bytes((jnp.zeros((5, 8, 640), jnp.bfloat16),), ()) == 640 * 2
    assert _cache_token_bytes((), ()) == 0


def test_the_paged_launch_takes_k_and_v_of_two_widths():
    """The launch at a group of 4 with K wider than V (interpreted here; the
    compile for the chip is tests/test_tpu_compile.py's): its output has V's
    width and equals the reference's, and its pages a step count both widths."""
    from distrl_llm_tpu.ops import paged, paged_native

    rng = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(rng[0], (3, 8, 24))
    k_pages = jax.random.normal(rng[1], (2, 12, 4, 24))
    v_pages = jax.random.normal(rng[2], (2, 12, 4, 16))
    table = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    lengths = jnp.asarray([16, 5, 9], jnp.int32)
    want = paged.paged_attention_reference(q, k_pages, v_pages, lengths, table)
    got = paged_native.paged_attention_native(
        q * 24 ** -0.5, k_pages, v_pages, lengths, table, pages_per_block=2, interpret=True)
    assert got.shape == want.shape == (3, 8, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # 192 takes two lane tiles beside V's one: 5 pages a step where 128 + 128 take 8
    at = dict(num_kv_heads=4, page_size=128, pps=164)
    assert paged_native.native_pages_per_step(head_dim=192, v_head_dim=128, **at) == 5
    assert paged_native.native_pages_per_step(head_dim=128, **at) == 8


def test_what_holds_k_and_v_of_one_kind_names_the_rings_it_cannot_hold():
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    paged = lambda **kw: PagedGenerationEngine(
        CFG, max_prompt_tokens=60, max_new_tokens=8, eos_token_ids=[-1], pad_token_id=0,
        scheduler="refill", max_concurrent_rows=4, autotune=False, **kw)
    builds = {
        "dense engine": lambda: GenerationEngine(
            CFG, max_prompt_tokens=60, max_new_tokens=8, eos_token_ids=[-1],
            pad_token_id=0, autotune=False),
        "kv_quant": lambda: paged(kv_quant="int8"),
        "spec_draft": lambda: paged(spec_draft=2),
        "prefix_sharing": lambda: paged(continuous_admission=True, prefix_cache=True),
        "max_kv_pages": lambda: paged(max_kv_pages=64),
        "kv_spill": lambda: paged(kv_spill=True),
    }
    for what, build in builds.items():
        with pytest.raises(ValueError) as e:
            build()
        said = str(e.value)
        assert what in said and "full_attention, sliding_attention layers" in said
        assert "a ring of the last sliding_window tokens' K and V and no page" in said
