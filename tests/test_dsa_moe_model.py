"""Latent attention behind a learned index over tokens, with a low-rank query
path and a share of the routed experts (GLM-5, ``glm_moe_dsa``), against its
plain reference, ``perfbench/reference_dsa_moe.py`` (the index's whole square,
a ranking of every key, the expanded form under the choice as a mask: no cache,
no absorption, no gather), at a small size on the CPU: the ``tiny-dsa`` preset
(hidden 64, a dense layer 0 before two expert layers, 4 heads of 16 + 8 over a
latent of 32, values of 20, a query latent of 48, 4 index heads of 16 choosing
8 tokens, 2 of 16 experts held, 4 a token, beside one shared expert). Float32
throughout, seeded weights with every term alive, contexts several times
``index_topk`` so that the choice is live in every mode.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s. The
rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_dsa_moe.py``.
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
from distrl_llm_tpu.models import ModelConfig, init_params
from distrl_llm_tpu.models import hybrid, moe, transformer
from distrl_llm_tpu.models.configs import PRESETS
from distrl_llm_tpu.ops import latent_attention, token_index
from perfbench import dsa_moe_counts
from perfbench import reference_dsa_moe as ref

CFG = PRESETS["tiny-dsa"]
TARGETS = {"wq_a", "wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down"}


def newest_tokens(monkeypatch):
    """The wrong choice: the newest ``index_topk`` tokens in place of the index's."""
    def mask(scores, visible, k):
        pos = jnp.arange(scores.shape[-1], dtype=jnp.float32)
        return token_index.chosen_mask(jnp.broadcast_to(pos, scores.shape), visible, k)

    def tokens(scores, lengths, k):
        pos = jnp.arange(scores.shape[-1], dtype=jnp.float32)
        return token_index.chosen_tokens(jnp.broadcast_to(pos, scores.shape), lengths, k)

    monkeypatch.setattr(hybrid, "chosen_mask", mask)
    monkeypatch.setattr(hybrid, "chosen_tokens", tokens)


def _control(name, monkeypatch, cfg=CFG):
    """The chip's controls, bent into the PROGRAM; returns the configuration
    the engine is told."""
    if name == "newest_tokens":
        newest_tokens(monkeypatch)
    elif name == "no_choice":
        monkeypatch.setattr(hybrid, "chosen_mask", lambda scores, visible, k: visible)
        monkeypatch.setattr(hybrid, "chosen_tokens", lambda scores, lengths, k: (
            token_index.chosen_tokens(scores, lengths, scores.shape[-1])))
    elif name == "topk_halved":
        return dataclasses.replace(cfg, index_topk=cfg.index_topk // 2)
    elif name == "top3_experts":
        return dataclasses.replace(cfg, experts_per_token=3)
    else:
        inputs = hybrid._index_inputs

        def bent(h, c_q, p, *, cfg, env):
            if name == "no_index_rope":
                env = {**env, "cos": jnp.ones_like(env["cos"]), "sin": jnp.zeros_like(env["sin"])}
            q_i, w, k_i = inputs(h, c_q, p, cfg=cfg, env=env)
            if name == "no_head_weights":
                w = jnp.ones_like(w)
            return q_i, w, k_i

        if name == "no_relu":  # the PROGRAM's scores alone: the reference keeps its relu
            def plain(q_i, w, k_i):
                keys = "bkd" if k_i.ndim == 3 else "kd"
                dots = jnp.einsum(f"bqhd,{keys}->bqhk", q_i, k_i)
                return jnp.einsum("bqh,bqhk->bqk", w, dots) * token_index.index_scale(
                    q_i.shape[-2], q_i.shape[-1])
            monkeypatch.setattr(token_index, "index_scores", plain)
            monkeypatch.setattr(hybrid, "index_scores", plain)
        elif name == "no_q_a_norm":
            norm = hybrid.rms_norm
            monkeypatch.setattr(hybrid, "rms_norm", lambda x, w, eps: (
                x if w.shape[-1] == cfg.q_lora_rank else norm(x, w, eps)))
        else:
            assert name in ("no_index_rope", "no_head_weights"), name
            monkeypatch.setattr(hybrid, "_index_inputs", bent)
    return cfg


def _pages(change):
    """An engine control: the index keys' pages the prefill hands the fan-out,
    ``change(k, v)``d."""
    def control(monkeypatch):
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, *rest = prefill(*a, **kw)
            return (*change(k, v), *rest)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    return control


def _bf16_keys(monkeypatch):
    inputs = hybrid._index_inputs
    monkeypatch.setattr(hybrid, "_index_inputs", lambda *a, **kw: tuple(
        jax.lax.reduce_precision(x, 8, 7) for x in inputs(*a, **kw)))


def _round_check(moved, result, engine, scheduler, slots):
    """The counters are the counts module's."""
    said = moved("engine/index_tokens_attended"), moved("engine/index_tokens_visible")
    want = dsa_moe_counts.index_tokens(
        dataclasses.asdict(CFG), [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    assert said == want == (3 * 8 * 24, 3 * 8 * 24)  # under 128 tokens: one unit each
    # expert layers x choices x rows x steps: not layer 0
    assert moved("engine/moe_pairs_routed") == 2 * 4 * 8 * 24
    assert moved("engine/latent_pages_read") == 0  # no dense walk ran
    assert engine.last_round_stats["slot_state_bytes"] == 0  # all of a slot is in pages


FORWARD_CONTROLS = ["no_choice", "newest_tokens", "topk_halved", "no_relu", "no_head_weights",
                    "no_index_rope", "no_q_a_norm", "top3_experts"]

FAMILY = fs.Family(
    name="dsa-moe", cfg=CFG, ref=ref, config_file="glm-5-ep16-L5.json",
    # the latents' and the index key's norms too, a LayerNorm bias
    seed_rules=((fs.named("b_index_k"), fs.normal(0.3)),),
    # Prefill in segments of 16 tokens (two pages of 8: every segment after the
    # first chooses 8 of what it sees, the first chooses all), the decode walk 3
    # columns a row and 4 rows a group with a shared block of 6 pages, so that
    # 40-57-token prompts cross every boundary the cell's 10k-20k-token prompts
    # cross: index keys read from earlier segments' pages, a group's shared
    # blocks of keys beside its private ones, a last segment that is part
    # padding. Decode rows dense, segments grouped.
    engine_pieces=(
        (paged_engine, "HYBRID_PREFILL_SEGMENT", 16),
        (hybrid, "LATENT_DECODE_PAGES", 3), (hybrid, "LATENT_DECODE_ROWS", 4),
        (latent_attention, "SHARED_SCORE_BYTES", 4 * 4 * 8 * 4 * 6),
        (moe, "expert_form", fs.expert_forms(8))),
    refusals=(
        ({"rope_scaling": {"rope_type": "yarn", "factor": 8}}, "rope_scaling"),
        ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_parameters"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"moe_layer_freq": 2}, "moe_layer_freq"),
        ({"n_group": 8}, "n_group"),
        ({"topk_group": 4}, "topk_group"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"indexer_rope_interleave": False}, "indexer_rope_interleave"),
        ({"rope_interleave": False}, "rope_interleave"),
        ({"attention_bias": True}, "attention_bias"),
        ({"q_lora_rank": None}, "index_topk needs"),
        ({"model_type": "glm_moe"}, "glm_moe")),
    loader_refusal=("glm_moe_dsa", "glm_moe_dsa"),
    # rows of 40 tokens, five times ``index_topk``, padded on either side
    forward_cases=(("plain", False, ()), ("remat", True, ())),
    # each thing the chip's controls bend
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # rows five times ``index_topk`` long; the choice is not differentiated in
    # either; a and b: eight targets in each of two stacks
    learner={"answer": 28, "leaves": 2 * 8 * 2},
    # the index, the router and the experts: none
    train_targets={"latent": TARGETS, "latent_moe": TARGETS},
    # two paged arrays a layer under one page table: 8 rows through 4 slots (a
    # freed slot takes another prompt's pages); every candidate at once; lockstep
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)), round_check=_round_check,
    # what only the cache path can get wrong (the prompt's index keys not handed
    # at the fan-out, or another prompt's; keys kept in bf16), and the controls of
    # the chip's check through segments, fan-out and decode steps: the newest 8
    # tokens in place of the index's, no choice, 4 for 8, no RoPE
    engine_controls={
        "keys_not_handed": _pages(lambda k, v: (k, tuple(jnp.zeros_like(x) for x in v))),
        "keys_from_other_prompt": _pages(lambda k, v: (k, tuple(
            jnp.roll(x, x.shape[0] // 2, axis=0) for x in v))),
        **{name: fs.through_the_engine(functools.partial(_control, name))
           for name in ("newest_tokens", "no_choice", "topk_halved", "no_index_rope")},
        "bf16_keys": _bf16_keys},
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 1e-5},
    # the index key beside the latent row (no new list)
    state_refusals=fs.NINE_REFUSALS,
    state_refusal_says=(
        "latent-attention (MLA) and routed-expert layers",
        "one latent row a token in place of K and V per head beside one index key a "
        "token in a second paged array"),
    span_args={"index_tokens_attended": 3 * 8 * 24, "index_tokens_visible": 3 * 8 * 24},
    report_tail=", index 576 of 576 x 128 tokens",
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


# --------------------------------------------------- what the program is told


def test_the_layers_and_what_a_token_keeps():
    assert CFG.layer_kinds == ("latent", "latent_moe", "latent_moe")
    assert CFG.latent and CFG.hybrid and CFG.model_type == "glm_moe_dsa"
    assert [CFG.layer_ffn(k) for k in CFG.layer_kinds] == ["dense", "experts", "experts"]
    assert CFG.held_experts == (0, 1) and CFG.router_width == 16
    assert (CFG.head_dim, CFG.q_dim, CFG.latent_dim, CFG.latent_row) == (24, 96, 40, 128)
    assert CFG.page_pool_shape(9, 8) == (9, 8, 128)
    assert CFG.second_pool_shape(9, 8) == (9, 8, 16)  # the index keys, same pages
    assert PRESETS["tiny-latent-moe"].second_pool_shape(9, 8) is None
    assert PRESETS["tiny"].second_pool_shape(9, 8) == PRESETS["tiny"].page_pool_shape(9, 8)
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    assert set(state) == {
        "lin", "pooled", "moe_stats", "moe_blocks", "moe_routed", "index_stats"}
    assert state["index_stats"].shape == (2,) and state["moe_routed"].shape == (1,)
    # Kimi-VL's shape holds what it held: no share, no index, its own counter
    assert set(hybrid.init_mixer_state(PRESETS["tiny-latent-moe"], 5, 64)) == {
        "lin", "pooled", "moe_stats", "moe_blocks", "latent_stats"}
    params = init_params(jax.random.PRNGKey(0), CFG)
    index = {"w_index_q", "w_index_k", "index_k_norm", "b_index_k", "w_index_w"}
    attention = {"attn_norm", "wq_a", "q_a_norm", "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo",
                 "mlp_norm"}
    mlp = {"w_gate", "w_up", "w_down"}
    experts = {"router", "e_score_bias", "experts_gate", "experts_up", "experts_down"}
    assert set(params["layers"]["latent"]) == attention | index | mlp
    assert set(params["layers"]["latent_moe"]) == attention | index | mlp | experts
    moe_stack = params["layers"]["latent_moe"]
    assert moe_stack["wq"].shape == (2, 48, 96) and moe_stack["wq_a"].shape == (2, 64, 48)
    assert moe_stack["w_index_q"].shape == (2, 48, 4 * 16)
    assert moe_stack["router"].shape == (2, 64, 16)  # the published width
    assert moe_stack["experts_gate"].shape == (2, 2, 64, 32)  # the two held
    with pytest.raises(ValueError, match="index_topk needs latent attention with q_lora_rank"):
        dataclasses.replace(CFG, q_lora_rank=0)


def test_a_model_without_a_rank_draws_what_it_drew():
    """The new leaves are drawn after every leaf a model without them has:
    Kimi-VL's shape and its seeded tests keep their values."""
    plain = PRESETS["tiny-latent-moe"]
    params = init_params(jax.random.PRNGKey(0), plain)
    assert "wq_a" not in params["layers"]["latent"]
    init = transformer._normal_init(jax.random.PRNGKey(0), 32, jnp.float32)
    np.testing.assert_array_equal(params["layers"]["latent"]["wq"], init((1, 64, 96)))


def test_parameters_are_counted_to_the_unit_by_program_and_yardstick():
    def tree_count(cfg):
        shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))

    model = dataclasses.asdict(CFG)
    assert dsa_moe_counts.param_count(model) == tree_count(CFG)
    full = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    model = dataclasses.asdict(full)
    assert dsa_moe_counts.param_count(model) == tree_count(full) == 3_909_632_768
    assert dsa_moe_counts.attention_params(model) == 165_019_648
    assert dsa_moe_counts.index_params(model) == 9_371_648
    small = sum(dsa_moe_counts.layer_small_params(model, f)
                for f in dsa_moe_counts.layer_kinds(model)) + 6144
    assert full.total_matmul_param_count + 19360 * 6144 + small == 3_909_632_768
    # what a token RUNS: 8 experts of the published 256 wherever they are held
    assert full.matmul_param_count == full.total_matmul_param_count - 4 * 8 * 3 * 6144 * 2048


def test_from_hf_config_reads_the_benchmarks_file():
    cfg = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    assert cfg.model_type == "glm_moe_dsa" and cfg.latent and cfg.hybrid
    assert cfg.layer_kinds == ("latent",) + ("latent_moe",) * 4
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_heads) == (6144, 19360, 64)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.head_dim, cfg.v_head_dim) == (
        2048, 512, 256, 256)  # the query head is nope + rope, not the file's head_dim 64
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.latent_row) == (192, 64, 640)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (32, 128, 2048)
    assert (cfg.n_routed_experts, cfg.router_width, cfg.experts_per_token) == (16, 256, 8)
    assert cfg.held_experts == tuple(range(16)) and cfg.shared_expert_size == 2048
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.routed_scaling_factor) == (1e6, 1e-5, 2.5)
    assert not cfg.tie_word_embeddings and cfg.first_dense_layers == 1
    with open(CONFIG_FILE) as f:
        held = json.load(f)
    assert held["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    assert held["share"] == {"chips_per_layer": 16, "published": {
        "n_routed_experts": 256, "vocab_size": 154880}}
    assert held["depth"] == {"published": {"num_hidden_layers": 78,
                                           "first_k_dense_replace": 3}}
    assert (held["reference"], held["counts"]) == ("reference_dsa_moe", "dsa_moe_counts")
    for said in ("index_rope", "index_k_norm", "index_scale", "index_ties", "index_precision",
                 "head_dim", "latent_norms", "softmax_scale", "router_precision",
                 "shared_expert", "held_experts", "vocabulary_slice", "adapter_targets",
                 "frozen", "mtp", "weights"):
        assert held["assumed"][said], said
    assert "3,910M parameters, 7.82 GB" in held["deployment"]
    # every number of the catalog's row, but the four that are cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f) if r["name"] == "GLM-5"]
        differ = {k for k, v in row["config"].items() if held.get(k) != v}
        assert differ == set(held["reduced"])


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("tokens", [6, 8, 9, 70])
def test_forward_equals_the_reference_on_both_sides_of_the_topk(weights, tokens):
    params, lora = weights
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(tokens), (2, tokens), 1, 256))
    mask = np.ones_like(ids)
    got = fs.forward_logprobs(FAMILY, params, lora, ids, mask)
    assert np.abs(got - fs.reference_logprobs(FAMILY, params, lora, ids, mask)).max() < 2e-5


# ------------------------------------------------------------- the choice


def reference_set(scores, visible, k):
    """The reference's ranking, written again: a key's rank among the query's
    keys by score, the lower index among equals; chosen where the rank is
    under k."""
    held = np.where(visible, scores, -np.inf)
    order = np.argsort(-held, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    return (rank < k) & visible


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_the_choice_is_the_references_set_on_ties_and_zeros(k):
    """relu makes exact zeros and bf16 keys make exact ties: scores drawn from
    five values (zero the most frequent), so nearly every k-th score is shared.
    The mask by counting and the positions by ``top_k`` are the same set, the
    reference's, with exactly ``min(k, visible)`` tokens a query."""
    rng = np.random.default_rng(k)
    scores = rng.choice([0.0, 0.0, 0.0, 0.5, 1.0, 1.5, -0.5], (3, 24, 24)).astype(np.float32)
    pos = np.arange(24)
    visible = np.broadcast_to(pos[None, :] <= pos[:, None], scores.shape)
    want = reference_set(scores, visible, k)
    got = np.asarray(token_index.chosen_mask(jnp.asarray(scores), jnp.asarray(visible), k))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(k, pos + 1)).all()
    for t in (0, 4, 23):  # one query a row at position t: the same set, as positions
        at, seen = token_index.chosen_tokens(
            jnp.asarray(scores[:, t]), jnp.full((3,), t, jnp.int32), k)
        for b in range(3):
            assert set(np.asarray(at[b])[np.asarray(seen[b])]) == set(np.flatnonzero(want[b, t]))
    # the reference's own function says the same of a layer's scores
    params, _ = fs.seeded(FAMILY, CFG)
    layer = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["latent"])
    h = jax.random.normal(jax.random.PRNGKey(0), (24, 64))
    c_q = jax.random.normal(jax.random.PRNGKey(1), (24, 48))
    model = dataclasses.replace(CFG, index_topk=k)
    chosen = np.asarray(ref.index_choice(h, c_q, jnp.ones((24,), bool), jnp.arange(24),
                                         layer, model))
    assert (chosen.sum(-1) == np.minimum(k, pos + 1)).all() and not np.triu(chosen, 1).any()


def test_index_scores_are_the_formula_and_a_shared_block_is_one_product():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 4))
    k = jax.random.normal(jax.random.PRNGKey(2), (5, 16))
    want = np.einsum("bqh,bqhk->bqk", np.asarray(w), np.maximum(
        np.einsum("bqhd,kd->bqhk", np.asarray(q), np.asarray(k)), 0)) * 4 ** -0.5 * 16 ** -0.5
    np.testing.assert_allclose(token_index.index_scores(q, w, k), want, atol=1e-5)
    np.testing.assert_allclose(
        token_index.index_scores(q, w, jnp.broadcast_to(k, (2, 5, 16))), want, atol=1e-5)


# --------------------------------------------------------------- the share


def test_eight_shares_sum_to_the_whole_layer_and_the_program_holds_its_own():
    """The eight chips' routed parts, with the shared expert and attention
    counted once, are what the uncut reference gives for the whole layer; the
    program's part for a share is the reference's; the latent family tells
    ``moe_half`` what it holds, as the other families with a share do."""
    fs.shares_add_up(FAMILY, "latent_moe", 8)


# -------------------------------------------------------------- the engine

@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_round_under_the_choice_through_the_launch_is_the_gathers(
        weights, small_pieces, monkeypatch, scheduler, slots):
    """The engine's decode with every layer-step's attention ONE
    ``absorbed_decode_kernel`` launch that walks the rows' pages whole behind
    the index's choice as a mask (interpreted; the dispatch and the shapes'
    inequality answered for it: 4 rows x 8 chosen tokens are fewer than the
    tiny table's positions). The same tokens are attended as the gather of
    the chosen rows attends: the sampled tokens are the gather's, the
    log-probabilities its and the reference's, the counter of attended tokens
    its, and ``ops/latent_decode_launches`` reads 3 layers x the steps where
    the gather's round reads 0."""
    import functools

    from distrl_llm_tpu import telemetry

    params, lora = weights
    counters = lambda: dict(telemetry.observe_snapshot()["counters"])
    moved = lambda before, name: counters().get(name, 0) - before.get(name, 0)
    # what an earlier case's launch recorded is not this case's plain engine's
    monkeypatch.setattr(latent_attention, "dispatch_choices", {})
    before = counters()
    _, _, plain = fs.generate(FAMILY, fs.engine(FAMILY, scheduler, slots))
    assert moved(before, telemetry.OPS_LATENT_DECODE_LAUNCHES) == 0  # the gather ran
    attended = moved(before, "engine/index_tokens_attended")
    monkeypatch.setattr(latent_attention, "absorbed_decode_impl", lambda heads, pages, rows: "kernel")
    monkeypatch.setattr(hybrid, "_choice_walks_pages", lambda cfg, pages, walk, rows: True)
    monkeypatch.setattr(latent_attention, "absorbed_decode_kernel", functools.partial(
        latent_attention.absorbed_decode_kernel, interpret=True))
    before = counters()
    ids, mask, result = fs.generate(FAMILY, fs.make_engine(FAMILY, scheduler, slots))
    assert moved(before, telemetry.OPS_LATENT_DECODE_LAUNCHES) == 3 * result.steps_dispatched
    assert moved(before, "engine/index_tokens_attended") == attended
    np.testing.assert_array_equal(result.tokens, plain.tokens)
    np.testing.assert_allclose(result.logprobs, plain.logprobs, atol=2e-5)
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result) < 2e-5



def test_a_prefill_through_the_fold_kernel_is_the_xla_forms(weights, small_pieces, monkeypatch):
    """The engine's prefill with every fold run by ``expanded_fold_kernel``
    under the index's choice (interpreted; the dispatch answered for it; the
    preset's K is 16 + 8 wide beside a V of 20): prompts of 40 and 57 tokens in
    segments of 16, so that every segment after the first chooses 8 of what it
    sees and the last ones are part padding. The captured
    log-probabilities are the XLA form's to the kernel's own rounding and the
    reference's; the sampled tokens are the same; ``ops/latent_kernel_folds``
    files 3 layers x (1 + 2 + 3 + 4) folds, as the stages run them."""
    import functools

    from distrl_llm_tpu import telemetry

    params, lora = weights
    folds = lambda: telemetry.observe_snapshot()["counters"].get(
        telemetry.OPS_LATENT_KERNEL_FOLDS, 0)
    before = folds()
    _, _, plain = fs.generate(FAMILY, fs.engine(FAMILY, "waves", 0))
    assert folds() == before  # the CPU's own form: none
    monkeypatch.setattr(latent_attention, "expanded_segment_impl", lambda q_nope, v_dim: "kernel")
    monkeypatch.setattr(latent_attention, "expanded_fold_kernel", functools.partial(
        latent_attention.expanded_fold_kernel, interpret=True))
    ids, mask, result = fs.generate(FAMILY, fs.make_engine(FAMILY, "waves", 0))
    assert folds() - before == 3 * 10
    np.testing.assert_array_equal(result.tokens, plain.tokens)
    np.testing.assert_allclose(result.logprobs, plain.logprobs, atol=2e-5)
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result) < 2e-5


def test_past_128_tokens_the_counters_say_what_was_spared(monkeypatch):
    """An index of 128 tokens, prompts of 150 and 260 in segments of 64: a step
    attends one unit of 128 tokens where latent attention without the index
    would attend two or three, and the engine still equals the reference."""
    from distrl_llm_tpu import telemetry

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 64)
    wide = dataclasses.replace(CFG, index_topk=128)
    params, lora = fs.seeded(FAMILY, wide)
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = fs.make_engine(FAMILY, "waves", 0, wide, prompt=272, page_size=16)
    ids, mask = fs.prompts((150, 260), 272)
    result = engine.generate(
        params, lora, ids, mask, SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=24),
        jax.random.PRNGKey(3))
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result, wide) < 2e-5
    after = telemetry.observe_snapshot()["counters"]
    said = tuple(after[f"engine/index_tokens_{k}"] - before.get(f"engine/index_tokens_{k}", 0)
                 for k in ("attended", "visible"))
    model = dataclasses.asdict(wide)
    want = dsa_moe_counts.index_tokens(model, [150, 150, 260, 260], result.lengths.reshape(-1))
    assert said == want == (3 * 4 * 24, 3 * 2 * 24 * (2 + 3))
    # the bytes: 16 values of index key a visible token (a prompt's once a group of 2),
    # 40 of latent row a chosen one
    assert dsa_moe_counts.index_key_bytes(model, [150, 150], [24, 24], kv_bytes=4,
                                          group_size=2) == 3 * 16 * 4 * (24 * 150 + 2 * 300)
    assert dsa_moe_counts.indexed_attn_bytes(model, [150], [24], kv_bytes=4) == (
        3 * 40 * 4 * 24 * 128)


def test_the_prefill_returns_index_keys_under_the_latent_rows_table(weights, small_pieces):
    params, lora = weights
    ids, mask, (k, v, logits, real_len, mixer) = fs.prefilled(FAMILY, params, lora)
    assert [x.shape for x in k] == [(16, 8, 128)] * 3 and [x.shape for x in v] == [(16, 8, 16)] * 3
    assert list(np.asarray(real_len)) == [40, 57] and "index_stats" in mixer
    # a token's key is where its row is; past a prompt's end both arrays are unread
    for layer in range(3):
        rows = np.abs(np.asarray(k[layer])).sum(-1) > 0
        keys = np.abs(np.asarray(v[layer])).sum(-1) > 0
        assert rows[:5].all() and keys[:5].all() and rows[8: 8 + 7].all() and keys[8: 8 + 7].all()
    # the latent row's last lanes are padding: 40 of 128 hold values
    assert not np.asarray(k[0])[..., 40:].any()


@pytest.mark.parametrize("topk,steps,segments,want", [
    (8, 24, None, 3 * (4 + 24)),   # a segment of 16 ends past 8: all four choose
    (8, 24, 3, 3 * (3 + 24)),      # the longest row ends in its third segment
    (40, 24, None, 3 * (2 + 24)),  # the first two segments end within 40 and choose all
    (40, 0, 1, 0),
    (64, 5, None, 3 * 5),          # the prompt's 64 columns are all chosen, a step's 88 are not
    (88, 5, None, 0),              # ``k >= width`` everywhere: nothing is counted
])
def test_the_counter_is_layers_times_the_choices_made_by_counting(monkeypatch, topk, steps,
                                                                  segments, want):
    """``ops/index_counted_choices``: 3 layers x (the decode steps whose table of
    88 columns is wider than ``index_topk`` + the segments of a 64-token prompt
    that end past it); the cell's 5 x (512 + 18 of 20) = 2,650 a round."""
    from distrl_llm_tpu import telemetry

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_index_telemetry(
        dataclasses.replace(CFG, index_topk=topk), steps, 8, 3, 8, segments)
    assert filed == [("ops/index_counted_choices", want)]
    filed.clear()  # a model without an index files nothing
    paged_engine._record_index_telemetry(PRESETS["tiny-latent-moe"], steps, 8, 3, 8, segments)
    assert filed == []


def test_the_cells_round_counts_2650_choices(monkeypatch):
    """``glm-5-ep16-L5.rollout-longctx-indexed``: 5 layers x (512 steps over a
    table of 168 pages + the 18 of 20 segments of 1,024 that end past 2,048)."""
    from distrl_llm_tpu import telemetry

    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    cell = SimpleNamespace(num_layers=5, index_topk=2048)  # what the function reads
    paged_engine._record_index_telemetry(cell, 512, 160, 8, 128, 20)
    assert filed == [("ops/index_counted_choices", 2650)]


@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_round_files_its_counted_choices(weights, small_pieces, scheduler, slots):
    """Both schedulers file the counter, tracing off: prompts of 40 and 57 tokens
    in segments of 16 (the longest row's four segments all end past
    ``index_topk`` = 8) and every dispatched step, in each of the 3 layers."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"].get(
        telemetry.OPS_INDEX_COUNTED_CHOICES, 0)
    result = fs.engine(FAMILY, scheduler, slots).generate(
        params, lora, *fs.prompts((40, 57)),
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=4),
        jax.random.PRNGKey(3))
    after = telemetry.observe_snapshot()["counters"][telemetry.OPS_INDEX_COUNTED_CHOICES]
    assert result.steps_dispatched > 0
    assert after - before == 3 * (4 + result.steps_dispatched)


def test_a_round_runs_without_the_cyclic_collector_and_leaves_it_as_it_was():
    """``PagedGenerationEngine.generate`` holds the collector off for the round
    (a full collection in the decode loop idles the chip: PERF.md, PR 54) and
    hands it back as it found it."""
    import gc


    assert gc.isenabled()
    with paged_engine._no_full_collection():
        assert not gc.isenabled()
    assert gc.isenabled()
    gc.disable()
    try:
        with paged_engine._no_full_collection():
            assert not gc.isenabled()
        assert not gc.isenabled()  # a caller's choice stands
    finally:
        gc.enable()
    with pytest.raises(RuntimeError):
        with paged_engine._no_full_collection():
            raise RuntimeError("a round that fails")
    assert gc.isenabled()


# --------------------------------------------- the budget, adapters and placement


def test_a_page_costs_the_latent_row_and_the_index_key():
    from distrl_llm_tpu.engine import budget

    assert budget.page_bytes(CFG, 8) == 3 * 8 * (128 + 16) * 2
    assert budget.page_bytes(PRESETS["tiny-latent-moe"], 8) == 3 * 8 * 128 * 2  # no index
    assert budget.slot_state_bytes(CFG, 88) == 0
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**8)
    assert budget.kv_pool_pages(CFG, slots=8, **common) == (
        int(10**8 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6
            - 2 * 8 * budget.page_bytes(CFG, 8)) // budget.page_bytes(CFG, 8))
    # the published widths: 640 lanes of latent row and 128 of index key, 1,536 B a token a layer
    full = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    assert budget.page_bytes(full, 128) == 5 * 128 * 1536
    assert dsa_moe_counts.page_token_bytes(dataclasses.asdict(full)) == 1536


def test_adapter_factors_are_each_stacks_own_and_merge(weights):
    from distrl_llm_tpu.models.lora import LATENT_RANK_TARGETS, LATENT_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == {"latent", "latent_moe"}
    assert set(LATENT_RANK_TARGETS) == TARGETS and LATENT_RANK_TARGETS[:7] == LATENT_TARGETS
    for kind in lora["layers"]:
        assert set(lora["layers"][kind]) == TARGETS  # nothing on the index or the router
    assert lora["layers"]["latent"]["wq_a"]["a"].shape == (1, 64, 4)
    assert lora["layers"]["latent"]["wq"]["a"].shape == (1, 48, 4)  # q_b reads the latent
    assert lora["layers"]["latent"]["w_gate"]["b"].shape == (1, 4, 128)
    assert lora["layers"]["latent_moe"]["w_gate"]["b"].shape == (2, 4, 32)
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_leaf_has_a_partition_spec_and_the_view_holds_q_b(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.ops.linear import OutIn
    from distrl_llm_tpu.parallel.partition import param_specs

    params, _ = weights
    for kind, stack in params["layers"].items():
        specs = param_specs(params)["layers"][kind]
        for name in ("q_a_norm", "kv_a_norm", "index_k_norm", "b_index_k", "wq_a",
                     "w_index_q", "w_index_k", "w_index_w"):
            assert specs[name] == P(*([None] * stack[name].ndim)), (kind, name)
        assert specs["wq"] == P(None, "fsdp", "tp") and specs["wo"] == P(None, "tp", "fsdp")
    view = transformer.decode_view(params)
    listed = {path for path, _ in transformer.decode_view_leaves(params["layers"])}
    assert listed == {("latent", "wq"), ("latent_moe", "wq")}
    for kind, stack in view["layers"].items():
        assert isinstance(stack["wq"], tuple) and isinstance(stack["wq"][0], OutIn)
        assert stack["wq_a"] is params["layers"][kind]["wq_a"]


def test_the_new_scopes_and_counters_are_the_programs_constants():
    from distrl_llm_tpu import telemetry

    assert (telemetry.MODEL_INDEX_SCORE, telemetry.MODEL_INDEX_SELECT,
            telemetry.MODEL_INDEXED_ATTN) == (
        "model/index_score", "model/index_select", "model/indexed_attn")
    assert {telemetry.MODEL_INDEX_SCORE, telemetry.MODEL_INDEX_SELECT,
            telemetry.MODEL_INDEXED_ATTN} <= set(telemetry.SCOPE_NAMES)
    assert telemetry.ENGINE_INDEX_TOKENS_ATTENDED == "engine/index_tokens_attended"
    assert telemetry.ENGINE_INDEX_TOKENS_VISIBLE == "engine/index_tokens_visible"
    assert telemetry.OPS_INDEX_COUNTED_CHOICES == "ops/index_counted_choices"
    assert hybrid.INDEX_COUNT_UNIT == dsa_moe_counts.COUNT_UNIT == 128
    assert hybrid.INDEX_NORM_EPS == ref.INDEX_NORM_EPS == 1e-6
