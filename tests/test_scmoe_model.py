"""A shortcut-connected expert model (LongCat-Flash-Chat, ``longcat_flash``):
two latent-attention sublayers and two dense MLPs a published layer round one
expert block, a softmax router over routed experts and experts that compute
nothing, against its plain reference, ``perfbench/reference_scmoe.py`` (the
expanded form over whole rows, every held expert on every token: no cache, no
absorption, no page, no carry), at a small size on the CPU: the ``tiny-scmoe``
preset (hidden 64, two published layers = four sublayers, 4 heads of 16 + 8
over a latent of 32, a query latent of 48, both latents scaled, 2 of 8 routed
experts held and 4 that compute nothing behind a router of 12, 3 a token,
weights 6 x the scores unnormalised). Float32 throughout, seeded weights with
every term alive.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s. The
rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_scmoe.py``.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite as fs
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig, forward, init_params
from distrl_llm_tpu.models import hybrid, moe
from distrl_llm_tpu.models.configs import PRESETS, SHORTCUT_KINDS
from distrl_llm_tpu.ops import latent_attention
from perfbench import reference_scmoe as ref
from perfbench import scmoe_counts

CFG = PRESETS["tiny-scmoe"]
TARGETS = {"wq_a", "wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down"}
#: the uncut model: all 8 routed experts here, the router as wide as it was
UNCUT = dataclasses.replace(CFG, n_routed_experts=8, router_experts=0)


def _control(name, monkeypatch, cfg=CFG):
    """The chip's controls, bent into the PROGRAM (never the reference);
    returns the configuration the engine is told."""
    if name == "no_zero_part":  # the experts that compute nothing add nothing
        monkeypatch.setattr(moe, "zero_part", lambda h, idx, w, first, alive=None: (
            jnp.zeros_like(h), jnp.int32(0)))
    elif name == "renormalised":
        return dataclasses.replace(cfg, norm_topk_prob=True)
    elif name == "sigmoid_router":
        return dataclasses.replace(cfg, router_softmax=False)
    elif name == "bias_in_the_weights":
        route = moe.route

        def biased(h, router, bias, cfg):
            idx, w = route(h, router, bias, cfg)
            return idx, w + cfg.routed_scaling_factor * bias.astype(jnp.float32)[idx]
        monkeypatch.setattr(moe, "route", biased)
    elif name == "no_bias":  # the correction bias dropped from the choice
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda h, router, bias, cfg: route(
            h, router, jnp.zeros_like(bias), cfg))
    elif name == "top2":
        return dataclasses.replace(cfg, experts_per_token=cfg.experts_per_token - 1)
    elif name == "e_after_the_fork":  # the experts' part joins a sublayer early
        block = hybrid._latent_block

        def early(x, p, lora, cache, *, kind, **kw):
            out, pages, stats = block(x, p, lora, cache, kind=kind, **kw)
            if kind == "latent_fork":
                out = (out[0] + out[1], jnp.zeros_like(out[1]))
            return out, pages, stats
        monkeypatch.setattr(hybrid, "_latent_block", early)
    elif name == "no_q_scale":
        return dataclasses.replace(cfg, latent_q_scale=1.0)
    elif name == "no_kv_scale":
        return dataclasses.replace(cfg, latent_kv_scale=1.0)
    elif name == "held_shifted":  # the same weights said to be experts 2-3
        return dataclasses.replace(cfg, expert_shard=1)
    else:
        raise AssertionError(name)
    return cfg


FORWARD_CONTROLS = ["no_zero_part", "renormalised", "sigmoid_router", "bias_in_the_weights",
                    "no_bias", "top2", "e_after_the_fork", "no_q_scale", "no_kv_scale", "held_shifted"]


def _pages(change):
    """An engine control: the latent pages the prefill hands the fan-out, ``change(k)``d."""
    def control(monkeypatch):
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, *rest = prefill(*a, **kw)
            return (change(k), *rest)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    return control


def _round_check(moved, result, engine, scheduler, slots):
    """The counters by hand: 2 published layers, 8 rows, 24 steps, 3 choices."""
    assert result.steps_dispatched >= 24 * (2 if slots == 4 else 1)
    routed = 2 * 3 * 8 * 24
    assert moved("engine/moe_pairs_routed") == routed  # ONE expert block a published layer
    zero, held = moved("engine/moe_pairs_zero"), moved("engine/moe_assignments")
    # 4 of 12 outputs compute nothing and 2 are held: a share of the pairs each
    assert 0.15 * routed < zero < 0.5 * routed and 0 < held < 0.4 * routed
    assert zero + held < routed  # the rest chose an expert held elsewhere
    # absorbed attention's pages: FOUR sublayers walk the table alike
    pages = np.asarray([[(p + t) // 8 + 1 for t in range(24)] for p in (40, 57)])
    assert moved("engine/latent_pages_attended") == 4 * 4 * pages.sum()
    assert engine.last_round_stats["slot_state_bytes"] == 0  # all of a slot is in pages


FAMILY = fs.Family(
    name="scmoe", cfg=CFG, ref=ref, config_file="longcat-flash-ep32-L4.json",
    # Prefill in segments of 16 tokens, the decode walk 3 columns a row and 4
    # rows a group with a shared block of 6 pages, so that 40-57-token prompts
    # cross every boundary the cell's prompts cross. Decode rows dense,
    # segments grouped.
    engine_pieces=(
        (paged_engine, "HYBRID_PREFILL_SEGMENT", 16),
        (hybrid, "LATENT_DECODE_PAGES", 3), (hybrid, "LATENT_DECODE_ROWS", 4),
        (latent_attention, "SHARED_SCORE_BYTES", 4 * CFG.num_heads * 6 * 8 * 4),
        (moe, "expert_form", fs.expert_forms(8))),
    # every key the field function cannot honour, by name
    refusals=(
        ({"zero_expert_type": "copy"}, "zero_expert_type"),
        ({"attention_method": "GQA"}, "attention_method"),
        ({"attention_bias": True}, "attention_bias"),
        ({"rope_scaling": {"rope_type": "yarn", "factor": 10}}, "rope_scaling"),
        ({"router_bias": True}, "router_bias"),
        ({"n_group": 8}, "n_group"),
        ({"topk_group": 4}, "topk_group"),
        ({"scoring_func": "sigmoid"}, "scoring_func"),
        ({"norm_topk_prob": True}, "norm_topk_prob"),
        ({"q_lora_rank": None}, "q_lora_rank"),
        ({"model_type": "longcat"}, "longcat")),
    forward_cases=(("plain", False, ()), ("remat", True, ())),
    forward_full_logits=True,
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # a and b: eight targets in each of the two sublayers' stacks
    learner={"answer": 20, "leaves": 2 * 8 * 2},
    # the router and the experts: none
    train_targets={kind: TARGETS for kind in SHORTCUT_KINDS},
    rounds=(("refill", 4), ("waves", 0)), round_check=_round_check,
    # what only the cache path can get wrong: a sublayer handed another's pool
    # (eight pools a model of four layers: four here), pages kept in bf16
    engine_controls={
        "pools_rotated_by_a_sublayer": _pages(lambda k: k[1:] + k[:1]),
        "bf16_pages": lambda monkeypatch: {"cache_dtype": jnp.bfloat16}},
    engine_limit=20 * 2e-5,
    # through segments, fan-out and the decode steps (the dense form there)
    engine_mechanisms=("no_zero_part", "e_after_the_fork", "held_shifted"),
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 1e-5},
    state_refusals=(
        ("dense", "dense engine"), ("sharded", "dp-sharded"), ("speculation", "spec_draft"),
        ("int8_pool", "int8"), ("radix_cache", "prefix_sharing"),
        ("pool_chains", "prefix_sharing"), ("continuous_admission", "continuous_admission"),
        ("preemption", "re-prefill")),
    state_refusal_says=("shortcut-connected routed-expert", "latent row"),
)
family, small_pieces, weights = fs.fixtures(FAMILY)


def sublayer(params, kind, j=0):
    return jax.tree_util.tree_map(lambda w: w[j], params["layers"][kind])


# --------------------------------------------------- what the program is told


def test_the_layers_count_sublayers_and_the_depth_stays_the_published_one():
    assert CFG.num_layers == 2 and CFG.layer_kinds == SHORTCUT_KINDS * 2
    assert CFG.latent and CFG.hybrid and CFG.model_type == "longcat_flash"
    assert [CFG.layer_ffn(k) for k in CFG.layer_kinds] == ["experts", "dense"] * 2
    assert CFG.paged_layers == 4 and CFG.page_pool_shape(9, 8) == (9, 8, 128)
    assert CFG.second_pool_shape(9, 8) is None
    assert CFG.held_experts == (0, 1) and CFG.router_width == 8 + 4
    assert UNCUT.held_experts == tuple(range(8)) and UNCUT.router_width == 12
    assert CFG.layer_runs == tuple(
        (kind, i, i // 2, 1) for i, kind in enumerate(SHORTCUT_KINDS * 2))
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    assert set(state) == {"lin", "pooled", "moe_stats", "moe_blocks", "moe_routed",
                          "moe_zero", "latent_stats"}
    assert state["moe_zero"].shape == (1,)
    # Kimi-VL's shape holds what it held: no counter of pairs that compute nothing
    assert "moe_zero" not in hybrid.init_mixer_state(PRESETS["tiny-latent-moe"], 5, 64)
    params = init_params(jax.random.PRNGKey(0), CFG)
    attention = {"attn_norm", "wq_a", "q_a_norm", "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo",
                 "mlp_norm"}
    mlp = {"w_gate", "w_up", "w_down"}
    experts = {"router", "e_score_bias", "experts_gate", "experts_up", "experts_down"}
    assert set(params["layers"]) == set(SHORTCUT_KINDS)
    assert set(params["layers"]["latent_fork"]) == attention | mlp | experts
    assert set(params["layers"]["latent_join"]) == attention | mlp
    fork = params["layers"]["latent_fork"]
    assert fork["router"].shape == (2, 64, 12)  # the routed experts AND the four of nothing
    assert fork["experts_gate"].shape == (2, 2, 64, 32)  # the two held
    assert fork["w_gate"].shape == (2, 64, 128)  # the dense MLP beside them
    with pytest.raises(ValueError, match="shortcut_moe is a latent-attention layer pair"):
        dataclasses.replace(CFG, kv_lora_rank=0)


def test_from_hf_config_reads_the_benchmarks_file_and_the_counts_agree():
    cfg = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    assert cfg.shortcut_moe and cfg.router_softmax and not cfg.norm_topk_prob
    assert cfg.num_layers == 4 and cfg.paged_layers == 8
    assert cfg.layer_kinds == SHORTCUT_KINDS * 4
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size) == (
        6144, 12288, 2048)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.head_dim, cfg.v_head_dim) == (
        1536, 512, 192, 128)
    assert (cfg.latent_dim, cfg.latent_row, cfg.num_heads) == (576, 640, 64)
    assert (cfg.experts_per_token, cfg.zero_experts, cfg.router_width) == (12, 256, 768)
    assert cfg.held_experts == tuple(range(16)) and cfg.router_experts == 512
    assert cfg.latent_q_scale == 2.0 and abs(cfg.latent_kv_scale - 12 ** 0.5) < 1e-12
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.routed_scaling_factor) == (1e7, 1e-5, 6.0)
    assert cfg.vocab_size == 16384 and not cfg.tie_word_embeddings

    def tree_count(cfg):
        shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))

    model = dataclasses.asdict(cfg)
    assert scmoe_counts.param_count(model) == tree_count(cfg) == 5_172_749_312
    assert scmoe_counts.param_count(dataclasses.asdict(CFG)) == tree_count(CFG)
    # the issue's arithmetic: 90.57M an attention, 226.49M an MLP, 37.75M an expert
    assert scmoe_counts.attention_params(model) == 90_570_752
    assert scmoe_counts.mlp_params(model) == 226_492_416
    assert scmoe_counts.expert_params(model) == 37_748_736
    # what is held, without the embedding's gather; what a token runs: 8 of its 12
    # choices on a routed expert under an even router
    held = cfg.total_matmul_param_count + cfg.vocab_size * cfg.hidden_size
    small = 4 * scmoe_counts.layer_small_params(model) + cfg.hidden_size
    assert held + small == 5_172_749_312
    assert cfg.total_matmul_param_count - cfg.matmul_param_count == 4 * (16 - 8) * 37_748_736
    # the whole model states no share: every expert here, and the same widths
    whole = fs.hf_config(FAMILY, num_layers=28, n_routed_experts=512, vocab_size=131072)
    del whole.share
    full = ModelConfig.from_hf_config(whole)
    assert full.held_experts == tuple(range(512)) and full.router_width == 768
    assert len(full.layer_kinds) == 56


@pytest.mark.parametrize("file", sorted(
    name for name in os.listdir(os.path.join(fs.REPO, "perfbench", "configs"))
    if name != FAMILY.config_file))
def test_softmax_scoring_stays_refused_for_every_other_family(file):
    """``_refuse_router_variants`` lets softmax through for no one: this
    family's own field function reads it, and every other family with a
    DeepSeek-style router still names the key."""
    from types import SimpleNamespace

    with open(os.path.join(fs.REPO, "perfbench", "configs", file)) as f:
        held = json.load(f)
    if "n_routed_experts" not in held:
        ModelConfig.from_hf_config(SimpleNamespace(**held))  # no such router: loads as ever
        return
    with pytest.raises(ValueError, match="scoring_func"):
        ModelConfig.from_hf_config(SimpleNamespace(**{**held, "scoring_func": "softmax"}))


# ------------------------------------------------------------ the mechanism


def test_every_tokens_choice_and_weights_are_the_references(weights):
    """``route`` in its softmax form: the same 3 of 12 outputs a token as the
    reference's one-at-a-time argmax picks, weighted 6 x the score."""
    params, _ = weights
    layer = sublayer(params, "latent_fork", 1)
    u = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    idx, w = moe.route(u, layer["router"], layer["e_score_bias"], CFG)
    comb = np.asarray(ref.combine_matrix(u, layer, CFG))
    got = np.zeros_like(comb)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=-1)
    np.testing.assert_allclose(got, comb, atol=1e-6)
    assert ((comb > 0).sum(-1) == 3).all()
    # unnormalised: a token's weights are 6 x scores that sum to less than one
    assert (comb.sum(-1) < 6.0).all() and comb.sum(-1).std() > 0.05
    assert (np.asarray(idx) >= 8).any() and (np.asarray(idx) < 2).any()


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_a_choice_that_computes_nothing_costs_no_block_and_is_counted_apart(
        weights, form, monkeypatch):
    """Every token sent to the experts of nothing (a bias no score outweighs):
    the block's output is (the sum of the weights) x u, no pair is computed, no
    block of the grouped form runs, and the fifth of the stats counts 3 a token."""
    monkeypatch.setattr(moe, "expert_form", fs.expert_forms(64 if form == "dense" else 8))
    params, _ = weights
    layer = sublayer(params, "latent_fork", 0)
    layer["e_score_bias"] = jnp.where(jnp.arange(12) >= 8, 10.0, -10.0)
    u = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    y, stats = jax.jit(lambda u, p: moe.moe_half(u, p, CFG, held=CFG.held_experts))(u, layer)
    comb = ref.combine_matrix(u, layer, CFG)
    assert float(jnp.abs(comb[:, :8]).max()) == 0.0
    np.testing.assert_allclose(y, comb.sum(-1, keepdims=True) * u, atol=1e-6)
    assert stats.tolist()[:3] == [0, 0, 0] and int(stats[4]) == 3 * 40


def test_a_call_of_more_pairs_than_one_layout_holds_runs_in_equal_runs_of_tokens(
        weights, monkeypatch):
    """``moe.GROUPED_MAX_PAIRS``: the grouped form's buffers are sized by ALL of
    a call's pairs, whoever holds their experts, so a call of more (the cell's
    prefill segment: 16 x 1,024 tokens x 12 choices) is cut into equal runs of
    tokens, one after another. Same values, same pairs, the fullest expert's
    load over the whole call; only the blocks laid differ (a run pads its own)."""
    monkeypatch.setattr(moe, "expert_form", fs.expert_forms(8))
    params, _ = weights
    layer = sublayer(params, "latent_fork", 1)
    u = jax.random.normal(jax.random.PRNGKey(11), (5, 8, 64))  # 40 tokens, 120 pairs
    alive = jnp.asarray([True, True, False, True, True])
    run = lambda: jax.jit(lambda u, p: moe.moe_half(
        u, p, CFG, held=CFG.held_experts, alive=alive))(u, layer)
    whole, stats = run()
    monkeypatch.setattr(moe, "GROUPED_MAX_PAIRS", 30)  # four runs of 10 tokens
    cut, cut_stats = run()
    np.testing.assert_allclose(cut, whole, atol=1e-6)
    assert cut_stats[:2].tolist() == stats[:2].tolist() and int(stats[0]) > 0
    assert int(cut_stats[4]) == int(stats[4]) > 0
    assert int(cut_stats[3]) == 4 * (30 // 64 + 3) > int(stats[3]) == 120 // 64 + 3
    monkeypatch.setattr(moe, "GROUPED_MAX_PAIRS", 31)  # 40 tokens have no run of 31 pairs:
    np.testing.assert_allclose(run()[0], whole, atol=1e-6)  # the next count that divides them


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_the_shares_and_the_part_that_computes_nothing_add_up_to_the_whole_layer(
        form, monkeypatch):
    """The guide's section 4 at the tiny size, over 4 disjoint shares of the
    router's 8 routed experts: each chip's routed part (the program's is the
    reference's), summed, with the zero-compute part, both attentions and both
    MLPs counted ONCE, is the uncut reference's whole published layer."""
    monkeypatch.setattr(moe, "expert_form", fs.expert_forms(64 if form == "dense" else 8))
    whole, _ = fs.seeded(FAMILY, UNCUT)
    fork, join = sublayer(whole, "latent_fork", 1), sublayer(whole, "latent_join", 1)
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    valid, positions = jnp.ones((24,), bool), jnp.arange(24)
    run = lambda cfg, layer, carry, k: ref._sublayer(
        *carry, valid, positions, layer, None, cfg, 1.0, k)
    mid, e_whole = run(UNCUT, fork, (x, jnp.zeros_like(x)), 0)
    want, _ = run(UNCUT, join, (mid, e_whole), 1)
    stream = want - e_whole  # both attentions and both MLPs: no expert in it
    a = x + ref._attention(ref._rms_norm(x, fork["attn_norm"], CFG.rms_norm_eps), valid,
                           positions, fork, None, UNCUT, 1.0)
    u = ref._rms_norm(a, fork["mlp_norm"], CFG.rms_norm_eps)  # what the experts read
    zero = ref.zero_part(u, ref.combine_matrix(u, fork, UNCUT), UNCUT)
    total = jnp.zeros_like(x)
    for shard in range(4):
        share = dataclasses.replace(CFG, expert_shard=shard)
        assert ref.held_ids(share) == [2 * shard, 2 * shard + 1] == list(share.held_experts)
        held = {**fork, **{name: fork[name][2 * shard: 2 * shard + 2]
                           for name in ("experts_gate", "experts_up", "experts_down")}}
        b_s, e_s = run(share, held, (x, jnp.zeros_like(x)), 0)
        np.testing.assert_allclose(b_s, mid, atol=1e-6)  # no share moves the stream
        got, _ = moe.moe_half(u, held, share, held=share.held_experts)
        np.testing.assert_allclose(got, e_s, atol=2e-5)  # this chip's part, zeros whole
        total = total + (e_s - zero)
    np.testing.assert_allclose(total + zero, e_whole, atol=2e-5)
    np.testing.assert_allclose(stream + total + zero, want, atol=2e-5)
    assert float(jnp.abs(zero).max()) > 0.05 and float(jnp.abs(total).max()) > 0.05


# ------------------------------------------------------------------ the loader


def published_state_dict(params, cfg):
    """The stacked tree under the checkpoint's names, written by hand: two
    attentions, four norms and two MLPs a layer in lists, one expert block."""
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    attn = {"wq_a": "q_a_proj", "wq": "q_b_proj", "wkv_a": "kv_a_proj_with_mqa",
            "wkv_b": "kv_b_proj", "wo": "o_proj"}
    for i in range(cfg.num_layers):
        at = f"model.layers.{i}."
        for k, kind in enumerate(SHORTCUT_KINDS):
            layer = jax.tree_util.tree_map(np.asarray, sublayer(params, kind, i))
            sd[f"{at}input_layernorm.{k}.weight"] = layer["attn_norm"]
            sd[f"{at}post_attention_layernorm.{k}.weight"] = layer["mlp_norm"]
            sd[f"{at}self_attn.{k}.q_a_layernorm.weight"] = layer["q_a_norm"]
            sd[f"{at}self_attn.{k}.kv_a_layernorm.weight"] = layer["kv_a_norm"]
            for ours, theirs in attn.items():
                sd[f"{at}self_attn.{k}.{theirs}.weight"] = layer[ours].T
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                sd[f"{at}mlps.{k}.{theirs}.weight"] = layer[ours].T
                if k == 0:
                    for e in range(cfg.n_routed_experts):
                        sd[f"{at}mlp.experts.{e}.{theirs}.weight"] = layer[
                            "experts" + ours[1:]][e].T
            if k == 0:
                sd[f"{at}mlp.router.classifier.weight"] = layer["router"].T
                sd[f"{at}mlp.router.e_score_correction_bias"] = layer["e_score_bias"]
    return sd


def test_the_published_names_load_into_the_sublayers_stacks(weights):
    """Every tensor of the layers that are run is used exactly once, a layer's
    two attentions land in the two kinds' stacks at the layer's index, and the
    loaded model is the reference's function."""
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    params, _ = weights
    sd = published_state_dict(params, CFG)
    assert sd["model.layers.1.mlp.router.classifier.weight"].shape == (12, 64)
    assert sd["model.layers.0.self_attn.1.kv_a_proj_with_mqa.weight"].shape == (40, 64)
    assert "model.layers.1.mlp.experts.2.up_proj.weight" not in sd  # two are held
    loaded = params_from_state_dict(dict(sd), CFG)
    for a, b in zip(jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    back = state_dict_from_params(params, CFG)
    assert set(back) == set(sd)
    for name in sd:
        np.testing.assert_array_equal(back[name], sd[name], err_msg=name)
    sd.pop("model.layers.1.self_attn.1.q_b_proj.weight")
    with pytest.raises(KeyError, match=r"layers\.1\.self_attn\.1\.q_b_proj"):
        params_from_state_dict(sd, CFG)


def test_a_saved_snapshot_loads_back_as_the_same_model(weights, tmp_path):
    from distrl_llm_tpu.models.loading import load_pretrained, save_hf_checkpoint

    params, _ = weights
    save_hf_checkpoint(jax.tree_util.tree_map(np.asarray, params), CFG, str(tmp_path))
    loaded, cfg = load_pretrained(str(tmp_path))
    assert cfg == dataclasses.replace(CFG, max_position_embeddings=cfg.max_position_embeddings)
    for a, b in zip(jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_adapter_factors_follow_both_sublayers_and_merge(weights):
    from distrl_llm_tpu.models.lora import LATENT_RANK_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == set(SHORTCUT_KINDS)
    for kind in SHORTCUT_KINDS:
        stack = lora["layers"][kind]
        assert set(stack) == set(LATENT_RANK_TARGETS)  # no router, no routed expert
        assert stack["wq"]["a"].shape == (2, 48, 4) and stack["wq_a"]["b"].shape == (2, 4, 48)
        assert stack["w_gate"]["b"].shape[-1] == 128  # the dense MLP's, in both
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_leaf_has_a_partition_spec_and_the_view_holds_wq_alone(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.models.transformer import decode_view
    from distrl_llm_tpu.ops.linear import OutIn
    from distrl_llm_tpu.parallel.partition import param_specs

    params, _ = weights
    specs = param_specs(params)["layers"]
    for kind in SHORTCUT_KINDS:
        assert set(specs[kind]) == set(params["layers"][kind])
    for name in ("router", "e_score_bias", "experts_gate", "wkv_a", "wkv_b"):
        leaf = params["layers"]["latent_fork"][name]
        assert specs["latent_fork"][name] == P(*([None] * leaf.ndim)), name
    view = decode_view(params)["layers"]
    for kind in SHORTCUT_KINDS:
        assert isinstance(view[kind]["wq"], tuple) and isinstance(view[kind]["wq"][0], OutIn)
        assert view[kind]["wo"] is params["layers"][kind]["wo"]


# -------------------------------------------------------------- the budget


def test_the_budget_and_the_pool_count_eight_sublayers_for_four_layers():
    from distrl_llm_tpu.engine import budget

    cfg = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    # 128 rows of 640 lanes, bf16, in each of 8 pools: 1,280 bytes a token a sublayer
    assert budget.page_bytes(cfg, 128) == 128 * 640 * 2 * 8
    assert budget.page_bytes(CFG, 8) == 8 * 128 * 2 * 4
    assert budget.page_bytes(cfg, 128) // 128 == 10_240  # the issue's 10.24 kB a token
