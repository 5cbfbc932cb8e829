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

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_dsa_moe.py``.
"""

import dataclasses
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from distrl_llm_tpu.config import SamplingConfig  # noqa: E402
from distrl_llm_tpu.models import ModelConfig, forward, init_lora_params, init_params  # noqa: E402
from distrl_llm_tpu.models import hybrid, moe, transformer  # noqa: E402
from distrl_llm_tpu.models.configs import PRESETS  # noqa: E402
from distrl_llm_tpu.ops import latent_attention, token_index  # noqa: E402
from perfbench import dsa_moe_counts  # noqa: E402
from perfbench import reference_dsa_moe as ref  # noqa: E402

CFG = PRESETS["tiny-dsa"]
LORA_SCALE = 2.0
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs", "glm-5-ep16-L5.json")
TARGETS = {"wq_a", "wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down"}


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(cfg, rank=4):
    """Seeded weights with every term alive: norms off 1 (the latents' and the
    index key's too), a LayerNorm bias, a correction bias and an adapter's b
    that are not zero."""
    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        if name.endswith("norm"):
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "b_index_k":
            return 0.3 * jax.random.normal(key, x.shape)
        if name == "e_score_bias":
            return 0.05 * jax.random.normal(key, x.shape)
        return 6.0 * x

    params = jax.tree_util.tree_map_with_path(base, init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x,
        init_lora_params(jax.random.PRNGKey(1), cfg, rank),
    )
    return params, lora


@pytest.fixture(scope="module")
def weights():
    return seeded(CFG)


#: the reference's whole program, traced once a configuration and a shape
_reference = jax.jit(
    ref.next_token_logprobs, static_argnums=1, static_argnames=("lora_scale",))


def reference_logprobs(params, lora, ids, mask, cfg=CFG):
    return np.asarray(_reference(
        params, cfg, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
        lora_scale=LORA_SCALE))


def forward_logprobs(params, lora, ids, mask, cfg=CFG, **kw):
    logits, _ = forward(params, cfg, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE, **kw)
    return np.asarray(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], jnp.asarray(ids)[:, 1:, None], -1)[..., 0])


def padded_rows(width=40):
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, width), 1, 256))
    mask = np.ones((3, width), np.int32)
    mask[0, :7] = 0
    mask[1, width - 7:] = 0
    return ids, mask, (mask[:, 1:] * mask[:, :-1]) > 0


def hf_config(**changes):
    with open(CONFIG_FILE) as f:
        return SimpleNamespace(**{**json.load(f), **changes})


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
    full = ModelConfig.from_hf_config(hf_config())
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
    cfg = ModelConfig.from_hf_config(hf_config())
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


@pytest.mark.parametrize("changes,named", [
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
    ({"model_type": "glm_moe"}, "glm_moe"),
])
def test_from_hf_config_refuses_what_it_cannot_represent(changes, named):
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(hf_config(**changes))


def test_the_loader_refuses_a_checkpoint_by_name_in_both_directions(weights):
    from distrl_llm_tpu.models import loading

    with pytest.raises(NotImplementedError, match="glm_moe_dsa"):
        loading.params_from_state_dict({}, CFG)
    with pytest.raises(NotImplementedError, match="glm_moe_dsa"):
        loading.state_dict_from_params(weights[0], CFG)


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("remat", [False, True])
def test_forward_equals_the_reference_past_the_index(weights, remat):
    """Rows of 40 tokens, five times ``index_topk``, padded on either side."""
    params, lora = weights
    ids, mask, both = padded_rows()
    got = forward_logprobs(params, lora, ids, mask, remat=remat)
    assert np.abs(got - reference_logprobs(params, lora, ids, mask))[both].max() < 2e-5


@pytest.mark.parametrize("tokens", [6, 8, 9, 70])
def test_forward_equals_the_reference_on_both_sides_of_the_topk(weights, tokens):
    params, lora = weights
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(tokens), (2, tokens), 1, 256))
    mask = np.ones_like(ids)
    got = forward_logprobs(params, lora, ids, mask)
    assert np.abs(got - reference_logprobs(params, lora, ids, mask)).max() < 2e-5


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


def _control(monkeypatch, name, cfg=CFG):
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


FORWARD_CONTROLS = ["no_choice", "newest_tokens", "topk_halved", "no_relu", "no_head_weights",
                    "no_index_rope", "no_q_a_norm", "top3_experts"]


@pytest.mark.parametrize("control", FORWARD_CONTROLS)
def test_the_forward_can_tell_each_mechanism(weights, control, monkeypatch):
    """Each thing the chip's controls bend moves this file's agreement by far
    more than its tolerance: a check that passes with one missing is no check."""
    params, lora = weights
    ids, mask, both = padded_rows()
    cfg = _control(monkeypatch, control)
    got = forward_logprobs(params, lora, ids, mask, cfg)
    assert np.abs(got - reference_logprobs(params, lora, ids, mask))[both].max() > 2e-3


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
    params, _ = seeded(CFG)
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


# ---------------------------------------------------------------- the learner


def test_the_learners_loss_and_adapter_gradient_are_the_references(weights):
    """No cache, remat, chunked cross-entropy over rows five times
    ``index_topk`` long: the policy-gradient loss over the answers and its
    gradient in every adapter factor against plain reverse mode through the
    reference. The choice is not differentiated in either."""
    from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

    params, lora = weights
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 256, (4, 12)).astype(np.int32)
    pmask = np.ones((4, 12), np.int32)
    pmask[0, :5] = 0
    answer = rng.integers(1, 256, (4, 28)).astype(np.int32)
    amask = np.ones((4, 28), np.int32)
    amask[2, 14:] = 0
    coeffs = jnp.asarray([0.7, -1.1, 0.4, 1.3])

    def loss(lo):
        logp = answer_logprobs(
            params, CFG, jnp.asarray(prompt), jnp.asarray(pmask), jnp.asarray(answer),
            jnp.asarray(amask), lora=lo, lora_scale=LORA_SCALE, remat=True, logit_chunk=8)
        return pg_loss(logp, jnp.asarray(amask), coeffs)

    got_loss, got = jax.value_and_grad(loss)(lora)
    ids = np.concatenate([prompt, answer], 1)
    mask = np.concatenate([pmask, amask], 1)
    scored = np.concatenate([np.zeros_like(pmask), amask], 1)
    want_loss, want = ref.pg_loss_and_lora_grad(
        params, CFG, lora, LORA_SCALE, jnp.asarray(ids), jnp.asarray(mask),
        jnp.asarray(scored), coeffs)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) == 2 * 8 * 2  # a and b: eight targets in each of two stacks
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-6,
                                   err_msg=str(path))


def test_a_train_step_moves_the_adapter_and_nothing_else(weights):
    import optax

    from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step

    params, lora = weights
    rng = np.random.default_rng(2)
    batch = UpdateBatch(
        prompt_ids=jnp.asarray(rng.integers(1, 256, (4, 12)), jnp.int32),
        prompt_mask=jnp.ones((4, 12), jnp.int32),
        answer_ids=jnp.asarray(rng.integers(1, 256, (4, 12)), jnp.int32),
        answer_mask=jnp.ones((4, 12), jnp.int32),
        coeffs=jnp.asarray([1.0, -1.0, 0.5, -0.5]),
        sample_mask=jnp.ones((4,), jnp.float32),
    )
    optimizer = optax.adam(1e-3)
    step = make_train_step(CFG, learner_type="pg", optimizer=optimizer,
                           lora_scale=LORA_SCALE, micro_size=2, donate=False)
    new_lora, _, loss = step(lora, optimizer.init(lora), params, batch)[:3]
    assert np.isfinite(float(loss))
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()), new_lora, lora)
    assert all(m > 0 for m in jax.tree_util.tree_leaves(moved))
    for kind in ("latent", "latent_moe"):  # the index, the router and the experts: none
        assert set(new_lora["layers"][kind]) == TARGETS


# --------------------------------------------------------------- the share


def test_eight_shares_sum_to_the_whole_layer_and_the_program_holds_its_own():
    """The eight chips' routed parts, with the shared expert and attention
    counted once, are what the uncut reference gives for the whole layer; the
    program's part for a share is the reference's; the latent family tells
    ``moe_half`` what it holds, as the other families with a share do."""
    uncut = dataclasses.replace(CFG, n_routed_experts=16, router_experts=0)
    whole, _ = seeded(uncut)
    layer = jax.tree_util.tree_map(lambda w: w[1], whole["layers"]["latent_moe"])
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    want = ref.routed_part(h, layer, uncut)
    total = jnp.zeros_like(want)
    for shard in range(8):
        share = dataclasses.replace(CFG, expert_shard=shard)
        assert ref.held_ids(share) == [2 * shard, 2 * shard + 1] == list(share.held_experts)
        held = {**layer, **{name: layer[name][2 * shard: 2 * shard + 2]
                            for name in ("experts_gate", "experts_up", "experts_down")}}
        part = ref.routed_part(h, held, share)
        got, _ = moe.moe_half(h, held, share, held=share.held_experts)
        np.testing.assert_allclose(got, part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1


# -------------------------------------------------------------- the engine


def make_engine(scheduler, slots, cfg=CFG, prompt=64, **kw):
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("page_size", 8)
    return PagedGenerationEngine(
        cfg, max_prompt_tokens=prompt, max_new_tokens=24, eos_token_ids=[-1],
        pad_token_id=0, lora_scale=LORA_SCALE,
        scheduler=scheduler, max_concurrent_rows=slots, capture_logprobs=True,
        autotune=False, **kw)


def prompts(lengths, width=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.zeros((len(lengths), width), np.int32)
    mask = np.zeros((len(lengths), width), np.int32)
    for r, n in enumerate(lengths):
        ids[r, width - n:] = rng.integers(1, 256, n)
        mask[r, width - n:] = 1
    return ids, mask


@pytest.fixture
def small_pieces(monkeypatch):
    """Prefill in segments of 16 tokens (two pages of 8: every segment after
    the first chooses 8 of what it sees, the first chooses all), the decode
    walk 3 columns a row and 4 rows a group with a shared block of 6 pages, so
    that 40-57-token prompts cross every boundary the cell's 10k-20k-token
    prompts cross: index keys read from earlier segments' pages, a group's
    shared blocks of keys beside its private ones, a last segment that is part
    padding."""
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
    monkeypatch.setattr(hybrid, "LATENT_DECODE_PAGES", 3)
    monkeypatch.setattr(hybrid, "LATENT_DECODE_ROWS", 4)
    monkeypatch.setattr(latent_attention, "SHARED_SCORE_BYTES", 4 * 4 * 8 * 4 * 6)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)  # decode rows dense, segments grouped


def worst_difference(params, lora, ids, mask, result, cfg=CFG):
    worst = 0.0
    for b in range(ids.shape[0]):
        prompt = ids[b][mask[b] > 0]
        rows = np.stack([np.concatenate([prompt, result.tokens[b, j]])
                         for j in range(result.tokens.shape[1])])
        want = reference_logprobs(params, lora, rows, np.ones_like(rows), cfg)
        worst = max(worst, np.abs(result.logprobs[b] - want[:, len(prompt) - 1:]).max())
    return worst


def generate(engine, params, lora, lengths=(40, 57), width=64):
    ids, mask = prompts(lengths, width)
    result = engine.generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=24),
        jax.random.PRNGKey(3))
    return ids, mask, result


@pytest.mark.parametrize("scheduler,slots", [
    ("refill", 4),  # 8 rows through 4 slots: a freed slot takes another prompt's pages
    ("refill", 8),  # every candidate admitted at once
    ("waves", 0),   # prefill, fan-out, lockstep
])
def test_generate_equals_the_reference_token_by_token(weights, scheduler, slots,
                                                      small_pieces):
    """Both schedulers hold a model that keeps two paged arrays a layer under
    one page table: prefill in segments whose queries choose among earlier
    segments' keys, each prompt's latent AND index-key pages aliased to its 4
    candidates, then one token a step: index scores over the table, an exact
    choice of 8 of 41-81 tokens, their rows gathered. The engine's own captured
    log-probability of every token it sampled is the reference's full
    forward's; the counters are the counts module's."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = make_engine(scheduler, slots)
    ids, mask, result = generate(engine, params, lora)
    assert (result.lengths == 24).all() and result.alive_slot_steps == 8 * 24
    assert worst_difference(params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()["counters"]
    said = tuple(after[f"engine/index_tokens_{k}"] - before.get(f"engine/index_tokens_{k}", 0)
                 for k in ("attended", "visible"))
    want = dsa_moe_counts.index_tokens(
        dataclasses.asdict(CFG), [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    assert said == want == (3 * 8 * 24, 3 * 8 * 24)  # under 128 tokens: one unit each
    routed = after["engine/moe_pairs_routed"] - before.get("engine/moe_pairs_routed", 0)
    assert routed == 2 * 4 * 8 * 24  # expert layers x choices x rows x steps: not layer 0
    assert "engine/latent_pages_read" not in after or after["engine/latent_pages_read"] == (
        before.get("engine/latent_pages_read", 0))  # no dense walk ran
    assert engine.last_round_stats["slot_state_bytes"] == 0  # all of a slot is in pages


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
    _, _, plain = generate(make_engine("waves", 0), params, lora)
    assert folds() == before  # the CPU's own form: none
    monkeypatch.setattr(latent_attention, "expanded_segment_impl", lambda q_nope, v_dim: "kernel")
    monkeypatch.setattr(latent_attention, "expanded_fold_kernel", functools.partial(
        latent_attention.expanded_fold_kernel, interpret=True))
    ids, mask, result = generate(make_engine("waves", 0), params, lora)
    assert folds() - before == 3 * 10
    np.testing.assert_array_equal(result.tokens, plain.tokens)
    np.testing.assert_allclose(result.logprobs, plain.logprobs, atol=2e-5)
    assert worst_difference(params, lora, ids, mask, result) < 2e-5


def test_past_128_tokens_the_counters_say_what_was_spared(monkeypatch):
    """An index of 128 tokens, prompts of 150 and 260 in segments of 64: a step
    attends one unit of 128 tokens where latent attention without the index
    would attend two or three, and the engine still equals the reference."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 64)
    wide = dataclasses.replace(CFG, index_topk=128)
    params, lora = seeded(wide)
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = make_engine("waves", 0, wide, prompt=272, page_size=16)
    ids, mask = prompts((150, 260), 272)
    result = engine.generate(
        params, lora, ids, mask, SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=24),
        jax.random.PRNGKey(3))
    assert worst_difference(params, lora, ids, mask, result, wide) < 2e-5
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


ENGINE_CONTROLS = {
    "keys_not_handed": lambda k, v: (k, tuple(jnp.zeros_like(x) for x in v)),
    "keys_from_other_prompt": lambda k, v: (k, tuple(
        jnp.roll(x, x.shape[0] // 2, axis=0) for x in v)),
}


@pytest.mark.parametrize("control", [*ENGINE_CONTROLS, "newest_tokens", "no_choice",
                                     "topk_halved", "no_index_rope", "bf16_keys"])
def test_this_files_agreement_can_tell_a_wrong_choice(weights, small_pieces, control,
                                                      monkeypatch):
    """What only the cache path can get wrong (the prompt's index keys not
    handed at the fan-out, or another prompt's; keys kept in bf16), and the
    controls of the chip's check through segments, fan-out and decode steps:
    the newest 8 tokens in place of the index's, no choice, 4 for 8, no RoPE."""
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    cfg = CFG
    if control in ENGINE_CONTROLS:
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, *rest = prefill(*a, **kw)
            return (*ENGINE_CONTROLS[control](k, v), *rest)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    elif control == "bf16_keys":
        inputs = hybrid._index_inputs
        monkeypatch.setattr(hybrid, "_index_inputs", lambda *a, **kw: tuple(
            jax.lax.reduce_precision(x, 8, 7) for x in inputs(*a, **kw)))
    else:
        cfg = _control(monkeypatch, control)
    ids, mask, result = generate(make_engine("waves", 0, cfg), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 5e-4


def test_the_fan_out_hands_both_arrays_of_pages(weights, small_pieces):
    """Greedy, 16 candidates of one prompt are 16 times the single row."""
    params, lora = weights
    ids, mask = prompts((45,))
    greedy = dict(temperature=0.0, top_p=1.0, max_tokens=12)
    many = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=16, **greedy), jax.random.PRNGKey(0))
    one = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=1, **greedy), jax.random.PRNGKey(0))
    assert (many.tokens == one.tokens[:, :1]).all()
    np.testing.assert_allclose(many.logprobs, np.repeat(one.logprobs, 16, 1), atol=1e-5)


def test_the_prefill_returns_index_keys_under_the_latent_rows_table(weights, small_pieces):
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    ids, mask = prompts((40, 57))
    k, v, logits, real_len, mixer = paged_engine._paged_prefill_hybrid(
        params, lora, jnp.asarray(ids), jnp.asarray(mask), cfg=CFG, prompt_pages=8,
        page_size=8, lora_scale=LORA_SCALE, cache_dtype=jnp.float32,
        attn_impl="reference", total_tokens=88)
    assert [x.shape for x in k] == [(16, 8, 128)] * 3 and [x.shape for x in v] == [(16, 8, 16)] * 3
    assert list(np.asarray(real_len)) == [40, 57] and "index_stats" in mixer
    # a token's key is where its row is; past a prompt's end both arrays are unread
    for layer in range(3):
        rows = np.abs(np.asarray(k[layer])).sum(-1) > 0
        keys = np.abs(np.asarray(v[layer])).sum(-1) > 0
        assert rows[:5].all() and keys[:5].all() and rows[8: 8 + 7].all() and keys[8: 8 + 7].all()
    # the latent row's last lanes are padding: 40 of 128 hold values
    assert not np.asarray(k[0])[..., 40:].any()


def test_the_rounds_span_and_trace_reports_line_say_what_was_attended(weights, tmp_path):
    from distrl_llm_tpu import telemetry
    from tools import trace_report

    params, lora = weights
    engine = make_engine("waves", 0)
    generate(engine, params, lora)  # warm-up: no compile/ span in the traced round
    telemetry.configure(True)
    try:
        telemetry.export_chrome_trace(str(tmp_path / "before.json"), clear=True)
        generate(engine, params, lora)
        path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"), clear=True)
    finally:
        telemetry.configure(False)
    events, metadata = trace_report.load_trace(path)
    (span,) = [e for e in events if e.get("name") == telemetry.ENGINE_DECODE]
    assert span["args"]["index_tokens_attended"] == 3 * 8 * 24
    assert span["args"]["index_tokens_visible"] == 3 * 8 * 24
    lines = trace_report.build_report(events, metadata).splitlines()
    (said,) = [line for line in lines if line.startswith("    host s:")]
    assert said.endswith(", index 576 of 576 x 128 tokens")


# ------------------------------------------------------------ the refusals


def _paged(**kw):
    return lambda: make_engine("refill", 4, **kw)


def _dense():
    from distrl_llm_tpu.engine.engine import GenerationEngine

    return GenerationEngine(CFG, max_prompt_tokens=64, max_new_tokens=8,
                            eos_token_ids=[-1], pad_token_id=0, autotune=False)


def _sharded():
    from distrl_llm_tpu.engine.sharded_paged import ShardedPagedEngine

    return ShardedPagedEngine(
        CFG, mesh=None, max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
        pad_token_id=0)


def _turn_hook():
    engine = make_engine("refill", 4)
    engine.turn_hook = lambda *a: None
    ids, mask = prompts((20,))
    return engine.generate(
        None, None, ids, mask, SamplingConfig(n=2, max_tokens=4), jax.random.PRNGKey(0))


@pytest.mark.parametrize("build,what", [
    (_dense, "dense engine"),
    (_sharded, "dp-sharded"),
    (_paged(kv_quant="int8"), "kv_quant"),
    (_paged(spec_draft=2), "spec_draft"),
    (_paged(prefix_sharing=True), "prefix_sharing"),
    (_paged(max_kv_pages=64), "max_kv_pages"),
    (_paged(continuous_admission=True, prefix_cache=True), "prefix_sharing"),
    (_paged(kv_spill=True), "kv_spill"),
    (_turn_hook, "turn_hook"),
], ids=["dense", "sharded", "int8_pool", "speculation", "pool_chains", "preemption",
        "radix_cache", "spill", "turn_resumption"])
def test_what_holds_k_and_v_of_one_kind_names_the_index_key_it_cannot_hold(build, what):
    """One sentence for every engine and feature that keeps K/V of one kind:
    it names the layers and what a token keeps for them, the index key beside
    the latent row (no new list)."""
    with pytest.raises(ValueError) as e:
        build()
    said = str(e.value)
    assert what in said and "latent-attention (MLA) and routed-expert layers" in said
    assert ("one latent row a token in place of K and V per head beside one index key a "
            "token in a second paged array") in said


@pytest.mark.parametrize("switch", ["paged_verify", "paged_chunked", "paged_prefix"])
def test_forward_refuses_the_dense_decoders_other_cache_modes(weights, switch):
    params, _ = weights
    cache = {"k": (), "v": (), "page_indices": jnp.zeros((1, 2), jnp.int32),
             "lengths": jnp.zeros((1,), jnp.int32)}
    with pytest.raises(NotImplementedError, match=switch):
        forward(params, CFG, jnp.ones((1, 1), jnp.int32), kv_cache=cache, page_size=8,
                **{switch: True})


# --------------------------------------------------------------- the budget


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
    full = ModelConfig.from_hf_config(hf_config())
    assert budget.page_bytes(full, 128) == 5 * 128 * 1536
    assert dsa_moe_counts.page_token_bytes(dataclasses.asdict(full)) == 1536


# ----------------------------------------------------- adapters and placement


def test_adapter_factors_are_each_stacks_own_and_merge(weights):
    from distrl_llm_tpu.models.lora import LATENT_RANK_TARGETS, LATENT_TARGETS, merge_lora

    params, lora = weights
    assert set(lora["layers"]) == {"latent", "latent_moe"}
    assert set(LATENT_RANK_TARGETS) == TARGETS and LATENT_RANK_TARGETS[:7] == LATENT_TARGETS
    for kind in lora["layers"]:
        assert set(lora["layers"][kind]) == TARGETS  # nothing on the index or the router
    assert lora["layers"]["latent"]["wq_a"]["a"].shape == (1, 64, 4)
    assert lora["layers"]["latent"]["wq"]["a"].shape == (1, 48, 4)  # q_b reads the latent
    assert lora["layers"]["latent"]["w_gate"]["b"].shape == (1, 4, 128)
    assert lora["layers"]["latent_moe"]["w_gate"]["b"].shape == (2, 4, 32)
    merged = merge_lora(params, lora, alpha=8.0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 1, 256)
    a, _ = forward(merged, CFG, ids)
    b, _ = forward(params, CFG, ids, lora=lora, lora_scale=2.0)
    np.testing.assert_allclose(a, b, atol=2e-4)


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
    from distrl_llm_tpu.engine import paged_engine

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
    from distrl_llm_tpu.engine import paged_engine

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
    result = make_engine(scheduler, slots).generate(
        params, lora, *prompts((40, 57)),
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

    from distrl_llm_tpu.engine import paged_engine

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
