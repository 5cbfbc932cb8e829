"""Compressed convolutional attention with an MLP router (ZAYA1-8B, ``zaya``)
against its plain reference, ``perfbench/reference_cca_moe.py`` (full causal
scores over the whole row: no cache, no page, no tail), at a small size on the
CPU: the ``tiny-cca`` preset (hidden 64, three layers, 4 query heads over 2 KV
heads of 16 in the latent, the first 8 of a head rotated, a router of width 16
choosing 1 of 4 experts). Float32 throughout, seeded weights with every term
alive.

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_cca_moe.py``.
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
from distrl_llm_tpu.models import hybrid, moe  # noqa: E402
from distrl_llm_tpu.models.configs import PRESETS  # noqa: E402
from perfbench import cca_moe_counts  # noqa: E402
from perfbench import reference_cca_moe as ref  # noqa: E402

CFG = PRESETS["tiny-cca"]
LORA_SCALE = 2.0
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs", "zaya1-8b-L20.json")
#: bytes of one slot's tail in one layer (float32 caches here)
TAIL_BYTES = (2 * (64 + 32) + 16) * 4


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(cfg=CFG, rank=4):
    """Seeded weights with every term alive: norms, temperatures, taps and the
    residual's scales off 1, every bias, shift and adapter b off 0, a router
    whose probabilities differ by more than its balancing bias."""
    def base(path, x):
        name = str(path[-1].key)
        noise = jax.random.normal(jax.random.PRNGKey(sum(map(ord, str(path))) % 9973), x.shape)
        if name.endswith("norm") or name in ("k_temp", "attn_res_scale", "mlp_res_scale"):
            return 1.0 + 0.3 * noise
        if name in ("conv0", "router_gamma"):
            return 0.6 + 0.3 * noise
        if name.startswith("b_") or name.endswith("res_shift"):
            return 0.1 * noise
        if name == "e_score_bias":
            return 0.05 * noise
        return (30.0 if name.startswith("router_w") else 6.0) * x

    params = jax.tree_util.tree_map_with_path(base, init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x,
        init_lora_params(jax.random.PRNGKey(1), cfg, rank),
    )
    return params, lora


@pytest.fixture(scope="module")
def weights():
    return seeded()


#: the reference's whole program, traced once a shape
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


@pytest.mark.parametrize("changes,named", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"layer_types": ["hybrid", "hybrid_sliding"] * 20}, "layer_types"),
    ({"layer_types": None}, "layer_types"),
    ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
    ({"cca_time0": 4}, "cca_time0"),
    ({"cca_time1": 3}, "cca_time1"),
    ({"attention_bias": True}, "attention_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"share": {"chips_per_layer": 2, "published": {"num_experts": 16}}}, "reads no share yet"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"rope_parameters": {"hybrid": {"rope_theta": 5000000, "rope_type": "yarn"}}},
     "rope_parameters"),
    ({"zaya_use_mod": True}, "zaya_use_mod"),
    ({"zaya_use_eda": False}, "zaya_use_eda"),
    ({"scale_residual_merge": False}, "scale_residual_merge"),
    ({"model_type": "zaya1_vl"}, "zaya1_vl"),
])
def test_from_hf_config_refuses_what_it_cannot_represent(changes, named):
    file = {**json.load(open(CONFIG_FILE)), **changes}
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(SimpleNamespace(**file))


def test_the_loader_refuses_a_checkpoint_by_name_in_both_directions(weights):
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    with pytest.raises(NotImplementedError, match="zaya.*seeded weights"):
        params_from_state_dict({}, CFG)
    with pytest.raises(NotImplementedError, match="zaya.*seeded weights"):
        state_dict_from_params(weights[0], CFG)


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
def test_what_holds_k_and_v_of_one_kind_names_the_tail_it_cannot_hold(build, what):
    """The nine refusals that name a row state name this one: a layer that
    keeps pages AND a tail."""
    with pytest.raises(ValueError) as e:
        build()
    said = str(e.value)
    assert what in said and "hybrid layers" in said
    assert "K/V pages and, beside them in the same layer, a row state" in said
    assert "convolutions' tail" in said


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("remat", [False, True])
def test_forward_equals_the_reference(weights, remat):
    """``full`` mode over rows padded on either side: a row starts from zeros,
    and a padded token feeds neither the convolutions nor the shift."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    assert np.abs(forward_logprobs(params, lora, ids, mask, remat=remat) - want)[both].max() < 2e-5
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE)
    whole = np.asarray(ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask),
                                       lora=lora, lora_scale=LORA_SCALE))
    assert np.abs(np.asarray(logits) - whole)[mask > 0].max() < 2e-5


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


def _control(monkeypatch, name):
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
    return CFG


FORWARD_CONTROLS = ["shift_dropped", "qk_mean_dropped", "temperature_dropped",
                    "first_convolution_dropped", "second_convolution_dropped",
                    "r_not_carried", "p_not_multiplied", "top_1_before_the_bias",
                    "residual_unscaled", "whole_head_rotated"]


@pytest.mark.parametrize("control", FORWARD_CONTROLS)
def test_the_forward_can_tell_each_mechanism(weights, control, monkeypatch):
    """Each mechanism dropped or bent IN THE PROGRAM moves the log-probabilities
    a hundred times further from the reference than the sound program's 2e-5."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    cfg = _control(monkeypatch, control)
    assert np.abs(forward_logprobs(params, lora, ids, mask, cfg) - want)[both].max() > 2e-3


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
    # choice are dense (up to ``DENSE_MAX_TOKENS_TOP1``), over it grouped
    assert moe.DENSE_MAX_TOKENS_TOP1 == 192 > moe.DENSE_MAX_TOKENS == 128
    for most in (192, 8):
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS_TOP1", most)
        y, stats = moe.moe_half(h, layer, CFG, choice=(idx, w))
        np.testing.assert_allclose(y, ref.routed_part(h, prob, layer), atol=2e-5)
        assert int(stats[0]) == 24


def test_the_learners_loss_and_adapter_gradient_are_the_references(weights):
    """No cache, remat, chunked cross-entropy: the policy-gradient loss over the
    answers and its gradient in every adapter factor against plain reverse mode
    through the reference."""
    from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

    params, lora = weights
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 256, (4, 12)).astype(np.int32)
    pmask = np.ones((4, 12), np.int32)
    pmask[0, :5] = 0
    answer = rng.integers(1, 256, (4, 20)).astype(np.int32)
    amask = np.ones((4, 20), np.int32)
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
    assert len(leaves) == 2 * 5  # a and b of q, k, the value's two halves and o
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
    assert set(new_lora["layers"]["cca"]) == {"wq", "wk", "wv1", "wv2", "wo"}


# -------------------------------------------------------------- the engine


def make_engine(scheduler, slots, cfg=CFG, prompt=64, **kw):
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("page_size", 8)
    return PagedGenerationEngine(
        cfg, max_prompt_tokens=prompt, max_new_tokens=16, eos_token_ids=[-1],
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
    """Prefill in segments of 16 tokens (two pages of 8) scored a page of keys
    at a time, so that prompts of 37 and 57 tokens cross what the cell's
    512-2,048-token prompts cross: a segment's first two tokens read the tail
    the segment before left, a last segment that is part padding ends on NO
    multiple of the convolutions' reach (37 = 2 x 16 + 5, 57 = 3 x 16 + 9), the
    shorter row rides two segments past its end, and a prompt's partial last
    page is copied beside its tail."""
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS_TOP1", 8)  # decode rows dense, segments grouped


def worst_difference(params, lora, ids, mask, result, cfg=CFG):
    worst = 0.0
    for b in range(ids.shape[0]):
        prompt = ids[b][mask[b] > 0]
        rows = np.stack([np.concatenate([prompt, result.tokens[b, j]])
                         for j in range(result.tokens.shape[1])])
        want = reference_logprobs(params, lora, rows, np.ones_like(rows), cfg)
        worst = max(worst, np.abs(result.logprobs[b] - want[:, len(prompt) - 1:]).max())
    return worst


def generate(engine, params, lora, lengths=(37, 57), width=64, n=4):
    ids, mask = prompts(lengths, width)
    result = engine.generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=n, max_tokens=16),
        jax.random.PRNGKey(3))
    return ids, mask, result


@pytest.mark.parametrize("scheduler,slots", [
    ("refill", 4),  # 8 rows through 4 slots: a freed slot takes another prompt's tail
    ("waves", 0),   # prefill, fan-out, lockstep
])
def test_generate_equals_the_reference_token_by_token(weights, scheduler, slots,
                                                      small_pieces):
    """Both schedulers hold a model whose every layer keeps pages AND a tail:
    prefill in segments, each prompt's tail COPIED and its page chain aliased to
    its 4 candidates beside a copy of its partial last page, then one token a
    step from the slots' tails over their pages. The engine's own captured
    log-probability of every token it sampled is the reference's full forward's;
    the gauge is what the slots' tails hold; the counters are the experts'."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = make_engine(scheduler, slots)
    ids, mask, result = generate(engine, params, lora)
    assert (result.lengths == 16).all() and result.alive_slot_steps == 8 * 16
    assert worst_difference(params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()
    pairs = after["counters"]["engine/moe_assignments"] - before.get(
        "engine/moe_assignments", 0)
    assert pairs == 3 * 8 * 16  # layers x rows x steps: one expert a token
    held = (slots or 8) * 3 * TAIL_BYTES
    assert after["gauges"]["engine/slot_state_bytes"] == held
    assert engine.last_round_stats["slot_state_bytes"] == held
    assert cca_moe_counts.slot_state_bytes(dataclasses.asdict(CFG), kv_bytes=4) == (
        3 * TAIL_BYTES)


ENGINE_CONTROLS = ["tail_not_handed", "tail_from_other_prompt", "tail_at_the_segments_end",
                   "bf16_tail"]


@pytest.mark.parametrize("control", ENGINE_CONTROLS)
def test_this_files_agreement_can_tell_a_wrong_tail(weights, small_pieces, control,
                                                    monkeypatch):
    """What only the cache path can get wrong: a tail the candidates are not
    handed, one handed from the other prompt, one taken at the SEGMENT's last
    token where the row's prompt ended before it, one kept in bf16."""
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    if control == "tail_at_the_segments_end":
        late = hybrid._shifted
        monkeypatch.setattr(hybrid, "_shifted", lambda x, before, valid: late(x, before, None))
    elif control == "bf16_tail":
        mix = hybrid._cca_mix
        monkeypatch.setattr(hybrid, "_cca_mix", lambda x, p, lora, cache, **kw: mix(
            x, p, lora, None if cache is None else (
                *cache[:2], jax.lax.reduce_precision(cache[2], 8, 7)), **kw))
    else:
        change = {"tail_not_handed": jnp.zeros_like,
                  "tail_from_other_prompt": lambda x: jnp.roll(x, 1, axis=0)}[control]
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, {
                **mixer, "cca_tail": tuple(map(change, mixer["cca_tail"]))}
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    ids, mask, result = generate(make_engine("waves", 0), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 5e-4


@pytest.mark.parametrize("control", ["shift_dropped", "second_convolution_dropped",
                                     "r_not_carried"])
def test_the_engines_agreement_can_tell_the_mechanisms_too(weights, small_pieces, control,
                                                           monkeypatch):
    """Controls of the chip's check that bend the mixer or the router, through
    segments, fan-out and the decode steps."""
    params, lora = weights
    cfg = _control(monkeypatch, control)
    ids, mask, result = generate(make_engine("waves", 0, cfg), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 2e-3


def test_the_fan_out_hands_the_tail_and_the_pages(weights, small_pieces):
    """Greedy, 8 candidates of one prompt are 8 times the single row; and a
    prompt that ends ON a page (40 = 5 pages of 8) needs no partial page."""
    params, lora = weights
    greedy = dict(temperature=0.0, top_p=1.0, max_tokens=12)
    ids, mask = prompts((45,))
    many = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=8, **greedy), jax.random.PRNGKey(3))
    one = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=1, **greedy), jax.random.PRNGKey(3))
    assert (many.tokens[0] == one.tokens[0, 0]).all()
    np.testing.assert_allclose(many.logprobs[0], np.broadcast_to(
        one.logprobs[0, 0], many.logprobs[0].shape), atol=2e-6)
    assert worst_difference(params, lora, ids, mask, one) < 2e-5
    ids, mask, whole = generate(make_engine("waves", 0), params, lora, lengths=(40,), n=8)
    assert worst_difference(params, lora, ids, mask, whole) < 2e-5
