"""A window model with routed experts (K-EXAONE-236B-A23B, ``exaone_moe``)
against its plain reference, ``perfbench/reference_window_moe.py`` (full causal
scores with the window written as a mask: no ring, no cache, no segment), at a
small size on the CPU: the ``tiny-exaone-moe`` preset (hidden 64, five layers
of the published pattern: window, window, window, full, window; a dense layer 0
before expert layers; 4 query heads over 2 KV heads of 16; a ring of 8 tokens;
2 of 16 experts held, 4 a token, beside one shared expert). Float32 throughout,
seeded weights with every term alive.

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_window_moe.py``.
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
from perfbench import reference_window_moe as ref  # noqa: E402
from perfbench import window_moe_counts  # noqa: E402

CFG = PRESETS["tiny-exaone-moe"]
#: the same model with the published window: contexts on both sides of 128
WIDE = dataclasses.replace(CFG, sliding_window=128)
LORA_SCALE = 2.0
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs", "k-exaone-236b-ep8-L5.json")
#: bytes of one slot's ring in one window layer (float32 caches here): K and V
RING_BYTES = 2 * 2 * 8 * 16 * 4


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(cfg, rank=4):
    """Seeded weights with every term alive: norms off 1 (the q/k norms too), a
    correction bias and an adapter's b that are not zero."""
    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        if name.endswith("norm"):
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
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


@pytest.mark.parametrize("changes,named", [
    ({"layer_types": ["sliding_attention", "chunked_attention"] * 24}, "layer_types"),
    ({"sliding_windows": [128] * 48}, "sliding_windows"),
    ({"first_k_dense_replace": 3}, "first_k_dense_replace"),
    ({"mlp_layer_types": ["dense"] + ["moe"] * 47}, "mlp_layer_types"),
    ({"mlp_layer_types": None}, "mlp_layer_types"),
    ({"rope_parameters": {"rope_theta": 1000000, "rope_type": "yarn"}}, "rope_parameters"),
    ({"n_group": 4}, "n_group"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"model_type": "exaone4"}, "exaone4"),
])
def test_from_hf_config_refuses_what_it_cannot_represent(changes, named):
    file = {**json.load(open(CONFIG_FILE)), **changes}
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(SimpleNamespace(**file))


def test_the_window_refuses_nothing_and_a_dense_models_still_does():
    CFG.check_within_window(10**6)  # a ring, not a limit
    with pytest.raises(ValueError, match="sliding_window"):
        PRESETS["mistral-7b"].check_within_window(5000)
    with pytest.raises(ValueError, match="sliding_window"):
        dataclasses.replace(CFG, sliding_window=None)


def test_the_loader_refuses_a_checkpoint_by_name_in_both_directions(weights):
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    with pytest.raises(NotImplementedError, match="exaone_moe.*seeded weights"):
        params_from_state_dict({}, CFG)
    with pytest.raises(NotImplementedError, match="exaone_moe.*seeded weights"):
        state_dict_from_params(weights[0], CFG)


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("tokens", [100, 128, 129, 3 * 128 + 5])
def test_forward_equals_the_reference_on_both_sides_of_the_window(tokens, side):
    """``full`` mode at the published window of 128: a context inside the
    window, exactly the window, one past it and three windows and five long,
    padded on either side."""
    params, lora = seeded(WIDE)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(tokens), (2, tokens + 9), 1, 256))
    mask = np.ones_like(ids)
    if side == "left":
        mask[0, :9] = 0
    else:
        mask[0, tokens:] = 0
    both = (mask[:, 1:] * mask[:, :-1]) > 0
    want = reference_logprobs(params, lora, ids, mask, WIDE)
    got = forward_logprobs(params, lora, ids, mask, WIDE)
    assert np.abs(got - want)[both].max() < 2e-5


@pytest.mark.parametrize("remat", [False, True])
def test_forward_equals_the_reference_with_the_tests_window(weights, remat):
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    assert np.abs(forward_logprobs(params, lora, ids, mask, remat=remat) - want)[both].max() < 2e-5
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE)
    whole = np.asarray(ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask),
                                       lora=lora, lora_scale=LORA_SCALE))
    assert np.abs(np.asarray(logits) - whole)[mask > 0].max() < 2e-5


def _with_proj(monkeypatch, name, bend):
    """``name`` (a mixer) handed a ``proj`` whose outputs ``bend(key, y, env,
    mode)`` bent."""
    mix = getattr(hybrid, name)

    def run(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
        def bent(h, p_, lora_, key, bias, scale):
            return bend(key, proj(h, p_, lora_, key, bias, scale), env, mode)
        return mix(x, p, lora, cache, cfg=cfg, mode=mode, env=env, proj=bent,
                   lora_scale=lora_scale)
    monkeypatch.setattr(hybrid, name, run)


def _without(monkeypatch, name, *leaves):
    """``name`` (a function of ``hybrid`` that takes a layer's ``p`` second)
    reading a layer without ``leaves``."""
    fn = getattr(hybrid, name)
    monkeypatch.setattr(hybrid, name, lambda x, p, *a, **kw: fn(
        x, {k: v for k, v in p.items() if k not in leaves}, *a, **kw))


def _control(monkeypatch, name, cfg=CFG):
    """Bend the PROGRAM in one place (never the reference). Returns the
    configuration the program is then given."""
    if name in ("window_7", "window_9"):
        return dataclasses.replace(cfg, sliding_window=int(name[-1]))
    if name == "no_window_rope":
        monkeypatch.setattr(hybrid, "apply_rope", lambda x, cos, sin: x)
    elif name == "rope_in_full_layer":
        def rotate(key, y, env, mode):
            if key not in ("wq", "wk"):
                return y
            pos = env["lengths"][:, None] if mode == "decode" else env["q_pos"]
            cos, sin = transformer.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
            b, s, wide = y.shape
            return transformer.apply_rope(
                y.reshape(b, s, -1, cfg.head_dim), cos, sin).reshape(b, s, wide)
        _with_proj(monkeypatch, "_softmax_mix", rotate)
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


FORWARD_CONTROLS = ["window_7", "window_9", "no_window_rope", "rope_in_full_layer",
                    "no_qk_norm", "no_shared_expert", "no_bias", "top_3", "no_scaling",
                    "held_shifted"]


@pytest.mark.parametrize("control", FORWARD_CONTROLS)
def test_the_forward_can_tell_each_mechanism(weights, control, monkeypatch):
    """Each mechanism dropped or bent moves the log-probabilities a hundred
    times further from the reference than the sound program's 2e-5: a window
    one token short or long, RoPE dropped where it belongs or added where it
    does not, the q/k norm, the shared expert, the bias, the count of experts a
    token runs, the scaling factor, which experts are held."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    cfg = _control(monkeypatch, control)
    assert np.abs(forward_logprobs(params, lora, ids, mask, cfg) - want)[both].max() > 2e-3


def test_the_band_is_a_mask_the_kernels_refuse_by_name():
    from distrl_llm_tpu.ops.attention import attention, causal_padding_mask

    band = np.asarray(causal_padding_mask(jnp.ones((1, 6), jnp.int32), 6, window=3))[0, 0]
    want = np.array([[c <= r and r - c < 3 for c in range(6)] for r in range(6)])
    assert (band == want).all()
    q = jnp.ones((1, 6, 2, 8))
    for impl in ("flash", "splash"):
        with pytest.raises(NotImplementedError, match="no band"):
            attention(q, q, q, None, impl=impl, key_valid=jnp.ones((1, 6), jnp.int32), window=3)


def test_the_learners_loss_and_adapter_gradient_are_the_references(weights):
    """No cache, remat, chunked cross-entropy over rows five windows long: the
    policy-gradient loss over the answers and its gradient in every adapter
    factor against plain reverse mode through the reference."""
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
    assert len(leaves) == 2 * 7 * 3  # a and b: seven targets in each of three stacks
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
    for kind in ("window_dense", "window", "softmax"):
        assert set(new_lora["layers"][kind]) == {
            "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}


# --------------------------------------------------------------- the share


def test_eight_shares_sum_to_the_whole_layer_and_eight_slices_to_the_whole_head():
    """The eight chips' routed parts, with the shared expert and the mixer
    counted once, are what the uncut reference gives for the whole layer; the
    program's part for a share is the reference's; and the eight vocabulary
    slices' logits concatenate to the whole head's."""
    uncut = dataclasses.replace(CFG, n_routed_experts=16, router_experts=0)
    whole, _ = seeded(uncut)
    layer = jax.tree_util.tree_map(lambda w: w[1], whole["layers"]["window"])
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    want = ref.routed_part(h, layer, uncut)
    total = jnp.zeros_like(want)
    for shard in range(8):
        share = dataclasses.replace(CFG, expert_shard=shard)
        assert ref.held_ids(share) == [2 * shard, 2 * shard + 1]
        held = {**layer, **{name: layer[name][2 * shard: 2 * shard + 2]
                            for name in ("experts_gate", "experts_up", "experts_down")}}
        part = ref.routed_part(h, held, share)
        got, _ = moe.moe_half(h, held, share, held=share.held_experts)
        np.testing.assert_allclose(got, part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1
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
    """Prefill in segments of 16 tokens (two pages of 8, two windows of 8) and
    the full layer's segment a page of keys at a time, so that 40-57-token
    prompts cross every boundary the cell's 10k-20k-token prompts cross: the
    ring carried from segment to segment, a window that starts in the segment
    before, the full layer over earlier segments' pages, a last segment that
    is part padding."""
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
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
    ("refill", 4),  # 8 rows through 4 slots: a freed slot takes another prompt's rings
    ("refill", 8),  # every candidate admitted at once
    ("waves", 0),   # prefill, fan-out, lockstep
])
def test_generate_equals_the_reference_token_by_token(weights, scheduler, slots,
                                                      small_pieces):
    """Both schedulers hold a model with 4 window layers and 1 full layer:
    prefill in segments whose boundaries fall inside windows, each prompt's
    rings COPIED and its page chain aliased to its 4 candidates, then one token
    a step over the slots' rings. The engine's own captured log-probability of
    every token it sampled is the reference's full forward's; the gauge is what
    the slots' rings hold; the counters are the counts module's."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = make_engine(scheduler, slots)
    ids, mask, result = generate(engine, params, lora)
    assert (result.lengths == 24).all() and result.alive_slot_steps == 8 * 24
    assert worst_difference(params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()
    said = {k: after["counters"][f"engine/window_pages_{k}"]
            - before.get(f"engine/window_pages_{k}", 0) for k in ("attended", "visible")}
    want = window_moe_counts.window_pages(
        dataclasses.asdict(CFG), [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    assert (said["attended"], said["visible"]) == want == (4 * 8 * 24, 4 * 8 * 24)
    routed = after["counters"]["engine/moe_pairs_routed"] - before.get(
        "engine/moe_pairs_routed", 0)
    assert routed == 4 * 4 * 8 * 24  # expert layers x choices x rows x steps: not layer 0
    held = (slots or 8) * 4 * RING_BYTES
    assert after["gauges"]["engine/slot_state_bytes"] == held
    assert engine.last_round_stats["slot_state_bytes"] == held
    assert window_moe_counts.slot_state_bytes(dataclasses.asdict(CFG), kv_bytes=4) == (
        4 * RING_BYTES)


def test_past_the_window_the_counters_say_what_was_spared(monkeypatch):
    """At the published window, prompts of 150 and 260 tokens in segments of
    64: the rings attend one unit of 128 keys a step where a full layer would
    attend two or three, and the engine still equals the reference."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 64)
    params, lora = seeded(WIDE)
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = make_engine("waves", 0, WIDE, prompt=272, page_size=16)
    ids, mask = prompts((150, 260), 272)
    result = engine.generate(
        params, lora, ids, mask, SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=24),
        jax.random.PRNGKey(3))
    assert worst_difference(params, lora, ids, mask, result, WIDE) < 2e-5
    after = telemetry.observe_snapshot()["counters"]
    said = tuple(after[f"engine/window_pages_{k}"] - before.get(f"engine/window_pages_{k}", 0)
                 for k in ("attended", "visible"))
    want = window_moe_counts.window_pages(
        dataclasses.asdict(WIDE), [150, 150, 260, 260], result.lengths.reshape(-1))
    # 150 + j stays under 256: two units; 260 + j is three: 2.5 on average
    assert said == want == (4 * 4 * 24, 4 * 2 * 24 * (2 + 3))
    assert window_moe_counts.window_kv_bytes(
        dataclasses.asdict(WIDE), [150], [24], kv_bytes=4) == 4 * 24 * 128 * 2 * 32 * 4


ENGINE_CONTROLS = {
    "bf16_ring": None,
    "ring_3_bits": None,
    "ring_not_handed": lambda m: {
        **m, **{n: tuple(jnp.zeros_like(x) for x in m[n]) for n in ("win_k", "win_v")}},
    "ring_from_other_prompt": lambda m: {
        **m, **{n: tuple(jnp.roll(x, 1, axis=0) for x in m[n]) for n in ("win_k", "win_v")}},
    "ring_rolled_by_one": lambda m: {
        **m, "win_k": tuple(jnp.roll(x, 1, axis=2) for x in m["win_k"])},
}


@pytest.mark.parametrize("control", sorted(ENGINE_CONTROLS))
def test_this_files_agreement_can_tell_a_wrong_ring(weights, small_pieces, control,
                                                    monkeypatch):
    """What only the cache path can get wrong: a ring kept in bf16 or at 3
    bits of mantissa, a ring the candidates are not handed, a ring handed from
    the other prompt, keys a slot away from their values."""
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    change = ENGINE_CONTROLS[control]
    if change is None:
        mix, bits = hybrid._window_mix, 3 if control == "ring_3_bits" else 7
        monkeypatch.setattr(hybrid, "_window_mix", lambda x, p, lora, cache, **kw: mix(
            x, p, lora, None if cache is None else tuple(
                jax.lax.reduce_precision(c, 8, bits) for c in cache), **kw))
    else:
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, change(mixer)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    ids, mask, result = generate(make_engine("waves", 0), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 5e-4


@pytest.mark.parametrize("control", ["window_7", "window_9", "no_window_rope",
                                     "rope_in_full_layer", "no_qk_norm", "no_shared_expert"])
def test_the_engines_agreement_can_tell_the_mechanisms_too(weights, small_pieces, control,
                                                           monkeypatch):
    """The controls of the chip's check that bend a mixer, through segments,
    fan-out and the decode steps; the chip's check cannot tell a window of 127
    or 129 from 128, this one can tell 7 and 9 from 8."""
    params, lora = weights
    cfg = _control(monkeypatch, control)
    ids, mask, result = generate(make_engine("waves", 0, cfg), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 2e-3


def test_the_fan_out_hands_the_rings_and_the_pages(weights, small_pieces):
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


def _prefilled(params, lora, lengths=(40, 57)):
    from distrl_llm_tpu.engine import paged_engine

    ids, mask = prompts(lengths)
    return ids, mask, paged_engine._paged_prefill_hybrid(
        params, lora, jnp.asarray(ids), jnp.asarray(mask), cfg=CFG, prompt_pages=8,
        page_size=8, lora_scale=LORA_SCALE, cache_dtype=jnp.float32,
        attn_impl="reference", total_tokens=88)


def test_the_prompts_ring_is_its_last_window_rotated_and_in_place(weights, small_pieces):
    """What the prefill returns for the fan-out: a ring a window layer a prompt
    that holds the prompt's last 8 tokens' K (normed, ROTATED) and V, position
    ``p`` at slot ``p % 8``, and pages for the one full layer only."""
    params, lora = weights
    ids, mask, (k, v, logits, real_len, mixer) = _prefilled(params, lora)
    assert len(k) == len(v) == 1 and k[0].shape == (2, 16, 8, 16)
    assert list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["win_k"]] == [(2, 2, 8, 16)] * 4
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=LORA_SCALE)[:, -1]
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
            y = (h @ layer[name] + LORA_SCALE * (h @ ab[name]["a"]) @ ab[name]["b"])
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
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    _, _, (k, v, _, real_len, mixer) = _prefilled(params, lora)
    blank = hybrid.init_mixer_state(CFG, 2, 88, jnp.float32)
    handed = paged_engine._hand_mixer(
        blank, mixer, jnp.asarray([1, 1]), jnp.asarray([True, True]))
    cache = {**handed, "k": k, "v": v, "lengths": jnp.asarray([57, 57], jnp.int32),
             "page_indices": jnp.tile(jnp.arange(8, 16, dtype=jnp.int32)[None], (2, 1)),
             "alive": jnp.asarray([True, True])}
    # the candidates' next pages would be their own; the decode token lands in
    # the prompt's last page here, which only the full layer reads
    _, out = forward(transformer.decode_view(params), CFG, jnp.asarray([[5], [9]]),
                     lora=lora, lora_scale=LORA_SCALE, kv_cache=cache, page_size=8,
                     paged_impl="reference")
    for name in ("win_k", "win_v"):
        for before, after in zip(mixer[name], out[name]):
            a, b = np.asarray(after[0]), np.asarray(after[1])
            assert np.abs(a[:, 57 % 8] - b[:, 57 % 8]).max() > 1e-3  # each wrote its own
            others = [s for s in range(8) if s != 57 % 8]
            np.testing.assert_array_equal(a[:, others], b[:, others])
            np.testing.assert_array_equal(a[:, others], np.asarray(before[1])[:, others])
    assert list(np.asarray(out["window_stats"])) == [4 * 2, 4 * 2]  # 4 layers x 2 rows, a unit


def test_the_rounds_span_and_trace_reports_line_say_what_was_attended(weights, tmp_path):
    """With tracing on the round's span carries the gauge and the counters, and
    ``tools/trace_report.py`` prints them on the round's host line."""
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
    assert span["args"]["slot_state_bytes"] == 8 * 4 * RING_BYTES
    assert span["args"]["window_pages_attended"] == 4 * 8 * 24
    assert span["args"]["window_pages_visible"] == 4 * 8 * 24
    lines = trace_report.build_report(events, metadata).splitlines()
    (said,) = [line for line in lines if line.startswith("    host s:")]
    assert said.endswith("; slot state 0.000 GB, window 768 of 768 x 128 keys")


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
def test_what_holds_k_and_v_of_one_kind_names_the_ring_it_cannot_hold(build, what):
    """One sentence for every engine and feature that keeps K/V of one kind:
    it names the layers and the state a slot holds for them (no new list)."""
    with pytest.raises(ValueError) as e:
        build()
    said = str(e.value)
    assert what in said and "full_attention, sliding_attention layers" in said
    assert "a ring of the last sliding_window tokens' K and V and no page" in said
    assert "K/V pages for its softmax layers only" in said


@pytest.mark.parametrize("switch", ["paged_verify", "paged_chunked", "paged_prefix"])
def test_forward_refuses_the_dense_decoders_other_cache_modes(weights, switch):
    params, _ = weights
    cache = {"k": (), "v": (), "page_indices": jnp.zeros((1, 2), jnp.int32),
             "lengths": jnp.zeros((1,), jnp.int32)}
    with pytest.raises(NotImplementedError, match=switch):
        forward(params, CFG, jnp.ones((1, 1), jnp.int32), kv_cache=cache, page_size=8,
                **{switch: True})


# --------------------------------------------------------------- the budget


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


# ----------------------------------------------------- adapters and placement


def test_adapter_factors_are_each_stacks_own_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS, merge_lora

    params, lora = weights
    assert set(lora["layers"]) == {"window_dense", "window", "softmax"}
    for kind in lora["layers"]:
        assert set(lora["layers"][kind]) == set(DEFAULT_TARGETS)
    # the MLP's three: layer 0's dense MLP, the other layers' shared expert
    assert lora["layers"]["window_dense"]["w_gate"]["b"].shape == (1, 4, 128)
    assert lora["layers"]["window"]["w_gate"]["b"].shape == (3, 4, 32)
    assert lora["layers"]["softmax"]["wk"]["b"].shape == (1, 4, 32)
    merged = merge_lora(params, lora, alpha=8.0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 1, 256)
    a, _ = forward(merged, CFG, ids)
    b, _ = forward(params, CFG, ids, lora=lora, lora_scale=2.0)
    np.testing.assert_allclose(a, b, atol=2e-4)


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
