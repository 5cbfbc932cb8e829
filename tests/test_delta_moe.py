"""A gated delta-rule model with routed experts held as one chip's share
(Solar-Open2-250B, ``solar_open2``) against its plain reference,
``perfbench/reference_delta_moe.py``, at a small size on the CPU: the
``tiny-delta-moe`` preset (hidden 64, a period of four: softmax at 0, delta
rule at 1-3; 2 of 16 experts held, 4 a token, 1 shared). Float32 throughout,
seeded weights with every term alive.

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_delta_moe.py``, the ops by
``tests/test_delta_attention.py``.
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
from perfbench import reference_delta_moe as ref  # noqa: E402

CFG = PRESETS["tiny-delta-moe"]
LORA_SCALE = 2.0
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs", "solar-open2-250b-ep8-L4.json")


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def both_expert_forms(monkeypatch):
    """Eight tokens or fewer take the dense form (a decode step of 8 rows), more
    the grouped one (a prefill segment, the learner's rows)."""
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)


def seeded(cfg, rank=4):
    """Seeded weights with every term alive: norms off 1, a correction bias
    that changes the choice, decays that remember, filters of order 1, an
    adapter whose b is not zero."""
    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        if name.endswith("norm"):
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "e_score_bias":
            return 0.05 * jax.random.normal(key, x.shape)
        if name == "A_log":
            return jax.random.uniform(key, x.shape, minval=-3.0, maxval=0.5)
        if name == "dt_bias":
            return jax.random.normal(key, x.shape)
        if name == "conv":
            return 0.5 * jax.random.normal(key, x.shape)
        return 3.0 * x

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
#: and not once a call (a test asks for it a row group at a time)
_reference = jax.jit(
    ref.next_token_logprobs, static_argnums=1, static_argnames=("lora_scale",))


def reference_logprobs(params, lora, ids, mask, cfg=CFG):
    return np.asarray(_reference(
        params, cfg, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
        lora_scale=LORA_SCALE))


def forward_logprobs(params, lora, ids, mask):
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE)
    return np.asarray(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], jnp.asarray(ids)[:, 1:, None], -1)[..., 0])


def padded_rows():
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, 40), 1, 256))
    mask = np.ones((3, 40), np.int32)
    mask[0, :7] = 0
    mask[1, 33:] = 0
    return ids, mask, (mask[:, 1:] * mask[:, :-1]) > 0


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


@pytest.mark.parametrize("changes,named", [
    ({"use_rope": True}, "use_rope"),
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"n_group": 4}, "n_group"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                             "num_heads": 64, "num_kv_heads": 8}}, "num_kv_heads"),
    ({"gqa_layers": None}, "gqa_layers"),
    ({"model_type": "solar_open3"}, "solar_open3"),
])
def test_from_hf_config_refuses_what_it_cannot_represent(changes, named):
    file = {**json.load(open(CONFIG_FILE)), **changes}
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(SimpleNamespace(**file))


def test_the_loader_refuses_a_checkpoint_by_name(weights):
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    with pytest.raises(NotImplementedError, match="solar_open2.*seeded weights"):
        params_from_state_dict({}, CFG)
    with pytest.raises(NotImplementedError, match="solar_open2"):
        state_dict_from_params(weights[0], CFG)


# ------------------------------------------------------------- the forward


def test_forward_equals_the_reference_with_padding_on_both_sides(weights):
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    assert np.abs(forward_logprobs(params, lora, ids, mask) - want)[both].max() < 2e-5


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


def _control(monkeypatch, name):
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
        from distrl_llm_tpu.models.transformer import apply_rope, rope_cos_sin
        mix = hybrid._softmax_mix

        def roped(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
            b, s, _ = x.shape
            pos = env["lengths"][:, None] if mode == "decode" else env["q_pos"]
            cos, sin = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)

            def proj2(h, p_, lora_, key, bias, scale):
                y = proj(h, p_, lora_, key, bias, scale)
                if key in ("wq", "wk"):
                    y = apply_rope(y.reshape(b, s, -1, cfg.head_dim), cos, sin).reshape(y.shape)
                return y
            return mix(x, p, lora, cache, cfg=cfg, mode=mode, env=env, proj=proj2,
                       lora_scale=lora_scale)
        monkeypatch.setattr(hybrid, "_softmax_mix", roped)
    elif name == "bf16_state":
        _rule(monkeypatch, change_state=lambda st: jax.lax.reduce_precision(st, 8, 7))
    else:
        raise AssertionError(name)


FORWARD_CONTROLS = [
    "beta_not_doubled", "no_decay", "scalar_decay", "no_conv", "no_softmax_gate",
    "no_delta_gate", "one_expert_fewer", "held_shifted", "no_shared", "rope_in_softmax",
]


@pytest.mark.parametrize("control", FORWARD_CONTROLS)
def test_the_forward_can_tell_each_mechanism(weights, control, monkeypatch):
    """Each mechanism dropped or bent moves the log-probabilities a hundred
    times further from the reference than the sound program's 2e-5."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    _control(monkeypatch, control)
    assert np.abs(forward_logprobs(params, lora, ids, mask) - want)[both].max() > 2e-3


# ------------------------------------------------------------- the share


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_the_eight_shares_of_a_layer_sum_to_the_uncut_references(weights, form):
    """One layer's second half run as each of the 8 chips of the deployment
    (its 2 of 16 experts, the router whole): the routed parts summed, with the
    shared expert counted once, are the uncut reference's whole expert layer."""
    from distrl_llm_tpu.models.transformer import _mlp_half, _proj, rms_norm

    whole_cfg = dataclasses.replace(CFG, n_routed_experts=16, router_experts=0)
    params, _ = seeded(whole_cfg)
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


# ------------------------------------------------------------- the learner


def test_the_learners_loss_and_adapter_gradient_are_the_references(weights):
    """No cache, remat, chunked cross-entropy, the chunked rule's own reverse
    mode and the grouped experts with ``held``: the policy-gradient loss over
    the answers and its gradient in every adapter factor against plain reverse
    mode through the reference's token-by-token recurrence. 4e-5 of a leaf's
    largest entry where the latent family's test holds 2e-5: the gradient
    crosses three triangular solves in float32 (one element in 5,000 reads 2.2e-5)."""
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
    assert len(leaves) == 2 * 7 * 2  # a and b of seven targets in two kinds
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=4e-5 * float(jnp.abs(w).max()) + 1e-6,
                                   err_msg=str(path))


# -------------------------------------------------------------- the engine


def make_engine(scheduler, slots, **kw):
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("page_size", 8)
    return PagedGenerationEngine(
        CFG, max_prompt_tokens=64, max_new_tokens=24, eos_token_ids=[-1],
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
    """Prefill in segments of 16 tokens (two pages of 8, scored a page at a
    time), so that 40-57-token prompts cross every boundary the cell's
    2,048-token prompts cross: the state, the tail and the pages carried from
    segment to segment, a last segment that is part padding."""
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
    assert moe.DENSE_MAX_TOKENS == 8  # 8 decode rows dense, 32-token segments grouped


def worst_difference(params, lora, ids, mask, result):
    worst = 0.0
    for b in range(ids.shape[0]):
        prompt = ids[b][mask[b] > 0]
        rows = np.stack([np.concatenate([prompt, result.tokens[b, j]])
                         for j in range(result.tokens.shape[1])])
        want = reference_logprobs(params, lora, rows, np.ones_like(rows))
        worst = max(worst, np.abs(result.logprobs[b] - want[:, len(prompt) - 1:]).max())
    return worst


def generate(engine, params, lora, lengths=(40, 57)):
    ids, mask = prompts(lengths)
    result = engine.generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=24),
        jax.random.PRNGKey(3))
    return ids, mask, result


@pytest.mark.parametrize("scheduler,slots", [
    ("refill", 4),  # 8 rows through 4 slots: a freed slot takes another prompt's state
    ("refill", 8),  # every candidate admitted at once
    ("waves", 0),   # prefill, fan-out, lockstep
])
def test_generate_equals_the_reference_token_by_token(weights, scheduler, slots,
                                                      small_pieces):
    """Prefill in segments (the chunked rule from the carried state, the
    convolution from the carried tail, softmax attention over earlier
    segments' pages), each prompt's state, tail and pages handed to its 4
    candidates, then the one-token rule and the paged kernel's reference
    through the cache: the engine's own captured log-probability of every
    token it sampled is the reference's full forward's."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"]
    ids, mask, result = generate(make_engine(scheduler, slots), params, lora)
    assert (result.lengths == 24).all()
    assert result.alive_slot_steps == 8 * 24
    assert worst_difference(params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()["counters"]
    moved = lambda name: after[name] - before.get(name, 0)
    # 4 expert layers x 8 rows x 24 steps x 4 experts a token, live slots only
    routed = 4 * 8 * 24 * 4
    assert moved("engine/moe_pairs_routed") == routed
    # 2 of 16 experts are held: some of the pairs land here, never all
    assert 0 < moved("engine/moe_assignments") < routed
    assert moved("engine/moe_assignments") / 2 <= moved("engine/moe_max_expert_load") <= (
        moved("engine/moe_assignments"))


ENGINE_CONTROLS = {
    "bf16_state": None,
    "tail_not_handed": lambda m: {**m, "conv": tuple(jnp.zeros_like(x) for x in m["conv"])},
    "state_from_wrong_prompt": lambda m: {
        **m, "delta": tuple(jnp.roll(x, 1, axis=0) for x in m["delta"])},
}


@pytest.mark.parametrize("control", sorted(ENGINE_CONTROLS))
def test_this_files_agreement_can_tell_a_wrong_state(weights, small_pieces, control,
                                                     monkeypatch):
    """What only the cache path can get wrong: a state kept in bf16 (the
    chip's check cannot tell it: the traffic file's ``basis``), a tail or a
    state that the candidates are not handed from their own prompt."""
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    change = ENGINE_CONTROLS[control]
    if change is None:
        _control(monkeypatch, control)
    else:
        prefill = paged_engine._paged_prefill_hybrid

        def patched(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, change(mixer)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched)
    ids, mask, result = generate(make_engine("waves", 0), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 5e-4


def test_sixteen_candidates_equal_sixteen_single_rows(weights, small_pieces):
    """The fan-out hands every candidate its prompt's state and tail: greedy,
    16 candidates of one prompt are 16 times the single row."""
    params, lora = weights
    ids, mask = prompts((45,))
    greedy = dict(temperature=0.0, top_p=1.0, max_tokens=12)
    many = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=16, **greedy), jax.random.PRNGKey(0))
    one = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=1, **greedy), jax.random.PRNGKey(0))
    assert (many.tokens == one.tokens[:, :1]).all()
    np.testing.assert_allclose(many.logprobs, np.repeat(one.logprobs, 16, 1), atol=2e-6)


def test_the_readers_read_the_share_off_the_counters(weights, small_pieces, monkeypatch):
    """``engine.expert_load_imbalance`` is fullest x HELD / pairs here (the
    field named ``n_routed_experts`` is the experts held, not the router's
    width), ``engine.expert_held_share`` pairs here / pairs routed."""
    from distrl_llm_tpu import telemetry
    from perfbench.readers import delta_moe_work, latent_moe_work

    params, lora = weights
    before = dict(telemetry.observe_snapshot()["counters"])
    generate(make_engine("waves", 0), params, lora)
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

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"].get(telemetry.OPS_DELTA_KERNEL_STEPS, 0)
    generate(make_engine(scheduler, slots), params, lora)
    head = CFG.delta_head_dim
    assert delta_attention.dispatch_choices[
        delta_attention.dispatch_key(CFG.delta_heads, head, head)] == "plain"
    after = telemetry.observe_snapshot()["counters"]
    assert after[telemetry.OPS_DELTA_KERNEL_STEPS] == before


@pytest.mark.parametrize("ran,steps,want", [
    ("kernel", 768, 3 * 768), ("plain", 768, 0), (None, 768, 0), ("kernel", 0, None)])
def test_the_counter_is_layers_times_steps_where_the_kernel_ran(monkeypatch, ran, steps, want):
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine
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
    from distrl_llm_tpu.models.configs import PRESETS
    filed.clear()
    paged_engine._record_delta_telemetry(PRESETS["tiny"], 768)
    assert filed == []


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


@pytest.mark.parametrize("build,what", [
    (_dense, "dense engine"),
    (_sharded, "dp-sharded"),
    (_paged(kv_quant="int8"), "kv_quant"),
    (_paged(spec_draft=2), "spec_draft"),
    (_paged(prefix_sharing=True), "prefix_sharing"),
    (_paged(max_kv_pages=64), "max_kv_pages"),
    (_paged(continuous_admission=True, prefix_cache=True), "prefix_sharing"),
])
def test_what_holds_k_and_v_of_one_kind_names_the_state_it_cannot_hold(build, what):
    """The refusal names the STATE KINDS, whatever the model: pages for some
    layers only, a float32 delta-rule state and a convolution tail."""
    with pytest.raises(ValueError) as e:
        build()
    said = str(e.value)
    assert what in said and "gqa, kda layers" in said
    assert "a float32 delta-rule state and a convolution tail" in said
    assert "K/V pages for its softmax layers only" in said


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


@pytest.mark.parametrize("switch", ["paged_verify", "paged_chunked", "paged_prefix"])
def test_forward_refuses_the_dense_decoders_other_cache_modes(weights, switch):
    params, _ = weights
    cache = {"k": (), "v": (), "page_indices": jnp.zeros((1, 2), jnp.int32),
             "lengths": jnp.zeros((1,), jnp.int32)}
    with pytest.raises(NotImplementedError, match=switch):
        forward(params, CFG, jnp.ones((1, 1), jnp.int32), kv_cache=cache, page_size=8,
                **{switch: True})


# ----------------------------------------------------- adapters and placement


def test_adapter_factors_follow_each_kinds_shapes_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS, merge_lora

    params, lora = weights
    assert set(lora["layers"]) == {"softmax", "delta"}
    for kind, kv in (("softmax", 32), ("delta", 64)):
        stack = lora["layers"][kind]
        # no router, no routed expert, no low-rank pair, no beta, no filter
        assert set(stack) == set(DEFAULT_TARGETS)
        assert stack["wk"]["b"].shape[-1] == kv and stack["wq"]["b"].shape[-1] == 64
        assert stack["w_gate"]["b"].shape[-1] == 32  # the shared expert's width
    merged = merge_lora(params, lora, alpha=8.0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 1, 256)
    a, _ = forward(merged, CFG, ids)
    b, _ = forward(params, CFG, ids, lora=lora, lora_scale=2.0)
    np.testing.assert_allclose(a, b, atol=2e-4)


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
