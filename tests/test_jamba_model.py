"""A state-space model (AI21-Jamba2-3B, ``jamba``) against its plain reference,
``perfbench/reference_jamba.py`` (a token-by-token scan from a zero state, full
causal attention: no chunk, no window, no cache), at a small size on the CPU:
the ``tiny-jamba`` preset (hidden 32, four layers of one period's kinds with
attention at 1, 4 query heads over ONE KV head of 16, a state of 16 x 64 a
Mamba layer, a tied head). Float32 throughout, seeded weights with every term
alive.

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_jamba.py``, the ops by
``tests/test_selective_scan.py``.
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
from distrl_llm_tpu.models import hybrid, transformer  # noqa: E402
from distrl_llm_tpu.models.configs import PRESETS  # noqa: E402
from distrl_llm_tpu.ops import selective_scan  # noqa: E402
from perfbench import reference_jamba as ref  # noqa: E402

CFG = PRESETS["tiny-jamba"]
LORA_SCALE = 2.0
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs", "jamba2-3b.json")
#: bytes of one slot's state and window in one Mamba layer (float32 caches here)
STATE_BYTES = 16 * 64 * 4
WINDOW_BYTES = 3 * 64 * 4


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(cfg, rank=4):
    """Seeded weights with every term alive: norms off 1, steps between 0.001
    and 0.1 that move with the token, A over -1..-16, a skip off 1, biases and
    an adapter's b that are not zero."""
    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        if name.endswith("norm"):
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "b_dt":
            return jax.random.uniform(key, x.shape, minval=-6.9, maxval=-2.2)
        if name == "ssm_a_log":
            return jax.random.uniform(key, x.shape, minval=0.0, maxval=2.77)
        if name == "ssm_d":
            return 1.0 + 0.2 * jax.random.normal(key, x.shape)
        if name == "b_conv":
            return 0.25 * jax.random.normal(key, x.shape)
        if name == "conv":
            return 0.5 * jax.random.normal(key, x.shape)
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
#: and not once a call (a test asks for it a row group at a time)
_reference = jax.jit(
    ref.next_token_logprobs, static_argnums=1, static_argnames=("lora_scale",))


def reference_logprobs(params, lora, ids, mask, cfg=CFG):
    return np.asarray(_reference(
        params, cfg, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
        lora_scale=LORA_SCALE))


def forward_logprobs(params, lora, ids, mask, **kw):
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE, **kw)
    return np.asarray(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], jnp.asarray(ids)[:, 1:, None], -1)[..., 0])


def padded_rows():
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, 40), 1, 256))
    mask = np.ones((3, 40), np.int32)
    mask[0, :7] = 0
    mask[1, 33:] = 0
    return ids, mask, (mask[:, 1:] * mask[:, :-1]) > 0


# --------------------------------------------------- what the program is told


def test_one_periods_kinds_and_what_a_slot_holds():
    assert CFG.layer_kinds == ("mamba", "softmax", "mamba", "mamba")
    assert CFG.layer_runs == (("mamba", 0, 0, 1), ("softmax", 1, 0, 1), ("mamba", 2, 1, 2))
    assert CFG.hybrid and CFG.mamba and not (CFG.latent or CFG.delta_moe or CFG.power)
    assert CFG.model_type == "jamba" and CFG.paged_layers == 1 and CFG.mamba_inner == 64
    assert CFG.mixer_names == "attention, mamba"
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    # the state float32 whatever the cache's type, channels last; the window the cache's
    assert [x.shape for x in state["ssm"]] == [(5, 16, 64)] * 3
    assert {x.dtype for x in state["ssm"]} == {jnp.dtype(jnp.float32)}
    assert [x.shape for x in state["conv"]] == [(5, 3, 64)] * 3
    assert {x.dtype for x in state["conv"]} == {jnp.dtype(jnp.bfloat16)}
    assert state["lin"] == () and state["pooled"] == () and state["ssm_stats"].shape == (1,)
    assert set(hybrid.ROW_STATES) >= {"ssm", "conv", "power", "power_z", "delta", "lin"}
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert "lm_head" not in params  # the tied head
    assert set(params["layers"]) == {"mamba", "softmax"}
    # an attention layer of this family has the dense MLP and no experts
    assert set(params["layers"]["softmax"]) == {
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert params["layers"]["mamba"]["ssm_a_log"].shape == (3, 16, 64)
    np.testing.assert_allclose(
        jnp.exp(params["layers"]["mamba"]["ssm_a_log"][0, :, 0]), np.arange(1, 17), rtol=1e-6)


def test_parameters_and_operations_count_both_kinds():
    d, f, v, e = CFG.hidden_size, CFG.intermediate_size, CFG.vocab_size, CFG.mamba_inner
    mlp = 3 * d * f
    attention = 2 * d * 64 + 2 * d * 16  # q, o; k, v of ONE head
    mamba = d * 2 * e + e * (8 + 32) + 8 * e + e * d  # W_in, W_x, W_dt, W_out
    assert CFG.matmul_param_count == CFG.total_matmul_param_count == (
        attention + mlp + 3 * (mamba + mlp) + d * v)
    # the attention layer attends over the context; a Mamba layer's token costs its state
    assert CFG.decode_flops_per_token(100.0) == (
        2.0 * CFG.matmul_param_count + 4.0 * 64 * 100.0 + 7.0 * 3 * 64 * 16)
    assert CFG.train_flops_per_token(200) == 3.0 * CFG.decode_flops_per_token(100.0)


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == [] and "share" not in file
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    kinds = cfg.layer_kinds
    assert len(kinds) == 28 and [i for i, k in enumerate(kinds) if k == "softmax"] == [7, 21]
    assert set(kinds) == {"softmax", "mamba"} and cfg.paged_layers == 2
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2560, 20, 1, 128)
    assert (cfg.intermediate_size, cfg.vocab_size) == (8192, 65536)
    assert (cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank) == (
        5120, 16, 4, 160)
    assert cfg.tie_word_embeddings and not cfg.attn_use_rope and not cfg.attention_bias
    assert cfg.rms_norm_eps == 1e-6 and cfg.sliding_window is None
    assert cfg.n_routed_experts == 0 and cfg.model_type == "jamba"
    # the catalog row's keys, every one as published
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
        "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
        "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k: file[k] for k in published} == published
    for key in ("layer_order", "head_dim", "inner_norms", "split_orders", "dt", "conv",
                "state", "padding", "adapter_targets", "frozen", "unread_keys", "weights"):
        assert key in file["assumed"], key
    # "auto" is the family's ceil(hidden / 16)
    auto = ModelConfig.from_hf_config(SimpleNamespace(**{**file, "mamba_dt_rank": "auto"}))
    assert auto.mamba_dt_rank == 160


@pytest.mark.parametrize("changes,named", [
    ({"num_experts": 16, "num_experts_per_tok": 2}, "num_experts=16"),
    ({"mamba_n_heads": 128}, "mamba_n_heads"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"attn_layer_period": None}, "attn_layer_period"),
    ({"model_type": "jamba2"}, "jamba2"),
])
def test_from_hf_config_refuses_what_it_cannot_represent(changes, named):
    file = {**json.load(open(CONFIG_FILE)), **changes}
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(SimpleNamespace(**file))


def test_the_loader_refuses_a_checkpoint_by_name_in_both_directions(weights):
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    with pytest.raises(NotImplementedError, match="jamba.*seeded weights"):
        params_from_state_dict({}, CFG)
    with pytest.raises(NotImplementedError, match="jamba.*seeded weights"):
        state_dict_from_params(weights[0], CFG)


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("chunk,remat", [(0, False), (16, True), (7, False)])
def test_forward_equals_the_reference_with_padding_on_both_sides(weights, chunk, remat,
                                                                 monkeypatch):
    """``full`` mode (the learner's and the scorer's): left- and right-padded
    rows packed; one chunk, or chunks that carry the state between them under
    remat as the learner runs it, or chunks that do not divide the row."""
    params, lora = weights
    ids, mask, both = padded_rows()
    if chunk:
        monkeypatch.setattr(selective_scan, "DEFAULT_CHUNK", chunk)
    want = reference_logprobs(params, lora, ids, mask)
    got = forward_logprobs(params, lora, ids, mask, remat=remat)
    assert np.abs(got - want)[both].max() < 2e-5
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE)
    whole = np.asarray(ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask),
                                       lora=lora, lora_scale=LORA_SCALE))
    assert np.abs(np.asarray(logits) - whole)[mask > 0].max() < 2e-5


def _with_params(monkeypatch, change):
    """``_mamba_mix`` reading a layer whose leaves ``change`` bent."""
    mix = hybrid._mamba_mix
    monkeypatch.setattr(hybrid, "_mamba_mix", lambda x, p, *a, **kw: mix(
        x, {**p, **change(p)}, *a, **kw))


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


def _control(monkeypatch, name):
    """Bend the PROGRAM in one place (never the reference)."""
    zero = lambda leaf: (lambda p: {leaf: jnp.zeros_like(p[leaf])})
    if name == "no_inner_norms":
        norm = hybrid.rms_norm
        monkeypatch.setattr(hybrid, "rms_norm", lambda x, w, eps, **kw: (
            x if w.shape[-1] < CFG.hidden_size else norm(x, w, eps, **kw)))
    elif name in ("no_b_conv", "no_b_dt", "no_d_skip"):
        _with_params(monkeypatch, zero({"no_b_conv": "b_conv", "no_b_dt": "b_dt",
                                        "no_d_skip": "ssm_d"}[name]))
    elif name == "no_gate":
        monkeypatch.setattr(selective_scan, "gate", lambda y, z: y)
    elif name == "a_log_as_a":  # A = -A_log where it is -exp(A_log)
        _with_params(monkeypatch, lambda p: {
            "ssm_a_log": jnp.log(jnp.maximum(p["ssm_a_log"].astype(jnp.float32), 1e-30))})
    elif name == "u_z_swapped":
        _with_proj(monkeypatch, "_mamba_mix", lambda key, y, env, mode: (
            jnp.roll(y, y.shape[-1] // 2, axis=-1) if key == "w_in" else y))
    elif name == "rope_in_attention":
        def rotate(key, y, env, mode):
            if key not in ("wq", "wk"):
                return y
            pos = env["lengths"][:, None] if mode == "decode" else env["q_pos"]
            cos, sin = transformer.rope_cos_sin(pos, CFG.head_dim, 10000.0)
            b, s, wide = y.shape
            return transformer.apply_rope(
                y.reshape(b, s, -1, CFG.head_dim), cos, sin).reshape(b, s, wide)
        _with_proj(monkeypatch, "_softmax_mix", rotate)
    elif name == "group_as_two_halves":  # the one KV head's group read as 2 + 2, swapped
        _with_proj(monkeypatch, "_softmax_mix", lambda key, y, env, mode: (
            jnp.roll(y, y.shape[-1] // 2, axis=-1) if key == "wq" else y))
    elif name in ("state_3_bits", "bf16_state"):  # the state rounded before every step
        step, bits = hybrid.ssm_step, 3 if name == "state_3_bits" else 7
        monkeypatch.setattr(hybrid, "ssm_step", lambda *a: step(
            *a[:6], jax.lax.reduce_precision(a[6], 8, bits), *a[7:]))
    else:
        raise AssertionError(name)


FORWARD_CONTROLS = ["no_inner_norms", "no_b_conv", "no_b_dt", "no_d_skip", "no_gate",
                    "a_log_as_a", "u_z_swapped", "rope_in_attention", "group_as_two_halves"]


@pytest.mark.parametrize("control", FORWARD_CONTROLS)
def test_the_forward_can_tell_each_mechanism(weights, control, monkeypatch):
    """Each mechanism dropped or bent moves the log-probabilities a hundred
    times further from the reference than the sound program's 2e-5."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    _control(monkeypatch, control)
    assert np.abs(forward_logprobs(params, lora, ids, mask) - want)[both].max() > 2e-3


def test_the_learners_loss_and_adapter_gradient_are_the_references(weights, monkeypatch):
    """No cache, remat, chunked cross-entropy, reverse mode through the
    rematerialised chunk scan across two chunks: the policy-gradient loss over
    the answers and its gradient in every adapter factor against plain reverse
    mode through the reference's token-by-token scan."""
    from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

    params, lora = weights
    monkeypatch.setattr(selective_scan, "DEFAULT_CHUNK", 16)
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
    assert len(leaves) == 2 * (7 + 5)  # a and b: seven targets in attention, five in Mamba
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-6,
                                   err_msg=str(path))


def test_a_train_step_moves_the_adapter_and_nothing_else(weights):
    """The learner's own update on this model: a finite loss, every adapter
    factor moved, and the targets a Mamba layer has."""
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
    assert set(new_lora["layers"]["mamba"]) == {"w_in", "w_out", "w_gate", "w_up", "w_down"}
    assert set(new_lora["layers"]["softmax"]) == {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}


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
    """Prefill in segments of 16 tokens (two pages of 8) and the attention
    layer's segment a page of keys at a time, so that 40-57-token prompts cross
    every boundary the cell's 2k-token prompts cross: the state and the window
    carried from segment to segment, the attention layer over earlier segments'
    pages, a last segment that is part padding."""
    from distrl_llm_tpu.engine import paged_engine

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)


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
    """Both schedulers hold a model with 3 Mamba layers and 1 attention layer:
    prefill in segments (the scan from the carried state, the convolution from
    the carried window, the attention layer over earlier segments' pages), each
    prompt's states, windows and page chain handed to its 4 candidates, then
    the one-token step through the slots' state. The engine's own captured
    log-probability of every token it sampled is the reference's full
    forward's; the counter x a state's bytes is what ``ssm_counts`` says the
    same rows must move, and the gauge what the slots' states and windows hold."""
    from distrl_llm_tpu import telemetry
    from perfbench import ssm_counts

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"]
    engine = make_engine(scheduler, slots)
    ids, mask, result = generate(engine, params, lora)
    assert (result.lengths == 24).all()
    assert result.alive_slot_steps == 8 * 24
    assert worst_difference(params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()
    stepped = after["counters"]["engine/ssm_states_stepped"] - before.get(
        "engine/ssm_states_stepped", 0)
    assert stepped == 3 * 8 * 24  # Mamba layers x rows x steps
    model = dataclasses.asdict(CFG)
    assert ssm_counts.state_bytes(model) == STATE_BYTES
    assert 2 * stepped * STATE_BYTES == ssm_counts.ssm_state_bytes(
        model, [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    held = (slots or 8) * 3 * (STATE_BYTES + WINDOW_BYTES)
    assert after["gauges"]["engine/slot_state_bytes"] == held
    assert engine.last_round_stats["slot_state_bytes"] == held


ENGINE_CONTROLS = {
    "bf16_state": None,
    "state_3_bits": None,
    "window_not_handed": lambda m: {
        **m, "conv": tuple(jnp.zeros_like(x) for x in m["conv"])},
    "state_not_handed": lambda m: {
        **m, "ssm": tuple(jnp.zeros_like(x) for x in m["ssm"])},
    "state_from_other_prompt": lambda m: {
        **m, "ssm": tuple(jnp.roll(x, 1, axis=0) for x in m["ssm"])},
}


@pytest.mark.parametrize("control", sorted(ENGINE_CONTROLS))
def test_this_files_agreement_can_tell_a_wrong_state(weights, small_pieces, control,
                                                     monkeypatch):
    """What only the cache path can get wrong: a state kept in bf16 or at 3
    bits of mantissa, a window or a state that the candidates are not handed,
    a state handed from the other prompt."""
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


@pytest.mark.parametrize("control", ["rope_in_attention", "group_as_two_halves", "no_b_conv",
                                     "no_inner_norms"])
def test_the_engines_agreement_can_tell_the_mechanisms_too(weights, small_pieces, control,
                                                           monkeypatch):
    """The controls of the chip's check that bend a mixer, through segments,
    fan-out and the decode steps (the paged kernel at one KV head)."""
    params, lora = weights
    _control(monkeypatch, control)
    ids, mask, result = generate(make_engine("waves", 0), params, lora)
    assert worst_difference(params, lora, ids, mask, result) > 2e-3


def test_the_fan_out_hands_the_state_the_window_and_the_pages(weights, small_pieces):
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


def test_the_prompts_state_is_the_scans_after_its_last_real_token(weights, small_pieces):
    """What the prefill returns for the fan-out: a state and a window a Mamba
    layer a prompt (the state float32, neither zero, the window the prompt's
    last three tokens' u), and pages for the one attention layer only."""
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    ids, mask = prompts((40, 57))
    k, v, logits, real_len, mixer = paged_engine._paged_prefill_hybrid(
        params, lora, jnp.asarray(ids), jnp.asarray(mask), cfg=CFG, prompt_pages=8,
        page_size=8, lora_scale=LORA_SCALE, cache_dtype=jnp.float32,
        attn_impl="reference", total_tokens=88)
    assert len(k) == len(v) == 1 and k[0].shape == (1, 16, 8, 16)
    assert list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["ssm"]] == [(2, 16, 64)] * 3
    assert [x.shape for x in mixer["conv"]] == [(2, 3, 64)] * 3
    assert all(float(jnp.abs(x).max()) > 0 for x in mixer["ssm"] + mixer["conv"])
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=LORA_SCALE)[:, -1]
    np.testing.assert_allclose(logits, want, atol=2e-5)
    # the first Mamba layer's window is W_in's u of the last three real tokens
    layer = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["mamba"])
    x = jnp.take(params["embed"], jnp.asarray(ids[:, -3:]), axis=0)
    h = transformer.rms_norm(x, layer["attn_norm"], CFG.rms_norm_eps)
    ab = jax.tree_util.tree_map(lambda w: w[0], lora["layers"]["mamba"]["w_in"])
    u = (h @ layer["w_in"] + LORA_SCALE * (h @ ab["a"]) @ ab["b"])[..., :64]
    np.testing.assert_allclose(mixer["conv"][0], u, atol=2e-5)


def test_the_rounds_span_and_trace_reports_line_say_what_was_stepped(weights, tmp_path):
    """With tracing on the round's span carries the gauge and the counter, and
    ``tools/trace_report.py`` prints them on the round's host line."""
    from distrl_llm_tpu import telemetry
    from tools import trace_report

    params, lora = weights
    engine = make_engine("waves", 0)
    generate(engine, params, lora)  # warm-up: no compile/ span in the traced round
    telemetry.configure(True)
    try:
        telemetry.export_chrome_trace(str(tmp_path / "before.json"), clear=True)  # others' spans
        generate(engine, params, lora)
        path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"), clear=True)
    finally:
        telemetry.configure(False)
    events, metadata = trace_report.load_trace(path)
    (span,) = [e for e in events if e.get("name") == telemetry.ENGINE_DECODE]
    assert span["args"]["slot_state_bytes"] == 8 * 3 * (STATE_BYTES + WINDOW_BYTES)
    assert span["args"]["ssm_states_stepped"] == 3 * 8 * 24
    lines = trace_report.build_report(events, metadata).splitlines()
    (said,) = [line for line in lines if line.startswith("    host s:")]
    assert said.endswith("; slot state 0.000 GB, 576 states stepped")


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
def test_what_holds_k_and_v_of_one_kind_names_the_state_it_cannot_hold(build, what):
    """One sentence for every engine and feature that keeps K/V of one kind:
    it names the layers and the state a slot holds for them."""
    with pytest.raises(ValueError) as e:
        build()
    said = str(e.value)
    assert what in said and "attention, mamba layers" in said
    assert "a float32 state-space state and a convolution window" in said
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


def test_a_page_costs_its_two_paged_layers_and_a_slot_its_states():
    """The pool is sized by what the states leave: a page is K and V of ONE
    head in the attention layers alone, a slot's state and window come off the
    budget first, for the decode slots and for the prompts' own."""
    from distrl_llm_tpu.engine import budget

    assert budget.page_bytes(CFG, 8) == 1 * 8 * 16 * 2 * 2 * 1  # one head, bf16, K and V, 1 layer
    # float32 state and a bf16 window, three Mamba layers
    slot = 3 * (STATE_BYTES + 3 * 64 * 2)
    assert budget.slot_state_bytes(CFG, 88) == slot
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**8)
    assert budget.kv_pool_pages(CFG, slots=8, **common) == (
        int(10**8 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6
            - 2 * 8 * budget.page_bytes(CFG, 8) - 10 * slot) // budget.page_bytes(CFG, 8))
    # the published widths: 9.32 MB a slot, 1 KB of K/V a token
    full = ModelConfig.from_hf_config(SimpleNamespace(**json.load(open(CONFIG_FILE))))
    assert budget.slot_state_bytes(full, 2432) == 26 * (327_680 + 3 * 5120 * 2)
    assert budget.page_bytes(full, 128) == 128 * 1024


# ----------------------------------------------------- adapters and placement


def test_adapter_factors_are_each_kinds_own_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS, MAMBA_TARGETS, merge_lora

    params, lora = weights
    assert set(lora["layers"]) == {"softmax", "mamba"}
    assert set(lora["layers"]["softmax"]) == set(DEFAULT_TARGETS)
    stack = lora["layers"]["mamba"]
    assert set(stack) == set(MAMBA_TARGETS)  # none on W_x, W_dt, the convolution, A_log, D
    assert stack["w_in"]["b"].shape == (3, 4, 128) and stack["w_out"]["a"].shape == (3, 64, 4)
    assert lora["layers"]["softmax"]["wk"]["b"].shape[-1] == 16
    # targets named by the caller go to the layers that have them
    named = init_lora_params(jax.random.PRNGKey(0), CFG, 4, targets=("wq", "w_in", "w_up"))
    assert set(named["layers"]["softmax"]) == {"wq", "w_up"}
    assert set(named["layers"]["mamba"]) == {"w_in", "w_up"}
    merged = merge_lora(params, lora, alpha=8.0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 1, 256)
    a, _ = forward(merged, CFG, ids)
    b, _ = forward(params, CFG, ids, lora=lora, lora_scale=2.0)
    np.testing.assert_allclose(a, b, atol=2e-4)


def test_every_new_leaf_has_a_partition_spec(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    specs = param_specs(params)["layers"]["mamba"]
    for name in ("conv", "w_x", "w_dt", "ssm_a_log", "ssm_d", "ssm_dt_norm", "ssm_b_norm",
                 "ssm_c_norm"):
        leaf = params["layers"]["mamba"][name]
        assert specs[name] == P(*([None] * leaf.ndim)), name
    assert specs["w_in"] == P(None, "fsdp", "tp") and specs["w_out"] == P(None, "tp", "fsdp")
    assert param_specs(lora)["layers"]["mamba"]["w_out"]["a"] == P(None, "tp", None)


def test_a_slices_scope_is_the_block_that_reads_it():
    """``_slice_layer`` names a Mamba layer's leaves under the scope that reads
    them, and a delta-rule layer's A_log keeps its own."""
    from distrl_llm_tpu import telemetry

    scopes = transformer._SLICE_SCOPES
    assert scopes["ssm_a_log"] == scopes["ssm_d"] == scopes["b_dt"] == telemetry.MODEL_SSM
    assert scopes["b_conv"] == scopes["conv"] == telemetry.MODEL_SHORT_CONV
    assert scopes["A_log"] == telemetry.MODEL_DELTA_ATTN
    assert telemetry.MODEL_SSM in telemetry.SCOPE_NAMES
