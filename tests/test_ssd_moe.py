"""A state-space expert model of ONE sublayer a layer
(NVIDIA-Nemotron-3-Nano-30B-A3B, ``nemotron_h``) against its plain reference,
``perfbench/reference_ssd_moe.py`` (the Mamba-2 recurrence token by token from a
zero state, full causal attention, the experts one at a time: no chunk, no
carried state, no tail, no cache), at a small size on the CPU: the
``tiny-nemotron-h`` preset (hidden 64, six layers ``M E M * E M``, 4 heads of
state 8 x 16 in two groups, 4 query heads over 2 KV heads of 16, 4 of 8
ungated relu^2 experts a chip of 2, 3 a token, a shared expert twice an
expert's width). Float32 throughout, seeded weights with every term alive.

This file holds the family's record, the ops' own cases (``ops/ssd.py``: the
chunked form against the token-by-token form), the configuration file against
the catalog's row and the share test of the guide's section 4; the cases every
family repeats are ``tests/test_family_conformance.py``'s. The rollout through
``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_ssd_moe.py``.
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
from distrl_llm_tpu.models import ModelConfig, init_params, moe, transformer
from distrl_llm_tpu.models import hybrid
from distrl_llm_tpu.models.configs import PRESETS
from distrl_llm_tpu.ops import ssd
from perfbench import reference_ssd_moe as ref

CFG = PRESETS["tiny-nemotron-h"]
#: bytes of one slot's state and tail in one Mamba-2 layer (float32 caches here)
STATE_BYTES = 4 * 8 * 16 * 4
TAIL_BYTES = 3 * (32 + 2 * 2 * 16) * 4


def _with_params(monkeypatch, change):
    """``_ssd_mix`` reading a layer whose leaves ``change`` bent."""
    mix = hybrid._ssd_mix
    monkeypatch.setattr(hybrid, "_ssd_mix", lambda x, p, *a, **kw: mix(
        x, {**p, **change(p)}, *a, **kw))


def _recurrence(monkeypatch, change):
    """Both forms of the recurrence handed ``change(x, dt, b, c, a, d)``'s arguments."""
    step, chunked = hybrid.ssd_step, hybrid.ssd_chunked
    monkeypatch.setattr(hybrid, "ssd_step", lambda *a: step(*change(*a[:6]), *a[6:]))
    monkeypatch.setattr(hybrid, "ssd_chunked", lambda *a, **kw: chunked(
        *change(*a[:6]), *a[6:], **kw))


def _control(name, monkeypatch):
    """The chip's controls (the traffic file's ``basis``), made the same way:
    the PROGRAM is bent in one place, never the reference."""
    zero = lambda leaf: (lambda p: {leaf: jnp.zeros_like(p[leaf])})
    if name == "no_decay":  # exp(dt A) = 1: a state that never forgets
        _recurrence(monkeypatch, lambda x, dt, b, c, a, d: (x, dt, b, c, a * 0, d))
    elif name == "b_from_wrong_group":
        _recurrence(monkeypatch, lambda x, dt, b, c, a, d: (
            x, dt, jnp.roll(b, 1, axis=-2), c, a, d))
    elif name == "c_from_wrong_group":
        _recurrence(monkeypatch, lambda x, dt, b, c, a, d: (
            x, dt, b, jnp.roll(c, 1, axis=-2), a, d))
    elif name == "gate_after_norm":
        norm = hybrid.gated_group_norm
        monkeypatch.setattr(hybrid, "gated_group_norm", lambda y, z, w, groups, eps: (
            norm(y, jnp.full_like(z, 1e4), w, groups, eps)  # silu(1e4) = 1e4: y normed alone
            * jax.nn.silu(z.astype(jnp.float32))))
    elif name in ("no_b_conv", "no_d_skip"):
        _with_params(monkeypatch, zero({"no_b_conv": "b_conv", "no_d_skip": "ssd_d"}[name]))
    elif name == "z_after_xbc":  # W_in's columns read as [xBC | z | dt]
        fs.with_proj(monkeypatch, "_ssd_mix", lambda key, y, env, mode: (
            jnp.concatenate([jnp.roll(y[..., :-CFG.ssd_heads], CFG.ssd_inner, axis=-1),
                             y[..., -CFG.ssd_heads:]], axis=-1) if key == "w_in" else y))
    elif name == "relu_not_squared":
        relu = lambda x: jax.nn.relu(x)
        monkeypatch.setattr(moe, "relu2", relu)
    elif name == "no_shared":
        monkeypatch.setattr(hybrid, "_mlp_half", lambda x, *a, **kw: x)
    elif name == "a_held_experts_pairs_dropped":  # the first held expert's pairs: not here
        local = moe._local_ids

        def dropped(idx, n, n_experts, held):
            ids = local(idx, n, n_experts, held)
            return jnp.where(ids == 0, n, ids)
        monkeypatch.setattr(moe, "_local_ids", dropped)
    elif name == "held_shifted":
        half = hybrid.moe_half
        monkeypatch.setattr(hybrid, "moe_half", lambda h, p, cfg, held=None, alive=None: half(
            h, p, cfg, held=tuple(i + 1 for i in held), alive=alive))
    elif name == "rope_in_attention":
        fs.rope_in_the_softmax_layers(monkeypatch, CFG.head_dim, 10000.0)
    elif name in ("state_3_bits", "bf16_state"):  # the state rounded before every step
        step, bits = hybrid.ssd_step, 3 if name == "state_3_bits" else 7
        monkeypatch.setattr(hybrid, "ssd_step", lambda *a: step(
            *a[:6], jax.lax.reduce_precision(a[6], 8, bits)))
    else:
        raise AssertionError(name)


def _round_check(moved, result, engine, scheduler, slots):
    """The counter x a state's bytes is what ``ssd_moe_counts`` says the same
    rows must move; the experts' pairs are the share's."""
    from perfbench import ssd_moe_counts

    stepped = moved("engine/ssm_states_stepped")
    assert stepped == 3 * 8 * 24  # Mamba-2 layers x rows x steps
    model = dataclasses.asdict(CFG)
    assert ssd_moe_counts.state_bytes(model) == STATE_BYTES
    assert 2 * stepped * STATE_BYTES == ssd_moe_counts.ssm_state_bytes(
        model, [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    routed = 2 * 8 * 24 * 3  # expert layers x rows x steps x choices
    assert moved("engine/moe_pairs_routed") == routed
    assert 0 < moved("engine/moe_assignments") < routed  # 4 of 8 experts are held


#: the chip's seven (the traffic file's ``basis``; its eighth, the state not handed
#: at the fan-out, is an engine control below) and what only the CPU can tell:
#: the skip dropped, RoPE where the model rotates nothing. ``_control`` knows
#: four more that the chip's script and a builder may call by name
FORWARD_CONTROLS = [
    "no_decay", "b_from_wrong_group", "c_from_wrong_group", "gate_after_norm",
    "relu_not_squared", "no_shared", "a_held_experts_pairs_dropped", "no_d_skip",
    "rope_in_attention"]

FAMILY = fs.Family(
    name="ssd-moe", cfg=CFG, ref=ref, config_file="nemotron-3-nano-ep2-L13.json",
    weight_scale=4.0,  # logits of order 5: float32 rounding stays under the suite's 2e-5
    # steps between 0.001 and 0.1 a head, A over -1..-16, a skip off 1, biases
    # that are not zero, taps of order 1
    seed_rules=(
        (fs.named("dt_bias"), fs.uniform(-6.9, -2.2)),
        (fs.named("A_log"), fs.uniform(0.0, 2.77)),
        (fs.named("ssd_d"), fs.normal(0.2, 1.0)),
        (fs.named("b_conv"), fs.normal(0.25)),
        (fs.named("conv"), fs.normal(0.5))),
    # Eight tokens or fewer take the experts' dense form (a decode step of 8
    # rows), more the grouped one (a prefill segment, the learner's rows).
    pieces=((moe, "expert_form", fs.expert_forms(8)),),
    # Prefill in segments of 16 tokens (two pages of 8) over chunks of 12 (the
    # preset's), so that 40-57-token prompts cross every boundary the cell's
    # prompts cross: a chunk boundary inside a segment, a segment that ends
    # inside a chunk and off the convolution's reach of 3, the state and the tail
    # carried from segment to segment, the attention layer over earlier
    # segments' pages, a last segment that is part padding.
    engine_pieces=((paged_engine, "HYBRID_PREFILL_SEGMENT", 16),),
    refusals=(
        ({"mamba_proj_bias": True}, "mamba_proj_bias"),
        ({"use_bias": True}, "use_bias"),
        ({"attention_bias": True}, "attention_bias"),
        ({"mlp_bias": True}, "mlp_bias"),
        ({"use_conv_bias": False}, "use_conv_bias"),
        ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
        ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
        ({"n_group": 4}, "n_group"),
        ({"topk_group": 2}, "topk_group"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"mamba_num_heads": 60}, "mamba_num_heads"),
        ({"hybrid_override_pattern": "MEMEM*EMEM"}, "places 10 layers"),
        ({"hybrid_override_pattern": "MEMEM*EMEMEM-"}, "dense relu\\^2 MLP"),
        ({"hybrid_override_pattern": "MEMEM*EMEMEMX"}, r"not \['X'\]"),
        ({"hybrid_override_pattern": None}, "hybrid_override_pattern"),
        ({"model_type": "nemotron_h2"}, "nemotron_h2")),
    loader_refusal=("nemotron_h.*seeded weights", "nemotron_h.*seeded weights"),
    # chunks of 12 (the preset's: three and a part over 40 tokens) that carry the
    # state between them, also under remat as the learner runs it (one chunk a
    # row and chunks that do not divide it: the ops' own cases below)
    forward_cases=(
        ("chunks_of_12", False, ()),
        ("chunks_of_12_remat", True, ())),
    forward_full_logits=True,
    forward_controls={name: functools.partial(_control, name) for name in FORWARD_CONTROLS},
    # reverse mode through the rematerialised chunks and the grouped experts with
    # ``held``: a and b of four targets in attention, two in Mamba-2, two in the
    # shared expert
    learner={"answer": 20, "leaves": 2 * (4 + 2 + 2), "atol": 4e-5},
    train_targets={"mamba2": {"w_in", "w_out"}, "softmax_alone": {"wq", "wk", "wv", "wo"},
                   "experts": {"w_up", "w_down"}},
    # 8 rows through 4 slots (a freed slot takes another prompt's state); every
    # candidate admitted at once; prefill, fan-out, lockstep
    rounds=(("refill", 4), ("waves", 0)),
    slot_bytes=3 * (STATE_BYTES + TAIL_BYTES), round_check=_round_check,
    # a state kept at 3 bits of mantissa, a tail or a state that the candidates
    # are not handed
    engine_controls={
        "state_3_bits": functools.partial(_control, "state_3_bits"),
        "state_not_handed": fs.handed_each(("ssm",), jnp.zeros_like),
        "tail_not_handed": fs.handed_each(("conv",), jnp.zeros_like)},
    # through segments, fan-out and the decode steps
    engine_mechanisms=("b_from_wrong_group", "relu_not_squared"),
    fan_out={"scheduler": "waves", "slots": 0, "length": 45, "n": 16, "max_tokens": 12,
             "atol": 1e-5},
    state_refusals=fs.NINE_REFUSALS[:8],
    state_refusal_says=("attention-only, mamba-2, moe layers",
                        "a float32 state a head of its state-space (Mamba-2) layers",
                        "K/V pages for its softmax layers only"),
    span_args={"ssm_states_stepped": 3 * 8 * 24},
    report_tail="; slot state 0.000 GB, 576 states stepped",
)
family, small_pieces, weights = fs.fixtures(FAMILY)
CONFIG_FILE = fs.config_path(FAMILY)


# --------------------------------------------------- what the program is told


def test_the_three_kinds_of_one_sublayer_and_what_a_slot_holds():
    assert CFG.layer_kinds == ("mamba2", "experts", "mamba2", "softmax_alone", "experts",
                               "mamba2")
    assert len(CFG.layer_runs) == 6 and all(run[3] == 1 for run in CFG.layer_runs)
    assert [CFG.layer_ffn(k) for k in CFG.layer_kinds] == [
        "none", "experts", "none", "none", "experts", "none"]
    assert CFG.hybrid and CFG.ssd_moe and not (CFG.mamba or CFG.delta_moe or CFG.latent)
    assert CFG.model_type == "nemotron_h" and CFG.paged_layers == 1
    assert (CFG.ssd_inner, CFG.ssd_conv_dim, CFG.ssd_in_dim) == (32, 96, 132)
    assert CFG.router_width == 8 and CFG.held_experts == (0, 1, 2, 3)
    assert dataclasses.replace(CFG, expert_shard=1).held_experts == (4, 5, 6, 7)
    with pytest.raises(ValueError, match="multiple of"):
        dataclasses.replace(CFG, ssd_groups=3)
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    # the state float32 whatever the cache's type, its columns last; the tail the cache's
    assert [x.shape for x in state["ssm"]] == [(5, 4, 8, 16)] * 3
    assert {x.dtype for x in state["ssm"]} == {jnp.dtype(jnp.float32)}
    assert [x.shape for x in state["conv"]] == [(5, 3 * 96)] * 3  # flat: ops/ssd.py
    assert {x.dtype for x in state["conv"]} == {jnp.dtype(jnp.bfloat16)}
    assert state["ssm_stats"].shape == (1,) and state["moe_routed"].shape == (1,)
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert set(params["layers"]) == {"mamba2", "softmax_alone", "experts"}
    # ONE norm a layer, no second half beside a mixer, no gate in an expert
    assert set(params["layers"]["softmax_alone"]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(params["layers"]["experts"]) == {
        "mlp_norm", "router", "e_score_bias", "experts_up", "experts_down", "w_up", "w_down"}
    assert set(params["layers"]["mamba2"]) == {
        "attn_norm", "w_in", "conv", "b_conv", "A_log", "dt_bias", "ssd_d", "gate_norm",
        "w_out"}
    assert params["layers"]["experts"]["w_up"].shape == (2, 64, 96)


def test_parameters_and_operations_count_the_three_kinds():
    d, f, v = CFG.hidden_size, CFG.moe_intermediate_size, CFG.vocab_size
    mamba = d * 132 + 32 * d
    attention = 2 * d * 64 + 2 * d * 32
    around = 2 * (2 * d * 96 + d * 8) + d * v  # the shared expert, the router, the head
    assert CFG.total_matmul_param_count == 3 * mamba + attention + around + 2 * 4 * 2 * d * f
    assert CFG.matmul_param_count == 3 * mamba + attention + around + 2 * 3 * 2 * d * f
    assert CFG.decode_flops_per_token(100.0) == (
        2.0 * CFG.matmul_param_count + 4.0 * 64 * 100.0 + 6.0 * 3 * 32 * 16)


def test_from_hf_config_reads_the_benchmarks_file_and_the_catalogs_row_is_whole():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert file["share"] == {"chips_per_layer": 2, "published": {
        "n_routed_experts": 128, "vocab_size": 131072}}
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    kinds = cfg.layer_kinds
    assert "".join({"mamba2": "M", "experts": "E", "softmax_alone": "*"}[k] for k in kinds) == (
        "MEMEM*EMEMEM*")
    assert len(cfg.mixer_types) == 52 and cfg.paged_layers == 2
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2688, 32, 2, 128)
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_groups, cfg.mamba_d_state) == (64, 64, 8, 128)
    assert (cfg.ssd_inner, cfg.ssd_conv_dim, cfg.ssd_in_dim, cfg.ssd_chunk) == (
        4096, 6144, 10304, 128)
    assert (cfg.n_routed_experts, cfg.router_width, cfg.experts_per_token) == (64, 128, 6)
    assert (cfg.moe_intermediate_size, cfg.shared_expert_size) == (1856, 3712)
    assert cfg.held_experts == tuple(range(64)) and cfg.routed_scaling_factor == 2.5
    assert cfg.vocab_size == 65536 and not cfg.tie_word_embeddings
    assert cfg.rms_norm_eps == 1e-5 and not cfg.attn_use_rope and cfg.model_type == "nemotron_h"
    # a slot's state: 6 x 2 MiB float32 and 6 tails of 3 x 6,144 bf16
    state = jax.eval_shape(lambda: hybrid.init_mixer_state(cfg, 1, 2560))
    assert [x.shape for x in state["ssm"]] == [(1, 64, 64, 128)] * 6
    assert sum(x.size * x.dtype.itemsize for x in state["ssm"]) == 6 * 2 * 2**20
    # the whole model by the same count: 31.58 B parameters (the row says 31.6 B)
    whole = ModelConfig.from_hf_config(SimpleNamespace(**{
        **file, "num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
        "share": None}))
    assert 31.5e9 < whole.total_matmul_param_count + 2688 * 131072 < 31.65e9
    # the catalog row's keys, every one as published but the three in ``reduced``
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
        "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
        "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    cut = {"num_hidden_layers": 13, "n_routed_experts": 64, "vocab_size": 65536}
    for key, value in published.items():
        assert key in file and file[key] == cut.get(key, value), key
    for key in ("d_inner", "split_orders", "head_to_group", "gate_before_norm",
                "positional_encoding", "unread_keys", "adapter_targets", "frozen", "weights"):
        assert key in file["assumed"], key
    assert (file["reference"], file["counts"], file["weight_rules"]) == (
        "reference_ssd_moe", "ssd_moe_counts", "nemotron_h")


# ---------------------------------------------------------------- the ops


def _inputs(b=3, t=40, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    heads, p, groups, n = 4, 8, 2, 16
    x = jax.random.normal(keys[0], (b, t, heads, p))
    dt = jax.nn.softplus(jax.random.uniform(keys[1], (b, t, heads), minval=-6.0, maxval=0.0))
    bb = jax.random.normal(keys[2], (b, t, groups, n))
    cc = jax.random.normal(keys[3], (b, t, groups, n))
    a = -jnp.exp(jax.random.uniform(keys[4], (heads,), minval=0.0, maxval=2.77))
    d = 1.0 + 0.2 * jax.random.normal(keys[5], (heads,))
    return x, dt, bb, cc, a, d


def _by_token(x, dt, bb, cc, a, d, valid=None):
    """The reference's token-by-token recurrence, a row at a time."""
    rows = [ref.ssd_scan(x[r], dt[r], bb[r], cc[r], a, d,
                         None if valid is None else valid[r] > 0) for r in range(x.shape[0])]
    return jnp.stack([y for y, _ in rows]), jnp.stack([s for _, s in rows])


@pytest.mark.parametrize("chunk", [16, 7, 64], ids=["boundary_inside", "no_divisor", "one_chunk"])
def test_the_chunked_form_is_the_token_by_token_form(chunk):
    """A chunk boundary inside a row (40 tokens over chunks of 16), chunks that
    do not divide it, one chunk; ``valid`` 0 in the middle and at the end is no
    step at all."""
    x, dt, bb, cc, a, d = _inputs()
    valid = np.ones((3, 40), np.int32)
    valid[0, 33:] = 0  # right padding: the state is the one at token 32
    valid[1, 11:19] = 0  # a hole across a chunk boundary
    with jax.default_matmul_precision("highest"):
        want_y, want_s = _by_token(x, dt, bb, cc, a, d, valid)
        got_y, got_s = ssd.ssd_chunked(x, dt, bb, cc, a, d, jnp.asarray(valid), chunk=chunk)
    np.testing.assert_allclose(got_s, want_s, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got_y)[valid > 0], np.asarray(want_y)[valid > 0],
                               atol=3e-5)


def test_a_carried_state_and_the_one_token_step_are_one_recurrence():
    """Segments of 24 and 16 tokens from the carried state, then a token at a
    time, against the whole row at once."""
    x, dt, bb, cc, a, d = _inputs(t=44)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = _by_token(x, dt, bb, cc, a, d)
        y0, s = ssd.ssd_chunked(*(v[:, :24] for v in (x, dt, bb, cc)), a, d, chunk=16)
        y1, s = ssd.ssd_chunked(*(v[:, 24:40] for v in (x, dt, bb, cc)), a, d, state=s, chunk=16)
        steps = []
        for t in range(40, 44):
            y, s = ssd.ssd_step(x[:, t], dt[:, t], bb[:, t], cc[:, t], a, d, s)
            steps.append(y[:, None])
    np.testing.assert_allclose(jnp.concatenate([y0, y1, *steps], 1), want_y, atol=3e-5)
    np.testing.assert_allclose(s, want_s, atol=3e-5)
    assert s.shape == (3, 4, 8, 16) and s.dtype == jnp.float32  # the columns last


def test_the_one_token_convolution_over_a_flat_tail_is_the_convolutions():
    from distrl_llm_tpu.ops.delta_attention import short_conv

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 10))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 10))
    want, _ = short_conv(x, w)
    tail = jnp.zeros((3, 30))
    for t in range(7):
        y, tail = ssd.conv_step(x[:, t], w, tail)
        np.testing.assert_allclose(y, want[:, t], atol=1e-6)
    np.testing.assert_allclose(tail, x[:, 4:].reshape(3, 30), atol=0)


def test_the_gate_comes_before_the_group_norm():
    y, z = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32)), jax.random.normal(
        jax.random.PRNGKey(1), (2, 5, 32))
    w = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    g = (y * jax.nn.silu(z)).reshape(2, 5, 2, 16)
    want = (g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)).reshape(2, 5, 32) * w
    np.testing.assert_allclose(ssd.gated_group_norm(y, z, w, 2, 1e-5), want, atol=1e-6)


# ---------------------------------------------------------------- the share


def test_the_two_shares_add_up_to_the_uncut_layer(weights):
    """The guide's section 4 at the tiny size: the two chips' routed parts of an
    expert layer add up to what the uncut reference gives, the shared expert
    (what every chip computes alike) counted ONCE; and the program's part for a
    share is the reference's, in both forms of the experts."""
    uncut = dataclasses.replace(CFG, n_routed_experts=8, router_experts=0)
    whole, _ = fs.seeded(FAMILY, uncut)
    layer = jax.tree_util.tree_map(lambda w: w[1], whole["layers"]["experts"])
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    shared = ref._relu2(h, layer["w_up"], layer["w_down"])
    want = ref._expert_layer(h, None, layer, None, uncut, 1.0)  # routed + shared, uncut
    np.testing.assert_allclose(want, ref.routed_part(h, layer, uncut) + shared, atol=1e-5)
    total = jnp.zeros_like(want)
    for shard in range(2):
        share = dataclasses.replace(CFG, expert_shard=shard)
        assert ref.held_ids(share) == list(range(4 * shard, 4 * shard + 4)) == list(
            share.held_experts)
        held = {**layer, **{name: layer[name][4 * shard: 4 * shard + 4]
                            for name in ("experts_up", "experts_down")}}
        part = ref.routed_part(h, held, share)
        for form in (fs.expert_forms(0, 8), fs.expert_forms(64)):  # grouped, dense
            with fs.patched(((moe, "expert_form", form),)):
                got, stats = moe.moe_half(h, held, share, held=share.held_experts)
            np.testing.assert_allclose(got, part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert float(jnp.abs(ref.routed_part(h, layer, uncut)).max()) > 0.1


def test_an_ungated_stack_runs_two_matrices_and_a_gated_one_three():
    """``routed_experts`` reads the form off the stack: with a ``gate`` the
    product is the gated SiLU's as it always was, without one relu^2's."""
    h = jax.random.normal(jax.random.PRNGKey(0), (6, 8))
    idx = jnp.asarray([[0], [1], [0], [1], [1], [0]], jnp.int32)
    w = jnp.ones((6, 1), jnp.float32)
    up = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 5))
    down = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 8))
    gate = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 5))
    for form in (fs.expert_forms(0, 8), fs.expert_forms(64)):
        with fs.patched(((moe, "expert_form", form),)), jax.default_matmul_precision("highest"):
            y2, _, _ = moe.routed_experts(h, idx, w, {"up": up, "down": down}, n_experts=2)
            y3, _, _ = moe.routed_experts(
                h, idx, w, {"gate": gate, "up": up, "down": down}, n_experts=2)
            for t in range(6):
                e = int(idx[t, 0])
                np.testing.assert_allclose(
                    y2[t], jnp.square(jax.nn.relu(h[t] @ up[e])) @ down[e], atol=1e-4)
                np.testing.assert_allclose(
                    y3[t], (jax.nn.silu(h[t] @ gate[e]) * (h[t] @ up[e])) @ down[e], atol=1e-4)


# -------------------------------------------------------------- the engine


def test_the_prompts_state_is_the_recurrences_after_its_last_real_token(weights, small_pieces):
    """What the prefill returns for the fan-out: a state and a tail a Mamba-2
    layer a prompt (the state float32, neither zero), pages for the one
    attention layer only, and the last real token's logits."""
    params, lora = weights
    ids, mask, (k, v, logits, real_len, mixer) = fs.prefilled(FAMILY, params, lora)
    assert len(k) == len(v) == 1 and k[0].shape == (2, 16, 8, 16)
    assert list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["ssm"]] == [(2, 4, 8, 16)] * 3
    assert [x.shape for x in mixer["conv"]] == [(2, 3 * 96)] * 3
    assert all(float(jnp.abs(x).max()) > 0 for x in mixer["ssm"] + mixer["conv"])
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=fs.LORA_SCALE)[:, -1]
    np.testing.assert_allclose(logits, want, atol=2e-5)
    # the first layer's state is the recurrence's after the row's last real token
    layer = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["mamba2"])
    ab = jax.tree_util.tree_map(lambda w: w[0], lora["layers"]["mamba2"])
    row = ids[0][mask[0] > 0]
    x = jnp.take(params["embed"], jnp.asarray(row), axis=0)
    h = transformer.rms_norm(x, layer["attn_norm"], CFG.rms_norm_eps)
    zxd = h @ layer["w_in"] + fs.LORA_SCALE * (h @ ab["w_in"]["a"]) @ ab["w_in"]["b"]
    xbc = jax.nn.silu(ref._conv(zxd[:, 32:128], layer["conv"], layer["b_conv"]))
    dt = jax.nn.softplus(zxd[:, 128:] + layer["dt_bias"])
    _, state = ref.ssd_scan(
        xbc[:, :32].reshape(-1, 4, 8), dt, xbc[:, 32:64].reshape(-1, 2, 16),
        xbc[:, 64:].reshape(-1, 2, 16), -jnp.exp(layer["A_log"]), layer["ssd_d"])
    np.testing.assert_allclose(mixer["ssm"][0][0], state, atol=2e-5)
    np.testing.assert_allclose(mixer["conv"][0][0], zxd[-3:, 32:128].reshape(-1), atol=2e-5)
