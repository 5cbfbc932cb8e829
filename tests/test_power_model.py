"""A power-retention model (Brumby-14B-Base, ``brumby``) against its plain
reference, ``perfbench/reference_power_retention.py`` (the ATTENTION form: no
state, no chunk, no cache), at a small size on the CPU: the ``tiny-power``
preset (hidden 64, three layers, 10 query heads over 2 KV heads of 16, a state
of 136 x 16 a KV head). Float32 throughout, seeded weights with every term
alive.

The rollout through ``perfbench/run.py`` is held by
``tests/perfbench/test_perfbench_rehearsal_power.py``, the ops by
``tests/test_power_retention.py``.
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
from distrl_llm_tpu.models import hybrid  # noqa: E402
from distrl_llm_tpu.models.configs import PRESETS  # noqa: E402
from distrl_llm_tpu.ops import power_retention  # noqa: E402
from perfbench import reference_power_retention as ref  # noqa: E402

CFG = PRESETS["tiny-power"]
LORA_SCALE = 2.0
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs", "brumby-14b-L4.json")
TRAFFIC_FILE = os.path.join(REPO, "perfbench", "traffic", "rollout-retention-16k.json")
#: bytes of one slot's state in one layer: 2 KV heads x (136 x 16 + 136) float32
STATE_BYTES = 2 * (136 * 16 + 136) * 4


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(cfg, rank=4):
    """Seeded weights with every term alive: norms off 1, a decay that
    remembers (e^g about 0.9-0.999) and moves with the token, an adapter
    whose b is not zero."""
    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        if name.endswith("norm"):
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "b_decay":
            return jax.random.uniform(key, x.shape, minval=2.0, maxval=7.0)
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


def test_every_layer_is_a_retention_layer_and_no_layer_keeps_a_page():
    assert CFG.layer_kinds == ("power",) * 3 and CFG.layer_runs == (("power", 0, 0, 3),)
    assert CFG.hybrid and CFG.power and not CFG.latent and not CFG.delta_moe
    assert CFG.model_type == "brumby" and CFG.paged_layers == 0
    assert CFG.power_state_dim == 136
    state = hybrid.init_mixer_state(CFG, 5, 64, jnp.bfloat16)
    # ONE state a KV head (2), not one a query head (10), float32 whatever the cache's type
    assert [x.shape for x in state["power"]] == [(5, 2, 136, 16)] * 3
    assert [x.shape for x in state["power_z"]] == [(5, 2, 136)] * 3
    assert {x.dtype for x in state["power"] + state["power_z"]} == {jnp.dtype(jnp.float32)}
    assert state["lin"] == () and state["pooled"] == ()
    assert state["power_stats"].shape == (1,)
    assert set(hybrid.ROW_STATES) >= {"power", "power_z", "delta", "conv", "lin", "pooled"}


def test_parameters_and_operations_count_the_state_packed():
    d, f, v = CFG.hidden_size, CFG.intermediate_size, CFG.vocab_size
    layer = 2 * d * 160 + 2 * d * 32 + d * 2 + 3 * d * f  # q, o; k, v; the decay; the MLP
    assert CFG.matmul_param_count == CFG.total_matmul_param_count == 3 * layer + d * v
    # a token costs its state whatever the context: 3 D d a KV head, 2 D d a query head
    state = 3 * 136 * 16 * (3 * 2 + 2 * 10)
    assert CFG.decode_flops_per_token() == CFG.decode_flops_per_token(5000.0) == (
        2.0 * CFG.matmul_param_count + state)


def test_from_hf_config_reads_the_benchmarks_file():
    from perfbench import spec

    file = json.load(open(CONFIG_FILE))
    spec.check_reduced(file, CONFIG_FILE)
    assert file["reduced"] == ["num_hidden_layers"] and "share" not in file
    cfg = ModelConfig.from_hf_config(SimpleNamespace(**file))
    assert cfg.layer_kinds == ("power",) * 4 and cfg.paged_layers == 0
    # every published width, both head counts and the whole vocabulary, unchanged
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (5120, 40, 8, 128)
    assert (cfg.intermediate_size, cfg.vocab_size) == (17408, 151936)
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6 and cfg.qk_norm
    assert not cfg.attention_bias and not cfg.tie_word_embeddings
    assert cfg.sliding_window is None and cfg.max_position_embeddings == 32768
    assert cfg.power_state_dim == 8256
    # the catalog row's keys, as published but for the depth
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: file[k] for k in published if k != "num_hidden_layers"} == {
        k: v for k, v in published.items() if k != "num_hidden_layers"}
    assert file["num_hidden_layers"] == 4
    for key in ("degree", "gate", "normaliser", "scale", "qk_norm", "rope", "state",
                "adapter_targets", "frozen", "weights"):
        assert key in file["assumed"], key
    # the round trip: the config names itself, and the full depth gives 40 such layers
    assert cfg.model_type == "brumby"
    full = ModelConfig.from_hf_config(SimpleNamespace(**{**file, "num_hidden_layers": 40}))
    assert full.kind_count("power") == 40
    # 34.08 MB a layer a slot, 14.77B parameters whole
    assert 8 * (8256 * 128 + 8256) * 4 == 34_080_768
    whole = full.matmul_param_count + full.hidden_size * full.vocab_size
    assert abs(whole - 14.77e9) < 0.01e9


@pytest.mark.parametrize("changes,named", [
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"power_degree": 4}, "power_degree"),
    ({"model_type": "brumby2"}, "brumby2"),
])
def test_from_hf_config_refuses_what_it_cannot_represent(changes, named):
    file = {**json.load(open(CONFIG_FILE)), **changes}
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(SimpleNamespace(**file))


def test_the_loader_refuses_a_checkpoint_by_name_in_both_directions(weights):
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    with pytest.raises(NotImplementedError, match="brumby.*seeded weights"):
        params_from_state_dict({}, CFG)
    with pytest.raises(NotImplementedError, match="brumby.*seeded weights"):
        state_dict_from_params(weights[0], CFG)


# ------------------------------------------------------------- the forward


def test_forward_equals_the_reference_with_padding_on_both_sides(weights):
    """``full`` mode (the learner's and the scorer's): left- and right-padded
    rows packed, one chunk, the attention form inside it."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    got = forward_logprobs(params, lora, ids, mask)
    assert np.abs(got - want)[both].max() < 2e-5
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE)
    whole = np.asarray(ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask),
                                       lora=lora, lora_scale=LORA_SCALE))
    assert np.abs(np.asarray(logits) - whole)[mask > 0].max() < 2e-5


def test_forward_in_chunks_carries_the_state_between_them(weights, monkeypatch):
    """Rows longer than a chunk: the chunked form from its own carried (S, z)
    inside ``full`` mode, under remat as the learner runs it."""
    params, lora = weights
    ids, mask, both = padded_rows()
    want = reference_logprobs(params, lora, ids, mask)
    monkeypatch.setattr(power_retention, "DEFAULT_CHUNK", 16)
    logits, _ = forward(params, CFG, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                        lora=lora, lora_scale=LORA_SCALE, remat=True)
    got = np.asarray(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], jnp.asarray(ids)[:, 1:, None], -1)[..., 0])
    assert np.abs(got - want)[both].max() < 2e-5


def _control(monkeypatch, name):
    """Bend the PROGRAM in one place (never the reference)."""
    if name == "degree_1":
        def phi1(x):
            x = x.astype(jnp.float32)
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [
                (0, power_retention.state_dim(x.shape[-1]) - x.shape[-1])])
        monkeypatch.setattr(power_retention, "phi", phi1)
        monkeypatch.setattr(power_retention, "_weights", lambda s: s)
    elif name == "no_normaliser":
        monkeypatch.setattr(power_retention, "_normalised", lambda num, den, eps: num)
    elif name == "no_gate":
        monkeypatch.setattr(hybrid, "_power_decay", lambda h, p: jnp.zeros(
            h.shape[:-1] + (p["w_decay"].shape[-1],), jnp.float32))
    elif name == "no_gate_bias":
        decay = hybrid._power_decay
        monkeypatch.setattr(hybrid, "_power_decay", lambda h, p: decay(
            h, {**p, "b_decay": jnp.zeros_like(p["b_decay"])}))
    elif name == "no_qk_norm":
        block = hybrid._block
        monkeypatch.setattr(hybrid, "_block", lambda x, p, *a, **kw: block(
            x, {k: v for k, v in p.items() if k not in ("q_norm", "k_norm")}, *a, **kw))
    elif name == "no_rope":
        monkeypatch.setattr(hybrid, "apply_rope", lambda x, cos, sin: x)
    elif name == "neighbour_kv_head":
        def shifted(form):
            def run(q, k, *rest, **kw):
                kv = k.shape[-2]
                split = q.shape[:-2] + (kv, q.shape[-2] // kv, q.shape[-1])
                o, state = form(jnp.roll(q.reshape(split), 1, axis=-3).reshape(q.shape),
                                k, *rest, **kw)
                return jnp.roll(o.reshape(split), -1, axis=-3).reshape(o.shape), state
            return run
        monkeypatch.setattr(hybrid, "power_step", shifted(hybrid.power_step))
        monkeypatch.setattr(hybrid, "power_chunked", shifted(hybrid.power_chunked))
    elif name == "bf16_state":
        step = hybrid.power_step
        monkeypatch.setattr(hybrid, "power_step", lambda q, k, v, g, st, eps=1e-6: step(
            q, k, v, g, (jax.lax.reduce_precision(st[0], 8, 7), st[1]), eps=eps))
    else:
        raise AssertionError(name)


FORWARD_CONTROLS = ["degree_1", "no_normaliser", "no_gate", "no_gate_bias", "no_qk_norm",
                    "no_rope", "neighbour_kv_head"]


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
    """No cache, remat, chunked cross-entropy, the chunked form's own reverse
    mode across two chunks: the policy-gradient loss over the answers and its
    gradient in every adapter factor against plain reverse mode through the
    reference's attention form."""
    from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

    params, lora = weights
    monkeypatch.setattr(power_retention, "DEFAULT_CHUNK", 16)
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
    assert len(leaves) == 2 * 7  # a and b of seven targets; none on the decay
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-6,
                                   err_msg=str(path))


def test_a_train_step_moves_the_adapter_and_nothing_else(weights):
    """The learner's own update on this model: a finite loss, every adapter
    factor's b moved, the frozen base (the decay's projection too) untouched."""
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
    assert set(new_lora["layers"]["power"]) == {
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
    """Prefill in segments of 16 tokens (two pages of 8), so that 40-57-token
    prompts cross every boundary the cell's 16k-token prompts cross: (S, z)
    carried from segment to segment, a last segment that is part padding."""
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
    """The engine holds a model with NO paged layer: prefill in segments (the
    chunked form from the carried (S, z)), each prompt's states handed to its 4
    candidates, then the one-token form through the slots' state. The engine's
    own captured log-probability of every token it sampled is the reference's
    full forward's; the counter is the bytes ``power_counts`` says the same rows
    must move, and the gauge what the slots' states hold."""
    from distrl_llm_tpu import telemetry
    from perfbench import power_counts

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"]
    engine = make_engine(scheduler, slots)
    ids, mask, result = generate(engine, params, lora)
    assert (result.lengths == 24).all()
    assert result.alive_slot_steps == 8 * 24
    assert worst_difference(params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()
    moved = after["counters"]["engine/power_state_bytes"] - before.get(
        "engine/power_state_bytes", 0)
    # 3 layers x 8 rows x 24 steps, each state read once and written once
    assert moved == 3 * 8 * 24 * 2 * STATE_BYTES
    model = dataclasses.asdict(CFG)
    assert moved == power_counts.power_state_bytes(
        model, [40] * 4 + [57] * 4, result.lengths.reshape(-1))
    assert power_counts.slot_state_bytes(model) == 3 * STATE_BYTES
    held = (slots or 8) * 3 * STATE_BYTES
    assert after["gauges"]["engine/slot_state_bytes"] == held
    assert engine.last_round_stats["slot_state_bytes"] == held
    assert power_retention.dispatch_choices[power_retention.dispatch_key(2, 5, 16, 16)] == "plain"


@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_cpu_round_counts_no_kernel_steps(weights, small_pieces, scheduler, slots):
    """``ops/power_kernel_steps`` is filed by both schedulers and reads 0 here:
    heads of 16 on a CPU take the plain form, and ``power_step`` says so."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"].get(telemetry.OPS_POWER_KERNEL_STEPS, 0)
    generate(make_engine(scheduler, slots), params, lora)
    assert power_retention.dispatch_choices[
        power_retention.dispatch_key(2, 5, 16, 16)] == "plain"
    after = telemetry.observe_snapshot()["counters"]
    assert after[telemetry.OPS_POWER_KERNEL_STEPS] == before


@pytest.mark.parametrize("ran,steps,want", [
    ("kernel", 256, 3 * 256), ("plain", 256, 0), (None, 256, 0), ("kernel", 0, None)])
def test_the_counter_is_layers_times_steps_where_the_kernel_ran(monkeypatch, ran, steps, want):
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine

    assert CFG.kind_count("power") == 3
    monkeypatch.setattr(power_retention, "dispatch_choices", {} if ran is None else {
        power_retention.dispatch_key(2, 5, 16, 16): ran})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_power_telemetry(CFG, steps)
    assert filed == ([] if want is None else [("ops/power_kernel_steps", want)])
    # a model without such layers files nothing
    filed.clear()
    paged_engine._record_power_telemetry(PRESETS["tiny"], 256)
    assert filed == []


ENGINE_CONTROLS = {
    "bf16_state": None,
    "neighbour_kv_head": None,
    "z_not_handed": lambda m: {
        **m, "power_z": tuple(jnp.zeros_like(x) for x in m["power_z"])},
    "state_from_other_prompt": lambda m: {
        **m, "power": tuple(jnp.roll(x, 1, axis=0) for x in m["power"])},
}


@pytest.mark.parametrize("control", sorted(ENGINE_CONTROLS))
def test_this_files_agreement_can_tell_a_wrong_state(weights, small_pieces, control,
                                                     monkeypatch):
    """What only the cache path can get wrong: a state kept in bf16 (the chip's
    check tells it by 22% only: the traffic file's ``basis``), a normaliser or
    a state that the candidates are not handed from their own prompt, a query
    head that reads its neighbour's state."""
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


def test_the_fan_out_hands_s_and_z(weights, small_pieces):
    """Greedy, 16 candidates of one prompt are the prompt sixteen times over,
    each row on its own: every candidate starts from its prompt's state AND
    its normaliser. (Sixteen rows on both sides, so both decode through the
    same products.)"""
    params, lora = weights
    ids, mask = prompts((45,))
    greedy = dict(temperature=0.0, top_p=1.0, max_tokens=12)
    many = make_engine("waves", 0).generate(
        params, lora, ids, mask, SamplingConfig(n=16, **greedy), jax.random.PRNGKey(0))
    each = make_engine("waves", 0).generate(
        params, lora, ids.repeat(16, 0), mask.repeat(16, 0), SamplingConfig(n=1, **greedy),
        jax.random.PRNGKey(0))
    assert (many.tokens[0] == each.tokens[:, 0]).all()
    np.testing.assert_allclose(many.logprobs[0], each.logprobs[:, 0], atol=2e-6)


def test_the_prompts_state_is_the_chunked_forms_after_its_last_real_token(weights,
                                                                          small_pieces):
    """What the prefill returns for the fan-out: S and z a layer a prompt,
    float32, not zero, and no page of K or V at all."""
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    ids, mask = prompts((40, 57))
    k, v, logits, real_len, mixer = paged_engine._paged_prefill_hybrid(
        params, lora, jnp.asarray(ids), jnp.asarray(mask), cfg=CFG, prompt_pages=8,
        page_size=8, lora_scale=LORA_SCALE, cache_dtype=jnp.float32,
        attn_impl="reference", total_tokens=88)
    assert k == () and v == () and list(np.asarray(real_len)) == [40, 57]
    assert [x.shape for x in mixer["power"]] == [(2, 2, 136, 16)] * 3
    assert all(float(jnp.abs(x).max()) > 0 for x in mixer["power"] + mixer["power_z"])
    want = ref.full_logits(params, CFG, jnp.asarray(ids), jnp.asarray(mask), lora=lora,
                           lora_scale=LORA_SCALE)[:, -1]
    np.testing.assert_allclose(logits, want, atol=2e-5)


def test_the_rounds_span_and_trace_reports_line_say_what_the_slots_hold(weights, tmp_path):
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
    assert span["args"]["slot_state_bytes"] == 8 * 3 * STATE_BYTES
    assert span["args"]["power_state_bytes"] == 3 * 8 * 24 * 2 * STATE_BYTES
    lines = trace_report.build_report(events, metadata).splitlines()
    (said,) = [line for line in lines if line.startswith("    host s:")]
    assert said.endswith("; slot state 0.000 GB, moved 0.0 GB")


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
    assert what in said and "power-retention layers" in said
    assert ("a float32 power-retention state and its normaliser a KV head, and no "
            "K/V at all") in said


@pytest.mark.parametrize("switch", ["paged_verify", "paged_chunked", "paged_prefix"])
def test_forward_refuses_the_dense_decoders_other_cache_modes(weights, switch):
    params, _ = weights
    cache = {"k": (), "v": (), "page_indices": jnp.zeros((1, 2), jnp.int32),
             "lengths": jnp.zeros((1,), jnp.int32)}
    with pytest.raises(NotImplementedError, match=switch):
        forward(params, CFG, jnp.ones((1, 1), jnp.int32), kv_cache=cache, page_size=8,
                **{switch: True})


# --------------------------------------------------------------- the budget


def test_a_page_costs_its_paged_layers_and_a_slot_its_states():
    from distrl_llm_tpu.engine import budget

    # no layer keeps a page: a page costs nothing, and nothing divides by it
    assert budget.page_bytes(CFG, 8) == 0
    assert budget.slot_state_bytes(CFG, 88) == 3 * STATE_BYTES
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**9)
    assert budget.kv_pool_pages(CFG, **common) == 0
    assert budget.kv_pool_pages(CFG, slots=8, **common) == 0
    # the slots a budget allows when a slot's whole cache is state
    slots = budget.state_slots(CFG, gpu_usage=0.9, param_bytes=10**6, max_tokens=88,
                               hbm_bytes=10**9)
    assert slots == int(10**9 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6) // (3 * STATE_BYTES)
    assert budget.state_slots(CFG, gpu_usage=0.1, param_bytes=10**9, max_tokens=88,
                              hbm_bytes=10**9) == 1
    # a dense model pays for every layer and holds no row state
    tiny = PRESETS["tiny"]
    assert budget.slot_state_bytes(tiny, 88) == 0
    assert budget.page_bytes(tiny, 8) == (
        tiny.num_kv_heads * 8 * tiny.head_dim * 2 * 2 * tiny.num_layers)


@pytest.mark.parametrize("preset,paged", [
    ("tiny-delta-moe", 1), ("tiny-latent-moe", 3), ("tiny-power", 0)])
def test_pages_are_counted_over_the_layers_that_keep_them(preset, paged):
    """A model whose layers differ in kind pays for pages in its paged layers
    only (it paid for every layer before), and its slots' row states come off
    the budget before pages: the pool of a hybrid model can only have grown."""
    from distrl_llm_tpu.engine import budget

    cfg = PRESETS[preset]
    assert cfg.paged_layers == paged
    every = dataclasses.replace(cfg, mixer_types=None) if not cfg.latent else cfg
    if paged and not cfg.latent:
        assert budget.page_bytes(cfg, 8) * cfg.num_layers == (
            budget.page_bytes(every, 8) * paged)
    state = budget.slot_state_bytes(cfg, 88)
    assert (state > 0) == (not cfg.latent)
    common = dict(gpu_usage=0.9, param_bytes=10**6, batch_prompts=2, max_prompt_tokens=64,
                  max_new_tokens=24, page_size=8, hbm_bytes=10**8)
    if paged:
        assert budget.kv_pool_pages(cfg, slots=8, **common) == (
            int(10**8 * (0.9 - budget.ACTIVATION_RESERVE) - 10**6
                - 2 * 8 * budget.page_bytes(cfg, 8) - 10 * state)
            // budget.page_bytes(cfg, 8))


# ----------------------------------------------------- adapters and placement


def test_adapter_factors_are_the_dense_decoders_and_merge(weights):
    from distrl_llm_tpu.models.lora import DEFAULT_TARGETS, merge_lora

    params, lora = weights
    assert set(lora["layers"]) == {"power"}
    stack = lora["layers"]["power"]
    assert set(stack) == set(DEFAULT_TARGETS)  # none on w_decay
    assert stack["wk"]["b"].shape[-1] == 32 and stack["wq"]["b"].shape[-1] == 160
    merged = merge_lora(params, lora, alpha=8.0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 1, 256)
    a, _ = forward(merged, CFG, ids)
    b, _ = forward(params, CFG, ids, lora=lora, lora_scale=2.0)
    np.testing.assert_allclose(a, b, atol=2e-4)


def test_every_new_leaf_has_a_partition_spec(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    specs = param_specs(params)["layers"]["power"]
    for name in ("w_decay", "b_decay", "q_norm", "k_norm"):
        leaf = params["layers"]["power"][name]
        assert specs[name] == P(*([None] * leaf.ndim)), name
    assert specs["wq"] == P(None, "fsdp", "tp") and specs["wo"] == P(None, "tp", "fsdp")
    assert param_specs(lora)["layers"]["power"]["wo"]["a"] == P(None, "tp", None)


def test_the_scope_and_the_names_are_telemetrys():
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine

    assert telemetry.MODEL_POWER_ATTN == "model/power_attn"
    assert telemetry.MODEL_POWER_ATTN in telemetry.SCOPE_NAMES
    assert paged_engine.ENGINE_POWER_STATE_BYTES == "engine/power_state_bytes"
    assert paged_engine.ENGINE_SLOT_STATE_BYTES == "engine/slot_state_bytes"
    # a model without such layers files neither
    filed = []
    orig = telemetry.counter_add
    try:
        telemetry.counter_add = lambda name, value: filed.append(name)
        paged_engine._count_mixer_stats({"lin": (), "pooled": ()})
    finally:
        telemetry.counter_add = orig
    assert filed == [] and paged_engine._file_slot_state(None) == {}


def test_the_traffic_file_is_the_issues_letter_for_letter():
    traffic = json.load(open(TRAFFIC_FILE))
    assert traffic["kind"] == "rollout" and traffic["eos"] == "never"
    assert traffic["train_config"] == {
        "engine_impl": "paged", "continuous_batching": True, "max_concurrent_sequences": 32,
        "kv_cache_quant": "none", "batch_size": 2, "num_candidates": 16,
        "max_prompt_tokens": 16384, "max_new_tokens": 256, "max_lora_rank": 32}
    assert traffic["prompt_tokens"] == [8192, 16384] and traffic["trace_units"] == 1
    check = traffic["check"]
    assert 0 < check["logprob_mean_abs_tol"] < check["logprob_max_abs_tol"]
    for word in ("degree", "normaliser", "gate", "z not handed", "other prompt",
                 "neighbour", "norm", "RoPE", "3 mantissa bits", "bf16 state",
                 "test_this_files_agreement_can_tell_a_wrong_state"):
        assert word in check["basis"], word
