"""A looped dense decoder (``ouro``: Ouro-2.6B) against its plain reference,
``perfbench/reference_looped.py``, with the ``tiny-ouro`` preset (2 weight
layers, 3 passes: cache layer ``u * L + l`` and ``l * T + u`` differ, so a
wrong index shows), float32, seeded weights with every norm off 1.

One set of weights and one adapter a module; one engine a scheduler, shared by
the cases that do not bend the program. The controls are one-line bends of the
PROGRAM, each of which must fail the agreement the sound program passes.
"""

import dataclasses
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.models import ModelConfig, init_lora_params, init_params
from distrl_llm_tpu.models import transformer as tr
from distrl_llm_tpu.models.configs import PRESETS, TINY
from perfbench import reference_looped as ref

CFG = PRESETS["tiny-ouro"]
L, T = CFG.num_layers, CFG.loop_steps
SCALE = 2.0
LIMIT = 2e-5  # float32 on the CPU, logits and log-probabilities alike
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeded(cfg):
    """Every term alive: norms off 1, the gate's bias off 0, an adapter whose
    b is not zero."""
    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        if name.endswith("norm"):
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "b":
            return 0.25 * jax.random.normal(key, x.shape)
        return x

    params = jax.tree_util.tree_map_with_path(base, init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x,
        init_lora_params(jax.random.PRNGKey(1), cfg, 4))
    return params, lora


@pytest.fixture(scope="module")
def weights():
    return seeded(CFG)


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 256, (2, 12)).astype(np.int32)
    mask = np.ones((2, 12), np.int32)
    return jnp.asarray(ids), jnp.asarray(mask)


@pytest.fixture(scope="module")
def reference_logits(weights, rows):
    """The reference's full forward as next-token log-probabilities and exit
    distribution, traced once."""
    params, lora = weights
    ids, mask = rows
    logp = jax.jit(ref.next_token_logprobs, static_argnums=1, static_argnames=("lora_scale",))(
        params, CFG, ids, mask, lora=lora, lora_scale=SCALE)
    exits = jax.jit(ref.exit_distribution, static_argnums=1, static_argnames=("lora_scale",))(
        params, CFG, ids, mask, lora=lora, lora_scale=SCALE)
    return np.asarray(logp), np.asarray(exits)


def next_logprobs(logits, ids):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return np.asarray(jnp.take_along_axis(logp[:, :-1], ids[:, 1:, None], -1)[..., 0])


def cached_logprobs(params, lora, cfg, ids, mask, split=9):
    """Prefill ``split`` tokens into the dense cache, then decode the rest a
    token at a time: the log-probabilities of a full forward."""
    b, s = ids.shape

    def run(params, lora, ids):
        cache = tr.init_kv_cache(cfg, b, s, dtype=jnp.float32)
        keys = (jnp.arange(s)[None, :] < split).astype(jnp.int32) * mask
        out, cache = tr.forward(params, cfg, ids[:, :split], attention_mask=keys, lora=lora,
                                lora_scale=SCALE, kv_cache=cache, cache_offset=0)
        outs = [out]
        for t in range(split, s):
            keys = keys.at[:, t].set(1)
            out, cache = tr.forward(params, cfg, ids[:, t:t + 1], attention_mask=keys,
                                    lora=lora, lora_scale=SCALE, kv_cache=cache, cache_offset=t)
            outs.append(out)
        return jnp.concatenate(outs, 1)

    return next_logprobs(jax.jit(run)(params, lora, ids), ids)


# ----------------------------------------------------------- the configuration


def published(**changed):
    with open(os.path.join(REPO, "perfbench", "configs", "ouro-2.6b-L8.json")) as f:
        return SimpleNamespace(**{**json.load(f), **changed})


def test_from_hf_config_reads_the_published_keys():
    cfg = ModelConfig.from_hf_config(published())
    assert (cfg.num_layers, cfg.loop_steps, cfg.layer_steps, cfg.paged_layers) == (8, 4, 32, 32)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (2048, 5632, 49152)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert cfg.rope_theta == 1e6 and cfg.sliding_window is None and not cfg.attention_bias
    assert cfg.sublayer_out_norm and not cfg.hybrid and cfg.model_type == "ouro"
    assert not cfg.tie_word_embeddings
    # the weights' count stays the weights'; a token's operations run them 4 times
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert cfg.matmul_param_count == 8 * layer + 2048 * 49152
    assert cfg.decode_flops_per_token(0.0) == 2.0 * (4 * 8 * layer + 2048 * 49152)
    assert cfg.decode_flops_per_token(100.0) - cfg.decode_flops_per_token(0.0) == (
        4 * 4.0 * 8 * 2048 * 100.0)
    assert PRESETS["tiny-ouro"].paged_layers == 6 and TINY.paged_layers == TINY.num_layers


@pytest.mark.parametrize("key, value, said", [
    ("early_exit_threshold", 0.9, "rows stop at different passes"),
    ("use_sliding_window", True, "whole context"),
    ("sliding_window", 4096, "whole context"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_theta alone"),
    ("layer_types", ["full_attention"] * 7 + ["sliding_attention"], "full_attention"),
    ("total_ut_steps", 0, "at least 1"),
])
def test_from_hf_config_refuses_by_key_what_it_cannot_run(key, value, said):
    with pytest.raises(ValueError, match=f"model_type 'ouro': {key}.*{said}"):
        ModelConfig.from_hf_config(published(**{key: value}))


def test_the_uncut_depth_loads_and_runs_at_the_tiny_widths():
    """The cut is data: the published 48 layers (192 cache layers), the
    published passes and head counts at 4 values a head, against the
    reference. 192 layer applications in float32 round further apart than the
    preset's six (3e-6 at 12 layers, 8e-5 at 24, 1.1e-4 at 48 when this was
    written): the limit is this depth's."""
    cfg = ModelConfig.from_hf_config(published(
        num_hidden_layers=48, hidden_size=64, intermediate_size=96, head_dim=4,
        vocab_size=128))
    assert (cfg.num_layers, cfg.paged_layers, cfg.num_heads) == (48, 192, 16)
    params, lora = seeded(cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, (1, 9)), jnp.int32)
    mask = jnp.ones_like(ids)
    got = next_logprobs(jax.jit(lambda p, lo, i: tr.forward(
        p, cfg, i, lora=lo, lora_scale=SCALE, remat=True)[0])(params, lora, ids), ids)
    want = jax.jit(ref.next_token_logprobs, static_argnums=1, static_argnames=("lora_scale",))(
        params, cfg, ids, mask, lora=lora, lora_scale=SCALE)
    assert np.abs(got - np.asarray(want)).max() < 25 * LIMIT


def test_a_model_with_no_loop_has_the_tree_and_the_cache_it_had():
    """A Qwen2 tree gains no leaf, its cache no layer and its cache-mode
    program no gate (its programs' lowered text was compared with the parent
    commit's, byte for byte, when this was written: CHANGES.md, PR 68)."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY))
    assert sorted(params) == ["embed", "final_norm", "layers", "lm_head"]
    assert not {"attn_out_norm", "mlp_out_norm"} & set(params["layers"])
    cache = jax.eval_shape(lambda: tr.init_kv_cache(TINY, 2, 8))
    assert sorted(cache) == ["k", "v"] and len(cache["k"]) == TINY.num_layers
    assert "exit_gate" not in params and "exit_stats" not in cache
    looped = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert looped["exit_gate"]["w"].shape == (64, 1) and looped["exit_gate"]["b"].shape == (1,)
    assert looped["layers"]["attn_out_norm"].shape == (L, 64)
    assert len(jax.eval_shape(lambda: tr.init_kv_cache(CFG, 2, 8))["k"]) == L * T


# ------------------------------------------------------------------ the forward


def test_forward_equals_the_reference(weights, rows, reference_logits):
    params, lora = weights
    ids, mask = rows
    logits = jax.jit(lambda p, lo, i, m: tr.forward(
        p, CFG, i, attention_mask=m, lora=lo, lora_scale=SCALE, remat=True)[0])(
        params, lora, ids, mask)
    assert np.abs(next_logprobs(logits, ids) - reference_logits[0]).max() < LIMIT


def test_the_exit_distribution_is_the_references(weights, rows, reference_logits):
    params, lora = weights
    ids, mask = rows
    got = np.asarray(jax.jit(lambda p, lo, i, m: tr.exit_distribution(
        p, CFG, i, attention_mask=m, lora=lo, lora_scale=SCALE))(params, lora, ids, mask))
    assert got.shape == (T, *ids.shape)
    assert np.abs(got - reference_logits[1]).max() < LIMIT
    np.testing.assert_allclose(got.sum(0), 1.0, atol=1e-6)
    assert 0.02 < got[0].min() and got[0].max() < 0.98  # the gates are not saturated


def test_prefill_then_decode_through_the_dense_cache_equals_the_reference(
        weights, rows, reference_logits):
    ids, mask = rows
    got = cached_logprobs(*weights, CFG, ids, mask)
    assert np.abs(got - reference_logits[0]).max() < LIMIT


# ----------------------------------------------- controls: the program is bent


def _previous_pass_cache(monkeypatch, params):
    monkeypatch.setattr(tr, "_cache_layer",
                        lambda cfg, u, l: max(u - 1, 0) * cfg.num_layers + l)
    return CFG, params


def _one_pass_fewer(monkeypatch, params):
    return dataclasses.replace(CFG, loop_steps=T - 1), params


def _no_output_norm(name):
    def bend(monkeypatch, params):
        layers = {k: v for k, v in params["layers"].items() if k != name}
        return CFG, {**params, "layers": layers}
    return bend


def _no_norm_between_passes(monkeypatch, params):
    sound, calls = tr._close_pass, []

    def bent(x, params, cfg):
        calls.append(1)
        closed, gate = sound(x, params, cfg)
        return (closed if len(calls) % cfg.loop_steps == 0 else x), gate

    monkeypatch.setattr(tr, "_close_pass", bent)
    return CFG, params


def _final_norm_twice(monkeypatch, params):
    sound = tr._head

    def bent(x, params, cfg, *rest):
        return sound(tr.rms_norm(x, params["final_norm"], cfg.rms_norm_eps), params, cfg, *rest)

    monkeypatch.setattr(tr, "_head", bent)
    return CFG, params


#: name -> (the bend, whether it needs the cache's unrolled pass loop to show)
CONTROLS = {
    "pass_u_reads_pass_u-1s_cache_layer": (_previous_pass_cache, True),
    "one_pass_fewer": (_one_pass_fewer, False),
    "attention_output_norm_dropped": (_no_output_norm("attn_out_norm"), False),
    "mlp_output_norm_dropped": (_no_output_norm("mlp_out_norm"), False),
    "final_norm_skipped_between_passes": (_no_norm_between_passes, True),
    "final_norm_twice_at_the_end": (_final_norm_twice, False),
}


@pytest.mark.parametrize("control", CONTROLS)
def test_a_bent_forward_is_not_the_reference(control, weights, rows, reference_logits,
                                             monkeypatch):
    """Through prefill and decode over the dense cache where the bend is of the
    cache or of the unrolled pass loop, through the learner's scan otherwise (a
    fresh trace each: a ``jax.jit`` of its own)."""
    params, lora = weights
    ids, mask = rows
    bend, cached = CONTROLS[control]
    cfg, bent = bend(monkeypatch, params)
    if cached:
        got = cached_logprobs(bent, lora, cfg, ids, mask)
    else:
        got = next_logprobs(jax.jit(lambda p, lo, i, m: tr.forward(
            p, cfg, i, attention_mask=m, lora=lo, lora_scale=SCALE, remat=True)[0])(
            bent, lora, ids, mask), ids)
    assert np.abs(got - reference_logits[0]).max() > 100 * LIMIT


# ------------------------------------------------------------------ the engines

PROMPT, NEW, PAGE = 40, 10, 8
LENS = (40, 17, 32)  # 5, 2 and 4 full pages, the second with a partial page


def prompts():
    rng = np.random.default_rng(7)
    ids = np.zeros((len(LENS), PROMPT), np.int32)
    mask = np.zeros((len(LENS), PROMPT), np.int32)
    for r, n in enumerate(LENS):
        ids[r, PROMPT - n:] = rng.integers(1, 256, n)
        mask[r, PROMPT - n:] = 1
    return ids, mask


def make_engine(kind, cfg=CFG, **kw):
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    common = dict(max_prompt_tokens=PROMPT, max_new_tokens=NEW, eos_token_ids=[-1],
                  pad_token_id=0, lora_scale=SCALE, capture_logprobs=True,
                  cache_dtype=jnp.float32, autotune=False, **kw)
    if kind == "dense":
        return GenerationEngine(cfg, **common)
    if kind == "paged-refill":
        common.update(max_concurrent_rows=5, scheduler="refill")
    return PagedGenerationEngine(cfg, page_size=PAGE, **common)


def worst_difference(result, params, lora, ids, mask):
    """The engine's captured log-probabilities of the tokens it sampled against
    the reference's full forward over prompt + answer, row by row; and the
    reference's mean expected exit pass over the decoded tokens."""
    logprobs = jax.jit(ref.next_token_logprobs, static_argnums=1, static_argnames=("lora_scale",))
    exits = jax.jit(ref.exit_distribution, static_argnums=1, static_argnames=("lora_scale",))
    worst, steps = 0.0, []
    for b in range(ids.shape[0]):
        prompt = ids[b][mask[b] > 0]
        for j in range(result.tokens.shape[1]):
            n = int(result.lengths[b, j])
            row = np.zeros((1, PROMPT + NEW), np.int32)
            valid = np.zeros_like(row)
            row[0, :len(prompt) + n] = np.concatenate([prompt, result.tokens[b, j, :n]])
            valid[0, :len(prompt) + n] = 1
            want = np.asarray(logprobs(params, CFG, row, valid, lora=lora, lora_scale=SCALE))[0]
            at = slice(len(prompt) - 1, len(prompt) - 1 + n)
            worst = max(worst, float(np.abs(result.logprobs[b, j, :n] - want[at]).max()))
            # the step that decodes token t runs the model ON token t
            p = np.asarray(exits(params, CFG, row, valid, lora=lora, lora_scale=SCALE))[:, 0]
            steps.extend((np.arange(1, T + 1)[:, None] * p[:, len(prompt):len(prompt) + n]).sum(0))
    return worst, float(np.mean(steps))


@pytest.fixture(scope="module")
def engines():
    return {}


@pytest.mark.parametrize("kind", ["dense", "paged-waves", "paged-refill"])
def test_generate_equals_the_reference_and_files_the_loops_account(kind, weights, engines):
    """Prompts of 5, 2 + a partial and 4 pages, four candidates each: every
    prompt page aliased to its candidates in ALL ``T x L`` pools, then ten
    decode steps; the engine's log-probabilities, the gauge
    ``engine/exit_step_mean`` and the counter ``engine/loop_layer_steps``."""
    params, lora = weights
    ids, mask = prompts()
    engine = engines[kind] = make_engine(kind)
    before = telemetry.observe_snapshot()["counters"].get(telemetry.ENGINE_LOOP_LAYER_STEPS, 0)
    result = engine.generate(params, lora, ids, mask,
                             SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=NEW),
                             jax.random.PRNGKey(5))
    said = telemetry.observe_snapshot()
    worst, want_steps = worst_difference(result, params, lora, ids, mask)
    assert worst < LIMIT
    assert int(result.lengths.sum()) == 3 * 4 * NEW
    mean = said["gauges"][telemetry.ENGINE_EXIT_STEP_MEAN]
    assert 1.0 < mean < T and abs(mean - want_steps) < 1e-4
    steps = result.steps_dispatched if kind != "dense" else NEW
    assert said["counters"][telemetry.ENGINE_LOOP_LAYER_STEPS] - before == L * T * (steps + 1)
    if kind != "dense":  # K and V of 4 heads x 16 in float32, in every cache layer
        assert said["gauges"][telemetry.ENGINE_CACHE_TOKEN_BYTES] == L * T * 2 * 4 * 16 * 4


def _unaliased_later_passes(state):
    """The fan-out as it would be had it aliased pass 0's pools alone: the later
    passes' pools hold no prompt page for a candidate to read."""
    blank = lambda pools: tuple(p if i < L else jnp.zeros_like(p) for i, p in enumerate(pools))
    return state._replace(k_pages=blank(state.k_pages), v_pages=blank(state.v_pages))


@pytest.mark.parametrize("control", ["fan_out_aliases_pass_0s_pools_alone",
                                     "pass_u_reads_pass_u-1s_cache_layer"])
def test_a_bent_round_through_the_pages_is_not_the_reference(control, weights, monkeypatch):
    from distrl_llm_tpu.engine import paged_engine

    params, lora = weights
    ids, mask = prompts()
    if control.startswith("fan_out"):
        sound = paged_engine._paged_fanout

        def bent(*args, **kw):
            state, table = sound(*args, **kw)
            return _unaliased_later_passes(state), table

        monkeypatch.setattr(paged_engine, "_paged_fanout", bent)
    else:
        _previous_pass_cache(monkeypatch, params)
    result = make_engine("paged-waves").generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=NEW), jax.random.PRNGKey(5))
    assert worst_difference(result, params, lora, ids, mask)[0] > 100 * LIMIT


@pytest.mark.parametrize("kind, kw, said", [
    ("paged-waves", dict(kv_quant="int8"), "kv_quant='int8'"),
    ("dense", dict(kv_quant="int8"), "kv_quant='int8'"),
    ("paged-refill", dict(spec_draft=2), "spec_draft"),
    ("paged-refill", dict(prefix_sharing=True), "prefix_sharing"),
    ("paged-refill", dict(continuous_admission=True), "prefix_sharing / continuous_admission"),
    ("paged-refill", dict(max_kv_pages=64), "max_kv_pages"),
    ("paged-sharded", {}, "dp-sharded paged engine"),
])
def test_what_was_not_held_to_the_reference_refuses_the_loop_by_name(kind, kw, said):
    """Every dense-family feature either agrees with the reference above or
    says why it will not run a model whose layers run several times: none runs
    and is silently wrong (the radix cache and the host spill need continuous
    admission, which refuses; a turn hook refuses at its round)."""
    if kind == "paged-sharded":
        from distrl_llm_tpu.engine.sharded_paged import ShardedPagedEngine

        build = lambda: ShardedPagedEngine(
            CFG, jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",)),
            max_prompt_tokens=PROMPT, max_new_tokens=NEW, eos_token_ids=[-1],
            pad_token_id=0)
    else:
        build = lambda: make_engine(kind, **kw)
    with pytest.raises(ValueError, match="looped model") as refused:
        build()
    assert said in str(refused.value) and "'ouro'" in str(refused.value)
    assert "2 weight layers run 3 times a token and keep 6 cache layers" in str(refused.value)


def test_the_loader_refuses_a_checkpoint_by_name(weights):
    from distrl_llm_tpu.models import loading

    for call in (lambda: loading.params_from_state_dict({}, CFG),
                 lambda: loading.state_dict_from_params(weights[0], CFG)):
        with pytest.raises(NotImplementedError, match="model_type 'ouro' checkpoints") as refused:
            call()
        assert "until the checkpoint's files are in the repository" in str(refused.value)
        assert "seeded weights only" in str(refused.value)


# ------------------------------------------------------------------ the learner


def learner_batch():
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 256, (4, 10)).astype(np.int32)
    pmask = np.ones((4, 10), np.int32)
    pmask[0, :4] = 0
    answer = rng.integers(1, 256, (4, 12)).astype(np.int32)
    amask = np.ones((4, 12), np.int32)
    amask[2, 9:] = 0
    return prompt, pmask, answer, amask, jnp.asarray([0.7, -1.1, 0.4, 1.3])


@pytest.fixture(scope="module")
def reference_gradient(weights):
    params, lora = weights
    prompt, pmask, answer, amask, coeffs = learner_batch()
    ids = np.concatenate([prompt, answer], 1)
    mask = np.concatenate([pmask, amask], 1)
    scored = np.concatenate([np.zeros_like(pmask), amask], 1)
    return jax.jit(ref.pg_loss_and_lora_grad, static_argnums=(1, 3))(
        params, CFG, lora, SCALE, jnp.asarray(ids), jnp.asarray(mask),
        jnp.asarray(scored), coeffs)


def learner_gradient(params, lora):
    from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

    prompt, pmask, answer, amask, coeffs = learner_batch()

    def loss(lo):
        logp = answer_logprobs(
            params, CFG, jnp.asarray(prompt), jnp.asarray(pmask), jnp.asarray(answer),
            jnp.asarray(amask), lora=lo, lora_scale=SCALE, remat=True, logit_chunk=8)
        return pg_loss(logp, jnp.asarray(amask), coeffs)

    return jax.jit(jax.value_and_grad(loss))(lora)


def gradient_gap(got, want) -> float:
    """The largest difference of a factor's gradient, relative to that
    factor's largest entry in the reference."""
    return max(float(jnp.abs(g - w).max() / jnp.abs(w).max())
               for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))


def test_the_learners_loss_and_adapter_gradient_are_the_references(weights,
                                                                   reference_gradient):
    """No cache, the rematerialised scan over the layers inside the scan over
    the passes, chunked cross-entropy: ONE adapter a weight layer serves every
    pass, and its gradient is the sum over the passes."""
    want_loss, want = reference_gradient
    got_loss, got = learner_gradient(*weights)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    assert all(float(jnp.abs(w).max()) > 0 for w in jax.tree_util.tree_leaves(want))
    assert set(got["layers"]) == {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert got["layers"]["wq"]["a"].shape[0] == L  # a factor a WEIGHT layer
    assert gradient_gap(got, want) < 5e-4  # 1.1e-4 when this was written


def test_a_gradient_that_drops_the_earlier_passes_is_not_the_references(
        weights, reference_gradient, monkeypatch):
    """The same loss to the last digit, and a gradient that lacks what the
    passes before the last contribute through the stream: told apart."""
    sound = tr._close_pass
    monkeypatch.setattr(tr, "_close_pass", lambda x, params, cfg: (
        jax.lax.stop_gradient(sound(x, params, cfg)[0]), sound(x, params, cfg)[1]))
    want_loss, want = reference_gradient
    got_loss, got = learner_gradient(*weights)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    assert gradient_gap(got, want) > 0.05


def test_a_train_step_moves_the_adapter_and_nothing_else(weights):
    import optax

    from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step

    params, lora = weights
    prompt, pmask, answer, amask, coeffs = learner_batch()
    batch = UpdateBatch(
        prompt_ids=jnp.asarray(prompt), prompt_mask=jnp.asarray(pmask),
        answer_ids=jnp.asarray(answer), answer_mask=jnp.asarray(amask),
        coeffs=coeffs, sample_mask=jnp.ones((4,), jnp.float32))
    optimizer = optax.adam(1e-3)
    step = make_train_step(CFG, learner_type="pg", optimizer=optimizer, lora_scale=SCALE,
                           micro_size=2, donate=False)
    new_lora, _, loss = step(lora, optimizer.init(lora), params, batch)[:3]
    assert np.isfinite(float(loss))
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()), new_lora, lora)
    assert all(m > 0 for m in jax.tree_util.tree_leaves(moved))


def test_the_kept_products_and_the_budget_count_every_pass():
    """What the learner's scan keeps and what the engine's budget gives a page
    are sized by the layer APPLICATIONS, the weights by the layers."""
    from distrl_llm_tpu.engine import budget
    from distrl_llm_tpu.learner import remat

    once = dataclasses.replace(CFG, loop_steps=1)
    for room in (10**9, 30_000):
        names, spent = remat.kept_products(CFG, tokens=64, itemsize=4, room=room)
        names_once, spent_once = remat.kept_products(once, tokens=64, itemsize=4, room=room * 3)
        assert spent == T * spent_once or (names, names_once) == ((), ())
    assert remat.kept_products(CFG, tokens=64, itemsize=4, room=10**9)[0] == (
        "wq", "wk", "wv", "w_gate", "w_up")
    work = lambda cfg: remat.step_working_set(
        cfg, rows=2, seq=32, head_positions=8, itemsize=4, trainable_bytes=0)
    assert work(CFG) - work(once) == (T - 1) * L * 64 * 64 * 4
    assert budget.page_bytes(CFG, page_size=8) == (
        T * budget.page_bytes(once, page_size=8))
