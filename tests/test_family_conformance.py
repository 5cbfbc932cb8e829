"""The cases every hybrid family repeats, written once and parametrised by
family (PR 62; ROADMAP D16): each family's tiny preset against its plain
reference, float32 on the CPU. ``tests/family_suite.py`` holds the helpers and
the ``Family`` record; each family's own file holds its record and the cases of
its own mechanism. A case is generated for the families whose record has what
it reads, and for no other: there is no ``skip`` here.

``tests/conftest.py`` orders this module a family at a time (a stable sort on
the ``family`` parameter) and hands a family's cases to ONE worker
(``unit_of``): ``family`` and ``small_pieces`` are set up once a family, and the
engines of ``family_suite.engine`` once a (family, scheduler, slots) a process.
The cases that do not touch an engine come first in this file, so that they run
before the family's ``small_pieces`` are in force, as they did in the family's
own file.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite as fs
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.models import ModelConfig, forward
from distrl_llm_tpu.models.configs import PRESETS

FAMILIES = fs.families()


#: ``family`` takes a record as its parameter; ``small_pieces`` once a family
family, small_pieces, weights = fs.fixtures()


def per_family(each):
    """``parametrize`` over ``each(fam) -> [(id, value), ...]`` of every family
    (a family with nothing to give gets no case)."""
    params = [pytest.param(fam, value, id=f"{fam.name}-{name}")
              for fam in FAMILIES for name, value in each(fam)]
    return pytest.mark.parametrize("family,case", params, indirect=["family"])


# ------------------------------------------------------ the suite's own machinery


def test_every_hybrid_preset_has_a_family_record_and_a_benchmark_file():
    """The next ``model_config`` PR that forgets its record fails here by name."""
    with open(os.path.join(fs.REPO, "BENCHMARK.json")) as f:
        configs = {os.path.basename(entry["file"]) for entry in json.load(f)["configs"]}
    held = {fam.cfg for fam in FAMILIES}
    for name, cfg in PRESETS.items():
        if cfg.hybrid:
            assert cfg in held, f"PRESETS[{name!r}] is hybrid and no Family record holds it"
    assert len({fam.name for fam in FAMILIES}) == len(FAMILIES) == len(fs.FAMILY_FILES)
    for fam in FAMILIES:
        assert fam.cfg.hybrid, fam.name
        assert fam.config_file in configs, f"{fam.name}: {fam.config_file} is no benchmark config"
        assert set(fam.engine_mechanisms) <= set(fam.forward_controls), fam.name
        assert {i for i, _ in fam.state_refusals} <= set(fs.STATE_REFUSALS), fam.name


# --------------------------------------------------- what the program is told


@per_family(lambda fam: [(named, (changes, named)) for changes, named in fam.refusals])
def test_from_hf_config_refuses_what_it_cannot_represent(family, case):
    """It ignores every key it does not know: an unknown architecture would
    load, silently, as a dense GQA decoder."""
    changes, named = case
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(fs.hf_config(family, **changes))


@per_family(lambda fam: [("both_directions", fam.loader_refusal)] if fam.loader_refusal else [])
def test_the_loader_refuses_a_checkpoint_by_name(family, case, weights):
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    loading, saving = case
    with pytest.raises(NotImplementedError, match=loading):
        params_from_state_dict({}, family.cfg)
    with pytest.raises(NotImplementedError, match=saving):
        state_dict_from_params(weights[0], family.cfg)


@per_family(lambda fam: [(switch, switch) for switch in
                         ("paged_verify", "paged_chunked", "paged_prefix")])
def test_forward_refuses_the_dense_decoders_other_cache_modes(family, case, weights):
    params, _ = weights
    cache = {"k": (), "v": (), "page_indices": jnp.zeros((1, 2), jnp.int32),
             "lengths": jnp.zeros((1,), jnp.int32)}
    with pytest.raises(NotImplementedError, match=case):
        forward(params, family.cfg, jnp.ones((1, 1), jnp.int32), kv_cache=cache, page_size=8,
                **{case: True})


# ------------------------------------------------------------- the forward


@per_family(lambda fam: [(name, (remat, pieces)) for name, remat, pieces in fam.forward_cases])
def test_forward_equals_the_reference(family, case, weights, monkeypatch):
    """``full`` mode (the learner's and the scorer's) over rows padded on either
    side: the next-token log-probabilities, and where the reference gives them
    every logit of every real token."""
    remat, pieces = case
    params, lora = weights
    ids, mask, both = fs.padded_rows()
    if pieces:
        fs.bend(monkeypatch, None, pieces)
    want = fs.reference_logprobs(family, params, lora, ids, mask)
    got, _ = fs.forward_both(family, params, lora, ids, mask, remat=remat)
    assert np.abs(got - want)[both].max() < 2e-5
    if family.forward_full_logits:
        _, logits = fs.forward_both(family, params, lora, ids, mask)
        whole = fs.reference_logits(family, params, lora, ids, mask)
        assert np.abs(logits - whole)[mask > 0].max() < 2e-5


@per_family(lambda fam: list(fam.forward_controls.items()))
def test_the_forward_can_tell_each_mechanism(family, case, weights, monkeypatch):
    """Each mechanism dropped or bent IN THE PROGRAM moves the log-probabilities
    a hundred times further from the reference than the sound program's 2e-5;
    and it does so AFTER the sound forward of the same shape has run, through
    ``forward_logprobs``'s own cache, in this very case."""
    params, lora = weights
    ids, mask, both = fs.padded_rows()
    want = fs.reference_logprobs(family, params, lora, ids, mask)
    assert np.abs(fs.forward_logprobs(family, params, lora, ids, mask) - want)[both].max() < 2e-5
    cfg = fs.bend(monkeypatch, case)
    got = fs.forward_logprobs(family, params, lora, ids, mask, cfg)
    assert np.abs(got - want)[both].max() > family.forward_limit


@per_family(lambda fam: list(fam.forward_controls.items())[:1])
def test_a_control_cannot_pass_on_a_trace_made_before_it(family, case, weights, monkeypatch):
    """What ``fresh_traces`` is for: with the forward's one ``jax.jit`` left in
    place, a control that patches a function the trace reads (and hands back no
    configuration of its own) runs the program traced BEFORE it and agrees with
    the reference; through ``bend`` it does not."""
    params, lora = weights
    ids, mask, both = fs.padded_rows()
    want = fs.reference_logprobs(family, params, lora, ids, mask)
    run = lambda cfg=None: np.abs(
        fs.forward_logprobs(family, params, lora, ids, mask, cfg) - want)[both].max()
    assert run() < 2e-5
    cfg = case(monkeypatch)  # bent, and the old trace still in the cache
    if cfg is None or cfg == family.cfg:
        assert run() < 2e-5  # the stale program: the hazard
    fs.fresh_traces(monkeypatch)
    assert run(cfg) > family.forward_limit


@per_family(lambda fam: [("pg", fam.learner)] if fam.learner else [])
def test_the_learners_loss_and_adapter_gradient_are_the_references(family, case, weights,
                                                                   monkeypatch):
    """No cache, remat, chunked cross-entropy: the policy-gradient loss over the
    answers and its gradient in every adapter factor against plain reverse mode
    through the reference."""
    from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

    params, lora = weights
    cfg, width = family.cfg, case["answer"]
    fs.bend(monkeypatch, None, case.get("pieces", ()))
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 256, (4, 12)).astype(np.int32)
    pmask = np.ones((4, 12), np.int32)
    pmask[0, :5] = 0
    answer = rng.integers(1, 256, (4, width)).astype(np.int32)
    amask = np.ones((4, width), np.int32)
    amask[2, 14:] = 0
    coeffs = jnp.asarray([0.7, -1.1, 0.4, 1.3])

    def loss(lo):
        logp = answer_logprobs(
            params, cfg, jnp.asarray(prompt), jnp.asarray(pmask), jnp.asarray(answer),
            jnp.asarray(amask), lora=lo, lora_scale=fs.LORA_SCALE, remat=True, logit_chunk=8)
        return pg_loss(logp, jnp.asarray(amask), coeffs)

    got_loss, got = jax.jit(jax.value_and_grad(loss))(lora)
    ids = np.concatenate([prompt, answer], 1)
    mask = np.concatenate([pmask, amask], 1)
    scored = np.concatenate([np.zeros_like(pmask), amask], 1)
    want_loss, want = jax.jit(family.ref.pg_loss_and_lora_grad, static_argnums=(1, 3))(
        params, cfg, lora, fs.LORA_SCALE, jnp.asarray(ids), jnp.asarray(mask),
        jnp.asarray(scored), coeffs)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    leaves = jax.tree_util.tree_leaves_with_path(got)
    if case.get("leaves") is not None:
        assert len(leaves) == case["leaves"]
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            g, w, atol=case.get("atol", 2e-5) * float(jnp.abs(w).max()) + case.get("floor", 1e-6),
            err_msg=str(path))


@per_family(lambda fam: [("pg", fam.train_targets)] if fam.train_targets else [])
def test_a_train_step_moves_the_adapter_and_nothing_else(family, case, weights):
    """The learner's own update on this model: a finite loss, every adapter
    factor moved, and the targets each kind of layer has."""
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
    step = make_train_step(family.cfg, learner_type="pg", optimizer=optimizer,
                           lora_scale=fs.LORA_SCALE, micro_size=2, donate=False)
    new_lora, _, loss = step(lora, optimizer.init(lora), params, batch)[:3]
    assert np.isfinite(float(loss))
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()), new_lora, lora)
    assert all(m > 0 for m in jax.tree_util.tree_leaves(moved))
    assert {kind: set(stack) for kind, stack in new_lora["layers"].items()} == dict(case)


# ------------------------------------------------------------ the refusals


@per_family(lambda fam: [(name, (name, what)) for name, what in fam.state_refusals])
def test_what_holds_k_and_v_of_one_kind_names_the_state_it_cannot_hold(family, case):
    """One sentence for every engine and feature that keeps K/V of one kind:
    it names the layers and the state a slot holds for them."""
    name, what = case
    with pytest.raises(ValueError) as e:
        fs.STATE_REFUSALS[name](family)
    said = str(e.value)
    assert what in said
    for phrase in family.state_refusal_says:
        assert phrase in said, phrase


# -------------------------------------------------------------- the engine


@per_family(lambda fam: [(f"{scheduler}-{slots}", (scheduler, slots))
                         for scheduler, slots in fam.rounds])
def test_generate_equals_the_reference_token_by_token(family, case, weights, small_pieces):
    """Both schedulers: prefill in segments, each prompt's state and page chain
    handed to its candidates, then one token a step through the cache. The
    engine's own captured log-probability of every token it sampled is the
    reference's full forward's; the gauge is what the slots hold; the counters
    are what the family's ``round_check`` says they are."""
    from distrl_llm_tpu import telemetry

    scheduler, slots = case
    params, lora = weights
    before = dict(telemetry.observe_snapshot()["counters"])
    engine = fs.engine(family, scheduler, slots)
    ids, mask, result = fs.generate(family, engine)
    rows = len(family.lengths) * family.candidates
    steps = family.engine_kw.get("max_new_tokens", 24)
    assert (result.lengths == steps).all() and result.alive_slot_steps == rows * steps
    assert fs.worst_difference(family, params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()
    if family.slot_bytes is not None:
        held = (slots or rows) * family.slot_bytes
        assert after["gauges"]["engine/slot_state_bytes"] == held
        assert engine.last_round_stats["slot_state_bytes"] == held
    family.round_check(fs.moved_counters(before, after["counters"]), result, engine,
                       scheduler, slots)


@per_family(lambda fam: list(fam.engine_controls.items()))
def test_this_files_agreement_can_tell_a_wrong_state(family, case, weights, small_pieces,
                                                     monkeypatch):
    """What only the cache path can get wrong (a state kept at a lower precision,
    one the candidates are not handed, one handed from the other prompt, ...):
    an engine of its own, built under the bend and dropped."""
    params, lora = weights
    kw = fs.bend(monkeypatch, case) or {}
    ids, mask, result = fs.generate(family, fs.make_engine(family, "waves", 0, **kw))
    assert fs.worst_difference(family, params, lora, ids, mask, result) > family.engine_limit


@per_family(lambda fam: [(name, fam.forward_controls[name]) for name in fam.engine_mechanisms])
def test_the_engines_agreement_can_tell_the_mechanisms_too(family, case, weights, small_pieces,
                                                           monkeypatch):
    """Controls of the chip's check that bend a mixer or the router, through
    segments, fan-out and the decode steps."""
    params, lora = weights
    cfg = fs.bend(monkeypatch, case)
    ids, mask, result = fs.generate(family, fs.make_engine(family, "waves", 0, cfg))
    assert fs.worst_difference(family, params, lora, ids, mask, result) > family.forward_limit


@per_family(lambda fam: [("greedy", fam.fan_out)] if fam.fan_out else [])
def test_the_fan_out_hands_every_candidate_its_prompts_state(family, case, weights,
                                                             small_pieces):
    """A group's fan-out aliases one prompt's pages and copies its state: at
    temperature 0 its candidates are what the single row gives (or, where the
    family says ``rows``, what as many rows of the same prompt give)."""
    params, lora = weights
    n = case["n"]
    ids, mask = fs.prompts((case["length"],), fs.prompt_width(family))
    greedy = dict(temperature=0.0, top_p=1.0, max_tokens=case["max_tokens"])
    engine = fs.engine(family, case["scheduler"], case["slots"])
    many = engine.generate(
        params, lora, ids, mask, SamplingConfig(n=n, **greedy), jax.random.PRNGKey(0))
    if case.get("rows"):
        each = engine.generate(
            params, lora, np.repeat(ids, n, 0), np.repeat(mask, n, 0),
            SamplingConfig(n=1, **greedy), jax.random.PRNGKey(0))
        np.testing.assert_array_equal(many.tokens[0], each.tokens[:, 0])
        np.testing.assert_allclose(many.logprobs[0], each.logprobs[:, 0], atol=case["atol"])
    else:
        one = engine.generate(
            params, lora, ids, mask, SamplingConfig(n=1, **greedy), jax.random.PRNGKey(0))
        assert (many.tokens == one.tokens[:, :1]).all()
        np.testing.assert_allclose(many.logprobs, np.repeat(one.logprobs, n, 1),
                                   atol=case["atol"])


@per_family(lambda fam: [("waves", fam.report_tail)] if fam.report_tail else [])
def test_the_rounds_span_and_trace_reports_line_say_what_the_round_did(
        family, case, weights, small_pieces, tmp_path):
    """With tracing on the round's span carries the gauge and the counters, and
    ``tools/trace_report.py`` prints them on the round's host line."""
    from distrl_llm_tpu import telemetry
    from tools import trace_report

    engine = fs.engine(family, "waves", 0)
    fs.generate(family, engine)  # warm-up: no compile/ span in the traced round
    telemetry.configure(True)
    try:
        telemetry.export_chrome_trace(str(tmp_path / "before.json"), clear=True)  # others' spans
        fs.generate(family, engine)
        path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"), clear=True)
    finally:
        telemetry.configure(False)
    events, metadata = trace_report.load_trace(path)
    (span,) = [e for e in events if e.get("name") == telemetry.ENGINE_DECODE]
    if family.slot_bytes is not None:
        rows = len(family.lengths) * family.candidates
        assert span["args"]["slot_state_bytes"] == rows * family.slot_bytes
    for name, value in family.span_args.items():
        assert span["args"][name] == value, name
    lines = trace_report.build_report(events, metadata).splitlines()
    (said,) = [line for line in lines if line.startswith("    host s:")]
    assert said.endswith(case)
