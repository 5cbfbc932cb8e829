"""What the train step's rematerialised layer scan keeps for the backward pass
(``learner/remat.py``, asked by ``make_train_step``): the same loss and
gradients whichever products are kept, the parent's program where nothing is,
a rule that is arithmetic over (shape, widths, layers, room), a working set
that counts the trainable tree's gradients, one decision a batch shape, and
cache-mode programs that the names leave alone.

The CPU reports no device memory, so a train step keeps nothing here; a kept
set is reached as a test reaches any memory reading, through
``DISTRL_OBS_FAKE_HBM``.
"""

import json
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distrl_llm_tpu import obs, telemetry
from distrl_llm_tpu.engine.budget import ACTIVATION_RESERVE
from distrl_llm_tpu.learner import remat
from distrl_llm_tpu.learner.train_step import UpdateBatch, _microbatch_loss, make_train_step
from distrl_llm_tpu.models import TINY, forward, init_kv_cache, init_lora_params, init_params
from distrl_llm_tpu.models import transformer
from distrl_llm_tpu.models.configs import ModelConfig

ROWS, PROMPT, ANSWER, CHUNK = 2, 6, 8, 4
SHAPE = dict(rows=ROWS, seq=PROMPT + ANSWER, head_positions=CHUNK, itemsize=4)
KEPT_SETS = {  # how many of the five names, in the rule's order
    "none": (), "qkv": ("wq", "wk", "wv"), "qkv+gate": ("wq", "wk", "wv", "w_gate"),
    "all-five": ("wq", "wk", "wv", "w_gate", "w_up"),
}


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def set_room(monkeypatch, cfg, room, *, trainable_bytes=0, **stats):
    """A device whose memory leaves ``choose_kept`` at least ``room`` bytes at
    ``SHAPE``, and less than ``room`` + 2."""
    work = remat.step_working_set(cfg, trainable_bytes=trainable_bytes, **SHAPE)
    limit = math.ceil((room + work) / (1 - ACTIVATION_RESERVE))
    monkeypatch.setenv("DISTRL_OBS_FAKE_HBM",
                       json.dumps({"bytes_limit": limit, "bytes_in_use": 0, **stats}))
    return limit


def bytes_of(cfg, names, tokens=ROWS * (PROMPT + ANSWER), itemsize=4):
    width = {"wq": cfg.q_dim, "wk": cfg.kv_dim, "wv": cfg.kv_dim,
             "w_gate": cfg.intermediate_size, "w_up": cfg.intermediate_size}
    return cfg.num_layers * tokens * itemsize * sum(width[n] for n in names)


def kept_gauges():
    gauges = telemetry.observe_snapshot()["gauges"]
    return gauges[telemetry.LEARNER_KEPT_PRODUCTS], gauges[telemetry.LEARNER_KEPT_PRODUCT_BYTES]


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    ids = rng.integers(1, TINY.vocab_size, size=(ROWS, PROMPT + ANSWER))
    return UpdateBatch(
        prompt_ids=jnp.asarray(ids[:, :PROMPT]), prompt_mask=jnp.ones((ROWS, PROMPT), jnp.int32),
        answer_ids=jnp.asarray(ids[:, PROMPT:]), answer_mask=jnp.ones((ROWS, ANSWER), jnp.int32),
        coeffs=jnp.asarray(rng.uniform(0.25, 1.0, ROWS), jnp.float32),
        sample_mask=jnp.ones(ROWS, jnp.float32),
    )


@pytest.fixture(scope="module")
def trees():
    base = init_params(jax.random.PRNGKey(0), TINY)
    lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
    # a fresh adapter's B is zero and half its gradients with it
    lora = jax.tree_util.tree_map(
        lambda x: x + 0.02 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape), lora)
    return base, lora


def program_text(lowered) -> str:
    """A lowered program's text, less the serial numbers JAX gives its private
    functions (``@closed_call_114``: a counter over everything traced, which a
    name that lowers to nothing still advances)."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered.as_text())


def loss_and_grads(trees, batch, mode, dropout, scan_remat):
    base, lora = trees
    fn = partial(
        _microbatch_loss, cfg=TINY, learner_type="pg", lora_scale=0.5,
        skip_semantics="all_zero", remat=scan_remat, attn_impl="reference",
        lora_dropout=dropout, dropout_rng=jax.random.PRNGKey(7) if dropout else None,
        logit_chunk=CHUNK, train_mode=mode)
    trainable = base if mode == "full" else lora
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda t: fn(t, base, mb=batch), has_aux=True))(trainable)
    return loss, grads


@pytest.fixture(scope="module")
def nothing_kept(trees, batch):
    """(mode, dropout) -> loss and gradients under ``forward(remat=True)``,
    JAX's ``nothing_saveable``, computed once a pair."""
    memo = {}

    def get(mode, dropout):
        if (mode, dropout) not in memo:
            memo[mode, dropout] = loss_and_grads(trees, batch, mode, dropout, True)
        return memo[mode, dropout]
    return get


@pytest.mark.parametrize("kept", list(KEPT_SETS))
@pytest.mark.parametrize("mode,dropout", [("lora", 0.0), ("lora", 0.25), ("full", 0.0)],
                         ids=["lora", "lora-dropout", "full"])  # full mode has no adapter to drop
def test_a_kept_set_changes_what_is_stored_not_what_is_computed(
        trees, batch, nothing_kept, mode, dropout, kept):
    want_loss, want = nothing_kept(mode, dropout)
    loss, grads = loss_and_grads(trees, batch, mode, dropout, remat.policy(KEPT_SETS[kept]))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5, atol=1e-7,
            err_msg=jax.tree_util.keystr(path))
    assert any(float(jnp.abs(g).max()) > 0 for _, g in flat)


OPTIMIZER = optax.sgd(1e-2)


def train_step_of(mode="lora"):
    return make_train_step(
        TINY, learner_type="pg", optimizer=OPTIMIZER, lora_scale=0.5,
        micro_size=ROWS, donate=False, logit_chunk=CHUNK, train_mode=mode)


def step_text(trees, batch, mode="lora"):
    base, lora = trees
    trainable, frozen = (base, None) if mode == "full" else (lora, base)
    return program_text(train_step_of(mode).lower(
        trainable, OPTIMIZER.init(trainable), frozen, batch))


def test_no_room_is_the_program_that_kept_nothing(monkeypatch, trees, batch):
    """The train step whose scan keeps nothing lowers to the text the parent's
    ``nothing_saveable`` lowered to: with no reading, with a reading that
    leaves no room, and with the names taken out of the program altogether."""
    lora_bytes = tree_bytes(trees[1])
    no_reading = step_text(trees, batch)
    assert kept_gauges() == (0, 0)
    set_room(monkeypatch, TINY, bytes_of(TINY, KEPT_SETS["qkv"]) - 2, trainable_bytes=lora_bytes)
    no_room = step_text(trees, batch)
    assert kept_gauges() == (0, 0)
    set_room(monkeypatch, TINY, bytes_of(TINY, KEPT_SETS["qkv"]), trainable_bytes=lora_bytes)
    some_room = step_text(trees, batch)
    assert kept_gauges() == (3, bytes_of(TINY, KEPT_SETS["qkv"]))
    monkeypatch.setattr(transformer, "checkpoint_name", lambda y, name: y)
    monkeypatch.setattr(remat, "policy", lambda names: jax.checkpoint_policies.nothing_saveable)
    parent = step_text(trees, batch)
    assert no_reading == parent
    assert no_room == parent
    assert some_room != parent


def test_full_mode_pays_for_its_gradients(monkeypatch, trees, batch):
    """The gradient accumulator, a micro-batch's gradients and the optimizer's
    update are temporaries of the step, 3 x the trainable tree: memory that
    holds all five products beside an adapter's gradients holds none beside a
    whole model's, and holds them again once it has that much more."""
    base, lora = trees
    every = bytes_of(TINY, KEPT_SETS["all-five"])
    args = dict(SHAPE, trainable_bytes=tree_bytes(lora))
    assert (remat.step_working_set(TINY, **dict(args, trainable_bytes=tree_bytes(base)))
            - remat.step_working_set(TINY, **args)) == 3 * (tree_bytes(base) - tree_bytes(lora))
    assert 3 * (tree_bytes(base) - tree_bytes(lora)) > every
    set_room(monkeypatch, TINY, every, trainable_bytes=tree_bytes(lora))
    step_text(trees, batch, "lora")
    assert kept_gauges() == (5, every)
    step_text(trees, batch, "full")
    assert kept_gauges() == (0, 0)
    set_room(monkeypatch, TINY, every, trainable_bytes=tree_bytes(base))
    step_text(trees, batch, "full")
    assert kept_gauges() == (5, every)


def test_a_shape_decides_once(monkeypatch, trees, batch):
    """A step holds what it chose at a shape's first call: memory that moves
    afterwards (an engine's round, the checked update's leftovers) does not
    make the next trace of that shape another program. Another shape asks
    anew."""
    base, lora = trees
    step, state = train_step_of(), OPTIMIZER.init(lora)
    set_room(monkeypatch, TINY, 1 << 30, trainable_bytes=tree_bytes(lora))
    first = program_text(step.lower(lora, state, base, batch))
    assert kept_gauges()[0] == 5
    telemetry.gauge_set(telemetry.LEARNER_KEPT_PRODUCTS, -1.0)
    monkeypatch.setenv("DISTRL_OBS_FAKE_HBM", json.dumps({"bytes_limit": 1, "bytes_in_use": 0}))
    assert program_text(step.lower(lora, state, base, batch)) == first
    assert kept_gauges()[0] == -1  # not asked again
    step(lora, state, base, batch)
    assert kept_gauges()[0] == -1
    narrower = batch._replace(answer_ids=batch.answer_ids[:, :6],
                              answer_mask=batch.answer_mask[:, :6])
    step(lora, state, base, narrower)
    assert kept_gauges() == (0, 0)


QWEN_7B_L14 = ModelConfig(
    vocab_size=152064, hidden_size=3584, intermediate_size=18944, num_layers=14,
    num_heads=28, num_kv_heads=4, head_dim=128, attention_bias=True)


@pytest.mark.parametrize("cfg,tokens,itemsize", [
    (TINY, ROWS * (PROMPT + ANSWER), 4), (QWEN_7B_L14, 4 * 1024, 2), (QWEN_7B_L14, 8 * 256, 2),
], ids=["tiny", "learner-1k", "rl-step-dense"])
def test_the_rule_is_arithmetic(cfg, tokens, itemsize):
    """Empty at no room, never more than it was given, monotone in room, whole
    groups in the stated order, and every byte counted."""
    every = bytes_of(cfg, KEPT_SETS["all-five"], tokens, itemsize)
    assert remat.kept_products(cfg, tokens=tokens, itemsize=itemsize, room=0) == ((), 0)
    assert remat.kept_products(cfg, tokens=tokens, itemsize=itemsize, room=-5) == ((), 0)
    last = 0
    seen = set()
    for room in np.linspace(0, every * 1.1, 97).astype(np.int64):
        names, spent = remat.kept_products(
            cfg, tokens=tokens, itemsize=itemsize, room=int(room))
        assert spent <= room and spent >= last
        assert names in KEPT_SETS.values()
        assert spent == bytes_of(cfg, names, tokens, itemsize)
        last = spent
        seen.add(names)
    assert seen == set(KEPT_SETS.values())
    assert remat.kept_products(
        cfg, tokens=tokens, itemsize=itemsize, room=every)[0] == KEPT_SETS["all-five"]


def test_the_cells_bytes_are_the_issues():
    """ISSUE 46's table: 4.87 GB for all five at ``learner-1k``'s micro-batch,
    2.70 GB for q/k/v and the gate, 2.44 GB for all five at ``rl-step-dense``'s."""
    rule = partial(remat.kept_products, QWEN_7B_L14, itemsize=2)
    assert rule(tokens=4096, room=1 << 40) == (KEPT_SETS["all-five"], 4_873_781_248)
    assert rule(tokens=4096, room=3 * 10**9) == (KEPT_SETS["qkv+gate"], 2_701_131_776)
    assert rule(tokens=2048, room=1 << 40) == (KEPT_SETS["all-five"], 2_436_890_624)


@pytest.mark.parametrize("why", ["no-reading", "layers-of-several-kinds"])
def test_nothing_to_read_or_nothing_to_name_keeps_nothing(monkeypatch, why):
    from distrl_llm_tpu.models.configs import PRESETS

    cfg = TINY
    monkeypatch.delenv("DISTRL_OBS_FAKE_HBM", raising=False)
    if why == "layers-of-several-kinds":  # models/hybrid.py's scan has its own policy
        cfg = PRESETS["tiny-jamba"]
        monkeypatch.setenv("DISTRL_OBS_FAKE_HBM",
                           json.dumps({"bytes_limit": 1 << 40, "bytes_in_use": 0}))
    assert remat.choose_kept(cfg, trainable_bytes=0, **SHAPE) == ()
    assert kept_gauges() == (0, 0)
    assert remat.policy(()) is jax.checkpoint_policies.nothing_saveable


def test_the_largest_free_block_bounds_the_room(monkeypatch):
    """A program's temporaries are one allocation: free bytes in pieces do not
    hold them."""
    need = bytes_of(TINY, KEPT_SETS["all-five"])
    limit = set_room(monkeypatch, TINY, need)
    for largest, count in ((limit, 5), (limit - need + bytes_of(TINY, KEPT_SETS["qkv"]), 3)):
        set_room(monkeypatch, TINY, need, largest_free_block_bytes=largest)
        assert len(remat.choose_kept(TINY, trainable_bytes=0, **SHAPE)) == count


def test_the_fullest_device_that_holds_the_trainable_tree_decides(monkeypatch, trees, batch):
    """The reading is of the devices the step's arguments lie on, not of
    whichever device is first, and the one with the least room sets it."""
    base, lora = trees
    asked = []
    need = bytes_of(TINY, KEPT_SETS["all-five"])
    limit = set_room(monkeypatch, TINY, need, trainable_bytes=tree_bytes(lora))

    def reading(device=None):
        asked.append(device)
        return limit, 0, limit
    monkeypatch.setattr(obs, "hbm_free", reading)
    step_text(trees, batch)
    assert asked == sorted(jax.tree_util.tree_leaves(lora)[0].sharding.addressable_devices,
                           key=lambda d: d.id)
    assert kept_gauges()[0] == 5
    fuller = (limit, need, limit)
    monkeypatch.setattr(
        obs, "hbm_free", lambda device=None: fuller if device == "b" else (limit, 0, limit))
    choose = partial(remat.choose_kept, TINY, trainable_bytes=tree_bytes(lora), **SHAPE)
    assert choose(devices=("a", "b")) == ()
    assert len(choose(devices=("a",))) == 5


@pytest.mark.parametrize("program", ["prefill", "decode", "no-cache"])
def test_the_names_leave_every_other_program_alone(monkeypatch, trees, program):
    """``checkpoint_name`` lowers to nothing: the cache-mode programs and the
    scan that is not rematerialised lower to the text they had without it."""
    base, lora = trees
    cache = init_kv_cache(TINY, ROWS, 16)
    ids = jnp.ones((ROWS, 1 if program == "decode" else PROMPT), jnp.int32)

    def run(base, lora, cache, ids):
        return forward(base, TINY, ids, lora=lora, lora_scale=0.5,
                       kv_cache=None if program == "no-cache" else cache,
                       cache_offset=PROMPT if program == "decode" else 0)

    with_names = program_text(jax.jit(run).lower(base, lora, cache, ids))
    monkeypatch.setattr(transformer, "checkpoint_name", lambda y, name: y)
    assert program_text(jax.jit(run).lower(base, lora, cache, ids)) == with_names
    for name in transformer.KEPT_PRODUCTS:
        assert f'"{name}"' not in with_names and f"name={name}" not in with_names
