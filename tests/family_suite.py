"""What the hybrid families' tests share, written once (PR 62; ROADMAP D16).

A family (a ``model_config`` PR's tiny preset against its plain reference) is a
``Family`` RECORD: data and a few functions, no class hierarchy. Its own file
(``tests/test_<family>.py``) holds the record and the cases of its own
mechanism; ``tests/test_family_conformance.py`` holds the cases every family
repeats, each parametrised by family. This module is not collected: it holds
ONE copy of ``seeded``, ``reference_logprobs``, ``forward_logprobs``,
``padded_rows``, ``prompts``, ``make_engine``, ``worst_difference`` and
``generate``, and the engines that the cases which do not bend the program
share.

A new family costs one record (``FAMILY = Family(...)`` in its own file, the
file's name in ``FAMILY_FILES``) and its own mechanism's cases.
"""

import contextlib
import dataclasses
import functools
import importlib
import json
import os
import sys
import types
from types import SimpleNamespace
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from distrl_llm_tpu.config import SamplingConfig  # noqa: E402
from distrl_llm_tpu.models import ModelConfig, forward, init_lora_params, init_params  # noqa: E402

LORA_SCALE = 2.0
#: the files that hold a ``FAMILY`` record each, in the order the models came
FAMILY_FILES = (
    "test_hybrid_model", "test_latent_moe", "test_delta_moe", "test_power_model",
    "test_jamba_model", "test_window_moe_model", "test_dsa_moe_model", "test_cca_moe",
    "test_swa_sink_moe_model", "test_scmoe_model", "test_ssd_moe")


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """One hybrid family at its tiny size. A field left ``None`` or empty means
    the shared case that reads it is not generated for the family."""

    name: str
    cfg: ModelConfig
    #: the plain reference: ``perfbench/reference_<family>.py``
    ref: types.ModuleType
    #: the benchmark's configuration file, under ``perfbench/configs/``
    config_file: str
    #: ``seeded``'s rules by leaf name that are the family's own, tried before
    #: the common ones: ``(match(name) -> bool, draw(key, x) -> array)``
    seed_rules: tuple = ()
    #: what ``seeded`` multiplies every other leaf by
    weight_scale: float = 6.0
    #: ``(module, attribute, value)`` in force for EVERY case of the family
    pieces: tuple = ()
    #: and for its engine cases (each file's ``small_pieces`` of old)
    engine_pieces: tuple = ()
    #: ``make_engine``'s keywords: ``page_size``, ``max_new_tokens``, ``prompt``
    engine_kw: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    #: a round's prompts and candidates
    lengths: tuple = (40, 57)
    candidates: int = 4
    #: ``from_hf_config``'s refusals: ``(changes, named)``
    refusals: tuple = ()
    #: what the loader says of a checkpoint: ``(loading, saving)`` patterns
    loader_refusal: tuple | None = None
    #: ``forward`` against the reference: ``(id, remat, pieces)``
    forward_cases: tuple = ()
    #: whether those cases hold every logit against ``ref.full_logits`` too
    forward_full_logits: bool = False
    #: name -> ``bend(monkeypatch) -> cfg | None``: the PROGRAM bent in one
    #: place (never the reference), each over ``forward_limit``
    forward_controls: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    forward_limit: float = 2e-3
    #: the learner's loss and gradient: ``answer`` tokens, ``leaves`` expected
    #: (or None), ``atol`` of a leaf's largest entry, ``floor``, ``pieces``
    learner: Mapping[str, Any] | None = None
    #: a train step's targets a kind
    train_targets: Mapping[str, set] | None = None
    #: ``generate`` through both schedulers: ``(scheduler, slots)``
    rounds: tuple = ()
    #: bytes a slot holds in float32 caches (the gauge), or None for no gauge
    slot_bytes: int | None = None
    #: ``check(moved, result, engine, scheduler, slots)``: the round's counters
    round_check: Callable | None = None
    #: name -> ``bend(monkeypatch) -> make_engine keywords | None``: what only
    #: the cache path can get wrong, each over ``engine_limit``
    engine_controls: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    engine_limit: float = 5e-4
    #: the forward's controls that run through the engine too, over ``forward_limit``
    engine_mechanisms: tuple = ()
    #: the fan-out: ``scheduler``, ``slots``, ``length``, ``n``, ``max_tokens``,
    #: ``atol``, ``rows`` (against n single rows, not one row)
    fan_out: Mapping[str, Any] | None = None
    #: who refuses the state: ``(id, what)`` of ``STATE_REFUSALS``, and what each says
    state_refusals: tuple = ()
    state_refusal_says: tuple = ()
    #: the round's span: its arguments and the report line's end
    span_args: Mapping[str, int] | None = None
    report_tail: str | None = None


def config_path(fam):
    return os.path.join(REPO, "perfbench", "configs", fam.config_file)


def hf_config(fam, **changes):
    """The benchmark's configuration file as ``from_hf_config`` takes it, with ``changes``."""
    with open(config_path(fam)) as f:
        return SimpleNamespace(**{**json.load(f), **changes})


def families():
    """Every family's record, read from the files that hold them."""
    return tuple(importlib.import_module(name).FAMILY for name in FAMILY_FILES)


# ------------------------------------------------------------ what is in force


@functools.lru_cache(maxsize=None)
def expert_forms(dense_to: int, block: int = 64):
    """A rule to stand in ``moe.expert_form``'s place, the ONE seam by which a
    test says which form the experts run in: the dense form for a call of up
    to ``dense_to`` tokens, blocks of ``block`` rows over it. One object a pair
    of arguments, so that a record's pieces compare equal."""
    return lambda t, *_: 0 if t <= dense_to else block


@contextlib.contextmanager
def patched(pieces):
    """``(module, attribute, value)`` set for the block."""
    patch = pytest.MonkeyPatch()
    try:
        for module, name, value in pieces:
            patch.setattr(module, name, value)
        yield
    finally:
        patch.undo()


@contextlib.contextmanager
def in_force(fam):
    """Float32 matmuls that are float32, and the family's ``pieces``."""
    with jax.default_matmul_precision("highest"), patched(fam.pieces):
        yield fam


def fixtures(fam=None):
    """``family``, ``small_pieces`` and ``weights`` for a test module. Given a
    record (a family's own file): ``family`` is autouse and ``small_pieces`` is
    applied a case at a time, as the file's own always was. Given none (the
    conformance module): ``family`` takes the record as its parameter, and
    ``small_pieces`` is applied once a family."""
    @pytest.fixture(scope="module", autouse=fam is not None)
    def family(request):
        with in_force(fam or request.param) as record:
            yield record

    @pytest.fixture(scope="function" if fam else "module")
    def small_pieces(family):
        with patched(family.engine_pieces):
            yield

    @pytest.fixture(scope="module")
    def family_weights(family):
        return weights(family)

    return family, small_pieces, family_weights


# ------------------------------------------------------------------ the weights


def named(*names):
    return lambda name: name in names


def ending(*ends):
    return lambda name: name.endswith(ends)


def starting(*starts):
    return lambda name: name.startswith(starts)


def normal(scale, mean=0.0):
    return lambda key, x: mean + scale * jax.random.normal(key, x.shape)


def uniform(low, high):
    return lambda key, x: jax.random.uniform(key, x.shape, minval=low, maxval=high)


def times(scale):
    return lambda key, x: scale * x


#: every family's: norms off 1, a correction bias that changes the choice
COMMON_RULES = ((ending("norm"), normal(0.3, 1.0)), (named("e_score_bias"), normal(0.05)))


def seeded(fam, cfg=None, rank=4):
    """Seeded weights with every term alive: norms off 1, the family's own
    leaves by ``fam.seed_rules``, every other leaf ``fam.weight_scale`` times
    its draw, an adapter whose b is not zero."""
    cfg = fam.cfg if cfg is None else cfg
    rules = fam.seed_rules + COMMON_RULES

    def base(path, x):
        name = str(path[-1].key)
        key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
        for match, draw in rules:
            if match(name):
                return draw(key, x)
        return fam.weight_scale * x

    params = jax.tree_util.tree_map_with_path(base, init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x,
        init_lora_params(jax.random.PRNGKey(1), cfg, rank),
    )
    return params, lora


@functools.cache
def weights(fam):
    """``seeded(fam)``, drawn once a process."""
    return seeded(fam)


# ------------------------------------------------ the reference and the forward


@functools.cache
def _reference(ref, name):
    """The reference's whole program, traced once a configuration and a shape."""
    return jax.jit(getattr(ref, name), static_argnums=1, static_argnames=("lora_scale",))


def reference_logprobs(fam, params, lora, ids, mask, cfg=None, of="next_token_logprobs"):
    return np.asarray(_reference(fam.ref, of)(
        params, fam.cfg if cfg is None else cfg, jnp.asarray(ids), jnp.asarray(mask),
        lora=lora, lora_scale=LORA_SCALE))


def reference_logits(fam, params, lora, ids, mask, cfg=None):
    return reference_logprobs(fam, params, lora, ids, mask, cfg, of="full_logits")


def _forward_program(params, cfg, ids, mask, lora, kw):
    logits, _ = forward(params, cfg, ids, attention_mask=mask, lora=lora,
                        lora_scale=LORA_SCALE, **dict(kw))
    logp = jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], ids[:, 1:, None], -1)[..., 0]
    return logp, logits


#: the program's ``full`` mode, traced once a (configuration, shape, keywords)
_forward = jax.jit(_forward_program, static_argnums=(1, 5))


def fresh_traces(monkeypatch):
    """For the rest of the case ``forward_logprobs`` goes through a ``jax.jit``
    of its own, made here: nothing it runs was traced before this call. Every
    control is applied through ``bend`` below, which ends with this, and a case
    that patches a constant the trace reads calls it itself; so no control can
    pass on a program traced before it was bent, however many sound forwards of
    the same shape ran first."""
    monkeypatch.setattr(sys.modules[__name__], "_forward", jax.jit(
        lambda *a: _forward_program(*a), static_argnums=(1, 5)))


def bend(monkeypatch, control, pieces=()):
    """Apply a control (and ``pieces``) and forget the forward's traces.
    Returns what the control returned."""
    for module, name, value in pieces:
        monkeypatch.setattr(module, name, value)
    out = None if control is None else control(monkeypatch)
    fresh_traces(monkeypatch)
    return out


def forward_both(fam, params, lora, ids, mask, cfg=None, **kw):
    """(next-token log-probabilities, every logit) of the program's ``full`` mode."""
    logp, logits = _forward(
        params, fam.cfg if cfg is None else cfg, jnp.asarray(ids), jnp.asarray(mask), lora,
        tuple(sorted(kw.items())))
    return np.asarray(logp), np.asarray(logits)


def forward_logprobs(fam, params, lora, ids, mask, cfg=None, **kw):
    return forward_both(fam, params, lora, ids, mask, cfg, **kw)[0]


def padded_rows(width=40):
    """Three rows: one padded on the left, one on the right, one whole; and
    where both a token and its next are real."""
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, width), 1, 256))
    mask = np.ones((3, width), np.int32)
    mask[0, :7] = 0
    mask[1, width - 7:] = 0
    return ids, mask, (mask[:, 1:] * mask[:, :-1]) > 0


# -------------------------------------------------------------------- the engine


def make_engine(fam, scheduler, slots, cfg=None, **kw):
    """A NEW engine of the family: what a control builds and drops."""
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    kw = {"cache_dtype": jnp.float32, "page_size": 8, "max_new_tokens": 24, "prompt": 64,
          **fam.engine_kw, **kw}
    if kw["page_size"] is None:  # the engine's own
        del kw["page_size"]
    return PagedGenerationEngine(
        fam.cfg if cfg is None else cfg, max_prompt_tokens=kw.pop("prompt"), eos_token_ids=[-1],
        pad_token_id=0, lora_scale=LORA_SCALE, scheduler=scheduler,
        max_concurrent_rows=slots, capture_logprobs=True, autotune=False, **kw)


_ENGINES: dict = {}


def engine(fam, scheduler, slots):
    """THE engine of a (family, scheduler, slots), built once a process and
    shared by every case that does not bend the program. It was traced under
    the family's pieces, so it runs under them and nothing else."""
    for module, name, value in fam.pieces + fam.engine_pieces:
        assert getattr(module, name) == value, (
            f"{fam.name}'s shared engine without its pieces: {module.__name__}.{name}")
    key = fam.name, scheduler, slots
    if key not in _ENGINES:
        _ENGINES[key] = make_engine(fam, scheduler, slots)
    return _ENGINES[key]


def prompt_width(fam):
    return fam.engine_kw.get("prompt", 64)


def prompts(lengths, width=64, seed=0):
    """Left-padded ``[B, width]`` ids and mask, as the engines take them."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((len(lengths), width), np.int32)
    mask = np.zeros((len(lengths), width), np.int32)
    for r, n in enumerate(lengths):
        ids[r, width - n:] = rng.integers(1, 256, n)
        mask[r, width - n:] = 1
    return ids, mask


def generate(fam, eng, params=None, lora=None, lengths=None, n=None, max_tokens=None,
             width=None):
    """A sampled round of the family's prompts: (ids, mask, result)."""
    if params is None:
        params, lora = weights(fam)
    ids, mask = prompts(fam.lengths if lengths is None else lengths,
                        prompt_width(fam) if width is None else width)
    result = eng.generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=fam.candidates if n is None else n,
                       max_tokens=max_tokens or fam.engine_kw.get("max_new_tokens", 24)),
        jax.random.PRNGKey(3))
    return ids, mask, result


def worst_difference(fam, params, lora, ids, mask, result, cfg=None):
    """The largest difference between the engine's own captured
    log-probability of a token it sampled and the reference's full forward's."""
    worst = 0.0
    for b in range(ids.shape[0]):
        prompt = ids[b][mask[b] > 0]
        rows = np.stack([np.concatenate([prompt, result.tokens[b, j]])
                         for j in range(result.tokens.shape[1])])
        want = reference_logprobs(fam, params, lora, rows, np.ones_like(rows), cfg)
        worst = max(worst, np.abs(result.logprobs[b] - want[:, len(prompt) - 1:]).max())
    return worst


def prefilled(fam, params, lora, lengths=(40, 57)):
    """What the engine's prefill returns for the fan-out, at the engine cases'
    sizes: (ids, mask, (k, v, logits, real_len, mixer))."""
    from distrl_llm_tpu.engine import paged_engine

    ids, mask = prompts(lengths)
    return ids, mask, paged_engine._paged_prefill_hybrid(
        params, lora, jnp.asarray(ids), jnp.asarray(mask), cfg=fam.cfg, prompt_pages=8,
        page_size=8, lora_scale=LORA_SCALE, cache_dtype=jnp.float32,
        attn_impl="reference", total_tokens=88)


def with_proj(monkeypatch, name, bend):
    """``hybrid.<name>`` (a mixer) handed a ``proj`` whose outputs
    ``bend(key, y, env, mode)`` bent."""
    from distrl_llm_tpu.models import hybrid

    mix = getattr(hybrid, name)

    def run(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
        def bent(h, p_, lora_, key, bias, scale):
            return bend(key, proj(h, p_, lora_, key, bias, scale), env, mode)
        return mix(x, p, lora, cache, cfg=cfg, mode=mode, env=env, proj=bent,
                   lora_scale=lora_scale)
    monkeypatch.setattr(hybrid, name, run)


def rope_in_the_softmax_layers(monkeypatch, head_dim, theta):
    """A control: q and k of ``_softmax_mix`` rotated where the model rotates nothing."""
    from distrl_llm_tpu.models import transformer

    def rotate(key, y, env, mode):
        if key not in ("wq", "wk"):
            return y
        pos = env["lengths"][:, None] if mode == "decode" else env["q_pos"]
        cos, sin = transformer.rope_cos_sin(pos, head_dim, theta)
        b, s, wide = y.shape
        return transformer.apply_rope(y.reshape(b, s, -1, head_dim), cos, sin).reshape(b, s, wide)
    with_proj(monkeypatch, "_softmax_mix", rotate)


def handed(change):
    """An engine control: what the prefill hands the fan-out, ``change(mixer)``d."""
    def control(monkeypatch):
        from distrl_llm_tpu.engine import paged_engine

        prefill = paged_engine._paged_prefill_hybrid

        def patched_prefill(*a, **kw):
            k, v, logits, real_len, mixer = prefill(*a, **kw)
            return k, v, logits, real_len, change(mixer)
        monkeypatch.setattr(paged_engine, "_paged_prefill_hybrid", patched_prefill)
    return control


def handed_each(names, change):
    """``handed``, with ``change`` over every layer's array of the states ``names``."""
    return handed(lambda m: {**m, **{n: tuple(change(x) for x in m[n]) for n in names}})


def through_the_engine(control):
    """A forward control as an engine control: the configuration it returns is
    the one the engine is told."""
    def bent(monkeypatch):
        cfg = control(monkeypatch)
        return None if cfg is None else {"cfg": cfg}
    return bent


def moved_counters(before, after):
    """name -> how far a counter moved between two snapshots' ``counters``."""
    return lambda name: after.get(name, 0) - before.get(name, 0)


def merged_equals_adapted(fam, params, lora):
    """The adapter merged into the base is the base run with the adapter."""
    from distrl_llm_tpu.models.lora import merge_lora

    merged = merge_lora(params, lora, alpha=8.0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 1, 256)
    a, _ = forward(merged, fam.cfg, ids)
    b, _ = forward(params, fam.cfg, ids, lora=lora, lora_scale=2.0)
    np.testing.assert_allclose(a, b, atol=2e-4)


def shares_add_up(fam, kind, shards):
    """The guide's section 4 at the tiny size: the chips' routed parts of an
    expert layer (``expert_shard`` 0..shards-1, two experts each) add up to what
    the uncut reference gives for the whole layer, and the program's part for a
    share is the reference's. Returns (the uncut model's weights, its
    configuration)."""
    from distrl_llm_tpu.models import moe

    uncut = dataclasses.replace(fam.cfg, n_routed_experts=2 * shards, router_experts=0)
    whole, _ = seeded(fam, uncut)
    layer = jax.tree_util.tree_map(lambda w: w[1], whole["layers"][kind])
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    want = fam.ref.routed_part(h, layer, uncut)
    total = jnp.zeros_like(want)
    for shard in range(shards):
        share = dataclasses.replace(fam.cfg, expert_shard=shard)
        assert fam.ref.held_ids(share) == [2 * shard, 2 * shard + 1] == list(share.held_experts)
        held = {**layer, **{name: layer[name][2 * shard: 2 * shard + 2]
                            for name in ("experts_gate", "experts_up", "experts_down")}}
        part = fam.ref.routed_part(h, held, share)
        got, _ = moe.moe_half(h, held, share, held=share.held_experts)
        np.testing.assert_allclose(got, part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1
    return whole, uncut


# -------------------------------------------------------- who refuses the state


def _paged(**kw):
    return lambda fam: make_engine(fam, "refill", 4, **kw)


def _dense(fam):
    from distrl_llm_tpu.engine.engine import GenerationEngine

    return GenerationEngine(fam.cfg, max_prompt_tokens=64, max_new_tokens=8,
                            eos_token_ids=[-1], pad_token_id=0, autotune=False)


def _sharded(fam):
    from distrl_llm_tpu.engine.sharded_paged import ShardedPagedEngine

    return ShardedPagedEngine(
        fam.cfg, mesh=None, max_prompt_tokens=16, max_new_tokens=8, eos_token_ids=[1],
        pad_token_id=0)


def _turn_hook(fam):
    eng = make_engine(fam, "refill", 4)
    eng.turn_hook = lambda *a: None
    ids, mask = prompts((20,), prompt_width(fam))
    return eng.generate(
        None, None, ids, mask, SamplingConfig(n=2, max_tokens=4), jax.random.PRNGKey(0))


#: every engine and feature that keeps K/V of one kind, by the id its case has
STATE_REFUSALS = {
    "dense": _dense,
    "sharded": _sharded,
    "int8_pool": _paged(kv_quant="int8"),
    "speculation": _paged(spec_draft=2),
    "pool_chains": _paged(prefix_sharing=True),
    "preemption": _paged(max_kv_pages=64),
    "radix_cache": _paged(continuous_admission=True, prefix_cache=True),
    "spill": _paged(kv_spill=True),
    "turn_resumption": _turn_hook,
    "continuous_admission": _paged(continuous_admission=True),
    "page_size": _paged(page_size=128),
}
#: the nine that name a row state, with the word each refusal says
NINE_REFUSALS = (
    ("dense", "dense engine"), ("sharded", "dp-sharded"), ("int8_pool", "kv_quant"),
    ("speculation", "spec_draft"), ("pool_chains", "prefix_sharing"),
    ("preemption", "max_kv_pages"), ("radix_cache", "prefix_sharing"),
    ("spill", "kv_spill"), ("turn_resumption", "turn_hook"))
