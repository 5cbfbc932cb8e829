"""The decode view of a frozen base: the mixer's q/k/v/o projections one
``[out, in]`` array a layer (``models/transformer.py::decode_view``), read by
the cache-mode programs where the stacked tree would be sliced and transposed
on every step; built once a base by the engines
(``engine.LoraMailbox._decode_params``)."""

import dataclasses
import gc
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite
from distrl_llm_tpu import telemetry
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.models import (
    PRESETS, forward, init_kv_cache, init_lora_params, init_params,
)
from distrl_llm_tpu.models.hybrid import init_mixer_state
from distrl_llm_tpu.models.transformer import (
    DECODE_VIEW_KEYS, _slice_layer, decode_view, decode_view_leaves,
)
from distrl_llm_tpu.ops.linear import OutIn, linear

#: the families' tiny configurations, by ``Family.name``
FAMILIES = {fam.name: fam.cfg for fam in family_suite.families()}
#: tests/test_hybrid_model.py's SALA at four layers: sparse at both ends, lightning between
SALA = dataclasses.replace(
    FAMILIES["sala"], num_layers=4,
    mixer_types=("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"))
#: every layer kind that has a key in the table: (config, page size)
KINDS = {
    "softmax": (PRESETS["tiny"], 8),
    "sparse+lightning": (SALA, 4),
    "softmax+delta": (FAMILIES["delta-moe"], 8),
    "power": (FAMILIES["power"], 8),
    "softmax+mamba": (FAMILIES["jamba"], 8),
    "latent": (FAMILIES["latent-moe"], 8),
}
ROWS, SEG_PAGES, WIDTH = 3, 2, 4


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def held_twice(params) -> int:
    return sum(leaf.nbytes for _, leaf in decode_view_leaves(params["layers"]))


def weights(cfg, with_lora: bool):
    params = jax.tree_util.tree_map(
        lambda x: 3.0 * x, init_params(jax.random.PRNGKey(0), cfg))
    if not with_lora:
        return params, None
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x,
        init_lora_params(jax.random.PRNGKey(1), cfg, 4))
    return params, lora


def paged_cache(cfg, page: int):
    pool = lambda: jnp.zeros(cfg.page_pool_shape(ROWS * WIDTH, page), jnp.float32)
    layers = cfg.paged_layers if cfg.hybrid else cfg.num_layers
    latent = cfg.hybrid and cfg.latent
    mixer = init_mixer_state(cfg, ROWS, WIDTH * page, jnp.float32) if cfg.hybrid else {}
    return {
        "k": tuple(pool() for _ in range(layers)),
        "v": () if latent else tuple(pool() for _ in range(layers)),
        **mixer,
        "page_indices": jnp.arange(ROWS * WIDTH, dtype=jnp.int32).reshape(ROWS, WIDTH),
    }


def assert_close(a, b):
    """Every leaf of ``a`` equals ``b``'s to float32 rounding at the leaf's
    own scale (the two trees' products sum in another order)."""
    flat_a, flat_b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        np.testing.assert_allclose(x, y, rtol=0, atol=1e-5 * max(1.0, np.abs(y).max()))


@pytest.mark.parametrize("with_lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_paged_programs_read_the_view_as_they_read_the_stacked_tree(kind, with_lora):
    """A prefill segment, then one decode token a row through the cache the
    segment left: logits and every cache entry with the view equal those with
    the stacked tree, for every layer kind whose projections are in the table."""
    cfg, page = KINDS[kind]
    params, lora = weights(cfg, with_lora)
    view = decode_view(params)
    assert held_twice(params) > 0
    seg = SEG_PAGES * page
    ids = jax.random.randint(jax.random.PRNGKey(2), (ROWS, seg + 1), 1, 256)
    cache = paged_cache(cfg, page)
    lengths = jnp.full((ROWS,), seg, jnp.int32)
    if cfg.hybrid:
        prefill = {**cache, "lengths": lengths, "segment_start": jnp.int32(0)}
        mask = jnp.ones((ROWS, seg), jnp.int32)
    else:
        prefill, mask = {**cache, "lengths": lengths}, jnp.ones((ROWS, seg), jnp.int32)
    run = lambda tree, tokens, cache, **kw: forward(
        tree, cfg, tokens, lora=lora, lora_scale=2.0, kv_cache=cache, page_size=page,
        paged_impl="reference", **kw)
    logits, filled = run(params, ids[:, :seg], prefill, attention_mask=mask)
    logits_v, filled_v = run(view, ids[:, :seg], prefill, attention_mask=mask)
    assert_close(logits_v, logits)
    assert_close(filled_v, filled)
    filled.pop("segment_start", None)
    step = {**filled, "lengths": lengths}
    if cfg.hybrid:
        step["alive"] = jnp.ones((ROWS,), bool)
    logits, after = run(params, ids[:, seg:], step)
    logits_v, after_v = run(view, ids[:, seg:], step)
    assert float(jnp.abs(logits).max()) > 0.1
    assert_close(logits_v, logits)
    assert_close(after_v, after)


@pytest.mark.parametrize("with_lora", [False, True], ids=["base", "lora"])
def test_the_dense_cache_reads_the_view_as_it_reads_the_stacked_tree(with_lora):
    cfg = PRESETS["tiny"]
    params, lora = weights(cfg, with_lora)
    view = decode_view(params)
    ids = jax.random.randint(jax.random.PRNGKey(2), (ROWS, 9), 1, 256)
    cache = init_kv_cache(cfg, ROWS, 16)
    mask = (jnp.arange(16)[None, :] < 8).astype(jnp.int32).repeat(ROWS, 0)
    run = lambda tree, tokens, cache, at, mask: forward(
        tree, cfg, tokens, lora=lora, lora_scale=2.0, kv_cache=cache,
        cache_offset=at, attention_mask=mask)
    logits, filled = run(params, ids[:, :8], cache, 0, mask)
    logits_v, filled_v = run(view, ids[:, :8], cache, 0, mask)
    assert_close(logits_v, logits)
    assert_close(filled_v, filled)
    mask = mask.at[:, 8].set(1)
    logits, _ = run(params, ids[:, 8:], filled, 8, mask)
    logits_v, _ = run(view, ids[:, 8:], filled, 8, mask)
    assert_close(logits_v, logits)


def test_a_view_shares_every_leaf_it_does_not_hold_per_layer():
    """Only the table's keys are duplicated, as ``layers`` arrays ``[out, in]``
    equal to the stacked leaf's transposed slices; every other leaf is the
    stacked tree's own array; ``_slice_layer`` indexes a view and hands
    ``linear`` the transposed weight."""
    cfg = PRESETS["tiny-jamba"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    view, copied = decode_view(params), held_twice(params)
    want = 0
    for kind, stack in params["layers"].items():
        for key, leaf in stack.items():
            held = view["layers"][kind][key]
            if key in DECODE_VIEW_KEYS:
                want += leaf.nbytes
                assert isinstance(held, tuple) and len(held) == leaf.shape[0]
                for i, w in enumerate(held):
                    assert isinstance(w, OutIn)
                    np.testing.assert_array_equal(w.w, leaf[i].T)
            else:
                assert held is leaf, (kind, key)
    assert "w_in" not in DECODE_VIEW_KEYS and "w_in" in params["layers"]["mamba"]
    assert copied == want > 0
    assert view["embed"] is params["embed"]
    layer = _slice_layer(view["layers"]["softmax"], 0)
    assert isinstance(layer["wq"], OutIn) and not isinstance(layer["w_gate"], OutIn)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.hidden_size))
    np.testing.assert_allclose(
        linear(x, layer["wq"]), x @ params["layers"]["softmax"]["wq"][0], rtol=1e-6, atol=1e-6)


def test_of_a_latent_layers_projections_the_view_holds_wq_alone():
    """The census's answer for the latent kinds: ``wq`` a layer at a time,
    ``wo`` and the latent projections the stacked tree's own arrays."""
    params = init_params(jax.random.PRNGKey(0), PRESETS["tiny-latent-moe"])
    view = decode_view(params)
    assert set(params["layers"]) == {"latent", "latent_moe"}
    for kind, stack in params["layers"].items():
        assert {"wq", "wo", "wkv_a", "wkv_b"} <= set(stack)
        for key, leaf in stack.items():
            held = view["layers"][kind][key]
            if key == "wq":
                assert all(isinstance(w, OutIn) for w in held) and len(held) == leaf.shape[0]
            else:
                assert held is leaf, (kind, key)
    assert held_twice(params) == sum(s["wq"].nbytes for s in params["layers"].values())


def test_a_quantized_container_passes_through_unchanged():
    """A quantized base's containers are dict leaves: the view holds the very
    same arrays under those keys and duplicates nothing of them."""
    from distrl_llm_tpu.ops.quant import quantize_params

    cfg = PRESETS["tiny"]
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), bits=8)
    assert isinstance(params["layers"]["wq"], dict)
    view = decode_view(params)
    assert held_twice(params) == 0
    for got, want in zip(jax.tree_util.tree_leaves(view), jax.tree_util.tree_leaves(params)):
        assert got is want


def test_a_tree_placed_on_a_mesh_keeps_its_sharding_with_the_axes_swapped():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    cfg = PRESETS["tiny"]
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = param_specs(params)
    placed = jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)), params, specs)
    view = decode_view(placed)
    for key in DECODE_VIEW_KEYS:
        spec = tuple(specs["layers"][key]) + (None,) * 3
        for held in view["layers"][key]:
            assert held.w.sharding.is_equivalent_to(
                NamedSharding(mesh, P(spec[2], spec[1])), 2), (key, held.w.sharding)
    ids = jnp.ones((2, 4), jnp.int32)
    cache = init_kv_cache(cfg, 2, 8)
    mask = jnp.ones((2, 8), jnp.int32)
    with jax.set_mesh(mesh):
        got, _ = jax.jit(lambda p: forward(p, cfg, ids, kv_cache=cache, attention_mask=mask))(view)
    want, _ = forward(params, cfg, ids, kv_cache=cache, attention_mask=mask)
    assert_close(got, want)


# ------------------------------------------------------------------ the memo


def make_engine(kind: str, cfg=PRESETS["tiny"]):
    kw = dict(max_prompt_tokens=16, max_new_tokens=4, eos_token_ids=[-1], pad_token_id=0,
              lora_scale=2.0, autotune=False)
    if kind == "dense":
        from distrl_llm_tpu.engine.engine import GenerationEngine

        return GenerationEngine(cfg, **kw)
    if kind == "paged":
        from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

        return PagedGenerationEngine(cfg, page_size=8, **kw)
    from jax.sharding import Mesh

    from distrl_llm_tpu.engine.sharded_paged import ShardedPagedEngine

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1), ("dp", "fsdp", "tp"))
    return ShardedPagedEngine(cfg, mesh=mesh, page_size=8, **kw)


def one_round(engine, params, lora, seed=0):
    ids = np.zeros((2, 16), np.int32)
    mask = np.zeros((2, 16), np.int32)
    ids[:, 10:], mask[:, 10:] = 7, 1
    return engine.generate(
        params, lora, ids, mask, SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=4),
        jax.random.PRNGKey(seed))


@pytest.mark.parametrize("kind", ["dense", "paged", "sharded"])
def test_three_rounds_on_one_base_build_one_view(kind):
    """Every engine takes the stacked tree every round and builds its view
    once: the counter reads 1 after three rounds, the gauge the table's
    leaves' bytes."""
    cfg = PRESETS["tiny"]
    params, lora = weights(cfg, True)
    engine = make_engine(kind)
    before = telemetry.observe_snapshot()["counters"].get(telemetry.ENGINE_DECODE_VIEW_BUILDS, 0)
    views = []
    for i in range(3):
        one_round(engine, params, lora, seed=i)
        views.append(engine._view_slot[1])
    assert views[0] is views[1] is views[2]
    snap = telemetry.observe_snapshot()
    assert snap["counters"][telemetry.ENGINE_DECODE_VIEW_BUILDS] - before == 1
    table = sum(params["layers"][key].nbytes for key in DECODE_VIEW_KEYS)
    assert snap["gauges"][telemetry.ENGINE_DECODE_VIEW_BYTES] == table


def test_the_engine_samples_what_it_sampled_from_the_stacked_tree(monkeypatch):
    """The same round through the view and, with the view switched off,
    through the stacked tree: the same tokens and log-probabilities."""
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    params, lora = weights(PRESETS["tiny"], True)
    build = lambda: PagedGenerationEngine(
        PRESETS["tiny"], max_prompt_tokens=16, max_new_tokens=4, eos_token_ids=[-1],
        pad_token_id=0, lora_scale=2.0, autotune=False, page_size=8, capture_logprobs=True)
    got = one_round(build(), params, lora)
    monkeypatch.setattr(PagedGenerationEngine, "_decode_params", lambda self, params: params)
    want = one_round(build(), params, lora)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_allclose(got.logprobs, want.logprobs, rtol=1e-5, atol=1e-5)


def builds() -> int:
    return telemetry.observe_snapshot()["counters"].get(telemetry.ENGINE_DECODE_VIEW_BUILDS, 0)


def test_a_base_the_device_has_no_room_to_hold_twice_is_read_stacked(monkeypatch, caplog):
    """A device whose memory is nearly full: no view, a warning once a base,
    the gauge at 0, and the round samples from the stacked tree as before."""
    from distrl_llm_tpu.engine import engine as engine_mod

    params, lora = weights(PRESETS["tiny"], True)
    want = one_round(make_engine("paged"), params, lora)
    table = held_twice(params)
    limit = 1 << 30
    in_use = int(limit * (1 - engine_mod.ACTIVATION_RESERVE)) - table + 1
    fake = lambda used: monkeypatch.setenv(  # obs.hbm_stats reads it
        "DISTRL_OBS_FAKE_HBM", json.dumps({"bytes_limit": limit, "bytes_in_use": used}))
    fake(in_use)
    engine, before = make_engine("paged"), builds()
    with caplog.at_level("WARNING", logger=engine_mod.__name__):
        got = one_round(engine, params, lora)
        one_round(engine, params, lora, seed=1)
    assert len([r for r in caplog.records if "no decode view" in r.getMessage()]) == 1
    assert engine._view_slot[1] is None and builds() == before
    assert telemetry.observe_snapshot()["gauges"][telemetry.ENGINE_DECODE_VIEW_BYTES] == 0
    np.testing.assert_array_equal(got.tokens, want.tokens)
    fake(in_use - 1)  # one byte more room: the same base, a new engine, fits
    engine = make_engine("paged")
    one_round(engine, params, lora)
    assert engine._view_slot[1] is not None and builds() == before + 1


def test_new_leaves_rebuild_the_view_and_the_old_one_is_unreachable():
    """A new base (full fine-tuning, a checkpoint load) is other arrays: the
    view is rebuilt from them, and nothing keeps the old view or the old base
    alive. A tree that differs in ONE leaf outside the table is a new base too."""
    cfg = PRESETS["tiny"]
    params, _ = weights(cfg, False)
    engine = make_engine("paged")
    one_round(engine, params, None)
    old = weakref.ref(engine._view_slot[1]["layers"]["wq"][0].w)
    old_base = weakref.ref(params["layers"]["wq"])
    before = builds()
    assert engine._decode_params(params) is engine._view_slot[1]
    assert builds() == before
    other = {**params, "embed": params["embed"] + 0}
    view = engine._decode_params(other)
    assert view["embed"] is other["embed"]
    assert builds() == before + 1
    params = jax.tree_util.tree_map(lambda x: x + 0, params)
    del other, view
    one_round(engine, params, None)
    assert builds() == before + 2
    gc.collect()
    assert old() is None and old_base() is None
    # the slot does not keep the stacked leaves of a base its caller dropped
    dropped = weakref.ref(params["layers"]["wq"])
    del params
    gc.collect()
    assert dropped() is None

