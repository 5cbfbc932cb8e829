"""A hybrid model's prefill in STAGES of a shrinking batch
(``engine/paged_engine.py::_paged_prefill_hybrid``) against a plain reference
written here: each row ALONE, a batch of one, through exactly its own segments.
Six families' tiny configurations, float32 on the CPU, 2e-5: the pages that
hold a row's tokens, every row state, the counters and the logits, for mixes
of lengths that put rows in every stage of the ladder. Also the ladder's rule
as a table, the trips it gives, one program for every mix, and the gauge
``engine/prefill_real_share`` on both schedulers.
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import family_suite  # noqa: E402
from distrl_llm_tpu import telemetry  # noqa: E402
from distrl_llm_tpu.config import SamplingConfig  # noqa: E402
from distrl_llm_tpu.engine import paged_engine  # noqa: E402
from distrl_llm_tpu.models import forward, init_lora_params, init_params  # noqa: E402
from distrl_llm_tpu.models import hybrid, moe  # noqa: E402
from distrl_llm_tpu.models.configs import PRESETS  # noqa: E402
from distrl_llm_tpu.ops import power_retention, selective_scan  # noqa: E402

#: the families' tiny configurations (``tests/family_suite.py``); MiniCPM-SALA's at
#: four layers: sparse layers at both ends, lightning layers between
TINY = {fam.name: fam.cfg for fam in family_suite.families()}
FAMILIES = {
    "sala": dataclasses.replace(
        TINY["sala"], num_layers=4,
        mixer_types=("minicpm4",) + ("lightning-attn",) * 2 + ("minicpm4",)),
    **{name: TINY[name] for name in ("latent-moe", "delta-moe", "power", "jamba", "window-moe")},
}
WIDTH, PAGE, SEGMENT, NEW_TOKENS, LORA_SCALE = 64, 8, 16, 16, 2.0
PAGES = WIDTH // PAGE
#: lengths a row: every stage of the ladders of 1, 2, 3 and 5 rows gets rows
MIXES = {
    "all_equal": (48, 48, 48),
    "all_different_unsorted": (17, 64, 33, 9, 50),
    "one_ends_mid_segment": (40, 64, 16),
    "one_single_token": (1, 64, 30),
    "one_empty_row": (0, 37, 64),
    "one_row": (41,),
    "two_rows_are_one_stage": (33, 64),
    "every_row_at_full_width": (64, 64, 64, 64, 64),
}


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def small_pieces(monkeypatch):
    """Segments of 16 tokens in pages of 8 and every inner piece as small, so
    that 64-token prompts cross every boundary the cells' 20k-token ones do."""
    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", SEGMENT)
    # a segment's tokens grouped
    monkeypatch.setattr(moe, "expert_form", family_suite.expert_forms(8))
    monkeypatch.setattr(power_retention, "DEFAULT_CHUNK", SEGMENT)
    monkeypatch.setattr(selective_scan, "DEFAULT_CHUNK", SEGMENT)


@functools.lru_cache(maxsize=None)
def weights(family: str):
    """Seeded float32 weights with every term alive: norms off 1, an adapter
    whose b is not zero."""
    cfg = FAMILIES[family]

    def base(path, x):
        if str(path[-1].key).endswith("norm"):
            key = jax.random.PRNGKey(sum(map(ord, str(path))) % 9973)
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        return x

    params = jax.tree_util.tree_map_with_path(base, init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x,
        init_lora_params(jax.random.PRNGKey(1), cfg, 4))
    return params, lora


def prompts(lengths, seed=0):
    return family_suite.prompts(lengths, WIDTH, seed)


def prefill_of(family: str):
    """The engine's prefill of a family at this file's sizes, not yet jitted."""
    return functools.partial(
        paged_engine._paged_prefill_hybrid, cfg=FAMILIES[family], prompt_pages=PAGES,
        page_size=PAGE, lora_scale=LORA_SCALE, cache_dtype=jnp.float32,
        attn_impl="reference", total_tokens=WIDTH + NEW_TOKENS)


@functools.lru_cache(maxsize=None)
def staged(family: str):
    """One ``jax.jit`` of a family's prefill for every batch the cases bring."""
    return jax.jit(prefill_of(family))


# ------------------------------------------------------- the plain reference


@functools.lru_cache(maxsize=None)
def one_segment(family: str):
    """A row's segment at ``start`` through every layer: (the last real
    token's hidden state if it lies here, the row's cache after it)."""
    cfg = FAMILIES[family]

    def run(params, lora, cache, ids, mask, real, start):
        x, out = forward(
            params, cfg, ids, attention_mask=mask, lora=lora, lora_scale=LORA_SCALE,
            attn_impl="reference", page_size=PAGE, skip_lm_head=True,
            kv_cache={**cache, "lengths": real, "segment_start": start,
                      "page_indices": jnp.arange(PAGES, dtype=jnp.int32)[None]},
            logits_positions=jnp.clip(real - 1 - start, 0, SEGMENT - 1))
        return x[:, 0], {name: out[name] for name in cache}

    return jax.jit(run)


def row_alone(family: str, row_ids: np.ndarray, n: int):
    """One prompt of ``n`` tokens as a batch of one with pages of its own, run
    through its ``ceil(n / SEGMENT)`` segments and no other: (pools, row
    states, logits). An empty row runs nothing and keeps what it started with."""
    cfg = FAMILIES[family]
    params, lora = weights(family)
    ids = np.zeros((1, WIDTH), np.int32)
    ids[0, :n] = row_ids[WIDTH - n:]
    mask = (np.arange(WIDTH)[None] < n).astype(np.int32)
    state = hybrid.init_mixer_state(cfg, 1, WIDTH + NEW_TOKENS, jnp.float32)
    pool = lambda: tuple(jnp.zeros(cfg.page_pool_shape(PAGES, PAGE), jnp.float32)
                         for _ in range(cfg.paged_layers))
    cache = {"k": pool(), "v": () if cfg.latent else pool(),
             **paged_engine._row_states(state)}
    hidden = jnp.zeros((1, cfg.hidden_size), jnp.float32)
    for start in range(0, n, SEGMENT):
        x, cache = one_segment(family)(
            params, lora, cache, ids[:, start: start + SEGMENT],
            mask[:, start: start + SEGMENT], jnp.asarray([n], jnp.int32),
            jnp.asarray(start, jnp.int32))
        if start <= n - 1 < start + SEGMENT:
            hidden = x
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    pools = {"k": cache.pop("k"), "v": cache.pop("v")}
    return pools, cache, np.asarray(hidden @ head)[0]


def pages_of(cfg, pool, row: int, held: int):
    """The first ``held`` pages of ``row``'s ``PAGES`` in a pool of any layout."""
    at = slice(row * PAGES, row * PAGES + held)
    return np.asarray(pool[at] if cfg.latent else pool[:, at])


def close(got, want, what):
    """Equal to 2e-5 of the array's largest value (a state sums 64 tokens)."""
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1.0, float(np.abs(want).max())) if want.size else 1.0
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0, err_msg=what)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("family", FAMILIES)
def test_the_staged_prefill_is_each_row_alone(family, mix):
    cfg, lengths = FAMILIES[family], MIXES[mix]
    ids, mask = prompts(lengths)
    k, v, logits, real_len, mixer = staged(family)(*weights(family), ids, mask)
    assert np.asarray(real_len).tolist() == list(lengths)
    fresh = hybrid.init_mixer_state(cfg, len(lengths), WIDTH + NEW_TOKENS, jnp.float32)
    assert set(mixer) == set(fresh)
    for row, n in enumerate(lengths):
        pools, states, want_logits = row_alone(family, ids[row], n)
        # the pages of the row's own segments (what lies after them is nobody's:
        # a row that rides on in a stage with a longer one writes pad tokens there)
        held = -(-n // SEGMENT) * SEGMENT // PAGE
        for name, got in (("k", k), ("v", v)):
            assert len(got) == len(pools[name])
            for layer, (mine, alone) in enumerate(zip(got, pools[name])):
                close(pages_of(cfg, mine, row, held), pages_of(cfg, alone, 0, held),
                      f"{name} pages, layer {layer}, row {row}")
        for name, alone in states.items():
            assert len(mixer[name]) == len(alone)
            for layer, (mine, want) in enumerate(zip(mixer[name], alone)):
                mine, want = np.asarray(mine[row]), np.asarray(want[0])
                if name == "pooled":  # pooled keys over the row's own segments
                    windows = (held * PAGE - cfg.sparse_kernel_size) // cfg.sparse_kernel_stride + 1
                    mine, want = mine[:max(windows, 0)], want[:max(windows, 0)]
                close(mine, want, f"{name}, layer {layer}, row {row}")
        close(logits[row], want_logits, f"logits, row {row}")
    # the round's counters pass through whole, but the one a prefill adds to:
    # the blocks its segments' experts ran, of those they laid
    for name in set(fresh) - set(hybrid.ROW_STATES) - set(paged_engine.PREFILL_COUNTERS):
        np.testing.assert_array_equal(np.asarray(mixer[name]), np.asarray(fresh[name]))
    if "moe_blocks" in fresh:
        run, laid = map(int, mixer["moe_blocks"])
        assert 0 < run <= laid


@pytest.mark.parametrize("family", FAMILIES)
def test_two_mixes_of_lengths_build_one_program(family):
    prefill = jax.jit(prefill_of(family))
    first = prefill(*weights(family), *prompts((64, 20, 41)))
    second = prefill(*weights(family), *prompts((7, 33, 33), seed=1))
    assert prefill._cache_size() == 1
    assert np.asarray(first[3]).tolist() == [64, 20, 41]
    assert np.asarray(second[3]).tolist() == [7, 33, 33]


# --------------------------------------------------------------- the ladder


@pytest.mark.parametrize("b,sizes", [
    (1, (1,)), (2, (2,)), (3, (3, 2)), (4, (4, 2)), (5, (5, 2)), (8, (8, 4)),
    (16, (16, 8)), (30, (30, 15)),
])
def test_the_ladders_rule(b, sizes):
    """b rows, then half of them rounded down and never under two, where the
    prompt has four segments or more; one stage for shorter prompts and for
    one or two rows (PERF.md §6, PR 52, says what a body costs and why no
    stage holds a single row)."""
    assert paged_engine._stage_sizes(b, 20) == paged_engine._stage_sizes(b, 4) == sizes
    for segments in (1, 2, 3):
        assert paged_engine._stage_sizes(b, segments) == (b,)


@pytest.mark.parametrize("b", range(1, 65))
def test_a_ladder_is_short_falls_and_holds_no_single_row(b):
    for segments in (1, 2, 3, 4, 16, 160):
        sizes = paged_engine._stage_sizes(b, segments)
        assert sizes[0] == b
        assert len(sizes) <= paged_engine.HYBRID_PREFILL_STAGES <= 4  # ISSUE 52's cap
        assert all(a > c for a, c in zip(sizes, sizes[1:]))
        assert min(sizes) >= min(b, 2)


@pytest.mark.parametrize("segments,sizes,trips", [
    # the long-context cells' rows: 4 x 14, then 2 x 6 = 68 of 80
    ((20, 17, 14, 10), (4, 2), [(0, 14), (14, 20)]),
    ((20, 17, 14, 10), (4, 3, 2), [(0, 10), (10, 14), (14, 20)]),  # a finer ladder
    ((16, 8), (2,), [(0, 16)]),  # one stage: both rows to the longer one's end
    ((5, 5, 5), (3, 2), [(0, 5), (5, 5)]),  # equal rows: the second stage has no trips
    ((2, 2, 2, 1, 1, 1, 0, 0), (8, 6, 4, 2), [(0, 0), (0, 1), (1, 2), (2, 2)]),
    ((0, 0), (2,), [(0, 0)]),  # nothing to run
])
def test_a_stage_runs_to_the_end_of_the_longest_row_the_next_one_drops(
        segments, sizes, trips):
    assert paged_engine._stage_trips(np.asarray(segments), sizes) == trips
    traced = jax.jit(lambda s: paged_engine._stage_trips(s, sizes))(jnp.asarray(segments))
    assert [(int(a), int(b)) for a, b in traced] == trips


def test_the_body_count_is_the_ladders():
    """One while loop a stage in the traced program, whatever the lengths."""
    traced = jax.make_jaxpr(prefill_of("power"))(*weights("power"), *prompts((64, 30, 9, 1)))
    stages = [e for e in traced.jaxpr.eqns if e.primitive.name == "while"]
    assert len(stages) == len(paged_engine._stage_sizes(4, 4)) == 2


# ---------------------------------------------------------------- the gauge


@pytest.mark.parametrize("lengths,share,longest", [
    ((10240, 10240, 10240, 10240), 100.0, 10),
    # 4 x 14 + 2 x 6 = 68 row-segments of 1,024 for 61,440 tokens
    ((13653, 20480, 10240, 17067), 100.0 * 61440 / 69632, 20),
    ((8192, 16384), 100.0 * 24576 / 32768, 16),  # two rows are one stage
    ((0, 0), 0.0, 0),
])
def test_the_share_is_host_arithmetic_over_the_lengths(monkeypatch, lengths, share, longest):
    filed = []
    monkeypatch.setattr(telemetry, "gauge_set", lambda name, value: filed.append((name, value)))
    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 1024)
    with jax.transfer_guard("disallow"):  # numpy in, a float out: nothing fetched
        assert paged_engine._file_prefill_share(np.asarray(lengths), 160, 128) == longest
    assert filed == [("engine/prefill_real_share", pytest.approx(share))]


@pytest.mark.parametrize("scheduler,slots", [("refill", 6), ("waves", 0)])
@pytest.mark.parametrize("lengths,share", [
    ((48, 48, 48), 100.0),
    # rows of 4, 2 and 2 segments: 3 x 2, then 2 x 2 = 10 of 16 tokens for 111
    ((30, 64, 17), 100.0 * 111 / 160),
])
def test_both_schedulers_file_the_share_once_a_round(
        monkeypatch, scheduler, slots, lengths, share):
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    engine = PagedGenerationEngine(
        FAMILIES["power"], max_prompt_tokens=WIDTH, max_new_tokens=NEW_TOKENS,
        eos_token_ids=[-1], pad_token_id=0, lora_scale=LORA_SCALE, scheduler=scheduler,
        max_concurrent_rows=slots, cache_dtype=jnp.float32, autotune=False, page_size=PAGE)
    filed = []
    gauge_set = telemetry.gauge_set
    monkeypatch.setattr(telemetry, "gauge_set", lambda name, value: (
        filed.append((name, value)), gauge_set(name, value))[1])
    for _ in range(2):
        engine.generate(*weights("power"), *prompts(lengths),
                        SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=4),
                        jax.random.PRNGKey(3))
    mine = [value for name, value in filed if name == "engine/prefill_real_share"]
    assert mine == [pytest.approx(share)] * 2
    assert telemetry.observe_snapshot()["gauges"]["engine/prefill_real_share"] == (
        pytest.approx(share))


def test_a_dense_model_files_no_share(monkeypatch):
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine

    cfg = PRESETS["tiny"]
    engine = PagedGenerationEngine(
        cfg, max_prompt_tokens=32, max_new_tokens=4, eos_token_ids=[-1], pad_token_id=0,
        scheduler="waves", max_concurrent_rows=0, cache_dtype=jnp.float32, autotune=False)
    filed = []
    monkeypatch.setattr(telemetry, "gauge_set", lambda name, value: filed.append(name))
    ids = np.zeros((2, 32), np.int32)
    ids[:, 20:] = 5
    engine.generate(init_params(jax.random.PRNGKey(0), cfg), None, ids, (ids > 0).astype(np.int32),
                    SamplingConfig(temperature=1.0, top_p=1.0, n=1, max_tokens=2),
                    jax.random.PRNGKey(0))
    assert "engine/prefill_real_share" not in filed


@pytest.mark.parametrize("longest,folds", [(4, 10), (3, 6), (1, 1), (0, 0), (None, 10)])
def test_the_folds_filed_are_those_the_stages_ran(monkeypatch, longest, folds):
    """The stages end with the longest row: segment j of it folds j + 1 blocks
    in each latent layer, one kernel launch a fold whatever the stage's batch
    (a prompt of 64 tokens in segments of 16: at most 1 + 2 + 3 + 4)."""
    from distrl_llm_tpu.ops import latent_attention as la

    cfg = FAMILIES["latent-moe"]
    monkeypatch.setattr(la, "dispatch_choices", {la.dispatch_key(
        cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        SEGMENT, jnp.float32): "kernel"})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_fold_telemetry(cfg, PAGES, PAGE, jnp.float32, longest)
    assert filed == [("ops/latent_kernel_folds", cfg.num_layers * folds)]


# ------------------------------------------------- the benchmark's new metric


def test_the_new_metric_is_a_data_file_for_the_reader_the_benchmark_has():
    from perfbench import spec

    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "engine.prefill_real_share"]
    held = spec.load_layer_metric(bench["paths"], "engine.prefill_real_share")
    assert entry == {
        "name": "engine.prefill_real_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine", "moves": "rollout_tok_s",
        # the cells whose layers differ in kind: the dense path's two
        # configurations have no staged prefill
        "workloads": [w["name"] for w in bench["workloads"]
                      if w["config"] not in ("qwen2.5-7b-L14", "ouro-2.6b-L8")]}
    assert {key: held[key] for key in ("layer", "unit", "better", "source", "moves")} == {
        key: entry[key] for key in ("layer", "unit", "better", "source", "moves")}
    assert held["reader"] == "program_gauge"
    assert held["args"] == {"name": paged_engine.ENGINE_PREFILL_REAL_SHARE, "scale": 1.0}
    reader = spec.load_module(bench["paths"], "readers", held["reader"])
    assert callable(reader.read)


def test_a_program_without_the_gauge_reads_none(monkeypatch):
    """The parent's run of the metric: its registry holds no such gauge, the
    reader returns None and the line leaves the metric out."""
    from perfbench import spec

    held = spec.load_layer_metric(["perfbench"], "engine.prefill_real_share")
    reader = spec.load_module(["perfbench"], "readers", held["reader"])
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"gauges": {
        "engine/slot_state_bytes": 1.0}, "counters": {}})
    assert reader.read({}, held["args"], object()) is None
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: {"gauges": {
        "engine/prefill_real_share": 93.75}, "counters": {}})
    assert reader.read({}, held["args"], object()) == 93.75
    assert reader.read({}, held["args"], None) is None  # no run, no number
