"""The grouped form of ``models/moe.py::routed_experts`` runs the blocks of
the pairs held HERE and no others. The form it replaced is kept below as the
plain reference (every block through an expert's matrices, the extra group's
clamped to the last expert held, then the ``where``): the forward's outputs
equal it bit for bit, the gradients agree to 1e-5, both agree with the dense
form, and the counters say how many blocks ran of how many were laid. Float32
at tiny widths on the CPU.

And the rule that chooses between the two forms (``moe.expert_form``): what it
says for every call of the benchmark's seven expert cells, read off their
files, and that what it chooses loses no pair where few experts are held.
"""

import functools
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

from distrl_llm_tpu.models import moe  # noqa: E402
from family_suite import expert_forms  # noqa: E402

D, F = 32, 48
#: (experts the router scores, the experts held or None for all, tokens, choices
#: a token, rows of a block)
CASES = {
    "16_of_128": (128, tuple(range(32, 48)), 512, 8, 256),
    "2_of_8": (8, (5, 6), 320, 2, 256),
    "all_held": (8, None, 320, 2, 128),
}


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def every_block(h, idx, w, experts, *, n_experts, held=None, bm=64):
    """The grouped form as it stood before this file, in blocks of ``bm`` rows:
    every block laid out is gathered ahead of the scan and multiplied, the
    pairs held elsewhere by the last expert held, and their rows zeroed
    afterwards."""
    t, k = idx.shape
    n = experts["gate"].shape[-3]
    local = moe._local_ids(idx, n, n_experts, held)
    load = jnp.zeros((n + 1,), jnp.int32).at[local.reshape(-1)].add(1)[:n]
    groups = n + (held is not None)
    rows_a = t * k
    blocks = rows_a // bm + groups
    flat = local.reshape(rows_a)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1)
    padded = -(-sizes // bm) * bm
    ends = jnp.cumsum(padded)
    rank = jnp.zeros((rows_a,), jnp.int32).at[order].set(
        jnp.arange(rows_a, dtype=jnp.int32) - (jnp.cumsum(sizes) - sizes)[flat[order]])
    at = (ends - padded)[flat] + rank
    src = jnp.full((blocks * bm,), t, jnp.int32).at[at].set(
        jnp.arange(rows_a, dtype=jnp.int32) // k)
    rows = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])[src]
    owner = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(blocks) * bm, side="right"), n - 1)
    stacks = [experts[name] for name in ("gate", "up", "down")]

    @jax.checkpoint
    def one(_, block):
        x, e = block
        return None, moe._gated(x, *(stack[e] for stack in stacks))

    _, y = jax.lax.scan(one, None, (rows.reshape(blocks, bm, -1), owner))
    y = y.reshape(blocks * bm, -1)[at].reshape(t, k, -1)
    y = jnp.where((local < n)[..., None], y, 0)
    return jnp.einsum("tk,tkd->td", w, y.astype(jnp.float32)).astype(h.dtype), load


def drawn(case: str):
    """Tokens, the router's choice (k distinct experts a token, uneven over the
    experts) and the stacks of the experts held."""
    n_experts, held, t, k, _ = CASES[case]
    n = n_experts if held is None else len(held)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    h = jax.random.normal(keys[0], (t, D))
    lean = 0.8 * jax.random.normal(keys[1], (n_experts,))  # some experts drawn more often
    _, idx = jax.lax.top_k(jax.random.normal(keys[2], (t, n_experts)) + lean, k)
    w = jax.random.uniform(keys[3], (t, k), minval=0.1, maxval=1.0)
    experts = {
        "gate": 0.2 * jax.random.normal(keys[4], (n, D, F)),
        "up": 0.2 * jax.random.normal(keys[5], (n, D, F)),
        "down": 0.2 * jax.random.normal(keys[0], (n, F, D)),
    }
    return h, idx.astype(jnp.int32), w, experts, {"n_experts": n_experts, "held": held}


def blocks_by_hand(idx, n_experts: int, held, bm: int) -> tuple[int, int]:
    """(blocks that hold a pair of an expert held, blocks laid) from the choice
    alone, in numpy."""
    ids = np.asarray(idx).reshape(-1)
    held = range(n_experts) if held is None else held
    run = sum(-(-int((ids == e).sum()) // bm) for e in held)
    return run, ids.size // bm + len(held) + (len(held) < n_experts)


@pytest.mark.parametrize("mode", ["forward", "grad"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_blocks_held_here_alone_give_what_every_block_gave(case, mode, monkeypatch):
    h, idx, w, experts, told = drawn(case)
    bm = CASES[case][-1]
    monkeypatch.setattr(moe, "expert_form", expert_forms(0, bm))  # the grouped form
    if mode == "forward":
        got, load, blocks = jax.jit(lambda *a: moe.routed_experts(*a, **told))(
            h, idx, w, experts)
        want, want_load = jax.jit(lambda *a: every_block(*a, **told, bm=bm))(
            h, idx, w, experts)
        np.testing.assert_array_equal(got, want)  # bit for bit
        np.testing.assert_array_equal(load, want_load)
        assert float(jnp.abs(want).max()) > 0.1
        run, laid = blocks_by_hand(idx, told["n_experts"], told["held"], bm)
        assert tuple(map(int, blocks)) == (run, laid) and run < laid
        if told["held"] is not None:  # most blocks belonged to another chip's experts
            assert run < laid / 2
        # and the dense form, which lays no block
        monkeypatch.setattr(moe, "expert_form", expert_forms(h.shape[0]))
        dense, dense_load, none = moe.routed_experts(h, idx, w, experts, **told)
        np.testing.assert_allclose(got, dense, atol=2e-5)
        np.testing.assert_array_equal(load, dense_load)
        assert tuple(map(int, none)) == (0, 0)
        return
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)

    def loss(form, **kw):
        return lambda h, experts: jnp.vdot(form(h, idx, w, experts, **told, **kw)[0], probe)

    got = jax.jit(jax.grad(loss(moe.routed_experts), argnums=(0, 1)))(h, experts)
    want = jax.jit(jax.grad(loss(every_block, bm=bm), argnums=(0, 1)))(h, experts)
    monkeypatch.setattr(moe, "expert_form", expert_forms(h.shape[0]))
    dense = jax.grad(loss(moe.routed_experts), argnums=(0, 1))(h, experts)
    for a, b, c in zip(*map(jax.tree_util.tree_leaves, (got, want, dense))):
        assert float(jnp.abs(b).max()) > 0.1
        np.testing.assert_allclose(a, b, atol=1e-5)
        np.testing.assert_allclose(a, c, atol=2e-5)


@pytest.mark.parametrize("sizes", [
    (0, 0, 0, 0),        # nothing held here is chosen: no block runs
    (1, 0, 0, 0),        # one pair: one block
    (64, 65, 0, 128),    # whole blocks, one over, none, two whole
    (300, 0, 0, 0),      # every pair at one expert: five blocks of 64
], ids=str)
def test_the_counters_count_the_blocks_of_the_groups_held(sizes, monkeypatch):
    """Hand-made choices, one a token: ``sizes[e]`` pairs at held expert ``e``
    and the rest at an expert held elsewhere. The blocks that run are the held
    groups' rounded up to whole blocks, the blocks laid ``pairs // bm + groups``."""
    t, held = 300, (1, 3, 4, 6)
    ids = np.full((t,), 7, np.int32)  # expert 7: elsewhere
    at = 0
    for e, size in zip(held, sizes):
        ids[at: at + size] = e
        at += size
    idx = jnp.asarray(np.random.default_rng(0).permutation(ids)[:, None])
    h = jax.random.normal(jax.random.PRNGKey(0), (t, D))
    experts = {name: 0.2 * jax.random.normal(jax.random.PRNGKey(i), shape)
               for i, (name, shape) in enumerate(
                   (("gate", (4, D, F)), ("up", (4, D, F)), ("down", (4, F, D))))}
    w = jnp.ones((t, 1))
    monkeypatch.setattr(moe, "expert_form", expert_forms(0, 64))
    y, load, blocks = moe.routed_experts(h, idx, w, experts, n_experts=8, held=held)
    assert tuple(map(int, load)) == sizes
    assert tuple(map(int, blocks)) == (sum(-(-s // 64) for s in sizes), 300 // 64 + 5)
    want, _ = every_block(h, idx, w, experts, n_experts=8, held=held)
    np.testing.assert_array_equal(y, want)
    # through ``moe_half`` the two follow the layer's pairs and the fullest expert's
    cfg = SimpleNamespace(router_width=8)
    p = {f"experts_{name}": x for name, x in experts.items()}
    _, stats = moe.moe_half(h, p, cfg, held=held, choice=(idx, w))
    assert tuple(map(int, stats)) == (sum(sizes), max(sizes), *map(int, blocks))


@pytest.mark.parametrize("scheduler,slots", [("waves", 0), ("refill", 4)])
def test_a_round_files_the_blocks_its_prefill_ran_and_laid(scheduler, slots, monkeypatch):
    """Through the engine at a tiny size (``tiny-exaone-moe``: 2 of 16 experts
    held, 4 a token, four expert layers): two prompts of 40 and 57 tokens in
    segments of 16, so a call lays ``2 x 16 x 4 // 64 + 3 = 5`` blocks, of which
    the two held experts' run (one each at most: a segment brings an expert 32
    pairs at most); the decode steps' 8 rows are dense and lay none. Both
    schedulers hand the prefill's count to the decode state, which files it."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.models import init_lora_params, init_params
    from distrl_llm_tpu.models.configs import PRESETS

    cfg = PRESETS["tiny-exaone-moe"]
    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
    monkeypatch.setattr(moe, "expert_form", expert_forms(8))
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=4)
    rng = np.random.default_rng(0)
    ids, mask = np.zeros((2, 64), np.int32), np.zeros((2, 64), np.int32)
    for row, length in enumerate((40, 57)):
        ids[row, 64 - length:] = rng.integers(1, 256, length)
        mask[row, 64 - length:] = 1
    engine = paged_engine.PagedGenerationEngine(
        cfg, max_prompt_tokens=64, max_new_tokens=6, eos_token_ids=[-1],
        pad_token_id=0, lora_scale=2.0, scheduler=scheduler, max_concurrent_rows=slots,
        cache_dtype=jnp.float32, page_size=8, autotune=False)
    names = (telemetry.ENGINE_MOE_BLOCKS_RUN, telemetry.ENGINE_MOE_BLOCKS_LAID)
    assert names == ("engine/moe_blocks_run", "engine/moe_blocks_laid")
    before = dict(telemetry.observe_snapshot()["counters"])
    result = engine.generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=6),
        jax.random.PRNGKey(3))
    assert (result.lengths == 6).all()
    after = telemetry.observe_snapshot()["counters"]
    run, laid = (after[name] - before.get(name, 0) for name in names)
    calls = 4 * 4  # expert layers x the longest prompt's segments
    assert laid == calls * 5 and 0 < run <= calls * 2


# ------------------------------------------------------------------- the rule


#: the benchmark's seven expert cells: the form of a decode step and the block
#: of each prefill call (a stage's rows x a segment of 1,024 tokens), as the
#: rule has to choose them
CELLS = {
    "kimi-vl-a3b-L7.rollout-longctx-latent": (0, (256, 256)),
    "solar-open2-250b-ep8-L4.rollout-reasoning": (0, (256,)),
    "k-exaone-236b-ep8-L5.rollout-longctx-window": (0, (256, 256)),
    "glm-5-ep16-L5.rollout-longctx-indexed": (0, (256, 128)),
    "zaya1-8b-L20.rollout-reasoning-cca": (0, (256,)),
    "mimo-v2-flash-ep16-L7.rollout-longctx-sink-128": (0, (256, 256)),
    "longcat-flash-ep32-L4.rollout-reasoning-zero-256": (0, (128,)),
}
#: the calls to which ``todays`` (the thresholds the rule replaced) gave
#: something else, and what: every other call's program is the parent's. A
#: held expert's 64 rows went in blocks of 256 (the mean over ALL the call's
#: pairs), and 256 tokens x 12 choices over 16 of 768, 4 pairs a held expert,
#: ran grouped: 16 blocks of 256 rows of padding (PERF.md section 6, PR 66)
MOVED = {
    ("glm-5-ep16-L5.rollout-longctx-indexed", "prefill-2"): 256,
    ("longcat-flash-ep32-L4.rollout-reasoning-zero-256", "prefill-16"): 256,
    ("longcat-flash-ep32-L4.rollout-reasoning-zero-256", "decode"): 256,
}


def todays(t: int, k: int, groups: int) -> int:
    """The parent's choice (``DENSE_MAX_TOKENS`` 128, ``DENSE_MAX_TOKENS_TOP1``
    192, ``block_rows``' mean over ALL the call's pairs), kept to compare with."""
    if t <= (192 if k == 1 else 128):
        return 0
    mean = max(t * k // groups, 1)
    return min(256, max(64, 1 << (mean - 1).bit_length()))


@functools.cache
def _calls(cell: str) -> tuple:
    """``(what, t, k, router width, groups)`` of every call of the routed
    experts in one round of ``cell``, read off its configuration and traffic
    files as the engine reads them: a decode step of every slot's row, and a
    prefill segment of each stage's rows, in ``moe.grouped_runs``' equal runs.
    (The experts' two widths are in the files too and the rule takes neither:
    an expert's bytes hide the same rows of its arithmetic whatever they are.)"""
    from distrl_llm_tpu.engine import paged_engine
    from perfbench import assembly, spec

    loaded = spec.load_cell(spec.load_json(os.path.join(REPO, "BENCHMARK.json")), cell)
    cfg, train = assembly.model_config(loaded.config), loaded.traffic["train_config"]
    k, width = cfg.experts_per_token, cfg.router_width
    groups = cfg.n_routed_experts + (cfg.held_experts is not None)
    rows = min(train["max_concurrent_sequences"], train["batch_size"] * train["num_candidates"])
    page = paged_engine.DEFAULT_PAGE_SIZE
    segment, segments = paged_engine._hybrid_segments(train["max_prompt_tokens"] // page, page)
    stages = paged_engine._stage_sizes(train["batch_size"], segments)
    return (("decode", rows, k, width, groups), *(
        (f"prefill-{stage}", stage * segment // moe.grouped_runs(stage * segment, k),
         k, width, groups) for stage in stages))


@pytest.mark.parametrize("cell,call", [
    (cell, call) for cell, (_, blocks) in CELLS.items() for call in range(1 + len(blocks))],
    ids=lambda x: str(x).split(".")[0])
def test_the_rule_gives_every_cells_calls_their_form_and_block(cell, call):
    decode, blocks = CELLS[cell]
    what, t, k, width, groups = _calls(cell)[call]
    want = (decode, *blocks)[call]
    assert (what == "decode") == (call == 0) and len(_calls(cell)) == 1 + len(blocks)
    assert moe.expert_form(t, k, width) == want, (what, t, k, width)
    assert todays(t, k, groups) == MOVED.get((cell, what), want), (what, t, k, groups)


@pytest.mark.parametrize("tokens", [256, 2048])
@pytest.mark.parametrize("case", ["some_get_none", "all_on_one"])
def test_the_chosen_form_computes_every_pair_where_few_experts_are_held(case, tokens):
    """The newest cell's kind of call at a small size: many tokens, 3 choices
    over 96 outputs of which 4 are held, so a held expert is given few pairs
    (8 of 256 tokens: the rule says dense; 64 of 2,048: grouped). Drawn so that
    two of the held experts get NO pair, and so that every pair held here
    lands on ONE expert (a block's worth and more): the form the rule chooses
    equals every pair computed a block at a time, and counts them all."""
    held, n_experts, k = (40, 41, 42, 43), 96, 3
    rng = np.random.default_rng(tokens)
    elsewhere = np.setdiff1d(np.arange(n_experts), held)
    idx = np.stack([rng.permutation(elsewhere)[:k] for _ in range(tokens)])
    if case == "some_get_none":  # a twelfth of the tokens choose 40 or 43, none 41 or 42
        chose = rng.permutation(tokens)[: tokens // 12]
        idx[chose, rng.integers(0, k, chose.size)] = rng.choice([40, 43], chose.size)
        loads = [int((idx == e).sum()) for e in held]
        assert loads[1] == loads[2] == 0 < min(loads[0], loads[3])
    else:  # every token chooses 42 beside two experts held elsewhere
        idx[:, 1] = 42
        loads = [0, 0, tokens, 0]
    keys = jax.random.split(jax.random.PRNGKey(tokens), 5)
    h = jax.random.normal(keys[0], (tokens, D))
    w = jax.random.uniform(keys[1], (tokens, k), minval=0.1, maxval=1.0)
    experts = {name: 0.2 * jax.random.normal(key, (4, *shape)) for name, key, shape in (
        ("gate", keys[2], (D, F)), ("up", keys[3], (D, F)), ("down", keys[4], (F, D)))}
    told = {"n_experts": n_experts, "held": held}
    idx = jnp.asarray(idx, jnp.int32)
    bm = moe.expert_form(tokens, k, n_experts)
    assert (bm == 0) == (tokens == 256)
    y, load, blocks = jax.jit(lambda *a: moe.routed_experts(*a, **told))(h, idx, w, experts)
    want, want_load = jax.jit(lambda *a: every_block(*a, **told, bm=bm or 64))(
        h, idx, w, experts)
    assert load.tolist() == want_load.tolist() == loads  # every pair, none dropped
    assert float(jnp.abs(want).max()) > 0.1
    if bm:
        np.testing.assert_array_equal(y, want)  # the same blocks: bit for bit
        assert tuple(map(int, blocks)) == (
            sum(-(-size // bm) for size in loads), tokens * k // bm + 5)
    else:
        np.testing.assert_allclose(y, want, atol=2e-5)
        assert tuple(map(int, blocks)) == (0, 0)
