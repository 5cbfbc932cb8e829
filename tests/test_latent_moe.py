"""A latent-attention model with routed experts (Kimi-VL-A3B's language model,
``deepseek_v3``) against its plain reference, ``perfbench/reference_latent_moe.py``,
at a small size on the CPU: the ``tiny-latent-moe`` preset (hidden 64, a dense
first layer, then 8 experts, 2 a token, 1 shared; ``kv_lora_rank`` 32, nope 16,
rope 8, v 16). Float32 throughout, seeded weights with every term alive.

The learner's update through ``trainer.train_step`` and the rollout through
``perfbench/run.py`` are held by ``tests/perfbench/test_perfbench_rehearsal_latent_moe.py``.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s.
"""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite as fs
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine import paged_engine
from distrl_llm_tpu.models import ModelConfig, forward, init_lora_params, init_params
from distrl_llm_tpu.models import hybrid, moe
from distrl_llm_tpu.models.configs import PRESETS
from distrl_llm_tpu.ops import latent_attention
from perfbench import reference_latent_moe as ref

CFG = PRESETS["tiny-latent-moe"]


def _round_check(moved, result, engine, scheduler, slots):
    # either scheduler counts its steps (the benchmark's step time and occupancy)
    assert result.steps_dispatched >= 24 * (2 if slots == 4 else 1)
    # 2 expert layers x 8 rows x 24 steps x 2 experts a token, live slots only
    assert moved("engine/moe_assignments") == 2 * 8 * 24 * 2
    # the fullest of 8 experts holds at least the mean, at most every pair's half
    assert 2 * 24 * 2 <= moved("engine/moe_max_expert_load") <= 2 * 8 * 24
    # absorbed attention's pages, from the page table by hand: at its step t a
    # row of a P-token prompt holds (P + t) // 8 + 1 pages in each of 3 layers
    held = np.asarray([[(p + t) // 8 + 1 for t in range(24)] for p in (40, 57)])
    assert moved("engine/latent_pages_attended") == 3 * 4 * held.sum()
    if scheduler == "waves":
        # a group of 4 is one prompt's candidates: its full pages (5 and 7) in
        # whole blocks of 6 columns are fetched once, every other page a row
        once = np.asarray([[5 // 6 * 6], [7 // 6 * 6]])
        assert moved("engine/latent_pages_read") == 3 * (once + 4 * (held - once)).sum()
    assert moved("engine/latent_pages_read") <= moved("engine/latent_pages_attended")


FAMILY = fs.Family(
    name="latent-moe", cfg=CFG, ref=ref, config_file="kimi-vl-a3b-L7.json",
    # projections large enough that a dropped term moves the logits, an adapter
    # (kv_b's too) whose b is not zero
    weight_scale=3.0,
    # Eight tokens or fewer take the dense form (a decode step of 8 rows), more
    # the grouped one (a prefill segment, the learner's rows), as 128 divides
    # the 64-row decode step from the 4,096-token segment at the real size.
    pieces=((moe, "expert_form", fs.expert_forms(8)),),
    # Prefill in segments of 16 tokens and decode attention over 3 pages (6 where
    # a group shares them: the scores of 4 rows' heads over 6 pages of 8) and 4
    # rows at a time, so that 40-57-token prompts in pages of 8 cross every
    # boundary the 21k-token cell crosses.
    engine_pieces=(
        (paged_engine, "HYBRID_PREFILL_SEGMENT", 16),
        (hybrid, "LATENT_DECODE_PAGES", 3), (hybrid, "LATENT_DECODE_ROWS", 4),
        (latent_attention, "SHARED_SCORE_BYTES", 4 * CFG.num_heads * 6 * 8 * 4)),
    refusals=(
        ({"n_group": 8}, "n_group"),
        ({"topk_group": 4}, "topk_group"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"rope_scaling": {"rope_type": "yarn", "factor": 64}}, "rope_scaling"),
        ({"moe_layer_freq": 2}, "moe_layer_freq"),
        ({"model_type": "deepseek_v2"}, "deepseek_v2")),
    forward_cases=(("plain", False, ()),),
    # every adapter factor (kv_a's, kv_b's, the shared expert's)
    learner={"answer": 20, "leaves": None, "floor": 1e-7},
    # prefill in segments over earlier segments' latent pages (expanded), the
    # fan-out aliasing the prompt's pages, then absorbed decode through the cache
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)), round_check=_round_check,
    # bf16 latent pages leave the 2e-5 agreement by a wide margin: it is what
    # holds the pages' precision, whatever the chip's check can tell
    engine_controls={"bf16_pages": lambda monkeypatch: {"cache_dtype": jnp.bfloat16}},
    engine_limit=20 * 2e-5,
    # sixteen candidates equal sixteen single rows, one at a time
    fan_out={"scheduler": "refill", "slots": 16, "length": 50, "n": 16, "max_tokens": 24,
             "atol": 1e-5, "rows": True},
    state_refusals=(
        ("dense", "dense engine"), ("sharded", "dp-sharded"), ("speculation", "spec_draft"),
        ("int8_pool", "int8"), ("radix_cache", "prefix_sharing"),
        ("pool_chains", "prefix_sharing"), ("continuous_admission", "continuous_admission"),
        ("preemption", "re-prefill")),
    state_refusal_says=("latent-attention (MLA)", "routed-expert", "latent row"),
)
family, small_pieces, weights = fs.fixtures(FAMILY)


def moe_layer(params, j=0):
    return jax.tree_util.tree_map(lambda w: w[j], params["layers"]["latent_moe"])


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("control", [
    "top1", "no_shared", "no_scaling", "no_bias", "no_k_rope", "no_kvb_adapter"])
def test_the_forward_can_tell_each_mechanism(weights, control, monkeypatch):
    """Each term the chip's controls drop moves this file's agreement by far
    more than its tolerance: a check that passes with one missing is no check."""
    params, lora = weights
    cfg = CFG
    if control == "top1":
        cfg = ModelConfig(**{**CFG.__dict__, "experts_per_token": 1})
    elif control == "no_scaling":
        cfg = ModelConfig(**{**CFG.__dict__, "routed_scaling_factor": 1.0})
    elif control == "no_shared":
        stack = params["layers"]["latent_moe"]
        params = {**params, "layers": {**params["layers"], "latent_moe": {
            k: v for k, v in stack.items() if k not in ("w_gate", "w_up", "w_down")}}}
        lora = None
    elif control == "no_bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if str(path[-1].key) == "e_score_bias" else x,
            params)
    elif control == "no_k_rope":
        rope = hybrid.rope_interleaved
        monkeypatch.setattr(
            hybrid, "rope_interleaved",
            lambda x, cos, sin: rope(x, cos, sin) if x.ndim == 4 else jnp.concatenate(
                [x[..., 0::2], x[..., 1::2]], -1))
    else:
        lora = {"layers": {kind: {k: v for k, v in stack.items() if k != "wkv_b"}
                           for kind, stack in lora["layers"].items()}}
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1, 256))
    mask = np.ones((2, 40), np.int32)
    fs.fresh_traces(monkeypatch)  # ``no_k_rope`` patches a function the trace reads
    got = fs.forward_logprobs(FAMILY, params, lora, ids, mask, cfg)
    want = fs.reference_logprobs(FAMILY, *weights, ids, mask)
    assert np.abs(got - want).mean() > 50 * 2e-5


# ------------------------------------------------------------ the attention


def test_rope_pairs_are_the_interleaved_ones():
    """(x[2i], x[2i+1]) rotate together: multiplication by e^{i pos w_i} of the
    complex number x[2i] + i x[2i+1]."""
    from distrl_llm_tpu.models.transformer import rope_cos_sin
    from distrl_llm_tpu.ops.latent_attention import rope_interleaved

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (1, 5, 8)))
    pos = jnp.arange(5)[None, :]
    cos, sin = rope_cos_sin(pos, 8, 10000.0)
    got = np.asarray(rope_interleaved(jnp.asarray(x), cos, sin))
    freq = 1.0 / (10000.0 ** (np.arange(0, 8, 2) / 8))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(1j * np.arange(5)[None, :, None] * freq)
    np.testing.assert_allclose(got[..., :4], z.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:], z.imag, atol=1e-5)


@pytest.mark.parametrize("blocks", [1, 3])
def test_absorbed_equals_expanded_with_an_adapter_on_kv_b(blocks):
    """The same function of the cache: one query over the latent rows
    (absorbed, folded in ``blocks`` pieces) against K and V rebuilt per head
    (expanded), with W_kvb carrying a non-zero adapter."""
    from distrl_llm_tpu.ops import latent_attention as la

    heads, nope, rope, v_dim, rank, sk = 4, 16, 8, 16, 32, 24
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    w = jax.random.normal(keys[0], (rank, heads * (nope + v_dim))) * 0.3
    w = w + 0.5 * (jax.random.normal(keys[1], (rank, 4)) @ jax.random.normal(keys[2], (4, w.shape[1])))
    latent = jax.random.normal(keys[3], (2, sk, rank + rope))
    q = jax.random.normal(keys[4], (2, heads, nope + rope))
    seen = jnp.arange(sk)[None, :] < jnp.asarray([sk, 17])[:, None]
    kv = (latent[..., :rank] @ w).reshape(2, sk, heads, nope + v_dim)
    want = la.expanded_finish(la.expanded_attention(
        q[:, None, :, :nope], q[:, None, :, nope:], kv, latent[..., rank:],
        seen[:, None, :]), jnp.float32)[:, 0]
    w_k, w_v = la.split_kvb(w, heads, nope, v_dim)
    q_row = la.absorbed_query(q[..., :nope], q[..., nope:], w_k)
    carry = None
    for part in range(blocks):
        cut = slice(part * sk // blocks, (part + 1) * sk // blocks)
        carry = la.absorbed_attention(
            q_row, latent[:, cut], seen[:, cut], (nope + rope) ** -0.5, carry)
    np.testing.assert_allclose(
        la.absorbed_output(carry, w_v, jnp.float32), want, atol=2e-5)


# ------------------------------------------- a prefill segment's fold kernel


FOLD = dict(b=2, s=256, h=2, nope=16, rope=8, v_dim=16)


def fold_case(seed, b, s, h, nope, rope, v_dim):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, s, h, nope)),
            jax.random.normal(keys[1], (b, s, h, rope)),
            jax.random.normal(keys[2], (b, s, h, nope + v_dim)),
            jax.random.normal(keys[3], (b, s, rope)))


@pytest.mark.parametrize("case,q_start,real", [
    ("the_causal_fold_last", 512, None),
    # row 1's tokens end 100 into the segment: its later queries and keys are
    # the engine's padding (zero rows), which the positions still order
    ("a_row_ends_mid_segment", 512, 100),
    # queries 64 tokens short of the third block: its last tile of keys is
    # seen by no query of the first tile, and only in part by the second
    ("queries_across_a_block", 448, None),
    # the first 32 queries stand before every key: they have seen none, keep
    # the start's (m, l, acc) and finish as zeros
    ("a_query_that_has_seen_no_key", -32, None),
])
def test_the_fold_kernel_is_expanded_attention(case, q_start, real):
    """``expanded_fold_kernel`` (interpreted, tiles of 128 so that a block is
    2 x 2 of them) against ``expanded_attention`` in float32, three blocks of
    keys at 0, 256 and 512 chained, the one that crosses the queries' own
    positions last: the carry after every fold and the finished output."""
    from distrl_llm_tpu.ops import latent_attention as la

    b, s, h, v_dim = FOLD["b"], FOLD["s"], FOLD["h"], FOLD["v_dim"]
    q_nope, q_pe, _, _ = fold_case(0, **FOLD)
    live = jnp.ones((b, s, 1))
    if real is not None:
        live = live.at[1, real:].set(0.0)
        q_nope, q_pe = q_nope * live[..., None], q_pe * live[..., None]
    want, got = la.expanded_start(b, s, h, v_dim), la.fold_start(b, s, h, v_dim)
    heads = la.fold_queries(q_nope, q_pe)
    for j in range(3):
        _, _, kv, k_pe = fold_case(j + 1, **FOLD)
        if j == 2:
            kv, k_pe = kv * live[..., None], k_pe * live
        mask = (j * s + jnp.arange(s))[None, :] <= (q_start + jnp.arange(s))[:, None]
        want = la.expanded_attention(
            q_nope, q_pe, kv, k_pe, jnp.broadcast_to(mask, (b, s, s)), want)
        by_positions = la.expanded_fold(
            q_nope, q_pe, kv, k_pe, q_start, j * s, None if j == 0 else by_positions)
        got = la.expanded_fold_kernel(
            *heads, kv, k_pe, jnp.int32(q_start), jnp.int32(j * s), got,
            tile_q=128, tile_k=128, interpret=True)
        for name, x, y in zip("mla", la.fold_carry(got), want):
            # l and acc are sums of up to 768 weights: 2e-5 of their size
            np.testing.assert_allclose(
                x, y, rtol=2e-5, atol=2e-5, err_msg=f"{name} after fold {j}")
    for x, y in zip(by_positions, want):  # the XLA form the segment takes elsewhere
        np.testing.assert_array_equal(x, y)
    out = la.fold_finish(got, jnp.float32)
    np.testing.assert_allclose(out, la.expanded_finish(want, jnp.float32), atol=2e-5)
    if q_start < 0:
        assert (np.asarray(out)[:, :-q_start] == 0).all()
        assert (np.asarray(got[1])[..., :-q_start] == 0).all()


FOLD_WIDE_V = dict(b=2, s=256, h=2, nope=24, rope=8, v_dim=32)
#: head layouts the kernel reads off its shapes: (nope, rope, v)


LAYOUTS = {
    "k24+8_v32": (24, 8, 32),  # the rope part in K's only, unfilled tile
    "k192+64_v128": (192, 64, 128),  # GLM-5's keys: a whole tile, then K's rest and k_pe in one
    "k128+64_v128": (128, 64, 128),  # Kimi-VL's: a tile of K, a tile of k_pe
}


KEYS = 3 * 256 + 128  # a page table's width: the blocks the folds reach and a ragged rest


def causal(q_start, b=2, s=256):
    """What two positions say, as a choice: ``[B, S, KEYS]`` bool."""
    seen = jnp.arange(KEYS)[None, :] <= (q_start + jnp.arange(s))[:, None]
    return jnp.broadcast_to(seen, (b, s, KEYS))


def choice_case(case):
    """(q_start, the choice ``[B, S, KEYS]`` bool) of a case below."""
    from distrl_llm_tpu.ops import token_index

    if case == "the_positions_themselves":
        return 448, causal(448)
    if case == "a_query_that_has_seen_no_key":
        return -32, causal(-32)
    visible = causal(512)
    if case == "eight_of_what_a_query_sees_with_ties_at_zero":
        # an index's scores: a relu's zeros on half the keys, so that most
        # queries' eighth score is one of many equals (the first few are kept)
        scores = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(9), visible.shape))
        scores = jnp.round(scores * 4) / 4
        return 512, token_index.chosen_mask(scores, visible, 8)
    assert case == "a_tile_that_holds_none_of_a_querys_choices"
    # queries 0-127 choose nothing of keys 128-383 (a whole tile of block 0 and
    # the first of block 1), queries 128-199 nothing of the segment's own block
    drawn = jax.random.uniform(jax.random.PRNGKey(9), visible.shape) < 0.1
    keys, queries = jnp.arange(KEYS)[None, None, :], jnp.arange(256)[None, :, None]
    drawn &= ~((queries < 128) & (keys >= 128) & (keys < 384))
    drawn &= ~((queries >= 128) & (queries < 200) & (keys >= 512))
    return 512, drawn & visible


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("case", [
    "eight_of_what_a_query_sees_with_ties_at_zero",
    "a_tile_that_holds_none_of_a_querys_choices",
    "a_query_that_has_seen_no_key",
    "the_positions_themselves",
])
def test_the_fold_kernel_under_a_choice_is_expanded_attention(case, layout):
    """``expanded_fold_kernel`` handed a choice (interpreted, tiles of 128, K
    and V of two widths in each of ``LAYOUTS``) against ``expanded_attention``
    under the same mask, three blocks of keys chained: the carry after every
    fold and the finished output. A query whose tile holds none of its choices keeps its
    ``(m, l, acc)``, one that has seen no key at all finishes as zeros, and a
    choice that says what the two positions say gives what the kernel gives
    without one: bit for bit where both mask (the block that crosses the
    diagonal, alone), and to an ulp of the CPU's exponential over the chain
    (of a tile seen whole the kernel without a choice masks nothing, and the
    CPU's compiler rounds the two programs apart)."""
    from distrl_llm_tpu.ops import latent_attention as la

    nope, rope, v_dim = LAYOUTS[layout]
    dims = dict(FOLD_WIDE_V, nope=nope, rope=rope, v_dim=v_dim)
    b, s, h = (dims[k] for k in ("b", "s", "h"))
    q_start, mask = choice_case(case)
    chosen = mask.astype(la.FOLD_MASK_DTYPE)
    q_nope, q_pe, _, _ = fold_case(0, **dims)
    want, got = la.expanded_start(b, s, h, v_dim), la.fold_start(b, s, h, v_dim)
    by_positions, by_choice = got, None
    heads = la.fold_queries(q_nope, q_pe)
    kernel = functools.partial(la.expanded_fold_kernel, tile_q=128, tile_k=128, interpret=True)
    for j in range(3):
        _, _, kv, k_pe = fold_case(j + 1, **dims)
        at = jnp.int32(q_start), jnp.int32(j * s)
        before = got
        want = la.expanded_attention(
            q_nope, q_pe, kv, k_pe, mask[:, :, j * s: (j + 1) * s], want)
        by_choice = la.expanded_fold(q_nope, q_pe, kv, k_pe, *at, by_choice, chosen)
        got = kernel(*heads, kv, k_pe, *at, got, chosen)
        by_positions = kernel(*heads, kv, k_pe, *at, by_positions)
        for name, x, y in zip("mla", la.fold_carry(got), want):
            np.testing.assert_allclose(
                x, y, rtol=2e-5, atol=2e-5, err_msg=f"{name} after fold {j}")
        if case == "a_tile_that_holds_none_of_a_querys_choices" and j == 2:
            for x, y in zip(before, got):  # m and l [B, H, 1, S], acc [B, H, S, v]
                x, y = np.asarray(x).reshape(b, h, s, -1), np.asarray(y).reshape(b, h, s, -1)
                np.testing.assert_array_equal(x[:, :, 128:200], y[:, :, 128:200])
                assert (x[:, :, 200:] != y[:, :, 200:]).any()
    for x, y in zip(by_choice, want):  # the XLA form under a choice
        np.testing.assert_array_equal(x, y)
    out = la.fold_finish(got, jnp.float32)
    np.testing.assert_allclose(out, la.expanded_finish(want, jnp.float32), atol=2e-5)
    if case in ("the_positions_themselves", "a_query_that_has_seen_no_key"):
        np.testing.assert_array_equal(got[0], by_positions[0])
        for x, y in zip(got[1:], by_positions[1:]):
            np.testing.assert_allclose(x, y, rtol=2e-6, atol=4e-6)
        fresh = la.fold_start(b, s, h, v_dim)
        for x, y in zip(kernel(*heads, kv, k_pe, *at, fresh, chosen),
                        kernel(*heads, kv, k_pe, *at, fresh)):
            np.testing.assert_array_equal(x, y)
    if q_start < 0:
        assert (np.asarray(out)[:, :-q_start] == 0).all()
        assert (np.asarray(got[1])[..., :-q_start] == 0).all()


@pytest.mark.parametrize("backend,dtype,nope,v_dim,segment,chosen,want", [
    ("tpu", jnp.bfloat16, 128, 128, 1024, False, "kernel"),  # the Kimi cell's segment
    ("tpu", jnp.bfloat16, 128, 128, 384, False, "kernel"),
    ("cpu", jnp.bfloat16, 128, 128, 1024, False, "xla"),
    ("tpu", jnp.bfloat16, 16, 16, 1024, False, "xla"),  # the tests' tiny heads
    ("tpu", jnp.float32, 128, 128, 1024, False, "xla"),
    ("tpu", jnp.bfloat16, 128, 128, 1000, False, "xla"),  # no whole tiles of queries
    ("tpu", jnp.bfloat16, 128, 256, 1024, False, "kernel"),  # K and V of two widths
    ("tpu", jnp.bfloat16, 192, 256, 1024, False, "kernel"),  # GLM-5's: K a tile and a half
    ("tpu", jnp.bfloat16, 192, 256, 1024, True, "kernel"),  # the GLM-5 cell's segment
    ("cpu", jnp.bfloat16, 192, 256, 1024, True, "xla"),
    ("tpu", jnp.bfloat16, 192, 192, 1024, True, "xla"),  # values of no whole tiles
])
def test_the_segments_form_is_read_off_the_backend_and_the_shapes(
        monkeypatch, backend, dtype, nope, v_dim, segment, chosen, want):
    """The rule, and what a segment traced under it records: a choice handed
    to ``expanded_segment`` changes neither."""
    from distrl_llm_tpu.ops import latent_attention as la

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(la, "dispatch_choices", {})
    shape = lambda *s, t=dtype: jax.ShapeDtypeStruct(s, t)
    q_nope = shape(2, segment, 4, nope)
    assert la.expanded_segment_impl(q_nope, v_dim) == want
    block = lambda j: (jnp.zeros((2, segment, 4, nope + v_dim), dtype),
                       jnp.zeros((2, segment, 64), dtype))
    out = jax.eval_shape(  # traced, never lowered: the kernel's launch is an equation
        lambda q_nope, q_pe, chosen: la.expanded_segment(
            q_nope, q_pe, block, jnp.int32(segment), v_dim, dtype, chosen),
        q_nope, shape(2, segment, 4, 64),
        shape(2, segment, 2 * segment + 128, t=la.FOLD_MASK_DTYPE) if chosen else None)
    assert out.shape == (2, segment, 4, v_dim)
    assert la.dispatch_choices == {la.dispatch_key(4, nope, 64, v_dim, segment, dtype): want}


def test_a_segment_records_the_form_it_took(monkeypatch):
    """``expanded_segment`` files its choice under the segment's geometry, and
    on the CPU it is the XLA form: the parent's folds, mask and all."""
    from distrl_llm_tpu.ops import latent_attention as la

    monkeypatch.setattr(la, "dispatch_choices", {})
    small = dict(FOLD, s=16)
    q_nope, q_pe, _, _ = fold_case(0, **small)
    blocks = [fold_case(j + 1, **small)[2:] for j in range(3)]
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *blocks)
    block = lambda j: jax.tree_util.tree_map(lambda x: x[j], stacked)
    got = la.expanded_segment(q_nope, q_pe, block, jnp.int32(32), 16, jnp.float32)
    assert la.dispatch_choices == {la.dispatch_key(2, 16, 8, 16, 16, jnp.float32): "xla"}
    carry = None
    for j, (kv, k_pe) in enumerate(blocks):
        carry = la.expanded_fold(q_nope, q_pe, kv, k_pe, 32, j * 16, carry)
    np.testing.assert_allclose(got, la.expanded_finish(carry, jnp.float32), atol=1e-6)


def test_full_mode_never_asks_for_the_kernel(weights, monkeypatch):
    """The learner's and the no-cache forward differentiate
    ``expanded_attention``: their program is the same whatever the backend
    answers, holds no custom call, and never reaches ``expanded_segment``."""
    from distrl_llm_tpu.ops import latent_attention as la

    params, lora = weights
    ids = jnp.ones((2, 32), jnp.int32)

    def lowered():
        step = jax.jit(lambda p, l, i: forward(p, CFG, i, lora=l, lora_scale=fs.LORA_SCALE)[0])
        grad = jax.jit(jax.grad(lambda l, p, i: forward(
            p, CFG, i, lora=l, lora_scale=fs.LORA_SCALE)[0].sum()))
        return step.lower(params, lora, ids).as_text(), grad.lower(lora, params, ids).as_text()

    on_cpu = lowered()

    def refuse(*args, **kw):
        raise AssertionError("full mode reached the prefill segment's dispatch")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(la, "expanded_segment", refuse)
    monkeypatch.setattr(la, "expanded_fold_kernel", refuse)
    assert lowered() == on_cpu
    assert "custom_call" not in on_cpu[0] and "custom_call" not in on_cpu[1]


WALK = dict(heads=4, nope=16, rope=8, v_dim=16, rank=32, row=48, ps=8, per=3,
            prompt_pages=16, private_pages=4)


def paged_walk_case(groups, dtype=jnp.float32, seed=0):
    """A pool and page tables as the engine's fan-out lays them out
    (``_page_table_rows``: a prompt's full pages shared, the partial page and
    the answer private), one group a ``(prompt tokens, [tokens generated a
    candidate])``; a generated count of -1 is a row of length 0. A row's
    newest cached position is its length, every whole page past it is NaN,
    and the slots past it in its newest page hold zeros."""
    from distrl_llm_tpu.engine.paged_engine import _page_table_rows

    w = SimpleNamespace(**WALK)
    rng = np.random.default_rng(seed)
    prompt_of = np.repeat(np.arange(len(groups)), [len(g[1]) for g in groups])
    prompt_len = np.asarray([g[0] for g in groups])[prompt_of]
    generated = np.concatenate([g[1] for g in groups])
    lengths = np.where(generated < 0, 0, prompt_len + generated)
    b = len(lengths)
    priv0 = len(groups) * w.prompt_pages + np.arange(b) * w.private_pages
    tables = np.asarray(_page_table_rows(
        jnp.asarray(prompt_of), jnp.asarray(prompt_len // w.ps), jnp.asarray(priv0),
        prompt_pages=w.prompt_pages, private_pages=w.private_pages))
    pool = np.full((priv0[-1] + w.private_pages, w.ps, w.row), np.nan, np.float32)
    prompt_rows = [rng.standard_normal((g[0], w.row)) for g in groups]
    context = []
    for r in range(b):
        rows = np.concatenate([
            prompt_rows[prompt_of[r]], rng.standard_normal((64, w.row))])[: lengths[r] + 1]
        rows[:, w.rank + w.rope:] = 0.0  # a row is [c, k_pe] and zeros to whole tiles
        rows = np.asarray(jnp.asarray(rows, dtype).astype(jnp.float32))
        for page in tables[r, : lengths[r] // w.ps + 1]:
            pool[page] = np.nan_to_num(pool[page], nan=0.0)
        pos = np.arange(lengths[r] + 1)
        pool[tables[r, pos // w.ps], pos % w.ps] = rows
        context.append(rows)
    return (jnp.asarray(pool, dtype), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
            context)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("wide", [3, 6])  # a shared block of a row's own 3 columns, or twice
@pytest.mark.parametrize("case,groups,rows,shared,dead", [
    # 96 prompt tokens are 12 full pages: all of the prompt is read once, and
    # the private copy of a page-aligned prompt's (clamped) partial page never
    ("fully_shared_page_aligned", [(96, [0, 1, 2, 3])], 4, {3: [4], 6: [2]}, ()),
    # every row another prompt: today's walk, a row at a time
    ("nothing_shared", [(40, [5]), (41, [5]), (57, [0]), (30, [9])], 4, {3: [0], 6: [0]}, ()),
    # 9 full pages: whole blocks of them shared, the rest of them read a row
    ("prefix_no_multiple_of_the_block", [(76, [0, 3, 9, 20])], 4, {3: [3], 6: [1]}, ()),
    # a candidate that stopped early beside one a page and a half further on
    ("unequal_generated_lengths", [(105, [0, 1, 13, 27])], 4, {3: [4], 6: [2]}, ()),
    # a dead slot of length 0 names its first page everywhere: nothing shared
    ("dead_row_of_length_0", [(57, [4, -1, 4, 6])], 4, {3: [0], 6: [0]}, (1,)),
    ("two_groups_of_different_prompts", [(50, [1, 2, 3, 4]), (110, [7, 7, 0, 1])], 4,
     {3: [2, 4], 6: [1, 2]}, ()),
    # 6 rows are no multiple of 4: one group of all six, two prompts in it
    ("b_no_multiple_of_the_rows", [(50, [0, 1, 2]), (50, [3, 4, 5])], 6, {3: [0], 6: [0]}, ()),
    ("b_no_multiple_of_the_rows_one_prompt", [(100, [0, 1, 2, 3, 4, 20])], 6,
     {3: [4], 6: [2]}, ()),
])
def test_the_split_walk_equals_the_row_walk_and_expanded_attention(
        case, groups, rows, shared, dead, wide, dtype):
    """Absorbed attention that reads a group's shared page prefix once
    (``absorbed_paged_attention``) against the same walk with nothing taken as
    shared (the parent's: a gather a row) and against K and V rebuilt per head
    over each row's own context; whole pages past a row's length hold NaN."""
    from distrl_llm_tpu.ops import latent_attention as la

    w = SimpleNamespace(**WALK)
    pool, tables, lengths, context = paged_walk_case(groups, dtype)
    b = len(context)
    alive = np.ones(b, bool)
    alive[list(dead)] = False
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    w_kvb = 0.3 * jax.random.normal(keys[0], (w.rank, w.heads * (w.nope + w.v_dim)))
    q = jax.random.normal(keys[1], (b, w.heads, w.nope + w.rope))
    w_k, w_v = la.split_kvb(w_kvb, w.heads, w.nope, w.v_dim)
    q_row = jnp.pad(la.absorbed_query(q[..., :w.nope], q[..., w.nope:], w_k),
                    ((0, 0), (0, 0), (0, w.row - w.rank - w.rope)))
    scale, shared = (w.nope + w.rope) ** -0.5, shared[wide]
    walk = la.shared_page_walk(
        tables, lengths, jnp.asarray(alive), page_size=w.ps, wide=wide, rows=rows)
    np.testing.assert_array_equal(walk.shared, shared)

    attend = jax.jit(lambda walk: la.absorbed_output(
        la.absorbed_paged_attention(
            q_row, pool, walk, lengths, scale, per=w.per, wide=wide, rows=rows),
        w_v, jnp.float32))
    got = np.asarray(attend(walk))
    by_row = np.asarray(attend(walk._replace(shared=jnp.zeros_like(walk.shared))))
    assert np.isfinite(got).all() and np.isfinite(by_row).all()
    exact = dtype == jnp.float32
    np.testing.assert_allclose(got, by_row, atol=2e-5 if exact else 2e-2)
    for r, rows_r in enumerate(context):  # a row's own context, K and V rebuilt
        latent = jnp.asarray(rows_r)[None]
        kv = (latent[..., :w.rank] @ w_kvb).reshape(1, -1, w.heads, w.nope + w.v_dim)
        want = la.expanded_finish(la.expanded_attention(
            q[r, None, None, :, :w.nope], q[r, None, None, :, w.nope:], kv,
            latent[..., w.rank: w.rank + w.rope],
            jnp.ones((1, 1, len(rows_r)), bool)), jnp.float32)[0, 0]
        np.testing.assert_allclose(got[r], want, atol=2e-5 if exact else 3e-2)

    # the counters, reckoned from the table by hand: a live row's pages, and
    # each fetched once a group below the split and once a row above it
    live = np.where(alive, np.asarray(lengths) // w.ps + 1, 0).reshape(-1, rows)
    once = np.asarray(shared)[:, None] * wide
    read = np.minimum(once[:, 0], live.max(axis=1)) + np.maximum(live - once, 0).sum(axis=1)
    np.testing.assert_array_equal(walk.stats, [live.sum(), read.sum()])
    if not any(shared):
        assert walk.stats[0] == walk.stats[1]


def test_a_column_past_a_rows_newest_page_repeats_that_page():
    """No page that a row does not hold is fetched, whatever its table names
    there (the engine clamps trailing columns to a private page the row has
    not reached; a refilled slot's may name another row's)."""
    from distrl_llm_tpu.ops import latent_attention as la

    tables = jnp.asarray([[4, 5, 6, 7, 8], [4, 5, 9, 10, 11]], jnp.int32)
    walk = la.shared_page_walk(
        tables, jnp.asarray([17, 8], jnp.int32), page_size=8, wide=2, rows=2)
    np.testing.assert_array_equal(
        walk.cols, [[4, 5, 6, 6, 6, 6], [4, 5, 5, 5, 5, 5]])
    np.testing.assert_array_equal(walk.shared, [1])
    np.testing.assert_array_equal(walk.newest, [2])
    # rows 0 and 1 hold 3 and 2 pages; two are fetched once for both
    np.testing.assert_array_equal(walk.stats, [5, 2 + 1 + 0])
    # a shared block's columns, from shapes alone
    assert la.shared_pages_per_block(16, 16, 128, 8, 165) == 16  # the Kimi cell's
    assert la.shared_pages_per_block(4, 4, 8, 3, 11) == 9  # no wider than the table
    assert la.shared_pages_per_block(64, 128, 128, 8, 165) == 8  # nor narrower than a row's


def _walk_inputs(groups, dtype, heads, cols=None):
    """``paged_walk_case`` with ``heads`` queries a row: (pool, tables, lengths,
    context, q, q_row, w_kvb, w_v, scale)."""
    w = SimpleNamespace(**WALK)
    pool, tables, lengths, context = paged_walk_case(groups, dtype)
    tables = tables if cols is None else tables[:, :cols]
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    w_kvb = 0.3 * jax.random.normal(keys[0], (w.rank, heads * (w.nope + w.v_dim)))
    q = jax.random.normal(keys[1], (len(context), heads, w.nope + w.rope))
    w_k, w_v = latent_attention.split_kvb(w_kvb, heads, w.nope, w.v_dim)
    q_row = jnp.pad(latent_attention.absorbed_query(q[..., :w.nope], q[..., w.nope:], w_k),
                    ((0, 0), (0, 0), (0, w.row - w.rank - w.rope)))
    return pool, tables, lengths, context, q, q_row, w_kvb, w_v, (w.nope + w.rope) ** -0.5


LAUNCH_CASES = [  # case, groups, rows, wide, block, heads, k
    # every column of the prompt shared: 12 columns in blocks of 4, and of 5 (a
    # ragged last block: its columns past the twelfth repeat it, masked)
    ("all_shared", [(96, [0, 1, 2, 3])], 4, 1, 4, 4, None),
    ("all_shared_ragged_last_block", [(96, [0, 1, 2, 3])], 4, 1, 5, 4, None),
    # the XLA walk's blocks of 3 columns: 9 shared, walked 2 pages a block
    ("some_shared_in_the_walks_blocks", [(76, [0, 3, 9, 20])], 4, 3, 2, 4, None),
    ("nothing_shared", [(40, [5]), (41, [5]), (57, [0]), (30, [9])], 4, 1, 4, 4, None),
    ("rows_end_on_different_pages", [(105, [0, 1, 13, 27])], 4, 1, 4, 4, None),
    ("a_row_not_alive", [(57, [4, -1, 4, 6])], 4, 1, 2, 4, None),
    ("two_groups", [(50, [1, 2, 3, 4]), (110, [7, 7, 0, 1])], 4, 1, 4, 4, None),
    ("one_group_of_every_row", [(50, [0, 1, 2]), (50, [3, 4, 5])], 6, 1, 4, 4, None),
    ("a_table_narrower_than_per", [(5, [0, 3, 6, 9])], 4, 2, 4, 4, None),
    ("kimis_heads", [(76, [0, 3, 9, 20])], 4, 1, 4, 16, None),
    ("glm5s_heads", [(76, [0, 3, 9, 20])], 4, 1, 4, 64, None),
    # under a choice: scores in halves tie at the k-th, a row of one token has
    # fewer than k, and a k of the table's width or more chooses all it sees
    ("choice_ties_at_the_kth", [(76, [0, 3, 9, 20])], 4, 3, 2, 4, 8),
    ("choice_a_row_of_fewer_than_k", [(57, [4, -1, 4, 6])], 4, 1, 4, 4, 16),
    ("choice_k_of_the_width_or_more", [(50, [1, 2, 3, 4])], 4, 1, 4, 4, 1000),
    ("choice_glm5s_heads", [(76, [0, 3, 9, 20])], 4, 1, 3, 64, 8),
]
# every case in float32, to 2e-5; the pages' own type where a block's weights
# are cast to it before their product: the chip's bf16
BF16_CASES = ("kimis_heads", "choice_glm5s_heads")


@pytest.mark.parametrize("case,groups,rows,wide,block,heads,k,dtype", [
    pytest.param(*c, dtype, id=f"{c[0]}-{name}")
    for name, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16))
    for c in LAUNCH_CASES if name == "f32" or c[0] in BF16_CASES])
def test_the_launch_equals_the_plain_walk_and_expanded_attention(
        case, groups, rows, wide, block, heads, k, dtype):
    """``absorbed_decode_kernel`` (interpreted) against the plain form it
    stands for (``absorbed_paged_attention``'s walk; under a choice, that
    and the gather of the chosen rows with ``absorbed_attention``) and against K and V
    rebuilt per head over each row's own context (under a choice, its chosen
    tokens); whole pages past a row's length hold NaN. The launch takes any
    ``wide`` (the XLA walk a multiple of its ``per``) and sizes its own blocks."""
    from distrl_llm_tpu.ops import latent_attention as la
    from distrl_llm_tpu.ops import token_index as ti

    w = SimpleNamespace(**WALK)
    narrow = 2 if case == "a_table_narrower_than_per" else None
    pool, tables, lengths, context, q, q_row, w_kvb, w_v, scale = _walk_inputs(
        groups, dtype, heads, narrow)
    b, per = len(context), min(w.per, tables.shape[1])
    alive = jnp.asarray(np.asarray([g for _, gen in groups for g in gen]) >= 0)
    walk = la.shared_page_walk(tables, lengths, alive, page_size=w.ps, wide=wide, rows=rows)
    plain_walk = la.shared_page_walk(tables, lengths, alive, page_size=w.ps, wide=per, rows=rows)
    chosen = mask = None
    if k is not None:  # the choice over the positions of the walk's columns
        width = walk.cols.shape[1] * w.ps
        scores = jnp.round(2 * jax.random.normal(jax.random.PRNGKey(11), (b, width))) / 2
        visible = jnp.arange(width, dtype=jnp.int32) <= lengths[:, None]
        mask = ti.chosen_mask(scores, visible, k)
        chosen = mask.astype(la.FOLD_MASK_DTYPE)
    out = lambda carry: np.asarray(la.absorbed_output(carry, w_v, jnp.float32))
    got = out(la.absorbed_decode_kernel(
        q_row, pool, walk, lengths, chosen, scale=scale, rank=w.rank, wide=wide, rows=rows,
        block_pages=block, interpret=True))
    plain = out(la.absorbed_paged_attention(
        q_row, pool, plain_walk, lengths, scale, chosen, per=per, wide=per, rows=rows))
    if k is not None:  # and as ``hybrid._absorbed_decode`` gathers the chosen rows
        at, seen = ti.chosen_tokens(scores, lengths, k)
        column = jnp.arange(walk.cols.shape[1], dtype=jnp.int32)
        page = jnp.where((at // w.ps)[..., None] == column, walk.cols[:, None], 0).sum(-1)
        gathered = out(la.absorbed_attention(q_row, pool[page, at % w.ps], seen, scale))
        np.testing.assert_allclose(got, gathered, atol=2e-5 if dtype == jnp.float32 else 2e-2)
    exact = dtype == jnp.float32
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, plain, atol=2e-5 if exact else 2e-2)
    # every row's own context, K and V rebuilt a head: one padded batch
    longest = max(len(rows_r) for rows_r in context)
    latent = jnp.stack([jnp.pad(jnp.asarray(rows_r), ((0, longest - len(rows_r)), (0, 0)))
                        for rows_r in context])
    sees = jnp.arange(longest) <= lengths[:, None]
    if mask is not None:
        sees &= mask[:, :longest]
    kv = (latent[..., :w.rank] @ w_kvb).reshape(b, longest, heads, w.nope + w.v_dim)
    want = la.expanded_finish(la.expanded_attention(
        q[:, None, :, :w.nope], q[:, None, :, w.nope:], kv,
        latent[..., w.rank: w.rank + w.rope], sees[:, None]), jnp.float32)[:, 0]
    np.testing.assert_allclose(got, want, atol=2e-5 if exact else 3e-2)
    # what the walk says it fetched is the walk's, whichever form follows it
    live = np.where(alive, np.asarray(lengths) // w.ps + 1, 0).reshape(-1, rows)
    once = np.asarray(walk.shared)[:, None] * wide
    read = np.minimum(once[:, 0], live.max(axis=1)) + np.maximum(live - once, 0).sum(axis=1)
    np.testing.assert_array_equal(walk.stats, [live.sum(), read.sum()])
    assert walk.stats[0] == plain_walk.stats[0] and walk.stats[1] <= plain_walk.stats[0]


@pytest.mark.parametrize("backend,dtype,heads,row,page,b,want", [
    ("tpu", jnp.bfloat16, 16, 640, 128, 32, "kernel"),  # the Kimi cell's
    ("tpu", jnp.bfloat16, 64, 640, 128, 32, "kernel"),  # the GLM-5 cell's
    ("tpu", jnp.float32, 8, 640, 128, 32, "kernel"),
    ("cpu", jnp.bfloat16, 16, 640, 128, 32, "xla"),
    ("tpu", jnp.bfloat16, 16, 576, 128, 32, "xla"),  # a row of no whole tiles
    ("tpu", jnp.bfloat16, 16, 640, 64, 32, "xla"),  # nor a page's tokens
    ("tpu", jnp.bfloat16, 8, 640, 128, 32, "xla"),  # heads that fill no bf16 tile's sublanes
    ("tpu", jnp.float16, 16, 640, 128, 32, "xla"),
    ("tpu", jnp.bfloat16, 64, 640, 128, 24, "kernel"),  # no groups of 16: ONE of 24 rows
    ("tpu", jnp.bfloat16, 64, 640, 128, 72, "xla"),  # ONE of 72: more than VMEM holds
])
def test_the_decode_walks_form_is_read_off_the_backend_and_the_pages(
        monkeypatch, backend, dtype, heads, row, page, b, want):
    """The rule, what a step traced under it records, and the walk's ``wide``
    that follows from it (ONE column for the launch, the XLA form's block of
    gathered pages otherwise)."""
    from distrl_llm_tpu.ops import latent_attention as la

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(la, "dispatch_choices", {})
    shape = lambda *s, t=dtype: jax.ShapeDtypeStruct(s, t)
    pages = shape(40, page, row)
    rows = 16 if b % 16 == 0 else b
    assert la.absorbed_decode_impl(heads, pages, rows) == want
    cfg = SimpleNamespace(index_topk=0, index_heads=0, num_heads=heads)
    tables = jnp.zeros((b, 20), jnp.int32)
    env = {"page_indices": tables, "page_size": page, "lengths": jnp.zeros((b,), jnp.int32)}
    sizes, walk = hybrid._latent_page_walk(env, cfg, pages)
    assert sizes["rows"] == rows and (sizes["wide"] == 1) == (want == "kernel")
    m, l, acc = jax.eval_shape(  # traced, never lowered: the launch is an equation
        lambda q, pages: la.absorbed_decode(
            q, pages, walk, env["lengths"], 0.1, rank=512, **sizes),
        shape(b, heads, row), pages)
    assert m.shape == l.shape == (b, heads) and acc.shape[:2] == (b, heads)
    assert la.dispatch_choices == {la.decode_dispatch_key(heads, row, page, dtype): want}


@pytest.mark.parametrize("backend,rows,topk,width,want", [
    ("tpu", 16, 2048, 164, True),  # the cell's: 32,768 gathered rows >= 20,992 positions
    ("tpu", 1, 2048, 164, False),  # a group of one row reads less by gathering
    ("tpu", 16, 2048, 1640, False),  # and so does a table of 200k tokens
    ("tpu", 16, 2048, 256, True),  # equal: the walk
    ("cpu", 16, 2048, 164, False),  # no launch, no walk
])
def test_a_choice_walks_the_pages_exactly_where_that_reads_no_more(
        monkeypatch, backend, rows, topk, width, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = SimpleNamespace(index_topk=topk, num_heads=64)
    walk = SimpleNamespace(cols=jnp.zeros((rows, width), jnp.int32))
    pages = jax.ShapeDtypeStruct((40, 128, 640), jnp.bfloat16)
    assert hybrid._choice_walks_pages(cfg, pages, walk, rows) is want


# -------------------------------------------------------------- the experts


def test_every_tokens_chosen_experts_are_the_references(weights):
    params, _ = weights
    layer = moe_layer(params)
    h = jax.random.normal(jax.random.PRNGKey(4), (64, CFG.hidden_size))
    idx, w = moe.route(h, layer["router"], layer["e_score_bias"], CFG)
    comb = np.asarray(ref.combine_matrix(h, layer, CFG))
    chosen = np.zeros_like(comb, bool)
    np.put_along_axis(chosen, np.asarray(idx), True, axis=-1)
    assert (chosen == (comb != 0)).all() and chosen.sum(-1).tolist() == [2] * 64
    np.testing.assert_allclose(np.take_along_axis(comb, np.asarray(idx), -1), w, rtol=1e-6)
    # the bias is in the choice: without it some token chooses otherwise
    plain, _ = moe.route(h, layer["router"], 0 * layer["e_score_bias"], CFG)
    assert (np.sort(plain, -1) != np.sort(idx, -1)).any()


def dense_experts(h, idx, w, experts):
    """Every pair, one at a time."""
    out = np.zeros(h.shape, np.float64)
    for t in range(h.shape[0]):
        for e, weight in zip(np.asarray(idx[t]), np.asarray(w[t])):
            g = jax.nn.silu(h[t] @ experts["gate"][e]) * (h[t] @ experts["up"][e])
            out[t] += weight * np.asarray(g @ experts["down"][e])
    return out


@pytest.mark.parametrize("form", ["grouped", "dense"])
@pytest.mark.parametrize("case", ["all_on_one_pair", "one_gets_none", "drawn"])
def test_no_token_is_dropped_at_any_imbalance(weights, case, form, monkeypatch):
    """Dropless, in either form: 48 tokens that ALL choose the same two experts
    (six of the eight get none), a batch in which one expert gets none, and a
    drawn one lose nothing against the pairs computed one at a time."""
    monkeypatch.setattr(moe, "expert_form", fs.expert_forms(0 if form == "grouped" else 48))
    params, _ = weights
    layer = moe_layer(params)
    experts = {k: layer[f"experts_{k}"] for k in ("gate", "up", "down")}
    h = jax.random.normal(jax.random.PRNGKey(6), (48, CFG.hidden_size))
    rng = np.random.default_rng(0)
    if case == "all_on_one_pair":
        idx = np.tile([[5, 2]], (48, 1))
    elif case == "one_gets_none":
        idx = np.stack([rng.permutation([0, 1, 2, 4, 5, 6, 7])[:2] for _ in range(48)])
    else:
        idx = np.stack([rng.permutation(8)[:2] for _ in range(48)])
    w = jnp.asarray(rng.uniform(0.2, 1.5, (48, 2)), jnp.float32)
    y, load, _ = moe.routed_experts(
        h, jnp.asarray(idx, jnp.int32), w, experts, n_experts=8)
    np.testing.assert_allclose(y, dense_experts(h, idx, w, experts), atol=2e-5)
    assert load.tolist() == np.bincount(idx.reshape(-1), minlength=8).tolist()
    assert int(load.sum()) == 96  # every pair computed


@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_the_shares_of_an_expert_parallel_layer_sum_to_the_whole(weights, form, monkeypatch):
    """The layer told which experts it holds, over 4 disjoint shares of the 8:
    the router scores all 8 and chooses among all; each share computes its own
    experts' part; with the shared expert counted ONCE the parts sum to the
    uncut reference's layer."""
    monkeypatch.setattr(moe, "expert_form", fs.expert_forms(0 if form == "grouped" else 40))
    params, _ = weights
    layer = moe_layer(params, 1)
    h = jax.random.normal(jax.random.PRNGKey(7), (40, CFG.hidden_size))
    shared = ref._gated(h, layer["w_gate"], layer["w_up"], layer["w_down"])
    want = np.asarray(ref._experts(h, layer, CFG) + shared)
    total, pairs = np.asarray(shared), 0
    for held in ([0, 1], [2, 3], [6, 7], [4, 5]):
        part = {**layer, **{f"experts_{k}": layer[f"experts_{k}"][jnp.asarray(held)]
                            for k in ("gate", "up", "down")}}
        y, stats = moe.moe_half(h, part, CFG, held=held)
        total = total + np.asarray(y)
        pairs += int(stats[0])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert pairs == 40 * 2
    whole, _ = moe.moe_half(h, layer, CFG)
    np.testing.assert_allclose(np.asarray(whole + shared), want, atol=2e-5)


@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_a_layer_read_from_the_whole_stack_equals_its_slice(weights, form, monkeypatch):
    """The cache modes hand the experts' products every layer's stack and the
    layer's index: (layer, expert) is one index, no layer is sliced out first."""
    monkeypatch.setattr(moe, "expert_form", fs.expert_forms(0 if form == "grouped" else 24))
    params, _ = weights
    stack = params["layers"]["latent_moe"]
    h = jax.random.normal(jax.random.PRNGKey(8), (24, CFG.hidden_size))
    want, _ = moe.moe_half(h, moe_layer(params, 1), CFG)
    whole = {**moe_layer(params, 1), "experts_layer": 1,
             **{k: stack[k] for k in stack if k.startswith("experts_")}}
    got, _ = moe.moe_half(h, whole, CFG)
    np.testing.assert_allclose(got, want, atol=1e-6)


# -------------------------------------------------------------- the engine


@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_cpu_round_counts_no_kernel_folds(weights, small_pieces, scheduler, slots):
    """``ops/latent_kernel_folds`` is filed by both schedulers and reads 0 here:
    float32 heads of 16 on a CPU take the XLA form, and ``expanded_segment``
    says so under the segment's geometry."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"].get(
        telemetry.OPS_LATENT_KERNEL_FOLDS, 0)
    fs.engine(FAMILY, scheduler, slots).generate(
        params, lora, *fs.prompts((40, 57)),
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=4),
        jax.random.PRNGKey(3))
    assert la.dispatch_choices[la.dispatch_key(4, 16, 8, 16, 16, jnp.float32)] == "xla"
    after = telemetry.observe_snapshot()["counters"]
    assert after[telemetry.OPS_LATENT_KERNEL_FOLDS] == before  # filed, and 0


@pytest.mark.parametrize("ran,want", [("kernel", 3 * 10), ("xla", 0), (None, 0)])
def test_the_counter_is_layers_times_folds_where_the_kernel_ran(monkeypatch, ran, want):
    """A prefill of 64 tokens in segments of 16 makes 1 + 2 + 3 + 4 folds in
    each of the 3 layers (the Kimi cell: 7 x 210 = 1,470)."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    monkeypatch.setattr(paged_engine, "HYBRID_PREFILL_SEGMENT", 16)
    monkeypatch.setattr(la, "dispatch_choices", {} if ran is None else {
        la.dispatch_key(4, 16, 8, 16, 16, jnp.float32): ran,
        la.dispatch_key(4, 16, 8, 16, 32, jnp.float32): "kernel"})  # another segment's
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_fold_telemetry(CFG, 8, 8, jnp.float32)
    assert filed == [("ops/latent_kernel_folds", want)]
    assert paged_engine._hybrid_segments(8, 8) == (16, 4)
    # a model without latent layers files nothing
    filed.clear()
    paged_engine._record_fold_telemetry(PRESETS["tiny"], 8, 8, jnp.float32)
    assert filed == []


def test_a_prefill_through_the_kernel_equals_the_reference(weights, small_pieces,
                                                           monkeypatch):
    """The engine's prefill with every fold run by ``expanded_fold_kernel``
    (interpreted; the dispatch answered for it): prompts of 40 and 57 tokens in
    segments of 16, so both rows end mid-segment and their last segments' later
    queries are padding. The captured log-probabilities are the reference's,
    and the counter reads 3 layers x (1 + 2 + 3 + 4) folds."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    params, lora = weights
    ids, mask = fs.prompts((40, 57))
    monkeypatch.setattr(la, "expanded_segment_impl", lambda q_nope, v_dim: "kernel")
    monkeypatch.setattr(la, "expanded_fold_kernel", functools.partial(
        la.expanded_fold_kernel, interpret=True))
    before = telemetry.observe_snapshot()["counters"].get(
        telemetry.OPS_LATENT_KERNEL_FOLDS, 0)
    result = fs.make_engine(FAMILY, "waves", 0).generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=4),
        jax.random.PRNGKey(3))
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result) < 2e-5
    after = telemetry.observe_snapshot()["counters"][telemetry.OPS_LATENT_KERNEL_FOLDS]
    assert after - before == 3 * 10


@pytest.mark.parametrize("scheduler,slots", [("refill", 8), ("waves", 0)])
def test_a_cpu_round_counts_no_decode_launches(weights, small_pieces, scheduler, slots):
    """``ops/latent_decode_launches`` is filed by both schedulers and reads 0
    here: a CPU takes the XLA walk, and ``absorbed_decode`` says so under the
    heads' and the pages' geometry."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    params, lora = weights
    before = telemetry.observe_snapshot()["counters"].get(
        telemetry.OPS_LATENT_DECODE_LAUNCHES, 0)
    fs.engine(FAMILY, scheduler, slots).generate(
        params, lora, *fs.prompts((40, 57)),
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=4),
        jax.random.PRNGKey(3))
    assert la.dispatch_choices[
        la.decode_dispatch_key(4, CFG.latent_row, 8, jnp.float32)] == "xla"
    after = telemetry.observe_snapshot()["counters"]
    assert after[telemetry.OPS_LATENT_DECODE_LAUNCHES] == before  # filed, and 0


@pytest.mark.parametrize("ran,want", [("kernel", 3 * 24), ("xla", 0), (None, 0)])
def test_the_counter_is_layers_times_steps_where_the_launch_ran(monkeypatch, ran, want):
    """24 decode steps through 3 latent layers (the Kimi cell: 7 x 512 = 3,584)."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    monkeypatch.setattr(la, "dispatch_choices", {} if ran is None else {
        la.decode_dispatch_key(4, CFG.latent_row, 8, jnp.float32): ran,
        la.decode_dispatch_key(4, CFG.latent_row, 16, jnp.float32): "kernel"})  # other pages
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_latent_decode_telemetry(CFG, 24, 8, jnp.float32)
    assert filed == [("ops/latent_decode_launches", want)]
    filed.clear()  # no step, or a model without latent layers: nothing filed
    paged_engine._record_latent_decode_telemetry(CFG, 0, 8, jnp.float32)
    paged_engine._record_latent_decode_telemetry(PRESETS["tiny"], 24, 8, jnp.float32)
    assert filed == []


def test_a_round_through_the_launch_equals_the_reference(weights, small_pieces, monkeypatch):
    """The engine's decode with every layer-step's attention run by
    ``absorbed_decode_kernel`` (interpreted; the dispatch answered for it):
    two prompts' candidates in groups of four, 24 steps that cross three page
    boundaries. The captured log-probabilities are the reference's, the
    counter reads 3 layers x 24 steps, the walk shares by ONE column (the
    prompts' 5 and 7 full pages are fetched once a group where the XLA walk's
    blocks of 6 share 0 and 6) and attends what it always did."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.ops import latent_attention as la

    params, lora = weights
    ids, mask = fs.prompts((40, 57))
    monkeypatch.setattr(la, "absorbed_decode_impl", lambda heads, pages, rows: "kernel")
    monkeypatch.setattr(hybrid, "absorbed_decode_impl", lambda heads, pages, rows: "kernel")
    monkeypatch.setattr(la, "absorbed_decode_kernel", functools.partial(
        la.absorbed_decode_kernel, interpret=True))
    counters = lambda: dict(telemetry.observe_snapshot()["counters"])
    before = counters()
    result = fs.make_engine(FAMILY, "waves", 0).generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=4, max_tokens=24),
        jax.random.PRNGKey(3))
    assert fs.worst_difference(FAMILY, params, lora, ids, mask, result) < 2e-5
    moved = lambda name: counters()[name] - before.get(name, 0)
    assert moved(telemetry.OPS_LATENT_DECODE_LAUNCHES) == 3 * 24
    held = np.asarray([[(p + t) // 8 + 1 for t in range(24)] for p in (40, 57)])
    once = np.asarray([[5], [7]])
    assert moved("engine/latent_pages_attended") == 3 * 4 * held.sum()
    assert moved("engine/latent_pages_read") == 3 * (once + 4 * (held - once)).sum()


def test_slots_of_mixed_prompts_fetch_every_page_a_row(weights, small_pieces):
    """The refill scheduler with two prompts' candidates in one group of four
    slots: no block is every row's, so pages attended = pages read, exactly."""
    from distrl_llm_tpu import telemetry

    params, lora = weights
    ids, mask = fs.prompts((40, 57))
    before = telemetry.observe_snapshot()["counters"]
    result = fs.engine(FAMILY, "refill", 4).generate(
        params, lora, ids, mask,
        SamplingConfig(temperature=1.0, top_p=1.0, n=2, max_tokens=24),
        jax.random.PRNGKey(3))
    assert result.alive_slot_steps == 4 * 24
    after = telemetry.observe_snapshot()["counters"]
    moved = lambda name: after[name] - before.get(name, 0)
    held = sum((p + t) // 8 + 1 for t in range(24) for p in (40, 57))
    assert moved("engine/latent_pages_attended") == 3 * 2 * held
    assert moved("engine/latent_pages_read") == moved("engine/latent_pages_attended")


def test_the_pool_is_one_latent_array_a_layer_and_its_budget():
    from distrl_llm_tpu.engine.budget import page_bytes
    from distrl_llm_tpu.engine.paged_engine import _copy_pages, _grow_pool

    assert CFG.page_pool_shape(10, 8) == (10, 8, 128) and CFG.paged_layers == 3
    assert (CFG.latent_dim, CFG.latent_row) == (40, 128)  # whole 128-lane tiles
    assert page_bytes(CFG, 8) == 8 * 128 * 2 * 3  # no V, no kv-head factor
    published = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    assert (published.latent_dim, published.latent_row) == (576, 640)
    assert page_bytes(published, 128) == 128 * 640 * 2 * 7
    pool = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4, 2, 3)
    grown = _grow_pool(pool, 2)
    assert grown.shape == (6, 2, 3) and (grown[:4] == pool).all() and not grown[4:].any()
    copied = _copy_pages(grown, jnp.asarray([0, 1]), jnp.asarray([4, 5]),
                         keep_mask=jnp.asarray([True, False]))
    assert (copied[4] == pool[0]).all() and not copied[5].any()


def test_spill_and_turn_hook_refuse_too(weights):
    with pytest.raises(ValueError, match="kv_spill.*latent-attention"):
        CFG.refuse_hybrid("kv_spill (K/V pages parked in host memory)")
    engine = fs.make_engine(FAMILY, "refill", 4)
    engine.turn_hook = lambda cand, tokens: None
    params, lora = weights
    with pytest.raises(ValueError, match="turn_hook.*latent-attention"):
        engine.generate(params, lora, *fs.prompts((20,)), SamplingConfig(n=1, max_tokens=4),
                        jax.random.PRNGKey(0))


# -------------------------------------------------- the config and the loader


def test_from_hf_config_reads_the_published_file():
    cfg = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    assert cfg.latent and cfg.hybrid and cfg.model_type == "deepseek_v3"
    assert cfg.layer_kinds == ("latent",) + ("latent_moe",) * 6
    assert (cfg.head_dim, cfg.q_dim, cfg.latent_dim) == (192, 3072, 576)
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.shared_expert_size) == (64, 6, 2816)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.routed_scaling_factor) == (800000, 1e-5, 2.446)
    # what a token runs against what is held: 917M against 3,928M (+ 336M embedding)
    assert cfg.matmul_param_count == 917_110_784
    assert cfg.total_matmul_param_count + cfg.vocab_size * cfg.hidden_size == 4_263_116_800
    full = ModelConfig.from_hf_config(fs.hf_config(FAMILY, num_hidden_layers=27))
    assert full.layer_kinds.count("latent_moe") == 26


def test_a_deepseek_v3_with_a_query_rank_loads_and_equals_the_reference():
    """``q_lora_rank`` was refused by name until PR 54: the low-rank query path
    (q_a_proj, q_a_layernorm, q_b_proj) now loads, takes an adapter on both
    halves, and equals ``perfbench/reference_dsa_moe.py`` told an index that
    chooses every token (that reference with no choice to make IS a
    deepseek_v3 with a rank; ``reference_latent_moe.py`` states
    ``q_lora_rank null``)."""
    import dataclasses

    from perfbench import reference_dsa_moe

    loaded = ModelConfig.from_hf_config(fs.hf_config(FAMILY, q_lora_rank=1536))
    assert (loaded.q_lora_rank, loaded.index_topk, loaded.model_type) == (
        1536, 0, "deepseek_v3")
    cfg = dataclasses.replace(CFG, q_lora_rank=48)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), x.shape)
        if str(path[-1].key).endswith("norm") else 3.0 * x,
        init_params(jax.random.PRNGKey(0), cfg))
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
        if str(path[-1].key) == "b" else x, init_lora_params(jax.random.PRNGKey(1), cfg, 4))
    assert params["layers"]["latent"]["wq"].shape == (1, 48, 4 * 24)
    assert params["layers"]["latent"]["wq_a"].shape == (1, 64, 48)
    assert set(lora["layers"]["latent_moe"]) == {
        "wq_a", "wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down"}
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1, 256)
    got, _ = forward(params, cfg, ids, lora=lora, lora_scale=fs.LORA_SCALE)
    # the same weights under an index of one head that chooses all 40 tokens
    told = dataclasses.replace(cfg, index_heads=1, index_head_dim=8, index_topk=64)
    index = lambda n: {
        "w_index_q": jnp.ones((n, 48, 8)), "w_index_k": jnp.ones((n, 64, 8)),
        "index_k_norm": jnp.ones((n, 8)), "b_index_k": jnp.zeros((n, 8)),
        "w_index_w": jnp.ones((n, 64, 1))}
    with_index = {**params, "layers": {
        kind: {**stack, **index(stack["wq"].shape[0])}
        for kind, stack in params["layers"].items()}}
    want = reference_dsa_moe.full_logits(
        with_index, told, ids, jnp.ones_like(ids), lora=lora, lora_scale=fs.LORA_SCALE)
    np.testing.assert_allclose(got, want, atol=2e-5)


def published_state_dict(params, cfg):
    from distrl_llm_tpu.models.loading import state_dict_from_params

    sd = {f"language_model.{k}": v for k, v in state_dict_from_params(params, cfg).items()}
    sd["vision_tower.encoder.blocks.0.wqkv.weight"] = np.ones((3, 3), np.float32)
    sd["multi_modal_projector.linear_1.weight"] = np.ones((2, 2), np.float32)
    return sd


def test_the_published_names_load_into_the_stacked_tree(weights):
    """A synthetic state dict in the checkpoint's names, the vision tower's and
    the projector's tensors present: every language-model tensor is used exactly
    once, the tower's are skipped, and the loaded model is the reference's
    function (so the RoPE pair layout is the published one on both sides)."""
    from distrl_llm_tpu.models.loading import params_from_state_dict

    params, lora = weights
    sd = published_state_dict(params, CFG)
    assert sd["language_model.model.layers.1.mlp.gate.weight"].shape == (8, 64)
    assert sd["language_model.model.layers.2.mlp.gate.e_score_correction_bias"].shape == (8,)
    assert sd["language_model.model.layers.1.mlp.experts.7.down_proj.weight"].shape == (64, 32)
    assert sd["language_model.model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].shape == (40, 64)
    assert "language_model.model.layers.0.mlp.gate_proj.weight" in sd
    assert "language_model.model.layers.1.mlp.shared_experts.up_proj.weight" in sd

    class Counting(dict):
        reads: dict = {}

        def __getitem__(self, key):
            self.reads[key] = self.reads.get(key, 0) + 1
            return super().__getitem__(key)

    counted = Counting(sd)
    loaded = params_from_state_dict(counted, CFG)
    language = [k for k in sd if k.startswith("language_model.")]
    assert all(counted.reads.get(k) == 1 for k in language)
    assert not any(k.startswith(("vision_tower.", "multi_modal")) for k in counted.reads)
    for a, b in zip(jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 30), 1, 256))
    logits, _ = forward(jax.tree_util.tree_map(jnp.asarray, loaded), CFG, jnp.asarray(ids))
    got = jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], jnp.asarray(ids)[:, 1:, None], -1)[..., 0]
    want = np.asarray(ref.next_token_logprobs(
        params, CFG, jnp.asarray(ids), jnp.ones((1, 30), jnp.int32)))
    assert np.abs(np.asarray(got) - want).max() < 2e-5


@pytest.mark.parametrize("change,error,named", [
    (lambda sd: sd.pop("language_model.model.layers.2.mlp.experts.5.up_proj.weight"),
     KeyError, "experts.5.up_proj"),
    (lambda sd: sd.update({"language_model.model.layers.1.mlp.experts.8.up_proj.weight": 0}),
     ValueError, "not loaded"),
    (lambda sd: sd.update({"language_model.model.layers.9.self_attn.q_proj.weight": 0}),
     None, ""),  # a layer the cut does not run is left alone
])
def test_a_missing_or_leftover_tensor_is_a_loud_error(weights, change, error, named):
    from distrl_llm_tpu.models.loading import params_from_state_dict

    sd = published_state_dict(weights[0], CFG)
    change(sd)
    if error is None:
        params_from_state_dict(sd, CFG)
        return
    with pytest.raises(error, match=named):
        params_from_state_dict(sd, CFG)


def test_a_saved_snapshot_loads_back_as_the_same_model(weights, tmp_path):
    from distrl_llm_tpu.models.loading import load_pretrained, save_hf_checkpoint

    params, _ = weights
    save_hf_checkpoint(jax.tree_util.tree_map(np.asarray, params), CFG, str(tmp_path))
    loaded, cfg = load_pretrained(str(tmp_path))
    assert cfg == ModelConfig(**{**CFG.__dict__, "max_position_embeddings":
                                 cfg.max_position_embeddings})
    for a, b in zip(jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_adapter_factors_follow_each_kinds_shapes_and_merge(weights):
    from distrl_llm_tpu.models.lora import LATENT_TARGETS

    params, lora = weights
    assert set(lora["layers"]) == {"latent", "latent_moe"}
    for kind, width in (("latent", 128), ("latent_moe", 32)):
        stack = lora["layers"][kind]
        assert set(stack) == set(LATENT_TARGETS)  # no router, no routed expert
        assert stack["wkv_a"]["b"].shape[-1] == 40 and stack["wkv_b"]["a"].shape[1] == 32
        assert stack["wo"]["a"].shape[1] == 4 * 16  # H x v, not the query's width
        assert stack["w_gate"]["b"].shape[-1] == width
    fs.merged_equals_adapted(FAMILY, params, lora)


def test_every_new_leaf_has_a_partition_spec_and_is_whole_on_a_chip(weights):
    from jax.sharding import PartitionSpec as P

    from distrl_llm_tpu.parallel.partition import param_specs

    params, lora = weights
    specs = param_specs(params)["layers"]["latent_moe"]
    for name in ("wkv_a", "wkv_b", "kv_a_norm", "router", "e_score_bias",
                 "experts_gate", "experts_up", "experts_down"):
        leaf = params["layers"]["latent_moe"][name]
        assert specs[name] == P(*([None] * leaf.ndim)), name
    assert specs["wq"] == P(None, "fsdp", "tp") and specs["w_down"] == P(None, "tp", "fsdp")
    assert param_specs(lora)["layers"]["latent"]["wkv_b"]["a"] == P(None, "fsdp", None)
