"""A model whose layers differ in kind (MiniCPM-SALA: block-sparse attention
beside lightning linear attention) against its plain reference,
``perfbench/reference_sala.py``, at a small size on the CPU: hidden 64, 2 KV
heads, six layers ``[sparse, lin, lin, lin, lin, sparse]``, blocks of 4,
top-2, a window of 8, ``dense_len`` 16, sequences of 48-96 tokens, so that the
choice of blocks is live everywhere. Float32 throughout, seeded weights.

The learner's update and ``Trainer.train()`` with the paged engine are held by
``tests/perfbench/test_perfbench_rehearsal_sala*.py``, through the harness's
own drivers and the same reference.

This file holds the family's record and the cases of its own mechanism; the
cases every family repeats are ``tests/test_family_conformance.py``'s.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_suite as fs
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.models import ModelConfig, hybrid, init_lora_params, init_params
from perfbench import reference_sala as ref

MIXERS = ("minicpm4",) + ("lightning-attn",) * 4 + ("minicpm4",)
CFG = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=6,
    num_heads=4, num_kv_heads=2, head_dim=16, mixer_types=MIXERS,
    lightning_heads=4, lightning_head_dim=16, qk_norm=True, attn_use_rope=False,
    attn_output_gate=True, lightning_output_gate=True, lightning_output_norm=True,
    sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=4,
    sparse_topk=2, sparse_window_size=8, sparse_dense_len=16,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=32,
)


def _state_bf16(monkeypatch):
    """The lightning state rounded to bf16 after every update."""
    to_bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rounded(fn):
        def run(*a, **k):
            out, state = fn(*a, **k)
            return out, to_bf16(state)
        return run

    monkeypatch.setattr(hybrid, "lightning_step", rounded(hybrid.lightning_step))
    monkeypatch.setattr(hybrid, "lightning_chunked", rounded(hybrid.lightning_chunked))
    assert all(x.dtype == jnp.float32 for x in hybrid.init_mixer_state(CFG, 2, 64)["lin"])


def _round_check(moved, result, engine, scheduler, slots):
    # either scheduler counts its steps (the benchmark's step time and occupancy)
    assert result.steps_dispatched >= 24 * (2 if slots == 4 else 1)
    attended = moved("engine/sparse_blocks_attended")
    visible = moved("engine/sparse_blocks_visible")
    # 2 sparse layers x 2 KV heads x 8 rows x 24 steps, 5-6 blocks of 11-21
    assert 2 * 2 * 8 * 24 * 5 <= attended <= 2 * 2 * 8 * 24 * 6 < visible / 2


FAMILY = fs.Family(
    name="sala", cfg=CFG, ref=ref, config_file="minicpm-sala-L10.json",
    # projections large enough that a dropped gate or decay moves the logits
    weight_scale=3.0,
    engine_kw={"page_size": None},  # the engine's own: a page is one block
    refusals=(
        ({"model_type": "olmoe"}, "olmoe"),
        ({"mixer_types": ["minicpm4", "mamba2"]}, "mamba2"),
        ({"model_type": "minicpm_sala", "mixer_types": None}, "mixer_types"),
        ({"lightning_nkv": 8}, "lightning_nkv")),
    # prefill in segments, the prompt's state and pooled keys handed to each
    # candidate, then decoding through the cache
    rounds=(("refill", 4), ("refill", 8), ("waves", 0)), round_check=_round_check,
    # The chip's check cannot tell a lightning state kept in bf16 from the float32
    # one (PERF.md, PR 29: the rounding sits inside the bf16 program's own noise).
    # What guards the state's precision is the 2e-5 agreement of the round above,
    # so it must be able to: with the state rounded to bf16 after every update, or
    # the K/V pages and pooled keys held in bf16, the same run leaves that
    # agreement by a wide margin.
    engine_controls={"state_bf16": _state_bf16,
                     "pages_bf16": lambda monkeypatch: {"cache_dtype": jnp.bfloat16}},
    engine_limit=20 * 2e-5,
    # a group's fan-out aliases one prompt's pages and copies its state: its 16
    # candidates are what 16 rows of the same prompt give, one at a time
    fan_out={"scheduler": "refill", "slots": 16, "length": 50, "n": 16, "max_tokens": 24,
             "atol": 1e-5, "rows": True},
    state_refusals=(
        ("dense", "dense engine"), ("sharded", "dp-sharded"), ("speculation", "spec_draft"),
        ("int8_pool", "int8"), ("radix_cache", "prefix_sharing"),
        ("pool_chains", "prefix_sharing"), ("continuous_admission", "continuous_admission"),
        ("preemption", "re-prefill"),
        ("page_size", "page_size=128")),  # a page is one block: not replaced
    state_refusal_says=("lightning-attn", "minicpm4"),
)
family, small_pieces, weights = fs.fixtures(FAMILY)


# ------------------------------------------------------------- the forward


def test_forward_equals_the_reference_with_padding_on_both_sides(weights):
    params, lora = weights
    s = 80
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, s), 0, 256))
    mask = np.ones((3, s), np.int32)
    mask[1, :7] = 0  # a left-padded prompt: blocks count from the first real token
    mask[1, 70:] = 0
    mask[2, 60:] = 0
    got = fs.forward_logprobs(FAMILY, params, lora, ids, mask)
    want = fs.reference_logprobs(FAMILY, params, lora, ids, mask)
    real = (mask[:, 1:] > 0) & (mask[:, :-1] > 0)
    assert np.abs(got - want)[real].max() < 2e-5


@pytest.mark.parametrize("control", ["dense", "no_gate"])
def test_the_forward_can_tell_each_mechanism(weights, control):
    """What the chip's check must be able to tell is alive at this size too:
    dense attention in place of the choice and a gate held at one half each
    move the log-probabilities by far more than the agreement above."""
    params, lora = weights
    cfg = CFG
    if control == "dense":
        cfg = dataclasses.replace(CFG, sparse_dense_len=4096)
    else:
        params = {**params, "layers": {
            kind: {**stack, "wz": jnp.zeros_like(stack["wz"])}
            for kind, stack in params["layers"].items()}}
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 80), 0, 256))
    run = lambda c, p: jax.nn.log_softmax(fs.forward_both(
        FAMILY, p, lora, ids, np.ones_like(ids), c)[1], axis=-1)
    moved = np.abs(np.asarray(run(cfg, params) - run(CFG, weights[0]))).mean()
    assert moved > 1e-3, moved


# ---------------------------------------------------------------- the ops


@pytest.mark.parametrize("chunk", [5, 16, 64])
def test_chunked_linear_attention_equals_one_step_at_a_time(chunk):
    from distrl_llm_tpu.ops.linear_attention import lightning_chunked, lightning_step

    b, s, h, d = 2, 37, 4, 16
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, s, h, d)) for i in range(3))
    rates = jnp.asarray(CFG.lightning_decay_rates()[1])
    valid = np.ones((b, s), np.int32)
    valid[0, :4] = 0  # left padding is no step at all
    valid[1, 30:] = 0  # nor is right padding: the state stops at the last real token
    out, state = lightning_chunked(q, k, v, rates, jnp.asarray(valid), chunk=chunk)
    carry = jnp.zeros((b, h, d, d))
    for t in range(s):
        o_t, new = lightning_step(q[:, t], k[:, t], v[:, t], rates, carry)
        keep = jnp.asarray(valid[:, t] > 0)[:, None, None, None]
        carry = jnp.where(keep, new, carry)
        real = valid[:, t] > 0
        np.testing.assert_allclose(
            np.asarray(out[:, t])[real], np.asarray(o_t)[real], atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(carry), atol=2e-5)
    # a second call continues the first: the state is the whole memory
    first, mid = lightning_chunked(
        q[:, :20], k[:, :20], v[:, :20], rates, jnp.asarray(valid[:, :20]), chunk=chunk)
    second, end = lightning_chunked(
        q[:, 20:], k[:, 20:], v[:, 20:], rates, jnp.asarray(valid[:, 20:]),
        state=mid, chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([first, second], axis=1)), np.asarray(out), atol=2e-5)
    np.testing.assert_allclose(np.asarray(end), np.asarray(state), atol=2e-5)


def test_the_chosen_blocks_are_the_references_and_the_choice_is_live():
    from distrl_llm_tpu.ops.sparse_attention import block_count, choose_blocks, pool_keys

    s, heads, kh, d = 96, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (1, s, heads, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, s, kh, d))
    pos = jnp.arange(s)[None, :]
    n_blocks = block_count(s, CFG)
    pooled = pool_keys(k, CFG)
    got = np.asarray(choose_blocks(q, pooled, pos, CFG, n_blocks))[0]
    want = np.asarray(ref.chosen_blocks(q[0], pooled[0], pos[0], CFG, n_blocks))
    np.testing.assert_array_equal(got, want)
    causal = np.arange(n_blocks)[None, :] <= (np.arange(s) // 4)[:, None]
    late = np.arange(s) >= 40
    # 1 first block + 2-3 window blocks + 2 chosen, of 11-24 visible
    assert (got[late].sum(-1) <= 6).all() and (causal[late].sum(-1) >= 11).all()
    assert (got[:16] == causal[:16, None, :]).all()  # within dense_len: every block
    assert (got[late][:, 0] != got[late][:, 1]).any()  # the KV heads choose apart


def test_a_segments_choice_is_the_mask_sparse_attend_makes():
    """``segment_choice`` (a block of queries at a time, every row's together,
    the last block ragged) is ``choose_blocks``' choice bit for bit, as the
    mask ``sparse_attend`` makes of it: each key of a chosen block at or
    before the query, a KV head's queries together."""
    from distrl_llm_tpu.ops.sparse_attention import (
        block_count, choose_blocks, pool_keys, segment_choice,
    )

    t, s, heads, kh, d = 96, 32, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (2, s, heads, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, t, kh, d))
    pos = 48 + jnp.broadcast_to(jnp.arange(s), (2, s))  # the fourth segment of six
    pooled = pool_keys(k, CFG)
    got = np.asarray(segment_choice(q, pooled, pos, CFG, t, q_block=12))
    blocks = np.asarray(choose_blocks(q, pooled, pos, CFG, block_count(t, CFG)))
    allowed = np.repeat(blocks, CFG.sparse_block_size, axis=-1)[..., :t] & (
        np.arange(t) <= np.asarray(pos)[:, :, None, None])  # [B, S, K, T], as PR 66 had it
    assert got.dtype == bool and got.shape == (2, kh, s, t)
    np.testing.assert_array_equal(got, allowed.transpose(0, 2, 1, 3))
    assert (got[:, 0] != got[:, 1]).any() and not got[..., 80:].any()


def test_a_sparse_layers_segments_are_its_full_mode(monkeypatch):
    """A sparse layer's attention over a row of 64 tokens, ``full`` mode
    (``sparse_attend``: the learner's, masked full scores) against four
    prefill segments of 16 over pages (the choice as the folds' mask, each
    block of keys folded by the kernel, interpreted; the form a CPU takes is
    held by the engine's rounds against the reference and by
    ``tests/test_softmax_fold.py``), float32, to this file's 2e-5: the first
    segment within ``dense_len``, the others past it."""
    from distrl_llm_tpu.ops import latent_attention as la
    from distrl_llm_tpu.ops.sparse_attention import pooled_count

    monkeypatch.setattr(la, "expanded_segment_impl", lambda q_nope, v_dim: "kernel")
    monkeypatch.setattr(la, "expanded_fold_kernel", functools.partial(
        la.expanded_fold_kernel, interpret=True))
    b, t, seg, ps, kh, d = 2, 64, 16, CFG.sparse_block_size, CFG.num_kv_heads, CFG.head_dim
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, t, h, d))
               for i, h in enumerate((CFG.num_heads, kh, kh)))
    arange = lambda n: jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    with jax.default_matmul_precision("highest"):
        want, _, _ = hybrid._sparse_mix(q, k, v, None, cfg=CFG, mode="full", env={
            "q_pos": arange(t), "valid": jnp.ones((b, t), jnp.int32)})
        pool = jnp.zeros((kh, b * t // ps, ps, d))
        cache = (pool, pool, jnp.zeros((b, pooled_count(t, CFG), kh, d)))
        table = jax.random.permutation(jax.random.PRNGKey(3), b * t // ps).reshape(b, -1)
        for start in range(0, t, seg):
            at = slice(start, start + seg)
            got, cache, _ = hybrid._sparse_mix(
                q[:, at], k[:, at], v[:, at], cache, cfg=CFG, mode="segment", env={
                    "segment_start": jnp.int32(start), "q_pos": start + arange(seg),
                    "page_indices": table.astype(jnp.int32), "page_size": ps})
            np.testing.assert_allclose(got, want[:, at], atol=2e-5)
    assert la.dispatch_choices[la.dispatch_key(
        CFG.num_heads, d, 0, d, seg, jnp.float32)] == "kernel"


@pytest.mark.parametrize("kh", [2, 4])
def test_the_decode_steps_pooled_key_is_a_plain_loops(kh):
    """``update_pooled`` reads the last ``kernel`` keys of each (row, KV head)
    back from the pages, the head as an index (ops/paged.py says why): the
    pooled keys it leaves are those of a loop over rows and heads, bit for
    bit, and a row whose count completes no pooled key is left alone."""
    from distrl_llm_tpu.ops.sparse_attention import update_pooled

    rng = np.random.default_rng(kh)
    rows, width, ps, hd, n_pooled = 5, 4, 4, 16, 8
    k_pages = rng.normal(size=(kh, rows * width + 1, ps, hd)).astype(np.float32)
    table = rng.permutation(rows * width + 1)[: rows * width].reshape(rows, width)
    pooled = rng.normal(size=(rows, n_pooled, kh, hd)).astype(np.float32)
    # kernel 4, stride 2: counts 4, 6, 10 complete keys 0, 1, 3 (the last
    # across a page boundary); 7 and 3 complete none
    lengths = np.array([4, 6, 10, 7, 3], np.int32)
    got = update_pooled(jnp.asarray(pooled), jnp.asarray(k_pages), jnp.asarray(lengths),
                        jnp.asarray(table.astype(np.int32)), CFG)
    want = pooled.copy()
    for r, (n, j) in enumerate(zip(lengths, [0, 1, 3, None, None])):
        if j is None:
            continue
        for h in range(kh):
            keys = np.stack([k_pages[h, table[r, t // ps], t % ps] for t in range(n - 4, n)])
            want[r, j, h] = np.asarray(jnp.asarray(keys).mean(axis=0))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (want != pooled).any()


def _decode_case(lengths, dtype, seed=0):
    """A pool, a page table a row and pooled keys for ``sparse_decode`` at this
    file's sizes (blocks of 4, 2 KV heads of 2 query heads each)."""
    rng = np.random.default_rng(seed)
    kh, g, hd, ps = CFG.num_kv_heads, CFG.num_heads // CFG.num_kv_heads, CFG.head_dim, 4
    rows, width = len(lengths), max(lengths) // ps + 2
    total = rows * width + 3
    table = rng.permutation(total)[: rows * width].reshape(rows, width).astype(np.int32)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    from distrl_llm_tpu.ops.sparse_attention import pooled_count

    return dict(
        q=normal(rows, kh * g, hd), k_pages=normal(kh, total, ps, hd),
        v_pages=normal(kh, total, ps, hd),
        pooled=normal(rows, pooled_count(width * ps, CFG), kh, hd),
        lengths=jnp.asarray(lengths, jnp.int32), page_indices=jnp.asarray(table))


DECODE_CASES = {
    # (the query's position a row, alive or None, chosen blocks a (row, KV head))
    "over_dense_len": ((70, 95, 49, 83, 90), None, (5, 6)),  # count <= n_sel = 6 of 13-24
    "every_block_of_a_short_context": ((10, 15, 3, 7, 12), None, (1, 2, 3, 4)),
    "a_blocks_first_and_last_position": ((64, 67, 68, 71, 16), None, (5, 6)),
    "a_dead_slot_and_a_length_of_0": ((70, 0, 0, 33, 95), (1, 0, 1, 1, 0), (1, 5, 6)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_the_decode_launch_is_the_plain_form_over_the_chosen_pages(case, dtype, monkeypatch):
    """``attend_pages_kernel`` under the Pallas interpreter against
    ``sparse_decode``'s plain form: the same output from the same page lists,
    over pools in which every (KV head, page) that no live entry of a list
    names is NaN: what lies past a list's count is neither fetched into the
    result nor computed on. Two pages a step, so a list is one to three steps
    and its last live page (the query's own block, the one masked) falls on
    either place. Then the whole of ``sparse_decode`` told to take the launch:
    the same output and ``stats`` equal to the integer."""
    from distrl_llm_tpu.ops import sparse_attention as sa

    lengths, alive, counts = DECODE_CASES[case]
    x = _decode_case(lengths, dtype)
    alive = None if alive is None else jnp.asarray(alive, bool)
    want, want_stats = sa.sparse_decode(**x, cfg=CFG, alive=alive)
    pages, _, count, _ = sa.chosen_pages(
        x["q"], x["pooled"], x["lengths"], x["page_indices"], CFG, alive)
    assert sorted(set(np.asarray(count).ravel().tolist())) == list(counts)
    assert pages.shape[-1] >= max(counts)
    if case == "over_dense_len":  # the KV heads choose apart
        assert (np.asarray(pages[:, 0]) != np.asarray(pages[:, 1])).any()
    live = np.asarray(count) * (1 if alive is None else np.asarray(alive)[:, None])
    named = np.zeros(x["k_pages"].shape[:2], bool)
    for r, k in np.ndindex(*live.shape):
        named[k, np.asarray(pages)[r, k, : live[r, k]]] = True
    assert not named.all()
    poison = lambda pool: jnp.where(jnp.asarray(named)[:, :, None, None], pool, jnp.nan)
    launch = functools.partial(sa.attend_pages_kernel, pages_per_step=2, interpret=True)
    got = launch(x["q"], poison(x["k_pages"]), poison(x["v_pages"]), pages,
                 jnp.asarray(live), x["lengths"])
    tol = dict(atol=2e-5) if dtype == jnp.float32 else dict(atol=2**-6, rtol=2**-7)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rows = np.ones(len(lengths), bool) if alive is None else np.asarray(alive)
    np.testing.assert_allclose(got[rows], want[rows], **tol)
    assert (got[~rows] == 0).all() and np.isfinite(want).all()
    # the whole step through the dispatcher, as a TPU backend takes it
    monkeypatch.setattr(sa, "sparse_decode_impl", lambda q, k_pages, n_sel: "kernel")
    monkeypatch.setattr(sa, "attend_pages_kernel", launch)
    out, stats = sa.sparse_decode(**x, cfg=CFG, alive=alive)
    np.testing.assert_array_equal(np.asarray(out, np.float32), got)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))


@pytest.mark.parametrize("backend,head,dtype,want", [
    ("tpu", 128, jnp.bfloat16, "kernel"),
    ("tpu", 128, jnp.float32, "kernel"),
    ("tpu", 64, jnp.bfloat16, "plain"),  # not a whole 128-lane tile
    ("tpu", 128, jnp.int8, "plain"),  # quantized pages
    ("cpu", 128, jnp.bfloat16, "plain"),
])
def test_the_decode_attention_takes_the_form_it_can_observe(
        monkeypatch, backend, head, dtype, want):
    from distrl_llm_tpu.ops import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = dataclasses.replace(CFG, head_dim=head)
    rows, kh, ps = 2, 2, 4
    q = jnp.zeros((rows, 4, head), jnp.float32 if dtype == jnp.int8 else dtype)
    pool = jnp.zeros((kh, 8, ps, head), dtype)
    assert sa.sparse_decode_impl(q, pool, 2) == want
    assert sa.sparse_decode_impl(q, pool, 2**20) == "plain"  # lists no VMEM holds
    # and sparse_decode records it; the launch itself is not run off the TPU
    seen = []
    monkeypatch.setattr(sa, "attend_pages_kernel", lambda *a: seen.append("kernel") or a[0])
    monkeypatch.setattr(sa, "attend_pages_plain", lambda *a: seen.append("plain") or a[0])
    monkeypatch.setattr(sa, "dispatch_choices", {})
    pooled = jnp.zeros((rows, sa.pooled_count(8, cfg), kh, head), q.dtype)
    sa.sparse_decode(q, pool, pool, pooled, jnp.asarray([5, 7]),
                     jnp.arange(4, dtype=jnp.int32).reshape(2, 2), cfg)
    assert seen == [want]
    assert sa.dispatch_choices == {sa.dispatch_key(4, kh, head, ps, dtype): want}


@pytest.mark.parametrize("ran,steps,want", [
    ("kernel", 512, 2 * 512), ("plain", 512, 0), (None, 512, 0), ("kernel", 0, None)])
def test_the_counter_is_sparse_layers_times_steps_where_the_launch_ran(
        monkeypatch, ran, steps, want):
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.ops import sparse_attention as sa

    assert CFG.kind_count("sparse") == 2
    key = sa.dispatch_key(CFG.num_heads, CFG.num_kv_heads, CFG.head_dim, 4, jnp.bfloat16)
    monkeypatch.setattr(sa, "dispatch_choices", {} if ran is None else {key: ran})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_sparse_telemetry(CFG, steps, jnp.bfloat16)
    assert filed == ([] if want is None else [("ops/sparse_kernel_steps", want)])
    # pages of another dtype are another geometry; a model without such layers files nothing
    filed.clear()
    paged_engine._record_sparse_telemetry(CFG, 512, jnp.float32)
    from distrl_llm_tpu.models.configs import PRESETS
    paged_engine._record_sparse_telemetry(PRESETS["tiny"], 512, jnp.bfloat16)
    assert filed == [("ops/sparse_kernel_steps", 0)]


@pytest.mark.parametrize("ran,longest,want", [
    ("kernel", None, 2 * 210), ("kernel", 3, 2 * 6), ("xla", None, 0), (None, None, 0)])
def test_the_fold_counter_is_sparse_layers_times_the_folds(monkeypatch, ran, longest, want):
    """``ops/softmax_kernel_folds`` counts a sparse layer's folds with the
    full-attention layers' (one path over the rows' pages): 2 layers x segment
    j's j + 1 of the long-context cell's 20 segments of 1,024 in pages of 64,
    420 a round, where ``expanded_segment`` recorded the kernel under the
    layers' geometry; to the longest row's segments where the stages end
    sooner; 0 where it took the XLA form or traced nothing."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine
    from distrl_llm_tpu.ops import latent_attention as la

    cfg = dataclasses.replace(CFG, head_dim=128, sparse_block_size=64)
    key = la.dispatch_key(cfg.num_heads, 128, 0, 128, 1024, jnp.bfloat16)
    monkeypatch.setattr(la, "dispatch_choices", {} if ran is None else {key: ran})
    filed = []
    monkeypatch.setattr(telemetry, "counter_add", lambda name, value: filed.append((name, value)))
    paged_engine._record_fold_telemetry(cfg, 320, 64, jnp.bfloat16, longest)
    assert filed == [("ops/softmax_kernel_folds", want)]


# ------------------------------------------------------------ the refusals


def test_spill_and_turn_hook_refuse_too(weights):
    from distrl_llm_tpu.models.configs import TINY

    with pytest.raises(ValueError, match="kv_spill.*lightning-attn"):
        CFG.refuse_hybrid("kv_spill (K/V pages parked in host memory)")
    TINY.refuse_hybrid("anything")  # a dense model is refused nothing
    engine = fs.make_engine(FAMILY, "refill", 4)
    engine.turn_hook = lambda cand, tokens: None
    params, lora = weights
    with pytest.raises(ValueError, match="turn_hook.*minicpm4"):
        engine.generate(params, lora, *fs.prompts((20,)), SamplingConfig(n=1, max_tokens=4),
                        jax.random.PRNGKey(0))


# -------------------------------------------------- the config and the loader


def test_from_hf_config_reads_the_published_file():
    cfg = ModelConfig.from_hf_config(fs.hf_config(FAMILY))
    assert cfg.hybrid and cfg.model_type == "minicpm_sala"
    assert len(cfg.mixer_types) == 32 and cfg.num_layers == 10
    assert cfg.layer_kinds == ("sparse",) + ("lightning",) * 8 + ("sparse",)
    assert cfg.layer_runs == (
        ("sparse", 0, 0, 1), ("lightning", 1, 0, 8), ("sparse", 9, 1, 1))
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.lightning_heads, cfg.lightning_head_dim) == (32, 128)
    assert (cfg.sparse_block_size, cfg.sparse_topk, cfg.sparse_dense_len) == (64, 64, 8192)
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12 and cfg.logit_scale == 1 / 16
    rates = cfg.lightning_decay_rates()
    assert rates.shape == (8, 32)  # layer 1's first head: 2^(-8/32) (1 - 1/31 + 1e-5)
    assert abs(rates[0, 0] - 2 ** -0.25 * (1 - 1 / 31 + 1e-5)) < 1e-6
    # 8 x 285.2M + 2 x 253.8M + the head's 300.8M (the embedding is no matmul)
    assert round(cfg.matmul_param_count / 1e6) == round(
        8 * 285.2128 + 2 * 253.7554 + 300.843)


def test_checkpoint_names_map_for_both_layer_kinds():
    """A synthetic state dict under the checkpoint's tensor names, through the
    loader and back: every tensor lands in its kind's stack, in layer order."""
    from distrl_llm_tpu.models.loading import params_from_state_dict, state_dict_from_params

    params = jax.tree_util.tree_map(
        np.asarray, init_params(jax.random.PRNGKey(3), CFG))
    sd = state_dict_from_params(params, CFG)
    assert sd["model.layers.0.self_attn.o_gate.weight"].shape == (64, 64)
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (32, 64)  # 2 KV heads
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (64, 64)  # every head
    assert sd["model.layers.2.self_attn.o_norm.weight"].shape == (64,)
    assert "model.layers.0.self_attn.o_norm.weight" not in sd  # sparse: no output norm
    assert sd["model.layers.5.self_attn.q_norm.weight"].shape == (16,)
    back = params_from_state_dict(sd, CFG)
    jax.tree_util.tree_map(np.testing.assert_array_equal, back, params)
    np.testing.assert_array_equal(  # model layer 5 is the SECOND sparse layer
        sd["model.layers.5.mlp.up_proj.weight"].T, params["layers"]["sparse"]["w_up"][1])
    del sd["model.layers.3.self_attn.o_gate.weight"]
    with pytest.raises(KeyError, match="layers.3.self_attn.o_gate"):
        params_from_state_dict(sd, CFG)


def test_adapter_factors_follow_each_kinds_shapes_and_merge():
    from distrl_llm_tpu.models.lora import merge_lora
    from distrl_llm_tpu.parallel.partition import param_specs

    lora = init_lora_params(jax.random.PRNGKey(0), CFG, 4)
    assert set(lora["layers"]) == {"sparse", "lightning"}
    assert lora["layers"]["sparse"]["wk"]["b"].shape == (2, 4, 32)
    assert lora["layers"]["lightning"]["wk"]["b"].shape == (4, 4, 64)
    assert "wz" not in lora["layers"]["sparse"]  # the gate is frozen
    params = init_params(jax.random.PRNGKey(1), CFG)
    lora["layers"]["lightning"]["wk"]["b"] = jnp.ones((4, 4, 64))
    merged = merge_lora(params, lora, alpha=8.0)
    assert not np.allclose(merged["layers"]["lightning"]["wk"], params["layers"]["lightning"]["wk"])
    np.testing.assert_array_equal(merged["layers"]["sparse"]["wk"], params["layers"]["sparse"]["wk"])
    specs = param_specs(params)
    assert tuple(specs["layers"]["lightning"]["wz"]) == (None, "fsdp", "tp")
    assert tuple(specs["layers"]["sparse"]["q_norm"]) == (None, None)
    assert tuple(param_specs(lora)["layers"]["sparse"]["wo"]["a"]) == (None, "tp", None)
