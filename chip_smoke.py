#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, one TPU v5e chip, no arguments: drives the trainer's main path
(rollout → reward → shaping → learner update → weight push → next rollout under
the new adapter version) at the full width of Qwen2.5-0.5B with seeded random
weights, through ``train_distributed.run_smoke`` — the same assembly
``python train_distributed.py --smoke`` runs on the CPU at tiny size.

Each phase prints one JSON object with its seconds; any phase that fails ends
the script with a non-zero code. With no accelerator (or ``JAX_PLATFORMS=cpu``)
it fails at the ``device`` phase and prints no result. The last line of a
passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``--chips 4`` (never given by the driver) runs one other thing and nothing
else: the same seeded 2-step paged run timeshared on one of four devices and
role-split 2 actors + 2 learners, compared with each other.

The cuts from the reference volume, stated here and in the output: prompts
≤ 256 and answers ≤ 256 tokens (reference 350 / 1,200), 8 prompts × 8
candidates (reference 30 × 16), LoRA rank 16, 3 train steps. Depth and width
are Qwen2.5-0.5B's own.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

from distrl_llm_tpu.telemetry import CompileLog  # the program's one compile listener

#: bf16 has 8 bits of mantissa (one ulp is 2^-8 ≈ 0.4% of a value); the two
#: four-chip runs reduce in different orders, so per-step losses may sit a few
#: ulps apart, and Adam's sign-like first steps turn a last-bit difference in
#: a near-zero gradient into a full ±lr step of that one element
LOSS_RTOL = 0.05
ADAPTER_REL_L2_TOL = 0.25


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------------ kernels


def _paged_case(key, *, rows, heads, kv_heads, head_dim, page_size, pps,
                quantized):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops.paged import (
        dispatch_choice_key, dispatch_choices, make_page_table,
        paged_attention_op, paged_attention_reference, quantize_pages,
        resolve_paged_impl,
    )

    kq, kk, kv, kl = jax.random.split(key, 4)
    shape = (kv_heads, rows * pps, page_size, head_dim)
    q = jax.random.normal(kq, (rows, heads, head_dim), jnp.bfloat16)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16)
    if quantized:
        k_pages, v_pages = quantize_pages(k_pages), quantize_pages(v_pages)
    lengths = jax.random.randint(kl, (rows,), 1, pps * page_size + 1)
    table = jnp.asarray(make_page_table(rows, pps * page_size, page_size))
    got = jax.jit(paged_attention_op)(q, k_pages, v_pages, lengths, table)
    want = paged_attention_reference(q, k_pages, v_pages, lengths, table)
    err = float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32)
    )))
    ran = dispatch_choices[dispatch_choice_key(
        quantized=quantized, num_kv_heads=kv_heads,
        num_groups=heads // kv_heads, head_dim=head_dim,
        page_size=page_size, pps=pps,
    )]
    assert ran == resolve_paged_impl("auto"), ran
    assert np.isfinite(err) and err < 5e-2, f"paged {ran} max|err| {err}"
    return {"impl": ran, "max_abs_err": round(err, 5)}


def _sampler_case(key, logits_dtype, *, rows, vocab):
    """The fused sampler, compiled, against the multi-pass reference: greedy
    tokens bit for bit, every logprob against ``token_logprob``, sampled
    tokens inside the reference nucleus, and the empirical distribution of
    many draws from one row against the reference probabilities."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops.sampling import (
        sample_with_logprob, token_logprob, top_p_filter_bisect,
    )

    k_logits, k_row, k1, k2 = jax.random.split(key, 4)
    logits = (3.0 * jax.random.normal(k_logits, (rows, vocab))).astype(
        logits_dtype
    )
    fused = jax.jit(
        lambda rng, lg, t, p: sample_with_logprob(
            rng, lg, t, p, capture_logprob=True, impl="fused"
        )
    )
    # greedy: the token is the argmax, bit for bit
    tok, logp = fused(k1, logits, 0.0, 0.95)
    np.testing.assert_array_equal(
        np.asarray(tok), np.asarray(jnp.argmax(logits, axis=-1))
    )
    np.testing.assert_allclose(
        np.asarray(logp), np.asarray(token_logprob(logits, tok)),
        rtol=1e-4, atol=1e-4,
    )
    # sampled: inside the nucleus, with the raw-basis logprob of what it drew
    t, p = 1.2, 0.95
    tok, logp = fused(k2, logits, t, p)
    tok_h = np.asarray(tok)
    assert ((0 <= tok_h) & (tok_h < vocab)).all()
    np.testing.assert_allclose(
        np.asarray(logp), np.asarray(token_logprob(logits, tok)),
        rtol=1e-4, atol=1e-4,
    )
    kept = np.asarray(
        top_p_filter_bisect(logits.astype(jnp.float32) / t, p)
    ) > -1e29
    outside = int((~kept[np.arange(rows), tok_h]).sum())
    # a token tied with the bisected threshold may sit on either side of it
    assert outside <= max(1, rows // 32), f"{outside}/{rows} outside nucleus"
    # distribution: many draws of ONE peaked row
    draws = 1024
    row = jnp.full((vocab,), -30.0, jnp.float32).at[:16].set(
        2.0 * jax.random.normal(k_row, (16,))
    )
    tiled = jnp.tile(row[None, :].astype(logits_dtype), (draws, 1))
    tok, _ = fused(k1, tiled, t, p)
    ref = jax.nn.softmax(
        top_p_filter_bisect(tiled[:1].astype(jnp.float32) / t, p)[0]
    )
    emp = np.bincount(np.asarray(tok), minlength=vocab) / draws
    tv = 0.5 * float(np.abs(emp - np.asarray(ref)).sum())
    support = int((np.asarray(ref) > 0).sum())
    assert tv < 3.0 * (support / draws) ** 0.5, f"TV {tv} over {support}"
    return {"outside_nucleus": outside, "tv_distance": round(tv, 4),
            "nucleus_support": support}


def _latent_attention_case(key, *, rows, heads, nope, rope, v_dim, rank, context):
    """Absorbed decode attention over latent rows (bf16, folded in two blocks)
    against the expanded form, K and V rebuilt per head, in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops import latent_attention as la

    kw, kl, kq, kn = jax.random.split(key, 4)
    w = (0.02 * jax.random.normal(kw, (rank, heads * (nope + v_dim)))).astype(jnp.bfloat16)
    latent = jax.random.normal(kl, (rows, context, rank + rope), jnp.bfloat16)
    q = jax.random.normal(kq, (rows, heads, nope + rope), jnp.bfloat16)
    seen = jnp.arange(context)[None, :] < jax.random.randint(
        kn, (rows, 1), context // 2, context + 1)
    w_k, w_v = la.split_kvb(w, heads, nope, v_dim)
    scale = (nope + rope) ** -0.5

    @jax.jit
    def absorbed(q, latent, seen):
        q_row = la.absorbed_query(q[..., :nope], q[..., nope:], w_k)
        carry, half = None, context // 2
        for cut in (slice(0, half), slice(half, context)):
            carry = la.absorbed_attention(q_row, latent[:, cut], seen[:, cut], scale, carry)
        return la.absorbed_output(carry, w_v, jnp.float32)

    with jax.default_matmul_precision("highest"):
        f32 = latent.astype(jnp.float32)
        kv = (f32[..., :rank] @ w.astype(jnp.float32)).reshape(
            rows, context, heads, nope + v_dim)
        qf = q.astype(jnp.float32)[:, None]
        want = la.expanded_finish(la.expanded_attention(
            qf[..., :nope], qf[..., nope:], kv, f32[..., rank:], seen[:, None, :]),
            jnp.float32)[:, 0]
    err = float(jnp.max(jnp.abs(absorbed(q, latent, seen) - want)))
    assert np.isfinite(err) and err < 5e-2, f"absorbed latent attention max|err| {err}"
    return {"max_abs_err": round(err, 5), "context": context}


def _shared_prefix_attention_case(key, *, rows, heads, nope, rope, v_dim, rank,
                                  latent_row, prompt, page, per, choose=None):
    """Absorbed decode attention through the page pool (bf16), ``rows``
    candidates of ONE prompt laid out as the engine's fan-out lays them out
    (its full pages shared, the partial page and a ragged answer private), as
    ``absorbed_decode`` dispatches it (the one Mosaic launch over the pool on
    a TPU, the XLA walk elsewhere; ``impl`` says which ran): the walk that
    reads the shared columns once a group, against the expanded form over
    each row's own gathered context in float32. With ``choose`` every row
    attends a drawn share of what it sees (a learned index's mask)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.engine.paged_engine import _page_table_rows
    from distrl_llm_tpu.ops import latent_attention as la

    kw, kl, kq, kn = jax.random.split(key, 4)
    w = (0.02 * jax.random.normal(kw, (rank, heads * (nope + v_dim)))).astype(jnp.bfloat16)
    prompt_pages, private_pages = -(-prompt // page), 3
    table = _page_table_rows(
        jnp.zeros((rows,), jnp.int32), jnp.full((rows,), prompt // page),
        prompt_pages + jnp.arange(rows) * private_pages,
        prompt_pages=prompt_pages, private_pages=private_pages)
    pool = jax.random.normal(
        kl, (prompt_pages + rows * private_pages, page, latent_row), jnp.bfloat16)
    pool = pool.at[..., rank + rope:].set(0)  # a row is [c, k_pe] and zeros
    q = jax.random.normal(kq, (rows, heads, nope + rope), jnp.bfloat16)
    # a row's newest position: the prompt and up to two pages of answer
    lengths = prompt + jax.random.randint(kn, (rows,), 0, 2 * page)
    w_k, w_v = la.split_kvb(w, heads, nope, v_dim)
    scale = (nope + rope) ** -0.5
    # as ``hybrid._latent_page_walk`` sizes it: the launch shares by the column
    wide = (1 if la.absorbed_decode_impl(heads, pool, rows) == "kernel"
            else la.shared_pages_per_block(rows, heads, page, per, table.shape[1]))
    walk = la.shared_page_walk(table, lengths, page_size=page, wide=wide, rows=rows)
    positions = jnp.arange(walk.cols.shape[1] * page)
    sees = positions[None, :] <= lengths[:, None]
    chosen = None
    if choose is not None:
        sees &= jax.random.uniform(jax.random.fold_in(key, 1), sees.shape) < choose
        chosen = sees.astype(la.FOLD_MASK_DTYPE)

    @jax.jit
    def absorbed(q, pool, walk, lengths, chosen):
        q_row = la.absorbed_query(q[..., :nope], q[..., nope:], w_k)
        q_row = jnp.pad(q_row, ((0, 0), (0, 0), (0, latent_row - rank - rope)))
        carry = la.absorbed_decode(
            q_row, pool, walk, lengths, scale, chosen, rank=rank, per=per, wide=wide,
            rows=rows)
        return la.absorbed_output(carry, w_v, jnp.float32)

    @jax.jit
    def expanded(q, pool, table_row, seen):  # one row: K and V of its context
        f32 = pool[table_row].reshape(1, -1, latent_row).astype(jnp.float32)
        kv = (f32[..., :rank] @ w.astype(jnp.float32)).reshape(1, -1, heads, nope + v_dim)
        qf = q.astype(jnp.float32)[None, None]
        return la.expanded_finish(la.expanded_attention(
            qf[..., :nope], qf[..., nope:], kv, f32[..., rank: rank + rope],
            seen[None, None, :f32.shape[1]]), jnp.float32)[0, 0]

    got = absorbed(q, pool, walk, lengths, chosen)
    ran = la.dispatch_choices[la.decode_dispatch_key(heads, latent_row, page, pool.dtype)]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([expanded(q[r], pool, table[r], sees[r]) for r in range(rows)])
    err = float(jnp.max(jnp.abs(got - want)))
    attended, read = (int(x) for x in walk.stats)
    assert int(walk.shared[0]) == prompt // page // wide, "the prompt's blocks are not read once"
    assert np.isfinite(err) and err < 5e-2, f"shared-prefix latent attention {ran} max|err| {err}"
    return {"impl": ran, "max_abs_err": round(err, 5), "shared_blocks": int(walk.shared[0]),
            "pages_attended": attended, "pages_read": read}


def _expert_layer_case(key, *, tokens, hidden, width, experts, per_token):
    """An expert layer's routed part (bf16; ``moe.expert_form`` says the form
    from the call's shapes: every expert on every token, or single-expert
    blocks of sorted pairs) against every pair computed one expert at a time in
    float32; no pair may be dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.models import moe

    kh, kg, ku, kd, ki, kw = jax.random.split(key, 6)
    h = jax.random.normal(kh, (tokens, hidden), jnp.bfloat16)
    draw = lambda k, shape: (0.02 * jax.random.normal(k, shape)).astype(jnp.bfloat16)
    stack = {"gate": draw(kg, (experts, hidden, width)),
             "up": draw(ku, (experts, hidden, width)),
             "down": draw(kd, (experts, width, hidden))}
    idx = jnp.argsort(jax.random.uniform(ki, (tokens, experts)), axis=-1)[:, :per_token]
    w = jax.random.uniform(kw, (tokens, per_token), jnp.float32, 0.2, 1.0)
    got, load, blocks = jax.jit(lambda h, idx, w: moe.routed_experts(
        h, idx.astype(jnp.int32), w, stack, n_experts=experts))(h, idx, w)
    comb = jnp.zeros((tokens, experts), jnp.float32).at[
        jnp.arange(tokens)[:, None], idx].set(w)
    with jax.default_matmul_precision("highest"):
        hf = h.astype(jnp.float32)
        want = sum(
            comb[:, e, None] * ((jax.nn.silu(hf @ stack["gate"][e].astype(jnp.float32))
                                 * (hf @ stack["up"][e].astype(jnp.float32)))
                                @ stack["down"][e].astype(jnp.float32))
            for e in range(experts))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert int(load.sum()) == tokens * per_token, "a token-expert pair was dropped"
    assert np.isfinite(err) and err < 5e-2, f"expert layer max|err| {err}"
    return {"max_abs_err": round(err, 5), "pairs": int(load.sum()),
            "fullest_expert": int(load.max()), "blocks_run_laid": blocks.tolist(),
            "form": "grouped" if moe.expert_form(tokens, per_token, experts) else "dense"}


def _delta_step_case(key, *, rows, heads, head_dim, steps=4):
    """The one-token delta rule as ``delta_step`` dispatches it (the Mosaic
    kernel on a TPU at heads of 128, float32 throughout) against the plain
    form, ``steps`` tokens from one carried state; what ran is read from the
    dispatch record."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops import delta_attention as da

    ks = jax.random.split(key, 6)
    shape = (steps, rows, heads, head_dim)
    q = da.l2norm(jax.random.normal(ks[0], shape)) * head_dim ** -0.5
    k = da.l2norm(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -jax.random.uniform(ks[3], shape, minval=1e-3, maxval=0.15)  # a_t in 0.86-0.999
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], shape[:3]))
    got_s = want_s = jax.random.normal(ks[5], (rows, heads, head_dim, head_dim))
    step, plain = jax.jit(da.delta_step), jax.jit(da.delta_step_plain)
    err = 0.0
    for t in range(steps):
        got_o, got_s = step(q[t], k[t], v[t], g[t], beta[t], got_s)
        want_o, want_s = plain(q[t], k[t], v[t], g[t], beta[t], want_s)
        err = max(err, float(jnp.max(jnp.abs(got_o - want_o))),
                  float(jnp.max(jnp.abs(got_s - want_s))))
    ran = da.dispatch_choices[da.dispatch_key(heads, head_dim, head_dim)]
    assert got_s.dtype == jnp.float32
    assert np.isfinite(err) and err < 2e-5, f"delta step {ran} max|err| {err}"
    return {"impl": ran, "max_abs_err": float(f"{err:.3g}")}


def _power_step_case(key, *, rows, kv_heads, group, head_dim, steps=4):
    """The one-token power-retention step as ``power_step`` dispatches it (the
    Mosaic kernel on a TPU at heads of 128, float32 throughout) against the
    plain form, ``steps`` tokens from one carried ``(S, z)``; what ran is read
    from the dispatch record."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops import power_retention as pr

    ks = jax.random.split(key, 6)
    heads = kv_heads * group
    q = jax.random.normal(ks[0], (steps, rows, heads, head_dim)) + 1.0
    k = jax.random.normal(ks[1], (steps, rows, kv_heads, head_dim)) + 1.0
    v = jax.random.normal(ks[2], (steps, rows, kv_heads, head_dim))
    g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (steps, rows, kv_heads)) + 4.0)
    # a state that remembers a prompt: 64 keys' second powers and their values
    pk = pr.phi((jax.random.normal(ks[4], (rows, kv_heads, 64, head_dim)) + 1.0)
                * head_dim ** -0.25)
    got = want = (jnp.einsum("bkjd,bkjv->bkdv", pk, jax.random.normal(
        ks[5], (rows, kv_heads, 64, head_dim)), precision="highest"), pk.sum(2))
    step, plain = jax.jit(pr.power_step), jax.jit(pr.power_step_plain)
    err = 0.0
    for t in range(steps):
        got_o, got = step(q[t], k[t], v[t], g[t], got)
        want_o, want = plain(q[t], k[t], v[t], g[t], want)
        err = max(err, float(jnp.max(jnp.abs(got_o - want_o))),
                  *(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want)))
    ran = pr.dispatch_choices[pr.dispatch_key(kv_heads, group, head_dim, head_dim)]
    assert got[0].dtype == got[1].dtype == jnp.float32
    assert np.isfinite(err) and err < 2e-5, f"power step {ran} max|err| {err}"
    return {"impl": ran, "max_abs_err": float(f"{err:.3g}")}


def _latent_fold_case(key, *, rows, heads, nope, rope, v_dim, rank, segment, blocks=3,
                      choose=None):
    """A latent-attention prefill segment as ``expanded_segment`` dispatches it
    (every fold of a block of keys the Mosaic kernel on a TPU at bf16 heads of
    whole or half tiles) against the XLA form's folds, ``blocks`` blocks of
    ``segment`` keys rebuilt from their latents, the queries on the last; what
    ran is read from the dispatch record. ``choose``: the share of the keys a
    query sees that a drawn choice keeps (a learned index's mask, over a table
    half a block wider than the blocks), handed to both forms."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.ops import latent_attention as la

    ks = jax.random.split(key, 5)
    bf = jnp.bfloat16
    q_nope = jax.random.normal(ks[0], (rows, segment, heads, nope), bf)
    q_pe = jax.random.normal(ks[1], (rows, segment, heads, rope), bf)
    latent = jax.random.normal(ks[2], (blocks, rows, segment, rank), bf)
    k_pe = jax.random.normal(ks[3], (blocks, rows, segment, rope), bf)
    w = (jax.random.normal(ks[4], (rank, heads * (nope + v_dim))) * rank ** -0.5).astype(bf)
    start = jnp.int32((blocks - 1) * segment)

    def block(j):
        kv = jax.lax.dynamic_index_in_dim(latent, j, 0, keepdims=False) @ w
        return (kv.reshape(rows, segment, heads, nope + v_dim),
                jax.lax.dynamic_index_in_dim(k_pe, j, 0, keepdims=False))

    chosen = None
    if choose is not None:
        width = blocks * segment + segment // 2
        seen = jnp.arange(width)[None, :] <= (start + jnp.arange(segment))[:, None]
        drawn = jax.random.uniform(jax.random.fold_in(key, 1), (rows, segment, width))
        chosen = (seen[None] & (drawn < choose)).astype(la.FOLD_MASK_DTYPE)
    got = jax.jit(lambda: la.expanded_segment(q_nope, q_pe, block, start, v_dim, bf, chosen))()
    ran = la.dispatch_choices[la.dispatch_key(heads, nope, rope, v_dim, segment, bf)]
    carry = None
    for j in range(blocks):
        carry = jax.jit(la.expanded_fold)(
            q_nope, q_pe, *block(j), start, jnp.int32(j * segment), carry, chosen)
    want = la.expanded_finish(carry, jnp.float32)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    # outputs of unit size rounded to bf16: half a unit in the last place of 2
    assert np.isfinite(err) and err < 2e-2, f"latent fold {ran} max|err| {err}"
    return {"impl": ran, "max_abs_err": float(f"{err:.3g}"), "folds": blocks}


def _softmax_fold_case(key, *, rows, kv_heads, group, head_dim, key_row, v_dim, segment,
                       page=128, blocks=3):
    """A full-attention layer's prefill segment over the rows' K/V pages as
    ``hybrid._segment_softmax`` dispatches it (the same fold kernel on a TPU,
    ``group`` query heads a KV head, a key of ``head_dim`` in ``key_row`` lanes)
    against the XLA form's folds of the same gathered blocks, the queries on
    the last of ``blocks``; what ran is read from the dispatch record."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.models import hybrid
    from distrl_llm_tpu.ops import latent_attention as la

    ks = jax.random.split(key, 4)
    bf, heads, per = jnp.bfloat16, kv_heads * group, segment // page
    pages = rows * blocks * per
    q = jax.random.normal(ks[0], (rows, segment, heads, head_dim), bf)
    pages_k = jax.random.normal(ks[1], (kv_heads, pages, page, key_row), bf)
    pages_k = pages_k.at[..., head_dim:].set(0)
    pages_v = jax.random.normal(ks[2], (kv_heads, pages, page, v_dim), bf)
    idx = jax.random.permutation(ks[3], pages).reshape(rows, -1).astype(jnp.int32)
    start = jnp.int32((blocks - 1) * segment)
    got = jax.jit(lambda: hybrid._segment_softmax(q, pages_k, pages_v, idx, start, page))()
    ran = la.dispatch_choices[la.dispatch_key(heads, key_row, 0, v_dim, segment, bf)]
    carry = None
    for j in range(blocks):
        at = idx[:, j * per: (j + 1) * per]
        held = lambda pool: pool[:, at].transpose(1, 0, 2, 3, 4).reshape(
            rows, kv_heads, segment, -1)
        carry = jax.jit(functools.partial(la.expanded_fold, scale=head_dim ** -0.5))(
            hybrid._to_row(q, key_row), q[..., :0], (held(pages_k), held(pages_v)),
            jnp.zeros((rows, segment, 0), bf), start, jnp.int32(j * segment), carry)
    want = la.expanded_finish(carry, jnp.float32)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    # outputs of unit size rounded to bf16: half a unit in the last place of 2
    assert np.isfinite(err) and err < 2e-2, f"softmax fold {ran} max|err| {err}"
    return {"impl": ran, "max_abs_err": float(f"{err:.3g}"), "folds": blocks}


def phase_kernels(seed: int, compiles: CompileLog) -> None:
    """Each Pallas kernel the trainer phases use, compiled (never
    interpreted) at the 0.5B geometry, against its reference on the chip."""
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.models import QWEN2_0_5B as cfg
    from distrl_llm_tpu.ops.paged import DEFAULT_PAGE_SIZE, pages_per_seq
    from distrl_llm_tpu.ops.sampling import sample_dispatch

    t0, mark = time.perf_counter(), compiles.mark()
    key = jax.random.PRNGKey(seed)
    # the paged trainer phase's own geometry: 256-token prompts and answers
    pps = pages_per_seq(256, DEFAULT_PAGE_SIZE) + 1 + pages_per_seq(
        256, DEFAULT_PAGE_SIZE
    )
    geom = dict(rows=64, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, page_size=DEFAULT_PAGE_SIZE, pps=pps)
    out: dict = {
        "paged_bf16": _paged_case(key, quantized=False, **geom),
        "paged_int8_kv": _paged_case(key, quantized=True, **geom),
        # "auto" is the same launch at head_dim 128 (every 7B/8B config); it
        # sizes its block of pages from the shapes
        "paged_bf16_hd128": _paged_case(
            key, quantized=False, **{**geom, "heads": 32, "kv_heads": 8,
                                     "head_dim": 128},
        ),
        # the benchmark's rollout cell: Qwen2.5-7B's 28 / 4 heads of 128,
        # page 128, five pages a row, ragged lengths
        "paged_bf16_7b": _paged_case(
            key, quantized=False, **{**geom, "heads": 28, "kv_heads": 4,
                                     "head_dim": 128},
        ),
    }
    use_fused, interpret = sample_dispatch("bisect")
    assert use_fused and not interpret, "auto sampler is not the fused kernel"
    for name, dt in (("sampler_f32", jnp.float32), ("sampler_bf16", jnp.bfloat16)):
        out[name] = _sampler_case(key, dt, rows=64, vocab=cfg.vocab_size)
    # the benchmark's third configuration at its published widths
    # (Kimi-VL-A3B's language model): absorbed against expanded latent
    # attention, and one expert layer against the plain form (plain XLA both)
    out["latent_attention"] = _latent_attention_case(
        key, rows=8, heads=16, nope=128, rope=64, v_dim=128, rank=512, context=2048)
    # 16 candidates over one 10k-token prompt: its pages read once for all
    # (one Mosaic launch over the pool where it lies: PR 63)
    out["latent_attention_shared_prefix"] = _shared_prefix_attention_case(
        key, rows=16, heads=16, nope=128, rope=64, v_dim=128, rank=512, latent_row=640,
        prompt=10300, page=128, per=8)
    assert out["latent_attention_shared_prefix"]["impl"] == "kernel", out
    # the eighth configuration's decode (GLM-5): 64 heads, the same launch with
    # each row attending a tenth of what it sees (a learned index's mask)
    out["latent_attention_chosen"] = _shared_prefix_attention_case(
        key, rows=16, heads=64, nope=192, rope=64, v_dim=256, rank=512, latent_row=640,
        prompt=10300, page=128, per=8, choose=0.1)
    assert out["latent_attention_chosen"]["impl"] == "kernel", out
    # a prefill segment of the same configuration: three folds of 1,024 keys
    # into 4 rows' 16 heads of 1,024 queries, the Mosaic kernel against the
    # XLA form
    out["latent_fold"] = _latent_fold_case(
        key, rows=4, heads=16, nope=128, rope=64, v_dim=128, rank=512, segment=1024)
    assert out["latent_fold"]["impl"] == "kernel", out["latent_fold"]
    # the eighth configuration's (GLM-5): 64 heads, K 192 + 64 wide beside V of
    # 256, each query attending a fifth of what it sees (a learned index's mask)
    out["latent_fold_chosen"] = _latent_fold_case(
        key, rows=2, heads=64, nope=192, rope=64, v_dim=256, rank=512, segment=1024,
        choose=0.2)
    assert out["latent_fold_chosen"]["impl"] == "kernel", out["latent_fold_chosen"]
    # the same kernel over K/V pages, the twelfth configuration's full layers
    # (MiMo-V2-Flash): 16 query heads a KV head, a key of 192 in 256 lanes
    # beside a value of 128
    out["softmax_fold"] = _softmax_fold_case(
        key, rows=2, kv_heads=4, group=16, head_dim=192, key_row=256, v_dim=128,
        segment=1024)
    assert out["softmax_fold"]["impl"] == "kernel", out["softmax_fold"]
    for name, tokens in (("expert_layer_decode", 64), ("expert_layer_grouped", 1024)):
        out[name] = _expert_layer_case(
            key, tokens=tokens, hidden=2048, width=1408, experts=64, per_token=6)
    # the benchmark's fourth configuration (Solar-Open2): the one-token delta
    # rule at its 64 heads of 128, the Mosaic kernel against the plain form
    out["delta_step"] = _delta_step_case(key, rows=16, heads=64, head_dim=128)
    assert out["delta_step"]["impl"] == "kernel", out["delta_step"]
    # the benchmark's fifth configuration (Brumby-14B): the one-token power
    # retention step over 8 KV heads' states of 8,256 x 128, five query heads a
    # state, the Mosaic kernel against the plain form
    out["power_step"] = _power_step_case(key, rows=8, kv_heads=8, group=5, head_dim=128)
    assert out["power_step"]["impl"] == "kernel", out["power_step"]
    # the learner's attention: the trainer phases run the CLI's default
    # attn_impl="reference" (XLA), so no attention kernel is on their path
    out["learner_attention"] = "reference (XLA): no kernel selected"
    emit("kernels", seconds=round(time.perf_counter() - t0, 2),
         compile=compiles.since(mark), **out)


# ------------------------------------------------------------------ trainer


def chip_sizes(steps: int):
    from train_distributed import SmokeSizes

    from distrl_llm_tpu.ops.paged import DEFAULT_PAGE_SIZE

    return SmokeSizes(
        prompts=8, candidates=8, steps=steps,
        max_prompt_tokens=256, max_new_tokens=256,
        micro_batch=8, lora_rank=16, dtype="bfloat16",
        page_size=DEFAULT_PAGE_SIZE, max_concurrent_rows=32, decode_chunk=128,
    )


def cuts(sizes, model_cfg) -> dict:
    """The cuts from the reference volume, as the trainer phases print them."""
    return {
        "max_prompt_tokens": f"{sizes.max_prompt_tokens} (reference 350)",
        "max_new_tokens": f"{sizes.max_new_tokens} (reference 1200)",
        "prompts_x_candidates":
            f"{sizes.prompts} x {sizes.candidates} (reference 30 x 16)",
        "weights": f"random from --seed, {sizes.dtype}",
        "depth": f"{model_cfg.num_layers} layers (uncut)",
    }


def trainer_config(engine_impl: str, seed: int, sizes, actors: int = 1,
                   learners: int = 1):
    from distrl_llm_tpu.config import TrainConfig

    paged = engine_impl == "paged"
    return TrainConfig(
        model="qwen2.5-0.5b (random weights)", engine_impl=engine_impl,
        # the refill scheduler, engaged: half as many decode slots as rows
        continuous_batching=paged,
        max_concurrent_sequences=sizes.max_concurrent_rows if paged else 0,
        # no plan database outside the checkout is read: the engines resolve
        # the static defaults, printed below
        autotune=False, seed=seed,
        # (TrainConfig carries these into its MeshConfig itself)
        number_of_actors=actors, number_of_learners=learners,
        # one engine call per round (no learner-side share of the rollout):
        # a role-split run then draws the same samples from the same keys
        # as a one-device run
        learner_chunk_size=0,
    )


def run_trainer(model_cfg, config, sizes, seed: int, compiles: CompileLog,
                devices=None) -> dict:
    """``run_smoke`` at these sizes with the dense smoke reward, plus the
    asserts every trainer phase shares. Returns the report with a
    ``summary`` of what to print."""
    import jax
    from train_distributed import dense_smoke_reward, run_smoke

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine.engine import ENGINE_CHUNK_FALLBACK
    from distrl_llm_tpu.ops import paged as paged_ops
    from distrl_llm_tpu.ops.sampling import (
        sample_dispatch_choices, sample_impl_mode,
    )

    paged_ops.dispatch_choices.clear()
    sample_dispatch_choices.clear()
    fallback0 = telemetry.observe_snapshot()["counters"].get(
        ENGINE_CHUNK_FALLBACK, 0
    )
    on_tpu = jax.default_backend() == "tpu"

    t0, mark0 = time.perf_counter(), compiles.mark()
    report = run_smoke(
        config, model_cfg, sizes, seed=seed, reward_fn=dense_smoke_reward,
        devices=devices,
    )
    seconds = time.perf_counter() - t0
    steps, rounds = report["steps"], report["rounds"]
    trainer = report["trainer"]
    engine = trainer.engine

    assert len(steps) == sizes.steps, (len(steps), sizes.steps)
    # rounds[0] is the initial evaluation; then one rollout per train step
    train_rounds = rounds[-sizes.steps:]
    assert all(r["gen_tokens"] > 0 for r in rounds), rounds
    versions = [r["policy_version"] for r in train_rounds]
    assert versions == list(range(sizes.steps)), (
        f"rollouts sampled under policy versions {versions}: step k must "
        "sample under the adapter that k-1 updates produced"
    )
    checksums = [r["adapter_checksum"] for r in train_rounds] + [
        report["final_adapter_checksum"]
    ]
    assert all(a != b for a, b in zip(checksums, checksums[1:])), (
        f"adapter unchanged by an update: {checksums}"
    )
    fallbacks = telemetry.observe_snapshot()["counters"].get(
        ENGINE_CHUNK_FALLBACK, 0
    ) - fallback0
    assert fallbacks == 0, f"{fallbacks} engine/chunk_fallback"
    # what the configuration asked for: DISTRL_SAMPLE_KERNEL, by default
    # "auto" = the fused kernel on a TPU backend, the multi-pass path elsewhere
    mode = sample_impl_mode()
    asked = "fused" if mode in ("fused", "interpret") or (
        mode == "auto" and on_tpu
    ) else "xla"
    sampler = sorted(set(sample_dispatch_choices.values()))
    assert sampler == [asked], (sampler, asked)
    paged_record = None
    if config.engine_impl == "paged":
        paged_record = paged_ops.dispatch_choices.get(engine._dispatch_key())
        assert paged_record is not None, "no paged dispatch was recorded"
        if on_tpu:
            assert paged_record.startswith("native"), paged_record
    # no compile after step 1 for a program already compiled: the second
    # train step's rollout starts the window
    again, fresh = [], []
    if sizes.steps > 1:
        again, fresh = compiles.recompiled_after(
            mark0, train_rounds[1]["started"]
        )
    assert not again, f"compiled again after step 1: {again}"
    plan = getattr(engine, "resolved_plan", None)
    peak = None
    stats = jax.local_devices()[0].memory_stats()
    if stats:
        peak = stats.get("peak_bytes_in_use")
    report["summary"] = {
        "seconds": round(seconds, 2),
        "compile": compiles.since(mark0),
        "losses": [m["loss"] for m in steps],
        "policy_versions": versions,
        "adapter_checksums": [round(c, 6) for c in checksums],
        "round_seconds": [round(r["seconds"], 2) for r in rounds],
        "update_seconds": [
            round(m["timing/update_duration"], 2) for m in steps
        ],
        "gen_tokens": [r["gen_tokens"] for r in rounds],
        "paged_dispatch": paged_record,
        "sampler": sampler[0],
        "chunk_fallbacks": fallbacks,
        "plan": plan.plan.to_dict() if plan is not None else None,
        "plan_source": plan.source if plan is not None else None,
        "peak_bytes_in_use": peak,
        "compiled_after_step_1": fresh,
    }
    return report


def phase_trainer(name: str, engine_impl: str, steps: int, seed: int,
                  compiles: CompileLog) -> None:
    from distrl_llm_tpu.models import QWEN2_0_5B

    sizes = chip_sizes(steps)
    report = run_trainer(
        QWEN2_0_5B, trainer_config(engine_impl, seed, sizes), sizes, seed,
        compiles,
    )
    emit(name, model="QWEN2_0_5B", engine_impl=engine_impl, steps=steps,
         cuts=cuts(sizes, QWEN2_0_5B), **report["summary"])


# --------------------------------------------------------------- four chips


def _device_ids(tree) -> set[int]:
    import jax

    return {
        d.id for leaf in jax.tree_util.tree_leaves(tree)
        for d in leaf.devices()
    }


def phase_four_chips(seed: int, compiles: CompileLog, model_cfg=None,
                     sizes=None) -> None:
    """The same seeded 2-step paged run (i) timeshared on one of the four
    devices and (ii) role-split, 2 actors + 2 data-parallel learners —
    losses and final adapter compared, placement read off the arrays."""
    import jax
    import numpy as np

    from distrl_llm_tpu.models import QWEN2_0_5B

    model_cfg = model_cfg if model_cfg is not None else QWEN2_0_5B
    sizes = sizes if sizes is not None else chip_sizes(steps=2)
    devices = jax.devices()
    assert len(devices) == 4, f"--chips 4 needs four devices, found {devices}"
    t0 = time.perf_counter()

    def run(actors: int, learners: int, devs):
        cfg = trainer_config("paged", seed, sizes, actors, learners)
        return run_trainer(model_cfg, cfg, sizes, seed, compiles, devs)

    one = run(1, 1, [devices[0]])
    lora_one = jax.device_get(one["trainer"].lora)
    assert one["trainer"].meshes.timeshared
    one["trainer"] = None  # drop the first run's device memory
    split = run(2, 2, devices)
    tr = split["trainer"]
    assert not tr.meshes.timeshared

    actor_ids = {d.id for d in tr.meshes.rollout.devices.flat}
    learner_ids = {d.id for d in tr.meshes.learner.devices.flat}
    assert len(actor_ids) == 2 and len(learner_ids) == 2
    assert not actor_ids & learner_ids
    placement = {
        "actor_devices": sorted(actor_ids),
        "learner_devices": sorted(learner_ids),
        "rollout_params": sorted(_device_ids(tr.base_params)),
        "rollout_adapter": sorted(_device_ids(tr._lora_rollout)),
        "kv_pages": sorted(tr.engine.last_pool_stats["kv_devices"]),
        "learner_params": sorted(_device_ids(tr.base_params_learner)),
        "learner_adapter": sorted(_device_ids(tr.lora)),
        "optimizer_state": sorted(_device_ids(tr.opt_state)),
    }
    for what in ("rollout_params", "rollout_adapter", "kv_pages"):
        assert set(placement[what]) == actor_ids, (what, placement)
    for what in ("learner_params", "learner_adapter", "optimizer_state"):
        assert set(placement[what]) == learner_ids, (what, placement)
    # _push_weights moved the adapter across submeshes: the rollout copy is
    # the learner's adapter, value for value, on the other devices
    lora_split = jax.device_get(tr.lora)
    pushed = jax.device_get(tr._lora_rollout)
    jax.tree_util.tree_map(np.testing.assert_array_equal, lora_split, pushed)
    assert tr._rollout_weight_version == tr.weight_version == sizes.steps

    loss_one, loss_split = one["summary"]["losses"], split["summary"]["losses"]
    scale = max(abs(x) for x in loss_one)
    loss_err = [abs(a - b) / scale for a, b in zip(loss_one, loss_split)]
    num = sum(
        float(np.sum((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2))
        for a, b in zip(jax.tree_util.tree_leaves(lora_one),
                        jax.tree_util.tree_leaves(lora_split))
    )
    # relative to how far training moved the adapter, not to its init
    moved = sum(
        float(np.sum(np.asarray(a["b"], np.float64) ** 2))
        for a in jax.tree_util.tree_leaves(
            lora_one, is_leaf=lambda x: isinstance(x, dict) and "b" in x
        )
    )
    rel_l2 = (num / max(moved, 1e-30)) ** 0.5
    emit(
        "four_chips", seconds=round(time.perf_counter() - t0, 2),
        tolerance={"loss_rel": LOSS_RTOL, "adapter_rel_l2": ADAPTER_REL_L2_TOL},
        losses_one_device=loss_one, losses_role_split=loss_split,
        loss_rel_err=[round(e, 6) for e in loss_err],
        adapter_rel_l2=round(rel_l2, 6), placement=placement,
        one_device=one["summary"], role_split=split["summary"],
    )
    assert all(e <= LOSS_RTOL for e in loss_err), (loss_err, LOSS_RTOL)
    assert rel_l2 <= ADAPTER_REL_L2_TOL, (rel_l2, ADAPTER_REL_L2_TOL)


# --------------------------------------------------------------------- main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    import jax

    from distrl_llm_tpu.utils.devices import enable_compile_cache, require_tpu

    cache_dir = enable_compile_cache()
    # no cpu_requested: this script has no CPU mode, whatever the variable says
    devices = require_tpu(jax.devices())
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    assert device["count"] == args.chips, (
        f"--chips {args.chips} but JAX sees {device['count']} device(s)"
    )
    emit("device", seconds=round(time.perf_counter() - t0, 2),
         compile_cache_dir=cache_dir, jax=jax.__version__, **device)

    compiles = CompileLog()
    if args.chips == 4:
        phase_four_chips(args.seed, compiles)
    else:
        phase_kernels(args.seed, compiles)
        phase_trainer("trainer_paged", "paged", 3, args.seed, compiles)
        phase_trainer("trainer_dense", "dense", 1, args.seed, compiles)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
