"""Speculative decoding for the paged refill engine: prompt-lookup drafts.

vLLM-class capability beyond the reference's configuration (its vLLM 0.7.2
ships speculative decoding; the reference never enables it — this build does,
TPU-first). Math-RL rollouts repeat prompt material (numbers, expressions,
format tags), so an n-gram lookup over the row's OWN sequence proposes the
next ``d`` tokens for free ("prompt lookup decoding" / vLLM's ngram
speculator): find the latest earlier occurrence of the last ``k`` tokens and
draft whatever followed it. The model then VERIFIES the whole draft block in
one forward — QKV/MLP/lm_head matmuls batch over [R, d+1] positions, which is
exactly where single-token decode is weight-bandwidth-bound — and a
rejection-sampling acceptance keeps the output distribution IDENTICAL to
plain sampling (exact equality under greedy, tested):

* draft q is a point mass, so token t_i is accepted with probability
  p_i(t_i) under the model's post-temperature/top-p distribution;
* the first rejected position resamples from the residual
  norm(p_i − onehot(t_i)) — unbiased for one-hot proposals;
* if the whole draft survives, one bonus token samples from the final
  distribution, so a step emits between 1 and d+1 tokens.

Cache bookkeeping rides the paged refill machinery: the verify forward
writes d+1 KVs at per-row offsets (transformer.forward(paged_verify=True));
rejected positions hold garbage ABOVE the row's valid length and are
overwritten before they can be read. All shapes are static; acceptance
counts are data.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.sampling import TOP_P_IMPLS


def sampling_probs(
    logits: jax.Array,  # [..., V]
    temperature,
    top_p,
    top_p_impl: str = "bisect",
) -> jax.Array:
    """The categorical distribution ``ops.sampling.sample`` draws from,
    as explicit probabilities (greedy → one-hot argmax). The acceptance test
    must use THIS distribution — not raw softmax — or speculative sampling
    would silently change semantics vs plain decoding."""
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    # shared impl registry: draft/verify sampling must use the SAME
    # filter as the main decode sampler for every impl string
    filtered = TOP_P_IMPLS[top_p_impl](logits.astype(jnp.float32) / t, top_p)
    probs = jax.nn.softmax(filtered, axis=-1)
    greedy = jax.nn.one_hot(
        jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=jnp.float32
    )
    is_greedy = jnp.asarray(temperature, jnp.float32) == 0.0
    return jnp.where(is_greedy, greedy, probs)


def propose_ngram_drafts(
    seq_buf: jax.Array,  # [R, W] the row's full token sequence so far
    buf_len: jax.Array,  # [R] valid tokens in seq_buf
    *,
    k: int,
    d: int,
) -> jax.Array:
    """Prompt-lookup proposal: the latest j < buf_len−k with
    seq_buf[j:j+k] == the last k tokens; draft = the d tokens that followed.
    Rows with no match draft their last token repeated (a cheap guess the
    verifier simply rejects when wrong). Returns [R, d] int32."""
    r, w = seq_buf.shape
    tail_idx = jnp.clip(
        buf_len[:, None] - k + jnp.arange(k)[None, :], 0, w - 1
    )
    tail = jnp.take_along_axis(seq_buf, tail_idx, axis=1)  # [R, k]

    n_win = w - k + 1
    match = jnp.ones((r, n_win), bool)
    for i in range(k):
        match = match & (seq_buf[:, i : i + n_win] == tail[:, i : i + 1])
    j = jnp.arange(n_win)[None, :]
    match = match & (j < (buf_len - k)[:, None])  # strictly before the tail
    found = match.any(axis=1)
    last_j = (n_win - 1) - jnp.argmax(match[:, ::-1], axis=1)  # [R]

    cont_idx = jnp.clip(
        last_j[:, None] + k + jnp.arange(d)[None, :], 0, w - 1
    )
    cont = jnp.take_along_axis(seq_buf, cont_idx, axis=1)  # [R, d]
    last_tok_idx = jnp.clip(buf_len - 1, 0, w - 1)
    last_tok = jnp.take_along_axis(seq_buf, last_tok_idx[:, None], axis=1)
    return jnp.where(found[:, None], cont, jnp.broadcast_to(last_tok, cont.shape))


def spec_accept(
    rng: jax.Array,
    probs: jax.Array,  # [R, d+1, V] — probs[:, i] judges draft[:, i]; [:, d] = bonus
    draft: jax.Array,  # [R, d]
    draft_probs: jax.Array | None = None,  # [R, d, V] full proposal dists q
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative rejection sampling. Returns (emit [R, d+1], n_emit [R],
    n_accept [R]): emit[:, :n_emit] are this step's new tokens — the
    accepted draft prefix followed by one resampled/bonus token; n_emit ∈
    [1, d+1]. ``n_accept`` ∈ [0, d] is the SAMPLER's accepted prefix
    length (n_emit − 1 before any EOS/budget truncation the caller
    applies) — the unbiased drafter-quality measure the accept-rate
    accounting consumes; deriving it from the post-truncation emit count
    would under-count a final emitted token that was itself an accepted
    draft (e.g. an accepted EOS).

    Without ``draft_probs`` the proposal is treated as a POINT MASS (the
    n-gram drafter's regime): token t_i is accepted with probability
    p_i(t_i), and the residual zeroes exactly the drafted token — the
    original one-hot algebra, bit-for-bit.

    With ``draft_probs`` this is standard full-distribution speculative
    sampling (the self-drafter's regime — q is the previous-version
    policy's own sampling distribution): accept t_i with probability
    min(1, p_i(t_i) / q_i(t_i)) — implemented as ``u · q < p`` so a
    zero-q never divides — and resample the first rejection from the
    residual norm(max(p_i − q_i, 0)). Both branches leave the output
    distribution IDENTICAL to plain sampling from p (the rejection-sampling
    identity; pinned empirically by tests/test_speculative.py). The
    one-hot path is the q = onehot(t_i) special case: u·1 < p(t_i) and
    max(p − onehot, 0) = p with the drafted token zeroed."""
    r, dp1, v = probs.shape
    d = dp1 - 1
    u = jax.random.uniform(jax.random.fold_in(rng, 0), (r, d))
    p_draft = jnp.take_along_axis(probs[:, :d], draft[..., None], axis=-1)[..., 0]
    if draft_probs is None:
        accept = u < p_draft  # [R, d]
    else:
        q_draft = jnp.take_along_axis(
            draft_probs, draft[..., None], axis=-1
        )[..., 0]
        accept = u * q_draft < p_draft  # u < p/q, division-free
    m = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)  # [R] prefix len

    rows = jnp.arange(r)
    final_probs = probs[rows, m]  # [R, V] — dist at the first rejected / bonus slot
    rejected = m < d
    if draft_probs is None:
        drop = jnp.take_along_axis(
            draft, jnp.minimum(m, d - 1)[:, None], axis=1
        )[:, 0]
        onehot_drop = jax.nn.one_hot(drop, v, dtype=bool)
        final_probs = jnp.where(
            rejected[:, None] & onehot_drop, 0.0, final_probs
        )
    else:
        q_at = draft_probs[rows, jnp.minimum(m, d - 1)]  # [R, V]
        resid = jnp.maximum(final_probs - q_at, 0.0)
        # p ≤ q everywhere ⇒ p == q ⇒ the residual is empty; any
        # acceptance test would have passed, so the event has measure
        # zero under exact arithmetic — guard the float-rounding case by
        # falling back to p itself (still exact: p == q there)
        resid_ok = resid.sum(axis=-1, keepdims=True) > 0
        final_probs = jnp.where(
            rejected[:, None], jnp.where(resid_ok, resid, final_probs),
            final_probs,
        )
    final_probs = final_probs / jnp.maximum(
        final_probs.sum(axis=-1, keepdims=True), 1e-20
    )
    final_tok = jax.random.categorical(
        jax.random.fold_in(rng, 1), jnp.log(jnp.maximum(final_probs, 1e-30))
    ).astype(jnp.int32)

    pos = jnp.arange(dp1)[None, :]
    draft_padded = jnp.pad(draft, ((0, 0), (0, 1)))
    emit = jnp.where(pos < m[:, None], draft_padded, 0)
    emit = jnp.where(pos == m[:, None], final_tok[:, None], emit)
    return (
        emit.astype(jnp.int32), (m + 1).astype(jnp.int32),
        m.astype(jnp.int32),
    )


class SpecRefillState(NamedTuple):
    """Refill decode state for speculative mode. Differences from
    ``_RefillState``: no carried logits — the carried quantity is
    ``last_tok`` (emitted but not yet resident in the KV cache; the next
    verify forward processes it as its first input) — plus each slot's full
    token sequence for the n-gram lookup."""

    step: jax.Array
    alive_steps: jax.Array  # [] sum over steps of alive-slot count
    out: jax.Array  # [total, T]
    logps_buf: jax.Array  # [total, T] behavior logprobs (raw log_softmax)
    lengths_buf: jax.Array  # [total]
    cand: jax.Array  # [R]
    done: jax.Array  # [R]
    last_tok: jax.Array  # [R] pending token (counted in gen_lengths, not in cache)
    seq_buf: jax.Array  # [R, W] prompt + generated tokens
    seq_lengths: jax.Array  # [R] tokens RESIDENT in the cache
    gen_lengths: jax.Array  # [R] generated tokens incl. last_tok
    page_indices: jax.Array  # [R, width]
    k_pages: tuple
    v_pages: tuple
    # acceptance accounting, carried ON DEVICE so the host pays no extra
    # round-trips: emit_hist[n] counts the alive slot-steps that emitted
    # exactly n tokens (n ∈ [0, d_max+1]; width is static at the CONFIGURED
    # max draft length so the adaptive controller can shrink d without a
    # shape change), draft_total sums alive·d_eff, accept_total sums the
    # SAMPLER's accepted prefix lengths (spec_accept's n_accept — pre-EOS/
    # budget truncation, so accept_rate = accept_total/draft_total is the
    # unbiased drafter-quality measure; emit-derived counts would
    # under-count rows whose final emitted token was an accepted draft,
    # e.g. an accepted EOS) — together they give the accept rate,
    # tokens/verify-step, and the emit distribution (engine/spec_*
    # telemetry)
    emit_hist: jax.Array  # [d_max+2] i32
    draft_total: jax.Array  # [] i32
    accept_total: jax.Array  # [] i32
