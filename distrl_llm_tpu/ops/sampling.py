"""Batched token sampling under jit: temperature, top-p (nucleus), greedy.

Replaces the vLLM sampler the reference drives through SamplingParams
(distributed_actor.py:43–48 — temperature, top_p=0.95, n candidates). All ops
are fixed-shape and branch-free so the whole decode loop stays on device; the
top-p filter is the exact sort-based formulation (keep the minimal prefix of
the sorted distribution whose mass reaches top_p).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.ops.attention import NEG_INF
from distrl_llm_tpu.ops.per_device import per_device


def top_p_filter(logits: jax.Array, top_p: jax.Array | float) -> jax.Array:
    """Mask logits outside the nucleus: sort descending, keep tokens until the
    cumulative probability first reaches ``top_p`` (the token that crosses the
    threshold is kept, matching vLLM/HF semantics). [B, V] → [B, V].

    Membership is mapped back by RANK, not by logit threshold, so ties at the
    cutoff don't expand the nucleus beyond top_p (stable argsort breaks ties
    deterministically by vocab index)."""
    order = jnp.argsort(-logits, axis=-1)  # descending, stable
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # keep tokens whose prefix mass EXCLUDING them has not yet reached top_p
    keep_sorted = (cum - sorted_probs) < top_p
    ranks = jnp.argsort(order, axis=-1)  # rank of each vocab position
    keep = jnp.take_along_axis(keep_sorted, ranks, axis=-1)
    return jnp.where(keep, logits, NEG_INF)


def top_p_filter_bisect(
    logits: jax.Array, top_p: jax.Array | float, iters: int = 16
) -> jax.Array:
    """Sort-free nucleus filter: bisect a probability threshold τ such that
    the kept mass Σ p·[p ≥ τ] just reaches ``top_p``, then keep p ≥ τ.

    Sorting 152k-vocab logits every decode step is the sampler's whole cost on
    TPU; bisection needs only ``iters`` masked reductions, which XLA fuses into
    cheap single-pass kernels. Uses the interval's LOW end so kept mass is
    always ≥ top_p (never drops a token the exact filter would keep); tokens
    tied exactly at the boundary may be kept where the rank-based filter would
    cut them — a measure-zero difference tested against ``top_p_filter``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p = jnp.asarray(top_p, jnp.float32)

    def body(_, interval):
        lo, hi = interval
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(probs >= mid[..., None], probs, 0.0), axis=-1)
        ok = mass >= top_p  # τ=mid still keeps enough mass → move lo up
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo = jnp.zeros(probs.shape[:-1], jnp.float32)
    hi = jnp.max(probs, axis=-1)
    lo, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return jnp.where(probs >= lo[..., None], logits, NEG_INF)


def top_p_filter_bisect_multiway(
    logits: jax.Array, top_p: jax.Array | float,
    passes: int = 4, k: int = 15,
) -> jax.Array:
    """Nucleus filter with MULTIWAY bisection: each pass tests ``k``
    thresholds of the current interval in one fused read of ``probs`` (the
    k masked reductions share one operand, which XLA's sibling multi-output
    fusion turns into a single V-pass with k accumulators), narrowing the
    interval (k+1)-fold. 4 passes × 15 thresholds reach the same 2^16
    resolution as 16 sequential binary iterations with ~1/4 the HBM
    traffic — at decode shapes ([480, 152k] f32) the binary loop's 16
    un-fusable passes are ~4.6 GB/step of pure sampler reads.

    Same kept-mass guarantee as ``top_p_filter_bisect``: the returned
    threshold always keeps mass ≥ top_p (lo only ever moves onto a tested
    threshold whose kept mass still reached top_p)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p = jnp.asarray(top_p, jnp.float32)
    frac = jnp.arange(1, k + 1, dtype=jnp.float32) / (k + 1)  # (0,1) interior

    def body(_, interval):
        lo, hi = interval  # [...]
        ts = lo[..., None] + (hi - lo)[..., None] * frac  # [..., k], increasing
        # unrolled so XLA sees k sibling reduces over the SAME probs operand
        masses = [
            jnp.sum(
                jnp.where(probs >= ts[..., j][..., None], probs, 0.0), axis=-1
            )
            for j in range(k)
        ]
        mass = jnp.stack(masses, axis=-1)  # [..., k]
        ok = mass >= top_p[..., None]  # top_p may be scalar or per-row
        # robust to float non-monotonicity: take the LARGEST passing
        # threshold and the SMALLEST failing one, not prefix counts
        new_lo = jnp.max(jnp.where(ok, ts, lo[..., None]), axis=-1)
        new_hi = jnp.min(jnp.where(ok, hi[..., None], ts), axis=-1)
        return new_lo, new_hi

    lo = jnp.zeros(probs.shape[:-1], jnp.float32)
    hi = jnp.max(probs, axis=-1)
    lo, _ = jax.lax.fori_loop(0, passes, body, (lo, hi))
    return jnp.where(probs >= lo[..., None], logits, NEG_INF)


TOP_P_IMPLS = {
    "exact": top_p_filter,
    "bisect": top_p_filter_bisect,
    "bisect_mw": top_p_filter_bisect_multiway,
}


def sample(
    rng: jax.Array,
    logits: jax.Array,  # [B, V]
    temperature: jax.Array | float,
    top_p: jax.Array | float = 1.0,
    top_p_impl: str = "bisect",
) -> jax.Array:
    """Sample token ids [B]. temperature == 0 → greedy (vLLM convention).

    Temperature and top_p may be traced scalars so train/eval sampling params
    (1.2/0.95 vs 0.6/0.95 — distributed_trainer.py:53–58) share one compiled
    decode loop.

    ``top_p_impl`` (static): "bisect" (default, sort-free — the fast path),
    "bisect_mw" (multiway bisection, ~1/4 the sampler HBM traffic — flip
    the default once tools/sampler_probe.py confirms the fusion on a real
    chip), or "exact" (rank-based sort filter, byte-identical to the
    reference's vLLM nucleus semantics) for reproducibility runs —
    SamplingConfig.top_p_exact.
    """
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    scaled = logits.astype(jnp.float32) / t
    filtered = TOP_P_IMPLS[top_p_impl](scaled, top_p)
    sampled = jax.random.categorical(rng, filtered, axis=-1)
    is_greedy = jnp.asarray(temperature, jnp.float32) == 0.0
    return jnp.where(is_greedy, greedy, sampled).astype(jnp.int32)


# --------------------------------------------------------- fused sampler
# One Pallas program per logits row: temperature scale, bisect top-p
# filter, Gumbel-max categorical draw, and the chosen token's RAW-basis
# logprob — replacing the multi-pass softmax/sort/cumsum pipeline that
# re-reads the [B, V] logits from HBM per pass AND the separate
# token_logprob logsumexp pass (at decode shapes, [480, 152k] f32, the
# sampler pipeline alone is multiple GB/step of HBM traffic; ISSUE 15).
#
# Greedy (temperature == 0) is argmax over the raw row — bit-identical to
# ``sample``'s greedy branch (pinned by tools/quant_smoke.py). The sampled
# path draws via Gumbel-max over the SAME bisect-filtered tempered
# distribution the multi-pass path uses, with uniforms from an in-kernel
# counter-hash PRNG (murmur3 finalizer over (per-row seed, column)) — the
# TPU-native prng primitives don't interpret on CPU, and a pure-jnp hash
# runs identically compiled and interpreted. The draw stream differs from
# jax.random.categorical by construction, so the sampled path is pinned
# DISTRIBUTION-exact (seeded statistical parity, the spec_accept
# precedent), not bit-exact.

#: trace-time dispatch record (ops.paged.dispatch_choices idiom): keyed by
#: (rows, vocab) → "fused" | "xla"; chip_smoke.py reads it
sample_dispatch_choices: dict = {}

SAMPLE_IMPLS = ("auto", "fused", "interpret", "xla")


def sample_impl_mode() -> str:
    """Resolved DISTRL_SAMPLE_KERNEL mode (validated; default "auto")."""
    mode = os.environ.get("DISTRL_SAMPLE_KERNEL", "auto")
    if mode not in SAMPLE_IMPLS:
        raise ValueError(
            f"DISTRL_SAMPLE_KERNEL must be one of {SAMPLE_IMPLS}, got "
            f"{mode!r}"
        )
    return mode


#: the kernel views one logits row as a [V/128, 128] tile (every vreg full;
#: a [1, V] row would occupy one sublane in eight) — rows are padded to a
#: whole number of (8, 128) tiles
_LANES = 128
_ROW_TILE = 8 * _LANES


def _fused_sample_kernel(temp_ref, topp_ref, seed_ref, logits_ref,
                         tok_ref, logp_ref, *, iters: int):
    """One logits row, viewed as an [R, 128] tile: (token, raw-basis
    logprob) in a single pass. Padded columns carry NEG_INF and can never
    win an argmax or contribute mass. All row reductions are full-tile
    reductions to a scalar; argmax is spelled max + min-index-at-max (first
    occurrence wins, jnp.argmax's tie rule)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    raw = logits_ref[0]  # [R, 128] f32
    t0 = temp_ref[0, 0]
    top_p = topp_ref[0, 0]

    # flat vocab index of each element; f32 holds it exactly (V < 2^24)
    idx = (
        jax.lax.broadcasted_iota(jnp.int32, raw.shape, 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, raw.shape, 1)
    )
    idx_f = idx.astype(jnp.float32)

    def argmax(x, x_max):
        return jnp.min(jnp.where(x == x_max, idx_f, jnp.float32(2.0 ** 24)))

    m_raw = jnp.max(raw)
    greedy = argmax(raw, m_raw)

    # tempered softmax (sample()'s exact order: scale, then filter)
    t = jnp.maximum(t0, 1e-6)
    scaled = raw / t
    e = jnp.exp(scaled - jnp.max(scaled))
    probs = e / jnp.sum(e)

    # bisect the keep threshold (top_p_filter_bisect's math: kept mass is
    # always >= top_p; the LOW end of the interval is the threshold)
    def body(_, interval):
        lo, hi = interval
        mid = 0.5 * (lo + hi)
        ok = jnp.sum(jnp.where(probs >= mid, probs, 0.0)) >= top_p
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, iters, body, (jnp.float32(0.0), jnp.max(probs))
    )
    filtered = jnp.where(probs >= lo, scaled, NEG_INF)

    # Gumbel-max draw with counter-hash uniforms: murmur3 fmix32 over
    # (seed, column) — identical bits compiled and interpreted
    h = idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + seed_ref[i].astype(
        jnp.uint32
    )
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # 23 high bits → u ∈ [2^-24, 1 - 2^-24], every endpoint EXACTLY
    # representable in f32: a 24-bit mapping can round to 1.0f (prob 2^-24
    # per element), where -log(-log(1)) = +inf hands the argmax to an
    # arbitrary — possibly padded — column
    u = (h >> 9).astype(jnp.int32).astype(jnp.float32) * jnp.float32(
        2.0 ** -23
    ) + jnp.float32(2.0 ** -24)
    noisy = filtered - jnp.log(-jnp.log(u))
    sampled = argmax(noisy, jnp.max(noisy))

    tok = jnp.where(t0 == 0.0, greedy, sampled)

    # raw-basis logprob of the chosen token (token_logprob's math)
    logz = jnp.log(jnp.sum(jnp.exp(raw - m_raw))) + m_raw
    picked = jnp.max(jnp.where(idx_f == tok, raw, NEG_INF))
    tok_ref[i] = tok.astype(jnp.int32)
    logp_ref[i] = picked - logz


def fused_sample(
    rng: jax.Array,
    logits: jax.Array,  # [B, V]
    temperature: jax.Array | float,
    top_p: jax.Array | float = 1.0,
    *,
    iters: int = 16,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(tokens [B] i32, raw-basis logprobs [B] f32) in one fused kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, v = logits.shape
    vp = -(-v // _ROW_TILE) * _ROW_TILE
    lg = logits.astype(jnp.float32)
    if vp != v:
        lg = jnp.pad(lg, ((0, 0), (0, vp - v)), constant_values=NEG_INF)
    lg = lg.reshape(b, vp // _LANES, _LANES)
    # one independent 32-bit seed per row off the caller's key — the same
    # key the multi-pass path would hand jax.random.categorical
    seeds = jax.random.bits(rng, (b,), jnp.uint32).astype(jnp.int32)
    t = jnp.full((1, 1), 0.0, jnp.float32) + jnp.asarray(
        temperature, jnp.float32
    )
    p = jnp.full((1, 1), 0.0, jnp.float32) + jnp.asarray(top_p, jnp.float32)
    # scalars and per-row seeds/results live whole in SMEM (Mosaic has no
    # legal (1, 1) block over a [B, 1] array); each grid step reads and
    # writes its own row's slot
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tok, logp = pl.pallas_call(
        functools.partial(_fused_sample_kernel, iters=iters),
        grid=(b,),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((1, vp // _LANES, _LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.float32),
        ],
        interpret=interpret,
    )(t, p, seeds, lg)
    return tok, logp


def sample_dispatch(top_p_impl: str) -> tuple[bool, bool]:
    """(use_fused, interpret) per DISTRL_SAMPLE_KERNEL.

    "auto" is the fused kernel on a TPU backend — except under an EXPLICIT
    exact-nucleus pin (top_p_impl="exact" is a reproducibility ask the
    bisect-filter kernel must not silently override) — and the multi-pass
    path on any other backend. A kernel that ``auto`` selected and that
    fails to compile fails the step: nothing here gives way to the
    multi-pass path (tests/test_tpu_compile.py holds the lowering)."""
    mode = sample_impl_mode()
    if mode == "xla":
        return False, False
    if mode == "interpret":
        return True, True
    on_tpu = jax.default_backend() == "tpu"
    if mode == "fused":
        return True, not on_tpu
    return (on_tpu and top_p_impl != "exact"), False


def sample_with_logprob(
    rng: jax.Array,
    logits: jax.Array,  # [B, V]
    temperature: jax.Array | float,
    top_p: jax.Array | float = 1.0,
    *,
    top_p_impl: str = "bisect",
    capture_logprob: bool = False,
    impl: str | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """The engines' one sampling entry point: (tokens [B], behavior
    logprobs [B] or None). Dispatches to the fused kernel when enabled
    (DISTRL_SAMPLE_KERNEL / probe), else to the multi-pass ``sample`` +
    ``token_logprob`` reference — greedy outputs bit-identical either way."""
    use, interp = (
        sample_dispatch(top_p_impl)
        if impl is None
        else ({"fused": (True, False), "interpret": (True, True),
               "xla": (False, False)}[impl])
    )
    sample_dispatch_choices[tuple(logits.shape)] = (
        "fused" if use else "xla"
    )
    if use:
        # NO scope round the fused kernel, here or in a caller: a Pallas call
        # traced inline is not metadata-only under a scope. The TPU compiler
        # names the custom call after the innermost scope (the benchmark finds
        # this kernel as ``%_unknown_``), and the kernel body's serialized
        # MLIR carries the name stack, which re-keys the program in the
        # persistent cache. It gets a name and a scope together, in the
        # ``benchmark`` PR that re-points ``kernel.sampler_share`` (ROADMAP S0b)
        tok, logp = per_device(fused_sample)(
            rng, logits, jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32), interpret=interp,
        )
        return tok, (logp if capture_logprob else None)
    with jax.named_scope(telemetry.ENGINE_SAMPLE):
        tok = sample(rng, logits, temperature, top_p, top_p_impl=top_p_impl)
        return tok, (token_logprob(logits, tok) if capture_logprob else None)


def token_logprob(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """RAW-model log-probability of ``tokens`` under ``logits`` ([..., V] ×
    [...] → [...] f32). This is the rollout-time BEHAVIOR logprob the
    PPO-clip objective ratios against the learner's recompute. Both sides
    use unscaled log_softmax — the RLHF/vLLM convention. Note this is an
    APPROXIMATION when temperature != 1 or top_p < 1: tokens were actually
    drawn from the tempered/filtered distribution, so the raw-basis ratio
    is not the exact importance ratio against the sampler; it is exact for
    the policy the LOSS optimizes (the raw model), which is why the
    convention is standard."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), tokens[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return picked - logz
