"""The jit-compiled generation engine: shared-prefill + on-device decode loop.

TPU-native replacement for the reference's in-process vLLM engine
(``policy.fast_generate`` with n-candidate SamplingParams,
distributed_actor.py:147–172 — SURVEY §2b N1/N2). Design:

* **Prefill once per prompt, decode n candidates.** Prompts are left-padded to
  a fixed length and prefilled at batch B; the KV cache is then repeated to
  B·n rows so the n sampled candidates per prompt (``num_candidates``, 16 by
  default) share one prompt forward — a 16× prefill saving the reference
  delegates to vLLM's prefix caching.
* **Host-dispatched donated decode steps.** Each token is one jitted,
  donated step program whose KV cache aliases in place (zero HBM temp bytes —
  an on-device ``lax.while_loop`` carry gets double-buffered by the TPU
  compiler, costing a full cache-sized temp). JAX async dispatch queues steps
  ahead so the device never waits on the host; the host syncs only on the
  done flags every ``decode_chunk`` steps and stops dispatching once every
  row has hit EOS — the fixed-shape equivalent of continuous batching's tail
  behavior. Temperature/top-p are traced scalars, so train and eval sampling
  share the compiled step.
* **LoRA rides the forward** as a pytree argument — "hot-swapping the adapter"
  is passing the latest arrays (SURVEY §2b N2: device-to-device weight sync
  replaces the reference's adapter-file bus, distributed_actor.py:150).

The engine is mesh-agnostic: pass sharded params/batches and GSPMD runs it
TP/DP-sharded; pass host arrays and it runs single-chip.
"""

from __future__ import annotations

import logging
import math
import statistics
import threading
import time
import weakref
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distrl_llm_tpu import obs, telemetry
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.engine.budget import ACTIVATION_RESERVE
from distrl_llm_tpu.models.transformer import (
    decode_view, decode_view_leaves, forward, init_kv_cache, init_kv_cache_int8,
)
from distrl_llm_tpu.ops.per_device import params_mesh
from distrl_llm_tpu.ops.sampling import sample_with_logprob

Params = dict[str, Any]

_logger = logging.getLogger(__name__)

# chunked-dispatch fallback counter (one owner; every engine's chunk cache
# funnels through compile_chunk_guarded here)
ENGINE_CHUNK_FALLBACK = "engine/chunk_fallback"


class GenerationResult(NamedTuple):
    tokens: np.ndarray  # [B, n, T] int32, pad-filled after EOS
    lengths: np.ndarray  # [B, n] generated token counts (incl. EOS)
    # decode step programs dispatched for this round (None where the engine
    # doesn't count them). With speculative decoding, tokens/steps/slots > 1
    # measures the realized draft acceptance — the number to tune spec_draft
    # against on real hardware.
    steps_dispatched: int | None = None
    # sum over dispatched steps of the number of ALIVE slots at that step
    # (the paged engine, both schedulers). tokens/alive_slot_steps is the
    # realized per-slot emission rate with the drain-tail idle slots excluded —
    # steps_dispatched*slots systematically understates spec acceptance.
    alive_slot_steps: int | None = None
    # RAW-model log-probabilities of the sampled tokens [B, n, T] f32 (the
    # behavior policy's logprobs — what vLLM returns as `logprobs`); the
    # PPO-clip learner objective ratios the current policy against these.
    logprobs: np.ndarray | None = None


class _DecodeState(NamedTuple):
    step: jax.Array
    out: jax.Array  # [Bn, T]
    logps: jax.Array  # [Bn, T] raw-model logprob of each sampled token
    lengths: jax.Array  # [Bn]
    done: jax.Array  # [Bn] bool
    key_mask: jax.Array  # [Bn, Smax]
    logits: jax.Array  # [Bn, V] logits for the next token
    cache: Params


def _prefill(params, lora, prompt_ids, prompt_mask, *, cfg: ModelConfig,
             max_total: int, lora_scale: float, cache_dtype, attn_impl: str):
    b, p = prompt_ids.shape
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        cache = (
            init_kv_cache_int8(cfg, b, max_total)
            if cache_dtype == "int8"
            else init_kv_cache(cfg, b, max_total, dtype=cache_dtype)
        )
        if cfg.looped:
            cache.update(looped_account())
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        key_mask = jnp.pad(prompt_mask, ((0, 0), (0, max_total - p)))
    last_logits, cache = forward(
        params, cfg, prompt_ids,
        attention_mask=key_mask, lora=lora, lora_scale=lora_scale,
        kv_cache=cache, cache_offset=0, attn_impl=attn_impl,
        logits_slice=(p - 1, 1),
    )
    return cache, key_mask, last_logits[:, 0]


def _decode_init(cache, key_mask, first_logits, row_alive,
                 *, n: int, max_steps: int, pad_id: int):
    """Expand prefill state to candidate rows: row b*n + j is candidate j of
    prompt b."""
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        # a looped model's exit account is the round's, not a row's
        account = {k: cache[k] for k in ("exit_stats",) if k in cache}
        cache = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, n, axis=0),
            {k: v for k, v in cache.items() if k not in account})
        cache.update(account)
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        key_mask = jnp.repeat(key_mask, n, axis=0)
        logits = jnp.repeat(first_logits, n, axis=0)
        bn = logits.shape[0]
        return _DecodeState(
            step=jnp.zeros((), jnp.int32),
            out=jnp.full((bn, max_steps), pad_id, jnp.int32),
            logps=jnp.zeros((bn, max_steps), jnp.float32),
            lengths=jnp.zeros((bn,), jnp.int32),
            # rows with an empty prompt are batch padding — born done, so they
            # never gate the early-exit or sample from their NaN logits
            done=jnp.repeat(~row_alive, n, axis=0),
            key_mask=key_mask,
            logits=logits,
            cache=cache,
        )


def _decode_step(params, lora, state: _DecodeState, rng,
                 *, cfg: ModelConfig, prompt_len: int, eos_ids, pad_id: int,
                 temperature, top_p, lora_scale: float, attn_impl: str,
                 top_p_impl: str = "bisect", capture_logprobs: bool = False,
                 cache_read_formulation: str = "dot"):
    """One decode step: sample from the carried logits, write token + KV,
    forward one position.

    The decode loop lives on the HOST, not in a ``lax.while_loop``: the TPU
    compiler double-buffers a while-loop carry that is updated by
    dynamic_update_slice, costing a full KV-cache-sized HBM temp (~9.4 GB at
    the reference rollout volume — measured via compile memory_analysis; the
    same program as a donated single step has ~0 temp bytes and aliases the
    cache exactly). JAX's async dispatch keeps the device saturated across
    host-dispatched steps, and the host-side gap is where early exit happens —
    once every row has hit EOS the remaining steps are never dispatched (the
    fixed-shape analogue of continuous batching draining its tail)."""
    s = state
    # fused sample+logprob when the kernel is enabled (DISTRL_SAMPLE_KERNEL
    # / probe — ops/sampling.py), multi-pass reference otherwise; greedy
    # outputs bit-identical either way. Done rows' logprobs are zeroed
    # below, so the pre-pad-substitution logprob is observably identical
    # to the old post-substitution token_logprob.
    with jax.named_scope(telemetry.ENGINE_SAMPLE):
        step_rng = jax.random.fold_in(rng, s.step)
    # outside every scope: the fused sampler's call must keep its name
    # (ops/sampling.py); the multi-pass path names its own work
    tok, logp_s = sample_with_logprob(
        step_rng, s.logits, temperature, top_p,
        top_p_impl=top_p_impl, capture_logprob=capture_logprobs,
    )
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        tok = jnp.where(s.done, pad_id, tok)
        out = jax.lax.dynamic_update_slice(s.out, tok[:, None], (0, s.step))
        if capture_logprobs:  # per-step vocab logsumexp — only when requested
            logp = jnp.where(s.done, 0.0, logp_s)
            logps = jax.lax.dynamic_update_slice(
                s.logps, logp[:, None], (0, s.step)
            )
        else:
            logps = s.logps
        lengths = s.lengths + (~s.done).astype(jnp.int32)
        hit_eos = jnp.isin(tok, eos_ids)
        # the just-sampled token occupies position prompt_len + step for rows
        # that were still alive; they attend to it on the next forward
        key_mask = jax.lax.dynamic_update_slice(
            s.key_mask, (~s.done).astype(s.key_mask.dtype)[:, None],
            (0, prompt_len + s.step),
        )
        done = s.done | hit_eos
    next_logits, cache = forward(
        params, cfg, tok[:, None],
        attention_mask=key_mask, lora=lora, lora_scale=lora_scale,
        # a looped model's exit account counts the rows that emit a token
        kv_cache=({**s.cache, "alive": ~s.done} if "exit_stats" in s.cache
                  else s.cache),
        cache_offset=prompt_len + s.step,
        attn_impl=attn_impl,
        cache_read_formulation=cache_read_formulation,
    )
    cache.pop("alive", None)
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        return _DecodeState(
            step=s.step + 1, out=out, logps=logps, lengths=lengths, done=done,
            key_mask=key_mask, logits=next_logits[:, 0], cache=cache,
        )


def _decode_chunk(params, lora, state: _DecodeState, rng,
                  *, chunk: int, cfg: ModelConfig,
                  prompt_len: int, eos_ids, pad_id: int, temperature, top_p,
                  lora_scale: float, attn_impl: str, top_p_impl: str,
                  capture_logprobs: bool,
                  cache_read_formulation: str = "mulred"):
    """``chunk`` decode steps in ONE dispatch via ``lax.scan``.

    Each host dispatch has a cost of its own (tools/dispatch_probe.py
    measures it); where that cost, not the chip, bounds decode throughput,
    scanning K steps into one program divides it by K.

    The body is NOT guarded by ``lax.cond`` — that select double-buffers
    the carried KV cache (see scan_steps_guarded). Steps past all-done are
    per-row no-ops (done rows write pad beyond their recorded length), but
    a step at ``step >= max_steps`` would clamp its dynamic_update_slice
    onto the last valid position and corrupt it, so the HOST must never
    dispatch a chunk crossing ``max_steps``: ``_generate_wave`` runs
    ``max_steps // chunk`` chunks and finishes a non-divisor tail with
    per-step dispatches.

    The engine still compile-checks ``memory_analysis().temp_size_in_bytes``
    before trusting a chunked program and falls back to the host loop if
    the cache got double-buffered anyway (``_chunk_fn_for_bucket``)."""
    def run(s):
        return _decode_step(
            params, lora, s, rng, cfg=cfg, prompt_len=prompt_len,
            eos_ids=eos_ids, pad_id=pad_id, temperature=temperature,
            top_p=top_p, lora_scale=lora_scale, attn_impl=attn_impl,
            top_p_impl=top_p_impl, capture_logprobs=capture_logprobs,
            cache_read_formulation=cache_read_formulation,
        )

    return scan_steps_guarded(run, state, chunk)


def generate_in_waves(
    inner_generate,
    max_rows: int,
    params,
    lora,
    prompt_ids,
    prompt_mask,
    sampling: SamplingConfig,
    rng: jax.Array,
    pad_id: int,
) -> GenerationResult:
    """Cap concurrent candidate rows at ``max_rows`` by running the round in
    sequential WAVES of whole prompt groups — vLLM's ``max_num_seqs``
    admission control, static-shape edition (the reference tunes the same
    knob as engine capacity: 256 concurrent sequences @ actor_gpu_usage,
    train_distributed.py:34). This is what lets a 7B model run the
    reference's 480-row rollout volume on one chip: each wave's KV cache
    fits, waves reuse one compiled program (the tail wave pads with dead
    rows), and early exit drains each wave's stragglers."""
    b = prompt_ids.shape[0]
    n = max(sampling.n, 1)
    if not max_rows or b * n <= max_rows:
        return inner_generate(params, lora, prompt_ids, prompt_mask, sampling, rng)
    per_wave = max(max_rows // n, 1)
    tokens, lengths, logps = [], [], []
    steps = alive = 0
    have_steps = have_alive = have_logps = True
    for w in range(-(-b // per_wave)):
        lo = w * per_wave
        ids = prompt_ids[lo : lo + per_wave]
        mask = prompt_mask[lo : lo + per_wave]
        pad = per_wave - ids.shape[0]
        if pad:  # tail wave: dead rows keep the compiled shape
            ids = jnp.concatenate(
                [jnp.asarray(ids), jnp.full((pad, ids.shape[1]), pad_id, jnp.int32)]
            )
            mask = jnp.concatenate(
                [jnp.asarray(mask), jnp.zeros((pad, mask.shape[1]), jnp.int32)]
            )
        res = inner_generate(
            params, lora, ids, mask, sampling, jax.random.fold_in(rng, w)
        )
        keep = per_wave - pad
        tokens.append(res.tokens[:keep])
        lengths.append(res.lengths[:keep])
        if res.logprobs is None:
            have_logps = False
        else:
            logps.append(res.logprobs[:keep])
        if res.steps_dispatched is None:
            have_steps = False
        else:
            steps += res.steps_dispatched
        if res.alive_slot_steps is None:
            have_alive = False
        else:
            # a tail wave's pad rows are dead from the start: never alive
            alive += res.alive_slot_steps
    return GenerationResult(
        tokens=np.concatenate(tokens, axis=0),
        lengths=np.concatenate(lengths, axis=0),
        steps_dispatched=steps if have_steps else None,
        alive_slot_steps=alive if have_alive else None,
        logprobs=np.concatenate(logps, axis=0) if have_logps else None,
    )


def scan_steps_guarded(run, state, chunk: int):
    """The one copy of the chunked-dispatch scaffolding every engine's
    chunk body shares: ``chunk`` iterations of ``lax.scan`` running one
    decode step each, UNCONDITIONALLY.

    Earlier rounds wrapped the body in ``lax.cond(halt, skip, run)`` to
    spare flops once every row was done — and that cond was exactly what
    double-buffered the carry: the select between the skipped and stepped
    KV caches keeps both alive, so the TPU compiler materialized a full
    cache-sized temp (found on the chip, tools/scan_alias_probe.py: the
    same body compiles with temp == cache bytes with the cond and ~0
    without, scan and fori_loop alike), and every chunked program then
    fell back to host dispatch.

    Running the body unconditionally is semantically safe because the
    step functions are ALREADY per-row no-ops for done rows — partial
    doneness forces that (done rows write pad beyond their length /
    scatter to dropped sentinel rows / park dead slots on the scratch
    page), and the rng step index advances exactly as the host loop
    would. The one case masking does NOT cover is a step whose write
    index would clamp past the output buffer (dense/wave flavors at
    ``step >= max_steps``), so CALLERS must never dispatch a chunk that
    crosses ``max_steps`` — the hosts run ``max_steps // k`` chunks and
    finish a non-divisor tail with per-step dispatches. Refill/spec
    flavors need no cadence guard: their slots self-stop at per-slot
    budgets and their writes drop out-of-range rows."""
    def body(s, _):
        return run(s), None

    return jax.lax.scan(body, state, None, length=chunk)[0]


def compile_chunk_guarded(fn_jit, alias_bytes: int, what: str,
                          *args, fusion_bytes: int = 0, **kwargs):
    """Lower + compile a K-steps-per-dispatch program and inspect its
    ``memory_analysis`` BEFORE it ever runs: if the TPU compiler
    double-buffered the scanned carry (temp bytes on the order of the KV
    buffers it was supposed to alias — ``alias_bytes``), the chunked
    program would OOM the very configs it is meant to speed up, so reject
    it (return None) and let the caller fall back to one dispatch per
    step. A compile FAILURE is raised on a TPU backend (the chip never
    measures another program in silence); other backends return None.
    Backends without memory analysis (CPU tests) accept the program.

    The rejection needs BOTH a relative and an absolute threshold: at tiny
    test scales, legitimate scratch (attention workspaces, gathers) can
    exceed half of a kilobyte-sized cache without any double-buffering —
    the failure mode this guards against is a CACHE-sized temp, which at
    any scale that matters is hundreds of MBs.

    ``fusion_bytes`` is the second, smaller envelope for
    mulred-formulation programs: the per-layer ``_gqa_mulred``
    broadcast-product temp ([B, KH, G, D, S] f32) that a backend failing
    to fuse reduce-of-product into the cache read would materialize. That
    temp is G× one cache layer but can sit BELOW half the total cache
    (e.g. G=7 over 24 layers ≈ 0.29× cache), sailing under the
    double-buffer check — so it gets its own threshold, with its own
    64 MiB floor against tiny-scale scratch false positives.

    EVERY fallback here is loud: a ``log.warning`` naming the cause plus
    an ``engine/chunk_fallback`` telemetry counter — silently flipping
    ``scan_chunk_active`` would let a run measure another program than
    the one its configuration names."""
    try:
        compiled = fn_jit.lower(*args, **kwargs).compile()
        # compile tracker (ISSUE 8): keyed by program name × the arg
        # shape signature, so compiling the SAME shapes twice — the
        # upstream caches are supposed to make that impossible — reads as
        # a retrace, while a genuinely new shape is just a compile
        obs.note_compile(what, arg_shape_signature(args, kwargs))
        temp = None
        try:
            ma = compiled.memory_analysis()
            temp = getattr(ma, "temp_size_in_bytes", None)
        except Exception:  # noqa: BLE001 — backend without memory analysis
            pass
        if temp is not None and temp > 0.5 * alias_bytes and temp > 256 * 2**20:
            _logger.warning(
                "%s: chunked program double-buffers its carry (temp %.2f "
                "GiB vs aliased buffers %.2f GiB) — falling back to "
                "host-dispatched steps",
                what, temp / 2**30, alias_bytes / 2**30,
            )
            telemetry.counter_add(ENGINE_CHUNK_FALLBACK)
            return None
        if (
            temp is not None and fusion_bytes
            and temp > 0.5 * fusion_bytes and temp > 64 * 2**20
        ):
            _logger.warning(
                "%s: chunked program materializes a broadcast-product-sized "
                "temp (%.2f GiB vs _gqa_mulred product %.2f GiB) — the "
                "backend failed to fuse the G-expanded [B,KH,G,D,S] "
                "multiply into the cache read; falling back to "
                "host-dispatched steps",
                what, temp / 2**30, fusion_bytes / 2**30,
            )
            telemetry.counter_add(ENGINE_CHUNK_FALLBACK)
            return None
        return compiled
    except Exception as e:  # pragma: no cover - backend-specific
        if jax.default_backend() == "tpu":
            # on the chip a program that does not compile is a fault to
            # show, not a reason to measure another program
            raise
        _logger.warning(
            "%s: chunked program compile failed (%s: %s) — falling back "
            "to host-dispatched steps",
            what, type(e).__name__, e,
        )
        telemetry.counter_add(ENGINE_CHUNK_FALLBACK)
        return None


def cached_chunk_program(cache: dict, mu, key, fn_jit, alias_bytes: int,
                         what: str, *args, fusion_bytes: int = 0, **kwargs):
    """Mutex-guarded memoization of ``compile_chunk_guarded`` — one shared
    implementation so every engine's chunk-program cache carries the same
    locking (concurrent generate() calls share an engine in the trainer's
    hybrid split) and the same None-means-fell-back convention."""
    with mu:
        if key not in cache:
            cache[key] = compile_chunk_guarded(
                fn_jit, alias_bytes, what, *args,
                fusion_bytes=fusion_bytes, **kwargs
            )
        return cache[key]


try:
    import resource

    def _involuntary_switches() -> int | None:
        return resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw

    def _major_faults() -> int | None:
        return resource.getrusage(resource.RUSAGE_SELF).ru_majflt
except ImportError:  # a platform without getrusage

    def _involuntary_switches() -> int | None:
        return None

    _major_faults = _involuntary_switches

#: an unmarked boundary longer than this many of its round's median intervals
#: is ``stalled``. The steady rounds of all eight rollout cells stay under 1.2
#: (PERF.md section 6, PR 56, says from which readings)
STALL_FACTOR = 1.5


class RoundHostAccount:
    """The host's side of one wave's decode loop, on ``perf_counter``, with
    tracing on or off. The clocks are read per host BOUNDARY (where the loops
    stop to read a snapshot), never per step, and every boundary is kept:
    ``boundaries`` holds, for each interval between two consecutive returns
    from the snapshot wait, ``(interval_s, host_s, cpu_s, nivcsw, steps,
    marks)``: the interval, the part of it before its wait began, the CPU
    seconds of ALL the process's threads over it (``time.process_time``), the
    involuntary context switches over it (``getrusage``; None where the
    platform has none), the decode steps dispatched in it, and which passes
    that are long by design ran in it (``mark``: ``a`` admission, ``g`` grant,
    ``p`` preemption; empty for a plain boundary).

    ``loop_s`` is the loop's wall (construction to ``stop()``); ``blocked_s``
    the seconds inside the snapshot waits and the readback's blocking reads
    (``readback_s`` that last part alone); ``first_s`` construction to the
    first return (set-up, the fan-out, the first admissions: in no interval of
    the list); ``slowest_s`` the longest interval
    and ``slowest_host_s`` the host part of THAT interval, which ``stop()``
    derives from the list: a long interval with a large host part is a host
    that stalled, with a small one a device that was late.
    ``accumulate_round_stats`` folds a wave's account into
    ``last_round_stats`` and files the gauges and the histogram."""

    __slots__ = ("t0", "loop_s", "blocked_s", "readback_s", "first_s",
                 "slowest_s", "slowest_host_s", "boundaries", "_last_return",
                 "_cpu", "_switches", "_steps", "_marks")

    def __init__(self):
        self.loop_s = self.blocked_s = self.readback_s = self.first_s = 0.0
        self.slowest_s = self.slowest_host_s = 0.0
        self.boundaries: list[tuple] = []
        self._last_return: float | None = None
        self._cpu = self._steps = 0
        self._switches: int | None = None
        self._marks = ""
        self.t0 = time.perf_counter()

    def waited(self, since: float, steps: int = 0) -> None:
        """A snapshot wait that began at ``since`` has just returned;
        ``steps`` decode steps have been dispatched so far."""
        now = time.perf_counter()
        cpu = time.process_time()
        switches = _involuntary_switches()
        self.blocked_s += now - since
        last = self._last_return
        if last is not None:
            self.boundaries.append((
                now - last, since - last, cpu - self._cpu,
                None if switches is None else switches - self._switches,
                steps - self._steps, self._marks,
            ))
        else:
            self.first_s = now - self.t0
        self._last_return = now
        self._cpu, self._switches, self._steps = cpu, switches, steps
        self._marks = ""

    def mark(self, what: str) -> None:
        """A pass that is long by design (``a``, ``g`` or ``p``) ran in the
        interval that the next return from the wait ends."""
        if what not in self._marks:
            self._marks += what

    def blocked(self, since: float) -> None:
        """The host has been blocked on the device from ``since`` to now (the
        readback's reads)."""
        waited = time.perf_counter() - since
        self.blocked_s += waited
        self.readback_s += waited

    def stop(self) -> float:
        self.loop_s = time.perf_counter() - self.t0
        if self.boundaries:
            self.slowest_s, self.slowest_host_s = max(
                self.boundaries, key=lambda b: b[0])[:2]
        return self.loop_s


def stalled_boundaries(boundaries) -> tuple[float, list[int], float]:
    """``(median_s, stalled, recovered_s)`` of a round's boundaries: the
    median interval; the indices of the unmarked boundaries longer than
    ``STALL_FACTOR`` medians; and, for the longest of those, how much of it
    the device had already worked off: the sum, over the boundaries that
    follow it in the round with no fewer steps, of what each came back under
    the median. If only the snapshot's arrival was late the device ran on
    through its queue and the next boundaries are short (the round loses
    less than the stall); if the device sat idle they come back at the median
    and this reads 0."""
    if not boundaries:
        return 0.0, [], 0.0
    median_s = statistics.median(b[0] for b in boundaries)
    stalled = [i for i, b in enumerate(boundaries)
               if not b[5] and b[0] > STALL_FACTOR * median_s]
    if not stalled:
        return median_s, stalled, 0.0
    worst = max(stalled, key=lambda i: boundaries[i][0])
    steps = boundaries[worst][4]
    recovered_s = sum(max(0.0, median_s - b[0])
                      for b in boundaries[worst + 1:] if b[4] >= steps)
    return median_s, stalled, recovered_s


def accumulate_round_stats(
    stats: dict | None, *, prefill_s: float, prefill_tokens: int,
    prompt_rows: int, decode_s: float, gen_tokens: int, gen_rows: int,
    host: RoundHostAccount | None = None,
) -> dict:
    """Fold one wave's timing/token counts into a round's running stats —
    the ``last_round_stats`` contract every engine shares. The trainer
    snapshots this per round (like ``last_pool_stats``) and derives the
    ``engine/prefill_tok_s`` / ``engine/decode_tok_s`` / ``engine/mfu``
    metric series from it. ``host`` is the wave's ``RoundHostAccount``: its
    walls are summed, its longest boundary is the maximum over the round's
    waves, its boundaries join the round's list (each interval also goes into
    the histogram ``engine/boundary_ms``), and the four gauges of the round
    so far are set from the sums."""
    if stats is None:
        stats = {
            "prefill_s": 0.0, "prefill_tokens": 0, "prompt_rows": 0,
            "decode_s": 0.0, "gen_tokens": 0, "gen_rows": 0,
        }
    if host is not None:
        stats["loop_s"] = stats.get("loop_s", 0.0) + host.loop_s
        stats["host_blocked_s"] = stats.get("host_blocked_s", 0.0) + host.blocked_s
        stats["readback_s"] = stats.get("readback_s", 0.0) + host.readback_s
        stats["first_boundary_s"] = stats.get("first_boundary_s", 0.0) + host.first_s
        if host.slowest_s >= stats.get("slowest_boundary_s", 0.0):
            stats["slowest_boundary_s"] = host.slowest_s
            stats["slowest_boundary_host_s"] = host.slowest_host_s
        boundaries = stats.setdefault("boundaries", [])
        boundaries.extend(host.boundaries)
        for b in host.boundaries:
            telemetry.hist_observe(telemetry.ENGINE_BOUNDARY_MS, 1e3 * b[0])
        if boundaries:
            stats["boundary_median_s"] = statistics.median(b[0] for b in boundaries)
            telemetry.gauge_set(
                telemetry.ENGINE_BOUNDARY_MEDIAN_MS, 1e3 * stats["boundary_median_s"])
        if stats["loop_s"] > 0:
            telemetry.gauge_set(
                telemetry.ENGINE_HOST_BUSY_SHARE,
                100.0 * (1.0 - stats["host_blocked_s"] / stats["loop_s"]),
            )
        telemetry.gauge_set(
            telemetry.ENGINE_SLOWEST_BOUNDARY_MS, 1e3 * stats["slowest_boundary_s"])
        telemetry.gauge_set(
            telemetry.ENGINE_SLOWEST_BOUNDARY_HOST_MS,
            1e3 * stats["slowest_boundary_host_s"],
        )
    stats["prefill_s"] += prefill_s
    stats["prefill_tokens"] += prefill_tokens
    stats["prompt_rows"] += prompt_rows
    stats["decode_s"] += decode_s
    stats["gen_tokens"] += gen_tokens
    stats["gen_rows"] += gen_rows
    # monotonic generated-token counter (ISSUE 8): the one series the live
    # endpoint and the driver's fleet aggregator derive tok/s from — one
    # locked dict write per WAVE, not per token
    if gen_tokens:
        telemetry.counter_add(obs.OBS_GEN_TOKENS, gen_tokens)
    return stats


_PRESSURE = ("cpu", "memory", "io")


def _pressure_us() -> dict | None:
    """``some total=`` microseconds of ``/proc/pressure/{cpu,memory,io}``:
    how long at least one task of the machine has waited for each so far.
    None where the kernel offers no such file."""
    out = {}
    try:
        for what in _PRESSURE:
            with open(f"/proc/pressure/{what}", encoding="ascii") as f:
                some = f.readline()
            out[what] = int(some.rsplit("total=", 1)[1])
    except (OSError, IndexError, ValueError):
        return None
    return out


class RoundMarks:
    """What ``file_round`` needs of a round's START, read at the entry of an
    engine's ``generate``: the wall and ``perf_counter``, the programs built
    and the full collections' milliseconds so far (``telemetry``), the major
    page faults and the machine's pressure totals. Two small reads of
    ``/proc`` and a ``getrusage`` a round; tracing on or off."""

    __slots__ = ("t0_ns", "t0", "programs", "gc_ms", "majflt", "pressure")

    def __init__(self):
        self.programs = telemetry.programs_built()
        self.gc_ms = telemetry.gc_full_ms()
        self.majflt = _major_faults()
        self.pressure = _pressure_us()
        self.t0_ns = time.time_ns()
        self.t0 = time.perf_counter()


def file_round(marks: RoundMarks, stats: dict | None) -> dict | None:
    """The end of an engine's ``generate``: one record of the round into
    ``telemetry.round_filed`` (the ring ``telemetry.round_records()`` reads),
    the counter ``engine/stalled_boundaries``, the round's verdict into
    ``stats`` (``stalled``, ``recovered_s``), and ONE warning where a
    boundary stalled, so that standard error of an untraced run holds what
    its result line cannot. ``stats`` is the round's ``last_round_stats``;
    an engine that kept no host account files nothing."""
    wall_s = time.perf_counter() - marks.t0
    if not stats or "boundaries" not in stats:
        return None
    boundaries = stats["boundaries"]
    median_s, stalled, recovered_s = stalled_boundaries(boundaries)
    stats["stalled"], stats["recovered_s"] = stalled, recovered_s
    majflt, pressure = _major_faults(), _pressure_us()
    record = telemetry.round_filed({
        "t0_ns": marks.t0_ns, "wall_s": wall_s,
        "prefill_s": stats["prefill_s"], "loop_s": stats["loop_s"],
        "blocked_s": stats["host_blocked_s"], "readback_s": stats["readback_s"],
        "first_s": stats["first_boundary_s"],
        "boundaries": [list(b) for b in boundaries],
        "median_s": median_s, "stalled": stalled, "recovered_s": recovered_s,
        "programs_built": telemetry.programs_built() - marks.programs,
        "gc_full_s": (telemetry.gc_full_ms() - marks.gc_ms) / 1e3,
        "majflt": None if majflt is None else majflt - marks.majflt,
        "pressure_us": None if pressure is None or marks.pressure is None else {
            k: pressure[k] - marks.pressure[k] for k in _PRESSURE},
    })
    if stalled:
        telemetry.counter_add(telemetry.ENGINE_STALLED_BOUNDARIES, len(stalled))
        worst = max(stalled, key=lambda i: boundaries[i][0])
        interval_s, host_s, cpu_s, switches, _, _ = boundaries[worst]
        _logger.warning(
            "round %d: boundary %d of %d stalled: %.1f ms for a median of %.1f "
            "(host part %.1f ms, process CPU %.1f ms, %s involuntary switches); "
            "%d stalled in the round; the boundaries after it came back %.1f ms "
            "under the median; full collections %.1f ms, programs built %d, "
            "major faults %s, pressure gained (us) %s",
            record["round"], worst, len(boundaries), 1e3 * interval_s,
            1e3 * median_s, 1e3 * host_s, 1e3 * cpu_s, switches, len(stalled),
            1e3 * recovered_s, 1e3 * record["gc_full_s"],
            record["programs_built"], record["majflt"], record["pressure_us"],
        )
    return record


def looped_account() -> dict:
    """What a looped model's decode state holds beside its K/V: the round's
    exit account, which every decode step adds to
    (``transformer._exit_step_sums``); no row state."""
    return {"exit_stats": jnp.zeros((2,), jnp.float32)}


def file_exit_stats(cfg: ModelConfig, stats) -> dict:
    """File a round's ``engine/exit_step_mean``: for a looped model its exit
    account (``stats`` ``[2]``: the sum over decoded tokens of the pass the
    exit distribution would stop at, and the tokens), returned as the round's
    span says it; for a model that runs its layers once the one pass every
    token takes, so that the gauge never holds another engine's reading."""
    if not cfg.looped:
        telemetry.gauge_set(telemetry.ENGINE_EXIT_STEP_MEAN, 1.0)
        return {}
    total, tokens = (float(x) for x in np.asarray(stats))
    if tokens <= 0:
        return {}
    telemetry.gauge_set(telemetry.ENGINE_EXIT_STEP_MEAN, total / tokens)
    return {"exit_step_mean": total / tokens}


def file_loop_layer_steps(cfg: ModelConfig, steps: int) -> None:
    """``engine/loop_layer_steps``: the layer applications of a looped
    model's round, ``layer_steps`` a decode step and as many for the prefill
    forward (host arithmetic, like ``ops/paged_grid_steps``). A model that
    runs its layers once files nothing."""
    if cfg.looped:
        telemetry.counter_add(
            telemetry.ENGINE_LOOP_LAYER_STEPS, cfg.layer_steps * (steps + 1))


def pool_nbytes(*trees) -> int:
    """Total bytes of the KV buffers a chunked program must alias in place
    (the denominator of compile_chunk_guarded's double-buffer check)."""
    return sum(
        x.nbytes for x in jax.tree_util.tree_leaves(trees)
    )


def arg_shape_signature(args, kwargs=None) -> tuple:
    """Hashable shape/dtype signature of a call's array leaves — the
    "shape signature" half of the obs compile tracker's key (non-array
    leaves are value-like and excluded: their churn is not a retrace)."""
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    return tuple(
        (tuple(x.shape), jnp.dtype(x.dtype).name)
        for x in leaves
        if hasattr(x, "shape") and hasattr(x, "dtype")
    )


def lora_signature(lora):
    """Hashable (structure, leaf shapes/dtypes) key for an adapter pytree.
    Compiled executables (unlike jits) raise on a structurally different
    tree instead of retracing, so chunk-program caches must key on this."""
    return (
        jax.tree_util.tree_structure(lora),
        tuple(
            (tuple(x.shape), jnp.dtype(x.dtype).name)
            for x in jax.tree_util.tree_leaves(lora)
        ),
    )


def make_swap_aware_chunk_step(mailbox, lora_cell: list, steps_seen: list,
                               k: int, max_steps: int, chunk_fn, lora0,
                               rebuild, run_chunk, run_step):
    """Chunk-dispatch step closure shared by the dense, paged-wave, and
    sharded engines: consumes in-flight adapter swaps at chunk boundaries,
    and refetches the chunk program from its signature-keyed cache when a
    swap changes the adapter's STRUCTURE (e.g. a None-adapter round
    receiving its first adapter) — compiled executables raise on a
    structurally different pytree instead of retracing.

    When the new signature's program fell back (memory guard / compile
    failure), the round finishes per-step at the same k-step cadence,
    capped at ``max_steps`` total: the per-step functions are UNGUARDED
    (they clamp-write onto the last output column past ``max_steps``),
    and the chunk program's scan body is unguarded too
    (scan_steps_guarded), so the HOST cadence is what keeps every
    dispatched step below ``max_steps``.

    ``rebuild(lora, state) -> program|None``;
    ``run_chunk(program, lora, state) -> state``;
    ``run_step(lora, state) -> state``.
    """
    cell = [chunk_fn, lora_signature(lora0)]

    def step(s):
        # in-flight swaps land at chunk boundaries: the recorded swap step
        # is the first position decoded under the new adapter
        prev = lora_cell[0]
        mailbox._take_pending_lora(lora_cell, steps_seen[0])
        if lora_cell[0] is not prev:
            sig = lora_signature(lora_cell[0])
            if sig != cell[1]:
                cell[0] = rebuild(lora_cell[0], s)
                cell[1] = sig
        start = steps_seen[0]
        steps_seen[0] += k
        if cell[0] is None:
            # min() is defensive: every caller now floor-divides the cadence
            # (run_nondivisor_tail), so start + k <= max_steps always holds
            for _ in range(min(k, max_steps - start)):
                s = run_step(lora_cell[0], s)
            return s
        return run_chunk(cell[0], lora_cell[0], s)

    return step


def pick_chunk(scan_chunk: int, max_steps: int) -> int:
    """Steps-per-dispatch for a wave of ``max_steps``: the largest divisor
    of ``max_steps`` that is ≤ ``scan_chunk``, preferred over a floor
    cadence with a per-step tail — at 1,200 steps and scan_chunk=64 the
    divisor 60 gives 20 full chunks and no tail, vs 18 chunks + 48
    per-step dispatches. Falls back to
    ``min(scan_chunk, max_steps)`` (run_nondivisor_tail handles the
    remainder) when the best divisor would lose more than half the
    requested amortization (e.g. a prime max_steps)."""
    k = max(1, min(scan_chunk, max_steps))
    best = max((d for d in range(1, k + 1) if max_steps % d == 0), default=1)
    return best if best * 2 > k else k


def run_nondivisor_tail(mailbox, lora_cell: list, steps_seen: list,
                        rem: int, state, run_step):
    """Finish a chunked wave's non-divisor tail with per-step dispatches —
    the one copy of the cadence invariant every wave engine shares:
    unguarded scan bodies (scan_steps_guarded) must never cross
    ``max_steps``, so hosts dispatch ``max_steps // k`` full chunks and
    run the remaining ``rem`` steps here (skipped once every row hit
    EOS). The in-flight-swap recording protocol matches the main loops:
    consume pending adapters before each step, advancing ``steps_seen``.
    ``run_step(lora, state) -> state`` — the same closure shape
    ``make_swap_aware_chunk_step`` takes."""
    # graftcheck: hot-region decode-tail
    # graftcheck: disable=GC301 -- one blocking all-done read per WAVE at tail entry, not per decode step
    if not rem or bool(np.asarray(state.done).all()):
        return state
    for _ in range(rem):
        mailbox._take_pending_lora(lora_cell, steps_seen[0])
        steps_seen[0] += 1
        with telemetry.span(telemetry.ENGINE_DISPATCH,
                            step=steps_seen[0] - 1, steps=1):
            state = run_step(lora_cell[0], state)
    # graftcheck: end-hot-region
    return state


def run_decode_loop(step_fn, state, max_steps: int, decode_chunk: int, *,
                    steps_per_call: int = 1,
                    host: RoundHostAccount | None = None):
    """Host-dispatched decode loop shared by the dense and paged engines:
    call ``step_fn(state) -> state`` up to ``max_steps`` times with async
    early exit. ``steps_per_call`` is what one call runs (k for a scanned
    chunk): the ``engine/dispatch`` span round each call says so.

    Every ``check`` steps a COPY of the done flags (the original is donated
    into the next step) starts an async device→host transfer; the oldest
    snapshot is read only once a newer one is in flight, so the read waits on
    a transfer that finished steps ago, never on the device's current step.
    Worst-case overshoot after all rows hit EOS is ~2·check steps — the
    fixed-shape analogue of continuous batching draining its tail.

    ``host`` is the wave's account of the host's side: the clocks are read
    round each snapshot wait, once a boundary, never per step. The boundary's
    own launches (the copy and its transfer) are the span
    ``engine/snapshot_launch``."""
    from collections import deque

    check = max(1, min(decode_chunk, 16))
    snapshots: deque = deque()
    steps_done = 0
    # graftcheck: hot-region decode
    while steps_done < max_steps:
        with telemetry.span(telemetry.ENGINE_DISPATCH,
                            step=steps_done * steps_per_call, steps=steps_per_call):
            state = step_fn(state)
        steps_done += 1
        if steps_done % check == 0 or steps_done == max_steps:
            with telemetry.span(telemetry.ENGINE_SNAPSHOT_LAUNCH):
                snap = jnp.copy(state.done)
                try:
                    snap.copy_to_host_async()
                except AttributeError:
                    pass
            snapshots.append(snap)
            stop = False
            while len(snapshots) > 1:
                # delayed read of an ASYNC-copied snapshot: a newer copy is
                # already in flight, so this waits on a transfer that
                # finished ~check steps ago, never on the current step
                t_wait = time.perf_counter()
                with telemetry.span(telemetry.ENGINE_SNAPSHOT_WAIT):
                    # graftcheck: disable=GC301 -- reads a finished async copy >=1 check-intervals old
                    all_done = bool(np.asarray(snapshots.popleft()).all())
                if host is not None:
                    host.waited(t_wait, steps_done * steps_per_call)
                if all_done:
                    stop = True
                    break
            if stop:
                break
    # graftcheck: end-hot-region
    return state


def _view_shortfall(held: list) -> int:
    """Bytes by which a second copy of ``held`` (the stacked leaves a decode
    view holds a layer at a time) would cut into the share of the device's
    memory that engine/budget.py keeps for a round's workspace
    (``ACTIVATION_RESERVE``): a device's shard of each leaf against the first
    local device's memory as it stands now (``obs.hbm_free``). 0 where the
    copy fits, and where the backend reports no memory (the CPU). What the
    round allocates after the view is built is not seen here."""
    reading = obs.hbm_free()
    if not held or reading is None:
        return 0
    limit, in_use, _ = reading

    def shard_bytes(leaf):
        sharding = getattr(leaf, "sharding", None)
        shape = sharding.shard_shape(leaf.shape) if sharding else leaf.shape
        return math.prod(shape) * leaf.dtype.itemsize

    room = int((1 - ACTIVATION_RESERVE) * limit) - in_use
    return max(0, sum(map(shard_bytes, held)) - room)


class LoraMailbox:
    """In-flight weight-update mailbox shared by every engine (PipelineRL —
    see ``push_lora``). ``_swapped_lora`` carries a consumed swap across the
    WAVES of one round (each wave builds a fresh closure from the
    round-entry adapter, which would otherwise silently revert the swap);
    ``_reset_lora_mailbox_round`` runs at round entry so a new round's
    trainer-passed adapter supersedes the carry."""

    # single-slot pending mailbox: (adapter, version) written/consumed as
    # ONE reference so the learner thread's push can never be paired with a
    # stale partner field by the concurrently-consuming generation thread
    _pending: tuple | None = None
    _swapped_lora = None
    # the adapter (and its version) the latest consumed swap SUPERSEDED —
    # i.e. the policy's own previous LoRA version. The speculative
    # self-drafter runs it as the draft model (PipelineRL's observation
    # that recent-checkpoint weights stay near-on-policy makes it a
    # high-acceptance draft source for free), and the (step, version) swap
    # log above gives exact draft/target version bookkeeping. Retention is
    # OPT-IN (_track_prev_lora — set by engines running the self drafter):
    # only that drafter reads the slot, and unconditional retention would
    # pin a whole extra adapter version in device memory for the engine's
    # lifetime on runs that never consume it
    _track_prev_lora = False
    _prev_lora = None
    _prev_lora_version: int | None = None

    # the frozen base as the cache-mode programs read it
    # (models/transformer.py::decode_view), single-slot: (weak references to
    # the leaves it was built from, the view, or None where it did not fit).
    # Weak, so the slot neither keeps a base its caller dropped nor mistakes a
    # new array at a dead one's address for it
    _view_slot: tuple | None = None

    def _decode_params(self, params):
        """``params`` with the mixer's projections one ``[out, in]`` array a
        layer, built once a base: the caller hands the stacked tree every
        round, and the same leaves give the same view. New leaves (full
        fine-tuning, a checkpoint load) drop the old view before the new one
        is built. The view is a second copy of those leaves: where the device
        has no room for it (``_view_shortfall``) the round reads the stacked
        tree as it is, and a warning says so once a base. Files
        ``engine/decode_view_builds`` and ``engine/decode_view_bytes``."""
        leaves = jax.tree_util.tree_leaves(params)
        slot = self._view_slot
        if (
            slot is not None and len(slot[0]) == len(leaves)
            and all(ref() is leaf for ref, leaf in zip(slot[0], leaves))
        ):
            return params if slot[1] is None else slot[1]
        self._view_slot = None
        held = [leaf for _, leaf in decode_view_leaves(params["layers"])]
        short = _view_shortfall(held)
        if short:
            _logger.warning(
                "no decode view of this base: holding its q/k/v/o projections "
                "a second time, one array a layer, would leave %.0f MB less "
                "than the %.0f%% of the device's memory kept for a round's "
                "workspace; every decode step slices and transposes them "
                "from the stacked tree instead", short / 1e6,
                100 * ACTIVATION_RESERVE)
            view, held = None, []
        else:
            view = decode_view(params)
            telemetry.counter_add(telemetry.ENGINE_DECODE_VIEW_BUILDS, 1)
        telemetry.gauge_set(
            telemetry.ENGINE_DECODE_VIEW_BYTES, float(sum(leaf.nbytes for leaf in held)))
        self._view_slot = (tuple(weakref.ref(leaf) for leaf in leaves), view)
        return params if view is None else view

    def _pending_mu(self) -> threading.Lock:
        # lazily per-instance (the mixin has no __init__); dict.setdefault
        # is atomic under the GIL, so two racing first-callers agree
        mu = self.__dict__.get("_pending_mu_lock")
        if mu is None:
            mu = self.__dict__.setdefault(
                "_pending_mu_lock", threading.Lock()
            )
        return mu

    def push_lora(self, lora, version: int | None = None) -> None:
        """In-flight weight update (PipelineRL-style): the next dispatched
        decode step onwards samples under this adapter, without waiting for
        the round to drain. Adapter shapes must match (the jitted step sees
        new VALUES, not new shapes — no recompile).

        Semantics: KV already resident stays as the OLD adapter computed it
        (the stale-KV regime in-flight updating accepts); post-swap tokens
        sample from the new adapter's forward over that cache. The captured
        per-token behavior logprob is the TRUE probability of that mixed
        sampling process, which is exactly what the PPO-clip ratio needs —
        enable via ``--inflight_weight_updates`` (requires clip_ratio > 0).

        ``version`` is the learner's weight_version for this adapter: the
        consumed swap records (step, version) pairs (``last_swap_steps`` /
        ``last_swap_versions``) so the trainer can tag every generated
        position with the policy version that sampled it
        (rollout/trajectory.py version tags)."""
        # push time rides in the same single-slot tuple (one reference —
        # the consuming thread can never pair it with a stale partner
        # field); the consume observes push→swap latency from it. The lock
        # orders the slot against discard_pending_at_or_below, which must
        # never clobber a newer push that lands mid-check.
        with self._pending_mu():
            self._pending = (lora, version, time.perf_counter())

    def discard_pending_at_or_below(self, version: int) -> None:
        """Drop a pending swap whose version is already covered by the
        adapter a round is about to open with (remote workers: the weight
        bus pushes every update into the mailbox so MID-round swaps work;
        the entry push would otherwise replay as a phantom step-0 swap).
        Atomic with ``push_lora``: a strictly newer push landing
        concurrently survives."""
        with self._pending_mu():
            pending = self._pending
            if (
                pending is not None and pending[1] is not None
                and int(pending[1]) <= int(version)
            ):
                self._pending = None

    def _take_pending_lora(self, lora_cell: list, dispatched: int) -> None:
        with self._pending_mu():
            pending, self._pending = self._pending, None
        if pending is not None:
            lora, version, pushed_t = pending
            # weight-sync observability (ISSUE 8): how long the learner's
            # push sat in the mailbox before a decode dispatch consumed it
            telemetry.hist_observe(
                obs.SWAP_LATENCY_MS,
                (time.perf_counter() - pushed_t) * 1e3,
            )
            if self._track_prev_lora:
                # the adapter being superseded becomes "the previous
                # version" — its own version is the last swap's (None
                # before any swap: the round-entry adapter's version is
                # the trainer's to know)
                self._prev_lora = lora_cell[0]
                self._prev_lora_version = (
                    self.last_swap_versions[-1] if self.last_swap_versions
                    else None
                )
            self._swapped_lora = lora
            lora_cell[0] = lora
            self.last_swap_steps.append(dispatched)
            self.last_swap_versions.append(version)

    def _round_entry_lora(self, lora):
        """Adapter a wave should open with: the in-round swap if one
        happened, else the caller's."""
        return self._swapped_lora if self._swapped_lora is not None else lora

    def _reset_lora_mailbox_round(self) -> None:
        self._swapped_lora = None


class GenerationEngine(LoraMailbox):
    """Compiled rollout engine bound to (model config, shapes, eos/pad ids).

    ``generate`` is the ``vllm_generate`` equivalent: prompts in, per-candidate
    token arrays + lengths out (decode to text happens host-side).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        max_prompt_tokens: int,
        max_new_tokens: int,
        eos_token_ids: Sequence[int],
        pad_token_id: int,
        lora_scale: float = 1.0,
        cache_dtype=jnp.bfloat16,
        # "int8": fused-dequant cache (paged parity). None = consult the
        # autotune plan DB (ExecutionPlan.kv_format; empty DB = "none",
        # byte-identical to the historical default); an explicit
        # "none"/"int8" always wins (the decode_scan_chunk convention)
        kv_quant: str | None = None,
        attn_impl: str = "reference",
        decode_chunk: int = 128,
        # None = consult the autotune plan DB (falls back to 0, the
        # historical default); an explicit int — including 0 — always wins
        scan_chunk: int | None = None,
        prompt_buckets: Sequence[int] | None = None,
        max_concurrent_rows: int = 0,  # 0 = unlimited (vLLM max_num_seqs)
        capture_logprobs: bool = False,  # record behavior logprobs (clip_ratio)
        cache_read_formulation: str | None = None,  # None = auto by scan_chunk
        autotune: bool = True,  # False pins the static defaults (no DB read)
        plan_db: str | None = None,  # plan-DB path; None = env/default path
        # expected concurrent candidate rows, for plan-key selection ONLY
        # (batch size arrives at generate()): callers that know the round
        # volume pass it so their own resolve and the engine's hit
        # the SAME DB entry; 0 = the any-rows entry
        plan_rows: int = 0,
    ):
        self.max_concurrent_rows = max_concurrent_rows
        self.capture_logprobs = capture_logprobs
        if scan_chunk is not None and scan_chunk < 0:
            raise ValueError(f"scan_chunk must be >= 0, got {scan_chunk}")
        if kv_quant not in (None, "none", "int8"):
            # validated BEFORE plan resolution so a typo'd kwarg fails with
            # the engine's own contract, not a plan-field error
            raise ValueError(f"kv_quant must be none/int8, got {kv_quant!r}")
        if cache_read_formulation not in (None, "dot", "mulred"):
            raise ValueError(
                "cache_read_formulation must be None/'dot'/'mulred', got "
                f"{cache_read_formulation!r}")
        # Execution-plan resolution (distrl_llm_tpu/autotune): explicit
        # kwargs always win; a stored measured plan fills the rest; with no
        # DB entry the static defaults apply byte-identically. decode_path
        # is pinned to this class so trace records stay honest.
        from distrl_llm_tpu.autotune import resolve_plan

        requested: dict[str, Any] = {"decode_path": "dense"}
        if scan_chunk is not None:
            requested["scan_chunk"] = scan_chunk
        if cache_read_formulation is not None:
            requested["cache_read_formulation"] = cache_read_formulation
        if prompt_buckets is not None:
            requested["prompt_buckets"] = tuple(prompt_buckets)
        if kv_quant is not None:
            # explicit "none" is a real pin (the int8-default A/B control),
            # not "unset" — the decode_scan_chunk convention
            requested["kv_format"] = kv_quant
        self.resolved_plan = resolve_plan(
            model_cfg=cfg, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens, rows=plan_rows,
            requested=requested, db_path=plan_db, enabled=autotune,
        )
        plan = self.resolved_plan.plan
        scan_chunk = plan.scan_chunk
        if prompt_buckets is None and plan.prompt_buckets:
            # a DB plan must never crash a run (store.py's contract): a
            # stored bucket that doesn't fit THIS engine's geometry (e.g. a
            # cross-geometry hand-copied entry) is dropped with a warning,
            # where the same bucket passed explicitly would raise below
            fitting = tuple(
                b for b in plan.prompt_buckets if 0 < b <= max_prompt_tokens
            )
            if fitting != plan.prompt_buckets:
                _logger.warning(
                    "autotune plan buckets %s exceed max_prompt_tokens=%d — "
                    "keeping only %s (re-run tools/autotune.py for this "
                    "geometry)",
                    list(plan.prompt_buckets), max_prompt_tokens,
                    list(fitting),
                )
            prompt_buckets = fitting or None
        # plan-suggested top-p implementation; an explicit SamplingConfig
        # pin (top_p_impl / top_p_exact) still wins at generate() —
        # SamplingConfig.resolved_top_p_impl(plan_default)
        self.plan_top_p_impl = plan.top_p_impl
        self.scan_chunk = scan_chunk
        # Chunk-configured engines read the cache via multiply+reduce in BOTH
        # the chunk program and the host-dispatched steps (tail / guard
        # fallback): a dot_general over the scanned carry makes TPU layout
        # assignment insert per-leaf relayout copies that OOM the program
        # (see ops.attention.attention_cached), and using one formulation
        # everywhere keeps chunk-vs-host greedy decode bit-identical. The
        # explicit kwarg exists for parity tests and on-chip formulation
        # A/Bs; None picks the right one for the dispatch mode.
        self.cache_read_formulation = (
            plan.cache_read_formulation
            or ("mulred" if scan_chunk else "dot"))
        # buckets where the chunked program compiled WITHOUT double-buffering
        # the KV cache (memory_analysis guard) hold their compiled fn here;
        # buckets where it did are marked None and use the host loop
        self._chunk_compiled: dict[int, Any] = {}
        cfg.refuse_hybrid("the dense engine (engine_impl='dense')")
        self.cfg = cfg
        self.max_prompt_tokens = max_prompt_tokens
        self.max_new_tokens = max_new_tokens
        self.max_total = max_prompt_tokens + max_new_tokens
        cfg.check_within_window(self.max_total)
        self.eos_ids = jnp.asarray(list(eos_token_ids), jnp.int32)
        self.pad_id = int(pad_token_id)
        self.lora_scale = lora_scale
        # post-resolution KV format: an explicit kwarg already rode the
        # requested dict (wins per-field); unset adopts the stored plan's
        # kv_format, defaulting to the historical "none"
        kv_quant = kv_quant if kv_quant is not None else (
            plan.kv_format or "none"
        )
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be none/int8, got {kv_quant!r}")
        if kv_quant == "int8":
            cfg.refuse_looped("kv_quant='int8' (an int8 dense cache)")
        # "int8" rides the cache_dtype static arg as a sentinel: _prefill
        # builds the scale-carrying cache and the forward's dense-cache
        # branch switches to attention_cached_quant
        self.cache_dtype = "int8" if kv_quant == "int8" else cache_dtype
        self.kv_quant = kv_quant
        self.attn_impl = attn_impl
        self.decode_chunk = decode_chunk
        # Length bucketing (SURVEY §2b N1 "static batch + length bucketing
        # first"): each generation round runs at the smallest bucket holding
        # its longest real prompt, cutting prefill FLOPs and every decode
        # step's KV length for short batches. One compile per bucket used.
        buckets = sorted(set(prompt_buckets or [])) or [max_prompt_tokens]
        if any(b <= 0 or b > max_prompt_tokens for b in buckets):
            raise ValueError(f"buckets must be in (0, {max_prompt_tokens}]: {buckets}")
        if buckets[-1] != max_prompt_tokens:
            buckets.append(max_prompt_tokens)
        self.prompt_buckets = buckets
        self._compiled: dict[int, tuple] = {}
        # concurrent generate() calls (hybrid rollout: actor + learner
        # submeshes decode in parallel threads) share the compiled-fn cache
        self._compile_mu = threading.Lock()
        # in-flight weight-update mailbox (LoraMailbox base): consumed-swap
        # steps and the learner weight_version pushed with each adapter
        self.last_swap_steps: list[int] = []
        self.last_swap_versions: list[int | None] = []
        # per-round prefill/decode timing + token counts (telemetry:
        # accumulate_round_stats); snapshotted by the trainer per round
        self.last_round_stats: dict | None = None

        # n and max_steps are static (shape-determining)
        self._decode_init = jax.jit(
            partial(_decode_init, pad_id=self.pad_id),
            static_argnames=("n", "max_steps"),
            # no cache donation: the candidate fan-out (jnp.repeat to B·n
            # rows) allocates fresh buffers the prefill cache can't alias
        )

    @property
    def scan_chunk_active(self) -> bool | None:
        """Whether chunked decode actually ran: True once a chunked program
        compiled AND passed the memory guard, False if every attempt fell
        back to the host loop, None before the first decode (or scan_chunk=0).
        A measurement reports this so a fallback can't masquerade as a
        chunked run."""
        if not self.scan_chunk or not self._chunk_compiled:
            return None
        return any(v is not None for v in self._chunk_compiled.values())

    def bucket_for(self, prompt_mask) -> int:
        """The bucket a batch with this mask will run at: the smallest bucket
        holding the longest real prompt."""
        if len(self.prompt_buckets) == 1:
            return self.prompt_buckets[0]
        longest = int(np.asarray(prompt_mask).sum(axis=-1).max())
        return next(bb for bb in self.prompt_buckets if bb >= max(longest, 1))

    def is_warm(self, bucket: int) -> bool:
        """Whether this bucket's programs have been built (first use of a
        bucket pays XLA compilation — callers with hang detectors exempt cold
        buckets, trainer._call_engine)."""
        return bucket in self._compiled

    def _fns_for_bucket(self, bucket: int) -> tuple:
        """(prefill, decode_step) jits for one prompt bucket — the step is
        donated so the cache updates in place (verified zero HBM temp bytes
        via compile memory_analysis)."""
        with self._compile_mu:
            if bucket not in self._compiled:
                obs.note_compile("dense/bucket_fns", (bucket,))
                prefill = jax.jit(
                    partial(
                        _prefill, cfg=self.cfg, max_total=bucket + self.max_new_tokens,
                        lora_scale=self.lora_scale, cache_dtype=self.cache_dtype,
                        attn_impl=self.attn_impl,
                    )
                )
                step = jax.jit(
                    partial(
                        _decode_step, cfg=self.cfg, prompt_len=bucket,
                        pad_id=self.pad_id, lora_scale=self.lora_scale,
                        attn_impl=self.attn_impl,
                        capture_logprobs=self.capture_logprobs,
                        cache_read_formulation=self.cache_read_formulation,
                    ),
                    donate_argnames=("state",),
                    static_argnames=("top_p_impl",),
                )
                self._compiled[bucket] = (prefill, step)
            return self._compiled[bucket]

    def _chunk_fn_for_bucket(
        self, bucket: int, max_steps: int, params, lora, state, rng,
        temperature, top_p, top_p_impl: str,
    ):
        """Compiled K-steps-per-dispatch program for this (bucket, shapes)
        combination, or None where the host loop should be used instead.

        The program is explicitly lowered + compiled so its
        ``memory_analysis`` can be inspected BEFORE it ever runs: if the TPU
        compiler double-buffered the scan carry (temp bytes on the order of
        the KV cache — the failure mode that made the host-dispatched loop
        the default, see module docstring) the chunked program would OOM the
        very configs it is meant to speed up, so it is rejected and the wave
        falls back to one dispatch per step. Compile failures (e.g. a Mosaic
        lowering surprise on a new config) also fall back rather than kill
        the round."""
        bn = state.out.shape[0]
        # lora=None rounds and adapter rounds need separate cache entries
        # (Compiled executables raise on structure changes, see
        # lora_signature)
        key = (bucket, max_steps, top_p_impl, bn, lora_signature(lora))
        with self._compile_mu:
            if key in self._chunk_compiled:
                return self._chunk_compiled[key]
            fn = jax.jit(
                partial(
                    _decode_chunk, chunk=pick_chunk(self.scan_chunk, max_steps),
                    cfg=self.cfg, prompt_len=bucket,
                    pad_id=self.pad_id, lora_scale=self.lora_scale,
                    attn_impl=self.attn_impl, top_p_impl=top_p_impl,
                    capture_logprobs=self.capture_logprobs,
                    cache_read_formulation=self.cache_read_formulation,
                ),
                donate_argnames=("state",),
            )
            cache_bytes = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(state.cache)
            )
            fusion_bytes = 0
            if self.cache_read_formulation == "mulred":
                # per-layer _gqa_mulred broadcast product at this bucket's
                # full window — the unfused-temp envelope
                from distrl_llm_tpu.ops.attention import mulred_broadcast_bytes

                fusion_bytes = mulred_broadcast_bytes(
                    bn, self.cfg.num_kv_heads,
                    self.cfg.num_heads // self.cfg.num_kv_heads,
                    self.cfg.head_dim, bucket + self.max_new_tokens,
                )
            compiled = compile_chunk_guarded(
                fn, cache_bytes, f"scan_chunk={self.scan_chunk} bucket={bucket}",
                params, lora, state, rng, fusion_bytes=fusion_bytes,
                eos_ids=self.eos_ids,
                temperature=temperature, top_p=top_p,
            )
            self._chunk_compiled[key] = compiled
            return compiled

    def generate(
        self,
        params: Params,
        lora: Params | None,
        prompt_ids: np.ndarray,  # [B, P] left-padded to max_prompt_tokens
        prompt_mask: np.ndarray,
        sampling: SamplingConfig,
        rng: jax.Array,
    ) -> GenerationResult:
        # a new round supersedes any swap consumed during the previous one
        # (the trainer hands the freshest adapter at round entry)
        marks = RoundMarks()
        self._reset_lora_mailbox_round()
        self.last_round_stats = None  # waves of THIS round accumulate below
        params = self._decode_params(params)
        # on a role submesh of several chips the round's programs span them:
        # their Pallas kernels need the mesh in context (ops/per_device.py)
        with params_mesh(params):
            result = generate_in_waves(
                self._generate_wave, self.max_concurrent_rows, params, lora,
                prompt_ids, prompt_mask, sampling, rng, self.pad_id,
            )
        file_round(marks, self.last_round_stats)
        return result

    def _generate_wave(
        self, params, lora, prompt_ids, prompt_mask,
        sampling: SamplingConfig, rng: jax.Array,
    ) -> GenerationResult:
        b, p = prompt_ids.shape
        if p != self.max_prompt_tokens:
            raise ValueError(f"prompts must be padded to {self.max_prompt_tokens}, got {p}")
        max_steps = min(sampling.max_tokens, self.max_new_tokens)
        with telemetry.span(telemetry.ENGINE_SETUP):
            # an in-flight swap from an earlier wave of THIS round also covers
            # this wave's prefill (its rows haven't sampled yet)
            lora = self._round_entry_lora(lora)

            # bucket selection: smallest bucket holding the longest real prompt;
            # prompts are left-padded, so the bucket keeps the trailing columns
            bucket = self.bucket_for(prompt_mask)
            if bucket < p:
                prompt_ids = prompt_ids[:, p - bucket:]
                prompt_mask = prompt_mask[:, p - bucket:]
            prefill_fn, decode_step_fn = self._fns_for_bucket(bucket)

            prefill_tokens = int(np.asarray(prompt_mask).sum())
        t0 = time.perf_counter()
        with telemetry.span(telemetry.ENGINE_PREFILL, rows=b, bucket=bucket,
                            tokens=prefill_tokens):
            cache, key_mask, last_logits = prefill_fn(
                params, lora, jnp.asarray(prompt_ids), jnp.asarray(prompt_mask)
            )
            # the block makes the prefill/decode timing split honest (the
            # decode loop's final readback syncs its side); it only forgoes
            # overlapping prefill device time with sub-ms host-side setup
            jax.block_until_ready(last_logits)
        t_prefill = time.perf_counter() - t0
        row_alive = jnp.asarray(prompt_mask).sum(axis=-1) > 0
        state = self._decode_init(
            cache, key_mask, last_logits, row_alive,
            n=sampling.n, max_steps=max_steps,
        )
        temperature = jnp.asarray(sampling.temperature, jnp.float32)
        top_p = jnp.asarray(sampling.top_p, jnp.float32)
        top_p_impl = sampling.resolved_top_p_impl(self.plan_top_p_impl)
        lora_cell = [lora]
        steps_seen = [0]
        # explicit enter/exit: the span must cover BOTH dispatch branches
        # and the final device→host readback that syncs the decode
        host = RoundHostAccount()
        dec_span = telemetry.span(telemetry.ENGINE_DECODE, rows=b * sampling.n,
                                  bucket=bucket)
        dec_span.__enter__()

        chunk_fn = (
            self._chunk_fn_for_bucket(
                bucket, max_steps, params, lora, state, rng,
                temperature, top_p, top_p_impl,
            )
            # > 1, matching the paged engines: a scan-of-one program has no
            # fusion benefit but would still report scan_chunk_active=True
            if self.scan_chunk > 1 and max_steps > 1
            else None
        )
        if chunk_fn is not None:
            k = pick_chunk(self.scan_chunk, max_steps)

            def run_step(l, s):
                return decode_step_fn(
                    params, l, s, rng, eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p,
                    top_p_impl=top_p_impl,
                )

            step = make_swap_aware_chunk_step(
                self, lora_cell, steps_seen, k, max_steps, chunk_fn, lora,
                rebuild=lambda l, s: self._chunk_fn_for_bucket(
                    bucket, max_steps, params, l, s, rng,
                    temperature, top_p, top_p_impl,
                ),
                run_chunk=lambda fn, l, s: fn(
                    params, l, s, rng, eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p,
                ),
                run_step=run_step,
            )
            # one "step" per chunk; snapshot done flags every chunk
            # (check=1), then the shared non-divisor tail
            full, rem = divmod(max_steps, k)
            state = run_decode_loop(step, state, full, 1,
                                    steps_per_call=k, host=host)
            state = run_nondivisor_tail(
                self, lora_cell, steps_seen, rem, state, run_step)
        else:

            def step(s):
                # in-flight weight-update mailbox: swap BEFORE sampling, so
                # the recorded swap step is the first position decoded under
                # the new adapter (dense decode: step index == position)
                self._take_pending_lora(lora_cell, steps_seen[0])
                steps_seen[0] += 1
                return decode_step_fn(
                    params, lora_cell[0], s, rng,
                    eos_ids=self.eos_ids, temperature=temperature, top_p=top_p,
                    top_p_impl=top_p_impl,
                )

            state = run_decode_loop(step, state, max_steps, self.decode_chunk,
                                    host=host)
        t_read = time.perf_counter()
        with telemetry.span(telemetry.ENGINE_READBACK):
            out = np.asarray(state.out).reshape(b, sampling.n, max_steps)
            lengths = np.asarray(state.lengths).reshape(b, sampling.n)
            logps = (
                np.asarray(state.logps).reshape(b, sampling.n, max_steps)
                if self.capture_logprobs else None
            )
            gen_tokens = int(lengths.sum())
            exit_said = file_exit_stats(self.cfg, state.cache.get("exit_stats"))
        host.blocked(t_read)
        dec_span.set(tokens=gen_tokens, steps=steps_seen[0], **exit_said)
        dec_span.__exit__(None, None, None)
        file_loop_layer_steps(self.cfg, steps_seen[0])
        self.last_round_stats = accumulate_round_stats(
            self.last_round_stats, prefill_s=t_prefill,
            prefill_tokens=prefill_tokens, prompt_rows=b,
            decode_s=host.stop(), gen_tokens=gen_tokens,
            gen_rows=b * sampling.n, host=host,
        )
        return GenerationResult(tokens=out, lengths=lengths, logprobs=logps)
