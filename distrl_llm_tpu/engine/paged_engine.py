"""Paged-KV generation engine: packed ragged decode (the full N1 core).

Where ``engine.GenerationEngine`` keeps a dense [B, K, hd, Smax] cache that
every decode step reads in full, this engine stores KV in PAGES and reads
each row's true [0, length) prefix only — vLLM's PagedAttention bandwidth
model (reference: requirements.txt:6, entered via ``policy.fast_generate``,
distributed_actor.py:148–150), built TPU-native:

* prompts are packed (left padding removed) during a jitted prefill, so a
  short prompt costs its own length, not ``max_prompt_tokens``;
* decode attention is our Pallas kernel on a TPU (ops/paged_native.py; the
  jnp reference elsewhere — ops/paged.py);
* candidates SHARE their prompt's full prompt pages (vLLM prefix sharing):
  the page table points each candidate's leading columns at a shared pool
  written once by prefill; only the partial last prompt page — extended in
  place by decode — is private per candidate. Prompt KV memory is ~B copies
  instead of B·n. The table is data-dependent but shape-static, so it rides
  as a traced array (an RL rollout round is a fixed batch, so vLLM's dynamic
  C++ block allocator reduces to this host-computed table);
* the host-dispatched donated decode-step loop, candidate fan-out after a
  shared prefill, and async early-exit snapshots all match the dense engine.

Parallelism note: this engine targets one rollout replica — a single chip or
a TP group (KV heads shard over "tp"). Data-parallel scale-out has two
paths: one engine per replica (the remote-worker fan-out,
distributed/remote_engine.py — vLLM's one-engine-per-GPU model), or ONE
wave-mode engine whose page pool is partitioned over the dp axis via
shard_map (engine/sharded_paged.py, reusing this module's jitted pieces as
the shard-local program).
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.control.governor import CONTROL_SHED_GROUPS
from distrl_llm_tpu.engine.engine import (
    GenerationResult,
    LoraMailbox,
    RoundHostAccount,
    RoundMarks,
    accumulate_round_stats,
    file_exit_stats,
    file_loop_layer_steps,
    looped_account,
    file_round,
    cached_chunk_program,
    generate_in_waves,
    scan_steps_guarded,
    lora_signature,
    make_swap_aware_chunk_step,
    pool_nbytes,
    pick_chunk,
    run_decode_loop,
    run_nondivisor_tail,
)
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.models.transformer import forward
from distrl_llm_tpu.ops.paged import (
    DEFAULT_PAGE_SIZE,
    make_page_table,
    pages_per_seq,
)
from distrl_llm_tpu.ops.per_device import params_mesh
from distrl_llm_tpu.ops.sampling import sample_with_logprob, token_logprob

# telemetry series owned by the paged engine (one owner per name —
# graftcheck GC2xx). ops/* attribute the Pallas grid-launch budget;
# engine/spec_* are the speculative-decoding accounting trace_report and
# the spec smoke read.
OPS_PAGED_GRID_STEPS = "ops/paged_grid_steps"              # counter
ENGINE_SPEC_DRAFT_RESIZES = "engine/spec_draft_resizes"    # counter
ENGINE_SPEC_ACCEPT_RATE = "engine/spec_accept_rate"        # gauge
ENGINE_SPEC_EMIT_TOKENS = "engine/spec_emit_tokens"        # hist (binned)
ENGINE_SPEC_VERIFY_GRID_STEPS = "engine/spec_verify_grid_steps"  # counter
# continuous-batching admission accounting (ISSUE 12): candidates admitted
# into freed slots AFTER the round's first dispatch (the backfill the fixed
# episode batch never gets), and lazy per-group prompt prefills run by the
# continuous-admission scheduler
ENGINE_BACKFILL_ADMITS = "engine/backfill_admits"          # counter
ENGINE_CONT_PREFILLS = "engine/cont_prefills"              # counter
# multi-turn episode continuation (ISSUE 17): slots resumed in place after
# the turn hook injected an observation, and the conversation-prefix tokens
# whose re-prefill that in-place resume avoided (KV stayed resident)
ENGINE_TURN_RESUMES = "engine/turn_resumes"                # counter
ENGINE_TURN_PREFILL_SAVED = "engine/turn_prefill_saved_tokens"  # counter
# block-sparse layers (MiniCPM-SALA): blocks a round's decode steps attended
# and blocks they could see, summed over sparse layers, KV heads, live slots
# and steps. Carried in the decode state, fetched with the round's result.
ENGINE_SPARSE_BLOCKS_ATTENDED = "engine/sparse_blocks_attended"  # counter
ENGINE_SPARSE_BLOCKS_VISIBLE = "engine/sparse_blocks_visible"    # counter

# routed experts (models/moe.py): token-expert pairs a round's decode steps
# computed, and the fullest expert's pairs, summed over expert layers and
# steps (fullest over mean = max_load * experts / assignments). Carried in the
# decode state like the block counters above.
ENGINE_MOE_ASSIGNMENTS = "engine/moe_assignments"          # counter
ENGINE_MOE_MAX_EXPERT_LOAD = "engine/moe_max_expert_load"  # counter
# token-expert pairs the router chose over ALL the experts it scores (live
# rows x experts_per_token), where a program holds one chip's share of them:
# moe_assignments (pairs of experts HELD) over this is the share that landed here
ENGINE_MOE_PAIRS_ROUTED = "engine/moe_pairs_routed"        # counter
# and those of them that chose an expert that computes nothing (a router with
# zero-compute outputs, models/moe.py::zero_part): over moe_pairs_routed, the
# share of a token's choices that cost no product
ENGINE_MOE_PAIRS_ZERO = "engine/moe_pairs_zero"            # counter
# power retention (ops/power_retention.py): bytes of S and z that live rows'
# decode steps read and wrote, summed over layers and steps (carried as a count
# of (live row, layer) states, ``mixer["power_stats"]``, and fetched with the
# round's result: a round's bytes pass what an int32 holds)
ENGINE_POWER_STATE_BYTES = "engine/power_state_bytes"      # counter
# state-space layers (ops/selective_scan.py): (live row, layer) states the
# decode steps read and wrote, summed over Mamba layers and steps (carried in
# ``mixer["ssm_stats"]`` as a count, not bytes, and fetched with the round's
# result); x a state's bytes, twice, is what the steps had to move
ENGINE_SSM_STATES_STEPPED = "engine/ssm_states_stepped"    # counter
# bytes the decode state's row states hold (models/hybrid.py::ROW_STATES, every
# kind: recurrent states, normalisers, convolution tails, pooled keys), filed
# when a round's decode state is built, with tracing on or off
ENGINE_SLOT_STATE_BYTES = "engine/slot_state_bytes"        # gauge
# a hybrid model's staged prefill (``_paged_prefill_hybrid``): the round's real
# prompt tokens over the tokens its stages ran, x 100, from host arithmetic
# over the prompts' lengths and the ladder; filed a round, tracing on or off
ENGINE_PREFILL_REAL_SHARE = "engine/prefill_real_share"    # gauge
# absorbed latent attention (ops/latent_attention.py): live (row, page) pairs
# a round's decode steps attended over, and live pages they fetched from the
# pool (a page that a group's rows share is fetched once a group), summed over
# layers and steps: attended / read is how often the shared walk engages
# (1.0 where nothing is shared). Pages: rows would overflow an int32 a round.
ENGINE_LATENT_PAGES_ATTENDED = "engine/latent_pages_attended"  # counter
ENGINE_LATENT_PAGES_READ = "engine/latent_pages_read"          # counter

Params = dict[str, Any]


class _PagedDecodeState(NamedTuple):
    step: jax.Array  # []
    out: jax.Array  # [Bn, T]
    logps: jax.Array  # [Bn, T] raw-model logprob of each sampled token
    gen_lengths: jax.Array  # [Bn] generated token counts (incl. EOS)
    done: jax.Array  # [Bn] bool
    logits: jax.Array  # [Bn, V]
    seq_lengths: jax.Array  # [Bn] tokens resident in the cache per row
    k_pages: tuple  # L × [K, total_pages, ps, hd]
    v_pages: tuple
    mixer: Any = None  # see _RefillState.mixer


class _RefillState(NamedTuple):
    """Decode state for the SLOT-REFILL scheduler (continuous batching).

    R decode slots run concurrently; each slot holds one candidate (or the
    dead sentinel ``total``). Completed candidates' tokens live in the
    [total, T] ``out`` buffer indexed by candidate id, so a slot can be
    re-assigned mid-decode without disturbing finished output."""

    step: jax.Array  # []
    alive_steps: jax.Array  # [] sum over steps of alive-slot count
    out: jax.Array  # [total, T] pad-filled; scatter-written by candidate id
    logps_buf: jax.Array  # [total, T] behavior logprobs, scatter-written
    lengths_buf: jax.Array  # [total] per-candidate generated counts
    cand: jax.Array  # [R] candidate id per slot (== total → dead slot)
    done: jax.Array  # [R]
    logits: jax.Array  # [R, V]
    seq_lengths: jax.Array  # [R] tokens resident per slot
    gen_lengths: jax.Array  # [R] tokens generated by the current occupant
    page_indices: jax.Array  # [R, width] — rewritten per admit
    k_pages: tuple
    v_pages: tuple
    # what a slot holds beside K/V pages when the model's layers differ in
    # kind (models/hybrid.py::init_mixer_state: a float32 state per lightning
    # layer, the selector's pooled keys per sparse layer, the round's
    # block counter). None for a dense GQA model: no leaf, the same programs.
    mixer: Any = None


def _pack_rows(ids: jax.Array, mask: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Left-padded [B, P] → packed [B, P] (first real token at column 0)."""
    b, p = ids.shape
    real_len = mask.sum(axis=-1).astype(jnp.int32)  # [B]
    shift = p - real_len  # left-pad amount per row
    cols = (jnp.arange(p)[None, :] + shift[:, None]) % p
    packed = jnp.take_along_axis(ids, cols, axis=1)
    packed_mask = (jnp.arange(p)[None, :] < real_len[:, None]).astype(mask.dtype)
    return packed * packed_mask, packed_mask, real_len



@contextlib.contextmanager
def _no_full_collection():
    """A round without the cyclic collector. The decode loop runs at most a
    second of queued steps ahead of the chip, and a full collection of a
    process that has traced fifty programs (and, in the benchmark, a float32
    reference) takes longer: it idled the chip for 0.6-2 s in three of 18
    rounds of a 35 s cell (PERF.md section 6, PR 54). A round makes few
    cycles; they wait for its end. Left as it was where a caller had the
    collector off already."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _count_mixer_stats(mixer) -> dict:
    """File a round's counters (``mixer["sel_stats"]``: the block-sparse
    layers' blocks; ``mixer["moe_stats"]`` / ``["moe_routed"]``: the expert
    layers' pairs, held here / chosen over all experts; ``mixer["moe_zero"]``: those
    of them that chose an expert that computes nothing; ``mixer["moe_blocks"]``:
    the blocks their grouped form ran and laid, the prefill's too;
    ``mixer["latent_stats"]``: absorbed attention's pages; ``mixer["power_stats"]``: the (live row, layer)
    power-retention states the decode steps read and wrote, filed as the bytes
    of S and z that is, a read and a write each; ``mixer["ssm_stats"]``: the
    (live row, layer) state-space states they read and wrote, filed as that
    count; ``mixer["window_stats"]``: the window layers' keys attended and the
    keys a full layer would attend, in units of 128; ``mixer["index_stats"]``:
    the same two of a model with a learned index over tokens, every layer's)
    with telemetry. Returns what the round's span says of them:
    ``power_state_bytes`` / ``ssm_states_stepped`` / ``window_pages_attended``
    and ``_visible`` / ``index_tokens_attended`` and ``_visible``, where there
    are any."""
    said = {}
    if mixer is not None and "power_stats" in mixer:
        a_state = sum(x.nbytes // x.shape[0]
                      for x in (mixer["power"][0], mixer["power_z"][0]))
        moved = 2 * a_state * int(np.asarray(mixer["power_stats"])[0])
        telemetry.counter_add(ENGINE_POWER_STATE_BYTES, moved)
        said["power_state_bytes"] = moved
    if mixer is not None and "ssm_stats" in mixer:
        stepped = int(np.asarray(mixer["ssm_stats"])[0])
        telemetry.counter_add(ENGINE_SSM_STATES_STEPPED, stepped)
        said["ssm_states_stepped"] = stepped
    for key, (said_as, names) in (
        ("window_stats", ("window_pages", (telemetry.ENGINE_WINDOW_PAGES_ATTENDED,
                                           telemetry.ENGINE_WINDOW_PAGES_VISIBLE))),
        ("index_stats", ("index_tokens", (telemetry.ENGINE_INDEX_TOKENS_ATTENDED,
                                          telemetry.ENGINE_INDEX_TOKENS_VISIBLE))),
    ):
        if mixer is not None and key in mixer:
            for which, name, value in zip(
                    ("attended", "visible"), names, np.asarray(mixer[key])):
                telemetry.counter_add(name, int(value))
                said[f"{said_as}_{which}"] = int(value)
    for key, names in (
        ("sel_stats", (ENGINE_SPARSE_BLOCKS_ATTENDED, ENGINE_SPARSE_BLOCKS_VISIBLE)),
        ("moe_stats", (ENGINE_MOE_ASSIGNMENTS, ENGINE_MOE_MAX_EXPERT_LOAD)),
        ("moe_routed", (ENGINE_MOE_PAIRS_ROUTED,)),
        ("moe_zero", (ENGINE_MOE_PAIRS_ZERO,)),
        ("moe_blocks", (telemetry.ENGINE_MOE_BLOCKS_RUN, telemetry.ENGINE_MOE_BLOCKS_LAID)),
        ("latent_stats", (ENGINE_LATENT_PAGES_ATTENDED, ENGINE_LATENT_PAGES_READ)),
    ):
        if mixer is not None and key in mixer:
            for name, value in zip(names, np.asarray(mixer[key])):
                telemetry.counter_add(name, int(value))
    return said


def _record_grid_telemetry(num_layers: int, steps: int,
                           *, per_call: int, calls_per_step: int = 1):
    """Paged grid telemetry: which launch geometry ran (a grid step costs
    0.37-1.5 µs on a v5e, by what the step moves:
    ``ops.paged.paged_grid_steps``). ``per_call`` is the
    dispatch chain's trace-time analytic count for the CALLER's own
    geometry and LIVE row count (engines derive it from their exact
    dispatch-choice record — see ``_grid_steps_per_call`` — never from a
    process-global cache, which could hold another engine's geometry or a
    stale batch);
    ``calls_per_step`` is the op calls per layer per dispatched step (1
    for plain decode, draft_len+1 for the speculative verify fan-out).
    Total grid steps this round = per-call × calls/step × layers × steps, where
    ``num_layers`` counts CACHE layers (``cfg.paged_layers``: a looped model
    launches once a (pass, layer))."""
    if per_call and steps:
        telemetry.counter_add(
            OPS_PAGED_GRID_STEPS, per_call * calls_per_step * num_layers * steps)


def _record_delta_telemetry(cfg: ModelConfig, steps: int) -> None:
    """``ops/delta_kernel_steps``: the round's delta-rule layer-steps that ran
    as the one-token Mosaic kernel, read from what ``delta_step`` recorded for
    this model's heads when the step was traced (0 where it took the plain
    form). A model without such layers files nothing."""
    layers = cfg.kind_count("delta")
    if not layers or not steps:
        return
    from distrl_llm_tpu.ops.delta_attention import dispatch_choices, dispatch_key

    head = cfg.delta_head_dim
    ran = dispatch_choices.get(dispatch_key(cfg.delta_heads, head, head))
    telemetry.counter_add(
        telemetry.OPS_DELTA_KERNEL_STEPS, layers * steps * (ran == "kernel"))


def _record_sparse_telemetry(cfg: ModelConfig, steps: int, cache_dtype) -> None:
    """``ops/sparse_kernel_steps``: the round's sparse layer-steps whose
    attention ran as the Mosaic launch over the chosen pages, read from what
    ``sparse_decode`` recorded for this model's heads and pages when the step
    was traced (0 where it took the plain form). A model without such layers
    files nothing."""
    layers = cfg.kind_count("sparse")
    if not layers or not steps:
        return
    from distrl_llm_tpu.ops.sparse_attention import dispatch_choices, dispatch_key

    ran = dispatch_choices.get(dispatch_key(
        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.sparse_block_size, cache_dtype))
    telemetry.counter_add(
        telemetry.OPS_SPARSE_KERNEL_STEPS, layers * steps * (ran == "kernel"))


def _record_power_telemetry(cfg: ModelConfig, steps: int) -> None:
    """``ops/power_kernel_steps``: the round's power-retention layer-steps that
    ran as the one-token Mosaic kernel, read from what ``power_step`` recorded
    for this model's heads when the step was traced (0 where it took the plain
    form). A model without such layers files nothing."""
    layers = cfg.kind_count("power")
    if not layers or not steps:
        return
    from distrl_llm_tpu.ops.power_retention import dispatch_choices, dispatch_key

    ran = dispatch_choices.get(dispatch_key(
        cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim, cfg.head_dim))
    telemetry.counter_add(
        telemetry.OPS_POWER_KERNEL_STEPS, layers * steps * (ran == "kernel"))


def _record_fold_telemetry(cfg: ModelConfig, prompt_pages: int, page_size: int,
                           dtype, segments: int | None = None) -> None:
    """``ops/latent_kernel_folds`` / ``ops/softmax_kernel_folds``: the folds of
    the round's prefill (one call; segment ``j`` folds ``j + 1`` blocks of keys
    in every layer that folds, whatever stage runs it and for as many rows as
    the stage holds) that ran as the Mosaic kernel, read from what
    ``expanded_segment`` recorded for the layers' head layout and segment when
    the prefill was traced (0 where it took the XLA form). The first counter
    is a latent model's (every layer; K ``nope + rope`` wide), the second that
    of a model whose "softmax" and "cca" layers fold the rows' K/V pages
    (``hybrid._segment_softmax``: a key ``key_row`` lanes wide, no rope part)
    or whose "sparse" layers do, under their choice (the same path, the same
    record); a model with none of them files nothing. ``dtype`` is the
    activations', the embedding's; ``segments`` the longest row's, where the
    stages end (every segment of the prompt's width where it is not given)."""
    if cfg.latent:
        name, layers = telemetry.OPS_LATENT_KERNEL_FOLDS, cfg.paged_layers
        layout = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim)
    else:
        name = telemetry.OPS_SOFTMAX_KERNEL_FOLDS
        layers = sum(cfg.mixer_count(m) for m in ("softmax", "cca", "sparse"))
        layout = (cfg.key_row, 0, cfg.value_head_dim)
    if not layers:
        return
    from distrl_llm_tpu.ops.latent_attention import dispatch_choices, dispatch_key

    seg, n_seg = _hybrid_segments(prompt_pages, page_size)
    n_seg = n_seg if segments is None else segments
    ran = dispatch_choices.get(dispatch_key(cfg.num_heads, *layout, seg, dtype))
    telemetry.counter_add(name, layers * (n_seg * (n_seg + 1) // 2) * (ran == "kernel"))


def _record_latent_decode_telemetry(cfg: ModelConfig, steps: int, page_size: int,
                                    cache_dtype) -> None:
    """``ops/latent_decode_launches``: the round's latent layer-steps whose
    decode attention ran as the one Mosaic launch over the pool, read from
    what ``absorbed_decode`` recorded for this model's heads and pages when
    the step was traced (0 where it took the XLA walk, and where a model with
    an index gathered its chosen rows: nothing is recorded there). A model
    without latent layers files nothing."""
    if not cfg.latent or not steps:
        return
    from distrl_llm_tpu.ops.latent_attention import decode_dispatch_key, dispatch_choices

    ran = dispatch_choices.get(decode_dispatch_key(
        cfg.num_heads, cfg.latent_row, page_size, cache_dtype))
    telemetry.counter_add(
        telemetry.OPS_LATENT_DECODE_LAUNCHES, cfg.paged_layers * steps * (ran == "kernel"))


def _record_index_telemetry(cfg: ModelConfig, steps: int, prompt_pages: int,
                            private_pages: int, page_size: int,
                            segments: int | None = None) -> None:
    """``ops/index_counted_choices``: the round's choices of the learned index
    that were made by counting (``ops/token_index.py``), every layer's: a
    decode step's where a row's page table is wider than ``index_topk``, and
    those of the prefill's segments that end past ``index_topk`` (as far as
    the longest row's ``segments``; every segment of the prompt's width where
    it is not given). A model without an index files nothing."""
    if not cfg.index_topk:
        return
    seg, n_seg = _hybrid_segments(prompt_pages, page_size)
    n_seg = n_seg if segments is None else segments
    chose = n_seg - min(n_seg, cfg.index_topk // seg)
    if (prompt_pages + private_pages) * page_size > cfg.index_topk:
        chose += steps
    telemetry.counter_add(telemetry.OPS_INDEX_COUNTED_CHOICES, cfg.num_layers * chose)


def _paged_prefill(params, lora, prompt_ids, prompt_mask, *, cfg: ModelConfig,
                   prompt_pages: int, page_size: int, lora_scale: float,
                   cache_dtype, attn_impl: str, kv_quant: str = "none"):
    """Pack prompts, run one forward over B rows, return per-prompt page
    tiles [K, B, prompt_pages, ps, hd] per layer + sampling logits."""
    b, p = prompt_ids.shape
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        packed_ids, packed_mask, real_len = _pack_rows(prompt_ids, prompt_mask)
        pad_to = prompt_pages * page_size
        packed_ids = jnp.pad(packed_ids, ((0, 0), (0, pad_to - p)))
        packed_mask = jnp.pad(packed_mask, ((0, 0), (0, pad_to - p)))

    shape = (cfg.num_kv_heads, b * prompt_pages, page_size, cfg.head_dim)

    def make_pages():
        if kv_quant == "int8":
            # int8 KV: halves resident cache memory (see the bandwidth caveat
            # on ops/paged.py:quantize_pages)
            from distrl_llm_tpu.ops.paged import init_quantized_pages

            return init_quantized_pages(shape)
        return jnp.zeros(shape, cache_dtype)

    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        cache = {
            # one pool a CACHE layer: a looped model's (pass, layer)
            "k": tuple(make_pages() for _ in range(cfg.paged_layers)),
            "v": tuple(make_pages() for _ in range(cfg.paged_layers)),
            "lengths": real_len,
            "page_indices": jnp.asarray(
                make_page_table(b, pad_to, page_size)
            ),
        }
    positions = jnp.broadcast_to(
        jnp.arange(pad_to, dtype=jnp.int32)[None, :], (b, pad_to)
    )
    logits, cache = forward(
        params, cfg, packed_ids, attention_mask=packed_mask,
        positions=positions, lora=lora, lora_scale=lora_scale,
        kv_cache=cache, attn_impl=attn_impl, page_size=page_size,
        # each packed row's sampling logits sit at its LAST REAL position —
        # a per-row gather that also skips the [B, Ppad, V] lm_head
        logits_positions=jnp.maximum(real_len - 1, 0),
    )
    if cfg.looped:  # and the round's exit account, which the decode steps add to
        return cache["k"], cache["v"], logits[:, 0], real_len, looped_account()
    return cache["k"], cache["v"], logits[:, 0], real_len


#: tokens of one prefill segment of a model whose layers differ in kind. A
#: hybrid prefill runs its segments in STAGES: stage k runs the ``b_k`` longest
#: rows, one segment a trip, until the longest row it drops has ended
#: (``_paged_prefill_hybrid``); ``_stage_sizes`` is the rule that gives the
#: ``b_k`` of a batch
HYBRID_PREFILL_SEGMENT = 1024

#: segment bodies one prefill program may hold, whatever its batch: a body is
#: every layer unrolled once more, and each is compiled, kept in the persistent
#: cache and traced, lowered and loaded at every start (2.3-2.9 s of a warm
#: start and 20-50 s of a cold one in the long-context cells: PERF.md §6, PR 52)
HYBRID_PREFILL_STAGES = 2


#: the round's counters (``models/hybrid.py::init_mixer_state``) that a
#: prefill's segments add to and hand on to the decode state: the blocks of the
#: experts' grouped form, which a decode step lays none of where
#: ``moe.expert_form`` gives its rows the dense form (every cell's)
PREFILL_COUNTERS = ("moe_blocks",)


def _hybrid_segments(prompt_pages: int, page_size: int) -> tuple[int, int]:
    """(tokens of one segment, segments) of a hybrid model's prefill: the
    most whole pages that divide the prompt's and hold no more than
    HYBRID_PREFILL_SEGMENT tokens (one page where a page holds more)."""
    seg_pages = max(
        d for d in range(1, prompt_pages + 1)
        if prompt_pages % d == 0 and d * page_size <= max(
            HYBRID_PREFILL_SEGMENT, page_size)
    )
    return seg_pages * page_size, prompt_pages // seg_pages


def _stage_sizes(b: int, segments: int) -> tuple[int, ...]:
    """The ladder of a hybrid prefill of ``b`` rows whose prompts hold
    ``segments`` segments: the batch of each stage, ``b`` first and strictly
    falling. A second stage holds half the rows, rounded down and never under
    two; there is one where the prompt has four segments or more (a stage for
    every two segments, HYBRID_PREFILL_STAGES at most: with fewer a second body
    has little to save and costs what any body costs). A prefill of one or two
    rows, or of prompts under four segments, is one stage. It reads the shapes
    alone: one program serves every mix of lengths.

    Why half: over lengths spread evenly, the rows a second stage saves times
    the segments it saves them is largest there. Why no stage of a single row
    (PERF.md §6, PR 52): a body is compiled for its batch, and the compiler
    builds a one-row body unlike the others (the retention cell's is 2.6 times
    the two-row body's text, 8 MB more executable to load at every start;
    MiniCPM-SALA's takes 1.3 GB more temporaries, and its trips run no faster
    than two rows'), so the last row's segments cost a body's set-up and buy
    the least."""
    stages = max(min(HYBRID_PREFILL_STAGES, segments // 2), 1)
    sizes = {min(b, max(b >> k, 2)) for k in range(stages)}
    return tuple(sorted(sizes, reverse=True))


def _stage_trips(segments, sizes: tuple[int, ...]) -> list[tuple]:
    """``(first, end)`` segment of each stage of the ladder ``sizes``, from
    ``segments [b]``, the segments each row holds a real token in, LONGEST
    FIRST: stage k runs rows ``0 .. sizes[k]`` from where stage k - 1 ended to
    the end of the longest row that stage k + 1 drops, the last stage to the
    end of the longest row. Traced values inside the program and numpy's on
    the host give the same trips."""
    ends = [segments[size] for size in sizes[1:]] + [segments[0]]
    return list(zip([0, *ends[:-1]], ends))


def _file_prefill_share(prompt_lens, prompt_pages: int, page_size: int) -> int:
    """File the gauge ``engine/prefill_real_share`` for a round's one prefill:
    real prompt tokens over the tokens the program's stages ran, x 100, from
    host arithmetic over the prompts' lengths and the ladder, nothing fetched
    from the device (100 where every row ends with the stage that drops it;
    under it by partly filled last segments and by the rows the ladder
    carries past their end). Returns the segments of the longest row, where
    the stages end: ``ops/latent_kernel_folds`` is counted off it."""
    seg, n_seg = _hybrid_segments(prompt_pages, page_size)
    lens = np.sort(np.asarray(prompt_lens, np.int64))[::-1]
    segments = -(-lens // seg)
    sizes = _stage_sizes(len(lens), n_seg)
    ran = sum(size * int(end - first)
              for size, (first, end) in zip(sizes, _stage_trips(segments, sizes)))
    telemetry.gauge_set(
        ENGINE_PREFILL_REAL_SHARE, 100.0 * int(lens.sum()) / max(ran * seg, 1))
    return int(segments[0])


def _paged_prefill_hybrid(params, lora, prompt_ids, prompt_mask, *,
                          cfg: ModelConfig, prompt_pages: int, page_size: int,
                          lora_scale: float, cache_dtype, attn_impl: str,
                          total_tokens: int):
    """``_paged_prefill`` for a model with sparse and lightning layers: the
    packed prompts run SEGMENT after segment, each through all layers, the
    cache carried between them: K/V pages and pooled selector keys for the
    sparse layers, a state for each lightning layer. A 20k-token prompt run
    whole would hold the MLP's activations and a sparse layer's scores for all
    of it at once.

    The segments run in STAGES of a shrinking batch, so that few rows run a
    segment after the one that holds their last real token: the rows are
    sorted by length inside the program, longest first, and stage k runs the
    first ``_stage_sizes(b, segments)[k]`` of them, every row of the stage at
    the same page-aligned offset, for a TRACED number of segments
    (``_stage_trips``: to the end of the longest row the next stage drops).
    Nothing about the lengths is static: one program for every mix. A sorted
    row writes its own pages through its own row of the page table, so the
    pools come out in the caller's order; the row states are carried in sorted
    order, a stage's dropped rows set aside as they end, and put back in the
    caller's order once, after the last stage (a ladder of one size drops no
    row and sorts none: the one loop a prefill of one or two rows always was,
    to the longest row's end). A row that rides on in a stage
    with a longer one (the ladder skips sizes, and holds no stage of one row)
    runs those segments masked, as every row did before the stages: its states
    and logits stay those of its last real token, and the pages past its end
    take what nobody reads. Rows that are all empty run nothing.

    Returns ``(k tiles, v tiles, logits, real_len, mixer)``: ``mixer`` is each
    PROMPT's state after its last real token and its pooled keys, which a
    candidate that aliases the prompt's pages is also handed at admission, and
    the round's counters as the segments leave them (``PREFILL_COUNTERS``: the
    decode state starts from them; the others count decode steps alone)."""
    from distrl_llm_tpu.models.hybrid import init_mixer_state
    from distrl_llm_tpu.ops.linear import linear

    b, p = prompt_ids.shape
    pad_to = prompt_pages * page_size
    seg, n_seg = _hybrid_segments(prompt_pages, page_size)
    sizes = _stage_sizes(b, n_seg)
    staged = len(sizes) > 1  # one stage drops no row: the rows keep the caller's order
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        packed_ids, packed_mask, real_len = _pack_rows(prompt_ids, prompt_mask)
        order = jnp.argsort(-real_len, stable=True)  # longest first
        trips = _stage_trips(-(-real_len[order] // seg), sizes)
        place = (lambda x: x[order]) if staged else (lambda x: x)
        ids = place(jnp.pad(packed_ids, ((0, 0), (0, pad_to - p))))
        mask = place(jnp.pad(packed_mask, ((0, 0), (0, pad_to - p))))
        table = place(jnp.asarray(make_page_table(b, pad_to, page_size)))
        sorted_len = place(real_len)
        last = jnp.maximum(sorted_len - 1, 0)
    shape = cfg.page_pool_shape(b * prompt_pages, page_size)
    second = cfg.second_pool_shape(b * prompt_pages, page_size)
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        mixer = init_mixer_state(cfg, b, total_tokens, cache_dtype)
        pool = lambda shape: () if shape is None else tuple(
            jnp.zeros(shape, cache_dtype) for _ in range(cfg.paged_layers))
        # a latent layer's pages are one array: there is no V pool, and the
        # second slot is empty or holds its index keys, one array a layer
        pools = {"k": pool(shape), "v": pool(second)}
        # every row's states start alike: these are the sorted rows' too
        rows = (_row_states(mixer),
                jnp.zeros((b, cfg.hidden_size), params["final_norm"].dtype))
        counted = _prefill_counters(mixer)

    def one_segment(j, carry):
        """Segment ``j`` of the stage's rows: the first ``n`` sorted ones."""
        pools, (states, hidden), counted = carry
        n = hidden.shape[0]
        start = j * seg
        x, out = forward(
            params, cfg, jax.lax.dynamic_slice_in_dim(ids[:n], start, seg, axis=1),
            attention_mask=jax.lax.dynamic_slice_in_dim(mask[:n], start, seg, axis=1),
            lora=lora, lora_scale=lora_scale, attn_impl=attn_impl, page_size=page_size,
            kv_cache={**pools, **states, **counted, "lengths": sorted_len[:n],
                      "page_indices": table[:n], "segment_start": start},
            # the row's last real token, if it lies in this segment
            logits_positions=jnp.clip(last[:n] - start, 0, seg - 1),
            skip_lm_head=True,
        )
        here = (last[:n] >= start) & (last[:n] < start + seg)
        hidden = jnp.where(here[:, None], x[:, 0], hidden)
        return ({name: out[name] for name in pools},
                ({name: out[name] for name in states}, hidden),
                {name: out[name] for name in counted})

    ended = []  # the rows each stage drops as it ends, the last stage's all
    for (first, end), keep in zip(trips, (*sizes[1:], 0)):
        pools, rows, counted = jax.lax.fori_loop(
            first, end, one_segment, (pools, rows, counted))
        ended.append(jax.tree_util.tree_map(lambda x: x[keep:], rows))
        rows = jax.tree_util.tree_map(lambda x: x[:keep], rows)
    states, hidden = ended[0]
    if staged:
        with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
            back = jnp.argsort(order)  # sorted row i is the caller's row order[i]
            states, hidden = jax.tree_util.tree_map(
                lambda *parts: jnp.concatenate(parts)[back], *reversed(ended))
    with jax.named_scope(telemetry.MODEL_HEAD):
        head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
        logits = linear(hidden, head).astype(jnp.float32)
    return pools["k"], pools["v"], logits, real_len, {**mixer, **states, **counted}


def _prefill_counters(mixer) -> dict:
    """Of the round's counters in a mixer state, those a prefill adds to
    (``PREFILL_COUNTERS``); nothing for a model that has none."""
    return {name: mixer[name] for name in PREFILL_COUNTERS if name in (mixer or {})}


def _row_states(mixer) -> dict:
    """The entries of a slot state (or a cache) that hold one array a row a
    layer: lightning states, pooled keys, delta-rule states and convolution
    tails (``models/hybrid.py::ROW_STATES``). The round's counters are not."""
    from distrl_llm_tpu.models.hybrid import ROW_STATES

    return {name: mixer[name] for name in ROW_STATES if name in mixer}


def _cache_token_bytes(k_pages, v_pages) -> int:
    """Bytes ONE more token of context costs a slot, summed over the layers
    that keep pages, read off the pools' own shapes, K's and V's apart: an
    array ``[.., pages, page, width]`` holds its leading axes x ``width``
    values a token (int8 pages: the weights and their scales)."""
    return sum(
        int(np.prod(x.shape[:-3], dtype=np.int64)) * x.shape[-1] * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves((k_pages, v_pages)))


def _file_slot_state(mixer, k_pages=(), v_pages=()) -> dict:
    """File what a round's decode state costs, tracing on or off, nothing
    fetched: ``engine/cache_token_bytes``, what one more token of context costs
    a slot in the pools ``k_pages`` / ``v_pages`` (every paged family), and the
    bytes the decode state's row states hold as ``engine/slot_state_bytes``,
    returned as the round's span and ``last_round_stats`` say them:
    ``{"slot_state_bytes": n}``; nothing for a model that has no such state."""
    telemetry.gauge_set(telemetry.ENGINE_CACHE_TOKEN_BYTES,
                        float(_cache_token_bytes(k_pages, v_pages)))
    if mixer is None:
        return {}
    held = pool_nbytes(_row_states(mixer))
    telemetry.gauge_set(ENGINE_SLOT_STATE_BYTES, float(held))
    return {"slot_state_bytes": held}


def _hand_mixer(mixer, prompt_mixer, prompt_of, admit_mask):
    """Admitted slots take their prompt's row states: lightning states and
    pooled keys, delta-rule states and convolution tails (a candidate aliases
    its prompt's K/V pages; what is not in pages is copied). Other slots and
    the round's counters are kept."""
    if mixer is None:
        return None

    def hand(slot, prompt):
        keep = admit_mask.reshape((-1,) + (1,) * (slot.ndim - 1))
        return jnp.where(keep, prompt[prompt_of], slot)

    return {
        **mixer,
        **{name: tuple(map(hand, held, prompt_mixer[name]))
           for name, held in _row_states(mixer).items()},
    }


def _mixer_cache(mixer, alive):
    """The entries a hybrid model's ``forward`` reads beside k/v pages."""
    return {} if mixer is None else {**mixer, "alive": alive}


def _mixer_from_cache(mixer, cache):
    return None if mixer is None else {name: cache[name] for name in mixer}


def _at_pages(arr, idx) -> tuple:
    """The index of pages ``idx`` in a pool: ``[K, pages, ps, hd]`` keeps its
    pages on axis 1, a latent pool ``[pages, ps, row]`` on axis 0."""
    return (slice(None),) * (arr.ndim - 3) + (idx,)


def _grow_pool(pages, extra_pages: int):
    """Append ``extra_pages`` zeroed pages to a pool [K, S, ps, tail] →
    [K, S+extra, ps, tail] (quantized pools grow weight + scales alike; a
    latent pool [S, ps, row] grows on its first axis)."""

    def grow(arr):
        lead, (s_, ps, tail) = arr.shape[:-3], arr.shape[-3:]
        out = jnp.zeros((*lead, s_ + extra_pages, ps, tail), arr.dtype)
        return out.at[_at_pages(arr, slice(0, s_))].set(arr)

    from distrl_llm_tpu.ops.paged import is_quantized_pages

    if is_quantized_pages(pages):
        return type(pages)(weight=grow(pages.weight), scales=grow(pages.scales))
    return grow(pages)


def _copy_pages(pages, src_idx, dst_idx, keep_mask=None):
    """``pages[:, dst_idx] = pages[:, src_idx]`` — the partial-prompt-page
    copy shared by fan-out and refill-admit. With ``keep_mask``, rows where
    it is False keep their current destination content (quantized pools copy
    weight + scales alike, preserving the (int8, scale) pairing)."""

    def cp(arr):
        tile = arr[_at_pages(arr, src_idx)]
        if keep_mask is not None:
            keep = keep_mask[(None,) * (arr.ndim - 3) + (slice(None), None, None)]
            tile = jnp.where(keep, tile, arr[_at_pages(arr, dst_idx)])
        return arr.at[_at_pages(arr, dst_idx)].set(tile)

    from distrl_llm_tpu.ops.paged import is_quantized_pages

    if is_quantized_pages(pages):
        return type(pages)(weight=cp(pages.weight), scales=cp(pages.scales))
    return cp(pages)


def _page_table_rows(prompt_of, full, priv0, *, prompt_pages: int,
                     private_pages: int):
    """Page-table rows [R, width] with shared prompt prefixes: column t of
    row r holds position block t — the prompt's shared full pages below
    ``full[r]``, the row's private pages after; trailing unused columns clamp
    to a valid private page (the jnp reference gathers the whole width)."""
    width = prompt_pages + private_pages
    col = jnp.arange(width)[None, :]
    shared_entry = prompt_of[:, None] * prompt_pages + col
    private_entry = jnp.minimum(
        priv0[:, None] + (col - full[:, None]),
        priv0[:, None] + private_pages - 1,
    )
    return jnp.where(col < full[:, None], shared_entry, private_entry).astype(
        jnp.int32
    )


def _cont_adopt(state, k_tiles, v_tiles, dst_idx, logits_buf, logits_row, g):
    """Adopt one lazily-prefilled group's prompt KV into the live pool
    arrays and publish its sampling logits (continuous admission).

    ``dst_idx`` [prompt_pages] is the pool-allocated chain padded with the
    scratch page — the tiles beyond the prompt's real chain carry prefill's
    pad-position garbage and land on scratch, which takes garbage writes by
    contract (duplicate scratch destinations are fine: whichever write wins
    is equally garbage). Quantized pools place weight + scales alike, so
    the (int8, scale) pairing survives adoption."""
    from distrl_llm_tpu.ops.paged import is_quantized_pages

    def place(pages, tiles):
        if is_quantized_pages(pages):
            return type(pages)(
                weight=pages.weight.at[:, dst_idx].set(tiles.weight),
                scales=pages.scales.at[:, dst_idx].set(tiles.scales),
            )
        return pages.at[:, dst_idx].set(tiles)

    state = state._replace(
        k_pages=tuple(place(p, t) for p, t in zip(state.k_pages, k_tiles)),
        v_pages=tuple(place(p, t) for p, t in zip(state.v_pages, v_tiles)),
    )
    return state, logits_buf.at[g].set(logits_row)


def _paged_fanout(prompt_k, prompt_v, last_logits, real_len, row_alive,
                  prompt_mixer=None,
                  *, n: int, b: int, prompt_pages: int, private_pages: int,
                  page_size: int, max_steps: int):
    """Expand B prompts to B·n candidate rows with SHARED prompt prefixes.

    vLLM's prefix sharing, static-shape edition: every candidate's page table
    points its leading columns at the prompt's FULL pages in the shared pool
    (written once by prefill, never written again), and only the partial last
    prompt page — which decode tokens will extend in place — is copied per
    candidate into a private region alongside its decode pages. At the
    reference volume this drops prompt KV memory from B·n to ~B copies.

    Returns (state, page_indices): the table is data-DEPENDENT (each prompt's
    full-page count is real_len // page_size) but shape-static, so it rides
    as a traced array and never forces a recompile."""
    bn = b * n
    total_shared = b * prompt_pages

    full = real_len // page_size  # [B] full shared pages per prompt
    full_r = jnp.repeat(full, n)  # [Bn]
    prompt_of_row = jnp.repeat(jnp.arange(b), n)  # [Bn]
    priv0 = total_shared + jnp.arange(bn) * private_pages  # [Bn]

    page_indices = _page_table_rows(
        prompt_of_row, full_r, priv0,
        prompt_pages=prompt_pages, private_pages=private_pages,
    )

    # the partial prompt page each candidate must own privately (clamped for
    # page-aligned prompts, where the copy content is never read)
    src_partial = prompt_of_row * prompt_pages + jnp.repeat(
        jnp.minimum(full, prompt_pages - 1), n
    )

    def expand(pages):
        grown = _grow_pool(pages, bn * private_pages)
        return _copy_pages(grown, src_partial, priv0)

    k_pages = tuple(expand(x) for x in prompt_k)
    v_pages = tuple(expand(x) for x in prompt_v)
    state = _PagedDecodeState(
        step=jnp.zeros((), jnp.int32),
        out=jnp.zeros((bn, max_steps), jnp.int32),
        logps=jnp.zeros((bn, max_steps), jnp.float32),
        gen_lengths=jnp.zeros((bn,), jnp.int32),
        done=jnp.repeat(~row_alive, n, axis=0),
        logits=jnp.repeat(last_logits, n, axis=0),
        seq_lengths=jnp.repeat(real_len, n, axis=0),
        k_pages=k_pages,
        v_pages=v_pages,
        # each candidate starts from its prompt's row states
        mixer=None if prompt_mixer is None else {
            **prompt_mixer,
            **{name: tuple(jnp.repeat(x, n, axis=0) for x in held)
               for name, held in _row_states(prompt_mixer).items()},
        },
    )
    return state, page_indices


def _paged_decode_step(params, lora, state: _PagedDecodeState, rng, page_indices,
                       *, cfg: ModelConfig, page_size: int, eos_ids, pad_id: int,
                       temperature, top_p, lora_scale: float, paged_impl: str,
                       top_p_impl: str = "bisect", capture_logprobs: bool = False):
    """One donated decode step over the paged cache (host-loop dispatched,
    zero cache-sized temps — same design as engine._decode_step)."""
    s = state
    # fused sample+logprob when enabled (ops/sampling.py); done rows'
    # logprobs are zeroed below, so pre-substitution logprobs are
    # observably identical to the old post-substitution token_logprob
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
        if capture_logprobs:
            logp = jnp.where(s.done, 0.0, logp_s)
            logps = jax.lax.dynamic_update_slice(
                s.logps, logp[:, None], (0, s.step))
        else:
            logps = s.logps
        gen_lengths = s.gen_lengths + (~s.done).astype(jnp.int32)
        hit_eos = jnp.isin(tok, eos_ids)
        done = s.done | hit_eos

    cache = {
        "k": s.k_pages, "v": s.v_pages,
        "lengths": s.seq_lengths,
        "page_indices": page_indices,
        **_mixer_cache(s.mixer, ~s.done),
    }
    next_logits, cache = forward(
        params, cfg, tok[:, None],
        positions=s.seq_lengths[:, None],
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_impl=paged_impl,
    )
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        seq_lengths = s.seq_lengths + (~s.done).astype(jnp.int32)
        step = s.step + 1
    return _PagedDecodeState(
        step=step, out=out, logps=logps, gen_lengths=gen_lengths,
        done=done, logits=next_logits[:, 0], seq_lengths=seq_lengths,
        k_pages=cache["k"], v_pages=cache["v"],
        mixer=_mixer_from_cache(s.mixer, cache),
    )


def _paged_decode_chunk(params, lora, state: _PagedDecodeState, rng,
                        page_indices, *, chunk: int,
                        cfg: ModelConfig, page_size: int, eos_ids,
                        pad_id: int, temperature, top_p, lora_scale: float,
                        paged_impl: str,
                        top_p_impl: str = "bisect",
                        capture_logprobs: bool = False):
    """``chunk`` wave-mode paged decode steps in ONE dispatch via
    ``lax.scan`` — the exact mirror of the dense engine's
    ``_decode_chunk`` (see its docstring for the dispatch-overhead
    rationale). The body is unguarded (a cond would double-buffer the
    page pools — scan_steps_guarded), so the HOST never dispatches a
    chunk crossing ``max_steps``; all-done steps are per-row no-ops."""
    def run(s):
        return _paged_decode_step(
            params, lora, s, rng, page_indices, cfg=cfg,
            page_size=page_size, eos_ids=eos_ids, pad_id=pad_id,
            temperature=temperature, top_p=top_p, lora_scale=lora_scale,
            paged_impl=paged_impl,
            top_p_impl=top_p_impl,
            capture_logprobs=capture_logprobs,
        )

    return scan_steps_guarded(run, state, chunk)


def _refill_init(prompt_k, prompt_v, counted=None,
                 *, b: int, r_slots: int, total: int,
                 max_steps: int, vocab: int, pool_pages: int,
                 prompt_pages: int, private_pages: int, pad_id: int,
                 shared_pages: int | None = None, cfg: ModelConfig | None = None,
                 page_size: int = 0, cache_dtype=jnp.bfloat16):
    """Empty R-slot decode state over the shared prompt pool: every slot is
    born dead; ``_refill_admit`` assigns occupants (including the first R).

    ``pool_pages`` is the decode page POOL size (the vLLM block pool behind
    ``gpu_memory_utilization`` / ``--actor_gpu_usage``): pages are owned by
    the host-side ``PagePool`` allocator, not statically partitioned per
    slot. Every slot's table starts pointed at the SCRATCH page (pool page
    0): decode steps run for dead slots too, and their garbage KV writes
    must land somewhere no live row ever reads — an all-zero table would
    alias physical page 0, a SHARED prefill page, and corrupt prompt 0's KV
    for every candidate (caught in review).

    ``shared_pages`` overrides the static prompt-region size (None = the
    historical ``b·prompt_pages``): continuous admission passes 0 — prompt
    chains are pool-allocated, ``prompt_k``/``prompt_v`` arrive as 0-page
    tiles, and the scratch page is physical page 0.

    ``counted`` is what a hybrid model's prefill left of the round's counters
    (``PREFILL_COUNTERS``): the state's start from it, not from zero."""
    total_shared = b * prompt_pages if shared_pages is None else shared_pages
    width = prompt_pages + private_pages
    mixer = None
    if cfg is not None and cfg.hybrid:  # what a slot holds beside K/V pages
        from distrl_llm_tpu.models.hybrid import init_mixer_state

        mixer = {**init_mixer_state(cfg, r_slots, width * page_size, cache_dtype),
                 **(counted or {})}
    elif cfg is not None and cfg.looped:
        mixer = looped_account()

    return _RefillState(
        step=jnp.zeros((), jnp.int32),
        alive_steps=jnp.zeros((), jnp.int32),
        out=jnp.full((total, max_steps), pad_id, jnp.int32),
        logps_buf=jnp.zeros((total, max_steps), jnp.float32),
        lengths_buf=jnp.zeros((total,), jnp.int32),
        cand=jnp.full((r_slots,), total, jnp.int32),
        done=jnp.ones((r_slots,), bool),
        logits=jnp.zeros((r_slots, vocab), jnp.float32),
        seq_lengths=jnp.zeros((r_slots,), jnp.int32),
        gen_lengths=jnp.zeros((r_slots,), jnp.int32),
        page_indices=jnp.full((r_slots, width), total_shared, jnp.int32),
        k_pages=tuple(_grow_pool(x, pool_pages) for x in prompt_k),
        v_pages=tuple(_grow_pool(x, pool_pages) for x in prompt_v),
        mixer=mixer,
    )


def _admit_tables(state, new_cand, admit_mask, real_len, dst_partial,
                  *, n: int, b: int, prompt_pages: int, page_size: int,
                  src_partial=None, copy_mask=None):
    """The admit work shared by the plain and speculative refill schedulers:
    merge slot assignments and build the partial-page recopy (the last,
    partial prompt page is extended in place by decode, so each admitted
    slot needs a private copy at the host-chosen ``dst_partial`` page).
    Page-TABLE rows are host-authored (engine/page_pool.py) and shipped via
    ``state._replace`` — the device no longer computes them.

    With ``src_partial``/``copy_mask`` the HOST authored the copy plan too
    (prefix sharing: the pool's copy-on-write splits name the pristine
    chain-tail source per slot, and page-aligned prompts need no copy at
    all); without them the source derives from the static prompt region
    exactly as it always has. Returns (cand, live_new, prompt_of, recopy)."""
    s = state
    total = b * n

    cand = jnp.where(admit_mask, new_cand, s.cand)
    live_new = new_cand < total
    prompt_of = jnp.clip(cand // n, 0, b - 1)
    if src_partial is None:
        full = real_len[prompt_of] // page_size  # [R] shared full pages
        src = prompt_of * prompt_pages + jnp.minimum(full, prompt_pages - 1)
        keep = admit_mask & live_new
    else:
        src = src_partial
        keep = copy_mask

    def recopy(pages):
        return _copy_pages(pages, src, dst_partial, keep_mask=keep)

    return cand, live_new, prompt_of, recopy


@jax.named_scope(telemetry.ENGINE_ADMIT)
def _refill_admit(state: _RefillState, new_cand, admit_mask, last_logits,
                  real_len, dst_partial, src_partial=None, copy_mask=None,
                  prompt_mixer=None,
                  *, n: int, b: int, prompt_pages: int, page_size: int):
    """Assign candidates to slots (vLLM's scheduler admitting waiting
    sequences into freed slots, static-shape edition). All shapes are
    static; which slots refill is data."""
    s = state
    cand, live_new, prompt_of, recopy = _admit_tables(
        s, new_cand, admit_mask, real_len, dst_partial, n=n, b=b,
        prompt_pages=prompt_pages, page_size=page_size,
        src_partial=src_partial, copy_mask=copy_mask,
    )

    return _RefillState(
        step=s.step,
        alive_steps=s.alive_steps,
        out=s.out,
        logps_buf=s.logps_buf,
        lengths_buf=s.lengths_buf,
        cand=cand,
        done=jnp.where(admit_mask, ~live_new, s.done),
        logits=jnp.where(admit_mask[:, None], last_logits[prompt_of], s.logits),
        seq_lengths=jnp.where(admit_mask, real_len[prompt_of], s.seq_lengths),
        gen_lengths=jnp.where(admit_mask, 0, s.gen_lengths),
        page_indices=s.page_indices,
        k_pages=tuple(recopy(x) for x in s.k_pages),
        v_pages=tuple(recopy(x) for x in s.v_pages),
        mixer=_hand_mixer(s.mixer, prompt_mixer, prompt_of, admit_mask & live_new),
    )


def _spec_resume_fixup(params, lora, state, slot, prefix_tok, prefix_len,
                       real_len_c, seq_row, first_logp, *, cfg: ModelConfig,
                       page_size: int, lora_scale: float):
    """Speculative-mode preemption resume: rebuild the evicted candidate's
    RESIDENT KV (its prefix minus the pending last token — spec slots carry
    ``last_tok`` emitted-but-not-resident, engine/speculative.py) via one
    chunked prefill, reseed the n-gram sequence buffer from the host-built
    row, and fast-forward the cursors. No logits to restore: the next spec
    step's verify forward consumes last_tok directly.

    The preceding re-admission (``_spec_admit``) sampled a FRESH first token
    and overwrote out[c, 0] / logps_buf[c, 0] / lengths_buf[c] — under
    sampling that token differs from the originally emitted prefix[0], so
    the buffers must be restored to the PREFIX the resident KV encodes
    (``first_logp`` is the original behavior logprob, read back by the host
    at preempt time). Pinned by the logprob-consistency regression in
    tests/test_paged_budget.py."""
    s = state
    t = prefix_tok.shape[0]
    resident = jnp.maximum(prefix_len - 1, 0)
    valid = (jnp.arange(t) < resident).astype(jnp.int32)[None, :]
    cache = {
        "k": s.k_pages, "v": s.v_pages,
        "lengths": real_len_c[None],
        "page_indices": s.page_indices[slot][None],
    }
    positions = (real_len_c + jnp.arange(t, dtype=jnp.int32))[None, :]
    _, cache = forward(
        params, cfg, prefix_tok[None],
        attention_mask=valid, positions=positions,
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_chunked=True,
        logits_positions=jnp.zeros((1,), jnp.int32),
    )
    last = prefix_tok[jnp.maximum(prefix_len - 1, 0)]
    cand = s.cand[slot]
    return s._replace(
        done=s.done.at[slot].set(False),
        out=s.out.at[cand, 0].set(prefix_tok[0]),
        logps_buf=s.logps_buf.at[cand, 0].set(first_logp),
        lengths_buf=s.lengths_buf.at[cand].set(prefix_len),
        last_tok=s.last_tok.at[slot].set(last),
        seq_buf=s.seq_buf.at[slot].set(seq_row),
        gen_lengths=s.gen_lengths.at[slot].set(prefix_len),
        seq_lengths=s.seq_lengths.at[slot].set(real_len_c + resident),
        k_pages=cache["k"], v_pages=cache["v"],
    )


def _resume_fixup(params, lora, state: _RefillState, slot, prefix_tok,
                  prefix_len, real_len_c, *, cfg: ModelConfig, page_size: int,
                  lora_scale: float):
    """Rebuild a PREEMPTED candidate's KV by continuation (chunked) prefill
    and resume it in ``slot`` — vLLM's preempt-by-recompute. The slot was
    just admitted normally (prompt logits, gen 0); this pass re-runs the
    candidate's generated prefix through the model in ONE forward (KV writes
    to the slot's pages; attention over the dense-gathered context), then
    fast-forwards the slot's cursor to the prefix end. The prefix tokens /
    behavior logprobs already live in the out/logps buffers (scatter by
    candidate id — preemption never erased them)."""
    s = state
    t = prefix_tok.shape[0]
    valid = (jnp.arange(t) < prefix_len).astype(jnp.int32)[None, :]
    cache = {
        "k": s.k_pages, "v": s.v_pages,
        "lengths": real_len_c[None],
        "page_indices": s.page_indices[slot][None],
    }
    positions = (real_len_c + jnp.arange(t, dtype=jnp.int32))[None, :]
    logits, cache = forward(
        params, cfg, prefix_tok[None],
        attention_mask=valid, positions=positions,
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_chunked=True,
        logits_positions=jnp.maximum(prefix_len - 1, 0)[None],
    )
    return s._replace(
        done=s.done.at[slot].set(False),
        logits=s.logits.at[slot].set(logits[0, 0]),
        gen_lengths=s.gen_lengths.at[slot].set(prefix_len),
        seq_lengths=s.seq_lengths.at[slot].set(real_len_c + prefix_len),
        k_pages=cache["k"], v_pages=cache["v"],
    )


def _turn_resume_fixup(params, lora, state: _RefillState, slot, obs_tok,
                       obs_len, cand_c, gen_len_c, seq_len_c, real_len_c,
                       *, cfg: ModelConfig, page_size: int, lora_scale: float,
                       max_steps: int, pad_id: int,
                       capture_logprobs: bool = False):
    """Continue a FINISHED candidate in place with environment-injected
    observation tokens (ISSUE 17 multi-turn episodes). Unlike ``_resume_fixup``
    the slot was never released: the whole conversation's KV (prompt + every
    prior turn, including the just-ended one) is still resident in the slot's
    pages, so this appends exactly the observation — one chunked forward over
    ``obs_len`` tokens instead of re-prefilling ``seq_len_c`` of context.

    The observation tokens are recorded in the candidate's out buffer (they
    are part of the answer the driver decodes) with behavior logprobs zeroed
    — the trainer's loss mask excludes env-injected spans, so the zeros are
    never consumed as behavior probabilities.

    Positions are clamped to the slot's page-table coverage
    (``real_len + max_steps`` tokens): masked padding lanes beyond ``obs_len``
    would otherwise scatter KV garbage past the table. Valid observation
    positions never reach the clamp — the host only resumes when
    ``gen_len + obs_len < max_steps`` — so the clamp target is only ever
    written by masked lanes whose KV is never attended to (attention is
    bounded by the cache lengths entry)."""
    s = state
    t = obs_tok.shape[0]
    steps = jnp.arange(t, dtype=jnp.int32)
    valid_vec = steps < obs_len
    valid = valid_vec.astype(jnp.int32)[None, :]
    cache = {
        "k": s.k_pages, "v": s.v_pages,
        "lengths": seq_len_c[None],
        "page_indices": s.page_indices[slot][None],
    }
    positions = jnp.minimum(seq_len_c + steps, real_len_c + max_steps - 1)[None, :]
    obs_tok = jnp.where(valid_vec, obs_tok, pad_id)
    logits, cache = forward(
        params, cfg, obs_tok[None],
        attention_mask=valid, positions=positions,
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_chunked=True,
        logits_positions=jnp.maximum(obs_len - 1, 0)[None],
    )
    total_cols = s.out.shape[1]
    # out-of-range column sentinel drops the padding lanes, mirroring the
    # decode step's dead-slot scatter discipline
    col = jnp.where(valid_vec, gen_len_c + steps, total_cols)
    out = s.out.at[cand_c, col].set(obs_tok, mode="drop")
    if capture_logprobs:
        logps_buf = s.logps_buf.at[cand_c, col].set(
            jnp.zeros_like(col, dtype=s.logps_buf.dtype), mode="drop")
    else:
        logps_buf = s.logps_buf
    new_gen = gen_len_c + obs_len
    return s._replace(
        out=out, logps_buf=logps_buf,
        lengths_buf=s.lengths_buf.at[cand_c].set(new_gen),
        done=s.done.at[slot].set(False),
        logits=s.logits.at[slot].set(logits[0, 0]),
        gen_lengths=s.gen_lengths.at[slot].set(new_gen),
        seq_lengths=s.seq_lengths.at[slot].set(seq_len_c + obs_len),
        k_pages=cache["k"], v_pages=cache["v"],
    )


def _gather_page_tiles(k_pages, v_pages, src):
    """One physical page's KV tiles across all layers — [K, ps, hd] (or
    int8 weight+scales) per layer — as INDEPENDENT device buffers: jit
    outputs, never views into the state pools, so a background spill
    thread may hold them across the decode loop's donated-state dispatches
    (ISSUE 18 tier-2 transport; the PR 15 quant idiom — quantized pools
    gather weight + scales alike, so the round-trip is a pure memcpy and
    bit-exact by construction)."""
    from distrl_llm_tpu.ops.paged import is_quantized_pages

    def take(pages):
        if is_quantized_pages(pages):
            return type(pages)(
                weight=pages.weight[:, src], scales=pages.scales[:, src]
            )
        return pages[:, src]

    return (
        tuple(take(p) for p in k_pages),
        tuple(take(p) for p in v_pages),
    )


def _restore_page_tiles(state, k_tiles, v_tiles, dst):
    """Scatter one parked page's tiles back into the live pools at page
    ``dst`` (the `_cont_adopt` placement idiom, single-page edition)."""
    from distrl_llm_tpu.ops.paged import is_quantized_pages

    def put(pages, tile):
        if is_quantized_pages(pages):
            return type(pages)(
                weight=pages.weight.at[:, dst].set(tile.weight),
                scales=pages.scales.at[:, dst].set(tile.scales),
            )
        return pages.at[:, dst].set(tile)

    return state._replace(
        k_pages=tuple(put(p, t) for p, t in zip(state.k_pages, k_tiles)),
        v_pages=tuple(put(p, t) for p, t in zip(state.v_pages, v_tiles)),
    )


def _warm_prefill(params, lora, state: _RefillState, row_ext, suffix_tok,
                  suffix_len, start, logits_buf, g, *, cfg: ModelConfig,
                  page_size: int, lora_scale: float, pad_id: int):
    """Suffix-only group prefill through a radix-cache hit (ISSUE 18): the
    prompt's first ``start`` tokens are already resident in cached chain
    pages, so this forwards only the un-cached suffix — KV writes land in
    the chain's FRESH pages (the hit is capped below the last token, so no
    suffix position ever writes into a cached page) and the group's
    sampling logits come off the suffix's last real token.

    Runs the ``paged_prefix`` forward mode: suffix KV is written to pages,
    then attention goes through the SAME packed ``attention`` front door
    the cold `_paged_prefill` uses, over the row's dense-gathered packed
    window in compute dtype — so a warm group's logits and suffix KV are
    bit-identical to the cold prefill's (cached pages hold exact ``astype``
    round-trips of the in-flight k/v the cold path attended over).

    ``row_ext`` is the chain's table row padded with the scratch page plus
    ONE extra trailing scratch column: masked padding lanes clamp their
    positions to ``prompt_pages * page_size``, whose block index is exactly
    that extra column — their garbage KV lands on scratch, never in a page
    another admission could alias (the `_turn_resume_fixup` clamp
    discipline, aimed at scratch instead of the write ceiling because
    cached pages are immutable cross-group state). The ``paged_prefix``
    gather drops that trailing column, so the attention key window is
    exactly the cold packed width."""
    s = state
    t = suffix_tok.shape[0]
    prompt_pages = row_ext.shape[0] - 1
    steps = jnp.arange(t, dtype=jnp.int32)
    valid_vec = steps < suffix_len
    valid = valid_vec.astype(jnp.int32)[None, :]
    cache = {
        "k": s.k_pages, "v": s.v_pages,
        "lengths": start[None],
        "page_indices": row_ext[None],
    }
    positions = jnp.where(
        valid_vec, start + steps, prompt_pages * page_size
    )[None, :]
    suffix_tok = jnp.where(valid_vec, suffix_tok, pad_id)
    logits, cache = forward(
        params, cfg, suffix_tok[None],
        attention_mask=valid, positions=positions,
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_prefix=True,
        logits_positions=jnp.maximum(suffix_len - 1, 0)[None],
    )
    s = s._replace(k_pages=cache["k"], v_pages=cache["v"])
    return s, logits_buf.at[g].set(logits[0, 0])


def _spill_resume_fixup(state: _RefillState, slot, logits_row, prefix_len,
                        real_len_c):
    """Cursor-only resume for a candidate whose KV pages were restored from
    the host spill store (ISSUE 18 tier 2): the preceding `_refill_admit`
    seated the slot with prompt logits and zeroed cursors, and the page
    restores already re-materialized the generated prefix's KV bit-exactly
    — so unlike `_resume_fixup` there is nothing to recompute, only the
    slot's logits row and cursors to fast-forward. The out/logps/lengths
    buffers are candidate-indexed and were never erased by preemption."""
    s = state
    return s._replace(
        done=s.done.at[slot].set(False),
        logits=s.logits.at[slot].set(logits_row),
        gen_lengths=s.gen_lengths.at[slot].set(prefix_len),
        seq_lengths=s.seq_lengths.at[slot].set(real_len_c + prefix_len),
    )


def _refill_decode_step(params, lora, state: _RefillState, rng,
                        *, cfg: ModelConfig, page_size: int, eos_ids,
                        pad_id: int, temperature, top_p, lora_scale: float,
                        paged_impl: str, max_steps: int,
                        top_p_impl: str = "bisect",
                        capture_logprobs: bool = False):
    """One donated decode step over R slots. Differences from the wave step:
    output/length writes scatter by candidate id (``mode="drop"`` discards
    dead slots via the out-of-range sentinel), and a slot self-stops once its
    occupant has generated ``max_steps`` tokens (the host loop's step count
    bounds a WAVE's lifetime, not a slot's)."""
    s = state
    total = s.out.shape[0]
    alive = ~s.done
    # fused sample+logprob when enabled (ops/sampling.py); dead slots'
    # writes are dropped via the out-of-range sentinel either way, so the
    # pre-substitution logprob is observably identical
    with jax.named_scope(telemetry.ENGINE_SAMPLE):
        step_rng = jax.random.fold_in(rng, s.step)
    # outside every scope: the fused sampler's call must keep its name
    # (ops/sampling.py); the multi-pass path names its own work
    tok, logp = sample_with_logprob(
        step_rng, s.logits, temperature, top_p,
        top_p_impl=top_p_impl, capture_logprob=capture_logprobs,
    )
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        tok = jnp.where(s.done, pad_id, tok)
        row = jnp.where(alive, s.cand, total)  # `total` is out of range → dropped
        out = s.out.at[row, s.gen_lengths].set(tok, mode="drop")
        if capture_logprobs:
            logps_buf = s.logps_buf.at[row, s.gen_lengths].set(logp, mode="drop")
        else:
            logps_buf = s.logps_buf
        gen_lengths = s.gen_lengths + alive.astype(jnp.int32)
        lengths_buf = s.lengths_buf.at[row].set(gen_lengths, mode="drop")
        hit_eos = jnp.isin(tok, eos_ids) & alive
        done = s.done | hit_eos | (gen_lengths >= max_steps)

    cache = {
        "k": s.k_pages, "v": s.v_pages,
        "lengths": s.seq_lengths,
        "page_indices": s.page_indices,
        **_mixer_cache(s.mixer, alive),
    }
    next_logits, cache = forward(
        params, cfg, tok[:, None],
        positions=s.seq_lengths[:, None],
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_impl=paged_impl,
    )
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        seq_lengths = s.seq_lengths + alive.astype(jnp.int32)
        step = s.step + 1
        alive_steps = s.alive_steps + alive.sum().astype(jnp.int32)
    return _RefillState(
        step=step,
        alive_steps=alive_steps,
        out=out, logps_buf=logps_buf,
        lengths_buf=lengths_buf, cand=s.cand,
        done=done, logits=next_logits[:, 0], seq_lengths=seq_lengths,
        gen_lengths=gen_lengths, page_indices=s.page_indices,
        k_pages=cache["k"], v_pages=cache["v"],
        mixer=_mixer_from_cache(s.mixer, cache),
    )


def _refill_decode_chunk(params, lora, state: _RefillState, rng,
                         *, chunk: int, cfg: ModelConfig, page_size: int,
                         eos_ids, pad_id: int, temperature, top_p,
                         lora_scale: float, paged_impl: str, max_steps: int,
                         top_p_impl: str = "bisect",
                         capture_logprobs: bool = False):
    """``chunk`` refill decode steps in ONE dispatch via ``lax.scan`` — the
    dispatch-overhead lever (engine.py::_decode_chunk has the full
    rationale).

    Semantically identical to ``chunk`` host-dispatched steps: the host
    only ever intervenes (snapshot reads, admissions, grants, preemption)
    every ``check`` steps, and the caller sizes ``chunk`` as a DIVISOR of
    ``check`` (a non-divisor would stretch the host cadence past the
    budgeted pool's grant horizon), so no host decision point is ever
    skipped or delayed. Done slots are no-ops by construction (dropped
    writes, frozen lengths — see ``_refill_decode_step``), and all-done
    steps advance the per-step rng index exactly as host-dispatched
    steps would, so post-refill sampling sees identical fold_in indices.
    The body is deliberately unguarded: a ``lax.cond`` skip branch would
    double-buffer the carried page pools (scan_steps_guarded).

    Returns ``(state, done_copy, seq_lengths_copy)``: the host's
    admission/grant cadence consumes a (done, seq_lengths) snapshot at
    every ``check`` boundary, and the copies the old per-boundary
    ``jnp.copy`` dispatches made are FUSED into this program instead —
    fresh buffers (safe to read while ``state`` is donated into the
    next dispatch) at zero extra device round-trips, so a steady-state
    chunk leaves nothing on the host but the snapshot read itself."""
    def run(s):
        return _refill_decode_step(
            params, lora, s, rng, cfg=cfg, page_size=page_size,
            eos_ids=eos_ids, pad_id=pad_id, temperature=temperature,
            top_p=top_p, lora_scale=lora_scale, paged_impl=paged_impl,
            max_steps=max_steps,
            top_p_impl=top_p_impl,
            capture_logprobs=capture_logprobs,
        )

    state = scan_steps_guarded(run, state, chunk)
    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        return state, jnp.copy(state.done), jnp.copy(state.seq_lengths)


def _spec_decode_chunk(params, lora, state, rng, drafter_lora=None,
                       *, chunk: int, cfg: ModelConfig, page_size: int,
                       eos_ids, pad_id: int, temperature, top_p,
                       lora_scale: float, paged_impl: str, max_steps: int, draft_len: int, ngram_k: int,
                       drafter: str = "ngram", spec_verify: str = "fused",
                       hist_width: int = 0,
                       top_p_impl: str = "bisect",
                       capture_logprobs: bool = False):
    """``chunk`` speculative decode steps in ONE dispatch — same contract
    as ``_refill_decode_chunk`` (chunk divides the host cadence; all-done
    steps run unguarded as per-slot no-ops and advance the rng step
    index): the spec step is fully functional (draft proposal, verify
    forward, rejection sampling, emission all device-side), so fusing
    steps changes nothing the host's admission/grant/preempt logic can
    observe. Each fused step still emits 1..d+1 tokens, so the
    dispatch-overhead amortization COMPOUNDS with speculative
    acceptance. Returns ``(state, done_copy, seq_lengths_copy,
    draft_total_copy, accept_total_copy)`` — the fused steady-state
    snapshot (see ``_refill_decode_chunk``) plus the acceptance accounting
    the adaptive draft-length controller consumes at the same boundaries
    (emit_hist stays in ``state``; round-end stats read it directly)."""
    def run(s):
        return _spec_step(
            params, lora, s, rng, drafter_lora, cfg=cfg, page_size=page_size,
            eos_ids=eos_ids, pad_id=pad_id, temperature=temperature,
            top_p=top_p, lora_scale=lora_scale, paged_impl=paged_impl,
            max_steps=max_steps,
            draft_len=draft_len, ngram_k=ngram_k,
            drafter=drafter, spec_verify=spec_verify, hist_width=hist_width,
            top_p_impl=top_p_impl, capture_logprobs=capture_logprobs,
        )

    state = scan_steps_guarded(run, state, chunk)
    return (
        state, jnp.copy(state.done), jnp.copy(state.seq_lengths),
        jnp.copy(state.draft_total), jnp.copy(state.accept_total),
    )


def _spec_init(prompt_k, prompt_v, *, b: int, r_slots: int, total: int,
               max_steps: int, buf_width: int, pool_pages: int,
               hist_width: int,
               prompt_pages: int, private_pages: int, pad_id: int,
               shared_pages: int | None = None):
    """Empty R-slot speculative decode state (engine/speculative.py)."""
    from distrl_llm_tpu.engine.speculative import SpecRefillState

    base = _refill_init(
        prompt_k, prompt_v, b=b, r_slots=r_slots, total=total,
        max_steps=max_steps, vocab=1, pool_pages=pool_pages,
        prompt_pages=prompt_pages, private_pages=private_pages, pad_id=pad_id,
        shared_pages=shared_pages,
    )
    return SpecRefillState(
        step=base.step, alive_steps=base.alive_steps,
        out=base.out, logps_buf=base.logps_buf,
        lengths_buf=base.lengths_buf,
        cand=base.cand, done=base.done,
        last_tok=jnp.zeros((r_slots,), jnp.int32),
        seq_buf=jnp.zeros((r_slots, buf_width), jnp.int32),
        seq_lengths=base.seq_lengths, gen_lengths=base.gen_lengths,
        page_indices=base.page_indices,
        k_pages=base.k_pages, v_pages=base.v_pages,
        emit_hist=jnp.zeros((hist_width,), jnp.int32),
        draft_total=jnp.zeros((), jnp.int32),
        accept_total=jnp.zeros((), jnp.int32),
    )


def _spec_admit(state, new_cand, admit_mask, last_logits, real_len,
                packed_ids, rng, temperature, top_p, dst_partial,
                src_partial=None, copy_mask=None,
                *, n: int, b: int, prompt_pages: int, page_size: int,
                eos_ids, top_p_impl: str = "bisect",
                capture_logprobs: bool = False):
    """Admit candidates into slots (speculative flavor): beyond the page-table
    work shared with ``_refill_admit``, sample each admitted slot's FIRST
    token from its prompt's prefill logits (the spec step carries a pending
    token, not logits), seed the n-gram sequence buffer with the packed
    prompt, and write that first token as generated output."""
    from distrl_llm_tpu.engine.speculative import SpecRefillState
    from distrl_llm_tpu.ops.sampling import sample_with_logprob

    s = state
    total = b * n
    with jax.named_scope(telemetry.ENGINE_ADMIT):
        cand, live_new, prompt_of, recopy = _admit_tables(
            s, new_cand, admit_mask, real_len, dst_partial, n=n, b=b,
            prompt_pages=prompt_pages, page_size=page_size,
            src_partial=src_partial, copy_mask=copy_mask,
        )
        first_logits = last_logits[prompt_of]

    # first token per admitted slot, from the prompt's last-position logits
    # (fused sample+logprob when enabled — ops/sampling.py; the rejection-
    # sampling accept path in _spec_step is untouched). Outside the scope:
    # the fused sampler's call must keep its name
    tok0, logp0 = sample_with_logprob(
        rng, first_logits, temperature, top_p,
        top_p_impl=top_p_impl, capture_logprob=capture_logprobs,
    )
    with jax.named_scope(telemetry.ENGINE_ADMIT):
        hit_eos = jnp.isin(tok0, eos_ids)
        done = jnp.where(admit_mask, ~live_new | hit_eos, s.done)

        # n-gram buffer: packed prompt then tok0 at position real_len
        w = s.seq_buf.shape[1]
        p_len = packed_ids.shape[1]
        seq_rows = jnp.pad(packed_ids[prompt_of], ((0, 0), (0, w - p_len)))
        rl = real_len[prompt_of]
        seq_rows = jnp.where(
            jnp.arange(w)[None, :] == rl[:, None], tok0[:, None], seq_rows
        )
        seq_buf = jnp.where(admit_mask[:, None], seq_rows, s.seq_buf)

        # tok0 is generated output: out[cand, 0] and per-candidate length 1
        row = jnp.where(admit_mask & live_new, cand, total)
        out = s.out.at[row, 0].set(tok0, mode="drop")
        if capture_logprobs:
            logps_buf = s.logps_buf.at[row, 0].set(logp0, mode="drop")
        else:
            logps_buf = s.logps_buf
        lengths_buf = s.lengths_buf.at[row].set(1, mode="drop")

        return SpecRefillState(
            step=s.step,
            alive_steps=s.alive_steps,
            out=out,
            logps_buf=logps_buf,
            lengths_buf=lengths_buf,
            cand=cand,
            done=done,
            last_tok=jnp.where(admit_mask, tok0, s.last_tok),
            seq_buf=seq_buf,
            seq_lengths=jnp.where(admit_mask, real_len[prompt_of], s.seq_lengths),
            gen_lengths=jnp.where(admit_mask, 1, s.gen_lengths),
            page_indices=s.page_indices,
            k_pages=tuple(recopy(x) for x in s.k_pages),
            v_pages=tuple(recopy(x) for x in s.v_pages),
            emit_hist=s.emit_hist,
            draft_total=s.draft_total,
            accept_total=s.accept_total,
        )


def _self_draft(params, drafter_lora, state, step_rng, *, cfg: ModelConfig,
                page_size: int, lora_scale: float, paged_impl: str,
                d: int, temperature, top_p,
                top_p_impl: str):
    """Online self-drafting: run the policy's own PREVIOUS LoRA version (the
    LoraMailbox swap log's superseded adapter) as the draft model — d
    autoregressive single-token decode steps over the SAME paged cache.

    The drafter's KV writes at positions seq_lengths..seq_lengths+d−1 are
    TRANSIENT: the verify forward re-processes the whole block under the
    target adapter and overwrites every one of them, so accepted positions
    always hold target-version KV (the drafter attending its own in-draft
    KV is the stale-KV mixture in-flight updating already embraces — and
    exactness never depends on it: the acceptance test consumes q as the
    distribution the draft was ACTUALLY sampled from, which this returns).

    Returns (draft [R, d], draft_probs q [R, d, V], k_pages, v_pages)."""
    from distrl_llm_tpu.engine.speculative import sampling_probs

    s = state
    k_pages, v_pages = s.k_pages, s.v_pages
    tok = s.last_tok
    drafts, qs = [], []
    for i in range(d):
        cache = {
            "k": k_pages, "v": v_pages,
            "lengths": s.seq_lengths + i,
            "page_indices": s.page_indices,
        }
        logits, cache = forward(
            params, cfg, tok[:, None],
            positions=(s.seq_lengths + i)[:, None],
            lora=drafter_lora, lora_scale=lora_scale,
            kv_cache=cache, page_size=page_size, paged_impl=paged_impl,
        )
        k_pages, v_pages = cache["k"], cache["v"]
        q_i = sampling_probs(
            logits[:, 0], temperature, top_p, top_p_impl=top_p_impl
        )  # [R, V] — the proposal distribution, exact (greedy → one-hot)
        tok = jax.random.categorical(
            jax.random.fold_in(step_rng, 7_000_000 + i),
            jnp.log(jnp.maximum(q_i, 1e-30)),
        ).astype(jnp.int32)
        drafts.append(tok)
        qs.append(q_i)
    return (
        jnp.stack(drafts, axis=1), jnp.stack(qs, axis=1), k_pages, v_pages,
    )


def _spec_step(params, lora, state, rng, drafter_lora=None, *,
               cfg: ModelConfig, page_size: int,
               eos_ids, pad_id: int, temperature, top_p, lora_scale: float,
               paged_impl: str, max_steps: int, draft_len: int, ngram_k: int,
               drafter: str = "ngram", spec_verify: str = "fused",
               hist_width: int = 0,
               top_p_impl: str = "bisect", capture_logprobs: bool = False):
    """One speculative decode step: propose d draft tokens (n-gram prompt
    lookup, or the previous-version policy itself — ``drafter``), verify
    [last_tok, draft] in one (d+1)-position forward whose attention runs as
    ONE fused blocked sweep (``spec_verify="fused"``; "unrolled" forces the
    per-position dispatch fan-out), accept by rejection sampling, and emit
    1..d+1 tokens (engine/speculative.py)."""
    from distrl_llm_tpu.engine.speculative import (
        SpecRefillState, propose_ngram_drafts, sampling_probs, spec_accept,
    )

    s = state
    total = s.out.shape[0]
    d = draft_len
    alive = ~s.done
    buf_len = s.seq_lengths + 1  # resident + the pending last_tok
    step_rng = jax.random.fold_in(rng, s.step)

    if drafter == "self":
        draft, draft_probs, k_pages, v_pages = _self_draft(
            params, drafter_lora if drafter_lora is not None else lora,
            s, step_rng, cfg=cfg, page_size=page_size,
            lora_scale=lora_scale, paged_impl=paged_impl,
            d=d,
            temperature=temperature, top_p=top_p, top_p_impl=top_p_impl,
        )
    else:
        draft = propose_ngram_drafts(s.seq_buf, buf_len, k=ngram_k, d=d)
        draft_probs = None
        k_pages, v_pages = s.k_pages, s.v_pages
    inputs = jnp.concatenate([s.last_tok[:, None], draft], axis=1)  # [R, d+1]
    positions = s.seq_lengths[:, None] + jnp.arange(d + 1)[None, :]
    cache = {
        "k": k_pages, "v": v_pages,
        "lengths": s.seq_lengths,
        "page_indices": s.page_indices,
    }
    logits, cache = forward(
        params, cfg, inputs, positions=positions,
        lora=lora, lora_scale=lora_scale,
        kv_cache=cache, page_size=page_size, paged_impl=paged_impl,
        paged_verify=True,
        paged_verify_impl=spec_verify,
    )  # [R, d+1, V]
    with jax.named_scope(telemetry.ENGINE_SAMPLE):
        probs = sampling_probs(logits, temperature, top_p, top_p_impl=top_p_impl)
        emit, n_emit, n_accept = spec_accept(step_rng, probs, draft, draft_probs)

    with jax.named_scope(telemetry.ENGINE_BOOKKEEPING):
        # EOS truncation: emission stops AT the first EOS among emitted tokens
        pos = jnp.arange(d + 1)[None, :]
        is_eos = jnp.isin(emit, eos_ids) & (pos < n_emit[:, None])
        any_eos = is_eos.any(axis=1)
        first_eos = jnp.argmax(is_eos, axis=1)
        n_emit = jnp.where(any_eos, first_eos + 1, n_emit)
        # cap at the per-candidate token budget
        room = jnp.maximum(max_steps - s.gen_lengths, 0)
        n_emit = jnp.minimum(n_emit, room)
        n_emit = jnp.where(alive, n_emit, 0)

        gen_lengths = s.gen_lengths + n_emit
        done = s.done | (alive & (any_eos | (gen_lengths >= max_steps)))

        # scatter the emitted tokens into out / seq_buf (static d+1 writes)
        out = s.out
        logps_buf = s.logps_buf
        seq_buf = s.seq_buf
        row = jnp.where(alive, s.cand, total)  # `total` → dropped
        for i in range(d + 1):
            live_i = i < n_emit
            row_i = jnp.where(live_i, row, total)
            out = out.at[row_i, s.gen_lengths + i].set(emit[:, i], mode="drop")
            if capture_logprobs:
                # behavior logprob on the RAW basis (same convention as every
                # other decode path): the verify logits at slot i judge/sample
                # emit[:, i]
                logp_i = token_logprob(logits[:, i], emit[:, i])
                logps_buf = logps_buf.at[row_i, s.gen_lengths + i].set(
                    logp_i, mode="drop"
                )
            slot_i = jnp.where(live_i, jnp.arange(row.shape[0]), row.shape[0])
            seq_buf = seq_buf.at[slot_i, buf_len + i].set(emit[:, i], mode="drop")
        lengths_buf = s.lengths_buf.at[row].set(gen_lengths, mode="drop")

        last_tok = jnp.where(
            alive,
            jnp.take_along_axis(
                emit, jnp.clip(n_emit - 1, 0, d)[:, None], axis=1
            )[:, 0],
            s.last_tok,
        )
        seq_lengths = s.seq_lengths + n_emit
        # acceptance accounting: one [d_max+2]-bucket histogram increment per
        # step (device-side — the host reads it at snapshot boundaries / round
        # end, never per step). hist_width is the CONFIGURED max draft length's
        # width, so adaptive shrink (draft_len < max) changes no shapes.
        hw = hist_width or (d + 2)
        hist_inc = (
            (n_emit[:, None] == jnp.arange(hw)[None, :]) & alive[:, None]
        ).astype(jnp.int32).sum(axis=0)
        return SpecRefillState(
            step=s.step + 1,
            alive_steps=s.alive_steps + alive.sum().astype(jnp.int32),
            out=out, logps_buf=logps_buf,
            lengths_buf=lengths_buf, cand=s.cand,
            done=done, last_tok=last_tok, seq_buf=seq_buf,
            seq_lengths=seq_lengths, gen_lengths=gen_lengths,
            page_indices=s.page_indices,
            k_pages=cache["k"], v_pages=cache["v"],
            emit_hist=s.emit_hist + hist_inc,
            draft_total=s.draft_total + d * alive.sum().astype(jnp.int32),
            accept_total=(
                s.accept_total
                + jnp.where(alive, n_accept, 0).sum().astype(jnp.int32)
            ),
        )


class PagedGenerationEngine(LoraMailbox):
    """Drop-in for ``GenerationEngine`` with a packed paged KV cache."""

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
        attn_impl: str = "reference",
        paged_impl: str = "auto",
        # None = DEFAULT_PAGE_SIZE (128), or one block where the model has
        # block-sparse layers (they attend by page); an explicit value that
        # such a model cannot run under is refused, never replaced
        page_size: int | None = None,
        decode_chunk: int = 128,
        # "none" | "int8" (per-token absmax KV cache, compact-scales Pallas
        # variants). None = consult the autotune plan DB
        # (ExecutionPlan.kv_format; empty DB = "none", byte-identical to
        # the historical default); an explicit value — including "none" —
        # always wins (the decode_scan_chunk convention)
        kv_quant: str | None = None,
        prompt_buckets: Sequence[int] | None = None,  # accepted for interface parity
        max_concurrent_rows: int = 0,  # 0 = unlimited (vLLM max_num_seqs)
        max_kv_pages: int = 0,  # refill decode-page pool size; 0 = worst-case
        scheduler: str = "waves",  # "waves" | "refill" (continuous batching)
        # prefix sharing (ISSUE 12): a group's N candidates ALIAS one
        # refcounted prompt-prefix page chain (copy-on-write tail split)
        # instead of each keeping a private partial-page copy against a
        # never-freed static region; finished groups' prompt pages recycle
        # into decode capacity. Refill scheduler only.
        prefix_sharing: bool = False,
        # continuous admission (ISSUE 12): replace the fixed-episode-batch
        # prefill with a group request queue — each group's prompt is
        # prefilled lazily into pool-allocated chain pages when freed slots
        # and page budget admit it, so short completions backfill
        # immediately. Implies prefix_sharing. None = consult the autotune
        # plan DB (cb_mode field; empty DB = off); an explicit bool —
        # including False — always wins (the decode_scan_chunk convention).
        continuous_admission: bool | None = None,
        # tiered KV cache (ISSUE 18). Tier 1: a cross-request radix prefix
        # index over the continuous-admission pool — admissions
        # longest-prefix-match their token ids against previously finished
        # chains, alias every matched full page refcounted, and prefill
        # only the un-cached suffix (SGLang RadixAttention-style; multi-turn
        # re-admission of a conversation's history costs zero prefill).
        # None = consult the autotune plan DB (prefix_cache field; empty
        # DB = off); an explicit bool — including False — always wins (the
        # decode_scan_chunk convention). Requires continuous admission.
        prefix_cache: bool | None = None,
        # Tier 2: evicted cache nodes and preempted chains park their KV
        # pages (int8 payload + scales travel as-is — the PR 15 quant
        # transport idiom) in a host-RAM page store on a background thread
        # and page back in on re-match/resume, bit-exact. Explicit-only:
        # host-memory geometry is a deployment fact, not a measured plan
        # field. Requires prefix_cache; speculative chains resume by
        # recompute instead (their draft state is not spillable).
        kv_spill: bool = False,
        kv_spill_host_mb: int = 0,  # host store byte cap; 0 = unbounded
        # speculative decoding (engine/speculative.py). None = consult the
        # autotune plan DB (spec_draft_len/spec_ngram_k/spec_drafter/
        # spec_verify plan fields; empty DB falls back to the historical
        # defaults: off / k=2 / "ngram" / "fused"); an explicit value —
        # including spec_draft=0 — always wins.
        spec_draft: int | None = None,  # >0: speculative, d draft tokens
        spec_ngram: int | None = None,  # lookup n-gram size
        spec_drafter: str | None = None,  # "ngram" | "self" (prev-LoRA policy)
        spec_verify: str | None = None,  # "fused" | "unrolled" verify sweep
        # acceptance-rate-driven draft-length adaptation: shrink the
        # effective d (halving, floor 1) when the accept-rate EMA says
        # drafts are being wasted, grow it back when acceptance recovers
        spec_adapt: bool = False,
        # None = consult the autotune plan DB (falls back to 0, the
        # historical default); an explicit int — including 0 — always wins
        scan_chunk: int | None = None,
        capture_logprobs: bool = False,  # record behavior logprobs (clip_ratio)
        autotune: bool = True,  # False pins the static defaults (no DB read)
        plan_db: str | None = None,  # plan-DB path; None = env/default path
        plan_rows: int = 0,  # expected rows for plan-KEY selection (0 = any)
    ):
        self.max_concurrent_rows = max_concurrent_rows
        self.capture_logprobs = capture_logprobs
        if scan_chunk is not None and scan_chunk < 0:
            raise ValueError(f"scan_chunk must be >= 0, got {scan_chunk}")
        if kv_quant not in (None, "none", "int8"):
            # validated BEFORE plan resolution so a typo'd kwarg fails with
            # the engine's own contract, not a plan-field error
            raise ValueError(f"kv_quant must be none/int8, got {kv_quant!r}")
        # Execution-plan resolution (distrl_llm_tpu/autotune): explicit
        # kwargs win, a stored measured plan fills the rest, no DB entry =
        # the static defaults byte-identically. decode_path is pinned to
        # what this construction actually is (honest trace records).
        from distrl_llm_tpu.autotune import resolve_plan

        requested: dict[str, Any] = {}
        if spec_draft is not None:
            requested["decode_path"] = "speculative" if spec_draft else "paged"
            requested["spec_draft_len"] = spec_draft
        elif scheduler != "refill" or not max_concurrent_rows:
            # only a refill engine can host a stored speculative plan (it
            # needs the slot scheduler); everything else pins the paged
            # path so a spec DB entry is treated as a decode-path miss
            requested["decode_path"] = "paged"
        else:
            # refill with spec unpinned: this engine can host "paged" OR
            # "speculative" — which one is exactly what the DB decides.
            # The tuple is a CONSTRAINT, not a pin: a stored entry from any
            # OTHER path (e.g. dense) is still a wholesale miss — its
            # scan_chunk/top_p were never measured here — and with no
            # entry the first element ("paged") is the default path
            requested["decode_path"] = ("paged", "speculative")
        if spec_ngram is not None:
            requested["spec_ngram_k"] = spec_ngram
        if spec_drafter is not None:
            requested["spec_drafter"] = spec_drafter
        if spec_verify is not None:
            requested["spec_verify"] = spec_verify
        if scan_chunk is not None:
            requested["scan_chunk"] = scan_chunk
        if continuous_admission is not None:
            # explicit bool pins the admission regime past any stored plan
            # (False is a real A/B control, not "unset")
            requested["cb_mode"] = (
                "continuous" if continuous_admission else "batch"
            )
        if kv_quant is not None:
            # explicit "none" is a real pin (the int8-default A/B control)
            requested["kv_format"] = kv_quant
        if prefix_cache is not None:
            # explicit False pins "off" past any stored plan (the cache-off
            # A/B control must never be silently re-armed by the DB)
            requested["prefix_cache"] = "on" if prefix_cache else "off"
        if kv_spill_host_mb < 0:
            raise ValueError(
                f"kv_spill_host_mb must be >= 0, got {kv_spill_host_mb}"
            )
        self.resolved_plan = resolve_plan(
            model_cfg=cfg, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens, rows=plan_rows,
            requested=requested, db_path=plan_db, enabled=autotune,
        )
        scan_chunk = self.resolved_plan.plan.scan_chunk
        self.plan_top_p_impl = self.resolved_plan.plan.top_p_impl
        self.scan_chunk = scan_chunk
        self._chunk_compiled: dict = {}
        self._chunk_mu = threading.Lock()
        if scheduler not in ("waves", "refill"):
            raise ValueError(f"scheduler must be waves/refill, got {scheduler!r}")
        if scheduler == "refill" and not max_concurrent_rows:
            # without a cap the refill path never engages and rounds would
            # silently run as one unlimited wave while reporting "refill"
            raise ValueError(
                "scheduler='refill' requires max_concurrent_rows (the decode "
                "slot count)"
            )
        # spec knobs post-resolution: plan fields hold user > DB > default
        # (resolve_plan). A system-written speculative plan carries
        # decode_path="speculative", so on a non-refill engine it is
        # dropped WHOLESALE by resolve_plan's decode-path mismatch (this
        # constructor pins requested decode_path="paged" above) and never
        # reaches here; the warning branch below additionally guards
        # hand-edited/inconsistent DB entries (a "paged" entry carrying a
        # nonzero spec_draft_len) — a stored plan must never crash or
        # silently reshape a run, while the same value passed EXPLICITLY
        # still raises below.
        plan = self.resolved_plan.plan
        spec_explicit = spec_draft is not None
        spec_draft = (
            spec_draft if spec_explicit else plan.spec_draft_len
        )
        if spec_draft and not spec_explicit and scheduler != "refill":
            import logging

            logging.getLogger(__name__).warning(
                "autotune: stored plan wants speculative decoding "
                "(spec_draft_len=%d) but this engine runs the %s scheduler "
                "— ignoring the plan's spec fields",
                spec_draft, scheduler,
            )
            spec_draft = 0
        if spec_draft and scheduler != "refill":
            raise ValueError(
                "spec_draft (speculative decoding) runs on the refill "
                "scheduler — set scheduler='refill' and max_concurrent_rows"
            )
        spec_ngram = (
            spec_ngram if spec_ngram is not None
            else (plan.spec_ngram_k or 2)
        )
        if spec_draft < 0 or spec_draft > 16:
            raise ValueError(
                f"spec_draft must be in [0, 16] (draft blocks beyond 16 "
                f"positions waste verify width faster than they amortize "
                f"weight reads), got {spec_draft}"
            )
        if spec_ngram < 1:
            raise ValueError(f"bad spec config: d={spec_draft}, k={spec_ngram}")
        self.spec_draft = spec_draft
        self.spec_ngram = spec_ngram
        self.spec_drafter = spec_drafter or plan.spec_drafter or "ngram"
        if self.spec_drafter not in ("ngram", "self"):
            raise ValueError(
                f"spec_drafter must be ngram/self, got {self.spec_drafter!r}"
            )
        self.spec_verify = spec_verify or plan.spec_verify or "fused"
        if self.spec_verify not in ("fused", "unrolled"):
            raise ValueError(
                f"spec_verify must be fused/unrolled, got {self.spec_verify!r}"
            )
        if spec_adapt and not spec_draft:
            if spec_explicit:
                raise ValueError(
                    "spec_adapt adapts the speculative draft length — it "
                    "requires spec_draft > 0"
                )
            # unpinned spec_draft resolved to 0 (no speculative plan for
            # this geometry in the DB): the same command line must not
            # crash on one host and run on another — same never-crash
            # policy as the scheduler-mismatch branch above
            import logging

            logging.getLogger(__name__).warning(
                "spec_adapt requested but spec_draft resolved to 0 from "
                "this host's plan DB (no speculative plan stored for the "
                "geometry) — the draft-length controller is inert this run"
            )
            spec_adapt = False
        self.spec_adapt = spec_adapt
        # only the self drafter consumes the mailbox's superseded-adapter
        # slot; leave retention off otherwise (it pins an extra adapter
        # version in device memory for the engine's lifetime)
        self._track_prev_lora = bool(spec_draft) and self.spec_drafter == "self"
        # ---- continuous-batching admission + prefix sharing (ISSUE 12)
        cb_explicit = continuous_admission is not None
        cont = (
            continuous_admission if cb_explicit
            else plan.cb_mode == "continuous"
        )
        if cont and (scheduler != "refill" or not max_concurrent_rows):
            if cb_explicit:
                raise ValueError(
                    "continuous_admission runs on the refill scheduler — "
                    "set scheduler='refill' and max_concurrent_rows"
                )
            # a stored plan must never crash or silently reshape a run the
            # engine can't host (the spec-plan scheduler-mismatch policy)
            import logging

            logging.getLogger(__name__).warning(
                "autotune: stored plan wants continuous admission "
                "(cb_mode='continuous') but this engine runs the %s "
                "scheduler — ignoring the plan's cb_mode", scheduler,
            )
            cont = False
        if cont:
            # continuous admission allocates prompt chains from the
            # refcounted pool — it IS prefix sharing plus lazy prefill
            prefix_sharing = True
        if prefix_sharing and (scheduler != "refill" or not max_concurrent_rows):
            raise ValueError(
                "prefix_sharing shares prompt-prefix pages across the "
                "refill scheduler's slots — set scheduler='refill' and "
                "max_concurrent_rows"
            )
        self.prefix_sharing = bool(prefix_sharing)
        self.continuous_admission = bool(cont)
        # the scheduler self-description telemetry record (the wave
        # path reports "waves" regardless; generate() stamps last_cb_mode
        # with what each round actually ran)
        self.cb_mode = (
            "waves" if scheduler == "waves" else (
                "continuous" if cont
                else ("refill_shared" if prefix_sharing else "refill")
            )
        )
        self.last_cb_mode: str | None = None
        # post-resolution KV format (explicit kwarg already won per-field
        # via the requested dict; unset adopts the stored plan, default
        # "none" — the historical behavior, byte-identical on an empty DB)
        kv_quant = kv_quant if kv_quant is not None else (
            plan.kv_format or "none"
        )
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be none/int8, got {kv_quant!r}")
        self.kv_quant = kv_quant
        if cfg.looped:
            # a looped model runs through the wave and refill schedulers, held
            # to the reference there; what re-derives, parks or re-reads K/V by
            # another path has not been, and refuses by name
            for asked, what in (
                (kv_quant != "none", f"kv_quant={kv_quant!r} (an int8 KV pool)"),
                (spec_draft, "spec_draft (speculative decoding)"),
                (prefix_sharing, "prefix_sharing / continuous_admission "
                                 "(pool-allocated prompt chains)"),
                (max_kv_pages, "max_kv_pages (a budgeted pool preempts by re-prefill)"),
                (prefix_cache, "prefix_cache (the radix cache over K/V pages)"),
                (kv_spill, "kv_spill (K/V pages parked in host memory)"),
            ):
                if asked:
                    cfg.refuse_looped(what)
        if cfg.hybrid:
            # a model whose layers differ in kind (sparse + lightning): each
            # slot also holds a recurrent state and a selector cache, handed
            # over at admission. What re-derives or moves K/V alone refuses.
            if kv_quant != "none":
                cfg.refuse_hybrid(f"kv_quant={kv_quant!r} (an int8 KV pool)")
            if spec_draft:
                cfg.refuse_hybrid("spec_draft (speculative decoding)")
            if prefix_sharing:
                cfg.refuse_hybrid(
                    "prefix_sharing / continuous_admission (pool-allocated "
                    "prompt chains)")
            if max_kv_pages:
                cfg.refuse_hybrid(
                    "max_kv_pages (a budgeted pool preempts by re-prefill)")
            block = cfg.sparse_block_size if cfg.kind_count("sparse") else None
            if block and page_size not in (None, block):
                raise ValueError(
                    f"page_size={page_size} cannot hold a model with "
                    f"{cfg.mixer_names} layers: its block-sparse layers attend "
                    f"by page, so a page is one block of {block} tokens "
                    f"(sparse_block_size). Pass page_size={block} or leave it out."
                )
            page_size = page_size or block
        if page_size is None:
            page_size = DEFAULT_PAGE_SIZE
        # ---- tiered KV cache (ISSUE 18) resolution: tier 1 aliases cached
        # chains out of the continuous-admission pool, so it inherits the
        # cb_mode policy verbatim — explicit wins (including False), a
        # stored plan the engine can't host degrades with a warning, the
        # same value passed explicitly raises
        pc_explicit = prefix_cache is not None
        pcache = (
            prefix_cache if pc_explicit else plan.prefix_cache == "on"
        )
        if pcache and kv_quant == "int8":
            # int8 pages are QUANTIZED rewrites of the in-flight k/v, so a
            # warm suffix prefill over cached pages could never be
            # bit-identical to the packed cold prefill (which attends the
            # un-quantized in-flight values) — the cache's core contract
            if pc_explicit:
                raise ValueError(
                    "prefix_cache requires a lossless KV pool "
                    "(kv_quant='none'): int8 pages cannot reproduce the "
                    "cold prefill's attention inputs bit-exactly"
                )
            import logging

            logging.getLogger(__name__).warning(
                "autotune: stored plan wants the radix prefix cache "
                "(prefix_cache='on') but the KV pool is int8-quantized — "
                "ignoring the plan's prefix_cache"
            )
            pcache = False
        if pcache and not cont:
            if pc_explicit:
                raise ValueError(
                    "prefix_cache aliases cached prompt chains out of the "
                    "continuous-admission pool — set "
                    "continuous_admission=True (refill scheduler with "
                    "max_concurrent_rows)"
                )
            import logging

            logging.getLogger(__name__).warning(
                "autotune: stored plan wants the radix prefix cache "
                "(prefix_cache='on') but this engine does not run "
                "continuous admission — ignoring the plan's prefix_cache"
            )
            pcache = False
        self.prefix_cache = bool(pcache)
        if self.prefix_cache:
            cfg.refuse_hybrid("prefix_cache (the radix cache over K/V pages)")
        if kv_spill:
            cfg.refuse_hybrid("kv_spill (K/V pages parked in host memory)")
        if kv_spill and not self.prefix_cache:
            raise ValueError(
                "kv_spill parks KV pages through the tiered cache's host "
                "store — it requires prefix_cache=True"
            )
        if kv_spill and spec_draft:
            raise ValueError(
                "kv_spill restores raw decode cursors the speculative "
                "scheduler does not expose (draft history, acceptance "
                "state) — preempted speculative chains already resume by "
                "recompute; drop kv_spill or spec_draft"
            )
        self.kv_spill = bool(kv_spill)
        # honesty: the record in resolved_plan must describe what this
        # engine actually is (generate() routes on spec_draft/scheduler,
        # not on the plan record), including when the decode_path came
        # from DEFAULT_PLAN ("dense" — never true of this class)
        self.resolved_plan = self.resolved_plan._replace(
            plan=plan.replace(
                decode_path="speculative" if spec_draft else "paged",
                spec_draft_len=spec_draft,
                spec_ngram_k=spec_ngram if spec_draft else 0,
                spec_drafter=self.spec_drafter if spec_draft else None,
                spec_verify=self.spec_verify if spec_draft else None,
                # what actually runs: a degraded stored "continuous" plan
                # records "batch", an explicit pin keeps its spelling
                cb_mode=(
                    "continuous" if cont
                    else ("batch" if plan.cb_mode is not None else None)
                ),
                prefix_cache=(
                    "on" if pcache
                    else ("off" if plan.prefix_cache is not None else None)
                ),
            )
        )
        self.scheduler = scheduler
        self.cfg = cfg
        self.max_prompt_tokens = max_prompt_tokens
        self.max_new_tokens = max_new_tokens
        cfg.check_within_window(max_prompt_tokens + max_new_tokens)
        self.page_size = page_size
        self.prompt_pages = pages_per_seq(max_prompt_tokens, page_size)
        # per-candidate private region: the partial prompt page (extended in
        # place by decode) + decode pages; full prompt pages are SHARED.
        # Speculative verify writes up to spec_draft KVs PAST a row's final
        # position (the unaccepted tail of the last draft block) — they must
        # land in scratch pages, not clamp onto valid resident KV (review
        # finding: near-budget rows otherwise corrupt their own cache).
        self.private_pages = 1 + pages_per_seq(
            max_new_tokens + max(spec_draft, 0), page_size
        )
        # page BUDGET for the refill scheduler's decode pool (vLLM's block
        # pool behind gpu_memory_utilization / --actor_gpu_usage): 0 means
        # worst-case provisioning (one private region per slot — admission
        # can never stall and preemption never fires); a smaller budget makes
        # KV HBM scale with REALIZED lengths, with admission gated on free
        # pages and preempt-by-recompute under pressure
        # continuous admission allocates prompt chains FROM the pool, so the
        # single-sequence floor additionally carries one prompt chain
        # the tiered cache keeps warm chains resident in the SAME pool, so
        # its floor carries one extra prompt chain (mirrors budget.py's
        # kv_pool_pages(prefix_cache=True) clamp)
        pool_floor = 1 + self.private_pages + (
            self.prompt_pages if self.continuous_admission else 0
        ) + (self.prompt_pages if self.prefix_cache else 0)
        if max_kv_pages and max_kv_pages < pool_floor:
            raise ValueError(
                f"max_kv_pages={max_kv_pages} cannot fit one sequence "
                f"(need >= {pool_floor}: scratch + "
                f"{self.private_pages} private pages"
                + (f" + {self.prompt_pages} prompt-chain pages for "
                   f"continuous admission" if self.continuous_admission
                   else "")
                + (f" + {self.prompt_pages} resident-cache pages for "
                   f"prefix_cache" if self.prefix_cache else "")
                + ")"
            )
        if (
            max_kv_pages and self.continuous_admission
            and max_kv_pages < pool_floor + self.private_pages
        ):
            # above the hard floor but wedge-prone (ISSUE 19 satellite, the
            # max_kv_pages<=16 gotcha): a budget that cannot hold the
            # head group's chain plus TWO private regions serializes every
            # admission behind a full drain, and a mid-round decline with
            # no live slot trips the wedge detector. Warn at build time
            # with the number, don't wait for the round to stall.
            import warnings

            warnings.warn(
                f"max_kv_pages={max_kv_pages} is wedge-prone under "
                f"continuous admission: the pool fits one sequence (floor "
                f"{pool_floor}) but cannot overlap the next admission's "
                f"private region — minimum comfortable budget is "
                f"{pool_floor + self.private_pages} pages "
                f"({pool_floor} floor + {self.private_pages} private)",
                RuntimeWarning, stacklevel=2,
            )
        self.max_kv_pages = max_kv_pages
        self.last_pool_stats: dict | None = None
        # request-level serving observability (ISSUE 13): when an owner
        # (trainer --serving_obs, worker --serving-obs)
        # attaches a serving_obs.ServingLedger here, the refill/spec/
        # continuous loops emit per-group lifecycle events and the
        # admission audit at host chunk boundaries. None = every hook site
        # is one attribute check — the telemetry-off fast path and the
        # byte-identity pins are untouched (the ledger observes, never
        # schedules)
        self.serving_ledger: Any = None
        # closed-loop admission limits (ISSUE 14): when an owner (trainer
        # --control, worker --control) attaches a
        # control.ControlLimits here, the continuous-admission loop
        # consults it — the HBM governor's chain-cap scale and the SLO
        # shedder's shed gate. None = one attribute check per admission
        # pass; a handle at its defaults makes byte-identical decisions
        # (pinned in tests/test_control.py)
        self.control_limits: Any = None
        # multi-turn episode continuation (ISSUE 17): when an owner (trainer
        # env driver) attaches a turn hook here, the refill
        # idle pass consults it before retiring a finished candidate —
        # ``hook(cand_id, gen_tokens) -> np.ndarray | None`` returns
        # observation tokens to append in place (KV chain stays resident) or
        # None to finish; ``hook.declined(cand_id)`` unwinds an accepted
        # observation the engine could not seat. None = one attribute check
        # per idle pass — single-turn rounds and byte-identity pins untouched
        self.turn_hook: Any = None
        # multi-tenant gateway identity (ISSUE 19): when a gateway owner
        # attaches per-round tenancy here, the continuous-admission loop
        # schedules by priority class. ``round_meta`` maps group index ->
        # {"tenant", "cls", "rank", "seq", "arrival_ts", "trace_ctx"};
        # ``quota_book`` is a gateway.TenantQuotaBook consulted (charge at
        # admission, credit at group finish) with the ``quota`` stall
        # reason on decline; ``stream_hook`` is ``fn(cand, token_list)``
        # called with newly visible tokens at host boundaries plus a
        # byte-complete final flush at round end. All three default None =
        # one attribute check per site — non-gateway rounds and the
        # byte-identity pins are untouched (pinned in tests/test_gateway.py)
        self.round_meta: Any = None
        self.quota_book: Any = None
        self.stream_hook: Any = None
        # per-round speculative stats (refill spec rounds only): drafter,
        # realized accept rate, tokens/verify-step, emit histogram, verify
        # kernel choice + grid steps, draft/target version bookkeeping
        self.last_spec_stats: dict | None = None
        # per-round prefill/decode timing + token counts (telemetry:
        # accumulate_round_stats); snapshotted by the trainer per round
        self.last_round_stats: dict | None = None
        # in-flight weight-update mailbox (LoraMailbox base)
        self.last_swap_steps: list[int] = []
        self.last_swap_versions: list[int | None] = []
        self.eos_ids = jnp.asarray(list(eos_token_ids), jnp.int32)
        self.pad_id = int(pad_token_id)
        self.lora_scale = lora_scale
        self.decode_chunk = decode_chunk
        self.paged_impl = paged_impl
        self.prompt_buckets = [max_prompt_tokens]
        # continuous admission builds per-layer 0-page tiles in this dtype
        # and reuses the jitted prefill at [1, P]
        self.cache_dtype = cache_dtype
        # tiered-KV engine state (ISSUE 18): the radix index and host page
        # store are ENGINE-owned — they outlive the per-round PagePool, so
        # warm prefixes survive into the next round's admissions; each
        # round's pool attaches to them and round end flushes residency to
        # the store (page ids are round-scoped, payloads are not). The
        # adapter identity guard invalidates the WHOLE cache whenever the
        # LoRA the KV was computed under changes (cached KV is only exact
        # for the adapter that wrote it — a strong reference keeps the
        # identity test sound against id() reuse).
        if self.prefix_cache:
            from distrl_llm_tpu.engine.page_pool import (
                HostPageStore, RadixCache,
            )

            self._radix = RadixCache(page_size)
            self._kv_store = HostPageStore(
                max_bytes=kv_spill_host_mb * 2**20
            )
        else:
            self._radix = None
            self._kv_store = None
        self._cache_lora_ref: Any = None

        self._prefill = jax.jit(
            partial(
                _paged_prefill_hybrid, cfg=cfg, prompt_pages=self.prompt_pages,
                page_size=page_size, lora_scale=lora_scale,
                cache_dtype=cache_dtype, attn_impl=attn_impl,
                total_tokens=(self.prompt_pages + self.private_pages) * page_size,
            ) if cfg.hybrid else partial(
                _paged_prefill, cfg=cfg, prompt_pages=self.prompt_pages,
                page_size=page_size, lora_scale=lora_scale,
                cache_dtype=cache_dtype, attn_impl=attn_impl, kv_quant=kv_quant,
            )
        )
        self._fanout = jax.jit(
            partial(
                _paged_fanout, prompt_pages=self.prompt_pages,
                private_pages=self.private_pages,
                page_size=page_size,
            ),
            static_argnames=("n", "b", "max_steps"),
        )
        self._decode_step = jax.jit(
            partial(
                _paged_decode_step, cfg=cfg, page_size=page_size,
                pad_id=self.pad_id, lora_scale=lora_scale, paged_impl=paged_impl,
                capture_logprobs=capture_logprobs,
            ),
            donate_argnames=("state",),
            static_argnames=("top_p_impl",),
        )
        self._refill_init = jax.jit(
            partial(
                _refill_init, prompt_pages=self.prompt_pages,
                private_pages=self.private_pages, pad_id=self.pad_id,
                cfg=cfg, page_size=page_size, cache_dtype=cache_dtype,
            ),
            static_argnames=(
                "b", "r_slots", "total", "max_steps", "vocab", "pool_pages",
                "shared_pages",
            ),
        )
        self._cont_adopt = jax.jit(
            _cont_adopt, donate_argnames=("state", "logits_buf"),
        )
        self._refill_admit = jax.jit(
            partial(
                _refill_admit, prompt_pages=self.prompt_pages,
                page_size=page_size,
            ),
            donate_argnames=("state",),
            static_argnames=("n", "b"),
        )
        self._resume_fixup = jax.jit(
            partial(
                _resume_fixup, cfg=cfg, page_size=page_size,
                lora_scale=lora_scale,
            ),
            donate_argnames=("state",),
        )
        self._spec_resume_fixup = jax.jit(
            partial(
                _spec_resume_fixup, cfg=cfg, page_size=page_size,
                lora_scale=lora_scale,
            ),
            donate_argnames=("state",),
        )
        self._turn_resume = jax.jit(
            partial(
                _turn_resume_fixup, cfg=cfg, page_size=page_size,
                lora_scale=lora_scale, pad_id=self.pad_id,
                capture_logprobs=capture_logprobs,
            ),
            donate_argnames=("state",),
            static_argnames=("max_steps",),
        )
        # tiered-KV programs (ISSUE 18): warm suffix prefill through a
        # radix hit, page spill/restore transport, spill-resume cursor
        # fast-forward. _gather_page deliberately does NOT donate — its
        # outputs must be independent buffers a host thread can park.
        self._warm_prefill = jax.jit(
            partial(
                _warm_prefill, cfg=cfg, page_size=page_size,
                lora_scale=lora_scale, pad_id=self.pad_id,
            ),
            donate_argnames=("state", "logits_buf"),
        )
        self._gather_page = jax.jit(_gather_page_tiles)
        self._restore_page = jax.jit(
            _restore_page_tiles, donate_argnames=("state",),
        )
        self._spill_fixup = jax.jit(
            _spill_resume_fixup, donate_argnames=("state",),
        )
        self._refill_step = jax.jit(
            partial(
                _refill_decode_step, cfg=cfg, page_size=page_size,
                pad_id=self.pad_id, lora_scale=lora_scale, paged_impl=paged_impl,
                capture_logprobs=capture_logprobs,
            ),
            donate_argnames=("state",),
            static_argnames=("max_steps", "top_p_impl"),
        )
        self._spec_init = jax.jit(
            partial(
                _spec_init, prompt_pages=self.prompt_pages,
                private_pages=self.private_pages, pad_id=self.pad_id,
            ),
            static_argnames=(
                "b", "r_slots", "total", "max_steps", "buf_width",
                "pool_pages", "hist_width", "shared_pages",
            ),
        )
        self._spec_admit = jax.jit(
            partial(
                _spec_admit, prompt_pages=self.prompt_pages,
                page_size=page_size,
                capture_logprobs=capture_logprobs,
            ),
            donate_argnames=("state",),
            static_argnames=("n", "b", "top_p_impl"),
        )
        self._spec_step = jax.jit(
            partial(
                _spec_step, cfg=cfg, page_size=page_size,
                pad_id=self.pad_id, lora_scale=lora_scale, paged_impl=paged_impl,
                drafter=self.spec_drafter, spec_verify=self.spec_verify,
                hist_width=self.spec_draft + 2,
                capture_logprobs=capture_logprobs,
            ),
            donate_argnames=("state",),
            static_argnames=("max_steps", "draft_len", "ngram_k", "top_p_impl"),
        )

    def _dispatch_key(self, verify_len: int = 0) -> tuple:
        """THIS engine's ``dispatch_choices`` key (decode when
        ``verify_len`` is 0, draft-block verify otherwise). One builder so
        the decode and verify lookups can't drift when the key grows a
        field — ``ops.paged.dispatch_choice_key`` owns the layout."""
        from distrl_llm_tpu.ops.paged import dispatch_choice_key

        return dispatch_choice_key(
            quantized=self.kv_quant == "int8",
            num_kv_heads=self.cfg.num_kv_heads,
            num_groups=self.cfg.num_heads // self.cfg.num_kv_heads,
            head_dim=self.cfg.head_dim,
            page_size=self.page_size,
            pps=self.prompt_pages + self.private_pages,
            impl=self.paged_impl,
            verify_len=verify_len,
        )

    def _grid_steps_per_call(self, rows: int) -> int:
        """Analytic grid-step count of ONE of this engine's paged-attention
        calls at ``rows`` concurrent rows. WHICH kernel ran is read from
        ``dispatch_choices`` under this engine's exact dispatch key (keyed
        by requested impl + geometry, so same-geometry engines pinned to
        different kernels never alias); the count itself is computed
        against the LIVE batch — it is batch-dependent and deliberately
        never cached at trace time. 0 until a decode step has traced, or
        on the reference path."""
        from distrl_llm_tpu.ops.paged import dispatch_choices, paged_grid_steps

        choice = dispatch_choices.get(self._dispatch_key())
        if not choice:
            return 0
        quantized = self.kv_quant == "int8"
        return paged_grid_steps(
            choice, batch=rows, num_kv_heads=self.cfg.num_kv_heads,
            pps=self.prompt_pages + self.private_pages,
            head_dim=self.cfg.head_dim, page_size=self.page_size,
            kv_itemsize=1 if quantized else jnp.dtype(self.cache_dtype).itemsize,
            quantized=quantized, v_head_dim=self.cfg.value_head_dim,
        )

    def _verify_dispatch_choice(self, draft_len: int | None = None):
        """What the draft-block verify dispatch actually ran:
        "native_verify" (the fused one-sweep kernel) or "unrolled" (S
        per-position dispatches), read from ``dispatch_choices`` under this
        engine's verify-marked key. The adaptive controller can trace
        several draft lengths in one round, so without an explicit
        ``draft_len`` the lookup walks d down from the configured max and
        returns the first recorded decision. None until a verify step has
        traced."""
        from distrl_llm_tpu.ops.paged import dispatch_choices

        lens = (
            [draft_len] if draft_len
            else list(range(self.spec_draft, 0, -1))
        )
        for dl in lens:
            choice = dispatch_choices.get(self._dispatch_key(verify_len=dl + 1))
            if choice:
                return choice
        return None

    @property
    def scan_chunk_active(self) -> bool | None:
        """Whether chunked decode (wave or refill) actually ran — None
        before the first round (or scan_chunk off), False if every attempt
        fell back to per-step dispatch (same contract as the dense
        engine's)."""
        if self.scan_chunk <= 1 or not self._chunk_compiled:
            return None
        return any(v is not None for v in self._chunk_compiled.values())

    def _chunk_program(self, tag: str, chunk_fn_partial, chunk: int,
                       max_steps: int, top_p_impl: str,
                       params, lora, state, rng, extra_args,
                       temperature, top_p):
        """Compiled K-steps-per-dispatch program (wave or refill flavor —
        ``tag`` keys them apart) for these shapes, or None where per-step
        dispatch should be used (memory guard or compile failure —
        compile_chunk_guarded). ``extra_args`` are flavor-specific
        positional operands after ``rng`` (the wave step's page table, the
        spec step's drafter adapter) — their STRUCTURE is part of the key:
        compiled executables raise on a structurally different operand
        tree instead of retracing, so e.g. a spec program built with a
        None drafter must not be handed a real adapter pytree."""
        key = (tag, chunk, max_steps, top_p_impl,
               lora_signature(state), lora_signature(lora),
               lora_signature(extra_args))
        return cached_chunk_program(
            self._chunk_compiled, self._chunk_mu, key,
            jax.jit(chunk_fn_partial, donate_argnames=("state",)),
            pool_nbytes(state.k_pages, state.v_pages),
            f"{tag} scan_chunk={chunk}",
            params, lora, state, rng, *extra_args,
            eos_ids=self.eos_ids, temperature=temperature, top_p=top_p,
        )

    def _refill_chunk_fn(self, chunk: int, max_steps: int, top_p_impl: str,
                         params, lora, state, rng, temperature, top_p):
        return self._chunk_program(
            "refill",
            partial(
                _refill_decode_chunk, chunk=chunk, cfg=self.cfg,
                page_size=self.page_size, pad_id=self.pad_id,
                lora_scale=self.lora_scale, paged_impl=self.paged_impl,
                max_steps=max_steps, top_p_impl=top_p_impl,
                capture_logprobs=self.capture_logprobs,
            ),
            chunk, max_steps, top_p_impl, params, lora, state, rng, (),
            temperature, top_p,
        )

    def _spec_chunk_fn(self, chunk: int, max_steps: int, top_p_impl: str,
                       params, lora, state, rng, temperature, top_p,
                       drafter_lora=None, draft_len: int | None = None):
        d = self.spec_draft if draft_len is None else draft_len
        return self._chunk_program(
            # the effective draft length is a static shape choice (the
            # adaptive controller switches it mid-round) — distinct
            # programs, distinct cache keys
            f"spec:d{d}",
            partial(
                _spec_decode_chunk, chunk=chunk, cfg=self.cfg,
                page_size=self.page_size, pad_id=self.pad_id,
                lora_scale=self.lora_scale, paged_impl=self.paged_impl,
                max_steps=max_steps, draft_len=d,
                ngram_k=self.spec_ngram,
                drafter=self.spec_drafter, spec_verify=self.spec_verify,
                hist_width=self.spec_draft + 2,
                top_p_impl=top_p_impl,
                capture_logprobs=self.capture_logprobs,
            ),
            chunk, max_steps, top_p_impl, params, lora, state, rng,
            (drafter_lora,),
            temperature, top_p,
        )

    def _wave_chunk_fn(self, chunk: int, max_steps: int, top_p_impl: str,
                       params, lora, state, rng, page_indices,
                       temperature, top_p):
        return self._chunk_program(
            "wave",
            partial(
                _paged_decode_chunk, chunk=chunk,
                cfg=self.cfg, page_size=self.page_size,
                pad_id=self.pad_id, lora_scale=self.lora_scale,
                paged_impl=self.paged_impl,
                top_p_impl=top_p_impl,
                capture_logprobs=self.capture_logprobs,
            ),
            chunk, max_steps, top_p_impl, params, lora, state, rng,
            (page_indices,), temperature, top_p,
        )

    def bucket_for(self, prompt_mask) -> int:
        """Single-bucket engine (interface parity with GenerationEngine's
        warm-key tracking in trainer._call_engine)."""
        return self.max_prompt_tokens

    def generate(
        self,
        params: Params,
        lora: Params | None,
        prompt_ids: np.ndarray,  # [B, P] left-padded (trainer contract)
        prompt_mask: np.ndarray,
        sampling: SamplingConfig,
        rng: jax.Array,
    ) -> GenerationResult:
        # on a role submesh of several chips the round's programs span them:
        # their Pallas kernels need the mesh in context (ops/per_device.py)
        marks = RoundMarks()
        with params_mesh(params), _no_full_collection():
            result = self._generate(
                params, lora, prompt_ids, prompt_mask, sampling, rng
            )
        file_round(marks, self.last_round_stats)
        return result

    def _generate(self, params, lora, prompt_ids, prompt_mask, sampling, rng):
        total = prompt_ids.shape[0] * max(sampling.n, 1)
        # a new round supersedes any swap consumed during the previous one
        self._reset_lora_mailbox_round()
        # pool telemetry is per-round (only the refill path produces it):
        # without this reset a wave-path round would leave a previous
        # refill/eval round's stats for the trainer's snapshots to misread
        self.last_pool_stats = None
        self.last_spec_stats = None
        self.last_round_stats = None  # waves/refill of THIS round accumulate
        if self.turn_hook is not None:
            self.cfg.refuse_hybrid("turn_hook (in-place multi-turn resume)")
            self.cfg.refuse_looped("turn_hook (in-place multi-turn resume)")
        if self.turn_hook is not None and (
            self.scheduler != "refill" or not self.max_concurrent_rows
            or self.spec_draft
        ):
            raise ValueError(
                "turn_hook (multi-turn episodes) requires the refill "
                "scheduler with max_concurrent_rows set and no spec_draft — "
                "turn continuation lives in the refill idle pass"
            )
        params = self._decode_params(params)
        if (
            self.scheduler == "refill"
            and self.max_concurrent_rows
            # spec decode and prefix sharing live on the refill path — a
            # configured speculative or prefix-sharing engine must not
            # silently fall back to plain waves on a small batch (review
            # finding; continuous_admission implies prefix_sharing). A turn
            # hook forces refill too: turn continuation is an idle-pass
            # feature
            and (total > self.max_concurrent_rows or self.spec_draft
                 or self.prefix_sharing or self.turn_hook is not None)
        ):
            self.last_cb_mode = self.cb_mode
            return self._generate_refill(
                params, lora, prompt_ids, prompt_mask, sampling, rng
            )
        self.last_cb_mode = "waves"
        return generate_in_waves(
            self._generate_wave, self.max_concurrent_rows, params, lora,
            prompt_ids, prompt_mask, sampling, rng, self.pad_id,
        )

    def _generate_refill(
        self, params, lora, prompt_ids, prompt_mask,
        sampling: SamplingConfig, rng: jax.Array,
    ) -> GenerationResult:
        """Continuous batching: R decode slots, refilled per-candidate.

        Where ``generate_in_waves`` admits whole prompt groups and pays each
        wave's straggler tail, this scheduler keeps exactly
        ``max_concurrent_rows`` candidate rows decoding and admits a pending
        candidate into every slot whose occupant hit EOS — vLLM's continuous
        batching (requirements.txt:6) at fixed shapes: the decode program is
        compiled once for [R] rows regardless of batch size; WHICH candidate
        a slot serves is data (the page-table row + scatter indices). Host
        bookkeeping mirrors slot occupancy; per-slot epochs ignore stale
        async done-snapshots taken before a refill."""
        from collections import deque

        b, p = prompt_ids.shape
        if p != self.max_prompt_tokens:
            raise ValueError(
                f"prompts must be padded to {self.max_prompt_tokens}, got {p}"
            )
        max_steps = min(sampling.max_tokens, self.max_new_tokens)
        n = max(sampling.n, 1)
        total = b * n
        # small batches (spec routing) need no more slots than candidates
        r_slots = min(self.max_concurrent_rows, total)
        sharing = self.prefix_sharing
        continuous = self.continuous_admission
        # serving observability (ISSUE 13): one attribute read per round
        # when unarmed; armed, the loop emits per-group lifecycle events
        # and the admission audit at its existing host boundaries — the
        # ledger observes, it never changes a scheduling decision
        sl = self.serving_ledger
        suid: dict[int, int] = {}  # group -> serving-record uid
        # multi-turn turn hook (ISSUE 17): one attribute read per round when
        # unarmed; armed, the idle pass consults it before retiring a
        # finished candidate (try_turn_resume below)
        th = self.turn_hook
        # closed-loop admission limits (ISSUE 14): one attribute read per
        # round when unarmed; armed, admit_groups consults the governors'
        # chain-cap scale and shed gate at its existing decision points —
        # a handle at its defaults decides identically to None (pinned)
        limits = self.control_limits
        # gateway tenancy (ISSUE 19): one attribute read per round when
        # unarmed; armed, admit_groups orders by class-then-FIFO-with-aging,
        # the quota book gates admissions, preemption prefers low classes,
        # and the stream hook flushes tokens at host boundaries
        meta = self.round_meta
        qb = self.quota_book
        stream = self.stream_hook
        t_enqueue = time.time()

        real_len_h = np.asarray(prompt_mask).sum(axis=-1).astype(np.int64)
        row_alive = real_len_h > 0
        ps = self.page_size
        prefill_tokens = int(real_len_h.sum())
        prompt_segments = None  # a hybrid model's: where its prefill's stages end
        if continuous:
            # lazy per-group prefill (continuous admission): the pool
            # arrays start with ZERO prompt pages — each group's prompt KV
            # is prefilled at [1, P] and adopted into pool-allocated chain
            # pages when the request queue admits it mid-round
            t_prefill = 0.0
            prompt_mixer = ()
            shape0 = (self.cfg.num_kv_heads, 0, ps, self.cfg.head_dim)
            if self.kv_quant == "int8":
                from distrl_llm_tpu.ops.paged import init_quantized_pages

                def _empty():
                    return init_quantized_pages(shape0)
            else:
                def _empty():
                    return jnp.zeros(shape0, self.cache_dtype)
            prompt_k = tuple(_empty() for _ in range(self.cfg.paged_layers))
            prompt_v = tuple(_empty() for _ in range(self.cfg.paged_layers))
            # per-group sampling logits, scatter-published by each adopt
            # (the admit paths index it by prompt id exactly as they index
            # the monolithic prefill's batched logits)
            last_logits = jnp.zeros((b, self.cfg.vocab_size), jnp.float32)
            real_len = jnp.asarray(real_len_h.astype(np.int32))
        else:
            t0 = time.perf_counter()
            with telemetry.span(telemetry.ENGINE_PREFILL, rows=b,
                                tokens=prefill_tokens):
                prompt_k, prompt_v, last_logits, real_len, *held = self._prefill(
                    params, lora, jnp.asarray(prompt_ids),
                    jnp.asarray(prompt_mask)
                )
                # a hybrid model's prefill also returns each prompt's
                # lightning states and pooled keys, for its candidates
                prompt_mixer = tuple(held)
                jax.block_until_ready(last_logits)
            t_prefill = time.perf_counter() - t0
            if self.cfg.hybrid:
                prompt_segments = _file_prefill_share(
                    real_len_h, self.prompt_pages, ps)
        host = RoundHostAccount()
        dec_span = telemetry.span(telemetry.ENGINE_REFILL_DECODE, slots=r_slots,
                                  candidates=total)
        dec_span.__enter__()
        # entry to the first dispatch: pool construction, prefix
        # registration, the chunk program, the round's queues
        setup_span = telemetry.span(telemetry.ENGINE_SETUP)
        setup_span.__enter__()

        temperature = jnp.asarray(sampling.temperature, jnp.float32)
        top_p = jnp.asarray(sampling.top_p, jnp.float32)
        top_p_impl = sampling.resolved_top_p_impl(self.plan_top_p_impl)

        # --- page pool (vLLM's budgeted block pool, host-authoritative) ----
        from distrl_llm_tpu.engine.page_pool import PagePool

        total_shared = b * self.prompt_pages
        width = self.prompt_pages + self.private_pages
        if continuous:
            # prompt chains live IN the pool: worst case = scratch + every
            # slot's private region + a chain per concurrently-active group
            # (slots span at most min(b, r_slots) groups) + one prefetched
            worst_pool = (
                1 + r_slots * self.private_pages
                + min(b, r_slots + 1) * self.prompt_pages
            )
            shared_static = 0
        else:
            worst_pool = 1 + r_slots * self.private_pages
            shared_static = total_shared
        pool_pages = (
            min(self.max_kv_pages, worst_pool) if self.max_kv_pages
            else worst_pool
        )
        budgeted = pool_pages < worst_pool
        # tiered KV cache (ISSUE 18): only a continuous-admission round can
        # host it (cached chains are pool pages) — __init__ enforces the
        # pairing, this flag just names the round-local arming
        cache_on = self.prefix_cache and continuous
        pool = PagePool(
            first_page=shared_static, n_pages=pool_pages, r_slots=r_slots,
            width=width, page_size=self.page_size,
            prompt_pages=self.prompt_pages, prefix_sharing=sharing,
            radix=self._radix if cache_on else None,
            store=self._kv_store if cache_on else None,
        )
        # round-local cache bookkeeping (all inert when the cache is off):
        # un-padded prompt token rows (radix keys + cache_chain retirement),
        # per-group hit sizes (serving-ledger provenance), and the round's
        # restore-latency samples (spill_restore_ms_p50)
        group_hit_tok: dict[int, int] = {}
        restore_ms: list[float] = []
        # live ("preempt", cand) host-store keys: candidate ids are round-
        # scoped, so any payload not consumed by a resume is dropped at
        # round end rather than leaking into the engine-lifetime store
        spilled_keys: set = set()
        if cache_on:
            # adapter identity guard: cached KV is only exact under the
            # adapter that wrote it — any change (each training round hands
            # the engine a new LoRA object) drops the whole cache. The
            # strong reference held in __init__ keeps `is` sound.
            if self._cache_lora_ref is not lora:
                pool.invalidate_cache()
                self._cache_lora_ref = lora
            real_toks = [
                np.asarray(prompt_ids[g])[
                    np.asarray(prompt_mask[g]) > 0
                ].astype(np.int32)
                for g in range(b)
            ]
            radix_snap0 = self._radix.snapshot()
        # cache writes stay legal until a mid-round weight swap is consumed
        # (chains prefilled before the swap must not enter the cache under
        # the post-swap adapter identity)
        cache_write = [cache_on]
        if sharing and not continuous:
            # adopt the monolithic prefill's static region as refcounted
            # prefix chains: ceil(rl/ps) live pages per prompt (full pages
            # + the pristine partial tail) held until the group finishes,
            # with each prompt's slack — and dead padding rows' whole
            # regions — reclaimed into the free list as decode capacity
            for g in range(b):
                base = g * self.prompt_pages
                region = list(range(base, base + self.prompt_pages))
                if not row_alive[g]:
                    pool.reclaim(region)
                    continue
                n_chain = max(-(-int(real_len_h[g]) // ps), 1)
                pool.register_prefix(
                    g, region[:n_chain], int(real_len_h[g]) // ps
                )
                pool.reclaim(region[n_chain:])
        # snapshot cadence: never longer than a short decode's whole run
        check = max(1, min(self.decode_chunk, 16, max_steps))
        # grant horizon: a slot's write frontier can advance for up to
        # 2·check steps of snapshot lag plus check steps until the next
        # grant pass (plain decode writes 1 token/step)
        lag_tokens = 3 * check
        # in-flight weight updates read the adapter through this cell
        lora_cell = [lora]
        # sampling-logits cell: continuous admission republishes it per
        # group adopt; the admit closures read it at call time
        logits_cell = [last_logits]

        def _admit_extras(src_partial, copy_mask):
            """Host-authored partial-page copy plan (prefix sharing): per-
            slot CoW sources + which admitted slots copy at all (page-
            aligned prompts skip the copy). Empty on unshared engines so
            the historical admit trace is untouched."""
            if not sharing:
                return ()
            sp = (
                np.full(r_slots, pool.scratch, np.int32)
                if src_partial is None else src_partial
            )
            cm = np.zeros(r_slots, bool) if copy_mask is None else copy_mask
            return (jnp.asarray(sp), jnp.asarray(cm))

        if self.spec_draft:
            # speculative mode: slots carry a pending token + sequence
            # buffer. Verify writes land up to spec_draft positions PAST the
            # frontier and emission is up to d+1 tokens/step, so the grant
            # horizon scales by (d+1) and covers the verify overhang
            d = self.spec_draft
            lag_tokens = 3 * check * (d + 1) + d
            write_ceiling_extra = d
            packed_ids, _, _ = _pack_rows(
                jnp.asarray(prompt_ids), jnp.asarray(prompt_mask)
            )
            buf_width = self.max_prompt_tokens + max_steps + self.spec_draft + 2
            state = self._spec_init(
                prompt_k, prompt_v, b=b, r_slots=r_slots, total=total,
                max_steps=max_steps, buf_width=buf_width,
                pool_pages=pool_pages, hist_width=d + 2,
                shared_pages=shared_static,
            )
            admit_seq = iter(range(1 << 30))
            # the self-drafter runs the policy's own PREVIOUS adapter
            # version (the LoraMailbox swap log's superseded slot); before
            # any swap has ever happened, the current adapter doubles as
            # its own drafter — q == p, near-total acceptance, exact.
            # the ngram drafter never reads the operand: keep it None so
            # every spec dispatch skips flattening a dead LoRA pytree and
            # the chunk-program signature stays swap-stable
            if self.spec_drafter == "self":
                drafter_cell = [
                    self._prev_lora if self._prev_lora is not None else lora
                ]
            else:
                drafter_cell = [None]
            drafter_version = (
                self._prev_lora_version
                if (self.spec_drafter == "self" and self._prev_lora is not None)
                else None
            )
            # effective draft length (the adaptive controller shrinks/grows
            # it between host boundaries; shapes sized for the max)
            d_cell = [d]
            d_switches = 0

            def step(s):
                return self._spec_step(
                    params, lora_cell[0], s, rng, drafter_cell[0],
                    eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p, max_steps=max_steps,
                    draft_len=d_cell[0], ngram_k=self.spec_ngram,
                    top_p_impl=top_p_impl,
                )

            def admit(s, new_cand, admit_mask, dst_partial,
                      src_partial=None, copy_mask=None):
                return self._spec_admit(
                    s, jnp.asarray(new_cand), jnp.asarray(admit_mask),
                    logits_cell[0], real_len, packed_ids,
                    jax.random.fold_in(rng, 100_000 + next(admit_seq)),
                    temperature, top_p, jnp.asarray(dst_partial),
                    *_admit_extras(src_partial, copy_mask),
                    n=n, b=b, eos_ids=self.eos_ids,
                    top_p_impl=top_p_impl,
                )

            def admit_last_pos(rl: int, plen: int) -> int:
                if not budgeted:
                    return rl + max_steps + d  # worst case: no grant passes
                # grow-as-you-go: cover the resumed prefix plus the spec
                # grant horizon, never past the ceiling + verify overhang
                return min(rl + max(plen, 1) + lag_tokens, rl + max_steps + d)
        else:
            write_ceiling_extra = 0
            state = self._refill_init(
                prompt_k, prompt_v,
                _prefill_counters(prompt_mixer[0] if prompt_mixer else None),
                b=b, r_slots=r_slots, total=total,
                max_steps=max_steps, vocab=self.cfg.vocab_size,
                pool_pages=pool_pages, shared_pages=shared_static,
            )

            def step(s):
                return self._refill_step(
                    params, lora_cell[0], s, rng, eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p, max_steps=max_steps,
                    top_p_impl=top_p_impl,
                )

            def admit(s, new_cand, admit_mask, dst_partial,
                      src_partial=None, copy_mask=None):
                return self._refill_admit(
                    s, jnp.asarray(new_cand), jnp.asarray(admit_mask),
                    logits_cell[0], real_len, jnp.asarray(dst_partial),
                    *_admit_extras(src_partial, copy_mask),
                    prompt_mixer=prompt_mixer[0] if prompt_mixer else None,
                    n=n, b=b,
                )

            def admit_last_pos(rl: int, plen: int) -> int:
                if not budgeted:
                    # worst-case pool: grant the full region at admit (no
                    # grant passes run, so nothing else would extend it)
                    return rl + max_steps
                # grow-as-you-go: cover the (resumed) prefix plus the grant
                # horizon, never past the sequence's hard ceiling
                return min(rl + plen + lag_tokens, rl + max_steps)

        if cache_on:
            # spill transport: MAIN-thread device gather into independent
            # buffers (jit outputs, never views into the donated state
            # pools) — the host store's worker thread only converts them.
            # The closure reads the loop's CURRENT `state` binding: every
            # pool path that can evict (alloc/admit/ensure/note_write) runs
            # between dispatches, while the binding holds live buffers.
            def _spill_payload(page):
                return self._gather_page(
                    state.k_pages, state.v_pages,
                    jnp.asarray(page, jnp.int32),
                )

            pool.spill_fn = _spill_payload
        # K-steps-per-dispatch (dispatch-overhead lever). K must
        # DIVIDE `check`: the host acts when since_host >= check, so a
        # non-divisor K stretches the effective cadence to ceil(check/K)·K
        # steps — past the grant horizon lag_tokens = 3·check, which on a
        # budgeted pool would let a slot's write frontier overrun its
        # granted pages and clamp-write onto resident KV (review finding).
        # With K | check, every host decision point runs at exactly its
        # per-step cadence and outputs are bit-identical.
        chunk_fn = None
        k_chunk = 1
        if self.spec_draft:
            def builder(k, ms, tpi, params_, lora_, state_, rng_, temp_, tp_):
                # late-bound drafter adapter and effective draft length: a
                # swap rotates the drafter, the controller resizes d — both
                # must reach any REBUILT program
                return self._spec_chunk_fn(
                    k, ms, tpi, params_, lora_, state_, rng_, temp_, tp_,
                    drafter_lora=drafter_cell[0], draft_len=d_cell[0],
                )
        else:
            builder = self._refill_chunk_fn
        # divisor-adjusted chunk size this round WANTS (0 = chunking off);
        # kept separate from k_chunk so a swap-driven fallback to per-step
        # dispatch can re-enable chunking when a later swap returns to a
        # signature whose program is cached
        k_conf = 0
        if self.scan_chunk > 1 and check > 1:
            k_conf = min(self.scan_chunk, check)
            while check % k_conf:
                k_conf -= 1
            if k_conf > 1:
                chunk_fn = builder(
                    k_conf, max_steps, top_p_impl, params, lora_cell[0],
                    state, rng, temperature, top_p,
                )
                k_chunk = k_conf if chunk_fn is not None else 1
            else:
                k_conf = 0
        # signature the chunk program was built for: an in-flight swap to a
        # structurally different adapter must refetch (compiled executables
        # raise on structure change instead of retracing). The
        # drafter operand is part of the signature: the first consumed swap
        # on a lora=None round rotates the drafter None→adapter with the
        # TARGET signature unchanged, so keying on the target alone would
        # feed a structurally new drafter tree to the stale executable
        def _chunk_round_sig():
            sig = lora_signature(lora_cell[0])
            if self.spec_draft:
                return (sig, lora_signature(drafter_cell[0]))
            return sig

        chunk_sig = _chunk_round_sig() if k_conf else None

        # dead prompts (batch padding) are never enqueued: their rows keep
        # pad tokens / zero length, same as wave mode's born-done rows.
        # Pending entries are candidate ids, or (cand, prefix, prefix_len)
        # for preempted candidates awaiting recompute.
        if continuous:
            # the request queue holds GROUPS awaiting their lazy prefill;
            # the candidate queue fills as admit_group() runs them
            pending: deque = deque()
            group_queue: deque = deque(g for g in range(b) if row_alive[g])
        else:
            pending = deque(c for c in range(total) if row_alive[c // n])
            group_queue = deque()
        finished = np.array([not row_alive[c // n] for c in range(total)])
        # per-group unfinished-candidate counts: a group's prefix-chain
        # hold drops only when its LAST candidate finishes (a preempted
        # candidate must still find the pristine chain on resume)
        group_left = np.array(
            [n if row_alive[g] else 0 for g in range(b)]
        )
        if sl is not None:
            # open one serving record per live group as it enters the
            # request queue (enqueue = round entry); the monolithic-
            # prefill path has every group's prompt KV resident before
            # any admission, so prefill-done lands here too
            for g in range(b):
                if row_alive[g]:
                    mg = meta.get(g) if meta is not None else None
                    suid[g] = sl.on_enqueue(
                        g, n=n, prompt_tokens=int(real_len_h[g]),
                        tenant=mg.get("tenant") if mg else None,
                        priority=mg.get("cls") if mg else None,
                        trace_ctx=mg.get("trace_ctx") if mg else None,
                        # gateway rounds stamp the request's true ARRIVAL
                        # time so queue_wait/TTFT include the open-queue
                        # wait, not just the in-round wait
                        ts=(
                            mg.get("arrival_ts") or t_enqueue
                        ) if mg else t_enqueue,
                    )
            if not continuous:
                for uid_g in suid.values():
                    sl.on_prefill_done(uid_g)
        groups_prefilled = 0
        backfill_admits = 0
        boundary_admits = 0  # admissions (slots + prefills) this host pass
        fill_declined: str | None = None  # fill_idle's head-of-line decline
        shed_groups_seen: set[int] = set()  # groups the shedder deferred
        # gateway round-local bookkeeping (ISSUE 19; all dead when meta is
        # None): per-group quota reservations, deterministic aging counters,
        # per-candidate streamed-token cursors, the declined head group's
        # class for the per-class stall attribution, and the per-class
        # shed/preempt action tally
        quota_charged: dict[int, int] = {}
        group_waited: dict[int, int] = {}
        stream_sent: dict[int, int] = {}
        decline_cls: str | None = None
        class_actions: dict[str, dict[str, int]] = {
            "shed": {}, "preempt": {},
        }
        # turn-resume declines for lack of max_new_tokens window (the
        # PR 17 CharTokenizer gotcha): (obs_tokens, needed_window) pairs,
        # warned once per round when EVERY resume was window-declined
        window_declines: list[tuple[int, int]] = []
        window_short = 0  # resumes never offered: no room for obs+1 at all

        def cls_of(g: int) -> str | None:
            mg = meta.get(g) if meta is not None else None
            return mg.get("cls") if mg else None

        def rank_of(g: int) -> int:
            mg = meta.get(g) if meta is not None else None
            return int(mg.get("rank", 0)) if mg else 0

        def eff_rank(g: int) -> int:
            # FIFO-with-aging (no starvation): every 16 passed-over
            # admission passes promote the group one class step toward
            # rank 0 — pass counters, never wall clock, so the schedule
            # is deterministic and replayable
            return max(0, rank_of(g) - group_waited.get(g, 0) // 16)
        dispatched = 0
        turn_resumes = 0  # in-place episode continuations (turn hook)
        turn_saved = 0  # resident-prefix tokens those resumes never re-prefilled
        host_cand = np.full(r_slots, total, np.int64)  # device `cand` mirror
        epoch = np.zeros(r_slots, np.int64)

        def mark_finished(c: int) -> None:
            if finished[c]:
                return
            finished[c] = True
            if sl is not None:
                sl.on_finish(suid.get(c // n), c)
            if qb is not None and quota_charged.get(c // n):
                g_q = c // n
                if bool(finished[g_q * n:(g_q + 1) * n].all()):
                    # the group's last candidate finished: release its
                    # tenant's token reservation (charge at admission,
                    # credit at close — the quota bounds in-flight
                    # footprint, not lifetime usage)
                    qb.credit(
                        (meta.get(g_q) or {}).get("tenant", ""),
                        quota_charged.pop(g_q),
                    )
            if sharing:
                g = c // n
                group_left[g] -= 1
                if group_left[g] == 0 and g in pool.chains:
                    if cache_write[0]:
                        # tiered cache (ISSUE 18): the finished chain's full
                        # pages become radix inventory instead of freeing —
                        # the next admission sharing this prefix aliases
                        # them with zero prefill. The mutable partial tail
                        # derefs as before; chain holds transfer in place.
                        pool.cache_chain(g, real_toks[g])
                    else:
                        # refcount hold drops; the chain pages free as the
                        # last slot references release (CoW release
                        # discipline)
                        pool.drop_prefix(g)

        # graftcheck: hot-region cont-admission
        def admit_group(g: int) -> bool:
            """Lazily prefill group ``g``'s prompt into pool-allocated
            chain pages ([1, P] reuse of the jitted prefill — bit-identical
            per row to the batched pass), adopt the tiles + logits into the
            live pool arrays, and enqueue the group's candidates."""
            nonlocal state, groups_prefilled, t_prefill, boundary_admits
            rl = int(real_len_h[g])
            n_chain = max(-(-rl // ps), 1)
            resident: list = []
            if cache_on:
                # tier-1 longest-prefix match (ISSUE 18), restoring any
                # spilled matched pages from the host store first
                nodes, _hit = pool.radix_match(real_toks[g])
                if nodes:
                    resident, uploads = pool.restore_nodes(nodes)
                    if uploads:
                        t0r = time.perf_counter()
                        for _node, page, payload in uploads:
                            k_t, v_t = payload
                            state = self._restore_page(
                                state, k_t, v_t,
                                jnp.asarray(page, jnp.int32),
                            )
                        jax.block_until_ready(state.k_pages[0])
                        ms = (time.perf_counter() - t0r) * 1e3
                        pool.note_restore_ms(ms)
                        restore_ms.append(ms)
            if resident:
                return admit_group_warm(g, resident, rl, n_chain)
            chain = pool.alloc_prefix(g, n_chain, rl // ps)
            if chain is None:
                return False
            if cache_on:
                group_hit_tok[g] = 0
            t0 = time.perf_counter()
            with telemetry.span(telemetry.ENGINE_PREFILL, rows=1, tokens=rl):
                k_t, v_t, logits_g, _rl = self._prefill(
                    params, lora_cell[0], prompt_ids_j[g:g + 1],
                    prompt_mask_j[g:g + 1],
                )
            dst = np.full(self.prompt_pages, pool.scratch, np.int32)
            dst[:n_chain] = chain
            state, logits_cell[0] = self._cont_adopt(
                state, k_t, v_t, jnp.asarray(dst), logits_cell[0],
                logits_g[0], jnp.asarray(g, jnp.int32),
            )
            # block before stopping the timer (the monolithic prefill
            # path's convention): under async dispatch the device-side
            # prefill would otherwise serialize into the decode stream and
            # be misattributed to decode_s. The measurement is an UPPER
            # bound — the wait can also absorb the drain of decode chunks
            # already queued — but decode absorbing prefill would bias the
            # fixed-vs-continuous A/B in the new mode's favor
            jax.block_until_ready(logits_cell[0])
            t_prefill += time.perf_counter() - t0
            groups_prefilled += 1
            boundary_admits += 1
            telemetry.counter_add(ENGINE_CONT_PREFILLS)
            if sl is not None:
                sl.on_prefill_done(suid.get(g))
            pending.extend(range(g * n, (g + 1) * n))
            return True

        def admit_group_warm(g: int, resident, rl: int,
                             n_chain: int) -> bool:
            """Radix-hit admission (ISSUE 18): alias the matched resident
            pages refcounted into group ``g``'s chain and forward ONLY the
            un-cached suffix — its KV writes land in the chain's fresh
            pages (the match is capped below the last token, so no write
            ever touches a cached page) and the group's sampling logits
            come off the suffix's last real token through the chunked
            paged forward, exactly the `_resume_fixup` shape."""
            nonlocal state, groups_prefilled, t_prefill, boundary_admits
            chain = pool.admit_cached(g, resident, n_chain, rl // ps)
            if chain is None:
                return False
            hit = len(resident) * ps
            group_hit_tok[g] = hit
            suffix = real_toks[g][hit:rl]
            t0 = time.perf_counter()
            with telemetry.span(telemetry.ENGINE_PREFILL, rows=1,
                                tokens=rl - hit):
                suf = np.full(self.prompt_pages * ps, self.pad_id,
                              np.int32)
                suf[:suffix.size] = suffix
                row_ext = np.full(self.prompt_pages + 1, pool.scratch,
                                  np.int32)
                row_ext[:n_chain] = chain
                state, logits_cell[0] = self._warm_prefill(
                    params, lora_cell[0], state, jnp.asarray(row_ext),
                    jnp.asarray(suf), jnp.asarray(suffix.size, jnp.int32),
                    jnp.asarray(hit, jnp.int32), logits_cell[0],
                    jnp.asarray(g, jnp.int32),
                )
                # same timer discipline as the cold adopt above: block so
                # the suffix forward is attributed to prefill, not decode
                jax.block_until_ready(logits_cell[0])
            t_prefill += time.perf_counter() - t0
            groups_prefilled += 1
            boundary_admits += 1
            telemetry.counter_add(ENGINE_CONT_PREFILLS)
            if sl is not None:
                sl.on_prefill_done(suid.get(g))
            pending.extend(range(g * n, (g + 1) * n))
            return True

        def try_admit_group(g: int) -> str | None:
            """One group's admission decision: the decline reason, or None
            when the group was admitted. Shared by the FIFO path and the
            gateway's class-ordered path — the checks and their order are
            identical, so non-gateway rounds decide exactly as before."""
            if limits is not None and limits.shed_active() and (
                pending or bool((host_cand < total).any())
            ) and rank_of(g) >= (
                limits.shed_floor() if meta is not None else 0
            ):
                # class-aware shed (ISSUE 19): the governor's shed floor
                # names the lowest rank still admitted — scavenger sheds
                # before batch, interactive never sheds at floor >= 1.
                # Without gateway identity every group is rank 0 and the
                # floor is pinned 0: the ISSUE 14 behavior, bit for bit
                if g not in shed_groups_seen:
                    # counted once per deferred group, however many
                    # passes decline it
                    shed_groups_seen.add(g)
                    telemetry.counter_add(CONTROL_SHED_GROUPS)
                    c_g = cls_of(g)
                    if c_g is not None:
                        class_actions["shed"][c_g] = (
                            class_actions["shed"].get(c_g, 0) + 1
                        )
                return "shed"
            if len(pending) >= r_slots:
                return "no_slots"
            cap = r_slots + 1
            if limits is not None:
                cap = limits.chain_cap(cap)
            if len(pool.chains) >= cap:
                return "chain_cap"
            if qb is not None and meta is not None and g not in quota_charged:
                # per-tenant token quota (ISSUE 19): reserve the group's
                # WORST-CASE footprint (prompt + full output window) before
                # touching pool state; a decline is the ``quota`` stall
                # reason. The charge sticks across declined passes (the
                # group stays queued) and credits back at group finish.
                mg = meta.get(g)
                if mg is not None:
                    # the window is the REQUEST's own budget when the meta
                    # carries one (the gateway caps each request below the
                    # round max) — this keeps the charge equal to what the
                    # gateway's submit-time quota check priced, so a request
                    # that entered the queue can always eventually admit
                    charge = int(real_len_h[g]) + n * min(
                        max_steps, int(mg.get("max_new", max_steps))
                    )
                    if not qb.try_charge(mg.get("tenant", ""), charge):
                        return "quota"
                    quota_charged[g] = charge
            n_chain = max(-(-int(real_len_h[g]) // ps), 1)
            if pool.free_pages < n_chain + self.private_pages:
                return "no_pages"
            if not admit_group(g):
                return "no_pages"
            return None

        def admit_groups() -> str | None:
            """Admission-ahead: keep the candidate queue stocked while the
            pool can afford the head group's chain AND a full private
            region on top (never starve a running slot's grants), capped at
            one prefetched chain beyond the slots' worst-case group spread
            (the worst_pool sizing above). Returns the head group's decline
            reason when the queue is left waiting (the admission audit's
            attribution, ISSUE 13), None when the queue drained.

            Control hooks (ISSUE 14): an armed SLO shedder declines new
            GROUP admissions with the ``shed`` reason — but only while the
            engine has live work to drain (shedding an otherwise-empty
            engine would wedge it, not protect it); the HBM governor's
            admission fraction scales the live-chain cap.

            Gateway rounds (ISSUE 19, ``meta`` armed): groups are visited
            in class-then-FIFO-with-aging order, and a POLICY decline
            (shed/quota) on one group skips ahead to the next — an
            interactive group never waits behind a shed scavenger. A
            RESOURCE decline (slots/pages/chain cap) still ends the pass
            head-of-line, exactly like the FIFO path, so pool pressure
            keeps its auditable ordering."""
            nonlocal decline_cls
            decline_cls = None
            if meta is None:
                while group_queue:
                    reason = try_admit_group(group_queue[0])
                    if reason is not None:
                        return reason
                    group_queue.popleft()
                return None
            while group_queue:
                order = sorted(
                    group_queue,
                    key=lambda g: (
                        eff_rank(g), (meta.get(g) or {}).get("seq", g),
                    ),
                )
                head_reason: str | None = None
                admitted_g: int | None = None
                for g in order:
                    reason = try_admit_group(g)
                    if reason is None:
                        admitted_g = g
                        break
                    if head_reason is None:
                        head_reason = reason
                        decline_cls = cls_of(g)
                    if reason not in ("shed", "quota"):
                        break  # resource decline: no skip-ahead past it
                if admitted_g is None:
                    for g in group_queue:
                        group_waited[g] = group_waited.get(g, 0) + 1
                    return head_reason
                group_queue.remove(admitted_g)
                for g in group_queue:
                    # passed-over groups age one pass per admission ahead
                    # of them (the deterministic starvation valve)
                    group_waited[g] = group_waited.get(g, 0) + 1
            return None
        # graftcheck: end-hot-region

        if continuous:
            prompt_ids_j = jnp.asarray(prompt_ids)
            prompt_mask_j = jnp.asarray(prompt_mask)

        def fill_idle(s, idle_slots):
            nonlocal backfill_admits, boundary_admits, fill_declined
            new_cand = np.full(r_slots, total, np.int32)
            admit_mask = np.zeros(r_slots, bool)
            dst_partial = np.full(r_slots, pool.scratch, np.int32)
            src_partial = np.full(r_slots, pool.scratch, np.int32)
            copy_mask = np.zeros(r_slots, bool)
            resumes = []
            for s_i in idle_slots:
                if not pending:
                    break
                entry = pending[0]
                c, prefix, plen, logp0 = (
                    entry if isinstance(entry, tuple) else (entry, None, 0, 0.0)
                )
                pr = c // n
                rl = int(real_len_h[pr])
                # admission gated on FREE PAGES (vLLM's can_allocate); the
                # queue is FIFO — a head-of-line candidate that doesn't fit
                # blocks the rest rather than being starved by skip-ahead.
                # Under prefix sharing the slot ALIASES its group's chain
                # (refcount++) and first_write=rl names the imminent first
                # decode write, so the pool's copy-on-write split of the
                # partial tail page runs as part of this admission — the
                # engine registers chains rather than passing donor slots
                # because a pending candidate must outlive its siblings
                # (the chain hold persists until the group finishes)
                if not pool.admit(
                    int(s_i), pr, rl, admit_last_pos(rl, plen),
                    first_write=rl if sharing else None,
                ):
                    fill_declined = "no_pages"
                    break
                pending.popleft()
                boundary_admits += 1
                new_cand[s_i] = c
                admit_mask[s_i] = True
                dst_partial[s_i] = pool.owned[int(s_i)][0]
                if sl is not None:
                    # admission event with the pool's chain-alias facts:
                    # how much of the prompt this slot aliases and whether
                    # a CoW tail split rides this admit dispatch — read
                    # BEFORE take_copy drains the queued copy source
                    alias = pool.slot_alias_info(int(s_i))
                    sl.on_admit(
                        suid.get(pr), cand=c, slot=int(s_i),
                        shared_pages=int(alias["shared_pages"]),
                        cow=bool(alias["cow_queued"]),
                        backfill=dispatched > 0, resumed=bool(plen),
                        prefix_hit_tokens=group_hit_tok.get(pr, 0),
                    )
                if sharing:
                    src = pool.take_copy(int(s_i))
                    if src is not None:
                        src_partial[s_i] = src
                        copy_mask[s_i] = True
                if plen:
                    resumes.append((int(s_i), prefix, plen, rl, c // n, logp0))
            if admit_mask.any():
                s = admit(s, new_cand, admit_mask, dst_partial,
                          src_partial, copy_mask)
                host_cand[admit_mask] = new_cand[admit_mask]
                epoch[admit_mask] += 1
                if dispatched:
                    # mid-round backfill: the admissions a fixed episode
                    # batch would have left idle
                    k_admit = int(admit_mask.sum())
                    backfill_admits += k_admit
                    telemetry.counter_add(ENGINE_BACKFILL_ADMITS, k_admit)
                # admitted slots' table rows must reach the device before
                # their first decode step (and before any resume fixup)
                s = s._replace(page_indices=jnp.asarray(pool.table))
                for s_i, prefix, plen, rl, pr, logp0 in resumes:
                    if self.kv_spill:
                        payload = self._kv_store.get(
                            ("preempt", int(new_cand[s_i]))
                        )
                        if payload is not None:
                            # tier-2 resume (ISSUE 18): the preempt spill
                            # parked the slot's written pages + logits row
                            # host-side — reload them bit-exactly into the
                            # freshly granted pages (same block order) and
                            # fast-forward the cursors; nothing recomputes.
                            # Payload aged out of the store's byte cap →
                            # fall through to the recompute fixup below.
                            t0r = time.perf_counter()
                            owned = pool.owned[int(s_i)]
                            nv = int(payload["n_valid"])
                            for pg, (k_t, v_t) in zip(
                                owned[:nv], payload["tiles"]
                            ):
                                s = self._restore_page(
                                    s, k_t, v_t,
                                    jnp.asarray(pg, jnp.int32),
                                )
                            s = self._spill_fixup(
                                s, jnp.asarray(s_i, jnp.int32),
                                jnp.asarray(payload["logits"]),
                                jnp.asarray(plen, jnp.int32),
                                jnp.asarray(rl, jnp.int32),
                            )
                            jax.block_until_ready(s.logits)
                            ms = (time.perf_counter() - t0r) * 1e3
                            pool.note_restore_ms(ms)
                            pool.note_restored(nv)
                            restore_ms.append(ms)
                            # the restored content goes stale the moment
                            # decode continues — drop the host copy
                            self._kv_store.drop(
                                ("preempt", int(new_cand[s_i]))
                            )
                            spilled_keys.discard(
                                ("preempt", int(new_cand[s_i]))
                            )
                            continue
                    if self.spec_draft:
                        # host-rebuilt n-gram buffer: packed prompt + prefix
                        buf_w = s.seq_buf.shape[1]
                        row = np.zeros(buf_w, np.int32)
                        real = np.asarray(prompt_ids[pr])[
                            np.asarray(prompt_mask[pr]) > 0
                        ]
                        row[:rl] = real
                        row[rl:rl + plen] = prefix[:plen]
                        s = self._spec_resume_fixup(
                            params, lora_cell[0], s, jnp.asarray(s_i, jnp.int32),
                            jnp.asarray(prefix, jnp.int32),
                            jnp.asarray(plen, jnp.int32),
                            jnp.asarray(rl, jnp.int32),
                            jnp.asarray(row),
                            jnp.asarray(logp0, jnp.float32),
                        )
                    else:
                        s = self._resume_fixup(
                            params, lora_cell[0], s, jnp.asarray(s_i, jnp.int32),
                            jnp.asarray(prefix, jnp.int32),
                            jnp.asarray(plen, jnp.int32),
                            jnp.asarray(rl, jnp.int32),
                        )
            return s

        def preempt(s_i: int):
            """Evict slot ``s_i``'s occupant: requeue it (with its generated
            prefix, for recompute) at the FRONT of the queue, free its pages,
            and kill the slot on device via a dead-sentinel admit."""
            nonlocal state
            c = int(host_cand[s_i])
            # blocking read of the slot's CURRENT truth (the snapshot lags):
            # preemption is rare, the sync is the cost of exactness
            if bool(np.asarray(state.done[s_i])):
                mark_finished(c)  # finished while we deliberated
            else:
                plen = int(np.asarray(state.lengths_buf[c]))
                if plen:
                    prefix = np.asarray(state.out[c]).astype(np.int32)
                    # spec re-admission re-samples a first token and clobbers
                    # logps_buf[c, 0]; carry the ORIGINAL behavior logprob so
                    # the resume fixup can restore it
                    logp0 = (
                        float(np.asarray(state.logps_buf[c, 0]))
                        if self.capture_logprobs else 0.0
                    )
                    if self.kv_spill:
                        # tier-2 spill (ISSUE 18): park the slot's WRITTEN
                        # pages (its CoW tail + decode pages, block order)
                        # and its logits row host-side BEFORE releasing the
                        # pages — resume becomes a bit-exact page reload
                        # instead of a recompute forward. Gathers dispatch
                        # here on the main thread; the store thread only
                        # converts the finished buffers.
                        rl_p = int(real_len_h[c // n])
                        owned = pool.owned[s_i]
                        n_valid = min(
                            (rl_p + plen - 1) // ps - rl_p // ps + 1,
                            len(owned),
                        )
                        self._kv_store.put(
                            ("preempt", c),
                            {
                                "tiles": [
                                    self._gather_page(
                                        state.k_pages, state.v_pages,
                                        jnp.asarray(pg, jnp.int32),
                                    )
                                    for pg in owned[:n_valid]
                                ],
                                "logits": state.logits[s_i],
                                "n_valid": np.int64(n_valid),
                            },
                        )
                        pool.note_spilled(n_valid)
                        spilled_keys.add(("preempt", c))
                    pending.appendleft((c, prefix, plen, logp0))
                else:
                    pending.appendleft(c)
                pool.preemptions += 1
                if sl is not None:
                    sl.on_preempt(suid.get(c // n), c)
                c_g = cls_of(c // n)
                if c_g is not None:
                    class_actions["preempt"][c_g] = (
                        class_actions["preempt"].get(c_g, 0) + 1
                    )
            pool.release(s_i)
            kill_cand = np.full(r_slots, total, np.int32)
            kill_mask = np.zeros(r_slots, bool)
            kill_mask[s_i] = True
            dstp = np.full(r_slots, pool.scratch, np.int32)
            state = admit(state, kill_cand, kill_mask, dstp)
            host_cand[s_i] = total
            epoch[s_i] += 1

        def try_turn_resume(s_i: int, c: int) -> bool:
            """Multi-turn continuation (ISSUE 17): before retiring a finished
            candidate, offer its completion to the turn hook. If the hook
            returns observation tokens and the slot has token room and pages,
            append them in place — the slot keeps its occupant AND its pages,
            so the whole conversation prefix (``seq_len`` tokens of resident
            KV) is never re-prefilled. Returns True when the slot resumed
            (the idle pass must then NOT release/finish it). Declines —
            size, page pressure — unwind via ``hook.declined`` so the driver
            can close the episode as truncated; declining instead of
            preempting victims keeps turn continuation strictly lower
            priority than first-turn progress."""
            nonlocal state, budget, turn_resumes, turn_saved, window_short
            # blocking read of the candidate's CURRENT truth: done is
            # monotone per epoch, so the occupant has truly finished; turn
            # boundaries are rare relative to decode steps, same cost
            # argument as preempt()
            gen_len = int(np.asarray(state.lengths_buf[c]))
            if gen_len + 2 > max_steps:
                # no room for even one observation + one decode token: the
                # hook is never consulted, the driver scores the final turn
                # from the result tensors
                window_short += 1
                return False
            tokens = np.asarray(state.out[c][:gen_len]).astype(np.int32)
            obs = th(c, tokens)
            if obs is None:
                return False
            obs = np.asarray(obs, np.int32).ravel()
            t_obs = int(obs.size)
            if t_obs == 0 or gen_len + t_obs + 1 > max_steps:
                if t_obs > 0:
                    # window decline, not an empty observation: remember
                    # what WOULD have fit for the round-end diagnostic
                    window_declines.append((t_obs, gen_len + t_obs + 1))
                th.declined(c)
                return False
            rl = int(real_len_h[c // n])
            seq_len = rl + gen_len
            if pool.ensure(s_i, admit_last_pos(rl, gen_len + t_obs)):
                th.declined(c)
                return False
            # any pages ensure granted must reach the device BEFORE the
            # fixup's chunked forward scatters observation KV
            state = state._replace(page_indices=jnp.asarray(pool.table))
            obs_pad = np.full(max_steps, self.pad_id, np.int32)
            obs_pad[:t_obs] = obs
            state = self._turn_resume(
                params, lora_cell[0], state, jnp.asarray(s_i, jnp.int32),
                jnp.asarray(obs_pad), jnp.asarray(t_obs, jnp.int32),
                jnp.asarray(c, jnp.int32), jnp.asarray(gen_len, jnp.int32),
                jnp.asarray(seq_len, jnp.int32), jnp.asarray(rl, jnp.int32),
                max_steps=max_steps,
            )
            # queued snapshots were taken while this slot's done flag was
            # set — the epoch bump stops them retiring the resumed occupant
            epoch[s_i] += 1
            # each resume spends up to one more notice-latency window of
            # idle slot-steps before the occupant's next EOS is seen
            budget += 2 * check
            turn_resumes += 1
            turn_saved += seq_len
            telemetry.counter_add(ENGINE_TURN_RESUMES)
            telemetry.counter_add(ENGINE_TURN_PREFILL_SAVED, float(seq_len))
            return True

        def serving_boundary(group_decline: str | None, had_idle: bool,
                             wedged: bool = False) -> None:
            """One admission-audit + occupancy sample per admission pass
            (ISSUE 13; only called with the ledger armed). A pass that
            admitted nothing while work waited is attributed to exactly
            one stall reason — the smoke asserts the reason counts sum to
            the declined passes, so an unattributable decline surfaces as
            a failure, not a silent gap."""
            nonlocal boundary_admits, fill_declined
            waiting = len(pending) + n * len(group_queue)
            reason = None
            if waiting and not boundary_admits:
                if wedged:
                    reason = "budget_wedge"
                elif group_decline is not None:
                    reason = group_decline
                elif fill_declined is not None and had_idle:
                    reason = fill_declined
                else:
                    # every slot is busy (or the pass offered no idle
                    # slot): the queue waits on decode progress
                    reason = "no_slots"
            cls = None
            if reason is not None and meta is not None:
                # class attribution (ISSUE 19): the declined head's class —
                # the group admit_groups declined, else the pending head's
                # group, else the queue head
                if reason == group_decline and decline_cls is not None:
                    cls = decline_cls
                elif pending:
                    e0 = pending[0]
                    c0 = e0[0] if isinstance(e0, tuple) else e0
                    cls = cls_of(c0 // n)
                elif group_queue:
                    cls = cls_of(group_queue[0])
            sl.on_boundary(
                live_slots=int((host_cand < total).sum()),
                queue_depth=waiting,
                free_pages=pool.free_pages,
                admitted=boundary_admits,
                reason=reason,
                cls=cls,
            )
            boundary_admits = 0
            fill_declined = None

        setup_span.__exit__(None, None, None)
        with telemetry.span(telemetry.ENGINE_ADMIT) as admit_span:
            group_decline = admit_groups() if continuous else None
            state = fill_idle(state, range(r_slots))
            admit_span.set(groups=groups_prefilled, slots=pool.total_admissions)
        if sl is not None:
            serving_boundary(group_decline, had_idle=True)

        snapshots: deque = deque()
        # each slot serves ≤ ceil(total/R) occupants × max_steps, plus up to
        # 2·check admission lag per handoff (the async snapshot pipeline); a
        # budgeted pool can additionally serialize candidates (admission
        # stalls) and recompute preempted prefixes, so its backstop is the
        # fully-serial bound — continuous admission takes the serial bound
        # too (its chains gate admission like a budget does)
        occupancies = -(-total // r_slots)
        budget = (max_steps + 2 * check) * (
            2 * (total + 2) if (budgeted or continuous) else occupancies + 2
        )
        since_host = 0
        stalled_boundaries = 0
        # speculative accounting for the grid-cost artifacts, accumulated
        # PER DISPATCH in per-layer units so a round that mixes dispatch
        # regimes stays exact (the adaptive controller can resize d
        # mid-round, and the fused-verify probe can pass at one draft
        # length but not another — a single round-end choice would then
        # misattribute every step): each dispatched step costs one fused
        # sweep when its verify traced "native_verify", else (d_eff+1)
        # per-position decode calls; the self drafter adds d_eff plain
        # decode calls per step either way
        verify_grid_units = 0
        draft_call_steps = 0
        grid_units_key: tuple | None = None
        grid_units_step = 0
        spec_prev_acc = 0
        spec_prev_draft = 0
        spec_ema: float | None = None
        # graftcheck: hot-region refill/spec
        while dispatched < budget and not finished.all():
            prev_lora = lora_cell[0]
            self._take_pending_lora(lora_cell, dispatched)
            if lora_cell[0] is not prev_lora:
                if self.spec_draft and self.spec_drafter == "self":
                    # the consumed swap made the superseded adapter "the
                    # previous version" — rotate it into the drafter slot
                    # (usually a value swap, but None→adapter on a
                    # lora=None round's second swap changes structure:
                    # _chunk_round_sig covers the drafter so that case
                    # rebuilds). Read the MAILBOX's slot, not a local
                    # snapshot: _take_pending_lora owns the
                    # superseded-adapter bookkeeping (value + version)
                    drafter_cell[0] = self._prev_lora
                    drafter_version = self._prev_lora_version
                if cache_on:
                    # consumed in-flight weight swap: KV cached under the
                    # superseded adapter is no longer exact — drop the whole
                    # cache and stop caching for the rest of the round
                    # (chains prefilled pre-swap must not be retired into
                    # the cache under the new identity). Already-admitted
                    # chains keep decoding on their pre-swap KV, exactly
                    # as the cache-off engine does.
                    pool.invalidate_cache()
                    cache_write[0] = False
                if k_conf:
                    sig = _chunk_round_sig()
                    if sig != chunk_sig:
                        # rebuild at k_conf (not k_chunk): a swap whose
                        # program fell back drops to per-step, and a later
                        # swap back to a cached signature re-enables
                        # chunked dispatch
                        chunk_fn = builder(
                            k_conf, max_steps, top_p_impl, params,
                            lora_cell[0], state, rng, temperature, top_p,
                        )
                        chunk_sig = sig
                        k_chunk = k_conf if chunk_fn is not None else 1
            # chunk dispatch only at k_conf-aligned offsets: a mid-interval
            # re-enable (swap back to a cached signature while running
            # per-step) would otherwise stretch one host-decision interval
            # past `check` steps — past the grant horizon on a budgeted
            # pool, the clamp-write overrun the K | check invariant exists
            # to prevent (see the cadence comment above)
            fused_snap = None
            fused_spec = None
            if chunk_fn is not None and since_host % k_conf == 0:
                with telemetry.span(telemetry.ENGINE_DISPATCH,
                                    step=dispatched, steps=k_chunk):
                    if self.spec_draft:
                        state, done_c, seq_c, dtot_c, acc_c = chunk_fn(
                            params, lora_cell[0], state, rng, drafter_cell[0],
                            eos_ids=self.eos_ids,
                            temperature=temperature, top_p=top_p,
                        )
                        fused_spec = (dtot_c, acc_c)
                    else:
                        state, done_c, seq_c = chunk_fn(
                            params, lora_cell[0], state, rng,
                            eos_ids=self.eos_ids,
                            temperature=temperature, top_p=top_p,
                        )
                fused_snap = (done_c, seq_c)
                dispatched += k_chunk
                since_host += k_chunk
                n_new = k_chunk
            else:
                with telemetry.span(telemetry.ENGINE_DISPATCH,
                                    step=dispatched, steps=1):
                    state = step(state)
                dispatched += 1
                since_host += 1
                n_new = 1
            if self.spec_draft:
                # by the time a dispatch returns, its verify decision is
                # in dispatch_choices (jit traces synchronously on first
                # call) — a dict lookup per host iteration, no device
                # sync. The per-step unit count is recomputed only when
                # (d, verify decision) changes: the analytic grid model
                # is pure Python the launch-bound loop shouldn't repay
                # every iteration
                vchoice_now = self._verify_dispatch_choice(d_cell[0])
                if (d_cell[0], vchoice_now) != grid_units_key:
                    grid_units_key = (d_cell[0], vchoice_now)
                    if vchoice_now and (
                        vchoice_now.split("!")[0] == "native_verify"
                    ):
                        from distrl_llm_tpu.ops.paged import paged_grid_steps

                        grid_units_step = paged_grid_steps(
                            "native_verify", batch=r_slots,
                            num_kv_heads=self.cfg.num_kv_heads,
                            pps=self.prompt_pages + self.private_pages,
                        )
                    else:
                        grid_units_step = (
                            (d_cell[0] + 1)
                            * self._grid_steps_per_call(r_slots)
                        )
                verify_grid_units += n_new * grid_units_step
                if self.spec_drafter == "self":
                    draft_call_steps += n_new * d_cell[0]
            if since_host < check:
                continue
            since_host = 0
            if self.spec_draft and self.spec_adapt:
                # draft-length controller: between-boundary accept-rate
                # deltas feed an EMA; persistent waste shrinks the
                # effective d (halving, floor 1), recovery grows it back
                # toward the configured max. The d choice depends only on
                # PAST acceptance data, so per-step output distributions
                # are untouched (any d is exact). Reads are one tiny
                # device sync per boundary — the knob is opt-in.
                if fused_spec is not None:
                    dtot_now, atot_now = fused_spec
                else:
                    dtot_now = jnp.copy(state.draft_total)
                    atot_now = jnp.copy(state.accept_total)
                # sampler acceptance (accept_total), NOT emit-derived: a
                # final emitted token that was itself an accepted draft
                # (an accepted EOS, a budget-clamped tail) must not read
                # as a rejection and bias the EMA toward shrinking d
                # graftcheck: disable=GC301 -- opt-in spec_adapt controller: one tiny read per host boundary, not per step
                acc_now = int(np.asarray(atot_now))
                # graftcheck: disable=GC301 -- same boundary read as the line above
                dtot_h = int(np.asarray(dtot_now))
                d_acc = acc_now - spec_prev_acc
                d_draft = dtot_h - spec_prev_draft
                spec_prev_acc, spec_prev_draft = acc_now, dtot_h
                if d_draft > 0:
                    rate = d_acc / d_draft
                    spec_ema = (
                        rate if spec_ema is None
                        else 0.5 * spec_ema + 0.5 * rate
                    )
                    new_d = d_cell[0]
                    if spec_ema < 0.35 and d_cell[0] > 1:
                        new_d = max(1, d_cell[0] // 2)
                    elif spec_ema > 0.75 and d_cell[0] < self.spec_draft:
                        new_d = min(self.spec_draft, d_cell[0] * 2)
                    if new_d != d_cell[0]:
                        d_cell[0] = new_d
                        d_switches += 1
                        telemetry.counter_add(ENGINE_SPEC_DRAFT_RESIZES)
                        if k_conf:
                            chunk_fn = builder(
                                k_conf, max_steps, top_p_impl, params,
                                lora_cell[0], state, rng, temperature, top_p,
                            )
                            k_chunk = k_conf if chunk_fn is not None else 1
            with telemetry.span(telemetry.ENGINE_SNAPSHOT_LAUNCH,
                                fused=fused_snap is not None):
                if fused_snap is not None:
                    # chunked steady state: the (done, seq) copies rode INSIDE
                    # the decode dispatch (_refill_decode_chunk's fused
                    # snapshot) — a boundary with no admissions or preemptions
                    # costs zero extra device round-trips
                    done_snap, seq_snap = fused_snap
                else:
                    done_snap = jnp.copy(state.done)
                    seq_snap = jnp.copy(state.seq_lengths)
                try:
                    done_snap.copy_to_host_async()
                    seq_snap.copy_to_host_async()
                except AttributeError:
                    pass
            snapshots.append(
                (done_snap, seq_snap, epoch.copy(), host_cand.copy())
            )
            if len(snapshots) <= 1:
                continue
            done_snap, seq_snap, snap_epoch, snap_cand = snapshots.popleft()
            # delayed reads of ASYNC-copied snapshots dispatched one host
            # boundary ago — the copy already completed while the last
            # `check` decode steps ran
            # (the one place the host waits on the device: a long span here
            # is a device-side stall, not host work)
            t_wait = time.perf_counter()
            with telemetry.span(telemetry.ENGINE_SNAPSHOT_WAIT):
                # graftcheck: disable=GC301 -- reads a finished async copy one boundary old
                done_h = np.asarray(done_snap)
                # graftcheck: disable=GC301 -- same delayed snapshot as the line above
                seq_h = np.asarray(seq_snap)
            host.waited(t_wait, dispatched)
            if sl is not None:
                # first-token detection off the same boundary snapshot: a
                # slot whose resident length moved past its occupant's
                # prompt has generated (boundary-granular — the loop's own
                # cadence, no extra device sync; a candidate that finished
                # between boundaries backfills at finish)
                for s_i in range(r_slots):
                    c_s = int(snap_cand[s_i])
                    if (
                        c_s < total and snap_epoch[s_i] == epoch[s_i]
                        and int(seq_h[s_i]) > int(real_len_h[c_s // n])
                    ):
                        sl.on_first_token(suid.get(c_s // n))
            if stream is not None:
                # gateway streaming (ISSUE 19): flush each live slot's
                # newly visible tokens off the boundary snapshot. The
                # snapshot's seq count is one boundary old, so the first
                # ``gen`` output positions are already written and
                # immutable — reading them from the CURRENT out buffer is
                # exact. One small blocking gather per streaming slot per
                # boundary, gateway-armed rounds only (opt-in cost); the
                # round-end flush below guarantees byte-complete streams
                # regardless of boundary cadence
                for s_i in range(r_slots):
                    c_s = int(snap_cand[s_i])
                    if c_s >= total or snap_epoch[s_i] != epoch[s_i]:
                        continue
                    gen = int(seq_h[s_i]) - int(real_len_h[c_s // n])
                    sent = stream_sent.get(c_s, 0)
                    if gen > sent:
                        # graftcheck: disable=GC301 -- gateway-armed rounds only: streamed positions are immutable once written
                        toks = np.asarray(state.out[c_s][sent:gen])
                        stream_sent[c_s] = gen
                        stream(c_s, [int(t) for t in toks])
            # a done flag is only believed if the slot hasn't been refilled
            # since the snapshot was dispatched (done is monotone per epoch)
            idle = [
                int(s_i) for s_i in np.nonzero(done_h)[0]
                if snap_epoch[s_i] == epoch[s_i]
            ]
            for s_i in idle:
                c = snap_cand[s_i]
                if (
                    th is not None and c < total and not finished[c]
                    and try_turn_resume(int(s_i), int(c))
                ):
                    # episode continues in place: occupant, pages and KV all
                    # kept — do not release or retire the slot
                    host.mark("a")  # the next turn's prefill ran in this pass
                    continue
                if pool.owned[s_i] or pool.shared[s_i]:
                    pool.release(s_i)  # frees pages + redirects to scratch
                if c < total:
                    # after the slot release so a completed group's chain
                    # pages free the moment the hold drops
                    mark_finished(int(c))
                host_cand[s_i] = total
            table_dirty = bool(idle)
            if budgeted:
                host.mark("g")
                with telemetry.span(telemetry.ENGINE_GRANT):
                    # grant pass: extend every occupied slot's pages to cover its
                    # write frontier through the next grant window (spec: the
                    # verify overhang rides in lag_tokens/write_ceiling_extra);
                    # preempt the least-advanced occupant when the pool runs dry
                    idle_set = set(idle)
                    for s_i in range(r_slots):
                        if host_cand[s_i] >= total or s_i in idle_set:
                            continue
                        if snap_epoch[s_i] != epoch[s_i]:
                            continue  # admitted post-snapshot; admit grant covers
                        rl = int(real_len_h[int(host_cand[s_i]) // n])
                        target = min(
                            int(seq_h[s_i]) + lag_tokens,
                            rl + max_steps + write_ceiling_extra,
                        )
                        while pool.ensure(s_i, target):
                            occupied = [
                                v for v in range(r_slots)
                                if host_cand[v] < total and v != s_i
                                and snap_epoch[v] == epoch[v]
                            ]
                            if occupied and meta is not None:
                                # class-aware preemption (ISSUE 19): evict the
                                # highest-rank (lowest-priority) occupant first
                                # — scavenger before batch before interactive —
                                # least progress within a class. Non-gateway
                                # rounds keep the pure least-progress victim
                                victim = min(
                                    occupied,
                                    key=lambda v: (
                                        -rank_of(int(host_cand[v]) // n),
                                        int(seq_h[v])
                                        - int(real_len_h[int(host_cand[v]) // n]),
                                    ),
                                )
                            elif occupied:
                                victim = min(
                                    occupied,
                                    key=lambda v: int(seq_h[v])
                                    - int(real_len_h[int(host_cand[v]) // n]),
                                )
                            else:
                                victim = s_i  # nothing else to evict: self-evict
                            host.mark("p")
                            with telemetry.span(telemetry.ENGINE_PREEMPT):
                                preempt(victim)
                            if victim == s_i:
                                break
                        table_dirty = True
            boundary_marks = pool.total_admissions + groups_prefilled
            group_decline = None
            idle_free = [s for s in idle if host_cand[s] >= total]
            # one span per admission pass that can admit: a queued group to
            # prefill, or a pending candidate and a free slot to put it in
            admit_span = (
                telemetry.span(telemetry.ENGINE_ADMIT)
                if (continuous and group_queue) or (pending and idle_free)
                else None
            )
            if admit_span is not None:
                host.mark("a")
                admit_span.__enter__()
                groups0, slots0 = groups_prefilled, pool.total_admissions
            if continuous and group_queue:
                # freed pages (released slots, dropped chains) may now fit
                # the next queued group's prefill — the backfill that
                # replaces the fixed episode batch
                group_decline = admit_groups()
            if pending:
                state = fill_idle(state, idle_free)
                table_dirty = True
            if admit_span is not None:
                admit_span.set(
                    groups=groups_prefilled - groups0,
                    slots=pool.total_admissions - slots0,
                )
                admit_span.__exit__(None, None, None)
            if table_dirty:
                state = state._replace(page_indices=jnp.asarray(pool.table))
            if pool.self_check:
                pool.check_invariants()
            wedged = False
            if continuous:
                # wedge detector: every slot dead, work still queued, and
                # this boundary neither prefilled nor admitted — decode
                # steps can free nothing, so give the one-boundary snapshot
                # lag a few rounds of grace and then name the stall instead
                # of silently spinning the step budget down
                if (
                    pool.total_admissions + groups_prefilled == boundary_marks
                    and (pending or group_queue)
                    and all(host_cand[v] >= total for v in range(r_slots))
                ):
                    stalled_boundaries += 1
                    wedged = True
                    if stalled_boundaries > 4:
                        # name the MINIMUM VIABLE page budget (ISSUE 19
                        # satellite): what the head of the queue needs to
                        # admit — chain pages for its prompt plus the full
                        # private region the admission gate reserves — so
                        # the fix is a number, not a bisection
                        if group_queue:
                            g_h = group_queue[0]
                            rl_h = int(real_len_h[g_h])
                        else:
                            e0 = pending[0]
                            c0 = e0[0] if isinstance(e0, tuple) else e0
                            rl_h = int(real_len_h[c0 // n])
                        need = (
                            max(-(-rl_h // ps), 1) + self.private_pages
                        )
                        raise RuntimeError(
                            f"continuous admission wedged: "
                            f"{int(finished.sum())}/{total} finished, "
                            f"{len(pending)} pending candidates + "
                            f"{len(group_queue)} queued groups, no live "
                            f"slot, and the pool ({pool.free_pages} free / "
                            f"{pool.universe_pages}) cannot admit the head "
                            f"— the page budget cannot make progress. "
                            f"Minimum viable budget for the head request: "
                            f"{need} pages (ceil(prompt {rl_h} / page_size "
                            f"{ps}) chain + {self.private_pages} private) — "
                            f"raise max_kv_pages to at "
                            f"least {need}"
                        )
                else:
                    stalled_boundaries = 0
            if sl is not None:
                serving_boundary(
                    group_decline, had_idle=len(idle_free) > 0,
                    wedged=wedged,
                )
        # graftcheck: end-hot-region

        # final blocking read closes the snapshot lag on the last occupants
        # (mark_finished, not a bare flag write: the serving ledger's
        # finish events and the sharing chain drops stay exactly-once)
        t_read = time.perf_counter()
        readback_span = telemetry.span(telemetry.ENGINE_READBACK)
        readback_span.__enter__()
        done_h = np.asarray(state.done)
        for s_i in np.nonzero(done_h)[0]:
            c = host_cand[s_i]
            if c < total:
                mark_finished(int(c))
        alive_h = int(np.asarray(state.alive_steps))
        slot_state = _file_slot_state(
            getattr(state, "mixer", None), getattr(state, "k_pages", ()),
            getattr(state, "v_pages", ()))
        mixer = getattr(state, "mixer", None)
        state_said = {**_count_mixer_stats(mixer), **slot_state,
                      **file_exit_stats(self.cfg, (mixer or {}).get("exit_stats"))}
        if cache_on:
            # park every resident cached page host-side: device page ids
            # are round-scoped, so the tree survives between rounds as a
            # host-resident index and the next round restores matched
            # prefixes from the store. Unconsumed preempt payloads drop
            # (candidate ids are round-scoped too).
            pool.flush_cache()
            for key in spilled_keys:
                self._kv_store.drop(key)
            radix_snap1 = self._radix.snapshot()
            radix_delta = {
                k: radix_snap1[k] - radix_snap0[k] for k in radix_snap1
            }
        self.last_pool_stats = {
            # which devices held this round's KV pages (role placement)
            "kv_devices": sorted(
                d.id
                # (any leaf stands in where no layer has K/V: all-lightning)
                for d in jax.tree_util.tree_leaves(
                    (state.k_pages, state))[0].devices()
            ),
            "pool_pages": pool_pages,
            "worst_case_pages": worst_pool,
            "peak_pages_used": pool.peak_pages_used,
            "preemptions": pool.preemptions,
            "budgeted": budgeted,
            # continuous-batching self-description (ISSUE 12, read by
            # tools/cb_smoke.py): which admission regime ran,
            # how much of the prompt segment was physically shared, and how
            # much mid-round backfill the fixed batch would have idled away
            "cb_mode": self.cb_mode,
            "cow_splits": pool.cow_splits,
            "pages_shared_frac": (
                round(pool.peak_shared_pages
                      / max(pool.peak_pages_used, 1), 4)
                if sharing else None
            ),
            "prefill_shared_frac": (
                round(pool.prefix_admissions
                      / max(pool.total_admissions, 1), 4)
                if sharing else None
            ),
            "backfill_admissions": backfill_admits,
            "groups_prefilled": groups_prefilled if continuous else None,
            # closed-loop control self-description (ISSUE 14): how many
            # groups the SLO shedder deferred at least once this round
            # (None = no ControlLimits attached, the controllers-off row)
            "shed_groups": (
                len(shed_groups_seen) if limits is not None else None
            ),
            # multi-tenant gateway rounds (ISSUE 19): per-class shed/preempt
            # action tally — the "actions land on low classes" contract
            # reads this (None = no gateway identity)
            "class_actions": (
                {k: dict(v) for k, v in class_actions.items()}
                if meta is not None else None
            ),
            "slot_idle_frac": (
                round(1.0 - alive_h / (r_slots * dispatched), 4)
                if dispatched else None
            ),
            # multi-turn episode continuation (ISSUE 17): in-place turn
            # resumes and the conversation-prefix tokens they kept resident
            # (None = no turn hook armed, the single-turn row)
            "turn_resumes": turn_resumes if th is not None else None,
            "turn_prefill_saved_tokens": (
                turn_saved if th is not None else None
            ),
            # tiered KV cache (ISSUE 18): per-round radix-counter deltas +
            # this round's spill/restore latency (None on the cache-off
            # control: a null, not a made-up zero)
            "prefix_cache": bool(cache_on),
            "radix_hit_rate": (
                round(
                    radix_delta["hit_tok"]
                    / max(radix_delta["lookup_tok"], 1), 4,
                ) if cache_on else None
            ),
            "prefill_tok_saved": (
                radix_delta["prefill_tok_saved"] if cache_on else None
            ),
            "radix_evictions": (
                radix_delta["evictions"] if cache_on else None
            ),
            "spilled_pages": (
                radix_delta["spilled_pages"] if cache_on else None
            ),
            "restored_pages": (
                radix_delta["restored_pages"] if cache_on else None
            ),
            "spill_restore_ms_p50": (
                round(float(np.percentile(restore_ms, 50)), 3)
                if cache_on and restore_ms else None
            ),
        }
        if not finished.all():
            missing = int((~finished).sum())
            raise RuntimeError(
                f"refill scheduler exhausted its step budget ({budget}) with "
                f"{missing}/{total} candidates unfinished — this is a bug"
            )
        if th is not None and turn_resumes == 0 and (
            window_declines or window_short
        ):
            # multi-turn window exhaustion (ISSUE 19 satellite, the PR 17
            # gotcha): every turn resume this round was declined for lack
            # of max_new_tokens window — the run silently degraded to
            # single-turn. One warning naming the observed observation
            # length and the minimum viable window.
            import warnings

            if window_declines:
                obs_max = max(t for t, _ in window_declines)
                need_w = max(w for _, w in window_declines)
                detail = (
                    f"observed observation length up to {obs_max} tokens; "
                    f"minimum viable max_new_tokens window: {need_w}"
                )
            else:
                detail = (
                    f"every finished candidate was within 2 tokens of the "
                    f"window, so no observation could seat at all "
                    f"(max_new_tokens={max_steps})"
                )
            warnings.warn(
                f"multi-turn window exhausted: all "
                f"{len(window_declines) + window_short} turn continuations "
                f"this round were declined for max_new_tokens room — the "
                f"round degraded to single-turn. {detail} (need "
                f"gen_len + obs_tokens + 1 <= max_new_tokens)",
                RuntimeWarning, stacklevel=2,
            )
        out = np.asarray(state.out).reshape(b, n, max_steps)
        lengths = np.asarray(state.lengths_buf).reshape(b, n)
        if stream is not None:
            # byte-complete final flush: whatever the boundary cadence
            # missed (fast finishes, the last chunk) streams here from the
            # already-host result tensors before the round returns
            for c in range(total):
                ln = int(lengths[c // n, c % n])
                sent = stream_sent.get(c, 0)
                if ln > sent:
                    stream_sent[c] = ln
                    stream(
                        c,
                        [int(t) for t in out[c // n, c % n, sent:ln]],
                    )
        if sl is not None:
            # realized token counts close each serving record (TPOT needs
            # them); the closed records stream to the JSONL here
            for g, uid_g in suid.items():
                sl.note_tokens(uid_g, int(lengths[g].sum()))
        logps = (
            np.asarray(state.logps_buf).reshape(b, n, max_steps)
            if self.capture_logprobs else None
        )
        gen_tokens = int(lengths.sum())
        readback_span.__exit__(None, None, None)
        host.blocked(t_read)
        if self.spec_draft:
            # acceptance accounting off the device-carried histogram: one
            # read at round end, zero per-step host traffic
            hist_h = np.asarray(state.emit_hist)
            drafted = int(np.asarray(state.draft_total))
            steps_alive = int(hist_h.sum())
            emit_tokens = int((hist_h * np.arange(hist_h.size)).sum())
            # sampler acceptance: pre-truncation accepted prefix lengths
            # (accept_total) over drafted — the emit-derived count
            # (emit_tokens - steps_alive) under-counts steps whose final
            # emitted token was an accepted draft (EOS/budget truncation)
            accepted = int(np.asarray(state.accept_total))
            accept_rate = accepted / drafted if drafted else 0.0
            tokens_per_verify_step = (
                emit_tokens / steps_alive if steps_alive else 0.0
            )
            telemetry.gauge_set(ENGINE_SPEC_ACCEPT_RATE, accept_rate)
            for n_val in range(hist_h.size):
                telemetry.hist_observe(
                    ENGINE_SPEC_EMIT_TOKENS, float(n_val),
                    count=int(hist_h[n_val]),
                )
            # grid cost: accumulated per dispatch in per-layer units (one
            # fused sweep vs (d_eff+1) per-position calls, read off the
            # step's own verify dispatch record — exact even when the
            # adaptive controller mixed regimes mid-round); scaled by
            # layer count here. vchoice is the SUMMARY spelling for the
            # stats record (the configured d's decision).
            vchoice = self._verify_dispatch_choice()
            verify_grid = verify_grid_units * self.cfg.paged_layers
            draft_grid = (
                self._grid_steps_per_call(r_slots)
                * self.cfg.paged_layers * draft_call_steps
            )
            if verify_grid:
                telemetry.counter_add(
                    ENGINE_SPEC_VERIFY_GRID_STEPS, verify_grid
                )
            self.last_spec_stats = {
                "drafter": self.spec_drafter,
                "spec_draft": self.spec_draft,
                "draft_len_final": d_cell[0],
                "draft_len_switches": d_switches,
                "accept_rate": round(accept_rate, 4),
                "tokens_per_verify_step": round(tokens_per_verify_step, 4),
                "emit_hist": hist_h.tolist(),
                "drafted": drafted,
                "verify_impl": vchoice,
                "verify_grid_steps": verify_grid,
                "draft_grid_steps": draft_grid,
                "drafter_version": drafter_version,
                "target_version": (
                    self.last_swap_versions[-1]
                    if self.last_swap_versions else None
                ),
            }
        dec_span.set(tokens=gen_tokens, steps=dispatched,
                     preemptions=pool.preemptions, **state_said,
                     **(
                         {
                             "spec_drafter": self.spec_drafter,
                             "spec_accept_rate": self.last_spec_stats[
                                 "accept_rate"],
                             "tokens_per_verify_step": self.last_spec_stats[
                                 "tokens_per_verify_step"],
                         }
                         if self.spec_draft else {}
                     ))
        dec_span.__exit__(None, None, None)
        decode_s = host.stop()
        if continuous:
            # lazy group prefills ran inside the decode loop; decode
            # throughput must not absorb their time, and each one blocked
            # the host on the device
            decode_s = max(decode_s - t_prefill, 1e-9)
            host.blocked_s += t_prefill
        if self.spec_draft:
            # aggregate attention grid steps (verify + drafter) — computed
            # directly, since the fused verify sweep and the drafter's
            # decode calls have different per-call counts
            _record_grid_telemetry(1, 1, per_call=verify_grid + draft_grid)
        else:
            _record_grid_telemetry(
                self.cfg.paged_layers, dispatched,
                per_call=self._grid_steps_per_call(r_slots),
            )
        file_loop_layer_steps(self.cfg, dispatched)
        _record_delta_telemetry(self.cfg, dispatched)
        _record_sparse_telemetry(self.cfg, dispatched, self.cache_dtype)
        _record_power_telemetry(self.cfg, dispatched)
        _record_fold_telemetry(
            self.cfg, self.prompt_pages, self.page_size, params["embed"].dtype,
            prompt_segments)
        _record_latent_decode_telemetry(
            self.cfg, dispatched, self.page_size, self.cache_dtype)
        _record_index_telemetry(
            self.cfg, dispatched, self.prompt_pages, self.private_pages,
            self.page_size, prompt_segments)
        self.last_round_stats = accumulate_round_stats(
            self.last_round_stats, prefill_s=t_prefill,
            prefill_tokens=prefill_tokens, prompt_rows=b,
            decode_s=decode_s, gen_tokens=gen_tokens,
            gen_rows=total, host=host,
        )
        self.last_round_stats.update(slot_state)
        return GenerationResult(
            tokens=out, lengths=lengths, steps_dispatched=dispatched,
            alive_slot_steps=alive_h,
            logprobs=logps,
        )

    def _generate_wave(
        self, params, lora, prompt_ids, prompt_mask,
        sampling: SamplingConfig, rng: jax.Array,
    ) -> GenerationResult:
        b, p = prompt_ids.shape
        if p != self.max_prompt_tokens:
            raise ValueError(f"prompts must be padded to {self.max_prompt_tokens}, got {p}")
        max_steps = min(sampling.max_tokens, self.max_new_tokens)
        n = sampling.n
        # an in-flight swap from an earlier wave of THIS round also covers
        # this wave's prefill (its rows haven't sampled yet)
        lora = self._round_entry_lora(lora)

        prompt_lens = np.asarray(prompt_mask).sum(axis=-1)
        prefill_tokens = int(prompt_lens.sum())
        t0 = time.perf_counter()
        with telemetry.span(telemetry.ENGINE_PREFILL, rows=b, tokens=prefill_tokens):
            prompt_k, prompt_v, last_logits, real_len, *prompt_mixer = self._prefill(
                params, lora, jnp.asarray(prompt_ids), jnp.asarray(prompt_mask)
            )
            jax.block_until_ready(last_logits)
        t_prefill = time.perf_counter() - t0
        prompt_segments = (
            _file_prefill_share(prompt_lens, self.prompt_pages, self.page_size)
            if self.cfg.hybrid else None)
        row_alive = jnp.asarray(prompt_mask).sum(axis=-1) > 0
        host = RoundHostAccount()
        dec_span = telemetry.span(telemetry.ENGINE_DECODE, rows=b * n)
        dec_span.__enter__()
        state, page_indices = self._fanout(
            prompt_k, prompt_v, last_logits, real_len, row_alive,
            prompt_mixer=prompt_mixer[0] if prompt_mixer else None,
            n=n, b=b, max_steps=max_steps,
        )
        slot_state = _file_slot_state(state.mixer, state.k_pages, state.v_pages)

        temperature = jnp.asarray(sampling.temperature, jnp.float32)
        top_p = jnp.asarray(sampling.top_p, jnp.float32)
        top_p_impl = sampling.resolved_top_p_impl(self.plan_top_p_impl)
        lora_cell = [lora]
        steps_seen = [0]

        chunk_fn = (
            self._wave_chunk_fn(
                pick_chunk(self.scan_chunk, max_steps), max_steps, top_p_impl,
                params, lora, state, rng, page_indices, temperature, top_p,
            )
            if self.scan_chunk > 1 and max_steps > 1
            else None
        )
        if chunk_fn is not None:
            k = pick_chunk(self.scan_chunk, max_steps)

            def run_step(l, s):
                return self._decode_step(
                    params, l, s, rng, page_indices, eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p,
                    top_p_impl=top_p_impl,
                )

            step = make_swap_aware_chunk_step(
                self, lora_cell, steps_seen, k, max_steps, chunk_fn, lora,
                rebuild=lambda l, s: self._wave_chunk_fn(
                    k, max_steps, top_p_impl, params, l, s, rng,
                    page_indices, temperature, top_p,
                ),
                run_chunk=lambda fn, l, s: fn(
                    params, l, s, rng, page_indices, eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p,
                ),
                run_step=run_step,
            )
            # floor chunks + shared non-divisor tail (run_nondivisor_tail
            # has the cadence invariant)
            full, rem = divmod(max_steps, k)
            state = run_decode_loop(step, state, full, 1,
                                    steps_per_call=k, host=host)
            state = run_nondivisor_tail(
                self, lora_cell, steps_seen, rem, state, run_step)
        else:

            def step(s):
                self._take_pending_lora(lora_cell, steps_seen[0])
                steps_seen[0] += 1
                return self._decode_step(
                    params, lora_cell[0], s, rng, page_indices,
                    eos_ids=self.eos_ids, temperature=temperature, top_p=top_p,
                    top_p_impl=top_p_impl,
                )

            state = run_decode_loop(step, state, max_steps, self.decode_chunk,
                                    host=host)
        t_read = time.perf_counter()
        with telemetry.span(telemetry.ENGINE_READBACK):
            out = np.asarray(state.out).reshape(b, n, max_steps)
            lengths = np.asarray(state.gen_lengths).reshape(b, n)
            logps = (
                np.asarray(state.logps).reshape(b, n, max_steps)
                if self.capture_logprobs else None
            )
            gen_tokens = int(lengths.sum())
            state_said = {
                **_count_mixer_stats(state.mixer),
                **file_exit_stats(self.cfg, (state.mixer or {}).get("exit_stats"))}
        host.blocked(t_read)
        dec_span.set(tokens=gen_tokens, steps=steps_seen[0], **state_said, **slot_state)
        dec_span.__exit__(None, None, None)
        decode_s = host.stop()
        _record_grid_telemetry(
            self.cfg.paged_layers, steps_seen[0],
            per_call=self._grid_steps_per_call(b * n),
        )
        file_loop_layer_steps(self.cfg, steps_seen[0])
        _record_delta_telemetry(self.cfg, steps_seen[0])
        _record_sparse_telemetry(self.cfg, steps_seen[0], self.cache_dtype)
        _record_power_telemetry(self.cfg, steps_seen[0])
        _record_fold_telemetry(
            self.cfg, self.prompt_pages, self.page_size, params["embed"].dtype,
            prompt_segments)
        _record_latent_decode_telemetry(
            self.cfg, steps_seen[0], self.page_size, self.cache_dtype)
        _record_index_telemetry(
            self.cfg, steps_seen[0], self.prompt_pages, self.private_pages,
            self.page_size, prompt_segments)
        self.last_round_stats = accumulate_round_stats(
            self.last_round_stats, prefill_s=t_prefill,
            prefill_tokens=prefill_tokens, prompt_rows=b,
            decode_s=decode_s, gen_tokens=gen_tokens,
            gen_rows=b * n, host=host,
        )
        self.last_round_stats.update(slot_state)
        # a wave steps in lockstep with no speculation: a row is alive at a
        # step exactly when it emits a token there
        return GenerationResult(
            tokens=out, lengths=lengths, logprobs=logps,
            steps_dispatched=steps_seen[0], alive_slot_steps=gen_tokens,
        )
