"""Native paged decode attention — our own Pallas TPU kernels.

Why this file exists (round 3, first silicon): both jaxlib paged-attention
kernels are unusable for head_dim % 128 != 0 models (e.g. Qwen2.5-0.5B,
hd=64, 14q/2kv). Their manual-DMA design slices the KV page array per
kv-head (``pages.at[head_index]`` — MultiPageAsyncCopyDescriptor,
paged_attention_kernel.py:52), and Mosaic rejects any ``tpu.memref_slice``
whose minor dimension is not lane-aligned: "Slice shape along dimension 3
must be aligned to tiling (128), but is 64". The newer ragged kernel
hard-asserts 128-lane accumulator shapes at trace time instead.

These kernels take the other road: **no manual DMA at all**. The page gather
happens in the k/v BlockSpec ``index_map``, which reads the scalar-prefetched
page table, and the pipeline emitter moves whole ``[K, 1, page_size,
head_dim]`` blocks, never slicing inside the minor dims — the pattern the
flash/splash launches use at d=64.

**What ``paged_impl="auto"`` runs on a TPU is ``paged_attention_native``**
(PR 32; timed on a v5e at 28 / 4 heads of 128, page 128, 64 rows of 129-640
tokens, 14 calls a step; PERF.md §6 has the table of every launch). One grid
step is one row's KV for ALL kv heads and up to ``native_pages_per_step``
pages — grid (B, ceil(pps / ppb)), one to two steps a row at that geometry,
0.25-1.3 MB a step:

* **The page walk is bounded by the row's length.** ``live_page_walk``
  rewrites the table so that a page slot past the row's last live page names
  the block that slot fetched at the previous grid step; the pipeline sees an
  unchanged block index and issues no copy. A row of length 0 fetches nothing
  and emits zeros.
* **One softmax a grid step, not one a page.** The body has one branch per
  count of live pages in the block (``pl.when(live == n)``, n = 1..ppb);
  branch n computes the n score tiles, ONE running-max / running-sum update
  over all of them, and n p·V products. A chain of per-page online-softmax
  updates costs 1.7x the arithmetic's time at the same transfers (121.6
  against 72.3 us a call with the DMAs taken out), and pages past the length
  are never computed on, so a poisoned page there cannot reach the output.

A call takes 91-98 us at that geometry against 86 us for its DMAs alone
(PERF.md §6, PR 32 and PR 47), where jaxlib's launch takes 242-435 us.
Float32 operands cost the MXU nothing here: Mosaic's default-precision
float32 dot is one bf16 pass, bit for bit what a bf16 operand gives.

The int8 path consumes the engine's COMPACT per-token scales ([K, P, ps,
1] f32, ops/paged.py::quantize_pages) directly: dequantization is one broadcast
multiply in VMEM, so int8 stays a bandwidth win (~1.03 bytes/element
moved) rather than the 5 bytes/element of jaxlib's pre-broadcast wrapper.

Parity: CI pins numerics against ``paged_attention_reference`` under the
Pallas interpreter and compiles the kernels for a described v5e
(tests/test_tpu_compile.py); ``chip_smoke.py`` compares them with the
reference on the chip (SURVEY §2b N1/N10).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.paged_attention.quantization_utils import (
    MAX_INT8,  # 127.5 — the to_int8/from_int8 contract the pages use
)

NEG_INF = -1e30

#: VMEM one grid step's K and V blocks may take, double-buffered by the
#: pipeline (2.6 MB at 4 kv heads of 128, page 128, 5 pages a row)
KV_VMEM_BUDGET_BYTES = 4 * 2**20
#: most pages one grid step moves: the body holds one unrolled branch per
#: live-page count, and past 4 pages of 128 a step nothing was gained
#: (8k contexts: 312.6 / 311.8 / 309.7 us a call at 4 / 8 / 16; PERF.md §6)
MAX_PAGES_PER_STEP = 8
_LANES = 128  # a VMEM tile's minor dimension; narrower blocks are padded to it


def native_pages_per_step(
    *, num_kv_heads: int, head_dim: int, page_size: int, pps: int,
    kv_itemsize: int = 2, quantized: bool = False, v_head_dim: int = 0,
) -> int:
    """Pages of one row (all kv heads) that one grid step of
    ``paged_attention_native`` moves: as many as ``KV_VMEM_BUDGET_BYTES``
    holds of K and V, double-buffered, at most ``MAX_PAGES_PER_STEP`` and at
    most the row's ``pps``. Chosen from what the launch observes (the shapes
    and the pages' dtype) and from nothing else. ``v_head_dim`` is V's width
    where it is not K's (0: it is); a width takes whole lane tiles in VMEM
    (64 -> 128, 192 -> 256)."""
    lanes = lambda width: -(-width // _LANES) * _LANES
    token = lanes(head_dim) + lanes(v_head_dim or head_dim)  # K and V
    if quantized:
        # the compact [ps, 1] float32 scales take a whole lane tile a token
        token += 2 * _LANES * 4 // kv_itemsize
    page = num_kv_heads * page_size * token * kv_itemsize
    return max(1, min(pps, MAX_PAGES_PER_STEP,
                      KV_VMEM_BUDGET_BYTES // (2 * page)))


def live_page_walk(tables: jax.Array, lengths: jax.Array, *, page_size: int,
                   ppb: int) -> jax.Array:
    """The page table ``paged_attention_native``'s index maps read: [B,
    nblk·ppb] page ids in grid order (row, block), where a slot past its
    row's length repeats what the SAME in-block slot held at the grid step
    before. Each in-block slot is its own pipelined operand, and the pipeline
    copies a block only when its index changes between consecutive grid
    steps, so a repeated id is a page that is never fetched: the walk is
    bounded by the length (85.5 against 117.7 us a call for the DMAs alone,
    table entries past the length naming arbitrary pages; PERF.md §6)."""
    batch, width = tables.shape
    steps = batch * width // ppb
    slot = jnp.arange(width, dtype=jnp.int32)[None, :]
    live = (slot * page_size < lengths[:, None]).reshape(steps, ppb)
    step = jnp.arange(steps, dtype=jnp.int32)[:, None]
    last_live = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    # before any live step there is nothing to repeat: step 0's own entry
    return jnp.take_along_axis(
        tables.reshape(steps, ppb), jnp.maximum(last_live, 0), axis=0
    ).reshape(batch, width)


def _make_native_kernel(*, page_size: int, ppb: int, nblk: int,
                        quantized: bool):
    """Kernel body for ``paged_attention_native`` (module header): grid (B,
    nblk), ``ppb`` pages of all kv heads a step, each page its own operand."""
    dims_qk = (((2,), (2,)), ((0,), (0,)))  # [K,G,hd] x [K,ps,hd] -> [K,G,ps]
    dims_pv = (((2,), (1,)), ((0,), (0,)))  # [K,G,ps] x [K,ps,hv] -> [K,G,hv]

    def kernel(lengths_ref, tables_ref, q_ref, *rest):
        k_refs = rest[0:ppb]
        v_refs = rest[ppb:2 * ppb]
        ks_refs = rest[2 * ppb:3 * ppb] if quantized else None
        vs_refs = rest[3 * ppb:4 * ppb] if quantized else None
        o_ref, m_scr, l_scr, acc_scr = rest[-4:]
        b = pl.program_id(0)
        jb = pl.program_id(1)

        @pl.when(jb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        length = lengths_ref[b]
        first = jb * (ppb * page_size)  # the block's first position

        def load(refs, scale_refs, i):
            x = refs[i][:, 0].astype(jnp.float32)  # [K, ps, hd]
            if quantized:
                # compact per-token absmax scales; dequant = w * scale /
                # MAX_INT8 (quantization_utils.from_int8 contract — 127.5,
                # not 127: /127 would bias every K/V value by +0.39%)
                x = x * (scale_refs[i][:, 0] * (1.0 / MAX_INT8))
            return x

        def attend(n):
            """The block's first ``n`` pages, all live, under ONE softmax
            update; only the last of them can hold positions past the
            length."""
            q = q_ref[...].astype(jnp.float32)  # [K, G, hd] (pre-scaled)
            scores = [
                jax.lax.dot_general(
                    q, load(k_refs, ks_refs, i),
                    dims_qk, preferred_element_type=jnp.float32,
                )
                for i in range(n)
            ]  # n x [K, G, ps]
            pos = first + (n - 1) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, page_size), 2
            )
            scores[-1] = jnp.where(pos < length, scores[-1], NEG_INF)
            m_prev = m_scr[...]  # [K, G, 1]
            m_new = functools.reduce(
                jnp.maximum,
                [jnp.max(s, axis=2, keepdims=True) for s in scores], m_prev,
            )
            alpha = jnp.exp(m_prev - m_new)
            probs = [jnp.exp(s - m_new) for s in scores]
            l_scr[...] = alpha * l_scr[...] + sum(
                jnp.sum(p, axis=2, keepdims=True) for p in probs
            )
            acc_scr[...] = acc_scr[...] * alpha + sum(
                jax.lax.dot_general(
                    p, load(v_refs, vs_refs, i),
                    dims_pv, preferred_element_type=jnp.float32,
                )
                for i, p in enumerate(probs)
            )
            m_scr[...] = m_new

        # live pages of THIS block: 0 for a block past the length (and for a
        # row of length 0), else 1..ppb — one static branch each
        live = jnp.clip(
            (length - first + page_size - 1) // page_size, 0, ppb
        )
        for n in range(1, ppb + 1):
            pl.when(live == n)(functools.partial(attend, n))

        @pl.when(jb == nblk - 1)
        def _emit():
            # rows with length 0 (empty decode slots) never accumulate: emit 0
            # instead of 0/0 — their logits are discarded by the done mask, but
            # NaNs must not exist to propagate
            o_ref[...] = (
                acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
            ).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_block", "interpret"),
)
def paged_attention_native(
    q: jax.Array,  # [B, H, hd] — pre-scaled by hd**-0.5 (op contract)
    k_pages: jax.Array,  # [K, P, ps, hd] bf16/f32, or int8 weight
    v_pages: jax.Array,  # [K, P, ps, hv]: V's own width, which need not be K's
    lengths: jax.Array,  # i32 [B]
    page_indices: jax.Array,  # i32 [B, pps]
    k_scales: jax.Array | None = None,  # f32 [K, P, ps, 1] compact (int8)
    v_scales: jax.Array | None = None,
    *,
    page_size: int | None = None,
    pages_per_block: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """The launch ``paged_impl="auto"`` runs on a TPU (module header). Its
    name is what the benchmark's kernel metrics select the trace events by
    (``%paged_attention_native ``; tests/test_tpu_compile.py holds it).
    ``pages_per_block`` 0 is the launch's own choice from the shapes
    (``native_pages_per_step``); the tests name one to reach rows of several
    blocks at small sizes. K's width is q's; V's is the output's ``[B, H,
    hv]``, read off the V pages: each array is moved at its own width."""
    batch, num_q_heads, head_dim = q.shape
    num_kv_heads, total_pages, ps, head_dim_k = k_pages.shape
    head_dim_v = v_pages.shape[-1]
    if page_size is None:
        page_size = ps
    if head_dim_k != head_dim:
        raise ValueError(f"head_dim mismatch: {head_dim_k} vs {head_dim}")
    if num_q_heads % num_kv_heads:
        raise ValueError(
            f"H={num_q_heads} not divisible by K={num_kv_heads}"
        )
    if pages_per_block < 0:
        raise ValueError(
            f"pages_per_block must be >= 0, got {pages_per_block}"
        )
    groups = num_q_heads // num_kv_heads
    _, pps = page_indices.shape
    quantized = k_scales is not None
    ppb = min(pps, pages_per_block) or native_pages_per_step(
        num_kv_heads=num_kv_heads, head_dim=head_dim, page_size=page_size,
        pps=pps, kv_itemsize=k_pages.dtype.itemsize, quantized=quantized,
        v_head_dim=head_dim_v,
    )
    nblk = -(-pps // ppb)

    lengths = lengths.astype(jnp.int32)
    # live entries are clamped so that a stale id stays addressable; entries
    # past the length (and the ragged final block's padding) are replaced by
    # the walk and never fetched
    tables = jnp.clip(page_indices.astype(jnp.int32), 0, total_pages - 1)
    tables = jnp.pad(tables, ((0, 0), (0, nblk * ppb - pps)))
    tables = live_page_walk(tables, lengths, page_size=page_size, ppb=ppb)
    q4 = q.reshape(batch, num_kv_heads, groups, head_dim)

    # index_maps receive the grid indices plus EVERY scalar-prefetch ref
    # (lengths, tables) appended — the page gather reads the table ref
    q_spec = pl.BlockSpec(
        (None, num_kv_heads, groups, head_dim),
        lambda b, j, lens, tabs: (b, 0, 0, 0),
    )

    def page_spec(i, minor):
        return pl.BlockSpec(
            (num_kv_heads, 1, page_size, minor),
            lambda b, j, lens, tabs, i=i: (0, tabs[b, j * ppb + i], 0, 0),
        )

    # the SAME pool array rides as ppb inputs, one per in-block page — each
    # gets its own index_map gather, so the pipeline emitter still only
    # ever moves whole [K, 1, ps, hd] blocks (never slicing the minor dims)
    in_specs = [q_spec] + [page_spec(i, head_dim) for i in range(ppb)] + [
        page_spec(i, head_dim_v) for i in range(ppb)]
    operands = [q4] + [k_pages] * ppb + [v_pages] * ppb
    if quantized:
        in_specs += [page_spec(i, 1) for i in range(ppb)] * 2
        operands += [k_scales] * ppb + [v_scales] * ppb

    out = pl.pallas_call(
        _make_native_kernel(
            page_size=page_size, ppb=ppb, nblk=nblk, quantized=quantized
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # lengths, tables ride SMEM
            grid=(batch, nblk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, num_kv_heads, groups, head_dim_v),
                lambda b, j, lens, tabs: (b, 0, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((num_kv_heads, groups, 1), jnp.float32),
                pltpu.VMEM((num_kv_heads, groups, 1), jnp.float32),
                pltpu.VMEM((num_kv_heads, groups, head_dim_v), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, num_kv_heads, groups, head_dim_v), q.dtype
        ),
        interpret=interpret,
    )(lengths, tables, *operands)
    return out.reshape(batch, num_q_heads, head_dim_v)


def _make_verify_kernel(*, page_size: int, ppb: int, nblk: int, s_len: int,
                        groups: int, quantized: bool):
    """Kernel body for ``paged_attention_native_verify``: an S-QUERY draft
    block per row — the speculative-decode verify forward in ONE grid sweep,
    grid (B, ceil(pps/ppb)), ``ppb`` pages of ALL kv heads a step, each page
    its own operand gathered by an ``index_map`` over the scalar-prefetched
    table (whole-block pipelined moves: the one DMA pattern proven at
    head_dim 64, the reason this file exists).

    Unrolled, the verify forward issues S separate ``paged_attention_op``
    dispatches a step, S sweeps over the same KV bytes. Here the S queries
    ride INSIDE the block, folded into the query-group axis as [K, S·G, hd],
    so the whole (d+1)-token verify costs one sweep. The body is a chain of
    per-page online-softmax updates carried in registers, the m/l/acc
    scratch touched once a grid step; it bounds neither its page walk by the
    length nor its softmax to one update a step, as ``paged_attention_native``
    does (no cell runs speculation: ROADMAP D5 gives it that body or retires
    it). Padded table slots of a ragged final block repeat the row's last
    page and are fully masked.

    Causality is per QUERY: draft position i (query rows i·G..(i+1)·G−1)
    attends key positions < lengths + i + 1 — the prefix plus draft tokens
    ≤ i, exactly the ``lengths + i + 1`` ladder the unrolled path passes
    per dispatch. The limit is a per-row vector built from a static
    row→position iota, so the mask is one vectorized compare, not a loop.

    Numerical safety: every query row has at least one attendable
    position — query i's own token sits at position lengths + i <
    lengths + i + 1, and block 0 always covers position 0 < lengths + 1 —
    so the running max is finite after block 0 for every row, and a page
    whose positions all sit past the limit folds in as
    ``exp(NEG_INF − m)`` = 0 exactly."""

    sg = s_len * groups

    def kernel(lengths_ref, tables_ref, q_ref, *rest):
        k_refs = rest[0:ppb]
        v_refs = rest[ppb:2 * ppb]
        if quantized:
            ks_refs = rest[2 * ppb:3 * ppb]
            vs_refs = rest[3 * ppb:4 * ppb]
            o_ref, m_scr, l_scr, acc_scr = rest[4 * ppb:]
        else:
            ks_refs = vs_refs = None
            o_ref, m_scr, l_scr, acc_scr = rest[2 * ppb:]
        b = pl.program_id(0)
        jb = pl.program_id(1)

        @pl.when(jb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        length = lengths_ref[b]
        # per-query-row causal limit: query row r = i·G + g judges draft
        # position i = r // G and may read positions < length + i + 1
        qpos = jax.lax.broadcasted_iota(jnp.int32, (1, sg, 1), 1) // groups
        limit = length + qpos + 1  # [1, S·G, 1]

        # the verify block extends the sequence by s_len tokens (their KV
        # is already resident — written before the attention call), so
        # blocks are live up to length + s_len, not length
        @pl.when(jb * (ppb * page_size) < length + s_len)
        def _block():
            q = q_ref[...].astype(jnp.float32)  # [K, S·G, hd] (pre-scaled)
            m = m_scr[...]  # [K, S·G, 1]
            l = l_scr[...]  # noqa: E741
            acc = acc_scr[...]  # [K, S·G, hd]
            for i in range(ppb):  # static unroll: ppb block loads per step
                k = k_refs[i][:, 0].astype(jnp.float32)  # [K, ps, hd]
                v = v_refs[i][:, 0].astype(jnp.float32)
                if quantized:
                    k = k * (ks_refs[i][:, 0] * (1.0 / MAX_INT8))
                    v = v * (vs_refs[i][:, 0] * (1.0 / MAX_INT8))
                s = jax.lax.dot_general(
                    q, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )  # [K, S·G, ps]
                pos = (jb * ppb + i) * page_size + jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, page_size), 2
                )
                s = jnp.where(pos < limit, s, NEG_INF)  # [K, S·G, ps]
                m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(p, axis=2, keepdims=True)  # noqa: E741
                acc = acc * alpha + jax.lax.dot_general(
                    p, v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
                m = m_new
            m_scr[...] = m
            l_scr[...] = l
            acc_scr[...] = acc

        @pl.when(jb == nblk - 1)
        def _emit():
            o_ref[...] = (
                acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
            ).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_block", "interpret"),
)
def paged_attention_native_verify(
    q: jax.Array,  # [B, S, H, hd] — pre-scaled by hd**-0.5 (op contract)
    k_pages: jax.Array,  # [K, P, ps, hd] bf16/f32, or int8 weight
    v_pages: jax.Array,
    lengths: jax.Array,  # i32 [B] — RESIDENT tokens BEFORE the draft block
    page_indices: jax.Array,  # i32 [B, pps]
    k_scales: jax.Array | None = None,  # f32 [K, P, ps, 1] compact (int8)
    v_scales: jax.Array | None = None,
    *,
    page_size: int | None = None,
    pages_per_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Launch for ``_make_verify_kernel``: the whole S-token draft-block
    verify in one (B, ceil(pps / pages_per_block)) sweep. The S draft
    tokens' KV must already be resident in the pages (the verify forward
    writes them first); query position i attends keys < lengths + i + 1.
    Returns [B, S, H, hd]."""
    batch, s_len, num_q_heads, head_dim = q.shape
    num_kv_heads, total_pages, ps, head_dim_k = k_pages.shape
    if page_size is None:
        page_size = ps
    if head_dim_k != head_dim:
        raise ValueError(f"head_dim mismatch: {head_dim_k} vs {head_dim}")
    if num_q_heads % num_kv_heads:
        raise ValueError(
            f"H={num_q_heads} not divisible by K={num_kv_heads}"
        )
    if pages_per_block < 1:
        raise ValueError(
            f"pages_per_block must be >= 1, got {pages_per_block}"
        )
    groups = num_q_heads // num_kv_heads
    _, pps = page_indices.shape
    quantized = k_scales is not None
    ppb = min(pages_per_block, pps)
    nblk = -(-pps // ppb)

    tables = jnp.clip(page_indices.astype(jnp.int32), 0, total_pages - 1)
    pad = nblk * ppb - pps
    if pad:
        tables = jnp.concatenate(
            [tables, jnp.broadcast_to(tables[:, -1:], (batch, pad))], axis=1
        )
    # [B, S, H, hd] → [B, K, S·G, hd]: head h = kv·G + g (the reshape
    # convention every kernel in this file uses), query row r = i·G + g
    q4 = (
        q.reshape(batch, s_len, num_kv_heads, groups, head_dim)
        .transpose(0, 2, 1, 3, 4)
        .reshape(batch, num_kv_heads, s_len * groups, head_dim)
    )

    q_spec = pl.BlockSpec(
        (None, num_kv_heads, s_len * groups, head_dim),
        lambda b, j, lens, tabs: (b, 0, 0, 0),
    )

    def kv_spec(i):
        return pl.BlockSpec(
            (num_kv_heads, 1, page_size, head_dim),
            lambda b, j, lens, tabs, i=i: (0, tabs[b, j * ppb + i], 0, 0),
        )

    def scale_spec(i):
        return pl.BlockSpec(
            (num_kv_heads, 1, page_size, 1),
            lambda b, j, lens, tabs, i=i: (0, tabs[b, j * ppb + i], 0, 0),
        )

    in_specs = (
        [q_spec]
        + [kv_spec(i) for i in range(ppb)]
        + [kv_spec(i) for i in range(ppb)]
    )
    operands = [q4] + [k_pages] * ppb + [v_pages] * ppb
    if quantized:
        in_specs += (
            [scale_spec(i) for i in range(ppb)]
            + [scale_spec(i) for i in range(ppb)]
        )
        operands += [k_scales] * ppb + [v_scales] * ppb

    out = pl.pallas_call(
        _make_verify_kernel(
            page_size=page_size, ppb=ppb, nblk=nblk, s_len=s_len,
            groups=groups, quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, nblk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, num_kv_heads, s_len * groups, head_dim),
                lambda b, j, lens, tabs: (b, 0, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((num_kv_heads, s_len * groups, 1), jnp.float32),
                pltpu.VMEM((num_kv_heads, s_len * groups, 1), jnp.float32),
                pltpu.VMEM(
                    (num_kv_heads, s_len * groups, head_dim), jnp.float32
                ),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, num_kv_heads, s_len * groups, head_dim), q.dtype
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), tables, *operands)
    return (
        out.reshape(batch, num_kv_heads, s_len, groups, head_dim)
        .transpose(0, 2, 1, 3, 4)
        .reshape(batch, s_len, num_q_heads, head_dim)
    )
