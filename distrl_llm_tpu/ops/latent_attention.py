"""Latent attention (MLA, DeepSeek-V2/V3): one cached row a token for all heads.

A token's cache row is ``[c, k_pe]``: the normed latent ``c`` (``kv_lora_rank``
values) and the rotated position key ``k_pe`` (``qk_rope_head_dim`` values,
shared by every head). A head's key is ``[c W_k[h], k_pe]`` and its value
``c W_v[h]`` (``W_kvb = [W_k | W_v]`` a head), so the same function of the cache
has two forms, and which is cheaper depends on queries a cached token:

* **expanded** (many queries: prefill, the learner): rebuild K and V per head
  from the latent, ``nope + rope``-wide scores, ``v``-wide values. The
  learner and the no-cache forward (``full`` mode) run ``expanded_attention``,
  plain XLA, which they differentiate; a prefill segment runs
  ``expanded_segment``, which folds each block of earlier keys into the
  segment's running softmax with one Mosaic kernel on a TPU
  (``expanded_fold_kernel``: the block's scores never leave VMEM) and with
  ``expanded_attention`` elsewhere. The fold is ONE algorithm whose
  parameters are read off what it is handed: the head's layout off the
  shapes (K 128 + 64 wide beside V of 128 for Kimi-VL, 192 + 64 beside 256 for
  GLM-5), which keys a query attends off whether a choice arrived (a mask, a
  tile of it read beside the tile of keys: one for every head, a learned
  index's, or one a KV head, a block-sparse layer's; its rank says which) or
  not (the two positions' order), and where the keys come from off what a block
  is: one array a head, the latent's product through W_kvb, or a pair ``(k, v)``
  a KV head, a block of the rows' K/V pages. The second is how a GQA layer's
  prefill segment runs the same fold (``models/hybrid.py::_segment_softmax``:
  several query heads a KV head, no rope part, the head's own scale);
* **absorbed** (one query a row: decode): fold ``W_k`` into the query and
  ``W_v`` into the output, so every head attends over the latent row itself:
  ``scores = (q_nope W_k[h]^T) . c + q_pe . k_pe``, ``o = (sum p c) W_v[h]``.
  The cache is read ``latent_dim`` values a token, whatever the heads. A
  row's pages are folded in a block of pages at a time (``absorbed_attention``'s
  running softmax): the gathered context of 64 rows of 21k tokens, 1.5 GB a
  layer, never exists whole.

**What is read once, and by what** (``absorbed_decode``). Rows that walk
their page tables together (a GRPO prompt's candidates) mostly name the SAME
physical pages: the prompt's. ``shared_page_walk`` reads off the tables how
many leading blocks of columns every row of a group holds in common, and the
walk is split there: a shared block is read ONCE (row 0's pages) and all the
group's (row, head) queries meet it in one product, ``[rows * H, row]`` by
``[row, block]``; the columns after it are read a row, carrying the same
running softmax on. Equal table entries are equal pages, so this is exact
whatever made them equal, and a group that shares nothing walks as it always
did. A column past a row's newest page repeats that page: no page that the row
does not hold is ever fetched. The walk is ONE algorithm in two forms, chosen
by what ``absorbed_decode`` can observe (``absorbed_decode_impl``; no
argument, no environment variable, recorded in ``dispatch_choices``): on a TPU
backend, for bf16 or float32 pages of whole 128-lane tiles,
``absorbed_decode_kernel``, ONE Mosaic launch a layer-step over the pool left
in HBM, which copies a group's shared pages itself into a double buffer in
VMEM (whole pages of ``page_size x latent_row``, the next block's copies
behind this block's products), then each row's own pages, multiplies back
over the rows' first ``kv_lora_rank`` lanes only, and writes nothing but the
running softmax; anywhere else (the CPU tests' small rows, a CPU run)
``absorbed_paged_attention``, the same walk in ``jnp``, which gathers each
block to HBM and reads it back (0.59 ms a layer where the launch takes 0.31:
PERF.md §6, PR 63) and is the launch's reference. The launch has two callers
and one body: a model whose rows attend all they see, and a model with a
learned index, which hands it the index's choice as the mask it is made as
(``chosen``: a tile of it ANDed into each block's position compare) where
walking a group's pages whole reads no more than gathering every row's
chosen tokens (``models/hybrid.py::_choice_walks_pages``).

The softmax
scale is ``(nope + rope)^-0.5`` in both. RoPE pairs are DeepSeek's interleaved ones,
``(x[2i], x[2i+1])``; the output keeps the halves apart (evens first), which a
score cannot tell as long as q and k are rotated alike.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distrl_llm_tpu.ops.attention import NEG_INF
from distrl_llm_tpu.ops.per_device import per_device

_LANES = 128  # a VMEM tile's minor axis: the kernel takes whole tiles
#: what each geometry's prefill segment (under ``dispatch_key``) and decode
#: walk (under ``decode_dispatch_key``) resolved to, "kernel" or "xla": the
#: engine's counters ``ops/latent_kernel_folds``, ``ops/softmax_kernel_folds``
#: and ``ops/latent_decode_launches`` and chip_smoke.py read it, so a run on the
#: XLA form cannot pass for the kernel
dispatch_choices: dict[tuple, str] = {}


def rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x [B, S, ..., D]`` by ``cos`` / ``sin [B, S, D/2]``, pairing
    ``(x[2i], x[2i+1])``; returns ``[rotated evens | rotated odds]``."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    extra = (None,) * (x.ndim - 3)
    cos = cos[(slice(None), slice(None)) + extra].astype(x.dtype)
    sin = sin[(slice(None), slice(None)) + extra].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def split_kvb(w_kvb: jax.Array, heads: int, nope: int, v_dim: int):
    """``W_kvb [rank, heads * (nope + v)]`` -> ``(W_k [rank, H, nope],
    W_v [rank, H, v])``."""
    w = w_kvb.reshape(w_kvb.shape[0], heads, nope + v_dim)
    return w[..., :nope], w[..., nope:]


def _heads_apart(kv, nope: int):
    """One block's K and V, a KV head's keys together: ``(k [B, K, Sk, .],
    v [B, K, Sk, .])``. A pair is that already (the rows' pages as a pool keeps
    them); one array ``[B, Sk, H, nope + v]`` (the latent through W_kvb) is cut
    at ``nope``."""
    if isinstance(kv, tuple):
        return kv
    kv = kv.transpose(0, 2, 1, 3)
    return kv[..., :nope], kv[..., nope:]


def expanded_attention(
    q_nope: jax.Array,  # [B, Sq, H, nope]
    q_pe: jax.Array,  # [B, Sq, H, rope], rotated
    kv,  # [B, Sk, H, nope + v]: the latent through W_kvb; or (k, v) a KV head
    k_pe: jax.Array,  # [B, Sk, rope], rotated
    mask: jax.Array,  # [B, Sq, Sk] bool, or [B, K, Sq, Sk] a KV head; True = attend
    carry=None,
    scale: float | None = None,
):
    """Attention with K and V a head, one block of keys folded into a running
    softmax (flash-style, in XLA: the block's float32 scores are an array in
    HBM). ``carry = (m [B, H, Sq], l [B, H, Sq], acc [B, Sq, H, v])`` in
    float32, ``None`` to start; ``expanded_finish`` gives ``[B, Sq, H, v]``.
    ``full`` mode's one form (the learner's rows are one block, and this is
    what it differentiates); a prefill segment's where ``expanded_segment``
    does not take the kernel, and the kernel's reference. The shared ``k_pe``
    is contracted on its own: it is never copied a head.

    The head's layout is read off what arrives. ``kv`` one array is latent
    attention's (K and V rebuilt a head from the latent); a pair ``(k [B, K, Sk,
    nope], v [B, K, Sk, v])`` is a GQA layer's, K and V of their own widths as
    the pages keep them, query head ``h`` reading KV head ``h // (H / K)``; a
    rope part of width 0 is none (the keys were rotated before they were
    kept). ``scale`` is the scores', ``(nope + rope)^-0.5`` where none is given
    (a key kept in more lanes than its head has hands the head's own)."""
    b, sq, h, nope = q_nope.shape
    k, v = _heads_apart(kv, nope)
    kh = k.shape[1]
    m, l, acc = carry or expanded_start(b, sq, h, v.shape[-1])
    if scale is None:
        scale = (nope + q_pe.shape[-1]) ** -0.5
    scores = jnp.einsum(
        "bqkgd,bkjd->bkgqj", q_nope.reshape(b, sq, kh, h // kh, nope), k,
        preferred_element_type=jnp.float32,
    ).reshape(b, h, sq, -1) + jnp.einsum(
        "bqhd,bkd->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32)
    # one mask for every head, or a KV head's for the query heads that share it
    seen = mask[:, None] if mask.ndim == 3 else jnp.repeat(mask, h // mask.shape[1], axis=1)
    scores = jnp.where(seen, scores * scale, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
    fix = jnp.exp(m - m_new)
    l = l * fix + p.sum(axis=-1)
    # the product head-major, then transposed: the CPU's dot refuses bf16
    # operands where the einsum itself names the token-major result
    acc = acc * fix.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bkgqj,bkjd->bkgqd", p.astype(v.dtype).reshape(b, kh, h // kh, sq, -1), v,
        preferred_element_type=jnp.float32).reshape(b, h, sq, -1).transpose(0, 2, 1, 3)
    return m_new, l, acc


def dispatch_key(heads: int, nope: int, rope: int, v_dim: int, segment: int,
                 dtype) -> tuple:
    """The key ``expanded_segment`` records its choice under: everything of a
    segment but its rows, which the choice does not depend on."""
    return (heads, nope, rope, v_dim, segment, jnp.dtype(dtype).name)


def expanded_segment_impl(q_nope: jax.Array, v_dim: int) -> str:
    """The form the folds of a prefill segment of ``q_nope [B, S, H, nope]``
    take: "kernel" on a TPU backend for bf16 operands whose segment and values
    are whole 128-lane tiles and whose keys' width is whole or half tiles
    (GLM-5's 192: the kernel reads a head's ``[K | V]`` as one block as wide as
    the array, and cuts it in VMEM; a GQA layer's key row is whole tiles by
    ``ModelConfig.key_row``), "xla" otherwise (the CPU, the tests' tiny heads,
    float32). K and V need not be one width, and neither a learned index's
    choice nor the KV heads a layer's queries share change anything here. On
    the TPU nothing falls back: a kernel that fails to lower fails the segment
    that called it."""
    s, nope = q_nope.shape[1], q_nope.shape[-1]
    whole = nope % (_LANES // 2) == 0 and v_dim % _LANES == 0 and s % _LANES == 0
    if jax.default_backend() == "tpu" and q_nope.dtype == jnp.bfloat16 and whole:
        return "kernel"
    return "xla"


def expanded_segment(q_nope, q_pe, block, start, v_dim: int, dtype,
                     chosen=None, scale: float | None = None) -> jax.Array:
    """A prefill segment's attention, ``[B, S, H, v]``: queries at positions
    ``start ..`` (every row alike) over the blocks ``0 .. start // S`` of ``S``
    keys each, block ``j`` at positions ``j * S ..`` and the last one the
    segment's own; a query sees the keys at or before it. ``block(j)`` gives
    ``(kv, k_pe [B, S, rope])`` of block ``j``, ``kv`` as ``expanded_attention``
    takes it: ``[B, S, H, nope + v]`` (latent attention: the block's product
    through W_kvb) or a GQA layer's pair ``(k [B, K, S, nope], v [B, K, S, v])``
    (the rows' K and V pages; ``q_pe`` and ``k_pe`` of width 0, ``scale`` the
    head's where a key's row is wider than its head). Each block
    is folded into the segment's running softmax by the form
    ``expanded_segment_impl`` names (recorded in ``dispatch_choices``): one
    ``expanded_fold_kernel`` launch, whose scores never leave VMEM and whose
    carry keeps the kernel's layout from the first fold to the last, or
    ``expanded_fold``, which is ``expanded_attention``. ``chosen`` of
    ``FOLD_MASK_DTYPE``, if given, is what each query attends (non-zero) over
    the row's key positions ``0 .. keys``, causality in it: ``[B, S, keys]`` one
    choice for every head (a learned index's) or ``[B, K, S, keys]`` a KV head's,
    shared by the query heads that read that head (a block-sparse layer's:
    ``ops/sparse_attention.py::segment_choice``). Either form then reads its
    block's columns of it in place of the two positions' order."""
    b, s, h, nope = q_nope.shape
    impl = expanded_segment_impl(q_nope, v_dim)
    dispatch_choices[dispatch_key(h, nope, q_pe.shape[-1], v_dim, s, q_nope.dtype)] = impl
    if impl == "kernel":
        queries, first, finish = fold_queries(q_nope, q_pe), fold_start, fold_finish
        one = per_device(expanded_fold_kernel)
    else:
        queries, first, finish = (q_nope, q_pe), expanded_start, expanded_finish
        one = expanded_fold
    carry = jax.lax.fori_loop(
        0, start // s + 1,
        lambda j, carry: one(*queries, *block(j), start, j * s, carry, chosen, scale=scale),
        first(b, s, h, v_dim))
    return finish(carry, dtype)


#: the type a choice is handed to the folds in, one byte a (query, key) pair:
#: the narrowest Mosaic loads on a v5e (a ``pred`` operand of a kernel is
#: widened to int32 at its boundary, four times the bytes)
FOLD_MASK_DTYPE = jnp.int8


def expanded_fold(q_nope, q_pe, kv, k_pe, q_start, k_start, carry=None, chosen=None,
                  *, scale: float | None = None):
    """``expanded_attention`` with the mask given as two positions: the queries
    stand at ``q_start ..``, the keys at ``k_start ..``, and a query sees the
    keys at or before it (every row alike); or, where ``chosen [B, S, keys]`` (or
    ``[B, K, S, keys]``, a KV head's) is given, as its columns ``k_start ..``
    (non-zero = attend). ``expanded_fold_kernel``'s reference, argument for
    argument after the queries (the kernel takes ``fold_queries``' one array
    for these two)."""
    b, sq = q_nope.shape[:2]
    sk = k_pe.shape[1]
    if chosen is None:
        mask = (k_start + jnp.arange(sk))[None, :] <= (q_start + jnp.arange(sq))[:, None]
        mask = jnp.broadcast_to(mask, (b, sq, sk))
    else:
        mask = jax.lax.dynamic_slice_in_dim(chosen, k_start, sk, axis=chosen.ndim - 1) != 0
    return expanded_attention(q_nope, q_pe, kv, k_pe, mask, carry, scale)


#: queries and keys of one tile of ``expanded_fold_kernel``: the widest
#: divisors of the segment no wider than these. Timed on a v5e over one layer's
#: folds at [4, 16, 1024] (PERF.md §6, PR 50), a fold with its block's product
#: through W_kvb: 0.543 ms at 1,024 x 1,024 (a head's whole block in one step:
#: one maximum, as ``expanded_attention`` takes it), 0.564 at 512 x 1,024,
#: 0.768 at 512 x 512 and 1.18-1.43 at 256 keys; the XLA form takes 2.108.
#: At [4, 64, 1024] with K 192 + 64 wide, V 256 and a choice of one byte a
#: pair (PERF.md §6, PR 57): 3.153 ms at 1,024 x 1,024 and 3.293 at 512 x 1,024;
#: with ``k_pe`` contracted in a tile of its own 3.499, 3.503 at 1,024 x 512,
#: 3.589 at 512 x 1,024, 3.763 at 512 x 512, the choice as bf16 3.488, K and V
#: sliced apart by XLA before the launch 4.427; the XLA form 10.05.
#: Over a GQA layer's K/V pages (PERF.md §6, PR 61; a fold of 1,024 keys with its
#: gather of the block's pages, 25 MB at 8 rows of 4 KV heads): at [8, 4 x 16,
#: 1024] with K 256 lanes / V 128, 3.424 ms at 1,024 x 1,024 (61% of the matrix
#: unit's peak), 3.640 at 512 x 1,024, 5.422 at 1,024 x 512, 5.162 at 512 x 512;
#: THE OTHER SOURCE OF KEYS, a page a tile read from the pool where it lies through
#: a scalar-prefetched page table (tiles of 128 keys), 12.455 at 1,024 queries and
#: 15.795 at 512; the XLA form 14.451. At [4, 8 x 8, 1024] with 128 / 128: 1.386,
#: 1.479 at 512 x 1,024, the table's 4.919, the XLA form 6.177.
#: Under a KV head's choice (PERF.md §6, PR 67; a block-sparse layer's, one byte a
#: pair, 16 query heads reading one tile of it; a fold of 1,024 keys gathered from
#: pages of 64): at [4, 2 x 16, 1024] with 128 / 128, 0.825 ms at 1,024 x 1,024,
#: 0.878 at 512 x 1,024, 1.178 at 1,024 x 512; the same fold with no choice 0.689
FOLD_TILE_Q = 1024
FOLD_TILE_K = 1024


def _whole_lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles."""
    return -(-width // _LANES) * _LANES


def _tile(n: int, most: int) -> int:
    """The widest tile of whole 128-lane vectors that divides ``n`` and is no
    wider than ``most``; ``n`` itself where it has none (the tests' blocks)."""
    fits = [t for t in range(_LANES, min(n, most) + 1, _LANES) if n % t == 0]
    return max(fits, default=n)


def fold_queries(q_nope: jax.Array, q_pe: jax.Array):
    """A segment's queries as ``expanded_fold_kernel`` reads them, made once a
    segment: a head's queries together, ``[B, H, S, .]``, ``[q_nope | q_pe]``
    padded with zeros to whole lanes (exact: a product with zero adds
    nothing), so that the rope part lies in the tile that ``q_nope``'s last
    values leave unfilled (GLM-5's 192 + 64: two whole tiles) or in one of its
    own (Kimi-VL's 128 + 64). A 1-tuple: the kernel's leading argument."""
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    pad = _whole_lanes(q.shape[-1]) - q.shape[-1]
    return (jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad))).transpose(0, 2, 1, 3),)


def fold_start(b: int, sq: int, heads: int, v_dim: int):
    """``expanded_start`` in the kernel's layout: ``(m [B, H, 1, S],
    l [B, H, 1, S], acc [B, H, S, v])``."""
    return (jnp.full((b, heads, 1, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, heads, 1, sq), jnp.float32),
            jnp.zeros((b, heads, sq, v_dim), jnp.float32))


def fold_carry(carry):
    """The kernel's carry as ``expanded_attention`` holds it: ``(m [B, H, S],
    l [B, H, S], acc [B, S, H, v])``."""
    m, l, acc = carry
    return m[:, :, 0], l[:, :, 0], acc.transpose(0, 2, 1, 3)


def fold_finish(carry, dtype) -> jax.Array:
    """``expanded_finish`` of the kernel's carry: ``[B, S, H, v]``."""
    return expanded_finish(fold_carry(carry), dtype)


def _column(row: jax.Array) -> jax.Array:
    """``[1, n]`` along the lanes -> ``[n, 1]`` down the sublanes."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _row(col: jax.Array) -> jax.Array:
    """``[n, 1]`` down the sublanes -> ``[1, n]`` along the lanes."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _fold_body(pos_ref, *refs, names: tuple, scale: float, nope: int):
    """One (row, head, tile of queries) against one tile of keys: the tile's
    scores, their maximum and their sum live and die in VMEM. ``refs`` are the
    launch's operands, results and scratch under ``names``. The tile of keys is
    ``kv`` (K, then V where no ``v`` came apart from it) and, where the head
    has lanes past K's whole tiles, ``k_pe``. Which keys a query attends is the
    tile of the choice where the launch was handed one (``chosen``, causality
    in it), the two positions' order otherwise."""
    ref = dict(zip(names, refs))
    q_ref, kv_ref, acc_s = ref["q"], ref["kv"], ref["acc_s"]
    tq, tk, v_dim = q_ref.shape[0], kv_ref.shape[0], acc_s.shape[1]
    whole = nope // _LANES * _LANES  # K's whole tiles; the rest shares ``k_pe``'s
    ki = pl.program_id(3)
    q0 = pos_ref[0] + pl.program_id(2) * tq  # the tile's first query, its first key
    k0 = pos_ref[1] + ki * tk

    @pl.when(ki == 0)
    def _():
        ref["m_s"][...] = _column(ref["m"][...])
        ref["l_s"][...] = _column(ref["l"][...])
        acc_s[...] = ref["acc"][...]

    def fold(masked: bool):
        nt = (((1,), (1,)), ((), ()))  # q . k^T
        s = None
        if "k_pe" in ref:
            # the keys' last tile: K's values past its whole tiles, then ``k_pe``
            # (which arrives at those lanes, zeros before it)
            last = ref["k_pe"][...]
            if nope > whole:
                lane = jax.lax.broadcasted_iota(jnp.int32, last.shape, 1)
                last = jnp.where(
                    lane < nope - whole, kv_ref[:, whole: whole + last.shape[1]], last)
            s = jax.lax.dot_general(
                q_ref[:, whole:], last, nt, preferred_element_type=jnp.float32)
        if whole:
            whole_s = jax.lax.dot_general(
                q_ref[:, :whole], kv_ref[:, :whole], nt,
                preferred_element_type=jnp.float32)
            s = whole_s if s is None else whole_s + s
        s = s * scale
        if masked:
            if "chosen" in ref:
                seen = ref["chosen"][...].astype(jnp.int32) != 0
            else:
                seen = (k0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
                        <= q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0))
            s = jnp.where(seen, s, NEG_INF)
        m = ref["m_s"][...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:  # a query that has seen no key yet: exp(0) of masked scores
            p = jnp.where(seen, p, 0.0)
        fix = jnp.exp(m - m_new)
        ref["m_s"][...] = m_new
        ref["l_s"][...] = ref["l_s"][...] * fix + p.sum(axis=1, keepdims=True)
        values = ref["v"][...] if "v" in ref else kv_ref[:, nope: nope + v_dim]
        acc_s[...] = acc_s[...] * fix + jnp.dot(
            p.astype(values.dtype), values, preferred_element_type=jnp.float32)

    # a tile of keys that no query of the tile sees is skipped (it changes
    # nothing); a choice is read wherever a query sees a key, and of the
    # positions' tiles the one that every query sees whole needs no mask
    if "chosen" in ref:
        pl.when(k0 <= q0 + tq - 1)(functools.partial(fold, True))
    else:
        pl.when(k0 + tk - 1 <= q0)(functools.partial(fold, False))
        pl.when((k0 + tk - 1 > q0) & (k0 <= q0 + tq - 1))(functools.partial(fold, True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        ref["m_out"][...] = _row(ref["m_s"][...])
        ref["l_out"][...] = _row(ref["l_s"][...])
        ref["acc_out"][...] = acc_s[...]


#: what Mosaic gives a kernel's buffers unasked on a v5e; a launch whose tiles
#: need more says so (``_fold_vmem``)
_VMEM_DEFAULT = 16 << 20


def _fold_vmem(tq: int, tk: int, q_width: int, kv_width: int, v_dim: int, chosen: bool):
    """``vmem_limit_bytes`` of a fold's launch, from its tiles: ``None`` where
    the default holds them (16 heads of 128 at 1,024 x 1,024: 15 MB by this
    count), twice the count otherwise (64 heads of 192 + 256 under a choice:
    25 MB by this count, 21.5 MB by Mosaic's, which refuses the launch under
    the default). Counted: the operands' blocks twice (the pipeline's two
    buffers; ``kv_width`` the lanes of a key's K, V and last tile together),
    the accumulator's scratch, and a tile's float32 scores, their weights, the
    weights' bf16 cast and the choice widened to the scores' layout."""
    blocks = 2 * (tq * q_width + tk * kv_width)
    blocks += 2 * tq * v_dim * 4 + chosen * tq * tk  # the carry in and out, the choice
    need = 2 * blocks + tq * v_dim * 4 + tq * tk * (4 + 4 + 2 + 4 * chosen)
    return None if need <= _VMEM_DEFAULT else 2 * need


@functools.partial(jax.jit, static_argnames=("scale", "tile_q", "tile_k", "interpret"))
def expanded_fold_kernel(q, kv, k_pe, q_start, k_start, carry, chosen=None,
                         *, scale: float | None = None, tile_q: int = FOLD_TILE_Q,
                         tile_k: int = FOLD_TILE_K, interpret: bool = False):
    """``expanded_attention``'s fold of one block of keys as one Mosaic kernel
    (a TPU; ``interpret`` for the CPU's tests). Without ``chosen`` the mask is
    two positions: the queries stand at ``q_start ..``, the keys at ``k_start
    ..``, and a query sees the keys at or before it. With ``chosen [B, S,
    keys]`` (``FOLD_MASK_DTYPE``; non-zero = attend, causality in it) a tile of
    it is read beside the tile of keys, the key axis at ``k_start ..``, and the
    positions only say which tiles lie wholly above the diagonal. ``chosen [B,
    K, S, keys]`` is a KV head's choice (its RANK says so): query head ``h``
    reads the tile of head ``h // (H / K)``, an index map like the keys', so the
    heads of a group name the same tile one after another and the pipeline
    copies it once a group. ``q [B, H, S,
    lanes]`` from ``fold_queries``, ``kv`` and ``k_pe [B, Sk, rope]`` as
    ``expanded_attention`` takes them, ``carry`` from ``fold_start`` or an
    earlier fold, updated in place; ``scale`` the scores', ``(nope + rope)^-0.5``
    where none is given.

    The head's layout is read off what arrives. ``kv [B, Sk, H, nope + v]``
    (latent attention): ``v`` off the carry, ``nope`` off ``kv`` less ``v``; K
    and V are the two parts of a head's block of ``kv``, ONE operand cut apart
    in VMEM (the product through W_kvb is written once, a head's keys together;
    sliced apart by XLA before the launch each part is a pass over it).
    ``kv = (k [B, K, Sk, nope], v [B, K, Sk, v])`` (a GQA layer: a block of the
    rows' K and V pages, gathered once a fold): two operands of their own
    widths, and query head ``h``'s tile of keys is KV head ``h // (H / K)``'s,
    an index map: the heads of a group follow one another in the grid and name
    the same block, which the pipeline then copies once. ``k_pe`` stays one
    vector a key, never copied a head in HBM: it arrives in the lanes that K's
    last tile leaves unfilled and joins that tile in VMEM, so that 192 + 64 is
    two whole tiles of one contraction (three matrix passes where K and
    ``k_pe`` are contracted apart: 3.50 -> 3.15 ms a fold), and 128 + 64 a tile
    of K and a tile of ``k_pe``; where K fills its tiles and there is no rope
    part (a GQA layer's 128 or 256 lanes) there is no such tile and no such
    operand.

    Grid (B, H, tiles of queries, tiles of keys), the keys innermost: a tile of
    queries keeps its running softmax in VMEM from its first tile of keys to
    its last, and a tile's scores, their maximum and their sum exist only
    there. bf16 operands into float32 products, float32 ``m`` and ``l``, the
    weights cast to the values' type before their product: ``expanded_
    attention``'s roundings. Tiles of keys past the last one a tile of queries
    can see are neither copied nor computed; a query whose tile holds none of
    its choices keeps its ``(m, l, acc)``."""
    b, h, s, q_width = q.shape
    sk, v_dim, rope = k_pe.shape[1], carry[2].shape[-1], k_pe.shape[-1]
    # a head's keys together; ``values`` where V is an operand of its own
    kv, values = kv if isinstance(kv, tuple) else (kv.transpose(0, 2, 1, 3), None)
    apart = values is not None
    group = h // kv.shape[1]  # query heads that read one head of ``kv``
    nope = kv.shape[-1] - (0 if apart else v_dim)
    whole = nope // _LANES * _LANES
    last = q_width - whole  # the keys' last tile: K past its whole tiles, then k_pe
    tq, tk = _tile(s, tile_q), _tile(sk, tile_k)
    pos = jnp.stack([q_start, k_start]).astype(jnp.int32)
    if scale is None:
        scale = (nope + rope) ** -0.5
    # the tests' tiny heads: the last tile is read whole out of kv's row
    kv = jnp.pad(kv, ((0, 0), (0, 0), (0, 0), (0, max(0, q_width - kv.shape[-1]))))

    def keys(i, j, pos):
        """The tile of keys that step ``j`` of queries' tile ``i`` reads: past
        the last one a query of the tile sees, that one again (no new copy)."""
        last = jnp.maximum(pos[0] + (i + 1) * tq - 1 - pos[1], 0) // tk
        return jnp.minimum(j, last)

    of_q = lambda w: pl.BlockSpec((None, None, tq, w), lambda b, h, i, j, pos: (b, h, i, 0))
    of_kv = lambda w: pl.BlockSpec(
        (None, None, tk, w), lambda b, h, i, j, pos: (b, h // group, keys(i, j, pos), 0))
    stat = pl.BlockSpec((None, None, 1, tq), lambda b, h, i, j, pos: (b, h, 0, i))
    operands = {"q": (q, of_q(q_width)), "kv": (kv, of_kv(kv.shape[-1]))}
    if apart:
        operands["v"] = (values, of_kv(v_dim))
    if last:
        operands["k_pe"] = (
            jnp.pad(k_pe, ((0, 0), (0, 0), (nope - whole, last - (nope - whole) - rope))),
            pl.BlockSpec((None, tk, last), lambda b, h, i, j, pos: (b, keys(i, j, pos), 0)))
    held = len(operands) + 1  # where the carry stands among the launch's arguments
    operands.update(zip(("m", "l", "acc"), zip(carry, (stat, stat, of_q(v_dim)))))
    if chosen is not None:
        # the choice's tile beside the tile of keys (``k_start`` is a multiple of
        # the block, the block of ``tk``). Its key axis is the page table's width
        # (20,992 positions in GLM-5's cell: no multiple of the tile), but a fold's
        # keys end at or before the segment's own, which whole blocks hold: the
        # ragged last tile is never named. One choice for every head, or (a
        # fourth axis) one a KV head, named by the heads that share it
        column = lambda i, j, pos: pos[1] // tk + keys(i, j, pos)
        if chosen.ndim == 3:
            tile = pl.BlockSpec(
                (None, tq, tk), lambda b, h, i, j, pos: (b, i, column(i, j, pos)))
        else:
            share = h // chosen.shape[1]
            tile = pl.BlockSpec(
                (None, None, tq, tk),
                lambda b, h, i, j, pos: (b, h // share, i, column(i, j, pos)))
        operands["chosen"] = (chosen, tile)
    names = (*operands, "m_out", "l_out", "acc_out", "m_s", "l_s", "acc_s")
    return tuple(pl.pallas_call(
        functools.partial(_fold_body, names=names, scale=scale, nope=nope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, s // tq, sk // tk),
            in_specs=[spec for _, spec in operands.values()],
            out_specs=[stat, stat, of_q(v_dim)],
            scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, v_dim), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32) for x in carry],
        input_output_aliases={held + i: i for i in range(3)},  # the carry, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_fold_vmem(
                tq, tk, q_width, _whole_lanes(kv.shape[-1]) + apart * v_dim + last, v_dim,
                chosen is not None)),
        interpret=interpret,
    )(pos, *(x for x, _ in operands.values())))


def expanded_start(b: int, sq: int, heads: int, v_dim: int):
    """The running softmax before any key."""
    return (
        jnp.full((b, heads, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, heads, sq), jnp.float32),
        jnp.zeros((b, sq, heads, v_dim), jnp.float32),
    )


def expanded_finish(carry, dtype) -> jax.Array:
    """The running softmax's output; a query that saw no key gives zeros."""
    _, l, acc = carry
    return (acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]).astype(dtype)


def absorbed_query(q_nope: jax.Array, q_pe: jax.Array, w_k: jax.Array) -> jax.Array:
    """``[q_nope W_k^T, q_pe]``: one query a (row, head) against the cached row
    itself. ``q_nope [B, H, nope]``, ``q_pe [B, H, rope]`` (rotated),
    ``w_k [rank, H, nope]`` -> ``[B, H, rank + rope]``."""
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_k.astype(q_nope.dtype))
    return jnp.concatenate([q_lat, q_pe], axis=-1)


def absorbed_attention(
    q_row: jax.Array,  # [B, H, rank + rope] from ``absorbed_query``
    latent: jax.Array,  # [B, Sk, rank + rope]: a block of the rows' cached [c, k_pe]
    seen: jax.Array,  # [B, Sk] bool: which cached rows the query may see
    scale: float,  # (nope + rope)^-0.5
    carry=None,
):
    """One block of cached rows folded into the running softmax of a decode
    step. ``carry = (m [B, H], l [B, H], acc [B, H, rank + rope])`` in float32,
    ``None`` to start. The score is one contraction over the row, and the
    values are the same row: the block is read for both. ``latent [Sk, rank +
    rope]`` is ONE block that every row attends over (``seen`` still a row's
    own): all B * H queries against it in one product."""
    m, l, acc = carry or absorbed_start(*q_row.shape)
    block = "bkd" if latent.ndim == 3 else "kd"
    scores = jnp.einsum(
        f"bhd,{block}->bhk", q_row.astype(latent.dtype), latent,
        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None], scores * scale, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.where(seen[:, None], jnp.exp(scores - m_new[..., None]), 0.0)
    fix = jnp.exp(m - m_new)
    # over the whole row, cut in ``absorbed_output``: a slice of the block
    # would be a copy of it
    acc = acc * fix[..., None] + jnp.einsum(
        f"bhk,{block}->bhd", p.astype(latent.dtype), latent,
        preferred_element_type=jnp.float32)
    return m_new, l * fix + p.sum(axis=-1), acc


def absorbed_start(b: int, heads: int, row: int):
    """A decode step's running softmax before any cached row."""
    return (jnp.full((b, heads), NEG_INF, jnp.float32),
            jnp.zeros((b, heads), jnp.float32),
            jnp.zeros((b, heads, row), jnp.float32))


#: float32 scores of one shared block, ``[rows * heads, pages * page_size]``. At
#: 2 MiB (16 pages of 128 rows for 16 rows' 16 heads) the block's two products
#: fill the MXU's rows; twice that is no faster and half is 13% slower a page
#: (a fragment of the Kimi cell's 7 layers on the v5e: PERF.md §6, PR 34)
SHARED_SCORE_BYTES = 2 << 20


def shared_pages_per_block(rows: int, heads: int, page_size: int, per: int,
                           width: int) -> int:
    """Columns of a shared block: a multiple of ``per`` (the columns a row
    gathers for itself at a time), as many as ``SHARED_SCORE_BYTES`` of scores
    and the table's width allow. From shapes alone."""
    fit = SHARED_SCORE_BYTES // (rows * heads * page_size * 4)
    return max(per, min(fit, width) // per * per)


class PageWalk(NamedTuple):
    """What ``shared_page_walk`` reads off a step's page tables."""

    cols: jax.Array  # [B, blocks * wide] page ids; past a row's newest page, that page
    shared: jax.Array  # [B // rows] leading blocks of ``wide`` columns a group's rows all hold
    newest: jax.Array  # [B // rows] the column of the newest page of a group's longest row
    stats: jax.Array  # [2] int32: (row, page) pairs attended, pages fetched


def shared_page_walk(tables: jax.Array, lengths: jax.Array, alive=None, *,
                     page_size: int, wide: int, rows: int) -> PageWalk:
    """How ``absorbed_paged_attention`` walks ``tables [B, W]``, ``rows`` rows
    together (``B`` a multiple of ``rows``), a row seeing positions ``0 ..
    lengths`` (its newest cached row is AT ``lengths``).

    A group's ``shared`` is the count of leading whole blocks of ``wide``
    columns in which every row's entries equal row 0's, no further than the
    group's longest row reaches. The counters are of live pages (a row's
    ``lengths // page_size + 1``; a row not ``alive`` has none): ``attended``
    every (row, page) pair, ``read`` a shared block's pages once a group and
    the others once a row. A few integer operations on the table: the same for
    every layer of a step."""
    b, width = tables.shape
    held = lengths // page_size  # the column of a row's newest page
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    last = jnp.take_along_axis(tables, jnp.minimum(held, width - 1)[:, None], axis=1)
    cols = jnp.where(col <= held[:, None], tables, last)
    cols = jnp.pad(cols, ((0, 0), (0, -width % wide)), mode="edge")
    groups = cols.reshape(b // rows, rows, -1, wide)
    same = (groups == groups[:, :1]).all(axis=(1, 3))  # [groups, blocks]
    newest = held.reshape(-1, rows).max(axis=1)
    shared = jnp.minimum(jnp.cumprod(same, axis=1).sum(axis=1), newest // wide + 1)
    live = held + 1 if alive is None else jnp.where(alive, held + 1, 0)
    live = live.reshape(-1, rows)
    once = shared[:, None] * wide  # columns fetched once a group
    read = jnp.minimum(once[:, 0], live.max(axis=1)) + jnp.maximum(live - once, 0).sum(axis=1)
    return PageWalk(cols, shared, newest, jnp.stack([live.sum(), read.sum()]))


def absorbed_paged_attention(
    q_row: jax.Array,  # [B, H, latent_row] from ``absorbed_query``, padded like a page's row
    pages: jax.Array,  # [pages, page_size, latent_row]: a layer's pool
    walk: PageWalk,
    lengths: jax.Array,  # [B]
    scale: float,
    chosen=None,  # [B, C * page_size]: non-zero where a row attends, of what it sees
    *, per: int, wide: int, rows: int,
):
    """A decode step's attention over each row's pages; returns the running
    softmax for ``absorbed_output``. A group walks its shared blocks first
    (``wide`` columns each, gathered once for all its rows), then the rest of
    its tables ``per`` columns a row at a time (module docstring); a group
    whose rows share nothing runs the second loop alone. ``wide`` is a
    multiple of ``per``. The plain form, and ``absorbed_decode_kernel``'s
    reference argument for argument: under a choice too (a learned index's,
    over the positions of ``walk.cols``' columns), though no model runs this
    form under one."""
    page_size = pages.shape[1]

    def group(q_g, cols_g, len_g, chosen_g, shared, newest):
        def block(j, n, of):
            at = jax.lax.dynamic_slice_in_dim(of, j * n, n, axis=of.ndim - 1)
            seen = (j * n * page_size + jnp.arange(n * page_size))[None, :] <= len_g[:, None]
            if chosen_g is not None:
                seen &= jax.lax.dynamic_slice_in_dim(
                    chosen_g, j * n * page_size, n * page_size, axis=1) != 0
            return pages[at].reshape(*of.shape[:-1], n * page_size, -1), seen

        def fold_shared(j, carry):
            return absorbed_attention(q_g, *block(j, wide, cols_g[0]), scale, carry)

        def fold_private(j, carry):
            return absorbed_attention(q_g, *block(j, per, cols_g), scale, carry)

        carry = jax.lax.fori_loop(0, shared, fold_shared, absorbed_start(*q_g.shape))
        return jax.lax.fori_loop(
            shared * (wide // per), newest // per + 1, fold_private, carry)

    return jax.tree_util.tree_map(
        lambda *parts: jnp.concatenate(parts, axis=0),
        *(group(q_row[r: r + rows], walk.cols[r: r + rows], lengths[r: r + rows],
                None if chosen is None else chosen[r: r + rows],
                walk.shared[r // rows], walk.newest[r // rows])
          for r in range(0, q_row.shape[0], rows)))


#: pages of one shared block of ``absorbed_decode_kernel`` (the copies started
#: together and multiplied together; the walk's ``wide`` is the XLA form's block
#: and sizes nothing here): no more than DECODE_BLOCK_PAGES, and no more than
#: DECODE_SCORE_BYTES of float32 scores for the group's ``rows * H`` queries.
#: Timed on a v5e at the two latent cells' shapes, 64 rows in groups of 16 over
#: prompts of 10,240-20,480 tokens and 256 decoded, us a layer (PERF.md §6, PR
#: 63): 16 heads, 388 / 323 / 305 / 313 at 2 / 4 / 8 / 16 pages where the XLA
#: walk takes 591; 64 heads under a choice, 1,007 / 991 / 1,003 at 2 / 4 / 8 where
#: the gather of the chosen rows and ``absorbed_attention`` take 2,663
DECODE_BLOCK_PAGES = 8
DECODE_SCORE_BYTES = 2 << 20
#: VMEM a launch may ask for of a v5e's 128 MiB; a group whose buffers need more
#: takes the XLA form (``_decode_vmem``)
_DECODE_VMEM_MOST = 100 << 20


def decode_block_pages(rows: int, heads: int, page_size: int) -> int:
    """Pages of a shared block of the launch, from shapes alone (above): 8 for
    16 rows' 16 heads, 4 for their 64."""
    fit = DECODE_SCORE_BYTES // (rows * heads * page_size * 4)
    return max(1, min(DECODE_BLOCK_PAGES, fit))


def _decode_vmem(rows: int, heads: int, page_size: int, row: int, itemsize: int) -> int:
    """``vmem_limit_bytes`` of a decode launch, twice what its buffers count
    to: the two page buffers (a block of shared pages and one page a row of
    the group) and the group's queries and running softmax, each twice, a
    block's float32 scores with their weights, the weights' cast and the mask
    beside them, and 4 MiB for a choice's rows (int8 in tiles of 32 sublanes,
    twice: 64k positions). 31 MiB for 16 rows' 16 heads over pages of 128 x 640
    bf16, 50 MiB for their 64 heads, over ``_DECODE_VMEM_MOST`` from about 48 rows
    of 64 heads a group."""
    queries = rows * heads
    block = decode_block_pages(rows, heads, page_size) * page_size
    pages = 2 * (block + rows * page_size) * row * itemsize
    group = 2 * queries * (row * itemsize + (row + 2 * _LANES) * 4)
    return 2 * (pages + group + queries * block * 14) + (4 << 20)


def decode_dispatch_key(heads: int, row: int, page_size: int, dtype) -> tuple:
    """The key ``absorbed_decode`` records its choice under in
    ``dispatch_choices``: the queries' heads and the pool's pages, and not the
    rows, the table's width or whether a choice arrived."""
    return ("decode", heads, row, page_size, jnp.dtype(dtype).name)


def absorbed_decode_impl(heads: int, pages: jax.Array, rows: int) -> str:
    """The form a decode step's attention of ``heads`` heads over a layer's
    latent ``pages [P, page_size, latent_row]`` takes, ``rows`` rows a group:
    "kernel" on a TPU backend for bf16 or float32 pages of whole 128-lane
    tiles (a row's lanes, a page's tokens), heads that fill the sublanes of a
    tile of that type (16 / 8: a row's heads are then whole tiles of the
    group's ``[rows * H, .]`` operand) and a group whose buffers VMEM holds
    (``_decode_vmem``: the engine's groups of 16 rows always), "xla" otherwise
    (the CPU, the tests' small rows). On the TPU nothing falls back: a launch
    that fails to lower fails the step that called it."""
    sublanes = {jnp.dtype(jnp.bfloat16): 16, jnp.dtype(jnp.float32): 8}.get(pages.dtype)
    page_size, row = pages.shape[1:]
    whole = row % _LANES == 0 and page_size % _LANES == 0
    if (jax.default_backend() == "tpu" and sublanes and whole and heads % sublanes == 0
            and _decode_vmem(rows, heads, page_size, row, pages.dtype.itemsize)
            <= _DECODE_VMEM_MOST):
        return "kernel"
    return "xla"


def absorbed_decode(q_row, pages, walk: PageWalk, lengths, scale: float, chosen=None,
                    *, rank: int, per: int, wide: int, rows: int):
    """A decode step's attention over each row's latent pages in the form
    ``absorbed_decode_impl`` names (recorded in ``dispatch_choices`` under
    ``decode_dispatch_key``): ONE ``absorbed_decode_kernel`` launch over the
    pool where it lies, or ``absorbed_paged_attention``. Returns the running
    softmax for ``absorbed_output``, whose values are the row's first ``rank``.
    ``chosen [B, C * page_size]`` of ``FOLD_MASK_DTYPE`` over the positions of
    ``walk.cols``' columns, if given, is what each row attends (non-zero) of
    what it sees: a learned index's choice."""
    impl = absorbed_decode_impl(q_row.shape[1], pages, rows)
    dispatch_choices[decode_dispatch_key(
        q_row.shape[1], pages.shape[-1], pages.shape[1], pages.dtype)] = impl
    if impl == "kernel":
        return per_device(absorbed_decode_kernel)(
            q_row, pages, walk, lengths, chosen, scale=scale, rank=rank, wide=wide,
            rows=rows)
    return absorbed_paged_attention(
        q_row, pages, walk, lengths, scale, chosen, per=per, wide=wide, rows=rows)


def _decode_body(cols_ref, ends_ref, *refs, names: tuple, scale: float, rows: int,
                 block: int, values: int):
    """One group of ``rows`` rows: its shared columns a block of ``block``
    pages at a time against all its (row, head) queries, then every column
    after them a page a row against that row's heads. ``refs`` are the
    launch's operands, results and scratch under ``names``; ``ends_ref [G, 2]``
    the group's shared columns and its longest row's newest column."""
    ref = dict(zip(names, refs))
    q_ref, pool, shared_buf, own_buf, sem = (
        ref[n] for n in ("q", "pages", "shared_buf", "own_buf", "sem"))
    m_ref, l_ref, acc_ref = ref["m"], ref["l"], ref["acc"]
    g = pl.program_id(0)
    ps = pool.shape[1]
    heads = q_ref.shape[0] // rows
    shared, newest = ends_ref[g, 0], ends_ref[g, 1]
    q = q_ref[...]
    length = ref["length"][...]  # [rows * H, 1]: a query's row's newest position

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(scores, first, weigh, end=None):
        """A block's scores ``[rows * H, tokens]`` (positions ``first ..``,
        those from ``end`` on repeats of a page) into the running softmax;
        ``weigh(p)`` the weights' product with the block's values."""
        tokens = scores.shape[1]
        pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
        seen = pos <= length
        if end is not None:
            seen &= pos < end
        if "chosen" in ref:  # a row's choice, every head of the row alike
            mine = ref["chosen"][:, pl.ds(pl.multiple_of(first, ps), tokens)]
            mine = jnp.broadcast_to(
                mine.astype(jnp.float32)[:, None, :], (rows, heads, tokens))
            seen &= mine.reshape(scores.shape) != 0
        scores = jnp.where(seen, scores * scale, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, scores.max(axis=1, keepdims=True))
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        fix = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * fix + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * fix + weigh(p.astype(pool.dtype))

    def landed(buf, slot):
        # a wait is for a size: one descriptor answers for a buffer's copies
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    # ---- the columns every row of the group holds: row 0's pages, once
    def fetch_shared(j, slot):
        for i in range(block):  # past the last shared column, that one again (masked)
            page = cols_ref[g * rows, jnp.minimum(j * block + i, shared - 1)]
            pltpu.make_async_copy(
                pool.at[page], shared_buf.at[slot, pl.ds(i * ps, ps)], sem.at[slot]).start()

    def fold_shared(j, _):
        slot = j % 2
        pl.when((j + 1) * block < shared)(lambda: fetch_shared(j + 1, 1 - slot))
        landed(shared_buf, slot)
        held = shared_buf[slot]  # [block * ps, row]
        scores = jax.lax.dot_general(
            q, held, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        fold(scores, j * block * ps,
             lambda p: jnp.dot(p, held[:, :values], preferred_element_type=jnp.float32),
             end=shared * ps)

    pl.when(shared > 0)(lambda: fetch_shared(0, 0))
    jax.lax.fori_loop(0, (shared + block - 1) // block, fold_shared, None)

    # ---- then each row's own: column ``c`` of every row of the group together
    def fetch_own(c, slot):
        for r in range(rows):  # past a row's newest page the walk repeats it (masked)
            pltpu.make_async_copy(
                pool.at[cols_ref[g * rows + r, c]], own_buf.at[slot, pl.ds(r * ps, ps)],
                sem.at[slot]).start()

    def fold_own(c, _):
        slot = (c - shared) % 2
        pl.when(c < newest)(lambda: fetch_own(c + 1, 1 - slot))
        landed(own_buf, slot)
        held = own_buf[slot].reshape(rows, ps, -1)
        scores = jax.lax.dot_general(
            q.reshape(rows, heads, -1), held, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).reshape(rows * heads, ps)
        fold(scores, c * ps,
             lambda p: jax.lax.dot_general(
                 p.reshape(rows, heads, ps), held[:, :, :values],
                 (((2,), (1,)), ((0,), (0,))),
                 preferred_element_type=jnp.float32).reshape(rows * heads, values))

    pl.when(shared <= newest)(lambda: fetch_own(shared, 0))
    jax.lax.fori_loop(shared, newest + 1, fold_own, None)


@functools.partial(jax.jit, static_argnames=(
    "scale", "rank", "wide", "rows", "block_pages", "interpret"))
def absorbed_decode_kernel(q_row, pages, walk: PageWalk, lengths, chosen=None, *,
                           scale: float, rank: int, wide: int, rows: int,
                           block_pages: int = 0, interpret: bool = False):
    """``absorbed_paged_attention``'s walk as ONE Mosaic launch over a pool
    left in HBM (a TPU; ``interpret`` for the CPU's tests): argument for
    argument the XLA form's, which is its reference, and the same running
    softmax out, ``(m [B, H], l [B, H], acc [B, H, .])`` in float32, ``acc``
    over the row's values alone (``rank`` rounded up to whole lanes).

    Grid (groups,), a step a group of ``rows`` rows whose ``rows * H`` queries
    sit in VMEM as one ``[rows * H, latent_row]`` operand. **The columns the
    group's rows all hold** (``walk.shared * wide`` of them) are copied by the
    kernel itself from row 0's pages, ``block_pages`` whole pages a block (a
    page is ``page_size x latent_row`` contiguous) into a double buffer, the
    next block's copies behind this block's arithmetic: one product ``[rows *
    H, row] x [row, block]``, one running-softmax update, one product back
    over the block's first ``rank`` lanes (a slice of a VMEM tile is free).
    **Then each row's own columns**, to the group's ``walk.newest``: column
    ``c`` of every row copied together, a row's ``H`` queries against its own
    page, the same ``m / l / acc`` carried on. A position past a row's
    ``lengths`` is masked by one compare; a column past a row's newest page
    repeats that page (``shared_page_walk``), so no page that a row does not
    hold is fetched. Nothing is written but the running softmax: no gathered
    block, no float32 scores in HBM.

    ``chosen [B, C * page_size]`` (``FOLD_MASK_DTYPE``; non-zero = attend), if
    given, is a learned index's choice over the positions of ``walk.cols``'
    columns: a group's rows of it sit in VMEM and a tile is ANDed into each
    block's position compare, every head of a row alike. The pages' type for
    the products' operands, float32 for everything else, as the XLA form.
    ``block_pages`` 0 is the launch's own choice (``decode_block_pages``); the
    tests name one to reach ragged last blocks at small sizes."""
    b, heads, row = q_row.shape
    ps, groups = pages.shape[1], b // rows
    block_pages = block_pages or decode_block_pages(rows, heads, ps)
    values = min(_whole_lanes(rank), row)
    ends = jnp.stack([walk.shared * wide, walk.newest], axis=1).astype(jnp.int32)
    of_group = lambda w: pl.BlockSpec((rows * heads, w), lambda g, *_: (g, 0))
    operands = {
        "q": (q_row.astype(pages.dtype).reshape(b * heads, row), of_group(row)),
        "length": (jnp.repeat(lengths.astype(jnp.int32), heads)[:, None], of_group(1)),
        "pages": (pages, pl.BlockSpec(memory_space=pl.ANY)),
    }
    if chosen is not None:
        operands["chosen"] = (
            chosen.reshape(groups, rows, -1),
            pl.BlockSpec((None, rows, chosen.shape[-1]), lambda g, *_: (g, 0, 0)))
    names = (*operands, "m", "l", "acc", "shared_buf", "own_buf", "sem")
    m, l, acc = pl.pallas_call(
        functools.partial(_decode_body, names=names, scale=scale, rows=rows,
                          block=block_pages, values=values),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the walk's columns and ends ride SMEM
            grid=(groups,),
            in_specs=[spec for _, spec in operands.values()],
            out_specs=[of_group(1), of_group(1), of_group(values)],
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * ps, row), pages.dtype),
                pltpu.VMEM((2, rows * ps, row), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b * heads, w), jnp.float32)
                   for w in (1, 1, values)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_decode_vmem(rows, heads, ps, row, pages.dtype.itemsize)),
        interpret=interpret,
    )(walk.cols.astype(jnp.int32), ends, *(x for x, _ in operands.values()))
    return m.reshape(b, heads), l.reshape(b, heads), acc.reshape(b, heads, values)


def absorbed_output(carry, w_v: jax.Array, dtype) -> jax.Array:
    """``(sum p c) W_v`` a head: ``[B, H, v]`` from the running softmax."""
    _, l, acc = carry
    rank = w_v.shape[0]
    o_lat = (acc[..., :rank] / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)
    return jnp.einsum("bhr,rhv->bhv", o_lat, w_v.astype(dtype))
