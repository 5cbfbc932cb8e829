"""Latent attention (MLA, DeepSeek-V2/V3): one cached row a token for all heads.

A token's cache row is ``[c, k_pe]``: the normed latent ``c`` (``kv_lora_rank``
values) and the rotated position key ``k_pe`` (``qk_rope_head_dim`` values,
shared by every head). A head's key is ``[c W_k[h], k_pe]`` and its value
``c W_v[h]`` (``W_kvb = [W_k | W_v]`` a head), so the same function of the cache
has two forms, and which is cheaper depends on queries a cached token:

* **expanded** (many queries: prefill, the learner): rebuild K and V per head
  from the latent, ``nope + rope``-wide scores, ``v``-wide values;
* **absorbed** (one query a row: decode): fold ``W_k`` into the query and
  ``W_v`` into the output, so every head attends over the latent row itself:
  ``scores = (q_nope W_k[h]^T) . c + q_pe . k_pe``, ``o = (sum p c) W_v[h]``.
  The cache is read ``latent_dim`` values a token, whatever the heads. A
  row's pages are gathered and folded in a block of pages at a time
  (``absorbed_attention``'s running softmax): the gathered context of 64 rows
  of 21k tokens, 1.5 GB a layer, never exists whole.

**What is read once** (``absorbed_paged_attention``). Rows that walk their
page tables together (a GRPO prompt's candidates) mostly name the SAME
physical pages: the prompt's. ``shared_page_walk`` reads off the tables how
many leading blocks of columns every row of a group holds in common, and the
walk is split there: a shared block is gathered ONCE (row 0's pages) and all
the group's (row, head) queries meet it in one product, ``[rows * H, row]`` by
``[row, block]``; the columns after it are gathered a row, as before (in
narrower blocks: most of a row's private columns are not reached yet),
carrying the same running softmax on. Equal table entries are equal pages, so
this is exact whatever made them equal, and a group that shares nothing walks
as it always did. A column past a row's newest page repeats that page: no page
that the row does not hold is ever fetched.

Both are plain XLA here (no Mosaic kernel yet: ROADMAP). The softmax scale is
``(nope + rope)^-0.5`` in both. RoPE pairs are DeepSeek's interleaved ones,
``(x[2i], x[2i+1])``; the output keeps the halves apart (evens first), which a
score cannot tell as long as q and k are rotated alike.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.attention import NEG_INF


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


def expanded_attention(
    q_nope: jax.Array,  # [B, Sq, H, nope]
    q_pe: jax.Array,  # [B, Sq, H, rope], rotated
    kv: jax.Array,  # [B, Sk, H, nope + v]: the latent through W_kvb
    k_pe: jax.Array,  # [B, Sk, rope], rotated
    mask: jax.Array,  # [B, Sq, Sk] bool; True = attend
    carry=None,
):
    """Attention with K and V rebuilt per head, one block of keys folded into
    a running softmax (flash-style, in XLA). ``carry = (m [B, H, Sq],
    l [B, H, Sq], acc [B, Sq, H, v])`` in float32, ``None`` to start;
    ``expanded_finish`` gives ``[B, Sq, H, v]``. A prefill segment attends over
    the earlier segments' pages block by block, so the scores of a 21k-token
    context never exist at once; the learner's rows are one block. The shared
    ``k_pe`` is contracted on its own: it is never copied a head."""
    b, sq, h, nope = q_nope.shape
    m, l, acc = carry or expanded_start(b, sq, h, kv.shape[-1] - nope)
    scale = (nope + q_pe.shape[-1]) ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q_nope, kv[..., :nope],
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bqhd,bkd->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None], scores * scale, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.where(mask[:, None], jnp.exp(scores - m_new[..., None]), 0.0)
    fix = jnp.exp(m - m_new)
    l = l * fix + p.sum(axis=-1)
    acc = acc * fix.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(kv.dtype), kv[..., nope:],
        preferred_element_type=jnp.float32)
    return m_new, l, acc


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
    *, per: int, wide: int, rows: int,
):
    """A decode step's attention over each row's pages; returns the running
    softmax for ``absorbed_output``. A group walks its shared blocks first
    (``wide`` columns each, gathered once for all its rows), then the rest of
    its tables ``per`` columns a row at a time (module docstring); a group
    whose rows share nothing runs the second loop alone."""
    page_size = pages.shape[1]

    def group(q_g, cols_g, len_g, shared, newest):
        def block(j, n, of):
            at = jax.lax.dynamic_slice_in_dim(of, j * n, n, axis=of.ndim - 1)
            seen = (j * n * page_size + jnp.arange(n * page_size))[None, :] <= len_g[:, None]
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
                walk.shared[r // rows], walk.newest[r // rows])
          for r in range(0, q_row.shape[0], rows)))


def absorbed_output(carry, w_v: jax.Array, dtype) -> jax.Array:
    """``(sum p c) W_v`` a head: ``[B, H, v]`` from the running softmax."""
    _, l, acc = carry
    rank = w_v.shape[0]
    o_lat = (acc[..., :rank] / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)
    return jnp.einsum("bhr,rhv->bhv", o_lat, w_v.astype(dtype))
