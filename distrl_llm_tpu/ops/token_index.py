"""A learned index over single tokens (DeepSeek's sparse attention; GLM-5's
``index_*`` keys): which cached tokens a query's attention sees.

Every token caches ONE index key ``k_I [D_I]`` beside its attention cache. A
query brings ``H_I`` small heads ``q_I [H_I, D_I]`` and a weight a head
``w [H_I]``, and scores every token at or before it::

    I[t, s] = scale * sum_h w[t, h] * relu(q_I[t, h] . k_I[s])      s <= t

Token ``t`` attends the ``min(k, t + 1)`` tokens of largest ``I[t, .]``, the
lower index among equal scores, AND NO OTHER. The choice is exact in every
form here, and NOTHING IS SORTED: the k-th largest score of a query is found
by counting (``kth_largest``: the float32 scores are mapped to unsigned
integers of the same order and the threshold is built from its top bits down,
two bits a pass, each pass three compares on one read of the row), what lies
above it is chosen and of what equals it the first few, again by counting
(``chosen_mask``). A row of many queries uses the mask; a decode row reads
its positions off the same mask in ascending order by rank within blocks of
128 columns (``mask_positions``: two compares and one small one-hot product,
no scatter and no gather of scalars). relu makes exact zeros, so equal scores
are the rule at the bottom of a ranking and not an accident. ``approx_max_k``
and any recall under 1 are another model.

The scores are float32 products of the operands as they are cached (bf16 on
the chip). Plain XLA throughout (on a v5e a segment's ``[4, 1024, 20480]``
k-th score takes 8 ms where the sort took 58, a decode step's positions for
64 rows 0.58 ms where ``top_k`` took 1.24: PERF.md section 6, PR 55); the
engine counts the choices made so in ``ops/index_counted_choices``. A decode
step whose group gathers at least as many tokens as its table holds does not
gather at all: the mask goes to ``latent_attention.absorbed_decode``'s launch
as it is made (``models/hybrid.py::_choice_walks_pages``; PR 63), and
``mask_positions`` is left to the tables too wide for that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distrl_llm_tpu.ops.attention import NEG_INF


def index_scale(heads: int, head_dim: int) -> float:
    """``H_I^-0.5 * D_I^-0.5``: it cannot change a choice (it is positive);
    kept so that scores compare with the published ones."""
    return heads ** -0.5 * head_dim ** -0.5


def index_scores(q_i: jax.Array, w: jax.Array, k_i: jax.Array) -> jax.Array:
    """``I`` without a mask. ``q_i [B, Sq, H, D]``, ``w [B, Sq, H]``, and
    ``k_i [B, Sk, D]`` a row's own keys or ``[Sk, D]`` ONE block of keys that
    every row scores (a prompt's, for its candidates): ``[B, Sq, Sk]``
    float32."""
    keys = "bkd" if k_i.ndim == 3 else "kd"
    dots = jnp.einsum(f"bqhd,{keys}->bqhk", q_i.astype(k_i.dtype), k_i,
                      preferred_element_type=jnp.float32)
    scale = index_scale(q_i.shape[-2], q_i.shape[-1])
    return jnp.einsum("bqh,bqhk->bqk", w.astype(jnp.float32) * scale,
                      jax.nn.relu(dots))


#: the bits of a score's ordered image that one counting pass settles: a pass
#: compares the row against ``2 ** COUNT_BITS - 1`` candidates on one read
COUNT_BITS = 2
#: positions are read off a choice mask by rank within blocks of this many
#: columns: a lane tile, and a count that bf16 holds exactly
RANK_BLOCK = 128
#: a float32's sign bit. A NUMPY scalar on purpose: a jax Array made outside a
#: trace is hoisted into the jitted program's arguments, and the engine's
#: decode step was then called with layers + 1 buffers too few (jax 0.9.0)
_SIGN = np.int32(-2 ** 31)


def ordered_image(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose INTEGER order is the floats' order: a negative's
    low 31 bits flipped, then the sign bit of every value. ``-0.0`` (a negative
    head weight times relu's zero) first becomes ``+0.0``, which it equals
    under the float compare that follows the count. No NaN is expected."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jax.lax.bitcast_convert_type(bits ^ ((bits >> 31) | _SIGN), jnp.uint32)


def from_ordered_image(image: jax.Array) -> jax.Array:
    """``ordered_image``'s inverse (``-0.0`` comes back as ``+0.0``)."""
    bits = jax.lax.bitcast_convert_type(image, jnp.int32)
    return jax.lax.bitcast_convert_type(bits ^ (~(bits >> 31) | _SIGN), jnp.float32)


def kth_largest(held: jax.Array, k: int) -> jax.Array:
    """The k-th largest value of every row of ``held [..., Sk]`` float32, as
    ``[..., 1]``, WITHOUT ordering the row: the threshold's ordered image is
    built from its top bits down, each pass keeping the largest candidate
    prefix that ``k`` or more of the row's images still reach (counts fall as
    the candidate rises, so the digit is how many candidates hold). 32 /
    COUNT_BITS passes over the row, each one read and a few compares."""
    image = ordered_image(held)
    digits = range(1, 1 << COUNT_BITS)

    def settle(i, prefix):
        shift = (32 - COUNT_BITS * (i + 1)).astype(jnp.uint32)
        holds = [(image >= (prefix | (np.uint32(d) << shift))).sum(axis=-1, keepdims=True) >= k
                 for d in digits]
        return prefix | (sum(h.astype(jnp.uint32) for h in holds) << shift)

    prefix = jax.lax.fori_loop(
        0, 32 // COUNT_BITS, settle, jnp.zeros((*held.shape[:-1], 1), jnp.uint32))
    return from_ordered_image(prefix)


def chosen_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """The choice of every query as a mask ``[..., Sk]``: True at the
    ``min(k, visible tokens)`` keys of largest score among ``visible [..., Sk]``,
    the lower index among equals. The k-th largest score is found by counting
    (``kth_largest``); everything above it is chosen, and of what equals it the
    first few, as many as are still wanted."""
    width = scores.shape[-1]
    if k >= width:
        return visible
    held = jnp.where(visible, scores, NEG_INF)
    kth = kth_largest(held, k)
    above = held > kth
    equal = held == kth
    wanted = k - above.sum(axis=-1, keepdims=True)
    first = jnp.cumsum(equal, axis=-1) <= wanted
    return (above | (equal & first)) & visible


def mask_positions(mask: jax.Array, k: int):
    """The first ``k`` True columns of every row of ``mask [B, Sk]`` in
    ascending order: (positions ``[B, k]`` int32, which of them are one ``[B,
    k]`` bool; the rest read 0). No sort, scatter or gather: a row's columns
    are counted in blocks of RANK_BLOCK, output slot ``j`` finds its block by
    comparing ``j`` with the blocks' cumulative counts, takes the block's
    running counts through a one-hot product (counts to 128 are exact in
    bf16) and its place in the block from one compare over them."""
    b, width = mask.shape
    blocks = -(-width // RANK_BLOCK)
    tiles = jnp.pad(mask, ((0, 0), (0, blocks * RANK_BLOCK - width))).reshape(
        b, blocks, RANK_BLOCK)
    running = jnp.cumsum(tiles, axis=-1, dtype=jnp.int32)  # within a block, inclusive
    total = running[..., -1]
    ends = jnp.cumsum(total, axis=-1)  # [B, blocks]: True columns to a block's end
    slot = jnp.arange(k, dtype=jnp.int32)
    before = ends[:, None, :] <= slot[None, :, None]  # [B, k, blocks]: those that end before slot j
    block = before.sum(axis=-1, dtype=jnp.int32)
    rank = slot[None, :] - jnp.where(before, total[:, None, :], 0).sum(axis=-1)
    own = block[..., None] == jnp.arange(blocks, dtype=jnp.int32)
    counts = jnp.einsum("bjn,bnw->bjw", own.astype(jnp.bfloat16), running.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    place = (counts <= rank[..., None].astype(jnp.float32)).sum(axis=-1, dtype=jnp.int32)
    seen = slot[None, :] < ends[:, -1:]
    return jnp.where(seen, block * RANK_BLOCK + place, 0), seen


def chosen_tokens(scores: jax.Array, lengths: jax.Array, k: int):
    """One query a row at position ``lengths [B]``: ``scores [B, Sk]`` over
    positions ``0 .. Sk``. Returns (positions ``[B, min(k, Sk)]`` int32, seen
    ``[B, min(k, Sk)]`` bool): the chosen tokens in ascending position
    (``chosen_mask``'s set, read off by ``mask_positions``), and which entries
    are one (a row with fewer than ``k`` tokens has the rest False)."""
    width = scores.shape[-1]
    pos = jnp.arange(width, dtype=jnp.int32)
    visible = pos[None, :] <= lengths[:, None]
    if k >= width:
        return jnp.broadcast_to(pos, visible.shape), visible
    return mask_positions(chosen_mask(scores, visible, k), k)


def index_paged_scores(q_i: jax.Array, w: jax.Array, key_pages: jax.Array, walk,
                       *, per: int, wide: int, rows: int) -> jax.Array:
    """A decode step's index scores over each row's pages: ``[B, blocks * wide
    * page_size]`` float32, position ``p`` of a row in column ``p`` (what lies
    past a row's newest token is garbage: the caller masks by length).
    ``q_i [B, H, D]``, ``w [B, H]``, ``key_pages [pages, page_size, D]``, and
    ``walk`` the step's ``latent_attention.PageWalk``: the leading blocks of
    ``wide`` columns that a group's ``rows`` rows all hold are gathered ONCE
    (row 0's pages) and meet all the group's queries in one product, the rest
    ``per`` columns a row at a time as far as the group's longest row reaches
    (``absorbed_paged_attention``'s walk, for keys of ``D`` values)."""
    page_size = key_pages.shape[1]
    width = walk.cols.shape[1]

    def group(q_g, w_g, cols_g, shared, newest):
        def put(out, j, n, keys):
            scores = index_scores(q_g[:, None], w_g[:, None], keys)[:, 0]
            return jax.lax.dynamic_update_slice_in_dim(
                out, scores, j * n * page_size, axis=1)

        def fold_shared(j, out):
            at = jax.lax.dynamic_slice_in_dim(cols_g[0], j * wide, wide)
            return put(out, j, wide, key_pages[at].reshape(wide * page_size, -1))

        def fold_private(j, out):
            at = jax.lax.dynamic_slice_in_dim(cols_g, j * per, per, axis=1)
            return put(out, j, per, key_pages[at].reshape(rows, per * page_size, -1))

        out = jnp.zeros((q_g.shape[0], width * page_size), jnp.float32)
        out = jax.lax.fori_loop(0, shared, fold_shared, out)
        return jax.lax.fori_loop(
            shared * (wide // per), newest // per + 1, fold_private, out)

    return jnp.concatenate([
        group(q_i[r: r + rows], w[r: r + rows], walk.cols[r: r + rows],
              walk.shared[r // rows], walk.newest[r // rows])
        for r in range(0, q_i.shape[0], rows)], axis=0)
