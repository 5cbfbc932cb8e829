"""A learned index over single tokens (DeepSeek's sparse attention; GLM-5's
``index_*`` keys): which cached tokens a query's attention sees.

Every token caches ONE index key ``k_I [D_I]`` beside its attention cache. A
query brings ``H_I`` small heads ``q_I [H_I, D_I]`` and a weight a head
``w [H_I]``, and scores every token at or before it::

    I[t, s] = scale * sum_h w[t, h] * relu(q_I[t, h] . k_I[s])      s <= t

Token ``t`` attends the ``min(k, t + 1)`` tokens of largest ``I[t, .]``, the
lower index among equal scores, AND NO OTHER. The choice is exact in every
form here: ``jax.lax.top_k`` (which orders equals by index) gives a decode
row's chosen positions, and a row of many queries takes its k-th largest
score from a sort of the scores and keeps what lies above it and the first of
what equals it, by counting (``chosen_mask``): the same set, as a mask. relu
makes exact zeros, so equal scores are the rule at the bottom of a ranking and
not an accident. ``approx_max_k`` and any recall under 1 are another model.

The scores are float32 products of the operands as they are cached (bf16 on
the chip). Plain XLA throughout: a kernel for the choice and for the gather
of the chosen rows is ROADMAP's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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


def chosen_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """The choice of every query as a mask ``[..., Sk]``: True at the
    ``min(k, visible tokens)`` keys of largest score among ``visible [..., Sk]``,
    the lower index among equals. The k-th largest score is read off the sorted
    scores; everything above it is chosen, and of what equals it the first few, as
    many as are still wanted."""
    width = scores.shape[-1]
    if k >= width:
        return visible
    held = jnp.where(visible, scores, NEG_INF)
    # a sort of the values alone, and not a stable one: ``top_k`` and a stable
    # sort each carry an index beside every value (equal values are one value)
    kth = jnp.sort(held, axis=-1, stable=False)[..., width - k: width - k + 1]
    above = held > kth
    equal = held == kth
    wanted = k - above.sum(axis=-1, keepdims=True)
    first = jnp.cumsum(equal, axis=-1) <= wanted
    return (above | (equal & first)) & visible


def chosen_tokens(scores: jax.Array, lengths: jax.Array, k: int):
    """One query a row at position ``lengths [B]``: ``scores [B, Sk]`` over
    positions ``0 .. Sk``. Returns (positions ``[B, min(k, Sk)]`` int32, seen
    ``[B, min(k, Sk)]`` bool): the chosen tokens, and which entries are one (a
    row with fewer than ``k`` tokens has the rest False)."""
    width = scores.shape[-1]
    pos = jnp.arange(width, dtype=jnp.int32)
    held = jnp.where(pos[None, :] <= lengths[:, None], scores, NEG_INF)
    _, at = jax.lax.top_k(held, min(k, width))
    at = at.astype(jnp.int32)
    return at, at <= lengths[:, None]


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
