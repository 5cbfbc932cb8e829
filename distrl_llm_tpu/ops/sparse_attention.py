"""Block-sparse attention with a learned-free selector (InfLLM-V2, one stage).

Keys are mean-pooled, ``kernel_size`` at a time every ``kernel_stride``
tokens, into a SELECTOR cache. A query scores the pooled keys that end at or
before it (a softmax per head, summed over the heads that share a KV head);
a block of ``block_size`` tokens scores as its best overlapping pooled key;
the query then attends, causally, to the first ``init_blocks`` blocks, the
blocks that cover its last ``window_size`` tokens and the ``topk`` best of
the rest. A query whose context is at most ``dense_len`` long attends to
every block. The choice is shared by a KV head's group and carries no
gradient (it is a boolean mask).

Three entry points over one ``choose_blocks``:

* ``sparse_attend``: many queries over dense K/V (training, and prefill over
  the pages gathered dense). Scores are full and masked, one block of queries
  at a time per row, so the operations are a dense attention's and the memory
  is one query block's.
* ``sparse_decode``: one query per slot over the paged cache with pages of
  one block: the chosen blocks ARE a page list per (slot, KV head), gathered
  and attended.
* ``pool_keys`` / ``update_pooled``: the selector cache, whole or one key.

``cfg`` is the program's ``ModelConfig`` (the ``sparse_*`` sizes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.ops.attention import NEG_INF

_F32 = jnp.float32
DEFAULT_Q_BLOCK = 128


def pooled_count(tokens: int, cfg) -> int:
    """Pooled keys a sequence of ``tokens`` can ever complete."""
    if tokens < cfg.sparse_kernel_size:
        return 0
    return (tokens - cfg.sparse_kernel_size) // cfg.sparse_kernel_stride + 1


def block_count(tokens: int, cfg) -> int:
    return -(-tokens // cfg.sparse_block_size)


def selected_blocks_cap(cfg, n_blocks: int) -> int:
    """The most blocks one query can attend: every block of a dense context,
    or the forced ones and the top-k."""
    bs = cfg.sparse_block_size
    forced = cfg.sparse_init_blocks + -(-cfg.sparse_window_size // bs) + 1
    dense = -(-cfg.sparse_dense_len // bs)
    return min(n_blocks, max(forced + cfg.sparse_topk, dense))


def pool_keys(k: jax.Array, cfg, count: int | None = None) -> jax.Array:
    """[B, T, K, hd] -> [B, count, K, hd]: pooled key j is the mean of keys
    [stride*j, stride*j + kernel). Entries whose window runs past the written
    tokens hold garbage that no query sees (``choose_blocks`` shows a query
    only the pooled keys that end at or before it)."""
    b, t, kh, hd = k.shape
    stride, m = cfg.sparse_kernel_stride, cfg.sparse_kernel_size // cfg.sparse_kernel_stride
    count = pooled_count(t, cfg) if count is None else count
    groups = count + m - 1
    need = groups * stride
    kf = k.astype(_F32)
    if need > t:
        kf = jnp.pad(kf, ((0, 0), (0, need - t), (0, 0), (0, 0)))
    sums = kf[:, :need].reshape(b, groups, stride, kh, hd).sum(axis=2)
    pooled = sum(sums[:, i: i + count] for i in range(m)) / cfg.sparse_kernel_size
    return pooled.astype(k.dtype)


def update_pooled(pooled, k_pages, lengths, page_indices, cfg):
    """One decode step's selector write. ``lengths`` [R] counts the tokens
    resident AFTER this step's K write; a slot whose count completes a pooled
    key (every ``stride`` tokens from ``kernel`` on) reads its last ``kernel``
    keys back from the pages and writes their mean. pooled [R, NP, K, hd]."""
    kernel, stride, ps = cfg.sparse_kernel_size, cfg.sparse_kernel_stride, k_pages.shape[2]
    r = lengths.shape[0]
    complete = (lengths >= kernel) & ((lengths - kernel) % stride == 0)
    j = jnp.where(complete, (lengths - kernel) // stride, pooled.shape[1])  # OOB: dropped
    pos = jnp.maximum(lengths[:, None] - kernel + jnp.arange(kernel)[None, :], 0)
    pages = jnp.take_along_axis(page_indices, pos // ps, axis=1)  # [R, kernel]
    # the KV head is an index, not a window: a window over it relayouts the
    # whole pool for this read (ops/paged.py::write_token_to_pages)
    head = jnp.arange(k_pages.shape[0])[:, None, None]
    keys = k_pages[head, pages[None], (pos % ps)[None]]  # [K, R, kernel, hd]
    mean = keys.astype(_F32).mean(axis=2).transpose(1, 0, 2)  # [R, K, hd]
    return pooled.at[jnp.arange(r), j].set(mean.astype(pooled.dtype), mode="drop")


def choose_blocks(q, pooled, q_pos, cfg, n_blocks: int):
    """The blocks each query attends: bool [B, S, K, n_blocks].

    q [B, S, H, hd]; pooled [B, NP, K, hd]; q_pos [B, S] the query's position
    in its own sequence (its context is q_pos + 1 tokens)."""
    b, s, h, hd = q.shape
    kh = pooled.shape[2]
    g = h // kh
    bs, stride, kernel = cfg.sparse_block_size, cfg.sparse_kernel_stride, cfg.sparse_kernel_size
    per_block, m = bs // stride, kernel // stride
    np_ = pooled.shape[1]
    blk = jnp.arange(n_blocks)
    pos = q_pos[:, :, None, None]  # [B, S, 1, 1]
    causal = blk <= pos // bs
    forced = causal & (
        (blk < cfg.sparse_init_blocks)
        | (blk * bs + bs - 1 >= pos - cfg.sparse_window_size + 1)
    )
    dense = pos + 1 <= cfg.sparse_dense_len
    if np_ == 0:
        return jnp.broadcast_to(jnp.where(dense, causal, forced), (b, s, kh, n_blocks))
    qg = q.reshape(b, s, kh, g, hd)
    logits = jnp.einsum(
        "bskgd,bjkd->bskgj", qg, pooled, preferred_element_type=_F32
    ) * hd**-0.5
    seen = (stride * jnp.arange(np_) + kernel - 1) <= q_pos[:, :, None]  # [B, S, NP]
    seen5 = seen[:, :, None, None, :]
    probs = jax.nn.softmax(jnp.where(seen5, logits, NEG_INF), axis=-1)
    # a pooled key no query sees yet scores below every real score (>= 0)
    pooled_score = jnp.where(seen[:, :, None, :], jnp.where(seen5, probs, 0.0).sum(3), -1.0)
    # a block's score: the best pooled key that overlaps it, keys
    # per_block*b - (m-1) ... per_block*b + per_block - 1
    width = per_block * n_blocks + m - 1
    padded = jnp.pad(
        pooled_score, ((0, 0),) * 3 + ((m - 1, max(width - (m - 1) - np_, 0)),),
        constant_values=-1.0,
    )[..., :width]
    score = jnp.max(jnp.stack([
        padded[..., o: o + per_block * n_blocks: per_block]
        for o in range(per_block + m - 1)
    ]), axis=0)  # [B, S, K, NB]
    rest = causal & ~forced
    rest_score = jnp.where(rest, score, -jnp.inf)
    top_val, top_idx = jax.lax.top_k(rest_score, min(cfg.sparse_topk, n_blocks))
    kth, kth_idx = top_val[..., -1:], top_idx[..., -1:]
    # the top-k as a mask: above the k-th, or level with it and not after it
    # (lax.top_k puts the lower index first among equals)
    picked = rest & ((rest_score > kth) | ((rest_score == kth) & (blk <= kth_idx)))
    return jnp.where(dense, causal, forced | picked)


def sparse_attend(q, k, v, pooled, q_pos, cfg, q_block: int = DEFAULT_Q_BLOCK):
    """Many queries over dense K/V. q [B, S, H, hd]; k, v [B, T, K, hd]
    (position order, the queries' own keys included); pooled [B, NP, K, hd];
    q_pos [B, S]. Returns [B, S, H, hd] in q's type. One block of queries of
    one row at a time; reverse mode recomputes each block."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    bs = cfg.sparse_block_size
    n_blocks = block_count(t, cfg)
    q_block = min(q_block, s)
    nq = -(-s // q_block)
    pad = nq * q_block - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, nq, q_block, h, hd)
    pb = jnp.pad(q_pos, ((0, 0), (0, pad))).reshape(b, nq, q_block)
    tpos = jnp.arange(t)

    @jax.checkpoint
    def one_block(q_c, pos_c, k_r, v_r, pooled_r):
        with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
            blocks = choose_blocks(
                q_c[None], pooled_r[None], pos_c[None], cfg, n_blocks
            )[0]  # [Q, K, NB]
            allowed = jnp.repeat(blocks, bs, axis=-1)[..., :t] & (
                tpos <= pos_c[:, None, None]
            )  # [Q, K, T]
        with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
            qg = q_c.reshape(q_block, kh, g, hd)
            logits = jnp.einsum(
                "qkgd,tkd->kgqt", qg, k_r, preferred_element_type=_F32
            ) * hd**-0.5
            logits = jnp.where(allowed.transpose(1, 0, 2)[:, None], logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1).astype(v_r.dtype)
            out = jnp.einsum("kgqt,tkd->qkgd", probs, v_r, preferred_element_type=_F32)
        return out.reshape(q_block, h, hd).astype(q_c.dtype)

    def one_row(row):
        q_r, pos_r, k_r, v_r, pooled_r = row
        return jax.lax.map(
            lambda c: one_block(c[0], c[1], k_r, v_r, pooled_r), (q_r, pos_r)
        )

    out = jax.lax.map(one_row, (qb, pb, k, v, pooled))  # [B, nq, Q, H, hd]
    return out.reshape(b, nq * q_block, h, hd)[:, :s]


def sparse_decode(q, k_pages, v_pages, pooled, lengths, page_indices, cfg,
                  alive=None):
    """One query per slot over the paged cache (pages of one block).

    q [R, H, hd]; k_pages, v_pages [K, pages, block, hd]; pooled
    [R, NP, K, hd]; lengths [R] the query's position (tokens resident before
    it; its own K/V are already written); page_indices [R, W], column c the
    page of block c. Returns (out [R, H, hd], stats [2] int32: blocks
    attended and blocks visible, summed over ``alive`` slots and KV heads)."""
    r, h, hd = q.shape
    kh, _, ps, _ = k_pages.shape
    g = h // kh
    if ps != cfg.sparse_block_size:
        raise ValueError(
            f"the sparse layers attend by page: page_size {ps} must be the "
            f"selector's block_size {cfg.sparse_block_size}"
        )
    n_blocks = page_indices.shape[1]
    n_sel = selected_blocks_cap(cfg, n_blocks)
    with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
        blocks = choose_blocks(
            q[:, None], pooled, lengths[:, None], cfg, n_blocks
        )[:, 0]  # [R, K, NB]
        # chosen blocks first, in position order (a stable sort of the mask)
        order = jnp.argsort(~blocks, axis=-1, stable=True)[..., :n_sel]
        count = blocks.sum(axis=-1)  # [R, K]
        pages = jnp.take_along_axis(
            jnp.broadcast_to(page_indices[:, None, :], (r, kh, n_blocks)), order, axis=-1
        )  # [R, K, n_sel]
        tok_pos = order[..., None] * ps + jnp.arange(ps)  # [R, K, n_sel, ps]
        allowed = (jnp.arange(n_sel) < count[..., None])[..., None] & (
            tok_pos <= lengths[:, None, None, None]
        )
        live = jnp.ones((r,), jnp.int32) if alive is None else alive.astype(jnp.int32)
        stats = jnp.stack([
            (count.astype(jnp.int32) * live[:, None]).sum(),
            ((lengths // ps + 1).astype(jnp.int32) * live).sum() * kh,
        ])
    with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
        head = jnp.arange(kh)[None, :, None]
        k_sel = k_pages[head, pages]  # [R, K, n_sel, ps, hd]
        v_sel = v_pages[head, pages]
        qg = q.reshape(r, kh, g, hd)
        logits = jnp.einsum(
            "rkgd,rknpd->rkgnp", qg, k_sel, preferred_element_type=_F32
        ) * hd**-0.5
        logits = jnp.where(allowed[:, :, None], logits, NEG_INF)
        probs = jax.nn.softmax(
            logits.reshape(r, kh, g, n_sel * ps), axis=-1
        ).reshape(logits.shape).astype(v_sel.dtype)
        out = jnp.einsum("rkgnp,rknpd->rkgd", probs, v_sel, preferred_element_type=_F32)
    return out.reshape(r, h, hd).astype(q.dtype), stats
