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

Four entry points over one ``choose_blocks``:

* ``sparse_attend``: many queries over dense K/V (``full`` mode: training,
  which differentiates it, and the tests' reference for the others). Scores
  are full and masked, one block of queries at a time per row, so the
  operations are a dense attention's and the memory is one query block's.
* ``segment_choice``: a prefill segment's choice as the mask ``allowed_keys``
  makes of it (``sparse_attend``'s own), a KV head's queries together. The
  segment does not attend here: ``models/hybrid.py::_sparse_mix`` hands the
  mask to the fold the full-attention layers run over the rows' pages
  (``ops/latent_attention.py::expanded_segment``), whose scores never leave
  VMEM on a TPU and which stops at the segment's own block of keys.
* ``sparse_decode``: one query per slot over the paged cache with pages of
  one block: the chosen blocks ARE a page list per (slot, KV head)
  (``chosen_pages``: the pages in position order, the last of them the
  query's own block), attended in one of two forms.
* ``pool_keys`` / ``update_pooled``: the selector cache, whole or one key.

**The decode attention** is one algorithm in two forms, chosen by what
``sparse_decode`` can observe (``sparse_decode_impl``; no argument, no
environment variable): on a TPU backend, with heads of whole 128-lane tiles
over bf16 or float32 pages, ``attend_pages_kernel``, a Mosaic launch that
copies each LIVE page of a list once from the pool where it lies into VMEM
and writes only ``[R, H, hd]``; anywhere else (the CPU tests' small heads,
quantized pages, a CPU run) ``attend_pages_plain``, the same mathematics in
``jnp``, which is also the launch's reference. The plain form writes a
gathered copy ``[R, K, n_sel, ps, hd]`` of K and of V to HBM and reads both
back, dead entries included: 3.17 ms a layer at the long-context cell's sizes
where the launch takes 0.67 (PERF.md §6, PR 39). ``dispatch_choices`` records
which form each geometry took.

The launch's grid is ``(R x K,)``, a (slot, KV head)'s list a step. The chosen
blocks differ by KV head, so a copy is one head's page, 16 KB at 64 tokens of
128 in bf16: too small for the pipeline's block operands (one operand a page:
1.46-1.84 ms a layer, bound by the operands' bookkeeping and not by the
copies), so the kernel starts the copies itself: the NEXT list's into the
other half of a double buffer, before it attends this one, whose copies were
started a step ago. Only the ``count`` live entries are ever read from the
table. The whole list is then one contiguous ``[n_sel x ps, hd]`` tile of K
and of V: one product, ONE softmax update for up to ``CHUNK_TOKENS`` tokens,
and positions masked by one compare (every live page is whole but the last).

``cfg`` is the program's ``ModelConfig`` (the ``sparse_*`` sizes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.ops.attention import NEG_INF
from distrl_llm_tpu.ops.per_device import per_device

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
    kth, kth_idx = _kth_best(rest_score, min(cfg.sparse_topk, n_blocks))
    # the top-k as a mask: above the k-th, or level with it and not after it
    picked = rest & ((rest_score > kth) | ((rest_score == kth) & (blk <= kth_idx)))
    return jnp.where(dense, causal, forced | picked)


def _kth_best(score, k: int):
    """The ``k``-th best of ``score [..., n]`` along its last axis and its
    index, both ``[..., 1]``, in ``jax.lax.top_k``'s order: descending, the
    lower index first among equals. One sort along the LEADING axis of ``[n,
    problems]``: the independent problems lie along the lanes and every
    compare-exchange is between rows. ``top_k`` sorts the minor axis, and there
    the compiler's choice of layout decides what it costs: one block of 128
    queries' 256 problems of 320 took 21 us on a v5e with the queries minor and
    486 us with the blocks minor, the same operation in two programs (PERF.md
    §6, PR 67)."""
    n = score.shape[-1]
    flat = score.reshape(-1, n).T
    order = jax.lax.broadcasted_iota(jnp.int32, flat.shape, 0)
    # ascending by (-score, index); a score is never -0.0 (a sum of weights, -1
    # or -inf), so negating keeps equals equal
    best, at = jax.lax.sort((-flat, order), dimension=0, num_keys=2)
    shape = (*score.shape[:-1], 1)
    return -best[k - 1].reshape(shape), at[k - 1].reshape(shape)


def allowed_keys(blocks, q_pos, keys: int, cfg):
    """The keys each query attends, from the blocks it chose: bool ``[..., K,
    S, keys]`` (a KV head's queries together, as the scores hold them) from
    ``choose_blocks``' ``[..., S, K, n_blocks]`` and ``q_pos [..., S]``: every
    key of a chosen block that lies at or before the query."""
    # a block's choice spread over its keys by a product with a 0/1 matrix
    # (exact: one non-zero term a sum), which the matrix unit writes in the
    # layout the mask is read in. ``jnp.repeat``'s reshape of the minor axis
    # (320 x 64 -> 20,480) is a relayout copy of the whole mask on a TPU, and
    # the folds' loop then re-lays it a fold: 2.2 ms a fold of 4 rows where
    # this form reads 0.82 (PERF.md §6, PR 67)
    spread = jnp.arange(keys) // cfg.sparse_block_size == jnp.arange(blocks.shape[-1])[:, None]
    chosen = jnp.einsum(
        "...sn,nt->...st", jnp.swapaxes(blocks, -3, -2).astype(jnp.bfloat16),
        spread.astype(jnp.bfloat16), preferred_element_type=_F32) > 0
    return chosen & (jnp.arange(keys) <= q_pos[..., None, :, None])


def _query_blocks(q, q_pos, q_block: int):
    """``q [B, S, H, hd]`` and ``q_pos [B, S]`` cut into blocks of at most
    ``q_block`` queries, the last one padded: ``([B, nq, Q, H, hd], [B, nq, Q])``."""
    b, s, h, hd = q.shape
    q_block = min(q_block, s)
    nq = -(-s // q_block)
    pad = nq * q_block - s
    return (jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, nq, q_block, h, hd),
            jnp.pad(q_pos, ((0, 0), (0, pad))).reshape(b, nq, q_block))


def segment_choice(q, pooled, q_pos, cfg, keys: int, q_block: int = DEFAULT_Q_BLOCK):
    """A prefill segment's choice over the row's first ``keys`` positions:
    bool ``[B, K, S, keys]``, ``allowed_keys`` of ``choose_blocks`` for q ``[B,
    S, H, hd]`` at ``q_pos [B, S]`` over pooled ``[B, NP, K, hd]``. The choice
    is made one block of queries of one row at a time, as ``sparse_attend``
    makes it (a block's logits, ``[128, 2, 16, 1315]`` float32 at the
    long-context cell's sizes, are 22 MB where a segment's whole are 689 MB),
    and only the blocks chosen are kept; the mask is made of them once."""
    b, s = q.shape[:2]
    n_blocks = block_count(keys, cfg)
    qb, pb = _query_blocks(q, q_pos, q_block)
    nq = qb.shape[1]
    # a (row, block of queries) an iteration, ``[B * nq, 1, Q, ...]``: no transpose
    blocks = jax.lax.map(
        lambda c: choose_blocks(
            c[0], jax.lax.dynamic_index_in_dim(pooled, c[2], keepdims=True), c[1], cfg,
            n_blocks),
        (qb.reshape(b * nq, 1, *qb.shape[2:]), pb.reshape(b * nq, 1, -1),
         jnp.arange(b * nq) // nq))  # [B * nq, 1, Q, K, NB]
    blocks = blocks.reshape(b, -1, *blocks.shape[3:])[:, :s]
    return allowed_keys(blocks, q_pos, keys, cfg)


def sparse_attend(q, k, v, pooled, q_pos, cfg, q_block: int = DEFAULT_Q_BLOCK):
    """Many queries over dense K/V. q [B, S, H, hd]; k, v [B, T, K, hd]
    (position order, the queries' own keys included); pooled [B, NP, K, hd];
    q_pos [B, S]. Returns [B, S, H, hd] in q's type. One block of queries of
    one row at a time; reverse mode recomputes each block."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    n_blocks = block_count(t, cfg)
    qb, pb = _query_blocks(q, q_pos, q_block)
    nq, q_block = qb.shape[1:3]

    @jax.checkpoint
    def one_block(q_c, pos_c, k_r, v_r, pooled_r):
        with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
            blocks = choose_blocks(
                q_c[None], pooled_r[None], pos_c[None], cfg, n_blocks
            )[0]  # [Q, K, NB]
            allowed = allowed_keys(blocks, pos_c, t, cfg)  # [K, Q, T]
        with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
            qg = q_c.reshape(q_block, kh, g, hd)
            logits = jnp.einsum(
                "qkgd,tkd->kgqt", qg, k_r, preferred_element_type=_F32
            ) * hd**-0.5
            logits = jnp.where(allowed[:, None], logits, NEG_INF)
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


def chosen_pages(q, pooled, lengths, page_indices, cfg, alive=None):
    """The decode step's choice as a page list: (pages [R, K, n_sel] the chosen
    blocks' pages first and in position order, order [R, K, n_sel] their block
    numbers, count [R, K] how many are chosen, stats [2] int32: blocks attended
    and blocks visible, summed over ``alive`` slots and KV heads)."""
    r, kh = q.shape[0], pooled.shape[2]
    ps = cfg.sparse_block_size  # a page is a block
    n_blocks = page_indices.shape[1]
    n_sel = selected_blocks_cap(cfg, n_blocks)
    blocks = choose_blocks(
        q[:, None], pooled, lengths[:, None], cfg, n_blocks
    )[:, 0]  # [R, K, NB]
    # chosen blocks first, in position order (a stable sort of the mask)
    order = jnp.argsort(~blocks, axis=-1, stable=True)[..., :n_sel]
    count = blocks.sum(axis=-1)  # [R, K]
    pages = jnp.take_along_axis(
        jnp.broadcast_to(page_indices[:, None, :], (r, kh, n_blocks)), order, axis=-1
    )  # [R, K, n_sel]
    live = jnp.ones((r,), jnp.int32) if alive is None else alive.astype(jnp.int32)
    stats = jnp.stack([
        (count.astype(jnp.int32) * live[:, None]).sum(),
        ((lengths // ps + 1).astype(jnp.int32) * live).sum() * kh,
    ])
    return pages, order, count, stats


def attend_pages_plain(q, k_pages, v_pages, pages, order, count, lengths):
    """``sparse_decode``'s attention in plain ``jnp``: the chosen pages gathered
    into ``[R, K, n_sel, ps, hd]`` copies of K and V, full scores under a mask.
    The launch's reference, and the path off the TPU."""
    r, h, hd = q.shape
    kh, _, ps, _ = k_pages.shape
    g = h // kh
    n_sel = pages.shape[-1]
    tok_pos = order[..., None] * ps + jnp.arange(ps)  # [R, K, n_sel, ps]
    allowed = (jnp.arange(n_sel) < count[..., None])[..., None] & (
        tok_pos <= lengths[:, None, None, None]
    )
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
    return out.reshape(r, h, hd).astype(q.dtype)


#: most tokens of a page list that one softmax update covers: float32 scores
#: ``[g, 8192]`` for the 16 query heads of a KV head are 512 KB. Timed on a v5e
#: at 64 slots x 2 KV heads, 98 live pages of 64 tokens of 128 (PERF.md §6, PR
#: 39): an update costs 0.29 us beside 15 ns a page, so a list of 128 pages
#: attended 32 / 64 / 128 pages an update takes 756 / 699 / 666 us a layer
CHUNK_TOKENS = 8192
#: pages whose copies are started, and later awaited, as one unrolled unit (the
#: pages left over of a list are started and awaited one by one): 8 / 16 / 32
#: read 732 / 699 / 682 us a layer at 64 pages an update
COPY_UNIT_PAGES = 16
#: VMEM the launch may take for its two buffered lists of K and of V (8 MiB at
#: 128 bf16 pages of 64 x 128); a longer list takes the plain form
LIST_VMEM_BYTES = 48 * 2**20
_LANES = 128

#: what each geometry's decode attention resolved to, "kernel" or "plain", under
#: ``dispatch_key``: the engine's counter ``ops/sparse_kernel_steps`` reads it,
#: so a run on the plain form cannot pass for the launch
dispatch_choices: dict = {}


def dispatch_key(heads: int, kv_heads: int, head_dim: int, page_size: int,
                 dtype=jnp.bfloat16) -> tuple:
    """The key ``sparse_decode`` records its choice under: the query's heads
    and the pool's pages, and not the rows or the table's width."""
    return (heads, kv_heads, head_dim, page_size, jnp.dtype(dtype).name)


def sparse_decode_impl(q: jax.Array, k_pages: jax.Array, n_sel: int) -> str:
    """The form ``sparse_decode``'s attention takes over lists of ``n_sel``
    pages: "kernel" on a TPU backend for heads of whole 128-lane tiles over
    bf16 or float32 pages whose buffered lists fit ``LIST_VMEM_BYTES``,
    "plain" otherwise (small heads, quantized pages, a CPU). On the TPU
    nothing falls back: a kernel that fails to lower fails the step that
    called it."""
    _, _, ps, hd = k_pages.shape
    fits = 4 * n_sel * ps * hd * k_pages.dtype.itemsize <= LIST_VMEM_BYTES
    pages_ok = k_pages.dtype in (jnp.bfloat16, jnp.float32) and q.dtype == k_pages.dtype
    if jax.default_backend() == "tpu" and hd % _LANES == 0 and pages_ok and fits:
        return "kernel"
    return "plain"


def _make_pages_kernel(*, ps: int, cpp: int, unit: int, kh: int, steps: int, scale: float):
    """Kernel body for ``attend_pages_kernel``: grid (R x K,), one (slot, KV
    head)'s page list a step, in three phases: start every copy of the NEXT
    list into the other half of the buffers, wait for every copy of this one
    (started a step ago, behind the last list's arithmetic), attend ``cpp``
    pages a softmax update."""
    rows = cpp * ps
    dims_qk = (((1,), (1,)), ((), ()))  # [g, hd] x [rows, hd] -> [g, rows]
    dims_pv = (((1,), (0,)), ((), ()))  # [g, rows] x [rows, hd] -> [g, hd]

    def kernel(len_ref, cnt_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem):
        s = pl.program_id(0)
        slot = s % 2

        def units(step, whole, one):
            """Over a list's live pages, ``unit`` at a time: ``whole(first)``
            for ``unit`` live pages from ``first`` on, ``one(j)`` for each
            page of the shorter last run. A dead entry is never touched."""
            n = cnt_ref[step]

            def run(u, _):
                left = n - u * unit
                pl.when(left >= unit)(lambda: whole(u * unit))

                @pl.when(left < unit)
                def _():
                    jax.lax.fori_loop(0, left, lambda i, _: one(u * unit + i), None)

            jax.lax.fori_loop(0, (n + unit - 1) // unit, run, None)

        def fetch(step, into):
            head = step % kh

            def one(j):
                page = tab_ref[step, j]
                dst = pl.ds(pl.multiple_of(j * ps, ps), ps)
                pltpu.make_async_copy(
                    k_hbm.at[head, page], kbuf.at[into, dst], sem.at[0, into]).start()
                pltpu.make_async_copy(
                    v_hbm.at[head, page], vbuf.at[into, dst], sem.at[1, into]).start()

            def whole(first):
                for i in range(unit):
                    one(first + i)

            units(step, whole, one)

        def land(step, into):
            def landed(first, pages):
                # a wait is for a size: one descriptor answers for a unit's copies
                at = pl.ds(pl.multiple_of(first * ps, ps), pages * ps)
                pltpu.make_async_copy(
                    kbuf.at[into, at], kbuf.at[into, at], sem.at[0, into]).wait()
                pltpu.make_async_copy(
                    vbuf.at[into, at], vbuf.at[into, at], sem.at[1, into]).wait()

            units(step, lambda first: landed(first, unit), lambda j: landed(j, 1))

        @pl.when(s == 0)
        def _first():
            # a list's last chunk is attended whole: what lies past its live
            # pages is masked, and V there must be finite for the mask to do
            vbuf[...] = jnp.zeros_like(vbuf)
            fetch(0, 0)

        pl.when(s + 1 < steps)(lambda: fetch(s + 1, 1 - slot))
        land(s, slot)

        count = cnt_ref[s]
        # the list's tokens the query sees: every live page whole but the last,
        # its own block, which it sees up to its own place
        limit = (count - 1) * ps + len_ref[s // kh] % ps + 1
        q = q_ref[...]

        def attend(c, carry):
            m_prev, l_prev, acc = carry
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            scores = jax.lax.dot_general(
                q, kbuf[slot, at, :], dims_qk, preferred_element_type=_F32) * scale
            tok = c * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            scores = jnp.where(tok < limit, scores, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(vbuf.dtype), vbuf[slot, at, :], dims_pv,
                preferred_element_type=_F32)
            return m_new, l_new, acc

        g, hd = q.shape
        _, l_end, acc = jax.lax.fori_loop(
            0, (count + cpp - 1) // cpp, attend,
            (jnp.full((g, 1), NEG_INF, _F32), jnp.zeros((g, 1), _F32),
             jnp.zeros((g, hd), _F32)))
        # a list of no live page (a slot that is not alive) emits zeros, not 0/0
        o_ref[...] = (acc / jnp.maximum(l_end, 1e-30)).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("pages_per_step", "interpret"))
def attend_pages_kernel(q, k_pages, v_pages, pages, count, lengths, *,
                        pages_per_step: int = 0, interpret: bool = False):
    """``sparse_decode``'s attention as one Mosaic launch (a TPU; ``interpret``
    for the CPU's tests): each live page of a list ``pages [R, K, n_sel]``
    (``count [R, K]`` live entries, the last the query's own block) is copied
    once from the pool where it lies into VMEM, and only ``[R, H, hd]`` is
    written. Float32 scores, running maximum, sum and accumulator; K, V and
    the probabilities in the pages' type, as the plain form has them. A list
    with no live entry emits zeros. ``pages_per_step`` 0 is the launch's own
    choice (``CHUNK_TOKENS``); the tests name one to reach lists of several
    updates at small sizes."""
    r, h, hd = q.shape
    kh, total, ps, _ = k_pages.shape
    g = h // kh
    n_sel = pages.shape[-1]
    cpp = min(pages_per_step or max(CHUNK_TOKENS // ps, 1), n_sel)
    width = -(-n_sel // cpp) * cpp  # the buffers hold whole updates
    steps = r * kh
    spec = pl.BlockSpec((None, None, g, hd), lambda s, *_: (s // kh, s % kh, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        _make_pages_kernel(ps=ps, cpp=cpp, unit=min(COPY_UNIT_PAGES, cpp), kh=kh,
                           steps=steps, scale=hd**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # lengths, counts, page lists ride SMEM
            grid=(steps,),
            in_specs=[spec, pool, pool],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((2, width * ps, hd), k_pages.dtype),
                pltpu.VMEM((2, width * ps, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # K / V x buffer half
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            # a step starts the next step's copies: the grid runs in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * width * ps * hd * k_pages.dtype.itemsize + 16 * 2**20),
        out_shape=jax.ShapeDtypeStruct((r, kh, g, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), count.astype(jnp.int32).reshape(-1),
      jnp.clip(pages.astype(jnp.int32), 0, total - 1).reshape(steps, n_sel),
      q.reshape(r, kh, g, hd), k_pages, v_pages)
    return out.reshape(r, h, hd)


def sparse_decode(q, k_pages, v_pages, pooled, lengths, page_indices, cfg,
                  alive=None):
    """One query per slot over the paged cache (pages of one block).

    q [R, H, hd]; k_pages, v_pages [K, pages, block, hd]; pooled
    [R, NP, K, hd]; lengths [R] the query's position (tokens resident before
    it; its own K/V are already written); page_indices [R, W], column c the
    page of block c. Returns (out [R, H, hd], stats [2] int32: blocks
    attended and blocks visible, summed over ``alive`` slots and KV heads).
    The attention takes the form ``sparse_decode_impl`` names (module header)
    and the choice is recorded in ``dispatch_choices``; as the launch, a slot
    that is not ``alive`` fetches nothing and emits zeros."""
    ps = k_pages.shape[2]
    if ps != cfg.sparse_block_size:
        raise ValueError(
            f"the sparse layers attend by page: page_size {ps} must be the "
            f"selector's block_size {cfg.sparse_block_size}"
        )
    with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
        pages, order, count, stats = chosen_pages(
            q, pooled, lengths, page_indices, cfg, alive)
    impl = sparse_decode_impl(q, k_pages, pages.shape[-1])
    dispatch_choices[dispatch_key(
        q.shape[1], k_pages.shape[0], q.shape[2], ps, k_pages.dtype)] = impl
    with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
        if impl == "kernel":
            live = count if alive is None else count * alive.astype(count.dtype)[:, None]
            out = per_device(attend_pages_kernel)(
                q, k_pages, v_pages, pages, live, lengths)
        else:
            out = attend_pages_plain(q, k_pages, v_pages, pages, order, count, lengths)
    return out, stats
