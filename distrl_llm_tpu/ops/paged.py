"""Paged KV cache ops: page tables, token writes, and ragged paged attention.

The N1 core the reference delegates to vLLM's PagedAttention
(requirements.txt:6; engine entered via ``policy.fast_generate``,
distributed_actor.py:148–150). TPU-native design:

* **Pages** are [num_kv_heads, total_pages, page_size, head_dim] arrays per
  layer; a row's sequence lives at the pages listed in its ``page_indices``
  row, valid up to ``lengths[row]`` tokens. Prompts are PACKED (position 0 is
  the first real token — no left padding inside the cache), so attention
  bandwidth is proportional to each row's true length, not the cache
  capacity: the decode kernel only reads [0, length) — vLLM's ragged read,
  where the dense cache reads all of Smax every step for every row.
* **Shape-static, host-authored page tables.** vLLM's C++ block allocator
  multiplexes an unknown online request stream; an RL rollout round is a
  FIXED batch of B·n candidates, so the tables are host-computed int32
  arrays of STATIC shape whose CONTENT changes (engine/page_pool.py: the
  free-list allocator behind ``--actor_gpu_usage`` grants pages as
  sequences grow and rewrites rows on admission/preemption; wave mode uses
  a per-round constant layout). The indirection layer is also what lets
  prompt-prefix sharing land without touching the kernel.
* **Kernel**: on a TPU backend our Pallas TPU kernel (ops/paged_native.py,
  compact int8 scales); a jnp reference with identical semantics elsewhere
  and for parity tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.ops.attention import NEG_INF
from distrl_llm_tpu.ops.per_device import per_device

DEFAULT_PAGE_SIZE = 128


def _quant_utils():
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        quantization_utils,
    )

    return quantization_utils


def is_quantized_pages(pages) -> bool:
    """True for the kernel's QuantizedTensor page container (int8 weight +
    per-token absmax scales)."""
    return hasattr(pages, "weight") and hasattr(pages, "scales")


def quantize_pages(pages: jax.Array):
    """float pages [K, P, ps, hd] → QuantizedTensor (int8 + f32 scales
    [K, P, ps, 1]). Halves the cache's resident HBM footprint.

    Decode-speed note: jaxlib's public ``paged_attention`` wrapper broadcasts
    these scales to head_dim before its pallas_call (a full-cache f32 temp
    per step, which would negate the bandwidth win); the native kernels
    (ops/paged_native.py) read the scales COMPACT ([ps, 1], ~1 + 4/head_dim
    bytes/element), so int8 KV buys both capacity AND read bandwidth."""
    return _quant_utils().quantize_to_int8(pages)


def init_quantized_pages(shape: tuple[int, int, int, int]):
    """Zero-initialized QuantizedTensor pages for ``shape``
    [K, total_pages, ps, hd] — the single owner of the quantized-page layout
    contract (int8 weight + f32 per-token scales [..., 1])."""
    qu = _quant_utils()
    return qu.QuantizedTensor(
        weight=jnp.zeros(shape, jnp.int8),
        scales=jnp.zeros(shape[:3] + (1,), jnp.float32),
    )


def dequantize_pages(pages, dtype=jnp.float32) -> jax.Array:
    if not is_quantized_pages(pages):
        return pages.astype(dtype)
    return _quant_utils().from_int8(pages.weight, pages.scales, dtype=dtype)


def pages_per_seq(max_len: int, page_size: int = DEFAULT_PAGE_SIZE) -> int:
    return -(-max_len // page_size)


def make_page_table(
    n_rows: int, max_len: int, page_size: int = DEFAULT_PAGE_SIZE
) -> np.ndarray:
    """Row-major identity page table: row r owns pages [r·pps, (r+1)·pps).

    int32 [n_rows, pages_per_seq]. Total pages = n_rows · pages_per_seq."""
    pps = pages_per_seq(max_len, page_size)
    return (
        np.arange(n_rows, dtype=np.int32)[:, None] * pps
        + np.arange(pps, dtype=np.int32)[None, :]
    )


def init_paged_kv_cache(
    cfg, n_rows: int, max_len: int, page_size: int = DEFAULT_PAGE_SIZE,
    dtype=jnp.bfloat16,
):
    """Per-layer page arrays for ``n_rows`` sequences of capacity ``max_len``.

    Layout [K, total_pages, page_size, hd] matches the Pallas kernel's
    contract (paged_attention_kernel.py)."""
    pps = pages_per_seq(max_len, page_size)
    shape = (cfg.num_kv_heads, n_rows * pps, page_size, cfg.head_dim)
    return {
        "k": tuple(jnp.zeros(shape, dtype) for _ in range(cfg.num_layers)),
        "v": tuple(jnp.zeros(shape, dtype) for _ in range(cfg.num_layers)),
    }


def write_prompt_to_pages(
    pages,  # [K, total_pages, ps, hd] array, or QuantizedTensor
    prompt_kv: jax.Array,  # [B, P, K, hd] packed (row position 0 = first token)
    page_indices: jax.Array,  # [B, pps_total]
    page_size: int,
):
    """Write every row's packed prompt KV into its leading pages.

    P must be a multiple of page_size (callers pad; positions beyond a row's
    real length hold garbage that ``lengths`` masking never reads)."""
    b, p, kh, hd = prompt_kv.shape
    assert p % page_size == 0, (p, page_size)
    n_prompt_pages = p // page_size
    # [B, P, K, hd] → [K, B·n_prompt_pages, ps, hd]
    tiles = (
        prompt_kv.reshape(b, n_prompt_pages, page_size, kh, hd)
        .transpose(3, 0, 1, 2, 4)
        .reshape(kh, b * n_prompt_pages, page_size, hd)
    )
    dest = page_indices[:, :n_prompt_pages].reshape(-1)  # [B·n_prompt_pages]
    if is_quantized_pages(pages):
        qu = _quant_utils()
        scales = qu.get_quantization_scales(tiles)  # [K, tiles, ps, 1]
        weight = pages.weight.at[:, dest].set(qu.to_int8(tiles, scales))
        return type(pages)(
            weight=weight,
            scales=pages.scales.at[:, dest].set(scales.astype(pages.scales.dtype)),
        )
    return pages.at[:, dest].set(tiles.astype(pages.dtype))


def write_token_to_pages(
    pages,  # [K, total_pages, ps, hd] array, or QuantizedTensor
    new_kv: jax.Array,  # [B, K, hd] — one token per row
    lengths: jax.Array,  # [B] current token counts (write position)
    page_indices: jax.Array,  # [B, pps]
    page_size: int,
    valid: jax.Array | None = None,  # [B] bool; False rows write nothing
):
    """Scatter one decoded token's KV into each row's current page slot.

    The KV head is an INDEX of the scatter, like page and slot, and not a
    window (``pages.at[:, page, slot]``): a window over the head makes XLA's
    TPU layout assignment relayout the whole pool into a head-minor tiling
    before the scatter and back after it, two pool-sized copies an array a
    step (tests/test_tpu_compile.py::test_page_write_keeps_the_pool_layout).

    With ``valid``, rows marked False are DROPPED (out-of-range page index +
    ``mode="drop"``) instead of written — the continuation-prefill path uses
    this so padding positions never touch pages the row doesn't own."""
    b, kh, _ = new_kv.shape
    rows = jnp.arange(b)
    page = page_indices[rows, lengths // page_size]  # [B]
    slot = lengths % page_size  # [B]
    raw = pages.weight if is_quantized_pages(pages) else pages
    if valid is not None:
        page = jnp.where(valid, page, raw.shape[1])  # OOB → dropped
    at = (jnp.arange(kh)[:, None], page[None, :], slot[None, :])  # → [K, B]
    tok = new_kv.transpose(1, 0, 2)  # [K, B, hd]
    if is_quantized_pages(pages):
        qu = _quant_utils()
        scales = qu.get_quantization_scales(tok)  # [K, B, 1]
        return type(pages)(
            weight=pages.weight.at[at].set(qu.to_int8(tok, scales), mode="drop"),
            scales=pages.scales.at[at].set(
                scales.astype(pages.scales.dtype), mode="drop"
            ),
        )
    return pages.at[at].set(tok.astype(pages.dtype), mode="drop")


def write_tokens_to_pages(
    pages,  # [K, total_pages, ps, hd] array, or QuantizedTensor
    new_kv: jax.Array,  # [B, D, K, hd] — D tokens per row
    lengths: jax.Array,  # [B] current token counts (first write position)
    page_indices: jax.Array,  # [B, pps]
    page_size: int,
    valid: jax.Array | None = None,  # [B, D] bool per-token validity
):
    """Scatter D consecutive tokens' KV per row (speculative-decode verify
    writes the whole draft block at once; D is small and static, so the loop
    unrolls inside the jitted step)."""
    d = new_kv.shape[1]
    for i in range(d):
        pages = write_token_to_pages(
            pages, new_kv[:, i], lengths + i, page_indices, page_size,
            valid=valid[:, i] if valid is not None else None,
        )
    return pages


def gather_pages_dense(pages, page_indices: jax.Array,
                       dtype=jnp.float32) -> jax.Array:
    """Gather each row's pages into a dense position-ordered context
    [B, width·ps, K, hd] (page-table column t covers positions
    [t·ps, (t+1)·ps), so the concatenation is position order). Quantized
    pools dequantize AFTER the gather — only the rows' own pages.

    ``dtype`` defaults to f32 (the chunked-attention accumulator contract);
    the warm radix-prefill path passes the COMPUTE dtype so the gathered
    context is bit-identical to the in-flight k/v the packed cold prefill
    attended over (page writes are exact ``astype`` round-trips when the
    pool dtype holds the compute dtype losslessly)."""
    if is_quantized_pages(pages):
        w = pages.weight[:, page_indices]
        s_ = pages.scales[:, page_indices]
        dense = _quant_utils().from_int8(w, s_, dtype=dtype)
    else:
        dense = pages[:, page_indices].astype(dtype)
    # [K, B, width, ps, hd] → [B, width·ps, K, hd]
    kh, b, width, ps, hd = dense.shape
    return dense.transpose(1, 2, 3, 0, 4).reshape(b, width * ps, kh, hd)


def chunked_context_attention(
    q: jax.Array,  # [B, S, H, hd] — S continuation queries per row
    ctx_k: jax.Array,  # [B, Sk, K, hd] dense-gathered context (f32)
    ctx_v: jax.Array,
    lengths: jax.Array,  # [B] resident tokens BEFORE the continuation block
    q_valid: jax.Array,  # [B, S] bool/int — which continuation tokens are real
) -> jax.Array:
    """Attention for chunked (continuation) prefill over a paged cache: query
    i at global position lengths+i attends context positions j <= lengths+i.
    The context already contains the continuation block's own KV (written to
    pages before the gather), so this is exact causality — vLLM's chunked
    prefill, dense-gather edition (ops are plain einsums; XLA fuses)."""
    b, s, h, hd = q.shape
    kh = ctx_k.shape[2]
    g = h // kh
    sk = ctx_k.shape[1]
    scale = hd**-0.5
    qg = q.reshape(b, s, kh, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bskgd,bjkd->bkgsj", qg, ctx_k) * scale  # [B,K,g,S,Sk]
    jpos = jnp.arange(sk)[None, None, :]  # [1, 1, Sk]
    qpos = lengths[:, None, None] + jnp.arange(s)[None, :, None]  # [B, S, 1]
    causal = jpos <= qpos  # [B, S, Sk]
    logits = jnp.where(causal[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgsj,bjkd->bskgd", probs, ctx_v)  # [B, S, K, g, hd]
    # invalid (padding) queries produce garbage rows — zero them so NaNs
    # can't propagate into downstream reductions
    out = jnp.where(q_valid[:, :, None, None, None] > 0, out, 0.0)
    return out.reshape(b, s, h, hd).astype(q.dtype)


def paged_attention_reference(
    q: jax.Array,  # [B, H, hd] — single decode query per row
    k_pages,  # [K, total_pages, ps, hd] array, or QuantizedTensor
    v_pages,
    lengths: jax.Array,  # [B] valid token counts (incl. current position)
    page_indices: jax.Array,  # [B, pps]
    scale: float | None = None,
) -> jax.Array:
    """jnp semantics-reference for the Pallas kernel: gather each row's pages
    and run masked GQA attention over its valid prefix (quantized pools
    dequantize AFTER the gather — only the rows' own pages)."""

    def gather(pages):
        if is_quantized_pages(pages):
            w = pages.weight[:, page_indices]
            s_ = pages.scales[:, page_indices]
            return _quant_utils().from_int8(w, s_, dtype=jnp.float32)
        return pages[:, page_indices].astype(jnp.float32)

    raw_k = k_pages.weight if is_quantized_pages(k_pages) else k_pages
    b, h, hd = q.shape
    kh = raw_k.shape[0]
    g = h // kh
    ps = raw_k.shape[2]
    if scale is None:
        scale = hd**-0.5
    # gather [K, B, pps, ps, hd] → [B, K, S, hd]
    k = gather(k_pages).transpose(1, 0, 2, 3, 4)
    v = gather(v_pages).transpose(1, 0, 2, 3, 4)
    s = k.shape[2] * ps
    k = k.reshape(b, kh, s, hd)
    v = v.reshape(b, kh, s, v.shape[-1])  # V's own width, which is the output's
    qg = q.reshape(b, kh, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, k.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] < lengths[:, None]  # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, v.shape[-1]).astype(q.dtype)


def paged_grid_steps(
    impl: str, *, batch: int, num_kv_heads: int, pps: int,
    head_dim: int = 0, page_size: int = 0,
    kv_itemsize: int = 2, quantized: bool = False, v_head_dim: int = 0,
) -> int:
    """Analytic Pallas grid-step count of ONE paged-attention call (one
    layer, one decode step) for ``impl``. The engines record it (the counter
    ``ops/paged_grid_steps``) so that a trace says which launch geometry ran. What a grid step costs
    depends on what it moves (a v5e at 4 kv heads of 128, page 128; PERF.md
    §6, PR 32): 0.37 us with one page of one head inside, 0.55 us with one
    page of all four heads, 1.5 us with a row's three to five pages and one
    softmax — the fewer and larger steps are the faster call, 479 / 176 /
    98 us.

    Counts per impl: "native" moves all kv heads and
    ``native_pages_per_step`` pages of a row a step — (B, ceil(pps / ppb)),
    with ppb from ``head_dim`` (and ``v_head_dim`` where V's width is not
    K's), ``page_size`` and the pages' dtype (``kv_itemsize``,
    ``quantized``), which this impl therefore needs;
    "native_verify" is the FUSED draft-block verify: the whole (d+1)-query
    speculative verify step in ONE sweep of ``VERIFY_PAGES_PER_BLOCK`` pages
    a step — (B, ceil(pps / ppb)), where the unrolled verify pays the decode
    launch's count (d+1) TIMES per step; the jnp reference has no Pallas
    grid (0)."""
    if impl == "native":
        from distrl_llm_tpu.ops.paged_native import native_pages_per_step

        if not head_dim or not page_size:
            raise ValueError(
                '"native" chooses its pages a step from the shapes: '
                "head_dim and page_size are needed"
            )
        ppb = native_pages_per_step(
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            page_size=page_size, pps=pps, kv_itemsize=kv_itemsize,
            quantized=quantized, v_head_dim=v_head_dim,
        )
        return batch * -(-pps // ppb)
    if impl == "native_verify":
        return batch * -(-pps // min(VERIFY_PAGES_PER_BLOCK, pps))
    return 0


def dispatch_choice_key(
    *, quantized: bool, num_kv_heads: int, num_groups: int, head_dim: int,
    page_size: int, pps: int, impl: str = "auto", verify_len: int = 0,
) -> tuple:
    """The per-config key ``paged_attention_op`` records its dispatch
    decision under ``dispatch_choices``. One function so engines can look
    up THEIR OWN entry instead of guessing across a process-global dict
    (several engines can trace in one process — the autotuner's candidate
    sweep). The REQUESTED ``impl`` is part of the key: two same-geometry
    engines pinned to different kernels must not share (and overwrite) one
    record. ``verify_len`` > 0 marks the speculative draft-block verify
    dispatch (``paged_verify_op``) — its decision ("native_verify" fused
    sweep vs "unrolled") is a different choice than the single-query
    decode's and must not alias it."""
    return (impl, quantized, num_kv_heads, num_groups, head_dim, page_size,
            pps, verify_len)


# per-config record of what each paged dispatch resolved to ("native" |
# "reference") — engines and chip_smoke.py read it, so a run on
# the reference can never pass for a kernel measurement
dispatch_choices: dict = {}
# NOTE on grid-step accounting: the analytic count is batch-dependent, so
# it is never cached here — consumers read WHICH impl ran from
# dispatch_choices (keyed per requested impl + geometry) and compute
# paged_grid_steps() against their own live batch.

#: every spelling ``paged_attention_op(impl=...)`` takes
PAGED_IMPLS = ("auto", "reference", "native")
#: what "auto" is on a TPU backend: ``paged_native.paged_attention_native`` —
#: a row's KV for all kv heads and as many of its pages as the VMEM budget
#: holds a grid step, no page past the row's length fetched, one softmax a
#: step. It lowers for head_dim 64 AND 128 (tests/test_tpu_compile.py);
#: chip_smoke.py holds it to the reference on the chip at both. Its launch
#: function's NAME is what the benchmark's kernel metrics find it by.
AUTO_TPU_IMPL = "native"


def resolve_paged_impl(impl: str) -> str:
    """The concrete impl a request runs as. "auto" is ``AUTO_TPU_IMPL`` on a
    TPU backend (``paged_attention_native``, whose block of pages a grid step
    adapts to the shapes: one decision for every geometry, no probe) and the
    jnp reference on any other; every other spelling is itself. Nothing
    downstream catches: on the TPU a kernel that fails to lower or to run
    fails the step that called it."""
    if impl not in PAGED_IMPLS:
        raise ValueError(f"impl must be one of {PAGED_IMPLS}, got {impl!r}")
    if impl == "auto":
        return AUTO_TPU_IMPL if jax.default_backend() == "tpu" else "reference"
    return impl


# the scope sits OUTSIDE the kernels' own jit: round an inline pallas_call it
# would rename the custom call and re-key the program (ops/sampling.py)
@jax.named_scope(telemetry.KERNEL_PAGED_ATTENTION)
def _native_call(q, k_pages, v_pages, lengths, page_indices,
                 *, quantized: bool, interpret: bool = False):
    """Adapter: the dispatch's launch signature → ``paged_attention_native``
    (ops/paged_native.py), which takes int8 weights and compact scales as
    separate arrays and sizes its own blocks."""
    from distrl_llm_tpu.ops.paged_native import paged_attention_native

    if quantized:
        return paged_attention_native(
            q, k_pages.weight, v_pages.weight, lengths, page_indices,
            k_scales=k_pages.scales, v_scales=v_pages.scales,
            interpret=interpret,
        )
    return paged_attention_native(
        q, k_pages, v_pages, lengths, page_indices, interpret=interpret
    )


#: pages of all kv heads one grid step of the fused verify launch moves
#: (``paged_native.paged_attention_native_verify``), at most the table's width
VERIFY_PAGES_PER_BLOCK = 8


@jax.named_scope(telemetry.KERNEL_PAGED_ATTENTION)
def _native_verify_call(q, k_pages, v_pages, lengths, page_indices,
                        *, quantized: bool, interpret: bool = False):
    """Adapter for the fused draft-block verify kernel
    (ops/paged_native.py::paged_attention_native_verify): q is the whole
    [B, S, H, hd] draft block, pre-scaled; ``lengths`` are the RESIDENT
    counts before the block (the kernel applies the per-query
    ``lengths + i + 1`` causal ladder itself)."""
    from distrl_llm_tpu.ops.paged_native import paged_attention_native_verify

    kw: dict = {
        "interpret": interpret, "pages_per_block": VERIFY_PAGES_PER_BLOCK,
    }
    if quantized:
        return paged_attention_native_verify(
            q, k_pages.weight, v_pages.weight, lengths, page_indices,
            k_scales=k_pages.scales, v_scales=v_pages.scales, **kw,
        )
    return paged_attention_native_verify(
        q, k_pages, v_pages, lengths, page_indices, **kw,
    )


def paged_attention_op(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    lengths: jax.Array,
    page_indices: jax.Array,
    *,
    impl: str = "auto",
    scale: float | None = None,
) -> jax.Array:
    """Dispatch one decode query per row over the paged cache. ``scale`` is
    what the scores are multiplied by where it is not ``q``'s width's root (a
    query and keys padded with zeros to whole lane tiles keep their own).

    ``impl``: "auto" (``resolve_paged_impl``: the native kernel on a TPU
    backend, the reference elsewhere), "native" (our pipeline-gather kernel,
    ops/paged_native.py: all kv heads and a length-bounded run of a row's
    pages a grid step, the block sized by the launch from the shapes) or
    "reference". What ran is recorded in ``dispatch_choices``."""
    pps = page_indices.shape[1]
    quantized = is_quantized_pages(k_pages)
    kw = k_pages.weight if quantized else k_pages
    num_kv_heads = kw.shape[0]
    resolved = resolve_paged_impl(impl)
    choice_key = dispatch_choice_key(
        quantized=quantized, num_kv_heads=num_kv_heads,
        num_groups=q.shape[1] // num_kv_heads, head_dim=kw.shape[-1],
        page_size=kw.shape[-2], pps=pps, impl=impl,
    )
    dispatch_choices[choice_key] = resolved
    if resolved == "reference":
        return paged_attention_reference(
            q, k_pages, v_pages, lengths, page_indices,
            **({} if scale is None else {"scale": scale}),
        )
    # the kernel computes raw q·k (no internal scaling)
    return per_device(_native_call)(
        q * (q.shape[-1] ** -0.5 if scale is None else scale), k_pages, v_pages,
        lengths.astype(jnp.int32), page_indices, quantized=quantized,
    ).astype(q.dtype)


def paged_verify_reference(
    q: jax.Array,  # [B, S, H, hd] — S-query draft block per row
    k_pages,
    v_pages,
    lengths: jax.Array,  # [B] RESIDENT tokens BEFORE the draft block
    page_indices: jax.Array,
) -> jax.Array:
    """Semantics reference for the draft-block verify: query position i
    attends each row's [0, lengths + i + 1) prefix — the exact per-position
    ladder the unrolled verify path has always dispatched. Returns
    [B, S, H, hd]."""
    return jnp.stack(
        [
            paged_attention_reference(
                q[:, i], k_pages, v_pages, lengths + i + 1, page_indices
            )
            for i in range(q.shape[1])
        ],
        axis=1,
    )


def paged_verify_op(
    q: jax.Array,  # [B, S, H, hd] — S-query draft block per row (UNscaled)
    k_pages,
    v_pages,
    lengths: jax.Array,  # [B] RESIDENT tokens BEFORE the draft block
    page_indices: jax.Array,
    *,
    impl: str = "auto",
    verify_impl: str = "fused",
) -> jax.Array:
    """Speculative-decode draft-block verify dispatch: the S-query
    attention of one verify forward, in ONE fused sweep when the hardware
    can (``paged_attention_native_verify``), else unrolled into S
    per-position ``paged_attention_op`` dispatches (the pre-fusion
    behavior, exact to the dispatch).

    ``verify_impl``: "fused" (the fused kernel on a TPU backend under
    "auto" / "native", unrolled elsewhere) or "unrolled" (force
    per-position dispatch — the A/B control and the interpreter-parity
    anchor). The decision is recorded in ``dispatch_choices`` under the
    verify-marked key (``dispatch_choice_key(..., verify_len=S)``):
    "native_verify" when the fused sweep ran, "unrolled" otherwise — so
    engines can compute the verify step's TRUE grid cost
    (``paged_grid_steps("native_verify", ...)`` × 1 call vs the per-impl
    count × (d+1) calls) instead of guessing."""
    b, s, h, hd = q.shape
    if verify_impl not in ("fused", "unrolled"):
        raise ValueError(
            f"verify_impl must be fused/unrolled, got {verify_impl!r}"
        )
    quantized = is_quantized_pages(k_pages)
    kw = k_pages.weight if quantized else k_pages
    num_kv_heads = kw.shape[0]
    choice_key = dispatch_choice_key(
        quantized=quantized, num_kv_heads=num_kv_heads,
        num_groups=h // num_kv_heads, head_dim=kw.shape[-1],
        page_size=kw.shape[-2], pps=page_indices.shape[1],
        impl=impl, verify_len=s,
    )
    # the fused kernel is a native launch; a "reference" pin has no fused
    # spelling and unrolls onto the reference
    if (
        verify_impl == "fused"
        and resolve_paged_impl(impl) == AUTO_TPU_IMPL
        and jax.default_backend() == "tpu"
    ):
        dispatch_choices[choice_key] = "native_verify"
        return per_device(_native_verify_call)(
            q * (hd ** -0.5), k_pages, v_pages, lengths.astype(jnp.int32),
            page_indices, quantized=quantized,
        ).astype(q.dtype)
    # unrolled: S per-position dispatches (each records its own decode
    # dispatch choice; the verify key records that the step ran unrolled)
    dispatch_choices[choice_key] = "unrolled"
    return jnp.stack(
        [
            paged_attention_op(
                q[:, i], k_pages, v_pages, lengths + i + 1, page_indices,
                impl=impl,
            )
            for i in range(s)
        ],
        axis=1,
    )
