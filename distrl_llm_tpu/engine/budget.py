"""HBM budget → KV page-pool sizing (the ``--actor_gpu_usage`` contract).

The reference passes ``--actor_gpu_usage`` straight to vLLM's
``gpu_memory_utilization`` (train_distributed.py:34-35), which sizes the KV
block pool as: usable = usage × device_memory − weights − activation
workspace, pool_blocks = usable / block_bytes. This module is the TPU-native
equivalent: it converts the same fraction into ``max_kv_pages`` for the paged
engine's refill pool (engine/page_pool.py), measured against the HBM the
TPU runtime reports (and against one v5e chip's 16 GiB on the CPU).
"""

from __future__ import annotations

import logging

import jax
import numpy as np

log = logging.getLogger(__name__)

# what a backend with no accelerator memory to report (the CPU of tests and
# rehearsals) is sized against: one v5e chip's 16 GiB
DEFAULT_HBM_BYTES = 16 * 1024**3

# slice of the budget held back for XLA workspace, decode activations, and
# the donated-state double buffers (vLLM hides the analogous costs inside its
# profiling "dummy run"; a fixed fraction is the static-shape equivalent)
ACTIVATION_RESERVE = 0.08


def device_hbm_bytes(device=None) -> int:
    """Accelerator memory capacity as the runtime reports it. A TPU that
    reports no ``bytes_limit`` is an error (a pool sized against a guessed
    chip either wastes the real one or overruns it); only a backend that is
    not a TPU gets ``DEFAULT_HBM_BYTES``."""
    dev = device or jax.local_devices()[0]
    if dev.platform != "tpu":
        return DEFAULT_HBM_BYTES
    stats = dev.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit'] to size the KV "
            "pool against — pass hbm_bytes explicitly"
        )
    return int(stats["bytes_limit"])


def tree_bytes(params) -> int:
    """Total bytes of a host/device param tree (quantized containers count
    weight + scales — whatever the leaves actually store)."""
    return sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
        if hasattr(leaf, "nbytes")
    )


def page_bytes(model_cfg, page_size: int, kv_quant: str = "none") -> int:
    """HBM bytes one KV page costs across the layers that KEEP pages
    (``paged_layers``: all of a dense or latent model's, the sparse or softmax
    layers of one whose layers differ in kind, none of a power-retention
    model's, whose page costs 0 bytes); k + v, each at its own width
    (``key_row``: ``head_dim`` in whole lane tiles where it is wider than one;
    ``value_head_dim``) over the paged layers' KV heads, or for a
    latent page one row a
    token, ``latent_dim`` values in ``latent_row`` lanes, one array a layer, no
    V beside it and no kv-head factor, and where the model has a learned index
    a token's index key (``index_head_dim`` values) in a second array."""
    layers = model_cfg.paged_layers
    if model_cfg.latent:
        index = model_cfg.index_head_dim if model_cfg.index_topk else 0
        return page_size * (model_cfg.latent_row + index) * 2 * layers
    tokens = model_cfg.num_kv_heads * page_size  # a KV head's, K's and V's alike
    values = tokens * (model_cfg.key_row + model_cfg.value_head_dim)
    if kv_quant == "int8":
        # int8 payload + f32 per-token absmax scales [K, P, ps, 1], K's and V's
        return (values + 2 * tokens * 4) * layers
    return values * 2 * layers  # bf16


def slot_state_bytes(model_cfg, max_tokens: int) -> int:
    """Bytes ONE slot holds beside its pages: the row states of a model whose
    layers differ in kind (``models/hybrid.py::ROW_STATES``: recurrent states,
    normalisers, convolution tails, a selector's pooled keys over
    ``max_tokens``), read off ``init_mixer_state``'s shapes. 0 for a dense
    model."""
    if not model_cfg.hybrid:
        return 0
    from distrl_llm_tpu.models.hybrid import ROW_STATES, init_mixer_state

    shapes = jax.eval_shape(lambda: init_mixer_state(model_cfg, 1, max_tokens))
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for name in ROW_STATES for x in shapes.get(name, ()))


def state_slots(model_cfg, *, gpu_usage: float, param_bytes: int, max_tokens: int,
                hbm_bytes: int | None = None) -> int:
    """Decode slots a budget allows where a slot's cache is state alone (no
    layer keeps a page, so ``kv_pool_pages`` has nothing to count): what is
    left of ``gpu_usage`` after the reserve and the weights, over
    ``slot_state_bytes``. At least 1."""
    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    budget = int(hbm * (gpu_usage - ACTIVATION_RESERVE) - param_bytes)
    return max(1, budget // max(slot_state_bytes(model_cfg, max_tokens), 1))


def kv_pool_pages(
    model_cfg,
    *,
    gpu_usage: float,
    param_bytes: int,
    batch_prompts: int,
    max_prompt_tokens: int,
    max_new_tokens: int,
    page_size: int,
    kv_quant: str = "none",
    spec_draft: int = 0,
    hbm_bytes: int | None = None,
    continuous: bool = False,
    prefix_cache: bool = False,
    slots: int = 0,
) -> int:
    """Pages available to the refill decode pool under ``gpu_usage``.

    Subtracts, in order: the (1 - usage) exclusion the knob demands, the
    activation reserve, resident weights, and the SHARED prompt page region
    (batch_prompts × prompt_pages — prefill owns those regardless of the
    pool). With ``continuous`` (ISSUE 12 continuous admission) prompt
    chains are allocated FROM the pool, so the static region subtraction
    drops — those bytes become pool capacity — and the single-sequence
    floor carries one prompt chain. Clamped below at that minimum, so a
    too-small budget degrades to serial decoding instead of refusing to
    run (with a warning naming the shortfall).

    A model whose layers differ in kind pays for pages in its PAGED layers
    only (``page_bytes``), and the row states of its ``slots`` decode slots
    and ``batch_prompts`` prompts (``slot_state_bytes``) come off the budget
    before pages. Where no layer keeps a page a page costs nothing and 0 is
    returned: the engine's worst-case table, and ``state_slots`` says how many
    slots the budget holds."""
    from distrl_llm_tpu.ops.paged import pages_per_seq

    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    pb = page_bytes(model_cfg, page_size, kv_quant)
    prompt_pages = pages_per_seq(max_prompt_tokens, page_size)
    shared_bytes = 0 if continuous else batch_prompts * prompt_pages * pb
    state_bytes = (slots + batch_prompts) * slot_state_bytes(
        model_cfg, max_prompt_tokens + max_new_tokens)
    budget = int(
        hbm * (gpu_usage - ACTIVATION_RESERVE) - param_bytes - shared_bytes
        - state_bytes
    )
    if pb == 0:  # no layer keeps a page: there is nothing to budget
        return 0
    pool = budget // pb if budget > 0 else 0
    private_pages = 1 + pages_per_seq(max_new_tokens + max(spec_draft, 0),
                                      page_size)
    floor = 1 + private_pages + (prompt_pages if continuous else 0)
    if prefix_cache:
        # tiered KV cache (ISSUE 18): warm radix-cache pages are resident
        # in the SAME pool, so the floor carries one extra prompt chain —
        # a clamped budget still leaves the cache able to keep at least one
        # cached prefix resident next to the serial-decode minimum
        floor += prompt_pages
    if pool < floor:
        log.warning(
            "actor_gpu_usage=%.2f leaves %d KV pages (< single-sequence "
            "minimum %d) after %.2f GiB weights on %.2f GiB HBM; clamping — "
            "decode will serialize",
            gpu_usage, pool, floor, param_bytes / 1024**3, hbm / 1024**3,
        )
        return floor
    return pool
