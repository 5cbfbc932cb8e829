"""Peaks of the chip, and the operations and bytes the algorithm needs.

The yardstick is kept here, with the benchmark, so that a change to the
program cannot move it. Every count is of what the ALGORITHM requires from the
shapes: recomputed operations (remat), padding the program adds and bytes it
re-reads do not count. ``model`` is the ``model`` object of a configuration
file (the keyword arguments of the program's ``ModelConfig``).

Origin: the decode-step byte count (weights once a step plus each row's KV at
its context, with the LoRA factors and the page granularity) began as a copy
of the arithmetic of a benchmark script that PR 31 deleted; this is now its
only copy. The
training count replaces ``ModelConfig.train_flops_per_token`` (3 x forward),
which counts base-weight gradient matmuls that LoRA training never runs.
"""

from __future__ import annotations

import json
import os
from typing import Any, Mapping

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

#: LoRA targets and their (in, out) widths, as the program's adapters have
#: them (models/lora.py DEFAULT_TARGETS); "q"/"kv" are heads x head_dim
_LORA_TARGETS = (
    ("wq", "hidden", "q"), ("wk", "hidden", "kv"), ("wv", "hidden", "kv"),
    ("wo", "q", "hidden"), ("w_gate", "hidden", "ffn"),
    ("w_up", "hidden", "ffn"), ("w_down", "ffn", "hidden"),
)


def peaks_for_kind(device_kind: str) -> dict[str, Any]:
    """The published peaks of one chip of ``device_kind``. A kind the table
    does not hold is an error, never a default."""
    with open(_PEAKS_FILE, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in perfbench/peaks.json "
            f"(it holds {sorted(table)}): add its published peaks with their "
            "source before measuring on it"
        )
    return table[device_kind]


def _widths(model: Mapping[str, Any]) -> dict[str, int]:
    return {
        "hidden": int(model["hidden_size"]),
        "q": int(model["num_heads"]) * int(model["head_dim"]),
        "kv": int(model["num_kv_heads"]) * int(model["head_dim"]),
        "ffn": int(model["intermediate_size"]),
        "vocab": int(model["vocab_size"]),
        "layers": int(model["num_layers"]),
    }


def layer_matmul_params(model: Mapping[str, Any]) -> int:
    """Weights of one layer's seven projections (q, k, v, o, gate, up, down)."""
    w = _widths(model)
    return (
        w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"] + w["q"] * w["hidden"]
        + 3 * w["hidden"] * w["ffn"]
    )


def layer_lora_params(model: Mapping[str, Any], rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over the seven targets."""
    w = _widths(model)
    return sum(rank * (w[i] + w[o]) for _, i, o in _LORA_TARGETS)


def decode_weight_bytes(
    model: Mapping[str, Any], *, weight_bytes: int = 2, lora_rank: int = 0,
    lora_bytes: int = 4,
) -> int:
    """Bytes of weights one decode step must read whatever the batch: every
    layer's projections, biases and norms, the output head (the embedding
    table itself when tied; an untied embedding is only gathered from, so it
    does not count), the final norm, and the adapter's factors."""
    w = _widths(model)
    per_layer = layer_matmul_params(model) + 2 * w["hidden"]  # + two norms
    if model.get("attention_bias"):
        per_layer += w["q"] + 2 * w["kv"]
    base = w["layers"] * per_layer + w["hidden"] * w["vocab"] + w["hidden"]
    lora = w["layers"] * layer_lora_params(model, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def kv_bytes_per_token(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """K and V of one token over all layers (bf16 by default)."""
    w = _widths(model)
    return 2 * w["kv"] * kv_bytes * w["layers"]


def decode_step_bytes(
    model: Mapping[str, Any], *, rows: float, mean_context: float,
    weight_bytes: int = 2, kv_bytes: int = 2, lora_rank: int = 0,
    lora_bytes: int = 4, page_size: int = 0,
) -> float:
    """Bytes one decode step of ``rows`` live rows must move: the weights once
    plus each row's KV at ``mean_context`` tokens. With ``page_size`` a row's
    context counts in whole pages, since a paged kernel reads pages."""
    ctx = mean_context
    if page_size:
        ctx = -(-mean_context // page_size) * page_size
    return (
        decode_weight_bytes(
            model, weight_bytes=weight_bytes, lora_rank=lora_rank,
            lora_bytes=lora_bytes,
        )
        + rows * ctx * kv_bytes_per_token(model, kv_bytes=kv_bytes)
    )


def decode_roofline_tok_s(
    model: Mapping[str, Any], *, rows: float, mean_context: float,
    hbm_bytes_per_s: float, **layout,
) -> float:
    """Bandwidth-bound decode ceiling in tokens per second: ``rows`` tokens a
    step, each step as fast as its bytes stream from HBM."""
    step = decode_step_bytes(model, rows=rows, mean_context=mean_context, **layout)
    return rows * hbm_bytes_per_s / step


def kv_read_bytes(
    model: Mapping[str, Any], prompt_lens, gen_lens, *, kv_bytes: int = 2,
) -> float:
    """KV bytes paged attention must read to decode rows of ``gen_lens``
    tokens after prompts of ``prompt_lens``: token t of a row attends over the
    prompt and the t tokens before it. Exact token granularity (no page
    rounding), all layers."""
    total = 0.0
    for p, g in zip(prompt_lens, gen_lens):
        p, g = float(p), float(g)
        total += g * p + g * (g + 1.0) / 2.0
    return total * kv_bytes_per_token(model, kv_bytes=kv_bytes)


def train_flops_per_token(
    model: Mapping[str, Any], *, seq_len: int, answer_len: int, lora_rank: int,
) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored.

    Per layer: the forward through the frozen projections (2 per weight), the
    backward to the ACTIVATIONS through them (2 per weight; a frozen weight has
    no gradient matmul), the adapter's forward, its backward to activations and
    its two weight gradients (6 per adapter weight), and causal attention at
    the mean key length seq_len/2 (QK and PV forward, twice that backward).
    The output head is frozen and only the scored positions are projected:
    forward and backward-to-activations there. Recomputation is not counted."""
    w = _widths(model)
    attn_forward = 4.0 * w["q"] * (seq_len / 2.0)
    per_layer = (
        4.0 * layer_matmul_params(model)
        + 6.0 * layer_lora_params(model, lora_rank)
        + 3.0 * attn_forward
    )
    head = 4.0 * w["hidden"] * w["vocab"] * (answer_len / float(seq_len))
    return w["layers"] * per_layer + head
