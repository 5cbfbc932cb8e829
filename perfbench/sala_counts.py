"""Operations and bytes MiniCPM-SALA needs: the ``counts`` module of
``configs/minicpm-sala-L10.json`` (found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time. A
decode token at position t (context t + 1) needs, in each SPARSE layer, the K
and V of the blocks its query attends (every block while the context is at
most ``dense_len``; else the first ``init_blocks``, the blocks that overlap
its last ``window_size`` tokens and ``topk`` of the rest; whole blocks but for
the one it writes) and the pooled keys it scores; in each LIGHTNING layer, the
state read once and written once in float32. ``model`` is
``dataclasses.asdict`` of the program's ``ModelConfig``.
"""

from __future__ import annotations

from typing import Any, Mapping

STATE_BYTES = 4  # the recurrent state is float32, whatever the weights are
_KIND = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def layer_kinds(model: Mapping[str, Any]) -> list[str]:
    return [_KIND[m] for m in model["mixer_types"][: int(model["num_layers"])]]


def _widths(model: Mapping[str, Any], kind: str) -> dict[str, int]:
    if kind == "sparse":
        q = int(model["num_heads"]) * int(model["head_dim"])
        kv = int(model["num_kv_heads"]) * int(model["head_dim"])
        gate = bool(model["attn_output_gate"])
        head_dim = int(model["head_dim"])
    else:
        q = kv = int(model["lightning_heads"]) * int(model["lightning_head_dim"])
        gate = bool(model["lightning_output_gate"])
        head_dim = int(model["lightning_head_dim"])
    return {"hidden": int(model["hidden_size"]), "ffn": int(model["intermediate_size"]),
            "q": q, "kv": kv, "gate": gate, "head_dim": head_dim}


def layer_matmul_params(model: Mapping[str, Any], kind: str) -> int:
    """Weights of one layer's projections: q, k, v, o, the output gate, and
    the MLP's gate, up and down."""
    w = _widths(model, kind)
    return (
        w["hidden"] * w["q"] * (2 + w["gate"]) + 2 * w["hidden"] * w["kv"]
        + 3 * w["hidden"] * w["ffn"]
    )


def layer_norm_params(model: Mapping[str, Any], kind: str) -> int:
    w = _widths(model, kind)
    n = 2 * w["hidden"] + (2 * w["head_dim"] if model["qk_norm"] else 0)
    if kind == "lightning" and model["lightning_output_norm"]:
        n += w["q"]
    return n


def layer_lora_params(model: Mapping[str, Any], kind: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over the seven targets
    (the output gate is frozen and has none)."""
    w = _widths(model, kind)
    pairs = [("hidden", "q"), ("hidden", "kv"), ("hidden", "kv"), ("q", "hidden"),
             ("hidden", "ffn"), ("hidden", "ffn"), ("ffn", "hidden")]
    return sum(rank * (w[i] + w[o]) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads whatever the batch: every
    layer's projections and norms, the untied head, the final norm, the
    adapter's factors (an untied embedding is only gathered from)."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    base = hidden * vocab + hidden
    lora = 0
    for kind in layer_kinds(model):
        base += layer_matmul_params(model, kind) + layer_norm_params(model, kind)
        lora += layer_lora_params(model, kind, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def attended_tokens(model: Mapping[str, Any], t: int) -> tuple[int, int]:
    """(tokens, blocks) a sparse layer's query at position ``t`` attends."""
    bs = int(model["sparse_block_size"])
    current = t // bs
    if t + 1 <= int(model["sparse_dense_len"]):
        return t + 1, current + 1
    first_window = max((t - int(model["sparse_window_size"]) + 1) // bs, 0)
    forced = {b for b in range(int(model["sparse_init_blocks"])) if b <= current}
    forced |= set(range(first_window, current + 1))
    rest = current + 1 - len(forced)
    blocks = len(forced) + min(int(model["sparse_topk"]), rest)
    return (blocks - 1) * bs + t % bs + 1, blocks


def pooled_seen(model: Mapping[str, Any], t: int) -> int:
    """Pooled keys that end at or before position ``t``."""
    kernel, stride = int(model["sparse_kernel_size"]), int(model["sparse_kernel_stride"])
    return max((t + 1 - kernel) // stride + 1, 0)


def _rows(prompt_lens, gen_lens):
    for p, g in zip(prompt_lens, gen_lens):
        yield int(p), int(g)


def sparse_attn_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """Bytes the sparse layers' decode must read: the chosen blocks' K and V
    and the pooled keys each query scores, over every decoded token."""
    kv_dim = int(model["num_kv_heads"]) * int(model["head_dim"])
    n = layer_kinds(model).count("sparse")
    tokens = pooled = 0
    for p, g in _rows(prompt_lens, gen_lens):
        for t in range(p, p + g):
            tokens += attended_tokens(model, t)[0]
            pooled += pooled_seen(model, t)
    return float(n * kv_dim * kv_bytes * (2 * tokens + pooled))


def linear_attn_bytes(model: Mapping[str, Any], prompt_lens, gen_lens) -> float:
    """Bytes the lightning layers' decode must move: each layer's state read
    once and written once, in float32, for every decoded token."""
    heads, d = int(model["lightning_heads"]), int(model["lightning_head_dim"])
    n = layer_kinds(model).count("lightning")
    steps = sum(g for _, g in _rows(prompt_lens, gen_lens))
    return float(steps * n * 2 * heads * d * d * STATE_BYTES)


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2) -> float:
    """What takes the place of a dense decoder's KV read: chosen K/V and
    pooled keys in the sparse layers, the state read and written in the
    lightning layers."""
    return sparse_attn_bytes(
        model, prompt_lens, gen_lens, kv_bytes=kv_bytes
    ) + linear_attn_bytes(model, prompt_lens, gen_lens)


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), the mixer forward and twice that
    backward, the frozen head at the scored positions. A sparse layer's mixer
    is attention over the tokens its queries attend (the mean over the row's
    positions); a lightning layer's is the recurrence, 4 D^2 per head and
    token (the outer product into the state, and the state times q)."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    mean_attended = sum(
        attended_tokens(model, t)[0] for t in range(seq_len)) / float(seq_len)
    total = 4.0 * hidden * vocab * (answer_len / float(seq_len))
    for kind in layer_kinds(model):
        w = _widths(model, kind)
        mixer = (
            4.0 * w["q"] * mean_attended if kind == "sparse"
            else 4.0 * w["q"] * w["head_dim"]
        )
        total += (
            4.0 * layer_matmul_params(model, kind)
            + 6.0 * layer_lora_params(model, kind, lora_rank) + 3.0 * mixer
        )
    return total
