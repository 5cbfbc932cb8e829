"""Operations and bytes a power-retention model needs (``brumby``:
Brumby-14B-Base): the ``counts`` module of ``configs/brumby-14b-L4.json``
(found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time, and
the state is reckoned PACKED, ``D = head_dim (head_dim + 1) / 2`` (8,256 at
128), whatever layout the program holds it in. ``model`` is
``dataclasses.asdict`` of the program's ``ModelConfig``.

A slot's whole cache is state: a float32 ``[D, head_dim]`` state and a ``[D]``
normaliser a KV head a layer (34.08 MB a layer a slot at the published
widths), read and written once a decoded token whatever the context. There is
no K/V to read, so ``kv_read_bytes`` is the state's traffic.
"""

from __future__ import annotations

from typing import Any, Mapping

#: a power-retention state and its normaliser are float32 whatever the served type
STATE_BYTES = 4
#: tokens of one chunk of the chunked form, the size its operations are counted
#: at: the engine's prefill segment (the attention form inside it)
CHUNK = 1024


def state_dim(model: Mapping[str, Any]) -> int:
    d = int(model["head_dim"])
    return d * (d + 1) // 2


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    heads, kv, hd = int(model["num_heads"]), int(model["num_kv_heads"]), int(model["head_dim"])
    return {"hidden": int(model["hidden_size"]), "q": heads * hd, "kv": kv * hd,
            "kv_heads": kv, "heads": heads, "mlp": int(model["intermediate_size"])}


def layer_params(model: Mapping[str, Any]) -> int:
    """One layer's matrices: q and o, k and v, the log-decay's projection, the
    gated MLP's three."""
    w = _sizes(model)
    return (2 * w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"]
            + w["hidden"] * w["kv_heads"] + 3 * w["hidden"] * w["mlp"])


def layer_small_params(model: Mapping[str, Any]) -> int:
    """Two norms, the q and k head norms, the log-decay's bias."""
    return (2 * int(model["hidden_size"]) + 2 * int(model["head_dim"])
            + int(model["num_kv_heads"]))


def layer_lora_params(model: Mapping[str, Any], rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, k, v, o, gate,
    up, down. The log-decay is frozen and has none."""
    w = _sizes(model)
    pairs = [(w["hidden"], w["q"]), (w["hidden"], w["kv"]), (w["hidden"], w["kv"]),
             (w["q"], w["hidden"]), (w["hidden"], w["mlp"]), (w["hidden"], w["mlp"]),
             (w["mlp"], w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer, the untied head,
    the final norm, the adapter's factors."""
    hidden, vocab, layers = (int(model["hidden_size"]), int(model["vocab_size"]),
                             int(model["num_layers"]))
    base = hidden * vocab + hidden + layers * (
        layer_params(model) + layer_small_params(model))
    lora = layers * layer_lora_params(model, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def slot_state_bytes(model: Mapping[str, Any]) -> int:
    """Bytes ONE slot's states hold: S and z a KV head a layer."""
    return (int(model["num_layers"]) * int(model["num_kv_heads"]) * state_dim(model)
            * (int(model["head_dim"]) + 1) * STATE_BYTES)


def power_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """Bytes the decode steps must move: each layer's S and z read once and
    written once, float32, for every decoded token (``kv_bytes`` is the pages'
    and is not read: there is no page)."""
    return float(2 * slot_state_bytes(model) * sum(int(g) for g in gen_lens))


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2) -> float:
    """What takes the place of a dense decoder's KV read: the states read and
    written, whatever the context."""
    return power_state_bytes(model, prompt_lens, gen_lens)


def power_flops_per_token(model: Mapping[str, Any], chunk: int = CHUNK) -> float:
    """Operations of the chunked form for ONE token of one layer at ``chunk``
    tokens a chunk: a query head's row of the chunk's scores and of their
    product with v (half a ``chunk x chunk`` each, 2 d a pair: 2 d chunk a
    head), its read of the carried state (2 D d) and normaliser (2 D); a KV
    head's write into the state (2 D d)."""
    w, d, big = _sizes(model), int(model["head_dim"]), state_dim(model)
    return float(w["heads"] * (2 * d * chunk + 2 * big * d + 2 * big)
                 + w["kv_heads"] * 2 * big * d)


def power_chunk_flops(model: Mapping[str, Any], prompt_lens, *, chunk: int = CHUNK) -> float:
    """Operations the prefill of ``prompt_lens`` (one entry a PROMPT, real
    tokens) needs in the retention layers."""
    return (int(model["num_layers"]) * power_flops_per_token(model, chunk)
            * sum(int(p) for p in prompt_lens))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), the mixer forward and twice that
    backward, the frozen head at the scored positions. The mixer is the
    chunked form at chunks of ``min(seq_len, CHUNK)``."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    mixer = power_flops_per_token(model, min(int(seq_len), CHUNK))
    return (4.0 * hidden * vocab * (answer_len / float(seq_len))
            + int(model["num_layers"]) * (
                4.0 * layer_params(model) + 6.0 * layer_lora_params(model, lora_rank)
                + 3.0 * mixer))
