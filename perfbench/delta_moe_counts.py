"""Operations and bytes a gated delta-rule model with routed experts needs
(``solar_open2``: Solar-Open2-250B), as ONE CHIP'S SHARE of a layer holds it:
the ``counts`` module of ``configs/solar-open2-250b-ep8-L4.json`` (found like
its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``n_routed_experts`` is the experts HELD here, ``router_experts`` the width
the router scores (0: the same), ``mixer_types`` the run layers' published
kinds ("gqa" softmax, "kda" delta rule).

Two counts of the experts, on purpose, as ``latent_moe_counts`` has them. A
decode STEP reads every expert HELD once (128 rows x 8 choices over 320 make
3.2 pairs an expert a step: none is idle). A TOKEN runs ``experts_per_token``
experts wherever they are held, so this chip's part of its operations is
``experts_per_token x held / width`` experts: that is what
``train_flops_per_token`` counts.
"""

from __future__ import annotations

from typing import Any, Mapping

#: a delta-rule state is float32 whatever the served type
STATE_BYTES = 4
#: tokens of one chunk of the chunked rule, the size its operations are counted at
CHUNK = 64
_KINDS = {"gqa": "softmax", "kda": "delta"}


def layer_kinds(model: Mapping[str, Any]) -> list[str]:
    return [_KINDS[m] for m in list(model["mixer_types"])[: int(model["num_layers"])]]


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    heads, hd = int(model["num_heads"]), int(model["head_dim"])
    return {
        "hidden": int(model["hidden_size"]),
        "q": heads * hd,
        "kv": int(model["num_kv_heads"]) * hd,
        "delta": int(model["delta_heads"]) * int(model["delta_head_dim"]),
        "rank": int(model["delta_low_rank"]),
        "expert": int(model["moe_intermediate_size"]),
        "shared": int(model["n_shared_experts"]) * int(model["moe_intermediate_size"]),
        "held": int(model["n_routed_experts"]),
        "width": int(model["router_experts"]) or int(model["n_routed_experts"]),
    }


def mixer_params(model: Mapping[str, Any], kind: str) -> int:
    """One layer's mixer matrices. softmax: q, o and the gate, k and v. delta:
    q, k, v, o, the decay's and the gate's low-rank pairs, beta."""
    w = _sizes(model)
    if kind == "softmax":
        return 3 * w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"]
    return (4 * w["hidden"] * w["delta"] + 2 * w["rank"] * (w["hidden"] + w["delta"])
            + w["hidden"] * int(model["delta_heads"]))


def ffn_params(model: Mapping[str, Any], routed: float) -> float:
    """One layer's gated MLPs with ``routed`` routed experts counted, the
    shared expert and the router at its published width."""
    w = _sizes(model)
    return (3 * w["hidden"] * (routed * w["expert"] + w["shared"])
            + w["hidden"] * w["width"])


def layer_small_params(model: Mapping[str, Any], kind: str) -> int:
    """Norms, the router's bias and, in a delta layer, the convolution
    filters, ``A_log``, ``dt_bias`` and the head-wise norm."""
    w = _sizes(model)
    small = 2 * w["hidden"] + w["width"]
    if kind == "delta":
        small += (int(model["delta_conv_size"]) * 3 * w["delta"] + w["delta"]
                  + int(model["delta_heads"]) + int(model["delta_head_dim"]))
    return small


def layer_lora_params(model: Mapping[str, Any], kind: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, k, v, o and the
    shared expert's three. Everything else is frozen and has none."""
    w = _sizes(model)
    q, kv = (w["q"], w["kv"]) if kind == "softmax" else (w["delta"], w["delta"])
    pairs = [(w["hidden"], q), (w["hidden"], kv), (w["hidden"], kv), (q, w["hidden"]),
             (w["hidden"], w["shared"]), (w["hidden"], w["shared"]),
             (w["shared"], w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer's mixer, norms,
    router, shared expert and EVERY expert held, the untied head over the
    vocabulary slice, the final norm, the adapter's factors."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    base = hidden * vocab + hidden
    lora = 0
    ffn = ffn_params(model, _sizes(model)["held"])
    for kind in layer_kinds(model):
        base += int(mixer_params(model, kind) + ffn + layer_small_params(model, kind))
        lora += layer_lora_params(model, kind, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads: every expert
    held, in every layer (each is an expert layer)."""
    w = _sizes(model)
    return len(layer_kinds(model)) * w["held"] * 3 * w["hidden"] * w["expert"] * weight_bytes


def softmax_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                     kv_bytes: int = 2) -> float:
    """Bytes of K and V the softmax layers' decode must read: every decoded
    token attends over its prompt and the tokens before it, once a row (K/V
    is per head: a prompt's pages are read once a candidate)."""
    w = _sizes(model)
    tokens = sum(int(g) * int(p) + int(g) * (int(g) + 1) // 2
                 for p, g in zip(prompt_lens, gen_lens))
    return float(layer_kinds(model).count("softmax") * 2 * w["kv"] * kv_bytes * tokens)


def delta_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """Bytes the delta-rule layers' decode must move: each layer's state read
    once and written once, float32, for every decoded token (``kv_bytes`` is
    the pages' and is not read: a state is float32)."""
    heads, d = int(model["delta_heads"]), int(model["delta_head_dim"])
    steps = sum(int(g) for g in gen_lens)
    return float(steps * layer_kinds(model).count("delta") * 2 * heads * d * d * STATE_BYTES)


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2) -> float:
    """What takes the place of a dense decoder's KV read: the softmax layers'
    K/V and the delta-rule layers' states read and written."""
    return (softmax_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes)
            + delta_state_bytes(model, prompt_lens, gen_lens))


def delta_flops_per_token(model: Mapping[str, Any], chunk: int = CHUNK) -> float:
    """Operations of the chunked rule for ONE token of one layer, all heads, at
    ``chunk`` tokens a chunk: its row of the two score matrices (half a
    ``chunk x chunk`` each, 2 D a pair), its row of the triangular solve and
    of the scores' product with the solution (half of 2 D ``chunk`` each), and
    three products with the state (into the right-hand side, into the output,
    into the new state: 2 D^2 each)."""
    heads, d = int(model["delta_heads"]), int(model["delta_head_dim"])
    return float(heads * (4 * chunk * d + 6 * d * d))


def delta_chunk_flops(model: Mapping[str, Any], prompt_lens, *, chunk: int = CHUNK) -> float:
    """Operations the delta-rule layers' prefill of ``prompt_lens`` (one entry
    a PROMPT, real tokens) needs."""
    return (layer_kinds(model).count("delta") * delta_flops_per_token(model, chunk)
            * sum(int(p) for p in prompt_lens))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), the mixer forward and twice that
    backward, the frozen head at the scored positions. Experts: this chip's
    part of the ``experts_per_token`` a token runs, and the shared one."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    w = _sizes(model)
    here = int(model["experts_per_token"]) * w["held"] / float(w["width"])
    total = 4.0 * hidden * vocab * (answer_len / float(seq_len))
    for kind in layer_kinds(model):
        mixer = (2.0 * 2 * w["q"] * (seq_len + 1) / 2.0 if kind == "softmax"
                 else delta_flops_per_token(model))
        total += (4.0 * (mixer_params(model, kind) + ffn_params(model, here))
                  + 6.0 * layer_lora_params(model, kind, lora_rank) + 3.0 * mixer)
    return total
