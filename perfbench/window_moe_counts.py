"""Operations and bytes a window model with routed experts needs
(``exaone_moe``: K-EXAONE-236B-A23B), as ONE CHIP'S SHARE of a layer holds it:
the ``counts`` module of ``configs/k-exaone-236b-ep8-L5.json`` (found like its
``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``mixer_types`` / ``mlp_types`` the published per-layer lists (the first
``num_layers`` entries are run), ``n_routed_experts`` the experts HELD here,
``router_experts`` the width the router scores (0: the same),
``sliding_window`` the keys a window layer's token attends, itself included.

Two caches, counted apart. A FULL layer keeps K and V of every token in pages
(4,096 B a token a layer at the published widths) and a decoded token reads
all of it. A WINDOW layer keeps a ring of ``sliding_window`` tokens a slot
(524,288 B a layer a slot) and a decoded token reads ``min(context, window)``
keys of it, whatever the context. ``softmax_kv_bytes`` is the full layers'
alone: it is what ``kernel.softmax_paged_roofline`` divides by the paged
launch's time, and the rings do NOT run as that launch (plain XLA under
``model/window_attn``), so their bytes are in ``window_kv_bytes`` and not there.

Two counts of the experts, on purpose, as ``delta_moe_counts`` has them: a
decode STEP reads every expert HELD once; a TOKEN runs ``experts_per_token``
experts wherever they are held, so this chip's part of its operations is
``experts_per_token x held / width`` experts (``train_flops_per_token``).
"""

from __future__ import annotations

from typing import Any, Mapping

#: keys one unit of the program's counters ``engine/window_pages_*`` stands for
COUNT_UNIT = 128


def layer_kinds(model: Mapping[str, Any]) -> list[tuple[str, str]]:
    """(mixer, second half) of each layer that is run: ("window" | "full",
    "dense" | "experts")."""
    n = int(model["num_layers"])
    return [
        ("window" if m == "sliding_attention" else "full",
         "dense" if f == "dense" else "experts")
        for m, f in zip(list(model["mixer_types"])[:n], list(model["mlp_types"])[:n])
    ]


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    hd = int(model["head_dim"])
    return {
        "hidden": int(model["hidden_size"]),
        "q": int(model["num_heads"]) * hd,
        "kv": int(model["num_kv_heads"]) * hd,
        "head": hd,
        "dense": int(model["intermediate_size"]),
        "expert": int(model["moe_intermediate_size"]),
        "shared": int(model["n_shared_experts"]) * int(model["moe_intermediate_size"]),
        "held": int(model["n_routed_experts"]),
        "width": int(model["router_experts"]) or int(model["n_routed_experts"]),
        "window": int(model["sliding_window"]),
    }


def mixer_params(model: Mapping[str, Any]) -> int:
    """One layer's q, k, v, o: the same in both mixers."""
    w = _sizes(model)
    return 2 * w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"]


def ffn_params(model: Mapping[str, Any], ffn: str, routed: float) -> float:
    """One layer's second half: the dense gated MLP, or ``routed`` routed
    experts counted beside the shared expert and the router at its published
    width."""
    w = _sizes(model)
    if ffn == "dense":
        return 3 * w["hidden"] * w["dense"]
    return 3 * w["hidden"] * (routed * w["expert"] + w["shared"]) + w["hidden"] * w["width"]


def layer_small_params(model: Mapping[str, Any], ffn: str) -> int:
    """The two layer norms, the q and k norms, and an expert layer's bias."""
    w = _sizes(model)
    return 2 * w["hidden"] + 2 * w["head"] + (w["width"] if ffn == "experts" else 0)


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter this program holds, to the unit: the embedding, the
    untied head, the final norm and each layer (3,712,028,416 at the cell's
    cut; a test holds it equal to the program's own tree)."""
    w = _sizes(model)
    total = 2 * w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    for _, ffn in layer_kinds(model):
        total += int(mixer_params(model) + ffn_params(model, ffn, w["held"])
                     + layer_small_params(model, ffn))
    return total


def layer_lora_params(model: Mapping[str, Any], ffn: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, k, v, o and the
    second half's gated MLP (the dense one, or the shared expert)."""
    w = _sizes(model)
    f = w["dense"] if ffn == "dense" else w["shared"]
    pairs = [(w["hidden"], w["q"]), (w["hidden"], w["kv"]), (w["hidden"], w["kv"]),
             (w["q"], w["hidden"]), (w["hidden"], f), (w["hidden"], f), (f, w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer's mixer, norms and
    second half with EVERY expert held, the untied head over the vocabulary
    slice, the final norm, the adapter's factors (the embedding is a lookup)."""
    w = _sizes(model)
    base = w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    lora = 0
    for _, ffn in layer_kinds(model):
        base += int(mixer_params(model) + ffn_params(model, ffn, w["held"])
                    + layer_small_params(model, ffn))
        lora += layer_lora_params(model, ffn, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads: every expert
    held, in every EXPERT layer (the dense layer has none)."""
    w = _sizes(model)
    layers = sum(1 for _, ffn in layer_kinds(model) if ffn == "experts")
    return layers * w["held"] * 3 * w["hidden"] * w["expert"] * weight_bytes


def kv_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """K and V of one token in ONE layer: a page's cost a token a full layer."""
    return 2 * _sizes(model)["kv"] * kv_bytes


def ring_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """One slot's ring in ONE window layer: K and V of ``sliding_window`` tokens."""
    return _sizes(model)["window"] * kv_token_bytes(model, kv_bytes=kv_bytes)


def slot_state_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """What a slot holds beside its pages: a ring a window layer."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "window")
    return layers * ring_bytes(model, kv_bytes=kv_bytes)


def _full_tokens(prompt_lens, gen_lens) -> int:
    """Keys the decoded tokens of these rows attend in a full layer: token j of
    a row attends its prompt and the j tokens up to itself."""
    return sum(int(g) * int(p) + int(g) * (int(g) + 1) // 2
               for p, g in zip(prompt_lens, gen_lens))


def _window_tokens(prompt_lens, gen_lens, window: int) -> int:
    """Keys they attend in a window layer: ``min(context, window)`` each."""
    return sum(min(int(p) + j, window)
               for p, g in zip(prompt_lens, gen_lens) for j in range(1, int(g) + 1))


def softmax_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                     kv_bytes: int = 2) -> float:
    """Bytes of K and V the FULL layers' decode must read, once a row (K/V is
    per head: a prompt's pages are read once a candidate). The rings are not
    here (module docstring)."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "full")
    return float(layers * kv_token_bytes(model, kv_bytes=kv_bytes)
                 * _full_tokens(prompt_lens, gen_lens))


def window_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                    kv_bytes: int = 2) -> float:
    """Bytes of K and V the WINDOW layers' decode must read: ``min(context,
    window)`` keys a live row a window layer a step, whatever implements it."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "window")
    return float(layers * kv_token_bytes(model, kv_bytes=kv_bytes)
                 * _window_tokens(prompt_lens, gen_lens, _sizes(model)["window"]))


def delta_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """No layer of this model keeps a delta-rule state. ``readers/delta_moe_work``
    asks a counts module for this name before it reads ``softmax_kv_bytes`` for
    ``kernel.softmax_paged_roofline``: nothing to move."""
    return 0.0


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2) -> float:
    """What takes the place of a dense decoder's KV read: the full layers'
    pages and the window layers' rings."""
    return (softmax_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes)
            + window_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes))


def window_pages(model: Mapping[str, Any], prompt_lens, gen_lens) -> tuple[int, int]:
    """What the program's counters ``engine/window_pages_attended`` /
    ``_visible`` must read for these rows: per row, window layer and decode
    step the keys attended and the keys a full layer would attend, each rounded
    up to whole units of ``COUNT_UNIT`` keys."""
    window = _sizes(model)["window"]
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "window")
    units = lambda n: -(-n // COUNT_UNIT)
    rows = [(int(p), int(g)) for p, g in zip(prompt_lens, gen_lens)]
    return (layers * sum(units(min(p + j, window)) for p, g in rows for j in range(1, g + 1)),
            layers * sum(units(p + j) for p, g in rows for j in range(1, g + 1)))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), attention forward and twice that
    backward (a full layer's token at the mean causal context, a window
    layer's at ``min`` of that and the window: the band, not the mask's
    square), the frozen head at the scored positions. Experts: this chip's
    part of the ``experts_per_token`` a token runs, and the shared one."""
    w = _sizes(model)
    here = int(model["experts_per_token"]) * w["held"] / float(w["width"])
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    mean_ctx = (seq_len + 1) / 2.0
    for mixer, ffn in layer_kinds(model):
        keys = mean_ctx if mixer == "full" else min(mean_ctx, float(w["window"]))
        attend = 2.0 * 2 * w["q"] * keys
        total += (4.0 * (mixer_params(model) + ffn_params(model, ffn, here))
                  + 6.0 * layer_lora_params(model, ffn, lora_rank) + 3.0 * attend)
    return total
