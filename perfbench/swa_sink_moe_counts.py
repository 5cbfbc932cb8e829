"""Operations and bytes a window model whose two mixers differ in KV heads
and whose keys and values differ in width needs (``mimo_v2_flash``:
MiMo-V2-Flash), as ONE CHIP'S SHARE of a layer holds it: the ``counts`` module
of ``configs/mimo-v2-flash-ep16-L7.json`` (found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``mixer_types`` / ``mlp_types`` the per-layer lists (the first ``num_layers``
entries are run), ``num_kv_heads`` the FULL layers' KV heads and
``window_kv_heads`` the window layers', ``head_dim`` the width of q and k and
``v_head_dim`` the value's, ``n_routed_experts`` the experts HELD here,
``router_experts`` the width the router scores (0: the same),
``sliding_window`` the keys a window layer's token attends, itself included.

Two caches, counted apart, K and V each at its own width. A FULL layer keeps K
and V of every token in pages (4 x (192 + 128) x 2 B = 2,560 B a token a layer
at the published widths) and a decoded token attends all of it. A WINDOW layer
keeps a ring of ``sliding_window`` tokens a slot (8 x 128 x (192 + 128) x 2 B =
655,360 B a layer a slot) and a decoded token reads ``min(context, window)``
keys of it, whatever the context. ``softmax_kv_bytes`` is the full layers'
alone: it is what ``kernel.softmax_paged_roofline`` divides by the paged
launch's time, and the rings do NOT run as that launch (plain XLA under
``model/window_attn``), so their bytes are in ``window_kv_bytes`` and not there.

**A shared prompt's pages once a GROUP.** K and V of a prompt are the same
bytes for every candidate of its group, so what the ALGORITHM must move for a
decoded position is the prompt's pages once for the group and each row's own
generated tail a row: ``softmax_kv_bytes`` and ``kv_read_bytes`` take
``group_size`` (the harness tells it to a function whose signature has it, as
it does for ``latent_moe_counts`` and ``cca_moe_counts``). The paged launch
that stands reads a prompt's pages once a ROW, so the roofline share reads low
by about the group's size: that is what the launch leaves, not a fault.

Two counts of the experts, on purpose, as ``delta_moe_counts`` has them: a
decode STEP reads every expert HELD once; a TOKEN runs ``experts_per_token``
experts wherever they are held, so this chip's part of its operations is
``experts_per_token x held / width`` experts (``train_flops_per_token``). There
is no shared expert.
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
    hv = int(model.get("v_head_dim") or 0) or hd
    heads = int(model["num_heads"])
    full = int(model["num_kv_heads"])
    return {
        "hidden": int(model["hidden_size"]),
        "heads": heads,
        "q": heads * hd,  # what W_q writes
        "o": heads * hv,  # what W_o reads
        "k_head": hd, "v_head": hv,
        "kv_full": full,
        "kv_window": int(model.get("window_kv_heads") or 0) or full,
        "dense": int(model["intermediate_size"]),
        "expert": int(model["moe_intermediate_size"]),
        "held": int(model["n_routed_experts"]),
        "width": int(model["router_experts"]) or int(model["n_routed_experts"]),
        "window": int(model["sliding_window"]),
    }


def kv_heads(model: Mapping[str, Any], mixer: str) -> int:
    """KV heads of a layer of ``mixer`` ("window" | "full")."""
    return _sizes(model)["kv_window" if mixer == "window" else "kv_full"]


def _mixer_pairs(model: Mapping[str, Any], mixer: str) -> list[tuple[int, int]]:
    """(in, out) of q, k, v, o of one layer of ``mixer``."""
    w = _sizes(model)
    kv = kv_heads(model, mixer)
    return [(w["hidden"], w["q"]), (w["hidden"], kv * w["k_head"]),
            (w["hidden"], kv * w["v_head"]), (w["o"], w["hidden"])]


def mixer_params(model: Mapping[str, Any], mixer: str) -> int:
    """One layer's q, k, v, o at its kind's KV heads and the two widths."""
    return sum(i * o for i, o in _mixer_pairs(model, mixer))


def ffn_params(model: Mapping[str, Any], ffn: str, routed: float) -> float:
    """One layer's second half: the dense gated MLP, or ``routed`` routed
    experts counted beside the router at its published width."""
    w = _sizes(model)
    if ffn == "dense":
        return 3 * w["hidden"] * w["dense"]
    return 3 * w["hidden"] * routed * w["expert"] + w["hidden"] * w["width"]


def layer_small_params(model: Mapping[str, Any], mixer: str, ffn: str) -> int:
    """The two layer norms, a window layer's sinks, and an expert layer's bias."""
    w = _sizes(model)
    sinks = w["heads"] if mixer == "window" and model.get("window_sink") else 0
    return 2 * w["hidden"] + sinks + (w["width"] if ffn == "experts" else 0)


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter this program holds, to the unit: the embedding, the
    untied head, the final norm and each layer (a test holds it equal to the
    program's own tree)."""
    w = _sizes(model)
    total = 2 * w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    for mixer, ffn in layer_kinds(model):
        total += int(mixer_params(model, mixer) + ffn_params(model, ffn, w["held"])
                     + layer_small_params(model, mixer, ffn))
    return total


def layer_lora_params(model: Mapping[str, Any], mixer: str, ffn: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, k, v, o and, in
    the dense layer, the gated MLP's three (an expert layer has no shared
    expert: nothing there carries an adapter)."""
    w = _sizes(model)
    pairs = _mixer_pairs(model, mixer)
    if ffn == "dense":
        pairs += [(w["hidden"], w["dense"]), (w["hidden"], w["dense"]),
                  (w["dense"], w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer's mixer, norms,
    sinks and second half with EVERY expert held, the untied head over the
    vocabulary slice, the final norm, the adapter's factors (the embedding is
    a lookup)."""
    w = _sizes(model)
    base = w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    lora = 0
    for mixer, ffn in layer_kinds(model):
        base += int(mixer_params(model, mixer) + ffn_params(model, ffn, w["held"])
                    + layer_small_params(model, mixer, ffn))
        lora += layer_lora_params(model, mixer, ffn, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads: every expert
    held, in every EXPERT layer (the dense layer has none)."""
    w = _sizes(model)
    layers = sum(1 for _, ffn in layer_kinds(model) if ffn == "experts")
    return layers * w["held"] * 3 * w["hidden"] * w["expert"] * weight_bytes


def kv_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2,
                   mixer: str = "full") -> int:
    """K and V of one token in ONE layer of ``mixer``, each at its own width:
    a page's cost a token a full layer (2,560 B), a ring's a slot of a window
    layer (5,120 B)."""
    w = _sizes(model)
    return kv_heads(model, mixer) * (w["k_head"] + w["v_head"]) * kv_bytes


def cache_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """What ONE more token of context costs a slot: the full layers' pages
    (the program's gauge ``engine/cache_token_bytes``); a ring costs it nothing."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "full")
    return layers * kv_token_bytes(model, kv_bytes=kv_bytes)


def ring_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """One slot's two rings in ONE window layer: K and V of ``sliding_window``
    tokens at the window layers' KV heads."""
    return _sizes(model)["window"] * kv_token_bytes(model, kv_bytes=kv_bytes, mixer="window")


def slot_state_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """What a slot holds beside its pages: the rings of every window layer."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "window")
    return layers * ring_bytes(model, kv_bytes=kv_bytes)


def _full_tokens(prompt_lens, gen_lens, group_size: int) -> int:
    """Keys' worth of pages the decoded tokens of these rows must be read for
    in a full layer: a shared prompt's ONCE a group at each decoded position
    (for as long as the group's longest answer runs), each row's own generated
    tail a row. With ``group_size`` 1 every row reads its prompt alone."""
    prompt_lens, gen_lens = list(prompt_lens), list(gen_lens)
    if group_size < 1 or len(prompt_lens) % group_size or len(prompt_lens) != len(gen_lens):
        raise ValueError(
            f"{len(prompt_lens)} prompts and {len(gen_lens)} answers are no whole "
            f"number of groups of {group_size}")
    tokens = 0
    for at in range(0, len(prompt_lens), group_size):
        prompts = {int(p) for p in prompt_lens[at:at + group_size]}
        answers = [int(g) for g in gen_lens[at:at + group_size]]
        if len(prompts) != 1:
            raise ValueError(f"rows {at}..{at + group_size - 1} share no one prompt: {prompts}")
        tokens += max(answers) * prompts.pop() + sum(g * (g + 1) // 2 for g in answers)
    return tokens


def _window_tokens(prompt_lens, gen_lens, window: int) -> int:
    """Keys they attend in a window layer: ``min(context, window)`` each."""
    return sum(min(int(p) + j, window)
               for p, g in zip(prompt_lens, gen_lens) for j in range(1, int(g) + 1))


def softmax_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                     kv_bytes: int = 2, group_size: int = 1) -> float:
    """Bytes of K and V the FULL layers' decode must read, a shared prompt's
    pages once a GROUP (module docstring). The rings are not here."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "full")
    return float(layers * kv_token_bytes(model, kv_bytes=kv_bytes)
                 * _full_tokens(prompt_lens, gen_lens, group_size))


def window_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                    kv_bytes: int = 2) -> float:
    """Bytes of K and V the WINDOW layers' decode must read: ``min(context,
    window)`` keys a live row a window layer a step, whatever implements it
    (a ring is a row's own: no group shares it)."""
    layers = sum(1 for mixer, _ in layer_kinds(model) if mixer == "window")
    return float(layers * kv_token_bytes(model, kv_bytes=kv_bytes, mixer="window")
                 * _window_tokens(prompt_lens, gen_lens, _sizes(model)["window"]))


def delta_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """No layer of this model keeps a delta-rule state. ``readers/delta_moe_work``
    asks a counts module for this name before it reads ``softmax_kv_bytes`` for
    ``kernel.softmax_paged_roofline``: nothing to move."""
    return 0.0


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """What takes the place of a dense decoder's KV read: the full layers'
    pages, a shared prompt's once a group, and the window layers' rings."""
    return (softmax_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes,
                             group_size=group_size)
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
    backward (q.k at the key's width and p v at the value's, a full layer's
    token at the mean causal context, a window layer's at ``min`` of that and
    the window: the band, not the mask's square), the frozen head at the scored
    positions. Experts: this chip's part of the ``experts_per_token`` a token
    runs."""
    w = _sizes(model)
    here = int(model["experts_per_token"]) * w["held"] / float(w["width"])
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    mean_ctx = (seq_len + 1) / 2.0
    for mixer, ffn in layer_kinds(model):
        keys = mean_ctx if mixer == "full" else min(mean_ctx, float(w["window"]))
        attend = 2.0 * w["heads"] * (w["k_head"] + w["v_head"]) * keys
        total += (4.0 * (mixer_params(model, mixer) + ffn_params(model, ffn, here))
                  + 6.0 * layer_lora_params(model, mixer, ffn, lora_rank) + 3.0 * attend)
    return total
