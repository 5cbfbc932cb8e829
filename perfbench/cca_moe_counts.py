"""Operations and bytes compressed convolutional attention with an MLP router
needs (``zaya``: ZAYA1-8B), every expert held: the ``counts`` module of
``configs/zaya1-8b-L20.json`` (found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``num_heads`` / ``num_kv_heads`` / ``head_dim`` the LATENT's heads (8 + 2 of
128 below a hidden width of 2,048), ``cca_time0`` / ``cca_time1`` the two
convolutions' taps, ``router_hidden_size`` the router's width,
``n_routed_experts`` the experts (all held), ``experts_per_token`` 1.

Two caches in ONE layer. K and V of every token lie in pages (1,024 B a token a
layer at the published widths in bf16: Qwen's page at half its KV heads) and a
decoded token attends all of them; K and V are per KV head, but the rows of a
GRPO group share their prompt's pages and ALL their query heads read the same
two KV heads, so one read of a prompt's pages can serve the group: the prompt
counts ONCE a group at each decoded position, a row's own tail a row
(``group_size``, as ``latent_moe_counts`` counts a shared prompt's latent
rows). And a TAIL a slot a layer (``tail_bytes``: the last token's ``[q~ | k~]``,
its first convolution's output and the half of the value the next token reads),
read and written once a decoded token whatever the context.

Two counts of the experts: a decode STEP reads every expert once
(``expert_bytes_per_step``); a TOKEN runs one (``train_flops_per_token``).
"""

from __future__ import annotations

from typing import Any, Mapping


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    hd = int(model["head_dim"])
    heads, kv = int(model["num_heads"]), int(model["num_kv_heads"])
    return {
        "hidden": int(model["hidden_size"]), "layers": int(model["num_layers"]),
        "q": heads * hd, "kv": kv * hd, "head": hd, "groups": heads + kv, "kv_heads": kv,
        "taps0": int(model["cca_time0"]), "taps1": int(model["cca_time1"]),
        "router": int(model["router_hidden_size"]),
        "expert": int(model["moe_intermediate_size"]),
        "experts": int(model["n_routed_experts"]),
    }


def mixer_params(model: Mapping[str, Any]) -> int:
    """One layer's q, k, the value's two halves and o, all in the latent."""
    w = _sizes(model)
    return 2 * w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"]


def conv_params(model: Mapping[str, Any]) -> int:
    """The convolution grouped by head: a ``head x head`` matrix a tap a head."""
    w = _sizes(model)
    return w["taps1"] * w["groups"] * w["head"] * w["head"]


def router_params(model: Mapping[str, Any]) -> int:
    """The router's four matrices: down, two square layers, the experts' scores."""
    w = _sizes(model)
    return w["hidden"] * w["router"] + 2 * w["router"] ** 2 + w["router"] * w["experts"]


def expert_params(model: Mapping[str, Any]) -> int:
    """ONE expert: a gated MLP's three matrices."""
    w = _sizes(model)
    return 3 * w["hidden"] * w["expert"]


def layer_small_params(model: Mapping[str, Any]) -> int:
    """What a layer holds that is no matrix: the two layer norms, the residual's
    eight vectors, the depth-wise taps, both convolutions' biases, a
    temperature a KV head, the router's four biases and vectors and its
    balancing bias."""
    w = _sizes(model)
    mixed = w["q"] + w["kv"]
    return (2 * w["hidden"] + 8 * w["hidden"] + w["taps0"] * mixed + 2 * mixed
            + w["kv_heads"] + 5 * w["router"] + w["experts"])


def layer_params(model: Mapping[str, Any]) -> int:
    """Every parameter of ONE layer, all its experts among them."""
    return (mixer_params(model) + conv_params(model) + router_params(model)
            + _sizes(model)["experts"] * expert_params(model) + layer_small_params(model))


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter this program holds, to the unit: the tied embedding,
    the final norm and each layer with all its experts (a test holds it equal
    to the program's own tree)."""
    w = _sizes(model)
    head = w["hidden"] * int(model["vocab_size"]) * (1 if model["tie_word_embeddings"] else 2)
    return head + w["hidden"] + w["layers"] * layer_params(model)


def layer_lora_params(model: Mapping[str, Any], rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, k, the value's
    two halves and o. Nothing in the second half carries one."""
    w = _sizes(model)
    pairs = [(w["hidden"], w["q"]), (w["hidden"], w["kv"]), (w["hidden"], w["kv"] // 2),
             (w["hidden"], w["kv"] // 2), (w["q"], w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step of many rows reads: every layer with
    EVERY expert (192 tokens over 16 experts touch them all), the head over
    the whole vocabulary (the tied embedding, read once as the head; the
    lookup is rows), the final norm, the adapter's factors."""
    w = _sizes(model)
    base = (w["hidden"] * int(model["vocab_size"]) + w["hidden"]
            + w["layers"] * layer_params(model))
    lora = w["layers"] * layer_lora_params(model, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads: every expert of
    every layer, once."""
    w = _sizes(model)
    return w["layers"] * w["experts"] * expert_params(model) * weight_bytes


def kv_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """K and V of one token in ONE layer: a page's cost a token."""
    return 2 * _sizes(model)["kv"] * kv_bytes


def tail_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """One slot's tail in ONE layer (module docstring)."""
    w = _sizes(model)
    return (2 * (w["q"] + w["kv"]) + w["kv"] // 2) * kv_bytes


def slot_state_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """What a slot holds beside its pages: a tail a layer."""
    return _sizes(model)["layers"] * tail_bytes(model, kv_bytes=kv_bytes)


def softmax_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                     kv_bytes: int = 2, group_size: int = 1) -> float:
    """Bytes of K and V the layers' decode must read: a shared prompt's pages
    ONCE a group at each decoded position (for as long as the group's longest
    answer runs), each row's own generated tail a row (module docstring). With
    ``group_size`` 1 every row reads its prompt alone, the dense decoder's
    count. How far the program gets there is ``kernel.softmax_paged_roofline``:
    the paged kernel reads a prompt's pages once a ROW."""
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
    return float(_sizes(model)["layers"] * kv_token_bytes(model, kv_bytes=kv_bytes) * tokens)


def tail_moved_bytes(model: Mapping[str, Any], gen_lens, *, kv_bytes: int = 2) -> float:
    """Bytes of tail the decode steps move: each decoded token reads its
    slot's tail and writes the next, in every layer."""
    return float(2 * slot_state_bytes(model, kv_bytes=kv_bytes) * sum(map(int, gen_lens)))


def delta_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """No layer of this model keeps a delta-rule state. ``readers/delta_moe_work``
    asks a counts module for this name before it reads ``softmax_kv_bytes`` for
    ``kernel.softmax_paged_roofline``: nothing to move."""
    return 0.0


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """What takes the place of a dense decoder's KV read: the pages, a shared
    prompt's once a group, and the tails."""
    return (softmax_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes,
                             group_size=group_size)
            + tail_moved_bytes(model, gen_lens, kv_bytes=kv_bytes))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen matrices forward and backward to activations (4 per
    weight: the mixer's, the grouped convolution's, the router's and the ONE
    expert a token runs), the adapter (6 per weight), attention in the latent
    forward and twice that backward at the mean causal context, the frozen
    head at the scored positions."""
    w = _sizes(model)
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    attend = 2.0 * 2 * w["q"] * (seq_len + 1) / 2.0
    frozen = (mixer_params(model) + conv_params(model) + router_params(model)
              + int(model["experts_per_token"]) * expert_params(model))
    return total + w["layers"] * (
        4.0 * frozen + 6.0 * layer_lora_params(model, lora_rank) + 3.0 * attend)
