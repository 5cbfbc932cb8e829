"""Operations and bytes a latent-attention model with routed experts needs
(``deepseek_v3``: Kimi-VL-A3B's language model): the ``counts`` module of
``configs/kimi-vl-a3b-L7.json`` (found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``.

Two different counts of the experts, on purpose. A TOKEN runs
``experts_per_token`` routed experts and the shared one: that is what
``train_flops_per_token`` counts. A decode STEP of many rows reads every
expert that any of its rows chose: that is what ``decode_weight_bytes``
counts, and at the cell's 64 rows x 6 choices over 64 experts it is all of
them (see there).
"""

from __future__ import annotations

from typing import Any, Mapping


def layer_kinds(model: Mapping[str, Any]) -> list[str]:
    n = int(model["num_layers"])
    dense = min(int(model["first_dense_layers"]), n) if model["n_routed_experts"] else n
    return ["latent"] * dense + ["latent_moe"] * (n - dense)


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    heads = int(model["num_heads"])
    return {
        "hidden": int(model["hidden_size"]),
        "q": heads * (int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])),
        "latent": int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"]),
        "rank": int(model["kv_lora_rank"]),
        "kvb": heads * (int(model["qk_nope_head_dim"]) + int(model["v_head_dim"])),
        "o": heads * int(model["v_head_dim"]),
        "dense": int(model["intermediate_size"]),
        "expert": int(model["moe_intermediate_size"]),
        "shared": int(model["n_shared_experts"]) * int(model["moe_intermediate_size"]),
        "experts": int(model["n_routed_experts"]),
    }


def attention_params(model: Mapping[str, Any]) -> int:
    """q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj of one layer."""
    w = _sizes(model)
    return (w["hidden"] * w["q"] + w["hidden"] * w["latent"]
            + w["rank"] * w["kvb"] + w["o"] * w["hidden"])


def ffn_params(model: Mapping[str, Any], kind: str, routed: int) -> int:
    """One layer's gated MLPs with ``routed`` routed experts counted, and the
    router."""
    w = _sizes(model)
    if kind == "latent":
        return 3 * w["hidden"] * w["dense"]
    return (3 * w["hidden"] * (routed * w["expert"] + w["shared"])
            + w["hidden"] * w["experts"])


def layer_norm_params(model: Mapping[str, Any], kind: str) -> int:
    w = _sizes(model)
    return 2 * w["hidden"] + w["rank"] + (w["experts"] if kind == "latent_moe" else 0)


def layer_lora_params(model: Mapping[str, Any], kind: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, kv_a, kv_b, o
    and the dense MLP's (or the shared expert's) three. The router and the
    routed experts are frozen and have none."""
    w = _sizes(model)
    ffn = w["dense"] if kind == "latent" else w["shared"]
    pairs = [(w["hidden"], w["q"]), (w["hidden"], w["latent"]), (w["rank"], w["kvb"]),
             (w["o"], w["hidden"])] + [(w["hidden"], ffn)] * 2 + [(ffn, w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer's projections,
    norms and EVERY expert held, the untied head, the final norm, the
    adapter's factors (an untied embedding is only gathered from).

    Every expert, because a step of R rows makes R x k choices: the chance
    that one of E experts is chosen by none is (1 - k/E)^R under even routing,
    0.906^64 = 0.18% at this cell's 64 rows, 6 of 64; 99.8% of the experts are
    read each step, and a skewed router changes which, not how many by much.
    A step of few rows (R x k << E) reads fewer, and this count is then too
    high: it is for cells that fill their slots."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    base = hidden * vocab + hidden
    lora = 0
    for kind in layer_kinds(model):
        base += (attention_params(model)
                 + ffn_params(model, kind, int(model["n_routed_experts"]))
                 + layer_norm_params(model, kind))
        lora += layer_lora_params(model, kind, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads, over the expert
    layers: every expert held (``decode_weight_bytes`` says why)."""
    w = _sizes(model)
    return (layer_kinds(model).count("latent_moe") * w["experts"]
            * 3 * w["hidden"] * w["expert"] * weight_bytes)


def expert_flops_per_token(model: Mapping[str, Any]) -> float:
    """Operations of the grouped products for ONE token, over the expert
    layers: the experts it runs, 2 a weight."""
    w = _sizes(model)
    return float(layer_kinds(model).count("latent_moe") * int(model["experts_per_token"])
                 * 2 * 3 * w["hidden"] * w["expert"])


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """Bytes of cache a round's decode must read: ``kv_lora_rank +
    qk_rope_head_dim`` values a cached token a layer. K and V are ONE read (the
    values are the row's first ``kv_lora_rank``), and there is no kv-head
    factor: every head reads the same row.

    ``group_size`` is the number of consecutive rows that share a prompt (a
    GRPO group's candidates). Absorbed attention scores a query against the
    latent row itself, so the prompt's rows serve every candidate of the group
    at once: at each decoded position they count ONCE a group, for as long as
    the group's longest answer runs, and each row's own generated tail
    (contexts 1 .. g) counts a row as before. With ``group_size`` 1 every row
    is its own group and reads its prompt alone: the count before PR 35, to
    the digit. How far the program gets there is its own report: the counters
    ``engine/latent_pages_attended`` over ``engine/latent_pages_read`` (8.91 in
    the cell, 16 with whole prompts shared, 1 where nothing is).

    The dense and block-sparse decoders' counts (``roofline.py``,
    ``sala_counts.py``) take no ``group_size``: their K/V is per head and their
    programs read a shared prompt once a candidate."""
    w = _sizes(model)
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
    return float(int(model["num_layers"]) * w["latent"] * kv_bytes * tokens)


latent_attn_bytes = kv_read_bytes


def latent_attn_flops_per_cached_token(model: Mapping[str, Any]) -> float:
    """Operations absorbed attention spends on one cached token of one layer:
    every head's score over the whole row and its value over the latent."""
    w = _sizes(model)
    return float(int(model["num_heads"]) * 2 * (w["latent"] + w["rank"]))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), expanded attention forward and twice
    that backward, the frozen head at the scored positions. Experts: the
    ``experts_per_token`` a token RUNS and the shared one, not all held."""
    hidden, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    w = _sizes(model)
    mean_context = (seq_len + 1) / 2.0
    # scores over nope + rope and values over v, a head, a key
    mixer = 2.0 * (w["q"] + w["o"]) * mean_context
    total = 4.0 * hidden * vocab * (answer_len / float(seq_len))
    for kind in layer_kinds(model):
        total += (
            4.0 * (attention_params(model)
                   + ffn_params(model, kind, int(model["experts_per_token"])))
            + 6.0 * layer_lora_params(model, kind, lora_rank) + 3.0 * mixer
        )
    return total
