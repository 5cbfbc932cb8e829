"""Operations and bytes a latent-attention model behind a learned index over
tokens needs (``glm_moe_dsa``: GLM-5), as ONE CHIP'S SHARE of a layer holds it:
the ``counts`` module of ``configs/glm-5-ep16-L5.json`` (found like its
``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``n_routed_experts`` the experts HELD here, ``router_experts`` the width the
router scores (0: the same), ``index_topk`` the tokens a token attends at most.

**What a decoded token must read of the cache**, a layer: the INDEX KEY of
every token it sees (``index_head_dim`` values: it scores them all) and the
LATENT ROW of the ``min(index_topk, context)`` tokens it chose
(``kv_lora_rank + qk_rope_head_dim`` values each). Not the whole context's
latent rows: a program that walks them all reads 5-10 times these bytes at
10k-21k of context, and ``kv_read_bytes`` must not call that good work. The
index keys of a prompt serve every candidate of its group at once (one product
of all the group's index queries against the block: ``index_key_bytes`` counts
them ONCE a group, as ``latent_moe_counts`` counts a prompt's latent rows); the
chosen rows are each row's own choice and are counted a row
(``indexed_attn_bytes``: a later kernel that reads a group's common choices
once arrives with a ``benchmark`` PR that counts again).

Two counts of the experts, on purpose, as ``window_moe_counts`` has them: a
decode STEP reads every expert HELD once; a TOKEN runs ``experts_per_token``
experts wherever they are held, so this chip's part of its operations is
``experts_per_token x held / width`` experts (``train_flops_per_token``).
"""

from __future__ import annotations

from typing import Any, Mapping

#: tokens one unit of the program's counters ``engine/index_tokens_*`` stands for
COUNT_UNIT = 128


def layer_kinds(model: Mapping[str, Any]) -> list[str]:
    """"dense" | "experts": the second half of each layer that is run."""
    n = int(model["num_layers"])
    dense = min(int(model["first_dense_layers"]), n) if model["n_routed_experts"] else n
    return ["dense"] * dense + ["experts"] * (n - dense)


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    heads = int(model["num_heads"])
    return {
        "hidden": int(model["hidden_size"]),
        "q_rank": int(model["q_lora_rank"]),
        "q": heads * (int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])),
        "latent": int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"]),
        "rank": int(model["kv_lora_rank"]),
        "kvb": heads * (int(model["qk_nope_head_dim"]) + int(model["v_head_dim"])),
        "o": heads * int(model["v_head_dim"]),
        "index_q": int(model["index_heads"]) * int(model["index_head_dim"]),
        "index_key": int(model["index_head_dim"]),
        "index_heads": int(model["index_heads"]),
        "topk": int(model["index_topk"]),
        "dense": int(model["intermediate_size"]),
        "expert": int(model["moe_intermediate_size"]),
        "shared": int(model["n_shared_experts"]) * int(model["moe_intermediate_size"]),
        "held": int(model["n_routed_experts"]),
        "width": int(model["router_experts"]) or int(model["n_routed_experts"]),
    }


def attention_params(model: Mapping[str, Any]) -> int:
    """q_a_proj, q_b_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj of one layer
    (165,019,648 at the published widths)."""
    w = _sizes(model)
    return (w["hidden"] * w["q_rank"] + w["q_rank"] * w["q"] + w["hidden"] * w["latent"]
            + w["rank"] * w["kvb"] + w["o"] * w["hidden"])


def index_params(model: Mapping[str, Any]) -> int:
    """The index's three projections of one layer: its queries from the query
    latent, its one key and its head weights from the stream (9,371,648)."""
    w = _sizes(model)
    return (w["q_rank"] * w["index_q"] + w["hidden"] * w["index_key"]
            + w["hidden"] * w["index_heads"])


def ffn_params(model: Mapping[str, Any], ffn: str, routed: float) -> float:
    """One layer's second half: the dense gated MLP, or ``routed`` routed
    experts counted beside the shared expert and the router at its published
    width."""
    w = _sizes(model)
    if ffn == "dense":
        return 3 * w["hidden"] * w["dense"]
    return 3 * w["hidden"] * (routed * w["expert"] + w["shared"]) + w["hidden"] * w["width"]


def layer_small_params(model: Mapping[str, Any], ffn: str) -> int:
    """The two layer norms, the two latents' norms, the index key's LayerNorm
    (weight and bias) and an expert layer's correction bias."""
    w = _sizes(model)
    return (2 * w["hidden"] + w["q_rank"] + w["rank"] + 2 * w["index_key"]
            + (w["width"] if ffn == "experts" else 0))


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter this program holds, to the unit: the embedding, the
    untied head, the final norm and each layer (3,909,632,768 at the cell's
    cut; a test holds it equal to the program's own tree)."""
    w = _sizes(model)
    total = 2 * w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    for ffn in layer_kinds(model):
        total += int(attention_params(model) + index_params(model)
                     + ffn_params(model, ffn, w["held"]) + layer_small_params(model, ffn))
    return total


def layer_lora_params(model: Mapping[str, Any], ffn: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q_a, q_b, kv_a,
    kv_b, o and the second half's gated MLP (the dense one, or the shared
    expert). The index, the router and the routed experts have none."""
    w = _sizes(model)
    f = w["dense"] if ffn == "dense" else w["shared"]
    pairs = [(w["hidden"], w["q_rank"]), (w["q_rank"], w["q"]), (w["hidden"], w["latent"]),
             (w["rank"], w["kvb"]), (w["o"], w["hidden"]),
             (w["hidden"], f), (w["hidden"], f), (f, w["hidden"])]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer's attention, index,
    norms and second half with EVERY expert held, the untied head over the
    vocabulary slice, the final norm, the adapter's factors (the embedding is
    a lookup)."""
    w = _sizes(model)
    base = w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    lora = 0
    for ffn in layer_kinds(model):
        base += int(attention_params(model) + index_params(model)
                    + ffn_params(model, ffn, w["held"]) + layer_small_params(model, ffn))
        lora += layer_lora_params(model, ffn, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads: every expert
    held, in every EXPERT layer (the dense layer has none)."""
    w = _sizes(model)
    return (layer_kinds(model).count("experts") * w["held"]
            * 3 * w["hidden"] * w["expert"] * weight_bytes)


def page_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """What one cached token costs in ONE layer's pages: its latent row padded
    to whole 128-lane tiles (576 -> 640) and its index key."""
    w = _sizes(model)
    return (-(-w["latent"] // 128) * 128 + w["index_key"]) * kv_bytes


def _groups(prompt_lens, gen_lens, group_size: int):
    prompt_lens, gen_lens = list(prompt_lens), list(gen_lens)
    if group_size < 1 or len(prompt_lens) % group_size or len(prompt_lens) != len(gen_lens):
        raise ValueError(
            f"{len(prompt_lens)} prompts and {len(gen_lens)} answers are no whole "
            f"number of groups of {group_size}")
    for at in range(0, len(prompt_lens), group_size):
        prompts = {int(p) for p in prompt_lens[at:at + group_size]}
        if len(prompts) != 1:
            raise ValueError(f"rows {at}..{at + group_size - 1} share no one prompt: {prompts}")
        yield prompts.pop(), [int(g) for g in gen_lens[at:at + group_size]]


def index_key_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                    kv_bytes: int = 2, group_size: int = 1) -> float:
    """Bytes of index keys a round's decode must read: ``index_head_dim``
    values a visible token a layer. A prompt's keys serve all the candidates of
    its group at once and count ONCE a group at each decoded position, for as
    long as the group's longest answer runs; each row's own generated tail
    (contexts 1 .. g) counts a row. ``group_size`` 1: every row reads its
    prompt's keys alone."""
    w = _sizes(model)
    tokens = sum(max(answers) * prompt + sum(g * (g + 1) // 2 for g in answers)
                 for prompt, answers in _groups(prompt_lens, gen_lens, group_size))
    return float(int(model["num_layers"]) * w["index_key"] * kv_bytes * tokens)


def indexed_attn_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                       kv_bytes: int = 2) -> float:
    """Bytes of latent rows a round's decode must read: the ``min(index_topk,
    context)`` rows a decoded token chose, ``kv_lora_rank + qk_rope_head_dim``
    values each, a layer; each row's own choice, counted a row (module
    docstring)."""
    w = _sizes(model)
    tokens = sum(min(int(p) + j, w["topk"])
                 for p, g in zip(prompt_lens, gen_lens) for j in range(1, int(g) + 1))
    return float(int(model["num_layers"]) * w["latent"] * kv_bytes * tokens)


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """What takes the place of a dense decoder's KV read: the index keys of
    what a token sees and the latent rows of what it chose (module docstring)."""
    return (index_key_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes,
                            group_size=group_size)
            + indexed_attn_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes))


def index_tokens(model: Mapping[str, Any], prompt_lens, gen_lens) -> tuple[int, int]:
    """What the program's counters ``engine/index_tokens_attended`` /
    ``_visible`` must read for these rows: per row, layer and decode step the
    tokens attended and the tokens seen, each rounded up to whole units of
    ``COUNT_UNIT`` tokens."""
    topk, layers = _sizes(model)["topk"], int(model["num_layers"])
    units = lambda n: -(-n // COUNT_UNIT)
    rows = [(int(p), int(g)) for p, g in zip(prompt_lens, gen_lens)]
    return (layers * sum(units(min(p + j, topk)) for p, g in rows for j in range(1, g + 1)),
            layers * sum(units(p + j) for p, g in rows for j in range(1, g + 1)))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight; the index's are forward only, 2: nothing is differentiated through
    the choice), the adapter (6 per weight), expanded attention over the
    ``min(mean context, index_topk)`` tokens attended forward and twice that
    backward, the index's scores over the mean context forward only, the
    frozen head at the scored positions. Experts: this chip's part of the
    ``experts_per_token`` a token runs, and the shared one."""
    w = _sizes(model)
    here = int(model["experts_per_token"]) * w["held"] / float(w["width"])
    mean_ctx = (seq_len + 1) / 2.0
    attend = 2.0 * (w["q"] + w["o"]) * min(mean_ctx, float(w["topk"]))
    score = 2.0 * w["index_q"] * mean_ctx
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    for ffn in layer_kinds(model):
        total += (4.0 * (attention_params(model) + ffn_params(model, ffn, here))
                  + 2.0 * index_params(model) + score
                  + 6.0 * layer_lora_params(model, ffn, lora_rank) + 3.0 * attend)
    return total
