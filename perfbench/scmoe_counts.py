"""Operations and bytes a shortcut-connected expert model needs
(``longcat_flash``: LongCat-Flash-Chat): the ``counts`` module of
``configs/longcat-flash-ep32-L4.json`` (found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``num_layers`` the PUBLISHED layers, each of which holds TWO latent-attention
sublayers (a query latent, a KV latent, their two norms), TWO dense gated MLPs
of ``intermediate_size`` and, between them, one router of ``router_width``
outputs (``router_experts`` routed experts, or ``n_routed_experts`` where the
file states no share, and ``zero_experts`` that compute nothing) over the
``n_routed_experts`` routed experts HELD here, ``experts_per_token`` choices a
token. No shared expert.

What is counted, and how.

* A cached token is one latent row a SUBLAYER: ``kv_lora_rank +
  qk_rope_head_dim`` values, ``2 x num_layers`` rows a token
  (``kv_read_bytes``; a prompt's rows once a group of candidates, as
  ``latent_moe_counts`` counts them and for its reason).
* A TOKEN's operations count the routed experts it RUNS wherever they are
  held: of its ``experts_per_token`` choices the share that falls on a routed
  expert under an even router, ``routed / router_width`` (8 of 12 at the
  published widths); a choice that computes nothing multiplies nothing
  (``expert_choices_run``, ``train_flops_per_token``).
* A decode STEP's bytes count the experts HELD that the step must read: an
  expert that none of the step's pairs chose is not read. Under an even router
  a step of ``R`` rows leaves an expert without a pair with probability
  ``(1 - 1 / router_width) ^ (R k)``: 1.8% at the one cell's 256 rows x 12
  choices over 768 outputs (4 pairs an expert a step), so 98.2% of the held
  experts' bytes (``held_experts_read``, ``STEP_ROWS``).
"""

from __future__ import annotations

from typing import Any, Mapping

from perfbench import latent_moe_counts

#: rows of a decode step in the cell that names this module (256 rows in 256
#: slots: ``rollout-reasoning-zero-256``); the readers pass no row count
STEP_ROWS = 256
SUBLAYERS = 2


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    heads = int(model["num_heads"])
    routed = int(model["router_experts"]) or int(model["n_routed_experts"])
    return {
        "hidden": int(model["hidden_size"]),
        "q_rank": int(model["q_lora_rank"]),
        "q": heads * (int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])),
        "latent": int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"]),
        "rank": int(model["kv_lora_rank"]),
        "kvb": heads * (int(model["qk_nope_head_dim"]) + int(model["v_head_dim"])),
        "o": heads * int(model["v_head_dim"]),
        "dense": int(model["intermediate_size"]),
        "expert": int(model["moe_intermediate_size"]),
        "held": int(model["n_routed_experts"]),
        "routed": routed,
        "router": routed + int(model["zero_experts"]),
        "layers": int(model["num_layers"]),
        "topk": int(model["experts_per_token"]),
    }


def attention_params(model: Mapping[str, Any]) -> int:
    """q_a_proj, q_b_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj of ONE sublayer."""
    w = _sizes(model)
    return (w["hidden"] * w["q_rank"] + w["q_rank"] * w["q"] + w["hidden"] * w["latent"]
            + w["rank"] * w["kvb"] + w["o"] * w["hidden"])


def mlp_params(model: Mapping[str, Any]) -> int:
    """ONE dense gated MLP: gate, up, down."""
    w = _sizes(model)
    return 3 * w["hidden"] * w["dense"]


def expert_params(model: Mapping[str, Any]) -> int:
    """ONE routed expert: gate, up, down."""
    w = _sizes(model)
    return 3 * w["hidden"] * w["expert"]


def router_params(model: Mapping[str, Any]) -> int:
    w = _sizes(model)
    return w["hidden"] * w["router"]


def layer_small_params(model: Mapping[str, Any]) -> int:
    """Norms and the router's bias of ONE published layer: two input norms,
    two post-attention norms, two query-latent and two KV-latent norms."""
    w = _sizes(model)
    return SUBLAYERS * (2 * w["hidden"] + w["q_rank"] + w["rank"]) + w["router"]


def layer_params(model: Mapping[str, Any], routed: int) -> int:
    """ONE published layer with ``routed`` routed experts counted."""
    return (SUBLAYERS * (attention_params(model) + mlp_params(model))
            + router_params(model) + routed * expert_params(model)
            + layer_small_params(model))


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter the program holds: the layers with the experts HELD,
    the embedding, the untied head, the final norm."""
    w = _sizes(model)
    vocab = int(model["vocab_size"])
    ends = w["hidden"] * vocab * (1 if model["tie_word_embeddings"] else 2) + w["hidden"]
    return w["layers"] * layer_params(model, w["held"]) + ends


def layer_lora_params(model: Mapping[str, Any], rank: int) -> int:
    """Adapter weights of ONE published layer: rank x (in + out) over q_a, q_b,
    kv_a, kv_b, o and the dense MLP's three, in both sublayers. The router and
    the routed experts are frozen and have none."""
    w = _sizes(model)
    pairs = [(w["hidden"], w["q_rank"]), (w["q_rank"], w["q"]), (w["hidden"], w["latent"]),
             (w["rank"], w["kvb"]), (w["o"], w["hidden"])] + [
                 (w["hidden"], w["dense"])] * 2 + [(w["dense"], w["hidden"])]
    return SUBLAYERS * sum(rank * (i + o) for i, o in pairs)


def expert_choices_run(model: Mapping[str, Any]) -> float:
    """Of a token's ``experts_per_token`` choices, those that run a routed
    expert under an even router (module docstring): 8 of 12 as published."""
    w = _sizes(model)
    return w["topk"] * w["routed"] / w["router"]


def held_experts_read(model: Mapping[str, Any], rows: int = STEP_ROWS) -> float:
    """Routed experts held here that a decode step of ``rows`` rows reads, a
    published layer, under an even router: those some pair chose (module
    docstring)."""
    w = _sizes(model)
    return w["held"] * (1.0 - (1.0 - 1.0 / w["router"]) ** (rows * w["topk"]))


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> float:
    """Bytes of routed experts' weights one decode step of ``STEP_ROWS`` rows
    must read, over the published layers: the held experts that have a pair,
    an expert with no pair none (``held_experts_read``)."""
    return (_sizes(model)["layers"] * held_experts_read(model)
            * expert_params(model) * weight_bytes)


def expert_flops_per_token(model: Mapping[str, Any]) -> float:
    """Operations of the routed experts' products for ONE token, over the
    published layers: the routed experts it runs wherever they are held, 2 a
    weight; a zero-compute choice none."""
    return float(_sizes(model)["layers"] * expert_choices_run(model)
                 * 2 * expert_params(model))


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> float:
    """Bytes of weights one decode step reads: both sublayers' projections and
    dense MLPs, the router, the norms and the held experts that have a pair
    (``expert_bytes_per_step``), the untied head, the final norm, the
    adapter's factors (an untied embedding is only gathered from)."""
    w = _sizes(model)
    base = w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    base += w["layers"] * layer_params(model, 0)
    lora = w["layers"] * layer_lora_params(model, lora_rank) if lora_rank else 0
    return (base * weight_bytes + expert_bytes_per_step(model, weight_bytes=weight_bytes)
            + lora * lora_bytes)


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """Bytes of cache a round's decode must read: ``kv_lora_rank +
    qk_rope_head_dim`` values a cached token a SUBLAYER (``2 x num_layers``
    latent rows a token), no kv-head factor. ``group_size`` consecutive rows
    share a prompt, whose rows absorbed attention reads ONCE a group at each
    decoded position for as long as the group's longest answer runs; each
    row's own generated tail counts a row: ``latent_moe_counts.kv_read_bytes``
    (which says why, and counts one row a token a LAYER), twice."""
    return SUBLAYERS * latent_moe_counts.kv_read_bytes(
        model, prompt_lens, gen_lens, kv_bytes=kv_bytes, group_size=group_size)


latent_attn_bytes = kv_read_bytes
#: operations absorbed attention spends on one cached token of one SUBLAYER
latent_attn_flops_per_cached_token = latent_moe_counts.latent_attn_flops_per_cached_token


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), expanded attention forward and twice
    that backward in BOTH sublayers, the frozen head at the scored positions.
    Experts: the routed ones a token RUNS (``expert_choices_run``), not all
    held and not the choices that compute nothing."""
    w = _sizes(model)
    mean_context = (seq_len + 1) / 2.0
    # scores over nope + rope and values over v, a head, a key, a sublayer
    mixer = 2.0 * (w["q"] + w["o"]) * mean_context
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    frozen = (SUBLAYERS * (attention_params(model) + mlp_params(model)) + router_params(model)
              + expert_choices_run(model) * expert_params(model))
    return total + w["layers"] * (
        4.0 * frozen + 6.0 * layer_lora_params(model, lora_rank) + SUBLAYERS * 3.0 * mixer)
