"""Operations and bytes a state-space model needs (``jamba``: AI21-Jamba2-3B):
the ``counts`` module of ``configs/jamba2-3b.json`` (found like its
``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time, and
a count is what the WORK must move, never what a program happens to move.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``mixer_types`` the layers' published kinds ("attention", "mamba").

A slot's cache is, a Mamba layer, a float32 state of ``d_inner x d_state``
(327,680 B at the published widths) and a window of the last ``d_conv - 1``
tokens' ``u`` at the cache's type, and, an attention layer, K and V of ONE head
a token (1,024 B a token over the two layers). A decoded token reads and
writes each state once whatever the context.
"""

from __future__ import annotations

from typing import Any, Mapping

#: a state-space state is float32 whatever the served type
STATE_BYTES = 4
#: tokens of one prefill segment, the unit a carried state is read and written at
SEGMENT = 1024
_KINDS = {"attention": "softmax", "mamba": "mamba"}


def layer_kinds(model: Mapping[str, Any]) -> list[str]:
    return [_KINDS[m] for m in list(model["mixer_types"])[: int(model["num_layers"])]]


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    hidden, hd = int(model["hidden_size"]), int(model["head_dim"])
    return {
        "hidden": hidden, "mlp": int(model["intermediate_size"]),
        "q": int(model["num_heads"]) * hd, "kv": int(model["num_kv_heads"]) * hd,
        "inner": int(model["mamba_expand"]) * hidden,
        "cols": int(model["mamba_d_state"]), "rank": int(model["mamba_dt_rank"]),
        "taps": int(model["mamba_d_conv"]),
    }


def mixer_params(model: Mapping[str, Any], kind: str) -> int:
    """One layer's mixer matrices. attention: q and o, k and v. mamba: W_in,
    W_x, W_dt, W_out."""
    w = _sizes(model)
    if kind == "softmax":
        return 2 * w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"]
    return (w["hidden"] * 2 * w["inner"] + w["inner"] * (w["rank"] + 2 * w["cols"])
            + w["rank"] * w["inner"] + w["inner"] * w["hidden"])


def mlp_params(model: Mapping[str, Any]) -> int:
    w = _sizes(model)
    return 3 * w["hidden"] * w["mlp"]


def layer_small_params(model: Mapping[str, Any], kind: str) -> int:
    """Two norms and, in a Mamba layer, the convolution's taps and bias, W_dt's
    bias, ``A_log``, ``D`` and the three inner norms."""
    w = _sizes(model)
    small = 2 * w["hidden"]
    if kind == "mamba":
        small += (w["taps"] * w["inner"] + w["inner"] + w["inner"]
                  + w["inner"] * w["cols"] + w["inner"] + w["rank"] + 2 * w["cols"])
    return small


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter of the model: the layers, the tied table once, the final norm."""
    w = _sizes(model)
    layers = sum(mixer_params(model, k) + mlp_params(model) + layer_small_params(model, k)
                 for k in layer_kinds(model))
    table = w["hidden"] * int(model["vocab_size"])
    return layers + table * (1 if model["tie_word_embeddings"] else 2) + w["hidden"]


def layer_lora_params(model: Mapping[str, Any], kind: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over q, k, v, o in an
    attention layer, W_in and W_out in a Mamba layer, and the MLP's three in
    both. Everything else is frozen and has none."""
    w = _sizes(model)
    mlp = [(w["hidden"], w["mlp"]), (w["hidden"], w["mlp"]), (w["mlp"], w["hidden"])]
    if kind == "softmax":
        own = [(w["hidden"], w["q"]), (w["hidden"], w["kv"]), (w["hidden"], w["kv"]),
               (w["q"], w["hidden"])]
    else:
        own = [(w["hidden"], 2 * w["inner"]), (w["inner"], w["hidden"])]
    return sum(rank * (i + o) for i, o in own + mlp)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer, the head (the tied
    table, read once as the head; the step's embedding rows are a rounding
    beside it), the final norm, the adapter's factors."""
    w = _sizes(model)
    base = w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    lora = 0
    for kind in layer_kinds(model):
        base += mixer_params(model, kind) + mlp_params(model) + layer_small_params(model, kind)
        lora += layer_lora_params(model, kind, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def state_bytes(model: Mapping[str, Any]) -> int:
    """Bytes of ONE Mamba layer's state a slot: ``d_inner x d_state`` float32."""
    w = _sizes(model)
    return w["inner"] * w["cols"] * STATE_BYTES


def window_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """Bytes of ONE Mamba layer's convolution window a slot."""
    w = _sizes(model)
    return (w["taps"] - 1) * w["inner"] * kv_bytes


def kv_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """Bytes of K and V ONE token holds over the attention layers."""
    return layer_kinds(model).count("softmax") * 2 * _sizes(model)["kv"] * kv_bytes


def slot_state_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """Bytes ONE slot holds beside its pages: a state and a window a Mamba layer."""
    return layer_kinds(model).count("mamba") * (
        state_bytes(model) + window_bytes(model, kv_bytes=kv_bytes))


def attention_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                       kv_bytes: int = 2) -> float:
    """Bytes of K and V the attention layers' decode must read: every decoded
    token attends over its prompt and the tokens before it, once a row."""
    tokens = sum(int(g) * int(p) + int(g) * (int(g) + 1) // 2
                 for p, g in zip(prompt_lens, gen_lens))
    return float(kv_token_bytes(model, kv_bytes=kv_bytes) * tokens)


def ssm_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                    kv_bytes: int = 2) -> float:
    """Bytes the Mamba layers' decode must move in state: each layer's state
    read once and written once, float32, for every decoded token (``kv_bytes``
    is the pages' and is not read: a state is float32)."""
    steps = sum(int(g) for g in gen_lens)
    return float(steps * layer_kinds(model).count("mamba") * 2 * state_bytes(model))


def window_moved_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                       kv_bytes: int = 2) -> float:
    """Bytes the Mamba layers' decode must move in windows: the three tokens
    before it read and its own ``u`` written, a layer a decoded token."""
    w = _sizes(model)
    steps = sum(int(g) for g in gen_lens)
    return float(steps * layer_kinds(model).count("mamba") * w["taps"] * w["inner"] * kv_bytes)


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2) -> float:
    """What takes the place of a dense decoder's KV read: the attention
    layers' K/V, the Mamba layers' states read and written, their windows."""
    return (attention_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes)
            + ssm_state_bytes(model, prompt_lens, gen_lens)
            + window_moved_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes))


def ssm_scan_bytes(model: Mapping[str, Any], prompt_lens, *, act_bytes: int = 2,
                   segment: int = SEGMENT) -> float:
    """Bytes the scan over the prompts ``prompt_lens`` (one entry a PROMPT,
    real tokens) must move, a Mamba layer: a token's ``c`` and ``z`` read and
    ``y`` written at the program's activation type, its ``dt`` (float32), ``B``
    and ``C`` read, and the carried state read and written once a segment of
    ``segment`` tokens. The scan multiplies nothing on the matrix unit."""
    w = _sizes(model)
    token = w["inner"] * (3 * act_bytes + 4) + 2 * w["cols"] * act_bytes
    total = sum(int(p) * token + -(-int(p) // segment) * 2 * state_bytes(model)
                for p in prompt_lens)
    return float(layer_kinds(model).count("mamba") * total)


def ssm_flops_per_token(model: Mapping[str, Any]) -> float:
    """Vector operations of the recurrence for ONE token of one layer: a state
    entry's decay (the product and the exp), ``dt c B``, the multiply-add and
    the reduction against ``C``: 7 an entry."""
    w = _sizes(model)
    return 7.0 * w["inner"] * w["cols"]


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored, counted as ``roofline.py``
    counts them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), the mixer forward and twice that
    backward, the frozen head at the scored positions."""
    w = _sizes(model)
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    for kind in layer_kinds(model):
        mixer = (2.0 * 2 * w["q"] * (seq_len + 1) / 2.0 if kind == "softmax"
                 else ssm_flops_per_token(model))
        total += (4.0 * (mixer_params(model, kind) + mlp_params(model))
                  + 6.0 * layer_lora_params(model, kind, lora_rank) + 3.0 * mixer)
    return total
