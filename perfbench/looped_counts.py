"""Operations and bytes a looped dense decoder needs (``ouro``: Ouro-2.6B):
the ``counts`` module of ``configs/ouro-2.6b-L8.json`` (found like its
``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder it imports the layer's widths from: nothing here reads what
the program chose at run time. ``model`` is ``dataclasses.asdict`` of the
program's ``ModelConfig``: ``num_layers`` WEIGHT layers, ``loop_steps`` passes
over them a token, ``num_layers x loop_steps`` CACHE layers (a pass attends
the keys and values that pass wrote).

**A layer's weights count once a PASS.** A decode step runs the same layer
``loop_steps`` times, with every other layer of the pass between two uses, and
no chip keeps a layer on chip from one pass to the next (51,388,416 parameters,
103 MB in bf16 at the published widths, against some tens of MB of fast
memory): the algorithm as it can run on this hardware reads them again. So a
step's weight bytes are ``loop_steps`` times the layers' and, ONCE, what
stands round the loop: the token's embedding row, the final norm (one vector,
applied ``loop_steps`` times from wherever it lies), the exit gate and the
head. The adapter's factors are a layer's and count with it.

**A token's K and V once a cache layer; a shared prompt's pages once a
GROUP.** A cached token is ``2 x num_kv_heads x head_dim`` values in each of
the ``num_layers x loop_steps`` cache layers (262,144 bytes in bf16 at 8
layers, 4 passes, 16 heads of 128). K and V of a prompt are the same bytes for
every candidate of its group, so what a decoded position must move is the
prompt's pages once for the group and each row's own generated tail a row
(``kv_read_bytes`` takes ``group_size``, which the harness tells a function
whose signature has it, as ``cca_moe_counts`` and ``swa_sink_moe_counts``
count). The paged launch that stands reads a prompt's pages once a ROW, so
the shares these counts bound read low by up to the group's size: that is what
the launch leaves, not a fault.
"""

from __future__ import annotations

from typing import Any, Mapping

from perfbench.roofline import _widths, layer_lora_params, layer_matmul_params

#: RMSNorm vectors a layer holds: before and after each of its two sublayers
LAYER_NORMS = 4


def passes(model: Mapping[str, Any]) -> int:
    return int(model.get("loop_steps") or 1)


def cache_layers(model: Mapping[str, Any]) -> int:
    """Layers that keep K and V: one a (pass, layer)."""
    return int(model["num_layers"]) * passes(model)


def layer_params(model: Mapping[str, Any]) -> int:
    """Everything one weight layer holds: its seven projections and its four
    norm vectors (no bias anywhere)."""
    return layer_matmul_params(model) + LAYER_NORMS * _widths(model)["hidden"]


def around_params(model: Mapping[str, Any]) -> int:
    """What a decode step reads ONCE round the loop: the token's embedding
    row, the final norm, the exit gate (a vector and a bias) and the head
    (untied: the embedding table itself is only gathered from)."""
    w = _widths(model)
    return w["hidden"] + w["hidden"] + (w["hidden"] + 1) + w["hidden"] * w["vocab"]


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter the configuration holds: the layers once, the embedding
    and the head, the final norm and the gate."""
    w = _widths(model)
    return (int(model["num_layers"]) * layer_params(model)
            + 2 * w["hidden"] * w["vocab"] + w["hidden"] + w["hidden"] + 1)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step must read whatever the batch: every
    layer's once a PASS with its adapter's factors, and what stands round the
    loop once (module docstring)."""
    layers = int(model["num_layers"]) * passes(model)
    lora = layers * layer_lora_params(model, lora_rank) if lora_rank else 0
    return ((layers * layer_params(model) + around_params(model)) * weight_bytes
            + lora * lora_bytes)


def kv_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """K and V of one token in ONE cache layer."""
    return 2 * _widths(model)["kv"] * kv_bytes


def cache_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """What one more token of context costs a slot: K and V in every cache
    layer (the program's gauge ``engine/cache_token_bytes``)."""
    return cache_layers(model) * kv_token_bytes(model, kv_bytes=kv_bytes)


def attended_tokens(prompt_lens, gen_lens, group_size: int = 1) -> int:
    """Keys' worth of pages the decoded tokens of these rows must be read for
    in one cache layer: a shared prompt's ONCE a group at each decoded
    position (for as long as the group's longest answer runs), each row's own
    generated tail a row. With ``group_size`` 1 every row reads its prompt
    alone, which is ``roofline.kv_read_bytes``'s count."""
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


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """K/V bytes paged attention must read to decode rows of ``gen_lens``
    tokens after prompts of ``prompt_lens``, in every cache layer, a shared
    prompt's pages once a group (module docstring). Exact token granularity."""
    return float(cache_token_bytes(model, kv_bytes=kv_bytes)
                 * attended_tokens(prompt_lens, gen_lens, group_size))


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose
    last ``answer_len`` positions are scored: ``roofline.train_flops_per_token``'s
    count of a layer (forward and backward-to-activations through the frozen
    projections, the adapter's forward, backward and two weight gradients,
    causal attention at the mean key length) once a PASS, the frozen head over
    the scored positions once. The norms and the gate are not counted, as the
    dense count leaves the norms out. Recomputation is not counted."""
    w = _widths(model)
    attn_forward = 4.0 * w["q"] * (seq_len / 2.0)
    per_layer = (
        4.0 * layer_matmul_params(model)
        + 6.0 * layer_lora_params(model, lora_rank)
        + 3.0 * attn_forward
    )
    head = 4.0 * w["hidden"] * w["vocab"] * (answer_len / float(seq_len))
    return cache_layers(model) * per_layer + head
