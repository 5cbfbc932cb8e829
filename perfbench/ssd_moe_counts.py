"""Operations and bytes a state-space expert model of one sublayer a layer needs
(``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B), as ONE CHIP'S SHARE of a
layer holds it: the ``counts`` module of ``configs/nemotron-3-nano-ep2-L13.json``
(found like its ``reference``).

The yardstick's own arithmetic from the shapes, as ``roofline.py`` is for the
dense GQA decoder: nothing here reads what the program chose at run time, and
a count is what the WORK must move, never what a program happens to move.
``model`` is ``dataclasses.asdict`` of the program's ``ModelConfig``:
``mixer_types`` the layers by name ("mamba-2", "attention-only", "moe": the
pattern's M, *, E; the first ``num_layers`` are run), ``ssd_heads`` x
``ssd_head_dim`` a Mamba-2 layer's channels, ``ssd_groups`` x ``mamba_d_state``
its B and its C, ``n_routed_experts`` the experts HELD, ``router_experts`` the
width the router scores (0: the same), ``shared_expert_width`` the shared
expert's own.

Three caches, counted apart. A Mamba-2 layer keeps a float32 state of ``heads
x head_dim x d_state`` a slot (2 MiB at the published sizes) and a tail of the
last ``d_conv - 1`` tokens' ``[x | B | C]`` at the cache's type; a decoded token
reads and writes each state ONCE whatever the context. An attention layer
keeps K and V of every token in pages (1,024 B a token a layer) and a decoded
token reads all of it, a shared prompt's pages ONCE a group of candidates
(``group_size``: one read can serve the group, as ``cca_moe_counts`` counts
it). An expert layer keeps nothing.

Two counts of the experts, on purpose, as ``delta_moe_counts`` has them: a
decode STEP reads every expert HELD once (two matrices each: the experts are
ungated); a TOKEN runs ``experts_per_token`` experts wherever they are held, so
this chip's part of its operations is ``experts_per_token x held / width``.

**The chunked form** (``ops/ssd.py``; chunks of ``ssd_chunk`` tokens): a
chunk of C tokens of one layer multiplies ``C B^T`` once a GROUP (2 C C N G),
the decayed scores by the values (2 C C P H), the carried state by ``C`` (2 C N
P H) and the values by ``B`` into the new state (2 C N P H), and moves a
token's x, B, C and y at the activations' type, its dt in float32 and the
carried state once in and once out a SEGMENT. ``ssd_chunk_flops`` counts an
operation ONCE whatever precision the program multiplies in (a float32 product
at full precision is six passes of the matrix unit: the program's cost, not
the algorithm's), so against the bf16 peak the form reads a sixth at best; at
this cell's shapes the BYTES are the larger time (30 ns a token a layer at 819
GB/s against 17 ns at 197 TFLOP/s), so ``kernel.ssd_chunk_roofline`` divides
by them.
"""

from __future__ import annotations

from typing import Any, Mapping

#: a state-space state is float32 whatever the served type
STATE_BYTES = 4
#: tokens of one prefill segment, the unit a carried state is read and written at
SEGMENT = 1024
_KINDS = {"mamba-2": "mamba2", "attention-only": "softmax", "moe": "experts"}


def layer_kinds(model: Mapping[str, Any]) -> list[str]:
    """"mamba2" | "softmax" | "experts" of each layer that is run."""
    return [_KINDS[m] for m in list(model["mixer_types"])[: int(model["num_layers"])]]


def _sizes(model: Mapping[str, Any]) -> dict[str, int]:
    hidden, hd = int(model["hidden_size"]), int(model["head_dim"])
    heads, p = int(model["ssd_heads"]), int(model["ssd_head_dim"])
    groups, cols = int(model["ssd_groups"]), int(model["mamba_d_state"])
    inner = heads * p
    return {
        "hidden": hidden, "q": int(model["num_heads"]) * hd,
        "kv": int(model["num_kv_heads"]) * hd,
        "heads": heads, "p": p, "groups": groups, "cols": cols, "inner": inner,
        "mixed": inner + 2 * groups * cols,  # what the convolution mixes: x, B, C
        "in": 2 * inner + 2 * groups * cols + heads,  # z, x B C, dt
        "taps": int(model["mamba_d_conv"]), "chunk": int(model["ssd_chunk"]),
        "expert": int(model["moe_intermediate_size"]),
        "shared": int(model["shared_expert_width"]) or (
            int(model["n_shared_experts"]) * int(model["moe_intermediate_size"])),
        "held": int(model["n_routed_experts"]),
        "width": int(model["router_experts"]) or int(model["n_routed_experts"]),
    }


def layer_params(model: Mapping[str, Any], kind: str, routed: float) -> float:
    """One layer's matrices: W_in and W_out; q, o, k, v; or ``routed`` ungated
    experts counted beside the shared one and the router at its published width."""
    w = _sizes(model)
    if kind == "mamba2":
        return w["hidden"] * w["in"] + w["inner"] * w["hidden"]
    if kind == "softmax":
        return 2 * w["hidden"] * w["q"] + 2 * w["hidden"] * w["kv"]
    return 2 * w["hidden"] * (routed * w["expert"] + w["shared"]) + w["hidden"] * w["width"]


def layer_small_params(model: Mapping[str, Any], kind: str) -> int:
    """The layer's ONE norm and, in a Mamba-2 layer, the convolution's taps and
    bias, A_log, dt_bias and D a head and the gate's norm; in an expert layer
    the router's correction bias."""
    w = _sizes(model)
    small = w["hidden"]
    if kind == "mamba2":
        small += (w["taps"] + 1) * w["mixed"] + 3 * w["heads"] + w["inner"]
    if kind == "experts":
        small += w["width"]
    return small


def param_count(model: Mapping[str, Any]) -> int:
    """Every parameter this program holds, to the unit: the embedding, the
    untied head, the final norm and each layer with the experts HELD (a test
    holds it equal to the program's own tree)."""
    w = _sizes(model)
    total = 2 * w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    for kind in layer_kinds(model):
        total += int(layer_params(model, kind, w["held"])) + layer_small_params(model, kind)
    return total


def layer_lora_params(model: Mapping[str, Any], kind: str, rank: int) -> int:
    """Adapter weights of one layer: rank x (in + out) over W_in and W_out; q,
    k, v, o; or the shared expert's up and down."""
    w = _sizes(model)
    pairs = {
        "mamba2": [(w["hidden"], w["in"]), (w["inner"], w["hidden"])],
        "softmax": [(w["hidden"], w["q"]), (w["hidden"], w["kv"]), (w["hidden"], w["kv"]),
                    (w["q"], w["hidden"])],
        "experts": [(w["hidden"], w["shared"]), (w["shared"], w["hidden"])],
    }[kind]
    return sum(rank * (i + o) for i, o in pairs)


def decode_weight_bytes(model: Mapping[str, Any], *, weight_bytes: int = 2,
                        lora_rank: int = 0, lora_bytes: int = 4) -> int:
    """Bytes of weights one decode step reads: every layer with EVERY expert
    held, the untied head over the vocabulary slice, the final norm, the
    adapter's factors (the embedding is a lookup)."""
    w = _sizes(model)
    base = w["hidden"] * int(model["vocab_size"]) + w["hidden"]
    lora = 0
    for kind in layer_kinds(model):
        base += int(layer_params(model, kind, w["held"])) + layer_small_params(model, kind)
        lora += layer_lora_params(model, kind, lora_rank) if lora_rank else 0
    return base * weight_bytes + lora * lora_bytes


def expert_bytes_per_step(model: Mapping[str, Any], *, weight_bytes: int = 2) -> int:
    """Bytes of routed experts' weights one decode step reads: every expert
    held, TWO matrices each, in every expert layer."""
    w = _sizes(model)
    return (layer_kinds(model).count("experts") * w["held"] * 2 * w["hidden"] * w["expert"]
            * weight_bytes)


def state_bytes(model: Mapping[str, Any]) -> int:
    """Bytes of ONE Mamba-2 layer's state a slot: heads x head_dim x d_state float32."""
    w = _sizes(model)
    return w["inner"] * w["cols"] * STATE_BYTES


def tail_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """Bytes of ONE Mamba-2 layer's convolution tail a slot."""
    w = _sizes(model)
    return (w["taps"] - 1) * w["mixed"] * kv_bytes


def kv_token_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """Bytes of K and V ONE token holds over the attention layers: what one more
    token of context costs a slot."""
    return layer_kinds(model).count("softmax") * 2 * _sizes(model)["kv"] * kv_bytes


def slot_state_bytes(model: Mapping[str, Any], *, kv_bytes: int = 2) -> int:
    """Bytes ONE slot holds beside its pages: a state and a tail a Mamba-2 layer."""
    return layer_kinds(model).count("mamba2") * (
        state_bytes(model) + tail_bytes(model, kv_bytes=kv_bytes))


def softmax_kv_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                     kv_bytes: int = 2, group_size: int = 1) -> float:
    """Bytes of K and V the attention layers' decode must read: a shared
    prompt's pages ONCE a group at each decoded position (for as long as the
    group's longest answer runs), each row's own generated tail a row. With
    ``group_size`` 1 every row reads its prompt alone. How far the program gets
    there is ``kernel.softmax_paged_roofline``: the paged kernel reads a
    prompt's pages once a ROW."""
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
    return float(kv_token_bytes(model, kv_bytes=kv_bytes) * tokens)


def ssm_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                    kv_bytes: int = 2) -> float:
    """Bytes the Mamba-2 layers' decode must move in state: each layer's state
    read once and written once, float32, for every decoded token (``kv_bytes``
    is the pages' and is not read: a state is float32)."""
    steps = sum(int(g) for g in gen_lens)
    return float(steps * layer_kinds(model).count("mamba2") * 2 * state_bytes(model))


def tail_moved_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                     kv_bytes: int = 2) -> float:
    """Bytes the Mamba-2 layers' decode must move in tails: the three tokens
    before it read and its own ``[x | B | C]`` written, a layer a decoded token."""
    w = _sizes(model)
    steps = sum(int(g) for g in gen_lens)
    return float(steps * layer_kinds(model).count("mamba2") * w["taps"] * w["mixed"] * kv_bytes)


def delta_state_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                      kv_bytes: int = 2) -> float:
    """No layer of this model keeps a delta-rule state. ``readers/delta_moe_work``
    asks a counts module for this name before it reads ``softmax_kv_bytes`` for
    ``kernel.softmax_paged_roofline``: nothing to move."""
    return 0.0


def kv_read_bytes(model: Mapping[str, Any], prompt_lens, gen_lens, *,
                  kv_bytes: int = 2, group_size: int = 1) -> float:
    """What takes the place of a dense decoder's KV read: the attention layers'
    pages (a shared prompt's once a group), the Mamba-2 layers' states read and
    written, their tails."""
    return (softmax_kv_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes,
                             group_size=group_size)
            + ssm_state_bytes(model, prompt_lens, gen_lens)
            + tail_moved_bytes(model, prompt_lens, gen_lens, kv_bytes=kv_bytes))


def _chunk_flops(model: Mapping[str, Any]) -> float:
    """One chunk of one layer: 2 C (C N G + C P H + 2 N P H)."""
    w = _sizes(model)
    c = w["chunk"]
    return 2.0 * c * (c * w["cols"] * w["groups"] + c * w["inner"] + 2 * w["cols"] * w["inner"])


def ssd_chunk_flops(model: Mapping[str, Any], prompt_lens) -> float:
    """Operations the chunked form needs over the prompts ``prompt_lens`` (one
    entry a PROMPT, real tokens), every Mamba-2 layer: a chunk of C tokens
    costs 2 C (C N G + C P H + 2 N P H) (module docstring), each product counted
    once whatever precision it is multiplied in."""
    c = _sizes(model)["chunk"]
    chunks = sum(-(-int(p) // c) for p in prompt_lens)
    return layer_kinds(model).count("mamba2") * chunks * _chunk_flops(model)


def ssd_chunk_bytes(model: Mapping[str, Any], prompt_lens, *, act_bytes: int = 2,
                    segment: int = SEGMENT) -> float:
    """Bytes the chunked form must move over the prompts, every Mamba-2 layer: a
    token's x, B and C read and y written at the activations' type, its dt a
    head read in float32, and the carried state read and written once a segment
    of ``segment`` tokens."""
    w = _sizes(model)
    token = (w["mixed"] + w["inner"]) * act_bytes + w["heads"] * STATE_BYTES
    total = sum(int(p) * token + -(-int(p) // segment) * 2 * state_bytes(model)
                for p in prompt_lens)
    return float(layer_kinds(model).count("mamba2") * total)


def ssm_flops_per_token(model: Mapping[str, Any]) -> float:
    """Vector operations of the one-token step for ONE token of one layer: the
    decay's multiply, ``dt x B^T``, the add, and the multiply-add of the
    reduction against ``C``: 6 a state entry."""
    w = _sizes(model)
    return 6.0 * w["inner"] * w["cols"]


def train_flops_per_token(model: Mapping[str, Any], *, seq_len: int,
                          answer_len: int, lora_rank: int) -> float:
    """Operations LoRA training needs per token of a ``seq_len`` row whose last
    ``answer_len`` positions are scored, counted as ``roofline.py`` counts
    them: frozen projections forward and backward to activations (4 per
    weight), the adapter (6 per weight), the mixer forward and twice that
    backward (an attention layer's token at the mean causal context, a Mamba-2
    layer's at the chunked form's cost a token), the frozen head at the scored
    positions. Experts: this chip's part of the ``experts_per_token`` a token
    runs, and the shared one."""
    w = _sizes(model)
    here = int(model["experts_per_token"]) * w["held"] / float(w["width"])
    total = 4.0 * w["hidden"] * int(model["vocab_size"]) * (answer_len / float(seq_len))
    chunked = -(-seq_len // w["chunk"]) * _chunk_flops(model) / float(seq_len)
    for kind in layer_kinds(model):
        mixer = {"softmax": 2.0 * 2 * w["q"] * (seq_len + 1) / 2.0, "mamba2": chunked,
                 "experts": 0.0}[kind]
        total += (4.0 * layer_params(model, kind, here)
                  + 6.0 * layer_lora_params(model, kind, lora_rank) + 3.0 * mixer)
    return total
