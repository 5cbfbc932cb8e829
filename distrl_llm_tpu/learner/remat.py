"""What the train step's rematerialised layer scan keeps for the backward pass.

``forward(remat=True)`` keeps each layer's input and computes the layer a
second time in the backward pass, the products of the frozen weights included:
a quarter of an update. The train step hands ``forward`` a checkpoint policy
instead that keeps the NAMED products (``transformer.KEPT_PRODUCT_GROUPS``) as
far as the device's memory holds them beside the step's own working set, and
recomputes the rest as before. One rule for every dense family, ``lora`` and
``full``: its one parameter, how much to keep, follows from the micro-batch's
shape, the trainable tree's size and the memory of the devices that hold it.
``make_train_step`` asks once a shape and holds the answer, so every trace of
a shape builds the same program. models/hybrid.py's scan keeps nothing.
"""

from __future__ import annotations

import jax

from distrl_llm_tpu import obs, telemetry
from distrl_llm_tpu.engine.budget import ACTIVATION_RESERVE
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.models.transformer import KEPT_PRODUCT_GROUPS


def kept_products(cfg: ModelConfig, *, tokens: int, itemsize: int,
                  room: int) -> tuple[tuple[str, ...], int]:
    """Which named products a rematerialised scan over ``cfg``'s layers keeps
    at ``tokens`` tokens a micro-batch when it may spend ``room`` bytes, and
    the bytes that takes: whole groups of ``KEPT_PRODUCT_GROUPS`` in their
    order, every layer application the same (``cfg.layer_steps``: a looped
    model's scan keeps a pass's products for every pass), until the next
    group does not fit. A token's
    value a byte is the same for all five (the product's own operations over
    its output's bytes), so the order sets only the grain. Arithmetic alone:
    nothing is compiled or run to decide."""
    width = {"wq": cfg.q_dim, "wk": cfg.kv_dim,
             "wv": cfg.num_kv_heads * cfg.value_head_dim,
             "w_gate": cfg.intermediate_size, "w_up": cfg.intermediate_size}
    names: tuple[str, ...] = ()
    spent = 0
    for group in KEPT_PRODUCT_GROUPS:
        cost = cfg.layer_steps * tokens * itemsize * sum(width[n] for n in group)
        if spent + cost > room:
            break
        names, spent = names + group, spent + cost
    return names, spent


def step_working_set(cfg: ModelConfig, *, rows: int, seq: int, head_positions: int,
                     itemsize: int, trainable_bytes: int) -> int:
    """Bytes of temporaries a train step holds beside what the scan was told
    to keep, as a function of the micro-batch's shape and the trainable
    tree's size: three trees of the trainable tree's size (the gradient
    accumulator, one micro-batch's gradients, and what the optimizer holds
    while it updates: half a GB for a rank-32 adapter, 12 bytes a parameter
    in ``full`` mode, where a step compiled with the 8-bit optimizer read 2.9
    to 3.7 trees' worth with its activations); every layer application's input (the
    scan's own residuals: a looped model's for every pass); and the larger of one layer's backward (the
    attention core's float32 scores and their cotangents, the MLP's
    intermediate-wide values, the hidden-wide ones) and the head's float32
    logits and their cotangent over ``head_positions`` positions a row (one
    cross-entropy chunk, or the whole answer), which run beside the kept
    products before the first layer's backward does. The factors are read
    off steps compiled for a v5e (tests/test_tpu_compile.py holds them from
    above in both modes); a kernel that keeps no scores (flash, splash, ring)
    is counted as if it did, which keeps less."""
    tokens = rows * seq
    inputs = cfg.layer_steps * tokens * cfg.hidden_size * itemsize
    scores = 2 * rows * cfg.num_heads * seq * seq * 4
    mlp = 6 * tokens * cfg.intermediate_size * itemsize
    stream = 8 * tokens * cfg.hidden_size * 4
    head = 2 * rows * head_positions * cfg.vocab_size * 4
    return 3 * trainable_bytes + inputs + max(scores + mlp + stream, head)


def choose_kept(cfg: ModelConfig, *, rows: int, seq: int, head_positions: int,
                itemsize: int, trainable_bytes: int, devices=()) -> tuple[str, ...]:
    """The names a train step over ``[rows, seq]`` micro-batches keeps, from
    the memory of ``devices`` (those that hold the step's trainable tree; the
    first local device where the caller knows none) as it stands now. A
    device's room is its limit less what is in use, and no more than its
    largest free block (a program's temporaries are one allocation), less the
    step's working set with nothing kept (``step_working_set``), less the share
    of the device that engine/budget.py leaves to a round's workspace
    (``ACTIVATION_RESERVE``, 1.35 GB of a v5e: an engine on the same device
    counts on it, and within about 0.8 GB of the limit XLA rematerialises on
    its own, which ran slower than keeping less: PERF.md, PR 46). The
    fullest device decides. A mesh's step is counted at its global shape
    against one device's room, which keeps less than would fit. No reading
    (the CPU), no room, or a model whose layers differ in kind: nothing.
    Files the choice as ``learner/kept_products`` and
    ``learner/kept_product_bytes``."""
    readings = [obs.hbm_free(d) for d in devices or (None,)]
    room = 0
    if not cfg.hybrid and all(readings):
        work = step_working_set(
            cfg, rows=rows, seq=seq, head_positions=head_positions,
            itemsize=itemsize, trainable_bytes=trainable_bytes)
        room = min(min(limit - in_use, largest) - int(ACTIVATION_RESERVE * limit)
                   for limit, in_use, largest in readings) - work
    names, spent = kept_products(cfg, tokens=rows * seq, itemsize=itemsize, room=room)
    telemetry.gauge_set(telemetry.LEARNER_KEPT_PRODUCTS, float(len(names)))
    telemetry.gauge_set(telemetry.LEARNER_KEPT_PRODUCT_BYTES, float(spent))
    return names


def policy(names: tuple[str, ...]):
    """The checkpoint policy that keeps ``names``; for none of them JAX's own
    ``nothing_saveable``, the program as it was before there were names."""
    if not names:
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*names)
