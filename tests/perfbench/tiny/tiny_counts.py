"""Counts module ``tiny_counts`` (the tests' own): what a second model family
brings beside its reference, named by its configuration's ``counts`` key.

It stands for a family that runs half of its MLP width for each token (as a
sparse-expert block does with its top-k of the experts): the operations and the
weight bytes a token needs are the dense decoder's at half the
``intermediate_size``. What does not differ is ``perfbench.roofline``'s own:
attention and its KV bytes. The three functions are the ones
``readers/required_work.py`` calls, with ``roofline.py``'s signatures, so the
metrics keep their names (``learner.mfu``, ``engine.decode_bandwidth_util``,
``paged_attn_roofline``) and one configuration's reading stands beside another's.
"""

from perfbench.roofline import decode_weight_bytes as _dense_weight_bytes
from perfbench.roofline import kv_read_bytes  # noqa: F401  (does not differ)
from perfbench.roofline import train_flops_per_token as _dense_train_flops

ACTIVE_SHARE = 0.5


def _active(model):
    return {**model, "intermediate_size": int(model["intermediate_size"] * ACTIVE_SHARE)}


def train_flops_per_token(model, *, seq_len, answer_len, lora_rank):
    return _dense_train_flops(
        _active(model), seq_len=seq_len, answer_len=answer_len, lora_rank=lora_rank)


def decode_weight_bytes(model, *, weight_bytes=2, lora_rank=0, lora_bytes=4):
    return _dense_weight_bytes(
        _active(model), weight_bytes=weight_bytes, lora_rank=lora_rank,
        lora_bytes=lora_bytes)
