"""``perfbench/window_moe_counts.py`` against hand arithmetic at
K-EXAONE-236B-A23B's published widths as one chip of eight holds them, and at
the cell's traffic: the yardstick's own numbers, from the shapes alone."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import window_moe_counts as counts


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/k-exaone-236b-ep8-L5.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


def test_the_layers_are_four_window_one_full_and_layer_0_dense(model):
    assert counts.layer_kinds(model) == [
        ("window", "dense"), ("window", "experts"), ("window", "experts"),
        ("full", "experts"), ("window", "experts")]


def test_parameters_are_the_issues_to_the_unit(model):
    attention = 6144 * 8192 * 2 + 2 * 6144 * 1024
    assert counts.mixer_params(model) == attention == 113_246_208
    assert counts.ffn_params(model, "dense", 16) == 3 * 6144 * 18432 == 339_738_624
    one_expert = 3 * 6144 * 2048
    assert one_expert == 37_748_736 and 128 * one_expert * 2 == 9_663_676_416  # 9.66 GB a layer
    router = 6144 * 128
    assert counts.ffn_params(model, "experts", 16) == 17 * one_expert + router == 642_514_944
    assert counts.layer_small_params(model, "dense") == 2 * 6144 + 2 * 128
    assert counts.layer_small_params(model, "experts") == 2 * 6144 + 2 * 128 + 128
    embed_and_head = 2 * 6144 * 19200
    assert embed_and_head == 235_929_600
    assert counts.param_count(model) == 3_712_028_416  # 7.42 GB in bf16
    # the experts a TOKEN runs here: 8 x 16 / 128 = one
    assert counts.ffn_params(model, "experts", 1.0) == 2 * one_expert + router


def test_a_token_costs_four_kilobytes_of_pages_and_a_slot_two_megabytes_of_rings(model):
    assert counts.kv_token_bytes(model) == 2 * 8 * 128 * 2 == 4_096  # ONE layer
    assert counts.ring_bytes(model) == 128 * 4_096 == 524_288
    assert counts.slot_state_bytes(model) == 4 * 524_288
    # the cell's 64 slots: 134 MB of rings; its 61,440 prompt tokens: 252 MB of pages
    assert 64 * counts.slot_state_bytes(model) == 134_217_728
    assert 61_440 * 4_096 == 251_658_240


def test_a_steps_bytes_are_the_issues(model):
    assert counts.expert_bytes_per_step(model) == 4 * 16 * 37_748_736 * 2 == 4_831_838_208
    weights = counts.decode_weight_bytes(model)
    # everything but the embedding's lookup, in bf16
    assert weights == (3_712_028_416 - 6144 * 19200) * 2 == 7_188_127_232
    with_adapter = counts.decode_weight_bytes(model, lora_rank=32)
    dense = counts.layer_lora_params(model, "dense", 32)
    shared = counts.layer_lora_params(model, "experts", 32)
    assert with_adapter - weights == 4 * (dense + 4 * shared)
    assert dense - shared == 32 * 3 * (18432 - 2048)
    # one decoded token a row at 15,616 tokens of context, 64 rows
    full = counts.softmax_kv_bytes(model, [15_615] * 64, [1] * 64)
    assert full == 64 * 15_616 * 4_096 == 4_093_640_704  # the issue's 4.1 GB
    rings = counts.window_kv_bytes(model, [15_615] * 64, [1] * 64)
    assert rings == 4 * 64 * 128 * 4_096 == 134_217_728  # the issue's 0.13 GB
    assert counts.kv_read_bytes(model, [15_615] * 64, [1] * 64) == full + rings
    assert counts.delta_state_bytes(model, [15_615], [1]) == 0.0
    # below the window a ring holds what a page would
    assert counts.window_kv_bytes(model, [9], [3]) == 4 * (10 + 11 + 12) * 4_096


def test_the_counters_units_are_of_128_keys_and_pass_no_int32(model):
    prompts = [p for p in (10_240, 13_653, 17_067, 20_480) for _ in range(16)]
    attended, visible = counts.window_pages(model, prompts, [512] * 64)
    assert attended == 4 * 64 * 512 == 131_072
    # in keys the visible count is 2.0e9, at the edge of an int32; in units 1.6e7
    keys = 4 * sum(p * 512 + 512 * 513 // 2 for p in prompts)
    assert 2.0e9 < keys < 2**31 and visible == 16_056_320
    assert 0.81 < 100.0 * attended / visible < 0.82
    assert counts.window_pages(model, [100], [28]) == (4 * 28, 4 * 28)  # 100 below 128
    assert counts.window_pages(model, [127], [2]) == (4 * 2, 4 * (1 + 2))


def test_training_operations_count_the_band_and_this_chips_part_of_the_experts(model):
    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=32)
    head = 4.0 * 6144 * 19200 * 0.75
    attention = 113_246_208
    one = 37_748_736
    layers = (4.0 * (5 * attention + 339_738_624 + 4 * (2 * one + 6144 * 128))
              + 6.0 * (counts.layer_lora_params(model, "dense", 32)
                       + 4 * counts.layer_lora_params(model, "experts", 32)))
    mixers = 3.0 * 2 * 2 * 8192 * (512.5 + 4 * 128)
    assert got == pytest.approx(head + layers + mixers)
