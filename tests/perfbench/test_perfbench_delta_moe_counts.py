"""``perfbench/delta_moe_counts.py`` against counts worked by hand, at the
cell's sizes (Solar-Open2-250B as one of 8 chips a layer, depth 4) and at the
tiny preset's."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import delta_moe_counts as counts


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/solar-open2-250b-ep8-L4.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


def test_the_layer_kinds_are_the_run_layers_of_the_published_pattern(model):
    assert counts.layer_kinds(model) == ["softmax", "delta", "delta", "delta"]


def test_a_steps_weights_are_the_issues_arithmetic(model):
    expert = 3 * 4096 * 1280
    assert counts.expert_bytes_per_step(model, weight_bytes=2) == 4 * 40 * expert * 2
    assert counts.expert_bytes_per_step(model) == 5_033_164_800  # 5.03 GB a step
    softmax = 3 * 4096 * 8192 + 2 * 4096 * 1024
    delta = 4 * 4096 * 8192 + 2 * 128 * (4096 + 8192) + 4096 * 64
    assert counts.mixer_params(model, "softmax") == softmax == 109_051_904
    assert counts.mixer_params(model, "delta") == delta == 137_625_600
    around = expert + 4096 * 320  # the shared expert and the router's 320 columns
    assert counts.ffn_params(model, 40) == 40 * expert + around
    small_softmax = 2 * 4096 + 320
    small_delta = small_softmax + 4 * 3 * 8192 + 8192 + 64 + 128
    base = (softmax + 3 * delta + 4 * (40 * expert + around) + small_softmax
            + 3 * small_delta + 4096 * 24576 + 4096)
    assert counts.decode_weight_bytes(model, weight_bytes=2) == 2 * base
    assert 6.40e9 < 2 * base < 6.46e9  # 5.03 of experts + 1.4 of mixers and head
    lora = 32 * ((4096 + 8192) + 2 * (4096 + 1024) + (8192 + 4096) + 3 * (4096 + 1280))
    lora += 3 * 32 * (4 * (4096 + 8192) + 3 * (4096 + 1280))
    assert counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32, lora_bytes=4) == (
        2 * base + 4 * lora)


def test_a_steps_state_and_pages(model):
    rows, new = 128, 768
    prompts, answers = [1280] * rows, [new] * rows
    # 3 layers x 128 rows x (64 x 128 x 128 float32 = 4 MiB), read and written
    state = counts.delta_state_bytes(model, prompts, answers)
    assert state == new * 3 * rows * 2 * 64 * 128 * 128 * 4
    assert state / new == 3_221_225_472  # 3.2 GB a step, whatever the context
    assert counts.delta_state_bytes(model, [20_000] * rows, answers) == state
    # one softmax layer: K and V of 8 x 128 bf16 over every attended token
    tokens = rows * (new * 1280 + new * (new + 1) // 2)
    kv = counts.softmax_kv_bytes(model, prompts, answers, kv_bytes=2)
    assert kv == 1 * 2 * 1024 * 2 * tokens
    assert 0.85e9 < kv / new < 0.90e9  # 0.87 GB a step at a mean context of 1.7k
    assert counts.kv_read_bytes(model, prompts, answers, kv_bytes=2) == kv + state
    # the state is float32 whatever the pages are kept in
    assert counts.kv_read_bytes(model, prompts, answers, kv_bytes=1) == kv / 2 + state


def test_the_chunked_rules_operations(model):
    # a token, a layer: 64 heads x (4 x 64 x 128 + 6 x 128 x 128)
    assert counts.delta_flops_per_token(model) == 64 * (32_768 + 98_304) == 8_388_608
    assert counts.delta_chunk_flops(model, [512, 2048]) == 3 * 8_388_608 * 2560
    assert counts.delta_flops_per_token(model, chunk=128) > counts.delta_flops_per_token(model)


def test_a_trained_tokens_operations_count_this_chips_part_of_its_experts(model):
    flops = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=32)
    expert = 3 * 4096 * 1280
    here = 8 * 40 / 320  # one of a token's eight experts is held here, on average
    ffn = here * expert + expert + 4096 * 320
    lora = counts.layer_lora_params(model, "softmax", 32) + 3 * counts.layer_lora_params(
        model, "delta", 32)
    mixers = 109_051_904 + 3 * 137_625_600
    attention = 2.0 * 2 * 8192 * 1025 / 2.0
    want = (4.0 * 4096 * 24576 * 0.75 + 4.0 * (mixers + 4 * ffn) + 6.0 * lora
            + 3.0 * (attention + 3 * 8_388_608))
    assert flops == pytest.approx(want)
    whole = dict(model, n_routed_experts=320, router_experts=0)
    assert counts.train_flops_per_token(
        whole, seq_len=1024, answer_len=768, lora_rank=32) > flops


def test_the_tiny_presets_counts(model):
    from distrl_llm_tpu.models.configs import PRESETS

    tiny = dataclasses.asdict(PRESETS["tiny-delta-moe"])
    assert counts.expert_bytes_per_step(tiny, weight_bytes=4) == 4 * 2 * 3 * 64 * 32 * 4
    assert counts.delta_state_bytes(tiny, [40, 57], [24, 24]) == 48 * 3 * 2 * 4 * 16 * 16 * 4
    assert counts.softmax_kv_bytes(tiny, [40], [24], kv_bytes=4) == (
        1 * 2 * 32 * 4 * (24 * 40 + 24 * 25 // 2))
