"""``perfbench/power_counts.py`` against counts worked by hand, at the cell's
sizes (Brumby-14B-Base at depth 4) and at the tiny preset's."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import power_counts as counts


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/brumby-14b-L4.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


def test_a_layer_is_the_issues_arithmetic(model):
    q_o, k_v, mlp = 2 * 5120 * 5120, 2 * 5120 * 1024, 3 * 5120 * 17408
    assert (q_o, k_v, mlp) == (52_428_800, 10_485_760, 267_386_880)
    assert counts.layer_params(model) == q_o + k_v + 5120 * 8 + mlp == 330_342_400
    assert counts.layer_small_params(model) == 2 * 5120 + 2 * 128 + 8
    head = 5120 * 151936
    base = head + 5120 + 4 * (330_342_400 + 10_504)
    assert counts.decode_weight_bytes(model, weight_bytes=2) == 2 * base
    # 2.643 GB of layers and 1.556 GB of head a step
    assert 4 * 330_342_400 * 2 == 2_642_739_200 and head * 2 == 1_555_824_640
    lora = 32 * ((5120 + 5120) * 2 + (5120 + 1024) * 2 + (5120 + 17408) * 3)
    assert counts.layer_lora_params(model, 32) == lora
    assert counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32, lora_bytes=4) == (
        2 * base + 4 * 4 * lora)
    # depth 4 with the whole embedding and head: 2,877M parameters, 5.754 GB
    assert abs(4 * 330_342_400 + 2 * head - 2.877e9) < 1e6


def test_a_slot_is_its_state_packed(model):
    assert counts.state_dim(model) == 8256
    a_layer = 8 * (8256 * 128 + 8256) * 4
    assert a_layer == 34_080_768 and counts.slot_state_bytes(model) == 4 * a_layer
    # 32 slots: 4.362 GB held, 8.72 GB read and written a step whatever the context
    assert 32 * counts.slot_state_bytes(model) == 4_362_338_304
    prompts, answers = [8192] * 16 + [16384] * 16, [256] * 32
    state = counts.power_state_bytes(model, prompts, answers)
    assert state == 256 * 32 * 2 * 4 * a_layer and state / 256 == 8_724_676_608
    assert counts.power_state_bytes(model, [100] * 32, answers) == state
    assert counts.kv_read_bytes(model, prompts, answers, kv_bytes=2) == state
    # a state holds as many bytes as 8,320 tokens of bf16 K and V of 8 heads
    assert a_layer // (2 * 8 * 128 * 2) == 8320


def test_the_chunked_forms_operations(model):
    big, d = 8256, 128
    token = 40 * (2 * d * 1024 + 2 * big * d + 2 * big) + 8 * 2 * big * d
    assert counts.power_flops_per_token(model) == token == 112_595_968
    # the state's part is the same at any chunk; the scores' part grows with it
    assert counts.power_flops_per_token(model, 256) == token - 40 * 2 * d * 768
    assert counts.power_chunk_flops(model, [8192, 16384]) == 4 * token * 24576
    # a layer's matrices are 2 x 330.3M = 660.7 MFLOP a token: retention is 15%
    share = token / (token + 2 * counts.layer_params(model))
    assert 0.14 < share < 0.15


def test_training_counts_the_mixer_at_the_rows_chunk(model):
    flops = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=32)
    mixer = counts.power_flops_per_token(model, 1024)
    want = 4.0 * 5120 * 151936 * 0.75 + 4 * (
        4.0 * 330_342_400 + 6.0 * counts.layer_lora_params(model, 32) + 3.0 * mixer)
    assert flops == want
    short = counts.train_flops_per_token(model, seq_len=256, answer_len=192, lora_rank=32)
    assert short == want - 4 * 3.0 * 40 * 2 * 128 * 768


def test_the_tiny_presets_counts_are_the_programs():
    from distrl_llm_tpu.models.configs import PRESETS

    cfg = PRESETS["tiny-power"]
    model = dataclasses.asdict(cfg)
    assert counts.state_dim(model) == cfg.power_state_dim == 136
    assert counts.slot_state_bytes(model) == 3 * 2 * (136 * 16 + 136) * 4
    assert 3 * counts.layer_params(model) + 64 * 256 == cfg.matmul_param_count
