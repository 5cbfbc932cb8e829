"""``perfbench/ssm_counts.py`` against hand arithmetic at AI21-Jamba2-3B's
published widths and at the tests' size: the yardstick's own numbers, from the
shapes alone."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import ssm_counts


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/jamba2-3b.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


def test_the_layers_are_two_attention_and_twenty_six_mamba(model):
    kinds = ssm_counts.layer_kinds(model)
    assert len(kinds) == 28 and kinds.count("mamba") == 26
    assert [i for i, k in enumerate(kinds) if k == "softmax"] == [7, 21]


def test_parameters_are_the_issues(model):
    mlp = 3 * 2560 * 8192
    assert ssm_counts.mlp_params(model) == mlp == 62_914_560
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert ssm_counts.mixer_params(model, "mamba") == mamba == 41_123_840
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert ssm_counts.mixer_params(model, "softmax") == attention == 13_762_560
    # the convolution and its bias, b_dt, A_log, D, three inner norms, two norms
    small = 4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120 + 160 + 16 + 16 + 2 * 2560
    assert ssm_counts.layer_small_params(model, "mamba") == small == 123_072
    assert ssm_counts.layer_small_params(model, "softmax") == 5120
    assert mamba + mlp + small == 104_161_472 and attention + mlp + 5120 == 76_682_240
    # 26 Mamba layers, 2 attention layers, the tied table once, the final norm
    assert ssm_counts.param_count(model) == (
        26 * 104_161_472 + 2 * 76_682_240 + 167_772_160 + 2_560) == 3_029_337_472
    untied = {**model, "tie_word_embeddings": False}
    assert ssm_counts.param_count(untied) == 3_029_337_472 + 167_772_160


def test_a_slot_is_nine_megabytes_and_a_token_one_kilobyte(model):
    assert ssm_counts.state_bytes(model) == 5120 * 16 * 4 == 327_680
    assert ssm_counts.window_bytes(model) == 3 * 5120 * 2 == 30_720
    assert ssm_counts.kv_token_bytes(model) == 2 * 2 * 128 * 2 == 1_024
    assert ssm_counts.slot_state_bytes(model) == 26 * (327_680 + 30_720) == 9_318_400
    # the cell's 480 slots: 4.09 GB of states and 0.38 GB of windows
    assert 480 * 26 * 327_680 == 4_089_446_400 and 480 * 26 * 30_720 == 383_385_600


def test_a_steps_bytes_are_the_issues(model):
    rows, new = 480, 384
    prompts = [1280] * rows  # the cell's mean prompt
    state = ssm_counts.ssm_state_bytes(model, prompts, [1] * rows)
    assert state == 480 * 26 * 327_680 * 2 == 8_178_892_800  # 8.18 GB a step
    window = ssm_counts.window_moved_bytes(model, prompts, [1] * rows)
    assert window == 480 * 26 * 4 * 5120 * 2 == 511_180_800
    # K/V of one head in two layers over the context: prompt + the token itself
    kv = ssm_counts.attention_kv_bytes(model, prompts, [1] * rows)
    assert kv == 480 * 1281 * 1024
    assert ssm_counts.kv_read_bytes(model, prompts, [1] * rows) == state + window + kv
    # a whole round: every state once in and once out a decoded token
    assert ssm_counts.ssm_state_bytes(model, prompts, [new] * rows) == state * new
    weights = ssm_counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=0)
    assert weights == 2 * (3_029_337_472) == 6_058_674_944  # the tied table read once
    lora = 2 * 32 * (4 * 2560 + 2560 + 128 + 128 + 2560 + 3 * (2560 + 8192)) + 26 * 32 * (
        2560 + 10240 + 5120 + 2560 + 3 * (2560 + 8192))
    assert ssm_counts.layer_lora_params(model, "softmax", 32) * 2 + (
        ssm_counts.layer_lora_params(model, "mamba", 32) * 26) == lora
    assert ssm_counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32) == (
        weights + 4 * lora)


def test_the_scan_reads_a_token_once_and_the_state_once_a_segment(model):
    token = 5120 * (2 + 2 + 2 + 4) + 2 * 16 * 2  # c, z, y at bf16, dt float32, B, C
    one = ssm_counts.ssm_scan_bytes(model, [1024])
    assert one == 26 * (1024 * token + 2 * 327_680)
    # a prompt of 1,025 tokens crosses a segment boundary: the state twice
    assert ssm_counts.ssm_scan_bytes(model, [1025]) == 26 * (1025 * token + 4 * 327_680)
    assert ssm_counts.ssm_scan_bytes(model, [512, 2048]) == (
        ssm_counts.ssm_scan_bytes(model, [512]) + ssm_counts.ssm_scan_bytes(model, [2048]))
    assert ssm_counts.ssm_flops_per_token(model) == 7 * 5120 * 16


def test_training_counts_both_kinds_and_the_scored_head(model):
    got = ssm_counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=32)
    head = 4.0 * 2560 * 65536 * 0.75
    mamba = 4.0 * (41_123_840 + 62_914_560) + 6.0 * ssm_counts.layer_lora_params(
        model, "mamba", 32) + 3.0 * 7 * 5120 * 16
    attention = 4.0 * (13_762_560 + 62_914_560) + 6.0 * ssm_counts.layer_lora_params(
        model, "softmax", 32) + 3.0 * 2.0 * 2 * 2560 * 1025 / 2.0
    assert got == pytest.approx(head + 26 * mamba + 2 * attention)
    # the matrix products are 99% of a Mamba layer's operations
    assert 3.0 * 7 * 5120 * 16 / mamba < 0.01


def test_the_tests_size(model):
    from distrl_llm_tpu.models.configs import PRESETS

    tiny = dataclasses.asdict(PRESETS["tiny-jamba"])
    assert ssm_counts.layer_kinds(tiny) == ["mamba", "softmax", "mamba", "mamba"]
    assert ssm_counts.state_bytes(tiny) == 16 * 64 * 4
    assert ssm_counts.kv_token_bytes(tiny) == 1 * 2 * 16 * 2
    assert ssm_counts.ssm_state_bytes(tiny, [40, 57], [24, 24]) == 48 * 3 * 2 * 4096
    # the program's own count of the same matrices
    cfg = PRESETS["tiny-jamba"]
    matrices = sum(ssm_counts.mixer_params(tiny, k) + ssm_counts.mlp_params(tiny)
                   for k in ssm_counts.layer_kinds(tiny)) + 32 * 256
    assert matrices == cfg.matmul_param_count
