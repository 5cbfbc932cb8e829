"""``perfbench/looped_counts.py`` against hand-worked arithmetic at the cell's
sizes (Ouro-2.6B, layers 0-7 of 48, run four times a token): the issue's own
numbers, digit for digit."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048  # projections and four norms
HEAD = 2048 * 49152


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/ouro-2.6b-L8.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


@pytest.fixture(scope="module")
def counts():
    from perfbench import looped_counts

    return looped_counts


def test_a_layer_the_cut_and_the_whole_are_the_issues(counts, model):
    assert counts.layer_params(model) == LAYER == 51_388_416
    assert counts.param_count(model) == 8 * LAYER + 2 * HEAD + 4_097 == 612_438_017
    assert 1.22e9 < 2 * counts.param_count(model) < 1.23e9  # the issue's 1.22 GB
    whole = {**model, "num_layers": 48}
    assert counts.param_count(whole) == 2_667_974_657  # 5.34 GB
    assert (counts.passes(model), counts.cache_layers(model)) == (4, 32)
    assert counts.cache_layers(whole) == 192


def test_a_step_reads_a_layers_weights_once_a_pass_and_the_rest_once(counts, model):
    around = 2048 + 2048 + 2049 + HEAD  # embedding row, N_f, the gate, the head
    assert counts.around_params(model) == around
    assert counts.decode_weight_bytes(model, weight_bytes=2) == (4 * 8 * LAYER + around) * 2
    # 4 x 0.82 GB of layers beside 0.2 GB of head: the issue's 3.5 GB
    assert 3.48e9 < counts.decode_weight_bytes(model, weight_bytes=2) < 3.50e9
    lora = 32 * (4 * (2048 + 2048) + 2 * (2048 + 5632) + (5632 + 2048))
    assert counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32, lora_bytes=4) == (
        (4 * 8 * LAYER + around) * 2 + 4 * 8 * lora * 4)
    once = {**model, "loop_steps": 1}
    assert counts.decode_weight_bytes(once) - around * 2 == (
        counts.decode_weight_bytes(model) - around * 2) // 4


def test_a_cached_token_is_262144_bytes_and_a_shared_prompt_counts_once_a_group(counts, model):
    assert counts.kv_token_bytes(model) == 2 * 16 * 128 * 2 == 8_192
    assert counts.cache_token_bytes(model) == 32 * 8_192 == 262_144
    assert counts.cache_token_bytes({**model, "num_layers": 48}) == 1_572_864
    # one group of 16 rows after a prompt of 1,024, 384 tokens each
    prompts, answers = [1024] * 16, [384] * 16
    tail = 384 * 385 // 2
    alone = counts.kv_read_bytes(model, prompts, answers, kv_bytes=2)
    shared = counts.kv_read_bytes(model, prompts, answers, kv_bytes=2, group_size=16)
    assert alone == 262_144 * 16 * (384 * 1024 + tail)
    assert shared == 262_144 * (384 * 1024 + 16 * tail)
    assert counts.attended_tokens(prompts, answers) == 16 * (384 * 1024 + tail)
    # without the keyword a row reads its prompt alone: the dense decoder's count
    from perfbench import roofline

    dense = {**model, "num_layers": 32}  # as many cache layers, run once
    assert alone == roofline.kv_read_bytes(dense, prompts, answers, kv_bytes=2)
    # the cell's round: four groups; a group's answers run together
    lens = [512, 1024, 1536, 2048]
    round_prompts = [p for p in lens for _ in range(16)]
    want = sum(384 * p + 16 * tail for p in lens)
    assert counts.kv_read_bytes(model, round_prompts, [384] * 64, group_size=16) == (
        262_144 * want)
    with pytest.raises(ValueError, match="share no one prompt"):
        counts.kv_read_bytes(model, [512] * 8 + [1024] * 8, [4] * 16, group_size=16)
    with pytest.raises(ValueError, match="no whole number of groups"):
        counts.kv_read_bytes(model, [512] * 10, [4] * 10, group_size=16)


def test_a_trained_token_runs_the_layers_once_a_pass_and_the_head_once(counts, model):
    from perfbench import roofline

    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=512, lora_rank=32)
    dense = roofline.train_flops_per_token(
        {**model, "num_layers": 32}, seq_len=1024, answer_len=512, lora_rank=32)
    assert got == dense  # 32 layer applications and one head, whoever's weights
    once = counts.train_flops_per_token(
        {**model, "loop_steps": 1}, seq_len=1024, answer_len=512, lora_rank=32)
    head = 4.0 * HEAD * 0.5
    assert got - head == 4 * (once - head)


def test_the_harness_tells_it_the_group_and_finds_it_by_name(counts, model):
    from perfbench.readers import required_work

    unit = {"prompt_lens": [1024] * 16, "gen_lens": [8] * 16, "group_size": 16}
    told = required_work.cache_bytes(counts.kv_read_bytes, model, unit, kv_bytes=2)
    assert told == counts.kv_read_bytes(model, [1024] * 16, [8] * 16, group_size=16)
    assert told < counts.kv_read_bytes(model, [1024] * 16, [8] * 16)
