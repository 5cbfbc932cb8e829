"""``perfbench/dsa_moe_counts.py`` against hand arithmetic at GLM-5's published
widths as one chip of sixteen holds them, and at the cell's traffic: the
yardstick's own numbers, from the shapes alone."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import dsa_moe_counts as counts


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/glm-5-ep16-L5.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


def test_the_layers_are_one_dense_and_four_of_experts(model):
    assert counts.layer_kinds(model) == ["dense"] + ["experts"] * 4


def test_parameters_are_the_issues_to_the_unit(model):
    q_a, q_b = 6144 * 2048, 2048 * 64 * 256
    kv_a, kv_b, o = 6144 * 576, 512 * 64 * (192 + 256), 64 * 256 * 6144
    assert (q_a, q_b, kv_a, kv_b, o) == (12_582_912, 33_554_432, 3_538_944, 14_680_064,
                                         100_663_296)
    assert counts.attention_params(model) == q_a + q_b + kv_a + kv_b + o == 165_019_648
    assert counts.index_params(model) == 2048 * 32 * 128 + 6144 * 128 + 6144 * 32 == 9_371_648
    assert counts.ffn_params(model, "dense", 16) == 3 * 6144 * 12288 == 226_492_416
    one_expert = 3 * 6144 * 2048
    assert one_expert == 37_748_736 and 256 * one_expert * 2 == 19_327_352_832  # 19.3 GB a layer
    router = 6144 * 256
    assert counts.ffn_params(model, "experts", 16) == 17 * one_expert + router == 643_301_376
    assert counts.layer_small_params(model, "dense") == 2 * 6144 + 2048 + 512 + 2 * 128
    assert counts.layer_small_params(model, "experts") == 2 * 6144 + 2048 + 512 + 2 * 128 + 256
    assert 2 * 6144 * 19360 == 237_895_680
    assert counts.param_count(model) == 3_909_632_768  # 3,910M: 7.82 GB in bf16
    # the experts a TOKEN runs here: 8 x 16 / 256 = half of one
    assert counts.ffn_params(model, "experts", 0.5) == 1.5 * one_expert + router


def test_a_token_costs_1536_bytes_of_pages_a_layer(model):
    assert counts.page_token_bytes(model) == (640 + 128) * 2 == 1_536
    # the cell's 61,440 prompt tokens and 32,768 decoded ones over five layers: 0.72 GB
    assert 5 * (61_440 + 32_768) * 1_536 == 723_517_440


def test_a_steps_bytes_are_the_issues(model):
    assert counts.expert_bytes_per_step(model) == 4 * 16 * 37_748_736 * 2 == 4_831_838_208
    weights = counts.decode_weight_bytes(model)
    # everything but the embedding's lookup, in bf16
    assert weights == (3_909_632_768 - 6144 * 19360) * 2 == 7_581_369_856
    with_adapter = counts.decode_weight_bytes(model, lora_rank=32)
    dense = counts.layer_lora_params(model, "dense", 32)
    shared = counts.layer_lora_params(model, "experts", 32)
    assert with_adapter - weights == 4 * (dense + 4 * shared)
    assert dense - shared == 32 * 3 * (12288 - 2048)
    # one decoded token a row at 15,616 tokens of context, 64 rows in 4 groups of 16:
    # a prompt's index keys once a group, each row's newest key a row
    keys = counts.index_key_bytes(model, [15_615] * 64, [1] * 64, group_size=16)
    assert keys == 5 * 128 * 2 * (4 * 15_615 + 64) == 80_030_720
    alone = counts.index_key_bytes(model, [15_615] * 64, [1] * 64)
    assert alone == 5 * 128 * 2 * 64 * 15_616
    rows = counts.indexed_attn_bytes(model, [15_615] * 64, [1] * 64)
    assert rows == 5 * 64 * 2048 * 576 * 2 == 754_974_720  # the chosen, not the 15,616 seen
    assert counts.kv_read_bytes(model, [15_615] * 64, [1] * 64, group_size=16) == keys + rows
    # what a dense walk of every latent row would read, a row: 7.6 times the chosen rows
    assert 5 * 64 * 15_616 * 576 * 2 / rows == pytest.approx(7.625)
    # under index_topk a token attends all it sees
    assert counts.indexed_attn_bytes(model, [9], [3]) == 5 * (10 + 11 + 12) * 576 * 2
    with pytest.raises(ValueError, match="share no one prompt"):
        counts.index_key_bytes(model, [10, 11], [1, 1], group_size=2)


def test_the_counters_units_are_of_128_tokens_and_pass_no_int32(model):
    prompts = [p for p in (10_240, 13_653, 17_067, 20_480) for _ in range(16)]
    attended, visible = counts.index_tokens(model, prompts, [512] * 64)
    assert attended == 5 * 64 * 512 * 16 == 2_621_440  # 2,048 tokens: 16 units
    # in tokens the visible count is 2.6e9, past an int32; in units 2.0e7
    tokens = 5 * sum(p * 512 + 512 * 513 // 2 for p in prompts)
    assert tokens > 2**31 and visible == 20_070_400
    assert 13.0 < 100.0 * attended / visible < 13.1  # engine.index_attended_share
    assert counts.index_tokens(model, [100], [28]) == (5 * 28, 5 * 28)  # 100 below 128
    assert counts.index_tokens(model, [2047], [2]) == (5 * 2 * 16, 5 * (16 + 17))


def test_training_operations_count_what_is_attended_and_this_chips_part_of_the_experts(model):
    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=32)
    head = 4.0 * 6144 * 19360 * 0.75
    one = 37_748_736
    layers = (4.0 * (5 * 165_019_648 + 226_492_416 + 4 * (1.5 * one + 6144 * 256))
              + 2.0 * 5 * 9_371_648
              + 6.0 * (counts.layer_lora_params(model, "dense", 32)
                       + 4 * counts.layer_lora_params(model, "experts", 32)))
    attend = 5 * 3.0 * 2 * (64 * 256 * 2) * 512.5  # 512.5 is under index_topk: all seen
    score = 5 * 2.0 * 32 * 128 * 512.5
    assert got == pytest.approx(head + layers + attend + score)
    long = counts.train_flops_per_token(model, seq_len=16384, answer_len=512, lora_rank=32)
    # at 16k the attention is capped at 2,048 tokens and the index's scores are not
    assert long - got == pytest.approx(
        4.0 * 6144 * 19360 * (512 / 16384 - 0.75)
        + 5 * 3.0 * 2 * (64 * 256 * 2) * (2048 - 512.5) + 5 * 2.0 * 32 * 128 * (8192.5 - 512.5))
