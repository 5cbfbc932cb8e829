"""``perfbench/ssd_moe_counts.py`` against hand arithmetic at
NVIDIA-Nemotron-3-Nano-30B-A3B's published widths (one of the 2 chips that
share a layer, layers 0-12) and at the tests' size: the yardstick's own
numbers, from the shapes alone."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import ssd_moe_counts as counts


@pytest.fixture(scope="module")
def cfg():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/nemotron-3-nano-ep2-L13.json")) as f:
        return ModelConfig.from_hf_config(SimpleNamespace(**json.load(f)))


@pytest.fixture(scope="module")
def model(cfg):
    return dataclasses.asdict(cfg)


def test_the_layers_are_six_mamba_five_experts_two_attention(model):
    kinds = counts.layer_kinds(model)
    assert "".join(k[0] for k in kinds) == "memems" "ememems".replace(" ", "")
    assert (kinds.count("mamba2"), kinds.count("experts"), kinds.count("softmax")) == (6, 5, 2)


def test_parameters_are_the_issues(model, cfg):
    mamba = 2688 * 10304 + 4096 * 2688
    assert counts.layer_params(model, "mamba2", 64) == mamba == 38_707_200
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert counts.layer_params(model, "softmax", 64) == attention == 23_396_352
    expert = 2 * 2688 * 1856
    assert expert == 9_977_856
    experts = 64 * expert + 2 * 2688 * 3712 + 2688 * 128
    assert counts.layer_params(model, "experts", 64) == experts == 658_882_560
    # the taps and their bias, A_log, dt_bias, D, the gate's norm, the one norm
    assert counts.layer_small_params(model, "mamba2") == 5 * 6144 + 3 * 64 + 4096 + 2688
    assert counts.layer_small_params(model, "experts") == 2688 + 128
    total = counts.param_count(model)
    assert 3.925e9 < total < 3.927e9  # the issue's 3,926 M: 7.85 GB at bf16
    assert total - sum(
        counts.layer_small_params(model, k) for k in counts.layer_kinds(model)) - 2688 == (
        cfg.total_matmul_param_count + 2688 * 65536)  # the program's own count, and the table


def test_the_programs_tree_holds_as_many(cfg):
    import jax

    from distrl_llm_tpu.models import init_params

    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    held = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert held == counts.param_count(dataclasses.asdict(cfg))


def test_a_slot_is_twelve_megabytes_and_a_token_two_kilobytes(model):
    assert counts.state_bytes(model) == 64 * 64 * 128 * 4 == 2 * 2**20
    assert counts.tail_bytes(model) == 3 * 6144 * 2 == 36_864
    assert counts.kv_token_bytes(model) == 2 * 2 * 256 * 2 == 2_048
    assert counts.slot_state_bytes(model) == 6 * (2_097_152 + 36_864) == 12_804_096
    # the cell's 256 slots: 3.22 GB of states and 0.06 GB of tails
    assert 256 * 6 * 2_097_152 == 3_221_225_472 and 256 * 6 * 36_864 == 56_623_104


def test_a_steps_bytes_are_the_issues(model):
    rows = 256
    prompts = [1280] * rows  # the cell's mean prompt
    state = counts.ssm_state_bytes(model, prompts, [1] * rows)
    assert state == 256 * 6 * 2_097_152 * 2 == 6_442_450_944  # 6.44 GB a step
    assert counts.expert_bytes_per_step(model) == 5 * 64 * 9_977_856 * 2 == 6_385_827_840
    tails = counts.tail_moved_bytes(model, prompts, [1] * rows)
    assert tails == 256 * 6 * 4 * 6144 * 2
    # K/V of two heads in two layers: a shared prompt's pages once a group of 16
    alone = counts.softmax_kv_bytes(model, prompts, [1] * rows)
    assert alone == 256 * 1281 * 2048
    shared = counts.softmax_kv_bytes(model, prompts, [1] * rows, group_size=16)
    assert shared == (16 * 1280 + 256) * 2048
    assert counts.kv_read_bytes(model, prompts, [1] * rows, group_size=16) == (
        shared + state + tails)
    assert counts.delta_state_bytes(model, prompts, [1] * rows) == 0.0
    with pytest.raises(ValueError, match="share no one prompt"):
        counts.softmax_kv_bytes(model, [5, 6], [1, 1], group_size=2)
    weights = counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=0)
    assert weights == 2 * (counts.param_count(model) - 2688 * 65536)  # the embedding is a lookup
    other = weights - counts.expert_bytes_per_step(model)
    assert 1.10e9 < other < 1.13e9  # the issue's 1.11 GB of other weights
    lora = 6 * 32 * (2688 + 10304 + 4096 + 2688) + 2 * 32 * (
        2 * (2688 + 4096) + 2 * (2688 + 256)) + 5 * 32 * 2 * (2688 + 3712)
    assert counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32) == weights + 4 * lora


def test_the_chunked_form_is_bound_by_its_bytes_at_these_shapes(model):
    chunk = 2.0 * 128 * (128 * 128 * 8 + 128 * 4096 + 2 * 128 * 4096)
    assert counts.ssd_chunk_flops(model, [128]) == 6 * chunk == 6 * 436_207_616
    assert counts.ssd_chunk_flops(model, [129]) == 12 * chunk  # a second chunk, part empty
    token = (6144 + 4096) * 2 + 64 * 4  # x, B, C and y at bf16, dt float32
    assert counts.ssd_chunk_bytes(model, [1024]) == 6 * (1024 * token + 2 * 2_097_152)
    # a prompt of 1,025 tokens crosses a segment boundary: the state twice
    assert counts.ssd_chunk_bytes(model, [1025]) == 6 * (1025 * token + 4 * 2_097_152)
    prompts = [512 + i * 1536 // 15 for i in range(16)]  # the cell's sixteen
    flops_s = counts.ssd_chunk_flops(model, prompts) / 197e12
    bytes_s = counts.ssd_chunk_bytes(model, prompts) / 819e9
    assert bytes_s > 1.5 * flops_s  # the bound the metric divides by: memory
    assert counts.ssm_flops_per_token(model) == 6 * 4096 * 128


def test_training_counts_the_three_kinds_and_the_scored_head(model):
    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=32)
    head = 4.0 * 2688 * 65536 * 0.75
    here = 6 * 64 / 128.0  # this chip's part of a token's six experts
    mamba = 4.0 * 38_707_200 + 6.0 * counts.layer_lora_params(model, "mamba2", 32) + (
        3.0 * 8 * 436_207_616 / 1024)
    attention = 4.0 * 23_396_352 + 6.0 * counts.layer_lora_params(model, "softmax", 32) + (
        3.0 * 2.0 * 2 * 4096 * 1025 / 2.0)
    experts = 4.0 * (2 * 2688 * (here * 1856 + 3712) + 2688 * 128) + (
        6.0 * counts.layer_lora_params(model, "experts", 32))
    assert got == pytest.approx(head + 6 * mamba + 2 * attention + 5 * experts)


def test_the_tests_size():
    from distrl_llm_tpu.models.configs import PRESETS

    cfg = PRESETS["tiny-nemotron-h"]
    tiny = dataclasses.asdict(cfg)
    assert counts.layer_kinds(tiny) == ["mamba2", "experts", "mamba2", "softmax", "experts",
                                        "mamba2"]
    assert counts.state_bytes(tiny) == 4 * 8 * 16 * 4
    assert counts.kv_token_bytes(tiny) == 2 * 2 * 16 * 2
    assert counts.ssm_state_bytes(tiny, [40, 57], [24, 24]) == 48 * 3 * 2 * 2048
    # the program's own count of the same matrices, the experts HELD
    matrices = sum(counts.layer_params(tiny, k, 4) for k in counts.layer_kinds(tiny)) + 64 * 256
    assert matrices == cfg.total_matmul_param_count
