"""``perfbench/latent_moe_counts.py`` against hand-worked arithmetic at the
published widths (Kimi-VL-A3B's language model, layers 0-6)."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/kimi-vl-a3b-L7.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


@pytest.fixture(scope="module")
def counts():
    from perfbench import latent_moe_counts

    return latent_moe_counts


ATTN = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048  # 13.76M
EXPERT = 3 * 2048 * 1408  # 8.65M


def test_layer_kinds_follow_first_k_dense_replace(counts, model):
    assert counts.layer_kinds(model) == ["latent"] + ["latent_moe"] * 6
    assert counts.attention_params(model) == ATTN == 13_762_560


@pytest.mark.parametrize("routed, want", [
    (64, 64 * EXPERT + 2 * EXPERT + 2048 * 64),  # held: 584.8M with attention
    (6, 6 * EXPERT + 2 * EXPERT + 2048 * 64),    # run by a token
])
def test_an_expert_layers_parameters_held_and_run(counts, model, routed, want):
    assert counts.ffn_params(model, "latent_moe", routed) == want
    assert counts.ffn_params(model, "latent", routed) == 3 * 2048 * 11264


def test_a_decode_step_reads_every_expert_held(counts, model):
    layers = 7 * ATTN + 3 * 2048 * 11264 + 6 * (66 * EXPERT + 2048 * 64)
    norms = 7 * (2 * 2048 + 512) + 6 * 64
    want = 2 * (layers + norms + 2048 * 163840 + 2048)
    assert counts.decode_weight_bytes(model, weight_bytes=2) == want
    assert 7.84e9 < want < 7.86e9  # the issue's 7.85 GB
    assert counts.expert_bytes_per_step(model) == 6 * 64 * EXPERT * 2
    with_adapter = counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32)
    lora = 32 * (
        7 * ((2048 + 3072) + (2048 + 576) + (512 + 4096) + (2048 + 2048))
        + 3 * (2048 + 11264) + 6 * 3 * (2048 + 2816))
    assert with_adapter - want == 4 * lora


def test_the_cache_read_is_one_row_of_576_values_a_token_a_layer(counts, model):
    # one row decoding 3 tokens after a prompt of 10: contexts 11, 12, 13
    assert counts.kv_read_bytes(model, [10], [3], kv_bytes=2) == 7 * 576 * 2 * 36
    assert counts.latent_attn_bytes is counts.kv_read_bytes
    cell = counts.kv_read_bytes(
        model, [10240, 13653, 17067, 20480] * 16, [512] * 64, kv_bytes=2) / 512
    assert 8.0e9 < cell < 8.1e9  # the issue's 8.1 GB a step
    assert counts.latent_attn_flops_per_cached_token(model) == 16 * 2 * (576 + 512)


def test_training_counts_the_experts_a_token_runs(counts, model):
    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=0)
    run = 7 * ATTN + 3 * 2048 * 11264 + 6 * (8 * EXPERT + 2048 * 64)
    mixer = 2.0 * (3072 + 2048) * 512.5
    want = 4.0 * run + 7 * 3.0 * mixer + 4.0 * 2048 * 163840 * 0.75
    assert got == pytest.approx(want)
    held = 4.0 * (7 * ATTN + 3 * 2048 * 11264 + 6 * (66 * EXPERT + 2048 * 64))
    assert got < held / 3  # all 64 would count more than three times as much
    assert counts.expert_flops_per_token(model) == 6 * 6 * 2 * EXPERT


def test_the_programs_own_count_agrees(counts, model):
    from distrl_llm_tpu.models import ModelConfig

    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})
    run = 7 * ATTN + 3 * 2048 * 11264 + 6 * (8 * EXPERT + 2048 * 64) + 2048 * 163840
    assert cfg.matmul_param_count == run
