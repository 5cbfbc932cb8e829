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


# ------------------------------------------------ latent rows once a group (PR 35)

#: the cell's round: 4 prompts x 16 candidates x 512 tokens, rows of a prompt consecutive
CELL_PROMPTS = [p for p in (10240, 13653, 17067, 20480) for _ in range(16)]
ROW = 7 * 576 * 2  # bytes a cached token, over the 7 layers


def parent_count(model, prompt_lens, gen_lens, kv_bytes=2):
    """``kv_read_bytes`` as it stood before PR 35, line for line."""
    tokens = 0
    for p, g in zip(prompt_lens, gen_lens):
        p, g = int(p), int(g)
        tokens += g * p + g * (g + 1) // 2
    return float(int(model["num_layers"]) * 576 * kv_bytes * tokens)


@pytest.mark.parametrize("prompts, answers", [
    ([10], [3]), ([10, 10, 7, 7], [3, 5, 1, 2]), (CELL_PROMPTS, [512] * 64),
    ([20480, 128, 4096], [512, 1, 77]),
], ids=["one-row", "four-rows", "the-cell", "uneven"])
def test_a_group_of_one_is_the_count_before_pr_35_bit_for_bit(counts, model, prompts, answers):
    want = parent_count(model, prompts, answers)
    assert counts.kv_read_bytes(model, prompts, answers, kv_bytes=2) == want
    assert counts.kv_read_bytes(model, prompts, answers, kv_bytes=2, group_size=1) == want


def test_the_cells_round_counts_a_prompt_once_a_group(counts, model):
    """The issue's hand count: 4 x 512 x the prompt + 64 tails of 512 x 513 / 2
    = 39.9M cached-token reads a round, 0.32 TB, where a row a prompt reads
    511.7M, 4.13 TB."""
    per_row = counts.kv_read_bytes(model, CELL_PROMPTS, [512] * 64, kv_bytes=2)
    assert per_row == ROW * (16 * 512 * 61440 + 64 * 131328) == ROW * 511_721_472
    grouped = counts.kv_read_bytes(model, CELL_PROMPTS, [512] * 64, kv_bytes=2, group_size=16)
    assert grouped == ROW * (512 * 61440 + 64 * 131328) == ROW * 39_862_272
    assert 0.32e12 < grouped < 0.325e12 and 4.12e12 < per_row < 4.13e12
    assert counts.latent_attn_bytes(
        model, CELL_PROMPTS, [512] * 64, kv_bytes=2, group_size=16) == grouped
    # 0.39 s at 819 GB/s against the 2.159 s PR 34 measured under model/latent_attn
    assert 17.5 < 100 * grouped / 819e9 / 2.159 < 18.5


def test_a_groups_prompt_counts_as_long_as_its_longest_answer_runs(counts, model):
    # two groups of two: answers 3 and 5 after a prompt of 10, 1 and 2 after 7
    got = counts.kv_read_bytes(model, [10, 10, 7, 7], [3, 5, 1, 2], kv_bytes=2, group_size=2)
    assert got == ROW * ((5 * 10 + 6 + 15) + (2 * 7 + 1 + 3))
    # a row that stopped at once adds nothing, and takes nothing from the group
    assert counts.kv_read_bytes(model, [10, 10], [5, 0], kv_bytes=2, group_size=2) == (
        counts.kv_read_bytes(model, [10], [5], kv_bytes=2))


@pytest.mark.parametrize("prompts, answers, size, said", [
    ([10, 10, 10], [1, 1, 1], 2, "no whole number of groups of 2"),
    ([10, 10], [1, 1], 0, "no whole number of groups of 0"),
    ([10, 10], [1], 1, "2 prompts and 1 answers"),
    ([10, 11], [1, 1], 2, "share no one prompt"),
], ids=["does-not-divide", "zero", "ragged", "two-prompts-in-a-group"])
def test_rows_that_are_no_whole_groups_are_refused(counts, model, prompts, answers, size, said):
    with pytest.raises(ValueError, match=said):
        counts.kv_read_bytes(model, prompts, answers, kv_bytes=2, group_size=size)


def test_the_readers_tell_a_counts_function_its_groups_only_if_it_asks(counts, model):
    """``required_work.cache_bytes``: the signature decides. ``roofline.py``'s
    and ``sala_counts.py``'s counts take no ``group_size`` and are called
    without it, so every reading that divides by them is the parent's."""
    from perfbench import roofline, sala_counts, spec

    required = spec.load_module(("perfbench",), "readers", "required_work")
    unit = {"prompt_lens": [10, 10], "gen_lens": [3, 5], "group_size": 2}
    seen = {}

    def plain(model, prompt_lens, gen_lens, *, kv_bytes=2):
        seen["plain"] = (list(prompt_lens), list(gen_lens), kv_bytes)
        return 1.0

    def grouped(model, prompt_lens, gen_lens, *, kv_bytes=2, group_size=1):
        seen["grouped"] = group_size
        return 2.0

    assert required.cache_bytes(plain, model, unit, kv_bytes=2) == 1.0
    assert seen["plain"] == ([10, 10], [3, 5], 2)
    assert required.cache_bytes(grouped, model, unit, kv_bytes=2) == 2.0 and seen["grouped"] == 2
    # a unit that says nothing of groups (the parent's driver, rl_step's): the default
    del unit["group_size"]
    assert required.cache_bytes(grouped, model, unit, kv_bytes=2) == 2.0 and seen["grouped"] == 1
    unit["group_size"] = 2
    assert required.cache_bytes(counts.kv_read_bytes, model, unit, kv_bytes=2) == (
        ROW * (5 * 10 + 6 + 15))
    for other in (roofline, sala_counts):
        import inspect

        assert "group_size" not in inspect.signature(other.kv_read_bytes).parameters


def test_decode_bandwidth_util_reads_the_grouped_count_for_this_family_alone(counts, model):
    from types import SimpleNamespace as NS

    from perfbench import roofline, spec

    required = spec.load_module(("perfbench",), "readers", "required_work")
    unit = {"steps_dispatched": 5, "prompt_lens": [10, 10], "gen_lens": [3, 5],
            "group_size": 2, "t0": 0.0, "t1": 2.0}
    layout = {"weight_bytes": 2, "lora_rank": 0, "kv_bytes": 2}
    observed = {"peaks": {"hbm_bytes_per_s": 1e9}, "model": model, "units": [unit],
                "rollout": layout}
    args = {"what": "decode_bandwidth_util"}

    def ctx(config):
        return NS(cell=NS(paths=("perfbench",), config=config))

    weights = counts.decode_weight_bytes(model, weight_bytes=2)
    assert required.read(observed, args, ctx({"counts": "latent_moe_counts"})) == pytest.approx(
        100.0 * (5 * weights + ROW * (5 * 10 + 6 + 15)) / 1e9 / 2.0)
    # the dense decoder's counts over the same unit: a prompt a candidate, as ever
    dense = dict(hidden_size=8, num_heads=2, num_kv_heads=1, head_dim=4, intermediate_size=16,
                 vocab_size=32, num_layers=3, attention_bias=True, tie_word_embeddings=False)
    want = 5 * roofline.decode_weight_bytes(dense, weight_bytes=2, lora_rank=0) + (
        roofline.kv_read_bytes(dense, [10, 10], [3, 5], kv_bytes=2))
    assert required.read({**observed, "model": dense}, args, ctx({})) == pytest.approx(
        100.0 * want / 1e9 / 2.0)
