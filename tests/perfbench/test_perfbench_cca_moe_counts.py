"""``perfbench/cca_moe_counts.py`` against hand arithmetic at ZAYA1-8B's
published widths, layers 0-19 with every expert, and at the cell's traffic: the
yardstick's own numbers, from the shapes alone."""

import dataclasses
import inspect
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO

from perfbench import cca_moe_counts as counts

#: the cell's 12 prompts, each 16 times, and 512 decoded tokens a row
PROMPTS = [512, 652, 791, 931, 1071, 1210, 1350, 1489, 1629, 1769, 1908, 2048]
ROWS = [p for p in PROMPTS for _ in range(16)]
ANSWERS = [512] * 192


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/zaya1-8b-L20.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


def test_parameters_are_the_issues_to_the_unit(model):
    q, k, v_half, o = 2048 * 1024, 2048 * 256, 2048 * 128, 1024 * 2048
    assert counts.mixer_params(model) == q + k + 2 * v_half + o == 5_242_880   # 5.24M
    assert counts.conv_params(model) == 2 * 10 * 128 * 128 == 327_680         # 0.33M
    assert counts.router_params(model) == 2048 * 256 + 2 * 256 * 256 + 256 * 16 == 659_456
    assert counts.expert_params(model) == 3 * 2048 * 2048 == 12_582_912       # 12.58M
    small = 10 * 2048 + 2 * 1280 + 2 * 1280 + 2 + 5 * 256 + 16
    assert counts.layer_small_params(model) == small == 26_898
    layer = 5_242_880 + 327_680 + 659_456 + 16 * 12_582_912 + small
    assert layer == 207_583_506                                                 # 207.6M
    embedding = 262_272 * 2048
    assert embedding == 537_133_056                                             # tied: once
    assert counts.param_count(model) == 20 * layer + embedding + 2048 == 4_688_805_224
    assert round(counts.param_count(model) * 2 / 1e9, 2) == 9.38                # GB in bf16


def test_a_steps_bytes_are_the_issues(model):
    assert counts.expert_bytes_per_step(model) == 20 * 16 * 12_582_912 * 2 == 8_053_063_680
    # every parameter once: the tied embedding is read as the head
    assert counts.decode_weight_bytes(model) == 4_688_805_224 * 2 == 9_377_610_448
    adapter = 20 * counts.layer_lora_params(model, 32)
    assert counts.layer_lora_params(model, 32) == 32 * (
        (2048 + 1024) + (2048 + 256) + 2 * (2048 + 128) + (1024 + 2048)) == 409_600
    assert counts.decode_weight_bytes(model, lora_rank=32) == 9_377_610_448 + adapter * 4
    # 11.5 ms a step at 819 GB/s
    assert round(9_377_610_448 / 819e9 * 1e3, 1) == 11.5


def test_a_token_keeps_1024_bytes_of_pages_and_a_slot_5376_of_tail_a_layer(model):
    assert counts.kv_token_bytes(model) == 2 * 2 * 128 * 2 == 1_024
    assert counts.tail_bytes(model) == (2 * 1280 + 128) * 2 == 5_376
    assert counts.slot_state_bytes(model) == 20 * 5_376 == 107_520
    assert 192 * 107_520 == 20_643_840                                          # 21 MB of tails
    assert counts.slot_state_bytes(model, kv_bytes=4) == 2 * 107_520


def test_a_shared_prompts_pages_count_once_a_group(model):
    assert sum(PROMPTS) == 15_360
    a_row = counts.softmax_kv_bytes(model, ROWS, ANSWERS)
    a_group = counts.softmax_kv_bytes(model, ROWS, ANSWERS, group_size=16)
    tail = 512 * 513 // 2
    assert a_row == 20 * 1_024 * 16 * sum(512 * p + tail for p in PROMPTS) == 3_093_383_086_080
    assert a_group == 20 * 1_024 * sum(512 * p + 16 * tail for p in PROMPTS) == 677_463_982_080
    # a step: 6.0 GB once a row, 1.3 GB once a group
    assert round(a_row / 512 / 1e9, 1) == 6.0 and round(a_group / 512 / 1e9, 1) == 1.3
    tails = counts.tail_moved_bytes(model, ANSWERS)
    assert tails == 2 * 107_520 * 192 * 512 == 21_139_292_160
    assert counts.kv_read_bytes(model, ROWS, ANSWERS, group_size=16) == a_group + tails
    assert counts.kv_read_bytes(model, ROWS, ANSWERS) == a_row + tails
    with pytest.raises(ValueError, match="share no one prompt"):
        counts.softmax_kv_bytes(model, ROWS, ANSWERS, group_size=32)
    with pytest.raises(ValueError, match="whole number of groups"):
        counts.softmax_kv_bytes(model, ROWS[:-1], ANSWERS[:-1], group_size=16)


def test_a_trained_token_runs_one_expert(model):
    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=512, lora_rank=32)
    frozen = 5_242_880 + 327_680 + 659_456 + 12_582_912
    want = 4.0 * 537_133_056 * 0.5 + 20 * (
        4.0 * frozen + 6.0 * 409_600 + 3.0 * 2 * 2 * 1024 * 1025 / 2)
    assert got == want == 2_754_404_352.0


def test_the_counts_module_answers_the_joined_readers():
    """``required_work``, ``latent_moe_work`` and ``delta_moe_work`` read this
    cell's counts through the functions they ask a counts module for: the
    experts' bytes a step, the pages with a group's size, and a delta-rule state
    of nothing, which ``delta_moe_work`` asks for before it reads
    ``softmax_kv_bytes``."""
    for name in ("expert_bytes_per_step", "decode_weight_bytes", "kv_read_bytes",
                 "train_flops_per_token", "softmax_kv_bytes", "delta_state_bytes",
                 "kv_token_bytes", "slot_state_bytes", "param_count"):
        assert callable(getattr(counts, name)), name
    for name in ("kv_read_bytes", "softmax_kv_bytes"):
        assert "group_size" in inspect.signature(getattr(counts, name)).parameters
    assert counts.delta_state_bytes({}, ROWS, ANSWERS) == 0.0
    assert not hasattr(counts, "latent_attn_bytes") and not hasattr(counts, "index_key_bytes")


def test_the_paged_roofline_reads_these_counts_through_its_accepted_reader(model, monkeypatch):
    """``kernel.softmax_paged_roofline`` (PR 36's reader) over a made-up trace
    of 4 s of the paged launch: the pages once a GROUP at the peak."""
    from perfbench import spec
    from perfbench.readers import delta_moe_work

    metric = spec.load_layer_metric(("perfbench",), "kernel.softmax_paged_roofline")
    assert metric["reader"] == "delta_moe_work"
    monkeypatch.setattr(delta_moe_work, "matching_seconds", lambda trace, regex: 4.0)
    ctx = SimpleNamespace(cell=SimpleNamespace(
        paths=("perfbench",), config={"counts": "cca_moe_counts"}), tracer=None)
    unit = {"prompt_lens": ROWS, "gen_lens": ANSWERS, "group_size": 16}
    observed = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": model,
                "rollout": {"kv_bytes": 2, "weight_bytes": 2}, "traced_units": [unit],
                "trace": {"devices": [0]}}
    got = delta_moe_work.read(observed, metric["args"], ctx)
    assert got == pytest.approx(100.0 * 677_463_982_080 / 819e9 / 4.0)
    assert 0 < got < 100
