"""``perfbench/scmoe_counts.py`` against hand-worked arithmetic at the published
widths (LongCat-Flash-Chat as one of 32 chips a layer, layers 0-3): the issue's
own numbers, digit for digit."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from tiny_spec import REPO


@pytest.fixture(scope="module")
def model():
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/longcat-flash-ep32-L4.json")) as f:
        return dataclasses.asdict(ModelConfig.from_hf_config(SimpleNamespace(**json.load(f))))


@pytest.fixture(scope="module")
def counts():
    from perfbench import scmoe_counts

    return scmoe_counts


# 6144x1536 + 1536x12288 + 6144x576 + 512x16384 + 8192x6144 = 90.57M
ATTN = 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
MLP = 3 * 6144 * 12288  # 226.49M
EXPERT = 3 * 6144 * 2048  # 37.75M
ROUTER = 6144 * 768  # 4.72M
SMALL = 2 * (2 * 6144 + 1536 + 512) + 768


def test_a_layer_is_two_attentions_two_mlps_a_router_and_the_experts_held(counts, model):
    assert counts.attention_params(model) == ATTN == 90_570_752
    assert counts.mlp_params(model) == MLP == 226_492_416
    assert counts.expert_params(model) == EXPERT == 37_748_736
    assert counts.router_params(model) == ROUTER
    outside = 2 * ATTN + 2 * MLP + ROUTER
    assert 638.7e6 < outside < 638.9e6  # the issue's 638.8M outside the experts
    assert counts.layer_params(model, 16) == outside + 16 * EXPERT + SMALL
    ends = 2 * 6144 * 16384 + 6144
    assert counts.param_count(model) == 4 * (outside + 16 * EXPERT + SMALL) + ends
    assert 10.34e9 < 2 * counts.param_count(model) < 10.36e9  # the issue's 10.35 GB


def test_a_token_runs_eight_experts_and_a_step_reads_the_held_ones_that_have_a_pair(
        counts, model):
    assert counts.expert_choices_run(model) == 12 * 512 / 768 == 8.0
    assert counts.expert_flops_per_token(model) == 4 * 8 * 2 * EXPERT
    # 3,072 pairs a step over 768 outputs: an expert is left out with e^-4
    read = counts.held_experts_read(model)
    assert read == pytest.approx(16 * (1 - (767 / 768) ** 3072)) and 15.69 < read < 15.72
    assert counts.expert_bytes_per_step(model) == pytest.approx(4 * read * EXPERT * 2)
    assert 4.7e9 < counts.expert_bytes_per_step(model) < 4.84e9  # the issue's 4.8 GB
    assert counts.held_experts_read(model, rows=10 ** 6) == pytest.approx(16.0)
    # a model that states no share counts its own experts: the uncut router
    uncut = {**model, "router_experts": 0, "n_routed_experts": 512}
    assert counts.expert_choices_run(uncut) == 8.0


def test_a_decode_step_reads_both_sublayers_and_the_head(counts, model):
    base = 4 * (2 * ATTN + 2 * MLP + ROUTER + SMALL) + 6144 * 16384 + 6144
    want = 2 * base + counts.expert_bytes_per_step(model)
    assert counts.decode_weight_bytes(model, weight_bytes=2) == pytest.approx(want)
    # the issue's step: 3.6 GB of dense MLPs, 1.45 GB of attention
    assert 3.6e9 < 4 * 2 * MLP * 2 < 3.65e9 and 1.44e9 < 4 * 2 * ATTN * 2 < 1.46e9
    lora = 32 * 4 * 2 * (
        (6144 + 1536) + (1536 + 12288) + (6144 + 576) + (512 + 16384) + (8192 + 6144)
        + 3 * (6144 + 12288))
    assert counts.layer_lora_params(model, 32) * 4 == lora
    with_adapter = counts.decode_weight_bytes(model, weight_bytes=2, lora_rank=32)
    assert with_adapter - want == pytest.approx(4 * lora)


def test_the_cache_read_is_one_row_of_576_values_a_token_a_sublayer(counts, model):
    # one row decoding 3 tokens after a prompt of 10: contexts 11, 12, 13, in 8 pools
    assert counts.kv_read_bytes(model, [10], [3], kv_bytes=2) == 8 * 576 * 2 * 36
    assert counts.latent_attn_bytes is counts.kv_read_bytes
    assert counts.latent_attn_flops_per_cached_token(model) == 64 * 2 * (576 + 512)
    # the cell's round: 16 prompts of 512-2,048 x 16, 512 steps; a prompt once a group
    prompts = [round(512 + i * 1536 / 15) for i in range(16)]
    grouped = counts.kv_read_bytes(
        model, [p for p in prompts for _ in range(16)], [512] * 256, kv_bytes=2, group_size=16)
    tokens = sum(512 * p for p in prompts) + 256 * 512 * 513 // 2
    assert grouped == 8 * 576 * 2 * tokens
    assert 0.75e9 < grouped / 512 < 0.85e9  # under a gigabyte of latent rows a step
    alone = counts.kv_read_bytes(
        model, [p for p in prompts for _ in range(16)], [512] * 256, kv_bytes=2)
    assert alone > 4 * grouped
    with pytest.raises(ValueError, match="share no one prompt"):
        counts.kv_read_bytes(model, [10, 11], [3, 3], group_size=2)
    with pytest.raises(ValueError, match="no whole number of groups"):
        counts.kv_read_bytes(model, [10] * 3, [3] * 3, group_size=2)


def test_training_counts_the_experts_a_token_runs_and_both_attentions(counts, model):
    got = counts.train_flops_per_token(model, seq_len=1024, answer_len=768, lora_rank=0)
    run = 4 * (2 * ATTN + 2 * MLP + ROUTER + 8 * EXPERT)
    mixer = 2.0 * (12288 + 8192) * 512.5
    want = 4.0 * run + 4 * 2 * 3.0 * mixer + 4.0 * 6144 * 16384 * 0.75
    assert got == pytest.approx(want)
    with_adapter = counts.train_flops_per_token(
        model, seq_len=1024, answer_len=768, lora_rank=32)
    assert with_adapter - got == pytest.approx(6.0 * 4 * counts.layer_lora_params(model, 32))


def test_the_programs_own_count_agrees(counts, model):
    """``ModelConfig``'s counts for the telemetry series are the yardstick's."""
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench/configs/longcat-flash-ep32-L4.json")) as f:
        cfg = ModelConfig.from_hf_config(SimpleNamespace(**json.load(f)))
    matmuls = 4 * (2 * ATTN + 2 * MLP + ROUTER) + 6144 * 16384
    assert cfg.total_matmul_param_count == matmuls + 4 * 16 * EXPERT
    assert cfg.matmul_param_count == matmuls + 4 * 8 * EXPERT
    # a decoded token attends in EIGHT sublayers
    assert cfg.decode_flops_per_token(1000.0) == (
        2.0 * cfg.matmul_param_count + 4.0 * 8 * 12288 * 1000.0)


def test_the_readers_read_this_cell_through_its_own_counts(counts, model, monkeypatch):
    """``latent_moe_work`` finds ``expert_bytes_per_step`` and
    ``latent_attn_bytes`` here (a prompt's rows once a group), and
    ``delta_moe_work`` the new counter beside ``engine/moe_pairs_routed``."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.engine import paged_engine
    from perfbench import spec, trace_scopes
    from tiny_spec import real_benchmark

    bench = real_benchmark()
    cell = spec.load_cell(bench, "longcat-flash-ep32-L4.rollout-reasoning-zero-256")
    ctx = SimpleNamespace(cell=cell, tracer=None)
    paths = cell.paths
    unit = {"prompt_lens": [512] * 16 + [2048] * 16, "gen_lens": [512] * 32,
            "group_size": 16, "steps_dispatched": 512}
    observed = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": model,
                "rollout": {"kv_bytes": 2, "weight_bytes": 2}, "traced_units": [unit]}
    seconds = {"^model/moe_experts$": 4.0, "^model/latent_attn$": 1.0}
    monkeypatch.setattr(trace_scopes, "seconds_in_spans", lambda ctx, scope, span: (
        seconds.get(scope) if span == "engine/decode" else None))
    reader = spec.load_module(paths, "readers", "latent_moe_work")
    experts = spec.load_layer_metric(paths, "kernel.moe_experts_roofline")
    got = reader.read(observed, experts["args"], ctx)
    assert got == pytest.approx(100 * 512 * counts.expert_bytes_per_step(model) / 819e9 / 4.0)
    attn = spec.load_layer_metric(paths, "kernel.latent_attn_roofline")
    needed = counts.latent_attn_bytes(
        model, unit["prompt_lens"], unit["gen_lens"], kv_bytes=2, group_size=16)
    assert reader.read(observed, attn["args"], ctx) == pytest.approx(100 * needed / 819e9 / 1.0)
    share = spec.load_layer_metric(paths, "engine.zero_expert_share")
    assert share["reader"] == "delta_moe_work"
    assert share["args"] == {"what": "expert_held_share",
                             "held": paged_engine.ENGINE_MOE_PAIRS_ZERO,
                             "routed": paged_engine.ENGINE_MOE_PAIRS_ROUTED}
    said = {"counters": {}}
    monkeypatch.setattr(telemetry, "observe_snapshot", lambda: said)
    counter = spec.load_module(paths, "readers", "delta_moe_work")
    assert counter.read({}, share["args"], ctx) is None  # the parent: no such counter
    said["counters"] = {paged_engine.ENGINE_MOE_PAIRS_ROUTED: 3072.0 * 4 * 512,
                        paged_engine.ENGINE_MOE_PAIRS_ZERO: 1024.0 * 4 * 512}
    assert counter.read({}, share["args"], ctx) == pytest.approx(100 / 3)
    zero = spec.load_layer_metric(paths, "model.moe_zero_share")
    assert zero["reader"] == "trace_scopes"
    assert zero["args"] == {"scope": f"^{telemetry.MODEL_MOE_ZERO}$", "of": "busy"}
