"""``perfbench/swa_sink_moe_counts.py`` (the ``counts`` module of
``mimo-v2-flash-ep16-L7``) against the program's own tree and the issue's
arithmetic: parameters to the unit, the two caches at their two widths, a shared
prompt's pages once a GROUP, the operations of a trained token."""

import dataclasses
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from tiny_spec import REPO

from perfbench import swa_sink_moe_counts as counts
from perfbench import window_moe_counts


def _model(name="mimo-v2-flash-ep16-L7"):
    from distrl_llm_tpu.models import ModelConfig

    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return ModelConfig.from_hf_config(SimpleNamespace(**json.load(f)))


@pytest.fixture(scope="module")
def cell():
    cfg = _model()
    return cfg, dataclasses.asdict(cfg)


def test_parameters_are_the_programs_tree_to_the_unit(cell):
    from distrl_llm_tpu.models import init_lora_params, init_params

    cfg, model = cell
    tree = jax.eval_shape(lambda key: init_params(key, cfg, dtype=jnp.bfloat16),
                          jax.random.PRNGKey(0))
    held = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert counts.param_count(model) == held == 3_429_955_392
    lora = jax.eval_shape(lambda key: init_lora_params(key, cfg, 32), jax.random.PRNGKey(1))
    adapters = sum(x.size for x in jax.tree_util.tree_leaves(lora))
    kinds = counts.layer_kinds(model)
    assert kinds == [("full", "dense")] + [("window", "experts")] * 4 + [
        ("full", "experts"), ("window", "experts")]
    assert adapters == sum(counts.layer_lora_params(model, m, f, 32) for m, f in kinds)
    # the issue's arithmetic: attention 89.13M (full), 94.37M (window); the cut 6.86 GB
    assert counts.mixer_params(model, "full") == 89_128_960
    assert counts.mixer_params(model, "window") == 94_371_840
    # a decode step reads everything but the embedding, and the adapter in float32
    embed = 19072 * 4096
    assert counts.decode_weight_bytes(model) == 2 * (held - embed)
    assert counts.decode_weight_bytes(model, lora_rank=32) == 2 * (held - embed) + 4 * adapters
    assert counts.expert_bytes_per_step(model) == 6 * 16 * 3 * 4096 * 2048 * 2


def test_the_two_caches_are_counted_at_their_two_widths(cell):
    _, model = cell
    assert counts.kv_token_bytes(model) == 4 * (192 + 128) * 2 == 2560
    assert counts.kv_token_bytes(model, mixer="window") == 8 * (192 + 128) * 2
    assert counts.cache_token_bytes(model) == 2 * 2560
    assert counts.ring_bytes(model) == 655_360 and counts.slot_state_bytes(model) == 5 * 655_360
    # a model whose V is as wide as its K and whose kinds share their heads counts
    # as the first window family's module counts it
    exaone = dataclasses.asdict(_model("k-exaone-236b-ep8-L5"))
    for fn in ("kv_token_bytes", "ring_bytes", "slot_state_bytes"):
        assert getattr(counts, fn)(exaone) == getattr(window_moe_counts, fn)(exaone), fn
    rows = ([10240] * 2 + [20480] * 2, [512, 500, 512, 512])
    assert counts.softmax_kv_bytes(exaone, *rows) == window_moe_counts.softmax_kv_bytes(
        exaone, *rows)
    assert counts.window_kv_bytes(exaone, *rows) == window_moe_counts.window_kv_bytes(
        exaone, *rows)
    assert counts.window_pages(model, *rows) == (5 * 4 * 512 - 5 * 12, 5 * sum(
        -(-(p + j) // 128) for p, g in zip(*rows) for j in range(1, g + 1)))


def test_a_shared_prompts_pages_are_read_once_a_group(cell):
    """The cell's round: 8 prompts x 16 candidates x 512 tokens. Once a row the
    full layers read 10.2 GB a step, once a group 0.8 GB; the rings 0.42 GB."""
    _, model = cell
    prompts = [10240, 11703, 13166, 14629, 16091, 17554, 19017, 20480]
    rows = [p for p in prompts for _ in range(16)], [512] * 128
    tail = 128 * 512 * 513 // 2
    a_row = counts.softmax_kv_bytes(model, *rows)
    a_group = counts.softmax_kv_bytes(model, *rows, group_size=16)
    assert a_row == 2 * 2560 * (16 * 512 * sum(prompts) + tail)
    assert a_group == 2 * 2560 * (512 * sum(prompts) + tail)
    assert 10.2e9 < a_row / 512 < 10.3e9 and 0.79e9 < a_group / 512 < 0.80e9
    rings = counts.window_kv_bytes(model, *rows)
    assert rings == 5 * 8 * (192 + 128) * 2 * 128 * 512 * 128 and rings / 512 == 419_430_400
    assert counts.kv_read_bytes(model, *rows, group_size=16) == a_group + rings
    assert counts.kv_read_bytes(model, *rows) == a_row + rings
    assert counts.delta_state_bytes(model, *rows) == 0.0
    # the harness tells ``group_size`` to a function whose signature has it
    from perfbench.readers.required_work import cache_bytes

    unit = {"prompt_lens": rows[0], "gen_lens": rows[1], "group_size": 16}
    assert cache_bytes(counts.softmax_kv_bytes, model, unit, kv_bytes=2) == a_group
    assert cache_bytes(counts.delta_state_bytes, model, unit, kv_bytes=2) == 0.0
    # a group's rows end apart: the prompt is read for as long as the longest runs
    uneven = counts.softmax_kv_bytes(model, [100] * 4, [3, 9, 1, 4], group_size=4)
    assert uneven == 2 * 2560 * (9 * 100 + 6 + 45 + 1 + 10)
    with pytest.raises(ValueError, match="share no one prompt"):
        counts.softmax_kv_bytes(model, [100, 101], [1, 1], group_size=2)
    with pytest.raises(ValueError, match="no whole number of groups"):
        counts.softmax_kv_bytes(model, [100] * 3, [1] * 3, group_size=2)


def test_a_trained_tokens_operations_count_both_widths_and_this_chips_experts(cell):
    _, model = cell
    at = dict(seq_len=1024, answer_len=512, lora_rank=32)
    got = counts.train_flops_per_token(model, **at)
    keys = {"full": 512.5, "window": 128.0}
    want = 4.0 * 4096 * 19072 * 0.5
    for mixer, ffn in counts.layer_kinds(model):
        ffn_params = 3 * 4096 * 16384 if ffn == "dense" else (
            3 * 4096 * 2048 * (8 * 16 / 256) + 4096 * 256)
        want += (4.0 * (counts.mixer_params(model, mixer) + ffn_params)
                 + 6.0 * counts.layer_lora_params(model, mixer, ffn, 32)
                 + 3.0 * 2.0 * 64 * (192 + 128) * keys[mixer])
    assert got == pytest.approx(want, rel=1e-12)
    for name in ("delta_state_bytes", "softmax_kv_bytes", "expert_bytes_per_step",
                 "decode_weight_bytes", "kv_read_bytes", "train_flops_per_token"):
        assert callable(getattr(counts, name)), name
