"""The yardstick's arithmetic on cases worked by hand."""

import os

import pytest

from perfbench import roofline

#: a model small enough to count on paper: hidden 8, 2 q heads and 1 kv head of
#: 4, FFN 16, vocabulary 32, 3 layers
TOY = dict(hidden_size=8, num_heads=2, num_kv_heads=1, head_dim=4,
           intermediate_size=16, vocab_size=32, num_layers=3,
           attention_bias=True, tie_word_embeddings=False)
LAYER = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16  # q, k+v, o, gate+up+down = 576


def test_layer_counts():
    assert roofline.layer_matmul_params(TOY) == LAYER == 576
    # rank 2: q 2(8+8) + k,v 2*2(8+4) + o 2(8+8) + gate,up 2*2(8+16) + down 2(16+8)
    assert roofline.layer_lora_params(TOY, 2) == 32 + 48 + 32 + 96 + 48 == 256


def test_decode_weight_bytes_by_hand():
    # per layer: 576 weights + 2 norms of 8 + biases (8 + 2*4) = 608; head 8*32;
    # final norm 8. bf16: 2 bytes. The untied embedding is not read.
    base = 3 * 608 + 256 + 8
    assert roofline.decode_weight_bytes(TOY) == base * 2 == 4176
    # plus a rank-2 adapter held in float32
    assert roofline.decode_weight_bytes(TOY, lora_rank=2) == 4176 + 3 * 256 * 4


def test_decode_step_bytes_and_roofline_by_hand():
    # KV of one token: k and v, 1 head of 4, bf16, 3 layers = 2*4*2*3 = 48 bytes
    assert roofline.kv_bytes_per_token(TOY) == 48
    # 10 rows at 100 tokens: 4176 + 10*100*48
    assert roofline.decode_step_bytes(TOY, rows=10, mean_context=100) == 4176 + 48000
    # a paged kernel reads whole pages: 100 tokens are 2 pages of 64
    assert roofline.decode_step_bytes(
        TOY, rows=10, mean_context=100, page_size=64) == 4176 + 10 * 128 * 48
    tok_s = roofline.decode_roofline_tok_s(
        TOY, rows=10, mean_context=100, hbm_bytes_per_s=52176.0)
    assert tok_s == pytest.approx(10.0)  # one step a second, ten tokens a step


def test_kv_read_bytes_by_hand():
    # prompt 3, 2 tokens decoded: contexts 3+1 and 3+2 = 9 token reads; a second
    # row with prompt 0 and 3 tokens: 1+2+3 = 6
    assert roofline.kv_read_bytes(TOY, [3, 0], [2, 3]) == (9 + 6) * 48


def test_train_flops_per_token_by_hand():
    # per layer: 4*576 (forward + backward to activations) + 6*256 (adapter) +
    # 3 * attention forward, where attention forward = 4 * q_dim 8 * (16/2) = 256
    per_layer = 4 * 576 + 6 * 256 + 3 * 256
    # head: 4 * 8 * 32 on the 12 scored positions of 16
    want = 3 * per_layer + 4 * 8 * 32 * 12 / 16
    got = roofline.train_flops_per_token(TOY, seq_len=16, answer_len=12, lora_rank=2)
    assert got == want == 14592.0


def test_it_is_less_than_three_forwards():
    """The count this replaces (3 x forward) charges base-weight gradient
    matmuls that LoRA training never runs."""
    from distrl_llm_tpu.models import QWEN2_7B
    import dataclasses

    sizes = dataclasses.asdict(QWEN2_7B)
    ours = roofline.train_flops_per_token(sizes, seq_len=1024, answer_len=1024, lora_rank=32)
    assert ours < QWEN2_7B.train_flops_per_token(1024)
    assert ours > (2.0 / 3.0) * QWEN2_7B.train_flops_per_token(1024)


def test_peaks_table():
    v5e = roofline.peaks_for_kind("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "cloud.google.com" in v5e["source"]
    with pytest.raises(KeyError, match="not in perfbench/peaks.json"):
        roofline.peaks_for_kind("TPU v99")


def test_7b_l14_numbers_the_records_quote():
    """PERF.md and the issue quote these; they come from this arithmetic."""
    from perfbench import assembly, spec

    cfg = assembly.model_config(spec.load_json(
        os.path.join(spec.ROOT, "perfbench", "configs", "qwen2.5-7b-L14.json")))
    sizes = assembly.model_sizes(cfg)
    assert roofline.layer_matmul_params(sizes) == 233_046_016
    assert roofline.decode_weight_bytes(sizes) / 1e9 == pytest.approx(7.62, abs=0.01)
    assert roofline.kv_bytes_per_token(sizes) == 28_672
    flops = roofline.train_flops_per_token(sizes, seq_len=1024, answer_len=768, lora_rank=32)
    assert flops / 1e9 == pytest.approx(15.2, abs=0.1)
