"""``perfbench/sala_counts.py`` and ``perfbench/readers/sala_work.py`` on a toy shape
worked by hand."""

from types import SimpleNamespace

import pytest

from perfbench import sala_counts, spec
from sala_spec import sala_benchmark

#: hidden 8; sparse: 2 q heads over 1 kv head of 4; lightning: 2 heads of 4;
#: FFN 16; vocabulary 32; layers [sparse, lightning, lightning] of a published 4
TOY = dict(
    hidden_size=8, num_heads=2, num_kv_heads=1, head_dim=4, intermediate_size=16,
    vocab_size=32, num_layers=3,
    mixer_types=("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"),
    lightning_heads=2, lightning_head_dim=4, qk_norm=True, attn_output_gate=True,
    lightning_output_gate=True, lightning_output_norm=True,
    sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=4, sparse_topk=2,
    sparse_init_blocks=1, sparse_window_size=8, sparse_dense_len=16,
)
SPARSE = 8 * 8 * 3 + 2 * 8 * 4 + 3 * 8 * 16  # q, o, gate; k, v; MLP = 640
LIGHTNING = 8 * 8 * 5 + 3 * 8 * 16  # q, k, v, o, gate; MLP = 704


def test_layers_by_hand():
    assert sala_counts.layer_kinds(TOY) == ["sparse", "lightning", "lightning"]
    assert sala_counts.layer_matmul_params(TOY, "sparse") == SPARSE == 640
    assert sala_counts.layer_matmul_params(TOY, "lightning") == LIGHTNING == 704
    # attn + mlp norms of 8, q and k norms of 4; a lightning layer's output norm of 8
    assert sala_counts.layer_norm_params(TOY, "sparse") == 24
    assert sala_counts.layer_norm_params(TOY, "lightning") == 32
    # rank 2: q 2(8+8), k and v 2(8+4) each, o 2(8+8), gate, up 2(8+16) each, down 2(16+8)
    assert sala_counts.layer_lora_params(TOY, "sparse", 2) == 32 + 48 + 32 + 96 + 48
    assert sala_counts.layer_lora_params(TOY, "lightning", 2) == 32 + 64 + 32 + 96 + 48


def test_decode_weight_bytes_by_hand():
    base = (640 + 24) + 2 * (704 + 32) + 8 * 32 + 8  # layers, head, final norm
    assert sala_counts.decode_weight_bytes(TOY) == base * 2 == 4800
    assert sala_counts.decode_weight_bytes(TOY, lora_rank=2) == 4800 + (256 + 2 * 272) * 4


@pytest.mark.parametrize("t,tokens,blocks", [
    (0, 1, 1), (15, 16, 4),  # a context of at most 16: every token
    (16, 17, 5),  # blocks 0 and 2-4 forced (2 overlaps tokens 9..16), block 1 the rest
    (30, 23, 6),  # block 0, blocks 5-7 (23..30), 2 of the 4 others: 5x4 + 3
    (31, 20, 5),  # block 0, blocks 6 and 7 (24..31), 2 of the 5 others: 4x4 + 4
])
def test_attended_tokens_by_hand(t, tokens, blocks):
    assert sala_counts.attended_tokens(TOY, t) == (tokens, blocks)


def test_bytes_a_decode_needs_by_hand():
    # a row of prompt 28 decoding 3 tokens, at positions 28, 29, 30
    attended = [sala_counts.attended_tokens(TOY, t)[0] for t in (28, 29, 30)]
    assert attended == [21, 22, 23]  # 6 blocks, the last one 1, 2, 3 tokens deep
    # pooled keys that end at or before t: (t + 1 - 4) // 2 + 1
    assert [sala_counts.pooled_seen(TOY, t) for t in (2, 3, 28, 29, 30)] == [0, 1, 13, 14, 14]
    # one sparse layer, kv_dim 4, bf16: K and V of the attended tokens, the pooled keys once
    sparse = 1 * 4 * 2 * (2 * (21 + 22 + 23) + (13 + 14 + 14))
    assert sala_counts.sparse_attn_bytes(TOY, [28], [3]) == sparse == 1384
    # two lightning layers, 2 heads of 4x4 float32, read and written, 3 steps
    state = 3 * 2 * 2 * (2 * 4 * 4 * 4)
    assert sala_counts.linear_attn_bytes(TOY, [28], [3]) == state == 1536
    assert sala_counts.kv_read_bytes(TOY, [28], [3]) == sparse + state
    assert sala_counts.kv_read_bytes(TOY, [28, 0], [3, 0]) == sparse + state


def test_train_flops_by_hand():
    # rows of 8 tokens (dense: a query at t attends t + 1), 4 scored, rank 2
    mean_attended = sum(range(1, 9)) / 8.0  # 4.5
    sparse = 4 * 640 + 6 * 256 + 3 * (4 * 8 * mean_attended)
    lightning = 4 * 704 + 6 * 272 + 3 * (4 * 8 * 4)
    head = 4 * 8 * 32 * (4 / 8)
    assert sala_counts.train_flops_per_token(
        TOY, seq_len=8, answer_len=4, lora_rank=2) == sparse + 2 * lightning + head


def test_the_reader_divides_what_is_needed_by_what_was_taken(monkeypatch):
    reader = spec.load_module(sala_benchmark()["paths"], "readers", "sala_work")
    ctx = SimpleNamespace(cell=SimpleNamespace(
        paths=("perfbench",), config={"counts": "sala_counts"}))
    unit = {"prompt_lens": [28], "gen_lens": [3]}
    observed = {"peaks": {"hbm_bytes_per_s": 1e4}, "model": TOY,
                "rollout": {"kv_bytes": 2}, "traced_units": [unit, unit]}
    # the traced rounds' decode spent 0.5 s under the scope the metric names
    monkeypatch.setattr(reader.trace_scopes, "seconds_in_spans", lambda ctx, scope, span: 0.5)
    args = {"scope": "^model/linear_attn$", "span": "engine/decode"}
    assert reader.read(observed, {"what": "linear_attn_roofline", **args}, ctx) == (
        pytest.approx(100.0 * 2 * 1536 / 1e4 / 0.5))
    assert reader.read(observed, {"what": "sparse_attn_roofline", **args}, ctx) == (
        pytest.approx(100.0 * 2 * 1384 / 1e4 / 0.5))
    # nothing traced, another family's counts, no run: nothing to read
    monkeypatch.setattr(reader.trace_scopes, "seconds_in_spans", lambda ctx, scope, span: None)
    assert reader.read(observed, {"what": "linear_attn_roofline", **args}, ctx) is None
    dense = SimpleNamespace(cell=SimpleNamespace(paths=("perfbench",), config={}))
    assert reader.read(observed, {"what": "linear_attn_roofline", **args}, dense) is None
    assert reader.read({}, {"what": "sparse_attended_share"}, None) is None


def test_the_counters_give_the_attended_share():
    from distrl_llm_tpu import telemetry

    reader = spec.load_module(sala_benchmark()["paths"], "readers", "sala_work")
    ctx = SimpleNamespace(cell=SimpleNamespace(paths=("perfbench",), config={}))
    args = {"what": "sparse_attended_share", "attended": "test/sala_attended",
            "visible": "test/sala_visible"}
    assert reader.read({}, args, ctx) is None  # a program that never counted
    telemetry.counter_add("test/sala_attended", 97 * 4)
    telemetry.counter_add("test/sala_visible", 250 * 4)
    assert reader.read({}, args, ctx) == pytest.approx(38.8)
