"""CLI flag-parity tests: every reference flag exists with the reference
default (train_distributed.py:10–35 — the README.md:48–61 CLI contract)."""

import pytest

from train_distributed import build_parser, config_from_args

REFERENCE_DEFAULTS = {
    "model": "Qwen/Qwen2.5-7B-Instruct",
    "dataset": "HuggingFaceH4/MATH-500",
    "project_name": "math-reasoning",
    "lora_save_path": "lora_request_math",
    "lr": 2e-5,
    "max_new_tokens": 1200,
    "max_prompt_tokens": 350,
    "temperature": 1.2,
    "episodes": 15,
    "num_candidates": 16,
    "batch_size": 30,
    "learner_chunk_size": 8,
    "train_batch_size": 8,
    "save_every": 100,
    "eval_every": 10,
    "number_of_actors": 2,
    "number_of_learners": 1,
    "learner": "pg",
    "max_lora_rank": 32,
    "lora_alpha": 16,
    "lora_dropout": 0.0,
    "topk": 16,
    "actor_gpu_usage": 0.91,
    "learner_gpu_usage": 0.35,
}


def test_reference_flags_and_defaults():
    args = build_parser().parse_args([])
    for flag, default in REFERENCE_DEFAULTS.items():
        assert getattr(args, flag) == default, flag


def test_config_roundtrip():
    args = build_parser().parse_args(
        ["--learner", "grpo", "--number_of_actors", "4", "--tp", "2",
         "--batch_size", "16"]
    )
    cfg = config_from_args(args)
    assert cfg.learner == "grpo"
    assert cfg.batch_size == 16
    assert cfg.mesh.number_of_actors == 4
    assert cfg.mesh.tp == 2
    assert cfg.max_seq_length == 1550  # 350 + 1200 (distributed_actor.py:25)


def test_learner_len_buckets_flag():
    args = build_parser().parse_args(["--learner_len_buckets", "256,512"])
    assert config_from_args(args).learner_len_buckets == (256, 512)


def test_trace_flags():
    args = build_parser().parse_args(
        ["--trace-dir", "out/tr", "--trace-steps", "3"]
    )
    cfg = config_from_args(args)
    assert cfg.trace_dir == "out/tr"
    assert cfg.trace_steps == 3
    # underscore spellings stay accepted (repo flag-style consistency)
    args = build_parser().parse_args(["--trace_dir", "out2"])
    assert config_from_args(args).trace_dir == "out2"


def test_invalid_learner_rejected():
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--learner", "ppo"])


def test_rollout_mode_flags():
    args = build_parser().parse_args(
        ["--rollout_mode", "async", "--max_staleness", "4",
         "--clip_ratio", "0.2", "--staleness_policy", "downweight",
         "--rollout_buffer_groups", "64"]
    )
    cfg = config_from_args(args)
    assert cfg.rollout_mode == "async"
    assert cfg.max_staleness == 4
    assert cfg.staleness_policy == "downweight"
    assert cfg.rollout_buffer_groups == 64
    assert cfg.allowed_weight_lag == 4
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--rollout_mode", "turbo"])


def test_async_rollout_alias_selects_pipelined():
    # the deprecated spelling keeps working: one-step overlap
    args = build_parser().parse_args(["--async_rollout"])
    cfg = config_from_args(args)
    assert cfg.rollout_mode == "pipelined"
    assert cfg.async_rollout is True
    # and the default is the reference's synchronous loop
    assert config_from_args(build_parser().parse_args([])).rollout_mode == "sync"


def test_workers_capture_logprobs_gate():
    from distrl_llm_tpu.config import TrainConfig

    with pytest.raises(ValueError, match="capture-logprobs"):
        TrainConfig(model="t", clip_ratio=0.2,
                    rollout_workers=("h:1",))
    cfg = config_from_args(build_parser().parse_args(
        ["--clip_ratio", "0.2", "--rollout_workers", "h:1",
         "--workers_capture_logprobs"]
    ))
    assert cfg.workers_capture_logprobs


class TestReadmeBaselineCommands:
    """The README's five reference-recipe commands must parse into valid
    TrainConfigs — documentation that cannot rot."""

    CMDS = [
        "--model /ckpts/Qwen2.5-0.5B-Instruct --learner pg "
        "--number_of_actors 1 --number_of_learners 1",
        "--model /ckpts/Qwen2.5-7B-Instruct --learner grpo "
        "--number_of_actors 2 --number_of_learners 1 --engine_impl paged "
        "--max_concurrent_sequences 128 --continuous_batching --spec_draft 4 "
        "--kv_cache_quant int8 --tp 2",
        "--model /ckpts/Meta-Llama-3-8B-Instruct --dataset openai/gsm8k "
        "--learner grpo --full_finetune --fsdp 4",
        "--model /ckpts/DeepSeek-R1-Distill-Qwen-7B --learner grpo "
        "--max_new_tokens 4096 --engine_impl paged "
        "--max_concurrent_sequences 64 --continuous_batching "
        "--attn_impl ring --sp 4 --logprob_chunk 256",
        "--model /ckpts/Qwen2.5-72B-Instruct --learner grpo --tp 4 --fsdp 8 "
        "--rollout_workers host1:7201,host2:7201",
    ]

    @pytest.mark.parametrize("cmd", CMDS)
    def test_baseline_config_command_parses(self, cmd):
        import shlex

        from train_distributed import build_parser, config_from_args

        cfg = config_from_args(build_parser().parse_args(shlex.split(cmd)))
        assert cfg.model

    def test_commands_match_readme(self):
        """Every flag string tested above appears verbatim in README.md."""
        readme = open("README.md").read().replace("\\\n", " ")
        squashed = " ".join(readme.split())
        for cmd in self.CMDS:
            for token in cmd.split():
                assert token in squashed, f"{token} not in README"


def test_quantized_serving_flags():
    """ISSUE 15: kv_cache_quant unset = plan-DB-resolvable (None), explicit
    values (including none) pin; quant_group_size rides base_quant."""
    cfg = config_from_args(build_parser().parse_args([]))
    assert cfg.kv_cache_quant is None  # unset → the plan DB decides
    cfg = config_from_args(
        build_parser().parse_args(["--kv_cache_quant", "none"])
    )
    assert cfg.kv_cache_quant == "none"  # an explicit pin, not "unset"
    cfg = config_from_args(build_parser().parse_args(
        ["--base_quant", "int4", "--quant_group_size", "32"]
    ))
    assert cfg.base_quant == "int4"
    assert cfg.quant_group_size == 32


def test_quant_group_size_requires_base_quant():
    import pytest

    with pytest.raises(ValueError, match="quant_group_size"):
        config_from_args(
            build_parser().parse_args(["--quant_group_size", "32"])
        )


def test_worker_quant_flag_parity():
    """The ISSUE-15 satellite: worker_main must express the driver's base
    quantization on the serve path (GC401) with agreeing defaults/types
    (GC402)."""
    import pytest

    from distrl_llm_tpu.distributed.worker_main import main as worker_main

    # parser-level dead-flag rejection, mirroring the driver's validation
    with pytest.raises(SystemExit):
        worker_main(["--quant-group-size", "32"])  # needs --base-quant
    # a tiny worker engine over an int4 base builds and quantizes
    import distrl_llm_tpu.distributed.worker_main as wm

    wm._init_engine("tiny", 8, 8, 0, engine_impl="dense",
                    base_quant="int4", quant_group_size=16)
    try:
        from distrl_llm_tpu.ops.quant import is_quantized_tree

        assert is_quantized_tree(wm._ENGINE_STATE["params"])
    finally:
        wm._ENGINE_STATE.clear()
