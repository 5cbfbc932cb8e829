"""The system under test, built from a cell's files through the program's own
constructors: ``ModelConfig.from_hf_config``, ``TrainConfig``,
``engine_kwargs_from_config`` and the two engine classes, ``build_role_meshes``.

This is a copy of the local-engine half of ``Trainer.from_pretrained`` (and of
``train_distributed.run_smoke``, the only assembly proven on the chip), with
seeded weights in place of a checkpoint. What the harness sets against the
CLI's defaults, and why:

* ``autotune=False``: no plan database outside the checkout is read.
* ``capture_logprobs=True`` on every engine: the correctness check reads the
  engine's own log-probabilities of the tokens it sampled. It is what a run
  with ``--clip_ratio`` captures; the fused sampler computes it in the kernel.
* no ``max_kv_pages`` budget: the pool is the worst case for the slots, as in
  ``run_smoke``. At these sizes the CLI's budget is larger than that.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Any

import numpy as np


def model_config(config_file: dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, which holds the
    published ``config.json`` keys as they are run."""
    from distrl_llm_tpu.models import ModelConfig

    return ModelConfig.from_hf_config(SimpleNamespace(**config_file))


def model_sizes(model_cfg) -> dict[str, Any]:
    """``model_cfg`` as the plain mapping ``roofline.py`` counts from."""
    return dataclasses.asdict(model_cfg)


def train_config(traffic: dict[str, Any], seed: int, dtype: str):
    """``TrainConfig`` with the traffic file's ``train_config`` over the CLI's
    defaults. Nothing is written or uploaded; no step is evaluated or saved."""
    from distrl_llm_tpu.config import TrainConfig

    fields = dict(traffic.get("train_config", {}))
    return TrainConfig(
        **fields, seed=seed, dtype=dtype, autotune=False, metrics_backend="null",
        eval_every=0, save_every=0, print_samples=False,
    )


def eos_ids(traffic: dict[str, Any], vocab_size: int, seed: int,
            real_eos: int) -> list[int]:
    """The ids that end an answer, as the traffic file chooses:

    * ``"eos": "never"``: none does. Every answer runs to the cap and a round
      is the same work under every seed (``rollout-lockstep``).
    * ``"eos_rate": r``: a seeded random subset covering ``r`` of the
      vocabulary. A random-weight policy then stops geometrically with mean
      1/r, the one way to draw a spread of answer lengths from an engine that
      takes a single cap for a round. The round's
      work is then the draw's, and tok/s swings with the seed by 3.4% (my chip
      runs, PR 23): traffic for a per-layer look at the scheduler, which the
      tiny rehearsal cell runs, and for no end-to-end bound.
    * neither: the tokenizer's one real EOS id, which random weights all but
      never sample."""
    if traffic.get("eos") == "never":
        return [-1]  # no id matches
    rate = float(traffic.get("eos_rate", 0.0))
    if rate <= 0.0:
        return [real_eos]
    n = max(1, round(rate * vocab_size))
    rng = np.random.default_rng(seed)
    return sorted(int(i) for i in rng.choice(vocab_size, size=n, replace=False))


def build_engine(config, model_cfg, *, eos: list[int], pad_id: int):
    """The rollout engine ``config`` names, as ``Trainer.from_pretrained``
    builds a local one."""
    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.trainer import engine_kwargs_from_config

    if config.engine_impl not in ("dense", "paged"):
        raise ValueError(f"no cell drives engine_impl={config.engine_impl!r} yet")
    engine_cls = (
        PagedGenerationEngine if config.engine_impl == "paged" else GenerationEngine
    )
    kwargs = engine_kwargs_from_config(config)
    kwargs["capture_logprobs"] = True
    return engine_cls(
        model_cfg,
        max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=eos, pad_token_id=pad_id,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
        attn_impl=config.attn_impl,
        prompt_buckets=config.prompt_buckets or None,
        **kwargs,
    )


def seeded_prompts(rng: np.random.Generator, *, rows: int, width: int,
                   min_len: int, max_len: int, vocab_size: int, pad_id: int):
    """``rows`` prompts of random token ids, LEFT-padded to ``width`` (the
    engines' contract). The lengths are the same in every round and for every
    seed, evenly spaced over [min_len, max_len]; the seed decides which prompt
    gets which, and the tokens. So every round is the same amount of work:
    with lengths drawn at random a round's time moved 3% with the draw (my chip
    runs, PR 23)."""
    lens = np.round(np.linspace(min_len, max_len, rows)).astype(int)
    rng.shuffle(lens)
    ids = np.full((rows, width), pad_id, np.int32)
    mask = np.zeros((rows, width), np.int32)
    for r, n in enumerate(lens):
        ids[r, width - n:] = rng.integers(0, vocab_size, size=n)
        mask[r, width - n:] = 1
    return ids, mask
