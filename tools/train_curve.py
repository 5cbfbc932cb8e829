"""Produce a reward-curve artifact a reviewer can overlay against the
reference's published runs (media/initial_pg_test.png, ref README.md:73-85).

Two scales:

* ``--model tiny`` (default, any host): the CPU-scale end-to-end RL loop —
  random-init TINY policy, dense digit-fraction reward (~8% base rate),
  engine sampling → reward → GRPO shaping → 8-bit-Adam LoRA updates →
  weight sync. The curve climbing is the same "de-facto integration test"
  the reference's screenshots document, at toy scale.
* ``--model <local checkpoint dir>`` (TPU): the real thing — reference
  recipe 1's shape via ``Trainer.from_pretrained`` with the native tokenizer
  and MATH-style data; logs the exact reference metric names.

Artifacts: ``media/reward_curve_<tag>.jsonl`` (one record per train step,
exact wandb metric names per distributed_trainer.py:348-366) and
``media/reward_curve_<tag>.png``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


class _StreamingSink:
    """MemorySink-compatible sink that ALSO streams each record to a
    partial JSONL (via the package's JsonlSink, so records carry ``_step``
    and survive non-serializable values) — a run can die part-way, and a
    half-finished on-chip curve is worth infinitely more than none."""

    def __init__(self, partial_path: str, fresh: bool = True):
        from distrl_llm_tpu.metrics import JsonlSink

        self.records: list[tuple[int, dict]] = []
        # fresh=False APPENDS across runs: with checkpoint+resume a retried
        # stage only trains the remaining steps, so the partial file
        # accumulates the whole curve across TPU windows (records carry
        # _step for ordering). Non-resuming modes pass fresh=True so
        # unrelated runs never interleave in one file.
        if fresh and os.path.exists(partial_path):
            os.remove(partial_path)
        self._jsonl = JsonlSink(partial_path)

    def log(self, metrics, step: int) -> None:
        self.records.append((step, dict(metrics)))
        self._jsonl.log(metrics, step)

    def finish(self) -> None:
        self._jsonl.finish()


def _is_eval_record(r: dict) -> bool:
    # the reference's eval/ namespace (pass@1 / BoN,
    # distributed_trainer.py:412–415)
    return any(k.startswith("eval/") for k in r)


def _is_curve_record(r: dict) -> bool:
    # train-step records carry the reference's reward name; eval records
    # the eval/ namespace — both belong in the curve artifact
    return "mean_accuracy_reward" in r or _is_eval_record(r)


def _read_partial(path: str) -> list[dict]:
    """Parse the accumulated stream back: train-step + eval records sorted
    by _step. This is the artifact source of truth for resuming runs — the
    in-process sink only saw the steps trained SINCE the last resume."""
    recs = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if _is_curve_record(r):
                    recs.append(r)
    recs.sort(key=lambda r: r.get("_step", 0))
    return recs


def _train_collect(trainer, sink):
    """Run training; on ANY failure keep the steps already collected.

    Returns (records, completed). Callers propagate ``completed`` as the
    process exit status so the resumable bench matrix retries interrupted
    runs instead of marking a truncated curve done."""
    completed = True
    try:
        trainer.train()
    except BaseException as e:  # noqa: BLE001 — partial curve > no curve
        completed = False
        print(f"training interrupted after {len(sink.records)} records: {e!r}")
    recs = []
    for step, m in sink.records:
        if _is_curve_record(m):
            m = dict(m)
            m.setdefault("_step", step)
            recs.append(m)
    return recs, completed


def run_synth(episodes: int, learner: str, model_name: str = "qwen2.5-0.5b"):
    """Real-scale learning without downloadable weights: a RANDOM-INIT
    QWEN2_0_5B policy + the dense digit-fraction reward. The policy can't
    solve MATH from random init, but it CAN learn to emit digits — the same
    full-loop learning signal as the tiny run at reference recipe 1's model
    scale, runnable the moment a chip answers (no egress required)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine import PagedGenerationEngine
    from distrl_llm_tpu.models import PRESETS, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    def digit_reward(completions, solutions):
        return np.asarray(
            [(0.0, sum(1 for ch in c if "0" <= ch <= "9") / max(len(c), 1))
             for c in completions],
            np.float32,
        )

    cfg_model = PRESETS[model_name]
    # run identity (model + learner) keys BOTH the checkpoint dir and the
    # partial stream: a pg run can never resume from grpo state or
    # interleave with its records. Delete the ckpt dir to force a fresh
    # curve after a completed run.
    ckpt_dir = f"/tmp/graft_synth_ckpt_{model_name}-{learner}"
    partial = f"/tmp/reward_curve_partial_synth-{model_name}-{learner}.jsonl"
    fresh = not (os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir))
    config = TrainConfig(
        model=model_name, learner=learner, episodes=episodes, lr=5e-4,
        max_prompt_tokens=64, max_new_tokens=128, batch_size=8,
        num_candidates=8, topk=8, train_batch_size=16, max_lora_rank=16,
        lora_alpha=32, number_of_actors=1, number_of_learners=1,
        learner_chunk_size=0, metrics_backend="null",
        # TPU windows are short and die without warning: checkpoint every
        # few steps and resume across retries so the on-chip curve
        # ACCUMULATES instead of restarting (stage retry in the bench
        # matrix + Orbax mid-episode cursor)
        checkpoint_dir=ckpt_dir,
        resume=True, save_every=4,
    )
    tok = CharTokenizer(vocab_size=cfg_model.vocab_size)
    problems = [f"write numbers about {c}" for c in "abcdefghijklmnop"]
    train = {"problem": problems, "solution": ["0"] * len(problems)}
    engine = PagedGenerationEngine(
        cfg_model, max_prompt_tokens=64, max_new_tokens=128,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        lora_scale=lora_scale(16, 32.0), page_size=64,
        max_concurrent_rows=64, scheduler="refill", decode_chunk=16,
    )
    params = init_params(jax.random.PRNGKey(0), cfg_model, dtype=jnp.bfloat16)
    sink = _StreamingSink(partial, fresh=fresh)
    trainer = Trainer(
        train, dict(train), digit_reward, config,
        tokenizer=tok, engine=engine, base_params=params,
        model_cfg=cfg_model, sink=sink,
    )
    recs, completed = _train_collect(trainer, sink)
    # the accumulated stream covers earlier windows' steps AND the
    # post-completion no-op retry (which trains nothing but must still
    # produce the full artifact and exit 0)
    merged = _read_partial(partial)
    if merged:
        recs = merged
    return (recs, completed), f"synth-{model_name}"


def run_tiny(episodes: int, learner: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.engine import GenerationEngine
    from distrl_llm_tpu.models import TINY, init_params
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.tokenizer import CharTokenizer
    from distrl_llm_tpu.trainer import Trainer

    def digit_reward(completions, solutions):
        return np.asarray(
            [(0.0, sum(1 for ch in c if "0" <= ch <= "9") / max(len(c), 1))
             for c in completions],
            np.float32,
        )

    config = TrainConfig(
        model="tiny", learner=learner, episodes=episodes, lr=3e-1,
        max_prompt_tokens=16, max_new_tokens=12, batch_size=4,
        num_candidates=8, topk=8, train_batch_size=8, max_lora_rank=8,
        lora_alpha=16, number_of_actors=1, number_of_learners=1,
        learner_chunk_size=1, metrics_backend="null",
    )
    tok = CharTokenizer()
    problems = [f"q {c}" for c in "abcdefgh"]
    train = {"problem": problems, "solution": [p[-1].upper() for p in problems]}
    engine = GenerationEngine(
        TINY, max_prompt_tokens=config.max_prompt_tokens,
        max_new_tokens=config.max_new_tokens,
        eos_token_ids=[tok.eos_token_id], pad_token_id=tok.pad_token_id,
        cache_dtype=jnp.float32,
        lora_scale=lora_scale(config.max_lora_rank, config.lora_alpha),
    )
    sink = _StreamingSink(f"/tmp/reward_curve_partial_tiny-cpu-{learner}.jsonl")
    trainer = Trainer(
        train, dict(train), digit_reward, config,
        tokenizer=tok, engine=engine,
        base_params=init_params(jax.random.PRNGKey(0), TINY),
        model_cfg=TINY, sink=sink,
    )
    return _train_collect(trainer, sink), "tiny-cpu"


def run_checkpoint(path: str, episodes: int, learner: str):
    from distrl_llm_tpu.config import TrainConfig
    from distrl_llm_tpu.data import prepare_dataset
    from distrl_llm_tpu.rewards import reward_function
    from distrl_llm_tpu.tokenizer import load_tokenizer
    from distrl_llm_tpu.trainer import Trainer

    config = TrainConfig(
        model=path, learner=learner, episodes=episodes,
        metrics_backend="null", engine_impl="paged",
        max_concurrent_sequences=128, continuous_batching=True,
        kv_cache_quant="int8",
    )
    tokenizer = load_tokenizer(path)
    train, test = prepare_dataset(
        config.dataset, tokenizer, test_size=0.1, seed=config.seed
    )
    name = os.path.basename(path.rstrip("/"))
    sink = _StreamingSink(f"/tmp/reward_curve_partial_{name}-{learner}.jsonl")
    trainer = Trainer.from_pretrained(
        train, test, reward_function, config, checkpoint_path=path,
        tokenizer=tokenizer, sink=sink,
    )
    return _train_collect(trainer, sink), name


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny",
                    help="'tiny' (CPU-scale) or a local HF checkpoint dir")
    ap.add_argument("--episodes", type=int, default=60)
    ap.add_argument("--learner", default="grpo", choices=["pg", "grpo"])
    ap.add_argument("--out-dir", default=os.path.join(
        os.path.dirname(__file__), "..", "media"))
    args = ap.parse_args()

    if args.model == "tiny":
        # tiny runs are CPU-scale by definition; JAX reads the variable at
        # its first backend touch, which comes after this line
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.model == "tiny":
        (records, completed), tag = run_tiny(args.episodes, args.learner)
    elif args.model.startswith("synth-"):
        (records, completed), tag = run_synth(
            args.episodes, args.learner, args.model.removeprefix("synth-")
        )
    else:
        (records, completed), tag = run_checkpoint(
            args.model, args.episodes, args.learner
        )

    import jax

    backend = jax.devices()[0].platform
    tag = f"{tag}-{args.learner}"
    train_recs = [m for m in records if "mean_accuracy_reward" in m]
    eval_recs = [m for m in records if _is_eval_record(m)]
    if not train_recs:
        # nothing to plot; the partial-stream file and the exception print
        # from _train_collect are the diagnostics. Nonzero exit keeps the
        # resumable bench matrix retrying the stage.
        print(f"no train records collected for {tag}; see /tmp partial jsonl")
        return 1
    os.makedirs(args.out_dir, exist_ok=True)
    jsonl = os.path.join(args.out_dir, f"reward_curve_{tag}.jsonl")
    with open(jsonl, "w") as f:
        f.write(json.dumps({"meta": {
            "model": args.model, "learner": args.learner,
            "episodes": args.episodes, "backend": backend,
        }}) + "\n")
        for m in records:
            f.write(json.dumps(m) + "\n")

    steps = [m.get("_step", i + 1) for i, m in enumerate(train_recs)]
    rewards = [m["mean_accuracy_reward"] for m in train_recs]
    # eval series: the reference's pass@1/BoN overlay
    # (distributed_trainer.py:412–415). Key names embed eval_n, so match
    # by prefix.
    def _eval_series(prefix: str):
        xs, ys = [], []
        for m in eval_recs:
            for k, v in m.items():
                if k.startswith(prefix):
                    xs.append(m.get("_step", 0))
                    ys.append(v)
                    break
        return xs, ys

    pass1_x, pass1_y = _eval_series("eval/pass@1")
    bon_x, bon_y = _eval_series("eval/BoN")
    k = max(len(rewards) // 20, 1)
    smooth = [
        sum(rewards[max(0, i - k + 1):i + 1]) / len(rewards[max(0, i - k + 1):i + 1])
        for i in range(len(rewards))
    ]
    try:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt

        fig, ax = plt.subplots(figsize=(7, 4))
        ax.plot(steps, rewards, alpha=0.35, label="mean_accuracy_reward")
        ax.plot(steps, smooth, label=f"rolling mean (k={k})")
        if pass1_y:
            ax.plot(pass1_x, pass1_y, "o-", ms=4, label="eval/pass@1")
        if bon_y:
            ax.plot(bon_x, bon_y, "s--", ms=4, label="eval/BoN")
        ax.set_xlabel("train step")
        ax.set_ylabel("mean_accuracy_reward")
        ax.set_title(f"{tag} ({backend}) — the curve the reference publishes "
                     "as media/*.png")
        ax.legend()
        fig.tight_layout()
        png = os.path.join(args.out_dir, f"reward_curve_{tag}.png")
        fig.savefig(png, dpi=120)
        print(f"wrote {png}")
    except Exception as e:  # noqa: BLE001 — headless plotting is best-effort
        print(f"plot skipped: {e}")
    print(f"wrote {jsonl}")
    print(f"first→last reward: {rewards[0]:.4f} → {rewards[-1]:.4f} "
          f"(rolling: {smooth[0]:.4f} → {smooth[-1]:.4f}) over {len(rewards)} steps")
    if pass1_y:
        bon = (f", BoN: {bon_y[0]:.4f} → {bon_y[-1]:.4f}" if bon_y else "")
        print(f"eval pass@1: {pass1_y[0]:.4f} → {pass1_y[-1]:.4f}{bon} "
              f"over {len(pass1_y)} evals")
    if not completed:
        print("run was INTERRUPTED — artifacts above are partial")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
