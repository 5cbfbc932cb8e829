"""Worker process entrypoint for the control plane.

``python -m distrl_llm_tpu.distributed.worker_main --port 0`` starts a worker
that prints ``PORT <n>`` on stdout and serves control-plane requests — the
native counterpart of a Ray actor process (distributed_actor.py:183–193).

Request payloads are pickled ``(op, arg)`` tuples:

* ``("echo", x)`` → x  (liveness / plumbing tests)
* ``("rollout_rewards", chunk)`` — chunk is a candidate dict shaped like the
  reference's generate output ({"answers": [...groups...], "solution":
  [...]}, distributed_actor.py:152–171); returns the per-group (n, 2) reward
  arrays computed with the parity reward function (reward_functions.py:44–49).
  This is the driver-side hot loop #2 moved ONTO workers — host-parallel
  reward computation across processes (SURVEY §3.6.10).
* ``("generate", shard)`` — a rollout shard: the worker runs its OWN
  generation engine over ``prompt_ids``/``prompt_mask`` with either the
  shipped LoRA adapter (``"lora"`` — legacy weight-in-the-request,
  distributed_actor.py:150) or a ``"weight_version"`` reference resolved
  from the versioned adapter cache the weight bus fills (ISSUE 9), and
  returns {tokens, lengths} plus the round's in-flight swap events.
  Requires ``--serve-model``.
* MSG_WEIGHTS frames (not an op — they arrive on their own connection,
  concurrent with a dispatch in flight) carry one versioned adapter update
  from the driver's WeightBus: decoded (delta against the last acked
  version, checksum-verified), cached, and fed into the engine's
  LoraMailbox for a true mid-round swap.
* ``("weights_debug", arg)`` — adapter-cache introspection for tests and
  the smoke gates: held versions + per-version checksums; ``{"corrupt":
  v}`` flips one byte of a cached leaf (the checksum-mismatch fallback
  drill).
* ``("sleep", seconds)`` → "slept" (hang-injection tests)
* ``("flaky", {"key": str, "fails": int})`` → raises a TRANSIENT
  ConnectionError for the first ``fails`` calls sharing ``key``, then
  succeeds — the fault used by the bounded-retry and poison-quarantine
  tests/chaos harness (resilience.py classification).

SIGTERM is graceful preemption (the preemptible-TPU contract): the serve
loop drains the dispatch in flight — its result is still delivered — then
exits 0, instead of dying mid-RPC and burning the driver's deadline.
"""

from __future__ import annotations

import argparse
import pickle
import sys
import time

_ENGINE_STATE: dict = {}
_FLAKY_COUNTS: dict[str, int] = {}


def _init_engine(model: str, max_prompt_tokens: int, max_new_tokens: int,
                 seed: int, lora_rank: int = 32, lora_alpha: float = 16.0,
                 engine_impl: str = "dense", kv_quant: str | None = None,
                 base_quant: str = "none",
                 quant_group_size: int | None = None,
                 max_concurrent: int = 0, scheduler: str = "waves",
                 decode_chunk: int | None = None,
                 spec_draft: int | None = None, spec_ngram: int | None = None,
                 spec_drafter: str | None = None,
                 spec_verify: str | None = None, spec_adapt: bool = False,
                 prefix_sharing: bool = False,
                 continuous_admission: bool = False,
                 prefix_cache: bool | None = None,
                 kv_spill: bool = False,
                 kv_spill_host_mb: int = 0,
                 gpu_usage: float = 0.0,
                 budget_batch: int = 0, scan_chunk: int | None = None,
                 autotune: bool = True, plan_db: str | None = None,
                 capture_logprobs: bool = False,
                 serving_obs: bool = False, serving_dir: str | None = None,
                 serving_ring: int = 1024) -> None:
    """Build this worker's rollout engine. "tiny" → deterministic random-init
    TINY model (tests/smoke; every worker with the same seed holds identical
    weights); anything else is a local HF checkpoint path."""
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu.engine.engine import GenerationEngine
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import TINY, init_params

    if model == "tiny":
        cfg = TINY
        params = init_params(jax.random.PRNGKey(seed), cfg)
        eos = [cfg.vocab_size - 1]
        pad = 0
        cache_dtype = jnp.float32
    else:
        from distrl_llm_tpu.models.loading import load_pretrained
        from distrl_llm_tpu.tokenizer import load_tokenizer

        import numpy as np

        params, cfg = load_pretrained(model, dtype=np.dtype("bfloat16"))
        tok = load_tokenizer(model)
        eos = [tok.eos_token_id]
        pad = tok.pad_token_id if tok.pad_token_id is not None else tok.eos_token_id
        cache_dtype = jnp.bfloat16
    if base_quant != "none":
        # quantized frozen base (ISSUE 15): the worker serves the SAME
        # int8/int4 containers the driver's --base_quant run trains over,
        # decoded through the fused dequant-matmul kernel where enabled
        # (ops/quant_matmul.py; probe-gated, XLA container fallback)
        from distrl_llm_tpu.ops.quant import (
            default_group_size, quant_bits_for, quantize_params,
        )

        bits = quant_bits_for(base_quant)
        params = quantize_params(
            params, bits=bits,
            group_size=quant_group_size or default_group_size(bits),
        )
    from distrl_llm_tpu.models.lora import lora_scale as _scale

    _ENGINE_STATE["lora_scale"] = _scale(lora_rank, lora_alpha)
    # None = this host's plan DB decides (ExecutionPlan.kv_format); an
    # explicit --kv-quant, including "none", pins — both engines support it
    kwargs = {"kv_quant": kv_quant}
    if capture_logprobs:
        # behavior-logprob capture for driver-side off-policy corrections
        # (clip / async truncated-IS): the handler already ships
        # result.logprobs back; the driver must be told workers record them
        # (--workers_capture_logprobs) so its config validation admits
        # clip_ratio > 0 over remote rollout
        kwargs["capture_logprobs"] = True
    # execution-plan autotune (distrl_llm_tpu/autotune): each worker
    # resolves against ITS OWN host's plan DB — remote engines are
    # configured via worker_main flags by design (config.py's
    # rollout_workers contract), so --autotune off / --plan-db /
    # --decode-scan-chunk are per-worker pins, same semantics as the
    # driver's engines (explicit values, including chunk 0, always win)
    if not autotune:
        kwargs["autotune"] = False
    if plan_db:
        kwargs["plan_db"] = plan_db
    if scan_chunk is not None:
        kwargs["scan_chunk"] = scan_chunk
    if decode_chunk is not None:
        # dispatch granularity = in-flight swap granularity: the engine
        # polls its weight-update mailbox between decode dispatches, so a
        # smaller chunk tightens how quickly a MSG_WEIGHTS push lands
        # mid-round (the engine default of 128 makes short rounds one
        # dispatch — pushes would only land at round boundaries)
        kwargs["decode_chunk"] = decode_chunk
    if engine_impl == "paged":
        engine_cls = PagedGenerationEngine
        kwargs["scheduler"] = scheduler
        # trainer-side convention (engine_kwargs_from_config): an explicit
        # value — INCLUDING --spec-draft 0 — always wins, so a worker-side
        # spec-off A/B control holds even when this host's plan DB stores a
        # speculative winner; None = unpinned, engine default / plan-DB
        if spec_draft is not None:
            kwargs["spec_draft"] = spec_draft
        if spec_ngram is not None:
            kwargs["spec_ngram"] = spec_ngram
        if spec_drafter is not None:
            kwargs["spec_drafter"] = spec_drafter
        if spec_verify is not None:
            kwargs["spec_verify"] = spec_verify
        if spec_adapt:
            kwargs["spec_adapt"] = True
        # forwarded only when set (trainer convention): an unset worker
        # stays plan-DB-resolvable at the engine (cb_mode field) and the
        # empty-DB default remains the historical fixed batches
        if prefix_sharing:
            kwargs["prefix_sharing"] = True
        if continuous_admission:
            kwargs["continuous_admission"] = True
        # tiered KV cache (ISSUE 18), trainer convention: None stays
        # plan-DB-resolvable; an explicit bool — including --prefix-cache
        # off — pins past any stored plan. kv_spill is explicit-only.
        if prefix_cache is not None:
            kwargs["prefix_cache"] = prefix_cache
        if kv_spill:
            kwargs["kv_spill"] = True
            if kv_spill_host_mb:
                kwargs["kv_spill_host_mb"] = kv_spill_host_mb
        if gpu_usage > 0:
            # --actor-gpu-usage → KV page budget, same contract as the
            # trainer's local engine (engine/budget.py)
            from distrl_llm_tpu.engine.budget import kv_pool_pages, tree_bytes
            from distrl_llm_tpu.ops.paged import DEFAULT_PAGE_SIZE

            if budget_batch <= 0:
                # silently guessing the round size would under-account the
                # shared prompt-page region and OOM exactly when the knob
                # should have prevented it
                raise ValueError(
                    "--actor-gpu-usage requires --budget-batch (prompts per "
                    "round, for the shared prompt-page accounting)"
                )
            kwargs["max_kv_pages"] = kv_pool_pages(
                cfg, gpu_usage=gpu_usage, param_bytes=tree_bytes(params),
                batch_prompts=budget_batch,
                max_prompt_tokens=max_prompt_tokens,
                max_new_tokens=max_new_tokens,
                # pool sizing sees only the EXPLICIT format (the
                # spec_draft convention): a plan-DB-resolved int8 KV
                # leaves the pool sized for bf16 pages — slack, never OOM
                page_size=DEFAULT_PAGE_SIZE, kv_quant=kv_quant or "none",
                # pool sizing sees only the EXPLICIT draft length (trainer
                # convention): a plan-DB entry that enables speculation
                # (spec_draft None) isn't resolved until engine
                # construction, so its ≤d extra resident tokens/row ride
                # the pool's refill-admission slack instead
                spec_draft=spec_draft or 0,
                # same convention: only the explicit flag reshapes the
                # pool math (chains move into the pool); a plan-DB-enabled
                # continuous run surfaces as the engine's pool-floor error
                continuous=continuous_admission,
                # only an explicit --prefix-cache on bumps the floor; a
                # plan-resolved cache rides the refill slack instead
                prefix_cache=bool(prefix_cache),
            )
    else:
        engine_cls = GenerationEngine
    if max_concurrent:
        kwargs["max_concurrent_rows"] = max_concurrent
    _ENGINE_STATE["engine"] = engine_cls(
        cfg, max_prompt_tokens=max_prompt_tokens, max_new_tokens=max_new_tokens,
        eos_token_ids=eos, pad_token_id=pad, cache_dtype=cache_dtype,
        lora_scale=_ENGINE_STATE["lora_scale"], **kwargs,
    )
    if serving_obs:
        # request-level serving ledger (ISSUE 13): this worker's refill
        # loops record per-group lifecycle + admission audit; the
        # serving/* registry series ride the obs blobs home so the driver
        # folds a fleet serving view (main() closes it at drain)
        from distrl_llm_tpu.serving_obs import ServingLedger

        ledger = ServingLedger(ring_size=serving_ring, out_dir=serving_dir)
        _ENGINE_STATE["engine"].serving_ledger = ledger
        _ENGINE_STATE["serving_ledger"] = ledger
    _ENGINE_STATE["params"] = params
    # versioned adapter cache (weight_bus.py, ISSUE 9): filled by MSG_WEIGHTS
    # pushes, read by version-referencing dispatches. 2 slots — current +
    # superseded, the remote twin of the LoraMailbox's self-drafter slot
    from distrl_llm_tpu.distributed.weight_bus import AdapterCache

    _ENGINE_STATE["adapter_cache"] = AdapterCache()


def _init_control(args) -> None:
    """Arm the worker-side control runtime (ISSUE 14): the engine-facing
    governors — HBM admission governor and SLO load-shedder — act on THIS
    worker's engine through its ControlLimits handle, pumped once per
    generation round (the 'generate' handler). Driver-only controllers
    (staleness, worker health, nan rollback) have no worker half.
    The armed set was computed ONCE in main()'s validation pass
    (args.control_hbm_armed / args.control_shed_armed) — one owner, so
    validation and registration cannot drift apart."""
    hbm = args.control_hbm_armed
    shed = args.control_shed_armed
    if not (hbm or shed):
        return
    from distrl_llm_tpu.control import (
        ControlLimits, ControlRuntime, HbmGovernor, SloShedGovernor,
    )

    limits = ControlLimits()
    _ENGINE_STATE["engine"].control_limits = limits
    runtime = ControlRuntime(budget=args.control_budget, limits=limits)
    if hbm:
        runtime.register(
            HbmGovernor(
                limits,
                cooldown_steps=args.control_cooldown_steps,
                dwell_steps=args.control_dwell_steps,
            ),
            triggers=("hbm_breach",),
        )
    if shed:
        runtime.register(
            SloShedGovernor(
                limits,
                slo_ttft_ms=args.slo_ttft_ms,
                slo_queue_wait_ms=args.slo_queue_wait_ms,
                cooldown_steps=args.control_cooldown_steps,
                dwell_steps=args.control_dwell_steps,
            ),
            triggers=("ttft_blowup", "queue_wait_blowup"),
        )
    _ENGINE_STATE["control"] = runtime
    _ENGINE_STATE["control_step"] = 0


def weights_handler(payload: bytes) -> bytes:
    """MSG_WEIGHTS frames (the driver's WeightBus): decode one versioned
    adapter update — delta against the cached base when the payload names
    one, checksum-verified either way — store it in the 2-slot cache, and
    feed it into the engine's LoraMailbox so a generation round in flight
    swaps at its next decode dispatch (the PipelineRL in-flight semantics,
    now over the wire). Runs on its OWN connection thread, concurrent with
    the dispatch handler."""
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.distributed.weight_bus import (
        WeightVersionError, decode_update,
    )

    cache = _ENGINE_STATE.get("adapter_cache")
    if cache is None:
        raise RuntimeError(
            "worker started without --serve-model: no adapter cache to "
            "receive weight pushes"
        )
    msg = pickle.loads(payload)
    base_version = msg.get("base_version")
    prev = cache.get(base_version) if base_version is not None else None
    if base_version is not None and prev is None:
        raise WeightVersionError(
            f"delta update v{msg.get('version')} names base v{base_version} "
            f"which this worker does not hold (cache: {cache.versions()}) — "
            "WeightVersionError: send full"
        )
    # causal trace context (ISSUE 10): a traced driver stamps its push
    # frames, so this worker's weights span links back to the originating
    # cp/weight_push span in the merged timeline
    ctx = msg.get("trace_ctx")
    if ctx is not None:
        telemetry.bind_trace_context(ctx)
    try:
        with telemetry.span(
            "worker/weights", version=int(msg.get("version", -1)),
            delta=bool(base_version is not None),
        ):
            version, tree = decode_update(msg, prev)  # checksum-verified
            engine = _ENGINE_STATE.get("engine")
            if engine is not None:
                import jax.numpy as jnp
                import jax

                # in-flight swap: the round currently running (if any)
                # consumes this at its next decode dispatch; between
                # rounds, the stale-pending guard at generate entry clears
                # it. Mailbox BEFORE cache: the cache is the gate a
                # version-naming dispatch waits on, so ordering guarantees
                # the pending entry is visible to that dispatch's entry
                # guard — a put-first order would let the dispatch start
                # and then replay this push as a phantom swap
                engine.push_lora(
                    jax.tree_util.tree_map(jnp.asarray, tree), version=version
                )
            cache.put(version, tree)
    finally:
        if ctx is not None:
            telemetry.unbind_trace_context()
    return pickle.dumps({"version": version, "checksum": msg["checksum"]})


def handler(payload: bytes) -> bytes:
    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.rewards import reward_function

    op, arg = pickle.loads(payload)
    # span per op: with tracing on (--trace / DISTRL_TRACE=1) these ship
    # back to the driver in the RPC response and land on this worker's
    # track in the merged trace (control_plane MSG_RESULT_TLM)
    if op == "echo":
        with telemetry.span("worker/echo"):
            return pickle.dumps(arg)
    if op == "sleep":
        time.sleep(float(arg))
        return pickle.dumps("slept")
    if op == "flaky":
        key = str(arg.get("key", "k"))
        fails = int(arg.get("fails", 1))
        n = _FLAKY_COUNTS.get(key, 0) + 1
        _FLAKY_COUNTS[key] = n
        if n <= fails:
            # ConnectionError classifies transient (resilience.py) — the
            # driver retries under its policy instead of aborting the round
            raise ConnectionError(
                f"injected transient fault {n}/{fails} for {key!r}"
            )
        return pickle.dumps(("ok", key, n))
    if op == "rollout_rewards":
        with telemetry.span("worker/rollout_rewards",
                            groups=len(arg["answers"])):
            rewards = [
                reward_function(answers, solutions)
                for answers, solutions in zip(arg["answers"], arg["solution"])
            ]
            return pickle.dumps(rewards)
    if op == "weights_debug":
        from distrl_llm_tpu.distributed.weight_bus import checksum_tree

        cache = _ENGINE_STATE.get("adapter_cache")
        if cache is None:
            raise RuntimeError("worker started without --serve-model")
        arg = arg or {}
        if arg.get("corrupt") is not None:
            import jax

            v = int(arg["corrupt"])
            tree = cache.get(v)
            if tree is None:
                raise ValueError(f"no cached adapter v{v} to corrupt")
            leaf = jax.tree_util.tree_leaves(tree)[0]
            leaf.reshape(-1).view("uint8")[0] ^= 0xFF  # flip one byte in place
        return pickle.dumps({
            "versions": cache.versions(),
            "current": cache.current_version,
            "checksums": {
                v: checksum_tree(cache.get(v)) for v in cache.versions()
            },
        })
    if op == "generate":
        if "engine" not in _ENGINE_STATE:
            raise RuntimeError("worker started without --serve-model")
        import jax
        import jax.numpy as jnp

        from distrl_llm_tpu.config import SamplingConfig

        engine = _ENGINE_STATE["engine"]
        lora = arg["lora"]
        weight_version = arg.get("weight_version")
        if lora is None and weight_version is not None:
            # broadcast bus (ISSUE 9): resolve the named version from the
            # adapter cache, waiting out the benign race where the dispatch
            # outran its broadcast; a genuine miss raises the transient
            # WeightVersionError the driver's re-request hook answers
            from distrl_llm_tpu.distributed import weight_bus as wb

            tree = _ENGINE_STATE["adapter_cache"].wait_for(
                int(weight_version), timeout_s=wb.resolve_wait_s()
            )
            lora = jax.tree_util.tree_map(jnp.asarray, tree)
            # a pending mailbox entry at or below the version this round
            # opens with would replay as a spurious step-0 swap — discard
            # it atomically (a strictly newer push racing in stays: it is
            # a real in-flight update this round should consume)
            engine.discard_pending_at_or_below(int(weight_version))
        elif lora is not None:
            lora = jax.tree_util.tree_map(jnp.asarray, lora)
        if lora is not None:
            # the adapter is only meaningful at the trainer's alpha/rank
            # scale — a mismatch means sampling a DIFFERENT policy than the
            # learner optimizes; fail loudly instead (review r2)
            want = arg.get("lora_scale")
            have = _ENGINE_STATE["lora_scale"]
            if want is not None and abs(want - have) > 1e-9:
                raise ValueError(
                    f"lora_scale mismatch: trainer sends {want}, worker "
                    f"engine built with {have} (--lora-rank/--lora-alpha)"
                )
        eos_override = arg.get("eos_token_ids")
        if eos_override:
            # the trainer's merged stop-token set wins over the worker's
            # single tokenizer eos (same compiled fns — eos ids are traced)
            engine.eos_ids = jnp.asarray(
                sorted(set(int(e) for e in eos_override)), jnp.int32
            )
        # snapshot the mailbox swap log so THIS round's in-flight swaps
        # (weight-bus pushes landing mid-generation) ship back with the
        # result — the driver merges them into its trajectory version tags
        swaps_before = len(getattr(engine, "last_swap_steps", ()))
        # when the serving gateway is armed (ISSUE 19) its round former
        # shares this engine — the mutex serializes trainer dispatches
        # against gateway rounds (absent a gateway there is no mutex and
        # nothing changes)
        from contextlib import nullcontext

        with telemetry.span(
            "worker/generate", rows=int(arg["prompt_ids"].shape[0]),
            n=int(arg["sampling"].get("n", 1)),
        ) as sp:
            with _ENGINE_STATE.get("engine_mutex") or nullcontext():
                result = engine.generate(
                    _ENGINE_STATE["params"], lora,
                    arg["prompt_ids"], arg["prompt_mask"],
                    SamplingConfig(**arg["sampling"]),
                    jax.random.PRNGKey(arg["rng_seed"]),
                )
            sp.set(tokens=int(result.lengths.sum()))
        ctrl = _ENGINE_STATE.get("control")
        if ctrl is not None:
            # one control pass per generation round (ISSUE 14): read the
            # round's windowed registry stats (serving latency maxes, …)
            # and let the governors adjust the NEXT round's admission
            # limits. metrics_snapshot is report-and-reset and nothing
            # else consumes it worker-side (the obs blobs ride the
            # non-destructive observe_snapshot)
            _ENGINE_STATE["control_step"] += 1
            ctrl.on_step(
                _ENGINE_STATE["control_step"], telemetry.metrics_snapshot()
            )
        return pickle.dumps({
            "tokens": result.tokens, "lengths": result.lengths,
            "logprobs": result.logprobs,
            "entry_version": weight_version,
            "swap_steps": list(
                getattr(engine, "last_swap_steps", ())
            )[swaps_before:],
            "swap_versions": list(
                getattr(engine, "last_swap_versions", ())
            )[swaps_before:],
        })
    raise ValueError(f"unknown op {op!r}")


def main(argv: list[str] | None = None) -> None:
    import os

    # the chip this worker owns is named in its environment by whoever
    # spawned it (utils.devices.worker_env): it takes the devices it sees
    from distrl_llm_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()

    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    # default None (no engine) vs the driver's reference-parity Qwen
    # default: a worker must never silently download/load a 7B checkpoint
    # just because the flag was omitted
    # graftcheck: disable=GC402 -- worker default None = serve no model; the driver's model default is reference parity
    parser.add_argument("--serve-model", type=str, default=None,
                        help='"tiny" (random-init test model) or a local HF '
                             "checkpoint path; enables the generate op")
    parser.add_argument("--max-prompt-tokens", type=int, default=350)
    parser.add_argument("--max-new-tokens", type=int, default=1200)
    # seed 0 vs driver 3407: this seeds the TINY test model's random
    # init (every worker with the same seed holds identical weights); the
    # driver's 3407 is the reference's dataset-split/training seed — they
    # are different knobs that happen to share a name
    # graftcheck: disable=GC402 -- worker seed inits the tiny test model, not the training run
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--lora-rank", type=int, default=32)
    parser.add_argument("--lora-alpha", type=float, default=16.0)
    parser.add_argument("--engine-impl", type=str, default="dense",
                        choices=["dense", "paged"])
    parser.add_argument("--kv-quant", type=str, default=None,
                        choices=["none", "int8"],
                        help="KV cache quantization; unset = this host's "
                             "autotune plan DB decides (kv_format; empty "
                             "DB = none). An explicit value, including "
                             "none, always wins over any stored plan")
    parser.add_argument("--base-quant", type=str, default="none",
                        choices=["none", "int8", "int4"],
                        help="weight-only quantization of this worker's "
                             "frozen base (the driver's --base_quant "
                             "counterpart on the serve path); decode runs "
                             "the fused dequant-matmul kernel where "
                             "enabled (DISTRL_QUANT_MATMUL)")
    parser.add_argument("--quant-group-size", type=int, default=None,
                        help="groupwise-scale width for --base-quant "
                             "(must divide the projection input dims); "
                             "unset = per-format default (int8: "
                             "per-column, int4: 64)")
    parser.add_argument("--max-concurrent-sequences", type=int, default=0,
                        help="decode row cap (vLLM max_num_seqs); 0 = unlimited")
    # driver-side spelling is --continuous_batching (a bool that maps to
    # refill); the worker exposes the scheduler enum directly because it
    # also hosts the waves/refill A/B harnesses
    # graftcheck: disable=GC401 -- driver expresses this as --continuous_batching (bool -> refill)
    parser.add_argument("--scheduler", type=str, default="waves",
                        choices=["waves", "refill"],
                        help="paged-engine batching: whole-prompt waves or "
                             "per-candidate slot refill (continuous batching)")
    parser.add_argument("--spec-draft", type=int, default=None,
                        help="speculative decoding draft length (requires "
                             "--scheduler refill); 0 pins speculation OFF "
                             "past any stored plan; unset = this host's "
                             "autotune plan DB decides. An explicit value, "
                             "including 0, always wins")
    parser.add_argument("--spec-ngram", type=int, default=None,
                        help="n-gram size for --spec-draft (unset = engine "
                             "default / plan-DB)")
    parser.add_argument("--spec-drafter", choices=["ngram", "self"],
                        default=None,
                        help="draft source for --spec-draft: 'ngram' or "
                             "'self' (the previous adapter off the weight-"
                             "push stream; needs a LoRA run). Unset = "
                             "engine default / plan-DB")
    parser.add_argument("--spec-verify", choices=["fused", "unrolled"],
                        default=None,
                        help="verify-attention kernel for --spec-draft "
                             "(unset = engine default / plan-DB)")
    parser.add_argument("--spec-adapt", action="store_true",
                        help="acceptance-rate-driven draft-length "
                             "adaptation (requires --spec-draft)")
    parser.add_argument("--prefix-sharing", action="store_true",
                        help="copy-on-write prompt-prefix sharing: a "
                             "group's candidates alias one refcounted "
                             "prompt page chain (requires --scheduler "
                             "refill); greedy-bit-identical to unshared")
    parser.add_argument("--continuous-admission", action="store_true",
                        help="lazy per-group prefill feeding freed slots "
                             "from a request queue instead of the fixed "
                             "episode batch; implies --prefix-sharing "
                             "(requires --scheduler refill). Unset leaves "
                             "this host's autotune plan DB in charge")
    parser.add_argument("--prefix-cache", choices=("on", "off"),
                        default=None,
                        help="tiered KV cache tier 1 (ISSUE 18): "
                             "cross-request radix prefix index — warm "
                             "prompts alias cached pages and prefill only "
                             "their un-cached suffix, bit-identically to "
                             "cache-off (requires --continuous-admission "
                             "and an unquantized pool). Explicit on/off "
                             "pins past this host's plan DB; unset leaves "
                             "the DB in charge")
    parser.add_argument("--kv-spill", action="store_true",
                        help="tiered KV cache tier 2 (ISSUE 18): "
                             "preempted chains spill written KV pages to "
                             "a host-RAM store and restore bit-exactly on "
                             "resume instead of recomputing (requires "
                             "--prefix-cache on; incompatible with "
                             "--spec-draft)")
    parser.add_argument("--kv-spill-host-mb", type=int, default=0,
                        help="host page-store byte cap in MiB for "
                             "--kv-spill (0 = unbounded); payloads LRU-"
                             "drop past the cap and fall back to the "
                             "recompute resume")
    parser.add_argument("--serving-obs", dest="serving_obs",
                        action="store_true",
                        help="request-level serving ledger (ISSUE 13): "
                             "per-group lifecycle + admission audit from "
                             "the refill loops; the serving/* series ride "
                             "this worker's obs blobs into the driver's "
                             "fleet fold (requires --scheduler refill)")
    parser.add_argument("--serving-dir", dest="serving_dir", type=str,
                        default=None,
                        help="stream closed serving records to "
                             "<dir>/serving.jsonl on THIS worker's "
                             "filesystem (implies --serving-obs); inspect "
                             "with tools/serving_report.py")
    parser.add_argument("--serving-ring", dest="serving_ring", type=int,
                        default=1024,
                        help="bounded ring of OPEN serving records; "
                             "overflow counted in serving/ring_evictions")
    parser.add_argument("--gateway-port", dest="gateway_port", type=int,
                        default=None,
                        help="multi-tenant serving gateway (ISSUE 19): "
                             "serve POST /v1/generate on 127.0.0.1:<port> "
                             "(0 = auto; the bound port prints as "
                             "'GATEWAY <n>'), streaming tokens per request "
                             "with tenant + priority class from X-Tenant / "
                             "X-Priority headers; requires --serve-model, "
                             "--scheduler refill and "
                             "--continuous-admission")
    parser.add_argument("--gateway-classes", dest="gateway_classes",
                        type=str, default=None,
                        help="comma-separated subset of priority classes "
                             "this gateway serves (default: interactive,"
                             "batch,scavenger); unserved classes get "
                             "HTTP 400")
    parser.add_argument("--tenant-quota", dest="tenant_quota", type=str,
                        default=None,
                        help="per-tenant reserved-token quotas "
                             "'tenant=tokens,...' ('default' caps unnamed "
                             "tenants); quota declines are the 'quota' "
                             "admission-stall reason (requires "
                             "--gateway-port)")
    # default 0.0 (worst-case page pool) vs the driver's reference-parity
    # 0.91: an unconfigured worker must size for the worst case rather
    # than assume it owns 91% of an unknown chip's HBM
    # graftcheck: disable=GC402 -- worker defaults to the conservative worst-case pool; 0.91 is driver-side reference parity
    parser.add_argument("--actor-gpu-usage", type=float, default=0.0,
                        help="HBM fraction for weights+KV (vLLM "
                             "gpu_memory_utilization); sizes the paged "
                             "engine's KV page pool. 0 = worst-case pool")
    # worker-only: the driver derives prompts-per-round from
    # batch_size x num_candidates; a remote worker cannot see that config
    # and must be told explicitly (config.py rollout_workers contract)
    # graftcheck: disable=GC401 -- driver derives this from batch_size x num_candidates
    parser.add_argument("--budget-batch", type=int, default=0,
                        help="prompts per round assumed by the page-budget "
                             "math (shared prompt-page region)")
    # worker-only: bounds THIS worker's in-flight swap latency; the
    # driver's local engines keep the engine default (remote engines are
    # configured via worker_main flags by design — see _init_engine)
    # graftcheck: disable=GC401 -- per-worker swap-latency pin; local engines use the engine default
    parser.add_argument("--decode-chunk", type=int, default=None,
                        help="decode steps per engine dispatch (unset = "
                             "engine default 128). The mailbox consuming "
                             "weight-bus pushes is polled between "
                             "dispatches, so this bounds in-flight swap "
                             "latency: a push can land mid-round at most "
                             "this many decode steps late")
    parser.add_argument("--decode-scan-chunk", type=int, default=None,
                        help="decode steps fused per dispatch; 0 = off; "
                             "unset = this host's autotune plan DB decides. "
                             "An explicit value, including 0, always wins")
    parser.add_argument("--autotune", type=str, default="on",
                        choices=["on", "off"],
                        help="'off' pins the static engine defaults without "
                             "reading this host's plan DB")
    parser.add_argument("--plan-db", dest="plan_db", type=str, default=None,
                        help="plan-DB path (default: $DISTRL_PLAN_DB or "
                             "~/.cache/distrl_llm_tpu/plan_db.json)")
    parser.add_argument("--capture-logprobs", action="store_true",
                        help="record per-token behavior logprobs during "
                             "generation and ship them with results — "
                             "required when the driver trains with "
                             "--clip_ratio > 0 / --rollout_mode async over "
                             "this worker (declare driver-side with "
                             "--workers_capture_logprobs)")
    parser.add_argument("--trace", action="store_true",
                        help="record telemetry spans and ship them to the "
                             "driver in RPC responses (also enabled by "
                             "DISTRL_TRACE=1); the driver merges them into "
                             "its trace under this worker's track")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="serve this worker's live metrics endpoint "
                             "(Prometheus at /metrics, JSON at "
                             "/metrics.json) on this port (0 = auto; the "
                             "bound port prints as 'METRICS <n>'), and "
                             "piggyback the registry snapshot on RPC "
                             "results for the driver's fleet aggregator "
                             "(snapshot-only export also via DISTRL_OBS=1)")
    parser.add_argument("--control", action="store_true",
                        help="self-healing runtime (ISSUE 14): arm every "
                             "engine-facing controller this worker's shape "
                             "supports (HBM admission governor; SLO "
                             "load-shedder when an --slo-* limit is set), "
                             "pumped once per generation round")
    parser.add_argument("--control-hbm", dest="control_hbm",
                        action="store_true",
                        help="HBM governor only: shrink this worker's "
                             "continuous-admission chain cap under "
                             "watermark pressure, regrow after a "
                             "sustained-headroom dwell (requires "
                             "--continuous-admission)")
    parser.add_argument("--control-shed", dest="control_shed",
                        action="store_true",
                        help="SLO load-shedder only: throttle this "
                             "worker's group admission (decline reason "
                             "'shed') while its serving TTFT/queue-wait "
                             "breach the --slo-* limits (requires "
                             "--continuous-admission and an SLO)")
    parser.add_argument("--control-budget", dest="control_budget",
                        type=int, default=64,
                        help="global actuation budget per run; once spent "
                             "every controller knob freezes")
    parser.add_argument("--control-cooldown-steps",
                        dest="control_cooldown_steps", type=int, default=2,
                        help="minimum rounds between two actions of one "
                             "governor")
    parser.add_argument("--control-dwell-steps",
                        dest="control_dwell_steps", type=int, default=3,
                        help="consecutive healthy rounds before a governor "
                             "regrows a shrunk knob")
    parser.add_argument("--slo-ttft-ms", dest="slo_ttft_ms", type=float,
                        default=None,
                        help="time-to-first-token SLO for this worker's "
                             "SLO load-shedder (requires --control-shed "
                             "or --control; driver-side the same flag "
                             "additionally arms the sentinel trigger)")
    parser.add_argument("--slo-queue-wait-ms", dest="slo_queue_wait_ms",
                        type=float, default=None,
                        help="queue-wait SLO for this worker's SLO "
                             "load-shedder")
    parser.add_argument("--env", type=str, default="math",
                        choices=["code", "math", "verifier"],
                        help="rollout environment (driver parity, GC402). "
                             "Multi-turn envs run driver-local this "
                             "iteration — the remote worker engine has no "
                             "turn hook, so any non-default value is "
                             "rejected loudly instead of silently sampling "
                             "single-turn")
    parser.add_argument("--max-turns", type=int, default=1,
                        help="conversation-turn budget (driver parity, "
                             "GC402); >1 is rejected worker-side — see "
                             "--env")
    parser.add_argument("--fault-schedule", type=str, default=None,
                        help="deterministic fault-injection schedule for "
                             "this worker's connections (resilience."
                             "FaultInjector grammar, e.g. "
                             "'seed=7;recv:3=delay:0.2'); also read from "
                             "$DISTRL_FAULT_SCHEDULE so chaos runs can "
                             "share one spec across processes")
    args = parser.parse_args(argv)
    if args.fault_schedule:
        os.environ["DISTRL_FAULT_SCHEDULE"] = args.fault_schedule
    if args.trace:
        from distrl_llm_tpu import telemetry

        telemetry.configure(enabled=True)
    if args.decode_chunk is not None and args.decode_chunk < 1:
        parser.error("--decode-chunk must be >= 1")
    if args.env != "math" or args.max_turns != 1:
        # multi-turn environments are driver-local this iteration: the
        # engine turn hook lives on the driver's own paged engine, and a
        # worker silently sampling single-turn would corrupt the round's
        # per-turn rewards — fail loudly (driver config.py rejects
        # env != 'math' over rollout_workers for the same reason)
        parser.error(
            "--env/--max-turns: multi-turn environments run driver-local "
            "only (the turn hook lives on the driver's paged engine); "
            "start the driver without --rollout_workers for env runs"
        )
    if args.quant_group_size is not None and args.quant_group_size < 1:
        parser.error("--quant-group-size must be >= 1")
    if args.quant_group_size is not None and args.base_quant == "none":
        # dead-flag policy (driver parity: TrainConfig rejects the same
        # combination) — the group size only shapes base containers
        parser.error(
            "--quant-group-size configures --base-quant's groupwise "
            "scales — set --base-quant int8/int4 (it would be silently "
            "ignored)"
        )
    if args.scheduler == "refill" and args.engine_impl != "paged":
        parser.error("--scheduler refill requires --engine-impl paged")
    if args.scheduler != "refill" and (
        args.spec_draft or args.spec_ngram is not None
        or args.spec_drafter is not None or args.spec_verify is not None
        or args.spec_adapt
    ):
        # the satellite pins too: a non-refill engine requests the plain
        # paged decode path, so a stored speculative plan can never engage
        # and the flags would be guaranteed no-ops
        parser.error(
            "--spec-draft/--spec-ngram/--spec-drafter/--spec-verify/"
            "--spec-adapt require --scheduler refill (the refill "
            "scheduler hosts speculative decoding)"
        )
    # unset (None) stays legal with the satellite pins: this host's plan DB
    # may enable speculation, and the engine re-validates post-resolution
    # (config.py convention); only an EXPLICIT 0 makes them dead flags
    if args.spec_draft == 0 and (
        args.spec_ngram is not None or args.spec_drafter is not None
        or args.spec_verify is not None or args.spec_adapt
    ):
        parser.error(
            "--spec-ngram/--spec-drafter/--spec-verify/--spec-adapt "
            "require --spec-draft > 0 (--spec-draft 0 pins speculation "
            "off, so they would be silently ignored)"
        )
    if args.scheduler != "refill" and (
        args.prefix_sharing or args.continuous_admission
    ):
        # same dead-flag policy as the spec satellites: the refill
        # scheduler hosts the prefix-sharing pool and admission queue
        parser.error(
            "--prefix-sharing/--continuous-admission require --scheduler "
            "refill (the refill scheduler hosts the shared page pool)"
        )
    if args.scheduler == "refill" and not args.max_concurrent_sequences:
        parser.error(
            "--scheduler refill requires --max-concurrent-sequences "
            "(the decode slot count)"
        )
    # tiered KV cache (ISSUE 18), driver-parity dead-flag policy
    if args.prefix_cache == "on" and not args.continuous_admission:
        parser.error(
            "--prefix-cache on aliases cached prompt chains out of the "
            "continuous-admission pool — add --continuous-admission"
        )
    if args.prefix_cache == "on" and args.kv_quant == "int8":
        parser.error(
            "--prefix-cache on requires a lossless KV pool: int8 pages "
            "cannot reproduce the cold prefill's attention inputs "
            "bit-exactly — drop --kv-quant int8 or the cache"
        )
    if args.kv_spill and args.prefix_cache != "on":
        parser.error(
            "--kv-spill parks KV pages through the tiered cache's host "
            "store — it requires --prefix-cache on"
        )
    if args.kv_spill and args.spec_draft:
        parser.error(
            "--kv-spill restores raw decode cursors the speculative "
            "scheduler does not expose — drop --kv-spill or --spec-draft"
        )
    if args.kv_spill_host_mb and not args.kv_spill:
        parser.error(
            "--kv-spill-host-mb caps the --kv-spill host store — it "
            "would be a dead knob without it"
        )
    # serving gateway (ISSUE 19): driver-parity validation — the gateway
    # schedules the continuous-admission refill engine
    if args.gateway_port is not None:
        if not (0 <= args.gateway_port <= 65535):
            parser.error("--gateway-port must be in [0, 65535] (0 = auto)")
        if not args.serve_model:
            parser.error("--gateway-port requires --serve-model (the "
                         "gateway fronts this worker's engine)")
        if not (args.scheduler == "refill" and args.continuous_admission):
            parser.error(
                "--gateway-port requires --scheduler refill with "
                "--continuous-admission (the request-queue scheduler is "
                "the gateway's admission plane)"
            )
        from distrl_llm_tpu.gateway.scheduler import (
            parse_gateway_classes, parse_tenant_quota,
        )

        try:
            parse_gateway_classes(args.gateway_classes)
            parse_tenant_quota(args.tenant_quota)
        except ValueError as e:
            parser.error(str(e))
    elif args.gateway_classes or args.tenant_quota:
        # dead-flag policy (driver parity): class/quota knobs shape the
        # gateway's admission plane only
        parser.error(
            "--gateway-classes/--tenant-quota configure the serving "
            "gateway — set --gateway-port (they would be silently ignored)"
        )
    if args.serving_dir and not args.serving_obs:
        args.serving_obs = True  # an output directory is an unambiguous ask
    if args.serving_obs and args.scheduler != "refill":
        # dead-flag policy (the prefix-sharing precedent): the serving
        # ledger instruments the refill/continuous loops only
        parser.error(
            "--serving-obs/--serving-dir require --scheduler refill "
            "(the refill scheduler hosts the instrumented admission loop)"
        )
    # self-healing runtime (ISSUE 14): worker-side parity for the
    # engine-facing controllers — same dead-flag policy as the driver
    if args.control_hbm and not (
        args.scheduler == "refill" and args.continuous_admission
    ):
        parser.error(
            "--control-hbm requires --scheduler refill with "
            "--continuous-admission (the chain cap it actuates)"
        )
    if args.control_shed:
        if not (args.scheduler == "refill" and args.continuous_admission):
            parser.error(
                "--control-shed requires --scheduler refill with "
                "--continuous-admission (the admission queue it throttles)"
            )
        if args.slo_ttft_ms is None and args.slo_queue_wait_ms is None:
            parser.error(
                "--control-shed needs an SLO to steer on "
                "(--slo-ttft-ms / --slo-queue-wait-ms)"
            )
    if args.control_budget < 1:
        # fail at the parser like the driver (TrainConfig validates the
        # same bound) — not as a post-model-load ValueError traceback
        parser.error("--control-budget must be >= 1")
    if args.control_cooldown_steps < 0:
        parser.error("--control-cooldown-steps must be >= 0")
    if args.control_dwell_steps < 1:
        parser.error("--control-dwell-steps must be >= 1")
    # the armed set, computed ONCE (the single owner _init_control reads):
    # validation below and governor registration can never drift apart
    args.control_hbm_armed = args.control_hbm or (
        args.control and args.continuous_admission
    )
    args.control_shed_armed = args.control_shed or (
        args.control and args.continuous_admission
        and (args.slo_ttft_ms is not None
             or args.slo_queue_wait_ms is not None)
    )
    if (
        args.slo_ttft_ms is not None or args.slo_queue_wait_ms is not None
    ) and not args.control_shed_armed:
        parser.error(
            "--slo-ttft-ms/--slo-queue-wait-ms feed the worker-side SLO "
            "load-shedder — arm it with --control-shed (or --control on "
            "a --continuous-admission worker); they would be silently "
            "ignored"
        )
    if args.control_shed_armed and not args.serving_obs:
        # the shedder steers on serving/* latency the ledger produces —
        # an SLO is an unambiguous ask, arm the measurement (the
        # driver-side slo_* precedent)
        args.serving_obs = True

    if args.serve_model:
        _init_engine(
            args.serve_model, args.max_prompt_tokens, args.max_new_tokens,
            args.seed, lora_rank=args.lora_rank, lora_alpha=args.lora_alpha,
            engine_impl=args.engine_impl, kv_quant=args.kv_quant,
            base_quant=args.base_quant,
            quant_group_size=args.quant_group_size,
            max_concurrent=args.max_concurrent_sequences,
            scheduler=args.scheduler, decode_chunk=args.decode_chunk,
            spec_draft=args.spec_draft,
            spec_ngram=args.spec_ngram, spec_drafter=args.spec_drafter,
            spec_verify=args.spec_verify, spec_adapt=args.spec_adapt,
            prefix_sharing=args.prefix_sharing,
            continuous_admission=args.continuous_admission,
            prefix_cache=(
                None if args.prefix_cache is None
                else args.prefix_cache == "on"
            ),
            kv_spill=args.kv_spill,
            kv_spill_host_mb=args.kv_spill_host_mb,
            gpu_usage=args.actor_gpu_usage, budget_batch=args.budget_batch,
            scan_chunk=args.decode_scan_chunk,
            autotune=args.autotune == "on", plan_db=args.plan_db,
            capture_logprobs=args.capture_logprobs,
            serving_obs=args.serving_obs, serving_dir=args.serving_dir,
            serving_ring=args.serving_ring,
        )
        _init_control(args)

    import signal

    from distrl_llm_tpu.distributed.control_plane import WorkerServer

    server = WorkerServer(port=args.port)
    if args.serve_model:
        # weight-bus receiver (ISSUE 9): MSG_WEIGHTS frames arrive on their
        # own connection and fill the versioned adapter cache — concurrent
        # with any generate dispatch, which is what makes mid-round swaps
        # possible over the control plane
        server.weights_handler = weights_handler

    gateway_server = None
    gateway_service = None
    if args.gateway_port is not None:
        # multi-tenant serving gateway (ISSUE 19): the service forms
        # class-ordered rounds on THIS worker's engine, serialized against
        # the control plane's generate op through the shared engine mutex
        # (the op acquires it below); the worker's serving ledger and
        # control limits stay attached — gateway rounds record into the
        # same ledger with tenant/priority stamped on each group
        import threading as _threading

        from distrl_llm_tpu.gateway.scheduler import (
            parse_gateway_classes, parse_tenant_quota,
        )
        from distrl_llm_tpu.gateway.server import GatewayServer
        from distrl_llm_tpu.gateway.service import GatewayService

        if args.serve_model == "tiny":
            from distrl_llm_tpu.models import TINY
            from distrl_llm_tpu.tokenizer import CharTokenizer

            gw_tok = CharTokenizer(TINY.vocab_size)
        else:
            from distrl_llm_tpu.tokenizer import load_tokenizer

            gw_tok = load_tokenizer(args.serve_model)
        engine_mutex = _threading.Lock()
        _ENGINE_STATE["engine_mutex"] = engine_mutex
        gateway_service = GatewayService(
            _ENGINE_STATE["engine"], _ENGINE_STATE["params"], gw_tok,
            classes=parse_gateway_classes(args.gateway_classes),
            quota=parse_tenant_quota(args.tenant_quota),
            max_groups_per_round=max(
                1, args.max_concurrent_sequences or 8
            ),
            seed=args.seed,
            engine_lock=engine_mutex,
        ).start()
        gateway_server = GatewayServer(
            gateway_service, port=args.gateway_port
        )

    metrics_server = None
    if args.metrics_port is not None:
        from distrl_llm_tpu import telemetry
        from distrl_llm_tpu.obs import MetricsServer

        # the endpoint serves this worker's cumulative registry; export
        # additionally piggybacks it on every RPC result so the driver's
        # fleet aggregator sees workers without scraping them
        telemetry.configure_obs(export=True)
        metrics_server = MetricsServer(args.metrics_port)

    def _drain(signum, frame):  # noqa: ARG001 — signal handler signature
        # graceful preemption: finish (and deliver) the dispatch in flight,
        # then exit 0 — the handler only sets a flag; the serve loop drains
        # at its next frame boundary
        server.request_shutdown()

    signal.signal(signal.SIGTERM, _drain)
    print(f"PORT {server.port}", flush=True)
    if metrics_server is not None:
        print(f"METRICS {metrics_server.port}", flush=True)
    if gateway_server is not None:
        print(f"GATEWAY {gateway_server.port}", flush=True)
    server.serve_forever(handler)
    if gateway_server is not None:
        gateway_server.close()
    if gateway_service is not None:
        gateway_service.close()
    if metrics_server is not None:
        metrics_server.close()
    serving_ledger = _ENGINE_STATE.get("serving_ledger")
    if serving_ledger is not None:
        # flush open records + the stall/occupancy summary line so a
        # drained worker's serving.jsonl is report-complete
        serving_ledger.close()
    if server.draining:
        # telemetry spans recorded since the last RPC have no response left
        # to ride home on — drop them explicitly rather than leak the list
        from distrl_llm_tpu import telemetry

        telemetry.drain_remote_blob()
        print("DRAINED", flush=True)


if __name__ == "__main__":
    main()
