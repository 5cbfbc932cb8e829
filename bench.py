"""Headline benchmark: rollout decode throughput (tokens/sec/chip) + MFU.

Measures the generation engine (engine/engine.py) at the reference's per-step
rollout volume — 30 prompts × 16 candidates, 350 prompt + up to 1200 new
tokens (train_distributed.py:17–28) — on however many chips are attached.

Baseline derivation (the reference publishes no tokens/sec — BASELINE.md):
100 steps ≈ 2 h on 3× RTX 4090 for Qwen2.5-7B-bnb-4bit, i.e. ~72 s/step with
generation dominating (~50 s by the timing/* split), 480 completions ×
~470 mean tokens → ~4500 tok/s over 3 GPUs ≈ **1500 tok/s per GPU**. That
number anchors ``vs_baseline``; the extra JSON keys record exactly what this
run measured so cross-model comparisons stay honest.

MFU is decode model-FLOPs utilisation: FLOPs/token derived from ModelConfig
(2·matmul-params + attention dot-products at mean KV length) ÷ the peak of
the chip that is there (``telemetry.device_peak_flops``; a ``device_kind``
the table does not hold is an error). On the CPU there is no peak and the
utilisation fields are null.

The benchmark needs a TPU: with none, it exits non-zero and prints no row.
Only an explicit ``JAX_PLATFORMS=cpu`` (tests of the record's shape) makes
it run on the CPU, and that row says ``"backend": "cpu"``.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REFERENCE_TOKENS_PER_SEC_PER_GPU = 1500.0


def _decode_flops_per_token(cfg, mean_kv_len: float) -> float:
    """Model FLOPs per decoded token — the FLOPs math lives on ModelConfig
    (models/configs.py) so bench and the telemetry MFU series agree."""
    return cfg.decode_flops_per_token(mean_kv_len)


def _emit(record: dict) -> None:
    print(json.dumps(record))


def _resolve_base_params(name: str, cfg, dtype, metric: str):
    """One owner of the BENCH_BASE_QUANT contract for every bench mode:
    validate the env var, build/restore the (possibly quantized) base tree
    on the host, and place it on the bench device. Returns (params, quant)
    or (None, quant) after emitting the one-line error record."""
    import jax

    from distrl_llm_tpu.models import init_params

    base_quant = os.environ.get("BENCH_BASE_QUANT", "none")
    if base_quant not in ("none", "int8", "int4"):
        _emit({
            "metric": metric, "value": 0.0,
            "unit": "tok/s/chip", "vs_baseline": 0.0,
            "error": f"invalid BENCH_BASE_QUANT={base_quant!r} "
                     "(expected none/int8/int4)",
            "backend": jax.devices()[0].platform,
        })
        return None, base_quant
    if base_quant == "none":
        return init_params(jax.random.PRNGKey(0), cfg, dtype=dtype), base_quant
    # init + quantize on the HOST: materializing the full-precision 7B tree
    # in HBM just to quantize it would blow the very budget int4 exists to
    # fit under. A forced non-cpu platform list opted out of the host path.
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = jax.devices()[0]
    params = host_quantized_params(
        name, cfg, dtype, base_quant, host,
        # on TPU, cache population is tools/prep_params.py's job — a miss
        # must not spend chip time serializing
        save_on_miss=jax.devices()[0].platform != "tpu",
    )
    return jax.device_put(params, jax.devices()[0]), base_quant


def host_quantized_params(name: str, cfg, dtype, base_quant: str, host,
                          save_on_miss: bool = True):
    """Host-side quantized param tree, disk-cached when BENCH_PARAMS_CACHE
    names a directory. The 7B int4 build is minutes of single-core host
    work (init 15 GiB of bf16 + groupwise quantize) that must not burn
    chip time — tools/prep_params.py runs it on the CPU beforehand, and the
    bench only pays the restore."""
    import jax

    from distrl_llm_tpu.models import init_params
    from distrl_llm_tpu.ops.quant import (
        default_group_size, pack_params_int4, quant_bits_for,
        quantize_params, unpack_params_int4,
    )

    def build():
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
        bits = quant_bits_for(base_quant)
        return quantize_params(
            params, bits=bits, group_size=default_group_size(bits)
        )

    def build_packed():
        # int4 payloads serialize nibble-packed (ops/quant.py transport
        # form — half the cache bytes and disk I/O; int8/none pass through)
        return pack_params_int4(build())

    cache_root = os.environ.get("BENCH_PARAMS_CACHE")
    with jax.default_device(host):
        if not cache_root:
            return build()
        import jax.numpy as jnp

        import orbax.checkpoint as ocp

        path = os.path.abspath(os.path.join(
            cache_root, f"{name}-{base_quant}-{jnp.dtype(dtype).name}"
        ))
        ckpt = ocp.StandardCheckpointer()
        if os.path.isdir(path):
            # explicit host sharding on the abstract tree: the checkpoint
            # was written by a CPU-only prep process, and a sharding-less
            # restore would try to resolve the SAVED process's device
            # strings in THIS process (orbax's cross-topology warning)
            from jax.sharding import SingleDeviceSharding

            abstract = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=SingleDeviceSharding(host)
                ),
                jax.eval_shape(build_packed),
            )
            try:
                return unpack_params_int4(ckpt.restore(path, abstract))
            except Exception as e:  # noqa: BLE001 — stale/pre-packed cache
                # rebuild WITHOUT re-saving (the stale directory is the
                # prep tool's to clear) — a bench on the chip must never
                # die on a cache-schema migration
                print(
                    f"bench: params cache at {path} unreadable under the "
                    f"packed-int4 schema ({type(e).__name__}) — rebuilding",
                    file=sys.stderr,
                )
                return build()
        params = build()
        if save_on_miss:
            # population is the prep tool's job; a cache miss on the chip
            # must not additionally pay a multi-GB serialize
            ckpt.save(path, pack_params_int4(params))
            ckpt.wait_until_finished()
        return params


def _decode_roofline_tok_s(
    params_bytes: int, cfg, kv_quant: str, batch_rows: int,
    mean_kv_len: float, hbm_gbps: float, tokens_per_slot_step: float = 1.0,
) -> float:
    """Bandwidth-bound decode ceiling (tok/s/chip): each decode step must
    stream every resident weight byte once (batch-amortized) plus each
    row's KV read at the mean context length. Decode is HBM-bound on TPU
    (arithmetic intensity ~1 per weight at batch 1), so
    measured/roofline — not MFU — is the honest utilisation statement
    (VERDICT r3 weak #2). v5e HBM ≈ 819 GB/s (BENCH_HBM_GBPS).

    ``tokens_per_slot_step`` scales the ceiling for speculative runs: a
    step that emits ~2 accepted tokens per slot raises the tok/s bound by
    the same factor (BASELINE.md's formula), so pct_of_roofline stays a
    step-rate comparison rather than crediting speculation as chip
    utilisation."""
    # per-token KV bytes via the single owner of the page layout math
    # (budget.page_bytes at page_size=1: int8 payload + f32 scales)
    from distrl_llm_tpu.engine.budget import page_bytes

    kv_bytes_per_token = page_bytes(cfg, 1, kv_quant)
    step_bytes = params_bytes + batch_rows * mean_kv_len * kv_bytes_per_token
    steps_per_s = hbm_gbps * 1e9 / step_bytes
    return batch_rows * steps_per_s * max(tokens_per_slot_step, 1.0)


def _train_flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs per trained token — delegated to ModelConfig
    (models/configs.py), the single owner of the FLOPs estimates."""
    return cfg.train_flops_per_token(seq_len)


def _device_kind() -> str:
    """Canonical device kind of the benching chip (autotune plan-key
    vocabulary: "tpu_v5e", "cpu", …)."""
    from distrl_llm_tpu.autotune import current_device_kind

    return current_device_kind()


def _paged_dispatch_choice():
    """Which paged-attention impl the probe chain actually dispatched
    ("native"/"native_folded"/"native_blocked"/"fixed"/"jaxlib"/
    "reference"), or None if no paged dispatch ran. Distinct per-config
    choices are joined with '+'. Verify-marked records (the speculative
    draft-block dispatch — nonzero verify_len in the key) describe a
    DIFFERENT decision and are reported via spec_verify_impl instead."""
    import importlib

    paged_mod = importlib.import_module("distrl_llm_tpu.ops.paged")
    choices = sorted({
        v for k, v in paged_mod.dispatch_choices.items()
        if not paged_mod.dispatch_key_is_verify(k)
    })
    return "+".join(choices) if choices else None


def _paged_kernel_ran():
    """Plan-vocabulary spelling ("one_page"/"folded"/"blocked") of the
    dispatched paged kernel, falling back to the raw impl name for
    non-native dispatches — the bench record's ``paged_kernel`` field,
    matching the ExecutionPlan field the autotuner stores."""
    choice = _paged_dispatch_choice()
    if choice is None:
        return None
    from distrl_llm_tpu.autotune import IMPL_TO_PAGED_KERNEL

    base = choice.split("!")[0]
    return IMPL_TO_PAGED_KERNEL.get(base, base)


def _paged_grid_steps_per_call(engine, cfg, rows: int):
    """Analytic Pallas grid-step count of one paged-attention call (one
    layer, one decode step): WHICH kernel ran comes from the dispatch
    record (scoped to this run — the dict is cleared before warmup), the
    count is computed at this run's slot geometry. 0 = reference path (no
    Pallas grid); None = no paged dispatch ran / ambiguous record."""
    choice = _paged_dispatch_choice()
    if choice is None or "+" in choice:
        return None
    from distrl_llm_tpu.ops.paged import paged_grid_steps

    return paged_grid_steps(
        choice, batch=rows, num_kv_heads=cfg.num_kv_heads,
        pps=engine.prompt_pages + engine.private_pages,
        pages_per_block=getattr(engine, "pages_per_block", 0) or 0,
    )


def _hbm_peak_bytes():
    """Device HBM peak watermark (ISSUE 8), or None on backends without
    memory stats (CPU fallback rows stay honest nulls)."""
    from distrl_llm_tpu import obs

    stats = obs.hbm_stats()
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
    return int(peak) if peak else None


def _recompile_count() -> int:
    """Compiles BEYOND the first per (fn × shape signature) key since the
    run-scoped tracker reset — 0 in a healthy run; anything else is a
    silent retrace storm the wall-clock numbers quietly paid for."""
    from distrl_llm_tpu import obs

    return obs.retrace_total()


def _serving_pct(ledger, metric: str, q: float, cls: str | None = None):
    """Rounded serving-latency percentile for a bench row, or None without
    a ledger / without samples (dense/fixed/fleet rows). ``cls`` narrows to
    one priority class's samples (gateway rows, ISSUE 19)."""
    if ledger is None:
        return None
    v = ledger.percentile(metric, q, cls=cls)
    return round(v, 3) if v is not None else None


def _serving_stall_frac(ledger):
    if ledger is None:
        return None
    v = ledger.stall_frac()
    return round(v, 4) if v is not None else None


def _gateway_shed_frac(service):
    """Per-class share of shed+preempt deferral events over a gateway
    replay (sums to 1.0), from GatewayService's run-cumulative tallies.
    None off-gateway or when nothing was deferred — the r19 contract
    checks >= 90% of the mass lands on batch/scavenger."""
    if service is None:
        return None
    counts: dict[str, int] = {}
    for action in ("shed", "preempt"):
        for cls, n in service.class_actions.get(action, {}).items():
            counts[cls] = counts.get(cls, 0) + int(n)
    total = sum(counts.values())
    if not total:
        return None
    return {cls: round(n / total, 4) for cls, n in sorted(counts.items())}


def _fleet_tok_s():
    """Fleet-aggregate tok/s gauge when a control-plane fleet published one
    in this process (obs.FleetAggregator). Local rows record null (bench
    drives the engine directly — no fleet exists); BENCH_WORKERS=N rows
    (ISSUE 10 satellite: the reserved slot PR 8 left schema-only) run the
    SAME rollout volume through N control-plane worker processes and fold
    the FleetAggregator's deltas in, so the gauge is populated from the
    workers' piggybacked obs/gen_tokens counters."""
    from distrl_llm_tpu import obs, telemetry

    return telemetry.observe_snapshot()["gauges"].get(obs.FLEET_TOK_S)


def _spawn_fleet(n: int, serve_model: str, max_prompt: int, max_new: int,
                 lora_rank: int, eos_ids, timeout_ms: int):
    """BENCH_WORKERS mode: N control-plane worker processes (obs export on,
    so their registry snapshots piggyback on results and feed the driver's
    FleetAggregator) wrapped as a RemoteEngine. Returns (engine, aggregator,
    procs); an atexit hook SIGKILLs leaked workers so an aborted bench
    never strands children."""
    import atexit
    import signal
    import subprocess

    from distrl_llm_tpu.distributed import connect_remote_engine
    from distrl_llm_tpu.models.lora import lora_scale
    from distrl_llm_tpu.obs import FleetAggregator
    from distrl_llm_tpu.utils.devices import holds_tpu, worker_env

    procs, addrs, chips = [], [], []

    def _reap():
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)

    # registered BEFORE the first spawn (closing over the filling list): a
    # worker that fails or hangs at startup must not strand its
    # already-started siblings past the bench process
    atexit.register(_reap)
    for _ in range(n):
        # one process per chip: each worker's chip is named in its
        # environment; this process, holding the TPU itself, is refused
        env, chip = worker_env(
            os.environ, {"DISTRL_OBS": "1"}, chips_in_use=chips,
            parent_holds_tpu=holds_tpu(),
        )
        if chip is not None:
            chips.append(chip)
        proc = subprocess.Popen(
            [
                sys.executable, "-m",
                "distrl_llm_tpu.distributed.worker_main",
                "--port", "0", "--serve-model", serve_model,
                "--max-prompt-tokens", str(max_prompt),
                "--max-new-tokens", str(max_new),
                "--seed", "0", "--lora-rank", str(lora_rank),
                "--lora-alpha", "16",
            ],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env,
        )
        procs.append(proc)  # in the reaper's sight before any wait
        line = proc.stdout.readline().strip()
        if not line.startswith("PORT "):
            raise RuntimeError(f"bench worker failed to start: {line!r}")
        addrs.append(("127.0.0.1", int(line.split()[1])))
    engine = connect_remote_engine(
        addrs, max_prompt_tokens=max_prompt, max_new_tokens=max_new,
        timeout_ms=timeout_ms,
        lora_scale=lora_scale(lora_rank, 16.0),
        eos_token_ids=[int(e) for e in eos_ids],
        weight_bus=os.environ.get("BENCH_WEIGHT_BUS", "dispatch"),
    )
    return engine, FleetAggregator(engine.driver), procs


def _attn_fallback_fired(attn_impl: str) -> bool:
    """True when attention() fell back to the XLA reference path during the
    (traced) first step — a "flash" record with this flag set measured
    reference attention, not the kernel."""
    if attn_impl == "reference":
        return False
    import importlib

    # ops/__init__ re-exports the attention FUNCTION under the same name;
    # import_module reliably returns the module
    attn_mod = importlib.import_module("distrl_llm_tpu.ops.attention")
    return attn_mod._flash_fallback_warned


class _BenchTurnHook:
    """Synthetic raw-token engine turn hook for the multi-turn A/B
    (BENCH_ENV/BENCH_MAX_TURNS, ISSUE 17): every candidate re-enters
    ``max_turns - 1`` times with a fixed observation block appended to its
    resident KV chain — the engine-side cost of multi-turn rollouts
    (turn-resume fixups, admission contention, idle interception) without
    any tokenizer or environment logic, so the row measures scheduling,
    not env.step."""

    def __init__(self, total: int, max_turns: int, obs_len: int, vocab: int):
        rng = np.random.default_rng(7)
        self.obs = rng.integers(1, vocab, size=obs_len).astype(np.int32)
        self.max_turns = max(1, int(max_turns))
        self.total = int(total)
        self.turns = np.ones(self.total, np.int64)
        self.step_ms: list[float] = []
        self.finished_turns: list[int] = []

    def reset(self) -> None:
        self.turns[:] = 1
        self.step_ms = []
        self.finished_turns = []

    def __call__(self, cand_id: int, gen_tokens) -> "np.ndarray | None":
        t0 = time.perf_counter()
        done = self.turns[cand_id] >= self.max_turns
        self.step_ms.append((time.perf_counter() - t0) * 1e3)
        if done:
            self.finished_turns.append(int(self.turns[cand_id]))
            return None
        self.turns[cand_id] += 1
        return self.obs

    def declined(self, cand_id: int) -> None:
        self.finished_turns.append(int(self.turns[cand_id]))


def _learner_bench(cfg, name: str) -> int:
    """BENCH_MODE=learner: time the jitted train step at the reference
    learner shapes (micro 8 × [350 prompt + 1200 answer], distributed_
    actor.py:217–229) — the second headline metric next to rollout tok/s."""
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.learner.optim import make_optimizer
    from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
    from distrl_llm_tpu.models import init_lora_params
    from distrl_llm_tpu.models.lora import lora_scale

    n_rows = int(os.environ.get("BENCH_ROWS", "8"))
    p_len = int(os.environ.get("BENCH_MAX_PROMPT", "350"))
    t_len = int(os.environ.get("BENCH_MAX_NEW", "1200"))
    micro = int(os.environ.get("BENCH_MICRO", str(min(n_rows, 8))))
    lora_rank = int(os.environ.get("BENCH_LORA_RANK", "32"))
    logit_chunk = int(os.environ.get("BENCH_LOGPROB_CHUNK", "128"))
    # the chip that is there, from the one table: None on the CPU, an error
    # for a TPU device_kind the table does not hold
    peak_flops = telemetry.device_peak_flops()
    steps = int(os.environ.get("BENCH_STEPS", "3"))
    # "reference" (XLA attention, the config default) vs "flash" (Pallas
    # kernel) — the A/B that decides the TPU-side default at S=1550
    attn_impl = os.environ.get("BENCH_ATTN_IMPL", "reference")
    if attn_impl not in ("reference", "flash", "splash"):
        _emit({
            "metric": "learner_tokens_per_sec_per_chip", "value": 0.0,
            "unit": "tok/s/chip", "vs_baseline": 0.0,
            "error": f"invalid BENCH_ATTN_IMPL={attn_impl!r} "
                     "(expected reference/flash/splash)",
            "backend": jax.devices()[0].platform,
        })
        return 1

    dtype = jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32
    # the learner trains LoRA over the SAME (possibly int4) base the
    # rollout serves (QLoRA — grads flow through dequant into LoRA only,
    # pinned by tests/test_quant.py::test_train_step_over_quantized_base)
    params, base_quant = _resolve_base_params(
        name, cfg, dtype, "learner_tokens_per_sec_per_chip")
    if params is None:
        return 1
    lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=lora_rank)
    optimizer = make_optimizer(2e-5, use_8bit=True)
    opt_state = optimizer.init(lora)
    # BENCH_LEARN_OBS=1 (ISSUE 16): bench the ARMED step — the dynamics
    # bundle rides the loss fetch, so its cost (if any) lands in
    # step_seconds, and the record carries the policy-health fields. Off
    # (default) emits the same fields as null, pinned by
    # test_bench_contract so dashboards can rely on the keys.
    learn_obs = os.environ.get("BENCH_LEARN_OBS", "0") == "1"
    step = make_train_step(
        cfg, learner_type="grpo", optimizer=optimizer,
        lora_scale=lora_scale(lora_rank, 16.0), micro_size=micro,
        donate=False, logit_chunk=logit_chunk, attn_impl=attn_impl,
        clip_ratio=0.2 if learn_obs else 0.0,
        emit_dynamics=learn_obs,
    )
    rng = np.random.default_rng(0)
    batch = UpdateBatch(
        prompt_ids=jnp.asarray(rng.integers(1, cfg.vocab_size, (n_rows, p_len)), jnp.int32),
        prompt_mask=jnp.ones((n_rows, p_len), jnp.int32),
        answer_ids=jnp.asarray(rng.integers(1, cfg.vocab_size, (n_rows, t_len)), jnp.int32),
        answer_mask=jnp.ones((n_rows, t_len), jnp.int32),
        coeffs=jnp.asarray(rng.normal(size=n_rows), jnp.float32),
        sample_mask=jnp.ones((n_rows,), jnp.float32),
        # synthetic behavior logprobs give the clip objective (and the
        # KL/ratio telemetry) a realistic off-policy spread to chew on
        behavior_logps=(
            jnp.asarray(
                rng.normal(-2.0, 0.25, size=(n_rows, t_len)), jnp.float32
            )
            if learn_obs else None
        ),
    )
    # Time against a device-to-host FETCH: float(loss) cannot return early,
    # the scalar's bytes depend on the whole step chain.
    import importlib

    importlib.import_module("distrl_llm_tpu.obs").reset_compile_tracker()
    kl_per_step: list[float] = []
    dynamics = None
    t0 = time.perf_counter()
    if learn_obs:
        lora, opt_state, loss, dynamics = step(lora, opt_state, params, batch)
    else:
        lora, opt_state, loss = step(lora, opt_state, params, batch)
    float(loss)
    compile_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        if learn_obs:
            lora, opt_state, loss, dynamics = step(
                lora, opt_state, params, batch
            )
            if "kl" in dynamics:
                # device reference only — converting here would force a
                # per-step host sync the off path doesn't pay, skewing dt
                kl_per_step.append(dynamics["kl"])
        else:
            lora, opt_state, loss = step(lora, opt_state, params, batch)
    loss_val = float(loss)
    dt = (time.perf_counter() - t0) / steps

    tokens = n_rows * (p_len + t_len)
    tps = tokens / dt
    # the step here is built with NO mesh, so jit places it on ONE device —
    # dividing by device_count would understate per-chip throughput/MFU by
    # the host's chip count (sharded-step benching comes with a mesh config)
    n_chips = 1
    flops = _train_flops_per_token(cfg, p_len + t_len)
    mfu = (tps / n_chips) * flops / peak_flops if peak_flops else None
    record = {
        "metric": "learner_tokens_per_sec_per_chip",
        "value": round(tps / n_chips, 1),
        "unit": "tok/s/chip",
        # baseline: reference learner processes 480 completions × ~1550
        # tokens per ~20 s update (timing split, BASELINE.md) ≈ 37k tok/s
        # over 1 GPU doing the update
        "vs_baseline": round(tps / n_chips / 37000.0, 3),
        "mfu": round(mfu, 6) if mfu is not None else None,
        "model": name,
        "base_quant": base_quant,
        "backend": jax.devices()[0].platform,
        "rows": n_rows, "micro": micro, "seq": p_len + t_len,
        "attn_impl": attn_impl,
        # honesty flag: attention() falls back to the reference path with
        # only a warning — a "flash" record with attn_fallback true measured
        # XLA reference attention, not the kernel
        "attn_fallback": _attn_fallback_fired(attn_impl),
        "logprob_chunk": logit_chunk,
        "step_seconds": round(dt, 3),
        "compile_plus_first_step_seconds": round(compile_dt, 2),
        "chips": n_chips,
        "devices_visible": jax.device_count(),
        "train_flops_per_token_gflop": round(flops / 1e9, 6),
        "loss": loss_val,
        # measured-attribution fields (ISSUE 8), shared with the rollout
        # record: device HBM watermark and shape-keyed retrace count
        "hbm_peak_bytes": _hbm_peak_bytes(),
        "recompile_count": _recompile_count(),
        # training-dynamics fields (ISSUE 16): null unless BENCH_LEARN_OBS
        # armed the fused bundle; direction-neutral in bench_history.py (a
        # curve shift is not a perf regression)
        "entropy": (
            round(float(dynamics["entropy"]), 6)
            if dynamics is not None and "entropy" in dynamics else None
        ),
        "kl_p90": (
            round(sorted(float(k) for k in kl_per_step)[
                min(int(len(kl_per_step) * 0.9), len(kl_per_step) - 1)
            ], 6)
            if kl_per_step else None
        ),
        "clip_frac": (
            round(float(dynamics["clip_frac"]), 6)
            if dynamics is not None and "clip_frac" in dynamics else None
        ),
        "ratio_cap_frac": (
            round(float(dynamics["cap_frac"]), 6)
            if dynamics is not None and "cap_frac" in dynamics else None
        ),
    }
    if mfu is not None and mfu > 0.6:
        # >60% MFU on a fwd+bwd step means the timing is broken, not that
        # the chip is fast — mark the record unusable rather than quotable
        record["error"] = (
            f"implausible timing (mfu {mfu:.2f}): steps did not synchronize"
        )
        record["vs_baseline"] = 0.0
    _emit(record)
    return 0


def main() -> int:
    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu import telemetry
    from distrl_llm_tpu.utils.devices import (
        enable_compile_cache, require_tpu,
    )

    enable_compile_cache()
    # no TPU and no explicit JAX_PLATFORMS=cpu: raise (exit non-zero, no
    # row) — this file never moves itself to another backend
    devices = require_tpu(
        jax.devices(), cpu_requested=os.environ.get("JAX_PLATFORMS")
    )

    # Driver-default production config: the plain `python bench.py` the
    # driver runs should measure this framework's best honest TPU config.
    # The knobs now come from the autotune plan DB when it holds a MEASURED
    # entry for this (device, model, geometry) — `_apply_production_defaults`
    # below, after the geometry is parsed — with the historical hard-coded
    # guesses (int8 KV + multiway top-p + chunk 16) only as the DB-less
    # fallback. Round 5's headline regression was exactly such a guess
    # (scan-chunk 16, measured 2.5× slower — VERDICT.md); with a populated
    # DB that misconfiguration is unrepresentable. Watcher/A-B invocations
    # set BENCH_PRODUCTION_DEFAULTS=0 and configure knobs explicitly, so
    # the defaults stay out of their way.
    prod_defaults = os.environ.get("BENCH_PRODUCTION_DEFAULTS", "1") == "1"

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine import GenerationEngine, PagedGenerationEngine
    from distrl_llm_tpu.models import QWEN2_0_5B, TINY, init_lora_params
    from distrl_llm_tpu.models.configs import QWEN2_7B

    name = os.environ.get("BENCH_MODEL", "qwen2.5-0.5b")
    cfg = {"tiny": TINY, "qwen2.5-0.5b": QWEN2_0_5B, "qwen2.5-7b": QWEN2_7B}[name]
    if os.environ.get("BENCH_MODE") == "learner":
        return _learner_bench(cfg, name)
    n_prompts = int(os.environ.get("BENCH_PROMPTS", "30"))
    n_cand = int(os.environ.get("BENCH_CANDIDATES", "16"))
    max_prompt = int(os.environ.get("BENCH_MAX_PROMPT", "350"))
    max_new = int(os.environ.get("BENCH_MAX_NEW", "1200"))
    lora_rank = int(os.environ.get("BENCH_LORA_RANK", "32"))
    # the chip that is there, from the one table: None on the CPU, an error
    # for a TPU device_kind the table does not hold
    peak_flops = telemetry.device_peak_flops()

    if prod_defaults and devices[0].platform == "tpu":
        from distrl_llm_tpu.autotune import resolve_plan

        # a measured plan for THIS (device, model, geometry) overrides the
        # hard-coded guesses; setdefault keeps explicit BENCH_* pins winning
        resolved = resolve_plan(
            model_cfg=cfg, max_prompt_tokens=max_prompt,
            max_new_tokens=max_new, rows=n_prompts * n_cand,
        )
        plan_applied = False
        if resolved.source == "db":
            plan = resolved.plan
            plan_engine = (
                "paged" if plan.decode_path in ("paged", "speculative")
                else "dense"
            )
            pinned_engine = os.environ.get("BENCH_ENGINE")
            if pinned_engine is not None and (
                (pinned_engine == "paged") != (plan_engine == "paged")
            ):
                # the plan's knobs were measured on a DIFFERENT decode path
                # than the user pinned — applying its scan_chunk/top_p here
                # would bench an unmeasured combination (the r5 trap), so
                # the whole plan is skipped, loudly
                print(
                    f"bench: stored plan is for the {plan_engine} path but "
                    f"BENCH_ENGINE={pinned_engine} is pinned — using static "
                    "defaults",
                    file=sys.stderr,
                )
            # a "speculative" winner is self-describing since the plan
            # space grew spec fields (spec_draft_len/spec_drafter/
            # spec_verify — ISSUE 6): the draft config comes from the plan
            # itself, and only the slot cap (not a plan-space choice)
            # defaults to the benched row count. Pre-spec-field DB entries
            # (spec_draft_len 0) still need explicit BENCH_SPEC_DRAFT.
            elif plan.decode_path == "speculative" and not (
                os.environ.get("BENCH_SPEC_DRAFT") or plan.spec_draft_len
            ):
                print(
                    "bench: stored plan is speculative but carries no "
                    "spec_draft_len and BENCH_SPEC_DRAFT is unset — using "
                    "static defaults",
                    file=sys.stderr,
                )
            else:
                os.environ.setdefault("BENCH_SCAN_CHUNK", str(plan.scan_chunk))
                if plan.top_p_impl:
                    os.environ.setdefault("BENCH_TOP_P_IMPL", plan.top_p_impl)
                if plan.decode_path in ("paged", "speculative"):
                    os.environ.setdefault("BENCH_ENGINE", "paged")
                    if plan.decode_path == "speculative":
                        os.environ.setdefault("BENCH_SCHEDULER", "refill")
                        if plan.spec_draft_len:
                            os.environ.setdefault(
                                "BENCH_SPEC_DRAFT", str(plan.spec_draft_len)
                            )
                        if plan.spec_drafter:
                            os.environ.setdefault(
                                "BENCH_SPEC_DRAFTER", plan.spec_drafter
                            )
                        if plan.spec_verify:
                            os.environ.setdefault(
                                "BENCH_SPEC_VERIFY", plan.spec_verify
                            )
                        os.environ.setdefault(
                            "BENCH_MAX_CONCURRENT",
                            str(min(n_prompts * n_cand, 128)),
                        )
                plan_applied = True
        if plan_applied:
            # quantized-serving plan fields (ISSUE 15): a MEASURED base/KV
            # format becomes the production default for this geometry;
            # explicit BENCH_* pins still win (setdefault)
            if resolved.plan.base_quant:
                os.environ.setdefault(
                    "BENCH_BASE_QUANT", resolved.plan.base_quant
                )
            if resolved.plan.kv_format:
                os.environ.setdefault(
                    "BENCH_KV_FORMAT", resolved.plan.kv_format
                )
        if not plan_applied:
            os.environ.setdefault("BENCH_SCAN_CHUNK", "16")
            os.environ.setdefault("BENCH_TOP_P_IMPL", "bisect_mw")
        # DB-less fallback: int8 KV stays the hard-coded production guess
        # (a stored kv_format above outranks it via BENCH_KV_FORMAT)
        os.environ.setdefault("BENCH_KV_QUANT", "int8")

    # the CPU fallback's dot thunk has no bf16 support — use f32 off-TPU
    dtype = jnp.bfloat16 if devices[0].platform == "tpu" else jnp.float32
    params, base_quant = _resolve_base_params(
        name, cfg, dtype, "rollout_tokens_per_sec_per_chip")
    if params is None:
        return 1
    lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=lora_rank, dtype=dtype)
    from distrl_llm_tpu.config import parse_buckets

    buckets = parse_buckets(os.environ.get("BENCH_PROMPT_BUCKETS"))
    # Fraction of the batch left-padded to half length. Default 1/3 models a
    # ragged batch; to MEASURE bucketing, set BENCH_SHORT_FRACTION=1 and a
    # bucket ≥ max_prompt/2 (bucket choice follows the batch's LONGEST real
    # prompt, so any full-length row pins the full bucket).
    short_fraction = float(os.environ.get("BENCH_SHORT_FRACTION", str(1 / 3)))
    engine_cls = (
        PagedGenerationEngine if os.environ.get("BENCH_ENGINE") == "paged"
        else GenerationEngine
    )
    # KV format (ISSUE 15): BENCH_KV_FORMAT (plan-field spelling) or the
    # legacy BENCH_KV_QUANT; an explicit value — including "none" — pins the
    # engine past any stored plan, unset leaves the plan DB in charge
    # (ExecutionPlan.kv_format; empty DB = "none", the historical default)
    kv_env = os.environ.get("BENCH_KV_FORMAT") or os.environ.get(
        "BENCH_KV_QUANT"
    )
    if kv_env and kv_env not in ("none", "int8"):
        _emit({
            "metric": "rollout_tokens_per_sec_per_chip", "value": 0.0,
            "unit": "tok/s/chip", "vs_baseline": 0.0,
            "error": f"invalid BENCH_KV_FORMAT/BENCH_KV_QUANT={kv_env!r} "
                     "(expected none/int8)",
            "backend": jax.devices()[0].platform,
        })
        return 1
    engine_kwargs = {"kv_quant": kv_env}  # None = plan-DB-resolvable
    # Engine-level plan resolution tracks bench's own: production-default
    # runs let the engine consult the DB (the feature), while explicit A/B
    # invocations (BENCH_PRODUCTION_DEFAULTS=0) pin the static
    # defaults so a populated user DB can't silently retune unpinned knobs
    # (formulation, buckets, top-p) out from under the recorded config.
    # BENCH_AUTOTUNE=0/1 overrides either way.
    engine_kwargs["autotune"] = os.environ.get(
        "BENCH_AUTOTUNE", "1" if prod_defaults else "0"
    ) == "1"
    # the engine's own plan resolution must hit the SAME rows-aware DB key
    # bench's production-defaults consult used — otherwise two tune runs at
    # different volumes could split one run's knobs across two entries
    engine_kwargs["plan_rows"] = n_prompts * n_cand
    if os.environ.get("BENCH_SCAN_CHUNK"):
        # K decode steps fused per dispatch (dense engine / paged refill) —
        # the dispatch-overhead lever; see tools/dispatch_probe.py
        engine_kwargs["scan_chunk"] = int(os.environ["BENCH_SCAN_CHUNK"])
    if os.environ.get("BENCH_ENGINE") == "paged":
        engine_kwargs["scheduler"] = os.environ.get("BENCH_SCHEDULER", "waves")
        if os.environ.get("BENCH_PAGED_IMPL"):
            # force a specific paged-attention launch ("native",
            # "native_folded", "kernel") for kernel A/Bs; default "auto"
            # walks the probe-gated chain
            engine_kwargs["paged_impl"] = os.environ["BENCH_PAGED_IMPL"]
        if os.environ.get("BENCH_SPEC_DRAFT"):
            # speculative decoding (needs the refill scheduler + cap)
            engine_kwargs["spec_draft"] = int(os.environ["BENCH_SPEC_DRAFT"])
            if os.environ.get("BENCH_SPEC_DRAFTER"):
                # "ngram" (prompt lookup) | "self" (previous-LoRA drafter)
                engine_kwargs["spec_drafter"] = os.environ[
                    "BENCH_SPEC_DRAFTER"]
            if os.environ.get("BENCH_SPEC_VERIFY"):
                # "fused" (one-sweep verify kernel) | "unrolled" (A/B)
                engine_kwargs["spec_verify"] = os.environ["BENCH_SPEC_VERIFY"]
            if os.environ.get("BENCH_SPEC_ADAPT") == "1":
                engine_kwargs["spec_adapt"] = True
        if os.environ.get("BENCH_KV_PAGES"):
            # refill decode-page pool budget (--actor_gpu_usage equivalent);
            # exercises page-gated admission + preempt-by-recompute
            engine_kwargs["max_kv_pages"] = int(os.environ["BENCH_KV_PAGES"])
        if os.environ.get("BENCH_PREFIX_SHARING") == "1":
            # copy-on-write prompt-prefix sharing (ISSUE 12): a group's
            # candidates alias one refcounted prompt page chain
            engine_kwargs["prefix_sharing"] = True
        if os.environ.get("BENCH_CONT_ADMISSION"):
            # continuous admission A/B (ISSUE 12): 1 = lazy per-group
            # prefill + pooled chains, 0 = pin the fixed-batch control
            # past any stored plan (unset leaves the plan DB in charge)
            engine_kwargs["continuous_admission"] = (
                os.environ["BENCH_CONT_ADMISSION"] == "1"
            )
        if os.environ.get("BENCH_PREFIX_CACHE"):
            # tiered KV cache A/B (ISSUE 18): 1 = radix prefix cache on,
            # 0 = pin cache-off past any stored plan (unset leaves the
            # plan DB in charge — the BENCH_CONT_ADMISSION convention)
            engine_kwargs["prefix_cache"] = (
                os.environ["BENCH_PREFIX_CACHE"] == "1"
            )
        if os.environ.get("BENCH_KV_SPILL") == "1":
            # tier-2 host spill rides tier 1 (needs BENCH_PREFIX_CACHE=1)
            engine_kwargs["kv_spill"] = True
            if os.environ.get("BENCH_KV_SPILL_HOST_MB"):
                engine_kwargs["kv_spill_host_mb"] = int(
                    os.environ["BENCH_KV_SPILL_HOST_MB"]
                )
    if os.environ.get("BENCH_MAX_CONCURRENT"):
        engine_kwargs["max_concurrent_rows"] = int(os.environ["BENCH_MAX_CONCURRENT"])
    # BENCH_EOS_RATE: approximate per-step stop probability. Random-init
    # weights essentially never sample the real EOS id, so every row decodes
    # max_new tokens — which hides scheduler differences (waves vs refill
    # only diverge under length VARIANCE). A random id subset covering
    # ~rate of the vocab makes stops ~geometric with mean ~1/rate, the
    # realistic shape (reference rollouts average ~470 of 1200 tokens).
    eos_rate = float(os.environ.get("BENCH_EOS_RATE", "0"))
    if os.environ.get("BENCH_NO_EOS") == "1":
        # unreachable id: every row decodes exactly max_new tokens, making
        # the benched volume deterministic (the pinned fallback's contract)
        eos_ids = [-1]
    elif eos_rate > 0:
        eos_rng = np.random.default_rng(42)
        n_eos = max(1, round(eos_rate * cfg.vocab_size))
        eos_ids = eos_rng.choice(cfg.vocab_size, size=n_eos, replace=False).tolist()
    else:
        eos_ids = [151645 % cfg.vocab_size]
    # BENCH_WORKERS=N (ISSUE 10 satellite): run the same rollout volume
    # through N control-plane worker processes instead of a local engine —
    # the fleet row that finally populates the reserved fleet_tok_s slot
    # (and the weight-bus provenance fields) from real FleetAggregator
    # deltas. Workers serve their own engines, so the local engine-plan
    # introspection fields honestly read null on these rows.
    fleet_n = int(os.environ.get("BENCH_WORKERS", "0"))
    fleet_agg = None
    fleet_procs: list = []
    if fleet_n > 0:
        serve_model = os.environ.get(
            "BENCH_WORKER_MODEL", name if name == "tiny" else ""
        )
        if not serve_model:
            _emit({
                "metric": "rollout_tokens_per_sec_per_chip", "value": 0.0,
                "unit": "tok/s/chip", "vs_baseline": 0.0,
                "error": "BENCH_WORKERS needs BENCH_WORKER_MODEL (a local "
                         "checkpoint path, or 'tiny') for non-tiny models",
                "backend": jax.devices()[0].platform,
            })
            return 1
        engine, fleet_agg, fleet_procs = _spawn_fleet(
            fleet_n, serve_model, max_prompt, max_new, lora_rank, eos_ids,
            timeout_ms=int(os.environ.get("BENCH_RPC_TIMEOUT_MS", "240000")),
        )
    else:
        engine = engine_cls(
            cfg, max_prompt_tokens=max_prompt, max_new_tokens=max_new,
            eos_token_ids=eos_ids, pad_token_id=151643 % cfg.vocab_size,
            prompt_buckets=buckets or None,
            **engine_kwargs,
        )
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, min(cfg.vocab_size, 50000), size=(n_prompts, max_prompt)).astype(np.int32)
    pmask = np.ones_like(prompts)
    n_short = int(round(n_prompts * min(max(short_fraction, 0.0), 1.0)))
    pmask[:n_short, : max_prompt // 2] = 0
    prompts[:n_short, : max_prompt // 2] = getattr(
        engine, "pad_id", 151643 % cfg.vocab_size
    )
    top_p_impl = os.environ.get("BENCH_TOP_P_IMPL")  # e.g. "bisect_mw"
    if top_p_impl:
        from distrl_llm_tpu.ops.sampling import TOP_P_IMPLS

        if top_p_impl not in TOP_P_IMPLS:
            _emit({
                "metric": "rollout_tokens_per_sec_per_chip", "value": 0.0,
                "unit": "tok/s/chip", "vs_baseline": 0.0,
                "error": f"invalid BENCH_TOP_P_IMPL={top_p_impl!r} "
                         f"(expected one of {sorted(TOP_P_IMPLS)})",
                "backend": jax.devices()[0].platform,
            })
            return 1
    sampling = SamplingConfig(
        max_tokens=max_new, temperature=1.2, top_p=0.95, n=n_cand,
        top_p_impl=top_p_impl,
    )

    def run(seed: int):
        t0 = time.perf_counter()
        out = engine.generate(params, lora, prompts, pmask, sampling, jax.random.PRNGKey(seed))
        dt = time.perf_counter() - t0
        return out, dt

    # clear stale dispatch records (e.g. a pre-run trace on another backend
    # or the "no-kernel-path" sentinel from an unrelated config): dispatch
    # decisions are made at trace time, i.e. during the warmup below, so
    # clearing here scopes paged_attn_impl to THIS run's geometry (ADVICE r3)
    import importlib

    importlib.import_module("distrl_llm_tpu.ops.paged").dispatch_choices.clear()
    # same scoping for the ISSUE 15 trace-time dispatch records: which
    # sampler implementation and which quant-matmul path THIS run ran
    importlib.import_module(
        "distrl_llm_tpu.ops.sampling"
    ).sample_dispatch_choices.clear()
    importlib.import_module(
        "distrl_llm_tpu.ops.quant_matmul"
    ).dispatch_choices.clear()
    # measured bytes/token (ISSUE 15): have the engines file their decode
    # step programs' XLA cost_analysis (resets with the tracker above)
    os.environ.setdefault("DISTRL_MEASURE_COST", "1")
    # scope the obs compile/retrace tracker to this run the same way: the
    # recompile_count field must describe THIS config's programs only
    importlib.import_module("distrl_llm_tpu.obs").reset_compile_tracker()
    # multi-turn A/B arm (ISSUE 17): BENCH_ENV marks this row as a
    # synthetic multi-turn env run — every candidate re-enters
    # BENCH_MAX_TURNS - 1 times through the engine turn hook, with the
    # observation appended to its resident KV chain (no re-prefill). The
    # hook is armed BEFORE warmup so compilation covers the turn-resume
    # fixup program; the single-turn control is the same invocation
    # without BENCH_ENV.
    turn_hook = None
    bench_env = os.environ.get("BENCH_ENV")
    if bench_env:
        if (
            fleet_n
            or getattr(engine, "scheduler", None) != "refill"
            or not getattr(engine, "max_concurrent_rows", 0)
            or getattr(engine, "spec_draft", 0)
        ):
            _emit({
                "metric": "rollout_tokens_per_sec_per_chip", "value": 0.0,
                "unit": "tok/s/chip", "vs_baseline": 0.0,
                "error": "BENCH_ENV needs a local paged refill engine with "
                         "BENCH_MAX_CONCURRENT set and no BENCH_SPEC_DRAFT "
                         "(the turn hook rides the refill scheduler)",
                "backend": jax.devices()[0].platform,
            })
            return 1
        turn_hook = _BenchTurnHook(
            total=n_prompts * n_cand,
            max_turns=int(os.environ.get("BENCH_MAX_TURNS", "2")),
            obs_len=int(os.environ.get("BENCH_ENV_OBS_TOKENS", "16")),
            vocab=cfg.vocab_size,
        )
        engine.turn_hook = turn_hook
    _, compile_dt = run(0)  # warmup: includes prefill+decode compilation
    if getattr(engine, "prefix_cache", False):
        # cache-on arms (ISSUE 18): the first warmup round ran COLD — the
        # tree was empty, so the warm-admission programs (suffix prefill
        # over cached pages, host-store page restore) never traced. A
        # second warmup round admits through the now-populated tree,
        # keeping those compiles out of timed round 1 like the cold
        # warmup keeps prefill/decode compiles out.
        _, warm_dt = run(0)
        compile_dt += warm_dt
    # serving observability over the TIMED rounds only (ISSUE 13): arm a
    # ledger on continuous-admission engines AFTER warmup so the recorded
    # TTFT/queue-wait percentiles describe steady-state serving, not the
    # compile-inflated warmup round. Fixed-batch and dense rows keep the
    # fields null (the cb A/B's contract, pinned in test_bench_contract).
    serving_ledger = None
    if getattr(engine, "continuous_admission", False):
        from distrl_llm_tpu.serving_obs import ServingLedger

        serving_ledger = ServingLedger(ring_size=4096)
        engine.serving_ledger = serving_ledger
    # BENCH_CONTROL_FRAC (ISSUE 14): pin a governor-shrunk admission
    # fraction on the timed rounds — the static twin of an HBM-governor
    # shrink, so an A/B against the unpinned row quantifies a controller
    # run's throughput cost. Attached AFTER warmup (the control fields
    # describe the timed window); rows without it keep the fields null.
    control_limits = None
    frac_env = os.environ.get("BENCH_CONTROL_FRAC")
    if frac_env and getattr(engine, "continuous_admission", False):
        from distrl_llm_tpu.control import ControlLimits

        control_limits = ControlLimits()
        control_limits.set_admission_frac(float(frac_env))
        engine.control_limits = control_limits
    from distrl_llm_tpu import telemetry as _tlm

    control_actions0 = _tlm.observe_snapshot()["counters"].get(
        "control/actions", 0.0
    )
    # BENCH_GATEWAY=1 (ISSUE 19): drive the timed window through the
    # serving gateway instead of fixed batched rounds — a seeded open-loop
    # arrival trace (BENCH_ARRIVAL_PROCESS, default burst, at
    # BENCH_ARRIVAL_RPS) replayed over the streaming HTTP front-end, with
    # tenant/priority classes mixed in. BENCH_SHED_FLOOR pins a class-aware
    # shed floor on the timed window (2 = scavenger only, 1 = batch too) —
    # the static twin of the class-aware SLO governor, same convention as
    # BENCH_CONTROL_FRAC. Gateway rows are only comparable to gateway rows
    # at the same arrival rate (bench_history comparable()).
    gateway_on = os.environ.get("BENCH_GATEWAY") == "1"
    gateway_rate = None
    gateway_service = None
    gateway_summary = None
    if gateway_on and (
        fleet_n
        or turn_hook is not None
        or not getattr(engine, "continuous_admission", False)
        or getattr(engine, "spec_draft", 0)
    ):
        _emit({
            "metric": "rollout_tokens_per_sec_per_chip", "value": 0.0,
            "unit": "tok/s/chip", "vs_baseline": 0.0,
            "error": "BENCH_GATEWAY needs a local continuous-admission "
                     "refill engine without BENCH_ENV/BENCH_SPEC_DRAFT "
                     "(the gateway schedules the plain refill boundaries)",
            "backend": jax.devices()[0].platform,
        })
        return 1
    if fleet_agg is not None:
        # first refresh sets the per-worker (ts, gen_tokens) marks off the
        # warmup round's piggybacked snapshots; the post-timing refresh
        # then yields an honest tokens/s delta over the timed window
        fleet_agg.refresh(force=True)
    # BENCH_REPEATS > 1 (the pinned fallback sets 3): sum tokens over N
    # timed runs so sub-second CPU measurements aren't dominated by
    # single-run jitter
    repeats = max(int(os.environ.get("BENCH_REPEATS", "1")), 1)
    timed = []
    total_tokens = 0
    sum_steps = sum_alive = 0
    have_steps = have_alive = True
    # engine.last_spec_stats covers ONE generate() round; steps_dispatched
    # sums over all repeats, so the grid totals must be summed the same way
    # or the quotient is ~repeats× off
    sum_spec_grid = spec_grid_rounds = 0
    env_counts: list[int] = []
    env_step_ms: list[float] = []
    if gateway_on:
        # the timed window IS the open-loop replay: wall clock covers the
        # whole drain (queueing included), so tok/s here is goodput under
        # the arrival process, not a closed-loop batch ceiling. Clients
        # fire on the trace's schedule whether or not earlier requests
        # completed — under 2× overload the queue grows, which is the
        # point of the r19 artifact.
        from distrl_llm_tpu.gateway import traffic as _traffic
        from distrl_llm_tpu.gateway.scheduler import parse_tenant_quota
        from distrl_llm_tpu.gateway.server import GatewayServer
        from distrl_llm_tpu.gateway.service import GatewayService
        from distrl_llm_tpu.tokenizer import CharTokenizer

        gateway_rate = float(os.environ.get("BENCH_ARRIVAL_RPS", "8"))
        gw_floor = os.environ.get("BENCH_SHED_FLOOR")
        if gw_floor:
            # static class-aware shed floor (2 = scavenger only, 1 = batch
            # too): the overload arm's stand-in for the SLO governor, so
            # A/B rows don't depend on the governor's dwell timing. Reuses
            # the BENCH_CONTROL_FRAC ControlLimits when both are set.
            if control_limits is None:
                from distrl_llm_tpu.control import ControlLimits

                control_limits = ControlLimits()
                engine.control_limits = control_limits
            control_limits.set_shed(True, floor=int(gw_floor))
        gateway_service = GatewayService(
            engine, params, CharTokenizer(cfg.vocab_size), lora=lora,
            quota=parse_tenant_quota(
                os.environ.get("BENCH_TENANT_QUOTA") or None
            ),
            max_groups_per_round=int(
                os.environ.get("BENCH_MAX_CONCURRENT", "0")
                or getattr(engine, "max_concurrent_rows", 0) or 8
            ),
            seed=7,
        ).start()
        gateway_server = GatewayServer(gateway_service, port=0)
        try:
            arrivals = _traffic.synthesize(
                seed=7, n_requests=n_prompts, rate_rps=gateway_rate,
                process=os.environ.get("BENCH_ARRIVAL_PROCESS", "burst"),
                max_prompt_tokens=max_prompt, max_new_tokens=max_new,
            )
            t0_gw = time.perf_counter()
            gateway_summary = _traffic.replay(gateway_server.url, arrivals)
            timed.append(time.perf_counter() - t0_gw)
        finally:
            gateway_server.close()
            gateway_service.close()
        total_tokens = sum(
            int(c["gen_tokens"])
            for c in gateway_summary["by_class"].values()
        )
        # per-step occupancy counters describe ONE generate() round; the
        # gateway runs many small rounds whose drain tails overlap client
        # arrivals, so those quotients would not mean what they mean on
        # batch rows — honest null
        have_steps = have_alive = False
    else:
        for i in range(repeats):
            if turn_hook is not None:
                turn_hook.reset()  # per-round turn cursors + timed stats
            result, dt_i = run(1 + i)
            timed.append(dt_i)
            if turn_hook is not None:
                env_counts.extend(int(x) for x in turn_hook.turns)
                env_step_ms.extend(turn_hook.step_ms)
            # random weights rarely emit EOS, so rows typically decode
            # max_new tokens; count actual generated lengths to stay
            # correct if not
            total_tokens += int(result.lengths.sum())
            if result.steps_dispatched is None:
                have_steps = False
            else:
                sum_steps += result.steps_dispatched
            if getattr(result, "alive_slot_steps", None) is None:
                have_alive = False
            else:
                sum_alive += result.alive_slot_steps
            st = getattr(engine, "last_spec_stats", None)
            if st and st.get("verify_grid_steps"):
                sum_spec_grid += (
                    st["verify_grid_steps"] + st.get("draft_grid_steps", 0)
                )
                spec_grid_rounds += 1
    steps_dispatched = sum_steps if have_steps else None
    alive_slot_steps = sum_alive if have_alive else None
    if fleet_agg is not None:
        # fold the timed window's per-worker token deltas into the fleet/*
        # gauges — _fleet_tok_s() below reads the published aggregate
        fleet_agg.refresh(force=True)
    dt = sum(timed)
    tps = total_tokens / dt
    n_chips = max(jax.device_count(), 1)
    tps_chip = tps / n_chips

    mean_prompt_len = float(pmask.sum(axis=1).mean())
    # mean over ALL repeats' candidates (the last run alone can be a
    # length outlier under EOS sampling, skewing mfu/roofline vs the
    # all-repeats tps numerator)
    # gateway rows run one request-group per prompt (n=1, single replay);
    # batch rows run n_cand candidates per prompt across every repeat
    mean_new = total_tokens / (
        n_prompts if gateway_on else n_prompts * n_cand * repeats
    )
    mean_kv = mean_prompt_len + mean_new / 2.0  # KV grows linearly over decode
    flops_per_token = _decode_flops_per_token(cfg, mean_kv)
    mfu = tps_chip * flops_per_token / peak_flops if peak_flops else None
    # report the scheduler that actually RAN: the refill path only engages
    # when the row cap is exceeded (otherwise generate() falls through to a
    # single wave) — recording the requested value would let an A/B
    # comparison attribute wave-mode throughput to "refill"
    if os.environ.get("BENCH_ENGINE") == "paged" and not fleet_n:
        # read the dispatch decision off the ENGINE (same condition as
        # PagedGenerationEngine.generate) so the record can't drift from it
        engaged = (
            engine.scheduler == "refill"
            and engine.max_concurrent_rows
            and (
                n_prompts * n_cand > engine.max_concurrent_rows
                or engine.spec_draft
                # prefix sharing (and continuous admission, which implies
                # it) pins the refill path even for small batches
                or engine.prefix_sharing
                # an armed turn hook pins refill too (the turn-resume
                # machinery lives on the refill scheduler's idle pass)
                or getattr(engine, "turn_hook", None) is not None
            )
        )
        scheduler_ran = "refill" if engaged else "waves"
        spec_ran = engine.spec_draft if engaged else 0
    else:
        scheduler_ran = None  # dense engine has no batching scheduler
        spec_ran = 0
    # realized speculation: mean tokens emitted per slot per dispatched step
    # (1.0 = plain decode; > 1 = drafts being accepted)
    accept_rate = None
    if alive_slot_steps:
        # divide by alive-slot-steps, not steps*slots: during the refill
        # drain tail many slots are idle while steps still dispatch, and the
        # constant-slot denominator understates realized acceptance
        accept_rate = round(total_tokens / alive_slot_steps, 3)
    elif steps_dispatched:
        slots = min(
            getattr(engine, "max_concurrent_rows", 0) or n_prompts * n_cand,
            n_prompts * n_cand,
        )
        accept_rate = round(
            total_tokens / (steps_dispatched * slots), 3
        )
    # bandwidth roofline at this config's slot count and mean context;
    # speculative runs raise the ceiling by their realized accept rate so
    # pct_of_roofline stays a step-rate comparison
    hbm_gbps = float(os.environ.get("BENCH_HBM_GBPS", "819"))
    slot_rows = min(
        getattr(engine, "max_concurrent_rows", 0) or n_prompts * n_cand,
        n_prompts * n_cand,
    )
    from distrl_llm_tpu.engine.budget import tree_bytes

    roofline = _decode_roofline_tok_s(
        tree_bytes(params), cfg,
        # the ENGINE-resolved format (explicit pin or plan-DB) — the
        # roofline must describe the bytes the run actually streamed
        (getattr(engine, "kv_quant", None) or "none"), slot_rows,
        mean_kv, hbm_gbps,
        tokens_per_slot_step=(accept_rate or 1.0) if spec_ran else 1.0,
    )
    # grid-overhead model (BASELINE r5): paged decode's cost floor is grid
    # steps × Mosaic's ~1 µs/grid-step. per-call count (trace-time record)
    # × layers = grid steps per decode step; measured seconds over total
    # grid steps = realized µs/grid-step — an UPPER bound (the quotient
    # carries non-attention work too), but it pins which regime a row is in
    grid_per_call = (
        _paged_grid_steps_per_call(engine, cfg, slot_rows)
        if os.environ.get("BENCH_ENGINE") == "paged" and not fleet_n
        else None
    )
    # speculative grid model (ISSUE 6): with the FUSED verify kernel the
    # whole (d+1)-token verify costs ONE blocked sweep per layer per step
    # (paged_grid_steps("native_verify")); unrolled verify pays the decode
    # per-call count (d+1) times; the self drafter adds d plain decode
    # calls per step either way
    spec_stats = getattr(engine, "last_spec_stats", None) if spec_ran else None
    spec_verify_ran = None
    if spec_stats:
        vbase = (spec_stats.get("verify_impl") or "").split("!")[0]
        spec_verify_ran = (
            "fused" if vbase == "native_verify"
            else ("unrolled" if vbase else None)
        )
    if spec_ran and spec_grid_rounds == repeats and steps_dispatched:
        # the engine accumulated the EXACT layer-scaled grid cost per
        # dispatch (each step's own verify decision and effective draft
        # length) — prefer it over the configured-d analytic model, which
        # overstates after the BENCH_SPEC_ADAPT controller shrinks d.
        # Summed per repeat above (all repeats must have contributed, else
        # fall back to the analytic model) to match the steps_dispatched
        # denominator's all-repeats scope.
        grid_steps_estimate = round(sum_spec_grid / steps_dispatched)
    elif spec_ran and grid_per_call is not None:
        from distrl_llm_tpu.ops.paged import paged_grid_steps

        if spec_verify_ran == "fused":
            verify_per_step = paged_grid_steps(
                "native_verify", batch=slot_rows,
                num_kv_heads=cfg.num_kv_heads,
                pps=engine.prompt_pages + engine.private_pages,
                pages_per_block=getattr(engine, "pages_per_block", 0) or 0,
            )
        else:
            verify_per_step = grid_per_call * (spec_ran + 1)
        draft_per_step = (
            grid_per_call * spec_ran
            if getattr(engine, "spec_drafter", "ngram") == "self" else 0
        )
        grid_steps_estimate = (
            (verify_per_step + draft_per_step) * cfg.num_layers
        )
    else:
        grid_steps_estimate = (
            grid_per_call * cfg.num_layers if grid_per_call
            else grid_per_call
        )
    us_per_grid_step = None
    if grid_steps_estimate and steps_dispatched and dt > 0:
        us_per_grid_step = round(
            dt * 1e6 / (grid_steps_estimate * steps_dispatched), 3
        )
    # ---- quantized-serving self-description (ISSUE 15) -------------------
    # effective KV format: what the engine RESOLVED (explicit env pin or
    # plan-DB), not what the env requested; fleet rows (worker-side
    # engines) honestly read null
    kv_ran = getattr(engine, "kv_quant", None) if not fleet_n else None
    # measured bytes/token from the decode step program's XLA cost_analysis
    # (DISTRL_MEASURE_COST): one step streams `step_bytes_accessed`; over
    # the timed window that is steps x bytes / tokens — for engines that
    # don't count steps (dense waves), one token per slot row per step
    # gives bytes/slot_rows (exact under BENCH_NO_EOS). Null when the
    # backend reports no cost analysis — never a fabricated number.
    _costs_now = importlib.import_module("distrl_llm_tpu.obs").costs()
    _step_what = (
        "decode_step/spec" if spec_ran
        else ("decode_step/refill" if scheduler_ran == "refill"
              else ("decode_step/paged"
                    if os.environ.get("BENCH_ENGINE") == "paged"
                    else "decode_step/dense"))
    )
    step_bytes_accessed = (
        _costs_now.get(_step_what, {}).get("bytes_accessed")
        if not fleet_n else None
    )
    bytes_per_token = None
    if step_bytes_accessed:
        if steps_dispatched and total_tokens:
            bytes_per_token = round(
                step_bytes_accessed * steps_dispatched / total_tokens, 1
            )
        elif total_tokens:
            bytes_per_token = round(step_bytes_accessed / slot_rows, 1)
    # which sampler implementation the engine's steps dispatched (the
    # sample_with_logprob trace-time record; distinct choices joined "+")
    _samp = importlib.import_module("distrl_llm_tpu.ops.sampling")
    _samp_choices = sorted(set(_samp.sample_dispatch_choices.values()))
    sample_kernel = "+".join(_samp_choices) if _samp_choices else None
    # whether quantized base matmuls ran the fused kernel or the XLA
    # container path (null when the base is unquantized — no dispatch)
    _qmm = importlib.import_module("distrl_llm_tpu.ops.quant_matmul")
    _qmm_choices = sorted(set(_qmm.dispatch_choices.values()))
    quant_matmul_ran = "+".join(_qmm_choices) if _qmm_choices else None
    record = {
        "metric": "rollout_tokens_per_sec_per_chip",
        "engine": os.environ.get("BENCH_ENGINE", "dense"),
        "scheduler": scheduler_ran,
        "spec_draft": spec_ran,
        # speculative self-description (ISSUE 6, pinned in
        # tests/test_bench_contract.py): which drafter proposed, the
        # realized draft-slot accept rate, mean tokens emitted per verify
        # step (engine-accounted, last timed round), and which verify
        # sweep actually ran ("fused" one-sweep kernel vs "unrolled")
        "spec_drafter": (
            getattr(engine, "spec_drafter", None) if spec_ran else None
        ),
        "spec_accept_rate": (
            spec_stats.get("accept_rate") if spec_stats else None
        ),
        "tokens_per_verify_step": (
            spec_stats.get("tokens_per_verify_step") if spec_stats else None
        ),
        "spec_verify_impl": spec_verify_ran,
        "tokens_per_slot_step": accept_rate,
        "eos_rate": eos_rate,
        "mean_gen_tokens": round(mean_new, 1),
        # the benched geometry AND device kind, so plan ingestion
        # (tools/autotune.py) can key this row without trusting
        # CLI-supplied defaults or inferring hardware from peak_tflops
        "max_prompt_tokens": max_prompt,
        "max_new_tokens": max_new,
        "device_kind": _device_kind(),
        # fleet rows: workers bucket their own shards — no local bucket
        "bucket_used": (
            engine.bucket_for(pmask) if hasattr(engine, "bucket_for")
            else None
        ),
        "short_fraction": round(short_fraction, 3),
        "value": round(tps_chip, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(tps_chip / REFERENCE_TOKENS_PER_SEC_PER_GPU, 3),
        "mfu": round(mfu, 6) if mfu is not None else None,
        "model": name,
        "base_quant": base_quant,
        # effective KV format the engine resolved (plan-field spelling;
        # "kv_quant" kept as the legacy alias of the same value)
        "kv_format": kv_ran,
        "kv_quant": kv_ran,
        # measured-bytes scoreboard (ISSUE 15, pinned in
        # tests/test_bench_contract.py): XLA cost_analysis bytes of ONE
        # decode step program and the derived HBM bytes per generated
        # token — the metric every quantized-serving sub-item must move;
        # bench_history scores bytes_per_token lower-is-better
        "step_bytes_accessed": step_bytes_accessed,
        "bytes_per_token": bytes_per_token,
        # which sampler ran ("fused" one-pass kernel vs "xla" multi-pass)
        # and which matmul path served the quantized base ("kernel" fused
        # dequant-matmul vs "xla" container; null = unquantized base)
        "sample_kernel": sample_kernel,
        "quant_matmul": quant_matmul_ran,
        "top_p_impl": sampling.resolved_top_p_impl(
            getattr(engine, "plan_top_p_impl", None)
        ),
        # the engine's EFFECTIVE chunk (post plan resolution), not the
        # requested env value — perf artifacts must be self-describing
        "scan_chunk": getattr(engine, "scan_chunk", 0),
        "scan_chunk_active": getattr(engine, "scan_chunk_active", None),
        # the full resolved execution plan + where it came from ("db" /
        # "default" / "disabled"), so a regression like "scan-chunk
        # silently engaged" is diffable from the artifact alone
        "plan": (
            engine.resolved_plan.plan.to_dict()
            if getattr(engine, "resolved_plan", None) else None
        ),
        "plan_source": (
            engine.resolved_plan.source
            if getattr(engine, "resolved_plan", None) else None
        ),
        "cache_read_formulation": getattr(
            engine, "cache_read_formulation", None
        ),
        # rollout-regime provenance, schema-shared with the trainer's
        # train-curve JSONL records (tests/test_bench_contract.py pins both):
        # bench drives the engine directly — one synchronous generation per
        # timing repeat — so the mode is always "sync", the effective
        # staleness bound 0, and nothing is ever dropped for staleness. The
        # fields exist so bench rows and async train curves are join-able
        # artifacts, not because bench exercises the buffer.
        "rollout_mode": "sync",
        "max_staleness": 0,
        "rollout_dropped_stale": 0,
        # which paged-attention impl the probe chain actually dispatched
        # (None for dense runs / before any paged dispatch)
        "paged_attn_impl": _paged_dispatch_choice(),
        # same choice in the plan-field vocabulary, plus the grid-overhead
        # self-description (ISSUE 3): analytic grid steps per decode step
        # across layers and the realized µs/grid-step upper bound
        "paged_kernel": _paged_kernel_ran(),
        "pages_per_block": getattr(engine, "pages_per_block", None),
        "grid_steps_estimate": grid_steps_estimate,
        "us_per_grid_step": us_per_grid_step,
        "backend": jax.devices()[0].platform,
        "completions": n_prompts * n_cand,
        "total_tokens": total_tokens,
        "decode_seconds": round(dt, 2),
        "repeats": repeats,
        "decode_seconds_each": [round(t, 3) for t in timed],
        # engine-internal counters, summed over repeats (VERDICT r4 weak
        # #6): efficiency regressions show up as dispatch/step-count drift
        # even when wall-clock is noisy
        "steps_dispatched": steps_dispatched,
        "alive_slot_steps": alive_slot_steps,
        "compile_plus_first_run_seconds": round(compile_dt, 2),
        "chips": n_chips,
        "flops_per_token_gflop": round(flops_per_token / 1e9, 6),
        "peak_tflops": peak_flops / 1e12 if peak_flops else None,
        # bandwidth-bound ceiling for THIS config (weights streamed once per
        # step + per-slot KV read at mean context; assumes bf16/quantized
        # residency as constructed) — decode utilisation is tok/s vs this,
        # not MFU; a low pct with scan_chunk=0 points at per-dispatch
        # host overhead rather than chip saturation
        "roofline_tok_s_per_chip": round(roofline, 1),
        "pct_of_roofline": round(100.0 * tps_chip / roofline, 2) if roofline else None,
        "hbm_gbps_assumed": hbm_gbps,
        "pool_stats": getattr(engine, "last_pool_stats", None),
        # continuous-batching self-description (ISSUE 12, pinned in
        # tests/test_bench_contract.py): which admission regime the round
        # actually ran ("waves" | "refill" | "refill_shared" |
        # "continuous"; null = dense/fleet rows), the fraction of
        # admissions served by a SHARED refcounted prompt prefix and of
        # in-use pages physically shared (last timed round's pool — both
        # null when the refill pool never ran or sharing is off), and the
        # fraction of slot-steps spent idle (the drain-tail/backfill
        # number the continuous A/B moves; derived from the same
        # alive_slot_steps counter, all repeats)
        # multi-turn env self-description (ISSUE 17, pinned in
        # tests/test_bench_contract.py): which synthetic env arm ran
        # (null = single-turn control), realized turns per candidate over
        # the timed rounds, and the hook's own wall time per consulted
        # turn — plus the engine's turn-resume accounting through
        # pool_stats (turn_resumes / turn_prefill_saved_tokens). The A/B's
        # claim is slot_idle_frac: re-admitting continuations onto
        # resident chains must keep idle within noise of the control.
        "env_name": bench_env or None,
        "turns_mean": (
            round(float(np.mean(env_counts)), 3) if env_counts else None
        ),
        "turns_max": int(np.max(env_counts)) if env_counts else None,
        "env_step_ms_p50": (
            round(float(np.median(env_step_ms)), 4) if env_step_ms else None
        ),
        "cb_mode": getattr(engine, "last_cb_mode", None),
        "prefill_shared_frac": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("prefill_shared_frac")
        ),
        "pages_shared_frac": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("pages_shared_frac")
        ),
        # tiered-KV-cache self-description (ISSUE 18, pinned in
        # tests/test_bench_contract.py): whether the radix cache armed the
        # timed rounds, its hit rate over looked-up prompt tokens, prefill
        # tokens warm admissions skipped, and the p50 host-store restore
        # latency — honest nulls on cache-off/dense/fleet rows (a cache-on
        # round that never restored reports a null p50, not 0)
        "prefix_cache": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("prefix_cache")
        ),
        "radix_hit_rate": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("radix_hit_rate")
        ),
        "prefill_tok_saved": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("prefill_tok_saved")
        ),
        "spill_restore_ms_p50": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("spill_restore_ms_p50")
        ),
        "slot_idle_frac": (
            round(1.0 - alive_slot_steps / (steps_dispatched * slot_rows), 4)
            if alive_slot_steps and steps_dispatched else None
        ),
        # request-level serving latencies (ISSUE 13, pinned in
        # tests/test_bench_contract.py): TTFT / queue-wait percentiles and
        # the attributed admission-stall fraction over the TIMED rounds,
        # from a ServingLedger armed post-warmup on continuous-admission
        # engines — null on dense/fixed-batch/fleet rows (no ledger). The
        # stall fraction is slot_idle_frac's EXPLANATION: declined
        # admission passes over all passes, with per-reason counts in the
        # registry (serving/admission_stalls/*)
        "ttft_p50_ms": _serving_pct(serving_ledger, "ttft_ms", 50),
        "ttft_p99_ms": _serving_pct(serving_ledger, "ttft_ms", 99),
        "queue_wait_p50_ms": _serving_pct(
            serving_ledger, "queue_wait_ms", 50
        ),
        "admission_stall_frac": _serving_stall_frac(serving_ledger),
        # self-healing-runtime provenance (ISSUE 14, pinned in
        # tests/test_bench_contract.py): dynamic control actuations over
        # the timed window and groups the shedder deferred — null unless a
        # ControlLimits was attached (BENCH_CONTROL_FRAC pins the static
        # governor-shrunk A/B arm; a pinned arm honestly records 0
        # actions, it is the shrunk CAP whose throughput cost the A/B
        # measures). Train-curve records carry the same story via the
        # control/* registry series.
        "control_actions": (
            _tlm.observe_snapshot()["counters"].get(
                "control/actions", 0.0
            ) - control_actions0
            if control_limits is not None else None
        ),
        "shed_groups": (
            (getattr(engine, "last_pool_stats", None) or {})
            .get("shed_groups")
        ),
        # serving-gateway provenance (ISSUE 19, pinned in
        # tests/test_bench_contract.py): BENCH_GATEWAY rows drive an
        # open-loop arrival trace through the streaming front-end, so
        # tok/s is goodput under load, only comparable to other gateway
        # rows at the same arrival rate (bench_history comparable()).
        # Per-class p99 TTFT comes from the server-side ledger — the
        # overload A/B's contract is bounded interactive p99 while the
        # shed floor pushes deferrals onto batch/scavenger.
        # shed_frac_by_class: each class's share of shed+preempt
        # deferral events over the whole replay (sums to 1.0; null when
        # nothing was deferred or off-gateway).
        "gateway_mode": gateway_on,
        "arrival_rate": gateway_rate,
        "ttft_p99_interactive_ms": (
            _serving_pct(serving_ledger, "ttft_ms", 99, cls="interactive")
            if gateway_on else None
        ),
        "ttft_p99_batch_ms": (
            _serving_pct(serving_ledger, "ttft_ms", 99, cls="batch")
            if gateway_on else None
        ),
        "shed_frac_by_class": _gateway_shed_frac(gateway_service),
        # measured-attribution fields (ISSUE 8, pinned in
        # tests/test_bench_contract.py): device HBM watermark (null on
        # backends without memory stats), shape-keyed retrace count since
        # the pre-warmup tracker reset (0 = no silent retrace storm), and
        # the fleet-aggregate tok/s gauge — null on single-process rows
        # (bench drives the engine directly), POPULATED on BENCH_WORKERS
        # rows from the FleetAggregator's per-worker token deltas over the
        # timed window (ISSUE 10 satellite: the slot PR 8 reserved)
        "hbm_peak_bytes": _hbm_peak_bytes(),
        "recompile_count": _recompile_count(),
        "fleet_tok_s": _fleet_tok_s(),
        "fleet_workers": fleet_n,
        # weight-bus provenance (ISSUE 9, pinned in
        # tests/test_bench_contract.py): which learner→worker weight
        # transport the row ran under ("dispatch" | "broadcast"; null =
        # local engine, no control-plane transport exercised), the bytes
        # one adapter update put on the wire, and the learner-push →
        # last-worker-ack latency (broadcast rows only — dispatch re-ships
        # the adapter per payload, there is no per-version push to time)
        "weight_bus": (
            getattr(engine, "weight_bus_mode", None) if fleet_n else None
        ),
        "weight_bytes_per_update": (
            engine.bus.last_broadcast_bytes
            if fleet_n and getattr(engine, "bus", None) is not None
            else None
        ),
        "weight_sync_ms": (
            engine.bus.last_broadcast_ms
            if fleet_n and getattr(engine, "bus", None) is not None
            else None
        ),
        "baseline_note": "baseline 1500 tok/s/GPU derived from reference's ~2h/100-step "
                         "Qwen2.5-7B-4bit runs on RTX 4090s (BASELINE.md); this run's "
                         "model is recorded in 'model'",
    }
    _emit(record)
    if fleet_procs:
        # graceful fleet teardown (the atexit hook only covers aborts);
        # the record is already emitted — a worker slow to drain must not
        # turn a valid measurement into a nonzero exit
        import signal as _signal
        import subprocess as _subprocess

        engine.driver.shutdown()
        for p in fleet_procs:
            try:
                p.wait(timeout=15)
            except _subprocess.TimeoutExpired:
                p.send_signal(_signal.SIGKILL)
                p.wait(timeout=5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
