"""One SHARDED paged engine over a GSPMD dp axis (shard_map edition).

Closes the round-2 "deliberate gap" (PARITY.md): the paged engine targeted
one replica, with data-parallel scale-out running one engine per replica
(vLLM's one-engine-per-GPU model, fanned out via remote workers). On a
single TPU slice the natural idiom is ONE engine whose page pool is
partitioned across the dp axis — this module builds exactly that with
``jax.experimental.shard_map``:

* each dp shard owns a LOCAL page pool and LOCAL page tables (page ids index
  the shard's own pool slice), so the per-step page gather never crosses the
  axis — the pool-partitioned design sketched in paged_engine.py;
* the per-replica jitted pieces (``_paged_prefill``, ``_paged_fanout``,
  ``_paged_decode_step``) are REUSED verbatim as the shard-local program —
  per-shard semantics are identical to a per-replica engine by construction
  (pinned by greedy bit-parity tests, tests/test_sharded_paged.py);
* decode steps dispatch from the host with donated state and async
  early-exit done-snapshots (``run_decode_loop``), exactly like the local
  engines; one dispatch steps every shard;
* sampling folds ``lax.axis_index("dp")`` into the step rng so rows in
  different shards draw independent noise.

Scope: the WAVE scheduler (whole-batch prefill → decode → drain). The
refill/speculative schedulers keep per-candidate host bookkeeping and stay
per-replica (remote-worker fan-out); TP inside a shard is likewise the
per-replica engines' job — this engine requires every non-dp mesh axis to
be size 1. The trainer detects the bound ``mesh`` attribute and routes the
WHOLE batch here (hybrid learner-share generation needs per-role device
placement the bound mesh precludes).

Reference anchor: vLLM data-parallel serving (one engine per GPU,
requirements.txt:6); the sharded pool is the TPU-native alternative the
round-2 verdict asked to build or refute.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from distrl_llm_tpu.config import SamplingConfig
import threading

from distrl_llm_tpu import obs
from distrl_llm_tpu.engine.engine import (
    GenerationResult,
    LoraMailbox,
    RoundHostAccount,
    RoundMarks,
    accumulate_round_stats,
    file_round,
    cached_chunk_program,
    lora_signature,
    make_swap_aware_chunk_step,
    pool_nbytes,
    pick_chunk,
    run_decode_loop,
    run_nondivisor_tail,
)
from distrl_llm_tpu.engine.paged_engine import (
    _paged_decode_chunk,
    _paged_decode_step,
    _paged_fanout,
    _paged_prefill,
    _PagedDecodeState,
)
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.ops.paged import pages_per_seq



def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checks off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )

Params = dict[str, Any]


class ShardedPagedEngine(LoraMailbox):
    """Paged wave-mode generation with the page pool partitioned over "dp"."""

    def __init__(
        self,
        cfg: ModelConfig,
        mesh: Mesh,
        *,
        max_prompt_tokens: int,
        max_new_tokens: int,
        eos_token_ids: Sequence[int],
        pad_token_id: int,
        lora_scale: float = 1.0,
        cache_dtype=jnp.bfloat16,
        attn_impl: str = "reference",
        paged_impl: str = "auto",
        page_size: int = 128,
        decode_chunk: int = 128,
        # None = consult the autotune plan DB (ExecutionPlan.kv_format;
        # empty DB = "none"); an explicit value — including "none" — pins
        kv_quant: str | None = None,
        prompt_buckets: Sequence[int] | None = None,  # interface parity
        # None = consult the autotune plan DB (falls back to 0, the
        # historical default); an explicit int — including 0 — always wins
        scan_chunk: int | None = None,
        capture_logprobs: bool = False,
        autotune: bool = True,  # False pins the static defaults (no DB read)
        plan_db: str | None = None,  # plan-DB path; None = env/default path
        plan_rows: int = 0,  # expected rows for plan-KEY selection (0 = any)
        # accepted-and-rejected so misrouted configs fail with a clear
        # error instead of a TypeError deep in trainer wiring
        spec_draft: int | None = None,
    ):
        cfg.refuse_hybrid("the dp-sharded paged engine (engine_impl='paged_sharded')")
        cfg.refuse_looped("the dp-sharded paged engine (engine_impl='paged_sharded')")
        if spec_draft:
            raise NotImplementedError(
                "speculative decoding is a per-replica refill-scheduler "
                "feature (PagedGenerationEngine with scheduler='refill' — "
                "one engine per rollout replica, distributed/"
                "remote_engine.py); ShardedPagedEngine runs the wave "
                "scheduler over a dp-partitioned pool and does not host it"
            )
        if scan_chunk is not None and scan_chunk < 0:
            raise ValueError(f"scan_chunk must be >= 0, got {scan_chunk}")
        if kv_quant not in (None, "none", "int8"):
            # validated BEFORE plan resolution so a typo'd kwarg fails with
            # the engine's own contract, not a plan-field error
            raise ValueError(f"kv_quant must be none/int8, got {kv_quant!r}")
        # execution-plan resolution (distrl_llm_tpu/autotune): explicit
        # kwargs win; no DB entry = the static defaults byte-identically
        from distrl_llm_tpu.autotune import resolve_plan

        requested: dict[str, Any] = {"decode_path": "paged"}
        if scan_chunk is not None:
            requested["scan_chunk"] = scan_chunk
        if kv_quant is not None:
            # explicit "none" is a real pin (the int8-default A/B control)
            requested["kv_format"] = kv_quant
        self.resolved_plan = resolve_plan(
            model_cfg=cfg, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens, rows=plan_rows,
            requested=requested, db_path=plan_db, enabled=autotune,
        )
        scan_chunk = self.resolved_plan.plan.scan_chunk
        self.plan_top_p_impl = self.resolved_plan.plan.top_p_impl
        self.paged_impl = paged_impl
        if "dp" not in mesh.shape:
            raise ValueError(f"mesh needs a 'dp' axis, got {dict(mesh.shape)}")
        other = {k: v for k, v in mesh.shape.items() if k != "dp" and v > 1}
        if other:
            raise ValueError(
                f"ShardedPagedEngine shards over dp only; non-trivial axes "
                f"{other} belong to per-replica engines (TP) — see module doc"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        self.max_prompt_tokens = max_prompt_tokens
        self.max_new_tokens = max_new_tokens
        cfg.check_within_window(max_prompt_tokens + max_new_tokens)
        self.page_size = page_size
        self.prompt_pages = pages_per_seq(max_prompt_tokens, page_size)
        self.private_pages = 1 + pages_per_seq(max_new_tokens, page_size)
        self.eos_ids = jnp.asarray(list(eos_token_ids), jnp.int32)
        self.pad_id = int(pad_token_id)
        self.lora_scale = lora_scale
        self.decode_chunk = decode_chunk
        self.capture_logprobs = capture_logprobs
        self.prompt_buckets = [max_prompt_tokens]
        # post-resolution KV format (explicit kwarg already won per-field)
        kv_quant = kv_quant if kv_quant is not None else (
            self.resolved_plan.plan.kv_format or "none"
        )
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be none/int8, got {kv_quant!r}")
        self._kv_quant = kv_quant
        self._prefill_kw = dict(
            cfg=cfg, prompt_pages=self.prompt_pages, page_size=page_size,
            lora_scale=lora_scale, cache_dtype=cache_dtype,
            attn_impl=attn_impl, kv_quant=kv_quant,
        )
        self._step_kw = dict(
            cfg=cfg, page_size=page_size, pad_id=self.pad_id,
            lora_scale=lora_scale, paged_impl=paged_impl,
            capture_logprobs=capture_logprobs,
        )
        self.scan_chunk = scan_chunk
        self._built: dict[tuple, tuple] = {}
        self._chunk_compiled: dict = {}
        self._chunk_mu = threading.Lock()
        # in-flight weight-update mailbox (LoraMailbox base)
        self.last_swap_steps: list[int] = []
        self.last_swap_versions: list[int | None] = []

    @property
    def scan_chunk_active(self) -> bool | None:
        """Honesty flag: whether chunked decode actually ran (None before
        the first round / scan_chunk off; False if every attempt fell back
        to per-step dispatch)."""
        if self.scan_chunk <= 1 or not self._chunk_compiled:
            return None
        return any(v is not None for v in self._chunk_compiled.values())

    def bucket_for(self, prompt_mask) -> int:
        return self.max_prompt_tokens

    # ------------------------------------------------------------------ build

    def _state_specs(self) -> _PagedDecodeState:
        page = P(None, "dp", None, None)
        pages = lambda: tuple(  # noqa: E731 — spec tuple per layer
            page for _ in range(self.cfg.paged_layers)
        )

        def quant_aware(spec_tuple):
            # quantized pools are QuantizedTensor pytrees (weight + scales):
            # shard_map specs are pytree PREFIXES, so a per-layer P() prefix
            # covers both leaves
            return spec_tuple

        return _PagedDecodeState(
            step=P(),
            out=P("dp", None),
            logps=P("dp", None),
            gen_lengths=P("dp"),
            done=P("dp"),
            logits=P("dp", None),
            seq_lengths=P("dp"),
            k_pages=quant_aware(pages()),
            v_pages=quant_aware(pages()),
        )

    def _build(self, n: int, b_local: int, max_steps: int,
               top_p_impl: str) -> tuple:
        key = (n, b_local, max_steps, top_p_impl)
        if key in self._built:
            return self._built[key]
        obs.note_compile("sharded_paged/build", key)
        mesh = self.mesh
        sspec = self._state_specs()

        def local_setup(params, lora, ids, mask):
            pk, pv, last_logits, real_len = _paged_prefill(
                params, lora, ids, mask, **self._prefill_kw
            )
            row_alive = mask.sum(axis=-1) > 0
            state, table = _paged_fanout(
                pk, pv, last_logits, real_len, row_alive,
                n=n, b=b_local, prompt_pages=self.prompt_pages,
                private_pages=self.private_pages, page_size=self.page_size,
                max_steps=max_steps,
            )
            return state, table

        setup = jax.jit(
            shard_map(
                local_setup, mesh=mesh,
                in_specs=(P(), P(), P("dp", None), P("dp", None)),
                out_specs=(sspec, P("dp", None)),
            )
        )

        def local_step(params, lora, state, rng, table, temperature, top_p):
            # decorrelate shards: every shard holds the same round rng, so
            # without the fold every shard's rows would draw IDENTICAL noise
            rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            return _paged_decode_step(
                params, lora, state, rng, table,
                eos_ids=self.eos_ids, temperature=temperature, top_p=top_p,
                top_p_impl=top_p_impl, **self._step_kw,
            )

        step = jax.jit(
            shard_map(
                local_step, mesh=mesh,
                in_specs=(P(), P(), sspec, P(), P("dp", None), P(), P()),
                out_specs=sspec,
            ),
            donate_argnums=(2,),
        )

        chunk_jit = None
        k = pick_chunk(self.scan_chunk, max_steps)
        if k > 1:
            # K steps per dispatch inside the SAME shard_map program. The
            # scan body is unguarded (a cond's select would double-buffer
            # the carried page pools — scan_steps_guarded); each shard's
            # done rows are per-row no-ops, and the host cadence below
            # keeps every dispatched step under max_steps.
            def local_chunk(params, lora, state, rng, table,
                            temperature, top_p):
                rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
                return _paged_decode_chunk(
                    params, lora, state, rng, table, chunk=k,
                    eos_ids=self.eos_ids,
                    temperature=temperature, top_p=top_p,
                    top_p_impl=top_p_impl, **self._step_kw,
                )

            chunk_jit = jax.jit(
                shard_map(
                    local_chunk, mesh=mesh,
                    in_specs=(P(), P(), sspec, P(), P("dp", None), P(), P()),
                    out_specs=sspec,
                ),
                donate_argnums=(2,),
            )
        self._built[key] = (setup, step, chunk_jit, k)
        return self._built[key]

    # --------------------------------------------------------------- generate

    def generate(
        self,
        params: Params,
        lora: Params | None,
        prompt_ids: np.ndarray,  # [B, P] left-padded (trainer contract)
        prompt_mask: np.ndarray,
        sampling: SamplingConfig,
        rng: jax.Array,
    ) -> GenerationResult:
        b, p = prompt_ids.shape
        if p != self.max_prompt_tokens:
            raise ValueError(
                f"prompts must be padded to {self.max_prompt_tokens}, got {p}"
            )
        marks = RoundMarks()
        t_round = time.perf_counter()
        params = self._decode_params(params)
        max_steps = min(sampling.max_tokens, self.max_new_tokens)
        n = max(sampling.n, 1)
        # pad the prompt batch to a dp multiple; padding rows have all-zero
        # masks → born done in fanout, pad-token output, zero lengths
        pad_rows = (-b) % self.dp
        if pad_rows:
            prompt_ids = np.concatenate(
                [np.asarray(prompt_ids),
                 np.zeros((pad_rows, p), np.int32)], axis=0
            )
            prompt_mask = np.concatenate(
                [np.asarray(prompt_mask),
                 np.zeros((pad_rows, p), np.int32)], axis=0
            )
        b_pad = b + pad_rows
        top_p_impl = sampling.resolved_top_p_impl(self.plan_top_p_impl)
        setup, step, chunk_jit, k = self._build(
            n, b_pad // self.dp, max_steps, top_p_impl
        )

        state, table = setup(
            params, lora, jnp.asarray(prompt_ids), jnp.asarray(prompt_mask)
        )
        temperature = jnp.asarray(sampling.temperature, jnp.float32)
        top_p = jnp.asarray(sampling.top_p, jnp.float32)
        self._reset_lora_mailbox_round()
        lora_cell = [lora]
        steps_seen = [0]

        host = RoundHostAccount()
        chunk_fn = None
        if chunk_jit is not None:
            chunk_fn = cached_chunk_program(
                self._chunk_compiled, self._chunk_mu,
                (n, b_pad, max_steps, top_p_impl, lora_signature(lora)),
                chunk_jit, pool_nbytes(state.k_pages, state.v_pages),
                f"sharded-wave scan_chunk={k}",
                params, lora, state, rng, table, temperature, top_p,
            )

        if chunk_fn is not None:

            def run_step(l, s):
                return step(params, l, s, rng, table, temperature, top_p)

            step_fn = make_swap_aware_chunk_step(
                self, lora_cell, steps_seen, k, max_steps, chunk_fn, lora,
                rebuild=lambda l, s: cached_chunk_program(
                    self._chunk_compiled, self._chunk_mu,
                    (n, b_pad, max_steps, top_p_impl, lora_signature(l)),
                    chunk_jit,
                    pool_nbytes(s.k_pages, s.v_pages),
                    f"sharded-wave scan_chunk={k}",
                    params, l, s, rng, table, temperature, top_p,
                ),
                run_chunk=lambda fn, l, s: fn(
                    params, l, s, rng, table, temperature, top_p
                ),
                run_step=run_step,
            )
            # floor chunks + shared non-divisor tail (run_nondivisor_tail
            # has the cadence invariant)
            full, rem = divmod(max_steps, k)
            state = run_decode_loop(step_fn, state, full, 1,
                                    steps_per_call=k, host=host)
            state = run_nondivisor_tail(
                self, lora_cell, steps_seen, rem, state, run_step)
        else:

            def step_fn(s):
                self._take_pending_lora(lora_cell, steps_seen[0])
                steps_seen[0] += 1
                return step(
                    params, lora_cell[0], s, rng, table, temperature, top_p
                )

            state = run_decode_loop(step_fn, state, max_steps, self.decode_chunk,
                                    host=host)
        t_read = time.perf_counter()
        out = np.asarray(state.out).reshape(b_pad, n, max_steps)[:b]
        lengths = np.asarray(state.gen_lengths).reshape(b_pad, n)[:b]
        logps = (
            np.asarray(state.logps).reshape(b_pad, n, max_steps)[:b]
            if self.capture_logprobs else None
        )
        host.blocked(t_read)
        host.stop()
        # round stats (engine.accumulate_round_stats contract, new here):
        # the sharded path previously published no throughput at all —
        # like RemoteEngine, the whole round is accounted as decode time
        # (prefill runs inside the same jitted setup; no honest split).
        # whole_round flags the coarse accounting so the trainer skips
        # engine/mfu on it — a prefill/compile-inclusive "decode" rate
        # against the chip peak would be a misleadingly low MFU (remote
        # rounds are excluded for the same reason via is_remote)
        self.last_round_stats = accumulate_round_stats(
            None, prefill_s=0.0,
            prefill_tokens=int(np.asarray(prompt_mask)[:b].sum()),
            prompt_rows=b,
            decode_s=time.perf_counter() - t_round,
            gen_tokens=int(lengths.sum()), gen_rows=b * n, host=host,
        )
        self.last_round_stats["whole_round"] = True
        file_round(marks, self.last_round_stats)
        return GenerationResult(tokens=out, lengths=lengths, logprobs=logps)
