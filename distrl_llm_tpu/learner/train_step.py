"""The pjit'd learner update: grad-accumulated PG/GRPO step over the learner mesh.

Replaces the reference's entire update machinery — the per-learner microbatch
loop with loss/num_batches scaling (distributed_actor.py:352–389), the
CPU-pickled gradient dicts, the driver-side mean, and the one-learner optimizer
step (:283–333, distributed_trainer.py:308–342) — with ONE jitted function:

* microbatches run as a ``lax.scan`` over fixed-shape slices, accumulating
  gradients on device;
* data parallelism is the mesh's ``dp`` axis — the batch is sharded over it and
  GSPMD inserts the gradient ``psum`` (ICI), which also fixes the reference's
  stale-learner bug by construction (SURVEY §3.4): every learner shard applies
  the same merged update in the same step;
* the zero-reward microbatch skip implements the reference's *intent* (skip
  only when every reward in the microbatch is zero — the reference's
  ``batch_rewards.all() == 0`` actually skips when ANY reward is zero,
  SURVEY §3.6.3; set ``skip_semantics="any_zero"`` for bug-parity).

Batch layout (host-prepared by ``prepare_update_batch``): all arrays lead with
N = num_micro · micro_size; rows beyond the real sample count are padding with
``sample_mask`` 0.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.learner import remat as remat_lib
from distrl_llm_tpu.learner.losses import (
    answer_logprobs, grpo_aipo_loss, grpo_clip_loss, grpo_loss, kl_to_ref,
    pg_loss,
)
from distrl_llm_tpu.models.configs import ModelConfig

# the device-side IS-ratio histogram (ISSUE 16) pre-bins over the SAME
# bucket ladder the host registry uses, so LearnLedger can replay the
# counts through hist_observe(count=) and the registry's own bisect
# reproduces them exactly; one extra overflow slot past the last bound
_RATIO_BOUNDS: tuple[float, ...] = telemetry.HIST_BUCKET_BOUNDS
_GRAD_DEPTH_BUCKETS = 4  # LoRA grad-norm depth groups (a0..a3 / b0..b3)


class UpdateBatch(NamedTuple):
    """Fixed-shape flattened candidates for one policy update."""

    prompt_ids: jax.Array  # [N, P] int32, left-padded
    prompt_mask: jax.Array  # [N, P]
    answer_ids: jax.Array  # [N, T] int32, right-padded
    answer_mask: jax.Array  # [N, T]
    coeffs: jax.Array  # [N] f32 — reward−baseline (PG) or advantage (GRPO)
    sample_mask: jax.Array  # [N] f32 — 0 for padding rows
    # rollout-time logprobs of answer tokens [N, T] (engine-captured) — the
    # PPO-clip objective's behavior policy; None for the no-clip losses
    behavior_logps: jax.Array | None = None
    # per-token policy-version lag [N, T] (learner version − sampling
    # version, from the rollout trajectory tags) — the AIPO objective masks
    # tokens beyond max_staleness; None outside the async regime
    version_lag: jax.Array | None = None
    # multi-turn env rounds (ISSUE 17): [N, T] — answer_mask restricted to
    # POLICY-generated spans. Environment-injected observation tokens stay
    # in answer_mask (they are attention context for later turns — the
    # behavior policy conditioned on them) but are excluded here, so every
    # loss/metric term trains only policy spans; None = single-turn rounds,
    # loss masks on answer_mask as always
    loss_mask: jax.Array | None = None


def _microbatch_dynamics(
    logps, entropy, mb: UpdateBatch, *,
    clip_ratio: float, off_policy: str, is_cap: float,
) -> dict:
    """Per-microbatch training-dynamics SUMS (ISSUE 16), computed under
    ``stop_gradient`` from intermediates the loss already materialized —
    the ``lax.scan`` accumulates elementwise and ``_derive_dynamics``
    normalizes after, so the whole bundle rides the step's existing single
    host fetch. Keys are static per step build (the behavior-logprob
    entries exist only when the batch carries them)."""
    logps = jax.lax.stop_gradient(logps)
    # dynamics over TRAINABLE tokens: multi-turn rounds exclude env-injected
    # spans (their behavior logprobs are zeroed placeholders — counting them
    # would poison the KL/ratio stats with fake ratios)
    train_mask = mb.answer_mask if mb.loss_mask is None else mb.loss_mask
    mask = train_mask.astype(jnp.float32) * mb.sample_mask[:, None]
    real = mb.sample_mask
    dyn = {
        "tok_count": mask.sum(),
        "entropy_sum": (jax.lax.stop_gradient(entropy) * mask).sum(),
        # advantage moments over real rows (coeffs are the baseline-
        # subtracted rewards / group-normalized advantages)
        "adv_count": real.sum(),
        "adv_sum": (mb.coeffs * real).sum(),
        "adv_sq_sum": (jnp.square(mb.coeffs) * real).sum(),
        "adv_pos": ((mb.coeffs > 0.0).astype(jnp.float32) * real).sum(),
    }
    if mb.behavior_logps is not None:
        # behavior↔policy KL via the k3 estimator (kl_to_ref's idiom:
        # zero the exponent at pads BEFORE exp — garbage pad logprobs
        # would overflow exp and poison the sum through inf·0)
        diff = (mb.behavior_logps - logps) * mask
        dyn["kl_sum"] = ((jnp.exp(diff) - diff - 1.0) * mask).sum()
        # device-binned IS-ratio histogram: bisect_left over the shared
        # bucket ladder (searchsorted side="left" = the registry's
        # inclusive-le semantics), masked tokens weighted out
        log_ratio = (logps - mb.behavior_logps) * mask
        ratio = jnp.exp(log_ratio)
        bounds = jnp.asarray(_RATIO_BOUNDS, jnp.float32)
        idx = jnp.searchsorted(bounds, ratio, side="left")
        dyn["ratio_counts"] = (
            jax.nn.one_hot(idx, len(_RATIO_BOUNDS) + 1, dtype=jnp.float32)
            * mask[..., None]
        ).sum((0, 1))
        if clip_ratio > 0.0 and off_policy == "aipo":
            # AIPO cap saturation: tokens whose raw ratio the truncation
            # flattened — the silently-saturating regime the bundle exists
            # to surface (answer-mask scope; the version-lag mask is an
            # admission decision, not a saturation signal)
            dyn["cap_count"] = (
                (ratio >= is_cap).astype(jnp.float32) * mask
            ).sum()
        elif clip_ratio > 0.0:
            dyn["clip_count"] = (
                (jnp.abs(ratio - 1.0) > clip_ratio).astype(jnp.float32)
                * mask
            ).sum()
    return dyn


def _grad_norm_groups(grads, train_mode: str,
                      n_buckets: int = _GRAD_DEPTH_BUCKETS) -> dict:
    """Whole-tree grad norm, plus — for the LoRA pytree ``{"layers":
    {target: {"a": [L, …], "b": [L, …]}}}`` — per-group norms split A vs B
    and bucketed over the leading layer axis into ``n_buckets`` depth
    groups (summed across targets). Full-finetune trees get the total
    only."""
    leaves = jax.tree_util.tree_leaves(grads)
    total_sq = sum(
        jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves
    )
    out = {"grad_norm_total": jnp.sqrt(total_sq)}
    layers = (
        grads.get("layers")
        if train_mode == "lora" and isinstance(grads, dict) else None
    )
    if not layers:
        return out
    for ab in ("a", "b"):
        per_layer = None  # [L] sum of squares across targets
        for target in layers.values():
            if ab not in target:
                continue
            g = target[ab].astype(jnp.float32)
            sq = jnp.sum(jnp.square(g), axis=tuple(range(1, g.ndim)))
            per_layer = sq if per_layer is None else per_layer + sq
        if per_layer is None:
            continue
        n = min(n_buckets, per_layer.shape[0])
        for i, seg in enumerate(jnp.array_split(per_layer, n)):
            out[f"grad_norm_{ab}{i}"] = jnp.sqrt(seg.sum())
    return out


def _derive_dynamics(sums, grads, *, train_mode: str) -> dict:
    """Normalize the scan-accumulated sums into the published bundle."""
    tok = jnp.maximum(sums["tok_count"], 1.0)
    nadv = jnp.maximum(sums["adv_count"], 1.0)
    adv_mean = sums["adv_sum"] / nadv
    adv_var = jnp.maximum(
        sums["adv_sq_sum"] / nadv - jnp.square(adv_mean), 0.0
    )
    dyn = {
        "entropy": sums["entropy_sum"] / tok,
        "tokens": sums["tok_count"],
        "adv_mean": adv_mean,
        "adv_std": jnp.sqrt(adv_var),
        "adv_pos_frac": sums["adv_pos"] / nadv,
    }
    if "kl_sum" in sums:
        dyn["kl"] = sums["kl_sum"] / tok
        dyn["ratio_counts"] = sums["ratio_counts"]
    if "cap_count" in sums:
        dyn["cap_frac"] = sums["cap_count"] / tok
    if "clip_count" in sums:
        dyn["clip_frac"] = sums["clip_count"] / tok
    dyn.update(_grad_norm_groups(grads, train_mode))
    return dyn


def _microbatch_loss(
    lora, base_params, cfg: ModelConfig, mb: UpdateBatch, *,
    learner_type: str, lora_scale: float, skip_semantics: str,
    remat,  # False, True, or the layer scan's checkpoint policy (forward)
    attn_impl: str, attn_mesh=None, lora_dropout: float = 0.0,
    dropout_rng=None, logit_chunk: int = 0, train_mode: str = "lora",
    clip_ratio: float = 0.0, kl_coeff: float = 0.0,
    off_policy: str = "clip", is_cap: float = 2.0, max_staleness: int = 0,
    emit_dynamics: bool = False,
):
    """Loss for one microbatch with the zero-reward skip folded in as a weight.

    ``train_mode="lora"``: ``lora`` is the trainable adapter over the frozen
    ``base_params``. ``train_mode="full"``: ``lora`` IS the full trainable
    param tree (bf16 full-rank — reference recipe 3's no-LoRA mode) and
    ``base_params`` is ignored.

    ``emit_dynamics`` (static) appends the per-microbatch dynamics sums to
    the aux pytree; off leaves the program and the aux shape exactly as
    before."""
    entropy = None
    if train_mode == "full":
        out = answer_logprobs(
            lora, cfg, mb.prompt_ids, mb.prompt_mask, mb.answer_ids,
            mb.answer_mask, lora=None, remat=remat,
            attn_impl=attn_impl, attn_mesh=attn_mesh,
            logit_chunk=logit_chunk, return_entropy=emit_dynamics,
        )
    else:
        out = answer_logprobs(
            base_params, cfg, mb.prompt_ids, mb.prompt_mask, mb.answer_ids,
            mb.answer_mask, lora=lora, lora_scale=lora_scale, remat=remat,
            attn_impl=attn_impl, attn_mesh=attn_mesh,
            lora_dropout=lora_dropout, dropout_rng=dropout_rng,
            logit_chunk=logit_chunk, return_entropy=emit_dynamics,
        )
    logps, entropy = out if emit_dynamics else (out, None)
    # loss terms mask on POLICY spans only (multi-turn env rounds);
    # answer_mask above stays the attention mask — env-injected tokens are
    # context the behavior policy conditioned on, they just don't train
    loss_m = mb.answer_mask if mb.loss_mask is None else mb.loss_mask
    if clip_ratio > 0.0 and off_policy == "aipo":
        # async regime: truncated-IS correction keyed on per-token version
        # lag (rollout/staleness.py) instead of the 1±ε clip — staleness up
        # to K steps makes ratios drift past the clip band, where the
        # clipped surrogate's gradient vanishes exactly on the samples that
        # need correcting
        loss = grpo_aipo_loss(
            logps, mb.behavior_logps, loss_m.astype(jnp.float32),
            mb.coeffs, mb.sample_mask, is_cap=is_cap,
            version_lag=mb.version_lag, max_staleness=max_staleness,
        )
    elif clip_ratio > 0.0:
        loss = grpo_clip_loss(
            logps, mb.behavior_logps, loss_m.astype(jnp.float32),
            mb.coeffs, mb.sample_mask, clip_ratio=clip_ratio,
        )
    else:
        loss_fn = grpo_loss if learner_type == "grpo" else pg_loss
        loss = loss_fn(
            logps, loss_m.astype(jnp.float32), mb.coeffs, mb.sample_mask
        )
    if kl_coeff > 0.0:
        # π_ref = the frozen base (no adapter): one extra stop-gradient
        # forward; the GRPO paper's KL term the reference never wires up
        ref_logps = jax.lax.stop_gradient(answer_logprobs(
            base_params, cfg, mb.prompt_ids, mb.prompt_mask, mb.answer_ids,
            mb.answer_mask, lora=None, remat=remat,
            attn_impl=attn_impl, attn_mesh=attn_mesh, logit_chunk=logit_chunk,
        ))
        loss = loss + kl_coeff * kl_to_ref(
            logps, ref_logps, loss_m.astype(jnp.float32),
            mb.sample_mask,
        )

    # The skip operates on COEFFS (baseline-subtracted rewards / advantages),
    # exactly like the reference: Learner.train flattens `r - b` and GRPO
    # flattens advantages BEFORE compute_loss tests `batch_rewards.all() == 0`
    # (distributed_actor.py:406, :495–504, :367). A GRPO group with identical
    # rewards therefore zeroes out and is skipped in both frameworks.
    real = mb.sample_mask > 0
    if skip_semantics == "any_zero":  # reference bug-parity (.all()==0)
        skip = jnp.any(real & (mb.coeffs == 0.0))
    else:  # "all_zero" — the documented intent
        skip = ~jnp.any(real & (mb.coeffs != 0.0))
    has_real = jnp.any(real)
    weight = jnp.where(skip | ~has_real, 0.0, 1.0)
    if emit_dynamics:
        dyn = _microbatch_dynamics(
            logps, entropy, mb,
            clip_ratio=clip_ratio, off_policy=off_policy, is_cap=is_cap,
        )
        return loss * weight, (weight, has_real.astype(jnp.float32), dyn)
    return loss * weight, (weight, has_real.astype(jnp.float32))


def make_train_step(
    cfg: ModelConfig,
    *,
    learner_type: str = "pg",
    optimizer: optax.GradientTransformation,
    lora_scale: float,
    micro_size: int,
    skip_semantics: str = "all_zero",
    remat: bool = True,
    attn_impl: str = "reference",
    attn_mesh=None,
    donate: bool = True,
    lora_dropout: float = 0.0,
    logit_chunk: int = 0,  # chunked fused-CE logprobs (losses.answer_logprobs)
    train_mode: str = "lora",  # "lora" | "full" (arg0 is the whole param tree)
    clip_ratio: float = 0.0,  # >0: PPO-clip surrogate over engine logprobs
    kl_coeff: float = 0.0,  # >0: + coeff·KL(π‖frozen base); LoRA mode only
    off_policy: str = "clip",  # "clip" (1±ε) | "aipo" (truncated IS, async)
    is_cap: float = 2.0,  # AIPO ratio truncation C
    max_staleness: int = 0,  # AIPO: mask tokens with version lag beyond this
    emit_dynamics: bool = False,  # ISSUE 16: fuse the dynamics bundle in
) -> Callable:
    """Build the jitted train step.

    Returns ``step(lora, opt_state, base_params, batch) -> (lora, opt_state,
    loss_sum)`` where ``loss_sum`` matches the reference's returned metric: the
    sum of unscaled microbatch losses (its ``total_loss`` accumulation at
    distributed_actor.py:387–389 cancels the /num_batches scaling).

    ``emit_dynamics=True`` (static) returns ``(lora, opt_state, loss_sum,
    dynamics)`` instead, where ``dynamics`` is the device-computed
    training-dynamics bundle (ISSUE 16): masked answer-token entropy,
    behavior↔policy KL + the pre-binned IS-ratio histogram + clip/cap
    saturation (only when the batch carries behavior logprobs), advantage
    moments, and per-layer-group grad norms — all derived under
    ``stop_gradient`` from intermediates the loss already materializes, so
    the loss/update subgraph is unchanged and the bundle rides the caller's
    existing single host fetch. Off compiles to the exact pre-ISSUE-16
    program.

    ``remat=True`` fits the device's memory: the layer scan keeps the weights'
    products for the backward pass as far as they fit beside the step
    (learner/remat.py), decided at a batch shape's first call from the memory
    of the devices that hold the trainable tree, and held for every later
    trace of that shape. ``step.lower(...)`` takes the call's arguments.
    """

    if train_mode == "full" and kl_coeff > 0.0:
        # the config layer also rejects this; guard the mechanism too — in
        # full mode there is no frozen base to serve as the reference policy
        raise ValueError("kl_coeff requires train_mode='lora' (frozen base = ref)")
    if off_policy not in ("clip", "aipo"):
        raise ValueError(
            f"off_policy must be 'clip' or 'aipo', got {off_policy!r}"
        )
    loss_fn = partial(
        _microbatch_loss,
        cfg=cfg,
        learner_type=learner_type,
        lora_scale=lora_scale,
        skip_semantics=skip_semantics,
        attn_impl=attn_impl,
        attn_mesh=attn_mesh,
        lora_dropout=lora_dropout,
        logit_chunk=logit_chunk,
        train_mode=train_mode,
        clip_ratio=clip_ratio,
        kl_coeff=kl_coeff,
        off_policy=off_policy,
        is_cap=is_cap,
        max_staleness=max_staleness,
        emit_dynamics=emit_dynamics,
    )

    def step(lora, opt_state, base_params, batch: UpdateBatch,
             dropout_rng=None, *, keep: tuple[str, ...] = ()):
        scan_remat = remat and remat_lib.policy(keep)
        n = batch.prompt_ids.shape[0]
        assert n % micro_size == 0, f"batch {n} not divisible by micro {micro_size}"
        num_micro = n // micro_size
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((num_micro, micro_size) + x.shape[1:]), batch
        )

        def scoped_loss(lo, mb, key):
            # forward under this name; JAX writes the rest of the path itself:
            # transpose(jvp(learner/loss)) is the backward pass, and
            # rematted_computation under it the recomputed forward
            with jax.named_scope(telemetry.LEARNER_LOSS):
                return loss_fn(lo, base_params, mb=mb, dropout_rng=key,
                               remat=scan_remat)

        grad_fn = jax.value_and_grad(scoped_loss, has_aux=True)
        # independent dropout masks per microbatch (None → dropout disabled)
        micro_keys = (
            jax.random.split(dropout_rng, num_micro)
            if dropout_rng is not None else None
        )

        def accumulate(carry, xs):
            mb, key = xs
            grads_acc, loss_acc, nb_acc = carry
            (loss, aux), grads = grad_fn(lora, mb, key)
            weight, has_real = aux[0], aux[1]
            with jax.named_scope(telemetry.LEARNER_GRAD_ACCUM):
                grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
                # dynamics sums ride the scan's ys output (stacked then summed
                # below) so the carry shape is untouched; None when off — the
                # exact pre-ISSUE-16 scan
                ys = aux[2] if emit_dynamics else None
                return (grads_acc, loss_acc + loss, nb_acc + has_real), ys

        with jax.named_scope(telemetry.LEARNER_GRAD_ACCUM):
            zero_grads = jax.tree_util.tree_map(jnp.zeros_like, lora)
        (grads, loss_sum, num_real_micro), dyn_stacked = jax.lax.scan(
            accumulate, (zero_grads, jnp.zeros([]), jnp.zeros([])),
            (micro, micro_keys),
        )
        # reference scaling: each microbatch contributes grad/num_batches
        # (distributed_actor.py:382); num_batches counts microbatches with real
        # rows, skipped-or-not — padding-only microbatches are excluded.
        with jax.named_scope(telemetry.LEARNER_GRAD_ACCUM):
            denom = jnp.maximum(num_real_micro, 1.0)
            grads = jax.tree_util.tree_map(lambda g: g / denom, grads)

        dynamics = None
        if emit_dynamics:
            sums = jax.tree_util.tree_map(
                lambda x: x.sum(axis=0), dyn_stacked
            )
            # grad norms read the averaged grads the optimizer consumes —
            # the same tree, pure reads, no effect on the update
            dynamics = _derive_dynamics(sums, grads, train_mode=train_mode)
        with jax.named_scope(telemetry.LEARNER_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, lora)
            lora = optax.apply_updates(lora, updates)
        if emit_dynamics:
            return lora, opt_state, loss_sum, dynamics
        return lora, opt_state, loss_sum

    jitted = jax.jit(step, static_argnames="keep",
                     donate_argnums=(0, 1) if donate else ())
    if not remat:
        return jitted
    kept: dict[tuple, tuple[str, ...]] = {}  # batch shape -> the names it keeps

    def keep_for(lora, base_params, batch: UpdateBatch) -> tuple[str, ...]:
        weights = lora if train_mode == "full" else base_params
        dtype = weights["embed"].dtype  # the activations', so the products'
        shape = (batch.prompt_ids.shape[1], batch.answer_ids.shape[1], dtype)
        if shape not in kept:
            trainable = jax.tree_util.tree_leaves(lora)
            sharding = getattr(trainable[0], "sharding", None)
            devices = sharding.addressable_devices if sharding else ()
            answer = shape[1]
            kept[shape] = remat_lib.choose_kept(
                cfg, rows=micro_size, seq=shape[0] + answer,
                head_positions=logit_chunk if 0 < logit_chunk < answer else answer,
                itemsize=dtype.itemsize,
                trainable_bytes=sum(x.size * x.dtype.itemsize for x in trainable),
                devices=sorted(devices, key=lambda d: d.id),
            )
        return kept[shape]

    def with_kept(fn):
        def call(lora, opt_state, base_params, batch, dropout_rng=None):
            return fn(lora, opt_state, base_params, batch, dropout_rng,
                      keep=keep_for(lora, base_params, batch))
        return call

    train_step = with_kept(jitted)
    train_step.lower = with_kept(jitted.lower)
    return train_step


def _bucket_width(mask, buckets, cap: int) -> int:
    """Smallest bucket holding the longest real row of ``mask`` (row length
    = mask sum), capped at ``cap``; ``cap`` when no bucket is large enough.
    The single owner of the learner-side bucket-selection rule (the engine's
    ``bucket_for`` is the same rule over its own bucket list)."""
    lens = np.asarray(mask).sum(axis=1)
    need = max(1, int(lens.max()) if lens.size else 1)
    return min(next((b for b in sorted(buckets) if b >= need), cap), cap)


def prepare_update_batch(
    tokenizer,
    problems: list[str],
    answers: list[str],
    coeffs: np.ndarray,
    *,
    max_prompt_tokens: int,
    max_new_tokens: int,
    micro_size: int,
    mesh=None,
    raw_rollout: dict | None = None,
    answer_buckets: "Sequence[int] | None" = None,
    prompt_buckets: "Sequence[int] | None" = None,
    current_version: int | None = None,
) -> UpdateBatch:
    """Host-side tokenize+pad to the fixed learner shapes.

    Mirrors the reference's encode calls (distributed_actor.py:217–229):
    prompts left-padded/truncated to max_prompt_tokens, answers right-padded/
    truncated to max_new_tokens. N is padded up to a multiple of micro_size
    with sample_mask-0 rows so the scan shape is static.

    ``answer_buckets``: learner-side length bucketing (the engine's
    prompt-bucket idea applied to the update step). The answer width is cut
    to the smallest bucket holding the batch's LONGEST real answer instead
    of always padding to max_new_tokens — the reference pads every row to
    the full window (distributed_actor.py:224–229), which at its own ~470
    mean generation length wastes ~60% of learner FLOPs on masked padding.
    Dropping trailing all-masked columns is exact (masked positions
    contribute zero loss and are causally invisible to real positions —
    pinned by TestAnswerBuckets parity). One compiled step per bucket
    width; buckets cap the recompile count.

    ``prompt_buckets``: the same cut on the LEFT-padded prompt side
    (leading all-masked columns dropped to the smallest bucket holding the
    longest real prompt) — reuses the engine's prompt-bucket config, since
    learner prompts are the same strings the engine saw. Equality here is
    up to RoPE float round-off rather than bit-exact: dropping k leading
    columns shifts every position in a row by the same constant, and RoPE
    attention depends only on relative distance (the same invariance the
    left-padded golden test pins), but the absolute angles differ.

    When ``mesh`` is given, every array is placed on it with the row dim over
    "dp" — the learner-mesh equivalent of the reference dispatching chunks to
    learner processes (distributed_trainer.py:312–327).
    """
    from distrl_llm_tpu.tokenizer import encode_fixed

    n_real = len(problems)
    prompt_ids, prompt_mask = encode_fixed(
        tokenizer, problems, max_prompt_tokens, side="left"
    )
    if prompt_buckets:
        # prompts are LEFT-padded: keep the trailing `width` columns
        # (leading all-masked columns are pure padding — exactly the
        # engine's bucket slice, engine.py::_generate_wave)
        p_width = _bucket_width(prompt_mask, prompt_buckets, max_prompt_tokens)
        if p_width < max_prompt_tokens:
            prompt_ids = np.asarray(prompt_ids)[:, -p_width:]
            prompt_mask = np.asarray(prompt_mask)[:, -p_width:]
    behavior_logps = None
    version_lag = None
    loss_mask = None
    if raw_rollout is not None:
        # PPO-clip path: train on the ENGINE'S token ids (retokenizing the
        # decoded text can shift token boundaries and desync the per-token
        # behavior logprobs — flatten_for_update docstring)
        eng_tokens = np.asarray(raw_rollout["answer_tokens"], np.int32)
        eng_logps = np.asarray(raw_rollout["behavior_logps"], np.float32)
        t_eng = eng_tokens.shape[1]
        width = min(t_eng, max_new_tokens)
        answer_ids = np.zeros((n_real, max_new_tokens), np.int32)
        behavior = np.zeros((n_real, max_new_tokens), np.float32)
        answer_ids[:, :width] = eng_tokens[:, :width]
        behavior[:, :width] = eng_logps[:, :width]
        # mask from real generated lengths: engine pads after EOS with a pad
        # token whose id may be a REAL vocab id, so the text-derived mask
        # cannot be reused
        # defensive clamp: engine lengths are bounded by the engine's token
        # buffer (t_eng), but if that invariant ever broke, an unclamped
        # length would unmask positions holding zero-filled ids / logprobs
        lengths = np.minimum(np.asarray(raw_rollout["lengths"], np.int32), width)
        answer_mask = (
            np.arange(max_new_tokens)[None, :] < lengths[:, None]
        ).astype(np.int32)
        if "loss_mask" in raw_rollout:
            # multi-turn env rounds (ISSUE 17): environment-injected
            # observation tokens stay in answer_mask (attention context —
            # the behavior policy conditioned on them) but are excluded
            # from the separate loss mask so they never train
            lm = np.zeros((n_real, max_new_tokens), np.int32)
            lm_src = np.asarray(raw_rollout["loss_mask"], np.int32)
            lm[:, :width] = lm_src[:, :width]
            loss_mask = answer_mask * lm
        behavior_logps = behavior
        if current_version is not None and "version_tags" in raw_rollout:
            # per-token optimizer-step lag from the rollout version tags
            # (rollout/trajectory.py); padded columns get lag 0 — they are
            # masked anyway, and a large filler value would trip the AIPO
            # staleness mask's comparison on garbage positions
            tags = np.asarray(raw_rollout["version_tags"], np.int32)
            version_lag = np.zeros((n_real, max_new_tokens), np.float32)
            version_lag[:, :width] = np.maximum(
                current_version - tags[:, :width], 0
            )
            version_lag *= loss_mask if loss_mask is not None else answer_mask
    else:
        answer_ids, answer_mask = encode_fixed(
            tokenizer, answers, max_new_tokens, side="right"
        )
    if answer_buckets:
        # smallest bucket holding the longest real answer (answers are
        # right-padded, so trailing columns past it are all-masked and
        # dropping them is exact); no bucket large enough → full width
        width = _bucket_width(answer_mask, answer_buckets, max_new_tokens)
        if width < max_new_tokens:
            answer_ids = np.asarray(answer_ids)[:, :width]
            answer_mask = np.asarray(answer_mask)[:, :width]
            if behavior_logps is not None:
                behavior_logps = behavior_logps[:, :width]
            if version_lag is not None:
                version_lag = version_lag[:, :width]
            if loss_mask is not None:
                loss_mask = loss_mask[:, :width]
    n = -(-max(n_real, 1) // micro_size) * micro_size
    pad = n - n_real

    def pad_rows(x):
        return np.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

    sample_mask = np.zeros(n, np.float32)
    sample_mask[:n_real] = 1.0
    batch = UpdateBatch(
        prompt_ids=jnp.asarray(pad_rows(prompt_ids)),
        prompt_mask=jnp.asarray(pad_rows(prompt_mask)),
        answer_ids=jnp.asarray(pad_rows(np.asarray(answer_ids))),
        answer_mask=jnp.asarray(pad_rows(np.asarray(answer_mask))),
        coeffs=jnp.asarray(pad_rows(np.asarray(coeffs, np.float32))),
        sample_mask=jnp.asarray(sample_mask),
        behavior_logps=(
            jnp.asarray(pad_rows(behavior_logps))
            if behavior_logps is not None else None
        ),
        version_lag=(
            jnp.asarray(pad_rows(version_lag))
            if version_lag is not None else None
        ),
        loss_mask=(
            jnp.asarray(pad_rows(np.asarray(loss_mask)))
            if loss_mask is not None else None
        ),
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # rows shard over dp only if the count divides evenly; otherwise the
        # batch stays replicated (tiny smoke runs) rather than failing
        def place(x):
            dp = mesh.shape["dp"]
            spec = P("dp", *([None] * (x.ndim - 1))) if x.shape[0] % dp == 0 else P()
            return jax.device_put(x, NamedSharding(mesh, spec))

        batch = jax.tree_util.tree_map(place, batch)
    return batch
