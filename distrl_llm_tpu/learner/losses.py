"""Policy-gradient and GRPO losses over recomputed answer logprobs.

Parity with the reference learner math (distributed_actor.py:215–260, :349–395,
:440–493):

* **Fixed-shape logprob recompute** — prompt left-padded to max_prompt_tokens,
  answer right-padded to max_new_tokens, one forward over the concat, shift by
  one, slice the answer region (:217–249). The reference chose fixed shapes to
  bound GPU memory; here they also mean exactly one XLA compilation.
* **PG loss** ``−(((logp·mask).Σ/mask.Σ)·coeff).mean()`` (:375) where coeff is
  reward − baseline (applied upstream, :406).
* **GRPO loss** uses the ratio trick ``exp(logp − stop_grad(logp))`` (≡1 at
  compute time, gradient = ∇logp · adv) with group-normalized advantages
  (:467–470). No KL, no clipping — the reference takes exactly one update per
  rollout batch, so the clipped objective never binds (SURVEY §3.6.2).

Instead of materializing the [B, T, V] log_softmax and gathering row-by-row in
a Python loop (the reference's memory cap, :252–260), per-token logprobs are
``gathered_logit − logsumexp`` — O(B·T) extra memory and XLA fuses the
logsumexp into the projection epilogue.

``logit_chunk`` goes further — the fused-cross-entropy equivalent of
unsloth's Triton CE kernel (SURVEY §2b N3): the lm_head projection +
logsumexp run per time-chunk under ``lax.scan`` with ``jax.checkpoint``, so
the live logits buffer is [B, Tc, V] instead of [B, T, V] in both the
forward AND the backward (the chunk recomputes its logits from the saved
[B, Tc, D] hidden slice). At the reference learner shapes (micro 8 × 1200
answer tokens × 152k vocab, f32) that is 5.8 GB → ~0.6 GB at Tc=128, with
bit-identical per-position math (each position's logsumexp still spans the
full vocab).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.models.configs import ModelConfig
from distrl_llm_tpu.models.transformer import forward
from distrl_llm_tpu.ops.linear import linear


def answer_logprobs(
    params,
    cfg: ModelConfig,
    prompt_ids: jax.Array,  # [B, P] left-padded
    prompt_mask: jax.Array,  # [B, P]
    answer_ids: jax.Array,  # [B, T] right-padded
    answer_mask: jax.Array,  # [B, T]
    *,
    lora=None,
    lora_scale: float = 1.0,
    remat=True,  # True, or the layer scan's checkpoint policy (forward)
    attn_impl: str = "reference",
    attn_mesh=None,
    lora_dropout: float = 0.0,
    dropout_rng: jax.Array | None = None,
    logit_chunk: int = 0,  # 0 = dense [B, T, V]; >0 = chunked CE (see module doc)
    return_entropy: bool = False,  # also return per-position vocab entropy
) -> jax.Array:
    """Per-token logprobs of the answer under the current policy, [B, T] f32.

    Equivalent to the reference's compute_current_policy_probs
    (distributed_actor.py:215–260): token t's logprob comes from the logit at
    position P−1+t of the concatenated sequence.

    ``return_entropy=True`` additionally returns the full-vocab policy
    entropy per position, [B, T] f32 — ``H = lse − Σ softmax(logits)·logits``,
    read off the same logits/logsumexp the logprob gather already
    materializes (both the dense and the chunked-CE path), so the
    training-dynamics bundle (ISSUE 16) costs no extra projection and no
    extra host transfer. The flag is static: the default-off program is
    unchanged.
    """
    full_ids = jnp.concatenate([prompt_ids, answer_ids], axis=1)
    full_mask = jnp.concatenate([prompt_mask, answer_mask], axis=1)
    p = prompt_ids.shape[1]
    t = answer_ids.shape[1]
    fwd_kwargs = dict(
        attention_mask=full_mask, lora=lora, lora_scale=lora_scale,
        remat=remat, attn_impl=attn_impl, attn_mesh=attn_mesh,
        # project only positions P-1 .. P-1+T-1 (the logits predicting answer
        # tokens) — prompt logits would be discarded, so don't compute them
        logits_slice=(p - 1, t),
        lora_dropout=lora_dropout, dropout_rng=dropout_rng,
    )
    if logit_chunk <= 0 or logit_chunk >= t:
        pred, _ = forward(params, cfg, full_ids, **fwd_kwargs)  # [B, T, V]
        with jax.named_scope(telemetry.LEARNER_LOSS_LOGPROB):
            gathered = jnp.take_along_axis(pred, answer_ids[..., None], axis=-1)[..., 0]
            lse = jax.nn.logsumexp(pred, axis=-1)
            if not return_entropy:
                return gathered - lse
            entropy = lse - (jax.nn.softmax(pred, axis=-1) * pred).sum(-1)
            return gathered - lse, entropy

    x, _ = forward(params, cfg, full_ids, skip_lm_head=True, **fwd_kwargs)
    with jax.named_scope(telemetry.LEARNER_LOSS_LOGPROB):
        return _chunked_logprobs(
            x, params, cfg, answer_ids, logit_chunk, return_entropy
        )


def _chunked_logprobs(x, params, cfg: ModelConfig, answer_ids, chunk: int,
                      return_entropy: bool):
    """The output head and the log-softmax gather per time chunk (module
    docstring): ``x`` is the final-norm hidden states of the answer region."""
    t = answer_ids.shape[1]
    b, _, d = x.shape
    # pad T up to a chunk multiple (padded positions are sliced off below) —
    # falling back to a DIVISOR of T would silently collapse to tiny chunks
    # for awkward lengths (prime T → chunk 1 → T sequential [B,1,V] matmuls)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    padded_ids = answer_ids
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        padded_ids = jnp.pad(answer_ids, ((0, 0), (0, pad)))
    lm_head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    xs = x.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)  # [n, B, C, D]
    ids = padded_ids.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def chunk_logprobs(x_c, ids_c):
        logits = linear(x_c, lm_head).astype(jnp.float32)  # [B, C, V]
        g = jnp.take_along_axis(logits, ids_c[..., None], axis=-1)[..., 0]
        lse = jax.nn.logsumexp(logits, axis=-1)
        if not return_entropy:
            return g - lse
        ent = lse - (jax.nn.softmax(logits, axis=-1) * logits).sum(-1)
        return g - lse, ent

    def body(carry, xc_ic):
        # checkpoint: the backward recomputes this chunk's logits from its
        # [B, C, D] hidden slice instead of keeping [B, C, V] alive per chunk
        return carry, jax.checkpoint(chunk_logprobs)(*xc_ic)

    _, out = jax.lax.scan(body, None, (xs, ids))  # [n, B, C]

    def unchunk(o):
        return o.swapaxes(0, 1).reshape(b, n_chunks * chunk)[:, :t]

    if not return_entropy:
        return unchunk(out)
    return unchunk(out[0]), unchunk(out[1])


def _masked_mean_seq(logp_like: jax.Array, mask: jax.Array) -> jax.Array:
    """(x·mask).Σ/mask.Σ per row, guarding empty answers (all-pad rows would be
    0/0 = NaN in the reference)."""
    denom = jnp.maximum(mask.sum(-1), 1.0)
    return (logp_like * mask).sum(-1) / denom


def pg_loss(
    logprobs: jax.Array,  # [B, T]
    answer_mask: jax.Array,  # [B, T]
    coeffs: jax.Array,  # [B] reward − baseline
    sample_mask: jax.Array | None = None,  # [B] 1 = real row (padding rows 0)
) -> jax.Array:
    """Vanilla PG: mean over rows of −(mean answer logprob)·coeff
    (distributed_actor.py:375)."""
    per_row = _masked_mean_seq(logprobs, answer_mask) * coeffs
    if sample_mask is None:
        return -per_row.mean()
    denom = jnp.maximum(sample_mask.sum(), 1.0)
    return -(per_row * sample_mask).sum() / denom


def grpo_loss(
    logprobs: jax.Array,
    answer_mask: jax.Array,
    advantages: jax.Array,
    sample_mask: jax.Array | None = None,
) -> jax.Array:
    """Single-update GRPO: ratio ≡ 1 at compute time, gradient flows through
    exp(logp − stop_grad(logp)) (distributed_actor.py:467–470)."""
    ratio = jnp.exp(logprobs - jax.lax.stop_gradient(logprobs))
    per_row = _masked_mean_seq(ratio, answer_mask) * advantages
    if sample_mask is None:
        return -per_row.mean()
    denom = jnp.maximum(sample_mask.sum(), 1.0)
    return -(per_row * sample_mask).sum() / denom


def grpo_clip_loss(
    logprobs: jax.Array,  # [B, T] current-policy logprobs
    behavior_logps: jax.Array,  # [B, T] rollout-time logprobs (engine-captured)
    answer_mask: jax.Array,  # [B, T]
    advantages: jax.Array,  # [B]
    sample_mask: jax.Array | None = None,
    clip_ratio: float = 0.2,
) -> jax.Array:
    """PPO-clip surrogate over raw-basis importance ratios — the stability
    mechanism the reference lacks (its GRPO has "no KL, no clipping",
    distributed_actor.py:467–470, and its README admits "training becomes
    unstable with longer training", README.md:91). The behavior logprobs
    come from the engine at sample time (GenerationResult.logprobs, the
    vLLM-logprobs equivalent), so the ratio is exact even when the update
    is off-policy (async_rollout's one-step staleness, or multiple
    optimizer steps per rollout batch). Both logprob sides are RAW
    log_softmax (see ops/sampling.token_logprob for the convention and its
    approximation at temperature != 1):

        ratio_t = exp(logp_current − logp_behavior)
        loss = −mean_rows( mean_t min(ratio·A, clip(ratio, 1±ε)·A) )
    """
    ratio = jnp.exp(logprobs - behavior_logps)
    clipped = jnp.clip(ratio, 1.0 - clip_ratio, 1.0 + clip_ratio)
    adv = advantages[:, None]
    surrogate = jnp.minimum(ratio * adv, clipped * adv)
    per_row = _masked_mean_seq(surrogate, answer_mask)
    if sample_mask is None:
        return -per_row.mean()
    denom = jnp.maximum(sample_mask.sum(), 1.0)
    return -(per_row * sample_mask).sum() / denom


def grpo_aipo_loss(
    logprobs: jax.Array,  # [B, T] current-policy logprobs
    behavior_logps: jax.Array,  # [B, T] rollout-time logprobs (engine-captured)
    answer_mask: jax.Array,  # [B, T]
    advantages: jax.Array,  # [B]
    sample_mask: jax.Array | None = None,
    is_cap: float = 2.0,
    version_lag: jax.Array | None = None,  # [B, T] optimizer-step lag per token
    max_staleness: int = 0,
) -> jax.Array:
    """Truncated-importance-sampling policy gradient — the asynchronous-RL
    objective (AIPO, LlamaRL arxiv 2505.24034 §4.2; PipelineRL trains the
    same shape). Where ``grpo_clip_loss`` clips the surrogate around 1±ε
    (right for near-on-policy data, one step stale at most), the async
    regime trains on trajectories up to ``max_staleness`` optimizer steps
    old, where ratios legitimately drift far from 1 — clipping both sides
    there zeroes the gradient of exactly the samples that need correcting.
    Truncated IS instead keeps the estimator unbiased-below-the-cap and
    bounds its variance above it:

        ratio_t = min(exp(logp_current − logp_behavior), C)
        loss = −mean_rows( mean_t ratio_t · A )

    ``version_lag`` keys the correction on the per-token policy-version tags
    (rollout/trajectory.py): a trajectory that spans K in-flight weight
    swaps carries per-token lags, and tokens whose OWN lag exceeds
    ``max_staleness`` are masked out of the objective — the admission
    policy's drop, enforced token-wise for mixed-version trajectories whose
    head is fresh but whose tail predates the bound (or vice versa).
    """
    ratio = jnp.minimum(jnp.exp(logprobs - behavior_logps), is_cap)
    mask = answer_mask
    if version_lag is not None and max_staleness > 0:
        mask = mask * (version_lag <= max_staleness).astype(mask.dtype)
    per_row = _masked_mean_seq(ratio * advantages[:, None], mask)
    if sample_mask is None:
        return -per_row.mean()
    denom = jnp.maximum(sample_mask.sum(), 1.0)
    return -(per_row * sample_mask).sum() / denom


def kl_to_ref(
    logprobs: jax.Array,  # [B, T] current-policy logprobs of sampled tokens
    ref_logps: jax.Array,  # [B, T] reference-policy logprobs (stop-gradient)
    answer_mask: jax.Array,  # [B, T]
    sample_mask: jax.Array | None = None,
) -> jax.Array:
    """Per-token KL(π‖π_ref) via the k3 estimator the GRPO paper uses
    (unbiased, always ≥ 0): exp(ref − cur) − (ref − cur) − 1, masked-meaned
    per row then averaged over real rows. The reference repo never loads a
    reference model (SURVEY §3.6.2); with LoRA the frozen base IS π_ref, so
    the penalty costs one extra no-adapter forward and no extra memory."""
    # zero the exponent at masked pads BEFORE exp: pad positions hold
    # garbage logprobs of the zero-filled token id, and exp(diff) overflows
    # to inf past ~88 nats — inf·0 mask would then poison the mean with NaN
    diff = (ref_logps - logprobs) * answer_mask
    k3 = jnp.exp(diff) - diff - 1.0
    per_row = _masked_mean_seq(k3, answer_mask)
    if sample_mask is None:
        return per_row.mean()
    denom = jnp.maximum(sample_mask.sum(), 1.0)
    return (per_row * sample_mask).sum() / denom


def entropy_bonus(logprobs_full: jax.Array, alpha: float) -> jax.Array:
    """Entropy regularizer over the vocab distribution — defined for API parity
    with the reference's compute_entropy_bonus (distributed_actor.py:266–281),
    which is never enabled there either (call sites commented out)."""
    probs = jnp.exp(logprobs_full)
    entropy = -(probs * logprobs_full).sum(-1)
    return alpha * entropy.mean()
