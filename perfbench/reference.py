"""Plain reference of the dense GQA decoder (the Qwen2 family), in float32.

Written from the published description: token embedding; per layer a pre-norm
RMSNorm, grouped-query attention with a bias on q, k and v, rotary positions
(rotate-half, base ``rope_theta``), causal softmax, output projection, a second
RMSNorm and a SwiGLU MLP, each added to the residual stream; a final RMSNorm;
the output head (the embedding transposed when tied). A LoRA adapter adds
``(x A) B * scale`` to each of the seven projections. No cache, no kernels, no
batching tricks: one full forward over the whole sequence.

Departures, each for memory on a 16 GB chip that also holds the system under
test: the weights stay in the type they are served in and are widened to
float32 one layer at a time inside a scan (bf16 widens exactly); rows run one
after another; the vocabulary is projected in chunks with a running
log-sum-exp; and reverse mode recomputes each row, layer and chunk from its
input (``jax.checkpoint``), so that no widened weight is kept. None changes a
value. Every matmul runs under
``default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise done in bf16 passes.

``model`` is the program's ``ModelConfig`` only as a bag of sizes (it is read
for ``hidden_size``, ``num_heads`` ... ``rope_theta``); no code of the program
runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
VOCAB_CHUNKS = 8


def _check_family(model) -> None:
    if getattr(model, "hidden_act", "silu") != "silu" or getattr(
        model, "rmsnorm_offset", False
    ) or getattr(model, "scale_embeddings", False):
        raise NotImplementedError(
            "perfbench/reference.py describes the Qwen2 family (SiLU, plain "
            "RMSNorm, unscaled embeddings); another family brings its own "
            "reference module, named by the configuration file"
        )


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x [S, H, D]; rotate-half convention: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    angles = positions.astype(_F32)[:, None] * inv_freq  # [S, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _project(x, layer, lora_layer, name, bias_name, scale):
    y = x @ layer[name].astype(_F32)
    if bias_name in layer:
        y = y + layer[bias_name].astype(_F32)
    if lora_layer is not None and name in lora_layer:
        a = lora_layer[name]["a"].astype(_F32)
        b = lora_layer[name]["b"].astype(_F32)
        y = y + (x @ a) @ b * scale
    return y


def _layer(x, valid, positions, layer, lora_layer, model, scale):
    """One decoder layer over one row. x [S, hidden] float32; valid [S] bool."""
    s = x.shape[0]
    heads, kv_heads, hd = model.num_heads, model.num_kv_heads, model.head_dim
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    q = _project(h, layer, lora_layer, "wq", "bq", scale).reshape(s, heads, hd)
    k = _project(h, layer, lora_layer, "wk", "bk", scale).reshape(s, kv_heads, hd)
    v = _project(h, layer, lora_layer, "wv", "bv", scale).reshape(s, kv_heads, hd)
    q = _rope(q, positions, model.rope_theta)
    k = _rope(k, positions, model.rope_theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)  # each kv head serves `group` q heads
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    allowed = causal & valid[None, :]
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    # a padding query attends nothing; keep its row finite (it is never read)
    scores = jnp.where(valid[None, :, None], scores, 0.0)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    x = x + _project(att, layer, lora_layer, "wo", "bo", scale)
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)
    gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
    up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
    return x + _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row. Padding (valid False)
    may sit anywhere; positions count the valid tokens only."""
    positions = jnp.maximum(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0)
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    lora_layers = lora["layers"] if lora is not None else None

    def body(x, per_layer):
        layer, lora_layer = per_layer
        return _layer(x, valid, positions, layer, lora_layer, model, scale), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, (params["layers"], lora_layers))
    return _rms_norm(x, params["final_norm"].astype(_F32), model.rms_norm_eps)


def _token_logprobs_row(params, model, hidden, targets):
    """log softmax(hidden @ head)[targets], the vocabulary in VOCAB_CHUNKS
    pieces with a running log-sum-exp. hidden [S, hidden]; targets [S]."""
    head = params["embed"].T if model.tie_word_embeddings else params["lm_head"]
    vocab = head.shape[1]
    chunk = -(-vocab // VOCAB_CHUNKS)
    pad = chunk * VOCAB_CHUNKS - vocab
    head = jnp.pad(head, ((0, 0), (0, pad)))
    head = head.reshape(head.shape[0], VOCAB_CHUNKS, chunk).transpose(1, 0, 2)
    starts = jnp.arange(VOCAB_CHUNKS) * chunk

    def body(carry, piece):
        lse, picked = carry
        w, start = piece
        logits = hidden @ w.astype(_F32)  # [S, chunk]
        col = start + jnp.arange(chunk)
        logits = jnp.where(col[None, :] < vocab, logits, -jnp.inf)
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
        local = targets - start
        here = (local >= 0) & (local < chunk)
        got = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=-1
        )[:, 0]
        return (lse, jnp.where(here, got, picked)), None

    init = (jnp.full(hidden.shape[:1], -jnp.inf, _F32),
            jnp.zeros(hidden.shape[:1], _F32))
    (lse, picked), _ = jax.lax.scan(jax.checkpoint(body), init, (head, starts))
    return picked - lse


def next_token_logprobs(params, model, ids, mask, *, lora=None, lora_scale=1.0):
    """[B, S-1] float32: log p(ids[:, t+1] | ids[:, :t+1]) under the model,
    teacher-forced over ``ids`` [B, S] with validity ``mask`` [B, S]. Entries
    whose target or context is padding mean nothing; the caller masks them."""
    _check_family(model)

    def row(args):
        ids_r, mask_r = args
        hidden = _hidden_row(params, lora, model, ids_r, mask_r > 0, lora_scale)
        return _token_logprobs_row(params, model, hidden[:-1], ids_r[1:])

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(jax.checkpoint(row), (ids, mask))


def pg_loss(params, model, lora, lora_scale, ids, mask, answer_mask, coeffs):
    """Vanilla policy gradient over whole rows ``ids`` [B, S]: the mean
    log-probability of each row's answer tokens (``answer_mask`` [B, S], 1
    where the token at that column is a scored answer token) times its
    coefficient, averaged over rows, negated."""
    logp = next_token_logprobs(
        params, model, ids, mask, lora=lora, lora_scale=lora_scale
    )
    scored = answer_mask[:, 1:].astype(_F32)
    per_row = (logp * scored).sum(-1) / jnp.maximum(scored.sum(-1), 1.0)
    return -(per_row * coeffs).mean()


def pg_loss_and_lora_grad(params, model, lora, lora_scale, ids, mask,
                          answer_mask, coeffs):
    """(loss, d loss / d adapter) of ``pg_loss``, by plain reverse mode."""
    return jax.value_and_grad(
        lambda lo: pg_loss(params, model, lo, lora_scale, ids, mask,
                           answer_mask, coeffs)
    )(lora)
