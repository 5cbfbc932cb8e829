"""Plain reference of a looped dense decoder (``ouro``: Ouro-2.6B), in float32.

Written from the equations of ISSUE 68 / PERF.md section 4, which are the
published ``config.json``'s sizes and, where that file is silent, the LoopLM
family's published description (each such reading is marked ASSUMED below and
listed under ``assumed`` in ``configs/ouro-2.6b-L8.json``). With ``L`` layers,
``T = loop_steps`` passes and ``N`` an RMSNorm at ``rms_norm_eps``::

    x = E[ids]
    for u in 0..T-1:                       # ONE set of weights, T passes
        for l in 0..L-1:
            x = x + N2_l(Attn_l(N1_l(x)))  # MHA/GQA, RoPE at the token's position
            x = x + N4_l(W_down_l(silu(W_gate_l h) * (W_up_l h))),  h = N3_l(x)
        x   = N_f(x)                       # after EVERY pass; feeds the next pass
        h_u = x
        g_u = sigmoid(w_g . h_u + b_g)     # one Linear(hidden, 1) for all passes
    p_u = g_u prod_{j<u}(1 - g_j)  (u < T-1),   p_{T-1} = prod_{j<T-1}(1 - g_j)
    logits = W_head h_{T-1}                # early_exit_threshold 1: the last pass

No cache (so nothing is shared or confused between passes: every pass attends
the keys and values it computes itself from its own input), no kernels, no
batching tricks: one full forward over the whole sequence, pass after pass. A
LoRA adapter adds ``(x A) B * scale`` to each of a WEIGHT layer's seven
projections, the same adapter in every pass, so its gradient is the sum over
the passes by plain reverse mode.

Departures, each for memory on a 16 GB chip that also holds the system under
test, as in ``reference.py``: the weights stay in the type they are served in
and are widened to float32 one layer at a time inside a scan (bf16 widens
exactly); rows run one after another; the vocabulary is projected in chunks
with a running log-sum-exp; reverse mode recomputes each row, pass, layer and
chunk from its input (``jax.checkpoint``). None changes a value. Every matmul
runs under ``default_matmul_precision("highest")``.

``model`` is the program's ``ModelConfig`` only as a bag of sizes
(``hidden_size``, ``num_heads``, ``num_kv_heads``, ``head_dim``,
``rope_theta``, ``rms_norm_eps``, ``loop_steps``); no code of the program runs
here and nothing of it is imported.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
VOCAB_CHUNKS = 8


def _check_family(model) -> None:
    if (getattr(model, "hidden_act", "silu") != "silu"
            or getattr(model, "rmsnorm_offset", False)
            or getattr(model, "scale_embeddings", False)
            or getattr(model, "mixer_types", None) is not None
            or int(getattr(model, "loop_steps", 1)) < 1):
        raise NotImplementedError(
            "perfbench/reference_looped.py describes a looped dense decoder "
            "(SiLU, plain RMSNorm, unscaled embeddings, every layer full "
            "attention, loop_steps passes); another family brings its own "
            "reference module, named by the configuration file")


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


def _project(x, layer, lora_layer, name, scale):
    # ASSUMED: no bias on any projection (the published config names none)
    y = x @ layer[name].astype(_F32)
    if lora_layer is not None and name in lora_layer:
        a = lora_layer[name]["a"].astype(_F32)
        b = lora_layer[name]["b"].astype(_F32)
        y = y + (x @ a) @ b * scale
    return y


def _layer(x, valid, positions, layer, lora_layer, model, scale):
    """One layer application over one row. x [S, hidden] float32; valid [S]."""
    s = x.shape[0]
    heads, kv_heads, hd = model.num_heads, model.num_kv_heads, model.head_dim
    eps = model.rms_norm_eps
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), eps)  # N1
    q = _project(h, layer, lora_layer, "wq", scale).reshape(s, heads, hd)
    k = _project(h, layer, lora_layer, "wk", scale).reshape(s, kv_heads, hd)
    v = _project(h, layer, lora_layer, "wv", scale).reshape(s, kv_heads, hd)
    # ASSUMED: the SAME positions in every pass (a token's own position)
    q = _rope(q, positions, model.rope_theta)
    k = _rope(k, positions, model.rope_theta)
    group = heads // kv_heads  # 1 at the published sizes: no grouping
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where((causal & valid[None, :])[None], scores, -jnp.inf)
    # a padding query attends nothing; keep its row finite (it is never read)
    scores = jnp.where(valid[None, :, None], scores, 0.0)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    # ASSUMED: the sublayer's OUTPUT is normed (N2) before it joins the stream
    x = x + _rms_norm(_project(att, layer, lora_layer, "wo", scale),
                      layer["attn_out_norm"].astype(_F32), eps)
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), eps)  # N3
    gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", scale))
    up = _project(h, layer, lora_layer, "w_up", scale)
    # ASSUMED: and so is the MLP's (N4)
    return x + _rms_norm(_project(gate * up, layer, lora_layer, "w_down", scale),
                         layer["mlp_out_norm"].astype(_F32), eps)


def _passes_row(params, lora, model, ids, valid, scale):
    """``(h [T, S, hidden], g [T, S])`` of one row: each pass's output after
    the final norm, and its exit gate. Padding (valid False) may sit anywhere;
    positions count the valid tokens only."""
    positions = jnp.maximum(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0)
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    lora_layers = lora["layers"] if lora is not None else None
    w_g = params["exit_gate"]["w"].astype(_F32)[:, 0]
    b_g = params["exit_gate"]["b"].astype(_F32)[0]

    def body(x, per_layer):
        layer, lora_layer = per_layer
        return _layer(x, valid, positions, layer, lora_layer, model, scale), None

    hidden, gates = [], []
    for _ in range(int(model.loop_steps)):  # the same weights, pass after pass
        x, _ = jax.lax.scan(jax.checkpoint(body), x, (params["layers"], lora_layers))
        # ASSUMED: N_f closes EVERY pass, and its output is the next pass's input
        x = _rms_norm(x, params["final_norm"].astype(_F32), model.rms_norm_eps)
        hidden.append(x)
        # ASSUMED: one Linear(hidden, 1) and a sigmoid, shared by the passes
        gates.append(jax.nn.sigmoid(x @ w_g + b_g))
    return jnp.stack(hidden), jnp.stack(gates)


def _exit_probabilities(gates):
    """``p_u`` of gates ``[T, ...]``: the first ``T - 1`` passes stop with
    their gate's share of what is left, the last takes the rest."""
    out, left = [], jnp.ones_like(gates[0])
    for u in range(gates.shape[0] - 1):
        out.append(gates[u] * left)
        left = left * (1.0 - gates[u])
    return jnp.stack(out + [left])


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
        hidden, _ = _passes_row(params, lora, model, ids_r, mask_r > 0, lora_scale)
        # ASSUMED: at early_exit_threshold 1 the cumulative exit mass reaches 1
        # at the LAST pass only, so the logits are the last pass's, whatever
        # the gates read; its output is normed once (by the pass's own N_f)
        return _token_logprobs_row(params, model, hidden[-1, :-1], ids_r[1:])

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(jax.checkpoint(row), (ids, mask))


def exit_distribution(params, model, ids, mask, *, lora=None, lora_scale=1.0):
    """[T, B, S] float32: the exit distribution ``p_u`` of every token (the
    module docstring's), teacher-forced over ``ids`` [B, S]. Padding columns
    mean nothing."""
    _check_family(model)

    def row(args):
        ids_r, mask_r = args
        _, gates = _passes_row(params, lora, model, ids_r, mask_r > 0, lora_scale)
        return _exit_probabilities(gates)

    with jax.default_matmul_precision("highest"):
        return jnp.moveaxis(jax.lax.map(row, (ids, mask)), 0, 1)


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
    """(loss, d loss / d adapter) of ``pg_loss``, by plain reverse mode: an
    adapter serves every pass, so its gradient is the sum over the passes."""
    return jax.value_and_grad(
        lambda lo: pg_loss(params, model, lo, lora_scale, ids, mask,
                           answer_mask, coeffs)
    )(lora)
