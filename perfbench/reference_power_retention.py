"""Plain reference of Brumby-14B-Base
(https://huggingface.co/manifestai/Brumby-14B-Base, ``model_type`` ``brumby``),
in float32: Qwen3-14B's block with every attention layer replaced by a
power-retention layer of degree 2, in its ATTENTION form.

Written from the published ``config.json`` (which has Qwen3-14B's shape keys
and no key of the retention layer), the catalog's description ("power
retention layers") and the published description of power retention (Manifest
AI, "Scaling Context Requires Rethinking Attention", arXiv:2507.04239; the
``retention`` package's ``power_retention(Q, K, V, log_G, deg, scale)``). What
those leave open is under ``assumed`` in the configuration file, each with one
line, and is repeated here where this file decides it. ``h = RMSNorm(x)``
before each half, the residual after::

    q_t = RoPE(RMSNorm_head(W_q h_t))  [H, D];   k_t = RoPE(RMSNorm_head(W_k h_t))  [K, D]
    v_t = W_v h_t  [K, D];   g_t = logsigmoid(W_g h_t + b_g)  [K];   G_t = sum_{r<=t} g_r
    query head i of KV head j = i // (H / K),  s <= t:
        a_ts = exp(G_t - G_s) * (q_t . k_s / sqrt(D))^2
        o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)
    x <- x + W_o o;    x <- x + W_down(silu(W_gate h') * (W_up h'))

There is no state here, no chunk, no cache and no feature map: the weights
``a_ts`` are formed for every pair of a query and an earlier key, which is what
the recurrent form's state of the symmetric second power sums up.

**Assumed** (the configuration file says each; a reader with the model's code
corrects the file, not the mechanism): the degree is 2; the gate is one scalar
a KV head with a bias, ``logsigmoid`` of a linear map of the layer's normed
input; the output is divided by the sum of the weights plus ``eps`` 1e-6; the
scale ``1 / sqrt(D)`` is inside the power; Qwen3's per-head RMSNorm on q and k
and full-width RoPE (rotate-half, ``rope_theta``) are kept; a padded token
neither decays nor is attended.

Departures from the published model, each stated in the configuration file:
the adapter is on q, k, v, o, gate, up, down; ``W_g`` and ``b_g`` carry none
and are not trained.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer at a time; rows run one
after another; a layer's queries run in blocks of ``Q_BLOCK`` against all the
row's keys (a 16.6k-token row's weights for 40 heads at once are 44 GB); the
MLP runs in blocks of ``T_BLOCK`` tokens; the vocabulary is projected in pieces
with a running log-sum-exp. Every matmul runs under
``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first and the results moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import _project, _rms_norm, _rope, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 256
T_BLOCK = 2048
EPS = 1e-6


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if kinds != {"power-retention"} or getattr(model, "hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "perfbench/reference_power_retention.py describes a brumby model (power "
            "retention of degree 2 in every layer, SiLU); another family brings its "
            "own reference module, named by the configuration file"
        )


def _blocks(fn, xs, size: int):
    """``fn`` over blocks of ``size`` leading rows of every array in ``xs``,
    joined: what ``fn(xs)`` gives where ``fn`` treats rows alone."""
    n = xs[0].shape[0]
    if n <= size:
        return fn(xs)
    pad = -n % size
    cut = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (-1, size) + x.shape[1:]) for x in xs)
    out = jax.lax.map(jax.checkpoint(fn), cut)
    return out.reshape((-1,) + out.shape[2:])[:n]


def _retention(h, valid, layer, lora_layer, model, scale):
    """The attention form over one row. h [S, hidden]; valid [S] bool, the
    valid tokens first."""
    s, heads, kv, hd = h.shape[0], model.num_heads, model.num_kv_heads, model.head_dim
    positions = jnp.arange(s)
    q = _project(h, layer, lora_layer, "wq", "bq", scale).reshape(s, heads, hd)
    k = _project(h, layer, lora_layer, "wk", "bk", scale).reshape(s, kv, hd)
    v = _project(h, layer, lora_layer, "wv", "bv", scale).reshape(s, kv, hd)
    q = _rms_norm(q, layer["q_norm"].astype(_F32), model.rms_norm_eps)
    k = _rms_norm(k, layer["k_norm"].astype(_F32), model.rms_norm_eps)
    q = _rope(q, positions, model.rope_theta)
    k = _rope(k, positions, model.rope_theta)
    g = jax.nn.log_sigmoid(
        h @ layer["w_decay"].astype(_F32) + layer["b_decay"].astype(_F32))  # [S, K]
    cum = jnp.cumsum(jnp.where(valid[:, None], g, 0.0), axis=0)  # G_t
    qg = q.reshape(s, kv, heads // kv, hd)

    def block(args):
        q_b, pos_b, cum_b = args  # [Qb, K, g, D], [Qb], [Qb, K]
        scores = jnp.einsum("qkgd,jkd->kgqj", q_b, k) / jnp.sqrt(_F32(hd))
        seen = (pos_b[:, None] >= positions[None, :]) & valid[None, :]  # [Qb, S]
        decay = jnp.exp(jnp.where(
            seen[None], cum_b.T[:, :, None] - cum.T[:, None, :], -jnp.inf))  # [K, Qb, S]
        a = jnp.square(scores) * decay[:, None]
        num = jnp.einsum("kgqj,jkd->qkgd", a, v)
        den = a.sum(-1).transpose(2, 0, 1)  # [Qb, K, g]
        return num / (den[..., None] + EPS)

    o = _blocks(block, (qg, positions, cum), Q_BLOCK)
    return _project(o.reshape(s, heads * hd), layer, lora_layer, "wo", "bo", scale)


def _layer(x, valid, layer, lora_layer, model, scale):
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    x = x + _retention(h, valid, layer, lora_layer, model, scale)

    def mlp(args):
        (x_b,) = args
        h = _rms_norm(x_b, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)
        gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
        up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
        return x_b + _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)

    return _blocks(mlp, (x,), T_BLOCK)


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    stack = params["layers"]["power"]
    lora_stack = lora["layers"].get("power") if lora is not None else None

    def body(x, per_layer):
        layer, lora_layer = per_layer
        return _layer(x, valid, layer, lora_layer, model, scale), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, (stack, lora_stack))
    x = _rms_norm(x, params["final_norm"].astype(_F32), model.rms_norm_eps)
    return jnp.zeros_like(x).at[front].set(x)


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


def full_logits(params, model, ids, mask, *, lora=None, lora_scale=1.0):
    """[B, S, V] float32 logits of whole rows: what the CPU tests hold the
    program's forward and its engine to."""
    _check_family(model)
    head = params["embed"].T if model.tie_word_embeddings else params["lm_head"]

    def row(args):
        ids_r, mask_r = args
        hidden = _hidden_row(params, lora, model, ids_r, mask_r > 0, lora_scale)
        return hidden @ head.astype(_F32)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(row, (ids, mask))


def pg_loss(params, model, lora, lora_scale, ids, mask, answer_mask, coeffs):
    """Vanilla policy gradient over whole rows, as ``reference.pg_loss``."""
    logp = next_token_logprobs(params, model, ids, mask, lora=lora, lora_scale=lora_scale)
    scored = answer_mask[:, 1:].astype(_F32)
    per_row = (logp * scored).sum(-1) / jnp.maximum(scored.sum(-1), 1.0)
    return -(per_row * coeffs).mean()


def pg_loss_and_lora_grad(params, model, lora, lora_scale, ids, mask,
                          answer_mask, coeffs):
    """(loss, d loss / d adapter) of ``pg_loss``, by plain reverse mode."""
    return jax.value_and_grad(
        lambda lo: pg_loss(params, model, lo, lora_scale, ids, mask, answer_mask, coeffs)
    )(lora)
