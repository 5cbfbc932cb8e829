"""Plain reference of Kimi-VL-A3B's language model
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct, ``text_config``,
``model_type`` ``deepseek_v3``), in float32: latent attention (MLA) in every
layer, a dense gated MLP in the first ``first_k_dense_replace`` layers, and a
router over ``n_routed_experts`` experts beside one shared expert in the rest.

Written from the published ``config.json`` and DeepSeek-V3's description of
the two mechanisms. ``h = RMSNorm(x)`` before each half, the residual after::

    q = W_q h                              [T, H, nope + rope]    (q_lora_rank null)
    [c_raw, k_pe] = W_kva h                [T, rank], [T, rope]
    c = RMSNorm(c_raw)                     kv_a_layernorm
    q_pe, k_pe <- RoPE                     interleaved pairs (x[2i], x[2i+1]); k_pe one for all heads
    [k_nope, v] = c W_kvb                  [T, H, nope], [T, H, v]
    o = softmax(q . [k_nope, k_pe] / sqrt(nope + rope), causal) v;   y = W_o o

    s = sigmoid(h W_g)                     [T, E]
    chosen = the k largest of s + b        b: e_score_correction_bias; the lower index among equals
    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    y = sum_k w_k E_k(h) + S(h)            E, S: W_down(silu(W_gate h) * (W_up h))

``S`` is ONE gated MLP of width ``n_shared_experts x moe_intermediate_size``.
Here the attention is the EXPANDED form over the whole row (K and V rebuilt
per head; no cache, no absorption), and the experts are the plainest form
there is: every expert runs on every token and a combine matrix ``[T, E]``,
zero outside the chosen k, weights the results. No sort, no grouping, no
kernel.

Departures from the published model, each stated in the configuration file:
no auxiliary load-balancing loss in ``pg_loss`` (``seq_aux`` is pretraining's;
the router is frozen under LoRA); no vision tower and no projector (the
configuration is the language model); the router and the routed experts carry
no adapter (the adapter is on q, kv_a, kv_b, o, the dense MLP and the shared
expert).

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a
time; rows run one after another; queries run in blocks of ``Q_BLOCK`` and the
MLPs' tokens in blocks of ``MLP_BLOCK``; the vocabulary is projected in pieces
with a running log-sum-exp; reverse mode recomputes each row, layer and block
(``jax.checkpoint``). Every matmul runs under
``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first (positions count real tokens only) and the results moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what does not differ from the dense decoder's reference: RMSNorm, a
# projection with its adapter, the head's log-probabilities in pieces
from perfbench.reference import _project, _rms_norm, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 512
MLP_BLOCK = 2048


def _check_family(model) -> None:
    if not getattr(model, "kv_lora_rank", 0) or getattr(
        model, "hidden_act", "silu"
    ) != "silu" or getattr(model, "mixer_types", None):
        raise NotImplementedError(
            "perfbench/reference_latent_moe.py describes a deepseek_v3 language "
            "model (latent attention, sigmoid-scored experts, SiLU); another "
            "family brings its own reference module, named by the configuration "
            "file"
        )


def _rope_pairs(x, positions, theta):
    """x [S, ..., D]: rotate the pairs (x[2i], x[2i+1]) by position."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    angles = positions.astype(_F32)[:, None] * inv_freq  # [S, D/2]
    angles = angles.reshape(angles.shape[:1] + (1,) * (x.ndim - 2) + angles.shape[1:])
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _attention(h, valid, positions, layer, lora_layer, model, scale):
    s, heads = h.shape[0], model.num_heads
    nope, rope, rank = model.qk_nope_head_dim, model.qk_rope_head_dim, model.kv_lora_rank
    q = _project(h, layer, lora_layer, "wq", "bq", scale).reshape(s, heads, nope + rope)
    kva = _project(h, layer, lora_layer, "wkv_a", "bkv_a", scale)
    c = _rms_norm(kva[:, :rank], layer["kv_a_norm"].astype(_F32), model.rms_norm_eps)
    k_pe = _rope_pairs(kva[:, rank:], positions, model.rope_theta)  # [S, rope]
    kv = _project(c, layer, lora_layer, "wkv_b", "bkv_b", scale).reshape(
        s, heads, nope + model.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], _rope_pairs(q[..., nope:], positions, model.rope_theta)], axis=-1)

    def block(args):
        q_b, pos_b = args  # [Q, H, nope + rope], [Q]
        scores = jnp.einsum("qhd,khd->hqk", q_b, k) / jnp.sqrt(_F32(nope + rope))
        allowed = (pos_b[:, None] >= positions[None, :]) & valid[None, :]
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        # a padding query may see nothing; keep its row finite (never read)
        scores = jnp.where(allowed.any(-1)[None, :, None], scores, 0.0)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    pad = -s % Q_BLOCK
    if s <= Q_BLOCK:
        o = block((q, positions))
    else:
        o = jax.lax.map(jax.checkpoint(block), (
            jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, heads, nope + rope),
            jnp.pad(positions, (0, pad), constant_values=-1).reshape(-1, Q_BLOCK),
        )).reshape(-1, heads, model.v_head_dim)[:s]
    return _project(o.reshape(s, heads * model.v_head_dim), layer, lora_layer,
                    "wo", "bo", scale)


def combine_matrix(h, layer, model):
    """[T, E] float32: ``w`` at a token's chosen experts, 0 elsewhere."""
    scores = jax.nn.sigmoid(h @ layer["router"].astype(_F32))
    biased = scores + layer["e_score_bias"].astype(_F32)
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(model.experts_per_token):  # the largest left, lowest index first
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, biased), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    w = jnp.where(chosen, scores, 0.0)
    if model.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * model.routed_scaling_factor


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(_F32)) * (h @ up.astype(_F32))) @ down.astype(_F32)


def _experts(h, layer, model):
    """sum_e combine[:, e] E_e(h): every expert on every token."""
    comb = combine_matrix(h, layer, model)

    def one(y, per_expert):
        gate, up, down, w = per_expert
        return y + w[:, None] * _gated(h, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"], comb.T))
    return y


def _layer(x, valid, positions, layer, lora_layer, model, scale, moe: bool):
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    x = x + _attention(h, valid, positions, layer, lora_layer, model, scale)
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)

    def ffn(h):
        # the dense MLP, or the shared expert: one gated MLP with its adapter
        y = 0.0
        if "w_gate" in layer:
            gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
            up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
            y = _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)
        return y + _experts(h, layer, model) if moe else y

    s, pad = h.shape[0], -h.shape[0] % MLP_BLOCK
    if s <= MLP_BLOCK:
        return x + ffn(h)
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, MLP_BLOCK, h.shape[1])
    return x + jax.lax.map(jax.checkpoint(ffn), blocks).reshape(-1, h.shape[1])[:s]


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    positions = jnp.arange(ids.shape[0])
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    dense = model.first_dense_layers if model.n_routed_experts else model.num_layers
    for index in range(model.num_layers):
        kind, at = ("latent", index) if index < dense else ("latent_moe", index - dense)
        lora_stack = lora["layers"].get(kind) if lora is not None else None

        def one(x, stack, lora_stack, kind=kind, at=at):
            # sliced INSIDE what reverse mode recomputes: what it keeps for a
            # layer is the stack that is there anyway, not a copy of the layer
            take = lambda tree: jax.tree_util.tree_map(lambda w: w[at], tree)
            return _layer(x, valid, positions, take(stack),
                          None if lora_stack is None else take(lora_stack),
                          model, scale, kind == "latent_moe")

        x = jax.checkpoint(one)(x, params["layers"][kind], lora_stack)
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


def pg_loss(params, model, lora, lora_scale, ids, mask, answer_mask, coeffs):
    """Vanilla policy gradient over whole rows, as ``reference.pg_loss``; no
    auxiliary loss (module docstring)."""
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
