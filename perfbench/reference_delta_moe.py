"""Plain reference of Solar-Open2-250B
(https://huggingface.co/upstage/Solar-Open2-250B, ``model_type``
``solar_open2``), in float32: gated softmax attention without RoPE in the
layers ``gqa_layers`` names, a gated delta rule with a per-channel decay
behind short convolutions in every other layer, and in every layer a router
over ``n_routed_experts`` experts beside one shared expert.

Written from the published ``config.json`` and the catalog's description; what
the config leaves open is under ``assumed`` in the configuration file.
``h = RMSNorm(x)`` before each half, the residual after; no RoPE anywhere::

    softmax layer:  q = W_q h [T, H, D];  k, v = W h [T, K, D]
                    o = softmax(q k^T / sqrt(D), causal) v;   y = W_o (o * sigmoid(W_g h))

    delta layer:    [q', k', v] = silu(conv4([W_q h, W_k h, W_v h]))
                      conv4: y_t = sum_{i=0..3} w[i] x_{t-3+i} a channel, zeros before the row
                    q = q' / sqrt(sum q'^2 + 1e-6) / sqrt(D);  k likewise, unscaled   (a head)
                    g_t = -exp(A_log) * softplus(W_fb (W_fa h_t) + dt_bias)   [H, D];  a_t = exp(g_t)
                    beta_t = beta_scale * sigmoid(W_b h_t)                    [H]
                    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
                    o_t = S_t^T q_t
                    y = W_o (RMSNorm_D(o) * sigmoid(W_gb (W_ga h)))

    experts:        s = sigmoid(h W_r)  [T, E];  chosen = the k largest of s + b, lowest index first
                    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
                    y = sum_{k held here} w_k E_k(h) + S(h)     E, S: W_down(silu(W_gate h) * (W_up h))

Here the recurrence runs TOKEN BY TOKEN (no chunks, no triangular solve, no
cache), the attention over the whole row, and the experts in the plainest form
there is: every expert held runs on every token and a combine matrix, zero
outside the chosen k, weights the results.

**The share.** The configuration states one chip's share of a layer that 8
chips divide: this reference is given the SAME share. The router has its
published width and chooses among all its experts; the experts whose weights
are here (``n_routed_experts`` of them, the ids ``expert_shard * n ..``) add
their part, a pair routed to an expert held elsewhere adds nothing; the shared
expert is whole; the vocabulary is the slice the file states, a smaller
vocabulary.

Departures from the published model, each stated in the configuration file:
no auxiliary loss in ``pg_loss`` (the router is frozen under LoRA); the router,
the routed experts, the low-rank pairs, ``W_b`` and the convolutions carry no
adapter (the adapter is on q, k, v, o of both mixers and the shared expert).

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a
time; rows run one after another; a softmax layer's queries run in blocks of
``Q_BLOCK``; the recurrence is a scan of scans (``STEP_BLOCK`` tokens inside
what reverse mode recomputes, so that it keeps a state a block and not a
token); the vocabulary is projected in pieces with a running log-sum-exp.
Every matmul runs under ``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first and the results moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import _project, _rms_norm, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 512
STEP_BLOCK = 64
#: published names of the two layer kinds -> the program's stack names
KINDS = {"gqa": "softmax", "kda": "delta"}


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if not kinds or kinds - set(KINDS) or getattr(model, "hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "perfbench/reference_delta_moe.py describes a solar_open2 model (gated "
            "softmax layers without RoPE, gated delta-rule layers, sigmoid-scored "
            "experts, SiLU); another family brings its own reference module, named "
            "by the configuration file"
        )


def held_ids(model) -> list[int]:
    """Ids of the routed experts whose weights are here, in stack order."""
    n = model.n_routed_experts
    first = model.expert_shard * n if model.router_experts else 0
    return list(range(first, first + n))


def _softmax_layer(h, valid, layer, lora_layer, model, scale):
    s, heads, kv, hd = h.shape[0], model.num_heads, model.num_kv_heads, model.head_dim
    q = _project(h, layer, lora_layer, "wq", "bq", scale).reshape(s, heads, hd)
    k = _project(h, layer, lora_layer, "wk", "bk", scale).reshape(s, kv, hd)
    v = _project(h, layer, lora_layer, "wv", "bv", scale).reshape(s, kv, hd)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
    positions = jnp.arange(s)

    def block(args):
        q_b, pos_b = args
        scores = jnp.einsum("qhd,khd->hqk", q_b, k) / jnp.sqrt(_F32(hd))
        allowed = (pos_b[:, None] >= positions[None, :]) & valid[None, :]
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        scores = jnp.where(allowed.any(-1)[None, :, None], scores, 0.0)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    pad = -s % Q_BLOCK
    if s <= Q_BLOCK:
        o = block((q, positions))
    else:
        o = jax.lax.map(jax.checkpoint(block), (
            jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, heads, hd),
            jnp.pad(positions, (0, pad), constant_values=-1).reshape(-1, Q_BLOCK),
        )).reshape(-1, heads, hd)[:s]
    o = o.reshape(s, heads * hd)
    if "wg" in layer:
        o = o * jax.nn.sigmoid(h @ layer["wg"].astype(_F32))
    return _project(o, layer, lora_layer, "wo", "bo", scale)


def _conv(x, w):
    """x [S, C], w [K, C]: y_t = sum_i w[i] x_{t-K+1+i}, zeros before the row."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[i: i + x.shape[0]] * w[i] for i in range(taps))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta_layer(h, valid, layer, lora_layer, model, scale):
    s, heads, hd = h.shape[0], model.delta_heads, model.delta_head_dim
    ok = valid.astype(_F32)[:, None]
    w = layer["conv"].astype(_F32)
    wide = heads * hd
    mixed = [
        jax.nn.silu(_conv(_project(h, layer, lora_layer, name, None, scale) * ok,
                          w[:, i * wide: (i + 1) * wide])).reshape(s, heads, hd)
        for i, name in enumerate(("wq", "wk", "wv"))
    ]
    q, k, v = _unit(mixed[0]) / jnp.sqrt(_F32(hd)), _unit(mixed[1]), mixed[2]
    rate = (h @ layer["wf_a"].astype(_F32)) @ layer["wf_b"].astype(_F32) + (
        layer["dt_bias"].astype(_F32))
    a = jnp.exp(-jnp.exp(layer["A_log"].astype(_F32))[None, :, None]
                * jax.nn.softplus(rate).reshape(s, heads, hd))
    beta = model.delta_beta_scale * jax.nn.sigmoid(h @ layer["wb"].astype(_F32))

    def step(state, x):
        q_t, k_t, v_t, a_t, b_t, ok_t = x
        kept = a_t[:, :, None] * state  # diag(a) S, [H, Dk, Dv]
        wrote = b_t[:, None] * (v_t - jnp.einsum("hkd,hk->hd", kept, k_t))
        new = kept + k_t[:, :, None] * wrote[:, None, :]
        new = jnp.where(ok_t, new, state)  # a padded token is no step
        return new, jnp.einsum("hkd,hk->hd", new, q_t)

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    pad = -s % STEP_BLOCK
    xs = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (-1, STEP_BLOCK) + x.shape[1:])
        for x in (q, k, v, a, beta, valid))
    _, o = jax.lax.scan(jax.checkpoint(block), jnp.zeros((heads, hd, hd), _F32), xs)
    o = o.reshape(-1, heads, hd)[:s]
    gate = jax.nn.sigmoid((h @ layer["wg_a"].astype(_F32)) @ layer["wg_b"].astype(_F32))
    o = _rms_norm(o, layer["head_norm"].astype(_F32), model.rms_norm_eps)
    return _project(o.reshape(s, wide) * gate, layer, lora_layer, "wo", "bo", scale)


def combine_matrix(h, layer, model):
    """[T, E] float32 over ALL the experts the router scores: ``w`` at a
    token's chosen experts, 0 elsewhere."""
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
    """The held experts' part of ``sum_e combine[:, e] E_e(h)``."""
    comb = combine_matrix(h, layer, model)[:, jnp.asarray(held_ids(model))]

    def one(y, per_expert):
        gate, up, down, w = per_expert
        return y + w[:, None] * _gated(h, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"], comb.T))
    return y


def _layer(x, valid, layer, lora_layer, model, scale, kind: str):
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    mix = _softmax_layer if kind == "softmax" else _delta_layer
    x = x + mix(h, valid, layer, lora_layer, model, scale)
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)
    y = _experts(h, layer, model)
    if "w_gate" in layer:  # the shared expert, with its adapter, added unweighted
        gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
        up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
        y = y + _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)
    return x + y


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    seen: dict[str, int] = {}
    for name in model.mixer_types[: model.num_layers]:
        kind = KINDS[name]
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        lora_stack = lora["layers"].get(kind) if lora is not None else None

        def one(x, stack, lora_stack, kind=kind, at=at):
            take = lambda tree: jax.tree_util.tree_map(lambda w: w[at], tree)
            return _layer(x, valid, take(stack),
                          None if lora_stack is None else take(lora_stack),
                          model, scale, kind)

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
