"""Plain reference of K-EXAONE-236B-A23B
(https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B, ``model_type``
``exaone_moe``), in float32: sliding-window attention in the layers
``layer_types`` calls ``sliding_attention``, full attention in the others, a
dense gated MLP where ``mlp_layer_types`` says ``dense`` (layer 0) and a router
over ``num_experts`` experts beside one shared expert where it says ``sparse``.

Written from the published ``config.json`` and the catalog's description; what
the config leaves open is under ``assumed`` in the configuration file, and each
such choice is marked at its line below. ``h`` is a layer's input::

    mixer:    a = RMSNorm(h);  q = RMSNorm_head(W_q a) [T, H, D]
              k = RMSNorm_head(W_k a), v = W_v a [T, K, D]            no bias
              sliding_attention: q, k <- RoPE(theta, rotate-half over all D);
                                 token t attends tokens max(0, t - W + 1) .. t
              full_attention:    no positional encoding; token t attends 0 .. t
              h <- h + W_o softmax(q k^T / sqrt(D)) v
    dense:    m = RMSNorm(h);  h <- h + W_down(silu(W_gate m) * (W_up m))
    sparse:   s = sigmoid(m W_r) [T, E];  chosen = the k largest of s + b, lowest index first
              w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
              h <- h + sum_{k held here} w_k E_k(m) + S(m)    E, S: W_down(silu(W_gate m) * (W_up m))
    head:     RMSNorm, then an untied head

Here attention is over the WHOLE row, full causal scores with the window
written as a mask (no ring, no cache, no segment), and the experts in the
plainest form there is: every expert held runs on every token and a combine
matrix, zero outside the chosen k, weights the results.

**The share.** The configuration states one chip's share of a layer that 8
chips divide: this reference is given the SAME share. The router has its
published width (128) and chooses among all its experts; the experts whose
weights are here (``n_routed_experts`` of them, the ids ``expert_shard * n ..``)
add their part, a pair routed to an expert held elsewhere adds nothing; the
shared expert and the mixers are whole; the vocabulary is the slice the file
states, a smaller vocabulary.

Departures from the published model, each stated in the configuration file:
the multi-token-prediction module is not instantiated (no logit of the main
head depends on it); no auxiliary loss in ``pg_loss`` (the router is frozen
under LoRA); the router, its bias and the routed experts carry no adapter.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a
time; rows run one after another; a layer's queries run in blocks of
``Q_BLOCK`` against its KV heads (the query heads of a group contracted with
their one K and V: no repeated copy) and a gated MLP's tokens in blocks of
``MLP_BLOCK``; the vocabulary is projected in pieces with a running log-sum-exp.
Every matmul runs under ``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first (a token's position is its rank among the valid ones) and the results
moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program's model or kernels runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import _project, _rms_norm, _rope, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 128
MLP_BLOCK = 2048
#: published names of the two mixers -> the program's stack names; a layer
#: whose second half is the dense MLP is stacked apart, under ``<name>_dense``
KINDS = {"sliding_attention": "window", "full_attention": "softmax"}


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if (not kinds or kinds - set(KINDS) or getattr(model, "mlp_types", None) is None
            or getattr(model, "hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            "perfbench/reference_window_moe.py describes an exaone_moe model "
            "(sliding-window and full attention layers with a per-head norm of q "
            "and k, a dense MLP or sigmoid-scored experts a layer, SiLU); another "
            "family brings its own reference module, named by the configuration file"
        )


def held_ids(model) -> list[int]:
    """Ids of the routed experts whose weights are here, in stack order."""
    n = model.n_routed_experts
    first = model.expert_shard * n if model.router_experts else 0
    return list(range(first, first + n))


def stack_names(model) -> list[str]:
    """The program's stack of each layer that is run, in published order."""
    return [
        KINDS[mixer] + ("_dense" if ffn == "dense" else "")
        for mixer, ffn in zip(model.mixer_types[: model.num_layers], model.mlp_types)
    ]


def _attention(h, valid, layer, lora_layer, model, scale, window: int):
    """Full causal scores; ``window`` > 0 writes the band as a mask and rotates
    q and k, 0 is a full-attention layer (no positional encoding: assumed, as
    EXAONE-4.0's modeling file does for the same LLLG pattern)."""
    s, heads, kv, hd = h.shape[0], model.num_heads, model.num_kv_heads, model.head_dim
    q = _project(h, layer, lora_layer, "wq", "bq", scale).reshape(s, heads, hd)
    k = _project(h, layer, lora_layer, "wk", "bk", scale).reshape(s, kv, hd)
    v = _project(h, layer, lora_layer, "wv", "bv", scale).reshape(s, kv, hd)
    # assumed: a per-head RMSNorm of q and k in BOTH mixers, one weight of head_dim each
    q = _rms_norm(q, layer["q_norm"].astype(_F32), model.rms_norm_eps)
    k = _rms_norm(k, layer["k_norm"].astype(_F32), model.rms_norm_eps)
    positions = jnp.arange(s)
    if window:  # assumed: RoPE in the window layers alone, after the norm
        q = _rope(q, positions, model.rope_theta)
        k = _rope(k, positions, model.rope_theta)
    q = q.reshape(s, kv, heads // kv, hd)  # a KV head's group of query heads

    def block(args):
        q_b, pos_b = args
        scores = jnp.einsum("qkgd,skd->kgqs", q_b, k) / jnp.sqrt(_F32(hd))
        allowed = (pos_b[:, None] >= positions[None, :]) & valid[None, :]
        if window:  # assumed: the window counts the token itself (W keys, not W + 1)
            allowed = allowed & (pos_b[:, None] - positions[None, :] < window)
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        scores = jnp.where(allowed.any(-1)[None, None, :, None], scores, 0.0)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    pad = -s % Q_BLOCK
    if s <= Q_BLOCK:
        o = block((q, positions))
    else:
        o = jax.lax.map(jax.checkpoint(block), (
            jnp.pad(q, ((0, pad),) + ((0, 0),) * 3).reshape(
                -1, Q_BLOCK, kv, heads // kv, hd),
            jnp.pad(positions, (0, pad), constant_values=-1).reshape(-1, Q_BLOCK),
        )).reshape(-1, heads, hd)[:s]
    return _project(o.reshape(s, heads * hd), layer, lora_layer, "wo", "bo", scale)


def combine_matrix(h, layer, model):
    """[T, E] float32 over ALL the experts the router scores: ``w`` at a
    token's chosen experts, 0 elsewhere. Assumed: the correction bias is in
    the choice and not in the weights (DeepSeek-V3's convention for these keys)."""
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


def routed_part(h, layer, model):
    """The held experts' part of ``sum_e combine[:, e] E_e(h)``."""
    comb = combine_matrix(h, layer, model)[:, jnp.asarray(held_ids(model))]

    def one(y, per_expert):
        gate, up, down, w = per_expert
        return y + w[:, None] * _gated(h, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"], comb.T))
    return y


def _gated_mlp(h, layer, lora_layer, scale):
    """The dense MLP, or the shared expert (assumed: ONE gated MLP, unweighted)."""
    def block(h_b):
        gate = jax.nn.silu(_project(h_b, layer, lora_layer, "w_gate", "b_gate", scale))
        up = _project(h_b, layer, lora_layer, "w_up", "b_up", scale)
        return _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)

    s, pad = h.shape[0], -h.shape[0] % MLP_BLOCK
    if s <= MLP_BLOCK:
        return block(h)
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, MLP_BLOCK, h.shape[1])
    return jax.lax.map(jax.checkpoint(block), blocks).reshape(-1, h.shape[1])[:s]


def _residual(x, weight, sublayer, model):
    """Where the norm sits, in ONE place. Assumed pre-norm: ``x + f(RMSNorm(x))``.
    EXAONE-4.0 norms each sublayer's OUTPUT instead (``x + RMSNorm(f(x))``) and
    ``config.json`` does not say which ``exaone_moe`` does: this is the line to
    change, with ``models/hybrid.py``'s, if the published code says otherwise."""
    return x + sublayer(_rms_norm(x, weight.astype(_F32), model.rms_norm_eps))


def _layer(x, valid, layer, lora_layer, model, scale, window: int):
    x = _residual(
        x, layer["attn_norm"],
        lambda h: _attention(h, valid, layer, lora_layer, model, scale, window), model)

    def second_half(h):
        if "router" not in layer:  # mlp_layer_types "dense"
            return _gated_mlp(h, layer, lora_layer, scale)
        y = routed_part(h, layer, model)
        if "w_gate" in layer:
            y = y + _gated_mlp(h, layer, lora_layer, scale)
        return y

    return _residual(x, layer["mlp_norm"], second_half, model)


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    seen: dict[str, int] = {}
    for name, mixer in zip(stack_names(model), model.mixer_types):
        at = seen.get(name, 0)
        seen[name] = at + 1
        lora_stack = lora["layers"].get(name) if lora is not None else None
        window = model.sliding_window if mixer == "sliding_attention" else 0

        def one(x, stack, lora_stack, at=at, window=window):
            take = lambda tree: jax.tree_util.tree_map(lambda w: w[at], tree)
            return _layer(x, valid, take(stack),
                          None if lora_stack is None else take(lora_stack),
                          model, scale, window)

        x = jax.checkpoint(one)(x, params["layers"][name], lora_stack)
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
