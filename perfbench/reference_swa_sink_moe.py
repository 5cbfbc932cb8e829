"""Plain reference of MiMo-V2-Flash
(https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash, ``model_type``
``mimo_v2_flash``), in float32: sliding-window attention with a learned sink in
the layers ``hybrid_layer_pattern`` marks 1, full attention in those it marks
0, a dense gated MLP where ``moe_layer_freq`` says 0 (layer 0) and a router over
``n_routed_experts`` experts, no shared expert, where it says 1.

Written from the catalog row's ``config`` and its description; what the config
leaves open is under ``assumed`` in the configuration file, and each such
reading is marked (A) at its line below. ``h`` is a layer's input, ``K_l`` the
KV heads of the layer's kind (``num_kv_heads`` full, ``window_kv_heads``
window), ``D`` = ``head_dim`` (q and k), ``Dv`` = ``v_head_dim``, ``R`` =
``rotary_dim``::

    mixer:    a = RMSNorm(h);  q = W_q a [T, H, D];  k = W_k a [T, K_l, D]     no bias, no q/k norm (A)
              v = value_scale * (W_v a) [T, K_l, Dv]                          (A: before the weighted sum)
              q, k <- RoPE on the first R values of a head, rotate-half pairs inside them (A),
                      base rope_theta (full) / window_rope_theta (window), absolute positions
              s[t, j] = q_t . k_j / sqrt(D)                                   (A: the q/k width)
              full:    j <= t;                 p = softmax_j(s)
              window:  max(0, t - W + 1) <= j <= t  (A: W keys, itself included)
                       p[t, j] = exp(s[t, j]) / (exp(sink_h) + sum_j' exp(s[t, j']))   (A: gpt-oss's form)
              h <- h + W_o [sum_j p[t, j] v_j of H heads x Dv]
    dense:    m = RMSNorm(h);  h <- h + W_down(silu(W_gate m) * (W_up m))
    experts:  s = sigmoid(m W_r) [T, E];  chosen = the k largest of s + b, lowest index first
              w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor (null: 1.0, A)
              h <- h + sum_{k held here} w_k E_k(m)        E: W_down(silu(W_gate m) * (W_up m))
    head:     RMSNorm, then an untied head

Every layer is two pre-norm sublayers (A: ``x + f(RMSNorm(x))``). Attention is
over the WHOLE row with the causal mask, the band and the sink written out (no
ring, no cache, no segment, no page), and the experts in the plainest form
there is: every expert held runs on every token and a combine matrix, zero
outside the chosen k, weights the results.

**The share.** The configuration states one chip's share of a layer that 16
chips divide: this reference is given the SAME share. The router has its
published width (256) and chooses among all its experts; the experts whose
weights are here (``n_routed_experts`` of them, the ids ``expert_shard * n ..``)
add their part, a pair routed to an expert held elsewhere adds nothing; the
mixers are whole; the vocabulary is the slice the file states, a smaller
vocabulary.

Departures from the published model, each stated in the configuration file:
the three multi-token-prediction layers are not instantiated (no logit of the
main head depends on them); no auxiliary loss in ``pg_loss`` (the router is
frozen under LoRA); the router, its bias, the routed experts and the sinks
carry no adapter.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a
time; rows run one after another; a layer's queries run in blocks of
``Q_BLOCK`` against its KV heads (the query heads of a group contracted with
their one K and V: no repeated copy), a window layer's block against the
``Q_BLOCK + W - 1`` keys that its band can reach and no others, and a gated
MLP's tokens in blocks of ``MLP_BLOCK``; the vocabulary is projected in pieces
with a running log-sum-exp. Every matmul runs under
``default_matmul_precision("highest")``.

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
#: the names ``ModelConfig`` gives the two kinds (from ``hybrid_layer_pattern``
#: 1 and 0) -> the program's stack names; a layer whose second half is the
#: dense MLP is stacked apart, under ``<name>_dense``
KINDS = {"sliding_attention": "window", "full_attention": "softmax"}


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if (not kinds or kinds - set(KINDS) or getattr(model, "mlp_types", None) is None
            or not getattr(model, "window_sink", False)
            or getattr(model, "n_shared_experts", 0)
            or getattr(model, "hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            "perfbench/reference_swa_sink_moe.py describes a mimo_v2_flash model "
            "(window layers with a learned sink beside full layers, KV heads a kind, "
            "k and v of two widths, a rotated share of a head, a dense MLP or "
            "sigmoid-scored experts without a shared expert a layer, SiLU); another "
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


def _partial_rope(x, positions, theta, rot: int):
    """RoPE on the first ``rot`` values of each head, rotate-half pairs INSIDE
    them (A: HF's default for a partial_rotary_factor), none on the rest."""
    return jnp.concatenate([_rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)


def _attention(h, valid, layer, lora_layer, model, scale, window: int):
    """Causal scores over the row; ``window`` > 0 is a window layer: its band,
    its KV heads, its RoPE base and its sink; 0 a full layer."""
    s, heads, hd = h.shape[0], model.num_heads, model.head_dim
    hv = model.v_head_dim or hd
    kv = (model.window_kv_heads if window else 0) or model.num_kv_heads
    theta = (model.window_rope_theta if window else 0.0) or model.rope_theta
    rot = model.rotary_dim or hd
    q = _project(h, layer, lora_layer, "wq", "bq", scale).reshape(s, heads, hd)
    k = _project(h, layer, lora_layer, "wk", "bk", scale).reshape(s, kv, hd)
    # (A) the scale multiplies V before the weighted sum
    v = model.value_scale * _project(h, layer, lora_layer, "wv", "bv", scale).reshape(s, kv, hv)
    positions = jnp.arange(s)
    q = _partial_rope(q, positions, theta, rot)
    k = _partial_rope(k, positions, theta, rot)
    q = q.reshape(s, kv, heads // kv, hd)  # a KV head's group of query heads
    # (A) one learned logit a query head, window layers alone: a column of the
    # softmax whose value is nothing
    sink = layer["sink"].astype(_F32).reshape(kv, heads // kv) if window else None
    # queries in blocks; a window layer's block reaches W - 1 keys back and no
    # further, a full layer's the whole row
    size = min(Q_BLOCK, s)
    pad = -s % size
    lead = window - 1 if window else 0
    reach = size + lead if window else s + pad
    k_all = jnp.pad(k, ((lead, pad), (0, 0), (0, 0)))
    v_all = jnp.pad(v, ((lead, pad), (0, 0), (0, 0)))
    valid_all = jnp.pad(valid, (lead, pad))

    def block(args):
        q_b, pos_b, first = args  # first: the block's first query position
        at = first if window else 0
        k_b = jax.lax.dynamic_slice_in_dim(k_all, at, reach, axis=0)
        v_b = jax.lax.dynamic_slice_in_dim(v_all, at, reach, axis=0)
        key_pos = at - lead + jnp.arange(reach)
        scores = jnp.einsum("qkgd,skd->kgqs", q_b, k_b) / jnp.sqrt(_F32(hd))  # (A) sqrt(D)
        allowed = ((pos_b[:, None] >= key_pos[None, :]) & (key_pos[None, :] >= 0)
                   & jax.lax.dynamic_slice_in_dim(valid_all, at, reach)[None, :])
        if window:  # (A) the window counts the token itself (W keys, not W + 1)
            allowed = allowed & (pos_b[:, None] - key_pos[None, :] < window)
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        if window:  # the sink: in the maximum and the denominator, of no value
            top = jnp.maximum(scores.max(-1), sink[:, :, None])[..., None]
            e = jnp.exp(scores - top)
            p = e / (e.sum(-1, keepdims=True) + jnp.exp(sink[:, :, None, None] - top))
        else:
            scores = jnp.where(allowed.any(-1)[None, None, :, None], scores, 0.0)
            p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v_b)

    o = jax.lax.map(jax.checkpoint(block), (
        jnp.pad(q, ((0, pad),) + ((0, 0),) * 3).reshape(-1, size, kv, heads // kv, hd),
        jnp.pad(positions, (0, pad), constant_values=-1).reshape(-1, size),
        jnp.arange((s + pad) // size) * size,
    )).reshape(-1, heads, hv)[:s]
    return _project(o.reshape(s, heads * hv), layer, lora_layer, "wo", "bo", scale)


def combine_matrix(h, layer, model):
    """[T, E] float32 over ALL the experts the router scores: ``w`` at a
    token's chosen experts, 0 elsewhere. ``topk_method`` ``noaux_tc``: the
    correction bias is in the choice and not in the weights."""
    scores = jax.nn.sigmoid(h @ layer["router"].astype(_F32))
    biased = scores + layer["e_score_bias"].astype(_F32)
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(model.experts_per_token):  # the largest left, lowest index first
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, biased), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    w = jnp.where(chosen, scores, 0.0)
    if model.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * model.routed_scaling_factor  # (A) null is 1.0


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(_F32)) * (h @ up.astype(_F32))) @ down.astype(_F32)


def routed_part(h, layer, model, held=None):
    """The held experts' part of ``sum_e combine[:, e] E_e(h)``; ``held`` names
    other ids than the model's own run (the tests' uncut layer)."""
    ids = held_ids(model) if held is None else list(held)
    comb = combine_matrix(h, layer, model)[:, jnp.asarray(ids)]

    def one(y, per_expert):
        gate, up, down, w = per_expert
        return y + w[:, None] * _gated(h, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"], comb.T))
    return y


def _gated_mlp(h, layer, lora_layer, scale):
    """The dense MLP of layer 0."""
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
    """Where the norm sits, in ONE place. (A) pre-norm: ``x + f(RMSNorm(x))``,
    the family's convention; the config has no key for it."""
    return x + sublayer(_rms_norm(x, weight.astype(_F32), model.rms_norm_eps))


def _layer(x, valid, layer, lora_layer, model, scale, window: int):
    x = _residual(
        x, layer["attn_norm"],
        lambda h: _attention(h, valid, layer, lora_layer, model, scale, window), model)

    def second_half(h):
        if "router" not in layer:  # moe_layer_freq 0
            return _gated_mlp(h, layer, lora_layer, scale)
        return routed_part(h, layer, model)  # no shared expert

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
