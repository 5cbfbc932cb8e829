"""Plain reference of ZAYA1-8B
(https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json, ``model_type``
``zaya``), in float32: compressed convolutional attention (CCA, arXiv:2510.04476)
in every layer, then one routed expert of 16 a token chosen by an MLP router
that reads the previous layer's router (the ZAYA1 technical report,
arXiv:2511.17127), each sublayer joined to the stream through learned scales
and shifts.

Written from the published ``config.json``, the two papers and the keys of the
sibling files ``ZAYA1-base`` and ``ZAYA1-VL-8B``; what ``config.json`` does not
fix is under ``assumed`` in the configuration file, and each such reading is
marked (A) at its line below. ``x`` is a layer's input, one row ``[S, hidden]``,
``H`` query heads and ``K`` KV heads of ``D`` in the latent::

    mixer:   h = RMSNorm(x);  q~ = W_q h [S, H, D];  k~ = W_k h [S, K, D];  u = [q~ | k~]
             c1_t = a_0 u_{t-1} + a_1 u_t + b_1                      depth-wise, zeros before the row
             c2_t[g] = C_0[g] c1_{t-1}[g] + C_1[g] c1_t[g] + b_2[g]  a head g of the H + K
             m^q[i] = (q~[i] + k~[i // (H/K)]) / 2;  m^k[j] = mean of its group's m^q
             q[i] = c2[i] + m^q[i];   k[j] = c2[H + j] + m^k[j]
             q <- sqrt(D) q / |q|;  k <- tau_j sqrt(D) k / |k|;  RoPE on the first rotary_dim of D
             v_t = [W_v1 h_t | W_v2 h_{t-1}]    the later half of the KV heads a token late
             y = W_o softmax(q k^T / sqrt(D)) v;    x <- (a_r x + b_r) + (a_o y + b_o)
    experts: h' = RMSNorm(x);  r_l = h' W_d + b_d (+ gamma_l r_{l-1} for l > 0), handed on
             p = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) + b_2));  e = argmax(p + beta)
             x <- (a_r' x + b_r') + (a_o' p_e E_e(h') + b_o'),  E_e = W_down(silu(W_gate h') * (W_up h'))
    head:    RMSNorm, then the embedding transposed (tied)

Here attention is over the WHOLE row (full causal scores, no cache, no page, no
tail: a token's convolutions read its two predecessors straight from the row),
and the experts are in the plainest form there is: every expert runs on every
token and a one-hot of the choice, times ``p_e``, weights the results.

Departures from the published model, each stated in the configuration file:
the sibling files' ``zaya_use_mod`` (a routing choice that skips the experts)
is NOT modelled: the router has the 16 outputs the config states; no auxiliary
loss in ``pg_loss`` (the router is frozen under LoRA); the convolutions, the
temperature, the residual's vectors, the router and the experts carry no adapter.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a time;
rows run one after another; a layer's queries run in blocks of ``Q_BLOCK``
against its KV heads; the vocabulary is projected in pieces read out of the
embedding where it lies (no padded or transposed copy of 1 GB), with a running
log-sum-exp. Every matmul runs under ``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first (a token's position is its rank among the valid ones) and the results
moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program's model or kernels runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import _project, _rms_norm, _rope

_F32 = jnp.float32
Q_BLOCK = 128
VOCAB_PIECES = 8
#: added under the root of a head's l2 norm (A: the program's; the papers give none)
L2_EPS = 1e-6


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if kinds != {"hybrid"} or not getattr(model, "router_hidden_size", 0):
        raise NotImplementedError(
            "perfbench/reference_cca_moe.py describes a zaya model (compressed "
            "convolutional attention and one routed expert a token behind an MLP "
            "router in every layer); another family brings its own reference "
            "module, named by the configuration file"
        )


def _late(x):
    """``x [S, ...]`` a token late: zeros before the row's first token."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def cca_qkv(h, layer, lora_layer, model, scale):
    """A layer's q ``[S, H, D]``, k and v ``[S, K, D]`` from its normed input
    ``h [S, hidden]``, before RoPE."""
    s, heads, kv, hd = h.shape[0], model.num_heads, model.num_kv_heads, model.head_dim
    q_raw = _project(h, layer, lora_layer, "wq", None, scale)
    k_raw = _project(h, layer, lora_layer, "wk", None, scale)
    u = jnp.concatenate([q_raw, k_raw], axis=-1)  # [S, (H + K) D]
    # (A) both convolutions carry a bias (torch's Conv1d default) and read zeros
    # before the row; the first is depth-wise, the second grouped by the H + K heads
    taps, mats = layer["conv0"].astype(_F32), layer["conv1"].astype(_F32)
    c1 = taps[0] * _late(u) + taps[1] * u + layer["b_conv0"].astype(_F32)
    by_head = lambda z, w: jnp.einsum("sgi,gio->sgo", z.reshape(s, heads + kv, hd), w)
    c2 = by_head(_late(c1), mats[0]) + by_head(c1, mats[1]) + (
        layer["b_conv1"].astype(_F32).reshape(heads + kv, hd))
    # the q-k mean, from the values BEFORE the convolutions
    mean_q = (q_raw.reshape(s, kv, heads // kv, hd) + k_raw.reshape(s, kv, 1, hd)) / 2
    q = c2[:, :heads] + mean_q.reshape(s, heads, hd)
    k = c2[:, heads:] + mean_q.mean(axis=2)
    root = jnp.sqrt(_F32(hd))
    q = root * _l2(q)
    k = root * layer["k_temp"].astype(_F32)[:, None] * _l2(k)  # one temperature a KV head
    # the value shift: the later half of the KV heads is of the PREVIOUS token
    v = jnp.concatenate([_project(h, layer, lora_layer, "wv1", None, scale),
                         _late(_project(h, layer, lora_layer, "wv2", None, scale))], axis=-1)
    return q, k, v.reshape(s, kv, hd)


def _attention(h, valid, layer, lora_layer, model, scale):
    s, heads, kv, hd = h.shape[0], model.num_heads, model.num_kv_heads, model.head_dim
    q, k, v = cca_qkv(h, layer, lora_layer, model, scale)
    positions, rot = jnp.arange(s), model.rotary_dim
    # (A) half-split pairs (i, i + rotary_dim / 2) inside the rotated part: HF's default
    rotate = lambda z: jnp.concatenate(
        [_rope(z[..., :rot], positions, model.rope_theta), z[..., rot:]], axis=-1)
    q, k = rotate(q).reshape(s, kv, heads // kv, hd), rotate(k)

    def block(args):
        q_b, pos_b = args
        scores = jnp.einsum("qkgd,skd->kgqs", q_b, k) / jnp.sqrt(_F32(hd))
        allowed = (pos_b[:, None] >= positions[None, :]) & valid[None, :]
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
    return _project(o.reshape(s, heads * hd), layer, lora_layer, "wo", None, scale)


def router(h, carried, layer, model):
    """``(p [S, E] float32, r_l [S, R])`` from the normed input and the layer
    before's ``r`` (None in layer 0). (A) exponential depth averaging with 256
    learned values a layer, ``r_{l-1}`` AFTER its own sum; three layers, exact
    GeLU, as the technical report's."""
    r = h @ layer["router_down"].astype(_F32) + layer["b_router_down"].astype(_F32)
    if carried is not None:
        r = r + layer["router_gamma"].astype(_F32) * carried
    z = _rms_norm(r, layer["router_norm"].astype(_F32), model.rms_norm_eps)
    gelu = lambda x: jax.nn.gelu(x, approximate=False)
    z = gelu(z @ layer["router_w1"].astype(_F32) + layer["b_router_w1"].astype(_F32))
    z = gelu(z @ layer["router_w2"].astype(_F32) + layer["b_router_w2"].astype(_F32))
    return jax.nn.softmax(z @ layer["router_w3"].astype(_F32), axis=-1), r


def routed_part(h, prob, layer):
    """``p_e E_e(h)`` of each token's one expert. (A) the balancing bias is in
    the choice and not in the weight; the lower index among equals. The
    experts' three stacks may be every layer's (``layer["experts_layer"]`` then
    says which is this one's): an expert is taken out of the stack where it is
    used, one at a time, and no layer's sixteen are ever copied out whole."""
    chosen = jnp.argmax(prob + layer["e_score_bias"].astype(_F32), axis=-1)
    comb = jax.nn.one_hot(chosen, prob.shape[-1], dtype=_F32) * prob  # [S, E]
    at = layer.get("experts_layer")
    stacks = [layer[name] for name in ("experts_gate", "experts_up", "experts_down")]

    def one(y, per_expert):
        e, w = per_expert
        gate, up, down = (x[e] if at is None else x[at, e] for x in stacks)
        act = jax.nn.silu(h @ gate.astype(_F32)) * (h @ up.astype(_F32))
        return y + w[:, None] * (act @ down.astype(_F32)), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                        (jnp.arange(comb.shape[1]), comb.T))
    return y


def _merge(x, y, layer, half):
    """(A) the scaled residual (the sibling files' ``scale_residual_merge``):
    ``(a_r x + b_r) + (a_o y + b_o)``, four learned vectors a sublayer."""
    a, b = layer[half + "_res_scale"].astype(_F32), layer[half + "_res_shift"].astype(_F32)
    return (a[0] * x + b[0]) + (a[1] * y + b[1])


def _layer(x, carried, valid, layer, lora_layer, model, scale):
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    x = _merge(x, _attention(h, valid, layer, lora_layer, model, scale), layer, "attn")
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)
    prob, carried = router(h, carried, layer, model)
    return _merge(x, routed_part(h, prob, layer), layer, "mlp"), carried


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    stack = params["layers"]["cca"]
    lora_stack = lora["layers"].get("cca") if lora is not None else None
    carried = None
    for at in range(model.num_layers):
        def one(x, carried, stack, lora_stack, at=at):
            take = lambda tree: jax.tree_util.tree_map(lambda w: w[at], tree)
            whole = {k: v for k, v in stack.items() if k.startswith("experts_")}
            layer = take({k: v for k, v in stack.items() if k not in whole})
            return _layer(x, carried, valid, {**layer, **whole, "experts_layer": at},
                          None if lora_stack is None else take(lora_stack), model, scale)

        x, carried = jax.checkpoint(one)(x, carried, stack, lora_stack)
    x = _rms_norm(x, params["final_norm"].astype(_F32), model.rms_norm_eps)
    return jnp.zeros_like(x).at[front].set(x)


def _token_logprobs_row(params, model, hidden, targets):
    """log softmax(hidden @ head)[targets]: the vocabulary in VOCAB_PIECES
    pieces, each read out of the (tied) embedding where it lies, with a running
    log-sum-exp. hidden [S, hidden]; targets [S]."""
    embed = params["embed"] if model.tie_word_embeddings else params["lm_head"].T
    vocab = embed.shape[0]
    piece = -(-vocab // VOCAB_PIECES)

    def body(i, carry):
        lse, picked = carry
        first = i * piece
        start = jnp.minimum(first, vocab - piece)  # the last piece may overlap the one before
        rows = jax.lax.dynamic_slice_in_dim(embed, start, piece, axis=0)
        logits = hidden @ rows.astype(_F32).T  # [S, piece]
        col = start + jnp.arange(piece)
        logits = jnp.where(col[None, :] >= first, logits, -jnp.inf)
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
        local = targets - start
        here = (targets >= first) & (local < piece)
        got = jnp.take_along_axis(
            logits, jnp.clip(local, 0, piece - 1)[:, None], axis=-1)[:, 0]
        return lse, jnp.where(here, got, picked)

    init = (jnp.full(hidden.shape[:1], -jnp.inf, _F32), jnp.zeros(hidden.shape[:1], _F32))
    lse, picked = jax.lax.fori_loop(0, VOCAB_PIECES, jax.checkpoint(body), init)
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
