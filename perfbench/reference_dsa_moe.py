"""Plain reference of GLM-5 (https://huggingface.co/zai-org/GLM-5,
``model_type`` ``glm_moe_dsa``), in float32: latent attention (MLA) with a
low-rank query path behind a learned index over single tokens (DeepSeek's
sparse attention) in every layer, a dense gated MLP in the first
``first_k_dense_replace`` layers and a router over ``n_routed_experts`` experts
beside one shared expert in the rest.

Written from the published ``config.json`` and the catalog's description; what
the config leaves open is under ``assumed`` in the configuration file, and each
such choice is marked at its line below. ``h = RMSNorm(x)`` before each half,
the residual after::

    c_q = RMSNorm(W_qa h)                  [T, q_lora_rank]       q_a_layernorm
    q = W_qb c_q                           [T, H, nope + rope]
    [c_raw, k_pe] = W_kva h                [T, rank], [T, rope]
    c = RMSNorm(c_raw)                     kv_a_layernorm
    q_pe, k_pe <- RoPE                     interleaved pairs (x[2i], x[2i+1]); k_pe one for all heads
    [k_nope, v] = c W_kvb                  [T, H, nope], [T, H, v]

    q_I = W_qI c_q  [T, H_I, D_I];  k_I = LayerNorm(W_kI h)  [T, D_I];  w = W_w h  [T, H_I]
    q_I, k_I <- RoPE on their FIRST rope values, interleaved pairs
    I[t, s] = H_I^-0.5 D_I^-0.5 sum_h w[t, h] relu(q_I[t, h] . k_I[s])          s <= t
    chosen(t) = the min(index_topk, t + 1) tokens of largest I[t, .], the lower index among equals

    o = softmax(q . [k_nope, k_pe] / sqrt(nope + rope) over chosen(t) ONLY) v;   y = W_o o

    s = sigmoid(h W_g)                     [T, E]
    picked = the k largest of s + b        b: e_score_correction_bias; the lower index among equals
    w = s[picked] / (sum s[picked] + 1e-20) * routed_scaling_factor
    y = sum_{k held here} w_k E_k(h) + S(h)     E, S: W_down(silu(W_gate h) * (W_up h))

Here the index's scores are the whole ``[T, T]`` square, the choice a ranking
of every key of every query (two stable sorts: a key's rank among the query's
keys by score, then by index), the attention the EXPANDED form over the whole
row with the choice as a mask (K and V rebuilt per head; no cache, no
absorption, no gather), and the experts the plainest form there is: every
expert held runs on every token and a combine matrix, zero outside the picked
k, weights the results.

**The share.** The configuration states one chip's share of a layer that 16
chips divide: this reference is given the SAME share. The router has its
published width (256) and picks among all its experts; the experts whose
weights are here (``n_routed_experts`` of them, the ids ``expert_shard * n ..``)
add their part, a pair routed to an expert held elsewhere adds nothing; the
shared expert, attention and the index are whole; the vocabulary is the slice
the file states, a smaller vocabulary.

Departures from the published model, each stated in the configuration file:
the published inference code quantises the index to fp8 behind a Hadamard
rotation of q_I and k_I; the rotation is orthogonal and leaves every q_I . k_I
as it is, and the configuration is bf16, so neither is here. The
multi-token-prediction module is not instantiated (no logit of the main head
depends on it). No auxiliary loss in ``pg_loss`` (the router is frozen under
LoRA); the index, the router, its bias and the routed experts carry no adapter,
and the choice is not differentiated (it is a set).

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a
time; rows run one after another; the index's square is made ``Q_BLOCK``
queries at a time and kept as the choice alone, one byte a (query, key);
attention runs ``HEAD_BLOCK`` heads at a time (their K and V rebuilt once) and
``Q_BLOCK`` queries at a time under them; a gated MLP's tokens in blocks of
``MLP_BLOCK``; the vocabulary is projected in pieces with a running
log-sum-exp. Every matmul runs under ``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first (positions count real tokens only) and the results moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program's model or kernels runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import _project, _rms_norm, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 256
HEAD_BLOCK = 16
MLP_BLOCK = 2048
#: assumed: eps of the index key's LayerNorm (torch's default; no key states it)
INDEX_NORM_EPS = 1e-6


def _check_family(model) -> None:
    if (not getattr(model, "kv_lora_rank", 0) or not getattr(model, "index_topk", 0)
            or not getattr(model, "q_lora_rank", 0)
            or getattr(model, "hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            "perfbench/reference_dsa_moe.py describes a glm_moe_dsa model (latent "
            "attention with a low-rank query path behind a learned index over "
            "tokens, sigmoid-scored experts, SiLU); another family brings its own "
            "reference module, named by the configuration file")


def held_ids(model) -> list[int]:
    """Ids of the routed experts whose weights are here, in stack order."""
    n = model.n_routed_experts
    first = model.expert_shard * n if model.router_experts else 0
    return list(range(first, first + n))


def _rope_pairs(x, positions, theta):
    """x [S, ..., D]: rotate the pairs (x[2i], x[2i+1]) by position."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    angles = positions.astype(_F32)[:, None] * inv_freq  # [S, D/2]
    angles = angles.reshape(angles.shape[:1] + (1,) * (x.ndim - 2) + angles.shape[1:])
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _blocks(fn, s: int, *arrays):
    """``fn`` over blocks of ``Q_BLOCK`` leading entries of ``arrays`` (the last
    padded: ``positions`` with -1, which sees nothing), the results joined."""
    if s <= Q_BLOCK:
        return fn(arrays)
    pad = -s % Q_BLOCK
    cut = lambda a: jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
        constant_values=-1 if jnp.issubdtype(a.dtype, jnp.integer) else 0,
    ).reshape(-1, Q_BLOCK, *a.shape[1:])
    out = jax.lax.map(jax.checkpoint(fn), tuple(map(cut, arrays)))
    return out.reshape(-1, *out.shape[2:])[:s]


def _head_blocks(w, heads: int, block: int):
    """A projection's columns, a head after another, cut into blocks of
    ``block`` heads: ``[in, heads * width]`` -> ``[heads / block, in, block *
    width]``."""
    return w.reshape(w.shape[0], heads // block, -1).transpose(1, 0, 2)


def index_choice(h, c_q, valid, positions, layer, model):
    """``chosen [S, S]`` bool: ``chosen[t, s]`` says that token ``t`` attends
    token ``s``. No adapter; nothing here is differentiated."""
    s, heads, dim = h.shape[0], model.index_heads, model.index_head_dim
    rope, k = model.qk_rope_head_dim, model.index_topk
    q_i = (c_q @ layer["w_index_q"].astype(_F32)).reshape(s, heads, dim)
    k_i = h @ layer["w_index_k"].astype(_F32)
    k_i = k_i - k_i.mean(-1, keepdims=True)  # assumed: LayerNorm with weight AND bias
    k_i = (k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, -1, keepdims=True) + INDEX_NORM_EPS)
           * layer["index_k_norm"].astype(_F32) + layer["b_index_k"].astype(_F32))
    w = h @ layer["w_index_w"].astype(_F32)
    # assumed: RoPE on the FIRST rope of the D_I values (DeepSeek-V3.2's published
    # inference code splits rope first), the same base and pairs as q_pe / k_pe
    rotate = lambda x: jnp.concatenate(
        [_rope_pairs(x[..., :rope], positions, model.rope_theta), x[..., rope:]], axis=-1)
    q_i, k_i = rotate(q_i), rotate(k_i)
    # assumed scale; positive, so it cannot change a choice
    scale = heads ** -0.5 * dim ** -0.5

    def block(args):
        q_b, w_b, pos_b = args  # [Q, H_I, D_I], [Q, H_I], [Q]
        scores = scale * jnp.einsum(
            "qh,qhk->qk", w_b, jax.nn.relu(jnp.einsum("qhd,kd->qhk", q_b, k_i)))
        allowed = (pos_b[:, None] >= positions[None, :]) & valid[None, :]
        scores = jnp.where(allowed, scores, -jnp.inf)
        # a key's rank among the query's keys: by score, the lower index among equals
        order = jnp.argsort(-scores, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        return (rank < k) & allowed

    return jax.lax.stop_gradient(_blocks(block, s, q_i, w, positions))


def _attention(h, valid, positions, layer, lora_layer, model, scale):
    s, heads = h.shape[0], model.num_heads
    nope, rope, rank = model.qk_nope_head_dim, model.qk_rope_head_dim, model.kv_lora_rank
    v_dim = model.v_head_dim
    # assumed: q_a_layernorm is an RMSNorm of one weight, eps rms_norm_eps
    c_q = _rms_norm(_project(h, layer, lora_layer, "wq_a", "bq_a", scale),
                    layer["q_a_norm"].astype(_F32), model.rms_norm_eps)
    kva = _project(h, layer, lora_layer, "wkv_a", "bkv_a", scale)
    c = _rms_norm(kva[:, :rank], layer["kv_a_norm"].astype(_F32), model.rms_norm_eps)
    k_pe = _rope_pairs(kva[:, rank:], positions, model.rope_theta)  # [S, rope]
    chosen = index_choice(h, c_q, valid, positions, layer, model)

    # heads in blocks, one block after another (a loop XLA may not overlap)
    hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    adapters = lora_layer or {}
    pieces = {name: (_head_blocks(layer[name], heads, hb),
                     _head_blocks(adapters[name]["b"], heads, hb) if name in adapters else None)
              for name in ("wq", "wkv_b")}

    def some_heads(pieces):
        """``hb`` heads: their queries, their K and V, then every query under
        its choice."""
        def project(x, name):
            w, b = pieces[name]
            lora_piece = None if b is None else {name: {"a": adapters[name]["a"], "b": b}}
            return _project(x, {name: w}, lora_piece, name, "", scale).reshape(s, hb, -1)

        q, kv = project(c_q, "wq"), project(c, "wkv_b")
        q_pe = _rope_pairs(q[..., nope:], positions, model.rope_theta)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(args):
            qn_b, qp_b, seen = args  # [Q, hb, nope], [Q, hb, rope], [Q, S]
            # assumed: the softmax scale is (nope + rope)^-0.5, no mscale (no scaled RoPE)
            scores = (jnp.einsum("qhd,khd->hqk", qn_b, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qp_b, k_pe)) / jnp.sqrt(_F32(nope + rope))
            scores = jnp.where(seen[None], scores, -jnp.inf)
            # a padding query may see nothing; keep its row finite (never read)
            scores = jnp.where(seen.any(-1)[None, :, None], scores, 0.0)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

        return _blocks(block, s, q[..., :nope], q_pe, chosen)

    o = jax.lax.map(jax.checkpoint(some_heads), pieces)  # [heads / hb, S, hb, v]
    return _project(o.transpose(1, 0, 2, 3).reshape(s, heads * v_dim), layer, lora_layer,
                    "wo", "bo", scale)


def combine_matrix(h, layer, model):
    """[T, E] float32 over ALL the experts the router scores: ``w`` at a
    token's picked experts, 0 elsewhere. Assumed: the correction bias is in
    the choice and not in the weights (DeepSeek-V3's convention for these keys)."""
    scores = jax.nn.sigmoid(h @ layer["router"].astype(_F32))
    biased = scores + layer["e_score_bias"].astype(_F32)
    picked = jnp.zeros(scores.shape, bool)
    for _ in range(model.experts_per_token):  # the largest left, lowest index first
        best = jnp.argmax(jnp.where(picked, -jnp.inf, biased), axis=-1)
        picked = picked | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    w = jnp.where(picked, scores, 0.0)
    if model.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * model.routed_scaling_factor


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(_F32)) * (h @ up.astype(_F32))) @ down.astype(_F32)


def routed_part(h, layer, model):
    """The held experts' part of ``sum_e combine[:, e] E_e(h)``. The experts'
    three stacks may be every layer's (``layer["experts_layer"]`` then says
    which is this one's): an expert is taken out of the stack where it is
    used, one at a time, and no layer's sixteen are ever copied out whole."""
    comb = combine_matrix(h, layer, model)[:, jnp.asarray(held_ids(model))]
    at = layer.get("experts_layer")
    stacks = [layer[name] for name in ("experts_gate", "experts_up", "experts_down")]

    def one(y, per_expert):
        e, w = per_expert
        gate, up, down = (x[e] if at is None else x[at, e] for x in stacks)
        return y + w[:, None] * _gated(h, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                        (jnp.arange(comb.shape[1]), comb.T))
    return y


def _layer(x, valid, positions, layer, lora_layer, model, scale, moe: bool):
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    x = x + _attention(h, valid, positions, layer, lora_layer, model, scale)
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)

    def ffn(h):
        # the dense MLP, or the shared expert (assumed: ONE gated MLP, unweighted)
        y = 0.0
        if "w_gate" in layer:
            gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
            up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
            y = _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)
        return y + routed_part(h, layer, model) if moe else y

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
            experts = {k: w for k, w in stack.items() if k.startswith("experts_")}
            layer = take({k: w for k, w in stack.items() if k not in experts})
            if experts:  # left in their stack: ``routed_part`` takes one at a time
                layer.update(experts, experts_layer=at)
            return _layer(x, valid, positions, layer,
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
