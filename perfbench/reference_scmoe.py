"""Plain reference of LongCat-Flash-Chat
(https://huggingface.co/meituan-longcat/LongCat-Flash-Chat, ``model_type``
``longcat_flash``), in float32: a shortcut-connected expert model. ONE
published layer holds two latent-attention (MLA) sublayers, each followed by a
dense gated MLP, and one expert block that reads the first MLP's normed input
and joins the stream after the second MLP.

Written from the published ``config.json`` and the catalog's description; what
the config leaves open is under ``assumed`` in the configuration file, and each
such choice is marked at its line below. With ``n_k`` and ``m_k`` the RMSNorms
(eps ``rms_norm_eps``) before sublayer ``k``'s attention and MLP, a layer is::

    a = x + MLA_0(n_0 x)            u = m_0 a            e = Experts(u)
    b = a + MLP_0(u)                c = b + MLA_1(n_1 b)
    y = c + MLP_1(m_1 c) + e

    MLA_k(h):  c_q = q_scale * RMSNorm(W_qa h)         [T, q_lora_rank]   q_scale = (hidden / q_lora_rank)^0.5
               q = W_qb c_q                            [T, H, nope + rope]
               [c_raw, k_pe] = W_kva h                 [T, rank], [T, rope]
               c = kv_scale * RMSNorm(c_raw)           kv_scale = (hidden / kv_lora_rank)^0.5
               q_pe, k_pe <- RoPE                      interleaved pairs (x[2i], x[2i+1]); k_pe one for all heads, not scaled
               [k_nope, v] = c W_kvb                   [T, H, nope], [T, H, v]
               o = softmax(q . [k_nope, k_pe] / sqrt(nope + rope), causal) v;   W_o o
    MLP_k(h):  W_down(silu(W_gate h) * (W_up h))       ffn_hidden_size wide

    Experts(u):  s = softmax(u W_r)                    [T, E + Z] float32: E routed experts, then Z that compute nothing
                 picked = the k largest of s + b       b: e_score_correction_bias, in the choice only; the lower index among equals
                 w = routed_scaling_factor * s[picked] NO renormalisation
                 e = sum_{j picked, j < E, held here} w_j E_j(u) + (sum_{j picked, j >= E} w_j) * u

Here attention is the EXPANDED form over the whole row (K and V rebuilt per
head; no cache, no absorption, no page), and the experts the plainest form
there is: every expert held runs on every token and a combine matrix, zero
outside the picked k, weights the results; the experts that compute nothing
are the sum of their columns of that matrix times ``u``.

**The share.** The configuration states one chip's share of a layer that 32
chips divide: this reference is given the SAME share. The router has its
published width (512 + 256) and picks among all its outputs; the routed
experts whose weights are here (``n_routed_experts`` of them, the ids
``expert_shard * n ..``) add their part, a pair routed to an expert held
elsewhere adds nothing; the zero-compute part is WHOLE here (it costs no
product and belongs to the token's home chip, as a shared expert does); both
attentions and both dense MLPs are whole; the vocabulary is the slice the file
states, a smaller vocabulary.

Departures from the published model, each stated in the configuration file:
the two scale constants multiply the normed latents BEFORE their
up-projections (the published code scales q and k_nope/v after them: the
up-projections are linear and carry no bias, so the values are the same); no
auxiliary loss in ``pg_loss`` (the router is frozen under LoRA); the router,
its bias and the routed experts carry no adapter.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one sublayer (one expert) at a
time; rows run one after another; attention runs ``HEAD_BLOCK`` heads at a
time and ``Q_BLOCK`` queries at a time under them; a gated MLP's tokens in
blocks of ``MLP_BLOCK``; the vocabulary is projected in pieces with a running
log-sum-exp. Every matmul runs under ``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first (positions count real tokens only) and the results moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program's model or kernels runs here. The parameter tree is the program's:
``params["layers"]["latent_fork"]`` stacks the first sublayers (with the
router and the experts held), ``["latent_join"]`` the second ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import _project, _rms_norm, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 256
HEAD_BLOCK = 16
MLP_BLOCK = 2048
SUBLAYERS = ("latent_fork", "latent_join")


def _check_family(model) -> None:
    if (not getattr(model, "shortcut_moe", False) or not getattr(model, "kv_lora_rank", 0)
            or not getattr(model, "q_lora_rank", 0) or not getattr(model, "router_softmax", False)
            or getattr(model, "norm_topk_prob", True)
            or getattr(model, "hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            "perfbench/reference_scmoe.py describes a longcat_flash model (two "
            "latent-attention sublayers a layer round a softmax-routed expert block "
            "with zero-compute experts, unnormalised weights, SiLU); another family "
            "brings its own reference module, named by the configuration file")


def routed_width(model) -> int:
    """Routed experts the router scores (the published count): its first outputs."""
    return model.router_experts or model.n_routed_experts


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


def _attention(h, valid, positions, layer, lora_layer, model, scale):
    s, heads = h.shape[0], model.num_heads
    nope, rope, rank = model.qk_nope_head_dim, model.qk_rope_head_dim, model.kv_lora_rank
    v_dim = model.v_head_dim
    # assumed: the constants multiply the normed latents before the up-projections
    c_q = model.latent_q_scale * _rms_norm(
        _project(h, layer, lora_layer, "wq_a", "bq_a", scale),
        layer["q_a_norm"].astype(_F32), model.rms_norm_eps)
    kva = _project(h, layer, lora_layer, "wkv_a", "bkv_a", scale)
    c = model.latent_kv_scale * _rms_norm(
        kva[:, :rank], layer["kv_a_norm"].astype(_F32), model.rms_norm_eps)
    # assumed: interleaved pairs, as DeepSeek-V3's MLA; the rotary key is not scaled
    k_pe = _rope_pairs(kva[:, rank:], positions, model.rope_theta)  # [S, rope]

    hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    adapters = lora_layer or {}
    pieces = {name: (_head_blocks(layer[name], heads, hb),
                     _head_blocks(adapters[name]["b"], heads, hb) if name in adapters else None)
              for name in ("wq", "wkv_b")}

    def some_heads(pieces):
        """``hb`` heads: their queries, their K and V, then every query over
        the keys at or before it."""
        def project(x, name):
            w, b = pieces[name]
            lora_piece = None if b is None else {name: {"a": adapters[name]["a"], "b": b}}
            return _project(x, {name: w}, lora_piece, name, "", scale).reshape(s, hb, -1)

        q, kv = project(c_q, "wq"), project(c, "wkv_b")
        q_pe = _rope_pairs(q[..., nope:], positions, model.rope_theta)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(args):
            qn_b, qp_b, pos_b = args  # [Q, hb, nope], [Q, hb, rope], [Q]
            # the softmax scale is (nope + rope)^-0.5 = 192^-0.5
            scores = (jnp.einsum("qhd,khd->hqk", qn_b, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qp_b, k_pe)) / jnp.sqrt(_F32(nope + rope))
            seen = (pos_b[:, None] >= positions[None, :]) & valid[None, :]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            # a padding query may see nothing; keep its row finite (never read)
            scores = jnp.where(seen.any(-1)[None, :, None], scores, 0.0)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

        return _blocks(block, s, q[..., :nope], q_pe, positions)

    o = jax.lax.map(jax.checkpoint(some_heads), pieces)  # [heads / hb, S, hb, v]
    return _project(o.transpose(1, 0, 2, 3).reshape(s, heads * v_dim), layer, lora_layer,
                    "wo", "bo", scale)


def combine_matrix(u, layer, model):
    """[T, E + Z] float32 over ALL the router's outputs: ``w`` at a token's
    picked outputs, 0 elsewhere. The correction bias is in the choice and not
    in the weights; the weights are the scores as they are, times
    ``routed_scaling_factor`` (assumed: ``norm_topk_prob`` false)."""
    scores = jax.nn.softmax(u @ layer["router"].astype(_F32), axis=-1)
    biased = scores + layer["e_score_bias"].astype(_F32)
    picked = jnp.zeros(scores.shape, bool)
    for _ in range(model.experts_per_token):  # the largest left, lowest index first
        best = jnp.argmax(jnp.where(picked, -jnp.inf, biased), axis=-1)
        picked = picked | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    return jnp.where(picked, scores, 0.0) * model.routed_scaling_factor


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(_F32)) * (h @ up.astype(_F32))) @ down.astype(_F32)


def zero_part(u, comb, model):
    """What the picked experts that compute nothing add: each returns ``u``."""
    return comb[:, routed_width(model):].sum(-1, keepdims=True) * u


def routed_part(u, comb, layer, model):
    """The held experts' part of ``sum_e comb[:, e] E_e(u)``. The experts'
    three stacks may be every layer's (``layer["experts_layer"]`` then says
    which is this one's): an expert is taken out of the stack where it is
    used, one at a time."""
    comb = comb[:, jnp.asarray(held_ids(model))]
    at = layer.get("experts_layer")
    stacks = [layer[name] for name in ("experts_gate", "experts_up", "experts_down")]

    def one(y, per_expert):
        e, w = per_expert
        gate, up, down = (x[e] if at is None else x[at, e] for x in stacks)
        return y + w[:, None] * _gated(u, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(u),
                        (jnp.arange(comb.shape[1]), comb.T))
    return y


def experts(u, layer, model):
    """``Experts(u)`` of the module docstring, under the share."""
    comb = combine_matrix(u, layer, model)
    return routed_part(u, comb, layer, model) + zero_part(u, comb, model)


def _token_blocks(fn, h):
    """``fn`` over blocks of ``MLP_BLOCK`` tokens of ``h [S, D]``."""
    s, pad = h.shape[0], -h.shape[0] % MLP_BLOCK
    if s <= MLP_BLOCK:
        return fn(h)
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, MLP_BLOCK, h.shape[1])
    return jax.lax.map(jax.checkpoint(fn), blocks).reshape(-1, h.shape[1])[:s]


def _sublayer(x, e, valid, positions, layer, lora_layer, model, scale, k: int):
    """Sublayer ``k`` of a layer: ``(x, e)`` -> ``(x, e)``; ``e`` is made in
    sublayer 0 and added in sublayer 1."""
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    x = x + _attention(h, valid, positions, layer, lora_layer, model, scale)
    u = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)

    def mlp(h):
        gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
        up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
        return _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)

    if k == 0:
        e = _token_blocks(lambda h: experts(h, layer, model), u)
        return x + _token_blocks(mlp, u), e
    return x + _token_blocks(mlp, u) + e, e


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    positions = jnp.arange(ids.shape[0])
    x = jnp.take(params["embed"], ids, axis=0).astype(_F32)
    e = jnp.zeros_like(x)
    for index in range(model.num_layers):
        for k, kind in enumerate(SUBLAYERS):
            lora_stack = lora["layers"].get(kind) if lora is not None else None

            def one(carry, stack, lora_stack, k=k, at=index):
                # sliced INSIDE what reverse mode recomputes: what it keeps for a
                # sublayer is the stack that is there anyway, not a copy of it
                take = lambda tree: jax.tree_util.tree_map(lambda w: w[at], tree)
                held = {name: w for name, w in stack.items() if name.startswith("experts_")}
                layer = take({name: w for name, w in stack.items() if name not in held})
                if held:  # left in their stack: ``routed_part`` takes one at a time
                    layer.update(held, experts_layer=at)
                return _sublayer(*carry, valid, positions, layer,
                                 None if lora_stack is None else take(lora_stack),
                                 model, scale, k)

            x, e = jax.checkpoint(one)((x, e), params["layers"][kind], lora_stack)
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
