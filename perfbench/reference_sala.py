"""Plain reference of MiniCPM-SALA (https://huggingface.co/openbmb/MiniCPM-SALA),
in float32: block-sparse attention layers (``minicpm4``, InfLLM-V2) beside
lightning linear-attention layers, in the order ``mixer_types`` publishes.

Written from the published ``config.json`` and the descriptions of the two
mixers (Lightning Attention as MiniMax-01 and ``fla`` build its decay;
InfLLM-V2 as MiniCPM4.1 publishes its ``sparse_config``). Every value the
config does not state is listed, with its sentence, under ``assumed`` in
``perfbench/configs/minicpm-sala-L10.json``.

The equations. ``c = scale_depth / sqrt(len(mixer_types))`` (the PUBLISHED
depth, whatever depth is run), ``h = RMSNorm(x)``::

    x0 = scale_emb * Embed(ids)
    x <- x + c * Mixer(h);   x <- x + c * W_down(silu(W_gate h) * (W_up h))
    logits = W_head(RMSNorm(x) / (hidden_size / dim_model_base))

* ``lightning-attn``: ``q, k, v = W_q h, W_k h, W_v h`` as [T, H, D], no
  bias, no activation; ``q, k <- RMSNorm_D`` (one weight of D each); ``q, k <-
  RoPE`` (rotate-half, all D dims). Per head ``S_t = lam_h S_{t-1} + k_t
  v_t^T`` (D x D), ``o_t = S_t^T q_t / sqrt(D)``, ``lam_h = exp(-s_h (1 -
  l/(L-1) + 1e-5))``, ``s_h = 2^(-8h/H)``, h = 1..H, l the layer's published
  index, L the published depth. ``y = W_o(RMSNorm_{H D}(o) * sigmoid(W_z h))``.
  Here: a ``lax.scan`` over tokens.
* ``minicpm4``: ``q`` [T, H, D], ``k, v`` [T, K, D], no bias, no RoPE, q and k
  normed as above. Pooled keys ``Kc_j = mean(K[stride j : stride j +
  kernel])``. For query t and KV head g: ``a_{h,j} = softmax_j(q_{t,h} . Kc_j /
  sqrt(D))`` over the pooled keys that end at or before t, summed over the
  group's heads; a block's score is the largest ``a_{g,j}`` among the pooled
  keys that overlap it; the query attends, causally, to the first
  ``init_blocks`` blocks, the blocks that overlap its last ``window_size``
  tokens, and the ``topk`` best of the rest (the lower index among equals), by
  ``softmax(q . K_sel / sqrt(D)) V_sel`` per head. ``y = W_o(o * sigmoid(W_z
  h))``. Here: full scores, an explicit token mask built from the chosen
  blocks, one softmax.

Departures from the published model, each stated in the configuration file:
one scoring stage (the published code also has a coarser pre-pass);
``dense_len`` is applied per QUERY (a query whose context is at most
``dense_len`` tokens attends to every block), so that scoring a sequence in
one pass and decoding it token by token are the same function.

Departures for memory, none of which changes a value: weights stay in the
type they are served in and are widened to float32 one layer at a time; rows
run one after another; a sparse layer's queries run in blocks of ``Q_BLOCK``
and the MLP's tokens in blocks of ``MLP_BLOCK``;
the recurrence is scanned in chunks so reverse mode keeps one state per chunk;
the vocabulary is projected in pieces with a running log-sum-exp; reverse mode
recomputes each row, layer and piece (``jax.checkpoint``). Every matmul runs
under ``default_matmul_precision("highest")``.

Padding may sit anywhere in a row: the valid tokens are moved to the front
first (positions, blocks and pooled keys count real tokens only) and the
results moved back.

``model`` is the program's ``ModelConfig`` only as a bag of sizes; no code of
the program runs here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# what does not differ from the dense decoder's reference: RMSNorm, rotate-half
# RoPE, and the head's log-probabilities in vocabulary pieces
from perfbench.reference import _rms_norm, _rope, _token_logprobs_row

_F32 = jnp.float32
Q_BLOCK = 128
SCAN_CHUNK = 64
MLP_BLOCK = 2048
_KIND = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def _check_family(model) -> None:
    if not getattr(model, "mixer_types", None) or getattr(
        model, "hidden_act", "silu"
    ) != "silu" or getattr(model, "attn_use_rope", True):
        raise NotImplementedError(
            "perfbench/reference_sala.py describes MiniCPM-SALA (mixer_types, "
            "SiLU, sparse layers without RoPE); another family brings its own "
            "reference module, named by the configuration file"
        )


def _project(x, layer, lora_layer, name, scale):
    y = x @ layer[name].astype(_F32)
    if lora_layer is not None and name in lora_layer:
        a = lora_layer[name]["a"].astype(_F32)
        b = lora_layer[name]["b"].astype(_F32)
        y = y + (x @ a) @ b * scale
    return y


def _decay(model, published_index: int):
    """lam_h of the lightning layer at ``published_index``: [H] float32."""
    heads = model.lightning_heads
    slopes = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=_F32) / heads)
    depth = len(model.mixer_types)
    return jnp.exp(-slopes * (1.0 - published_index / (depth - 1) + 1e-5))


def _lightning(h, valid, positions, layer, lora_layer, model, scale, index):
    s = h.shape[0]
    heads, d = model.lightning_heads, model.lightning_head_dim
    eps = model.rms_norm_eps
    q = _project(h, layer, lora_layer, "wq", scale).reshape(s, heads, d)
    k = _project(h, layer, lora_layer, "wk", scale).reshape(s, heads, d)
    v = _project(h, layer, lora_layer, "wv", scale).reshape(s, heads, d)
    q = _rms_norm(q, layer["q_norm"].astype(_F32), eps)
    k = _rms_norm(k, layer["k_norm"].astype(_F32), eps)
    q = _rope(q, positions, model.rope_theta)
    k = _rope(k, positions, model.rope_theta)
    lam = _decay(model, index)[:, None, None]

    def step(state, x):
        q_t, k_t, v_t, ok = x
        new = lam * state + k_t[:, :, None] * v_t[:, None, :]
        state = jnp.where(ok, new, state)
        return state, jnp.einsum("hkd,hk->hd", state, q_t) / math.sqrt(d)

    pad = -s % SCAN_CHUNK
    xs = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (-1, SCAN_CHUNK) + x.shape[1:])
        for x in (q, k, v, valid)
    )

    def chunk(state, x):
        return jax.lax.scan(step, state, x)

    _, o = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((heads, d, d), _F32), xs)
    o = o.reshape(-1, heads * d)[:s]
    o = _rms_norm(o, layer["o_norm"].astype(_F32), eps)
    gate = jax.nn.sigmoid(h @ layer["wz"].astype(_F32))
    return _project(o * gate, layer, lora_layer, "wo", scale)


def chosen_blocks(q, pooled, t, model, n_blocks: int):
    """bool [Q, K, n_blocks]: the blocks queries ``q`` [Q, H, D] at positions
    ``t`` [Q] attend, given every pooled key ``pooled`` [NP, K, D]."""
    kh = pooled.shape[1]
    n_q, heads, d = q.shape
    group = heads // kh
    bs, stride, kernel = (model.sparse_block_size, model.sparse_kernel_stride,
                          model.sparse_kernel_size)
    j = jnp.arange(pooled.shape[0])
    blk = jnp.arange(n_blocks)
    seen = (stride * j + kernel - 1)[None, :] <= t[:, None]  # [Q, NP]
    logits = jnp.einsum("qkgd,jkd->qkgj", q.reshape(n_q, kh, group, d), pooled)
    logits = jnp.where(seen[:, None, None, :], logits / math.sqrt(d), -jnp.inf)
    a = jnp.where(seen[:, None, None, :], jax.nn.softmax(logits, axis=-1), 0.0)
    a = jnp.where(seen.any(-1)[:, None, None, None], a, 0.0).sum(axis=2)  # [Q, K, NP]
    overlap = (stride * j[None, :] <= bs * blk[:, None] + bs - 1) & (
        stride * j[None, :] + kernel - 1 >= bs * blk[:, None]
    )  # [NB, NP]
    usable = overlap[None, None] & seen[:, None, None, :]
    score = jnp.max(jnp.where(usable, a[:, :, None, :], -1.0), axis=-1)  # [Q, K, NB]
    causal = blk[None, :] <= (t // bs)[:, None]
    forced = causal & (
        (blk[None, :] < model.sparse_init_blocks)
        | (bs * blk[None, :] + bs - 1 >= (t - model.sparse_window_size + 1)[:, None])
    )
    rest = (causal & ~forced)[:, None, :]
    _, best = jax.lax.top_k(
        jnp.where(rest, score, -jnp.inf), min(model.sparse_topk, n_blocks))
    picked = jnp.zeros((n_q, kh, n_blocks), bool).at[
        jnp.arange(n_q)[:, None, None], jnp.arange(kh)[None, :, None], best
    ].set(True) & rest
    dense = (t + 1 <= model.sparse_dense_len)[:, None, None]
    return jnp.where(dense, causal[:, None, :], forced[:, None, :] | picked)


def _sparse(h, valid, layer, lora_layer, model, scale):
    """Valid tokens sit at the front of the row (``_hidden_row`` moved them)."""
    s = h.shape[0]
    heads, kh, d = model.num_heads, model.num_kv_heads, model.head_dim
    group = heads // kh
    bs, stride, kernel = (model.sparse_block_size, model.sparse_kernel_stride,
                          model.sparse_kernel_size)
    eps = model.rms_norm_eps
    q = _project(h, layer, lora_layer, "wq", scale).reshape(s, heads, d)
    k = _project(h, layer, lora_layer, "wk", scale).reshape(s, kh, d)
    v = _project(h, layer, lora_layer, "wv", scale).reshape(s, kh, d)
    q = _rms_norm(q, layer["q_norm"].astype(_F32), eps)
    k = _rms_norm(k, layer["k_norm"].astype(_F32), eps)
    n_pooled = max((s - kernel) // stride + 1, 0)
    starts = stride * jnp.arange(n_pooled)
    pooled = jax.vmap(
        lambda at: jax.lax.dynamic_slice_in_dim(k, at, kernel, axis=0).mean(axis=0)
    )(starts) if n_pooled else jnp.zeros((0, kh, d), _F32)
    n_blocks = -(-s // bs)
    pad = -s % Q_BLOCK
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, heads, d)
    ts = jnp.pad(jnp.arange(s), (0, pad)).reshape(-1, Q_BLOCK)
    key_pos = jnp.arange(s)

    def block(x):
        q_c, t = x
        if n_pooled:
            blocks = chosen_blocks(q_c, pooled, t, model, n_blocks)
        else:  # shorter than one pooled key: nothing to choose from
            blocks = jnp.broadcast_to(
                (jnp.arange(n_blocks)[None, :] <= (t // bs)[:, None])[:, None, :],
                (Q_BLOCK, kh, n_blocks))
        allowed = jnp.repeat(blocks, bs, axis=-1)[..., :s]  # [Q, K, S]
        allowed = allowed & (key_pos[None, None, :] <= t[:, None, None])
        allowed = allowed & valid[None, None, :]
        scores = jnp.einsum("qkgd,skd->kgqs", q_c.reshape(Q_BLOCK, kh, group, d), k)
        scores = jnp.where(allowed.transpose(1, 0, 2)[:, None], scores / math.sqrt(d),
                           -jnp.inf)
        # a padding query attends nothing; keep its row finite (never read)
        scores = jnp.where(allowed.any(-1).T[:, None, :, None], scores, 0.0)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v).reshape(Q_BLOCK, heads * d)

    o = jax.lax.map(jax.checkpoint(block), (qs, ts)).reshape(-1, heads * d)[:s]
    gate = jax.nn.sigmoid(h @ layer["wz"].astype(_F32))
    return _project(o * gate, layer, lora_layer, "wo", scale)


def _layer(x, valid, positions, layer, lora_layer, model, scale, kind, index):
    c = model.scale_depth / math.sqrt(len(model.mixer_types))
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    if kind == "sparse":
        y = _sparse(h, valid, layer, lora_layer, model, scale)
    else:
        y = _lightning(h, valid, positions, layer, lora_layer, model, scale, index)
    x = x + c * y
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)

    def mlp(h):
        gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", scale))
        up = _project(h, layer, lora_layer, "w_up", scale)
        return _project(gate * up, layer, lora_layer, "w_down", scale)

    s, pad = h.shape[0], -h.shape[0] % MLP_BLOCK
    if s <= MLP_BLOCK:
        return x + c * mlp(h)
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, MLP_BLOCK, h.shape[1])
    return x + c * jax.lax.map(jax.checkpoint(mlp), blocks).reshape(-1, h.shape[1])[:s]


def _hidden_row(params, lora, model, ids, valid, scale):
    """Final-norm hidden states [S, hidden] of one row, scaled for the head."""
    front = jnp.argsort(~valid, stable=True)  # the valid tokens first, in order
    ids, valid = ids[front], valid[front]
    positions = jnp.arange(ids.shape[0])
    x = model.scale_emb * jnp.take(params["embed"], ids, axis=0).astype(_F32)
    seen = {"sparse": 0, "lightning": 0}
    for index, name in enumerate(model.mixer_types[: model.num_layers]):
        kind = _KIND[name]
        at = seen[kind]
        seen[kind] += 1
        layer = jax.tree_util.tree_map(lambda w: w[at], params["layers"][kind])
        lora_layer = (
            jax.tree_util.tree_map(lambda w: w[at], lora["layers"][kind])
            if lora is not None and kind in lora["layers"] else None
        )
        x = jax.checkpoint(
            lambda x, layer, lora_layer, kind=kind, index=index: _layer(
                x, valid, positions, layer, lora_layer, model, scale, kind, index)
        )(x, layer, lora_layer)
    x = _rms_norm(x, params["final_norm"].astype(_F32), model.rms_norm_eps)
    x = x / (model.hidden_size / model.dim_model_base)
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
