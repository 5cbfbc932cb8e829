"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type`` ``nemotron_h``), in float32: layers of ONE sublayer each, placed
by the characters of ``hybrid_override_pattern``: ``M`` Mamba-2, ``*`` attention
without positional encoding, ``E`` ungated relu^2 experts beside a shared one.

Written from the published ``config.json`` and the published descriptions of
the family (Nemotron-H, arXiv:2504.03624; Mamba-2 / state-space duality,
arXiv:2405.21060; DeepSeek-V3's router, arXiv:2412.19437). Every reading the
config does not fix is marked **(A)** where this file decides it and listed
under ``assumed`` in the configuration file; a reader with the model's code
corrects the file, not the mechanism. ``h = RMSNorm(x)`` (eps
``layer_norm_epsilon``) before the layer's one sublayer, the residual after::

    M:  [z | xBC | dt] = W_in h              (A) this order; widths E, E + 2 G N, H
        E = mamba_num_heads x mamba_head_dim (A) NOT expand x hidden
        xBC = silu(b_conv + sum_{i<4} w_i * xBC_{t-3+i})     all E + 2 G N channels, zeros before the row
        [x | B | C] = xBC                    (A) this order; x [H, P], B, C [G, N]
        head h reads group h // (H / G)      (A)
        dt = softplus(dt + dt_bias) a head;  A = -exp(A_log) ONE SCALAR a head
        S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T  in R^{P x N};  y_t = S_t C_t + D x_t
        g = y * silu(z);  g <- RMSNorm over each of the G groups of E / G values, times w   (A) the gate BEFORE the norm
        out = W_out g
    *:  q = W_q h [T, 32, 128];  k, v = W_k h, W_v h [T, 2, 128]
        o = softmax(q k^T / sqrt(128), causal) v;  out = W_o o      (A) no rotary, no bias
    E:  s = sigmoid(h W_g);  top-6 of s + e_score_correction_bias
        w = s[idx] / (sum + 1e-20) * routed_scaling_factor
        out = sum_{k held here} w_k W_down_k relu(W_up_k h)^2 + W_down_s relu(W_up_s h)^2

    x <- x + out;  after the last layer norm_f, then the (untied) head

Here the recurrence runs TOKEN BY TOKEN from a zero state (``lax.scan``, one
token a step: no chunk, no matrix form, no carried state, no tail, no cache,
no kernel) and the attention over the whole row.

**A share.** The model may state ONE CHIP'S share of a layer
(``router_experts`` the published 128 the router scores, ``n_routed_experts``
the experts held, ``expert_shard`` which run of ids): pairs routed to experts
held elsewhere add nothing, here as in the program, and that partial result is
what goes on. The shared expert is computed whole.

**(A) also**: the keys read by nothing (``expand``, ``rope_theta``,
``partial_rotary_factor``, ``time_step_*``, ``rescale_prenorm_residual``,
``residual_in_fp32``, ``num_logits_to_keep``, ``use_mamba_kernels``,
``max_position_embeddings``); the adapter on q, k, v, o of the attention
layers, ``W_in`` and ``W_out`` of the Mamba-2 layers and the shared expert's
two matrices, everything else frozen; a padded token neither decays nor
writes; the weights are seeded.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer (one expert) at a
time; rows run one after another; an attention layer's queries run in blocks
of ``Q_BLOCK``; the recurrence is a scan of scans (``STEP_BLOCK`` tokens inside
what reverse mode recomputes); the vocabulary is projected in pieces with a
running log-sum-exp. Every matmul runs under
``default_matmul_precision("highest")``. Padding may sit anywhere in a row:
the valid tokens are moved to the front first and the results moved back.

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
#: the names ``mixer_types`` holds for M, * and E -> the program's stack names
KINDS = {"mamba-2": "mamba2", "attention-only": "softmax_alone", "moe": "experts"}


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if not kinds or kinds - set(KINDS):
        raise NotImplementedError(
            "perfbench/reference_ssd_moe.py describes a nemotron_h model (Mamba-2 "
            "layers, attention layers without positional encoding and ungated "
            "relu^2 expert layers, one sublayer a layer); another family brings its "
            "own reference module, named by the configuration file"
        )


def held_ids(model) -> list[int]:
    """Ids of the routed experts whose weights are here, in stack order."""
    n = model.n_routed_experts
    first = model.expert_shard * n if model.router_experts else 0
    return list(range(first, first + n))


def _attention_layer(h, valid, layer, lora_layer, model, scale):
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
    return _project(o.reshape(s, heads * hd), layer, lora_layer, "wo", "bo", scale)


def _conv(x, w, bias):
    """x [S, C], w [K, C]: y_t = bias + sum_i w[i] x_{t-K+1+i}, zeros before the row."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return bias + sum(padded[i: i + x.shape[0]] * w[i] for i in range(taps))


def ssd_scan(x, dt, b, c, a, skip, valid=None):
    """The recurrence alone over one row, token by token from a zero state:
    ``x [T, H, P]``, ``dt [T, H]``, ``b, c [T, G, N]``, ``a, skip [H]``, ``valid
    [T]`` bool or None -> (y [T, H, P], the last state [H, P, N]). (A) head
    ``h`` reads group ``h // (H / G)``. Also what the CPU tests hold
    ``ops/ssd.py`` to."""
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    valid = jnp.ones((s,), bool) if valid is None else valid

    def step(state, tok):
        x_t, dt_t, b_t, c_t, ok_t = tok
        b_h = jnp.repeat(b_t, heads // groups, axis=0)  # [H, N]
        c_h = jnp.repeat(c_t, heads // groups, axis=0)
        new = jnp.exp(dt_t * a)[:, None, None] * state + (
            (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        new = jnp.where(ok_t, new, state)  # a padded token is no step
        return new, jnp.einsum("hpn,hn->hp", new, c_h) + skip[:, None] * x_t

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    pad = -s % STEP_BLOCK
    xs = tuple(
        jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
            (-1, STEP_BLOCK) + v.shape[1:])
        for v in (*(v.astype(_F32) for v in (x, dt, b, c)), valid))
    with jax.default_matmul_precision("highest"):
        state, y = jax.lax.scan(
            jax.checkpoint(block), jnp.zeros((heads, p, n), _F32), xs)
    return y.reshape(-1, heads, p)[:s], state


def _mamba2_layer(h, valid, layer, lora_layer, model, scale):
    s = h.shape[0]
    heads, p, groups, n = (model.ssd_heads, model.ssd_head_dim, model.ssd_groups,
                           model.mamba_d_state)
    inner = heads * p  # (A) not expand x hidden
    zxd = _project(h, layer, lora_layer, "w_in", None, scale)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner: 2 * inner + 2 * groups * n],
                  zxd[:, 2 * inner + 2 * groups * n:])  # (A) [z | xBC | dt]
    ok = valid.astype(_F32)[:, None]
    xbc = jax.nn.silu(_conv(xbc * ok, layer["conv"].astype(_F32),
                            layer["b_conv"].astype(_F32)))
    x = xbc[:, :inner].reshape(s, heads, p)  # (A) [x | B | C]
    b = xbc[:, inner: inner + groups * n].reshape(s, groups, n)
    c = xbc[:, inner + groups * n:].reshape(s, groups, n)
    dt = jax.nn.softplus(dt + layer["dt_bias"].astype(_F32))
    a = -jnp.exp(layer["A_log"].astype(_F32))
    y, _ = ssd_scan(x, dt, b, c, a, layer["ssd_d"].astype(_F32), valid)
    g = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, inner // groups)  # (A)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + model.rms_norm_eps)
    g = g.reshape(s, inner) * layer["gate_norm"].astype(_F32)
    return _project(g, layer, lora_layer, "w_out", None, scale)


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
    w = w / (w.sum(-1, keepdims=True) + 1e-20)  # norm_topk_prob is true
    return w * model.routed_scaling_factor


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up.astype(_F32))) @ down.astype(_F32)


def routed_part(h, layer, model):
    """The held experts' part of ``sum_e combine[:, e] E_e(h)``. The experts'
    two stacks may be every layer's (``layer["experts_layer"]`` then says which
    is this one's): an expert is taken out of the stack where it is used, one
    at a time, and no layer's experts are ever copied out whole."""
    comb = combine_matrix(h, layer, model)[:, jnp.asarray(held_ids(model))]
    at = layer.get("experts_layer")
    stacks = [layer[name] for name in ("experts_up", "experts_down")]

    def one(y, per_expert):
        e, w = per_expert
        up, down = (x[e] if at is None else x[at, e] for x in stacks)
        return y + w[:, None] * _relu2(h, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                        (jnp.arange(comb.shape[1]), comb.T))
    return y


def _expert_layer(h, valid, layer, lora_layer, model, scale):
    y = routed_part(h, layer, model)
    if "w_up" in layer:  # the shared expert, with its adapter, added unweighted
        up = jnp.square(jax.nn.relu(_project(h, layer, lora_layer, "w_up", None, scale)))
        y = y + _project(up, layer, lora_layer, "w_down", None, scale)
    return y


_LAYERS = {"mamba2": ("attn_norm", _mamba2_layer),
           "softmax_alone": ("attn_norm", _attention_layer),
           "experts": ("mlp_norm", _expert_layer)}


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
            whole = {k: v for k, v in stack.items() if k.startswith("experts_")}
            layer = take({k: v for k, v in stack.items() if k not in whole})
            if whole:
                layer.update(whole, experts_layer=at)
            norm, sublayer = _LAYERS[kind]
            h = _rms_norm(x, layer[norm].astype(_F32), model.rms_norm_eps)
            return x + sublayer(h, valid, layer,
                                None if lora_stack is None else take(lora_stack),
                                model, scale)

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
