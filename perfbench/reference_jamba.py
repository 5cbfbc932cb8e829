"""Plain reference of AI21-Jamba2-3B
(https://huggingface.co/ai21labs/AI21-Jamba2-3B, ``model_type`` ``jamba``), in
float32: Mamba-1 state-space layers beside multi-query attention layers
without RoPE, each followed by a gated SiLU MLP, a tied head, and no positional
encoding anywhere.

Written from the published ``config.json`` and the published descriptions of
the family (Jamba, arXiv:2403.19887; Mamba, arXiv:2312.00752); what the config
leaves open is under ``assumed`` in the configuration file, each with one line,
and is repeated here where this file decides it. ``h = RMSNorm(x)`` before
each half, the residual after; layer ``i`` is attention where
``i % attn_layer_period == attn_layer_offset`` (the program's ``mixer_types``
says which, by the published names "attention" and "mamba")::

    attention:  q = W_q h [T, H, D];  k, v = W_k h, W_v h [T, K, D]   (K = 1: every head reads it)
                o = softmax(q k^T / sqrt(D), causal) v;   y = W_o o

    mamba:      [u, z]      = W_in h                         u first, z second
                c_t         = silu(b_conv + sum_{i<4} w_i * u_{t-3+i})      zeros before the row
                [d, B, C]_t = W_x c_t                        widths dt_rank, d_state, d_state
                d, B, C     <- RMSNorm(d), RMSNorm(B), RMSNorm(C)           eps rms_norm_eps
                dt_t        = softplus(W_dt d_t + b_dt)      [d_inner]
                A           = -exp(A_log)                    [d_inner, d_state]
                h_t         = exp(dt_t A) * h_{t-1} + (dt_t c_t) B_t^T
                y_t         = h_t C_t + D * c_t;   out = W_out (y_t * silu(z_t))

    x <- x + Mixer(h);   x <- x + W_down(silu(W_gate h') * (W_up h'))

Here the recurrence runs TOKEN BY TOKEN from a zero state (``lax.scan``, one
token a step: no chunk, no carried state, no window, no cache, no kernel) and
the attention over the whole row.

**Assumed** (the configuration file says each; a reader with the model's code
corrects the file, not the mechanism): the layer-order rule above;
``head_dim`` 128 = hidden / heads; Jamba's three inner RMSNorms on ``d``, ``B``
and ``C``; ``u`` before ``z`` in ``W_in``'s columns and ``d, B, C`` in
``W_x``'s; softplus after ``W_dt``'s bias; a padded token neither decays nor
writes.

Departures from the published model, each stated in the configuration file:
the adapter is on q, k, v, o of the attention layers, ``W_in`` and ``W_out`` of
the Mamba layers and gate, up, down of every MLP; ``W_x``, ``W_dt``, the
convolution, ``A_log``, ``D`` and the inner norms carry none. The program's
tree holds ``A_log`` as ``[d_state, d_inner]`` (``ssm_a_log``): it is
transposed here to the published ``[d_inner, d_state]``.

Departures for memory, none of which changes a value: weights stay in the type
they are served in and are widened to float32 one layer at a time; rows run one
after another; an attention layer's queries run in blocks of ``Q_BLOCK``; the
recurrence is a scan of scans (``STEP_BLOCK`` tokens inside what reverse mode
recomputes, so that it keeps a state a block and not a token); the vocabulary
is projected in pieces with a running log-sum-exp. Every matmul runs under
``default_matmul_precision("highest")``.

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
KINDS = {"attention": "softmax", "mamba": "mamba"}


def _check_family(model) -> None:
    kinds = set(getattr(model, "mixer_types", None) or ())
    if "mamba" not in kinds or kinds - set(KINDS) or (
            getattr(model, "hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            "perfbench/reference_jamba.py describes a jamba model (Mamba-1 layers "
            "beside attention layers without RoPE, a dense SiLU MLP in every layer); "
            "another family brings its own reference module, named by the "
            "configuration file"
        )


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


def _mamba_layer(h, valid, layer, lora_layer, model, scale):
    inner = model.mamba_expand * model.hidden_size
    cols, rank, eps = model.mamba_d_state, model.mamba_dt_rank, model.rms_norm_eps
    ok = valid.astype(_F32)[:, None]
    uz = _project(h, layer, lora_layer, "w_in", None, scale)
    u, z = uz[:, :inner], uz[:, inner:]
    c = jax.nn.silu(_conv(u * ok, layer["conv"].astype(_F32), layer["b_conv"].astype(_F32)))
    dbc = c @ layer["w_x"].astype(_F32)
    d = _rms_norm(dbc[:, :rank], layer["ssm_dt_norm"].astype(_F32), eps)
    b = _rms_norm(dbc[:, rank: rank + cols], layer["ssm_b_norm"].astype(_F32), eps)
    cc = _rms_norm(dbc[:, rank + cols:], layer["ssm_c_norm"].astype(_F32), eps)
    dt = jax.nn.softplus(d @ layer["w_dt"].astype(_F32) + layer["b_dt"].astype(_F32))
    a = -jnp.exp(layer["ssm_a_log"].astype(_F32)).T  # [d_inner, d_state], as published
    y, _ = ssm_scan(c, dt, b, cc, a, layer["ssm_d"].astype(_F32), valid)
    return _project(y * jax.nn.silu(z), layer, lora_layer, "w_out", None, scale)


def ssm_scan(c, dt, b, cc, a, skip, valid=None):
    """The recurrence alone over one row, token by token from a zero state:
    ``c, dt [T, E]``, ``b, cc [T, N]``, ``a [E, N]``, ``skip [E]``, ``valid
    [T]`` bool or None -> (y [T, E], the last state [E, N]). Also what the CPU
    tests hold ``ops/selective_scan.py`` to."""
    s = c.shape[0]
    valid = jnp.ones((s,), bool) if valid is None else valid

    def step(state, x):
        c_t, dt_t, b_t, cc_t, ok_t = x
        new = jnp.exp(dt_t[:, None] * a) * state + (dt_t * c_t)[:, None] * b_t[None, :]
        new = jnp.where(ok_t, new, state)  # a padded token is no step
        return new, new @ cc_t + skip * c_t

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    pad = -s % STEP_BLOCK
    xs = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (-1, STEP_BLOCK) + x.shape[1:])
        for x in (*(x.astype(_F32) for x in (c, dt, b, cc)), valid))
    with jax.default_matmul_precision("highest"):
        state, y = jax.lax.scan(jax.checkpoint(block), jnp.zeros(a.shape, _F32), xs)
    return y.reshape(-1, c.shape[1])[:s], state


def _layer(x, valid, layer, lora_layer, model, scale, kind: str):
    h = _rms_norm(x, layer["attn_norm"].astype(_F32), model.rms_norm_eps)
    mix = _attention_layer if kind == "softmax" else _mamba_layer
    x = x + mix(h, valid, layer, lora_layer, model, scale)
    h = _rms_norm(x, layer["mlp_norm"].astype(_F32), model.rms_norm_eps)
    gate = jax.nn.silu(_project(h, layer, lora_layer, "w_gate", "b_gate", scale))
    up = _project(h, layer, lora_layer, "w_up", "b_up", scale)
    return x + _project(gate * up, layer, lora_layer, "w_down", "b_down", scale)


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
