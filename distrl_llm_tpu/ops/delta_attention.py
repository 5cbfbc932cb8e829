"""A gated delta rule with a per-channel decay (linear attention whose state
forgets channel by channel and overwrites what a key already holds), two
forms, and the short causal convolution that feeds it.

Per head, with ``a_t = exp(g_t)`` in ``(0, 1]^{D_k}`` and ``beta_t`` in
``[0, 2)``::

    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T    [D_k, D_v] float32
    o_t = S_t^T q_t

``delta_chunked`` runs a whole sequence in chunks of ``chunk`` tokens (prefill,
its segments, training; plain ``jnp`` under a ``lax.scan``, so reverse mode is
JAX's own), ``delta_step`` one token (decode). Both take and return the state,
so a prompt prefilled in segments and then decoded token by token is one
recurrence.

**The chunked form.** Write ``G_t`` for the running sum of ``g`` inside the
chunk and ``u_t = beta_t (v_t - S_{t-1}^T (a_t * k_t))`` for what token ``t``
writes; then ``S_t = diag(exp G_t) S_0 + sum_{j<=t} (k_j * exp(G_t - G_j)) u_j^T``
and the ``u`` of a chunk solve one unit lower triangular system a head::

    A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])        i > j
    (I + diag(beta) A) U = diag(beta) (V - (K * exp G) S_0)
    O    = (Q * exp G) S_0 + tril(B) U,   B_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])
    S_C  = diag(exp G_C) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

Every decay factor is ``exp`` of a non-positive number: the pairwise
``exp(G_i - G_j)`` with ``i >= j`` is formed from the difference, never as
``exp(G_i) * exp(-G_j)``, so nothing overflows however fast a channel forgets.
That costs a ``[chunk, chunk, D_k]`` product a head in place of a matmul; the
chunk body is rematerialised in reverse mode, so a sequence keeps a state a
chunk and not that product.

Padding: a token whose ``valid`` is 0 is no step at all. It neither decays
(``g = 0``) nor writes (``beta = 0``, ``k = 0``), so the state after a
right-padded prompt is the state at its last real token.

The products run in float32 at the highest precision, as
``ops/linear_attention.py``'s do: the state is what a long context is
remembered in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
DEFAULT_CHUNK = 64


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_chunked(
    q: jax.Array,  # [B, S, H, Dk]
    k: jax.Array,  # [B, S, H, Dk]
    v: jax.Array,  # [B, S, H, Dv]
    g: jax.Array,  # [B, S, H, Dk] log-decay a channel, <= 0
    beta: jax.Array,  # [B, S, H]
    valid: jax.Array,  # [B, S] 1 = a real token
    state: jax.Array | None = None,  # [B, H, Dk, Dv] float32
    chunk: int = DEFAULT_CHUNK,
) -> tuple[jax.Array, jax.Array]:
    """(o [B, S, H, Dv] in q's type, the state after the last valid token)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    n = -(-s // chunk)
    pad = n * chunk - s
    ok = valid.astype(_F32)

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape((b, n, chunk) + x.shape[2:]).swapaxes(0, 1)

    xs = (
        chunks(q.astype(_F32)),
        chunks(k.astype(_F32) * ok[..., None, None]),
        chunks(v.astype(_F32)),
        chunks(g.astype(_F32) * ok[..., None, None]),
        chunks(beta.astype(_F32) * ok[..., None]),
    )
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=_F32)

    @jax.checkpoint
    def body(s0, x):
        qc, kc, vc, gc, bc = x  # [B, C, H, D] x4, [B, C, H]
        cum = jnp.cumsum(gc, axis=1)  # G_t, [B, C, H, Dk]
        cum_h, q_h, k_h = (x.transpose(0, 2, 1, 3) for x in (cum, qc, kc))  # [B, H, C, Dk]
        # exp(G_i - G_j) for i >= j, formed from the difference: [B, H, i, j, Dk]
        diff = cum_h[:, :, :, None, :] - cum_h[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[None, None, :, :, None], diff, -jnp.inf))
        kd = k_h[:, :, None, :, :] * decay  # k_j * exp(G_i - G_j)
        a = jnp.sum(k_h[:, :, :, None, :] * kd, axis=-1)  # [B, H, i, j]
        bq = jnp.sum(q_h[:, :, :, None, :] * kd, axis=-1)
        into = jnp.exp(cum)  # exp(G_t): decay from the chunk's start, [B, C, H, Dk]
        bh = bc.transpose(0, 2, 1)  # [B, H, C]
        rhs = bh[..., None] * (vc.transpose(0, 2, 1, 3) - jnp.einsum(
            "bchk,bhkd->bhcd", kc * into, s0, precision=_HI))
        system = eye + bh[..., None] * jnp.where(strict, a, 0.0)
        u = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)  # [B, H, C, Dv]
        out = jnp.einsum("bchk,bhkd->bchd", qc * into, s0, precision=_HI) + jnp.einsum(
            "bhij,bhjd->bihd", jnp.where(lower, bq, 0.0), u, precision=_HI)
        left = jnp.exp(cum[:, -1:] - cum)  # exp(G_C - G_j), [B, C, H, Dk]
        new = s0 * jnp.exp(cum[:, -1])[..., None] + jnp.einsum(
            "bjhk,bhjd->bhkd", kc * left, u, precision=_HI)
        return new, out

    if state is None:
        state = jnp.zeros((b, h, dk, dv), _F32)
    state, out = jax.lax.scan(body, state.astype(_F32), xs)
    out = out.swapaxes(0, 1).reshape(b, n * chunk, h, dv)[:, :s]
    return out.astype(q.dtype), state


def delta_step(
    q: jax.Array,  # [B, H, Dk]
    k: jax.Array,
    v: jax.Array,  # [B, H, Dv]
    g: jax.Array,  # [B, H, Dk]
    beta: jax.Array,  # [B, H]
    state: jax.Array,  # [B, H, Dk, Dv] float32
) -> tuple[jax.Array, jax.Array]:
    """One token: (o [B, H, Dv] in q's type, the new state). Multiply and
    reduce rather than a dot, in float32 whatever the backend's matmul
    precision: the decayed state is read for ``S^T k`` and ``S^T q`` in one
    pass and read again to be written with the token's outer product."""
    q32, k32 = q.astype(_F32), k.astype(_F32)
    decayed = state * jnp.exp(g.astype(_F32))[..., None]  # diag(a) S
    seen = jnp.sum(decayed * k32[..., None], axis=-2)  # S^T k, [B, H, Dv]
    read = jnp.sum(decayed * q32[..., None], axis=-2)  # S^T q
    u = beta.astype(_F32)[..., None] * (v.astype(_F32) - seen)
    out = read + u * jnp.sum(q32 * k32, axis=-1, keepdims=True)
    return out.astype(q.dtype), decayed + k32[..., None] * u[..., None, :]


def short_conv(
    x: jax.Array,  # [B, S, C]
    w: jax.Array,  # [K, C]: w[K-1] multiplies the token itself
    valid: jax.Array | None = None,  # [B, S] a run of real tokens a row
    tail: jax.Array | None = None,  # [B, K-1, C]: the K-1 tokens before x
) -> tuple[jax.Array, jax.Array]:
    """A causal depth-wise convolution, one filter a channel, no bias:
    ``y_t = sum_i w[i] x_{t-K+1+i}``. Returns (y [B, S, C], the new tail: the
    last K-1 real tokens' ``x``, zeros where the row has fewer). Padding is
    zeroed before it is summed, so a left-padded row starts from nothing, and
    the tail of a right-padded row ends at its last real token."""
    b, s, c = x.shape
    taps = w.shape[0]
    if valid is not None:
        x = x * valid.astype(x.dtype)[..., None]
    if tail is None:
        tail = jnp.zeros((b, taps - 1, c), x.dtype)
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, S + K-1, C]
    y = sum(full[:, i: i + s] * w[i].astype(x.dtype) for i in range(taps))
    if valid is None:
        return y, full[:, s:]
    # one past the row's last real token: the tail's rows start there in ``full``
    end = jnp.max(jnp.arange(1, s + 1)[None, :] * (valid > 0), axis=1)
    rows = end[:, None] + jnp.arange(taps - 1)[None, :]
    return y, jnp.take_along_axis(full, rows[:, :, None], axis=1)
