"""Linear attention with a per-head decay (Lightning Attention), two forms.

Per head, with ``lam = exp(-rate)``::

    S_t = lam * S_{t-1} + k_t v_t^T        (a [D, D] state, float32)
    o_t = S_t^T q_t / sqrt(D)

``lightning_chunked`` runs a whole sequence in chunks of ``chunk`` tokens
(prefill and training; plain ``jnp`` under a ``lax.scan``, so reverse mode is
JAX's own), ``lightning_step`` one token (decode). Both take and return the
state, so a prompt prefilled in segments and then decoded token by token is
one recurrence.

Padding: a token whose ``valid`` is 0 is no step at all. Its k and v are
zeroed and it does not decay the state, so the state after a right-padded
prompt is the state at the prompt's last real token, and a left-padded row
starts from zero at its first real token.

Every factor is a decay over a non-negative number of steps (``exp`` of a
non-positive number): nothing is divided by a decay, so nothing overflows
however fast a head forgets. The matmuls run in float32 at the highest
precision: they are a few per cent of a layer's operations, and the state is
what a long context is remembered in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
DEFAULT_CHUNK = 128


def lightning_chunked(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    rates: jax.Array,  # [H] float32 log-decay per valid token (>= 0)
    valid: jax.Array,  # [B, S] 1 = a real token
    state: jax.Array | None = None,  # [B, H, D, D] float32
    chunk: int = DEFAULT_CHUNK,
) -> tuple[jax.Array, jax.Array]:
    """(o [B, S, H, D] in q's type, the state after the last valid token)."""
    b, s, h, d = q.shape
    chunk = min(chunk, s)
    n = -(-s // chunk)
    pad = n * chunk - s
    ok = valid.astype(_F32)
    scale = d**-0.5

    def chunks(x, fill=0.0):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                    constant_values=fill)
        return x.reshape((b, n, chunk) + x.shape[2:]).swapaxes(0, 1)

    xs = (
        chunks(q.astype(_F32) * scale),
        chunks(k.astype(_F32) * ok[..., None, None]),
        chunks(v.astype(_F32) * ok[..., None, None]),
        chunks(ok),
    )
    rates = rates.astype(_F32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(carry, x):
        qc, kc, vc, okc = x  # [B, C, H, D] x3, [B, C]
        cnt = jnp.cumsum(okc, axis=1)  # valid tokens up to and including i
        total = cnt[:, -1]
        steps = cnt[:, :, None] - cnt[:, None, :]  # [B, i, j]: decays from j to i
        decay = jnp.where(
            lower[None, None],
            jnp.exp(-rates[None, :, None, None] * jnp.maximum(steps, 0.0)[:, None]),
            0.0,
        )  # [B, H, i, j]
        scores = jnp.einsum("bihd,bjhd->bhij", qc, kc, precision=_HI) * decay
        intra = jnp.einsum("bhij,bjhd->bihd", scores, vc, precision=_HI)
        from_state = jnp.einsum("bihk,bhkd->bihd", qc, carry, precision=_HI)
        into = jnp.exp(-rates[None, None, :] * cnt[:, :, None])  # [B, C, H]
        out = intra + from_state * into[..., None]
        left = jnp.exp(-rates[None, None, :] * (total[:, None] - cnt)[:, :, None])
        new = carry * jnp.exp(-rates[None, :] * total[:, None])[..., None, None]
        new = new + jnp.einsum(
            "bjhk,bjhd->bhkd", kc * left[..., None], vc, precision=_HI
        )
        return new, out

    if state is None:
        state = jnp.zeros((b, h, d, d), _F32)
    state, out = jax.lax.scan(body, state, xs)
    out = out.swapaxes(0, 1).reshape(b, n * chunk, h, d)[:, :s]
    return out.astype(q.dtype), state


def lightning_step(
    q: jax.Array,  # [B, H, D]
    k: jax.Array,
    v: jax.Array,
    rates: jax.Array,  # [H]
    state: jax.Array,  # [B, H, D, D] float32
) -> tuple[jax.Array, jax.Array]:
    """One token: (o [B, H, D] in q's type, the new state). Multiply and
    reduce rather than a dot: the state is read once and written once, in
    float32, whatever the backend's matmul precision."""
    d = q.shape[-1]
    lam = jnp.exp(-rates.astype(_F32))[None, :, None, None]
    new = state * lam + k.astype(_F32)[..., :, None] * v.astype(_F32)[..., None, :]
    out = jnp.sum(new * (q.astype(_F32) * d**-0.5)[..., :, None], axis=-2)
    return out.astype(q.dtype), new
