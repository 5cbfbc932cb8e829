"""Mamba-1's selective state-space recurrence, two forms.

Per channel ``e`` of ``E`` (``d_inner``) and state column ``n`` of ``N``
(``d_state``), with ``dt_t > 0`` a channel, ``A < 0`` a channel and column,
``B_t`` and ``C_t`` a token (shared by every channel)::

    h_t[n, e] = exp(dt_t[e] A[n, e]) h_{t-1}[n, e] + dt_t[e] c_t[e] B_t[n]     float32
    y_t[e]    = sum_n h_t[n, e] C_t[n] + D[e] c_t[e]

The state is held ``[B, N, E]``, the published ``[E, N]`` transposed, and so is
``A``: the 5,120 channels lie along the TPU's 128 lanes and the 16 columns
along its sublanes. Column-minor, a float32 ``[.., 5120, 16]`` array is padded
to 128 lanes, eight times its bytes.

``ssm_step`` runs one token a row (decode), ``ssm_chunked`` a segment or a
whole sequence (prefill, its segments, training, scoring). Both take and return
the state, so a prompt prefilled in segments and then decoded token by token is
one recurrence. Every ``exp`` is of a non-positive number (``dt > 0``,
``A < 0``), so nothing overflows however fast a channel forgets.

**The chunked form** is a scan over chunks of ``chunk`` tokens along T whose
body steps the chunk's tokens one after another from the carried state: the
recurrence is element-wise, a step of B rows is ``B N E`` multiply-adds that
fill the vector unit, and it moves the state once in and once out where a
parallel scan over the chunk's tokens moves a ``[B, chunk, N, E]`` array a
dozen times. The chunk's body is rematerialised in reverse mode (plain
autodiff: no custom rule), so what an update keeps a layer is the chunk
boundaries' states, and the largest temporary is one chunk's states,
``[chunk, B, N, E]`` float32, while that chunk is differentiated. No
``[T, N, E]`` array is ever whole.

Padding: a token whose ``valid`` is 0 is no step at all. It neither decays
(``dt = 0``, so ``exp(0) = 1``) nor writes (``dt c B = 0``), so the state after
a right-padded prompt is the state at its last real token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
DEFAULT_CHUNK = 64


def gate(y: jax.Array, z: jax.Array) -> jax.Array:
    """A Mamba layer's output gate, ``y * silu(z)``, in float32."""
    return y * jax.nn.silu(z.astype(_F32))


def ssm_step(
    c: jax.Array,  # [B, E] the convolved, activated input
    dt: jax.Array,  # [B, E] > 0
    b: jax.Array,  # [B, N]
    cc: jax.Array,  # [B, N]
    a: jax.Array,  # [N, E] < 0
    d: jax.Array,  # [E]
    state: jax.Array,  # [B, N, E] float32
    z: jax.Array | None = None,  # [B, E]: the gate's input
) -> tuple[jax.Array, jax.Array]:
    """One token a row: (y [B, E] float32, the new state). With ``z`` the
    output is gated, ``y * silu(z)``, and cast to ``z``'s type."""
    c, dt, b, cc = (x.astype(_F32) for x in (c, dt, b, cc))
    decay = jnp.exp(dt[:, None, :] * a.astype(_F32))  # [B, N, E]
    state = decay * state + b[:, :, None] * (dt * c)[:, None, :]
    y = jnp.sum(state * cc[:, :, None], axis=1) + d.astype(_F32) * c
    return (y if z is None else gate(y, z).astype(z.dtype)), state


def ssm_chunked(
    c: jax.Array,  # [B, T, E]
    dt: jax.Array,  # [B, T, E] > 0
    b: jax.Array,  # [B, T, N]
    cc: jax.Array,  # [B, T, N]
    a: jax.Array,  # [N, E] < 0
    d: jax.Array,  # [E]
    valid: jax.Array | None = None,  # [B, T]: 0 is no step at all
    state: jax.Array | None = None,  # [B, N, E] float32, or None: zeros
    *,
    z: jax.Array | None = None,  # [B, T, E]: the gate's input
    chunk: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """A segment or a whole sequence: (y [B, T, E], the state after the last
    token); y is float32, or gated and of ``z``'s type as ``ssm_step`` gives
    it. ``chunk`` tokens a chunk (``DEFAULT_CHUNK`` where 0). The inputs go
    into the scan in the types they come in and are widened a token at a time:
    a float32 copy of a 30 x 1,024 x 5,120 segment is 0.6 GB an array."""
    bsz, t, e = c.shape
    n = b.shape[-1]
    chunk = min(chunk or DEFAULT_CHUNK, t)
    if valid is not None:
        dt = dt * valid.astype(dt.dtype)[..., None]
    if state is None:
        state = jnp.zeros((bsz, n, e), _F32)
    xs = (c, dt, b, cc) if z is None else (c, dt, b, cc, z)
    pad = -t % chunk
    if pad:  # padded tokens are no steps: dt = 0
        xs = tuple(jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in xs)
    # [chunks, chunk, B, .]: a token's rows are one slice of the scanned axis
    xs = tuple(x.swapaxes(0, 1).reshape(-1, chunk, bsz, x.shape[-1]) for x in xs)

    def one_token(h, x):
        y, h = ssm_step(*x[:4], a, d, h, *x[4:])
        return h, y

    def one_chunk(h, x):
        return jax.lax.scan(one_token, h, x)

    state, y = jax.lax.scan(jax.checkpoint(one_chunk), state, xs)
    return y.reshape(-1, bsz, e)[:t].swapaxes(0, 1), state
