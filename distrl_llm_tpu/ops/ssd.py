"""Mamba-2's state-space recurrence (the state-space duality), two forms.

Per head ``h`` of ``H`` with ``P`` channels (``head_dim``) and ``N`` state
columns, ``dt_t > 0`` a head, ``A < 0`` ONE SCALAR a head, ``B_t`` and ``C_t``
``[G, N]`` a token, shared by the ``H / G`` heads of a group (head ``h`` reads
group ``h // (H / G)``)::

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T        S in R^{P x N}, float32
    y_t = S_t C_t + D x_t

**What differs from Mamba-1** (``ops/selective_scan.py``): the decay is a
scalar a head and a token where Mamba-1's is a value a channel AND a column, so
a chunk of tokens is matrix products (below) where Mamba-1's is an
element-wise scan; ``dt`` is a head's (one bias a head, no low-rank pair) and
``B``, ``C`` come straight from ``W_in`` through the convolution (no ``W_x``,
no inner norms); the state is ``[B, H, P, N]``, 64 x 64 x 128 float32 = 2 MiB a
layer a slot at the published sizes, with the 128 state columns along the
TPU's lanes (Mamba-1's ``[N, E]`` keeps its 16 columns on the sublanes): no
padding either way; the gate comes BEFORE a norm over each group's channels
(``gated_group_norm``) where Mamba-1 gates and is done.

It is gated linear attention with keys ``B``, queries ``C``, values ``dt x``
and a scalar log-decay ``dt A`` a head a token. ``ops/linear_attention.py``'s
chunked form does not carry it: its decay is a CONSTANT rate a head times a
count of valid tokens (one ``exp`` of an outer difference of counts), its
state is square with q, k and v a head each, and handing it ``B`` and ``C``
repeated over a group's 8 heads would read and multiply them eight times.
Here a chunk's scores ``C B^T`` are computed once a GROUP and the decays a
head: a file of its own.

``ssd_step`` runs one token a row (decode): multiply and reduce, the state
read once and written once. ``ssd_chunked`` runs a segment or a whole
sequence in chunks of ``chunk`` tokens (the published ``chunk_size`` 128)::

    l_i = sum_{s <= i} dt_s A                     the chunk's running log-decay (<= 0)
    y_i = sum_{j <= i} exp(l_i - l_j) (C_i . B_j) dt_j x_j  +  exp(l_i) S_0 C_i  +  D x_i
    S_c = exp(l_c) S_0 + sum_j exp(l_c - l_j) (dt_j x_j) B_j^T

Both take and return the state, so a prompt prefilled in segments and decoded
token by token is one recurrence. Every ``exp`` is of a non-positive number,
so nothing overflows however fast a head forgets. The products run in float32
at the highest precision, as ``linear_attention``'s do and for its reason. A
chunk's body is rematerialised in reverse mode: an update keeps the chunk
boundaries' states and nothing a token.

Padding: a token whose ``valid`` is 0 is no step at all (``dt = 0``: no decay,
nothing written), so the state after a right-padded row is the state at its
last real token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def gated_group_norm(y: jax.Array, z: jax.Array, weight: jax.Array, groups: int,
                     eps: float) -> jax.Array:
    """``RMSNorm_group(y * silu(z)) * weight``: the gate FIRST, then a norm over
    each of the ``groups`` runs of ``E / groups`` channels. ``y``, ``z``
    ``[..., E]`` -> float32."""
    g = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    by_group = g.reshape(*g.shape[:-1], groups, -1)
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return by_group.reshape(g.shape) * weight.astype(_F32)


def conv_step(x: jax.Array, w: jax.Array, tail: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token of the causal depth-wise convolution over a FLAT tail: ``x [B,
    C]``, ``w [K, C]`` (``w[K-1]`` multiplies the token itself), ``tail [B, (K-1)
    C]`` the K-1 tokens before it, oldest first -> (y [B, C], the new tail). A
    slot's tail is kept flat because a ``[B, K-1, C]`` array has 3 rows on the
    sublanes: the TPU pads them to a tile (five times the bytes in bf16) or the
    compiler moves the rows' axis there and copies the array in and out of
    every step (tests/test_tpu_compile.py). Flat, a tap is a slice of lanes."""
    c = x.shape[-1]
    taps = w.shape[0]
    held = tail.astype(x.dtype)
    y = x * w[taps - 1].astype(x.dtype) + sum(
        held[:, i * c: (i + 1) * c] * w[i].astype(x.dtype) for i in range(taps - 1))
    return y, jnp.concatenate([held[:, c:], x], axis=-1)


def ssd_step(
    x: jax.Array,  # [B, H, P] the convolved, activated input
    dt: jax.Array,  # [B, H] > 0, float32
    b: jax.Array,  # [B, G, N]
    c: jax.Array,  # [B, G, N]
    a: jax.Array,  # [H] < 0
    d: jax.Array,  # [H]
    state: jax.Array,  # [B, H, P, N] float32
) -> tuple[jax.Array, jax.Array]:
    """One token a row: (y [B, H, P] float32, the new state)."""
    bsz, heads, p = x.shape
    groups = b.shape[1]
    x, dt, b, c = (v.astype(_F32) for v in (x, dt, b, c))
    by_group = lambda v: v.reshape(bsz, groups, heads // groups, *v.shape[2:])
    decay = jnp.exp(dt * a.astype(_F32))  # [B, H]
    held = by_group(state)  # [B, G, H/G, P, N]
    new = by_group(decay)[..., None, None] * held + (
        by_group(dt[..., None] * x)[..., None] * b[:, :, None, None, :])
    y = jnp.sum(new * c[:, :, None, None, :], axis=-1).reshape(bsz, heads, p)
    return y + d.astype(_F32)[:, None] * x, new.reshape(state.shape)


def ssd_chunked(
    x: jax.Array,  # [B, T, H, P]
    dt: jax.Array,  # [B, T, H] > 0, float32
    b: jax.Array,  # [B, T, G, N]
    c: jax.Array,  # [B, T, G, N]
    a: jax.Array,  # [H] < 0
    d: jax.Array,  # [H]
    valid: jax.Array | None = None,  # [B, T]: 0 is no step at all
    state: jax.Array | None = None,  # [B, H, P, N] float32, or None: zeros
    *,
    chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """A segment or a whole sequence: (y [B, T, H, P] float32, the state after
    the last valid token). The inputs go into the scan in the types they come
    in and are widened a chunk at a time."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    chunk = min(chunk, t)
    dt = dt.astype(_F32)
    if valid is not None:
        dt = dt * valid.astype(_F32)[..., None]
    if state is None:
        state = jnp.zeros((bsz, heads, p, n), _F32)
    pad = -t % chunk
    # [chunks, B, chunk, ...]; padded tokens are no steps: dt = 0
    xs = tuple(
        jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)).reshape(
            bsz, -1, chunk, *v.shape[2:]).swapaxes(0, 1)
        for v in (x, dt, b, c))
    a, d = a.astype(_F32), d.astype(_F32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(held, piece):
        xc, dtc, bc, cc = (v.astype(_F32) for v in piece)
        log = jnp.cumsum(dtc * a, axis=1)  # [B, C, H]: l_i, <= 0 and falling
        by_head = log.transpose(0, 2, 1)  # [B, H, C]
        # exp(l_i - l_j) for j <= i: the exponent is <= 0 there, 0 elsewhere
        decay = jnp.where(
            lower, jnp.exp(jnp.minimum(by_head[..., :, None] - by_head[..., None, :], 0.0)),
            0.0)  # [B, H, i, j]
        scores = jnp.einsum("bign,bjgn->bgij", cc, bc, precision=_HI)  # a GROUP's, once
        mixed = (scores[:, :, None] * decay.reshape(bsz, groups, per, chunk, chunk)
                 ).reshape(bsz, heads, chunk, chunk)
        moved = dtc[..., None] * xc  # [B, C, H, P]: dt x, the values
        y = jnp.einsum("bhij,bjhp->bihp", mixed, moved, precision=_HI)
        # what the carried state gives token i: exp(l_i) S_0 C_i
        from_state = jnp.einsum(
            "bign,bgkpn->bigkp", cc, held.reshape(bsz, groups, per, p, n),
            precision=_HI).reshape(bsz, chunk, heads, p)
        y = y + jnp.exp(log)[..., None] * from_state + d[:, None] * xc
        # S_c = exp(l_c) S_0 + sum_j exp(l_c - l_j) (dt_j x_j) B_j^T
        left = jnp.exp(log[:, -1:] - log)  # [B, C, H]
        wrote = jnp.einsum(
            "bjgkp,bjgn->bgkpn",
            (left[..., None] * moved).reshape(bsz, chunk, groups, per, p), bc,
            precision=_HI).reshape(bsz, heads, p, n)
        return jnp.exp(log[:, -1])[..., None, None] * held + wrote, y

    state, y = jax.lax.scan(jax.checkpoint(one_chunk), state, xs)
    return y.swapaxes(0, 1).reshape(bsz, -1, heads, p)[:, :t], state
