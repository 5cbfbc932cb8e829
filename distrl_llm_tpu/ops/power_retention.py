"""Power retention of degree 2 (linear attention whose feature map is the
symmetric second power of a key), three forms of one function.

Per query head ``i`` of KV head ``j = i // group``, with a scalar log-decay
``g_t <= 0`` a KV head, ``G_t = sum_{r<=t} g_r`` and ``d`` the head size::

    attention form   a_ts = exp(G_t - G_s) (q_t . k_s / sqrt(d))^2         s <= t
                     o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)
    recurrent form   S_t = e^{g_t} S_{t-1} + phi(k_t / d^{1/4}) v_t^T      [D, d_v] float32
                     z_t = e^{g_t} z_{t-1} + phi(k_t / d^{1/4})            [D]      float32
                     o_t = S_t^T phi(q_t / d^{1/4}) / (z_t . phi(q_t / d^{1/4}) + eps)

``phi: R^d -> R^D``, ``D = d (d + 1) / 2``, is the symmetric second power:
``x_a x_b`` for every unordered pair, ``sqrt(2)`` off the diagonal, so that
``phi(q) . phi(k) = (q . k)^2`` exactly and the two forms are one function. A
state is held ONCE A KV HEAD and read by the ``group`` query heads that share
it (the lightning and delta-rule layers have one state a query head).

**The layout of D** is by wrapped diagonals, because that is what a vector
unit computes without a gather: entry ``o * d + a`` is ``x_a x_{(a + o) mod d}``
for offsets ``o = 0 .. d/2 - 1`` (``o = 0`` the squares, every other offset
each pair once: ``sqrt(2)``), then the half diagonal ``x_a x_{a + d/2}`` for
``a < d/2``. ``d/2`` whole rows of ``d`` lanes and half a row: packed, D =
8,256 at d = 128. ``phi`` is ``d/2 + 1`` lane rotations and a product.

``power_attention`` is the attention form (no state: the plain form the tests
hold the others to). ``power_step`` is one token of the recurrent form
(decode). ``power_chunked`` runs a sequence in chunks of ``chunk`` tokens from
a carried ``(S, z)``: the attention form inside a chunk, the state between
chunks (prefill, its segments, training; plain ``jnp``, so reverse mode is
JAX's own). It goes ONE KV HEAD AT A TIME (``lax.map``): ``phi`` of a chunk's
queries for every head at once is 1.35 GB a prompt at 1,024 x 40 x 8,256.

Padding: a token whose ``valid`` is 0 is no step at all. It neither decays
(``g = 0``) nor writes (``phi(k) = 0``), so the state after a right-padded
prompt is the state at its last real token.

Every decay factor is ``exp`` of a non-positive number, formed from the
difference ``G_t - G_s``. State, normaliser, the gate's cumulation and the
chunk's products are float32 at the highest precision, as
``ops/linear_attention.py``'s are: the state is what a long context is
remembered in. ``dispatch_choices`` records which form of the step each
geometry took ("plain": there is no kernel yet).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
#: tokens of one chunk: the engine's prefill segment, so a segment is one
#: chunk (the attention form inside it, the carried state before it)
DEFAULT_CHUNK = 1024
#: (rows, kv heads, group, head size) -> the form ``power_step`` took
dispatch_choices: dict[tuple[int, int, int, int], str] = {}


def state_dim(d: int) -> int:
    """D: entries of the symmetric second power of a ``d`` vector."""
    return d * (d + 1) // 2


def phi(x: jax.Array) -> jax.Array:
    """The symmetric second power over the last axis, ``[..., d] -> [..., D]``
    float32, in the module's layout. ``d`` is even."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"the head size must be even, got {d}")
    x = x.astype(_F32)
    root2 = jnp.sqrt(_F32(2.0))
    rows = [x * x] + [x * jnp.roll(x, -o, axis=-1) * root2 for o in range(1, d // 2)]
    rows.append(x[..., : d // 2] * x[..., d // 2:] * root2)
    return jnp.concatenate(rows, axis=-1)


def _weights(scores: jax.Array) -> jax.Array:
    """The power: a scaled dot product to the weight it gives (degree 2)."""
    return jnp.square(scores)


def _normalised(num: jax.Array, den: jax.Array, eps: float) -> jax.Array:
    """``num [..., d_v] / (den [...] + eps)``: the sum over the sum of weights."""
    return num / (den[..., None] + eps)


def init_state(rows: int, kv_heads: int, d: int, d_v: int | None = None):
    """An empty ``(S [rows, K, D, d_v], z [rows, K, D])``, float32."""
    big = state_dim(d)
    return (jnp.zeros((rows, kv_heads, big, d_v or d), _F32),
            jnp.zeros((rows, kv_heads, big), _F32))


def power_attention(
    q: jax.Array,  # [B, S, H, d]
    k: jax.Array,  # [B, S, K, d]
    v: jax.Array,  # [B, S, K, d_v]
    g: jax.Array,  # [B, S, K] log-decay a KV head, <= 0
    valid: jax.Array,  # [B, S] 1 = a real token
    eps: float = 1e-6,
) -> jax.Array:
    """The attention form over whole rows: ``o [B, S, H, d_v]`` in q's type."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    ok = valid.astype(_F32)
    cum = jnp.cumsum(g.astype(_F32) * ok[..., None], axis=1)  # G_t, [B, S, K]
    qg = q.astype(_F32).reshape(b, s, kv, h // kv, d)
    scores = jnp.einsum("bikgd,bjkd->bkgij", qg, k.astype(_F32), precision=_HI)
    seen = jnp.tril(jnp.ones((s, s), bool))[None] & (ok[:, None, :] > 0)  # [B, i, j]
    diff = cum.transpose(0, 2, 1)[:, :, :, None] - cum.transpose(0, 2, 1)[:, :, None, :]
    decay = jnp.exp(jnp.where(seen[:, None], diff, -jnp.inf))  # [B, K, i, j]
    a = _weights(scores * _F32(d) ** -0.5) * decay[:, :, None]
    num = jnp.einsum("bkgij,bjkv->bikgv", a, v.astype(_F32), precision=_HI)
    den = a.sum(-1).transpose(0, 3, 1, 2)  # [B, i, K, g]
    return _normalised(num, den, eps).reshape(b, s, h, -1).astype(q.dtype)


def power_step(
    q: jax.Array,  # [B, H, d]
    k: jax.Array,  # [B, K, d]
    v: jax.Array,  # [B, K, d_v]
    g: jax.Array,  # [B, K] log-decay, <= 0
    state: tuple[jax.Array, jax.Array],  # S [B, K, D, d_v], z [B, K, D] float32
    eps: float = 1e-6,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One token: (o [B, H, d_v] in q's type, the new (S, z)). The state is
    decayed and written in one pass; the ``group`` query heads of a KV head
    read its new state together."""
    big, z = state
    b, h, d = q.shape
    kv = k.shape[1]
    dispatch_choices[(b, kv, h // kv, d)] = "plain"
    root = _F32(d) ** -0.25
    a = jnp.exp(g.astype(_F32))
    pk = phi(k.astype(_F32) * root)  # [B, K, D]
    big = big * a[..., None, None] + pk[..., :, None] * v.astype(_F32)[..., None, :]
    z = z * a[..., None] + pk
    pq = phi(q.astype(_F32).reshape(b, kv, h // kv, d) * root)  # [B, K, g, D]
    num = jnp.einsum("bkgd,bkdv->bkgv", pq, big, precision=_HI)
    den = jnp.einsum("bkgd,bkd->bkg", pq, z, precision=_HI)
    out = _normalised(num, den, eps)
    return out.reshape(b, h, -1).astype(q.dtype), (big, z)


def _chunk_of_one_head(carry, x, *, eps: float, use_state: bool):
    """One chunk of one KV head. ``carry``: ``S [B, D, d_v]``, ``z [B, D]``;
    ``x``: ``q [B, C, g, d]`` and ``k [B, C, d]`` (scaled by ``d^-1/4``, k
    zeroed where padded), ``v [B, C, d_v]``, ``cum [B, C]`` (G_t inside the
    chunk). Returns ((S, z) after the chunk, o [B, C, g, d_v])."""
    big, z = carry
    q, k, v, cum = x
    c = q.shape[1]
    scores = jnp.einsum("bigd,bjd->bgij", q, k, precision=_HI)
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower[None], cum[:, :, None] - cum[:, None, :], -jnp.inf))
    a = _weights(scores) * decay[:, None]
    num = jnp.einsum("bgij,bjv->bigv", a, v, precision=_HI)
    den = a.sum(-1).transpose(0, 2, 1)  # [B, C, g]
    if use_state:
        pq = phi(q) * jnp.exp(cum)[:, :, None, None]  # decayed from the chunk's start
        num = num + jnp.einsum("bigd,bdv->bigv", pq, big, precision=_HI)
        den = den + jnp.einsum("bigd,bd->big", pq, z, precision=_HI)
    out = _normalised(num, den, eps)
    total = cum[:, -1]
    pk = phi(k) * jnp.exp(total[:, None] - cum)[..., None]  # [B, C, D]
    fade = jnp.exp(total)
    big = big * fade[:, None, None] + jnp.einsum("bjd,bjv->bdv", pk, v, precision=_HI)
    z = z * fade[:, None] + pk.sum(1)
    return (big, z), out


def power_chunked(
    q: jax.Array,  # [B, S, H, d]
    k: jax.Array,  # [B, S, K, d]
    v: jax.Array,  # [B, S, K, d_v]
    g: jax.Array,  # [B, S, K] log-decay a KV head, <= 0
    valid: jax.Array,  # [B, S] 1 = a real token
    state: tuple[jax.Array, jax.Array] | None = None,
    chunk: int | None = None,
    eps: float = 1e-6,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """(o [B, S, H, d_v] in q's type, (S, z) after the last valid token). With
    no state handed in, the first chunk reads none; a caller that carries
    nothing on (the learner's whole rows) drops the state, and the compiler the
    work of its last chunk's."""
    b, s, h, d = q.shape
    kv, dv = k.shape[2], v.shape[-1]
    group = h // kv
    chunk = min(chunk or DEFAULT_CHUNK, s)
    n = -(-s // chunk)
    pad = n * chunk - s
    ok = valid.astype(_F32)
    root = _F32(d) ** -0.25

    def chunks(x):  # [B, S, K, ...] -> [n, K, B, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 0), 2, 0)

    gated = jnp.pad(g.astype(_F32) * ok[..., None], ((0, 0), (0, pad), (0, 0)))
    gated = gated.reshape(b, n, chunk, kv)
    xs = (
        chunks(q.astype(_F32).reshape(b, s, kv, group, d) * root),
        chunks(k.astype(_F32) * (ok[..., None, None] * root)),
        chunks(v.astype(_F32)),
        jnp.cumsum(gated, axis=2).transpose(1, 3, 0, 2),  # [n, K, B, C]
    )
    carried = state is not None
    if state is None:
        state = init_state(b, kv, d, dv)
    heads_first = lambda st: (st[0].swapaxes(0, 1), st[1].swapaxes(0, 1))  # [K, B, ..]

    def one_chunk(st, x, use_state: bool):
        body = jax.checkpoint(lambda args: _chunk_of_one_head(
            args[0], args[1], eps=eps, use_state=use_state))
        return jax.lax.map(body, (st, x))

    st = heads_first((state[0].astype(_F32), state[1].astype(_F32)))
    first = jax.tree_util.tree_map(lambda a: a[0], xs)
    # the first chunk of a row that starts here reads no state
    st, out = one_chunk(st, first, carried)
    out = out[None]  # [n, K, B, C, g, dv]
    if n > 1:
        rest = jax.tree_util.tree_map(lambda a: a[1:], xs)
        st, more = jax.lax.scan(lambda c, x: one_chunk(c, x, True), st, rest)
        out = jnp.concatenate([out, more], axis=0)
    out = out.transpose(2, 0, 3, 1, 4, 5).reshape(b, n * chunk, h, dv)[:, :s]
    return out.astype(q.dtype), heads_first(st)
