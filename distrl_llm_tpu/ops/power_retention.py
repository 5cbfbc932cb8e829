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
remembered in.

**The one-token step is a dispatch** (``power_step``): on a TPU, for a float32
state of whole 128-lane heads, ONE Mosaic kernel (``power_step_kernel``) brings
a KV head's tile ``S [D, d_v]`` through VMEM once, and in that pass decays it,
adds ``phi(k) v^T``, writes it back to the buffer it came from and reads the
NEW tile out for the ``group`` query heads, phi(k) and phi(q) expanded inside
from the 128-vectors (the layout above is made for that). Elsewhere (the CPU,
small heads, a bf16 state) it is ``power_step_plain``, which XLA lowers to two
passes over the state. ``dispatch_choices`` records which form each geometry
took.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distrl_llm_tpu.ops.per_device import per_device

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
#: tokens of one chunk: the engine's prefill segment, so a segment is one
#: chunk (the attention form inside it, the carried state before it)
DEFAULT_CHUNK = 1024
_LANES = 128  # a float32 VMEM tile is (8, 128): the kernel takes whole tiles of state
_SUBLANES = 8
#: offsets a trip of the kernel's loop over a strip: independent registers for
#: the scheduler to interleave. Timed on a v5e over four layers' states of 32
#: slots x 8 KV heads (PERF.md §6, PR 41): at 1, 3, 7 and 13 the step runs at
#: the rate of a bare copy of the state through VMEM (3.398 / 3.390 / 3.389 /
#: 3.388 ms a layer against 3.383); at 7 the arithmetic alone is half of that
POWER_UNROLL = 7
#: what each geometry's one-token step resolved to, "kernel" or "plain", under
#: ``dispatch_key``: the engines' counter ``ops/power_kernel_steps`` and
#: chip_smoke.py read it, so a run on the plain form cannot pass for the kernel
dispatch_choices: dict[tuple, str] = {}


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


def dispatch_key(kv_heads: int, group: int, d: int, d_v: int, dtype=_F32) -> tuple:
    """The key ``power_step`` records its choice under: everything of the step
    but its rows, which the choice does not depend on (a row-sharded engine
    traces the step at its shard's rows)."""
    return (kv_heads, group, d, d_v, jnp.dtype(dtype).name)


def power_step_impl(state: tuple[jax.Array, jax.Array]) -> str:
    """The form a one-token step over ``state`` (``S [B, K, D, d_v]``, ``z``)
    takes: "kernel" on a TPU backend for a float32 state whose head size and
    ``d_v`` are whole 128-lane tiles, "plain" otherwise. On the TPU nothing
    falls back: a kernel that fails to lower fails the step that called it."""
    big = state[0]
    d_v = big.shape[-1]  # the kernel's tile has the head size on both sides
    whole = d_v % _LANES == 0 and big.shape[-2] == state_dim(d_v)
    if jax.default_backend() == "tpu" and big.dtype == _F32 and whole:
        return "kernel"
    return "plain"


def power_step(
    q: jax.Array,  # [B, H, d]
    k: jax.Array,  # [B, K, d]
    v: jax.Array,  # [B, K, d_v]
    g: jax.Array,  # [B, K] log-decay, <= 0
    state: tuple[jax.Array, jax.Array],  # S [B, K, D, d_v], z [B, K, D] float32
    eps: float = 1e-6,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One token: (o [B, H, d_v] in q's type, the new (S, z)), by the form
    ``power_step_impl`` names; the choice is recorded in ``dispatch_choices``."""
    impl = power_step_impl(state)
    kv = k.shape[1]
    dispatch_choices[dispatch_key(
        kv, q.shape[1] // kv, q.shape[-1], v.shape[-1], state[0].dtype)] = impl
    if impl == "kernel":
        return per_device(power_step_kernel)(q, k, v, g, *state, eps=eps)
    return power_step_plain(q, k, v, g, state, eps)


def power_step_plain(q, k, v, g, state, eps: float = 1e-6):
    """``power_step`` in plain ``jnp``: the kernel's reference and the path off
    the TPU. The state is decayed and written in one pass and read again by the
    product with phi(q) (two fusions on a TPU: 1.5 x the bytes); the ``group``
    query heads of a KV head read its new state together."""
    big, z = state
    b, h, d = q.shape
    kv = k.shape[1]
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


def _power_step_body(x_ref, s_ref, num_ref, new_ref, col_ref, *, group: int):
    """The kernel over one (row, KV head): the tile ``S [D, d_v]`` comes
    through VMEM once, ``D`` down the sublanes in the module's layout (offset
    ``o``'s rows are ``x_a x_{(a + o) mod d}``, ``a`` down the sublanes).

    ``x_ref [R, d]`` holds the step's vectors as rows: the ``group`` queries
    and the key (scaled by ``d^-1/4``), the value, and the decay ``e^g`` in
    every lane. Queries and key are needed DOWN THE SUBLANES and rotated by
    ``o``, the same in every lane: each is spread over ``d`` rows, transposed
    as a whole tile and stored twice over in ``col_ref [group + 1, 2d, d]``,
    so that a vector rotated by ``o`` is a load at sublane ``a + o``.

    The work goes a strip of 8 sublanes ``a`` at a time over all offsets, so
    that a query head keeps ONE accumulator register a strip: per register of
    state a decay, the write ``k_{a+o} (k_a v)`` and, a query head, one
    multiply by ``q_{a+o}`` and one add; ``q_a``, ``sqrt(2)`` and the sum over
    ``a`` are applied once a strip."""
    d = s_ref.shape[-1]
    half = d // 2
    root2 = math.sqrt(2.0)
    x = x_ref[0, 0]
    for i in range(group + 1):  # queries, then the key
        col = jnp.broadcast_to(x[i:i + 1], (d, d)).T
        col_ref[i, :d] = col
        col_ref[i, d:] = col
    value = jnp.broadcast_to(x[group + 1:group + 2], (_SUBLANES, d))
    decay = jnp.broadcast_to(x[group + 2:group + 3], (_SUBLANES, d))

    def update(row, key_rot, kv):
        """Decay and write one register of state; returns the new register."""
        at = pl.ds(pl.multiple_of(row, _SUBLANES), _SUBLANES)
        new = decay * s_ref[0, 0, at, :] + key_rot * kv
        new_ref[0, 0, at, :] = new
        return new

    def strip(i, total, *, with_half: bool):
        a0 = pl.multiple_of(i * _SUBLANES, _SUBLANES)
        window = lambda j, o: col_ref[j, pl.ds(a0 + o, _SUBLANES), :]
        q_col = [window(j, 0) for j in range(group)]
        k_col = window(group, 0)
        kv = k_col * value
        new = update(a0, k_col, kv) * (1.0 / root2)  # the squares: weight 1
        acc = tuple(c * new for c in q_col)
        kv = kv * root2

        def offset(o, acc):
            new = update(o * d + a0, window(group, o), kv)
            return tuple(s + window(j, o) * new for j, s in enumerate(acc))

        def offsets(block, acc):  # POWER_UNROLL offsets a trip: independent work to interleave
            for u in range(POWER_UNROLL):
                acc = offset(1 + block * POWER_UNROLL + u, acc)
            return acc

        whole = (half - 1) // POWER_UNROLL
        acc = jax.lax.fori_loop(0, whole, offsets, acc)
        for o in range(1 + whole * POWER_UNROLL, half):
            acc = offset(o, acc)
        if with_half:  # the half diagonal: pairs (a, a + d/2) for a < d/2
            acc = offset(half, acc)
        return tuple(t + (c * root2) * s for t, c, s in zip(total, q_col, acc))

    total = tuple(jnp.zeros((_SUBLANES, d), _F32) for _ in range(group))
    strips = half // _SUBLANES
    total = jax.lax.fori_loop(
        0, strips, functools.partial(strip, with_half=True), total)
    total = jax.lax.fori_loop(
        strips, 2 * strips, functools.partial(strip, with_half=False), total)
    for j, t in enumerate(total):
        num_ref[0, 0, j:j + 1] = jnp.sum(t, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def power_step_kernel(q, k, v, g, big, z, *, eps: float = 1e-6,
                      interpret: bool = False):
    """``power_step`` as one Mosaic kernel (a TPU; ``interpret`` for the CPU's
    tests): grid (B, K), a grid step one KV head's float32 tile ``[D, d_v]``,
    read once and written once to the buffer it came from, with phi(k) and
    phi(q) expanded inside. The normaliser (0.8% of the bytes) stays ``jnp``:
    z is read once and written once. Float32 throughout."""
    b, h, d = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    group = h // kv
    root = _F32(d) ** -0.25
    a = jnp.exp(g.astype(_F32))
    q32 = q.astype(_F32).reshape(b, kv, group, d) * root
    k32 = k.astype(_F32) * root
    vectors = jnp.concatenate([
        q32, k32[:, :, None], v.astype(_F32)[:, :, None],
        jnp.broadcast_to(a[..., None, None], (b, kv, 1, d))], axis=2)
    rows = -(-(group + 3) // _SUBLANES) * _SUBLANES  # whole sublane tiles
    vectors = jnp.pad(vectors, ((0, 0), (0, 0), (0, rows - group - 3), (0, 0)))
    heads = -(-group // _SUBLANES) * _SUBLANES
    block = lambda r, c: pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0))
    # a tile in and out, double-buffered (16.9 MB at d = 128), and the columns
    vmem = 4 * (4 * big.shape[2] * dv + 2 * (group + 1) * d * d) + (4 << 20)
    num, big = pl.pallas_call(
        functools.partial(_power_step_body, group=group),
        grid=(b, kv),
        in_specs=[block(rows, d), block(big.shape[2], dv)],
        out_specs=[block(heads, dv), block(big.shape[2], dv)],
        out_shape=[jax.ShapeDtypeStruct((b, kv, heads, dv), _F32),
                   jax.ShapeDtypeStruct(big.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((group + 1, 2 * d, d), _F32)],
        input_output_aliases={1: 1},  # the state is updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(vectors, big)
    pk = phi(k32)
    z = z * a[..., None] + pk
    den = jnp.einsum("bkgd,bkd->bkg", phi(q32), z, precision=_HI)
    out = _normalised(num[:, :, :group], den, eps)
    return out.reshape(b, h, dv).astype(q.dtype), (big, z)


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
