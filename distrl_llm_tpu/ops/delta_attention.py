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

**The one-token step** is one algorithm in two forms, chosen by what
``delta_step`` can observe (``delta_step_impl``; no argument, no environment
variable): on a TPU backend, with a float32 state whose ``D_k`` and ``D_v`` are
multiples of 128, ``delta_step_kernel``, a Mosaic kernel that brings each
head's ``[D_k, D_v]`` tile into VMEM once, decays it, reduces it against ``k``,
writes ``diag(a) S + k u^T`` back to the same buffer and reduces that against
``q``; anywhere else (the CPU tests' small heads, a CPU run)
``delta_step_plain``, the same mathematics in ``jnp``, which is also the
kernel's reference. The plain form compiles to two fusions on a TPU, one that
reads the state to reduce it and one that reads it again to write it: 1.5 x the
bytes, 51% of the state's roofline where the kernel's transfers reach 79%
(PERF.md §6, PR 37). ``dispatch_choices`` records which form each geometry took.

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

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distrl_llm_tpu.ops.per_device import per_device

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


#: heads a grid step of the one-token kernel moves: 16 tiles of 128 x 128
#: float32 are 1 MiB in and 1 MiB out, double-buffered 4 MiB of VMEM. Timed on
#: a v5e over three layers' states of 128 slots x 64 heads (PERF.md §6, PR 37):
#: 16, 32 and 64 heads all run at the rate of a bare copy of the state through
#: VMEM; at 8 the grid's fixed cost and the arithmetic show (1.6-11% slower)
DELTA_HEAD_BLOCK = 16
_LANES = 128  # a float32 VMEM tile is (8, 128): the kernel takes whole tiles of state

#: what each geometry's one-token step resolved to, "kernel" or "plain", under
#: ``dispatch_key``: the engines' counter ``ops/delta_kernel_steps`` and
#: chip_smoke.py read it, so a run on the plain form cannot pass for the kernel
dispatch_choices: dict = {}


def dispatch_key(heads: int, dk: int, dv: int, dtype=_F32) -> tuple:
    """The key ``delta_step`` records its choice under: everything of the
    state but its rows, which the choice does not depend on (a row-sharded
    engine traces the step at its shard's rows)."""
    return (heads, dk, dv, jnp.dtype(dtype).name)


def delta_step_impl(state: jax.Array) -> str:
    """The form a one-token step over ``state [B, H, Dk, Dv]`` takes: "kernel"
    on a TPU backend for a float32 state of whole 128-lane tiles, "plain"
    otherwise. On the TPU nothing falls back: a kernel that fails to lower
    fails the step that called it."""
    dk, dv = state.shape[-2:]
    whole = dk % _LANES == 0 and dv % _LANES == 0
    if jax.default_backend() == "tpu" and state.dtype == _F32 and whole:
        return "kernel"
    return "plain"


def delta_step(
    q: jax.Array,  # [B, H, Dk]
    k: jax.Array,
    v: jax.Array,  # [B, H, Dv]
    g: jax.Array,  # [B, H, Dk]
    beta: jax.Array,  # [B, H]
    state: jax.Array,  # [B, H, Dk, Dv] float32
) -> tuple[jax.Array, jax.Array]:
    """One token: (o [B, H, Dv] in q's type, the new state), by the form
    ``delta_step_impl`` names; the choice is recorded in ``dispatch_choices``."""
    impl = delta_step_impl(state)
    dispatch_choices[dispatch_key(*state.shape[1:], state.dtype)] = impl
    if impl == "kernel":
        return per_device(delta_step_kernel)(q, k, v, g, beta, state)
    return delta_step_plain(q, k, v, g, beta, state)


def delta_step_plain(q, k, v, g, beta, state) -> tuple[jax.Array, jax.Array]:
    """``delta_step`` in plain ``jnp``: the kernel's reference and the path off
    the TPU. Multiply and reduce rather than a dot, in float32 whatever the
    backend's matmul precision."""
    q32, k32 = q.astype(_F32), k.astype(_F32)
    decayed = state * jnp.exp(g.astype(_F32))[..., None]  # diag(a) S
    seen = jnp.sum(decayed * k32[..., None], axis=-2)  # S^T k, [B, H, Dv]
    read = jnp.sum(decayed * q32[..., None], axis=-2)  # S^T q
    u = beta.astype(_F32)[..., None] * (v.astype(_F32) - seen)
    out = read + u * jnp.sum(q32 * k32, axis=-1, keepdims=True)
    return out.astype(q.dtype), decayed + k32[..., None] * u[..., None, :]


def _delta_step_body(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, o_ref, new_ref):
    """The kernel over one row's block of heads. A head's tile has ``Dk`` on
    sublanes and ``Dv`` on lanes, so ``a``, ``k`` and ``q`` (rows of ``Dk``
    lanes as they arrive) are needed down the sublanes: each is spread over
    ``Dv`` rows and transposed as a whole tile, on the transpose unit, beside
    the vector unit's multiplies and whole-register adds over ``Dk``."""
    heads, dk, dv = s_ref.shape[1:]
    q, k, a = q_ref[0], k_ref[0], jnp.exp(g_ref[0])  # [heads, Dk]
    v, beta = v_ref[0], beta_ref[0]  # [heads, Dv]; beta the same in every lane
    for h in range(heads):
        down = lambda x: jnp.broadcast_to(x[h:h + 1], (dv, dk)).T  # x[h] a column, [Dk, Dv]
        k_col = down(k)
        decayed = s_ref[0, h] * down(a)  # diag(a) S
        seen = jnp.sum(decayed * k_col, axis=0, keepdims=True)  # S^T k, [1, Dv]
        u = beta[h:h + 1] * (v[h:h + 1] - seen)
        new = decayed + k_col * u
        new_ref[0, h] = new
        # S_t^T q, which is S^T q + u (q . k)
        o_ref[0, h:h + 1] = jnp.sum(new * down(q), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_step_kernel(q, k, v, g, beta, state, *, interpret: bool = False):
    """``delta_step`` as one Mosaic kernel (a TPU; ``interpret`` for the CPU's
    tests): grid (B, ceil(H / DELTA_HEAD_BLOCK)), a grid step one row's block of
    heads, each head's float32 tile read once and written once to the buffer it
    came from. Float32 throughout (the state arrives float32); a last block
    past ``H`` computes on padding that is never written."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    heads = min(DELTA_HEAD_BLOCK, -(-h // 8) * 8)  # whole sublane tiles of the vectors
    vec = lambda d: pl.BlockSpec((1, heads, d), lambda i, j: (i, j, 0))
    tile = pl.BlockSpec((1, heads, dk, dv), lambda i, j: (i, j, 0, 0))
    f32 = lambda x: x.astype(_F32)
    o, new = pl.pallas_call(
        _delta_step_body,
        grid=(b, -(-h // heads)),
        in_specs=[vec(dk), vec(dk), vec(dv), vec(dk), vec(dv), tile],
        out_specs=[vec(dv), tile],
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        input_output_aliases={5: 1},  # the state is updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(f32(q), f32(k), f32(v), f32(g),
      jnp.broadcast_to(f32(beta)[..., None], (b, h, dv)), state)
    return o.astype(q.dtype), new


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
