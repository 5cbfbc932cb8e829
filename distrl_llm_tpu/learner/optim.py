"""Adam with blockwise 8-bit quantized moment state, as an Optax transform.

TPU-native equivalent of the reference's ``bnb.optim.Adam8bit``
(distributed_actor.py:209–211, :432–434 — SURVEY §2b N4): both Adam moments are
stored int8 with per-block absmax scales (block = 256 elements, matching
bitsandbytes' blockwise quantization granularity), dequantized for the update
and requantized after. For LoRA-sized states the memory win is modest, but the
transform works for full-rank fine-tuning too.

Moment codes are DYNAMIC (exponent + linear fraction), not linear. Linear
absmax codes round any element below 1/254 of its block's max to ZERO — for
the second moment that turns ``1/(sqrt(nu)+eps)`` into ``1/eps`` and the Adam
step explodes by ~1e8·lr (observed as adapter weights at 1e6 in an RL
training run; this is why bitsandbytes uses its "dynamic" quantization map
for optimizer state). The dynamic code splits the 127 magnitude levels across
7 decades with 2^(6−d) linear fractions in decade d: ~0.7% relative error
near the block max (where most moment mass sits), coarser but NEVER ZERO down
to 1e-7·blockmax — so a denominator can be off by a bounded factor but can
never collapse to eps.

Code m in 1..127 sits in decade d = 6 − floor(log2 m), at position
j = m − 2^(6−d) of that decade's 2^(6−d) levels; code 0 is zero.

The quantize/dequantize round-trip runs inside the jitted update, and it has to
be element-wise there: a TPU has no fast per-element gather. Written as a table
search and a table lookup it cost 127 ns an element on the v5e, 5.16 s of a
6.50 s update over a rank-32 adapter's 40.4M elements (ledger, PR 24). As
selects between constants (``_select``) the same codes cost 0.35 ns an element,
0.014 s of a 1.35 s update (PERF.md, PR 25).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distrl_llm_tpu import telemetry

BLOCK = 256


def _dynamic_table() -> np.ndarray:
    """127 ascending magnitudes in (0, 1]: decade d (values f·10^−d,
    f ∈ [0.1, 1)) gets 2^(6−d) linear fraction levels — 64 in the top decade
    down to a single level at 1e-7. The max (1.0) is exactly representable so
    each block's absmax round-trips bit-exact."""
    mags: list[float] = []
    for d in range(7):
        n = 2 ** (6 - d)
        if d == 0:
            fr = np.linspace(0.1, 1.0, n)  # include 1.0
        else:
            fr = np.linspace(0.1, 1.0, n, endpoint=False)
        mags.extend((fr * 10.0**-d).tolist())
    table = np.sort(np.asarray(mags, np.float64))
    assert table.shape == (127,) and table[-1] == 1.0
    return table


_TABLE = _dynamic_table()
# decision boundaries: below mid(0) → code 0 (zero); else nearest table entry
_MIDS = np.concatenate(([_TABLE[0] / 2.0], (_TABLE[:-1] + _TABLE[1:]) / 2.0))
_LUT = np.concatenate(([0.0], _TABLE)).astype(np.float32)  # code → magnitude


@dataclass
class _Quantized:
    """int8 payload + per-block absmax scale; flat layout with tail padding.
    ``size``/``shape`` are static pytree aux data, not traced leaves."""

    q: jax.Array  # int8 [nblocks * BLOCK]
    scale: jax.Array  # f32 [nblocks]
    size: int  # original element count (static)
    shape: tuple  # original shape (static)


jax.tree_util.register_pytree_node(
    _Quantized,
    lambda z: ((z.q, z.scale), (z.size, z.shape)),
    lambda aux, children: _Quantized(children[0], children[1], aux[0], aux[1]),
)


_MIDS32 = _MIDS.astype(np.float32)


def _select(table: np.ndarray, bits: list[jax.Array]) -> jax.Array:
    """``table[i]`` per element, ``i`` given by its boolean ``bits`` (least
    significant first; ``len(table) == 2 ** len(bits)``), as a tree of
    ``len(table) - 1`` selects between constants: element-wise and exact,
    where indexing the table is a per-element gather."""
    level = list(table)
    for bit in bits:
        level = [jnp.where(bit, hi, lo) for lo, hi in zip(level[0::2], level[1::2])]
    (value,) = level
    return value


# jitted so that the few hundred selects are traced once per leaf shape and
# not once per leaf and moment. Inlined while the update is traced: a call
# left in the program keeps XLA from fusing across it (3x the codec's time)
_traced_once = partial(jax.jit, inline=True)


@_traced_once
def _magnitude(code: jax.Array) -> jax.Array:
    """The magnitude ``_LUT`` holds for each code in 0..127."""
    return _select(_LUT, [(code & (1 << k)) != 0 for k in range(7)])


@_traced_once
def _count_boundaries_at_or_below(r: jax.Array) -> jax.Array:
    """How many of the 127 ascending boundaries are ``<= r``: the bisection a
    sorted search makes, unrolled into 7 compares, each against the boundary
    that the earlier outcomes pick (``_select``). ``~(r < mid)`` rather than
    ``r >= mid`` sends a NaN right at every level, as that search does."""
    right: list[jax.Array] = []  # the outcomes, most significant first
    for k in range(7):
        half = 1 << (6 - k)
        mid = _select(_MIDS32[half - 1 :: 2 * half], right[::-1])
        right.append(~(r < mid))
    return sum(jnp.where(b, 1 << (6 - k), 0) for k, b in enumerate(right))


@jax.named_scope(telemetry.LEARNER_OPTIMIZER_CODEC)
def _quantize(x: jax.Array) -> _Quantized:
    """Signed dynamic code: q = sign·m, m ∈ {0..127} indexing ``_LUT``."""
    flat = x.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1)
    safe = jnp.where(scale > 0, scale, 1.0)[:, None]
    r = jnp.abs(blocks) / safe
    m = _count_boundaries_at_or_below(r)
    q = (jnp.sign(blocks) * m.astype(jnp.float32)).astype(jnp.int8)
    return _Quantized(q.reshape(-1), scale, size, tuple(x.shape))


@jax.named_scope(telemetry.LEARNER_OPTIMIZER_CODEC)
def _dequantize(z: _Quantized, dtype=jnp.float32) -> jax.Array:
    q = z.q.reshape(-1, BLOCK).astype(jnp.int32)
    mag = _magnitude(jnp.abs(q))
    val = jnp.sign(q.astype(jnp.float32)) * mag * z.scale[:, None]
    return val.astype(dtype).reshape(-1)[: z.size].reshape(z.shape)


# bump when the int8 code semantics change (v2 = dynamic LUT + sqrt-nu
# storage; v1 was linear absmax over raw nu). The version leaf makes a resume
# from an incompatible checkpoint fail LOUDLY at restore (tree-structure /
# value mismatch) instead of silently mis-decoding the moment payloads.
STATE_FORMAT = 2


class Adam8bitState(NamedTuple):
    count: jax.Array
    mu: dict
    nu: dict  # stores sqrt(nu) — see adam8bit docstring
    code_version: jax.Array  # == STATE_FORMAT


def adam8bit(
    learning_rate: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    clip_normalized: float = 5.0,
) -> optax.GradientTransformation:
    """Adam(lr) with int8 blockwise moment state. Defaults match
    bnb.optim.Adam8bit's (the reference passes only lr).

    Two hardening choices beyond bnb, both motivated by an observed RL
    blowup (see module docstring):

    * the second moment is stored as ``sqrt(nu)`` — squaring on dequant
      doubles the code's dynamic range in nu-space (grad ratios down to
      1e-7 of the block max stay representable, vs 3e-4 if nu were stored
      directly);
    * the normalized update ``mu_hat/(sqrt(nu_hat)+eps)`` is clipped to
      ``±clip_normalized`` (exact Adam keeps it near ±1, so 5.0 never binds
      on healthy steps) — the backstop for elements whose second moment
      still quantizes to zero, where the step would otherwise be
      ``mu_hat/eps ~ 1e8``.
    """

    def init_fn(params):
        def zeros(p):  # what _quantize makes of zeros, without running it op by op
            nblocks = -(-p.size // BLOCK)
            return _Quantized(
                jnp.zeros(nblocks * BLOCK, jnp.int8), jnp.zeros(nblocks, jnp.float32),
                p.size, tuple(p.shape),
            )

        return Adam8bitState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params),
            code_version=jnp.asarray(STATE_FORMAT, jnp.int32),
        )

    def update_fn(updates, state, params=None):
        count = state.count + 1
        def upd(g, mu_q, nu_q):
            g = g.astype(jnp.float32)
            mu = b1 * _dequantize(mu_q) + (1 - b1) * g
            nu = b2 * jnp.square(_dequantize(nu_q)) + (1 - b2) * g * g
            mu_hat = mu / (1 - b1 ** count.astype(jnp.float32))
            nu_hat = nu / (1 - b2 ** count.astype(jnp.float32))
            normalized = jnp.clip(
                mu_hat / (jnp.sqrt(nu_hat) + eps),
                -clip_normalized, clip_normalized,
            )
            step = -learning_rate * normalized
            return step, _quantize(mu), _quantize(jnp.sqrt(nu))

        flat_u, treedef = jax.tree_util.tree_flatten(updates)
        flat_mu = treedef.flatten_up_to(state.mu)
        flat_nu = treedef.flatten_up_to(state.nu)
        out = [upd(g, m, n) for g, m, n in zip(flat_u, flat_mu, flat_nu)]
        steps = treedef.unflatten([o[0] for o in out])
        new_mu = treedef.unflatten([o[1] for o in out])
        new_nu = treedef.unflatten([o[2] for o in out])
        steps = jax.tree_util.tree_map(
            lambda s, g: s.astype(g.dtype), steps, updates
        )
        return steps, Adam8bitState(
            count=count, mu=new_mu, nu=new_nu,
            code_version=state.code_version,
        )

    return optax.GradientTransformation(init_fn, update_fn)


def check_state_format(opt_state) -> None:
    """Raise if a (restored) 8-bit Adam state's code version differs from
    this build's ``STATE_FORMAT`` — same-structure format changes would
    otherwise restore cleanly and silently mis-decode the moment payloads
    (different-structure changes already fail at Orbax restore)."""
    if isinstance(opt_state, Adam8bitState):
        got = int(opt_state.code_version)
        if got != STATE_FORMAT:
            raise ValueError(
                f"checkpointed 8-bit Adam state is format v{got}; this build "
                f"reads v{STATE_FORMAT} — restart without resume (the moment "
                "payloads are not decodable across formats)"
            )


def make_optimizer(lr: float, use_8bit: bool = True) -> optax.GradientTransformation:
    """The learner optimizer: Adam(lr), 8-bit state by default (reference:
    Adam8bit with no weight decay — distributed_actor.py:209–211)."""
    return adam8bit(lr) if use_8bit else optax.adam(lr)
