"""Fused quantized-matmul Pallas kernel: dequant on the operand read, LoRA in
the epilogue.

Decode is memory-bound (~2% MFU, ≈ 4% of the HBM roofline in the round-5
chip rows), so
tok/s/chip tracks resident bytes per token almost linearly.  The container
path in ``ops/linear.py`` *hopes* XLA fuses ``(q·scale).astype → einsum`` into
the MXU operand read; this module replaces the hope with a measured kernel for
decode shapes:

* the int8/int4 payload is streamed from HBM at storage width and dequantized
  **in VMEM** per (K-block, N-tile): ``w = (q · scale).astype(x.dtype)`` right
  before the ``jnp.dot`` — the weight never exists at bf16 width in HBM;
* the **LoRA delta rides the epilogue**: ``((x@A)@B)·scale`` is accumulated
  into the same output tile, so the adapter path costs no extra output
  round-trip and no separate kernel launch (the reference runs NF4 base +
  fp16 LoRA as two CUDA paths; here they are one program);
* the math ORDER mirrors the container path exactly — dequant in f32, cast to
  the activation dtype, single full-K contraction, then ``(dot + bias) +
  delta`` — so greedy decode through the kernel is bit-identical to the
  XLA-container path (pinned by tools/quant_smoke.py and
  tests/test_quant_matmul.py).

Dispatch: ``DISTRL_QUANT_MATMUL`` = ``auto`` (kernel on a TPU backend;
container path elsewhere — the CPU tier-1 default, byte-identical to before
this module),
``kernel`` (force; implies interpret off-TPU), ``interpret`` (Pallas
interpreter — CPU parity tests), ``xla`` (pin the container path).

Gradients: the kernel is wrapped in a ``jax.custom_vjp`` whose backward runs
``jax.vjp`` over the *reference* math, so the learner's QLoRA step (grads
through dequant into LoRA only — tests/test_quant.py) differentiates through
`linear`/`_proj` unchanged whichever path dispatched.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.ops.per_device import per_device

#: trace-time dispatch record (the ops.paged.dispatch_choices idiom): keyed by
#: (bits, K, N, rank, dtype) → "kernel" | "xla", so that a run claiming the
#: fused path can be shown not to have measured the container path
dispatch_choices: dict = {}

MODES = ("auto", "kernel", "interpret", "xla")


def quant_matmul_mode() -> str:
    """Resolved DISTRL_QUANT_MATMUL mode (validated; default "auto")."""
    mode = os.environ.get("DISTRL_QUANT_MATMUL", "auto")
    if mode not in MODES:
        raise ValueError(
            f"DISTRL_QUANT_MATMUL must be one of {MODES}, got {mode!r}"
        )
    return mode


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _kernel_body(x_ref, q_ref, s_ref, *rest,
                 out_dtype, has_bias: bool, has_lora: bool,
                 lora_scale: float):
    """One (bm, bn) output tile: full-K dequant-matmul + optional bias +
    optional LoRA epilogue.

    The contraction is ONE ``jnp.dot`` over the whole K (not a K-block
    accumulation loop): decode-shape weights fit VMEM at int width, and a
    single dot keeps the per-element reduction order identical to the
    container path's einsum — the bit-identity contract."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    a_ref = rest.pop(0) if has_lora else None
    b_ref = rest.pop(0) if has_lora else None
    o_ref = rest.pop(0)

    x = x_ref[...]  # [bm, K]
    q3 = q_ref[...]  # [G, g, bn] int8/int4
    sc = s_ref[...]  # [G, 1, bn] f32
    gdim, g, bn = q3.shape
    # dequant exactly as the container path: q·scale in f32 (bf16-rounding
    # the scales would stack ~0.4% error), ONE cast to the activation dtype
    w = (q3.astype(jnp.float32) * sc).astype(x.dtype).reshape(gdim * g, bn)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(out_dtype)
    if has_bias:
        y = y + bias_ref[...].astype(out_dtype)
    if has_lora:
        # LoRA epilogue, in lora_delta's exact dtype discipline: factors cast
        # to the activation dtype, delta never widens the residual stream
        a = a_ref[...].astype(x.dtype)  # [K, r]
        b = b_ref[...].astype(x.dtype)  # [r, bn]
        xa = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
        xab = jnp.dot(xa, b, preferred_element_type=jnp.float32).astype(x.dtype)
        y = y + (xab * jnp.asarray(lora_scale, x.dtype)).astype(out_dtype)
    o_ref[...] = y


def _kernel_call(x2, q, scale, bias, a, b, lora_scale: float,
                 *, interpret: bool):
    """Padded pallas_call over a [M, K] × container[K→G·g, N] matmul."""
    m, k = x2.shape
    gdim, g, n = q.shape
    out_dtype = x2.dtype

    bn = 128
    bm = 128 if m >= 128 else _round_up(m, 8)
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    if np_ != n:
        # zero q/scale/bias/b columns dequantize to exact zeros — the padded
        # tail never contaminates real columns and is sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, np_ - n)))
        scale = jnp.pad(scale, ((0, 0), (0, 0), (0, np_ - n)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, np_ - n),))
        if b is not None:
            b = jnp.pad(b, ((0, 0), (0, np_ - n)))

    has_bias = bias is not None
    has_lora = a is not None
    grid = (mp // bm, np_ // bn)
    in_specs = [
        pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((gdim, g, bn), lambda i, j: (0, 0, j)),
        pl.BlockSpec((gdim, 1, bn), lambda i, j: (0, 0, j)),
    ]
    operands = [x2, q, scale]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        operands.append(bias.reshape(1, np_))
    if has_lora:
        r = a.shape[-1]
        in_specs.append(pl.BlockSpec((k, r), lambda i, j: (0, 0)))
        in_specs.append(pl.BlockSpec((r, bn), lambda i, j: (0, j)))
        operands.extend([a, b])

    # this call is traced inline under the decoder's ``model/*`` scopes, and
    # the TPU compiler names a custom call after its innermost scope: with
    # this one the kernel reads ``%quant_matmul`` in a trace, not ``%mlp``
    with jax.named_scope(telemetry.KERNEL_QUANT_MATMUL):
        out = pl.pallas_call(
            functools.partial(
                _kernel_body, out_dtype=out_dtype, has_bias=has_bias,
                has_lora=has_lora, lora_scale=lora_scale,
            ),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
            interpret=interpret,
        )(*operands)
    return out[:m, :n]


def _reference(x2, q, scale, bias, a, b, lora_scale):
    """The exact XLA-container math (ops/linear.py + lora_delta), flattened
    to the kernel's argument list — the fallback path AND the custom-VJP
    backward's primal."""
    gdim, g, n = q.shape
    w = (q.astype(jnp.float32) * scale).astype(x2.dtype).reshape(gdim * g, n)
    y = jnp.einsum("mi,io->mo", x2, w)
    if bias is not None:
        y = y + bias
    if a is not None:
        ac = a.astype(x2.dtype)
        bc = b.astype(x2.dtype)
        y = y + (x2 @ ac @ bc) * jnp.asarray(lora_scale, x2.dtype)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _quant_matmul_p(x2, q, scale, bias, a, b, lora_scale, interpret):
    return _kernel_call(x2, q, scale, bias, a, b, lora_scale,
                        interpret=interpret)


def _qmm_fwd(x2, q, scale, bias, a, b, lora_scale, interpret):
    out = _kernel_call(x2, q, scale, bias, a, b, lora_scale,
                       interpret=interpret)
    return out, (x2, q, scale, bias, a, b)


def _qmm_bwd(lora_scale, interpret, res, g_out):
    # backward through the REFERENCE math: standard XLA matmul grads (dx,
    # dbias, dA, dB; int payloads get float0) — QLoRA trains LoRA only, so
    # a Pallas backward kernel would buy nothing the forward didn't
    del interpret
    x2, q, scale, bias, a, b = res
    _, vjp = jax.vjp(
        lambda *args: _reference(*args, lora_scale), x2, q, scale, bias, a, b
    )
    return vjp(g_out)


_quant_matmul_p.defvjp(_qmm_fwd, _qmm_bwd)


def quant_matmul_dispatch() -> tuple[bool, bool]:
    """(use_kernel, interpret) for this call, per DISTRL_QUANT_MATMUL.

    "auto" is the kernel on a TPU backend and the container path on any
    other (CPU/tier-1 keeps the container path byte-identically); a kernel
    that fails to compile on the TPU fails the step — nothing gives way to
    the container path (tests/test_tpu_compile.py holds the lowering at
    model widths). "kernel" forces it (interpreted off-TPU — the CI/e2e
    drill); "interpret" forces the Pallas interpreter everywhere; "xla"
    pins the container path."""
    mode = quant_matmul_mode()
    if mode == "xla":
        return False, False
    on_tpu = jax.default_backend() == "tpu"
    if mode == "interpret":
        return True, True
    if mode == "kernel":
        return True, not on_tpu
    return on_tpu, False


def quant_matmul(
    x: jax.Array,  # [..., K]
    w: dict,  # {"q": [G, g, N] int8/int4, "scale": [G, 1, N] f32}
    bias: jax.Array | None = None,
    lora_a: jax.Array | None = None,  # [K, r]
    lora_b: jax.Array | None = None,  # [r, N]
    lora_scale: float = 1.0,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequant-matmul (+ bias, + LoRA epilogue) through the Pallas
    kernel. Callers go through ``linear()``/``_proj`` which decide the
    kernel-vs-container dispatch; this entry point always runs the kernel
    (``interpret`` selects the Pallas interpreter for CPU parity)."""
    q, scale = w["q"], w["scale"]
    if q.ndim != 3:
        raise ValueError(
            f"quant_matmul takes per-layer containers [G, g, N], got "
            f"q.shape={q.shape} (stacked trees are sliced per layer by the "
            "transformer's unrolled loop)"
        )
    lead = x.shape[:-1]
    k = x.shape[-1]
    if q.shape[0] * q.shape[1] != k:
        raise ValueError(
            f"container input dim {q.shape[0]}x{q.shape[1]} != x's {k}"
        )
    x2 = x.reshape(-1, k)
    out = per_device(
        lambda *arrays: _quant_matmul_p(*arrays, float(lora_scale), interpret)
    )(x2, q, scale, bias, lora_a, lora_b)
    return out.reshape(*lead, q.shape[-1])
