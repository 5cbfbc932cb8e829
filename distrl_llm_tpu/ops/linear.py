"""Linear projection with pluggable weight containers.

All model matmuls route through ``linear`` so the frozen base can swap its
weights for quantized containers (int8/int4 weight-only — the N4 equivalent of
the reference's bitsandbytes NF4 base, distributed_actor.py:17) without
touching model code. Quantized containers live in ops/quant.py and are
registered pytrees, so they flow through jit/pjit/scan like arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class OutIn:
    """One layer's weight stored ``[out, in]``: the contraction dimension
    minor, which is how the TPU compiler wants a weight it keeps resident
    beside a matmul of a decode step's few rows. ``decode_view`` in
    models/transformer.py holds a stack's weight as a tuple of these, one a
    layer, where the tree holds ONE ``[layers, in, out]`` array; ``linear``
    contracts over the last axis."""

    __slots__ = ("w",)

    def __init__(self, w):
        self.w = w

    def tree_flatten(self):
        return (self.w,), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def linear(x: jax.Array, w, b: jax.Array | None = None) -> jax.Array:
    """y = x @ w (+ b). ``w`` is a plain [in, out] array, an ``OutIn`` (the
    same weight stored [out, in]) or a quantized container dict
    (ops/quant.py): {"q": [G, g, out], "scale": [G, 1, out]}.

    Quantized containers dispatch to the fused Pallas dequant-matmul
    (ops/quant_matmul.py) when it is enabled for this backend
    (DISTRL_QUANT_MATMUL; "auto" = TPU only), else to the XLA
    container path below — same math, same order, greedy-bit-identical."""
    if isinstance(w, dict):
        if w["q"].ndim == 3:
            from distrl_llm_tpu.ops.quant_matmul import (
                dispatch_choices, quant_matmul, quant_matmul_dispatch,
            )

            bits = 4 if w["q"].dtype == jnp.int4 else 8
            use, interp = quant_matmul_dispatch()
            dispatch_choices[(bits, x.shape[-1], w["q"].shape[-1], 0)] = (
                "kernel" if use else "xla"
            )
            if use:
                return quant_matmul(x, w, b, interpret=interp)
        # dequant folded into the matmul: XLA fuses the convert+scale into
        # the MXU operand read, so the weight moves through HBM at int8/int4
        # width (the N4 dequant-matmul — the fused kernel's exact-fallback)
        # q·scale in f32 (scale is stored f32 — bf16-rounding the scales
        # would stack ~0.4% error on the quantization error), cast once
        wq = (w["q"].astype(jnp.float32) * w["scale"]).astype(x.dtype)
        G, g, d_out = wq.shape[-3:]
        y = jnp.einsum("...i,io->...o", x, wq.reshape(G * g, d_out))
    elif isinstance(w, OutIn):
        y = jnp.einsum("...i,oi->...o", x, w.w)
    else:
        y = jnp.einsum("...i,io->...o", x, w)
    if b is not None:
        y = y + b
    return y


def lora_delta(
    x: jax.Array, a: jax.Array, b: jax.Array, scale,
    dropout_rate: float = 0.0, dropout_rng: jax.Array | None = None,
) -> jax.Array:
    """LoRA contribution (x @ A) @ B · scale, computed in the activation dtype.
    A: [in, r], B: [r, out], scale = alpha / r (rsLoRA off — helper.py:44).
    Factors stored at higher precision (f32 LoRA over a bf16 base) are cast to
    the activation dtype so the delta never widens the residual stream.

    ``dropout_rate`` + ``dropout_rng`` enable peft-style LoRA dropout: the
    adapter INPUT is dropped (inverted scaling), the base path is untouched —
    matching ``lora_dropout`` in the reference's init_peft_model
    (helper.py:40). Inference callers pass no rng and pay nothing."""
    a = a.astype(x.dtype)
    b = b.astype(x.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, x.shape)
        x = jnp.where(keep, x / (1.0 - dropout_rate), 0.0).astype(x.dtype)
    return (x @ a @ b) * jnp.asarray(scale, dtype=x.dtype)
