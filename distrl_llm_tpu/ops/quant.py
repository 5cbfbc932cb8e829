"""Weight-only int8/int4 quantization for the frozen base model.

TPU-native equivalent of the reference's bitsandbytes NF4 base weights
(LOAD_IN_4BIT at distributed_actor.py:17, the ``*-bnb-4bit`` checkpoints at
train_distributed.py:11 — SURVEY §2b N4). Instead of CUDA dequant kernels:

* a quantized weight is a plain dict ``{"q": int8|int4 [..., G, g, out],
  "scale": f32 [..., G, 1, out]}`` — groupwise symmetric absmax over the
  input dim (bnb's NF4 uses 64-wide blocks; same knob here). Plain dicts
  flow through jit/scan/tree_map/sharding exactly like arrays, so the model
  and partition code need no special cases beyond ``ops.linear``.
* dequantization is ``q * scale`` folded into the consuming matmul — XLA
  fuses the convert+multiply into the MXU operand read, so HBM traffic drops
  by the storage ratio (2× int8, 4× int4) with no custom kernel. (A Pallas
  dequant-matmul is the escalation path if profiling ever shows the fusion
  breaking.)

Only the per-layer projection weights are quantized; embeddings, lm_head,
norms, and biases stay in the working dtype (mirrors bnb, which quantizes
nn.Linear only).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# projection weights eligible for quantization (helper.py:29–37 targets — the
# same set LoRA adapts, which is every linear in the decoder layer)
QUANT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def is_quantized(w) -> bool:
    return isinstance(w, dict) and "q" in w and "scale" in w


def is_quantized_tree(params) -> bool:
    """True if any layer projection in the param tree is a quantized
    container (full fine-tuning must refuse these — int payloads have no
    gradients)."""
    layers = params.get("layers", {}) if isinstance(params, dict) else {}
    return any(is_quantized(w) for w in layers.values())


def quantize(w: jax.Array, bits: int = 8, group_size: int | None = None) -> Params:
    """Quantize [..., in, out] → {"q": [..., G, g, out], "scale": [..., G, 1, out]}.

    Symmetric absmax per (group, out-column). ``group_size`` divides the input
    dim; None means one group (pure per-column scales — fine for int8; int4
    wants 64–128 wide groups for accuracy, matching bnb's blockwise NF4).
    """
    if bits == 8:
        qmax, dtype = 127.0, jnp.int8
    elif bits == 4:
        qmax, dtype = 7.0, jnp.int4
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    *lead, d_in, d_out = w.shape
    g = group_size or d_in
    if d_in % g != 0:
        raise ValueError(f"group_size {g} does not divide input dim {d_in}")
    grouped = w.astype(jnp.float32).reshape(*lead, d_in // g, g, d_out)
    absmax = jnp.max(jnp.abs(grouped), axis=-2, keepdims=True)  # [..., G, 1, out]
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(grouped * inv), -qmax, qmax).astype(dtype)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def dequantize(w: Params, dtype=jnp.bfloat16) -> jax.Array:
    """[..., G, g, out] quantized → [..., in, out] dense in ``dtype``."""
    q, scale = w["q"], w["scale"]
    full = q.astype(jnp.float32) * scale
    *lead, G, g, d_out = full.shape
    return full.reshape(*lead, G * g, d_out).astype(dtype)


def quantize_params(
    params: Params, bits: int = 8, group_size: int | None = None
) -> Params:
    """Quantize a decoder param tree's layer projections in place of their
    bf16 arrays. Embed/lm_head/norms/biases pass through untouched."""
    layers = dict(params["layers"])
    for name in QUANT_TARGETS:
        if name in layers:
            layers[name] = quantize(layers[name], bits=bits, group_size=group_size)
    out = dict(params)
    out["layers"] = layers
    return out


def quant_bits_for(config_value: str) -> int | None:
    """Map the ``base_quant`` config field ({"none","int8","int4"}) to bits."""
    return {"none": None, "int8": 8, "int4": 4}[config_value]


def default_group_size(bits: int) -> int | None:
    """int4 needs blockwise scales for accuracy (bnb uses 64); int8 is fine
    with pure per-column scales."""
    return 64 if bits == 4 else None
