"""LoRA adapter pytrees over the frozen base decoder.

Equivalent of the reference's unsloth PEFT wrap (helper.py:25–46): rank-r
adapters on q/k/v/o/gate/up/down projections, alpha scaling (rsLoRA off),
zero-init B so step 0 is the base model. Unlike the reference, the adapter is
a plain pytree — weight sync to rollout workers is `jax.device_put` of these
arrays, not a filesystem round-trip (SURVEY §2b N2).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from distrl_llm_tpu.models.configs import ModelConfig, mixer_of

Params = dict[str, Any]

# layer-param key → (in_dim_attr, out_dim_attr) resolved against ModelConfig
_TARGET_DIMS = {
    "wq": ("q_in", "q_dim"),
    "wk": ("hidden_size", "kv_dim"),
    "wv": ("hidden_size", "v_dim"),
    "wo": ("o_dim", "hidden_size"),
    "w_gate": ("hidden_size", "intermediate_size"),
    "w_up": ("hidden_size", "intermediate_size"),
    "w_down": ("intermediate_size", "hidden_size"),
    # latent attention (kv_a_proj_with_mqa, kv_b_proj); wo's input is then H x v
    "wkv_a": ("hidden_size", "latent_dim"),
    "wkv_b": ("kv_lora_rank", "kvb_dim"),
    # its low-rank query path: q_a_proj, and ``wq`` is then q_b_proj (q_in the rank)
    "wq_a": ("hidden_size", "q_lora_rank"),
    # a Mamba layer's two projections: [u, z] from the stream, and back
    "w_in": ("hidden_size", "mamba_in_dim"),
    "w_out": ("mamba_inner", "hidden_size"),
    # compressed convolutional attention's value: this token's half, the last one's
    "wv1": ("hidden_size", "v_half"),
    "wv2": ("hidden_size", "v_half"),
}

# reference target_modules (helper.py:29–37) in our key naming
DEFAULT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# a latent-attention model has no k or v projection: its targets are q, kv_a,
# kv_b, o, and the gated MLP that is a dense layer's MLP or an expert layer's
# shared expert. The router and the routed experts are frozen.
LATENT_TARGETS = ("wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down")
# with a low-rank query path q_a joins them, LAST: the others' keys stay theirs.
# A learned index (its three projections and its norm) carries no adapter
LATENT_RANK_TARGETS = (*LATENT_TARGETS, "wq_a")
# a Mamba layer has no q, k, v or o: its targets are W_in, W_out and the MLP's
# three. W_x, W_dt, the convolution, A_log, D and the inner norms are frozen.
MAMBA_TARGETS = ("w_in", "w_out", "w_gate", "w_up", "w_down")
# compressed convolutional attention: q, k, the value's two halves and o, all in
# the latent. The convolutions, the temperature, the residual's vectors, the
# router and the routed experts are frozen; there is no shared expert.
CCA_TARGETS = ("wq", "wk", "wv1", "wv2", "wo")


def lora_scale(rank: int, alpha: float) -> float:
    return alpha / rank


def init_lora_params(
    rng: jax.Array,
    cfg: ModelConfig,
    rank: int,
    targets: Sequence[str] | None = None,
    dtype=jnp.float32,
) -> Params:
    """A ~ N(0, 1/r) (std r^-1/2), B = 0 — output delta starts at 0 and the
    initial A@B gradient scale is rank-independent.

    A model whose layers differ in kind (``cfg.hybrid``) gets one set of
    factors per kind, ``{"layers": {kind: {target: {"a", "b"}}}}``, stacked
    like that kind's base weights: a lightning layer's k and v project to all
    its heads, a sparse layer's to its few KV heads. ``targets`` left out are
    ``DEFAULT_TARGETS``, or ``LATENT_TARGETS`` for a latent-attention model
    (whose expert layers' w_gate / w_up / w_down are the SHARED expert's, as
    they are in a gated delta-rule model's "softmax" and "delta" layers). A
    power-retention layer has the dense decoder's seven targets and no factor
    on its log-decay. A state-space model's attention layers have the seven and
    its Mamba layers ``MAMBA_TARGETS``; targets the caller names go to the
    layers that have them. A window model (``exaone_moe``, ``mimo_v2_flash``)
    has the seven in every kind of layer: the MLP's three are the dense MLP's in
    a ``_dense`` kind and the shared expert's in the others, or absent where
    the family has no shared expert. A compressed-convolutional
    layer has ``CCA_TARGETS`` and nothing in its second half."""
    named = targets is not None
    if targets is None:
        targets = ((LATENT_RANK_TARGETS if cfg.q_lora_rank else LATENT_TARGETS)
                   if cfg.latent else CCA_TARGETS if cfg.cca else DEFAULT_TARGETS)

    def factors(rng, n_layers: int, dims: dict[str, int], targets=targets) -> Params:
        layers: Params = {}
        for key, target in zip(jax.random.split(rng, len(targets)), targets):
            if not all(attr in dims for attr in _TARGET_DIMS[target]):
                continue  # this kind of layer has no such projection
            d_in, d_out = (dims[attr] for attr in _TARGET_DIMS[target])
            a = jax.random.normal(key, (n_layers, d_in, rank)) * (rank**-0.5)
            layers[target] = {
                "a": a.astype(dtype),
                "b": jnp.zeros((n_layers, rank, d_out), dtype),
            }
        return layers

    dims = {
        attr: getattr(cfg, attr)
        for attr in ("hidden_size", "intermediate_size", "q_dim", "kv_dim")
    }
    dims["o_dim"] = cfg.q_dim  # what wo reads
    dims["v_dim"] = cfg.kv_dim  # what wv writes: k's width unless a family says
    dims["q_in"] = cfg.hidden_size  # and wq
    kind_targets: dict[str, Sequence[str]] = {}  # a kind whose targets are its own
    if not cfg.hybrid:
        return {"layers": factors(rng, cfg.num_layers, dims)}
    if cfg.latent:
        dims.update(
            kv_lora_rank=cfg.kv_lora_rank, latent_dim=cfg.latent_dim,
            kvb_dim=cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            o_dim=cfg.num_heads * cfg.v_head_dim,
        )
        if cfg.q_lora_rank:
            dims.update(q_lora_rank=cfg.q_lora_rank, q_in=cfg.q_lora_rank)
        per_kind = {
            "latent": dims,
            "latent_moe": {**dims, "intermediate_size": cfg.shared_expert_size},
            # a shortcut-connected layer's two sublayers: each its own attention
            # and its own dense MLP; the router and the routed experts are frozen
            "latent_fork": dims, "latent_join": dims,
        }
    elif cfg.delta_moe:
        # q, k, v, o of both mixers and the shared expert's three; the router,
        # the routed experts, the low-rank pairs, beta and the convolutions are frozen
        shared = {**dims, "intermediate_size": cfg.shared_expert_size}
        delta = dict.fromkeys(("q_dim", "kv_dim", "v_dim", "o_dim"), cfg.delta_dim)
        per_kind = {"softmax": shared, "delta": {**shared, **delta}}
    elif cfg.window_moe:
        # q, k, v, o of both mixers (k and v at the KV heads of the layer's kind,
        # v and o at the value's width), then the layer's own second half: the
        # dense MLP's three, or the shared expert's where the family has one; the
        # router, the routed experts and a window layer's sinks are frozen
        def mixer_dims(kind: str) -> dict:
            kv = cfg.kv_heads_of(mixer_of(kind))
            held = {**dims, "kv_dim": kv * cfg.head_dim,
                    "v_dim": kv * cfg.value_head_dim, "o_dim": cfg.o_dim}
            if cfg.layer_ffn(kind) != "dense":  # the shared expert's three, or none
                if cfg.shared_expert_size:
                    held["intermediate_size"] = cfg.shared_expert_size
                else:
                    del held["intermediate_size"]
            return held

        per_kind = {kind: mixer_dims(kind) for kind in dict.fromkeys(cfg.layer_kinds)}
    elif cfg.cca:
        per_kind = {"cca": {
            "hidden_size": cfg.hidden_size, "q_in": cfg.hidden_size, "q_dim": cfg.q_dim,
            "kv_dim": cfg.kv_dim, "v_half": cfg.kv_dim // 2, "o_dim": cfg.q_dim}}
    elif cfg.power:
        # Qwen3's seven targets; the log-decay's projection and bias are frozen
        per_kind = {"power": dims}
    elif cfg.ssd_moe:
        # layers of ONE sublayer: q, k, v, o of an attention layer; W_in and W_out
        # of a Mamba-2 layer; the shared expert's TWO matrices (it has no gate) of
        # an expert layer. The convolution, A_log, dt_bias, D, the gate's norm, the
        # router and the routed experts are frozen
        attention = {k: v for k, v in dims.items() if k != "intermediate_size"}
        per_kind = {
            "softmax_alone": attention,
            "mamba2": {"hidden_size": cfg.hidden_size, "mamba_inner": cfg.ssd_inner,
                       "mamba_in_dim": cfg.ssd_in_dim},
            "experts": {"hidden_size": cfg.hidden_size,
                        **({"intermediate_size": cfg.shared_expert_size}
                           if cfg.shared_expert_size else {})}}
        # a gate is a gated MLP's: this family's shared expert has none
        kind_targets = {"experts": tuple(t for t in targets if t != "w_gate")}
        if not named:
            kind_targets["mamba2"] = MAMBA_TARGETS
    elif cfg.mamba:
        per_kind = {"softmax": dims, "mamba": {
            "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
            "mamba_inner": cfg.mamba_inner, "mamba_in_dim": 2 * cfg.mamba_inner}}
        kind_targets = {} if named else {"mamba": MAMBA_TARGETS}
    else:
        lightning = dict.fromkeys(("q_dim", "kv_dim", "v_dim", "o_dim"), cfg.lightning_dim)
        per_kind = {"sparse": dims, "lightning": {**dims, **lightning}}
    kinds = [k for k in per_kind if cfg.kind_count(k)]
    return {"layers": {
        kind: factors(key, cfg.kind_count(kind), per_kind[kind],
                      kind_targets.get(kind, targets))
        for key, kind in zip(jax.random.split(rng, len(kinds)), kinds)
    }}


def merge_lora(base: Params, lora: Params, alpha: float) -> Params:
    """Fold adapters into a copy of the base weights (W + A@B·alpha/r) — used
    for checkpoint export, mirroring the reference's save_pretrained artifact
    (distributed_actor.py:263–264). Rank is derived from the adapter shapes so
    the scale can't silently mismatch."""
    from distrl_llm_tpu.ops.quant import is_quantized

    def merge(weights: Params, factors: Params) -> Params:
        merged = dict(weights)
        for target, ab in factors.items():
            if "a" not in ab:  # one more level: a layer kind's own stacks
                merged[target] = merge(weights[target], ab)
                continue
            w = weights[target]
            if is_quantized(w):
                raise NotImplementedError(
                    "cannot merge LoRA into quantized base weights")
            scale = lora_scale(ab["a"].shape[-1], alpha)
            delta = jnp.einsum(
                "lir,lro->lio", ab["a"].astype(w.dtype), ab["b"].astype(w.dtype))
            merged[target] = w + delta * scale
        return merged

    out = dict(base)
    out["layers"] = merge(base["layers"], lora["layers"])
    return out


def lora_param_count(lora: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(lora))
