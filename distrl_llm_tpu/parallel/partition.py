"""PartitionSpec rules for the decoder param/LoRA/cache pytrees.

GSPMD does the heavy lifting: we annotate parameters and batch inputs, XLA
inserts the collectives (SURVEY §2c — TP sharding replaces the reference's
unused vLLM TP; fsdp shards learner state; dp shards the batch). Specs are
assigned by param-tree path so they survive structural additions like
quantized weight containers.

Layout conventions (models/transformer.py):
  layers/w*:   [L, in, out]  → out over "tp" for up-projections (qkv, gate,
               up), in over "tp" for down-projections (o, down) — Megatron
               style, so the pair needs no resharding between them.
  embed:       [V, D] vocab over "tp" (logits psum'd by GSPMD), D over "fsdp".
  lm_head:     [D, V] V over "tp".
  lora a/b:    factor dims follow the base weight's sharded dim; the rank dim
               is always replicated.
  kv cache:    per-layer tuples of [B, K, hd, S]; batch over "dp", kv heads
               over "tp" (build it with models.init_kv_cache).
  hybrid:      a model whose layers differ in kind keeps one stack per kind,
               layers/<kind>/w* (models/hybrid.py); the rules read a leaf's
               own name and its target's, so the extra level changes nothing.
  latent/moe:  a latent-attention model's wkv_a / wkv_b / kv_a_norm and an
               expert layer's router, e_score_bias and experts_{gate,up,down}
               [L, E, in, out] are REPLICATED (``_REPLICATED``): one chip holds
               every expert; sharding E over chips, and the all-to-all that
               needs, come with a four-chip cell.
  delta rule:  a delta-rule layer's convolution filters, low-rank pairs
               (wf_a/wf_b, wg_a/wg_b), wb, A_log and dt_bias are small and
               fall to the last rule: whole on every chip.
  retention:   a power-retention layer's log-decay (w_decay [L, hidden, K],
               b_decay [L, K]: one column a KV head) is whole on every chip
               (``_REPLICATED``); q, k, v, o and the MLP go as the dense
               decoder's.
  state space: a Mamba layer's W_in [L, hidden, 2 d_inner] goes column
               parallel and its W_out [L, d_inner, hidden] row parallel, like
               the MLP's pair (39 of its 41 M parameters); W_x, W_dt, the
               convolution, A_log, D and the inner norms are small and whole
               on every chip. A Mamba-2 layer's pair (``nemotron_h``) goes the
               same way under the same names; its convolution, A_log, dt_bias,
               D and the gate's norm are whole on every chip, and an expert
               layer's two matrices an expert are ``_REPLICATED`` like a gated
               expert's three.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = dict[str, Any]

# layer weights whose OUT dim is tp-sharded (column parallel)
# (wz, wg: the output gate of a sparse or lightning layer and of a gated
# softmax layer, models/hybrid.py)
_COL = {"wq", "wk", "wv", "wz", "wg", "w_gate", "w_up", "w_in", "wv1", "wv2"}
# layer weights whose IN dim is tp-sharded (row parallel)
_ROW = {"wo", "w_down", "w_out"}
# a latent-attention / expert layer's own leaves: whole on every chip
_REPLICATED = {"wkv_a", "wkv_b", "router", "e_score_bias", "experts_gate",
               "experts_up", "experts_down",
               # its low-rank query path's first half and a learned index's
               # leaves (models/hybrid.py): small beside the experts
               "wq_a", "w_index_q", "w_index_k", "w_index_w", "b_index_k",
               # a power-retention layer's log-decay: one column a KV head
               "w_decay", "b_decay",
               # compressed convolutional attention's convolutions and key
               # temperature, its MLP router and the residual's scales and
               # shifts: small beside the experts
               "conv0", "b_conv0", "conv1", "b_conv1", "k_temp", "router_down",
               "b_router_down", "router_gamma", "router_w1", "b_router_w1",
               "router_w2", "b_router_w2", "router_w3", "attn_res_scale",
               "attn_res_shift", "mlp_res_scale", "mlp_res_shift"}


def _spec_for_path(path: tuple[str, ...], shape: tuple[int, ...]) -> P:
    ndim = len(shape)
    name = path[-1]
    if len(path) >= 2 and path[-2] == "exit_gate":  # a looped model's gate: 2,049 values
        return P(*([None] * ndim))
    if name in ("a", "b"):  # LoRA factor: path is (..., "layers", target, "a"|"b")
        target = path[-2]
        if name == "a":  # [L, in, r]
            return P(None, "tp" if target in _ROW else "fsdp", None)
        return P(None, None, "tp" if target in _COL else "fsdp")  # [L, r, out]
    if name == "embed":
        return P("tp", "fsdp")
    if name == "lm_head":
        return P("fsdp", "tp")
    if name.endswith("norm") or name in _REPLICATED:
        # final/attn/mlp norms, a hybrid layer's q/k/o/kv_a norms; _REPLICATED
        return P(*([None] * ndim))
    if name in _COL:
        return P(None, "fsdp", "tp")
    if name in _ROW:
        return P(None, "tp", "fsdp")
    if name.startswith("b"):  # projection biases [L, out]
        return P(None, "tp") if name in ("bq", "bk", "bv") else P(None, "fsdp")
    if name in ("k", "v"):  # kv cache: per-layer [B, K, hd, S] (S minormost)
        return P("dp", "tp", None, None)
    if name in ("q", "scale") and len(path) >= 2 and path[-2] in (_COL | _ROW):
        # quantized weight container (ops/quant.py): q [L, G, g, out],
        # scale [L, G, 1, out]. The base weight's input-dim sharding goes on
        # G when there are multiple groups (blockwise int4 — contiguous groups
        # per shard, so the dequant reshape [G, g] → [G·g] stays local); with
        # a single group (per-column int8, G=1) it goes on g for q and is
        # dropped for scale (whose g dim is 1).
        target = path[-2]
        in_ax, out_ax = ("fsdp", "tp") if target in _COL else ("tp", "fsdp")
        if shape[1] > 1:  # [L, G>1, ...]: shard the group axis
            return P(None, in_ax, None, out_ax)
        if name == "q":
            return P(None, None, in_ax, out_ax)
        return P(None, None, None, out_ax)  # scale [L, 1, 1, out]
    return P(*([None] * ndim))


def _tree_specs(tree: Params) -> Params:
    def walk(path: tuple[str, ...], node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):  # per-layer cache tuples
            return type(node)(walk(path, v) for v in node)
        if node is None:
            return None
        return _spec_for_path(path, tuple(getattr(node, "shape", ())))

    return walk((), tree)


def param_specs(params: Params) -> Params:
    """PartitionSpec tree matching ``params``' structure (base, LoRA, or cache)."""
    return _tree_specs(params)


def shard_tree(tree: Params, mesh: Mesh, specs: Params | None = None) -> Params:
    """device_put the tree onto ``mesh`` with its specs (host→device scatter)."""
    if specs is None:
        specs = param_specs(tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


def opt_state_specs(opt_state: Any) -> Any:
    """Specs for an optimizer-state tree. Optax moment trees mirror the param
    tree's dict structure (mu/nu hold the same nested dicts), so each state
    leaf's DictKey path suffix IS a param path — route it through the same
    ``_spec_for_path`` rules. Leaves whose shape no longer matches the rule
    (step counts, blockwise-quantized flat payloads) are replicated.

    Needed because ``jit(optimizer.init)`` does NOT propagate input shardings:
    init only uses input *shapes*, so the compiled program has no array inputs
    and its outputs land on the default device."""
    from jax.tree_util import DictKey

    def spec_for(path, leaf):
        ndim = len(getattr(leaf, "shape", ()))
        names = tuple(k.key for k in path if isinstance(k, DictKey))
        if names:
            s = _spec_for_path(names, tuple(leaf.shape))
            if len(s) == ndim:
                return s
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(spec_for, opt_state)


def shard_opt_state(opt_state: Any, mesh: Mesh) -> Any:
    """Place optimizer state on ``mesh``, moments sharded like their params
    (explicit FSDP sharding of learner state — SURVEY §2c)."""
    specs = opt_state_specs(opt_state)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), opt_state, specs
    )


def batch_spec() -> P:
    """Activations/batch inputs: leading dim over dp."""
    return P("dp")


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
