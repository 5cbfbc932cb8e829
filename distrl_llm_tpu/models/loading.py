"""HF checkpoint → param-pytree loading.

Maps transformers-style state dicts (Qwen2/Llama safetensors) onto the stacked
[L, ...] layout of models/transformer.py. Replaces the reference's
FastLanguageModel.from_pretrained load path (distributed_actor.py:58–66) —
here loading is a host-side numpy pass followed by an optional device_put with
sharding, so multi-host loads stream straight to their shards.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Mapping

import numpy as np

from distrl_llm_tpu.models.configs import SHORTCUT_KINDS, ModelConfig

Params = dict[str, Any]

# our layer key → (HF projection name, transpose?)  — HF Linear stores [out, in]
_HF_LAYER_MAP = {
    "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "w_gate": "mlp.gate_proj.weight",
    "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "bq": "self_attn.q_proj.bias",
    "bk": "self_attn.k_proj.bias",
    "bv": "self_attn.v_proj.bias",
    "attn_norm": "input_layernorm.weight",
    "mlp_norm": "post_attention_layernorm.weight",
}

# what a layer of a model with per-layer mixers (MiniCPM-SALA, models/hybrid.py)
# holds beside the names above, in both its kinds: the per-head q/k norms, the
# output gate, and a lightning layer's norm over its joined heads. The two
# kinds share every tensor name; which stack a layer's tensors join is read
# off ``cfg.layer_kinds``. (No checkpoint was at hand when this was written:
# the names follow the family's modeling file as MiniCPM4 names them.)
_HF_HYBRID_MAP = {
    **{k: v for k, v in _HF_LAYER_MAP.items() if not k.startswith("b")},
    "q_norm": "self_attn.q_norm.weight",
    "k_norm": "self_attn.k_norm.weight",
    "wz": "self_attn.o_gate.weight",
    "o_norm": "self_attn.o_norm.weight",
}


# a latent-attention model with routed experts (deepseek_v3; Kimi-VL-A3B keeps
# its language model under ``language_model.`` beside a vision tower and a
# projector, which are not loaded). Layer kind "latent" has the dense MLP's
# names, "latent_moe" the shared expert's under the same keys, the router, and
# ``mlp.experts.<e>.<proj>.weight`` stacked over e.
_HF_LATENT_MAP = {
    "attn_norm": "input_layernorm.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight",
    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_a_norm": "self_attn.kv_a_layernorm.weight",
    "wkv_b": "self_attn.kv_b_proj.weight",
    "wo": "self_attn.o_proj.weight",
}
_HF_LATENT_MLP = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
_HF_ROUTER = {"router": "mlp.gate.weight",
              "e_score_bias": "mlp.gate.e_score_correction_bias"}
#: tensors of a multimodal checkpoint that are not the language model's
_SKIPPED_PREFIXES = ("vision_tower.", "multi_modal_projector.")


def _latent_layer_names(cfg: ModelConfig, kind: str) -> dict[str, Any]:
    """Leaf -> the layer's checkpoint name (a list over experts for an expert
    stack), for one layer of ``kind``."""
    if kind in SHORTCUT_KINDS:
        return _shortcut_layer_names(cfg, SHORTCUT_KINDS.index(kind))
    names: dict[str, Any] = dict(_HF_LATENT_MAP)
    if cfg.q_lora_rank:  # deepseek_v3's low-rank query path: ``wq`` is q_b_proj
        names.update(wq="self_attn.q_b_proj.weight", wq_a="self_attn.q_a_proj.weight",
                     q_a_norm="self_attn.q_a_layernorm.weight")
    if kind == "latent":
        names.update({k: f"mlp.{v}.weight" for k, v in _HF_LATENT_MLP.items()})
        return names
    if cfg.shared_expert_size:
        names.update({
            k: f"mlp.shared_experts.{v}.weight" for k, v in _HF_LATENT_MLP.items()})
    names.update(_HF_ROUTER)
    for k, v in _HF_LATENT_MLP.items():
        names["experts" + k[1:]] = [
            f"mlp.experts.{e}.{v}.weight" for e in range(cfg.n_routed_experts)]
    return names


def _shortcut_layer_names(cfg: ModelConfig, k: int) -> dict[str, Any]:
    """``_latent_layer_names`` for SUBLAYER ``k`` of a ``longcat_flash`` layer:
    the published layer holds its two attentions, four norms and two dense MLPs
    in lists (``self_attn.<k>``, ``input_layernorm.<k>``, ``mlps.<k>``), and one
    expert block (``mlp.router``, ``mlp.experts.<e>``) that sublayer 0's stack
    keeps. Its zero-compute experts hold no tensor."""
    names: dict[str, Any] = {
        "attn_norm": f"input_layernorm.{k}.weight",
        "mlp_norm": f"post_attention_layernorm.{k}.weight",
        "wq_a": f"self_attn.{k}.q_a_proj.weight",
        "q_a_norm": f"self_attn.{k}.q_a_layernorm.weight",
        "wq": f"self_attn.{k}.q_b_proj.weight",
        "wkv_a": f"self_attn.{k}.kv_a_proj_with_mqa.weight",
        "kv_a_norm": f"self_attn.{k}.kv_a_layernorm.weight",
        "wkv_b": f"self_attn.{k}.kv_b_proj.weight",
        "wo": f"self_attn.{k}.o_proj.weight",
        **{key: f"mlps.{k}.{v}.weight" for key, v in _HF_LATENT_MLP.items()},
    }
    if k == 0:
        names.update(router="mlp.router.classifier.weight",
                     e_score_bias="mlp.router.e_score_correction_bias")
        for key, v in _HF_LATENT_MLP.items():
            names["experts" + key[1:]] = [
                f"mlp.experts.{e}.{v}.weight" for e in range(cfg.n_routed_experts)]
    return names


def _published_layer(cfg: ModelConfig, i: int) -> int:
    """The published layer that entry ``i`` of ``cfg.layer_kinds`` lies in."""
    return i // len(SHORTCUT_KINDS) if cfg.shortcut_moe else i


def _is_matrix(key: str) -> bool:
    """HF Linear stores [out, in]; ours is [in, out]."""
    return key.startswith(("w", "experts_")) or key == "router"


def _latent_params_from_state_dict(sd, cfg: ModelConfig, dtype) -> Params:
    """The published names into one stack per layer kind. Every tensor of the
    language model's layers that are run is used exactly once; one that is
    missing (an expert, say) or left over is an error, and the vision tower's
    and the projector's are skipped by prefix."""
    prefix = "language_model." if any(
        k.startswith("language_model.") for k in sd) else ""
    used: set[str] = set()

    def take(name: str) -> np.ndarray:
        full = prefix + name
        if full not in sd:
            raise KeyError(f"the checkpoint has no tensor {full!r}")
        if full in used:
            raise ValueError(f"tensor {full!r} is read twice")
        used.add(full)
        return np.asarray(sd[full])

    layers: Params = {}
    for kind in dict.fromkeys(cfg.layer_kinds):
        at = [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
        layers[kind] = {}
        for key, name in _latent_layer_names(cfg, kind).items():
            per_layer = [
                np.stack([take(f"model.layers.{i}.{n}") for n in name])
                if isinstance(name, list) else take(f"model.layers.{i}.{name}")
                for i in (_published_layer(cfg, i) for i in at)
            ]
            out = np.stack(per_layer).astype(dtype)
            layers[kind][key] = out.swapaxes(-1, -2) if _is_matrix(key) else out
    params: Params = {
        "embed": take("model.embed_tokens.weight").astype(dtype),
        "final_norm": take("model.norm.weight").astype(dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = take("lm_head.weight").astype(dtype).T
    def of_a_layer_not_run(k: str) -> bool:
        m = re.match(re.escape(prefix) + r"model\.layers\.(\d+)\.", k)
        return m is not None and int(m.group(1)) >= cfg.num_layers

    left = [
        k for k in sd
        if k not in used and not k.startswith(_SKIPPED_PREFIXES)
        and not k.endswith("rotary_emb.inv_freq") and not of_a_layer_not_run(k)
    ]
    if left:
        raise ValueError(
            f"{len(left)} tensors of the checkpoint were not loaded, e.g. "
            f"{sorted(left)[:4]}: the model would run without them")
    return params


def _get(sd: Mapping[str, np.ndarray], name: str) -> np.ndarray:
    if name in sd:
        return np.asarray(sd[name])
    # some exports drop the "model." prefix
    alt = name.removeprefix("model.")
    if alt in sd:
        return np.asarray(sd[alt])
    raise KeyError(name)


def _refuse_unnamed(cfg: ModelConfig) -> None:
    """A ``solar_open2``, ``brumby``, ``jamba``, ``exaone_moe``, ``mimo_v2_flash``, ``glm_moe_dsa``, ``zaya`` or ``ouro`` checkpoint is refused by name, in both directions:
    its published tensor names cannot be read here, and names guessed for the
    delta-rule layers' convolutions, low-rank pairs and gates would load or
    save something else under the model's name. Seeded weights only."""
    if cfg.model_type == "ouro":
        raise NotImplementedError(
            "model_type 'ouro' checkpoints are not supported: the published tensor "
            "names of its layers (the two norms on each sublayer's output, the exit "
            "gate) cannot be checked here until the checkpoint's files are in the "
            "repository, and a guessed name would load or save something else under "
            "the model's name; the model runs from seeded weights only (init_params)")
    if cfg.index_topk:
        raise NotImplementedError(
            "model_type 'glm_moe_dsa' checkpoints are not supported: the published "
            "tensor names of its index (the three projections and the key's "
            "LayerNorm) and of its multi-token-prediction module cannot be read "
            "here, and a guessed name would load or save something else under the "
            "model's name; the model runs from seeded weights only (init_params)")
    if cfg.cca:
        raise NotImplementedError(
            "model_type 'zaya' checkpoints are not supported: the published tensor "
            "names of its layers (the two convolutions, the keys' temperature, the "
            "value's two halves, the router's MLP with the vector it weights the "
            "previous layer's value by, the residual's scales and shifts) cannot be "
            "read here, and a guessed name would load or save something else under "
            "the model's name; the model runs from seeded weights only (init_params)")
    if cfg.power:
        raise NotImplementedError(
            "model_type 'brumby' checkpoints are not supported: the published "
            "tensor names of its power-retention layers (the log-decay's projection "
            "and bias) are not known to this loader, and a guessed name would load "
            "or save something else under the model's name; the model runs from "
            "seeded weights only (init_params)")
    if cfg.mamba:
        raise NotImplementedError(
            "model_type 'jamba' checkpoints are not supported: the published tensor "
            "names of its Mamba layers (in_proj, conv1d, x_proj, dt_proj, A_log, D, "
            "the three inner norms) cannot be checked here, the state's A_log is held "
            "transposed, and a guessed name would load or save something else under "
            "the model's name; the model runs from seeded weights only (init_params)")
    if cfg.ssd_moe:
        raise NotImplementedError(
            "model_type 'nemotron_h' checkpoints are not supported: the published "
            "tensor names of its layers (a Mamba-2 mixer's in_proj, conv1d, A_log, D, "
            "dt_bias and gated norm, the router with its correction bias, the experts' "
            "and the shared expert's two matrices) cannot be checked here, and a "
            "guessed name would load or save something else under the model's name; "
            "the model runs from seeded weights only (init_params)")
    if cfg.model_type == "mimo_v2_flash":
        raise NotImplementedError(
            "model_type 'mimo_v2_flash' checkpoints are not supported: the published "
            "tensor names of its layers (the window layers' sinks, k and v of two head "
            "counts and two widths, the router and its correction bias, the experts, "
            "the multi-token-prediction layers) cannot be read here, and a guessed "
            "name would load or save something else under the model's name; the "
            "model runs from seeded weights only (init_params)")
    if cfg.window_moe:
        raise NotImplementedError(
            "model_type 'exaone_moe' checkpoints are not supported: the published "
            "tensor names of its layers (the q/k norms, the router and its correction "
            "bias, the experts, the shared expert, the multi-token-prediction module) "
            "cannot be read here, and a guessed name would load or save something "
            "else under the model's name; the model runs from seeded weights only "
            "(init_params)")
    if cfg.delta_moe:
        raise NotImplementedError(
            "model_type 'solar_open2' checkpoints are not supported: the published "
            "tensor names of its delta-rule and gated softmax layers are not known "
            "to this loader; the model runs from seeded weights only (init_params)")


def params_from_state_dict(
    sd: Mapping[str, np.ndarray], cfg: ModelConfig, dtype=np.float32
) -> Params:
    """Numpy state dict (HF names) → our stacked param pytree."""
    _refuse_unnamed(cfg)
    if cfg.latent:
        return _latent_params_from_state_dict(sd, cfg, dtype)
    if cfg.hybrid:
        return _hybrid_params_from_state_dict(sd, cfg, dtype)

    def stack(key: str, hf_name: str) -> np.ndarray:
        per_layer = [
            _get(sd, f"model.layers.{i}.{hf_name}") for i in range(cfg.num_layers)
        ]
        out = np.stack(per_layer).astype(dtype)
        if key.startswith("w"):  # weights: HF [out, in] → ours [in, out]
            out = out.transpose(0, 2, 1)
        return out

    layers = {
        key: stack(key, hf_name)
        for key, hf_name in _HF_LAYER_MAP.items()
        if cfg.attention_bias or not key.startswith("b")
    }
    params: Params = {
        "embed": _get(sd, "model.embed_tokens.weight").astype(dtype),
        "final_norm": _get(sd, "model.norm.weight").astype(dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _get(sd, "lm_head.weight").astype(dtype).T
    return params


def _hybrid_layer_keys(cfg: ModelConfig, kind: str) -> list[str]:
    """The leaves of one layer of ``kind``, as ``init_hybrid_params`` lays
    them out."""
    gate = cfg.attn_output_gate if kind == "sparse" else cfg.lightning_output_gate
    skip = set() if cfg.qk_norm else {"q_norm", "k_norm"}
    skip |= set() if gate else {"wz"}
    skip |= set() if kind == "lightning" and cfg.lightning_output_norm else {"o_norm"}
    return [k for k in _HF_HYBRID_MAP if k not in skip]


def _hybrid_params_from_state_dict(sd, cfg: ModelConfig, dtype) -> Params:
    """One stack per layer kind, each in the order its layers appear in the
    model: layer i's tensors join the stack of ``cfg.layer_kinds[i]``."""
    layers: Params = {}
    for kind in dict.fromkeys(cfg.layer_kinds):
        at = [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
        layers[kind] = {}
        for key in _hybrid_layer_keys(cfg, kind):
            out = np.stack([
                _get(sd, f"model.layers.{i}.{_HF_HYBRID_MAP[key]}") for i in at
            ]).astype(dtype)
            layers[kind][key] = out.transpose(0, 2, 1) if key.startswith("w") else out
    params: Params = {
        "embed": _get(sd, "model.embed_tokens.weight").astype(dtype),
        "final_norm": _get(sd, "model.norm.weight").astype(dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _get(sd, "lm_head.weight").astype(dtype).T
    return params


def load_safetensors_dir(path: str) -> dict[str, np.ndarray]:
    """All tensors from a checkpoint directory's .safetensors shards, on host.
    Honors the index file when present."""
    from safetensors.numpy import load_file

    index_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index_path):
        with open(index_path) as f:
            shards = sorted(set(json.load(f)["weight_map"].values()))
    else:
        shards = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    sd: dict[str, np.ndarray] = {}
    for shard in shards:
        sd.update(load_file(os.path.join(path, shard)))
    return sd


def state_dict_from_params(params: Params, cfg: ModelConfig) -> dict[str, np.ndarray]:
    """Our stacked param pytree → HF-named numpy state dict (the exact
    inverse of ``params_from_state_dict``)."""
    _refuse_unnamed(cfg)
    sd: dict[str, np.ndarray] = {}
    layers = params["layers"]
    if cfg.latent:
        for kind, stack in layers.items():
            at = [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
            names = _latent_layer_names(cfg, kind)
            for key, stacked in stack.items():
                stacked = np.asarray(stacked)
                if _is_matrix(key):
                    stacked = stacked.swapaxes(-1, -2)
                for j, i in enumerate(_published_layer(cfg, i) for i in at):
                    each = names[key] if isinstance(names[key], list) else None
                    for e, name in enumerate(each or [names[key]]):
                        sd[f"model.layers.{i}.{name}"] = np.ascontiguousarray(
                            stacked[j, e] if each else stacked[j])
        layers = {}
    elif cfg.hybrid:
        for kind, stack in layers.items():
            at = [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
            for key, stacked in stack.items():
                stacked = np.asarray(stacked)
                if key.startswith("w"):
                    stacked = stacked.transpose(0, 2, 1)
                for j, i in enumerate(at):
                    sd[f"model.layers.{i}.{_HF_HYBRID_MAP[key]}"] = (
                        np.ascontiguousarray(stacked[j]))
        layers = {}
    for key, hf_name in _HF_LAYER_MAP.items():
        if key not in layers:
            continue
        stacked = np.asarray(layers[key])
        if key.startswith("w"):  # ours [L, in, out] → HF [out, in]
            stacked = stacked.transpose(0, 2, 1)
        for i in range(cfg.num_layers):
            sd[f"model.layers.{i}.{hf_name}"] = np.ascontiguousarray(stacked[i])
    sd["model.embed_tokens.weight"] = np.asarray(params["embed"])
    sd["model.norm.weight"] = np.asarray(params["final_norm"])
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = np.ascontiguousarray(np.asarray(params["lm_head"]).T)
    return sd


def save_hf_checkpoint(
    params: Params,
    cfg: ModelConfig,
    path: str,
    *,
    lora: Params | None = None,
    lora_alpha: float = 16.0,
    model_type: str | None = None,  # default: derived from cfg.model_type
) -> None:
    """Write an HF-format checkpoint directory (model.safetensors +
    config.json), optionally with the LoRA adapter MERGED into the base —
    the reference's per-``save_every`` ``save_pretrained`` snapshot
    (distributed_actor.py:263–264 ← distributed_trainer.py:372–380), loadable
    back through ``load_pretrained`` or transformers."""
    from safetensors.numpy import save_file

    from distrl_llm_tpu.models.lora import merge_lora

    if lora is not None:
        params = merge_lora(params, lora, lora_alpha)
    os.makedirs(path, exist_ok=True)
    sd = state_dict_from_params(params, cfg)
    save_file(sd, os.path.join(path, "model.safetensors"))
    torch_dtype = str(sd["model.embed_tokens.weight"].dtype)
    model_type = model_type or cfg.model_type
    arch = {
        "qwen2": "Qwen2ForCausalLM",
        "llama": "LlamaForCausalLM",
        "mistral": "MistralForCausalLM",
        "gemma": "GemmaForCausalLM",
        "deepseek_v3": "DeepseekV3ForCausalLM",
        "longcat_flash": "LongcatFlashForCausalLM",
    }.get(model_type, "LlamaForCausalLM")
    hf_cfg = {
        "model_type": model_type,
        "architectures": [arch],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings,
        "torch_dtype": torch_dtype,
    }
    if cfg.shortcut_moe:  # the family's own names for depth, widths and router
        for key in ("head_dim", "num_hidden_layers", "intermediate_size",
                    "num_key_value_heads"):
            del hf_cfg[key]
        hf_cfg.update(
            num_layers=cfg.num_layers, ffn_hidden_size=cfg.intermediate_size,
            expert_ffn_hidden_size=cfg.moe_intermediate_size,
            kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
            mla_scale_q_lora=cfg.latent_q_scale != 1.0,
            mla_scale_kv_lora=cfg.latent_kv_scale != 1.0,
            n_routed_experts=cfg.n_routed_experts, moe_topk=cfg.experts_per_token,
            zero_expert_num=cfg.zero_experts, zero_expert_type="identity",
            routed_scaling_factor=cfg.routed_scaling_factor,
            attention_method="MLA",
        )
        if cfg.router_experts:  # one chip's share of the routed experts
            hf_cfg.update(expert_shard=cfg.expert_shard, share={
                "chips_per_layer": cfg.router_experts // cfg.n_routed_experts,
                "published": {"n_routed_experts": cfg.router_experts}})
    elif cfg.latent:
        del hf_cfg["head_dim"]  # the query head is nope + rope
        hf_cfg.update(
            kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank or None,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
            n_routed_experts=cfg.n_routed_experts,
            n_shared_experts=cfg.n_shared_experts,
            num_experts_per_tok=cfg.experts_per_token,
            moe_intermediate_size=cfg.moe_intermediate_size,
            first_k_dense_replace=cfg.first_dense_layers, moe_layer_freq=1,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
            topk_group=1, rope_scaling=None,
        )
    if cfg.hidden_act == "gelu_tanh":
        hf_cfg["hidden_act"] = "gelu_pytorch_tanh"
    if cfg.sliding_window is not None:
        hf_cfg["sliding_window"] = cfg.sliding_window
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=2)


def load_pretrained(
    path: str,
    cfg: ModelConfig | None = None,
    dtype=np.float32,
    shard_fn: Callable[[Params], Params] | None = None,
) -> tuple[Params, ModelConfig]:
    """Load an HF-format local checkpoint directory. ``shard_fn`` (e.g. a
    device_put with NamedSharding) is applied to the host tree, letting each
    process materialize only its shards."""
    if cfg is None:
        with open(os.path.join(path, "config.json")) as f:
            hf_cfg = json.load(f)

        class _NS:
            def __init__(self, d):
                self.__dict__.update(d)

        cfg = ModelConfig.from_hf_config(_NS(hf_cfg))
    sd = load_safetensors_dir(path)
    params = params_from_state_dict(sd, cfg, dtype=dtype)
    if shard_fn is not None:
        params = shard_fn(params)
    return params, cfg
