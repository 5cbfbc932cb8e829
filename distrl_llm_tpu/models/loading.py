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
from typing import Any, Callable, Mapping

import numpy as np

from distrl_llm_tpu.models.configs import ModelConfig

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


def _get(sd: Mapping[str, np.ndarray], name: str) -> np.ndarray:
    if name in sd:
        return np.asarray(sd[name])
    # some exports drop the "model." prefix
    alt = name.removeprefix("model.")
    if alt in sd:
        return np.asarray(sd[alt])
    raise KeyError(name)


def params_from_state_dict(
    sd: Mapping[str, np.ndarray], cfg: ModelConfig, dtype=np.float32
) -> Params:
    """Numpy state dict (HF names) → our stacked param pytree."""
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
    sd: dict[str, np.ndarray] = {}
    layers = params["layers"]
    if cfg.hybrid:
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
