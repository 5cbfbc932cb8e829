"""Golden-logit tests: our pure-JAX decoder vs transformers' torch Qwen2 on CPU.

A tiny random Qwen2 (GQA, qkv bias, untied head) is built in torch, its state
dict mapped through models/loading.py, and logits compared position-by-position
— this validates RoPE convention, GQA repeat, RMSNorm eps placement, SwiGLU,
and the state-dict name/transpose mapping in one shot (SURVEY §4 "numerics").
"""

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from distrl_llm_tpu.models import TINY, forward, init_kv_cache
from distrl_llm_tpu.models.loading import params_from_state_dict


@pytest.fixture(scope="module")
def golden():
    hf_cfg = transformers.Qwen2Config(
        vocab_size=TINY.vocab_size,
        hidden_size=TINY.hidden_size,
        intermediate_size=TINY.intermediate_size,
        num_hidden_layers=TINY.num_layers,
        num_attention_heads=TINY.num_heads,
        num_key_value_heads=TINY.num_kv_heads,
        max_position_embeddings=TINY.max_position_embeddings,
        rope_theta=TINY.rope_theta,
        rms_norm_eps=TINY.rms_norm_eps,
        tie_word_embeddings=TINY.tie_word_embeddings,
        attention_dropout=0.0,
    )
    torch.manual_seed(0)
    model = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params = params_from_state_dict(sd, TINY, dtype=np.float32)
    return model, params


def hf_logits(model, ids, mask=None):
    with torch.no_grad():
        out = model(
            input_ids=torch.tensor(ids),
            attention_mask=None if mask is None else torch.tensor(mask),
        )
    return out.logits.numpy()


class TestGoldenLogits:
    def test_full_sequence_no_padding(self, golden):
        model, params = golden
        rng = np.random.default_rng(0)
        ids = rng.integers(0, TINY.vocab_size, size=(2, 17))
        ours, _ = forward(params, TINY, jnp.asarray(ids))
        theirs = hf_logits(model, ids)
        np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-4)

    def test_left_padded_batch(self, golden):
        # the learner's fixed-shape recompute left-pads prompts
        # (distributed_actor.py:217–219) — padded positions must not leak in
        model, params = golden
        rng = np.random.default_rng(1)
        ids = rng.integers(0, TINY.vocab_size, size=(2, 12))
        mask = np.ones((2, 12), dtype=np.int64)
        mask[0, :5] = 0
        mask[1, :2] = 0
        ours, _ = forward(params, TINY, jnp.asarray(ids), attention_mask=jnp.asarray(mask))
        theirs = hf_logits(model, ids, mask)
        # compare only non-pad positions: HF emits arbitrary values at pads
        ours_np = np.asarray(ours)
        for b in range(2):
            real = mask[b].astype(bool)
            np.testing.assert_allclose(
                ours_np[b][real], theirs[b][real], atol=2e-4, rtol=2e-4
            )

    def test_remat_matches(self, golden):
        _, params = golden
        ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, size=(1, 9)))
        plain, _ = forward(params, TINY, ids, remat=False)
        remat, _ = forward(params, TINY, ids, remat=True)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(remat), atol=1e-5)


class TestKVCacheConsistency:
    def test_prefill_then_decode_matches_full_forward(self, golden):
        """Prefill + token-by-token decode must reproduce the no-cache forward —
        the engine's correctness backbone."""
        _, params = golden
        rng = np.random.default_rng(3)
        prompt_len, total_len, batch = 7, 12, 2
        ids = rng.integers(0, TINY.vocab_size, size=(batch, total_len))
        full, _ = forward(params, TINY, jnp.asarray(ids))

        cache = init_kv_cache(TINY, batch, total_len, dtype=jnp.float32)
        key_mask = np.zeros((batch, total_len), dtype=np.int32)
        key_mask[:, :prompt_len] = 1
        logits, cache = forward(
            params, TINY, jnp.asarray(ids[:, :prompt_len]),
            attention_mask=jnp.asarray(key_mask), kv_cache=cache, cache_offset=0,
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full)[:, :prompt_len], atol=2e-4, rtol=2e-4
        )
        for t in range(prompt_len, total_len):
            key_mask[:, t] = 1
            logits, cache = forward(
                params, TINY, jnp.asarray(ids[:, t : t + 1]),
                attention_mask=jnp.asarray(key_mask), kv_cache=cache, cache_offset=t,
            )
            np.testing.assert_allclose(
                np.asarray(logits)[:, 0], np.asarray(full)[:, t], atol=3e-4, rtol=3e-4
            )


class TestLlamaFamilyShapes:
    """The Llama-3 family differs from Qwen2 in exactly the knobs that can
    silently break a shared implementation: NO qkv bias, UNTIED embeddings,
    different rms eps. Exercise that configuration end-to-end on tiny shapes
    (the Qwen2 path is covered by the torch golden test above)."""

    def _tiny_llama(self):
        from distrl_llm_tpu.models.configs import ModelConfig

        return ModelConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=500000.0, rms_norm_eps=1e-5,
            attention_bias=False, tie_word_embeddings=False,
        )

    def test_forward_and_engine(self):
        import numpy as np

        import jax
        import jax.numpy as jnp

        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.models import init_lora_params, init_params
        from distrl_llm_tpu.models.transformer import forward

        cfg = self._tiny_llama()
        params = init_params(jax.random.PRNGKey(0), cfg)
        assert "bq" not in params["layers"]  # no attention bias
        assert "lm_head" in params  # untied
        ids = jnp.asarray(
            np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 8)), jnp.int32
        )
        logits, _ = forward(params, cfg, ids)
        assert logits.shape == (2, 8, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())

        lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=4)
        engine = GenerationEngine(
            cfg, max_prompt_tokens=8, max_new_tokens=4,
            eos_token_ids=[cfg.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32,
        )
        res = engine.generate(
            params, lora, np.asarray(ids), np.ones((2, 8), np.int32),
            SamplingConfig(max_tokens=4, temperature=0.0, n=2),
            jax.random.PRNGKey(2),
        )
        assert res.tokens.shape == (2, 2, 4)

    def test_preset_mapping(self):
        from distrl_llm_tpu.models.configs import LLAMA3_8B, preset_for_model_name

        assert preset_for_model_name("meta-llama/Meta-Llama-3-8B") is LLAMA3_8B


class TestHfSnapshotRoundtrip:
    """save_hf_checkpoint (the reference's save_pretrained artifact) must
    round-trip through load_pretrained with the adapter merged."""

    def test_merged_save_load(self, tmp_path):
        import numpy as np

        import jax
        import jax.numpy as jnp

        from distrl_llm_tpu.models import TINY, init_lora_params, init_params
        from distrl_llm_tpu.models.lora import merge_lora
        from distrl_llm_tpu.models.loading import load_pretrained, save_hf_checkpoint
        from distrl_llm_tpu.models.transformer import forward

        params = init_params(jax.random.PRNGKey(0), TINY)
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        # nonzero B so the merge actually changes the weights
        lora = jax.tree_util.tree_map(lambda x: x + 0.01, lora)

        path = str(tmp_path / "model_5")
        save_hf_checkpoint(params, TINY, path, lora=lora, lora_alpha=8.0)
        restored, cfg2 = load_pretrained(path)
        assert cfg2.num_layers == TINY.num_layers
        assert cfg2.attention_bias == TINY.attention_bias

        ids = jnp.asarray(
            np.random.default_rng(0).integers(1, TINY.vocab_size, (2, 6)), jnp.int32
        )
        want, _ = forward(merge_lora(params, lora, 8.0), TINY, ids)
        got, _ = forward(restored, cfg2, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


class TestMistralGolden:
    """Mistral is Llama-structured (no bias, untied) plus a recorded sliding
    window. Within the window, full attention is exact — golden-checked
    against transformers' MistralForCausalLM."""

    def _configs(self):
        from distrl_llm_tpu.models.configs import ModelConfig

        hf_cfg = transformers.MistralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, rope_theta=10000.0, rms_norm_eps=1e-5,
            sliding_window=64, tie_word_embeddings=False,
            attention_dropout=0.0,
        )
        ours = ModelConfig.from_hf_config(hf_cfg)
        assert ours.sliding_window == 64
        assert not ours.attention_bias
        return hf_cfg, ours

    def test_golden_logits(self):
        hf_cfg, cfg = self._configs()
        torch.manual_seed(1)
        model = transformers.MistralForCausalLM(hf_cfg).eval()
        sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
        from distrl_llm_tpu.models.loading import params_from_state_dict

        params = params_from_state_dict(sd, cfg, dtype=np.float32)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(2, 17))
        ours, _ = forward(params, cfg, jnp.asarray(ids))
        theirs = hf_logits(model, ids)
        np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-4)

    def test_window_guard(self):
        """Sequences past the window must fail loudly, not silently run full
        attention where the checkpoint was trained with SWA."""
        import jax

        _, cfg = self._configs()
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.models import init_params

        params = init_params(jax.random.PRNGKey(0), cfg)
        ids = np.random.default_rng(0).integers(1, cfg.vocab_size, (1, 70))
        with pytest.raises(ValueError, match="sliding_window"):
            forward(params, cfg, jnp.asarray(ids))
        with pytest.raises(ValueError, match="sliding_window"):
            GenerationEngine(
                cfg, max_prompt_tokens=40, max_new_tokens=40,
                eos_token_ids=[1], pad_token_id=0,
            )

    def test_preset_mapping(self):
        from distrl_llm_tpu.models.configs import (
            GEMMA_7B, MISTRAL_7B, preset_for_model_name,
        )

        assert preset_for_model_name("mistralai/Mistral-7B-Instruct-v0.1") is MISTRAL_7B
        assert preset_for_model_name("google/gemma-7b-it") is GEMMA_7B


class TestLlamaGolden:
    """Llama-3-style config: GQA, untied embeddings, no attention bias,
    large rope_theta. Golden-checked against transformers'
    LlamaForCausalLM (the LLAMA3_8B preset's family — models/configs.py)."""

    def _configs(self):
        from distrl_llm_tpu.models.configs import ModelConfig

        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, rope_theta=500000.0, rms_norm_eps=1e-5,
            tie_word_embeddings=False, attention_bias=False,
            attention_dropout=0.0,
        )
        ours = ModelConfig.from_hf_config(hf_cfg)
        assert not ours.attention_bias
        assert not ours.tie_word_embeddings
        assert ours.rope_theta == 500000.0
        return hf_cfg, ours

    def test_golden_logits(self):
        hf_cfg, cfg = self._configs()
        torch.manual_seed(2)
        model = transformers.LlamaForCausalLM(hf_cfg).eval()
        sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
        from distrl_llm_tpu.models.loading import params_from_state_dict

        params = params_from_state_dict(sd, cfg, dtype=np.float32)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(2, 17))
        ours, _ = forward(params, cfg, jnp.asarray(ids))
        theirs = hf_logits(model, ids)
        np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-4)

    def test_engine_decode(self):
        """Greedy engine decode matches transformers' greedy generate on the
        same checkpoint — the rollout path end-to-end for the family."""
        import jax

        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine import GenerationEngine
        from distrl_llm_tpu.models.loading import params_from_state_dict

        hf_cfg, cfg = self._configs()
        torch.manual_seed(2)
        model = transformers.LlamaForCausalLM(hf_cfg).eval()
        sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
        params = params_from_state_dict(sd, cfg, dtype=np.float32)
        rng = np.random.default_rng(1)
        ids = rng.integers(1, cfg.vocab_size, size=(1, 8))
        with torch.no_grad():
            want = model.generate(
                torch.tensor(ids), max_new_tokens=6, do_sample=False,
                eos_token_id=None, pad_token_id=0,
            ).numpy()[:, 8:]
        engine = GenerationEngine(
            cfg, max_prompt_tokens=8, max_new_tokens=6,
            # unreachable eos: force the full 6 greedy steps, like hf above
            eos_token_ids=[cfg.vocab_size - 1 + 10**6], pad_token_id=0,
        )
        got = engine.generate(
            params, None, ids.astype(np.int32), np.ones_like(ids, np.int32),
            SamplingConfig(max_tokens=6, temperature=0.0, top_p=1.0, n=1),
            jax.random.PRNGKey(0),
        ).tokens[:, 0, :]
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_preset_mapping(self):
        from distrl_llm_tpu.models.configs import (
            LLAMA3_8B, preset_for_model_name,
        )

        assert (
            preset_for_model_name("meta-llama/Meta-Llama-3-8B-Instruct")
            is LLAMA3_8B
        )


class TestGemmaGolden:
    """Gemma differs in every knob ModelConfig added for it: tanh-GELU MLP,
    RMSNorm (1+w) offset, sqrt(hidden) embedding scaling, tied embeddings,
    MQA-style few kv heads. Golden-checked against transformers' torch
    GemmaForCausalLM."""

    @pytest.fixture(scope="class")
    def golden_gemma(self):
        from distrl_llm_tpu.models.configs import ModelConfig

        hf_cfg = transformers.GemmaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
            head_dim=16, rope_theta=10000.0, rms_norm_eps=1e-6,
            tie_word_embeddings=True, hidden_activation="gelu_pytorch_tanh",
            attention_dropout=0.0,
        )
        cfg = ModelConfig.from_hf_config(hf_cfg)
        assert cfg.hidden_act == "gelu_tanh"
        assert cfg.rmsnorm_offset and cfg.scale_embeddings
        assert cfg.tie_word_embeddings
        torch.manual_seed(2)
        model = transformers.GemmaForCausalLM(hf_cfg).eval()
        sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
        from distrl_llm_tpu.models.loading import params_from_state_dict

        params = params_from_state_dict(sd, cfg, dtype=np.float32)
        return model, params, cfg

    def test_golden_logits(self, golden_gemma):
        model, params, cfg = golden_gemma
        rng = np.random.default_rng(3)
        ids = rng.integers(0, cfg.vocab_size, size=(2, 13))
        ours, _ = forward(params, cfg, jnp.asarray(ids))
        theirs = hf_logits(model, ids)
        np.testing.assert_allclose(np.asarray(ours), theirs, atol=3e-4, rtol=3e-4)

    def test_engine_decode(self, golden_gemma):
        """Greedy engine decode matches torch greedy generation."""
        import jax

        model, params, cfg = golden_gemma
        from distrl_llm_tpu.config import SamplingConfig
        from distrl_llm_tpu.engine import GenerationEngine

        rng = np.random.default_rng(4)
        ids = rng.integers(1, cfg.vocab_size, size=(1, 8))
        engine = GenerationEngine(
            cfg, max_prompt_tokens=8, max_new_tokens=5,
            eos_token_ids=[cfg.vocab_size - 1], pad_token_id=0,
            cache_dtype=jnp.float32,
        )
        import jax as _jax

        res = engine.generate(
            params, None, ids, np.ones_like(ids),
            SamplingConfig(max_tokens=5, temperature=0.0, n=1),
            _jax.random.PRNGKey(0),
        )
        with torch.no_grad():
            out = model.generate(
                torch.tensor(ids), max_new_tokens=5, do_sample=False,
                pad_token_id=0,
            )
        np.testing.assert_array_equal(res.tokens[0, 0], out[0, 8:].numpy())


class TestFamilyReviewRegressions:
    @pytest.mark.slow
    def test_gemma_snapshot_roundtrip_keeps_family(self, tmp_path):
        """HF snapshot export must label Gemma checkpoints model_type='gemma'
        so reload keeps the (1+w) norm offset and embedding scaling (review:
        the old caller hardcoded qwen2/llama)."""
        import jax

        from distrl_llm_tpu.models import init_params
        from distrl_llm_tpu.models.configs import ModelConfig
        from distrl_llm_tpu.models.loading import load_pretrained, save_hf_checkpoint

        cfg = ModelConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=1, head_dim=16,
            tie_word_embeddings=True, hidden_act="gelu_tanh",
            rmsnorm_offset=True, scale_embeddings=True,
        )
        assert cfg.model_type == "gemma"
        params = init_params(jax.random.PRNGKey(0), cfg)
        path = str(tmp_path / "snap")
        save_hf_checkpoint(params, cfg, path)
        restored, cfg2 = load_pretrained(path)
        assert cfg2.rmsnorm_offset and cfg2.scale_embeddings
        assert cfg2.hidden_act == "gelu_tanh"
        ids = jnp.asarray([[1, 2, 3]])
        want, _ = forward(params, cfg, ids)
        got, _ = forward(restored, cfg2, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_mistral_snapshot_roundtrip_keeps_window(self, tmp_path):
        import jax

        from distrl_llm_tpu.models import init_params
        from distrl_llm_tpu.models.configs import ModelConfig
        from distrl_llm_tpu.models.loading import load_pretrained, save_hf_checkpoint

        cfg = ModelConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=1, head_dim=16,
            sliding_window=128,
        )
        assert cfg.model_type == "mistral"
        path = str(tmp_path / "snap")
        save_hf_checkpoint(init_params(jax.random.PRNGKey(0), cfg), cfg, path)
        _, cfg2 = load_pretrained(path)
        assert cfg2.sliding_window == 128

    def test_gemma2_rejected_loudly(self):
        """Gemma-2/3 state dicts carry norms/softcapping the mapper would
        silently drop — from_hf_config must refuse them."""
        from distrl_llm_tpu.models.configs import ModelConfig

        class _NS:
            model_type = "gemma2"
            vocab_size = 64
            hidden_size = 32
            intermediate_size = 64
            num_hidden_layers = 2
            num_attention_heads = 2

        with pytest.raises(ValueError, match="gemma2"):
            ModelConfig.from_hf_config(_NS())

    def test_preset_does_not_claim_mixtral_or_v02(self):
        from distrl_llm_tpu.models.configs import preset_for_model_name

        assert preset_for_model_name("mistralai/Mixtral-8x7B-Instruct-v0.1") is None
        assert preset_for_model_name("mistralai/Mistral-7B-Instruct-v0.2") is None
        assert preset_for_model_name("mistralai/Mistral-7B-Instruct-v0.3") is None


class TestR1DistillPreset:
    def test_r1_distill_models_refuse_presets(self):
        """Reference recipe 4's models match preset tensor dims but NOT RoPE
        (R1-Distill-Qwen-7B derives from Qwen2.5-Math-7B: rope_theta 1e4 vs
        the preset's 1e6) — a preset would silently produce garbage logits,
        so every distill id must force config.json-driven loading (review)."""
        from distrl_llm_tpu.models.configs import preset_for_model_name

        assert preset_for_model_name(
            "deepseek-ai/DeepSeek-R1-Distill-Qwen-7B") is None
        assert preset_for_model_name(
            "deepseek-ai/DeepSeek-R1-Distill-Qwen-1.5B") is None
        assert preset_for_model_name(
            "deepseek-ai/DeepSeek-R1-Distill-Llama-8B") is None
