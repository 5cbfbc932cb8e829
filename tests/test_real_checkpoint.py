"""Real-format checkpoint realism.

The loader was previously exercised against state dicts synthesized by THIS
repo's own code paths; these tests make ``transformers`` itself write the
artifact — ``save_pretrained`` with safetensors sharding and an index file,
plus its own ``config.json`` — and push it through ``load_pretrained`` →
forward parity → one train step. That is the reference's load path
(distributed_actor.py:58–66: FastLanguageModel.from_pretrained on a hub
checkpoint) with the hub swapped for a locally-written but format-identical
directory (zero-egress environment).

The slow test repeats the load at the REAL Qwen2.5-0.5B geometry (the
flagship bench model): every stacked tensor must land with the exact shapes
``init_params(QWEN2_0_5B)`` produces, and the forward must reproduce the
torch model's logits.
"""

import json
import os

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distrl_llm_tpu.models import TINY, forward, init_lora_params  # noqa: E402
from distrl_llm_tpu.models.configs import QWEN2_0_5B  # noqa: E402
from distrl_llm_tpu.models.loading import load_pretrained  # noqa: E402


def _hf_qwen2_config(cfg, **overrides):
    kw = dict(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        max_position_embeddings=cfg.max_position_embeddings,
        rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        tie_word_embeddings=cfg.tie_word_embeddings,
        attention_dropout=0.0,
    )
    kw.update(overrides)
    return transformers.Qwen2Config(**kw)


def _save_real_artifact(model, path, max_shard_size):
    """transformers' own serialization — safetensors shards + index +
    config.json written by the library, not by this repo."""
    model.save_pretrained(path, safe_serialization=True, max_shard_size=max_shard_size)


class TestTransformersWrittenArtifact:
    @pytest.fixture(scope="class")
    def artifact(self, tmp_path_factory):
        torch.manual_seed(0)
        model = transformers.Qwen2ForCausalLM(_hf_qwen2_config(TINY)).eval()
        path = tmp_path_factory.mktemp("hf_ckpt")
        # tiny shard cap forces the MULTI-shard layout + index.json — the
        # format a real multi-GB hub checkpoint ships in
        _save_real_artifact(model, str(path), max_shard_size="200KB")
        return model, str(path)

    def test_sharded_index_layout(self, artifact):
        _, path = artifact
        shards = [f for f in os.listdir(path) if f.endswith(".safetensors")]
        assert len(shards) > 1, shards  # the index path is what's under test
        assert os.path.exists(os.path.join(path, "model.safetensors.index.json"))

    def test_load_and_logit_parity(self, artifact):
        model, path = artifact
        # cfg=None: ModelConfig must come from transformers' own config.json
        params, cfg = load_pretrained(path, cfg=None, dtype=np.float32)
        assert cfg.num_layers == TINY.num_layers
        assert cfg.num_kv_heads == TINY.num_kv_heads
        ids = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 12))
        with torch.no_grad():
            ref = model(input_ids=torch.tensor(ids)).logits.numpy()
        ours, _ = forward(params, cfg, jnp.asarray(ids, jnp.int32))
        np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-4, rtol=2e-3)

    def test_train_step_on_loaded_params(self, artifact):
        _, path = artifact
        params, cfg = load_pretrained(path, cfg=None, dtype=np.float32)
        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
        from distrl_llm_tpu.models.lora import lora_scale

        lora = init_lora_params(jax.random.PRNGKey(1), cfg, rank=4)
        optimizer = make_optimizer(2e-5, use_8bit=True)
        opt_state = optimizer.init(lora)
        step = make_train_step(
            cfg, learner_type="grpo", optimizer=optimizer,
            lora_scale=lora_scale(4, 8.0), micro_size=2, donate=False,
            logit_chunk=4,
        )
        rng = np.random.default_rng(1)
        rows, p_len, t_len = 2, 8, 8
        batch = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, p_len)), jnp.int32),
            prompt_mask=jnp.ones((rows, p_len), jnp.int32),
            answer_ids=jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, t_len)), jnp.int32),
            answer_mask=jnp.ones((rows, t_len), jnp.int32),
            coeffs=jnp.asarray(rng.normal(size=rows), jnp.float32),
            sample_mask=jnp.ones((rows,), jnp.float32),
        )
        _, _, loss = step(lora, opt_state, jax.device_put(params), batch)
        assert np.isfinite(float(loss))


@pytest.mark.slow
class TestRealGeometry05B:
    """The flagship 0.5B geometry through a transformers-written artifact:
    the HF-name mapping at the real layer count / GQA split / tied-embedding
    layout, not a shrunken stand-in."""

    def test_qwen25_05b_load_shapes_and_logits(self, tmp_path):
        cfg = QWEN2_0_5B
        torch.manual_seed(0)
        model = transformers.Qwen2ForCausalLM(_hf_qwen2_config(cfg)).eval()
        path = str(tmp_path / "qwen05b")
        _save_real_artifact(model, path, max_shard_size="900MB")  # ≥2 shards
        params, loaded_cfg = load_pretrained(path, cfg=None, dtype=np.float32)
        with open(os.path.join(path, "config.json")) as f:
            assert json.load(f)["num_key_value_heads"] == 2  # real GQA split
        assert loaded_cfg.hidden_size == cfg.hidden_size
        assert loaded_cfg.num_layers == cfg.num_layers
        assert loaded_cfg.tie_word_embeddings

        # exact shape agreement with this repo's random-init layout
        from distrl_llm_tpu.models import init_params

        ref_tree = jax.eval_shape(
            lambda k: init_params(k, cfg, dtype=jnp.float32),
            jax.random.PRNGKey(0),
        )
        got = {
            "/".join(map(str, kp)): np.asarray(v).shape
            for kp, v in jax.tree_util.tree_flatten_with_path(params)[0]
        }
        want = {
            "/".join(map(str, kp)): v.shape
            for kp, v in jax.tree_util.tree_flatten_with_path(ref_tree)[0]
        }
        assert got == want

        ids = np.random.default_rng(0).integers(1, cfg.vocab_size, (1, 8))
        with torch.no_grad():
            ref = model(input_ids=torch.tensor(ids)).logits.numpy()
        ours, _ = forward(params, loaded_cfg, jnp.asarray(ids, jnp.int32))
        np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-3, rtol=2e-2)
