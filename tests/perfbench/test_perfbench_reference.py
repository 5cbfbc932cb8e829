"""``perfbench/reference.py`` against the program's own forward and losses at
tiny size on the CPU: the same arithmetic in float32, so they agree to
round-off. (On the chip, at the published widths, the comparison runs the other
way: the program is held to the reference, ``perfbench/correct.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss
from distrl_llm_tpu.models import TINY, ModelConfig, forward, init_lora_params
from perfbench import reference, weights

TIED = ModelConfig(
    vocab_size=200, hidden_size=48, intermediate_size=96, num_layers=3,
    num_heads=6, num_kv_heads=2, head_dim=8, rope_theta=1e6,
    attention_bias=True, tie_word_embeddings=True,
)


def setup(cfg, seed, with_lora):
    params = weights.make_base_params(cfg, "float32", seed)
    lora = None
    if with_lora:
        lora = weights.randomize_lora_b(
            init_lora_params(jax.random.PRNGKey(seed), cfg, 4), seed)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (3, 24), 0, cfg.vocab_size)
    # a left-padded row, a right-padded row, a full row
    mask = jnp.ones((3, 24), jnp.int32).at[0, :7].set(0).at[1, 19:].set(0)
    return params, lora, ids, mask


@pytest.mark.parametrize("cfg,with_lora", [
    (TINY, False), (TINY, True), (TIED, True),
], ids=["untied", "untied+lora", "tied+lora"])
def test_logprobs_match_the_programs_forward(cfg, with_lora):
    params, lora, ids, mask = setup(cfg, 3, with_lora)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, ids, attention_mask=mask, lora=lora,
                            lora_scale=2.0)
    want = jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1)[:, :-1], ids[:, 1:, None], -1)[..., 0]
    got = reference.next_token_logprobs(
        params, cfg, ids, mask, lora=lora, lora_scale=2.0)
    both_real = (mask[:, :-1] * mask[:, 1:]) > 0
    np.testing.assert_allclose(
        np.where(both_real, got, 0), np.where(both_real, want, 0), atol=2e-5)


def test_seeded_weights_have_the_terms_the_check_must_see():
    """The program's constructors zero every bias and every adapter b; the
    benchmark's values do not, so a dropped bias or adapter moves the logits."""
    params, lora, ids, mask = setup(TINY, 5, True)
    assert float(jnp.abs(params["layers"]["bq"]).mean()) > 0.1
    assert all(float(jnp.abs(t["b"]).mean()) > 0 for t in lora["layers"].values())
    full = reference.next_token_logprobs(params, TINY, ids, mask, lora=lora, lora_scale=2.0)
    no_lora = reference.next_token_logprobs(params, TINY, ids, mask)
    no_bias = {**params, "layers": {
        k: (jnp.zeros_like(v) if k in ("bq", "bk", "bv") else v)
        for k, v in params["layers"].items()}}
    dropped = reference.next_token_logprobs(no_bias, TINY, ids, mask, lora=lora, lora_scale=2.0)
    assert float(jnp.abs(full - no_lora).mean()) > 0.01
    assert float(jnp.abs(full - dropped).mean()) > 0.01


def test_same_seed_same_weights():
    a = weights.make_base_params(TINY, "float32", 11)
    b = weights.make_base_params(TINY, "float32", 11)
    c = weights.make_base_params(TINY, "float32", 12)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(jnp.array_equal, a, b))
    assert not jnp.array_equal(a["embed"], c["embed"])


def test_pg_loss_and_adapter_gradient_match_the_programs():
    cfg = TINY
    params, lora, _, _ = setup(cfg, 7, True)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)
    answer = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 14)), jnp.int32)
    ones_p, ones_a = jnp.ones_like(prompt), jnp.ones_like(answer)
    coeffs = jnp.asarray([0.5, -0.75], jnp.float32)

    def program_loss(lo):
        with jax.default_matmul_precision("highest"):
            logp = answer_logprobs(params, cfg, prompt, ones_p, answer, ones_a,
                                   lora=lo, lora_scale=2.0, remat=False)
        return pg_loss(logp, ones_a.astype(jnp.float32), coeffs)

    want_loss, want_grad = jax.value_and_grad(program_loss)(lora)
    ids = jnp.concatenate([prompt, answer], axis=1)
    answer_cols = jnp.concatenate([jnp.zeros_like(prompt), ones_a], axis=1)
    loss, grad = reference.pg_loss_and_lora_grad(
        params, cfg, lora, 2.0, ids, jnp.ones_like(ids), answer_cols, coeffs)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grad), jax.tree_util.tree_leaves(want_grad)):
        np.testing.assert_allclose(g, w, atol=1e-6, rtol=1e-4)


def test_another_family_is_refused():
    import dataclasses

    gelu = dataclasses.replace(TINY, hidden_act="gelu_tanh")
    params, _, ids, mask = setup(TINY, 1, False)
    with pytest.raises(NotImplementedError, match="brings its own reference"):
        reference.next_token_logprobs(params, gelu, ids, mask)
