"""Train-step tests: grad-accum invariance, skip semantics, dp-sharded psum
equivalence on the virtual mesh, batch prep shapes (SURVEY §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distrl_llm_tpu.learner import (
    UpdateBatch,
    make_optimizer,
    make_train_step,
    prepare_update_batch,
)
from distrl_llm_tpu.models import TINY, init_lora_params, init_params


class FakeTok:
    pad_token_id = 0

    def encode(self, text):
        return [ord(c) % 250 + 1 for c in text]

    def decode(self, ids):
        return "".join(chr(i) for i in ids)


def make_batch(rng, n, p=6, t=5, coeffs=None):
    ids = rng.integers(1, TINY.vocab_size, size=(n, p + t))
    return UpdateBatch(
        prompt_ids=jnp.asarray(ids[:, :p]),
        prompt_mask=jnp.ones((n, p), jnp.int32),
        answer_ids=jnp.asarray(ids[:, p:]),
        answer_mask=jnp.ones((n, t), jnp.int32),
        coeffs=jnp.asarray(coeffs if coeffs is not None else rng.normal(size=n), jnp.float32),
        sample_mask=jnp.ones(n, jnp.float32),
    )


@pytest.fixture(scope="module")
def model():
    base = init_params(jax.random.PRNGKey(0), TINY)
    lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
    return base, lora


class TestGradAccum:
    @pytest.mark.slow
    @pytest.mark.parametrize("learner_type", ["pg", "grpo"])
    def test_micro_size_invariance(self, model, learner_type):
        """One step with micro=8 must equal one step with micro=4 (same total
        batch): the /num_batches scaling makes accumulation size-invariant
        (distributed_actor.py:382)."""
        base, lora = model
        rng = np.random.default_rng(0)
        batch = make_batch(rng, 8)
        results = []
        for micro in (8, 4, 2):
            step = make_train_step(
                TINY, learner_type=learner_type,
                optimizer=make_optimizer(1e-2, use_8bit=False),
                lora_scale=0.5, micro_size=micro, remat=False, donate=False,
            )
            opt_state = make_optimizer(1e-2, use_8bit=False).init(lora)
            new_lora, _, loss = step(lora, opt_state, base, batch)
            results.append((new_lora, float(loss)))
        # microbatch-mean grads are identical across accumulation factors
        for other, _ in results[1:]:
            for a, b in zip(
                jax.tree_util.tree_leaves(results[0][0]), jax.tree_util.tree_leaves(other)
            ):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    @pytest.mark.slow
    def test_loss_sum_parity(self, model):
        """Returned loss = Σ unscaled microbatch losses (reference total_loss,
        distributed_actor.py:387–389)."""
        base, lora = model
        rng = np.random.default_rng(1)
        batch = make_batch(rng, 4)
        from distrl_llm_tpu.learner.losses import answer_logprobs, pg_loss

        step = make_train_step(
            TINY, learner_type="pg", optimizer=make_optimizer(1e-2, use_8bit=False),
            lora_scale=0.5, micro_size=2, remat=False, donate=False,
        )
        opt_state = make_optimizer(1e-2, use_8bit=False).init(lora)
        _, _, loss = step(lora, opt_state, base, batch)

        manual = 0.0
        for i in range(2):
            sl = slice(2 * i, 2 * i + 2)
            lp = answer_logprobs(
                base, TINY, batch.prompt_ids[sl], batch.prompt_mask[sl],
                batch.answer_ids[sl], batch.answer_mask[sl], lora=lora,
                lora_scale=0.5, remat=False,
            )
            manual += float(
                pg_loss(lp, batch.answer_mask[sl].astype(jnp.float32),
                        batch.coeffs[sl], batch.sample_mask[sl])
            )
        assert float(loss) == pytest.approx(manual, rel=1e-4)


class TestSkipSemantics:
    @pytest.mark.slow
    def test_all_zero_microbatch_contributes_nothing(self, model):
        base, lora = model
        rng = np.random.default_rng(2)
        # microbatch 0: zero coeffs; microbatch 1: nonzero
        coeffs = np.array([0.0, 0.0, 1.0, -1.0])
        batch = make_batch(rng, 4, coeffs=coeffs)
        opt = make_optimizer(1e-2, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=opt, lora_scale=0.5,
            micro_size=2, skip_semantics="all_zero", remat=False, donate=False,
        )
        lora1, _, _ = step(lora, opt.init(lora), base, batch)

        # same update with only the nonzero microbatch but same denominator (2
        # real microbatches) — equality means mb0 was skipped
        batch_b = make_batch(rng, 4, coeffs=np.array([0.0, 0.0, 1.0, -1.0]))
        batch_b = batch_b._replace(
            prompt_ids=batch.prompt_ids, prompt_mask=batch.prompt_mask,
            answer_ids=batch.answer_ids, answer_mask=batch.answer_mask,
        )
        lora2, _, _ = step(lora, opt.init(lora), base, batch_b)
        for a, b in zip(jax.tree_util.tree_leaves(lora1), jax.tree_util.tree_leaves(lora2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)

    @pytest.mark.slow
    def test_any_zero_bug_parity_mode(self, model):
        """skip_semantics='any_zero' reproduces the reference bug: one zero
        coeff poisons the whole microbatch (SURVEY §3.6.3)."""
        base, lora = model
        rng = np.random.default_rng(3)
        coeffs = np.array([0.0, 5.0])  # one zero → whole microbatch skipped
        batch = make_batch(rng, 2, coeffs=coeffs)
        opt = make_optimizer(1e-2, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=opt, lora_scale=0.5,
            micro_size=2, skip_semantics="any_zero", remat=False, donate=False,
        )
        new_lora, _, loss = step(lora, opt.init(lora), base, batch)
        assert float(loss) == 0.0
        # B factors start at zero and grads are zero → lora unchanged
        for a, b in zip(jax.tree_util.tree_leaves(lora), jax.tree_util.tree_leaves(new_lora)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


class TestDataParallelStep:
    def test_dp_sharded_step_matches_single_device(self, model):
        """The mesh-dp path (GSPMD-inserted psum over ICI) must produce the
        same update as the unsharded step — this is the multi-learner gradient
        merge of SURVEY §3.4 done right."""
        base, lora = model
        rng = np.random.default_rng(4)
        batch = make_batch(rng, 8)
        opt = make_optimizer(1e-2, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="grpo", optimizer=opt, lora_scale=0.5,
            micro_size=2, remat=False, donate=False,
        )
        expected, _, expected_loss = step(lora, opt.init(lora), base, batch)

        from distrl_llm_tpu.parallel.mesh import _make_mesh
        mesh = _make_mesh(jax.devices()[:4], 1, 1, 1)  # dp=4

        shard = lambda x: jax.device_put(x, NamedSharding(mesh, P("dp")))
        repl = lambda t: jax.device_put(t, NamedSharding(mesh, P()))
        batch_sh = jax.tree_util.tree_map(shard, batch)
        lora_sh, base_sh = repl(lora), repl(base)
        opt_sh = opt.init(lora_sh)
        got, _, got_loss = step(lora_sh, opt_sh, base_sh, batch_sh)
        # NOTE: microbatching scans over the dp-sharded leading axis; with dp=4
        # each shard sees its quarter — num_micro stays global because shapes
        # are global under GSPMD. Results must match exactly.
        for a, b in zip(jax.tree_util.tree_leaves(expected), jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        assert float(got_loss) == pytest.approx(float(expected_loss), rel=1e-5)


class TestPrepareUpdateBatch:
    def test_shapes_and_padding(self):
        tok = FakeTok()
        batch = prepare_update_batch(
            tok, ["hello", "x"], ["ans", "two"],
            np.array([1.0, -0.5]), max_prompt_tokens=8, max_new_tokens=6,
            micro_size=4,
        )
        assert batch.prompt_ids.shape == (4, 8)
        assert batch.answer_ids.shape == (4, 6)
        np.testing.assert_array_equal(np.asarray(batch.sample_mask), [1, 1, 0, 0])
        # left padding: mask ends with 1s
        pm = np.asarray(batch.prompt_mask)
        assert pm[0, -1] == 1 and pm[0, 0] == 0
        # right padding: mask starts with 1s
        am = np.asarray(batch.answer_mask)
        assert am[1, 0] == 1 and am[1, -1] == 0

    def test_truncation_keeps_leading_tokens(self):
        tok = FakeTok()
        long = "abcdefghijklmnop"
        batch = prepare_update_batch(
            tok, [long], [long], np.array([1.0]),
            max_prompt_tokens=4, max_new_tokens=4, micro_size=1,
        )
        expected = [ord(c) % 250 + 1 for c in long[:4]]
        np.testing.assert_array_equal(np.asarray(batch.prompt_ids)[0], expected)
        np.testing.assert_array_equal(np.asarray(batch.answer_ids)[0], expected)


class TestAnswerBuckets:
    """learner_len_buckets (the engine's prompt-bucket idea on the update
    step): each update runs at the smallest bucket holding the batch's
    longest real answer — and the truncation is EXACT, because trailing
    all-masked columns contribute nothing to the loss and are causally
    invisible to real positions. Reference contrast: distributed_actor.py
    :224–229 pads every row to the full window."""

    def test_bucket_selection_and_slicing(self):
        tok = FakeTok()
        batch = prepare_update_batch(
            tok, ["pp", "q"], ["abc", "abcdef"], np.array([1.0, 1.0]),
            max_prompt_tokens=8, max_new_tokens=32, micro_size=2,
            answer_buckets=(4, 8, 16),
        )
        # longest real answer = 6 tokens -> bucket 8
        assert batch.answer_ids.shape == (2, 8)
        assert batch.answer_mask.shape == (2, 8)
        np.testing.assert_array_equal(
            np.asarray(batch.answer_mask).sum(axis=1), [3, 6]
        )

    def test_no_bucket_large_enough_falls_back_to_full_width(self):
        tok = FakeTok()
        batch = prepare_update_batch(
            tok, ["p"], ["abcdefghijkl"], np.array([1.0]),
            max_prompt_tokens=8, max_new_tokens=16, micro_size=1,
            answer_buckets=(4, 8),
        )
        assert batch.answer_ids.shape == (1, 16)

    def test_raw_rollout_path_slices_behavior_logps(self):
        tok = FakeTok()
        rng = np.random.default_rng(0)
        t_eng = 32
        raw = {
            "answer_tokens": rng.integers(1, 100, (2, t_eng)),
            "behavior_logps": rng.normal(size=(2, t_eng)).astype(np.float32),
            "lengths": np.array([3, 6]),
        }
        batch = prepare_update_batch(
            tok, ["p", "q"], ["", ""], np.array([1.0, 1.0]),
            max_prompt_tokens=8, max_new_tokens=t_eng, micro_size=2,
            raw_rollout=raw, answer_buckets=(8,),
        )
        assert batch.answer_ids.shape == (2, 8)
        assert batch.behavior_logps.shape == (2, 8)
        np.testing.assert_allclose(
            np.asarray(batch.behavior_logps)[1, :6],
            raw["behavior_logps"][1, :6],
        )

    def test_prompt_bucket_slices_left_padded_side(self):
        tok = FakeTok()
        batch = prepare_update_batch(
            tok, ["abc", "abcdef"], ["x", "y"], np.array([1.0, 1.0]),
            max_prompt_tokens=32, max_new_tokens=4, micro_size=2,
            prompt_buckets=(8, 16),
        )
        # longest real prompt = 6 -> bucket 8; left padding: real ids at END
        assert batch.prompt_ids.shape == (2, 8)
        pm = np.asarray(batch.prompt_mask)
        np.testing.assert_array_equal(pm.sum(axis=1), [3, 6])
        assert pm[0, -1] == 1 and pm[0, 0] == 0

    @pytest.mark.slow
    def test_prompt_bucket_loss_matches_full_width(self):
        """Dropping leading all-masked prompt columns shifts every position
        in a row by the same constant; RoPE attention depends on relative
        distance only, so the step must agree with the full-width step up
        to float round-off."""
        import jax

        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import (
            UpdateBatch, make_train_step,
        )
        from distrl_llm_tpu.models import TINY, init_lora_params, init_params

        base = init_params(jax.random.PRNGKey(0), TINY)
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        rng = np.random.default_rng(0)
        n, p_full, p_cut, t_len = 4, 16, 8, 4
        p_lens = np.array([3, 8, 5, 1])
        pmask_full = (
            np.arange(p_full)[None, :] >= p_full - p_lens[:, None]
        ).astype(np.int32)  # left-padded
        full = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, p_full)), jnp.int32),
            prompt_mask=jnp.asarray(pmask_full),
            answer_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, t_len)), jnp.int32),
            answer_mask=jnp.ones((n, t_len), jnp.int32),
            coeffs=jnp.asarray(rng.normal(size=n), jnp.float32),
            sample_mask=jnp.ones((n,), jnp.float32),
        )
        cut = full._replace(
            prompt_ids=full.prompt_ids[:, -p_cut:],
            prompt_mask=full.prompt_mask[:, -p_cut:],
        )
        opt = make_optimizer(1e-2, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="grpo", optimizer=opt, lora_scale=0.5,
            micro_size=2, remat=False, donate=False, logit_chunk=4,
        )
        _, _, loss_f = step(lora, opt.init(lora), base, full)
        _, _, loss_c = step(lora, opt.init(lora), base, cut)
        assert float(loss_c) == pytest.approx(float(loss_f), abs=2e-5)

    @pytest.mark.slow
    def test_loss_and_update_exactly_match_full_width(self):
        """The headline property: a bucketed step must produce the SAME
        loss and the SAME updated adapter as the full-width step (masked
        trailing columns are pure padding)."""
        import jax

        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import (
            UpdateBatch, make_train_step,
        )
        from distrl_llm_tpu.models import TINY, init_lora_params, init_params

        base = init_params(jax.random.PRNGKey(0), TINY)
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        rng = np.random.default_rng(0)
        n, p_len, t_full, t_cut = 4, 8, 16, 8
        lens = np.array([3, 8, 5, 1])
        answer_mask_full = (
            np.arange(t_full)[None, :] < lens[:, None]
        ).astype(np.int32)
        full = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, p_len)), jnp.int32),
            prompt_mask=jnp.ones((n, p_len), jnp.int32),
            answer_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, t_full)), jnp.int32),
            answer_mask=jnp.asarray(answer_mask_full),
            coeffs=jnp.asarray(rng.normal(size=n), jnp.float32),
            sample_mask=jnp.ones((n,), jnp.float32),
        )
        cut = full._replace(
            answer_ids=full.answer_ids[:, :t_cut],
            answer_mask=full.answer_mask[:, :t_cut],
        )
        opt = make_optimizer(1e-2, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="grpo", optimizer=opt, lora_scale=0.5,
            micro_size=2, remat=False, donate=False, logit_chunk=4,
        )
        lora_f, _, loss_f = step(lora, opt.init(lora), base, full)
        lora_c, _, loss_c = step(lora, opt.init(lora), base, cut)
        assert float(loss_c) == pytest.approx(float(loss_f), abs=1e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(lora_f), jax.tree_util.tree_leaves(lora_c)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6
            )


class TestLoraDropout:
    """lora_dropout is implemented, not a dead flag:
    peft-style adapter-input dropout in the learner forward."""

    def _setup(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
        from distrl_llm_tpu.models import TINY, init_lora_params, init_params

        base = init_params(jax.random.PRNGKey(0), TINY)
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
        # nonzero B so the adapter actually contributes (dropout then matters)
        lora = jax.tree_util.tree_map(
            lambda x: x + 0.01 if x.ndim == 3 else x, lora
        )
        rng = np.random.default_rng(0)
        n, p_len, t_len = 4, 8, 8
        batch = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, p_len)), jnp.int32),
            prompt_mask=jnp.ones((n, p_len), jnp.int32),
            answer_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, t_len)), jnp.int32),
            answer_mask=jnp.ones((n, t_len), jnp.int32),
            coeffs=jnp.asarray(rng.normal(size=n), jnp.float32),
            sample_mask=jnp.ones((n,), jnp.float32),
        )
        opt = make_optimizer(1e-3, use_8bit=False)
        return base, lora, batch, opt

    @pytest.mark.slow
    def test_dropout_changes_loss_and_zero_rate_does_not(self):
        import jax
        import numpy as np

        from distrl_llm_tpu.learner.train_step import make_train_step
        from distrl_llm_tpu.models.lora import lora_scale

        base, lora, batch, opt = self._setup()
        kw = dict(
            learner_type="pg", optimizer=opt, lora_scale=lora_scale(4, 8.0),
            micro_size=2, donate=False,
        )
        from distrl_llm_tpu.models import TINY

        step_plain = make_train_step(TINY, **kw)
        step_drop = make_train_step(TINY, lora_dropout=0.5, **kw)
        opt_state = opt.init(lora)
        _, _, loss_ref = step_plain(lora, opt_state, base, batch)
        # rate 0 with an rng supplied == no dropout at all
        _, _, loss_zero = step_plain(lora, opt_state, base, batch, jax.random.PRNGKey(3))
        np.testing.assert_allclose(float(loss_ref), float(loss_zero), rtol=1e-6)
        # rate 0.5 with an rng → different masks → different loss
        _, _, loss_a = step_drop(lora, opt_state, base, batch, jax.random.PRNGKey(3))
        _, _, loss_b = step_drop(lora, opt_state, base, batch, jax.random.PRNGKey(4))
        assert float(loss_a) != float(loss_ref)
        assert float(loss_a) != float(loss_b)  # key-dependent masks
        # deterministic per key
        _, _, loss_a2 = step_drop(lora, opt_state, base, batch, jax.random.PRNGKey(3))
        np.testing.assert_allclose(float(loss_a), float(loss_a2), rtol=1e-6)


class TestLearningDynamics:
    """Repeated updates on one fixed batch with positive coefficients must
    drive the (negative logprob-weighted) PG loss down — the de-facto
    integration check behind the reference's 'reward curve goes up' runs."""

    @pytest.mark.slow
    def test_repeated_steps_reduce_pg_loss(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from distrl_llm_tpu.learner.optim import make_optimizer
        from distrl_llm_tpu.learner.train_step import UpdateBatch, make_train_step
        from distrl_llm_tpu.models import TINY, init_lora_params, init_params
        from distrl_llm_tpu.models.lora import lora_scale

        base = init_params(jax.random.PRNGKey(0), TINY)
        lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=8)
        rng = np.random.default_rng(0)
        n, p_len, t_len = 4, 8, 8
        batch = UpdateBatch(
            prompt_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, p_len)), jnp.int32),
            prompt_mask=jnp.ones((n, p_len), jnp.int32),
            answer_ids=jnp.asarray(rng.integers(1, TINY.vocab_size, (n, t_len)), jnp.int32),
            answer_mask=jnp.ones((n, t_len), jnp.int32),
            coeffs=jnp.ones((n,), jnp.float32),  # uniformly "good" answers
            sample_mask=jnp.ones((n,), jnp.float32),
        )
        optimizer = make_optimizer(5e-3, use_8bit=True)
        opt_state = optimizer.init(lora)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=optimizer,
            lora_scale=lora_scale(8, 16.0), micro_size=2, donate=False,
        )
        losses = []
        for _ in range(6):
            lora, opt_state, loss = step(lora, opt_state, base, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses


class TestTensorParallelStep:
    """Reference recipes 2 and 5 train with TP (and FSDP) learner shardings; the
    update must be invariant to them. Base params take the Megatron specs
    (parallel/partition.py), the batch shards over dp, and the LoRA update
    must equal the single-device step's."""

    @pytest.mark.parametrize("tp,fsdp,dp", [
        pytest.param(2, 1, 4, marks=pytest.mark.slow),
        (2, 2, 2),
        pytest.param(4, 2, 1, marks=pytest.mark.slow),
    ])
    def test_tp_fsdp_sharded_step_matches_single_device(self, model, tp, fsdp, dp):
        from distrl_llm_tpu.parallel import param_specs, shard_tree
        from distrl_llm_tpu.parallel.mesh import _make_mesh
        from distrl_llm_tpu.parallel.partition import shard_opt_state

        base, lora = model
        rng = np.random.default_rng(5)
        batch = make_batch(rng, 8)
        opt = make_optimizer(1e-2, use_8bit=False)
        step = make_train_step(
            TINY, learner_type="pg", optimizer=opt, lora_scale=0.5,
            micro_size=4, remat=False, donate=False,
            logit_chunk=4,  # chunked CE must also be sharding-invariant
        )
        expected, _, expected_loss = step(lora, opt.init(lora), base, batch)

        mesh = _make_mesh(jax.devices()[: tp * fsdp * dp], tp, 1, fsdp)
        base_sh = shard_tree(base, mesh, param_specs(base))
        lora_sh = shard_tree(lora, mesh)
        opt_sh = shard_opt_state(opt.init(lora_sh), mesh)
        shard_rows = lambda x: jax.device_put(
            x, NamedSharding(mesh, P("dp") if x.ndim == 1 else P("dp", None))
        )
        batch_sh = jax.tree_util.tree_map(shard_rows, batch)
        with mesh:
            got, _, got_loss = step(lora_sh, opt_sh, base_sh, batch_sh)
        for a, b in zip(
            jax.tree_util.tree_leaves(expected), jax.tree_util.tree_leaves(got)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
        assert float(got_loss) == pytest.approx(float(expected_loss), rel=1e-4)
