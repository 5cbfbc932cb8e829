"""8-bit Adam state tests: quantization round-trip accuracy and trajectory
agreement with exact f32 Adam (the reference's Adam8bit claim: 'without losing
any accuracy' — distributed_actor.py:207–208)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from distrl_llm_tpu.learner.optim import (
    _LUT,
    _MIDS,
    BLOCK,
    STATE_FORMAT,
    _dequantize,
    _Quantized,
    _quantize,
    adam8bit,
    make_optimizer,
)


class TestQuantizeRoundtrip:
    @pytest.mark.parametrize("shape", [(7,), (256,), (1000,), (3, 5, 17)])
    def test_error_bounded_by_dynamic_code(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 0.01
        z = _quantize(x)
        back = _dequantize(z)
        assert back.shape == x.shape
        # dynamic code: the largest gap between adjacent levels is the top
        # decade's fraction step (0.9/63), so per-element error ≤ half of
        # that × the block's absmax ≤ global absmax
        bound = float(jnp.abs(x).max()) * (0.9 / 63 / 2) * 1.05
        assert float(jnp.abs(back - x).max()) <= bound

    def test_blockmax_is_exact(self):
        # 1.0 is a table level, so each block's largest element round-trips
        x = jnp.asarray([3.0, -0.5, 0.25] + [0.0] * 253)
        back = _dequantize(_quantize(x))
        assert float(back[0]) == 3.0

    def test_small_magnitudes_never_collapse_to_zero(self):
        """THE property that makes the 8-bit Adam stable (and the reason
        bitsandbytes uses a dynamic map): elements far below the block max
        must keep a nonzero representation — a linear absmax code rounds
        anything below 1/254 of the max to 0, and a second moment of 0 turns
        1/(sqrt(nu)+eps) into 1e8."""
        x = jnp.asarray([1.0, 1e-3, 1e-5, 3e-7] + [0.0] * 252)
        back = np.asarray(_dequantize(_quantize(x)))
        assert (back[:4] != 0).all(), back[:4]
        # relative error stays bounded where the decades have ≥4 levels
        # (deeper decades are coarser but still nonzero — the property that
        # matters for 1/sqrt(nu) stability)
        rel = np.abs(back[:3] - np.asarray(x[:3])) / np.asarray(x[:3])
        assert rel.max() < 0.5, rel

    def test_zeros_stay_zero(self):
        z = _quantize(jnp.zeros(300))
        np.testing.assert_array_equal(np.asarray(_dequantize(z)), 0.0)


class TestAdam8bit:
    def test_tracks_exact_adam(self):
        params = {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1}
        opt8 = adam8bit(1e-3)
        opt32 = optax.adam(1e-3)
        s8, s32 = opt8.init(params), opt32.init(params)
        p8 = p32 = params

        @jax.jit
        def grad_at(p, i):
            return {"w": jnp.sin(p["w"] + i * 0.1)}

        for i in range(20):
            g8, g32 = grad_at(p8, i), grad_at(p32, i)
            u8, s8 = opt8.update(g8, s8, p8)
            u32, s32 = opt32.update(g32, s32, p32)
            p8 = optax.apply_updates(p8, u8)
            p32 = optax.apply_updates(p32, u32)
        diff = float(jnp.abs(p8["w"] - p32["w"]).max())
        scale = float(jnp.abs(p32["w"] - params["w"]).max())
        assert diff < 0.05 * max(scale, 1e-6), (diff, scale)

    def test_jittable_update(self):
        params = {"a": jnp.ones((300,)), "b": {"c": jnp.ones((5, 5))}}
        opt = adam8bit(1e-2)
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            g = jax.tree_util.tree_map(jnp.ones_like, p)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s

        p1, state = step(params, state)
        p2, state = step(p1, state)
        assert float(p2["a"][0]) < float(p1["a"][0]) < 1.0

    def test_make_optimizer_switch(self):
        assert make_optimizer(1e-3, use_8bit=True) is not None
        assert make_optimizer(1e-3, use_8bit=False) is not None

    @pytest.mark.parametrize("shape", [(), (7,), (256,), (3, 5, 17), (2, 512)])
    def test_initial_state_is_the_code_of_zeros(self, shape):
        """``init`` writes the zero state without running the codec."""
        state = adam8bit(1e-3).init({"w": jnp.ones(shape)})
        want = _quantize(jnp.zeros(shape, jnp.float32))
        for got in (state.mu["w"], state.nu["w"]):
            assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
            for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_state_is_int8(self):
        params = {"w": jnp.ones((512,))}
        state = adam8bit(1e-3).init(params)
        assert state.mu["w"].q.dtype == jnp.int8
        assert state.nu["w"].q.dtype == jnp.int8


class TestNoSecondMomentBlowup:
    """Regression for the linear-code instability found by the RL reward-climb
    test: grads spanning several orders of magnitude within one block drove
    nu elements to dequantize as 0, step = lr*mu_hat/eps, and adapter weights
    to ~1e6. The dynamic code must track exact Adam within a small factor."""

    def test_wide_magnitude_grads_stay_bounded(self):
        n = 256
        mags = jnp.asarray(
            np.repeat([1.0, 1e-2, 1e-4, 1e-5], n // 4), jnp.float32
        )
        params = {"w": jnp.zeros((n,), jnp.float32)}
        opt8, opt32 = adam8bit(0.5), optax.adam(0.5)
        s8, s32 = opt8.init(params), opt32.init(params)
        p8, p32 = params, params
        rng = np.random.default_rng(0)
        for i in range(30):
            g = {"w": mags * jnp.asarray(rng.normal(size=n), jnp.float32)}
            u8, s8 = opt8.update(g, s8, p8)
            u32, s32 = opt32.update(g, s32, p32)
            p8 = optax.apply_updates(p8, u8)
            p32 = optax.apply_updates(p32, u32)
        m8 = float(jnp.abs(p8["w"]).max())
        m32 = float(jnp.abs(p32["w"]).max())
        # exact Adam stays ~lr*steps; the old linear code reached ~1e6 here
        assert m8 < 3 * m32 + 1.0, (m8, m32)


# ---- the codec against an independent NumPy reference ---------------------
# The reference is the table search and the table lookup the codec was
# written as before it lost its gathers: the codes and magnitudes must stay
# equal bit for bit, or STATE_FORMAT has to change.

_MIDS32 = _MIDS.astype(np.float32)


def _ref_quantize(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    blocks = np.asarray(x, np.float32).reshape(-1, BLOCK)
    scale = np.abs(blocks).max(axis=1)
    safe = np.where(scale > 0, scale, np.float32(1.0))[:, None]
    m = np.searchsorted(_MIDS32, np.abs(blocks) / safe, side="right")
    return (np.sign(blocks) * m).astype(np.int8).reshape(-1), scale


def _ref_dequantize(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    q = q.reshape(-1, BLOCK).astype(np.int32)
    mag = _LUT[np.abs(q)]
    return (np.sign(q).astype(np.float32) * mag * scale[:, None]).reshape(-1)


def _bits(a) -> np.ndarray:
    return np.asarray(a, np.float32).view(np.uint32)


def _ratio_cases() -> dict[str, np.ndarray]:
    rng = np.random.default_rng(25)
    return {
        "boundaries": _MIDS32,
        "just_below_boundaries": np.nextafter(_MIDS32, np.float32(0)),
        "just_above_boundaries": np.nextafter(_MIDS32, np.float32(2)),
        "zero_one_and_under_the_first_boundary": np.asarray(
            [0.0, 1.0, _MIDS32[0] / 2, 1e-9, 1e-12, np.nextafter(np.float32(1), np.float32(0))],
            np.float32,
        ),
        "table_levels": _LUT,
        "uniform": rng.random(40_000).astype(np.float32),
        "log_uniform": (10.0 ** rng.uniform(-9, 0, 40_000)).astype(np.float32),
    }


class TestCodecMatchesReference:
    @pytest.mark.parametrize("scale", [1.0, 3.0, 1e-3, 2.5e-7, 7.3e4])
    def test_decode_every_code_bit_for_bit(self, scale):
        codes = np.arange(-127, 128, dtype=np.int8)  # all 255; -128 is never written
        q = np.concatenate([codes, np.zeros(BLOCK - codes.size, np.int8)])
        sc = np.asarray([scale], np.float32)
        got = jax.jit(_dequantize)(_Quantized(jnp.asarray(q), jnp.asarray(sc), BLOCK, (BLOCK,)))
        np.testing.assert_array_equal(_bits(got), _bits(_ref_dequantize(q, sc)))

    @pytest.mark.parametrize("case", list(_ratio_cases()))
    @pytest.mark.parametrize("sign", [1.0, -1.0])
    def test_encode_code_for_code(self, case, sign):
        """Each block's first element is +-1, so the ratio a code is chosen
        from IS the value written there, whatever the float division does."""
        r = _ratio_cases()[case]
        r = np.concatenate([r, np.zeros((-r.size) % (BLOCK - 1), np.float32)])
        blocks = np.concatenate(
            [np.ones((r.size // (BLOCK - 1), 1), np.float32), r.reshape(-1, BLOCK - 1)], axis=1
        ) * np.float32(sign)
        z = jax.jit(_quantize)(jnp.asarray(blocks))
        q_ref, scale_ref = _ref_quantize(blocks)
        np.testing.assert_array_equal(np.asarray(z.q), q_ref)
        np.testing.assert_array_equal(_bits(z.scale), _bits(scale_ref))
        assert z.q.dtype == jnp.int8

    @pytest.mark.parametrize(
        "name",
        ["all_zero_block", "largest_is_negative", "scaled_blocks_spanning_decades", "ragged_tail"],
    )
    def test_encode_whole_blocks(self, name):
        rng = np.random.default_rng(7)
        x = {
            "all_zero_block": np.concatenate(
                [np.zeros(BLOCK, np.float32), rng.normal(size=BLOCK).astype(np.float32)]
            ),
            "largest_is_negative": np.concatenate(
                [[-4.0], rng.uniform(-3.9, 3.9, BLOCK - 1)]
            ).astype(np.float32),
            "scaled_blocks_spanning_decades": (
                rng.normal(size=(64, BLOCK)) * 10.0 ** rng.uniform(-7, 0, (64, BLOCK))
                * 10.0 ** rng.uniform(-6, 3, (64, 1))
            ).astype(np.float32).reshape(-1),
            "ragged_tail": rng.normal(size=3 * BLOCK - 5).astype(np.float32),
        }[name]
        z = jax.jit(_quantize)(jnp.asarray(x))
        padded = np.concatenate([x, np.zeros((-x.size) % BLOCK, np.float32)])
        q_ref, scale_ref = _ref_quantize(padded)
        np.testing.assert_array_equal(np.asarray(z.q), q_ref)
        np.testing.assert_array_equal(_bits(z.scale), _bits(scale_ref))
        want = _ref_dequantize(q_ref, scale_ref)[: x.size]
        np.testing.assert_array_equal(_bits(jax.jit(_dequantize)(z)), _bits(want))


class TestStateCompatibility:
    """The guard that STATE_FORMAT need not be bumped: 20 jitted updates with
    gradients spanning six decades, each compared with the same step done in
    NumPy over the reference codec, from the state the jitted update left.

    Decoding is compared bit for bit. The codes written cannot be: XLA's CPU
    backend fuses multiplies into adds and NumPy does not, which moved a
    block's absmax by one ulp at the second step. So the moments are formed in
    float64 with a bound on what float32 rounding, in any order, can do to
    them, and each written code has to be the reference's code for a value
    inside that bound: one code for all but the few elements whose ratio sits
    on a boundary, where the neighbour is allowed too."""

    def test_twenty_updates_write_the_reference_codes(self):
        lr, b1, b2, eps, clip = 1e-2, 0.9, 0.999, 1e-8, 5.0
        # the weights as the float32 program holds them: 1 - 0.999**t moves by 6e-5 otherwise
        w_mu, w_nu = ([float(np.float32(w)) for w in (b, 1 - b)] for b in (b1, b2))
        ulp = float(np.finfo(np.float32).eps)
        rng = np.random.default_rng(2)
        shapes = {"a": (6, 128), "b": (3, 5, 17), "c": (BLOCK,)}
        opt = adam8bit(lr)
        update = jax.jit(opt.update)
        decode = jax.jit(_dequantize)
        state = opt.init({k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()})
        assert int(state.code_version) == STATE_FORMAT == 2

        def blocks(x):
            return np.concatenate([x, np.zeros((-x.size) % BLOCK)]).reshape(-1, BLOCK)

        def check_written(z, x, slack, where):
            """``z`` holds the reference's codes for ``x`` +- ``slack``."""
            x, slack = blocks(x), blocks(slack)
            scale = np.asarray(z.scale, np.float64)
            absmax = np.abs(x).max(axis=1)
            assert (np.abs(scale - absmax) <= slack.max(axis=1) + 4 * ulp * absmax).all(), where
            safe = np.where(scale > 0, scale, 1.0)[:, None]
            lo = np.maximum(np.abs(x) - slack, 0) / safe * (1 - 4 * ulp)
            hi = (np.abs(x) + slack) / safe * (1 + 4 * ulp)
            lo, hi = (
                np.searchsorted(_MIDS32, r.astype(np.float32), side="right") for r in (lo, hi)
            )
            q = np.asarray(z.q, np.int32).reshape(-1, BLOCK)
            assert ((lo <= np.abs(q)) & (np.abs(q) <= hi)).all(), where
            assert (np.sign(q) == np.sign(x))[lo > 0].all(), where
            return (lo == hi).mean()

        decided = []
        for t in range(1, 21):
            grads = {
                k: (rng.normal(size=s) * 10.0 ** rng.uniform(-6, 0, s)).astype(np.float32)
                for k, s in shapes.items()
            }
            before = state
            steps, state = update({k: jnp.asarray(g) for k, g in grads.items()}, state)
            for k, g in grads.items():
                g = g.reshape(-1).astype(np.float64)
                old = []
                for z in (before.mu[k], before.nu[k]):
                    want = _ref_dequantize(np.asarray(z.q), np.asarray(z.scale))[: g.size]
                    np.testing.assert_array_equal(_bits(decode(z)).reshape(-1), _bits(want))
                    old.append(want.astype(np.float64))
                mu = w_mu[0] * old[0] + w_mu[1] * g
                nu = w_nu[0] * old[1] ** 2 + w_nu[1] * g * g
                where = f"{k}, step {t}"
                mu_slack = 8 * ulp * (np.abs(w_mu[0] * old[0]) + np.abs(w_mu[1] * g))
                decided += [
                    check_written(state.mu[k], mu, mu_slack, where),
                    check_written(state.nu[k], np.sqrt(nu), 8 * ulp * np.sqrt(nu), where),
                ]
                step = -lr * np.clip(
                    mu / (1 - w_mu[0] ** t) / (np.sqrt(nu / (1 - w_nu[0] ** t)) + eps), -clip, clip
                )
                np.testing.assert_allclose(  # atol: where the two terms of mu cancel
                    np.asarray(steps[k]).reshape(-1), step, rtol=1e-5, atol=1e-5 * lr, err_msg=where
                )
        assert min(decided) > 0.99 and np.mean(decided) > 0.999, (min(decided), np.mean(decided))


class TestCodecStaysElementwise:
    """A TPU has no fast per-element gather: the gathers this codec once held
    cost 127 ns an element on the v5e (PERF.md, PR 25)."""

    def test_lowered_update_holds_no_gather_and_no_loop(self):
        params = {"a": jnp.ones((300,)), "b": {"c": jnp.ones((5, 64))}}
        opt = adam8bit(1e-3)
        text = jax.jit(opt.update).lower(params, opt.init(params)).as_text()
        assert "stablehlo.select" in text
        assert "gather" not in text and "while" not in text

    def test_no_table_wide_temporary(self):
        """An [elements, 127] comparison buffer is 127 bytes an element or
        more; full fine-tuning runs this on leaves of a model's size."""
        n = 14 * 3584 * 32
        opt = adam8bit(1e-3)
        g = {"w": jax.ShapeDtypeStruct((14, 3584, 32), jnp.float32)}
        compiled = jax.jit(opt.update).lower(g, jax.eval_shape(opt.init, g)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * n
