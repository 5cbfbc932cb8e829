"""The gated delta rule with a per-channel decay (``ops/delta_attention.py``):
the chunked form and the one-token step against the plain recurrence written
here, token by token, and the short convolution with its tail. Float32 on the
CPU at small sizes; the one-token Mosaic kernel in interpret mode against the
plain form at heads of 128, and which of the two ``delta_step`` takes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from distrl_llm_tpu.ops import delta_attention  # noqa: E402
from distrl_llm_tpu.ops.delta_attention import (  # noqa: E402
    delta_chunked, delta_step, delta_step_kernel, delta_step_plain, l2norm, short_conv,
)

B, S, H, D = 2, 50, 3, 8


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, g, beta, valid, state):
    """S_t = (I - beta k k^T) diag(a) S_{t-1} + beta k v^T; o_t = S_t^T q_t,
    written out with matrices; a padded token is no step."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    state = np.asarray(state, np.float64).copy()
    out = np.zeros(q.shape[:3] + (v.shape[-1],))
    for b in range(q.shape[0]):
        for h in range(q.shape[2]):
            s = state[b, h]
            for t in range(q.shape[1]):
                if valid[b, t]:
                    kt = k[b, t, h][:, None]
                    s = (np.eye(len(kt)) - beta[b, t, h] * kt @ kt.T) @ (
                        np.exp(g[b, t, h])[:, None] * s) + beta[b, t, h] * kt @ v[b, t, h][None, :]
                out[b, t, h] = s.T @ q[b, t, h]
            state[b, h] = s
    return out, state


@pytest.fixture(scope="module")
def inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = l2norm(jax.random.normal(ks[0], (B, S, H, D)))
    k = l2norm(jax.random.normal(ks[1], (B, S, H, D)))
    v = jax.random.normal(ks[2], (B, S, H, D))
    g = -jnp.exp(2 * jax.random.normal(ks[3], (B, S, H, D)))
    g = g.at[..., 0].set(-60.0)  # a channel that forgets everything each token
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, S, H)))
    state = jax.random.normal(ks[5], (B, H, D, D))
    return q, k, v, g, beta, state


MASKS = {
    "whole": lambda m: m,
    "right": lambda m: m.at[0, 40:].set(0),
    "left": lambda m: m.at[1, :7].set(0),
    "both": lambda m: m.at[0, 40:].set(0).at[1, :7].set(0),
}


@pytest.mark.parametrize("chunk", [1, 7, 16, 64])
@pytest.mark.parametrize("padding", sorted(MASKS))
def test_chunked_equals_the_recurrence(inputs, chunk, padding):
    q, k, v, g, beta, state = inputs
    assert float(beta.max()) > 1.5 and float(jnp.exp(g).min()) == 0.0
    valid = MASKS[padding](jnp.ones((B, S), jnp.int32))
    want_o, want_s = recurrence(q, k, v, g, beta, np.asarray(valid), state)
    got_o, got_s = delta_chunked(q, k, v, g, beta, valid, state, chunk=chunk)
    real = np.asarray(valid)[..., None, None] > 0
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.abs(np.where(real, np.asarray(got_o) - want_o, 0)).max() < 5e-5
    assert np.abs(np.asarray(got_s) - want_s).max() < 5e-5


def test_a_channel_that_forgets_at_once_overflows_nothing(inputs):
    """exp(-G_j) alone would be exp(+3000) here: every factor is exp of a
    difference that is not positive."""
    q, k, v, g, beta, state = inputs
    fast = jnp.full_like(g, -50.0)
    valid = jnp.ones((B, S), jnp.int32)
    o, s = delta_chunked(q, k, v, fast, beta, valid, state, chunk=64)
    want_o, want_s = recurrence(q, k, v, fast, beta, np.asarray(valid), state)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    assert np.abs(np.asarray(o) - want_o).max() < 5e-5
    grads = jax.grad(lambda g_: delta_chunked(q, k, v, g_, beta, valid, state)[0].sum())(fast)
    assert np.isfinite(np.asarray(grads)).all()


@pytest.mark.parametrize("cut", [16, 23])
def test_segments_then_steps_are_one_recurrence(inputs, cut):
    """A prompt prefilled in two segments (the second right-padded) and then
    decoded token by token from the carried state."""
    q, k, v, g, beta, state = inputs
    valid = jnp.ones((B, S), jnp.int32)
    want_o, want_s = recurrence(q, k, v, g, beta, np.asarray(valid), state)
    part = lambda x, a, b: x[:, a:b]
    o1, s1 = delta_chunked(
        *(part(x, 0, cut) for x in (q, k, v, g, beta, valid)), state, chunk=8)
    # second segment: tokens cut..40, padded on the right to 40 + 5
    pad = lambda x: jnp.pad(part(x, cut, 40), ((0, 0), (0, 5)) + ((0, 0),) * (x.ndim - 2))
    o2, s2 = delta_chunked(*(pad(x) for x in (q, k, v, g, beta, valid)), s1, chunk=8)
    outs, s = [o1, o2[:, : 40 - cut]], s2
    for t in range(40, S):
        o, s = delta_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(o[:, None])
    assert np.abs(np.asarray(jnp.concatenate(outs, 1)) - want_o).max() < 5e-5
    assert np.abs(np.asarray(s) - want_s).max() < 5e-5


def test_the_chunked_forms_gradients_are_the_steps(inputs):
    q, k, v, g, beta, state = inputs
    valid = MASKS["both"](jnp.ones((B, S), jnp.int32))

    def by_steps(q, k, v, g, beta, state):
        def one(s, x):
            q_, k_, v_, g_, b_, ok = x
            o, new = delta_step(q_, k_, v_, g_, b_, s)
            return jnp.where(ok[:, None, None, None] > 0, new, s), o
        s, o = jax.lax.scan(one, state, tuple(
            jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta, valid)))
        return jnp.swapaxes(o, 0, 1), s

    def scalar(fn):
        def f(*args):
            o, s = fn(*args)
            return (o * valid[..., None, None]).sum() + (s * s).sum()
        return f

    got = jax.grad(scalar(lambda *a: delta_chunked(*a[:5], valid, a[5], chunk=16)),
                   argnums=tuple(range(6)))(q, k, v, g, beta, state)
    want = jax.grad(scalar(by_steps), argnums=tuple(range(6)))(q, k, v, g, beta, state)
    for name, a, b in zip("q k v g beta state".split(), got, want):
        scale = float(jnp.abs(b).max())
        assert scale > 0 and float(jnp.abs(a - b).max()) < 2e-5 * scale + 2e-5, name


@pytest.mark.parametrize("cut", [1, 2, 20])
def test_the_convolutions_tail_crosses_a_segment_boundary(cut):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x, w = jax.random.normal(ks[0], (B, S, 12)), jax.random.normal(ks[1], (4, 12))
    valid = jnp.ones((B, S), jnp.int32).at[0, 44:].set(0)
    want = np.zeros((B, S, 12))
    xs = np.asarray(x) * np.asarray(valid)[..., None]
    for t in range(S):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(w)[i] * xs[:, t - 3 + i]
    y, tail = short_conv(x, w, valid)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    y1, t1 = short_conv(x[:, :cut], w, valid[:, :cut])
    y2, t2 = short_conv(x[:, cut:], w, valid[:, cut:], t1)
    assert np.abs(np.asarray(jnp.concatenate([y1, y2], 1)) - want).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(tail))
    # the tail ends at the row's last real token: row 0's is tokens 41..43
    np.testing.assert_allclose(np.asarray(tail)[0], np.asarray(x)[0, 41:44])
    np.testing.assert_allclose(np.asarray(tail)[1], np.asarray(x)[1, S - 3:])
    # a decode step: one token from the tail
    y3, t3 = short_conv(x[:, -1:], w, None, short_conv(x[:, :-1], w, valid[:, :-1])[1])
    np.testing.assert_allclose(np.asarray(y3)[1, 0], want[1, -1], atol=1e-5)
    np.testing.assert_allclose(np.asarray(t3)[1], np.asarray(x)[1, S - 3:])


def test_a_left_padded_row_starts_from_nothing():
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 10, 4))
    w = jax.random.normal(jax.random.PRNGKey(5), (4, 4))
    valid = jnp.ones((1, 10), jnp.int32).at[0, :6].set(0)
    y, tail = short_conv(x, w, valid)
    alone, tail_alone = short_conv(x[:, 6:], w, valid[:, 6:])
    np.testing.assert_allclose(np.asarray(y)[:, 6:], np.asarray(alone), atol=1e-6)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_alone), atol=1e-6)


# ------------------------------------------------ the one-token Mosaic kernel

KD = 128  # the kernel takes whole 128-lane tiles of state
# rows x heads: a whole head block, one and a half (the last block runs past
# the heads), fewer heads than a block
GEOMETRIES = {"1x16": (1, 16), "3x24": (3, 24), "8x3": (8, 3)}
FLAVOURS = {
    "drawn": lambda g, beta: (g, beta),
    "no-decay": lambda g, beta: (jnp.zeros_like(g), beta),  # g = 0: a = 1
    "no-write": lambda g, beta: (g, jnp.zeros_like(beta)),  # beta = 0: only the decay
    "decay-0.86": lambda g, beta: (jnp.full_like(g, np.log(0.86)), beta),
    "decay-0.999": lambda g, beta: (jnp.full_like(g, np.log(0.999)), beta),
}


def step_inputs(rows, heads, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (rows, heads, KD))) * KD ** -0.5
    k = l2norm(jax.random.normal(ks[1], (rows, heads, KD)))
    v = jax.random.normal(ks[2], (rows, heads, KD))
    g = -jax.random.uniform(ks[3], (rows, heads, KD), minval=1e-3, maxval=0.15)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (rows, heads)))
    state = jax.random.normal(ks[5], (rows, heads, KD, KD))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_kernel_is_the_plain_step(geometry, flavour):
    q, k, v, g, beta, state = step_inputs(*GEOMETRIES[geometry])
    g, beta = FLAVOURS[flavour](g, beta)
    want_o, want_s = delta_step_plain(q, k, v, g, beta, state)
    got_o, got_s = delta_step_kernel(q, k, v, g, beta, state, interpret=True)
    assert got_s.dtype == jnp.float32 and got_s.shape == state.shape
    assert got_o.dtype == q.dtype and got_o.shape == v.shape
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5
    if flavour == "no-write":  # what was there, decayed, and nothing else
        np.testing.assert_allclose(
            np.asarray(got_s), np.asarray(state * jnp.exp(g)[..., None]), atol=2e-6)


def test_a_prompt_then_kernel_steps_are_one_recurrence():
    """A prompt through the chunked form, then 32 tokens through the kernel
    from the carried state, against the chunked form over the whole."""
    rows, heads, prompt, steps = 2, 2, 24, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    shape = (rows, prompt + steps, heads, KD)
    q = l2norm(jax.random.normal(ks[0], shape)) * KD ** -0.5
    k = l2norm(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -jax.random.uniform(ks[3], shape, minval=1e-3, maxval=0.15)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], shape[:3]))
    valid = jnp.ones(shape[:2], jnp.int32)
    want_o, want_s = delta_chunked(q, k, v, g, beta, valid)
    o, s = delta_chunked(*(x[:, :prompt] for x in (q, k, v, g, beta, valid)))
    outs = [o]
    for t in range(prompt, prompt + steps):
        o, s = delta_step_kernel(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s, interpret=True)
        outs.append(o[:, None])
    assert s.dtype == jnp.float32
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want_o).max()) < 2e-5
    assert float(jnp.abs(s - want_s).max()) < 2e-5


@pytest.mark.parametrize("backend,head,dtype,want", [
    ("tpu", 128, jnp.float32, "kernel"),
    ("tpu", 256, jnp.float32, "kernel"),
    ("tpu", 16, jnp.float32, "plain"),  # the CPU tests' heads: no whole tile
    ("tpu", 128, jnp.bfloat16, "plain"),  # the kernel is float32 throughout
    ("cpu", 128, jnp.float32, "plain"),
])
def test_the_step_takes_the_form_it_can_observe(monkeypatch, backend, head, dtype, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    state = jnp.zeros((2, 4, head, head), dtype)
    assert delta_attention.delta_step_impl(state) == want
    # and delta_step records it; the kernel itself is not run off the TPU
    seen = []
    monkeypatch.setattr(delta_attention, "delta_step_kernel",
                        lambda *a: seen.append("kernel") or (a[2], a[5]))
    monkeypatch.setattr(delta_attention, "delta_step_plain",
                        lambda *a: seen.append("plain") or (a[2], a[5]))
    monkeypatch.setattr(delta_attention, "dispatch_choices", {})
    x = jnp.zeros((2, 4, head))
    delta_step(x, x, x, x, jnp.zeros((2, 4)), state)
    assert seen == [want]
    assert delta_attention.dispatch_choices == {
        delta_attention.dispatch_key(4, head, head, dtype): want}
