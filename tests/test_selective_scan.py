"""Mamba-1's selective scan (``ops/selective_scan.py``): the one-token step and
the chunked form against each other and against the reference's token-by-token
scan (``perfbench/reference_jamba.py::ssm_scan``, which imports neither), in
float32 on the CPU, and the convolution window that feeds it
(``ops/delta_attention.py::short_conv`` plus the bias at the call site).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from distrl_llm_tpu.ops.delta_attention import short_conv  # noqa: E402
from distrl_llm_tpu.ops.selective_scan import DEFAULT_CHUNK, ssm_chunked, ssm_step  # noqa: E402
from perfbench import reference_jamba as ref  # noqa: E402

ROWS, T, E, N = 3, 50, 24, 16


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def inputs():
    """Steps spread over 0.001-1 and A over -1..-16, so that some channels
    forget in a token and some remember all fifty."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    c = jax.random.normal(keys[0], (ROWS, T, E))
    dt = jnp.exp(jax.random.uniform(keys[1], (ROWS, T, E), minval=-6.9, maxval=0.0))
    b = jax.random.normal(keys[2], (ROWS, T, N))
    cc = jax.random.normal(keys[3], (ROWS, T, N))
    a = -jnp.exp(jax.random.uniform(keys[4], (N, E), minval=0.0, maxval=2.77))
    d = 1.0 + 0.2 * jax.random.normal(keys[5], (E,))
    return c, dt, b, cc, a, d


def reference(inputs, lengths=None):
    """The reference's scan a row over its first ``lengths[r]`` tokens: (y
    [B, T, E] with zeros past a row's end, the state [B, N, E] at its end)."""
    c, dt, b, cc, a, d = inputs
    ys, states = [], []
    for r in range(c.shape[0]):
        n = c.shape[1] if lengths is None else lengths[r]
        y, state = ref.ssm_scan(c[r, :n], dt[r, :n], b[r, :n], cc[r, :n], a.T, d)
        ys.append(jnp.pad(y, ((0, c.shape[1] - n), (0, 0))))
        states.append(state.T)
    return np.asarray(jnp.stack(ys)), np.asarray(jnp.stack(states))


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    if want.size:
        assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("chunk", [0, 1, 7, 16, 50, 64])
def test_chunked_equals_the_sequential_scan(inputs, chunk):
    """Every chunk size gives the reference's numbers: one token a chunk, a
    chunk that does not divide the row, the whole row, a chunk longer than it."""
    assert DEFAULT_CHUNK == 64
    want_y, want_state = reference(inputs)
    y, state = ssm_chunked(*inputs, chunk=chunk)
    assert y.shape == (ROWS, T, E) and y.dtype == jnp.float32
    assert state.shape == (ROWS, N, E) and state.dtype == jnp.float32
    close(y, want_y)
    close(state, want_state)


def test_the_step_token_by_token_equals_the_chunked_form(inputs):
    c, dt, b, cc, a, d = inputs
    want_y, want_state = ssm_chunked(*inputs, chunk=16)
    state = jnp.zeros((ROWS, N, E), jnp.float32)
    ys = []
    for t in range(T):
        y, state = ssm_step(c[:, t], dt[:, t], b[:, t], cc[:, t], a, d, state)
        ys.append(y)
    close(jnp.stack(ys, 1), want_y)
    close(state, want_state)


@pytest.mark.parametrize("cut", [1, 16, 33])
def test_a_segment_boundary_carries_the_state(inputs, cut):
    """Two segments from the carried state are one recurrence, wherever the
    boundary falls against the chunks."""
    want_y, want_state = reference(inputs)
    first = tuple(x[:, :cut] for x in inputs[:4]) + inputs[4:]
    second = tuple(x[:, cut:] for x in inputs[:4]) + inputs[4:]
    y1, state = ssm_chunked(*first, chunk=16)
    y2, state = ssm_chunked(*second, state=state, chunk=16)
    close(jnp.concatenate([y1, y2], 1), want_y)
    close(state, want_state)


@pytest.mark.parametrize("chunk", [8, 64])
def test_a_padded_token_neither_decays_nor_writes(inputs, chunk):
    """Right padding: the state after the row is the state at its last real
    token. A row that is all padding keeps the state it was handed."""
    lengths = [50, 31, 0]
    valid = jnp.asarray(np.arange(T)[None, :] < np.asarray(lengths)[:, None], jnp.int32)
    want_y, want_state = reference(inputs, lengths)
    y, state = ssm_chunked(*inputs, valid, chunk=chunk)
    close(np.asarray(y) * np.asarray(valid)[..., None], want_y)
    close(state, want_state)
    handed = jnp.ones((ROWS, N, E), jnp.float32)
    _, kept = ssm_chunked(*inputs, jnp.zeros((ROWS, T), jnp.int32), state=handed, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(handed))


def test_the_gate_inside_the_scan_is_y_times_silu_z(inputs):
    """With ``z`` both forms gate their output and give it ``z``'s type: what
    the ungated float32 y, gated outside, rounds to."""
    c, dt, b, cc, a, d = inputs
    z = jax.random.normal(jax.random.PRNGKey(9), c.shape).astype(jnp.bfloat16)
    y, state = ssm_chunked(*inputs, chunk=16)
    want = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.bfloat16)
    got, gated_state = ssm_chunked(*inputs, z=z, chunk=16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(gated_state), np.asarray(state))
    one, _ = ssm_step(c[:, 0], dt[:, 0], b[:, 0], cc[:, 0], a, d,
                      jnp.zeros((ROWS, N, E), jnp.float32), z[:, 0])
    np.testing.assert_array_equal(np.asarray(one, np.float32), np.asarray(want[:, 0], np.float32))


def test_reverse_mode_runs_through_the_chunk_scan(inputs):
    """Plain autodiff through the rematerialised chunk scan equals autodiff
    through the reference's scan, in every input and in A and D."""
    c, dt, b, cc, a, d = inputs

    def ours(c, dt, b, cc, a, d):
        y, state = ssm_chunked(c, dt, b, cc, a, d, chunk=16)
        return jnp.sum(jnp.sin(y)) + jnp.sum(state ** 2)

    def theirs(c, dt, b, cc, a, d):
        total = 0.0
        for r in range(ROWS):
            y, state = ref.ssm_scan(c[r], dt[r], b[r], cc[r], a.T, d)
            total = total + jnp.sum(jnp.sin(y)) + jnp.sum(state ** 2)
        return total

    got = jax.grad(ours, argnums=range(6))(*inputs)
    want = jax.grad(theirs, argnums=range(6))(*inputs)
    for g, w in zip(got, want):
        close(g, w)


@pytest.mark.parametrize("lengths", [(20, 20), (20, 13), (2, 0)])
def test_the_window_is_the_last_three_real_tokens(lengths):
    """The convolution with its bias from a carried window: two segments are
    one convolution, and the window after a right-padded segment ends at the
    row's last real token (zeros where the row has fewer than three)."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (2, 20, E))
    w = jax.random.normal(keys[1], (4, E))
    bias = jax.random.normal(keys[2], (E,))
    valid = jnp.asarray(np.arange(20)[None, :] < np.asarray(lengths)[:, None], jnp.int32)
    whole, tail = short_conv(x, w, valid)
    for r, n in enumerate(lengths):
        want = ref._conv(x[r, :n], w, bias)
        close((whole + bias)[r, :n], want)
        kept = np.zeros((3, E), np.float32)
        if n:
            kept[max(0, 3 - n):] = np.asarray(x[r, max(0, n - 3): n])
        close(tail[r], kept)
    # a second segment from the window: the same numbers as the row run whole
    a, tail = short_conv(x[:, :8], w, jnp.ones((2, 8), jnp.int32))
    b, _ = short_conv(x[:, 8:], w, jnp.ones((2, 12), jnp.int32), tail)
    full, _ = short_conv(x, w, jnp.ones((2, 20), jnp.int32))
    close(jnp.concatenate([a, b], 1), full)
    # and one token from it, as a decode step reads it
    one, _ = short_conv(x[:, 8:9], w, None, tail)
    close(one[:, 0], full[:, 8])
