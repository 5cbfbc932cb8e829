"""``ops/power_retention.py``: the symmetric second power and the three forms
of power retention of degree 2 (attention form, one-token step, chunked form
from a carried state) against each other, float32 on the CPU at 2e-5 of 1
(and 1e-4 of a value: with heads of 8 a query's sum of weights comes near
zero, and what is divided by it is large).

The model around them is held by ``tests/test_power_model.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu.ops import power_retention as pr

B, S, H, K, D = 2, 37, 10, 2, 8  # five query heads a KV head; a state of 36 x 8
TOL = 2e-5


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def rows():
    """q, k like RMS-normed heads with a common part (so that a first token's
    only weight, its own, is not near zero), a decay that remembers
    (0.9-0.999), rows of 37 and 20 real tokens, right-padded."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D)) + 1.0
    k = jax.random.normal(ks[1], (B, S, K, D)) + 1.0
    v = jax.random.normal(ks[2], (B, S, K, D))
    g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (B, S, K)) + 4.0)
    lens = jnp.asarray([S, 20])
    valid = (jnp.arange(S)[None] < lens[:, None]).astype(jnp.int32)
    return q, k, v, g, valid


def close(got, want, atol=TOL):
    np.testing.assert_allclose(got, want, atol=atol, rtol=1e-4)


def real(x, valid):
    return np.asarray(x * valid[..., None, None])


def stepped(q, k, v, g, valid):
    """Token by token through ``power_step``; a padded token is no step."""
    state = pr.init_state(B, K, D)
    outs = []
    for t in range(q.shape[1]):
        o, new = pr.power_step(q[:, t], k[:, t], v[:, t], g[:, t], state)
        keep = valid[:, t] > 0
        state = (jnp.where(keep[:, None, None, None], new[0], state[0]),
                 jnp.where(keep[:, None, None], new[1], state[1]))
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(d):
    """The symmetric second power: ``d (d + 1) / 2`` entries (8,256 at 128),
    each pair once, and an inner product that is the square exactly."""
    q, k = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    assert pr.phi(q).shape == (7, pr.state_dim(d)) and pr.state_dim(128) == 8256
    want = np.square(np.einsum("td,sd->ts", np.asarray(q, np.float64), np.asarray(k, np.float64)))
    got = np.einsum("tx,sx->ts", np.asarray(pr.phi(q), np.float64),
                    np.asarray(pr.phi(k), np.float64))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # every unordered pair is there once: the squares, and sqrt(2) x_a x_b
    if d > 16:
        return
    x = jnp.arange(1.0, d + 1.0)
    pairs = sorted(float(a * b * (1.0 if a == b else 2.0 ** 0.5))
                   for i, a in enumerate(np.asarray(x)) for b in np.asarray(x)[i:])
    np.testing.assert_allclose(sorted(np.asarray(pr.phi(x))), pairs, rtol=1e-6)
    with pytest.raises(ValueError, match="even"):
        pr.phi(jnp.ones((3,)))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunked_form_is_the_attention_form(rows, chunk):
    """Chunks of 8 and 16 cross the ragged row's end (20 real tokens) inside a
    chunk; 64 is one chunk, which reads no state at all."""
    q, k, v, g, valid = rows
    want = pr.power_attention(q, k, v, g, valid)
    got, (big, z) = pr.power_chunked(q, k, v, g, valid, chunk=chunk)
    assert big.shape == (B, K, 36, D) and z.shape == (B, K, 36)
    assert big.dtype == z.dtype == jnp.float32
    close(real(got, valid), real(want, valid))


def test_the_step_is_the_attention_form_and_ends_in_the_chunked_forms_state(rows):
    q, k, v, g, valid = rows
    got, (big, z) = stepped(q, k, v, g, valid)
    close(real(got, valid), real(pr.power_attention(q, k, v, g, valid), valid))
    _, (big_c, z_c) = pr.power_chunked(q, k, v, g, valid, chunk=8)
    np.testing.assert_allclose(big, big_c, atol=TOL)
    np.testing.assert_allclose(z, z_c, atol=TOL)
    assert pr.dispatch_choices[pr.dispatch_key(K, H // K, D, D)] == "plain"


def test_a_padded_token_neither_decays_nor_writes(rows):
    """The state after a right-padded row is the state at its last real token:
    what follows it (any q, k, v, g) changes nothing."""
    q, k, v, g, valid = rows
    _, (big, z) = pr.power_chunked(q, k, v, g, valid, chunk=8)
    _, (big20, z20) = pr.power_chunked(
        q[1:, :20], k[1:, :20], v[1:, :20], g[1:, :20], valid[1:, :20], chunk=8)
    np.testing.assert_allclose(big[1], big20[0], atol=1e-6)
    np.testing.assert_allclose(z[1], z20[0], atol=1e-6)
    noise = lambda x: x + 100.0 * (1 - valid.reshape(valid.shape + (1,) * (x.ndim - 2)))
    _, (big_n, z_n) = pr.power_chunked(noise(q), noise(k), noise(v), g - 5.0 * (
        1 - valid[..., None]), valid, chunk=8)
    np.testing.assert_allclose(big_n, big, atol=1e-6)
    np.testing.assert_allclose(z_n, z, atol=1e-6)


@pytest.mark.parametrize("cut,chunk", [(16, 8), (16, 16), (24, 5)])
def test_a_segment_continues_from_the_carried_state(rows, cut, chunk):
    """A prompt prefilled in segments: the second from the first's (S, z)."""
    q, k, v, g, valid = rows
    part = lambda lo, hi: (q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], g[:, lo:hi], valid[:, lo:hi])
    first, carried = pr.power_chunked(*part(0, cut), chunk=chunk)
    second, (big, z) = pr.power_chunked(*part(cut, S), state=carried, chunk=chunk)
    want = pr.power_attention(q, k, v, g, valid)
    close(real(jnp.concatenate([first, second], 1), valid), real(want, valid))
    _, (big_w, z_w) = pr.power_chunked(q, k, v, g, valid, chunk=64)
    np.testing.assert_allclose(big, big_w, atol=TOL)
    np.testing.assert_allclose(z, z_w, atol=TOL)


def test_five_query_heads_read_one_state_and_the_sixth_the_next(rows):
    """GQA inside a recurrent layer: heads 0-4 read KV head 0's state, heads
    5-9 KV head 1's. Changing KV head 1's k, v or decay moves heads 5-9 alone,
    in every form."""
    q, k, v, g, valid = rows
    k2, v2, g2 = k.at[:, :, 1].multiply(-0.5), v.at[:, :, 1].add(1.0), g.at[:, :, 1].add(-0.1)
    forms = {
        "attention": lambda *a: pr.power_attention(*a, valid),
        "chunked": lambda *a: pr.power_chunked(*a, valid, chunk=8)[0],
        "step": lambda *a: stepped(*a, valid)[0],
    }
    for name, form in forms.items():
        base, moved = real(form(q, k, v, g), valid), real(form(q, k2, v2, g2), valid)
        assert np.abs(moved[:, :, :5] - base[:, :, :5]).max() == 0.0, name
        assert np.abs(moved[:, :, 5:] - base[:, :, 5:]).max() > 0.1, name
    # and one state a KV head is what is held, not one a query head
    _, (big, z) = pr.power_chunked(q, k, v, g, valid, chunk=8)
    assert big.shape[1] == z.shape[1] == K


def test_the_state_is_the_normaliser_and_the_sum_it_says(rows):
    """``z . phi(q)`` is the sum of the weights and ``S^T phi(q)`` the weighted
    sum of v: a dropped normaliser or a state without its z is another function."""
    q, k, v, g, valid = rows
    _, (big, z) = pr.power_chunked(q, k, v, g, valid, chunk=8)
    t = S - 1  # row 0 is whole: its last token's weights
    cum = jnp.cumsum(g[0], axis=0)
    scores = jnp.einsum("hd,skd->ksh", q[0, t].reshape(K, H // K, D).reshape(H, D),
                        k[0])  # [K, s, H]
    a = jnp.square(scores / jnp.sqrt(1.0 * D)) * jnp.exp(cum[t][None] - cum).T[..., None]
    pq = pr.phi(q[0, t] * D ** -0.25)  # [H, 36]
    for head in (0, 4, 5, 9):
        kv = head // (H // K)
        np.testing.assert_allclose(z[0, kv] @ pq[head], a[kv, :, head].sum(), rtol=2e-5)
        np.testing.assert_allclose(
            big[0, kv].T @ pq[head], a[kv, :, head] @ v[0, :, kv], rtol=2e-4, atol=2e-5)


def test_reverse_mode_through_the_chunks_is_the_attention_forms(rows):
    """The learner's path: whole rows, the state dropped, chunk bodies
    rematerialised; gradients in q, k, v and the decay."""
    q, k, v, g, valid = rows
    w = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, D)) * valid[..., None, None]

    def loss(form):
        return lambda q, k, v, g: jnp.sum(form(q, k, v, g) * w)

    chunked = loss(lambda *a: pr.power_chunked(*a, valid, chunk=8)[0])
    plain = loss(lambda *a: pr.power_attention(*a, valid))
    got = jax.grad(chunked, argnums=(0, 1, 2, 3))(q, k, v, g)
    want = jax.grad(plain, argnums=(0, 1, 2, 3))(q, k, v, g)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 1e-3
        np.testing.assert_allclose(a, b, atol=4e-5 * float(jnp.abs(b).max()))


# ------------------------------------------- the one-token step as a kernel

WIDE = 128  # the kernel takes whole 128-lane heads: a tile of 8,256 x 128


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))  # eager, phi alone is 65 dispatches
def wide_step(rows, kv, group, decay, seed=0):
    """One token's q, k, v, g at heads of 128 and a state that remembers 48
    keys, so that the sum of weights is far from zero and ``o`` is of order 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (rows, kv * group, WIDE)) + 1.0
    k = jax.random.normal(ks[1], (rows, kv, WIDE)) + 1.0
    v = jax.random.normal(ks[2], (rows, kv, WIDE))
    g = jnp.full((rows, kv), np.log(decay), jnp.float32)
    pk = pr.phi((jax.random.normal(ks[3], (rows, kv, 48, WIDE)) + 1.0) * WIDE ** -0.25)
    big = jnp.einsum("bkjd,bkjv->bkdv", pk, jax.random.normal(ks[4], (rows, kv, 48, WIDE)))
    return q, k, v, g, (big, pk.sum(2))


plain_step = jax.jit(pr.power_step_plain)


@pytest.mark.parametrize("rows,kv,group,decay", [
    (1, 1, 5, 0.99), (3, 2, 1, 1.0), (1, 2, 5, 1.0)])
def test_the_kernel_is_the_plain_step(rows, kv, group, decay):
    """``power_step_kernel`` through the interpreter against ``power_step_plain``
    on ``o``, S and z: phi(k) and phi(q) expanded inside from the 128-vectors,
    the squares once and every other pair by sqrt(2), the half diagonal's 64
    rows, five query heads reading one tile (or one), a log-decay of zero."""
    q, k, v, g, state = wide_step(rows, kv, group, decay, seed=rows + 10 * kv + group)
    want_o, (want_s, want_z) = plain_step(q, k, v, g, state)
    got_o, (got_s, got_z) = pr.power_step_kernel(q, k, v, g, *state, interpret=True)
    assert got_s.shape == (rows, kv, 8256, WIDE) and got_s.dtype == got_z.dtype == jnp.float32
    assert 0.1 < float(jnp.abs(want_o).max()) < 10.0
    close(got_o, want_o)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)
    np.testing.assert_allclose(got_z, want_z, atol=TOL)


def test_a_prompt_through_the_chunks_then_tokens_through_the_kernel():
    """Prefill's carry is the kernel's state as it stands (packed, float32, one
    a KV head): 24 tokens through ``power_chunked``, then 16 through the kernel
    from its (S, z), against the attention form over the whole row of 40."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    rows, kv, group, prompt, more = 1, 1, 2, 24, 16
    s = prompt + more
    q = jax.random.normal(ks[0], (rows, s, kv * group, WIDE)) + 1.0
    k = jax.random.normal(ks[1], (rows, s, kv, WIDE)) + 1.0
    v = jax.random.normal(ks[2], (rows, s, kv, WIDE))
    g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (rows, s, kv)) + 4.0)
    valid = jnp.ones((rows, s), jnp.int32)
    want = pr.power_attention(q, k, v, g, valid)
    first, state = pr.power_chunked(
        q[:, :prompt], k[:, :prompt], v[:, :prompt], g[:, :prompt], valid[:, :prompt], chunk=8)
    outs = [first]
    for t in range(prompt, s):
        o, state = pr.power_step_kernel(q[:, t], k[:, t], v[:, t], g[:, t], *state,
                                        interpret=True)
        outs.append(o[:, None])
    close(jnp.concatenate(outs, 1), want)


@pytest.mark.parametrize("backend,dtype,head,want", [
    ("tpu", jnp.float32, 128, "kernel"), ("cpu", jnp.float32, 128, "plain"),
    ("tpu", jnp.bfloat16, 128, "plain"), ("tpu", jnp.float32, 16, "plain"),
    ("cpu", jnp.bfloat16, 16, "plain")])
def test_the_step_chooses_from_what_it_can_observe(monkeypatch, backend, dtype, head, want):
    """The backend, the state's type and whether a head is whole 128-lane
    tiles: no argument, field or variable selects the form."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    big = pr.state_dim(head)
    state = (jax.ShapeDtypeStruct((4, 2, big, head), dtype),
             jax.ShapeDtypeStruct((4, 2, big), dtype))
    assert pr.power_step_impl(state) == want


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_the_step_records_its_choice_under_a_key_without_the_rows(monkeypatch, backend):
    """``power_step`` on a "TPU" goes to the kernel (here through the
    interpreter) and says so; the record's key has no rows, so an engine that
    traces the step at its shard's rows reads the same entry."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pr, "power_step_kernel",
                        functools.partial(pr.power_step_kernel, interpret=True))
    monkeypatch.setattr(pr, "dispatch_choices", {})
    want = {"tpu": "kernel", "cpu": "plain"}[backend]
    for rows in (1, 2):
        q, k, v, g, state = wide_step(rows, 1, 1, 0.99, seed=rows)
        # a function of this test's own: the record is made when it is traced
        got_o, got = jax.jit(lambda *xs: pr.power_step(*xs))(q, k, v, g, state)
        want_o, wanted = plain_step(q, k, v, g, state)
        close(got_o, want_o)
        np.testing.assert_allclose(got[0], wanted[0], atol=TOL)
        np.testing.assert_allclose(got[1], wanted[1], atol=TOL)
        assert pr.dispatch_choices == {pr.dispatch_key(1, 1, WIDE, WIDE): want}
    assert pr.dispatch_key(1, 1, WIDE, WIDE) == (1, 1, WIDE, WIDE, "float32")
