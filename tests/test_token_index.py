"""The learned index's choice without a sort (``ops/token_index.py``): the k-th
largest score by counting over the scores' ordered bits, a decode row's
positions by rank within blocks. The oracles live HERE: the sort the module
had before (a k-th score read off ``jnp.sort``), ``jax.lax.top_k`` and
``np.flatnonzero``. Every comparison is exact: the chosen set is the module
docstring's, bit for bit, or the model is another model.
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

from distrl_llm_tpu.ops import token_index  # noqa: E402
from distrl_llm_tpu.ops.attention import NEG_INF  # noqa: E402

WIDTH = 300  # two whole blocks of RANK_BLOCK and a part of a third


def sorted_mask(scores, visible, k):
    """``chosen_mask`` as it stood before the count: the k-th largest score
    read off a sort of the row."""
    width = scores.shape[-1]
    if k >= width:
        return visible
    held = jnp.where(visible, scores, NEG_INF)
    kth = jnp.sort(held, axis=-1, stable=False)[..., width - k: width - k + 1]
    above = held > kth
    equal = held == kth
    wanted = k - above.sum(axis=-1, keepdims=True)
    first = jnp.cumsum(equal, axis=-1) <= wanted
    return (above | (equal & first)) & visible


def drawn(seed, shape=(3, 17, WIDTH)):
    """Scores as the index makes them: most of them exact zeros of both signs
    (relu's zero times a head weight of either sign), ties among the rest,
    negatives, and a visible set with holes; one row all ``-0.0``, one row that
    sees nothing, one whose scores are all one value."""
    rng = np.random.default_rng(seed)
    scores = (np.round(rng.standard_normal(shape) * 4) / 4).astype(np.float32)
    scores = scores * rng.choice([0.0, -0.0, 0.0, 1.0, -1.0, 1e-3], shape).astype(np.float32)
    scores[0, 0] = -0.0
    scores[2, 5] = 0.75
    visible = rng.random(shape) < 0.8
    visible[1, 2] = False
    return scores, visible


@pytest.mark.parametrize("k", [1, 5, 64, 128, WIDTH - 1, WIDTH, WIDTH + 100])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_mask_by_counting_is_the_sorts_bit_for_bit(seed, k):
    scores, visible = drawn(seed)
    got = np.asarray(token_index.chosen_mask(jnp.asarray(scores), jnp.asarray(visible), k))
    np.testing.assert_array_equal(
        got, np.asarray(sorted_mask(jnp.asarray(scores), jnp.asarray(visible), k)))
    assert (got.sum(-1) == np.minimum(k, visible.sum(-1))).all()
    assert not got[1, 2].any()  # a row that sees nothing chooses nothing


@pytest.mark.parametrize("seed", [0, 1])
def test_a_k_of_each_rows_visible_count_chooses_all_it_sees(seed):
    """``k`` exactly the visible count of a row: the k-th score is the row's
    smallest visible one, and everything visible is chosen."""
    scores, visible = drawn(seed, (5, WIDTH))
    for row in range(5):
        k = int(visible[row].sum())
        got = np.asarray(token_index.chosen_mask(
            jnp.asarray(scores[row: row + 1]), jnp.asarray(visible[row: row + 1]), k))
        np.testing.assert_array_equal(got[0], visible[row])


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_the_kth_score_is_the_same_however_many_bits_a_pass_settles(monkeypatch, bits):
    scores, visible = drawn(bits)
    held = jnp.where(jnp.asarray(visible), jnp.asarray(scores), NEG_INF)
    monkeypatch.setattr(token_index, "COUNT_BITS", bits)
    for k in (1, 7, 200):
        want = np.sort(np.asarray(held), axis=-1)[..., WIDTH - k: WIDTH - k + 1]
        np.testing.assert_array_equal(np.asarray(token_index.kth_largest(held, k)), want)


@pytest.mark.parametrize("k", [1, 5, 64, 128, 200, WIDTH, WIDTH + 100])
@pytest.mark.parametrize("seed", [0, 1])
def test_a_rows_positions_are_top_ks_set_in_ascending_order(seed, k):
    """Rows shorter than ``k``, exactly ``k`` tokens long and full: the seen
    positions are ``jax.lax.top_k``'s as a set, the lower index among equals
    included (``top_k`` is given the scores with ``-0.0`` made ``+0.0``: on
    some backends it ranks the two zeros apart, which no float compare does),
    in ascending order; what is not seen is a position inside the row."""
    scores, _ = drawn(seed, (4, WIDTH))
    lengths = np.asarray([3, min(k, WIDTH) - 1, 150, WIDTH - 1], np.int32)
    at, seen = map(np.asarray, token_index.chosen_tokens(
        jnp.asarray(scores), jnp.asarray(lengths), k))
    assert at.shape == seen.shape == (4, min(k, WIDTH)) and at.dtype == np.int32
    pos = np.arange(WIDTH)
    held = np.where(pos[None, :] <= lengths[:, None], scores + 0.0, NEG_INF)
    _, top = jax.lax.top_k(jnp.asarray(held), min(k, WIDTH))
    for row in range(4):
        want = np.asarray(top[row])
        want = want[want <= lengths[row]]
        got = at[row][seen[row]]
        assert (np.diff(got) > 0).all()
        assert set(got) == set(want) and len(got) == len(want) == min(k, lengths[row] + 1)
        assert ((at[row] >= 0) & (at[row] < WIDTH)).all()


@pytest.mark.parametrize("width,k", [(300, 40), (300, 299), (128, 128), (129, 5), (7, 3),
                                     (1024, 1000)])
def test_positions_read_off_a_mask_are_flatnonzeros(width, k):
    """Widths of whole blocks, of a part of one and of one column past a
    block; rows with more True columns than ``k``, fewer, none and all."""
    rng = np.random.default_rng(width + k)
    mask = rng.random((6, width)) < rng.choice([0.05, 0.5, 0.95], (6, 1))
    mask[0] = False
    mask[1] = True
    at, seen = map(np.asarray, token_index.mask_positions(jnp.asarray(mask), k))
    assert at.shape == seen.shape == (6, k)
    for row in range(6):
        want = np.flatnonzero(mask[row])[:k]
        np.testing.assert_array_equal(at[row][seen[row]], want)
        assert seen[row].sum() == len(want) and seen[row][: len(want)].all()
        assert (at[row][~seen[row]] == 0).all()


def test_the_ordered_image_is_monotone_and_round_trips():
    """A sorted float32 sample over both signs, both zeros, the mask's
    ``-1e30`` and the largest finite values: the image never falls, rises
    wherever the float does, and comes back as the float it was (``-0.0`` as
    ``+0.0``, which equals it)."""
    rng = np.random.default_rng(0)
    sample = np.sort(np.concatenate([
        rng.standard_normal(2000).astype(np.float32) * 10.0 ** rng.integers(-20, 20, 2000),
        np.asarray([0.0, -0.0, NEG_INF, -NEG_INF, 3.4e38, -3.4e38, 1.0, 1.0, -1.0], np.float32),
    ]).astype(np.float32))
    image = np.asarray(token_index.ordered_image(jnp.asarray(sample)))
    assert image.dtype == np.uint32
    steps = np.diff(image.astype(np.int64))
    assert (steps >= 0).all()
    assert ((steps > 0) == (np.diff(sample) > 0)).all()
    back = np.asarray(token_index.from_ordered_image(jnp.asarray(image)))
    np.testing.assert_array_equal(back, sample)  # -0.0 == +0.0
    zeros = np.asarray(token_index.ordered_image(jnp.asarray([0.0, -0.0], jnp.float32)))
    assert zeros[0] == zeros[1] == 2 ** 31
