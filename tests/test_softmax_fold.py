"""A full-attention layer's prefill folds (``models/hybrid.py::_segment_softmax``)
in the two forms ``ops/latent_attention.py::expanded_segment`` dispatches between,
at small sizes on the CPU: the fold kernel (interpreted) handed a GQA layer's head
layout (queries a KV head, K and V two arrays of two widths from the rows' pages,
no rope part, the head's own scale) against the XLA form, and both against causal
attention over the gathered context written out here. A block-sparse layer's
segment (MiniCPM-SALA's, ``hybrid._sparse_mix``) is the same path under a KV
head's choice: its cases hold both forms to ``sparse_attend`` over the same
context gathered dense, and the mask to ``sparse_attend``'s own. The latent
callers' cases of the same kernel are ``tests/test_latent_moe.py``'s and
``tests/test_dsa_moe_model.py``'s.
"""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from distrl_llm_tpu.models import hybrid  # noqa: E402
from distrl_llm_tpu.ops import latent_attention as la  # noqa: E402
from distrl_llm_tpu.ops import sparse_attention as sa  # noqa: E402
from distrl_llm_tpu.ops.paged import gather_pages_dense  # noqa: E402

#: the five families' full layers: KV heads, query heads a KV head, a head's
#: width, the lanes its key takes in a page, a value's width, a segment, a page.
#: MiMo's at its published widths (192 in 256 lanes beside 128: K fills its
#: tiles, the launch has no last tile); the others at 16 lanes (the launch's
#: last tile holds all of K, zeros where a rope part would lie), Solar's at its
#: own 128
LAYOUTS = {
    "mimo_4x16_192in256_v128": (4, 16, 192, 256, 128, 128, 64),
    "exaone_8x8": (8, 8, 16, 16, 16, 16, 8),
    "solar_8x8_128_v128": (8, 8, 128, 128, 128, 128, 32),
    "jamba_1x20": (1, 20, 16, 16, 16, 16, 8),
    "zaya_2x4": (2, 4, 16, 16, 16, 16, 16),
    # a block-sparse layer's: the fold under a KV head's choice (``SELECTOR``)
    "sala_2x2_choice": (2, 2, 16, 16, 16, 16, 4),
}
ROWS = 2
#: the selector of the layout with a choice: blocks of a page, the first block
#: and the window's forced, the two best of the rest; a segment (16 tokens) is
#: ``dense_len``, so the first segment attends all it sees and every later one
#: chooses
SELECTOR = types.SimpleNamespace(
    sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=4, sparse_topk=2,
    sparse_init_blocks=1, sparse_window_size=6, sparse_dense_len=16)


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def case(layout: str, blocks: int):
    """bf16 values held in float32 (products and sums in float32, no rounding
    of the weights: what differs between the forms is their arithmetic's
    order): q, a K pool with zeros after a key's own values, a V pool, and a
    page table that scatters ``blocks`` segments of each row over the pool."""
    kv, group, hd, row, hv, s, ps = LAYOUTS[layout]
    keys = jax.random.split(jax.random.PRNGKey(len(layout) + blocks), 4)
    draw = lambda key, *shape: jax.random.normal(key, shape).astype(jnp.bfloat16).astype(
        jnp.float32)
    pages = ROWS * blocks * (s // ps)
    pages_k = draw(keys[0], kv, pages, ps, row).at[..., hd:].set(0.0)
    pages_v = draw(keys[1], kv, pages, ps, hv)
    idx = jax.random.permutation(keys[2], pages).reshape(ROWS, -1).astype(jnp.int32)
    return draw(keys[3], ROWS, s, kv * group, hd), pages_k, pages_v, idx, ps


def chosen_case(layout: str, blocks: int):
    """``case`` with keys that make the choice plain: KV head ``h``'s keys of
    segment ``h`` carry four times a direction that every query carries too, so
    a query past ``dense_len`` picks its two blocks there, the two heads apart,
    and a head's queries choose NOTHING in the other head's segment. Returns the
    case, the context gathered dense, the selector's pooled keys and the
    queries' positions."""
    q, pages_k, pages_v, idx, ps = case(layout, blocks)
    kv, s = pages_k.shape[0], q.shape[1]
    k = gather_pages_dense(pages_k, idx)  # [B, T, K, hd]
    toward = jnp.ones((q.shape[-1],), jnp.float32)
    for head in range(kv):
        at = slice(head * s, (head + 1) * s)
        k = k.at[:, at, head].set(k[:, at, head] + 4.0 * toward)
    q = q + toward
    pages_k = hybrid._write_segment_pages(pages_k, k, idx, ps)
    pos = (blocks - 1) * s + jnp.broadcast_to(jnp.arange(s), (ROWS, s))
    return (q, pages_k, pages_v, idx, ps), k, sa.pool_keys(k, SELECTOR), pos



def causal_attention(q, pages_k, pages_v, idx, start, ps):
    """Every query over every key of its row at or before it, one softmax."""
    b, s, heads, hd = q.shape
    kv = pages_k.shape[0]
    context = lambda pages: pages[:, idx[:, : (start + s) // ps]].transpose(
        1, 2, 3, 0, 4).reshape(b, start + s, kv, -1)
    k, v = context(pages_k)[..., :hd], context(pages_v)
    scores = jnp.einsum("bskgd,bjkd->bkgsj", q.reshape(b, s, kv, heads // kv, hd), k)
    seen = jnp.arange(start + s)[None, :] <= (start + jnp.arange(s))[:, None]
    p = jax.nn.softmax(jnp.where(seen, scores * hd ** -0.5, -jnp.inf), axis=-1)
    return jnp.einsum("bkgsj,bjkd->bskgd", p, v).reshape(b, s, heads, -1)


def through_the_kernel(monkeypatch):
    monkeypatch.setattr(la, "expanded_segment_impl", lambda q_nope, v_dim: "kernel")
    monkeypatch.setattr(la, "expanded_fold_kernel", functools.partial(
        la.expanded_fold_kernel, interpret=True))


def wrong_group(segment):
    """Query head h reads KV head h // group + 1."""
    def bent(q_nope, q_pe, block, *rest, **kw):
        def rolled(j):
            (k, v), k_pe = block(j)
            return (jnp.roll(k, 1, axis=1), jnp.roll(v, 1, axis=1)), k_pe
        return segment(q_nope, q_pe, rolled, *rest, **kw)
    return bent


def rows_scale(segment):
    """The scores scaled by the key row's 256 lanes, not the head's 192."""
    return lambda *args, scale, **kw: segment(*args, scale=None, **kw)


def other_heads_choice(segment):
    """Query head h attends what KV head h // group + 1 chose."""
    return lambda *args, **kw: segment(
        *args[:-1], jnp.roll(args[-1], 1, axis=1), **kw)


@pytest.mark.parametrize("blocks", [1, 4], ids=["first_segment", "after_three_blocks"])
@pytest.mark.parametrize("layout,bend", [(name, None) for name in LAYOUTS] + [
    ("mimo_4x16_192in256_v128", wrong_group), ("mimo_4x16_192in256_v128", rows_scale),
    ("sala_2x2_choice", other_heads_choice)])
def test_the_fold_kernel_over_pages_is_the_xla_form_and_causal_attention(
        monkeypatch, layout, bend, blocks):
    """``_segment_softmax`` with every fold run by ``expanded_fold_kernel``
    (interpreted) against the form it takes on a CPU, to 2e-5, and both against
    causal attention over the row's gathered context: a first segment (one
    block, the diagonal alone) and a later one (three blocks seen whole, then
    the diagonal), in each family's head layout. A program that reads a KV
    head for the wrong group, or scales the scores by the key's row, leaves
    the agreement in both forms.

    The layout with a choice is a block-sparse layer's segment: both forms
    under ``segment_choice``'s mask, a KV head's, against ``sparse_attend``
    over the same context gathered dense. Its first segment lies within
    ``dense_len`` (every head attends all it sees: handed the OTHER head's
    choice it still agrees); its later one past it, where the two heads choose
    apart, a head's tile of queries has no choice at all in one whole tile of
    keys (which the fold must leave as it found it), and the other head's
    choice leaves the agreement."""
    chosen = ()
    if "choice" in layout:
        (q, pages_k, pages_v, idx, ps), k, pooled, pos = chosen_case(layout, blocks)
        mask = sa.segment_choice(q, pooled, pos, SELECTOR, k.shape[1])  # [B, K, S, T]
        chosen = (mask.astype(la.FOLD_MASK_DTYPE),)
        want = sa.sparse_attend(q, k, gather_pages_dense(pages_v, idx), pooled, pos, SELECTOR)
        tiles = np.asarray(mask).reshape(*mask.shape[:3], blocks, -1).any(axis=(2, 4))
        apart = bool((np.asarray(mask[:, 0]) != np.asarray(mask[:, 1])).any())
        # [B, K, tiles of keys]: past dense_len a head skips the other's segment
        assert apart == (not tiles.all()) == (blocks > 1)
    else:
        q, pages_k, pages_v, idx, ps = case(layout, blocks)
        want = None
    start = (blocks - 1) * q.shape[1]
    if bend is not None:
        monkeypatch.setattr(hybrid, "expanded_segment", bend(la.expanded_segment))
    run = lambda: hybrid._segment_softmax(
        q, pages_k, pages_v, idx, jnp.int32(start), ps, *chosen)
    if want is None:
        want = causal_attention(q, pages_k, pages_v, idx, start, ps)
    xla = run()
    through_the_kernel(monkeypatch)
    kernel = run()
    assert kernel.shape == want.shape == (*q.shape[:3], pages_v.shape[-1])
    np.testing.assert_allclose(kernel, xla, rtol=2e-5, atol=2e-5)
    if bend is None or (chosen and blocks == 1):
        np.testing.assert_allclose(kernel, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(xla, want, rtol=2e-5, atol=2e-5)
    else:
        assert np.abs(np.asarray(kernel) - want).max() > 1e-2
        assert np.abs(np.asarray(xla) - want).max() > 1e-2


@pytest.mark.parametrize("backend,dtype,layout,segment,want", [
    ("tpu", jnp.bfloat16, (4, 16, 192, 256, 128), 1024, "kernel"),  # the sink cell's
    ("tpu", jnp.bfloat16, (8, 8, 128, 128, 128), 1024, "kernel"),  # the window cell's
    ("tpu", jnp.bfloat16, (1, 20, 128, 128, 128), 640, "kernel"),  # 20 heads over one
    ("tpu", jnp.bfloat16, (2, 4, 128, 128, 128), 1024, "kernel"),
    ("cpu", jnp.bfloat16, (4, 16, 192, 256, 128), 1024, "xla"),
    ("tpu", jnp.float32, (8, 8, 128, 128, 128), 1024, "xla"),
    ("tpu", jnp.bfloat16, (2, 4, 16, 16, 16), 1024, "xla"),  # the tests' tiny heads
    ("tpu", jnp.bfloat16, (8, 8, 128, 128, 128), 1000, "xla"),  # no whole tiles of queries
])
def test_the_full_layers_form_is_read_off_the_backend_and_the_shapes(
        monkeypatch, backend, dtype, layout, segment, want):
    """The rule for a GQA layer's pages, and what a segment traced under it
    records: its query heads, its key's row, no rope part, its value's width."""
    kv, group, hd, row, hv = layout
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(la, "dispatch_choices", {})
    shape = lambda *s, t=dtype: jax.ShapeDtypeStruct(s, t)
    ps = 128 if segment % 128 == 0 else 8  # a segment is whole pages
    out = jax.eval_shape(  # traced, never lowered: the kernel's launch is an equation
        lambda *args: hybrid._segment_softmax(*args, jnp.int32(segment), ps),
        shape(2, segment, kv * group, hd), shape(kv, 64, ps, row), shape(kv, 64, ps, hv),
        shape(2, 2 * segment // ps, t=jnp.int32))
    assert out.shape == (2, segment, kv * group, hv) and out.dtype == dtype
    assert la.dispatch_choices == {
        la.dispatch_key(kv * group, row, 0, hv, segment, dtype): want}
