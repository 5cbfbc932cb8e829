"""Native paged-decode kernel parity vs the jnp reference (interpreter mode).

The kernel exists because both jaxlib paged kernels reject head_dim % 128
!= 0 on real Mosaic (round-3 silicon finding — ops/paged_native.py). CI
pins its numerics here at exactly the shapes that broke: GQA 14q/2kv,
hd=64, ragged lengths, dead rows; tests/test_tpu_compile.py holds the
lowering for a v5e and chip_smoke.py the numbers on the chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrl_llm_tpu.ops.paged import (
    make_page_table,
    paged_attention_reference,
    quantize_pages,
)
import functools

from distrl_llm_tpu.ops.paged_native import (
    live_page_walk,
    native_pages_per_step,
    paged_attention_native,
)

KERNELS = {
    # what "auto" launches on a TPU: all kv heads and a length-bounded run of
    # pages a grid step, the block sized from the shapes (here: the whole row)
    "native": paged_attention_native,
    # ... and with rows of several blocks, ragged on most of the shared cases
    "native_ppb2": functools.partial(
        paged_attention_native, pages_per_block=2
    ),
}


def _setup(b, h, kh, hd, ps, pps, seed=0, lengths=None):
    rng = np.random.default_rng(seed)
    cap = pps * ps
    kp = jnp.asarray(rng.standard_normal((kh, b * pps, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((kh, b * pps, ps, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    table = jnp.asarray(make_page_table(b, cap, ps))
    if lengths is None:
        lengths = rng.integers(1, cap + 1, size=(b,))
    lengths = jnp.asarray(lengths, jnp.int32)
    return q, kp, vp, lengths, table


@pytest.fixture(params=sorted(KERNELS))
def _native(request):
    """Both block sizes share every parity case: they differ in grid and
    block shape, and in how many pages one softmax update covers."""
    kernel = KERNELS[request.param]

    def call(q, kp, vp, lengths, table, **kw):
        hd = q.shape[-1]
        return kernel(
            q * hd**-0.5, kp, vp, lengths, table, interpret=True, **kw
        )

    return call


class TestNativePagedParity:
    def test_qwen05b_geometry(self, _native):
        """14 q heads / 2 kv heads / hd=64 — the exact config both jaxlib
        kernels reject on real Mosaic."""
        q, kp, vp, lengths, table = _setup(b=4, h=14, kh=2, hd=64, ps=8, pps=3)
        got = _native(q, kp, vp, lengths, table)
        want = paged_attention_reference(q, kp, vp, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_hd128_and_mha(self, _native):
        for h, kh, hd in ((8, 8, 128), (4, 1, 32)):
            q, kp, vp, lengths, table = _setup(
                b=3, h=h, kh=kh, hd=hd, ps=8, pps=2, seed=h
            )
            got = _native(q, kp, vp, lengths, table)
            want = paged_attention_reference(q, kp, vp, lengths, table)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
            )

    def test_dead_rows_emit_zeros_not_nan(self, _native):
        """length-0 rows (empty decode slots) must produce finite output —
        a NaN would poison the logsumexp capture path even though the done
        mask discards the sampled token."""
        q, kp, vp, _, table = _setup(b=3, h=4, kh=2, hd=64, ps=8, pps=2)
        lengths = jnp.asarray([10, 0, 16], jnp.int32)
        got = np.asarray(_native(q, kp, vp, lengths, table))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[1], 0.0)
        want = np.asarray(paged_attention_reference(q, kp, vp, lengths, table))
        np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-5, rtol=2e-5)

    def test_single_page_sequences(self, _native):
        q, kp, vp, _, table = _setup(b=2, h=4, kh=2, hd=64, ps=8, pps=1)
        lengths = jnp.asarray([3, 8], jnp.int32)
        got = _native(q, kp, vp, lengths, table)
        want = paged_attention_reference(q, kp, vp, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_garbage_table_entries_beyond_length_ignored(self, _native):
        """Entries past a row's allocated pages may be stale ids — clamped
        and masked, they must not affect the output."""
        q, kp, vp, _, table = _setup(b=2, h=4, kh=2, hd=64, ps=8, pps=3)
        lengths = jnp.asarray([5, 9], jnp.int32)  # rows use 1 and 2 pages
        base = _native(q, kp, vp, lengths, table)
        poisoned = np.asarray(table).copy()
        poisoned[0, 1:] = 99999  # out of range — clamp must keep it legal
        poisoned[1, 2:] = -7
        got = _native(q, kp, vp, lengths, jnp.asarray(poisoned))
        np.testing.assert_allclose(np.asarray(got), np.asarray(base), atol=0, rtol=0)

    def test_int8_compact_scales(self, _native):
        q, kp, vp, lengths, table = _setup(b=4, h=14, kh=2, hd=64, ps=8, pps=3)
        kq = quantize_pages(jnp.asarray(kp, jnp.bfloat16))
        vq = quantize_pages(jnp.asarray(vp, jnp.bfloat16))
        got = _native(
            q.astype(jnp.bfloat16), kq.weight, vq.weight, lengths, table,
            k_scales=kq.scales, v_scales=vq.scales,
        )
        want = paged_attention_reference(
            q.astype(jnp.bfloat16), kq, vq, lengths, table
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_validation(self):
        q, kp, vp, lengths, table = _setup(b=2, h=4, kh=2, hd=64, ps=8, pps=2)
        with pytest.raises(ValueError, match="head_dim"):
            paged_attention_native(
                q[..., :32], kp, vp, lengths, table, interpret=True
            )
        with pytest.raises(ValueError, match="divisible"):
            paged_attention_native(
                q[:, :3], kp, vp, lengths, table, interpret=True
            )
        with pytest.raises(ValueError, match="pages_per_block"):
            paged_attention_native(
                q, kp, vp, lengths, table, pages_per_block=-1, interpret=True
            )


# the decode geometries "auto" serves: the benchmark's rollout cells
# (Qwen2.5-7B: 28 / 4 heads of 128, page 128, five pages a row; Jamba2-3B's
# two attention layers: ONE kv head under a group of 20; Solar-Open2's
# softmax layer: 64 / 8) and the CLI's small model (Qwen2.5-0.5B: 14 / 2
# heads of 64)
AUTO_GEOMETRIES = {
    "cell-28x4x128": dict(h=28, kh=4, hd=128),
    "jamba-20x1x128": dict(h=20, kh=1, hd=128),
    "qwen05b-14x2x64": dict(h=14, kh=2, hd=64),
    "solar-64x8x128": dict(h=64, kh=8, hd=128),
}
PAGE, PPS = 128, 5
#: every length the walk has a case for: an empty slot, one token, a page to
#: the token, one past it, a row in its third page, the full table
EDGE_LENGTHS = (0, 1, PAGE, PAGE + 1, 2 * PAGE + 37, PPS * PAGE)


class TestAutoLaunch:
    """What ``paged_impl="auto"`` launches on a TPU (PR 32), under the
    interpreter at the geometries it serves: one grid step a row at five
    pages of 128 (``native_pages_per_step``), two and three with a block
    named; bf16 pages and the int8 container; the page walk bounded by each
    row's length."""

    @staticmethod
    def _case(h, kh, hd, pages, poisoned):
        rng = np.random.default_rng(hd + kh)
        b = len(EDGE_LENGTHS)
        shape = (kh, b * PPS, PAGE, hd)
        kp = rng.standard_normal(shape).astype(np.float32)
        vp = rng.standard_normal(shape).astype(np.float32)
        q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.bfloat16)
        table = make_page_table(b, PPS * PAGE, PAGE)
        lengths = jnp.asarray(EDGE_LENGTHS, jnp.int32)
        kp, vp = jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16)
        want = paged_attention_reference(
            q, *((quantize_pages(kp), quantize_pages(vp))
                 if pages == "int8" else (kp, vp)),
            lengths, jnp.asarray(table),
        )
        if poisoned:
            # every page past a row's last live one holds NaN: a fetch that
            # reached the arithmetic would show in the output
            dead = np.concatenate([
                table[r, -(-n // PAGE):] for r, n in enumerate(EDGE_LENGTHS)
            ])
            kp = kp.at[:, dead].set(jnp.nan)
            vp = vp.at[:, dead].set(jnp.nan)
        scales = {}
        if pages == "int8":
            kq, vq = quantize_pages(kp), quantize_pages(vp)
            kp, vp = kq.weight, vq.weight
            scales = dict(k_scales=kq.scales, v_scales=vq.scales)
        return q, kp, vp, lengths, jnp.asarray(table), scales, want

    @pytest.mark.parametrize(
        "poisoned", [False, True], ids=["clean", "nan-past-length"])
    @pytest.mark.parametrize("ppb", [0, 2, 3], ids=["own-block", "ppb2", "ppb3"])
    @pytest.mark.parametrize("pages", ["bf16", "int8"])
    @pytest.mark.parametrize("geom", sorted(AUTO_GEOMETRIES))
    def test_parity_at_edge_lengths(self, geom, pages, ppb, poisoned):
        g = AUTO_GEOMETRIES[geom]
        q, kp, vp, lengths, table, scales, want = self._case(
            **g, pages=pages, poisoned=poisoned)
        got = np.asarray(paged_attention_native(
            q * g["hd"]**-0.5, kp, vp, lengths, table, **scales,
            pages_per_block=ppb, interpret=True,
        ), np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[0], 0.0)  # the empty slot
        tol = 3e-2 if pages == "int8" else 1e-2  # the bf16 output's rounding
        np.testing.assert_allclose(
            got[1:], np.asarray(want, np.float32)[1:], atol=tol, rtol=tol)

    @pytest.mark.parametrize(
        "geom,pages,pps,want",
        [
            ("cell-28x4x128", "bf16", 5, 5),  # the cell: a row is one step
            ("cell-28x4x128", "bf16", 64, 8),  # 8k contexts: the cap
            ("cell-28x4x128", "int8", 64, 3),  # the compact scales' lane tiles
            ("qwen05b-14x2x64", "bf16", 5, 5),
            ("qwen05b-14x2x64", "bf16", 64, 8),
            # one kv head: the cap of 8 pages long before the VMEM budget
            ("jamba-20x1x128", "bf16", 19, 8),  # the cell's table: 3 steps a row
            ("jamba-20x1x128", "int8", 19, 8),
            # eight kv heads: the budget holds four pages of them, two as int8
            ("solar-64x8x128", "bf16", 22, 4),  # the cell's table: 6 steps a row
            ("solar-64x8x128", "int8", 22, 1),
        ],
    )
    def test_pages_per_step_come_from_the_shapes(self, geom, pages, pps, want):
        g = AUTO_GEOMETRIES[geom]
        assert native_pages_per_step(
            num_kv_heads=g["kh"], head_dim=g["hd"], page_size=PAGE, pps=pps,
            kv_itemsize=1 if pages == "int8" else 2,
            quantized=pages == "int8",
        ) == want

    @pytest.mark.parametrize("ppb", [1, 2, 5])
    def test_walk_names_a_new_page_only_where_one_is_live(self, ppb):
        """The pipeline copies a block when its index changes between two
        grid steps: over the whole walk the changes are the live pages, and
        every entry past a row's length repeats the entry one step before."""
        lengths = np.asarray(EDGE_LENGTHS + (3 * PAGE, 0, 5), np.int32)
        b, width = len(lengths), -(-PPS // ppb) * ppb
        rng = np.random.default_rng(ppb)
        table = rng.permutation(b * width).astype(np.int32).reshape(b, width)
        walk = np.asarray(live_page_walk(
            jnp.asarray(table), jnp.asarray(lengths), page_size=PAGE, ppb=ppb,
        )).reshape(-1, ppb)
        flat = table.reshape(-1, ppb)
        live = (
            np.arange(width)[None, :] * PAGE < lengths[:, None]
        ).reshape(-1, ppb)
        np.testing.assert_array_equal(walk[live], flat[live])
        later = np.s_[1:]
        np.testing.assert_array_equal(
            walk[later][~live[later]], walk[:-1][~live[later]])
        changes = int((walk[1:] != walk[:-1]).sum())
        assert changes <= int(live.sum())


class TestBlocksOfARow:
    """``paged_attention_native`` with its block named, so that a row is
    several grid steps at small sizes: a ragged final block for every pps %
    ppb, a block wider than the table, the int8 container, empty slots; and
    the analytic grid-step count the engines record against the launch's
    real grid."""

    @pytest.mark.parametrize("ppb", [1, 2, 4, 8])
    def test_r5_geometry_parity_nondivisor_tail(self, ppb):
        """The benched 0.5B shape: 14q/2kv, hd=64, pps=13 — 13 is a
        non-divisor of every ppb > 1, so the final block is ragged."""
        q, kp, vp, lengths, table = _setup(
            b=4, h=14, kh=2, hd=64, ps=8, pps=13
        )
        got = paged_attention_native(
            q * 64**-0.5, kp, vp, lengths, table,
            pages_per_block=ppb, interpret=True,
        )
        want = paged_attention_reference(q, kp, vp, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_hd128_parity(self):
        """The 7B-class shape (4 kv heads, hd=128), ppb > pps clamps."""
        q, kp, vp, lengths, table = _setup(
            b=3, h=28, kh=4, hd=128, ps=8, pps=3, seed=7
        )
        got = paged_attention_native(
            q * 128**-0.5, kp, vp, lengths, table,
            pages_per_block=8, interpret=True,
        )
        want = paged_attention_reference(q, kp, vp, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("ppb", [2, 4, 8])
    def test_int8_compact_scales(self, ppb):
        q, kp, vp, lengths, table = _setup(b=4, h=14, kh=2, hd=64, ps=8, pps=5)
        kq = quantize_pages(jnp.asarray(kp, jnp.bfloat16))
        vq = quantize_pages(jnp.asarray(vp, jnp.bfloat16))
        got = paged_attention_native(
            q.astype(jnp.bfloat16) * 64**-0.5, kq.weight, vq.weight,
            lengths, table, k_scales=kq.scales, v_scales=vq.scales,
            pages_per_block=ppb, interpret=True,
        )
        want = paged_attention_reference(
            q.astype(jnp.bfloat16), kq, vq, lengths, table
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_dead_rows_emit_zeros_not_nan(self):
        q, kp, vp, _, table = _setup(b=3, h=4, kh=2, hd=64, ps=8, pps=5)
        lengths = jnp.asarray([10, 0, 37], jnp.int32)
        got = np.asarray(paged_attention_native(
            q * 64**-0.5, kp, vp, lengths, table,
            pages_per_block=4, interpret=True,
        ))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[1], 0.0)

    @pytest.mark.parametrize("pages", ["bf16", "int8"])
    @pytest.mark.parametrize("geom,pps", [
        ("cell-28x4x128", 5), ("jamba-20x1x128", 19), ("solar-64x8x128", 22),
        ("qwen05b-14x2x64", 13),
    ])
    def test_the_count_of_grid_steps_is_the_launchs_grid(self, geom, pps, pages,
                                                         monkeypatch):
        """``paged_grid_steps("native", ...)`` against the grid the launch
        hands Pallas at the same shapes, read where it is handed over."""
        from distrl_llm_tpu.ops import paged_native
        from distrl_llm_tpu.ops.paged import init_quantized_pages, paged_grid_steps

        g, rows, seen = AUTO_GEOMETRIES[geom], 3, []
        spec = paged_native.pltpu.PrefetchScalarGridSpec
        monkeypatch.setattr(
            paged_native.pltpu, "PrefetchScalarGridSpec",
            lambda **kw: seen.append(kw["grid"]) or spec(**kw))
        shape = (g["kh"], rows * pps, PAGE, g["hd"])
        if pages == "int8":
            pool = init_quantized_pages(shape)
            kw = dict(k_scales=pool.scales, v_scales=pool.scales)
            pool = pool.weight
        else:
            pool, kw = jnp.zeros(shape, jnp.bfloat16), {}
        jax.eval_shape(
            functools.partial(paged_attention_native.__wrapped__, interpret=True),
            jnp.zeros((rows, g["h"], g["hd"]), jnp.bfloat16), pool, pool,
            jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows, pps), jnp.int32), **kw)
        (grid,) = seen
        assert grid[0] * grid[1] == paged_grid_steps(
            "native", batch=rows, num_kv_heads=g["kh"], pps=pps,
            head_dim=g["hd"], page_size=PAGE,
            kv_itemsize=1 if pages == "int8" else 2, quantized=pages == "int8")

    def test_grid_step_budget_r5_geometry(self):
        """At the benched r5 paged geometry (480 rows × 2 kv × 13 pages) a
        (B, K, pps) grid of one page of one head a step is about 12k grid
        steps a call, 300k a decode step (PERF.md §7): the launch sizes its
        own block, 8 pages of 128 x 64 over both heads, an eighth and less."""
        from distrl_llm_tpu.ops.paged import paged_grid_steps

        r5 = dict(batch=480, num_kv_heads=2, pps=13)
        steps = paged_grid_steps("native", head_dim=64, page_size=128, **r5)
        assert steps == 480 * 2  # ceil(13/8) = 2 blocks per row
        assert steps * 8 <= 480 * 2 * 13

    def test_grid_step_model_shapes(self):
        from distrl_llm_tpu.ops.paged import paged_grid_steps

        g = dict(batch=8, num_kv_heads=2, pps=12)
        # native: (B, ceil(pps / its own pages a step)), from the shapes and
        # the pages' dtype; the benchmark's cell is one step a row; the
        # reference has no grid
        assert paged_grid_steps(
            "native", head_dim=64, page_size=16, **g) == 8 * 2
        assert paged_grid_steps(
            "native", batch=64, num_kv_heads=4, pps=5, head_dim=128,
            page_size=128) == 64
        assert paged_grid_steps(
            "native", batch=64, num_kv_heads=4, pps=64, head_dim=128,
            page_size=128, kv_itemsize=1, quantized=True) == 64 * 22
        with pytest.raises(ValueError, match="head_dim"):
            paged_grid_steps("native", **g)
        assert paged_grid_steps("reference", **g) == 0


class TestTheLaunchTheBackendChooses:
    """``resolve_paged_impl``: the three spellings a caller may name, and
    what "auto" is: one launch a backend, whatever the shapes (PERF.md §6,
    PR 47)."""

    @pytest.mark.parametrize("gone", ["kernel", "native_folded", "native_blocked"])
    def test_a_deleted_spelling_is_refused_by_name(self, gone):
        from distrl_llm_tpu.ops.paged import (
            PAGED_IMPLS, paged_attention_op, resolve_paged_impl,
        )

        assert PAGED_IMPLS == ("auto", "reference", "native")
        with pytest.raises(ValueError, match=gone) as err:
            resolve_paged_impl(gone)
        assert all(name in str(err.value) for name in PAGED_IMPLS)
        q, kp, vp, lengths, table = _setup(b=2, h=4, kh=2, hd=64, ps=8, pps=2)
        with pytest.raises(ValueError, match=gone):
            paged_attention_op(q, kp, vp, lengths, table, impl=gone)

    @pytest.mark.parametrize("backend,want", [("tpu", "native"), ("cpu", "reference")])
    @pytest.mark.parametrize("h,kh,hd,pps", [
        (28, 4, 128, 5), (20, 1, 128, 19), (64, 8, 128, 22),  # the cells' tables
        (28, 4, 128, 64), (20, 1, 128, 72), (14, 2, 64, 64),  # rows of 8k-9k
    ])
    def test_auto_is_one_launch_a_backend(
            self, backend, want, h, kh, hd, pps, monkeypatch):
        """What "auto" hands the step at the cells' geometries and at tables
        three times as wide: the native adapter on a TPU backend, the
        reference elsewhere, and the record under the geometry's key."""
        from distrl_llm_tpu.ops import paged

        ran = []
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(
            paged, "_native_call",
            lambda q, *a, quantized: ran.append("native") or q)
        monkeypatch.setattr(
            paged, "paged_attention_reference",
            lambda q, *a: ran.append("reference") or q)
        assert paged.resolve_paged_impl("auto") == want
        for named in ("reference", "native"):
            assert paged.resolve_paged_impl(named) == named
        q, kp, vp, lengths, table = _setup(b=2, h=h, kh=kh, hd=hd, ps=8, pps=pps)
        paged.paged_attention_op(q, kp, vp, lengths, table)
        assert ran == [want]
        assert paged.dispatch_choices[paged.dispatch_choice_key(
            quantized=False, num_kv_heads=kh, num_groups=h // kh,
            head_dim=hd, page_size=8, pps=pps,
        )] == want


class TestVerifyKernel:
    """Fused draft-block verify (ISSUE 6): the whole S-query speculative
    verify in ONE blocked sweep — parity vs the per-position ladder
    reference (``paged_verify_reference``), causal offsets, ragged tails,
    int8, and the analytic grid model the engines/bench consume."""

    @staticmethod
    def _setup_verify(b, s, h, kh, hd, ps, pps, seed=0, lengths=None):
        rng = np.random.default_rng(seed)
        cap = pps * ps
        kp = jnp.asarray(
            rng.standard_normal((kh, b * pps, ps, hd)), jnp.float32)
        vp = jnp.asarray(
            rng.standard_normal((kh, b * pps, ps, hd)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
        table = jnp.asarray(make_page_table(b, cap, ps))
        if lengths is None:
            # resident BEFORE the draft block: leave room for s tokens
            lengths = rng.integers(1, cap - s, size=(b,))
        lengths = jnp.asarray(lengths, jnp.int32)
        return q, kp, vp, lengths, table

    @pytest.mark.parametrize("ppb", [1, 2, 4, 8])
    def test_r5_geometry_parity_per_query_causality(self, ppb):
        """GQA 14q/2kv hd=64 at d=3 (verify width 4), including non-divisor
        page tails, vs the exact lengths + i + 1 ladder the unrolled path
        dispatches per position."""
        from distrl_llm_tpu.ops.paged import paged_verify_reference
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        q, kp, vp, lengths, table = self._setup_verify(
            b=3, s=4, h=14, kh=2, hd=64, ps=8, pps=5)
        got = paged_attention_native_verify(
            q * 64**-0.5, kp, vp, lengths, table,
            pages_per_block=ppb, interpret=True)
        want = paged_verify_reference(q, kp, vp, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("s", [2, 5])
    def test_draft_lengths_and_page_crossing(self, s):
        """Lengths pinned right at / one below a page boundary so the draft
        block itself crosses pages — the in-kernel causal ladder must track
        each query's own limit, not the block guard's."""
        from distrl_llm_tpu.ops.paged import paged_verify_reference
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        q, kp, vp, _, table = self._setup_verify(
            b=4, s=s, h=8, kh=2, hd=32, ps=4, pps=6)
        lengths = jnp.asarray([3, 4, 7, 15], jnp.int32)
        got = paged_attention_native_verify(
            q * 32**-0.5, kp, vp, lengths, table,
            pages_per_block=2, interpret=True)
        want = paged_verify_reference(q, kp, vp, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_int8_compact_scales(self):
        from distrl_llm_tpu.ops.paged import paged_verify_reference
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        q, kp, vp, lengths, table = self._setup_verify(
            b=3, s=3, h=14, kh=2, hd=64, ps=8, pps=4, seed=3)
        kq, vq = quantize_pages(kp), quantize_pages(vp)
        got = paged_attention_native_verify(
            q * 64**-0.5, kq.weight, vq.weight, lengths, table,
            k_scales=kq.scales, v_scales=vq.scales,
            pages_per_block=4, interpret=True)
        want = paged_verify_reference(q, kq, vq, lengths, table)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_s1_matches_blocked_decode_at_length_plus_one(self):
        """A 1-token 'draft block' is a decode step over length+1 keys: at
        one page a grid step the verify kernel must agree with the decode
        kernel exactly (same op order, same online-softmax carry)."""
        q, kp, vp, lengths, table = self._setup_verify(
            b=4, s=1, h=14, kh=2, hd=64, ps=8, pps=3)
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        got = paged_attention_native_verify(
            q * 64**-0.5, kp, vp, lengths, table,
            pages_per_block=1, interpret=True)
        want = paged_attention_native(
            q[:, 0] * 64**-0.5, kp, vp, lengths + 1, table,
            pages_per_block=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(want))

    def test_s1_is_within_rounding_of_native_at_two_pages_a_step(self):
        """NEW with PR 47, and looser than the case above on purpose: at
        several pages a grid step ``paged_attention_native`` makes ONE
        softmax update of them where the verify kernel still chains one a
        page (the deleted blocked body's order; ROADMAP D5), so the two
        agree to float32 rounding and not to the bit."""
        q, kp, vp, lengths, table = self._setup_verify(
            b=4, s=1, h=14, kh=2, hd=64, ps=8, pps=3)
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        got = paged_attention_native_verify(
            q * 64**-0.5, kp, vp, lengths, table,
            pages_per_block=2, interpret=True)
        want = paged_attention_native(
            q[:, 0] * 64**-0.5, kp, vp, lengths + 1, table,
            pages_per_block=2, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got[:, 0]), np.asarray(want), atol=2e-6, rtol=2e-6)

    def test_zero_length_rows_emit_finite(self):
        """Dead refill slots verify garbage over scratch pages — outputs
        must be finite (every query row attends at least its own draft
        position, so the 0/0 softmax hazard cannot arise)."""
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        q, kp, vp, _, table = self._setup_verify(
            b=3, s=4, h=4, kh=2, hd=32, ps=4, pps=4)
        out = paged_attention_native_verify(
            q * 32**-0.5, kp, vp, jnp.zeros((3,), jnp.int32), table,
            pages_per_block=2, interpret=True)
        assert np.isfinite(np.asarray(out)).all()

    def test_grid_step_model(self):
        """The acceptance pin: a (d+1)-token verify step at the r5 geometry
        costs ONE blocked sweep — B·ceil(pps/ppb) — not (d+1) sweeps."""
        from distrl_llm_tpu.ops.paged import (
            VERIFY_PAGES_PER_BLOCK, paged_grid_steps,
        )

        r5 = dict(batch=480, num_kv_heads=2, pps=13)
        fused = paged_grid_steps("native_verify", **r5)
        assert VERIFY_PAGES_PER_BLOCK == 8
        assert fused == 480 * -(-13 // 8)  # ONE sweep
        # the unrolled fan-out pays the decode launch's count (d+1)× a step
        decode = paged_grid_steps("native", head_dim=64, page_size=128, **r5)
        assert fused == decode
        # a table narrower than the block is one block a row
        assert paged_grid_steps(
            "native_verify", batch=480, num_kv_heads=2, pps=5) == 480

    def test_validation(self):
        from distrl_llm_tpu.ops.paged_native import (
            paged_attention_native_verify,
        )

        q, kp, vp, lengths, table = self._setup_verify(
            b=2, s=2, h=4, kh=2, hd=32, ps=4, pps=2)
        with pytest.raises(ValueError, match="pages_per_block"):
            paged_attention_native_verify(
                q, kp, vp, lengths, table, pages_per_block=0, interpret=True)
        with pytest.raises(ValueError, match="divisible"):
            paged_attention_native_verify(
                q[:, :, :3], kp, vp, lengths, table, interpret=True)


class TestVerifyDispatch:
    """paged_verify_op: the dispatch layer the transformer's verify branch
    routes through — unrolled fallback exactness off-TPU, choice records
    keyed apart from decode dispatches."""

    def test_unrolled_matches_per_position_op(self):
        from distrl_llm_tpu.ops.paged import (
            paged_attention_op, paged_verify_op,
        )

        q, kp, vp, lengths, table = TestVerifyKernel._setup_verify(
            b=3, s=3, h=14, kh=2, hd=64, ps=8, pps=4)
        for verify_impl in ("fused", "unrolled"):
            # off-TPU both resolve to the unrolled per-position dispatch —
            # bit-identical to what the transformer always did
            got = paged_verify_op(
                q, kp, vp, lengths, table, verify_impl=verify_impl)
            want = jnp.stack(
                [
                    paged_attention_op(
                        q[:, i], kp, vp, lengths + i + 1, table)
                    for i in range(3)
                ],
                axis=1,
            )
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_choice_recorded_under_verify_key(self):
        from distrl_llm_tpu.ops import paged as paged_mod

        q, kp, vp, lengths, table = TestVerifyKernel._setup_verify(
            b=2, s=3, h=4, kh=2, hd=32, ps=4, pps=2)
        paged_mod.dispatch_choices.clear()
        paged_mod.paged_verify_op(q, kp, vp, lengths, table)
        key = paged_mod.dispatch_choice_key(
            quantized=False, num_kv_heads=2, num_groups=2, head_dim=32,
            page_size=4, pps=2, impl="auto", verify_len=3)
        assert paged_mod.dispatch_choices[key] == "unrolled"  # CPU backend
        # verify keys never alias the single-query decode record
        assert key[-1] == 3
        paged_mod.dispatch_choices.clear()

    def test_verify_impl_validation(self):
        from distrl_llm_tpu.ops.paged import paged_verify_op

        q, kp, vp, lengths, table = TestVerifyKernel._setup_verify(
            b=2, s=2, h=4, kh=2, hd=32, ps=4, pps=2)
        with pytest.raises(ValueError, match="verify_impl"):
            paged_verify_op(
                q, kp, vp, lengths, table, verify_impl="bogus")
