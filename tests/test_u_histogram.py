"""Precomputed-U histogram path (ops/u_histogram.py) and its train wiring.

The U pass replaces the reference engine's per-iteration native histogram
construction (``lightgbm/TrainUtils.scala:220-315``) with one MXU
contraction against a fit-resident one-hot; these tests pin (a) numerical
agreement with the bf16-input reference model, (b) exact counts, (c) the
packed per-feature-width layout, and (d) end-to-end training parity when
the path is forced on CPU (``histogram_method='u'``)."""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from mmlspark_tpu.lightgbm.binning import bin_dataset
from mmlspark_tpu.lightgbm.objectives import auc
from mmlspark_tpu.lightgbm.train import TrainOptions, train
from mmlspark_tpu.ops.histogram import build_histograms
from mmlspark_tpu.ops.u_histogram import (
    build_histograms_u,
    build_u,
    make_u_spec,
    stat_rows,
    u_bytes,
)


def _mixed_case(seed=0, n=3000, k=5):
    rng = np.random.default_rng(seed)
    widths = [32, 5, 17, 32, 2, 9, 31]
    f, b = len(widths), 32
    bins = np.stack(
        [rng.integers(0, w, size=n) for w in widths], axis=1
    ).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1, size=n).astype(np.float32)
    c = (rng.uniform(size=n) > 0.2).astype(np.float32)
    node = rng.integers(-1, k + 2, size=n).astype(np.int32)  # incl. OOR keys
    return widths, f, b, bins, g, h, c, node


class TestUHistogram:
    def test_matches_bf16_reference_and_counts_exact(self):
        widths, f, b, bins, g, h, c, node = _mixed_case()
        k = 5
        m = ((node >= 0) & (node < k)).astype(np.float32)
        bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
        # reference: exact sums of bf16-rounded inputs — the precision model
        # of the MXU pass (bf16 inputs, f32 accumulation)
        ref = np.asarray(build_histograms(
            jnp.asarray(bins), jnp.asarray(bf(g) * m), jnp.asarray(bf(h) * m),
            jnp.asarray(c * m), jnp.asarray(np.clip(node, 0, k - 1)), k, b,
            method="segment",
        ))
        spec = make_u_spec(b, f, per_feature=widths)
        assert spec.k == sum(widths)  # packed, not f*b
        u = build_u(jnp.asarray(bins), spec)
        assert u.shape[0] == spec.k_pad
        for stats in (None, stat_rows(jnp.asarray(g), jnp.asarray(h), jnp.asarray(c))):
            out = np.asarray(build_histograms_u(
                u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
                jnp.asarray(node), k, spec, stats=stats,
            ))
            np.testing.assert_array_equal(out[..., 2], ref[..., 2])  # counts
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)

    def test_out_of_range_nodes_are_the_in_leaf_mask(self):
        widths, f, b, bins, g, h, c, node = _mixed_case(seed=3)
        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins), spec)
        k = 4
        out = np.asarray(build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec,
        ))
        in_range = (node >= 0) & (node < k)
        # total count over all cells of feature 0 == rows with in-range keys
        assert out[:, 0, :, 2].sum() == (c * in_range).sum()

    def test_panel_width_guard(self):
        widths, f, b, bins, g, h, c, node = _mixed_case()
        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins), spec)
        with pytest.raises(ValueError, match="lane group"):
            build_histograms_u(
                u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
                jnp.asarray(node), 64, spec,
            )

    def test_u_bytes_budget(self):
        spec = make_u_spec(256, 28)
        assert u_bytes(400_000, spec) == 400_384 * spec.k_pad  # 512-aligned rows


class TestUTrainParity:
    def test_forced_u_path_matches_default(self):
        rng = np.random.default_rng(0)
        n = 3000
        X = rng.normal(size=(n, 8))
        y = ((X[:, 0] * 1.5 + X[:, 1] * X[:, 2]) > 0).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=63)
        base = dict(objective="binary", num_iterations=6, num_leaves=15, max_bin=63)
        r0 = train(bins, y, TrainOptions(**base), mapper=mp)
        ru = train(bins, y, TrainOptions(**base, histogram_method="u"), mapper=mp)
        a0 = auc(y, r0.booster.raw_margin(X)[:, 0], np.ones(n))
        au = auc(y, ru.booster.raw_margin(X)[:, 0], np.ones(n))
        # CPU default path is exact f32; the U path is the bf16 MXU model —
        # structurally near-identical trees, AUC within noise
        assert abs(a0 - au) < 0.005, (a0, au)

    @pytest.mark.parametrize("variant", ["depthwise", "goss", "bagging", "multiclass"])
    def test_u_path_boosting_variants(self, variant):
        rng = np.random.default_rng(1)
        n = 2000
        X = rng.normal(size=(n, 6))
        if variant == "multiclass":
            y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float64)
            extra = dict(objective="multiclass", num_class=3)
        else:
            y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
            extra = dict(objective="binary")
        if variant == "depthwise":
            extra.update(growth="depthwise", max_depth=4)
        elif variant == "goss":
            extra.update(boosting_type="goss")
        elif variant == "bagging":
            extra.update(bagging_fraction=0.7, bagging_freq=1)
        bins, mp = bin_dataset(X, max_bin=31)
        r = train(
            bins, y,
            TrainOptions(num_iterations=4, num_leaves=7, max_bin=31,
                         histogram_method="u", **extra),
            mapper=mp,
        )
        margins = r.booster.raw_margin(X)
        if variant == "multiclass":
            acc = (margins.argmax(1) == y).mean()
            assert acc > 0.7, acc
        else:
            a = auc(y, margins[:, 0], np.ones(n))
            assert a > 0.85, a

    def test_device_resident_bins_accepted(self):
        from mmlspark_tpu.lightgbm.binning import bin_dataset_to_device

        rng = np.random.default_rng(2)
        n = 1500
        X = rng.normal(size=(n, 5))
        y = (X[:, 0] > 0).astype(np.float64)
        bins_np, mp = bin_dataset(X, max_bin=31)
        bins_dev, mp2 = bin_dataset_to_device(X, max_bin=31)
        np.testing.assert_array_equal(np.asarray(bins_dev), bins_np)
        np.testing.assert_array_equal(mp2.edges, mp.edges)
        opts = TrainOptions(objective="binary", num_iterations=3,
                            num_leaves=7, max_bin=31)
        r_np = train(bins_np, y, opts, mapper=mp)
        r_dev = train(bins_dev, y, opts, mapper=mp2)
        np.testing.assert_allclose(
            r_dev.booster.leaf_values, r_np.booster.leaf_values, rtol=1e-6
        )

    def test_forced_u_with_voting_parallel_degrades_gracefully(self):
        rng = np.random.default_rng(3)
        n = 1200
        X = rng.normal(size=(n, 5))
        y = (X[:, 0] > 0).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=31)
        r = train(
            bins, y,
            TrainOptions(objective="binary", num_iterations=3, num_leaves=7,
                         max_bin=31, histogram_method="u",
                         tree_learner="voting_parallel", top_k=3),
            mapper=mp,
        )
        a = auc(y, r.booster.raw_margin(X)[:, 0], np.ones(n))
        assert a > 0.85, a


class TestQuantizedGrad:
    """LightGBM's use_quantized_grad analogue: 8-bit stochastically-rounded
    stat rows, s8 x s8 integer MXU pass, per-stat dequant scales."""

    def test_stat_rows_quant_counts_exact_and_sums_unbiased(self):
        import jax

        rng = np.random.default_rng(7)
        n = 20000
        g = rng.normal(size=n).astype(np.float32)
        h = rng.uniform(0.05, 1.0, size=n).astype(np.float32)
        c = (rng.uniform(size=n) > 0.3).astype(np.float32)
        from mmlspark_tpu.ops.u_histogram import stat_rows_quant

        stats, scales = stat_rows_quant(
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jax.random.PRNGKey(0),
        )
        stats = np.asarray(stats)
        scales = np.asarray(scales)
        assert stats.dtype == np.int8
        # counts are bit-exact 0/1, scale exactly 1
        np.testing.assert_array_equal(stats[2], c.astype(np.int8))
        assert scales[2] == 1.0
        # per-element quantization stays within one grid step of the input
        for row, x, s in ((0, g, scales[0]), (1, h, scales[1])):
            deq = stats[row].astype(np.float32) * s
            np.testing.assert_allclose(deq, x, atol=float(s) + 1e-7)
            # stochastic rounding is unbiased => SUM of dequantized values
            # concentrates: n * grid * O(1/sqrt(n)) tolerance
            assert abs(deq.sum() - x.sum()) < float(s) * 6 * np.sqrt(n)

    def test_quant_histogram_counts_exact_gh_within_grid(self):
        import jax

        widths, f, b, bins, g, h, c, node = _mixed_case(seed=3)
        k = 5
        from mmlspark_tpu.ops.u_histogram import stat_rows_quant

        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins), spec)
        exact = np.asarray(build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec,
        ))
        qstats = stat_rows_quant(
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jax.random.PRNGKey(1),
        )
        quant = np.asarray(build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec, stats=qstats,
        ))
        # counts ride the exact int path: bit-identical
        np.testing.assert_array_equal(quant[..., 2], exact[..., 2])
        # g/h bin sums: each of the <=n member rows contributes at most one
        # grid step of quantization error
        scales = np.asarray(qstats[1])
        n_bin = exact[..., 2]
        for s_idx in (0, 1):
            bound = scales[s_idx] * (n_bin + 1) + 1e-4
            assert (np.abs(quant[..., s_idx] - exact[..., s_idx]) <= bound).all()

    def test_end_to_end_quantized_fit_quality_and_determinism(self):
        rng = np.random.default_rng(11)
        n = 4000
        X = rng.normal(size=(n, 8))
        y = ((X[:, 0] + X[:, 1] * X[:, 2]) > 0).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=63)
        base = TrainOptions(objective="binary", num_iterations=25,
                            num_leaves=15, max_bin=63, histogram_method="u")
        import dataclasses

        r_exact = train(bins, y, base, mapper=mp)
        qopts = dataclasses.replace(base, use_quantized_grad=True)
        r_q = train(bins, y, qopts, mapper=mp)
        a_exact = auc(y, r_exact.booster.raw_margin(X)[:, 0], np.ones(n))
        a_q = auc(y, r_q.booster.raw_margin(X)[:, 0], np.ones(n))
        assert a_q > a_exact - 0.01, (a_q, a_exact)
        # seeded stochastic rounding: same options => identical model
        r_q2 = train(bins, y, qopts, mapper=mp)
        np.testing.assert_array_equal(
            r_q.booster.leaf_values, r_q2.booster.leaf_values
        )

    def test_param_flows_from_stage(self):
        from mmlspark_tpu.lightgbm.classifier import LightGBMClassifier

        stage = LightGBMClassifier(useQuantizedGrad=True)
        assert stage._make_options(num_class=1).use_quantized_grad is True
        assert (
            LightGBMClassifier()._make_options(num_class=1).use_quantized_grad
            is False
        )

    def test_multiclass_quantized(self):
        rng = np.random.default_rng(13)
        n = 3000
        X = rng.normal(size=(n, 6))
        y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
        bins, mp = bin_dataset(X, max_bin=31)
        opts = TrainOptions(objective="multiclass", num_class=3,
                            num_iterations=10, num_leaves=7, max_bin=31,
                            histogram_method="u", use_quantized_grad=True)
        r = train(bins, y.astype(np.float64), opts, mapper=mp)
        pred = r.booster.raw_margin(X).argmax(1)
        assert (pred == y).mean() > 0.8

    def test_quant_falls_back_with_warning_when_u_inactive(self, caplog):
        import logging

        rng = np.random.default_rng(17)
        n = 1500
        X = rng.normal(size=(n, 5))
        y = (X[:, 0] > 0).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=63)
        opts = TrainOptions(objective="binary", num_iterations=3,
                            num_leaves=7, max_bin=63,
                            use_quantized_grad=True,
                            tree_learner="voting_parallel", top_k=3)
        with caplog.at_level(logging.WARNING, logger="mmlspark_tpu.lightgbm"):
            r = train(bins, y, opts, mapper=mp)
        assert any("use_quantized_grad" in m for m in caplog.messages)
        assert r.booster.num_trees >= 1


    def test_quant_through_binary_classifier_stage(self):
        # regression: binary classifiers carry num_class=2 with ONE margin
        # column; the stochastic-rounding keys must follow grad.shape[1]
        from mmlspark_tpu.data.table import Table
        from mmlspark_tpu.lightgbm.classifier import LightGBMClassifier

        rng = np.random.default_rng(23)
        n = 1200
        X = rng.normal(size=(n, 6))
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
        tbl = Table({"features": X, "label": y})
        m = LightGBMClassifier(
            numIterations=8, useQuantizedGrad=True,
            featuresCol="features", labelCol="label",
        ).fit(tbl)
        p = np.asarray(m.transform(tbl)["probability"])[:, 1]
        assert auc(y, p, np.ones(n)) > 0.9


class TestChunkedU:
    """Row-chunked U pass: past the one-hot residency cliff the histogram
    pass streams row chunks through the same MXU contraction instead of
    falling back to the compare-built path (the old all-or-nothing budget
    cliff). Selection is pure host logic, so the >1M-row regression guard
    runs devicelessly in CI."""

    def test_over_budget_1m_shape_selects_chunked_mxu_path(self):
        # CI guard: the headline >1M-row shape (28 features x 256 bins)
        # must stream chunks on the MXU path, never fall off it
        from mmlspark_tpu.ops.u_histogram import chunked_u_spec, num_u_chunks

        spec = make_u_spec(256, 28)
        budget = 8 << 30  # the MMLSPARK_TPU_U_BUDGET default
        rows = 1_500_000
        assert u_bytes(rows, spec) > budget  # resident U would blow HBM
        c = chunked_u_spec(rows, spec, budget)
        assert c.chunk_rows > 0, "over-budget shape must chunk, not fall back"
        assert c.chunk_rows % 512 == 0  # row-alignment block
        assert c.widths == spec.widths and c.k_pad == spec.k_pad
        # double-buffered scan: current + next chunk one-hots fit the budget
        assert 2 * c.chunk_rows * c.k_pad <= budget
        assert num_u_chunks(rows, c) * c.chunk_rows >= rows
        # under-budget shapes keep the resident layout
        assert u_bytes(400_000, spec) <= budget

    def test_tiny_budget_floors_at_one_aligned_chunk(self):
        from mmlspark_tpu.ops.u_histogram import chunked_u_spec, num_u_chunks

        spec = make_u_spec(32, 7, per_feature=[32, 5, 17, 32, 2, 9, 31])
        c = chunked_u_spec(3000, spec, budget=1)
        assert c.chunk_rows == 512  # floor: one alignment block
        assert num_u_chunks(3000, c) == 6

    @pytest.mark.parametrize("quant", [False, True])
    def test_chunked_matches_resident(self, quant):
        import jax

        from mmlspark_tpu.ops.u_histogram import (
            build_histograms_u_chunked,
            chunked_u_spec,
            prepare_chunked_bins,
            stat_rows_quant,
        )

        widths, f, b, bins, g, h, c, node = _mixed_case()
        k = 5
        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins), spec)
        if quant:
            stats = stat_rows_quant(
                jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
                jax.random.PRNGKey(5),
            )
        else:
            stats = None
        ref = np.asarray(build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec, stats=stats,
        ))
        cspec = chunked_u_spec(len(bins), spec, budget=1)  # 512-row chunks
        chunks = prepare_chunked_bins(jnp.asarray(bins), cspec)
        assert chunks.shape == (6, f, 512)
        out = np.asarray(build_histograms_u_chunked(
            chunks, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, cspec, stats=stats,
        ))
        np.testing.assert_array_equal(out[..., 2], ref[..., 2])  # counts
        if quant:
            # integer accumulation: chunked partial sums are bit-exact
            np.testing.assert_array_equal(out, ref)
        else:
            # f32 accumulation: association differs only at rounding level
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)

    def test_train_over_budget_streams_chunks_and_publishes_event(
        self, monkeypatch
    ):
        from mmlspark_tpu.observability import HistogramChunked, get_bus

        rng = np.random.default_rng(29)
        n = 3000
        X = rng.normal(size=(n, 8))
        y = ((X[:, 0] * 1.5 + X[:, 1] * X[:, 2]) > 0).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=63)
        opts = TrainOptions(objective="binary", num_iterations=6,
                            num_leaves=15, max_bin=63, histogram_method="u")
        r_resident = train(bins, y, opts, mapper=mp)

        seen = []
        bus = get_bus()
        bus.add_listener(seen.append)
        try:
            monkeypatch.setenv("MMLSPARK_TPU_U_BUDGET", "200000")
            r_chunked = train(bins, y, opts, mapper=mp)
        finally:
            bus.remove_listener(seen.append)
        ev = [e for e in seen if isinstance(e, HistogramChunked)]
        assert ev, "over-budget fit must publish HistogramChunked"
        assert ev[0].num_chunks > 1 and ev[0].chunk_rows % 512 == 0
        assert ev[0].budget_bytes == 200_000
        # same trees as the resident pass (f32 association tolerance)
        np.testing.assert_allclose(
            r_chunked.booster.leaf_values, r_resident.booster.leaf_values,
            rtol=1e-4, atol=1e-5,
        )
        a = auc(y, r_chunked.booster.raw_margin(X)[:, 0], np.ones(n))
        ar = auc(y, r_resident.booster.raw_margin(X)[:, 0], np.ones(n))
        assert abs(a - ar) < 0.002, (a, ar)


class TestAccumulatorDtype:
    """Deterministic overflow promotion for narrow histogram accumulators:
    f32 on the exact path; on the quant path the narrowest signed int whose
    range provably holds 127 * n_rows (each quantized stat is in [-127,
    127], so a bin's partial sum is bounded by 127 * members)."""

    def test_promotion_ladder(self):
        from mmlspark_tpu.ops.u_histogram import histogram_acc_dtype

        assert histogram_acc_dtype(10**9, False) == jnp.float32
        assert histogram_acc_dtype(258, True) == jnp.int16  # 127*258 = 32766
        assert histogram_acc_dtype(259, True) == jnp.int32
        assert histogram_acc_dtype(1 << 24, True) == jnp.int32

    def test_int16_tier_is_exact(self):
        import jax

        from mmlspark_tpu.ops.u_histogram import (
            histogram_acc_dtype,
            stat_rows_quant,
        )

        widths, f, b, bins, g, h, c, node = _mixed_case(seed=7, n=200)
        k = 3
        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins[:200]), spec)
        stats = stat_rows_quant(
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jax.random.PRNGKey(9),
        )
        assert histogram_acc_dtype(200, True) == jnp.int16
        packed16 = build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec, stats=stats, dequant=False,
        )
        assert packed16.dtype == jnp.int16
        full = build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec, stats=stats,
        )
        from mmlspark_tpu.ops.u_histogram import dequant_hist

        np.testing.assert_array_equal(
            np.asarray(dequant_hist(packed16, stats[1])), np.asarray(full)
        )


class TestSiblingSubtraction:
    """Sibling histogram subtraction (native LightGBM's always-on trick):
    build only the smaller child, derive the sibling as parent - smaller in
    PACKED space. On the quant path both orders are exact integer sums, so
    subtraction-on model text is byte-identical to subtraction-off; on the
    f32 path they differ only at rounding level."""

    def _fit_case(self, seed=11, n=1400):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 8))
        y = ((X[:, 0] + X[:, 1] * X[:, 2]) > 0).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=63)
        return X, y, bins, mp

    def _ab(self, bins, y, opts, mp):
        import dataclasses

        r_on = train(bins, y, opts, mapper=mp)
        r_off = train(
            bins, y,
            dataclasses.replace(opts, histogram_subtraction=False),
            mapper=mp,
        )
        return r_on, r_off

    # Byte-identity is a QUANT-U-PATH property: integer subtraction in
    # spec space is exact, so the trained model text is identical with
    # subtraction on or off. Under MMLSPARK_TPU_NO_U=1 the quant request
    # falls back to f32 compare-built histograms (documented warning),
    # where parent - smaller rounds differently and a tipped split is
    # legitimate — the quant tests are skipped there and the f32 contract
    # (dAUC <= 2e-5) is pinned by test_f32_u_path_parity /
    # test_compare_built_path_parity instead.
    _quant_path = pytest.mark.skipif(
        os.environ.get("MMLSPARK_TPU_NO_U") == "1",
        reason="quant U path inactive under MMLSPARK_TPU_NO_U=1; f32 "
               "subtraction parity covered by the dAUC tests",
    )

    def _assert_model_parity(self, r_on, r_off):
        assert r_on.booster.model_to_string() == r_off.booster.model_to_string()

    def test_packed_space_subtraction_is_integer_exact(self):
        import jax

        from mmlspark_tpu.ops.u_histogram import stat_rows_quant

        widths, f, b, bins, g, h, c, _ = _mixed_case(seed=19)
        n = len(bins)
        # rows split 2 ways under one parent: node 0 = left, 1 = right
        child = (np.arange(n) % 3 == 0).astype(np.int32)
        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins), spec)
        stats = stat_rows_quant(
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jax.random.PRNGKey(7),
        )
        parent = build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.zeros(n, jnp.int32), 1, spec, stats=stats, dequant=False,
        )
        both = build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(child), 2, spec, stats=stats, dequant=False,
        )
        # parent - directly-built child == directly-built sibling, bit-exact
        np.testing.assert_array_equal(
            np.asarray(parent[0] - both[1]), np.asarray(both[0])
        )
        np.testing.assert_array_equal(
            np.asarray(parent[0] - both[0]), np.asarray(both[1])
        )

    @_quant_path
    def test_quant_model_text_byte_identical(self):
        X, y, bins, mp = self._fit_case()
        opts = TrainOptions(
            objective="binary", num_iterations=6, num_leaves=15,
            max_bin=63, histogram_method="u", use_quantized_grad=True,
        )
        r_on, r_off = self._ab(bins, y, opts, mp)
        self._assert_model_parity(r_on, r_off)

    def test_f32_u_path_parity(self):
        X, y, bins, mp = self._fit_case(seed=13)
        opts = TrainOptions(
            objective="binary", num_iterations=6, num_leaves=15,
            max_bin=63, histogram_method="u",
        )
        r_on, r_off = self._ab(bins, y, opts, mp)
        n = len(y)
        a_on = auc(y, r_on.booster.raw_margin(X)[:, 0], np.ones(n))
        a_off = auc(y, r_off.booster.raw_margin(X)[:, 0], np.ones(n))
        assert abs(a_on - a_off) <= 2e-5, (a_on, a_off)

    @_quant_path
    def test_bundled_quant_byte_identical(self):
        # EFB: subtraction must happen in PACKED space (before expansion);
        # _expand_bundled is linear, so the orders agree — and on the quant
        # path exactly.
        rng = np.random.default_rng(31)
        n = 1400
        blocks, card = 6, 5
        X = np.zeros((n, blocks * card))
        for bl in range(blocks):
            hot = rng.integers(0, card, n)
            X[np.arange(n), bl * card + hot] = rng.uniform(0.5, 2.0, n)
        X = np.hstack([X, rng.normal(size=(n, 3))])
        y = (X[:, 0] + 2 * X[:, card + 2] + X[:, -1] > 1.2).astype(np.float64)
        bins, mp = bin_dataset(X, max_bin=63, feature_bundling=True)
        assert mp.bundles is not None
        opts = TrainOptions(
            objective="binary", num_iterations=8, num_leaves=15,
            max_bin=63, histogram_method="u", use_quantized_grad=True,
        )
        r_on, r_off = self._ab(bins, y, opts, mp)
        self._assert_model_parity(r_on, r_off)

    @_quant_path
    def test_chunked_quant_byte_identical(self, monkeypatch):
        X, y, bins, mp = self._fit_case(seed=17)
        opts = TrainOptions(
            objective="binary", num_iterations=8, num_leaves=15,
            max_bin=63, histogram_method="u", use_quantized_grad=True,
        )
        monkeypatch.setenv("MMLSPARK_TPU_U_BUDGET", "120000")
        r_on, r_off = self._ab(bins, y, opts, mp)
        self._assert_model_parity(r_on, r_off)

    def test_compare_built_path_parity(self, monkeypatch):
        # MMLSPARK_TPU_NO_U=1: subtraction on the compare-built (non-U)
        # builders — the packed()/expand() split must hold there too
        monkeypatch.setenv("MMLSPARK_TPU_NO_U", "1")
        X, y, bins, mp = self._fit_case(seed=23)
        opts = TrainOptions(
            objective="binary", num_iterations=8, num_leaves=15, max_bin=63,
        )
        r_on, r_off = self._ab(bins, y, opts, mp)
        n = len(y)
        a_on = auc(y, r_on.booster.raw_margin(X)[:, 0], np.ones(n))
        a_off = auc(y, r_off.booster.raw_margin(X)[:, 0], np.ones(n))
        assert abs(a_on - a_off) <= 2e-5, (a_on, a_off)

    @_quant_path
    def test_multiclass_quant_byte_identical(self):
        X, y, bins, mp = self._fit_case(seed=37)
        y3 = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
        opts = TrainOptions(
            objective="multiclass", num_class=3, num_iterations=4,
            num_leaves=7, max_bin=63, histogram_method="u",
            use_quantized_grad=True,
        )
        r_on, r_off = self._ab(bins, y3.astype(np.float64), opts, mp)
        self._assert_model_parity(r_on, r_off)

    def test_event_published(self):
        from mmlspark_tpu.observability import HistogramSubtracted, get_bus

        X, y, bins, mp = self._fit_case(seed=41)
        opts = TrainOptions(
            objective="binary", num_iterations=4, num_leaves=15,
            max_bin=63, histogram_method="u", use_quantized_grad=True,
        )
        seen = []
        bus = get_bus()
        bus.add_listener(seen.append)
        try:
            train(bins, y, opts, mapper=mp)
        finally:
            bus.remove_listener(seen.append)
        ev = [e for e in seen if isinstance(e, HistogramSubtracted)]
        assert ev, "subtraction fit must publish HistogramSubtracted"
        # quant at n > 258 rows -> int32 cache; under NO_U the quant
        # request falls back to f32 and the event reports that honestly
        exp = "float32" if os.environ.get("MMLSPARK_TPU_NO_U") == "1" else "int32"
        assert ev[0].acc_dtype == exp
        assert ev[0].children_per_split == 1
        assert ev[0].cache_bytes > 0 and ev[0].bytes_saved_per_tree > 0

    @pytest.mark.slow
    def test_procfit_two_process_parity(self):
        # procfit rejects the quant path, so the gang runs f32 histograms:
        # byte-identity is NOT a property there (parent - smaller rounds
        # differently than a direct build); the contract is structural
        # parity (model_texts_close) between subtraction on/off AND between
        # the 2-process gang and the serial fit, with the gang allreducing
        # only the smaller child per split.
        import dataclasses

        from mmlspark_tpu.lightgbm.procfit import (
            fit_process_group,
            model_texts_close,
        )

        X, y, _, _ = self._fit_case(seed=43, n=800)
        opts = TrainOptions(
            objective="binary", num_iterations=6, num_leaves=7,
            max_bin=32, min_data_in_leaf=5, seed=2,
        )
        r_on = fit_process_group(
            X, y, opts, num_processes=2,
            group_options={"epoch_timeout_s": 180.0},
        )
        r_off = fit_process_group(
            X, y, dataclasses.replace(opts, histogram_subtraction=False),
            num_processes=2, group_options={"epoch_timeout_s": 180.0},
        )
        assert model_texts_close(r_on.model_text, r_off.model_text)
        bins, mp = bin_dataset(X, max_bin=32)
        serial = train(bins, y, opts, mapper=mp)
        assert model_texts_close(
            r_on.model_text, serial.booster.model_to_string()
        )
