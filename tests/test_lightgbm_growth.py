"""Leaf-wise growth, SHAP, and voting-parallel tests.

Reference anchors: leaf-wise is LightGBM's defining algorithm
(``numLeaves`` bounds leaves, ``lightgbm/LightGBMParams.scala:13-251``);
SHAP is ``LightGBMBooster.featuresShap`` (``LightGBMBooster.scala:240-275``);
voting-parallel is ``tree_learner=voting_parallel`` + ``topK``
(``LightGBMParams.scala:20-24``).
"""

import numpy as np
import pytest

from mmlspark_tpu.data.table import Table
from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.lightgbm.binning import bin_dataset
from mmlspark_tpu.lightgbm.booster import Booster
from mmlspark_tpu.lightgbm.objectives import auc as auc_metric
from mmlspark_tpu.lightgbm.train import TrainOptions, train


def _make_binary(n=3000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    logit = X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n)
    y = (logit > 0).astype(np.float64)
    return X, y


def _to_table(X, y):
    return Table({"features": X.astype(np.float64), "label": y})


def test_leafwise_honors_num_leaves():
    X, y = _make_binary()
    bins, mapper = bin_dataset(X, max_bin=63)
    opts = TrainOptions(
        objective="binary", num_iterations=3, num_leaves=8, max_bin=63,
        growth="leafwise", min_data_in_leaf=5,
    )
    r = train(bins, y, opts, mapper=mapper)
    b = r.booster
    # Every tree has at most num_leaves reachable leaves, and the tree can be
    # deeper than ceil(log2(num_leaves)) — the signature of best-first growth.
    for t in range(b.num_trees):
        n_leaves = int(b.is_leaf[t].sum())
        assert 1 <= n_leaves <= 8
    assert b.max_depth >= 3


def test_leafwise_beats_or_matches_depthwise_quality():
    X, y = _make_binary(seed=3)
    n_train = 2400
    bins, mapper = bin_dataset(X, max_bin=63)
    scores = {}
    for growth in ("leafwise", "depthwise"):
        opts = TrainOptions(
            objective="binary", num_iterations=30, num_leaves=15, max_bin=63,
            growth=growth,
        )
        r = train(bins[:n_train], y[:n_train], opts, mapper=mapper)
        m = r.booster.raw_margin(X[n_train:])[:, 0]
        scores[growth] = auc_metric(
            y[n_train:], m, np.ones(len(y) - n_train)
        )
    assert scores["leafwise"] > 0.9
    # Leaf-wise should be competitive with the balanced-tree fast path.
    assert scores["leafwise"] >= scores["depthwise"] - 0.02


def test_leafwise_max_depth_cap():
    X, y = _make_binary()
    bins, mapper = bin_dataset(X, max_bin=63)
    opts = TrainOptions(
        objective="binary", num_iterations=3, num_leaves=31, max_depth=3,
        max_bin=63, growth="leafwise",
    )
    r = train(bins, y, opts, mapper=mapper)
    assert r.booster.max_depth <= 3


def test_shap_sums_to_margin():
    X, y = _make_binary(n=800)
    bins, mapper = bin_dataset(X, max_bin=63)
    opts = TrainOptions(objective="binary", num_iterations=8, num_leaves=7, max_bin=63)
    r = train(bins, y, opts, mapper=mapper)
    phi = r.booster.features_shap(X[:100])  # (N, 1, F+1)
    margins = r.booster.raw_margin(X[:100])
    np.testing.assert_allclose(phi.sum(axis=-1), margins, rtol=1e-4, atol=1e-4)
    # The two informative features should dominate attribution mass.
    mass = np.abs(phi[:, 0, :-1]).mean(axis=0)
    assert mass[0] == mass.max()


def test_shap_multiclass_sums_to_margin():
    rng = np.random.default_rng(5)
    n = 900
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] > 0.4).astype(int) + (X[:, 1] > 0.2).astype(int)
    bins, mapper = bin_dataset(X, max_bin=31)
    opts = TrainOptions(
        objective="multiclass", num_class=3, num_iterations=5, num_leaves=7,
        max_bin=31,
    )
    r = train(bins, y.astype(np.float64), opts, mapper=mapper)
    phi = r.booster.features_shap(X[:40])  # (N, 3, F+1)
    np.testing.assert_allclose(
        phi.sum(axis=-1), r.booster.raw_margin(X[:40]), rtol=1e-4, atol=1e-4
    )


def test_features_shap_col_output():
    X, y = _make_binary(n=600)
    clf = LightGBMClassifier(
        numIterations=5, numLeaves=7, featuresShapCol="shap", minDataInLeaf=5
    )
    model = clf.fit(_to_table(X, y))
    out = model.transform(_to_table(X[:30], y[:30]))
    shap = out["shap"]
    assert shap.shape == (30, X.shape[1] + 1)  # binary: C=1 → F+1 contribs
    raw = out["rawPrediction"][:, 1]  # positive-class margin
    np.testing.assert_allclose(shap.sum(axis=1), raw, rtol=1e-4, atol=1e-4)


def test_shap_serde_roundtrip():
    X, y = _make_binary(n=500)
    bins, mapper = bin_dataset(X, max_bin=31)
    opts = TrainOptions(objective="binary", num_iterations=3, num_leaves=7, max_bin=31)
    b = train(bins, y, opts, mapper=mapper).booster
    b2 = Booster.from_string(b.model_to_string())
    np.testing.assert_allclose(
        b2.features_shap(X[:20]), b.features_shap(X[:20]), rtol=1e-6
    )


def test_voting_parallel_quality(mesh8):
    X, y = _make_binary(n=2048, f=16, seed=7)
    bins, mapper = bin_dataset(X, max_bin=63)
    base = dict(
        objective="binary", num_iterations=15, num_leaves=15, max_bin=63,
    )
    r_full = train(
        bins, y, TrainOptions(**base), mapper=mapper, mesh=mesh8
    )
    r_vote = train(
        bins, y,
        TrainOptions(**base, tree_learner="voting_parallel", top_k=6),
        mapper=mapper, mesh=mesh8,
    )
    w = np.ones(len(y))
    auc_full = auc_metric(y, r_full.booster.raw_margin(X)[:, 0], w)
    auc_vote = auc_metric(y, r_vote.booster.raw_margin(X)[:, 0], w)
    # Voting reduces comms F→topK; quality must stay close to the full
    # data_parallel reduction (PV-Tree guarantee).
    assert auc_vote > auc_full - 0.02, (auc_vote, auc_full)


def test_voting_parallel_estimator_param(mesh8):
    X, y = _make_binary(n=1024)
    clf = LightGBMClassifier(
        numIterations=5, numLeaves=7, parallelism="voting_parallel", topK=4
    )
    model = clf.fit(_to_table(X, y))
    out = model.transform(_to_table(X[:50], y[:50]))
    assert "prediction" in out.columns


def test_regressor_leafwise_quality():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(2000, 8))
    y = X[:, 0] * 2 + np.sin(X[:, 1] * 3) + 0.1 * rng.normal(size=2000)
    reg = LightGBMRegressor(numIterations=40, numLeaves=31)
    model = reg.fit(_to_table(X, y))
    pred = model.transform(_to_table(X, y))["prediction"]
    r2 = 1 - np.var(y - pred) / np.var(y)
    assert r2 > 0.9, r2


def test_voting_parallel_feature_fraction(mesh8):
    """featureFraction masks must steer the vote: masked-out features may
    not spend top-K slots, so growth continues on the allowed ones."""
    X, y = _make_binary(n=2048, f=16, seed=9)
    bins, mapper = bin_dataset(X, max_bin=63)
    r = train(
        bins, y,
        TrainOptions(
            objective="binary", num_iterations=10, num_leaves=15, max_bin=63,
            tree_learner="voting_parallel", top_k=4, feature_fraction=0.5, seed=3,
        ),
        mapper=mapper, mesh=mesh8,
    )
    w = np.ones(len(y))
    score = auc_metric(y, r.booster.raw_margin(X)[:, 0], w)
    assert score > 0.8, score
    # trees actually grew (premature-leaf regression guard)
    assert (~r.booster.is_leaf).sum() > 0


class TestBoostingTypes:
    """rf/dart/goss are real algorithms, not accepted-and-ignored strings
    (LightGBMParams.scala boostingType)."""

    def _data(self, n=800, f=8, seed=21):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, f))
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
        return X, y

    def test_goss_differs_from_gbdt_and_learns(self):
        X, y = self._data()
        bins, mapper = bin_dataset(X, max_bin=63)
        base = dict(objective="binary", num_iterations=15, num_leaves=15, max_bin=63)
        r_gbdt = train(bins, y, TrainOptions(**base), mapper=mapper)
        r_goss = train(
            bins, y, TrainOptions(**base, boosting_type="goss"), mapper=mapper
        )
        w = np.ones(len(y))
        auc_goss = auc_metric(y, r_goss.booster.raw_margin(X)[:, 0], w)
        assert auc_goss > 0.9, auc_goss
        # the sampled histogram must actually change the trees
        assert not np.array_equal(
            r_gbdt.booster.leaf_values, r_goss.booster.leaf_values
        )

    def test_goss_rejects_bagging(self):
        X, y = self._data(n=100)
        bins, mapper = bin_dataset(X, max_bin=31)
        with pytest.raises(ValueError, match="goss"):
            train(
                bins, y,
                TrainOptions(
                    objective="binary", num_iterations=2, boosting_type="goss",
                    bagging_fraction=0.5, bagging_freq=1,
                ),
                mapper=mapper,
            )

    def test_rf_mode_averages(self):
        X, y = self._data()
        bins, mapper = bin_dataset(X, max_bin=63)
        r = train(
            bins, y,
            TrainOptions(
                objective="binary", num_iterations=10, num_leaves=15, max_bin=63,
                boosting_type="rf", bagging_fraction=0.6, bagging_freq=1,
            ),
            mapper=mapper,
        )
        w = np.ones(len(y))
        score = auc_metric(y, r.booster.raw_margin(X)[:, 0], w)
        assert score > 0.9, score
        # averaged leaves: magnitudes an order below full-strength trees
        mags = np.abs(r.booster.leaf_values[r.booster.is_leaf])
        assert mags.max() < 2.0

    def test_rf_requires_bagging(self):
        X, y = self._data(n=100)
        bins, mapper = bin_dataset(X, max_bin=31)
        with pytest.raises(ValueError, match="rf"):
            train(
                bins, y,
                TrainOptions(objective="binary", num_iterations=2, boosting_type="rf"),
                mapper=mapper,
            )

    def test_dart_learns_and_scales_trees(self):
        X, y = self._data()
        bins, mapper = bin_dataset(X, max_bin=63)
        r = train(
            bins, y,
            TrainOptions(
                objective="binary", num_iterations=20, num_leaves=15, max_bin=63,
                boosting_type="dart", drop_rate=0.3, seed=5,
            ),
            mapper=mapper,
        )
        w = np.ones(len(y))
        score = auc_metric(y, r.booster.raw_margin(X)[:, 0], w)
        assert score > 0.9, score

    def test_dart_rejects_early_stopping(self):
        X, y = self._data(n=100)
        bins, mapper = bin_dataset(X, max_bin=31)
        with pytest.raises(ValueError, match="dart"):
            train(
                bins, y,
                TrainOptions(
                    objective="binary", num_iterations=2, boosting_type="dart",
                    early_stopping_round=2,
                ),
                mapper=mapper,
            )

    def test_estimator_boosting_type_param(self):
        X, y = self._data(n=300)
        t = _to_table(X, y)
        m = LightGBMClassifier(
            numIterations=5, numLeaves=7, boostingType="dart", dropRate=0.2,
            parallelism="serial",
        ).fit(t)
        out = m.transform(t)
        assert "prediction" in out.columns


class TestPathMatrixPredict:
    """Pin the path-matrix predict to the pointer-routing kernels: leaf
    assignments bit-identical, margins within fp32 summation order."""

    @pytest.mark.parametrize("growth", ["leafwise", "depthwise"])
    @pytest.mark.parametrize("classes,obj", [(1, "binary"), (3, "multiclass")])
    def test_matches_routing_kernels(self, growth, classes, obj):
        import jax.numpy as jnp

        from mmlspark_tpu.lightgbm.booster import (
            _predict_leaf_jit,
            _predict_margin_jit,
        )

        rng = np.random.default_rng(13)
        X = rng.normal(size=(2000, 8))
        X[::9, 2] = np.nan
        y = (
            (np.abs(np.nan_to_num(X[:, 0])).astype(int) % 3).astype(np.float64)
            if classes > 1
            else (np.nan_to_num(X[:, 0]) + X[:, 1] > 0).astype(np.float64)
        )
        bins, mapper = bin_dataset(X, max_bin=63)
        r = train(
            bins, y,
            TrainOptions(
                objective=obj, num_class=classes, num_iterations=6,
                num_leaves=7, max_bin=63, growth=growth,
            ),
            mapper=mapper,
        )
        b = r.booster
        t = b._used_trees(None)
        old_m = np.asarray(_predict_margin_jit(
            jnp.asarray(X, jnp.float32), jnp.asarray(b.split_feature[:t]),
            jnp.asarray(b.split_threshold[:t]), jnp.asarray(b.left_child[:t]),
            jnp.asarray(b.right_child[:t]), jnp.asarray(b.is_leaf[:t]),
            jnp.asarray(b.leaf_values[:t]), jnp.asarray(b.init_score),
            b.num_classes, b.max_depth,
        ))
        np.testing.assert_allclose(b.raw_margin(X), old_m, rtol=1e-5, atol=1e-6)
        old_l = np.asarray(_predict_leaf_jit(
            jnp.asarray(X, jnp.float32), jnp.asarray(b.split_feature[:t]),
            jnp.asarray(b.split_threshold[:t]), jnp.asarray(b.left_child[:t]),
            jnp.asarray(b.right_child[:t]), jnp.asarray(b.is_leaf[:t]),
            b.max_depth,
        ))
        np.testing.assert_array_equal(b.predict_leaf(X), old_l)


class TestLeafBatchRatio:
    def test_ratio_one_reproduces_exact_best_first(self):
        """leaf_batch_ratio=1.0 only batches exact gain ties, so (absent
        ties) every pass splits one leaf and the tree equals the
        leaf_batch=1 sequential build bit for bit."""
        X, y = _make_binary(n=700)
        bins, mapper = bin_dataset(X, max_bin=31)
        base = dict(objective="binary", num_iterations=4, num_leaves=15, max_bin=31)
        seq = train(bins, y, TrainOptions(**base, leaf_batch=1), mapper=mapper)
        gated = train(
            bins, y, TrainOptions(**base, leaf_batch=8, leaf_batch_ratio=1.0),
            mapper=mapper,
        )
        for field in ("split_feature", "split_bin", "left_child", "right_child",
                      "is_leaf"):
            np.testing.assert_array_equal(
                getattr(gated.booster, field), getattr(seq.booster, field),
                err_msg=field,
            )
        np.testing.assert_allclose(
            gated.booster.leaf_values, seq.booster.leaf_values, rtol=1e-6
        )

    def test_ratio_gate_still_fills_leaf_budget(self):
        X, y = _make_binary(n=700)
        bins, mapper = bin_dataset(X, max_bin=31)
        r = train(
            bins, y,
            TrainOptions(objective="binary", num_iterations=2, num_leaves=15,
                         max_bin=31, leaf_batch=8, leaf_batch_ratio=0.3),
            mapper=mapper,
        )
        # every tree still reaches the leaf budget when data supports it
        assert (np.asarray(r.booster.is_leaf).sum(axis=1) == 15).all()

    def test_negative_min_gain_terminates(self):
        """A negative min_gain_to_split (legal on a directly-constructed
        TrainOptions) combined with leaf_batch_ratio must still make
        progress: the pass best always qualifies for its own ratio gate,
        so the while_loop cannot spin on an uncommittable frontier."""
        X, y = _make_binary(n=400)
        bins, mapper = bin_dataset(X, max_bin=15)
        r = train(
            bins, y,
            TrainOptions(objective="binary", num_iterations=2, num_leaves=7,
                         max_bin=15, min_gain_to_split=-5.0,
                         leaf_batch=4, leaf_batch_ratio=0.5),
            mapper=mapper,
        )
        assert r.booster.num_trees == 2


class TestScanSegmentation:
    """The one-dispatch scanned fit splits into equal segments when a single
    device program would run for minutes (MMLSPARK_TPU_SCAN_ROW_ITERS); margins thread between dispatches, so
    results must be BIT-identical to the unsegmented scan — including GOSS,
    whose per-iteration rng folds on the GLOBAL iteration id."""

    @pytest.mark.parametrize("boosting", ["gbdt", "goss"])
    def test_segmented_scan_is_bit_identical(self, boosting, monkeypatch):
        X, y = _make_binary(n=3000, f=8, seed=17)
        bins, mapper = bin_dataset(X, max_bin=31)
        opts = TrainOptions(
            objective="binary", num_iterations=9, num_leaves=15, max_bin=31,
            boosting_type=boosting,
        )
        single = train(bins, y, opts, mapper=mapper)
        monkeypatch.setenv("MMLSPARK_TPU_SCAN_ROW_ITERS", "9000")  # 3 segments
        segmented = train(bins, y, opts, mapper=mapper)
        np.testing.assert_array_equal(
            np.asarray(segmented.booster.leaf_values),
            np.asarray(single.booster.leaf_values),
        )
        np.testing.assert_array_equal(
            np.asarray(segmented.booster.split_feature),
            np.asarray(single.booster.split_feature),
        )


class TestCategoricalURouting:
    """Row routing through categorical splits has two formulations: the
    matmul against the fit-resident one-hot U (TPU hot path) and the
    per-leaf mask gather (no-U fallback, what the mesh/CPU paths use).
    This pins the membership MATH of the matmul formulation — exactly the
    expression the leafwise builder traces — against the direct gather.
    (Comparing whole fits would conflate routing with the histogram
    pass's different fp summation order.)"""

    def test_membership_matmul_matches_gather(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.u_histogram import (
            build_u, cat_row_maps, make_u_spec, membership_matmul,
        )

        rng = np.random.default_rng(23)
        n, k, b = 1000, 8, 16
        widths = [5, 16, 9, 3]  # ragged per-feature bin counts
        f = len(widths)
        bins_np = np.column_stack(
            [rng.integers(0, w, size=n) for w in widths]
        ).astype(np.int32)
        spec = make_u_spec(b, f, widths)
        u = build_u(jnp.asarray(bins_np), spec)

        sf = jnp.asarray(rng.integers(0, f, size=k), jnp.int32)
        scm = jnp.asarray(rng.random((k, b)) < 0.4)

        # the SAME helpers the leafwise builder traces, with a STRICT
        # subset of categorical features (the production shape): leaves
        # splitting on a non-categorical feature must produce all-False
        # rows (the caller masks them via the node's is-categorical flag)
        cat_subset = [0, 2]
        rows_np, fr_np, lr_np = cat_row_maps(spec, cat_subset)
        in_set = np.asarray(
            membership_matmul(
                u[jnp.asarray(rows_np)],
                jnp.asarray(fr_np), jnp.asarray(lr_np), sf, scm, n,
            )
        )

        # the gather reference, row by row
        scm_np = np.asarray(scm)
        sf_np = np.asarray(sf)
        expected = np.stack(
            [
                scm_np[jj][bins_np[:, sf_np[jj]]]
                if sf_np[jj] in cat_subset
                else np.zeros(n, bool)
                for jj in range(k)
            ]
        )
        np.testing.assert_array_equal(in_set, expected)
