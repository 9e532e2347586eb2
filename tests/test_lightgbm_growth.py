"""Leaf-wise growth, SHAP, and voting-parallel tests.

Reference anchors: leaf-wise is LightGBM's defining algorithm
(``numLeaves`` bounds leaves, ``lightgbm/LightGBMParams.scala:13-251``);
SHAP is ``LightGBMBooster.featuresShap`` (``LightGBMBooster.scala:240-275``);
voting-parallel is ``tree_learner=voting_parallel`` + ``topK``
(``LightGBMParams.scala:20-24``).
"""

import os

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


# -- one tree straight from the leaf-wise grower ------------------------------
#
# ``_build_tree_leafwise`` on fixed data: the passes it built and skipped, the
# tree's structure against what the commit before the skip grew
# (``fixtures/leafwise_parent_trees.npz``, recorded there by ``_record_parent``),
# and every leaf's value and cover against sums over the rows routed to it.

PARENT_TREES = os.path.join(os.path.dirname(__file__), "fixtures", "leafwise_parent_trees.npz")
STRUCTURE = ("feat", "bin", "thr", "left", "right", "is_leaf", "gain", "row_leaf")


def _tree_data(kind, n=3000, seed=4):
    """(X, categorical feature ids, bundling?) and per-row gradient pairs of a
    binary objective part-way through a fit (so gradients are not +-0.5)."""
    rng = np.random.default_rng(seed)
    cats = None
    if kind == "bundled":  # six blocks of five exclusive indicators, three dense columns
        X = np.zeros((n, 30), np.float64)
        for block in range(6):
            X[np.arange(n), block * 5 + rng.integers(0, 5, n)] = rng.uniform(0.5, 2.0, n)
        X = np.hstack([X, rng.normal(size=(n, 3))])
        y = (X[:, 0] + 2 * X[:, 7] + X[:, -1] > 1.2).astype(np.float64)
    else:
        X, y = _make_binary(n=n, seed=seed)
        if kind == "categorical":
            X[:, 5] = rng.integers(0, 12, n)  # sorted-prefix search
            X[:, 6] = rng.integers(0, 3, n)  # one-vs-rest search
            y = ((X[:, 5] % 3 == 0) ^ (X[:, 6] == 1) ^ (X[:, 0] > 0.3)).astype(np.float64)
            cats = [5, 6]
    p = 1.0 / (1.0 + np.exp(-0.4 * rng.normal(size=n)))
    return X, cats, (p - y).astype(np.float32), (p * (1.0 - p)).astype(np.float32)


def _tree_program(kind="numeric", u_path=False, **options):
    """``_build_tree_leafwise`` as ``train()`` calls it, unjitted, with its
    arguments: (build, args, u, grad, hess, the options the grower sees)."""
    import dataclasses
    from functools import partial

    import jax.numpy as jnp

    from mmlspark_tpu.lightgbm.train import _build_tree_leafwise, _hist_fn

    X, cats, grad, hess = _tree_data(kind)
    max_bin = options.pop("max_bin", 63)
    bins, mapper = bin_dataset(
        X, max_bin=max_bin, categorical_features=cats, feature_bundling=kind == "bundled")
    bundle = getattr(mapper, "bundles", None)
    assert (bundle is not None) == (kind == "bundled")
    opts = TrainOptions(objective="binary", max_bin=max_bin, min_data_in_leaf=5, **options)
    if mapper.cat_values:
        opts = dataclasses.replace(
            opts, categorical_slots=tuple(sorted(mapper.cat_values)),
            onehot_slots=tuple(f for f in sorted(mapper.cat_values)
                               if len(mapper.cat_values[f]) <= opts.max_cat_to_onehot))
    u = u_spec = None
    if u_path:
        from mmlspark_tpu.ops.u_histogram import build_u, make_u_spec

        u_spec = (make_u_spec(bundle.num_bins, bins.shape[1], [int(w) for w in bundle.widths])
                  if bundle is not None else
                  make_u_spec(max_bin + 1, bins.shape[1], [int(b) for b in mapper.num_bins]))
        u = build_u(jnp.asarray(bins), u_spec)
    features = bundle.num_features if bundle is not None else bins.shape[1]
    edges = np.where(np.isfinite(mapper.edges), mapper.edges, np.finfo(np.float32).max)
    build = partial(
        _build_tree_leafwise, num_bins=max_bin + 1, opts=opts,
        histf=_hist_fn(opts, None, u_spec, bundle=bundle), u_spec=u_spec, bundle=bundle)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.ones(len(grad), jnp.float32),
            jnp.asarray(edges.astype(np.float32)), jnp.ones(features, jnp.float32))
    return build, args, u, grad, hess, opts


def _grow_tree(**options):
    """One tree: (tree as numpy, grad, hess, the options the grower saw)."""
    import jax

    build, args, u, grad, hess, opts = _tree_program(**options)
    return jax.tree.map(np.asarray, jax.jit(build)(*args, u=u)), grad, hess, opts


PARENT_CASES = {
    "batch1": dict(num_leaves=31, leaf_batch=1),
    "batch8": dict(num_leaves=31, leaf_batch=8),
    "batch42": dict(num_leaves=31, leaf_batch=42),
    "batch8_u": dict(num_leaves=31, leaf_batch=8, u_path=True),
    "batch8_direct": dict(num_leaves=31, leaf_batch=8, histogram_subtraction=False),
    "batch8_categorical": dict(kind="categorical", num_leaves=15, leaf_batch=8),
    "batch8_bundled_u": dict(kind="bundled", num_leaves=15, leaf_batch=8, u_path=True, max_bin=255),
}


FIT_STRUCTURE = ("split_feature", "split_bin", "split_threshold", "left_child", "right_child", "is_leaf")


def _fit_five(**options):
    X, y = _make_binary(n=3000, seed=4)
    bins, mapper = bin_dataset(X, max_bin=63)
    opts = TrainOptions(objective="binary", num_iterations=5, num_leaves=31, max_bin=63, **options)
    return train(bins, y, opts, mapper=mapper).booster


FIT_CASES = {"fit5": {}, "fit5_u": {"histogram_method": "u"}}


def _record_parent():  # python -c "from tests.test_lightgbm_growth import _record_parent as r; r()"
    out = {}
    for case, kwargs in FIT_CASES.items():
        booster = _fit_five(**kwargs)
        out.update({f"{case}.{field}": np.asarray(getattr(booster, field))
                    for field in FIT_STRUCTURE + ("split_gain",)})
    for case, kwargs in PARENT_CASES.items():
        tree = _grow_tree(**kwargs)[0]
        out.update({f"{case}.{field}": getattr(tree, field) for field in STRUCTURE})
    np.savez_compressed(PARENT_TREES, **out)


@pytest.mark.parametrize("options,built,skipped,leaves", [
    (dict(num_leaves=31, leaf_batch=8), 6, 1, 31),  # root + rounds of 1, 2, 4, 8, 8 | 7
    (dict(num_leaves=31, leaf_batch=1), 30, 1, 31),  # root + 29 rounds | the 30th
    (dict(num_leaves=31, leaf_batch=42), 5, 1, 31),  # k = 30: rounds of 1, 2, 4, 8 | 15
    (dict(num_leaves=2), 1, 1, 2),  # the root's pass finds the one split there is budget for
    (dict(num_leaves=31, leaf_batch=8, histogram_subtraction=False), 6, 1, 31),
    (dict(num_leaves=31, leaf_batch=8, u_path=True), 6, 1, 31),
    # a tree that ends for another reason cannot know it before it has looked: nothing skipped
    (dict(num_leaves=31, leaf_batch=8, min_gain_to_split=60.0), None, 0, None),
    (dict(num_leaves=31, leaf_batch=8, max_depth=3), 4, 0, 8),  # root + 3 levels, the last capped
    (dict(num_leaves=31, leaf_batch=8, min_gain_to_split=1e9), 1, 0, 1),  # the root stays a leaf
], ids=["batch8", "batch1", "batch42", "two_leaves", "no_subtraction", "u_path", "min_gain", "max_depth",
        "no_split"])
def test_a_tree_builds_a_pass_only_where_a_later_round_can_read_it(options, built, skipped, leaves):
    tree = _grow_tree(**options)[0]
    grown = int(tree.is_leaf.sum())
    if built is None:  # stopped by min_gain_to_split part-way: a pass after every round that grew
        assert 1 < grown < options["num_leaves"] and tree.passes[0] >= 2
    else:
        assert (int(tree.passes[0]), grown) == (built, leaves)
    assert int(tree.passes[1]) == skipped
    # the slots of a round whose pass was skipped are leaves like any other
    assert int((~tree.is_leaf & (tree.left > 0)).sum()) == grown - 1 and tree.gain[tree.is_leaf].max() == 0.0


@pytest.mark.parametrize("case", sorted(PARENT_CASES))
def test_tree_structure_is_the_parent_commits_bit_for_bit(case):
    tree = _grow_tree(**PARENT_CASES[case])[0]
    with np.load(PARENT_TREES) as parent:
        for field in STRUCTURE:
            np.testing.assert_array_equal(getattr(tree, field), parent[f"{case}.{field}"], err_msg=field)


@pytest.mark.parametrize("case", sorted(FIT_CASES))
def test_five_trees_split_where_the_parent_commits_did(case):
    """From the second tree on the margins carry the float32 rounding the leaf
    values moved by, so a gain may differ in its last digits; no split does."""
    booster = _fit_five(**FIT_CASES[case])
    with np.load(PARENT_TREES) as parent:
        for field in FIT_STRUCTURE:
            np.testing.assert_array_equal(getattr(booster, field), parent[f"{case}.{field}"], err_msg=field)
        np.testing.assert_array_equal(booster.split_gain[0], parent[f"{case}.split_gain"][0])
        np.testing.assert_allclose(booster.split_gain, parent[f"{case}.split_gain"], rtol=1e-4)


@pytest.mark.parametrize("u_path", [False, True], ids=["segment", "u"])
@pytest.mark.parametrize("subtraction", [True, False], ids=["subtraction", "direct"])
@pytest.mark.parametrize("kind", ["numeric", "categorical", "bundled"])
def test_a_leafs_value_and_cover_are_sums_over_the_rows_routed_to_it(kind, subtraction, u_path):
    """``cover`` counts ``row_leaf`` exactly; ``leaf_val`` is ``-lr G / (H + l2)`` over
    those rows, ``l2 + cat_l2`` under a categorical split: the statistics a leaf
    was given by the split that made it are its own."""
    import jax.numpy as jnp

    tree, grad, hess, opts = _grow_tree(
        kind=kind, u_path=u_path, num_leaves=15, leaf_batch=4, lambda_l2=1.0,
        histogram_subtraction=subtraction, max_bin=255 if kind == "bundled" else 63)
    if u_path:  # the histogram's inputs are bfloat16 there
        grad, hess = (np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)) for x in (grad, hess))
    leaves = np.flatnonzero(tree.is_leaf)
    assert len(leaves) == 15 and tree.passes.tolist() == [5, 1]  # root; rounds of 1, 2, 4, 4 | 3
    np.testing.assert_array_equal(tree.cover[leaves], np.bincount(tree.row_leaf, minlength=len(tree.feat))[leaves])
    parent = np.zeros(len(tree.feat), int)
    for side in (tree.left, tree.right):
        parent[side[~tree.is_leaf]] = np.flatnonzero(~tree.is_leaf)
    if kind == "categorical":
        assert tree.cat_node[parent[leaves]].any() and not tree.cat_node[parent[leaves]].all()
    l2 = opts.lambda_l2 + opts.cat_l2 * tree.cat_node[parent[leaves]]
    G = np.bincount(tree.row_leaf, weights=grad.astype(np.float64), minlength=len(tree.feat))[leaves]
    H = np.bincount(tree.row_leaf, weights=hess.astype(np.float64), minlength=len(tree.feat))[leaves]
    np.testing.assert_allclose(tree.leaf_val[leaves], -opts.learning_rate * G / (H + l2), rtol=1e-5, atol=1e-7)


def _inner_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            jaxpr = getattr(item, "jaxpr", item)
            if hasattr(jaxpr, "eqns"):
                yield jaxpr


def _holds(eqn, found):
    """Does the equation, or one inside a jaxpr it carries, satisfy ``found``?"""
    return found(eqn) or any(_holds(e, found) for inner in _inner_jaxprs(eqn) for e in inner.eqns)


@pytest.mark.parametrize("subtraction", [True, False], ids=["subtraction", "direct"])
def test_a_round_begins_with_the_one_contraction_of_u_and_the_loop_leaves_after_the_routing(subtraction):
    """The traced tree program on the U path: the root's contraction before the
    loop; in the loop's body one contraction, ahead of the round's choice of
    leaves (``top_k``) and so of its routing; none in the condition and none
    after the loop. A round whose splits spend the budget is thus followed by
    the exit, not by a pass."""
    import jax

    build, args, u, _, _, _ = _tree_program(
        u_path=True, num_leaves=31, leaf_batch=8, histogram_subtraction=subtraction)
    eqns = jax.make_jaxpr(lambda *a: build(*a[:-1], u=a[-1]))(*args, u).jaxpr.eqns

    def streams_u(e):
        return e.primitive.name == "dot_general" and e.invars[0].aval.shape == u.shape

    def picks_leaves(e):
        return e.primitive.name == "top_k"

    (at, loop), = [(i, e) for i, e in enumerate(eqns) if e.primitive.name == "while"]
    outside = [i for i, e in enumerate(eqns) if i != at and _holds(e, streams_u)]
    assert len(outside) == 1 and outside[0] < at  # the root's
    body = loop.params["body_jaxpr"].jaxpr.eqns
    passes = [i for i, e in enumerate(body) if _holds(e, streams_u)]
    rounds = [i for i, e in enumerate(body) if _holds(e, picks_leaves)]
    assert len(passes) == 1 and len(rounds) == 1 and passes[0] < rounds[0]
    assert not any(_holds(e, streams_u) for e in loop.params["cond_jaxpr"].jaxpr.eqns)


@pytest.mark.parametrize("classes,objective", [(1, "binary"), (3, "multiclass")])
def test_the_boosting_step_runs_the_passes_the_counter_counts(classes, objective):
    """The step vmaps the grower over classes, a single class too, where a
    conditional would run both its branches: the passes that RUN are counted
    here by the gang's allreduce hook, a host callback inside every pass.
    Two 15-leaf trees a class at ``leaf_batch`` 4: the root's pass and rounds
    of 1, 2, 4, 4 | 3, five passes a tree and none after the last round."""
    X, y = _make_binary(n=3000, seed=4)
    if classes > 1:
        y = (X[:, 0] > 0.4).astype(np.float64) + (X[:, 1] > 0.2)
    bins, mapper = bin_dataset(X, max_bin=63)
    ran = []

    def allreduce(hist):
        ran.append(hist.shape[:2])  # (classes, nodes of the pass)
        return hist

    opts = TrainOptions(objective=objective, num_class=classes, num_iterations=2, num_leaves=15,
                        leaf_batch=4, max_bin=63)
    booster = train(bins, y, opts, mapper=mapper, hist_reduce=allreduce).booster
    assert (np.asarray(booster.is_leaf).sum(axis=1) == 15).all()
    assert ran == [(classes, 1), (classes, 4), (classes, 4), (classes, 4), (classes, 4)] * 2
