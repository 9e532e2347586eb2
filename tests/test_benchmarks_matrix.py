"""The wide golden matrix — the reference's 190-row benchmark CSVs scaled
to this runtime (``benchmarks_VerifyLightGBMClassifier.csv`` is 31
dataset x boosting rows; ``benchmarks_VerifyTrainClassifier.csv`` is a
111-row learner matrix). Every row here is a pinned metric asserted in CI:
classifier x 4 datasets x 4 boosting types, regressor x 4 datasets x 4
boosting types, the TrainClassifier/TrainRegressor CROSS-LEARNER matrices
(7 classification + 6 regression learner families through the wrapper +
ComputeModelStatistics flow — 89 rows incl. the multiclass slice, the
VerifyTrainClassifier analogue), multiclass, categorical, VW per-loss (adagrad AND ftrl),
ragged-group LTR ndcg at several cutoffs, the train/tune wrappers, and
the quantized-gradient slice (use_quantized_grad AUC + logloss per
dataset, seeded-deterministic). 198 pinned rows total across the
golden_*.csv files — the reference's benchmark breadth — incl. the
regression-objective matrix (l1/huber/quantile/poisson/tweedie), per-cell
AUC AND logloss on the classifier matrix, and a labelGain-wired ranker
dataset.

Promote intended changes by copying the corresponding
``golden_matrix_*.csv.new.csv`` over its golden (the harness writes them
on every run)."""

import os

import numpy as np
import pytest

from mmlspark_tpu.core.benchmarks import BenchmarkSuite
from mmlspark_tpu.data.table import Table

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "benchmarks")


def _golden(name):
    return os.path.join(GOLDEN_DIR, f"golden_matrix_{name}.csv")

BOOSTING = (
    ("gbdt", {}),
    ("goss", {}),
    ("dart", {"dropRate": 0.2}),
    ("rf", {"baggingFraction": 0.6, "baggingFreq": 1}),
)


def _auc(y, score):
    from mmlspark_tpu.lightgbm.objectives import auc

    return float(auc(np.asarray(y, np.float64), np.asarray(score), np.ones(len(y))))


def _split(X, y, seed=0, frac=0.8):
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(y))
    X, y = np.asarray(X)[perm], np.asarray(y, dtype=np.float64)[perm]
    n = int(frac * len(y))
    return (X[:n], y[:n]), (X[n:], y[n:])


def _table(X, y):
    return Table({"features": np.asarray(X, np.float64), "label": np.asarray(y, np.float64)})


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """This module alone fits some two hundred models of different shapes in
    one process, and every XLA:CPU executable it keeps holds memory mappings:
    47,610 of the kernel's 65,530 (``vm.max_map_count``) by the tenth test
    at PR 35, and past them with a tree program a third larger, where the
    next compile dies in ``backend_compile_and_load`` (PERF.md, PR 36;
    ``tests/conftest.py`` bounds the same thing between modules). No test
    here reuses another's programs, so each releases its own."""
    yield
    import mmlspark_tpu

    mmlspark_tpu.clear_compiled_caches()


@pytest.fixture(scope="module")
def class_sets():
    from sklearn.datasets import load_breast_cancer, load_digits, load_wine, make_classification

    bc = load_breast_cancer()
    dg = load_digits()
    wn = load_wine()
    Xs, ys = make_classification(
        n_samples=1500, n_features=12, n_informative=6, flip_y=0.05,
        random_state=11,
    )
    return {
        "breastcancer": _split(bc.data, bc.target, 0),
        "digitszero": _split(dg.data, (dg.target == 0).astype(float), 2),
        "winebinary": _split(wn.data, (wn.target == 0).astype(float), 1),
        "synthetic": _split(Xs, ys, 3),
    }


@pytest.fixture(scope="module")
def reg_sets():
    from sklearn.datasets import load_diabetes, make_friedman1, make_friedman2, make_regression

    db = load_diabetes()
    X1, y1 = make_friedman1(n_samples=900, n_features=10, noise=1.0, random_state=0)
    X2, y2 = make_friedman2(n_samples=900, noise=0.5, random_state=0)
    Xl, yl = make_regression(n_samples=900, n_features=8, noise=8.0, random_state=4)
    return {
        "diabetes": _split(db.data, db.target, 0),
        "friedman1": _split(X1, y1, 1),
        "friedman2": _split(X2, y2 / 100.0, 2),
        "linear": _split(Xl, yl, 3),
    }


def test_golden_matrix_classifiers(class_sets):
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.lightgbm.objectives import binary_logloss

    suite = BenchmarkSuite("matrix_classifier")
    for dname, ((Xtr, ytr), (Xte, yte)) in class_sets.items():
        for boosting, extra in BOOSTING:
            m = LightGBMClassifier(
                numIterations=30, numLeaves=15, boostingType=boosting,
                seed=0, parallelism="serial", **extra,
            ).fit(_table(Xtr, ytr))
            margins = m.booster.raw_margin(Xte)[:, 0]
            suite.add(f"{dname}_{boosting}_auc", _auc(yte, margins), 0.015)
            # second metric per cell, same fit: logloss catches calibration
            # drift AUC is blind to (rank-preserving margin scaling)
            suite.add(
                f"{dname}_{boosting}_logloss",
                float(binary_logloss(yte, margins, np.ones(len(yte)))),
                0.06, higher_is_better=False,
            )
    suite.verify(_golden("classifier"))


def test_golden_matrix_regressors(reg_sets):
    from mmlspark_tpu.lightgbm import LightGBMRegressor

    suite = BenchmarkSuite("matrix_regressor")
    for dname, ((Xtr, ytr), (Xte, yte)) in reg_sets.items():
        scale = float(np.std(ytr)) or 1.0
        for boosting, extra in BOOSTING:
            m = LightGBMRegressor(
                numIterations=40, numLeaves=15, boostingType=boosting,
                seed=0, parallelism="serial", **extra,
            ).fit(_table(Xtr, ytr))
            rmse = float(np.sqrt(np.mean((m.booster.raw_margin(Xte)[:, 0] - yte) ** 2)))
            suite.add(f"{dname}_{boosting}_rmse", rmse / scale, 0.08,
                      higher_is_better=False)
    suite.verify(_golden("regressor"))


@pytest.fixture(scope="module")
def multiclass_sets():
    """(name, X, y, iters) triples shared by BOTH multiclass golden suites —
    one definition so the dataset construction cannot silently diverge."""
    from sklearn.datasets import load_digits, load_wine, make_blobs

    wn = load_wine()
    dg = load_digits()
    Xb, yb = make_blobs(n_samples=900, centers=4, n_features=6,
                        cluster_std=3.0, random_state=5)
    return (
        ("wine", wn.data, wn.target, 25),
        ("digits10", dg.data[:900], dg.target[:900], 25),
        ("blobs4", Xb, yb, 15),
    )


def test_golden_matrix_multiclass_and_categorical(class_sets, multiclass_sets):
    from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor

    suite = BenchmarkSuite("matrix_multiclass")
    for dname, X, y, iters in multiclass_sets:
        (Xtr, ytr), (Xte, yte) = _split(X, y, 1)
        m = LightGBMClassifier(
            objective="multiclass", numIterations=iters, numLeaves=15,
            minDataInLeaf=5, seed=0, parallelism="serial",
        ).fit(_table(Xtr, ytr))
        acc = float((m.booster.raw_margin(Xte).argmax(axis=1) == yte).mean())
        suite.add(f"{dname}_multiclass_acc", acc, 0.05)

    # categorical splits: classifier AND regressor rows
    rng = np.random.default_rng(21)
    nc = 2500
    catf = rng.integers(0, 10, size=nc)
    eff = rng.normal(size=10) * 2.0
    Xc = np.column_stack([catf.astype(np.float64), rng.normal(size=(nc, 3))])
    yc = ((eff[catf] + Xc[:, 1]) > 0).astype(np.float64)
    (Xtr, ytr), (Xte, yte) = _split(Xc, yc, 4)
    mc = LightGBMClassifier(
        numIterations=20, numLeaves=15, seed=0, parallelism="serial",
        categoricalSlotIndexes=[0],
    ).fit(_table(Xtr, ytr))
    suite.add("catshape_gbdt_auc", _auc(yte, mc.booster.raw_margin(Xte)[:, 0]), 0.015)

    ycr = eff[catf] + Xc[:, 1] + 0.2 * rng.normal(size=nc)
    (Xtr, ytr), (Xte, yte) = _split(Xc, ycr, 5)
    mr = LightGBMRegressor(
        numIterations=25, numLeaves=15, seed=0, parallelism="serial",
        categoricalSlotIndexes=[0],
    ).fit(_table(Xtr, ytr))
    rmse = float(np.sqrt(np.mean((mr.booster.raw_margin(Xte)[:, 0] - yte) ** 2)))
    suite.add("catshape_gbdt_rmse", rmse / float(np.std(ytr)), 0.08,
              higher_is_better=False)

    # isUnbalance golden (positive-recall at the default threshold)
    rngu = np.random.default_rng(31)
    Xu = rngu.normal(size=(2500, 6))
    yu = ((Xu[:, 0] + 0.5 * rngu.normal(size=2500)) > 1.2).astype(np.float64)
    (Xtr, ytr), (Xte, yte) = _split(Xu, yu, 6)
    mu = LightGBMClassifier(
        numIterations=15, numLeaves=15, isUnbalance=True, seed=0,
        parallelism="serial",
    ).fit(_table(Xtr, ytr))
    pred = (mu.booster.raw_margin(Xte)[:, 0] > 0).astype(float)
    pos = yte > 0.5
    suite.add("unbalanced_isunbalance_recall",
              float(pred[pos].mean()) if pos.any() else 0.0, 0.06)
    suite.verify(_golden("multiclass"))


def test_golden_matrix_cross_learner_classifiers(class_sets):
    """The TrainClassifier x learner matrix — the reference's
    ``benchmarks_VerifyTrainClassifier.csv`` shape (111 rows of learner x
    dataset metrics through the SAME wrapper): every classification learner
    family runs through TrainClassifier + ComputeModelStatistics, with
    accuracy AND AUC pinned per dataset."""
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.train import ComputeModelStatistics, TrainClassifier
    from mmlspark_tpu.vw import VowpalWabbitClassifier

    def lgbm(**kw):
        return LightGBMClassifier(
            numIterations=25, numLeaves=15, seed=0, parallelism="serial", **kw
        )

    LEARNERS = (
        ("lgbm_gbdt", lambda: lgbm()),
        ("lgbm_goss", lambda: lgbm(boostingType="goss")),
        ("lgbm_dart", lambda: lgbm(boostingType="dart", dropRate=0.2)),
        ("lgbm_rf", lambda: lgbm(
            boostingType="rf", baggingFraction=0.6, baggingFreq=1)),
        ("vw_logistic", lambda: VowpalWabbitClassifier(numPasses=8)),
        ("vw_ftrl", lambda: VowpalWabbitClassifier(
            numPasses=8, passThroughArgs="--ftrl --ftrl_alpha 0.1")),
        ("vw_hinge", lambda: VowpalWabbitClassifier(
            numPasses=8, passThroughArgs="--loss_function hinge")),
    )
    suite = BenchmarkSuite("matrix_trainclassifier")
    for dname, ((Xtr, ytr), (Xte, yte)) in class_sets.items():
        # one normalization for every learner (VW is scale-sensitive; trees
        # are invariant to it, so the comparison stays apples-to-apples)
        mu, sd = Xtr.mean(0), Xtr.std(0) + 1e-9
        Xtr_n, Xte_n = (Xtr - mu) / sd, (Xte - mu) / sd
        for lname, make in LEARNERS:
            m = TrainClassifier(model=make(), labelCol="label").fit(
                _table(Xtr_n, ytr)
            )
            stats = ComputeModelStatistics(labelCol="label").transform(
                m.transform(_table(Xte_n, yte))
            )
            suite.add(f"{dname}_{lname}_acc", float(stats["accuracy"][0]), 0.03)
            suite.add(f"{dname}_{lname}_auc", float(stats["AUC"][0]), 0.03)
    suite.verify(_golden("trainclassifier"))


def test_golden_matrix_cross_learner_multiclass(multiclass_sets):
    """Multiclass through the SAME TrainClassifier + ComputeModelStatistics
    wrapper flow: 3 datasets x 3 boosting types, accuracy pinned (the
    multiclass slice of the reference's cross-learner matrix)."""
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.train import ComputeModelStatistics, TrainClassifier

    suite = BenchmarkSuite("matrix_trainmulticlass")
    for dname, X, y, _iters in multiclass_sets:
        (Xtr, ytr), (Xte, yte) = _split(X, y, 7)
        for boosting, extra in (("gbdt", {}), ("goss", {}),
                                ("dart", {"dropRate": 0.2})):
            m = TrainClassifier(
                model=LightGBMClassifier(
                    objective="multiclass", numIterations=20, numLeaves=15,
                    minDataInLeaf=5, boostingType=boosting, seed=0,
                    parallelism="serial", **extra,
                ),
                labelCol="label",
            ).fit(_table(Xtr, ytr))
            stats = ComputeModelStatistics(labelCol="label").transform(
                m.transform(_table(Xte, yte))
            )
            suite.add(
                f"{dname}_lgbm_{boosting}_acc", float(stats["accuracy"][0]), 0.05
            )
    suite.verify(_golden("trainmulticlass"))


def test_golden_matrix_cross_learner_regressors(reg_sets):
    """TrainRegressor x learner matrix (the regression half of the
    reference's cross-learner benchmarks): scale-normalized RMSE through
    TrainRegressor + ComputeModelStatistics per learner family."""
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from mmlspark_tpu.train import ComputeModelStatistics, TrainRegressor
    from mmlspark_tpu.vw import VowpalWabbitRegressor

    def lgbm(**kw):
        return LightGBMRegressor(
            numIterations=35, numLeaves=15, seed=0, parallelism="serial", **kw
        )

    LEARNERS = (
        ("lgbm_gbdt", lambda: lgbm()),
        ("lgbm_goss", lambda: lgbm(boostingType="goss")),
        ("lgbm_dart", lambda: lgbm(boostingType="dart", dropRate=0.2)),
        ("lgbm_rf", lambda: lgbm(
            boostingType="rf", baggingFraction=0.6, baggingFreq=1)),
        ("vw_squared", lambda: VowpalWabbitRegressor(numPasses=10)),
        ("vw_ftrl", lambda: VowpalWabbitRegressor(
            numPasses=10, passThroughArgs="--ftrl --ftrl_alpha 0.1")),
    )
    suite = BenchmarkSuite("matrix_trainregressor")
    for dname, ((Xtr, ytr), (Xte, yte)) in reg_sets.items():
        mu, sd = Xtr.mean(0), Xtr.std(0) + 1e-9
        Xtr_n, Xte_n = (Xtr - mu) / sd, (Xte - mu) / sd
        scale = float(np.std(ytr)) or 1.0
        for lname, make in LEARNERS:
            m = TrainRegressor(model=make(), labelCol="label").fit(
                _table(Xtr_n, ytr)
            )
            stats = ComputeModelStatistics(
                labelCol="label", evaluationMetric="regression"
            ).transform(m.transform(_table(Xte_n, yte)))
            suite.add(
                f"{dname}_{lname}_rmse", float(stats["root_mean_squared_error"][0]) / scale,
                0.08, higher_is_better=False,
            )
    suite.verify(_golden("trainregressor"))


def test_golden_matrix_regression_objectives(reg_sets):
    """Objective-math goldens: every non-default regression objective
    (l1/huber/quantile/poisson/tweedie) pinned on two real datasets with an
    objective-appropriate metric — l1/huber by scale-normalized MAE,
    quantile by empirical coverage at alpha, poisson/tweedie by normalized
    RMSE on positive targets. A silent gradient/hessian regression in any
    objective moves its rows."""
    from mmlspark_tpu.lightgbm import LightGBMRegressor

    suite = BenchmarkSuite("matrix_objectives")
    for dname in ("diabetes", "friedman1"):  # both have positive targets
        (Xtr, ytr), (Xte, yte) = reg_sets[dname]
        scale = float(np.std(ytr)) or 1.0

        def fit(objective, **extra):
            return LightGBMRegressor(
                objective=objective, numIterations=40, numLeaves=15,
                seed=0, parallelism="serial", **extra,
            ).fit(_table(Xtr, ytr))

        for objective in ("regression_l1", "huber"):
            m = fit(objective)
            mae = float(np.mean(np.abs(m.booster.raw_margin(Xte)[:, 0] - yte)))
            suite.add(f"{dname}_{objective}_mae", mae / scale, 0.08,
                      higher_is_better=False)

        mq = fit("quantile", alpha=0.9)
        coverage = float((yte <= mq.booster.raw_margin(Xte)[:, 0]).mean())
        # |coverage - alpha| so drift in EITHER direction moves the row
        # (a one-sided coverage pin would pass an overshooting fit)
        suite.add(f"{dname}_quantile090_coverage_err", abs(coverage - 0.9),
                  0.07, higher_is_better=False)

        for objective in ("poisson", "tweedie"):
            m = fit(objective)
            pred = np.exp(m.booster.raw_margin(Xte)[:, 0])  # log-link margins
            rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
            suite.add(f"{dname}_{objective}_rmse", rmse / scale, 0.10,
                      higher_is_better=False)
    suite.verify(_golden("objectives"))


def test_golden_matrix_vw(class_sets, reg_sets):
    from mmlspark_tpu.vw import VowpalWabbitClassifier, VowpalWabbitRegressor
    from mmlspark_tpu.lightgbm.objectives import binary_logloss

    suite = BenchmarkSuite("matrix_vw")
    for dname in ("breastcancer", "synthetic"):
        (Xtr, ytr), (Xte, yte) = class_sets[dname]
        mu, sd = Xtr.mean(0), Xtr.std(0) + 1e-9
        Xtr_n, Xte_n = (Xtr - mu) / sd, (Xte - mu) / sd
        for args, label in (("", "adagrad"), ("--ftrl --ftrl_alpha 0.1", "ftrl")):
            m = VowpalWabbitClassifier(numPasses=5, passThroughArgs=args).fit(
                _table(Xtr_n, ytr)
            )
            margins = m._margins(_table(Xte_n, yte))
            suite.add(f"{dname}_vw_{label}_auc", _auc(yte, margins), 0.02)
        mh = VowpalWabbitClassifier(
            numPasses=5, passThroughArgs="--loss_function hinge"
        ).fit(_table(Xtr_n, ytr))
        suite.add(f"{dname}_vw_hinge_acc",
                  float(((mh._margins(_table(Xte_n, yte)) > 0) == (yte > 0.5)).mean()),
                  0.03)

    for dname in ("diabetes", "friedman1"):
        (Xtr, ytr), (Xte, yte) = reg_sets[dname]
        mu, sd = Xtr.mean(0), Xtr.std(0) + 1e-9
        ymu, ysd = ytr.mean(), ytr.std() or 1.0
        m = VowpalWabbitRegressor(numPasses=8).fit(
            _table((Xtr - mu) / sd, (ytr - ymu) / ysd)
        )
        pred = m._margins(_table((Xte - mu) / sd, yte)) * ysd + ymu
        suite.add(f"{dname}_vw_squared_rmse",
                  float(np.sqrt(np.mean((pred - yte) ** 2)) / ysd), 0.1,
                  higher_is_better=False)
        mq = VowpalWabbitRegressor(
            numPasses=8, passThroughArgs="--loss_function quantile --quantile_tau 0.5"
        ).fit(_table((Xtr - mu) / sd, (ytr - ymu) / ysd))
        predq = mq._margins(_table((Xte - mu) / sd, yte)) * ysd + ymu
        suite.add(f"{dname}_vw_quantile_mae",
                  float(np.mean(np.abs(predq - yte)) / ysd), 0.1,
                  higher_is_better=False)
    suite.verify(_golden("vw"))


def test_golden_matrix_ranker_ragged():
    """LTR goldens with RAGGED groups (sizes 3..25) at several ndcg
    cutoffs — the reference pins lambdarank metrics on a real LTR set
    (VerifyLightGBMRanker.scala); this is the deterministic local stand-in."""
    from mmlspark_tpu.lightgbm import LightGBMRanker
    from mmlspark_tpu.lightgbm.ranker import ndcg_at_k

    suite = BenchmarkSuite("matrix_ranker")
    # dataset "c" pins the labelGain wiring: a LINEAR gain table instead of
    # LightGBM's default 2^i - 1 must change the fitted ordering pressure
    for seed, tag, extra in ((9, "a", {}), (23, "b", {}),
                             (31, "c", {"labelGain": [0, 1, 2, 3, 4]})):
        rng = np.random.default_rng(seed)
        sizes = rng.integers(3, 26, size=50)
        n = int(sizes.sum())
        group = np.repeat(np.arange(len(sizes)), sizes)
        X = rng.normal(size=(n, 6))
        rel = np.clip(
            (X[:, 0] * 1.2 + 0.5 * X[:, 1] + rng.normal(scale=0.5, size=n)) + 1.5,
            0, 4,
        ).round()
        t = Table({
            "features": X, "label": rel.astype(np.float64),
            "query": group.astype(np.int64),
        })
        m = LightGBMRanker(
            numIterations=25, groupCol="query", minDataInLeaf=3, seed=0,
            parallelism="serial", **extra,
        ).fit(t)
        score = m.transform(t)["prediction"]
        ks = (3, 5, 10) if tag != "c" else (1, 3, 5, 10)
        for k in ks:
            suite.add(f"ltr{tag}_ndcg_at_{k}", float(ndcg_at_k(rel, score, group, k)),
                      0.02)
    suite.verify(_golden("ranker"))


def test_golden_matrix_wrappers(class_sets, reg_sets):
    from mmlspark_tpu.automl import TuneHyperparameters
    from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
    from mmlspark_tpu.train import TrainClassifier, TrainRegressor

    suite = BenchmarkSuite("matrix_wrappers")
    (Xtr, ytr), (Xte, yte) = class_sets["breastcancer"]
    tc = TrainClassifier(
        model=LightGBMClassifier(numIterations=15, numLeaves=7, parallelism="serial"),
        labelCol="label",
    ).fit(_table(Xtr, ytr))
    out = tc.transform(_table(Xte, yte))
    suite.add("breastcancer_trainclassifier_acc",
              float((out["prediction"] == yte).mean()), 0.03)

    (Xtr, ytr), (Xte, yte) = reg_sets["friedman1"]
    tr = TrainRegressor(
        model=LightGBMRegressor(numIterations=30, numLeaves=7, parallelism="serial"),
        labelCol="label",
    ).fit(_table(Xtr, ytr))
    outr = tr.transform(_table(Xte, yte))
    rmse = float(np.sqrt(np.mean((outr["prediction"] - yte) ** 2)))
    suite.add("friedman1_trainregressor_rmse", rmse / float(np.std(ytr)), 0.08,
              higher_is_better=False)

    (Xtr, ytr), (Xte, yte) = class_sets["synthetic"]
    from mmlspark_tpu.automl.hyperparam import DiscreteHyperParam

    tuned = TuneHyperparameters(
        models=LightGBMClassifier(numIterations=10, parallelism="serial"),
        paramSpace={"numLeaves": DiscreteHyperParam([7, 15])},
        evaluationMetric="accuracy",
        numFolds=2,
        numRuns=2,
        seed=0,
    ).fit(_table(Xtr, ytr))
    suite.add("synthetic_tune_best_acc", float(tuned.getBestMetric()), 0.03)
    suite.verify(_golden("wrappers"))


def test_golden_matrix_quantized(class_sets):
    """Quantized-gradient fits (use_quantized_grad) are seeded-
    deterministic — pin AUC + logloss across the classification datasets.
    Engine-level with histogram_method='u' so the quantized s8 pass
    actually runs under CPU CI (the stage default would silently fall back
    to exact stats off-TPU, pinning nothing new)."""
    from mmlspark_tpu.lightgbm.binning import bin_dataset
    from mmlspark_tpu.lightgbm.objectives import binary_logloss
    from mmlspark_tpu.lightgbm.train import TrainOptions, train

    suite = BenchmarkSuite("matrix_quant")
    for dname, ((Xtr, ytr), (Xte, yte)) in class_sets.items():
        bins, mp = bin_dataset(np.asarray(Xtr, np.float64), max_bin=255)
        opts = TrainOptions(
            objective="binary", num_iterations=30, num_leaves=15, seed=0,
            histogram_method="u", use_quantized_grad=True,
        )
        r = train(bins, np.asarray(ytr, np.float64), opts, mapper=mp)
        margins = r.booster.raw_margin(np.asarray(Xte, np.float64))[:, 0]
        suite.add(f"{dname}_quant_auc", _auc(yte, margins), 0.015)
        suite.add(
            f"{dname}_quant_logloss",
            float(binary_logloss(yte, margins, np.ones(len(yte)))),
            0.06, higher_is_better=False,
        )
    suite.verify(_golden("quant"))
