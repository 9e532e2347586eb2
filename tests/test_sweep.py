"""Many-models sweep plane tests (``mmlspark_tpu.sweep``): shape-
bucketing rules, batched-vs-sequential parity, the ``TrainValidSweep``
estimator (selection + ModelStore commit), golden selection parity with
the thread-pool ``TuneHyperparameters`` baseline, compile amortization
(the bench regression guard), and the gang/chaos path — a SIGKILL'd
sweep worker must not change the selected model."""

import os

import numpy as np
import pytest

from mmlspark_tpu.automl.hyperparam import (
    DefaultHyperparams,
    DiscreteHyperParam,
    DoubleRangeHyperParam,
    GridSpace,
)
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.sweep import (
    GBDT_VMAPPED,
    VW_VMAPPED,
    TrainValidSweep,
    bucket_candidates,
    fit_bucket,
)
from mmlspark_tpu.vw import VowpalWabbitClassifier


@pytest.fixture
def clf_table(rng):
    X = rng.normal(size=(240, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return Table({"features": X, "label": y})


class TestBucketing:
    def test_vmapped_params_share_one_bucket(self):
        est = LightGBMClassifier(numIterations=5)
        maps = [
            {"learningRate": 0.05},
            {"learningRate": 0.1, "featureFraction": 0.8},
            {"learningRate": 0.2, "baggingFraction": 0.7, "baggingFreq": 1},
        ]
        buckets = bucket_candidates([(est, m) for m in maps])
        assert len(buckets) == 1
        assert buckets[0].kind == "gbdt"
        assert buckets[0].size == 3
        assert buckets[0].indices == [0, 1, 2]

    def test_static_params_split_buckets(self):
        est = LightGBMClassifier(numIterations=5)
        maps = [
            {"learningRate": 0.1, "numLeaves": 7},
            {"learningRate": 0.2, "numLeaves": 7},
            {"learningRate": 0.1, "numLeaves": 15},
        ]
        buckets = bucket_candidates([(est, m) for m in maps])
        assert sorted(b.size for b in buckets) == [1, 2]
        # the union of indices is exactly the candidate list
        assert sorted(i for b in buckets for i in b.indices) == [0, 1, 2]

    def test_classifier_and_regressor_never_share(self):
        cands = [
            (LightGBMClassifier(numIterations=5), {"learningRate": 0.1}),
            (LightGBMRegressor(numIterations=5), {"learningRate": 0.1}),
        ]
        buckets = bucket_candidates(cands)
        assert len(buckets) == 2

    def test_unbatchable_gbdt_falls_back_to_singletons(self):
        est = LightGBMClassifier(numIterations=5, earlyStoppingRound=2)
        buckets = bucket_candidates(
            [(est, {"learningRate": lr}) for lr in (0.1, 0.2)]
        )
        assert [b.kind for b in buckets] == [None, None]
        assert all(b.size == 1 for b in buckets)

    def test_vw_bucket_and_arg_conflict(self):
        est = VowpalWabbitClassifier(numPasses=2)
        buckets = bucket_candidates(
            [(est, {"learningRate": lr}) for lr in (0.3, 0.6)]
        )
        assert len(buckets) == 1 and buckets[0].kind == "vw"
        # a pass-through flag pinning a vmapped lane breaks batching
        pinned = VowpalWabbitClassifier(
            numPasses=2, passThroughArgs="--learning_rate 0.5"
        )
        buckets = bucket_candidates(
            [(pinned, {"powerT": p}) for p in (0.0, 0.5)]
        )
        assert [b.kind for b in buckets] == [None, None]

    def test_vmapped_name_sets(self):
        est = LightGBMClassifier()
        assert all(est.hasParam(n) for n in GBDT_VMAPPED)
        vw = VowpalWabbitClassifier()
        assert all(vw.hasParam(n) for n in VW_VMAPPED)


class TestBatchedParity:
    def test_gbdt_batched_scores_match_sequential(self, clf_table):
        est = LightGBMClassifier(
            labelCol="label", numIterations=5, numLeaves=7, maxBin=32
        )
        maps = [{"learningRate": lr} for lr in (0.05, 0.1, 0.2)]
        (bucket,) = bucket_candidates([(est, m) for m in maps])
        mask = np.zeros(clf_table.num_rows, dtype=bool)
        mask[: clf_table.num_rows * 3 // 4] = True
        train, valid = clf_table.filter(mask), clf_table.filter(~mask)
        scored = fit_bucket(bucket, train, valid, "label", "AUC")
        from mmlspark_tpu.automl.tune import _evaluate

        for m, (metric, _model) in zip(maps, scored):
            ref = est.copy(m).fit(train)
            ref_metric = _evaluate(ref.transform(valid), "label", "AUC")
            assert np.isclose(metric, ref_metric, rtol=1e-5), (m, metric)

    @pytest.mark.parametrize("bundled", [False, True])
    @pytest.mark.parametrize("boosting,bagging", [
        ("gbdt", False), ("gbdt", True), ("goss", False),
    ])
    def test_train_many_of_one_is_train(self, boosting, bagging, bundled):
        """``train_many([o])`` shares ``train(o)``'s set-up and step: the
        model texts are byte-equal."""
        from mmlspark_tpu.lightgbm.binning import bin_dataset
        from mmlspark_tpu.lightgbm.train import TrainOptions, train, train_many

        rng = np.random.default_rng(0)
        if bundled:  # one non-zero column a row: the columns bundle
            X = np.zeros((600, 10))
            X[np.arange(600), rng.integers(0, 10, 600)] = rng.normal(size=600) + 2
            y = (X[:, 0] + X[:, 1] - X[:, 2] > 0.5).astype(np.float64)
        else:
            X = rng.normal(size=(600, 6))
            y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
        bins, mapper = bin_dataset(X, max_bin=31, feature_bundling=bundled)
        assert (mapper.bundles is not None) == bundled
        bag = dict(bagging_fraction=0.7, bagging_freq=1) if bagging else {}
        o = TrainOptions(
            objective="binary", num_iterations=5, num_leaves=7, max_bin=31,
            boosting_type=boosting, learning_rate=0.15, feature_fraction=0.8,
            **bag,
        )
        (many,) = train_many(bins, y, [o], mapper=mapper)
        one = train(bins, y, o, mapper=mapper)
        assert many.booster.model_to_string() == one.booster.model_to_string()

    def test_vw_batched_scores_match_sequential(self, clf_table, monkeypatch):
        # the vmapped core is single-device; the sequential reference must
        # run mesh-free too (row sharding reorders SGD accumulation)
        from mmlspark_tpu.vw.base import VowpalWabbitBase

        monkeypatch.setattr(
            VowpalWabbitBase, "_select_mesh", lambda self: None
        )
        est = VowpalWabbitClassifier(labelCol="label", numPasses=2)
        maps = [
            {"learningRate": 0.3, "powerT": 0.5},
            {"learningRate": 0.6, "powerT": 0.0, "l2": 1e-6},
        ]
        (bucket,) = bucket_candidates([(est, m) for m in maps])
        mask = np.zeros(clf_table.num_rows, dtype=bool)
        mask[: clf_table.num_rows * 3 // 4] = True
        train, valid = clf_table.filter(mask), clf_table.filter(~mask)
        scored = fit_bucket(bucket, train, valid, "label", "accuracy")
        from mmlspark_tpu.automl.tune import _evaluate

        for m, (metric, _model) in zip(maps, scored):
            ref = est.copy(m).fit(train)
            ref_metric = _evaluate(ref.transform(valid), "label", "accuracy")
            assert np.isclose(metric, ref_metric, rtol=1e-5), (m, metric)


class TestTrainValidSweep:
    def test_selects_best_and_commits_standalone_bytes(
        self, clf_table, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("MMLSPARK_TPU_CHECKPOINT_DIR", str(tmp_path))
        est = LightGBMClassifier(
            labelCol="label", numIterations=5, numLeaves=7, maxBin=32
        )
        sweep = TrainValidSweep(
            estimator=est,
            paramSpace=GridSpace({
                "learningRate": [0.05, 0.1, 0.2],
                "numLeaves": [7, 15],
            }),
            labelCol="label",
            evaluationMetric="AUC",
            seed=3,
        )
        model = sweep.fit(clf_table)
        metrics = model.getAllMetrics()
        assert len(metrics) == 6
        higher_best = int(np.nanargmax(np.asarray(metrics)))
        assert metrics[higher_best] == model.getBestMetric()
        assert "prediction" in model.transform(clf_table)

        board = model.leaderboard()
        assert list(board.column("rank")) == list(range(6))
        assert board.column("metric")[0] == model.getBestMetric()

        # the committed model IS a standalone fit with the winning
        # params, byte for byte (the refit-on-full-table contract)
        from mmlspark_tpu.runtime.journal import ModelStore

        store = ModelStore(str(tmp_path / "models"))
        version, text = store.latest("sweep-lightgbmclassificationmodel")
        assert version == model.getModelVersion() == 1
        standalone = est.copy(model.getBestParams()).fit(clf_table)
        assert text == standalone.get_model_string()

    def test_dist_dict_space_samples_num_runs(self, clf_table):
        sweep = TrainValidSweep(
            estimator=LightGBMClassifier(
                labelCol="label", numIterations=3, numLeaves=7, maxBin=32
            ),
            paramSpace={
                "learningRate": DoubleRangeHyperParam(0.05, 0.3),
                "numLeaves": DiscreteHyperParam([7, 15]),
            },
            labelCol="label",
            numRuns=3,
            seed=1,
            commitModel=False,
        )
        model = sweep.fit(clf_table)
        assert len(model.getAllMetrics()) == 3
        assert model.getModelVersion() == -1

    def test_tune_batched_selection_matches_threadpool(self, clf_table):
        """Golden parity: TuneHyperparameters routed through the batched
        plane must pick the SAME best candidate as the thread-pool
        baseline under a fixed seed (metric values match to float
        tolerance; selection must match exactly)."""
        from mmlspark_tpu.automl import TuneHyperparameters

        kwargs = dict(
            models=LightGBMClassifier(numIterations=5, maxBin=32),
            paramSpace={
                "numLeaves": DiscreteHyperParam([3, 15]),
                "learningRate": DoubleRangeHyperParam(0.05, 0.3),
            },
            evaluationMetric="AUC",
            numFolds=2,
            numRuns=3,
            seed=5,
        )
        batched = TuneHyperparameters(
            sweepMode="batched", **kwargs
        ).fit(clf_table)
        threadpool = TuneHyperparameters(
            sweepMode="threadpool", **kwargs
        ).fit(clf_table)
        assert batched.getBestParams() == threadpool.getBestParams()
        np.testing.assert_allclose(
            batched.getAllMetrics(), threadpool.getAllMetrics(), rtol=1e-5
        )


class TestDefaultHyperparams:
    def test_spaces_name_real_estimator_params(self):
        gbdt = LightGBMClassifier()
        for name in DefaultHyperparams.lightgbm():
            assert gbdt.hasParam(name), name
        vw = VowpalWabbitClassifier()
        for name in DefaultHyperparams.sgd():
            assert vw.hasParam(name), name
        for name in DefaultHyperparams.vw():
            assert vw.hasParam(name), name


@pytest.mark.slow
class TestCompileAmortization:
    def test_bench_guard_at_smoke_scale(self, monkeypatch):
        """The bench regression guard (satellite of the acceptance
        criterion): a >=12-candidate sweep must compile strictly fewer
        batched programs than it has candidates and beat the sequential
        baseline on models/sec. Reuses bench._sweep_block + sweep_guard
        verbatim so the CI bench job and this test enforce one rule."""
        import bench
        from mmlspark_tpu.observability.profiler import get_profiler

        monkeypatch.setattr(bench, "N_ROWS", 1200)
        monkeypatch.setattr(bench, "N_ITERS", 3)
        monkeypatch.setenv("BENCH_SWEEP_ROWS", "1200")
        monkeypatch.setenv("BENCH_SWEEP_ITERS", "3")
        prof = get_profiler()
        was_enabled = prof.enabled
        prof.enable()
        try:
            block = bench.sweep_guard(bench._sweep_block())
        finally:
            if not was_enabled:
                prof.disable()
        assert block["sweep_candidates"] >= 12
        assert block["sweep_batched_compiles"] < block["sweep_candidates"]
        assert max(block["sweep_bucket_sizes"]) > 1


def _gang_grid_sweep(table, num_processes=0, group_options=None):
    est = LightGBMClassifier(
        labelCol="label", numIterations=4, numLeaves=7, maxBin=32
    )
    sweep = TrainValidSweep(
        estimator=est,
        paramSpace=GridSpace({
            "learningRate": [0.05, 0.2],
            "numLeaves": [7, 15],
        }),
        labelCol="label",
        evaluationMetric="AUC",
        seed=3,
        numProcesses=num_processes,
    )
    if group_options is not None:
        sweep._group_options = group_options
    return sweep, sweep.fit(table)


@pytest.mark.slow
class TestSweepGang:
    def test_gang_matches_inline(self, clf_table, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "MMLSPARK_TPU_CHECKPOINT_DIR", str(tmp_path / "inline")
        )
        _, inline = _gang_grid_sweep(clf_table)
        monkeypatch.setenv(
            "MMLSPARK_TPU_CHECKPOINT_DIR", str(tmp_path / "gang")
        )
        sweep, gang = _gang_grid_sweep(
            clf_table, num_processes=2,
            group_options={"epoch_timeout_s": 180.0},
        )
        assert sweep._process_sweep["epochs"] == 1
        np.testing.assert_allclose(
            gang.getAllMetrics(), inline.getAllMetrics(), rtol=1e-5
        )
        assert gang.getBestParams() == inline.getBestParams()


@pytest.mark.slow
class TestSweepChaos:
    def test_sigkill_mid_sweep_does_not_change_selection(
        self, clf_table, tmp_path, monkeypatch
    ):
        """Satellite chaos pass: kill a sweep worker mid-bucket; the gang
        re-forms, journaled buckets resume with zero re-execution, and
        the final leaderboard + committed ModelStore version/bytes are
        identical to the undisturbed run."""
        from mmlspark_tpu import observability as obs
        from mmlspark_tpu.runtime.faults import FaultPlan
        from mmlspark_tpu.runtime.journal import ModelStore

        event_log = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("MMLSPARK_TPU_EVENT_LOG", event_log)
        monkeypatch.setenv(
            "MMLSPARK_TPU_CHECKPOINT_DIR", str(tmp_path / "base")
        )
        _, base = _gang_grid_sweep(
            clf_table, num_processes=2,
            group_options={"epoch_timeout_s": 180.0},
        )

        # kill member 1 at bucket index 1 (the grid above makes 2
        # buckets, so the directive must target an index < 2)
        monkeypatch.setenv(
            "MMLSPARK_TPU_CHECKPOINT_DIR", str(tmp_path / "chaos")
        )
        plan = FaultPlan(seed=11).kill_process(1, iteration=1)
        sweep, chaos = _gang_grid_sweep(
            clf_table, num_processes=2,
            group_options={"faults": plan, "epoch_timeout_s": 180.0},
        )
        monkeypatch.delenv("MMLSPARK_TPU_EVENT_LOG")

        assert plan.fired == [("kill_process", 1, 0)]
        info = sweep._process_sweep
        assert info["epochs"] == 2
        killed = [s for s in info["exit_statuses"] if s.reason == "signal:9"]
        assert killed and killed[0].member == 1

        # selection unchanged: metrics, winner, committed version + bytes
        assert chaos.getAllMetrics() == base.getAllMetrics()
        assert chaos.getBestParams() == base.getBestParams()
        assert chaos.getModelVersion() == base.getModelVersion() == 1
        name = "sweep-lightgbmclassificationmodel"
        _, base_text = ModelStore(str(tmp_path / "base/models")).latest(name)
        _, chaos_text = ModelStore(str(tmp_path / "chaos/models")).latest(name)
        assert chaos_text == base_text

        events = obs.replay(event_log)
        names = [type(e).__name__ for e in events]
        assert names.count("ProcessLost") == 1
        assert names.count("GroupReformed") == 1
        assert names.count("SweepStarted") == 2
        assert names.count("SweepCompleted") == 2
