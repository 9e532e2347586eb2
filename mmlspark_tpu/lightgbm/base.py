"""Shared LightGBM-style estimator machinery: param surface + train flow.

Param names/defaults mirror ``lightgbm/LightGBMParams.scala:13-251`` so a
reference user finds the identical knobs. The train flow re-creates
``LightGBMBase.train``/``innerTrain`` (``lightgbm/LightGBMBase.scala:26-213``):
column extraction, validation-indicator split, batch-mode chaining
(``numBatches``), and worker/mesh selection — minus everything the TPU
runtime makes obsolete (socket rendezvous, barrier mode, Kryo reduce).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from mmlspark_tpu.core.params import (
    HasFeaturesCol,
    HasInitScoreCol,
    HasLabelCol,
    HasPredictionCol,
    HasValidationIndicatorCol,
    HasWeightCol,
    Param,
    Params,
    ge,
    gt,
    in_range,
    one_of,
    to_bool,
    to_float,
    to_int,
    to_list_int,
    to_list_str,
    to_str,
)
from mmlspark_tpu.core.pipeline import Estimator, Model
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.lightgbm.binning import BinMapper, bin_dataset
from mmlspark_tpu.lightgbm.booster import Booster
from mmlspark_tpu.lightgbm.train import TrainOptions, TrainResult, train
from mmlspark_tpu.observability.tracing import get_tracer


class LightGBMParams(
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasWeightCol,
    HasInitScoreCol,
    HasValidationIndicatorCol,
    Params,
):
    """The shared knob surface (LightGBMParams.scala)."""

    numIterations = Param("Number of boosting iterations", default=100, converter=to_int, validator=gt(0))
    learningRate = Param("Shrinkage rate", default=0.1, converter=to_float, validator=gt(0))
    numLeaves = Param("Max leaves per tree", default=31, converter=to_int, validator=gt(1))
    maxDepth = Param("Max tree depth (-1 = derive from numLeaves)", default=-1, converter=to_int)
    maxBin = Param("Max number of feature bins", default=255, converter=to_int, validator=gt(1))
    binSampleCount = Param(
        "Rows sampled when computing histogram bin edges "
        "(bin_construct_sample_cnt)",
        default=200000, converter=to_int, validator=gt(0),
    )
    maxBinByFeature = Param(
        "Per-feature max-bin override (empty = maxBin everywhere)",
        default=[], converter=to_list_int,
    )
    slotNames = Param(
        "Feature slot names (overrides the generated f0..fN; also the "
        "namespace categoricalSlotNames resolves against)",
        default=[], converter=to_list_str,
    )
    baggingFraction = Param("Row subsample fraction", default=1.0, converter=to_float, validator=in_range(0, 1))
    posBaggingFraction = Param(
        "Positive-class bagging fraction (binary; 1.0 = off)",
        default=1.0, converter=to_float, validator=in_range(0, 1),
    )
    negBaggingFraction = Param(
        "Negative-class bagging fraction (binary; 1.0 = off)",
        default=1.0, converter=to_float, validator=in_range(0, 1),
    )
    baggingFreq = Param("Resample bagging mask every k iterations (0=off)", default=0, converter=to_int, validator=ge(0))
    baggingSeed = Param("Bagging seed", default=3, converter=to_int)
    featureFraction = Param("Feature subsample fraction per tree", default=1.0, converter=to_float, validator=in_range(0, 1))
    lambdaL1 = Param("L1 regularization", default=0.0, converter=to_float, validator=ge(0))
    lambdaL2 = Param("L2 regularization", default=0.0, converter=to_float, validator=ge(0))
    minSumHessianInLeaf = Param("Minimum hessian sum per leaf", default=1e-3, converter=to_float, validator=ge(0))
    minDataInLeaf = Param("Minimum rows per leaf", default=20, converter=to_int, validator=ge(0))
    minGainToSplit = Param("Minimum gain to split", default=0.0, converter=to_float, validator=ge(0))
    maxDeltaStep = Param("Max leaf output magnitude (0=off)", default=0.0, converter=to_float, validator=ge(0))
    boostingType = Param(
        "gbdt, rf, dart, or goss", default="gbdt",
        converter=to_str, validator=one_of("gbdt", "rf", "dart", "goss"),
    )
    earlyStoppingRound = Param("Stop after k rounds without improvement (0=off)", default=0, converter=to_int, validator=ge(0))
    improvementTolerance = Param("Minimal delta counted as improvement", default=0.0, converter=to_float, validator=ge(0))
    metric = Param("Eval metric name ('' = objective default)", default="", converter=to_str)
    parallelism = Param(
        "data_parallel, voting_parallel, or serial",
        default="data_parallel", converter=to_str,
        validator=one_of("data_parallel", "voting_parallel", "serial"),
    )
    topK = Param("Top features for voting parallel", default=20, converter=to_int, validator=gt(0))
    topRate = Param("GOSS: kept fraction of large-gradient rows", default=0.2, converter=to_float, validator=in_range(0, 1))
    otherRate = Param("GOSS: sampled fraction of remaining rows", default=0.1, converter=to_float, validator=in_range(0, 1))
    dropRate = Param("DART: per-tree dropout probability", default=0.1, converter=to_float, validator=in_range(0, 1))
    growthPolicy = Param(
        "leafwise (LightGBM best-first, numLeaves-bounded) or depthwise "
        "(balanced levels — fewer, larger MXU passes)",
        default="leafwise", converter=to_str, validator=one_of("leafwise", "depthwise"),
    )
    leafBatch = Param(
        "Frontier leaves split per histogram pass under leafwise growth. "
        "NOTE: the default (8) is a batched APPROXIMATION of LightGBM's "
        "sequential best-first growth — up to 8 frontier leaves commit "
        "together, so default fits are not best-first-exact and differ "
        "slightly from the native engine's trees (bench AUC delta ~0.001, "
        "docs/perf_histogram.md). Set leafBatch=1 for the exact sequential "
        "algorithm (~4x slower), or leafBatchRatio=1.0 to keep batching "
        "only for exact gain ties. >1 costs ~one pass via the panel kernel",
        default=8, converter=to_int, validator=gt(0),
    )
    leafBatchRatio = Param(
        "Only batch leaves whose gain >= ratio * pass-best (0 = off; 1.0 "
        "reproduces exact best-first; ~0.2 measured to IMPROVE holdout AUC "
        "past both exact best-first and the CPU engine at ~20% extra fit "
        "time — docs/perf_histogram.md)",
        default=0.0, converter=to_float, validator=in_range(0, 1),
    )
    useQuantizedGrad = Param(
        "LightGBM's gradient-quantization training (use_quantized_grad): "
        "stochastically round g/h to an 8-bit per-tree grid so the "
        "histogram pass runs on the integer MXU (~15% faster fits at the "
        "bench shape, docs/perf_histogram.md). Per-bin sums stay unbiased "
        "and counts exact; off (default) keeps bit-exact bf16 stats. "
        "Requires the precomputed-U path (single-device, maxBin <= 255, U "
        "within the HBM budget) and < 2^24 rows (f32 count exactness) — "
        "otherwise training logs a warning and proceeds with exact stats. "
        "Depthwise fits with depth >= 7 exceed the 128-slot U panel "
        "budget on deep levels (> 42 frontier nodes) and fall back "
        "per-level to exact histograms, logged once per fit",
        default=False, converter=to_bool,
    )
    featureBundling = Param(
        "Exclusive Feature Bundling (native enable_bundle): greedily pack "
        "(near-)mutually-exclusive features into shared bin columns at "
        "binning time. Shrinks K = sum_f bins_f — the HBM re-stream that "
        "bounds every histogram pass — and the column count, so sparse/"
        "one-hot matrices fit the precomputed-U budget at row counts that "
        "previously overflowed it. Splits, model text, SHAP, and "
        "prediction stay in original feature space (emitted models are "
        "indistinguishable from unbundled fits; with zero bundling "
        "conflicts the tree structure is identical). Off by default — the "
        "native engine defaults on, but bundled histogram g/h for a "
        "member's default bin are recovered by subtraction, so float "
        "leaf values can differ in the last ulp from an unbundled fit",
        default=False, converter=to_bool,
    )
    maxConflictRate = Param(
        "EFB conflict budget (native max_conflict_rate): fraction of "
        "sampled rows where two bundled features may be simultaneously "
        "non-default. 0.0 = only perfectly exclusive features bundle "
        "(lossless); small values (e.g. 0.05) bundle harder at a bounded "
        "accuracy cost on conflict rows",
        default=0.0, converter=to_float, validator=in_range(0, 1),
    )
    categoricalSlotIndexes = Param(
        "Feature indexes treated as categorical (value-identity bins + "
        "LightGBM sorted-set split search)",
        default=[], converter=to_list_int,
    )
    categoricalSlotNames = Param(
        "Feature names treated as categorical (resolved against the "
        "assembled feature names, e.g. 'f3')",
        default=[], converter=to_list_str,
    )
    maxCatThreshold = Param(
        "Max categories in a categorical split's left set",
        default=32, converter=to_int, validator=gt(0),
    )
    catSmooth = Param(
        "Smoothing for the categorical g/h bin ordering",
        default=10.0, converter=to_float, validator=ge(0),
    )
    catL2 = Param(
        "Extra L2 applied to categorical split gains",
        default=10.0, converter=to_float, validator=ge(0),
    )
    maxCatToOnehot = Param(
        "Categorical features with at most this many seen categories use "
        "the one-vs-rest split search instead of the sorted-set algorithm "
        "(native LightGBM max_cat_to_onehot)",
        default=4, converter=to_int, validator=gt(0),
    )
    minDataPerGroup = Param(
        "Minimal rows a category needs to enter the sorted-set split "
        "search (native LightGBM min_data_per_group; the one-vs-rest "
        "path is exempt)",
        default=100, converter=to_int, validator=gt(0),
    )
    boostFromAverage = Param(
        "Start boosting from the label average init score (false = from 0)",
        default=True, converter=to_bool,
    )
    isProvideTrainingMetric = Param(
        "Record the train-set metric each iteration (evals['training'])",
        default=False, converter=to_bool,
    )
    numBatches = Param("Split training into sequential batches (0=off)", default=0, converter=to_int, validator=ge(0))
    modelString = Param("Warm-start booster string", default="", converter=to_str)
    verbosity = Param("Verbosity", default=-1, converter=to_int)
    seed = Param("Master seed", default=0, converter=to_int)
    featuresShapCol = Param("Output column for SHAP values ('' = off)", default="", converter=to_str)
    leafPredictionCol = Param("Output column for leaf indices ('' = off)", default="", converter=to_str)
    useSingleDatasetMode = Param("Accepted for API parity (dataset is always host-resident)", default=True, converter=to_bool)
    numTasks = Param("Override number of mesh shards (0 = all devices)", default=0, converter=to_int, validator=ge(0))
    numExecutors = Param(
        "Run the histogram-binning prepass as partitioned tasks on this "
        "many fault-tolerant executors (mmlspark_tpu.runtime): bounded "
        "retries, heartbeat-loss re-dispatch, and lineage recompute apply, "
        "and the binned matrix is bit-identical to the inline pass. 0 "
        "(default) bins inline; an ambient runtime.policy() also activates "
        "the scheduler",
        default=0, converter=to_int, validator=ge(0),
    )
    numProcesses = Param(
        "Run the fit itself across this many real worker processes under a "
        "supervised gang (mmlspark_tpu.runtime.procgroup): each process "
        "fits a contiguous row shard, histograms allreduce over sockets, "
        "and a process killed mid-fit triggers gang recovery that resumes "
        "from the fit journal with zero re-execution of committed "
        "iterations. The distributed analog of the reference's "
        "per-executor native fit. 0/1 (default) fits in-process. Process "
        "mode restricts options (no bagging/GOSS/dart, no validation "
        "sets); see lightgbm.procfit.validate_process_options",
        default=0, converter=to_int, validator=ge(0),
    )

    def _objective_name(self) -> str:
        raise NotImplementedError

    def _extra_train_options(self) -> dict:
        return {}

    def _make_options(self, num_class: int = 1) -> TrainOptions:
        kwargs = dict(
            objective=self._objective_name(),
            num_iterations=self.getNumIterations(),
            learning_rate=self.getLearningRate(),
            num_leaves=self.getNumLeaves(),
            max_depth=self.getMaxDepth(),
            max_bin=self.getMaxBin(),
            lambda_l1=self.getLambdaL1(),
            lambda_l2=self.getLambdaL2(),
            min_data_in_leaf=self.getMinDataInLeaf(),
            min_sum_hessian_in_leaf=self.getMinSumHessianInLeaf(),
            min_gain_to_split=self.getMinGainToSplit(),
            bagging_fraction=self.getBaggingFraction(),
            pos_bagging_fraction=self.getPosBaggingFraction(),
            neg_bagging_fraction=self.getNegBaggingFraction(),
            bagging_freq=self.getBaggingFreq(),
            feature_fraction=self.getFeatureFraction(),
            max_delta_step=self.getMaxDeltaStep(),
            num_class=num_class,
            boosting_type=self.getBoostingType(),
            metric=self.getMetric() or None,
            early_stopping_round=self.getEarlyStoppingRound(),
            improvement_tolerance=self.getImprovementTolerance(),
            seed=self.getSeed(),
            growth=self.getGrowthPolicy(),
            leaf_batch=self.getLeafBatch(),
            leaf_batch_ratio=self.getLeafBatchRatio(),
            use_quantized_grad=self.getUseQuantizedGrad(),
            tree_learner=(
                "voting_parallel"
                if self.getParallelism() == "voting_parallel"
                else "data_parallel"
            ),
            top_k=self.getTopK(),
            top_rate=self.getTopRate(),
            other_rate=self.getOtherRate(),
            drop_rate=self.getDropRate(),
            max_cat_threshold=self.getMaxCatThreshold(),
            cat_smooth=self.getCatSmooth(),
            cat_l2=self.getCatL2(),
            max_cat_to_onehot=self.getMaxCatToOnehot(),
            min_data_per_group=self.getMinDataPerGroup(),
            boost_from_average=self.getBoostFromAverage(),
            provide_training_metric=self.getIsProvideTrainingMetric(),
        )
        kwargs.update(self._extra_train_options())
        return TrainOptions(**kwargs)


def extract_features(table: Table, features_col: str, num_features: int = 0):
    """Dense (N, F) float64 — or a :class:`CSRMatrix` when the column holds
    per-row (indices, values) sparse tuples (the
    ``LGBM_DatasetCreateFromCSRSpark`` ingest path,
    LightGBMUtils.scala:246-266). ``num_features`` pins the sparse feature
    count (pass the trained F at predict/valid time so a batch whose highest
    explicit index is smaller does not silently shrink the matrix)."""
    from mmlspark_tpu.data.sparse import csr_column_to_matrix, is_sparse_column

    feats = table.column(features_col)
    if feats.dtype == object:
        if is_sparse_column(feats):
            return csr_column_to_matrix(feats, num_features=num_features)
        feats = np.stack([np.asarray(row, dtype=np.float64) for row in feats])
    return np.asarray(feats, dtype=np.float64)


class LightGBMBase(LightGBMParams, Estimator):
    """Shared fit flow (LightGBMBase.scala:26-213)."""

    def _num_classes(self, y: np.ndarray) -> int:
        return 1

    def _adjust_weights(self, y: np.ndarray, w):
        """Label-dependent weight hook (isUnbalance lives in the classifier)."""
        return w

    def _select_mesh(self):
        """Mesh selection = the ClusterUtil worker-count computation
        (LightGBMBase.scala:166-176): all devices on the data axis unless
        `numTasks` caps it or parallelism is serial."""
        import jax

        if self.getParallelism() == "serial":
            return None
        n = len(jax.devices())
        if self.getNumTasks() > 0:
            n = min(n, self.getNumTasks())
        if n <= 1:
            return None
        from mmlspark_tpu.parallel.mesh import best_mesh

        return best_mesh(n)

    def _prepare(self, table: Table, num_features: int = 0):
        X = extract_features(table, self.getFeaturesCol(), num_features)
        y = np.asarray(table.column(self.getLabelCol()), dtype=np.float64)
        w = None
        if self.isSet("weightCol"):
            w = np.asarray(table.column(self.getWeightCol()), dtype=np.float64)
        init = None
        if self.isSet("initScoreCol"):
            init = np.asarray(table.column(self.getInitScoreCol()), dtype=np.float64)
        return X, y, w, init

    def set_delegate(self, *callbacks) -> "LightGBMBase":
        """Attach training delegates
        (:class:`~mmlspark_tpu.lightgbm.callbacks.TrainingCallback`) — the
        ``LightGBMDelegate.scala`` hook surface. Delegates are live objects,
        not Params: they do not serialize with the stage (matching the
        reference, whose delegate is a transient field)."""
        self._callbacks = list(callbacks)
        return self

    @property
    def callbacks(self):
        return list(getattr(self, "_callbacks", []))

    def _bin_dataset(self, X, opts, cat_slots):
        """Histogram-discretize the training matrix. With `numExecutors` > 0
        or an ambient :func:`mmlspark_tpu.runtime.policy`, the per-row pass
        runs as partitioned tasks on the fault-tolerant scheduler — the
        Spark analog of binning inside executors — and is bit-identical to
        the inline path (apply_bins is row-pure). Scheduler metrics land on
        ``self._runtime_metrics`` for inspection."""
        kwargs = dict(
            max_bin=opts.max_bin,
            categorical_features=sorted(cat_slots) or None,
            sample_cnt=self.getBinSampleCount(),
            max_bin_by_feature=self.getMaxBinByFeature() or None,
            # EFB is a histogram-layout optimization; the voting reducer
            # ships per-feature vote sets in original ids, so bundling is
            # gated to the non-voting learners.
            feature_bundling=(
                self.getFeatureBundling()
                and self.getParallelism() != "voting_parallel"
            ),
            max_conflict_rate=self.getMaxConflictRate(),
        )
        from mmlspark_tpu import runtime
        from mmlspark_tpu.native import native_available

        ambient = runtime.current_policy()
        inline = ambient is None and self.getNumExecutors() <= 0
        # exactly one span a fit, whichever branch bins
        with get_tracer().span(
            "lightgbm.binning", rows=int(getattr(X, "shape", (0,))[0]),
            path="partitioned" if not inline
            else "native" if native_available() else "numpy",
        ):
            if inline:
                return bin_dataset(X, **kwargs)
            from mmlspark_tpu.lightgbm.binning import bin_dataset_partitioned

            pol = ambient or runtime.SchedulerPolicy(
                max_workers=self.getNumExecutors(), seed=self.getSeed()
            )
            # durable binning: under MMLSPARK_TPU_CHECKPOINT_DIR each
            # partition's binned block checkpoints as it completes, so a
            # killed fit rerun with the same params + data resumes with zero
            # re-execution of finished partitions
            journal_root = journal_key = None
            ckpt_root = runtime.default_checkpoint_dir()
            if ckpt_root is not None:
                import os

                journal_root = os.path.join(ckpt_root, "binning")
                journal_key = self._checkpoint_key(X, kwargs)
            self._runtime_metrics = runtime.RuntimeMetrics()
            bins, mapper = bin_dataset_partitioned(
                X, policy=pol, metrics=self._runtime_metrics,
                journal_root=journal_root, journal_key=journal_key, **kwargs
            )
            self._runtime_metrics.log(prefix="binning: ")
            return bins, mapper

    def _checkpoint_key(self, X, bin_kwargs: dict) -> str:
        """Identity of one durable fit: estimator class + binning params +
        a data fingerprint (shape + content CRC). A rerun with identical
        inputs resumes; any change lands in a fresh journal directory."""
        import zlib

        arr = np.ascontiguousarray(np.asarray(X, dtype=np.float64))
        crc = zlib.crc32(arr.view(np.uint8).reshape(-1)) & 0xFFFFFFFF
        parts = [type(self).__name__, f"seed{self.getSeed()}"]
        parts += [f"{k}={bin_kwargs[k]}" for k in sorted(bin_kwargs)]
        parts.append(f"X{arr.shape[0]}x{arr.shape[1] if arr.ndim > 1 else 1}")
        parts.append(f"{crc:08x}")
        return "-".join(parts)

    def _fit(self, table: Table) -> "LightGBMModelBase":
        """One ``lightgbm.fit`` span (``observability/tracing``) roots the
        fit's trace; its children are ``lightgbm.prepare`` (Table to float
        arrays, options, slots), ``lightgbm.binning`` (:meth:`_bin_dataset`),
        the four that :func:`~mmlspark_tpu.lightgbm.train.train` opens
        (``upload``, ``program``, ``u_build``, ``boost``) and
        ``lightgbm.pack`` (the Booster's arrays in ``train``, the model and
        its commit here). How many there are depends on the fit's shape,
        never on ``numIterations``."""
        tracer = get_tracer()
        # The root takes the manual form: in the ring, ambient for the fit
        # (so every span below is its child), but not mirrored into a
        # profiler session. A trace reduction names an idle gap by the
        # annotation that overlaps it most, and an annotation around the
        # whole fit would own every gap its children are there to name.
        root = tracer.start_span("lightgbm.fit", iterations=self.getNumIterations())
        status = "ok"
        try:
            with tracer.attach(root):
                return self._fit_traced(table, root)
        except BaseException as e:
            status = type(e).__name__
            raise
        finally:
            tracer.finish(root, status=status)

    def _fit_traced(self, table: Table, root) -> "LightGBMModelBase":
        tracer = get_tracer()
        with tracer.span("lightgbm.prepare", rows=table.num_rows):
            # Validation split by indicator column (LightGBMBase.scala:196-197).
            valid_table = None
            if self.isSet("validationIndicatorCol"):
                ind = np.asarray(table.column(self.getValidationIndicatorCol()), dtype=bool)
                valid_table, table = table.filter(ind), table.filter(~ind)

            warm = self.getModelString()
            prev = Booster.from_string(warm) if warm else None
            # Warm start: pin sparse extraction to the previous booster's feature
            # count so its trees never gather past the new batch's explicit width.
            X, y, w, init = self._prepare(
                table, num_features=prev.num_features if prev else 0
            )
            w = self._adjust_weights(y, w)
            num_class = self._num_classes(y)
            opts = self._make_options(num_class)

            # Feature slot names: slotNames overrides the generated f0..fN
            # (LightGBMParams slotNames) and is the namespace categorical names
            # resolve against.
            num_features = X.shape[1] if hasattr(X, "shape") else X.num_features
            slot_names = self.getSlotNames() or []
            if slot_names and len(slot_names) != num_features:
                raise ValueError(
                    f"slotNames has {len(slot_names)} entries for "
                    f"{num_features} features"
                )
            feature_names = list(slot_names) or [f"f{i}" for i in range(num_features)]

            # Categorical slot resolution (LightGBMBase.scala:148-156): indexes
            # union names resolved against the feature slot names.
            cat_slots = set(self.getCategoricalSlotIndexes() or [])
            names = self.getCategoricalSlotNames() or []
            bad = sorted(i for i in cat_slots if not (0 <= i < num_features))
            if bad:
                raise ValueError(
                    f"categoricalSlotIndexes out of range for {num_features} "
                    f"features: {bad}"
                )
            if names:
                name_to_idx = {nm: i for i, nm in enumerate(feature_names)}
                for nm in names:
                    if nm not in name_to_idx:
                        raise ValueError(
                            f"categoricalSlotNames: unknown feature name {nm!r}"
                        )
                    cat_slots.add(name_to_idx[nm])
        root.tags.update(rows=int(len(y)), features=int(num_features))

        bins, mapper = self._bin_dataset(X, opts, cat_slots)
        valid_sets = []
        if valid_table is not None and valid_table.num_rows > 0:
            Xv, yv, wv, _ = self._prepare(valid_table, num_features=X.shape[1])
            bv, _ = bin_dataset(Xv, mapper=mapper)
            valid_sets.append(("valid_0", bv, yv, wv))

        mesh = self._select_mesh()
        init_margins = None
        if init is not None:
            init_margins = np.asarray(init, dtype=np.float32)
            if init_margins.ndim == 1:
                init_margins = init_margins[:, None]
        if prev is not None:
            init_margins = prev.raw_margin(X)

        num_batches = self.getNumBatches()
        num_processes = self.getNumProcesses()
        if num_processes > 1:
            result = self._fit_process_group(
                bins, y, w, init_margins, opts, mapper, valid_sets,
                feature_names, num_processes, num_batches, X,
            )
        elif num_batches and num_batches > 1:
            result = self._fit_batches(
                bins, y, w, init_margins, opts, mapper, mesh, valid_sets, feature_names,
                num_batches,
            )
        else:
            result = train(
                bins, y, opts, w=w, init_margins=init_margins,
                valid_sets=valid_sets, mapper=mapper, mesh=mesh,
                feature_names=feature_names, callbacks=self.callbacks,
            )
        with tracer.span(
            "lightgbm.pack", trees=int(result.booster.num_trees)
        ):
            model = self._make_model(result)
            model.parent = self
            # per-iteration metric histories (valid sets + 'training' when
            # isProvideTrainingMetric) — transient, like the reference's
            # delegate-observed metrics
            model._train_evals = result.evals
            from mmlspark_tpu.observability.events import ModelCommitted, get_bus

            # durable model commit: atomic-rename versioned write under the
            # checkpoint root, so a warm-restarting server's recovery scan
            # (ModelStore.latest) never observes a torn model file
            version = None
            from mmlspark_tpu.runtime.journal import ModelStore, default_checkpoint_dir

            ckpt_root = default_checkpoint_dir()
            if ckpt_root is not None:
                import os

                store = ModelStore(os.path.join(ckpt_root, "models"))
                version = store.commit(
                    model.get_model_string(), name=type(model).__name__.lower()
                )
            bus = get_bus()
            if bus.active:
                detail = (
                    f"{result.booster.num_trees} trees"
                    if getattr(result, "booster", None) is not None else ""
                )
                if version is not None:
                    detail = f"{detail} v{version}".strip()
                bus.publish(ModelCommitted(
                    model=type(model).__name__, detail=detail,
                ))
        return model

    def _fit_process_group(
        self, bins, y, w, init_margins, opts, mapper, valid_sets,
        feature_names, num_processes, num_batches, X,
    ) -> TrainResult:
        """`numProcesses` > 1: hand the fit to a supervised worker gang
        (:func:`mmlspark_tpu.lightgbm.procfit.fit_process_group`). The
        feature combinations a shard-local process cannot reproduce are
        rejected up front rather than silently diverging."""
        from mmlspark_tpu.lightgbm.procfit import fit_process_group

        if num_batches and num_batches > 1:
            raise ValueError("numProcesses and numBatches are exclusive")
        if valid_sets:
            raise ValueError(
                "process-parallel fit does not support validation sets "
                "(validation is driver-side; score the model after fit)"
            )
        if init_margins is not None:
            raise ValueError(
                "process-parallel fit does not support initScoreCol or "
                "modelString warm start"
            )
        if self.callbacks:
            raise ValueError(
                "training delegates cannot cross the process boundary; "
                "unset delegates or numProcesses"
            )
        journal_root = journal_key = None
        from mmlspark_tpu.runtime.journal import default_checkpoint_dir

        ckpt_root = default_checkpoint_dir()
        if ckpt_root is not None:
            import os

            journal_root = os.path.join(ckpt_root, "procfit")
            journal_key = self._checkpoint_key(
                X, {"procs": num_processes, "iters": opts.num_iterations}
            )
        result = fit_process_group(
            None, y, opts, w=w, num_processes=num_processes,
            feature_names=feature_names, bins=bins, mapper=mapper,
            journal_root=journal_root,
            journal_key=journal_key or "procfit",
        )
        self._process_fit = result  # epochs/exit statuses for inspection
        return TrainResult(
            booster=result.booster, evals={}, best_iteration=-1
        )

    def _fit_batches(
        self, bins, y, w, init_margins, opts, mapper, mesh, valid_sets,
        feature_names, num_batches,
    ) -> TrainResult:
        """Batch-mode training: boosters chained across row batches with
        margin carry-over (LightGBMBase.scala:26-48)."""
        n = len(y)
        edges = np.linspace(0, n, num_batches + 1).astype(int)
        boosters: List[Booster] = []
        merged_evals: dict = {}
        result = None
        for bi in range(num_batches):
            lo, hi = edges[bi], edges[bi + 1]
            if hi <= lo:
                continue
            im = None if init_margins is None else init_margins[lo:hi]
            if boosters:
                # margins of previous ensemble on this batch's rows
                im = _ensemble_margin(boosters, bins[lo:hi], mapper)
            result = train(
                bins[lo:hi], y[lo:hi], opts,
                w=None if w is None else w[lo:hi],
                init_margins=im, valid_sets=valid_sets, mapper=mapper, mesh=mesh,
                feature_names=feature_names,
            )
            boosters.append(result.booster)
            # metric histories concatenate across the chained batches (each
            # batch's scores are its delta booster on its own rows)
            for name, metrics in result.evals.items():
                dst = merged_evals.setdefault(name, {})
                for mname, scores in metrics.items():
                    dst.setdefault(mname, []).extend(scores)
        merged = _merge_boosters(boosters)
        return TrainResult(booster=merged, evals=merged_evals, best_iteration=result.best_iteration)

    def _make_model(self, result: TrainResult) -> "LightGBMModelBase":
        raise NotImplementedError


def _ensemble_margin(boosters: List[Booster], bins: np.ndarray, mapper: BinMapper) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.lightgbm.train import _bundle_route_consts, _route_binned

    spec = getattr(mapper, "bundles", None)
    consts = _bundle_route_consts(spec) if spec is not None else None
    total = None
    for b in boosters:
        # Route in bin space (bins built with the shared mapper; EFB-packed
        # when the mapper carries a bundle plan — trees are in original ids).
        def margin_fn(bv):
            m = jnp.broadcast_to(
                jnp.asarray(b.init_score)[None, :], (bv.shape[0], b.num_classes)
            )
            for t in range(b.num_trees):
                leaf = _route_binned(
                    bv,
                    jnp.asarray(b.split_feature[t]),
                    jnp.asarray(b.split_bin[t]),
                    jnp.asarray(b.left_child[t]),
                    jnp.asarray(b.right_child[t]),
                    jnp.asarray(b.is_leaf[t]),
                    b.max_depth,
                    cat_node=(
                        None if b.cat_nodes is None
                        else jnp.asarray(b.cat_nodes[t])
                    ),
                    cat_mask=(
                        None if b.cat_masks is None
                        else jnp.asarray(b.cat_masks[t])
                    ),
                    bundle_consts=consts,
                )
                m = m.at[:, t % b.num_classes].add(jnp.asarray(b.leaf_values[t])[leaf])
            return m

        m = np.asarray(jax.jit(margin_fn)(jnp.asarray(bins, dtype=jnp.int32)))
        total = m if total is None else total + m
    return total


def _merge_boosters(boosters: List[Booster]) -> Booster:
    """Concatenate chained batch boosters into one additive model
    (the `LGBM_BoosterMerge` analogue, TrainUtils.scala:165-167)."""
    if len(boosters) == 1:
        return boosters[0]
    first = boosters[0]

    def cat(field, pad=0):
        arrs = [getattr(b, field) for b in boosters]
        if any(a is None for a in arrs):
            return None
        arrs = [np.asarray(a) for a in arrs]
        # Pad trailing (node/bitmask) axes to the widest booster before
        # stacking trees: a model-text round-trip shrinks node arrays to
        # each tree's true width, so chained-fit boosters legitimately
        # disagree on M. Dead slots are unreachable (child indices only
        # point inside the original tree); is_leaf pads True so even an
        # accidental visit terminates.
        ndim = arrs[0].ndim
        target = tuple(max(a.shape[d] for a in arrs) for d in range(1, ndim))
        padded = []
        for a in arrs:
            widths = [(0, 0)] + [
                (0, t - a.shape[d + 1]) for d, t in enumerate(target)
            ]
            if any(w for _, w in widths):
                a = np.pad(a, widths, constant_values=pad)
            padded.append(a)
        return np.concatenate(padded)

    return Booster(
        split_feature=cat("split_feature"),
        split_bin=cat("split_bin"),
        split_threshold=cat("split_threshold"),
        left_child=cat("left_child"),
        right_child=cat("right_child"),
        is_leaf=cat("is_leaf", pad=1),
        leaf_values=cat("leaf_values"),
        cover=cat("cover"),
        split_gain=cat("split_gain"),
        init_score=first.init_score,
        num_classes=first.num_classes,
        objective=first.objective,
        max_depth=max(b.max_depth for b in boosters),
        best_iteration=-1,
        feature_names=first.feature_names,
        bin_edges=first.bin_edges,
        nan_left=cat("nan_left"),
        zero_missing=cat("zero_missing"),
        cat_nodes=cat("cat_nodes"),
        cat_masks=cat("cat_masks"),
        cat_values=first.cat_values,
    )


class LightGBMModelBase(HasFeaturesCol, HasPredictionCol, Model):
    """Shared model surface: booster access, native-model serde, leaf output."""

    boosterData = Param("Fitted booster state", is_complex=True)
    leafPredictionCol = Param("Output column for leaf indices ('' = off)", default="", converter=to_str)
    featuresShapCol = Param("Output column for SHAP values ('' = off)", default="", converter=to_str)

    @property
    def booster(self) -> Booster:
        return Booster.from_dict(self.getBoosterData())

    def set_booster(self, booster: Booster) -> None:
        self.set("boosterData", booster.to_dict())

    def get_model_string(self) -> str:
        return self.booster.model_to_string()

    def save_native_model(self, path: str) -> None:
        """`saveNativeModel` (LightGBMClassifier.scala:172-180)."""
        with open(path, "w") as f:
            f.write(self.get_model_string())

    @classmethod
    def from_model_string(cls, text: str, **kwargs) -> "LightGBMModelBase":
        """Build a model from native model text — the loader a
        warm-restarting server hands to
        :func:`mmlspark_tpu.serving.recover_model`."""
        m = cls(**kwargs)
        m.set_booster(Booster.from_string(text))
        return m

    @classmethod
    def load_native_model(cls, path: str, **kwargs) -> "LightGBMModelBase":
        with open(path) as f:
            return cls.from_model_string(f.read(), **kwargs)

    def get_feature_importances(self, importance_type: str = "split") -> np.ndarray:
        return self.booster.feature_importances(importance_type)

    def _with_leaf_col(self, table: Table, X: np.ndarray) -> Table:
        if self.getLeafPredictionCol():
            leaves = self.booster.predict_leaf(X).astype(np.float64)
            table = table.with_column(self.getLeafPredictionCol(), leaves)
        if self.getFeaturesShapCol():
            # (N, C, F+1) → (N, C*(F+1)) — LightGBM's contrib layout: per
            # class, per-feature contributions then the bias term
            # (LightGBMBooster.scala:240-275 featuresShap).
            shap = self.booster.features_shap(X)
            n = shap.shape[0]
            table = table.with_column(
                self.getFeaturesShapCol(), shap.reshape(n, -1).astype(np.float64)
            )
        return table
