"""Per-stage test fixtures for the fuzzing meta-suite.

The reference's ``Fuzzing.scala`` traits require every exported stage to
provide ``testObjects()`` — a stage instance plus fit/transform frames —
and ``FuzzingTest.scala:27-197`` reflectively asserts no stage escapes
coverage. Same contract: every concrete public PipelineStage subclass must
appear in TEST_OBJECTS, be named as a fixture's ``fit_produces`` model, or
carry an EXEMPT entry with a reason. ``tests/test_fuzzing.py`` enforces it.

Fixtures are zero-arg callables so stage/table construction stays lazy.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import numpy as np

from mmlspark_tpu.data.table import Table


@dataclasses.dataclass
class TestObject:
    stage: Any
    table: Table
    transform_table: Optional[Table] = None  # defaults to `table`
    check_transform: bool = True  # False: construct/serde only (needs a live server)
    fit_produces: Optional[str] = None  # qualname of the model class fit() returns


def _rng(seed=0):
    return np.random.default_rng(seed)


def _numeric_table(n=40, f=4, seed=0):
    rng = _rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] > 0).astype(np.float64)
    return Table({"features": X, "label": y})


def _mixed_table():
    rng = _rng(1)
    n = 30
    return Table(
        {
            "num": rng.normal(size=n),
            "cat": np.array([["red", "green", "blue"][i % 3] for i in range(n)], dtype=object),
            "label": (rng.random(n) > 0.5).astype(np.float64),
        }
    )


def _image_table():
    rng = _rng(2)
    images = np.empty(3, dtype=object)
    for i in range(3):
        images[i] = rng.integers(0, 256, size=(16, 16, 3), dtype=np.uint8)
    return Table({"id": np.arange(3), "image": images})


def _text_table():
    return Table(
        {
            "text": np.array(
                ["the quick brown fox", "jumps over the dog", "hello world again"],
                dtype=object,
            ),
            "label": np.array([1.0, 0.0, 1.0]),
        }
    )


def _events_table():
    users, items = [], []
    for u, its in [(0, [0, 1, 2]), (1, [0, 1, 2]), (2, [3, 4]), (3, [3, 4, 0])]:
        for i in its:
            users.append(u)
            items.append(i)
    return Table(
        {
            "user": np.array(users, dtype=np.int64),
            "item": np.array(items, dtype=np.int64),
            "rating": np.ones(len(users)),
        }
    )


def _http_request_table():
    req = np.empty(2, dtype=object)
    req[0] = {"url": "http://localhost:1/x", "method": "GET"}
    req[1] = {"url": "http://localhost:1/y", "method": "GET"}
    return Table({"req": req, "payload": np.array(["a", "b"], dtype=object)})


def _dnn_apply(params, inputs):
    x = inputs["x"] if isinstance(inputs, dict) else inputs
    return {"y": x * 2.0}


from mmlspark_tpu.core.params import Param as _Param
from mmlspark_tpu.core.params import to_str as _to_str
from mmlspark_tpu.core.pipeline import Transformer as _Transformer


class _FuzzLinearModel(_Transformer):
    """Inner model for LIME fixtures: y = x @ w. State lives in Params so
    the stage-serializer (save_stage persists params only) roundtrips it."""

    weights = _Param("weight vector", is_complex=True, default=None)
    col = _Param("input column", default="features", converter=_to_str)

    def __init__(self, w=None, **kw):
        super().__init__(**kw)
        if w is not None:
            self.set("weights", np.asarray(w, dtype=np.float64))

    def transform(self, table):
        w = np.asarray(self.getWeights(), dtype=np.float64)
        X = np.asarray(
            [np.asarray(r, dtype=np.float64).ravel() for r in table.column(self.getCol())]
        )
        X = X[:, : len(w)]
        return table.with_column("prediction", X @ w)


class _FuzzImageModel(_Transformer):
    """ImageLIME inner model: mean intensity per image."""

    col = _Param("input column", default="image", converter=_to_str)

    def transform(self, table):
        scores = np.asarray(
            [float(np.asarray(x, dtype=np.float64).mean()) for x in table.column(self.getCol())]
        )
        return table.with_column("prediction", scores)


def _udf_double(c):
    return c * 2


def _lambda_fn(t):
    return t.with_column("twice", t.column("num") * 2)


def _custom_in(row):
    return {"url": "http://localhost:1/z", "method": "GET", "body": str(row)}


def _custom_out(resp):
    return str(resp)


def _make_test_objects() -> Dict[str, Callable[[], TestObject]]:
    reg: Dict[str, Callable[[], TestObject]] = {}

    def add(qualname: str, fn: Callable[[], TestObject]):
        reg[qualname] = fn

    # --- lightgbm -----------------------------------------------------------
    def lgbm_clf():
        from mmlspark_tpu.lightgbm import LightGBMClassifier

        return TestObject(
            LightGBMClassifier(numIterations=5, numLeaves=5, parallelism="serial"),
            _numeric_table(),
            fit_produces="mmlspark_tpu.lightgbm.classifier.LightGBMClassificationModel",
        )

    add("mmlspark_tpu.lightgbm.classifier.LightGBMClassifier", lgbm_clf)

    def lgbm_reg():
        from mmlspark_tpu.lightgbm import LightGBMRegressor

        t = _numeric_table(seed=3)
        t = t.with_column("label", t.column("features")[:, 0] * 2.0)
        return TestObject(
            LightGBMRegressor(numIterations=5, numLeaves=5, parallelism="serial"),
            t,
            fit_produces="mmlspark_tpu.lightgbm.regressor.LightGBMRegressionModel",
        )

    add("mmlspark_tpu.lightgbm.regressor.LightGBMRegressor", lgbm_reg)

    def lgbm_ranker():
        from mmlspark_tpu.lightgbm import LightGBMRanker

        rng = _rng(4)
        n = 24
        t = Table(
            {
                "features": rng.normal(size=(n, 3)),
                "label": rng.integers(0, 3, size=n).astype(np.float64),
                "group": np.repeat(np.arange(4), 6),
            }
        )
        return TestObject(
            LightGBMRanker(
                numIterations=4, numLeaves=5, groupCol="group", parallelism="serial"
            ),
            t,
            fit_produces="mmlspark_tpu.lightgbm.ranker.LightGBMRankerModel",
        )

    add("mmlspark_tpu.lightgbm.ranker.LightGBMRanker", lgbm_ranker)

    # --- vw -----------------------------------------------------------------
    def vw_clf():
        from mmlspark_tpu.vw import VowpalWabbitClassifier

        return TestObject(
            VowpalWabbitClassifier(numPasses=1),
            _numeric_table(seed=5),
            fit_produces="mmlspark_tpu.vw.classifier.VowpalWabbitClassificationModel",
        )

    add("mmlspark_tpu.vw.classifier.VowpalWabbitClassifier", vw_clf)

    def vw_reg():
        from mmlspark_tpu.vw import VowpalWabbitRegressor

        t = _numeric_table(seed=6)
        t = t.with_column("label", t.column("features")[:, 0])
        return TestObject(
            VowpalWabbitRegressor(numPasses=1),
            t,
            fit_produces="mmlspark_tpu.vw.regressor.VowpalWabbitRegressionModel",
        )

    add("mmlspark_tpu.vw.regressor.VowpalWabbitRegressor", vw_reg)

    def vw_feat():
        from mmlspark_tpu.vw import VowpalWabbitFeaturizer

        return TestObject(
            VowpalWabbitFeaturizer(inputCols=["text"], outputCol="features", stringSplit=True),
            _text_table(),
        )

    add("mmlspark_tpu.vw.featurizer.VowpalWabbitFeaturizer", vw_feat)

    def vw_inter():
        from mmlspark_tpu.vw import VowpalWabbitFeaturizer, VowpalWabbitInteractions

        t = _text_table()
        t = VowpalWabbitFeaturizer(inputCols=["text"], outputCol="fa", numBits=10, stringSplit=True).transform(t)
        t = VowpalWabbitFeaturizer(inputCols=["label"], outputCol="fb", numBits=10).transform(t)
        return TestObject(
            VowpalWabbitInteractions(inputCols=["fa", "fb"], outputCol="cross", numBits=10),
            t,
        )

    add("mmlspark_tpu.vw.interactions.VowpalWabbitInteractions", vw_inter)

    # --- featurize ----------------------------------------------------------
    def clean():
        from mmlspark_tpu.featurize import CleanMissingData

        rng = _rng(7)
        a = rng.normal(size=20)
        a[::4] = np.nan
        return TestObject(
            CleanMissingData(inputCols=["a"], cleaningMode="Mean"),
            Table({"a": a}),
            fit_produces="mmlspark_tpu.featurize.clean.CleanMissingDataModel",
        )

    add("mmlspark_tpu.featurize.clean.CleanMissingData", clean)

    def conv():
        from mmlspark_tpu.featurize import DataConversion

        return TestObject(
            DataConversion(inputCols=["x"], convertTo="double"),
            Table({"x": np.arange(5, dtype=np.int64)}),
        )

    add("mmlspark_tpu.featurize.conversion.DataConversion", conv)

    def assemble():
        from mmlspark_tpu.featurize import AssembleFeatures

        return TestObject(
            AssembleFeatures(inputCols=["num", "label"]),
            _mixed_table(),
            fit_produces="mmlspark_tpu.featurize.featurize.FeaturizeModel",
        )

    add("mmlspark_tpu.featurize.featurize.AssembleFeatures", assemble)

    def featurize():
        from mmlspark_tpu.featurize import Featurize

        return TestObject(
            Featurize(inputCols=["num", "cat"], outputCol="features"),
            _mixed_table(),
            fit_produces="mmlspark_tpu.featurize.featurize.FeaturizeModel",
        )

    add("mmlspark_tpu.featurize.featurize.Featurize", featurize)

    def value_indexer():
        from mmlspark_tpu.featurize import ValueIndexer

        return TestObject(
            ValueIndexer(inputCol="cat", outputCol="idx"),
            _mixed_table(),
            fit_produces="mmlspark_tpu.featurize.indexers.ValueIndexerModel",
        )

    add("mmlspark_tpu.featurize.indexers.ValueIndexer", value_indexer)

    def index_to_value():
        from mmlspark_tpu.featurize import ValueIndexer, IndexToValue

        t = _mixed_table()
        t2 = ValueIndexer(inputCol="cat", outputCol="idx").fit(t).transform(t)
        return TestObject(IndexToValue(inputCol="idx", outputCol="orig"), t2)

    add("mmlspark_tpu.featurize.indexers.IndexToValue", index_to_value)

    def text_featurizer():
        from mmlspark_tpu.featurize import TextFeaturizer

        return TestObject(
            TextFeaturizer(inputCol="text", outputCol="features"),
            _text_table(),
            fit_produces="mmlspark_tpu.featurize.text.TextFeaturizerModel",
        )

    add("mmlspark_tpu.featurize.text.TextFeaturizer", text_featurizer)

    def multi_ngram():
        from mmlspark_tpu.featurize import MultiNGram

        t = _text_table()
        toks = np.empty(t.num_rows, dtype=object)
        for i, s in enumerate(t.column("text")):
            toks[i] = s.split()
        return TestObject(
            MultiNGram(inputCol="tokens", outputCol="grams", lengths=[1, 2]),
            t.with_column("tokens", toks),
        )

    add("mmlspark_tpu.featurize.text.MultiNGram", multi_ngram)

    def page_splitter():
        from mmlspark_tpu.featurize import PageSplitter

        return TestObject(
            PageSplitter(inputCol="text", outputCol="pages", maximumPageLength=10),
            _text_table(),
        )

    add("mmlspark_tpu.featurize.text.PageSplitter", page_splitter)

    # --- image --------------------------------------------------------------
    def image_transformer():
        from mmlspark_tpu.image import ImageTransformer

        return TestObject(
            ImageTransformer(inputCol="image", outputCol="out").resize(8, 8),
            _image_table(),
        )

    add("mmlspark_tpu.image.transforms.ImageTransformer", image_transformer)

    def image_augmenter():
        from mmlspark_tpu.image import ImageSetAugmenter

        return TestObject(
            ImageSetAugmenter(inputCol="image", outputCol="image"), _image_table()
        )

    add("mmlspark_tpu.image.transforms.ImageSetAugmenter", image_augmenter)

    def unroll():
        from mmlspark_tpu.image import UnrollImage

        return TestObject(UnrollImage(inputCol="image", outputCol="vec"), _image_table())

    add("mmlspark_tpu.image.unroll.UnrollImage", unroll)

    def image_featurizer():
        from mmlspark_tpu.image import ImageFeaturizer
        from mmlspark_tpu.models import init_resnet

        params = init_resnet(variant="resnet18", num_classes=4, small_inputs=True)
        return TestObject(
            ImageFeaturizer(
                inputCol="image", outputCol="features", modelParams=params,
                inputHeight=32, inputWidth=32, batchSize=2,
            ),
            _image_table(),
        )

    add("mmlspark_tpu.image.featurizer.ImageFeaturizer", image_featurizer)

    def lm_featurizer():
        import jax

        from mmlspark_tpu.featurize import LMFeaturizer
        from mmlspark_tpu.models import init_afmoe

        config = dict(
            hidden_size=32, num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            intermediate_size=48, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, num_dense_layers=1, sliding_window=4, rope_theta=10000,
            rms_norm_eps=1e-5, route_scale=2.0, vocab_size=64, layers=3, interpret=True,
            layer_types=["sliding_attention", "full_attention", "sliding_attention"],
        )
        params = jax.tree.map(np.asarray, init_afmoe(jax.random.PRNGKey(0), config))
        tokens = _rng(3).integers(0, 64, size=(3, 12)).astype(np.int32)
        return TestObject(
            LMFeaturizer(modelParams=params, modelConfig=config, batchSize=2),
            Table({"id": np.arange(3), "tokens": tokens}),
        )

    add("mmlspark_tpu.featurize.lm.LMFeaturizer", lm_featurizer)

    def lm_featurizer_latent():
        import jax

        from mmlspark_tpu.featurize import LMFeaturizer
        from mmlspark_tpu.models import init_mla_moe

        config = dict(
            model_type="joyai_llm_flash", hidden_size=32, num_attention_heads=2, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4, n_shared_experts=1,
            num_experts_per_tok=2, first_k_dense_replace=1, routed_scaling_factor=2.5,
            rope_theta=32000000, rms_norm_eps=1e-6, vocab_size=64, layers=2, interpret=True,
        )
        params = jax.tree.map(np.asarray, init_mla_moe(jax.random.PRNGKey(0), config))
        tokens = _rng(3).integers(0, 64, size=(3, 12)).astype(np.int32)
        return TestObject(
            LMFeaturizer(modelParams=params, modelConfig=config, batchSize=2),
            Table({"id": np.arange(3), "tokens": tokens}),
        )

    # a second fixture of one class: the part after '#' names the variant
    add("mmlspark_tpu.featurize.lm.LMFeaturizer#latent", lm_featurizer_latent)

    def superpixel():
        from mmlspark_tpu.lime import SuperpixelTransformer

        return TestObject(
            SuperpixelTransformer(inputCol="image", cellSize=8), _image_table()
        )

    add("mmlspark_tpu.lime.superpixel.SuperpixelTransformer", superpixel)

    # --- lime ---------------------------------------------------------------
    def tabular_lime():
        from mmlspark_tpu.lime import TabularLIME

        return TestObject(
            TabularLIME(
                model=_FuzzLinearModel(np.array([1.0, -1.0, 0.5, 0.0])),
                inputCol="features", outputCol="weights", nSamples=60, seed=1,
            ),
            _numeric_table(seed=8),
            fit_produces="mmlspark_tpu.lime.lime.TabularLIMEModel",
        )

    add("mmlspark_tpu.lime.lime.TabularLIME", tabular_lime)

    def image_lime():
        from mmlspark_tpu.lime import ImageLIME

        return TestObject(
            ImageLIME(
                model=_FuzzImageModel(), inputCol="image", outputCol="weights",
                nSamples=8, cellSize=8, seed=1,
            ),
            _image_table(),
        )

    add("mmlspark_tpu.lime.lime.ImageLIME", image_lime)

    # --- nn -----------------------------------------------------------------
    def knn():
        from mmlspark_tpu.nn import KNN

        rng = _rng(9)
        t = Table(
            {
                "features": rng.normal(size=(30, 4)),
                "values": np.arange(30).astype(np.float64),
            }
        )
        return TestObject(
            KNN(k=3, outputCol="matches"),
            t,
            fit_produces="mmlspark_tpu.nn.knn.KNNModel",
        )

    add("mmlspark_tpu.nn.knn.KNN", knn)

    def cknn():
        from mmlspark_tpu.nn import ConditionalKNN

        rng = _rng(10)
        labels = np.array([["a", "b"][i % 2] for i in range(30)], dtype=object)
        t = Table(
            {
                "features": rng.normal(size=(30, 4)),
                "values": np.arange(30).astype(np.float64),
                "labels": labels,
            }
        )
        q = Table(
            {
                "features": rng.normal(size=(5, 4)),
                "conditioner": np.array([["a"]] * 5, dtype=object),
            }
        )
        return TestObject(
            ConditionalKNN(k=2, labelCol="labels", outputCol="matches"),
            t,
            transform_table=q,
            fit_produces="mmlspark_tpu.nn.knn.ConditionalKNNModel",
        )

    add("mmlspark_tpu.nn.knn.ConditionalKNN", cknn)

    # --- isolation forest ---------------------------------------------------
    def iforest():
        from mmlspark_tpu.isolationforest import IsolationForest

        return TestObject(
            IsolationForest(numEstimators=10),
            _numeric_table(seed=11),
            fit_produces="mmlspark_tpu.isolationforest.forest.IsolationForestModel",
        )

    add("mmlspark_tpu.isolationforest.forest.IsolationForest", iforest)

    # --- recommendation -----------------------------------------------------
    def sar():
        from mmlspark_tpu.recommendation import SAR

        return TestObject(
            SAR(supportThreshold=1),
            _events_table(),
            fit_produces="mmlspark_tpu.recommendation.sar.SARModel",
        )

    add("mmlspark_tpu.recommendation.sar.SAR", sar)

    def rec_indexer():
        from mmlspark_tpu.recommendation import RecommendationIndexer

        t = Table(
            {
                "customer": np.array(["alice", "bob", "alice"], dtype=object),
                "product": np.array(["x", "y", "y"], dtype=object),
            }
        )
        return TestObject(
            RecommendationIndexer(
                userInputCol="customer", userOutputCol="user",
                itemInputCol="product", itemOutputCol="item",
            ),
            t,
            fit_produces="mmlspark_tpu.recommendation.ranking.RecommendationIndexerModel",
        )

    add("mmlspark_tpu.recommendation.ranking.RecommendationIndexer", rec_indexer)

    def ranking_adapter():
        from mmlspark_tpu.recommendation import RankingAdapter, SAR

        return TestObject(
            RankingAdapter(recommender=SAR(supportThreshold=1), k=2),
            _events_table(),
            fit_produces="mmlspark_tpu.recommendation.ranking.RankingAdapterModel",
        )

    add("mmlspark_tpu.recommendation.ranking.RankingAdapter", ranking_adapter)

    def ranking_tvs():
        from mmlspark_tpu.recommendation import (
            RankingEvaluator,
            RankingTrainValidationSplit,
            SAR,
        )

        return TestObject(
            RankingTrainValidationSplit(
                estimator=SAR(supportThreshold=1),
                evaluator=RankingEvaluator(k=2, nItems=5),
                trainRatio=0.6,
                seed=7,
            ),
            _events_table(),
            fit_produces="mmlspark_tpu.recommendation.ranking.RankingTrainValidationSplitModel",
        )

    add("mmlspark_tpu.recommendation.ranking.RankingTrainValidationSplit", ranking_tvs)

    # --- stages -------------------------------------------------------------
    def _words_table():
        return Table(
            {
                "num": np.arange(6, dtype=np.float64),
                "words": np.array(list("abcdef"), dtype=object),
                "label": np.array([0, 1, 0, 1, 0, 1], dtype=np.float64),
            }
        )

    simple = {
        "Cacher": lambda S: TestObject(S(), _words_table()),
        "DropColumns": lambda S: TestObject(S(cols=["num"]), _words_table()),
        "SelectColumns": lambda S: TestObject(S(cols=["num", "words"]), _words_table()),
        "RenameColumn": lambda S: TestObject(S(inputCol="words", outputCol="w2"), _words_table()),
        "Repartition": lambda S: TestObject(S(n=2), _words_table()),
        "StratifiedRepartition": lambda S: TestObject(S(labelCol="label"), _words_table()),
        "SummarizeData": lambda S: TestObject(S(), _words_table()),
        "UnicodeNormalize": lambda S: TestObject(S(inputCol="words", outputCol="norm"), _words_table()),
        "Explode": lambda S: TestObject(
            S(inputCol="vals"),
            Table({"vals": np.array([[1, 2], [3]], dtype=object)}),
        ),
        "UDFTransformer": lambda S: TestObject(
            S(inputCol="num", outputCol="n2", udf=_udf_double), _words_table()
        ),
        "Lambda": lambda S: TestObject(S(transformFunc=_lambda_fn), _words_table()),
        "TextPreprocessor": lambda S: TestObject(
            S(inputCol="words", outputCol="pp", map={"a": "z"}), _words_table()
        ),
    }
    for name, maker in simple.items():
        qual = f"mmlspark_tpu.stages.basic.{name}"

        def fx(maker=maker, name=name):
            import mmlspark_tpu.stages.basic as basic

            return maker(getattr(basic, name))

        add(qual, fx)

    def class_balancer():
        from mmlspark_tpu.stages.basic import ClassBalancer

        return TestObject(
            ClassBalancer(inputCol="label"),
            _words_table(),
            fit_produces="mmlspark_tpu.stages.basic.ClassBalancerModel",
        )

    add("mmlspark_tpu.stages.basic.ClassBalancer", class_balancer)

    def ensemble_by_key():
        from mmlspark_tpu.stages.basic import EnsembleByKey

        t = Table(
            {
                "key": np.array(["a", "a", "b"], dtype=object),
                "score": np.array([1.0, 3.0, 5.0]),
            }
        )
        return TestObject(EnsembleByKey(keys=["key"], cols=["score"]), t)

    add("mmlspark_tpu.stages.basic.EnsembleByKey", ensemble_by_key)

    def multi_column_adapter():
        from mmlspark_tpu.stages.basic import MultiColumnAdapter, UDFTransformer

        return TestObject(
            MultiColumnAdapter(
                baseStage=UDFTransformer(udf=_udf_double),
                inputCols=["num", "label"],
                outputCols=["num2", "label2"],
            ),
            _words_table(),
        )

    add("mmlspark_tpu.stages.basic.MultiColumnAdapter", multi_column_adapter)

    def timer():
        from mmlspark_tpu.stages.basic import Timer, UDFTransformer

        return TestObject(
            Timer(stage=UDFTransformer(inputCol="num", outputCol="n2", udf=_udf_double)),
            _words_table(),
            fit_produces="mmlspark_tpu.stages.basic.TimerModel",
        )

    add("mmlspark_tpu.stages.basic.Timer", timer)

    def fixed_batcher():
        from mmlspark_tpu.stages.batching import FixedMiniBatchTransformer

        return TestObject(FixedMiniBatchTransformer(batchSize=2), _words_table())

    add("mmlspark_tpu.stages.batching.FixedMiniBatchTransformer", fixed_batcher)

    def dynamic_batcher():
        from mmlspark_tpu.stages.batching import DynamicMiniBatchTransformer

        return TestObject(DynamicMiniBatchTransformer(maxBatchSize=3), _words_table())

    add("mmlspark_tpu.stages.batching.DynamicMiniBatchTransformer", dynamic_batcher)

    def time_batcher():
        from mmlspark_tpu.stages.batching import TimeIntervalMiniBatchTransformer

        return TestObject(
            TimeIntervalMiniBatchTransformer(millisToWait=5), _words_table()
        )

    add("mmlspark_tpu.stages.batching.TimeIntervalMiniBatchTransformer", time_batcher)

    def flatten_batch():
        from mmlspark_tpu.stages.batching import FixedMiniBatchTransformer, FlattenBatch

        t = FixedMiniBatchTransformer(batchSize=2).transform(_words_table())
        return TestObject(FlattenBatch(), t)

    add("mmlspark_tpu.stages.batching.FlattenBatch", flatten_batch)

    # --- train --------------------------------------------------------------
    def train_classifier():
        from mmlspark_tpu.lightgbm import LightGBMClassifier
        from mmlspark_tpu.train import TrainClassifier

        return TestObject(
            TrainClassifier(
                model=LightGBMClassifier(numIterations=4, numLeaves=5, parallelism="serial"),
                labelCol="label",
            ),
            _mixed_table(),
            fit_produces="mmlspark_tpu.train.trainers.TrainedClassifierModel",
        )

    add("mmlspark_tpu.train.trainers.TrainClassifier", train_classifier)

    def train_regressor():
        from mmlspark_tpu.lightgbm import LightGBMRegressor
        from mmlspark_tpu.train import TrainRegressor

        t = _mixed_table()
        t = t.with_column("label", t.column("num") * 2.0)
        return TestObject(
            TrainRegressor(
                model=LightGBMRegressor(numIterations=4, numLeaves=5, parallelism="serial"),
                labelCol="label",
            ),
            t,
            fit_produces="mmlspark_tpu.train.trainers.TrainedRegressorModel",
        )

    add("mmlspark_tpu.train.trainers.TrainRegressor", train_regressor)

    def compute_stats():
        from mmlspark_tpu.lightgbm import LightGBMClassifier
        from mmlspark_tpu.train import ComputeModelStatistics, TrainClassifier

        t = _mixed_table()
        out = (
            TrainClassifier(
                model=LightGBMClassifier(numIterations=4, numLeaves=5, parallelism="serial"),
                labelCol="label",
            )
            .fit(t)
            .transform(t)
        )
        return TestObject(ComputeModelStatistics(labelCol="label"), out)

    add("mmlspark_tpu.train.statistics.ComputeModelStatistics", compute_stats)

    def per_instance_stats():
        from mmlspark_tpu.lightgbm import LightGBMClassifier
        from mmlspark_tpu.train import ComputePerInstanceStatistics, TrainClassifier

        t = _mixed_table()
        out = (
            TrainClassifier(
                model=LightGBMClassifier(numIterations=4, numLeaves=5, parallelism="serial"),
                labelCol="label",
            )
            .fit(t)
            .transform(t)
        )
        return TestObject(ComputePerInstanceStatistics(labelCol="label"), out)

    add("mmlspark_tpu.train.statistics.ComputePerInstanceStatistics", per_instance_stats)

    # --- dnn ----------------------------------------------------------------
    def dnn_model():
        from mmlspark_tpu.dnn import DNNModel

        return TestObject(
            DNNModel(
                applyFn=_dnn_apply,
                modelParams={},
                feedDict={"x": "features"},
                fetchDict={"out": "y"},
                batchSize=4,
            ),
            _numeric_table(seed=12),
        )

    add("mmlspark_tpu.dnn.model.DNNModel", dnn_model)

    # --- io/http (client stack: pure parsers transform; live-server stages
    # are serde-only here, exercised end-to-end in tests/test_http.py) -------
    def json_input_parser():
        from mmlspark_tpu.io.http import JSONInputParser

        return TestObject(
            JSONInputParser(url="http://localhost:1/api", inputCol="payload", outputCol="req"),
            _http_request_table(),
        )

    add("mmlspark_tpu.io.http.transformers.JSONInputParser", json_input_parser)

    def custom_input_parser():
        from mmlspark_tpu.io.http import CustomInputParser

        return TestObject(
            CustomInputParser(inputCol="payload", outputCol="req", udf=_custom_in),
            _http_request_table(),
        )

    add("mmlspark_tpu.io.http.transformers.CustomInputParser", custom_input_parser)

    def custom_output_parser():
        from mmlspark_tpu.io.http import CustomOutputParser

        return TestObject(
            CustomOutputParser(inputCol="req", outputCol="parsed", udf=_custom_out),
            _http_request_table(),
        )

    add("mmlspark_tpu.io.http.transformers.CustomOutputParser", custom_output_parser)

    def string_output_parser():
        from mmlspark_tpu.io.http import StringOutputParser

        return TestObject(
            StringOutputParser(inputCol="req", outputCol="s"),
            _http_request_table(),
            check_transform=False,  # consumes HTTPResponseData from a live call
        )

    add("mmlspark_tpu.io.http.transformers.StringOutputParser", string_output_parser)

    def json_output_parser():
        from mmlspark_tpu.io.http import JSONOutputParser

        return TestObject(
            JSONOutputParser(inputCol="req", outputCol="parsed"),
            _http_request_table(),
            check_transform=False,
        )

    add("mmlspark_tpu.io.http.transformers.JSONOutputParser", json_output_parser)

    def http_transformer():
        from mmlspark_tpu.io.http import HTTPTransformer

        return TestObject(
            HTTPTransformer(inputCol="req", outputCol="resp"),
            _http_request_table(),
            check_transform=False,
        )

    add("mmlspark_tpu.io.http.transformers.HTTPTransformer", http_transformer)

    def simple_http():
        from mmlspark_tpu.io.http import JSONInputParser, SimpleHTTPTransformer

        return TestObject(
            SimpleHTTPTransformer(
                inputCol="payload",
                outputCol="out",
                inputParser=JSONInputParser(url="http://localhost:1/api"),
            ),
            _http_request_table(),
            check_transform=False,
        )

    add("mmlspark_tpu.io.http.transformers.SimpleHTTPTransformer", simple_http)

    def powerbi():
        from mmlspark_tpu.io.powerbi import PowerBIWriter

        return TestObject(
            PowerBIWriter(url="http://localhost:1/push", batchSize=2),
            Table({"a": np.arange(3, dtype=np.float64)}),
            check_transform=False,  # pushes to a live endpoint
        )

    add("mmlspark_tpu.io.powerbi.PowerBIWriter", powerbi)

    def consolidator():
        from mmlspark_tpu.io.http import PartitionConsolidator

        return TestObject(
            PartitionConsolidator(inputCol="req", outputCol="resp", concurrency=2),
            _http_request_table(),
            check_transform=False,
        )

    add("mmlspark_tpu.io.http.transformers.PartitionConsolidator", consolidator)

    return reg


TEST_OBJECTS = _make_test_objects()


# Classes that are deliberately NOT fuzzed directly, with the reason — the
# analogue of FuzzingTest.scala's exemption lists. Abstract/base classes and
# models that only exist via their estimator's fit() (covered through
# fit_produces) do not belong here; this list is for everything else.
EXEMPT: Dict[str, str] = {
    "mmlspark_tpu.core.pipeline.PipelineStage": "abstract base",
    "mmlspark_tpu.core.pipeline.Transformer": "abstract base",
    "mmlspark_tpu.core.pipeline.Estimator": "abstract base",
    "mmlspark_tpu.core.pipeline.Model": "abstract base",
    "mmlspark_tpu.core.pipeline.Pipeline": "meta-stage; roundtrip covered in test_core_params Pipeline tests",
    "mmlspark_tpu.core.pipeline.PipelineModel": "meta-stage; covered with Pipeline",
    "mmlspark_tpu.lightgbm.base.LightGBMBase": "abstract learner base (objective hooks unimplemented)",
    "mmlspark_tpu.lightgbm.base.LightGBMModelBase": "abstract model base",
    "mmlspark_tpu.vw.base.VowpalWabbitBase": "abstract learner base",
    "mmlspark_tpu.vw.base.VowpalWabbitModelBase": "abstract model base",
    "mmlspark_tpu.automl.tune.TuneHyperparameters": "estimator-of-estimators; covered in test_automl (needs param grids)",
    "mmlspark_tpu.automl.tune.TuneHyperparametersModel": "produced by TuneHyperparameters; covered in test_automl",
    "mmlspark_tpu.automl.tune.FindBestModel": "model-selection meta-stage; covered in test_automl",
    "mmlspark_tpu.automl.tune.BestModel": "produced by FindBestModel; covered in test_automl",
    "mmlspark_tpu.sweep.estimator.TrainValidSweep": "estimator-of-estimators; covered in test_sweep (needs param spaces)",
    "mmlspark_tpu.sweep.estimator.TrainValidSweepModel": "produced by TrainValidSweep; covered in test_sweep",
}
