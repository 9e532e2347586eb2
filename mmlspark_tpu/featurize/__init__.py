"""Automatic featurization (reference ``featurize/`` — SURVEY.md §2.10)."""

from mmlspark_tpu.featurize.clean import CleanMissingData, CleanMissingDataModel
from mmlspark_tpu.featurize.conversion import DataConversion
from mmlspark_tpu.featurize.featurize import AssembleFeatures, Featurize
from mmlspark_tpu.featurize.indexers import (
    IndexToValue,
    ValueIndexer,
    ValueIndexerModel,
)
from mmlspark_tpu.featurize.lm import LMFeaturizer
from mmlspark_tpu.featurize.text import (
    MultiNGram,
    PageSplitter,
    TextFeaturizer,
    TextFeaturizerModel,
)

__all__ = [
    "AssembleFeatures",
    "CleanMissingData",
    "CleanMissingDataModel",
    "DataConversion",
    "Featurize",
    "IndexToValue",
    "LMFeaturizer",
    "MultiNGram",
    "PageSplitter",
    "TextFeaturizer",
    "TextFeaturizerModel",
    "ValueIndexer",
    "ValueIndexerModel",
]
