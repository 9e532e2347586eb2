"""ImageFeaturizer — transfer-learning featurization on TPU.

Re-design of ``image/ImageFeaturizer.scala:40-86``: the reference wraps a
downloaded CNTK model, cuts ``cutOutputLayers`` layers off the top, and
prepends resize/unroll. Here the backbone is a native JAX network (default:
the :mod:`mmlspark_tpu.models.resnet` zoo) and the whole chain — resize →
normalize → NCHW layout → backbone forward with ``cut`` — jits into one XLA
program executed in fixed-shape device batches by :class:`DNNModel`, built
once a process for each (backbone, ``cutOutputLayers``, ``scale``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from mmlspark_tpu.core.device import cached_program
from mmlspark_tpu.core.params import Param, gt, to_bool, to_int, to_str
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.dnn.model import DNNModel
from mmlspark_tpu.image.transforms import ImageTransformer
from mmlspark_tpu.observability.tracing import get_tracer


def _apply_fn(backbone, cut: int, scale: float):
    """The ``applyFn`` handed to :class:`DNNModel`: one function object a
    process for everything it captures, so that a later ``transform``, of
    any featurizer of this definition, finds the program built for it."""

    def make():
        def apply_fn(p, inputs):
            x = inputs["input"].astype("float32") * scale
            x = x.transpose(0, 3, 1, 2)  # NHWC -> NCHW
            return {"output": backbone(p, x, cut)}

        return apply_fn

    return cached_program(("image.featurizer", backbone, cut, scale), make)


class ImageFeaturizer(Model):
    """Featurize an image column with a (cut) deep network."""

    inputCol = Param("Image column", default="image", converter=to_str)
    outputCol = Param("Feature vector column", default="features", converter=to_str)
    modelParams = Param(
        "Backbone parameter pytree (mmlspark_tpu.models zoo format)",
        default=None,
        is_complex=True,
    )
    applyFn = Param(
        "Backbone (params, x, cut) -> array; default resnet_apply",
        default=None,
        is_complex=True,
    )
    cutOutputLayers = Param(
        "Layers cut from the top: 0 = logits (headful), 1 = pooled features "
        "(reference default), 2 = feature map",
        default=1,
        converter=to_int,
    )
    inputHeight = Param("Model input height", default=32, converter=to_int, validator=gt(0))
    inputWidth = Param("Model input width", default=32, converter=to_int, validator=gt(0))
    autoResize = Param(
        "Resize images to the model input (ResizeImageTransformer analogue)",
        default=True,
        converter=to_bool,
    )
    scale = Param("Pixel scale applied before the backbone", default=1.0 / 255.0)
    batchSize = Param("Device batch size", default=64, converter=to_int, validator=gt(0))

    def _backbone(self):
        fn = self.getApplyFn()
        if fn is None:
            from mmlspark_tpu.models.resnet import resnet_apply

            fn = resnet_apply
        return fn

    def transform(self, table: Table) -> Table:
        """One ``image.featurize`` span (``observability/tracing``) roots the
        call's trace: the resize stage's ``image.*`` spans and the batched
        forward's ``dnn.*`` spans are its descendants."""
        with get_tracer().span(
            "image.featurize", rows=table.num_rows, batch_size=self.getBatchSize()
        ):
            params = self.getModelParams()
            if params is None:
                raise ValueError("modelParams must be set (see mmlspark_tpu.models)")
            work = table
            image_col = self.getInputCol()
            if self.getAutoResize():
                resized_col = "__resized__"
                work = ImageTransformer(
                    inputCol=image_col,
                    outputCol=resized_col,
                    toFloat=True,
                    stages=[
                        {
                            "op": "ResizeImage",
                            "height": self.getInputHeight(),
                            "width": self.getInputWidth(),
                        }
                    ],
                ).transform(work)
                image_col = resized_col

            apply_fn = _apply_fn(
                self._backbone(), self.getCutOutputLayers(), float(self.getScale())
            )

            dnn = DNNModel(
                applyFn=apply_fn,
                modelParams=params,
                feedDict={"input": image_col},
                fetchDict={self.getOutputCol(): "output"},
                batchSize=self.getBatchSize(),
            )
            out = dnn.transform(work)
            if image_col != self.getInputCol():
                out = out.drop(image_col)
            return out
