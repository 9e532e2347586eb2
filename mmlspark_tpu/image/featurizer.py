"""ImageFeaturizer — transfer-learning featurization on TPU.

Re-design of ``image/ImageFeaturizer.scala:40-86``: the reference wraps a
downloaded CNTK model, cuts ``cutOutputLayers`` layers off the top, and
prepends resize/unroll. Here the backbone is a native JAX network (default:
the :mod:`mmlspark_tpu.models.resnet` zoo) and the chain is two XLA
programs with a device-resident table between them. The first is
:class:`ImageTransformer`'s stage program (``autoResize``): one execution a
shape group, uint8 rows up, ``(rows, H*W*C)`` float32 left on the device
(``ImageTransformer._device_groups``; nothing is fetched). The second is
normalize → NCHW layout → backbone forward with ``cut``, built once a process
for each (backbone, ``cutOutputLayers``, ``scale``) and executed in
fixed-shape batches by :class:`DNNModel`, whose batch loop is handed the
first program's ``jax.Array`` as its fed column (``DNNModel._transform``) and
slices each batch out of it on the device. The resized table is never a host
column: what comes down is the feature rows. With ``autoResize=False`` the
image column itself is fed, from the host, as any ``DNNModel`` column is.
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
            apply_fn = _apply_fn(
                self._backbone(), self.getCutOutputLayers(), float(self.getScale())
            )
            in_col, out_col = self.getInputCol(), self.getOutputCol()

            def forward(fed_col: str) -> DNNModel:
                return DNNModel(
                    applyFn=apply_fn,
                    modelParams=params,
                    feedDict={"input": fed_col},
                    fetchDict={out_col: "output"},
                    batchSize=self.getBatchSize(),
                )

            if not self.getAutoResize():
                return forward(in_col).transform(table)
            groups = ImageTransformer(
                inputCol=in_col,
                toFloat=True,
                stages=[
                    {
                        "op": "ResizeImage",
                        "height": self.getInputHeight(),
                        "width": self.getInputWidth(),
                    }
                ],
            )._device_groups(table)
            if not groups:
                raise ValueError("need at least one image to featurize")
            resized_col = "__resized__"  # the name the forward is fed under; never a column
            dnn = forward(resized_col)
            if len(groups) == 1:  # every row, in order: the cell, any uniform column
                _, shape, resized = groups[0]
                return dnn._transform(table, {resized_col: resized}, {resized_col: shape[1:]})
            # several input shapes: each group's rows go through on their
            # own, and what is put back in input order is the feature rows
            features = None
            for idxs, shape, resized in groups:
                rows = dnn._transform(
                    table.select(in_col).take(idxs),
                    {resized_col: resized}, {resized_col: shape[1:]},
                )[out_col]
                if features is None:
                    features = np.empty((table.num_rows,) + rows.shape[1:], dtype=rows.dtype)
                features[idxs] = rows
            return table.with_column(out_col, features)
