"""LMFeaturizer — score or featurize a column of token rows with a decoder.

The text sibling of :class:`mmlspark_tpu.image.ImageFeaturizer`: a language
model applied to whole sequences by :class:`DNNModel` in fixed-shape device
batches, features and last-position logits out, for a downstream learner.
``modelConfig["model_type"]`` names the decoder family, one of
:data:`FAMILIES` (``afmoe`` where the key is absent); the stage's own
documentation lists them from that table. No generation loop and no cache
of keys, values or recurrent state: every call runs whole sequences. The
program itself is built once a process for each decoder configuration (by
content) and found again by later calls.
"""

from __future__ import annotations

import copy
import importlib

import numpy as np

from mmlspark_tpu.core.device import cached_program, frozen
from mmlspark_tpu.core.params import Param, gt, to_int, to_str
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.dnn.model import DNNModel
from mmlspark_tpu.observability.tracing import get_tracer

_LOAD = "expert_load"

# model_type -> (the family's module, its ``*_apply(params, tokens, config)``
# and its ``init_*(key, config)`` there). The module's ``span_tags(config)``
# says what ``lm.featurize`` tells of a configuration of its family, so the
# key names stay with the family. A new family is one line here: the stage's
# documentation is made from this table.
FAMILIES = {
    "afmoe": ("mmlspark_tpu.models.afmoe", "afmoe_apply", "init_afmoe"),
    "joyai_llm_flash": ("mmlspark_tpu.models.mla_moe", "mla_moe_apply", "init_mla_moe"),
    "nemotron_h": ("mmlspark_tpu.models.nemotron_h", "nemotron_h_apply", "init_nemotron_h"),
    "lfm2_moe": ("mmlspark_tpu.models.lfm2_moe", "lfm2_moe_apply", "init_lfm2_moe"),
}
_DEFAULT = "afmoe"
_MODULES = ", ".join(f"'{model_type}': {module}" for model_type, (module, _, _) in FAMILIES.items())
_INITS = ", ".join(f"{module}.{init}" for module, _, init in FAMILIES.values())


def _family(config: dict):
    """-> (``model_type``, the family's apply function, its span tags)."""
    model_type = config.get("model_type", _DEFAULT)
    if model_type not in FAMILIES:
        raise ValueError(f"modelConfig['model_type'] {model_type!r}: one of {sorted(FAMILIES)}")
    module, name, _ = FAMILIES[model_type]
    module = importlib.import_module(module)
    return model_type, getattr(module, name), module.span_tags(config)


def _apply_fn(config: dict):
    """The ``applyFn`` handed to :class:`DNNModel`: one function object a
    process for a configuration's content (a fresh ``dict`` of equal content
    is the same key), so that a later ``transform`` finds its program."""
    _, apply, _ = _family(config)

    def make():
        # a later trace (another batch shape) must read what the key says,
        # whatever the caller has done to its dict since
        own = copy.deepcopy(config)
        return lambda p, inputs: apply(p, inputs["input"], own)

    return cached_program(("lm.featurizer", frozen(config)), make)


class LMFeaturizer(Model):
    __doc__ = f"""Apply a decoder to a column of int32 token rows of one length.

    The decoder families, by ``modelConfig["model_type"]`` ('{_DEFAULT}' where
    the key is absent): {_MODULES}."""

    inputCol = Param("Column of token-id rows, all of one length", default="tokens", converter=to_str)
    outputCols = Param(
        "model output ('hidden': last position after the final norm, 'logits': "
        "last-position logits, 'expert_load': tokens of the row each expert "
        "received, per expert layer) -> output column",
        default={"hidden": "features"},
    )
    modelParams = Param(
        f"Decoder parameter pytree (the family's init_* format: {_INITS})",
        default=None, is_complex=True,
    )
    modelConfig = Param(
        "Decoder configuration: the family's published config.json keys and 'layers'; "
        f"'model_type' names the family ('{_DEFAULT}' where absent; {_MODULES})", default=None)
    batchSize = Param("Rows per device batch", default=4, converter=to_int, validator=gt(0))

    def transform(self, table: Table) -> Table:
        """One ``lm.featurize`` span roots the call's trace, the batched
        forward's ``dnn.*`` spans beneath it; ``lm.route_stats`` then sums the
        fetched expert loads per dispatch (``observability/tracing``)."""
        params, config = self.getModelParams(), self.getModelConfig()
        if params is None or config is None:
            raise ValueError(f"modelParams and modelConfig must be set (see {_INITS})")
        outputs = dict(self.getOutputCols())
        unknown = set(outputs) - {"hidden", "logits", _LOAD}
        if unknown or not outputs:
            raise ValueError(f"outputCols maps hidden / logits / expert_load to columns (got {sorted(outputs)})")
        batch = self.getBatchSize()
        tokens = len(table.column(self.getInputCol())[0])
        model_type, _, tags = _family(config)
        with get_tracer().span(
            "lm.featurize", rows=table.num_rows, tokens=tokens, batch_size=batch,
            model_type=model_type, **tags,
        ):
            load_col = outputs.get(_LOAD, "__expert_load__")  # fetched always: route_stats reads it
            dnn = DNNModel(
                applyFn=_apply_fn(config),
                modelParams=params,
                feedDict={"input": self.getInputCol()},
                fetchDict={**{col: out for out, col in outputs.items()}, load_col: _LOAD},
                batchSize=batch,
                inputDtype="int32",
            )
            out = dnn.transform(table)
            with get_tracer().span("lm.route_stats") as sp:
                # (rows, expert layers, experts) -> per dispatch (the rows of one batch)
                load = np.asarray(out[load_col], np.int64)
                per_dispatch = np.add.reduceat(load, np.arange(0, len(load), batch), axis=0)
                sp.tags["load_peak"] = int(per_dispatch.max(axis=-1).sum())
                sp.tags["load_mean"] = float(per_dispatch.mean(axis=-1).sum())
                sp.tags["tokens_routed"] = int(load.sum())
                sp.tags["experts_empty"] = int((per_dispatch == 0).sum())
                sp.tags["expert_groups"] = int(per_dispatch.size)
            if _LOAD not in outputs:
                out = out.drop(load_col)
            return out
