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
    "falcon_h1": ("mmlspark_tpu.models.falcon_h1", "falcon_h1_apply", "init_falcon_h1"),
    "KeyeVL2": ("mmlspark_tpu.models.keye_vl2", "keye_vl2_apply", "init_keye_vl2"),
}
_DEFAULT = "afmoe"
_MODULES = ", ".join(f"'{model_type}': {module}" for model_type, (module, _, _) in FAMILIES.items())
_INITS = ", ".join(f"{module}.{init}" for module, _, init in FAMILIES.values())


def _route_tags(load, batch: int, tokens: int) -> dict:
    """``expert_load`` (rows, expert layers, experts) -> ``lm.route_stats``'
    tags, summed per dispatch (the rows of one batch)."""
    load = np.asarray(load, np.int64)
    per_dispatch = np.add.reduceat(load, np.arange(0, len(load), batch), axis=0)
    return {"load_peak": int(per_dispatch.max(axis=-1).sum()), "load_mean": float(per_dispatch.mean(axis=-1).sum()),
            "tokens_routed": int(load.sum()), "experts_empty": int((per_dispatch == 0).sum()),
            "expert_groups": int(per_dispatch.size)}


def _select_tags(selection, batch: int, tokens: int) -> dict:
    """``key_selection`` (rows, layers, 1 + words), the keys each layer kept
    over a row's queries first -> ``lm.select_stats``' tags: the keys kept,
    the keys the same queries see (``j <= i``: causal) and the queries."""
    kept = np.asarray(selection[..., 0], np.int64)
    return {"keys_kept": int(kept.sum()), "keys_visible": kept.size * tokens * (tokens + 1) // 2,
            "queries": kept.size * tokens}


# A statistic a family returns -> (the ``span_tags`` key that says the family
# returns it, what the stage says where it does not, the span that books it,
# its tags from the fetched column). The stage fetches each statistic its
# family has, asked for as an output column or not, and books it.
STATISTICS = {
    "expert_load": ("experts", "no expert layers", "lm.route_stats", _route_tags),
    "key_selection": ("sparse_topk", "no sparse attention", "lm.select_stats", _select_tags),
}


def _family(config: dict):
    """-> (``model_type``, the family's apply function, its span tags)."""
    model_type = config.get("model_type", _DEFAULT)
    if model_type not in FAMILIES:
        raise ValueError(f"modelConfig['model_type'] {model_type!r}: one of {sorted(FAMILIES, key=str.lower)}")
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
        "received, per expert layer, for a family with experts; 'key_selection': "
        "keys kept over the row's queries and the last position's kept keys, per "
        "layer, for a family with sparse attention) -> output column",
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
        forward's ``dnn.*`` spans beneath it; then a span for each statistic
        the family returns (:data:`STATISTICS`: ``lm.route_stats`` for a
        family with expert layers, ``lm.select_stats`` for one with sparse
        attention), booked from the fetched column
        (``observability/tracing``). A family without a statistic returns
        none: none is fetched and none is booked."""
        params, config = self.getModelParams(), self.getModelConfig()
        if params is None or config is None:
            raise ValueError(f"modelParams and modelConfig must be set (see {_INITS})")
        outputs = dict(self.getOutputCols())
        unknown = set(outputs) - {"hidden", "logits", *STATISTICS}
        if unknown or not outputs:
            raise ValueError(f"outputCols maps hidden / logits / {' / '.join(STATISTICS)} to columns "
                             f"(got {sorted(outputs)})")
        batch = self.getBatchSize()
        tokens = len(table.column(self.getInputCol())[0])
        model_type, _, tags = _family(config)
        for stat, (flag, lacking, _, _) in STATISTICS.items():
            if stat in outputs and not tags.get(flag):
                raise ValueError(f"model_type {model_type!r} has {lacking}: no {stat} to map to a column")
        # fetched always where the family has it: its span reads it
        stats = {stat: outputs.get(stat, f"__{stat}__") for stat, (flag, *_) in STATISTICS.items() if tags.get(flag)}
        with get_tracer().span(
            "lm.featurize", rows=table.num_rows, tokens=tokens, batch_size=batch,
            model_type=model_type, **tags,
        ):
            dnn = DNNModel(
                applyFn=_apply_fn(config),
                modelParams=params,
                feedDict={"input": self.getInputCol()},
                fetchDict={**{col: out for out, col in outputs.items()}, **{col: stat for stat, col in stats.items()}},
                batchSize=batch,
                inputDtype="int32",
            )
            out = dnn.transform(table)
            for stat, col in stats.items():
                _, _, span, book = STATISTICS[stat]
                with get_tracer().span(span) as sp:
                    sp.tags.update(book(out[col], batch, tokens))
                if stat not in outputs:
                    out = out.drop(col)
            return out
