"""mmlspark_tpu — a TPU-native machine-learning pipeline framework.

A brand-new framework with the capabilities of MMLSpark (Microsoft Machine
Learning for Apache Spark), re-designed TPU-first on JAX/XLA/Pallas/pjit:

- Columnar :class:`~mmlspark_tpu.data.Table` replaces Spark DataFrames; columns
  live in host numpy and move to TPU HBM in large batched transfers.
- ``Estimator.fit`` / ``Transformer.transform`` / ``Pipeline`` compose exactly
  like SparkML stages (reference: ``core/contracts/Params.scala``), but all
  heavy compute is jitted XLA running on a ``jax.sharding.Mesh`` of TPU chips.
- Distributed training replaces socket/spanning-tree allreduce with
  ``lax.psum`` over the ICI mesh (reference: ``lightgbm/LightGBMUtils.scala``,
  ``vw/VowpalWabbitBase.scala``).

Subpackages mirror the reference's component inventory (SURVEY.md §2):

- ``core``      — params/pipeline contracts, serialization, schema, topology
- ``runtime``   — fault-tolerant partition scheduler (the driver/executor
  layer Spark provided: retries, heartbeats, lineage recompute)
- ``data``      — columnar Table, readers, partitioning
- ``parallel``  — mesh construction, sharding helpers, collectives, ring attention
- ``ops``       — hashing, histograms, image kernels (XLA + Pallas)
- ``lightgbm``  — histogram GBDT learners (LightGBM-on-Spark equivalent)
- ``vw``        — online linear learners (VowpalWabbit-on-Spark equivalent)
- ``nn_models`` — deep-model inference, ImageFeaturizer (CNTKModel equivalent)
- ``stages``    — generic pipeline stages
- ``featurize`` — auto-featurization, text featurization
- ``train``     — simplified train/eval API + model statistics
- ``automl``    — hyperparameter search, best-model selection
- ``knn``       — (conditional) nearest neighbors
- ``recommendation`` — SAR, ranking evaluation
- ``lime``      — model-agnostic interpretability
- ``isolationforest`` — anomaly detection
- ``io``        — HTTP-on-TPU client stack + low-latency serving
- ``streaming`` — Structured-Streaming-analogue micro-batch engine:
  offset-tracked sources, checkpointed exactly-once queries, incremental
  warm-start fit sinks feeding zero-downtime model hot swap in serving
- ``resilience`` — request-plane fault tolerance: circuit breakers,
  deadline propagation (``X-Deadline-Ms``), retry budgets, admission
  control shared by serving and every outbound HTTP caller
- ``cognitive`` — REST cognitive-service transformers
- ``downloader`` — pretrained model repository
"""

__version__ = "0.1.0"

# The runtime lock witness (MMLSPARK_TPU_LOCKCHECK=1) must wrap
# threading.Lock/RLock before any package module allocates one, so this
# hook runs ahead of every other package import. No-op unless the env
# var is set.
from mmlspark_tpu.analysis.witness import install_from_env as _install_lock_witness

_install_lock_witness()

from mmlspark_tpu.core.params import Param, Params
from mmlspark_tpu.core.pipeline import (
    Estimator,
    Evaluator,
    Model,
    Pipeline,
    PipelineModel,
    PipelineStage,
    Transformer,
)
from mmlspark_tpu.data.table import Table


def clear_compiled_caches() -> None:
    """Release every compiled-program cache the package (and JAX) holds.

    Long-lived processes that fit many differently-shaped models — test
    harnesses, notebook sessions, serving workers cycling models —
    accumulate compiled XLA executables: the package's program cache
    (``core.device.cached_program``: the boosting step, the deep path's
    ``applyFn`` and image-stage programs), module-level jitted predict
    kernels, and JAX's own pjit caches. XLA:CPU tolerates only so much of
    this in one process (an upstream compiler crash reproduces after
    several hundred accumulated compilations — see
    ``tests/conftest.py``); calling this between workloads bounds the
    footprint. Safe at any point: every cache refills on demand.
    """
    import gc

    import jax

    from mmlspark_tpu.core import device as _device

    _device._PROGRAM_CACHE.clear()
    jax.clear_caches()
    gc.collect()


__all__ = [
    "Param",
    "Params",
    "PipelineStage",
    "Transformer",
    "Estimator",
    "Model",
    "Pipeline",
    "PipelineModel",
    "Evaluator",
    "Table",
    "clear_compiled_caches",
    "__version__",
]
