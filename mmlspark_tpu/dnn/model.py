"""DNNModel — batched deep-network inference transformer.

Re-design of ``CNTKModel`` (``cntk/CNTKModel.scala:145-531``) for TPU:

- the serialized CNTK ``Function`` broadcast to executors becomes a jittable
  ``applyFn(params, inputs) -> outputs`` plus a ``params`` pytree placed on
  device once per transform (the ``rebroadcastCNTKModel`` analogue,
  ``CNTKModel.scala:411-413``) and passed as an argument; the program is
  built once a process for each ``applyFn`` object (``_jitted``) and a later
  ``transform``, of this instance or another, finds it;
- mini-batching is ON by default (reference wraps with
  ``FixedMiniBatchTransformer(batchSize=10)`` then ``FlattenBatch``,
  ``CNTKModel.scala:374,496-528``) — here every batch is right-padded to a
  single static shape so XLA compiles ONE program and the MXU sees full
  tiles;
- ``feedDict``/``fetchDict`` map model input/output names to columns
  (``CNTKModel.scala:225-367``); the single-input/single-output convenience
  setters mirror ``setInputCol``/``setOutputCol``;
- input coercion float/double/vector (``CNTKModel.scala:417-460``) becomes
  a cast while the host batch is written, and no copy at all where a dense
  column's slice already is the batch (``_stack_batch``).

Optionally shards each batch over the mesh ``data`` axis — the reference's
per-partition embarrassing parallelism (``CNTKModelUtils.applyModel``,
``CNTKModel.scala:30-140``) expressed as one SPMD program.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

from mmlspark_tpu.core.device import cached_program, frozen, programs_built
from mmlspark_tpu.core.params import Param, gt, to_bool, to_int, to_str
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.observability.tracing import get_tracer


def _stack_batch(col: np.ndarray, pad_to: int, dtype: Any) -> np.ndarray:
    """A column's slice of rows -> the [pad_to, ...] batch of ``dtype`` the
    program is fed, copying only where the slice itself forces a copy.

    A dense slice that already is that batch (``pad_to`` rows, ``dtype``,
    C-contiguous) is returned as it is: a view of an immutable ``Table``
    column, possibly read-only, so nothing may write into a batch and the
    program must not donate its inputs. Any other dense slice (short of
    ``pad_to``, another dtype, strided) is written once into a fresh batch:
    the assignment casts, the tail rows are zero. An object column's rows
    (ragged tables, lists of arrays) are stacked first, which raises on rows
    of unequal shape."""
    if col.dtype == object:
        col = np.stack([np.asarray(v) for v in col])
    rows = len(col)
    if rows == pad_to and col.dtype == dtype and col.flags.c_contiguous:
        return col
    batch = np.empty((pad_to,) + col.shape[1:], dtype=dtype)
    batch[:rows] = col
    batch[rows:] = 0
    return batch


def _build_device_batch():
    """What :func:`_device_batch` caches: one jitted function for every
    offset (``lo`` is an argument), retraced only for another column shape,
    row count, batch shape or dtype."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def device_batch(col, lo, rows, batch_shape, dtype):
        batch = lax.dynamic_slice_in_dim(col, lo, rows).astype(dtype)
        if rows < batch_shape[0]:  # a short last batch: zero rows behind it
            batch = jnp.pad(batch, [(0, batch_shape[0] - rows)] + [(0, 0)] * (batch.ndim - 1))
        return batch.reshape(batch_shape)

    return device_batch


def _device_batch(col: Any, lo: int, hi: int, pad_to: int, row_shape: Tuple[int, ...], dtype: np.dtype):
    """:func:`_stack_batch` for a column that lives on the device: rows
    ``lo:hi`` of the ``jax.Array`` as the ``[pad_to, *row_shape]`` batch of
    ``dtype``, sliced, cast, zero-padded and reshaped there by one small
    program; nothing crosses the host boundary but ``lo``."""
    program = cached_program(("dnn.device_batch",), _build_device_batch)
    return program(col, np.int32(lo), hi - lo, (pad_to,) + tuple(row_shape), dtype)


def _place_on_device(params):
    """One device: numpy leaves go up, a ``jax.Array`` stays where it is."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.asarray, params)


def _mesh_program(apply_fn: Callable, mesh: Any, tp: Dict[str, int]):
    """``(fn, place)`` under ``shardOverMesh``: each batch sharded over the
    mesh ``data`` axis, parameters replicated or, where ``tp`` names their
    key, sharded over ``model`` on that axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch_sharding = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())

    def shard_for(key, value):
        if key in tp:
            spec = [None] * np.ndim(value)
            spec[tp[key]] = "model"
            return NamedSharding(mesh, P(*spec))
        return replicated

    def place_params(params):
        """Commit weights to their FINAL shardings once, outside the
        compiled call — so the in-program device_put is a no-op
        rather than a per-batch broadcast/reshard over ICI."""
        if isinstance(params, dict):
            return {
                k: jax.device_put(v, shard_for(k, v))
                for k, v in params.items()
            }
        return jax.device_put(params, replicated)

    def run(params, inputs):
        inputs = {
            k: jax.device_put(v, batch_sharding) for k, v in inputs.items()
        }
        return apply_fn(params, inputs)

    return jax.jit(run), place_params


class DNNModel(Model):
    """Applies a jittable network to feature columns in device batches."""

    applyFn = Param(
        "Jittable (params, {name: array}) -> {name: array} | array",
        default=None, is_complex=True,
    )
    modelParams = Param("Model parameter pytree", default=None, is_complex=True)
    feedDict = Param(
        "model input name -> feature column name", default={},
    )
    fetchDict = Param(
        "output column name -> model output name", default={},
    )
    batchSize = Param(
        "Rows per device batch (static shape; last batch padded)",
        default=64,
        converter=to_int,
        validator=gt(0),
    )
    miniBatcher = Param(
        "Batch rows before eval (CNTKModel batches by default)",
        default=True,
        converter=to_bool,
    )
    inputDtype = Param("Cast inputs to this dtype", default="float32", converter=to_str)
    paramShardings = Param(
        "Tensor-parallel map: param key -> axis index sharded over the mesh "
        "'model' axis (None = fully replicated params)",
        default=None, is_complex=True,
    )
    meshConfig = Param(
        "MeshConfig for shardOverMesh (None = all devices on the data axis)",
        default=None, is_complex=True,
    )
    shardOverMesh = Param(
        "Shard each batch over the mesh 'data' axis", default=False, converter=to_bool
    )

    # -- convenience single input/output API (CNTKModel.scala:302-367) -------

    def setInputCol(self, value: str) -> "DNNModel":
        feeds = dict(self.getFeedDict())
        feeds["input"] = value
        return self.setFeedDict(feeds)

    def setOutputCol(self, value: str) -> "DNNModel":
        fetches = dict(self.getFetchDict())
        fetches[value] = "output"
        return self.setFetchDict(fetches)

    def getInputCol(self) -> str:
        return next(iter(self.getFeedDict().values()))

    def getOutputCol(self) -> str:
        return next(iter(self.getFetchDict().keys()))

    # -- evaluation ----------------------------------------------------------

    def _jitted(self):
        """``(fn, place)``: the jitted program, and the function that puts
        ``modelParams`` on the device once a call. ``place`` leaves a leaf the
        caller already committed (a ``jax.Array`` on its mesh) where it is:
        an ``applyFn`` that runs its own parallel op (``ops/pipeline_parallel``,
        ``ops/expert_parallel``) is handed parameters placed for it.

        Both come from ``core.device.cached_program``, keyed on everything
        the traced function sees besides its arguments: the ``applyFn`` object
        (two closures of one source are two keys; a ``functools.partial`` is
        its own, a bound method its instance's) and, under ``shardOverMesh``,
        the mesh and ``paramShardings`` by content. No weight is in a key or
        in what is cached, so one program serves every ``modelParams``; the
        cache keeps ``applyFn`` alive until it is evicted, so an ``applyFn``
        should take its weights from ``params``, not close over them."""
        import jax

        apply_fn = self.getApplyFn()
        if apply_fn is None:
            raise ValueError("applyFn must be set")
        if not self.getShardOverMesh():
            return cached_program(
                ("dnn", apply_fn), lambda: (jax.jit(apply_fn), _place_on_device)
            )
        from mmlspark_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(self.getMeshConfig())
        # Tensor parallelism: paramShardings maps a param-pytree key to
        # the axis index sharded over the mesh "model" axis (e.g. the
        # output-features dim of a Linear weight). XLA then partitions
        # the matmuls and inserts the all-gather/reduce-scatter
        # collectives (the TP recipe: annotate shardings, let GSPMD
        # place the collectives).
        tp: Dict[str, int] = dict(self.getParamShardings() or {})
        if tp and not isinstance(self.getModelParams(), dict):
            raise ValueError(
                "paramShardings requires modelParams to be a flat dict "
                f"of arrays (got {type(self.getModelParams()).__name__})"
            )
        for key, axis in tp.items():
            val = self.getModelParams().get(key)
            if val is None:
                raise ValueError(f"paramShardings key {key!r} not in modelParams")
            if np.ndim(val) <= axis:
                raise ValueError(
                    f"paramShardings[{key!r}]={axis} out of range for a "
                    f"{np.ndim(val)}-d param"
                )
        return cached_program(
            ("dnn.mesh", apply_fn, mesh, frozen(tp)),
            lambda: _mesh_program(apply_fn, mesh, tp),
        )

    def transform(self, table: Table) -> Table:
        """Spans (``observability/tracing``; under ``ServingServer``'s batch
        loop they join the request's trace): ``dnn.transform`` around the
        call, ``dnn.place_params``, then per batch ``dnn.stack`` (the
        column's slice as one padded host batch; its ``bytes`` is what it
        copied, 0 where the slice is the batch), ``dnn.dispatch`` (input
        transfer and enqueue, ``bytes`` what crosses from the host: the
        batches the program is fed; where
        the call had to build its program (``dnn.transform``'s
        ``programs_built`` 1, else 0: ``_jitted``) or meets a new batch
        shape, its first batch also holds the trace and lowering) and
        ``dnn.fetch`` (it owns the wait on the forward), and ``dnn.assemble``
        for the output columns. Byte tags come from shapes."""
        return self._transform(table, {}, {})

    def _transform(
        self, table: Table, fed: Mapping[str, Any], row_shapes: Mapping[str, Tuple[int, ...]]
    ) -> Table:
        """:meth:`transform` with the columns of ``fed`` ({column name: its
        ``table.num_rows`` rows}) standing where the table's would, so that
        an earlier device stage can hand its result over without a
        ``Table`` in between (``ImageFeaturizer``). The loop looks at what
        it is handed: a numpy column is batched on the host as ever; a
        ``jax.Array`` is batched where it lives (``_device_batch``), each row
        reshaped to ``row_shapes[name]`` where that is given (a stage program
        returns ``(rows, H*W*C)``). Such a batch records ``dnn.stack``
        ``bytes`` 0 and adds nothing to ``dnn.dispatch``'s, and
        ``dnn.transform``'s ``device_batches`` counts the batches whose every
        fed column was sliced on the device."""
        import jax

        tracer = get_tracer()
        with tracer.span("dnn.transform", rows=table.num_rows) as whole:
            feeds: Dict[str, str] = self.getFeedDict()
            fetches: Dict[str, str] = self.getFetchDict()
            if not feeds or not fetches:
                raise ValueError("feedDict and fetchDict must both be set")
            batch_size = self.getBatchSize()
            if self.getShardOverMesh():
                from mmlspark_tpu.parallel.mesh import make_mesh

                n_dev = make_mesh(self.getMeshConfig()).shape.get("data", 1)
                batch_size = max(batch_size, n_dev)
                batch_size += (-batch_size) % n_dev
            dtype = np.dtype(self.getInputDtype())
            n = table.num_rows
            built_before = programs_built()
            fn, place = self._jitted()
            whole.tags["programs_built"] = programs_built() - built_before
            # Pin weights on device ONCE, with their final shardings when the
            # mesh is in play: numpy param leaves would re-transfer (and sharded
            # ones re-broadcast) on every batch dispatch.
            with tracer.span("dnn.place_params", bytes=sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree.leaves(self.getModelParams())
            )):
                params = place(self.getModelParams())

            out_cols: Dict[str, List[np.ndarray]] = {name: [] for name in fetches}
            bounds = (
                [(lo, min(lo + batch_size, n)) for lo in range(0, n, batch_size)]
                if self.getMiniBatcher()
                else [(0, n)]
            )
            whole.tags["batches"] = len(bounds)
            whole.tags["device_batches"] = 0
            for lo, hi in bounds:
                pad_to = batch_size if self.getMiniBatcher() else n
                with tracer.span("dnn.stack", pad_rows=pad_to - (hi - lo)) as sp:
                    inputs, copied, crossing, sliced = {}, 0, 0, 0
                    for model_in, col in feeds.items():
                        column = fed[col] if col in fed else table.column(col)
                        if isinstance(column, jax.Array):
                            row_shape = row_shapes.get(col, column.shape[1:])
                            inputs[model_in] = _device_batch(column, lo, hi, pad_to, row_shape, dtype)
                            sliced += 1
                            continue
                        rows = column[lo:hi]
                        batch = inputs[model_in] = _stack_batch(rows, pad_to, dtype)
                        if batch is not rows:
                            copied += batch.nbytes
                        crossing += batch.nbytes
                    sp.tags["bytes"] = copied
                    whole.tags["device_batches"] += sliced == len(feeds)
                with tracer.span("dnn.dispatch", bytes=crossing):
                    outputs = fn(params, inputs)
                with tracer.span("dnn.fetch") as sp:
                    if not isinstance(outputs, dict):
                        outputs = {"output": outputs}
                    fetched = 0
                    for col_name, model_out in fetches.items():
                        if model_out not in outputs:
                            raise KeyError(
                                f"model returned {sorted(outputs)}, no output {model_out!r}"
                            )
                        arr = np.asarray(jax.device_get(outputs[model_out]))
                        fetched += arr.nbytes
                        out_cols[col_name].append(arr[: hi - lo])
                    sp.tags["bytes"] = fetched
            with tracer.span("dnn.assemble") as sp:
                result, copied = table, 0
                for col_name, parts in out_cols.items():
                    column = np.concatenate(parts)
                    copied += column.nbytes
                    result = result.with_column(col_name, column)
                sp.tags["bytes"] = copied
            return result
