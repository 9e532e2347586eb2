"""ImageTransformer — a pipeline of image ops executed as batched XLA programs.

Re-design of ``opencv/ImageTransformer.scala:40-219``: the reference encodes
each OpenCV stage as a ``Map[String, Any]`` and runs a per-row UDF over JNI
mats. Here the same stage list drives a jitted NHWC float pipeline: images
are grouped by shape, stacked into batches, and every stage is a pure JAX
op — so a transformer chain compiles to ONE fused XLA program per input
shape instead of |rows| × |stages| native calls.

Stage dict vocabulary mirrors the reference (``ResizeImage``, ``CropImage``,
``ColorFormat``, ``Flip``, ``Blur``, ``Threshold``, ``GaussianKernel``).
Flip codes follow OpenCV: 0 = vertical (x-axis), 1 = horizontal, -1 = both.
"""

from __future__ import annotations

import copy
import functools
from typing import Any, Callable, Dict, Iterator, List, Tuple

import numpy as np

from mmlspark_tpu.core.device import cached_program, frozen, programs_built
from mmlspark_tpu.core.params import HasInputCol, HasOutputCol, Param, to_bool, to_str
from mmlspark_tpu.core.pipeline import Transformer
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.observability.tracing import get_tracer


def _op_resize(stage: Dict[str, Any]) -> Callable:
    import jax.image

    h, w = int(stage["height"]), int(stage["width"])

    def run(x):
        return jax.image.resize(
            x, (x.shape[0], h, w, x.shape[3]), method=stage.get("method", "linear")
        )

    return run


def _op_crop(stage: Dict[str, Any]) -> Callable:
    x0, y0 = int(stage.get("x", 0)), int(stage.get("y", 0))
    h, w = int(stage["height"]), int(stage["width"])

    def run(x):
        return x[:, y0 : y0 + h, x0 : x0 + w, :]

    return run


def _op_color_format(stage: Dict[str, Any]) -> Callable:
    import jax.numpy as jnp

    fmt = stage["format"]

    def run(x):
        if fmt == "gray":
            # OpenCV BGR2GRAY luma weights, channel order B,G,R.
            weights = jnp.asarray([0.114, 0.587, 0.299], dtype=x.dtype)
            return (x * weights).sum(axis=-1, keepdims=True)
        if fmt in ("bgr2rgb", "rgb2bgr"):
            return x[..., ::-1]
        raise ValueError(f"unknown color format {fmt!r}")

    return run


def _op_flip(stage: Dict[str, Any]) -> Callable:
    code = int(stage.get("flipCode", 1))

    def run(x):
        if code == 0:
            return x[:, ::-1, :, :]
        if code > 0:
            return x[:, :, ::-1, :]
        return x[:, ::-1, ::-1, :]

    return run


def _depthwise_filter(x, kernel2d):
    """Same-padding depthwise conv of an NHWC batch with one 2-D kernel."""
    import jax.numpy as jnp
    from jax import lax

    c = x.shape[-1]
    k = jnp.asarray(kernel2d, dtype=x.dtype)
    w = jnp.tile(k[None, None, :, :], (c, 1, 1, 1))  # OIHW, O=C, I=1
    xt = jnp.transpose(x, (0, 3, 1, 2))
    out = lax.conv_general_dilated(
        xt, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=c,
    )
    return jnp.transpose(out, (0, 2, 3, 1))


def _op_blur(stage: Dict[str, Any]) -> Callable:
    kh, kw = int(stage["height"]), int(stage["width"])
    kernel = np.full((kh, kw), 1.0 / (kh * kw))

    def run(x):
        return _depthwise_filter(x, kernel)

    return run


def _op_threshold(stage: Dict[str, Any]) -> Callable:
    import jax.numpy as jnp

    thresh = float(stage["threshold"])
    max_val = float(stage.get("maxVal", 255.0))

    def run(x):
        return jnp.where(x > thresh, max_val, 0.0).astype(x.dtype)

    return run


def _op_gaussian(stage: Dict[str, Any]) -> Callable:
    size = int(stage["apertureSize"])
    sigma = float(stage.get("sigma", 0.0))
    if sigma <= 0:  # OpenCV's default sigma rule
        sigma = 0.3 * ((size - 1) * 0.5 - 1) + 0.8
    ax = np.arange(size) - (size - 1) / 2.0
    g = np.exp(-(ax**2) / (2 * sigma**2))
    kernel = np.outer(g, g)
    kernel /= kernel.sum()

    def run(x):
        return _depthwise_filter(x, kernel)

    return run


def _op_normalize(stage: Dict[str, Any]) -> Callable:
    mean = np.asarray(stage.get("mean", 0.0), dtype=np.float32)
    std = np.asarray(stage.get("std", 1.0), dtype=np.float32)
    scale = float(stage.get("scale", 1.0))

    def run(x):
        return (x * scale - mean) / std

    return run


_OPS: Dict[str, Callable[[Dict[str, Any]], Callable]] = {
    "ResizeImage": _op_resize,
    "CropImage": _op_crop,
    "ColorFormat": _op_color_format,
    "Flip": _op_flip,
    "Blur": _op_blur,
    "Threshold": _op_threshold,
    "GaussianKernel": _op_gaussian,
    "Normalize": _op_normalize,
}


def _build_pipeline(stage_list: List[Dict[str, Any]]):
    """What :meth:`ImageTransformer._pipeline` caches for one stage list."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ops = []
    # an op reads its dict when it is traced, and a later trace (another
    # shape group) must read what the key says
    for stage in copy.deepcopy(stage_list):
        op_name = stage["op"]
        if op_name not in _OPS:
            raise ValueError(f"unknown image op {op_name!r}; have {sorted(_OPS)}")
        ops.append(_OPS[op_name](stage))

    def stages(batch):
        x = batch.astype("float32")
        for op in ops:
            x = op(x)
        return x

    @functools.partial(jax.jit, static_argnums=1)
    def run(slabs, shape):
        # every op is an image's own, so a slab is staged by itself and its
        # result written where its rows lie in the group's. (Joined first,
        # the uint8 slabs are a second copy of the table on the device: the
        # TPU compiler moves the cast behind a concatenation wherever it is
        # written.)
        parts = [stages(s.reshape((s.shape[0],) + shape[1:])).reshape(s.shape[0], -1)
                 for s in slabs]
        flat = parts[0]
        if len(parts) > 1:
            flat, lo = jnp.zeros((shape[0], flat.shape[1]), flat.dtype), 0
            for part in parts:
                flat = lax.dynamic_update_slice(flat, part, (lo, 0))
                lo += part.shape[0]
        return flat

    return stages, run, {}


# A shape group of more bytes than this reaches the device a slab at a time
# (``ImageTransformer._staged``). On the chip's host the first write to a
# fresh page costs ten times the copy (6,144 rows of 224 x 224 x 3 stacked
# into a fresh 0.92 GB batch: 0.97-1.10 s; into pages written before: 0.088),
# so a large group is stacked through two buffers of this size, filled in
# turn. The wire takes a slab at 5.4-5.6 GB/s, half the rate the host stacks
# at, so what the stage costs is the upload: 0.14-0.15 s a job of that table
# at 96 MiB a slab (640 such rows; the pair is 0.2 GB of fresh pages a
# call), where one batch took 0.96 s and went up afterwards (PERF.md, PR 38).
_SLAB_BYTES = 96 << 20


def _slab_rows(row_bytes: int) -> int:
    """How many rows of ``row_bytes`` a slab holds: whole tiles of 8 where it
    holds more than one. The device keeps rows in tiles of 8, and a slab
    that ends on one is written into the group's result in place; one that
    ends inside one goes through a temporary of its own size
    (``tests/test_chip_layouts.py``)."""
    rows = max(1, _SLAB_BYTES // max(1, row_bytes))
    return rows - rows % 8 if rows > 8 else rows


def _host_is_device() -> bool:
    """True on the CPU backend: it takes an aligned host buffer as the
    array's own memory, so nothing is transferred and the array reads the
    buffer for as long as it lives. A buffer handed over there is never
    filled again."""
    import jax

    return jax.default_backend() == "cpu"


class ImageTransformer(HasInputCol, HasOutputCol, Transformer):
    """Applies a list of image stages to an image column."""

    stages = Param("List of {'op': name, ...} stage dicts", default=[])
    toFloat = Param(
        "Emit float32 images (skip uint8 round-trip)", default=False, converter=to_bool
    )

    inputCol = Param("Image column", default="image", converter=to_str)
    outputCol = Param("Output image column", default="image_out", converter=to_str)

    # -- fluent stage builders (ImageTransformer.scala:70-219) ---------------

    def _add(self, stage: Dict[str, Any]) -> "ImageTransformer":
        self.set("stages", list(self.getStages()) + [stage])
        return self

    def resize(self, height: int, width: int) -> "ImageTransformer":
        return self._add({"op": "ResizeImage", "height": height, "width": width})

    def crop(self, x: int, y: int, height: int, width: int) -> "ImageTransformer":
        return self._add(
            {"op": "CropImage", "x": x, "y": y, "height": height, "width": width}
        )

    def color_format(self, fmt: str) -> "ImageTransformer":
        return self._add({"op": "ColorFormat", "format": fmt})

    def flip(self, flip_code: int = 1) -> "ImageTransformer":
        return self._add({"op": "Flip", "flipCode": flip_code})

    def blur(self, height: int, width: int) -> "ImageTransformer":
        return self._add({"op": "Blur", "height": height, "width": width})

    def threshold(self, threshold: float, max_val: float = 255.0) -> "ImageTransformer":
        return self._add(
            {"op": "Threshold", "threshold": threshold, "maxVal": max_val}
        )

    def gaussian_kernel(self, aperture_size: int, sigma: float = 0.0) -> "ImageTransformer":
        return self._add(
            {"op": "GaussianKernel", "apertureSize": aperture_size, "sigma": sigma}
        )

    def normalize(self, mean: Any, std: Any, scale: float = 1.0) -> "ImageTransformer":
        return self._add({"op": "Normalize", "mean": mean, "std": std, "scale": scale})

    # -- execution -----------------------------------------------------------

    def _pipeline(self) -> Tuple[Callable, Callable, Dict[Tuple[int, ...], Tuple[int, ...]]]:
        """``(stages, run, shapes)``, built once a process for a stage list's
        content (``core.device.cached_program``; a fresh list of equal dicts
        finds it): the stages as one NHWC -> NHWC function, the jitted
        program over one shape group, ``run(slabs, shape) -> flat result``
        (``slabs``: the group's rows in order, cut into one array or more),
        and the result shape ``stages`` gave each batch shape seen so far
        (so ``jax.eval_shape`` traces them once a shape, not once a call).
        The program takes and returns ``(rows, H * W * C)`` and reshapes to
        NHWC inside, so that what crosses the host boundary is in the host's
        row order: the
        TPU keeps a 4-D image batch with the batch dimension minor-most and
        ``device_get`` hands a device layout back as strides, so a 4-D
        result arrives with every image scattered across the whole buffer
        and the first reader of its rows pays a strided gather (3.7 GB at
        0.17 GB/s: PERF.md, PR 26). Where the stages change no shape the
        reshapes cancel and the program moves nothing."""
        stage_list = self.getStages()
        return cached_program(
            ("image.pipeline", frozen(stage_list)), lambda: _build_pipeline(stage_list)
        )

    def _staged(self, table: Table, whole: Any, fetch: bool) -> Iterator[Tuple[List[int], Tuple[int, ...], Any]]:
        """The stage up to and including its program, one shape group at a
        time, under the caller's ``image.transform`` span ``whole``: ->
        (the group's row indices, the shape its result has as a column,
        ``(rows, H, W, C)`` or ``(rows, H, W)`` for gray rows, and the
        result itself as the program returned it, ``(rows, H*W*C)`` float32).

        The rows reach the device a slab of ``_SLAB_BYTES`` at a time: a
        group of at most one slab is one ``np.stack`` into a fresh batch and
        one upload, as it always was; a larger one is stacked slab by slab
        into two staging buffers that the call owns and fills in turn
        (``image.stack`` a slab), each slab's upload (``image.apply_fetch``,
        ``bytes_down`` 0) running while the next is stacked, and the stage
        program is handed all of them. ``whole`` counts ``slabs`` and, of
        those, the ones stacked into a buffer an earlier slab of the call
        had used (``staging_reused``: all but two of a group's; none on the
        CPU backend, :func:`_host_is_device`). The group's last
        ``image.apply_fetch`` also holds the stage program's enqueue.
        With ``fetch`` the result is brought to the host inside
        that span, which then owns the wait on the device;
        without, it stays the ``jax.Array`` the program returned, the span
        holds the upload's and the program's enqueue and ``bytes_down`` is 0:
        whoever reads the array first waits (``ImageFeaturizer`` hands it to
        ``DNNModel``'s batch loop, so the first ``dnn.fetch`` does)."""
        import jax

        tracer = get_tracer()
        col = table.column(self.getInputCol())
        built_before = programs_built()
        stages, run, shapes = self._pipeline()
        whole.tags["programs_built"] = programs_built() - built_before
        images = [np.asarray(im) for im in col]
        # Group equal-shape images into device batches: one compile per
        # distinct input shape, one program execution per group.
        by_shape: Dict[Tuple[int, ...], List[int]] = {}
        for i, im in enumerate(images):
            by_shape.setdefault(im.shape, []).append(i)
        whole.tags["groups"] = len(by_shape)
        refill = not _host_is_device()
        whole.tags["slabs"] = whole.tags["staging_reused"] = 0
        for shape, idxs in by_shape.items():
            n, lo = len(idxs), 0
            # NHWC; gray rows come without a channel axis
            batch_shape = (n,) + shape if len(shape) == 3 else (n,) + shape + (1,)
            staging: List[np.ndarray] = []
            slabs: List[Any] = []
            while lo < n:
                with tracer.span("image.stack") as sp:
                    if not slabs:  # what one np.stack of the group works out
                        rows = [images[i] for i in idxs]
                        dtype = np.result_type(*{row.dtype for row in rows})
                        per_slab = _slab_rows(rows[0].size * dtype.itemsize)
                    hi = min(lo + per_slab, n)
                    if len(slabs) < 2 or not refill:
                        staging.append(np.empty((hi - lo,) + shape, dtype))
                        buf = staging[-1]
                    else:  # the slab before the last is up (waited for below): its buffer again
                        buf = staging[len(slabs) % 2][: hi - lo]
                        whole.tags["staging_reused"] += 1
                    np.stack(rows[lo:hi], out=buf)
                    sp.tags["bytes"] = buf.nbytes
                with tracer.span("image.apply_fetch", bytes_up=buf.nbytes, bytes_down=0) as sp:
                    slabs.append(jax.device_put(buf.reshape(hi - lo, -1)))
                    if hi == n:
                        out_shape = shapes.get(batch_shape)
                        if out_shape is None:
                            out_shape = shapes[batch_shape] = jax.eval_shape(
                                stages, jax.ShapeDtypeStruct(batch_shape, dtype)).shape
                        flat = run(tuple(slabs), batch_shape)
                        if fetch:
                            flat = np.asarray(jax.device_get(flat))
                            sp.tags["bytes_down"] = flat.nbytes
                    elif refill and len(slabs) > 1:
                        # this slab goes up while the next is stacked into
                        # the buffer the slab before this one came from, and
                        # the runtime reads a host buffer until its transfer
                        # is complete: that one has had a whole slab's
                        # stacking, so this waits only where the wire is
                        # slower than the stacking
                        slabs[-2].block_until_ready()
                lo = hi
            whole.tags["slabs"] += len(slabs)
            if out_shape[-1] == 1 and len(shape) == 2:
                out_shape = out_shape[:-1]  # gray rows came without a channel axis
            yield idxs, out_shape, flat

    def _device_groups(self, table: Table) -> List[Tuple[List[int], Tuple[int, ...], Any]]:
        """``transform`` without its second half, for a device stage that
        comes next: every shape group's (row indices, result shape, result)
        of :meth:`_staged` with the result left on the device. Float32
        whatever ``toFloat`` says: the uint8 path's clip and round are host
        work of ``transform``."""
        with get_tracer().span("image.transform", rows=table.num_rows) as whole:
            return list(self._staged(table, whole, fetch=False))

    def transform(self, table: Table) -> Table:
        """Spans (``observability/tracing``): ``image.transform`` around the
        whole stage (``programs_built``: 1 where this call had to build the
        stage program, 0 where an earlier call had; ``slabs`` and
        ``staging_reused``: :meth:`_staged`); per shape group, once a slab,
        ``image.stack`` (rows to one host batch) and ``image.apply_fetch``
        (upload, and in the group's last the stage program and the download:
        it owns the wait on the device), then ``image.assemble`` (the fetch
        as NHWC, clip/round of the uint8 path, gray squeeze); one more
        ``image.assemble`` around the output column (``_image_column``).
        An ``image.assemble``'s ``bytes`` is what it copied. Byte tags come
        from shapes."""
        tracer = get_tracer()
        with tracer.span("image.transform", rows=table.num_rows) as whole:
            groups: List[Tuple[List[int], np.ndarray]] = []
            to_float = self.getToFloat()
            for idxs, out_shape, flat in self._staged(table, whole, fetch=True):
                with tracer.span("image.assemble") as sp:
                    # the fetch is in row order unless the device kept this
                    # 2-D shape column-major (it does where that pads less);
                    # then one gather here instead of one in every reader
                    copied = 0 if flat.flags.c_contiguous else flat.nbytes
                    result = np.ascontiguousarray(flat).reshape(out_shape)
                    if not to_float:
                        result = np.clip(np.rint(result), 0, 255).astype(np.uint8)
                        copied += result.nbytes
                    sp.tags["bytes"] = copied
                groups.append((idxs, result))
            with tracer.span("image.assemble") as sp:
                column, sp.tags["bytes"] = _image_column(groups, table.num_rows)
                return table.with_column(self.getOutputCol(), column)


def _image_column(
    groups: List[Tuple[List[int], np.ndarray]], n: int
) -> Tuple[np.ndarray, int]:
    """The output column over ``n`` rows from each shape group's (row
    indices, fetched result), and the bytes it copied. One group holds every
    row in order, so its result is the column as fetched (possibly a
    read-only view of the device's buffer: a Table's columns are immutable).
    Groups whose results share a shape are written once into one dense
    array, rows in input order. Otherwise an object column of row views."""
    if not groups:
        return np.empty(0), 0
    if len(groups) == 1:
        return groups[0][1], 0
    if len({result.shape[1:] for _, result in groups}) == 1:
        first = groups[0][1]
        dense = np.empty((n,) + first.shape[1:], dtype=first.dtype)
        for idxs, result in groups:
            dense[idxs] = result
        return dense, dense.nbytes
    rows = np.empty(n, dtype=object)
    for idxs, result in groups:
        for j, i in enumerate(idxs):
            rows[i] = result[j]
    return rows, 0


class ImageSetAugmenter(HasInputCol, HasOutputCol, Transformer):
    """Flip-based dataset augmentation (``image/ImageSetAugmenter.scala``):
    emits the original rows plus a flipped copy per enabled axis."""

    inputCol = Param("Image column", default="image", converter=to_str)
    outputCol = Param("Output image column", default="image", converter=to_str)
    flipLeftRight = Param("Mirror horizontally", default=True, converter=to_bool)
    flipUpDown = Param("Mirror vertically", default=False, converter=to_bool)

    def transform(self, table: Table) -> Table:
        in_col, out_col = self.getInputCol(), self.getOutputCol()
        base = table if in_col == out_col else table.with_column(
            out_col, table.column(in_col)
        )
        results = [base]
        if self.getFlipLeftRight():
            flipped = ImageTransformer(
                inputCol=in_col, outputCol=out_col, stages=[
                    {"op": "Flip", "flipCode": 1}
                ]
            ).transform(table)
            results.append(flipped)
        if self.getFlipUpDown():
            flipped = ImageTransformer(
                inputCol=in_col, outputCol=out_col, stages=[
                    {"op": "Flip", "flipCode": 0}
                ]
            ).transform(table)
            results.append(flipped)
        return Table.concat(results)
