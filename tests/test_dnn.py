"""Deep inference stack: DNNModel, torch import, ResNet zoo
(reference ``cntk/`` suites — SURVEY.md §2.4)."""

import numpy as np
import pytest

from mmlspark_tpu.data.table import Table
from mmlspark_tpu.dnn import DNNModel, from_torch
from mmlspark_tpu.models import init_resnet, resnet_apply


def _torch_cnn():
    import torch.nn as nn

    return nn.Sequential(
        nn.Conv2d(3, 8, 3, stride=1, padding=1),
        nn.BatchNorm2d(8),
        nn.ReLU(),
        nn.MaxPool2d(2),
        nn.Conv2d(8, 16, 3, stride=2, padding=1, groups=2),
        nn.ReLU(),
        nn.AdaptiveAvgPool2d((1, 1)),
        nn.Flatten(),
        nn.Linear(16, 5),
        nn.Softmax(dim=-1),
    )


class _ResidualNet:
    """Built lazily so torch imports stay inside tests."""

    def __new__(cls):
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        class Block(nn.Module):
            def __init__(self):
                super().__init__()
                self.conv1 = nn.Conv2d(4, 4, 3, padding=1)
                self.conv2 = nn.Conv2d(4, 4, 3, padding=1)
                self.fc = nn.Linear(4, 3)

            def forward(self, x):
                h = F.relu(self.conv1(x))
                h = self.conv2(h) + x  # residual add
                h = torch.flatten(F.adaptive_avg_pool2d(h, (1, 1)), 1)
                return self.fc(h)

        return Block()


def test_torch_import_matches_torch():
    import torch

    torch.manual_seed(0)
    net = _torch_cnn().eval()
    x = np.random.default_rng(0).standard_normal((4, 3, 16, 16)).astype(np.float32)
    with torch.no_grad():
        expected = net(torch.from_numpy(x)).numpy()
    fn, params = from_torch(net)
    got = np.asarray(fn(params, {"input": x})["output"])
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_torch_import_residual():
    import torch

    torch.manual_seed(1)
    net = _ResidualNet().eval()
    x = np.random.default_rng(1).standard_normal((2, 4, 8, 8)).astype(np.float32)
    with torch.no_grad():
        expected = net(torch.from_numpy(x)).numpy()
    fn, params = from_torch(net)
    got = np.asarray(fn(params, {"input": x})["output"])
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_dnn_model_transform_batched():
    import torch

    torch.manual_seed(0)
    net = _torch_cnn().eval()
    fn, params = from_torch(net)
    n = 23  # deliberately not a multiple of batchSize: exercises padding
    images = np.random.default_rng(2).standard_normal((n, 3, 16, 16)).astype(np.float32)
    t = Table({"id": np.arange(n), "images": [img for img in images]})
    model = DNNModel(
        applyFn=fn,
        modelParams=params,
        feedDict={"input": "images"},
        fetchDict={"scores": "output"},
        batchSize=8,
    )
    out = model.transform(t)
    assert out["scores"].shape == (n, 5)
    with torch.no_grad():
        expected = net(torch.from_numpy(images)).numpy()
    np.testing.assert_allclose(out["scores"], expected, rtol=1e-4, atol=1e-5)


def _stack_batch_reference(col, pad_to, dtype):
    """``_stack_batch`` as it was before it learned to hand over a view: the
    plain reference (a list of rows, ``np.stack``, ``astype``, zero pad)."""
    rows = [np.asarray(v) for v in col]
    batch = np.stack(rows).astype(dtype)
    if len(rows) < pad_to:
        pad = np.zeros((pad_to - len(rows),) + batch.shape[1:], dtype=batch.dtype)
        batch = np.concatenate([batch, pad])
    return batch


def _object_column(rows):
    col = np.empty(len(rows), dtype=object)
    for i, row in enumerate(rows):
        col[i] = row
    return col


def _readonly(a):
    a.flags.writeable = False
    return a


_IMAGES = np.random.default_rng(5).standard_normal((12, 4, 4, 3))
_IMAGES32 = _IMAGES.astype(np.float32)
_PAD_TO = 4
_STACK_CASES = {
    # name: (column, the batch's rows, dtype, how the batch is made)
    "dense_full": (_IMAGES32, slice(4, 8), "float32", "view"),
    "dense_full_readonly": (_readonly(_IMAGES32.copy()), slice(0, 4), "float32", "view"),
    "dense_padded": (_IMAGES32, slice(8, 11), "float32", "copy"),
    "dense_float64_to_float32": (_IMAGES, slice(0, 4), "float32", "copy"),
    "dense_int32_tokens": (np.arange(96, dtype=np.int32).reshape(8, 12), slice(4, 8), "int32", "view"),
    "dense_scalars_padded": (np.arange(6.0), slice(4, 6), "float32", "copy"),
    "dense_strided": (_IMAGES32, slice(0, 8, 2), "float32", "copy"),
    "dense_transposed_rows": (_IMAGES32.transpose(0, 3, 1, 2), slice(0, 4), "float32", "copy"),
    "object_equal_rows": (_object_column(list(_IMAGES32)), slice(0, 4), "float32", "copy"),
    "object_padded_cast": (_object_column(list(_IMAGES)), slice(9, 12), "float32", "copy"),
    "object_unequal_rows": (
        _object_column([np.zeros((2, 3)), np.zeros((3, 3)), np.zeros((2, 3))]), slice(0, 3), "float32", "raises"),
}


@pytest.mark.parametrize("case", sorted(_STACK_CASES))
def test_stack_batch_equals_the_row_by_row_reference(case):
    """Every path of ``_stack_batch`` gives the old implementation's batch
    byte for byte; a dense, contiguous, equal-dtype full slice is handed
    over as a view (also of a read-only column), anything else is a fresh
    writable batch."""
    from mmlspark_tpu.dnn.model import _stack_batch

    column, which, dtype, how = _STACK_CASES[case]
    rows, dtype = column[which], np.dtype(dtype)
    if how == "raises":
        with pytest.raises(ValueError):
            _stack_batch_reference(rows, _PAD_TO, dtype)
        with pytest.raises(ValueError):
            _stack_batch(rows, _PAD_TO, dtype)
        return
    want = _stack_batch_reference(rows, _PAD_TO, dtype)
    before = np.array(rows, copy=True)
    got = _stack_batch(rows, _PAD_TO, dtype)
    assert (got.shape, got.dtype) == (want.shape, want.dtype)
    assert len(got) == _PAD_TO and got.dtype == dtype
    assert got.flags.c_contiguous
    assert got.tobytes() == want.tobytes()
    if how == "view":
        assert got is rows and np.shares_memory(got, column)
    else:
        assert not np.shares_memory(got, column) and got.flags.writeable
        if column.dtype != object:
            np.testing.assert_array_equal(rows, before)  # the column is not written


def test_dnn_model_same_features_from_dense_and_object_columns():
    """The view path (full batches of a read-only dense float32 column), the
    one-copy path (its padded last batch) and the stacked path (the same rows
    as an object column) feed the program the same bytes."""
    from mmlspark_tpu.observability.tracing import get_tracer

    rng = np.random.default_rng(7)
    dense = _readonly(rng.standard_normal((11, 6)).astype(np.float32))
    weights = rng.standard_normal((6, 3)).astype(np.float32)
    model = DNNModel(
        applyFn=lambda params, inputs: {"output": inputs["x"] @ params["w"]},
        modelParams={"w": weights}, feedDict={"x": "x"},
        fetchDict={"y": "output"}, batchSize=4,
    )
    tracer = get_tracer()
    tracer.clear()
    from_dense = model.transform(Table({"x": dense}))["y"]
    from_rows = model.transform(Table({"x": _object_column(list(dense))}))["y"]
    assert from_dense.shape == (11, 3)
    assert from_dense.tobytes() == from_rows.tobytes()
    np.testing.assert_allclose(from_dense, dense @ weights, rtol=1e-5, atol=1e-6)
    copied = [s["tags"]["bytes"] for s in tracer.export() if s["name"] == "dnn.stack"]
    batch = 4 * 6 * 4
    assert copied == [0, 0, batch] + [batch] * 3


def test_dnn_model_sharded(mesh8):
    fn = lambda params, inputs: {"output": inputs["x"] * params["scale"]}
    n = 40
    t = Table({"x": np.arange(n, dtype=np.float32)})
    model = DNNModel(
        applyFn=fn,
        modelParams={"scale": np.float32(3.0)},
        feedDict={"x": "x"},
        fetchDict={"y": "output"},
        batchSize=16,
        shardOverMesh=True,
    )
    out = model.transform(t)
    np.testing.assert_allclose(out["y"], np.arange(n) * 3.0)


def test_place_leaves_a_caller_committed_leaf_where_it_is(mesh8):
    """An ``applyFn`` that runs its own parallel op is handed parameters the
    caller placed on the mesh: the single-device ``place`` returns such a leaf
    as the same object, and puts a numpy leaf on the device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = jax.device_put(
        np.arange(16, dtype=np.float32).reshape(8, 2), NamedSharding(mesh8, P("data"))
    )
    model = DNNModel(
        applyFn=lambda p, i: i["x"], modelParams={"w": sharded, "b": np.ones(2)}
    )
    _, place = model._jitted()
    placed = place(model.getModelParams())
    assert placed["w"] is sharded
    assert isinstance(placed["b"], jax.Array)


def test_dnn_model_declares_exactly_its_ten_params():
    from mmlspark_tpu.core.params import Param

    declared = {k for k, v in vars(DNNModel).items() if isinstance(v, Param)}
    assert declared == {
        "applyFn", "modelParams", "feedDict", "fetchDict", "batchSize",
        "miniBatcher", "inputDtype", "paramShardings", "meshConfig",
        "shardOverMesh",
    }


def test_dnn_model_single_io_convenience():
    fn = lambda params, inputs: inputs["input"] + 1.0
    model = (
        DNNModel(applyFn=fn, modelParams={}, batchSize=4)
        .setInputCol("x")
        .setOutputCol("y")
    )
    t = Table({"x": np.arange(6, dtype=np.float32)})
    out = model.transform(t)
    np.testing.assert_allclose(out["y"], np.arange(6) + 1.0)
    assert model.getInputCol() == "x" and model.getOutputCol() == "y"


def test_dnn_model_missing_feed():
    model = DNNModel(applyFn=lambda p, i: i, modelParams={})
    with pytest.raises(ValueError):
        model.transform(Table({"x": np.arange(3.0)}))


def test_resnet_shapes_and_cut():
    import jax

    params = init_resnet(variant="resnet18", num_classes=7, small_inputs=True)
    x = np.random.default_rng(0).standard_normal((2, 3, 32, 32)).astype(np.float32)
    logits = jax.jit(lambda p, v: resnet_apply(p, v))(params, x)
    assert logits.shape == (2, 7)
    feats = resnet_apply(params, x, cut=1)
    assert feats.shape == (2, 512)
    fmap = resnet_apply(params, x, cut=2)
    assert fmap.shape == (2, 512, 4, 4)


def test_resnet50_bottleneck():
    params = init_resnet(variant="resnet50", num_classes=3, small_inputs=True)
    x = np.zeros((1, 3, 32, 32), np.float32)
    feats = resnet_apply(params, x, cut=1)
    assert feats.shape == (1, 2048)


def test_resnet_in_dnn_model():
    params = init_resnet(variant="resnet18", num_classes=4, small_inputs=True)
    fn = lambda p, inputs: {"output": resnet_apply(p, inputs["input"])}
    images = np.random.default_rng(3).standard_normal((5, 3, 32, 32)).astype(np.float32)
    t = Table({"images": [im for im in images]})
    model = DNNModel(
        applyFn=fn,
        modelParams=params,
        feedDict={"input": "images"},
        fetchDict={"scores": "output"},
        batchSize=4,
    )
    out = model.transform(t)
    assert out["scores"].shape == (5, 4)
    assert np.isfinite(out["scores"]).all()


def test_onnx_gate():
    from mmlspark_tpu.dnn import onnx_import

    if not onnx_import.onnx_available():
        with pytest.raises(ImportError):
            onnx_import.from_onnx("/tmp/nope.onnx")


# -- the program is built once a process (core.device.cached_program) ----------

def _counting_fn():
    """An ``applyFn`` whose Python body notes every trace of it."""
    traces = []

    def fn(params, inputs):
        traces.append(1)
        return {"output": inputs["x"] * params["scale"] + params["shift"]}

    return fn, traces


def _model(fn, scale=3.0, shift=0.0, **params):
    return DNNModel(
        applyFn=fn, modelParams={"scale": np.float32(scale), "shift": np.float32(shift)},
        feedDict={"x": "x"}, fetchDict={"y": "output"}, batchSize=8, **params,
    )


def _transform(model, table):
    """(the output column, the ``dnn.transform`` span's ``programs_built``)."""
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    tracer.clear()
    out = np.asarray(model.transform(table)["y"])
    (built,) = [s["tags"]["programs_built"] for s in tracer.export() if s["name"] == "dnn.transform"]
    return out, built


_X = np.arange(20, dtype=np.float32)


class _Scaler:
    def __init__(self, traces):
        self.traces = traces

    def apply(self, params, inputs):
        self.traces.append(1)
        return {"output": inputs["x"] * params["scale"] + params["shift"]}



def _apply_fn_pairs():
    """name -> () -> (the two models' applyFns, the trace list they share),
    and how many programs the second model has to build."""
    import functools

    def plain():
        fn, traces = _counting_fn()
        return (fn, fn), traces

    def partial_object():
        fn, traces = _counting_fn()
        bound = functools.partial(fn)
        return (bound, bound), traces

    def bound_method_read_twice():
        scaler = _Scaler([])
        return (scaler.apply, scaler.apply), scaler.traces  # two method objects, equal

    def two_closures_of_one_source():
        traces = []

        def make():
            def fn(params, inputs):
                traces.append(1)
                return {"output": inputs["x"] * params["scale"] + params["shift"]}
            return fn

        return (make(), make()), traces

    def two_partials_of_one_function():
        fn, traces = _counting_fn()
        return (functools.partial(fn), functools.partial(fn)), traces

    return {
        "one_function": (plain, 0), "one_partial": (partial_object, 0),
        "one_bound_method": (bound_method_read_twice, 0),
        "two_closures": (two_closures_of_one_source, 1),
        "two_partials": (two_partials_of_one_function, 1),
    }


@pytest.mark.parametrize("case", sorted(_apply_fn_pairs()))
def test_two_models_of_one_apply_fn_build_one_program(case):
    """The key is the ``applyFn`` object: a second ``DNNModel`` with the same
    one finds the first's program (traced once, ``programs_built`` 0) and runs
    it over its own weights; another object of the same source is another
    key, as it is to JAX."""
    make, second_builds = _apply_fn_pairs()[case]
    (first, second), traces = make()
    table = Table({"x": _X})
    out, built = _transform(_model(first, scale=3.0), table)
    assert built == 1 and len(traces) == 1
    np.testing.assert_array_equal(out, _X * 3.0)
    out, built = _transform(_model(second, scale=-2.0, shift=1.0), table)  # other weights
    assert built == second_builds and len(traces) == 1 + second_builds
    np.testing.assert_array_equal(out, _X * -2.0 + 1.0)
    # the same instance again, a table of another length: the batch shape is the same
    out, built = _transform(_model(second, scale=0.5), Table({"x": _X[:11]}))
    assert built == 0 and len(traces) == 1 + second_builds
    np.testing.assert_array_equal(out, _X[:11] * 0.5)


def test_a_new_batch_shape_retraces_under_the_cached_program():
    fn, traces = _counting_fn()
    table = Table({"x": _X})
    _transform(_model(fn), table)
    out, built = _transform(_model(fn).setBatchSize(5), table)
    assert built == 0 and len(traces) == 2  # jit re-specialises; nothing was rebuilt
    np.testing.assert_array_equal(out, _X * 3.0)


_MESH_KEYS = {
    # name: (params of the second model, programs it builds)
    "same": ({}, 0),
    "mesh_config": ({"meshConfig": "model2"}, 1),
    "param_shardings": ({"paramShardings": {"w": 1}}, 1),
    "param_shardings_equal_content": ({"paramShardings": {"w": 0}}, 0),
}


@pytest.mark.parametrize("case", sorted(_MESH_KEYS))
def test_under_a_mesh_the_key_holds_the_mesh_and_the_shardings(mesh8, case):
    """``run`` and ``place`` close over the mesh and ``paramShardings``: a
    change in either is a program of its own, and each places the weights as
    its own value demands."""
    import jax

    from mmlspark_tpu.parallel.mesh import MeshConfig

    traces = []

    def fn(params, inputs):
        traces.append(1)
        return {"output": inputs["x"] @ params["w"]}

    w = np.arange(32, dtype=np.float32).reshape(4, 8)
    x = np.random.default_rng(0).standard_normal((16, 4)).astype(np.float32)

    def model(**params):
        if params.get("meshConfig") == "model2":
            params["meshConfig"] = MeshConfig(data=4, model=2)
        params.setdefault("paramShardings", {"w": 0})
        return DNNModel(applyFn=fn, modelParams={"w": w}, feedDict={"x": "x"},
                        fetchDict={"y": "output"}, batchSize=8, shardOverMesh=True, **params)

    def run(m):
        return _transform(m, Table({"x": x}))

    out, built = run(model())
    assert built == 1
    np.testing.assert_allclose(out, x @ w, rtol=1e-5)
    overrides, builds = _MESH_KEYS[case]
    second = model(**overrides)
    out, built = run(second)
    assert built == builds
    np.testing.assert_allclose(out, x @ w, rtol=1e-5)
    _, place = second._jitted()
    placed = place({"w": w})["w"]
    axis = second.getParamShardings()["w"]
    spec = [None, None]
    spec[axis] = "model"
    assert tuple(placed.sharding.spec) == tuple(spec)
    model_axis = (second.getMeshConfig() or MeshConfig()).resolve(len(jax.devices()))["model"]
    assert placed.sharding.mesh.shape["model"] == model_axis


def test_the_lru_evicts_at_its_bound_and_a_rebuilt_program_gives_the_same_bytes(monkeypatch):
    from mmlspark_tpu.core import device

    monkeypatch.setattr(device, "_PROGRAM_CACHE_SIZE", 2)
    device._PROGRAM_CACHE.clear()
    fns = [_counting_fn() for _ in range(3)]
    table = Table({"x": _X})
    first, built = _transform(_model(fns[0][0]), table)
    assert built == 1
    for fn, _ in fns[1:]:
        assert _transform(_model(fn), table)[1] == 1
    assert len(device._PROGRAM_CACHE) == 2
    assert ("dnn", fns[0][0]) not in device._PROGRAM_CACHE  # the oldest went
    assert _transform(_model(fns[2][0]), table)[1] == 0  # the newest stayed
    again, built = _transform(_model(fns[0][0]), table)
    assert built == 1 and again.tobytes() == first.tobytes()
    assert len(device._PROGRAM_CACHE) == 2


def test_two_threads_that_transform_at_once_build_one_program():
    """``ServingServer``'s batch loop and a user's thread may both call
    ``transform``: the look-up is under a lock, so one of them builds and the
    other finds, and each tag counts its own thread's builds only."""
    import sys
    import threading

    from mmlspark_tpu.observability.tracing import get_tracer

    fn, _ = _counting_fn()
    table = Table({"x": _X})
    workers, results = 8, {}
    start = threading.Barrier(workers)

    def work(i):
        start.wait(timeout=30)
        results[i] = np.asarray(_model(fn, scale=float(i)).transform(table)["y"])

    tracer = get_tracer()
    tracer.clear()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    built = [s["tags"]["programs_built"] for s in tracer.export() if s["name"] == "dnn.transform"]
    assert sorted(built) == [0] * (workers - 1) + [1]
    for i in range(workers):
        np.testing.assert_array_equal(results[i], _X * float(i))


def test_a_weight_is_in_no_key_and_in_nothing_cached():
    """The cache must not pin weights: what it holds for a model is the
    jitted ``applyFn`` and a placing function, neither of which references
    ``modelParams``."""
    import gc
    import weakref

    from mmlspark_tpu.core import device

    fn = lambda params, inputs: {"output": inputs["x"] * params["scale"]}  # noqa: E731
    scale = np.full((1,), 2.0, np.float32)
    model = DNNModel(applyFn=fn, modelParams={"scale": scale}, feedDict={"x": "x"}, fetchDict={"y": "output"})
    np.testing.assert_array_equal(np.asarray(model.transform(Table({"x": _X}))["y"]), _X * 2.0)
    assert ("dnn", fn) in device._PROGRAM_CACHE
    alive = weakref.ref(scale)
    del scale, model
    gc.collect()
    assert alive() is None


@pytest.mark.parametrize("value,same,other", [
    ({"a": [1, 2], "b": {"c": "x"}}, {"b": {"c": "x"}, "a": [1, 2]}, {"a": [1, 3], "b": {"c": "x"}}),
    ([{"op": "Flip", "flipCode": 1}], [{"flipCode": 1, "op": "Flip"}], [{"op": "Flip", "flipCode": 0}]),
    ({"mean": np.array([1.0, 2.0])}, {"mean": np.array([1.0, 2.0])}, {"mean": np.array([1.0, 2.5])}),
    ({"mean": np.zeros(2, np.float32)}, {"mean": np.zeros(2, np.float32)}, {"mean": np.zeros(2, np.float64)}),
    ((1, "a"), [1, "a"], (1, "b")),
    ({"s": {1, 2}}, {"s": {1, 2}}, {"s": {1, 3}}),
], ids=["nested_dict", "stage_list", "array", "array_dtype", "tuple", "unhashable_leaf_by_repr"])
def test_frozen_is_a_key_by_content(value, same, other):
    from mmlspark_tpu.core.device import frozen

    assert frozen(value) == frozen(same) and hash(frozen(value)) == hash(frozen(same))
    assert frozen(value) != frozen(other)


# -- a fed column that lives on the device (DNNModel._transform) ---------------

def _spans_of(job):
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    tracer.clear()
    out = job()
    return out, tracer.export()


def _pair_model(**params):
    """Two fed columns: rows of 6 floats and rows handed over flat."""
    fn = lambda p, i: {"output": i["a"].sum(axis=(1, 2)) * p["scale"] + i["b"][:, 0]}
    return DNNModel(
        applyFn=fn, modelParams={"scale": np.float32(0.5)},
        feedDict={"a": "a", "b": "b"}, fetchDict={"y": "output"}, batchSize=4, **params,
    )


_DEVICE_CASES = {
    # name: (rows, miniBatcher, inputDtype, dtype of the column on the device, which columns are fed from it)
    "full_batches": (12, True, "float32", np.float32, ("a", "b")),
    "short_last_batch": (10, True, "float32", np.float32, ("a", "b")),
    "fewer_rows_than_a_batch": (3, True, "float32", np.float32, ("a", "b")),
    "one_batch_of_every_row": (10, False, "float32", np.float32, ("a", "b")),
    "cast_on_the_device": (10, True, "float32", np.uint8, ("a", "b")),
    "cast_to_a_narrower_dtype": (10, True, "bfloat16", np.float32, ("a", "b")),
    "one_column_of_two_on_the_device": (10, True, "float32", np.float32, ("a",)),
    "every_column_on_the_host": (10, True, "float32", np.float32, ()),
}


@pytest.mark.parametrize("case", sorted(_DEVICE_CASES))
def test_a_device_column_is_batched_where_it_lives_and_gives_the_host_columns_bits(case):
    import jax
    import jax.numpy as jnp

    rows, mini, input_dtype, col_dtype, on_device = _DEVICE_CASES[case]
    rng = np.random.default_rng(5)
    host = {"a": rng.integers(0, 200, size=(rows, 2, 3)).astype(col_dtype),
            "b": rng.integers(0, 200, size=(rows, 1)).astype(col_dtype)}
    table = Table(host)
    model = _pair_model(miniBatcher=mini, inputDtype=input_dtype)
    want, host_spans = _spans_of(lambda: model.transform(table))
    # "a" is handed over as a stage program would leave it: (rows, 2 * 3)
    fed = {name: jnp.asarray(host[name].reshape(rows, -1)) for name in on_device}
    got, spans = _spans_of(lambda: model._transform(table, fed, {"a": (2, 3)}))
    assert got.columns == want.columns
    assert got["y"].dtype == want["y"].dtype
    np.testing.assert_array_equal(got["y"], want["y"])

    def tags(recorded, name):  # less what a first call paid JAX to compile, booked on the same spans
        from mmlspark_tpu.observability.tracing import COMPILE_TAGS

        return [{k: v for k, v in s["tags"].items() if k not in COMPILE_TAGS}
                for s in recorded if s["name"] == name]

    batches = -(-rows // 4) if mini else 1
    all_on_device = len(on_device) == 2
    (whole,), (host_whole,) = tags(spans, "dnn.transform"), tags(host_spans, "dnn.transform")
    assert whole["batches"] == host_whole["batches"] == batches
    assert whole["device_batches"] == (batches if all_on_device else 0)
    assert host_whole["device_batches"] == 0
    itemsize = np.dtype(input_dtype).itemsize
    pad_to = 4 if mini else rows
    crossing = sum(pad_to * width * itemsize for name, width in (("a", 6), ("b", 1))
                   if name not in on_device)
    assert [t["bytes"] for t in tags(spans, "dnn.dispatch")] == [crossing] * batches
    assert [t["pad_rows"] for t in tags(spans, "dnn.stack")] == [
        t["pad_rows"] for t in tags(host_spans, "dnn.stack")]
    if all_on_device:
        assert [t["bytes"] for t in tags(spans, "dnn.stack")] == [0] * batches
    if not on_device:  # nothing fed from the device: today's tags, every one
        for name in ("dnn.stack", "dnn.dispatch", "dnn.fetch", "dnn.assemble", "dnn.place_params"):
            assert tags(spans, name) == tags(host_spans, name)
    assert tags(spans, "dnn.fetch") == tags(host_spans, "dnn.fetch")
    assert isinstance(got["y"], np.ndarray) and not isinstance(got["y"], jax.Array)


@pytest.mark.parametrize("rows,programs", [(48, 1), (46, 2)], ids=["twelve_full", "a_short_twelfth"])
def test_twelve_batches_of_a_device_column_build_one_slice_program(monkeypatch, rows, programs):
    """The offset is an argument of the slice program, so every full batch
    runs the one trace; a short last batch is one more (its row count)."""
    import jax.numpy as jnp
    from jax import lax

    import mmlspark_tpu

    mmlspark_tpu.clear_compiled_caches()
    traced = []
    real = lax.dynamic_slice_in_dim
    monkeypatch.setattr(lax, "dynamic_slice_in_dim",
                        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    x = np.arange(rows * 3, dtype=np.float32).reshape(rows, 3)
    model = DNNModel(applyFn=lambda p, i: i["x"] * 2.0, modelParams={},
                     feedDict={"x": "x"}, fetchDict={"y": "output"}, batchSize=4)
    out, spans = _spans_of(lambda: model._transform(Table({"x": x}), {"x": jnp.asarray(x)}, {}))
    np.testing.assert_array_equal(out["y"], x * 2.0)
    (whole,) = [s["tags"] for s in spans if s["name"] == "dnn.transform"]
    assert whole["batches"] == whole["device_batches"] == 12
    assert len(traced) == programs
    # the call's own program is what programs_built counts, as before
    assert whole["programs_built"] == 1
    again, _ = _spans_of(lambda: model._transform(Table({"x": x}), {"x": jnp.asarray(x)}, {}))
    assert len(traced) == programs
    np.testing.assert_array_equal(again["y"], out["y"])
