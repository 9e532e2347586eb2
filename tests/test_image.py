"""Image pipeline (reference ``opencv/``/``image/`` suites — SURVEY.md §2.5)."""

import numpy as np
import pytest

from mmlspark_tpu.data.table import Table
from mmlspark_tpu.image import (
    ImageFeaturizer,
    ImageSetAugmenter,
    ImageTransformer,
    UnrollImage,
    roll_image,
    unroll_image,
)


@pytest.fixture()
def image_table(rng):
    images = np.empty(3, dtype=object)
    for i in range(3):
        images[i] = rng.integers(0, 256, size=(20, 24, 3), dtype=np.uint8)
    return Table({"id": np.arange(3), "image": images})


def test_resize_crop(image_table):
    t = (
        ImageTransformer(inputCol="image", outputCol="out")
        .resize(10, 12)
        .crop(2, 1, 8, 8)
        .transform(image_table)
    )
    assert t["out"][0].shape == (8, 8, 3)
    assert t["out"][0].dtype == np.uint8


def test_flip_matches_numpy(image_table):
    out = (
        ImageTransformer(inputCol="image", outputCol="out")
        .flip(1)
        .transform(image_table)
    )
    np.testing.assert_array_equal(out["out"][0], image_table["image"][0][:, ::-1, :])
    out = (
        ImageTransformer(inputCol="image", outputCol="out")
        .flip(0)
        .transform(image_table)
    )
    np.testing.assert_array_equal(out["out"][0], image_table["image"][0][::-1, :, :])


def test_gray_threshold(image_table):
    out = (
        ImageTransformer(inputCol="image", outputCol="out")
        .color_format("gray")
        .threshold(127.0)
        .transform(image_table)
    )
    img = out["out"][0]
    assert img.shape == (20, 24, 1)
    assert set(np.unique(img)) <= {0, 255}


def test_blur_constant_image():
    images = np.empty(1, dtype=object)
    images[0] = np.full((8, 8, 3), 100, dtype=np.uint8)
    t = Table({"image": images})
    out = (
        ImageTransformer(inputCol="image", outputCol="out")
        .blur(3, 3)
        .transform(t)
    )
    # Box blur of a constant image keeps the interior constant.
    np.testing.assert_array_equal(out["out"][0][2:-2, 2:-2], 100)


def test_gaussian_kernel_smooths(rng):
    images = np.empty(1, dtype=object)
    img = np.zeros((9, 9, 1), dtype=np.uint8)
    img[4, 4, 0] = 255
    images[0] = img
    t = Table({"image": images})
    out = (
        ImageTransformer(inputCol="image", outputCol="out", toFloat=True)
        .gaussian_kernel(5, 1.0)
        .transform(t)
    )
    res = out["out"][0][..., 0]
    assert res[4, 4] == res.max() and res[4, 4] < 255
    assert res[2, 4] > 0


def test_mixed_shapes_grouped(rng):
    images = np.empty(4, dtype=object)
    images[0] = rng.integers(0, 255, (16, 16, 3), dtype=np.uint8)
    images[1] = rng.integers(0, 255, (20, 10, 3), dtype=np.uint8)
    images[2] = rng.integers(0, 255, (16, 16, 3), dtype=np.uint8)
    images[3] = rng.integers(0, 255, (20, 10, 3), dtype=np.uint8)
    t = Table({"image": images})
    out = ImageTransformer(inputCol="image", outputCol="out").resize(8, 8).transform(t)
    assert all(im.shape == (8, 8, 3) for im in out["out"])


# How ImageTransformer builds its output column from what its program
# returned (one case each): input shapes, toFloat, stages, the column's kind.
COLUMN_CASES = {
    "one_group_float": ([(8, 6, 3)] * 4, True, lambda t: t.resize(4, 5), "dense"),
    "one_group_uint8": ([(8, 6, 3)] * 4, False, lambda t: t.resize(4, 5), "dense"),
    "interleaved_sizes_resized_float": (
        [(s, s, 3) for s in (8, 12, 8, 12, 8)], True, lambda t: t.resize(4, 5), "dense"),
    "interleaved_sizes_resized_uint8": (
        [(s, s, 3) for s in (8, 12, 8, 12, 8)], False, lambda t: t.resize(4, 5), "dense"),
    "mixed_results_sharing_their_first_dimension": (
        [(8, 4, 3), (8, 6, 3), (8, 4, 3)], False, lambda t: t.flip(1), "object"),
    "mixed_results": ([(8, 8, 3), (12, 12, 3), (8, 8, 3)], True, lambda t: t.flip(0), "object"),
    "gray_keeps_its_squeeze": ([(8, 6)] * 3, False, lambda t: t.flip(1), "dense"),
    "gray_interleaved_sizes_resized": (
        [(8, 6), (12, 6), (8, 6)], False, lambda t: t.resize(4, 5), "dense"),
    "empty_table": ([], False, lambda t: t.flip(1), "empty"),
}


@pytest.mark.parametrize("case", list(COLUMN_CASES))
def test_output_column_is_built_from_the_fetched_results(case):
    from mmlspark_tpu.observability.tracing import get_tracer

    shapes, to_float, stages, kind = COLUMN_CASES[case]
    rng = np.random.default_rng(7)
    images = np.empty(len(shapes), dtype=object)
    for i, shape in enumerate(shapes):
        # its own fill value and its own noise: row i can only be image i
        images[i] = (20 * i + rng.integers(0, 20, size=shape)).astype(np.uint8)

    def stage():
        return stages(ImageTransformer(inputCol="image", outputCol="out", toFloat=to_float))

    tracer = get_tracer()
    tracer.clear()
    out = stage().transform(Table({"image": images}))
    assembled = [s for s in tracer.export() if s["name"] == "image.assemble"]
    column = out["out"]
    assert out.num_rows == len(shapes) and isinstance(column, np.ndarray)
    if kind == "empty":
        assert column.shape == (0,) and column.dtype == np.float64  # as before
        assert [s["tags"] for s in assembled] == [{"bytes": 0}]
        return
    alone = []
    for i in range(len(shapes)):
        one = np.empty(1, dtype=object)
        one[0] = images[i]
        alone.append(stage().transform(Table({"image": one}))["out"][0])
    dtype = np.float32 if to_float else np.uint8
    if kind == "dense":
        assert column.dtype == dtype and column.flags.c_contiguous
        assert column.shape == (len(shapes),) + alone[0].shape
        assert column.ndim == 1 + len(shapes[0])  # (n, H, W, C); gray (n, H, W)
    else:
        assert column.dtype == object and column.shape == (len(shapes),)
        assert [im.shape for im in column] == list(shapes)
    for i, want in enumerate(alone):
        assert column[i].dtype == dtype
        np.testing.assert_array_equal(column[i], want)
    # one image.assemble per shape group, then the column's: the only copy
    # it may make is the one scatter into a dense column of several groups
    groups = len(set(shapes))
    assert len(assembled) == groups + 1
    scattered = kind == "dense" and groups > 1
    assert assembled[-1]["tags"] == {"bytes": column.nbytes if scattered else 0}


def test_augmenter(image_table):
    out = ImageSetAugmenter(inputCol="image", outputCol="image").transform(image_table)
    assert out.num_rows == 6
    np.testing.assert_array_equal(out["image"][3], image_table["image"][0][:, ::-1, :])


def test_unroll_roll_roundtrip(image_table):
    out = UnrollImage(inputCol="image", outputCol="vec").transform(image_table)
    vec = out["vec"]
    assert vec.shape == (3, 20 * 24 * 3)
    rolled = roll_image(vec[0], 20, 24, 3)
    np.testing.assert_array_equal(rolled, image_table["image"][0].astype(np.float64))
    # Single-image helper agrees with the column path.
    np.testing.assert_array_equal(unroll_image(image_table["image"][0]), vec[0])


def test_image_featurizer(image_table):
    from mmlspark_tpu.models import init_resnet

    params = init_resnet(variant="resnet18", num_classes=6, small_inputs=True)
    feat = ImageFeaturizer(
        inputCol="image",
        outputCol="features",
        modelParams=params,
        inputHeight=32,
        inputWidth=32,
        batchSize=4,
    )
    out = feat.transform(image_table)
    assert out["features"].shape == (3, 512)
    assert np.isfinite(out["features"]).all()
    # Headful: cut=0 emits class scores.
    logits = feat.copy({"cutOutputLayers": 0}).transform(image_table)
    assert logits["features"].shape == (3, 6)


def test_read_images(tmp_path, rng):
    from PIL import Image

    from mmlspark_tpu.io import read_binary_files, read_images

    for i in range(3):
        arr = rng.integers(0, 255, (10, 12, 3), dtype=np.uint8)
        Image.fromarray(arr).save(tmp_path / f"img_{i}.png")
    (tmp_path / "notes.txt").write_text("not an image")

    files = read_binary_files(str(tmp_path))
    assert files.num_rows == 4
    imgs = read_images(str(tmp_path), pattern="*.png")
    assert imgs.num_rows == 3
    assert imgs["image"][0].shape == (10, 12, 3)
    # Undecodable files are dropped (reference emits null images).
    all_files = read_images(str(tmp_path))
    assert all_files.num_rows == 3


def test_read_zip(tmp_path):
    import zipfile

    with zipfile.ZipFile(tmp_path / "archive.zip", "w") as zf:
        zf.writestr("a.txt", "alpha")
        zf.writestr("sub/b.txt", "beta")
    from mmlspark_tpu.io import read_binary_files

    t = read_binary_files(str(tmp_path))
    assert t.num_rows == 2
    assert any(p.endswith("!a.txt") for p in t["path"])
    assert b"beta" in list(t["bytes"])


# -- programs are built once a process (core.device.cached_program) ------------

def _built(job, *names):
    """(what ``job`` returned, {span name: its ``programs_built``})."""
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    tracer.clear()
    out = job()
    return out, {s["name"]: s["tags"]["programs_built"] for s in tracer.export() if s["name"] in names}


def _counting_backbone():
    """A backbone whose Python body notes each trace and the ``cut`` it saw."""
    cuts = []

    def backbone(p, x, cut):
        cuts.append(cut)
        pooled = x.mean(axis=(2, 3))  # (rows, channels), NCHW in
        return pooled @ p["w"] + cut

    return backbone, cuts


def _pixels(rows=5, side=8):
    images = np.random.default_rng(11).integers(0, 256, size=(rows, side, side, 3), dtype=np.uint8)
    column = np.empty(rows, dtype=object)
    for i in range(rows):
        column[i] = images[i]
    return images, Table({"image": column})


def _featurizer(backbone, w, **params):
    return ImageFeaturizer(applyFn=backbone, modelParams={"w": w}, inputHeight=8, inputWidth=8,
                           batchSize=4, **params)


_W = np.arange(6, dtype=np.float32).reshape(3, 2)


def _expected(images, w, cut=1, scale=1.0 / 255.0):
    return (images.astype(np.float32) * np.float32(scale)).mean(axis=(1, 2)) @ w + cut


def test_two_featurizers_of_one_definition_trace_the_backbone_once():
    backbone, cuts = _counting_backbone()
    images, table = _pixels()
    spans = ("image.transform", "dnn.transform")
    out, built = _built(lambda: _featurizer(backbone, _W).transform(table)["features"], *spans)
    assert built["dnn.transform"] == 1 and cuts == [1]
    np.testing.assert_allclose(out, _expected(images, _W), rtol=1e-5)
    # another featurizer object, other weights: the same program over them
    out, built = _built(lambda: _featurizer(backbone, -_W).transform(table)["features"], *spans)
    assert built == {"image.transform": 0, "dnn.transform": 0} and cuts == [1]
    np.testing.assert_allclose(out, _expected(images, -_W), rtol=1e-5)


_CAPTURED = {
    # what the featurizer's applyFn closes over, each changed in turn:
    # name -> (params of the second featurizer, what its output has to be)
    "cutOutputLayers": ({"cutOutputLayers": 2}, lambda im: _expected(im, _W, cut=2)),
    "scale": ({"scale": 0.5}, lambda im: _expected(im, _W, scale=0.5)),
    "scale_given_as_an_int": ({"scale": 1}, lambda im: _expected(im, _W, scale=1.0)),
}


@pytest.mark.parametrize("case", sorted(_CAPTURED))
def test_each_value_the_featurizers_apply_fn_captures_is_in_its_key(case):
    backbone, cuts = _counting_backbone()
    images, table = _pixels()
    _featurizer(backbone, _W).transform(table)
    params, expected = _CAPTURED[case]
    out, built = _built(lambda: _featurizer(backbone, _W, **params).transform(table)["features"],
                        "dnn.transform")
    assert built == {"dnn.transform": 1} and len(cuts) == 2
    np.testing.assert_allclose(out, expected(images), rtol=1e-5)
    # and the first definition still finds its own
    out, built = _built(lambda: _featurizer(backbone, _W).transform(table)["features"], "dnn.transform")
    assert built == {"dnn.transform": 0} and len(cuts) == 2
    np.testing.assert_allclose(out, _expected(images, _W), rtol=1e-5)


def test_another_backbone_is_another_program():
    first, first_cuts = _counting_backbone()
    images, table = _pixels()
    _featurizer(first, _W).transform(table)

    def doubled(p, x, cut):
        return 2.0 * first(p, x, cut)

    out, built = _built(lambda: _featurizer(doubled, _W).transform(table)["features"], "dnn.transform")
    assert built == {"dnn.transform": 1} and len(first_cuts) == 2
    np.testing.assert_allclose(out, 2.0 * _expected(images, _W), rtol=1e-5)


def _run_stages(table, stage_list):
    """(the output column, ``image.transform``'s ``programs_built`` by name)."""
    return _built(
        lambda: ImageTransformer(inputCol="image", outputCol="out", stages=stage_list).transform(table)["out"],
        "image.transform")


def _stage_program_traces(monkeypatch):
    """Count the traces of the flip op's body (every trace of the stage
    function runs it once: ``eval_shape`` and the jitted program alike)."""
    from mmlspark_tpu.image import transforms

    traces = []
    flip = transforms._op_flip

    def counting(stage):
        run = flip(stage)

        def counted(x):
            traces.append(x.shape)
            return run(x)

        return counted

    monkeypatch.setitem(transforms._OPS, "Flip", counting)
    return traces


def test_a_fresh_stage_list_of_equal_content_finds_the_stage_program(monkeypatch):
    traces = _stage_program_traces(monkeypatch)
    images, table = _pixels()
    stages = [{"op": "Flip", "flipCode": 0}, {"op": "Threshold", "threshold": 77.0, "maxVal": 200.0}]

    want = np.where(images[:, ::-1].astype(np.float32) > 77.0, 200, 0).astype(np.uint8)
    out, built = _run_stages(table, stages)
    assert built == {"image.transform": 1}
    assert traces == [(5, 8, 8, 3)] * 2  # the result's shape, then the program
    np.testing.assert_array_equal(np.stack(list(out)), want)
    out, built = _run_stages(table, [dict(reversed(list(s.items()))) for s in stages])  # equal dicts, made anew
    assert built == {"image.transform": 0} and len(traces) == 2  # nor was the shape asked for again
    np.testing.assert_array_equal(np.stack(list(out)), want)
    # the fluent builders arrive at the same list
    fluent = ImageTransformer(inputCol="image", outputCol="out").flip(0).threshold(77.0, 200.0)
    assert _built(lambda: fluent.transform(table), "image.transform")[1] == {"image.transform": 0}
    # a table of other rows is a shape not seen yet: traced, not rebuilt
    _, fewer = _pixels(rows=3)
    out, built = _built(lambda: fluent.transform(fewer)["out"], "image.transform")
    assert built == {"image.transform": 0} and traces[2:] == [(3, 8, 8, 3)] * 2


@pytest.mark.parametrize("change", ["a_value", "a_stage_more", "the_order"])
def test_a_stage_list_that_differs_is_a_program_of_its_own(monkeypatch, change):
    traces = _stage_program_traces(monkeypatch)
    images, table = _pixels()
    base = [{"op": "Flip", "flipCode": 0}, {"op": "Threshold", "threshold": 60.0, "maxVal": 255.0}]
    changed, want = {
        "a_value": ([{"op": "Flip", "flipCode": 1}, base[1]],
                    lambda x: np.where(x[:, :, ::-1] > 60.0, 255, 0)),
        "a_stage_more": (base + [{"op": "Flip", "flipCode": 1}],
                         lambda x: np.where(x[:, ::-1, ::-1] > 60.0, 255, 0)),
        "the_order": ([base[1], base[0]], lambda x: np.where(x > 60.0, 255, 0)[:, ::-1]),
    }[change]

    _run_stages(table, base)
    before = len(traces)
    out, built = _run_stages(table, changed)
    assert built == {"image.transform": 1} and len(traces) > before
    np.testing.assert_array_equal(np.stack(list(out)), want(images.astype(np.float32)).astype(np.uint8))


def test_a_stage_dict_changed_in_place_after_a_call_is_another_key(monkeypatch):
    """An op reads its dict while it is traced; the cached program holds a
    copy, so a caller's later edit neither reaches the program built before
    it nor is missed by the key."""
    _stage_program_traces(monkeypatch)
    images, table = _pixels()
    stage = {"op": "Flip", "flipCode": 0, "note": "a key no op reads makes this list this test's own"}
    transformer = ImageTransformer(inputCol="image", outputCol="out", stages=[stage])
    out, _ = _built(lambda: transformer.transform(table)["out"], "image.transform")
    np.testing.assert_array_equal(np.stack(list(out)), images[:, ::-1])
    stage["flipCode"] = 1
    out, built = _built(lambda: transformer.transform(table)["out"], "image.transform")
    assert built == {"image.transform": 1}
    np.testing.assert_array_equal(np.stack(list(out)), images[:, :, ::-1])
    _, fewer = _pixels(rows=2)  # a new shape under the first program: its own copy of the dict
    first = ImageTransformer(inputCol="image", outputCol="out", stages=[dict(stage, flipCode=0)])
    out, built = _built(lambda: first.transform(fewer)["out"], "image.transform")
    assert built == {"image.transform": 0}
    np.testing.assert_array_equal(np.stack(list(out)), images[:2, ::-1])


def test_an_unknown_op_raises_on_every_call():
    _, table = _pixels()
    bad = ImageTransformer(inputCol="image", outputCol="out", stages=[{"op": "Sharpen"}])
    for _ in range(2):
        with pytest.raises(ValueError, match="unknown image op 'Sharpen'"):
            bad.transform(table)


# -- the resized table stays on the device (ImageFeaturizer) -------------------

def _every_pixel_backbone(p, x, cut):
    """Features that read every pixel of an NCHW batch of any channel count."""
    return x.mean(axis=1).reshape(x.shape[0], -1) @ p["w"] + cut


# name: (row shapes in row order, batchSize, autoResize); the model input is 6 x 5
_HANDOVER_CASES = {
    "one_group_at_the_target_size": ([(6, 5, 3)] * 8, 4, True),
    "one_group_that_needs_the_resize": ([(9, 7, 3)] * 8, 4, True),
    "several_groups_interleaved": ([(9, 7, 3), (6, 5, 3), (12, 10, 3), (9, 7, 3), (6, 5, 3),
                                    (9, 7, 3), (12, 10, 3)], 2, True),
    "gray_images": ([(9, 7, 1)] * 5, 4, True),
    "gray_images_of_two_sizes": ([(9, 7, 1), (6, 5, 1), (9, 7, 1)], 4, True),
    "a_short_last_batch": ([(9, 7, 3)] * 10, 4, True),
    "fewer_rows_than_a_batch": ([(6, 5, 3)] * 3, 8, True),
    "no_auto_resize": ([(6, 5, 3)] * 7, 4, False),
}


def _handover_table(shapes):
    rng = np.random.default_rng(13)
    column = np.empty(len(shapes), dtype=object)
    for i, shape in enumerate(shapes):
        column[i] = rng.integers(0, 256, size=shape, dtype=np.uint8)
    return Table({"id": np.arange(len(shapes)), "image": column})


def _handover_featurizer(batch, resize):
    w = np.random.default_rng(3).standard_normal((6 * 5, 4)).astype(np.float32)
    return ImageFeaturizer(applyFn=_every_pixel_backbone, modelParams={"w": w}, inputHeight=6,
                           inputWidth=5, batchSize=batch, autoResize=resize)


@pytest.mark.parametrize("case", sorted(_HANDOVER_CASES))
def test_featurizer_equals_the_public_stages_composed_over_a_host_table(case, monkeypatch):
    """``ImageFeaturizer`` hands the stage program's result to the batch loop
    on the device; the features are those of ``ImageTransformer.transform``
    followed by ``DNNModel.transform`` over the host column, bit for bit, and
    nothing the size of a resized image comes down in between."""
    import jax

    from mmlspark_tpu.dnn import DNNModel
    from mmlspark_tpu.image.featurizer import _apply_fn
    from mmlspark_tpu.observability.tracing import get_tracer

    shapes, batch, resize = _HANDOVER_CASES[case]
    table = _handover_table(shapes)
    featurizer = _handover_featurizer(batch, resize)

    staged = table
    if resize:
        staged = ImageTransformer(inputCol="image", outputCol="resized", toFloat=True).resize(
            6, 5).transform(table)
        assert isinstance(staged["resized"], np.ndarray) and staged["resized"].dtype == np.float32
        assert staged["resized"].shape == (len(shapes), 6, 5, shapes[0][2])
    want = DNNModel(
        applyFn=_apply_fn(_every_pixel_backbone, 1, 1.0 / 255.0), modelParams=featurizer.getModelParams(),
        feedDict={"input": "resized" if resize else "image"}, fetchDict={"features": "output"},
        batchSize=batch,
    ).transform(staged)["features"]

    fetched = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: fetched.append(np.shape(x)) or real_get(x))
    tracer = get_tracer()
    tracer.clear()
    out = featurizer.transform(table)
    spans = tracer.export()
    got = out["features"]
    assert out.columns == ["id", "image", "features"]  # __resized__ was never a column
    assert isinstance(got, np.ndarray) and got.dtype == want.dtype and got.shape == (len(shapes), 4)
    np.testing.assert_array_equal(got, want)
    # only feature batches come down: the resized table is never fetched
    assert fetched and all(shape == (batch, 4) for shape in fetched)

    def tags(name):
        return [s["tags"] for s in spans if s["name"] == name]

    groups = len(set(shapes)) if resize else 0
    assert [t["bytes_down"] for t in tags("image.apply_fetch")] == [0] * groups
    assert not tags("image.assemble")
    forwards = tags("dnn.transform")
    assert len(forwards) == max(groups, 1)
    assert sum(t["rows"] for t in forwards) == len(shapes)
    for t in forwards:
        assert t["device_batches"] == (t["batches"] if resize else 0)
    assert all(t["bytes"] == 0 for t in tags("dnn.stack") + tags("dnn.dispatch")) == resize


def test_a_table_without_rows_raises_as_the_forward_always_did():
    with pytest.raises(ValueError, match="need at least one"):
        _handover_featurizer(4, True).transform(_handover_table([]))
    with pytest.raises(ValueError, match="need at least one"):
        _handover_featurizer(4, False).transform(_handover_table([]))


def test_the_stage_alone_still_returns_its_host_column():
    from mmlspark_tpu.observability.tracing import COMPILE_TAGS, get_tracer

    table = _handover_table([(9, 7, 3)] * 4)
    tracer = get_tracer()
    tracer.clear()
    out = ImageTransformer(inputCol="image", outputCol="out", toFloat=True).resize(6, 5).transform(table)
    (span,) = [s["tags"] for s in tracer.export() if s["name"] == "image.apply_fetch"]
    column = out["out"]
    assert isinstance(column, np.ndarray) and column.dtype == np.float32
    assert column.shape == (4, 6, 5, 3) and column.flags.c_contiguous
    span = {k: v for k, v in span.items() if k not in COMPILE_TAGS}
    assert span == {"bytes_up": 4 * 9 * 7 * 3, "bytes_down": column.nbytes}  # less what the first call compiled


def test_device_groups_are_the_stage_programs_own_arrays():
    import jax

    table = _handover_table([(9, 7, 3), (6, 5), (9, 7, 3), (6, 5)])
    stage = ImageTransformer(inputCol="image", outputCol="out", toFloat=True).resize(6, 5)
    host = stage.transform(table.take([0, 2]))["out"], stage.transform(table.take([1, 3]))["out"]
    groups = stage._device_groups(table)
    assert [idxs for idxs, _, _ in groups] == [[0, 2], [1, 3]]
    # gray rows that came without a channel axis keep their squeeze
    assert [shape for _, shape, _ in groups] == [(2, 6, 5, 3), (2, 6, 5)]
    for (_, shape, flat), want in zip(groups, host):
        assert isinstance(flat, jax.Array) and flat.dtype == np.float32
        assert flat.shape == (2, int(np.prod(shape[1:])))
        np.testing.assert_array_equal(np.asarray(flat).reshape(shape), want)


# -- a shape group of more than a slab's bytes goes up slab by slab -------------

_SLAB_TABLES = {
    "three_channels": [(7, 5, 3)] * 11,
    "gray": [(7, 5)] * 11,
    "two_shapes": [(7, 5, 3), (4, 6, 3), (7, 5, 3)] * 5 + [(7, 5, 3)],
}


class _LateSlab:
    """What ``jax.device_put`` returns from a runtime with memory of its own,
    at its slowest: the host buffer is read as late as it may be, when the
    array is waited for or first used, and until then it is not the
    caller's to write."""

    def __init__(self, view, honours_waits=True):
        self.view, self.array, self.honours_waits = view, None, honours_waits

    def settle(self):
        import jax

        if self.array is None:
            self.array = jax.numpy.asarray(np.array(self.view))
        return self.array

    def block_until_ready(self):
        if self.honours_waits:
            self.settle()
        return self


def _register_late_slab():
    import jax

    try:
        jax.tree_util.register_pytree_node(
            _LateSlab, lambda slab: ((slab.settle(),), None), lambda _, leaves: leaves[0])
    except ValueError:  # registered by an earlier test of this process
        pass


@pytest.fixture()
def slabs_of_four_rows(monkeypatch):
    """Slabs of four 7 x 5 x 3 uint8 rows (five of 4 x 6 x 3, twelve gray)."""
    from mmlspark_tpu.image import transforms

    monkeypatch.setattr(transforms, "_SLAB_BYTES", 4 * 7 * 5 * 3)


@pytest.fixture()
def a_runtime_that_reads_late(monkeypatch):
    """The chip's side of the protocol on the CPU: a device with memory of
    its own (staging buffers are filled again) whose uploads read late."""
    import jax

    from mmlspark_tpu.image import transforms

    _register_late_slab()
    monkeypatch.setattr(transforms, "_host_is_device", lambda: False)
    monkeypatch.setattr(jax, "device_put", _LateSlab)


def _staged_results(stage, table, fetch):
    if fetch:
        return [np.asarray(row) for row in stage.transform(table)["out"]]
    return [(idxs, shape, np.asarray(flat)) for idxs, shape, flat in stage._device_groups(table)]


def _slab_stage(to_float=True):
    return ImageTransformer(inputCol="image", outputCol="out", toFloat=to_float).resize(6, 5).flip(1)


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if isinstance(g, tuple):
            assert g[:2] == w[:2]
            g, w = g[2], w[2]
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("runtime", ["the_cpu_backend", "a_runtime_that_reads_late"])
@pytest.mark.parametrize("fetch", [True, False], ids=["fetched", "left_on_the_device"])
@pytest.mark.parametrize("kind", sorted(_SLAB_TABLES))
def test_slabs_give_what_one_stack_and_one_upload_gave(kind, fetch, runtime, request):
    """Several slabs a group, the last one short (11 rows in slabs of 4, the
    gray rows' 11 in slabs of 8, a second shape's 5 in one of 5): bit for bit
    the result of the one-slab path, which is ``np.stack`` and one upload as
    it always was."""
    table = _handover_table(_SLAB_TABLES[kind])
    want = _staged_results(_slab_stage(), table, fetch)
    request.getfixturevalue("slabs_of_four_rows")
    if runtime != "the_cpu_backend":
        request.getfixturevalue(runtime)
    _assert_same(_staged_results(_slab_stage(), table, fetch), want)


def test_slabs_round_and_clip_to_uint8_as_one_batch_did(slabs_of_four_rows, monkeypatch):
    from mmlspark_tpu.image import transforms

    table = _handover_table(_SLAB_TABLES["three_channels"])
    got = _staged_results(_slab_stage(to_float=False), table, True)
    monkeypatch.setattr(transforms, "_SLAB_BYTES", 1 << 20)
    _assert_same(got, _staged_results(_slab_stage(to_float=False), table, True))
    assert got[0].dtype == np.uint8


def _slab_tags(table):
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    tracer.clear()
    _slab_stage()._device_groups(table)
    (tags,) = [s["tags"] for s in tracer.export() if s["name"] == "image.transform"]
    return tags["slabs"], tags["staging_reused"]


@pytest.mark.parametrize("rows,slabs", [(3, 1), (4, 1), (5, 2), (8, 2), (9, 3), (11, 3), (23, 6)])
def test_a_call_counts_its_slabs_and_the_buffers_it_filled_again(
        rows, slabs, slabs_of_four_rows, a_runtime_that_reads_late):
    """Two staging buffers a group however many slabs: every slab after the
    second is stacked where an earlier one was."""
    assert _slab_tags(_handover_table([(7, 5, 3)] * rows)) == (slabs, max(0, slabs - 2))


def test_every_shape_group_has_a_staging_pair_of_its_own(slabs_of_four_rows, a_runtime_that_reads_late):
    # 11 rows in slabs of 4 and 5 rows of another shape in one slab of 5
    assert _slab_tags(_handover_table(_SLAB_TABLES["two_shapes"])) == (3 + 1, 1)


def test_the_cpu_backend_is_handed_every_buffer_for_good(slabs_of_four_rows):
    """It takes an aligned host buffer as the array's own memory, so there
    no buffer is filled twice (the results above are right either way)."""
    assert _slab_tags(_handover_table([(7, 5, 3)] * 11)) == (3, 0)


def test_a_slab_holds_whole_tiles_of_eight_rows(monkeypatch):
    from mmlspark_tpu.image import transforms

    monkeypatch.setattr(transforms, "_SLAB_BYTES", 21 * 7 * 5 * 3)
    assert _slab_tags(_handover_table([(7, 5, 3)] * 33)) == (3, 0)  # 16, 16, 1: not 21, 12


@pytest.mark.parametrize("honours_waits", [True, False], ids=["waited_for", "not_waited_for"])
def test_no_staging_buffer_is_written_while_its_upload_may_be_read(
        honours_waits, slabs_of_four_rows, monkeypatch):
    """The wait in front of a refill is what keeps a slab's rows: an upload
    that reads its buffer only when the stage program is handed the slabs
    (a wait that settled nothing) finds the rows of a later slab there."""
    import functools

    import jax

    from mmlspark_tpu.image import transforms

    table = _handover_table([(7, 5, 3)] * 19)
    want = np.stack(list(table["image"]))[:, :, ::-1].astype(np.float32).reshape(19, -1)
    _register_late_slab()
    monkeypatch.setattr(transforms, "_host_is_device", lambda: False)
    monkeypatch.setattr(jax, "device_put", functools.partial(_LateSlab, honours_waits=honours_waits))
    flip = ImageTransformer(inputCol="image", outputCol="out", toFloat=True).flip(1)
    ((_, _, got),) = _staged_results(flip, table, False)
    assert np.array_equal(got, want) == honours_waits
    # the last two slabs are never refilled, so their rows are right whatever the wait does
    np.testing.assert_array_equal(got[12:], want[12:])
