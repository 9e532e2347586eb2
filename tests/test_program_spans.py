"""The spans and scopes inside ``fit`` and ``featurize``: the span tree a job
records (names, one trace id, parents, counts that follow the job's shape
and never ``numIterations``), tags equal to shape arithmetic, results
unchanged from the commit before the spans, the device-side scope names
read back from the lowered module, and one clock for spans and profiler."""

import hashlib
import os
import re
from functools import partial

import numpy as np
import pytest

from mmlspark_tpu.data.table import Table
from mmlspark_tpu.observability.tracing import COMPILE_TAGS, get_tracer

FIT_CHILDREN = {
    "lightgbm.prepare", "lightgbm.binning", "lightgbm.upload", "lightgbm.program",
    "lightgbm.u_build", "lightgbm.boost", "lightgbm.pack",
}
# sha256 of what the commit before the spans (de0f6de) gives for _fit_table()
# and _image_table() below on this CPU backend: no span may change a result.
# The model text is that commit's (ace44a60...) with PR 36's leaf values: a leaf's
# output comes from the split that made it, which moved `leaf_value` by under
# 4e-6 relative and, from the second tree on, `split_gain` by under 3e-6; every
# other line of the text is that commit's.
PARENT_MODEL_TEXT = "6ee05eedbfadba9d01bbd31cc9665d6112cf328b05dda004d89912ccbb5c1c82"
PARENT_FEATURES = "317ae1ac426cee2dcf3c7b0201dfccfaf66794287f70c9ffb5f7218d7fd44c9b"


def _fit_table(rows=2000, features=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features))
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return Table({"features": X, "label": y})


def _recorded(job):
    """Every span the tracer finished while ``job`` ran, with the tags the
    job's shape gives: what a first call paid JAX to trace and compile is
    booked on the same spans (``tests/test_compile_booking.py``) and
    depends on what the process compiled before."""
    tracer = get_tracer()
    tracer.clear()
    out = job()
    spans = tracer.export()
    for span in spans:
        span["tags"] = {k: v for k, v in span["tags"].items() if k not in COMPILE_TAGS}
    return out, spans


def _fit_spans(iterations, **params):
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    est = LightGBMClassifier(numIterations=iterations, numLeaves=7, **params)
    return _recorded(lambda: est.fit(_fit_table()))


# numTasks=1: one device, so the scanned program; the training metric needs
# the margins back every iteration, so the loop; the mesh is a loop as well
PATHS = {
    "scan": {"numTasks": 1},
    "loop": {"numTasks": 1, "isProvideTrainingMetric": True},
    "mesh": {"numTasks": 2},
}


@pytest.mark.parametrize("binning", ["inline", "partitioned"])
@pytest.mark.parametrize("path", list(PATHS))
def test_fit_records_one_span_tree_whatever_the_iterations(path, binning):
    params = dict(PATHS[path], **({"numExecutors": 2} if binning == "partitioned" else {}))
    _, few = _fit_spans(5, **params)
    _, many = _fit_spans(50, **params)
    # (the scheduler's own task spans under partitioned binning follow its
    # retries and speculation, not the fit)
    ours = [s for s in few if s["name"].startswith("lightgbm.")]
    names = [s["name"] for s in ours]
    assert names == [s["name"] for s in many if s["name"].startswith("lightgbm.")], \
        "the span count follows numIterations"
    root, = [s for s in ours if s["name"] == "lightgbm.fit"]
    children = [s for s in ours if s is not root]
    assert {s["trace_id"] for s in few} == {root["trace_id"]}
    assert root["parent_id"] is None
    assert all(s["parent_id"] == root["span_id"] for s in children)
    assert {s["name"] for s in children} == FIT_CHILDREN - {"lightgbm.u_build"}  # no U off the chip
    assert names.count("lightgbm.binning") == 1
    by_name = {s["name"]: s for s in children}
    assert by_name["lightgbm.binning"]["tags"]["path"] in (
        ("partitioned",) if binning == "partitioned" else ("native", "numpy")
    )
    assert by_name["lightgbm.boost"]["tags"]["segments"] == (1 if path == "scan" else 0)
    assert root["tags"] == {"iterations": 5, "rows": 2000, "features": 6}
    assert all(s["status"] == "ok" and s["duration"] >= 0 for s in ours)
    covered = sum(s["duration"] for s in children)
    assert 0 < covered <= root["duration"]


def test_a_fit_that_raises_still_closes_its_root():
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    est = LightGBMClassifier(numIterations=2, slotNames=["only_one"])
    tracer = get_tracer()
    tracer.clear()
    with pytest.raises(ValueError, match="slotNames"):
        est.fit(_fit_table())
    status = {s["name"]: s["status"] for s in tracer.export()}
    assert status == {"lightgbm.prepare": "ValueError", "lightgbm.fit": "ValueError"}
    assert tracer.current() is None


@pytest.mark.parametrize("budget,chunks", [(None, 1), (str(64 << 10), 2)])
def test_train_tags_are_shape_arithmetic(budget, chunks, monkeypatch):
    """``train()`` on the U path (forced: the program takes it on a TPU
    only): bytes uploaded, U bytes and chunks from shapes alone."""
    from mmlspark_tpu.lightgbm.binning import bin_dataset
    from mmlspark_tpu.lightgbm.train import TrainOptions, train
    from mmlspark_tpu.ops.u_histogram import make_u_spec, u_bytes

    if budget:
        monkeypatch.setenv("MMLSPARK_TPU_U_BUDGET", budget)
    table = _fit_table(rows=1000, features=4)
    X, y = np.asarray(table["features"]), np.asarray(table["label"])
    bins, mapper = bin_dataset(X, max_bin=15)
    opts = TrainOptions(num_iterations=3, num_leaves=7, max_bin=15, histogram_method="u")
    _, spans = _recorded(lambda: train(bins, y, opts, mapper=mapper))
    by_name = {s["name"]: s for s in spans}
    assert [s["name"] for s in spans] == [
        "lightgbm.upload", "lightgbm.program", "lightgbm.u_build", "lightgbm.boost", "lightgbm.pack",
    ]
    # float32 edges, uint8 bins, labels as uint8: nothing else crosses
    assert by_name["lightgbm.upload"]["tags"] == {"bytes": mapper.edges.size * 4 + 1000 * 4 + 1000}
    spec = make_u_spec(16, 4, [int(b) for b in mapper.num_bins])
    want = chunks * 512 * 4 if budget else u_bytes(1000, spec)  # chunked: the bins stack
    assert by_name["lightgbm.u_build"]["tags"] == {"chunks": chunks, "u_bytes": want}
    # a 7-leaf tree at leaf_batch 8: the root's pass, rounds of 1, 2 and 3 splits, no pass after the last
    assert by_name["lightgbm.boost"]["tags"] == {
        "iterations": 3, "segments": 1, "hist_passes_built": 3 * 3, "hist_passes_skipped": 3}
    assert by_name["lightgbm.pack"]["tags"] == {"trees": 3}
    assert by_name["lightgbm.program"]["tags"]["cache_hit"] in (True, False)


# -- featurize ----------------------------------------------------------------

def _image_table(images=10, side=16, seed=0):
    rng = np.random.default_rng(seed)
    column = np.empty(images, dtype=object)
    for i in range(images):
        column[i] = rng.integers(0, 256, size=(side, side, 3), dtype=np.uint8)
    return Table({"image": column})


def _featurizer(batch, **params_):
    from mmlspark_tpu.image import ImageFeaturizer
    from mmlspark_tpu.models import init_resnet

    params = init_resnet(seed=0, variant="resnet18", small_inputs=True)
    return ImageFeaturizer(modelParams=params, inputHeight=16, inputWidth=16, batchSize=batch,
                           **params_), params


@pytest.mark.parametrize("resize", [True, False], ids=["resized_on_the_device", "fed_as_it_is"])
@pytest.mark.parametrize("images,batch", [(10, 4), (8, 4), (3, 8)])
def test_featurize_records_three_spans_a_batch(images, batch, resize):
    featurizer, params = _featurizer(batch, autoResize=resize)
    out, spans = _recorded(lambda: featurizer.transform(_image_table(images)))
    assert np.asarray(out["features"]).shape == (images, 512)
    batches = -(-images // batch)
    names = [s["name"] for s in spans]
    for per_batch in ("dnn.stack", "dnn.dispatch", "dnn.fetch"):
        assert names.count(per_batch) == batches
    # one shape group; the resized table is never assembled on the host
    assert len(spans) == (7 if resize else 4) + 3 * batches
    root, = [s for s in spans if s["name"] == "image.featurize"]
    assert {s["trace_id"] for s in spans} == {root["trace_id"]}
    by_id = {s["span_id"]: s for s in spans}
    parent_of = {s["name"]: by_id[s["parent_id"]]["name"] for s in spans if s is not root}
    stage_spans = {
        "image.transform": "image.featurize", "image.stack": "image.transform",
        "image.apply_fetch": "image.transform",
    }
    assert parent_of == {
        "dnn.transform": "image.featurize", "dnn.place_params": "dnn.transform",
        "dnn.stack": "dnn.transform", "dnn.dispatch": "dnn.transform",
        "dnn.fetch": "dnn.transform", "dnn.assemble": "dnn.transform",
        **(stage_spans if resize else {}),
    }

    def tags(name):
        return [s["tags"] for s in spans if s["name"] == name]

    uint8 = images * 16 * 16 * 3
    assert root["tags"] == {"rows": images, "batch_size": batch}
    # programs_built: 1 in the module's first call of a definition, then 0
    # (tests/test_dnn.py and tests/test_image.py hold the counts)
    (forward,) = tags("dnn.transform")
    assert forward.pop("programs_built") in (0, 1)
    if resize:
        (stage,) = tags("image.transform")
        assert stage.pop("programs_built") in (0, 1)
        # under a slab's bytes: one slab, stacked once and uploaded once
        assert stage == {"rows": images, "groups": 1, "slabs": 1, "staging_reused": 0}
        assert tags("image.stack") == [{"bytes": uint8}]
        # the stage program's result stays on the device for the batch loop
        assert tags("image.apply_fetch") == [{"bytes_up": uint8, "bytes_down": 0}]
    assert forward == {"rows": images, "batches": batches,
                       "device_batches": batches if resize else 0}
    import jax

    assert tags("dnn.place_params") == [{"bytes": sum(a.nbytes for a in jax.tree.leaves(params))}]
    fed = batch * 16 * 16 * 3 * 4  # every batch is padded to batchSize
    pads = [0] * (batches - 1) + [batches * batch - images]
    if resize:
        # sliced, padded and handed to the forward where the table lives
        assert tags("dnn.stack") == [{"pad_rows": p, "bytes": 0} for p in pads]
        assert tags("dnn.dispatch") == [{"bytes": 0}] * batches
    else:
        # an object column of uint8 rows: stacked and cast once a batch
        assert tags("dnn.stack") == [{"pad_rows": p, "bytes": fed} for p in pads]
        assert tags("dnn.dispatch") == [{"bytes": fed}] * batches
    assert tags("dnn.fetch") == [{"bytes": batch * 512 * 4}] * batches
    assert tags("dnn.assemble") == [{"bytes": images * 512 * 4}]


def test_mixed_shapes_record_a_stack_fetch_assemble_per_group():
    from mmlspark_tpu.image import ImageTransformer

    column = np.empty(5, dtype=object)
    for i, side in enumerate((8, 12, 8, 12, 8)):
        column[i] = np.full((side, side, 3), i, dtype=np.uint8)
    stage = ImageTransformer(inputCol="image", outputCol="out").flip(1)
    out, spans = _recorded(lambda: stage.transform(Table({"image": column})))
    assert [im.shape for im in out["out"]] == [c.shape for c in column]
    names = [s["name"] for s in spans]
    assert names == ["image.stack", "image.apply_fetch", "image.assemble"] * 2 + [
        "image.assemble", "image.transform"]
    whole = spans[-1]
    assert whole["tags"].pop("programs_built") in (0, 1)
    assert whole["tags"] == {"rows": 5, "groups": 2, "slabs": 2, "staging_reused": 0}
    # uint8 out: the round trip's clip and cast is a copy; the object column is not
    assert [s["tags"]["bytes"] for s in spans if s["name"] == "image.assemble"] == [
        3 * 8 * 8 * 3, 2 * 12 * 12 * 3, 0]


@pytest.mark.parametrize("fetch", [True, False], ids=["fetched", "left_on_the_device"])
@pytest.mark.parametrize("rows,slab_rows", [(10, [4, 4, 2]), (8, [4, 4]), (4, [4]), (23, [4] * 5 + [3])])
def test_a_group_of_several_slabs_records_a_stack_and_an_upload_a_slab(rows, slab_rows, fetch, monkeypatch):
    """One ``image.stack`` then one ``image.apply_fetch`` a slab, their byte
    tags the slab's own, so the group's bytes are counted once each way
    however it is cut (``host_copy_gib`` sums every ``bytes*`` tag); the
    stage program and the fetch sit in the last slab's span, where a
    one-slab group always had them."""
    from mmlspark_tpu.image import ImageTransformer, transforms

    row = 16 * 16 * 3
    monkeypatch.setattr(transforms, "_SLAB_BYTES", 4 * row)
    stage = ImageTransformer(inputCol="image", outputCol="out", toFloat=True).flip(0)
    table = _image_table(rows)
    _, spans = _recorded(lambda: stage.transform(table) if fetch else stage._device_groups(table))
    names = [s["name"] for s in spans]
    after = ["image.assemble"] * 2 if fetch else []
    assert names == ["image.stack", "image.apply_fetch"] * len(slab_rows) + after + ["image.transform"]
    assert [s["tags"] for s in spans if s["name"] == "image.stack"] == [{"bytes": n * row} for n in slab_rows]
    down = [0] * (len(slab_rows) - 1) + [rows * row * 4 if fetch else 0]
    assert [s["tags"] for s in spans if s["name"] == "image.apply_fetch"] == [
        {"bytes_up": n * row, "bytes_down": d} for n, d in zip(slab_rows, down)]
    whole = spans[-1]["tags"]
    assert whole.pop("programs_built") in (0, 1)
    # on the CPU backend a buffer handed over is the array's own: none is filled again
    assert whole == {"rows": rows, "groups": 1, "slabs": len(slab_rows), "staging_reused": 0}
    by_id = {s["span_id"]: s["name"] for s in spans}
    assert {by_id[s["parent_id"]] for s in spans[:-1]} == {"image.transform"}


# -- results are the parent's -------------------------------------------------

def test_results_are_bit_for_bit_those_of_the_commit_before_the_spans():
    model, _ = _fit_spans(5, numTasks=1)
    text = model.get_model_string()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_MODEL_TEXT
    featurizer, _ = _featurizer(4)
    feats = np.asarray(featurizer.transform(_image_table())["features"])
    assert feats.dtype == np.float32
    assert hashlib.sha256(feats.tobytes()).hexdigest() == PARENT_FEATURES


# -- device-side names --------------------------------------------------------

def _scopes(lowered):
    """Every named scope in the lowered module's locations; a vmapped scope
    reads ``vmap(name)``."""
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r"[\w.]+", " ".join(re.findall(r'loc\("([^"]+)"', text))))


def _lowered_step(growth, chunked):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.lightgbm.objectives import get_objective
    from mmlspark_tpu.lightgbm.train import TrainOptions, _make_step
    from mmlspark_tpu.ops import u_histogram as uh

    n, f, nb = 1024, 4, 16
    bins = jnp.asarray(np.random.default_rng(0).integers(0, nb - 1, size=(n, f), dtype=np.uint8))
    spec = uh.make_u_spec(nb, f)
    if chunked:
        spec = uh.chunked_u_spec(n, spec, 1)
    opts = TrainOptions(num_iterations=1, num_leaves=7, max_bin=nb - 1,
                        histogram_method="u", growth=growth)
    step = _make_step(opts, get_objective("binary"), nb, u_spec=spec)
    u = uh.prepare_chunked_bins(bins, spec) if chunked else uh.build_u(bins, spec)
    args = (bins, jnp.zeros(n), jnp.ones(n), jnp.zeros((n, 1)), jnp.zeros((f, nb)),
            jnp.ones(n), jnp.ones(f), jnp.int32(0), jnp.float32(0.1))
    return jax.jit(step).lower(*args, u=u)


@pytest.mark.parametrize("growth", ["leafwise", "depthwise"])
@pytest.mark.parametrize("chunked", [False, True], ids=["resident", "chunked"])
def test_boosting_step_scopes_are_in_the_lowered_module(growth, chunked):
    found = _scopes(_lowered_step(growth, chunked))
    assert {"grad_hess", "hist_pass", "split_search", "route", "margin_update"} <= found


def test_u_build_scope_is_in_the_lowered_module():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.u_histogram import build_u, make_u_spec

    lowered = jax.jit(partial(build_u, spec=make_u_spec(16, 4))).lower(jnp.zeros((1024, 4), jnp.uint8))
    assert "u_build" in _scopes(lowered)


@pytest.mark.parametrize("cut,head", [(0, True), (1, True), (2, False)])
def test_resnet_scopes_are_in_the_lowered_module(cut, head):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models import init_resnet
    from mmlspark_tpu.models.resnet import resnet_apply

    params = init_resnet(seed=0, variant="resnet18", small_inputs=True)
    lowered = jax.jit(partial(resnet_apply, cut=cut)).lower(params, jnp.zeros((2, 3, 16, 16)))
    found = _scopes(lowered)
    assert {"resnet_stem", "resnet_stage1", "resnet_stage2", "resnet_stage3", "resnet_stage4"} <= found
    assert ("head" in found) is head


# -- one clock ----------------------------------------------------------------

def test_profile_trace_yields_the_zero_of_the_traces_clock(tmp_path):
    """A span's ``trace_events`` tuple and its own annotation in the trace
    agree on where it starts and how long it took."""
    import time

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from mmlspark_tpu.core.profiling import profile_trace

    tracer = get_tracer()
    tracer.clear()
    before = time.monotonic()
    with profile_trace(str(tmp_path), host_tracer_level=1) as t0:
        assert before <= t0 <= time.monotonic()
        time.sleep(0.02)
        with tracer.span("test.one_clock"):
            jnp.ones(8).block_until_ready()
            time.sleep(0.02)
    (name, start_ns, duration_ns), = tracer.trace_events(t0)
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs if f.endswith(".xplane.pb")]
    events = [e for plane in ProfileData.from_file(path).planes for line in plane.lines
              for e in line.events if e.name == "test.one_clock"]
    assert name == "test.one_clock" and len(events) == 1
    assert abs(events[0].start_ns - start_ns) < 5e6  # measured: 0.24 ms
    assert abs(events[0].duration_ns - duration_ns) < 5e6
    assert start_ns >= 20e6 and duration_ns >= 20e6
