"""Fuzzing meta-suite — the ``FuzzingTest.scala:27-197`` analogue.

Reflectively discovers every concrete public PipelineStage subclass in the
package and enforces that each one (a) has a fixture in
``tests/fuzzing_objects.py``, (b) is produced by a fixtured estimator's
``fit`` (``fit_produces``), or (c) carries an explicit exemption with a
reason. For every fixture the suite then runs the two reference fuzzing
traits: ExperimentFuzzing (fit/transform executes) and SerializationFuzzing
(save/load roundtrips preserve params and transform output).

Adding a new stage without a fixture fails ``test_every_stage_is_covered``
— the honesty-keeping mechanism SURVEY.md §4 calls out.
"""

import importlib
import inspect
import pkgutil

import numpy as np
import pytest

import mmlspark_tpu
from mmlspark_tpu.core.pipeline import Estimator, PipelineStage

from fuzzing_objects import EXEMPT, TEST_OBJECTS, TestObject

_SKIP_MODULES = ("mmlspark_tpu.cognitive",)  # service stubs fuzzed in test_cognitive


def discover_stage_classes():
    """Every concrete public PipelineStage subclass in the package."""
    found = {}
    for m in pkgutil.walk_packages(mmlspark_tpu.__path__, "mmlspark_tpu."):
        if m.name.startswith(_SKIP_MODULES):
            continue
        mod = importlib.import_module(m.name)
        for name, obj in vars(mod).items():
            if (
                inspect.isclass(obj)
                and issubclass(obj, PipelineStage)
                and obj.__module__ == m.name
                and not name.startswith("_")
                and not inspect.isabstract(obj)
            ):
                found[f"{obj.__module__}.{name}"] = obj
    return found


DISCOVERED = discover_stage_classes()
_PRODUCED = set()
for _fx_name, _fx in TEST_OBJECTS.items():
    pass  # fit_produces is declared per-fixture; resolved lazily in the test


def _produced_model_names():
    names = set()
    for maker in TEST_OBJECTS.values():
        obj = maker()
        if obj.fit_produces:
            names.add(obj.fit_produces)
    return names


def test_every_stage_is_covered():
    produced = _produced_model_names()
    missing = []
    for qual in sorted(DISCOVERED):
        if qual in TEST_OBJECTS or qual in EXEMPT or qual in produced:
            continue
        missing.append(qual)
    assert not missing, (
        "stages without fuzzing coverage (add a fixture to "
        f"tests/fuzzing_objects.py or an EXEMPT reason): {missing}"
    )


def test_no_stale_entries():
    stale = [q for q in list(TEST_OBJECTS) + list(EXEMPT) if q.split("#")[0] not in DISCOVERED]
    assert not stale, f"fixtures/exemptions for classes that no longer exist: {stale}"


def _approx_equal(x, y):
    """Recursive tolerant equality over scalars/arrays/dicts/sequences —
    serde may turn np.float64 into float, tuples into lists, etc."""
    if isinstance(x, dict) and isinstance(y, dict):
        assert set(x) == set(y), (x, y)
        for k in x:
            _approx_equal(x[k], y[k])
        return
    if isinstance(x, (list, tuple)) and isinstance(y, (list, tuple)):
        assert len(x) == len(y), (x, y)
        for xi, yi in zip(x, y):
            _approx_equal(xi, yi)
        return
    xa, ya = np.asarray(x), np.asarray(y)
    if xa.dtype.kind in "fc" and xa.shape == ya.shape:
        np.testing.assert_allclose(xa, ya, rtol=1e-5, atol=1e-6)
    elif xa.dtype.kind in "iub" and ya.dtype.kind in "iubfc":
        np.testing.assert_allclose(
            xa.astype(np.float64), ya.astype(np.float64), rtol=1e-5
        )
    else:
        assert str(x) == str(y)


def _tables_close(a, b):
    assert set(a.columns) == set(b.columns), (a.columns, b.columns)
    for c in a.columns:
        ca, cb = a.column(c), b.column(c)
        if ca.dtype == object or cb.dtype == object:
            assert len(ca) == len(cb)
            for x, y in zip(ca, cb):
                _approx_equal(x, y)
        elif ca.dtype.kind in "fc":
            np.testing.assert_allclose(ca, cb, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(ca, cb)


@pytest.fixture(params=sorted(TEST_OBJECTS), ids=lambda q: q.rsplit(".", 1)[-1].replace("#", "-"))
def test_object(request) -> TestObject:
    return TEST_OBJECTS[request.param]()


def test_experiment_fuzzing(test_object):
    """Fit/transform executes without error (ExperimentFuzzing,
    Fuzzing.scala:75-103)."""
    stage = test_object.stage
    table = test_object.table
    tt = test_object.transform_table or table
    if isinstance(stage, Estimator):
        model = stage.fit(table)
        if test_object.fit_produces:
            got = f"{type(model).__module__}.{type(model).__qualname__}"
            assert got == test_object.fit_produces, got
        if test_object.check_transform:
            out = model.transform(tt)
            assert out.num_rows >= 0
    elif test_object.check_transform:
        out = stage.transform(tt)
        assert out.num_rows >= 0


def test_serialization_fuzzing(test_object, tmp_path):
    """Save/load roundtrip of the stage (and fitted model) preserves the
    transform (SerializationFuzzing, Fuzzing.scala:105-181)."""
    stage = test_object.stage
    table = test_object.table
    tt = test_object.transform_table or table

    p1 = str(tmp_path / "stage")
    stage.save(p1)
    reloaded = type(stage).load(p1)
    assert type(reloaded) is type(stage)

    if isinstance(stage, Estimator):
        model = stage.fit(table)
        p2 = str(tmp_path / "model")
        model.save(p2)
        model2 = type(model).load(p2)
        if test_object.check_transform:
            _tables_close(model.transform(tt), model2.transform(tt))
    elif test_object.check_transform:
        _tables_close(stage.transform(tt), reloaded.transform(tt))


def test_ci_shards_cover_every_test_file():
    """Every tests/test_*.py must appear in a CI shard — a new test file
    that CI never runs is a silent coverage hole (the same class of
    meta-check as the stage-fixture requirement above)."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ci = open(os.path.join(root, ".github", "workflows", "ci.yml")).read()
    sharded = set(re.findall(r"tests/test_\w+\.py", ci))
    on_disk = {
        f"tests/{f}" for f in os.listdir(os.path.dirname(os.path.abspath(__file__)))
        if f.startswith("test_") and f.endswith(".py")
    }
    missing = sorted(on_disk - sharded)
    assert not missing, f"test files absent from CI shards: {missing}"
