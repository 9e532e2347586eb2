"""Test harness configuration.

Forces an 8-virtual-device CPU platform so every distributed code path
(shard_map/psum over the mesh) is exercised without TPU hardware — the
analogue of the reference running multi-worker LightGBM on `local[*]`
partitions (SURVEY.md §4 "Distributed behavior without a real cluster").

Both variables must be set before jax is first imported, hence the env
mutation at module import time.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()

import numpy as np
import pytest


# A test of the benchmark's own that holds only at the PR that wrote it, with why. ``tests/chipbench/`` is one of
# the benchmark's ``paths``: a PR that is no ``benchmark`` PR may add files there and edit none, and BENCHMARK.json's
# lists may only be appended to AT THE END (an entry put in the middle reads to the driver as a change to what was
# there). PR 32's test asserts that ITS metric is the manifest's last ``per_layer`` entry, which the next PR to append
# one makes false (PR 33 did). Everything else that test asserts is asserted by name in
# ``tests/chipbench/test_device_batches_by_name.py``. Strict: the day a ``benchmark`` PR makes the test look its entry
# up by name it passes again, this line fails, and goes with that file (PERF.md 7, row 15).
_OUTDATED_BY_AN_APPEND = {
    "tests/chipbench/test_device_batches.py::test_the_metric_file_is_found_for_its_cell_and_the_manifest_repeats_it":
        "asserts per_layer[-1] is PR 32's entry; PR 33 appended thirteen after it and may not edit a file of the benchmark",
    # PR 35: set-up's metrics list every cell from birth (PERF.md 7, row 12: no second copy a cell), and these two
    # assert that their cell's metrics are exactly the thirteen of the PR that wrote them. Everything else they
    # assert is asserted by name in ``tests/chipbench/test_compile_log.py``.
    "tests/chipbench/test_lm_score_mla.py::test_the_cell_is_the_issues":
        "asserts the cell lists exactly PR 31's thirteen metrics; PR 35's five over compile_log list every cell",
    "tests/chipbench/test_lm_score_ssm.py::test_the_cell_is_the_issues":
        "asserts the cell lists exactly PR 33's thirteen metrics; PR 35's five over compile_log list every cell",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = _OUTDATED_BY_AN_APPEND.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))


def pytest_configure(config):
    """Build the native library once, before any test file runs.

    ``native/libmmlspark_native.so`` is git-ignored, so a fresh checkout has
    none, and the ``native`` half of ``test_hashing_batch.py``'s fixture skips
    unless a file that builds it happened to reach a worker first. The
    controller's ``pytest_configure`` runs before xdist starts its workers,
    so every worker finds the library and the count of passing tests is the
    same on a clean tree and a warm one. Without a toolchain, or where the
    build fails, those tests skip as before."""
    import shutil
    import subprocess

    from mmlspark_tpu import native

    if (
        hasattr(config, "workerinput")  # an xdist worker: the controller built it
        or native.native_disabled()
        or shutil.which("make") is None
        or shutil.which("g++") is None
        or native.native_available()
    ):
        return
    try:
        native.build()
    except (OSError, RuntimeError, subprocess.CalledProcessError) as e:
        print(f"conftest: native build failed, native tests will skip: {e}",
              file=sys.stderr)


@pytest.fixture(scope="session")
def mesh8():
    from mmlspark_tpu.parallel import make_mesh

    return make_mesh()


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_program_accumulation():
    """Evict compiled-program caches after every test module.

    The full suite in ONE process accumulates hundreds of XLA:CPU
    executables; past a threshold the compiler itself segfaults inside
    ``backend_compile_and_load`` while building the next big shard_map
    program (reproduced deterministically at ~300 tests on the
    voting-parallel training step; neither half of the suite alone
    triggers it, and the CI shard layout used to mask it). Module scope
    keeps within-file program reuse intact while bounding the process-wide
    footprint — the same ``mmlspark_tpu.clear_compiled_caches()`` a
    long-lived production process should call between workloads.
    """
    yield
    import mmlspark_tpu

    mmlspark_tpu.clear_compiled_caches()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def assert_tables_equal(a, b, rtol=1e-5, atol=1e-6):
    """Tolerant Table equality — the `DataFrameEquality` analogue
    (reference `core/test/base/TestBase.scala:244-316`)."""
    assert a.columns == b.columns, f"{a.columns} != {b.columns}"
    assert a.num_rows == b.num_rows
    for name in a.columns:
        ca, cb = a[name], b[name]
        if ca.dtype == object or cb.dtype == object:
            assert list(map(str, ca.ravel())) == list(map(str, cb.ravel())), name
        elif np.issubdtype(ca.dtype, np.floating):
            np.testing.assert_allclose(
                ca.astype(float), cb.astype(float), rtol=rtol, atol=atol, err_msg=name
            )
        else:
            np.testing.assert_array_equal(ca, cb, err_msg=name)


@pytest.fixture()
def table_equal():
    return assert_tables_equal


@pytest.fixture()
def basic_table():
    """`makeBasicDF` fixture analogue (TestBase.scala:191-205)."""
    from mmlspark_tpu.data.table import Table

    return Table(
        {
            "numbers": np.array([0, 1, 2, 3], dtype=np.int64),
            "doubles": np.array([0.0, 1.5, 2.5, 3.5]),
            "words": np.array(["guitars", "drums", "bass", "keys"], dtype=object),
        }
    )
