"""The harness: window arithmetic, trace reduction, work counted from shapes,
the look for a chip, a dry run's last line, cells and metrics added as
files, and the comparison that decides ``correct`` shown to fail: under the
lower-precision control and under each fault a cell can have."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import run, trace_reduce
from chipbench.drivers import featurize, gbdt_fit
from chipbench.reference import gbdt as gref
from chipbench.reference import resnet as rref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIT, FEATURIZE = "gbdt-higgs.fit-1m-resident", "resnet50-224.featurize-bulk"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# -- the window ---------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.mark.parametrize("job_s,seconds,jobs", [(4.0, 10.0, 3), (5.0, 10.0, 2), (30.0, 10.0, 1), (1.0, 0.0, 1)])
def test_window_ends_on_a_job_boundary(job_s, seconds, jobs):
    clock = FakeClock()

    def job():
        clock.now += job_s
        return "out"

    window_s, done = run.window(job, seconds, clock)
    assert len(done) == jobs and window_s == jobs * job_s
    assert all(secs == job_s and err is None for _, err, secs in done)


def test_window_divides_all_the_work_by_all_the_time():
    clock = FakeClock()
    took = iter([2.0, 7.0, 3.0])

    def job():
        clock.now += next(took) + 0.5  # the half second between jobs counts too
        return "out"

    window_s, done = run.window(job, 12.0, clock)
    assert window_s == 13.5 and len(done) == 3
    assert gbdt_fit.end_to_end({}, window_s, 3)["fit_s"] == (4.5, "s")
    assert featurize.end_to_end({"images": 100}, window_s, 3)["featurize_img_per_s"][0] == 300 / 13.5


def test_a_job_that_raises_is_counted_and_the_window_goes_on():
    clock = FakeClock()
    calls = []

    def job():
        clock.now += 6.0
        calls.append(1)
        if len(calls) == 1:
            raise MemoryError("boom")
        return "out"

    _, done = run.window(job, 10.0, clock)
    assert [err for _, err, _ in done] == ["MemoryError: boom", None]


# -- the trace ----------------------------------------------------------------

def test_fixture_is_what_its_encoder_writes():
    import xplane_fixture

    with open(xplane_fixture.PATH, "rb") as f:
        assert f.read() == xplane_fixture.encode()


def test_trace_reduction_is_exact_on_the_recorded_trace():
    path = os.path.join(HERE, "fixtures", "two_ops_one_gap.xplane.pb")
    devices, host, seen = trace_reduce.load(path)
    assert seen == {"/device:TPU:0": ["XLA Ops"], "/host:CPU": ["python3"]}
    got = trace_reduce.reduce(devices, host)
    assert got["busy_s"] == 6000e-9 and got["window_s"] == 10000e-9
    assert got["device_ops"] == [["fusion.2", 3000e-9], ["copy.3", 1500e-9], ["while.1", 1500e-9]]
    assert got["idle_gaps"] == [["(no host span)", 2000e-9], ["lightgbm.binning", 2000e-9]]
    from chipbench.readers import trace_idle

    assert trace_idle.read({"trace": got}) == pytest.approx(40.0)
    assert trace_idle.read({"trace": None}) is None


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce({"/device:TPU:0": []}, [("x", 0.0, 1.0)])


def test_union_and_self_times():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    events = [("outer", 0.0, 10.0), ("inner", 2.0, 3.0), ("inner", 6.0, 1.0), ("alone", 20.0, 4.0)]
    assert trace_reduce.self_times(events) == {"outer": 6.0, "inner": 4.0, "alone": 4.0}


# -- readers ------------------------------------------------------------------

def test_readers_return_nothing_where_there_is_nothing_to_read():
    from chipbench.readers import compile_clock, memory_stats, mfu, tracer_span

    ctx = {"jobs": 0, "work": {"flops": 0, "bytes": 8}, "window_s": 2.0, "spans": [],
           "memory": {}, "peaks": {"hbm_bytes_per_s": 100.0},
           "compile": {"compile_secs": 0.25, "trace_secs": 0.5}}
    assert mfu.read(ctx, bound="bytes", peak="hbm_bytes_per_s") is None
    assert mfu.read(dict(ctx, jobs=5), bound="flops", peak="hbm_bytes_per_s") is None
    assert mfu.read(dict(ctx, jobs=5), bound="bytes", peak="hbm_bytes_per_s") == 20.0
    assert tracer_span.read(ctx, span="lightgbm.binning") is None
    spans = [{"name": "lightgbm.binning", "duration": 0.5}, {"name": "lightgbm.binning", "duration": 1.5},
             {"name": "other", "duration": 9.0}]
    assert tracer_span.read(dict(ctx, spans=spans), span="lightgbm.binning") == 1000.0
    assert memory_stats.read(ctx) is None
    assert memory_stats.read(dict(ctx, memory={"peak_bytes_in_use": 2**31})) == 2.0
    assert compile_clock.read(ctx) == 0.75


# -- work from shapes ---------------------------------------------------------

def test_resnet50_flops_against_the_hand_count():
    # He et al. give 3.8e9 multiply-adds with the 1x1 projections uncounted
    # in some tables; torchvision's resnet50 at 224 is 4.09e9 with the fc
    # layer (2.05e6), so the convolutions alone are 4.087e9 x 2.
    flops = featurize.conv_flops([3, 4, 6, 3], 7, 224)
    stem = 2 * 112 * 112 * 64 * 3 * 49
    assert flops > stem and abs(flops / 2 - 4.087e9) / 4.087e9 < 0.005
    one = featurize.work({"blocks": [3, 4, 6, 3], "stem_kernel": 7, "image_size": 224}, {"images": 1})
    assert featurize.work({"blocks": [3, 4, 6, 3], "stem_kernel": 7, "image_size": 224},
                          {"images": 4096})["flops"] == 4096 * one["flops"]


@pytest.mark.parametrize("rows", [1_000_000, 2_000_000, 6_000, 513])
def test_u_bytes_against_the_programs_own(rows):
    from mmlspark_tpu.ops.u_histogram import make_u_spec, u_bytes

    spec = make_u_spec(256, 28, [256] * 28)
    assert gbdt_fit.u_bytes(rows, 28, 255) == u_bytes(rows, spec)
    assert gbdt_fit.u_bytes(1_000_000, 28, 255) == 1_000_448 * 7_168


def test_fit_work_is_one_read_of_bins_and_gradient_pairs_a_tree():
    cell = run.load_cell(FIT)
    got = gbdt_fit.work(cell["config_file"]["params"], cell["params"])
    assert got["bytes"] == 100 * 1_000_000 * (28 + 8)
    fewer = {**cell["params"], "estimator": {"numIterations": 20}, "rows": 2_000_000}
    assert gbdt_fit.work(cell["config_file"]["params"], fewer)["bytes"] == 20 * 2_000_000 * 36


# -- the look for a chip ------------------------------------------------------

def _cli(args, cwd=ROOT, env=None):
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", *args], cwd=cwd, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )


def test_off_chip_without_the_flag_exits_nonzero_and_prints_no_result():
    got = _cli(["--workload", FIT, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert got.returncode == 2 and got.stdout == ""
    assert "needs 1 TPU chip" in got.stderr


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    got = _cli(["--workload", FIT, "--seed", "1", "--seconds", "1", "--trace", "0", "--dry-run-cpu"],
               cwd=str(tmp_path), env={"PYTHONPATH": ""})
    assert got.returncode != 0 and got.stdout == ""
    assert "mmlspark_tpu" in got.stderr


def test_an_unknown_device_is_an_error(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "cb",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(tmp_path / "cb" / "peaks.json", "w") as f:
        json.dump({"TPU v9": {}}, f)
    with pytest.raises(KeyError, match="not in chipbench/peaks.json"):
        run.measure(run.load_cell(FIT), 1, 0.0, False, True, root=str(tmp_path / "cb"))


# -- a dry run, end to end ----------------------------------------------------

@pytest.fixture(scope="module")
def dry_lines():
    """One dry run of each driver through the command itself, traced."""
    out = {}
    for cell in (FIT, FEATURIZE):
        got = _cli(["--workload", cell, "--seed", str(2**31 + 7), "--seconds", "0.2",
                    "--trace", "1", "--dry-run-cpu"])
        assert got.returncode == 0, got.stderr[-2000:]
        out[cell] = (json.loads(got.stdout.strip().splitlines()[-1]), got.stderr)
    return out


@pytest.mark.parametrize("cell", [FIT, FEATURIZE])
def test_last_line_of_a_dry_run_has_the_contracts_keys(dry_lines, cell):
    line, stderr = dry_lines[cell]
    assert set(line) == RESULT_KEYS | {"breakdown", "checks"}
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s"}
    assert line["device"]["platform"] == "cpu"
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(rows) <= 10 for rows in line["breakdown"].values())
    assert line["metrics"] and all(name.startswith("dry_") for name in line["metrics"])
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    for name, check in line["checks"].items():
        assert f"check {name}: {check['value']!r} limit {check['limit']!r}" in stderr
    assert stderr.rstrip().endswith("correct: True")


@pytest.mark.parametrize("cell", [FIT, FEATURIZE])
def test_a_traced_dry_run_reports_the_cells_layer_metrics(dry_lines, cell):
    line, _ = dry_lines[cell]
    listed = {"dry_" + name for name in run.layer_metrics(cell)}
    # the CPU reports no memory statistics, so that reader has nothing to read
    assert set(line["metrics"]) == {n for n in listed if "hbm_peak" not in n}


# -- cells and metrics as added files -----------------------------------------

@pytest.fixture()
def copy_of_bench(tmp_path):
    dst = tmp_path / "cb"
    shutil.copytree(os.path.join(ROOT, "chipbench"), dst, ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def test_a_cell_added_as_a_file_is_found_with_no_edit(copy_of_bench):
    new = "gbdt-higgs.fit-1m-short"
    spec = json.loads((copy_of_bench / "workloads" / f"{FIT}.json").read_text())
    spec.update(traffic="fit-1m-short", why="ten trees: binning and upload are most of the job")
    spec["dry"]["estimator"] = {"numIterations": 2}
    (copy_of_bench / "workloads" / f"{new}.json").write_text(json.dumps(spec))
    cell = run.load_cell(new, root=str(copy_of_bench))
    assert cell["name"] == new and cell["config_file"]["params"]["features"] == 28
    line = run.measure(cell, 5, 0.0, False, True, root=str(copy_of_bench))
    assert line["correct"] and set(line["metrics"]) == {"dry_fit_s", "dry_setup_s"}
    assert line["checks"]["leaf_gap_max"]["value"] < 1e-4


def test_a_layer_metric_added_as_a_file_is_found_with_no_edit(copy_of_bench):
    spec = {"layer": "host binning", "unit": "ms", "better": "lower", "source": "program_span",
            "moves": "fit_s", "workloads": [FIT], "reader": "tracer_span",
            "args": {"span": "chipbench.binning"}}
    (copy_of_bench / "layer_metrics" / "binning_again_ms.fit.json").write_text(json.dumps(spec))
    assert "binning_again_ms.fit" in run.layer_metrics(FIT, root=str(copy_of_bench))
    assert "binning_again_ms.fit" not in run.layer_metrics(FEATURIZE, root=str(copy_of_bench))
    line = run.measure(run.load_cell(FIT), 5, 0.0, True, True, root=str(copy_of_bench))
    assert line["metrics"]["dry_binning_again_ms.fit"] == line["metrics"]["dry_binning_ms.fit"]


# -- correct, shown to fail ---------------------------------------------------

def _broken(driver, job):
    """The driver with its timed job replaced once the warm-up has passed;
    everything else as it is."""
    calls = []

    def after_warm_up(state):
        calls.append(1)
        return driver.job(state) if len(calls) == 1 else job(state)

    return types.SimpleNamespace(**{
        k: getattr(driver, k) for k in ("setup", "fault", "end_to_end", "work", "compare")
    }, job=after_warm_up)


def _fit_with(alter):
    def job(state):
        out = gbdt_fit.job(state)
        booster = out["model"].booster
        alter(booster, state)
        out["model"].set_booster(booster)
        return out

    return run.measure(run.load_cell(FIT), 9, 0.0, False, True, driver=_broken(gbdt_fit, job))


def _stuck_state(booster, state):
    """A boosting step that hands its margins back unchanged grows the
    same tree again."""
    for name in ("split_feature", "split_bin", "split_threshold", "left_child",
                 "right_child", "is_leaf", "leaf_values", "cover", "split_gain"):
        getattr(booster, name)[1] = getattr(booster, name)[0]


def _one_leaf_altered(booster, state):
    tree, slot = 2, int(np.flatnonzero(booster.is_leaf[2])[0])
    booster.leaf_values[tree, slot] *= 1.02


def _threshold_altered(booster, state):
    booster.split_threshold[0, 0] += np.float32(0.05)


@pytest.mark.parametrize("alter,number", [
    (_stuck_state, "leaf_gap_max"), (_one_leaf_altered, "leaf_gap_max"),
    (_threshold_altered, "leaf_gap_rms"),
])
def test_a_broken_fit_is_not_correct(alter, number):
    line = _fit_with(alter)
    assert line["correct"] is False and line["failed"] == 0
    check = line["checks"][number]
    assert check["value"] > check["limit"]


def test_a_fit_over_half_the_rows_is_not_correct(monkeypatch):
    """Half of the batch left out, the sums taken over the rest."""
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    whole = LightGBMClassifier.fit
    monkeypatch.setattr(
        LightGBMClassifier, "fit", lambda self, t, **kw: whole(self, t.slice(0, t.num_rows // 2), **kw)
    )
    line = run.measure(run.load_cell(FIT), 9, 0.0, False, True)
    assert line["correct"] is False
    assert line["checks"]["leaf_gap_rms"]["value"] > line["checks"]["leaf_gap_rms"]["limit"]


def test_a_predict_that_alters_an_answer_is_not_correct(monkeypatch):
    from mmlspark_tpu.lightgbm.booster import Booster

    honest = Booster.raw_margin

    def altered(self, X, *a, **kw):
        out = honest(self, X, *a, **kw)
        out[len(out) // 2] += 1e-3
        return out

    monkeypatch.setattr(Booster, "raw_margin", altered)
    line = run.measure(run.load_cell(FIT), 9, 0.0, False, True)
    assert line["correct"] is False
    assert line["checks"]["predict_gap"]["value"] > line["checks"]["predict_gap"]["limit"]
    assert line["checks"]["leaf_gap_max"]["value"] <= line["checks"]["leaf_gap_max"]["limit"]


def test_a_fit_that_degrades_counts_as_failed(monkeypatch):
    from mmlspark_tpu.observability.events import HistogramDegraded, get_bus

    def job(state):
        out = gbdt_fit.job(state)
        out["events"].append("HistogramDegraded")
        return out

    line = run.measure(run.load_cell(FIT), 9, 0.0, False, True, driver=_broken(gbdt_fit, job))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)
    with gbdt_fit.fit_events() as seen:  # and the listener does hear the bus
        fields = {f: 0 for f in getattr(HistogramDegraded, "__dataclass_fields__", {})}
        get_bus().publish(HistogramDegraded(**fields))
    assert seen == ["HistogramDegraded"]


@pytest.mark.parametrize("events,path,peak_ok,failed", [
    ([], "resident", True, False), (["HistogramChunked"], "resident", True, True),
    ([], "resident", False, True), ([], "chunked", True, True),
    (["HistogramChunked"], "chunked", True, False), (["MemoryPressure"], "chunked", True, True),
    (["HistogramChunked"], None, True, False),
])
def test_a_fit_off_its_cells_path_counts_as_failed(events, path, peak_ok, failed, monkeypatch):
    import jax

    peak = 10 if peak_ok else 1
    device = types.SimpleNamespace(memory_stats=lambda: {"peak_bytes_in_use": peak})
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    reason = gbdt_fit.fault({"u_path": path, "u_bytes": 5}, {"events": events})
    assert bool(reason) is failed


def _featurize_with(wrap, monkeypatch):
    import mmlspark_tpu.models.resnet as zoo

    honest = zoo.resnet_apply
    monkeypatch.setattr(zoo, "resnet_apply", lambda p, x, cut=0, **kw: wrap(honest(p, x, cut, **kw)))
    return run.measure(run.load_cell(FEATURIZE), 9, 0.0, False, True)


def _half_left_out(feats):
    import jax.numpy as jnp

    half = feats.shape[0] // 2
    kept = feats[:half]
    return jnp.concatenate([kept, jnp.broadcast_to(kept.mean(0), feats[half:].shape)])


@pytest.mark.parametrize("wrap", [_half_left_out, lambda feats: feats.at[1].multiply(1.05)])
def test_a_broken_forward_is_not_correct(wrap, monkeypatch):
    line = _featurize_with(wrap, monkeypatch)
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["feature_gap_max"]["value"] > line["checks"]["feature_gap_max"]["limit"]


def test_non_finite_features_count_as_failed():
    def job(state):
        out = featurize.job(state)
        return dict(out, finite=False)

    line = run.measure(run.load_cell(FEATURIZE), 9, 0.0, False, True, driver=_broken(featurize, job))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)


# -- the control, at a size a test can hold -----------------------------------

def _small_state(rows, trees, seed=4):
    cell = run.load_cell(FIT)
    config = {**cell["config_file"]["params"]}
    traffic = {**cell["params"], **cell["dry"], "rows": rows, "estimator": {"numIterations": trees}}
    return gbdt_fit.setup(config, traffic, seed)


def _fit_checks(state, **estimator):
    """The comparison's numbers for the program's own fit with these
    estimator params switched on, the honest predict in place."""
    low = dict(state, estimator={**state["estimator"], **estimator})
    forest = gbdt_fit.forest_of(gbdt_fit.job(low)["model"])
    xte = np.ascontiguousarray(state["Xte"].astype(np.float32).T)
    return forest, gbdt_fit.checks(state, forest, gref.margins(xte, forest), 0)


@pytest.fixture(scope="module")
def small_fit():
    state = _small_state(20_000, 40)
    return (state, *_fit_checks(state))


def test_the_int8_control_fails_the_leaf_numbers(small_fit, monkeypatch):
    """The program's own path one precision below bfloat16 gradients,
    ``useQuantizedGrad``, as on the chip (PERF.md). It rides the U path,
    which the program takes on a TPU only, so the test says it is on one."""
    import mmlspark_tpu.lightgbm.train as train

    state, _, honest = small_fit
    assert run.passes(honest)
    monkeypatch.setattr(train, "on_tpu", lambda: True)
    _, control = _fit_checks(state, useQuantizedGrad=True)
    assert control["leaf_gap_rms"]["value"] > state["limits"]["leaf_gap_rms"]
    assert not run.passes(control)


def test_the_coarse_bins_control_fails_the_split_regret():
    """``maxBin`` 15 (4-bit bins where the configuration states 8-bit), one
    tree, at a size where tree 0 has nodes large enough to search again."""
    state = _small_state(400_000, 1)
    _, honest = _fit_checks(state)
    _, control = _fit_checks(state, maxBin=gbdt_fit.COARSE_BINS)
    limit = state["limits"]["split_regret_rms"]
    assert honest["split_regret_rms"]["value"] <= limit < control["split_regret_rms"]["value"]


def test_a_toy_fit_has_no_node_to_search_again(small_fit):
    assert "split_regret_rms" not in small_fit[2]


def test_the_exact_search_against_brute_force():
    """One feature that separates, one that does not: the best gain is the
    brute-force maximum over every threshold, and a fit that split on the
    noise column reads a regret near 1."""
    rng = np.random.default_rng(0)
    n = 400
    x = rng.normal(size=(2, n)).astype(np.float32)
    y = (x[0] + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    init = float(np.log(y.mean() / (1 - y.mean())))
    g, h = gref.grad_hess(np.full(n, init), y)
    best = 0.0
    for col in x:
        for t in np.unique(col)[:-1]:
            left = col <= t
            if min(left.sum(), n - left.sum()) >= 20:
                gain = g[left].sum() ** 2 / h[left].sum() + g[~left].sum() ** 2 / h[~left].sum()
                best = max(best, gain - g.sum() ** 2 / h.sum())

    def stump(feature, threshold):
        slots = lambda *v: np.asarray([v])
        return gref.Forest(
            feature=slots(feature, 0, 0), threshold=slots(threshold, 0, 0).astype(np.float32),
            left=slots(1, 0, 0), right=slots(2, 0, 0), is_leaf=slots(False, True, True),
            value=np.zeros((1, 3), np.float32), init_score=init,
        )

    good = gref.split_gains(x, y, stump(0, 0.0), min_rows=100, min_data=20)
    assert good.shape == (1, 2) and np.isclose(good[0, 1], best, rtol=1e-12)
    assert 0 <= 1 - good[0, 0] / good[0, 1] < 0.1
    noise = gref.split_gains(x, y, stump(1, 0.0), min_rows=100, min_data=20)
    assert 1 - noise[0, 0] / noise[0, 1] > 0.9
    assert gref.split_gains(x, y, stump(0, 0.0), min_rows=n + 1, min_data=20).shape == (0, 2)


def test_the_bfloat16_control_fails_the_predict_number(small_fit):
    state, forest, _ = small_fit
    xte = np.ascontiguousarray(state["Xte"].astype(np.float32).T)
    rounded = gref.margins(xte, forest, values=gref.bfloat16(forest.value))
    gap = np.abs(rounded - gref.margins(xte, forest)).max()
    assert gap > state["limits"]["predict_gap"]


def test_bfloat16_rounding_is_nearest_even():
    import jax.numpy as jnp

    x = np.random.default_rng(0).normal(size=1000).astype(np.float32)
    assert np.array_equal(gref.bfloat16(x), np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)))


def _dry_featurize_state(seed=4):
    cell = run.load_cell(FEATURIZE)
    return featurize.setup(*run.sizes(cell, True), seed)


def test_the_bfloat16_control_fails_the_feature_number():
    """The program's own ``resnet_apply(dtype=bfloat16)`` in its place."""
    state = _dry_featurize_state()
    assert run.passes(featurize.checks(state, [featurize.job(state)]))
    control = featurize.control(dict(state))["control"]
    assert control["feature_gap_max"]["value"] > state["limits"]["feature_gap_max"]


def test_the_reference_rounds_products_as_the_configuration_states():
    """``bfloat16`` products are the float32 convolution of inputs rounded
    to bfloat16; anything the configuration does not state is an error."""
    import jax.numpy as jnp

    cell = run.load_cell(FEATURIZE)
    assert cell["config_file"]["params"]["products"] == "bfloat16"
    assert run.sizes(cell, True)[0]["products"] == "float32"  # the CPU's default
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3, 8, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 3, 3, 3)), jnp.float32)
    rounded = [jnp.asarray(gref.bfloat16(np.asarray(a))) for a in (x, w)]
    assert np.array_equal(rref._conv(x, w, 1, "bfloat16"), rref._conv(*rounded, 1, "float32"))
    assert not np.array_equal(rref._conv(x, w, 1, "bfloat16"), rref._conv(x, w, 1, "float32"))
    with pytest.raises(ValueError):
        rref._conv(x, w, 1, "float8")


# -- the readings the limits are set from -------------------------------------

def _calibrate(args):
    return subprocess.run(
        [sys.executable, "-m", "chipbench.calibrate", *args], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_calibrate_reads_nothing_off_the_chip():
    got = _calibrate(["--workload", FIT, "--seeds", "1"])
    assert got.returncode == 2 and got.stdout == ""
    assert "needs 1 TPU chip" in got.stderr


def test_calibrate_names_the_device_and_says_correct_on_every_line():
    got = _calibrate(["--workload", FEATURIZE, "--seeds", "3", "--control-seeds", "3", "--dry-run-cpu"])
    assert got.returncode == 0, got.stderr[-2000:]
    lines = [json.loads(l) for l in got.stdout.strip().splitlines()]
    assert [(l["side"], l["correct"]) for l in lines] == [("control", False), ("program", True)]
    assert all("platform='cpu'" in l["device"] for l in lines)
    assert all(l["correct"] == run.passes(l["checks"]) for l in lines)


def test_reference_weights_have_the_zoos_shape_and_come_from_the_seed():
    import jax

    from mmlspark_tpu.models import init_resnet

    ours = rref.init_params(featurize.key_of(2**31 + 5))
    zoo = init_resnet(seed=0, variant="resnet50")
    assert jax.tree.structure(ours) == jax.tree.structure(zoo)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(zoo)))
    again = rref.init_params(featurize.key_of(2**31 + 5))
    other = rref.init_params(featurize.key_of(5))
    first = lambda p: np.asarray(p["stem"]["conv"]["w"])
    assert np.array_equal(first(ours), first(again)) and not np.array_equal(first(ours), first(other))
