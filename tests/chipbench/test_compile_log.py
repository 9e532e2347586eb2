"""The six metrics over ``readers/compile_log`` (PR 35), looked up by name:
their files are found for their cells and ``BENCHMARK.json`` repeats them; the
reader splits the program's compile log at the window's start, reads 0.0 for a
window whose spans paid nothing and nothing where there is nothing to read; a
traced dry run prints them all, set-up's shares under ``dry_setup_s`` and the
window's equal to the harness's own clock."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run
from chipbench.readers import compile_log
from mmlspark_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIT, FEATURIZE, SCORE, MLA, SSM = (
    "gbdt-higgs.fit-1m-resident", "resnet50-224.featurize-bulk", "trinity-mini.score-8k",
    "joyai-llm-flash.score-16k", "nemotron-3-nano.score-16k")
CELLS, DEEP = [FIT, FEATURIZE, SCORE, MLA, SSM], [FEATURIZE, SCORE, MLA, SSM]
ROOTS = ["lightgbm.fit", "image.featurize", "lm.featurize"]
CACHE, FIRST = "compile cache", "first call of a stage"
# metric: (unit, better, source, layer, moves, cells, args)
METRICS = {
    "setup_compile_s": ("s", "lower", "program_counter", CACHE, "setup_s", CELLS, {"setup": ["compile_s"]}),
    "setup_trace_s": ("s", "lower", "program_counter", CACHE, "setup_s", CELLS, {"setup": ["trace_s"]}),
    "setup_cache_misses": ("count", "lower", "program_counter", CACHE, "setup_s", CELLS,
                           {"setup": ["cache_misses"]}),
    "first_call_s": ("s", "lower", "program_span", FIRST, "setup_s", CELLS, {"first_call": ROOTS}),
    "program_compile_s.fit": ("s", "lower", "program_span", CACHE, "fit_s", [FIT],
                              {"window": ["trace_s", "compile_s"]}),
    "program_compile_s.transform": ("s", "lower", "program_span", CACHE, "featurize_img_per_s", DEEP,
                                    {"window": ["trace_s", "compile_s"]}),
}
WINDOW_OF = {FIT: ("program_compile_s.fit", "window_compile_s.fit"),
             FEATURIZE: ("program_compile_s.transform", "window_compile_s.featurize")}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metric_file_is_found_for_its_cells_and_the_manifest_repeats_it(metric):
    unit, better, source, layer, moves, cells, args = METRICS[metric]
    for cell in CELLS:
        found = run.layer_metrics(cell).get(metric)
        assert (found is not None) == (cell in cells), cell
    spec = run.layer_metrics(cells[0])[metric]
    assert spec == {"layer": layer, "unit": unit, "better": better, "source": source, "moves": moves,
                    "workloads": cells, "reader": "compile_log", "args": args}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert {m["name"]: m for m in manifest["per_layer"]}[metric] == {
        "name": metric, "unit": unit, "better": better, "source": source, "layer": layer,
        "moves": moves, "workloads": cells}
    # an addition, after everything PR 34's manifest held
    assert names.index(metric) > names.index("experts_roofline_pct.ssm")


# Everything ``test_lm_score_mla.py`` / ``test_lm_score_ssm.py::test_the_cell_is_the_issues`` assert but
# one thing: that their cell lists exactly the thirteen metrics of the PR that wrote it, which the five
# metrics above end (``tests/conftest.py`` marks the two ``xfail`` with the reason; PERF.md 7, row 15).
THIRTEEN = ("score_mfu_pct", "device_idle_pct", "hbm_peak_gib", "window_compile_s", "programs_built",
            "stack_ms", "dispatch_ms", "fetch_ms", "span_coverage_pct", "expert_load_peak_pct",
            "attn_roofline_pct", "experts_roofline_pct", "experts_empty_pct")


@pytest.mark.parametrize("cell,suffix,driver,config,patterns", [
    (MLA, ".mla", "lm_score_mla", "joyai-llm-flash", {"attn_roofline_pct": {"pattern": "^attn_full"}}),
    (SSM, ".ssm", "lm_score_ssm", "nemotron-3-nano", {
        "attn_roofline_pct": {"pattern": "^attn_full", "rows": 1},
        "experts_roofline_pct": {"pattern": "^ragged-dot", "rows": 2}}),
])
def test_a_latent_or_hybrid_cell_is_its_issues_but_for_the_count_of_its_metrics(
        cell, suffix, driver, config, patterns):
    spec = run.load_cell(cell)
    assert (spec["chips"], spec["driver"], spec["config"]) == (1, driver, config)
    params = spec["params"]
    assert (params["rows"], params["tokens"], params["batchSize"]) == (12, 16384, 1)
    assert (params["zipf_exponent"], params["compare_rows"]) == (1.0, 2)
    assert spec["profiler"] == {"host_tracer_level": 1} and len(spec["why"]) <= 200
    assert set(params["limits"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max", "head_gap_max"}
    listed = run.layer_metrics(cell)
    own = {name: listed[name] for name in listed if name.endswith(suffix)}
    assert set(own) == {name + suffix for name in THIRTEEN}
    assert set(listed) - set(own) == {m for m, v in METRICS.items() if cell in v[5]}, "and PR 35's five"
    assert all(m["workloads"] == [cell] and m["moves"] == "featurize_img_per_s" for m in own.values())
    for name, args in patterns.items():
        assert {k: own[name + suffix]["args"][k] for k in args} == args
    for other in {SCORE, MLA, SSM} - {cell}:
        assert not run.layer_metrics(other).keys() & own.keys()


def _span(name, start, **tags):
    return {"name": name, "start": start, "duration": 1.0, "tags": tags}


def _record(t, span, trace_id="t1", **paid):
    return {"t": t, "span": span, "trace_id": trace_id,
            **{"trace_s": 0.0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0, **paid}}


class HandMadeTracer:
    """The two things the reader asks the program's tracer for."""

    def __init__(self, log, calls):
        self.log, self.calls = log, calls

    def compile_log(self):
        return list(self.log)

    def first_calls(self):
        return list(self.calls)


@pytest.fixture()
def hand_made(monkeypatch):
    def install(log, calls=()):
        monkeypatch.setattr(tracing, "_TRACER", HandMadeTracer(log, calls))
    return install


LOG = [
    _record(10.0, "(no span)", "", trace_s=0.5, compile_s=2.0, cache_misses=3),   # the driver's weights
    _record(12.0, "lightgbm.program", trace_s=1.5, compile_s=0.25, cache_hits=4),  # the warm-up job
    _record(13.0, "lightgbm.boost", trace_s=2.0, compile_s=4.0, cache_misses=1, cache_hits=2),
    _record(20.5, "lightgbm.boost", "t2", trace_s=8.0, compile_s=16.0, cache_misses=32),  # in the window
    _record(31.0, "(no span)", "", trace_s=64.0, compile_s=128.0, cache_misses=256),  # the reference, after it
]
CALLS = [{"name": "image.transform", "trace_id": "t0", "start": 11.0, "duration": 0.125},
         {"name": "lightgbm.fit", "trace_id": "t1", "start": 11.5, "duration": 7.5},
         {"name": "lm.featurize", "trace_id": "t3", "start": 25.0, "duration": 99.0}]
WINDOW = [_span("lightgbm.fit", 20.0, rows=8), _span("lightgbm.boost", 20.25, iterations=3),
          _span("lightgbm.fit", 24.0, rows=8)]


def test_the_reader_splits_the_log_at_the_windows_start(hand_made):
    hand_made(LOG, CALLS)
    ctx = {"spans": WINDOW}
    assert compile_log.read(ctx, setup=["compile_s"]) == 6.25
    assert compile_log.read(ctx, setup=["trace_s"]) == 4.0
    assert compile_log.read(ctx, setup=["cache_misses"]) == 4.0
    assert compile_log.read(ctx, setup=["trace_s", "compile_s"]) == 10.25
    # the earliest of the listed roots, before the window: the warm-up job
    assert compile_log.read(ctx, first_call=ROOTS) == 7.5
    assert compile_log.read(ctx, first_call=["image.transform", "lightgbm.fit"]) == 0.125
    assert compile_log.read(ctx, first_call=["lm.featurize"]) is None, "its first call is in the window"
    # a later window start moves the split, not the reader
    late = {"spans": [_span("lightgbm.fit", 30.0)]}
    assert compile_log.read(late, setup=["compile_s"]) == 22.25
    assert compile_log.read(late, first_call=["lm.featurize"]) == 99.0


def test_the_window_reads_its_spans_tags_and_zero_where_none_paid(hand_made):
    hand_made(LOG, CALLS)
    args = METRICS["program_compile_s.fit"][-1]
    assert compile_log.read({"spans": WINDOW}, **args) == 0.0
    paid = WINDOW + [_span("lightgbm.boost", 26.0, trace_s=0.5, compile_s=0.25, cache_misses=7),
                     _span("dnn.dispatch", 27.0, trace_s=0.125)]
    assert compile_log.read({"spans": paid}, **args) == 0.875


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_nothing_to_read_reads_as_nothing(metric, hand_made, monkeypatch):
    args = METRICS[metric][-1]
    hand_made(LOG, CALLS)
    assert compile_log.read({"spans": []}, **args) is None, "no span: no window to split at"
    hand_made([], [])
    got = compile_log.read({"spans": WINDOW}, **args)
    assert got == (0.0 if "window" in args else None), "no record, no first call"
    hand_made([r for r in LOG if r["t"] > 20.0], [c for c in CALLS if c["start"] > 20.0])
    assert compile_log.read({"spans": WINDOW}, **args) == (0.0 if "window" in args else None)
    # a program whose tracer books nothing (the parent commit's): every metric silent, none raises
    monkeypatch.setattr(tracing, "_TRACER", object())
    assert compile_log.read({"spans": WINDOW}, **args) is None


def _cli(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    got = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", str(2**31 + 35),
         "--seconds", "0.5", "--trace", "1", "--dry-run-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    lines = [json.loads(l) for l in got.stdout.strip().splitlines()]
    logged = {key: value for line in lines[:-1] for key, value in line.items()}
    return lines[-1], logged["setup"], logged["end_to_end"]["setup_s"]


@pytest.mark.parametrize("cell", sorted(WINDOW_OF))
def test_a_traced_dry_run_prints_the_new_metrics_and_they_add_up(cell, tmp_path):
    line, harness_clock, setup_s = _cli(cell, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    got = {name: m["value"] for name, m in line["metrics"].items()}
    ours, harness = WINDOW_OF[cell]
    for metric in ("setup_compile_s", "setup_trace_s", "setup_cache_misses", "first_call_s", ours):
        assert line["metrics"]["dry_" + metric]["unit"] == METRICS[metric][0], metric
    assert 0 < got["dry_setup_compile_s"] + got["dry_setup_trace_s"] < setup_s
    assert 0 < got["dry_first_call_s"] < setup_s
    # the harness's own clock listened to the same events from the same moment on
    assert got["dry_setup_compile_s"] == pytest.approx(harness_clock["compile_secs"], rel=1e-9)
    assert got["dry_setup_trace_s"] == pytest.approx(harness_clock["trace_secs"], rel=1e-9)
    assert got["dry_setup_cache_misses"] == harness_clock["cache_misses"]
    assert got["dry_" + ours] == got["dry_" + harness] == 0.0
