"""The two ``programs_built`` metrics (layer "compile cache"): their files are
found for their cells and no other, ``BENCHMARK.json`` repeats them, they read
what the spans' tag says a job, nothing where the program has no such tag,
and 0 in a dry run's window (the untimed warm-up job is the one that builds)."""

import json
import os

import pytest

from chipbench import run
from chipbench.readers import span_tag_per_job

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIT, FEATURIZE, SCORE = ("gbdt-higgs.fit-1m-resident", "resnet50-224.featurize-bulk",
                         "trinity-mini.score-8k")
METRICS = {"programs_built.featurize": FEATURIZE, "programs_built.score": SCORE}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metric_file_is_found_for_its_cell_and_the_manifest_repeats_it(metric):
    cell = METRICS[metric]
    spec = run.layer_metrics(cell)[metric]
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"], spec["moves"]) == (
        "compile cache", "count", "lower", "program_counter", "featurize_img_per_s")
    assert spec["reader"] == "span_tag_per_job" and spec["args"]["tag"] == "programs_built"
    assert all(metric not in run.layer_metrics(other) for other in (FIT, FEATURIZE, SCORE) if other != cell)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[metric]
    assert entry["workloads"] == [cell] and entry["layer"] == "compile cache"


def _span(name, **tags):
    return {"name": name, "duration": 1.0, "tags": tags}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_reader_counts_builds_a_job_and_reads_nothing_without_the_tag(metric):
    args = run.layer_metrics(METRICS[metric])[metric]["args"]
    names = args["spans"]
    parent = [_span(name, rows=8) for name in names] * 3  # the spans before they had the tag
    assert span_tag_per_job.read({"spans": parent, "jobs": 3}, **args) is None
    hits = [_span(name, rows=8, programs_built=0) for name in names] * 3
    assert span_tag_per_job.read({"spans": hits, "jobs": 3}, **args) == 0.0
    rebuilt = [_span(name, rows=8, programs_built=1) for name in names] * 3  # a program a span, every job
    assert span_tag_per_job.read({"spans": rebuilt, "jobs": 3}, **args) == float(len(names))
    other = [_span("image.featurize", programs_built=5), _span("lm.featurize", programs_built=5)]
    assert span_tag_per_job.read({"spans": other, "jobs": 1}, **args) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_dry_runs_window_builds_nothing_after_the_warm_up_job(metric):
    cell = METRICS[metric]
    line = run.measure(run.load_cell(cell), 2**31 + 30, 0.0, True, True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["metrics"]["dry_" + metric] == {"value": 0.0, "unit": "count"}
    assert line["metrics"]["dry_" + metric.replace("programs_built", "window_compile_s")]["value"] == 0.0
