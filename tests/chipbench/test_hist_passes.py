"""``hist_passes_built.fit`` and ``hist_passes_skipped.fit`` (layer "boosting
program"): data only, two files over the reader ``span_tag_per_job``. They are
found for the fit cell and no other, ``BENCHMARK.json`` repeats them after
everything PR 35's manifest held, each reads its own tag of ``lightgbm.boost``
and not the other's (the reader matches a tag by ``startswith``), nothing where
the program has no such tag (the parent), and in a dry run 6 passes built and 1
skipped a 31-leaf tree."""

import json
import os

import pytest

from chipbench import run
from chipbench.readers import span_tag_per_job

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "gbdt-higgs.fit-1m-resident"
LAYER = "boosting program (dispatch, device, tree fetch)"
METRICS = {"hist_passes_built.fit": "lower", "hist_passes_skipped.fit": "higher"}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metric_file_names_the_existing_reader_and_the_manifest_repeats_it_at_the_end(metric):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    tag = metric[: -len(".fit")]
    assert spec == {
        "layer": LAYER, "unit": "count", "better": METRICS[metric], "source": "program_counter",
        "moves": "fit_s", "workloads": [CELL], "reader": "span_tag_per_job",
        "args": {"spans": ["lightgbm.boost"], "tag": tag}}
    assert run.layer_metrics(CELL)[metric] == spec
    assert os.path.exists(os.path.join(ROOT, "chipbench", "readers", "span_tag_per_job.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    others = [w["name"] for w in manifest["workloads"] if w["name"] != CELL]
    assert len(others) >= 4 and all(metric not in run.layer_metrics(other) for other in others)
    names = [m["name"] for m in manifest["per_layer"]]
    assert manifest["per_layer"][names.index(metric)] == {
        "name": metric, "unit": "count", "better": METRICS[metric], "source": "program_counter",
        "layer": LAYER, "moves": "fit_s", "workloads": [CELL]}
    assert names.index(metric) > names.index("program_compile_s.transform")
    assert LAYER == {m["name"]: m for m in manifest["per_layer"]}["boost_ms.fit"]["layer"]


def _span(name, **tags):
    return {"name": name, "duration": 1.0, "tags": tags}


def test_each_reads_its_own_tag_a_job_and_nothing_from_a_program_without_it():
    built, skipped = (run.layer_metrics(CELL)[metric]["args"] for metric in sorted(METRICS))
    assert not built["tag"].startswith(skipped["tag"]) and not skipped["tag"].startswith(built["tag"])
    parent = [_span("lightgbm.boost", iterations=100, segments=1)] * 4
    assert span_tag_per_job.read({"spans": parent, "jobs": 4}, **built) is None
    assert span_tag_per_job.read({"spans": parent, "jobs": 4}, **skipped) is None
    change = [_span("lightgbm.boost", iterations=100, segments=1,
                    hist_passes_built=600, hist_passes_skipped=100)] * 4
    assert span_tag_per_job.read({"spans": change, "jobs": 4}, **built) == 600.0
    assert span_tag_per_job.read({"spans": change, "jobs": 4}, **skipped) == 100.0
    other = [_span("lightgbm.fit", hist_passes_built=7), _span("lightgbm.pack", hist_passes_skipped=7)]
    assert span_tag_per_job.read({"spans": other, "jobs": 1}, **built) is None
    assert span_tag_per_job.read({"spans": other, "jobs": 1}, **skipped) is None


def test_a_dry_fit_builds_six_passes_a_tree_and_skips_the_seventh():
    line = run.measure(run.load_cell(CELL), 2**31 + 36, 0.0, True, True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    # the dry cell: 3 trees of 31 leaves at leaf_batch 8: the root's pass and rounds of 1, 2, 4, 8, 8 | 7
    assert line["metrics"]["dry_hist_passes_built.fit"] == {"value": 18.0, "unit": "count"}
    assert line["metrics"]["dry_hist_passes_skipped.fit"] == {"value": 3.0, "unit": "count"}
