"""``device_batches.featurize`` (layer "batch stacking"): its file is found for
the featurize cell and no other, ``BENCHMARK.json`` repeats it, it reads how
many of a job's batches ``dnn.transform`` says were sliced on the device (2 at
the cell's dry size of 8 images in batches of 4; 12 on the chip), and nothing
where the program has no such tag."""

import json
import os

from chipbench import run
from chipbench.readers import span_tag_per_job

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRIC, CELL = "device_batches.featurize", "resnet50-224.featurize-bulk"
OTHERS = ("gbdt-higgs.fit-1m-resident", "trinity-mini.score-8k", "joyai-llm-flash.score-16k")


def test_the_metric_file_is_found_for_its_cell_and_the_manifest_repeats_it():
    spec = run.layer_metrics(CELL)[METRIC]
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"], spec["moves"]) == (
        "batch stacking", "count", "higher", "program_counter", "featurize_img_per_s")
    assert spec["reader"] == "span_tag_per_job"
    assert spec["args"] == {"spans": ["dnn.transform"], "tag": "device_batches"}
    assert all(METRIC not in run.layer_metrics(other) for other in OTHERS)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert per_layer[-1] == {
        "name": METRIC, "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "batch stacking", "moves": "featurize_img_per_s", "workloads": [CELL]}
    assert "device_batches" not in "".join(  # it is a count, not a copy: host_copy_gib sums bytes*
        run.layer_metrics(CELL)["host_copy_gib.featurize"]["args"]["tag"])


def _span(name, **tags):
    return {"name": name, "duration": 1.0, "tags": tags}


def test_the_reader_counts_a_jobs_device_batches_and_reads_nothing_without_the_tag():
    args = run.layer_metrics(CELL)[METRIC]["args"]
    parent = [_span("dnn.transform", rows=6144, batches=12, programs_built=0)] * 3
    assert span_tag_per_job.read({"spans": parent, "jobs": 3}, **args) is None
    chip = [_span("dnn.transform", rows=6144, batches=12, programs_built=0, device_batches=12)] * 3
    assert span_tag_per_job.read({"spans": chip, "jobs": 3}, **args) == 12.0
    host = [_span("dnn.transform", rows=32, batches=8, device_batches=0)] * 2
    assert span_tag_per_job.read({"spans": host, "jobs": 2}, **args) == 0.0
    # mixed sizes: a dnn.transform a shape group, summed over the job
    groups = [_span("dnn.transform", batches=2, device_batches=2),
              _span("dnn.transform", batches=1, device_batches=1)]
    assert span_tag_per_job.read({"spans": groups, "jobs": 1}, **args) == 3.0
    other = [_span("image.featurize", device_batches=5), _span("dnn.stack", device_batches=5)]
    assert span_tag_per_job.read({"spans": other, "jobs": 1}, **args) is None


def test_a_dry_job_of_the_cell_slices_both_of_its_batches_on_the_device():
    line = run.measure(run.load_cell(CELL), 2**31 + 32, 0.0, True, True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["metrics"]["dry_" + METRIC] == {"value": 2.0, "unit": "count"}
    # the warm-up job built the slice program with every other
    assert line["metrics"]["dry_programs_built.featurize"]["value"] == 0.0
    assert line["metrics"]["dry_window_compile_s.featurize"]["value"] == 0.0
