"""Every assertion of ``test_device_batches.py``'s first test but one: that
test asserts besides that its metric is ``BENCHMARK.json``'s LAST ``per_layer``
entry, which held only until the next PR appended one, and ``tests/conftest.py``
marks it ``xfail`` for that (PERF.md 7, row 15). What it checked of the
metric, its file and its place in the manifest is checked here by name; this
file goes when a ``benchmark`` PR repairs that one."""

import json
import os

from chipbench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRIC, CELL = "device_batches.featurize", "resnet50-224.featurize-bulk"


def test_the_metric_file_is_found_for_its_cell_alone_and_the_manifest_repeats_it():
    spec = run.layer_metrics(CELL)[METRIC]
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"], spec["moves"]) == (
        "batch stacking", "count", "higher", "program_counter", "featurize_img_per_s")
    assert spec["reader"] == "span_tag_per_job"
    assert spec["args"] == {"spans": ["dnn.transform"], "tag": "device_batches"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    others = [w["name"] for w in manifest["workloads"] if w["name"] != CELL]
    assert len(others) >= 3 and all(METRIC not in run.layer_metrics(other) for other in others)
    assert {m["name"]: m for m in manifest["per_layer"]}[METRIC] == {
        "name": METRIC, "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "batch stacking", "moves": "featurize_img_per_s", "workloads": [CELL]}
    assert "device_batches" not in "".join(  # it is a count, not a copy: host_copy_gib sums bytes*
        run.layer_metrics(CELL)["host_copy_gib.featurize"]["args"]["tag"])
