"""``stage_slabs.featurize`` and ``staging_reuse_pct.featurize`` (layer "resize
stage"): data only, two files over readers the benchmark has
(``span_tag_per_job``, ``span_tag_ratio``). They are found for the featurize
cell and no other, ``BENCHMARK.json`` repeats them after everything PR 37's
manifest held, each reads ``image.transform``'s own tag off a recorded span
list (the share is of the slabs that were stacked into a buffer an earlier
slab of the call had used: all but two of a group's), neither tag's name
starts with ``bytes`` (``host_copy_gib.featurize`` sums every tag that does),
nothing where the program has no such tags (the parent), and a dry run's one
small group is one slab, none of it in a buffer used before."""

import json
import os

import pytest

from chipbench import run
from chipbench.readers import span_tag_per_job, span_tag_ratio

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "resnet50-224.featurize-bulk"
LAYER = "resize stage"
METRICS = {
    "stage_slabs.featurize": ("count", "span_tag_per_job", {"spans": ["image.transform"], "tag": "slabs"}),
    "staging_reuse_pct.featurize": ("%", "span_tag_ratio", {
        "span": "image.transform", "numerator": "staging_reused", "denominator": "slabs", "scale": 100.0}),
}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metric_file_names_an_existing_reader_and_the_manifest_repeats_it_at_the_end(metric):
    unit, reader, args = METRICS[metric]
    with open(os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec == {
        "layer": LAYER, "unit": unit, "better": "higher", "source": "program_counter",
        "moves": "featurize_img_per_s", "workloads": [CELL], "reader": reader, "args": args}
    assert run.layer_metrics(CELL)[metric] == spec
    assert os.path.exists(os.path.join(ROOT, "chipbench", "readers", reader + ".py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    others = [w["name"] for w in manifest["workloads"] if w["name"] != CELL]
    assert len(others) >= 5 and all(metric not in run.layer_metrics(other) for other in others)
    names = [m["name"] for m in manifest["per_layer"]]
    assert manifest["per_layer"][names.index(metric)] == {
        "name": metric, "unit": unit, "better": "higher", "source": "program_counter",
        "layer": LAYER, "moves": "featurize_img_per_s", "workloads": [CELL]}
    assert names.index(metric) > names.index("program_compile_s.conv")
    assert LAYER == {m["name"]: m for m in manifest["per_layer"]}["resize_stage_ms.featurize"]["layer"]


def _span(name, **tags):
    return {"name": name, "duration": 1.0, "tags": tags}


def _job(slabs, reused, rows=6144):
    """A job's spans as the program records them: a stack and an upload a
    slab under one ``image.transform``."""
    slab = [_span("image.stack", bytes=77), _span("image.apply_fetch", bytes_up=77, bytes_down=0)]
    return slab * slabs + [_span("image.transform", rows=rows, groups=1, programs_built=0,
                                 slabs=slabs, staging_reused=reused)]


def test_each_reads_the_stages_own_tag_off_a_recorded_span_list():
    slabs, reuse = (METRICS[m][2] for m in sorted(METRICS))
    window = {"spans": _job(12, 10) * 3, "jobs": 3}
    assert span_tag_per_job.read(window, **slabs) == 12.0
    assert span_tag_ratio.read(window, **reuse) == pytest.approx(100.0 * 10 / 12)
    one_slab = {"spans": _job(1, 0), "jobs": 1}
    assert span_tag_per_job.read(one_slab, **slabs) == 1.0
    assert span_tag_ratio.read(one_slab, **reuse) == 0.0
    # a table of two shape groups: 12 slabs and 3, a staging pair each
    two_groups = {"spans": _job(15, 10 + 1), "jobs": 1}
    assert span_tag_ratio.read(two_groups, **reuse) == pytest.approx(100.0 * 11 / 15)
    # the copies' sum reads tags by prefix: neither name may begin as a byte tag does
    assert not slabs["tag"].startswith("bytes") and not reuse["numerator"].startswith("bytes")
    copies = run.layer_metrics(CELL)["host_copy_gib.featurize"]["args"]
    assert span_tag_per_job.read(window, **copies) == 12 * (77 + 77) / copies["scale"]


def test_nothing_is_read_from_a_program_without_the_tags():
    slabs, reuse = (METRICS[m][2] for m in sorted(METRICS))
    parent = {"jobs": 2, "spans": [
        _span("image.stack", bytes=924), _span("image.apply_fetch", bytes_up=924, bytes_down=0),
        _span("image.transform", rows=6144, groups=1, programs_built=0)] * 2}
    assert span_tag_per_job.read(parent, **slabs) is None
    assert span_tag_ratio.read(parent, **reuse) is None
    elsewhere = {"jobs": 1, "spans": [_span("dnn.transform", slabs=3, staging_reused=1)]}
    assert span_tag_per_job.read(elsewhere, **slabs) is None
    assert span_tag_ratio.read(elsewhere, **reuse) is None


def test_a_dry_job_is_one_slab_stacked_into_a_fresh_buffer():
    line = run.measure(run.load_cell(CELL), 2**31 + 38, 0.0, True, True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["metrics"]["dry_stage_slabs.featurize"] == {"value": 1.0, "unit": "count"}
    assert line["metrics"]["dry_staging_reuse_pct.featurize"] == {"value": 0.0, "unit": "%"}
    assert line["metrics"]["dry_device_batches.featurize"]["value"] == 2.0
