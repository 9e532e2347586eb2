"""The benchmark's data files: every one loads, every name resolves, and
BENCHMARK.json says what the files say."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _stems(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, kind)) if f.endswith(".json"))


def _load(kind, stem):
    with open(os.path.join(BENCH, kind, stem + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS, CONFIGS, METRICS = _stems("workloads"), _stems("configs"), _stems("layer_metrics")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_resolves(cell):
    spec = _load("workloads", cell)
    assert NAME.match(cell) and NAME.match(spec["traffic"])
    assert cell == f"{spec['config']}.{spec['traffic']}"
    assert spec["config"] in CONFIGS
    assert spec["chips"] in (1, 4)
    assert 1 <= len(spec["why"]) <= 200 and "\n" not in spec["why"]
    driver = importlib.import_module(f"chipbench.drivers.{spec['driver']}")
    for fn in ("setup", "job", "fault", "end_to_end", "work", "compare"):
        assert callable(getattr(driver, fn)), fn
    assert spec["params"]["limits"], "a cell states the limits of its comparison"
    assert set(spec["dry"]) <= set(spec["params"]) | {"estimator"}


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_states_source_and_cuts(config):
    spec = _load("configs", config)
    assert NAME.match(config)
    for key in ("source", "deployment", "precision", "params", "reduced", "assumed"):
        assert key in spec, key
    assert all(NAME.match(k) for k in spec["reduced"]) and len(spec["reduced"]) <= 16
    banned = re.compile(r"(_dim|_rank)$|width|hidden|intermediate|head")
    assert not [k for k in spec["reduced"] if banned.search(k)], "a width was cut"


@pytest.mark.parametrize("metric", METRICS)
def test_layer_metric_file_resolves(metric, manifest):
    spec = _load("layer_metrics", metric)
    assert NAME.match(metric) and UNIT.match(spec["unit"])
    assert spec["better"] in ("lower", "higher") and spec["source"] in SOURCES
    assert 1 <= len(spec["layer"]) <= 200
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert callable(reader.read)
    assert spec["workloads"] and set(spec["workloads"]) <= set(CELLS)
    moved = {m["name"]: m for m in manifest["end_to_end"]}[spec["moves"]]
    reporting = set(moved.get("workloads", CELLS))
    assert set(spec["workloads"]) <= reporting, "a cell does not report what this moves"


@pytest.mark.parametrize("metric", METRICS)
def test_manifest_repeats_the_metric_file(metric, manifest):
    spec = _load("layer_metrics", metric)
    entry = {m["name"]: m for m in manifest["per_layer"]}[metric]
    want = {k: spec[k] for k in ("unit", "better", "source", "layer", "moves", "workloads")}
    assert entry == {"name": metric, **want}


@pytest.mark.parametrize("cell", CELLS)
def test_manifest_repeats_the_cell_file(cell, manifest):
    spec = _load("workloads", cell)
    entry = {w["name"]: w for w in manifest["workloads"]}[cell]
    assert entry == {"name": cell, **{k: spec[k] for k in ("config", "traffic", "chips", "why")}}


@pytest.mark.parametrize("config", CONFIGS)
def test_manifest_repeats_the_config_file(config, manifest):
    spec = _load("configs", config)
    entry = {c["name"]: c for c in manifest["configs"]}[config]
    assert entry["file"] == f"chipbench/configs/{config}.json"
    assert entry["reduced"] == spec["reduced"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert config in {w["config"] for w in manifest["workloads"]}


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert manifest["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= manifest["run_seconds"] <= 51
    runs = 2 + 14 * 24  # a full check with every cell the contract allows
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert "setup_s" in names
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_one_more_metric_and_a_layer_metric(cell, manifest):
    ends = [m["name"] for m in manifest["end_to_end"] if cell in m.get("workloads", [cell])]
    assert "setup_s" in ends and len(ends) >= 2
    assert [m for m in manifest["per_layer"] if cell in m["workloads"]]
    mfu = [m for m in manifest["per_layer"] if cell in m["workloads"] and "mfu" in m["name"].split("_")]
    assert mfu and all(m["unit"] == "%" for m in mfu), "the whole step's share of the peak"


def test_files_under_paths_are_named_from_allowed_characters():
    bad = []
    for path in ("chipbench", "tests/chipbench"):
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            bad += [f for f in files if not re.match(r"^[A-Za-z0-9_.\-]+$", f)]
    assert not bad


def test_peaks_table_is_the_published_v5e_and_nothing_else():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"]) == (197e12, 393e12)
    assert (v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (819e9, 16e9)
    assert v5e["source"] == "Google Cloud documentation, TPU v5e"
    assert "cpu" not in peaks
