"""The sparse-attention cell: its files, its work counted from shapes on the
sparse work, a dry run that ends ``correct`` and reports the cell's metrics,
the control and each planted fault shown to end ``correct: false`` through
``run.measure``, and a job off the cell's path counted as failed."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.drivers import lm_score_dsa
from chipbench.reference import keye_vl2 as ref

CELL = "keye-vl2-30b-a3b.score-32k"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 4300000101  # a large seed: more than 32 signed bits hold
COPIED = {name + ".dsa" for name in (
    "score_mfu_pct", "device_idle_pct", "hbm_peak_gib", "programs_built", "stack_ms", "dispatch_ms", "fetch_ms",
    "span_coverage_pct", "setup_compile_s", "setup_trace_s", "setup_cache_misses", "first_call_s",
    "program_compile_s", "expert_load_peak_pct", "experts_empty_pct")}
NEW = {"indexer_roofline_pct.dsa", "sparse_attn_roofline_pct.dsa", "keys_kept_pct.dsa"}
CHECKS = {"logit_gap_max", "hidden_gap_max", "kept_gap_max", "kept_gap_first", "load_gap_max", "head_gap_max"}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


# -- the configuration's file, and the cell's -----------------------------------

def test_the_file_repeats_every_published_key_of_the_catalog_row(cell):
    spec = cell["config_file"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {c["name"]: c for c in manifest["configs"]}["keye-vl2-30b-a3b"]
    assert spec["source"].startswith(entry["source"]) and "KeyeVL2" in spec["source"] and len(spec["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["file"] == "chipbench/configs/keye-vl2-30b-a3b.json"
    published = {k: v for k, v in spec["params"].items() if k != "layers"}
    assert published == {k: spec[k] for k in published}, "params and the top level disagree"
    assert spec["reduced"] == entry["reduced"] == ["layers"] and spec["layers"] == spec["params"]["layers"] == 6
    catalog = os.environ.get("MODEL_CATALOG", "")  # the published catalog of architectures, as JSON lines
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert published == row["config"] and entry["source"] == row["source_url"]
    # every width as published
    assert (spec["hidden_size"], spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]) == (
        2048, 32, 4, 128)
    assert (spec["num_experts"], spec["num_experts_per_tok"], spec["moe_intermediate_size"]) == (128, 8, 768)
    assert spec["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                                 "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert spec["rope_scaling"]["mrope_section"] == [16, 24, 24] and spec["rope_theta"] == 10000000
    assert (spec["vocab_size"], spec["tie_word_embeddings"], spec["num_hidden_layers"]) == (151936, False, 48)
    assert spec["norm_topk_prob"] and spec["mlp_only_layers"] == [] and not spec["use_sliding_window"]
    assert set(spec["dry"]) <= set(spec["params"]) | {"interpret"}
    for key in ("layers", "q_norm_k_norm", "mrope", "indexer_query", "indexer_key_norm", "indexer_rotary",
                "indexer_weights", "hadamard", "chunks", "selection", "vision_tower"):
        assert key in spec["assumed"], key
    assert "stage 0 of eight" in spec["deployment"] and "8.15 GiB" in spec["deployment"]
    assert "ep_size 1" in spec["deployment"]
    assert "the selection float32" in spec["precision"]


def test_the_cell_is_as_declared_and_its_metric_files_are_found_by_name(cell):
    assert (cell["chips"], cell["driver"], cell["config"]) == (1, "lm_score_dsa", "keye-vl2-30b-a3b")
    params = cell["params"]
    assert (params["rows"], params["tokens"], params["batchSize"]) == (4, 32768, 1)
    assert params["zipf_exponent"] == 1.0 and params["compare_rows"] in (1, 2)
    assert cell["profiler"] == {"host_tracer_level": 1} and len(cell["why"]) <= 200
    assert set(params["limits"]) == CHECKS == set(cell["dry"]["limits"])
    listed = run.layer_metrics(CELL)
    assert set(listed) >= COPIED | NEW  # a later PR may list the cell in more
    assert all(listed[name]["workloads"] == [CELL] for name in COPIED | NEW)
    assert not {n for n in listed if "ragged" in json.dumps(listed[n])}  # that op is gone
    on_setup = {n + ".dsa" for n in ("setup_compile_s", "setup_trace_s", "setup_cache_misses", "first_call_s")}
    assert {n for n in COPIED | NEW if listed[n]["moves"] == "setup_s"} == on_setup
    assert listed["indexer_roofline_pct.dsa"]["args"] == {
        "work": "indexer_flops", "pattern": "^dsa_index", "rows": 1, "peak": "bf16_flops_per_s"}
    assert listed["sparse_attn_roofline_pct.dsa"]["args"] == {
        "work": "sparse_attn_flops", "pattern": "^dsa_attn", "rows": 1, "peak": "bf16_flops_per_s"}
    assert listed["keys_kept_pct.dsa"]["args"] == {
        "span": "lm.select_stats", "numerator": "keys_kept", "denominator": "keys_visible", "scale": 100.0}
    assert listed["span_coverage_pct.dsa"]["args"] == {"root": "lm.featurize", "children": [
        "dnn.place_params", "dnn.stack", "dnn.dispatch", "dnn.fetch", "dnn.assemble", "lm.route_stats",
        "lm.select_stats"]}
    # each copied one reads what the dense hybrid's or the latent cell's reads, in this cell
    folder = os.path.join(ROOT, "chipbench", "layer_metrics")
    for name in COPIED - {"span_coverage_pct.dsa"}:
        stem = name[: -len(".dsa")]
        source = stem + (".mla.json" if "expert" in stem else ".par.json")
        with open(os.path.join(folder, source)) as f:
            spec = json.load(f)
        keys = ("reader", "args", "layer", "unit", "better", "source", "moves")
        assert {k: listed[name].get(k) for k in keys} == {k: spec.get(k) for k in keys}, name
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for other in (w["name"] for w in manifest["workloads"] if w["name"] != CELL):
        assert not run.layer_metrics(other).keys() & (COPIED | NEW)
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in COPIED | NEW:
        assert declared[name]["workloads"] == [CELL]
        assert {k: declared[name][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source", "layer", "moves")}
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in end["featurize_img_per_s"]["workloads"] and "workloads" not in end["setup_s"]
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1 and len(manifest["workloads"]) >= 8
    assert {w["name"]: w["chips"] for w in manifest["workloads"]}[CELL] == 1


# -- work and bytes, from shapes ----------------------------------------------

def test_work_against_the_hand_count(cell):
    config, traffic = run.sizes(cell, False)
    work = lm_score_dsa.work(config, traffic)
    layer_rows, tokens = 4 * 6, 4 * 32768 * 6
    seen, kept = 32768 * 32769 // 2, 2048 * 2049 // 2 + (32768 - 2048) * 2048
    assert (seen, kept) == (536_887_296, 65_012_736) and round(100 * kept / seen, 3) == 12.109
    # the indexer scores every key a query sees, 2 x 1,024 a pair; attention reads only the kept keys
    assert work["indexer_flops"] == layer_rows * 2 * 1024 * seen
    assert work["sparse_attn_flops"] == layer_rows * 4 * 32 * 128 * kept
    assert work["expert_flops"] == tokens * 2 * 3 * 2048 * 768 * 8
    assert work["projection_flops"] == tokens * 2 * 2048 * (128 * (64 + 8) + 1024 + 64 + 16)
    assert work["router_flops"] == tokens * 2 * 2048 * 128
    head = 4 * 2 * 2048 * 151936
    parts = ("indexer_flops", "sparse_attn_flops", "expert_flops", "projection_flops", "router_flops")
    assert work["flops"] == sum(work[p] for p in parts) + head
    # TFLOP a job of 4 x 32,768 tokens through 6 layers: a layer-row's 1.100 + 1.065 + 2.474 + 1.385 + 0.017
    got = [round(work[p] / 1e12, 2) for p in parts] + [round(work["flops"] / 1e12, 2)]
    assert got == [26.39, 25.56, 59.37, 33.24, 0.41, 144.98]
    assert [round(1000 * work[p] / work["flops"]) for p in parts] == [182, 176, 410, 229, 3]
    # the same attention done densely would be 8.3 x the sparse work
    assert round(4 * 32 * 128 * seen / (4 * 32 * 128 * kept), 2) == 8.26
    assert work["indexer_bytes"] == tokens * (2 * 1024 + 2 * 64 + 4 * 16) + layer_rows * seen // 8
    assert work["sparse_attn_bytes"] == tokens * 2 * (2 * 32 * 128 + 2 * 4 * 128) + layer_rows * seen // 8
    assert work["bytes"] == 0
    half = lm_score_dsa.work(config, {**traffic, "rows": 2})
    assert half["flops"] * 2 == work["flops"]


def test_kept_keys_are_the_sum_of_min_position_and_topk():
    for tokens, topk in ((50, 16), (16, 16), (10, 16), (32768, 2048)):
        assert lm_score_dsa.kept_keys(tokens, topk) == sum(min(i + 1, topk) for i in range(tokens))


def test_weight_bytes_are_the_trees_own(cell):
    import jax

    from mmlspark_tpu.models.keye_vl2 import init_keye_vl2

    for dry in (True, False):
        config, _ = run.sizes(cell, dry)
        tree = jax.eval_shape(lambda k: init_keye_vl2(k, config), jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(tree)
        nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
        assert lm_score_dsa.weight_bytes(config) == nbytes and "head" in tree
    # a layer: attention 18,878,720, indexer 2,261,120, router 262,144, experts 603,979,776
    layer = sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(tree["layers"]))
    assert layer == 625_381_760 == 18_878_720 + 2_261_120 + 262_144 + 603_979_776
    assert sum(int(np.prod(a.shape)) for a in leaves) == 4_374_622_464
    assert nbytes == 2 * 4_374_622_464 and abs(nbytes / 2**30 - 8.15) < 0.005
    assert nbytes > 0.25 * 16 * 2**30  # the memory floor, by the weights alone


# -- a dry run, and correct shown to fail -------------------------------------

@pytest.fixture(scope="module")
def dry_line(cell):
    return run.measure(cell, SEED, 0.0, True, True)


def test_the_dry_run_ends_correct_and_reports_the_cells_metrics(dry_line):
    assert dry_line["correct"] is True and dry_line["failed"] == 0 and dry_line["attempted"] == 1
    assert set(dry_line["checks"]) == CHECKS
    # the CPU reports no memory statistics, and its trace has no chip's operation names
    missing = {"dry_hbm_peak_gib.dsa", "dry_indexer_roofline_pct.dsa", "dry_sparse_attn_roofline_pct.dsa"}
    assert set(dry_line["metrics"]) >= {"dry_" + n for n in COPIED | NEW} - missing
    assert not set(dry_line["metrics"]) & missing
    metrics = {k: v["value"] for k, v in dry_line["metrics"].items()}
    assert 0 < metrics["dry_score_mfu_pct.dsa"] < 100
    assert metrics["dry_span_coverage_pct.dsa"] > 90
    # 50 tokens keeping 16: (136 + 34 x 16) of 1,275 pairs
    assert metrics["dry_keys_kept_pct.dsa"] == pytest.approx(100 * 680 / 1275)
    assert metrics["dry_programs_built.dsa"] == 0 and metrics["dry_program_compile_s.dsa"] == 0.0


def test_an_untraced_dry_run_reports_the_two_end_to_end_metrics(cell):
    line = run.measure(cell, SEED, 0.0, False, True)
    assert line["correct"] and set(line["metrics"]) == {"dry_featurize_img_per_s", "dry_setup_s"}


def _with_job(job):
    """The driver with its timed job replaced once the warm-up has passed."""
    calls = []

    def after_warm_up(state):
        calls.append(1)
        return lm_score_dsa.job(state) if len(calls) == 1 else job(state)

    return types.SimpleNamespace(**{
        k: getattr(lm_score_dsa, k) for k in ("setup", "fault", "end_to_end", "work", "compare")
    }, job=after_warm_up)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_not_correct(cell, fault):
    def job(state):
        honest = lm_score_dsa.job(state)
        side = lm_score_dsa.reference_outputs(state, fault=fault)
        return dict(honest, sample=side, last_kept=side["last_kept"])

    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(job))
    assert line["correct"] is False and line["failed"] == 0
    over = {name for name, c in line["checks"].items() if c["value"] > c["limit"]}
    assert {"hidden_gap_max", "logit_gap_max", "load_gap_max"} <= over
    assert line["checks"]["head_gap_max"]["value"] < 1e-5  # the reference's head follows its own hidden state


def test_the_float8_products_control_is_not_correct(cell):
    """The program's own path with every product's inputs one step below
    the bfloat16 the configuration states."""
    low = lambda state: lm_score_dsa.job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(low))
    assert line["correct"] is False and line["failed"] == 0
    for number in ("hidden_gap_max", "logit_gap_max", "head_gap_max", "load_gap_max"):
        assert line["checks"][number]["value"] > 1.5 * line["checks"][number]["limit"]


def test_control_reads_the_control_and_every_fault(cell):
    state = lm_score_dsa.setup(*run.sizes(cell, True), SEED)
    assert run.passes(lm_score_dsa.checks(state, [lm_score_dsa.job(state)]))
    sides = lm_score_dsa.control(dict(state))
    assert set(sides) == {"control", *ref.FAULTS}
    assert not any(run.passes(checks) for checks in sides.values())


@pytest.mark.parametrize("alter,reason", [
    (lambda out: dict(out, finite=False), "non-finite"),
    (lambda out: dict(out, shapes=dict(out["shapes"], logits=(3, 256))), "outputs of shapes"),
    (lambda out: dict(out, routed=[99]), "a token was dropped"),
    (lambda out: dict(out, kept=[679, 680]), "keys over a row"),
])
def test_a_job_off_the_cells_path_counts_as_failed(cell, alter, reason):
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(lambda s: alter(lm_score_dsa.job(s))))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)
    state = lm_score_dsa.setup(*run.sizes(cell, True), SEED)
    assert reason in lm_score_dsa.fault(state, alter(lm_score_dsa.job(state)))


def test_a_peak_under_the_weights_counts_as_failed(cell, monkeypatch):
    import jax

    state = lm_score_dsa.setup(*run.sizes(cell, True), SEED)
    out = lm_score_dsa.job(state)
    for peak, failed in ((state["weight_bytes"] - 1, True), (state["weight_bytes"], False)):
        device = types.SimpleNamespace(memory_stats=lambda peak=peak: {"peak_bytes_in_use": peak})
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
        assert bool(lm_score_dsa.fault(state, out)) is failed


def test_the_parent_cannot_run_the_cell_and_says_so_at_once():
    """Without this PR's program (no ``mmlspark_tpu.models.keye_vl2``) the
    driver's set-up raises on import: a clean, early failure, not a hang; and
    the driver imports only what is generic of the other drivers."""
    import inspect

    source = inspect.getsource(lm_score_dsa.setup)
    assert "from mmlspark_tpu.models.keye_vl2 import init_keye_vl2" in source
    with open(os.path.join(ROOT, "chipbench", "drivers", "lm_score_dsa.py")) as f:
        top = f.read().split("def ", 1)[0]
    assert "mmlspark_tpu" not in top.split('"""', 2)[2]  # nothing of the program at module level
    assert "lm_score_mla" not in top and "lm_score_par" not in top and "lm_score_conv" not in top
