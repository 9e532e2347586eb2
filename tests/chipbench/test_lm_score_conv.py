"""The short-convolution cell: its files, its work and bytes counted from
shapes, a dry run that ends ``correct`` and reports the cell's metrics, the
control and each planted fault shown to end ``correct: false`` through
``run.measure``, and a job off the cell's path counted as failed."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.drivers import lm_score_conv
from chipbench.reference import lfm2_moe as ref

CELL = "lfm2-8b-a1b.score-8k"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 9  # a toy seed; the last-position numbers take the smaller gap of six rows, so no tie decides
METRICS = {name + ".conv" for name in (
    "score_mfu_pct", "device_idle_pct", "hbm_peak_gib", "window_compile_s", "programs_built", "stack_ms",
    "dispatch_ms", "fetch_ms", "span_coverage_pct", "expert_load_peak_pct", "experts_empty_pct",
    "attn_roofline_pct", "experts_roofline_pct", "setup_compile_s", "setup_trace_s", "setup_cache_misses",
    "first_call_s", "program_compile_s")}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


# -- the configuration's file, and the cell's -----------------------------------

def test_the_file_repeats_every_published_key_of_the_catalog_row(cell):
    spec = cell["config_file"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {c["name"]: c for c in manifest["configs"]}["lfm2-8b-a1b"]
    assert spec["source"].startswith(entry["source"]) and "lfm2_moe" in spec["source"] and len(spec["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200
    published = {k: v for k, v in spec["params"].items() if k != "layers"}
    assert published == {k: spec[k] for k in published}, "params and the top level disagree"
    assert spec["reduced"] == entry["reduced"] == ["layers"] and spec["layers"] == spec["params"]["layers"] == 16
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
        assert published == row["config"] and entry["source"] == row["source_url"]
    # every width as published
    assert (spec["hidden_size"], spec["num_attention_heads"], spec["num_key_value_heads"]) == (2048, 32, 8)
    assert "head_dim" not in spec and ref.head_dim(spec["params"]) == 64
    assert (spec["intermediate_size"], spec["moe_intermediate_size"]) == (7168, 1792)
    assert (spec["num_experts"], spec["num_experts_per_tok"], spec["num_dense_layers"]) == (32, 4, 2)
    assert (spec["conv_L_cache"], spec["conv_bias"], spec["vocab_size"]) == (3, False, 65536)
    assert (spec["norm_topk_prob"], spec["use_expert_bias"], spec["routed_scaling_factor"]) == (True, True, 1)
    assert (spec["rope_theta"], spec["norm_eps"]) == (1000000, 1e-5)
    assert spec["num_hidden_layers"] == 24 == len(spec["layer_types"])
    assert spec["layer_types"].count("conv") == 18 and spec["layer_types"].count("full_attention") == 6
    held = spec["layer_types"][:16]
    assert held.count("conv") == 12 and held.count("full_attention") == 4  # 3 : 1, as published
    assert set(spec["dry"]) <= set(spec["params"]) | {"interpret"}
    for key in ("layers", "tied_head", "head_dim", "conv_thirds", "conv", "qk_norm_before_rotary", "routing_bias",
                "denominator", "not_built", "weights", "tokens", "interpret"):
        assert key in spec["assumed"], key
    assert "stage 0 of two" in spec["deployment"] and "10.06 GiB" in spec["deployment"]
    assert "convolution's multiply-adds float32" in spec["precision"]


def test_the_cell_is_the_issues_and_its_metric_files_are_found_by_name(cell):
    assert (cell["chips"], cell["driver"], cell["config"]) == (1, "lm_score_conv", "lfm2-8b-a1b")
    params = cell["params"]
    assert (params["rows"], params["tokens"], params["batchSize"]) == (24, 8192, 4)
    assert (params["zipf_exponent"], params["compare_rows"]) == (1.0, 2)
    assert cell["profiler"] == {"host_tracer_level": 1} and len(cell["why"]) <= 200
    assert set(params["limits"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max", "head_gap_max",
                                     "load_gap_early_max"}
    assert params["early_layers"] == 2 and params["limits"]["load_gap_early_max"] < params["limits"]["load_gap_max"]
    listed = run.layer_metrics(CELL)
    assert set(listed) >= METRICS  # a later PR may list the cell in more
    assert all(listed[name]["workloads"] == [CELL] for name in METRICS)
    moves = {name: listed[name]["moves"] for name in METRICS}
    on_setup = {n + ".conv" for n in ("setup_compile_s", "setup_trace_s", "setup_cache_misses", "first_call_s")}
    assert {n for n, m in moves.items() if m == "setup_s"} == on_setup
    assert all(m == "featurize_img_per_s" for n, m in moves.items() if n not in on_setup)
    assert listed["attn_roofline_pct.conv"]["args"] == {
        "work": "attn_flops", "pattern": "^attn_full", "rows": 1, "peak": "bf16_flops_per_s"}
    assert listed["experts_roofline_pct.conv"]["args"] == {
        "work": "expert_flops", "pattern": "^ragged-dot", "rows": 3, "peak": "bf16_flops_per_s"}
    # each copies its original's reader and args: the same number, read in this cell
    folder = os.path.join(ROOT, "chipbench", "layer_metrics")
    for name in METRICS - {"attn_roofline_pct.conv", "experts_roofline_pct.conv"}:
        stem = name[: -len(".conv")]
        original = next(o for o in (stem + ".ssm", stem + ".transform", stem) if os.path.exists(
            os.path.join(folder, o + ".json")))
        with open(os.path.join(folder, original + ".json")) as f:
            spec = json.load(f)
        assert {k: listed[name].get(k) for k in ("reader", "args", "layer", "unit", "better", "source", "moves")} == {
            k: spec.get(k) for k in ("reader", "args", "layer", "unit", "better", "source", "moves")}, name
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for other in (w["name"] for w in manifest["workloads"] if w["name"] != CELL):
        assert not run.layer_metrics(other).keys() & METRICS
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in end["featurize_img_per_s"]["workloads"] and "workloads" not in end["setup_s"]
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1 and len(manifest["workloads"]) >= 6
    assert all(w["chips"] == 1 for w in manifest["workloads"])


# -- work and bytes, from shapes ----------------------------------------------

def test_work_against_the_hand_count(cell):
    config, traffic = run.sizes(cell, False)
    work = lm_score_conv.work(config, traffic)
    tokens = 24 * 8192
    # ISSUE 37, multiply-adds x 2 a token: a conv operator 33.57 MFLOP (in 25.17, the three taps 0.01, out 8.39), an
    # attention operator 20.97 of projections and 4 x 32 x 64 a key seen, a dense feed-forward 88.08, an expert
    # block 88.21 (four routed experts of three products 88.08, the router 0.13)
    conv_operator = 2 * 2048 * 6144 + 2 * 3 * 2048 + 2 * 2048 * 2048
    assert conv_operator == 33_566_720
    assert work["conv_operator_flops"] == tokens * 12 * conv_operator
    assert work["expert_flops"] == tokens * 14 * 4 * 2 * 3 * 2048 * 1792
    seen = 8192 * 8193 // 2
    assert work["attn_flops"] == 24 * 4 * 4 * 32 * 64 * seen
    projections = 2 * 2048 * (2048 + 512 + 512) + 2 * 2048 * 2048
    dense = 2 * 3 * 2048 * 7168
    router = 2 * 2048 * 32
    a_token = 12 * conv_operator + 4 * projections + 2 * dense + 14 * (4 * 2 * 3 * 2048 * 1792 + router)
    assert work["flops"] == work["attn_flops"] + tokens * a_token + 24 * 2 * 2048 * 65536
    # 399.5 TFLOP a job: routed experts 60.7%, conv operators 19.8%, dense 8.7%, attention scores 6.6%, projections 4.1%
    assert abs(work["flops"] / 1e12 - 399.5) < 0.05 and abs(work["expert_flops"] / 1e12 - 242.4) < 0.05
    assert abs(work["conv_operator_flops"] / 1e12 - 79.2) < 0.05 and abs(work["attn_flops"] / 1e12 - 26.4) < 0.05
    shares = [round(1000 * part / work["flops"]) for part in (
        work["expert_flops"], work["conv_operator_flops"], tokens * 2 * dense, work["attn_flops"],
        tokens * 4 * projections)]
    assert shares == [607, 198, 87, 66, 41]
    # what short_conv itself must do and touch: three taps and two gates a channel; three thirds in, one out, bfloat16
    assert work["conv_flops"] == tokens * 12 * (2 * 3 + 2) * 2048
    assert work["conv_bytes"] == tokens * 12 * 4 * 2048 * 2
    assert work["conv_bytes"] / 819e9 > work["conv_flops"] / 197e12  # the bytes bind
    assert work["bytes"] == 0
    half = lm_score_conv.work(config, {**traffic, "rows": 12})
    assert half["flops"] * 2 == work["flops"]


def test_weight_bytes_are_the_trees_own(cell):
    import jax

    from mmlspark_tpu.models.lfm2_moe import init_lfm2_moe

    for dry in (True, False):
        config, _ = run.sizes(cell, dry)
        tree = jax.eval_shape(lambda k: init_lfm2_moe(k, config), jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(tree)
        nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
        assert lm_score_conv.weight_bytes(config) == nbytes and "head" not in tree
    # 5,399,129,024 parameters (ISSUE 37), all bfloat16 but 14 routing biases of 32: 896 B more
    assert sum(int(np.prod(a.shape)) for a in leaves) == 5_399_129_024
    assert nbytes == 2 * 5_399_129_024 + 2 * 14 * 32 and abs(nbytes / 2**30 - 10.06) < 0.005
    assert nbytes > 0.25 * 16 * 2**30  # the memory floor, by the weights alone


# -- a dry run, and correct shown to fail -------------------------------------

@pytest.fixture(scope="module")
def dry_line(cell):
    return run.measure(cell, SEED, 0.0, True, True)


def test_the_dry_run_ends_correct_and_reports_the_cells_metrics(dry_line, cell):
    assert dry_line["correct"] is True and dry_line["failed"] == 0 and dry_line["attempted"] == 1
    assert set(dry_line["checks"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max", "head_gap_max",
                                       "load_gap_early_max"}
    # the CPU reports no memory statistics, and its trace has no chip's operation names
    missing = {"dry_" + n for n in METRICS if "hbm_peak" in n or "roofline" in n}
    assert len(missing) == 3 and set(dry_line["metrics"]) >= {"dry_" + n for n in METRICS} - missing
    assert not set(dry_line["metrics"]) & missing
    assert 0 < dry_line["metrics"]["dry_score_mfu_pct.conv"]["value"] < 100
    assert dry_line["metrics"]["dry_expert_load_peak_pct.conv"]["value"] >= 100
    assert 0 <= dry_line["metrics"]["dry_experts_empty_pct.conv"]["value"] < 100
    assert dry_line["metrics"]["dry_span_coverage_pct.conv"]["value"] > 90
    assert dry_line["metrics"]["dry_programs_built.conv"]["value"] == 0
    assert dry_line["metrics"]["dry_program_compile_s.conv"]["value"] == 0.0
    assert dry_line["metrics"]["dry_first_call_s.conv"]["value"] > 0


def test_an_untraced_dry_run_reports_the_two_end_to_end_metrics(cell):
    line = run.measure(cell, SEED, 0.0, False, True)
    assert line["correct"] and set(line["metrics"]) == {"dry_featurize_img_per_s", "dry_setup_s"}


def _with_job(job):
    """The driver with its timed job replaced once the warm-up has passed."""
    calls = []

    def after_warm_up(state):
        calls.append(1)
        return lm_score_conv.job(state) if len(calls) == 1 else job(state)

    return types.SimpleNamespace(**{
        k: getattr(lm_score_conv, k) for k in ("setup", "fault", "end_to_end", "work", "compare")
    }, job=after_warm_up)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_not_correct(cell, fault):
    def job(state):
        honest = lm_score_conv.job(state)
        return dict(honest, sample=lm_score_conv.reference_outputs(state, fault=fault))

    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(job))
    assert line["correct"] is False and line["failed"] == 0
    over = {name for name, c in line["checks"].items() if c["value"] > c["limit"]}
    if fault in ("untied_head", "head_inputs_3_mantissa_bits"):  # nothing before the head moves: the following check's
        assert "head_gap_max" in over and "load_gap_max" not in over
        assert line["checks"]["head_gap_max"]["value"] > 3 * cell["dry"]["limits"]["head_gap_max"]
        return
    # the reference's own tied head over its own hidden state follows itself
    assert line["checks"]["head_gap_max"]["value"] < 1e-6
    assert {"hidden_gap_max", "logit_gap_max"} <= over
    first_routing_untouched = fault in ("weights_from_biased_scores", "one_expert_short")  # the second layer's sees it
    assert "load_gap_early_max" in over or first_routing_untouched


def test_the_early_number_reads_the_first_expert_layers_alone(cell):
    """A load that differs in the last expert layer only moves ``load_gap_max``
    and leaves ``load_gap_early_max`` where it was; one in the second layer
    moves both."""
    state = lm_score_conv.setup(*run.sizes(cell, True), SEED)
    out = lm_score_conv.job(state)
    honest = lm_score_conv.checks(state, [out])

    def moved(layer):
        load = out["sample"]["expert_load"].copy()
        load[0, layer, :2] += np.array([10, -10]) * (1 if load[0, layer, 1] >= 10 else -1)
        return lm_score_conv.checks(state, [dict(out, sample=dict(out["sample"], expert_load=load))])

    late, early = moved(-1), moved(1)
    assert late["load_gap_early_max"] == honest["load_gap_early_max"]
    assert late["load_gap_max"]["value"] >= 0.1 > honest["load_gap_max"]["value"]
    assert early["load_gap_early_max"]["value"] >= 0.1 and early["load_gap_max"]["value"] >= 0.1


def test_the_float8_products_control_is_not_correct(cell):
    """The program's own path with every product's inputs one step below
    the bfloat16 the configuration states."""
    low = lambda state: lm_score_conv.job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(low))
    assert line["correct"] is False and line["failed"] == 0
    for number in ("hidden_gap_max", "head_gap_max"):
        assert line["checks"][number]["value"] > 3 * line["checks"][number]["limit"]
    assert line["checks"]["load_gap_max"]["value"] > line["checks"]["load_gap_max"]["limit"]


def test_control_reads_the_control_and_every_fault(cell):
    state = lm_score_conv.setup(*run.sizes(cell, True), SEED)
    assert run.passes(lm_score_conv.checks(state, [lm_score_conv.job(state)]))
    sides = lm_score_conv.control(dict(state))
    assert set(sides) == {"control", *ref.FAULTS}
    assert not any(run.passes(checks) for checks in sides.values())


@pytest.mark.parametrize("alter,reason", [
    (lambda out: dict(out, finite=False), "non-finite"),
    (lambda out: dict(out, routed=[99, 100]), "a token was dropped"),
    (lambda out: dict(out, shapes=dict(out["shapes"], expert_load=(6, 9, 8))), "outputs of shapes"),
])
def test_a_job_off_the_cells_path_counts_as_failed(cell, alter, reason):
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(lambda s: alter(lm_score_conv.job(s))))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)
    state = lm_score_conv.setup(*run.sizes(cell, True), SEED)
    assert reason in lm_score_conv.fault(state, alter(lm_score_conv.job(state)))


def test_a_peak_under_the_weights_counts_as_failed(cell, monkeypatch):
    import jax

    state = lm_score_conv.setup(*run.sizes(cell, True), SEED)
    out = lm_score_conv.job(state)
    for peak, failed in ((state["weight_bytes"] - 1, True), (state["weight_bytes"], False)):
        device = types.SimpleNamespace(memory_stats=lambda peak=peak: {"peak_bytes_in_use": peak})
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
        assert bool(lm_score_conv.fault(state, out)) is failed


def test_the_parent_cannot_run_the_cell_and_says_so_at_once():
    """Without this PR's program (no ``mmlspark_tpu.models.lfm2_moe``) the
    driver's set-up raises on import: a clean, early failure, not a hang."""
    import inspect

    source = inspect.getsource(lm_score_conv.setup)
    assert "from mmlspark_tpu.models.lfm2_moe import init_lfm2_moe" in source
    with open(os.path.join(ROOT, "chipbench", "drivers", "lm_score_conv.py")) as f:
        top = f.read().split("def ", 1)[0]
    assert "mmlspark_tpu" not in top.split('"""', 2)[2]  # nothing of the program at module level
