"""The hybrid state-space cell: its files, its work and bytes counted from
shapes, a dry run that ends ``correct`` and reports exactly the cell's
metrics, the control and each planted fault shown to end ``correct: false``
through ``run.measure``, and a job off the cell's path counted as failed."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.drivers import lm_score_ssm
from chipbench.reference import nemotron_h as ref

CELL = "nemotron-3-nano.score-16k"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 9  # a toy seed; the last-position numbers take the smaller gap of six rows, so no tie decides


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


# -- the configuration's file, and the cell's -----------------------------------

def test_the_file_repeats_every_published_key_of_the_catalog_row(cell):
    spec = cell["config_file"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {c["name"]: c for c in manifest["configs"]}["nemotron-3-nano"]
    assert spec["source"].startswith(entry["source"]) and "nemotron_h" in spec["source"] and len(spec["source"]) <= 200
    assert all(1 <= len(e["why"]) <= 200 for e in manifest["configs"] + manifest["workloads"])
    published = {k: v for k, v in spec["params"].items() if k != "layers"}
    assert published == {k: spec[k] for k in published}, "params and the top level disagree"
    assert spec["reduced"] == entry["reduced"] == ["layers"] and spec["layers"] == spec["params"]["layers"] == 9
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert published == row["config"] and entry["source"] == row["source_url"]
    # every width as published
    assert (spec["hidden_size"], spec["mamba_num_heads"], spec["mamba_head_dim"]) == (2688, 64, 64)
    assert (spec["n_groups"], spec["ssm_state_size"], spec["conv_kernel"], spec["chunk_size"]) == (8, 128, 4, 128)
    assert (spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]) == (32, 2, 128)
    assert (spec["n_routed_experts"], spec["num_experts_per_tok"], spec["n_shared_experts"]) == (128, 6, 1)
    assert (spec["moe_intermediate_size"], spec["moe_shared_expert_intermediate_size"]) == (1856, 3712)
    assert (spec["mlp_hidden_act"], spec["routed_scaling_factor"], spec["norm_topk_prob"]) == ("relu2", 2.5, True)
    assert spec["vocab_size"] == 131072 and not spec["tie_word_embeddings"] and spec["layer_norm_epsilon"] == 1e-5
    assert spec["num_hidden_layers"] == 52 == len(spec["hybrid_override_pattern"])
    assert spec["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert set(spec["dry"]) <= set(spec["params"]) | {"interpret"}
    for key in ("layers", "no_positions_in_attention", "expand_unread", "dt", "gate_before_norm", "denominator",
                "not_built", "weights", "tokens", "interpret"):
        assert key in spec["assumed"], key
    assert "stage 0 of six" in spec["deployment"] and "11.31 GiB" in spec["deployment"]
    assert "carried state float32" in spec["precision"]


def test_the_cell_is_the_issues(cell):
    assert (cell["chips"], cell["driver"], cell["config"]) == (1, "lm_score_ssm", "nemotron-3-nano")
    params = cell["params"]
    assert (params["rows"], params["tokens"], params["batchSize"]) == (12, 16384, 1)
    assert (params["zipf_exponent"], params["compare_rows"]) == (1.0, 2)
    assert cell["profiler"] == {"host_tracer_level": 1} and len(cell["why"]) <= 200
    assert set(params["limits"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max", "head_gap_max"}
    listed = run.layer_metrics(CELL)
    assert set(listed) == {name + ".ssm" for name in (
        "score_mfu_pct", "device_idle_pct", "hbm_peak_gib", "window_compile_s", "programs_built",
        "stack_ms", "dispatch_ms", "fetch_ms", "span_coverage_pct", "expert_load_peak_pct",
        "experts_empty_pct", "attn_roofline_pct", "experts_roofline_pct")}
    assert all(spec["workloads"] == [CELL] and spec["moves"] == "featurize_img_per_s" for spec in listed.values())
    assert listed["attn_roofline_pct.ssm"]["args"] == dict(
        listed["attn_roofline_pct.ssm"]["args"], pattern="^attn_full", rows=1)
    assert listed["experts_roofline_pct.ssm"]["args"] == dict(
        listed["experts_roofline_pct.ssm"]["args"], pattern="^ragged-dot", rows=2)
    for other in ("trinity-mini.score-8k", "joyai-llm-flash.score-16k"):
        assert not run.layer_metrics(other).keys() & listed.keys()


# -- work and bytes, from shapes ----------------------------------------------

def test_work_against_the_hand_count(cell):
    config, traffic = run.sizes(cell, False)
    work = lm_score_ssm.work(config, traffic)
    tokens = 12 * 16384
    # ISSUE 33, multiply-adds x 2 a token: a mixer 80.87 MFLOP (two projections 77.41, the convolution 0.05,
    # the scan's four products 3.41), an expert block 160.33 (six routed experts of two products 119.73, the
    # shared expert 39.91, the router 0.69), the attention block 46.79 of projections and 4 x 32 x 128 a key seen
    scan = 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 2 * 2 * 128 * 64)
    assert lm_score_ssm.ssd_work(config) == (scan, 20_736) and scan == 3_407_872
    mixer = 2 * 2688 * 10304 + 2 * 4 * 6144 + 2 * 4096 * 2688 + scan
    assert mixer == 80_871_424
    assert work["ssd_flops"] == tokens * 4 * scan and work["ssd_bytes"] == tokens * 4 * 20_736
    assert work["expert_flops"] == tokens * 4 * 6 * 2 * 2 * 2688 * 1856
    seen = 16384 * 16385 // 2
    assert work["attn_flops"] == 12 * 4 * 32 * 128 * seen
    expert_block = 6 * 2 * 2 * 2688 * 1856 + 2 * 2 * 2688 * 3712 + 2 * 2688 * 128
    projections = 2 * 2688 * (4096 + 256 + 256) + 2 * 4096 * 2688
    a_token = 4 * mixer + 4 * expert_block + projections
    assert work["flops"] == work["attn_flops"] + tokens * a_token + 12 * 2 * 2688 * 131072
    # 1,145.8 MFLOP a token with attention at its mean of 8,192.5 keys: mixers 28%, expert blocks 56%, attention 16%
    whole = a_token + 4 * 32 * 128 * seen / 16384
    assert abs(whole / 1e6 - 1145.8) < 0.1
    assert [round(100 * part / whole) for part in (4 * mixer, 4 * expert_block, whole - 4 * mixer - 4 * expert_block)] == [28, 56, 16]
    assert abs(work["flops"] / 1e12 - 225.29) < 0.005 and abs(work["attn_flops"] / 1e12 - 26.39) < 0.005
    assert abs(work["expert_flops"] / 1e12 - 94.16) < 0.005 and abs(work["ssd_flops"] / 1e12 - 2.68) < 0.005
    assert work["bytes"] == 0
    half = lm_score_ssm.work(config, {**traffic, "rows": 6})
    assert half["flops"] * 2 == work["flops"]


def test_weight_bytes_are_the_trees_own(cell):
    import jax

    from mmlspark_tpu.models.nemotron_h import init_nemotron_h

    for dry in (True, False):
        config, _ = run.sizes(cell, dry)
        tree = jax.eval_shape(lambda k: init_nemotron_h(k, config), jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(tree)
        nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
        assert lm_score_ssm.weight_bytes(config) == nbytes
    # 6,072,897,024 parameters: 12,145,794,048 B were all of them bfloat16 (ISSUE 33's figure); A_log, dt_bias
    # and D of four mixers and four routing biases are float32, 2 x (4 x 3 x 64 + 4 x 128) = 2,560 B more
    assert sum(int(np.prod(a.shape)) for a in leaves) == 6_072_897_024
    assert nbytes == 12_145_794_048 + 2_560 and abs(nbytes / 2**30 - 11.31) < 0.005


# -- a dry run, and correct shown to fail -------------------------------------

@pytest.fixture(scope="module")
def dry_line(cell):
    return run.measure(cell, SEED, 0.0, True, True)


def test_the_dry_run_ends_correct_and_reports_the_cells_metrics(dry_line, cell):
    assert dry_line["correct"] is True and dry_line["failed"] == 0 and dry_line["attempted"] == 1
    assert set(dry_line["checks"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max", "head_gap_max"}
    listed = {"dry_" + name for name in run.layer_metrics(CELL)}
    # the CPU reports no memory statistics, and its trace has no chip's operation names
    missing = {n for n in listed if "hbm_peak" in n or "roofline" in n}
    assert len(missing) == 3 and set(dry_line["metrics"]) == listed - missing
    assert 0 < dry_line["metrics"]["dry_score_mfu_pct.ssm"]["value"] < 100
    assert dry_line["metrics"]["dry_expert_load_peak_pct.ssm"]["value"] >= 100
    assert 0 <= dry_line["metrics"]["dry_experts_empty_pct.ssm"]["value"] < 100
    assert dry_line["metrics"]["dry_span_coverage_pct.ssm"]["value"] > 90
    assert dry_line["metrics"]["dry_programs_built.ssm"]["value"] == 0


def test_an_untraced_dry_run_reports_the_two_end_to_end_metrics(cell):
    line = run.measure(cell, SEED, 0.0, False, True)
    assert line["correct"] and set(line["metrics"]) == {"dry_featurize_img_per_s", "dry_setup_s"}


def _with_job(job):
    """The driver with its timed job replaced once the warm-up has passed."""
    calls = []

    def after_warm_up(state):
        calls.append(1)
        return lm_score_ssm.job(state) if len(calls) == 1 else job(state)

    return types.SimpleNamespace(**{
        k: getattr(lm_score_ssm, k) for k in ("setup", "fault", "end_to_end", "work", "compare")
    }, job=after_warm_up)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_not_correct(cell, fault):
    def job(state):
        honest = lm_score_ssm.job(state)
        return dict(honest, sample=lm_score_ssm.reference_outputs(state, fault=fault))

    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(job))
    assert line["correct"] is False and line["failed"] == 0
    over = {name for name, c in line["checks"].items() if c["value"] > c["limit"]}
    if fault == "head_inputs_3_mantissa_bits":  # nothing before the head moves: the following check sees it
        assert "head_gap_max" in over and "load_gap_max" not in over
        assert line["checks"]["head_gap_max"]["value"] > 3 * cell["dry"]["limits"]["head_gap_max"]
        return
    # the reference's own head over its own hidden state follows itself
    assert line["checks"]["head_gap_max"]["value"] < 1e-6
    if fault == "rotary_in_attention":  # one block in nine over 50 positions: the last positions see it, the loads hardly
        assert {"hidden_gap_max", "logit_gap_max"} <= over
    else:
        assert {"hidden_gap_max", "logit_gap_max", "load_gap_max"} <= over


def test_the_float8_products_control_is_not_correct(cell):
    """The program's own path with every product's inputs one step below
    the bfloat16 the configuration states."""
    low = lambda state: lm_score_ssm.job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(low))
    assert line["correct"] is False and line["failed"] == 0
    for number in ("hidden_gap_max", "head_gap_max"):
        assert line["checks"][number]["value"] > 3 * line["checks"][number]["limit"]
    assert line["checks"]["load_gap_max"]["value"] > line["checks"]["load_gap_max"]["limit"]


def test_control_reads_the_control_and_every_fault(cell):
    state = lm_score_ssm.setup(*run.sizes(cell, True), SEED)
    assert run.passes(lm_score_ssm.checks(state, [lm_score_ssm.job(state)]))
    sides = lm_score_ssm.control(dict(state))
    assert set(sides) == {"control", *ref.FAULTS}
    assert not any(run.passes(checks) for checks in sides.values())


@pytest.mark.parametrize("alter,reason", [
    (lambda out: dict(out, finite=False), "non-finite"),
    (lambda out: dict(out, routed=[99, 100]), "a token was dropped"),
    (lambda out: dict(out, shapes=dict(out["shapes"], expert_load=(6, 9, 16))), "outputs of shapes"),
])
def test_a_job_off_the_cells_path_counts_as_failed(cell, alter, reason):
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(lambda s: alter(lm_score_ssm.job(s))))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)
    state = lm_score_ssm.setup(*run.sizes(cell, True), SEED)
    assert reason in lm_score_ssm.fault(state, alter(lm_score_ssm.job(state)))


def test_a_peak_under_the_weights_counts_as_failed(cell, monkeypatch):
    import jax

    state = lm_score_ssm.setup(*run.sizes(cell, True), SEED)
    out = lm_score_ssm.job(state)
    for peak, failed in ((state["weight_bytes"] - 1, True), (state["weight_bytes"], False)):
        device = types.SimpleNamespace(memory_stats=lambda peak=peak: {"peak_bytes_in_use": peak})
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
        assert bool(lm_score_ssm.fault(state, out)) is failed


def test_the_parent_cannot_run_the_cell_and_says_so_at_once():
    """Without this PR's program (no ``mmlspark_tpu.models.nemotron_h``) the
    driver's set-up raises on import: a clean, early failure, not a hang."""
    import inspect

    source = inspect.getsource(lm_score_ssm.setup)
    assert "from mmlspark_tpu.models.nemotron_h import init_nemotron_h" in source
    with open(os.path.join(ROOT, "chipbench", "drivers", "lm_score_ssm.py")) as f:
        top = f.read().split("def ", 1)[0]
    assert "mmlspark_tpu" not in top.split('"""', 2)[2]  # nothing of the program at module level
