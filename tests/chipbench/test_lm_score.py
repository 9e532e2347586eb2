"""The language-model cell: its files, its work counted from shapes, a dry
run that ends ``correct``, the control and each planted fault shown to end
``correct: false`` through ``run.measure``, and the two readers it brings."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.drivers import lm_score
from chipbench.readers import op_roofline, span_tag_ratio
from chipbench.reference import afmoe as ref

CELL = "trinity-mini.score-8k"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 9  # a toy seed whose last positions sit on no routing tie (PERF.md 6a)


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


# -- the configuration's file -------------------------------------------------

def test_the_file_repeats_every_published_key_beside_what_is_run(cell):
    spec = cell["config_file"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["trinity-mini"]
    assert spec["source"].startswith(entry["source"])
    published = {k: v for k, v in spec["params"].items() if k != "layers"}
    assert published == {k: spec[k] for k in published}, "params and the top level disagree"
    assert spec["reduced"] == ["layers"] and spec["layers"] == spec["params"]["layers"] == 6
    assert spec["num_hidden_layers"] == 32 and len(spec["layer_types"]) == 32
    # every width as published
    assert (spec["hidden_size"], spec["head_dim"], spec["intermediate_size"]) == (2048, 128, 6144)
    assert (spec["moe_intermediate_size"], spec["num_experts"], spec["num_experts_per_tok"]) == (1024, 128, 8)
    assert (spec["num_attention_heads"], spec["num_key_value_heads"], spec["sliding_window"]) == (32, 4, 2048)
    assert spec["vocab_size"] == 200192 and not spec["tie_word_embeddings"]
    assert set(spec["dry"]) <= set(spec["params"]) | {"interpret"}
    for key in ("qk_norm", "output_gate", "four_norms", "positions", "embedding_scale", "routing_bias"):
        assert key in spec["assumed"], key


# -- work and bytes, from shapes ----------------------------------------------

def test_work_against_the_hand_count(cell):
    config, traffic = run.sizes(cell, False)
    work = lm_score.work(config, traffic)
    tokens = 32 * 8192
    # ISSUE 27: projections 55 MFLOP a token and layer, experts 113 (routed 100.7 + shared 12.6),
    # attention 29 sliding / 67 full at 8,192, dense FFN 75; 1.14 GFLOP a token, 300 TFLOP a job
    assert work["expert_flops"] == tokens * 4 * 8 * 3 * 2 * 2048 * 1024
    sliding = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    full = 8192 * 8193 // 2
    assert lm_score.seen_keys(8192, 2048) == sliding and lm_score.seen_keys(8192) == full
    assert lm_score.seen_keys(100, 2048) == 100 * 101 // 2
    assert work["attn_flops"] == 32 * 4 * 32 * 128 * (5 * sliding + full)
    assert abs(work["flops"] / tokens / 1e9 - 1.147) < 0.001 and abs(work["flops"] / 1e12 - 300.7) < 0.1
    assert work["bytes"] == 0
    half = lm_score.work(config, {**traffic, "rows": 16})
    assert half["flops"] * 2 == work["flops"]


def test_weight_bytes_are_the_trees_own(cell):
    import jax

    from mmlspark_tpu.models.afmoe import init_afmoe

    for dry in (True, False):
        config, _ = run.sizes(cell, dry)
        tree = jax.eval_shape(lambda k: init_afmoe(k, config), jax.random.PRNGKey(0))
        nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))
        assert lm_score.weight_bytes(config) == nbytes
    assert 8.60e9 < nbytes < 8.62e9 and nbytes > 0.5 * 16e9  # 8.02 GiB: half the chip before any activation


def test_zipf_tokens_come_from_the_seed_and_are_skewed():
    draw = lambda seed: lm_score.zipf_tokens(np.random.default_rng(seed), 8, 4096, 200192, 1.0)
    a, b = draw(2**31 + 5), draw(2**31 + 5)
    assert a.dtype == np.int32 and a.shape == (8, 4096) and np.array_equal(a, b)
    assert not np.array_equal(a, draw(5)) and 0 <= a.min() and a.max() < 200192
    counts = np.sort(np.bincount(a.ravel()))[::-1]
    assert counts[0] > 0.05 * a.size and counts[0] > 1.5 * counts[1] > 1.5 * counts[3]


# -- a dry run, and correct shown to fail -------------------------------------

@pytest.fixture(scope="module")
def dry_line(cell):
    return run.measure(cell, SEED, 0.0, True, True)


def test_the_dry_run_ends_correct_and_reports_the_cells_metrics(dry_line, cell):
    assert dry_line["correct"] is True and dry_line["failed"] == 0 and dry_line["attempted"] == 1
    assert set(dry_line["checks"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max"}
    listed = {"dry_" + name for name in run.layer_metrics(CELL)}
    # the CPU reports no memory statistics, and its trace has no chip's operation names
    missing = {n for n in listed if "hbm_peak" in n or "roofline" in n}
    assert len(missing) == 3 and set(dry_line["metrics"]) == listed - missing
    assert 0 < dry_line["metrics"]["dry_score_mfu_pct"]["value"] < 100
    assert dry_line["metrics"]["dry_expert_load_peak_pct.score"]["value"] >= 100
    assert dry_line["metrics"]["dry_span_coverage_pct.score"]["value"] > 90


def test_an_untraced_dry_run_reports_the_two_end_to_end_metrics(cell):
    line = run.measure(cell, SEED, 0.0, False, True)
    assert line["correct"] and set(line["metrics"]) == {"dry_featurize_img_per_s", "dry_setup_s"}


def _with_job(job):
    """The driver with its timed job replaced once the warm-up has passed."""
    calls = []

    def after_warm_up(state):
        calls.append(1)
        return lm_score.job(state) if len(calls) == 1 else job(state)

    return types.SimpleNamespace(**{
        k: getattr(lm_score, k) for k in ("setup", "fault", "end_to_end", "work", "compare")
    }, job=after_warm_up)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_not_correct(cell, fault):
    def job(state):
        honest = lm_score.job(state)
        return dict(honest, sample=lm_score.reference_outputs(state, fault=fault))

    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(job))
    assert line["correct"] is False and line["failed"] == 0
    over = [n for n, c in line["checks"].items() if c["value"] > c["limit"]]
    assert "load_gap_max" in over or fault == "window_ignored" and over


def test_the_float8_products_control_is_not_correct(cell):
    """The program's own path with every product's inputs one step below
    the bfloat16 the configuration states."""
    low = lambda state: lm_score.job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(low))
    assert line["correct"] is False and line["failed"] == 0
    for number in ("hidden_gap_max", "load_gap_max"):
        assert line["checks"][number]["value"] > 3 * line["checks"][number]["limit"]


def test_control_reads_the_control_and_every_fault(cell):
    state = lm_score.setup(*run.sizes(cell, True), SEED)
    assert run.passes(lm_score.checks(state, [lm_score.job(state)]))
    sides = lm_score.control(dict(state))
    assert set(sides) == {"control", *ref.FAULTS}
    assert not any(run.passes(checks) for checks in sides.values())


@pytest.mark.parametrize("alter,reason", [
    (lambda out: dict(out, finite=False), "non-finite"),
    (lambda out: dict(out, routed=[99, 100]), "a token was dropped"),
    (lambda out: dict(out, shapes=dict(out["shapes"], logits=(6, 3))), "outputs of shapes"),
])
def test_a_job_off_the_cells_path_counts_as_failed(cell, alter, reason):
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(lambda s: alter(lm_score.job(s))))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)
    state = lm_score.setup(*run.sizes(cell, True), SEED)
    assert reason in lm_score.fault(state, alter(lm_score.job(state)))


def test_a_peak_under_the_weights_counts_as_failed(cell, monkeypatch):
    import jax

    state = lm_score.setup(*run.sizes(cell, True), SEED)
    out = lm_score.job(state)
    for peak, failed in ((state["weight_bytes"] - 1, True), (state["weight_bytes"], False)):
        device = types.SimpleNamespace(memory_stats=lambda peak=peak: {"peak_bytes_in_use": peak})
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
        assert bool(lm_score.fault(state, out)) is failed


# -- the readers this cell brings ---------------------------------------------

def test_span_tag_ratio_on_a_hand_made_window():
    spans = [
        {"name": "lm.route_stats", "tags": {"load_peak": 300, "load_mean": 200.0}},
        {"name": "lm.route_stats", "tags": {"load_peak": 500, "load_mean": 200.0}},
        {"name": "lm.route_stats", "tags": {"load_peak": 9}},  # lacks the other tag: adds nothing
        {"name": "dnn.fetch", "tags": {"load_peak": 1e9, "load_mean": 1.0}},
    ]
    args = {"span": "lm.route_stats", "numerator": "load_peak", "denominator": "load_mean"}
    assert span_tag_ratio.read({"spans": spans}, **args, scale=100.0) == 200.0
    assert span_tag_ratio.read({"spans": spans}, **args) == 2.0
    assert span_tag_ratio.read({"spans": spans[2:]}, **args) is None
    assert span_tag_ratio.read({"spans": []}, **args) is None


def test_op_roofline_on_a_hand_made_trace():
    ops = [["ragged-dot.3 bf16[262144,1024]", 0.5], ["fusion.9 f32[4,8]", 2.0],
           ["ragged-dot.4 bf16[262144,1024]", 0.25], ["ragged-dot.5 f32[262144,2048]", 0.25]]
    ctx = {"trace": {"device_ops": ops}, "work": {"expert_flops": 50e12, "none": 0},
           "peaks": {"bf16_flops_per_s": 100e12}}
    args = {"work": "expert_flops", "pattern": r"^ragged-dot", "peak": "bf16_flops_per_s"}
    assert op_roofline.read(ctx, rows=3, **args) == pytest.approx(50.0)
    # a row of the kernel is missing from the table: a share over part of its time would read high
    assert op_roofline.read(ctx, rows=4, **args) is None
    assert op_roofline.read(dict(ctx, trace={"device_ops": ops[1:2]}), rows=1, **args) is None
    assert op_roofline.read(dict(ctx, trace=None), rows=3, **args) is None
    assert op_roofline.read(ctx, rows=3, **{**args, "work": "none"}) is None
    assert op_roofline.read(ctx, rows=3, **{**args, "work": "absent"}) is None
