"""The latent-attention cell: its files, its work and bytes counted from
shapes, a dry run that ends ``correct`` and reports exactly the cell's
metrics, the control and each planted fault shown to end ``correct: false``
through ``run.measure``, and a job off the cell's path counted as failed."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.drivers import lm_score_mla
from chipbench.reference import mla_moe as ref

CELL = "joyai-llm-flash.score-16k"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 9  # a toy seed whose last positions sit on no routing tie (PERF.md 6a)


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


# -- the configuration's file, and the cell's -----------------------------------

def test_the_file_repeats_every_published_key_beside_what_is_run(cell):
    spec = cell["config_file"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["joyai-llm-flash"]
    assert spec["source"].startswith(entry["source"]) and "joyai_llm_flash" in spec["source"]
    published = {k: v for k, v in spec["params"].items() if k != "layers"}
    assert published == {k: spec[k] for k in published}, "params and the top level disagree"
    assert spec["reduced"] == entry["reduced"] == ["layers"] and spec["layers"] == spec["params"]["layers"] == 5
    assert spec["num_hidden_layers"] == 40 and spec["model_type"] == "joyai_llm_flash"
    # every width as published
    assert (spec["hidden_size"], spec["num_attention_heads"], spec["num_key_value_heads"]) == (2048, 32, 32)
    assert (spec["q_lora_rank"], spec["kv_lora_rank"]) == (1536, 512)
    assert (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"], spec["qk_head_dim"], spec["v_head_dim"]) == (128, 64, 192, 128)
    assert (spec["intermediate_size"], spec["moe_intermediate_size"]) == (7168, 768)
    assert (spec["n_routed_experts"], spec["num_experts_per_tok"], spec["n_shared_experts"]) == (256, 8, 1)
    assert (spec["first_k_dense_replace"], spec["moe_layer_freq"], spec["routed_scaling_factor"]) == (1, 1, 2.5)
    assert (spec["rope_theta"], spec["rope_interleave"], spec["rope_scaling"]) == (32000000, True, None)
    assert spec["vocab_size"] == 129280 and not spec["tie_word_embeddings"] and spec["rms_norm_eps"] == 1e-6
    assert spec["num_nextn_predict_layers"] == 1 and "not built" in spec["assumed"]["multi_token_prediction"]
    assert set(spec["dry"]) <= set(spec["params"]) | {"interpret"}
    for key in ("equations", "latent_attention", "latent_norms", "rotary_pairs", "shared_rotary_key",
                "softmax_scale", "two_norms", "embedding_scale", "routing_bias", "denominator", "head",
                "weights", "tokens", "interpret"):
        assert key in spec["assumed"], key
    assert "stage 0 of eight" in spec["deployment"] and "10.35 GiB" in spec["deployment"]


def test_the_cell_is_the_issues(cell):
    assert (cell["chips"], cell["driver"], cell["config"]) == (1, "lm_score_mla", "joyai-llm-flash")
    params = cell["params"]
    assert (params["rows"], params["tokens"], params["batchSize"]) == (12, 16384, 1)
    assert (params["zipf_exponent"], params["compare_rows"]) == (1.0, 2)
    assert cell["profiler"] == {"host_tracer_level": 1}
    assert set(params["limits"]) == {"logit_gap_max", "hidden_gap_max", "load_gap_max", "head_gap_max"}
    listed = run.layer_metrics(CELL)
    assert set(listed) == {name + ".mla" for name in (
        "score_mfu_pct", "device_idle_pct", "hbm_peak_gib", "window_compile_s", "programs_built",
        "stack_ms", "dispatch_ms", "fetch_ms", "span_coverage_pct", "expert_load_peak_pct",
        "attn_roofline_pct", "experts_roofline_pct", "experts_empty_pct")}
    assert all(spec["workloads"] == [CELL] and spec["moves"] == "featurize_img_per_s" for spec in listed.values())
    assert listed["attn_roofline_pct.mla"]["args"]["pattern"] == "^attn_full"
    assert not run.layer_metrics("trinity-mini.score-8k").keys() & listed.keys()


# -- work and bytes, from shapes ----------------------------------------------

def test_work_against_the_hand_count(cell):
    config, traffic = run.sizes(cell, False)
    work = lm_score_mla.work(config, traffic)
    tokens = 12 * 16384
    # ISSUE 31, multiply-adds x 2 a token a layer: the four latent projections 35.9 MFLOP (52.7 with the
    # output projection), routed experts 75.5, shared expert and router 10.5, dense FFN 88.1,
    # attention 2 x 32 x (192 + 128) a key seen
    latent = 2 * (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256)
    assert latent == 35_913_728 and latent + 2 * 4096 * 2048 == 52_690_944
    assert work["latent_flops"] == tokens * 5 * latent
    assert work["expert_flops"] == tokens * 4 * 8 * 3 * 2 * 2048 * 768
    seen = 16384 * 16385 // 2
    assert work["attn_flops"] == 12 * 5 * 2 * 32 * 320 * seen
    other = 5 * 2 * 4096 * 2048 + 2 * 3 * 2048 * 7168 + 4 * (2 * 3 * 2048 * 768 + 2 * 2048 * 256)
    assert work["flops"] == (work["attn_flops"] + work["expert_flops"] + work["latent_flops"]
                             + tokens * other + 12 * 2 * 2048 * 129280)
    # attention is 55% of an expert layer's work at 16,384 tokens a row, 72% with the latent projections
    layer = 2 * 32 * 320 * seen / 16384 + 52_690_944 + 75_497_472 + 10_485_760
    assert abs(2 * 32 * 320 * seen / 16384 / layer - 0.55) < 0.01
    assert abs(work["flops"] / 12 / 1e12 - 25.1) < 0.1 and abs(work["flops"] / 1e12 - 301.7) < 0.1
    assert work["bytes"] == 0
    half = lm_score_mla.work(config, {**traffic, "rows": 6})
    assert half["flops"] * 2 == work["flops"]


def test_weight_bytes_are_the_trees_own(cell):
    import jax

    from mmlspark_tpu.models.mla_moe import init_mla_moe

    for dry in (True, False):
        config, _ = run.sizes(cell, dry)
        tree = jax.eval_shape(lambda k: init_mla_moe(k, config), jax.random.PRNGKey(0))
        nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))
        assert lm_score_mla.weight_bytes(config) == nbytes
    # 10.35 GiB: 69% of the chip before any activation, so the 4.00 GiB floor is met by the weights alone
    assert nbytes == 11_116_285_952 and abs(nbytes / 2**30 - 10.35) < 0.005 and nbytes > 11.1e9


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
    assert 0 < dry_line["metrics"]["dry_score_mfu_pct.mla"]["value"] < 100
    assert dry_line["metrics"]["dry_expert_load_peak_pct.mla"]["value"] >= 100
    assert 0 <= dry_line["metrics"]["dry_experts_empty_pct.mla"]["value"] < 100
    assert dry_line["metrics"]["dry_span_coverage_pct.mla"]["value"] > 90
    assert dry_line["metrics"]["dry_programs_built.mla"]["value"] == 0


def test_an_untraced_dry_run_reports_the_two_end_to_end_metrics(cell):
    line = run.measure(cell, SEED, 0.0, False, True)
    assert line["correct"] and set(line["metrics"]) == {"dry_featurize_img_per_s", "dry_setup_s"}


def _with_job(job):
    """The driver with its timed job replaced once the warm-up has passed."""
    calls = []

    def after_warm_up(state):
        calls.append(1)
        return lm_score_mla.job(state) if len(calls) == 1 else job(state)

    return types.SimpleNamespace(**{
        k: getattr(lm_score_mla, k) for k in ("setup", "fault", "end_to_end", "work", "compare")
    }, job=after_warm_up)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_not_correct(cell, fault):
    def job(state):
        honest = lm_score_mla.job(state)
        return dict(honest, sample=lm_score_mla.reference_outputs(state, fault=fault))

    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(job))
    assert line["correct"] is False and line["failed"] == 0
    over = {name for name, c in line["checks"].items() if c["value"] > c["limit"]}
    if fault == "head_inputs_3_mantissa_bits":
        # the head alone at float8's mantissa: nothing before it moves, and at the cell's size, where the
        # logits' own limit is coarse (0.8), only the following check sees it
        assert over == {"head_gap_max", "logit_gap_max"}
        assert 0.02 < line["checks"]["logit_gap_max"]["value"] < cell["params"]["limits"]["logit_gap_max"]
        assert line["checks"]["head_gap_max"]["value"] > 3 * cell["params"]["limits"]["head_gap_max"]
    else:  # the reference's own head over its own hidden state follows itself
        assert "load_gap_max" in over and line["checks"]["head_gap_max"]["value"] < 1e-6


def test_the_float8_products_control_is_not_correct(cell):
    """The program's own path with every product's inputs one step below
    the bfloat16 the configuration states."""
    low = lambda state: lm_score_mla.job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(low))
    assert line["correct"] is False and line["failed"] == 0
    for number in ("hidden_gap_max", "load_gap_max", "head_gap_max"):
        assert line["checks"][number]["value"] > 3 * line["checks"][number]["limit"]


def test_control_reads_the_control_and_every_fault(cell):
    state = lm_score_mla.setup(*run.sizes(cell, True), SEED)
    assert run.passes(lm_score_mla.checks(state, [lm_score_mla.job(state)]))
    sides = lm_score_mla.control(dict(state))
    assert set(sides) == {"control", *ref.FAULTS}
    assert not any(run.passes(checks) for checks in sides.values())


def test_one_sampled_row_on_a_routing_tie_cannot_fail_a_run_and_every_row_moved_does(cell):
    """A last position whose 8th and 9th expert tie reads 0.1-0.6 on the chip
    (PERF.md 6a): the last-position numbers take the smaller gap of a job's
    sampled rows, so it takes every row to fail them."""
    state = lm_score_mla.setup(*run.sizes(cell, True), SEED)
    out = lm_score_mla.job(state)

    def moved(rows):
        sample = {name: a.copy() for name, a in out["sample"].items()}
        for name in ("hidden", "logits"):
            sample[name][rows] *= 1.5  # a gap of 0.5; the head still follows (its product is linear)
        return lm_score_mla.checks(state, [dict(out, sample=sample)])

    one, every = moved([0]), moved(slice(None))
    assert run.passes(one) and one["hidden_gap_max"]["value"] < 0.03
    assert not run.passes(every)
    assert every["hidden_gap_max"]["value"] == pytest.approx(0.5, abs=0.02)
    assert every["logit_gap_max"]["value"] == pytest.approx(0.5, abs=0.02)
    assert every["head_gap_max"]["value"] < every["head_gap_max"]["limit"]


@pytest.mark.parametrize("alter,reason", [
    (lambda out: dict(out, finite=False), "non-finite"),
    (lambda out: dict(out, routed=[99, 100]), "a token was dropped"),
    (lambda out: dict(out, shapes=dict(out["shapes"], logits=(6, 3))), "outputs of shapes"),
])
def test_a_job_off_the_cells_path_counts_as_failed(cell, alter, reason):
    line = run.measure(cell, SEED, 0.0, False, True, driver=_with_job(lambda s: alter(lm_score_mla.job(s))))
    assert (line["attempted"], line["failed"], line["correct"]) == (1, 1, False)
    state = lm_score_mla.setup(*run.sizes(cell, True), SEED)
    assert reason in lm_score_mla.fault(state, alter(lm_score_mla.job(state)))


def test_a_peak_under_the_weights_counts_as_failed(cell, monkeypatch):
    import jax

    state = lm_score_mla.setup(*run.sizes(cell, True), SEED)
    out = lm_score_mla.job(state)
    for peak, failed in ((state["weight_bytes"] - 1, True), (state["weight_bytes"], False)):
        device = types.SimpleNamespace(memory_stats=lambda peak=peak: {"peak_bytes_in_use": peak})
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
        assert bool(lm_score_mla.fault(state, out)) is failed


def test_the_parent_cannot_run_the_cell_and_says_so_at_once():
    """Without this PR's program (no ``mmlspark_tpu.models.mla_moe``) the
    driver's set-up raises on import: a clean, early failure, not a hang."""
    import inspect

    source = inspect.getsource(lm_score_mla.setup)
    assert "from mmlspark_tpu.models.mla_moe import init_mla_moe" in source
    with open(os.path.join(ROOT, "chipbench", "drivers", "lm_score_mla.py")) as f:
        top = f.read().split("def ", 1)[0]
    assert "mmlspark_tpu" not in top.split('"""', 2)[2]  # nothing of the program at module level
