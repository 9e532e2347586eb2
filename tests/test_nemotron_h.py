"""The hybrid state-space decoder (``model_type`` ``nemotron_h``) on the deep
path, at a small size on the CPU: ``LMFeaturizer`` through
``DNNModel.transform`` against the benchmark's plain reference (whose scan is
the recurrence a position at a time), every planted fault far from it, the
pattern read as units, attention blocks looked up by index, the seeded
state-space values, the spans, and the family table the stage documents
itself from."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as ref
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.featurize.lm import FAMILIES, LMFeaturizer
from mmlspark_tpu.models import init_nemotron_h, nemotron_h_apply
from mmlspark_tpu.models.nemotron_h import span_tags, state_width, units
from mmlspark_tpu.observability.tracing import get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # as published: 52 blocks
SMALL = dict(
    model_type="nemotron_h", hybrid_override_pattern=PATTERN, layers=9, hidden_size=64,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=16,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, rope_theta=10000,
    n_routed_experts=16, num_experts_per_tok=2, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
    vocab_size=512,
    interpret=True,  # the attention and scan kernels, on a backend that is no TPU
)
ALL_OUTPUTS = {"hidden": "h", "logits": "l", "expert_load": "e"}


def _tokens(seed, rows=5, length=50):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], size=(rows, length)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return init_nemotron_h(jax.random.PRNGKey(11), SMALL)


@pytest.fixture(scope="module")
def program(params):
    return jax.jit(lambda p, x: nemotron_h_apply(p, x, SMALL))(params, _tokens(4))


# -- the model, through the stage ---------------------------------------------

@pytest.mark.parametrize("seed,batch", [(0, 2), (1, 5), (3, 3)])
def test_featurizer_agrees_with_the_reference_on_all_three_outputs(params, seed, batch):
    """50 tokens are three chunks of 16 and two positions: the carried state
    crosses three boundaries and the row is padded behind."""
    tokens = _tokens(seed)
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=batch).transform(Table({"tokens": tokens}))
    want = ref.forward(params, tokens, SMALL)
    assert out["h"].shape == (5, 64) and out["l"].shape == (5, 512) and out["e"].shape == (5, 4, 16)
    assert out["h"].dtype == np.float32 and out["l"].dtype == np.float32 and out["e"].dtype == np.int32
    # two-matrix relu^2 experts square a rounding's share: a toy row reads 0.005-0.03, a fault 0.3-1.5
    assert np.sort(ref.relative_gaps(out["h"], want["hidden"]))[-2] < 0.04
    assert np.sort(ref.relative_gaps(out["l"], want["logits"]))[-2] < 0.04
    # a sharp softmax over 50 keys turns a rounding into another key: 12 toy seeds read 0.02-0.06, the control 0.13-0.17
    assert ref.load_gaps(out["e"], want["expert_load"], 50 * 2).max() <= 0.1
    # no token is dropped: every expert block of every row routed S x k
    assert (out["e"].sum(axis=-1) == 50 * 2).all()


@pytest.mark.parametrize("width,seed", [(256, 3), (40, 2)], ids=["on_the_256_grid", "another_off_it"])
def test_featurizer_agrees_with_the_reference_at_another_expert_width(width, seed):
    """Whatever the published inner width, the stacks are read where they lie
    at that width: the tree is the published count, and the same limits hold."""
    config = dict(SMALL, moe_intermediate_size=width)
    params = init_nemotron_h(jax.random.PRNGKey(11), config)
    assert params["experts"]["e_up"].shape == (4, 16, 64, width) and params["experts"]["e_down"].shape == (4, 16, width, 64)
    tokens = _tokens(seed)
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=config,
                       batchSize=3).transform(Table({"tokens": tokens}))
    want = ref.forward(params, tokens, config)
    assert np.sort(ref.relative_gaps(out["h"], want["hidden"]))[-2] < 0.04
    assert np.sort(ref.relative_gaps(out["l"], want["logits"]))[-2] < 0.04
    assert ref.load_gaps(out["e"], want["expert_load"], 50 * 2).max() <= 0.1
    assert (out["e"].sum(axis=-1) == 50 * 2).all()


def _eqns(jaxpr, name):
    found = []
    for eqn in jaxpr.eqns:
        found += [eqn] if eqn.primitive.name == name else []
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


@pytest.mark.parametrize("product_dtype", ["bfloat16", "float8_e4m3fn"])
def test_the_expert_stacks_are_read_where_they_lie_when_the_products_take_them_as_stored(params, product_dtype):
    """As stated, the two stacks stay out of the unit scan's ``xs``: the scan
    closes over all 4 x 16 groups and the Pallas grouped matmul is told where
    the unit's stand; nothing slices a unit's matrices out. Product inputs
    narrower than stored are rounded into a copy whatever is done, so then
    the stacks go through the scan as every other leaf, a unit's at a time,
    to ``lax.ragged_dot``: the program it was."""
    config = dict(SMALL, product_dtype=product_dtype)
    jaxpr = jax.make_jaxpr(lambda p, x: nemotron_h_apply(p, x, config))(params, _tokens(0))
    (scan,) = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"]
    consts = scan.params["num_consts"]
    closed_over = [v.aval.shape for v in scan.invars[:consts]]
    carried_or_scanned = [v.aval.shape for v in scan.invars[consts:]]
    body = scan.params["jaxpr"].jaxpr
    stacks, in_place = [(4, 16, 64, 24), (4, 16, 24, 64)], [(64, 64, 24), (64, 24, 64)]
    if product_dtype == "bfloat16":
        assert all(shape in closed_over for shape in in_place)
        assert not any(shape in carried_or_scanned for shape in stacks + in_place)
        assert len(_eqns(body, "pallas_call")) >= 2 and not _eqns(body, "ragged_dot_general")
        assert not [e for e in _eqns(body, "dynamic_slice") if e.outvars[0].aval.shape[-2:] in ((64, 24), (24, 64))]
    else:
        assert all(shape in carried_or_scanned for shape in stacks)
        assert [e.invars[1].aval.shape for e in _eqns(body, "ragged_dot_general")] == [(16, 64, 24), (16, 24, 64)]


def test_reading_the_stacks_in_place_changes_no_routing_and_no_result(params, program):
    """The same tree with its two expert stacks in float32 (the same values)
    takes the other way, a unit's matrices through the scan to
    ``lax.ragged_dot``: every expert receives the same tokens, and the
    outputs agree to a product's rounding."""
    wide = dict(params, experts=dict(params["experts"], **{
        name: params["experts"][name].astype(jnp.float32) for name in ("e_up", "e_down")}))
    through_the_scan = jax.jit(lambda p, x: nemotron_h_apply(p, x, SMALL))(wide, _tokens(4))
    assert np.array_equal(through_the_scan["expert_load"], program["expert_load"])
    assert ref.relative_gaps(through_the_scan["hidden"], program["hidden"]).max() < 1e-3
    assert ref.relative_gaps(through_the_scan["logits"], program["logits"]).max() < 1e-3


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference_far_from_the_program(params, program, fault):
    tokens = _tokens(4)
    honest = ref.relative_gaps(program["hidden"], ref.forward(params, tokens, SMALL)["hidden"])
    faulty = ref.forward(params, tokens, SMALL, fault=fault)
    wrong = ref.relative_gaps(program["hidden"], faulty["hidden"])
    if fault == "head_inputs_3_mantissa_bits":  # nothing before the head moves; the logits alone do
        assert np.sort(wrong)[-2] < 0.04
        assert ref.relative_gaps(faulty["logits"], ref.head_of(params, faulty["hidden"])).min() > 0.02
        return
    assert wrong.min() > 0.1
    assert wrong.min() > 2 * np.sort(honest)[-2]


def test_the_head_over_the_programs_own_hidden_state_is_the_programs_logits(params, program):
    assert ref.relative_gaps(program["logits"], ref.head_of(params, program["hidden"])).max() < 1e-5
    low = jax.jit(lambda p, x: nemotron_h_apply(p, x, dict(SMALL, product_dtype="float8_e4m3fn")))(params, _tokens(4))
    assert ref.relative_gaps(low["logits"], ref.head_of(params, low["hidden"])).min() > 0.02


def test_an_unknown_fault_is_an_error(params):
    with pytest.raises(ValueError, match="unknown fault"):
        ref.forward(params, _tokens(0, rows=1), SMALL, fault="typo")


def test_the_reference_imports_nothing_of_the_program_and_scans_a_position_at_a_time():
    with open(os.path.join(ROOT, "chipbench", "reference", "nemotron_h.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mmlspark_tpu" not in code and "cumsum" not in code and "lax.scan(step" in code
    # its scan against the recurrence written out in float64
    rng = np.random.default_rng(0)
    x, B, C = rng.normal(size=(9, 2, 3)), rng.normal(size=(9, 1, 4)), rng.normal(size=(9, 1, 4))
    dt, A, D = rng.uniform(0.01, 0.5, size=(9, 2)), -rng.uniform(1, 4, size=2), rng.uniform(0.5, 1.5, size=2)
    state, want = np.zeros((2, 3, 4)), np.zeros((9, 2, 3))
    for t in range(9):
        for h in range(2):
            state[h] = np.exp(dt[t, h] * A[h]) * state[h] + dt[t, h] * np.outer(x[t, h], B[t, 0])
            want[t, h] = state[h] @ C[t, 0] + D[h] * x[t, h]
    got = ref.scan(*(jnp.asarray(a, jnp.float32) for a in (x, dt, A, B, C, D)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    reset = ref.scan(*(jnp.asarray(a, jnp.float32) for a in (x, dt, A, B, C, D)), reset_every=4)
    np.testing.assert_allclose(reset[:4], want[:4], rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(reset[4:]) - want[4:]).max() > 0.05


def test_the_references_convolution_is_causal_with_three_zeros_in_front():
    rng = np.random.default_rng(1)
    x, taps, bias = rng.normal(size=(10, 3)), rng.normal(size=(3, 4)), rng.normal(size=3)
    got = np.asarray(ref.conv(*(jnp.asarray(a, jnp.float32) for a in (x, taps, bias))))
    ahead = np.concatenate([np.zeros((3, 3)), x])
    want = np.stack([bias + sum(taps[:, j] * ahead[t + j] for j in range(4)) for t in range(10)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.allclose(got[0], bias + taps[:, 3] * x[0], atol=1e-6)  # the first position sees itself alone


def test_narrower_product_inputs_are_a_different_result(params, program):
    tokens = _tokens(4)
    again = jax.jit(lambda p, x: nemotron_h_apply(p, x, dict(SMALL, product_dtype="bfloat16")))(params, tokens)
    low = jax.jit(lambda p, x: nemotron_h_apply(p, x, dict(SMALL, product_dtype="float8_e4m3fn")))(params, tokens)
    assert np.array_equal(program["hidden"], again["hidden"])
    assert 0.02 < ref.relative_gaps(low["hidden"], program["hidden"]).min()


# -- the pattern, read as units -----------------------------------------------

def test_units_of_the_published_pattern_and_of_the_cut():
    assert units(dict(SMALL, layers=52)).count(True) == 6 and len(units(dict(SMALL, layers=52))) == 23
    assert units(SMALL) == [False, False, True, False]  # MEMEM*EME
    assert ref.blocks(SMALL) == [("M", 0), ("E", 0), ("M", 1), ("E", 1), ("M", 2), ("*", 0), ("E", 2),
                                 ("M", 3), ("E", 3)]
    assert units(dict(SMALL, layers=0)) == []
    with open(os.path.join(ROOT, "chipbench", "configs", "nemotron-3-nano.json")) as f:
        spec = json.load(f)
    assert spec["hybrid_override_pattern"] == PATTERN and len(PATTERN) == spec["num_hidden_layers"] == 52
    assert spec["params"]["layers"] == 9 and units(spec["params"]) == [False, False, True, False]


@pytest.mark.parametrize("layers", [1, 3, 5, 6, 8, 53])
def test_a_pattern_that_does_not_end_on_a_whole_unit_is_an_error(layers):
    with pytest.raises(ValueError, match="not whole units"):
        units(dict(SMALL, layers=layers))
    with pytest.raises(ValueError, match="not whole units"):
        init_nemotron_h(jax.random.PRNGKey(0), dict(SMALL, layers=layers))
    if layers < 53:
        with pytest.raises(ValueError, match="not whole units"):
            ref.blocks(dict(SMALL, layers=layers))


@pytest.mark.parametrize("pattern,layers,attention_blocks", [
    ("M*EMEM*E", 8, 2), ("MEME", 4, 0), ("M*E", 3, 1)], ids=["first_and_third_unit", "no_attention", "one_unit"])
def test_attention_blocks_are_held_where_the_pattern_has_them_and_looked_up_by_index(pattern, layers, attention_blocks):
    config = dict(SMALL, hybrid_override_pattern=pattern, layers=layers)
    weights = init_nemotron_h(jax.random.PRNGKey(2), config)
    assert weights["attention"]["wq"].shape[0] == attention_blocks
    assert weights["mixer"]["in_proj"].shape[0] == weights["experts"]["e_up"].shape[0] == pattern.count("E")
    tokens = _tokens(8, rows=4, length=40)
    got = jax.jit(lambda p, x: nemotron_h_apply(p, x, config))(weights, tokens)
    want = ref.forward(weights, tokens, config)
    assert np.sort(ref.relative_gaps(got["hidden"], want["hidden"]))[-2] < 0.04
    assert got["expert_load"].shape == (4, pattern.count("E"), 16)
    if attention_blocks == 2:  # the second block's weights in the first's place are another result
        swapped = dict(weights, attention=jax.tree.map(lambda a: a[::-1], weights["attention"]))
        other = jax.jit(lambda p, x: nemotron_h_apply(p, x, config))(swapped, tokens)
        assert ref.relative_gaps(other["hidden"], got["hidden"]).min() > 0.1


# -- the family table, the spans, the scopes ----------------------------------

def test_the_stage_documents_itself_from_the_family_table(params):
    assert set(FAMILIES) >= {"afmoe", "joyai_llm_flash", "nemotron_h"}
    described = [LMFeaturizer.__doc__, LMFeaturizer._param_specs["modelConfig"].doc]
    for model_type, (module, apply, init) in FAMILIES.items():
        assert all(f"'{model_type}': {module}" in text for text in described)
        assert f"{module}.{init}" in LMFeaturizer._param_specs["modelParams"].doc
        family = __import__(module, fromlist=[apply])
        assert callable(getattr(family, apply)) and callable(getattr(family, init)) and callable(family.span_tags)
    with pytest.raises(ValueError, match="mmlspark_tpu.models.nemotron_h.init_nemotron_h"):
        LMFeaturizer().transform(Table({"tokens": _tokens(0)}))
    with pytest.raises(ValueError, match="'gpt': one of .'afmoe', 'joyai_llm_flash', .*'nemotron_h'"):
        LMFeaturizer(modelParams=params, modelConfig=dict(SMALL, model_type="gpt")).transform(
            Table({"tokens": _tokens(0)}))


def test_spans_of_a_transform(params):
    tracer = get_tracer()
    tracer.clear()
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=2).transform(Table({"tokens": _tokens(5)}))
    spans = {s["name"]: s for s in tracer.export()}
    root = spans["lm.featurize"]
    assert root["tags"] == {
        "rows": 5, "tokens": 50, "batch_size": 2, "layers": 9, "experts": 16, "model_type": "nemotron_h",
        "attention": "grouped", "mixer_layers": 4, "expert_layers": 4, "attention_layers": 1,
        "state_width": 4 * 8 * 16}
    assert spans["dnn.transform"]["parent_id"] == root["span_id"]
    stats = spans["lm.route_stats"]["tags"]
    assert stats["tokens_routed"] == 5 * 4 * 50 * 2 and stats["expert_groups"] == 3 * 4 * 16
    assert out["e"].shape == (5, 4, 16)
    with open(os.path.join(ROOT, "chipbench", "configs", "nemotron-3-nano.json")) as f:
        published = json.load(f)["params"]
    assert span_tags(published)["state_width"] == state_width(published) == 524288


def test_named_scopes_are_in_the_lowered_program(params):
    text = jax.jit(lambda p, x: nemotron_h_apply(p, x, SMALL)).lower(params, _tokens(0)).as_text(debug_info=True)
    for scope in ("ssm_conv", "ssm_scan", "ssm_gate_norm", "attn_full", "moe_route", "moe_experts", "lm_head"):
        assert scope in text, scope


def test_weights_come_from_the_key_and_the_state_space_values_from_the_published_recipe(params):
    leaves = jax.tree.leaves(params)
    assert all(isinstance(a, jax.Array) for a in leaves)
    wide = {f"{kind}.{name}" for kind in ("mixer", "attention", "experts") for name, a in params[kind].items()
            if a.dtype == jnp.float32}
    assert wide == {"mixer.A_log", "mixer.dt_bias", "mixer.D", "experts.router_bias"}
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert params["mixer"]["in_proj"].shape == (4, 64, 32 + (32 + 2 * 2 * 16) + 4)
    assert params["mixer"]["conv_w"].shape == (4, 96, 4) and params["mixer"]["conv_b"].shape == (4, 96)
    assert params["mixer"]["gate_norm"].shape == (4, 32) and params["mixer"]["out_proj"].shape == (4, 32, 64)
    assert params["attention"]["wq"].shape == (1, 64, 64) and params["attention"]["wk"].shape == (1, 64, 32)
    assert params["experts"]["e_up"].shape == (4, 16, 64, 24) and params["experts"]["e_down"].shape == (4, 16, 24, 64)
    assert params["experts"]["s_up"].shape == (4, 64, 48) and "e_gate" not in params["experts"]
    rate = np.exp(np.asarray(params["mixer"]["A_log"]))
    step = np.log1p(np.exp(np.asarray(params["mixer"]["dt_bias"], np.float64)))  # softplus
    assert (1 <= rate).all() and (rate < 16).all() and (0.001 <= step + 1e-9).all() and (step <= 0.1 + 1e-6).all()
    assert (0.2 < np.exp(-step * rate)).all() and (np.exp(-step * rate) < 0.9999).all()  # a step's decay
    again = init_nemotron_h(jax.random.PRNGKey(11), SMALL)
    other = init_nemotron_h(jax.random.PRNGKey(8), SMALL)
    for name in ("in_proj", "A_log", "dt_bias", "D"):
        assert np.array_equal(params["mixer"][name], again["mixer"][name])
        assert not np.array_equal(params["mixer"][name], other["mixer"][name])


def test_the_embedding_is_not_scaled_and_the_head_reads_the_last_position(params):
    """With every block's closing projection zeroed the stream is the
    embedding, unscaled, and nothing but the last position's token is read."""
    zero = lambda stack, *names: {n: jnp.zeros_like(a) if n in names else a for n, a in stack.items()}
    bare = dict(params, mixer=zero(params["mixer"], "out_proj"), attention=zero(params["attention"], "wo"),
                experts=zero(params["experts"], "e_down", "s_down"))
    tokens = _tokens(6, rows=2, length=9)
    got = jax.jit(lambda p, x: nemotron_h_apply(p, x, SMALL))(bare, tokens)
    last = np.asarray(params["embed"][tokens[:, -1]], np.float32)
    scale = np.asarray(params["final_norm"], np.float32)
    want = last / np.sqrt((last * last).mean(axis=-1, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(got["hidden"], want, rtol=1e-5)
