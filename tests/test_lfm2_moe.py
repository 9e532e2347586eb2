"""The short-convolution decoder (``model_type`` ``lfm2_moe``) on the deep
path, at a small size on the CPU: ``LMFeaturizer`` through
``DNNModel.transform`` against the benchmark's plain reference, for lists of
layer kinds that hold both operators, a dense and an expert layer and an
irregular tail; rows that do not see each other; every planted fault far
from the program; the tied head and the expert block with no shared expert
through the shared code; the convolution the hybrid family shares; the
spans, the scopes and the family table the stage documents itself from."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2_moe as ref
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.featurize.lm import FAMILIES, LMFeaturizer
from mmlspark_tpu.models import init_lfm2_moe, lfm2_moe_apply
from mmlspark_tpu.models.lfm2_moe import conv_state_width, layer_kinds, span_tags
from mmlspark_tpu.models.moe_decoder import causal_conv, last_position, norm
from mmlspark_tpu.observability.tracing import COMPILE_TAGS, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C, A = "conv", "full_attention"
PUBLISHED = [C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, A, C, C]  # 24 layers: 18 conv, 6 attention
SMALL = dict(
    model_type="lfm2_moe", layer_types=PUBLISHED, layers=7, num_dense_layers=2, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=24,
    num_experts=8, num_experts_per_tok=2, routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
    conv_L_cache=3, conv_bias=False, rope_theta=1000000, norm_eps=1e-5, vocab_size=512,
    interpret=True,  # the attention kernel, on a backend that is no TPU
)
ALL_OUTPUTS = {"hidden": "h", "logits": "l", "expert_load": "e"}


def _tokens(seed, rows=5, length=50):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], size=(rows, length)).astype(np.int32)


def _apply(config):
    return jax.jit(lambda p, x: lfm2_moe_apply(p, x, config))


@pytest.fixture(scope="module")
def params():
    return init_lfm2_moe(jax.random.PRNGKey(11), SMALL)


@pytest.fixture(scope="module")
def program(params):
    return _apply(SMALL)(params, _tokens(4))


def _agrees(got, want, length, config):
    """All rows but one (top-2 of 8 at 40-50 tokens: a routing tie flips an
    assignment in some row, and the layers after it follow): a toy row reads
    0-0.02 on the last position, a planted fault 0.07-1.5 on every row."""
    assert np.sort(ref.relative_gaps(got["hidden"], want["hidden"]))[-2] < 0.03
    assert np.sort(ref.relative_gaps(got["logits"], want["logits"]))[-2] < 0.03
    routed = length * config["num_experts_per_tok"]
    a_rows_widest = ref.load_gaps(got["expert_load"], want["expert_load"], routed).max(axis=1, initial=0.0)
    assert np.sort(a_rows_widest)[-2] <= 0.03
    # no token is dropped: every expert layer of every row routed S x k
    assert (np.asarray(got["expert_load"]).sum(axis=-1) == routed).all()


# -- the model, through the stage ---------------------------------------------

@pytest.mark.parametrize("seed,batch", [(0, 2), (1, 5), (3, 3)])
def test_featurizer_agrees_with_the_reference_on_all_three_outputs(params, seed, batch):
    """Seven layers ``c c a c c c a``: both kinds of operator, two dense
    layers and five expert layers, the attention layers among the experts."""
    tokens = _tokens(seed)
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=batch).transform(Table({"tokens": tokens}))
    assert out["h"].shape == (5, 64) and out["l"].shape == (5, 512) and out["e"].shape == (5, 5, 8)
    assert out["h"].dtype == np.float32 and out["l"].dtype == np.float32 and out["e"].dtype == np.int32
    _agrees({"hidden": out["h"], "logits": out["l"], "expert_load": out["e"]}, ref.forward(params, tokens, SMALL),
            50, SMALL)


@pytest.mark.parametrize("layer_types,layers,dense", [
    (PUBLISHED, 24, 2), (PUBLISHED, 16, 2), ([A, C, C, A, A, C], 6, 3), ([A, A, A], 3, 1), ([C, C, C], 3, 0),
    ([C, A], 2, 2), ([A, C, A, C], 4, 9)],
    ids=["the_published_list_with_its_irregular_tail", "the_cells_cut", "attention_first_and_among_the_dense",
         "attention_alone", "convolution_alone_no_dense_layer", "dense_layers_alone", "more_dense_layers_than_layers"])
def test_any_list_of_the_two_kinds_runs_and_agrees_with_the_reference(layer_types, layers, dense):
    config = dict(SMALL, layer_types=layer_types, layers=layers, num_dense_layers=dense)
    weights = init_lfm2_moe(jax.random.PRNGKey(3), config)
    kinds = layer_types[:layers]
    held = min(dense, layers)
    assert weights["conv"]["in_proj"].shape[0] == kinds.count(C) and weights["attention"]["wq"].shape[0] == kinds.count(A)
    assert weights["dense"]["w_up"].shape[0] == held and weights["moe"]["e_up"].shape[0] == layers - held
    tokens = _tokens(8, rows=4, length=40)
    got = _apply(config)(weights, tokens)
    assert got["expert_load"].shape == (4, layers - held, 8)
    _agrees(got, ref.forward(weights, tokens, config), 40, config)


def test_an_operator_is_looked_up_by_its_place_in_its_kinds_stack():
    """The second attention layer's weights in the first's place, or the
    convolution layers' in reverse, are another result."""
    config = dict(SMALL, layer_types=[C, A, C, A, C], layers=5, num_dense_layers=1)
    weights = init_lfm2_moe(jax.random.PRNGKey(2), config)
    tokens = _tokens(8, rows=4, length=40)
    got = _apply(config)(weights, tokens)
    for stack in ("attention", "conv"):
        swapped = dict(weights, **{stack: jax.tree.map(lambda a: a[::-1], weights[stack])})
        other = _apply(config)(swapped, tokens)
        assert ref.relative_gaps(other["hidden"], got["hidden"]).min() > 0.1, stack


@pytest.mark.parametrize("layer_types,layers,reason", [
    ([C, "sliding_attention", A], 3, "sliding_attention"), ([C, A], 3, "holds 2 layers")])
def test_an_unknown_kind_or_a_list_too_short_is_an_error(layer_types, layers, reason):
    config = dict(SMALL, layer_types=layer_types, layers=layers)
    for entry in (layer_kinds, span_tags, lambda c: init_lfm2_moe(jax.random.PRNGKey(0), c)):
        with pytest.raises(ValueError, match=reason):
            entry(config)
    if "sliding" in reason:
        with pytest.raises(ValueError, match=reason):
            ref.layers(config)


def test_no_tap_and_no_key_crosses_a_rows_start(params):
    """Four rows side by side in every convolution and every attention call:
    other tokens in row 0 leave the other rows' outputs bit-equal, and a
    row's own outputs do not depend on where in the batch it stands."""
    tokens = _tokens(6, rows=4)
    other = tokens.copy()
    other[0] = _tokens(7, rows=1)[0]
    first, second = _apply(SMALL)(params, tokens), _apply(SMALL)(params, other)
    moved = _apply(SMALL)(params, tokens[::-1].copy())
    for name in ALL_OUTPUTS:
        assert np.array_equal(first[name][1:], second[name][1:]), name
        assert not np.array_equal(first[name][0], second[name][0]), name
        assert np.array_equal(first[name], np.asarray(moved[name])[::-1]), name
    # and the first position of a row sees itself alone: a row of one token equals that token alone in a longer batch
    alone = _apply(SMALL)(params, tokens[:, :1])
    again = _apply(SMALL)(params, np.concatenate([tokens[1:, :1], tokens[:1, :1]]))
    assert np.array_equal(alone["hidden"][0], again["hidden"][-1])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference_far_from_the_program(params, program, fault):
    tokens = _tokens(4)
    honest = ref.relative_gaps(program["hidden"], ref.forward(params, tokens, SMALL)["hidden"])
    faulty = ref.forward(params, tokens, SMALL, fault=fault)
    wrong = ref.relative_gaps(program["hidden"], faulty["hidden"])
    if fault in ("untied_head", "head_inputs_3_mantissa_bits"):  # nothing before the head moves; the logits alone do
        assert np.sort(wrong)[-2] < 0.03
        assert ref.relative_gaps(faulty["logits"], ref.head_of(params, faulty["hidden"])).min() > 0.02
        assert ref.relative_gaps(program["logits"], faulty["logits"]).min() > 0.02
        return
    thin = fault == "weights_from_biased_scores"  # a bias of 0.1 on scores near 0.5 moves a weight by a tenth
    assert wrong.min() > (0.03 if thin else 0.2) and wrong.min() > 2 * np.sort(honest)[-2]


def test_an_unknown_fault_is_an_error(params):
    with pytest.raises(ValueError, match="unknown fault"):
        ref.forward(params, _tokens(0, rows=1), SMALL, fault="typo")


def test_narrower_product_inputs_are_a_different_result(params, program):
    tokens = _tokens(4)
    again = _apply(dict(SMALL, product_dtype="bfloat16"))(params, tokens)
    low = _apply(dict(SMALL, product_dtype="float8_e4m3fn"))(params, tokens)
    assert np.array_equal(program["hidden"], again["hidden"])
    assert 0.02 < ref.relative_gaps(low["hidden"], program["hidden"]).min()
    assert ref.relative_gaps(low["logits"], ref.head_of(params, low["hidden"])).min() > 0.02


# -- the tied head, and the expert block with no shared expert -----------------

def test_the_head_is_the_embedding_transposed_and_the_tree_holds_it_once(params, program):
    assert "head" not in params and set(params) == {"embed", "final_norm", "conv", "attention", "dense", "moe"}
    assert ref.relative_gaps(program["logits"], ref.head_of(params, program["hidden"])).max() < 1e-5
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)
    want = bf(program["hidden"]) @ bf(params["embed"]).T
    np.testing.assert_allclose(program["logits"], want, rtol=1e-4, atol=1e-5)
    # a configuration that says it is not tied draws a head and applies it
    untied = dict(SMALL, tie_word_embeddings=False)
    weights = init_lfm2_moe(jax.random.PRNGKey(11), untied)
    assert weights["head"].shape == (64, 512) and np.array_equal(weights["embed"], params["embed"])
    got = _apply(untied)(weights, _tokens(4))
    assert np.array_equal(got["hidden"], program["hidden"])
    np.testing.assert_allclose(got["logits"], bf(got["hidden"]) @ bf(weights["head"]), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("head", ["untied", "tied"])
def test_last_position_applies_the_head_the_tree_holds(head):
    rng = np.random.default_rng(3)
    tree = {"embed": jnp.asarray(rng.normal(size=(40, 16)), jnp.bfloat16),
            "final_norm": jnp.asarray(rng.uniform(0.5, 1.5, size=16), jnp.bfloat16)}
    if head == "untied":
        tree["head"] = jnp.asarray(rng.normal(size=(16, 40)), jnp.bfloat16)
    h = jnp.asarray(rng.normal(size=(3, 7, 16)), jnp.bfloat16)
    hidden, logits = jax.jit(lambda p, h: last_position(p, h, 1e-5, jnp.bfloat16))(tree, h)
    np.testing.assert_allclose(hidden, norm(h[:, -1], tree["final_norm"], 1e-5), rtol=1e-6)
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)
    matrix = bf(tree["head"]) if head == "untied" else bf(tree["embed"]).T
    assert logits.shape == (3, 40) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, bf(hidden) @ matrix, rtol=1e-4, atol=1e-5)
    low = jax.jit(lambda p, h: last_position(p, h, 1e-5, jnp.dtype("float8_e4m3fn")))(tree, h)[1]
    assert ref.relative_gaps(low, logits).min() > 0.01  # product_dtype reaches either head


def test_the_expert_block_holds_no_shared_expert(params):
    assert set(params["moe"]) == {"norm", "router", "router_bias", "e_gate", "e_up", "e_down"}
    assert params["moe"]["router_bias"].dtype == jnp.float32 and params["moe"]["e_up"].shape == (5, 8, 64, 24)


# -- the convolution the two families share ------------------------------------

@pytest.mark.parametrize("taps,bias", [(4, True), (3, False)], ids=["the_hybrids_four_with_a_bias", "three_and_none"])
def test_the_shared_convolution_is_the_shifted_multiply_adds_it_replaced(taps, bias):
    """``nemotron_h._mixer`` held these lines itself until PR 37; through the
    shared function its result is bit for bit what they gave."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(3, 20, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, taps)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=12), jnp.bfloat16) if bias else None

    def as_it_was(x, w, b):
        K, S = w.shape[1], x.shape[1]
        ahead = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        wide = w.astype(jnp.float32)
        out = sum(ahead[:, j:j + S] * wide[:, j] for j in range(K))
        return out + b.astype(jnp.float32) if b is not None else out

    got = jax.jit(causal_conv)(x, w, b)
    assert got.dtype == jnp.float32 and np.array_equal(got, jax.jit(as_it_was)(x, w, b))
    # against the sum written out in float64: tap K-1 meets the position itself, zeros before a row's first
    wide, padded = np.asarray(w, np.float64), np.concatenate([np.zeros((3, taps - 1, 12)), np.asarray(x, np.float64)], 1)
    want = sum(padded[:, j:j + 20] * wide[:, j] for j in range(taps)) + (np.asarray(b, np.float64) if bias else 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], np.asarray(x)[:, 0] * wide[:, -1] + (np.asarray(b, np.float64) if bias else 0.0),
                               rtol=1e-5, atol=1e-6)


def test_the_references_convolution_is_causal_and_its_planted_fault_looks_ahead():
    rng = np.random.default_rng(1)
    z, taps = rng.normal(size=(10, 3)), rng.normal(size=(3, 3))
    got = np.asarray(ref.conv(jnp.asarray(z, jnp.float32), jnp.asarray(taps, jnp.float32)))
    ahead = np.concatenate([np.zeros((2, 3)), z])
    want = np.stack([sum(taps[:, j] * ahead[t + j] for j in range(3)) for t in range(10)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.allclose(got[0], taps[:, 2] * z[0], atol=1e-6)  # the first position sees itself alone
    reverse = np.asarray(ref.conv(jnp.asarray(z, jnp.float32), jnp.asarray(taps, jnp.float32), reverse=True))
    behind = np.concatenate([z, np.zeros((2, 3))])
    np.testing.assert_allclose(reverse, np.stack([sum(taps[:, j] * behind[t + 2 - j] for j in range(3))
                                                  for t in range(10)]), rtol=1e-5, atol=1e-6)
    assert np.allclose(reverse[-1], taps[:, 2] * z[-1], atol=1e-6)


def test_the_reference_imports_nothing_of_the_program_and_scans_no_stack():
    with open(os.path.join(ROOT, "chipbench", "reference", "lfm2_moe.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mmlspark_tpu" not in code and "lax.scan" not in code and "pallas" not in code
    assert "astype(jnp.bfloat16)" not in code and float(ref._bf(jnp.float32(1.0 + 2.0 ** -9))) == 1.0
    assert 'default_matmul_precision("highest")' in code
    assert ref.layers(SMALL) == [("conv", 0, "dense", 0), ("conv", 1, "dense", 1), ("attention", 0, "moe", 0),
                                 ("conv", 2, "moe", 1), ("conv", 3, "moe", 2), ("conv", 4, "moe", 3),
                                 ("attention", 1, "moe", 4)]


# -- the configuration, the family table, the spans, the scopes ----------------

def test_layer_kinds_of_the_published_list_and_of_the_cut():
    whole = layer_kinds(dict(SMALL, layers=24))
    assert whole[0] == [False, False] and len(whole[1]) == 22 and sum(whole[1]) == 6
    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2-8b-a1b.json")) as f:
        spec = json.load(f)
    assert spec["layer_types"] == PUBLISHED and spec["num_hidden_layers"] == len(PUBLISHED) == 24
    dense, moe = layer_kinds(spec["params"])
    assert spec["params"]["layers"] == 16 and dense == [False, False] and len(moe) == 14 and sum(moe) == 4
    assert layer_kinds(dict(SMALL, layers=0)) == ([], [])


def test_the_stage_documents_itself_from_the_family_table(params):
    assert set(FAMILIES) >= {"afmoe", "joyai_llm_flash", "nemotron_h", "lfm2_moe"}
    assert FAMILIES["lfm2_moe"] == ("mmlspark_tpu.models.lfm2_moe", "lfm2_moe_apply", "init_lfm2_moe")
    described = [LMFeaturizer.__doc__, LMFeaturizer._param_specs["modelConfig"].doc]
    assert all("'lfm2_moe': mmlspark_tpu.models.lfm2_moe" in text for text in described)
    assert "mmlspark_tpu.models.lfm2_moe.init_lfm2_moe" in LMFeaturizer._param_specs["modelParams"].doc
    with pytest.raises(ValueError, match="mmlspark_tpu.models.lfm2_moe.init_lfm2_moe"):
        LMFeaturizer().transform(Table({"tokens": _tokens(0)}))
    with pytest.raises(ValueError, match="'gpt': one of .*'lfm2_moe'"):
        LMFeaturizer(modelParams=params, modelConfig=dict(SMALL, model_type="gpt")).transform(
            Table({"tokens": _tokens(0)}))


def test_spans_of_a_transform(params):
    tracer = get_tracer()
    tracer.clear()
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=2).transform(Table({"tokens": _tokens(5)}))
    spans = {s["name"]: s for s in tracer.export()}
    root = spans["lm.featurize"]
    assert {k: v for k, v in root["tags"].items() if k not in COMPILE_TAGS} == {
        "rows": 5, "tokens": 50, "batch_size": 2, "layers": 7, "experts": 8, "model_type": "lfm2_moe",
        "attention": "grouped", "head_dim": 16, "conv_layers": 5, "attention_layers": 2, "dense_layers": 2,
        "expert_layers": 5, "conv_state_width": 3 * 64}
    assert spans["dnn.transform"]["parent_id"] == root["span_id"]
    stats = spans["lm.route_stats"]["tags"]
    assert stats["tokens_routed"] == 5 * 5 * 50 * 2 and stats["expert_groups"] == 3 * 5 * 8
    assert out["e"].shape == (5, 5, 8)
    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2-8b-a1b.json")) as f:
        published = json.load(f)["params"]
    tags = span_tags(published)
    assert tags["conv_state_width"] == conv_state_width(published) == 6144 and tags["head_dim"] == 64
    assert (tags["conv_layers"], tags["attention_layers"], tags["dense_layers"], tags["expert_layers"]) == (12, 4, 2, 14)


def test_named_scopes_are_in_the_lowered_program(params):
    text = _apply(SMALL).lower(params, _tokens(0)).as_text(debug_info=True)
    for scope in ("conv_in", "short_conv", "conv_out", "attn_full", "moe_route", "moe_experts", "lm_head"):
        assert scope in text, scope


def test_weights_come_from_the_key_and_the_query_norm_is_sharp(params):
    leaves = jax.tree.leaves(params)
    assert all(isinstance(a, jax.Array) for a in leaves)
    wide = {f"{kind}.{name}" for kind in ("conv", "attention", "dense", "moe") for name, a in params[kind].items()
            if a.dtype == jnp.float32}
    assert wide == {"moe.router_bias"} and {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert params["conv"]["in_proj"].shape == (5, 64, 192) and params["conv"]["conv_w"].shape == (5, 64, 3)
    assert params["conv"]["out_proj"].shape == (5, 64, 64) and "conv_b" not in params["conv"]
    assert params["attention"]["wq"].shape == (2, 64, 64) and params["attention"]["wk"].shape == (2, 64, 32)
    assert params["attention"]["q_norm"].shape == (2, 16) and params["dense"]["w_up"].shape == (2, 64, 96)
    q, k = (np.asarray(params["attention"][n], np.float32) for n in ("q_norm", "k_norm"))
    assert (2.0 <= q).all() and (q < 6.0).all() and (0.5 <= k).all() and (k < 1.5).all()
    again = init_lfm2_moe(jax.random.PRNGKey(11), SMALL)
    other = init_lfm2_moe(jax.random.PRNGKey(8), SMALL)
    for stack, name in (("conv", "in_proj"), ("conv", "conv_w"), ("attention", "wq"), ("moe", "router_bias")):
        assert np.array_equal(params[stack][name], again[stack][name])
        assert not np.array_equal(params[stack][name], other[stack][name])


def test_the_embedding_is_not_scaled_and_the_head_reads_the_last_position(params):
    """With every operator's and feed-forward's closing projection zeroed the
    stream is the embedding, unscaled, and nothing but the last position's
    token is read."""
    zero = lambda stack, *names: {n: jnp.zeros_like(a) if n in names else a for n, a in stack.items()}
    bare = dict(params, conv=zero(params["conv"], "out_proj"), attention=zero(params["attention"], "wo"),
                dense=zero(params["dense"], "w_down"), moe=zero(params["moe"], "e_down"))
    tokens = _tokens(6, rows=2, length=9)
    got = _apply(SMALL)(bare, tokens)
    last = np.asarray(params["embed"][tokens[:, -1]], np.float32)
    scale = np.asarray(params["final_norm"], np.float32)
    want = last / np.sqrt((last * last).mean(axis=-1, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(got["hidden"], want, rtol=1e-5)
