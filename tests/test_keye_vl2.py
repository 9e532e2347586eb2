"""The sparse-attention decoder (``model_type`` ``KeyeVL2``) on the deep path,
at a small size on the CPU with every published switch at its value: the
indexer's scores and the selection (every key while a query sees no more
than ``topk``, exactly ``topk`` after, ties to the lower position), both
Pallas kernels in the interpreter against plain ``jax.numpy``, a key block
no query keeps never read, ``LMFeaturizer`` through ``DNNModel.transform``
against the benchmark's plain reference, every planted fault far from it,
the statistics the stage books, and the switches that are not built."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench import run
from chipbench.reference import keye_vl2 as ref
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.featurize.lm import FAMILIES, STATISTICS, LMFeaturizer
from mmlspark_tpu.models import init_keye_vl2, keye_vl2_apply
from mmlspark_tpu.models.keye_vl2 import span_tags
from mmlspark_tpu.observability.tracing import COMPILE_TAGS, get_tracer
from mmlspark_tpu.ops.sparse_attention import dsa_attn, dsa_index, order_keys, unpack_keys, words_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "configs", "keye-vl2-30b-a3b.json")) as f:
    _SPEC = json.load(f)
# the published row with the cell's toy widths: 4 query heads over 2, 8 experts top-2, an indexer of 4 heads of 16
# keeping 16 keys a query, 2 layers; every switch as published
SMALL = {**_SPEC["params"], **_SPEC["dry"]}
OUTPUTS = {"hidden": "h", "logits": "l", "expert_load": "e", "key_selection": "k"}
_NONE = np.iinfo(np.int32).min


def _tokens(seed, rows=3, length=50):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], size=(rows, length)).astype(np.int32)


def _apply(config):
    return jax.jit(lambda p, x: keye_vl2_apply(p, x, config))


def _scores(qi, w, ki):
    """The indexer's score, plainly: sum_j w[t, j] relu(qi[t, j] . ki[s]), float64."""
    r = np.einsum("bthd,bsd->bths", np.asarray(qi, np.float64), np.asarray(ki, np.float64))
    return np.einsum("bth,bths->bts", np.asarray(w, np.float64), np.maximum(r, 0.0))


def _selection(scores, topk):
    """Each query's kept keys by ``lax.top_k`` over the keys it sees."""
    B, S, _ = scores.shape
    seen = np.arange(S)[None, :] <= np.arange(S)[:, None]
    keys = jnp.where(seen, order_keys(jnp.asarray(scores, jnp.float32)), _NONE)
    _, chosen = lax.top_k(keys, min(topk, S))
    kept = np.zeros((B, S, S), bool)
    np.put_along_axis(kept, np.asarray(chosen), True, axis=-1)
    return kept & seen


def _pack(kept):
    """bool (B, S, S) -> the bitmask ``dsa_index`` writes (numpy)."""
    B, S, _ = kept.shape
    words = np.zeros((B, S, words_for(S)), np.uint32)
    s = np.arange(S)
    for key in s:
        column, plane = (key // 4096) * 128 + key % 128, (key // 128) % 32
        words[..., column] |= kept[..., key].astype(np.uint32) << np.uint32(plane)
    return words.view(np.int32)


def _indexer_inputs(seed, B=2, S=300, heads=4, d=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(ks[0], (B, S, heads, d)).astype(jnp.bfloat16)
    ki = jax.random.normal(ks[1], (B, S, d)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[2], (B, S, heads)) * 0.1
    return qi, w, ki


# -- the indexer and the selection --------------------------------------------

def test_order_keys_keep_the_order_of_the_scores():
    x = np.array([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-45, 2.0, 3e38, np.inf], np.float32)
    keys = np.asarray(order_keys(jnp.asarray(x)))
    assert (np.diff(keys[[0, 1, 2, 3, 5, 6, 7, 8]]) > 0).all()
    assert keys[3] == keys[4] == 0  # minus zero is zero
    rng = np.random.default_rng(0)
    a, b = (rng.standard_normal(1000).astype(np.float32) * 10.0 ** rng.integers(-30, 30, 1000) for _ in range(2))
    ka, kb = np.asarray(order_keys(jnp.asarray(a))), np.asarray(order_keys(jnp.asarray(b)))
    assert ((ka < kb) == (a < b)).all() and ((ka == kb) == (a == b)).all()


@pytest.mark.parametrize("S,topk,head_scale", [
    (1100, 300, None), (50, 16, None), (600, 1, None), (257, 600, None),
    (1600, 700, None), (1100, 300, (1.0, -2.0, 0.0, 3.0))],
    ids=["cut_across_key_blocks", "the_cells_dry_size", "one_key", "topk_past_the_row",
         "cut_inside_a_lane_tile", "heads_of_mixed_sign_and_zero"])
def test_the_indexer_keeps_the_topk_of_the_scores_a_query_sees(S, topk, head_scale):
    """Every key while ``t + 1 <= topk``, exactly ``topk`` after, and the
    set is the ``lax.top_k`` of the plain scores over the keys ``s <= t``.
    At 1,600 keys the query blocks see one to four key blocks and a query's
    last key lies anywhere in a 128-lane tile; with each head's weight given
    one sign and one head zeroed, a weight read for another head or another
    query changes the kept set."""
    qi, w, ki = _indexer_inputs(S, S=S)
    if head_scale is not None:
        w = jnp.abs(w) * jnp.asarray(head_scale)
    words = dsa_index(qi, w, ki, topk, interpret=True)
    assert words.shape == (2, S, words_for(S)) and words.dtype == jnp.int32
    got = unpack_keys(words, S)
    want = _selection(_scores(qi, w, ki), topk)
    np.testing.assert_array_equal(got, want)
    t = np.arange(S)
    np.testing.assert_array_equal(got.sum(-1), np.broadcast_to(np.minimum(t + 1, topk), (2, S)))
    assert not (got & (t[None, :] > t[:, None])).any()  # no key after its query
    if head_scale is not None:  # the heads' weights decide the set: in another order they choose others
        assert (got != _selection(_scores(qi, w[..., ::-1], ki), topk)).sum(-1)[:, -1].min() > 4


def test_ties_go_to_the_lower_position():
    """Integer scores tie often; all-zero weights tie every key: then the
    lowest positions are kept, the rule of ``lax.top_k``."""
    S, topk = 700, 300
    qi, _, _ = _indexer_inputs(1, S=S)
    qi = jnp.round(qi.astype(jnp.float32)).astype(jnp.bfloat16)
    ki = jax.random.randint(jax.random.PRNGKey(2), (2, S, 64), 0, 2).astype(jnp.bfloat16)
    w = jnp.ones((2, S, 4))
    scores = _scores(qi, w, ki)
    assert len(np.unique(scores[0, -1])) < S // 4  # many ties at the cut
    got = unpack_keys(dsa_index(qi, w, ki, topk, interpret=True), S)
    np.testing.assert_array_equal(got, _selection(scores, topk))
    flat = unpack_keys(dsa_index(qi, jnp.zeros_like(w), ki, topk, interpret=True), S)
    t = np.arange(S)
    np.testing.assert_array_equal(flat[0], t[None, :] <= np.minimum(t, topk - 1)[:, None])


def test_the_kernel_scores_the_relu_of_each_head_weighted():
    """Scores well apart (one large head weight a query): the kept set is the
    top of ``sum_j w relu(q_j . k)``; without the relu, or with every
    weight alike, another set."""
    S, topk = 200, 20
    qi, w, ki = _indexer_inputs(3, S=S)
    got = unpack_keys(dsa_index(qi, w, ki, topk, interpret=True), S)
    np.testing.assert_array_equal(got, _selection(_scores(qi, w, ki), topk))
    r = np.einsum("bthd,bsd->bths", np.asarray(qi, np.float64), np.asarray(ki, np.float64))
    linear = _selection(np.einsum("bth,bths->bts", np.asarray(w, np.float64), r), topk)
    alike = _selection(_scores(qi, np.ones_like(w), ki), topk)
    assert (got != linear).sum(-1)[:, -1].min() > 4 and (got != alike).sum(-1)[:, -1].min() > 4


# -- attention over the kept keys ---------------------------------------------

def _attention(q, k, v, kept):
    """Softmax over the kept keys, plainly, float32."""
    G = q.shape[2] // k.shape[2]
    k, v = (np.repeat(np.asarray(a, np.float32), G, axis=2) for a in (k, v))
    s = np.einsum("bthd,bshd->bhts", np.asarray(q, np.float32), k) / np.sqrt(q.shape[-1])
    s = np.where(kept[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhts,bshd->bthd", p / p.sum(-1, keepdims=True), v)


def _qkv(seed, S, H=8, KV=2, d=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (2 * jax.random.normal(ks[0], (2, S, H, d))).astype(jnp.bfloat16), *(
        jax.random.normal(kk, (2, S, KV, d)).astype(jnp.bfloat16) for kk in ks[1:])


def test_the_attention_kernel_is_softmax_over_the_kept_keys():
    S = 1100
    qi, w, ki = _indexer_inputs(4, S=S)
    words = dsa_index(qi, w, ki, 300, interpret=True)
    q, k, v = _qkv(5, S)
    out = dsa_attn(q, k, v, words, interpret=True)
    assert out.shape == q.shape and out.dtype == jnp.bfloat16
    want = _attention(q, k, v, unpack_keys(words, S))
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=0.02)
    dense = _attention(q, k, v, np.tril(np.ones((S, S), bool))[None].repeat(2, 0))
    assert np.abs(np.asarray(out, np.float32) - dense).max() > 0.3  # the selection matters


def test_a_key_block_no_query_of_a_block_keeps_is_never_read():
    """Queries keep the first 128 keys and the keys of their own key block
    of 512: no query past 1,023 keeps a key of the block 512..1,023. With
    that block's keys and values NaN, those rows are what they were, to the
    bit."""
    S, bk = 1536, 512
    t, s = np.arange(S)[:, None], np.arange(S)[None, :]
    kept = np.broadcast_to((s <= t) & ((s < 128) | (s // bk == t // bk)), (2, S, S))
    words = jnp.asarray(_pack(kept))
    q, k, v = _qkv(6, S)
    got = np.asarray(dsa_attn(q, k, v, words, interpret=True), np.float32)
    np.testing.assert_allclose(got, _attention(q, k, v, kept), atol=0.02)
    hole = jnp.asarray((np.arange(S) >= bk) & (np.arange(S) < 2 * bk))[None, :, None, None]
    k2, v2 = (jnp.where(hole, jnp.nan, a) for a in (k, v))
    poisoned = np.asarray(dsa_attn(q, k2, v2, words, interpret=True), np.float32)
    late = np.arange(S) >= 2 * bk
    np.testing.assert_array_equal(poisoned[:, late], got[:, late])
    assert np.isnan(poisoned[:, bk:2 * bk]).all(axis=(0, 2, 3)).any()  # the rows that keep the block read it


# -- the model, through the stage ---------------------------------------------

@pytest.fixture(scope="module")
def params():
    return init_keye_vl2(jax.random.PRNGKey(11), SMALL)


@pytest.fixture(scope="module")
def program(params):
    return _apply(SMALL)(params, _tokens(4))


@pytest.fixture(scope="module")
def want(params):
    return ref.forward(params, _tokens(4), SMALL)


@pytest.mark.parametrize("seed,batch", [(0, 2), (1, 3), (3, 1)])
def test_featurizer_agrees_with_the_reference(params, seed, batch):
    """50 tokens keeping 16 keys a query: the cut moves from every key to
    16 at position 16. The toy program and the reference round at the same
    places: a row reads 0 to 1e-7, a planted fault 0.1 and up."""
    tokens = _tokens(seed)
    out = LMFeaturizer(outputCols=OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=batch).transform(Table({"tokens": tokens}))
    want = ref.forward(params, tokens, SMALL)
    assert out["h"].shape == (3, 64) and out["l"].shape == (3, 512) and out["e"].shape == (3, 2, 8)
    assert out["k"].shape == (3, 2, 1 + words_for(50)) and out["k"].dtype == np.int32
    assert ref.relative_gaps(out["h"], want["hidden"]).max() < 1e-3
    assert ref.relative_gaps(out["l"], want["logits"]).max() < 1e-3
    np.testing.assert_array_equal(out["e"], want["expert_load"])
    np.testing.assert_array_equal(out["k"][..., 0], want["keys_kept"])
    assert (out["k"][..., 0] == 16 * 17 // 2 + 34 * 16).all()
    np.testing.assert_array_equal(unpack_keys(out["k"][..., 1:], 50), want["last_kept"])
    assert (want["last_kept"].sum(-1) == 16).all()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference_far_from_the_program(params, program, want, fault):
    """Each departure moves every row by 0.01 at least (1e4 x the program's
    gap), and each that changes a selection moves the last position's kept
    set in some layer."""
    honest = ref.relative_gaps(program["hidden"], want["hidden"]).max()
    faulty = ref.forward(params, _tokens(4), SMALL, fault=fault)
    wrong = np.minimum(ref.relative_gaps(program["hidden"], faulty["hidden"]),
                       ref.relative_gaps(program["logits"], faulty["logits"]))
    assert wrong.min() > 0.01 and wrong.min() > 1e4 * honest
    kept = unpack_keys(np.asarray(program["key_selection"])[..., 1:], 50)
    moved = ref.kept_gaps(faulty["last_kept"], kept).max(axis=1)  # the program's kept keys the fault did not keep
    assert bool(moved.min() > 0.1) is (fault != "dense_attention")


def test_the_float8_control_is_a_different_result(params, program):
    again = _apply(dict(SMALL, product_dtype="bfloat16"))(params, _tokens(4))
    low = _apply(dict(SMALL, product_dtype="float8_e4m3fn"))(params, _tokens(4))
    assert np.array_equal(program["hidden"], again["hidden"])
    assert ref.relative_gaps(low["hidden"], program["hidden"]).min() > 0.05


def test_rows_of_a_batch_do_not_see_each_other(params):
    tokens = _tokens(7, rows=2)
    other = tokens.copy()
    other[0] = _tokens(8, rows=1)[0]
    a, b = _apply(SMALL)(params, tokens), _apply(SMALL)(params, other)
    for name in ("hidden", "logits", "expert_load", "key_selection"):
        assert np.array_equal(a[name][1], b[name][1])
    assert not np.array_equal(a["hidden"][0], b["hidden"][0])


def test_the_seeded_qk_scales_sharpen_the_softmax(params):
    for name in ("q_norm", "k_norm"):
        scale = np.asarray(params["layers"][name], np.float32)
        assert scale.min() >= 1.0 and scale.max() < 1.8 and abs(np.mean(scale ** 2) - 2.0) < 0.25
    assert np.asarray(params["layers"]["norm1"], np.float32).max() < 1.5


def test_an_unknown_fault_is_an_error(params):
    with pytest.raises(ValueError, match="unknown fault"):
        ref.forward(params, _tokens(0, rows=1), SMALL, fault="typo")


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [1]), ("tie_word_embeddings", True),
    ("attention_bias", True), ("decoder_sparse_step", 2), ("norm_topk_prob", False),
    ("sa_config", {**SMALL["sa_config"], "indexer_num_kv_heads": 2}),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("rope_scaling", {"mrope_section": [2, 3, 4], "rope_type": "default"}),
    ("num_local_experts", 16),
])
def test_a_published_switch_that_is_not_built_is_an_error(params, key, value):
    with pytest.raises(ValueError):
        keye_vl2_apply(params, _tokens(0, rows=1), {**SMALL, key: value})


# -- the stage: statistics, spans, the family table ----------------------------

def test_select_stats_books_the_keys_kept_and_seen(params):
    tracer = get_tracer()
    tracer.clear()
    out = LMFeaturizer(outputCols={"hidden": "h"}, modelParams=params, modelConfig=SMALL,
                       batchSize=2).transform(Table({"tokens": _tokens(5)}))
    assert out.columns == ["tokens", "h"]
    spans = {s["name"]: s for s in tracer.export()}
    root = spans["lm.featurize"]
    assert {k: v for k, v in root["tags"].items() if k not in COMPILE_TAGS} == {
        "rows": 3, "tokens": 50, "batch_size": 2, "layers": 2, "experts": 8, "model_type": "KeyeVL2",
        "attention": "grouped", "sparse_topk": 16, "indexer_heads": 4, "indexer_width": 16}
    select, route = spans["lm.select_stats"], spans["lm.route_stats"]
    assert select["parent_id"] == route["parent_id"] == root["span_id"]
    assert select["tags"] == {"keys_kept": 3 * 2 * 680, "keys_visible": 3 * 2 * 1275, "queries": 3 * 2 * 50}
    assert set(route["tags"]) == {"load_peak", "load_mean", "tokens_routed", "experts_empty", "expert_groups"}
    assert route["tags"]["tokens_routed"] == 3 * 2 * 50 * 2 and route["tags"]["expert_groups"] == 2 * 2 * 8
    tags = span_tags(_SPEC["params"])
    assert (tags["sparse_topk"], tags["indexer_heads"], tags["indexer_width"], tags["experts"]) == (2048, 16, 64, 128)


def test_the_statistics_table_routes_each_statistic_to_its_span():
    assert {stat: span for stat, (_, _, span, _) in STATISTICS.items()} == {
        "expert_load": "lm.route_stats", "key_selection": "lm.select_stats"}


def test_a_family_without_sparse_attention_has_no_key_selection():
    from mmlspark_tpu.models.afmoe import init_afmoe

    config, _ = run.sizes(run.load_cell("trinity-mini.score-8k"), True)

    tracer = get_tracer()
    tracer.clear()
    trinity = init_afmoe(jax.random.PRNGKey(0), config)
    tokens = np.random.default_rng(1).integers(0, config["vocab_size"], size=(2, 24)).astype(np.int32)
    LMFeaturizer(outputCols={"hidden": "h"}, modelParams=trinity, modelConfig=config).transform(
        Table({"tokens": tokens}))
    assert "lm.select_stats" not in {s["name"] for s in tracer.export()}
    with pytest.raises(ValueError, match="no sparse attention"):
        LMFeaturizer(outputCols={"key_selection": "k"}, modelParams=trinity, modelConfig=config).transform(
            Table({"tokens": tokens}))


def test_the_stage_documents_itself_from_the_family_table(params):
    assert FAMILIES["KeyeVL2"] == ("mmlspark_tpu.models.keye_vl2", "keye_vl2_apply", "init_keye_vl2")
    described = [LMFeaturizer.__doc__, LMFeaturizer._param_specs["modelConfig"].doc]
    assert all("'KeyeVL2': mmlspark_tpu.models.keye_vl2" in text for text in described)
    assert "mmlspark_tpu.models.keye_vl2.init_keye_vl2" in LMFeaturizer._param_specs["modelParams"].doc
    assert "key_selection" in LMFeaturizer._param_specs["outputCols"].doc
    with pytest.raises(ValueError, match="outputCols"):
        LMFeaturizer(outputCols={"keys": "k"}, modelParams=params, modelConfig=SMALL).transform(
            Table({"tokens": _tokens(0)}))


def test_named_scopes_and_kernels_are_in_the_lowered_program(params):
    text = _apply(SMALL).lower(params, _tokens(0)).as_text(debug_info=True)
    for scope in ("dsa_indexer", "dsa_attention", "moe_route", "moe_experts", "lm_head"):
        assert scope in text, scope
