"""The ``afmoe`` decoder on the deep path, at a small size on the CPU:
``LMFeaturizer`` through ``DNNModel.transform`` against the benchmark's
plain reference, the blocked attention against dense masked attention, the
sorted top-k experts against every expert under a mask, and the layer kinds
of the benchmark's cut."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import afmoe as ref
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.featurize.lm import LMFeaturizer
from mmlspark_tpu.models.afmoe import afmoe_apply, init_afmoe, layer_kinds
from mmlspark_tpu.observability.tracing import get_tracer
from mmlspark_tpu.ops.attention import blocked_attention
from mmlspark_tpu.models.moe_decoder import relu2
from mmlspark_tpu.ops.expert_parallel import moe_topk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
SMALL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_shared_experts=1, num_dense_layers=2, sliding_window=8, rope_theta=10000,
    rms_norm_eps=1e-5, route_scale=2.826, vocab_size=512, layer_types=KINDS * 2, layers=6,
    interpret=True,  # the attention kernel, on a backend that is no TPU
)
ALL_OUTPUTS = {"hidden": "h", "logits": "l", "expert_load": "e"}


def _tokens(seed, rows=5, length=50):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], size=(rows, length)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return init_afmoe(jax.random.PRNGKey(7), SMALL)


# -- the model, through the stage ---------------------------------------------

@pytest.mark.parametrize("seed,batch", [(0, 2), (1, 5), (2, 3)])
def test_featurizer_agrees_with_the_reference_on_all_three_outputs(params, seed, batch):
    tokens = _tokens(seed)
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=batch).transform(Table({"tokens": tokens}))
    want = ref.forward(params, tokens, SMALL)
    assert out["h"].shape == (5, 64) and out["l"].shape == (5, 512) and out["e"].shape == (5, 4, 8)
    assert out["h"].dtype == np.float32 and out["l"].dtype == np.float32 and out["e"].dtype == np.int32
    assert ref.relative_gaps(out["h"], want["hidden"]).max() < 0.03
    assert ref.relative_gaps(out["l"], want["logits"]).max() < 0.03
    assert ref.load_gaps(out["e"], want["expert_load"], 50 * 2).max() <= 0.02
    # no token is dropped: every expert layer of every row routed S x k
    assert (out["e"].sum(axis=-1) == 50 * 2).all()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference_far_from_the_program(params, fault):
    tokens = _tokens(3)
    got = jax.jit(lambda p, x: afmoe_apply(p, x, SMALL))(params, tokens)
    wrong = ref.forward(params, tokens, SMALL, fault=fault)
    assert ref.relative_gaps(got["hidden"], wrong["hidden"]).min() > 0.1


def test_an_unknown_fault_is_an_error(params):
    with pytest.raises(ValueError, match="unknown fault"):
        ref.forward(params, _tokens(0, rows=1), SMALL, fault="typo")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference", "afmoe.py")) as f:
        assert "mmlspark_tpu" not in f.read().split('"""', 2)[2]


def test_narrower_product_inputs_are_a_different_result(params):
    tokens = _tokens(4)
    stated = jax.jit(lambda p, x: afmoe_apply(p, x, SMALL))(params, tokens)
    again = jax.jit(lambda p, x: afmoe_apply(p, x, dict(SMALL, product_dtype="bfloat16")))(params, tokens)
    low = jax.jit(lambda p, x: afmoe_apply(p, x, dict(SMALL, product_dtype="float8_e4m3fn")))(params, tokens)
    assert np.array_equal(stated["hidden"], again["hidden"])
    assert 0.02 < ref.relative_gaps(low["hidden"], stated["hidden"]).min()


def test_outputs_follow_output_cols_and_the_load_column_is_dropped_again(params):
    table = Table({"tokens": _tokens(0), "id": np.arange(5)})
    out = LMFeaturizer(modelParams=params, modelConfig=SMALL, batchSize=4).transform(table)
    assert set(out.columns) == {"tokens", "id", "features"}
    with pytest.raises(ValueError, match="outputCols"):
        LMFeaturizer(outputCols={"probs": "p"}, modelParams=params, modelConfig=SMALL).transform(table)
    with pytest.raises(ValueError, match="modelParams and modelConfig"):
        LMFeaturizer(modelParams=params).transform(table)


def test_spans_of_a_transform(params):
    tracer = get_tracer()
    tracer.clear()
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=2).transform(Table({"tokens": _tokens(5)}))
    spans = {s["name"]: s for s in tracer.export()}
    root = spans["lm.featurize"]
    assert root["tags"] == {"rows": 5, "tokens": 50, "batch_size": 2, "layers": 6, "experts": 8,
                            "model_type": "afmoe", "attention": "grouped"}
    assert spans["dnn.transform"]["parent_id"] == root["span_id"]
    stats = spans["lm.route_stats"]["tags"]
    per_dispatch = np.add.reduceat(out["e"].astype(np.int64), [0, 2, 4], axis=0)
    assert stats["load_peak"] == per_dispatch.max(axis=-1).sum()
    assert stats["load_mean"] == pytest.approx(5 * 4 * 50 * 2 / 8)
    assert stats["tokens_routed"] == 5 * 4 * 50 * 2
    assert stats["load_peak"] >= stats["load_mean"]
    assert stats["expert_groups"] == 3 * 4 * 8  # dispatches x expert layers x experts
    assert stats["experts_empty"] == (per_dispatch == 0).sum()


def test_named_scopes_are_in_the_lowered_program(params):
    text = jax.jit(lambda p, x: afmoe_apply(p, x, SMALL)).lower(params, _tokens(0)).as_text(debug_info=True)
    for scope in ("attn_window", "attn_full", "moe_route", "moe_experts", "lm_head"):
        assert scope in text, scope


def test_weights_are_bfloat16_on_the_device_and_come_from_the_key(params):
    leaves = jax.tree.leaves(params)
    assert all(isinstance(a, jax.Array) for a in leaves)
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert params["moe"]["router_bias"].dtype == jnp.float32  # the one float32 buffer
    assert params["moe"]["e_gate"].shape == (4, 8, 64, 32) and params["dense"]["w_up"].shape == (2, 64, 96)
    again = init_afmoe(jax.random.PRNGKey(7), SMALL)
    other = init_afmoe(jax.random.PRNGKey(8), SMALL)
    assert np.array_equal(params["head"], again["head"]) and not np.array_equal(params["head"], other["head"])


# -- the benchmark's cut ------------------------------------------------------

def test_layer_kinds_of_the_cut_are_the_published_lists_first_six():
    with open(os.path.join(ROOT, "chipbench", "configs", "trinity-mini.json")) as f:
        spec = json.load(f)
    config = spec["params"]
    assert config["layers"] == 6 and spec["num_hidden_layers"] == 32 and len(config["layer_types"]) == 32
    dense, moe = layer_kinds(config)
    assert [k == "sliding_attention" for k in config["layer_types"][:6]] == dense + moe
    assert dense == [True, True] and moe == [True, False, True, True]  # one whole period, three to one
    assert [(s, i) for s, i, _ in ref.layer_kinds(config)] == [
        ("dense", 0), ("dense", 1), ("moe", 0), ("moe", 1), ("moe", 2), ("moe", 3)]
    assert [sl for _, _, sl in ref.layer_kinds(config)] == dense + moe


# -- blocked attention --------------------------------------------------------

def _dense_attention(q, k, v, window):
    B, S, H, d = q.shape
    G = H // k.shape[2]
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (j > i - window)
    out = np.zeros(q.shape[:3] + v.shape[3:])  # a value head may have another width than a key head
    for h in range(H):
        scores = np.einsum("bsd,btd->bst", q[:, :, h], k[:, :, h // G]) / np.sqrt(d)
        scores = np.where(seen, scores, -np.inf)
        weights = np.exp(scores - scores.max(axis=-1, keepdims=True))
        out[:, :, h] = np.einsum("bst,btd->bsd", weights / weights.sum(axis=-1, keepdims=True), v[:, :, h // G])
    return out


@pytest.mark.parametrize("window", [None, 1, 7, 16, 40, 1000])
@pytest.mark.parametrize("length,block", [(50, 16), (64, 16), (9, 512)])
def test_blocked_attention_against_dense_masked_attention(window, length, block):
    rng = np.random.default_rng(length)
    q = jnp.asarray(rng.normal(size=(2, length, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, length, 2, 8)), jnp.float32) for _ in range(2))
    got = jax.jit(lambda *a: blocked_attention(*a, window=window, block=block, interpret=True))(q, k, v)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, _dense_attention(q, k, v, window), atol=2e-5)


def test_blocked_attention_in_bfloat16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 40, 4, 8)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(1, 40, 2, 8)), jnp.bfloat16) for _ in range(2))
    for window in (5, None):
        got = blocked_attention(q, k, v, window=window, block=16, interpret=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float64), _dense_attention(q, k, v, window), atol=0.03)


@pytest.mark.parametrize("group", [1, 3, 4, 7, 16])
def test_blocked_attention_runs_every_head_group_at_the_default_block(group):
    """300 positions pad to a key/value block of 384, three query blocks of
    128, whatever the group: 28 heads over 4 (7), 40 over 8 (5) and 32 over
    2 (16: a step's score product has 2,048 rows) are published shapes."""
    rng = np.random.default_rng(group)
    q = jnp.asarray(rng.normal(size=(1, 300, 2 * group, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 300, 2, 8)), jnp.float32) for _ in range(2))
    got = jax.jit(lambda *a: blocked_attention(*a, interpret=True))(q, k, v)
    np.testing.assert_allclose(got, _dense_attention(q, k, v, None), atol=2e-5)


def test_blocked_attention_refuses_heads_that_do_not_group():
    q = jnp.zeros((1, 8, 3, 4))
    with pytest.raises(ValueError, match="grouped heads"):
        blocked_attention(q, jnp.zeros((1, 8, 2, 4)), jnp.zeros((1, 8, 2, 4)))
    with pytest.raises(ValueError, match="whole query blocks"):
        blocked_attention(jnp.zeros((1, 300, 2, 4)), *[jnp.zeros((1, 300, 2, 4))] * 2, block=200)


# -- top-k experts ------------------------------------------------------------

def _every_expert_masked(x, choose, weigh, experts, k, scale):
    x, choose, weigh = (np.asarray(a, np.float64) for a in (x, choose, weigh))
    chosen = np.argsort(-choose, axis=1, kind="stable")[:, :k]
    picked = np.take_along_axis(weigh, chosen, axis=1)
    weights = picked / picked.sum(axis=1, keepdims=True) * scale
    y = np.zeros_like(x)
    for e in range(choose.shape[1]):
        gate, up, down = (np.asarray(experts[n][e], np.float64) for n in ("gate", "up", "down"))
        a = x @ gate
        out = (a / (1 + np.exp(-a)) * (x @ up)) @ down
        y += (weights * (chosen == e)).sum(axis=1)[:, None] * out
    return y, chosen


def _per_token_loop(x, choose, weigh, experts, k, scale, activation):
    """Two-matrix experts, a token and an expert at a time."""
    x, choose, weigh = (np.asarray(a, np.float64) for a in (x, choose, weigh))
    up, down = (np.asarray(experts[n], np.float64) for n in ("up", "down"))
    y = np.zeros_like(x)
    for t in range(len(x)):
        chosen = np.argsort(-choose[t], kind="stable")[:k]
        weights = weigh[t, chosen] / weigh[t, chosen].sum() * scale
        for e, w in zip(chosen, weights):
            y[t] += w * (activation(x[t] @ up[e]) @ down[e])
    return y


@pytest.mark.parametrize("k,gated", [(1, True), (2, True), (3, True), (1, False), (2, False), (3, False)])
def test_moe_topk_against_every_expert_under_a_mask(k, gated):
    """Expert 5 gets no token, expert 2 gets half of them (every even
    token's first choice), and nothing is dropped. ``gated``: the three
    SwiGLU matrices; not: two matrices with relu(x)^2 between them, against a
    loop a token and an expert at a time."""
    rng = np.random.default_rng(k)
    T, D, F, E = 64, 16, 8, 6
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    experts = {"gate": jnp.asarray(rng.normal(size=(E, D, F)) / 4, jnp.float32),
               "up": jnp.asarray(rng.normal(size=(E, D, F)) / 4, jnp.float32),
               "down": jnp.asarray(rng.normal(size=(E, F, D)) / 3, jnp.float32)}
    if not gated:
        del experts["gate"]
    weigh = rng.uniform(0.1, 0.9, size=(T, E))
    bias = np.zeros((T, E))
    bias[:, 5] = -10.0  # never chosen
    bias[::2, 2] = 10.0  # always chosen by every other token
    bias[1::2, 2] = -10.0
    choose = jnp.asarray(weigh + bias, jnp.float32)
    if gated:
        y, chosen = jax.jit(lambda *a: moe_topk(*a, k, 2.5))(x, choose, jnp.asarray(weigh, jnp.float32), experts)
        want, want_chosen = _every_expert_masked(x, choose, weigh, experts, k, 2.5)
    else:
        y, chosen = jax.jit(lambda *a: moe_topk(*a, k, 2.5, relu2))(x, choose, jnp.asarray(weigh, jnp.float32), experts)
        want = _per_token_loop(x, choose, weigh, experts, k, 2.5, lambda a: np.maximum(a, 0) ** 2)
        want_chosen = np.argsort(-np.asarray(choose, np.float64), axis=1, kind="stable")[:, :k]
        silu_too = jax.jit(lambda *a: moe_topk(*a, k, 2.5))(x, choose, jnp.asarray(weigh, jnp.float32), experts)[0]
        assert np.abs(np.asarray(silu_too) - want).max() > 0.05  # the activation is the caller's, not a default's
    load = np.bincount(np.asarray(chosen).ravel(), minlength=E)
    assert load[5] == 0 and load[2] == T // 2 and load.sum() == T * k
    assert np.array_equal(np.sort(chosen, axis=1), np.sort(want_chosen, axis=1))
    assert y.dtype == jnp.float32 and chosen.dtype == jnp.int32
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("width", [8, 1856, 768, 1024, 128])
def test_moe_topk_hands_the_grouped_product_the_matrices_the_tree_holds(width):
    """One path whatever the inner width: 1,856 = 14.5 x 128, off the lane
    grid, meets ``ragged_dot`` as 1,856, as 768 and 1,024 on it do. A storage
    layout that suits the chip better belongs where the weights are made,
    once, not in a copy of both expert stacks on every call (PERF.md, PR 33)."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    for experts in ({"gate": spec(4, 16, width), "up": spec(4, 16, width), "down": spec(4, width, 16)},
                    {"up": spec(4, 16, width), "down": spec(4, width, 16)}):
        jaxpr = jax.make_jaxpr(lambda x, s, e: moe_topk(x, s, s, e, 2))(
            spec(32, 16), jax.ShapeDtypeStruct((32, 4), jnp.float32), experts)
        products = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "ragged_dot_general"]
        assert len(products) == len(experts)
        assert sorted(eqn.invars[1].aval.shape for eqn in products) == sorted(
            [(4, width, 16)] + [(4, 16, width)] * (len(experts) - 1))
        assert not [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pad"]


def test_moe_topk_when_one_expert_takes_every_token():
    rng = np.random.default_rng(0)
    T, D, F, E = 32, 8, 4, 4
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    experts = {n: jnp.asarray(rng.normal(size=s), jnp.float32)
               for n, s in (("gate", (E, D, F)), ("up", (E, D, F)), ("down", (E, F, D)))}
    scores = jnp.zeros((T, E)).at[:, 3].set(1.0)
    y, chosen = moe_topk(x, scores, scores, experts, 1, 1.0)
    want, _ = _every_expert_masked(x, scores, scores, experts, 1, 1.0)
    assert (np.asarray(chosen) == 3).all()
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)


# -- a stack of several layers' experts, read where it lies ------------------

def _moe_topk_as_it_was(x, scores_to_choose, scores_to_weigh, experts, k, scale=1.0, activation=jax.nn.silu):
    """``moe_topk`` of PR 33, statement for statement: what Trinity's and
    JoyAI's programs were traced from."""
    from jax import lax

    T, D = x.shape
    E = scores_to_choose.shape[1]
    _, chosen = lax.top_k(scores_to_choose, k)
    weights = jnp.take_along_axis(scores_to_weigh, chosen, axis=1)
    weights = weights / weights.sum(axis=1, keepdims=True) * scale
    expert_of = chosen.reshape(T * k)
    order = jnp.argsort(expert_of, stable=True)
    sizes = (expert_of[:, None] == jnp.arange(E, dtype=expert_of.dtype)).sum(axis=0)
    sizes = sizes.astype(jnp.int32)
    xs = x[order // k]
    dot = lambda a, w: lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)
    if "gate" in experts:
        inner = activation(dot(xs, experts["gate"])) * dot(xs, experts["up"])
    else:
        inner = activation(dot(xs, experts["up"]))
    inner = inner.astype(x.dtype)
    ys = dot(inner, experts["down"])
    back = jnp.argsort(order)
    y = (ys[back].reshape(T, k, D) * weights[:, :, None].astype(jnp.float32)).sum(axis=1)
    return y, chosen.astype(jnp.int32)


@pytest.mark.parametrize("width", [32, 24], ids=["a_power_of_two", "off_every_grid"])
@pytest.mark.parametrize("gated", [True, False], ids=["three_matrices", "two_matrices"])
def test_moe_topk_with_as_many_groups_as_experts_lowers_to_the_program_it_was(gated, width):
    """Which product runs is read from the matrices' leading length: equal to
    the router's width, as in every family whose stacks come through a
    scan's ``xs``, the lowered text is PR 33's, whatever the inner width and
    whatever ``first_group`` and ``interpret`` say."""
    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    experts = {"gate": spec(8, 16, width), "up": spec(8, 16, width), "down": spec(8, width, 16)}
    if not gated:
        del experts["gate"]
    shapes = (spec(64, 16), spec(64, 8, dtype=jnp.float32), spec(64, 8, dtype=jnp.float32), experts)

    def lowered(fn):
        def program(x, choose, weigh, experts):
            return fn(x, choose, weigh, experts, 2, 2.5)
        return jax.jit(program).lower(*shapes).as_text()

    was = lowered(_moe_topk_as_it_was)
    assert len(was) > 5000 and lowered(moe_topk) == was
    assert lowered(lambda *a: moe_topk(*a, first_group=24, interpret=True)) == was
    assert "dynamic_update_slice" not in was and "custom_call" not in was


def _a_stack(rng, names, U, E, D, F):
    stack = {n: rng.normal(size=(U * E, F, D) if n == "down" else (U * E, D, F)) / 4 for n in names}
    return {n: jnp.asarray(a, jnp.bfloat16) for n, a in stack.items()}


@pytest.mark.parametrize("unit", [0, 1, 2])
@pytest.mark.parametrize("gated", [True, False], ids=["three_matrices", "two_matrices"])
def test_moe_topk_over_a_stack_of_units_is_each_units_experts_alone(unit, gated):
    """``units x E`` groups with the unit's place among them, traced as a
    scan hands it over or not: that unit's ``(E, D, F)`` matrices alone give
    the same numbers and the same choice, so nothing of the 2 x E other
    groups' matrices arrives: they are empty. 96 assigned rows are no whole
    tile of 256, and expert 4 of the unit's own gets no token."""
    rng = np.random.default_rng(7)
    T, D, F, E, U, k = 48, 16, 8, 6, 3, 2
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.bfloat16)
    stack = _a_stack(rng, ("gate", "up", "down") if gated else ("up", "down"), U, E, D, F)
    own = {n: a[unit * E:(unit + 1) * E] for n, a in stack.items()}
    weigh = jnp.asarray(rng.uniform(0.1, 0.9, size=(T, E)), jnp.float32)
    choose = weigh.at[:, 4].add(-10.0)
    alone, chosen_alone = jax.jit(lambda *a: moe_topk(*a, k, 2.5, relu2))(x, choose, weigh, own)
    traced, chosen = jax.jit(lambda x, c, w, e, g: moe_topk(x, c, w, e, k, 2.5, relu2, g, True))(
        x, choose, weigh, stack, jnp.int32(unit * E))
    static, _ = jax.jit(lambda *a: moe_topk(*a, k, 2.5, relu2, unit * E, True))(x, choose, weigh, stack)
    assert np.isfinite(np.asarray(alone)).all() and not (np.asarray(chosen) == 4).any()
    assert np.array_equal(chosen, chosen_alone)
    assert np.array_equal(traced, static)
    np.testing.assert_allclose(traced, alone, rtol=1e-6, atol=1e-6)
    if unit != 1:  # and another unit's place is another result
        other, _ = jax.jit(lambda *a: moe_topk(*a, k, 2.5, relu2, E, True))(x, choose, weigh, stack)
        assert np.abs(np.asarray(other) - np.asarray(alone)).max() > 0.05


def test_a_stack_that_is_no_whole_number_of_layers_is_an_error():
    rng = np.random.default_rng(0)
    stack = _a_stack(rng, ("up", "down"), 1, 9, 16, 8)
    scores = jnp.asarray(rng.uniform(size=(8, 6)), jnp.float32)
    with pytest.raises(AssertionError, match="9 groups of matrices for 6 experts"):
        moe_topk(jnp.zeros((8, 16), jnp.bfloat16), scores, scores, stack, 2, interpret=True)


@pytest.mark.parametrize("M,K,N,transposed", [
    (96, 128, 72, True),    # written width off the 128 lanes, contracted on them: read as the chip stores it
    (300, 72, 128, False),  # the way back; 300 rows are padded to 304
    (520, 24, 40, False),   # a toy's widths, more rows than one tile of 256
])
def test_the_product_in_place_is_ragged_dot(M, K, N, transposed):
    """Groups of every size, empty ones among them, first and last, and the
    matrices of the groups that get no row full of NaN: they are not read."""
    from jax import lax

    from mmlspark_tpu.ops import expert_parallel

    rng = np.random.default_rng(M)
    G = 12
    sizes = rng.multinomial(M, rng.dirichlet(np.ones(G - 4)))
    sizes = np.concatenate([[0, 0], sizes, [0, 0]]).astype(np.int32)
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    w = np.asarray(rng.normal(size=(G, K, N)) / 4, np.float32)
    w[sizes == 0] = np.nan
    w = jnp.asarray(w, jnp.bfloat16)
    text = jax.make_jaxpr(lambda a, w, s: expert_parallel._product_in_place(a, w, s, True))(a, w, jnp.asarray(sizes))
    assert ("transpose[permutation=(0, 2, 1)]" in str(text)) == transposed
    got = expert_parallel._product_in_place(a, w, jnp.asarray(sizes), True)
    want = lax.ragged_dot(a, jnp.nan_to_num(w), jnp.asarray(sizes), preferred_element_type=jnp.float32)
    assert got.shape == (M, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_tiles_of_the_published_hybrid_widths():
    """Up: all of 2,688 contracted at once, 1,856 written in four tiles of
    512 (the last clipped). Down: all of 1,856 contracted, 2,688 written in
    three of 896 (my chip runs, PR 34: 10.8 and 9.3 ms at these, PERF.md 6)."""
    from mmlspark_tpu.ops.expert_parallel import _tile

    assert (_tile(2688, 3072), _tile(1856, 512)) == (2688, 512)
    assert (_tile(1856, 3072), _tile(2688, 512)) == (1856, 896)
    assert _tile(24, 512) == 24 and _tile(4096, 3072) == 1024 and _tile(7168, 3072) == 1024 and _tile(3000, 512) == 512
