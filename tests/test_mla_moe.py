"""The latent-attention decoder (``model_type`` ``joyai_llm_flash``) on the
deep path, at a small size on the CPU: ``LMFeaturizer`` through
``DNNModel.transform`` against the benchmark's plain reference, every
planted fault far from it, the blocked attention with values narrower than
keys and a group of one against dense masked attention, interleaved rotary
against complex multiplication, the routing bias, the family table."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mla_moe as ref
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.featurize.lm import FAMILIES, LMFeaturizer
from mmlspark_tpu.models import init_mla_moe, mla_moe_apply
from mmlspark_tpu.models.mla_moe import layer_counts, rope_interleaved
from mmlspark_tpu.models.moe_decoder import routed_experts
from mmlspark_tpu.observability.tracing import get_tracer
from mmlspark_tpu.ops.attention import blocked_attention
from test_afmoe import _dense_attention  # dense masked attention in float64

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(
    model_type="joyai_llm_flash", hidden_size=64, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, routed_scaling_factor=2.5,
    rope_theta=32000000, rms_norm_eps=1e-6, vocab_size=512, layers=5,
    interpret=True,  # the attention kernel, on a backend that is no TPU
)
ALL_OUTPUTS = {"hidden": "h", "logits": "l", "expert_load": "e"}


def _tokens(seed, rows=5, length=50):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], size=(rows, length)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return init_mla_moe(jax.random.PRNGKey(11), SMALL)  # no last position of the seeds below sits on a routing tie


# -- the model, through the stage ---------------------------------------------

@pytest.mark.parametrize("seed,batch", [(0, 2), (1, 5), (3, 3)])
def test_featurizer_agrees_with_the_reference_on_all_three_outputs(params, seed, batch):
    tokens = _tokens(seed)
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=batch).transform(Table({"tokens": tokens}))
    want = ref.forward(params, tokens, SMALL)
    assert out["h"].shape == (5, 64) and out["l"].shape == (5, 512) and out["e"].shape == (5, 4, 16)
    assert out["h"].dtype == np.float32 and out["l"].dtype == np.float32 and out["e"].dtype == np.int32
    assert ref.relative_gaps(out["h"], want["hidden"]).max() < 0.03
    assert ref.relative_gaps(out["l"], want["logits"]).max() < 0.03
    assert ref.load_gaps(out["e"], want["expert_load"], 50 * 2).max() <= 0.02
    # no token is dropped: every expert layer of every row routed S x k
    assert (out["e"].sum(axis=-1) == 50 * 2).all()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference_far_from_the_program(params, fault):
    tokens = _tokens(4)
    got = jax.jit(lambda p, x: mla_moe_apply(p, x, SMALL))(params, tokens)
    wrong = ref.forward(params, tokens, SMALL, fault=fault)
    if fault == "head_inputs_3_mantissa_bits":  # nothing before the head moves; the logits alone do
        assert ref.relative_gaps(got["hidden"], wrong["hidden"]).max() < 0.03
        assert ref.relative_gaps(wrong["logits"], ref.head_of(params, wrong["hidden"])).min() > 0.02
    else:
        assert ref.relative_gaps(got["hidden"], wrong["hidden"]).min() > 0.1


def test_the_head_over_the_programs_own_hidden_state_is_the_programs_logits(params):
    """The following check: whatever routing did to the hidden state, the
    logits are the head's product of it, to the float32 sum's last bits."""
    got = jax.jit(lambda p, x: mla_moe_apply(p, x, SMALL))(params, _tokens(2))
    assert ref.relative_gaps(got["logits"], ref.head_of(params, got["hidden"])).max() < 1e-5
    low = jax.jit(lambda p, x: mla_moe_apply(p, x, dict(SMALL, product_dtype="float8_e4m3fn")))(params, _tokens(2))
    assert ref.relative_gaps(low["logits"], ref.head_of(params, low["hidden"])).min() > 0.02


def test_an_unknown_fault_is_an_error(params):
    with pytest.raises(ValueError, match="unknown fault"):
        ref.forward(params, _tokens(0, rows=1), SMALL, fault="typo")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference", "mla_moe.py")) as f:
        assert "mmlspark_tpu" not in f.read().split('"""', 2)[2]


def test_narrower_product_inputs_are_a_different_result(params):
    tokens = _tokens(4)
    stated = jax.jit(lambda p, x: mla_moe_apply(p, x, SMALL))(params, tokens)
    again = jax.jit(lambda p, x: mla_moe_apply(p, x, dict(SMALL, product_dtype="bfloat16")))(params, tokens)
    low = jax.jit(lambda p, x: mla_moe_apply(p, x, dict(SMALL, product_dtype="float8_e4m3fn")))(params, tokens)
    assert np.array_equal(stated["hidden"], again["hidden"])
    assert 0.02 < ref.relative_gaps(low["hidden"], stated["hidden"]).min()


def test_the_family_is_read_from_model_type_and_afmoe_is_the_default(params):
    from mmlspark_tpu.models import init_afmoe

    assert set(FAMILIES) >= {"afmoe", "joyai_llm_flash", "nemotron_h"}
    afmoe = dict(
        hidden_size=32, num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        intermediate_size=48, moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        num_dense_layers=1, sliding_window=4, rope_theta=10000, rms_norm_eps=1e-5,
        route_scale=2.0, vocab_size=64, layers=2, interpret=True,
        layer_types=["sliding_attention", "full_attention"],
    )
    table = Table({"tokens": _tokens(0, rows=3, length=12) % 64})
    weights = init_afmoe(jax.random.PRNGKey(1), afmoe)
    absent = LMFeaturizer(modelParams=weights, modelConfig=afmoe).transform(table)
    named = LMFeaturizer(modelParams=weights, modelConfig=dict(afmoe, model_type="afmoe")).transform(table)
    assert np.array_equal(absent["features"], named["features"])
    with pytest.raises(ValueError, match="'gpt': one of .'afmoe', 'joyai_llm_flash'"):
        LMFeaturizer(modelParams=weights, modelConfig=dict(afmoe, model_type="gpt")).transform(table)
    # the latent family's tree under the default family's name has none of its keys
    with pytest.raises(KeyError):
        LMFeaturizer(modelParams=params, modelConfig={k: v for k, v in SMALL.items() if k != "model_type"}
                     ).transform(Table({"tokens": _tokens(0)}))


def test_spans_of_a_transform(params):
    tracer = get_tracer()
    tracer.clear()
    out = LMFeaturizer(outputCols=ALL_OUTPUTS, modelParams=params, modelConfig=SMALL,
                       batchSize=2).transform(Table({"tokens": _tokens(5)}))
    spans = {s["name"]: s for s in tracer.export()}
    root = spans["lm.featurize"]
    assert root["tags"] == {"rows": 5, "tokens": 50, "batch_size": 2, "layers": 5, "experts": 16,
                            "model_type": "joyai_llm_flash", "attention": "latent", "latent_width": 40}
    assert spans["dnn.transform"]["parent_id"] == root["span_id"]
    stats = spans["lm.route_stats"]["tags"]
    per_dispatch = np.add.reduceat(out["e"].astype(np.int64), [0, 2, 4], axis=0)
    assert stats["load_peak"] == per_dispatch.max(axis=-1).sum()
    assert stats["load_mean"] == pytest.approx(5 * 4 * 50 * 2 / 16)
    assert stats["tokens_routed"] == 5 * 4 * 50 * 2
    assert stats["expert_groups"] == 3 * 4 * 16  # dispatches x expert layers x experts
    assert stats["experts_empty"] == (per_dispatch == 0).sum()
    assert 0 <= stats["experts_empty"] < stats["expert_groups"]


def test_named_scopes_are_in_the_lowered_program(params):
    text = jax.jit(lambda p, x: mla_moe_apply(p, x, SMALL)).lower(params, _tokens(0)).as_text(debug_info=True)
    for scope in ("mla_latent", "mla_up", "attn_full", "moe_route", "moe_experts", "lm_head"):
        assert scope in text, scope


def test_weights_are_bfloat16_on_the_device_and_come_from_the_key(params):
    leaves = jax.tree.leaves(params)
    assert all(isinstance(a, jax.Array) for a in leaves)
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert params["moe"]["router_bias"].dtype == jnp.float32  # the one float32 buffer
    assert params["moe"]["e_gate"].shape == (4, 16, 64, 32) and params["dense"]["w_up"].shape == (1, 64, 96)
    assert params["moe"]["w_qb"].shape == (4, 48, 4 * 24) and params["moe"]["w_kva"].shape == (4, 64, 40)
    assert params["moe"]["w_kvb"].shape == (4, 32, 4 * 32) and params["moe"]["wo"].shape == (4, 64, 64)
    assert set(params["dense"]) >= {"q_norm", "kv_norm", "norm1", "norm2"} and "norm3" not in params["dense"]
    again = init_mla_moe(jax.random.PRNGKey(11), SMALL)
    other = init_mla_moe(jax.random.PRNGKey(8), SMALL)
    assert np.array_equal(params["head"], again["head"]) and not np.array_equal(params["head"], other["head"])


def test_the_embedding_is_not_scaled_and_the_head_reads_the_last_position(params):
    """A row's outputs depend on the last position's token through the
    residual stream alone: with every layer's output projection zeroed the
    stream is the embedding, unscaled."""
    bare = dict(params, dense=dict(params["dense"], wo=jnp.zeros_like(params["dense"]["wo"]),
                                   w_down=jnp.zeros_like(params["dense"]["w_down"])))
    config = dict(SMALL, layers=1)
    tokens = _tokens(6, rows=2, length=9)
    got = jax.jit(lambda p, x: mla_moe_apply(p, x, config))(bare, tokens)
    last = np.asarray(params["embed"][tokens[:, -1]], np.float32)
    scale = np.asarray(params["final_norm"], np.float32)
    want = last / np.sqrt((last * last).mean(axis=-1, keepdims=True) + 1e-6) * scale
    np.testing.assert_allclose(got["hidden"], want, rtol=1e-5)
    assert got["expert_load"].shape == (2, 0, 16)


# -- the benchmark's cut ------------------------------------------------------

def test_layer_kinds_of_the_cut_are_the_dense_layer_and_four_expert_layers():
    with open(os.path.join(ROOT, "chipbench", "configs", "joyai-llm-flash.json")) as f:
        spec = json.load(f)
    config = spec["params"]
    assert config["layers"] == 5 and spec["num_hidden_layers"] == 40
    assert layer_counts(config) == (1, 4)
    assert ref.layer_kinds(config) == [("dense", 0), ("moe", 0), ("moe", 1), ("moe", 2), ("moe", 3)]
    assert layer_counts(dict(config, layers=40)) == (1, 39) and layer_counts(dict(config, layers=1)) == (1, 0)


# -- rotary on interleaved pairs ----------------------------------------------

def test_interleaved_rotary_against_complex_multiplication():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 37, 3, 8)).astype(np.float32)
    theta = 32000000.0
    got = np.asarray(rope_interleaved(jnp.asarray(x), theta))
    z = x[..., 0::2].astype(np.float64) + 1j * x[..., 1::2]
    angle = np.arange(37)[:, None] * theta ** (-2 * np.arange(4) / 8)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-4)
    # position 0 is untouched, and a half-split pairing is another function
    np.testing.assert_array_equal(got[:, 0], x[:, 0])
    np.testing.assert_allclose(np.asarray(ref.rope(jnp.asarray(x[0]), theta)), want[0], atol=1e-4)
    assert np.abs(np.asarray(ref.rope(jnp.asarray(x[0]), theta, half_split=True)) - want[0]).max() > 0.1


# -- blocked attention, values narrower than keys, a group of one -----------------

@pytest.mark.parametrize("window", [None, 7, 40])
@pytest.mark.parametrize("kv_heads,d_v", [(4, 8), (4, 16), (2, 8)], ids=["group1_narrow", "group1_wide", "group2_narrow"])
@pytest.mark.parametrize("length,block", [(50, 16), (64, 16), (9, 512)])
def test_blocked_attention_with_values_of_another_width(window, kv_heads, d_v, length, block):
    rng = np.random.default_rng(length + d_v)
    q = jnp.asarray(rng.normal(size=(2, length, 4, 12)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, length, kv_heads, 12)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, length, kv_heads, d_v)), jnp.float32)
    got = jax.jit(lambda *a: blocked_attention(*a, window=window, block=block, interpret=True))(q, k, v)
    assert got.shape == (2, length, 4, d_v) and got.dtype == q.dtype
    np.testing.assert_allclose(got, _dense_attention(q, k, v, window), atol=2e-5)


def test_blocked_attention_refuses_keys_and_values_of_other_lengths_or_heads():
    q = jnp.zeros((1, 8, 4, 12))
    with pytest.raises(ValueError, match="grouped heads"):
        blocked_attention(q, jnp.zeros((1, 8, 4, 12)), jnp.zeros((1, 8, 2, 8)))
    with pytest.raises(ValueError, match="grouped heads"):
        blocked_attention(q, jnp.zeros((1, 8, 4, 8)), jnp.zeros((1, 8, 4, 8)))  # a key as wide as the value, not as q


# -- the routing bias ---------------------------------------------------------

@pytest.mark.parametrize("shared", [True, False], ids=["one_shared_expert", "no_shared_expert"])
def test_experts_are_chosen_by_biased_scores_and_weighed_by_unbiased_ones(shared):
    """16 experts, top-2. The bias makes expert 3 every token's first choice
    and keeps experts 10-15 empty; the weights must still be the plain
    sigmoid scores over their sum times the scale. A tree that holds no
    ``s_*`` leaves gives the routed output alone."""
    rng = np.random.default_rng(0)
    S, D, F, E, k, scale = 24, 16, 8, 16, 2, 2.5
    p = {"router": rng.normal(size=(D, E)) / 4, "router_bias": np.zeros(E),
         "e_gate": rng.normal(size=(E, D, F)) / 4, "e_up": rng.normal(size=(E, D, F)) / 4,
         "e_down": rng.normal(size=(E, F, D)) / 3,
         "s_gate": rng.normal(size=(D, F)) / 4, "s_up": rng.normal(size=(D, F)) / 4,
         "s_down": rng.normal(size=(F, D)) / 3}
    p["router_bias"][3], p["router_bias"][10:] = 10.0, -10.0
    p = {n: jnp.asarray(a, jnp.float32) for n, a in p.items() if shared or not n.startswith("s_")}
    x = jnp.asarray(rng.normal(size=(1, S, D)), jnp.bfloat16)
    y, load = jax.jit(lambda p, x: routed_experts(p, x, k, scale, jnp.bfloat16))(p, x)
    load = np.asarray(load)[0]
    assert load[3] == S and (load[10:] == 0).all() and load.sum() == S * k

    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    swiglu = lambda x, g, u, d: bf(silu(x @ bf(g)) * (x @ bf(u))) @ bf(d)
    xs = bf(x[0])
    scores = 1 / (1 + np.exp(-(xs @ bf(p["router"]))))
    biased = scores + np.asarray(p["router_bias"], np.float64)
    want = swiglu(xs, p["s_gate"], p["s_up"], p["s_down"]) if shared else np.zeros((S, D))
    for t in range(S):
        chosen = np.argsort(-biased[t])[:k]
        assert chosen[0] == 3
        weights = scores[t, chosen] / scores[t, chosen].sum() * scale  # the bias is not in them
        for e, w in zip(chosen, weights):
            want[t] += w * swiglu(xs[t:t + 1], p["e_gate"][e], p["e_up"][e], p["e_down"][e])[0]
    np.testing.assert_allclose(np.asarray(y[0], np.float64), want, rtol=0.02, atol=0.02)
    # weights from the biased scores would be another result: expert 3's would be ~10 of 10.x
    assert np.abs(np.asarray(y[0], np.float64) - want).max() < 0.05 < np.abs(want).max()
