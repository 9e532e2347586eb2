"""The gated families (``afmoe``, ``mla_moe``, ``lfm2_moe``) read their
routed experts' stacks where they lie, as the hybrid family does: the expert
scan closes over every layer's groups and the Pallas grouped matmul is told
where a layer's stand; a tree whose stacks are of another dtype than the
products' (the benchmark's float8 control) hands a layer's matrices through
the scan to ``lax.ragged_dot``. Either way every expert receives the same
tokens and the outputs agree to a product's rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference.afmoe import relative_gaps
from mmlspark_tpu.models import afmoe_apply, init_afmoe, init_lfm2_moe, init_mla_moe, lfm2_moe_apply, mla_moe_apply
from mmlspark_tpu.models.afmoe import layer_kinds as afmoe_kinds
from mmlspark_tpu.models.lfm2_moe import layer_kinds as lfm2_kinds
from mmlspark_tpu.models.mla_moe import layer_counts
from test_afmoe import SMALL as AFMOE
from test_lfm2_moe import SMALL as LFM2
from test_mla_moe import SMALL as MLA

# family: (init, apply, small config, experts, expert layers held)
FAMILIES = {
    "afmoe": (init_afmoe, afmoe_apply, AFMOE, AFMOE["num_experts"], len(afmoe_kinds(AFMOE)[1])),
    "mla_moe": (init_mla_moe, mla_moe_apply, MLA, MLA["n_routed_experts"], layer_counts(MLA)[1]),
    "lfm2_moe": (init_lfm2_moe, lfm2_moe_apply, LFM2, LFM2["num_experts"], len(lfm2_kinds(LFM2)[1])),
}


def _tokens(config, seed=4, rows=5, length=50):
    return np.random.default_rng(seed).integers(0, config["vocab_size"], size=(rows, length)).astype(np.int32)


@pytest.fixture(scope="module")
def trees():
    return {name: init(jax.random.PRNGKey(5), config) for name, (init, _, config, _, _) in FAMILIES.items()}


def _eqns(jaxpr, name):
    found = []
    for eqn in jaxpr.eqns:
        found += [eqn] if eqn.primitive.name == name else []
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


@pytest.mark.parametrize("product_dtype", ["bfloat16", "float8_e4m3fn"], ids=["as_stated", "float8_control"])
@pytest.mark.parametrize("family", FAMILIES)
def test_the_expert_stacks_are_read_where_they_lie_when_the_products_take_them_as_stored(trees, family, product_dtype):
    """As stated, the expert scan closes over the ``(layers x E, ., .)``
    stacks, its body's products are Pallas calls and nothing slices a
    layer's ``(E, D, F)`` or ``(E, F, D)`` matrices out. Under the float8
    control a layer's matrices are rounded into a copy whatever is done, so
    the stacks go through the scan to ``lax.ragged_dot``: three products of
    one layer's matrices."""
    _, apply, config, E, layers = FAMILIES[family]
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    config = dict(config, product_dtype=product_dtype)
    jaxpr = jax.make_jaxpr(lambda p, x: apply(p, x, config))(trees[family], _tokens(config))
    (scan,) = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"
               and "moe_combine" in [e.params["name"] for e in _eqns(eqn.params["jaxpr"].jaxpr, "pallas_call")]]
    consts = scan.params["num_consts"]
    closed_over = [v.aval.shape for v in scan.invars[:consts]]
    carried_or_scanned = [v.aval.shape for v in scan.invars[consts:]]
    body = scan.params["jaxpr"].jaxpr
    stacks = [(layers, E, D, F), (layers, E, F, D)]
    a_layers = [(E, D, F), (E, F, D)]
    if product_dtype == "bfloat16":
        assert closed_over.count((layers * E, D, F)) == 2 and (layers * E, F, D) in closed_over
        assert not any(shape in carried_or_scanned for shape in stacks + [(layers * E, D, F), (layers * E, F, D)])
        products = [e for e in _eqns(body, "pallas_call")
                    if {v.aval.shape for v in e.invars} & {(layers * E, D, F), (layers * E, F, D)}]
        assert len(products) == 3 and not _eqns(body, "ragged_dot_general")
        assert not [e for e in _eqns(body, "dynamic_slice") if e.outvars[0].aval.shape[-3:] in a_layers]
    else:
        assert carried_or_scanned.count(stacks[0]) == 2 and stacks[1] in carried_or_scanned
        assert [e.invars[1].aval.shape for e in _eqns(body, "ragged_dot_general")] == [a_layers[0]] * 2 + [a_layers[1]]


@pytest.mark.parametrize("family", FAMILIES)
def test_reading_the_stacks_in_place_changes_no_routing_and_no_result(trees, family):
    """The same tree with its three expert stacks in float32 (the same values)
    takes the other way, a layer's matrices through the scan to
    ``lax.ragged_dot``: every expert receives the same tokens, no token is
    dropped, and the outputs agree to a product's rounding. (The two sum a
    product in another order; on some draws a float32 sum then rounds to
    another bfloat16 between the products, and the layers after it carry
    that: ten draws of tree and tokens a family read 0 on 8, 4 and 5 of them
    and at most 4.5e-3, 1.1e-2 (over a routing tie) and 4.0e-3. This draw
    reads 0 in all three.)"""
    _, apply, config, _, _ = FAMILIES[family]
    params = trees[family]
    wide = dict(params, moe=dict(params["moe"], **{
        name: params["moe"][name].astype(jnp.float32) for name in ("e_gate", "e_up", "e_down")}))
    program = jax.jit(lambda p, x: apply(p, x, config))
    in_place, through_the_scan = program(params, _tokens(config, 3)), program(wide, _tokens(config, 3))
    assert np.array_equal(through_the_scan["expert_load"], in_place["expert_load"])
    assert (np.asarray(in_place["expert_load"]).sum(axis=-1) == 50 * config["num_experts_per_tok"]).all()
    assert relative_gaps(through_the_scan["hidden"], in_place["hidden"]).max() < 1e-3
    assert relative_gaps(through_the_scan["logits"], in_place["logits"]).max() < 1e-3
