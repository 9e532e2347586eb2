"""Driver: ``lm_score``'s job (one ``LMFeaturizer(...).transform(Table)``
over a table of int32 token rows of one length) for the short-convolution
family (``model_type`` ``lfm2_moe``): its own work and bytes from shapes,
its own weights and its own plain reference. What is generic comes from
``lm_score`` (the job, the tokens, the keys a position sees, the metric) and
from ``lm_score_mla`` (the comparison's four numbers); PERF.md section 7
asks a ``benchmark`` issue to fold the four, keyed by ``model_type``.
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers import lm_score_mla
from chipbench.drivers.featurize import key_of
from chipbench.drivers.lm_score import OUTPUTS, end_to_end, job, seen_keys, zipf_tokens  # noqa: F401
from chipbench.reference import lfm2_moe as ref


def _layers(config: dict):
    """(conv operators run, attention operators run, dense feed-forwards run, expert blocks run)."""
    held = ref.layers(config)
    operators, ffns = [layer[0] for layer in held], [layer[2] for layer in held]
    return operators.count("conv"), operators.count("attention"), ffns.count("dense"), ffns.count("moe")


def work(config: dict, traffic: dict) -> dict:
    """Multiply-adds x 2 of one job, from shapes alone, whatever implements
    them: ``attn_flops`` (scores and weighted values over the keys a position
    sees), ``expert_flops`` (the routed experts' three products for the
    experts a token chose), ``conv_operator_flops`` (a ``conv`` operator's two
    projections and its taps), and ``flops`` (those, the attention operator's
    four projections, the dense feed-forwards, the router and the tied head
    at each row's last position). ``conv_flops`` and ``conv_bytes`` are the
    ``short_conv`` scope's own: the taps' multiply-adds and the two gates'
    multiplies, and what it must read and write once in bfloat16, three
    thirds in and one third out: the bytes bind. Norms, softmax, rotary,
    activations and the gather of the embedding are not counted."""
    c = config
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], ref.head_dim(c)
    rows, S = traffic["rows"], traffic["tokens"]
    convs, attends, dense, experts = _layers(c)
    conv_operator = 2 * D * 3 * D + 2 * c["conv_L_cache"] * D + 2 * D * D
    projections = 2 * D * hd * (2 * H + 2 * KV)
    routed = 2 * 3 * D * c["moe_intermediate_size"] * c["num_experts_per_tok"]
    a_token = (convs * conv_operator + attends * projections + dense * 2 * 3 * D * c["intermediate_size"]
               + experts * (routed + 2 * D * c["num_experts"]))
    attn = attends * 4 * H * hd * seen_keys(S)
    head = 2 * D * c["vocab_size"]
    return {
        "flops": rows * (attn + S * a_token + head), "bytes": 0,
        "attn_flops": rows * attn, "expert_flops": rows * S * experts * routed,
        "conv_operator_flops": rows * S * convs * conv_operator,
        "conv_flops": rows * S * convs * (2 * c["conv_L_cache"] + 2) * D, "conv_bytes": rows * S * convs * 2 * 4 * D,
    }


def weight_bytes(config: dict) -> int:
    """Bytes of the parameter tree: bfloat16, but for the routing bias of an
    expert block, float32; the head is the embedding, held once."""
    c = config
    D, hd, E = c["hidden_size"], ref.head_dim(c), c["num_experts"]
    convs, attends, dense, experts = _layers(c)
    conv = D + D * 3 * D + D * c["conv_L_cache"] + D * D
    attention = D + D * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"]) + 2 * hd
    in_bfloat16 = (c["vocab_size"] * D + D + convs * conv + attends * attention
                   + dense * (D + 3 * D * c["intermediate_size"])
                   + experts * (D + D * E + 3 * D * c["moe_intermediate_size"] * E))
    return 2 * in_bfloat16 + 4 * experts * E


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.models.lfm2_moe import init_lfm2_moe

    params = init_lfm2_moe(key_of(seed), config)
    rng = np.random.default_rng(seed)
    rows, S = traffic["rows"], traffic["tokens"]
    tokens = zipf_tokens(rng, rows, S, config["vocab_size"], traffic["zipf_exponent"])
    sample = np.sort(rng.choice(rows, size=min(traffic["compare_rows"], rows), replace=False))
    return {
        "params": params, "config": config, "tokens": tokens, "table": Table({"tokens": tokens}),
        "sample": sample, "rows": rows, "S": S, "batch": traffic["batchSize"],
        "limits": traffic["limits"], "weight_bytes": weight_bytes(config), "early_layers": traffic["early_layers"],
    }


def fault(state: dict, out: dict):
    """Why this job left the cell's path, or None."""
    import jax

    c, rows = state["config"], state["rows"]
    want = {"hidden": (rows, c["hidden_size"]), "logits": (rows, c["vocab_size"]),
            "expert_load": (rows, _layers(c)[3], c["num_experts"])}
    if out["shapes"] != want:
        return f"outputs of shapes {out['shapes']}"
    if not out["finite"]:
        return "non-finite outputs"
    routed = state["S"] * c["num_experts_per_tok"]
    if out["routed"] != [routed]:
        return f"a layer's load sums to {out['routed']}, not {routed} a row: a token was dropped"
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None and peak < state["weight_bytes"]:
        return f"peak {peak} B < the weights' {state['weight_bytes']} B"
    return None


def reference_outputs(state: dict, fault=None) -> dict:
    return ref.forward(state["params"], state["tokens"][state["sample"]], state["config"], fault)


def checks(state: dict, outputs: list) -> dict:
    """``lm_score_mla.checks``' four numbers against this family's
    reference: ``load_gap_max`` (every expert layer's routing sees every
    position of the operators before it), ``head_gap_max`` (the job's logits
    against the TIED head over the job's own hidden state: the embedding
    stands where that check reads a ``head``, transposed), and
    ``hidden_gap_max`` / ``logit_gap_max`` as the smaller of the sampled rows
    (a last position on a routing tie moves one row, a fault every row).
    And a fifth, ``load_gap_early_max``: the same share of assignments that
    went elsewhere over the first ``early_layers`` expert layers alone. An
    assignment that flips moves its token by a quarter of a layer's routed
    output and the layers after it follow, so program and reference drift
    apart with depth (PERF.md 6a: 0.0013 at the first expert layer, 0.007 at
    the fourteenth) and the widest layer is the deepest and the noisiest;
    whatever is wrong before the second expert layer's routing shows where the
    drift has hardly begun."""
    if "want" not in state:
        state["want"], state["tied"] = reference_outputs(state), {"head": state["params"]["embed"].T}
    out = lm_score_mla.checks(dict(state, params=state["tied"]), outputs)
    n, routed = state["early_layers"], state["S"] * state["config"]["num_experts_per_tok"]
    early = max(ref.load_gaps(o["sample"]["expert_load"][:, :n], state["want"]["expert_load"][:, :n], routed).max()
                for o in outputs)
    out["load_gap_early_max"] = {"value": float(early), "limit": state["limits"]["load_gap_early_max"]}
    return out


def compare(state: dict, outputs: list, seed: int) -> dict:
    """Every job's sampled rows against the reference's forward of the same
    weights and tokens, after the table has gone."""
    state.pop("table", None)
    return checks(state, outputs)


def control(state: dict) -> dict:
    """{side: the comparison's numbers with that side in the program's
    place}: the program's own path with ``product_dtype`` float8 (e4m3), one
    step below the bfloat16 the configuration states for a matrix product's
    inputs, one whole job at the cell's size; then the reference with each
    of its planted faults standing where the program's output stands."""
    out = {"control": checks(state, [job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))])}
    for name in ref.FAULTS:
        out[name] = checks(state, [{"sample": reference_outputs(state, fault=name)}])
    return out
