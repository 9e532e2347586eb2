"""Driver: ``lm_score``'s job (one ``LMFeaturizer(...).transform(Table)``
over a table of int32 token rows of one length) for the hybrid state-space
family (``model_type`` ``nemotron_h``): its own work and bytes from shapes,
its own weights and its own plain reference. What is generic comes from
``lm_score`` (the job, the tokens, the keys a position sees, the metric) and
from ``lm_score_mla`` (the comparison's four numbers); PERF.md section 7
asks a ``benchmark`` issue to fold the three, keyed by ``model_type``.
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers import lm_score_mla
from chipbench.drivers.featurize import key_of
from chipbench.drivers.lm_score import OUTPUTS, end_to_end, job, seen_keys, zipf_tokens  # noqa: F401
from chipbench.reference import nemotron_h as ref


def _blocks(config: dict):
    """(mixers run, attention blocks run, expert blocks run)."""
    letters = [letter for letter, _ in ref.blocks(config)]
    return letters.count("M"), letters.count("*"), letters.count("E")


def ssd_work(config: dict):
    """(multiply-adds x 2, bytes) of the state-space scan, a token a mixer,
    in its chunked form at the published chunk ``L``: ``C B^T`` once a group
    (``2 L N``), and a head's three products, the decay-masked scores with
    ``x`` (``2 L P``), the carried state with ``C`` and the chunk's closing
    state (``2 N P`` each); and what the scan must read and write once:
    ``xBC`` in and ``y`` out in bfloat16, ``dt`` in float32."""
    c = config
    H, P, G, N, L = c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"], c["chunk_size"]
    flops = G * 2 * L * N + H * (2 * L * P + 2 * 2 * N * P)
    return flops, 2 * (H * P + 2 * G * N) + 2 * H * P + 4 * H


def work(config: dict, traffic: dict) -> dict:
    """Multiply-adds x 2 of one job, from shapes alone, whatever implements
    them: ``attn_flops`` (scores and weighted values over the keys a position
    sees), ``expert_flops`` (the routed experts' two products for the experts
    a token chose), ``ssd_flops`` and ``ssd_bytes`` (:func:`ssd_work`), and
    ``flops`` (those, the mixers' two projections and convolution, the
    attention block's four projections, shared expert, router and the head at
    each row's last position). Norms, softmax, activations, the gate and the
    gather of the embedding are not counted."""
    c = config
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    rows, S = traffic["rows"], traffic["tokens"]
    mixers, attends, experts = _blocks(c)
    scan_flops, scan_bytes = ssd_work(c)
    mixer = 2 * D * (inner + conv + c["mamba_num_heads"]) + 2 * c["conv_kernel"] * conv + 2 * inner * D
    routed = 2 * 2 * D * c["moe_intermediate_size"] * c["num_experts_per_tok"]
    shared_and_router = 2 * 2 * D * c["moe_shared_expert_intermediate_size"] + 2 * D * c["n_routed_experts"]
    attn = attends * 4 * H * hd * seen_keys(S)
    projections = 2 * D * hd * (2 * H + 2 * KV)
    a_token = mixers * (mixer + scan_flops) + attends * projections + experts * (routed + shared_and_router)
    head = 2 * D * c["vocab_size"]
    return {
        "flops": rows * (attn + S * a_token + head), "bytes": 0,
        "attn_flops": rows * attn, "expert_flops": rows * S * experts * routed,
        "ssd_flops": rows * S * mixers * scan_flops, "ssd_bytes": rows * S * mixers * scan_bytes,
    }


def weight_bytes(config: dict) -> int:
    """Bytes of the parameter tree: bfloat16, but for ``A_log``, ``dt_bias``
    and ``D`` of a mixer and the routing bias of an expert block, float32."""
    c = config
    D, E = c["hidden_size"], c["n_routed_experts"]
    heads, inner = c["mamba_num_heads"], c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    hd = c["head_dim"]
    mixers, attends, experts = _blocks(c)
    mixer = D + D * (inner + conv + heads) + conv * (c["conv_kernel"] + 1) + inner + inner * D
    attention = D + D * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])
    expert = (D + D * E + 2 * D * c["moe_intermediate_size"] * E
              + 2 * D * c["moe_shared_expert_intermediate_size"])
    in_bfloat16 = 2 * c["vocab_size"] * D + D + mixers * mixer + attends * attention + experts * expert
    return 2 * in_bfloat16 + 4 * (mixers * 3 * heads + experts * E)


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.models.nemotron_h import init_nemotron_h

    params = init_nemotron_h(key_of(seed), config)
    rng = np.random.default_rng(seed)
    rows, S = traffic["rows"], traffic["tokens"]
    tokens = zipf_tokens(rng, rows, S, config["vocab_size"], traffic["zipf_exponent"])
    sample = np.sort(rng.choice(rows, size=min(traffic["compare_rows"], rows), replace=False))
    return {
        "params": params, "config": config, "tokens": tokens, "table": Table({"tokens": tokens}),
        "sample": sample, "rows": rows, "S": S, "batch": traffic["batchSize"],
        "limits": traffic["limits"], "weight_bytes": weight_bytes(config),
    }


def fault(state: dict, out: dict):
    """Why this job left the cell's path, or None."""
    import jax

    c, rows = state["config"], state["rows"]
    want = {"hidden": (rows, c["hidden_size"]), "logits": (rows, c["vocab_size"]),
            "expert_load": (rows, _blocks(c)[2], c["n_routed_experts"])}
    if out["shapes"] != want:
        return f"outputs of shapes {out['shapes']}"
    if not out["finite"]:
        return "non-finite outputs"
    routed = state["S"] * c["num_experts_per_tok"]
    if out["routed"] != [routed]:
        return f"a block's load sums to {out['routed']}, not {routed} a row: a token was dropped"
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None and peak < state["weight_bytes"]:
        return f"peak {peak} B < the weights' {state['weight_bytes']} B"
    return None


def reference_outputs(state: dict, fault=None) -> dict:
    return ref.forward(state["params"], state["tokens"][state["sample"]], state["config"], fault)


def checks(state: dict, outputs: list) -> dict:
    """``lm_score_mla.checks``' four numbers against this family's
    reference: ``load_gap_max`` (the sharp one: every expert block's routing
    sees every position of the mixers and the attention block before it),
    ``head_gap_max``, and ``hidden_gap_max`` / ``logit_gap_max`` as the
    smaller of the sampled rows (a last position on a routing tie moves one
    row, a fault every row)."""
    if "want" not in state:
        state["want"] = reference_outputs(state)
    return lm_score_mla.checks(state, outputs)


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
