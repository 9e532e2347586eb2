"""Driver: ``lm_score``'s job (one ``LMFeaturizer(...).transform(Table)``
over a table of int32 token rows of one length) for the latent-attention
family (``model_type`` ``joyai_llm_flash``): its own work and bytes from
shapes, its own weights and its own plain reference. What is generic in
``lm_score`` (the job, the tokens, the keys a position sees, the metric) is
imported from there; PERF.md section 7 asks a ``benchmark`` issue to fold
the two, keyed by ``model_type``.
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers.featurize import key_of
from chipbench.drivers.lm_score import OUTPUTS, end_to_end, job, seen_keys, zipf_tokens  # noqa: F401
from chipbench.reference import mla_moe as ref


def _layers(config: dict):
    """(dense layers run, expert layers run)."""
    kinds = [kind for kind, _ in ref.layer_kinds(config)]
    return kinds.count("dense"), kinds.count("moe")


def work(config: dict, traffic: dict) -> dict:
    """Multiply-adds x 2 of one job, from shapes alone, whatever implements
    them: ``attn_flops`` (scores over keys of ``nope + rope`` and weighted
    values of ``v``, over the keys a position sees: what the algorithm
    needs in its up-projected form, not what a blocked kernel computes),
    ``expert_flops`` (the routed experts' three products for the experts a
    token chose), ``latent_flops`` (the four low-rank projections: into and
    out of the query latent and the key/value latent) and ``flops`` (those,
    the output projection, dense and shared feed-forward, router and the
    head at each row's last position). Norms, softmax, rotary and the
    gather of the embedding are not counted."""
    c = config
    D, H = c["hidden_size"], c["num_attention_heads"]
    key, dv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    rows, S = traffic["rows"], traffic["tokens"]
    dense, moe = _layers(c)
    latent = 2 * (D * rq + rq * H * key + D * (rkv + c["qk_rope_head_dim"])
                  + rkv * H * (c["qk_nope_head_dim"] + dv))
    out_proj = 2 * H * dv * D
    F = c["moe_intermediate_size"]
    routed = 2 * 3 * D * F * c["num_experts_per_tok"]
    shared_and_router = 2 * 3 * D * F * c["n_shared_experts"] + 2 * D * c["n_routed_experts"]
    attn = (dense + moe) * 2 * H * (key + dv) * seen_keys(S)
    other = S * ((dense + moe) * out_proj + dense * 2 * 3 * D * c["intermediate_size"]
                 + moe * shared_and_router)
    head = 2 * D * c["vocab_size"]
    return {
        "flops": rows * (attn + S * ((dense + moe) * latent + moe * routed) + other + head),
        "bytes": 0, "attn_flops": rows * attn, "expert_flops": rows * S * moe * routed,
        "latent_flops": rows * S * (dense + moe) * latent,
    }


def weight_bytes(config: dict) -> int:
    """Bytes of the parameter tree in bfloat16 (the router's bias is four)."""
    c = config
    D, H, F = c["hidden_size"], c["num_attention_heads"], c["moe_intermediate_size"]
    rq, rkv, rope = c["q_lora_rank"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    attention = (D * rq + rq * H * (c["qk_nope_head_dim"] + rope) + D * (rkv + rope)
                 + rkv * H * (c["qk_nope_head_dim"] + c["v_head_dim"]) + H * c["v_head_dim"] * D
                 + rq + rkv + 2 * D)
    dense, moe = _layers(c)
    E = c["n_routed_experts"]
    total = 2 * c["vocab_size"] * D + D + (dense + moe) * attention
    total += dense * 3 * D * c["intermediate_size"]
    total += moe * (3 * D * F * (E + c["n_shared_experts"]) + D * E + 2 * E)
    return 2 * total


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.models.mla_moe import init_mla_moe

    params = init_mla_moe(key_of(seed), config)
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
            "expert_load": (rows, _layers(c)[1], c["n_routed_experts"])}
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
    """Of every job, over its sampled rows: last-position logits and hidden
    state by relative L2 against the reference's, the SMALLER gap of the
    rows (a tie between a last position's 8th and 9th expert moves a row by
    0.1-0.6, a fault moves every row; so one row on a tie cannot fail a
    run); each expert layer's load by the share of its assignments that
    went elsewhere, the widest of any row; and the logits against the
    reference's head over the job's OWN hidden state, the widest of any row
    (``head_gap_max``: a following check, which no tie moves)."""
    if "want" not in state:
        state["want"] = reference_outputs(state)
    want, limits = state["want"], state["limits"]
    routed = state["S"] * state["config"]["num_experts_per_tok"]
    params = state["params"]
    gaps = {
        "logit_gap_max": max(ref.relative_gaps(o["sample"]["logits"], want["logits"]).min() for o in outputs),
        "hidden_gap_max": max(ref.relative_gaps(o["sample"]["hidden"], want["hidden"]).min() for o in outputs),
        "load_gap_max": max(ref.load_gaps(o["sample"]["expert_load"], want["expert_load"], routed).max()
                            for o in outputs),
        "head_gap_max": max(ref.relative_gaps(o["sample"]["logits"],
                                              ref.head_of(params, o["sample"]["hidden"])).max() for o in outputs),
    }
    return {name: {"value": float(v), "limit": limits[name]} for name, v in gaps.items()}


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
