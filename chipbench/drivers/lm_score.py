"""Driver: one job is ``LMFeaturizer(...).transform(Table)`` over a table of
int32 token rows of one length: batch stacking, upload, the decoder's
forward over whole sequences, the fetch of each row's last-position hidden
state and logits and of every expert layer's load, column assembly, and
whatever tracing or cache load the call itself causes.
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers.featurize import key_of
from chipbench.reference import afmoe as ref

OUTPUTS = {"hidden": "hidden", "logits": "logits", "expert_load": "expert_load"}


def seen_keys(tokens: int, window=None) -> int:
    """Sum over a row's positions of the keys each one sees: ``j <= i`` and,
    with a window, ``j > i - window``."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def work(config: dict, traffic: dict) -> dict:
    """Multiply-adds x 2 of one job, from shapes alone, whatever implements
    them: ``attn_flops`` (scores and weighted values over the keys a position
    sees: what the algorithm needs, not what a blocked kernel computes),
    ``expert_flops`` (the routed experts' three products for the experts a
    token chose) and ``flops`` (those, the projections, dense and shared
    feed-forward, router and the head at each row's last position). Norms,
    softmax, rotary and the gather of the embedding are not counted."""
    c = config
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    rows, S = traffic["rows"], traffic["tokens"]
    projections = 2 * D * hd * (3 * H + 2 * KV)  # q, gate, out; k, v
    routed = 2 * 3 * D * c["moe_intermediate_size"] * c["num_experts_per_tok"]
    shared = 2 * 3 * D * c["moe_intermediate_size"] * c["num_shared_experts"]
    attn = other = 0
    for stack, _, sliding in ref.layer_kinds(c):
        attn += 4 * H * hd * seen_keys(S, c["sliding_window"] if sliding else None)
        other += S * projections
        other += S * (2 * 3 * D * c["intermediate_size"] if stack == "dense"
                      else shared + 2 * D * c["num_experts"])
    experts = S * routed * sum(stack == "moe" for stack, _, _ in ref.layer_kinds(c))
    head = 2 * D * c["vocab_size"]
    return {
        "flops": rows * (attn + experts + other + head), "bytes": 0,
        "attn_flops": rows * attn, "expert_flops": rows * experts,
    }


def weight_bytes(config: dict) -> int:
    """Bytes of the parameter tree in bfloat16 (the router's bias is four)."""
    c = config
    D, hd, F = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    attention = D * hd * (3 * c["num_attention_heads"] + 2 * c["num_key_value_heads"]) + 4 * D + 2 * hd
    total = 2 * c["vocab_size"] * D + D
    for stack, _, _ in ref.layer_kinds(c):
        total += attention
        if stack == "dense":
            total += 3 * D * c["intermediate_size"]
        else:
            total += 3 * D * F * (c["num_experts"] + 1) + D * c["num_experts"] + 2 * c["num_experts"]
    return 2 * total


def zipf_tokens(rng, rows: int, tokens: int, vocabulary: int, exponent: float):
    """Token ids whose frequencies fall as rank ** -exponent, the ranks
    dealt to ids by the seed."""
    p = np.arange(1, vocabulary + 1, dtype=np.float64) ** -exponent
    ranks = rng.choice(vocabulary, size=(rows, tokens), p=p / p.sum())
    return rng.permutation(vocabulary)[ranks].astype(np.int32)


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.models.afmoe import init_afmoe

    params = init_afmoe(key_of(seed), config)
    rng = np.random.default_rng(seed)
    rows, S = traffic["rows"], traffic["tokens"]
    tokens = zipf_tokens(rng, rows, S, config["vocab_size"], traffic["zipf_exponent"])
    sample = np.sort(rng.choice(rows, size=min(traffic["compare_rows"], rows), replace=False))
    return {
        "params": params, "config": config, "tokens": tokens, "table": Table({"tokens": tokens}),
        "sample": sample, "rows": rows, "S": S, "batch": traffic["batchSize"],
        "limits": traffic["limits"], "weight_bytes": weight_bytes(config),
    }


def job(state: dict) -> dict:
    from mmlspark_tpu.featurize.lm import LMFeaturizer

    out = LMFeaturizer(
        inputCol="tokens", outputCols=OUTPUTS, modelParams=state["params"],
        modelConfig={**state["config"], **state.get("model_config", {})}, batchSize=state["batch"],
    ).transform(state["table"])
    got = {name: np.asarray(out[name]) for name in OUTPUTS}
    return {
        "shapes": {name: a.shape for name, a in got.items()},
        "finite": bool(all(np.isfinite(got[n]).all() for n in ("hidden", "logits"))),
        "routed": np.unique(got["expert_load"].sum(axis=-1)).tolist(),
        "sample": {name: a[state["sample"]].copy() for name, a in got.items()},
    }


def fault(state: dict, out: dict):
    """Why this job left the cell's path, or None."""
    import jax

    c, rows = state["config"], state["rows"]
    layers = sum(stack == "moe" for stack, _, _ in ref.layer_kinds(c))
    want = {"hidden": (rows, c["hidden_size"]), "logits": (rows, c["vocab_size"]),
            "expert_load": (rows, layers, c["num_experts"])}
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


def end_to_end(state: dict, window_s: float, jobs: int) -> dict:
    """One "img" of the deep path's metric is one row of the transformed
    table: here one whole token sequence."""
    return {"featurize_img_per_s": (jobs * state["rows"] / window_s, "img/s")}


def reference_outputs(state: dict, fault=None) -> dict:
    return ref.forward(state["params"], state["tokens"][state["sample"]], state["config"], fault)


def checks(state: dict, outputs: list) -> dict:
    """The widest gap of any sampled row of any job: last-position logits
    and hidden state by relative L2, and each expert layer's load by the
    share of its assignments that went elsewhere."""
    if "want" not in state:
        state["want"] = reference_outputs(state)
    want, limits = state["want"], state["limits"]
    routed = state["S"] * state["config"]["num_experts_per_tok"]
    gaps = {
        "logit_gap_max": max(ref.relative_gaps(o["sample"]["logits"], want["logits"]).max() for o in outputs),
        "hidden_gap_max": max(ref.relative_gaps(o["sample"]["hidden"], want["hidden"]).max() for o in outputs),
        "load_gap_max": max(ref.load_gaps(o["sample"]["expert_load"], want["expert_load"], routed).max()
                            for o in outputs),
    }
    return {name: {"value": float(v), "limit": limits[name]} for name, v in gaps.items()}


def compare(state: dict, outputs: list, seed: int) -> dict:
    """Every job's sampled rows against the reference's forward of the same
    weights and tokens, after the table has gone."""
    state.pop("table", None)
    return checks(state, outputs)


def control(state: dict) -> dict:
    """{side: the comparison's numbers with that side in the program's
    place}. ``control`` is the program's own path one step below the
    bfloat16 the configuration states for a matrix product's inputs:
    ``product_dtype`` float8 (e4m3), one whole job at the cell's size.
    (Statistics in bfloat16 where float32 is stated read as the program
    does, on the chip: PERF.md 6a.) Then the reference with each of its
    planted faults standing where the program's output stands."""
    out = {"control": checks(state, [job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))])}
    for name in ref.FAULTS:
        out[name] = checks(state, [{"sample": reference_outputs(state, fault=name)}])
    return out
