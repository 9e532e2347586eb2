"""Driver: ``lm_score``'s job (one ``LMFeaturizer(...).transform(Table)`` over
a table of int32 token rows of one length) for the sparse-attention family
(``model_type`` ``KeyeVL2``): batch stacking, upload, the decoder's forward
over whole sequences, the fetch of each row's last-position hidden state and
logits, of every layer's expert load and of every layer's key selection
(the keys it kept, and the last position's kept keys), column assembly. Its
own work and bytes from shapes, weights, plain reference and comparison;
what is generic comes from ``lm_score`` (the tokens, the keys a position
sees, the metric).
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers.featurize import key_of
from chipbench.drivers.lm_score import end_to_end, seen_keys, zipf_tokens  # noqa: F401
from chipbench.reference import keye_vl2 as ref

OUTPUTS = {"hidden": "hidden", "logits": "logits", "expert_load": "expert_load", "key_selection": "key_selection"}


def kept_keys(tokens: int, topk: int) -> int:
    """Sum over a row's positions of the keys each keeps: ``min(i + 1, topk)``."""
    below = min(tokens, topk)
    return below * (below + 1) // 2 + (tokens - below) * topk


def work(config: dict, traffic: dict) -> dict:
    """Multiply-adds x 2 of one job, from shapes alone, whatever implements
    them, counted on the SPARSE work (a kernel that computes dense and masks
    reads as far below its roofline as it is): ``indexer_flops`` (the
    indexer's score of every key a position sees, ``2 H_I d_I`` a pair) and
    ``indexer_bytes`` (its queries, key and weights read once, one bit a
    pair it sees written); ``sparse_attn_flops`` (scores and weighted values
    over the ``min(i + 1, topk)`` keys a position keeps) and
    ``sparse_attn_bytes`` (queries, keys and values read once, the output
    written once, one bit a pair read); ``expert_flops`` (the routed
    experts' three products for the experts a token chose) and ``flops``
    (those, the four attention projections, the indexer's three, the
    router and the head at each row's last position). Norms, softmax,
    rotary, the selection itself and the gather of the embedding are not
    counted."""
    c = config
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    HI, dI, topk = (c["sa_config"][k] for k in ("indexer_num_heads", "indexer_head_dim", "topk"))
    rows, S, layers = traffic["rows"], traffic["tokens"], c["layers"]
    seen, kept = rows * layers * seen_keys(S), rows * layers * kept_keys(S, topk)
    tokens = rows * S * layers  # a token through a layer
    parts = {
        "indexer_flops": seen * 2 * HI * dI,
        "sparse_attn_flops": kept * 4 * H * hd,
        "expert_flops": tokens * 2 * 3 * D * c["moe_intermediate_size"] * c["num_experts_per_tok"],
        "projection_flops": tokens * 2 * D * (hd * (2 * H + 2 * KV) + HI * dI + dI + HI),
        "router_flops": tokens * 2 * D * c["num_experts"],
    }
    return {"flops": sum(parts.values()) + rows * 2 * D * c["vocab_size"], "bytes": 0, **parts,
            "indexer_bytes": tokens * (2 * HI * dI + 2 * dI + 4 * HI) + seen // 8,
            "sparse_attn_bytes": tokens * 2 * (2 * H * hd + 2 * KV * hd) + seen // 8}


def weight_bytes(config: dict) -> int:
    """Bytes of the parameter tree, all of it bfloat16."""
    c = config
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    HI, dI = c["sa_config"]["indexer_num_heads"], c["sa_config"]["indexer_head_dim"]
    E, F = c["num_experts"], c["moe_intermediate_size"]
    attention = D * hd * (2 * H + 2 * KV) + 2 * hd + 2 * D
    indexer = D * (HI * dI + dI + HI) + 2 * dI
    layer = attention + indexer + D * E + 3 * E * D * F
    return 2 * (2 * c["vocab_size"] * D + D + c["layers"] * layer)


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.models.keye_vl2 import init_keye_vl2

    params = init_keye_vl2(key_of(seed), config)
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
        "kept": np.unique(got["key_selection"][..., 0]).tolist(),
        "sample": {name: a[state["sample"]].copy() for name, a in got.items()},
    }


def fault(state: dict, out: dict):
    """Why this job left the cell's path, or None: the outputs' shapes, a
    non-finite value, a token dropped by the routing, a layer whose
    selection kept other than ``min(i + 1, topk)`` keys at a position
    summed over a row, or weights the device does not hold."""
    import jax

    from mmlspark_tpu.ops.sparse_attention import words_for

    c, rows, S = state["config"], state["rows"], state["S"]
    layers = c["layers"]
    want = {"hidden": (rows, c["hidden_size"]), "logits": (rows, c["vocab_size"]),
            "expert_load": (rows, layers, c["num_experts"]), "key_selection": (rows, layers, 1 + words_for(S))}
    if out["shapes"] != want:
        return f"outputs of shapes {out['shapes']}"
    if not out["finite"]:
        return "non-finite outputs"
    routed = S * c["num_experts_per_tok"]
    if out["routed"] != [routed]:
        return f"a layer's load sums to {out['routed']}, not {routed} a row: a token was dropped"
    kept = kept_keys(S, c["sa_config"]["topk"])
    if out["kept"] != [kept]:
        return f"a layer kept {out['kept']} keys over a row, not {kept}"
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None and peak < state["weight_bytes"]:
        return f"peak {peak} B < the weights' {state['weight_bytes']} B"
    return None


def reference_outputs(state: dict, fault=None) -> dict:
    return ref.forward(state["params"], state["tokens"][state["sample"]], state["config"], fault)


def _kept_last(selection, S: int):
    from mmlspark_tpu.ops.sparse_attention import unpack_keys

    return unpack_keys(selection[..., 1:], S)


def checks(state: dict, outputs: list) -> dict:
    """Of every job, over its sampled rows: last-position logits and hidden
    state by relative L2 against the reference's forward, and each layer's
    kept keys of the last position by the share of the reference's that the
    program did not keep, each the SMALLER of the rows (a rounding that
    moves a key across the cut, or a token's 8th expert past its 9th, moves
    what the next layer reads: after six layers a row reads 0.05-0.15, PERF.md
    6a; a fault moves every row); the first layer's kept keys, whose inputs
    no rounding of the program has touched yet (``kept_gap_first``: the
    selection kernel itself, the widest of any row); each layer's load by the
    share of its assignments that went elsewhere, the widest of any row; the
    logits against the reference's head over the job's OWN hidden state
    (``head_gap_max``: a following check, which no selection or routing
    moves). A side with no ``key_selection`` (a planted fault of the
    reference) hands over the reference's own ``last_kept``."""
    if "want" not in state:
        state["want"] = reference_outputs(state)
    want, limits, params, S = state["want"], state["limits"], state["params"], state["S"]
    routed = S * state["config"]["num_experts_per_tok"]

    def kept(o):
        got = o["last_kept"] if "last_kept" in o else _kept_last(o["sample"]["key_selection"], S)
        return ref.kept_gaps(got, want["last_kept"])  # (rows, layers)

    gaps = {
        "logit_gap_max": max(ref.relative_gaps(o["sample"]["logits"], want["logits"]).min() for o in outputs),
        "hidden_gap_max": max(ref.relative_gaps(o["sample"]["hidden"], want["hidden"]).min() for o in outputs),
        "kept_gap_max": max(kept(o).max(axis=1).min() for o in outputs),
        "kept_gap_first": max(kept(o)[:, 0].max() for o in outputs),
        "load_gap_max": max(ref.load_gaps(o["sample"]["expert_load"], want["expert_load"], routed).max()
                            for o in outputs),
        "head_gap_max": max(ref.relative_gaps(o["sample"]["logits"], ref.head_of(params, o["sample"]["hidden"])).max()
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
    place}: the program's own path with ``product_dtype`` float8 (e4m3), one
    step below the bfloat16 the configuration states for a matrix product's
    inputs, one whole job at the cell's size; then the reference with each
    of its planted faults standing where the program's output stands."""
    out = {"control": checks(state, [job(dict(state, model_config={"product_dtype": "float8_e4m3fn"}))])}
    for name in ref.FAULTS:
        side = reference_outputs(state, fault=name)
        out[name] = checks(state, [{"sample": side, "last_kept": side["last_kept"]}])
    return out
