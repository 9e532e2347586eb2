"""Driver: one job is ``LightGBMClassifier(...).fit(Table)`` on host float64
data: prepare, host binning, upload, U build, every boosting iteration, tree
fetch. Nothing device-resident is carried from one job to the next.
"""

from __future__ import annotations

import contextlib

import numpy as np

from chipbench.reference import gbdt as ref

LANE, ROW_ALIGN = 128, 512  # the one-hot's padding, from its documented layout
BINNING_SPAN = "chipbench.binning"
REGRET_ROWS = 62_500  # tree 0's nodes that hold this many rows are searched again
COARSE_BINS = 15  # the control's: 4-bit bins where the configuration states 8-bit


def make_data(rows: int, test_rows: int, features: int, seed: int):
    """Higgs-like continuous float64 features (the chip_smoke recipe): four
    informative columns, the rest noise, label noise of 0.5."""
    n = rows + test_rows
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, features)).astype(np.float64)
    logit = (
        X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + 0.8 * np.sin(X[:, 3])
        + 0.5 * rng.normal(size=n)
    )
    y = (logit > 0).astype(np.float64)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def u_bytes(rows: int, features: int, max_bin: int) -> int:
    """Bytes of the resident int8 one-hot: every continuous feature owns
    max_bin + 1 rows of it (its edges, the missing bin, the overflow bin),
    padded to the lane block; rows padded to the row block."""
    k_pad = -(-features * (max_bin + 1) // LANE) * LANE
    return -(-rows // ROW_ALIGN) * ROW_ALIGN * k_pad


def work(config: dict, traffic: dict) -> dict:
    """The least one fit must move, whatever the implementation: every tree
    reads every row's bin ids (one byte a feature) and its gradient pair
    (two float32) once."""
    est = _estimator(config, traffic)
    per_row = config["features"] * 1 + 8
    return {"bytes": est["numIterations"] * traffic["rows"] * per_row, "flops": 0}


def _estimator(config: dict, traffic: dict) -> dict:
    return {**config["estimator"], **traffic.get("estimator", {})}


@contextlib.contextmanager
def fit_events():
    """The histogram-path events a fit publishes while the block runs."""
    from mmlspark_tpu.observability.events import (
        HistogramChunked, HistogramDegraded, MemoryPressure, get_bus,
    )

    seen: list = []
    kinds = (HistogramChunked, HistogramDegraded, MemoryPressure)

    def listener(event) -> None:
        if isinstance(event, kinds):
            seen.append(type(event).__name__)

    bus = get_bus()
    bus.add_listener(listener)
    try:
        yield seen
    finally:
        bus.remove_listener(listener)


@contextlib.contextmanager
def binning_span():
    """The benchmark's own span around the program's host binning call. The
    program has a span there only on its partitioned path (numExecutors > 0),
    which a default fit does not take."""
    import mmlspark_tpu.lightgbm.base as base
    from mmlspark_tpu.observability.tracing import get_tracer

    inner = base.bin_dataset

    def timed(X, **kwargs):
        with get_tracer().span(BINNING_SPAN, rows=int(X.shape[0])):
            return inner(X, **kwargs)

    base.bin_dataset = timed
    try:
        yield
    finally:
        base.bin_dataset = inner


def setup(config: dict, traffic: dict, seed: int) -> dict:
    import jax

    from mmlspark_tpu.data.table import Table

    Xtr, ytr, Xte, _ = make_data(
        traffic["rows"], traffic["test_rows"], config["features"], seed
    )
    est = _estimator(config, traffic)
    return {
        "train": Table({"features": Xtr, "label": ytr}),
        "test": Table({"features": Xte}),
        "Xtr": Xtr, "ytr": ytr, "Xte": Xte,
        "estimator": est,
        "u_path": traffic["u_path"] if jax.devices()[0].platform == "tpu" else None,
        "u_bytes": u_bytes(traffic["rows"], config["features"], est["maxBin"]),
        "limits": traffic["limits"],
    }


def job(state: dict) -> dict:
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    with fit_events() as events, binning_span():
        model = LightGBMClassifier(**state["estimator"]).fit(state["train"])
    return {"model": model, "events": list(events)}


def fault(state: dict, out: dict):
    """Why this job does not count as a fit on the cell's path, or None. The
    program falls back in silence in many places; a fallback is not a fit."""
    import jax

    events = out["events"]
    bad = [e for e in events if e != "HistogramChunked"]
    if bad:
        return f"the fit degraded: {bad}"
    path = state["u_path"]
    if path == "resident":
        if events:
            return f"the resident cell chunked: {events}"
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
        if peak < state["u_bytes"]:
            return f"peak {peak} B < resident U {state['u_bytes']} B: no resident U ran"
    if path == "chunked" and "HistogramChunked" not in events:
        return "the chunked cell published no HistogramChunked"
    return None


def end_to_end(state: dict, window_s: float, jobs: int) -> dict:
    return {"fit_s": (window_s / jobs if jobs else float("inf"), "s")}


def forest_of(model) -> ref.Forest:
    b = model.booster
    return ref.Forest(
        feature=np.asarray(b.split_feature), threshold=np.asarray(b.split_threshold, np.float32),
        left=np.asarray(b.left_child), right=np.asarray(b.right_child),
        is_leaf=np.asarray(b.is_leaf), value=np.asarray(b.leaf_values, np.float32),
        init_score=float(np.asarray(b.init_score).reshape(-1)[0]),
    )


def compare(state: dict, outputs: list, seed: int) -> dict:
    """The last fit's model against the reference (every leaf of every tree,
    and the device predict on the held-out rows); the window's other fits
    saw the same rows and have to give the same text."""
    model = outputs[-1]["model"]
    text = model.get_model_string()
    differ = sum(o["model"].get_model_string() != text for o in outputs[:-1])
    raw = np.asarray(model.transform(state["test"])["rawPrediction"])[:, 1]
    forest = forest_of(model)
    outputs.clear()
    del model
    return checks(state, forest, raw, differ)


def checks(state: dict, forest: ref.Forest, raw: np.ndarray, differ: int) -> dict:
    est = state["estimator"]
    xt = np.ascontiguousarray(state["Xtr"].astype(np.float32).T)
    gaps = ref.leaf_gaps(forest, ref.leaf_sums(xt, state["ytr"], forest), est["learningRate"])
    gains = ref.split_gains(
        xt, state["ytr"], forest, REGRET_ROWS, est.get("minDataInLeaf", 20)
    )
    want = ref.margins(np.ascontiguousarray(state["Xte"].astype(np.float32).T), forest)
    values = {
        "leaf_gap_max": float(gaps.max()),
        "leaf_gap_rms": float(np.sqrt(np.mean(gaps ** 2))),
        "predict_gap": float(np.abs(raw - want).max()),
        "models_differ": differ,
    }
    if len(gains):  # a toy fit has no node large enough to search again
        regrets = 1.0 - gains[:, 0] / gains[:, 1]
        values["split_regret_rms"] = float(np.sqrt(np.mean(regrets ** 2)))
    return {k: {"value": v, "limit": state["limits"][k]} for k, v in values.items()}


def control(state: dict) -> dict:
    """{side: the comparison's numbers with that side in the program's
    place}. ``control``: the program's own paths one precision below what
    the configuration states: ``useQuantizedGrad`` (int8 gradients for the
    bfloat16 stated) for the leaf numbers, ``maxBin`` 15 (4-bit bins for the
    8-bit stated; one tree, the regret reads tree 0 alone) for the split
    regret, and the reference's margins with bfloat16 leaf values for the
    predict number. Then two faults a fit can have, read at the cell's own
    size: a boosting step that hands its margins back unchanged (the next
    tree is the same tree again), and half of the rows left out of the fit."""
    import copy

    xte = np.ascontiguousarray(state["Xte"].astype(np.float32).T)

    def fit(**estimator):
        return forest_of(job(dict(state, estimator={**state["estimator"], **estimator}))["model"])

    forest = fit(useQuantizedGrad=True)
    rounded = ref.margins(xte, forest, values=ref.bfloat16(forest.value))
    low = checks(state, forest, rounded, 0)
    coarse = fit(maxBin=COARSE_BINS, numIterations=1)
    low["split_regret_rms"] = checks(state, coarse, ref.margins(xte, coarse), 0)["split_regret_rms"]

    honest = fit()
    stuck = copy.deepcopy(honest)
    for field in ("feature", "threshold", "left", "right", "is_leaf", "value"):
        getattr(stuck, field)[1] = getattr(stuck, field)[0]
    half = dict(state, train=state["train"].slice(0, state["train"].num_rows // 2))
    halved = forest_of(job(half)["model"])
    raw = ref.margins(xte, honest)
    return {
        "control": low,
        "fault_stuck": checks(state, stuck, raw, 0),
        "fault_half": checks(state, halved, ref.margins(xte, halved), 0),
    }
