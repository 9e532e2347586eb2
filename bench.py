"""Headline benchmark: GBDT training on TPU vs a REAL CPU GBDT.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N, ...}

Workload: binary-classification boosting on a Higgs-like dense matrix
(BASELINE.json config 3's shape at bench-friendly scale), leaf-wise growth
with LightGBM-default 31 leaves — the flagship semantics.

``value`` is TPU row-iterations/sec (rows × boosting iterations / fit wall
time; binning included, one-time XLA compile excluded — the persistent
compilation cache is placed by ``configure_compile_cache``). ``vs_baseline`` is the speedup over
sklearn's ``HistGradientBoostingClassifier`` — the same histogram-GBDT
algorithm family as LightGBM, run at matched settings (same rows, features,
iterations, leaves, bins, learning rate; median of 3 runs). Both sides also
report held-out AUC so the comparison is at matched quality, per the
"identical AUC" clause of the ≥10× north star (BASELINE.md).
"""

import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 400_000))
N_FEATURES = int(os.environ.get("BENCH_FEATURES", 28))
N_ITERS = int(os.environ.get("BENCH_ITERS", 100))  # LightGBM's default
N_TEST = 50_000
NUM_LEAVES = 31
LEARNING_RATE = 0.1
MAX_BIN = 255
CPU_RUNS = 3
TPU_RUNS = 5  # median-of-5: the host->device upload varies run to run


def _make_data(n, f, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float64)
    logit = (
        X[:, 0] * 1.5
        + X[:, 1] * X[:, 2]
        + 0.8 * np.sin(X[:, 3])
        + 0.5 * rng.normal(size=n)
    )
    y = (logit > 0).astype(np.float64)
    return X, y


# Mixed workload: the data distribution real tabular users have —
# categorical + ordinal + a few continuous columns (the reference's own
# perf claims are dataset-level, lightgbm.md:17-21). Effective bin width
# B≈64, the regime where the packed-U layout (K = Σ_f B_f) shines.
MIXED_CARDS = (4, 8, 12, 16, 24, 32, 48, 64)  # 8 categorical features
MIXED_ORDINALS = 12  # integer features with <= 64 levels
MIXED_CONTINUOUS = 8
MIXED_MAX_BIN = 63


def _make_mixed_data(n, seed=0):
    rng = np.random.default_rng(seed)
    cats = [rng.integers(0, c, size=n).astype(np.float64) for c in MIXED_CARDS]
    effs = [rng.normal(size=c) for c in MIXED_CARDS]
    ords = [
        rng.integers(0, 64, size=n).astype(np.float64)
        for _ in range(MIXED_ORDINALS)
    ]
    conts = rng.normal(size=(n, MIXED_CONTINUOUS))
    logit = (
        effs[1][cats[1].astype(int)]
        + 0.8 * effs[4][cats[4].astype(int)]
        + 0.03 * (ords[0] - 32)
        + 0.5 * ((ords[1] > 40) & (cats[0] == 2))
        + conts[:, 0]
        + 0.6 * rng.normal(size=n)
    )
    y = (logit > 0).astype(np.float64)
    X = np.column_stack(cats + ords + [conts])
    cat_idx = list(range(len(MIXED_CARDS)))
    return X, y, cat_idx


# Sparse workload: blocks of one-hot indicator columns (the output of any
# categorical-encoding featurizer — and the shape EFB was invented for:
# LightGBM paper §4). Indicators within a block are mutually exclusive, so
# feature bundling packs each block into ONE dense column and the histogram
# width K = Σ_f B_f drops measurably; the bench reports K before/after.
SPARSE_BLOCKS = 12
SPARSE_CARD = 16  # indicators per block -> 192 one-hot features
SPARSE_CONTINUOUS = 2
SPARSE_MAX_BIN = 63
SPARSE_ROWS = min(N_ROWS, 200_000)


def _make_sparse_data(n, seed=2):
    rng = np.random.default_rng(seed)
    cats = rng.integers(0, SPARSE_CARD, size=(n, SPARSE_BLOCKS))
    effs = rng.normal(size=(SPARSE_BLOCKS, SPARSE_CARD))
    X = np.zeros((n, SPARSE_BLOCKS * SPARSE_CARD + SPARSE_CONTINUOUS))
    X[
        np.arange(n)[:, None],
        np.arange(SPARSE_BLOCKS)[None, :] * SPARSE_CARD + cats,
    ] = 1.0
    conts = rng.normal(size=(n, SPARSE_CONTINUOUS))
    X[:, SPARSE_BLOCKS * SPARSE_CARD:] = conts
    logit = (
        effs[0][cats[:, 0]]
        + 0.8 * effs[3][cats[:, 3]]
        + 0.6 * conts[:, 0]
        + 0.5 * rng.normal(size=n)
    )
    y = (logit > 0).astype(np.float64)
    return X, y


def _load_real_data():
    """(source, X, y) for the gbdt_real_* block. Prefers the vendored
    Covertype sample (``tools/fetch_covtype.py`` writes it; requires
    network once, ROADMAP 5a) — 10 continuous + 44 binary indicator
    columns, the canonical EFB dataset. Falls back to sklearn's bundled
    digits (odd vs even digits) so the real-data block always runs in
    network-less containers; the JSON labels which source was used."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tests", "fixtures", "covtype_sample.npz",
    )
    if os.path.exists(path):
        d = np.load(path)
        return "covtype_sample", d["X"].astype(np.float64), d["y"].astype(np.float64)
    from sklearn.datasets import load_digits

    d = load_digits()
    return (
        "sklearn_digits_odd_vs_even",
        d.data.astype(np.float64),
        (d.target % 2).astype(np.float64),
    )


def _bundling_k(X, max_bin):
    """(k_before, k_after, num_features, num_columns, conflicts) from one
    host binning pass each way — the measured histogram-width reduction
    feature bundling buys on this matrix."""
    from mmlspark_tpu.lightgbm.binning import bin_dataset

    _, m_plain = bin_dataset(X, max_bin=max_bin)
    _, m_bund = bin_dataset(X, max_bin=max_bin, feature_bundling=True)
    k_before = int(sum(int(b) for b in m_plain.num_bins))
    spec = m_bund.bundles
    if spec is None:
        return k_before, k_before, X.shape[1], X.shape[1], 0
    return (
        k_before,
        int(spec.k_packed),
        int(spec.num_features),
        int(spec.num_columns),
        int(spec.conflict_count),
    )


def _chunked_u_evidence():
    """Static proof (no device needed) that a >1M-row headline-shape fit
    takes the chunked MXU path, not a gather fallback: runs the exact
    u-spec selection logic train() uses for a 4M-row fit of the headline
    feature set against the configured HBM budget."""
    from mmlspark_tpu.ops.u_histogram import (
        chunked_u_spec,
        make_u_spec,
        num_u_chunks,
        u_bytes,
    )

    rows = 4_000_000
    try:
        budget = int(os.environ.get("MMLSPARK_TPU_U_BUDGET", str(8 << 30)))
    except ValueError:
        budget = 8 << 30
    spec = make_u_spec(MAX_BIN + 1, N_FEATURES, None)
    resident = u_bytes(rows, spec)
    out = {
        "rows": rows,
        "k_packed": int(spec.k_pad),
        "budget_bytes": budget,
        "resident_one_hot_bytes": int(resident),
    }
    if resident > budget:
        cspec = chunked_u_spec(rows, spec, budget)
        out["path"] = "mxu_chunked"
        out["chunk_rows"] = int(cspec.chunk_rows)
        out["num_chunks"] = int(num_u_chunks(rows, cspec))
    else:
        out["path"] = "mxu_resident"
    return out


def _hist_bytes_evidence(leaf_batch=8):
    """Analytic bytes-per-build roofline for the 255-bin continuous
    headline shape (deviceless, like the chunked-U selection trace): the
    row-proportional HBM bytes ONE histogram pass over a ``leaf_batch``
    split frontier must stream, per variant. "r05_u_path" is the previous
    round's hot path (resident U, both children built, f32 panel);
    "subtraction" keys only the smaller children (panel width halves,
    siblings derive from the leaf cache); "subtraction_packed" rides the
    quantized int8 panel; "fused_subtraction_packed" is the Pallas
    bin+scatter-add kernel, which reads the raw binned rows once (int32
    lanes + an 8-row f32 aux block) instead of re-streaming the K_pad-byte
    one-hot row."""
    from mmlspark_tpu.ops.u_histogram import make_u_spec

    spec = make_u_spec(MAX_BIN + 1, N_FEATURES, None)
    k = leaf_batch
    per_row = {
        "r05_u_path": spec.k_pad + 3 * 2 * k * 4,
        "subtraction": spec.k_pad + 3 * k * 4,
        "subtraction_packed": spec.k_pad + 3 * k * 1,
        "fused_subtraction_packed": 4 * N_FEATURES + 32 + 3 * k * 1,
    }
    before = per_row["r05_u_path"]
    return {
        "shape": f"{N_FEATURES}cont x {MAX_BIN + 1}bins, leaf_batch={k}",
        "k_packed": int(spec.k_pad),
        "bytes_per_row_per_build": per_row,
        "reduction_vs_r05": {
            name: round(before / b, 3) for name, b in per_row.items()
        },
    }


def _auc(y, score):
    from mmlspark_tpu.lightgbm.objectives import auc

    return auc(y, score, np.ones(len(y)))


def _fit_tpu(
    X, y, Xt, max_bin=MAX_BIN, cat_idx=None, extra_opts=None,
    bundling=False, n_iters=None,
):
    """Returns (wire_secs, resident_secs, binning_host_secs, wire_runs,
    resident_runs, test margins, booster)."""
    from mmlspark_tpu.lightgbm.binning import bin_dataset, bin_dataset_to_device
    from mmlspark_tpu.lightgbm.train import TrainOptions, train

    opts = TrainOptions(
        objective="binary",
        num_iterations=n_iters or N_ITERS,
        num_leaves=NUM_LEAVES,
        learning_rate=LEARNING_RATE,
        max_bin=max_bin,
        growth="leafwise",
        **(extra_opts or {}),
    )
    kw = {"categorical_features": cat_idx} if cat_idx else {}
    if bundling:
        kw["feature_bundling"] = True
    # Compile warm-up: jit programs are shape-specialized, so run ONE
    # full-size fit untimed; the timed runs below then hit the in-process
    # executable cache and measure binning + boosting only. Median of
    # TPU_RUNS timed fits — host<->device transfer latency varies run to
    # run, and the CPU side is already a median.
    # Binning + upload run overlapped (bin_dataset_to_device): chunked
    # async device_put hides the host binning behind the wire transfer.
    bins, mapper = bin_dataset_to_device(X, max_bin=max_bin, **kw)
    train(bins, y, opts, mapper=mapper)

    times = []
    result = None
    for _ in range(TPU_RUNS):
        t0 = time.perf_counter()
        bins, mapper = bin_dataset_to_device(X, max_bin=max_bin, **kw)
        result = train(bins, y, opts, mapper=mapper)
        times.append(time.perf_counter() - t0)
    # Decomposition: the same fit with bins already device-resident (median
    # of TPU_RUNS, like the wire-inclusive number): the fit time with the
    # host->device upload taken out.
    resident = []
    for _ in range(TPU_RUNS):
        t0 = time.perf_counter()
        result = train(bins, y, opts, mapper=mapper)
        resident.append(time.perf_counter() - t0)
    resident_secs = float(np.median(resident))
    # Host-only binning cost (no device in the path) so the artifact's
    # wire-vs-compute split is self-evident: wire ≈ median(times) -
    # resident - binning overlap; binning itself is stable host work.
    t0 = time.perf_counter()
    bin_dataset(X, max_bin=max_bin, **kw)
    binning_secs = time.perf_counter() - t0
    margins = result.booster.raw_margin(Xt)[:, 0]
    return (
        float(np.median(times)),
        resident_secs,
        binning_secs,
        [round(t, 3) for t in times],
        [round(t, 3) for t in resident],
        margins,
        result.booster,
    )


def _predict_throughput_tpu(booster, X, reps=10):
    """Warm on-device predict loop (path-matrix formulation): rows/sec with
    the input device-resident — host->device transfer excluded, the same
    measurement discipline as the training number (compile excluded)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mmlspark_tpu.lightgbm.booster import (
        _paths_cache,
        _predict_margin_paths_jit,
    )

    t = booster._used_trees(None)
    pc = _paths_cache(booster, t)
    Xd = jnp.asarray(X, jnp.float32)
    cargs = [jnp.asarray(a) for a in (pc.feats, pc.thrs, pc.nanl, pc.zm, pc.P, pc.plen, pc.lvals)]
    isc = jnp.asarray(booster.init_score)

    @jax.jit
    def loop(Xd, f, th, nl, zm_, Pm, pl, lv, isc):
        def body(i, acc):
            m = _predict_margin_paths_jit(
                Xd * (1 + i.astype(jnp.float32) * 1e-9), f, th, nl, zm_, Pm, pl, lv, isc, 1
            )
            return acc + m[0, 0]

        import jax.lax as _lax

        return _lax.fori_loop(0, reps, body, jnp.float32(0.0))

    float(loop(Xd, *cargs, isc))  # compile
    t0 = time.perf_counter()
    float(loop(Xd, *cargs, isc))
    return len(X) * reps / (time.perf_counter() - t0)


def _predict_throughput_cpu(clf, X, reps=3):
    clf.predict_proba(X[:1000])  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        clf.predict_proba(X)
    return len(X) * reps / (time.perf_counter() - t0)


def _fit_cpu(X, y, Xt, max_bin=MAX_BIN, cat_idx=None):
    """sklearn HistGradientBoosting (LightGBM-style CPU GBDT); median of
    CPU_RUNS fits for a stable baseline. Categorical slots are declared to
    the CPU engine too, so the mixed comparison is algorithm-for-algorithm
    (both sides run native categorical split search)."""
    from sklearn.ensemble import HistGradientBoostingClassifier

    cat_kw = {}
    if cat_idx:
        mask = np.zeros(X.shape[1], dtype=bool)
        mask[cat_idx] = True
        cat_kw["categorical_features"] = mask
    times, margins = [], None
    for run in range(CPU_RUNS):
        clf = HistGradientBoostingClassifier(
            max_iter=N_ITERS,
            max_leaf_nodes=NUM_LEAVES,
            learning_rate=LEARNING_RATE,
            max_bins=max_bin,
            early_stopping=False,
            random_state=run,
            **cat_kw,
        )
        t0 = time.perf_counter()
        clf.fit(X, y)
        times.append(time.perf_counter() - t0)
        margins = clf.decision_function(Xt)
    return float(np.median(times)), margins, clf


def sweep_guard(block):
    """Regression guard for the many-models sweep plane (shared with
    tests/test_sweep.py): batched fitting must beat the candidate-at-a-
    time baseline on models/sec AND actually amortize compilation — at
    least one shape-bucket holds >1 candidate, and the batched run
    compiles strictly fewer programs than it has candidates."""
    cand = block["sweep_candidates"]
    assert cand >= 12, block
    assert max(block["sweep_bucket_sizes"]) > 1, block
    assert block["sweep_batched_compiles"] < cand, block
    assert (
        block["sweep_models_per_sec_batched"]
        > block["sweep_models_per_sec_sequential"]
    ), block
    return block


def _sweep_block():
    """Many-models sweep evidence (docs/automl_sweep.md): a >=12-candidate
    GBDT grid fit through the batched ``TrainValidSweep`` plane vs the
    same candidates fit one at a time, with ``ProfileCompiled`` counts as
    the compile-amortization proof (buckets, not candidates, compile)."""
    from mmlspark_tpu.automl.hyperparam import GridSpace
    from mmlspark_tpu.automl.tune import _evaluate
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.observability import ProfileCompiled, get_bus
    from mmlspark_tpu.sweep import TrainValidSweep, bucket_candidates

    rows = min(N_ROWS, int(os.environ.get("BENCH_SWEEP_ROWS", 20_000)))
    iters = min(N_ITERS, int(os.environ.get("BENCH_SWEEP_ITERS", 10)))
    n_cand = max(12, int(os.environ.get("BENCH_SWEEP_CANDIDATES", 12)))
    # off-grid learning rates (no collision with the headline fits) x two
    # numLeaves values -> exactly two shape-buckets of n_cand/2 candidates
    lrs = [
        round(float(v), 4)
        for v in np.linspace(0.055, 0.295, -(-n_cand // 2))
    ]
    space = GridSpace({"learningRate": lrs, "numLeaves": [15, 31]})
    maps = list(space.param_maps())

    X, y = _make_data(rows, N_FEATURES, seed=9)
    tbl = Table({"features": X, "label": y.astype(np.float64)})
    est = LightGBMClassifier(
        labelCol="label", featuresCol="features", numIterations=iters,
    )
    buckets = bucket_candidates([(est, m) for m in maps])

    bus = get_bus()
    compiles = []
    listener = (
        lambda e: compiles.append(e.name)
        if isinstance(e, ProfileCompiled) else None
    )

    sweep = TrainValidSweep(
        estimator=est, paramSpace=space, labelCol="label",
        evaluationMetric="AUC", seed=3, commitModel=False,
    )
    bus.add_listener(listener)
    try:
        t0 = time.perf_counter()
        swept = sweep.fit(tbl)
        batched_secs = time.perf_counter() - t0
    finally:
        bus.remove_listener(listener)
    batched_compiles = sum(1 for n in compiles if n == "gbdt.scan_many")

    # candidate-at-a-time baseline on the SAME split/candidates/metric:
    # each distinct learningRate bakes into its own program, so the
    # sequential pass pays one compile per candidate
    mask = sweep._split(tbl.num_rows)
    train, valid = tbl.filter(mask), tbl.filter(~mask)
    compiles.clear()
    bus.add_listener(listener)
    try:
        t0 = time.perf_counter()
        seq_scores = []
        for m in maps:
            fitted = est.copy(m).fit(train)
            seq_scores.append(
                _evaluate(fitted.transform(valid), "label", "AUC")
            )
        seq_secs = time.perf_counter() - t0
    finally:
        bus.remove_listener(listener)
    # the single-model fit compiles as "gbdt.scan" (fused scan path) or
    # "gbdt.step" (per-iteration path on a device mesh) depending on
    # dispatch — either way it is one program per distinct learningRate
    seq_compiles = sum(1 for n in compiles if n in ("gbdt.scan", "gbdt.step"))

    return sweep_guard({
        "sweep_candidates": len(maps),
        "sweep_buckets": len(buckets),
        "sweep_bucket_sizes": [b.size for b in buckets],
        "sweep_rows": rows,
        "sweep_iterations": iters,
        "sweep_batched_secs": round(batched_secs, 3),
        "sweep_sequential_secs": round(seq_secs, 3),
        "sweep_models_per_sec_batched": round(len(maps) / batched_secs, 3),
        "sweep_models_per_sec_sequential": round(len(maps) / seq_secs, 3),
        "sweep_batched_vs_sequential": round(seq_secs / batched_secs, 3),
        "sweep_batched_compiles": batched_compiles,
        "sweep_sequential_compiles": seq_compiles,
        "sweep_best_params": swept.getBestParams(),
        "sweep_best_auc": round(float(swept.getBestMetric()), 5),
    })


def main():
    # the BENCH artifact carries its own attribution: per-program
    # compile/execute timing and the roofline section ride in "profiler"
    from mmlspark_tpu.core.device import configure_compile_cache
    from mmlspark_tpu.observability.profiler import get_profiler

    configure_compile_cache()
    prof = get_profiler().enable()

    # Capture the fit-path evidence events: HistogramChunked is the live
    # proof a fit streamed its U pass in row chunks (vs silently falling
    # off the MXU path), FeatureBundled records each EFB packing decision.
    from mmlspark_tpu.observability import (
        FeatureBundled,
        HistogramChunked,
        HistogramSubtracted,
        get_bus,
    )

    captured = []
    get_bus().add_listener(
        lambda e: captured.append(e)
        if isinstance(e, (FeatureBundled, HistogramChunked, HistogramSubtracted))
        else None
    )

    X, y = _make_data(N_ROWS + N_TEST, N_FEATURES)
    Xtr, ytr = X[:N_ROWS], y[:N_ROWS]
    Xte, yte = X[N_ROWS:], y[N_ROWS:]

    import jax

    backend = jax.default_backend()
    (
        tpu_secs, resident_secs, binning_secs, wire_runs, resident_runs,
        tpu_margins, booster,
    ) = _fit_tpu(Xtr, ytr, Xte)
    tpu_tput = N_ROWS * N_ITERS / tpu_secs
    auc_tpu = _auc(yte, tpu_margins)
    # throughput is per-row: cap the measurement batch so the one-dispatch
    # (N, T, I) decision tensor stays in HBM at any BENCH_ROWS
    pred_rows = min(N_ROWS, 400_000)
    pred_tpu = _predict_throughput_tpu(booster, Xtr[:pred_rows])

    try:
        cpu_secs, cpu_margins, clf = _fit_cpu(Xtr, ytr, Xte)
        cpu_tput = N_ROWS * N_ITERS / cpu_secs
        auc_cpu = _auc(yte, cpu_margins)
        vs = tpu_tput / cpu_tput
        pred_cpu = _predict_throughput_cpu(clf, Xtr[:pred_rows])
    except Exception as e:  # pragma: no cover
        print(f"cpu baseline failed: {e}", file=sys.stderr)
        cpu_secs, auc_cpu, vs, pred_cpu = 0.0, 0.0, 0.0, 0.0

    # Mixed categorical/ordinal workload (realistic tabular distribution,
    # effective B≈64): the packed-U layout's strong regime, reported as its
    # own metric block. The CPU engine gets the same categorical
    # declarations — both sides run their native categorical algorithms.
    mx, my, mcat = _make_mixed_data(N_ROWS + N_TEST, seed=1)
    mXtr, mytr, mXte, myte = mx[:N_ROWS], my[:N_ROWS], mx[N_ROWS:], my[N_ROWS:]
    (
        m_secs, m_resident, m_binning, m_wire_runs, m_resident_runs,
        m_margins, _,
    ) = _fit_tpu(mXtr, mytr, mXte, max_bin=MIXED_MAX_BIN, cat_idx=mcat)
    # TPU-side mixed numbers stand on their own; the CPU-relative keys join
    # only when the baseline engine can run the categorical workload.
    mixed = {
        "gbdt_mixed_train_row_iterations_per_sec": round(
            N_ROWS * N_ITERS / m_secs, 1
        ),
        "gbdt_mixed_tpu_fit_secs": round(m_secs, 3),
        "gbdt_mixed_tpu_fit_secs_device_resident": round(m_resident, 3),
        "gbdt_mixed_binning_host_secs": round(m_binning, 3),
        "gbdt_mixed_auc_tpu": round(float(_auc(myte, m_margins)), 5),
        "gbdt_mixed_wire_runs_secs": m_wire_runs,
        "gbdt_mixed_resident_runs_secs": m_resident_runs,
        "gbdt_mixed_shape": (
            f"{len(MIXED_CARDS)}cat(card<=64)+{MIXED_ORDINALS}ord(64)"
            f"+{MIXED_CONTINUOUS}cont, max_bin={MIXED_MAX_BIN}"
        ),
    }
    try:
        mc_secs, mc_margins, _mclf = _fit_cpu(
            mXtr, mytr, mXte, max_bin=MIXED_MAX_BIN + 1, cat_idx=mcat
        )
        mixed.update(
            {
                "gbdt_mixed_vs_baseline": round(mc_secs / m_secs, 3),
                "gbdt_mixed_vs_baseline_device_resident": round(
                    mc_secs / m_resident, 3
                ),
                "gbdt_mixed_cpu_fit_secs": round(mc_secs, 3),
                "gbdt_mixed_auc_cpu": round(float(_auc(myte, mc_margins)), 5),
            }
        )
    except Exception as e:  # pragma: no cover
        print(f"mixed cpu baseline failed: {e}", file=sys.stderr)

    # Throughput preset on the SAME continuous workload: LightGBM's own
    # gradient-quantization training (use_quantized_grad — 8-bit
    # stochastically-rounded g/h, s8 x s8 integer MXU histogram pass) plus
    # a 16-leaf frontier batch (one fewer U stream per tree). Quality is
    # reported, not assumed: AUC lands within ~0.001 of the exact fit and
    # above the CPU engine's. Compared against the same CPU run as the
    # headline (the CPU engine has no quantized mode at matched settings).
    (
        q_secs, q_resident, _q_binning, _q_wire_runs, q_resident_runs,
        q_margins, _,
    ) = _fit_tpu(
        Xtr, ytr, Xte,
        extra_opts={"use_quantized_grad": True, "leaf_batch": 16},
    )
    quant = {
        "gbdt_quant_train_row_iterations_per_sec": round(
            N_ROWS * N_ITERS / q_secs, 1
        ),
        "gbdt_quant_tpu_fit_secs": round(q_secs, 3),
        "gbdt_quant_tpu_fit_secs_device_resident": round(q_resident, 3),
        "gbdt_quant_auc_tpu": round(float(_auc(yte, q_margins)), 5),
        "gbdt_quant_resident_runs_secs": q_resident_runs,
        "gbdt_quant_config": "use_quantized_grad=True, leaf_batch=16",
    }
    if cpu_secs:
        quant["gbdt_quant_vs_baseline"] = round(cpu_secs / q_secs, 3)
        quant["gbdt_quant_vs_baseline_device_resident"] = round(
            cpu_secs / q_resident, 3
        )

    # Sibling-subtraction A/B on the headline shape: the headline and
    # quant fits above already run subtraction (the default); this block
    # re-fits both with histogram_subtraction=False so the artifact
    # carries the measured on/off delta AND the parity clause — the
    # default-config dAUC is the CI regression guard (<= 2e-5).
    (
        _so_secs, so_resident, _sob, _sowr, _sorr, so_margins, _,
    ) = _fit_tpu(
        Xtr, ytr, Xte, extra_opts={"histogram_subtraction": False},
    )
    so_auc = float(_auc(yte, so_margins))
    (
        _qo_secs, qo_resident, _qob, _qowr, _qorr, qo_margins, _,
    ) = _fit_tpu(
        Xtr, ytr, Xte,
        extra_opts={
            "use_quantized_grad": True, "leaf_batch": 16,
            "histogram_subtraction": False,
        },
    )
    # Quant-path byte-identity, measured live: subtraction is an integer
    # subtraction of integer partial sums, so the model text must be
    # byte-identical on/off. The quant preset above auto-selects the U
    # path only on TPU backends, so this check FORCES histogram_method='u'
    # (runs everywhere, CPU smoke included) at a declared reduced scale.
    import dataclasses as _dc

    from mmlspark_tpu.lightgbm.binning import bin_dataset as _bin
    from mmlspark_tpu.lightgbm.train import TrainOptions as _TO
    from mmlspark_tpu.lightgbm.train import train as _train

    qi_rows = min(N_ROWS, 50_000)
    qi_iters = min(N_ITERS, 20)
    qi_opts = _TO(
        objective="binary", num_iterations=qi_iters, num_leaves=NUM_LEAVES,
        learning_rate=LEARNING_RATE, max_bin=MAX_BIN, growth="leafwise",
        histogram_method="u", use_quantized_grad=True,
    )
    qi_bins, qi_mapper = _bin(Xtr[:qi_rows], max_bin=MAX_BIN)
    qi_on = _train(qi_bins, ytr[:qi_rows], qi_opts, mapper=qi_mapper)
    qi_off = _train(
        qi_bins, ytr[:qi_rows],
        _dc.replace(qi_opts, histogram_subtraction=False),
        mapper=qi_mapper,
    )
    sub = {
        "gbdt_sub_config": "histogram_subtraction A/B, headline shape",
        "gbdt_sub_on_fit_secs_device_resident": round(resident_secs, 3),
        "gbdt_sub_off_fit_secs_device_resident": round(so_resident, 3),
        "gbdt_sub_speedup_device_resident": round(
            so_resident / resident_secs, 3
        ),
        "gbdt_sub_dauc": round(abs(float(auc_tpu) - so_auc), 7),
        "gbdt_quant_sub_off_fit_secs_device_resident": round(qo_resident, 3),
        "gbdt_quant_sub_speedup_device_resident": round(
            qo_resident / q_resident, 3
        ),
        # the quant preset's own margins on/off — informational; identical
        # only where the preset actually rides the quantized U path (TPU)
        "gbdt_quant_sub_max_abs_margin_delta": float(
            np.max(np.abs(np.asarray(q_margins) - np.asarray(qo_margins)))
        ),
        "gbdt_quant_sub_byte_identical": bool(
            qi_on.booster.model_to_string()
            == qi_off.booster.model_to_string()
        ),
        "gbdt_quant_sub_byte_identity_config": (
            f"histogram_method='u', use_quantized_grad=True,"
            f" rows={qi_rows}, iterations={qi_iters}"
        ),
    }

    # Sparse one-hot workload: the Exclusive Feature Bundling regime.
    # Same fit bundled and unbundled; the block reports the measured K
    # (= Σ_f B_f histogram width) before/after packing, both fit times,
    # and both AUCs — the parity clause is |ΔAUC|, not a vibe.
    sx, sy = _make_sparse_data(SPARSE_ROWS + N_TEST)
    sXtr, sytr = sx[:SPARSE_ROWS], sy[:SPARSE_ROWS]
    sXte, syte = sx[SPARSE_ROWS:], sy[SPARSE_ROWS:]
    s_k_before, s_k_after, s_f, s_cols, s_conf = _bundling_k(
        sXtr, SPARSE_MAX_BIN
    )
    (s_secs, s_resident, _sb, _swr, _srr, s_margins, _) = _fit_tpu(
        sXtr, sytr, sXte, max_bin=SPARSE_MAX_BIN
    )
    (sb_secs, sb_resident, _sbb, _sbwr, _sbrr, sb_margins, _) = _fit_tpu(
        sXtr, sytr, sXte, max_bin=SPARSE_MAX_BIN, bundling=True
    )
    s_auc, sb_auc = float(_auc(syte, s_margins)), float(_auc(syte, sb_margins))
    sparse = {
        "gbdt_sparse_shape": (
            f"{SPARSE_BLOCKS}x{SPARSE_CARD} one-hot blocks"
            f"+{SPARSE_CONTINUOUS}cont, rows={SPARSE_ROWS},"
            f" max_bin={SPARSE_MAX_BIN}"
        ),
        "gbdt_sparse_k_before_bundling": s_k_before,
        "gbdt_sparse_k_after_bundling": s_k_after,
        "gbdt_sparse_k_reduction": round(s_k_before / max(s_k_after, 1), 3),
        "gbdt_sparse_columns_before": s_f,
        "gbdt_sparse_columns_after": s_cols,
        "gbdt_sparse_bundle_conflicts": s_conf,
        "gbdt_sparse_tpu_fit_secs": round(s_secs, 3),
        "gbdt_sparse_tpu_fit_secs_bundled": round(sb_secs, 3),
        "gbdt_sparse_tpu_fit_secs_device_resident": round(s_resident, 3),
        "gbdt_sparse_tpu_fit_secs_device_resident_bundled": round(
            sb_resident, 3
        ),
        "gbdt_sparse_bundled_speedup_device_resident": round(
            s_resident / sb_resident, 3
        ),
        "gbdt_sparse_auc_tpu": round(s_auc, 5),
        "gbdt_sparse_auc_tpu_bundled": round(sb_auc, 5),
        "gbdt_sparse_bundling_dauc": round(abs(s_auc - sb_auc), 6),
    }

    # Real-dataset mode (ROADMAP 5a): the vendored Covertype sample when
    # tools/fetch_covtype.py has run, else sklearn's bundled digits — the
    # synthetic-only bench criticism, answered with labeled provenance.
    r_src, rX, ry = _load_real_data()
    r_rows = len(rX)
    r_split = max(1, int(r_rows * 0.8))
    r_iters = min(N_ITERS, 100)
    rXtr, rytr = rX[:r_split], ry[:r_split]
    rXte, ryte = rX[r_split:], ry[r_split:]
    r_k_before, r_k_after, _rf, _rc, r_conf = _bundling_k(rXtr, MAX_BIN)
    (r_secs, r_resident, _rb, _rwr, _rrr, r_margins, _) = _fit_tpu(
        rXtr, rytr, rXte, n_iters=r_iters
    )
    (rb_secs, rb_resident, _rbb, _rbwr, _rbrr, rb_margins, _) = _fit_tpu(
        rXtr, rytr, rXte, n_iters=r_iters, bundling=True
    )
    r_auc = float(_auc(ryte, r_margins))
    rb_auc = float(_auc(ryte, rb_margins))
    real = {
        "gbdt_real_source": r_src,
        "gbdt_real_rows": r_rows,
        "gbdt_real_features": int(rX.shape[1]),
        "gbdt_real_iterations": r_iters,
        "gbdt_real_k_before_bundling": r_k_before,
        "gbdt_real_k_after_bundling": r_k_after,
        "gbdt_real_bundle_conflicts": r_conf,
        "gbdt_real_tpu_fit_secs": round(r_secs, 3),
        "gbdt_real_tpu_fit_secs_bundled": round(rb_secs, 3),
        "gbdt_real_tpu_fit_secs_device_resident": round(r_resident, 3),
        "gbdt_real_tpu_fit_secs_device_resident_bundled": round(
            rb_resident, 3
        ),
        "gbdt_real_auc_tpu": round(r_auc, 5),
        "gbdt_real_auc_tpu_bundled": round(rb_auc, 5),
        "gbdt_real_bundling_dauc": round(abs(r_auc - rb_auc), 6),
    }
    # When the digits fallback is active because a covtype download was
    # tried and failed (network-less container), carry the recorded
    # attempt so the provenance is "attempted, unreachable" rather than
    # silently synthetic-adjacent.
    attempt_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tests", "fixtures", "covtype_fetch_attempt.json",
    )
    if r_src != "covtype_sample" and os.path.exists(attempt_path):
        with open(attempt_path) as f:
            real["gbdt_real_covtype_fetch_attempt"] = json.load(f)
    try:
        rc_secs, rc_margins, _rclf = _fit_cpu(rXtr, rytr, rXte)
        real["gbdt_real_cpu_fit_secs"] = round(rc_secs, 3)
        real["gbdt_real_auc_cpu"] = round(float(_auc(ryte, rc_margins)), 5)
        real["gbdt_real_vs_baseline_device_resident"] = round(
            rc_secs / r_resident, 3
        )
    except Exception as e:  # pragma: no cover
        print(f"real cpu baseline failed: {e}", file=sys.stderr)

    # Many-models sweep: >=12-candidate grid, batched vs sequential
    # models/sec, ProfileCompiled amortization proof. sweep_guard raises
    # inside — a regression here fails the bench job, not just a number.
    sweep = _sweep_block()

    chunk_events = [
        {
            "rows": e.rows,
            "k_packed": e.k_packed,
            "chunk_rows": e.chunk_rows,
            "num_chunks": e.num_chunks,
            "budget_bytes": e.budget_bytes,
            "acc_dtype": e.acc_dtype,
            "bytes_saved": e.bytes_saved,
        }
        for e in captured
        if isinstance(e, HistogramChunked)
    ]
    sub_events = [
        {
            "rows": e.rows,
            "num_leaves": e.num_leaves,
            "packed_columns": e.packed_columns,
            "packed_bins": e.packed_bins,
            "acc_dtype": e.acc_dtype,
            "cache_bytes": e.cache_bytes,
            "bytes_saved_per_tree": e.bytes_saved_per_tree,
        }
        for e in captured
        if isinstance(e, HistogramSubtracted)
    ]
    bundle_events = [
        {
            "num_features": e.num_features,
            "num_columns": e.num_columns,
            "k_before": e.k_before,
            "k_after": e.k_after,
            "conflicts": e.conflicts,
        }
        for e in captured
        if isinstance(e, FeatureBundled)
    ]

    print(
        json.dumps(
            {
                "metric": f"gbdt_leafwise_train_row_iterations_per_sec_{backend}",
                "value": round(tpu_tput, 1),
                "unit": "rows*iters/sec",
                "vs_baseline": round(vs, 3),
                "tpu_fit_secs": round(tpu_secs, 3),
                "tpu_fit_secs_device_resident": round(resident_secs, 3),
                "vs_baseline_device_resident": (
                    round(cpu_secs / resident_secs, 3) if cpu_secs else 0.0
                ),
                # Decomposition so the artifact explains its own variance:
                # wire = what the upload adds over the resident fit;
                # per-run lists expose its run-to-run swing.
                "binning_host_secs": round(binning_secs, 3),
                "upload_overhead_secs": round(tpu_secs - resident_secs, 3),
                "wire_runs_secs": wire_runs,
                "resident_runs_secs": resident_runs,
                "cpu_fit_secs": round(cpu_secs, 3),
                "auc_tpu": round(float(auc_tpu), 5),
                "auc_cpu": round(float(auc_cpu), 5),
                "predict_rows_per_sec_tpu": round(pred_tpu, 0),
                "predict_rows_per_sec_cpu": round(pred_cpu, 0),
                "predict_vs_cpu": round(pred_tpu / pred_cpu, 2) if pred_cpu else 0.0,
                "cpu_engine": "sklearn.HistGradientBoostingClassifier(median of 3)",
                # Declared configs, stated where the numbers live: every
                # block above runs the DEFAULT config (exact bf16
                # histogram accumulation) unless its *_config key says
                # otherwise; the 9.6x-class throughput preset is opt-in.
                "gbdt_default_config": (
                    "exact bf16 histograms: use_quantized_grad=False,"
                    " leaf_batch=8, histogram_subtraction=True"
                ),
                "gbdt_fast_preset": (
                    "use_quantized_grad=True, leaf_batch=16 (opt-in;"
                    " measured in the gbdt_quant_* block)"
                ),
                **mixed,
                **quant,
                **sub,
                **sparse,
                **real,
                **sweep,
                # Chunked-U evidence: the static 4M-row selection trace
                # (proof the >1M shape compiles to the streamed MXU path)
                # plus any HistogramChunked events the fits above actually
                # published — live at BENCH_ROWS large enough to exceed
                # MMLSPARK_TPU_U_BUDGET.
                "u_chunking_4m_selection": _chunked_u_evidence(),
                # Bytes-per-build roofline for the 255-bin continuous
                # shape: the byte reduction subtraction + packed panels +
                # the fused bin+scatter kernel buy per histogram pass.
                "hist_bytes_per_build_255bin": _hist_bytes_evidence(),
                "histogram_chunked_events": chunk_events[:8],
                "histogram_chunked_event_count": len(chunk_events),
                "histogram_subtracted_events": sub_events[:8],
                "histogram_subtracted_event_count": len(sub_events),
                "feature_bundled_events": bundle_events[:8],
                "profiler": prof.snapshot(),
            }
        )
    )


if __name__ == "__main__":
    main()
