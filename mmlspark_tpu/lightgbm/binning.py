"""Quantile feature binning — the ``max_bin`` dataset-construction stage.

Replaces LightGBM's native dataset build (``LGBM_DatasetCreateFromMat``,
reference ``lightgbm/LightGBMUtils.scala:212-239``): features are
quantile-binned once on the host into a row-major uint8 matrix that ships to
TPU HBM as a single transfer. Bin 0 is reserved for NaN/missing, matching
LightGBM's ``use_missing`` default semantics.

Host numpy today; the layout (contiguous uint8, per-feature edge arrays) is
chosen so the C++ ingest library (SURVEY.md §2.20 item 1) can take over
without format changes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from mmlspark_tpu.lightgbm.bundling import (
    BundleSpec,
    fit_feature_bundles,
    pack_bundles,
)

MISSING_BIN = 0


@dataclasses.dataclass
class BinMapper:
    """Per-feature quantile bin edges. ``edges[f]`` has shape (max_bin-1,);
    value v maps to bin ``1 + searchsorted(edges[f], v, 'left')`` (bin 0 = NaN).
    ``upper[f][b]`` is the raw-value threshold meaning "bin <= b goes left".

    Categorical features (``categoricalSlotIndexes``/``Names``, reference
    ``lightgbm/LightGBMParams.scala:125-133``) bin by VALUE IDENTITY instead:
    each of the up to ``max_bin - 1`` most frequent category values owns one
    bin (``cat_values[f][b-1]`` is bin b's raw value, a bijection), and any
    other/unseen/NaN value maps to the missing bin 0 — which categorical
    split search treats as "not in any left set" (routes right), matching
    LightGBM's unseen-category behavior."""

    edges: np.ndarray  # (F, max_bin-1) float64, padded with +inf
    num_bins: np.ndarray  # (F,) actual bin count per feature (incl. missing bin)
    max_bin: int
    # feature index -> sorted-by-frequency raw category values (bin i+1 <-> v[i])
    cat_values: Optional[dict] = None
    # Exclusive Feature Bundling layout (mmlspark_tpu.lightgbm.bundling):
    # when set, apply_bins emits PACKED (N, C) columns and the trainer
    # expands histograms / converts routing back to original feature
    # space. None = unbundled (every consumer behaves exactly as before).
    bundles: Optional[BundleSpec] = None

    @property
    def num_features(self) -> int:
        return self.edges.shape[0]

    @property
    def categorical_features(self):
        return sorted(self.cat_values) if self.cat_values else []

    def is_categorical(self, feature: int) -> bool:
        return bool(self.cat_values) and feature in self.cat_values

    def threshold_value(self, feature: int, bin_idx: int) -> float:
        """Raw-value decision threshold for 'go left if x <= t' at bin_idx."""
        return float(self.edges[feature, bin_idx])


def fit_bin_mapper(
    X: np.ndarray,
    max_bin: int = 255,
    sample_cnt: int = 200_000,
    seed: int = 0,
    categorical_features=None,
    max_bin_by_feature=None,
) -> BinMapper:
    """Compute per-feature quantile edges (LightGBM ``bin_construct_sample_cnt``
    defaults to 200k sampled rows; ``binSampleCount``). ``categorical_features``:
    indices binned by value identity (one bin per frequent category).
    ``max_bin_by_feature``: per-feature bin cap (LightGBM maxBinByFeature;
    empty/None = the global ``max_bin`` everywhere)."""
    n, f = X.shape
    cat_set = set(int(c) for c in (categorical_features or []))
    caps = list(max_bin_by_feature or [])
    if caps:
        if len(caps) != f:
            raise ValueError(
                f"maxBinByFeature has {len(caps)} entries for {f} features"
            )
        bad = [c for c in caps if not (2 <= int(c) <= max_bin)]
        if bad:
            # explicit diagnostic instead of a silent clamp: this runtime's
            # uint8 bin layout caps per-feature bins at the global max_bin
            # (unlike native LightGBM, whose per-feature bins may exceed it)
            raise ValueError(
                f"maxBinByFeature entries must be in [2, maxBin={max_bin}] "
                f"(got {bad[:5]})"
            )
    if n > sample_cnt:
        rng = np.random.default_rng(seed)
        idx = rng.choice(n, size=sample_cnt, replace=False)
        sample = X[idx]
    else:
        sample = X
    # max_bin usable value bins (bin 0 reserved for missing) -> max_bin-1 edges.
    edges = np.full((f, max_bin - 1), np.inf, dtype=np.float64)
    num_bins = np.zeros(f, dtype=np.int32)
    cat_values: dict = {}
    for j in range(f):
        mb = int(caps[j]) if caps else max_bin
        col = sample[:, j]
        col = col[~np.isnan(col)]
        if j in cat_set:
            u, counts = np.unique(col, return_counts=True)
            cat_values[j] = _cat_values_from_counts(u, counts, mb)
            num_bins[j] = len(cat_values[j]) + 1  # + missing bin
            continue
        if col.size == 0:
            num_bins[j] = 1
            continue
        u, counts = np.unique(col, return_counts=True)
        e = _edges_from_counts(u, counts, mb, np.linspace(0, 1, mb))
        k = len(e)
        edges[j, :k] = e
        num_bins[j] = k + 2  # +1 missing bin, +1 overflow bin above last edge
    mapper = _snap_edges(edges, num_bins, max_bin)
    mapper.cat_values = cat_values or None
    return mapper


def _cat_values_from_counts(u: np.ndarray, counts: np.ndarray, mb: int) -> np.ndarray:
    """Value-identity bin list for one categorical feature: most frequent
    first (ties by value), capacity ``mb - 1`` — the ONE rule shared by the
    dense and CSR fits (they must stay bit-identical)."""
    order = np.lexsort((u, -counts))
    return np.asarray(u[order][: mb - 1], dtype=np.float64)


def _edges_from_counts(
    u: np.ndarray, counts: np.ndarray, max_bin: int, qs: np.ndarray
) -> np.ndarray:
    """Edges for one feature from its sorted unique non-NaN values + counts —
    the single edge rule shared by the dense and CSR fits (the two must stay
    bit-identical for sparse/dense training parity)."""
    if len(u) <= max_bin - 1:
        # One bin per distinct value; edge = the value itself ("<= v" left).
        return u
    qvals = _weighted_quantile(u, counts, qs)
    return np.unique(qvals)[:-1]  # drop max so the top quantile maps inside


def _snap_edges(edges: np.ndarray, num_bins: np.ndarray, max_bin: int) -> BinMapper:
    # Snap edges to the float32 grid: prediction routes raw float32 values
    # against float32 thresholds, so binning must use the identical
    # comparison grid or boundary values (x == edge) route differently in
    # train vs predict vs SHAP.
    finite = np.isfinite(edges)
    edges[finite] = edges[finite].astype(np.float32).astype(np.float64)
    return BinMapper(edges=edges, num_bins=num_bins, max_bin=max_bin)


def cat_to_bins(col: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Raw category column -> bin ids: value ``values[i]`` -> bin ``i+1``;
    NaN/unseen -> missing bin 0. The ONE definition of categorical bin
    assignment (train, predict, and SHAP must agree)."""
    order = np.argsort(values, kind="stable")
    sv = values[order]
    col = np.asarray(col, dtype=np.float64)
    pos = np.searchsorted(sv, col)
    pos = np.clip(pos, 0, len(sv) - 1) if len(sv) else np.zeros(len(col), np.int64)
    hit = len(sv) > 0
    match = (sv[pos] == col) if hit else np.zeros(len(col), bool)
    bins = np.where(match, (order[pos] + 1) if hit else 0, MISSING_BIN)
    return np.where(np.isnan(col), MISSING_BIN, bins).astype(np.int64)


def apply_bins(X: np.ndarray, mapper: BinMapper) -> np.ndarray:
    """Map raw features to uint8 bin indices — row-major (N, F) uint8, or
    the PACKED (N, C) layout when the mapper carries a fitted
    :class:`~mmlspark_tpu.lightgbm.bundling.BundleSpec` (so train, valid
    sets, batch chaining, and procfit shards all bin consistently).
    Row-pure either way (the partitioned path concatenates shards)."""
    out = _apply_bins_raw(X, mapper)
    spec = getattr(mapper, "bundles", None)  # pre-EFB pickles lack the field
    if spec is not None:
        out = pack_bundles(out, spec)
    return out


def _apply_bins_raw(X: np.ndarray, mapper: BinMapper) -> np.ndarray:
    """Original-feature-space binning (pre-bundling). Uses the host C++
    library when built (bit-identical contract,
    ``native/mmlspark_native.cpp``); numpy otherwise. Categorical columns
    are overlaid afterwards (value-identity bins, ``cat_to_bins``)."""
    from mmlspark_tpu.native import apply_bins_native

    native = apply_bins_native(np.asarray(X, dtype=np.float64), mapper.edges, mapper.max_bin)
    if native is not None:
        if mapper.cat_values:
            native = np.array(native, copy=True)
            for j, vals in mapper.cat_values.items():
                native[:, j] = cat_to_bins(X[:, j], vals).astype(np.uint8)
        return native
    n, f = X.shape
    out = np.zeros((n, f), dtype=np.uint8)
    for j in range(f):
        if mapper.is_categorical(j):
            out[:, j] = cat_to_bins(X[:, j], mapper.cat_values[j]).astype(np.uint8)
            continue
        # float32 comparison grid — identical to the predict/SHAP paths.
        col = X[:, j].astype(np.float32)
        nan_mask = np.isnan(col)
        # 'left' => v <= edge stays at that edge's bin; v > last edge -> overflow bin.
        b = 1 + np.searchsorted(mapper.edges[j].astype(np.float32), col, side="left")
        b = np.where(nan_mask, MISSING_BIN, b)
        out[:, j] = np.clip(b, 0, mapper.max_bin).astype(np.uint8)
    return out


def fit_bundles_inplace(
    mapper: BinMapper,
    raw_bins: np.ndarray,
    max_conflict_rate: float = 0.0,
    sample_cnt: int = 200_000,
    seed: int = 0,
) -> Optional[BundleSpec]:
    """Fit Exclusive Feature Bundling over a row sample of the ALREADY
    binned (original-space) matrix and attach the spec to the mapper.
    Stays None when no bundle gains a second member — then every consumer
    is bit-identical to an unbundled fit. Same sampling discipline as the
    edge fit (``sample_cnt`` rows, seeded rng)."""
    n = raw_bins.shape[0]
    if n > sample_cnt:
        rng = np.random.default_rng(seed)
        sample = raw_bins[rng.choice(n, size=sample_cnt, replace=False)]
    else:
        sample = raw_bins
    spec = fit_feature_bundles(
        sample,
        mapper.num_bins,
        max_conflict_rate=max_conflict_rate,
        categorical_slots=mapper.categorical_features,
    )
    mapper.bundles = spec
    if spec is not None:
        from mmlspark_tpu.observability.events import FeatureBundled, get_bus

        bus = get_bus()
        if bus.active:
            bus.publish(FeatureBundled(
                num_features=spec.num_features,
                num_columns=spec.num_columns,
                k_before=int(sum(int(x) for x in mapper.num_bins)),
                k_after=spec.k_packed,
                conflicts=spec.conflict_count,
                sample_rows=spec.sample_rows,
            ))
    return spec


def bin_dataset_to_device(
    X: np.ndarray,
    max_bin: int = 255,
    mapper: Optional[BinMapper] = None,
    categorical_features=None,
    feature_bundling: bool = False,
    max_conflict_rate: float = 0.0,
):
    """Bin on the host, then dispatch ONE asynchronous ``jax.device_put`` —
    the transfer flies while the caller sets up the rest of the fit (every
    transfer carries a fixed cost, so one shot rather than chunks). Returns
    (device_bins uint8 (N, F) — or (N, C) packed under ``feature_bundling``
    — and the mapper); feed the device array straight to
    :func:`~mmlspark_tpu.lightgbm.train.train` (it skips its own upload
    for device-resident bins)."""
    import jax

    bins, mapper = bin_dataset(
        X, max_bin=max_bin, mapper=mapper,
        categorical_features=categorical_features,
        feature_bundling=feature_bundling,
        max_conflict_rate=max_conflict_rate,
    )
    return jax.device_put(np.ascontiguousarray(bins)), mapper


def bin_dataset(
    X, max_bin: int = 255, mapper: Optional[BinMapper] = None,
    categorical_features=None, sample_cnt: int = 200_000,
    max_bin_by_feature=None, feature_bundling: bool = False,
    max_conflict_rate: float = 0.0,
) -> Tuple[np.ndarray, BinMapper]:
    from mmlspark_tpu.data.sparse import CSRMatrix

    fresh = mapper is None
    if isinstance(X, CSRMatrix):
        if max_bin_by_feature:
            raise ValueError(
                "maxBinByFeature is not supported on sparse (CSR) input"
            )
        if fresh:
            mapper = fit_bin_mapper_csr(
                X, max_bin=max_bin, sample_cnt=sample_cnt,
                categorical_features=categorical_features,
            )
        raw = _apply_bins_csr_raw(X, mapper)
    else:
        X = np.asarray(X, dtype=np.float64)
        if fresh:
            mapper = fit_bin_mapper(
                X, max_bin=max_bin, sample_cnt=sample_cnt,
                categorical_features=categorical_features,
                max_bin_by_feature=max_bin_by_feature,
            )
        raw = _apply_bins_raw(X, mapper)
    if fresh and feature_bundling:
        fit_bundles_inplace(
            mapper, raw, max_conflict_rate=max_conflict_rate,
            sample_cnt=sample_cnt,
        )
    spec = getattr(mapper, "bundles", None)
    if spec is not None:
        return pack_bundles(raw, spec), mapper
    return raw, mapper


def bin_dataset_partitioned(
    X, max_bin: int = 255, mapper: Optional[BinMapper] = None,
    categorical_features=None, sample_cnt: int = 200_000,
    max_bin_by_feature=None, policy=None, metrics=None,
    journal_root: Optional[str] = None, journal_key: Optional[str] = None,
    feature_bundling: bool = False, max_conflict_rate: float = 0.0,
) -> Tuple[np.ndarray, BinMapper]:
    """:func:`bin_dataset` with the row-binning pass dispatched as
    partitioned tasks on the fault-tolerant scheduler
    (:mod:`mmlspark_tpu.runtime`). The :class:`BinMapper` fit stays inline
    (one cheap, deterministic quantile pass over a sample); the expensive
    per-row :func:`apply_bins` is row-pure, so partition results
    concatenated in index order are bit-identical to the inline call — an
    injected executor death mid-bin retries/recomputes and changes nothing
    downstream. Each partition records lineage (its row slice), so a
    :class:`~mmlspark_tpu.runtime.lineage.PartitionLostError` rebuilds the
    shard instead of failing the fit.

    CSR input falls back to the inline path (``apply_bins_csr`` scatters
    over the whole matrix in one pass).

    ``journal_root`` + ``journal_key`` make the pass durable: each
    partition's binned block checkpoints to a
    :class:`~mmlspark_tpu.runtime.journal.FitJournal` as it completes, so
    a killed process rerun with the same key restores finished partitions
    with zero re-execution (the partition count is folded into the
    journal identity — a different ``max_workers`` starts clean rather
    than mixing incompatible row slices).
    """
    from mmlspark_tpu import runtime
    from mmlspark_tpu.data.sparse import CSRMatrix

    if isinstance(X, CSRMatrix):
        return bin_dataset(
            X, max_bin=max_bin, mapper=mapper,
            categorical_features=categorical_features, sample_cnt=sample_cnt,
            max_bin_by_feature=max_bin_by_feature,
            feature_bundling=feature_bundling,
            max_conflict_rate=max_conflict_rate,
        )
    X = np.asarray(X, dtype=np.float64)
    fresh = mapper is None
    if fresh:
        mapper = fit_bin_mapper(
            X, max_bin=max_bin, sample_cnt=sample_cnt,
            categorical_features=categorical_features,
            max_bin_by_feature=max_bin_by_feature,
        )
    if fresh and feature_bundling:
        # Bundle fit stays inline (like the mapper fit): bin only the
        # sample rows in original space, attach the spec, and every
        # partition task's apply_bins packs consistently (row-pure).
        n_all = X.shape[0]
        if n_all > sample_cnt:
            rng = np.random.default_rng(0)
            rows = X[rng.choice(n_all, size=sample_cnt, replace=False)]
        else:
            rows = X
        fit_bundles_inplace(
            mapper, _apply_bins_raw(rows, mapper),
            max_conflict_rate=max_conflict_rate, sample_cnt=sample_cnt,
        )
    pol = policy or runtime.current_policy() or runtime.SchedulerPolicy()
    n = X.shape[0]
    num_parts = max(1, min(pol.max_workers, n))
    if n == 0:
        return apply_bins(X, mapper), mapper
    bounds = np.linspace(0, n, num_parts + 1).astype(np.int64)
    lineage = runtime.Lineage()
    shards = [
        lineage.record(
            i,
            (lambda lo=int(bounds[i]), hi=int(bounds[i + 1]): X[lo:hi]),
            describe=f"rows[{bounds[i]}:{bounds[i + 1]}]",
        )
        for i in range(num_parts)
    ]
    journal = None
    if journal_root is not None and journal_key is not None:
        journal = runtime.FitJournal(
            journal_root, f"{journal_key}-p{num_parts}", num_tasks=num_parts
        )
    try:
        parts = runtime.run_partitioned(
            lambda rows: apply_bins(rows, mapper), shards, pol,
            lineage=lineage, metrics=metrics, journal=journal,
        )
    finally:
        if journal is not None:
            journal.close()
    return np.concatenate(parts, axis=0), mapper


# ---------------------------------------------------------------------------
# Sparse (CSR) ingest — the LGBM_DatasetCreateFromCSRSpark analogue
# (reference lightgbm/LightGBMUtils.scala:246-266). Implicit entries are 0.0;
# the dense float matrix is never materialized: quantiles fold the implicit
# zero mass in analytically, and bin assignment scatters explicit entries over
# a zero-bin-initialized uint8 matrix (the layout training wants anyway).
# ---------------------------------------------------------------------------


def _weighted_quantile(u: np.ndarray, c: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """Quantiles of the multiset {u[k] repeated c[k] times}, matching
    ``np.quantile(..., method='linear')`` bit-for-bit: position p = q*(W-1),
    linear interpolation between virtual sorted elements floor(p)/ceil(p)."""
    w = int(c.sum())
    cum = np.cumsum(c)
    p = qs * (w - 1)
    i = np.floor(p).astype(np.int64)
    frac = p - i
    i2 = np.minimum(i + 1, w - 1)
    a_lo = u[np.searchsorted(cum, i, side="right")]
    a_hi = u[np.searchsorted(cum, i2, side="right")]
    # numpy's _lerp switches formula at t >= 0.5 for monotonicity; reproduce
    # it so these edges are bitwise np.quantile's.
    diff = a_hi - a_lo
    out = a_lo + frac * diff
    return np.where(frac >= 0.5, a_hi - diff * (1 - frac), out)


def fit_bin_mapper_csr(csr, max_bin: int = 255, sample_cnt: int = 200_000,
                       seed: int = 0, categorical_features=None) -> BinMapper:
    """Per-feature quantile edges from CSR without densifying. Matches
    :func:`fit_bin_mapper` on the equivalent dense matrix exactly (same
    sampling rng, same quantile arithmetic with the implicit-zero mass;
    categorical features count the implicit zeros toward category 0.0's
    frequency)."""
    n, f = csr.shape
    if n > sample_cnt:
        rng = np.random.default_rng(seed)
        idx = rng.choice(n, size=sample_cnt, replace=False)
        sel = np.zeros(n, dtype=bool)
        sel[idx] = True
        n_sample = sample_cnt
    else:
        sel = None
        n_sample = n

    if sel is not None:
        row_ids = np.repeat(np.arange(n, dtype=np.int64), np.diff(csr.indptr))
        keep = sel[row_ids]
        cols, vals = csr.indices[keep], csr.data[keep]
    else:
        cols, vals = csr.indices, csr.data

    order = np.argsort(cols, kind="stable")
    cols_s, vals_s = cols[order], vals[order]
    col_starts = np.searchsorted(cols_s, np.arange(f + 1))

    cat_set = set(int(c) for c in (categorical_features or []))
    edges = np.full((f, max_bin - 1), np.inf, dtype=np.float64)
    num_bins = np.zeros(f, dtype=np.int32)
    cat_values: dict = {}
    qs = np.linspace(0, 1, max_bin)
    for j in range(f):
        explicit = vals_s[col_starts[j] : col_starts[j + 1]]
        n_zero = n_sample - len(explicit)  # implicit entries are 0.0
        explicit = explicit[~np.isnan(explicit)]
        if len(explicit) + n_zero == 0:
            num_bins[j] = 1
            continue
        # Fold the implicit zero mass into the (value, count) multiset, then
        # defer to the shared edge rule.
        u, counts = np.unique(explicit, return_counts=True)
        pos = np.searchsorted(u, 0.0)
        if pos < len(u) and u[pos] == 0.0:
            counts = counts.copy()
            counts[pos] += n_zero
        elif n_zero > 0:
            u = np.insert(u, pos, 0.0)
            counts = np.insert(counts, pos, n_zero)
        if j in cat_set:
            # shared rule, with the implicit-zero mass already folded in
            cat_values[j] = _cat_values_from_counts(u, counts, max_bin)
            num_bins[j] = len(cat_values[j]) + 1
            continue
        e = _edges_from_counts(u, counts, max_bin, qs)
        k = len(e)
        edges[j, :k] = e
        num_bins[j] = k + 2
    mapper = _snap_edges(edges, num_bins, max_bin)
    mapper.cat_values = cat_values or None
    return mapper


def apply_bins_csr(csr, mapper: BinMapper) -> np.ndarray:
    """CSR → dense row-major uint8 bins (packed when the mapper bundles).
    Bit-identical to ``apply_bins`` on the densified matrix."""
    out = _apply_bins_csr_raw(csr, mapper)
    spec = getattr(mapper, "bundles", None)
    if spec is not None:
        out = pack_bundles(out, spec)
    return out


def _apply_bins_csr_raw(csr, mapper: BinMapper) -> np.ndarray:
    """Original-feature-space CSR binning: initialize every cell to its
    feature's zero-bin, then scatter the explicit entries column-by-column."""
    n, f = csr.shape
    edges32 = mapper.edges.astype(np.float32)
    zero_bins = np.clip(
        1 + np.array([np.searchsorted(edges32[j], np.float32(0.0), side="left") for j in range(f)]),
        0,
        mapper.max_bin,
    ).astype(np.uint8)
    for j, vals in (mapper.cat_values or {}).items():
        # categorical zero-fill: category 0.0's value bin (or missing)
        zero_bins[j] = np.uint8(cat_to_bins(np.array([0.0]), vals)[0])
    out = np.broadcast_to(zero_bins[None, :], (n, f)).copy()

    col_indptr, row_ids, values = csr.to_csc()
    for j in range(f):
        lo, hi = col_indptr[j], col_indptr[j + 1]
        if hi == lo:
            continue
        if mapper.is_categorical(j):
            b = cat_to_bins(values[lo:hi], mapper.cat_values[j])
            out[row_ids[lo:hi], j] = b.astype(np.uint8)
            continue
        v = values[lo:hi].astype(np.float32)
        b = 1 + np.searchsorted(edges32[j], v, side="left")
        b = np.where(np.isnan(v), MISSING_BIN, b)
        out[row_ids[lo:hi], j] = np.clip(b, 0, mapper.max_bin).astype(np.uint8)
    return out
