"""Booster: the trained forest, with jitted batch predict, SHAP, and serde.

Equivalent of ``LightGBMBooster`` (reference ``lightgbm/LightGBMBooster.scala``):
score / predictLeaf / featuresShap / raw-margin output, iteration slicing for
early stopping, string serde. Instead of per-row JNI calls with ThreadLocal
native buffers (``LightGBMBooster.scala:37-128``), prediction is one jitted
XLA program over the whole batch.

Tree layout — pointer-based node arrays (per tree, ``M`` node slots), the
layout LightGBM's own model text uses, supporting both level-wise and
LightGBM's defining *leaf-wise* growth (unbalanced trees would explode an
implicit heap: depth can reach ``num_leaves - 1``):

- ``split_feature``   (M,) int32   — internal nodes; 0 at leaves/dead slots
- ``split_threshold`` (M,) float32 — raw-value "go left if NaN or x <= t";
                                      +inf at dead slots (float64 on imported
                                      LightGBM models; predict snaps DOWN to
                                      f32, see ``_thr_f32``)
- ``split_bin``       (M,) int32   — binned-space threshold (training path)
- ``left_child`` / ``right_child`` (M,) int32 — slot indices
- ``is_leaf``         (M,) bool
- ``leaf_values``     (M,) float32 — learning-rate-scaled outputs at leaves
- ``cover``           (M,) float32 — training rows through the node (TreeSHAP)
- ``split_gain``      (M,) float32 — realized gain (importance_type="gain")

Routing is ``max_depth`` rounds of gathers — no data-dependent control flow;
rows that reach a leaf early simply stay there (``is_leaf`` gate).

Forest arrays stack trees as (num_trees, M) where tree ``i*C + c`` is
iteration i, class c (LightGBM's tree ordering).
"""

from __future__ import annotations

import dataclasses
import json
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from mmlspark_tpu.lightgbm.binning import BinMapper

#: LightGBM's kZeroThreshold: |x| <= this counts as zero (zero_as_missing).
K_ZERO_THRESHOLD = 1e-35

#: Size gate for the dense (T*I, Fc*Bc) categorical mask matrix: above
#: this, predict uses the memory-bounded gather kernel instead.
_CM_BYTES_CAP = 128 << 20

def _predict_chunk_rows(
    t: int, i: int, budget_bytes: int = 256 << 20, extra_row_bytes: int = 0
) -> int:
    """Rows per predict dispatch. The budget covers the (N, T, I) decision
    tensor AND its same-shape temporaries (D, score, match ≈ 4x), plus any
    caller-declared per-row transients (``extra_row_bytes`` — the
    categorical path's stacked one-hot and decision matrices), so huge
    forests shrink the chunk rather than OOM; no floor overrides it."""
    per_row = 16 * max(t * i, 1) + max(extra_row_bytes, 0)
    return max(1, min(131072, budget_bytes // per_row))


def _cat_row_bytes(cat) -> int:
    """Per-row transient bytes of the categorical predict kernels, for the
    chunk budget: the matmul path materializes a bf16 (Fc*Bc, N) one-hot +
    an f32 (T*I, N) decision matrix; the gather path an int32 (N, T, I)
    index tensor."""
    if cat[0] == "matmul":
        _, iscat, cfeats, cm = cat
        t, i = iscat.shape
        return 2 * cm.shape[1] + 4 * t * i
    _, iscat, catm = cat
    t, i = iscat.shape
    return 4 * t * i


@dataclasses.dataclass
class Booster:
    split_feature: np.ndarray  # (T, M) int32
    split_threshold: np.ndarray  # (T, M) float32 (float64 on imported models)
    split_bin: np.ndarray  # (T, M) int32
    left_child: np.ndarray  # (T, M) int32
    right_child: np.ndarray  # (T, M) int32
    is_leaf: np.ndarray  # (T, M) bool
    leaf_values: np.ndarray  # (T, M) float32
    init_score: np.ndarray  # (C,)
    num_classes: int  # margin columns C
    objective: str
    max_depth: int  # routing steps (>= realized depth of every tree)
    cover: Optional[np.ndarray] = None  # (T, M) float32
    split_gain: Optional[np.ndarray] = None  # (T, M) float32
    best_iteration: int = -1  # -1 = use all
    feature_names: Optional[list] = None
    bin_edges: Optional[np.ndarray] = None  # (F, max_bin-1) for re-binning
    # (T, M) bool: where a NaN feature value routes at each internal node.
    # None = all True (trees trained here always send missing left); imported
    # LightGBM models carry per-node directions from their decision_type.
    nan_left: Optional[np.ndarray] = None
    # Categorical splits (reference LightGBMParams.scala:125-133): cat_nodes
    # (T, M) bool marks categorical decisions; cat_masks (T, M, Bc) bool is
    # the LEFT set over the feature's value-bin ids; cat_values maps feature
    # -> sorted-by-frequency raw category values (bin i+1 <-> values[i]).
    # A raw value not in cat_values (unseen/NaN) routes RIGHT, matching
    # native LightGBM's unseen-category behavior.
    cat_nodes: Optional[np.ndarray] = None
    cat_masks: Optional[np.ndarray] = None
    cat_values: Optional[Dict[int, np.ndarray]] = None
    # (T, M) bool: zero_as_missing nodes (imported LightGBM missing_type=
    # Zero): a 0.0 or NaN feature value routes per nan_left there.
    zero_missing: Optional[np.ndarray] = None
    # Linear trees (imported ``linear_tree=true`` models; training here
    # never produces them): at leaf slot m the output is
    # ``leaf_const[t, m] + sum_l leaf_coeff[t, m, l] * x[leaf_feat[t, m, l]]``
    # over valid entries (``leaf_feat >= 0``; -1 pads). If ANY feature used
    # by the leaf's model is NaN, the plain ``leaf_values`` output applies —
    # native LightGBM's missing fallback for linear leaves.
    leaf_const: Optional[np.ndarray] = None  # (T, M) float64
    leaf_coeff: Optional[np.ndarray] = None  # (T, M, L) float64
    leaf_feat: Optional[np.ndarray] = None  # (T, M, L) int32, -1 pad

    @property
    def has_categorical(self) -> bool:
        return self.cat_nodes is not None and bool(np.any(self.cat_nodes))

    @property
    def has_linear(self) -> bool:
        return self.leaf_const is not None

    def _cat_binned(self, X: np.ndarray) -> np.ndarray:
        """Replace categorical columns of a raw batch with their value-bin
        ids (float) — the predict-side twin of training's binning, via the
        shared ``cat_to_bins`` rule."""
        from mmlspark_tpu.lightgbm.binning import cat_to_bins

        Xp = np.array(X, dtype=np.float64, copy=True)
        for f, vals in (self.cat_values or {}).items():
            Xp[:, f] = cat_to_bins(X[:, f], np.asarray(vals, np.float64))
        return Xp

    @property
    def num_trees(self) -> int:
        return self.split_feature.shape[0]

    @property
    def num_features(self) -> int:
        """Trained feature-space width (pins CSR predict batches to the
        training F so narrower sparse batches can't silently shrink)."""
        if self.feature_names:
            return len(self.feature_names)
        if self.bin_edges is not None:
            return self.bin_edges.shape[0]
        internal = (~self.is_leaf) & np.isfinite(self.split_threshold)
        feats = self.split_feature[internal]
        return int(feats.max()) + 1 if feats.size else 0

    @property
    def num_iterations(self) -> int:
        return self.num_trees // self.num_classes

    def _used_trees(self, num_iteration: Optional[int] = None) -> int:
        it = num_iteration
        if it is None:
            it = self.best_iteration if self.best_iteration > 0 else self.num_iterations
        return min(it, self.num_iterations) * self.num_classes

    # -- predict -------------------------------------------------------------

    def raw_margin(
        self, X, num_iteration: Optional[int] = None
    ) -> np.ndarray:
        """(N, C) raw margins (init_score + sum of tree outputs). ``X`` may be
        dense (N, F) or a CSRMatrix (densified in bounded row chunks)."""
        chunks = _csr_chunks(
            X,
            dtype=np.float64
            if (self.has_categorical or self.has_linear)
            else np.float32,
        )
        if chunks is not None:
            return np.concatenate(
                [self.raw_margin(c, num_iteration) for c in chunks], axis=0
            )
        t = self._used_trees(num_iteration)
        if t == 0:
            return np.broadcast_to(
                self.init_score[None, :], (X.shape[0], self.num_classes)
            ).copy()
        if self.has_linear:
            return self._raw_margin_linear(X, num_iteration)
        pc = _paths_cache(self, t)
        has_cat = self.has_categorical
        X32 = np.asarray(
            self._cat_binned(X) if has_cat else X, dtype=np.float32
        )
        if has_cat:
            cat = _cat_paths_cache(self, t)
        extra = _cat_row_bytes(cat) if has_cat else 0
        chunk = _predict_chunk_rows(*pc.feats.shape, extra_row_bytes=extra)
        outs = []
        # device-resident constants built ONCE — a jnp.asarray per chunk
        # would re-upload every tree table each iteration
        cargs = (
            jnp.asarray(pc.feats), jnp.asarray(pc.thrs),
            jnp.asarray(pc.nanl), jnp.asarray(pc.zm),
            jnp.asarray(pc.P), jnp.asarray(pc.plen),
        )
        lvals_d = jnp.asarray(pc.lvals)
        isc_d = jnp.asarray(self.init_score)
        if has_cat:
            cat_kernel = (
                _predict_margin_paths_cat_jit
                if cat[0] == "matmul"
                else _predict_margin_paths_catgather_jit
            )
            catargs = tuple(jnp.asarray(a) for a in cat[1:])
        for lo in range(0, max(len(X32), 1), chunk):
            xd = jnp.asarray(X32[lo : lo + chunk])
            if has_cat:
                m = cat_kernel(
                    xd, *cargs, *catargs, lvals_d, isc_d, self.num_classes,
                )
            else:
                m = _predict_margin_paths_jit(
                    xd, *cargs, lvals_d, isc_d, self.num_classes,
                )
            outs.append(np.asarray(m))
        return np.concatenate(outs, axis=0) if outs else np.zeros((0, self.num_classes), np.float32)

    def _raw_margin_linear(
        self, X, num_iteration: Optional[int] = None
    ) -> np.ndarray:
        """Margins for linear-tree models: leaf ROUTING stays on device (the
        jitted path-matrix leaf predict), the per-leaf linear models run in
        float64 on host — native LightGBM evaluates linear leaves in double,
        and an f32 detour would visibly drift coefficient-heavy leaves.
        A leaf whose model touches a NaN feature falls back to the plain
        constant output (native behavior for linear leaves + missing)."""
        slots = self.predict_leaf(X, num_iteration)  # (N, T) leaf slots
        t = slots.shape[1]
        Xd = np.asarray(X, np.float64)
        n = Xd.shape[0]
        tt = np.arange(t)[None, :]
        lmax = self.leaf_feat.shape[-1]
        out = np.empty((n, t), np.float64)
        chunk = max(1, (64 << 20) // max(8 * t * lmax, 1))
        for lo in range(0, max(n, 1), chunk):
            sl = slots[lo : lo + chunk]
            const = self.leaf_const[tt, sl]  # (n, T)
            coeff = self.leaf_coeff[tt, sl]  # (n, T, L)
            fidx = self.leaf_feat[tt, sl]  # (n, T, L)
            valid = fidx >= 0
            rows = np.arange(sl.shape[0])[:, None, None]
            xv = Xd[lo : lo + chunk][rows, np.maximum(fidx, 0)]
            nanf = np.any(valid & np.isnan(xv), axis=-1)
            lin = const + np.where(
                valid & ~np.isnan(xv), coeff * xv, 0.0
            ).sum(axis=-1)
            plain = self.leaf_values[tt, sl].astype(np.float64)
            out[lo : lo + chunk] = np.where(nanf, plain, lin)
        rounds = t // self.num_classes
        margins = out.reshape(n, rounds, self.num_classes).sum(axis=1)
        return margins + np.asarray(self.init_score, np.float64)[None, :]

    def predict_leaf(
        self, X, num_iteration: Optional[int] = None
    ) -> np.ndarray:
        """(N, T) leaf slot per tree (``predictLeaf``, LightGBMBooster.scala:240+)."""
        chunks = _csr_chunks(
            X, dtype=np.float64 if self.has_categorical else np.float32
        )
        if chunks is not None:
            return np.concatenate(
                [self.predict_leaf(c, num_iteration) for c in chunks], axis=0
            )
        t = self._used_trees(num_iteration)
        if t == 0:
            return np.zeros((np.shape(X)[0], 0), np.int32)
        pc = _paths_cache(self, t)
        has_cat = self.has_categorical
        X32 = np.asarray(
            self._cat_binned(X) if has_cat else X, dtype=np.float32
        )
        if has_cat:
            cat = _cat_paths_cache(self, t)
        extra = _cat_row_bytes(cat) if has_cat else 0
        chunk = _predict_chunk_rows(*pc.feats.shape, extra_row_bytes=extra)
        outs = []
        cargs = (
            jnp.asarray(pc.feats), jnp.asarray(pc.thrs),
            jnp.asarray(pc.nanl), jnp.asarray(pc.zm),
            jnp.asarray(pc.P), jnp.asarray(pc.plen),
        )
        lslots_d = jnp.asarray(pc.lslots)
        if has_cat:
            cat_kernel = (
                _predict_leaf_paths_cat_jit
                if cat[0] == "matmul"
                else _predict_leaf_paths_catgather_jit
            )
            catargs = tuple(jnp.asarray(a) for a in cat[1:])
        for lo in range(0, max(len(X32), 1), chunk):
            xd = jnp.asarray(X32[lo : lo + chunk])
            if has_cat:
                leaves = cat_kernel(xd, *cargs, *catargs, lslots_d)
            else:
                leaves = _predict_leaf_paths_jit(xd, *cargs, lslots_d)
            outs.append(np.asarray(leaves))
        return np.concatenate(outs, axis=0) if outs else np.zeros((0, t), np.int32)

    def features_shap(
        self, X, num_iteration: Optional[int] = None
    ) -> np.ndarray:
        """(N, C, F+1) per-feature SHAP values plus bias term (last column);
        ``sum(axis=-1) == raw_margin`` (``featuresShap``,
        LightGBMBooster.scala:240-275). Path-dependent TreeSHAP using the
        training covers recorded per node."""
        from mmlspark_tpu.lightgbm.shap import tree_shap

        if self.has_linear:
            raise NotImplementedError(
                "SHAP values are not implemented for linear-tree models "
                "(leaf outputs are per-leaf linear functions, outside "
                "TreeSHAP's piecewise-constant contract)"
            )
        chunks = _csr_chunks(X, dtype=np.float64)
        if chunks is not None:
            return np.concatenate(
                [self.features_shap(c, num_iteration) for c in chunks], axis=0
            )
        return tree_shap(self, np.asarray(X, dtype=np.float64), num_iteration)

    # -- serde ---------------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Booster":
        d = dict(d)
        for k in ("split_feature", "split_bin", "left_child", "right_child"):
            d[k] = np.asarray(d[k], dtype=np.int32)
        for k in ("leaf_values", "init_score"):
            d[k] = np.asarray(d[k], dtype=np.float32)
        # thresholds keep f64 when they arrive as f64 (imported LightGBM
        # models); trained-here boosters are exact f32 values either way
        thr = np.asarray(d["split_threshold"])
        d["split_threshold"] = thr.astype(
            np.float64 if thr.dtype == np.float64 else np.float32
        )
        d["is_leaf"] = np.asarray(d["is_leaf"], dtype=bool)
        for k in ("cover", "split_gain"):
            if d.get(k) is not None:
                d[k] = np.asarray(d[k], dtype=np.float32)
        for k in ("nan_left", "cat_nodes", "cat_masks", "zero_missing"):
            if d.get(k) is not None:
                d[k] = np.asarray(d[k], dtype=bool)
        if d.get("bin_edges") is not None:
            d["bin_edges"] = np.asarray(d["bin_edges"], dtype=np.float64)
        if d.get("cat_values") is not None:
            d["cat_values"] = {
                int(k): np.asarray(v, dtype=np.float64)
                for k, v in d["cat_values"].items()
            }
        for k, dt in (
            ("leaf_const", np.float64),
            ("leaf_coeff", np.float64),
            ("leaf_feat", np.int32),
        ):
            if d.get(k) is not None:
                d[k] = np.asarray(d[k], dtype=dt)
        return Booster(**d)

    def model_to_string(self) -> str:
        """``saveNativeModel`` string — the REAL LightGBM model-text format
        (``LightGBMBooster.scala:277-310``): loadable by any LightGBM
        runtime, ONNX converters, and SHAP tooling. See
        :mod:`mmlspark_tpu.lightgbm.model_text` for encoding notes (the init
        score is folded into iteration-0 leaf values, as LightGBM's own
        boost_from_average does, so margins survive the round-trip)."""
        from mmlspark_tpu.lightgbm.model_text import to_lightgbm_text

        return to_lightgbm_text(self)

    def to_json_string(self) -> str:
        """Lossless internal JSON dump (keeps split_bin / bin_edges /
        init_score exactly — the stage-serialization payload)."""
        d = self.to_dict()
        for k, v in d.items():
            if isinstance(v, np.ndarray):
                d[k] = {"__nd__": v.tolist(), "dtype": str(v.dtype), "shape": v.shape}
        if d.get("cat_values") is not None:
            d["cat_values"] = {
                str(k): np.asarray(v).tolist() for k, v in d["cat_values"].items()
            }
        return json.dumps(d)

    @staticmethod
    def from_string(s: str) -> "Booster":
        """Parse either format: LightGBM model text (starts with ``tree``)
        or the internal JSON dump."""
        head = s.lstrip()[:16]
        if head.startswith("tree"):
            from mmlspark_tpu.lightgbm.model_text import from_lightgbm_text

            return from_lightgbm_text(s)
        d = json.loads(s)
        for k, v in list(d.items()):
            if isinstance(v, dict) and "__nd__" in v:
                d[k] = np.asarray(v["__nd__"], dtype=v["dtype"]).reshape(v["shape"])
        return Booster.from_dict(d)

    def feature_importances(self, importance_type: str = "split") -> np.ndarray:
        """Split-count or total-gain importance
        (``getFeatureImportances``, LightGBMBooster.scala:295-310)."""
        internal = (~self.is_leaf) & np.isfinite(self.split_threshold)
        feats = self.split_feature[internal]
        num_features = self.num_features
        if importance_type == "gain":
            if self.split_gain is None:
                raise ValueError(
                    "importance_type='gain' requires split_gain (absent on "
                    "this booster — e.g. merged from a booster without it)"
                )
            gains = self.split_gain[internal]
            out = np.zeros(num_features, dtype=np.float64)
            np.add.at(out, feats.ravel(), gains.ravel())
            return out
        if importance_type != "split":
            raise ValueError(f"unknown importance_type {importance_type!r}")
        return np.bincount(feats.ravel(), minlength=num_features).astype(np.float64)


def _csr_chunks(X, target_bytes: int = 256 << 20, dtype=np.float32):
    """None for dense inputs; for CSRMatrix, an iterator of densified row
    chunks sized so each chunk stays under ``target_bytes`` regardless of
    feature count (wide sparse data shrinks the row window).

    Categorical boosters must densify in float64: training bins CSR
    categorical values in f64 (``apply_bins_csr``), and a float32 detour
    would round category ids above 2**24 before ``_cat_binned``'s
    value-identity match, silently routing them as 'unseen'."""
    from mmlspark_tpu.data.sparse import CSRMatrix

    if not isinstance(X, CSRMatrix):
        return None
    itemsize = np.dtype(dtype).itemsize
    chunk_rows = min(
        65536, max(1, target_bytes // (itemsize * max(X.num_features, 1)))
    )
    return (
        X.row_slice(lo, min(lo + chunk_rows, X.num_rows)).to_dense(dtype)
        for lo in range(0, max(X.num_rows, 1), chunk_rows)
    )


# ---------------------------------------------------------------------------
# Path-matrix predict: trees as one MXU matmul instead of serial gathers
# ---------------------------------------------------------------------------
#
# Pointer-chasing routing costs max_depth serial gather rounds per tree —
# gathers are the slowest primitive on TPU (measured ~19 ms/round at 400k
# rows). The TPU-native formulation evaluates ALL internal-node decisions at
# once and selects the leaf algebraically:
#   d[n,i]   = x_{feat_i} <= thr_i (or NaN)        # (N, I) compares
#   D        = 2 d - 1                             # ±1
#   score    = D @ P                               # (N, L) MXU matmul
#   leaf     = argmax(score == pathlen)            # exact path match
# where P[i,l] is +1/-1/0 as leaf l's root path goes left/right/misses node
# i. A row matches pathlen[l] exactly for its true leaf only. Tree structure
# is host-precomputed once per booster (cached) and baked as constants.


def _thr_f32(thr) -> np.ndarray:
    """f64 thresholds → the LARGEST f32 value <= each threshold. For f32
    inputs x, ``x <= thr_f32`` then decides identically to LightGBM's f64
    ``x <= thr`` (round-to-nearest narrowing could round UP past the
    threshold and admit rows the f64 comparison rejects)."""
    thr = np.asarray(thr)
    if thr.dtype != np.float64:
        return thr.astype(np.float32)
    t32 = thr.astype(np.float32)
    over = t32.astype(np.float64) > thr
    if over.any():
        t32 = np.where(over, np.nextafter(t32, np.float32(-np.inf)), t32)
    return t32


class PathConsts(NamedTuple):
    """Per-tree padded predict constants (one derivation for everything
    the path-matrix kernels consume — _cat_paths aligns on `internals`)."""

    feats: np.ndarray  # (T, I) int32 split features
    thrs: np.ndarray  # (T, I) f32 thresholds (f64 snapped DOWN, _thr_f32)
    P: np.ndarray  # (T, I, L) ±1/0 path signs
    plen: np.ndarray  # (T, L) path lengths
    lvals: np.ndarray  # (T, L) leaf values
    lslots: np.ndarray  # (T, L) leaf slot ids
    nanl: np.ndarray  # (T, I) bool NaN-goes-left
    zm: np.ndarray  # (T, I) bool zero_as_missing
    internals: list  # per-tree internal-slot ordering


def _leaf_paths(b: "Booster", t: int) -> "PathConsts":
    feats_l, thrs_l, P_l, plen_l, lvals_l, lslots_l, nanl_l = [], [], [], [], [], [], []
    zm_l = []
    max_i = max_l = 1
    per_tree = []
    for ti in range(t):
        is_leaf = b.is_leaf[ti]
        left, right = b.left_child[ti], b.right_child[ti]
        feat, thr = b.split_feature[ti], b.split_threshold[ti]
        # DFS from the root collecting root->leaf paths
        paths = []  # (leaf_slot, [(internal_slot, +1|-1), ...])
        stack = [(0, [])]
        while stack:
            slot, path = stack.pop()
            if is_leaf[slot]:
                paths.append((slot, path))
                continue
            stack.append((int(left[slot]), path + [(slot, 1)]))
            stack.append((int(right[slot]), path + [(slot, -1)]))
        internal = sorted({s for _, path in paths for s, _ in path})
        per_tree.append((paths, internal))
        max_i = max(max_i, len(internal))
        max_l = max(max_l, len(paths))
    for ti in range(t):
        paths, internal = per_tree[ti]
        pos = {s: k for k, s in enumerate(internal)}
        fe = np.zeros(max_i, np.int32)
        th = np.full(max_i, np.inf, np.float32)  # padding: always-left, off-path
        nl = np.ones(max_i, bool)  # padding: NaN goes left (off-path anyway)
        zm = np.zeros(max_i, bool)  # padding: plain numeric comparison
        fe[: len(internal)] = b.split_feature[ti][internal]
        th[: len(internal)] = _thr_f32(b.split_threshold[ti][internal])
        if b.nan_left is not None:
            nl[: len(internal)] = b.nan_left[ti][internal]
        if b.zero_missing is not None:
            zm[: len(internal)] = b.zero_missing[ti][internal]
        P = np.zeros((max_i, max_l), np.float32)
        plen = np.full(max_l, np.float32(max_i + 1))  # unmatched sentinel
        lv = np.zeros(max_l, np.float32)
        ls = np.zeros(max_l, np.int32)
        for li, (slot, path) in enumerate(paths):
            for s, sign in path:
                P[pos[s], li] = sign
            plen[li] = len(path)
            lv[li] = b.leaf_values[ti][slot]
            ls[li] = slot
        feats_l.append(fe)
        thrs_l.append(th)
        nanl_l.append(nl)
        zm_l.append(zm)
        P_l.append(P)
        plen_l.append(plen)
        lvals_l.append(lv)
        lslots_l.append(ls)
    return PathConsts(
        feats=np.stack(feats_l),
        thrs=np.stack(thrs_l),
        P=np.stack(P_l),
        plen=np.stack(plen_l),
        lvals=np.stack(lvals_l),
        lslots=np.stack(lslots_l),
        nanl=np.stack(nanl_l),
        zm=np.stack(zm_l),
        internals=[internal for _, internal in per_tree],
    )


def _node_features(X, feats):
    """(N, T, I): each row's value of every internal node's split feature.
    ONE gather with the 2-D index — the flat gather + reshape spelling,
    ``take(X, feats.reshape(-1), axis=1).reshape(n, t, i)``, is miscompiled
    by XLA:TPU (libtpu 0.0.34) when the two fuse: at T*I = 300 every
    element came back wrong for 48k-70k rows per dispatch, so a 50k-row
    predict returned one constant (PERF.md, bring-up). ``chip_smoke.py``
    phase 2 walks the trees on the host to catch a recurrence."""
    return jnp.take(X, feats, axis=1)


def _path_match(X, feats, thrs, nanl, zm, P, plen):
    """(N, T, L) one-hot leaf membership per tree."""
    x = _node_features(X, feats)
    # missing (NaN — and 0.0 at zero_as_missing nodes) routes per the
    # node's nan_left flag; pads are always-left
    miss = jnp.isnan(x) | (zm[None] & (jnp.abs(x) <= K_ZERO_THRESHOLD))
    d = jnp.where(miss, nanl[None], x <= thrs[None])
    D = 2.0 * d.astype(jnp.float32) - 1.0  # (N, T, I)
    score = jnp.einsum(
        "nti,til->ntl", D, P, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )
    # true leaf: every on-path sign agrees -> score == plen; any miss costs 2
    return score >= plen[None]


@partial(jax.jit, static_argnames=("num_classes",))
def _predict_margin_paths_jit(X, feats, thrs, nanl, zm, P, plen, lvals, init_score, num_classes):
    match = _path_match(X, feats, thrs, nanl, zm, P, plen)
    # match is one-hot over leaves: the contribution IS a matmul, no gather
    contrib = jnp.einsum(
        "ntl,tl->nt", match.astype(jnp.float32), lvals,
        preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST,
    )
    n, t = contrib.shape
    rounds = t // num_classes
    margins = contrib.reshape(n, rounds, num_classes).sum(axis=1)
    return margins + init_score[None, :]


@jax.jit
def _predict_leaf_paths_jit(X, feats, thrs, nanl, zm, P, plen, lslots):
    match = _path_match(X, feats, thrs, nanl, zm, P, plen)
    # one-hot contraction again: slot id = sum_l match * slot_l
    return jnp.einsum(
        "ntl,tl->nt", match.astype(jnp.float32), lslots.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(jnp.int32)


def _path_match_cat_gather(X, feats, thrs, nanl, zm, P, plen, iscat, catm):
    """Memory-bounded categorical path match: flat 1-D gather over the
    (T, I, Bc) mask tables. ~Two orders of magnitude slower than the
    matmul kernel below (docs/perf_histogram.md round 5) — used only when
    the dense (T*I, Fc*Bc) mask matrix would exceed its size gate."""
    x = _node_features(X, feats)
    n = X.shape[0]
    t, i = feats.shape
    miss = jnp.isnan(x) | (zm[None] & (jnp.abs(x) <= K_ZERO_THRESHOLD))
    d_num = jnp.where(miss, nanl[None], x <= thrs[None])
    bc = catm.shape[-1]
    xb = jnp.clip(x, 0, bc - 1).astype(jnp.int32)
    lin = (
        jnp.arange(t, dtype=jnp.int32)[None, :, None] * (i * bc)
        + jnp.arange(i, dtype=jnp.int32)[None, None, :] * bc
        + xb
    )
    d = jnp.where(iscat[None], catm.reshape(-1)[lin], d_num)
    D = 2.0 * d.astype(jnp.float32) - 1.0
    score = jnp.einsum(
        "nti,til->ntl", D, P, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )
    return score >= plen[None]


@partial(jax.jit, static_argnames=("num_classes",))
def _predict_margin_paths_catgather_jit(
    X, feats, thrs, nanl, zm, P, plen, iscat, catm, lvals, init_score, num_classes
):
    match = _path_match_cat_gather(X, feats, thrs, nanl, zm, P, plen, iscat, catm)
    contrib = jnp.einsum(
        "ntl,tl->nt", match.astype(jnp.float32), lvals,
        preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST,
    )
    n, t = contrib.shape
    rounds = t // num_classes
    margins = contrib.reshape(n, rounds, num_classes).sum(axis=1)
    return margins + init_score[None, :]


@jax.jit
def _predict_leaf_paths_catgather_jit(
    X, feats, thrs, nanl, zm, P, plen, iscat, catm, lslots
):
    match = _path_match_cat_gather(X, feats, thrs, nanl, zm, P, plen, iscat, catm)
    return jnp.einsum(
        "ntl,tl->nt", match.astype(jnp.float32), lslots.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(jnp.int32)


def _path_match_cat(X, feats, thrs, nanl, zm, P, plen, iscat, cfeats, cm):
    """(N, T, L) leaf membership with categorical decisions: categorical
    columns of ``X`` hold value-bin ids (``Booster._cat_binned``); at cat
    nodes d = mask[bin] (bin 0 = unseen/NaN => right).

    Categorical decisions for EVERY node come from one MXU matmul: stacked
    per-feature bin one-hots (Fc*Bc, N) against the per-node mask matrix
    ``cm`` (T*I, Fc*Bc) built by ``_cat_paths``. Gather formulations of
    this lookup (3-axis batched or flattened) measured 300-450x slower
    than the numeric compare path on TPU (r5)."""
    x = _node_features(X, feats)
    n = X.shape[0]
    t, i = feats.shape
    miss = jnp.isnan(x) | (zm[None] & (jnp.abs(x) <= K_ZERO_THRESHOLD))
    d_num = jnp.where(miss, nanl[None], x <= thrs[None])
    fc = cfeats.shape[0]
    bc = cm.shape[1] // max(fc, 1)
    xc = jnp.take(X, cfeats, axis=1)  # (N, Fc) value-bin ids
    xct = jnp.clip(xc, 0, bc - 1).astype(jnp.int32).T  # (Fc, N)
    oh = (
        jnp.arange(bc, dtype=jnp.int32)[None, :, None] == xct[:, None, :]
    ).reshape(fc * bc, n)  # stacked per-feature one-hots
    D_cat = lax.dot_general(
        cm.astype(jnp.bfloat16), oh.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (T*I, N); exact: both operands 0/1
    d_cat = (D_cat > 0).T.reshape(n, t, i)
    d = jnp.where(iscat[None], d_cat, d_num)
    D = 2.0 * d.astype(jnp.float32) - 1.0
    score = jnp.einsum(
        "nti,til->ntl", D, P, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )
    return score >= plen[None]


@partial(jax.jit, static_argnames=("num_classes",))
def _predict_margin_paths_cat_jit(
    X, feats, thrs, nanl, zm, P, plen, iscat, cfeats, cm, lvals, init_score, num_classes
):
    match = _path_match_cat(X, feats, thrs, nanl, zm, P, plen, iscat, cfeats, cm)
    contrib = jnp.einsum(
        "ntl,tl->nt", match.astype(jnp.float32), lvals,
        preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST,
    )
    n, t = contrib.shape
    rounds = t // num_classes
    margins = contrib.reshape(n, rounds, num_classes).sum(axis=1)
    return margins + init_score[None, :]


@jax.jit
def _predict_leaf_paths_cat_jit(X, feats, thrs, nanl, zm, P, plen, iscat, cfeats, cm, lslots):
    match = _path_match_cat(X, feats, thrs, nanl, zm, P, plen, iscat, cfeats, cm)
    return jnp.einsum(
        "ntl,tl->nt", match.astype(jnp.float32), lslots.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ).astype(jnp.int32)


def _paths_cache(b: "Booster", t: int):
    cache = getattr(b, "_path_cache", None)
    if cache is None or cache[0] != t:
        consts = _leaf_paths(b, t)
        object.__setattr__(b, "_path_cache", (t, consts))
        cache = (t, consts)
    return cache[1]


def _cat_paths(b: "Booster", t: int):
    """(ISCAT (T, I), CFEATS (Fc,), CM (T*I, Fc*Bc)) aligned by construction
    with _leaf_paths' padded constants (it shares the internal-slot ordering
    _leaf_paths returns — no second derivation to drift).

    CM is the matmul form of the per-node left-set masks: row ti*I+ii of a
    categorical node carries its (Bc,) mask at the column block of its
    feature, so the whole batch's categorical decisions are ONE
    (T*I, Fc*Bc) x (Fc*Bc, N) contraction against stacked per-feature
    one-hots — the 3-axis batched gather this replaces ran ~450x slower
    than the numeric compare path (39k rows/s, r5)."""
    consts = _paths_cache(b, t)
    max_i = consts.feats.shape[1]
    internals = consts.internals
    bc = b.cat_masks.shape[-1]
    iscat = np.zeros((t, max_i), bool)
    catm = np.zeros((t, max_i, bc), bool)
    for ti in range(t):
        internal = internals[ti]
        iscat[ti, : len(internal)] = b.cat_nodes[ti][internal]
        catm[ti, : len(internal)] = b.cat_masks[ti][internal]
    cfeats = np.asarray(sorted(b.cat_values or {}), np.int32)
    # cm is block-sparse stored dense ((T*I, Fc*Bc), one Bc block per cat
    # node): Fc-times the old (T, I, Bc) tables. Gate it — a huge imported
    # forest with many high-cardinality features must fall back to the
    # (slow but memory-bounded) gather kernel rather than OOM.
    if t * max_i * len(cfeats) * bc <= _CM_BYTES_CAP:
        cpos = {int(f_): j for j, f_ in enumerate(cfeats)}
        cm = np.zeros((t * max_i, len(cfeats) * bc), np.uint8)
        for ti in range(t):
            for ii in np.nonzero(iscat[ti])[0]:
                j = cpos[int(consts.feats[ti, ii])]
                cm[ti * max_i + ii, j * bc : (j + 1) * bc] = catm[ti, ii]
        return ("matmul", iscat, cfeats, cm)
    return ("gather", iscat, catm)


def _cat_paths_cache(b: "Booster", t: int):
    cache = getattr(b, "_cat_path_cache", None)
    if cache is None or cache[0] != t:
        consts = _cat_paths(b, t)
        object.__setattr__(b, "_cat_path_cache", (t, consts))
        cache = (t, consts)
    return cache[1]


# ---------------------------------------------------------------------------
# Jitted predict kernels
# ---------------------------------------------------------------------------


def _route_rows(X, feat, thr, left, right, is_leaf, depth: int):
    """One tree, all rows: ``depth`` gather steps through the pointer arrays.
    X (N,F) raw float32. Returns final leaf slot (N,). Rows at a leaf stay."""
    n = X.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)
    for _ in range(depth):
        f = feat[node]  # (N,)
        t = thr[node]
        x = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
        go_left = jnp.isnan(x) | (x <= t)
        nxt = jnp.where(go_left, left[node], right[node])
        node = jnp.where(is_leaf[node], node, nxt)
    return node


@partial(jax.jit, static_argnames=("num_classes", "depth"))
def _predict_margin_jit(
    X, feat, thr, left, right, is_leaf, leaf_vals, init_score, num_classes, depth
):
    t = feat.shape[0]
    rounds = t // num_classes

    def r(a):
        return a.reshape(rounds, num_classes, -1)

    n = X.shape[0]

    def one_round(margins, tree):
        f, th, lc, rc, il, lv = tree

        def one_class(c):
            leaf = _route_rows(X, f[c], th[c], lc[c], rc[c], il[c], depth)
            return lv[c][leaf]

        contrib = jax.vmap(one_class, out_axes=1)(jnp.arange(num_classes))
        return margins + contrib, None

    init = jnp.broadcast_to(init_score[None, :], (n, num_classes))
    margins, _ = jax.lax.scan(
        one_round, init, (r(feat), r(thr), r(left), r(right), r(is_leaf), r(leaf_vals))
    )
    return margins


@partial(jax.jit, static_argnames=("depth",))
def _predict_leaf_jit(X, feat, thr, left, right, is_leaf, depth):
    def one_tree(tree):
        f, th, lc, rc, il = tree
        return _route_rows(X, f, th, lc, rc, il, depth)

    return jax.vmap(one_tree, out_axes=1)((feat, thr, left, right, is_leaf))
