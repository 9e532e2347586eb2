"""GBDT training loop: leaf-wise (LightGBM semantics) and level-wise growth,
jitted per-iteration step.

Replaces the reference's native training core (``LGBM_BoosterUpdateOneIter``
driven from ``lightgbm/TrainUtils.scala:220-315``) with a single jitted XLA
program per boosting iteration:

  gradients → histogram pass(es) → split search over the
  (node, feature, bin) lattice → routing update → leaf values → margins.

Two growth policies, both emitting pointer-based trees (see booster.py):

- ``leafwise`` (default — LightGBM's defining best-first algorithm,
  ``numLeaves`` bounds the *leaf count*, ``LightGBMParams.scala:13-251``):
  ``num_leaves - 1`` sequential splits; each step picks the frontier leaf
  with the best cached gain, routes its rows, and builds the two-child
  histogram in ONE masked one-hot pass over all rows. Static shapes
  throughout — the per-split histogram matmul is (N x 2B) so total FLOPs
  match a level-wise build of the same leaf count.
- ``depthwise``: every level is ONE dense histogram pass over all rows —
  fewer, larger MXU matmuls; the fast path when balanced trees are fine.

Early stopping, eval-metric direction, and improvement tolerance follow
``TrainUtils.scala:276-315``.

Distribution (``tree_learner=data_parallel``): rows are sharded over the
mesh ``data`` axis; the histogram is a row-sum, so XLA inserts the
cross-device all-reduce — the ``lax.psum`` equivalent of LightGBM's socket
allreduce. Split decisions are computed identically on every device from the
reduced histogram, so routing needs no further communication.
``tree_learner=voting_parallel`` (``topK``, ``LightGBMParams.scala:20-24``)
reduces only the top-K-voted features' histograms — see ``ops/voting.py``.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.core.device import cached_program, on_tpu, programs_built
from mmlspark_tpu.core.profiling import get_logger
from mmlspark_tpu.lightgbm.binning import BinMapper
from mmlspark_tpu.lightgbm.bundling import expand_maps, route_maps
from mmlspark_tpu.lightgbm.callbacks import (
    CallbackEnv,
    _has_iteration_hooks,
    _lr_schedule,
)
from mmlspark_tpu.observability.events import (
    HistogramChunked,
    HistogramDegraded,
    HistogramSubtracted,
    MemoryPressure,
    get_bus,
)
from mmlspark_tpu.observability.profiler import get_profiler
from mmlspark_tpu.observability.tracing import get_tracer
from mmlspark_tpu.lightgbm.booster import Booster
from mmlspark_tpu.lightgbm.objectives import (
    METRICS,
    Objective,
    get_objective,
    metric_higher_is_better,
)
from mmlspark_tpu.ops.histogram import build_histograms
from mmlspark_tpu.ops.u_histogram import (
    build_histograms_u,
    build_histograms_u_chunked,
    build_u,
    chunked_u_spec,
    dequant_hist,
    histogram_acc_dtype,
    make_u_spec,
    num_u_chunks,
    prepare_chunked_bins,
    stat_rows,
    stat_rows_quant,
    u_bytes,
)
from mmlspark_tpu.parallel.mesh import (
    AXIS_MODEL,
    data_sharding,
    feature_parallel_sharding,
    pad_to_multiple,
    replicated,
)
from mmlspark_tpu.runtime.faults import current_faults, is_oom_error


@dataclasses.dataclass
class TrainOptions:
    """Native ``TrainParams`` equivalent (``lightgbm/TrainParams.scala:8-128``),
    defaults matching ``LightGBMParams.scala:13-251``."""

    objective: str = "binary"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = -1  # -1: unbounded (leafwise) / derived (depthwise)
    max_bin: int = 255
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    bagging_fraction: float = 1.0
    # class-stratified bagging (LightGBM pos/neg_bagging_fraction; 1.0 = off,
    # both must be set together with bagging_freq to take effect)
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    feature_fraction: float = 1.0
    max_delta_step: float = 0.0
    num_class: int = 1
    alpha: float = 0.9  # quantile/huber
    tweedie_variance_power: float = 1.5
    boosting_type: str = "gbdt"
    metric: Optional[str] = None
    early_stopping_round: int = 0
    improvement_tolerance: float = 0.0
    seed: int = 0
    histogram_method: Optional[str] = None
    growth: str = "leafwise"  # leafwise | depthwise
    tree_learner: str = "data_parallel"  # data_parallel | voting_parallel
    top_k: int = 20  # voting_parallel vote width
    top_rate: float = 0.2  # goss: kept fraction of large-gradient rows
    other_rate: float = 0.1  # goss: sampled fraction of the rest
    drop_rate: float = 0.1  # dart: per-tree drop probability
    leaf_batch: int = 8  # frontier leaves split per histogram pass (1 = exact best-first)
    # LightGBM's gradient-quantization training (use_quantized_grad): g/h
    # stochastically rounded to a 127-level per-tree grid so the U-pass
    # histogram contraction runs s8 x s8 on the int MXU (2x the ops/cycle
    # of bf16) — per-bin sums stay unbiased, counts exact below 2^24 rows
    # (the f32 integer-exactness limit; the row gate enforces it). Only
    # affects fits on the precomputed-U path; off = bit-exact bf16 stats.
    use_quantized_grad: bool = False
    # Sibling histogram subtraction (native LightGBM's always-on trick,
    # exposed as a knob for A/B measurement): build only the SMALLER child
    # of each split and derive the sibling as parent - smaller, in packed
    # (pre-EFB-expansion) space — integer-exact on the quantized path, so
    # subtraction on/off grows byte-identical trees there. Off = build
    # both children directly (the measurement baseline).
    histogram_subtraction: bool = True
    # only batch leaves with gain >= ratio * pass-best (0 = off): tightens
    # multi-leaf passes toward best-first; 1.0 reproduces leaf_batch=1
    leaf_batch_ratio: float = 0.0
    # categorical split search (LightGBMParams.scala:125-133 forwards these
    # to native LightGBM; same names/defaults as the native engine):
    categorical_slots: tuple = ()  # feature indices treated as categorical
    max_cat_threshold: int = 32  # max categories in a split's left set
    cat_smooth: float = 10.0  # smoothing for the g/h category sort
    cat_l2: float = 10.0  # extra L2 applied to categorical split gains
    # one-vs-rest split search for categorical features with at most this
    # many seen categories (native LightGBM's max_cat_to_onehot; the engine
    # the reference forwards to switches algorithms on this boundary)
    max_cat_to_onehot: int = 4
    # sorted-path candidate gate: categories with fewer rows than this never
    # enter the g/h-ratio sort (native min_data_per_group; the one-vs-rest
    # path is exempt, as in the native engine)
    min_data_per_group: int = 100
    # derived from the mapper at fit time: the categorical_slots subset that
    # uses the one-vs-rest search (static => part of the program cache key)
    onehot_slots: tuple = ()
    # boost_from_average=False: margins start at 0 instead of the
    # objective's average-based init score (LightGBMParams boostFromAverage)
    boost_from_average: bool = True
    # compute the train-set metric each iteration into evals["training"]
    # (isProvideTrainingMetric; forces the per-iteration loop path)
    provide_training_metric: bool = False
    verbosity: int = -1

    @property
    def depth(self) -> int:
        """Static depth of a depthwise tree."""
        if self.max_depth and self.max_depth > 0:
            return self.max_depth
        return max(1, math.ceil(math.log2(max(2, self.num_leaves))))

    @property
    def num_nodes(self) -> int:
        """Node-slot count M of one tree in pointer layout."""
        if self.growth == "depthwise":
            return 2 ** (self.depth + 1) - 1
        return 2 * self.num_leaves - 1

    @property
    def routing_steps(self) -> int:
        """Static bound on tree depth for routing loops."""
        if self.growth == "depthwise":
            return self.depth
        if self.max_depth and self.max_depth > 0:
            return min(self.max_depth, self.num_leaves - 1)
        return self.num_leaves - 1


@dataclasses.dataclass
class TrainResult:
    booster: Booster
    evals: Dict[str, Dict[str, List[float]]]  # set name -> metric -> history
    best_iteration: int


class TreeArrays(NamedTuple):
    """One tree in pointer layout (each (M,) — or (C, M) after vmap)."""

    feat: jax.Array
    bin: jax.Array
    thr: jax.Array
    left: jax.Array
    right: jax.Array
    is_leaf: jax.Array
    leaf_val: jax.Array
    cover: jax.Array
    gain: jax.Array
    row_leaf: jax.Array  # (N,) final leaf slot of every training row
    cat_node: jax.Array  # (M,) bool: categorical split at this node
    cat_mask: jax.Array  # (M, B) bool left-set bins ((M, 1) placeholder when no cat)
    passes: jax.Array  # (2,) int32: histogram passes the grower built, and skipped


class SplitSearch(NamedTuple):
    """Per-node best-split candidates from one histogram batch (each (k,))."""

    value: jax.Array  # own leaf value (lr-scaled)
    cover: jax.Array  # row count
    hess: jax.Array  # hessian sum
    gain: jax.Array  # best gain, -inf if unsplittable
    feat: jax.Array
    bin: jax.Array
    thr: jax.Array  # raw-value threshold
    lval: jax.Array  # left child value if split (lr-scaled)
    rval: jax.Array
    lcov: jax.Array
    rcov: jax.Array
    is_cat: jax.Array  # (k,) bool: categorical split (bin = the prefix-
    # defining BIN id; the left set itself lives in cat_mask)
    cat_mask: jax.Array  # (k, B) bool: bins in the LEFT set (all-False if numeric)


def _soft_threshold(g: jax.Array, l1: float) -> jax.Array:
    if l1 == 0.0:
        return g
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


@lru_cache(maxsize=None)
def _cat_static_maps(
    cat_slots: tuple, onehot_slots: tuple, num_features: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host-side index maps for the categorical split search, memoized on
    the (static) slot tuples so none of the numpy setup runs under trace:
    sorted categorical feature indices, the is-categorical mask, the
    feature -> categorical-slice position map, and the one-vs-rest mask."""
    cat_idx = np.asarray(sorted(cat_slots), np.int32)
    is_cat = np.zeros(num_features, bool)
    is_cat[cat_idx] = True
    inv = np.zeros(num_features, np.int32)
    inv[cat_idx] = np.arange(len(cat_idx))
    onehot = np.isin(cat_idx, np.asarray(onehot_slots, np.int32))
    return cat_idx, is_cat, inv, onehot


@jax.named_scope("split_search")
def _split_search(
    hist: jax.Array,  # (k, F, B, 3)
    totals: jax.Array,  # (k, 3) exact per-node [sum_g, sum_h, count]
    edges: jax.Array,  # (F, E)
    feature_mask: jax.Array,  # (F,)
    opts: TrainOptions,
    lr=None,  # traced per-iteration learning rate (dynamic-LR callbacks)
) -> SplitSearch:
    """Best split per node from its histogram — the split-finding core the
    native library runs per leaf (``TrainUtils.scala:220-315`` inner loop)."""
    k, f, b, _ = hist.shape
    l1, l2 = opts.lambda_l1, opts.lambda_l2
    if lr is None:
        lr = opts.learning_rate

    g_tot, h_tot, c_tot = totals[:, 0], totals[:, 1], totals[:, 2]

    # Left stats at "<= bin": a lower-triangular ones-matmul over the bin
    # axis instead of jnp.cumsum — XLA lowers cumsum to reduce-window on
    # TPU (measured 0.27 ms per search at B=256, ~1.4 ms/tree), while the
    # (B, B) triangle rides the MXU for free. Counts stay exact below
    # 2^24 rows (0/1 triangle x integer sums; f32 holds integers exactly
    # only up to 2^24 — the quantized-path row gate enforces the bound,
    # and the exact path's counts carry the same f32 caveat past it);
    # g/h association differs from
    # reduce-window's only within f32 rounding, which the cumsum lowering
    # never specified either.
    tri = jnp.tril(jnp.ones((b, b), jnp.float32))
    cum = jnp.einsum(
        "ij,kfjs->kfis", tri, hist, precision=lax.Precision.HIGHEST
    )  # (k, F, B, 3)
    gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
    gr = g_tot[:, None, None] - gl
    hr = h_tot[:, None, None] - hl
    cr = c_tot[:, None, None] - cl

    tl, tr = _soft_threshold(gl, l1), _soft_threshold(gr, l1)
    tg = _soft_threshold(g_tot, l1)
    parent_score = (tg * tg) / (h_tot + l2)  # (k,)
    gain = tl * tl / (hl + l2) + tr * tr / (hr + l2) - parent_score[:, None, None]

    valid = (
        (cl >= opts.min_data_in_leaf)
        & (cr >= opts.min_data_in_leaf)
        & (hl >= opts.min_sum_hessian_in_leaf)
        & (hr >= opts.min_sum_hessian_in_leaf)
        & (jnp.arange(b)[None, None, :] < b - 1)
        & (feature_mask[None, :, None] > 0)
    )
    gain = jnp.where(valid, gain, -jnp.inf)

    # Categorical split search (LightGBM's sorted-prefix algorithm, native
    # FindBestThresholdCategoricalInner): bins of a categorical feature sort
    # by sum_g / (sum_h + cat_smooth) and the candidate left sets are the
    # prefixes of that order — scanned in BOTH directions (a small
    # high-ratio set is a short descending prefix), capped at
    # max_cat_threshold categories, with lambda_l2 + cat_l2 regularization.
    # The missing bin 0 never enters a left set (unseen/NaN routes right).
    has_cat = bool(opts.categorical_slots)
    if has_cat:
        # All sorted-prefix machinery runs on the (k, F_cat, B) SLICE only —
        # sorts are the expensive primitive here, and categorical features
        # are typically a small subset of the matrix.
        cat_idx_np, cf_np, inv_np, oh_np = _cat_static_maps(
            opts.categorical_slots, opts.onehot_slots, f
        )
        cat_idx = jnp.asarray(cat_idx_np)
        hist_c = hist[:, cat_idx]  # (k, Fc, B, 3)
        gsum, hsum, cnt = hist_c[..., 0], hist_c[..., 1], hist_c[..., 2]
        jpos = jnp.arange(b)[None, None, :]
        # min_data_per_group gates the SORTED candidates (native builds its
        # sorted_idx list only from categories with enough rows; the
        # one-vs-rest path below is exempt, also as in native)
        nonempty = (cnt >= max(1, opts.min_data_per_group)) & (jpos > 0)
        ratio = gsum / (hsum + opts.cat_smooth)
        l2c = l2 + opts.cat_l2
        parent_c = (tg * tg) / (h_tot + l2c)  # tg shared with the numeric branch
        fm_c = feature_mask[cat_idx]
        # Sorted-prefix search WITHOUT sorting: the prefix of the g/h-ratio
        # order ending at category i is exactly {j : key_j <= key_i} (ties
        # broken by bin index, = a stable sort's order), so each candidate's
        # prefix sums are one masked einsum against the (B, B) order-
        # indicator M — dense MXU/VPU work replacing the per-pass argsort +
        # gather + cumsum chain (which also made the CPU test battery ~2x
        # slower). Candidate index = BIN id (the prefix-defining category),
        # and the winner's left-set mask is just M's row — no order
        # permutation to invert. Both scan directions ride a leading axis d
        # (0 = ascending ratio, 1 = descending).
        keys = jnp.stack([ratio, -ratio], axis=0)  # (2, k, Fc, B)
        ki = keys[..., :, None]  # key_i, candidate axis
        kj = keys[..., None, :]  # key_j, member axis
        tie = jnp.arange(b)[None, :] <= jnp.arange(b)[:, None]  # j <= i
        M = ((kj < ki) | ((kj == ki) & tie)) & nonempty[None, ..., None, :]
        Mf = M.astype(jnp.float32)
        hp = lax.Precision.HIGHEST

        def prefix(stat):  # (k, Fc, B) member sums -> (2, k, Fc, B) per candidate
            return jnp.einsum("dkfij,kfj->dkfi", Mf, stat, precision=hp)

        sg, sh, sc = prefix(gsum), prefix(hsum), prefix(cnt)
        sizes = prefix(nonempty.astype(jnp.float32))
        grc = g_tot[None, :, None, None] - sg
        hrc = h_tot[None, :, None, None] - sh
        crc = c_tot[None, :, None, None] - sc
        tlc, trc = _soft_threshold(sg, l1), _soft_threshold(grc, l1)
        gain_c = (
            tlc * tlc / (sh + l2c)
            + trc * trc / (hrc + l2c)
            - parent_c[None, :, None, None]
        )
        valid_c = (
            nonempty[None]  # the prefix-defining category itself qualifies
            & (sizes <= opts.max_cat_threshold)
            & (sc >= opts.min_data_in_leaf)
            & (crc >= opts.min_data_in_leaf)
            & (sh >= opts.min_sum_hessian_in_leaf)
            & (hrc >= opts.min_sum_hessian_in_leaf)
            & (fm_c[None, None, :, None] > 0)
        )
        gain_dirs = jnp.where(valid_c, gain_c, -jnp.inf)  # (2, k, Fc, B)
        gain_cat = jnp.maximum(gain_dirs[0], gain_dirs[1])
        use_desc = gain_dirs[1] > gain_dirs[0]  # (k, Fc, B)

        # One-vs-rest search (native use_onehot, max_cat_to_onehot): for
        # small-cardinality features the candidates are the SINGLE-category
        # left sets {bin j} — position j in the gain plane IS bin j (no sort
        # order involved). Same lambda_l2 + cat_l2 regularization; no
        # cat_smooth, no min_data_per_group (native's one-hot loop applies
        # neither). Bin 0 (unseen/NaN) never splits left.
        if oh_np.any():
            gr_oh = g_tot[:, None, None] - gsum
            hr_oh = h_tot[:, None, None] - hsum
            cr_oh = c_tot[:, None, None] - cnt
            tl_oh = _soft_threshold(gsum, l1)
            tr_oh = _soft_threshold(gr_oh, l1)
            gain_oh = (
                tl_oh * tl_oh / (hsum + l2c)
                + tr_oh * tr_oh / (hr_oh + l2c)
                - parent_c[:, None, None]
            )
            valid_oh = (
                (jpos > 0)
                & (cnt >= opts.min_data_in_leaf)
                & (cr_oh >= opts.min_data_in_leaf)
                & (hsum >= opts.min_sum_hessian_in_leaf)
                & (hr_oh >= opts.min_sum_hessian_in_leaf)
                & (fm_c[None, :, None] > 0)
            )
            gain_oh = jnp.where(valid_oh, gain_oh, -jnp.inf)
            oh_mask = jnp.asarray(oh_np)  # (Fc,) static
            gain_cat = jnp.where(oh_mask[None, :, None], gain_oh, gain_cat)
        gain = gain.at[:, cat_idx, :].set(gain_cat)

    flat = gain.reshape(k, f * b)
    best_idx = jnp.argmax(flat, axis=1)  # (k,)
    best_gain = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
    best_f = (best_idx // b).astype(jnp.int32)
    best_b = (best_idx % b).astype(jnp.int32)

    def leaf_value(g, h):
        v = -_soft_threshold(g, l1) / (h + l2)
        if opts.max_delta_step > 0:
            v = jnp.clip(v, -opts.max_delta_step, opts.max_delta_step)
        return v * lr

    iota = jnp.arange(k)
    glb = gl[iota, best_f, best_b]
    hlb = hl[iota, best_f, best_b]
    clb = cl[iota, best_f, best_b]

    # Raw threshold: split bin t means "x <= edges[f, t-1]"; t=0 ⇒ NaN-only left.
    thr_raw = edges[best_f, jnp.maximum(best_b - 1, 0)]
    thr_raw = jnp.where(best_b == 0, -jnp.inf, thr_raw).astype(jnp.float32)

    is_cat_best = jnp.zeros(k, bool)
    cat_mask = jnp.zeros((k, b), bool)
    if has_cat:
        # Native parity: leaves created BY a categorical split get outputs
        # regularized with lambda_l2 + cat_l2 (LightGBM's
        # CalculateSplittedLeafOutput for the categorical path).
        def leaf_value_cat(g, h):
            v = -_soft_threshold(g, l1) / (h + l2 + opts.cat_l2)
            if opts.max_delta_step > 0:
                v = jnp.clip(v, -opts.max_delta_step, opts.max_delta_step)
            return v * lr

        is_cat_best = jnp.asarray(cf_np)[best_f]  # (k,)
        cpos = jnp.asarray(inv_np)[best_f]  # (k,) index into the cat slice
        dsel = use_desc[iota, cpos, best_b].astype(jnp.int32)  # (k,) direction

        glb_c = sg[dsel, iota, cpos, best_b]
        hlb_c = sh[dsel, iota, cpos, best_b]
        clb_c = sc[dsel, iota, cpos, best_b]
        # One-vs-rest winners read their left stats STRAIGHT from the
        # histogram at bin best_b (no prefix involved).
        is_oh_best = (
            jnp.asarray(oh_np)[cpos] & is_cat_best
            if oh_np.any() else jnp.zeros(k, bool)
        )
        if oh_np.any():
            glb_c = jnp.where(is_oh_best, gsum[iota, cpos, best_b], glb_c)
            hlb_c = jnp.where(is_oh_best, hsum[iota, cpos, best_b], hlb_c)
            clb_c = jnp.where(is_oh_best, cnt[iota, cpos, best_b], clb_c)
        glb = jnp.where(is_cat_best, glb_c, glb)
        hlb = jnp.where(is_cat_best, hlb_c, hlb)
        clb = jnp.where(is_cat_best, clb_c, clb)
        thr_raw = jnp.where(is_cat_best, jnp.inf, thr_raw)
        # Left-set membership: the winning candidate's row of M IS the set.
        cat_mask = M[dsel, iota, cpos, best_b, :] & is_cat_best[:, None]
        if oh_np.any():
            # one-vs-rest left set = exactly {best_b}
            cat_mask = jnp.where(
                is_oh_best[:, None],
                jnp.arange(b)[None, :] == best_b[:, None],
                cat_mask,
            )
        lval = jnp.where(
            is_cat_best, leaf_value_cat(glb, hlb), leaf_value(glb, hlb)
        )
        rval = jnp.where(
            is_cat_best,
            leaf_value_cat(g_tot - glb, h_tot - hlb),
            leaf_value(g_tot - glb, h_tot - hlb),
        )
    else:
        lval = leaf_value(glb, hlb)
        rval = leaf_value(g_tot - glb, h_tot - hlb)

    return SplitSearch(
        value=leaf_value(g_tot, h_tot),
        cover=c_tot,
        hess=h_tot,
        gain=best_gain,
        feat=best_f,
        bin=best_b,
        thr=thr_raw,
        lval=lval,
        rval=rval,
        lcov=clb,
        rcov=c_tot - clb,
        is_cat=is_cat_best,
        cat_mask=cat_mask,
    )


def _bundle_route_consts(bundle):
    """Device views of the per-original-feature routing arrays (col, lo,
    span, skip, dflt) — host lru-cached numpy underneath, so traces close
    over stable constants."""
    return tuple(jnp.asarray(a) for a in route_maps(bundle))


def _orig_bins(packed_cols, feats, consts):
    """Packed column values → ORIGINAL-feature bin ids at a routing site.

    ``packed_cols`` holds bin values already gathered from each feature's
    packed column (any shape broadcastable with ``feats``); ``feats`` are
    original feature ids. q = xb - lo recovers the member-local offset,
    the +1 skip jump crosses the member's elided default bin, and any
    out-of-span value means some OTHER member of the bundle was
    non-default — i.e. this feature sat at its default bin."""
    _, lo, span, skip, dflt = consts
    xb = packed_cols.astype(jnp.int32)
    q = xb - lo[feats]
    inb = (q >= 0) & (q < span[feats])
    return jnp.where(inb, q + (q >= skip[feats]).astype(jnp.int32), dflt[feats])


def _expand_bundled(h, totals, bundle, num_bins):
    """Bundle-space histogram (k, C, B_b, 3) → original space (k, F, B, 3).

    Runs ONCE per pass, after the optional cross-process reduce (so the
    allreduce payload stays in the smaller packed space). Each original
    feature's non-default bins gather straight out of its packed column;
    the default bin is recovered by subtraction from the per-node totals
    (LightGBM's most_freq_bin trick) — counts stay exact, grad/hess exact
    up to f32 association order."""
    cidx, gmask, dmask = expand_maps(bundle, num_bins)
    k = h.shape[0]
    flat = h.reshape(k, -1, 3)  # (k, C*B_b, 3)
    dense = jnp.take(flat, jnp.asarray(cidx.reshape(-1)), axis=1)
    dense = dense.reshape(k, bundle.num_features, num_bins, 3)
    dense = dense * jnp.asarray(gmask)[None, :, :, None]
    resid = totals[:, None, :] - dense.sum(axis=2)
    return dense + jnp.asarray(dmask)[None, :, :, None] * resid[:, :, None, :]


def _hist_fn(opts: TrainOptions, mesh=None, u_spec=None, hist_reduce=None,
             bundle=None):
    """Histogram builder honoring the tree_learner choice. Returns a
    callable producing (hist (k,F,B,3), totals (k,3)); ``feature_mask``
    (featureFraction) steers voting so reduced histograms are spent only
    on splittable features.

    ``hist_reduce`` is the cross-PROCESS reduction hook (data-parallel
    fit over OS processes, ``lightgbm/procfit.py``): a host callable
    summing the local histogram across the worker gang — LightGBM's
    socket ``Network::Allreduce`` at the same point in the algorithm. It
    is injected via ``jax.pure_callback`` right after the local build, so
    everything downstream (totals, split search, leaf values) sees GLOBAL
    statistics and every member grows byte-identical trees. The histogram
    is the only tensor that crosses processes; its shape is row-count
    independent, so members with different shard sizes stay aligned.

    When ``u_spec`` is set and the caller passes the fit-resident ``u``
    one-hot (``ops/u_histogram.py``), passes whose panel fits one lane
    group run as a single MXU contraction against U — measured 2.1x the
    compare-built kernel at the bench hot shape; wider passes (deep
    depthwise levels) fall back to the compare-built path."""
    if opts.tree_learner == "voting_parallel":
        from mmlspark_tpu.ops.voting import build_histograms_voting

        vfull = partial(
            build_histograms_voting,
            top_k=opts.top_k,
            mesh=mesh,
            # 'u' has no meaning inside the voting reducer — auto-pick there
            method=None if opts.histogram_method == "u" else opts.histogram_method,
        )

        def voting(bins, grad, hess, count, node, num_nodes, num_bins,
                   feature_mask=None, u=None, stats=None):
            return vfull(bins, grad, hess, count, node, num_nodes, num_bins,
                         feature_mask=feature_mask)

        return voting

    method = opts.histogram_method
    if method == "u":
        method = None  # 'u' forces the U path; fallback shape-gated passes auto-pick
    if mesh is not None and method in (None, "pallas"):
        # pallas_call has no GSPMD partitioning rule: under jit with
        # row-sharded inputs it cannot shard over the data axis the way the
        # plain-XLA formulations do, so the mesh path sticks to those.
        method = "onehot" if on_tpu() else "segment"

    def packed(bins, grad, hess, count, node, num_nodes, num_bins,
               feature_mask=None, u=None, stats=None):
        """SPEC-space histogram (k, C, B_b, 3) + per-node totals — the
        pass BEFORE dequantization and bundle expansion. This is the
        representation the sibling-subtraction cache lives in: packed
        columns (C <= F under EFB) and, on the quantized U path, the
        narrow integer accumulator dtype — so parent - child is an exact
        integer subtraction and the allreduce payload stays minimal."""
        if u is not None and u_spec is not None and 3 * num_nodes <= 128:
            pass_u = build_histograms_u_chunked if u_spec.chunk_rows else build_histograms_u
            h = pass_u(
                u, grad, hess, count, node, num_nodes, u_spec,
                stats=stats, dequant=False,
            )
        else:
            h = build_histograms(
                bins, grad, hess, count, node, num_nodes,
                bundle.num_bins if bundle is not None else num_bins,
                method=method, chunk_rows=(mesh is None),
            )
        if hist_reduce is not None:
            # host round-trip per histogram pass; "expand_dims" keeps one
            # callback call under the per-class vmap so gang members make
            # identical, aligned allreduce sequences. Runs in the packed
            # space, so under sibling subtraction the gang allreduces only
            # the smaller child's histograms (the quant path never reaches
            # here — procfit rejects it — so the payload is always f32).
            h = jax.pure_callback(
                hist_reduce, jax.ShapeDtypeStruct(h.shape, h.dtype), h,
                vmap_method="expand_dims",
            )
        totals = h[:, 0, :, :].sum(axis=1)  # feature/column 0 covers all rows
        return h, totals

    def expand(h, totals, num_bins, stats=None):
        """Finish a ``packed`` result for the split search: apply the
        deferred quant scales (exactly once, AFTER any subtraction), then
        expand EFB's packed columns back to original feature space
        (``num_bins`` = the ORIGINAL bin width the search expects)."""
        if jnp.issubdtype(h.dtype, jnp.integer):
            scales = stats[1]
            h = dequant_hist(h, scales)
            totals = dequant_hist(totals, scales)
        if bundle is not None:
            h = _expand_bundled(h, totals, bundle, num_bins)
        return h, totals

    def full(bins, grad, hess, count, node, num_nodes, num_bins,
             feature_mask=None, u=None, stats=None):
        h, totals = packed(
            bins, grad, hess, count, node, num_nodes, num_bins,
            feature_mask=feature_mask, u=u, stats=stats,
        )
        return expand(h, totals, num_bins, stats=stats)

    full.packed = packed
    full.expand = expand
    return full


# ---------------------------------------------------------------------------
# Depthwise (level-wise) growth — one histogram pass per level.
# ---------------------------------------------------------------------------


def _build_tree_depthwise(
    bins: jax.Array,  # (N, F) int32
    grad: jax.Array,  # (N,)
    hess: jax.Array,  # (N,)
    count: jax.Array,  # (N,) 1/0 bagging presence
    edges: jax.Array,  # (F, E) float32 raw-value bin edges
    feature_mask: jax.Array,  # (F,) float32 0/1
    *,
    num_bins: int,
    opts: TrainOptions,
    histf,
    lr=None,
    u=None,
    qkey=None,
    bundle=None,
) -> TreeArrays:
    n = bins.shape[0]
    b = num_bins
    depth = opts.depth
    stats = _tree_stats(grad, hess, count, qkey) if u is not None else None
    rconsts = _bundle_route_consts(bundle) if bundle is not None else None

    node = jnp.zeros(n, dtype=jnp.int32)  # heap position
    alive = jnp.ones(1, dtype=bool)
    inherited = jnp.zeros(1, dtype=jnp.float32)
    cover_cur = jnp.zeros(1, dtype=jnp.float32)

    has_cat = bool(opts.categorical_slots)
    feat_lv, bin_lv, thr_lv, cover_lv, gain_lv = [], [], [], [], []
    iscat_lv, catmask_lv = [], []

    for d in range(depth):
        k = 1 << d
        offset = k - 1
        local = node - offset
        hist, totals = histf(
            bins, grad, hess, count, local, k, b, feature_mask=feature_mask,
            u=u, stats=stats,
        )
        # (k, F, B, 3) — row-sum: XLA all-reduces across data shards here.
        s = _split_search(hist, totals, edges, feature_mask, opts, lr=lr)

        can_split = alive & jnp.isfinite(s.gain) & (s.gain > opts.min_gain_to_split)
        # A node's value-if-it-ends-here is what its PARENT's split assigned
        # (``inherited`` — which carries the l2+cat_l2 output for children of
        # categorical splits); recomputing from own totals would silently
        # drop that regularization. The root has no parent: use its own.
        value_cur = s.value if d == 0 else inherited
        cover_here = jnp.where(alive, s.cover, cover_cur)

        # Record this level (dead/non-split nodes: bin=b ⇒ every row left, thr=+inf).
        feat_lv.append(jnp.where(can_split, s.feat, 0))
        bin_lv.append(jnp.where(can_split, s.bin, b))
        thr_lv.append(jnp.where(can_split, s.thr, jnp.inf).astype(jnp.float32))
        cover_lv.append(cover_here)
        gain_lv.append(jnp.where(can_split, s.gain, 0.0))
        if has_cat:
            iscat_lv.append(can_split & s.is_cat)
            catmask_lv.append(s.cat_mask & can_split[:, None])

        # Route rows down one level. Split features/bins live in ORIGINAL
        # space (histograms are expanded before the search); under bundling
        # the row's value gathers from the feature's packed column and
        # decodes back to an original bin before the compare.
        with jax.named_scope("route"):
            row_f = feat_lv[-1][local]
            row_b = bin_lv[-1][local]
            row_c = rconsts[0][row_f] if rconsts is not None else row_f
            x_bin = jnp.take_along_axis(bins, row_c[:, None], axis=1)[:, 0]
            if rconsts is not None:
                x_bin = _orig_bins(x_bin, row_f, rconsts)
            go_right = x_bin > row_b
            if has_cat:
                ic = iscat_lv[-1][local]
                cm = catmask_lv[-1].reshape(-1)[local * b + x_bin.astype(jnp.int32)]
                go_right = jnp.where(ic, ~cm, go_right)
            go_right = go_right.astype(jnp.int32)
            node = 2 * node + 1 + go_right

        inherited = jnp.stack(
            [
                jnp.where(can_split, s.lval, value_cur),
                jnp.where(can_split, s.rval, value_cur),
            ],
            axis=1,
        ).reshape(2 * k)
        cover_cur = jnp.stack(
            [
                jnp.where(can_split, s.lcov, cover_here),
                jnp.where(can_split, s.rcov, 0.0),
            ],
            axis=1,
        ).reshape(2 * k)
        alive = jnp.repeat(can_split, 2)

    # Heap → pointer layout: internal slots 0..2^D-2, leaves 2^D-1..2^(D+1)-2.
    internal = 2**depth - 1
    leaves = 2**depth
    iota = jnp.arange(internal, dtype=jnp.int32)
    zeros_l = jnp.zeros(leaves, dtype=jnp.int32)
    return TreeArrays(
        feat=jnp.concatenate([jnp.concatenate(feat_lv), zeros_l]),
        bin=jnp.concatenate([jnp.concatenate(bin_lv), jnp.full(leaves, b, jnp.int32)]),
        thr=jnp.concatenate(
            [jnp.concatenate(thr_lv), jnp.full(leaves, jnp.inf, jnp.float32)]
        ),
        left=jnp.concatenate([2 * iota + 1, zeros_l]),
        right=jnp.concatenate([2 * iota + 2, zeros_l]),
        is_leaf=jnp.concatenate(
            [jnp.zeros(internal, bool), jnp.ones(leaves, bool)]
        ),
        leaf_val=jnp.concatenate([jnp.zeros(internal, jnp.float32), inherited]),
        cover=jnp.concatenate([jnp.concatenate(cover_lv), cover_cur]),
        gain=jnp.concatenate([jnp.concatenate(gain_lv), jnp.zeros(leaves, jnp.float32)]),
        row_leaf=node,  # already absolute pointer slots
        cat_node=(
            jnp.concatenate([jnp.concatenate(iscat_lv), jnp.zeros(leaves, bool)])
            if has_cat else jnp.zeros(internal + leaves, bool)
        ),
        cat_mask=(
            jnp.concatenate(
                [jnp.concatenate(catmask_lv), jnp.zeros((leaves, b), bool)]
            )
            if has_cat else jnp.zeros((internal + leaves, 1), bool)
        ),
        passes=jnp.array([depth, 0], jnp.int32),  # one a level, none after the last
    )


# ---------------------------------------------------------------------------
# Leaf-wise (best-first) growth — LightGBM's algorithm.
# ---------------------------------------------------------------------------


def _build_tree_leafwise(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    count: jax.Array,
    edges: jax.Array,
    feature_mask: jax.Array,
    *,
    num_bins: int,
    opts: TrainOptions,
    histf,
    lr=None,
    u=None,
    u_spec=None,
    qkey=None,
    bundle=None,
) -> TreeArrays:
    """Best-first growth, ``leaf_batch`` frontier leaves per histogram pass.

    A round splits the top-``k`` frontier leaves by cached candidate gain
    (``split_round``: rows routed, nodes and leaves recorded), and the next
    round begins by building its children's histograms in ONE node-keyed
    pass (``build_round``) — the panel formulation
    (``ops/pallas_histogram.py``) makes a k-node pass cost the same as a
    1-node pass. A leaf's value and cover come from the split that made it
    (LightGBM's rule, and the depth-wise grower's), so a pass exists only to
    find the children's OWN splits, and the round that spends the last of
    the leaf budget is followed by none. Passes a tree: the root's, plus one
    for every round but the last when the budget ends the tree (``R``
    rounds: ``1 + R - 1``; a 31-leaf tree at ``k = 8`` splits 1, 2, 4, 8, 8,
    7 leaves, so 6 passes), plus one for every round when nothing is left
    worth splitting or the depth cap ends it (``1 + R``: that the last
    round's children cannot be split is what its pass finds out).
    ``TreeArrays.passes`` counts built and skipped. Under ``vmap`` (the
    boosting step: one tree a class) the loop runs while any class's tree
    grows, so a pass is saved when every class's tree ends on its budget.

    ``k = 1`` is LightGBM's exact sequential best-first; ``k > 1``
    approximates it (the k-th split is committed before the first split's
    children can compete — ties and near-ties resolve in frontier-gain
    order, then by lower slot index, matching ``lax.top_k``'s ordering).
    Slots are allocated densely in split order: the j-th split overall
    creates slots 2j+1 and 2j+2, so the layout is deterministic and
    static-shaped (M = 2*num_leaves - 1) and ``k = 1`` reproduces the
    sequential layout bit-for-bit."""
    # Under bundling ``bins`` is (N, C) packed columns while the histogram
    # cache / subtraction / search all live in ORIGINAL feature space —
    # f here sizes those, NOT the packed width.
    n = bins.shape[0]
    f = bundle.num_features if bundle is not None else bins.shape[1]
    rconsts = _bundle_route_consts(bundle) if bundle is not None else None
    b = num_bins
    num_leaves = opts.num_leaves
    m = 2 * num_leaves - 1
    max_depth = opts.max_depth if (opts.max_depth and opts.max_depth > 0) else m

    # Histogram subtraction (LightGBM's core trick): cache every frontier
    # leaf's histogram, build only the SMALLER child of each split per
    # pass, and derive the sibling as parent - smaller — halving the node
    # count of the hot pass from 2k to k AND keying the pass on the child
    # with fewer rows. The cache lives in PACKED space — (M, C, B_b, 3)
    # where C is the EFB-packed column count and, on the quantized U path,
    # the narrow integer accumulator dtype — so subtraction is an exact
    # integer op before dequantization/expansion and the cache shrinks
    # with the K-reduction. :func:`_subtraction` gates it, on the same
    # shapes the fit's plan reads.
    c_cols = bins.shape[1]  # packed column count (== f without bundling)
    b_pack = bundle.num_bins if bundle is not None else b
    quant = u is not None and qkey is not None
    use_sub = _subtraction(opts, n, c_cols, b_pack, quant).on
    # Panel-pass node budget: 3 stats x nodes must fit one 128-lane group
    # (subtraction keys k left children; without it 2k child nodes).
    cap = 42 if use_sub else 21
    k = max(1, min(opts.leaf_batch, num_leaves - 1, cap))

    def searchk(histk, totalsk, depthk):
        """Candidate searches for freshly created children; depth-capped.
        NaN gains (0/0 under zero-regularization params) are sanitized to
        -inf at write time so one poisoned candidate can neither halt the
        whole build through cond's max nor win an argmax."""
        s = _split_search(histk, totalsk, edges, feature_mask, opts, lr=lr)
        capped = jnp.where(depthk >= max_depth, -jnp.inf, s.gain)
        capped = jnp.where(jnp.isnan(capped), -jnp.inf, capped)
        return s._replace(gain=capped)

    # Per-tree hoist for the U path: the (3, N) stat rows are node-
    # independent, so they upload to the panel layout once per tree.
    stats = _tree_stats(grad, hess, count, qkey) if u is not None else None

    # Root: one-node histogram over all rows. Under subtraction the packed
    # (pre-expansion) result seeds the cache and is expanded separately
    # for the search.
    if use_sub:
        root_p, root_tp = histf.packed(
            bins, grad, hess, count, jnp.zeros(n, jnp.int32), 1, b,
            feature_mask=feature_mask, u=u, stats=stats,
        )
        root_hist, root_tot = histf.expand(root_p, root_tp, b, stats=stats)
    else:
        root_hist, root_tot = histf(
            bins, grad, hess, count, jnp.zeros(n, jnp.int32), 1, b,
            feature_mask=feature_mask, u=u, stats=stats,
        )
    root = _split_search(root_hist, root_tot, edges, feature_mask, opts, lr=lr)

    def at0(template, s_):
        return template.at[0].set(s_[0])

    def kids_of(search):
        return jnp.stack(
            [search.lval, search.rval, search.lcov, search.rcov], axis=1
        )  # (nodes, 4)

    has_cat = bool(opts.categorical_slots)
    # Categorical-row view of U, sliced ONCE here (outside the while_loop —
    # XLA does not hoist the gather out of the loop body; left inside it
    # re-sliced ~90 MB per pass and cost ~1 s per mixed fit, measured r5).
    u_cat = fr_dev = lrow_dev = None
    if (
        has_cat and u is not None and u_spec is not None
        and not u_spec.chunk_rows  # chunked u is a bins stack, not a one-hot
    ):
        if bundle is not None:
            # categoricals are identity columns under bundling: only the
            # column lookup changes; the matmul still matches ORIGINAL ids
            from mmlspark_tpu.lightgbm.bundling import cat_row_maps_bundled

            rows_np, fr_np, lr_np = cat_row_maps_bundled(
                u_spec, bundle, opts.categorical_slots
            )
        else:
            from mmlspark_tpu.ops.u_histogram import cat_row_maps

            rows_np, fr_np, lr_np = cat_row_maps(u_spec, opts.categorical_slots)
        u_cat = u[jnp.asarray(rows_np)]
        fr_dev = jnp.asarray(fr_np)
        lrow_dev = jnp.asarray(lr_np)
    zi = jnp.zeros(m, jnp.int32)
    zf = jnp.zeros(m, jnp.float32)
    state = dict(
        node=jnp.zeros(n, dtype=jnp.int32),
        feat=zi,
        bin=jnp.full(m, b, jnp.int32),
        thr=jnp.full(m, jnp.inf, jnp.float32),
        left=zi,
        right=zi,
        is_leaf=jnp.zeros(m, bool).at[0].set(True),
        leaf_val=at0(zf, root.value),
        cover=at0(zf, root.cover),
        gain=zf,
        depth=zi,
        n_splits=jnp.int32(0),
        # frontier candidates (-inf gain = not frontier / not splittable;
        # NaN sanitized at write so top_k's order stays NaN-free)
        c_gain=jnp.full(m, -jnp.inf).at[0].set(
            jnp.where(jnp.isnan(root.gain[0]), -jnp.inf, root.gain[0])
        ),
        c_feat=at0(zi, root.feat),
        c_bin=at0(zi, root.bin),
        c_thr=at0(zf, root.thr),
        # what the candidate split would leave in its two children: leaf
        # values (l2 + cat_l2 under a categorical split) and row counts
        c_kids=at0(jnp.zeros((m, 4), jnp.float32), kids_of(root)),
        passes=jnp.int32(1),  # histogram passes built: the root's so far
    )
    if has_cat:
        zb = jnp.zeros(m, bool)
        zmb = jnp.zeros((m, b), bool)
        state.update(
            cat_node=zb,
            cat_mask=zmb,
            c_iscat=at0(zb, root.is_cat),
            c_catmask=zmb.at[0].set(root.cat_mask[0]),
        )
    if use_sub:
        # Packed-space cache: C columns x bundle-bin width in the pass's
        # accumulator dtype (narrow int on the quantized U path) — the
        # subtraction happens here, BEFORE dequant/EFB expansion.
        state["leaf_hist"] = (
            jnp.zeros((m, c_cols, b_pack, 3), root_p.dtype).at[0].set(root_p[0])
        )
        state["leaf_tot"] = (
            jnp.zeros((m, 3), root_tp.dtype).at[0].set(root_tp[0])
        )
        # Which child of each cached candidate split is SMALLER (by row
        # count): the pass builds that child and derives the other. False
        # (left) for non-candidates — harmless, their gain is -inf.
        state["c_subR"] = jnp.zeros(m, bool).at[0].set(
            root.rcov[0] < root.lcov[0]
        )

    def put(arr, slots, values):
        """Guarded scatter: a disabled lane's slot is m, out of range, and is
        dropped, never clipped onto a live slot."""
        return arr.at[slots].set(values, mode="drop")

    def put_children(arr, lslot, rslot, left, right):
        return put(put(arr, lslot, left), rslot, right)

    def split_round(st, lanes=k):
        """Split the top ``lanes`` frontier leaves by cached candidate gain
        (sorted descending, ties by lower slot index): route their rows,
        record the nodes and the two leaves each leaves behind, and leave
        in ``pending`` what a pass over the new children needs. Reads no
        histogram. (The root's round has a frontier of one: one lane.)"""
        top_g, top_l = lax.top_k(st["c_gain"], lanes)
        j = jnp.arange(lanes, dtype=jnp.int32)
        can = (top_g > opts.min_gain_to_split) & (
            st["n_splits"] + j < num_leaves - 1
        )  # monotone in j: gains sorted descending, budget consumed in order
        if opts.leaf_batch_ratio > 0.0:
            # quality gate: only leaves whose gain is within ratio of the
            # pass best split together — tightens batched growth toward
            # sequential best-first (monotone in j: gains sorted). Lane 0 IS
            # the pass best, so it always qualifies — without that exemption
            # a negative best gain (legal when min_gain_to_split < 0) fails
            # its own ratio test and the while_loop never makes progress.
            can = can & ((j == 0) | (top_g >= opts.leaf_batch_ratio * top_g[0]))
        lslot = 2 * (st["n_splits"] + j) + 1
        rslot = lslot + 1
        gparent = jnp.where(can, top_l, m)
        glslot = jnp.where(can, lslot, m)
        grslot = jnp.where(can, rslot, m)

        sf = st["c_feat"][top_l]  # (k,) split feature / bin / threshold
        sb = st["c_bin"][top_l]
        sthr = st["c_thr"][top_l]
        if use_sub:
            small_r = st["c_subR"][top_l]  # (k,) smaller child is RIGHT
        if has_cat:
            sic = st["c_iscat"][top_l]  # (k,)
            scm = st["c_catmask"][top_l]  # (k, B)

        # Route rows and build the next pass's node keys in one unrolled
        # sweep: key = j for rows entering split j's SMALLER child
        # (subtraction mode; 2j + went_right without), 2k (invalid)
        # elsewhere — the panel histogram drops out-of-range keys, so the
        # key IS the in-leaf mask and grad/hess need no masking pass.
        with jax.named_scope("route"):
            node = st["node"]
            new_node = node
            key = jnp.full(n, 2 * k, jnp.int32)
            in_set = None
            if u_cat is not None:
                # Categorical membership for ALL k leaves as one MXU matmul
                # against the CATEGORICAL rows of the fit-resident one-hot U
                # (streams ~Σ cat widths per pass, not K_pad); the per-leaf
                # gather fallback below serves the no-U paths (mesh, CPU).
                from mmlspark_tpu.ops.u_histogram import membership_matmul

                in_set = membership_matmul(u_cat, fr_dev, lrow_dev, sf, scm, n)
            # One (N, k) gather for all k split columns — k separate lane-axis
            # dynamic slices each paid their own relayout (measured ~2 ms/tree
            # at k=16); jnp.take batches them into a single op. Under bundling
            # the gather targets the packed columns and decodes to original
            # bins for the whole (N, k) block at once.
            if rconsts is not None:
                cols = jnp.take(bins, rconsts[0][sf], axis=1)  # (N, k) packed
                cols = _orig_bins(cols, sf, rconsts)
            else:
                cols = jnp.take(bins, sf, axis=1)  # (N, k)
            for jj in range(lanes):
                colj = cols[:, jj]
                in_j = (node == top_l[jj]) & can[jj]
                right_j = colj > sb[jj]
                if has_cat:
                    # categorical: LEFT iff the row's bin is in the split set
                    right_j = jnp.where(
                        sic[jj],
                        ~in_set[jj]
                        if in_set is not None
                        else ~scm[jj][colj.astype(jnp.int32)],
                        right_j,
                    )
                new_node = jnp.where(
                    in_j, jnp.where(right_j, rslot[jj], lslot[jj]), new_node
                )
                if use_sub:
                    # key rows landing in the SMALLER child (right when
                    # small_r, else left) — the built child of split jj
                    key = jnp.where(
                        in_j & (right_j == small_r[jj]), jj, key
                    )
                else:
                    key = jnp.where(in_j, 2 * jj + right_j.astype(jnp.int32), key)

        st = dict(st)
        st["node"] = new_node
        st["feat"] = put(st["feat"], gparent, sf)
        st["bin"] = put(st["bin"], gparent, sb)
        st["thr"] = put(st["thr"], gparent, sthr)
        st["left"] = put(st["left"], gparent, lslot)
        st["right"] = put(st["right"], gparent, rslot)
        st["is_leaf"] = put_children(
            put(st["is_leaf"], gparent, False), glslot, grslot, True, True
        )
        # A leaf's value and cover come from the split that CREATED it
        # (native parity; children of categorical splits carry the
        # l2+cat_l2 output): known when the parent's split was chosen, so
        # no pass is ever built for their sake.
        kids = st["c_kids"][top_l]  # (k, 4)
        st["leaf_val"] = put_children(
            st["leaf_val"], glslot, grslot, kids[:, 0], kids[:, 1]
        )
        st["cover"] = put_children(
            st["cover"], glslot, grslot, kids[:, 2], kids[:, 3]
        )
        st["gain"] = put(st["gain"], gparent, top_g)
        child_depth = st["depth"][top_l] + 1  # (k,)
        st["depth"] = put_children(
            st["depth"], glslot, grslot, child_depth, child_depth
        )
        # off the frontier; the children join it if build_round finds
        # their splits (their fresh slots' gains are -inf until then)
        st["c_gain"] = put(st["c_gain"], gparent, -jnp.inf)
        if has_cat:
            st["cat_node"] = put(st["cat_node"], gparent, sic)
            st["cat_mask"] = put(st["cat_mask"], gparent, scm)
        st["n_splits"] = st["n_splits"] + can.sum().astype(jnp.int32)
        st["grew"] = can[0]  # the round split at least one leaf

        def lane(values, idle):  # the pass has k lanes whatever the round had
            return jnp.pad(values, (0, k - lanes), constant_values=idle)

        # the new children, which no pass has built yet: every row's node
        # key (2k = in none of them) and, a lane of the pass, the parent,
        # its two slots (m = the lane split nothing) and their depth
        st["pending"] = (
            key, lane(top_l, 0), lane(glslot, m), lane(grslot, m), lane(child_depth, 0)
        )
        return st

    def build_round(st):
        """One histogram pass over the children the last round created, and
        the search for each one's own best split: they join the frontier."""
        key, top_l, glslot, grslot, child_depth = st["pending"]
        if use_sub:
            # Build the smaller child in PACKED space, derive the sibling
            # as parent - smaller (exact integer subtraction on the quant
            # path — the derived sibling is bit-identical to a direct
            # build), then assign built/derived back to left/right.
            small_r = st["c_subR"][top_l]
            histS, totS = histf.packed(
                bins, grad, hess, count, key, k, b, feature_mask=feature_mask,
                u=u, stats=stats,
            )  # (k, C, B_b, 3)
            histO = st["leaf_hist"][top_l] - histS
            totO = st["leaf_tot"][top_l] - totS
            sel = small_r[:, None, None, None]
            histL_p = jnp.where(sel, histO, histS)
            histR_p = jnp.where(sel, histS, histO)
            totL_p = jnp.where(small_r[:, None], totO, totS)
            totR_p = jnp.where(small_r[:, None], totS, totO)
            hlr, tlr = histf.expand(
                jnp.concatenate([histL_p, histR_p]),
                jnp.concatenate([totL_p, totR_p]),
                b, stats=stats,
            )
        else:
            h2, t2 = histf(
                bins, grad, hess, count, key, 2 * k, b, feature_mask=feature_mask,
                u=u, stats=stats,
            )
            # (2k,) keyed [2j + went_right] -> [left children | right children]
            hlr = h2.reshape(k, 2, f, b, 3).swapaxes(0, 1).reshape(2 * k, f, b, 3)
            tlr = t2.reshape(k, 2, 3).swapaxes(0, 1).reshape(2 * k, 3)

        cs = searchk(
            hlr, tlr, jnp.concatenate([child_depth, child_depth])
        )  # (2k,) fields: [left children | right children]

        def children(name, values):
            return put_children(st[name], glslot, grslot, values[:k], values[k:])

        new = dict(
            c_gain=children("c_gain", cs.gain),
            c_feat=children("c_feat", cs.feat),
            c_bin=children("c_bin", cs.bin),
            c_thr=children("c_thr", cs.thr),
            c_kids=children("c_kids", kids_of(cs)),
            passes=st["passes"] + 1,
        )
        if has_cat:
            new["c_iscat"] = children("c_iscat", cs.is_cat)
            new["c_catmask"] = children("c_catmask", cs.cat_mask)
        if use_sub:
            new["leaf_hist"] = put_children(
                st["leaf_hist"], glslot, grslot, histL_p, histR_p
            )
            new["leaf_tot"] = put_children(
                st["leaf_tot"], glslot, grslot, totL_p, totR_p
            )
            new["c_subR"] = children("c_subR", cs.rcov < cs.lcov)
        return {**st, **new}

    # The root's round, then a pass and a round for as long as the last
    # round grew the tree and left budget for its children to be split in
    # turn. The exit follows a round's routing, so the children of the round
    # that spends the budget are never built: the tree ends there whatever
    # their histograms would say, and nothing else reads them. A round that
    # finds no gain worth a split, or meets the depth cap, splits nothing,
    # and the pass before it was the cost of finding that out. (The exit,
    # and not a ``lax.cond`` around the pass: the boosting step vmaps the
    # grower over classes, a single class too, and there a cond is a select
    # that runs both branches.)
    def cond(st):
        return st["grew"] & (st["n_splits"] < num_leaves - 1)

    state = jax.lax.while_loop(
        cond, lambda st: split_round(build_round(st)), split_round(state, lanes=1)
    )
    # at the exit a round that grew the tree can only have spent the budget
    skipped = state["grew"].astype(jnp.int32)

    return TreeArrays(
        feat=state["feat"],
        bin=state["bin"],
        thr=state["thr"],
        left=state["left"],
        right=state["right"],
        is_leaf=state["is_leaf"],
        leaf_val=state["leaf_val"],
        cover=state["cover"],
        gain=state["gain"],
        row_leaf=state["node"],
        cat_node=state["cat_node"] if has_cat else jnp.zeros(m, bool),
        cat_mask=state["cat_mask"] if has_cat else jnp.zeros((m, 1), bool),
        passes=jnp.stack([state["passes"], skipped]),
    )


# ---------------------------------------------------------------------------
# Boosting step
# ---------------------------------------------------------------------------


def _route_binned(
    bins: jax.Array, feat, binthr, left, right, is_leaf, steps: int,
    cat_node=None, cat_mask=None, bundle_consts=None,
) -> jax.Array:
    """Route binned rows through one pointer tree; returns final leaf slot.
    ``cat_mask`` (M, B) bool: at categorical nodes (``cat_node``) a row goes
    LEFT iff its bin is in the node's set ((M, 1) placeholder = no cats).
    ``bundle_consts`` (from :func:`_bundle_route_consts`): ``bins`` is EFB-
    packed — gather each node's packed column and decode to the original
    bin before the compare; tree arrays are always in original space."""
    n = bins.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)
    for _ in range(steps):
        fcur = feat[node]
        bcur = binthr[node]
        fcol = bundle_consts[0][fcur] if bundle_consts is not None else fcur
        x_bin = jnp.take_along_axis(bins, fcol[:, None], axis=1)[:, 0]
        if bundle_consts is not None:
            x_bin = _orig_bins(x_bin, fcur, bundle_consts)
        go_left = x_bin <= bcur
        if cat_mask is not None and cat_mask.shape[-1] > 1:
            bwidth = cat_mask.shape[-1]
            cm = cat_mask.reshape(-1)[node * bwidth + x_bin.astype(jnp.int32)]
            go_left = jnp.where(cat_node[node], cm, go_left)
        nxt = jnp.where(go_left, left[node], right[node])
        node = jnp.where(is_leaf[node], node, nxt)
    return node


def _tree_stats(grad, hess, count, qkey=None):
    if qkey is not None:
        return stat_rows_quant(grad, hess, count, qkey)
    return stat_rows(grad, hess, count)


def _make_step(
    opts: TrainOptions, objective: Objective, num_bins: int, mesh=None,
    n_real: Optional[int] = None, u_spec=None, hist_reduce=None, bundle=None,
):
    build = (
        _build_tree_leafwise if opts.growth == "leafwise" else _build_tree_depthwise
    )
    histf = _hist_fn(opts, mesh, u_spec, hist_reduce=hist_reduce, bundle=bundle)
    obj_kwargs = {
        "num_classes": opts.num_class,
        "alpha": opts.alpha,
        "tweedie_variance_power": opts.tweedie_variance_power,
    }

    def step(bins, y, w, margins, edges, bag_mask, feature_mask, it, lr=None, u=None):
        with jax.named_scope("grad_hess"):
            grad, hess = objective.grad_hess(margins, y, w, **obj_kwargs)  # (N, C)

        if opts.boosting_type == "goss":
            # Gradient-based One-Side Sampling: keep the top_rate fraction of
            # rows by |gradient|, sample other_rate of the rest, and amplify
            # the sampled small-gradient rows by (1-a)/b so histogram sums
            # stay unbiased (the GOSS estimator from the LightGBM paper).
            # Exactly n_top rows are kept (top_k index selection, ties broken
            # by lower row index — LightGBM's own sort-based top-N), and
            # n_top is computed from the UNPADDED row count so mesh padding
            # never inflates the kept fraction.
            n_rows = grad.shape[0]
            gabs = jnp.abs(grad).sum(axis=1) * bag_mask
            n_top = max(1, int(round((n_real or n_rows) * opts.top_rate)))
            _, top_idx = lax.top_k(gabs, n_top)
            top = jnp.zeros(n_rows, bool).at[top_idx].set(True)
            key = jax.random.fold_in(jax.random.PRNGKey(opts.seed), it)
            p = opts.other_rate / max(1e-12, 1.0 - opts.top_rate)
            sampled = (~top) & (jax.random.uniform(key, (n_rows,)) < p)
            amp = (1.0 - opts.top_rate) / max(1e-12, opts.other_rate)
            goss_w = top.astype(grad.dtype) + sampled.astype(grad.dtype) * amp
            bag_mask = bag_mask * goss_w

        grad = grad * bag_mask[:, None]
        hess = hess * bag_mask[:, None]
        count = (bag_mask > 0).astype(grad.dtype)

        def per_class(g, h, qk=None):
            kw = {"u_spec": u_spec} if opts.growth == "leafwise" else {}
            return build(
                bins, g, h, count, edges, feature_mask,
                num_bins=num_bins, opts=opts, histf=histf, lr=lr, u=u,
                qkey=qk, bundle=bundle, **kw,
            )

        if opts.use_quantized_grad and u is not None:
            # One stochastic-rounding key per (iteration, margin column);
            # folded from the fit seed so quantized fits are run-to-run
            # deterministic like everything else. grad.shape[1], NOT
            # opts.num_class: binary classifiers carry num_class=2 with a
            # single margin column.
            qkeys = jax.random.split(
                jax.random.fold_in(
                    jax.random.PRNGKey(opts.seed ^ 0x51AB51AB), it
                ),
                grad.shape[1],
            )
            tree = jax.vmap(per_class, in_axes=(1, 1, 0))(grad, hess, qkeys)
        else:
            tree = jax.vmap(per_class, in_axes=(1, 1))(grad, hess)  # (C, ...)

        # Percentile leaf renewal (native RenewTreeOutput,
        # regression_objective.hpp): quantile and L1 objectives have
        # CONSTANT-magnitude gradients, so gradient-derived leaf values move
        # margins by at most ~lr per iteration in RAW label units — on
        # unscaled targets the fit never reaches the requested percentile.
        # Native replaces each leaf's output with the weighted alpha-
        # percentile (L1: median) of the leaf's residuals, then shrinks by
        # the learning rate; so do we, before margins update.
        if objective.name in ("quantile", "regression_l1"):
            pct = opts.alpha if objective.name == "quantile" else 0.5
            lr_t = lr if lr is not None else opts.learning_rate
            resid = y - margins[:, 0]
            w_eff = w * bag_mask
            leaf = tree.row_leaf[0]  # (N,) — both objectives are C=1
            m_slots = tree.leaf_val.shape[1]
            n_rows = resid.shape[0]
            # O(N) weighted per-leaf percentile: order rows by (leaf,
            # residual) with two STABLE sorts (a composite integer sort key
            # would silently overflow int32 at large num_leaves x rows —
            # TPU truncates int64), then ONE global weight cumsum with
            # per-leaf boundaries from segment reductions — no
            # (num_leaves, N) matrix materializes inside the scanned step.
            perm1 = jnp.argsort(resid)
            order = perm1[jnp.argsort(leaf[perm1], stable=True)]
            r_s = resid[order]
            l_s = leaf[order]
            w_s = w_eff[order]
            cum_all = jnp.cumsum(w_s)
            tw = jax.ops.segment_sum(w_s, l_s, num_segments=m_slots)
            before = cum_all - w_s  # exclusive global prefix
            start = jax.ops.segment_min(before, l_s, num_segments=m_slots)
            in_leaf_cum = cum_all - start[l_s]  # inclusive prefix WITHIN leaf
            hit = in_leaf_cum >= jnp.maximum(pct * tw[l_s], 1e-12)
            # f32 rounding of million-row global cumsums can leave the
            # threshold unreached in a leaf at alpha near 1; the percentile
            # is always <= the leaf's max residual, so the last row of each
            # leaf hits by definition.
            last_in_leaf = jnp.concatenate(
                [l_s[1:] != l_s[:-1], jnp.ones(1, bool)]
            )
            hit = hit | last_in_leaf
            pos = jnp.where(hit, jnp.arange(n_rows), n_rows)
            first = jax.ops.segment_min(pos, l_s, num_segments=m_slots)
            vals = r_s[jnp.clip(first, 0, n_rows - 1)] * lr_t
            renewed = jnp.where(
                (tw > 0) & (first < n_rows), vals, tree.leaf_val[0]
            )
            tree = tree._replace(leaf_val=renewed[None, :])

        if opts.boosting_type == "rf":
            # Random-forest mode: trees fit the init-score residual
            # independently; margins never accumulate during training and
            # the final booster's leaf values are averaged post-hoc.
            return tree, margins
        # margins update: row_leaf (C, N) slots into leaf_val (C, M)
        with jax.named_scope("margin_update"):
            contrib = jnp.take_along_axis(tree.leaf_val, tree.row_leaf, axis=1).T  # (N, C)
            return tree, margins + contrib

    return step


def _opts_key(opts: "TrainOptions"):
    return dataclasses.astuple(opts)


#: TrainOptions fields the many-models plane threads through the compiled
#: program as TRACED per-candidate data instead of baked constants, each
#: with the value a bucket's representative pins it to: learning_rate
#: rides the scanned (K, iterations) lr stack, and the bagging/feature-
#: fraction knobs only shape the host-side _mask_schedule draws (the
#: program consumes the resulting mask stacks, never the fractions
#: themselves). Everything else — num_leaves, num_iterations,
#: regularization, objective, seed (GOSS/quantized bake PRNGKey(seed)
#: statically) — changes the traced program and therefore the bucket.
_MANY_PINNED = dict(
    learning_rate=0.0, feature_fraction=1.0, bagging_fraction=1.0,
    bagging_freq=0, pos_bagging_fraction=1.0, neg_bagging_fraction=1.0,
)
MANY_VMAPPED_FIELDS = tuple(_MANY_PINNED)


def normalize_many_opts(opts: "TrainOptions") -> "TrainOptions":
    """Canonical representative of ``opts``' shape-bucket: the vmapped
    fields pinned to fixed values. Two candidates batch into one compiled
    program iff their normalized options (plus mapper/objective context)
    agree — the shape-bucketing rule documented in docs/automl_sweep.md."""
    return dataclasses.replace(opts, **_MANY_PINNED)


def many_bucket_key(opts: "TrainOptions"):
    """Hashable shape-bucket key for the many-models plane."""
    return _opts_key(normalize_many_opts(opts))


def _scan_program(step, per_iter_bag: bool, per_iter_lr: bool = False,
                  with_u: bool = False, many: bool = False):
    """All boosting iterations in ONE device program: ``lax.scan`` over the
    per-tree step, per-iteration bagging/feature masks as scanned inputs,
    stacked tree arrays as the scan output. One dispatch and one bulk fetch
    replace per-iteration host round-trips.

    When bagging never resamples (``per_iter_bag=False``) the single (N,)
    mask is closed over inside the program rather than scanned, so no
    (iterations, N) buffer is ever materialized. A dynamic learning-rate
    schedule (``per_iter_lr``) rides as one more scanned (iterations,)
    input — schedule callbacks keep the one-dispatch fast path.

    ``with_u`` (U histogram path): the caller builds the fit-resident
    one-hot ONCE per fit and passes it in — building it inside this program
    would redo the multi-GB materialization once per SEGMENT when a long
    fit is split into several dispatches.

    ``many``: the many-models program, the same body vmapped over a leading
    candidate axis so K same-shaped fits train in ONE compiled dispatch.
    Data (bins, y, w, edges) is SHARED across candidates (in_axes=None — XLA
    keeps one copy); margins, the masks and the learning-rate stack carry
    the candidate axis. lr is always scanned there: it is the vmapped
    hyperparameter, and a traced f32 scalar is bit-identical to the baked
    Python float the sequential path closes over (weak f32 typing), so
    batched and sequential fits agree. When no candidate in the bucket bags
    the shared (N,) presence mask broadcasts and no (K, iterations, N) mask
    stack ever materializes."""
    per_iter_lr = per_iter_lr or many

    def run(bins, y, w, margins, edges, bag, fm_all, lr_all, it0, u_arg):
        iters = fm_all.shape[0]
        u = u_arg if with_u else None

        def body(m, per_iter):
            it, fmv = per_iter[0], per_iter[-1 if not per_iter_lr else -2]
            bag_i = per_iter[1] if per_iter_bag else bag
            lr_i = per_iter[-1] if per_iter_lr else None
            tree, m2 = step(
                bins, y, w, m, edges, bag_i.astype(jnp.float32), fmv, it, lr_i,
                u=u,
            )
            return m2, tree._replace(row_leaf=jnp.zeros((), jnp.int32))

        # global iteration ids (it0 > 0 on segmented fits): GOSS's per-
        # iteration rng folds on these, so segments never repeat a stream
        idx = jnp.arange(iters, dtype=jnp.int32) + it0
        xs = [idx]
        if per_iter_bag:
            xs.append(bag)
        xs.append(fm_all)
        if per_iter_lr:
            xs.append(lr_all)
        margins_out, trees = lax.scan(body, margins, tuple(xs))
        return margins_out, trees

    if many:
        in_axes = (
            None, None, None, 0, None, 0 if per_iter_bag else None, 0, 0,
            None, None,
        )
        run = jax.vmap(run, in_axes=in_axes)
    return jax.jit(run, donate_argnums=(3,))


def _bagging_active(opts: "TrainOptions") -> bool:
    return opts.bagging_freq > 0 and (
        opts.bagging_fraction < 1.0
        or opts.pos_bagging_fraction < 1.0
        or opts.neg_bagging_fraction < 1.0
    )


def _mask_schedule(opts: "TrainOptions", n, pad, f, y=None):
    """Per-iteration (bag_mask, bag_changed, feature_mask_or_None) — the ONE
    definition of the bagging/feature-sampling schedule and its rng stream,
    shared by the scan and loop paths so they cannot diverge. Class-
    stratified bagging (pos/neg_bagging_fraction) samples each binary class
    at its own rate, matching native LightGBM's goal-oriented sampling."""
    rng = np.random.default_rng(opts.seed)
    num_bag = max(1, int(round(n * opts.bagging_fraction)))
    num_feat = max(1, int(round(f * opts.feature_fraction)))
    bag = np.ones(n + pad, dtype=np.float32)  # presence: the pad tail is zero
    bag[n:] = 0.0
    stratified = (
        opts.pos_bagging_fraction < 1.0 or opts.neg_bagging_fraction < 1.0
    ) and y is not None
    if stratified:
        pos_idx = np.nonzero(np.asarray(y[:n]) > 0.5)[0]
        neg_idx = np.nonzero(np.asarray(y[:n]) <= 0.5)[0]
        n_pos = max(1, int(round(len(pos_idx) * opts.pos_bagging_fraction)))
        n_neg = max(1, int(round(len(neg_idx) * opts.neg_bagging_fraction)))
    for it in range(opts.num_iterations):
        changed = False
        if _bagging_active(opts):
            if it % opts.bagging_freq == 0:
                bag = np.zeros(n + pad, dtype=np.float32)
                if stratified:
                    if len(pos_idx):
                        bag[rng.choice(pos_idx, size=n_pos, replace=False)] = 1.0
                    if len(neg_idx):
                        bag[rng.choice(neg_idx, size=n_neg, replace=False)] = 1.0
                else:
                    bag[rng.choice(n, size=num_bag, replace=False)] = 1.0
                changed = True
        if opts.feature_fraction < 1.0:
            fm = np.zeros(f, dtype=np.float32)
            fm[rng.choice(f, size=num_feat, replace=False)] = 1.0
        else:
            fm = None
        yield bag, changed, fm


def _make_tree_contrib(steps: int, bundle=None):
    """(N, C) margin contribution of ONE tree-round on a binned matrix —
    used by dart mode to subtract dropped trees. ``bundle``: the matrix is
    EFB-packed; routing decodes per-node original bins on the fly."""
    consts = _bundle_route_consts(bundle) if bundle is not None else None

    @jax.jit
    def contrib(bins_v, feat, bthr, lc, rc, il, vals, catn, catm):
        def per_class(f_, b_, l_, r_, i_, v_, cn_, cm_):
            leaf = _route_binned(
                bins_v, f_, b_, l_, r_, i_, steps, cat_node=cn_, cat_mask=cm_,
                bundle_consts=consts,
            )
            return v_[leaf]

        return jax.vmap(per_class, out_axes=1)(feat, bthr, lc, rc, il, vals, catn, catm)

    return contrib


def _make_valid_update(steps: int, bundle=None):
    contrib = _make_tree_contrib(steps, bundle)

    def update(bins_v, margins_v, tree):
        return margins_v + contrib(
            bins_v, tree.feat, tree.bin, tree.left, tree.right, tree.is_leaf,
            tree.leaf_val, tree.cat_node, tree.cat_mask,
        )

    return jax.jit(update, donate_argnums=(1,))


def _margin_to_score(margins: np.ndarray, metric: str, objective: str) -> np.ndarray:
    """What the metric consumes: margins for loss metrics, margin column 0
    for auc (rank-invariant), response scale for poisson/tweedie l2."""
    if metric in ("multi_logloss", "multi_error"):
        return margins
    if objective in ("poisson", "tweedie") and metric in ("l2", "rmse", "l1"):
        return np.exp(margins[:, 0])
    return margins[:, 0]


def _evaluate(
    metric: str, objective: str, y: np.ndarray, margins: np.ndarray, w: np.ndarray,
    alpha: float,
) -> float:
    fn, _ = METRICS[metric]
    score = _margin_to_score(margins, metric, objective)
    if metric == "quantile":
        return fn(y, score, w, alpha=alpha)
    return fn(y, score, w)


# ---------------------------------------------------------------------------
# The fit's driver: train() calls one function a span.
# ---------------------------------------------------------------------------


def _check_contracts(opts: TrainOptions, valid_sets=None) -> None:
    """Boosting-type and bagging contracts, with native LightGBM's own errors."""
    if opts.boosting_type == "rf":
        if not (opts.bagging_fraction < 1.0 and opts.bagging_freq > 0):
            raise ValueError(
                "boosting_type='rf' requires bagging "
                "(bagging_fraction < 1 and bagging_freq > 0)"
            )
        if valid_sets:
            raise ValueError(
                "boosting_type='rf' does not support validation sets "
                "(averaged-ensemble eval is not incremental)"
            )
    elif opts.boosting_type == "goss":
        if opts.bagging_fraction < 1.0:
            raise ValueError("boosting_type='goss' cannot be combined with bagging")
        if opts.top_rate + opts.other_rate > 1.0:
            raise ValueError(
                "goss requires top_rate + other_rate <= 1 "
                f"(got {opts.top_rate} + {opts.other_rate})"
            )
    elif opts.boosting_type == "dart" and opts.early_stopping_round > 0:
        raise ValueError("early stopping is not available in dart mode")
    if (
        opts.pos_bagging_fraction < 1.0 or opts.neg_bagging_fraction < 1.0
    ) and opts.objective != "binary":
        # native LightGBM likewise restricts pos/neg bagging to binary
        raise ValueError(
            "posBaggingFraction/negBaggingFraction require the binary "
            f"objective (got {opts.objective!r})"
        )


def _with_mapper_slots(opts: TrainOptions, mapper: Optional[BinMapper]) -> TrainOptions:
    """The mapper is the single source of truth for categorical features
    (LightGBMBase.scala:148-156 likewise resolves slots before training)."""
    if mapper is None or not mapper.cat_values:
        return opts
    return dataclasses.replace(
        opts,
        categorical_slots=tuple(sorted(mapper.cat_values)),
        # native max_cat_to_onehot boundary: features whose SEEN category
        # count is small use the one-vs-rest search instead of the sort
        onehot_slots=tuple(
            f_
            for f_ in sorted(mapper.cat_values)
            if len(mapper.cat_values[f_]) <= opts.max_cat_to_onehot
        ),
    )


class _Prep(NamedTuple):
    """A fit's host-side set-up, shared by :func:`train` and :func:`train_many`."""

    opts: TrainOptions  # categorical slots resolved; rf's trees full-strength
    objective: Objective
    num_classes: int
    num_bins: int  # max_bin + the missing bin
    bundle: Any  # the mapper's EFB plan, or None
    mapper: Optional[BinMapper]
    bins: Any  # (N, f) as the caller gave them
    n: int
    f: int  # columns of ``bins`` (packed under EFB)
    f_feat: int  # original features: what the feature masks cover
    y: np.ndarray  # (N,) float32
    w: np.ndarray  # (N,) float32
    unit_w: bool  # the caller gave no weights: they are made on the device
    init_score: np.ndarray  # (C,)
    margins0: Optional[np.ndarray]  # (N, C) warm start; None: init_score


def _prepare(bins, y, w, init_margins, opts: TrainOptions, mapper) -> _Prep:
    """Objective, classes, bundle, categorical slots, init score, margins."""
    if opts.boosting_type == "rf":
        # rf trees are full-strength; averaging happens at the end
        opts = dataclasses.replace(opts, learning_rate=1.0)
    objective = get_objective(opts.objective)
    num_classes = objective.num_outputs_fn(opts.num_class)
    n, f = bins.shape
    # EFB: when the mapper carries a bundle plan, ``bins`` is the PACKED
    # (N, C) matrix. Histograms build in packed space and expand to the
    # original (k, F, B, 3) before the split search, so everything from the
    # search down (tree arrays, model text, SHAP) stays in original ids;
    # f_feat sizes the original-feature surfaces (feature_fraction masks).
    bundle = getattr(mapper, "bundles", None) if mapper is not None else None
    if bundle is not None:
        if f != bundle.num_columns:
            raise ValueError(
                f"bundled mapper expects packed bins with {bundle.num_columns} "
                f"columns, got {f} — bin through apply_bins/bin_dataset with "
                "this mapper"
            )
        if opts.tree_learner == "voting_parallel":
            raise ValueError(
                "featureBundling is not supported with tree_learner="
                "'voting_parallel' (voting's top-K feature exchange needs "
                "per-feature histograms on the wire)"
            )
    opts = _with_mapper_slots(opts, mapper)
    unit_w = w is None
    w = np.ones(n, dtype=np.float32) if w is None else np.asarray(w, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    margins0 = None
    if init_margins is not None:
        # Warm start from provided margins: the booster is a delta model
        # (LightGBM disables boost_from_average when init_score is given).
        init_score = np.zeros(num_classes, dtype=np.float32)
        margins0 = np.asarray(init_margins, dtype=np.float32).reshape(n, num_classes)
    elif opts.boost_from_average:
        init_score = objective.init_score(y, num_classes, w)
    else:
        init_score = np.zeros(num_classes, dtype=np.float32)
    return _Prep(
        opts, objective, num_classes, opts.max_bin + 1, bundle, mapper, bins, n, f,
        bundle.num_features if bundle is not None else f, y, w, unit_w, init_score,
        margins0,
    )


class _Placed(NamedTuple):
    """A fit's arrays on the device, rows padded to the mesh's data axis."""

    bins: Any
    y: Any
    w: Any
    margins: Any
    edges: Any
    pad: int
    rows: int  # n + pad
    put_rows: Callable  # host array -> device, row-sharded on a mesh
    put_rep: Callable  # host array -> device, replicated on a mesh
    dev_rows: Callable  # device array -> the row sharding (no wire traffic)
    bytes: int  # host bytes handed to the device


def _place(prep: _Prep, mesh=None) -> _Placed:
    """Padding, sharding, uint8 bins and labels: the host's share of
    placement. Rows are padded to a multiple of the data-axis size; padding
    rides along with zero weight/count so it never influences histograms or
    stats — the "empty partition sends ignore" analogue
    (LightGBMUtils.scala:144-161)."""
    n, f = prep.n, prep.f
    bins, y, w, margins0 = prep.bins, prep.y, prep.w, prep.margins0
    pad = 0
    sh_bins = None
    up_bytes = 0

    def host(a):
        """A host array on its way to the device, counted for the tag."""
        nonlocal up_bytes
        up_bytes += a.nbytes
        return a

    if mesh is not None:
        _, pad = pad_to_multiple(n, int(mesh.shape["data"]))
        if pad:
            bins = np.concatenate([bins, np.zeros((pad, f), dtype=bins.dtype)])
            y = np.concatenate([y, np.zeros(pad, dtype=np.float32)])
            w = np.concatenate([w, np.zeros(pad, dtype=np.float32)])
            if margins0 is not None:
                margins0 = np.concatenate(
                    [margins0, np.zeros((pad, prep.num_classes), dtype=margins0.dtype)]
                )
        sh_rows = data_sharding(mesh)
        sh_rep = replicated(mesh)
        model_size = int(mesh.shape.get(AXIS_MODEL, 1))
        if model_size > 1 and f % model_size == 0 and prep.bundle is None:
            # feature parallel: bins vertically partitioned over the model
            # axis (LightGBM's feature_parallel layout); XLA partitions the
            # histogram build/split search and inserts the best-split
            # argmax collectives across model shards itself. (Indivisible
            # feature counts stay row-sharded/replicated over model.)
            sh_bins = feature_parallel_sharding(mesh)
        put_rows = dev_rows = lambda a: jax.device_put(a, sh_rows)
        put_rep = lambda a: jax.device_put(a, sh_rep)
    else:
        put_rows = put_rep = jnp.asarray
        dev_rows = lambda a: a
    rows = n + pad

    if prep.mapper is not None:
        edges = np.where(
            np.isfinite(prep.mapper.edges), prep.mapper.edges,
            np.float32(np.finfo(np.float32).max),
        )
    else:
        edges = np.zeros((f, 1))
    edges_dev = put_rep(host(edges.astype(np.float32)))

    # Ship bins as uint8 when they fit (4x less host->device traffic);
    # consumers compare/gather fine on uint8 and the histogram kernels
    # upcast per-tile. Device-RESIDENT bins (bin_dataset_to_device's
    # overlapped streaming upload) skip the put entirely.
    put_bins = (lambda a: jax.device_put(a, sh_bins)) if sh_bins is not None else put_rows
    if isinstance(bins, jax.Array) and mesh is None:
        bins_dev = bins
    elif prep.num_bins <= 256:
        # uint8 inputs (incl. out-of-core memmaps) upload as-is — no host
        # copy; device_put streams straight from the mapping
        b8 = np.asarray(bins) if not isinstance(bins, np.ndarray) else bins
        b8 = b8 if b8.dtype == np.uint8 else b8.astype(np.uint8)
        bins_dev = put_bins(host(np.ascontiguousarray(b8)))
    else:
        bins_dev = put_bins(host(np.asarray(bins, dtype=np.int32)))
    # Integer-valued labels (binary/multiclass/count targets) ride the wire
    # as uint8 and upcast on device — 4x less of the per-fit transfer cost.
    if y.size and np.all(np.mod(y, 1) == 0) and np.all((y >= 0) & (y <= 255)):
        y_dev = put_rows(host(y.astype(np.uint8))).astype(jnp.float32)
    else:
        y_dev = put_rows(host(y))
    # Constant-valued operands are created ON device instead of uploaded.
    w_dev = dev_rows(jnp.ones(rows, jnp.float32)) if prep.unit_w else put_rows(host(w))
    if margins0 is None:
        margins = dev_rows(
            jnp.asarray(prep.init_score, dtype=jnp.float32)[None, :]
            * jnp.ones((rows, 1), jnp.float32)
        )
    else:
        margins = put_rows(host(margins0.astype(np.float32)))
    return _Placed(
        bins_dev, y_dev, w_dev, margins, edges_dev, pad, rows, put_rows, put_rep,
        dev_rows, up_bytes,
    )


class _Subtraction(NamedTuple):
    """Leaf-wise sibling subtraction's histogram cache on static shapes."""

    on: bool
    acc_dtype: Any  # dtype of the packed accumulator
    cache_bytes: int
    cols: int  # packed columns
    bins: int  # packed bins


def _subtraction(opts: TrainOptions, rows: int, cols: int, bins: int, quant: bool) -> _Subtraction:
    """Whether the leaf-wise grower subtracts siblings. The cache holds every
    node's packed histogram (packed columns x packed bins x 3 stats in the
    accumulator's dtype) once a class, since the boosting step vmaps the
    grower over classes, and must fit 256 MiB. Voting's histograms carry only
    the top-K winner features, so parent - smaller is garbage there.
    :func:`_u_plan` and :func:`_build_tree_leafwise` both ask this."""
    dt = jnp.dtype(histogram_acc_dtype(rows, quant))
    cache = max(1, opts.num_class) * (2 * opts.num_leaves - 1) * cols * bins * 3 * dt.itemsize
    on = (
        opts.histogram_subtraction
        and cache <= (256 << 20)
        and opts.tree_learner != "voting_parallel"
    )
    return _Subtraction(bool(on), dt, cache, cols, bins)


# Two ceilings on quantized gradients, the tighter (2^24) enforced: s8 x s8
# sums accumulate in int32 (|sum| <= 127 * rows wraps past 2^31/127 ~= 16.9M
# rows), and the f32 count channel loses integer exactness above 2^24 — the
# "counts stay exact" contract in _split_search holds only below it.
_QUANT_ROW_CAP = min((1 << 31) // 127, 1 << 24)


class _UPlan(NamedTuple):
    """The U histogram path as one fit takes it."""

    spec: Any  # USpec (chunk_rows > 0: streamed in row chunks); None: no U path
    budget: int  # U's HBM budget in force (0 without U); the OOM ladder halves it
    quant: bool  # quantized gradients in force
    quant_off: Optional[str]  # why a quantized request trains on exact stats
    sub: _Subtraction


def _u_plan(opts: TrainOptions, rows: int, cols: int, num_bins: int, bundle=None,
            mapper=None, mesh=None, budget: Optional[int] = None) -> _UPlan:
    """The one decision of the U histogram path (ops/u_histogram.py).

    Single-device fits contract each pass against a fit-resident U instead
    of rebuilding the one-hot (measured 2.1x/pass on v5e); a U over the HBM
    budget is streamed in row chunks. ``histogram_method='u'`` forces the
    path (tests exercise it on CPU); ``MMLSPARK_TPU_NO_U=1`` kills it and
    ``MMLSPARK_TPU_U_BUDGET`` resizes the budget, both read here. Quantized
    gradients need U and at most ``_QUANT_ROW_CAP`` rows, and sibling
    subtraction depends on the quantization in force, so both are decided
    here too. ``budget`` given: a rung of the OOM ladder, on a path already
    on: the same U, chunked to that budget."""
    spec = None
    if budget is not None or (
        mesh is None
        and opts.tree_learner != "voting_parallel"
        and num_bins <= 256
        and os.environ.get("MMLSPARK_TPU_NO_U") != "1"
        and (
            opts.histogram_method == "u"
            or (opts.histogram_method in (None, "pallas") and on_tpu())
        )
    ):
        if bundle is not None:
            # U laid out over the PACKED columns — K = Σ bundle widths is
            # the whole point: fewer one-hot rows to re-stream per pass.
            spec = make_u_spec(bundle.num_bins, cols, [int(wd) for wd in bundle.widths])
        else:
            per_feature = None if mapper is None else [int(x) for x in mapper.num_bins]
            spec = make_u_spec(num_bins, cols, per_feature)
        if budget is not None:
            spec = chunked_u_spec(rows, spec, budget)
        else:
            try:
                budget = int(os.environ.get("MMLSPARK_TPU_U_BUDGET", str(8 << 30)))
            except ValueError:
                get_logger("mmlspark_tpu.lightgbm").warning(
                    "MMLSPARK_TPU_U_BUDGET=%r is not an integer byte count; "
                    "using the default 8 GB budget",
                    os.environ["MMLSPARK_TPU_U_BUDGET"],
                )
                budget = 8 << 30
            if u_bytes(rows, spec) > budget:
                # Over budget: stream the pass in row chunks instead of
                # abandoning the MXU path wholesale (the pre-chunking behavior
                # was an all-or-nothing cliff: one row past the budget and the
                # whole fit fell back to the compare-built kernels).
                spec = chunked_u_spec(rows, spec, budget)
    quant_off = None
    if opts.use_quantized_grad and spec is None:
        quant_off = (
            "the precomputed-U histogram path is inactive (non-TPU "
            "backend without histogram_method='u', mesh/voting "
            "parallelism, num_bins > 256, or U over the HBM budget)"
        )
    elif opts.use_quantized_grad and rows > _QUANT_ROW_CAP:
        quant_off = (
            f"{rows} rows exceeds the quantized-path cap "
            "min(2^31/127, 2^24) = 2^24 (f32 count exactness / int32 "
            "histogram accumulator)"
        )
    quant = bool(opts.use_quantized_grad) and quant_off is None
    packed_bins = bundle.num_bins if bundle is not None else num_bins
    return _UPlan(
        spec, budget if spec is not None else 0, quant, quant_off,
        _subtraction(opts, rows, cols, packed_bins, quant),
    )


def _publish_plan(plan: _UPlan, opts: TrainOptions, rows: int) -> None:
    """The plan's log lines and events, once a fit."""
    log = get_logger("mmlspark_tpu.lightgbm")
    bus = get_bus()
    spec, sub = plan.spec, plan.sub
    if spec is not None and spec.chunk_rows:
        chunks = num_u_chunks(rows, spec)
        log.info(
            "U one-hot (%.1f GB) exceeds MMLSPARK_TPU_U_BUDGET (%.1f GB);"
            " streaming each histogram pass in %d row chunks of %d",
            u_bytes(rows, dataclasses.replace(spec, chunk_rows=0)) / 1e9,
            plan.budget / 1e9, chunks, spec.chunk_rows,
        )
        if bus.active:
            k3 = 3 * max(1, min(opts.leaf_batch, opts.num_leaves - 1))
            bus.publish(HistogramChunked(
                rows=rows, k_packed=spec.k_pad, chunk_rows=spec.chunk_rows,
                num_chunks=chunks, budget_bytes=plan.budget,
                acc_dtype=sub.acc_dtype.name,
                bytes_saved=spec.k_pad * k3 * (4 - sub.acc_dtype.itemsize),
            ))
    if plan.quant_off is not None:
        log.warning(
            "use_quantized_grad requested but %s; training with exact "
            "bf16 stats instead", plan.quant_off,
        )
    if plan.quant and opts.growth == "depthwise" and opts.depth >= 7:
        # The U panel packs 3 stat planes per frontier node into 128
        # slots, so levels with > 42 nodes (2^6 = 64 at level 6, reached
        # once depth >= 7) can't ride the quantized U kernel; _hist_fn
        # drops those levels to the exact histogram path.
        log.warning(
            "use_quantized_grad with depthwise growth and depth %d: levels "
            "deeper than 5 have > 42 frontier nodes and exceed the 128-slot "
            "U panel budget (3 stats x nodes), so those levels fall back to "
            "exact (non-quantized) histograms per level",
            opts.depth,
        )
    if opts.growth == "leafwise" and sub.on and bus.active:
        bus.publish(HistogramSubtracted(
            rows=rows, num_leaves=opts.num_leaves,
            packed_columns=sub.cols, packed_bins=sub.bins,
            acc_dtype=sub.acc_dtype.name, cache_bytes=sub.cache_bytes,
            bytes_saved_per_tree=(opts.num_leaves - 1) * sub.cols
            * sub.bins * 3 * sub.acc_dtype.itemsize,
        ))


class _Programs(NamedTuple):
    """A fit's programs, under the keys the process-wide cache holds them by."""

    step_raw: Any  # one boosting iteration, unjitted: the scan bodies trace it
    step: Any  # ``step_raw`` jitted, margins donated: the loop path's dispatch
    runner: Any  # every iteration in one scan, or None on the loop path
    u_builder: Any  # lays out U (or the chunked bins stack), or None
    cache_hit: bool  # the step program came from an earlier fit's build


def _programs(opts: TrainOptions, objective: Objective, num_bins: int, n: int,
              bundle=None, spec=None, mesh=None, hist_reduce=None, scan=None,
              many: bool = False) -> _Programs:
    """The step, its jitted dispatch, the scan runner and U's builder. The
    first build and every rung of the OOM ladder call this.

    ``scan``: :func:`train`'s ``(per_iter_bag, per_iter_lr)``, or with
    ``many`` :func:`train_many`'s ``per_iter_bag``; None builds no runner.
    A ``hist_reduce`` hook closes over a live socket group, so a program
    holding it is never shared across fits. The dispatches book through
    :meth:`DeviceProfiler.wrap`, which hands them back unwrapped when the
    profiler is off."""
    okey = (_opts_key(opts), num_bins, mesh, spec, bundle, objective.cache_token)
    if opts.boosting_type == "goss":
        okey = okey + (n,)  # GOSS bakes the unpadded row count into the program

    def make(hook=None):
        return _make_step(
            opts, objective, num_bins, mesh, n_real=n, u_spec=spec,
            hist_reduce=hook, bundle=bundle,
        )

    prof = get_profiler()
    if many:
        step_raw = cached_program(("step_raw_many", okey), make)
        runner = cached_program(
            ("scan_many", okey, scan),
            lambda: _scan_program(step_raw, per_iter_bag=scan, many=True),
        )
        return _Programs(
            step_raw, None, prof.wrap(runner, "gbdt.scan_many", cost_analysis=False),
            None, False,
        )
    built_before = programs_built()
    if hist_reduce is not None:
        step_raw = make(hist_reduce)
        step = jax.jit(step_raw, donate_argnums=(3,))
    else:
        step_raw = cached_program(("step_raw", okey), make)
        step = cached_program(
            ("step_jit", okey), lambda: jax.jit(step_raw, donate_argnums=(3,))
        )
    cache_hit = hist_reduce is None and programs_built() == built_before
    runner = None
    if scan is not None:
        per_iter_bag, per_iter_lr = scan
        runner = cached_program(
            ("scan", okey, per_iter_bag, per_iter_lr),
            lambda: _scan_program(
                step_raw, per_iter_bag=per_iter_bag, per_iter_lr=per_iter_lr,
                with_u=spec is not None,
            ),
        )
        runner = prof.wrap(runner, "gbdt.scan", cost_analysis=False)
    u_builder = None
    if spec is not None:
        # the chunked pass consumes a (num_chunks, F, chunk) bins stack laid
        # out once per fit, not the resident one-hot
        u_builder = partial(prepare_chunked_bins if spec.chunk_rows else build_u, spec=spec)
    return _Programs(
        step_raw, prof.wrap(step, "gbdt.step", cost_analysis=False), runner,
        u_builder, cache_hit,
    )


_OOM_RETRY_CAP = 8  # ladder rungs one dispatch may walk before its OOM is final


@dataclasses.dataclass
class _Run:
    """One fit's device side: placed inputs, the U plan, its programs and U.

    The RESOURCE_EXHAUSTED degradation ladder (docs/resilience.md) replaces
    the last three together. An HBM OOM during a histogram dispatch is
    retryable at a reduced footprint: halve U's budget (floor 1 MiB), chunk
    U to it, rebuild the programs and U, and run the SAME dispatch again.
    Chunked and resident passes are bit-exact, so the final model text
    matches an undisturbed run byte for byte. The last rung — a smaller
    ``leaf_batch`` — changes split-scheduling and is left to the caller (it
    trades reproducibility for survival)."""

    opts: TrainOptions
    prep: _Prep
    data: _Placed
    plan: _UPlan
    mesh: Any = None
    hist_reduce: Any = None
    scan: Any = None  # what _programs builds the runner for
    faults: Any = None  # the active fault plan: injected OOMs fire pre-dispatch
    progs: Optional[_Programs] = None
    u: Any = None

    def build_programs(self) -> None:
        p = self.prep
        self.progs = _programs(
            self.opts, p.objective, p.num_bins, p.n, p.bundle, self.plan.spec,
            self.mesh, self.hist_reduce, self.scan,
        )

    def build_u(self) -> None:
        """The fit-resident U (or the chunked bins stack): one dispatch,
        again only after an OOM degraded the plan. The build's device time
        is waited for by whatever reads its result: ``lightgbm.boost``."""
        spec, rows = self.plan.spec, self.data.rows
        chunks = num_u_chunks(rows, spec)
        u_jit = cached_program(("u_build_jit", spec), lambda: jax.jit(self.progs.u_builder))
        with get_tracer().span(
            "lightgbm.u_build", chunks=chunks,
            u_bytes=chunks * spec.chunk_rows * self.prep.f if spec.chunk_rows
            else u_bytes(rows, spec),
        ):
            self.u = u_jit(self.data.bins)

    def dispatch(self, stage: str, iteration: int, attempt: Callable[[int], Any]):
        """``attempt(retries)`` under the active fault plan; a device OOM
        walks one rung down the ladder and tries the same dispatch again."""
        retries = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.apply_on_histogram(iteration, retries)
                return attempt(retries)
            except Exception as e:  # noqa: BLE001 - OOM-classified below
                if (
                    not is_oom_error(e)
                    or retries >= _OOM_RETRY_CAP
                    or not self._degrade(e, stage, iteration, retries + 1)
                ):
                    raise
                retries += 1
                self.build_u()

    def _degrade(self, err, stage: str, iteration: int, retries: int) -> bool:
        """Walk one rung down the ladder; True when the caller may retry."""
        plan, p, rows = self.plan, self.prep, self.data.rows
        if plan.spec is None:
            return False  # no U path active: nothing to shrink in-loop
        budget = max(plan.budget // 2, 1 << 20)
        if budget == plan.budget and plan.spec.chunk_rows:
            return False  # floor reached; the OOM is genuine scarcity
        self.plan = _u_plan(
            self.opts, rows, p.f, p.num_bins, p.bundle, p.mapper, budget=budget
        )
        self.build_programs()
        chunk_rows = self.plan.spec.chunk_rows
        get_logger("mmlspark_tpu.lightgbm").warning(
            "histogram %s dispatch hit RESOURCE_EXHAUSTED at iteration %d "
            "(%s); degrading: U budget -> %d bytes, chunk_rows -> %d, "
            "retry %d",
            stage, iteration, str(err)[:120], budget, chunk_rows, retries,
        )
        bus = get_bus()
        if bus.active:
            bus.publish(MemoryPressure(
                source="device", level="critical", used_bytes=0.0,
                limit_bytes=0.0, detail=str(err)[:200],
            ))
            bus.publish(HistogramDegraded(
                rows=rows, budget_bytes=budget, chunk_rows=chunk_rows,
                stage=stage, iteration=int(iteration), retries=int(retries),
            ))
        return True


@dataclasses.dataclass
class _Watch:
    """What a fit reports between trees and stops on: callbacks, validation
    sets and their metric history, the training metric, early stopping.
    The scan path runs only the callbacks' start and end."""

    opts: TrainOptions
    prep: _Prep
    callbacks: list
    lr_all: Optional[np.ndarray]  # (iterations,) schedule; None: constant
    metric: str
    higher_better: bool
    valid: List[dict]
    evals: Dict[str, Dict[str, List[float]]]
    update: Any  # the validation sets' margin update program
    best_score: float
    best_iter: int = 0
    stale: int = 0

    @classmethod
    def start(cls, opts, prep: _Prep, valid_sets, callbacks, lr_all) -> "_Watch":
        valid = []
        for name, bv, yv, wv in valid_sets:
            wv = np.ones(len(yv), dtype=np.float32) if wv is None else np.asarray(wv, np.float32)
            mv = np.broadcast_to(prep.init_score[None, :], (len(yv), prep.num_classes)).copy()
            valid.append({
                "name": name,
                "bins": jnp.asarray(np.asarray(bv, dtype=np.int32)),
                "y": np.asarray(yv, dtype=np.float32),
                "w": wv,
                "margins": jnp.asarray(mv.astype(np.float32)),
            })
        metric = opts.metric or prep.objective.default_metric
        higher_better = metric_higher_is_better(metric)
        evals = {vs["name"]: {metric: []} for vs in valid}
        if opts.provide_training_metric:
            evals["training"] = {metric: []}
        update = cached_program(
            ("valid_update", opts.routing_steps, prep.bundle),
            lambda: _make_valid_update(opts.routing_steps, prep.bundle),
        )
        watch = cls(
            opts, prep, callbacks, lr_all, metric, higher_better, valid, evals,
            update, -np.inf if higher_better else np.inf,
        )
        for cb in callbacks:
            cb.before_training(watch.env(0))
        return watch

    @property
    def best_iteration(self) -> int:
        """The booster's: -1 unless early stopping watched a validation set."""
        return self.best_iter if (self.valid and self.opts.early_stopping_round > 0) else -1

    def env(self, it: int) -> CallbackEnv:
        lr = self.lr_all
        return CallbackEnv(
            iteration=it, num_iterations=self.opts.num_iterations,
            learning_rate=float(lr[it]) if (lr is not None and it < len(lr))
            else self.opts.learning_rate,
            evals=self.evals,
        )

    def after(self, it: int, tree: TreeArrays, margins) -> bool:
        """Record iteration ``it``; True when a callback or early stopping
        ends the fit."""
        opts, p, metric = self.opts, self.prep, self.metric
        if opts.provide_training_metric:
            # isProvideTrainingMetric: train-set metric per iteration (a
            # device fetch per round — opt-in, loop path only)
            self.evals["training"][metric].append(_evaluate(
                metric, opts.objective, p.y, np.asarray(margins)[:p.n], p.w, opts.alpha,
            ))
        improved = False
        for vs in self.valid:
            if not vs.pop("_updated", False):  # dart applies its own delta
                vs["margins"] = self.update(vs["bins"], vs["margins"], tree)
            score = _evaluate(
                metric, opts.objective, vs["y"], np.asarray(vs["margins"]), vs["w"],
                opts.alpha,
            )
            self.evals[vs["name"]][metric].append(score)
            # best-so-far from the true score (TrainUtils.scala:276-315);
            # the first finite eval improves on the ±inf sentinel
            # naturally, and a NaN score never registers as an improvement.
            delta = (score - self.best_score) if self.higher_better else (self.best_score - score)
            if delta > opts.improvement_tolerance:
                self.best_score, self.best_iter, improved = score, it + 1, True
        if any([cb.after_iteration(self.env(it)) for cb in self.callbacks]):
            return True  # every callback still sees the iteration
        if self.valid and opts.early_stopping_round > 0:
            self.stale = 0 if improved else self.stale + 1
            return self.stale >= opts.early_stopping_round
        return False

    def finish(self, iterations: int) -> None:
        for cb in self.callbacks:
            cb.after_training(self.env(max(0, iterations - 1)))
        if self.opts.verbosity < 1:
            return
        logger = get_logger("mmlspark_tpu.lightgbm")
        # verbosity is an explicit request for output — lift the level floor
        # for THIS summary only, restoring the configured level after
        root_logger = logging.getLogger("mmlspark_tpu")
        prev_level = root_logger.level
        if root_logger.getEffectiveLevel() > logging.INFO:
            root_logger.setLevel(logging.INFO)
        try:
            for name, metrics in self.evals.items():
                for mname, scores in metrics.items():
                    if not scores:
                        continue
                    arr = np.asarray(scores, dtype=np.float64)
                    if np.isnan(arr).all():
                        logger.info("valid %s %s: all evals NaN", name, mname)
                        continue
                    best_i = int(
                        np.nanargmax(arr) if self.higher_better else np.nanargmin(arr)
                    )
                    logger.info(
                        "valid %s %s: last=%.6f best=%.6f@%d",
                        name, mname, scores[-1], arr[best_i], best_i + 1,
                    )
        finally:
            root_logger.setLevel(prev_level)


def _mask_stacks(schedule, f: int) -> Tuple[List[np.ndarray], np.ndarray]:
    """Every iteration's masks up front: the bagging masks as drawn, and the
    (iterations, F) feature masks."""
    bags, fms = [], []
    for bag, _, fm in schedule:
        bags.append(bag)
        fms.append(fm if fm is not None else np.ones(f, np.float32))
    return bags, np.stack(fms)


def _scan_inputs(schedule, f: int, bagging: bool, presence, lr_all):
    """The scan's per-iteration operands: (iterations, N) bagging masks
    (uint8 on the wire, 4x less than f32; cast per scan step) or, when
    bagging never resamples, the (N,) presence mask closed over in the
    program; the feature masks; and the learning-rate schedule (the feature
    masks stand in, unused, when the rate is constant)."""
    bags, fms = _mask_stacks(schedule, f)
    bag_arg = jnp.asarray(np.stack(bags).astype(np.uint8)) if bagging else presence
    fm_all = jnp.asarray(fms)
    return bag_arg, fm_all, jnp.asarray(lr_all) if lr_all is not None else fm_all


def _boost_scan(run: _Run, inputs, span) -> TreeArrays:
    """Every iteration in one device program a segment: one dispatch and one
    bulk fetch replace per-iteration host round-trips. Returns the stacked
    trees."""
    opts, d = run.opts, run.data
    bag_arg, fm_all, lr_arg = inputs
    per_iter_bag, per_iter_lr = run.scan
    iters = opts.num_iterations
    # Segment the one-dispatch fit so no single device program runs for
    # minutes. Observed on an earlier v5e host: a 4M-row x 100-iteration
    # scan (~90 s on-device) reproducibly killed the TPU worker, while
    # 4M x 50 and 2M x 100 (~50 s) ran fine. Whether the current machine
    # still needs this is an open ROADMAP question; the bound is
    # MMLSPARK_TPU_SCAN_ROW_ITERS. Equal-length segments share one compiled
    # program; margins thread between dispatches, so results are identical
    # to the single scan.
    row_iters = run.prep.n * max(1, iters) * max(1, run.prep.num_classes)
    budget = int(os.environ.get("MMLSPARK_TPU_SCAN_ROW_ITERS", 200_000_000))
    nseg = max(1, -(-row_iters // budget))
    # prefer a divisor of the iteration count close to nseg: equal segment
    # lengths mean ONE compiled shape instead of two
    for cand in range(nseg, min(nseg + 3, max(1, iters)) + 1):
        if iters % cand == 0:
            nseg = cand
            break
    seg = -(-iters // nseg)
    span.tags["segments"] = nseg
    no_u = jnp.int32(0)  # unused placeholder when no U path
    margins, parts = d.margins, []
    for s0 in range(0, iters, seg):
        s1 = min(s0 + seg, iters)
        # margins is donated into the runner; a degraded retry of this
        # segment needs the pre-dispatch value back, so keep a host snapshot
        # (segments are rare — usually one per fit)
        before = np.asarray(margins)

        def attempt(retries, s0=s0, s1=s1, m=margins, before=before):
            return run.progs.runner(
                d.bins, d.y, d.w, jnp.asarray(before) if retries else m, d.edges,
                bag_arg[s0:s1] if per_iter_bag else bag_arg,
                fm_all[s0:s1],
                lr_arg[s0:s1] if per_iter_lr else lr_arg,
                jnp.int32(s0),
                no_u if run.u is None else run.u,
            )

        margins, part = run.dispatch("scan", s0, attempt)
        parts.append(part)
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


def _boost_loop(run: _Run, watch: _Watch, schedule, bag_dev, iteration_hook=None,
                start_iteration: int = 0) -> List[TreeArrays]:
    """A dispatch an iteration, for what needs the host between trees: dart,
    callbacks, validation sets and early stopping, the mesh, process fits'
    journal hook. Inputs are re-uploaded only when bagging or feature
    fraction resamples, and per-tree outputs stay on device until one bulk
    fetch after the loop."""
    opts, d, bundle = run.opts, run.data, run.prep.bundle
    tree_contrib = cached_program(
        ("tree_contrib", opts.routing_steps, bundle),
        lambda: _make_tree_contrib(opts.routing_steps, bundle),
    )
    dart_rng = np.random.default_rng(opts.seed + 7919)
    fm_ones_dev = d.put_rep(np.ones(run.prep.f_feat, dtype=np.float32))
    margins, trees, pending_bag = d.margins, [], None

    def contrib_of(tr, bins_v):
        return tree_contrib(
            bins_v, tr.feat, tr.bin, tr.left, tr.right, tr.is_leaf, tr.leaf_val,
            tr.cat_node, tr.cat_mask,
        )

    def dropped_contrib(dropped, bins_v):
        c = contrib_of(trees[dropped[0]], bins_v)
        for di in dropped[1:]:
            c = c + contrib_of(trees[di], bins_v)
        return c

    for it, (bag_np, bag_changed, fm_np) in enumerate(schedule):
        if it < start_iteration:
            # journal resume: consume the draw (rng stream stays aligned
            # with an uninterrupted fit) without boosting
            if bag_changed:
                pending_bag = bag_np
            continue
        if pending_bag is not None:
            # the last skipped resample is the mask in force at k
            if not bag_changed:
                bag_np, bag_changed = pending_bag, True
            pending_bag = None
        if bag_changed:
            bag_dev = d.put_rows(bag_np)
        fm_dev = d.put_rep(fm_np) if fm_np is not None else fm_ones_dev
        for cb in watch.callbacks:
            cb.before_iteration(watch.env(it))
        # traced scalar (not a baked constant) so per-iteration LR values
        # don't each recompile the step program
        lr_it = jnp.float32(
            watch.lr_all[it] if watch.lr_all is not None else opts.learning_rate
        )

        # dart: drop a random subset of existing trees from the margins the
        # new tree fits against (each with prob drop_rate), then renormalize
        # — new tree x 1/(k+1), dropped trees x k/(k+1) (the DART
        # weight-shrinkage rule).
        dropped = []
        if opts.boosting_type == "dart" and trees:
            dropped = list(np.nonzero(dart_rng.random(len(trees)) < opts.drop_rate)[0])
        if dropped:
            c_d = dropped_contrib(dropped, d.bins)
            margins_in = margins - c_d
        else:
            margins_in = margins

        # Injected OOM faults fire BEFORE dispatch, so margins_in has not
        # been donated when the degraded retry re-dispatches. A real device
        # OOM surfaces after donation; the retry is then best-effort (the
        # allocator usually fails before consuming the donated buffer, but
        # that is not contractual).
        tree, new_margins = run.dispatch("loop", it, lambda _: run.progs.step(
            d.bins, d.y, d.w, margins_in, d.edges, bag_dev, fm_dev,
            jnp.int32(it), lr_it, u=run.u,
        ))

        if dropped:
            k = len(dropped)
            scale_new = 1.0 / (k + 1)
            scale_drop = k / (k + 1)
            # margins_in was donated into step — recover the unscaled
            # new-tree contribution from the row->leaf map it computed
            c_new = jnp.take_along_axis(tree.leaf_val, tree.row_leaf, axis=1).T
            # valid-set deltas need the PRE-scaled dropped trees
            for vs in watch.valid:
                c_dv = dropped_contrib(dropped, vs["bins"])
                c_newv = contrib_of(tree, vs["bins"])
                vs["margins"] = vs["margins"] - c_dv * scale_new + c_newv * scale_new
                vs["_updated"] = True
            tree = tree._replace(leaf_val=tree.leaf_val * scale_new)
            for di in dropped:
                trees[di] = trees[di]._replace(leaf_val=trees[di].leaf_val * scale_drop)
            margins = margins - c_d * scale_new + c_new * scale_new
        else:
            margins = new_margins
        # Synchronize each iteration on the mesh path: an unbounded async
        # queue of collective programs can starve a device thread past the
        # XLA rendezvous timeout (hard abort on the host-platform mesh),
        # and per-iteration sync is the barrier-execution-mode semantics
        # of the reference anyway (TrainUtils.scala:477-483).
        jax.block_until_ready(margins)
        # drop row_leaf, a (C, N) buffer per tree, before retaining
        trees.append(tree._replace(row_leaf=None))
        if iteration_hook is not None:
            # the commit point: the iteration's tree is final and its
            # margins applied — procfit journals it here
            iteration_hook(it, trees[-1])
        if watch.after(it, tree, margins):
            break
    return trees


def train(
    bins: np.ndarray,  # (N, F) uint8
    y: np.ndarray,
    opts: TrainOptions,
    w: Optional[np.ndarray] = None,
    init_margins: Optional[np.ndarray] = None,  # (N, C) warm-start margins
    valid_sets: Optional[Sequence[Tuple[str, np.ndarray, np.ndarray, Optional[np.ndarray]]]] = None,
    mapper: Optional[BinMapper] = None,
    mesh: Optional[Any] = None,
    feature_names: Optional[List[str]] = None,
    callbacks: Optional[Sequence[Any]] = None,
    hist_reduce: Optional[Any] = None,
    iteration_hook: Optional[Any] = None,
    start_iteration: int = 0,
) -> TrainResult:
    """Run boosting. ``valid_sets`` entries are (name, bins_v, y_v, w_v).

    ``callbacks`` are :class:`~mmlspark_tpu.lightgbm.callbacks.TrainingCallback`
    delegates (``LightGBMDelegate.scala`` analogue): LR schedules ride the
    scan fast path; per-iteration hooks run on the loop path.

    ``hist_reduce`` is the process-parallel histogram allreduce hook (see
    :func:`_hist_fn`); ``iteration_hook(it, tree)`` fires after each
    committed iteration on the loop path with the retained
    :class:`TreeArrays` — the journal-commit point for
    ``lightgbm/procfit.py``. Either forces the loop path (per-iteration
    host control is the point) and bypasses the shared program cache
    (the hook closures are fit-specific).

    ``start_iteration`` resumes a journaled fit at iteration k: the first
    k bagging/feature-mask draws are consumed WITHOUT running (the rng
    stream stays aligned with an uninterrupted fit — the property model
    parity after gang recovery rests on) and boosting begins at absolute
    iteration k against the caller-rebuilt ``init_margins``. The returned
    booster then contains only the new trees; a resuming caller packs
    restored + new trees itself via :func:`_pack_booster`."""
    _check_contracts(opts, valid_sets)
    valid_sets, callbacks = list(valid_sets or []), list(callbacks or [])
    prep = _prepare(bins, y, w, init_margins, opts, mapper)
    opts = prep.opts
    tracer = get_tracer()
    # ``lightgbm.upload`` is the host's share of placement: padding, casts
    # and the hand-off of each host array. The transfers themselves run on
    # (a 28 MB bins matrix: 15 ms here, 0.29 s on the wire, measured on a
    # v5e, PERF.md) and are waited for by the first program that reads
    # them, inside ``lightgbm.boost``.
    with tracer.span("lightgbm.upload") as up_span:
        data = _place(prep, mesh)
        up_span.tags["bytes"] = data.bytes

    with tracer.span("lightgbm.program") as prog_span:
        plan = _u_plan(opts, data.rows, prep.f, prep.num_bins, prep.bundle, mapper, mesh)
        _publish_plan(plan, opts, data.rows)
        opts = dataclasses.replace(opts, use_quantized_grad=plan.quant)
        lr_all = _lr_schedule(callbacks, opts.learning_rate, opts.num_iterations)
        bagging = _bagging_active(opts)
        # The scan path: no per-iteration host decisions, every iteration in
        # ONE device program. Its (iterations, N) bagging masks are gated so
        # a huge fit (10M rows x 1000 iterations = 10 GB) takes the loop path,
        # which re-uploads only on resample.
        scan_path = (
            mesh is None
            and not valid_sets
            and not _has_iteration_hooks(callbacks)
            and (not bagging or opts.num_iterations * data.rows <= (512 << 20))
            and opts.num_iterations > 0
            and opts.boosting_type != "dart"  # dart drops trees per host decision
            and not opts.provide_training_metric  # needs per-iteration margins
            and hist_reduce is None  # process fits need per-iteration control
            and iteration_hook is None
            and start_iteration == 0
        )
        if hist_reduce is not None:
            # times the host-side collective per call: histogram build
            # (device) vs allreduce (wire) time
            hist_reduce = get_profiler().wrap_host(hist_reduce, "gbdt.hist_allreduce")
        run = _Run(
            opts, prep, data, plan, mesh=mesh, hist_reduce=hist_reduce,
            scan=(bagging, lr_all is not None) if scan_path else None,
            faults=current_faults(),
        )
        run.build_programs()
        # whether this fit's step program was already built by an earlier fit
        prog_span.tags["cache_hit"] = run.progs.cache_hit
        watch = _Watch.start(opts, prep, valid_sets, callbacks, lr_all)
        schedule = _mask_schedule(opts, prep.n, data.pad, prep.f_feat, y=prep.y)
        # presence mask built on device (zeroed pad tail) — no upload
        ones = jnp.ones(data.rows, jnp.float32)
        bag_dev = data.dev_rows(ones if data.pad == 0 else ones.at[prep.n:].set(0.0))
        if scan_path:
            inputs = _scan_inputs(schedule, prep.f_feat, bagging, bag_dev, lr_all)

    if plan.spec is not None:
        run.build_u()  # once a fit, shared by every segment or iteration
    # ``lightgbm.boost`` runs from the first dispatch until the trees are on
    # the host: the dispatches return at once, so the span's time is the
    # device's, waited for in _fetch_trees (and per iteration on the loop
    # path). Nothing in it is recorded per iteration.
    with tracer.span(
        "lightgbm.boost", iterations=opts.num_iterations - start_iteration
    ) as boost_span:
        trees = stacked = None
        if scan_path:
            stacked = _boost_scan(run, inputs, boost_span)
        else:
            boost_span.tags["segments"] = 0  # the loop path: a dispatch an iteration
            trees = _boost_loop(run, watch, schedule, bag_dev, iteration_hook, start_iteration)
        iters_done = opts.num_iterations if stacked is not None else len(trees)
        watch.finish(iters_done)
        # with the trees, how many times the growers streamed the rows for a
        # histogram and how many rounds' children they left unbuilt
        fetched, (built, skipped) = _fetch_trees(trees, stacked, opts, prep.num_classes)
        boost_span.tags["hist_passes_built"] = int(built)
        boost_span.tags["hist_passes_skipped"] = int(skipped)
    with tracer.span("lightgbm.pack", trees=iters_done * prep.num_classes):
        booster = _assemble_booster(
            fetched, opts, prep.num_classes, prep.init_score, mapper, feature_names,
            best_iteration=watch.best_iteration,
        )
    return TrainResult(booster=booster, evals=watch.evals, best_iteration=watch.best_iter)


def train_many(
    bins: np.ndarray,  # (N, F) uint8 — SHARED by every candidate
    y: np.ndarray,
    opts_list: Sequence[TrainOptions],
    w: Optional[np.ndarray] = None,
    mapper: Optional[BinMapper] = None,
    feature_names: Optional[List[str]] = None,
) -> List[TrainResult]:
    """Train K candidates of ONE shape-bucket in a single compiled program.

    The many-models plane: every candidate must share
    :func:`many_bucket_key` (callers bucket heterogeneous grids first and
    call once per bucket). The per-iteration step is vmapped over a leading
    candidate axis (:func:`_scan_program`), so the whole sweep
    bucket is one dispatch and one compile — the per-candidate
    hyperparameters ride as traced data: learning_rate as a scanned
    (K, iterations) stack, bagging/feature-fraction as host-drawn mask
    stacks from the same :func:`_mask_schedule` the sequential path uses
    (identical rng stream per candidate seed, so a batched fit matches the
    equivalent :func:`train` call).

    Scope (ValueError outside it): single-device (no mesh), gbdt/goss
    boosting, no validation sets / callbacks / warm start. The U histogram
    path is bypassed — candidates share the compare-built kernels, which
    vmap over the candidate axis safely.
    """
    opts_list = list(opts_list)
    if not opts_list:
        raise ValueError("train_many requires at least one candidate")
    base_key = many_bucket_key(opts_list[0])
    for o in opts_list[1:]:
        if many_bucket_key(o) != base_key:
            raise ValueError(
                "train_many candidates must share one shape-bucket "
                "(many_bucket_key agreement) — partition heterogeneous "
                "grids into buckets first"
            )
    if opts_list[0].boosting_type not in ("gbdt", "goss"):
        raise ValueError(
            "train_many supports boosting_type 'gbdt' or 'goss' (dart "
            "drops trees per host decision; rf averages at the end) — got "
            f"{opts_list[0].boosting_type!r}"
        )
    if opts_list[0].num_iterations <= 0:
        raise ValueError("train_many requires num_iterations > 0")
    for o in opts_list:
        _check_contracts(o)
    # same mapper → same slot resolution for every candidate (the bucket key
    # already agrees on categorical/onehot slots); boost_from_average is
    # static (outside MANY_VMAPPED_FIELDS), so one init_score serves all
    opts_list = [_with_mapper_slots(o, mapper) for o in opts_list]
    prep = _prepare(bins, y, w, None, normalize_many_opts(opts_list[0]), mapper)
    data = _place(prep)
    n, K = prep.n, len(opts_list)

    # Per-candidate host-side schedules: each candidate draws its own
    # bagging/feature masks from ITS seed and fractions — the exact
    # sequential-path stream — and its constant learning rate becomes an
    # (iterations,) lane of the scanned lr stack.
    any_bag = any(_bagging_active(o) for o in opts_list)
    bag_stacks, fm_stacks = [], []
    for o in opts_list:
        bags, fms = _mask_stacks(_mask_schedule(o, n, 0, prep.f_feat, y=prep.y), prep.f_feat)
        if any_bag:
            bag_stacks.append(np.stack(bags).astype(np.uint8))
        fm_stacks.append(fms)
    lr_all = np.stack([
        np.full(prep.opts.num_iterations, o.learning_rate, dtype=np.float32)
        for o in opts_list
    ])
    progs = _programs(
        prep.opts, prep.objective, prep.num_bins, n, prep.bundle, scan=any_bag, many=True
    )
    _, stacked = progs.runner(
        data.bins, data.y, data.w,
        jnp.broadcast_to(data.margins, (K, *data.margins.shape)), data.edges,
        jnp.asarray(np.stack(bag_stacks)) if any_bag else jnp.ones(n, jnp.float32),
        jnp.asarray(np.stack(fm_stacks)), jnp.asarray(lr_all),
        jnp.int32(0), jnp.int32(0),
    )

    results: List[TrainResult] = []
    for ki, o in enumerate(opts_list):
        cand = jax.tree.map(lambda x, _ki=ki: x[_ki], stacked)
        booster = _pack_booster(
            None, cand, o, prep.num_classes, prep.init_score, mapper, feature_names,
            best_iteration=-1,
        )
        results.append(TrainResult(booster=booster, evals={}, best_iteration=0))
    return results


def _pack_booster(
    trees: Optional[List[TreeArrays]],
    stacked_trees: Optional[TreeArrays],
    opts: TrainOptions,
    num_classes: int,
    init_score: np.ndarray,
    mapper: Optional[BinMapper],
    feature_names: Optional[List[str]] = None,
    best_iteration: int = -1,
) -> Booster:
    """Pack per-tree arrays into one :class:`Booster` — train()'s tail,
    factored so the process-parallel fit (``procfit.py``) can rebuild the
    identical booster from journal-restored trees. Accepts either a list
    of per-iteration :class:`TreeArrays` (loop path / journal restore) or
    a scan-stacked TreeArrays pytree. Two halves, so that train() can end
    its ``lightgbm.boost`` span where the device's work has reached the
    host: :func:`_fetch_trees`, then :func:`_assemble_booster`."""
    return _assemble_booster(
        _fetch_trees(trees, stacked_trees, opts, num_classes)[0],
        opts, num_classes, init_score, mapper, feature_names, best_iteration,
    )


_FIELDS = (
    "feat", "bin", "thr", "left", "right", "is_leaf", "leaf_val", "cover", "gain",
)


def _fetch_trees(
    trees: Optional[List[TreeArrays]],
    stacked_trees: Optional[TreeArrays],
    opts: TrainOptions,
    num_classes: int,
) -> Tuple[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]], np.ndarray]:
    """Device to host: (packed (9, T*C, M) float32 tree fields, categorical
    node flags, categorical masks), and the histogram passes the growers
    built and skipped over all T*C trees. The first fetch waits for every
    dispatch still in flight."""
    t = opts.num_iterations if stacked_trees is not None else len(trees)
    m = opts.num_nodes

    def flat(field, *tail):
        """``field`` of every tree, (T*C, *tail): scan-stacked (T, C, ...) or
        a list of (C, ...) per-iteration arrays."""
        if stacked_trees is not None:
            dev = getattr(stacked_trees, field)
        else:
            dev = jnp.concatenate([getattr(tr, field) for tr in trees], axis=0)
        return dev.reshape(t * num_classes, *tail)

    # ONE device-side pack + ONE fetch for all tree fields: every int/bool
    # field's values fit float32 exactly (slot ids < 2^24), so the 9 fields
    # ride a single (9, T*C, M) f32 wire transfer instead of 9 round-trips.
    # The pass counts ride the same fetch: no round trip of their own.
    packed, passes = jax.device_get((
        jnp.stack([flat(fld, m).astype(jnp.float32) for fld in _FIELDS]),
        flat("passes", 2).sum(axis=0),
    ))

    # Categorical split arrays ride separate (small) transfers: the bool
    # mask matrix does not fit the homogeneous f32 pack.
    cat_nodes_np = cat_masks_np = None
    if opts.categorical_slots:
        cat_nodes_np = np.asarray(flat("cat_node", m)).astype(bool)
        cat_masks_np = np.asarray(flat("cat_mask", m, -1).astype(jnp.uint8)).astype(bool)
    return (packed, cat_nodes_np, cat_masks_np), passes


def _assemble_booster(
    fetched: Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]],
    opts: TrainOptions,
    num_classes: int,
    init_score: np.ndarray,
    mapper: Optional[BinMapper],
    feature_names: Optional[List[str]] = None,
    best_iteration: int = -1,
) -> Booster:
    """Host only: the fetched tree fields to one :class:`Booster`."""
    packed, cat_nodes_np, cat_masks_np = fetched
    t = packed.shape[1] // num_classes

    def stack(field, dtype):
        return packed[_FIELDS.index(field)].astype(dtype)

    left = stack("left", np.int32)
    right = stack("right", np.int32)
    is_leaf = stack("is_leaf", bool)
    leaf_values = stack("leaf_val", np.float32)
    if opts.boosting_type == "rf":
        # random-forest mode predicts the AVERAGE of the trees
        leaf_values = leaf_values / max(1, t)
    return Booster(
        split_feature=stack("feat", np.int32),
        split_bin=stack("bin", np.int32),
        split_threshold=stack("thr", np.float32),
        left_child=left,
        right_child=right,
        is_leaf=is_leaf,
        leaf_values=leaf_values,
        cover=stack("cover", np.float32),
        split_gain=stack("gain", np.float32),
        init_score=np.asarray(init_score, dtype=np.float32),
        num_classes=num_classes,
        objective=opts.objective,
        max_depth=_realized_depth(left, right, is_leaf, opts.routing_steps),
        best_iteration=best_iteration,
        feature_names=feature_names,
        bin_edges=None if mapper is None else mapper.edges,
        cat_nodes=cat_nodes_np,
        cat_masks=cat_masks_np,
        cat_values=(
            None if (mapper is None or not mapper.cat_values)
            else {int(j): np.asarray(v) for j, v in mapper.cat_values.items()}
        ),
    )


def _realized_depth(left, right, is_leaf, bound: int) -> int:
    """Max root→leaf depth over all trees (host-side; the static routing
    step count for predict). One forward pass over slots suffices: children
    always occupy a higher slot index than their parent in both layouts."""
    t, m = left.shape
    depth = np.zeros((t, m), dtype=np.int64)
    rows = np.arange(t)
    for j in range(m):
        internal = ~is_leaf[:, j] & (left[:, j] > j)  # real internal nodes only
        if not internal.any():
            continue
        for child in (left[:, j], right[:, j]):
            depth[rows[internal], child[internal]] = depth[internal, j] + 1
    reachable = depth[is_leaf]
    realized = int(reachable.max()) if reachable.size else 1
    return max(1, min(realized, bound))
