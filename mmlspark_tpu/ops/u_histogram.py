"""Precomputed-U histogram pass: hoist the one-hot build out of the hot loop.

The compare-built histogram kernels (``ops/pallas_histogram.py``) pay the
VPU one-hot construction — the binding resource of the op
(``docs/perf_histogram.md``) — on EVERY pass. But bins are static across a
fit: the one-hot matrix ``U[off_f + b, i] = (bins[i, f] == b)`` can be built
ONCE on device (int8, transposed so rows ride the lane dimension) and every
histogram pass becomes one MXU contraction against the node-keyed stat panel

    hist[col, d] = sum_i U[col, i] * panel[d, i]        (K, 3k) = U @ panelᵀ

an "NT" matmul with BOTH operands' contraction on their lane axis — no
relayout anywhere in the hot loop. That layout discipline is the whole
game on this toolchain: every (N,) -> (N, D) lane-broadcast or f32->int8
convert of row vectors measured 3-5 ms by itself (sublane<->lane shuffles),
as much as the dot. Measured at the bench hot shape (400k x 28 x 256, 8
nodes, v5e): 4.9 ms vs 12.7 ms for the compare-built panel kernel — the
one-hot is s8 (exact 0/1), the panel bf16, f32 accumulation: the IDENTICAL
precision model as the compare-built kernel's default MXU pass, so split
decisions and histogram sums agree in distribution (both: g/h bf16 input
rounding, counts exact).

This is the TPU analogue of the reference engine's bin-major feature
groups (its native dataset also fixes the bin layout once,
``lightgbm/LightGBMUtils.scala:212-239``) — pay the layout once, stream it
every pass.

Feature packing rides in the U row layout: feature f owns rows
``[off_f, off_f + width_f)`` where ``width_f`` is its ACTUAL bin count
(``BinMapper.num_bins``), so K = sum_f width_f, not F * max_bin — on real
datasets with low-cardinality features U (and the HBM re-stream that bounds
the pass) shrinks proportionally. A static (F, max_bin) gather map expands
the packed result back to the dense (k, F, B, 3) histogram the split search
consumes.

Memory: U is fit-resident HBM (K_pad · N_pad bytes as int8). Callers gate
on :func:`u_bytes` — at 400k x 28 x 256 that is ~2.9 GB (fine on 16 GB
v5e), at 4M it would be 29 GB (gate fails, compare-built kernels take
over).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_LANE = 128
_N_ALIGN = 512  # row padding granularity (lane-dim alignment for U tiles)


@dataclasses.dataclass(frozen=True)
class USpec:
    """Static host-side description of the packed one-hot layout (hashable:
    part of the jitted-program cache key)."""

    widths: Tuple[int, ...]  # per-feature bin count (incl. missing bin)
    offsets: Tuple[int, ...]  # per-feature first packed row of U
    k: int  # sum of widths
    k_pad: int  # k rounded up to the sublane block
    num_bins: int  # dense histogram width B the caller expects
    # 0 = fit-resident U (build_u once, stream it every pass). > 0 = the
    # ROW-CHUNKED pass: no full U is ever materialized — each histogram
    # pass scans ``chunk_rows``-row chunks of the (pre-laid-out) bins,
    # builds that chunk's one-hot in-trace, contracts it against the
    # chunk's stat panel, and accumulates the packed partial histograms
    # (build_histograms_u_chunked). This is how the MXU path survives past
    # the ~1M-row residency cliff: HBM holds one bins copy + O(chunk)
    # transients instead of the full K_pad x N_pad int8 U.
    chunk_rows: int = 0

    @property
    def num_features(self) -> int:
        return len(self.widths)


def make_u_spec(num_bins: int, num_features: int, per_feature=None) -> USpec:
    """``per_feature`` = BinMapper.num_bins (actual per-feature widths);
    None = uniform ``num_bins`` (no mapper — e.g. pre-binned input)."""
    if per_feature is None:
        widths = [num_bins] * num_features
    else:
        widths = [int(min(max(w, 1), num_bins)) for w in per_feature]
    offsets = np.concatenate([[0], np.cumsum(widths[:-1])]).astype(int)
    k = int(np.sum(widths))
    k_pad = ((k + _LANE - 1) // _LANE) * _LANE
    return USpec(
        widths=tuple(widths), offsets=tuple(int(o) for o in offsets),
        k=k, k_pad=k_pad, num_bins=num_bins,
    )


def u_bytes(n_rows: int, spec: USpec) -> int:
    """Resident HBM cost of the int8 U for ``n_rows`` (pre-padding)."""
    n_pad = ((n_rows + _N_ALIGN - 1) // _N_ALIGN) * _N_ALIGN
    return n_pad * spec.k_pad


def chunked_u_spec(n_rows: int, spec: USpec, budget: int) -> USpec:
    """Derive the row-chunked variant of ``spec`` sized to ``budget``
    (MMLSPARK_TPU_U_BUDGET): the per-chunk one-hot transient
    (chunk_rows x k_pad int8) is capped at HALF the budget — the scan
    keeps the current chunk plus the double-buffered next one in flight —
    and chunk_rows stays a multiple of the row-alignment block."""
    per_row = max(1, spec.k_pad)
    target = max(budget // 2, per_row * _N_ALIGN)
    chunk = max(_N_ALIGN, (target // per_row) // _N_ALIGN * _N_ALIGN)
    n_pad = ((n_rows + _N_ALIGN - 1) // _N_ALIGN) * _N_ALIGN
    chunk = min(chunk, n_pad)
    return dataclasses.replace(spec, chunk_rows=int(chunk))


def num_u_chunks(n_rows: int, spec: USpec) -> int:
    """Chunk count of one histogram pass for a chunked spec."""
    if not spec.chunk_rows:
        return 1
    return -(-n_rows // spec.chunk_rows)


def prepare_chunked_bins(bins: jax.Array, spec: USpec) -> jax.Array:
    """One-time per-fit layout for the chunked pass: (N, F) bins →
    (num_chunks, F, chunk_rows) uint8, feature-major within each chunk so
    the in-trace one-hot build gathers rows exactly like :func:`build_u`.
    Pad rows keep bin value 0 — a VALID one-hot column — and are silenced
    by the pass itself (their node key is padded to -1, so their panel
    columns are zero and they contribute nothing)."""
    n, f = bins.shape
    chunk = spec.chunk_rows
    if not chunk:
        raise ValueError("prepare_chunked_bins needs a chunked spec")
    m = -(-n // chunk)
    pad = m * chunk - n
    x = bins.astype(jnp.uint8)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x.reshape(m, chunk, f).transpose(0, 2, 1)


@functools.lru_cache(maxsize=64)
def _col_maps_cached(spec: USpec) -> Tuple[np.ndarray, np.ndarray]:
    """Static per-spec column maps: ``feat_of_col[c]`` = feature owning
    packed row c, ``local_of_col[c]`` = c's bin id within that feature
    (-1 on the k..k_pad tail so tail rows match nothing). Cached as HOST
    numpy (the lru_cache host boundary graftlint understands): callers may
    hit this inside a trace, and a device array built there would be a
    trace-local constant the cache must not retain."""
    feat = np.zeros(spec.k_pad, np.int32)
    local = np.full(spec.k_pad, -1, np.int32)
    for j, (o, w) in enumerate(zip(spec.offsets, spec.widths)):
        feat[o : o + w] = j
        local[o : o + w] = np.arange(w)
    return feat, local


@jax.named_scope("u_build")
def build_u(bins: jax.Array, spec: USpec, dtype=jnp.int8) -> jax.Array:
    """(K_pad, N_pad) TRANSPOSED one-hot of the packed bin ids — ONE compare
    pass's worth of VPU work (~120 ms at 400k x 28 x 256), paid once per
    fit. The bin axis leads so the pass contraction is lane-on-lane.

    Built by a ``lax.scan`` over 128-row K blocks: trace size is O(1) in
    the feature count (a thousands-of-features dataset must not inflate
    trace/compile time — the original per-feature Python loop did), and
    the per-step gather transient is bounded at 128 x N_pad int32 — the
    single whole-K gather formulation made the TPU compiler itself crash
    at 1M rows (the (K_pad, N_pad) int32 intermediate is tens of GB).
    Pad rows carry bin id -1 and the k..k_pad tail carries local id -1,
    so both contribute nothing."""
    n, f = bins.shape
    pad = (-n) % _N_ALIGN
    ids = bins.astype(jnp.int32)
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    ids_t = ids.T  # (F, N_pad)
    feat_of_col, local_of_col = _col_maps_cached(spec)
    blk = _LANE  # k_pad is always a multiple of the lane block
    fo = feat_of_col.reshape(-1, blk)
    lo = local_of_col.reshape(-1, blk)

    def block(_, fl):
        fb, lb = fl
        rows = jnp.take(ids_t, fb, axis=0)  # (blk, N_pad)
        return None, (rows == lb[:, None]).astype(dtype)

    _, u = lax.scan(block, None, (fo, lo))
    return u.reshape(spec.k_pad, n + pad)


def _dense_maps(spec: USpec) -> Tuple[np.ndarray, np.ndarray]:
    """(F, B) packed-row gather map + validity mask for expanding the packed
    (K, D) result into the dense (F, B, D) histogram."""
    f, b = spec.num_features, spec.num_bins
    idx = np.zeros((f, b), np.int32)
    mask = np.zeros((f, b), np.float32)
    for j in range(f):
        w = spec.widths[j]
        idx[j, :w] = spec.offsets[j] + np.arange(w)
        mask[j, :w] = 1.0
    return idx, mask


@functools.lru_cache(maxsize=64)
def _dense_maps_cached(spec: USpec) -> Tuple[np.ndarray, np.ndarray]:
    # Cached as HOST numpy; see _col_maps_cached.
    return _dense_maps(spec)


def cat_row_maps(spec: USpec, cat_slots) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static maps for the CATEGORICAL subset of U's packed rows:
    (row ids into U, feature id per row, local bin per row). Restricting
    the membership matmul to these rows streams only the categorical
    features' one-hot block per pass (~Σ cat widths instead of K_pad)."""
    rows, feats, locals_ = [], [], []
    for f_ in sorted(int(s) for s in cat_slots):
        w = spec.widths[f_]
        o = spec.offsets[f_]
        rows.extend(range(o, o + w))
        feats.extend([f_] * w)
        locals_.extend(range(w))
    return (
        np.asarray(rows, np.int32),
        np.asarray(feats, np.int32),
        np.asarray(locals_, np.int32),
    )


def membership_matmul(
    u_rows: jax.Array,  # (Kc, N_pad) int8 — the cat-feature rows of U
    feat_of_row: jax.Array,  # (Kc,) int32 feature id per row
    local_of_row: jax.Array,  # (Kc,) int32 feature-local bin per row
    sf: jax.Array,  # (k,) int32 split feature per leaf
    scm: jax.Array,  # (k, B) bool left-set mask per leaf (feature-local bins)
    n: int,
) -> jax.Array:
    """(k, n) bool: row in leaf jj's categorical left set — ONE standard
    (k, Kc) x (Kc, N) MXU matmul against the categorical rows of the
    fit-resident one-hot instead of per-leaf (N,) gathers (each tiny
    gather costs ~ms of layout round-trip in-context on TPU; measured
    ~35 ms/tree in the leafwise while_loop). Scatter each leaf's mask
    into packed-row space via the static row maps, dot, threshold.
    Numerically exact: the one-hot and the mask are 0/1 in bf16."""
    k = sf.shape[0]
    kc = feat_of_row.shape[0]
    sel = feat_of_row[None, :] == sf[:, None]
    masks = (
        jnp.take_along_axis(
            scm, jnp.broadcast_to(local_of_row[None, :], (k, kc)), axis=1
        )
        & sel
    )  # (k, Kc) — small (no N axis); bins hold feature-local ids
    in_set_f = lax.dot_general(
        masks.astype(jnp.bfloat16), u_rows.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (k, N_pad)
    return in_set_f[:, :n] > 0


def stat_rows(grad: jax.Array, hess: jax.Array, count: jax.Array) -> jax.Array:
    """(3, N) bf16 stat stack [g; h; c] in the row-on-lanes layout the panel
    wants. Node-independent — build it ONCE per tree and reuse across every
    pass of that tree (g/h/c are fixed within a tree)."""
    return jnp.stack(
        [grad, hess, count], axis=0
    ).astype(jnp.bfloat16)


def stat_rows_quant(
    grad: jax.Array, hess: jax.Array, count: jax.Array, key: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """8-bit stochastically-rounded stat rows + dequant scales — LightGBM's
    gradient-quantization training (``use_quantized_grad``: its engine
    discretizes g/h onto a small symmetric grid with stochastic rounding so
    histogram accumulation rides the integer SIMD/MXU path; here the whole
    U pass becomes one s8 x s8 MXU contraction at 2x the int ops/cycle of
    the bf16 path and a narrower panel stream). 127-level symmetric grid
    per tree: x_q = floor(x * 127/max|x| + u), u ~ U[0,1) — unbiased
    (E[x_q] = x * 127/max|x|), so per-bin SUMS are unbiased estimators and
    split gains converge to the exact ones at histogram row counts. Counts
    are 0/1 and stay exact. Returns ((3, N) int8 [g_q; h_q; c],
    (3,) f32 per-stat dequant scales [gs/127, hs/127, 1])."""
    g = grad.astype(jnp.float32)
    h = hess.astype(jnp.float32)
    gs = jnp.maximum(jnp.max(jnp.abs(g)), jnp.float32(1e-30))
    hs = jnp.maximum(jnp.max(jnp.abs(h)), jnp.float32(1e-30))
    kg, kh = jax.random.split(key)

    def q(x, s, kk):
        u = jax.random.uniform(kk, x.shape, dtype=jnp.float32)
        return jnp.clip(
            jnp.floor(x * (127.0 / s) + u), -127, 127
        ).astype(jnp.int8)

    stats = jnp.stack([q(g, gs, kg), q(h, hs, kh), count.astype(jnp.int8)])
    scales = jnp.stack([gs / 127.0, hs / 127.0, jnp.float32(1.0)])
    return stats, scales


def histogram_acc_dtype(n_rows: int, quant: bool):
    """Narrowest histogram-accumulator dtype that is provably overflow-free
    for ``n_rows`` — the deterministic promotion rule of the quantized
    path's packed accumulators (LightGBM's quantized training picks
    per-leaf hist bit widths the same way, from a bound on rows x grad
    range; here the bound is static per fit so the choice is part of the
    compiled program, never a runtime saturation check).

    Quantized stats are 127-level ints, so any per-bin partial sum is
    bounded by ``127 * n_rows`` (counts are 0/1 and bounded by ``n_rows``
    alone): int16 when that fits, else int32 — still exact integer sums
    either way, just wider. The f32 path keeps f32 (its sums are not
    integer, so narrowing would change results)."""
    if not quant:
        return jnp.float32
    if 127 * n_rows <= np.iinfo(np.int16).max:
        return jnp.int16
    return jnp.int32


@jax.named_scope("hist_pass")
def build_histograms_u(
    u: jax.Array,  # (K_pad, N_pad) int8 from build_u
    grad: jax.Array,  # (N,) — ignored when stats is given
    hess: jax.Array,
    count: jax.Array,
    node: jax.Array,  # (N,) int32; out-of-range => row contributes nothing
    num_nodes: int,
    spec: USpec,
    *,
    stats=None,  # (3, N) bf16 from stat_rows(), or (stats_i8, scales) quant
    dequant: bool = True,
) -> jax.Array:
    """(num_nodes, F, B, 3) float32 — same contract as
    ``ops.histogram.build_histograms`` but with the one-hot precomputed.

    The per-pass work is: a (3k, N) transposed panel (node-key select over
    the stat rows, built entirely in the row-on-lanes layout) and one
    s8 x bf16 NT matmul. Precision model = the compare-built kernel's
    default MXU pass (bf16 inputs, f32 accumulation; counts exact).

    When ``stats`` is a ``stat_rows_quant`` tuple the pass runs entirely in
    int8 (s8 x s8 MXU, s32 accumulation — exact integer sums of the
    quantized per-row values) and the packed result is dequantized by the
    per-stat scales; counts stay bit-exact either way. ``dequant=False``
    keeps the quant result in the narrowest provably overflow-free integer
    dtype (:func:`histogram_acc_dtype`) so the caller can do exact integer
    sibling subtraction before applying the scales (:func:`dequant_hist`)."""
    scales = None
    if isinstance(stats, tuple):
        stats, scales = stats
    if 3 * num_nodes > _LANE:
        raise ValueError(f"panel width 3*{num_nodes} exceeds one lane group")
    k = num_nodes
    n = node.shape[0]
    n_pad = u.shape[1]

    if stats is None:
        stats = stat_rows(grad, hess, count)

    panel_t = _stat_panel_t(stats, node, k, n_pad)
    if scales is not None:
        packed = lax.dot_general(
            u, panel_t,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32,
        )  # (K_pad, 3k) exact int sums of quantized stats
    else:
        packed = lax.dot_general(
            u.astype(jnp.bfloat16), panel_t,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (K_pad, 3k)

    if scales is not None and not dequant:
        # narrow to the statically overflow-free accumulator width (exact:
        # MXU accumulation is s32; the downcast is lossless under the
        # 127 * n_rows bound histogram_acc_dtype derives from)
        packed = packed.astype(histogram_acc_dtype(n, quant=True))
    return _expand_packed(packed, scales, spec, k, dequant=dequant)


def _stat_panel_t(
    stats: jax.Array,  # (3, N) bf16 | int8
    node: jax.Array,  # (N,)
    k: int,
    n_pad: int,
) -> jax.Array:
    """(3k, N_pad) stat-major transposed panel: row s*k+j carries stat s
    for rows whose node key is j, 0 elsewhere. node broadcasts across
    SUBLANES (cheap); no lane-dim relayout anywhere. Materialized behind
    an optimization barrier: without it XLA re-fuses the panel build into
    the dot's rhs load and recomputes it per K-tile (~2x slower)."""
    n = node.shape[0]
    key = jnp.tile(jnp.arange(k, dtype=jnp.int32), 3)[:, None]  # (3k, 1)
    mask_t = key == node.astype(jnp.int32)[None, :]  # (3k, N)
    zero = jnp.int8(0) if stats.dtype == jnp.int8 else jnp.bfloat16(0)
    vals_t = jnp.repeat(stats, k, axis=0)  # (3k, N) bf16 | int8
    panel_t = jnp.where(mask_t, vals_t, zero)
    if n_pad != n:
        panel_t = jnp.pad(panel_t, ((0, 0), (0, n_pad - n)))
    return lax.optimization_barrier(panel_t)


def _expand_packed(
    packed: jax.Array, scales, spec: USpec, k: int, dequant: bool = True
) -> jax.Array:
    """Shared pass tail: dequantize (quant path — row s*k+j carries stat
    s, so the (3, k) reshape broadcasts each stat's scale over its k node
    columns) and expand the packed (K_pad, 3k) result to the dense
    (k, F, B, 3) histogram via the static gather maps.

    ``dequant=False`` DEFERS the scale multiply: the gather expansion runs
    in the packed integer domain and the result keeps the accumulator
    dtype, so callers (the sibling-subtraction cache in the leafwise
    grower) can subtract parent - child as exact integer sums and apply
    the scales once, after subtraction — the subtracted sibling is then
    bit-identical to a directly built one."""
    if scales is not None and dequant:
        packed = (
            packed.reshape(-1, 3, k).astype(jnp.float32)
            * scales[None, :, None]
        ).reshape(-1, 3 * k)
    f, b = spec.num_features, spec.num_bins
    idx, mask = _dense_maps_cached(spec)
    dense = packed[idx.reshape(-1)].reshape(f, b, 3 * k)
    dense = dense * jnp.asarray(mask).astype(dense.dtype)[:, :, None]
    return dense.reshape(f, b, 3, k).transpose(3, 0, 1, 2)


def dequant_hist(h: jax.Array, scales: jax.Array) -> jax.Array:
    """Apply the deferred per-stat dequant scales to a spec-space histogram
    built with ``dequant=False`` (last axis = [g, h, c] — matches the (3,)
    scale stack from :func:`stat_rows_quant`)."""
    return h.astype(jnp.float32) * scales


@jax.named_scope("hist_pass")
def build_histograms_u_chunked(
    bins_chunks: jax.Array,  # (m, F, chunk) uint8 from prepare_chunked_bins
    grad: jax.Array,  # (N,) — ignored when stats is given
    hess: jax.Array,
    count: jax.Array,
    node: jax.Array,  # (N,) int32; out-of-range => row contributes nothing
    num_nodes: int,
    spec: USpec,  # chunked (spec.chunk_rows > 0)
    *,
    stats=None,  # (3, N) bf16 from stat_rows(), or (stats_i8, scales) quant
    dequant: bool = True,
) -> jax.Array:
    """Row-chunked variant of :func:`build_histograms_u` — same contract,
    same precision model, but NO fit-resident U: a ``lax.scan`` walks the
    pre-laid-out bins chunks, rebuilds each chunk's one-hot in-trace (the
    same 128-row K-block gather loop as :func:`build_u`), contracts it
    against the chunk's stat panel, and accumulates the packed (K_pad, 3k)
    partial histograms — int32 (exact) on the quantized path, f32
    otherwise (partial-sum association differs from the resident pass only
    within f32 rounding, the precision the compare-built kernels already
    carry). The scan's sequential chunks let XLA double-buffer the next
    chunk's bins stream behind the current contraction, so past the
    residency cliff the pass stays MXU-bound instead of falling back to
    the compare-built slow path.

    Pad rows (the m*chunk - N tail) carry bin 0 — a valid one-hot column —
    but their node key is padded to -1, so their panel columns are zero
    and they contribute nothing, exactly like build_u's -1 pad rows."""
    scales = None
    if isinstance(stats, tuple):
        stats, scales = stats
    if 3 * num_nodes > _LANE:
        raise ValueError(f"panel width 3*{num_nodes} exceeds one lane group")
    k = num_nodes
    m, _, chunk = bins_chunks.shape
    n = node.shape[0]
    if stats is None:
        stats = stat_rows(grad, hess, count)
    quant = scales is not None

    total = m * chunk
    node_p = node.astype(jnp.int32)
    if total != n:
        node_p = jnp.pad(node_p, (0, total - n), constant_values=-1)
        stats = jnp.pad(stats, ((0, 0), (0, total - n)))
    node_c = node_p.reshape(m, chunk)
    stats_c = stats.reshape(3, m, chunk).transpose(1, 0, 2)  # (m, 3, chunk)

    feat_of_col, local_of_col = _col_maps_cached(spec)
    fo = feat_of_col.reshape(-1, _LANE)
    lo = local_of_col.reshape(-1, _LANE)

    def chunk_step(acc, xs):
        ids_t, nd, st = xs  # (F, chunk) u8, (chunk,) i32, (3, chunk)
        ids32 = ids_t.astype(jnp.int32)

        def block(_, fl):
            fb, lb = fl
            rows = jnp.take(ids32, fb, axis=0)  # (128, chunk)
            return None, (rows == lb[:, None]).astype(jnp.int8)

        _, u_c = lax.scan(block, None, (fo, lo))
        u_c = u_c.reshape(spec.k_pad, chunk)
        panel_t = _stat_panel_t(st, nd, k, chunk)
        if quant:
            part = lax.dot_general(
                u_c, panel_t,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32,
            )
        else:
            part = lax.dot_general(
                u_c.astype(jnp.bfloat16), panel_t,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
        return acc + part.astype(acc.dtype), None

    # The scan CARRY is the pass's HBM-resident accumulator — on the quant
    # path it narrows to the statically overflow-free integer width (the
    # per-chunk MXU partial is s32, downcast exact under the whole-pass
    # 127 * n_rows bound, which dominates every chunk partial).
    acc0 = jnp.zeros((spec.k_pad, 3 * k), histogram_acc_dtype(n, quant))
    packed, _ = lax.scan(chunk_step, acc0, (bins_chunks, node_c, stats_c))
    return _expand_packed(packed, scales, spec, k, dequant=dequant)
