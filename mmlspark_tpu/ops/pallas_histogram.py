"""Pallas TPU kernel for GBDT histogram building.

The hot op (reference ``lightgbm/TrainUtils.scala:220-315`` runs it natively
per iteration) re-expressed for the MXU: instead of materializing a one-hot
matrix in HBM and matmuling (the XLA ``onehot`` path in
``ops/histogram.py``), the kernel fuses one-hot construction and the
reduction entirely in VMEM:

- grid (F, N/block): each step loads one feature's combined-id tile
  (``node*B + bin``, pre-added outside the kernel so XLA fuses it into the
  transpose pass) and the (g, h, c) data tile;
- builds the (8, bw, K) one-hot *in VMEM* via an iota compare (never
  written to HBM); rows are tiled (8, bw) because Mosaic cannot flatten a
  sublane×lane tile to 1D, so the contraction is a sublane-batched
  ``dot_general`` summed over the batch;
- accumulates into the (K, 3) output block, which stays resident in VMEM
  across the whole row loop (revisited output block = accumulation idiom);
- default MXU precision (1-pass bf16 inputs, f32 accumulation) measures
  3.3x faster than ``Precision.HIGHEST`` on v5e and matches what the XLA
  one-hot path does on TPU anyway; the one-hot side is exactly
  representable, so only g/h pick up bf16 input rounding (~0.4%% relative
  per element, unbiased — the same class of approximation as LightGBM's
  own histogram binning). ``precision="highest"`` restores exact f32.

HBM traffic is therefore just the operands — the id matrix (4·N·F bytes),
data (12·N bytes, re-read per feature tile) and the (F·K·3·4)-byte result —
the bandwidth floor of the op. See ``docs/perf_histogram.md`` for the
measured A/B against the XLA formulation and the roofline argument.

VMEM budget gates the row-block size: the one-hot tile is 8·bw·K·4 bytes,
so ``bw`` shrinks as K = num_nodes·num_bins grows; below the minimum lane
width the kernel refuses and the caller falls back to XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One-hot VMEM budget. 6 MiB leaves room for the id/data tiles, the (K, 3)
# accumulator, and double buffering within ~16 MiB of VMEM.
_ONEHOT_BYTES = 6 << 20
_SUBLANES = 8
_MIN_BW = 128
_MAX_BW = 512


def pick_bw(k: int) -> int:
    """Lane width bw whose one-hot (8, bw, K) f32 tile fits the VMEM budget;
    0 when even the minimum would blow it (caller must fall back to XLA)."""
    bw = _ONEHOT_BYTES // (4 * _SUBLANES * max(k, 1))
    bw = min(_MAX_BW, (bw // _MIN_BW) * _MIN_BW)
    return bw if bw >= _MIN_BW else 0


def panel_fits(num_nodes: int, num_bins: int) -> bool:
    """Whether the panel kernel applies: the node panel must fit one MXU
    lane group and the bin one-hot must fill at least one."""
    return 3 * num_nodes <= 128 and num_bins >= 128 and pick_bw(num_bins) > 0


def build_node_panel(grad, hess, count, node, num_nodes: int):
    """(N, 3*num_nodes) stat-major data panel [g·nodes | h·nodes | c·nodes]:
    row i carries its (g, h, c) in the node[i]-keyed columns and zeros
    elsewhere; out-of-range node keys zero the whole row (the in-leaf mask
    convention). The ONE definition of the panel layout — the pallas and XLA
    histogram paths both decode it as reshape(F, B, 3, k).transpose(3,0,1,2),
    so they must share the encoder."""
    node = node.astype(jnp.int32)
    nodeoh = (
        node[:, None] == jnp.arange(num_nodes, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32)  # (N, k)
    data = jnp.stack(
        [grad.astype(jnp.float32), hess.astype(jnp.float32), count.astype(jnp.float32)],
        axis=-1,
    )  # (N, 3)
    return (data[:, :, None] * nodeoh[:, None, :]).reshape(node.shape[0], 3 * num_nodes)


def build_histograms_panel_pallas(
    bins: jax.Array,  # (N, F) integer bin indices
    grad: jax.Array,  # (N,)
    hess: jax.Array,  # (N,)
    count: jax.Array,  # (N,)
    node: jax.Array,  # (N,) int32 node key; out-of-range ⇒ row contributes 0
    num_nodes: int,
    num_bins: int,
    *,
    bw: Optional[int] = None,
    interpret: bool = False,
    precision: str = "default",
) -> jax.Array:
    """(num_nodes, F, num_bins, 3) float32 via the panel formulation: the
    node key moves from the one-hot ids (where each node adds B VPU-built
    one-hot columns) into a precomputed (N, 3*num_nodes) data panel whose
    lane dimension the MXU pads to 128 anyway — so up to ``floor(128/3) =
    42`` nodes cost the same pass as one. The panel is built by ONE fused
    XLA pass over the rows (node one-hot × [g,h,c]); the kernel itself is
    the same VMEM-fused bin one-hot as the combined-id kernel, just with a
    wide data operand. This is what makes multi-leaf-per-pass leafwise
    growth ~free (train.py).

    Unlike the combined-id kernel, rows whose node key is outside
    [0, num_nodes) contribute nothing (zero panel row) — callers exploit
    this as the in-leaf mask, so no grad/hess pre-masking pass is needed."""
    n, f = bins.shape
    if 3 * num_nodes > 128:
        raise ValueError(f"panel width 3*{num_nodes} exceeds one lane group")
    if bw is None:
        bw = pick_bw(num_bins)
    if not bw:
        raise ValueError(f"num_bins={num_bins} too large for the VMEM budget")

    block_n = _SUBLANES * bw
    panel = build_node_panel(grad, hess, count, node, num_nodes)
    ids = bins.astype(jnp.int32)

    pad = (-n) % block_n
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
        panel = jnp.pad(panel, ((0, pad), (0, 0)))
    n_pad = n + pad
    tiles = n_pad // block_n
    d = 3 * num_nodes

    ids3 = ids.T.reshape(f, tiles * _SUBLANES, bw)
    panel3 = panel.reshape(tiles * _SUBLANES, bw, d)

    prec = lax.Precision.HIGHEST if precision == "highest" else None
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bw=bw, k=num_bins, precision=prec),
        grid=(f, tiles),
        in_specs=[
            pl.BlockSpec((1, _SUBLANES, bw), lambda j, t: (j, t, 0)),
            pl.BlockSpec((_SUBLANES, bw, d), lambda j, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, num_bins, d), lambda j, t: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, num_bins, d), jnp.float32),
        interpret=interpret,
    )(ids3, panel3)
    # (F, B, 3*nodes) stat-major → (nodes, F, B, 3)
    return out.reshape(f, num_bins, 3, num_nodes).transpose(3, 0, 1, 2)


def _hist_kernel(ids_ref, data_ref, out_ref, *, bw: int, k: int, precision):
    t = pl.program_id(1)
    ids = ids_ref[0]  # (8, bw) int32 combined node*B + bin
    onehot = (
        ids[:, :, None] == lax.broadcasted_iota(jnp.int32, (_SUBLANES, bw, k), 2)
    ).astype(jnp.float32)
    # Sublane-batched (8, K, 3) matmul on the MXU, then fold the batch.
    contrib = lax.dot_general(
        onehot,
        data_ref[:],
        (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=precision,
    ).sum(axis=0)

    @pl.when(t == 0)
    def _init():
        out_ref[0] = contrib

    @pl.when(t != 0)
    def _acc():
        out_ref[0] += contrib


def build_histograms_pallas(
    bins: jax.Array,  # (N, F) integer bin indices
    grad: jax.Array,  # (N,)
    hess: jax.Array,  # (N,)
    count: jax.Array,  # (N,)
    node: jax.Array,  # (N,) int32 local node index
    num_nodes: int,
    num_bins: int,
    *,
    bw: Optional[int] = None,
    interpret: bool = False,
    precision: str = "default",
) -> jax.Array:
    """(num_nodes, F, num_bins, 3) float32 — same contract as
    ``ops.histogram.build_histograms``. Raises ValueError when K exceeds
    the VMEM budget (callers gate on :func:`pick_bw`)."""
    n, f = bins.shape
    k = num_nodes * num_bins
    if bw is None:
        bw = pick_bw(k)
    if not bw:
        raise ValueError(
            f"histogram K={k} too large for the Pallas VMEM budget; "
            "use the XLA fallback"
        )

    block_n = _SUBLANES * bw
    data = jnp.stack(
        [grad.astype(jnp.float32), hess.astype(jnp.float32), count.astype(jnp.float32)],
        axis=-1,
    )  # (N, 3)
    ids = bins.astype(jnp.int32) + (node.astype(jnp.int32) * num_bins)[:, None]

    pad = (-n) % block_n
    if pad:
        # Padding rows carry zero data, so their one-hot contribution is 0.
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
        data = jnp.pad(data, ((0, pad), (0, 0)))
    n_pad = n + pad
    tiles = n_pad // block_n

    ids3 = ids.T.reshape(f, tiles * _SUBLANES, bw)
    data3 = data.reshape(tiles * _SUBLANES, bw, 3)

    prec = lax.Precision.HIGHEST if precision == "highest" else None
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bw=bw, k=k, precision=prec),
        grid=(f, tiles),
        in_specs=[
            pl.BlockSpec((1, _SUBLANES, bw), lambda j, t: (j, t, 0)),
            # Trailing dim 3 = the packed (g, h, 1) stat triple; Mosaic pads
            # the lane axis to 128 and the deliberate waste is the measured
            # win over splitting stats into three aligned operands.
            pl.BlockSpec((_SUBLANES, bw, 3), lambda j, t: (t, 0, 0)),  # graftlint: disable=pallas-tile-alignment
        ],
        out_specs=pl.BlockSpec((1, k, 3), lambda j, t: (j, 0, 0)),  # graftlint: disable=pallas-tile-alignment
        out_shape=jax.ShapeDtypeStruct((f, k, 3), jnp.float32),
        interpret=interpret,
    )(ids3, data3)
    return out.reshape(f, num_nodes, num_bins, 3).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# Fused bin + scatter-add pass: the U contraction without the U.
# ---------------------------------------------------------------------------

_SCATTER_TN = 512  # rows per N-tile (lane-dim block of the bins stream)
_SCATTER_VMEM = 24 << 20


def bin_scatter_fits_vmem(k_pad: int, num_features: int, tn: int = _SCATTER_TN) -> bool:
    """VMEM gate for the fused bin+scatter pass: the per-tile one-hot
    scratch (k_pad x tn s8), the resident accumulator block (k_pad x 128,
    <= 4 B), the double-buffered bins tiles (F x tn s32) and the panel all
    have to sit inside the ~24 MB working budget."""
    f_pad = -(-max(num_features, 1) // _SUBLANES) * _SUBLANES
    return (
        k_pad * (tn + 4 * 128) + 2 * f_pad * tn * 4 + 8 * tn * 4
    ) <= _SCATTER_VMEM


def _bin_scatter_kernel(
    ids_ref, aux_ref, out_ref, u_scr, *, k: int, spec, quant: bool, tn: int
):
    """One N-tile of the fused pass. Reads the raw binned rows (F x tn s32
    — F bytes-per-row-class traffic instead of the K_pad-byte one-hot
    re-stream of the resident-U pass), rebuilds the packed one-hot tile in
    a VMEM scratch (per-feature iota compare at each feature's static
    packed offset — the "bin" half), and scatter-adds it into the
    VMEM-resident accumulator block through one MXU contraction against
    the node-keyed stat panel (the "scatter-add" half: on TPU a keyed
    scatter IS a one-hot matmul). The accumulator block never leaves VMEM
    until the last tile, and on the quantized path it carries the narrow
    integer dtype picked by ``histogram_acc_dtype``."""
    j2 = lax.broadcasted_iota(jnp.int32, (128, tn), 0)
    leaf = (j2 % k).astype(jnp.float32)
    sidx = j2 // k
    g, h, c = aux_ref[0:1, :], aux_ref[1:2, :], aux_ref[2:3, :]
    nodev = aux_ref[3:4, :]
    val = jnp.where(sidx == 0, g, jnp.where(sidx == 1, h, c))
    panel = jnp.where((nodev == leaf) & (j2 < 3 * k), val, 0.0)  # (128, tn)

    # Bin: packed one-hot tile, one static-offset compare block per
    # feature (row ranges are the USpec layout, so bins >= width match
    # nothing — identical semantics to build_u's local-id compare).
    for j, (off, w) in enumerate(zip(spec.offsets, spec.widths)):
        local = lax.broadcasted_iota(jnp.int32, (w, tn), 0)
        u_scr[off : off + w, :] = (ids_ref[j : j + 1, :] == local).astype(
            jnp.int8
        )
    if spec.k < spec.k_pad:
        u_scr[spec.k :, :] = jnp.zeros((spec.k_pad - spec.k, tn), jnp.int8)

    if quant:
        acc = lax.dot_general(
            u_scr[...], panel.astype(jnp.int8),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    else:
        acc = lax.dot_general(
            u_scr[...].astype(jnp.bfloat16), panel.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += acc.astype(out_ref.dtype)


def build_histograms_bin_scatter(
    bins: jax.Array,  # (N, F) integer bin indices (ORIGINAL layout, no U)
    grad: jax.Array,  # (N,) — ignored when stats is given
    hess: jax.Array,
    count: jax.Array,
    node: jax.Array,  # (N,) int32; out-of-range => row contributes nothing
    num_nodes: int,
    spec,  # ops.u_histogram.USpec (packed row layout)
    *,
    stats=None,  # (3, N) bf16 stat rows, or (stats_i8, scales) quant tuple
    dequant: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Fused bin+scatter-add histogram pass — same contract as
    ``ops.u_histogram.build_histograms_u`` but fed by the RAW binned rows:
    per row the pass streams 4F bytes of bins + 32 bytes of stats instead
    of the K_pad-byte one-hot column of the resident-U formulation (at the
    bench hot shape: 144 B/row vs ~7 KB/row), trading that HBM saving for
    the in-VMEM one-hot rebuild each tile. The A/B against the MXU U-path
    (``benchmarks/hist_u_ab.py``) decides which side of that trade the
    current chip lands on; the pass exists so the answer is measurable.

    Quant path: s8 x s8 MXU scatter into a VMEM accumulator of the narrow
    ``histogram_acc_dtype`` width (int16 when the whole-pass 127 * N bound
    proves it overflow-free, int32 otherwise — deterministic promotion,
    never a runtime saturation). ``dequant=False`` returns the spec-space
    integer histogram for exact sibling subtraction, as in
    ``build_histograms_u``."""
    from mmlspark_tpu.ops.u_histogram import (
        _expand_packed,
        histogram_acc_dtype,
        stat_rows,
    )

    scales = None
    if isinstance(stats, tuple):
        stats, scales = stats
    if 3 * num_nodes > 128:
        raise ValueError(f"panel width 3*{num_nodes} exceeds one lane group")
    k = num_nodes
    n, f = bins.shape
    if not bin_scatter_fits_vmem(spec.k_pad, f):
        raise ValueError(
            f"bin+scatter tile k_pad={spec.k_pad} too large for the VMEM "
            "budget; use the U or compare-built paths"
        )
    if stats is None:
        stats = stat_rows(grad, hess, count)
    quant = scales is not None

    tn = _SCATTER_TN
    pad = (-n) % tn
    f_pad = -(-f // _SUBLANES) * _SUBLANES
    ids_t = bins.astype(jnp.int32).T  # (F, N)
    ids_t = jnp.pad(ids_t, ((0, f_pad - f), (0, pad)), constant_values=-1)
    aux = jnp.concatenate(
        [
            stats.astype(jnp.float32),  # quantized values are small ints
            node.astype(jnp.float32)[None, :],
            jnp.zeros((4, n), jnp.float32),
        ]
    )
    if pad:
        aux = jnp.pad(aux, ((0, 0), (0, pad)))
        aux = aux.at[3, n:].set(-1.0)  # pad rows match no leaf
    n_pad = n + pad

    acc_dtype = histogram_acc_dtype(n, quant)
    packed = pl.pallas_call(
        functools.partial(
            _bin_scatter_kernel, k=k, spec=spec, quant=quant, tn=tn
        ),
        grid=(n_pad // tn,),
        in_specs=[
            pl.BlockSpec((f_pad, tn), lambda i: (0, i)),
            pl.BlockSpec((8, tn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((spec.k_pad, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((spec.k_pad, 128), acc_dtype),
        scratch_shapes=[pltpu.VMEM((spec.k_pad, tn), jnp.int8)],
        interpret=interpret,
    )(ids_t, aux)
    packed = packed[:, : 3 * k]
    if quant and dequant:
        packed = packed.astype(jnp.int32)
    return _expand_packed(packed, scales, spec, k, dequant=dequant)
