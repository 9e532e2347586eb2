"""Gradient/hessian/count histogram building over (node, feature, bin).

The hot op of GBDT training — the TPU replacement for LightGBM's native
per-leaf histogram construction (``LGBM_BoosterUpdateOneIter``'s inner loop,
reference ``lightgbm/TrainUtils.scala:220-315``). Two implementations:

- ``segment``: flat ``segment_sum`` scatter-add. Fast on CPU; on TPU XLA
  lowers it to serialized scatters, so it is the fallback path.
- ``onehot``: per-feature one-hot matmul ``one_hot(node*B + bin) @ [g,h,c]``.
  Dense MXU work with static shapes — the TPU-first formulation: ~N*K*3
  FLOPs per feature beat sparse scatter on the systolic array.
- ``pallas``: hand-written kernel fusing one-hot construction with the
  reduction in VMEM (``ops/pallas_histogram.py``); falls back to
  ``onehot`` when K exceeds its VMEM budget. A/B numbers and the roofline
  argument live in ``docs/perf_histogram.md``.

Distribution: callers shard rows across the mesh ``data`` axis; the
histogram is a sum over rows, so under jit XLA inserts the cross-device
``all-reduce`` automatically — this *is* the ``data_parallel`` histogram
allreduce that LightGBM runs over its socket mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from mmlspark_tpu.core.device import on_tpu


def _default_method() -> str:
    # pallas (VMEM-fused one-hot) measures 1.6x faster than the XLA one-hot
    # at the leafwise hot shape on v5e (docs/perf_histogram.md); it falls
    # back to onehot itself when K exceeds its VMEM budget.
    return "pallas" if on_tpu() else "segment"


def build_histograms(
    bins: jax.Array,  # (N, F) integer bin indices
    grad: jax.Array,  # (N,)
    hess: jax.Array,  # (N,)
    count: jax.Array,  # (N,) sample weight-of-presence (0/1 under bagging)
    node: jax.Array,  # (N,) int32 local node index in [0, num_nodes)
    num_nodes: int,
    num_bins: int,
    method: Optional[str] = None,
    chunk_rows: bool = True,
) -> jax.Array:
    """Returns (num_nodes, F, num_bins, 3) float32: per-cell [sum_g, sum_h, count].

    ``chunk_rows=False`` disables the bounded-transient row chunking of the
    onehot/panel formulations — required under a mesh, where padding and
    scan-slicing the ROW-SHARDED dimension would force GSPMD to all-gather
    the full matrix per pass (each device's shard is 1/devices of N there,
    so the unchunked transient is already bounded)."""
    method = method or _default_method()
    n, f = bins.shape
    bins = bins.astype(jnp.int32)
    node = node.astype(jnp.int32)
    data = jnp.stack(
        [grad.astype(jnp.float32), hess.astype(jnp.float32), count.astype(jnp.float32)],
        axis=-1,
    )  # (N, 3)

    if method == "segment":
        # ids[i, j] = ((node_i * F) + j) * B + bins[i, j]
        ids = (node[:, None] * f + jnp.arange(f, dtype=jnp.int32)[None, :]) * num_bins + bins
        flat_ids = ids.reshape(-1)
        flat_data = jnp.broadcast_to(data[:, None, :], (n, f, 3)).reshape(-1, 3)
        seg = jax.ops.segment_sum(
            flat_data, flat_ids, num_segments=num_nodes * f * num_bins
        )
        return seg.reshape(num_nodes, f, num_bins, 3)

    if method == "pallas":
        from mmlspark_tpu.ops.pallas_histogram import (
            build_histograms_pallas,
            build_histograms_panel_pallas,
            panel_fits,
            pick_bw,
        )

        # Multi-node passes route to the panel kernel: its one-hot build —
        # the VPU-bound resource — is independent of the node count (the
        # node key rides in MXU lane padding), so k nodes cost ~one.
        if num_nodes > 1 and panel_fits(num_nodes, num_bins):
            return build_histograms_panel_pallas(
                bins, grad, hess, count, node, num_nodes, num_bins
            )
        k = num_nodes * num_bins
        # Below one lane group the XLA one-hot wins (measured 6x at K=64,
        # docs/perf_histogram.md); above the VMEM budget pallas refuses.
        if k >= 128 and pick_bw(k):
            return build_histograms_pallas(
                bins, grad, hess, count, node, num_nodes, num_bins
            )
        method = "onehot"

    if method == "panel" or (method == "onehot" and num_nodes > 1 and 3 * num_nodes <= 128):
        # XLA panel formulation (mesh-compatible — plain jnp, so GSPMD can
        # row-shard it and insert the allreduce): bin-only one-hot against a
        # node-keyed (N, 3k) data panel. Rows with node outside [0, k) get a
        # zero panel row, which callers use as the in-leaf mask.
        # The one-hot is built in bounded ROW CHUNKS: an (N, B) f32 one-hot
        # at multi-million rows is gigabytes of transient per scan step and
        # crashes the TPU worker (this is the >1M fallback path — the
        # precomputed-U formulation gates off on its own HBM budget there).
        from mmlspark_tpu.ops.pallas_histogram import build_node_panel

        k = num_nodes
        panel = build_node_panel(grad, hess, count, node, k)
        if not chunk_rows:
            def per_feature_whole(_, feat_col):
                oh = jax.nn.one_hot(feat_col, num_bins, dtype=panel.dtype)
                return None, oh.T @ panel  # (B, 3k)

            _, hists = lax.scan(per_feature_whole, None, bins.T)
            return hists.reshape(f, num_bins, 3, k).transpose(3, 0, 1, 2)
        chunk = max(1, min(n, (64 << 20) // max(4 * num_bins, 1)))
        pad = (-n) % chunk
        bins_p = jnp.pad(bins, ((0, pad), (0, 0))) if pad else bins
        panel_p = jnp.pad(panel, ((0, pad), (0, 0))) if pad else panel
        r = (n + pad) // chunk
        bins_r = bins_p.reshape(r, chunk, f).transpose(2, 0, 1)  # (F, R, chunk)
        panel_r = panel_p.reshape(r, chunk, 3 * k)

        def per_feature_panel(_, feat_rows):  # (R, chunk)
            def per_chunk(acc, rc):
                fc, pl = rc  # padded rows carry zero panel rows => no-op
                oh = jax.nn.one_hot(fc, num_bins, dtype=panel.dtype)
                return acc + oh.T @ pl, None

            h0 = jnp.zeros((num_bins, 3 * k), panel.dtype)
            h, _ = lax.scan(per_chunk, h0, (feat_rows, panel_r))
            return None, h

        _, hists = lax.scan(per_feature_panel, None, bins_r)  # (F, B, 3k)
        return hists.reshape(f, num_bins, 3, k).transpose(3, 0, 1, 2)

    if method == "onehot":
        k = num_nodes * num_bins
        base = node * num_bins  # (N,)
        if not chunk_rows:
            def per_feature_whole(_, feat_col):
                oh = jax.nn.one_hot(base + feat_col, k, dtype=jnp.float32)
                return None, oh.T @ data  # (K, 3) — MXU matmul

            _, hists = lax.scan(per_feature_whole, None, bins.T)
            return hists.reshape(f, num_nodes, num_bins, 3).transpose(1, 0, 2, 3)
        chunk = max(1, min(n, (64 << 20) // max(4 * k, 1)))
        pad = (-n) % chunk
        bins_p = jnp.pad(bins, ((0, pad), (0, 0))) if pad else bins
        base_p = jnp.pad(base, (0, pad)) if pad else base
        data_p = jnp.pad(data, ((0, pad), (0, 0))) if pad else data
        r = (n + pad) // chunk
        bins_r = bins_p.reshape(r, chunk, f).transpose(2, 0, 1)  # (F, R, chunk)
        base_r = base_p.reshape(r, chunk)
        data_r = data_p.reshape(r, chunk, 3)

        def per_feature(_, feat_rows):  # (R, chunk)
            def per_chunk(acc, rc):
                fc, bc, dc = rc  # padded rows carry zero data rows => no-op
                oh = jax.nn.one_hot(bc + fc, k, dtype=jnp.float32)
                return acc + oh.T @ dc, None

            h0 = jnp.zeros((k, 3), jnp.float32)
            h, _ = lax.scan(per_chunk, h0, (feat_rows, base_r, data_r))
            return None, h

        _, hists = lax.scan(per_feature, None, bins_r)  # (F, K, 3)
        return hists.reshape(f, num_nodes, num_bins, 3).transpose(1, 0, 2, 3)

    raise ValueError(f"unknown histogram method {method!r}")
