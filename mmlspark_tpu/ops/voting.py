"""Voting-parallel histogram reduction (PV-Tree) over the mesh data axis.

LightGBM's ``tree_learner=voting_parallel`` (reference
``lightgbm/LightGBMParams.scala:20-24``, ``topK`` param) cuts the histogram
allreduce from F features to ~topK: each worker *votes* for its locally best
features, the vote is aggregated, and only the winning features' histograms
are globally reduced. The data-parallel reduction moves ``k·F·B·3`` floats
per level; voting moves ``k·F`` vote counts plus ``k·topK·B·3`` floats —
a ~F/topK communication cut when F >> topK.

TPU-native formulation: an explicit ``shard_map`` over the mesh ``data``
axis replaces the worker socket mesh. Local histograms never leave the
device; ``lax.psum`` carries only votes, per-node totals, and the gathered
top-K feature histograms over ICI. The returned histogram has the full
(node, F, B, 3) shape with non-selected features zeroed, so the split
search works unchanged — their zero stats fail the ``min_data_in_leaf``
validity mask and can never win a split.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.core.device import on_tpu
from mmlspark_tpu.ops.histogram import build_histograms


def _local_feature_gains(hist: jax.Array, l2: float = 1e-3) -> jax.Array:
    """(k, F) best split gain per feature from a LOCAL histogram — the
    voting score. Unregularized apart from a small l2 floor; only the
    *ranking* matters."""
    totals = hist.sum(axis=2)  # (k, F, 3)
    g_tot, h_tot = totals[..., 0], totals[..., 1]
    cum = jnp.cumsum(hist, axis=2)
    gl, hl = cum[..., 0], cum[..., 1]
    gr = g_tot[..., None] - gl
    hr = h_tot[..., None] - hl
    gain = gl * gl / (hl + l2) + gr * gr / (hr + l2)  # (k, F, B)
    return gain.max(axis=2)


def build_histograms_voting(
    bins: jax.Array,  # (N, F) int32
    grad: jax.Array,
    hess: jax.Array,
    count: jax.Array,
    node: jax.Array,
    num_nodes: int,
    num_bins: int,
    *,
    top_k: int = 20,
    mesh=None,
    method: Optional[str] = None,
    feature_mask: Optional[jax.Array] = None,  # (F,) 0/1
) -> Tuple[jax.Array, jax.Array]:
    """Returns (hist (k, F, B, 3) with non-winning features zeroed,
    totals (k, 3) exact). Falls back to the full reduction when unsharded.
    ``feature_mask`` (featureFraction subsampling) excludes features from the
    vote so the K reduced histograms are spent only on splittable features."""
    f = bins.shape[1]
    k_sel = min(top_k, f)

    meshed = mesh is not None and int(mesh.shape.get("data", 1)) > 1
    if not meshed or k_sel == f:
        m = method
        if meshed and m in (None, "pallas"):
            # Under jit with row-sharded inputs pallas_call has no GSPMD
            # partitioning rule — keep the shardable XLA formulations.
            m = "onehot" if on_tpu() else "segment"
        hist = build_histograms(
            bins, grad, hess, count, node, num_nodes, num_bins, method=m,
            # row chunking must stay off when the N axis is GSPMD-sharded
            # (see build_histograms); the shard_map branch below chunks its
            # LOCAL shards safely
            chunk_rows=not meshed,
        )
        return hist, hist[:, 0, :, :].sum(axis=1)

    def local_fn(bins_l, grad_l, hess_l, count_l, node_l, fmask):
        h = build_histograms(
            bins_l, grad_l, hess_l, count_l, node_l, num_nodes, num_bins,
            method=method,
        )  # LOCAL (k, F, B, 3)
        totals = lax.psum(h[:, 0, :, :].sum(axis=1), "data")  # (k, 3) exact

        # Local vote: top-K features per node by local split gain; masked-out
        # features (featureFraction) may not spend vote slots.
        gains = _local_feature_gains(h)  # (k, F)
        gains = jnp.where(fmask[None, :] > 0, gains, -jnp.inf)
        _, local_top = lax.top_k(gains, k_sel)  # (k, K)
        votes = jnp.zeros((num_nodes, f), dtype=jnp.int32)
        votes = jax.vmap(lambda v, idx: v.at[idx].add(1))(votes, local_top)
        votes = lax.psum(votes, "data")

        # Global winners per node (ties break toward lower feature index).
        score = votes * (f + 1) - jnp.arange(f, dtype=jnp.int32)[None, :]
        _, sel = lax.top_k(score, k_sel)  # (k, K)

        # Reduce ONLY the winners' histograms — the communication saving.
        h_sel = jnp.take_along_axis(h, sel[:, :, None, None], axis=1)
        h_sel = lax.psum(h_sel, "data")  # (k, K, B, 3)

        full = jnp.zeros_like(h)
        full = jax.vmap(lambda fu, si, hs: fu.at[si].set(hs))(full, sel, h_sel)
        return full, totals

    sharded = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P("data", None),
            P("data"),
            P("data"),
            P("data"),
            P("data"),
            P(),  # feature mask replicated
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )
    if feature_mask is None:
        feature_mask = jnp.ones(f, dtype=jnp.float32)
    return sharded(bins, grad, hess, count, node, feature_mask)
