"""Plain reference for a binary-logloss GBDT: follow a fitted forest tree by
tree in float64 and say what each leaf's value has to be.

Imports nothing of the program. Given the training rows and the trees a fit
produced (split feature, float32 threshold, children, leaf flags, leaf
values), it recomputes from the raw data what LightGBM's algorithm fixes
once the tree's shape is given: the margins after trees 0..t-1, the
gradient pair of every row (g = p - y, h = p(1 - p)), the rows each leaf
holds (x <= threshold goes left, compared on the float32 grid the
configuration states) and so each leaf's value -lr * sum(g) / sum(h). A
histogram summed in lower precision, rows left out of it, a margin that was
not updated, a threshold that does not match the routing, or a leaf changed
after the fact all show as a gap between the fitted value and this one.

What the tree's shape does not fix, the reference searches itself: over the
large nodes of tree 0 it finds the best split there is, exactly, and says by
how much the fit's split falls short of it (``split_gains``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

HESS_FLOOR = 1e-16  # LightGBM's binary objective floors p(1-p) here


@dataclasses.dataclass(frozen=True)
class Forest:
    """Arrays of shape (trees, slots); slot 0 is each tree's root."""

    feature: np.ndarray
    threshold: np.ndarray  # float32: the comparison grid
    left: np.ndarray
    right: np.ndarray
    is_leaf: np.ndarray
    value: np.ndarray
    init_score: float


def sigmoid(m: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-m))


def grad_hess(margin: np.ndarray, y: np.ndarray):
    p = sigmoid(margin)
    return p - y, np.maximum(p * (1.0 - p), HESS_FLOOR)


def route(xt: np.ndarray, forest: Forest, t: int) -> np.ndarray:
    """Leaf slot of every row under tree ``t``. ``xt`` is (features, rows)
    float32, so one node's comparison reads one contiguous column."""
    n = xt.shape[1]
    leaf = np.empty(n, np.int32)
    todo = [(0, None)]
    while todo:
        node, idx = todo.pop()
        if forest.is_leaf[t, node]:
            leaf[slice(None) if idx is None else idx] = node
            continue
        col = xt[forest.feature[t, node]]
        vals = col if idx is None else col[idx]
        go_left = vals <= forest.threshold[t, node]
        rows = np.flatnonzero(go_left) if idx is None else idx[go_left]
        rest = np.flatnonzero(~go_left) if idx is None else idx[~go_left]
        todo.append((int(forest.left[t, node]), rows))
        todo.append((int(forest.right[t, node]), rest))
    return leaf


def leaf_sums(xt, y, forest: Forest) -> np.ndarray:
    """(trees, slots, 3): per tree and slot the sum of g, the sum of h and
    the row count, the margins moved on by the forest's own leaf values (the
    fit's later trees were grown on those). ``xt`` is (features, rows)."""
    trees, slots = forest.value.shape
    sums = np.zeros((trees, slots, 3))
    margin = np.full(y.size, forest.init_score, np.float64)
    for t in range(trees):
        g, h = grad_hess(margin, y)
        leaf = route(xt, forest, t)
        sums[t, :, 0] = np.bincount(leaf, g, slots)
        sums[t, :, 1] = np.bincount(leaf, h, slots)
        sums[t, :, 2] = np.bincount(leaf, minlength=slots)
        margin += forest.value[t].astype(np.float64)[leaf]
    return sums


def leaf_values(sums: np.ndarray, learning_rate: float) -> np.ndarray:
    with np.errstate(divide="ignore", invalid="ignore"):
        return -learning_rate * sums[..., 0] / sums[..., 1]


def leaf_gaps(forest: Forest, sums: np.ndarray, learning_rate: float):
    """Gap of every reachable leaf's fitted value from the reference's,
    measured against the reference's value or the tree's median leaf,
    whichever is larger (late trees have leaves whose gradients cancel).
    Returns the flat array of gaps over all trees."""
    want = leaf_values(sums, learning_rate)
    gaps = []
    for t in range(forest.value.shape[0]):
        live = forest.is_leaf[t] & (sums[t, :, 2] > 0)
        if not live.any():
            continue
        w = np.abs(want[t, live])
        scale = np.maximum(w, np.median(w))
        gaps.append(np.abs(forest.value[t, live] - want[t, live]) / scale)
    return np.concatenate(gaps) if gaps else np.zeros(0)


def margins(xt: np.ndarray, forest: Forest, values=None) -> np.ndarray:
    """Raw margins of rows under the whole forest, float64 sums. ``xt`` is
    (features, rows)."""
    out = np.full(xt.shape[1], forest.init_score, np.float64)
    vals = forest.value if values is None else values
    for t in range(forest.value.shape[0]):
        out += vals[t].astype(np.float64)[route(xt, forest, t)]
    return out


def _gain(gl, hl, g, h):
    """LightGBM's split gain at lambda_l2 = 0, the parent's term g*g/h
    not yet taken off."""
    return gl * gl / hl + (g - gl) ** 2 / (h - hl)


def split_gains(xt, y, forest: Forest, min_rows: int, min_data: int, threads: int = 8):
    """(nodes, 2): for every internal node of tree 0 that holds ``min_rows``
    rows or more, the gain of the fit's split and the gain of the best split
    there is. The reference searches every threshold of every feature over
    the node's own rows (exact greedy, float64, both children at least
    ``min_data`` rows); over a small node such a search finds gain in the
    noise, hence ``min_rows``. Tree 0's gradients come from the init score
    alone, so the search is the reference's own from the raw rows on: it
    takes the node's rows from the fit and nothing else. A search over fewer
    features or coarser bins than the configuration states shows here and
    nowhere else. One thread a feature: numpy's sort, take and cumsum run
    outside the interpreter's lock."""
    from concurrent.futures import ThreadPoolExecutor

    n = xt.shape[1]
    g, h = grad_hess(np.full(n, forest.init_score, np.float64), y)
    least = max(min_data, 1)

    def best_of(col, idx, G, H):
        rows = idx[np.argsort(col[idx])]
        xs = col[rows]
        gl, hl = np.cumsum(g[rows])[:-1], np.cumsum(h[rows])[:-1]
        ok = xs[:-1] < xs[1:]  # a threshold separates two values
        ok[: least - 1] = False
        ok[ok.size - least + 1 :] = False
        return float(_gain(gl[ok], hl[ok], G, H).max()) if ok.any() else 0.0

    out, todo = [], [(0, np.arange(n))]
    with ThreadPoolExecutor(threads) as pool:
        while todo:
            node, idx = todo.pop()
            if forest.is_leaf[0, node] or idx.size < min_rows:
                continue  # a small node's subtree holds only smaller ones
            G, H = g[idx].sum(), h[idx].sum()
            parent = G * G / H
            best = max(pool.map(lambda col: best_of(col, idx, G, H), xt))
            left = xt[forest.feature[0, node], idx] <= forest.threshold[0, node]
            got = _gain(g[idx[left]].sum(), h[idx[left]].sum(), G, H)
            if best > parent:
                out.append((got - parent, best - parent))
            todo.append((int(forest.left[0, node]), idx[left]))
            todo.append((int(forest.right[0, node]), idx[~left]))
    return np.asarray(out).reshape(-1, 2)


def bfloat16(a: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even) and back: the predict
    control's precision."""
    bits = np.asarray(a, np.float32).view(np.uint32)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return rounded.astype(np.uint32).view(np.float32)
