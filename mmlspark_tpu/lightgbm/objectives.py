"""Training objectives and evaluation metrics.

Mirrors the objective set accepted by the reference's param surface
(``lightgbm/LightGBMParams.scala``, ``lightgbm/TrainParams.scala``:
binary, multiclass, regression/l2, l1, huber, quantile, poisson, tweedie)
with gradients/hessians as jitted closed forms. Eval-metric direction
handling (auc/ndcg/map maximize, losses minimize) matches
``TrainUtils.scala:276-308`` early-stopping semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Objective:
    name: str
    num_outputs_fn: Callable[[int], int]  # num_classes -> margin columns
    # (margins (N,C), y (N,), w (N,)) -> grad (N,C), hess (N,C)
    grad_hess: Callable[..., Tuple[jax.Array, jax.Array]]
    # (y, num_classes, w) -> init margin (C,)
    init_score: Callable[..., np.ndarray]
    default_metric: str
    # Distinguishes data-specific objective INSTANCES sharing a name in the
    # jitted-program cache (core.device.cached_program keys on this): the registry
    # singletons use None; per-fit objectives (lambdarank closes over the
    # query-group structure) must carry a unique token or a later fit with
    # identical TrainOptions silently reuses the first fit's closure.
    cache_token: Any = None


def _sigmoid(x):
    return jax.nn.sigmoid(x)


# -- binary ------------------------------------------------------------------

def _binary_grad_hess(margins, y, w, **kw):
    p = _sigmoid(margins[:, 0])
    g = (p - y) * w
    h = jnp.maximum(p * (1.0 - p), 1e-16) * w
    return g[:, None], h[:, None]


def _binary_init(y, num_classes, w):
    pos = float(np.sum(y * w))
    neg = float(np.sum(w)) - pos
    pos, neg = max(pos, 1e-12), max(neg, 1e-12)
    return np.array([np.log(pos / neg)], dtype=np.float32)


# -- multiclass softmax ------------------------------------------------------

def _multiclass_grad_hess(margins, y, w, num_classes=2, **kw):
    p = jax.nn.softmax(margins, axis=-1)  # (N, C)
    onehot = jax.nn.one_hot(y.astype(jnp.int32), num_classes)
    g = (p - onehot) * w[:, None]
    h = jnp.maximum(2.0 * p * (1.0 - p), 1e-16) * w[:, None]
    return g, h


def _multiclass_init(y, num_classes, w):
    counts = np.array(
        [np.sum(w[np.asarray(y) == c]) for c in range(num_classes)], dtype=np.float64
    )
    probs = np.maximum(counts / max(counts.sum(), 1e-12), 1e-12)
    return np.log(probs).astype(np.float32)


# -- regression family -------------------------------------------------------

def _l2_grad_hess(margins, y, w, **kw):
    g = (margins[:, 0] - y) * w
    return g[:, None], w[:, None] * jnp.ones_like(g)[:, None]


def _l2_init(y, num_classes, w):
    return np.array([np.average(y, weights=w)], dtype=np.float32)


def _l1_grad_hess(margins, y, w, **kw):
    g = jnp.sign(margins[:, 0] - y) * w
    return g[:, None], w[:, None] * jnp.ones_like(g)[:, None]


def _huber_grad_hess(margins, y, w, alpha=0.9, **kw):
    d = margins[:, 0] - y
    g = jnp.clip(d, -alpha, alpha) * w
    return g[:, None], w[:, None] * jnp.ones_like(g)[:, None]


def _quantile_grad_hess(margins, y, w, alpha=0.9, **kw):
    d = margins[:, 0] - y
    g = jnp.where(d >= 0, 1.0 - alpha, -alpha) * w
    return g[:, None], w[:, None] * jnp.ones_like(g)[:, None]


def _poisson_grad_hess(margins, y, w, **kw):
    mu = jnp.exp(margins[:, 0])
    g = (mu - y) * w
    h = jnp.maximum(mu, 1e-16) * w
    return g[:, None], h[:, None]


def _poisson_init(y, num_classes, w):
    return np.array([np.log(max(np.average(y, weights=w), 1e-12))], dtype=np.float32)


def _tweedie_grad_hess(margins, y, w, tweedie_variance_power=1.5, **kw):
    rho = tweedie_variance_power
    m = margins[:, 0]
    a = y * jnp.exp((1.0 - rho) * m)
    b = jnp.exp((2.0 - rho) * m)
    g = (-a + b) * w
    h = jnp.maximum(-a * (1.0 - rho) + b * (2.0 - rho), 1e-16) * w
    return g[:, None], h[:, None]


OBJECTIVES: Dict[str, Objective] = {
    "binary": Objective("binary", lambda c: 1, _binary_grad_hess, _binary_init, "auc"),
    "multiclass": Objective(
        "multiclass", lambda c: c, _multiclass_grad_hess, _multiclass_init, "multi_logloss"
    ),
    "regression": Objective("regression", lambda c: 1, _l2_grad_hess, _l2_init, "l2"),
    "regression_l1": Objective("regression_l1", lambda c: 1, _l1_grad_hess, _l2_init, "l1"),
    "huber": Objective("huber", lambda c: 1, _huber_grad_hess, _l2_init, "l2"),
    "quantile": Objective("quantile", lambda c: 1, _quantile_grad_hess, _l2_init, "quantile"),
    "poisson": Objective("poisson", lambda c: 1, _poisson_grad_hess, _poisson_init, "poisson"),
    "tweedie": Objective("tweedie", lambda c: 1, _tweedie_grad_hess, _poisson_init, "tweedie"),
}

# LightGBM objective aliases (TrainParams.scala objective strings).
_ALIASES = {"l2": "regression", "mean_squared_error": "regression", "mse": "regression",
            "l1": "regression_l1", "mae": "regression_l1", "lambdarank": "lambdarank"}


def get_objective(name: str) -> Objective:
    name = _ALIASES.get(name, name)
    if name not in OBJECTIVES:
        raise ValueError(f"unknown objective {name!r}; known: {sorted(OBJECTIVES)}")
    return OBJECTIVES[name]


# ---------------------------------------------------------------------------
# Metrics (host-side numpy; validation sets are small relative to train)
# ---------------------------------------------------------------------------

def auc(y: np.ndarray, score: np.ndarray, w: np.ndarray) -> float:
    order = np.argsort(score, kind="stable")
    y, w = np.asarray(y, dtype=np.float64)[order], np.asarray(w, dtype=np.float64)[order]
    pos_w = y * w
    neg_w = (1.0 - y) * w
    cum_neg = np.cumsum(neg_w)
    total_pos, total_neg = pos_w.sum(), neg_w.sum()
    if total_pos == 0 or total_neg == 0:
        return 0.5
    # rank-sum with tie correction via averaging over equal-score groups
    auc_sum = 0.0
    i = 0
    n = len(y)
    score = score[order]
    prev_cum_neg = 0.0
    while i < n:
        j = i
        while j < n and score[j] == score[i]:
            j += 1
        grp_pos = pos_w[i:j].sum()
        grp_neg = neg_w[i:j].sum()
        auc_sum += grp_pos * (prev_cum_neg + grp_neg / 2.0)
        prev_cum_neg += grp_neg
        i = j
    return float(auc_sum / (total_pos * total_neg))


def _sigmoid_np(x):
    return 1.0 / (1.0 + np.exp(-x))


def binary_logloss(y, margin, w):
    p = np.clip(_sigmoid_np(margin), 1e-15, 1 - 1e-15)
    return float(np.average(-(y * np.log(p) + (1 - y) * np.log(1 - p)), weights=w))


def multi_logloss(y, margins, w):
    m = margins - margins.max(axis=1, keepdims=True)
    logp = m - np.log(np.exp(m).sum(axis=1, keepdims=True))
    ll = logp[np.arange(len(y)), np.asarray(y, dtype=int)]
    return float(np.average(-ll, weights=w))


def multi_error(y, margins, w):
    pred = margins.argmax(axis=1)
    return float(np.average(pred != np.asarray(y, dtype=int), weights=w))


def l2_loss(y, pred, w):
    return float(np.average((pred - y) ** 2, weights=w))


def rmse(y, pred, w):
    return float(np.sqrt(l2_loss(y, pred, w)))


def l1_loss(y, pred, w):
    return float(np.average(np.abs(pred - y), weights=w))


def quantile_loss(y, pred, w, alpha=0.9):
    d = y - pred
    return float(np.average(np.maximum(alpha * d, (alpha - 1) * d), weights=w))


def binary_error(y, margin, w):
    return float(np.average((margin > 0) != (y > 0.5), weights=w))


#: metric name -> (fn(y, score_or_margin, w), higher_is_better)
METRICS = {
    "auc": (auc, True),
    "binary_logloss": (binary_logloss, False),
    "binary_error": (binary_error, False),
    "multi_logloss": (multi_logloss, False),
    "multi_error": (multi_error, False),
    "l2": (l2_loss, False),
    "mse": (l2_loss, False),
    "rmse": (rmse, False),
    "l1": (l1_loss, False),
    "mae": (l1_loss, False),
    "quantile": (quantile_loss, False),
    "poisson": (l2_loss, False),  # monitored via l2 on the response scale
    "tweedie": (l2_loss, False),
}


def metric_higher_is_better(name: str) -> bool:
    if name in METRICS:
        return METRICS[name][1]
    # ndcg@k / map@k style names maximize (TrainUtils.scala:283-287)
    return name.split("@")[0] in ("auc", "ndcg", "map")
