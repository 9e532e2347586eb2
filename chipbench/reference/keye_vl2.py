"""Plain reference for the language model of the ``KeyeVL2`` family (Kwai
Keye-VL-2.0-30B-A3B): whole token rows in, the last position's normalised
hidden state and logits out, with each layer's expert load, the keys its
selection kept over the row and the last position's kept keys.

Imports nothing of the program and uses no trick of its: float32 arithmetic
at ``highest`` matrix precision; the indexer's scores of a block of queries
over every key, the selection by ``lax.top_k`` over the scores a query sees,
and attention as dense scores masked to the kept keys, one block of queries
at a time (so that they fit beside the program's weights at 32,768 tokens);
the experts as ``reference/mla_moe`` runs them, each given the tokens that
chose it. It computes at the precision the configuration STATES, no finer:
the residual stream, every matrix product's inputs and the indexer's queries
and key are rounded to bfloat16 (``_bf``: ``lax.reduce_precision``, which no
compiler folds away), while norms, softmax, rotary, router scores, the
choice of experts, the indexer's scores and the selection stay float32. The
parameter tree is the program's; everything else is written from the
equations the configuration's file gives:

- ``h = E[token]``; a layer is ``a = h + Attn(RMSNorm_1(h))``, ``h' = a +
  MoE(RMSNorm_2(a))``;
- Attn: ``q = RMSNorm_q(x W_q)``, ``k = RMSNorm_k(x W_k)`` over each head,
  ``v = x W_v``, rotary positions on pairs ``(i, i + head/2)`` turned by
  ``position x rope_theta^(-2i/head)``. ``mrope_section`` splits the pairs
  into time, height and width; a text row's three ids are one position, so
  this is exactly the published three-part rotary;
- the indexer: ``qi = RoPE(x W_iq)`` (heads of ``d_I``), ``ki =
  RoPE(LayerNorm(x W_ik))``, rotary on the first half of each vector (pairs
  ``(i, i + d_I/4)``), ``w = x W_iw / sqrt(H_I d_I)``; ``I[t, s] = sum_j
  w[t, j] relu(qi[t, j] . ki[s])``; query ``t`` keeps the ``min(t + 1,
  topk)`` of ``s <= t`` with the largest ``I`` (``lax.top_k``: a tie to the
  lower position); softmax at ``1/sqrt(head)`` over the kept keys alone,
  query head ``h`` reading key/value head ``h // (heads / kv_heads)``; ``W_o``;
- MoE: router probabilities ``softmax(x W_r)`` over every expert, the
  ``num_experts_per_tok`` largest chosen, weights renormalised over them;
  SwiGLU experts; no shared expert;
- the final norm and the untied head at the last position.

Departures from the published model, all the program's too: the vision
tower and image positions are not built; the indexer follows DeepSeek-V3.2's
(the configuration's ``assumed``): its query from ``x``, the key's LayerNorm
with a weight and a bias, rotary on the first half; V3.2's Hadamard rotation
(orthogonal, so no product moves) and its float8 cache are left out; a score
of minus zero counts as zero.

``fault`` plants one departure, for the tests and for ``calibrate``:
``dense_attention`` (every key a query sees), ``select_by_attention`` (the
keys of the largest attention score summed over the heads),
``topk_halved``, ``indexer_without_relu``, ``indexer_without_weights`` (each
head's ``w`` the constant ``1 / sqrt(H_I d_I)``), ``future_key_visible``
(query ``t`` sees ``t + 1`` as well) and ``qk_norm_skipped``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference.afmoe import load_gaps, relative_gaps  # noqa: F401  (the comparison's numbers)

FAULTS = ("dense_attention", "select_by_attention", "topk_halved", "indexer_without_relu",
          "indexer_without_weights", "future_key_visible", "qk_norm_skipped")
QUERIES_AT_A_TIME = 128
TOKENS_AT_A_TIME = 1024  # of those that chose one expert
_ATTENTION = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "iq", "ik", "iw", "ik_norm", "ik_bias")


def _bf(x, mantissa=7):
    """Round to bfloat16 (8 exponent bits, 7 of mantissa), stay float32."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=mantissa)


def _norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, r). Pair ``i`` is ``(i, i + r/2)``, turned by
    ``position x theta^(-2i/r)``."""
    S, _, r = x.shape
    freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    angle = (jnp.arange(S, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


@functools.partial(jax.jit, static_argnames=("config", "fault"))
def _attend(stack, index, h, config, fault):
    """The attention half of layer ``index`` over one row: -> (``a = h +
    Attn(N1(h))``, the experts' input ``N2(a)``, both rounded; the keys the
    selection kept over the row; the last position's kept keys (S,) bool)."""
    config = dict(config)
    sa = dict(config["sa_config"])
    S = h.shape[0]
    H, KV, hd, eps = (config[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps"))
    HI, dI = sa["indexer_num_heads"], sa["indexer_head_dim"]
    topk = sa["topk"] // 2 if fault == "topk_halved" else sa["topk"]
    theta = float(config["rope_theta"])
    with jax.default_matmul_precision("highest"):
        p = {n: lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32) for n in _ATTENTION}
        x = _bf(_norm(h, p["norm1"], eps))
        q, k = (x @ p["wq"]).reshape(S, H, hd), (x @ p["wk"]).reshape(S, KV, hd)
        if fault != "qk_norm_skipped":
            q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)
        q, k, v = _bf(_rope(q, theta)), _bf(_rope(k, theta)), _bf(x @ p["wv"]).reshape(S, KV, hd)
        qi = (x @ p["iq"]).reshape(S, HI, dI)
        qi = _bf(jnp.concatenate([_rope(qi[..., : dI // 2], theta), qi[..., dI // 2:]], axis=-1))
        ki = x @ p["ik"]
        ki = ki - ki.mean(axis=-1, keepdims=True)
        ki = ki * lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + eps) * p["ik_norm"] + p["ik_bias"]
        ki = _bf(jnp.concatenate([_rope(ki[:, None, : dI // 2], theta)[:, 0], ki[:, dI // 2:]], axis=-1))
        w = (jnp.ones((S, HI)) if fault == "indexer_without_weights" else x @ p["iw"]) * (HI * dI) ** -0.5
        block = QUERIES_AT_A_TIME if S % QUERIES_AT_A_TIME == 0 else S
        reach = 1 if fault == "future_key_visible" else 0
        s = jnp.arange(S)[None, :]
        group = H // KV

        def some_queries(start):
            t = start + jnp.arange(block)[:, None]
            seen = s <= t + reach
            mine = lambda a: lax.dynamic_slice_in_dim(a, start, block, axis=0)
            scores = jnp.einsum("ckgd,skd->kgcs", mine(q).reshape(block, KV, group, hd), k) / np.sqrt(hd)
            r = jnp.einsum("cjd,sd->cjs", mine(qi), ki)
            if fault != "indexer_without_relu":
                r = jax.nn.relu(r)
            index = jnp.einsum("cj,cjs->cs", mine(w), r)
            if fault == "select_by_attention":
                index = scores.sum(axis=(0, 1))
            index = jnp.where(index == 0, 0.0, index)  # minus zero as zero
            _, chosen = lax.top_k(jnp.where(seen, index, -jnp.inf), min(topk, S))
            kept = jnp.zeros((block, S), bool).at[jnp.arange(block)[:, None], chosen].set(True) & seen
            if fault == "dense_attention":
                kept = seen
            scores = jnp.where(kept, scores, -jnp.inf)
            # rounded before they are normalised: an online softmax has no
            # denominator yet when its weights meet the values
            weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
            total = weights.sum(axis=-1, keepdims=True)
            out = jnp.einsum("kgcs,skd->ckgd", _bf(weights), v) / total.transpose(2, 0, 1, 3)
            return out.reshape(block, H * hd), kept.sum(dtype=jnp.int32), kept[-1]

        out, kept, last = lax.map(some_queries, jnp.arange(0, S, block))
        a = _bf(h + _bf(out.reshape(S, H * hd)) @ p["wo"])
        return a, _bf(_norm(a, p["norm2"], eps)), kept.sum(), last[-1]


@functools.partial(jax.jit, static_argnames=("k",))
def _route(router, x, k):
    """-> (chosen experts (S, k), their weights (S, k)): softmax over every
    expert in float32, the ``k`` largest, renormalised over them."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ router.astype(jnp.float32), axis=-1)
        picked, chosen = lax.top_k(probs, k)
        return chosen, picked / picked.sum(axis=1, keepdims=True)


def _swiglu(x, gate, up, down):
    return _bf(jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, donate_argnums=0)
def _add_expert(y, x, tokens, weights, stack, index, expert):
    """``y[tokens] += weights * Expert(x[tokens])`` for one expert of layer
    ``index``; a padded slot names token ``S`` at weight 0 and is dropped."""
    with jax.default_matmul_precision("highest"):
        wide = [lax.dynamic_index_in_dim(lax.dynamic_index_in_dim(stack[n], index, keepdims=False),
                                         expert, keepdims=False).astype(jnp.float32)
                for n in ("e_gate", "e_up", "e_down")]
        mine = x[jnp.minimum(tokens, x.shape[0] - 1)]
        return y.at[tokens].add(weights[:, None] * _swiglu(mine, *wide), mode="drop")


@jax.jit
def _close(a, y):
    return _bf(a + y)


def _experts(stack, index, a, x, config):
    """-> (``a + MoE(x)`` rounded (S, hidden), tokens an expert received (experts,))."""
    E, k, S = config["num_experts"], config["num_experts_per_tok"], x.shape[0]
    router = lax.dynamic_index_in_dim(stack["router"], index, keepdims=False)
    chosen, weights = (np.asarray(v) for v in _route(router, x, k))
    y = jnp.zeros_like(x)
    for e in range(E):
        tokens, slots = np.nonzero(chosen == e)
        for start in range(0, len(tokens), TOKENS_AT_A_TIME):
            some = slice(start, start + TOKENS_AT_A_TIME)
            padded = np.full(TOKENS_AT_A_TIME, S, np.int32)
            padded[: len(tokens[some])] = tokens[some]
            w = np.zeros(TOKENS_AT_A_TIME, np.float32)
            w[: len(tokens[some])] = weights[tokens[some], slots[some]]
            y = _add_expert(y, x, padded, w, stack, index, e)
    return _close(a, y), np.bincount(chosen.ravel(), minlength=E).astype(np.int32)


@jax.jit
def _head_product(head, hidden):
    with jax.default_matmul_precision("highest"):
        return _bf(hidden) @ head.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _last(final_norm, h_last, eps):
    return _norm(h_last, final_norm.astype(jnp.float32), eps)


def head_of(params, hidden):
    """The untied head over given normalised last-position states ``(rows,
    hidden)`` -> logits ``(rows, vocabulary)``, float32. Handed the PROGRAM's
    own ``hidden`` it follows the program: the program's logits have to be
    this product of it."""
    return np.asarray(_head_product(params["head"], jnp.asarray(hidden, jnp.float32)))


def _frozen(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    return tuple(value) if isinstance(value, list) else value


class _Config(dict):
    """A configuration that is a static argument of a jit: hashed by content."""

    def __hash__(self):
        return hash(_frozen(self))


def forward(params, tokens, config: dict, fault=None) -> dict:
    """tokens: (rows, S) int. -> ``hidden`` (rows, hidden) and ``logits``
    (rows, vocabulary) of each row's last position, float32; ``expert_load``
    (rows, layers, experts) int32; ``keys_kept`` (rows, layers) int, the keys
    the selection kept over the row's queries; ``last_kept`` (rows, layers,
    S) bool, the last position's kept keys."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = _Config({k: v for k, v in config.items() if k not in ("interpret", "product_dtype")})
    stack = params["layers"]
    attention = {n: stack[n] for n in _ATTENTION}
    out = {"hidden": [], "logits": [], "expert_load": [], "keys_kept": [], "last_kept": []}
    for row in np.asarray(tokens):
        h = params["embed"][jnp.asarray(row)].astype(jnp.float32)
        loads, kept, last = [], [], []
        for index in range(config["layers"]):
            a, x, n, mine = _attend(attention, index, h, frozen, fault)
            h, load = _experts(stack, index, a, x, config)
            loads.append(load)
            kept.append(int(n))
            last.append(np.asarray(mine))
        hidden = _last(params["final_norm"], h[-1], config["rms_norm_eps"])
        out["hidden"].append(np.asarray(hidden))
        out["logits"].append(head_of(params, hidden[None])[0])
        out["expert_load"].append(np.stack(loads))
        out["keys_kept"].append(kept)
        out["last_kept"].append(np.stack(last))
    return {name: np.stack(v) if name != "keys_kept" else np.asarray(v, np.int64) for name, v in out.items()}


def kept_gaps(got, want):
    """Per row and layer: the share of the reference's kept keys of the last
    position that the program did not keep, over bool masks (..., S)."""
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    return (want & ~got).sum(axis=-1) / want.sum(axis=-1)
