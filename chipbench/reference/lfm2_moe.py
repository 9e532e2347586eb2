"""Plain reference for the decoder of the ``lfm2_moe`` family (LiquidAI
LFM2 with routed experts, as LFM2-8B-A1B publishes it): whole token rows in,
the last position's normalised hidden state and logits out, with each expert
layer's load a row.

Imports nothing of the program and uses no trick of its: float32 arithmetic
at ``highest`` matrix precision; no kernel and no scan over stacked layers
(a Python loop over the published ``layer_types``, one layer of the
program's tree widened at a time); the convolution is three shifted
multiply-adds over one row; attention is dense masked ``(S, S)`` scores, a
few heads and a block of queries at a time, so that they fit beside the
weights; the experts are looped over, each given the tokens that chose it
(found on the host and handed over 1,024 at a time, as
``reference/mla_moe.py`` does). It computes at the precision the
configuration STATES, no finer: the residual stream and every matrix
product's inputs are rounded to bfloat16 (``_bf``: ``lax.reduce_precision``,
which no compiler folds away), while norms, softmax, rotary, router scores
and the choice of experts, the two gates and the convolution's multiply-adds
stay float32. What is one form in every family's reference comes from
``reference/mla_moe.py`` (the rounding, the RMS norm, SwiGLU, one expert over
the tokens that chose it, rotary as a complex multiplication); everything
else is written from the equations the configuration's file gives:

- ``h = E[token]``; a layer is ``a = h + Op(N(h; operator_norm))``, ``h' = a
  + FFN(N(a; ffn_norm))``, ``Op`` by ``layer_types[i]`` and ``FFN`` dense for
  ``i < num_dense_layers``, experts after;
- ``conv``: ``[B | C | x] = u W_in``; ``z = B * x``; ``c[t] = sum_j w[:, j]
  z[t - 2 + j]``, zeros before the row, no bias, no activation; ``(C * c)
  W_out``;
- ``full_attention``: ``q = Nq(u W_q)``, ``k = Nk(u W_k)`` over each head,
  ``v = u W_v``; rotary on the whole head, pairs ``(i, i + head/2)``; causal
  softmax of ``q.k / sqrt(head)``, query head ``h`` reading key/value head
  ``h // group``; ``W_o``;
- dense ``FFN``: SwiGLU; experts: sigmoid scores ``s``, the ``k`` largest of
  ``s + b`` chosen, weights ``s / sum(s) * routed_scaling_factor`` over the
  chosen, each expert SwiGLU, no shared expert;
- the final norm at the last position, and the head: the embedding
  transposed.

Departures from the published model, both the program's too: the one-token
step and any cache across calls are not built (nothing here generates), and
the ``1e-6`` of the published routing denominator is left out (four sigmoid
scores sum to order 1).

``head_of`` applies the tied head to hidden states it is handed; the
comparison hands it the program's, for a number that no routing tie moves.

``fault`` plants one departure, for the tests and for ``calibrate``: see
``FAULTS``. ``taps_reversed`` gives tap ``j`` the position ``t + 2 - j``'s
place, a convolution that looks ahead; ``weights_from_biased_scores``
weighs the chosen experts by ``s + b``; ``untied_head`` applies a head
drawn apart from the embedding; ``head_inputs_3_mantissa_bits`` rounds the
head's product alone to what float8 e4m3 keeps of a mantissa (nothing
before the head moves, so only the following check sees it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference.afmoe import load_gaps, relative_gaps  # noqa: F401  (the comparison's numbers)
# rounding by ``lax.reduce_precision``, the RMS norm, SwiGLU and one expert over the tokens that chose it: one form
from chipbench.reference.mla_moe import _add_expert, _bf, _norm, _swiglu
from chipbench.reference.mla_moe import rope as _rope

FAULTS = ("c_gate_skipped", "b_gate_skipped", "taps_reversed", "conv_skipped", "no_rotary", "no_qk_norm",
          "key_value_heads_swapped", "routing_bias_ignored", "weights_from_biased_scores", "one_expert_short",
          "untied_head", "head_inputs_3_mantissa_bits")
HEADS_AT_A_TIME = 4
QUERIES_AT_A_TIME = 1024
TOKENS_AT_A_TIME = 1024  # of those that chose one expert


def layers(config: dict):
    """[(operator's stack, index in it, feed-forward's stack, index in it)]
    of the layers that are run: the first ``layers`` of the published
    ``layer_types``, the leading ``num_dense_layers`` of them dense."""
    seen, out = {"conv": 0, "attention": 0}, []
    dense = config["num_dense_layers"]
    for i, kind in enumerate(config["layer_types"][: config["layers"]]):
        if kind not in ("conv", "full_attention"):
            raise ValueError(f"layer {i} is of kind {kind!r}")
        stack = "conv" if kind == "conv" else "attention"
        out.append((stack, seen[stack], "dense" if i < dense else "moe", i if i < dense else i - dense))
        seen[stack] += 1
    return out


def head_dim(config: dict) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def _layer(stack, index, names=None):
    """Layer ``index`` of a stack (or the ``names`` of it), widened."""
    return {n: lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
            for n in (names or stack)}


def conv(z, taps, reverse=False):
    """Depthwise and causal over time: z (S, channels), taps (channels, K);
    tap ``K - 1`` meets the position itself, tap 0 the one ``K - 1`` before,
    and what lies before the row is zero. ``reverse`` plants the fault: tap
    ``j`` meets the position ``K - 1 - j`` AHEAD, zeros behind the row."""
    S, K = z.shape[0], taps.shape[1]
    out = taps[:, K - 1] * z
    for away in range(1, K):
        moved = (jnp.concatenate([z[away:], jnp.zeros_like(z[:away])]) if reverse
                 else jnp.concatenate([jnp.zeros_like(z[:away]), z[: S - away]]))
        out = out + taps[:, K - 1 - away] * moved
    return out


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _convolve(stack, index, h, eps, fault):
    """``h + Conv(N(h))`` over one row, rounded."""
    with jax.default_matmul_precision("highest"):
        p = _layer(stack, index)
        D = h.shape[1]
        mixed = _bf(_norm(h, p["norm"], eps)) @ p["in_proj"]
        B, C, x = mixed[:, :D], mixed[:, D:2 * D], mixed[:, 2 * D:]
        z = x if fault == "b_gate_skipped" else B * x
        c = z if fault == "conv_skipped" else conv(z, p["conv_w"], reverse=fault == "taps_reversed")
        y = c if fault == "c_gate_skipped" else C * c
        return _bf(h + _bf(y) @ p["out_proj"])


@functools.partial(jax.jit, static_argnames=("H", "KV", "hd", "theta", "eps", "fault"))
def _attend(stack, index, h, H, KV, hd, theta, eps, fault):
    """``h + Attn(N(h))`` over one row, rounded."""
    with jax.default_matmul_precision("highest"):
        p = _layer(stack, index)
        S = h.shape[0]
        x = _bf(_norm(h, p["norm"], eps))
        q, k, v = ((x @ p[w]).reshape(S, n, hd) for w, n in (("wq", H), ("wk", KV), ("wv", KV)))
        if fault != "no_qk_norm":
            q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)
        if fault != "no_rotary":
            q, k = _rope(q, theta, half_split=True), _rope(k, theta, half_split=True)  # pairs (i, i + d/2)
        q, k, v = _bf(q), _bf(k), _bf(v)
        block = QUERIES_AT_A_TIME if S % QUERIES_AT_A_TIME == 0 else S
        j = jnp.arange(S)[None, :]

        def some_heads(heads):  # (n,) query heads -> (blocks, n, block, hd)
            group = heads // (H // KV)
            if fault == "key_value_heads_swapped":
                group = KV - 1 - group
            mine_k, mine_v = k[:, group], v[:, group]

            def some_queries(start):
                i = start + jnp.arange(block)[:, None]
                mine = lax.dynamic_slice_in_dim(q, start, block, axis=0)[:, heads]
                scores = jnp.einsum("shd,thd->hst", mine, mine_k) / np.sqrt(hd)
                scores = jnp.where(j <= i, scores, -jnp.inf)
                # rounded before they are normalised: an online softmax has no
                # denominator yet when its weights meet the values
                weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
                total = weights.sum(axis=-1, keepdims=True)
                return jnp.einsum("hst,thd->hsd", _bf(weights), mine_v) / total

            return lax.map(some_queries, jnp.arange(0, S, block))

        n = min(HEADS_AT_A_TIME, H)
        out = lax.map(some_heads, jnp.arange(H).reshape(H // n, n))  # (H/n, blocks, n, block, hd)
        out = out.transpose(1, 3, 0, 2, 4).reshape(S, H * hd)
        return _bf(h + _bf(out) @ p["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(stack, index, a, eps):
    with jax.default_matmul_precision("highest"):
        p = _layer(stack, index)
        return _bf(a + _swiglu(_bf(_norm(a, p["norm"], eps)), p["w_gate"], p["w_up"], p["w_down"]))


@functools.partial(jax.jit, static_argnames=("k", "scale", "eps", "fault"))
def _route(stack, index, a, k, scale, eps, fault):
    """-> (the experts' input ``N(a)`` rounded, chosen experts (S, k), their weights (S, k))."""
    with jax.default_matmul_precision("highest"):
        p = _layer(stack, index, ("norm", "router", "router_bias"))
        x = _bf(_norm(a, p["norm"], eps))
        scores = jax.nn.sigmoid(x @ p["router"])
        biased = scores + p["router_bias"]
        _, chosen = lax.top_k(scores if fault == "routing_bias_ignored" else biased, k)
        picked = jnp.take_along_axis(biased if fault == "weights_from_biased_scores" else scores, chosen, axis=1)
        weights = picked / picked.sum(axis=1, keepdims=True) * scale
        if fault == "one_expert_short":
            weights = jnp.where(picked == picked.min(axis=1, keepdims=True), 0.0, weights)
        return x, chosen, weights


@jax.jit
def _close(a, y):
    return _bf(a + y)


def _experts(stack, index, a, config, fault):
    """-> (``a + Experts(N(a))`` (S, hidden), tokens an expert received (experts,))."""
    E, k, S = config["num_experts"], config["num_experts_per_tok"], a.shape[0]
    x, chosen, weights = _route(stack, index, a, k, float(config["routed_scaling_factor"]), config["norm_eps"], fault)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
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
    load = np.bincount(chosen.ravel(), minlength=E).astype(np.int32)
    return _close(a, y), load


@functools.partial(jax.jit, static_argnames=("mantissa",))
def _head_product(embed, hidden, mantissa=7):
    """The tied head: ``hidden`` times the embedding transposed, both sides
    rounded to ``mantissa`` bits (bfloat16's 7 as stated), float32 sums."""
    with jax.default_matmul_precision("highest"):
        return _bf(hidden, mantissa) @ _bf(embed.astype(jnp.float32), mantissa).T


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(final_norm, h_last, eps):
    return _norm(h_last, final_norm.astype(jnp.float32), eps)


def _another_head(embed):
    """The planted fault's head: drawn apart from the embedding, as an untied
    model's would be, of the embedding's shape, variance and dtype."""
    drawn = jax.random.normal(jax.random.PRNGKey(0), embed.shape, jnp.float32) * embed.shape[1] ** -0.5
    return drawn.astype(embed.dtype)


def head_of(params, hidden):
    """The tied head over given normalised last-position states ``(rows,
    hidden)`` -> logits ``(rows, vocabulary)``, float32. Handed the PROGRAM's
    own ``hidden`` it follows the program: whatever a routing tie did to that
    state, the program's logits have to be this product of it."""
    return np.asarray(_head_product(params["embed"], jnp.asarray(hidden, jnp.float32)))


def forward(params, tokens, config: dict, fault=None) -> dict:
    """tokens: (rows, S) int. -> ``hidden`` (rows, hidden) and ``logits``
    (rows, vocabulary) of each row's last position, float32, and
    ``expert_load`` (rows, expert layers, experts) int32."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    c, eps = config, config["norm_eps"]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    head = _another_head(params["embed"]) if fault == "untied_head" else params["embed"]
    hidden, logits, loads = [], [], []
    for row in np.asarray(tokens):
        h = params["embed"][jnp.asarray(row)].astype(jnp.float32)
        row_loads = []
        for operator, at, ffn, index in layers(c):
            if operator == "conv":
                h = _convolve(params["conv"], at, h, eps, fault)
            else:
                h = _attend(params["attention"], at, h, H, KV, hd, float(c["rope_theta"]), eps, fault)
            if ffn == "dense":
                h = _dense(params["dense"], index, h, eps)
            else:
                h, load = _experts(params["moe"], index, h, c, fault)
                row_loads.append(load)
        hid = _final_norm(params["final_norm"], h[-1], eps)
        log = _head_product(head, hid, 3 if fault == "head_inputs_3_mantissa_bits" else 7)
        hidden.append(np.asarray(hid))
        logits.append(np.asarray(log))
        loads.append(np.asarray(row_loads, np.int32).reshape(-1, c["num_experts"]))
    return {"hidden": np.stack(hidden), "logits": np.stack(logits),
            "expert_load": np.stack(loads)}
