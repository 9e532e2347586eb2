"""Plain reference for the latent-attention decoder with routed experts
(the DeepSeek-V3 family's layer, as jdopensource JoyAI-LLM-Flash publishes
it): whole token rows in, the last position's normalised hidden state and
logits out, with each expert layer's load a row.

Imports nothing of the program and uses no trick of its: float32 arithmetic
at ``highest`` matrix precision, dense masked scores (a few heads and a
block of queries at a time, so that they fit beside the weights), rotary as
a complex multiplication of the interleaved pairs, the shared rotary key
broadcast plainly. With 256 experts "every expert over every token under a
mask" would cost 32 x the model, so the reference loops over the experts
and gives each the tokens that chose it (their indices found on the host
and handed over 1,024 at a time, so that the loop compiles one shape and
not one a count; a padded slot gathers any token at weight 0 and its
result is dropped). It computes at the precision the configuration STATES,
no finer: the residual stream and every matrix product's inputs are rounded
to bfloat16 (``_bf``; their products are then exact in float32, and the
sums are float32), while norms, softmax, rotary, router scores and the
choice of experts stay float32. The parameter tree is the program's (it arrives in
bfloat16 and is widened here a layer, and an expert, at a time); everything
else is written from the equations the configuration's file gives:

- ``h = E[token]``; a layer is ``a = h + Attn(N1(h))``, ``h' = a +
  FFN(N2(a))``, every norm RMSNorm with a learned scale;
- ``Attn``: ``c_q = Nq(x W_qa)``, ``q = c_q W_qb`` = heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_r] = x W_kva``, ``Nkv(c_kv) W_kvb`` = heads of
  ``[k_nope | v]``; rotary on pairs ``(2i, 2i+1)`` of ``q_rope`` and of
  ``k_r``, one vector a token shared by every head; ``k = [k_nope | k_r]``,
  causal scores over ``sqrt(nope + rope)``, then ``W_o``;
- ``FFN`` of a leading dense layer: SwiGLU; of an expert layer: sigmoid
  router scores ``s``, the ``k`` largest of ``s + b`` chosen, weights
  ``s / sum(s) * routed_scaling_factor`` over the chosen, plus the shared
  expert.

Departures from the published model, both the program's too: the one
multi-token-prediction module is not built (it feeds a training loss or a
drafting step, and nothing here trains or generates), and the ``1e-20`` of
the published denominator is left out (sigmoid scores cannot sum to zero).

``head_of`` applies the head to hidden states it is handed; the comparison
hands it the program's, for a number that no routing tie moves.

``fault`` plants one departure, for the tests and for ``calibrate``:
``latent_norms_skipped``, ``half_split_rotary`` (pairs ``(i, i + r/2)``),
``no_rotary_on_shared_key``, ``scale_from_nope_width`` (``1/sqrt(128)``),
``one_expert_short`` (the least of a token's chosen experts adds nothing),
``routing_bias_ignored``, ``no_shared_expert`` and
``head_inputs_3_mantissa_bits`` (the head's product alone with what float8
e4m3 keeps of a mantissa, one step below the stated bfloat16: nothing before
the head moves, so only the following check sees it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference.afmoe import load_gaps, relative_gaps  # noqa: F401  (the comparison's numbers)

FAULTS = ("latent_norms_skipped", "half_split_rotary", "no_rotary_on_shared_key",
          "scale_from_nope_width", "one_expert_short", "routing_bias_ignored", "no_shared_expert",
          "head_inputs_3_mantissa_bits")
HEADS_AT_A_TIME = 4
QUERIES_AT_A_TIME = 1024
TOKENS_AT_A_TIME = 1024  # of those that chose one expert
_ATTENTION = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo", "q_norm", "kv_norm", "norm1", "norm2")


def layer_kinds(config: dict):
    """[(stack, index in its stack)] for the layers that are run: the first
    ``layers``, the leading ``first_k_dense_replace`` of them dense."""
    dense = config["first_k_dense_replace"]
    return [("dense", i) if i < dense else ("moe", i - dense) for i in range(config["layers"])]


def _bf(x, mantissa=7):
    """Round to bfloat16 (8 exponent bits, 7 of mantissa), stay float32: the
    stated precision of the residual stream and of a matrix product's
    inputs. ``reduce_precision`` and not a cast there and back: under a jit
    XLA may fold such a pair away as excess precision, and on the chip it
    did, in the one-row product of the head (PERF.md 6a, PR 31)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=mantissa)


def _norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta, half_split=False):
    """x: (S, heads, r). Pair ``i`` is ``(2i, 2i+1)`` (``half_split``: ``(i,
    i + r/2)``), read as a complex number and turned by ``position x
    theta^(-2i/r)``."""
    S, heads, r = x.shape
    freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    turn = jnp.exp(1j * (jnp.arange(S, dtype=jnp.float32)[:, None] * freq))[:, None, :]
    if half_split:
        z = lax.complex(x[..., : r // 2], x[..., r // 2:]) * turn
        return jnp.concatenate([z.real, z.imag], axis=-1)
    pairs = x.reshape(S, heads, r // 2, 2)
    z = lax.complex(pairs[..., 0], pairs[..., 1]) * turn
    return jnp.stack([z.real, z.imag], axis=-1).reshape(S, heads, r)


def _attention(p, x, config, fault):
    """x: (S, hidden) of one row, rounded."""
    S = x.shape[0]
    H, rkv = config["num_attention_heads"], config["kv_lora_rank"]
    nope, r, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    latent_norm = (lambda c, scale: c) if fault == "latent_norms_skipped" else (
        lambda c, scale: _norm(c, scale, eps))
    half_split = fault == "half_split_rotary"
    c_q = _bf(latent_norm(x @ p["w_qa"], p["q_norm"]))
    kva = x @ p["w_kva"]
    c_kv = _bf(latent_norm(kva[:, :rkv], p["kv_norm"]))
    q = (c_q @ p["w_qb"]).reshape(S, H, nope + r)
    kv = (c_kv @ p["w_kvb"]).reshape(S, H, nope + dv)
    k_r = kva[:, None, rkv:]  # one vector a token
    if fault != "no_rotary_on_shared_key":
        k_r = rope(k_r, theta, half_split)
    q = _bf(jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta, half_split)], axis=-1))
    k = _bf(jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (S, H, r))], axis=-1))
    v = _bf(kv[..., nope:])
    scale = 1.0 / np.sqrt(nope if fault == "scale_from_nope_width" else nope + r)
    block = QUERIES_AT_A_TIME if S % QUERIES_AT_A_TIME == 0 else S
    j = jnp.arange(S)[None, :]

    def some_heads(heads):  # (n,) heads -> (blocks, n, block, dv)
        def some_queries(start):
            i = start + jnp.arange(block)[:, None]
            mine = lax.dynamic_slice_in_dim(q, start, block, axis=0)[:, heads]
            scores = jnp.einsum("shd,thd->hst", mine, k[:, heads]) * scale
            scores = jnp.where(j <= i, scores, -jnp.inf)
            # rounded before they are normalised: an online softmax has no
            # denominator yet when its weights meet the values
            weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
            total = weights.sum(axis=-1, keepdims=True)
            return jnp.einsum("hst,thd->hsd", _bf(weights), v[:, heads]) / total

        return lax.map(some_queries, jnp.arange(0, S, block))

    n = min(HEADS_AT_A_TIME, H)
    out = lax.map(some_heads, jnp.arange(H).reshape(H // n, n))  # (H/n, blocks, n, block, dv)
    out = out.transpose(1, 3, 0, 2, 4).reshape(S, H * dv)
    return _bf(out) @ p["wo"]


def _swiglu(x, gate, up, down):
    return _bf(jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("config", "fault"))
def _attend(stack, index, h, config, fault):
    """The attention half of layer ``index`` of ``stack`` over one row: ->
    (``a = h + Attn(N1(h))``, the feed-forward's input ``N2(a)``), rounded."""
    config = dict(config)
    with jax.default_matmul_precision("highest"):
        p = {n: lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
             for n in _ATTENTION}
        eps = config["rms_norm_eps"]
        a = _bf(h + _attention(p, _bf(_norm(h, p["norm1"], eps)), config, fault))
        return a, _bf(_norm(a, p["norm2"], eps))


@jax.jit
def _dense(stack, index, a, x):
    with jax.default_matmul_precision("highest"):
        wide = [lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
                for n in ("w_gate", "w_up", "w_down")]
        return _bf(a + _swiglu(x, *wide))


@functools.partial(jax.jit, static_argnames=("k", "scale", "fault"))
def _route(stack, index, x, k, scale, fault):
    """-> (chosen experts (S, k), their weights (S, k))."""
    with jax.default_matmul_precision("highest"):
        router, bias = (lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
                        for n in ("router", "router_bias"))
        scores = jax.nn.sigmoid(x @ router)
        _, chosen = lax.top_k(scores if fault == "routing_bias_ignored" else scores + bias, k)
        picked = jnp.take_along_axis(scores, chosen, axis=1)
        weights = picked / picked.sum(axis=1, keepdims=True) * scale
        if fault == "one_expert_short":
            weights = jnp.where(picked == picked.min(axis=1, keepdims=True), 0.0, weights)
        return chosen, weights


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


@functools.partial(jax.jit, static_argnames=("shared",))
def _close(stack, index, a, x, y, shared):
    with jax.default_matmul_precision("highest"):
        if shared:
            y = y + _swiglu(x, *(lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
                                 for n in ("s_gate", "s_up", "s_down")))
        return _bf(a + y)


def _experts(stack, index, a, x, config, fault):
    """-> (the layer's output (S, hidden), tokens an expert received (experts,))."""
    E, k, S = config["n_routed_experts"], config["num_experts_per_tok"], x.shape[0]
    chosen, weights = (np.asarray(v) for v in _route(
        stack, index, x, k, config["routed_scaling_factor"], fault))
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
    return _close(stack, index, a, x, y, fault != "no_shared_expert"), load


@functools.partial(jax.jit, static_argnames=("mantissa",))
def _head_product(head, hidden, mantissa=7):
    """Both sides rounded to ``mantissa`` bits (bfloat16's 7 as stated), float32 sums."""
    with jax.default_matmul_precision("highest"):
        return _bf(hidden, mantissa) @ _bf(head.astype(jnp.float32), mantissa)


@functools.partial(jax.jit, static_argnames=("eps", "mantissa"))
def _head(final_norm, head, h_last, eps, mantissa):
    hidden = _norm(h_last, final_norm.astype(jnp.float32), eps)
    return hidden, _head_product(head, hidden, mantissa)


def head_of(params, hidden):
    """The untied head over given normalised last-position states ``(rows,
    hidden)`` -> logits ``(rows, vocabulary)``, float32. Handed the PROGRAM's
    own ``hidden`` it follows the program (as the GBDT reference follows the
    fitted forest): whatever a routing tie did to that state, the program's
    logits have to be this product of it."""
    return np.asarray(_head_product(params["head"], jnp.asarray(hidden, jnp.float32)))


def _hashable(config: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in config.items()))


def forward(params, tokens, config: dict, fault=None) -> dict:
    """tokens: (rows, S) int. -> ``hidden`` (rows, hidden) and ``logits``
    (rows, vocabulary) of each row's last position, float32, and
    ``expert_load`` (rows, expert layers, experts) int32."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = _hashable(config)
    hidden, logits, loads = [], [], []
    for row in np.asarray(tokens):
        h = params["embed"][jnp.asarray(row)].astype(jnp.float32)
        row_loads = []
        for kind, index in layer_kinds(config):
            stack = params[kind]
            a, x = _attend({n: stack[n] for n in _ATTENTION}, index, h, frozen, fault)
            if kind == "dense":
                h = _dense(stack, index, a, x)
            else:
                h, load = _experts(stack, index, a, x, config, fault)
                row_loads.append(load)
        hid, log = _head(params["final_norm"], params["head"], h[-1], config["rms_norm_eps"],
                         3 if fault == "head_inputs_3_mantissa_bits" else 7)
        hidden.append(np.asarray(hid))
        logits.append(np.asarray(log))
        loads.append(np.asarray(row_loads, np.int32).reshape(-1, config["n_routed_experts"]))
    return {"hidden": np.stack(hidden), "logits": np.stack(logits),
            "expert_load": np.stack(loads)}
