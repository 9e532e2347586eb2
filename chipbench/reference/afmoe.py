"""Plain reference for the ``afmoe`` decoder (arcee-ai Trinity): whole token
rows in, the last position's normalised hidden state and logits out, with
each expert layer's load a row.

Imports nothing of the program and uses no trick of its: float32 arithmetic
at ``highest`` matrix precision, dense masked scores (a few heads at a time,
so that they fit), every expert over every token under a 0/1 mask. It
computes at the precision the configuration STATES, no finer: the residual
stream and every matrix product's inputs are rounded to bfloat16 (``_bf``;
their products are then exact in float32, and the sums are float32), while
norms, softmax, router scores and the choice of experts stay float32. A
float32 reference that rounded nothing would differ from any bfloat16
program by the stated rounding itself, and one step less precision would
hide inside that gap. The parameter tree is the program's (it arrives in
bfloat16 and is widened here a layer, and an expert, at a time); everything
else is written from the equations the configuration's file gives:

- ``h = E[token] * sqrt(hidden)``; a layer is ``a = h + N2(Attn(N1(h)))``,
  ``h' = a + N4(FFN(N3(a)))``, every norm RMSNorm with a learned scale;
- ``Attn``: ``q, k`` normalised over the head, rotary (half-split pairs) in
  ``sliding_attention`` layers only, causal scores over ``sqrt(head)``, in a
  sliding layer also ``j > i - window``, query head ``h`` reads key/value
  head ``h // group``, output gated by ``sigmoid(x Wg)`` before ``Wo``;
- ``FFN`` of a leading dense layer: SwiGLU; of an expert layer: sigmoid
  router scores ``s``, the ``k`` largest of ``s + b`` chosen, weights
  ``s / sum(s) * route_scale`` over the chosen, plus one shared expert.

``fault`` plants one departure, for the tests and for ``calibrate``:
``window_ignored`` (sliding layers attend to everything before them),
``rope_in_full`` (rotary in the full layer too), ``one_expert_short`` (the
least of a token's chosen experts adds nothing) and ``no_shared_expert``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FAULTS = ("window_ignored", "rope_in_full", "one_expert_short", "no_shared_expert")
HEADS_AT_A_TIME = 4


def layer_kinds(config: dict):
    """[(stack, index in its stack, sliding?)] for the layers that are run:
    the first ``layers`` of the published ``layer_types``, the leading
    ``num_dense_layers`` of them dense."""
    dense = config["num_dense_layers"]
    return [
        ("dense" if i < dense else "moe", i if i < dense else i - dense,
         kind == "sliding_attention")
        for i, kind in enumerate(config["layer_types"][: config["layers"]])
    ]


def _bf(x):
    """Round to bfloat16, stay float32: the stated precision of the residual
    stream and of a matrix product's inputs."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, head). Pairs are (i, i + head/2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, config, sliding, fault):
    """x: (S, hidden) of one row, rounded."""
    S = x.shape[0]
    H, KV, hd = (config[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    eps = config["rms_norm_eps"]
    q = _norm((x @ p["wq"]).reshape(S, H, hd), p["q_norm"], eps)
    k = _norm((x @ p["wk"]).reshape(S, KV, hd), p["k_norm"], eps)
    v = _bf(x @ p["wv"]).reshape(S, KV, hd)
    if sliding or fault == "rope_in_full":
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    q, k = _bf(q), _bf(k)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if sliding and fault != "window_ignored":
        seen &= j > i - config["sliding_window"]

    def some_heads(heads):  # (n,) query heads -> (n, S, head)
        kv = heads // (H // KV)
        scores = jnp.einsum("shd,thd->hst", q[:, heads], k[:, kv]) / np.sqrt(hd)
        scores = jnp.where(seen, scores, -jnp.inf)
        # rounded before they are normalised: an online softmax has no
        # denominator yet when its weights meet the values
        weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        total = weights.sum(axis=-1, keepdims=True)
        return jnp.einsum("hst,thd->hsd", _bf(weights), v[:, kv]) / total

    n = min(HEADS_AT_A_TIME, H)
    out = lax.map(some_heads, jnp.arange(H).reshape(H // n, n))
    out = out.reshape(H, S, hd).transpose(1, 0, 2).reshape(S, H * hd)
    return _bf(_bf(out) * jax.nn.sigmoid(x @ p["wg"])) @ p["wo"]


def _swiglu(x, gate, up, down):
    return _bf(jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(p, x, config, fault):
    """-> (the routed and shared experts' sum (S, hidden), tokens an expert
    received (experts,))."""
    E, k = config["num_experts"], config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = lax.top_k(scores + p["router_bias"], k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / picked.sum(axis=1, keepdims=True) * config["route_scale"]
    load = (chosen[:, :, None] == jnp.arange(E)).sum(axis=(0, 1))
    if fault == "one_expert_short":
        weights = jnp.where(picked == picked.min(axis=1, keepdims=True), 0.0, weights)
    # (S, E): a token's weight for an expert, 0 where it was not chosen
    dense = (weights[:, :, None] * (chosen[:, :, None] == jnp.arange(E))).sum(axis=1)

    def one(total, e):
        wide = [lax.dynamic_index_in_dim(p[n], e, keepdims=False).astype(jnp.float32)
                for n in ("e_gate", "e_up", "e_down")]
        return total + dense[:, e, None] * _swiglu(x, *wide), None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    if fault != "no_shared_expert":
        y += _swiglu(x, *(p[n].astype(jnp.float32) for n in ("s_gate", "s_up", "s_down")))
    return y, load.astype(jnp.int32)


_EXPERT_WEIGHTS = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")


@functools.partial(jax.jit, static_argnames=("config", "sliding", "dense", "fault"))
def _layer(stack, index, h, config, sliding, dense, fault):
    """One layer over one row. ``stack`` is the program's stacked tree of
    this kind of layer; layer ``index`` of it is widened here (the experts'
    matrices one expert at a time, inside ``_experts``)."""
    config = dict(config)
    with jax.default_matmul_precision("highest"):
        p = {n: lax.dynamic_index_in_dim(a, index, keepdims=False) for n, a in stack.items()}
        p = {n: a if n in _EXPERT_WEIGHTS else a.astype(jnp.float32) for n, a in p.items()}
        eps = config["rms_norm_eps"]
        a = _bf(h + _norm(_attention(p, _bf(_norm(h, p["norm1"], eps)), config, sliding, fault),
                          p["norm2"], eps))
        x = _bf(_norm(a, p["norm3"], eps))
        if dense:
            y, load = _swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), None
        else:
            y, load = _experts(p, x, config, fault)
        return _bf(a + _norm(y, p["norm4"], eps)), load


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, head, h_last, eps):
    with jax.default_matmul_precision("highest"):
        hidden = _norm(h_last, final_norm.astype(jnp.float32), eps)
        return hidden, _bf(hidden) @ head.astype(jnp.float32)


def _hashable(config: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in config.items()))


def forward(params, tokens, config: dict, fault=None) -> dict:
    """tokens: (rows, S) int. -> ``hidden`` (rows, hidden) and ``logits``
    (rows, vocabulary) of each row's last position, float32, and
    ``expert_load`` (rows, expert layers, experts) int32."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = _hashable(config)
    scale = np.sqrt(config["hidden_size"])
    hidden, logits, loads = [], [], []
    for row in np.asarray(tokens):
        h = _bf(params["embed"][jnp.asarray(row)].astype(jnp.float32) * scale)
        row_loads = []
        for stack, index, sliding in layer_kinds(config):
            h, load = _layer(params[stack], index, h, frozen, sliding, stack == "dense", fault)
            if load is not None:
                row_loads.append(load)
        hid, log = _head(params["final_norm"], params["head"], h[-1], config["rms_norm_eps"])
        hidden.append(np.asarray(hid))
        logits.append(np.asarray(log))
        loads.append(np.asarray(row_loads, np.int32).reshape(-1, config["num_experts"]))
    return {"hidden": np.stack(hidden), "logits": np.stack(logits),
            "expert_load": np.stack(loads)}


def relative_gaps(got, want):
    """Per row: ||got - want|| / ||want||, in float64."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def load_gaps(got, want, routed: int):
    """Per row and expert layer: half the L1 distance of the two loads over
    the ``routed`` (tokens x experts a token) assignments of a row: the
    share of assignments that went to another expert."""
    got, want = np.asarray(got, np.int64), np.asarray(want, np.int64)
    return np.abs(got - want).sum(axis=-1) / (2.0 * routed)
