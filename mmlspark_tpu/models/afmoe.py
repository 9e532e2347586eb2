"""A decoder of the ``afmoe`` family (arcee-ai Trinity): sliding-window and
full attention layers mixed, grouped key/value heads with QK-norm and a
sigmoid output gate, leading dense SwiGLU layers, then layers of many small
routed experts (sigmoid scores, top-k, one shared expert).

Inference only, whole rows in: :func:`afmoe_apply` takes ``(rows, S)`` token
ids and returns each row's last position (normalised hidden state, logits)
and each expert layer's load. Layers of one kind are stacked and run under
one ``lax.scan`` (the dense stack, then the expert stack), so the program is
traced once a kind; within a scan a layer's attention kind, read from the
configuration's ``layer_types`` by index, picks a ``lax.cond`` branch.

``config`` holds the published ``config.json`` keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
``num_experts_per_tok``, ``num_dense_layers``, ``sliding_window``,
``rope_theta``, ``rms_norm_eps``, ``route_scale``, ``vocab_size``,
``layer_types``) and ``layers``: how many of ``layer_types``, from the
first, are held here (the rest would be further pipeline stages).

Precision: weights and the residual stream bfloat16; every matrix product
takes bfloat16 inputs and sums in float32; norms, softmax, router scores and
the choice of experts are float32. ``config["product_dtype"]`` rounds every
projection's, feed-forward's, expert's, router's and head's inputs to a
narrower dtype first (``float8_e4m3fn``, say), for measuring what that
costs, and ``config["interpret"]`` runs the attention kernel in the Pallas interpreter
(a backend that is no TPU has no other way; nothing here chooses it).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.ops.attention import blocked_attention
from mmlspark_tpu.ops.expert_parallel import moe_topk

SLIDING = "sliding_attention"


def layer_kinds(config: Dict[str, Any]):
    """-> (sliding? of each dense layer held, sliding? of each expert layer
    held), from ``layer_types`` by index."""
    kinds = [k == SLIDING for k in config["layer_types"][: config["layers"]]]
    dense = min(config["num_dense_layers"], len(kinds))
    return kinds[:dense], kinds[dense:]


def _layer_shapes(c, dense: bool):
    """{name: (shape, fan-in or None for a norm's scale)} of one layer."""
    D, H, KV, hd = (c[k] for k in ("hidden_size", "num_attention_heads",
                                   "num_key_value_heads", "head_dim"))
    out = {"wq": ((D, H * hd), D), "wk": ((D, KV * hd), D), "wv": ((D, KV * hd), D),
           "wg": ((D, H * hd), D), "wo": ((H * hd, D), H * hd)}
    out.update({n: ((D,), None) for n in ("norm1", "norm2", "norm3", "norm4")})
    out.update(q_norm=((hd,), None), k_norm=((hd,), None))
    if dense:
        F = c["intermediate_size"]
        out.update(w_gate=((D, F), D), w_up=((D, F), D), w_down=((F, D), F))
    else:
        E, F = c["num_experts"], c["moe_intermediate_size"]
        out.update(router=((D, E), D), router_bias=((E,), None),
                   e_gate=((E, D, F), D), e_up=((E, D, F), D), e_down=((E, F, D), F),
                   s_gate=((D, F), D), s_up=((D, F), D), s_down=((F, D), F))
    return out


def _init_layer(key, shapes):
    out = {}
    for k, (name, (shape, fan_in)) in zip(jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if name == "router_bias":  # a buffer in float32: it is added to float32 scores
            out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif fan_in is None:  # a norm's scale, drawn away from 1
            out[name] = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5).astype(jnp.bfloat16)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(jnp.bfloat16)
    return out


def init_afmoe(key, config: Dict[str, Any]):
    """Seeded weights, made on the device in bfloat16 (nothing passes through
    the host): the layers of a kind live stacked on a leading axis, and one
    jitted call a layer draws that layer and writes it into the donated
    stack, so nothing is ever held twice. Matrices are normal with variance
    1 / fan-in, the embedding 1 / hidden (so that ``E[token] * sqrt(hidden)``
    has unit variance), norm scales uniform in [0.5, 1.5), the router's
    balancing bias normal x 0.1 in float32."""
    D, V = config["hidden_size"], config["vocab_size"]
    k_embed, k_head, k_norm, k_dense, k_moe = jax.random.split(key, 5)
    matrix = jax.jit(
        lambda k, shape: (jax.random.normal(k, shape, jnp.float32) * D ** -0.5).astype(jnp.bfloat16),
        static_argnums=1)
    params = {
        "embed": matrix(k_embed, (V, D)), "head": matrix(k_head, (D, V)),
        "final_norm": jax.random.uniform(k_norm, (D,), jnp.float32, 0.5, 1.5).astype(jnp.bfloat16),
    }
    kinds = dict(zip(("dense", "moe"), layer_kinds(config)))
    for name, k, dense in (("dense", k_dense, True), ("moe", k_moe, False)):
        shapes, n = _layer_shapes(config, dense), len(kinds[name])

        @functools.partial(jax.jit, donate_argnums=0)
        def write(stack, i, kk):
            layer = _init_layer(kk, shapes)
            return {m: lax.dynamic_update_index_in_dim(stack[m], layer[m], i, 0) for m in stack}

        stack = {m: jnp.zeros((n,) + shape, jnp.float32 if m == "router_bias" else jnp.bfloat16)
                 for m, (shape, _) in shapes.items()}
        for i, kk in enumerate(jax.random.split(k, n)):
            stack = write(stack, i, kk)
        params[name] = stack
    return params


def _norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rounded(a, dtype):
    """``a`` in bfloat16, rounded to ``dtype`` on the way if that is narrower."""
    return a.astype(dtype).astype(jnp.bfloat16)


def _dot(x, w, dtype):
    """Inputs rounded to ``dtype`` (bfloat16 as stated), float32 sums."""
    return jnp.dot(_rounded(x, dtype), _rounded(w, dtype), preferred_element_type=jnp.float32)


def _rope(x, theta):
    """x: (rows, S, heads, head), float32. Pairs are (i, i + head/2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, sliding, c, dt):
    """x: (rows, S, hidden), normalised. ``sliding`` is a traced bool."""
    B, S, _ = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    q = _norm(_dot(x, p["wq"], dt).reshape(B, S, H, hd), p["q_norm"], eps)
    k = _norm(_dot(x, p["wk"], dt).reshape(B, S, KV, hd), p["k_norm"], eps)
    v = _dot(x, p["wv"], dt).reshape(B, S, KV, hd).astype(jnp.bfloat16)
    interpret = bool(c.get("interpret", False))

    def window_layer(q, k, v):
        with jax.named_scope("attn_window"):
            q, k = (_rope(a, c["rope_theta"]).astype(jnp.bfloat16) for a in (q, k))
            return blocked_attention(q, k, v, window=c["sliding_window"], interpret=interpret)

    def full_layer(q, k, v):
        with jax.named_scope("attn_full"):
            return blocked_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v,
                                     interpret=interpret)

    out = lax.cond(sliding, window_layer, full_layer, q, k, v).reshape(B, S, H * hd)
    gate = jax.nn.sigmoid(_dot(x, p["wg"], dt))
    return _dot(out.astype(jnp.float32) * gate, p["wo"], dt)


def _swiglu(x, gate, up, down, dt):
    inner = jax.nn.silu(_dot(x, gate, dt)) * _dot(x, up, dt)
    return _dot(inner, down, dt)


def _experts(p, x, c, dt):
    """x: (rows, S, hidden). -> (routed + shared (rows, S, hidden) float32,
    the tokens of each row that each expert received (rows, experts))."""
    B, S, D = x.shape
    E, k = c["num_experts"], c["num_experts_per_tok"]
    flat = _rounded(x.reshape(B * S, D), dt)
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(_dot(flat, p["router"], dt))
    with jax.named_scope("moe_experts"):
        experts = {n: _rounded(p["e_" + n], dt) for n in ("gate", "up", "down")}
        routed, chosen = moe_topk(flat, scores + p["router_bias"], scores, experts, k, c["route_scale"])
        shared = _swiglu(flat, p["s_gate"], p["s_up"], p["s_down"], dt)
    load = (chosen.reshape(B, S * k, 1) == jnp.arange(E, dtype=jnp.int32)).sum(axis=1)
    return (routed + shared).reshape(B, S, D), load.astype(jnp.int32)


def afmoe_apply(params, tokens, config: Dict[str, Any]):
    """tokens: (rows, S) integers. -> ``hidden`` (rows, hidden) float32, the
    last position after the final norm; ``logits`` (rows, vocabulary)
    float32, the untied head applied to it; ``expert_load`` (rows, expert
    layers, experts) int32, the tokens of the row each expert received (a
    layer's sum is ``S x num_experts_per_tok``: no token is dropped)."""
    c = config
    dt = jnp.dtype(c.get("product_dtype", "bfloat16"))
    eps = c["rms_norm_eps"]
    dense_kinds, moe_kinds = layer_kinds(c)
    h = (params["embed"][tokens].astype(jnp.float32) * np.sqrt(c["hidden_size"])).astype(jnp.bfloat16)

    def layer(h, p, sliding, ffn):
        a = _attention(p, _norm(h, p["norm1"], eps).astype(jnp.bfloat16), sliding, c, dt)
        a = (h.astype(jnp.float32) + _norm(a, p["norm2"], eps)).astype(jnp.bfloat16)
        y, load = ffn(p, _norm(a, p["norm3"], eps).astype(jnp.bfloat16))
        return (a.astype(jnp.float32) + _norm(y, p["norm4"], eps)).astype(jnp.bfloat16), load

    def dense_layer(h, xs):
        p, sliding = xs
        return layer(h, p, sliding, lambda p, x: (_swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None))

    def moe_layer(h, xs):
        p, sliding = xs
        return layer(h, p, sliding, lambda p, x: _experts(p, x, c, dt))

    if dense_kinds:
        h, _ = lax.scan(dense_layer, h, (params["dense"], jnp.asarray(dense_kinds)))
    if moe_kinds:
        h, loads = lax.scan(moe_layer, h, (params["moe"], jnp.asarray(moe_kinds)))
        loads = loads.transpose(1, 0, 2)
    else:
        loads = jnp.zeros((tokens.shape[0], 0, c["num_experts"]), jnp.int32)
    with jax.named_scope("lm_head"):
        hidden = _norm(h[:, -1], params["final_norm"], eps)
        logits = _dot(hidden, params["head"], dt)
    return {"hidden": hidden, "logits": logits, "expert_load": loads}
