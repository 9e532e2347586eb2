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

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.models.moe_decoder import (
    dot,
    init_decoder,
    last_position,
    norm,
    rope,
    routed_experts,
    swiglu,
)
from mmlspark_tpu.ops.attention import blocked_attention

SLIDING = "sliding_attention"


def layer_kinds(config: Dict[str, Any]):
    """-> (sliding? of each dense layer held, sliding? of each expert layer
    held), from ``layer_types`` by index."""
    kinds = [k == SLIDING for k in config["layer_types"][: config["layers"]]]
    dense = min(config["num_dense_layers"], len(kinds))
    return kinds[:dense], kinds[dense:]


def span_tags(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the ``lm.featurize`` span says of a configuration of this family."""
    return {"layers": config["layers"], "experts": config["num_experts"], "attention": "grouped"}


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


def init_afmoe(key, config: Dict[str, Any]):
    """Seeded weights, made on the device in bfloat16 a layer at a time
    (:func:`moe_decoder.init_decoder`): the embedding's variance is
    1 / hidden, so that ``E[token] * sqrt(hidden)`` has unit variance."""
    dense, moe = layer_kinds(config)
    return init_decoder(key, config, (_layer_shapes(config, True), len(dense)),
                        (_layer_shapes(config, False), len(moe)))


def _attention(p, x, sliding, c, dt):
    """x: (rows, S, hidden), normalised. ``sliding`` is a traced bool."""
    B, S, _ = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    q = norm(dot(x, p["wq"], dt).reshape(B, S, H, hd), p["q_norm"], eps)
    k = norm(dot(x, p["wk"], dt).reshape(B, S, KV, hd), p["k_norm"], eps)
    v = dot(x, p["wv"], dt).reshape(B, S, KV, hd).astype(jnp.bfloat16)
    interpret = bool(c.get("interpret", False))

    def window_layer(q, k, v):
        with jax.named_scope("attn_window"):
            q, k = (rope(a, c["rope_theta"]).astype(jnp.bfloat16) for a in (q, k))
            return blocked_attention(q, k, v, window=c["sliding_window"], interpret=interpret)

    def full_layer(q, k, v):
        with jax.named_scope("attn_full"):
            return blocked_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v,
                                     interpret=interpret)

    out = lax.cond(sliding, window_layer, full_layer, q, k, v).reshape(B, S, H * hd)
    gate = jax.nn.sigmoid(dot(x, p["wg"], dt))
    return dot(out.astype(jnp.float32) * gate, p["wo"], dt)


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
        a = _attention(p, norm(h, p["norm1"], eps).astype(jnp.bfloat16), sliding, c, dt)
        a = (h.astype(jnp.float32) + norm(a, p["norm2"], eps)).astype(jnp.bfloat16)
        y, load = ffn(p, norm(a, p["norm3"], eps).astype(jnp.bfloat16))
        return (a.astype(jnp.float32) + norm(y, p["norm4"], eps)).astype(jnp.bfloat16), load

    def dense_layer(h, xs):
        p, sliding = xs
        return layer(h, p, sliding, lambda p, x: (swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None))

    def moe_layer(h, xs):
        p, sliding = xs
        return layer(h, p, sliding, lambda p, x: routed_experts(
            p, x, c["num_experts_per_tok"], c["route_scale"], dt))

    if dense_kinds:
        h, _ = lax.scan(dense_layer, h, (params["dense"], jnp.asarray(dense_kinds)))
    if moe_kinds:
        h, loads = lax.scan(moe_layer, h, (params["moe"], jnp.asarray(moe_kinds)))
        loads = loads.transpose(1, 0, 2)
    else:
        loads = jnp.zeros((tokens.shape[0], 0, c["num_experts"]), jnp.int32)
    hidden, logits = last_position(params, h, eps, dt)
    return {"hidden": hidden, "logits": logits, "expert_load": loads}
