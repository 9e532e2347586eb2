"""A decoder of the ``lfm2_moe`` family (LiquidAI LFM2 with routed experts):
gated short-convolution layers and a few grouped-attention layers mixed by a
published list, leading dense SwiGLU layers, then layers of routed experts
with **no shared expert**, and a head **tied** to the embedding.

Every layer is ``h <- h + Op(RMSNorm(h))`` then ``h <- h + FFN(RMSNorm(h))``,
``Op`` by the layer's kind in ``layer_types`` and ``FFN`` by its place (the
first ``num_dense_layers`` dense, the rest experts):

- ``conv``: ``[B | C | x] = u W_in`` (hidden -> 3 x hidden, thirds in that
  order); ``z = B * x``; ``c[t] = sum_j w[:, j] z[t - (L - 1) + j]``, a
  depthwise causal convolution of ``conv_L_cache`` taps, a channel on its
  own, zeros before a row's first position, no bias, **no activation**
  (:func:`moe_decoder.causal_conv`); ``(C * c) W_out``.
- ``full_attention``: grouped key/value heads; ``q`` and ``k``
  RMS-normalised over each head with a learned scale, then rotary positions
  on the whole head (pairs ``(i, i + head/2)``); causal softmax at
  ``1/sqrt(head)``; no bias, no gate, no window.
- dense feed-forward: SwiGLU at ``intermediate_size``.
- experts: sigmoid router scores in float32, the ``num_experts_per_tok``
  largest of score + ``expert_bias`` chosen, weighed by the scores alone over
  their sum times ``routed_scaling_factor``; an expert is SwiGLU at
  ``moe_intermediate_size``; nothing beside them
  (:func:`moe_decoder.routed_experts` over a tree with no ``s_*`` leaves).

The two kinds of operator hold weights of different shapes, and the
feed-forward kind changes at another place than the operator kind does. So
the weights live in four stacks, ``conv`` and ``attention`` (the operators,
each kind's layers in order) and ``dense`` and ``moe`` (the feed-forwards),
and the layers run under two ``lax.scan``s, the dense layers then the expert
layers, as :mod:`afmoe`'s do. A scan's ``xs`` are its feed-forward stack;
a layer's operator is looked up by index in its kind's stack, under a
``lax.cond`` on the kind where the scan's layers are of both kinds. Any
list of the two kinds runs (the published tail is not regular); an unknown
kind is an error. No embedding scale; after the last layer one RMS norm and
the head, the embedding read transposed, at each row's last position.

Inference only, whole rows in, as :mod:`afmoe`. Not built: the one-token
step and any cache across calls, of a ``conv`` layer's last ``L - 1`` inputs
(``conv_L_cache x hidden_size`` numbers a row a layer) or of keys and values
(the system has no generation loop).

``config`` holds the published ``config.json`` keys (``layer_types``,
``num_dense_layers``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``moe_intermediate_size``,
``num_experts``, ``num_experts_per_tok``, ``routed_scaling_factor``,
``conv_L_cache``, ``rope_theta``, ``norm_eps``, ``vocab_size``) and
``layers``: how many of ``layer_types``, from the first, are held here.
``head_dim`` is ``hidden_size / num_attention_heads`` where the key is
absent, and ``tie_word_embeddings`` true where it is absent (the family's
default). Precision is :mod:`moe_decoder`'s; besides, the two gates and the
convolution's multiply-adds are float32. ``config["product_dtype"]`` and
``config["interpret"]`` as in :mod:`afmoe`.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from mmlspark_tpu.models.moe_decoder import (
    causal_conv,
    dot,
    init_stacks,
    last_position,
    norm,
    rope,
    routed_experts,
    swiglu,
)
from mmlspark_tpu.ops.attention import blocked_attention

CONV, ATTENTION = "conv", "full_attention"
# Seeded weights only: the query norm's learned scale over another norm's. A score q.k / sqrt(head) of two
# RMS-normalised heads has a standard deviation near 1, a position's softmax then lies on thousands of its keys, the
# layer returns the mean of the values and a comparison cannot tell it from none (PERF.md 6a, PR 33). At 4 it lies on
# a handful, as a trained model's does.
_QUERY_SCALE = 4.0


def head_dim(config: Dict[str, Any]) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def tied(config: Dict[str, Any]) -> bool:
    return bool(config.get("tie_word_embeddings", True))


def layer_kinds(config: Dict[str, Any]):
    """-> (attention? of each dense layer held, attention? of each expert
    layer held), from ``layer_types[:layers]`` and ``num_dense_layers``."""
    held = list(config["layer_types"][: config["layers"]])
    unknown = sorted(set(held) - {CONV, ATTENTION})
    if unknown or len(held) != config["layers"]:
        raise ValueError(f"layer_types[:{config['layers']}] holds {len(held)} layers, of kinds {unknown} besides "
                         f"{CONV!r} and {ATTENTION!r}")
    kinds = [kind == ATTENTION for kind in held]
    dense = min(config["num_dense_layers"], len(kinds))
    return kinds[:dense], kinds[dense:]


def _counts(config: Dict[str, Any]):
    """(conv operators, attention operators, dense feed-forwards, expert blocks) held."""
    dense, moe = layer_kinds(config)
    attention = sum(dense) + sum(moe)
    return len(dense) + len(moe) - attention, attention, len(dense), len(moe)


def conv_state_width(config: Dict[str, Any]) -> int:
    """What a cache would hold a row a ``conv`` layer (the family's own count)."""
    return config["conv_L_cache"] * config["hidden_size"]


def span_tags(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the ``lm.featurize`` span says of a configuration of this family;
    ``conv_state_width`` is what a cache would hold a row a ``conv`` layer,
    beside the attention layers' keys and values."""
    conv, attention, dense, moe = _counts(config)
    return {"layers": config["layers"], "experts": config["num_experts"], "attention": "grouped",
            "head_dim": head_dim(config), "conv_layers": conv, "attention_layers": attention,
            "dense_layers": dense, "expert_layers": moe, "conv_state_width": conv_state_width(config)}


def _shapes(c):
    """{stack: {name: (shape, fan-in, None for a norm's scale, or a recipe)}}
    of one layer's operator or feed-forward."""
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    E, F = c["num_experts"], c["moe_intermediate_size"]
    sharp = lambda key, shape: (_QUERY_SCALE * jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)).astype(jnp.bfloat16)
    return {
        "conv": {"norm": ((D,), None), "in_proj": ((D, 3 * D), D),
                 "conv_w": ((D, c["conv_L_cache"]), c["conv_L_cache"]), "out_proj": ((D, D), D)},
        "attention": {"norm": ((D,), None), "wq": ((D, H * hd), D), "wk": ((D, KV * hd), D), "wv": ((D, KV * hd), D),
                      "wo": ((H * hd, D), H * hd), "q_norm": ((hd,), sharp), "k_norm": ((hd,), None)},
        "dense": {"norm": ((D,), None), "w_gate": ((D, c["intermediate_size"]), D),
                  "w_up": ((D, c["intermediate_size"]), D), "w_down": ((c["intermediate_size"], D), c["intermediate_size"])},
        "moe": {"norm": ((D,), None), "router": ((D, E), D), "router_bias": ((E,), None),
                "e_gate": ((E, D, F), D), "e_up": ((E, D, F), D), "e_down": ((E, F, D), F)},
    }


def init_lfm2_moe(key, config: Dict[str, Any]):
    """Seeded weights, made on the device a layer at a time
    (:func:`moe_decoder.init_stacks`): stacks ``conv`` and ``attention`` (the
    operators of each kind held, in order) and ``dense`` and ``moe`` (the
    feed-forwards); no ``head`` where the configuration ties it. The taps'
    variance is 1 / ``conv_L_cache``; the query norm's scale is four times
    another norm's, so that a layer's softmax is sharp."""
    shapes = _shapes(config)
    return init_stacks(key, config, {name: (shapes[name], n) for name, n in zip(
        ("conv", "attention", "dense", "moe"), _counts(config))}, tied=tied(config))


def _short_conv(p, x, dt):
    """x: (rows, S, hidden), normalised."""
    D = x.shape[-1]
    with jax.named_scope("conv_in"):
        mixed = dot(x, p["in_proj"], dt)  # [B | C | x], float32
    with jax.named_scope("short_conv"):
        y = mixed[..., D:2 * D] * causal_conv(mixed[..., :D] * mixed[..., 2 * D:], p["conv_w"])
    with jax.named_scope("conv_out"):
        return dot(y, p["out_proj"], dt)


def _attention(p, x, c, dt):
    """x: (rows, S, hidden), normalised."""
    B, S, _ = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    q = norm(dot(x, p["wq"], dt).reshape(B, S, H, hd), p["q_norm"], c["norm_eps"])
    k = norm(dot(x, p["wk"], dt).reshape(B, S, KV, hd), p["k_norm"], c["norm_eps"])
    v = dot(x, p["wv"], dt).reshape(B, S, KV, hd).astype(jnp.bfloat16)
    with jax.named_scope("attn_full"):
        q, k = (rope(a, c["rope_theta"]).astype(jnp.bfloat16) for a in (q, k))
        out = blocked_attention(q, k, v, interpret=bool(c.get("interpret", False)))
    return dot(out.reshape(B, S, H * hd), p["wo"], dt)


def lfm2_moe_apply(params, tokens, config: Dict[str, Any]):
    """tokens: (rows, S) integers. -> ``hidden`` (rows, hidden) float32, the
    last position after the final norm; ``logits`` (rows, vocabulary)
    float32, the head (the embedding transposed where the tree holds no
    ``head``) applied to it; ``expert_load`` (rows, expert layers held,
    experts) int32, the tokens of the row each expert received (a layer's sum
    is ``S x num_experts_per_tok``: no token is dropped)."""
    c = config
    dt = jnp.dtype(c.get("product_dtype", "bfloat16"))
    eps = c["norm_eps"]
    dense_kinds, moe_kinds = layer_kinds(c)
    h = params["embed"][tokens]

    def normed(h, p):
        return norm(h, p["norm"], eps).astype(jnp.bfloat16)

    def added(h, y):  # the stream stays bfloat16
        return (h.astype(jnp.float32) + y).astype(jnp.bfloat16)

    def operator(stack, apply):
        def run(h, index):  # this layer's place in its kind's stack
            p = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, keepdims=False), params[stack])
            return added(h, apply(p, normed(h, p)))
        return run

    convolve = operator("conv", lambda p, x: _short_conv(p, x, dt))
    attend = operator("attention", lambda p, x: _attention(p, x, c, dt))

    seen = [0, 0]  # conv and attention layers met so far: a layer's place in its kind's stack

    def layers(h, stack, kinds, ffn):
        """The layers of one feed-forward kind under one scan."""
        index = []
        for attends in kinds:
            index.append(seen[attends])
            seen[attends] += 1

        def layer(h, xs):
            p, attends, index = xs
            if len(set(kinds)) == 1:  # one kind of operator: no conditional
                h = (attend if kinds[0] else convolve)(h, index)
            else:
                h = lax.cond(attends, attend, convolve, h, index)
            y, load = ffn(p, normed(h, p))
            return added(h, y), load

        return lax.scan(layer, h, (params[stack], jnp.asarray(kinds), jnp.asarray(index, jnp.int32)))

    if dense_kinds:
        h, _ = layers(h, "dense", dense_kinds, lambda p, x: (swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None))
    if moe_kinds:
        h, loads = layers(h, "moe", moe_kinds, lambda p, x: routed_experts(
            p, x, c["num_experts_per_tok"], c["routed_scaling_factor"], dt, interpret=bool(c.get("interpret", False))))
        loads = loads.transpose(1, 0, 2)
    else:
        loads = jnp.zeros((tokens.shape[0], 0, c["num_experts"]), jnp.int32)
    hidden, logits = last_position(params, h, eps, dt)
    return {"hidden": hidden, "logits": logits, "expert_load": loads}
