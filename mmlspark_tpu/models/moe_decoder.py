"""What the decoder families with routed experts share (:mod:`afmoe`,
:mod:`mla_moe`, :mod:`nemotron_h`, :mod:`lfm2_moe`, :mod:`keye_vl2`): the stated precision of
a norm and of a matrix product, rotary positions on half-split pairs, the
depthwise causal convolution, the feed-forward with a gate (SwiGLU) and
without one, the routed-expert block with a shared expert or none, the head
at a row's last position, untied or the embedding transposed, and seeded
weights made on the device a layer at a time.

Precision, for every family here: weights and the residual stream bfloat16;
every matrix product takes bfloat16 inputs and sums in float32; norms,
softmax, rotary, router scores and the choice of experts are float32.
``config["product_dtype"]`` rounds every product's inputs to a narrower
dtype first (``float8_e4m3fn``, say), for measuring what that costs.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from mmlspark_tpu.ops.expert_parallel import moe_topk


def norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def rounded(a, dtype):
    """``a`` in bfloat16, rounded to ``dtype`` on the way if that is narrower."""
    return a.astype(dtype).astype(jnp.bfloat16)


def dot(x, w, dtype):
    """Inputs rounded to ``dtype`` (bfloat16 as stated), float32 sums."""
    return jnp.dot(rounded(x, dtype), rounded(w, dtype), preferred_element_type=jnp.float32)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def rope(x, theta):
    """x: (rows, S, heads, head), float32. Pairs are (i, i + head/2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_conv(x, taps, bias=None):
    """Depthwise and causal over time, shifted multiply-adds in float32: x
    (rows, S, channels), taps (channels, K), a channel on its own; tap
    ``K - 1`` meets the position itself, tap 0 the one ``K - 1`` before, and
    what lies before a row's first position is zero. ``bias`` (channels,)
    is added where there is one. No activation."""
    S, K = x.shape[1], taps.shape[1]
    ahead = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))  # zeros in front of a row
    taps = taps.astype(jnp.float32)
    out = sum(ahead[:, j:j + S] * taps[:, j] for j in range(K))
    return out if bias is None else out + bias.astype(jnp.float32)


def feed_forward(x, w, dt, activation: Callable = jax.nn.silu):
    """What ``w`` holds: ``gate``, ``up`` and ``down`` are
    ``down(activation(gate x) * up x)``; ``up`` and ``down`` alone, two
    matrices and no gate, ``down(activation(up x))``."""
    if "gate" in w:
        inner = activation(dot(x, w["gate"], dt)) * dot(x, w["up"], dt)
    else:
        inner = activation(dot(x, w["up"], dt))
    return dot(inner, w["down"], dt)


def swiglu(x, gate, up, down, dt):
    return feed_forward(x, {"gate": gate, "up": up, "down": down}, dt)


def routed_experts(p, x, k: int, scale: float, dt, activation: Callable = jax.nn.silu,
                   first_group=0, interpret: bool = False, gate: Callable = jax.nn.sigmoid):
    """x: (rows, S, hidden). Router scores ``gate`` of the router's product
    (sigmoid; a softmax over the experts, say); a token's ``k`` experts
    are the largest of score + ``router_bias`` (the scores alone where the
    tree holds no bias), weighed by the scores alone
    over their sum times ``scale`` (:func:`moe_topk`); one shared expert
    beside them, of whatever width its matrices have, where the tree holds
    ``s_*`` leaves, and the routed output alone where it holds none. The
    experts' body is what the tree holds: ``e_gate``, ``e_up``, ``e_down``
    (and ``s_*`` likewise) a gated feed-forward, ``e_up`` and ``e_down`` alone
    two matrices with ``activation`` between them (:func:`feed_forward`).
    ``e_*`` may hold several layers' experts one after another, read where
    they lie: ``first_group`` then says where this layer's stand.
    ``interpret`` is :func:`moe_topk`'s, for its Pallas kernels.
    -> (routed (+ shared) (rows, S, hidden) float32, the tokens of each row
    that each expert received (rows, experts))."""
    B, S, D = x.shape
    E = p["router"].shape[-1]
    flat = rounded(x.reshape(B * S, D), dt)
    with jax.named_scope("moe_route"):
        scores = gate(dot(flat, p["router"], dt))
    with jax.named_scope("moe_experts"):
        held = [n for n in ("gate", "up", "down") if "e_" + n in p]
        experts = {n: rounded(p["e_" + n], dt) for n in held}
        choose = scores + p["router_bias"] if "router_bias" in p else scores
        routed, chosen = moe_topk(flat, choose, scores, experts, k, scale, activation, first_group, interpret)
        shared = feed_forward(flat, {n: p["s_" + n] for n in held}, dt, activation) if "s_down" in p else None
    load = (chosen.reshape(B, S * k, 1) == jnp.arange(E, dtype=jnp.int32)).sum(axis=1)
    y = routed if shared is None else routed + shared
    return y.reshape(B, S, D), load.astype(jnp.int32)


def experts_in_place(stack, dt):
    """Split a stack of expert layers for a ``lax.scan`` over its layers. A
    layer's expert matrices sliced out of their stacks by the scan are copies
    (a layer's worth a layer a dispatch), so each of ``e_gate``, ``e_up``,
    ``e_down`` stored in ``dt`` is read where it lies instead: reshaped to
    ``(layers x experts, ., .)`` (a bitcast), every layer's groups one after
    another, to be closed over and told where a layer's stand
    (:func:`routed_experts`' ``first_group``). Inputs narrower than stored are
    rounded into a copy whatever is done, so those stacks stay with the rest.
    -> (the matrices read in place, what the scan hands over a layer at a time)."""
    whole = {name: a.reshape((-1,) + a.shape[2:])  # a bitcast
             for name, a in stack.items() if name in ("e_gate", "e_up", "e_down") and a.dtype == dt}
    return whole, {name: a for name, a in stack.items() if name not in whole}


def last_position(params, h, eps, dt):
    """-> (each row's last position after the final norm (rows, hidden),
    the head applied to it (rows, vocabulary)), float32. The head is
    ``params["head"]`` (hidden, vocabulary) where the tree holds one, and the
    embedding read transposed where it holds none (a tied head, held once)."""
    with jax.named_scope("lm_head"):
        hidden = norm(h[:, -1], params["final_norm"], eps)
        if "head" in params:
            return hidden, dot(hidden, params["head"], dt)
        return hidden, lax.dot_general(rounded(hidden, dt), rounded(params["embed"], dt), (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)


def _init_layer(key, shapes):
    out = {}
    for k, (name, (shape, fan_in)) in zip(jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if callable(fan_in):  # a leaf with a recipe of its own: (key, shape) -> values
            out[name] = fan_in(k, shape)
        elif name == "router_bias":  # a buffer in float32: it is added to float32 scores
            out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif fan_in is None:  # a norm's scale, drawn away from 1
            out[name] = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5).astype(jnp.bfloat16)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(jnp.bfloat16)
    return out


def init_stacks(key, config: Dict[str, Any], stacks: Dict[str, Any], tied: bool = False):
    """Seeded weights, made on the device (nothing passes through the host).
    ``stacks`` names each kind of layer: (``{name: (shape, fan-in, or None
    for a norm's scale, or a recipe (key, shape) -> values)}`` of one layer,
    how many layers): the layers of a kind live stacked on a leading axis,
    and one jitted call a layer draws that layer and writes it into the
    donated stack, so nothing is ever held twice. Matrices are normal with
    variance 1 / fan-in in bfloat16, the embedding and the head 1 / hidden,
    norm scales uniform in [0.5, 1.5), the router's balancing bias normal x
    0.1 in float32; a recipe's leaf has the recipe's dtype. ``tied``: the
    configuration ties the head to the embedding, and no ``head`` is drawn
    (:func:`last_position` then reads the embedding transposed)."""
    D, V = config["hidden_size"], config["vocab_size"]
    k_embed, k_head, k_norm, *k_stacks = jax.random.split(key, 3 + len(stacks))
    matrix = jax.jit(
        lambda k, shape: (jax.random.normal(k, shape, jnp.float32) * D ** -0.5).astype(jnp.bfloat16),
        static_argnums=1)
    params = {
        "embed": matrix(k_embed, (V, D)),
        "final_norm": jax.random.uniform(k_norm, (D,), jnp.float32, 0.5, 1.5).astype(jnp.bfloat16),
    }
    if not tied:
        params["head"] = matrix(k_head, (D, V))
    for k, (name, (shapes, n)) in zip(k_stacks, stacks.items()):

        @functools.partial(jax.jit, donate_argnums=0)
        def write(stack, i, kk):
            layer = _init_layer(kk, shapes)
            return {m: lax.dynamic_update_index_in_dim(stack[m], layer[m], i, 0) for m in stack}

        one = jax.eval_shape(lambda kk: _init_layer(kk, shapes), k)
        stack = {m: jnp.zeros((n,) + a.shape, a.dtype) for m, a in one.items()}
        for i, kk in enumerate(jax.random.split(k, n)):
            stack = write(stack, i, kk)
        params[name] = stack
    return params


def init_decoder(key, config: Dict[str, Any], dense, moe):
    """:func:`init_stacks` of a dense stack and an expert stack."""
    return init_stacks(key, config, {"dense": dense, "moe": moe})
