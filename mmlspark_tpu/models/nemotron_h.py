"""A hybrid decoder of the ``nemotron_h`` family (NVIDIA Nemotron-H /
Nemotron 3): Mamba-2 state-space mixers, a few grouped-attention blocks and
blocks of many small routed experts, **one sublayer a block**.

Every block is ``h <- h + f(RMSNorm(h))`` with ``f`` chosen by the block's
letter in the published ``hybrid_override_pattern``:

- ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = x W_in``; ``xBC <-
  silu(conv(xBC))``, a depthwise causal convolution over time with a bias;
  ``xBC`` splits into ``x`` (heads x head width), ``B`` and ``C`` (groups x
  state width, a head reading group ``h // (heads / groups)``); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the selective scan ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
  (:func:`mmlspark_tpu.ops.ssd.ssd_scan`, chunked); ``u = y * silu(z)``,
  RMS-normalised inside each group of channels (the gate first, then the
  norm) times a learned scale; ``u W_out``. ``expand`` is not read: the
  inner width is ``mamba_num_heads x mamba_head_dim``.
- ``*``, attention: grouped key/value heads, causal softmax at
  ``1/sqrt(head_dim)``, no bias, **no positions** (the family's published
  description uses none; the state-space blocks carry order).
- ``E``, experts: sigmoid router scores, the ``num_experts_per_tok``
  largest of score + balancing bias, weighed by the scores alone over their
  sum times ``routed_scaling_factor``; an expert is **two** matrices with
  ``relu(x)^2`` between them, no gate; one shared expert of its own width
  beside them (:func:`moe_decoder.routed_experts`). The grouped products
  read every unit's experts where they lie, one stack of ``units x
  experts`` groups at the published width, told where this unit's stand.

Read as units the pattern is regular: ``M``, then an optional ``*``, then
``E``. The blocks of a kind are stacked and the units run under one
``lax.scan`` (the attention block under a ``lax.cond``, its weights looked
up by index, so a unit without one holds nothing; the routed experts' two
stacks closed over whole, the unit's number scanned), so each kernel is one
operation of the program. A pattern that does not end on a whole unit is an
error. No embedding scale; the final norm and the untied head at each row's
last position.

Inference only, whole rows in, as :mod:`afmoe`. Not built: the one-token
recurrent step and any cache across calls, of convolution and scan state or
of keys and values (the system has no generation loop;
``num_logits_to_keep`` is a generation setting).

``config`` holds the published ``config.json`` keys
(``hybrid_override_pattern``, ``hidden_size``, ``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
``chunk_size``, ``time_step_min`` / ``_max`` / ``_floor``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``n_routed_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``routed_scaling_factor``,
``layer_norm_epsilon``, ``vocab_size``) and ``layers``: how many blocks of
the pattern, from the first, are held here. Precision is
:mod:`moe_decoder`'s; besides, the convolution, ``dt``, every decay and the
scan's carried state are float32, and ``z``, ``xBC`` after its activation
and the scan's ``y`` are bfloat16. ``config["product_dtype"]`` and
``config["interpret"]`` as in :mod:`afmoe`.
"""

from __future__ import annotations

import re
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
    relu2,
    routed_experts,
)
from mmlspark_tpu.ops.attention import blocked_attention
from mmlspark_tpu.ops.ssd import ssd_scan

_UNITS = re.compile(r"(M\*?E)*")
# Seeded weights only: a query matrix's standard deviation over another seeded matrix's. A score q.k / sqrt(head_dim)
# then has a standard deviation near 4.5 and a position's softmax lies on a handful of its keys, as a trained model's
# does. At 1 it lies on thousands of 16,384 keys, the block returns the mean of the values, a hundredth of the stream,
# and a comparison cannot tell the block from none (PERF.md 6a, PR 33).
_QUERY_SCALE = 4.0


def units(config: Dict[str, Any]):
    """-> for each unit held (``M``, an optional ``*``, ``E``): has it an
    attention block? From ``hybrid_override_pattern[:layers]`` alone."""
    held = config["hybrid_override_pattern"][: config["layers"]]
    if len(held) != config["layers"] or not _UNITS.fullmatch(held):
        raise ValueError(f"blocks {held!r} ({config['layers']} of the pattern): not whole units of M, an optional *, E")
    return ["*" in unit for unit in re.findall(r"M\*?E", held)]


def state_width(config: Dict[str, Any]) -> int:
    """What a cache would hold a row a mixer, whatever the row's length."""
    return config["mamba_num_heads"] * config["mamba_head_dim"] * config["ssm_state_size"]


def span_tags(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the ``lm.featurize`` span says of a configuration of this family;
    ``state_width`` is what a cache would hold a row a mixer."""
    attention = units(config)
    return {"layers": config["layers"], "experts": config["n_routed_experts"], "attention": "grouped",
            "mixer_layers": len(attention), "expert_layers": len(attention),
            "attention_layers": sum(attention), "state_width": state_width(config)}


def _inverse_softplus_of_a_step(c):
    """``dt_bias``: the inverse softplus of a step drawn log-uniformly in
    [``time_step_min``, ``time_step_max``] and floored (the published recipe)."""
    lo, hi, floor = c["time_step_min"], c["time_step_max"], c["time_step_floor"]

    def draw(key, shape):
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(lo), jnp.log(hi)))
        step = jnp.maximum(step, floor)
        return step + jnp.log(-jnp.expm1(-step))

    return draw


def _shapes(c):
    """{kind: {name: (shape, fan-in, None for a norm's scale, or a recipe)}}
    of one block of each kind."""
    D = c["hidden_size"]
    inner, state = c["mamba_num_heads"] * c["mamba_head_dim"], c["n_groups"] * c["ssm_state_size"]
    conv, heads = inner + 2 * state, c["mamba_num_heads"]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    E, F, Fs = c["n_routed_experts"], c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    away_from_one = lambda key, shape: jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    return {
        "mixer": {
            "norm": ((D,), None), "in_proj": ((D, inner + conv + heads), D),
            "conv_w": ((conv, c["conv_kernel"]), c["conv_kernel"]),
            "conv_b": ((conv,), lambda key, shape: (0.1 * jax.random.normal(key, shape)).astype(jnp.bfloat16)),
            "A_log": ((heads,), lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))),
            "dt_bias": ((heads,), _inverse_softplus_of_a_step(c)), "D": ((heads,), away_from_one),
            "gate_norm": ((inner,), None), "out_proj": ((inner, D), inner),
        },
        "attention": {
            "norm": ((D,), None), "wq": ((D, H * hd), D / _QUERY_SCALE ** 2), "wk": ((D, KV * hd), D),
            "wv": ((D, KV * hd), D), "wo": ((H * hd, D), H * hd),
        },
        "experts": {
            "norm": ((D,), None), "router": ((D, E), D), "router_bias": ((E,), None),
            "e_up": ((E, D, F), D), "e_down": ((E, F, D), F), "s_up": ((D, Fs), D), "s_down": ((Fs, D), Fs),
        },
    }


def init_nemotron_h(key, config: Dict[str, Any]):
    """Seeded weights, made on the device a block at a time
    (:func:`moe_decoder.init_stacks`): stacks ``mixer`` and ``experts`` (one
    block a unit) and ``attention`` (the attention blocks held, in order).
    ``A_log = log(U[1, 16))``, ``dt_bias`` by the published recipe, ``D``
    uniform in [0.5, 1.5): float32 leaves, as the routing bias; the
    convolution's bias normal x 0.1; an attention block's query matrix four
    times a seeded matrix's, so that its softmax is sharp."""
    attention = units(config)
    shapes = _shapes(config)
    return init_stacks(key, config, {
        "mixer": (shapes["mixer"], len(attention)), "attention": (shapes["attention"], sum(attention)),
        "experts": (shapes["experts"], len(attention))})


def _mixer(p, x, c, dt):
    """x: (rows, S, hidden), normalised."""
    B, S, _ = x.shape
    H, P, G, N = c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"]
    inner = H * P
    mixed = dot(x, p["in_proj"], dt)  # [z | xBC | dt]
    z = mixed[..., :inner].astype(jnp.bfloat16)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(causal_conv(mixed[..., inner:-H], p["conv_w"], p["conv_b"])).astype(jnp.bfloat16)
    with jax.named_scope("ssm_scan"):
        step = jax.nn.softplus(mixed[..., -H:] + p["dt_bias"])
        y = ssd_scan(
            xbc[..., :inner].reshape(B, S, H, P), step, -jnp.exp(p["A_log"]),
            xbc[..., inner:inner + G * N].reshape(B, S, G, N), xbc[..., inner + G * N:].reshape(B, S, G, N),
            p["D"], chunk=c["chunk_size"], interpret=bool(c.get("interpret", False)))
    with jax.named_scope("ssm_gate_norm"):
        gated = y.reshape(B, S, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        scale = p["gate_norm"].reshape(G, inner // G)
        u = norm(gated.reshape(B, S, G, inner // G), scale, c["layer_norm_epsilon"]).reshape(B, S, inner)
    return dot(u, p["out_proj"], dt)


def _attention(p, x, c, dt):
    """x: (rows, S, hidden), normalised. No positions are added."""
    B, S, _ = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q, k, v = (dot(x, p[w], dt).reshape(B, S, n, hd).astype(jnp.bfloat16)
               for w, n in (("wq", H), ("wk", KV), ("wv", KV)))
    with jax.named_scope("attn_full"):
        out = blocked_attention(q, k, v, interpret=bool(c.get("interpret", False)))
    return dot(out.reshape(B, S, H * hd), p["wo"], dt)


def nemotron_h_apply(params, tokens, config: Dict[str, Any]):
    """tokens: (rows, S) integers. -> ``hidden`` (rows, hidden) float32, the
    last position after the final norm; ``logits`` (rows, vocabulary)
    float32, the untied head applied to it; ``expert_load`` (rows, expert
    blocks held, experts) int32, the tokens of the row each expert received
    (a block's sum is ``S x num_experts_per_tok``: no token is dropped)."""
    c = config
    dt = jnp.dtype(c.get("product_dtype", "bfloat16"))
    eps = c["layer_norm_epsilon"]
    attention = units(c)
    h = params["embed"][tokens]

    def normed(h, p):
        return norm(h, p["norm"], eps).astype(jnp.bfloat16)

    def added(h, y):  # the stream stays bfloat16
        return (h.astype(jnp.float32) + y).astype(jnp.bfloat16)

    def attend(h, index):
        p = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, keepdims=False), params["attention"])
        return added(h, _attention(p, normed(h, p), c, dt))

    # A unit's two expert matrices sliced out of their stacks by the scan are copies, 1.2 GiB each a unit a dispatch
    # at the published size, the first transposed on the way. Products that take the matrices as they are stored read
    # the whole stacks where they lie instead, every unit's groups one after another, told where this unit's stand.
    # (Inputs narrower than stored are rounded into a copy whatever is done, so those stacks go through the scan.)
    E = c["n_routed_experts"]
    whole = {name: a.reshape((-1,) + a.shape[2:])  # a bitcast
             for name, a in params["experts"].items() if name in ("e_up", "e_down") and a.dtype == dt}
    scanned = {name: a for name, a in params["experts"].items() if name not in whole}

    def unit(h, xs):
        mixer, experts, attends, index, number = xs
        h = added(h, _mixer(mixer, normed(h, mixer), c, dt))
        if any(attention):
            h = lax.cond(attends, attend, lambda h, index: h, h, index)
        y, load = routed_experts({**experts, **whole}, normed(h, experts), c["num_experts_per_tok"],
                                 c["routed_scaling_factor"], dt, relu2, number * E, bool(c.get("interpret", False)))
        return added(h, y), load

    if attention:
        has = jnp.asarray(attention)
        index = jnp.cumsum(has) - has  # an attention block's place in its stack
        h, loads = lax.scan(unit, h, (params["mixer"], scanned, has, index, jnp.arange(len(attention))))
        loads = loads.transpose(1, 0, 2)
    else:
        loads = jnp.zeros((tokens.shape[0], 0, c["n_routed_experts"]), jnp.int32)
    hidden, logits = last_position(params, h, eps, dt)
    return {"hidden": hidden, "logits": logits, "expert_load": loads}
