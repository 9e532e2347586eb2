"""The language model of the ``KeyeVL2`` family (Kwai Keye-VL-2.0): in every
layer grouped attention whose keys each query chooses by a learned indexer
(DeepSeek's sparse attention), then 128 softmax-routed experts, top-8, with
no shared expert; an untied head.

A layer, with ``h`` the residual stream and ``x = RMSNorm_1(h)``:

- attention: ``q = RMSNorm_q(x W_q)`` and ``k = RMSNorm_k(x W_k)`` over each
  head of 128, ``v = x W_v``; rotary positions at ``rope_theta`` on pairs
  ``(i, i + 64)``. ``rope_scaling.mrope_section`` splits the 64 pairs into
  time, height and width; a text row's three position ids are one position,
  so it is plain rotary here;
- the indexer (``sa_config``): ``qi[t, j] = RoPE(x_t W_iq)_j`` over ``H_I``
  heads of ``d_I``, one key ``ki[s] = RoPE(LayerNorm(x_s W_ik))``, and
  ``w[t, j] = (x_t W_iw)_j / sqrt(H_I d_I)``; rotary on the first half of an
  indexer vector, pairs ``(i, i + d_I/4)``. The score ``I[t, s] = sum_j w[t, j]
  relu(qi[t, j] . ki[s])`` for ``s <= t``;
- query ``t`` keeps the ``min(t + 1, topk)`` keys of the largest score, ties
  to the lower position, one selection for all 32 heads; causal softmax at
  ``1/sqrt(128)`` over the kept keys alone, query head ``h`` reading
  key/value head ``h // 8`` (:mod:`ops.sparse_attention`, kernels
  ``dsa_index`` and ``dsa_attn``); ``h <- h + o W_o``;
- ``h <- h + sum_{e in top-8} p_e SwiGLU_e(RMSNorm_2(h))``, ``p`` the
  float32 softmax of ``RMSNorm_2(h) W_r`` over all 128 experts, renormalised
  over the eight chosen (``norm_topk_prob``).

No scaling of the embedding; after the last layer one RMS norm and the
untied head at each row's last position. All layers are alike, so one stack
and one ``lax.scan`` serve; the routed experts' stacks are closed over whole
and read where they lie (:func:`moe_decoder.experts_in_place`).

Inference only, whole token rows in, as :mod:`afmoe`. Not built: the vision
tower and its merger (their sizes are not in the language model's config),
so no image positions and no three-part position ids; no generation loop, so
no cache of keys, values or indexer keys. A published switch this module
does not build (a sliding window, dense layers among the expert ones, a
tied head, projection biases, more than one indexer key a position, a
rotary scaling other than the default) is an error. Assumed where the
published row is silent, following DeepSeek-V3.2's indexer: the indexer
query is taken from ``x`` (the model has no query latent), the key's
LayerNorm has a weight and a bias at ``rms_norm_eps``, and V3.2's Hadamard
rotation before float8 is left out (orthogonal: it changes no product).
``q_chunk_size`` / ``kv_chunk_size`` are a tiling, which changes no result.

``config`` holds the published ``config.json`` keys and ``layers``: how many
layers, from the first, are held here. Precision is :mod:`moe_decoder`'s;
the indexer's queries and key are bfloat16, its scores, ``w`` and the
selection float32. ``config["product_dtype"]`` and ``config["interpret"]``
as in :mod:`afmoe`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from mmlspark_tpu.models.moe_decoder import (
    dot,
    experts_in_place,
    init_stacks,
    last_position,
    norm,
    rope,
    routed_experts,
)
from mmlspark_tpu.ops.sparse_attention import dsa_attn, dsa_index

# What this module builds of the published switches; another value is an error.
_BUILT = {"attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [], "use_sliding_window": False,
          "sliding_window": None, "tie_word_embeddings": False, "norm_topk_prob": True, "hidden_act": "silu"}
# Seeded weights only. q_norm's and k_norm's scales are drawn from [1, 1.8) and not from a norm's [0.5, 1.5): a score
# q.k / sqrt(128) of two normalised heads then has a standard deviation near 2 (E[scale^2]) and not near 1, so the
# softmax over 2,048 kept keys rests on some 40 of them (2,048 exp(-4)), and which keys a query kept decides what it
# reads; at a norm's scales it averages over hundreds, and attention over every key a query sees reads much as the
# program. Sharper (a deviation of 4: one key) a rounding that moves one key across the cut moves the row.
_QK_SCALES = (1.0, 1.8)


def _checked(config: Dict[str, Any]) -> Dict[str, Any]:
    for key, built in _BUILT.items():
        if config.get(key, built) != built:
            raise ValueError(f"{key} {config[key]!r}: only {built!r} is built")
    sa = config["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError(f"sa_config indexer_num_kv_heads {sa['indexer_num_kv_heads']}: only one indexer key is built")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError(f"rope_scaling {scaling!r}: only the default rotary is built")
    if sum(scaling.get("mrope_section", [config["head_dim"] // 2])) != config["head_dim"] // 2:
        raise ValueError(f"mrope_section {scaling['mrope_section']} does not split the head's {config['head_dim'] // 2} pairs")
    if config.get("num_local_experts", config["num_experts"]) != config["num_experts"]:
        raise ValueError("num_local_experts and num_experts differ")
    return config


def span_tags(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the ``lm.featurize`` span says of a configuration of this family;
    ``indexer_width``, the width of the one indexer key, is what a cache of
    indexer keys would hold a token a layer."""
    sa = _checked(config)["sa_config"]
    return {"layers": config["layers"], "experts": config["num_experts"], "attention": "grouped",
            "sparse_topk": sa["topk"], "indexer_heads": sa["indexer_num_heads"],
            "indexer_width": sa["indexer_head_dim"]}


def _shapes(c):
    """{name: (shape, fan-in, None for a norm's scale, or a recipe)} of one layer."""
    D, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    E, F = c["num_experts"], c["moe_intermediate_size"]
    HI, dI = c["sa_config"]["indexer_num_heads"], c["sa_config"]["indexer_head_dim"]
    sharp = lambda key, shape: jax.random.uniform(key, shape, jnp.float32, *_QK_SCALES).astype(jnp.bfloat16)
    return {
        "norm1": ((D,), None), "norm2": ((D,), None),
        "wq": ((D, H * hd), D), "wk": ((D, KV * hd), D), "wv": ((D, KV * hd), D), "wo": ((H * hd, D), H * hd),
        "q_norm": ((hd,), sharp), "k_norm": ((hd,), sharp),
        "iq": ((D, HI * dI), D), "ik": ((D, dI), D), "iw": ((D, HI), D), "ik_norm": ((dI,), None),
        "ik_bias": ((dI,), lambda key, shape: (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)),
        "router": ((D, E), D),
        "e_gate": ((E, D, F), D), "e_up": ((E, D, F), D), "e_down": ((E, F, D), F),
    }


def init_keye_vl2(key, config: Dict[str, Any]):
    """Seeded weights, made on the device a layer at a time
    (:func:`moe_decoder.init_stacks`): one stack ``layers`` and an untied
    ``head``; q_norm's and k_norm's scales from [1, 1.8), so that the
    softmax is sharper than at a norm's scales, the indexer key's LayerNorm
    bias normal x 0.1."""
    return init_stacks(key, config, {"layers": (_shapes(_checked(config)), config["layers"])})


def _rope_first(x, theta, n: int):
    """Rotary positions on the first ``n`` of the last axis, pairs ``(i, i + n/2)``."""
    return jnp.concatenate([rope(x[..., :n], theta), x[..., n:]], axis=-1)


def _layer_norm(x, weight, bias, eps):
    x = x - x.mean(axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32) + bias.astype(
        jnp.float32)


def _attention(p, x, c, dt):
    """x: (rows, S, hidden), normalised. -> (the attention's output (rows, S,
    hidden) float32, each query's kept keys as :func:`dsa_index`'s bitmask)."""
    B, S, _ = x.shape
    H, KV, hd, eps = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["rms_norm_eps"]
    HI, dI, topk = (c["sa_config"][k] for k in ("indexer_num_heads", "indexer_head_dim", "topk"))
    theta = float(c["rope_theta"])
    interpret = bool(c.get("interpret", False))
    q = norm(dot(x, p["wq"], dt).reshape(B, S, H, hd), p["q_norm"], eps)
    k = norm(dot(x, p["wk"], dt).reshape(B, S, KV, hd), p["k_norm"], eps)
    v = dot(x, p["wv"], dt).reshape(B, S, KV, hd).astype(jnp.bfloat16)
    with jax.named_scope("dsa_indexer"):
        qi = _rope_first(dot(x, p["iq"], dt).reshape(B, S, HI, dI), theta, dI // 2).astype(jnp.bfloat16)
        ki = _layer_norm(dot(x, p["ik"], dt), p["ik_norm"], p["ik_bias"], eps)
        ki = _rope_first(ki[:, :, None], theta, dI // 2)[:, :, 0].astype(jnp.bfloat16)
        w = dot(x, p["iw"], dt) * (HI * dI) ** -0.5
        words = dsa_index(qi, w, ki, topk, interpret=interpret)
    with jax.named_scope("dsa_attention"):
        q, k = (rope(a, theta).astype(jnp.bfloat16) for a in (q, k))
        out = dsa_attn(q, k, v, words, interpret=interpret)
    return dot(out.reshape(B, S, H * hd), p["wo"], dt), words


def keye_vl2_apply(params, tokens, config: Dict[str, Any]):
    """tokens: (rows, S) integers. -> ``hidden`` (rows, hidden) float32, the
    last position after the final norm; ``logits`` (rows, vocabulary)
    float32, the untied head applied to it; ``expert_load`` (rows, layers,
    experts) int32, the tokens of the row each expert received (a layer's
    sum is ``S x num_experts_per_tok``); ``key_selection`` (rows, layers, 1
    + words) int32: the keys the layer's selection kept over the row's
    queries, then the last position's kept keys as a bitmask
    (:func:`ops.sparse_attention.unpack_keys`)."""
    c = _checked(config)
    dt = jnp.dtype(c.get("product_dtype", "bfloat16"))
    eps, E = c["rms_norm_eps"], c["num_experts"]
    h = params["embed"][tokens]
    whole, scanned = experts_in_place(params["layers"], dt)
    softmax = functools.partial(jax.nn.softmax, axis=-1)

    def layer(h, xs):
        p, number = xs
        a, words = _attention(p, norm(h, p["norm1"], eps).astype(jnp.bfloat16), c, dt)
        h = (h.astype(jnp.float32) + a).astype(jnp.bfloat16)
        y, load = routed_experts({**p, **whole}, norm(h, p["norm2"], eps).astype(jnp.bfloat16),
                                 c["num_experts_per_tok"], 1.0, dt, first_group=number * E,
                                 interpret=bool(c.get("interpret", False)), gate=softmax)
        kept = lax.population_count(words).sum(axis=(1, 2), dtype=jnp.int32)
        selection = jnp.concatenate([kept[:, None], words[:, -1]], axis=-1)
        return (h.astype(jnp.float32) + y).astype(jnp.bfloat16), (load, selection)

    h, (loads, selection) = lax.scan(layer, h, (scanned, jnp.arange(c["layers"])))
    hidden, logits = last_position(params, h, eps, dt)
    return {"hidden": hidden, "logits": logits, "expert_load": loads.transpose(1, 0, 2),
            "key_selection": selection.transpose(1, 0, 2)}
