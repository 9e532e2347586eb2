"""A decoder with multi-head latent attention (MLA) and many small routed
experts: the DeepSeek-V3 family's layer, as JoyAI-LLM-Flash
(``model_type`` ``joyai_llm_flash``) publishes it.

Queries and keys/values pass through low-rank latents, each with its own
norm: ``c_q = Nq(x W_qa)``, ``q = c_q W_qb`` gives every head
``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``, ``Nkv(c_kv) W_kvb`` gives
every head ``[k_nope | v]``. Positions are rotary on interleaved pairs
``(2i, 2i+1)`` of ``q_rope`` and of ``k_r``, which is one vector a token
shared by all heads; a head's key is ``[k_nope | k_r]``, wider than its
value, and scores are scaled by the key's width. Two norms a layer
(``a = h + Attn(N1(h))``, ``h' = a + FFN(N2(a))``), no embedding scale;
``first_k_dense_replace`` leading layers have a dense SwiGLU, the rest
routed experts (:func:`moe_decoder.routed_experts`).

Inference only, whole rows in, as :mod:`afmoe`: :func:`mla_moe_apply`
takes ``(rows, S)`` token ids and returns each row's last position and each
expert layer's load; the layers of a kind are stacked and run under one
``lax.scan``, the routed experts' stacks closed over whole and read where
they lie (:func:`moe_decoder.experts_in_place`). Attention runs in its
up-projected form (per-head keys and values rebuilt from the latent): the
absorbed form, which attends over the latent itself, costs ``(kv_lora_rank
+ rope) + kv_lora_rank`` multiply-adds a key where this costs ``(nope +
rope) + v``, and pays only where a cache is read a token at a time, which
nothing here does. The multi-token
prediction module (``num_nextn_predict_layers``) feeds a training loss or a
drafting step and is not built.

``config`` holds the published ``config.json`` keys (``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``,
``rms_norm_eps``, ``intermediate_size``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``, ``first_k_dense_replace``, ``vocab_size``) and
``layers``: how many layers, from the first, are held here. Precision,
``config["product_dtype"]`` and ``config["interpret"]`` are
:mod:`moe_decoder`'s and :mod:`afmoe`'s.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from mmlspark_tpu.models.moe_decoder import (
    dot,
    experts_in_place,
    init_decoder,
    last_position,
    norm,
    routed_experts,
    swiglu,
)
from mmlspark_tpu.ops.attention import blocked_attention


def layer_counts(config: Dict[str, Any]):
    """-> (dense layers held, expert layers held)."""
    dense = min(config["first_k_dense_replace"], config["layers"])
    return dense, config["layers"] - dense


def span_tags(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the ``lm.featurize`` span says of a configuration of this family;
    ``latent_width`` is what a cache would hold a token a layer."""
    return {"layers": config["layers"], "experts": config["n_routed_experts"], "attention": "latent",
            "latent_width": config["kv_lora_rank"] + config["qk_rope_head_dim"]}


def _layer_shapes(c, dense: bool):
    """{name: (shape, fan-in or None for a norm's scale)} of one layer."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    out = {"w_qa": ((D, rq), D), "w_qb": ((rq, H * (nope + rope)), rq),
           "w_kva": ((D, rkv + rope), D), "w_kvb": ((rkv, H * (nope + dv)), rkv),
           "wo": ((H * dv, D), H * dv),
           "q_norm": ((rq,), None), "kv_norm": ((rkv,), None),
           "norm1": ((D,), None), "norm2": ((D,), None)}
    if dense:
        F = c["intermediate_size"]
        out.update(w_gate=((D, F), D), w_up=((D, F), D), w_down=((F, D), F))
    else:
        E, F = c["n_routed_experts"], c["moe_intermediate_size"]
        Fs = F * c["n_shared_experts"]
        out.update(router=((D, E), D), router_bias=((E,), None),
                   e_gate=((E, D, F), D), e_up=((E, D, F), D), e_down=((E, F, D), F),
                   s_gate=((D, Fs), D), s_up=((D, Fs), D), s_down=((Fs, D), Fs))
    return out


def init_mla_moe(key, config: Dict[str, Any]):
    """Seeded weights, made on the device in bfloat16 a layer at a time
    (:func:`moe_decoder.init_decoder`)."""
    dense, moe = layer_counts(config)
    return init_decoder(key, config, (_layer_shapes(config, True), dense),
                        (_layer_shapes(config, False), moe))


def rope_interleaved(x, theta):
    """x: (rows, S, heads, r), float32. Pair ``i`` is ``(2i, 2i + 1)``,
    turned by ``position x theta ** (-2i / r)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _attention(p, x, c, dt):
    """x: (rows, S, hidden), normalised."""
    B, S, _ = x.shape
    H, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    with jax.named_scope("mla_latent"):
        c_q = norm(dot(x, p["w_qa"], dt), p["q_norm"], eps).astype(jnp.bfloat16)
        kva = dot(x, p["w_kva"], dt)
        c_kv = norm(kva[..., :rkv], p["kv_norm"], eps).astype(jnp.bfloat16)
    with jax.named_scope("mla_up"):
        q = dot(c_q, p["w_qb"], dt).reshape(B, S, H, nope + rope)
        kv = dot(c_kv, p["w_kvb"], dt).reshape(B, S, H, nope + dv)
        q = jnp.concatenate([q[..., :nope], rope_interleaved(q[..., nope:], theta)], axis=-1)
        # one rotary key a token, the same for every head
        k_r = rope_interleaved(kva[..., None, rkv:], theta)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (B, S, H, rope))], axis=-1)
        q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, kv[..., nope:]))
    with jax.named_scope("attn_full"):
        out = blocked_attention(q, k, v, interpret=bool(c.get("interpret", False)))
    return dot(out.reshape(B, S, H * dv), p["wo"], dt)


def mla_moe_apply(params, tokens, config: Dict[str, Any]):
    """tokens: (rows, S) integers. -> ``hidden`` (rows, hidden) float32, the
    last position after the final norm; ``logits`` (rows, vocabulary)
    float32, the untied head applied to it; ``expert_load`` (rows, expert
    layers, experts) int32, the tokens of the row each expert received (a
    layer's sum is ``S x num_experts_per_tok``: no token is dropped)."""
    c = config
    dt = jnp.dtype(c.get("product_dtype", "bfloat16"))
    eps = c["rms_norm_eps"]
    dense, moe = layer_counts(c)
    h = params["embed"][tokens]

    def layer(h, p, ffn):
        a = _attention(p, norm(h, p["norm1"], eps).astype(jnp.bfloat16), c, dt)
        a = (h.astype(jnp.float32) + a).astype(jnp.bfloat16)
        y, load = ffn(p, norm(a, p["norm2"], eps).astype(jnp.bfloat16))
        return (a.astype(jnp.float32) + y).astype(jnp.bfloat16), load

    def dense_layer(h, p):
        return layer(h, p, lambda p, x: (swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None))

    E = c["n_routed_experts"]
    whole, scanned = experts_in_place(params["moe"], dt)

    def moe_layer(h, xs):
        p, number = xs
        return layer(h, p, lambda p, x: routed_experts(
            {**p, **whole}, x, c["num_experts_per_tok"], c["routed_scaling_factor"], dt, first_group=number * E,
            interpret=bool(c.get("interpret", False))))

    if dense:
        h, _ = lax.scan(dense_layer, h, params["dense"])
    if moe:
        h, loads = lax.scan(moe_layer, h, (scanned, jnp.arange(moe)))
        loads = loads.transpose(1, 0, 2)
    else:
        loads = jnp.zeros((tokens.shape[0], 0, c["n_routed_experts"]), jnp.int32)
    hidden, logits = last_position(params, h, eps, dt)
    return {"hidden": hidden, "logits": logits, "expert_load": loads}
