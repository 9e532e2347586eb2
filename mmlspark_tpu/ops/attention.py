"""Blocked causal attention inside a model: grouped key/value heads (a
group of one too), values of another width than keys, an optional sliding
window, online softmax, one Pallas kernel.

``ring_attention.attention_reference`` materialises ``(heads, S, S)`` scores
(8.6 GB a row at 32 heads x 8,192 tokens). Here a grid step holds one block
of queries for all the query heads that share a key/value head (so the
scores' product has ``group x block`` rows) and that head's whole key and
value sequence in VMEM; an inner loop meets one key/value block at a time
and keeps a running maximum, denominator and accumulator. Blocks above the
diagonal and, with a window, blocks that lie wholly below the band are never
computed: the loop's bounds are the band's, not a mask.

Measured on a v5e at 4 rows x 8,192 tokens x 32 heads over 4 of 128 (PERF.md,
PR 27): 24.9 ms full and 14.4 ms with a 2,048 window, against 88.2 and
46.6 ms for the same loop written with ``lax`` (its float32 scores pass
through HBM three times a block).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q, BLOCK_K = 128, 512
_LOW = -1e30  # stands for minus infinity where a difference of two must stay finite


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _kernel(q_ref, k_ref, v_ref, o_ref, *, window, scale, bq, bk):
    """One block of ``bq`` queries of every head of a group, against the
    key/value blocks ``first..last`` of its band."""
    i = pl.program_id(2)
    G, _, d = q_ref.shape
    d_v = v_ref.shape[-1]
    q = q_ref[...].reshape(G * bq, d)
    q_pos = lax.broadcasted_iota(jnp.int32, (G * bq, bk), 0) % bq + i * bq
    k_off = lax.broadcasted_iota(jnp.int32, (G * bq, bk), 1)
    first = jnp.maximum(i * bq - (window - 1), 0) // bk
    last = (i * bq + bq - 1) // bk

    def meet(j, carry):
        acc, top, denom = carry
        k = k_ref[pl.ds(j * bk, bk), :]
        v = v_ref[pl.ds(j * bk, bk), :]
        scores = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        k_pos = k_off + j * bk
        seen = (k_pos <= q_pos) & (k_pos > q_pos - window)
        scores = jnp.where(seen, scores, _LOW)
        new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        weights = jnp.where(seen, jnp.exp(scores - new_top), 0.0)
        denom = denom * shrink + weights.sum(axis=-1, keepdims=True)
        acc = acc * shrink + jnp.dot(weights.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, new_top, denom

    start = (jnp.zeros((G * bq, d_v), jnp.float32), jnp.full((G * bq, 1), _LOW, jnp.float32),
             jnp.zeros((G * bq, 1), jnp.float32))
    acc, _, denom = lax.fori_loop(first, last + 1, meet, start)
    o_ref[...] = (acc / denom).reshape(G, bq, d_v).astype(o_ref.dtype)


def blocked_attention(q, k, v, window: Optional[int] = None, *, block: int = BLOCK_K,
                      interpret: bool = False):
    """Causal attention, position ``i`` seeing ``j <= i`` and, with a
    ``window``, only ``j > i - window``.

    ``q`` is ``(batch, S, heads, d)``, ``k`` ``(batch, S, kv_heads, d)`` and
    ``v`` ``(batch, S, kv_heads, d_v)``, with ``heads`` a multiple of
    ``kv_heads``: query head ``h`` reads key/value head
    ``h // (heads // kv_heads)``. A value head may be narrower or wider than
    a key head (latent attention: keys of 192, values of 128). Scores are
    ``q . k / sqrt(d)``, the key's width; products take the inputs' dtype
    and sum in float32; maximum, exponentials and denominator are float32.
    ``S`` need not be a multiple of ``block`` (the key/value block; a query
    block is a quarter of it). A key/value head's whole sequence is held in
    VMEM, which bounds ``S x (d + d_v)`` (65,536 x 256 in bfloat16).
    ``interpret`` runs the kernel in the Pallas interpreter, for a backend
    that is no TPU; it is never chosen here. Returns
    ``(batch, S, heads, d_v)`` in ``q``'s dtype."""
    B, S, H, d = q.shape
    KV, d_v = k.shape[2], v.shape[-1]
    if H % KV or k.shape != (B, S, KV, d) or v.shape != (B, S, KV, d_v):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: not grouped heads of one length")
    G = H // KV
    bk = min(block, _round_up(S, 128))
    bq = min(BLOCK_Q, bk)
    if bk % bq:
        raise ValueError(f"block {block}: a key/value block holds whole query blocks of {bq}")
    padded = _round_up(S, bk)
    if padded != S:  # padded keys lie after every real query; padded queries are cut off
        q, k, v = (jnp.pad(a, ((0, 0), (0, padded - S), (0, 0), (0, 0))) for a in (q, k, v))
    def per_queries(width):
        return pl.BlockSpec((None, None, G, bq, width), lambda b, h, i: (b, h, 0, i, 0))

    def whole_sequence(width):
        return pl.BlockSpec((None, None, padded, width), lambda b, h, i: (b, h, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, window=padded if window is None else int(window),
                          scale=d ** -0.5, bq=bq, bk=bk),
        grid=(B, KV, padded // bq),
        in_specs=[per_queries(d), whole_sequence(d), whole_sequence(d_v)],
        out_specs=per_queries(d_v),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, padded, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 2**20),
        interpret=interpret,
        name="attn_full" if window is None else "attn_window",
    )(
        q.reshape(B, padded, KV, G, d).transpose(0, 2, 3, 1, 4),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
    )
    return out.transpose(0, 3, 1, 2, 4).reshape(B, padded, H, d_v)[:, :S]
