"""Ring attention — sequence-parallel attention over the mesh ``seq`` axis.

Long-context support is first-class in this framework: sequences longer
than one chip's HBM shard over the ``seq`` mesh axis, and attention runs
as a RING — each device holds its local Q/K/V block, K/V blocks rotate
around the ring via ``lax.ppermute`` (neighbor exchanges ride the ICI
torus), and every device accumulates its queries' attention over all
blocks with the numerically-stable ONLINE softmax (flash-attention's
running max/denominator), so the full (S, S) score matrix never exists.

Communication: (S/p) x d K/V tiles move p-1 times per device —
all bandwidth on nearest-neighbor ICI links, overlapping compute, the
standard TPU ring-collective shape. The causal variant masks by GLOBAL
position, so rotated blocks mask correctly regardless of ring step.

API:
- :func:`ring_attention` — shard_map'd entry over a mesh with a ``seq``
  axis; inputs (B, S, H, D) sharded on S.
- :func:`attention_reference` — O(S^2) single-device reference used by
  tests and small inputs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.parallel.mesh import AXIS_SEQ


def attention_reference(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Plain O(S^2) attention: q/k/v (B, S, H, D) -> (B, S, H, D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # (B, H, S, S)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    if causal:
        s, t = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s, t), dtype=bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _block_attend(q, k, v, q_pos, k_pos, acc, row_max, denom, causal, scale):
    """One ring step: attend local q to one K/V block with online softmax.

    q (B, Sq, H, D); k/v (B, Sk, H, D); q_pos (Sq,), k_pos (Sk,) GLOBAL
    positions; acc (B, Sq, H, D) running numerator; row_max/denom
    (B, Sq, H) running stats. Returns updated (acc, row_max, denom)."""
    scores = jnp.einsum("bshd,bthd->bhst", q, k) * scale  # (B, H, Sq, Sk)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # (Sq, Sk) global causal
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    block_max = scores.max(axis=-1)  # (B, H, Sq)
    new_max = jnp.maximum(row_max, block_max.transpose(0, 2, 1))  # (B, Sq, H)
    # guard: rows with no visible keys anywhere yet keep -inf max
    safe_max = jnp.where(jnp.isfinite(new_max), new_max, 0.0)
    correction = jnp.exp(
        jnp.where(jnp.isfinite(row_max), row_max - safe_max, -jnp.inf)
    )  # (B, Sq, H)
    probs = jnp.exp(
        scores - safe_max.transpose(0, 2, 1)[..., None]
    )  # (B, H, Sq, Sk); -inf rows -> 0
    block_num = jnp.einsum("bhst,bthd->bshd", probs, v)
    block_den = probs.sum(axis=-1).transpose(0, 2, 1)  # (B, Sq, H)
    acc = acc * correction[..., None] + block_num
    denom = denom * correction + block_den
    return acc, new_max, denom


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = False,
    scale: Optional[float] = None,
):
    """Sequence-parallel attention: (B, S, H, D) inputs sharded over the
    mesh ``seq`` axis; output identically sharded. Falls back to the
    reference when the seq axis is 1."""
    p = int(mesh.shape.get(AXIS_SEQ, 1))
    if p <= 1:
        return attention_reference(q, k, v, causal=causal, scale=scale)
    d = q.shape[-1]
    scale_val = scale if scale is not None else 1.0 / (d ** 0.5)
    s_global = q.shape[1]
    if s_global % p != 0:
        raise ValueError(f"sequence {s_global} not divisible by seq axis {p}")
    s_local = s_global // p

    def local_fn(q_l, k_l, v_l):
        # my ring position and my queries' global positions
        idx = lax.axis_index(AXIS_SEQ)
        q_pos = idx * s_local + jnp.arange(s_local)

        b, _, h, _ = q_l.shape
        acc = jnp.zeros_like(q_l)
        row_max = jnp.full((b, s_local, h), -jnp.inf, dtype=q_l.dtype)
        denom = jnp.zeros((b, s_local, h), dtype=q_l.dtype)

        perm = [(i, (i + 1) % p) for i in range(p)]  # ring: pass K/V right

        # Iteration 0 (the local block) is peeled out of the loop so the
        # ppermute inside the loop body is UNCONDITIONAL: a collective under
        # value-dependent control flow is fragile across XLA backends even
        # with a replicated predicate (deadlock if the pattern is ever
        # disturbed). p-1 rotations total, no discarded final permute.
        acc, row_max, denom = _block_attend(
            q_l, k_l, v_l, q_pos, idx * s_local + jnp.arange(s_local),
            acc, row_max, denom, causal, scale_val,
        )

        def step(i, carry):
            k_blk, v_blk, acc, row_max, denom = carry
            k_blk = lax.ppermute(k_blk, AXIS_SEQ, perm)
            v_blk = lax.ppermute(v_blk, AXIS_SEQ, perm)
            # the block we hold at ring step i originated at (idx - i) mod p
            src = (idx - i) % p
            k_pos = src * s_local + jnp.arange(s_local)
            acc, row_max, denom = _block_attend(
                q_l, k_blk, v_blk, q_pos, k_pos, acc, row_max, denom,
                causal, scale_val,
            )
            return k_blk, v_blk, acc, row_max, denom

        _, _, acc, row_max, denom = lax.fori_loop(
            1, p, step, (k_l, v_l, acc, row_max, denom)
        )
        # rows with zero visible keys (can't happen causally: self is visible)
        return acc / jnp.maximum(denom, 1e-30)[..., None]

    from mmlspark_tpu.parallel.mesh import AXIS_DATA

    # batch rides the data axis simultaneously (attention is batch-local),
    # so a data x seq mesh uses both without gathers
    spec = P(AXIS_DATA if int(mesh.shape.get(AXIS_DATA, 1)) > 1 else None, AXIS_SEQ)
    shard = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return shard(q, k, v)
