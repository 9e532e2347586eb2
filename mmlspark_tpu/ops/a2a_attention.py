"""All-to-all (Ulysses-style) sequence-parallel attention.

The second of the two standard long-context layouts (ring attention in
``ops/ring_attention.py`` is the other): instead of rotating K/V blocks
around a ring, ONE ``all_to_all`` re-shards the activations from
sequence-parallel to head-parallel — each device receives the FULL
sequence for H/p of the heads, runs ordinary (flash-style) attention
locally with no inner loop, and a second ``all_to_all`` restores the
sequence sharding.

Trade-offs vs the ring (why both exist):

- a2a moves each activation tensor twice total (2·S/p·H·D per device per
  tensor), independent of p; the ring moves K/V p−1 times. For p ≫ 2 the
  a2a wins on bytes, and both patterns ride ICI.
- a2a needs ``num_heads % p == 0`` (head-parallel inner layout); the ring
  has no head-count constraint and never holds more than an S/p block of
  K/V — a2a materializes (B, S, H/p) activations, so its memory
  high-water mark grows with S while the ring's stays at S/p.
- the ring overlaps communication with compute step by step; a2a is two
  bulk collectives around one big MXU-friendly attention — typically the
  faster choice until S/p attention no longer fits.

API matches :func:`~mmlspark_tpu.ops.ring_attention.ring_attention`:
inputs (B, S, H, D) sharded over the mesh ``seq`` axis.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.ops.ring_attention import attention_reference
from mmlspark_tpu.parallel.mesh import AXIS_SEQ


def a2a_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = False,
    scale: Optional[float] = None,
):
    """Sequence-parallel attention via head↔sequence all_to_all.

    q/k/v (B, S, H, D) sharded over ``seq``; output identically sharded.
    Requires ``H % p == 0``; falls back to the reference when p == 1."""
    p = int(mesh.shape.get(AXIS_SEQ, 1))
    if p <= 1:
        return attention_reference(q, k, v, causal=causal, scale=scale)
    b, s_global, h, d = q.shape
    if h % p != 0:
        raise ValueError(
            f"a2a attention needs num_heads divisible by the seq axis "
            f"({h} % {p} != 0); use ring_attention for odd head counts"
        )
    if s_global % p != 0:
        raise ValueError(f"sequence {s_global} not divisible by seq axis {p}")

    def local_fn(q_l, k_l, v_l):
        # (B, S/p, H, D) -> all_to_all -> (B, S, H/p, D): scatter the head
        # axis, gather the sequence axis.
        def to_heads(x):
            return lax.all_to_all(
                x, AXIS_SEQ, split_axis=2, concat_axis=1, tiled=True
            )

        def to_seq(x):
            return lax.all_to_all(
                x, AXIS_SEQ, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = to_heads(q_l), to_heads(k_l), to_heads(v_l)
        out = attention_reference(qh, kh, vh, causal=causal, scale=scale)
        return to_seq(out)

    from mmlspark_tpu.parallel.mesh import AXIS_DATA

    spec = P(AXIS_DATA if int(mesh.shape.get(AXIS_DATA, 1)) > 1 else None, AXIS_SEQ)
    shard = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return shard(q, k, v)
