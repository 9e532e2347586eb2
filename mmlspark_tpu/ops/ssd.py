"""The Mamba-2 selective state-space scan in its chunked form (Dao & Gu
2024, "SSD"), one Pallas kernel.

A head carries a state ``S`` (head width x state width), zero at a row's
start: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
D x_t``, with ``A < 0`` and ``D`` one scalar a head, ``dt_t > 0`` one scalar
a head a position, and ``B_t``, ``C_t`` shared by the heads of a group.
Position by position that is a loop of ``S`` steps over an outer product.
In chunks of ``L`` positions it is four matrix products a chunk: with
``cum_t`` the sum of ``dt A`` over the chunk up to and with ``t``,

- ``G = C B^T`` (L x L), once a group;
- ``y += (G * exp(cum_t - cum_s) * dt_s, s <= t) x``: what the chunk's own
  positions add;
- ``y += exp(cum_t) * C S_prev^T``: what the state carried in adds;
- ``S = exp(cum_L) S_prev + (x * exp(cum_L - cum_s) dt_s)^T B``: the state
  carried out.

The products take the inputs' dtype (bfloat16 in a model) and sum in
float32; ``dt``, every cumulative decay, every ``exp`` and the carried state
are float32 (the state is rounded only where it enters a product), and no
exponent is ever positive. The chunks of a row run in order along the
grid's last axis with the state in VMEM scratch, so the pass over the chunk
states is inside the kernel and the state never visits HBM. A grid step
holds several chunks of one group's heads; heads are met as many at a time
as fill 128 lanes (two of width 64), since a product that wide costs the
MXU what one head's would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
CHUNKS_A_STEP = 4  # at most; fewer where a row has no such multiple
LANES = 128


def _kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, y_ref, state, *, heads, width, together):
    """One group's ``heads`` heads over the step's chunks. ``rows_ref`` holds
    ``cum`` then ``dt`` with positions along lanes, ``cols_ref`` the same two
    with positions along sublanes; ``state`` is ``S^T`` of ``together`` heads
    side by side."""
    chunks, L, _ = x_ref.shape
    W = together * width

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    earlier = (lax.broadcasted_iota(jnp.int32, (L, L), 1) <= lax.broadcasted_iota(jnp.int32, (L, L), 0))
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, W), 1) // width

    def side_by_side(of_head):
        """``of_head(i)`` for each head met together, each over its own lanes."""
        out = of_head(0)
        for i in range(1, together):
            out = jnp.where(head_of_lane == i, of_head(i), out)
        return out

    def chunk(ci, carry):
        b, c = b_ref[ci], c_ref[ci]
        rows, cols = rows_ref[ci], cols_ref[ci]
        g = lax.dot_general(c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        for j in range(heads // together):
            first = j * together
            x = x_ref[ci, :, j * W:(j + 1) * W]
            prev = state[j]

            def own(i):  # what the chunk's own positions add, head first + i
                h = first + i
                cum_t, cum_s = cols[:, h:h + 1], rows[0, h:h + 1, :]
                decay = jnp.exp(jnp.minimum(cum_t - cum_s, 0.0))
                mixed = jnp.where(earlier, g * decay * rows[1, h:h + 1, :], 0.0)
                return jnp.dot(mixed.astype(x.dtype), x, preferred_element_type=jnp.float32)

            cum = side_by_side(lambda i: cols[:, first + i:first + i + 1])  # (L, W) once broadcast
            dt = side_by_side(lambda i: cols[:, heads + first + i:heads + first + i + 1])
            last = side_by_side(lambda i: rows[0, first + i:first + i + 1, L - 1:L])  # (1, W)
            carried = jnp.dot(c, prev.astype(c.dtype), preferred_element_type=jnp.float32)
            xf = x.astype(jnp.float32)
            y = side_by_side(own) + jnp.exp(cum) * carried + d_ref[:, j * W:(j + 1) * W] * xf
            y_ref[ci, :, j * W:(j + 1) * W] = y.astype(y_ref.dtype)
            weighed = (xf * (jnp.exp(last - cum) * dt)).astype(x.dtype)
            state[j] = jnp.exp(last) * prev + lax.dot_general(
                b, weighed, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = CHUNK, interpret: bool = False):
    """``y_t = S_t C_t + D x_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``S`` zero before a row's first position.

    ``x`` is ``(batch, S, heads, width)``, ``dt`` ``(batch, S, heads)``
    float32 and positive (after its softplus), ``A`` ``(heads,)`` negative,
    ``B`` and ``C`` ``(batch, S, groups, state)`` with ``heads`` a multiple
    of ``groups`` (head ``h`` reads group ``h // (heads // groups)``), ``D``
    ``(heads,)``. ``S`` need not be a multiple of ``chunk``: the row is
    padded behind with positions that add nothing, which no real position
    sees. ``interpret`` runs the kernel in the Pallas interpreter, for a
    backend that is no TPU; it is never chosen here. Returns ``(batch, S,
    heads, width)`` in ``x``'s dtype."""
    batch, S, H, P = x.shape
    G, N = B.shape[2:]
    if H % G or B.shape != (batch, S, G, N) or C.shape != B.shape or dt.shape != (batch, S, H):
        raise ValueError(f"x {x.shape}, dt {dt.shape}, B {B.shape}, C {C.shape}: not heads in groups over one length")
    per_group, L = H // G, chunk
    together = max(d for d in range(1, per_group + 1) if per_group % d == 0 and d * P <= max(P, LANES))
    n = -(-S // L)
    a_step = max(d for d in range(1, CHUNKS_A_STEP + 1) if n % d == 0)
    if n * L != S:
        behind = ((0, 0), (0, n * L - S))
        x, B, C = (jnp.pad(a, behind + ((0, 0), (0, 0))) for a in (x, B, C))
        dt = jnp.pad(dt, behind + ((0, 0),))  # dt 0: the state passes unchanged
    dt = dt.astype(jnp.float32).reshape(batch, n, L, H)
    cum = jnp.cumsum(dt * A.astype(jnp.float32), axis=2)
    both = jnp.stack([cum, dt], axis=3).reshape(batch, n, L, 2, G, per_group)
    rows = both.transpose(0, 4, 1, 3, 5, 2)  # (batch, G, n, 2, per_group, L)
    cols = both.transpose(0, 4, 1, 2, 3, 5).reshape(batch, G, n, L, 2 * per_group)
    d = jnp.repeat(D.astype(jnp.float32), P).reshape(1, H * P)

    def per_group_of(width):  # of an array (batch, n, L, groups x width)
        return pl.BlockSpec((None, a_step, L, width), lambda b, g, i: (b, i, 0, g))

    y = pl.pallas_call(
        functools.partial(_kernel, heads=per_group, width=P, together=together),
        grid=(batch, G, n // a_step),
        in_specs=[
            per_group_of(per_group * P), per_group_of(N), per_group_of(N),
            pl.BlockSpec((None, None, a_step, 2, per_group, L), lambda b, g, i: (b, g, i, 0, 0, 0)),
            pl.BlockSpec((None, None, a_step, L, 2 * per_group), lambda b, g, i: (b, g, i, 0, 0)),
            pl.BlockSpec((1, per_group * P), lambda b, g, i: (0, g)),
        ],
        out_specs=per_group_of(per_group * P),
        out_shape=jax.ShapeDtypeStruct((batch, n, L, H * P), x.dtype),
        scratch_shapes=[pltpu.VMEM((per_group // together, N, together * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
        name="ssd_scan",
    )(
        x.reshape(batch, n, L, H * P), B.reshape(batch, n, L, G * N), C.reshape(batch, n, L, G * N),
        rows, cols, d,
    )
    return y.reshape(batch, n * L, H, P)[:, :S]


def ssd_reference(x, dt, A, B, C, D):
    """The same function a position at a time in float32: what the chunked
    form has to equal. Shapes as :func:`ssd_scan`; returns float32."""
    batch, S, H, P = x.shape
    G, N = B.shape[2:]
    x, dt, B, C = (a.astype(jnp.float32) for a in (x, dt, B, C))
    B, C = (jnp.repeat(a, H // G, axis=2) for a in (B, C))  # a head's own copy of its group's

    def step(state, at):
        x_t, dt_t, b_t, c_t = at  # (batch, H, P), (batch, H), (batch, H, N) x 2
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, (state * c_t[..., None, :]).sum(axis=-1) + D[:, None] * x_t

    start = jnp.zeros((batch, H, P, N), jnp.float32)
    _, y = lax.scan(step, start, tuple(a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1)
