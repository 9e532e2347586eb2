"""Expert parallelism over the mesh ``expert`` axis (MoE dispatch).

Experts shard one-per-device over the ``expert`` axis. Two dispatch
formulations, both static-shape:

- :func:`moe_apply` — masked-dense: every device applies ITS expert to the
  full (replicated) token batch, masks the tokens routed elsewhere, and a
  ``lax.psum`` combines. Dense compute trades FLOPs for zero
  load-imbalance stalls; right while the batch fits replicated.
- :func:`moe_apply_a2a` — capacity-based ``all_to_all`` (the GShard
  layout): tokens shard over the expert axis, each device packs its local
  tokens into fixed-capacity per-expert send buffers, ONE all_to_all
  routes buffers to the owning expert, the expert runs on its received
  tokens, and the reverse all_to_all brings outputs home. Compute and
  memory per device stay ∝ B/E; tokens beyond an expert's capacity are
  dropped (output zero), the standard capacity-factor contract.

And one for many experts on ONE device, inside a model:

- :func:`moe_topk` — top-k by sort: the (token, expert) assignments are
  sorted by expert, each expert's feed-forward (three matrices with a
  gate, or two without) runs over its own contiguous group
  (``lax.ragged_dot``; a Pallas grouped matmul where the matrices are a
  stack of several layers' experts, read in place), and the results go
  back weighted in one Pallas kernel (``moe_combine``, :func:`_combine`).
  Work is ∝ tokens x k whatever the imbalance; no capacity,
  so no token is ever dropped, and an expert that got no token is an empty
  group.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.parallel.mesh import AXIS_EXPERT


def moe_apply(
    expert_fn: Callable,
    expert_params,
    x: jax.Array,
    gate_logits: jax.Array,
    mesh,
):
    """Top-1 mixture of experts.

    ``expert_fn(params_one_expert, x) -> y`` applies one expert to a token
    batch; ``expert_params`` leaves carry a leading axis of size E sharded
    over ``expert``; ``x`` is (B, D); ``gate_logits`` is (B, E). Returns
    (B, D_out) = gate_prob[chosen] * expert_chosen(x), replicated. Falls
    back to a sequential scan when the expert axis is 1."""
    e_mesh = int(mesh.shape.get(AXIS_EXPERT, 1))
    e_total = jax.tree.leaves(expert_params)[0].shape[0]
    if e_mesh > 1 and e_total != e_mesh:
        raise ValueError(
            f"{e_total} experts but expert axis of {e_mesh} — the masked "
            "dispatch places exactly one expert per device"
        )
    probs = jax.nn.softmax(gate_logits, axis=1)
    assign = jnp.argmax(gate_logits, axis=1)  # (B,)
    chosen_p = jnp.take_along_axis(probs, assign[:, None], axis=1)  # (B, 1)

    if e_mesh <= 1:
        def seq_body(acc, inputs):
            eidx, params_e = inputs
            mask = (assign == eidx)[:, None]
            return acc + expert_fn(params_e, x) * mask * chosen_p, None

        shape = jax.eval_shape(
            expert_fn, jax.tree.map(lambda a: a[0], expert_params), x
        )
        zero = jnp.zeros(shape.shape, shape.dtype)
        out, _ = lax.scan(
            seq_body, zero, (jnp.arange(e_total), expert_params)
        )
        return out

    def local_fn(params_local, x_l, assign_l, chosen_l):
        params_one = jax.tree.map(lambda a: a[0], params_local)
        eidx = lax.axis_index(AXIS_EXPERT)
        mask = (assign_l == eidx)[:, None]
        out = expert_fn(params_one, x_l) * mask * chosen_l
        return lax.psum(out, AXIS_EXPERT)

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(AXIS_EXPERT), expert_params),
            P(),
            P(),
            P(),
        ),
        out_specs=P(),
        check_vma=False,
    )(expert_params, x, assign, chosen_p)


def moe_apply_a2a(
    expert_fn: Callable,
    expert_params,
    x: jax.Array,
    gate_logits: jax.Array,
    mesh,
    capacity_factor: float = 1.25,
):
    """Top-1 MoE via capacity-based all_to_all dispatch.

    ``x`` (B, D) and ``gate_logits`` (B, E) shard their batch over the
    ``expert`` mesh axis (B divisible by E); expert e lives on device e.
    Each device packs its B/E local tokens into (E, C) send slots with
    ``C = ceil(B/E/E * capacity_factor)`` per destination, one
    ``all_to_all`` delivers every expert its (E, C) received tokens, the
    expert runs once on E*C tokens, and the reverse all_to_all routes
    outputs back. Tokens that overflow an expert's local capacity are
    DROPPED (zero output, the capacity-factor contract). Falls back to the
    masked-dense form when the expert axis is 1."""
    e_mesh = int(mesh.shape.get(AXIS_EXPERT, 1))
    if e_mesh <= 1:
        return moe_apply(expert_fn, expert_params, x, gate_logits, mesh)
    e_total = jax.tree.leaves(expert_params)[0].shape[0]
    if e_total != e_mesh:
        raise ValueError(
            f"{e_total} experts but expert axis of {e_mesh} — one expert "
            "per device"
        )
    b, d = x.shape
    if b % e_mesh != 0:
        raise ValueError(f"batch {b} not divisible by expert axis {e_mesh}")
    b_local = b // e_mesh
    import math

    cap = max(1, math.ceil(b_local / e_mesh * capacity_factor))

    probs = jax.nn.softmax(gate_logits, axis=1)
    assign = jnp.argmax(gate_logits, axis=1).astype(jnp.int32)  # (B,)
    chosen_p = jnp.take_along_axis(probs, assign[:, None], axis=1)  # (B, 1)

    def local_fn(params_local, x_l, assign_l, chosen_l):
        params_one = jax.tree.map(lambda a: a[0], params_local)
        # position of each local token within its destination expert's
        # send buffer (rank among same-destination tokens, in order)
        dest_oh = (
            assign_l[:, None] == jnp.arange(e_mesh, dtype=jnp.int32)[None, :]
        )  # (b_local, E)
        pos = jnp.cumsum(dest_oh.astype(jnp.int32), axis=0) - 1  # rank per dest
        my_pos = jnp.take_along_axis(pos, assign_l[:, None], axis=1)[:, 0]
        keep = my_pos < cap  # overflow tokens dropped

        # scatter local tokens into (E, C, D) send buffers; slot (e, c)
        # holds the c-th kept token destined for expert e
        slot = jnp.where(keep, assign_l * cap + my_pos, e_mesh * cap)  # drop->OOB
        send = jnp.zeros((e_mesh * cap, x_l.shape[1]), x_l.dtype).at[slot].set(
            x_l, mode="drop"
        ).reshape(e_mesh, cap, x_l.shape[1])

        # deliver: device e receives the e-th buffer from every source
        recv = lax.all_to_all(send, AXIS_EXPERT, split_axis=0, concat_axis=0,
                              tiled=True)
        # flatten (E, C, D) -> (E*C, D): expert_fn's contract is a 2-D token
        # batch, same as the masked-dense path
        recv = recv.reshape(e_mesh * cap, recv.shape[-1])
        out = expert_fn(params_one, recv)  # (E*C, D_out)

        # route home: reverse all_to_all returns each source its slots
        back = lax.all_to_all(
            out.reshape(e_mesh, cap, out.shape[-1]), AXIS_EXPERT,
            split_axis=0, concat_axis=0, tiled=True,
        ).reshape(e_mesh * cap, out.shape[-1])

        # gather my tokens' outputs from their slots; dropped -> zero
        safe_slot = jnp.where(keep, slot, 0)
        y = back[safe_slot] * keep[:, None] * chosen_l
        return y

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(AXIS_EXPERT), expert_params),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
        ),
        out_specs=P(AXIS_EXPERT),
        check_vma=False,
    )(expert_params, x, assign, chosen_p)


def _tile(n: int, whole: int) -> int:
    """A tile of a dimension of ``n``: all of it up to ``whole``, else the
    largest multiple of 128 up to 1,024 that divides it, else 512 (the
    kernel masks a contracted edge and clips a written one)."""
    if n <= whole:
        return n
    return max((t for t in range(128, 1025, 128) if n % t == 0), default=512)


def _product_in_place(a, w, sizes, interpret: bool):
    """``lax.ragged_dot(a, w, sizes)`` in float32 by the Pallas grouped
    matmul (``megablox.gmm``): consecutive groups of ``a``'s rows (M, K),
    ``sizes`` (G,) long, each times its own matrix of ``w`` (G, K, N). The
    kernel finds a group's matrix by its number, so ``w`` is read where it
    lies and a group of size 0 costs no tile; and it tiles a width by what
    fits and masks the edge, where XLA:TPU's grouped product tiles one by
    the power of two that divides it (1,856 = 29 x 64 by 128: a tenth of the
    chip's peak; 2,688 x 1,856 on a v5e 17 -> 91 TFLOP/s, and back 18 ->
    106: PERF.md, PR 34).

    The chip stores a matrix whose last dimension is off the 128 lanes and
    whose last but one is on them with that one minor, so such a ``w`` is
    handed over transposed, which is the same bytes and no copy."""
    (M, K), N = a.shape, w.shape[2]
    tm = min(256, -(-M // 8) * 8)
    a = jnp.pad(a, ((0, -M % tm), (0, 0)))  # rows behind the last group are in no tile
    as_stored = N % 128 != 0 and K % 128 == 0
    if as_stored:
        w = jnp.swapaxes(w, 1, 2)
    y = gmm(a, w, sizes, jnp.float32, (tm, _tile(K, 3072), _tile(N, 512)),
            transpose_rhs=as_stored, interpret=interpret)
    return y[:M]


_ROWS = 8  # a float32 tile's rows: the least a copy out of HBM may move
_COMBINE_BLOCK = 256  # tokens a grid step where the buffers fit: within 5% of the best of 64-1,024 (PERF.md)
# VMEM bytes for both halves of the tile buffer and the output blocks: what a
# block of 256 at 8 of 128 experts and 2,048 wide needs (PERF.md)
_COMBINE_VMEM = 68 * 2**20


def _combine_tiles(tb: int, k: int, E: int) -> int:
    """Tiles of 8 rows that hold a block of ``tb`` tokens' ``tb * k`` rows,
    at most: one run a chosen expert, a run of ``L`` rows touching ``L / 8
    + 1.75`` tiles at most; never more tiles than rows."""
    return min(tb * k, -(-tb * k // _ROWS) + 2 * E)


def _combine_plan(T: int, k: int, D: int, E: int) -> int:
    """-> the tokens a grid step of :func:`_combine` takes:
    ``_COMBINE_BLOCK`` (all of ``T`` where that is fewer), halved until two
    tile buffers and two output blocks of the whole width ``D`` fit
    ``_COMBINE_VMEM``."""
    tb = _COMBINE_BLOCK
    while tb > _ROWS and 2 * (_combine_tiles(tb, k, E) * _ROWS + tb) * D * 4 > _COMBINE_VMEM:
        tb //= 2
    return min(tb, T)


def _combine_kernel(first_ref, next_ref, pos_ref, w_ref, ys_hbm, out_ref, buf, count, sem, *, k, tb):
    """Grid step ``g``, token block ``g``: start the tile copies of block
    ``g + 1`` into the other half of ``buf``, wait for this block's, and
    write each of its tokens' ``sum_j w[t, j] * row(t, j)``, slot by slot.
    ``first_ref`` lists block 0's tiles and ``next_ref`` the next block's
    (how many, then which); ``pos_ref`` says where in its half each of this
    block's rows lies and ``w_ref`` weighs it, token-major."""
    g, blocks = pl.program_id(0), pl.num_programs(0)

    def copy(tiles, q, half):
        tile = pl.multiple_of(tiles[0, 1 + q] * _ROWS, _ROWS)
        return pltpu.make_async_copy(ys_hbm.at[pl.ds(tile, _ROWS)],
                                     buf.at[half, pl.ds(pl.multiple_of(q * _ROWS, _ROWS), _ROWS)], sem.at[half])

    def fetch(tiles, half):
        count[half] = tiles[0, 0]

        def start(q, carry):
            copy(tiles, q, half).start()
            return carry
        lax.fori_loop(0, tiles[0, 0], start, 0)

    @pl.when(g == 0)
    def _():
        fetch(first_ref, 0)

    @pl.when(g + 1 < blocks)
    def _():
        fetch(next_ref, (g + 1) % 2)

    half = g % 2

    def wait(q, carry):  # every copy moves one tile's bytes
        copy(first_ref, 0, half).wait()
        return carry
    lax.fori_loop(0, count[half], wait, 0)

    unroll = 8 if tb % 8 == 0 else 1

    def tokens(s, carry):
        for u in range(unroll):
            t = s * unroll + u
            acc = buf[half, pl.ds(pos_ref[0, t * k], 1), :] * w_ref[0, t * k]
            for j in range(1, k):
                acc = acc + buf[half, pl.ds(pos_ref[0, t * k + j], 1), :] * w_ref[0, t * k + j]
            out_ref[pl.ds(t, 1), :] = acc
        return carry
    lax.fori_loop(0, tb // unroll, tokens, 0)


def _tile_tables(back, expert_of, sizes, k: int, tb: int, most: int):
    """For each block of ``tb`` tokens: the tiles of 8 rows its rows lie
    in, ascending (how many, then which, padded to ``most``), and where each
    of its rows lies once those tiles are side by side, token-major; tokens
    past the end read place 0 and list nothing. Read off the runs, with no
    sort and no gather: a block gives expert ``e`` ``count[b, e]`` rows,
    which start where the earlier blocks' rows of ``e`` end, and a run's
    first tile is the last run's last where both touch it. ``expert_of``
    (T*k,) is each row's expert, ``sizes`` (E,) each expert's rows.
    -> (tiles (blocks, 1, 1 + most), pos (blocks, 1, tb * k)) int32."""
    E = sizes.shape[0]
    T = back.shape[0] // k
    blocks = -(-T // tb)
    pad = blocks * tb * k - T * k
    experts = jnp.arange(E, dtype=jnp.int32)
    mine = jnp.pad(expert_of.astype(jnp.int32), (0, pad), constant_values=E).reshape(blocks, tb * k)
    rows = jnp.pad(back.astype(jnp.int32), (0, pad)).reshape(blocks, tb * k)

    def pick(table, index):  # table[b, index[b, i]] as a sum over one-hot experts: no gather
        return jnp.where(index[:, :, None] == experts, table[:, None, :], 0).sum(axis=-1)

    count = (mine[:, :, None] == experts).sum(axis=1, dtype=jnp.int32)  # (blocks, E)
    row0 = (jnp.cumsum(sizes) - sizes).astype(jnp.int32) + jnp.cumsum(count, axis=0) - count
    first, last = row0 // _ROWS, (row0 + count - 1) // _ROWS
    held = count > 0
    before = lax.cummax(jnp.where(held, last, -1), axis=1)
    shared = held & (jnp.pad(before[:, :-1], ((0, 0), (1, 0)), constant_values=-1) == first)
    new = jnp.where(held, last - first + 1, 0) - shared
    end = jnp.cumsum(new, axis=1)
    rank0 = end - new - shared  # rank of a run's first tile among its block's
    q = jnp.arange(most, dtype=jnp.int32)
    run = (end[:, None, :] <= q[:, None]).sum(axis=-1, dtype=jnp.int32)  # the run that lists slot q
    tiles = jnp.where(q < end[:, -1:], pick(first - rank0, run) + q, 0)
    tiles = jnp.concatenate([end[:, -1:], tiles], axis=1)
    pos = (pick(rank0 - first, mine) + rows // _ROWS) * _ROWS + rows % _ROWS
    pos = jnp.where(mine < E, pos, 0)
    return tiles.reshape(blocks, 1, 1 + most), pos.reshape(blocks, 1, tb * k)


def _combine(ys, back, expert_of, sizes, weights, interpret: bool):
    """``sum_j weights[t, j] * ys[back[t * k + j]]`` for every token ``t``,
    in float32, slot by slot: the routed experts' rows (``ys``, (T*k, D)
    float32, sorted by expert, a token's rows in token order within each
    expert's run) weighed back into token order in one pass. A block of
    tokens copies the 8-row tiles of ``ys`` that hold its rows into VMEM,
    double-buffered, and reads each row there: a tile is the least a copy
    out of HBM may move (the chip keeps a float32 matrix in tiles of 8 x 128),
    and a block's rows lie in at most one run of each expert, so what is
    read beyond the rows is the runs' ragged ends. ``expert_of`` (T*k,) is
    each assignment's expert, ``sizes`` (E,) each expert's rows. Nothing of
    size (T*k, D) is written. -> (T, D) float32."""
    T, k = weights.shape
    D, E = ys.shape[1], sizes.shape[0]
    tb = _combine_plan(T, k, D, E)
    blocks, most = -(-T // tb), _combine_tiles(tb, k, E)
    pad = blocks * tb - T
    tiles, pos = _tile_tables(back, expert_of, sizes, k, tb, most)
    weights = jnp.pad(weights.astype(jnp.float32), ((0, pad), (0, 0))).reshape(blocks, 1, tb * k)
    if (T * k) % _ROWS:  # the last tile whole (a model's T * k is a multiple of 8: no copy)
        ys = jnp.pad(ys, ((0, -(T * k) % _ROWS), (0, 0)))
    table = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    with jax.named_scope("moe_combine"):
        y = pl.pallas_call(
            functools.partial(_combine_kernel, k=k, tb=tb),
            grid=(blocks,),
            in_specs=[
                table((None, 1, 1 + most), lambda g: (0, 0, 0)),
                table((None, 1, 1 + most), lambda g: ((g + 1) % blocks, 0, 0)),
                table((None, 1, tb * k), lambda g: (g, 0, 0)),
                table((None, 1, tb * k), lambda g: (g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tb, D), lambda g: (g, 0)),
            out_shape=jax.ShapeDtypeStruct((blocks * tb, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, most * _ROWS, D), jnp.float32), pltpu.SMEM((2,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",), vmem_limit_bytes=_COMBINE_VMEM + 16 * 2**20),
            interpret=interpret,
            name="moe_combine",
        )(tiles, tiles, pos, weights, ys)
    return y[:T] if pad else y


def moe_topk(x, scores_to_choose, scores_to_weigh, experts, k: int, scale: float = 1.0,
             activation: Callable = jax.nn.silu, first_group=0, interpret: bool = False):
    """Top-``k`` mixture of the experts held on this device.

    ``x`` is (T, D). A token's experts are the ``k`` largest of its row of
    ``scores_to_choose`` (T, E) (a router's scores plus a balancing bias,
    say); their weights are its ``scores_to_weigh`` (T, E) at the chosen,
    divided by their sum, times ``scale``. An expert is what ``experts``
    holds: ``gate`` and ``up`` (E, D, F) and ``down`` (E, F, D) compute
    ``down(activation(gate x) * up x)`` (SwiGLU as it stands); ``up`` and
    ``down`` alone, two matrices and no gate, ``down(activation(up x))``.
    Products in ``x``'s dtype summed in float32 (``lax.ragged_dot``); the
    weighted sum of a token's ``k`` results in float32, slot by slot
    (:func:`_combine`, a Pallas kernel on either path).

    The matrices may hold more groups than the router has experts, a whole
    multiple: several layers' experts one after another, as a model stores
    them, read where they lie (:func:`_product_in_place`). ``first_group``
    (an integer, traced or not) then says where these ``E`` stand among
    them: every other group is empty, which costs the product no tile and
    the program no copy of ``E`` matrices. With ``E`` groups it is not read.
    ``interpret`` runs the Pallas kernels in the interpreter, for a backend
    other than the chip. -> (y (T, D) float32, chosen (T, k) int32)."""
    T, D = x.shape
    E = scores_to_choose.shape[1]
    _, chosen = lax.top_k(scores_to_choose, k)
    weights = jnp.take_along_axis(scores_to_weigh, chosen, axis=1)
    weights = weights / weights.sum(axis=1, keepdims=True) * scale
    # the T*k assignments, sorted by expert (stable: a token's order holds)
    expert_of = chosen.reshape(T * k)
    order = jnp.argsort(expert_of, stable=True)
    sizes = (expert_of[:, None] == jnp.arange(E, dtype=expert_of.dtype)).sum(axis=0)
    sizes = sizes.astype(jnp.int32)
    xs = x[order // k]
    dot = lambda a, w: lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)
    groups = experts["up"].shape[0]
    if groups != E:  # these experts' sizes at their place, every other group empty
        assert groups % E == 0, f"{groups} groups of matrices for {E} experts"
        assert not isinstance(first_group, int) or first_group in range(0, groups, E), first_group  # traced: clamped
        among = lax.dynamic_update_slice(jnp.zeros(groups, jnp.int32), sizes, (first_group,))
        dot = lambda a, w: _product_in_place(a, w, among, interpret)
    if "gate" in experts:
        inner = activation(dot(xs, experts["gate"])) * dot(xs, experts["up"])
    else:
        inner = activation(dot(xs, experts["up"]))
    inner = inner.astype(x.dtype)
    ys = dot(inner, experts["down"])  # (T*k, D) float32, still sorted by expert
    back = jnp.argsort(order)  # where assignment (token, slot) sits in the sorted rows
    return _combine(ys, back, expert_of, sizes, weights, interpret), chosen.astype(jnp.int32)
