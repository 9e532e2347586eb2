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
  back weighted. Work is ∝ tokens x k whatever the imbalance; no capacity,
  so no token is ever dropped, and an expert that got no token is an empty
  group.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
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
    Products in ``x``'s dtype summed in float32 (``lax.ragged_dot``).

    The matrices may hold more groups than the router has experts, a whole
    multiple: several layers' experts one after another, as a model stores
    them, read where they lie (:func:`_product_in_place`; ``interpret`` is
    its Pallas kernel's, for a backend other than the chip). ``first_group``
    (an integer, traced or not) then says where these ``E`` stand among
    them: every other group is empty, which costs the product no tile and
    the program no copy of ``E`` matrices. With ``E`` groups neither is read.
    -> (y (T, D) float32, chosen (T, k) int32)."""
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
    y = (ys[back].reshape(T, k, D) * weights[:, :, None].astype(jnp.float32)).sum(axis=1)
    return y, chosen.astype(jnp.int32)
