"""Pipeline parallelism over the mesh ``pipe`` axis (GPipe schedule).

A stack of layers is split into contiguous stages, one stage per device on
the ``pipe`` axis; a batch is split into microbatches that flow through the
stages in a bubble schedule: at step t, stage s processes microbatch
t - s while activations hop stage→stage over ``lax.ppermute`` (neighbor
ICI links). With M microbatches and p stages the bubble is the standard
(p-1)/(M+p-1) fraction.

API: :func:`pipeline_apply` — stage params stacked on a leading axis
sharded over ``pipe``; the output is replicated. Shapes must be uniform
across stages (each stage maps (mb, d) -> (mb, d)); project in/out around
the pipeline.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.parallel.mesh import AXIS_PIPE


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    mesh,
    num_microbatches: int,
):
    """Run ``x`` through ``p`` pipeline stages.

    ``stage_fn(params_one_stage, h) -> h`` applies ONE stage;
    ``stage_params`` is a pytree whose leaves have a leading axis of size
    ``p`` (one slice per stage), sharded over the ``pipe`` mesh axis;
    ``x`` is (B, D) with B divisible by ``num_microbatches``. Returns the
    (B, D_out) result, replicated. Falls back to a sequential scan over
    stages when the pipe axis is 1."""
    p = int(mesh.shape.get(AXIS_PIPE, 1))
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    if p > 1 and n_stages != p:
        raise ValueError(
            f"{n_stages} stages but pipe axis of {p} — the schedule places "
            "exactly one stage per device; fold layers into stages so the "
            "leading params axis equals the pipe size"
        )
    if p <= 1:
        def seq_body(h, params_s):
            return stage_fn(params_s, h), None

        out, _ = lax.scan(seq_body, x, stage_params)
        return out

    b = x.shape[0]
    m = num_microbatches
    if b % m != 0:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    mb = b // m
    xs = x.reshape(m, mb, *x.shape[1:])

    perm = [(i, i + 1) for i in range(p - 1)]  # stage s -> s+1

    def local_fn(params_local, xs_l):
        # params_local leaves arrive as (1, ...) slices of the stage axis
        params_local = jax.tree.map(lambda a: a[0], params_local)
        s = lax.axis_index(AXIS_PIPE)
        steps = m + p - 1
        zero_mb = jnp.zeros_like(stage_fn(params_local, xs_l[0]))
        recv = jnp.zeros_like(xs_l[0])
        outputs = jnp.zeros((m,) + zero_mb.shape, zero_mb.dtype)

        def step(t, carry):
            recv, outputs = carry
            feed_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(s == 0, xs_l[feed_idx], recv)
            out = stage_fn(params_local, inp)
            # last stage records microbatch t-(p-1) BEFORE the hop
            rec_idx = jnp.clip(t - (p - 1), 0, m - 1)
            record = (s == p - 1) & (t >= p - 1)
            outputs = outputs.at[rec_idx].set(
                jnp.where(record, out, outputs[rec_idx])
            )
            recv = lax.ppermute(out, AXIS_PIPE, perm)
            return recv, outputs

        _, outputs = lax.fori_loop(0, steps, step, (recv, outputs))
        # only the last stage holds real outputs; psum replicates them
        outputs = jnp.where(s == p - 1, outputs, 0.0)
        return lax.psum(outputs, AXIS_PIPE)

    # strip the stage axis onto the mesh; microbatches replicated
    out = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(AXIS_PIPE), stage_params),
            P(),
        ),
        out_specs=P(),
        check_vma=False,
    )(stage_params, xs)
    return out.reshape(b, *out.shape[2:])
