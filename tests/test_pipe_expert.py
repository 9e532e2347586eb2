"""Pipeline (pipe axis) and MoE (expert axis) parallelism — the last two
mesh axes exercised on the 8-virtual-device CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops.expert_parallel import moe_apply
from mmlspark_tpu.ops.pipeline_parallel import pipeline_apply
from mmlspark_tpu.parallel.mesh import MeshConfig, make_mesh


def _stage_fn(params, h):
    w, b = params
    return jnp.tanh(h @ w + b)


def _stack_params(rng, stages, d):
    ws = jnp.asarray(rng.normal(size=(stages, d, d)) * 0.5, jnp.float32)
    bs = jnp.asarray(rng.normal(size=(stages, d)) * 0.1, jnp.float32)
    return (ws, bs)


def _sequential(params, x):
    ws, bs = params
    h = x
    for i in range(ws.shape[0]):
        h = _stage_fn((ws[i], bs[i]), h)
    return h


class TestPipelineParallel:
    def test_matches_sequential(self):
        mesh = make_mesh(MeshConfig(data=1, pipe=4), devices=jax.devices()[:4])
        rng = np.random.default_rng(0)
        params = _stack_params(rng, 4, 16)
        x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        ref = _sequential(params, x)
        out = pipeline_apply(_stage_fn, params, x, mesh, num_microbatches=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_single_microbatch_and_many(self):
        mesh = make_mesh(MeshConfig(data=1, pipe=8))
        rng = np.random.default_rng(1)
        params = _stack_params(rng, 8, 8)
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        ref = _sequential(params, x)
        for m in (1, 2, 16):
            out = pipeline_apply(_stage_fn, params, x, mesh, num_microbatches=m)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5,
                err_msg=f"microbatches={m}",
            )

    def test_pipe_axis_one_falls_back(self):
        mesh = make_mesh(MeshConfig(data=8, pipe=1))
        rng = np.random.default_rng(2)
        params = _stack_params(rng, 3, 8)
        x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        out = pipeline_apply(_stage_fn, params, x, mesh, num_microbatches=2)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_sequential(params, x)), rtol=2e-4, atol=2e-5
        )

    def test_indivisible_batch_raises(self):
        mesh = make_mesh(MeshConfig(data=1, pipe=4), devices=jax.devices()[:4])
        rng = np.random.default_rng(3)
        params = _stack_params(rng, 4, 8)
        x = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            pipeline_apply(_stage_fn, params, x, mesh, num_microbatches=3)


def _expert_fn(params, x):
    w, b = params
    return x @ w + b


class TestExpertParallel:
    def _setup(self, e=4, b=24, d=8, seed=0):
        rng = np.random.default_rng(seed)
        ws = jnp.asarray(rng.normal(size=(e, d, d)) * 0.3, jnp.float32)
        bs = jnp.asarray(rng.normal(size=(e, d)) * 0.1, jnp.float32)
        x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
        gates = jnp.asarray(rng.normal(size=(b, e)), jnp.float32)
        return (ws, bs), x, gates

    def _reference(self, params, x, gates):
        ws, bs = params
        probs = np.asarray(jax.nn.softmax(gates, axis=1))
        assign = np.asarray(jnp.argmax(gates, axis=1))
        out = np.zeros((x.shape[0], ws.shape[2]), np.float32)
        xn = np.asarray(x)
        for i in range(x.shape[0]):
            e = assign[i]
            out[i] = (xn[i] @ np.asarray(ws[e]) + np.asarray(bs[e])) * probs[i, e]
        return out

    def test_matches_reference(self):
        mesh = make_mesh(MeshConfig(data=1, expert=4), devices=jax.devices()[:4])
        params, x, gates = self._setup()
        out = moe_apply(_expert_fn, params, x, gates, mesh)
        np.testing.assert_allclose(
            np.asarray(out), self._reference(params, x, gates), rtol=2e-4, atol=2e-5
        )

    def test_expert_axis_one_falls_back(self):
        mesh = make_mesh(MeshConfig(data=8, expert=1))
        params, x, gates = self._setup(seed=1)
        out = moe_apply(_expert_fn, params, x, gates, mesh)
        np.testing.assert_allclose(
            np.asarray(out), self._reference(params, x, gates), rtol=2e-4, atol=2e-5
        )

    def test_all_axes_engaged(self):
        """Every one of the five mesh axes now has a real consumer: this
        test documents the inventory (data: GBDT/DNN batch; model:
        feature-parallel bins + TP matmuls; seq: ring attention; pipe:
        pipeline_apply; expert: moe_apply)."""
        mesh = make_mesh(MeshConfig(data=2, expert=4))
        params, x, gates = self._setup(seed=2)
        out = moe_apply(_expert_fn, params, x, gates, mesh)
        np.testing.assert_allclose(
            np.asarray(out), self._reference(params, x, gates), rtol=2e-4, atol=2e-5
        )


def test_stage_count_mismatch_raises():
    mesh = make_mesh(MeshConfig(data=1, pipe=4), devices=jax.devices()[:4])
    rng = np.random.default_rng(5)
    params = _stack_params(rng, 8, 8)  # 8 stages over a 4-way pipe
    x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    with pytest.raises(ValueError, match="one stage per device"):
        pipeline_apply(_stage_fn, params, x, mesh, num_microbatches=2)


def test_expert_count_mismatch_raises():
    mesh = make_mesh(MeshConfig(data=1, expert=4), devices=jax.devices()[:4])
    rng = np.random.default_rng(6)
    ws = jnp.asarray(rng.normal(size=(8, 8, 8)), jnp.float32)
    bs = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    gates = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    with pytest.raises(ValueError, match="one expert per device"):
        moe_apply(_expert_fn, (ws, bs), x, gates, mesh)


def _placed(tree, mesh, axis):
    """The caller's half of the contract: leading axis onto the mesh axis,
    committed, before the parameters are handed to ``DNNModel``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(tree, NamedSharding(mesh, P(axis)))


def _pipeline_model(params, mesh, microbatches, **kw):
    from mmlspark_tpu.dnn import DNNModel

    return DNNModel(
        applyFn=lambda p, i: {
            "output": pipeline_apply(_stage_fn, p, i["x"], mesh, microbatches)
        },
        modelParams=_placed(params, mesh, "pipe"),
        feedDict={"x": "f"},
        fetchDict={"y": "output"},
        **kw,
    )


def _moe_model(experts, gate, mesh, expert_fn, **kw):
    from mmlspark_tpu.dnn import DNNModel

    def apply(p, i):
        x = i["x"]
        return {"output": moe_apply(expert_fn, p["experts"], x, x @ p["gate"], mesh)}

    return DNNModel(
        applyFn=apply,
        modelParams={"experts": _placed(experts, mesh, "expert"), "gate": gate},
        feedDict={"x": "f"},
        fetchDict={"y": "output"},
        **kw,
    )


class TestDNNModelConsumers:
    """The pipe/expert ops behind the PUBLIC DNNModel API — a user-facing
    transform engages the axes through an ``applyFn`` that calls the op, with
    parameters the caller placed on the mesh."""

    def test_pipeline_mode_through_dnnmodel(self):
        from mmlspark_tpu.data.table import Table

        rng = np.random.default_rng(0)
        d, n, p = 8, 24, 4
        params = _stack_params(rng, p, d)
        X = rng.normal(size=(n, d)).astype(np.float32)
        mesh = make_mesh(MeshConfig(data=2, pipe=p))

        out = _pipeline_model(params, mesh, 2, batchSize=8).transform(Table({"f": X}))

        want = np.asarray(_sequential(params, jnp.asarray(X)))
        np.testing.assert_allclose(out.column("y"), want, rtol=2e-4, atol=2e-5)

    def test_moe_mode_through_dnnmodel(self):
        from mmlspark_tpu.data.table import Table

        rng = np.random.default_rng(1)
        d, n, e = 8, 30, 8
        experts = (
            jnp.asarray(rng.normal(size=(e, d, d)) * 0.5, jnp.float32),
            jnp.asarray(rng.normal(size=(e, d)) * 0.1, jnp.float32),
        )
        gate = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
        X = rng.normal(size=(n, d)).astype(np.float32)
        mesh = make_mesh(MeshConfig(data=1, expert=e))

        def expert_fn(params, x):
            w, b = params
            return jnp.tanh(x @ w + b)

        out = _moe_model(experts, gate, mesh, expert_fn, batchSize=10).transform(
            Table({"f": X})
        )

        # reference: dense per-token top-1 expert
        logits = X @ np.asarray(gate)
        probs = np.exp(logits - logits.max(axis=1, keepdims=True))
        probs /= probs.sum(axis=1, keepdims=True)
        assign = logits.argmax(axis=1)
        want = np.zeros_like(X)
        for i in range(n):
            w_, b_ = np.asarray(experts[0][assign[i]]), np.asarray(experts[1][assign[i]])
            want[i] = np.tanh(X[i] @ w_ + b_) * probs[i, assign[i]]
        np.testing.assert_allclose(out.column("y"), want, rtol=2e-4, atol=2e-5)

    def test_without_apply_fn_raises(self):
        from mmlspark_tpu.data.table import Table
        from mmlspark_tpu.dnn import DNNModel

        m = DNNModel(feedDict={"x": "f"}, fetchDict={"y": "output"})
        with pytest.raises(ValueError, match="applyFn must be set"):
            m._jitted()
        with pytest.raises(ValueError, match="applyFn must be set"):
            m.transform(Table({"f": np.zeros((2, 3), np.float32)}))

    def test_wrong_expert_count_raises_through_transform(self):
        """Eight experts on a four-way expert axis: the op's own error comes
        out of ``transform``; the class validates nothing of a mode."""
        from mmlspark_tpu.data.table import Table

        rng = np.random.default_rng(6)
        experts = (
            jnp.asarray(rng.normal(size=(8, 8, 8)), jnp.float32),
            jnp.asarray(rng.normal(size=(8, 8)), jnp.float32),
        )
        gate = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        mesh = make_mesh(MeshConfig(data=1, expert=4), devices=jax.devices()[:4])
        m = _moe_model(experts, gate, mesh, _expert_fn, batchSize=8)
        with pytest.raises(ValueError, match="one expert per device"):
            m.transform(Table({"f": np.zeros((8, 8), np.float32)}))


def test_pipeline_unbatched_batch_must_divide_microbatches():
    """``miniBatcher=False`` feeds the table's rows as one batch, as they are:
    a count the microbatches do not divide raises the op's error through
    ``transform``, and one they divide equals the sequential stack."""
    from mmlspark_tpu.data.table import Table

    rng = np.random.default_rng(2)
    d, p = 8, 4
    params = _stack_params(rng, p, d)
    mesh = make_mesh(MeshConfig(data=2, pipe=p))
    m = _pipeline_model(params, mesh, p, miniBatcher=False)

    X = rng.normal(size=(10, d)).astype(np.float32)  # 10 % 4 != 0
    with pytest.raises(ValueError, match="not divisible"):
        m.transform(Table({"f": X}))

    X = rng.normal(size=(12, d)).astype(np.float32)
    out = m.transform(Table({"f": X}))
    want = np.asarray(_sequential(params, jnp.asarray(X)))
    np.testing.assert_allclose(out.column("y"), want, rtol=2e-4, atol=2e-5)


class TestExpertA2A:
    """Capacity-based all_to_all MoE dispatch (the GShard layout): tokens
    shard over the expert axis; overflow tokens drop to zero output."""

    def _setup(self, e=4, b=32, d=8, seed=0, skew=None):
        rng = np.random.default_rng(seed)
        ws = jnp.asarray(rng.normal(size=(e, d, d)) * 0.3, jnp.float32)
        bs = jnp.asarray(rng.normal(size=(e, d)) * 0.1, jnp.float32)
        x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
        gates = rng.normal(size=(b, e)).astype(np.float32)
        if skew is not None:
            gates[:, skew] += 10.0  # route (almost) everything to one expert
        return (ws, bs), x, jnp.asarray(gates)

    def test_matches_masked_dense_when_capacity_ample(self):
        from mmlspark_tpu.ops.expert_parallel import moe_apply, moe_apply_a2a

        mesh = make_mesh(MeshConfig(data=1, expert=4), devices=jax.devices()[:4])
        params, x, gates = self._setup()
        # capacity_factor high enough that nothing drops
        a2a = moe_apply_a2a(_expert_fn, params, x, gates, mesh, capacity_factor=4.0)
        dense = moe_apply(_expert_fn, params, x, gates, mesh)
        np.testing.assert_allclose(
            np.asarray(a2a), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    def test_overflow_tokens_drop_to_zero(self):
        from mmlspark_tpu.ops.expert_parallel import moe_apply_a2a

        mesh = make_mesh(MeshConfig(data=1, expert=4), devices=jax.devices()[:4])
        params, x, gates = self._setup(skew=2)  # everyone wants expert 2
        out = np.asarray(
            moe_apply_a2a(_expert_fn, params, x, gates, mesh, capacity_factor=1.0)
        )
        # per source: 8 local tokens, cap = ceil(8/4*1.0) = 2 slots for
        # expert 2 -> exactly 2 kept per device, 6 dropped (zero rows)
        zero_rows = (np.abs(out) < 1e-12).all(axis=1)
        assert zero_rows.sum() == 4 * 6, zero_rows.sum()
        # kept tokens match the dense computation for expert 2
        probs = np.asarray(jax.nn.softmax(gates, axis=1))
        xn = np.asarray(x)
        w2, b2 = np.asarray(params[0][2]), np.asarray(params[1][2])
        for i in np.nonzero(~zero_rows)[0]:
            want = (xn[i] @ w2 + b2) * probs[i, 2]
            np.testing.assert_allclose(out[i], want, rtol=2e-4, atol=2e-5)

    def test_expert_axis_one_falls_back(self):
        from mmlspark_tpu.ops.expert_parallel import moe_apply, moe_apply_a2a

        mesh = make_mesh(MeshConfig(data=8, expert=1))
        params, x, gates = self._setup(seed=2)
        np.testing.assert_allclose(
            np.asarray(moe_apply_a2a(_expert_fn, params, x, gates, mesh)),
            np.asarray(moe_apply(_expert_fn, params, x, gates, mesh)),
            rtol=2e-4, atol=2e-5,
        )

    def test_indivisible_batch_raises(self):
        from mmlspark_tpu.ops.expert_parallel import moe_apply_a2a

        mesh = make_mesh(MeshConfig(data=1, expert=4), devices=jax.devices()[:4])
        params, x, gates = self._setup(b=30)
        with pytest.raises(ValueError, match="not divisible"):
            moe_apply_a2a(_expert_fn, params, x, gates, mesh)
