"""Mesh/topology tests — run on the 8-virtual-device CPU platform."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.data.table import Table
from mmlspark_tpu.parallel.mesh import (
    MeshConfig,
    get_topology,
    make_mesh,
    pad_to_multiple,
)


def test_topology_discovery():
    topo = get_topology()
    assert topo.num_devices == 8
    assert topo.platform == "cpu"


def test_default_mesh_all_data():
    mesh = make_mesh()
    assert mesh.shape["data"] == 8
    assert mesh.shape["model"] == 1


def test_mesh_config_resolution():
    cfg = MeshConfig(model=2)
    sizes = cfg.resolve(8)
    assert sizes["data"] == 4 and sizes["model"] == 2
    with pytest.raises(ValueError):
        MeshConfig(model=3).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(data=3, model=2).resolve(8)


def test_psum_over_mesh():
    from jax.sharding import PartitionSpec as P


    mesh = make_mesh()
    x = jnp.arange(8.0)

    def f(x):
        return jax.lax.psum(x, "data")

    out = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
    )(x)
    assert float(out[0]) == 28.0


def test_pad_to_multiple():
    assert pad_to_multiple(10, 8) == (16, 6)
    assert pad_to_multiple(16, 8) == (16, 0)


class TestDistributedBootstrap:
    def test_executor_keyed_numbering(self):
        from mmlspark_tpu.parallel.mesh import distributed_init

        # single-executor: no process group to form, returns local topology
        topo = distributed_init(
            executor_ids=["exec-1"], local_executor_id="exec-1"
        )
        assert topo.num_devices >= 1
        # multi-executor derivation without a coordinator must fail loudly,
        # not silently run single-host
        with pytest.raises(ValueError, match="coordinator_address"):
            distributed_init(
                executor_ids=["exec-3", "exec-1", "exec-2"],
                local_executor_id="exec-2",
            )

    def test_executor_keyed_validation(self):
        from mmlspark_tpu.parallel.mesh import distributed_init

        with pytest.raises(ValueError, match="local_executor_id"):
            distributed_init(executor_ids=["a", "b"])
        with pytest.raises(ValueError, match="not in executor_ids"):
            distributed_init(executor_ids=["a", "b"], local_executor_id="c")

    def test_partition_assignment(self, mesh8):
        from mmlspark_tpu.parallel.mesh import partition_assignment

        assign = partition_assignment(16, mesh8)
        assert len(assign) == 16
        data_coords = [c[0] for c in assign.values()]
        # round-robin covers every data slice exactly twice
        assert sorted(data_coords) == sorted(list(range(8)) * 2)

    def test_partition_assignment_underfull_raises(self, mesh8):
        from mmlspark_tpu.parallel.mesh import partition_assignment

        with pytest.raises(ValueError, match="empty mesh slices"):
            partition_assignment(4, mesh8)


class TestModelAxis:
    def _mesh42(self):
        from mmlspark_tpu.parallel.mesh import MeshConfig, make_mesh

        return make_mesh(MeshConfig(data=4, model=2))

    def test_feature_parallel_gbdt_matches_serial(self):
        from mmlspark_tpu.lightgbm.binning import bin_dataset
        from mmlspark_tpu.lightgbm.train import TrainOptions, train

        rng = np.random.default_rng(0)
        X = rng.normal(size=(256, 8))  # 8 features over model=2
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
        bins, mapper = bin_dataset(X, max_bin=31)
        opts = TrainOptions(objective="binary", num_iterations=5, num_leaves=7, max_bin=31)
        r_serial = train(bins, y, opts, mapper=mapper)
        r_fp = train(bins, y, opts, mapper=mapper, mesh=self._mesh42())
        np.testing.assert_array_equal(
            r_serial.booster.split_feature, r_fp.booster.split_feature
        )
        np.testing.assert_allclose(
            r_serial.booster.leaf_values, r_fp.booster.leaf_values, rtol=1e-5, atol=1e-6
        )

    def test_dnn_tensor_parallel_matches_replicated(self):
        from mmlspark_tpu.dnn import DNNModel
        from mmlspark_tpu.parallel.mesh import MeshConfig

        rng = np.random.default_rng(1)
        w1 = rng.normal(size=(6, 16)).astype(np.float32)
        w2 = rng.normal(size=(16, 3)).astype(np.float32)

        def mlp(params, inputs):
            import jax.numpy as jnp

            h = jnp.maximum(inputs["x"] @ params["w1"], 0)
            return {"y": h @ params["w2"]}

        X = rng.normal(size=(16, 6)).astype(np.float64)
        t = Table({"f": X})
        base = dict(
            applyFn=mlp, modelParams={"w1": w1, "w2": w2},
            feedDict={"x": "f"}, fetchDict={"out": "y"}, batchSize=8,
        )
        plain = DNNModel(**base).transform(t)
        tp = DNNModel(
            **base,
            shardOverMesh=True,
            meshConfig=MeshConfig(data=4, model=2),
            # w1 sharded over its output dim, w2 over its input dim — the
            # classic column-then-row TP split of an MLP
            paramShardings={"w1": 1, "w2": 0},
        ).transform(t)
        np.testing.assert_allclose(
            plain.column("out"), tp.column("out"), rtol=1e-4, atol=1e-5
        )
