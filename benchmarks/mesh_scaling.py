"""Data-parallel GBDT scaling over the mesh ``data`` axis (1/2/4/8 devices).

Makes the "linear speed-up" claim of distributed LightGBM
(``/root/reference/docs/lightgbm.md:19-21``) falsifiable for this runtime:
the SAME dataset is fitted at every mesh width, reporting

- measured wall time per boosting iteration (CAVEAT below),
- XLA-compiled cost-model FLOPs of one boosting step per device — the
  hardware-independent compute-side evidence: it must shrink ~1/devices,
- the analytic per-pass allreduce payload (k*F*B*3*4 bytes — independent of
  both N and the device count: the histogram reduce is the ONLY
  communication, which is why the algorithm weak-scales),
- held-out AUC at every width (exact histogram sums -> parity).

CAVEAT: this rig emulates the mesh with virtual CPU devices on ONE physical
core (`xla_force_host_platform_device_count`), so wall time cannot flatten —
the devices time-share the core and collectives serialize. Wall time is
reported for honesty; the falsifiable scaling signal on this hardware is the
per-device cost-model FLOPs plus the constant communication volume. On a
real ICI mesh the same programs run one device per chip.

Run: ``python benchmarks/mesh_scaling.py`` (forces the CPU platform itself).
Writes ``docs/mesh_scaling.md``.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ROWS = int(os.environ.get("MESH_BENCH_ROWS", 200_000))
N_FEATURES = 16
N_ITERS = 10
NUM_LEAVES = 15
MAX_BIN = 63


def main():
    from mmlspark_tpu.parallel.mesh import force_platform

    force_platform("cpu", min_devices=8)

    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()

    import jax
    import numpy as np

    from mmlspark_tpu.lightgbm.binning import bin_dataset
    from mmlspark_tpu.lightgbm.objectives import auc
    from mmlspark_tpu.lightgbm.train import TrainOptions, train
    from mmlspark_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(0)
    n_test = 40_000
    X = rng.normal(size=(N_ROWS + n_test, N_FEATURES))
    y = ((X[:, 0] + X[:, 1] * X[:, 2] + 0.5 * rng.normal(size=len(X))) > 0).astype(
        np.float64
    )
    Xtr, ytr = X[:N_ROWS], y[:N_ROWS]
    Xte, yte = X[N_ROWS:], y[N_ROWS:]
    bins, mapper = bin_dataset(Xtr, max_bin=MAX_BIN)

    opts = TrainOptions(
        objective="binary", num_iterations=N_ITERS, num_leaves=NUM_LEAVES,
        max_bin=MAX_BIN,
    )

    rows = []
    for d in (1, 2, 4, 8):
        mesh = (
            None if d == 1
            else make_mesh(MeshConfig(data=d), devices=jax.devices()[:d])
        )
        train(bins, ytr, opts, mapper=mapper, mesh=mesh)  # warm (compile)
        t0 = time.perf_counter()
        result = train(bins, ytr, opts, mapper=mapper, mesh=mesh)
        dt = time.perf_counter() - t0
        a = auc(yte, result.booster.raw_margin(Xte)[:, 0], np.ones(n_test))

        flops = _step_flops(d, bins, ytr, opts, mapper, mesh)
        rows.append(
            dict(
                devices=d,
                rows_per_device=N_ROWS // d,
                secs_per_iter=dt / N_ITERS,
                step_flops_per_device=flops,
                auc=a,
            )
        )
        print(rows[-1])

    aucs = [r["auc"] for r in rows]
    assert max(aucs) - min(aucs) < 2e-3, f"AUC parity violated: {aucs}"

    # Per-pass allreduce payload: the reduced histogram (leaf_batch nodes x
    # F x B x 3 f32) — independent of N and of the device count.
    k = min(opts.leaf_batch, NUM_LEAVES - 1)
    comm = k * N_FEATURES * (MAX_BIN + 1) * 3 * 4

    base = rows[0]["step_flops_per_device"]
    lines = [
        "# Mesh scaling — data-parallel GBDT (virtual 8-device CPU mesh)",
        "",
        f"Dataset {N_ROWS:,} x {N_FEATURES}, {N_ITERS} iterations, "
        f"{NUM_LEAVES} leaves, max_bin {MAX_BIN}. Same data at every width.",
        "",
        "| data devices | rows/device | wall secs/iter* | step FLOPs/device (XLA cost model) | vs 1-device | holdout AUC |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        ratio = (
            "—" if not (base and r["step_flops_per_device"])
            else f"{r['step_flops_per_device'] / base:.2f}x"
        )
        fl = r["step_flops_per_device"]
        lines.append(
            f"| {r['devices']} | {r['rows_per_device']:,} | "
            f"{r['secs_per_iter']:.3f} | {fl:.3g} | {ratio} | {r['auc']:.4f} |"
        )
    lines += [
        "",
        "*Wall time on this rig CANNOT flatten: the 8 virtual devices",
        "time-share ONE physical core and collectives serialize "
        "(`xla_force_host_platform_device_count`). The falsifiable scaling",
        "evidence here is the cost-model FLOPs column — the per-device",
        "compute of one compiled boosting step, which XLA partitions to",
        "~1/devices — plus the communication side: the only collective is",
        f"the histogram allreduce, {comm:,} bytes per pass "
        "(leaf_batch x F x B x 3 f32), independent of BOTH the row count",
        "and the device count. Compute shrinks per device, communication",
        "stays constant per pass: the weak-scaling shape of distributed",
        "LightGBM's own experiments (docs/lightgbm.md:19-21), with AUC",
        "parity at every width (exact histogram sums).",
        "",
        f"Generated by `benchmarks/mesh_scaling.py` (rows={N_ROWS:,}).",
    ]
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "mesh_scaling.md",
    )
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    print(f"wrote {out}")


def _step_flops(d, bins, y, opts, mapper, mesh):
    """FLOPs of ONE compiled boosting step per device, from XLA's cost
    model. Under SPMD the analysis reports the per-device program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.lightgbm.objectives import get_objective
    from mmlspark_tpu.lightgbm.train import _make_step

    try:
        objective = get_objective(opts.objective)
        step = _make_step(opts, objective, opts.max_bin + 1, mesh)
        n, f = bins.shape
        edges = np.where(
            np.isfinite(mapper.edges), mapper.edges, np.finfo(np.float32).max
        ).astype(np.float32)

        if mesh is not None:
            from mmlspark_tpu.parallel.mesh import data_sharding, replicated

            sh_rows = data_sharding(mesh)
            sh_rep = replicated(mesh)
            bins_d = jax.device_put(bins.astype(np.uint8), sh_rows)
            y_d = jax.device_put(y.astype(np.float32), sh_rows)
            edges_d = jax.device_put(edges, sh_rep)
        else:
            bins_d = jnp.asarray(bins.astype(np.uint8))
            y_d = jnp.asarray(y.astype(np.float32))
            edges_d = jnp.asarray(edges)
        w_d = jnp.ones_like(y_d)
        margins = jnp.zeros((n, 1), jnp.float32)
        bag = jnp.ones(n, jnp.float32)
        fm = jnp.ones(f, jnp.float32)
        lowered = jax.jit(step).lower(
            bins_d, y_d, w_d, margins, edges_d, bag, fm, jnp.int32(0), None
        )
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0)) if cost else 0.0
    except Exception as e:  # cost model availability varies by backend
        print(f"  (cost analysis unavailable: {type(e).__name__}: {e})")
        return 0.0


if __name__ == "__main__":
    main()
