#!/usr/bin/env python
"""Model-text parity of the GBDT fit across two checkouts.

    python tools/train_parity.py [--repo DIR] [--fit-cell-rows N] [--out FILE]

Fits each case of a fixed matrix (scan and loop paths, resident and chunked
U, early stopping, dart, GOSS, rf, feature bundling, quantized gradients, a
two-device mesh, an injected device OOM, ``train_many``) on the same seeded
data and prints one JSON line: the sha256 of every model text, keyed by
case. Run it once with ``--repo`` pointing at each checkout and compare the
lines: a refactor of the fit's driver must leave every hash where it was.

``--fit-cell-rows N`` adds the sha256 of the boosting scan program that the
``gbdt-higgs.fit-1m-resident`` cell's estimator lowers (28 features, 100
iterations, 31 leaves; 1,000,000 rows in the cell), captured at its first
dispatch, after which that fit is abandoned. On a TPU that is the resident
U program; off the chip the fit takes the compare-built path unless
``--force-u`` is given.

The mesh case needs two devices: off the chip the script asks XLA for two
host devices itself, on the chip it builds the mesh from the host's CPU
devices (the variable below gives it two there too).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

_FLAG = "--xla_force_host_platform_device_count=2"
if _FLAG.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def _data(rows: int = 1200, features: int = 8, seed: int = 3):
    import numpy as np

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features))
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=rows) > 0).astype(np.float64)
    return X, y


def _sparse_data(rows: int = 1200, features: int = 12, seed: int = 4):
    """Mostly-zero columns that never overlap, so feature bundling packs them."""
    import numpy as np

    rng = np.random.default_rng(seed)
    X = np.zeros((rows, features))
    owner = rng.integers(0, features, size=rows)
    X[np.arange(rows), owner] = rng.normal(size=rows) + 2.0
    y = (X[:, 0] + X[:, 1] - X[:, 2] > 0.5).astype(np.float64)
    return X, y


def cases():
    """name -> zero-argument callable returning the model texts of one case."""
    import jax
    import numpy as np

    from mmlspark_tpu.lightgbm.binning import bin_dataset
    from mmlspark_tpu.lightgbm.train import TrainOptions, train, train_many

    X, y = _data()
    bins, mapper = bin_dataset(X, max_bin=31)
    base = dict(objective="binary", num_iterations=6, num_leaves=7, max_bin=31, seed=5)

    def fit(b=bins, m=mapper, env=None, **kw):
        old = {k: os.environ.get(k) for k in (env or {})}
        os.environ.update(env or {})
        try:
            opts = TrainOptions(**{**base, **kw.pop("opts", {})})
            return [train(b, y, opts, mapper=m, **kw).booster.model_to_string()]
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def valid_early_stop():
        cut = 900
        opts = TrainOptions(**{**base, "num_iterations": 30, "early_stopping_round": 3,
                               "learning_rate": 0.4})
        res = train(bins[:cut], y[:cut], opts, mapper=mapper,
                    valid_sets=[("v", bins[cut:], y[cut:], None)])
        return [res.booster.model_to_string(), repr(res.evals), str(res.best_iteration)]

    def efb():
        Xs, ys = _sparse_data()
        bins_b, mapper_b = bin_dataset(Xs, max_bin=31, feature_bundling=True)
        assert mapper_b.bundles is not None, "no bundle formed"
        opts = TrainOptions(**base)
        return [train(bins_b, ys, opts, mapper=mapper_b).booster.model_to_string(),
                train(bins_b, ys, TrainOptions(**{**base, "histogram_method": "u"}),
                      mapper=mapper_b).booster.model_to_string()]

    def mesh2():
        from mmlspark_tpu.parallel import make_mesh

        devices = jax.devices() if len(jax.devices()) >= 2 else jax.devices("cpu")
        with jax.default_device(devices[0]):
            return fit(mesh=make_mesh(devices=devices[:2]))

    def oom(iteration, **kw):
        """A device OOM at ``iteration``'s histogram dispatch (the scan path
        dispatches at iteration 0 only)."""
        from mmlspark_tpu.runtime.faults import FaultPlan, inject_faults

        plan = FaultPlan().oom_task(iteration, "device")
        with inject_faults(plan):
            out = fit(opts={"histogram_method": "u"}, **kw)
        assert plan.fired, "the injected OOM never fired"
        return out

    def many():
        grid = [
            TrainOptions(**{**base, "learning_rate": 0.05}),
            TrainOptions(**{**base, "learning_rate": 0.1, "feature_fraction": 0.7}),
            TrainOptions(**{**base, "learning_rate": 0.2, "bagging_fraction": 0.7,
                            "bagging_freq": 1}),
        ]
        return [r.booster.model_to_string() for r in train_many(bins, y, grid, mapper=mapper)]

    return {
        "scan": lambda: fit(),
        "scan_bagging_features": lambda: fit(opts={"bagging_fraction": 0.7, "bagging_freq": 2,
                                                   "feature_fraction": 0.6}),
        "u_resident": lambda: fit(opts={"histogram_method": "u"}),
        "u_chunked": lambda: fit(opts={"histogram_method": "u"},
                                 env={"MMLSPARK_TPU_U_BUDGET": str(64 << 10)}),
        "loop_hook": lambda: fit(iteration_hook=lambda it, tree: None),
        "valid_early_stop": valid_early_stop,
        "dart": lambda: fit(opts={"boosting_type": "dart", "drop_rate": 0.5}),
        "goss": lambda: fit(opts={"boosting_type": "goss"}),
        "rf": lambda: fit(opts={"boosting_type": "rf", "bagging_fraction": 0.7,
                                "bagging_freq": 1}),
        "multiclass": lambda: _multiclass(bins, mapper, base),
        "efb": efb,
        "quantized": lambda: fit(opts={"histogram_method": "u", "use_quantized_grad": True}),
        "mesh2": mesh2,
        "device_oom_scan": lambda: oom(0),
        "device_oom_loop": lambda: oom(2, iteration_hook=lambda it, tree: None),
        "train_many3": many,
    }


def _multiclass(bins, mapper, base):
    import numpy as np

    from mmlspark_tpu.lightgbm.train import TrainOptions, train

    X, _ = _data()
    y3 = np.digitize(X[:, 0] + X[:, 3], [-0.5, 0.5]).astype(np.float64)
    opts = TrainOptions(**{**base, "objective": "multiclass", "num_class": 3})
    return [train(bins, y3, opts, mapper=mapper).booster.model_to_string()]


def fit_cell_lowering(rows: int, force_u: bool = False) -> str:
    """sha256 of the fit cell's scan program, lowered at its first dispatch.
    ``force_u``: take the chip's resident-U path off the chip (``on_tpu``
    answers True) and build only U's shape, so the program the cell runs on
    a TPU lowers on a host that could not hold U."""
    import jax
    import numpy as np

    import mmlspark_tpu.lightgbm.train as train_mod
    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, 28))
    y = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + 0.5 * rng.normal(size=rows) > 0).astype(np.float64)
    found = {}

    class _Lowered(Exception):
        pass

    inner = train_mod.cached_program

    def spy(key, make):
        program = inner(key, make)
        if force_u and isinstance(key, tuple) and key and key[0] == "u_build_jit":
            return lambda bins: jax.eval_shape(program, bins)
        if not (isinstance(key, tuple) and key and key[0] == "scan"):
            return program

        def lower_only(*args):
            found["sha"] = _sha(program.lower(*args).as_text())
            raise _Lowered

        return lower_only

    train_mod.cached_program = spy
    on_tpu = train_mod.on_tpu
    if force_u:
        train_mod.on_tpu = lambda: True
    try:
        LightGBMClassifier(numIterations=100, numLeaves=31, maxBin=255, learningRate=0.1,
                           numTasks=1).fit(Table({"features": X, "label": y}))
    except _Lowered:
        pass
    finally:
        train_mod.cached_program = inner
        train_mod.on_tpu = on_tpu
    return found.get("sha", "not lowered")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    help="checkout whose mmlspark_tpu is imported")
    ap.add_argument("--fit-cell-rows", type=int, default=0)
    ap.add_argument("--force-u", action="store_true",
                    help="lower the fit cell's resident-U program off the chip")
    ap.add_argument("--out", default=None, help="also write the JSON line here")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax

    out = {"repo": os.path.abspath(args.repo), "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind, "cases": {}}
    for name, run in cases().items():
        try:
            out["cases"][name] = _sha("\n\x00\n".join(run()))
        except Exception as e:  # noqa: BLE001 - a failing case is reported, not fatal
            out["cases"][name] = f"error: {type(e).__name__}: {str(e)[:200]}"
    if args.fit_cell_rows:
        out["fit_cell_rows"] = args.fit_cell_rows
        out["fit_cell_scan_lowered"] = fit_cell_lowering(args.fit_cell_rows, args.force_u)
    line = json.dumps(out, sort_keys=True)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")


if __name__ == "__main__":
    main()
