#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py               # on a TPU; anything else exits non-zero
    python chip_smoke.py --dry-run-cpu # same phases, toy sizes, CPU, says so

One process, no arguments on the chip, no network, data from a seed. It
drives fit -> predict -> serve through the public entry points at the
flagship width (28 features x 255 bins x 31 leaves), then the deep path and
every Pallas kernel the tree keeps, compiled. A phase that fails raises, so
the exit code is non-zero; nothing here catches a failure to print it.

The last line of standard output is one JSON object naming the device as
JAX reports it. Per-phase lines above it carry what each phase measured;
``compile_secs`` is what JAX spent in backend compilation (or in loading
from the persistent cache), so a second run in the same checkout shows the
drop. No number printed here is a speed.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import urllib.request
from typing import NamedTuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int
    test_rows: int
    iterations: int
    auc_slack: float  # allowed AUC shortfall against the sklearn reference
    chunk_budget: int  # MMLSPARK_TPU_U_BUDGET of the chunked-U phase
    posts: int
    clients: int
    resnet: str
    small_inputs: bool
    image_size: int
    images: int
    kernel_rows: int
    kernel_features: int
    interpret: bool  # passed to every Pallas kernel explicitly


FEATURES, MAX_BIN, NUM_LEAVES = 28, 255, 31
CHIP = Sizes(
    rows=400_000, test_rows=50_000, iterations=10, auc_slack=0.01,
    chunk_budget=1 << 30, posts=64, clients=8, resnet="resnet50",
    small_inputs=False, image_size=224, images=64, kernel_rows=400_000,
    kernel_features=FEATURES, interpret=False,
)
DRY = Sizes(
    rows=6_000, test_rows=2_000, iterations=3, auc_slack=0.05,
    chunk_budget=1 << 20, posts=16, clients=4, resnet="resnet18",
    small_inputs=True, image_size=32, images=8, kernel_rows=2_048,
    kernel_features=4, interpret=True,
)


class Data(NamedTuple):
    train: object  # Table(features, label)
    test: object
    Xtr: object
    ytr: object
    Xte: object
    yte: object


def make_data(sz: Sizes, seed: int = 0) -> Data:
    """The bench.py recipe: Higgs-like continuous float64 features."""
    import numpy as np

    from mmlspark_tpu.data.table import Table

    n = sz.rows + sz.test_rows
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, FEATURES)).astype(np.float64)
    logit = (
        X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + 0.8 * np.sin(X[:, 3])
        + 0.5 * rng.normal(size=n)
    )
    y = (logit > 0).astype(np.float64)
    Xtr, ytr, Xte, yte = X[: sz.rows], y[: sz.rows], X[sz.rows :], y[sz.rows :]
    return Data(
        Table({"features": Xtr, "label": ytr}),
        Table({"features": Xte, "label": yte}),
        Xtr, ytr, Xte, yte,
    )


class CompileClock:
    """What the program's own tracer booked of JAX's compile events between
    ``reset`` calls (``Tracer.compile_log``, every owner span and
    ``(no span)`` together): seconds in backend compilation (a
    persistent-cache hit books its load time there), seconds tracing and
    lowering, and the cache's hit and miss counts. The log keeps its newest
    thousand records; the seven phases book a few dozen."""

    _KEYS = {
        "compile_secs": "compile_s", "trace_secs": "trace_s",
        "cache_hits": "cache_hits", "cache_misses": "cache_misses",
    }

    def __init__(self) -> None:
        # imported after jax, so the tracer listens from here on at the latest
        from mmlspark_tpu.observability.tracing import get_tracer

        self._log = get_tracer().compile_log
        self._seen = self._totals()

    def _totals(self) -> dict:
        log = self._log()
        return {out: sum(r[key] for r in log) for out, key in self._KEYS.items()}

    def reset(self) -> dict:
        """What accumulated since the last call; starts the next window."""
        now = self._totals()
        out = {key: now[key] - self._seen[key] for key in now}
        self._seen = now
        out["compile_secs"] = round(out["compile_secs"], 2)
        out["trace_secs"] = round(out["trace_secs"], 2)
        return out


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, report: dict):
    """Time one phase and print its line. The body fills ``out``; an
    exception leaves the ``with`` block unprinted and ends the run."""
    clock.reset()
    out: dict = {}
    t0 = time.perf_counter()
    yield out
    out["wall_secs"] = round(time.perf_counter() - t0, 2)
    out.update(clock.reset())
    report[name] = out
    print(json.dumps({"phase": name, **out}), flush=True)


def require(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


@contextlib.contextmanager
def fit_events():
    """The histogram-path events a fit publishes while the block runs."""
    from mmlspark_tpu.observability.events import (
        HistogramChunked,
        HistogramDegraded,
        MemoryPressure,
        get_bus,
    )

    seen: list = []
    kinds = (HistogramChunked, HistogramDegraded, MemoryPressure)

    def listener(event) -> None:
        if isinstance(event, kinds):
            seen.append(event)

    bus = get_bus()
    bus.add_listener(listener)
    try:
        yield seen
    finally:
        bus.remove_listener(listener)


def _names(events) -> list:
    return [type(e).__name__ for e in events]


def _classifier(sz: Sizes, num_tasks: int):
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    return LightGBMClassifier(
        numIterations=sz.iterations, numLeaves=NUM_LEAVES, maxBin=MAX_BIN,
        numTasks=num_tasks,
    )


def _auc(data: Data, model) -> float:
    from sklearn.metrics import roc_auc_score

    scored = model.transform(data.test)
    return float(roc_auc_score(data.yte, scored["probability"][:, 1]))


def phase_fit(sz: Sizes, data: Data, out: dict):
    """Resident-U fit on one device against sklearn at matched settings."""
    import jax
    import numpy as np
    from sklearn.ensemble import HistGradientBoostingClassifier
    from sklearn.metrics import roc_auc_score

    from mmlspark_tpu.core.device import on_tpu
    from mmlspark_tpu.ops.u_histogram import make_u_spec, u_bytes

    with fit_events() as events:
        model = _classifier(sz, num_tasks=1).fit(data.train)
    out["events"] = _names(events)
    require(not events, f"the fit degraded or chunked: {out['events']}")

    out["auc"] = round(_auc(data, model), 5)
    ref = HistGradientBoostingClassifier(
        max_iter=sz.iterations, max_leaf_nodes=NUM_LEAVES, learning_rate=0.1,
        max_bins=MAX_BIN, early_stopping=False, random_state=0,
    ).fit(data.Xtr, data.ytr)
    out["auc_sklearn"] = round(
        float(roc_auc_score(data.yte, ref.decision_function(data.Xte))), 5
    )
    require(
        out["auc"] >= out["auc_sklearn"] - sz.auc_slack,
        f"AUC {out['auc']} < sklearn {out['auc_sklearn']} - {sz.auc_slack}",
    )

    # the resident one-hot is the largest thing a fit allocates: a peak
    # below it means some fallback ran instead. A continuous feature owns
    # its finite edges plus the missing and overflow bins.
    widths = [int(np.isfinite(e).sum()) + 2 for e in model.booster.bin_edges]
    spec = make_u_spec(MAX_BIN + 1, FEATURES, widths)
    out["u_bytes"] = u_bytes(sz.rows, spec)
    stats = jax.devices()[0].memory_stats()
    if on_tpu():
        require(stats is not None, "the device reports no memory_stats()")
        out["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
        require(
            out["peak_bytes_in_use"] >= out["u_bytes"],
            f"peak {out['peak_bytes_in_use']} B < resident U {out['u_bytes']}"
            " B: the resident-U path did not run on the device",
        )
    else:
        out["peak_bytes_in_use"] = "not reported off-chip (no U path there)"
    return model


def phase_predict(sz: Sizes, data: Data, model, out: dict):
    """transform on the held-out rows against the booster's raw margin and
    a host tree walk over the same arrays."""
    import numpy as np

    Xte = data.Xte
    scored = model.transform(data.test)
    probs = np.asarray(scored["probability"])
    require(probs.shape == (sz.test_rows, 2), f"probability {probs.shape}")
    require(bool(np.isfinite(probs).all()), "non-finite probability")
    booster = model.booster
    margin = booster.raw_margin(Xte)[:, 0]
    linked = 1.0 / (1.0 + np.exp(-margin))
    out["max_abs_vs_margin"] = float(np.abs(probs[:, 1] - linked).max())
    require(
        out["max_abs_vs_margin"] <= 1e-5, "transform disagrees with raw_margin"
    )

    # reference on a small input: walk each tree on the host in float32.
    # The margins under test come from the one full-size dispatch above,
    # which is where a shape-dependent miscompile shows (PERF.md, bring-up).
    rows = min(256, sz.test_rows)
    X32 = Xte[:rows].astype(np.float32)
    walked = np.full(rows, float(booster.init_score[0]), np.float64)
    for t in range(booster.num_trees):
        for i in range(rows):
            node = 0
            while not booster.is_leaf[t, node]:
                x = X32[i, booster.split_feature[t, node]]
                thr = np.float32(booster.split_threshold[t, node])
                child = booster.left_child if np.isnan(x) or x <= thr else booster.right_child
                node = child[t, node]
            walked[i] += booster.leaf_values[t, node]
    out["max_abs_vs_host_walk"] = float(np.abs(walked - margin[:rows]).max())
    require(
        out["max_abs_vs_host_walk"] <= 1e-4,
        "device margins disagree with the host tree walk",
    )
    return probs


def phase_chunked(sz: Sizes, data: Data, model, out: dict):
    """The same fit with the U budget cut so every pass streams row chunks;
    train.py claims the model text does not change."""
    from mmlspark_tpu.core.device import on_tpu

    previous = os.environ.get("MMLSPARK_TPU_U_BUDGET")
    os.environ["MMLSPARK_TPU_U_BUDGET"] = str(sz.chunk_budget)
    try:
        with fit_events() as events:
            chunked = _classifier(sz, num_tasks=1).fit(data.train)
    finally:
        if previous is None:
            del os.environ["MMLSPARK_TPU_U_BUDGET"]
        else:
            os.environ["MMLSPARK_TPU_U_BUDGET"] = previous
    out["events"] = _names(events)
    bad = [n for n in out["events"] if n != "HistogramChunked"]
    require(not bad, f"the chunked fit degraded: {bad}")
    if on_tpu():
        require("HistogramChunked" in out["events"], "no HistogramChunked event")
        out["num_chunks"] = events[0].num_chunks
        out["chunk_rows"] = events[0].chunk_rows
    else:
        out["u_path"] = "inactive off-chip: both fits take the XLA path"
    out["auc"] = round(_auc(data, chunked), 5)
    out["model_text_identical"] = (
        chunked.get_model_string() == model.get_model_string()
    )
    require(out["model_text_identical"], "chunked and resident U disagree")


def phase_serve(sz: Sizes, data: Data, model, probs, out: dict):
    """ServingServer on an ephemeral port, POSTs from client threads."""
    import numpy as np

    from mmlspark_tpu.observability.registry import MetricsRegistry
    from mmlspark_tpu.serving import ServingServer

    Xte = data.Xte
    registry = MetricsRegistry()
    replies: list = [None] * sz.posts

    def client(worker: int, url: str) -> None:
        for i in range(worker, sz.posts, sz.clients):
            body = json.dumps({"features": Xte[i].tolist()}).encode()
            request = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"}
            )
            # urlopen raises on any status but 2xx; the slot then stays None
            with urllib.request.urlopen(request, timeout=120) as reply:
                replies[i] = (reply.status, json.loads(reply.read()))

    with ServingServer(
        model, input_col="features", output_col="probability",
        registry=registry, reply_timeout_s=120.0,
    ) as server:
        threads = [
            threading.Thread(target=client, args=(w, server.info.url))
            for w in range(sz.clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        require(not any(t.is_alive() for t in threads), "a client thread hung")
    require(all(r is not None for r in replies), "a POST failed")
    require(all(status == 200 for status, _ in replies), "a reply was not 200")
    served = np.asarray([body["probability"] for _, body in replies])
    out["max_abs_vs_transform"] = float(np.abs(served - probs[: sz.posts]).max())
    require(out["max_abs_vs_transform"] <= 1e-6, "served != transform")
    out["requests"] = int(registry.get("serving_requests_total").value)
    out["batches"] = int(registry.get("serving_batches_total").value)
    out["retries"] = int(registry.get("serving_retries_total").value)
    require(out["requests"] == sz.posts, f"{out['requests']} requests answered")
    require(out["retries"] == 0, f"{out['retries']} task retries hid an error")


def phase_deep(sz: Sizes, out: dict):
    """ImageFeaturizer over seeded ResNet weights against the float32
    forward of the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.image import ImageFeaturizer
    from mmlspark_tpu.models import init_resnet, resnet_apply

    params = init_resnet(
        seed=0, variant=sz.resnet, small_inputs=sz.small_inputs
    )
    rng = np.random.default_rng(1)
    images = np.empty(sz.images, dtype=object)
    for i in range(sz.images):
        images[i] = rng.integers(
            0, 256, size=(sz.image_size, sz.image_size, 3), dtype=np.uint8
        )
    featurizer = ImageFeaturizer(
        inputCol="image", outputCol="features", modelParams=params,
        inputHeight=sz.image_size, inputWidth=sz.image_size,
        batchSize=sz.images,
    )
    feats = np.asarray(featurizer.transform(Table({"image": images}))["features"])
    width = 2048 if sz.resnet == "resnet50" else 512
    require(feats.shape == (sz.images, width), f"features {feats.shape}")
    require(bool(np.isfinite(feats).all()), "non-finite features")

    x = np.stack(list(images)).astype(np.float32) / 255.0
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(
            jax.jit(lambda p, v: resnet_apply(p, v, 1))(
                params, jnp.asarray(x.transpose(0, 3, 1, 2))
            )
        )
    cosine = (feats * ref).sum(1) / (
        np.linalg.norm(feats, axis=1) * np.linalg.norm(ref, axis=1)
    )
    out["model"] = f"{sz.resnet} {sz.image_size}x{sz.image_size} batch {sz.images}"
    out["min_cosine_vs_f32"] = float(cosine.min())
    require(out["min_cosine_vs_f32"] >= 0.999, "featurizer drifted from float32")


def phase_kernels(sz: Sizes, out: dict):
    """Every Pallas kernel the tree keeps against the XLA one-hot pass.
    g and h are bf16-exact so both formulations see the same products and
    only the float32 accumulation order differs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.ops.histogram import build_histograms
    from mmlspark_tpu.ops.pallas_histogram import (
        bin_scatter_fits_vmem,
        build_histograms_bin_scatter,
        build_histograms_pallas,
        build_histograms_panel_pallas,
    )
    from mmlspark_tpu.ops.u_histogram import make_u_spec, stat_rows_quant

    n, f, b, nodes = sz.kernel_rows, sz.kernel_features, MAX_BIN + 1, 8
    rng = np.random.default_rng(2)

    def exact(a):
        return jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)

    # mixed per-feature widths: the packed layouts then start features at
    # offsets that are not multiples of any tile
    widths = [(256, 256, 200, 64, 17, 256, 3)[j % 7] for j in range(f)]
    bins = jnp.asarray(
        np.stack([rng.integers(0, w, size=n) for w in widths], axis=1),
        jnp.int32,
    )
    g, h = exact(rng.normal(size=n)), exact(rng.uniform(0.1, 1.0, size=n))
    c = jnp.asarray(rng.uniform(size=n) < 0.8, jnp.float32)
    node1 = jnp.zeros(n, jnp.int32)
    node8 = jnp.asarray(rng.integers(0, nodes, size=n), jnp.int32)

    def reference(grad, hess, node, k):
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(
                lambda *a: build_histograms(*a, k, b, method="onehot")
            )(bins, grad, hess, c, node))

    def check(name, got, want):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[..., 2], want[..., 2], err_msg=name)
        # sums reach 1e5 and cancel; a dropped or doubled row shows in the
        # exact count check above, so this only bounds float32 reordering
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=0.25, err_msg=name)
        how = "interpreted" if sz.interpret else "compiled"
        out[name] = f"{how}, matches onehot"

    ref1, ref8 = reference(g, h, node1, 1), reference(g, h, node8, nodes)
    check("build_histograms_pallas", jax.jit(
        lambda *a: build_histograms_pallas(*a, 1, b, interpret=sz.interpret)
    )(bins, g, h, c, node1), ref1)
    check("build_histograms_panel_pallas", jax.jit(
        lambda *a: build_histograms_panel_pallas(*a, nodes, b, interpret=sz.interpret)
    )(bins, g, h, c, node8), ref8)

    spec = make_u_spec(b, f, widths)
    require(bin_scatter_fits_vmem(spec.k_pad, f), "bin_scatter VMEM gate")
    check("build_histograms_bin_scatter", jax.jit(
        lambda *a: build_histograms_bin_scatter(*a, nodes, spec, interpret=sz.interpret)
    )(bins, g, h, c, node8), ref8)
    # quantized variant: integer sums, so the comparison is exact
    q, scales = stat_rows_quant(g, h, c, jax.random.PRNGKey(0))
    qf = q.astype(jnp.float32)
    want = np.rint(reference(qf[0], qf[1], node8, nodes)).astype(np.int64)
    got = jax.jit(lambda q_, scales_, *a: build_histograms_bin_scatter(
        *a, nodes, spec, stats=(q_, scales_), dequant=False,
        interpret=sz.interpret,
    ))(q, scales, bins, g, h, c, node8)
    np.testing.assert_array_equal(
        np.asarray(got).astype(np.int64), want, err_msg="bin_scatter quant"
    )
    out["build_histograms_bin_scatter_quant"] = f"{got.dtype} sums exact"


def _shard_probe(rows: int):
    """A training delegate that, while the fit's arrays are still alive,
    finds the binned matrix among them and records where its shards sit."""
    from mmlspark_tpu.lightgbm.callbacks import TrainingCallback

    class Probe(TrainingCallback):
        def __init__(self) -> None:
            self.shards: list = []

        def after_training(self, env) -> None:
            import jax
            import numpy as np

            for a in jax.live_arrays():
                if a.dtype == np.uint8 and a.shape == (rows, FEATURES):
                    self.shards = [
                        (str(s.device), int(s.data.shape[0]))
                        for s in a.addressable_shards
                    ]

    return Probe()


def phase_four_chips(sz: Sizes, data: Data, auc_one: float, out: dict):
    """The phase-1 fit row-sharded over four devices."""
    import jax

    if len(jax.devices()) < 4:
        out["skipped"] = f"{len(jax.devices())} device"
        return
    probe = _shard_probe(sz.rows)
    with fit_events() as events:
        model = _classifier(sz, num_tasks=4).set_delegate(probe).fit(data.train)
    out["events"] = _names(events)
    require(not events, f"the mesh fit degraded: {out['events']}")
    out["bins_shards"] = probe.shards
    require(
        len({d for d, _ in probe.shards}) == 4
        and all(r == sz.rows // 4 for _, r in probe.shards),
        f"binned matrix not split over four devices: {probe.shards}",
    )
    out["auc"] = round(_auc(data, model), 5)
    out["auc_delta_vs_one_chip"] = round(out["auc"] - auc_one, 5)
    require(abs(out["auc_delta_vs_one_chip"]) <= 2e-3, "mesh fit AUC moved")


def build_native() -> str:
    """Build the host library from what git commits, and say which path
    bins. A checkout has no .so; without this the numpy path runs silently."""
    import mmlspark_tpu.native as native

    if native.native_disabled():
        return "numpy (MMLSPARK_TPU_NATIVE disables the library)"
    if not (shutil.which("make") and shutil.which("g++")):
        return "numpy (no make/g++ on this machine)"
    native.build()  # raises when make fails or the built library won't load
    require(native.native_available(), "native library built but not in use")
    return "native (built from native/mmlspark_native.cpp)"


def run(sz: Sizes, dry_run: bool) -> dict:
    import jax

    from mmlspark_tpu.core.device import configure_compile_cache

    device = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps({"device": device, "dry_run": dry_run}), flush=True)
    cache_dir = configure_compile_cache()
    print(json.dumps({
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": (
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        ),
        "binning": build_native(),
    }), flush=True)

    clock = CompileClock()
    report: dict = {}
    data = make_data(sz)
    with phase("1_fit", clock, report) as out:
        model = phase_fit(sz, data, out)
    with phase("2_predict", clock, report) as out:
        probs = phase_predict(sz, data, model, out)
    with phase("3_chunked_u", clock, report) as out:
        phase_chunked(sz, data, model, out)
    with phase("4_serve", clock, report) as out:
        phase_serve(sz, data, model, probs, out)
    with phase("5_deep", clock, report) as out:
        phase_deep(sz, out)
    with phase("6_kernels", clock, report) as out:
        phase_kernels(sz, out)
    with phase("7_four_chips", clock, report) as out:
        phase_four_chips(sz, data, report["1_fit"]["auc"], out)
    print(json.dumps({
        "compile_secs_total": round(
            sum(p.get("compile_secs", 0.0) for p in report.values()), 2
        ),
        "compile_cache_dir": cache_dir,
    }), flush=True)
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--dry-run-cpu", action="store_true",
        help="run the same phases at toy sizes on the CPU, Pallas kernels "
        "interpreted; for debugging this script where there is no chip",
    )
    args = parser.parse_args(argv)
    if args.dry_run_cpu:
        # must precede the first jax import; four host devices so the
        # four-chip phase runs too
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()

    import jax

    platform = jax.devices()[0].platform
    if args.dry_run_cpu:
        require(platform == "cpu", f"--dry-run-cpu got platform {platform!r}")
        device = run(DRY, dry_run=True)
        print(json.dumps(
            {"ok": True, "dry_run": True, "platform": "cpu", "device": device}
        ))
        return 0
    if platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found platform={platform!r} "
            f"({len(jax.devices())} x {jax.devices()[0].device_kind}); "
            "only --dry-run-cpu runs off-chip",
            file=sys.stderr,
        )
        return 2
    device = run(CHIP, dry_run=False)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
