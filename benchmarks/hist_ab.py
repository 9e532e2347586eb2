"""A/B benchmark: histogram formulations on the real TPU chip.

Measures the GBDT hot op (``ops/histogram.py`` vs ``ops/pallas_histogram.py``)
at realistic training shapes and prints per-method wall time plus the
bandwidth roofline. Results are recorded in ``docs/perf_histogram.md``.

Run: ``python benchmarks/hist_ab.py`` (single real chip).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.ops.histogram import build_histograms

SHAPES = [
    # (rows, features, nodes, bins)  — leafwise child pass / depthwise levels
    (1 << 20, 28, 1, 256),   # leafwise + subtraction: one B-wide child pass
    (1 << 20, 28, 2, 256),   # two-child pass (voting-parallel path)
    (1 << 20, 28, 8, 256),   # depthwise level 3
    (1 << 18, 128, 1, 256),  # wide features
    (1 << 22, 28, 1, 64),    # 4M rows, small bins
]


def bench(method, bins, g, h, c, node, nodes, b, iters=20):
    """One jitted on-device fori_loop over `iters` histogram builds — a
    single dispatch, so per-call dispatch latency amortizes away. The
    gradient is perturbed per iteration to defeat loop-invariant hoisting,
    and a scalar chained out forces execution."""
    from jax import lax as _lax

    @jax.jit
    def loop(bins_, g_, h_, c_, node_):
        def body(i, acc):
            gi = g_ * (1.0 + i.astype(jnp.float32) * 1e-9)
            out = build_histograms(bins_, gi, h_, c_, node_, nodes, b, method=method)
            return acc + out[0, 0, 0, 0]

        return _lax.fori_loop(0, iters, body, jnp.float32(0.0))

    float(loop(bins, g, h, c, node))  # warm / compile
    t0 = time.perf_counter()
    float(loop(bins, g, h, c, node))
    return (time.perf_counter() - t0) / iters


def main():
    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()
    print(f"backend: {jax.default_backend()}, device: {jax.devices()[0]}")
    for n, f, nodes, b in SHAPES:
        rng = np.random.default_rng(0)
        bins = jnp.asarray(rng.integers(0, b, size=(n, f)), dtype=jnp.int32)
        g = jnp.asarray(rng.normal(size=n), dtype=jnp.float32)
        h = jnp.asarray(rng.random(n), dtype=jnp.float32)
        c = jnp.ones(n, dtype=jnp.float32)
        node = jnp.asarray(rng.integers(0, nodes, size=n), dtype=jnp.int32)

        # bandwidth floor: ids int32 read + data 12B/row/feature-pass
        ids_bytes = 4 * n * f
        out_bytes = 4 * f * nodes * b * 3
        floor_bytes = ids_bytes + 12 * n + out_bytes

        row = f"N={n:>8} F={f:>4} nodes={nodes} B={b}: "
        results = {}
        for method in ("onehot", "pallas", "segment"):
            try:
                dt = bench(method, bins, g, h, c, node, nodes, b)
                gbps = floor_bytes / dt / 1e9
                results[method] = dt
                row += f"{method}={dt*1e3:7.2f}ms ({gbps:6.1f} GB/s eff)  "
            except Exception as e:
                row += f"{method}=FAIL({type(e).__name__})  "
        if "onehot" in results and "pallas" in results:
            row += f"speedup={results['onehot']/results['pallas']:.2f}x"
        print(row)


if __name__ == "__main__":
    main()
