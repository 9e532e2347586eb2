"""A/B: precomputed-U histogram pass vs the compare-built panel kernel.

Run ON the real chip, idle machine, one TPU process:

    python benchmarks/hist_u_ab.py [N] [F] [B] [K_NODES]

Measurement discipline: every timed op runs inside a jitted ``fori_loop`` whose input is perturbed per iteration
(or XLA hoists the loop-invariant call), synced by fetching a small slice.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.core.device import configure_compile_cache, on_tpu
from mmlspark_tpu.observability.profiler import get_profiler
from mmlspark_tpu.ops.histogram import build_histograms
from mmlspark_tpu.ops.u_histogram import (
    build_histograms_u,
    build_u,
    histogram_acc_dtype,
    make_u_spec,
    stat_rows,
    stat_rows_quant,
)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 409_600
F = int(sys.argv[2]) if len(sys.argv) > 2 else 28
B = int(sys.argv[3]) if len(sys.argv) > 3 else 256
KN = int(sys.argv[4]) if len(sys.argv) > 4 else 8
# 200, NOT 20: the closing sync fetch is a fixed cost per loop, and a short
# loop books it against every pass (the round-3 inflation documented in
# docs/perf_histogram.md)
REPS = int(sys.argv[5]) if len(sys.argv) > 5 else 200


def sync(x):
    return np.asarray(x.reshape(-1)[:4])


def timed(make_loop, *args, label=""):
    # the profiler wrap books the first (compiling) call as
    # ProfileCompiled with the program's cost_analysis FLOPs/bytes, the
    # warm call as ProfileExecuted — the BENCH JSON's profiler section
    loop = get_profiler().wrap(jax.jit(make_loop), name=label or "loop")
    sync(loop(*args))  # compile
    t0 = time.perf_counter()
    sync(loop(*args))
    dt = (time.perf_counter() - t0) / REPS * 1000
    print(f"{label:40s} {dt:8.2f} ms/pass")
    return dt


def main():
    configure_compile_cache()
    prof = get_profiler().enable()
    rng = np.random.default_rng(0)
    bins = rng.integers(0, B, size=(N, F)).astype(np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=N).astype(np.float32)
    c = np.ones(N, np.float32)
    node = rng.integers(0, KN, size=N).astype(np.int32)

    bins_d = jnp.asarray(bins)
    g_d, h_d, c_d = jnp.asarray(g), jnp.asarray(h), jnp.asarray(c)
    node_d = jnp.asarray(node)
    spec = make_u_spec(B, F)
    print(f"N={N} F={F} B={B} nodes={KN} K_pad={spec.k_pad} "
          f"U_int8={spec.k_pad * N / 1e9:.2f} GB backend={jax.default_backend()}")

    # --- baseline: compare-built panel kernel (the previous hot path)
    def loop_cmp(bins_, g_, h_, c_, node_):
        def body(i, acc):
            gi = g_ * (1 + i.astype(jnp.float32) * 1e-9)
            hist = build_histograms(bins_, gi, h_, c_, node_, KN, B, method="pallas")
            return acc + hist[0, 0, 0, 0]

        return lax.fori_loop(0, REPS, body, jnp.float32(0.0))

    t_cmp = timed(loop_cmp, bins_d, g_d, h_d, c_d, node_d,
                  label="compare-built panel kernel")

    # --- U build (once per fit) — ONE jitted callable, warm timing
    build8 = jax.jit(lambda b_: build_u(b_, spec, jnp.int8))
    u8 = build8(bins_d)
    sync(u8)
    t0 = time.perf_counter()
    u8 = build8(bins_d)
    sync(u8)
    print(f"{'U build (int8, warm)':40s} "
          f"{(time.perf_counter() - t0) * 1000:8.2f} ms once/fit")

    # --- U pass, per-pass stat build vs per-tree hoisted stat rows
    def loop_u(hoist_stats):
        def fn(u_, g_, h_, c_, node_):
            pre = stat_rows(g_, h_, c_) if hoist_stats else None

            def body(i, acc):
                gi = g_ * (1 + i.astype(jnp.float32) * 1e-9)
                hist = build_histograms_u(
                    u_, gi, h_, c_, node_ + (i % 2), KN, spec,
                    stats=pre,
                )
                return acc + hist[0, 0, 0, 0]

            return lax.fori_loop(0, REPS, body, jnp.float32(0.0))

        return fn

    t_u = timed(loop_u(False), u8, g_d, h_d, c_d, node_d,
                label="U pass (stats built per pass)")
    t_uh = timed(loop_u(True), u8, g_d, h_d, c_d, node_d,
                 label="U pass (stat rows hoisted per tree)")

    print(f"speedup vs compare-built: {t_cmp / min(t_u, t_uh):.2f}x")

    # --- sibling subtraction A/B: a split level has 2*KN children. Without
    # subtraction the pass panels all 2*KN; with it, only the KN smaller
    # children ride the matmul and siblings are a vector subtract from the
    # cached parent histograms (which the leaf batch already materialized).
    node2_d = jnp.asarray(rng.integers(0, 2 * KN, size=N).astype(np.int32))

    def loop_both(u_, g_, h_, c_, node_):
        pre = stat_rows(g_, h_, c_)

        def body(i, acc):
            gi = g_ * (1 + i.astype(jnp.float32) * 1e-9)
            hist = build_histograms_u(u_, gi, h_, c_, node_ + (i % 2),
                                      2 * KN, spec, stats=pre)
            return acc + hist[0, 0, 0, 0]

        return lax.fori_loop(0, REPS, body, jnp.float32(0.0))

    def loop_sub(u_, g_, h_, c_, node_, parent_):
        pre = stat_rows(g_, h_, c_)

        def body(i, acc):
            gi = g_ * (1 + i.astype(jnp.float32) * 1e-9)
            small = build_histograms_u(u_, gi, h_, c_, node_ + (i % 2), KN,
                                       spec, stats=pre)
            sibling = parent_ - small
            return acc + small[0, 0, 0, 0] + sibling[0, 0, 0, 0]

        return lax.fori_loop(0, REPS, body, jnp.float32(0.0))

    parent = build_histograms_u(u8, g_d, h_d, c_d, node_d, KN, spec)
    t_both = timed(loop_both, u8, g_d, h_d, c_d, node2_d,
                   label=f"split level, both children (2x{KN})")
    t_sub = timed(loop_sub, u8, g_d, h_d, c_d, node_d, parent,
                  label=f"split level, subtraction ({KN}+derive)")
    print(f"subtraction speedup per split level: {t_both / t_sub:.2f}x")

    # --- packed (quantized int) accumulators: dequant deferred, so the
    # pass writes/streams narrow ints instead of f32
    acc_dt = jnp.dtype(histogram_acc_dtype(N, True))
    qstats = stat_rows_quant(g_d, h_d, c_d, jax.random.PRNGKey(0))

    def loop_packed(u_, g_, h_, c_, node_):
        def body(i, acc):
            hist = build_histograms_u(u_, g_, h_, c_, node_ + (i % 2), KN,
                                      spec, stats=qstats, dequant=False)
            return acc + hist[0, 0, 0, 0].astype(jnp.int32)

        return lax.fori_loop(0, REPS, body, jnp.int32(0)).astype(jnp.float32)

    t_packed = timed(loop_packed, u8, g_d, h_d, c_d, node_d,
                     label=f"U pass (packed {acc_dt.name} accumulators)")

    # --- fused Pallas bin+scatter-add: reads RAW BINS once per pass (4F
    # B/row as i32 lanes) instead of re-streaming the K_pad-byte/row U.
    # The kernel never interprets on its own, so only time it on a chip.
    t_scatter = None
    if on_tpu():
        from mmlspark_tpu.ops.pallas_histogram import (
            bin_scatter_fits_vmem,
            build_histograms_bin_scatter,
        )

        if bin_scatter_fits_vmem(spec.k_pad, F):
            def loop_scatter(bins_, g_, h_, c_, node_):
                def body(i, acc):
                    hist = build_histograms_bin_scatter(
                        bins_, g_, h_, c_, node_ + (i % 2), KN, spec,
                        stats=qstats, dequant=False,
                    )
                    return acc + hist[0, 0, 0, 0].astype(jnp.int32)

                return lax.fori_loop(
                    0, REPS, body, jnp.int32(0)
                ).astype(jnp.float32)

            t_scatter = timed(loop_scatter, bins_d, g_d, h_d, c_d, node_d,
                              label="fused bin+scatter-add (Pallas)")
        else:
            print("fused bin+scatter-add: K_pad exceeds the VMEM tile budget")
    else:
        print("fused bin+scatter-add: skipped (not a TPU backend)")

    # Analytic roofline: bytes of ROW-SIZED input each pass must re-stream
    # from HBM (the traffic the U/EFB/subtraction work targets). Stats rows
    # ride along at 12 B/row f32 (3 B/row int8 on the quant path); the U
    # path re-reads the resident K_pad x N int8 one-hot, the raw-bins paths
    # re-read the (N, F) bins.
    bytes_per_row_restream = {
        "compare_built": F * bins_d.dtype.itemsize + 12,
        "u": spec.k_pad + 12,
        "u_hoisted": spec.k_pad + 12,
        "u_packed": spec.k_pad + 3,
        "bin_scatter": 4 * F + 32,
        # per split level (2*KN children resolved): both-children streams
        # rows twice vs once under subtraction
        "split_level_both": 2 * (spec.k_pad + 12),
        "split_level_subtraction": spec.k_pad + 12,
    }

    # ONE JSON line (the bench.py artifact convention): headline numbers
    # plus the profiler section. Each profiled program is a REPS-iteration
    # fori_loop, so per-iteration timing/FLOPs = the program totals / REPS.
    snap = prof.snapshot()
    per_iter = {
        name: {
            "compile_s": f["compile_seconds"],
            "exec_ms_per_iter": (
                f["device_seconds"] / max(f["executions"], 1) / REPS * 1e3
            ),
            "flops_per_iter": f["flops"] / REPS,
            "bytes_per_iter": f["bytes_accessed"] / REPS,
        }
        for name, f in snap["functions"].items()
    }
    ms = {
        "compare_built": t_cmp, "u": t_u, "u_hoisted": t_uh,
        "split_level_both": t_both, "split_level_subtraction": t_sub,
        "u_packed": t_packed,
    }
    if t_scatter is not None:
        ms["bin_scatter"] = t_scatter
    print(json.dumps({
        "bench": "hist_u_ab",
        "n": N, "f": F, "b": B, "nodes": KN, "reps": REPS,
        "ms_per_pass": ms,
        "speedup_vs_compare_built": t_cmp / min(t_u, t_uh),
        "subtraction": {
            "speedup_per_split_level": t_both / t_sub,
            "children_built_per_split": 1,
        },
        "packed": {
            "acc_dtype": acc_dt.name,
            "acc_bytes_vs_f32": acc_dt.itemsize / 4,
        },
        "bytes_per_row_restream": bytes_per_row_restream,
        "profiler": dict(snap, per_iteration=per_iter),
    }))


if __name__ == "__main__":
    main()
