"""Pallas histogram kernels — interpret-mode correctness on the CPU mesh,
``interpret=True`` passed explicitly (the kernels never interpret on their
own). ``chip_smoke.py`` compiles each of them on the chip."""

import numpy as np
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops.histogram import build_histograms
from mmlspark_tpu.ops.pallas_histogram import (
    build_histograms_pallas,
    panel_fits,
    pick_bw,
)


def _case(n, f, nodes, b, seed=0):
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, b, size=(n, f)), dtype=jnp.int32)
    g = jnp.asarray(rng.normal(size=n), dtype=jnp.float32)
    h = jnp.asarray(rng.random(n), dtype=jnp.float32)
    c = jnp.asarray((rng.random(n) < 0.8), dtype=jnp.float32)
    node = jnp.asarray(rng.integers(0, nodes, size=n), dtype=jnp.int32)
    return bins, g, h, c, node


@pytest.mark.parametrize("n,f,nodes,b", [(3000, 5, 2, 33), (1024, 3, 4, 17)])
def test_pallas_matches_segment(n, f, nodes, b):
    bins, g, h, c, node = _case(n, f, nodes, b)
    ref = build_histograms(bins, g, h, c, node, nodes, b, method="segment")
    pal = build_histograms_pallas(
        bins, g, h, c, node, nodes, b, interpret=True
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), rtol=1e-5, atol=1e-5)


def test_pallas_pads_ragged_rows():
    # N not a multiple of the row block: padding rows must contribute nothing.
    bins, g, h, c, node = _case(2500, 2, 2, 9)
    ref = build_histograms(bins, g, h, c, node, 2, 9, method="segment")
    pal = build_histograms_pallas(bins, g, h, c, node, 2, 9, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), rtol=1e-5, atol=1e-5)


def test_pick_bw_budget():
    assert pick_bw(512) >= 128  # leafwise hot shape fits
    assert pick_bw(100_000) == 0  # absurd K refuses


def test_method_dispatch_falls_back():
    # Too many nodes for the panel kernel's lane group and K too large for
    # the combined-id kernel's VMEM budget: method="pallas" degrades to the
    # XLA one-hot rather than erroring (no kernel runs, so no chip needed).
    bins, g, h, c, node = _case(512, 2, 64, 256)  # K = 16384
    assert not panel_fits(64, 256) and pick_bw(64 * 256) == 0
    out = build_histograms(bins, g, h, c, node, 64, 256, method="pallas")
    ref = build_histograms(bins, g, h, c, node, 64, 256, method="segment")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_pallas_oob_value_error():
    bins, g, h, c, node = _case(512, 2, 2, 9)
    with pytest.raises(ValueError, match="VMEM budget"):
        build_histograms_pallas(bins, g, h, c, node, 2, 9, bw=0)


class TestBinScatter:
    """Fused bin+scatter-add kernel: reads raw binned rows once and
    scatters into narrow VMEM accumulators — vs the resident-U MXU path,
    which re-streams K_pad bytes/row. Interpret-mode parity against
    ``build_histograms_u`` (f32 to rounding, quant bit-exact)."""

    def _u_case(self, seed=0, n=700, k=4):
        from mmlspark_tpu.ops.u_histogram import build_u, make_u_spec

        rng = np.random.default_rng(seed)
        widths = [16, 3, 9, 16, 7]
        f, b = len(widths), 16
        bins = np.stack(
            [rng.integers(0, w, size=n) for w in widths], axis=1
        ).astype(np.int32)
        g = rng.normal(size=n).astype(np.float32)
        h = rng.uniform(0.1, 1, size=n).astype(np.float32)
        c = (rng.uniform(size=n) > 0.2).astype(np.float32)
        node = rng.integers(-1, k + 2, size=n).astype(np.int32)
        spec = make_u_spec(b, f, per_feature=widths)
        u = build_u(jnp.asarray(bins), spec)
        return bins, g, h, c, node, k, spec, u

    def test_f32_matches_u_builder(self):
        from mmlspark_tpu.ops.pallas_histogram import (
            build_histograms_bin_scatter,
        )
        from mmlspark_tpu.ops.u_histogram import build_histograms_u

        bins, g, h, c, node, k, spec, u = self._u_case()
        ref = np.asarray(build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec,
        ))
        out = np.asarray(build_histograms_bin_scatter(
            jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(c), jnp.asarray(node), k, spec, interpret=True,
        ))
        np.testing.assert_array_equal(out[..., 2], ref[..., 2])
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("dequant", [True, False])
    def test_quant_bit_exact(self, dequant):
        import jax

        from mmlspark_tpu.ops.pallas_histogram import (
            build_histograms_bin_scatter,
        )
        from mmlspark_tpu.ops.u_histogram import (
            build_histograms_u,
            stat_rows_quant,
        )

        bins, g, h, c, node, k, spec, u = self._u_case(seed=3)
        stats = stat_rows_quant(
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jax.random.PRNGKey(2),
        )
        ref = np.asarray(build_histograms_u(
            u, jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
            jnp.asarray(node), k, spec, stats=stats, dequant=dequant,
        ))
        out = np.asarray(build_histograms_bin_scatter(
            jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(c), jnp.asarray(node), k, spec, stats=stats,
            dequant=dequant, interpret=True,
        ))
        np.testing.assert_array_equal(out, ref)  # integer path: bit-exact

    def test_panel_width_guard(self):
        from mmlspark_tpu.ops.pallas_histogram import (
            build_histograms_bin_scatter,
        )

        bins, g, h, c, node, _, spec, _ = self._u_case()
        with pytest.raises(ValueError, match="lane group"):
            build_histograms_bin_scatter(
                jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                jnp.asarray(c), jnp.asarray(node), 64, spec, interpret=True,
            )

    def test_vmem_gate(self):
        from mmlspark_tpu.ops.pallas_histogram import bin_scatter_fits_vmem

        assert bin_scatter_fits_vmem(7168, 28)  # 255-bin headline shape
        assert not bin_scatter_fits_vmem(60_000, 28)  # absurd K refuses
