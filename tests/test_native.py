"""Host C++ library (native/mmlspark_native.cpp) — bit-parity with the
numpy reference paths and graceful fallback when absent (SURVEY.md §2.20:
C++ where host-side)."""

import shutil

import numpy as np
import pytest

import mmlspark_tpu.native as native_mod
from mmlspark_tpu.native import (
    apply_bins_native,
    build,
    murmur3_bytes_native,
    murmur3_ints_native,
    murmur3_strings_native,
    native_available,
)


def _pack(tokens, encoding="utf-8"):
    bs = [t.encode(encoding) for t in tokens]
    lens = np.array([len(b) for b in bs], dtype=np.int64)
    starts = np.zeros(len(bs), dtype=np.int64)
    np.cumsum(lens[:-1], out=starts[1:])
    return np.frombuffer(b"".join(bs), dtype=np.uint8), starts, lens


#: Read while the file is collected, before ``built_library`` can build
#: anything: only ``conftest.pytest_configure`` can have made this true on a
#: fresh checkout.
_LOADED_AT_COLLECTION = native_mod.load_library() is not None


@pytest.fixture(scope="module", autouse=True)
def built_library():
    if not native_available():
        if shutil.which("make") is None or shutil.which("g++") is None:
            pytest.skip("no native toolchain in this environment")
        build()
    assert native_available()


def test_library_loaded_at_collection_time():
    """The property the ``native`` cases of ``test_hashing_batch.py`` rest on:
    with a toolchain, the library is there before any test file runs, whichever
    file a worker is handed first."""
    if native_mod.native_disabled():
        pytest.skip("MMLSPARK_TPU_NATIVE turns the library off")
    assert _LOADED_AT_COLLECTION


def _numpy_apply_bins(X, mapper):
    """The pure-numpy reference (native disabled)."""
    from mmlspark_tpu.lightgbm.binning import MISSING_BIN

    n, f = X.shape
    out = np.zeros((n, f), dtype=np.uint8)
    for j in range(f):
        col = X[:, j].astype(np.float32)
        nan_mask = np.isnan(col)
        b = 1 + np.searchsorted(mapper.edges[j].astype(np.float32), col, side="left")
        b = np.where(nan_mask, MISSING_BIN, b)
        out[:, j] = np.clip(b, 0, mapper.max_bin).astype(np.uint8)
    return out


class TestBinningParity:
    @pytest.mark.parametrize("max_bin", [255, 31])
    def test_bit_identical_to_numpy(self, max_bin):
        from mmlspark_tpu.lightgbm.binning import fit_bin_mapper

        rng = np.random.default_rng(0)
        X = rng.normal(size=(2000, 9))
        X[::13, 4] = np.nan
        X[:, 8] = rng.choice([0.0, 1.0, 2.0], size=2000)  # low cardinality
        mapper = fit_bin_mapper(X, max_bin=max_bin)
        ours = apply_bins_native(X, mapper.edges, mapper.max_bin)
        np.testing.assert_array_equal(ours, _numpy_apply_bins(X, mapper))

    def test_boundary_values_route_identically(self):
        """Values exactly on an edge must take the same bin in both paths
        (the float32-grid contract that keeps train/predict/SHAP aligned)."""
        from mmlspark_tpu.lightgbm.binning import fit_bin_mapper

        rng = np.random.default_rng(1)
        base = rng.normal(size=(500, 3))
        mapper = fit_bin_mapper(base, max_bin=63)
        # probe exactly at the edges
        probes = np.stack(
            [mapper.edges[j][np.isfinite(mapper.edges[j])][:40] for j in range(3)],
            axis=1,
        )
        ours = apply_bins_native(probes, mapper.edges, mapper.max_bin)
        np.testing.assert_array_equal(ours, _numpy_apply_bins(probes, mapper))

    def test_apply_bins_dispatches_to_native(self):
        from mmlspark_tpu.lightgbm.binning import bin_dataset

        rng = np.random.default_rng(2)
        X = rng.normal(size=(300, 5))
        bins, mapper = bin_dataset(X, max_bin=63)
        np.testing.assert_array_equal(bins, _numpy_apply_bins(X, mapper))


class TestMurmurParity:
    """Compare the C++ implementations against the PURE-python reference —
    ops.hashing dispatches to native itself, so the reference side is
    computed with the library disabled."""

    def test_bytes_matches_python(self, monkeypatch):
        from mmlspark_tpu.ops.hashing import murmur32_bytes

        cases = [
            (data, seed)
            for data in (b"", b"a", b"ab", b"abc", b"abcd", b"hello tpu world", bytes(range(37)))
            for seed in (0, 1, 0xDEADBEEF)
        ]
        native_vals = [murmur3_bytes_native(d, s) for d, s in cases]
        assert all(v is not None for v in native_vals)
        with monkeypatch.context() as m:
            m.setattr(native_mod, "_LIB", None)
            m.setattr(native_mod, "_LOAD_ATTEMPTED", True)
            pure = [murmur32_bytes(d, s) for d, s in cases]
        assert native_vals == pure

    def test_ints_match_python(self, monkeypatch):
        from mmlspark_tpu.ops.hashing import murmur32_ints

        rng = np.random.default_rng(3)
        vals = rng.integers(0, 2**32, size=1000, dtype=np.uint32)
        native_vals = murmur3_ints_native(vals, seed=7)
        with monkeypatch.context() as m:
            m.setattr(native_mod, "_LIB", None)
            m.setattr(native_mod, "_LOAD_ATTEMPTED", True)
            pure = murmur32_ints(vals, seed=7)
        np.testing.assert_array_equal(native_vals, pure)


class TestMurmurStringsParity:
    """The array-of-strings entry (one call per featurizer column) must agree
    byte-for-byte with the scalar bytes hash — prefixes of every alignment,
    1-3 byte tails, empty strings, multi-byte codepoints."""

    TOKENS = [
        "", "a", "ab", "abc", "abcd", "abcde", "héllo", "wörld", "漢字", "™",
        "χρώμα", "x" * 37, "the quick brown fox", "𝔘𝔫𝔦𝔠𝔬𝔡𝔢",
    ]

    @pytest.mark.parametrize("prefix", [b"", b"c", b"ns!", b"text", b"abcdefgh"])
    @pytest.mark.parametrize("seed", [0, 7, 0xDEADBEEF])
    def test_matches_scalar_bytes_hash(self, prefix, seed):
        buf, starts, lens = _pack(self.TOKENS)
        got = murmur3_strings_native(buf, starts, lens, seed, prefix)
        assert got is not None
        want = [
            murmur3_bytes_native(prefix + t.encode("utf-8"), seed)
            for t in self.TOKENS
        ]
        np.testing.assert_array_equal(got, np.array(want, dtype=np.uint32))

    def test_random_strings_match_numpy_fallback(self, monkeypatch):
        from mmlspark_tpu.ops.hashing import murmur32_bytes_batch

        rng = np.random.default_rng(11)
        alphabet = list("abc 01\t\n") + ["é", "漢", "™", "𝔘", " ", " "]
        tokens = [
            "".join(rng.choice(alphabet, size=rng.integers(0, 12)))
            for _ in range(300)
        ]
        buf, starts, lens = _pack(tokens)
        native_vals = murmur32_bytes_batch(buf, starts, lens, 5, b"pfx")
        with monkeypatch.context() as m:
            m.setattr(native_mod, "_LIB", None)
            m.setattr(native_mod, "_LOAD_ATTEMPTED", True)
            pure = murmur32_bytes_batch(buf, starts, lens, 5, b"pfx")
        np.testing.assert_array_equal(native_vals, pure)


class TestFallback:
    def test_absent_library_returns_none(self, monkeypatch):
        monkeypatch.setattr(native_mod, "_LIB", None)
        monkeypatch.setattr(native_mod, "_LOAD_ATTEMPTED", True)
        assert native_mod.apply_bins_native(np.zeros((2, 2)), np.zeros((2, 1)), 3) is None
        assert native_mod.murmur3_bytes_native(b"x") is None
        # binning still works through the numpy path
        from mmlspark_tpu.lightgbm.binning import bin_dataset

        bins, _ = bin_dataset(np.random.default_rng(0).normal(size=(50, 3)), max_bin=15)
        assert bins.dtype == np.uint8
