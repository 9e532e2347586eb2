"""What this process can observe about its accelerator, and where its
compiled programs are kept between runs.

Two decisions every device code path shares live here so they are spelled
once: whether the default device is a TPU (kernel and histogram-path
selection), and which directory holds JAX's persistent compilation cache
(process entry points call :func:`configure_compile_cache`; importing the
package never does).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax


def on_tpu() -> bool:
    """True exactly when the default device is a TPU. Off-chip callers get
    the XLA formulations; Pallas kernels are never picked (nor silently
    interpreted) there."""
    return jax.devices()[0].platform == "tpu"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. With ``JAX_COMPILATION_CACHE_DIR`` set nothing is touched;
    otherwise the cache lives in ``<checkout>/.jax_cache``, derived from the
    package location — the path is part of the cache key, so it must not
    move between runs."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed  # JAX reads the variable itself
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
