"""What this process can observe about its accelerator, and where its
compiled programs are kept, within a run and between runs.

Three decisions every device code path shares live here so they are spelled
once: whether the default device is a TPU (kernel and histogram-path
selection), which directory holds JAX's persistent compilation cache
(process entry points call :func:`configure_compile_cache`; importing the
package never does), and which jitted programs this process has already
built (:func:`cached_program`, the one program cache of the package).
"""

from __future__ import annotations

import collections
import os
import threading
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np


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


# Jitted programs shared across fit and transform calls. JAX keys its trace
# cache and its fast dispatch path on the function object, so a caller that
# builds its closures and its ``jax.jit`` wrapper anew re-traces and lowers
# the whole program and reloads an executable it already holds: seconds of
# host work in front of a warm fit, 0.4-0.7 s in front of every deep-path
# ``transform`` (PERF.md, PR 30). A key is everything the traced function
# can see besides its arguments (options, bin count, mesh; an ``applyFn``
# object; a stage list by content) and never an input or a weight: jit
# re-specialises per argument shape underneath a cached callable, and what
# a key or a cached closure holds stays alive until it is evicted.
# LRU-bounded so hyperparameter sweeps (every combination is a distinct key)
# don't grow compiled executables without limit; 256 entries ~ 64 fit
# configurations in flight, far beyond a CV fold x param-grid working set.
_PROGRAM_CACHE: "collections.OrderedDict[Any, Any]" = collections.OrderedDict()
_PROGRAM_CACHE_SIZE = 256
# re-entrant: a ``make`` may look up a program of its own
_PROGRAM_CACHE_LOCK = threading.RLock()
_BUILT = threading.local()


def cached_program(key: Any, make: Callable[[], Any]) -> Any:
    """What ``make()`` returned the first time this process saw ``key``:
    built under the lock, so two threads that ask at once build one."""
    with _PROGRAM_CACHE_LOCK:
        program = _PROGRAM_CACHE.get(key)
        hit = program is not None
        if hit:
            _PROGRAM_CACHE.move_to_end(key)
        else:
            program = _PROGRAM_CACHE[key] = make()
            _BUILT.count = programs_built() + 1
            if len(_PROGRAM_CACHE) > _PROGRAM_CACHE_SIZE:
                _PROGRAM_CACHE.popitem(last=False)
        size = len(_PROGRAM_CACHE)
    from mmlspark_tpu.observability.profiler import get_profiler

    prof = get_profiler()
    if prof.active:
        prof.note_program_cache(hit=hit, size=size)
    return program


def programs_built() -> int:
    """How many :func:`cached_program` look-ups of the calling thread have
    missed so far. A caller reads it before and after its look-ups: the
    difference is what that call had to build (a span's ``programs_built``,
    ``lightgbm.program``'s ``cache_hit``)."""
    return getattr(_BUILT, "count", 0)


def frozen(value: Any) -> Any:
    """``value`` by content, hashable: the part of a key that a dict, a list
    or an array gives (a fresh ``dict`` of equal content is the same key).
    A leaf that cannot be hashed stands in as its ``repr``."""
    if isinstance(value, dict):
        return tuple(sorted((k, frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(frozen(v) for v in value)
    if isinstance(value, (np.ndarray, jax.Array)):
        value = np.asarray(value)  # by content: a repr leaves elements out
        return (value.shape, value.dtype.str, value.tobytes())
    try:
        hash(value)
        return value
    except TypeError:
        return repr(value)
