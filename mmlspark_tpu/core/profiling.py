"""Tracing/profiling utilities (SURVEY.md §5 "tracing/profiling").

The reference's point solutions (``Timer`` stage wall-times, VW per-phase
StopWatch stats) exist in their packages; this module adds the
device-level layer the TPU build owes: ``jax.profiler`` wiring so any
pipeline region can be captured as an xprof/TensorBoard trace, plus the
named-region annotation that shows stage boundaries inside the trace.

    from mmlspark_tpu.core.profiling import profile_trace, annotate, StopWatch

    with profile_trace("/tmp/xprof"):          # full device trace
        with annotate("gbdt-fit"):             # named region in the trace
            model = clf.fit(table)

    sw = StopWatch()
    with sw.measure("binning"):
        ...
    sw.summary()  # {"binning": seconds}
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Dict, Iterator, Optional


def get_logger(name: str = "mmlspark_tpu") -> logging.Logger:
    """Framework logger (the slf4j analogue): a namespaced logger with one
    stderr handler installed on first use; level via MMLSPARK_TPU_LOGLEVEL."""
    import os

    logger = logging.getLogger(name)
    root = logging.getLogger("mmlspark_tpu")
    if not root.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(name)s %(levelname)s: %(message)s")
        )
        root.addHandler(handler)
        root.setLevel(os.environ.get("MMLSPARK_TPU_LOGLEVEL", "WARNING").upper())
        # propagate stays True: log-capture tooling (pytest caplog) hooks the
        # python root; an app that also configures root logging may see the
        # line twice, which is the lesser evil
    return logger


@contextlib.contextmanager
def profile_trace(
    log_dir: str, host_tracer_level: Optional[int] = None
) -> Iterator[float]:
    """Capture a jax.profiler (xprof) device trace into ``log_dir`` for
    TensorBoard's profile plugin. Yields the session's ``t0``: the
    ``time.monotonic()`` instant the trace's own clock reads zero at, which
    ``Tracer.trace_events(t0)`` rebases the program's spans onto.

    ``host_tracer_level`` is the runtime's (default: JAX's own, 2). At 0 the
    trace holds no host events at all — the level for a job that moves
    gigabytes, where the runtime's host threads would log every transfer
    chunk — and the spans from ``trace_events`` are then the only names the
    host side has."""
    import jax

    options = jax.profiler.ProfileOptions()
    if host_tracer_level is not None:
        options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    t0 = time.monotonic()
    try:
        yield t0
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named region inside an active device trace (StepTraceAnnotation's
    host-side sibling); no-op overhead when no trace is running."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class StopWatch:
    """Accumulating named phase timer — the reference's ``StopWatch``
    (``core/utils/StopWatch.scala``) / VW per-phase diagnostics pattern."""

    def __init__(self) -> None:
        self._totals: Dict[str, float] = {}

    @contextlib.contextmanager
    def measure(self, phase: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(phase, time.perf_counter() - t0)

    def add(self, phase: str, seconds: float) -> None:
        """Fold an externally-timed duration into ``phase`` — the public
        form of what :meth:`measure` records, for callers that already
        hold a measured interval (e.g. the scheduler's queue-wait/run
        times, which are timestamp differences across threads)."""
        self._totals[phase] = self._totals.get(phase, 0.0) + seconds

    def summary(self) -> Dict[str, float]:
        return dict(self._totals)

    def log(self, logger: Optional[logging.Logger] = None, prefix: str = "") -> None:
        logger = logger or get_logger()
        total = sum(self._totals.values()) or 1.0
        for phase, secs in sorted(self._totals.items(), key=lambda kv: -kv[1]):
            logger.info("%s%s: %.3fs (%.0f%%)", prefix, phase, secs, 100 * secs / total)
