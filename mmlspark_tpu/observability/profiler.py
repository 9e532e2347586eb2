"""Device-performance profiler — the task-metrics/SQL-metrics half of the
observability plane (SURVEY.md §5).

Spark's UI attributes every stage to compile/deserialize/run/GC time and
every SQL operator to rows/bytes/time; nothing here could say the same
about a jitted hot path (ROADMAP items 2-4: the flat bench trajectory has
no compile-time accounting, no HBM/roofline attribution, no
registry-derived SLO for serving). :class:`DeviceProfiler` closes that by
wrapping the jitted callables the framework dispatches:

- **compile accounting**: an unseen input signature (an executable-cache
  miss, read from the jit cache itself when the function exposes it)
  books a :class:`~mmlspark_tpu.observability.events.ProfileCompiled`
  event with the compiling call's wall time;
- **device timing**: every call runs in a ``block_until_ready`` window
  and books :class:`~mmlspark_tpu.observability.events.ProfileExecuted`
  plus a ``profiler_device_seconds{fn=...}`` histogram observation;
- **roofline attribution**: XLA ``cost_analysis()`` FLOPs / bytes for
  the compiled program fold into achieved FLOP/s and bytes/s against the
  device's peak MXU / HBM numbers (``docs/perf_histogram.md`` uses the
  same v5e peaks), labelling each hot path compute- or memory-bound;
- **HBM gauges**: :meth:`sample_memory` reads ``Device.memory_stats()``
  into ``profiler_hbm_bytes_in_use``/``_limit`` gauges (absent on
  backends that do not report, e.g. CPU — sampling is always safe);
- **transfer counters**: :meth:`note_transfer` accumulates host<->device
  bytes into ``profiler_transfer_bytes_total{direction=...}``.

The process-global profiler (:func:`get_profiler`) is DISABLED by
default: wrapped call sites fall through with one attribute read, so the
serving hot path and the fit loop pay nothing until someone sets
``MMLSPARK_TPU_PROFILE=1`` or calls ``get_profiler().enable()`` (the
bench drivers and the perf-report CI smoke do).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

from mmlspark_tpu.core.profiling import get_logger
from mmlspark_tpu.observability.events import (
    ProfileCompiled,
    ProfileExecuted,
    get_bus,
)
from mmlspark_tpu.observability.registry import (
    FIT_BUCKETS,
    MetricsRegistry,
    get_registry,
)

logger = get_logger("mmlspark_tpu.observability")

#: device_kind substring (lowercased) -> (peak FLOP/s, peak HBM bytes/s).
#: v5e numbers are the bf16 MXU peak and the HBM bandwidth the round-4
#: roofline case in docs/perf_histogram.md is argued against (670 GB/s
#: measured = 83% of peak). Unknown backends report (0, 0) with the
#: ``unknown-platform`` sentinel and roofline fractions stay None.
_DEVICE_PEAKS: Tuple[Tuple[str, Tuple[float, float]], ...] = (
    ("v5 lite", (1.97e14, 8.1e11)),
    ("v5e", (1.97e14, 8.1e11)),
    ("v5p", (4.59e14, 2.765e12)),
    ("v4", (2.75e14, 1.2e12)),
    ("v3", (1.23e14, 9.0e11)),
)

#: the platform label when no peak-table row (and no env override)
#: matched — CI CPU rigs land here. Bound classification is SKIPPED for
#: this sentinel: labelling a host CPU "compute-bound" against a TPU
#: machine-balance ridge is provenance-free noise (ISSUE 18 satellite).
UNKNOWN_PLATFORM = "unknown-platform"


class DevicePeaks(tuple):
    """``(peak FLOP/s, peak HBM bytes/s)`` that still unpacks like the
    bare 2-tuple it replaces, plus the ``platform`` label the peaks came
    from (``v5e``, ``env-override``, or :data:`UNKNOWN_PLATFORM`)."""

    def __new__(
        cls, peak_flops: float, peak_bw: float, platform: str
    ) -> "DevicePeaks":
        self = super().__new__(cls, (float(peak_flops), float(peak_bw)))
        self.platform = str(platform)
        return self

    @property
    def known(self) -> bool:
        return self.platform != UNKNOWN_PLATFORM


def device_peaks(device=None) -> DevicePeaks:
    """:class:`DevicePeaks` for ``device`` (default: the first jax
    device), overridable via ``MMLSPARK_TPU_PEAK_FLOPS`` /
    ``MMLSPARK_TPU_PEAK_HBM_BYTES`` for rigs the table doesn't know.
    A rig with no table row and no override gets ``(0, 0)`` labelled
    :data:`UNKNOWN_PLATFORM`, never a silently-zero TPU claim."""
    env_f = os.environ.get("MMLSPARK_TPU_PEAK_FLOPS")
    env_b = os.environ.get("MMLSPARK_TPU_PEAK_HBM_BYTES")
    if env_f or env_b:
        return DevicePeaks(
            float(env_f or 0.0), float(env_b or 0.0), "env-override"
        )
    if device is None:
        try:
            import jax

            device = jax.devices()[0]
        except Exception:  # noqa: BLE001 - no backend is a valid state
            return DevicePeaks(0.0, 0.0, UNKNOWN_PLATFORM)
    kind = str(getattr(device, "device_kind", "")).lower()
    for needle, peaks in _DEVICE_PEAKS:
        if needle in kind:
            return DevicePeaks(peaks[0], peaks[1], needle)
    return DevicePeaks(0.0, 0.0, UNKNOWN_PLATFORM)


@dataclasses.dataclass
class FunctionProfile:
    """Accumulated per-function profile (one row of the roofline table)."""

    name: str
    compiles: int = 0
    compile_seconds: float = 0.0
    cache_hits: int = 0
    executions: int = 0
    device_seconds: float = 0.0
    #: cost_analysis estimates for ONE execution of the compiled program
    flops: float = 0.0
    bytes_accessed: float = 0.0
    transfer_bytes: float = 0.0

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def roofline(
        self,
        peak_flops: float = 0.0,
        peak_bw: float = 0.0,
        platform: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Achieved vs peak attribution for this function: FLOP/s and
        bytes/s over the mean execution window, the fraction of the MXU
        and HBM peaks they represent, and which wall the program leans
        on (``bound``). On an :data:`UNKNOWN_PLATFORM` rig the bound
        stays ``"unknown"`` — the intensity fallback argues against a
        TPU machine balance no unknown rig is known to have."""
        row: Dict[str, Any] = {
            "name": self.name,
            "executions": self.executions,
            "mean_ms": (
                self.device_seconds / self.executions * 1e3
                if self.executions else 0.0
            ),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "achieved_flops_per_s": 0.0,
            "achieved_bytes_per_s": 0.0,
            "mxu_frac": None,
            "hbm_frac": None,
            "bound": "unknown",
        }
        if platform is not None:
            row["platform"] = platform
        if self.executions and self.device_seconds > 0:
            mean = self.device_seconds / self.executions
            row["achieved_flops_per_s"] = self.flops / mean
            row["achieved_bytes_per_s"] = self.bytes_accessed / mean
        if peak_flops > 0 and row["achieved_flops_per_s"]:
            row["mxu_frac"] = row["achieved_flops_per_s"] / peak_flops
        if peak_bw > 0 and row["achieved_bytes_per_s"]:
            row["hbm_frac"] = row["achieved_bytes_per_s"] / peak_bw
        if row["mxu_frac"] is not None and row["hbm_frac"] is not None:
            row["bound"] = (
                "memory" if row["hbm_frac"] >= row["mxu_frac"] else "compute"
            )
        elif platform != UNKNOWN_PLATFORM and (
            self.flops or self.bytes_accessed
        ):
            # no peak table but a KNOWN platform: still label by arithmetic
            # intensity against the classic ~10 FLOPs/byte machine-balance
            # ridge (division guarded — zero bytes_accessed clamps to 1)
            intensity = self.flops / max(self.bytes_accessed, 1.0)
            row["bound"] = "compute" if intensity > 10.0 else "memory"
        return row


def _signature(args, kwargs) -> str:
    """Shape/dtype signature of a call, mirroring what the jit cache
    keys on closely enough to detect retraces."""
    parts: List[str] = []
    for a in list(args) + sorted(kwargs.items()):
        if isinstance(a, tuple):
            a = a[1]
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None:
            parts.append(f"{dtype}{tuple(shape)}")
        else:
            parts.append(type(a).__name__)
    return ",".join(parts)


def _jit_cache_size(fn) -> Optional[int]:
    """The jitted function's in-process executable-cache size, when the
    jax version exposes it (the authoritative hit/miss signal)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:  # noqa: BLE001 - introspection only, never fatal
        return None


class DeviceProfiler:
    """Wraps jitted hot paths with compile/execute/roofline accounting.

    Pass an isolated ``registry``/``bus`` for tests; the process-global
    instance (:func:`get_profiler`) feeds the shared metrics plane and
    event bus. ``enabled=False`` makes every entry point a cheap no-op
    and :meth:`wrap` the identity."""

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        bus=None,
        enabled: bool = True,
        cost_analysis: bool = True,
    ):
        self.registry = registry if registry is not None else get_registry()
        self._bus = bus
        self.enabled = bool(enabled)
        self.cost_analysis = bool(cost_analysis)
        self._lock = threading.Lock()
        self._profiles: Dict[str, FunctionProfile] = {}
        reg = self.registry
        self._reg_compiles = reg.counter(
            "profiler_compiles_total",
            "Executable compiles observed by the device profiler",
        )
        self._reg_cache_hits = reg.counter(
            "profiler_cache_hits_total",
            "Profiled calls answered from a warm executable cache",
        )
        self._reg_compile_s = reg.histogram(
            "profiler_compile_seconds",
            "Wall time of compiling calls (trace + XLA compile + first run)",
            buckets=FIT_BUCKETS,
        )
        self._reg_device_s = reg.histogram(
            "profiler_device_seconds",
            "Per-call device window (dispatch through block_until_ready)",
        )
        self._reg_transfer = reg.counter(
            "profiler_transfer_bytes_total",
            "Host<->device bytes moved through profiled call sites",
        )

    # -- plumbing ------------------------------------------------------------

    @property
    def bus(self):
        return self._bus if self._bus is not None else get_bus()

    @property
    def active(self) -> bool:
        return self.enabled

    def enable(self) -> "DeviceProfiler":
        self.enabled = True
        return self

    def disable(self) -> "DeviceProfiler":
        self.enabled = False
        return self

    def _profile(self, name: str) -> FunctionProfile:
        with self._lock:
            prof = self._profiles.get(name)
            if prof is None:
                prof = self._profiles[name] = FunctionProfile(name)
            return prof

    # -- recording -----------------------------------------------------------

    def note_compile(
        self,
        name: str,
        seconds: float,
        flops: float = 0.0,
        bytes_accessed: float = 0.0,
        signature: str = "",
    ) -> None:
        prof = self._profile(name)
        with self._lock:
            prof.compiles += 1
            prof.compile_seconds += seconds
            if flops:
                prof.flops = flops
            if bytes_accessed:
                prof.bytes_accessed = bytes_accessed
        self._reg_compiles.labels(fn=name).inc()
        self._reg_compile_s.observe(seconds)
        bus = self.bus
        if bus.active:
            bus.publish(ProfileCompiled(
                name=name, seconds=seconds, flops=flops,
                bytes_accessed=bytes_accessed, signature=signature,
            ))

    def note_execute(self, name: str, seconds: float) -> None:
        prof = self._profile(name)
        with self._lock:
            prof.executions += 1
            prof.device_seconds += seconds
        self._reg_device_s.labels(fn=name).observe(seconds)
        bus = self.bus
        if bus.active:
            bus.publish(ProfileExecuted(name=name, seconds=seconds))

    def note_cache_hit(self, name: str) -> None:
        prof = self._profile(name)
        with self._lock:
            prof.cache_hits += 1
        self._reg_cache_hits.labels(fn=name).inc()

    def note_transfer(
        self, nbytes: float, direction: str = "h2d", name: str = ""
    ) -> None:
        """Book host->device (``h2d``) or device->host (``d2h``) bytes."""
        if nbytes <= 0:
            return
        self._reg_transfer.labels(direction=direction).inc(float(nbytes))
        if name:
            prof = self._profile(name)
            with self._lock:
                prof.transfer_bytes += float(nbytes)

    def merge(
        self,
        name: str,
        executions: int = 0,
        device_seconds: float = 0.0,
        compiles: int = 0,
        compile_seconds: float = 0.0,
    ) -> None:
        """Fold externally measured totals into the profile table — the
        per-member fold for process-spanning fits, where each worker
        times its own collectives and the driver merges the summaries.
        Counters update; histograms don't (the per-call distribution
        never crossed the process boundary). Only the profile table (and
        so roofline/snapshot) updates — histograms and hit counters stay
        the driver's own observations."""
        prof = self._profile(name)
        with self._lock:
            prof.executions += int(executions)
            prof.device_seconds += float(device_seconds)
            prof.compiles += int(compiles)
            prof.compile_seconds += float(compile_seconds)
        if compiles:
            self._reg_compiles.labels(fn=name).inc(int(compiles))

    def note_program_cache(self, hit: bool, size: int) -> None:
        """Accounting for the package's program cache
        (``core.device.cached_program``: the GBDT fit's jitted step/scan
        programs and the deep path's): hit/miss counters plus a live size
        gauge."""
        reg = self.registry
        if hit:
            reg.counter(
                "profiler_program_cache_hits_total",
                "Jitted-program cache hits (no retrace/lower)",
            ).inc()
        else:
            reg.counter(
                "profiler_program_cache_misses_total",
                "Jitted-program cache misses (program built + traced)",
            ).inc()
        reg.gauge(
            "profiler_program_cache_size",
            "Compiled programs resident in the program cache",
        ).set(size)

    @contextmanager
    def measure(self, name: str):
        """Time a host-side window as one execution of ``name`` (the
        caller is responsible for any device sync inside the block)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.note_execute(name, time.perf_counter() - t0)

    # -- the wrapper ---------------------------------------------------------

    def wrap(
        self,
        fn: Callable[..., Any],
        name: Optional[str] = None,
        cost_analysis: Optional[bool] = None,
    ) -> Callable[..., Any]:
        """Profile a (jitted) callable. Each call runs in a
        ``block_until_ready`` window; a call that grows the executable
        cache (or presents an unseen shape/dtype signature when the
        cache is not introspectable) books a compile with the program's
        ``cost_analysis()`` FLOPs/bytes, every call books an execution.
        Returns ``fn`` unchanged when the profiler is disabled."""
        if not self.enabled:
            return fn
        label = name or getattr(fn, "__name__", None) or repr(fn)
        do_cost = self.cost_analysis if cost_analysis is None else cost_analysis
        seen: Dict[str, bool] = {}
        profiler = self

        def profiled(*args, **kwargs):
            if not profiler.enabled:
                return fn(*args, **kwargs)
            import jax

            sig = _signature(args, kwargs)
            before = _jit_cache_size(fn)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            after = _jit_cache_size(fn)
            if after is not None and before is not None:
                missed = after > before
            else:
                missed = sig not in seen
            seen[sig] = True
            if missed:
                cost = (
                    profiler._cost(fn, args, kwargs) if do_cost else {}
                )
                profiler.note_compile(label, dt, signature=sig, **cost)
            else:
                profiler.note_cache_hit(label)
            profiler.note_execute(label, dt)
            return out

        profiled.__name__ = f"profiled_{label}"
        profiled.__wrapped__ = fn  # type: ignore[attr-defined]
        return profiled

    def wrap_host(
        self, fn: Callable[..., Any], name: str
    ) -> Callable[..., Any]:
        """Time a host-side callable (collective hooks, host folds) as
        executions of ``name`` — no device sync, no compile accounting.
        Returns ``fn`` unchanged when the profiler is disabled."""
        if not self.enabled:
            return fn
        profiler = self

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                profiler.note_execute(name, time.perf_counter() - t0)

        timed.__name__ = f"profiled_{name}"
        timed.__wrapped__ = fn  # type: ignore[attr-defined]
        return timed

    def _cost(self, fn, args, kwargs) -> Dict[str, float]:
        """XLA cost_analysis FLOPs/bytes for this call's program; {} when
        the function can't lower or the backend declines to estimate."""
        lower = getattr(fn, "lower", None)
        if lower is None:
            return {}
        try:
            lowered = lower(*args, **kwargs)
        except Exception:  # noqa: BLE001 - profiling must never fail the call
            return {}
        analysis = None
        try:
            analysis = lowered.cost_analysis()
        except Exception:  # noqa: BLE001
            analysis = None
        if not analysis:
            try:
                analysis = lowered.compile().cost_analysis()
            except Exception:  # noqa: BLE001
                return {}
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        if not isinstance(analysis, dict):
            return {}
        return {
            "flops": float(analysis.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(
                analysis.get("bytes accessed", 0.0) or 0.0
            ),
        }

    # -- gauges + reports ----------------------------------------------------

    def sample_memory(self) -> Dict[str, Dict[str, float]]:
        """Read ``Device.memory_stats()`` into per-device HBM gauges.
        Backends that don't report (CPU returns None) yield {} and set
        nothing — always safe to call."""
        try:
            import jax

            devices = jax.devices()
        except Exception:  # noqa: BLE001 - no backend is a valid state
            return {}
        out: Dict[str, Dict[str, float]] = {}
        g_use = self.registry.gauge(
            "profiler_hbm_bytes_in_use", "Device memory in use (memory_stats)"
        )
        g_lim = self.registry.gauge(
            "profiler_hbm_bytes_limit", "Device memory limit (memory_stats)"
        )
        g_peak = self.registry.gauge(
            "profiler_hbm_bytes_peak", "Peak device memory (memory_stats)"
        )
        for d in devices:
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001
                stats = None
            if not stats:
                continue
            key = str(d)
            rec: Dict[str, float] = {}
            in_use = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit")
            peak = stats.get("peak_bytes_in_use")
            if in_use is not None:
                g_use.labels(device=key).set(float(in_use))
                rec["bytes_in_use"] = float(in_use)
            if limit is not None:
                g_lim.labels(device=key).set(float(limit))
                rec["bytes_limit"] = float(limit)
            if peak is not None:
                g_peak.labels(device=key).set(float(peak))
                rec["peak_bytes_in_use"] = float(peak)
            if rec:
                out[key] = rec
        return out

    def roofline(self) -> List[Dict[str, Any]]:
        """One attribution row per profiled function, hottest first."""
        peaks = device_peaks()
        with self._lock:
            profiles = list(self._profiles.values())
        rows = [
            p.roofline(peaks[0], peaks[1], platform=peaks.platform)
            for p in profiles
        ]
        rows.sort(key=lambda r: -(r["mean_ms"] * r["executions"]))
        return rows

    def snapshot(self) -> Dict[str, Any]:
        """The JSON-safe profiler section for BENCH artifacts: device
        identity + peaks, per-function compile/execute totals, roofline
        rows, and the latest memory sample."""
        try:
            import jax

            device = jax.devices()[0]
            dev = {
                "backend": jax.default_backend(),
                "kind": str(getattr(device, "device_kind", "")),
                "count": len(jax.devices()),
            }
        except Exception:  # noqa: BLE001
            dev = {"backend": "none", "kind": "", "count": 0}
        peaks = device_peaks()
        with self._lock:
            functions = {
                name: p.to_dict() for name, p in self._profiles.items()
            }
        return {
            "device": dev,
            "platform": peaks.platform,
            "peak_flops_per_s": peaks[0],
            "peak_hbm_bytes_per_s": peaks[1],
            "functions": functions,
            "roofline": self.roofline(),
            "memory": self.sample_memory(),
        }

    def clear(self) -> None:
        with self._lock:
            self._profiles.clear()


# -- process-global profiler --------------------------------------------------

_PROFILER: Optional[DeviceProfiler] = None
_PROFILER_LOCK = threading.Lock()


def _env_enabled() -> Optional[bool]:
    raw = os.environ.get("MMLSPARK_TPU_PROFILE")
    if raw is None:
        return None
    return raw.strip().lower() not in ("", "0", "false", "off", "no")


def get_profiler() -> DeviceProfiler:
    """The process-global profiler, DISABLED unless
    ``MMLSPARK_TPU_PROFILE=1`` (re-checked per call, like the event-log
    sink) or a caller ran ``enable()``. Instrumented hot paths guard on
    ``profiler.active`` so the quiet default costs one attribute read."""
    global _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            _PROFILER = DeviceProfiler(enabled=bool(_env_enabled()))
    env = _env_enabled()
    if env is not None:
        _PROFILER.enabled = env
    return _PROFILER
