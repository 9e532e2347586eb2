"""One run of one cell: ``python3 -m chipbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Set-up (data and weights from the seed, one untimed job as warm-up), a
window of jobs back to back until ``--seconds`` have passed and the job in
flight has ended, the comparison of what the window produced with the plain
reference, and one JSON line last on standard output. Everything that
belongs to one cell, configuration, driver or per-layer metric is a file
found by name (README.md); nothing in this module names one.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".chipbench_trace")
DRY_PEAKS = "TPU v5 lite"  # a dry run's numbers are no device numbers


def load_cell(name: str, root: str = HERE) -> dict:
    """A cell's own file with its configuration's beside it."""
    def read(kind, stem):
        with open(os.path.join(root, kind, stem + ".json")) as f:
            return json.load(f)

    cell = read("workloads", name)
    cell["name"] = name
    cell["config_file"] = read("configs", cell["config"])
    return cell


def sizes(cell: dict, dry: bool):
    """(configuration params, traffic params); a dry run's toy overrides on."""
    config, traffic = dict(cell["config_file"]["params"]), dict(cell["params"])
    if dry:
        config.update(cell["config_file"].get("dry", {}))
        traffic.update(cell.get("dry", {}))
    return config, traffic


def layer_metrics(cell_name: str, root: str = HERE) -> dict:
    """{metric: spec} of the per-layer metrics that list this cell."""
    out = {}
    folder = os.path.join(root, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".json"):
            with open(os.path.join(folder, fname)) as f:
                spec = json.load(f)
            if cell_name in spec["workloads"]:
                out[fname[: -len(".json")]] = spec
    return out


class CompileClock:
    """Sums JAX's own compile events between ``reset`` calls: seconds in
    backend compilation (a persistent-cache hit books its load time here),
    seconds tracing and lowering, and the cache's hit and miss counts."""

    _DURATIONS = {
        "/jax/core/compile/backend_compile_duration": "compile_secs",
        "/jax/core/compile/jaxpr_trace_duration": "trace_secs",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_secs",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self._totals = self._zero()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    @staticmethod
    def _zero() -> dict:
        return {"compile_secs": 0.0, "trace_secs": 0.0, "cache_hits": 0, "cache_misses": 0}

    def _duration(self, event: str, secs: float, **_) -> None:
        key = self._DURATIONS.get(event)
        if key:
            with self._lock:
                self._totals[key] += secs

    def _count(self, event: str, **_) -> None:
        key = self._COUNTS.get(event)
        if key:
            with self._lock:
                self._totals[key] += 1

    def reset(self) -> dict:
        """What accumulated since the last call; starts the next span."""
        with self._lock:
            out, self._totals = self._totals, self._zero()
        return out


def look_for_chip(cell: dict, dry: bool):
    """The one look for a chip, for every entry that reads anything: ->
    (devices, what was found in words). Exits with 2 and prints no result
    where the platform is not ``tpu`` or holds fewer chips than the cell
    asks for; ``dry`` wants the CPU, and gets it before jax is imported."""
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    import mmlspark_tpu  # noqa: F401  (absent: no line is printed at all)

    devices = jax.devices()
    platform = devices[0].platform
    found = f"platform={platform!r} ({len(devices)} x {devices[0].device_kind})"
    if dry and platform != "cpu":
        print(f"chipbench: --dry-run-cpu got {found}", file=sys.stderr)
        sys.exit(2)
    if not dry and (platform != "tpu" or len(devices) < cell["chips"]):
        print(
            f"chipbench: {cell['name']} needs {cell['chips']} TPU chip(s), "
            f"found {found}; only --dry-run-cpu runs off-chip", file=sys.stderr,
        )
        sys.exit(2)
    return devices, found


def passes(checks: dict) -> bool:
    """``correct``, for a run and for a control alike: every number
    compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def window(job, seconds: float, clock=time.perf_counter):
    """Run ``job`` back to back until ``seconds`` have passed and the job in
    flight has ended. -> (window seconds from the first job's start to the
    last job's end, [(output or None, error or None, job seconds)])."""
    done = []
    start = clock()
    while True:
        t0 = clock()
        try:
            out, err = job(), None
        except Exception as e:  # a failed job is counted, not fatal
            out, err = None, f"{type(e).__name__}: {e}"
        now = clock()
        done.append((out, err, now - t0))
        if now - start >= seconds:
            return now - start, done


def rss_gib() -> float:
    """This process's peak resident memory so far (Linux reports KiB)."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def build_native(log) -> None:
    """Build native/*.so where it is missing; a checkout has none, and
    without it binning falls to numpy in silence."""
    import mmlspark_tpu.native as native

    if not native.native_available() and not native.native_disabled():
        if shutil.which("make") and shutil.which("g++"):
            native.build()
    log(binning="native" if native.native_available() else "numpy")


def traced_job(job, host_tracer_level: int, dry: bool, log) -> dict:
    """One more job under the profiler; -> trace_reduce.reduce's dict. The
    host tracer level is the cell's (``profiler`` in its file): at 1 the
    trace holds the program's and the benchmark's annotations, which name
    the idle gaps; at 0 it holds none, and the job's own clock bounds the
    window."""
    import jax

    from chipbench import trace_reduce

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans come from TraceAnnotation
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    marks = [time.perf_counter()]
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            job()
        marks.append(time.perf_counter())
    finally:
        jax.profiler.stop_trace()
    marks.append(time.perf_counter())
    try:
        path = trace_reduce.find_xplane(TRACE_DIR)
        size = os.path.getsize(path)
        devices, host, seen = trace_reduce.load(path, cpu_as_device=dry)
        marks.append(time.perf_counter())
        reduced = trace_reduce.reduce(devices, host, job_ns=(marks[1] - marks[0]) * 1e9)
        marks.append(time.perf_counter())
        took = [b - a for a, b in zip(marks[:-1], marks[1:])]
        log(traced=dict(zip(("job_s", "stop_s", "load_s", "reduce_s"), took),
                        xplane_bytes=size, host_events=len(host),
                        device_events=sum(len(v) for v in devices.values())),
            host_peak_gib=rss_gib())
        return dict(reduced, planes=seen)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def measure(cell: dict, seed: int, seconds: float, trace: bool, dry: bool,
            log=lambda **kw: None, driver=None, root: str = HERE) -> dict:
    """Everything after the look for a chip. -> the result line's dict.
    ``driver`` and ``root`` are for the tests: a driver with its timed path
    broken, and a copy of this directory with files added."""
    import jax

    from mmlspark_tpu.core.device import configure_compile_cache
    from mmlspark_tpu.observability.tracing import get_tracer

    devices = jax.devices()
    log(compile_cache_dir=configure_compile_cache())
    build_native(log)
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)
    kind = DRY_PEAKS if dry else devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    clock = CompileClock()
    driver = driver or importlib.import_module(f"chipbench.drivers.{cell['driver']}")
    config, traffic = sizes(cell, dry)

    state = driver.setup(config, traffic, seed)
    warm = driver.job(state)
    reason = driver.fault(state, warm)
    if reason:
        raise RuntimeError(f"the warm-up job left its path: {reason}")
    del warm
    log(setup=clock.reset(), host_peak_gib=rss_gib())
    get_tracer().clear()
    setup_s = time.perf_counter() - _T0

    window_s, done = window(lambda: driver.job(state), seconds)
    compile_in_window = clock.reset()
    spans = get_tracer().export()
    outputs, failed = [], 0
    for out, err, secs in done:
        reason = err or driver.fault(state, out)
        log(job_s=secs, failed=reason)
        if reason:
            failed += 1
        else:
            outputs.append(out)
    stats = devices[0].memory_stats() or {}
    peak_bytes = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices
    )
    reduced = (
        # a dry run's "device" is the host's XLA threads, so it needs level 1
        traced_job(lambda: driver.job(state),
                   1 if dry else cell.get("profiler", {}).get("host_tracer_level", 1), dry, log)
        if trace else None
    )

    good = len(outputs)
    end_to_end = driver.end_to_end(state, window_s, good)
    end_to_end["setup_s"] = (setup_s, "s")
    ctx = {
        "window_s": window_s, "jobs": good, "work": driver.work(config, traffic),
        "peaks": peaks[kind], "spans": spans, "compile": compile_in_window,
        "memory": stats, "trace": reduced,
    }
    metrics = {}
    if trace:
        for name, spec in layer_metrics(cell["name"], root).items():
            reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:  # nothing to read: the metric is left out
                metrics[name] = (value, spec["unit"])
    else:
        metrics = end_to_end
    log(end_to_end={k: v for k, (v, _) in end_to_end.items()}, host_peak_gib=rss_gib())

    checks = driver.compare(state, outputs, seed) if outputs else {}
    correct = good > 0 and passes(checks)
    prefix = "dry_" if dry else ""
    result = {
        "correct": correct,
        "attempted": len(done),
        "failed": failed,
        "metrics": {
            prefix + k: {"value": v, "unit": u} for k, (v, u) in metrics.items()
        },
        "device": {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes,
        },
    }
    if reduced:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
        log(trace_planes=reduced["planes"])
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--dry-run-cpu", action="store_true",
        help="same control flow at the cell's toy sizes on the CPU, every "
        "metric under a dry_ name; for the tests, never a measurement",
    )
    args = parser.parse_args(argv)
    cell = load_cell(args.workload)
    _, found = look_for_chip(cell, args.dry_run_cpu)

    def log(**kw):
        print(json.dumps(kw, default=str), flush=True)

    log(workload=cell["name"], seed=args.seed, device=found, dry_run=args.dry_run_cpu)
    result = measure(
        cell, args.seed, args.seconds, bool(args.trace), args.dry_run_cpu, log
    )
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
