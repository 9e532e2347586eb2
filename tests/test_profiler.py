"""Tests for the perf-observability plane: DeviceProfiler, event-log
rotation, fit-scale buckets, the SLO fold, and the history render."""

import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import observability as obs
from mmlspark_tpu.observability.events import EventLogSink
from mmlspark_tpu.observability.history import main as history_main
from mmlspark_tpu.observability.history import render_report
from mmlspark_tpu.observability.profiler import (
    DeviceProfiler,
    device_peaks,
    get_profiler,
)
from mmlspark_tpu.observability.registry import (
    DEFAULT_BUCKETS,
    FIT_BUCKETS,
    MetricsRegistry,
)
from mmlspark_tpu.observability.slo import SLOReport, SLOTargets


def _fresh_profiler():
    bus = obs.EventBus()
    seen = []
    bus.add_listener(seen.append)
    prof = DeviceProfiler(registry=MetricsRegistry(), bus=bus)
    return prof, seen


class TestDeviceProfiler:
    def test_compile_then_execute_event_ordering(self):
        prof, seen = _fresh_profiler()
        fn = prof.wrap(jax.jit(lambda x: x * 2.0), name="double")
        x = jnp.ones((8, 8), jnp.float32)
        fn(x)
        fn(x)
        kinds = [type(e).__name__ for e in seen]
        # first call compiles (and executes); second is a warm execution
        assert kinds == [
            "ProfileCompiled", "ProfileExecuted", "ProfileExecuted",
        ], kinds
        assert seen[0].name == "double"
        assert seen[0].seconds > 0
        p = prof.snapshot()["functions"]["double"]
        assert p["compiles"] == 1
        assert p["executions"] == 2
        assert p["cache_hits"] == 1

    def test_new_shape_books_a_second_compile(self):
        prof, seen = _fresh_profiler()
        fn = prof.wrap(jax.jit(lambda x: x + 1.0), name="inc")
        fn(jnp.ones((4,), jnp.float32))
        fn(jnp.ones((8,), jnp.float32))  # new shape -> retrace
        kinds = [type(e).__name__ for e in seen]
        assert kinds.count("ProfileCompiled") == 2, kinds

    def test_cost_analysis_folds_flops_and_bytes(self):
        prof, _ = _fresh_profiler()
        fn = prof.wrap(jax.jit(lambda a, b: a @ b), name="matmul")
        a = jnp.ones((32, 32), jnp.float32)
        fn(a, a)
        p = prof.snapshot()["functions"]["matmul"]
        # XLA's estimate for one execution of the compiled program
        assert p["flops"] > 0
        assert p["bytes_accessed"] > 0
        row = prof.roofline()[0]
        assert row["name"] == "matmul"
        assert row["achieved_flops_per_s"] > 0
        # a rig with no peak-table row argues against no machine balance
        if device_peaks().known:
            assert row["bound"] in ("compute", "memory")
        else:
            assert row["bound"] == "unknown"

    def test_memory_stats_absent_on_cpu_backend(self):
        prof, _ = _fresh_profiler()
        # CPU devices return None from memory_stats(): the sample must be
        # safe, empty, and set no per-device gauge series
        sample = prof.sample_memory()
        assert sample == {}
        gauge = prof.registry.get("profiler_hbm_bytes_in_use")
        assert gauge is not None and not gauge._children

    def test_disabled_profiler_is_identity(self):
        prof = DeviceProfiler(registry=MetricsRegistry(), bus=obs.EventBus(),
                              enabled=False)
        fn = jax.jit(lambda x: x)
        assert prof.wrap(fn) is fn
        assert prof.wrap_host(fn, "h") is fn
        assert not prof.active

    def test_transfer_counter(self):
        prof, _ = _fresh_profiler()
        prof.note_transfer(1024, "h2d", name="up")
        prof.note_transfer(256, "d2h", name="up")
        prof.note_transfer(-5, "h2d")  # ignored
        c = prof.registry.get("profiler_transfer_bytes_total")
        assert c.labels(direction="h2d").value == 1024
        assert c.labels(direction="d2h").value == 256
        assert prof.snapshot()["functions"]["up"]["transfer_bytes"] == 1280

    def test_merge_folds_external_totals(self):
        prof, _ = _fresh_profiler()
        prof.merge("procfit.allreduce[m0]", executions=10, device_seconds=0.5)
        prof.merge("procfit.allreduce[m0]", executions=5, device_seconds=0.25)
        p = prof.snapshot()["functions"]["procfit.allreduce[m0]"]
        assert p["executions"] == 15
        assert p["device_seconds"] == pytest.approx(0.75)

    def test_measure_and_wrap_host(self):
        prof, seen = _fresh_profiler()
        with prof.measure("window"):
            pass
        timed = prof.wrap_host(lambda v: v + 1, "hostfn")
        assert timed(41) == 42
        fns = prof.snapshot()["functions"]
        assert fns["window"]["executions"] == 1
        assert fns["hostfn"]["executions"] == 1
        assert all(type(e).__name__ == "ProfileExecuted" for e in seen)

    def test_peak_env_overrides(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("MMLSPARK_TPU_PEAK_HBM_BYTES", "1e11")
        assert device_peaks() == (1e12, 1e11)

    def test_global_profiler_env_resync(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PROFILE", "1")
        assert get_profiler().active
        monkeypatch.setenv("MMLSPARK_TPU_PROFILE", "0")
        assert not get_profiler().active

    def test_compile_metrics_use_fit_buckets(self):
        prof, _ = _fresh_profiler()
        prof.note_compile("slow", 120.0)  # a 2-minute XLA compile
        h = prof.registry.get("profiler_compile_seconds")
        assert h.buckets == FIT_BUCKETS
        assert h.percentile(0.99) > 10.0  # not clamped at DEFAULT's top


class TestEventLogRotation:
    def _events(self, n):
        return [obs.ProfileExecuted(name=f"fn{i}", seconds=float(i))
                for i in range(n)]

    def test_rotation_and_ordered_replay(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        sink = EventLogSink(path, max_bytes=150)
        events = self._events(12)
        for e in events:
            sink(e)
        sink.close()
        segs = obs.log_segments(path)
        assert len(segs) > 1, "log never rotated"
        assert segs[-1] == path  # live file last
        # every rotated segment respects the bound
        for seg in segs[:-1]:
            assert os.path.getsize(seg) <= 150
        replayed = obs.replay(path)
        assert [e.name for e in replayed] == [e.name for e in events]

    def test_oversized_event_does_not_rotate_forever(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        sink = EventLogSink(path, max_bytes=10)  # smaller than any record
        for e in self._events(3):
            sink(e)
        sink.close()
        # each event rotates the previous one out; all three survive
        assert len(obs.replay(path)) == 3

    def test_max_bytes_from_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_EVENT_LOG_MAX_BYTES", "123")
        sink = EventLogSink(str(tmp_path / "ev.jsonl"))
        assert sink.max_bytes == 123
        sink.close()
        monkeypatch.setenv("MMLSPARK_TPU_EVENT_LOG_MAX_BYTES", "0")
        sink = EventLogSink(str(tmp_path / "ev2.jsonl"))
        assert sink.max_bytes is None  # 0 = unbounded
        sink.close()

    def test_unrelated_siblings_are_not_segments(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        (tmp_path / "ev.jsonl.bak").write_text("not a segment\n")
        (tmp_path / "ev.jsonl.2") .write_text("")
        EventLogSink(path).close()
        segs = obs.log_segments(path)
        assert str(tmp_path / "ev.jsonl.bak") not in segs
        assert segs == [str(tmp_path / "ev.jsonl.2"), path]

    def test_reopened_sink_continues_sequence(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        sink = EventLogSink(path, max_bytes=150)
        for e in self._events(8):
            sink(e)
        sink.close()
        before = len(obs.log_segments(path))
        sink = EventLogSink(path, max_bytes=150)  # a restarted process
        for e in self._events(8):
            sink(e)
        sink.close()
        assert len(obs.log_segments(path)) > before
        assert len(obs.replay(path)) == 16


class TestFitBuckets:
    def test_fit_scale_percentile_is_not_clamped(self):
        reg = MetricsRegistry()
        h = reg.histogram("fit_seconds", buckets=FIT_BUCKETS)
        for v in (45.0, 90.0, 200.0, 400.0):
            h.observe(v)
        assert h.percentile(0.99) > 10.0
        # the old DEFAULT_BUCKETS behavior this fixes: everything in +Inf
        d = reg.histogram("fit_seconds_default")
        for v in (45.0, 90.0, 200.0, 400.0):
            d.observe(v)
        assert d.percentile(0.99) == DEFAULT_BUCKETS[-1]

    def test_fit_buckets_are_sorted_and_extend_default(self):
        assert list(FIT_BUCKETS) == sorted(FIT_BUCKETS)
        assert FIT_BUCKETS[-1] > DEFAULT_BUCKETS[-1]


class TestSLOReport:
    def _served(self, n, latency=0.002, status=200):
        return [obs.RequestServed(rid=f"r{i}", status=status, latency=latency)
                for i in range(n)]

    def test_fold_determinism_under_seeded_chaos(self, monkeypatch):
        """The report must equal the registry fold exactly — the PR 3
        summary-equality posture — even with unrelated seeded-chaos
        events (task kills, retries) interleaved in the stream."""
        monkeypatch.setenv("MMLSPARK_TPU_FAULT_SEED", "0")
        from mmlspark_tpu import runtime

        plan = runtime.FaultPlan(seed=0).kill_task(1)
        pol = runtime.SchedulerPolicy(max_workers=2, backoff_base=0.01,
                                      faults=plan)
        bus = obs.get_bus()
        chaos = []
        bus.add_listener(chaos.append)
        try:
            out = runtime.run_partitioned(lambda x: x * 2, [1, 2, 3], pol)
        finally:
            bus.remove_listener(chaos.append)
        assert out == [2, 4, 6]
        assert any(isinstance(e, obs.TaskFailed) for e in chaos)

        reg = MetricsRegistry()
        reg.counter("serving_requests_total").inc(6)
        reg.counter("serving_shed_total").inc(2)
        q = reg.histogram("serving_queue_wait_seconds")
        a = reg.histogram("serving_apply_latency_seconds")
        for v in (0.001, 0.002, 0.003):
            q.observe(v)
            a.observe(v)
        events = chaos + self._served(5) + self._served(1, status=503)

        report = SLOReport.fold(reg, events=events)
        summary = reg.summary()
        # exact equality between the report and the registry fold
        assert report.requests == summary["serving_requests_total"]
        assert report.shed == summary["serving_shed_total"]
        assert report.stages["queue"] == summary["serving_queue_wait_seconds"]
        assert report.stages["apply"] == summary["serving_apply_latency_seconds"]
        assert report.e2e["count"] == 6  # chaos events never count
        assert report.errors == 1
        # folding the summary DICT (the history server's path) is
        # byte-identical to folding the registry object
        assert SLOReport.fold(summary, events=events).to_dict() == \
            report.to_dict()
        # and the fold is a pure function of its inputs
        assert SLOReport.fold(reg, events=events).to_json() == \
            report.to_json()

    def test_shed_pct_and_error_budget(self):
        reg = MetricsRegistry()
        reg.counter("serving_requests_total").inc(98)
        reg.counter("serving_shed_total").inc(2)
        events = self._served(97) + self._served(1, status=500)
        report = SLOReport.fold(reg, events=events)
        assert report.shed_pct == pytest.approx(2.0)
        assert report.error_rate == pytest.approx(1 / 98)
        # 3 nines = 0.1% budget; 1/98 errors blows it
        assert report.error_budget_consumed > 1.0
        assert not report.ok()

    def test_event_only_fold(self):
        report = SLOReport.fold(None, events=self._served(4, latency=0.01))
        assert report.requests == 4
        assert report.e2e["p50"] == pytest.approx(0.01)

    def test_renderers(self):
        report = SLOReport.fold(None, events=self._served(3),
                                targets=SLOTargets(p50_ms=1.0))
        md = report.to_markdown()
        assert "| apply p50 |" in md and "| stage |" in md
        parsed = json.loads(report.to_json())
        assert parsed["requests"] == 3
        assert "stages" in parsed and "targets" in parsed


class TestTrainProfilerWiring:
    @pytest.fixture()
    def data(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        return X, y

    def _fit(self, X, y, **kw):
        from mmlspark_tpu.lightgbm.train import TrainOptions, train

        return train(
            X, y, TrainOptions(objective="binary", num_iterations=3,
                               num_leaves=7), **kw,
        )

    def test_loop_path_books_per_iteration_windows(self, data):
        X, y = data
        prof = get_profiler().enable()
        prof.clear()
        try:
            # iteration_hook forces the loop path
            self._fit(X, y, iteration_hook=lambda it, tree: None)
            p = prof.snapshot()["functions"]["gbdt.step"]
            assert p["executions"] == 3
            assert p["compiles"] >= 1
            assert p["device_seconds"] > 0
        finally:
            prof.disable()
            prof.clear()

    def test_scan_path_books_segment_windows(self, data):
        X, y = data
        prof = get_profiler().enable()
        prof.clear()
        try:
            self._fit(X, y)
            p = prof.snapshot()["functions"]["gbdt.scan"]
            assert p["executions"] >= 1
            assert p["device_seconds"] > 0
        finally:
            prof.disable()
            prof.clear()

    def test_disabled_profiler_books_nothing(self, data):
        X, y = data
        prof = get_profiler()
        prof.disable()
        prof.clear()
        self._fit(X, y, iteration_hook=lambda it, tree: None)
        assert "gbdt.step" not in prof.snapshot()["functions"]


class TestServingProfilerWiring:
    def test_serving_apply_booked(self):
        from mmlspark_tpu.core.pipeline import Model
        from mmlspark_tpu.data.table import Table
        from mmlspark_tpu.serving import ServingServer

        class _Echo(Model):
            def transform(self, t):
                return Table({
                    "prediction": np.asarray(t.column("input"), np.float64)
                })

        prof = get_profiler().enable()
        prof.clear()
        try:
            with ServingServer(_Echo(), max_latency_ms=1.0) as srv:
                base = srv.info.url.rstrip("/")
                req = urllib.request.Request(
                    base, data=json.dumps({"input": 1.0}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                urllib.request.urlopen(req, timeout=10).read()
            p = prof.snapshot()["functions"]["serving.apply"]
            assert p["executions"] >= 1
            assert p["transfer_bytes"] > 0
        finally:
            prof.disable()
            prof.clear()


class TestHistoryReport:
    def _events(self):
        return [
            obs.StageStarted(job_id=0, stage_id=0, name="Binning", t=1.0),
            obs.StageCompleted(job_id=0, stage_id=0, name="Binning",
                               duration=0.5, t=1.5),
            obs.StageStarted(job_id=0, stage_id=1, name="Boost", t=1.5),
            obs.StageCompleted(job_id=0, stage_id=1, name="Boost",
                               duration=1.0, status="ValueError", t=2.5),
            obs.TaskFailed(job_id=0, task_id=1, reason="executor_death",
                           worker=0, duration=0.1, attempt=0),
            obs.TaskFailed(job_id=0, task_id=1, reason="timeout", worker=1,
                           duration=0.2, attempt=1, speculative=True),
            obs.RequestServed(rid="r1", status=200, latency=0.002),
            obs.RequestShed(reason="queue_full", queue_depth=9),
            obs.BreakerTripped(breaker="apply", failures=3, window_s=30.0),
            obs.ModelSwapped(name="m", version=2, server="s1"),
            obs.ProfileCompiled(name="gbdt.step", seconds=0.4, flops=1e9,
                                bytes_accessed=1e8),
            obs.ProfileExecuted(name="gbdt.step", seconds=0.01),
            obs.StreamEpochCommitted(query="q", epoch=0, rows=100),
        ]

    def test_render_contains_all_sections(self):
        doc = render_report(self._events(), title="t")
        for needle in (
            "Stage timeline", "Task attempts", "Serving SLO",
            "Profiler roofline", "Resilience", "Streaming",
            "executor_death", "gbdt.step", "apply p50",
            "bar failed",  # the failed Boost stage renders red
        ):
            assert needle in doc, f"report missing {needle!r}"
        # self-contained: no external refs
        assert "http://" not in doc and "https://" not in doc

    def test_render_escapes_html(self):
        evs = [obs.StageStarted(job_id=0, stage_id=0,
                                name="<script>alert(1)</script>")]
        doc = render_report(evs)
        assert "<script>alert(1)" not in doc
        assert "&lt;script&gt;" in doc

    def test_cli_writes_report(self, tmp_path, capsys):
        log = tmp_path / "ev.jsonl"
        sink = EventLogSink(str(log))
        for e in self._events():
            sink(e)
        sink.close()
        metrics = tmp_path / "metrics.json"
        metrics.write_text(json.dumps({"serving_requests_total": 1.0}))
        out = tmp_path / "report.html"
        rc = history_main([str(log), "-o", str(out),
                           "--metrics", str(metrics), "--title", "ci run"])
        assert rc == 0
        assert capsys.readouterr().out.strip() == str(out)
        doc = out.read_text()
        assert "ci run" in doc and "Stage timeline" in doc

    def test_cli_default_output_path(self, tmp_path, capsys):
        log = tmp_path / "ev.jsonl"
        sink = EventLogSink(str(log))
        sink(obs.RequestServed(rid="r", status=200, latency=0.001))
        sink.close()
        assert history_main([str(log)]) == 0
        assert (tmp_path / "ev.jsonl.html").exists()
