"""The three readers of the program's own spans, on hand-made span lists, and
the program's spans as the host events of a trace recorded without any."""

import os

import pytest

from chipbench import trace_reduce
from chipbench.readers import span_coverage, span_per_job, span_tag_per_job

HERE = os.path.dirname(os.path.abspath(__file__))


def _span(name, duration, **tags):
    return {"name": name, "duration": duration, "tags": tags}


def _ctx(spans, jobs):
    return {"spans": spans, "jobs": jobs}


# two jobs of three batches each, as the tracer's export() gives them
TWO_JOBS = [
    _span("dnn.stack", 0.25, bytes=100, pad_rows=0), _span("dnn.stack", 0.25, bytes=100, pad_rows=0),
    _span("dnn.stack", 0.5, bytes=100, pad_rows=3), _span("dnn.fetch", 1.0, bytes=8),
    _span("dnn.stack", 0.25, bytes=100, pad_rows=0), _span("dnn.stack", 0.25, bytes=100, pad_rows=0),
    _span("dnn.stack", 0.5, bytes=100, pad_rows=3), _span("dnn.fetch", None, bytes=8),
    _span("image.apply_fetch", 2.0, bytes_up=1000, bytes_down=4000),
    _span("image.apply_fetch", 2.0, bytes_up=1000, bytes_down=4000),
    _span("dnn.transform", 3.0, rows=10), _span("dnn.transform", 3.0, rows=10),
]


@pytest.mark.parametrize("reader,args", [
    (span_per_job, {"span": "dnn.stack"}),
    (span_tag_per_job, {"spans": ["dnn."], "tag": "bytes"}),
    (span_coverage, {"root": "dnn.transform", "children": ["dnn.stack"]}),
])
def test_span_readers_return_nothing_where_there_is_nothing_to_read(reader, args):
    assert reader.read(_ctx([], 2), **args) is None
    assert reader.read(_ctx([_span("other", 1.0, other=1)], 2), **args) is None
    if reader is not span_coverage:  # a share of the root's time needs no count of jobs
        assert reader.read(_ctx(TWO_JOBS, 0), **args) is None


def test_span_per_job_sums_every_occurrence_over_the_jobs():
    # the mean of one occurrence (tracer_span) would say 333 ms; a job spends 1000
    assert span_per_job.read(_ctx(TWO_JOBS, 2), span="dnn.stack") == 1000.0
    assert span_per_job.read(_ctx(TWO_JOBS, 1), span="dnn.stack") == 2000.0
    # a span that never finished has no duration and is left out
    assert span_per_job.read(_ctx(TWO_JOBS, 2), span="dnn.fetch") == 500.0


def test_span_tag_per_job_sums_every_tag_of_that_stem():
    ctx = _ctx(TWO_JOBS, 2)
    assert span_tag_per_job.read(ctx, spans=["dnn.stack"], tag="bytes") == 300.0
    assert span_tag_per_job.read(ctx, spans=["dnn.stack"], tag="pad_rows") == 3.0
    # bytes, bytes_up and bytes_down of every span under either prefix
    both = span_tag_per_job.read(ctx, spans=["image.", "dnn."], tag="bytes", scale=2.0)
    assert both == (600 + 16 + 10000) / 2 / 2.0
    # a tag no span carries; and spans of which only some carry it
    assert span_tag_per_job.read(ctx, spans=["dnn."], tag="u_bytes") is None
    some = [_span("a.x", 1.0, bytes=6), _span("a.y", 1.0)]
    assert span_tag_per_job.read(_ctx(some, 1), spans=["a."], tag="bytes") == 6.0


def test_span_coverage_is_the_leaves_share_of_the_root():
    nested = [
        _span("root", 10.0), _span("stage", 6.0), _span("leaf.a", 2.0), _span("leaf.a", 3.0),
        _span("leaf.b", 3.5), _span("root", 10.0), _span("leaf.a", 9.0), _span("open", None),
    ]
    leaves = span_coverage.read(_ctx(nested, 2), root="root", children=["leaf.a", "leaf.b", "open"])
    assert leaves == pytest.approx(100.0 * 17.5 / 20.0)
    # a parent listed beside its own children counts their time twice
    assert span_coverage.read(_ctx(nested, 2), root="root", children=["stage", "leaf.a", "leaf.b"]) > 100.0
    assert span_coverage.read(_ctx(nested, 2), root="absent", children=["leaf.a"]) is None


# -- the program's spans in a trace's place -----------------------------------

class _Clock:
    """``time.monotonic`` for the tracer, moved by hand; the session started
    at 100 s."""

    T0 = 100.0

    def __init__(self):
        self.ns = 0

    def __call__(self):
        return self.T0 + self.ns * 1e-9


def _spans_at(monkeypatch, intervals):
    """A tracer that recorded {name: (start_ns, end_ns)} under one root."""
    from mmlspark_tpu.observability import tracing

    clock = _Clock()
    monkeypatch.setattr(tracing.time, "monotonic", clock)
    tracer = tracing.Tracer(xprof=False)
    root = tracer.start_span("image.featurize")
    with tracer.attach(root):
        for name, (start, end) in sorted(intervals.items(), key=lambda kv: kv[1]):
            clock.ns = start
            span = tracer.start_span(name)
            clock.ns = end
            tracer.finish(span)
    clock.ns = 10_000
    tracer.finish(root)
    return tracer, clock


def test_trace_events_name_the_gap_of_a_trace_without_host_events(monkeypatch):
    """The recorded fixture's device operations with the tracer's spans in
    the host events' place: the gap [6000, 8000) falls under dnn.fetch."""
    devices, _, _ = trace_reduce.load(os.path.join(HERE, "fixtures", "two_ops_one_gap.xplane.pb"))
    tracer, clock = _spans_at(monkeypatch, {
        "dnn.stack": (5_000, 6_050), "dnn.fetch": (6_100, 7_900), "dnn.dispatch": (7_950, 8_000),
    })
    host = tracer.trace_events(clock.T0)
    assert sorted(name for name, _, _ in host) == ["dnn.dispatch", "dnn.fetch", "dnn.stack"]
    fetch = next(e for e in host if e[0] == "dnn.fetch")
    assert fetch[1:] == pytest.approx((6_100.0, 1_800.0), abs=1e-3)
    got = trace_reduce.reduce(devices, host, job_ns=10_000.0)
    assert got["busy_s"] == 6000e-9 and got["window_s"] == 10000e-9
    owners = dict((name, secs) for name, secs in got["idle_gaps"])
    assert owners["dnn.fetch"] == pytest.approx(2000e-9)
    assert "image.featurize" not in owners  # the root is no leaf: it would own every gap


def test_trace_events_leave_out_parents_and_what_ended_before_the_session(monkeypatch):
    tracer, clock = _spans_at(monkeypatch, {"early": (-500, -100), "across": (-50, 40), "late": (50, 60)})
    names = [name for name, _, _ in tracer.trace_events(clock.T0)]
    assert names == ["across", "late"]
    (start, duration), = [e[1:] for e in tracer.trace_events(clock.T0) if e[0] == "across"]
    assert (start, duration) == pytest.approx((-50.0, 90.0), abs=1e-3)
