"""The span that paid for a compile says so: JAX's trace, lowering, compile and
cache events booked to the innermost ambient span of the global tracer, kept in
``Tracer.compile_log()`` beside the ring, and the first call of every root span
in ``Tracer.first_calls()`` (docs/observability.md)."""

import threading

import jax
import jax.numpy as jnp
import pytest

from chipbench.run import CompileClock
from mmlspark_tpu.core import device
from mmlspark_tpu.observability import tracing
from mmlspark_tpu.observability.tracing import COMPILE_TAGS as PAID, NO_SPAN, Tracer, get_tracer


def fresh_program():
    """A jitted function no earlier test compiled: JAX keys its caches on
    the function object."""
    return jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)


def log_since(mark):
    """The records spans added since ``mark``; a test's own eager operations
    (``jnp.ones``) compile under no span and are read by ``unowned``."""
    return [r for r in get_tracer().compile_log()[mark:] if r["span"] != NO_SPAN]


def unowned(key):
    records = [r for r in get_tracer().compile_log() if r["span"] == NO_SPAN]
    assert all(r["trace_id"] == "" for r in records)
    return len(records), sum(r[key] for r in records)


@pytest.fixture()
def mark():
    """Where the log stood when the test began (it is never emptied). Far
    from the bound, so the slice holds."""
    assert tracing._LISTENING, "conftest imports jax first: the module listens from import on"
    n = len(get_tracer().compile_log())
    assert n < tracing._COMPILE_LOG_SIZE - 64
    return n


def test_a_first_call_books_on_the_innermost_span_and_not_on_its_parent(mark):
    tracer, x = get_tracer(), jnp.ones(7)
    program = fresh_program()
    with tracer.span("booking.outer") as outer:
        with tracer.span("booking.inner") as inner:
            program(x)
    assert inner.tags["trace_s"] > 0 and inner.tags["compile_s"] > 0
    assert not set(outer.tags) & set(PAID)
    (record,) = log_since(mark)
    assert record == {
        "t": inner.start, "span": "booking.inner", "trace_id": inner.trace_id,
        "trace_s": inner.tags["trace_s"], "compile_s": inner.tags["compile_s"],
        "cache_hits": inner.tags.get("cache_hits", 0),
        "cache_misses": inner.tags.get("cache_misses", 0),
    }
    exported = {s["name"]: s["tags"] for s in tracer.export(inner.trace_id)}
    assert exported["booking.inner"]["compile_s"] == record["compile_s"]


def test_the_same_shape_again_books_nothing_and_adds_no_tag(mark):
    tracer, x = get_tracer(), jnp.ones(7)
    program = fresh_program()
    with tracer.span("booking.first"):
        program(x)
    with tracer.span("booking.again") as again:
        program(x)
    assert again.tags == {} and again.booked is None
    assert [r["span"] for r in log_since(mark)] == ["booking.first"]


def test_a_new_shape_under_one_wrapper_books_again_and_builds_no_program(mark):
    tracer = get_tracer()
    made = []
    program = device.cached_program(
        ("test_compile_booking", "new shape"), lambda: made.append(1) or fresh_program())
    built = device.programs_built()
    with tracer.span("booking.shape_a") as a:
        device.cached_program(("test_compile_booking", "new shape"), fresh_program)(jnp.ones(4))
    with tracer.span("booking.shape_b") as b:
        device.cached_program(("test_compile_booking", "new shape"), fresh_program)(jnp.ones(5))
    assert made == [1] and device.programs_built() == built
    assert a.tags["compile_s"] > 0 and b.tags["compile_s"] > 0 and b.tags["trace_s"] > 0
    assert [r["span"] for r in log_since(mark)] == ["booking.shape_a", "booking.shape_b"]
    assert program is device.cached_program(("test_compile_booking", "new shape"), fresh_program)


def test_no_ambient_span_books_to_no_span_and_coalesces(mark, monkeypatch):
    tracer, x = get_tracer(), jnp.ones(3)
    assert tracer.current() is None
    monkeypatch.setattr(tracing, "_NO_SPAN_COALESCE_S", 3600.0)  # however slow this machine compiles
    clock = CompileClock()
    (n, compiled), (_, traced) = unowned("compile_s"), unowned("trace_s")
    clock.reset()
    fresh_program()(x)
    fresh_program()(x)
    told = clock.reset()
    assert log_since(mark) == []
    assert told["compile_secs"] > 0 and told["trace_secs"] > 0
    assert unowned("compile_s")[1] - compiled == pytest.approx(told["compile_secs"], rel=1e-6)
    assert unowned("trace_s")[1] - traced == pytest.approx(told["trace_secs"], rel=1e-6)
    assert unowned("compile_s")[0] - n <= 1, "two programs' dozen events coalesce into the open record"


def test_a_no_span_record_closes_after_its_second(mark, monkeypatch):
    tracer = get_tracer()
    tracer._book("trace_s", 0.25)
    n, _ = unowned("trace_s")
    tracer._book("trace_s", 0.25)
    assert unowned("trace_s")[0] == n, "within its second: the same record"
    monkeypatch.setattr(tracing, "_NO_SPAN_COALESCE_S", -1.0)  # every event is late
    tracer._book("trace_s", 0.5)
    assert unowned("trace_s")[0] == n + 1
    first, second = [r for r in tracer.compile_log() if r["span"] == NO_SPAN][-2:]
    assert second["trace_s"] == 0.5 and second["t"] >= first["t"]


def test_a_compile_on_another_thread_goes_to_that_threads_span(mark):
    tracer, x = get_tracer(), jnp.ones(6)
    program, seen = fresh_program(), {}

    def work():
        with tracer.span("booking.thread") as sp:
            program(x)
        seen["span"] = sp

    with tracer.span("booking.main") as main:
        worker = threading.Thread(target=work)
        worker.start()
        worker.join()
    assert seen["span"].tags["compile_s"] > 0
    assert seen["span"].parent_id is None, "a new thread has no ambient span"
    assert not set(main.tags) & set(PAID)
    assert [r["span"] for r in log_since(mark)] == ["booking.thread"]


def test_an_attached_thread_books_to_the_span_it_joined(mark):
    tracer, x = get_tracer(), jnp.ones(6)
    program = fresh_program()
    with tracer.span("booking.joined") as joined:
        def work():
            with tracer.attach(joined):
                program(x)
        worker = threading.Thread(target=work)
        worker.start()
        worker.join()
    assert joined.tags["compile_s"] > 0
    assert [r["span"] for r in log_since(mark)] == ["booking.joined"]


def test_the_cache_counts_are_booked_as_tags_where_they_happen(mark):
    tracer = get_tracer()
    with tracer.span("booking.counts") as sp:
        tracing._on_event("/jax/compilation_cache/cache_misses")
        tracing._on_event("/jax/compilation_cache/cache_misses")
        tracing._on_event("/jax/compilation_cache/cache_hits")
        tracing._on_event("/jax/compilation_cache/tasks_using_cache")  # not booked
        tracing._on_seconds("/jax/core/compile/jaxpr_trace_duration", 0.5)
        tracing._on_seconds("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25)
        tracing._on_seconds("/jax/compilation_cache/cache_retrieval_time_sec", 9.0)  # not booked
    assert sp.tags == {"cache_misses": 2, "cache_hits": 1, "trace_s": 0.75}
    (record,) = log_since(mark)
    assert (record["cache_misses"], record["cache_hits"], record["trace_s"], record["compile_s"]) == (
        2, 1, 0.75, 0.0)


def test_clear_empties_the_ring_and_keeps_the_log_and_the_first_calls(mark):
    tracer, x = get_tracer(), jnp.ones(9)
    program = fresh_program()
    with tracer.span("booking.stage_kept") as first:
        program(x)
    with tracer.span("booking.stage_kept"):
        pass
    assert tracer.export(first.trace_id)
    tracer.clear()
    assert tracer.export() == []
    (record,) = log_since(mark)
    assert record["span"] == "booking.stage_kept" and record["trace_id"] == first.trace_id
    calls = {c["name"]: c for c in tracer.first_calls()}
    assert calls["booking.stage_kept"] == {
        "name": "booking.stage_kept", "trace_id": first.trace_id,
        "start": first.start, "duration": first.duration,
    }, "the first instance, not the second"
    starts = [c["start"] for c in tracer.first_calls()]
    assert starts == sorted(starts)
    # a stage's compile share is the log's records of its first call's trace
    share = [r for r in tracer.compile_log() if r["trace_id"] == first.trace_id]
    assert sum(r["compile_s"] for r in share) == first.tags["compile_s"]


def test_only_root_spans_are_first_calls():
    tracer = Tracer(xprof=False)
    with tracer.span("root.a"):
        with tracer.span("child.b"):
            pass
    manual = tracer.start_span("root.manual")
    tracer.finish(manual)
    assert [c["name"] for c in tracer.first_calls()] == ["root.a", "root.manual"]


def test_the_log_and_the_first_call_table_are_bounded():
    tracer = Tracer(xprof=False)
    for i in range(tracing._COMPILE_LOG_SIZE + 40):
        with tracer.span(f"bounded.{i}"):
            tracer._book("compile_s", 1.0)
    log = tracer.compile_log()
    assert len(log) == tracing._COMPILE_LOG_SIZE
    assert log[-1]["span"] == f"bounded.{tracing._COMPILE_LOG_SIZE + 39}", "the newest are kept"
    assert len(tracer.first_calls()) == tracing._FIRST_CALLS_SIZE
    assert tracer.first_calls()[0]["name"] == "bounded.0", "the first names are kept"


def test_registering_twice_books_once_and_a_second_tracer_books_nothing(mark):
    import importlib

    assert importlib.import_module("mmlspark_tpu.observability.tracing") is tracing
    tracing._listen()
    tracing._listen()
    other = Tracer()
    clock = CompileClock()
    program, x = fresh_program(), jnp.ones(11)
    clock.reset()
    with other.span("booking.other_tracer") as elsewhere:
        with get_tracer().span("booking.once") as sp:
            program(x)
    told = clock.reset()
    assert other.compile_log() == [] and elsewhere.tags == {}
    assert sp.tags["compile_s"] == told["compile_secs"], "twice registered would read twice"
    assert [r["span"] for r in log_since(mark)] == ["booking.once"]


def test_the_harness_clock_and_the_log_agree_to_the_float(mark):
    clock = CompileClock()
    program, x = fresh_program(), jnp.ones((5, 3))
    clock.reset()
    with get_tracer().span("booking.agree") as sp:
        program(x)
        program(jnp.ones((6, 3)))
    told = clock.reset()
    (record,) = log_since(mark)
    assert (record["compile_s"], record["trace_s"]) == (told["compile_secs"], told["trace_secs"])
    assert (record["cache_hits"], record["cache_misses"]) == (told["cache_hits"], told["cache_misses"])
    assert sp.tags["trace_s"] == told["trace_secs"] > 0


def test_a_span_that_pays_nothing_runs_what_it_ran_before():
    tracer = get_tracer()
    with tracer.span("booking.free", rows=3) as sp:
        pass
    assert sp.tags == {"rows": 3} and sp.booked is None
    assert "booked" not in vars(sp), "no attribute is set on a span that pays nothing"
    assert "booked" not in {f.name for f in __import__("dataclasses").fields(sp)}
