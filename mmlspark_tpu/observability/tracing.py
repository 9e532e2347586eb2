"""Request/stage tracing — Dapper-style spans with ``contextvars`` propagation.

Spark's UI reconstructs "what ran inside what" from listener events; a
serving stack needs the stronger form: a trace id minted at the request
edge that survives thread hops (HTTP handler -> micro-batch loop ->
model apply) so one request's full span tree can be read back. This
module is that layer:

- :class:`Span` — name, ids, monotonic start/end, tags, status;
- :class:`Tracer` — ``with tracer.span("stage"):`` opens a child of the
  ambient span (a ``contextvars.ContextVar``, so nesting follows the
  call stack and is async/thread-correct); ``start_span``/``finish``
  are the manual form for spans that cross threads (the scheduler's
  attempts, the serving batch loop);
- **the span that paid for a compile says so**: JAX's own monitoring events
  (trace, lowering, backend compile or cache load, cache hit and miss) are
  booked to the innermost ambient span of the global tracer as the tags
  ``trace_s``, ``compile_s``, ``cache_hits``, ``cache_misses``, and kept
  in :meth:`Tracer.compile_log`, which :meth:`Tracer.clear` and the ring
  leave alone; :meth:`Tracer.first_calls` keeps the first finished
  instance of every root span, a stage's first-call penalty;
- ids are **deterministic**: process-wide counters, not random — two
  identical single-threaded runs produce identical span ids, which is
  what replay-based tests want;
- :class:`TraceContext` carries a trace across the **wire**
  (``X-Trace-Id`` / ``X-Parent-Span-Id`` headers, or a plain dict in a
  process-group epoch spec); ``start_span(..., context=ctx)`` opens a
  span whose trace id came from another process. Wire parent ids are
  qualified ``<process>:<span_id>`` so the merged fleet log can resolve
  parents unambiguously even though every process mints span ids from
  its own counter;
- every span entered through the context manager is bridged into
  :func:`mmlspark_tpu.core.profiling.annotate`, so an active xprof
  device trace shows the same names as the exported span tree.

Finished spans accumulate in a bounded ring (default 4096);
:meth:`Tracer.export` gives them as JSON-able records and
:meth:`Tracer.trace_events` on the clock of a profiler session. When the
event bus has listeners (``MMLSPARK_TPU_EVENT_LOG`` set), every finished
span is also published
as a :class:`~mmlspark_tpu.observability.events.SpanRecorded` event, so
the per-process event-log segments carry the span stream the history
server's cross-process waterfall is rebuilt from.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

#: JAX's monitoring events a span is booked for -> the tag each adds to.
#: ``backend_compile_duration`` holds a persistent-cache hit's load time;
#: JAX times a jit traced inside another on its own and inside the outer
#: one's trace again, so ``trace_s`` counts what JAX's events count.
_BOOKED_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_BOOKED_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
#: the tags a span gains where it paid, and a compile-log record's sums
COMPILE_TAGS = ("trace_s", "compile_s", "cache_hits", "cache_misses")
#: owner of an event that met no ambient span (a caller's own jits, a
#: stage that opens no span); its records coalesce a second at a time
NO_SPAN = "(no span)"
_NO_SPAN_COALESCE_S = 1.0
_COMPILE_LOG_SIZE = 1024
_FIRST_CALLS_SIZE = 256


@dataclasses.dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    start: float
    end: Optional[float] = None
    status: str = "ok"
    tags: Dict[str, Any] = dataclasses.field(default_factory=dict)

    #: this instance's record in the compile log once a JAX event was booked
    #: to it; a class attribute and no field, so a span that pays nothing
    #: never sets it
    booked = None

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def to_record(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "end": self.end,
            "duration": self.duration,
            "status": self.status,
            "tags": dict(self.tags),
        }


#: wire headers a :class:`TraceContext` rides in (HTTP hop or epoch spec)
TRACE_HEADER = "X-Trace-Id"
PARENT_HEADER = "X-Parent-Span-Id"


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """A trace's identity off the wire: enough to parent a local span
    under a span minted in another process.

    ``parent_span_id`` is **qualified** as ``<process>:<span_id>`` when it
    crosses a process boundary (see :meth:`from_span`) — span-id counters
    are per-process, so the bare id alone is ambiguous in a merged fleet
    log. In-process parent ids stay bare; the history server resolves a
    bare id within the owning process first.
    """

    trace_id: str
    parent_span_id: str = ""

    def to_headers(self) -> Dict[str, str]:
        """The HTTP carrier: ``X-Trace-Id`` (+ ``X-Parent-Span-Id``)."""
        headers = {TRACE_HEADER: self.trace_id}
        if self.parent_span_id:
            headers[PARENT_HEADER] = self.parent_span_id
        return headers

    @classmethod
    def from_headers(cls, headers: Any) -> Optional["TraceContext"]:
        """Parse the carrier headers (any ``.get``-able mapping, e.g.
        ``BaseHTTPRequestHandler.headers``); None when no trace rode in."""
        if headers is None:
            return None
        trace_id = headers.get(TRACE_HEADER)
        if not trace_id:
            return None
        return cls(
            trace_id=str(trace_id),
            parent_span_id=str(headers.get(PARENT_HEADER) or ""),
        )

    @classmethod
    def from_span(cls, span: Span) -> "TraceContext":
        """The context to ship when ``span`` is the remote parent; the
        parent id is qualified with this process's event-log label."""
        from mmlspark_tpu.observability.events import process_label

        return cls(
            trace_id=span.trace_id,
            parent_span_id=f"{process_label()}:{span.span_id}",
        )

    def to_dict(self) -> Dict[str, str]:
        """JSON-able form for non-HTTP carriers (epoch specs)."""
        return {"trace_id": self.trace_id, "parent_span_id": self.parent_span_id}

    @classmethod
    def from_dict(cls, rec: Optional[Dict[str, Any]]) -> Optional["TraceContext"]:
        if not rec or not rec.get("trace_id"):
            return None
        return cls(
            trace_id=str(rec["trace_id"]),
            parent_span_id=str(rec.get("parent_span_id") or ""),
        )


class Tracer:
    """Span factory + ambient-span propagation + finished-span ring.

    ``xprof=True`` (the default) mirrors context-managed spans into
    ``core.profiling.annotate`` so device traces carry the same names;
    the bridge is skipped silently when jax is unavailable.
    """

    def __init__(self, max_spans: int = 4096, xprof: bool = True):
        self._lock = threading.Lock()
        self._trace_seq = 0
        self._span_seq = 0
        self._finished: "collections.deque[Span]" = collections.deque(
            maxlen=max_spans
        )
        self._current: "contextvars.ContextVar[Optional[Span]]" = (
            contextvars.ContextVar("mmlspark_tpu_span", default=None)
        )
        self._xprof = xprof
        # what compiling cost, by the span instance that paid (module
        # docstring): outlives ``clear`` and the ring
        self._compile_log: "collections.deque[Dict[str, Any]]" = (
            collections.deque(maxlen=_COMPILE_LOG_SIZE)
        )
        self._unowned: Optional[Dict[str, Any]] = None  # open NO_SPAN record
        self._first_calls: Dict[str, Dict[str, Any]] = {}

    # -- ids (deterministic: counters, not random) ---------------------------

    def _next_ids(self, parent: Optional[Span]) -> tuple:
        with self._lock:
            self._span_seq += 1
            span_id = f"{self._span_seq:08x}"
            if parent is not None:
                return parent.trace_id, span_id
            self._trace_seq += 1
            return f"t{self._trace_seq:08x}", span_id

    # -- ambient span --------------------------------------------------------

    def current(self) -> Optional[Span]:
        return self._current.get()

    @contextlib.contextmanager
    def attach(self, span: Optional[Span]) -> Iterator[None]:
        """Make ``span`` ambient for the body — how a worker thread joins
        a trace started elsewhere (pass the parent captured at submit)."""
        token = self._current.set(span)
        try:
            yield
        finally:
            self._current.reset(token)

    # -- manual spans (cross-thread lifecycles) ------------------------------

    def start_span(
        self,
        name: str,
        parent: Optional[Span] = None,
        context: Optional[TraceContext] = None,
        **tags: Any,
    ) -> Span:
        """Open a span without making it ambient. ``parent=None`` uses the
        ambient span; a detached root needs an explicit ``parent`` of a
        fresh trace (or no ambient span). ``context`` adopts a trace that
        arrived over the wire: the span joins the remote trace id with the
        (qualified) remote span as its parent — a local ``parent`` wins
        when both are given."""
        parent = parent if parent is not None else self.current()
        if parent is None and context is not None:
            with self._lock:
                self._span_seq += 1
                span_id = f"{self._span_seq:08x}"
            return Span(
                name=name,
                trace_id=context.trace_id,
                span_id=span_id,
                parent_id=context.parent_span_id or None,
                start=time.monotonic(),
                tags=dict(tags),
            )
        trace_id, span_id = self._next_ids(parent)
        return Span(
            name=name,
            trace_id=trace_id,
            span_id=span_id,
            parent_id=parent.span_id if parent is not None else None,
            start=time.monotonic(),
            tags=dict(tags),
        )

    def finish(self, span: Span, status: str = "ok", **tags: Any) -> Span:
        span.end = time.monotonic()
        span.status = status
        if tags:
            span.tags.update(tags)
        with self._lock:
            self._finished.append(span)
            if span.parent_id is None:
                self._note_first_call(span)
        self._publish(span)
        return span

    def _note_first_call(self, span: Span) -> None:
        # under the lock; a root span only
        if (
            span.name not in self._first_calls
            and len(self._first_calls) < _FIRST_CALLS_SIZE
        ):
            self._first_calls[span.name] = {
                "name": span.name,
                "trace_id": span.trace_id,
                "start": span.start,
                "duration": span.duration,
            }

    def _publish(self, span: Span) -> None:
        """Mirror a finished span onto the event bus (SpanRecorded) so the
        per-process event-log segments carry the span stream; free when
        nobody listens."""
        from mmlspark_tpu.observability import events as _events

        bus = _events.get_bus()
        if not bus.active:
            return
        duration = span.duration or 0.0
        bus.publish(_events.SpanRecorded(
            name=span.name,
            trace_id=span.trace_id,
            span_id=span.span_id,
            parent_id=span.parent_id or "",
            start=span.start,
            duration=duration,
            wall_start=time.time() - duration,
            status=span.status,
            tags={
                k: v for k, v in span.tags.items()
                if isinstance(v, (str, int, float, bool))
            },
        ))

    # -- context-managed spans (the common form) -----------------------------

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        parent: Optional[Span] = None,
        context: Optional[TraceContext] = None,
        **tags: Any,
    ) -> Iterator[Span]:
        """Open a span as a child of ``parent`` (default: the ambient
        span; ``context`` joins a wire-propagated trace), make it ambient
        for the body, finish it on exit (status = exception class name on
        error), and mirror the name into any active xprof trace."""
        sp = self.start_span(name, parent=parent, context=context, **tags)
        token = self._current.set(sp)
        try:
            with self._annotate(name):
                yield sp
        except BaseException as e:
            self.finish(sp, status=type(e).__name__)
            raise
        else:
            self.finish(sp)
        finally:
            self._current.reset(token)

    def _annotate(self, name: str):
        if not self._xprof:
            return contextlib.nullcontext()
        return _annotation()(name)

    # -- export --------------------------------------------------------------

    def export(self, trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
        """Finished spans as JSON-able records, oldest first; optionally
        filtered to one trace."""
        with self._lock:
            spans = list(self._finished)
        return [
            s.to_record()
            for s in spans
            if trace_id is None or s.trace_id == trace_id
        ]

    def trace_events(self, t0: float) -> List[Tuple[str, float, float]]:
        """Finished leaf spans as ``(name, start_ns, duration_ns)`` on the
        clock of a profiler session that started at ``t0`` (what
        :func:`mmlspark_tpu.core.profiling.profile_trace` yields): the
        host-event tuples a trace reduction takes, so the idle gaps of a
        trace recorded without host events still get an owner's name. A
        span with children is left out, because a reduction names a gap by
        the event that overlaps it most and a parent overlaps whatever its
        children do; so are spans that ended before the session."""
        with self._lock:
            spans = list(self._finished)
        parents = {s.parent_id for s in spans}
        return [
            (s.name, (s.start - t0) * 1e9, (s.end - s.start) * 1e9)
            for s in spans
            if s.end >= t0 and s.span_id not in parents
        ]

    def span_tree(self, trace_id: str) -> Dict[str, Any]:
        """One trace as a nested dict (children under "children"), the
        shape the acceptance check reads: request -> batch -> apply."""
        records = self.export(trace_id)
        by_id = {r["span_id"]: dict(r, children=[]) for r in records}
        roots = []
        for r in by_id.values():
            parent = by_id.get(r["parent_id"])
            if parent is not None:
                parent["children"].append(r)
            else:
                roots.append(r)
        return {"trace_id": trace_id, "roots": roots}

    def clear(self) -> None:
        """Empty the ring of finished spans. The compile log and the
        first-call table stay: they describe the process, not a window."""
        with self._lock:
            self._finished.clear()

    # -- what compiling cost, and who paid -----------------------------------

    def _book(self, key: str, amount: float) -> None:
        """Add one JAX event to the innermost ambient span, as a tag and in
        its record of the compile log; to ``NO_SPAN`` where there is none.
        JAX fires its events on the thread that traces or compiles, so the
        ambient span here is the caller's."""
        span = self._current.get()
        with self._lock:
            if span is None:
                now = time.monotonic()
                record = self._unowned
                if record is None or now - record["t"] > _NO_SPAN_COALESCE_S:
                    record = self._unowned = _compile_record(now, NO_SPAN, "")
                    self._compile_log.append(record)
                record[key] += amount
                return
            record = span.booked
            if record is None:
                record = span.booked = _compile_record(
                    span.start, span.name, span.trace_id
                )
                self._compile_log.append(record)
            span.tags[key] = record[key] = record[key] + amount

    def compile_log(self) -> List[Dict[str, Any]]:
        """What JAX's tracing, lowering, compiling and cache look-ups cost
        this process, oldest first: one record for each span instance that
        paid anything, ``{t, span, trace_id, trace_s, compile_s, cache_hits,
        cache_misses}`` with ``t`` the span's monotonic start, and
        ``NO_SPAN`` records stamped with their first event's time. Bounded
        (the newest ~thousand); only the global tracer's is ever filled."""
        with self._lock:
            return [dict(record) for record in self._compile_log]

    def first_calls(self) -> List[Dict[str, Any]]:
        """The first finished instance of every root span name in this
        process, earliest first: ``{name, trace_id, start, duration}``, a
        stage's first-call penalty. Its compile share is the compile log's
        records of that ``trace_id``."""
        with self._lock:
            calls = [dict(call) for call in self._first_calls.values()]
        return sorted(calls, key=lambda call: call["start"])


def _compile_record(t: float, span: str, trace_id: str) -> Dict[str, Any]:
    return {
        "t": t, "span": span, "trace_id": trace_id,
        "trace_s": 0.0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
    }  # COMPILE_TAGS, seconds as floats and counts as ints


_ANNOTATE = None


def _annotation():
    # cached like core/pipeline._tracer: every span enters one, and a span
    # must not pay import-machinery cost
    global _ANNOTATE
    if _ANNOTATE is None:
        try:
            from mmlspark_tpu.core.profiling import annotate
        except ImportError:  # pragma: no cover - jax is a hard dep in practice
            annotate = contextlib.nullcontext
        _listen()  # jax is imported by now, if it can be
        _ANNOTATE = annotate
    return _ANNOTATE


_TRACER = Tracer()

_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _on_seconds(event: str, secs: float, **_: Any) -> None:
    key = _BOOKED_SECONDS.get(event)
    if key:
        _TRACER._book(key, secs)


def _on_event(event: str, **_: Any) -> None:
    key = _BOOKED_COUNTS.get(event)
    if key:
        _TRACER._book(key, 1)


def _listen() -> None:
    """Register the two ``jax.monitoring`` listeners that book to the global
    tracer, once a process however often it is called. Importing the package
    must not import jax, so this runs when the module is imported after jax
    (every entry point that looks for its device first) and otherwise on the
    first context-managed span."""
    global _LISTENING
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        try:
            import jax.monitoring
        except ImportError:  # pragma: no cover - jax is a hard dep in practice
            return
        jax.monitoring.register_event_duration_secs_listener(_on_seconds)
        jax.monitoring.register_event_listener(_on_event)
        _LISTENING = True


if "jax" in sys.modules:
    _listen()


def get_tracer() -> Tracer:
    """The process-global tracer every instrumented layer shares."""
    return _TRACER
