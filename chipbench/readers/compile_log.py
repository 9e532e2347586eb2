"""What the program's own tracer booked of JAX's trace, lowering, compile and
cache events (``Tracer.compile_log``, ``Tracer.first_calls`` and the span tags
``trace_s`` / ``compile_s``), one of three ways:

- ``setup``: the sum of those keys over every record of the log before the
  window, ``(no span)`` included. The window starts with the first of
  ``ctx["spans"]`` (the harness clears the ring there), on the clock of the
  log's ``t``;
- ``window``: the sum of those tags over ``ctx["spans"]``, 0.0 where the
  window's spans paid nothing;
- ``first_call``: the duration of the earliest first call among those root
  spans, a cell's warm-up job.

A reader's ``ctx`` does not hold the tracer, so this one asks the program for
it. A program that books nothing (no ``compile_log``), a window with no span
and a log with no record before it all read as nothing."""


def read(ctx, setup=None, window=None, first_call=None):
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    if not hasattr(tracer, "compile_log") or not ctx["spans"]:
        return None
    if window:
        return float(sum(s["tags"].get(key, 0.0) for s in ctx["spans"] for key in window))
    start = min(s["start"] for s in ctx["spans"])
    if first_call:
        calls = [c for c in tracer.first_calls()
                 if c["name"] in first_call and c["start"] < start]
        return calls[0]["duration"] if calls else None
    before = [r for r in tracer.compile_log() if r["t"] < start]
    return float(sum(r[key] for r in before for key in setup)) if before else None
