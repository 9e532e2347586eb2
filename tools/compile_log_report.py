#!/usr/bin/env python
"""Which span paid for what one benchmark run compiled, and what the booking costs.

    python tools/compile_log_report.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``chipbench.run`` in this process with the arguments it is given (on the
chip, or with ``--dry-run-cpu`` here), then reads the program's own record,
``Tracer.compile_log()`` and ``Tracer.first_calls()``, and prints after the
run's result line one JSON line ``{"compile_log_report": ...}``:

- ``setup`` and ``later``: the log by owner span on either side of the window's
  start (the first span in the ring the harness cleared there), each owner's
  ``trace_s``, ``compile_s``, ``cache_hits``, ``cache_misses`` and how many
  span instances paid. ``(no span)`` before the window is the driver's own
  weight and data programs, after it the plain reference's;
- ``first_calls``: the first finished instance of every root span;
- ``events``: how many ``jax.monitoring`` events JAX fired before the window
  and how many of them the tracer books (a listener of this script's counts);
- ``cost_us``: one booked event with and without an ambient span, and one span
  that books nothing, timed on a tracer of the script's own so that the
  report above is not touched.

For a cold set-up point ``JAX_COMPILATION_CACHE_DIR`` at an empty directory.
"""

import json
import os
import sys
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def by_owner(records, tags):
    owners = {}
    for record in records:
        row = owners.setdefault(record["span"], dict.fromkeys(tags, 0) | {"instances": 0})
        row["instances"] += 1
        for key in tags:
            row[key] += record[key]
    return owners


def cost_us(tracing, number=20000):
    tracer = tracing.Tracer(xprof=False)

    def book_one():
        tracer._book("trace_s", 1e-6)

    def empty_span():
        with tracer.span("report.empty"):
            pass

    each = {}
    each["event_no_span"] = timeit.timeit(book_one, number=number) / number * 1e6
    with tracer.span("report.owner"):
        each["event_in_span"] = timeit.timeit(book_one, number=number) / number * 1e6
    each["span_that_books_nothing"] = timeit.timeit(empty_span, number=number) / number * 1e6

    def global_empty_span():  # the annotation bridge on, as the program's spans have it
        with tracing.get_tracer().span("report.empty"):
            pass

    each["span_that_books_nothing_annotated"] = (
        timeit.timeit(global_empty_span, number=number) / number * 1e6)
    return each


def main(argv) -> int:
    from chipbench import run  # first: the harness counts set-up from its import

    import jax.monitoring

    from mmlspark_tpu.observability import tracing

    fired = []  # (monotonic time, event)
    jax.monitoring.register_event_listener(lambda event, **_: fired.append((time.monotonic(), event)))
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: fired.append((time.monotonic(), event)))

    code = run.main(argv)
    tracer = tracing.get_tracer()
    ring = tracer.export()
    start = min(s["start"] for s in ring)
    log = tracer.compile_log()
    booked = set(tracing._BOOKED_SECONDS) | set(tracing._BOOKED_COUNTS)
    before = [event for t, event in fired if t < start]
    report = {
        "setup": by_owner([r for r in log if r["t"] < start], tracing.COMPILE_TAGS),
        "later": by_owner([r for r in log if r["t"] >= start], tracing.COMPILE_TAGS),
        "first_calls": tracer.first_calls(),
        "events": {"before_window": len(before),
                   "booked_before_window": sum(e in booked for e in before),
                   "all": len(fired), "log_records": len(log)},
        "cost_us": cost_us(tracing),
    }
    print(json.dumps({"compile_log_report": report}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
