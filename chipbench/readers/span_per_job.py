"""Milliseconds a job spends in the program's spans of one name: the sum of
their durations over the window's jobs (``tracer_span`` gives the mean of
one occurrence, wrong for a span a job records once a batch)."""


def read(ctx, span: str):
    took = [s["duration"] for s in ctx["spans"]
            if s["name"] == span and s["duration"] is not None]
    return 1000.0 * sum(took) / ctx["jobs"] if took and ctx["jobs"] else None
