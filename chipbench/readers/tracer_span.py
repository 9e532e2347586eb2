"""Mean milliseconds of one of the program's own spans over the window."""


def read(ctx, span: str):
    took = [s["duration"] for s in ctx["spans"]
            if s["name"] == span and s["duration"] is not None]
    return 1000.0 * sum(took) / len(took) if took else None
