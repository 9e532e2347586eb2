"""What the program's spans counted, a job: the sum of a tag over the spans
whose name starts with one of ``spans`` (a whole name, or a prefix that ends
in a dot), every tag whose own name starts with ``tag``, over the window's
jobs and ``scale``. A span without the tag adds nothing."""


def read(ctx, spans, tag: str, scale: float = 1.0):
    counted = [value for s in ctx["spans"] if s["name"].startswith(tuple(spans))
               for key, value in s["tags"].items() if key.startswith(tag)]
    return sum(counted) / ctx["jobs"] / scale if counted and ctx["jobs"] else None
