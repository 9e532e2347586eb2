"""One tag of the program's spans over another, times ``scale``: both summed
over every span of that name in the window. A span that lacks either tag
adds nothing; nothing to divide by reads as nothing."""


def read(ctx, span: str, numerator: str, denominator: str, scale: float = 1.0):
    tags = [s["tags"] for s in ctx["spans"] if s["name"] == span
            and numerator in s["tags"] and denominator in s["tags"]]
    below = sum(t[denominator] for t in tags)
    return scale * sum(t[numerator] for t in tags) / below if below else None
