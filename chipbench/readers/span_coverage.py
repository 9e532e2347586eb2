"""Share of a root span's time that the listed spans beneath it account for.
List spans that do not overlap (the leaves): what is left is host time inside
the job that no span owns."""


def read(ctx, root: str, children):
    def seconds(names):
        return sum(s["duration"] for s in ctx["spans"]
                   if s["name"] in names and s["duration"] is not None)

    whole = seconds({root})
    return 100.0 * seconds(set(children)) / whole if whole else None
