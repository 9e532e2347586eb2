"""The whole step's share of the chip's peak: work a job needs, counted from
shapes by the driver's ``work``, times jobs, over all the window's time."""


def read(ctx, bound: str, peak: str):
    if not ctx["jobs"] or not ctx["work"].get(bound):
        return None
    rate = ctx["work"][bound] * ctx["jobs"] / ctx["window_s"]
    return 100.0 * rate / ctx["peaks"][peak]
