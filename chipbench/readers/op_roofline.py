"""A kernel's share of the chip's peak while it runs: the operations a job
needs of it, counted from shapes by the driver's ``work`` (whatever
implements them), over the device seconds of its operations in the traced
job, over the peak.

``trace["device_ops"]`` holds the traced job's longest operations by HLO
name. ``pattern`` is the regular expression the chip's trace gives the
kernel's operations (PERF.md says where each was read), and ``rows`` how
many of them the kernel is made of. Fewer matching rows mean part of the
kernel's time is not in the table, and a share taken over part of the time
would read high: then there is nothing to read."""

import re


def read(ctx, work: str, pattern: str, rows: int, peak: str):
    trace, needed = ctx["trace"], ctx["work"].get(work)
    if not trace or not needed:
        return None
    took = [secs for name, secs in trace["device_ops"] if re.search(pattern, name)]
    if len(took) < rows or not sum(took):
        return None
    return 100.0 * needed / sum(took) / ctx["peaks"][peak]
