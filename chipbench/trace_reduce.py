"""From a profiler trace (.xplane.pb) to device busy and idle time, the
device operations that took most time, and the idle gaps by what the host
was doing in them.

The arithmetic works on plain tuples ``(name, start_ns, duration_ns)`` so it
can be checked exactly on a small recorded trace; ``load`` is the only part
that touches the file format (through ``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"  # one event per executed HLO op, children nested
WINDOW_SPAN = "chipbench.traced_job"  # the harness's own host annotation
LONGEST_GAPS = 64  # gaps given an owner; the rest are summed as short gaps
_HLO = re.compile(r"^%?([\w.\-]+) = (\w+\[[\d,]*\])")


def short_name(name: str) -> str:
    """The trace names a device op by its whole HLO line; keep the op's name
    and its result's shape."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, cpu_as_device: bool = False):
    """-> ({device plane: [event]}, [host event], {plane: [line names]}).
    ``cpu_as_device`` is for a dry run only: the CPU backend has no device
    plane, so its XLA worker threads stand in for one."""
    from jax.profiler import ProfileData

    devices, host, seen = {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        seen[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith(DEVICE_PLANE):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            devices[plane.name] = [
                (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                for ln in ops for e in ln.events
            ]
        elif plane.name == HOST_PLANE:
            if cpu_as_device:
                devices["cpu"] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for ln in lines if ln.name.startswith("tf_XLA")
                    for e in ln.events if e.duration_ns > 0
                ]
            host += [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for ln in lines for e in ln.events if e.duration_ns > 0
            ]
    return devices, host, seen


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def self_times(events):
    """{name: ns} with each event's time less what its nested children
    cover: a scanned program is one ``while`` op around all its steps, and
    the table should name the steps."""
    totals = collections.defaultdict(float)
    stack = []  # [name, end, remaining self time]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] += max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:  # only the part inside the parent is the parent's child
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return dict(totals)


def _gap_owners(gaps, host):
    """For each gap the host span that covers most of it; the shortest such
    span when several cover it alike (the innermost one)."""
    host = [h for h in host if h[0] != WINDOW_SPAN]
    if not host:
        return ["(no host span)"] * len(gaps)
    start = np.array([h[1] for h in host])
    dur = np.array([h[2] for h in host])
    out = []
    for a, b in gaps:
        overlap = np.minimum(b, start + dur) - np.maximum(a, start)
        most = overlap.max()
        if most <= 0:
            out.append("(no host span)")
            continue
        ties = np.flatnonzero(overlap == most)
        out.append(host[ties[np.argmin(dur[ties])]][0])
    return out


def reduce(devices, host, top: int = 10, job_ns: float = 0.0) -> dict:
    """Busy seconds (union of device-op intervals inside the window, mean
    over the device planes that ran anything), the window's seconds, and the
    two tables. The window is the harness's ``WINDOW_SPAN`` host annotation
    when the trace has it. A trace taken without host events has none: the
    window is then ``job_ns`` long (the traced job by the host's clock, the
    trace having started with it) and ends no earlier than the last op."""
    used = {k: v for k, v in devices.items() if v}
    if not used:
        raise ValueError("the trace holds no device operation")
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if spans:
        w0, w1 = min(a for a, _ in spans), max(b for _, b in spans)
    else:
        first = min(s for ev in used.values() for _, s, _ in ev)
        last = max(s + d for ev in used.values() for _, s, d in ev)
        w0 = min(first, 0.0)  # a trace's clock starts with the trace
        w1 = max(last, w0 + job_ns)
    busy_ns, ops, gaps = 0.0, collections.defaultdict(float), collections.defaultdict(float)
    for events in used.values():
        merged = union(
            (max(s, w0), min(s + d, w1)) for _, s, d in events
            if s + d > w0 and s < w1 and d > 0
        )
        busy_ns += sum(b - a for a, b in merged)
        for name, ns in self_times(events).items():
            ops[name] += ns
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = sorted(
            ((a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a),
            key=lambda g: g[0] - g[1],
        )
        longest = idle[:LONGEST_GAPS]
        for (a, b), owner in zip(longest, _gap_owners(longest, host)):
            gaps[owner] += b - a
        if idle[LONGEST_GAPS:]:
            gaps["(short gaps)"] += sum(b - a for a, b in idle[LONGEST_GAPS:])
    n = len(used)

    def table(d):
        rows = sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
        return [[name, ns / n / 1e9] for name, ns in rows]

    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": table(ops),
        "idle_gaps": table(gaps),
    }
