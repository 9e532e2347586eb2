"""Writes tests/chipbench/fixtures/two_ops_one_gap.xplane.pb: the smallest
trace that exercises every rule of chipbench.trace_reduce. Hand-encoded
protobuf (tsl/profiler/protobuf/xplane.proto), so no writer library is
needed:

    device /device:TPU:0, line "XLA Ops", times in ns from 0
        while.1   [1000, 5000)   encloses fusion.2
        fusion.2  [2000, 4000)
        copy.3    [4500, 6000)   overlaps while.1's tail
        fusion.2  [8000, 9000)
    host /host:CPU
        chipbench.traced_job [0, 10000)   the window
        lightgbm.binning     [6100, 7900) the span over the gap [6000, 8000)

busy = [1000, 6000) + [8000, 9000) = 6000 ns of a 10000 ns window; self times
while.1 1500 (less fusion.2 and the 500 ns of copy.3 inside it), fusion.2
3000, copy.3 1500; gaps 2000 under lightgbm.binning, 2000 under no span.
"""

from __future__ import annotations

import os

PATH = os.path.join(os.path.dirname(__file__), "fixtures", "two_ops_one_gap.xplane.pb")


def _varint(n: int) -> bytes:
    out = b""
    while True:
        byte, n = n & 0x7F, n >> 7
        out += bytes([byte | (0x80 if n else 0)])
        if not n:
            return out


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(plane_id: int, name: str, line_name: str, events) -> bytes:
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    line = _int(1, 1) + _bytes(2, line_name.encode()) + _int(3, 0)
    for n, start_ns, dur_ns in events:
        line += _bytes(4, _int(1, ids[n]) + _int(2, start_ns * 1000) + _int(3, dur_ns * 1000))
    out = _int(1, plane_id) + _bytes(2, name.encode()) + _bytes(3, line)
    for n, i in ids.items():
        meta = _int(1, i) + _bytes(2, n.encode())
        out += _bytes(4, _int(1, i) + _bytes(2, meta))
    return out


DEVICE_EVENTS = [
    ("while.1", 1000, 4000), ("fusion.2", 2000, 2000),
    ("copy.3", 4500, 1500), ("fusion.2", 8000, 1000),
]
HOST_EVENTS = [("chipbench.traced_job", 0, 10000), ("lightgbm.binning", 6100, 1800)]


def encode() -> bytes:
    return (
        _bytes(1, _plane(1, "/device:TPU:0", "XLA Ops", DEVICE_EVENTS))
        + _bytes(1, _plane(2, "/host:CPU", "python3", HOST_EVENTS))
    )


if __name__ == "__main__":
    with open(PATH, "wb") as f:
        f.write(encode())
    print(PATH, len(encode()), "bytes")
