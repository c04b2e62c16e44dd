"""Writes ``tiny.xplane.pb``: a hand-made profiler trace small enough to reduce
by hand.  The XSpace message (tsl/profiler/protobuf/xplane.proto) is encoded
here field by field, so the fixture needs no protobuf module to make and only
``jax.profiler.ProfileData`` to read.  Run it again only to change the
fixture; the expected numbers are in test_benchmark_trace.py.

All times in ns from the session's start.  The clock-sync annotation sits at
1,000 ns and says the host's perf_counter read 5,001,000 then, so the host
clock is the trace's plus 5,000,000.

  /device:TPU:0  XLA Ops      fusion.1 [10_000, 14_000)  copy.2 [13_000, 20_000)
                              fusion.1 [40_000, 45_000)
                 XLA Modules  jit_local_fn [10_000, 20_000)  jit_local_fn [40_000, 45_000)
  /device:TPU:1  XLA Ops      fusion.1 [30_000, 50_000)
"""

import os
import struct


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, wire: int, payload: bytes) -> bytes:
    return varint(num << 3 | wire) + payload


def vint(num: int, value: int) -> bytes:
    return field(num, 0, varint(value))


def blob(num: int, payload: bytes) -> bytes:
    return field(num, 2, varint(len(payload)) + payload)


def event(metadata_id: int, start_ns: int, end_ns: int, stats: bytes = b"") -> bytes:
    # XEvent: metadata_id=1, offset_ps=2, duration_ps=3, stats=4
    return vint(1, metadata_id) + vint(2, start_ns * 1000) + vint(3, (end_ns - start_ns) * 1000) + stats


def line(line_id: int, name: str, events) -> bytes:
    # XLine: id=1, name=2, timestamp_ns=3, events=4
    body = vint(1, line_id) + blob(2, name.encode()) + vint(3, 0)
    return body + b"".join(blob(4, e) for e in events)


def plane(plane_id: int, name: str, lines, event_names, stat_names=()) -> bytes:
    # XPlane: id=1, name=2, lines=3, event_metadata=4 (map), stat_metadata=5 (map)
    body = vint(1, plane_id) + blob(2, name.encode()) + b"".join(blob(3, ln) for ln in lines)
    for i, n in enumerate(event_names, start=1):
        meta = vint(1, i) + blob(2, n.encode())  # XEventMetadata: id=1, name=2
        body += blob(4, vint(1, i) + blob(2, meta))
    for i, n in enumerate(stat_names, start=1):
        meta = vint(1, i) + blob(2, n.encode())  # XStatMetadata: id=1, name=2
        body += blob(5, vint(1, i) + blob(2, meta))
    return body


def main() -> None:
    names = ["fusion.1", "copy.2", "jit_local_fn"]
    tpu0 = plane(1, "/device:TPU:0", [
        line(1, "XLA Ops", [event(1, 10_000, 14_000), event(2, 13_000, 20_000), event(1, 40_000, 45_000)]),
        line(2, "XLA Modules", [event(3, 10_000, 20_000), event(3, 40_000, 45_000)]),
    ], names)
    tpu1 = plane(2, "/device:TPU:1", [line(1, "XLA Ops", [event(1, 30_000, 50_000)])], names)
    # XStat: metadata_id=1, uint64_value=3
    sync_stat = blob(4, vint(1, 1) + vint(3, 5_001_000))
    host = plane(3, "/host:CPU", [
        line(7, "python", [event(1, 1_000, 1_100, sync_stat)]),
    ], ["bench.clock_sync"], ["perf_counter_ns"])
    space = b"".join(blob(1, p) for p in (tpu0, tpu1, host))  # XSpace: planes=1
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny.xplane.pb"), "wb") as f:
        f.write(space)


if __name__ == "__main__":
    main()
