#!/usr/bin/env python
"""Generate the golden wire-format fixtures for the JVM shim protocol.

Each fixture is the exact request frame the Java DaemonClient puts on the wire
(jvm/src/.../DaemonClient.java header builders; frame layout
docs/SHIM_PROTOCOL.md).  Three parties assert against these bytes:

* ``jvm/src/.../FixtureCheck.java`` re-encodes every frame with the Java
  builders and compares (run by CI after javac);
* ``tests/test_daemon.py`` regenerates them here (drift guard) and feeds the
  raw bytes to a live daemon (decode interop; 11, the several-block
  WritePartition, in a replay of its own; 12, the landing's offer, in
  ``tests/test_daemon_mapped_landing.py``);
* a human diffing a protocol change sees exactly which bytes moved.

Java's String.format JSON headers and Python's ``json.dumps`` agree
byte-for-byte (same key order, ", "/": " separators) — that equality is the
drift guard's whole point.

Usage: python scripts/gen_shim_fixtures.py [--check]
"""

import json
import os
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparkucx_tpu.core.definitions import AmId, MAX_FRAME_BYTES  # noqa: E402
from sparkucx_tpu.shuffle.daemon import DaemonOp, _frame  # noqa: E402

FIXTURE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "jvm", "fixtures")

# Canonical parameters — FixtureCheck.java uses the same literals.
SHUFFLE_ID, NUM_MAPPERS, NUM_REDUCERS = 7, 4, 8
MAP_ID, WRITER, REDUCE_ID = 2, 3, 5
FETCH_TAG = 0x1122334455667788
FETCH_MAPS, FETCH_REDUCES = (0, 3), (5, 5)
WRITE_BODY = bytes(range(256))


#: 08: a Spark-3.x AQE partial-map read (startMapIndex=1, endMapIndex=3 over
#: one reduce partition).  Spark 2.4 (no AQE) always reads the full map range;
#: both generations land on the SAME wire shape — explicit (shuffle, mapIndex,
#: reduce) triples, the client enumerating its range — so the fixture pins
#: that the protocol is compat-generation-agnostic (jvm/README.md, "Spark 2.4
#: vs 3.x").
AQE_MAPS, AQE_REDUCES = (1, 2), (REDUCE_ID, REDUCE_ID)

#: 09: an AQE COALESCED read — one reducer task reading a coalesced range of
#: reduce partitions (5..6) across EVERY mapper (0..3), the
#: ShufflePartitionSpec shape AQE emits after coalescing small partitions.
#: Some of these (map, reduce) cells are legitimately empty in the behavioral
#: replay (tests/test_daemon.py) — the daemon must answer size 0, never -1.
COALESCE_MAPS = tuple(m for m in range(NUM_MAPPERS) for _ in (5, 6))
COALESCE_REDUCES = tuple(r for _ in range(NUM_MAPPERS) for r in (5, 6))

#: 10: an OVERSIZED frame header — op WritePartition claiming a body one byte
#: past MAX_FRAME_BYTES.  Negative fixture: both sides must REFUSE it
#: (FixtureCheck.java asserts the Java limit matches and rejects; the daemon
#: drops the connection and keeps serving — tests/test_daemon.py).
OVERSIZED_HEADER = struct.pack(
    "<IQQ", DaemonOp.WRITE_PARTITION, 0, MAX_FRAME_BYTES + 1
)


#: 11: SEVERAL BLOCKS of one writer in one WritePartition frame (the second
#: header form): reduce 5 in two entries (one partition continued, as repeated
#: frames continue it), an empty block, then reduce 6 — what
#: TpuShuffleWriter.java ships its buckets in and what the Python
#: ``DaemonClient.write_partition`` sends when its batch is full or the map
#: commits.  Replayed against a live daemon in tests/test_daemon.py.
BATCH_REDUCE_IDS = (1, 5, 5, 6)
BATCH_BODIES = (bytes(range(16)), WRITE_BODY, b"", bytes(range(255, 223, -1)))


#: 12: a same-host client's OFFER of a landing for its fetch replies (PR 60):
#: the name of a file under /dev/shm the client made and mapped, and its
#: capacity.  Only the Python ``DaemonClient`` sends it; the Java client does
#: not map shared memory and stays on the socket (jvm/README.md), so
#: FixtureCheck.java has no builder for it — the fixture pins the frame for
#: the day it does.  Replayed raw in tests/test_daemon_mapped_landing.py
#: (nobody made that name: the daemon refuses it and keeps the connection).
LANDING_NAME = "sparkucx-landing-00112233445566778899aabbccddeeff"
LANDING_CAPACITY = 1 << 20


def fetch_frame(maps=FETCH_MAPS, reduces=FETCH_REDUCES) -> bytes:
    body = struct.pack("<QI", FETCH_TAG, len(maps))
    for m, r in zip(maps, reduces):
        body += struct.pack("<iii", SHUFFLE_ID, m, r)
    return struct.pack("<IQQ", int(AmId.FETCH_BLOCK_REQ), 0, len(body)) + body


def fixtures() -> dict:
    return {
        "01_create_shuffle.bin": _frame(
            DaemonOp.CREATE_SHUFFLE,
            {"shuffle_id": SHUFFLE_ID, "num_mappers": NUM_MAPPERS, "num_reducers": NUM_REDUCERS},
        ),
        "02_open_map_writer.bin": _frame(
            DaemonOp.OPEN_MAP_WRITER, {"shuffle_id": SHUFFLE_ID, "map_id": MAP_ID}
        ),
        "03_write_partition.bin": _frame(
            DaemonOp.WRITE_PARTITION, {"writer": WRITER, "reduce_id": REDUCE_ID}, WRITE_BODY
        ),
        "04_commit_map.bin": _frame(DaemonOp.COMMIT_MAP, {"writer": WRITER}),
        "05_run_exchange.bin": _frame(DaemonOp.RUN_EXCHANGE, {"shuffle_id": SHUFFLE_ID}),
        "06_fetch.bin": fetch_frame(),
        "07_remove_shuffle.bin": _frame(DaemonOp.REMOVE_SHUFFLE, {"shuffle_id": SHUFFLE_ID}),
        "08_fetch_aqe_maprange.bin": fetch_frame(AQE_MAPS, AQE_REDUCES),
        "09_fetch_coalesced_empty.bin": fetch_frame(COALESCE_MAPS, COALESCE_REDUCES),
        "10_oversized_frame.bin": OVERSIZED_HEADER,
        "11_write_partitions.bin": _frame(
            DaemonOp.WRITE_PARTITION,
            {"writer": WRITER, "reduce_ids": list(BATCH_REDUCE_IDS), "lengths": [len(b) for b in BATCH_BODIES]},
            b"".join(BATCH_BODIES),
        ),
        "12_offer_landing.bin": _frame(
            DaemonOp.OFFER_LANDING, {"name": LANDING_NAME, "capacity": LANDING_CAPACITY}
        ),
    }


def main() -> int:
    check = "--check" in sys.argv
    os.makedirs(FIXTURE_DIR, exist_ok=True)
    ok = True
    for name, frame in fixtures().items():
        path = os.path.join(FIXTURE_DIR, name)
        if check:
            with open(path, "rb") as f:
                if f.read() != frame:
                    print(f"DRIFT: {name}", file=sys.stderr)
                    ok = False
        else:
            with open(path, "wb") as f:
                f.write(frame)
            print(f"wrote {path} ({len(frame)} B)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
