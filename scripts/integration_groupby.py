#!/usr/bin/env python
"""GroupByTest-style integration driver — the ``buildlib/test.sh`` workload analogue.

The reference's integration gate runs stock Spark examples (GroupByTest, SparkTC)
on a real 2-executor standalone cluster (test.sh:163-179).  This driver runs the
same shape against the real process topology of this framework:

1. start the shuffle daemon (the TPU runtime process),
2. spawn EXECUTORS separate *mapper processes*, each writing its map tasks'
   partitioned (key, value) records over the daemon wire protocol,
3. run the collective exchange,
4. spawn separate *reducer processes* that fetch, aggregate, and report per-key
   sums,
5. verify the union of reducer outputs against a single-process oracle.

Exit code 0 = pass.  Knobs via env (test.sh style): EXECUTORS, MAPPERS,
REDUCERS, PAIRS_PER_MAP.

``FAULTS=1`` adds OS-process fault injection (recovery the reference never had
— SURVEY.md section 5.3: a failed UCX send just logs; no retry anywhere):

* executor 0's mapper is first run as a *crashing attempt*: it fully commits
  its first map task, half-writes the next one, and SIGKILLs itself
  mid-write.  The retry attempt then rewrites ALL its maps — with a poisoned
  record added to the already-committed map.  First-commit-wins over the wire
  (IndexShuffleBlockResolver.scala:161-217 semantics at the daemon boundary)
  means the poison must be discarded; it appearing in any reducer's output
  fails the oracle check.  The half-written map's bytes must vanish entirely
  (its partition stream never closed, so nothing was ever recorded).
* one reducer process is SIGKILLed after fetching a prefix of its partitions
  and a fresh process re-runs the same partitions — post-exchange fetches are
  idempotent reads of the daemon's received shards, so the retry must see
  exactly the same bytes.
"""

import json
import os
import pickle
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXECUTORS = int(os.environ.get("EXECUTORS", "2"))
MAPPERS = int(os.environ.get("MAPPERS", "4"))
REDUCERS = int(os.environ.get("REDUCERS", "8"))
PAIRS = int(os.environ.get("PAIRS_PER_MAP", "5000"))
FAULTS = os.environ.get("FAULTS", "") == "1"
SHUFFLE_ID = 0
POISON_KEY = 10**6  # far outside the 0..99 key space; must never surface

MAPPER_SCRIPT = """
import os, pickle, sys
sys.path.insert(0, {root!r})
from sparkucx_tpu.shuffle.daemon import DaemonClient
from sparkucx_tpu.shuffle.reader import serialize_records
import numpy as np

host, port, map_ids = sys.argv[1], int(sys.argv[2]), [int(x) for x in sys.argv[3].split(",")]
R, PAIRS = int(sys.argv[4]), int(sys.argv[5])
# maps whose writes this (retry) attempt poisons: if first-commit-wins fails
# to discard them over the wire, the poison key reaches a reducer and the
# driver's oracle check fails
poison = [int(x) for x in sys.argv[6].split(",") if x] if len(sys.argv) > 6 else []
client = DaemonClient((host, port))
for m in map_ids:
    rng = np.random.default_rng(1000 + m)  # deterministic per map (oracle twin)
    keys = rng.integers(0, 100, size=PAIRS)
    parts = keys % R
    w = client.open_map_writer({sid}, m)
    for r in np.unique(parts):
        recs = [(int(k), 1) for k in keys[parts == r]]
        if m in poison:
            recs.append(({poison_key}, 10**9))
        client.write_partition(w, int(r), serialize_records(recs))
    client.commit_map(w)
client.close()
print("mapper done", map_ids)
"""

CRASHING_MAPPER_SCRIPT = """
import os, signal, sys
sys.path.insert(0, {root!r})
from sparkucx_tpu.shuffle.daemon import DaemonClient
from sparkucx_tpu.shuffle.reader import serialize_records
import numpy as np

host, port, map_ids = sys.argv[1], int(sys.argv[2]), [int(x) for x in sys.argv[3].split(",")]
R, PAIRS = int(sys.argv[4]), int(sys.argv[5])
client = DaemonClient((host, port))
# 1. first map: full, committed — attempt 1 wins it
m = map_ids[0]
rng = np.random.default_rng(1000 + m)
keys = rng.integers(0, 100, size=PAIRS)
parts = keys % R
w = client.open_map_writer({sid}, m)
for r in np.unique(parts):
    client.write_partition(
        w, int(r), serialize_records((int(k), 1) for k in keys[parts == r]))
client.commit_map(w)
# 2. second map: half-write garbage into one partition stream, never close it,
#    then die hard mid-task (kill -9: no atexit, no socket shutdown handshake)
m2 = map_ids[1]
w2 = client.open_map_writer({sid}, m2)
client.write_partition(w2, 0, b"GARBAGE-HALF-WRITTEN" * 50)
client.flush()  # a held block would die with this process unsent: the garbage has to reach staging
print("crashing mapper: committed", m, "dying inside", m2, flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""

REDUCER_SCRIPT = """
import json, os, pickle, signal, sys
sys.path.insert(0, {root!r})
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.shuffle.daemon import DaemonClient
from sparkucx_tpu.shuffle.reader import default_deserializer

host, port = sys.argv[1], int(sys.argv[2])
partitions = [int(x) for x in sys.argv[3].split(",")]
M = int(sys.argv[4])
# die hard after fetching this many partitions (fault injection; 0 = never)
fault_after = int(sys.argv[5]) if len(sys.argv) > 5 else 0
client = DaemonClient((host, port))
counts = {{}}
for i, r in enumerate(partitions):
    if fault_after and i >= fault_after:
        print("crashing reducer: dying after", i, "partitions", flush=True)
        os.kill(os.getpid(), signal.SIGKILL)
    blocks = client.fetch_blocks([ShuffleBlockId({sid}, m, r) for m in range(M)])
    for blk in blocks:
        if not blk:
            continue
        for k, v in default_deserializer(blk):
            counts[k] = counts.get(k, 0) + v
client.close()
print("REDUCER_RESULT " + json.dumps(counts))
"""


def oracle():
    import numpy as np

    total = np.zeros(100, dtype=np.int64)
    for m in range(MAPPERS):
        rng = np.random.default_rng(1000 + m)
        total += np.bincount(rng.integers(0, 100, size=PAIRS), minlength=100)
    return {k: int(v) for k, v in enumerate(total) if v}


def main() -> int:
    t0 = time.monotonic()
    env = dict(os.environ)
    daemon = subprocess.Popen(
        [sys.executable, "-m", "sparkucx_tpu.shuffle.daemon", "--port", "0",
         "--executors", str(EXECUTORS)],
        stdout=subprocess.PIPE, text=True, cwd=ROOT, env=env,
    )
    try:
        deadline = time.monotonic() + 120
        host = port = None
        while time.monotonic() < deadline:
            line = daemon.stdout.readline().strip()
            if "shuffle daemon on " in line:
                host, port = line.rsplit(" ", 1)[-1].split(":")
                break
        if host is None:
            print("[integration] FAIL: daemon did not report its address")
            return 1
        print(f"[integration] daemon on {host}:{port}")

        from sparkucx_tpu.shuffle.daemon import DaemonClient

        ctl = DaemonClient((host, int(port)))
        ctl.create_shuffle(SHUFFLE_ID, MAPPERS, REDUCERS)

        # Fault phase A (FAULTS=1): executor 0's mapper crashes mid-task —
        # first map committed, second map half-written, then SIGKILL.
        if FAULTS:
            mine0 = [str(m) for m in range(MAPPERS) if m % EXECUTORS == 0]
            if len(mine0) < 2:
                print("[integration] FAIL: FAULTS=1 needs >= 2 maps on executor 0")
                return 1
            crash = subprocess.Popen(
                [sys.executable, "-c",
                 CRASHING_MAPPER_SCRIPT.format(root=ROOT, sid=SHUFFLE_ID),
                 host, port, ",".join(mine0), str(REDUCERS), str(PAIRS)],
                cwd=ROOT, env=env,
            )
            rc = crash.wait(timeout=300)
            if rc == 0:
                print("[integration] FAIL: crashing mapper did not crash")
                return 1
            print(f"[integration] fault A: mapper SIGKILLed mid-write (rc={rc}); retrying")

        # mapper processes (maps split round-robin over executor processes);
        # under FAULTS, executor 0 is the RETRY attempt and poisons the map the
        # crashed attempt already committed — first-commit-wins must discard it
        mappers = []
        for e in range(EXECUTORS):
            mine = [str(m) for m in range(MAPPERS) if m % EXECUTORS == e]
            if not mine:
                continue
            script = MAPPER_SCRIPT.format(root=ROOT, sid=SHUFFLE_ID, poison_key=POISON_KEY)
            argv = [sys.executable, "-c", script, host, port, ",".join(mine),
                    str(REDUCERS), str(PAIRS)]
            if FAULTS and e == 0:
                argv.append(mine[0])  # poison the committed map's retry writes
            mappers.append(subprocess.Popen(argv, cwd=ROOT, env=env))
        for p in mappers:
            if p.wait(timeout=300) != 0:
                print("[integration] FAIL: mapper exited nonzero")
                return 1

        ctl.run_exchange(SHUFFLE_ID)
        print("[integration] exchange complete")

        # Fault phase B (FAULTS=1): one reducer dies after fetching half its
        # partitions; a fresh process re-runs the SAME partitions — the
        # post-exchange fetch is an idempotent read, so the retry sees
        # identical bytes and the oracle check stays exact.
        script = REDUCER_SCRIPT.format(root=ROOT, sid=SHUFFLE_ID)
        per = -(-REDUCERS // EXECUTORS)
        if FAULTS:
            mine0 = [str(r) for r in range(0, min(per, REDUCERS))]
            if len(mine0) < 2:
                # fault_after=max(1, 0)=1 would let a 1-partition reducer
                # finish before the kill fires — a config artifact, not a pass
                print("[integration] FAIL: FAULTS=1 needs >= 2 reduce partitions "
                      "on the faulted reducer (raise REDUCERS or lower EXECUTORS)")
                return 1
            crash = subprocess.Popen(
                [sys.executable, "-c", script, host, port, ",".join(mine0),
                 str(MAPPERS), str(max(1, len(mine0) // 2))],
                stdout=subprocess.PIPE, text=True, cwd=ROOT, env=env,
            )
            out, _ = crash.communicate(timeout=300)
            if crash.returncode == 0 or any(
                line.startswith("REDUCER_RESULT ") for line in out.splitlines()
            ):
                print("[integration] FAIL: crashing reducer did not crash")
                return 1
            print(f"[integration] fault B: reducer SIGKILLed mid-fetch "
                  f"(rc={crash.returncode}); re-running its partitions")

        # reducer processes (partitions split contiguously like peer ranges)
        reducers = []
        for e in range(EXECUTORS):
            mine = [str(r) for r in range(e * per, min((e + 1) * per, REDUCERS))]
            if not mine:
                continue
            reducers.append(subprocess.Popen(
                [sys.executable, "-c", script, host, port, ",".join(mine), str(MAPPERS)],
                stdout=subprocess.PIPE, text=True, cwd=ROOT, env=env,
            ))
        got = {}
        for p in reducers:
            out, _ = p.communicate(timeout=300)
            if p.returncode != 0:
                print("[integration] FAIL: reducer exited nonzero")
                return 1
            for line in out.splitlines():
                if line.startswith("REDUCER_RESULT "):
                    for k, v in json.loads(line[len("REDUCER_RESULT "):]).items():
                        got[int(k)] = got.get(int(k), 0) + v

        expected = oracle()
        if FAULTS and POISON_KEY in got:
            print("[integration] FAIL: poisoned retry write of a committed map "
                  "surfaced — first-commit-wins discard broken over the wire")
            return 1
        if got != expected:
            missing = {k: v for k, v in expected.items() if got.get(k) != v}
            print(f"[integration] FAIL: result mismatch ({len(missing)} keys differ)")
            return 1
        total = sum(got.values())
        faults = " (+mapper/reducer fault injection)" if FAULTS else ""
        print(f"[integration] PASS: {MAPPERS} maps x {PAIRS} pairs -> "
              f"{len(got)} keys, {total} records, {EXECUTORS} executor processes, "
              f"{time.monotonic() - t0:.1f}s wall{faults}")
        ctl.remove_shuffle(SHUFFLE_ID)
        ctl.shutdown()
        return 0
    finally:
        daemon.terminate()
        try:
            daemon.wait(timeout=10)
        except subprocess.TimeoutExpired:
            daemon.kill()


if __name__ == "__main__":
    sys.exit(main())
