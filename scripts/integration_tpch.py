#!/usr/bin/env python
"""TPC-H q18 integration driver — a Spark-SQL-shaped JOB through the daemon
(VERDICT r4 item 7): the SQL pipelines that run as device ops in
tests/test_tpch.py here run as a multi-process, two-stage, two-shuffle job
over the wire protocol, proving the L7 surface carries the BASELINE
configs[2] workloads, not only groupby/terasort.

q18 ("large volume customer") physical plan, mapped to shuffles:

    stage 1  lineitem --(shuffle A: hash by l_orderkey)--> SUM(l_quantity)
             GROUP BY l_orderkey HAVING sum > THRESHOLD          (HashAgg)
    stage 2  survivors --(shuffle B: re-keyed)--+
             orders    --(shuffle C: hash by o_orderkey)--+--> join on
             orderkey -> (c_custkey, o_totalprice, sum_qty) rows  (SHJ)

Mapper processes generate deterministic lineitem/orders shards and write
partition blocks over the daemon protocol; stage-1 reducer processes fetch,
aggregate, apply the HAVING filter, and act as stage-2 MAPPERS (writing the
survivors into shuffle B) — the classic multi-stage DAG where one stage's
reduce side is the next stage's map side.  Stage-2 reducers join shuffles B
and C per partition and emit the final q18 rows; the driver compares the
merged result against a full numpy oracle over the regenerated inputs.

Which path this is: the DAEMON's wire (OS processes, ``DaemonClient``) with
the aggregate and the join in numpy ON THE HOST, at 200,000 rows — it proves
the L7 surface, not the operators.  The served path with the stages after each
exchange run on the chip at SF=10 is the benchmark's cell
``q18sf10-queryjobs-1chip`` (``QueryRunner``'s batch lane, ``query/batch.py``).

Reference gate analogue: buildlib/test.sh:196's gate composition;
BASELINE.json configs[2] (TPC-H SF=10 plan shapes).
Knobs via env: EXECUTORS, MAPPERS, REDUCERS, ROWS (lineitem), ORDERS.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXECUTORS = int(os.environ.get("EXECUTORS", "2"))
MAPPERS = int(os.environ.get("MAPPERS", "4"))
REDUCERS = int(os.environ.get("REDUCERS", "8"))
ROWS = int(os.environ.get("ROWS", "200000"))          # lineitem rows
ORDERS = int(os.environ.get("ORDERS", "10000"))       # orders rows (unique keys)
CUSTOMERS = max(ORDERS // 10, 1)
# HAVING SUM(l_quantity) > : with ROWS/ORDERS ~ 20 rows/order at mean qty
# 25.5, 650 qualifies ~1 order in 7 — the filter really filters (q18's HAVING
# is the plan's whole point)
THRESHOLD = int(os.environ.get("THRESHOLD", "650"))
ROWS_PER_MAP = -(-ROWS // MAPPERS)
SHUFFLE_LINEITEM, SHUFFLE_SURVIVORS, SHUFFLE_ORDERS = 18, 19, 20

# partitioner shared by every stage (and the oracle): hash(orderkey) % R
PARTITION = "lambda k, R: ((k.astype('uint64') * 2654435761) >> 16) % R"


LINEITEM_MAPPER = """
import sys
sys.path.insert(0, {root!r})
import numpy as np
from sparkucx_tpu.shuffle.daemon import DaemonClient

host, port, map_ids = sys.argv[1], int(sys.argv[2]), [int(x) for x in sys.argv[3].split(",")]
R, N, ORDERS = int(sys.argv[4]), int(sys.argv[5]), int(sys.argv[6])
part_of = {partition}
client = DaemonClient((host, port))
for m in map_ids:
    rng = np.random.default_rng(1800 + m)   # deterministic: the oracle's twin
    okey = rng.integers(0, ORDERS, size=N, dtype=np.uint64).astype(np.uint32)
    qty = rng.integers(1, 51, size=N, dtype=np.uint64).astype(np.uint32)
    parts = part_of(okey, R)
    w = client.open_map_writer({sid}, m)
    for r in np.unique(parts):
        sel = parts == r
        client.write_partition(w, int(r), np.stack([okey[sel], qty[sel]], axis=1).tobytes())
    client.commit_map(w)
client.close()
print("lineitem mapper done", map_ids)
"""


ORDERS_MAPPER = """
import sys
sys.path.insert(0, {root!r})
import numpy as np
from sparkucx_tpu.shuffle.daemon import DaemonClient

host, port, map_ids = sys.argv[1], int(sys.argv[2]), [int(x) for x in sys.argv[3].split(",")]
R, ORDERS, CUSTOMERS, M = int(sys.argv[4]), int(sys.argv[5]), int(sys.argv[6]), int(sys.argv[7])
part_of = {partition}
client = DaemonClient((host, port))
for m in map_ids:
    # orders table striped over mappers; attributes derive from the key so
    # any process (and the oracle) can regenerate them without coordination
    okey = np.arange(m, ORDERS, M, dtype=np.uint32)
    cust = (okey * np.uint32(2246822519)) % np.uint32(CUSTOMERS)
    price = (okey % np.uint32(9973)) + np.uint32(1)
    parts = part_of(okey, R)
    w = client.open_map_writer({sid}, m)
    for r in np.unique(parts):
        sel = parts == r
        client.write_partition(
            w, int(r), np.stack([okey[sel], cust[sel], price[sel]], axis=1).tobytes())
    client.commit_map(w)
client.close()
print("orders mapper done", map_ids)
"""


# Stage-1 reducer AND stage-2 mapper: aggregates its lineitem partitions,
# applies HAVING, re-publishes survivors into the survivors shuffle keyed by
# the same partitioner (map_id = partition id — the DAG edge).
STAGE1_SCRIPT = """
import sys
sys.path.insert(0, {root!r})
import numpy as np
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.shuffle.daemon import DaemonClient

host, port = sys.argv[1], int(sys.argv[2])
partitions = [int(x) for x in sys.argv[3].split(",")]
M, R, THRESHOLD = int(sys.argv[4]), int(sys.argv[5]), int(sys.argv[6])
part_of = {partition}
client = DaemonClient((host, port))
for r in partitions:
    blocks = client.fetch_blocks([ShuffleBlockId({sid_in}, m, r) for m in range(M)])
    rows = [np.frombuffer(b, dtype=np.uint32).reshape(-1, 2) for b in blocks if b]
    data = np.concatenate(rows) if rows else np.empty((0, 2), dtype=np.uint32)
    # HashAggregateExec: SUM(l_quantity) GROUP BY l_orderkey, then HAVING
    uniq, inv = np.unique(data[:, 0], return_inverse=True)
    sums = np.zeros(len(uniq), dtype=np.uint64)
    np.add.at(sums, inv, data[:, 1])
    keep = sums > THRESHOLD
    survivors = np.stack(
        [uniq[keep], sums[keep].astype(np.uint32)], axis=1
    ) if keep.any() else np.empty((0, 2), dtype=np.uint32)
    # stage-2 map side: survivors re-partitioned by the SAME partitioner
    # (hash partitioning is stable, so each survivor stays in partition r —
    # the degenerate exchange Spark's AQE would elide; written through the
    # wire anyway to exercise the full stage boundary)
    w = client.open_map_writer({sid_out}, r)
    parts = part_of(survivors[:, 0], R)
    for rr in np.unique(parts):
        sel = parts == rr
        client.write_partition(w, int(rr), survivors[sel].tobytes())
    client.commit_map(w)
client.close()
print("stage1 done", partitions)
"""


STAGE2_SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
import numpy as np
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.shuffle.daemon import DaemonClient

host, port = sys.argv[1], int(sys.argv[2])
partitions = [int(x) for x in sys.argv[3].split(",")]
R, OM = int(sys.argv[4]), int(sys.argv[5])
client = DaemonClient((host, port))
out = []
for r in partitions:
    sblocks = client.fetch_blocks([ShuffleBlockId({sid_surv}, m, r) for m in range(R)])
    oblocks = client.fetch_blocks([ShuffleBlockId({sid_ord}, m, r) for m in range(OM)])
    srows = [np.frombuffer(b, dtype=np.uint32).reshape(-1, 2) for b in sblocks if b]
    orows = [np.frombuffer(b, dtype=np.uint32).reshape(-1, 3) for b in oblocks if b]
    surv = np.concatenate(srows) if srows else np.empty((0, 2), dtype=np.uint32)
    orders = np.concatenate(orows) if orows else np.empty((0, 3), dtype=np.uint32)
    # ShuffledHashJoin on orderkey: orders is the build side (PK), survivors
    # probe; both sides were hash-partitioned by the same key so the join is
    # partition-local.
    order_by_key = {{int(k): (int(c), int(p)) for k, c, p in orders}}
    for okey, sq in surv:
        cust, price = order_by_key[int(okey)]   # PK-FK: must always hit
        out.append((int(cust), int(okey), price, int(sq)))
client.close()
print("STAGE2_RESULT " + json.dumps(out))
"""


def oracle():
    """Full numpy q18 over the regenerated inputs."""
    import numpy as np

    okeys = []
    qtys = []
    for m in range(MAPPERS):
        rng = np.random.default_rng(1800 + m)
        okeys.append(rng.integers(0, ORDERS, size=ROWS_PER_MAP, dtype=np.uint64).astype(np.uint32))
        qtys.append(rng.integers(1, 51, size=ROWS_PER_MAP, dtype=np.uint64).astype(np.uint32))
    okey = np.concatenate(okeys)
    qty = np.concatenate(qtys)
    uniq, inv = np.unique(okey, return_inverse=True)
    sums = np.zeros(len(uniq), dtype=np.uint64)
    np.add.at(sums, inv, qty)
    keep = sums > THRESHOLD
    rows = []
    for k, s in zip(uniq[keep], sums[keep]):
        # uint32-wraparound twin of ORDERS_MAPPER's array arithmetic
        cust = ((int(k) * 2246822519) & 0xFFFFFFFF) % CUSTOMERS
        price = int(k) % 9973 + 1
        rows.append((cust, int(k), price, int(s)))
    return sorted(rows)


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
            print("[tpch] FAIL: daemon did not report its address")
            return 1
        print(f"[tpch] daemon on {host}:{port}")

        from sparkucx_tpu.shuffle.daemon import DaemonClient

        ctl = DaemonClient((host, int(port)))
        ctl.create_shuffle(SHUFFLE_LINEITEM, MAPPERS, REDUCERS)
        ctl.create_shuffle(SHUFFLE_ORDERS, MAPPERS, REDUCERS)
        # survivors shuffle: stage-1 reducers are its mappers (one per partition)
        ctl.create_shuffle(SHUFFLE_SURVIVORS, REDUCERS, REDUCERS)

        def spawn_over_executors(script, ids, *extra):
            procs = []
            for e in range(EXECUTORS):
                mine = [str(i) for i in ids if i % EXECUTORS == e]
                if not mine:
                    continue
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", script, host, port, ",".join(mine), *extra],
                    stdout=subprocess.PIPE, text=True, cwd=ROOT, env=env,
                ))
            return procs

        def wait_all(procs, label):
            outs = []
            for p in procs:
                out, _ = p.communicate(timeout=600)
                if p.returncode != 0:
                    raise RuntimeError(f"{label} exited nonzero")
                outs.append(out)
            return outs

        # stage 0: both base tables, concurrently
        li = spawn_over_executors(
            LINEITEM_MAPPER.format(root=ROOT, sid=SHUFFLE_LINEITEM, partition=PARTITION),
            range(MAPPERS), str(REDUCERS), str(ROWS_PER_MAP), str(ORDERS),
        )
        om = spawn_over_executors(
            ORDERS_MAPPER.format(root=ROOT, sid=SHUFFLE_ORDERS, partition=PARTITION),
            range(MAPPERS), str(REDUCERS), str(ORDERS), str(CUSTOMERS), str(MAPPERS),
        )
        wait_all(li, "lineitem mapper")
        wait_all(om, "orders mapper")
        ctl.run_exchange(SHUFFLE_LINEITEM)
        ctl.run_exchange(SHUFFLE_ORDERS)
        print(f"[tpch] stage-0 exchanges complete ({time.monotonic()-t0:.1f}s)")

        # stage 1: aggregate + HAVING; republish survivors (stage-2 map side)
        s1 = spawn_over_executors(
            STAGE1_SCRIPT.format(
                root=ROOT, sid_in=SHUFFLE_LINEITEM, sid_out=SHUFFLE_SURVIVORS,
                partition=PARTITION,
            ),
            range(REDUCERS), str(MAPPERS), str(REDUCERS), str(THRESHOLD),
        )
        wait_all(s1, "stage-1 reducer")
        ctl.run_exchange(SHUFFLE_SURVIVORS)
        print(f"[tpch] stage-1 exchange complete ({time.monotonic()-t0:.1f}s)")

        # stage 2: partition-local join + final rows
        s2 = spawn_over_executors(
            STAGE2_SCRIPT.format(root=ROOT, sid_surv=SHUFFLE_SURVIVORS, sid_ord=SHUFFLE_ORDERS),
            range(REDUCERS), str(REDUCERS), str(MAPPERS),
        )
        got = []
        for out in wait_all(s2, "stage-2 reducer"):
            for line in out.splitlines():
                if line.startswith("STAGE2_RESULT "):
                    got.extend(tuple(row) for row in json.loads(line[len("STAGE2_RESULT "):]))

        want = oracle()
        got = sorted(got)
        if got != want:
            print(f"[tpch] FAIL: result mismatch ({len(got)} rows vs {len(want)})")
            for g, w in list(zip(got, want))[:5]:
                if g != w:
                    print(f"  first diff: got {g} want {w}")
                    break
            return 1
        print(
            f"[tpch] PASS: q18 over {ROWS} lineitem x {ORDERS} orders -> "
            f"{len(got)} qualifying rows, 3 shuffles, 2 stages, "
            f"{EXECUTORS} executor processes, {time.monotonic()-t0:.1f}s wall"
        )
        return 0
    finally:
        daemon.terminate()
        try:
            daemon.wait(timeout=10)
        except subprocess.TimeoutExpired:
            daemon.kill()


if __name__ == "__main__":
    sys.exit(main())
