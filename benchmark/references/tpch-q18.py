"""TPC-H Q18 ("Large Volume Customer", specification clause 2.4.18): the three
tables' columns the query reads, the map output of its three order-key
shuffles, and the plain answer they are checked against.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey having sum(l_quantity) > :threshold)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate limit 100

**The population laws** (dbgen, clause 4.2.3, for the columns Q18 reads,
written from memory — no network here): ``orders`` = 1,500,000 x SF rows;
``o_orderkey`` sparse, the first 8 of every 32 integers (``mk_sparse``: order
``i`` = 1.. has key ``((i >> 3) << 5) | (i & 7)``); ``o_custkey`` uniform over
1..150,000 x SF without the multiples of 3; ``o_orderdate`` uniform over
1992-01-01..1998-08-02; 1..7 lines an order, uniform, written together in
order-key order; ``l_quantity`` uniform 1..50; ``c_name`` = ``Customer#`` and
the key in nine digits.  ``o_totalprice`` is drawn (dbgen derives it from the
lines' prices, which the query never reads).  Every column is 8 bytes in a
record: a ``bigint``, a ``decimal(15,2)`` as its unscaled hundredths, a date as
days since 1970-01-01 widened — little-endian, key first, records back to back.

**The plan** (Spark's sort-merge plan with every large input partitioned by
the order key): shuffle ``A`` — lineitem's splits, each map task's partial
sums ``(l_orderkey, sum)``; shuffle ``B`` — orders ``(o_orderkey, o_custkey,
o_totalprice, o_orderdate)``; shuffle ``C`` — lineitem again, ``(l_orderkey,
l_quantity)``; all three by ``partition_of`` into ``partitions``.  A reduce
task sums A by key and keeps the sums over the threshold, semi-joins B with
them, joins C with the surviving orders and sums again.  Here the program does
all that and this reference only computes the answer, from the generated
columns and never from what a shuffle returned: plain numpy on the host.

**The partitioner**: Spark's is Murmur3 ``pmod`` partitions over the row's
key (written from memory); here it is MurmurHash3's 64-bit finalizer
(``fmix64``) of the whole 8-byte key, mod ``partitions`` — a mixing hash of
every key bit (the keys are sparse: a plain ``mod`` would load 1 partition in
4), stated in the configuration's ``assumed``.

How many lines each order has comes from the fixed layout stream, the same
for every ``--seed``: every run stages the same blocks of the same sizes.
Quantities, customers, prices and dates come from ``--seed``.

Nothing here imports the code under test.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.cells import load_module

#: the generator's stream for the block layout, apart from every ``--seed``'s
LAYOUT_DRAW = load_module("references", "groupby").LAYOUT_DRAW
_EPOCH = datetime.date(1970, 1, 1)
FIRST_ORDERDATE = (datetime.date(1992, 1, 1) - _EPOCH).days
LAST_ORDERDATE = (datetime.date(1998, 8, 2) - _EPOCH).days
#: hundredths; dbgen's totals lie between about these
PRICE_LOW, PRICE_HIGH = 85_771, 55_528_516
MAX_LINES, MAX_QUANTITY = 7, 50
KEY_BYTES = 8
#: record widths of the three shuffles, and the columns after the key
SHUFFLES = {"A": 16, "B": 32, "C": 16}
#: a result row: o_orderkey, sum(l_quantity), o_custkey, o_totalprice, o_orderdate
RESULT_COLUMNS = 5
_MASK = (1 << 64) - 1


def order_keys(first: int, count: int) -> np.ndarray:
    """``o_orderkey`` of orders ``first`` .. ``first + count - 1`` (1-based)."""
    i = np.arange(first, first + count, dtype=np.uint64)
    return ((i >> np.uint64(3)) << np.uint64(5)) | (i & np.uint64(7))


def partition_of(keys: np.ndarray, partitions: int) -> np.ndarray:
    """The reduce partition of every 8-byte key: ``fmix64(key) mod partitions``."""
    h = keys.astype(np.uint64)
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xFF51AFD7ED558CCD)
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xC4CEB9FE1A85EC53)
    h = h ^ (h >> np.uint64(33))
    return (h % np.uint64(partitions)).astype(np.int64)


def customer_name(custkey: int) -> str:
    return f"Customer#{int(custkey):09d}"


def _sizes(config: dict) -> Tuple[int, int, int, int]:
    orders, partitions = int(config["orders"]), int(config["partitions"])
    line_splits, order_splits = int(config["lineitem_splits"]), int(config["orders_splits"])
    if not (orders >= 1 and 1 <= partitions <= 65535 and line_splits >= 1 and order_splits >= 1):
        raise ValueError(f"no Q18 deployment: {orders} orders, {partitions} partitions")
    if int(config["record_key_bytes"]) != KEY_BYTES or int(config["column_bytes"]) != 8:
        raise ValueError("Q18's records here have 8-byte columns and keys")
    return orders, partitions, line_splits, order_splits


def lines_of(config: dict) -> np.ndarray:
    """Lines of every order, from the layout stream: the same for every seed."""
    draw = np.random.default_rng([LAYOUT_DRAW, 18])
    return draw.integers(1, MAX_LINES + 1, size=int(config["orders"]), dtype=np.int64)


def split_bounds(rows: int, splits: int) -> np.ndarray:
    """Row bounds of ``splits`` equal splits of a table's file."""
    return (np.arange(splits + 1, dtype=np.int64) * rows) // splits


@dataclass
class Split:
    """One map task's output for one shuffle: its records grouped by reduce
    partition (inside a partition in the order the task made them) and
    ``bounds``: partition ``r``'s records are rows ``[bounds[r], bounds[r+1])``."""

    records: np.ndarray  # (n, record_bytes) uint8
    bounds: np.ndarray   # (partitions + 1,) int64


def _grouped(columns: List[np.ndarray], partitions: int) -> Split:
    """Records of 8-byte columns (the key first), grouped by their partition."""
    part = partition_of(columns[0], partitions)
    order = np.argsort(part.astype(np.uint8 if partitions <= 256 else np.uint16), kind="stable")
    rows = np.stack([c.astype("<u8", copy=False) for c in columns], axis=1)[order]
    bounds = np.searchsorted(part[order], np.arange(partitions + 1))
    return Split(rows.view(np.uint8).reshape(len(rows), 8 * len(columns)), bounds)


def _partial_sums(keys: np.ndarray, values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """A map task's partial aggregate over rows that come with equal keys
    together: one ``(key, sum)`` a run of equal keys."""
    if not len(keys):
        return keys, values
    starts = np.flatnonzero(np.concatenate([[True], keys[1:] != keys[:-1]]))
    return keys[starts], np.add.reduceat(values, starts)


@dataclass
class Planted:
    """Orders beyond the source's laws, for the controls: ``high_lane`` orders
    whose keys differ from a source order's only in their high four bytes yet
    fall into its partition, each with lines that pass the threshold while the
    source order's do not; ``large_sum`` one order whose quantities sum past
    2**32 hundredths."""

    high_lane: int = 0
    large_sum: bool = False


class Query:
    """One query's map output and what a correct run of it returns."""

    def __init__(self, config: dict, seed: int) -> None:
        orders, self.partitions, line_splits, order_splits = _sizes(config)
        self.threshold = int(config["quantity_threshold"]) * 100  # hundredths
        self.limit = int(config["limit"])
        self.key_bytes = KEY_BYTES
        planted = Planted(**config.get("planted", {}))
        rng = np.random.default_rng([seed, 18])
        okey = order_keys(1, orders)
        lines = lines_of(config)
        customers = int(config["customers"])
        u = rng.integers(0, customers - customers // 3, size=orders, dtype=np.uint64)
        custkey = (u // np.uint64(2)) * np.uint64(3) + (u % np.uint64(2)) + np.uint64(1)
        price = rng.integers(PRICE_LOW, PRICE_HIGH + 1, size=orders, dtype=np.uint64)
        date = rng.integers(FIRST_ORDERDATE, LAST_ORDERDATE + 1, size=orders, dtype=np.uint64)
        l_okey = np.repeat(okey, lines)
        quantity = rng.integers(1, MAX_QUANTITY + 1, size=len(l_okey), dtype=np.uint64) * np.uint64(100)
        if planted.high_lane or planted.large_sum:
            okey, custkey, price, date, l_okey, quantity = _plant(
                planted, self.partitions, self.threshold, okey, custkey, price, date, l_okey, quantity)
        self.orders, self.lines = len(okey), len(l_okey)

        # the map output, a split a map task
        lb, ob = split_bounds(self.lines, line_splits), split_bounds(self.orders, order_splits)
        P = self.partitions

        def line_split(s: int):
            k, q = l_okey[lb[s] : lb[s + 1]], quantity[lb[s] : lb[s + 1]]
            return _grouped(list(_partial_sums(k, q)), P), _grouped([k, q], P)

        def order_split(s: int):
            cut = slice(ob[s], ob[s + 1])
            return _grouped([okey[cut], custkey[cut], price[cut], date[cut]], P)

        with ThreadPoolExecutor(max_workers=6) as pool:
            made = list(pool.map(line_split, range(line_splits)))
            self.shuffles: Dict[str, List[Split]] = {
                "A": [a for a, _ in made], "B": list(pool.map(order_split, range(order_splits))),
                "C": [c for _, c in made],
            }

        # the plain answer, from the columns
        if np.any(l_okey[1:] < l_okey[:-1]):
            by_key = np.argsort(l_okey, kind="stable")
            l_okey, quantity = l_okey[by_key], quantity[by_key]
        sum_keys, sums = _partial_sums(l_okey, quantity)
        big = sums > np.uint64(self.threshold)
        big_keys, big_sums = sum_keys[big], sums[big]
        by_okey = np.argsort(okey, kind="stable")
        at = by_okey[np.searchsorted(okey[by_okey], big_keys)]
        if not np.array_equal(okey[at], big_keys):
            raise AssertionError("a line's order key is no order's")
        #: every row the query's reduce side makes: (orderkey, sum, custkey, totalprice, orderdate)
        self.rows = np.stack([big_keys, big_sums, custkey[at], price[at], date[at]], axis=1)
        part = partition_of(big_keys, P)
        #: the rows of each reduce task, by order key
        self.task_rows: List[np.ndarray] = [self.rows[part == r] for r in range(P)]
        #: per reduce task: rows, and the sum of their words mod 2**64
        self.expected: List[Tuple[int, int]] = [(len(rows), digest(rows)) for rows in self.task_rows]
        # order by o_totalprice desc, o_orderdate, ties by o_orderkey; limit
        top = np.lexsort((self.rows[:, 0], self.rows[:, 4], -self.rows[:, 3].astype(np.int64)))[: self.limit]
        self.answer: List[tuple] = [answer_row(row) for row in self.rows[top]]
        #: lines that joined (the second aggregate's input) and partial sums (the first's)
        self.joined_lines = int(np.isin(l_okey, big_keys).sum())
        self.records_aggregated = sum(len(s.records) for s in self.shuffles["A"]) + self.joined_lines
        #: a (split, partition) of shuffle C that holds a line of a surviving
        #: order — withheld, that order's sum comes out short
        self.survivor_block: Optional[Tuple[int, int]] = None
        if len(big_keys):
            line = int(np.searchsorted(l_okey, big_keys[0]))
            self.survivor_block = (int(np.searchsorted(lb, line, side="right")) - 1, int(part[0]))

    # -- what the job is to the shuffle ---------------------------------------

    @property
    def num_mappers(self) -> int:
        return sum(len(splits) for splits in self.shuffles.values())

    @property
    def num_blocks(self) -> int:
        return sum(int(np.count_nonzero(np.diff(s.bounds))) for splits in self.shuffles.values() for s in splits)

    @property
    def total_bytes(self) -> int:
        return sum(s.records.size for splits in self.shuffles.values() for s in splits)

    def task_check(self, reduce_id: int, rows: np.ndarray) -> bool:
        """A timed reduce task's check: its rows' count and digest."""
        return (len(rows), digest(rows)) == self.expected[reduce_id]

    def task_equals(self, reduce_id: int, rows: np.ndarray) -> bool:
        """The warm-up query's check of one task: its rows — every surviving
        order with its sum and its joined columns — equal the reference's,
        as a set (a task's rows come in the program's key order)."""
        want = self.task_rows[reduce_id]
        rows = np.asarray(rows, dtype=np.uint64).reshape(-1, RESULT_COLUMNS)
        return rows.shape == want.shape and np.array_equal(rows[np.argsort(rows[:, 0], kind="stable")], want)


def digest(rows: np.ndarray) -> int:
    """An order-free sum over every word of the rows, mod 2**64."""
    return int(np.asarray(rows, dtype=np.uint64).sum(dtype=np.uint64)) & _MASK


def answer_row(row) -> tuple:
    """A result row as the query returns it: (c_name, c_custkey, o_orderkey,
    o_orderdate, o_totalprice, sum(l_quantity)) — the date in days since 1970,
    the decimals in hundredths."""
    okey, total, custkey, price, date = (int(v) for v in row)
    return (customer_name(custkey), custkey, okey, date, price, total)


def _plant(planted: Planted, partitions: int, threshold: int, okey, custkey, price, date, l_okey, quantity):
    """The source's tables with the controls' orders appended (their lines
    after the source's: the answer sorts by key first)."""
    new_keys, new_lines = [], []
    low = 0
    for _ in range(planted.high_lane):
        # a source order and a key that shares its low four bytes and its partition
        while True:
            base = int(okey[low])
            low += 1
            found = [base + (h << 32) for h in range(1, 4 * partitions)
                     if partition_of(np.array([base + (h << 32)], np.uint64), partitions)[0]
                     == partition_of(np.array([base], np.uint64), partitions)[0]]
            if found:
                break
        new_keys.append(found[0])
        new_lines.append([threshold // MAX_LINES + 100] * MAX_LINES)  # just over the threshold together
    if planted.large_sum:
        new_keys.append(int(okey[-1]) + (1 << 33))
        new_lines.append([1_000_000_000] * 5)  # 5e9 hundredths: past 2**32
    k = np.array(new_keys, np.uint64)
    n = len(k)
    okey = np.concatenate([okey, k])
    custkey = np.concatenate([custkey, np.arange(1, n + 1, dtype=np.uint64) * np.uint64(3) + np.uint64(1)])
    price = np.concatenate([price, np.full(n, PRICE_HIGH, np.uint64) - np.arange(n, dtype=np.uint64)])
    date = np.concatenate([date, np.full(n, FIRST_ORDERDATE, np.uint64)])
    l_okey = np.concatenate([l_okey, np.repeat(k, [len(q) for q in new_lines])])
    quantity = np.concatenate([quantity, np.array([q for qs in new_lines for q in qs], np.uint64)])
    return okey, custkey, price, date, l_okey, quantity


def make_records(config: dict, seed: int) -> Query:
    return Query(config, seed)


def geometry(config: dict, chips: int) -> dict:
    """What a query is to the store, from the layout alone (no value is made,
    so it is the same for every ``--seed``): each shuffle's map tasks, records,
    bytes and blocks, the spread of its partitions, the bytes it stages in its
    one round (every block padded to the store's ``alignment``) and the record
    places a reduce task's ordered read sorts at (every block of the task
    rounded up to a slot: ``lcm(record_bytes, alignment)`` bytes); then the
    query's totals and the device memory it holds while it runs: a staged
    round and a received shard a shuffle, of ``staging_bytes`` each."""
    orders, partitions, line_splits, order_splits = _sizes(config)
    if chips != 1:
        raise ValueError("the configuration is one executor on one chip")
    store = config["store"]
    align, staging = int(store["alignment"]), int(store["staging_bytes"])
    okey, lines = order_keys(1, orders), lines_of(config)
    part = partition_of(okey, partitions)
    lb, ob = split_bounds(int(lines.sum()), line_splits), split_bounds(orders, order_splits)
    first_line = np.concatenate([[0], np.cumsum(lines)])  # order i's lines are [first_line[i], first_line[i+1])
    blocks = {}
    # A: an order gives a row to every lineitem split it has a line in
    rows_a = np.zeros((line_splits, partitions), dtype=np.int64)
    rows_c = np.zeros((line_splits, partitions), dtype=np.int64)
    for s in range(line_splits):
        lo, hi = int(np.searchsorted(first_line, lb[s], side="right")) - 1, int(np.searchsorted(first_line, lb[s + 1]))
        inside = np.minimum(first_line[lo + 1 : hi + 1], lb[s + 1]) - np.maximum(first_line[lo:hi], lb[s])
        rows_a[s] = np.bincount(part[lo:hi], weights=inside > 0, minlength=partitions)
        rows_c[s] = np.bincount(part[lo:hi], weights=inside, minlength=partitions)
    blocks["A"], blocks["C"] = rows_a, rows_c
    blocks["B"] = np.stack([np.bincount(part[ob[s] : ob[s + 1]], minlength=partitions) for s in range(order_splits)])
    out: dict = {"shuffles": {}}
    for name, rows in blocks.items():
        width = SHUFFLES[name]
        nbytes = rows * width
        slot = int(np.lcm(width, align))
        staged = int((-(-nbytes // align) * align).sum())
        if staged > staging:
            raise ValueError(f"shuffle {name} stages {staged} B: more than one round of {staging}")
        task_rows = rows.sum(axis=0)
        out["shuffles"][name] = {
            "map_tasks": int(rows.shape[0]), "record_bytes": width, "records": int(rows.sum()),
            "bytes": int(nbytes.sum()), "blocks": int(np.count_nonzero(rows)),
            "smallest_block_bytes": int(nbytes[nbytes > 0].min()), "largest_block_bytes": int(nbytes.max()),
            "smallest_partition_records": int(task_rows.min()), "largest_partition_records": int(task_rows.max()),
            "staged_bytes": staged, "rounds": 1,
            "sort_capacity_records": int((-(-nbytes // slot)).sum(axis=0).max()) * (slot // width),
        }
    per = out["shuffles"].values()
    out.update({
        "job_bytes": sum(s["bytes"] for s in per), "records": sum(s["records"] for s in per),
        "blocks": sum(s["blocks"] for s in per), "map_tasks": sum(s["map_tasks"] for s in per),
        "reduce_tasks": partitions,
        "hbm_bytes_held": 2 * staging * len(blocks),
    })
    return out
