"""The query runner's batch lane (``query/batch.py``): TPC-H Q18's DAG
through ``QueryRunner.run`` on ``TpuShuffleManager`` at the rehearsal's size,
held row for row to ``benchmark/references/tpch-q18.py`` (which imports
nothing of the program) on one and on four CPU devices; three live shuffles
removed leave the store and the gauges where they stood; the typed refusals;
the planted controls (keys that differ only in their high four bytes, a sum
past 2**32 hundredths)."""

import gc

import jax
import numpy as np
import pytest

from benchmark.cells import load_cell, load_module
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import SplitBlockError
from sparkucx_tpu.query import QueryRunner, Stage, StageDag
from sparkucx_tpu.query import batch
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import FixedWidthSerializer, RaggedBlockError

CELL = "q18sf10-queryjobs-1chip"
reference = load_module("references", "tpch-q18")
driver = load_module("traffic", "manager-queryjobs")
SEED = 2_147_483_777  # the driver's seeds pass 2**31


@pytest.fixture(scope="module")
def config():
    return load_cell(CELL, rehearse=True).config


@pytest.fixture(scope="module")
def query(config):
    return reference.make_records(config, SEED)


def manager_of(config, executors=1, **conf):
    return TpuShuffleManager(TpuShuffleConf(**{**config["conf"], **conf}), num_executors=executors)


def inputs_of(query, partitioned=("A", "B", "C")):
    """The three scans' splits; a shuffle not named is handed over as bare
    arrays, in the order the table has them: the runner partitions those."""
    out = {}
    for scan, name in (("lineitem_sums", "A"), ("orders", "B"), ("lineitem", "C")):
        if name in partitioned:
            out[scan] = [batch.RecordSplit(s.records, s.bounds) for s in query.shuffles[name]]
        else:
            out[scan] = [np.random.default_rng(7).permutation(s.records) for s in query.shuffles[name]]
    return out


def holds(query, result):
    """The run's rows are the reference's: every task's, and the query's in its order."""
    return (all(query.task_equals(r, rows) for r, rows in enumerate(result.partitions))
            and all(query.task_check(r, rows) for r, rows in enumerate(result.partitions))
            and [reference.answer_row(row) for row in result.rows] == query.answer)


@pytest.mark.parametrize("executors", [1, 4])
def test_q18_through_the_runner_equals_the_reference_row_for_row(config, query, executors):
    assert len(query.rows) >= 5, "the rehearsal's threshold leaves survivors to join"
    with manager_of(config, executors) as mgr:
        runner = QueryRunner(mgr)
        dag = driver.q18_dag(config, query.threshold)
        result = runner.run(dag, inputs_of(query))
        assert holds(query, result)
        assert len(result.partitions) == len(result.task_seconds) == query.partitions
        again = runner.run(dag, inputs_of(query, partitioned=("B",)))  # A and C partitioned by the runner
        assert holds(query, again)
        counted = runner.counters()
        assert counted["queries"] == 2 and counted["exchanges_executed"] == 6
        assert counted["device_tasks"] == 2 * query.partitions
        assert counted["records_aggregated"] == 2 * query.records_aggregated
        assert counted["rows_joined"] == 2 * (len(query.rows) + query.joined_lines)
        assert counted["groups_out"] == 2 * 2 * len(query.rows)
        assert counted["overflow_checks"] == 2 * 2 * query.partitions
        groups = config["max_groups_per_task"]
        assert counted["result_d2h_bytes"] == 2 * query.partitions * 4 * (groups * 10 + 4 * 4)
        # nothing of the three shuffles crossed: no ordered read was brought to the host
        assert sum(row["d2h_bytes"] for row in mgr.cluster.ordered_read_stats()) == 0
        assert f"sparkucx_tpu_query_device_tasks {2 * query.partitions}" in mgr.cluster.metrics_text()


def test_the_programs_partitioner_is_the_references(query):
    for name, splits in query.shuffles.items():
        records = splits[0].records
        keys = np.ascontiguousarray(records[:, :8]).view("<u8").ravel()
        mine = batch.hash_partition(records, 8, query.partitions)
        assert np.array_equal(mine, reference.partition_of(keys, query.partitions)), name
        assert np.array_equal(np.searchsorted(mine, np.arange(query.partitions + 1)), splits[0].bounds)
    wide = np.random.default_rng(3).integers(0, 256, (500, 20), dtype=np.uint8)  # a 12-byte key: two words
    parts = batch.hash_partition(wide, 12, 7)
    changed = wide.copy()
    changed[:, 11] ^= 1  # the key's last byte moves the partition; a byte after the key does not
    assert (batch.hash_partition(changed, 12, 7) != parts).any()
    changed = wide.copy()
    changed[:, 12] ^= 1
    assert np.array_equal(batch.hash_partition(changed, 12, 7), parts)


def test_three_live_shuffles_removed_leave_the_store_and_the_gauges_where_they_stood(config, query):
    gc.collect()
    gc.disable()
    try:
        with manager_of(config) as mgr:
            runner = QueryRunner(mgr)
            dag = driver.q18_dag(config, query.threshold)
            store = mgr.cluster.transport(0).store
            live = []

            class Phases:
                def __init__(self, name, sids):
                    self.name, self.sids = name, sids

                def __enter__(self):
                    if self.name == "release":  # all three still registered, exchanged, on the device
                        live.append((list(self.sids), len(store._shuffles),
                                     [mgr.cluster.meta(s).recv_device is not None for s in self.sids]))

                def __exit__(self, *exc):
                    return False

            runner.run(dag, inputs_of(query), phases=Phases)  # compiles; the free list takes its buffers
            before_arrays = {id(a) for a in jax.live_arrays()}
            before = store.write_stats()
            result = runner.run(dag, inputs_of(query), phases=Phases)
            assert holds(query, result)
            assert [(len(s), n, d) for s, n, d in live] == [(3, 3, [True] * 3)] * 2
            assert live[0][0] != live[1][0]  # fresh shuffle ids a query
            del result
            after = store.write_stats()
            assert store._shuffles == {} and mgr._shuffle_dims == {}
            assert [a.shape for a in jax.live_arrays() if id(a) not in before_arrays] == []
            # the three staging buffers came from the free list and went back to it
            assert after["pool_hits"] - before["pool_hits"] == 3 and after["pool_misses"] == before["pool_misses"]
            assert after["pool_held_bytes"] == before["pool_held_bytes"] == 3 * config["conf"]["staging_capacity_per_executor"]
            gauges = mgr.cluster.ordered_read_stats()[0]
            assert gauges["in_flight"] == 0 and gauges["in_flight_device_bytes"] == 0
            assert gauges["in_flight_peak"] == 1 and gauges["tasks"] == 2 * 3 * query.partitions
    finally:
        gc.enable()


def test_a_conf_that_keeps_no_shards_on_the_device_is_refused_before_a_row_moves(config, query):
    with manager_of(config, keep_device_recv=False, host_recv_mode="array") as mgr:
        runner = QueryRunner(mgr)
        with pytest.raises(batch.BatchLaneRefusedError, match="keep_device_recv"):
            runner.run(driver.q18_dag(config, query.threshold), inputs_of(query))
        assert mgr._shuffle_dims == {} and runner.counters()["queries"] == 0
        assert mgr.cluster.transport(0).store.write_stats()["pool_misses"] == 0  # no staging was touched


def small_dag(partitions, record_bytes=16, max_groups=8, **aggregate):
    return StageDag([
        Stage.make("rows", "scan"),
        Stage.make("x", "exchange", ["rows"], partitions=partitions, record_bytes=record_bytes, key_bytes=8),
        Stage.make("sums", "aggregate", ["x"], value_byte=8, max_groups=max_groups, **aggregate),
    ])


def rows_of(keys, values):
    return np.stack([np.asarray(keys, "<u8"), np.asarray(values, "<u8")], axis=1).view(np.uint8).reshape(-1, 16)


def test_a_sum_past_63_bits_is_raised_typed_and_the_shuffles_are_removed(config):
    with manager_of(config) as mgr:
        runner = QueryRunner(mgr)
        with pytest.raises(batch.QuerySumOverflowError, match="63 bits"):
            runner.run(small_dag(2), {"rows": [rows_of([5, 5, 6], [1 << 62, 1 << 62, 3])]})
        assert mgr._shuffle_dims == {} and mgr.cluster.transport(0).store._shuffles == {}
        # a sum that fits is exact, whatever lane it would have wrapped in
        result = runner.run(small_dag(2), {"rows": [rows_of([5, 5, 6], [(1 << 62) - 1, 1 << 62, 3])]})
        assert sorted(result.rows.tolist()) == [[5, (1 << 63) - 1], [6, 3]]


def test_more_groups_than_a_stage_has_room_for_is_raised_typed(config):
    with manager_of(config) as mgr:
        with pytest.raises(batch.QueryCapacityError, match="max_groups"):
            QueryRunner(mgr).run(small_dag(1, max_groups=4), {"rows": [rows_of(np.arange(9), np.ones(9))]})
        assert mgr._shuffle_dims == {}


def test_a_block_that_is_no_whole_number_of_records_is_ragged_as_today(config):
    with manager_of(config) as mgr:
        mgr.register_shuffle(7, 1, 1)
        writer = mgr.get_writer(7, 0)
        with writer.get_partition_writer(0).open_stream() as stream:
            stream.write(bytes(40))
        writer.commit_all_partitions()
        mgr.run_exchange(7)
        reader = mgr.get_reader(7, 0, 1, deserializer=FixedWidthSerializer(16, 8), key_ordering=True)
        with pytest.raises(RaggedBlockError):
            reader.read_device()
        # and the lane's own writer never makes one: the records' width is the stage's
        with pytest.raises(ValueError, match="records of shape"):
            QueryRunner(mgr).run(small_dag(1, record_bytes=32), {"rows": [rows_of([1], [1])]})
        mgr.unregister_shuffle(7)
        assert list(mgr._shuffle_dims) == []


def test_a_split_block_is_refused_typed_not_read_short(config):
    """A block longer than a peer region is staged in pieces (PR 58); the
    ordered device read takes a block out of one round's shard and refuses
    it by name — the query ends typed and its shuffles are removed."""
    conf = {"staging_capacity_per_executor": 64 << 10}  # four executors: 16 KiB regions
    with manager_of(config, executors=4, **conf) as mgr:
        keys = np.full(3000, 42)  # 48 KB in one block
        with pytest.raises(SplitBlockError):
            QueryRunner(mgr).run(small_dag(4, max_groups=8), {"rows": [rows_of(keys, np.ones(3000))]})
        assert mgr._shuffle_dims == {}


def test_the_planted_controls_come_out_exact(config):
    """Orders whose keys differ from a source order's only in their high four
    bytes (and fall into its partition), and an order whose quantities sum
    past 2**32 hundredths: an operator that compared four key bytes, or
    summed in a 32-bit lane, would pass the source's data by luck."""
    planted = reference.make_records({**config, "planted": {"high_lane": 3, "large_sum": True}}, SEED)
    extra = planted.rows[planted.rows[:, 0] >> np.uint64(32) != 0]
    large = extra[extra[:, 1] == 5_000_000_000]
    twins_of = extra[extra[:, 1] != 5_000_000_000][:, 0]
    assert len(large) == 1 and len(twins_of) == 3
    twins = [int(k) & 0xFFFFFFFF for k in twins_of]
    assert all(reference.partition_of(np.array([low], np.uint64), planted.partitions)[0]
               == reference.partition_of(np.array([key], np.uint64), planted.partitions)[0]
               for low, key in zip(twins, twins_of))
    with manager_of(config) as mgr:
        result = QueryRunner(mgr).run(driver.q18_dag(config, planted.threshold), inputs_of(planted))
    assert holds(planted, result)
    got = {int(row[0]): int(row[1]) for row in result.rows}
    assert all(got[int(k)] == int(s) for k, s in extra[:, :2])
    assert not set(twins) & set(got)  # the source orders they shadow did not pass, and were not merged


def test_a_query_is_all_arrays_or_all_tuples(config):
    with manager_of(config) as mgr:
        dag = StageDag([
            Stage.make("a", "scan"), Stage.make("b", "scan"),
            Stage.make("xa", "exchange", ["a"], partitions=2, record_bytes=16, key_bytes=8),
            Stage.make("xb", "exchange", ["b"], partitions=2, record_bytes=16, key_bytes=8),
            Stage.make("j", "join", ["xa", "xb"], max_rows=8),
        ])
        with pytest.raises(ValueError, match="all record arrays"):
            QueryRunner(mgr).run(dag, {"a": [rows_of([1], [1])], "b": [(1, 2)]})
        with pytest.raises(ValueError, match="ends in an aggregate or a join"):
            QueryRunner(mgr).run(StageDag(dag.stages[:4]), {"a": [rows_of([1], [1])], "b": [rows_of([1], [1])]})
        result = QueryRunner(mgr).run(dag, {"a": [rows_of([1, 2], [10, 20])], "b": [rows_of([2, 2, 3], [7, 8, 9])]})
        assert sorted(result.rows.tolist()) == [[2, 7, 20], [2, 8, 20]]  # probe row, then the build row's payload
