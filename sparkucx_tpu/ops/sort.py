"""Distributed sample sort — the device-resident TeraSort core.

BASELINE.md lists TeraSort as a headline workload ("TeraSort 10GB", north star
"shuffle-read GB/s ... TeraSort-100GB").  In Spark, TeraSort is `sortByKey`:
a range-partitioning shuffle (sampled splitters decide which reducer owns each
key range) followed by a per-partition sort.  The reference accelerates only the
shuffle *transport* of that job (UCX block fetch); here the ENTIRE job runs on
device — sampling, range partitioning, the all-to-all, and the final sort are
one jitted SPMD program over the executor mesh:

    local sort -> sample splitters (all_gather) -> range-partition owners ->
    ragged all_to_all (reuses ops/columnar machinery) -> local sort of received

After the step, executor j holds the j-th global key range, sorted; the
concatenation of shards in mesh order is the fully sorted dataset.  This is the
TPU-native answer to the job the reference's GroupByTest/TeraSort harness runs
over Spark + UCX (buildlib/test.sh:163-179, BASELINE.json configs[1]).

Rows are 32-bit lanes.  Every sort here is ``sort_rows``: a row's first
``key_lanes`` lanes are its key, compared lexicographically, most significant
lane first — either as the ``uint32`` values they hold (the distributed sort:
one ``uint32`` key lane in front of ``width`` payload lanes) or, with
``key_bytes``, as the row's first ``key_bytes`` BYTES in memory order,
unsigned, most significant first (a TeraSort record: 100 bytes are 25 lanes,
its 10-byte key 2.5 of them; the reduce side's ordered return,
``transport/tpu.py`` ``ordered_records``).  Keys travel with their payload
through one exchange (bitcast into the payload dtype) so the permutation is
applied exactly once.

The distributed sort's *splitters* stay one-lane ``uint32`` scalars: no cell
range-partitions on the chip (TeraSort's map side does, with
``TeraSortPartitioner``), so a lexicographic splitter has no caller yet.

Skew: splitters come from `samples_per_shard` evenly spaced local samples, so a
range can exceed `recv_capacity` only under adversarial key skew; the returned
per-shard receive totals let the caller detect overflow (`counts >
recv_capacity`) and re-run with more headroom — the host-side analogue of the
multi-round spill path in transport/tpu.py.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.columnar import (
    ColumnarSpec,
    columnar_shard_dense,
    columnar_shard_ragged,
    shard_rows_host,
    size_matrix_from_owners,
    unpack_shard_prefixes,
)
from sparkucx_tpu.ops.exchange import gather_rows, resolve_collective_impl

KEY_MAX = np.uint32(0xFFFFFFFF)  # padding sentinel; sorts last


@dataclass(frozen=True)
class SortSpec:
    """Static description of one compiled distributed sort.

    ``capacity``: per-executor input rows (pad short shards; padding keys must
    be ``KEY_MAX`` and are excluded via ``num_valid``).
    ``recv_capacity``: per-executor output rows — headroom over the balanced
    ``total/n`` guards against sampling error (1.5-2x is ample for uniform
    keys, e.g. TeraSort's).
    ``width``: payload lanes of ``dtype`` per row (>= 0).  The key is ONE
    ``uint32`` lane compared by value (the splitters are scalars of it);
    ``sort_rows`` itself orders any number of key lanes.
    """

    num_executors: int
    capacity: int
    recv_capacity: int
    width: int = 24  # 96-byte payload -> 100-byte rows like TeraSort
    dtype: np.dtype = np.dtype(np.int32)
    samples_per_shard: int = 64
    axis_name: str = "ex"
    impl: str = "auto"

    def resolve_impl(self, platform: Optional[str] = None) -> "SortSpec":
        """'auto' -> 'single' when one executor (sample sort degenerates to one
        local sort — no splitters, no exchange, HALF the sort work; any
        backend), else 'ragged' on TPU / 'dense' elsewhere."""
        if self.impl != "auto":
            return self
        if self.num_executors == 1 and self.recv_capacity >= self.capacity:
            return replace(self, impl="single")
        return replace(self, impl=resolve_collective_impl(self.impl, platform))

    def validate(self) -> None:
        if self.impl not in ("ragged", "dense", "single"):
            raise ValueError(f"unknown impl {self.impl!r}")
        if self.impl == "single" and (
            self.num_executors != 1 or self.recv_capacity < self.capacity
        ):
            raise ValueError(
                "impl='single' needs num_executors=1 and recv_capacity >= capacity"
            )
        if np.dtype(self.dtype).itemsize != 4:
            raise ValueError("payload dtype must be 32-bit (keys bitcast through it)")
        if self.samples_per_shard < self.num_executors:
            raise ValueError("samples_per_shard must be >= num_executors")


def _byteswap32(lanes: jnp.ndarray) -> jnp.ndarray:
    """``uint32`` lanes with their four bytes reversed: a row's bytes lie
    little-endian in a lane, a byte-string key compares its first byte first."""
    return (
        (lanes << 24)
        | ((lanes & jnp.uint32(0xFF00)) << 8)
        | ((lanes >> 8) & jnp.uint32(0xFF00))
        | (lanes >> 24)
    )


def key_lanes_of(key_bytes: int) -> int:
    """32-bit lanes a key of ``key_bytes`` bytes reaches into."""
    return -(-int(key_bytes) // 4)


def comparable_lanes(lanes, key_bytes: int):
    """A byte-string key's ``uint32`` lanes in the form whose lexicographic
    order, lane 0 first, is the order of the keys as unsigned bytes, most
    significant first: each lane byte-swapped, the last masked to the key's
    bytes in it.  Returns ``(lanes, tail)``, ``tail`` the key bytes in the
    last lane (1..4).  Two keys are equal iff every such lane is: what
    ``key_order`` sorts by and ``ops.relational``'s local operators compare."""
    lanes = [_byteswap32(lane) for lane in lanes]
    tail = key_bytes - 4 * (len(lanes) - 1)
    if tail < 4:
        lanes[-1] = lanes[-1] & jnp.uint32((0xFFFFFFFF << (8 * (4 - tail))) & 0xFFFFFFFF)
    return lanes, tail


def key_order(keys, valid: jnp.ndarray, key_bytes: Optional[int] = None):
    """The permutation that sorts rows by their key lanes, and how many of
    them are valid: ``(order, count)`` — ``rows[order]`` has the valid rows
    first, in key order, **stable**, and ``count`` of them.

    ``keys``: the rows' key lanes, a ``(k, N)`` array of a 32-bit dtype or
    ``k`` 1-D lanes, most significant first; ``valid`` and ``key_bytes`` as
    ``sort_rows`` takes them.  The first end of ``sort_rows``, shared with a
    caller that applies the permutation itself (``transport/tpu.py``
    ``ordered_records``: a slice of it, the padding zeroed in the host's form).

    ONE ``lax.sort`` over (the key lanes..., the row index) with every
    operand a key — the index last, so the order is total: stable by
    construction and the same on every run without ``is_stable``.  Rows that
    are not valid sort last by the all-ones key where no row can have it (a
    byte-string key whose last lane is masked, ``key_bytes`` no multiple of
    4), else by a leading flag lane.  The sort's compile time grows with its
    operands and doubles with ``is_stable`` (161 s for five stable operands,
    54 s for these four; PERF.md section 6, PR 48), so nothing rides along
    that need not."""
    lanes = [jax.lax.bitcast_convert_type(lane, jnp.uint32) for lane in keys]
    if key_bytes is not None and len(lanes) != key_lanes_of(key_bytes):
        raise ValueError(f"a {key_bytes}-byte key is {key_lanes_of(key_bytes)} lanes, not {len(lanes)}")
    idx = jnp.arange(lanes[0].shape[0], dtype=jnp.int32)
    valid = jnp.asarray(valid)
    if valid.ndim == 0:
        valid = idx < valid
    tail = 4
    if key_bytes is not None:
        lanes, tail = comparable_lanes(lanes, key_bytes)
    if tail < 4:  # the all-ones key is no row's: padding rows take it
        lanes = [jnp.where(valid, lane, KEY_MAX) for lane in lanes]
    else:
        lanes.insert(0, jnp.logical_not(valid).astype(jnp.uint32))
    order = jax.lax.sort((*lanes, idx), num_keys=len(lanes) + 1, is_stable=False)[-1]
    return order, valid.sum(dtype=jnp.int32)


def sort_rows(
    rows: jnp.ndarray,
    key_lanes: int,
    valid: jnp.ndarray,
    key_bytes: Optional[int] = None,
) -> jnp.ndarray:
    """The one local sort: ``rows`` ``(N, W)`` of a 32-bit dtype, ordered by
    their first ``key_lanes`` lanes compared lexicographically (lane 0 most
    significant), **stable**, rows that are not valid last and zeroed.

    ``valid`` is a count (a scalar: the first ``valid`` rows are the data) or
    an ``(N,)`` bool mask.  ``key_bytes`` ``None``: a lane compares as the
    ``uint32`` it holds.  ``key_bytes = k``: the key is the row's first ``k``
    bytes in memory order compared as unsigned bytes, most significant first
    — each lane byte-swapped, the last one masked to the key's bytes in it;
    ``key_lanes`` must be ``key_lanes_of(k)``.

    How: the key lanes taken from ``rows`` ONCE, as the rows of one ``(N, k)
    -> (k, N)`` transposition (a TPU holds an ``(N, 25)`` array of 32-bit
    lanes in rows of 128, five times its bytes, and ``rows[:, i]`` a lane was
    a pass that wrote a padded ``(N, 1)`` column and another that read it
    back: 2.3 ms for three lanes at TeraSort's shape, 0.23 taken once);
    ``key_order`` over them (one ``lax.sort``); then one row gather by the
    sorted index, so the payload lanes move once, whatever the key's width.
    Inside the executable's own device trace at TeraSort's shape (342,784
    rows of 25 lanes; ``scripts/probe_ordered_passes.py``, PR 55): the sort
    0.80 ms, the row gather 1.25 ms — 3.6 ns an index whatever it fetches, so
    sorting a lane at a time through the order so far would be slower.
    Chosen by the code; there is no host fallback."""
    if not 1 <= key_lanes <= rows.shape[1]:
        raise ValueError(f"key_lanes {key_lanes} of rows {rows.shape[1]} lanes wide")
    order, count = key_order(rows[:, :key_lanes].T, valid, key_bytes)
    # valid rows sort to the front, so the first ``count`` of the output are
    # the data; a padding row's lanes must not leak through the permutation
    idx = jnp.arange(rows.shape[0], dtype=jnp.int32)
    return jnp.where((idx < count)[:, None], gather_rows(rows, order), jnp.zeros((), rows.dtype))


def _global_splitters(spec: SortSpec, sorted_keys: jnp.ndarray, num_valid: jnp.ndarray):
    """Sample each shard's sorted prefix, gather, and pick n-1 range boundaries.

    This is the on-device analogue of Spark's RangePartitioner sketch: sizes are
    published before data moves, like the MapperInfo commit the reference sends
    ahead of block serving (NvkvShuffleMapOutputWriter.scala:116-148)."""
    n = spec.num_executors
    s = spec.samples_per_shard
    # Each shard's sample weight is proportional to its fill (num_valid /
    # capacity), so a near-empty shard doesn't drag the splitters toward its few
    # keys: it uses `used` of its s sample slots, the rest are KEY_MAX sentinels
    # that sort to the top and (given any non-degenerate fill) are never cut.
    # float32 ratio: ~1e-7 relative error is irrelevant for sampling weights and
    # avoids s*num_valid int32 overflow on huge shards.
    nv = num_valid.astype(jnp.int32)
    used = jnp.minimum(
        s, (nv.astype(jnp.float32) / spec.capacity * s).astype(jnp.int32) + (nv > 0)
    )
    # Evenly spaced positions over the valid prefix: (i*nv)//used, decomposed so
    # the product can't overflow int32 for i < used (i*(nv//used) <= nv).
    i = jnp.arange(s, dtype=jnp.int32)
    u = jnp.maximum(used, 1)
    pos = i * (nv // u) + (i * (nv % u)) // u
    local = jnp.where(i < used, sorted_keys[jnp.clip(pos, 0, spec.capacity - 1)], KEY_MAX)
    allsamp = jax.lax.all_gather(local, spec.axis_name, tiled=True)  # (n*s,)
    allsamp = jnp.sort(allsamp)
    # Cut at sample-quantiles of the *real* samples only (sentinels sorted last).
    total_used = jax.lax.psum(used, spec.axis_name)
    k = jnp.arange(1, n, dtype=jnp.int32)
    cut = k * (total_used // n) + (k * (total_used % n)) // n
    return allsamp[jnp.clip(cut, 0, n * s - 1)]  # (n-1,) splitters


def _keyed_rows(spec: SortSpec, keys: jnp.ndarray, payload: jnp.ndarray) -> jnp.ndarray:
    """(key | payload) rows: the key lane bitcast into the payload's dtype."""
    return jnp.concatenate([jax.lax.bitcast_convert_type(keys, spec.dtype)[:, None], payload], axis=1)


def _sorted_keys(rows: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """The key lane of sorted rows, ``KEY_MAX`` on the padding rows."""
    keys = jax.lax.bitcast_convert_type(rows[:, 0], jnp.uint32)
    return jnp.where(jnp.arange(rows.shape[0], dtype=jnp.int32) < count, keys, KEY_MAX)


def _sort_body(spec: SortSpec, keys: jnp.ndarray, payload: jnp.ndarray, num_valid: jnp.ndarray):
    n = spec.num_executors
    nv = num_valid[0].astype(jnp.int32)

    # 1. Local sort, key and payload as one row (padding rows last).
    idx = jnp.arange(spec.capacity, dtype=jnp.int32)
    rows = sort_rows(_keyed_rows(spec, keys, payload), 1, nv)
    skeys = _sorted_keys(rows, nv)

    # 2. Splitters -> per-row destination executor (padding rows -> n, never sent).
    splitters = _global_splitters(spec, skeys, nv)
    owners = jnp.searchsorted(splitters, skeys, side="right").astype(jnp.int32)
    owners = jnp.where(idx < nv, owners, n)

    # 3. One exchange moves key+payload together.
    # keys already sorted => owners are non-decreasing: rows are dest-contiguous.
    sizes, send_sizes, recv_sizes, output_offsets = size_matrix_from_owners(
        spec.axis_name, n, owners
    )
    cspec = ColumnarSpec(
        num_executors=n,
        capacity=spec.capacity,
        recv_capacity=spec.recv_capacity,
        width=spec.width + 1,
        dtype=spec.dtype,
        axis_name=spec.axis_name,
        impl=spec.impl,
    )
    xchg = columnar_shard_ragged if spec.impl == "ragged" else columnar_shard_dense
    recv, recv_sizes = xchg(cspec, rows, send_sizes, recv_sizes, output_offsets)

    # 4. Final local sort of the received range.
    total = recv_sizes.sum().astype(jnp.int32)
    out = sort_rows(recv, 1, total)
    return _sorted_keys(out, total), out[:, 1:], total[None]


def _sort_body_single(spec: SortSpec, keys: jnp.ndarray, payload: jnp.ndarray, num_valid: jnp.ndarray):
    """n=1 degenerate sample sort: ONE local sort.

    The distributed body would sort locally, self-exchange ~100 B/row, and
    sort the (recv_capacity-padded) receive buffer again — twice the sort and
    a pointless copy."""
    nv = num_valid[0].astype(jnp.int32)
    # padding rows come out last and zeroed, the collective lowerings' output
    # contract: the caller's padding payload does not leak through
    out = sort_rows(_keyed_rows(spec, keys, payload), 1, nv)
    out_keys, out_pay = _sorted_keys(out, nv), out[:, 1:]
    pad = spec.recv_capacity - spec.capacity
    if pad:
        out_keys = jnp.concatenate([out_keys, jnp.full(pad, KEY_MAX, jnp.uint32)])
        out_pay = jnp.concatenate([out_pay, jnp.zeros((pad, spec.width), spec.dtype)])
    return out_keys, out_pay, nv[None]


def build_distributed_sort(mesh: Mesh, spec: SortSpec):
    """Compile the full distributed sort for ``mesh``.

    Returns jitted ``fn(keys, payload, num_valid) -> (keys_out, payload_out, counts)``:

    * ``keys``: (n * capacity,) uint32, sharded over ``axis_name``;
    * ``payload``: (n * capacity, width) of ``dtype``, row-sharded (same row
      order as ``keys``);
    * ``num_valid``: (n,) int32, sharded — valid rows per shard (rest padding);
    * ``keys_out``: (n * recv_capacity,) uint32 — shard j = j-th global key
      range, ascending; concatenating valid prefixes in mesh order yields the
      fully sorted keys.  Padding tail is KEY_MAX.
    * ``payload_out``: rows permuted identically to ``keys_out``.  The sort is
      **stable**: rows with equal keys keep their global input order (this is
      a contract, not an accident — the n=1 lowering's padding handling
      already requires stable argsort, the exchange lands senders in rank
      order, and the differential fuzz asserts row-exact agreement with
      ``np.argsort(kind='stable')`` under heavy duplication);
    * ``counts``: (n,) int32 — valid rows per output shard.  Any value >
      ``recv_capacity`` means splitter skew overflowed the headroom; re-run
      with a larger ``recv_capacity``.
    """
    if spec.num_executors != mesh.devices.size:
        raise ValueError(f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}")
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    spec.validate()
    ax = spec.axis_name

    body = _sort_body_single if spec.impl == "single" else _sort_body
    shard = shard_map(
        functools.partial(body, spec),
        mesh=mesh,
        in_specs=(P(ax), P(ax, None), P(ax)),
        out_specs=(P(ax), P(ax, None), P(ax)),
        check_vma=False,
    )
    fn = jax.jit(
        shard,
        in_shardings=(
            NamedSharding(mesh, P(ax)),
            NamedSharding(mesh, P(ax, None)),
            NamedSharding(mesh, P(ax)),
        ),
        out_shardings=(
            NamedSharding(mesh, P(ax)),
            NamedSharding(mesh, P(ax, None)),
            NamedSharding(mesh, P(ax)),
        ),
    )
    fn.spec = spec
    return fn


def oracle_sort(keys: np.ndarray, payload: np.ndarray):
    """CPU reference: globally sorted (keys, payload) for oracle checks."""
    order = np.argsort(keys, kind="stable")
    return keys[order], payload[order]


def _sort_one_batch(
    mesh: Mesh,
    spec: SortSpec,
    keys: np.ndarray,
    payload: np.ndarray,
    max_attempts: int,
    fns: dict,
):
    """One <=``n*capacity``-row chunk through the compiled sort: shard, run,
    retry with doubled ``recv_capacity`` on splitter-skew overflow, unpack the
    valid prefixes.  ``fns`` caches compiled sorts by full spec so callers
    looping over batches (run_external_sort) compile once per capacity."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = spec.num_executors
    pk, pv, nv = shard_rows_host(
        keys, payload, n, spec.capacity, key_fill=int(KEY_MAX), value_dtype=spec.dtype
    )
    key_sh = NamedSharding(mesh, P(spec.axis_name))
    row_sh = NamedSharding(mesh, P(spec.axis_name, None))
    gk = jax.device_put(pk, key_sh)
    gv = jax.device_put(pv, row_sh)
    gn = jax.device_put(nv, key_sh)

    attempt_spec = spec
    for _ in range(max_attempts):
        rc = attempt_spec.recv_capacity
        fn = fns.get(attempt_spec)  # keyed by the full spec: a reused cache
        if fn is None:              # with a different spec must recompile
            fn = fns[attempt_spec] = build_distributed_sort(mesh, attempt_spec)
        out_keys, out_pay, counts = fn(gk, gv, gn)
        counts_h = np.asarray(counts)
        if (counts_h <= rc).all():
            sk, sp = unpack_shard_prefixes((out_keys, out_pay), counts_h, rc)
            return sk, sp
        attempt_spec = replace(attempt_spec, recv_capacity=2 * rc)
    raise RuntimeError(
        f"sort overflowed recv_capacity {attempt_spec.recv_capacity // 2} after "
        f"{max_attempts} doublings — key distribution too skewed for range "
        f"partitioning (most keys identical?)"
    )


def run_distributed_sort(
    mesh: Mesh,
    spec: SortSpec,
    keys: np.ndarray,
    payload: np.ndarray,
    max_attempts: int = 3,
):
    """Host driver: shard, run the compiled sort, and retry with doubled
    ``recv_capacity`` when splitter skew overflows a shard — the re-run
    contract the spec documents, automated (the TeraSort job surface, like
    ``run_transitive_closure`` is SparkTC's).

    ``keys``: (T,) uint32; ``payload``: (T, width).  Returns (sorted keys,
    payload rows in the same order) as host arrays.  Raises after
    ``max_attempts`` doublings (pathological skew: most keys identical).
    """
    n = spec.num_executors
    total = keys.shape[0]
    cap = spec.capacity
    if total > n * cap:
        raise ValueError(f"{total} rows exceed {n} x {cap} capacity")
    if mesh.devices.size != n:
        raise ValueError(f"mesh size {mesh.devices.size} != num_executors {n}")
    return _sort_one_batch(mesh, spec, keys, payload, max_attempts, {})


def merge_sorted_runs(run_keys, run_payloads):
    """Stable host merge of sorted (keys, payload) runs into one sorted pair.

    Pairwise ``searchsorted`` merges over (key, global-row-index) only —
    log2(R) linear passes moving 8 B/row — then each run's payload is placed
    ONCE, read sequentially and scattered to its final positions (no
    concatenated intermediate; moving the wide payload through every level
    measured 5x slower, and the concat another ~1.7x on the final phase).
    Stability contract matches the device sort's: runs must be in row order
    (run i holds earlier input rows than run i+1); within a merge, equal keys
    from the later run land after the earlier run's (``side='right'`` ranks
    place them past the equal block)."""
    run_keys = [np.asarray(k) for k in run_keys]
    run_payloads = list(run_payloads)
    if not run_keys:
        raise ValueError("no runs to merge")
    if len(run_keys) != len(run_payloads) or any(
        len(k) != len(p) for k, p in zip(run_keys, run_payloads)
    ):
        raise ValueError(
            "run_keys and run_payloads must pair up row-for-row "
            f"({[len(k) for k in run_keys]} keys vs "
            f"{[len(p) for p in run_payloads]} payload rows)"
        )
    offsets = np.cumsum([0] + [len(k) for k in run_keys[:-1]])
    run_idx = [
        np.arange(len(k), dtype=np.int64) + off for k, off in zip(run_keys, offsets)
    ]
    while len(run_keys) > 1:
        nk, ni = [], []
        for i in range(0, len(run_keys) - 1, 2):
            k1, x1 = run_keys[i], run_idx[i]
            k2, x2 = run_keys[i + 1], run_idx[i + 1]
            # output position of each k2 element: its searchsorted-right rank
            # among k1 plus the k2 elements already placed before it
            pos2 = np.searchsorted(k1, k2, side="right") + np.arange(len(k2))
            total = len(k1) + len(k2)
            mk = np.empty(total, k1.dtype)
            mx = np.empty(total, np.int64)
            mask = np.ones(total, bool)
            mask[pos2] = False
            mk[pos2] = k2
            mx[pos2] = x2
            mk[mask] = k1
            mx[mask] = x1
            nk.append(mk)
            ni.append(mx)
        if len(run_keys) % 2:
            nk.append(run_keys[-1])
            ni.append(run_idx[-1])
        run_keys, run_idx = nk, ni
    perm = run_idx[0]
    if len(run_payloads) == 1:
        return run_keys[0], run_payloads[0][perm]
    total = len(perm)
    inv = np.empty(total, np.int64)
    inv[perm] = np.arange(total, dtype=np.int64)  # dest position per global row
    out = np.empty((total, run_payloads[0].shape[1]), run_payloads[0].dtype)
    for off, p in zip(offsets, run_payloads):
        out[inv[off : off + len(p)]] = p
    return run_keys[0], out


def run_external_sort(
    mesh: Mesh,
    spec: SortSpec,
    keys: np.ndarray,
    payload: np.ndarray,
    max_attempts: int = 3,
    fns: Optional[dict] = None,
):
    """Out-of-core TeraSort driver: datasets past device capacity are sorted
    in device batches of ``num_executors * capacity`` rows (one compiled sort
    reused across batches), then the sorted runs are merged on the host.

    One chip's HBM bounds the rows a single device sort can hold; this
    driver is how the "TeraSort 10GB" workload (BASELINE.json configs[1])
    runs on hardware that can't hold the dataset: the device does the
    O(N log N) work per batch, the host does log2(runs) linear merge passes.
    Peak host memory is ~2.5x the dataset (input + runs being merged).

    Same contract as :func:`run_distributed_sort` (stable, oracle-exact),
    same skew-retry behavior per batch.  Pass a dict as ``fns`` to keep the
    compiled sorts across calls (repeat-measurement loops would otherwise
    re-trace every call and time compilation)."""
    n = spec.num_executors
    batch = n * spec.capacity
    total = keys.shape[0]
    if mesh.devices.size != n:
        raise ValueError(f"mesh size {mesh.devices.size} != num_executors {n}")
    if fns is None:
        fns = {}  # SortSpec -> compiled sort, reused across batches
    if total <= batch:
        return _sort_one_batch(mesh, spec, keys, payload, max_attempts, fns)

    run_keys, run_payloads = [], []
    for start in range(0, total, batch):
        sk, sp = _sort_one_batch(
            mesh, spec, keys[start : start + batch], payload[start : start + batch],
            max_attempts, fns,
        )
        run_keys.append(sk)
        run_payloads.append(sp)
    return merge_sorted_runs(run_keys, run_payloads)
