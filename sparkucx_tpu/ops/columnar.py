"""Device-resident columnar shuffle — the ``GpuColumnarExchange`` analogue.

BASELINE.md lists "RAPIDS GpuColumnarExchange columnar shuffle -> TPU HBM" as a
target config: on GPU Spark, columnar batches are shuffled device-to-device
without ever landing in host memory.  This module is that capability on TPU —
and it is the *most* TPU-native path in the framework: map output that is
already a ``jax.Array`` (a Spark-SQL-style columnar batch, model activations,
any fixed-width rows) is repartitioned entirely in HBM:

    rows sorted by destination (on device)  ->  ragged all_to_all over ICI  ->
    each executor holds exactly its rows, still in HBM

No byte store, no staging regions, no host round-trip — one jitted function.
The row-granular size matrix is computed on device from the owner vector
(``bincount``), playing the MapperInfo role entirely inside the collective.

Like ops/exchange.py it has two bit-identical lowerings (``ragged`` for TPU,
``dense`` for backends without a ragged-all-to-all kernel), selected the same
way.  Layout here is *tight* (rows contiguous after the sort), not slot —
there are no pre-carved regions to respect.

Payload reduction (ops/compress.py) composes with this module on both rails:
rows that spill to the striped TCP wire travel through the per-chunk lossless
codec transparently (``compress.codec`` — the transport encodes/decodes at
the chunk layer, so shuffled bytes are bit-identical either way), and the
partial-aggregate exchange built on these shuffles (ops/relational.py) can
opt into lossy block quantization of its float value lanes
(``quantize.mode``); keys travel bitcast and are never quantized.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import ragged_all_to_all
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import (
    exclusive_cumsum,
    gather_rows,
    ragged_params,
    resolve_collective_impl,
)


@dataclass(frozen=True)
class ColumnarSpec:
    """Static description of one compiled columnar shuffle.

    ``capacity`` / ``recv_capacity`` are per-executor row counts (static shapes;
    pad the input with ``owner = num_executors`` rows — they are never sent).
    ``width`` is the row width in elements of ``dtype``.
    """

    num_executors: int
    capacity: int
    recv_capacity: int
    width: int
    dtype: np.dtype = np.dtype(np.float32)
    axis_name: str = "ex"
    impl: str = "auto"

    def resolve_impl(self, platform: Optional[str] = None) -> "ColumnarSpec":
        return replace(self, impl=resolve_collective_impl(self.impl, platform))


def size_matrix_from_owners(axis_name: str, num_executors: int, owners: jnp.ndarray):
    """Gather the global (n, n) size matrix from each shard's owner vector and
    derive this shard's send/recv sizes and landing offsets — the collective
    MapperInfo analogue shared by the columnar shuffle and the distributed sort.

    Rows with ``owner == num_executors`` are padding and counted nowhere."""
    n = num_executors
    me = jax.lax.axis_index(axis_name)
    counts = jnp.bincount(owners, length=n + 1)[:n].astype(jnp.int32)  # rows me -> j
    sizes = jax.lax.all_gather(counts[None, :], axis_name, tiled=True)  # (n, n)
    # compact-layout ragged params — ONE formula source (exchange.ragged_params)
    # shared with the exchange and covered by tests/test_ragged_plan.py
    _, send_sizes, output_offsets, recv_sizes = ragged_params(sizes, me, None)
    return sizes, send_sizes, recv_sizes, output_offsets


def _sort_and_sizes(spec: ColumnarSpec, rows: jnp.ndarray, owners: jnp.ndarray):
    """Sort rows by destination executor; gather the global size matrix."""
    order = jnp.argsort(owners, stable=True)  # padding (owner == n) sorts last
    sorted_rows = gather_rows(rows, order)
    sorted_owners = owners[order]
    _, send_sizes, recv_sizes, output_offsets = size_matrix_from_owners(
        spec.axis_name, spec.num_executors, owners
    )
    return sorted_rows, sorted_owners, send_sizes, recv_sizes, output_offsets


def columnar_shard_ragged(spec: ColumnarSpec, payload, send_sizes, recv_sizes, output_offsets):
    input_offsets = exclusive_cumsum(send_sizes)
    out = jnp.zeros((spec.recv_capacity, payload.shape[1]), dtype=payload.dtype)
    out = ragged_all_to_all(
        payload,
        out,
        input_offsets.astype(jnp.int32),
        send_sizes.astype(jnp.int32),
        output_offsets.astype(jnp.int32),
        recv_sizes.astype(jnp.int32),
        axis_name=spec.axis_name,
    )
    return out, recv_sizes


def columnar_shard_dense(spec: ColumnarSpec, payload, send_sizes, recv_sizes, output_offsets):
    """Portable lowering: scatter sorted rows into fixed slots, tiled
    all_to_all, then compaction — same receive layout as the ragged path."""
    n = spec.num_executors
    slot = spec.capacity  # worst case: every row goes to one destination
    starts = exclusive_cumsum(send_sizes)

    # slot grid (n, slot, W): row k of dest j's slot <- sorted row starts[j]+k
    k = jnp.arange(slot, dtype=jnp.int32)
    src = starts[:, None] + k[None, :]                        # (n, slot)
    valid = k[None, :] < send_sizes[:, None]
    src = jnp.clip(src, 0, payload.shape[0] - 1)
    slots = jnp.where(valid[..., None], payload[src], jnp.zeros((), dtype=payload.dtype))

    received = jax.lax.all_to_all(slots, spec.axis_name, split_axis=0, concat_axis=0, tiled=True)
    flat = received.reshape(n * slot, payload.shape[1])

    rstarts = exclusive_cumsum(recv_sizes)
    cum = jnp.cumsum(recv_sizes)
    total = cum[-1]
    pos = jnp.arange(spec.recv_capacity, dtype=jnp.int32)
    sender = jnp.clip(jnp.searchsorted(cum, pos, side="right").astype(jnp.int32), 0, n - 1)
    gsrc = sender * slot + (pos - rstarts[sender])
    ok = pos < total
    gathered = gather_rows(flat, jnp.clip(gsrc, 0, n * slot - 1))
    out = jnp.where(ok[:, None], gathered, jnp.zeros((), dtype=payload.dtype))
    return out, recv_sizes


def columnar_body(spec: ColumnarSpec, rows, owners):
    """Shared body: sort once, then exchange the sorted payload."""
    sorted_rows, _, send_sizes, recv_sizes, output_offsets = _sort_and_sizes(spec, rows, owners)
    body = columnar_shard_ragged if spec.impl == "ragged" else columnar_shard_dense
    out, recv_sizes = body(spec, sorted_rows, send_sizes, recv_sizes, output_offsets)
    return out, recv_sizes[None, :]


def build_columnar_shuffle(mesh: Mesh, spec: ColumnarSpec):
    """Compile the device-resident columnar shuffle.

    Returns jitted ``fn(rows, owners) -> (recv_rows, recv_counts)``:

    * ``rows``: (n * capacity, width) of ``dtype``, row-sharded — executor i's
      local rows (padding rows allowed anywhere);
    * ``owners``: (n * capacity,) int32, sharded — destination executor per row;
      use ``num_executors`` for padding rows (never sent);
    * ``recv_rows``: (n * recv_capacity, width) row-sharded — executor j's shard
      holds all rows destined to it, sender-major, each sender's rows in that
      sender's stable pre-sort order;
    * ``recv_counts``: (n, n) int32 row-sharded — rows j received from each i.
    """
    if spec.num_executors != mesh.devices.size:
        raise ValueError(f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}")
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    ax = spec.axis_name

    shard = shard_map(
        functools.partial(columnar_body, spec),
        mesh=mesh,
        in_specs=(P(ax, None), P(ax)),
        out_specs=(P(ax, None), P(ax, None)),
        check_vma=False,
    )
    rows_sharding = NamedSharding(mesh, P(ax, None))
    owners_sharding = NamedSharding(mesh, P(ax))
    counts_sharding = NamedSharding(mesh, P(ax, None))
    fn = jax.jit(
        shard,
        in_shardings=(rows_sharding, owners_sharding),
        out_shardings=(rows_sharding, counts_sharding),
    )
    fn.spec = spec
    return fn


def run_columnar_shuffle(
    mesh: Mesh,
    spec: ColumnarSpec,
    rows,
    owners,
    max_attempts: int = 3,
):
    """Overflow-retry wrapper (the job surface of run_distributed_sort /
    run_grouped_aggregate, for data already resident on device): runs the
    compiled shuffle and doubles ``recv_capacity`` when a destination's row
    count exceeds it.

    ``rows``/``owners`` may be host or device arrays shaped per
    ``build_columnar_shuffle``.  Returns (recv_rows, recv_counts) with the
    final (possibly enlarged) capacity.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = jax.device_put(rows, NamedSharding(mesh, P(spec.axis_name, None)))
    owners = jax.device_put(owners, NamedSharding(mesh, P(spec.axis_name)))
    attempt_spec = spec
    for _ in range(max_attempts):
        fn = build_columnar_shuffle(mesh, attempt_spec)
        recv, counts = fn(rows, owners)
        per_dest = np.asarray(counts).sum(axis=1)
        if (per_dest <= attempt_spec.recv_capacity).all():
            return recv, counts
        attempt_spec = replace(attempt_spec, recv_capacity=2 * attempt_spec.recv_capacity)
    raise RuntimeError(
        f"columnar shuffle overflowed recv_capacity {attempt_spec.recv_capacity // 2} "
        f"after {max_attempts} doublings — destination skew too extreme"
    )


def shard_rows_host(
    keys: np.ndarray,
    values: np.ndarray,
    num_shards: int,
    capacity: int,
    key_fill: int = 0,
    value_dtype=None,
):
    """Deal host (keys, value-rows) into the padded per-shard layout every
    mesh-op driver feeds ``device_put``: contiguous near-equal shares, shard s
    padded to ``capacity`` with ``key_fill`` keys / zero rows.  Returns
    (padded_keys (n*cap,) uint32, padded_values (n*cap, width), num_valid
    (n,) int32).  Shared by run_distributed_sort, run_grouped_aggregate, and
    tests — one definition of the sharding convention."""
    n, cap = num_shards, capacity
    total = len(keys)
    if values.shape[0] != total:
        raise ValueError(
            f"keys/values row mismatch: {total} keys vs {values.shape[0]} value rows"
        )
    if total > n * cap:
        raise ValueError(f"{total} rows exceed {n} x {cap} capacity")
    width = values.shape[1]
    pk = np.full(n * cap, key_fill, np.uint32)
    pv = np.zeros((n * cap, width), value_dtype or values.dtype)
    nv = np.zeros(n, np.int32)
    base, rem = divmod(total, n)
    start = 0
    for s in range(n):
        take = base + (1 if s < rem else 0)
        pk[s * cap : s * cap + take] = keys[start : start + take]
        pv[s * cap : s * cap + take] = values[start : start + take]
        nv[s] = take
        start += take
    return pk, pv, nv


def unpack_shard_prefixes(arrays, counts, capacity: int):
    """Inverse of :func:`shard_rows_host`: concatenate each shard's valid
    prefix from per-shard padded layouts.  ``arrays``: host arrays shaped
    (n * capacity, ...); ``counts``: (n,) valid rows per shard.  Returns the
    unpacked arrays in shard order — with shard_rows_host, the one definition
    of the sharding convention's pack/unpack pair."""
    n = len(counts)
    outs = []
    for a in arrays:
        a2 = np.asarray(a).reshape(n, capacity, *np.asarray(a).shape[1:])
        outs.append(np.concatenate([a2[s, : counts[s]] for s in range(n)]))
    return outs


def owners_from_partitions(
    partition_ids: jnp.ndarray, num_partitions: int, num_executors: int
) -> jnp.ndarray:
    """Map reduce-partition ids to owning executors (the contiguous ranges of
    store/hbm_store.default_peer_ranges, computed on device).  Padding rows
    (partition_id < 0 or >= num_partitions) map to ``num_executors``."""
    base, rem = divmod(num_partitions, num_executors)
    # partition p belongs to executor e iff start(e) <= p < start(e+1)
    starts = jnp.array(
        [e * base + min(e, rem) for e in range(num_executors + 1)], dtype=jnp.int32
    )
    owner = jnp.searchsorted(starts, partition_ids, side="right").astype(jnp.int32) - 1
    invalid = (partition_ids < 0) | (partition_ids >= num_partitions)
    return jnp.where(invalid, num_executors, owner)
