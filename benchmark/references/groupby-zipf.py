"""GroupByTest records whose keys follow a popularity law, and the plain
GroupBy they are checked against.

The job and the record are ``references/groupby.py``'s (``GroupByTest
<mappers> <pairs> <value_bytes> <reducers>``: ``(int key, value_bytes random
bytes)`` in the typed record codec's wire format, ``key mod reducers``); its
``Records``, ``TaskCheck`` and ``FullCheck`` are loaded, not copied.  What
differs is which keys a mapper draws (``keys: "zipf"``):

* a record's *rank* is drawn Zipf(``zipf_s``) over ``distinct_keys`` ranks —
  rank ``k`` (from 1) with probability ``k**-s / H(distinct_keys, s)``, by
  inversion of the exact cumulative sum, no rejection and no approximation;
* rank -> *key id* in ``[0, distinct_keys)`` by ONE fixed permutation, so the
  popular keys land on arbitrary reducers, as hashing lands them;
* reducer = ``key id mod reducers``.

The ranks and the permutation come from the fixed layout stream
(``LAYOUT_DRAW``), as the accepted generator's block layout does: every
``--seed`` stages the same ragged blocks in the same staging rounds, so the
amount of work does not hang on the seed.  From ``--seed`` come every value
and the rest of a key: key = key id + ``stride`` x a lift drawn once a key id
(``stride`` the multiple of ``reducers`` at or above ``distinct_keys``), which
keeps ``key mod reducers`` and the groups — two records share a key under one
seed exactly when they share it under another.

``geometry`` gives what the law does to the job (bytes a reducer, the hottest
block, bytes a chip receives) from the layout alone: the numbers a
configuration's file states.  Nothing here imports the code under test.
"""

from __future__ import annotations

import functools
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import numpy as np

from benchmark.cells import load_module

groupby = load_module("references", "groupby")

Records = groupby.Records
TaskCheck = groupby.TaskCheck
FullCheck = groupby.FullCheck
HEADER_BYTES = groupby.HEADER_BYTES
LAYOUT_DRAW = groupby.LAYOUT_DRAW
record_bytes = groupby.record_bytes
_MASK = (1 << 64) - 1
#: second words of the layout stream's seeds: a mapper's ranks, the permutation
_RANKS, _PERMUTATION = 1, 2
#: second word of the seed stream that lifts key ids to keys
_LIFT = 3


def harmonic(n: int, s: float) -> float:
    """H(n, s), the generalised harmonic number the law is normalised by."""
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -s))


@functools.lru_cache(maxsize=4)
def _law(distinct: int, s: float) -> Tuple[np.ndarray, np.ndarray]:
    """The law's cumulative distribution over the ranks and the fixed
    permutation rank -> key id."""
    weights = np.arange(1, distinct + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    permutation = np.random.default_rng([LAYOUT_DRAW, _PERMUTATION]).permutation(distinct)
    return cdf, permutation


def draw_ranks(config: dict, m: int) -> np.ndarray:
    """Mapper ``m``'s ranks (from 0, the most popular) in the order drawn:
    the same for every ``--seed``."""
    cdf, _ = _law(int(config["distinct_keys"]), float(config["zipf_s"]))
    u = np.random.default_rng([LAYOUT_DRAW, _RANKS, m]).random(int(config["pairs_per_mapper"]))
    return np.searchsorted(cdf, u, side="right")  # cdf[-1] is 1.0 and u < 1


def layout(config: dict, m: int) -> Tuple[np.ndarray, np.ndarray]:
    """Mapper ``m``'s key ids grouped by reducer (inside a reducer in the order
    drawn) and ``bounds``: reducer ``r``'s records are ``[bounds[r],
    bounds[r + 1])``."""
    reducers = int(config["reducers"])
    _, permutation = _law(int(config["distinct_keys"]), float(config["zipf_s"]))
    key_ids = permutation[draw_ranks(config, m)]
    key_ids = key_ids[np.argsort(key_ids % reducers, kind="stable")]
    return key_ids, np.searchsorted(key_ids % reducers, np.arange(reducers + 1))


def _check(config: dict) -> None:
    if config["keys"] != "zipf":
        raise ValueError(f"unknown key distribution {config['keys']!r}")
    if not (float(config["zipf_s"]) > 0 and int(config["distinct_keys"]) >= int(config["reducers"])):
        raise ValueError("the law needs zipf_s > 0 and at least a key a reducer")


def _stride(config: dict) -> int:
    """The least multiple of ``reducers`` that holds every key id."""
    reducers = int(config["reducers"])
    return -(-int(config["distinct_keys"]) // reducers) * reducers


def _mapper(config: dict, seed: int, m: int, lift: np.ndarray):
    """One mapper's blocks and its part of the reference, as
    ``groupby._mapper`` makes them: the values are one draw, the 19 bytes
    before each are then overwritten with the codec's framing."""
    pairs = int(config["pairs_per_mapper"])
    vbytes = int(config["value_bytes"])
    reducers = int(config["reducers"])
    width = record_bytes(vbytes)
    key_ids, bounds = layout(config, m)
    keys = key_ids + _stride(config) * lift[key_ids]
    rng = np.random.default_rng([seed, m])
    words = rng.integers(0, 2**64, size=-(-pairs * width // 8), dtype=np.uint64)
    rows = words.view(np.uint8)[: pairs * width].reshape(pairs, width)
    rows[:, :6] = np.frombuffer(b"t" + (2).to_bytes(4, "big") + b"i", dtype=np.uint8)
    rows[:, 6:14] = keys.astype(">i8").view(np.uint8).reshape(pairs, 8)
    rows[:, 14:HEADER_BYTES] = np.frombuffer(b"b" + vbytes.to_bytes(4, "big"), dtype=np.uint8)
    blocks = [
        (r, rows[bounds[r] : bounds[r + 1]].tobytes())
        for r in range(reducers)
        if bounds[r + 1] > bounds[r]
    ]
    first8 = np.zeros((pairs, 8), dtype=np.uint8)
    first8[:, : min(8, vbytes)] = rows[:, HEADER_BYTES : HEADER_BYTES + 8]
    lead = first8.view("<u8").reshape(pairs)
    digests = {
        int(r): int(lead[bounds[r] : bounds[r + 1]].sum(dtype=np.uint64))
        for r in np.flatnonzero(np.diff(bounds))
    }
    crcs = [zlib.crc32(rows[i, HEADER_BYTES:]) for i in range(pairs)]
    return blocks, np.diff(bounds), digests, keys.tolist(), crcs


def make_records(config: dict, seed: int) -> Records:
    """The output of the configuration's ``mappers`` mappers from ``seed``."""
    _check(config)
    num_mappers = int(config["mappers"])
    reducers = int(config["reducers"])
    vbytes = int(config["value_bytes"])
    distinct, stride = int(config["distinct_keys"]), _stride(config)
    # non-negative int keys below Int.MaxValue, as the source job draws them
    lift = np.random.default_rng([seed, _LIFT, 0]).integers(
        0, (2**31 - 1 - distinct) // stride, size=distinct, dtype=np.int64)
    with ThreadPoolExecutor(max_workers=min(8, num_mappers)) as pool:
        made = list(pool.map(lambda m: _mapper(config, seed, m, lift), range(num_mappers)))
    blocks: List[List[Tuple[int, bytes]]] = []
    groups: Dict[int, List[int]] = {}
    counts = np.zeros(reducers, dtype=np.int64)
    digests = [0] * reducers
    for mapper_blocks, mapper_counts, mapper_digests, keys, crcs in made:
        blocks.append(mapper_blocks)
        counts += mapper_counts
        for r, digest in mapper_digests.items():
            digests[r] = (digests[r] + digest) & _MASK
        for key, crc in zip(keys, crcs):
            groups.setdefault(key, []).append(crc)
    expected = [(int(c), int(c) * vbytes, digests[r]) for r, c in enumerate(counts)]
    return Records(reducers, blocks, expected, groups)


def geometry(config: dict, chips: int) -> dict:
    """What the law does to the job, from the layout alone (no value is made,
    so it is the same for every ``--seed``): the framed bytes of every reducer
    and of every (mapper, reducer) block, and the bytes each of ``chips``
    chips receives when the reducers are dealt to them in contiguous, balanced
    ranges (Spark's range partitioning of reduce ids over executors)."""
    _check(config)
    reducers = int(config["reducers"])
    width = record_bytes(int(config["value_bytes"]))
    block_bytes = np.stack(
        [np.diff(layout(config, m)[1]) for m in range(int(config["mappers"]))]) * width
    reducer_bytes = block_bytes.sum(axis=0)
    base, extra = divmod(reducers, chips)
    ends = np.cumsum([base + (p < extra) for p in range(chips)])
    chip_bytes = [int(part.sum()) for part in np.split(reducer_bytes, ends[:-1])]
    median = float(np.median(reducer_bytes))
    return {
        "job_bytes": int(block_bytes.sum()),
        "blocks": int(np.count_nonzero(block_bytes)),
        "hottest_reducer_bytes": int(reducer_bytes.max()),
        "median_reducer_bytes": median,
        "hottest_over_median": float(reducer_bytes.max() / median),
        "smallest_reducer_bytes": int(reducer_bytes.min()),
        "largest_block_bytes": int(block_bytes.max()),
        "smallest_block_bytes": int(block_bytes[block_bytes > 0].min()),
        "blocks_over_4MiB": int(np.count_nonzero(block_bytes > 4 << 20)),
        "chip_received_bytes": chip_bytes,
    }
