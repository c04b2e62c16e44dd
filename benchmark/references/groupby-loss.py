"""GroupByTest records for a job that loses an executor mid-exchange, and what
the loss must cost by the layout alone.

The records and the plain GroupBy they are checked against are
``references/groupby.py``'s, from the same ``--seed`` (its generator is
loaded, not copied): a job that loses an executor and recovers hands its
reduce tasks the very records of the job that loses nothing, so
``make_records``, ``check`` and ``complete`` are the control's.

``loss_geometry`` is what the event is to the store and the exchange, in plain
Python over the block layout (no value is made, so it is the same for every
``--seed``): the map tasks the lost executor owned, their blocks and bytes —
what must come back from its ring successor's replicas — the staging rounds
that had been submitted when it died, the rounds the recovery runs again, and
the sub-exchanges on the shrunk mesh that carry rows.  Nothing here imports
the code under test; ``tests/benchmark/test_benchmark_loss.py`` holds it to
the configuration's file, to the traffic's file and to the driver's ``loss:``
line.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.cells import load_module

groupby = load_module("references", "groupby")

HEADER_BYTES = groupby.HEADER_BYTES
Records = groupby.Records
TaskCheck = groupby.TaskCheck
FullCheck = groupby.FullCheck
record_bytes = groupby.record_bytes
make_records = groupby.make_records


def block_bytes(config: dict) -> np.ndarray:
    """(mappers, reducers) framed bytes of every block, by the generator's
    fixed layout draw: which reducer each record of a mapper goes to."""
    pairs, reducers = int(config["pairs_per_mapper"]), int(config["reducers"])
    width = record_bytes(int(config["value_bytes"]))
    counts = [
        np.bincount(np.random.default_rng([groupby.LAYOUT_DRAW, m]).integers(0, reducers, size=pairs),
                    minlength=reducers)
        for m in range(int(config["mappers"]))
    ]
    return np.stack(counts) * width


def staged_rounds(config: dict, chips: int) -> List[List[np.ndarray]]:
    """``rounds[e][k][p]``: rows of executor ``e``'s staging round ``k`` that
    go to executor ``p``, for the store the configuration's ``store`` block
    describes: map task ``m`` writes on executor ``m mod chips`` in task order,
    its blocks in reducer order; the staging buffer is one region an executor,
    the reducers dealt to the executors in contiguous, balanced ranges; a
    block takes its bytes padded to the alignment; a round rolls when the
    region of the block's owner cannot take the block."""
    store = config["store"]
    align, region = int(store["alignment"]), int(store["staging_bytes"]) // chips
    reducers = int(config["reducers"])
    base, extra = divmod(reducers, chips)
    owner = np.repeat(np.arange(chips), [base + (p < extra) for p in range(chips)])
    sizes = block_bytes(config)
    rounds: List[List[np.ndarray]] = []
    for e in range(chips):
        mine = [np.zeros(chips, dtype=np.int64)]
        for m in range(e, len(sizes), chips):
            for r in np.flatnonzero(sizes[m]):
                padded = -(-int(sizes[m][r]) // align) * align
                if mine[-1][owner[r]] + padded > region:
                    mine.append(np.zeros(chips, dtype=np.int64))
                mine[-1][owner[r]] += padded
        rounds.append([used // align for used in mine])
    return rounds


def loss_geometry(config: dict, traffic: dict, chips: int = 4) -> Dict[str, object]:
    """What the loss of ``traffic['lost_executor']`` at the submit of staging
    round ``traffic['lost_at_round']`` is to the job: the dead executor's map
    tasks (``m mod chips``), their blocks and bytes (restaged from the ring
    successor's replicas), the replica tier's bytes (one copy of every block),
    the rounds submitted before the kill (aborted) and run again (all), the
    shrunk mesh — the largest power of two of survivors, the first of them in
    order — and the sub-exchanges that carry rows: a round is re-run as
    ``waves x waves`` pairs of (senders' wave, consumers' wave), logical
    executor ``l`` in wave ``l // m``; a pair no sender of which has a row for
    a consumer of it dispatches nothing."""
    lost, at_round = int(traffic["lost_executor"]), int(traffic["lost_at_round"])
    sizes = block_bytes(config)
    maps = [m for m in range(len(sizes)) if m % chips == lost]
    rounds = staged_rounds(config, chips)
    num_rounds = max(len(r) for r in rounds)
    if not 0 <= at_round < num_rounds:
        raise ValueError(f"lost_at_round {at_round} outside the job's {num_rounds} staging rounds")
    survivors = [e for e in range(chips) if e != lost]
    m = 1 << (len(survivors).bit_length() - 1)
    waves = -(-chips // m)
    per_round = []
    for k in range(num_rounds):
        rows = np.stack([r[k] if k < len(r) else np.zeros(chips, dtype=np.int64) for r in rounds])
        per_round.append(sum(
            bool(rows[i * m:(i + 1) * m, j * m:(j + 1) * m].sum())
            for i in range(waves) for j in range(waves)))
    return {
        "lost_executor": lost,
        "replica_holder": (lost + 1) % chips,
        "survivors": survivors,
        "lost_map_tasks": maps,
        "restaged_blocks": int(np.count_nonzero(sizes[maps])),
        "restaged_bytes": int(sizes[maps].sum()),
        "replicated_bytes": int(sizes.sum()),
        "rounds": num_rounds,
        "rounds_aborted": at_round,
        "rounds_rerun": num_rounds,
        "shrunk_mesh": survivors[:m],
        "waves": waves,
        "subexchanges": int(sum(per_round)),
        "subexchanges_per_round": per_round,
    }
