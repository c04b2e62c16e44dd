"""GroupByTest records for a job that loses an executor AFTER its exchange,
and what the loss must cost the reduce stage by the layout alone.

The records and the plain GroupBy they are checked against are
``references/groupby.py``'s, from the same ``--seed``, and the block layout
is ``references/groupby-loss.py``'s (both loaded, not copied): a reduce task
the engine re-places on a live executor hands its consumer the very records
of the job that loses nothing, so ``make_records``, ``check`` and
``complete`` are the control's.

``read_loss_geometry`` is what the event is to the reduce stage, in plain
Python over the block layout (no value is made, so it is the same for every
``--seed``): the reduce partitions the lost executor had received (its
contiguous range), the live executor each of their tasks is re-placed on,
and for every block such a task must pull, where a copy still lies — the
staging of the executor that ran the map task (``m mod chips``) or, that one
being lost, the replica tier of its first live ring successor within the
configuration's ``replication_factor``.  ``replaced_tasks`` is the same task
by task.  Nothing here imports the code under test;
``tests/benchmark/test_benchmark_readloss.py`` holds it to the
configuration's file, to the traffic's file and to the driver's ``readloss:``
line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from benchmark.cells import load_module

loss = load_module("references", "groupby-loss")

HEADER_BYTES = loss.HEADER_BYTES
Records = loss.Records
TaskCheck = loss.TaskCheck
FullCheck = loss.FullCheck
record_bytes = loss.record_bytes
make_records = loss.make_records
block_bytes = loss.block_bytes


def lost_executors(traffic: dict) -> List[int]:
    lost = traffic["lost_executor"]
    return [int(e) for e in lost] if isinstance(lost, (list, tuple)) else [int(lost)]


def owner_of_reduce(reducers: int, chips: int) -> np.ndarray:
    """The executor that receives each reduce partition: contiguous, balanced
    ranges in executor order (the first ``reducers mod chips`` one longer)."""
    base, extra = divmod(reducers, chips)
    return np.repeat(np.arange(chips), [base + (p < extra) for p in range(chips)])


def holder_of(stager: int, lost: List[int], chips: int, factor: int) -> Optional[int]:
    """Where a copy of a block staged on ``stager`` still lies: the stager
    itself, else its first live ring successor among the ``factor`` that hold
    its replicas, else nowhere."""
    if stager not in lost:
        return stager
    for step in range(1, factor + 1):
        successor = (stager + step) % chips
        if successor != stager and successor not in lost:
            return successor
    return None


def replaced_tasks(config: dict, traffic: dict, chips: int = 4) -> List[Optional[Dict[str, object]]]:
    """For every reduce task, what it must pull, or ``None`` for a task the
    loss does not touch: the live executor it is re-placed on
    (``survivors[r mod len(survivors)]``), its non-empty blocks, those of
    them that only a replica tier still holds, the same in bytes, the blocks
    no live executor holds (0 while the guarantee stands), and ``sources``:
    (holder, whether it is a replica holder) of every block that is served."""
    lost = lost_executors(traffic)
    owner = owner_of_reduce(int(config["reducers"]), chips)
    survivors = [e for e in range(chips) if e not in lost]
    factor = int(config["conf"].get("replication_factor", 0))
    sizes = block_bytes(config)
    tasks: List[Optional[Dict[str, object]]] = []
    for reduce_id in range(len(owner)):
        if int(owner[reduce_id]) not in lost:
            tasks.append(None)
            continue
        task = dict.fromkeys(("pulled_blocks", "pulled_bytes", "replica_blocks", "replica_bytes", "unserved_blocks"), 0)
        sources = []
        for m in np.flatnonzero(sizes[:, reduce_id]):
            stager, nbytes = int(m) % chips, int(sizes[m, reduce_id])
            holder = holder_of(stager, lost, chips, factor)
            task["pulled_blocks"] += 1
            task["pulled_bytes"] += nbytes
            if holder is None:
                task["unserved_blocks"] += 1
                continue
            sources.append((holder, holder != stager))
            if holder != stager:
                task["replica_blocks"] += 1
                task["replica_bytes"] += nbytes
        tasks.append({"executor": survivors[reduce_id % len(survivors)], **task, "sources": sources})
    return tasks


def replaced_task(config: dict, traffic: dict, chips: int, reduce_id: int) -> Optional[Dict[str, object]]:
    """``replaced_tasks`` for one reduce task."""
    return replaced_tasks(config, traffic, chips)[reduce_id]


def read_loss_geometry(config: dict, traffic: dict, chips: int = 4) -> Dict[str, object]:
    """What the loss of ``traffic['lost_executor']`` after the exchange is to
    the job's reduce stage: the partitions whose received copy died, where
    their tasks run instead, the blocks those tasks pull and from where — by
    map owner's staging and by replica holder — and their bytes; the tasks
    the loss does not touch; and what it is NOT: no staging round runs again
    and no recovery is counted (the exchange had returned)."""
    lost = lost_executors(traffic)
    factor = int(config["conf"].get("replication_factor", 0))
    survivors = [e for e in range(chips) if e not in lost]
    job_bytes = int(block_bytes(config).sum())
    tasks = replaced_tasks(config, traffic, chips)
    replaced = [r for r, task in enumerate(tasks) if task is not None]
    sources = [source for r in replaced for source in tasks[r]["sources"]]
    count = lambda holder, replica: sum(source == (holder, replica) for source in sources)
    total = lambda key: int(sum(tasks[r][key] for r in replaced))
    return {
        "lost_executor": lost[0] if len(lost) == 1 else lost,
        "replica_holder": holder_of(lost[0], lost, chips, factor),
        "survivors": survivors,
        "lost_map_tasks": [m for m in range(int(config["mappers"])) if m % chips in lost],
        "replaced_tasks": len(replaced),
        "replaced_partitions": [replaced[0], replaced[-1]] if replaced else [],
        "tasks_placed_on": {str(e): sum(tasks[r]["executor"] == e for r in replaced) for e in survivors},
        "pulled_blocks": total("pulled_blocks"),
        "pulled_bytes": total("pulled_bytes"),
        "pulled_from_staging": {str(e): count(e, False) for e in survivors},
        "pulled_from_replicas": {str(e): count(e, True) for e in survivors},
        "staging_blocks": sum(not replica for _, replica in sources),
        "replica_blocks": total("replica_blocks"),
        "replica_bytes": total("replica_bytes"),
        "unserved_blocks": total("unserved_blocks"),
        "undisturbed_tasks": len(tasks) - len(replaced),
        "job_bytes": job_bytes,
        "replicated_bytes": job_bytes * factor,
        "rounds_rerun": 0,
        "recoveries": 0,
    }
