"""The Zipf-keyed GroupByTest records at a deployment whose peer regions are
smaller than its largest blocks, and what that does to the job by the layout
alone.

The records, the law and the plain GroupBy they are checked against are
``references/groupby-zipf.py``'s, from the same ``--seed`` (its generator is
loaded, not copied): a block that is staged in pieces is handed back as the
bytes that were written, so ``make_records``, ``check`` and ``complete`` are
the control's, and the plain reference of the semantics is the plain GroupBy
it already holds (``FullCheck``).

What differs is the deployment: the configuration states the peer region a
(map, reduce) block is staged into (``region_bytes``: the executor's staging
divided by the executors of the mesh it stands for, ``deployment_executors``),
and ``geometry`` adds, from the blocks' sizes and that one number, which
blocks are longer than a region, their bytes, the fewest pieces they can be
staged in and how many of them the hottest reduce task reads.  It encodes no
placement rule of the store — where a piece begins hangs on what its region
held before, which is the store's business — only what no placement can
avoid.  Nothing here imports the code under test;
``tests/benchmark/test_benchmark_regions.py`` holds the configuration's file
to it.
"""

from __future__ import annotations

import numpy as np

from benchmark.cells import load_module

zipf = load_module("references", "groupby-zipf")

Records = zipf.Records
TaskCheck = zipf.TaskCheck
FullCheck = zipf.FullCheck
HEADER_BYTES = zipf.HEADER_BYTES
record_bytes = zipf.record_bytes
make_records = zipf.make_records
layout = zipf.layout


def block_bytes(config: dict) -> np.ndarray:
    """(mappers, reducers) framed bytes of every block, by the fixed layout."""
    width = record_bytes(int(config["value_bytes"]))
    return np.stack([np.diff(layout(config, m)[1]) for m in range(int(config["mappers"]))]) * width


def geometry(config: dict, chips: int) -> dict:
    """``groupby-zipf``'s geometry and, beside it, what the deployment's peer
    region (``region_bytes``) and the rows a block is padded to
    (``row_bytes``) make of the blocks: the same for every ``--seed``."""
    out = zipf.geometry(config, chips)
    region, row = int(config["region_bytes"]), int(config["row_bytes"])
    blocks = block_bytes(config)
    padded = -(-blocks // row) * row
    over = padded > region
    hottest = int(np.argmax(blocks.sum(axis=0)))
    out.update(
        region_bytes=region,
        blocks_over_a_region=int(np.count_nonzero(over)),
        bytes_in_blocks_over_a_region=int(blocks[over].sum()),
        share_of_the_job_in_blocks_over_a_region=float(blocks[over].sum() / blocks.sum()),
        least_pieces=int((-(-padded[over] // region)).sum()),
        largest_block_in_regions=float(blocks.max() / region),
        hottest_reducer_blocks_over_a_region=int(np.count_nonzero(over[:, hottest])),
        hottest_reducer_blocks=int(np.count_nonzero(blocks[:, hottest])),
        map_tasks_with_a_block_over_a_region=int(np.count_nonzero(over.any(axis=1))),
    )
    return out
