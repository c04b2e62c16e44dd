"""GroupByTest records whose map output is born on the device: the packed
buffer a map task hands the shuffle, and its inverse.

The records and the consumers are ``references/groupby-hbm.py``'s, from the
same ``--seed`` (that module is loaded, not copied, as it loads
``groupby.py``): ``make_records``, and ``Records.check`` with its ``TaskCheck``
/ ``FullCheck`` on the chip.  What this configuration adds is the producer's
side, in plain NumPy and one ``jax.device_put``:

``map_output``  mapper ``m``'s blocks as ONE ``(rows, row_bytes // 4)`` int32
                host array — the blocks back to back in reducer order, each
                from a fresh row, the rest of a block's last row zeros — with
                the reducer ids and the true byte lengths;
``unpack``      the blocks' bytes out of such an array again (tier-1 holds
                ``unpack(*map_output(...)) == records.blocks[m]``);
``on_device``   the array on a device, its capacity rounded up to a multiple
                of ``pack_rows`` rows with a zero tail (a jitted map stage has
                a static output shape).

Nothing here imports the code under test.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from benchmark.cells import load_module

hbm = load_module("references", "groupby-hbm")

Records = hbm.Records
make_records = hbm.make_records


def map_output(records, m: int, row_bytes: int) -> Tuple[np.ndarray, List[int], List[int]]:
    """``(packed, reduce_ids, lengths)`` of mapper ``m``."""
    parts = records.blocks[m]
    reduce_ids = [r for r, _ in parts]
    lengths = [len(payload) for _, payload in parts]
    rows = [-(-n // row_bytes) for n in lengths]
    flat = np.zeros(sum(rows) * row_bytes, dtype=np.uint8)
    at = 0
    for (_, payload), n in zip(parts, rows):
        flat[at : at + len(payload)] = np.frombuffer(payload, dtype=np.uint8)
        at += n * row_bytes
    return flat.view(np.int32).reshape(-1, row_bytes // 4), reduce_ids, lengths


def unpack(packed: np.ndarray, reduce_ids: List[int], lengths: List[int]) -> List[Tuple[int, bytes]]:
    """``[(reduce_id, bytes)]`` of a packed array, in its order."""
    row_bytes = int(packed.shape[1]) * 4
    flat = np.ascontiguousarray(packed).reshape(-1).view(np.uint8)
    out, at = [], 0
    for reduce_id, n in zip(reduce_ids, lengths):
        out.append((reduce_id, flat[at : at + n].tobytes()))
        at += -(-n // row_bytes) * row_bytes
    return out


def on_device(packed: np.ndarray, device, pack_rows: int = 1):
    """``packed`` on ``device``, zero rows appended up to the next multiple of
    ``pack_rows``."""
    import jax

    tail = -int(packed.shape[0]) % max(int(pack_rows), 1)
    if tail:
        packed = np.concatenate([packed, np.zeros((tail, packed.shape[1]), dtype=packed.dtype)])
    return jax.device_put(packed, device)
