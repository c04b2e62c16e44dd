"""Published peaks by ``device_kind``, and the least time an exchange needs.

One TPU v5e chip (Google Cloud documentation, "TPU v5e"): 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  JAX reports the chip as
``TPU v5 lite``.  A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 1600e9 / 8, "hbm_bytes": 16e9},
}


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"device kind {kind!r} is not in the peaks table {sorted(PEAKS)}")
    return PEAKS[kind]


def exchange_min_seconds(kind: str, chips: int, used_rows: int, row_bytes: int) -> float:
    """The least time the chips could take to exchange ``used_rows`` staged
    rows of ``row_bytes`` (all chips together, spread evenly, padding not
    counted).  One chip: every row is read from HBM and written back, so HBM
    bandwidth bounds it.  Several: a chip keeps 1/chips of its rows and sends
    the rest over the interconnect, which is the slower of the two and bounds
    it."""
    peaks = peaks_for(kind)
    per_chip = used_rows * row_bytes / chips
    if chips == 1:
        return 2 * per_chip / peaks["hbm_bytes_per_s"]
    return per_chip * (chips - 1) / chips / peaks["ici_bytes_per_s"]
