"""The work of a product and its share of the HBM roofline.

Bytes are the CSR minimum of each chip's share of the product, the work
itself and not what a storage format reads: 4 B per non-zero value,
4 B per int32 column id, 4 B per owned x entry and 4 B per y entry, all
float32, times the number of operand columns.  An SpMV does 2 flops per
8+ bytes, far below the v5e's ridge point, so the HBM roofline is its
roofline.
"""
from __future__ import annotations

from typing import Dict, Optional


def csr_min_bytes(share: dict, nv: int) -> int:
    return 8 * share["nnz"] + 4 * nv * (share["x_entries"] + share["rows"])


def share_pct(run, dev_seconds: Dict[int, float]) -> Optional[float]:
    """Lowest share over the cell's chips of the roofline, in %, with
    ``dev_seconds`` the time per device that the measured part took
    inside the window's applies; None where a chip has none or there is
    no peak for the device.  The devices come in rank order, the order
    of ``run.shares``."""
    t = run.trace
    n = len(t.span_walls.get("bench.apply", ()))
    if not n or run.peaks is None or len(dev_seconds) != len(run.shares):
        return None
    nv = int(run.cell.mix["nv"])
    shares = []
    for share, seconds in zip(run.shares, dev_seconds.values()):
        if seconds <= 0.0:
            return None
        ideal = csr_min_bytes(share, nv) / run.peaks["hbm_bytes_per_s"]
        shares.append(100.0 * ideal / (seconds / n))
    return min(shares)
